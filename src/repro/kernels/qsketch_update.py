"""Pallas TPU kernel: batched QSketch register update (the paper's hot loop).

The paper's Alg. 2 spends its time generating m exponential variables per
element and folding them into m registers. On TPU the natural schedule is a
2-D grid over (register blocks × batch blocks): each kernel invocation

  1. regenerates the hash bits for its (B_blk × M_blk) tile *in VMEM* with
     pure integer VPU ops (no HBM traffic for the randomness — this is the
     fusion win over a materialize-then-reduce XLA schedule),
  2. quantizes y = floor(log2 w - log2(-ln u)) (Eq. 5),
  3. max-reduces over the batch rows, and
  4. accumulates into the output register block across the batch grid axis.

Layout: registers live on the 128-wide lane axis (M_blk a multiple of 128),
batch on the 8-deep sublane axis (B_blk a multiple of 8). The (B,1)-shaped
id/weight columns broadcast along lanes. Registers are int32 in-kernel
(int8 packing happens at the state boundary in ops.py; VMEM cost of the
register block is negligible next to the generation tile).

Grid iteration order is (m_block, batch_block) with the batch axis innermost
("arbitrary" semantics): the output block for a given m_block stays resident
in VMEM while all batch blocks stream through it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing

# Default tile: 256 x 512 f32 intermediate = 512 KiB VMEM, well under budget.
DEFAULT_BLOCK_B = 256
DEFAULT_BLOCK_M = 512


def _tile_y(ids_lo, ids_hi, log2w, j0, block_m, salt, r_min, r_max):
    """Quantized values y' for a (B_blk, M_blk) tile; shared by both kernels."""
    bb = ids_lo.shape[0]
    j = jax.lax.broadcasted_iota(jnp.uint32, (bb, block_m), 1) + j0
    e = hashing.neg_log_uniform((ids_lo, ids_hi, j), salt)
    y = jnp.floor(log2w - jnp.log2(e))
    return jnp.clip(y, float(r_min), float(r_max)).astype(jnp.int32)


def _qsketch_kernel(ids_lo_ref, ids_hi_ref, log2w_ref, regs_ref, out_ref, *, block_m, salt, r_min, r_max, nbatch):
    bi = pl.program_id(1)  # batch-block index (innermost)
    mi = pl.program_id(0)  # register-block index

    j0 = (mi * block_m).astype(jnp.uint32)
    y = _tile_y(
        ids_lo_ref[...], ids_hi_ref[...], log2w_ref[...], j0, block_m, salt, r_min, r_max
    )
    tile_max = jnp.max(y, axis=0, keepdims=True)  # (1, M_blk)

    @pl.when(bi == 0)
    def _init():
        out_ref[...] = jnp.maximum(regs_ref[...], tile_max)

    @pl.when(bi > 0)
    def _accum():
        out_ref[...] = jnp.maximum(out_ref[...], tile_max)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_m", "salt", "r_min", "r_max", "interpret")
)
def qsketch_update_padded(
    ids_lo,
    ids_hi,
    log2w,
    regs,
    *,
    block_b: int = DEFAULT_BLOCK_B,
    block_m: int = DEFAULT_BLOCK_M,
    salt: int,
    r_min: int,
    r_max: int,
    interpret: bool = False,
):
    """Kernel entry on pre-padded operands.

    ids_lo/ids_hi: (B, 1) uint32, B % block_b == 0. Padding rows must carry
      log2w = -inf (their y clips to r_min -> no-ops under max).
    log2w: (B, 1) float32.
    regs: (1, M) int32, M % block_m == 0.
    Returns updated (1, M) int32 registers.
    """
    b = ids_lo.shape[0]
    m = regs.shape[1]
    grid = (m // block_m, b // block_b)

    kernel = functools.partial(
        _qsketch_kernel,
        block_m=block_m,
        salt=salt,
        r_min=r_min,
        r_max=r_max,
        nbatch=b // block_b,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, 1), lambda mi, bi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda mi, bi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda mi, bi: (bi, 0)),
            pl.BlockSpec((1, block_m), lambda mi, bi: (0, mi)),
        ],
        out_specs=pl.BlockSpec((1, block_m), lambda mi, bi: (0, mi)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(ids_lo, ids_hi, log2w, regs)


def _float_kernel(ids_lo_ref, ids_hi_ref, w_ref, regs_ref, out_ref, *, block_m, salt, big):
    """LM-family float min-sketch tile: r = -ln(u)/w, min-accumulate.

    Padding rows are flagged with w <= 0 and masked to +big (an e/w division
    rather than e * (1/w) keeps the rounding bit-identical to the jnp core).
    """
    bi = pl.program_id(1)
    mi = pl.program_id(0)
    bb = ids_lo_ref.shape[0]

    j0 = (mi * block_m).astype(jnp.uint32)
    j = jax.lax.broadcasted_iota(jnp.uint32, (bb, block_m), 1) + j0
    e = hashing.neg_log_uniform((ids_lo_ref[...], ids_hi_ref[...], j), salt)
    w = w_ref[...]
    r = jnp.where(w > 0, e / w, big)
    tile_min = jnp.min(r, axis=0, keepdims=True)

    @pl.when(bi == 0)
    def _init():
        out_ref[...] = jnp.minimum(regs_ref[...], tile_min)

    @pl.when(bi > 0)
    def _accum():
        out_ref[...] = jnp.minimum(out_ref[...], tile_min)


@functools.partial(jax.jit, static_argnames=("block_b", "block_m", "salt", "interpret"))
def float_sketch_update_padded(
    ids_lo,
    ids_hi,
    w,
    regs,
    *,
    block_b: int = DEFAULT_BLOCK_B,
    block_m: int = DEFAULT_BLOCK_M,
    salt: int,
    interpret: bool = False,
):
    """LM/FastGM-family fused update (min semantics, float32 registers)."""
    b = ids_lo.shape[0]
    m = regs.shape[1]
    grid = (m // block_m, b // block_b)
    kernel = functools.partial(_float_kernel, block_m=block_m, salt=salt, big=jnp.finfo(jnp.float32).max)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, 1), lambda mi, bi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda mi, bi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda mi, bi: (bi, 0)),
            pl.BlockSpec((1, block_m), lambda mi, bi: (0, mi)),
        ],
        out_specs=pl.BlockSpec((1, block_m), lambda mi, bi: (0, mi)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(ids_lo, ids_hi, w, regs)
