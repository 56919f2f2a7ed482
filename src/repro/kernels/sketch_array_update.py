"""Pallas TPU kernel: keyed multi-sketch (SketchArray) register update.

Same hot loop as kernels/qsketch_update.py — regenerate the hash bits for a
(B_blk × M_blk) tile in VMEM, quantize y = floor(log2 w - log2(-ln u)) — but
instead of max-reducing the batch axis into ONE register row, each batch row
is routed to register row ``keys[i]`` of the resident (K × M_blk) output
block:

  grid = (m_block, batch_block), batch innermost ("arbitrary"): the FULL
  K-row register slab for this m_block stays in VMEM while every batch block
  streams through it. Routing is a fori_loop of dynamic-row scatter-maxes —
  max is commutative/associative, so the sequential loop is bit-identical to
  the core's segment scatter (and to K independent single-sketch updates).

Layout: registers on the 128-wide lane axis (M_blk multiple of 128), sketch
rows K on the sublane axis (padded to a multiple of 8), batch ids/weights/keys
as (B, 1) columns. The VMEM budget is the y tile (B_blk × M_blk f32) plus the
(K_pad × M_blk) int32 slab — the ops.py wrapper shrinks M_blk as K grows to
stay inside ~6 MiB.

Padding contracts (enforced by ops.py): padding batch rows carry
log2w = -inf (y clips to r_min -> scatter is a no-op on whatever row their
key routes to) and key 0; padded register rows/cols are sliced off after.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .qsketch_update import _tile_y

# Smaller default batch tile than the single-sketch kernel: the register slab
# (K_pad x M_blk) shares VMEM with the y tile.
DEFAULT_BLOCK_B = 128
DEFAULT_BLOCK_M = 512


def _sketch_array_kernel(
    ids_lo_ref, ids_hi_ref, log2w_ref, keys_ref, regs_ref, out_ref, *, block_b, block_m, salt, r_min, r_max
):
    bi = pl.program_id(1)  # batch-block index (innermost)
    mi = pl.program_id(0)  # register-block index

    @pl.when(bi == 0)
    def _init():
        out_ref[...] = regs_ref[...]

    j0 = (mi * block_m).astype(jnp.uint32)
    y = _tile_y(
        ids_lo_ref[...], ids_hi_ref[...], log2w_ref[...], j0, block_m, salt, r_min, r_max
    )
    keys = keys_ref[...]  # (B_blk, 1) int32

    def route(i, _):
        k = jax.lax.dynamic_slice(keys, (i, 0), (1, 1))[0, 0]
        y_row = jax.lax.dynamic_slice(y, (i, 0), (1, block_m))
        out_ref[pl.ds(k, 1), :] = jnp.maximum(out_ref[pl.ds(k, 1), :], y_row)
        return _

    jax.lax.fori_loop(0, block_b, route, None)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_m", "salt", "r_min", "r_max", "interpret")
)
def sketch_array_update_padded(
    ids_lo,
    ids_hi,
    log2w,
    keys,
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
      log2w = -inf and key 0.
    log2w: (B, 1) float32.
    keys: (B, 1) int32 in [0, K) — every key must be a valid row of ``regs``.
    regs: (K, M) int32, M % block_m == 0, K a sublane multiple.
    Returns updated (K, M) int32 registers.
    """
    b = ids_lo.shape[0]
    k, m = regs.shape
    grid = (m // block_m, b // block_b)

    kernel = functools.partial(
        _sketch_array_kernel,
        block_b=block_b,
        block_m=block_m,
        salt=salt,
        r_min=r_min,
        r_max=r_max,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, 1), lambda mi, bi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda mi, bi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda mi, bi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda mi, bi: (bi, 0)),
            pl.BlockSpec((k, block_m), lambda mi, bi: (0, mi)),
        ],
        out_specs=pl.BlockSpec((k, block_m), lambda mi, bi: (0, mi)),
        out_shape=jax.ShapeDtypeStruct((k, m), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(ids_lo, ids_hi, log2w, keys, regs)
