"""Pallas TPU kernel: fused epoch-union + per-row register bincount.

The windowed read ``window_array.estimate_window(w)`` needs, per tenant row,
the FULL value histogram of the max-union of the last w epoch register
planes. The pure-JAX path gathers ``regs[idx]`` — an HBM-resident
``[w, K, m]`` intermediate — before reducing. This kernel streams the epoch
planes through VMEM instead:

  grid = (k_block, E), epochs innermost ("arbitrary"): the (K_blk × m) union
  accumulator lives in an int32 VMEM scratch tile across the epoch sweep;
  each epoch contributes ``max`` if its per-epoch include flag (an SMEM
  scalar computed from ``head`` and w by the wrapper) selects it, else
  r_min. On the LAST epoch step the resident union tile is bincounted into
  the output (``bincount_rows``: masked lane-reductions, stored 128 bins at
  a time), so neither the ``[w, K, m]`` gather nor the union itself ever
  reaches HBM.

Bin semantics: the histogram is FULL (bin 0 counts r_min = untouched
registers among the REAL m lanes; padded lanes are excluded by an iota mask),
rows sum to m — exactly ``estimators.histogram`` of the union row, which is
what the vmapped MLE consumes. Padded bins beyond 2^b count values no int8
register can hold and come out exactly 0.

Layout: registers on the lane axis (m padded to 128), tenant rows on
sublanes (K padded to the block), epoch include flags as (E,) int32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_K = 256


def bincount_rows(hist_ref, u, *, m, r_min):
    """Per-row value histogram of ``u`` (block_k, m_pad) int32 into
    ``hist_ref`` (block_k, nb_padded): bin v counts lanes < m equal to
    v + r_min.

    Bins are filled one 128-lane group at a time — a fori_loop of masked
    lane reductions selected into the group's register tile — and each
    group is stored at a static, lane-aligned offset: Mosaic has no
    width-1 store at a dynamic lane offset.
    """
    lane_valid = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1) < m
    block_k, nb_padded = hist_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_k, 128), 1)
    for g in range(nb_padded // 128):

        def bin_body(v, acc, g=g):
            cnt = jnp.sum(
                jnp.where(lane_valid & (u == g * 128 + v + r_min), 1, 0),
                axis=1,
                keepdims=True,
            )
            return jnp.where(lane == v, cnt, acc)

        acc = jax.lax.fori_loop(0, 128, bin_body, jnp.zeros((block_k, 128), jnp.int32))
        hist_ref[:, g * 128 : (g + 1) * 128] = acc.astype(hist_ref.dtype)


def _window_union_kernel(inc_ref, regs_ref, hist_ref, union_ref, *, n_epochs, m, r_min):
    ei = pl.program_id(1)  # epoch step (innermost)
    inc = inc_ref[ei]  # 1 if this epoch is inside the window (SMEM scalar)
    # Widened per block (the v5e VPU has no int8 max); HBM stays int8.
    plane = regs_ref[0].astype(jnp.int32)  # (K_blk, m_pad), this epoch
    contrib = jnp.where(inc > 0, plane, r_min)

    @pl.when(ei == 0)
    def _init():
        union_ref[...] = contrib

    @pl.when(ei > 0)
    def _accum():
        union_ref[...] = jnp.maximum(union_ref[...], contrib)

    @pl.when(ei == n_epochs - 1)
    def _bincount():
        bincount_rows(hist_ref, union_ref[...], m=m, r_min=r_min)


@functools.partial(
    jax.jit, static_argnames=("m", "nb_padded", "r_min", "block_k", "interpret")
)
def window_union_padded(
    regs,
    include,
    *,
    m: int,
    nb_padded: int,
    r_min: int,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
):
    """Kernel entry on pre-padded operands.

    regs: (E, K_pad, m_pad) int8, K_pad % block_k == 0, m_pad % 128 == 0,
      pad rows/lanes at r_min. int8 end to end: the ring is streamed at its
      native register width (the only HBM intermediate the wrapper creates
      is the padded int8 copy, and none when K and m are already aligned).
    include: (E,) int32 — 1 for epochs inside the window, 0 outside; held
      whole in SMEM and read as a scalar per epoch step.
    Returns hist (K_pad, nb_padded) int32, the full per-row histogram of
    the window's union over the real m lanes only (the union itself lives
    in a VMEM scratch tile and never reaches HBM).
    """
    e, kp, mp = regs.shape
    kernel = functools.partial(_window_union_kernel, n_epochs=e, m=m, r_min=r_min)
    return pl.pallas_call(
        kernel,
        grid=(kp // block_k, e),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_k, mp), lambda ki, ei: (ei, ki, 0)),
        ],
        out_specs=pl.BlockSpec((block_k, nb_padded), lambda ki, ei: (ki, 0)),
        out_shape=jax.ShapeDtypeStruct((kp, nb_padded), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_k, mp), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(include, regs)
