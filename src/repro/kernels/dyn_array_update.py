"""Pallas TPU kernel: keyed QSketch-Dyn batch q_R against gathered histograms.

The DynArray update's dense inner stage is the per-element update
probability

    q_i = 1 - (1/m) Σ_k T[key_i, k] · exp(-w_i · s_k),  s_k = 2^{-(k+r_min+1)}

— the keyed generalization of ``kernels/qdyn_qr.py``: instead of ONE
histogram broadcast against every weight, each element brings its own key's
batch-start histogram row. The caller gathers ``hists[keys]`` (an XLA gather
HBM->HBM); the kernel streams (B_blk × NB) row-tiles through VMEM fused with
the exp/multiply/reduce, so the (B × 2^b) f32 intermediate product never
exists in HBM. At serving batch sizes this runs per decoded batch for every
tenant-keyed stream — the DynArray hot path.

The remaining update stages (dedup lexsort, segment scatter-max, incremental
histogram moves) are data-dependent scatters that stay in XLA
(``core/dyn_array._apply_update``); ``ops.dyn_array_update_op`` fuses kernel
q_R + core tail and is bit-identical to ``core.dyn_array.update_batch``.

Layout: histogram bins (NB = 2^b <= 256) on the lane axis padded to a
128-multiple (zero-count pad bins contribute exact 0.0 to the sum); batch on
sublanes. Padding batch rows carry w = 1 against a zero histogram row
(q = 1) and are sliced off by the wrapper. The bin sum follows
``qsketch_dyn.fold_sum``'s order (``lane_fold_sum``), so q_R — and the
chats built from it — match the jnp route bit for bit on the TPU too, where
Mosaic's and XLA's own reductions associate differently.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_B = 512


def lane_fold_sum(x):
    """Row sums of ``x`` (rows, NB), NB a multiple of 128, in
    ``qsketch_dyn.fold_sum`` order: 128-lane groups fold pairwise, then a
    lane butterfly (lane i += lane i + s, s = 64 .. 1) leaves that order's
    sum in lane 0. Returns (rows, 1). Zero pad lanes beyond the 2^b real
    bins add exact zeros, so the sum equals the unpadded fold."""
    while x.shape[1] > 128:
        h = x.shape[1] // 2
        x = x[:, :h] + x[:, h:]
    for s in (64, 32, 16, 8, 4, 2, 1):
        x = x + pltpu.roll(x, 128 - s, 1)
    return x[:, 0:1]


def _keyed_qr_kernel(w_ref, hist_rows_ref, scales_ref, out_ref, *, m):
    w = w_ref[...]  # (B_blk, 1)
    t = hist_rows_ref[...]  # (B_blk, NB) — this block's gathered rows
    s = scales_ref[...]  # (1, NB)
    expo = jnp.exp(-w * s)  # (B_blk, NB) lives only in VMEM/VREGs
    acc = lane_fold_sum(t * expo)  # (B_blk, 1), the jnp route's sum order
    out_ref[...] = 1.0 - acc / m


@functools.partial(jax.jit, static_argnames=("m", "block_b", "interpret"))
def dyn_array_qr_padded(
    weights, hist_rows, scales, *, m: int, block_b: int = DEFAULT_BLOCK_B, interpret: bool = False
):
    """q_R per element. weights: (B, 1) f32, B % block_b == 0; hist_rows:
    (B, NB) f32 — row i is element i's key's histogram — with NB a multiple
    of 128 (zero-count pad bins); scales: (1, NB) f32."""
    b = weights.shape[0]
    nb = hist_rows.shape[1]
    kernel = functools.partial(_keyed_qr_kernel, m=float(m))
    return pl.pallas_call(
        kernel,
        grid=(b // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, 1), lambda bi: (bi, 0)),
            pl.BlockSpec((block_b, nb), lambda bi: (bi, 0)),
            pl.BlockSpec((1, nb), lambda bi: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, 1), lambda bi: (bi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(weights, hist_rows, scales)
