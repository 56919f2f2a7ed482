"""Pallas TPU kernel: QSketch-Dyn batch q_R computation.

q_R(w) = 1 - (1/m) Σ_k T[k] · exp(-w · s_k),  s_k = 2^{-(k + r_min + 1)}

is the per-element update probability (paper §4.3). For a batch of B weights
this is a (B × 2^b) dense exp + a row reduction against the histogram — small
but on the serving hot path (it runs per decoded batch). The kernel keeps the
histogram block resident in VMEM and streams weight blocks through it, fusing
exp/multiply/reduce so the (B × 2^b) intermediate never exists in HBM.

The histogram axis (2^b <= 256) lives on the lane axis padded to 128/256;
weights on sublanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dyn_array_update import lane_fold_sum

DEFAULT_BLOCK_B = 512


def _qr_kernel(w_ref, hist_ref, scales_ref, out_ref, *, m):
    w = w_ref[...]  # (B_blk, 1)
    t = hist_ref[...]  # (1, NB)
    s = scales_ref[...]  # (1, NB)
    # exp(-w * s): (B_blk, NB) lives only in VMEM/VREGs.
    expo = jnp.exp(-w * s)
    acc = lane_fold_sum(t * expo)  # (B_blk, 1), core's sum order
    out_ref[...] = 1.0 - acc / m


@functools.partial(jax.jit, static_argnames=("m", "block_b", "interpret"))
def qdyn_qr_padded(weights, hist, scales, *, m: int, block_b: int = DEFAULT_BLOCK_B, interpret: bool = False):
    """q_R per weight. weights: (B,1) f32 (B % block_b == 0); hist/scales: (1, NB)
    f32 with NB a multiple of 128 (pad with zero counts)."""
    b = weights.shape[0]
    nb = hist.shape[1]
    kernel = functools.partial(_qr_kernel, m=float(m))
    return pl.pallas_call(
        kernel,
        grid=(b // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, 1), lambda bi: (bi, 0)),
            pl.BlockSpec((1, nb), lambda bi: (0, 0)),
            pl.BlockSpec((1, nb), lambda bi: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, 1), lambda bi: (bi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(weights, hist, scales)
