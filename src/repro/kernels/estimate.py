"""Pallas TPU kernel: fused per-row bincount + fixed-iteration MLE solve.

``estimation.estimate_rows(solver="fused")`` answers "Ĉ for every register
row" without ever materializing the ``[K, 2^b]`` histogram block in HBM: the
jnp path builds that block (1 GB at K = 2^20, b = 8) just to reduce it again.
This kernel streams ``block_k`` register rows at a time through VMEM and does
both stages on the resident tile:

  grid = (K_pad / block_k,), blocks independent ("parallel"): each step
  bincounts its (block_k × m_pad) int8 tile into a VMEM scratch histogram —
  ``window_union.bincount_rows``, masked lane reductions — then runs
  the rebased safeguarded Newton of ``estimators.qsketch_mle`` on the
  (block_k × 2^b) scratch, vectorized across the block's rows, for a FIXED
  ``_N_ITERS`` iterations (kernels cannot data-dependently early-exit a
  while_loop per lane; 30 capped 8×-per-step iterations cover the worst
  collapse trajectory to the 1e-30 floor). Only the three (block_k, 1)
  result columns ever leave the kernel.

The solve replicates ``estimators._f_and_fprime`` term-for-term on tiles
(interior / bin-0 / top-bin selected by a lane iota), including the rebase
Δ = round(mean register value) and the degenerate fallbacks, so agreement
with the ``newton`` solver is bounded only by the fixed-vs-adaptive
iteration count (tested against the float64 reference at LUT tolerance).

Built for TPU; on CPU it runs in interpret mode (Python-executed kernel
body — validation speed only, use ``solver="lut"`` there).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .window_union import bincount_rows


DEFAULT_BLOCK_K = 256
_N_ITERS = 30
_EPS_Z = 1e-4  # series-switch threshold for z = C*s (estimators._EPS_Z)


def _expm1(z):
    """exp(z) - 1 to a few ulps from exp and log alone (Kahan's form; Mosaic
    has no expm1): the rounding of u = exp(z) cancels in (u - 1) * z / ln u."""
    u = jnp.exp(z)
    d = u - 1.0
    return jnp.where(d == 0.0, z, d * z / jnp.log(u))


def _two_sinh(x):
    """2·sinh(x) without cancellation at small x (Mosaic has no sinh)."""
    return _expm1(x) - _expm1(-x)


def _estimate_kernel(
    regs_ref, chat_ref, std_ref, conv_ref, hist_ref, *, m, r_min, top_bin
):
    bincount_rows(hist_ref, regs_ref[...].astype(jnp.int32), m=m, r_min=r_min)

    t = hist_ref[...]  # (block_k, nb_pad) f32, rows sum to m
    lane = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    kval = lane.astype(jnp.float32) + float(r_min)

    # Rebase (estimators.qsketch_mle): Δ = round(mean register value).
    delta = jnp.round(jnp.sum(t * kval, axis=1, keepdims=True) / m)
    expo = jnp.clip(delta - (kval + 1.0), -126.0, 126.0)
    s = jnp.exp2(expo)

    c0 = (m - 1) / jnp.maximum(
        jnp.sum(t * s * 2.0, axis=1, keepdims=True), jnp.float32(1e-30)
    )
    c0 = jnp.clip(c0, jnp.float32(1e-20), jnp.float32(1e20))

    t0 = t[:, 0:1]
    tt = t[:, top_bin : top_bin + 1]
    degenerate = (t0 == m) | (tt == m)

    s_bot = s[:, 0:1]
    a = 2.0 * s[:, top_bin : top_bin + 1]

    def f_and_fprime(c):
        z = c * s
        zz = jnp.clip(z, _EPS_Z, 88.0)
        f_int = jnp.where(z < _EPS_Z, 1.0 / c - 0.5 * s, s / _expm1(zz)) - s
        lsh = jnp.where(
            zz > 40.0, zz / 2.0, jnp.log(_two_sinh(jnp.minimum(zz, 40.0) / 2.0))
        )
        fp_int = jnp.where(
            z < _EPS_Z, -1.0 / (c * c), -jnp.exp(2.0 * (jnp.log(s) - lsh))
        )

        za = c * a
        zza = jnp.clip(za, _EPS_Z, 88.0)
        f_top = jnp.where(za < _EPS_Z, 1.0 / c - 0.5 * a, a / _expm1(zza))
        lsha = jnp.where(
            zza > 40.0, zza / 2.0, jnp.log(_two_sinh(jnp.minimum(zza, 40.0) / 2.0))
        )
        fp_top = jnp.where(
            za < _EPS_Z, -1.0 / (c * c), -jnp.exp(2.0 * (jnp.log(a) - lsha))
        )

        f_terms = jnp.where(lane == 0, -s_bot, jnp.where(lane == top_bin, f_top, f_int))
        fp_terms = jnp.where(
            lane == 0, jnp.float32(0.0), jnp.where(lane == top_bin, fp_top, fp_int)
        )
        f = jnp.sum(t * f_terms, axis=1, keepdims=True)
        fp = jnp.sum(t * fp_terms, axis=1, keepdims=True)
        return f, fp

    def newton_body(_, c):
        f, fp = f_and_fprime(c)
        step = f / jnp.where(jnp.abs(fp) > 0, fp, jnp.float32(-1e-30))
        c_new = jnp.clip(c - step, c / 8.0, c * 8.0)
        c_new = jnp.maximum(c_new, jnp.float32(1e-30))
        return jnp.where(degenerate, c, c_new)

    c = jax.lax.fori_loop(0, _N_ITERS, newton_body, c0)
    _, fp = f_and_fprime(c)
    std = jnp.sqrt(
        jnp.maximum(-1.0 / jnp.where(jnp.abs(fp) > 0, fp, jnp.float32(-1e-30)), 0.0)
    )
    scale_back = jnp.exp2(delta)
    chat = jnp.where(t0 == m, jnp.float32(0.0), c * scale_back)

    chat_ref[...] = chat
    std_ref[...] = std * scale_back
    conv_ref[...] = jnp.where(degenerate, 0, 1).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("m", "nb_padded", "r_min", "top_bin", "block_k", "interpret")
)
def estimate_rows_padded(
    regs,
    *,
    m: int,
    nb_padded: int,
    r_min: int,
    top_bin: int,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
):
    """Kernel entry on pre-padded operands.

    regs: (K_pad, m_pad) int8, K_pad % block_k == 0, m_pad % 128 == 0, pad
      rows/lanes at r_min (padded lanes are excluded from the bincount by an
      iota mask; padded rows solve to the degenerate 0 and are sliced off by
      the wrapper).
    Returns (chat (K_pad, 1) f32, stddev (K_pad, 1) f32, conv (K_pad, 1)
    int32) — the unscaled per-row MLE triple; ``ops.estimate_rows_op``
    applies the kind convention.
    """
    kp, mp = regs.shape
    kernel = functools.partial(_estimate_kernel, m=m, r_min=r_min, top_bin=top_bin)
    return pl.pallas_call(
        kernel,
        grid=(kp // block_k,),
        in_specs=[pl.BlockSpec((block_k, mp), lambda ki: (ki, 0))],
        out_specs=[
            pl.BlockSpec((block_k, 1), lambda ki: (ki, 0)),
            pl.BlockSpec((block_k, 1), lambda ki: (ki, 0)),
            pl.BlockSpec((block_k, 1), lambda ki: (ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, nb_padded), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(regs)
