"""Pallas TPU kernel: per-element pool placement of the VirtualDynArray.

The virtual tier's dense inner stage is pure per-element hashing: register
choice j = g(x), value quantization y = floor(log2 w − log2 e) (Eq. 5), and
the pool slot p = hash(tenant, j; salt_pool) mod M. None of it reads sketch
state — the randomness is regenerated in VMEM with the repo's integer hash
family (``core/hashing.py``, the same jnp ops the reference path runs, so the
kernel is bit-exact vs ``qsketch_dyn._choose_and_quantize`` +
``virtual_dyn_array.pool_slots`` by construction).

The data-dependent tail (slot-grouping lexsort, segment scatter-max, the
incremental full-histogram move) stays in XLA and is SHARED with the core
path via ``virtual_dyn_array._apply_update``; ``ops.virtual_dyn_update_op``
fuses kernel placement + core tail and is bit-identical to
``core.virtual_dyn_array.update_tenants``.

Layout: (B, 1) operand columns on sublanes (batch) with a broadcast lane,
matching the id/weight column convention of ``qsketch_update.py``. Padding
rows carry log2w = −inf: their y quantizes to the r_min no-op floor, and the
wrapper slices them off before the tail anyway.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing

DEFAULT_BLOCK_B = 512


def _pool_route_kernel(
    lo_ref, hi_ref, tlo_ref, thi_ref, log2w_ref, p_ref, y_ref,
    *, salt_g, salt_h, salt_pool, m, pool_size, r_min, r_max,
):
    lo = lo_ref[...]  # (B_blk, 1) uint32 element id words
    hi = hi_ref[...]
    t_lo = tlo_ref[...]  # (B_blk, 1) uint32 tenant id words
    t_hi = thi_ref[...]
    log2w = log2w_ref[...]  # (B_blk, 1) f32

    j = hashing.hash_mod((lo, hi), salt_g, m)
    e = hashing.neg_log_uniform((lo, hi, j.astype(jnp.uint32)), salt_h)
    y = jnp.floor(log2w - jnp.log2(e))
    y = jnp.minimum(y, float(r_max))
    y = jnp.where(jnp.isfinite(y), y, float(r_min))

    p_ref[...] = hashing.hash_mod((t_lo, t_hi, j.astype(jnp.uint32)), salt_pool, pool_size)
    y_ref[...] = y.astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=(
        "salt_g", "salt_h", "salt_pool", "m", "pool_size", "r_min", "r_max",
        "block_b", "interpret",
    ),
)
def virtual_pool_route_padded(
    lo, hi, t_lo, t_hi, log2w,
    *, salt_g: int, salt_h: int, salt_pool: int, m: int, pool_size: int,
    r_min: int, r_max: int, block_b: int = DEFAULT_BLOCK_B, interpret: bool = False,
):
    """(p, y) per element on pre-padded operands.

    lo/hi, t_lo/t_hi: (B, 1) uint32 element / tenant id words, B % block_b
    == 0; log2w: (B, 1) f32 with −inf on padding rows (y floors to r_min).
    Returns (p int32[B, 1] pool slots, y int32[B, 1] quantized values) —
    bit-exact vs the jnp reference helpers.
    """
    b = lo.shape[0]
    kernel = functools.partial(
        _pool_route_kernel,
        salt_g=salt_g, salt_h=salt_h, salt_pool=salt_pool,
        m=m, pool_size=pool_size, r_min=r_min, r_max=r_max,
    )
    return pl.pallas_call(
        kernel,
        grid=(b // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, 1), lambda bi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda bi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda bi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda bi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda bi: (bi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda bi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda bi: (bi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lo, hi, t_lo, t_hi, log2w)
