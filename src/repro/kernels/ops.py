"""User-facing jit'd wrappers around the Pallas sketch kernels.

These adapt (SketchConfig, sketch-state, raw id/weight batches) to the padded
2-D operand layout the kernels want, pick interpret mode automatically off
the backend (interpret=True executes the kernel body in Python on CPU — the
validation mode this container uses; on TPU the same code lowers to Mosaic),
and convert between the int8 register state and the kernel's int32 blocks.

Padding contracts:
  * batch rows are padded to a block multiple with log2w = -inf (QSketch) or
    w = -1 (float sketches mask non-positive w): padded rows are no-ops.
  * registers are padded to a block multiple; padded registers evolve
    independently and are sliced off — they never alias real ones because
    each register consumes its own hash lane.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    dyn_array,
    hashing,
    key_directory,
    qsketch_dyn,
    sharding,
    window_array,
)
from repro.core.types import (
    DynArrayState,
    FloatSketchState,
    QSketchState,
    ShardedDynArrayState,
    ShardedWindowArrayState,
    SketchArrayState,
    SketchConfig,
    WindowArrayState,
)
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

from . import (
    dyn_array_update,
    estimate,
    qdyn_qr,
    qsketch_update,
    sketch_array_update,
    virtual_pool_update,
    window_union,
)

_NEG_INF = float(np.finfo(np.float32).min)
# VMEM the keyed SketchArray kernel may hold resident (slabs + y tile).
_SKETCH_ARRAY_VMEM_BUDGET = 6 * 2**20
_POS_INF = float(np.finfo(np.float32).max)

_M_KERNEL_TRACES = obs_metrics.counter(
    "kernel_trace_total",
    help="op-wrapper executions under an active jax trace, per op — growth "
         "at steady state means shape churn is forcing retraces",
    labels=("op",),
)


def _note_trace(op: str) -> None:
    """Count one trace-time execution of an op wrapper (retrace telemetry).

    The wrapper body only re-runs when jit (re)traces, so at steady state
    the per-op counter is flat; a rising count is the recompilation signal
    (shape churn defeating the lru_cache'd executables). Host-side int
    mutation during tracing captures no tracer, so the jitted computation
    is untouched.
    """
    if obs_metrics.enabled() and obs_trace.tracing_active():
        _M_KERNEL_TRACES.labels(op=op).inc()


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _pick_blocks(b: int, m: int, block_b, block_m):
    """Clamp default blocks to the (padded) problem size."""
    bb = block_b or min(qsketch_update.DEFAULT_BLOCK_B, _round_up(b, 8))
    bm = block_m or min(qsketch_update.DEFAULT_BLOCK_M, _round_up(m, 128))
    return bb, bm


def _pad_batch(arrs, b_padded, fill_values):
    out = []
    for a, fill in zip(arrs, fill_values):
        pad = b_padded - a.shape[0]
        out.append(jnp.pad(a, ((0, pad),), constant_values=fill)[:, None])
    return out


def qsketch_update_op(
    cfg: SketchConfig,
    state: QSketchState,
    ids,
    weights,
    *,
    block_b: int | None = None,
    block_m: int | None = None,
    interpret: bool | None = None,
) -> QSketchState:
    """Kernel-backed equivalent of ``core.qsketch.update`` (bit-identical)."""
    _note_trace("qsketch_update")
    interpret = _interpret_default() if interpret is None else interpret
    lo, hi = hashing.split_id64(ids)
    b = lo.shape[0]
    bb, bm = _pick_blocks(b, cfg.m, block_b, block_m)
    bp, mp = _round_up(b, bb), _round_up(cfg.m, bm)

    log2w = jnp.log2(weights.astype(jnp.float32))
    lo2, hi2, lw2 = _pad_batch([lo, hi, log2w], bp, [0, 0, _NEG_INF])
    regs = jnp.pad(
        state.regs.astype(jnp.int32), ((0, mp - cfg.m),), constant_values=cfg.r_min
    )[None, :]

    out = qsketch_update.qsketch_update_padded(
        lo2,
        hi2,
        lw2,
        regs,
        block_b=bb,
        block_m=bm,
        salt=cfg.salt_h,
        r_min=cfg.r_min,
        r_max=cfg.r_max,
        interpret=interpret,
    )
    return QSketchState(regs=out[0, : cfg.m].astype(jnp.int8))


def sketch_array_update_op(
    cfg: SketchConfig,
    state: SketchArrayState,
    keys,
    ids,
    weights,
    mask=None,
    *,
    block_b: int | None = None,
    block_m: int | None = None,
    interpret: bool | None = None,
) -> SketchArrayState:
    """Kernel-backed equivalent of ``core.sketch_array.update`` (bit-identical).

    ``keys`` follows the *slot* contract: dense int[B] in [0, K), i.e. the
    output of ``core.key_directory.route`` (sparse 64-bit tenant streams go
    through ``sketch_array_update_tenants_op`` below).

    ``mask`` is folded into log2w (masked rows -> -inf -> y = r_min), which is
    exactly the core's post-clip masking, so bit-identity is preserved.
    The register slab (K_pad x block_m, int32) must sit in VMEM next to the
    y tile; block_m is halved until the slab fits a 6 MiB budget, and a K
    whose slab does not fit even at block_m = 128 raises ValueError.
    """
    _note_trace("sketch_array_update")
    interpret = _interpret_default() if interpret is None else interpret
    k = state.regs.shape[0]
    lo, hi = hashing.split_id64(ids)
    b = lo.shape[0]

    bb = block_b or min(sketch_array_update.DEFAULT_BLOCK_B, _round_up(b, 8))
    bm = block_m or min(sketch_array_update.DEFAULT_BLOCK_M, _round_up(cfg.m, 128))
    kp = _round_up(k, 8)
    # Residency = regs_ref + out_ref slabs (int32 each) + the y tile.
    resident = lambda bm: (2 * kp + bb) * bm * 4
    if block_m is None:
        # Halve in 128-aligned steps: M_blk must stay a lane-tile multiple.
        while resident(bm) > _SKETCH_ARRAY_VMEM_BUDGET and bm > 128:
            bm = max(128, (bm // 2) // 128 * 128)
    if resident(bm) > _SKETCH_ARRAY_VMEM_BUDGET:
        raise ValueError(
            f"sketch_array_update_op: K={k} rows need {resident(bm)} B of VMEM "
            f"for the register slab at block_m={bm}, over the "
            f"{_SKETCH_ARRAY_VMEM_BUDGET} B budget (K <= "
            f"{(_SKETCH_ARRAY_VMEM_BUDGET // (128 * 4) - bb) // 2} fits); use "
            "core.sketch_array.update for larger K"
        )
    bp, mp = _round_up(b, bb), _round_up(cfg.m, bm)

    log2w = jnp.log2(weights.astype(jnp.float32))
    if mask is not None:
        log2w = jnp.where(mask, log2w, _NEG_INF)
    keys = jnp.clip(keys.astype(jnp.int32), 0, k - 1)
    lo2, hi2, lw2, keys2 = _pad_batch([lo, hi, log2w, keys], bp, [0, 0, _NEG_INF, 0])
    regs = jnp.pad(
        state.regs.astype(jnp.int32),
        ((0, kp - k), (0, mp - cfg.m)),
        constant_values=cfg.r_min,
    )

    out = sketch_array_update.sketch_array_update_padded(
        lo2,
        hi2,
        lw2,
        keys2,
        regs,
        block_b=bb,
        block_m=bm,
        salt=cfg.salt_h,
        r_min=cfg.r_min,
        r_max=cfg.r_max,
        interpret=interpret,
    )
    return SketchArrayState(regs=out[:k, : cfg.m].astype(jnp.int8))


def sketch_array_update_tenants_op(
    cfg: SketchConfig,
    dcfg: key_directory.DirectoryConfig,
    state: SketchArrayState,
    dir_state: key_directory.DirectoryState,
    tenant_keys,
    ids,
    weights,
    mask=None,
    **kernel_kwargs,
):
    """Sparse-tenant front of ``sketch_array_update_op``.

    Routes 64-bit tenant ids (uint32 array or pre-split (lo, hi) pair)
    through the key directory — collision telemetry included — then runs the
    Pallas-backed keyed update on the resulting slots. Returns
    (SketchArrayState, DirectoryState).
    """
    if dcfg.capacity != state.regs.shape[0]:
        raise ValueError(
            f"directory capacity {dcfg.capacity} != SketchArray rows {state.regs.shape[0]}"
        )
    slots, dir_state = key_directory.route(dcfg, dir_state, tenant_keys, mask=mask)
    out = sketch_array_update_op(cfg, state, slots, ids, weights, mask=mask, **kernel_kwargs)
    return out, dir_state


def dyn_array_update_op(
    cfg: SketchConfig,
    state: DynArrayState,
    keys,
    ids,
    weights,
    mask=None,
    *,
    block_b: int | None = None,
    interpret: bool | None = None,
    donate: bool = False,
) -> DynArrayState:
    """Kernel-backed equivalent of ``core.dyn_array.update_batch`` (bit-identical).

    The dense inner stage — per-element q_R against the element's key's
    batch-start histogram — runs in the Pallas kernel
    (``kernels/dyn_array_update.py``) on gathered rows; the data-dependent
    tail (dedup lexsort, segment scatter-max, incremental histogram moves)
    is shared with the core path via ``dyn_array._apply_update``, so the two
    entries agree bitwise on every state field.

    ``keys`` follows the slot contract (dense int[B], clipped to [0, K));
    sparse 64-bit tenant streams go through ``dyn_array_update_tenants_op``.
    Padding batch rows carry w = 1 against a zero histogram row (q = 1) and
    are sliced off before the tail.

    ``donate=True`` runs the whole op under one jit with the state donated,
    so the scatter tail reuses the state buffers in place instead of copying
    the [K, m] + [K, 2^b] block per batch — the steady-state ingest mode
    (the non-donating call stays un-jitted at top level: its Pallas stage
    compiles per shape and the tail dispatches eagerly, the validation
    configuration the bit-identity tests run). The caller's ``state`` is
    dead after a donating call (``dyn_array.update_batch`` has the full
    donation contract).
    """
    interpret = _interpret_default() if interpret is None else interpret
    if donate:
        return _dyn_array_update_donated(cfg, block_b, interpret)(
            state, keys, ids, weights, mask
        )
    return _dyn_array_update_body(
        cfg, state, keys, ids, weights, mask, block_b=block_b, interpret=interpret
    )


@functools.lru_cache(maxsize=32)
def _dyn_array_update_donated(cfg: SketchConfig, block_b, interpret: bool):
    """Jitted, state-donating closure of ``_dyn_array_update_body`` — one
    cache entry per (cfg, block_b, interpret) so repeated ingest batches hit
    the same executable (and its input-output buffer aliasing)."""

    def fn(state, keys, ids, weights, mask):
        return _dyn_array_update_body(
            cfg, state, keys, ids, weights, mask,
            block_b=block_b, interpret=interpret,
        )

    return jax.jit(fn, donate_argnums=(0,))


def _dyn_array_update_body(
    cfg: SketchConfig, state: DynArrayState, keys, ids, weights, mask,
    *, block_b, interpret,
) -> DynArrayState:
    from repro.core import estimators

    _note_trace("dyn_array_update")
    k = state.regs.shape[0]
    lo, hi = hashing.split_id64(ids)
    w = weights.astype(jnp.float32)
    keys = jnp.clip(keys.astype(jnp.int32), 0, k - 1)
    live = qsketch_dyn._live_weight_mask(w, mask)

    b = lo.shape[0]
    bb = block_b or min(dyn_array_update.DEFAULT_BLOCK_B, _round_up(b, 8))
    bp = _round_up(b, bb)
    nbp = _round_up(cfg.num_bins, 128)

    scales = jnp.pad(
        jnp.asarray(estimators._bin_scales(cfg)), ((0, nbp - cfg.num_bins),)
    )[None, :]
    rows = jnp.pad(
        state.hists[keys].astype(jnp.float32),
        ((0, bp - b), (0, nbp - cfg.num_bins)),
    )
    w2 = jnp.pad(w, ((0, bp - b),), constant_values=1.0)[:, None]

    q = dyn_array_update.dyn_array_qr_padded(
        w2, rows, scales, m=cfg.m, block_b=bb, interpret=interpret
    )
    q = jnp.maximum(q[:b, 0], qsketch_dyn._QR_FLOOR)
    return dyn_array._apply_update(cfg, state, keys, lo, hi, w, live, q)


def dyn_array_update_tenants_op(
    cfg: SketchConfig,
    dcfg: key_directory.DirectoryConfig,
    state: DynArrayState,
    dir_state: key_directory.DirectoryState,
    tenant_keys,
    ids,
    weights,
    mask=None,
    **kernel_kwargs,
):
    """Sparse-tenant front of ``dyn_array_update_op`` (key-directory routing,
    collision telemetry included). Returns (DynArrayState, DirectoryState).
    """
    if dcfg.capacity != state.regs.shape[0]:
        raise ValueError(
            f"directory capacity {dcfg.capacity} != DynArray rows {state.regs.shape[0]}"
        )
    slots, dir_state = key_directory.route(dcfg, dir_state, tenant_keys, mask=mask)
    out = dyn_array_update_op(cfg, state, slots, ids, weights, mask=mask, **kernel_kwargs)
    return out, dir_state


def virtual_dyn_update_op(
    cfg: SketchConfig,
    vcfg,
    state,
    tenant_keys,
    ids,
    weights,
    mask=None,
    *,
    block_b: int | None = None,
    interpret: bool | None = None,
):
    """Kernel-backed equivalent of ``core.virtual_dyn_array.update_tenants``
    (bit-identical on every state field).

    The dense inner stage — per-element register choice, value quantization,
    and pool-slot placement — runs in the Pallas kernel
    (``kernels/virtual_pool_update.py``), regenerating the hash bits in VMEM
    with the same integer family as the jnp reference; the data-dependent
    tail (hot/tail routing split, dense-row update, slot-grouped scatter-max
    and the incremental full-histogram move) is shared with the core path
    via ``virtual_dyn_array._apply_update``, so the two entries agree
    bitwise. Padding rows carry log2w = −inf (y floors to the r_min no-op)
    and are sliced off before the tail.
    """
    from repro.core import virtual_dyn_array

    _note_trace("virtual_dyn_update")
    interpret = _interpret_default() if interpret is None else interpret
    t_lo, t_hi = hashing.split_id64(tenant_keys)
    lo, hi = hashing.split_id64(ids)
    w = weights.astype(jnp.float32)
    live = qsketch_dyn._live_weight_mask(w, mask)
    log2w = jnp.log2(w)

    b = lo.shape[0]
    bb = block_b or min(virtual_pool_update.DEFAULT_BLOCK_B, _round_up(b, 8))
    bp = _round_up(b, bb)
    lo2, hi2, tlo2, thi2, lw2 = _pad_batch(
        [lo, hi, t_lo, t_hi, log2w], bp, [0, 0, 0, 0, _NEG_INF]
    )

    # Tail geometry: register choice modulus is the VIRTUAL row width m_v
    # (free registers — the vHLL decoupling); the b-derived quantization
    # range and the seed-derived salts are shared with the dense cfg.
    p, y = virtual_pool_update.virtual_pool_route_padded(
        lo2, hi2, tlo2, thi2, lw2,
        salt_g=cfg.salt_g, salt_h=cfg.salt_h, salt_pool=vcfg.salt_pool,
        m=virtual_dyn_array.tail_m(cfg, vcfg), pool_size=vcfg.pool_size,
        r_min=cfg.r_min, r_max=cfg.r_max,
        block_b=bb, interpret=interpret,
    )
    return virtual_dyn_array._apply_update(
        cfg, vcfg, state, t_lo, t_hi, lo, hi, w, live, p[:b, 0], y[:b, 0]
    )


def window_union_estimate_op(
    cfg: SketchConfig,
    state: WindowArrayState,
    w: int,
    *,
    solver: str = "newton",
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Kernel-backed equivalent of ``window_array.estimate_window`` — Ĉ[K]
    over the last w <= E epochs, bit-identical to the pure-JAX union path.

    The union-of-epochs + per-row bincount runs in the Pallas kernel
    (``kernels/window_union.py``) streaming the ring's int8 epoch planes
    through VMEM, so the ``[w, K, m]`` gather the jnp path materializes never
    exists (the ring is read in place at native register width; padding only
    copies when K or m are tile-unaligned); the vmapped histogram MLE then
    runs on the exact same integer histograms, making the two entries agree
    bitwise. Epochs outside the window are masked by an include flag computed
    from the ring head, so the (traced) ``head`` never forces a host sync.
    """
    _note_trace("window_union_estimate")
    interpret = _interpret_default() if interpret is None else interpret
    e, k, m = state.regs.shape
    w = window_array._check_w(state, w)

    bk = block_k or min(window_union.DEFAULT_BLOCK_K, _round_up(k, 8))
    kp, mp = _round_up(k, bk), _round_up(m, 128)
    nbp = _round_up(cfg.num_bins, 128)

    regs = jnp.pad(
        state.regs,
        ((0, 0), (0, kp - k), (0, mp - m)),
        constant_values=cfg.r_min,
    )
    # Epoch slot ei is inside the window iff its age (head - ei) mod E < w.
    age = (state.head - jnp.arange(e, dtype=jnp.int32)) % e
    include = (age < w).astype(jnp.int32)

    hists = window_union.window_union_padded(
        regs,
        include,
        m=m,
        nb_padded=nbp,
        r_min=cfg.r_min,
        block_k=bk,
        interpret=interpret,
    )
    return dyn_array.estimate_mle_hists(cfg, hists[:k, : cfg.num_bins], solver=solver)


def estimate_rows_op(
    cfg: SketchConfig,
    regs,
    *,
    kind: str = "routed",
    block_k: int | None = None,
    interpret: bool | None = None,
):
    """Kernel-backed fused bincount + MLE over register rows — the
    ``solver="fused"`` backend of ``core.estimation.estimate_rows(_with_ci)``.

    One Pallas pass (``kernels/estimate.py``) streams the int8 rows through
    VMEM and emits (Ĉ[K], stddev[K], converged[K]) without materializing the
    ``[K, 2^b]`` histogram block in HBM. The kind convention matches the
    estimation layer: ``"full"`` returns the MLE, ``"routed"`` scales ×m with
    untouched rows (all registers at r_min) pinned to exactly 0.0 — inside
    the kernel that guard coincides with the degenerate-low fallback.
    """
    from repro.core import estimation

    _note_trace("estimate_rows")
    estimation._check_kind(kind)
    interpret = _interpret_default() if interpret is None else interpret
    k, m = regs.shape

    bk = block_k or min(estimate.DEFAULT_BLOCK_K, _round_up(k, 8))
    kp, mp = _round_up(k, bk), _round_up(m, 128)
    nbp = _round_up(cfg.num_bins, 128)

    regs_p = jnp.pad(
        regs, ((0, kp - k), (0, mp - m)), constant_values=cfg.r_min
    )
    chat, std, conv = estimate.estimate_rows_padded(
        regs_p,
        m=m,
        nb_padded=nbp,
        r_min=cfg.r_min,
        top_bin=cfg.top_bin,
        block_k=bk,
        interpret=interpret,
    )
    chat, std, conv = chat[:k, 0], std[:k, 0], conv[:k, 0] > 0
    if kind == "routed":
        return chat * cfg.m, std * cfg.m, conv
    return chat, std, conv


def sharded_dyn_array_update_op(
    cfg: SketchConfig,
    mesh,
    state: ShardedDynArrayState,
    keys,
    ids,
    weights,
    mask=None,
    *,
    axis: str = sharding.AXIS,
    block_b: int | None = None,
    interpret: bool | None = None,
) -> ShardedDynArrayState:
    """Kernel-backed equivalent of ``sharded_dyn_array.update_batch``
    (bit-identical on every state leaf).

    The per-shard body is exactly ``dyn_array_update_op`` — the Pallas q_R
    kernel streams each shard's gathered histogram rows through VMEM, the
    data-dependent tail stays ``dyn_array._apply_update`` — run under
    ``shard_map`` with the replicated batch hash-routed to the owning shard
    (``sharding.own_slots``), the same dispatch as the jnp-backed sharded
    path. ``check_vma=False`` because pallas_call has no replication rule;
    every operand the kernel touches is shard-local, so the check is
    vacuous.
    """
    _note_trace("sharded_dyn_array_update")
    sharding.check_divisible(state.regs.shape[0], mesh, axis)
    k = state.regs.shape[0]
    rows = k // sharding.num_shards(mesh, axis)
    keys = jnp.clip(keys.astype(jnp.int32), 0, k - 1)
    mask = jnp.ones(keys.shape, bool) if mask is None else mask

    def local(st, keys, ids, w, m):
        local_keys, own = sharding.own_slots(keys, rows, axis, m)
        return tuple(
            dyn_array_update_op(
                cfg, st, local_keys, ids, w, mask=own,
                block_b=block_b, interpret=interpret,
            )
        )

    return ShardedDynArrayState(
        *sharding.shard_map_rows(
            local,
            mesh,
            in_dims=(DynArrayState(0, 0, 0), None, None, None, None),
            out_dims=(0, 0, 0),
            axis=axis,
            check_vma=False,
        )(DynArrayState(*state), keys, ids, weights, mask)
    )


def sharded_window_union_estimate_op(
    cfg: SketchConfig,
    mesh,
    state: ShardedWindowArrayState,
    w: int,
    *,
    axis: str = sharding.AXIS,
    solver: str = "newton",
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Kernel-backed equivalent of ``sharded_window_array.estimate_window``
    for sub-ring windows — Ĉ[K] over the last w <= E epochs, bit-identical
    to both the sharded jnp path and the single-host op.

    Each shard runs the fused union+bincount kernel
    (``kernels/window_union.py``) over its own rows of the epoch planes —
    the epoch-plane max-union commutes with row sharding, so no plane ever
    crosses a shard boundary. The ring head is replicated; w is a static
    host-side int.
    """
    _note_trace("sharded_window_union_estimate")
    sharding.check_divisible(state.regs.shape[1], mesh, axis)
    w = window_array._check_w(state, w)

    def local(regs_l, head):
        st = WindowArrayState(
            regs_l, None, None, None, None, None,
            head=head, filled=jnp.int32(0), epoch_id=jnp.int32(0),
        )
        return window_union_estimate_op(
            cfg, st, w, solver=solver, block_k=block_k, interpret=interpret
        )

    return sharding.shard_map_rows(
        local, mesh, in_dims=(1, None), out_dims=0, axis=axis, check_vma=False
    )(state.regs, state.head)


def float_sketch_update_op(
    cfg: SketchConfig,
    state: FloatSketchState,
    ids,
    weights,
    *,
    block_b: int | None = None,
    block_m: int | None = None,
    interpret: bool | None = None,
) -> FloatSketchState:
    """Kernel-backed equivalent of ``core.baselines.lm_update`` (bit-identical)."""
    _note_trace("float_sketch_update")
    interpret = _interpret_default() if interpret is None else interpret
    lo, hi = hashing.split_id64(ids)
    b = lo.shape[0]
    bb, bm = _pick_blocks(b, cfg.m, block_b, block_m)
    bp, mp = _round_up(b, bb), _round_up(cfg.m, bm)

    # Padding rows are flagged with w = -1 (kernel masks non-positive w).
    lo2, hi2, w2 = _pad_batch([lo, hi, weights.astype(jnp.float32)], bp, [0, 0, -1.0])
    regs = jnp.pad(state.regs, ((0, mp - cfg.m),), constant_values=_POS_INF)[None, :]

    out = qsketch_update.float_sketch_update_padded(
        lo2, hi2, w2, regs, block_b=bb, block_m=bm, salt=cfg.salt_h, interpret=interpret
    )
    return FloatSketchState(regs=out[0, : cfg.m])


def qdyn_qr_op(
    cfg: SketchConfig,
    hist,
    weights,
    *,
    block_b: int | None = None,
    interpret: bool | None = None,
):
    """Kernel-backed q_R batch (matches core.qsketch_dyn._q_update_prob)."""
    _note_trace("qdyn_qr")
    interpret = _interpret_default() if interpret is None else interpret
    b = weights.shape[0]
    bb = block_b or min(qdyn_qr.DEFAULT_BLOCK_B, _round_up(b, 8))
    bp = _round_up(b, bb)
    nbp = _round_up(cfg.num_bins, 128)

    from repro.core import estimators

    scales = jnp.pad(
        jnp.asarray(estimators._bin_scales(cfg)), ((0, nbp - cfg.num_bins),)
    )[None, :]
    histp = jnp.pad(hist.astype(jnp.float32), ((0, nbp - cfg.num_bins),))[None, :]
    w2 = jnp.pad(weights.astype(jnp.float32), ((0, bp - b),), constant_values=1.0)[:, None]

    q = qdyn_qr.qdyn_qr_padded(w2, histp, scales, m=cfg.m, block_b=bb, interpret=interpret)
    return jnp.maximum(q[:b, 0], 1e-12)
