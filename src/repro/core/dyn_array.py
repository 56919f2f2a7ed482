"""DynArray: K independent QSketch-Dyn sketches with O(1)-anytime reads.

``core/sketch_array.py`` gives K QSketches one fused keyed update, but every
``estimate_all`` query still pays the O(K·2^b) vmapped Newton — 55 s at
K = 2^20 on the host mesh (ROADMAP). ``qsketch_dyn`` already carries the
paper's §4.3 martingale, which makes the estimate a running scalar that is
simply *read*. This module lifts that to the keyed array: per-tenant
weighted cardinality becomes an O(K) device read (``estimate_all`` returns
``state.chats``), paid for by a slightly heavier update that maintains
per-key histograms and martingales.

State (``DynArrayState``): ``int8[K, m]`` registers + ``int32[K, 2^b]``
touched-register histograms + ``f32[K]`` running estimates. Row k is
bit-identical to a standalone ``DynState`` fed the key-k sub-stream — the
register choice g(x) and quantized value y(x, w) never see the key, dedup is
per (key, id), and each element's update probability q_R comes from ITS
key's batch-start histogram (Eq. 12 semantics per row). The K-loop oracle
``update_reference`` verifies this (registers/histograms bitwise; chats
accumulate the same per-key terms in a different — but fixed — float32
association order, equal to the loop within rounding).

Update cost is O(B log B) (dedup sort) + O(B·2^b) (q_R, histogram delta
rows) + O(B) scatters — independent of K. The histogram is maintained
*incrementally*: each register changed by the batch moves one unit of mass
old-bin -> new-bin, counted once via a per-(key, register) dedup — exactly
equivalent to the single sketch's rebuild-from-registers because untouched
registers hold r_min and bin 0 is pinned to zero (asserted against
``rebuild_hists`` in tests).

Keyed martingale semantics (DESIGN.md §8.4): per-key chats ARE additive
across disjoint batches of one stream (the martingale telescopes), but NOT
across shards/pods that may have seen the same element — cross-shard
``merge`` therefore max-merges registers and re-estimates every chat with
the per-key histogram MLE, mirroring ``qsketch_dyn.merge``.
"""

from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp

from . import estimation, estimators, hashing, key_directory, qsketch_dyn
from .types import DynArrayState, DynState, SketchConfig


def init(cfg: SketchConfig, k: int) -> DynArrayState:
    """K fresh Dyn sketches; K is carried by the state shape, cfg stays shared."""
    if k < 1:
        raise ValueError("DynArray needs k >= 1 sketches")
    return DynArrayState(
        regs=jnp.full((k, cfg.m), cfg.r_min, dtype=jnp.int8),
        hists=jnp.zeros((k, cfg.num_bins), dtype=jnp.int32),
        chats=jnp.zeros((k,), dtype=jnp.float32),
    )


def num_sketches(state: DynArrayState) -> int:
    """Tenant capacity K (the row count of every state leaf)."""
    return state.regs.shape[0]


def row(state: DynArrayState, k: int) -> DynState:
    """Extract sketch k as a standalone (bit-identical) DynState.

    Host-side API: ``k`` must be a concrete int in [0, K).
    """
    n = state.regs.shape[0]
    if not 0 <= k < n:
        raise IndexError(f"dyn sketch row {k} out of range for K={n}")
    return DynState(regs=state.regs[k], hist=state.hists[k], chat=state.chats[k])


def _keyed_dedup_mask(keys, lo, hi, live):
    """First live occurrence per (key, id): the per-key form of
    ``qsketch_dyn._dedup_mask``. Same id under two keys is two distinct
    elements (one per sketch); live rows sort ahead of dead rows of the same
    (key, id) so padding can never shadow a live element (the fixed
    dedup/mask ordering contract, DESIGN.md §4.2)."""
    dead = (~live).astype(jnp.uint32)
    order = jnp.lexsort((dead, lo, hi, keys))
    sk, slo, shi = keys[order], lo[order], hi[order]
    first = jnp.concatenate(
        [
            jnp.array([True]),
            (sk[1:] != sk[:-1]) | (slo[1:] != slo[:-1]) | (shi[1:] != shi[:-1]),
        ]
    )
    mask = jnp.zeros_like(first).at[order].set(first)
    return mask


class UpdatePlan(typing.NamedTuple):
    """B-sized scatter payloads from the read-only half of one batch update.

    Produced by ``_plan_scatters`` (gathers + per-element math), consumed by
    ``_commit_scatters`` (pure scatters). The donated DynArray path compiles
    the halves as SEPARATE executables, so the commit that receives the
    donated state only scatters into it. What keeps that commit in place on
    TPU is the scatters' shape: registers take a scalar scatter-max (an
    ``int8[K, m]`` plane bitcasts to 1-D for free), and the histogram mass
    moves land as ONE scatter-add of ``int32[B, 2^b]`` delta rows. A scalar
    scatter at (key, bin) into the ``int32[K, 2^b]`` plane would make the
    TPU compiler flatten the plane to 1-D first: the plane is tiled
    T(8,128), not row-major, so the flattening is a relayout copy out and a
    reshape back, two passes over the whole plane per batch.
    """

    keys: jax.Array  # int32[B] clipped row routes
    j: jax.Array  # int32[B] register choice g(x)
    y_eff: jax.Array  # int8[B] scatter-max payload (r_min where unchanged)
    chat_add: jax.Array  # f32[B] martingale increments w/q (0 where unchanged)
    old_bin: jax.Array  # int32[B] batch-start histogram bin of regs[key, j]
    final_bin: jax.Array  # int32[B] post-batch histogram bin of regs[key, j]
    hist_dec: jax.Array  # int32[B] -1 where this element retires old_bin mass
    hist_inc: jax.Array  # int32[B] +1 where this element deposits final_bin


def _plan_scatters(
    cfg: SketchConfig, state: DynArrayState, keys, lo, hi, w, live, q, *ring
) -> UpdatePlan:
    """Read-only half of the update: dedup, batch-start change indicators,
    incremental-histogram bookkeeping — every output is B-sized and state
    is only gathered, never written. ``q`` is the per-element update
    probability from the element's key's batch-start histogram.

    ``ring``: empty for a DynArray; the window's ``head`` when the leaves
    of ``state`` are whole ``[E, K, ...]`` ring planes (core/window_array.py)
    — the registers are then gathered at (head, key, j), with no epoch
    slice of the ring."""
    j, y = qsketch_dyn._choose_and_quantize(cfg, lo, hi, w)

    alive = _keyed_dedup_mask(keys, lo, hi, live) & live
    old = state.regs[(*ring, keys, j)].astype(jnp.int32)
    changed = alive & (y > old)

    chat_add = jnp.where(changed, w / q, 0.0)

    # y_eff is r_min (unchanged) or in (old, r_max] (changed), so the
    # scatter-max runs on int8 directly — no int32 round-trip of the whole
    # [K, m] matrix on the hot path.
    y_eff = jnp.where(changed, y, jnp.int32(cfg.r_min))

    # Incremental histogram: every register the batch changed moves one unit
    # of mass old-bin -> final-bin, counted ONCE per (key, register).
    # ``final`` — the register's post-batch value — is the segment max of
    # y_eff over the element's (key, register) group, floored by ``old``:
    # integer max, so EXACTLY the value the commit's scatter-max leaves
    # there, computed without re-gathering the scattered matrix (which
    # would drag the [K, m] buffer back into a gather-after-write live
    # range). Equivalent to a full rebuild (bin 0 pinned to zero) at O(B)
    # instead of O(K·m).
    reg_order = jnp.lexsort((j, keys))
    rk, rj = keys[reg_order], j[reg_order]
    starts = jnp.concatenate(
        [jnp.array([True]), (rk[1:] != rk[:-1]) | (rj[1:] != rj[:-1])]
    )
    seg = jnp.cumsum(starts) - 1
    smax = jax.ops.segment_max(
        y_eff[reg_order], seg, num_segments=y_eff.shape[0], indices_are_sorted=True
    )
    final_sorted = jnp.maximum(old[reg_order], smax[seg])
    final = jnp.zeros_like(final_sorted).at[reg_order].set(final_sorted)
    reg_first = jnp.zeros_like(starts).at[reg_order].set(starts)
    reg_changed = reg_first & (final > old)
    dec = reg_changed & (old > cfg.r_min)  # old at r_min was never tracked
    return UpdatePlan(
        keys=keys,
        j=j,
        y_eff=y_eff.astype(jnp.int8),
        chat_add=chat_add,
        old_bin=old - cfg.r_min,
        final_bin=final - cfg.r_min,
        hist_dec=jnp.where(dec, -1, 0),
        hist_inc=jnp.where(reg_changed, 1, 0),
    )


def _hist_delta_rows(plan: UpdatePlan, num_bins: int) -> jax.Array:
    """``int32[B, 2^b]`` histogram mass moves, one row per element: -1 at
    ``old_bin`` where it retires mass, +1 at ``final_bin`` where it deposits
    it. A one-hot built elementwise — B-sized, no scatter."""
    bins = jnp.arange(num_bins, dtype=jnp.int32)
    return (
        jnp.where(bins == plan.old_bin[:, None], plan.hist_dec[:, None], 0)
        + jnp.where(bins == plan.final_bin[:, None], plan.hist_inc[:, None], 0)
    )


def _commit_scatters(state: DynArrayState, plan: UpdatePlan, *ring) -> DynArrayState:
    """Scatter-only half of the update: register scatter-max, histogram
    mass moves, martingale accumulation. Every state leaf is written only
    at the rows the batch addresses, never gathered. The histogram moves
    are one scatter-add of whole delta rows at ``keys`` (``UpdatePlan``
    says why not two scalar scatters); integer adds commute, so duplicate
    keys in a batch give the same bits in any order.

    ``ring``: as in ``_plan_scatters`` — the window's ``head`` when the
    leaves are whole ring planes, so the rows land straight in that epoch
    with no slice taken or written back."""
    rows = (*ring, plan.keys)
    regs = state.regs.at[(*rows, plan.j)].max(plan.y_eff)
    hists = state.hists.at[rows].add(
        _hist_delta_rows(plan, state.hists.shape[-1])
    )
    chats = state.chats.at[rows].add(plan.chat_add)
    return DynArrayState(regs=regs, hists=hists, chats=chats)


def _apply_update(
    cfg: SketchConfig, state: DynArrayState, keys, lo, hi, w, live, q, *ring
):
    """Shared tail of the jnp and Pallas-backed update paths: the plan and
    commit halves fused back into one trace. The sharded/window/kernel
    routes and the non-donated ``update_batch`` all come through here, so
    every route runs the identical math as the split donated path.
    ``ring`` as in ``_plan_scatters``."""
    return _commit_scatters(
        state, _plan_scatters(cfg, state, keys, lo, hi, w, live, q, *ring), *ring
    )


def _plan_batch(
    cfg: SketchConfig, state: DynArrayState, keys, ids, weights, mask=None
) -> UpdatePlan:
    k = state.regs.shape[0]
    lo, hi = hashing.split_id64(ids)
    w = weights.astype(jnp.float32)
    keys = jnp.clip(keys.astype(jnp.int32), 0, k - 1)
    live = qsketch_dyn._live_weight_mask(w, mask)
    # Per-element q_R against the element's key's batch-start histogram —
    # the same expression as the single sketch, broadcast over gathered rows.
    q = qsketch_dyn._q_update_prob(cfg, state.hists[keys], w)
    return _plan_scatters(cfg, state, keys, lo, hi, w, live, q)


def _update_batch_impl(
    cfg: SketchConfig, state: DynArrayState, keys, ids, weights, mask=None
) -> DynArrayState:
    return _commit_scatters(state, _plan_batch(cfg, state, keys, ids, weights, mask))


_update_batch_jit = jax.jit(_update_batch_impl, static_argnums=(0,))
_plan_batch_jit = jax.jit(_plan_batch, static_argnums=(0,))
_commit_donated = jax.jit(_commit_scatters, donate_argnums=(0,))


def update_batch(
    cfg: SketchConfig, state: DynArrayState, keys, ids, weights, mask=None,
    *, donate: bool = False,
) -> DynArrayState:
    """One fused keyed batch, batch-stale per row (qsketch_dyn.update_batch
    semantics lifted to K rows).

    keys: int[B] in [0, K) routing each element to its sketch row;
      out-of-range keys are clipped (callers pad with key 0 + mask=False).
    mask: optional bool[B]; masked rows and degenerate (non-positive /
      non-finite) weights are dropped before dedup — they neither shadow a
      live duplicate nor enter the martingale.
    donate: run the update as TWO executables — a read-only plan (gathers +
      per-element math) and a scatter-only commit that donates ``state``
      (``donate_argnums``) — so the scatters reuse the state buffers
      instead of allocating a fresh int8[K, m] + int32[K, 2^b] + f32[K]
      copy per batch: the steady-state ingest mode (sketchstream/ingest.py).
      The compiled commit writes only the B addressed rows of each leaf:
      on a TPU v5e its temporaries are B-sized, with no copy or reshape of
      a plane (tests/test_tpu_compile.py), because the histogram moves are
      one row scatter-add (``UpdatePlan``). The caller's ``state`` is DEAD
      afterwards (same values live on in the returned state); keep
      ``donate=False`` anywhere the old state is still read (oracles,
      merges, A/B tests). Both modes are bit-identical: the plan/commit
      math is one trace, split or fused.
    """
    if donate:
        return _commit_donated(state, _plan_batch_jit(cfg, state, keys, ids, weights, mask))
    return _update_batch_jit(cfg, state, keys, ids, weights, mask)


def rebuild_hists(cfg: SketchConfig, regs) -> jnp.ndarray:
    """Per-key touched-register histograms from scratch (bin 0 pinned to 0).

    O(K·m) — the reference the incremental maintenance is tested against,
    and the rebuild used by ``merge``.
    """
    hists = jax.vmap(lambda r: estimators.histogram(cfg, r))(regs)
    return hists.at[:, 0].set(0)


def estimate_all(state: DynArrayState) -> jnp.ndarray:
    """Ĉ for every sketch: a pure O(K) read of the running martingales.

    This is the whole point of the Dyn array — no Newton, no histogram walk;
    at K = 2^20 this is a device read where ``sketch_array.estimate_all``
    pays an O(K·2^b) vmapped solve (benchmarks/dyn_array.py).
    """
    return state.chats


def estimate_mle_rows(cfg: SketchConfig, regs, *, solver: str = "newton") -> jnp.ndarray:
    """Per-row histogram-MLE Ĉ from an ``int8[K, m]`` register matrix.

    The regs-only core of ``estimate_mle_all``, shared with the windowed
    union reads (core/window_array.py): each row's MLE recovers C_k/m and is
    scaled by m; untouched rows report 0. Thin shim over
    ``estimation.estimate_rows(kind="routed")`` — the solve (and the
    untouched-row guard) lives in the estimation layer; ``solver`` picks
    newton / lut / fused (DESIGN.md §8.7).
    """
    return estimation.estimate_rows(cfg, regs, kind="routed", solver=solver)


def estimate_mle_hists(cfg: SketchConfig, full_hists, *, solver: str = "newton") -> jnp.ndarray:
    """Per-row histogram-MLE Ĉ from FULL histograms ``int32[K, 2^b]`` (bin 0
    counts untouched r_min registers, rows sum to m).

    Bit-identical to ``estimate_mle_rows`` on the registers the histograms
    were counted from — the likelihood sees registers only through their
    value histogram (DESIGN.md §8.3) — which is what lets the window array's
    cached union histograms skip the register walk entirely. Thin shim over
    ``estimation.estimate_hists(kind="routed")``.
    """
    return estimation.estimate_hists(cfg, full_hists, kind="routed", solver=solver)


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("solver",))
def estimate_mle_all(
    cfg: SketchConfig, state: DynArrayState, *, solver: str = "newton"
) -> jnp.ndarray:
    """Per-key histogram-MLE re-estimate, Ĉ[K].

    The vmapped form of ``qsketch_dyn.estimate_mle`` (each row's MLE recovers
    C_k/m and is scaled by m); untouched rows report 0. Use after cross-shard
    merges or as a self-check — the hot path reads ``estimate_all``.

    ``solver="lut"`` reads the maintained ``state.hists`` (bin 0 re-derived
    from the row sums, an invariant tested against ``rebuild_hists``) instead
    of bincounting the registers — the whole O(K·m) register walk disappears
    along with the Newton loop. ``"fused"`` streams the registers through the
    Pallas estimate kernel (TPU).
    """
    if solver == "lut":
        full = state.hists.at[:, 0].set(cfg.m - jnp.sum(state.hists, axis=1))
        return estimation.estimate_hists(cfg, full, kind="routed", solver="lut")
    return estimation.estimate_rows(cfg, state.regs, kind="routed", solver=solver)


def merge(cfg: SketchConfig, a: DynArrayState, b: DynArrayState) -> DynArrayState:
    """Merge two fleets sketching (possibly overlapping) sub-streams.

    Registers: row-wise max (exact union). Histograms: rebuilt. Chats:
    re-estimated per key via the histogram MLE — running martingales are NOT
    additive across shards that may share elements (DESIGN.md §8.4), exactly
    as in ``qsketch_dyn.merge``. Shapes must agree: a (K, m) mismatch means
    different tenant spaces / register geometries.
    """
    if a.regs.shape != b.regs.shape:
        raise ValueError(
            f"DynArray merge needs matching (K, m), got {a.regs.shape} vs {b.regs.shape}"
        )
    regs = jnp.maximum(a.regs, b.regs)
    merged = DynArrayState(
        regs=regs, hists=rebuild_hists(cfg, regs), chats=a.chats
    )
    return merged._replace(chats=estimate_mle_all(cfg, merged))


def check_disjoint_rows(a, b) -> None:
    """Eagerly reject overlapping key partitions before a disjoint merge.

    A row touched in BOTH states (nonzero histogram mass on each side) means
    the two fleets both saw that key's traffic — the key-partition contract
    ``merge_disjoint`` relies on is broken and adding chats would
    double-count any shared element. The check is host-side: under jit
    tracing it CANNOT run, and rather than silently dropping a guard the
    caller asked for, it raises — run the merge eagerly, or pass
    ``check_partition=False`` when the pipeline owns the invariant by
    construction. Shared by the single-host and sharded
    (``sharded_dyn_array``) disjoint merges.
    """
    both = (jnp.sum(a.hists, axis=1) > 0) & (jnp.sum(b.hists, axis=1) > 0)
    if isinstance(both, jax.core.Tracer):
        raise ValueError(
            "merge_disjoint: cannot verify key-partition disjointness under "
            "jit tracing — run the merge eagerly, or pass "
            "check_partition=False if the caller owns the invariant"
        )
    n = int(jnp.sum(both))
    if n:
        raise ValueError(
            f"merge_disjoint: {n} key rows are live in BOTH states — the "
            "streams are not key-partitioned; use merge() for overlapping "
            "streams (chats re-estimate via the MLE instead of adding)"
        )


def merge_disjoint(
    cfg: SketchConfig, a: DynArrayState, b: DynArrayState,
    check_partition: bool = False,
) -> DynArrayState:
    """Merge fleets whose streams are known element-disjoint: chats ADD.

    The production sharding is BY KEY — a tenant's stream lands on exactly
    one shard — so two shards never see the same element and the per-key
    martingales telescope across them: Ĉ_merged = Ĉ_a + Ĉ_b, exactly and
    with no MLE (which ``merge`` needs for possibly-overlapping streams and
    which is misspecified for lightly-loaded rows, DESIGN.md §8.4).
    Registers still max-merge (the union sketch) and histograms rebuild, so
    subsequent batches see correct q_R state.

    Element-disjointness is the true precondition (two streams with shared
    key rows but disjoint element ids still add exactly); key-partitioning
    is the production contract that *guarantees* it. ``check_partition=True``
    enforces the stricter contract eagerly via ``check_disjoint_rows`` — a
    row live in both fleets is rejected, and a traced (jit) call raises
    rather than silently skipping the requested guard. The sharded fleet
    merge (``sharded_dyn_array.merge_disjoint``) enforces it by default;
    here the caller owns the disjointness invariant.
    """
    if a.regs.shape != b.regs.shape:
        raise ValueError(
            f"DynArray merge needs matching (K, m), got {a.regs.shape} vs {b.regs.shape}"
        )
    if check_partition:
        check_disjoint_rows(a, b)
    regs = jnp.maximum(a.regs, b.regs)
    return DynArrayState(
        regs=regs, hists=rebuild_hists(cfg, regs), chats=a.chats + b.chats
    )


def update_tenants(
    cfg: SketchConfig,
    dcfg: key_directory.DirectoryConfig,
    state: DynArrayState,
    dir_state: key_directory.DirectoryState,
    tenant_keys,
    ids,
    weights,
    mask=None,
):
    """Sparse-tenant entry: route 64-bit tenant ids through the key directory,
    then run the fused keyed update. Returns (state, directory telemetry) —
    the same production contract as ``sketch_array.update_tenants``.
    """
    if dcfg.capacity != state.regs.shape[0]:
        raise ValueError(
            f"directory capacity {dcfg.capacity} != DynArray rows {state.regs.shape[0]}"
        )
    slots, dir_state = key_directory.route(dcfg, dir_state, tenant_keys, mask=mask)
    return update_batch(cfg, state, slots, ids, weights, mask=mask), dir_state


def update_reference(
    cfg: SketchConfig, state: DynArrayState, keys, ids, weights, mask=None
) -> DynArrayState:
    """Oracle: partition the stream by key (order preserved), run K
    independent ``qsketch_dyn.update_batch`` calls. O(K) dispatches —
    tests/benchmarks only, never the hot path. ``mask`` rows are dropped from
    their key's sub-stream entirely, so padded batches are verified too.
    ``ids`` follows the usual contract: a uint32 array or a (lo, hi) pair.
    """
    import numpy as np

    keys_np = np.asarray(jnp.clip(keys.astype(jnp.int32), 0, state.regs.shape[0] - 1))
    live = np.ones(keys_np.shape, bool) if mask is None else np.asarray(mask)
    lo, hi = hashing.split_id64(ids)
    lo_np, hi_np, w_np = np.asarray(lo), np.asarray(hi), np.asarray(weights)
    rows = []
    for k in range(state.regs.shape[0]):
        st_k = DynState(regs=state.regs[k], hist=state.hists[k], chat=state.chats[k])
        sel = (keys_np == k) & live
        if sel.any():
            st_k = qsketch_dyn.update_batch(
                cfg, st_k,
                (jnp.asarray(lo_np[sel]), jnp.asarray(hi_np[sel])),
                jnp.asarray(w_np[sel]),
            )
        rows.append(st_k)
    return DynArrayState(
        regs=jnp.stack([r.regs for r in rows]),
        hists=jnp.stack([r.hist for r in rows]),
        chats=jnp.stack([r.chat for r in rows]),
    )
