"""WindowArray: sliding-window weighted cardinality over K tenants.

Every estimate the repo produced so far is *cumulative* — "weighted distinct
traffic since init". The paper's headline application (real-time anomaly
detection) consumes the *time-scoped* form: "weighted distinct traffic in the
last W minutes". This module adds the temporal axis as a ring of E epoch
sub-states layered on the DynArray (Wang et al. 2018 in PAPERS.md shows the
register-sharing machinery extends to time-scoped estimates; we get the same
effect from plain epoch rings because register max-merge is lossless).

State (``WindowArrayState``): ``int8[E, K, m]`` registers + per-epoch DynArray
histograms/chats, a ``head`` ring pointer, and a cached *union* sub-state
(max over all E epochs, with DynArray histogram + martingale maintenance on
top). Semantics:

* ``update_batch`` folds a keyed batch into the CURRENT epoch — one fused
  DynArray update on the head sub-state, and the same elements through the
  union sub-state (2x the DynArray update cost, still independent of K and E).
* ``rotate()`` closes the current epoch: O(1) ring bookkeeping (advance
  ``head``, reset the slot it lands on — evicting the oldest epoch once the
  ring is full) plus a rebuild of the union cache from the surviving epochs,
  paid at rotation cadence and amortized over an epoch of updates: the
  register max (O(E·K·m)), the union histograms counted densely by
  ``dyn_array.rebuild_hists`` (K·m int8 read, K·2^b int32 written: 128 MiB
  and 1 GiB at K = 2^20, m = 128, b = 8, ~1.5 ms at a TPU v5e's HBM
  bandwidth; the measured time is in PERF.md §6) and the MLE re-base of
  every row.
* ``estimate_window(w)`` answers "weighted cardinality over the last
  w <= E epochs": all-max union of the w epoch register planes — EXACT,
  the union of epoch streams is sketched by the register-wise max — read out
  with the vmapped histogram MLE. Per-epoch chats can NOT be summed across
  epochs (an element alive in two epochs would double-count; DESIGN.md §8.5),
  which is why sub-ring windows pay the MLE. The full-ring window w == E
  skips the union+bincount entirely: the cached ``union_hists`` are
  maintained incrementally and the read is bit-identical to the from-scratch
  path. ``ops.window_union_estimate_op`` is the fused kernel form of the
  sub-ring read (no [w, K, m] intermediate).
* ``estimate_ring_anytime`` is the O(K) fast path for the full-ring window:
  a pure read of the running union martingales (exact §4.3 chain within the
  current epoch, MLE re-based at each rotation) — what a per-step anomaly
  detector consumes (sketchstream/anomaly.py).

Window semantics: epochs are closed by the caller's clock (``rotate`` per
wall-time tick / N batches), so "the last w epochs" is a tumbling-grain
sliding window with grain = one epoch. ``filled`` tracks how many ring slots
have ever been active; w beyond it clamps harmlessly (unfilled slots hold
r_min everywhere and are no-ops in the union).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import dyn_array, hashing, key_directory, qsketch_dyn
from .types import DynArrayState, SketchConfig, WindowArrayState


def init(cfg: SketchConfig, k: int, e: int) -> WindowArrayState:
    """K tenants x E ring epochs; epoch 0 starts as the current epoch."""
    if k < 1:
        raise ValueError("WindowArray needs k >= 1 sketches")
    if e < 2:
        raise ValueError("WindowArray needs e >= 2 epochs (e == 1 is a DynArray)")
    return WindowArrayState(
        regs=jnp.full((e, k, cfg.m), cfg.r_min, dtype=jnp.int8),
        hists=jnp.zeros((e, k, cfg.num_bins), dtype=jnp.int32),
        chats=jnp.zeros((e, k), dtype=jnp.float32),
        union_regs=jnp.full((k, cfg.m), cfg.r_min, dtype=jnp.int8),
        union_hists=jnp.zeros((k, cfg.num_bins), dtype=jnp.int32),
        union_chats=jnp.zeros((k,), dtype=jnp.float32),
        head=jnp.int32(0),
        filled=jnp.int32(1),
        epoch_id=jnp.int32(0),
    )


def num_epochs(state: WindowArrayState) -> int:
    """Ring size E (the epoch-plane count of every per-epoch leaf)."""
    return state.regs.shape[0]


def num_sketches(state: WindowArrayState) -> int:
    """Tenant capacity K (the row count within each epoch plane)."""
    return state.regs.shape[1]


def epoch_substate(state: WindowArrayState, e) -> DynArrayState:
    """Epoch slot e's sub-state as a DynArray (a view, not a copy under jit)."""
    return DynArrayState(
        regs=state.regs[e], hists=state.hists[e], chats=state.chats[e]
    )


def union_substate(state: WindowArrayState) -> DynArrayState:
    """The cached full-ring union as a DynArray (a view, not a copy)."""
    return DynArrayState(
        regs=state.union_regs, hists=state.union_hists, chats=state.union_chats
    )


def _apply_update(cfg: SketchConfig, state: WindowArrayState, keys, lo, hi, w, live):
    """Shared tail of the single-host and sharded windowed updates: two fused
    DynArray updates on the same dedup'd elements — the head epoch and the
    union cache. ``keys`` are in-range row indices and ``live`` is the final
    element mask (padding, degenerate weights and — in the sharded form —
    foreign shards' elements already dropped).

    The head epoch is updated in the ring itself: its batch-start rows are
    gathered at (head, keys) and the scatters land at (head, keys, ...), so
    no epoch plane is sliced out of the ring or written back into it."""
    head = state.head
    ring = DynArrayState(regs=state.regs, hists=state.hists, chats=state.chats)
    q_ep = qsketch_dyn._q_update_prob(cfg, state.hists[head, keys], w)
    ep = dyn_array._apply_update(cfg, ring, keys, lo, hi, w, live, q_ep, head)

    un = union_substate(state)
    q_un = qsketch_dyn._q_update_prob(cfg, un.hists[keys], w)
    un = dyn_array._apply_update(cfg, un, keys, lo, hi, w, live, q_un)

    return state._replace(
        regs=ep.regs,
        hists=ep.hists,
        chats=ep.chats,
        union_regs=un.regs,
        union_hists=un.hists,
        union_chats=un.chats,
    )


def _update_batch_impl(
    cfg: SketchConfig, state: WindowArrayState, keys, ids, weights, mask=None
) -> WindowArrayState:
    k = state.regs.shape[1]
    lo, hi = hashing.split_id64(ids)
    w = weights.astype(jnp.float32)
    keys = jnp.clip(keys.astype(jnp.int32), 0, k - 1)
    live = qsketch_dyn._live_weight_mask(w, mask)
    return _apply_update(cfg, state, keys, lo, hi, w, live)


_update_batch_jit = jax.jit(_update_batch_impl, static_argnums=(0,))
_update_batch_donated = jax.jit(
    _update_batch_impl, static_argnums=(0,), donate_argnums=(1,)
)


def update_batch(
    cfg: SketchConfig, state: WindowArrayState, keys, ids, weights, mask=None,
    *, donate: bool = False,
) -> WindowArrayState:
    """Fold one keyed batch into the current epoch (and the union cache).

    Same contract as ``dyn_array.update_batch`` (keys clipped to [0, K),
    masked / degenerate-weight rows dropped before dedup). Two fused DynArray
    updates run on the same dedup'd elements:

    * the head epoch sub-state — its registers/hists/chats stay bit-identical
      to a standalone DynArray fed only this epoch's sub-stream;
    * the union sub-state — q_R and change-indicators against the UNION
      batch-start state, advancing the full-ring anytime martingale.

    The union-regs invariant (union == max over epochs) is preserved exactly:
    an element raises union[k, j] iff its y exceeds the union register, which
    already dominates the epoch register it also raises.

    ``donate=True`` hands the (large: int8[E, K, m] + int32[E, K, 2^b]) ring
    state to XLA for in-place reuse — the steady-state ingest mode; the
    caller's ``state`` is dead afterwards (``dyn_array.update_batch`` has the
    full contract). Both halves scatter only the B addressed rows: the head
    epoch is updated at (head, keys) in the ring, never sliced out and
    written back, and the histogram moves are row scatter-adds
    (``dyn_array.UpdatePlan``). Compiled for a TPU v5e, the donated update's
    temporaries are B-sized, with no copy, reshape or (dynamic) slice of a
    plane (tests/test_tpu_compile.py).
    """
    fn = _update_batch_donated if donate else _update_batch_jit
    return fn(cfg, state, keys, ids, weights, mask)


def _rotate_impl(cfg: SketchConfig, state: WindowArrayState) -> WindowArrayState:
    """Close the current epoch and open the next ring slot.

    Ring bookkeeping is O(1): advance ``head`` and reset the slot it lands on
    — once the ring is full that slot holds the OLDEST epoch, which is
    thereby evicted (its elements leave every window). The union cache is
    then rebuilt from the surviving epoch planes (O(E·K·m) + histogram
    rebuild + one vmapped MLE pass, rotation-cadence cost) and the running
    union martingale re-bases to the MLE of the surviving union — eviction
    can lower the union, which no running martingale can track (DESIGN.md
    §8.5). ``epoch_id`` advances monotonically: it is the clock fed to
    ``key_directory.evict_older_than`` for cold-tenant aging.
    """
    e, k, m = state.regs.shape
    head = (state.head + 1) % e
    regs = state.regs.at[head].set(jnp.full((k, m), cfg.r_min, jnp.int8))
    hists = state.hists.at[head].set(jnp.zeros((k, cfg.num_bins), jnp.int32))
    chats = state.chats.at[head].set(jnp.zeros((k,), jnp.float32))
    union_regs = jnp.max(regs, axis=0)
    union_hists = dyn_array.rebuild_hists(cfg, union_regs)
    return WindowArrayState(
        regs=regs,
        hists=hists,
        chats=chats,
        union_regs=union_regs,
        union_hists=union_hists,
        union_chats=_chats_from_touched_hists(cfg, union_hists),
        head=head,
        filled=jnp.minimum(state.filled + 1, e),
        epoch_id=state.epoch_id + 1,
    )


_rotate_jit = jax.jit(_rotate_impl, static_argnums=(0,))
_rotate_donated = jax.jit(_rotate_impl, static_argnums=(0,), donate_argnums=(1,))


def rotate(
    cfg: SketchConfig, state: WindowArrayState, *, donate: bool = False
) -> WindowArrayState:
    """Close the current epoch and open the next ring slot (see
    ``_rotate_impl`` for the full semantics: O(1) ring bookkeeping, oldest-
    epoch eviction, union-cache rebuild, martingale re-base, monotone
    ``epoch_id``). ``donate=True`` reuses the ring buffers in place — safe
    whenever the pre-rotation state is not read again (the ingest layer's
    retire barrier guarantees exactly that)."""
    fn = _rotate_donated if donate else _rotate_jit
    return fn(cfg, state)


def _chats_from_touched_hists(cfg: SketchConfig, hists, solver: str = "newton") -> jnp.ndarray:
    """Per-row MLE Ĉ from touched-register histograms (bin 0 pinned to 0,
    the stored convention): fill bin 0 with the untouched count and run the
    shared histogram MLE — bit-identical to walking the registers again,
    without the second O(K·m) histogram pass."""
    full = hists.at[:, 0].set(cfg.m - jnp.sum(hists, axis=1))
    return dyn_array.estimate_mle_hists(cfg, full, solver=solver)


def _window_slots(state: WindowArrayState, w: int) -> jnp.ndarray:
    """Ring slots of the last w epochs, newest first: head, head-1, ..."""
    e = state.regs.shape[0]
    return (state.head - jnp.arange(w, dtype=jnp.int32)) % e


def window_union_regs(state: WindowArrayState, w: int) -> jnp.ndarray:
    """Exact union registers of the last w epochs, int8[K, m] (pure-JAX path;
    materializes the [w, K, m] gather — the Pallas op streams instead)."""
    return jnp.max(state.regs[_window_slots(state, w)], axis=0)


def _check_w(state: WindowArrayState, w: int) -> int:
    e = state.regs.shape[0]
    w = int(w)
    if not 1 <= w <= e:
        raise ValueError(f"window w={w} out of range [1, E={e}]")
    return w


@functools.partial(jax.jit, static_argnums=(0, 2), static_argnames=("solver",))
def _estimate_subring(cfg: SketchConfig, state: WindowArrayState, w: int, *, solver: str = "newton"):
    return dyn_array.estimate_mle_rows(cfg, window_union_regs(state, w), solver=solver)


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("solver",))
def _estimate_full_ring(cfg: SketchConfig, state: WindowArrayState, *, solver: str = "newton"):
    """Cached path: the union histograms are maintained incrementally, so the
    full-ring read skips union + bincount and goes straight to the MLE."""
    return _chats_from_touched_hists(cfg, state.union_hists, solver=solver)


def estimate_window(
    cfg: SketchConfig, state: WindowArrayState, w: int, *, solver: str = "newton"
) -> jnp.ndarray:
    """Ĉ[K] over the last w <= E epochs (w static, host-side int).

    Union-of-epochs registers -> batched histogram MLE. Bit-identical to
    rebuilding the retained epochs from their element logs (registers are
    max-monoid, estimation is a pure function of the union histogram). The
    full-ring window reads the cached union histograms — same bits, no
    union/bincount pass. Epochs beyond ``filled`` hold r_min everywhere, so
    w > filled clamps harmlessly; untouched windows report Ĉ = 0.
    ``solver`` picks newton / lut / fused (core/estimation.py; the full-ring
    path is histogram-fed, so "fused" applies to sub-ring reads only).
    """
    w = _check_w(state, w)
    if w == state.regs.shape[0]:
        return _estimate_full_ring(cfg, state, solver=solver)
    return _estimate_subring(cfg, state, w, solver=solver)


def estimate_ring_anytime(state: WindowArrayState) -> jnp.ndarray:
    """O(K) anytime read of the full-ring window: the running union
    martingales. Exact §4.3 semantics within the current epoch; re-based to
    the union MLE at every rotation (== ``estimate_window(E)`` at that
    instant). The per-step fast path anomaly scoring consumes."""
    return state.union_chats


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("solver",))
def estimate_epochs_all(
    cfg: SketchConfig, state: WindowArrayState, *, solver: str = "newton"
) -> jnp.ndarray:
    """Per-epoch MLE re-estimates, Ĉ[E, K] — the naive alternative the
    windowed read replaces (E independent solve passes; benchmarked in
    benchmarks/window_array.py). Per-epoch anytime reads are ``state.chats``.
    """
    e, k, m = state.regs.shape
    return dyn_array.estimate_mle_rows(
        cfg, state.regs.reshape(e * k, m), solver=solver
    ).reshape(e, k)


def update_tenants(
    cfg: SketchConfig,
    dcfg: key_directory.DirectoryConfig,
    state: WindowArrayState,
    dir_state: key_directory.DirectoryState,
    tenant_keys,
    ids,
    weights,
    mask=None,
):
    """Sparse-tenant entry: route 64-bit tenant ids through the key directory
    (stamping each routed slot with the window's monotone ``epoch_id`` so
    cold-tenant aging can use the ring as its clock), then run the fused
    keyed update. Returns (state, directory telemetry).
    """
    if dcfg.capacity != state.regs.shape[1]:
        raise ValueError(
            f"directory capacity {dcfg.capacity} != WindowArray rows {state.regs.shape[1]}"
        )
    slots, dir_state = key_directory.route(
        dcfg, dir_state, tenant_keys, mask=mask, epoch=state.epoch_id
    )
    return update_batch(cfg, state, slots, ids, weights, mask=mask), dir_state


def check_ring_aligned(a: WindowArrayState, b: WindowArrayState) -> None:
    """Shared merge validation (single-host AND sharded fronts): two windows
    combine only with matching geometry and an aligned ring clock. Host-side
    entry — head/filled/epoch_id must be concrete."""
    if a.regs.shape != b.regs.shape:
        raise ValueError(
            f"WindowArray merge needs matching (E, K, m), got {a.regs.shape} vs {b.regs.shape}"
        )
    if (int(a.head), int(a.filled), int(a.epoch_id)) != (
        int(b.head),
        int(b.filled),
        int(b.epoch_id),
    ):
        raise ValueError(
            "WindowArray merge needs ring-aligned states (same head/filled/"
            "epoch_id): pods must rotate on a shared clock"
        )


def _merged_arrays(cfg: SketchConfig, regs_a, regs_b):
    """Array tail of the ring-aligned merge, shared with the sharded front
    (runs shard-local there): per-epoch register max, histogram rebuilds,
    MLE re-estimated chats, union-cache rebuild. Returns the six array
    fields of the merged state (ring scalars are the caller's)."""
    e, k, m = regs_a.shape
    regs = jnp.maximum(regs_a, regs_b)
    flat_hists = dyn_array.rebuild_hists(cfg, regs.reshape(e * k, m))
    union_regs = jnp.max(regs, axis=0)
    union_hists = dyn_array.rebuild_hists(cfg, union_regs)
    return (
        regs,
        flat_hists.reshape(e, k, cfg.num_bins),
        _chats_from_touched_hists(cfg, flat_hists).reshape(e, k),
        union_regs,
        union_hists,
        _chats_from_touched_hists(cfg, union_hists),
    )


def merge(cfg: SketchConfig, a: WindowArrayState, b: WindowArrayState) -> WindowArrayState:
    """Cross-pod merge of ring-ALIGNED windows (same E/K/m, same head/filled/
    epoch_id — pods rotate on a shared clock).

    Per-epoch registers max-merge (exact union of that epoch's streams);
    per-epoch histograms rebuild and chats re-estimate via the MLE (running
    martingales are not additive across pods that may share elements, exactly
    as ``dyn_array.merge``); the union cache rebuilds from the merged epochs.
    Host-side entry (concrete head/filled): alignment is checked eagerly.
    """
    check_ring_aligned(a, b)
    regs, hists, chats, union_regs, union_hists, union_chats = _merged_arrays(
        cfg, a.regs, b.regs
    )
    return WindowArrayState(
        regs=regs,
        hists=hists,
        chats=chats,
        union_regs=union_regs,
        union_hists=union_hists,
        union_chats=union_chats,
        head=a.head,
        filled=a.filled,
        epoch_id=a.epoch_id,
    )


def update_reference(
    cfg: SketchConfig, state: WindowArrayState, keys, ids, weights, mask=None
) -> WindowArrayState:
    """Oracle: the K-loop ``dyn_array.update_reference`` applied to the head
    epoch AND the union sub-state (each is a DynArray fed the same keyed
    batch). O(K) dispatches — tests/benchmarks only, never the hot path.
    Host-side entry: ``state.head`` must be concrete.
    """
    head = int(state.head)
    ep = dyn_array.update_reference(
        cfg, epoch_substate(state, head), keys, ids, weights, mask=mask
    )
    un = dyn_array.update_reference(
        cfg, union_substate(state), keys, ids, weights, mask=mask
    )
    return state._replace(
        regs=state.regs.at[head].set(ep.regs),
        hists=state.hists.at[head].set(ep.hists),
        chats=state.chats.at[head].set(ep.chats),
        union_regs=un.regs,
        union_hists=un.hists,
        union_chats=un.chats,
    )
