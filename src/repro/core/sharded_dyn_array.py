"""ShardedDynArray: per-tenant O(K)-anytime estimates past one host.

``core/dyn_array.py`` removes the per-query Newton cost with per-key §4.3
martingales, but its state — int8[K, m] registers, int32[K, 2^b] histograms,
f32[K] chats — still lives on one host. This module shards all three leaves
row-wise over a ``"sketch"`` mesh axis via the shared sharding layer
(``core/sharding.py``), the ROADMAP follow-on to PR 3: per-shard chats plus
``merge_disjoint`` make the sharding EXACT for key-partitioned streams.

Every operation stays shard-local, and every shard runs the single-host
container code verbatim on its K/S rows:

* **update_batch** — the replicated batch is hash-routed: each shard masks
  to the slots it owns (``sharding.own_slots``) and runs the same fused
  ``dyn_array._apply_update`` tail (dedup, batch-start q_R, scatter-max,
  incremental histogram moves, martingale accumulation). Registers,
  histograms AND chats are bit-identical to the single-host DynArray: the
  per-(key, id) dedup groups and the per-key q_R rows are untouched by the
  restriction to owned slots, and non-owned elements contribute exact +0.0
  no-ops to the chat scatter-add (tests/test_sharded_dyn_array.py).
* **estimate_all** — a pure O(K) read of the sharded chats; nothing moves.
* **merge** (possibly-overlapping streams) — register max + shard-local
  histogram rebuild + shard-local per-key MLE re-estimate, mirroring
  ``dyn_array.merge`` row for row.
* **merge_disjoint** (key-partitioned fleets) — chats ADD exactly (the
  per-key martingales telescope across element-disjoint sub-streams,
  DESIGN.md §8.4); overlapping partitions are rejected eagerly when the
  states are concrete (a row live in both fleets means the partition
  contract is broken).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import dyn_array, estimation, hashing, key_directory, qsketch_dyn, sharding
from .types import DynArrayState, ShardedDynArrayState, SketchConfig

AXIS = sharding.AXIS

# Shared-layer geometry helpers, re-exported like sharded_array's.
num_shards = sharding.num_shards
padded_k = sharding.padded_k

# Row-dim pytree: every leaf carries K at dim 0.
DIMS = ShardedDynArrayState(regs=0, hists=0, chats=0)


def init(cfg: SketchConfig, k: int, mesh, axis: str = AXIS) -> ShardedDynArrayState:
    """K fresh Dyn sketches, all three leaves row-sharded over ``axis``,
    each device building only its own rows (``sharding.init_rows``)."""
    return ShardedDynArrayState(
        *sharding.init_rows(lambda: dyn_array.init(cfg, k), mesh, DIMS, axis)
    )


def from_array(state: DynArrayState, mesh, axis: str = AXIS) -> ShardedDynArrayState:
    """Reshard a single-host DynArray (pure data movement, same values)."""
    return ShardedDynArrayState(
        *sharding.device_put_rows(state, mesh, DIMS, axis)
    )


def to_array(state: ShardedDynArrayState) -> DynArrayState:
    """Gather back to the single-host form (tests / row extraction)."""
    return DynArrayState(*jax.device_get(tuple(state)))


def num_sketches(state: ShardedDynArrayState) -> int:
    """Total tenant capacity K across all shards."""
    return state.regs.shape[0]


def _update_impl(cfg: SketchConfig, mesh, axis: str, state, keys, lo, hi, w, mask):
    rows = state.regs.shape[0] // sharding.num_shards(mesh, axis)

    def local(st, keys, lo, hi, w, m):
        local_keys, own = sharding.own_slots(keys, rows, axis, m)
        live = qsketch_dyn._live_weight_mask(w, own)
        # Per-element q_R against the element's key's batch-start histogram
        # row — gathered from THIS shard's rows; identical bits to the
        # single-host gather for every owned element (non-owned elements are
        # dead and their q is never consumed).
        q = qsketch_dyn._q_update_prob(cfg, st.hists[local_keys], w)
        return tuple(
            dyn_array._apply_update(cfg, st, local_keys, lo, hi, w, live, q)
        )

    return ShardedDynArrayState(
        *sharding.shard_map_rows(
            local,
            mesh,
            in_dims=(DynArrayState(0, 0, 0), None, None, None, None, None),
            out_dims=(0, 0, 0),
            axis=axis,
        )(DynArrayState(*state), keys, lo, hi, w, mask)
    )


_update = jax.jit(_update_impl, static_argnums=(0, 1, 2))
_update_donated = jax.jit(
    _update_impl, static_argnums=(0, 1, 2), donate_argnums=(3,)
)


def update_batch(
    cfg: SketchConfig, mesh, state: ShardedDynArrayState, keys, ids, weights,
    mask=None, axis: str = AXIS, *, donate: bool = False,
) -> ShardedDynArrayState:
    """One fused keyed batch, hash-routed; bit-identical to the single-host
    ``dyn_array.update_batch`` on every state leaf (chats included).

    Same contract: ``keys`` are dense row indices in [0, K) (clipped),
    masked / degenerate-weight rows are dropped before dedup. Each element
    updates exactly the shard owning its row; no collective runs.
    ``donate=True`` donates the sharded state leaves for in-place buffer
    reuse (sharding is unchanged row-in/row-out, so aliasing is legal); the
    caller's ``state`` is dead afterwards — the steady-state ingest mode.
    """
    sharding.check_divisible(state.regs.shape[0], mesh, axis)
    k = state.regs.shape[0]
    lo, hi = hashing.split_id64(ids)
    w = weights.astype(jnp.float32)
    keys = jnp.clip(keys.astype(jnp.int32), 0, k - 1)
    mask = jnp.ones(keys.shape, bool) if mask is None else mask
    fn = _update_donated if donate else _update
    return fn(cfg, mesh, axis, state, keys, lo, hi, w, mask)


def estimate_all(state: ShardedDynArrayState) -> jnp.ndarray:
    """Ĉ for every sketch: the O(K)-anytime read of the sharded martingales
    (still sharded — callers sum/slice in place or ``device_get`` a view)."""
    return state.chats


@functools.partial(jax.jit, static_argnums=(0, 1, 2), static_argnames=("solver",))
def _estimate_mle(cfg: SketchConfig, mesh, axis: str, regs, hists, *, solver: str = "newton"):
    def local(regs_l, hists_l):
        if solver == "lut":
            full = hists_l.at[:, 0].set(cfg.m - jnp.sum(hists_l, axis=1))
            return estimation.estimate_hists(cfg, full, kind="routed", solver="lut")
        return dyn_array.estimate_mle_rows(cfg, regs_l, solver=solver)

    # check_vma=False on the newton path only: the MLE Newton is a
    # lax.while_loop (no replication rule); the solve is shard-local so the
    # check is vacuous. The lut solver is while_loop-free and reads the
    # maintained histograms — replication check stays on.
    return sharding.shard_map_rows(
        local, mesh, in_dims=(0, 0), out_dims=0, axis=axis,
        check_vma=(solver == "lut"),
    )(regs, hists)


def estimate_mle_all(
    cfg: SketchConfig, mesh, state: ShardedDynArrayState, axis: str = AXIS,
    *, solver: str = "newton",
) -> jnp.ndarray:
    """Per-key histogram-MLE re-estimate, Ĉ[K]; shard-local solve (the
    O(K·2^b) cost divides by the shard count). Use after cross-fleet
    ``merge`` or as a self-check — the hot path reads ``estimate_all``.
    ``solver="lut"`` reads each shard's maintained histograms (no register
    walk, no while_loop; the lut grid is per-row so the answer is batch-
    independent mathematically, but the per-shard GEMM tiles differently
    than the single-host call's, so agreement is at f32 rounding — within
    the documented tolerance — not bitwise)."""
    return _estimate_mle(cfg, mesh, axis, state.regs, state.hists, solver=solver)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _merge(cfg: SketchConfig, mesh, axis: str, a, b):
    def local(a_l, b_l):
        return tuple(dyn_array.merge(cfg, a_l, b_l))

    return ShardedDynArrayState(
        *sharding.shard_map_rows(
            local,
            mesh,
            in_dims=(DynArrayState(0, 0, 0), DynArrayState(0, 0, 0)),
            out_dims=(0, 0, 0),
            axis=axis,
            check_vma=False,  # MLE while_loop inside
        )(DynArrayState(*a), DynArrayState(*b))
    )


def merge(cfg: SketchConfig, mesh, a: ShardedDynArrayState, b: ShardedDynArrayState, axis: str = AXIS) -> ShardedDynArrayState:
    """Merge two sharded fleets sketching possibly-OVERLAPPING sub-streams:
    register max (exact union), shard-local histogram rebuild, shard-local
    per-key MLE re-estimated chats — ``dyn_array.merge`` row for row
    (running martingales are not additive across overlapping streams,
    DESIGN.md §8.4)."""
    sharding.check_same_shape(a, b, "ShardedDynArray")
    return _merge(cfg, mesh, axis, a, b)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _merge_disjoint(cfg: SketchConfig, mesh, axis: str, a, b):
    def local(a_l, b_l):
        return tuple(dyn_array.merge_disjoint(cfg, a_l, b_l))

    return ShardedDynArrayState(
        *sharding.shard_map_rows(
            local,
            mesh,
            in_dims=(DynArrayState(0, 0, 0), DynArrayState(0, 0, 0)),
            out_dims=(0, 0, 0),
            axis=axis,
        )(DynArrayState(*a), DynArrayState(*b))
    )


def merge_disjoint(
    cfg: SketchConfig, mesh, a: ShardedDynArrayState, b: ShardedDynArrayState,
    axis: str = AXIS, check_partition: bool = True,
) -> ShardedDynArrayState:
    """Merge fleets whose streams are KEY-partitioned: chats ADD exactly.

    The production sharding contract (DESIGN.md §8.4): a tenant's stream
    lands on exactly one fleet, so per-key martingales telescope across
    fleets — Ĉ_merged = Ĉ_a + Ĉ_b with no MLE. Registers max-merge and
    histograms rebuild shard-locally. Overlapping partitions (a key row
    live in BOTH fleets) are rejected eagerly by default — this is the
    production fleet merge, so the strict contract is on unless the caller
    explicitly owns an element-disjoint-but-key-shared invariant
    (``check_partition=False``).
    """
    sharding.check_same_shape(a, b, "ShardedDynArray")
    if check_partition:
        dyn_array.check_disjoint_rows(a, b)
    return _merge_disjoint(cfg, mesh, axis, a, b)


def update_tenants(
    cfg: SketchConfig,
    dcfg: key_directory.DirectoryConfig,
    mesh,
    state: ShardedDynArrayState,
    dir_state: key_directory.DirectoryState,
    tenant_keys,
    ids,
    weights,
    mask=None,
    axis: str = AXIS,
):
    """Sparse-tenant entry: route 64-bit tenant ids through the (replicated)
    key directory, then run the hash-routed fused update. Returns
    (sharded state, directory telemetry) — the same production contract as
    ``sharded_array.update_tenants``."""
    if dcfg.capacity != state.regs.shape[0]:
        raise ValueError(
            f"directory capacity {dcfg.capacity} != sharded DynArray rows "
            f"{state.regs.shape[0]}"
        )
    slots, dir_state = key_directory.route(dcfg, dir_state, tenant_keys, mask=mask)
    return (
        update_batch(cfg, mesh, state, slots, ids, weights, mask=mask, axis=axis),
        dir_state,
    )
