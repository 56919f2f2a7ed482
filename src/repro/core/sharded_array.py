"""ShardedSketchArray: the [K, m] register matrix sharded over a mesh axis.

``core/sketch_array.py`` stops at a single host: one int8[K, m] matrix, one
device. The paper's headline settings (per-flow anomaly detection, per-user
DAU) want K ~ 1e7 tenants, which is where this module picks up — the row
axis is sharded over a ``"sketch"`` mesh axis with ``shard_map``, and every
operation stays shard-local:

* **update** — the batch (slots, ids, weights) is visible to all shards;
  each shard hash-routes by ``slot // rows_per_shard`` and folds ONLY its
  own rows with the same fused segment scatter-max as the single-host path.
  Row k receives exactly the contributions it would receive unsharded (the
  y-table is key-independent), so the result is BIT-identical to
  ``sketch_array.update`` — the max-monoid argument, verified bitwise in
  tests/test_sharded_array.py.
* **merge** — element-wise max, the cross-pod collective. Exact at any
  scale because every register is a plain max-monoid element; two pods that
  saw overlapping streams merge without double counting.
* **estimate_all** — the vmapped histogram-MLE runs *inside* shard_map on
  each shard's K/S rows: no register gather, no cross-shard traffic, and the
  O(K·2^b) Newton cost is divided by the shard count.

The mesh machinery itself (row specs, shard_map wrapping, hash-routed
dispatch) lives in ``core/sharding.py`` and is shared with the Dyn and
Window sharded fronts (``sharded_dyn_array``, ``sharded_window_array``);
this module is the thinnest instantiation — a single sharded leaf.

Slots come from ``core/key_directory.py`` (sparse 64-bit tenant ids,
collision telemetry, pinned hot keys); ``update_tenants`` fuses routing and
update. Dense in-range slots remain valid inputs, so the single-host tests'
contract embeds unchanged.

The shard axis name is a parameter (default ``"sketch"``): telemetry inside
a training step can reuse an existing mesh axis (e.g. ``"data"``) instead of
building a second mesh over the same devices.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import key_directory, sharding, sketch_array
from .types import SketchArrayState, ShardedArrayState, SketchConfig

AXIS = sharding.AXIS

# Shared-layer geometry helpers, re-exported for existing callers/tests.
num_shards = sharding.num_shards
padded_k = sharding.padded_k


def init(cfg: SketchConfig, k: int, mesh, axis: str = AXIS) -> ShardedArrayState:
    """K fresh sketches, rows sharded over ``axis`` of ``mesh``, each
    device building only its own rows (``sharding.init_rows``)."""
    return ShardedArrayState(
        regs=sharding.init_rows(
            lambda: jnp.full((k, cfg.m), cfg.r_min, dtype=jnp.int8), mesh, 0, axis
        )
    )


def from_array(state: SketchArrayState, mesh, axis: str = AXIS) -> ShardedArrayState:
    """Reshard a single-host SketchArray (pure data movement, same values)."""
    return ShardedArrayState(
        regs=sharding.device_put_rows(state.regs, mesh, 0, axis)
    )


def to_array(state: ShardedArrayState) -> SketchArrayState:
    """Gather back to the single-host form (tests / row extraction)."""
    return SketchArrayState(regs=jax.device_get(state.regs))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _update(cfg: SketchConfig, mesh, axis: str, regs, slots, ids, weights, mask):
    rows = regs.shape[0] // sharding.num_shards(mesh, axis)

    def local(regs_l, slots, ids, w, m):
        # Hash-routed dispatch: this shard owns slot range [lo, lo + rows).
        local_slots, own = sharding.own_slots(slots, rows, axis, m)
        st = sketch_array.update(
            cfg, SketchArrayState(regs=regs_l), local_slots, ids, w, mask=own
        )
        return st.regs

    return sharding.shard_map_rows(
        local,
        mesh,
        in_dims=(0, None, None, None, None),
        out_dims=0,
        axis=axis,
    )(regs, slots, ids, weights, mask)


def update(
    cfg: SketchConfig, mesh, state: ShardedArrayState, slots, ids, weights,
    mask=None, axis: str = AXIS,
) -> ShardedArrayState:
    """One keyed batch into the sharded matrix; bit-identical to unsharded.

    ``slots`` are dense row indices in [0, K) — the output of
    ``key_directory.route`` (or legacy dense keys). Each element updates
    exactly the shard owning its slot; no collective is needed, the register
    state never leaves its shard.
    """
    sharding.check_divisible(state.regs.shape[0], mesh, axis)
    slots = slots.astype(jnp.int32)
    mask = jnp.ones(slots.shape, bool) if mask is None else mask
    regs = _update(cfg, mesh, axis, state.regs, slots, ids, weights, mask)
    return ShardedArrayState(regs=regs)


def update_tenants(
    cfg: SketchConfig,
    dcfg: key_directory.DirectoryConfig,
    mesh,
    state: ShardedArrayState,
    dir_state: key_directory.DirectoryState,
    tenant_keys,
    ids,
    weights,
    mask=None,
    axis: str = AXIS,
):
    """Sparse 64-bit tenant ids in, (sharded state, directory telemetry) out.

    ``tenant_keys`` is a uint32 array or a (lo, hi) uint32 pair (64-bit ids
    pre-split host-side via ``key_directory.split_uint64``).
    """
    if dcfg.capacity != state.regs.shape[0]:
        raise ValueError(
            f"directory capacity {dcfg.capacity} != sharded rows {state.regs.shape[0]}"
        )
    slots, dir_state = key_directory.route(dcfg, dir_state, tenant_keys, mask=mask)
    return update(cfg, mesh, state, slots, ids, weights, mask=mask, axis=axis), dir_state


@functools.partial(jax.jit, static_argnums=(0, 1, 2), static_argnames=("solver",))
def _estimate_with_ci(cfg: SketchConfig, mesh, axis: str, regs, *, solver: str = "newton"):
    def local(regs_l):
        return sketch_array.estimate_all_with_ci(
            cfg, SketchArrayState(regs=regs_l), solver=solver
        )

    # check_vma=False on the newton path only: its lax.while_loop has no
    # replication rule on current JAX (everything here is shard-local so the
    # check is vacuous). The lut solver is while_loop-free, so it keeps the
    # replication check on.
    return sharding.shard_map_rows(
        local,
        mesh,
        in_dims=(0,),
        out_dims=(0, 0, 0),
        axis=axis,
        check_vma=(solver == "lut"),
    )(regs)


def estimate_all_with_ci(
    cfg: SketchConfig, mesh, state: ShardedArrayState, axis: str = AXIS,
    *, solver: str = "newton",
):
    """(Ĉ[K], stddev[K], converged[K]); the solve stays local to each shard
    (``solver`` picks newton / lut, DESIGN.md §8.7 — with lut each shard
    anchors its own grid, so lut results can differ from the single-host
    call within the documented tolerance; newton stays bit-identical)."""
    sharding.check_divisible(state.regs.shape[0], mesh, axis)
    return _estimate_with_ci(cfg, mesh, axis, state.regs, solver=solver)


def estimate_all(
    cfg: SketchConfig, mesh, state: ShardedArrayState, axis: str = AXIS,
    *, solver: str = "newton",
) -> jnp.ndarray:
    """Ĉ for every slot — the sharded form of ``sketch_array.estimate_all``."""
    return estimate_all_with_ci(cfg, mesh, state, axis=axis, solver=solver)[0]


def merge(a: ShardedArrayState, b: ShardedArrayState) -> ShardedArrayState:
    """All-max cross-shard merge: exact union of two sharded sketch fleets.

    Row-wise max monoid, so pods/hosts that built their states independently
    (even over overlapping streams) combine without bias. Shapes must agree —
    same capacity, same m — or the row algebra is meaningless.
    """
    sharding.check_same_shape(a, b, "ShardedSketchArray")
    return ShardedArrayState(regs=jnp.maximum(a.regs, b.regs))
