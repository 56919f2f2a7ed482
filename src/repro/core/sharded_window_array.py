"""Sharded WindowArray: the sliding-window epoch ring past one host.

``core/window_array.py`` holds a ring of E epoch DynArray sub-states plus a
cached union — ``int8[E, K, m]`` registers and ``int32[E, K, 2^b]``
histograms, which at production K is the biggest state in the repo (the
histograms alone are 1 KiB x E x K at b = 8). This module shards every
per-tenant leaf over the ``"sketch"`` mesh axis at its K dimension
(``core/sharding.py`` row_dim 1 for the epoch planes, 0 for the union
cache) while the ring clock — ``head``/``filled``/``epoch_id`` — stays
replicated, so all shards rotate in lockstep; the ROADMAP follow-on to
PR 4.

Why everything stays shard-local (DESIGN.md §8.6): the epoch-plane
max-union is an element-wise reduction over the epoch axis, which commutes
with any partitioning of the K axis — a shard's union plane is exactly the
union of its epoch-plane rows. So:

* **update_batch** — hash-routed like every sharded front: each shard
  masks the replicated batch to its own rows and runs the same two fused
  DynArray updates (head epoch + union cache) via the shared
  ``window_array._apply_update`` tail. All leaves bit-identical to the
  single-host WindowArray (tests/test_sharded_window_array.py).
* **rotate** — per-shard O(1) ring bookkeeping: each shard advances the
  (replicated) head, resets its slice of the slot the head lands on, and
  rebuilds ITS rows of the union cache + re-bases its anytime martingales
  to the surviving union's MLE — ``window_array.rotate`` verbatim on the
  local state, no collective.
* **estimate_window / estimate_ring_anytime** — the sub-ring union + MLE
  and the cached full-ring read run on each shard's rows; the anytime read
  is the sharded ``union_chats``.
* **merge** — ring-aligned cross-pod merge (alignment checked host-side on
  the replicated clock), array tail (``window_array._merged_arrays``)
  shard-local.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import dyn_array, hashing, key_directory, qsketch_dyn, sharding, window_array
from .types import ShardedWindowArrayState, SketchConfig, WindowArrayState

AXIS = sharding.AXIS

# Shared-layer geometry helpers, re-exported like sharded_array's.
num_shards = sharding.num_shards
padded_k = sharding.padded_k

# Row-dim pytree: epoch planes carry K at dim 1, the union cache at dim 0,
# the ring clock is replicated.
DIMS = ShardedWindowArrayState(
    regs=1, hists=1, chats=1,
    union_regs=0, union_hists=0, union_chats=0,
    head=None, filled=None, epoch_id=None,
)
_ARRAY_DIMS = (1, 1, 1, 0, 0, 0)  # the six per-tenant leaves, in state order


def init(cfg: SketchConfig, k: int, e: int, mesh, axis: str = AXIS) -> ShardedWindowArrayState:
    """K tenants x E ring epochs, per-tenant leaves sharded over ``axis``,
    each device building only its own rows (``sharding.init_rows``)."""
    return ShardedWindowArrayState(
        *sharding.init_rows(lambda: window_array.init(cfg, k, e), mesh, DIMS, axis)
    )


def from_array(state: WindowArrayState, mesh, axis: str = AXIS) -> ShardedWindowArrayState:
    """Reshard a single-host WindowArray (pure data movement, same values)."""
    return ShardedWindowArrayState(
        *sharding.device_put_rows(state, mesh, DIMS, axis)
    )


def to_array(state: ShardedWindowArrayState) -> WindowArrayState:
    """Gather back to the single-host form (tests / row extraction)."""
    return WindowArrayState(*jax.device_get(tuple(state)))


def num_epochs(state: ShardedWindowArrayState) -> int:
    """Ring size E."""
    return state.regs.shape[0]


def num_sketches(state: ShardedWindowArrayState) -> int:
    """Total tenant capacity K across all shards."""
    return state.regs.shape[1]


def _local_window(st: ShardedWindowArrayState, arrays) -> WindowArrayState:
    """Assemble a shard-local WindowArrayState from local array leaves plus
    the replicated ring clock (used inside shard_map local bodies)."""
    return WindowArrayState(*arrays, head=st.head, filled=st.filled, epoch_id=st.epoch_id)


def _update_impl(cfg: SketchConfig, mesh, axis: str, state, keys, lo, hi, w, mask):
    rows = state.regs.shape[1] // sharding.num_shards(mesh, axis)

    def local(arrays, head, keys, lo, hi, w, m):
        st = WindowArrayState(*arrays, head=head, filled=jnp.int32(0), epoch_id=jnp.int32(0))
        local_keys, own = sharding.own_slots(keys, rows, axis, m)
        live = qsketch_dyn._live_weight_mask(w, own)
        out = window_array._apply_update(cfg, st, local_keys, lo, hi, w, live)
        return tuple(out)[:6]

    arrays = sharding.shard_map_rows(
        local,
        mesh,
        in_dims=(_ARRAY_DIMS, None, None, None, None, None, None),
        out_dims=_ARRAY_DIMS,
        axis=axis,
    )(tuple(state)[:6], state.head, keys, lo, hi, w, mask)
    return ShardedWindowArrayState(
        *arrays, head=state.head, filled=state.filled, epoch_id=state.epoch_id
    )


_update = jax.jit(_update_impl, static_argnums=(0, 1, 2))
_update_donated = jax.jit(
    _update_impl, static_argnums=(0, 1, 2), donate_argnums=(3,)
)


def update_batch(
    cfg: SketchConfig, mesh, state: ShardedWindowArrayState, keys, ids, weights,
    mask=None, axis: str = AXIS, *, donate: bool = False,
) -> ShardedWindowArrayState:
    """Fold one keyed batch into the current epoch (and the union cache),
    hash-routed; bit-identical to ``window_array.update_batch`` on every
    leaf. Same contract: keys clipped to [0, K), masked / degenerate-weight
    rows dropped before dedup. ``donate=True`` donates the sharded epoch
    planes + union cache for in-place reuse (sharding is unchanged, so
    aliasing is legal); the caller's ``state`` is dead afterwards."""
    sharding.check_divisible(state.regs.shape[1], mesh, axis)
    k = state.regs.shape[1]
    lo, hi = hashing.split_id64(ids)
    w = weights.astype(jnp.float32)
    keys = jnp.clip(keys.astype(jnp.int32), 0, k - 1)
    mask = jnp.ones(keys.shape, bool) if mask is None else mask
    fn = _update_donated if donate else _update
    return fn(cfg, mesh, axis, state, keys, lo, hi, w, mask)


def _rotate_impl(cfg: SketchConfig, mesh, axis: str, state):
    def local(arrays, head, filled, epoch_id):
        st = WindowArrayState(*arrays, head=head, filled=filled, epoch_id=epoch_id)
        return tuple(window_array.rotate(cfg, st))

    # The ring clock comes back out of the local body (replicated out
    # specs): the single-host rotate owns the head/eviction policy, so the
    # sharded wrapper can never desynchronize the clock from the plane the
    # local body actually reset.
    return sharding.shard_map_rows(
        local,
        mesh,
        in_dims=(_ARRAY_DIMS, None, None, None),
        out_dims=_ARRAY_DIMS + (None, None, None),
        axis=axis,
        check_vma=False,  # union-MLE re-base is a lax.while_loop
    )(tuple(state)[:6], state.head, state.filled, state.epoch_id)


_rotate = jax.jit(_rotate_impl, static_argnums=(0, 1, 2))
_rotate_donated = jax.jit(
    _rotate_impl, static_argnums=(0, 1, 2), donate_argnums=(3,)
)


def rotate(
    cfg: SketchConfig, mesh, state: ShardedWindowArrayState, axis: str = AXIS,
    *, donate: bool = False,
) -> ShardedWindowArrayState:
    """Close the current epoch and open the next ring slot, shard-locally.

    Each shard runs ``window_array.rotate`` verbatim on its rows: O(1) ring
    bookkeeping (advance head, reset/evict the slot it lands on), rebuild
    of ITS union-cache rows from the surviving epoch planes, and the MLE
    re-base of its anytime martingales. The replicated ring clock advances
    identically on every shard — no collective, no host sync.
    ``donate=True`` reuses the ring buffers in place; safe once no earlier
    view of the state is read again (the ingest retire barrier's contract).
    """
    fn = _rotate_donated if donate else _rotate
    return ShardedWindowArrayState(*fn(cfg, mesh, axis, state))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3), static_argnames=("solver",))
def _estimate_subring(cfg: SketchConfig, mesh, axis: str, w: int, regs, head, *, solver: str = "newton"):
    def local(regs_l, head):
        st = WindowArrayState(
            regs_l, None, None, None, None, None,
            head=head, filled=jnp.int32(0), epoch_id=jnp.int32(0),
        )
        return dyn_array.estimate_mle_rows(
            cfg, window_array.window_union_regs(st, w), solver=solver
        )

    # check_vma stays off for newton (lax.while_loop, no replication rule)
    # and fused (pallas_call, same); lut is while_loop-free so it keeps the
    # replication check on.
    return sharding.shard_map_rows(
        local, mesh, in_dims=(1, None), out_dims=0, axis=axis,
        check_vma=(solver == "lut"),
    )(regs, head)


@functools.partial(jax.jit, static_argnums=(0, 1, 2), static_argnames=("solver",))
def _estimate_full_ring(cfg: SketchConfig, mesh, axis: str, union_hists, *, solver: str = "newton"):
    def local(hists_l):
        return window_array._chats_from_touched_hists(cfg, hists_l, solver=solver)

    return sharding.shard_map_rows(
        local, mesh, in_dims=(0,), out_dims=0, axis=axis,
        check_vma=(solver == "lut"),
    )(union_hists)


def estimate_window(
    cfg: SketchConfig, mesh, state: ShardedWindowArrayState, w: int, axis: str = AXIS,
    *, solver: str = "newton",
) -> jnp.ndarray:
    """Ĉ[K] over the last w <= E epochs (w static, host-side int), sharded.

    Shard-local epoch-plane union + histogram MLE — the union over epochs
    commutes with row sharding, so each shard's answer is exactly the
    single-host ``window_array.estimate_window`` restricted to its rows
    (bit-identical for the default newton solver; the full-ring w == E reads
    the cached union histograms with no union/bincount pass, same as the
    single-host fast path). ``solver="lut"`` drops the Newton wall — each
    shard anchors its own grid, so lut agreement with the single-host call
    is at the documented tolerance, not bitwise.
    """
    w = window_array._check_w(state, w)
    if w == state.regs.shape[0]:
        return _estimate_full_ring(cfg, mesh, axis, state.union_hists, solver=solver)
    return _estimate_subring(cfg, mesh, axis, w, state.regs, state.head, solver=solver)


def estimate_ring_anytime(state: ShardedWindowArrayState) -> jnp.ndarray:
    """O(K) anytime read of the full-ring window: the running (sharded)
    union martingales — what a per-step anomaly detector consumes."""
    return state.union_chats


def update_tenants(
    cfg: SketchConfig,
    dcfg: key_directory.DirectoryConfig,
    mesh,
    state: ShardedWindowArrayState,
    dir_state: key_directory.DirectoryState,
    tenant_keys,
    ids,
    weights,
    mask=None,
    axis: str = AXIS,
):
    """Sparse-tenant entry: route 64-bit tenant ids through the (replicated)
    key directory — stamping routed slots with the window's monotone
    ``epoch_id`` so cold-tenant aging can use the ring as its clock — then
    run the hash-routed fused update. Returns (state, directory telemetry).
    """
    if dcfg.capacity != state.regs.shape[1]:
        raise ValueError(
            f"directory capacity {dcfg.capacity} != sharded WindowArray rows "
            f"{state.regs.shape[1]}"
        )
    slots, dir_state = key_directory.route(
        dcfg, dir_state, tenant_keys, mask=mask, epoch=state.epoch_id
    )
    return (
        update_batch(cfg, mesh, state, slots, ids, weights, mask=mask, axis=axis),
        dir_state,
    )


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _merge(cfg: SketchConfig, mesh, axis: str, regs_a, regs_b):
    def local(ra, rb):
        return window_array._merged_arrays(cfg, ra, rb)

    return sharding.shard_map_rows(
        local,
        mesh,
        in_dims=(1, 1),
        out_dims=_ARRAY_DIMS,
        axis=axis,
        check_vma=False,  # MLE while_loop in the chat re-estimates
    )(regs_a, regs_b)


def merge(cfg: SketchConfig, mesh, a: ShardedWindowArrayState, b: ShardedWindowArrayState, axis: str = AXIS) -> ShardedWindowArrayState:
    """Cross-pod merge of ring-ALIGNED sharded windows (same E/K/m, same
    head/filled/epoch_id — pods rotate on a shared clock; checked eagerly
    on the replicated ring scalars, exactly as the single-host merge).

    The array tail — per-epoch register max, histogram rebuilds, MLE
    re-estimated chats, union-cache rebuild — is ``window_array``'s own
    ``_merged_arrays``, run shard-local over each shard's rows.
    """
    sharding.check_same_shape(tuple(a)[:6], tuple(b)[:6], "sharded WindowArray")
    window_array.check_ring_aligned(a, b)
    arrays = _merge(cfg, mesh, axis, a.regs, b.regs)
    return ShardedWindowArrayState(
        *arrays, head=a.head, filled=a.filled, epoch_id=a.epoch_id
    )
