"""Reusable row-sharding layer for the keyed sketch containers.

PR 2 hard-coded the mesh machinery inside ``core/sharded_array.py``: a
``"sketch"`` mesh axis, row partitioning of the ``[K, m]`` register matrix,
hash-routed batch dispatch, all-max merge, and shard-local estimation. The
Dyn and Window containers (PRs 3-4) want exactly the same machinery — their
states are just bigger pytrees (histograms, chats, epoch rings) with the
same "row k belongs to exactly one shard" geometry. This module extracts
that machinery so every sharded front (``sharded_array``,
``sharded_dyn_array``, ``sharded_window_array``) shares one implementation:

* **Row specs** (``spec``, ``tree_specs``) — a leaf's partitioning is
  described by the index of its K axis (``row_dim``; ``None`` = replicated
  scalar/telemetry). ``DynArrayState`` leaves are all ``row_dim=0``;
  ``WindowArrayState`` epoch planes are ``row_dim=1`` with replicated ring
  scalars.
* **Placement** (``device_put_rows``) — reshard a host pytree onto the mesh
  (pure data movement, values unchanged); ``init_rows`` builds a fresh
  state straight into its row shardings, so no device ever holds more
  than its own rows (a state larger than one chip can only be made so).
* **shard_map wrapping** (``shard_map_rows``) — wrap a *shard-local*
  function so it runs per shard over row-sharded pytrees; replicated args
  (batches, ring scalars) are broadcast. The local function sees plain
  unsharded arrays of K/S rows and reuses the single-host container code
  verbatim — which is what makes bit-identity provable instead of hoped-for.
* **Hash-routed dispatch** (``own_slots``) — inside a local function, mask
  the replicated batch down to the slot range this shard owns and rebase
  slots to local row indices. Every element updates exactly the shard that
  owns its row; no collective is needed and register state never leaves its
  shard. ``shard_counts`` is the same layout on the host (telemetry).
* **All-max merge** — cross-pod merges stay element-wise ``jnp.maximum``
  on the sharded arrays themselves (the max monoid needs no resharding);
  ``check_same_shape`` is the shared validation.

The shard axis name is a parameter everywhere (default ``"sketch"``):
telemetry embedded in a training step can reuse an existing mesh axis (e.g.
``"data"``) instead of building a second mesh over the same devices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

AXIS = "sketch"


def num_shards(mesh, axis: str = AXIS) -> int:
    """Shard count of ``axis`` in ``mesh`` (host-side int)."""
    return int(mesh.shape[axis])


def padded_k(k: int, mesh, axis: str = AXIS) -> int:
    """Round a tenant capacity up to a shard multiple (rows must divide)."""
    s = num_shards(mesh, axis)
    return ((k + s - 1) // s) * s


def check_divisible(k: int, mesh, axis: str = AXIS) -> None:
    """Raise unless K rows split evenly over the ``axis`` shard count."""
    s = num_shards(mesh, axis)
    if k % s:
        raise ValueError(
            f"K={k} rows must be divisible by the '{axis}' axis shard count "
            f"({s}); round up with sharding.padded_k"
        )


def spec(row_dim: int | None, axis: str = AXIS) -> P:
    """PartitionSpec sharding one named dimension: ``axis`` at ``row_dim``,
    everything else replicated. ``row_dim=None`` is a fully replicated leaf
    (ring scalars, directory telemetry)."""
    if row_dim is None:
        return P()
    return P(*((None,) * row_dim), axis)


def tree_specs(row_dims, axis: str = AXIS):
    """Map a pytree of row dims (int | None) to a pytree of PartitionSpecs.

    ``row_dims`` mirrors the state pytree: e.g. for a ``DynArrayState``
    pass ``DynArrayState(regs=0, hists=0, chats=0)``; for a
    ``WindowArrayState`` the epoch planes are 1 and the ring scalars None.
    ints are leaves here, so ``jax.tree.map`` cannot be used directly —
    this maps with ``is_leaf`` accepting None.
    """
    return jax.tree.map(
        lambda d: spec(d, axis), row_dims, is_leaf=lambda d: d is None
    )


def device_put_rows(tree, mesh, row_dims, axis: str = AXIS):
    """Reshard a pytree onto ``mesh`` row-sharded per ``row_dims`` (pure data
    movement, same values). The K dimension of every sharded leaf must
    divide the shard count. Leaf-wise: ``row_dims`` only has to match the
    tree's leaf order, not its container types (a DynArrayState can be
    placed with ShardedDynArrayState dims)."""
    leaves, treedef = jax.tree.flatten(tree)
    dims = jax.tree.leaves(row_dims, is_leaf=lambda d: d is None)
    if len(leaves) != len(dims):
        raise ValueError(
            f"row_dims has {len(dims)} leaves for a tree of {len(leaves)}"
        )
    out = []
    for leaf, d in zip(leaves, dims):
        if d is not None:
            check_divisible(leaf.shape[d], mesh, axis)
        out.append(jax.device_put(leaf, NamedSharding(mesh, spec(d, axis))))
    return jax.tree.unflatten(treedef, out)


def init_rows(init_fn, mesh, row_dims, axis: str = AXIS):
    """A fresh state built in place on ``mesh``: ``init_fn()`` (the
    container's own single-host ``init``, closed over its arguments) jitted
    with each leaf's row sharding as its output sharding, so every device
    fills only its own K/S rows. Values and shardings are those of
    ``device_put_rows(init_fn(), mesh, row_dims, axis)``; ``row_dims`` is
    matched leaf-wise, as there."""
    fill, treedef = init_rows_jit(init_fn, mesh, row_dims, axis)
    return jax.tree.unflatten(treedef, fill())


def init_rows_jit(init_fn, mesh, row_dims, axis: str = AXIS):
    """``init_rows``' executable, not yet run: (the jitted fill of the
    state's leaves, in leaf order; the state's treedef)."""
    leaves, treedef = jax.tree.flatten(jax.eval_shape(init_fn))
    dims = jax.tree.leaves(row_dims, is_leaf=lambda d: d is None)
    if len(leaves) != len(dims):
        raise ValueError(
            f"row_dims has {len(dims)} leaves for a tree of {len(leaves)}"
        )
    for leaf, d in zip(leaves, dims):
        if d is not None:
            check_divisible(leaf.shape[d], mesh, axis)
    fill = jax.jit(
        lambda: jax.tree.leaves(init_fn()),
        out_shardings=[NamedSharding(mesh, spec(d, axis)) for d in dims],
    )
    return fill, treedef


def shard_map_rows(
    fn,
    mesh,
    in_dims,
    out_dims,
    axis: str = AXIS,
    check_vma: bool = True,
):
    """Wrap a shard-local ``fn`` over row-sharded pytrees.

    ``in_dims`` / ``out_dims`` are tuples (one entry per positional arg /
    output) of row-dim pytrees as in ``tree_specs``. The wrapped function
    receives each sharded leaf as a plain array of K/S rows and each
    replicated leaf whole, and must return outputs matching ``out_dims``.

    ``check_vma=False`` is needed whenever the local body contains a
    ``lax.while_loop`` (the Newton/MLE solvers have no replication rule on
    current JAX); everything these containers run locally is shard-local,
    so the check is vacuous there.
    """
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=tuple(tree_specs(d, axis) for d in in_dims),
        out_specs=tuple(tree_specs(d, axis) for d in out_dims)
        if isinstance(out_dims, tuple)
        else tree_specs(out_dims, axis),
        check_vma=check_vma,
    )


def own_slots(slots, rows: int, axis: str = AXIS, mask=None):
    """Hash-routed dispatch, called INSIDE a shard-local function.

    This shard owns the contiguous global slot range
    ``[axis_index * rows, (axis_index + 1) * rows)``. Returns
    ``(local_slots, own)`` where ``own`` masks the replicated batch down to
    the elements this shard owns (intersected with the caller's ``mask``)
    and ``local_slots = slots - lo`` rebases them to local row indices
    (clipped to [0, rows) so non-own elements stay safe gather/scatter
    no-ops under their dead mask).
    """
    lo = (jax.lax.axis_index(axis) * rows).astype(jnp.int32)
    own = (slots >= lo) & (slots < lo + rows)
    if mask is not None:
        own = own & mask
    return jnp.clip(slots - lo, 0, rows - 1), own


def shard_counts(slots: np.ndarray, k: int, shards: int) -> np.ndarray:
    """Host-side twin of ``own_slots``' layout, for telemetry: how many of
    the host array ``slots`` (global slots of a K-row state) each of the
    ``shards`` contiguous row ranges owns. A slot outside [0, K) belongs to
    no shard, as on the device."""
    slots = np.asarray(slots)
    live = slots[(slots >= 0) & (slots < k)]
    return np.bincount(live // (k // shards), minlength=shards)


def check_same_shape(a, b, what: str) -> None:
    """Shared merge validation: two sharded states must agree on every leaf
    shape (same K/m/E geometry) or the row algebra is meaningless."""
    sa = [x.shape for x in jax.tree.leaves(a)]
    sb = [x.shape for x in jax.tree.leaves(b)]
    if sa != sb:
        raise ValueError(f"{what} merge needs matching shapes, got {sa} vs {sb}")
