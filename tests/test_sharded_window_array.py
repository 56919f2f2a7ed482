"""Sharded WindowArray tests.

Acceptance: every ring/union leaf and every windowed read is bit-identical
to the single-host WindowArray driven with the same batches and rotation
schedule on the 8-device host mesh — including across rotation boundaries
(eviction), the fused union kernel op, ring-aligned merges, and the
misaligned-ring-head rejection (for both the sharded and the single-host
merge — the previously-untested edge case).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SketchConfig,
    key_directory,
    sharded_window_array,
    sharding,
    window_array,
)
from repro.core.key_directory import DirectoryConfig
from repro.kernels import ops
from repro.launch.mesh import make_sketch_mesh
from repro.sketchstream import monitor


@pytest.fixture(scope="module")
def mesh():
    return make_sketch_mesh()  # 8 shards under scripts/test.sh


def test_init_builds_each_shard_in_place_like_device_put_rows(mesh):
    """``init`` through ``sharding.init_rows`` equals the single-host
    ``window_array.init`` placed by ``device_put_rows``, leaf for leaf and
    sharding for sharding, and each device holds only its own K/S rows."""
    cfg, k, e = SketchConfig(m=16, b=8, seed=1), 64, 3
    got = sharded_window_array.init(cfg, k, e, mesh)
    ref = sharding.device_put_rows(
        window_array.init(cfg, k, e), mesh, sharded_window_array.DIMS
    )
    s = sharding.num_shards(mesh)
    dims = jax.tree.leaves(sharded_window_array.DIMS, is_leaf=lambda d: d is None)
    for a, b, d in zip(jax.tree.leaves(got), jax.tree.leaves(ref), dims):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype and a.sharding == b.sharding
        shards = a.addressable_shards
        assert len(shards) == s
        for sh in shards:
            want = a.shape if d is None else a.shape[:d] + (k // s,) + a.shape[d + 1:]
            assert sh.data.shape == want


def test_host_shard_counts_match_own_slots(mesh):
    """``sharding.shard_counts`` (the host's per-shard event count) gives
    each shard what ``own_slots`` masks it down to on the device, slots
    outside [0, K) included (owned by no shard)."""
    k, s = 64, sharding.num_shards(mesh)
    rng = np.random.default_rng(5)
    slots = np.concatenate([rng.integers(0, k, 200), [-3, -1, k, k + 5]]).astype(np.int32)
    local = lambda sl: jnp.sum(sharding.own_slots(sl, k // s)[1]).reshape(1)
    dev = sharding.shard_map_rows(local, mesh, in_dims=(None,), out_dims=0)(jnp.asarray(slots))
    host = sharding.shard_counts(slots, k, s)
    np.testing.assert_array_equal(host, np.asarray(dev))
    assert host.sum() == 200


def _stream(n, k, seed):
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(rng.integers(0, k, n, dtype=np.int32))
    ids = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
    w = jnp.asarray((rng.gamma(1.0, 2.0, n) + 1e-5).astype(np.float32))
    return keys, ids, w


def _drive_pair(cfg, k, e, mesh, n_epochs, batches_per_epoch=2, batch=500, seed=0):
    """Drive a sharded state and the single-host reference with identical
    batches and rotations; returns (sharded, reference)."""
    sh = sharded_window_array.init(cfg, k, e, mesh)
    ref = window_array.init(cfg, k, e)
    for ep in range(n_epochs):
        for i in range(batches_per_epoch):
            keys, ids, w = _stream(batch, k, seed=seed + 31 * ep + i)
            sh = sharded_window_array.update_batch(cfg, mesh, sh, keys, ids, w)
            ref = window_array.update_batch(cfg, ref, keys, ids, w)
        if ep < n_epochs - 1:
            sh = sharded_window_array.rotate(cfg, mesh, sh)
            ref = window_array.rotate(cfg, ref)
    return sh, ref


def _assert_states_equal(sh, ref):
    for name in ("regs", "hists", "chats", "union_regs", "union_hists", "union_chats"):
        np.testing.assert_array_equal(
            np.asarray(getattr(sh, name)), np.asarray(getattr(ref, name)),
            err_msg=f"leaf {name} diverged",
        )
    assert (int(sh.head), int(sh.filled), int(sh.epoch_id)) == (
        int(ref.head), int(ref.filled), int(ref.epoch_id),
    )


# ---------------------------------------------------------------------------
# acceptance: update/rotate/estimate vs the single-host WindowArray, bitwise
# ---------------------------------------------------------------------------


def test_ring_bit_identical_across_rotations(mesh):
    cfg = SketchConfig(m=96, b=8, seed=31)  # ragged m
    k, e = sharding.padded_k(50, mesh), 3
    # e + 2 epochs: the ring wraps, so eviction + union rebuild are on-path.
    sh, ref = _drive_pair(cfg, k, e, mesh, n_epochs=e + 2)
    _assert_states_equal(sh, ref)
    for w in range(1, e + 1):
        np.testing.assert_array_equal(
            np.asarray(sharded_window_array.estimate_window(cfg, mesh, sh, w)),
            np.asarray(window_array.estimate_window(cfg, ref, w)),
            err_msg=f"estimate_window({w}) diverged",
        )
    np.testing.assert_array_equal(
        np.asarray(sharded_window_array.estimate_ring_anytime(sh)),
        np.asarray(window_array.estimate_ring_anytime(ref)),
    )
    with pytest.raises(ValueError, match="out of range"):
        sharded_window_array.estimate_window(cfg, mesh, sh, e + 1)


def test_masked_updates_and_reshard_roundtrip(mesh):
    cfg = SketchConfig(m=64, b=8, seed=33)
    k, e = sharding.padded_k(24, mesh), 2
    keys, ids, w = _stream(400, k, seed=5)
    mask = jnp.asarray(np.random.default_rng(3).random(400) < 0.5)
    sh = sharded_window_array.update_batch(
        cfg, mesh, sharded_window_array.init(cfg, k, e, mesh), keys, ids, w, mask=mask
    )
    ref = window_array.update_batch(
        cfg, window_array.init(cfg, k, e), keys, ids, w, mask=mask
    )
    _assert_states_equal(sh, ref)
    _assert_states_equal(sharded_window_array.to_array(sh), ref)
    _assert_states_equal(sharded_window_array.from_array(ref, mesh), ref)
    assert sharded_window_array.num_epochs(sh) == e
    assert sharded_window_array.num_sketches(sh) == k


def test_window_union_kernel_op_bit_identity(mesh):
    cfg = SketchConfig(m=64, b=8, seed=35)
    k, e = sharding.padded_k(16, mesh), 4
    sh, ref = _drive_pair(cfg, k, e, mesh, n_epochs=e + 1, seed=7)
    for w in (1, 2, 3):
        np.testing.assert_array_equal(
            np.asarray(ops.sharded_window_union_estimate_op(cfg, mesh, sh, w)),
            np.asarray(ops.window_union_estimate_op(cfg, ref, w)),
            err_msg=f"sharded union op diverged at w={w}",
        )


# ---------------------------------------------------------------------------
# merges: ring-aligned bit-identity + misaligned-head rejection
# ---------------------------------------------------------------------------


def test_merge_matches_single_host(mesh):
    cfg = SketchConfig(m=64, b=8, seed=41)
    k, e = sharding.padded_k(24, mesh), 3
    sh_a, ref_a = _drive_pair(cfg, k, e, mesh, n_epochs=e + 1, seed=11)
    sh_b, ref_b = _drive_pair(cfg, k, e, mesh, n_epochs=e + 1, seed=211)
    _assert_states_equal(
        sharded_window_array.merge(cfg, mesh, sh_a, sh_b),
        window_array.merge(cfg, ref_a, ref_b),
    )


def test_merge_rejects_misaligned_ring_heads(mesh):
    """Pods must rotate on a shared clock: one extra rotation on either side
    desynchronizes head/epoch_id and BOTH merges (sharded and single-host)
    must refuse — the previously-untested cross-shard edge case."""
    cfg = SketchConfig(m=64, b=8, seed=43)
    k, e = sharding.padded_k(16, mesh), 3
    sh_a, ref_a = _drive_pair(cfg, k, e, mesh, n_epochs=2, seed=13)
    sh_b, ref_b = _drive_pair(cfg, k, e, mesh, n_epochs=2, seed=113)
    sh_b = sharded_window_array.rotate(cfg, mesh, sh_b)
    ref_b = window_array.rotate(cfg, ref_b)
    with pytest.raises(ValueError, match="ring-aligned"):
        sharded_window_array.merge(cfg, mesh, sh_a, sh_b)
    with pytest.raises(ValueError, match="ring-aligned"):
        window_array.merge(cfg, ref_a, ref_b)
    # A full ring of extra rotations brings the head back around but leaves
    # epoch_id desynchronized — still misaligned (the eviction clocks
    # disagree even though the ring pointers coincide).
    for _ in range(e):
        ref_b = window_array.rotate(cfg, ref_b)
        sh_b = sharded_window_array.rotate(cfg, mesh, sh_b)
    assert int(ref_b.head) != int(ref_a.head) or int(ref_b.epoch_id) != int(ref_a.epoch_id)
    with pytest.raises(ValueError, match="ring-aligned"):
        window_array.merge(cfg, ref_a, ref_b)
    with pytest.raises(ValueError, match="ring-aligned"):
        sharded_window_array.merge(cfg, mesh, sh_a, sh_b)
    with pytest.raises(ValueError, match="matching"):
        sharded_window_array.merge(
            cfg, mesh, sh_a, sharded_window_array.init(cfg, k, e + 1, mesh)
        )


# ---------------------------------------------------------------------------
# sparse tenants + monitor + train threading
# ---------------------------------------------------------------------------


def test_sparse_tenants_stamp_epochs(mesh):
    cfg = SketchConfig(m=64, b=8, seed=45)
    dcfg = DirectoryConfig(capacity=sharding.padded_k(128, mesh), seed=47)
    rng = np.random.default_rng(15)
    tenants = rng.integers(2**33, 2**64, 200, dtype=np.uint64)
    keys = key_directory.split_uint64(tenants)
    ids = jnp.asarray(rng.integers(0, 2**32, 200, dtype=np.uint32))
    w = jnp.asarray(rng.uniform(0.5, 1.5, 200).astype(np.float32))

    sh = sharded_window_array.init(cfg, dcfg.capacity, 3, mesh)
    sh = sharded_window_array.rotate(cfg, mesh, sh)  # epoch_id -> 1
    dstate = key_directory.init(dcfg)
    sh, dstate = sharded_window_array.update_tenants(
        cfg, dcfg, mesh, sh, dstate, keys, ids, w
    )
    assert int(dstate.n_routed) == 200
    touched = np.asarray(dstate.last_touch)
    assert (touched[touched >= 0] == 1).all()  # stamped with the ring clock

    ref = window_array.rotate(cfg, window_array.init(cfg, dcfg.capacity, 3))
    slots = key_directory.route_slots(dcfg, keys)
    ref = window_array.update_batch(cfg, ref, slots, ids, w)
    _assert_states_equal(sh, ref)


def test_sharded_window_monitor_roundtrip(mesh):
    cfg = SketchConfig(m=64, b=8, seed=61)
    mon = monitor.ShardedWindowMonitor.for_mesh(cfg, 64, 3, mesh, evict_after=2)
    ref_mon = monitor.WindowMonitor(cfg, mon.dcfg, 3, evict_after=2)
    rng = np.random.default_rng(26)
    n = 4000
    tkeys = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
    ids = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
    w = jnp.asarray(rng.uniform(0.5, 1.5, n).astype(np.float32))
    mask = jnp.asarray(np.arange(n) < 3600)

    st = mon.update(mon.init(), tkeys, ids, w, mask=mask)
    ref = ref_mon.update(ref_mon.init(), tkeys, ids, w, mask=mask)
    assert int(st.n_seen) == 3600
    np.testing.assert_array_equal(
        np.asarray(mon.estimate(st)), np.asarray(ref_mon.estimate(ref))
    )
    np.testing.assert_array_equal(
        np.asarray(mon.estimate(st, w=2)), np.asarray(ref_mon.estimate(ref, w=2))
    )
    m = mon.metrics(st)
    assert int(m["tenant_elements_seen"]) == 3600
    assert int(m["tenant_window_epoch"]) == 0

    # Rotate the live epoch out: the window empties, aging releases claims.
    for _ in range(3):
        st = mon.rotate(st)
        ref = ref_mon.rotate(ref)
    np.testing.assert_array_equal(
        np.asarray(mon.estimate(st)), np.asarray(ref_mon.estimate(ref))
    )
    np.testing.assert_array_equal(np.asarray(mon.estimate(st)), 0.0)
    assert int(mon.metrics(st)["tenant_slots_claimed"]) == 0

    merged = mon.merge(st, mon.update(st, tkeys, ids, w, mask=mask))
    assert int(merged.n_seen) == 2 * 3600 + 3600


def test_train_step_threads_sharded_window_telemetry(mesh):
    from repro import configs
    from repro.models import common as mcommon, transformer
    from repro.sketchstream.monitor import TelemetryState
    from repro.train import optimizer, train_step as ts

    mcfg = configs.smoke_config("h2o-danube-1.8b")
    params = mcommon.init_params(transformer.model_defs(mcfg), jax.random.PRNGKey(6))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    rng = np.random.default_rng(27)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, mcfg.vocab, (4, 16)), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, mcfg.vocab, (4, 16)), jnp.int32),
        "doc_ids": jnp.asarray(rng.integers(0, 2**32, (4,), dtype=np.uint32)),
    }
    skc = SketchConfig(m=64, b=8, seed=63)
    mon = monitor.ShardedWindowMonitor.for_mesh(skc, 256, 4, mesh)
    ocfg = optimizer.OptConfig(lr=1e-3, warmup_steps=0)
    step = jax.jit(ts.make_train_step(mcfg, ocfg, None, sketch_cfg=skc, tenant_monitor=mon))
    opt, comp, sk = ts.init_states(mcfg, ocfg, params, sketch_cfg=skc, tenant_monitor=mon)
    assert isinstance(sk, TelemetryState)

    _, _, _, sk, metrics = step(params, opt, comp, sk, batch)
    assert int(sk.tenants.n_seen) == 64
    assert "tenant_window_weight" in metrics
    est = np.asarray(mon.estimate(sk.tenants))
    assert (est > 0).sum() == 4  # 4 documents -> exactly 4 live rows

    # Epoch clock outside the jit'd step, as with the single-host monitor.
    sk = TelemetryState(scalar=sk.scalar, tenants=mon.rotate(sk.tenants))
    _, _, _, sk, metrics = step(params, opt, comp, sk, batch)
    assert int(metrics["tenant_window_epoch"]) == 1
