"""Streaming ingest pipeline (sketchstream/ingest.py) tests.

Acceptance contracts:

1. **Bit-identity** — any interleaving of pushes (random sizes), flushes and
   rotations through the pipeline produces container states bit-identical to
   a synchronous element-log oracle driven over the SAME micro-batch
   partition (the partition is deterministic: FIFO fill of the fixed
   ``batch_size`` staging shape; a flush/rotate seals the partial batch).
   This includes a FORCED-backpressure schedule (the readiness probe pinned
   to "never ready", so every dispatch beyond ``queue_depth`` blocks), the
   Pallas kernel route, and the sharded fronts on the 8-device host mesh.
2. **Drop determinism** — with policy="drop" and a never-ready queue,
   exactly the first ``queue_depth`` batches are admitted, everything after
   is counted in ``dropped`` (never silently lost), and the settled state
   equals the oracle over the admitted prefix.
3. **Donation is real** — the ``donate=True`` update/rotate entry points
   reuse the input state buffers in place (``unsafe_buffer_pointer``
   equality), the no-copy guarantee the sustained-Mops headline rests on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SketchConfig,
    dyn_array,
    key_directory,
    sharded_dyn_array,
    sharded_window_array,
    window_array,
)
from repro.core.key_directory import DirectoryConfig
from repro.kernels import ops
from repro.launch.mesh import make_sketch_mesh
from repro.sketchstream import ingest

CFG = SketchConfig(m=64, b=6, seed=3)
K = 64


@pytest.fixture(scope="module")
def mesh():
    return make_sketch_mesh()  # 8 shards under scripts/test.sh


def _elements(n, seed, k=K):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, k, n, dtype=np.int32)
    ids = rng.integers(0, 2**32, n, dtype=np.uint32)
    w = (rng.gamma(1.0, 2.0, n) + 1e-5).astype(np.float32)
    return keys, ids, w


def _partition(keys, ids, w, bsz):
    """The micro-batch partition the pipeline's FIFO fill induces on a
    contiguous element log (unpadded tail — the mask no-op contract makes
    the pipeline's mask-padded tail equivalent)."""
    return [
        (keys[i : i + bsz], ids[i : i + bsz], w[i : i + bsz])
        for i in range(0, len(keys), bsz)
    ]


def _oracle_dyn(cfg, k, batches):
    st = dyn_array.init(cfg, k)
    for keys, ids, w in batches:
        st = dyn_array.update_batch(
            cfg, st, jnp.asarray(keys), jnp.asarray(ids), jnp.asarray(w)
        )
    return st

def _assert_dyn_equal(a, b):
    for leaf in ("regs", "hists", "chats"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, leaf)), np.asarray(getattr(b, leaf)),
            err_msg=f"leaf {leaf} diverged",
        )


def _assert_window_equal(a, b):
    for leaf in ("regs", "hists", "chats", "union_regs", "union_hists",
                 "union_chats"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, leaf)), np.asarray(getattr(b, leaf)),
            err_msg=f"leaf {leaf} diverged",
        )
    assert (int(a.head), int(a.filled), int(a.epoch_id)) == (
        int(b.head), int(b.filled), int(b.epoch_id),
    )


# ---------------------------------------------------------------------------
# bit-identity vs the synchronous element-log oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bsz", [64, 97])
def test_random_push_interleaving_bit_identical(bsz):
    """Random push sizes (including > batch_size and size-1) through a
    depth-4 queue land bit-identically to the oracle over the induced
    partition — the headline property test, and the regression test for the
    staging-buffer reuse race (queue_depth > #staging buffers)."""
    rng = np.random.default_rng(11)
    logs = []
    pipe = ingest.dyn_pipeline(
        CFG, dyn_array.init(CFG, K),
        ingest.IngestConfig(batch_size=bsz, queue_depth=4),
    )
    for i in range(17):
        n = int(rng.integers(1, 3 * bsz))
        trip = _elements(n, seed=100 + i)
        logs.append(trip)
        pipe.push(*trip)
    got = pipe.result()

    keys, ids, w = (np.concatenate([t[j] for t in logs]) for j in range(3))
    ref = _oracle_dyn(CFG, K, _partition(keys, ids, w, bsz))
    _assert_dyn_equal(got, ref)
    assert pipe.stats.pushed == len(keys)
    assert pipe.stats.batches == -(-len(keys) // bsz)
    assert pipe.stats.dropped == 0


def test_flush_seals_batch_boundaries():
    """Explicit flush() seals a partial batch — the oracle must see the SAME
    boundary or chats (partition-dependent martingales) would diverge."""
    a = _elements(40, seed=1)
    b = _elements(50, seed=2)
    pipe = ingest.dyn_pipeline(
        CFG, dyn_array.init(CFG, K), ingest.IngestConfig(batch_size=64)
    )
    pipe.push(*a)
    pipe.flush()  # seals [40], next batch starts empty
    pipe.push(*b)
    got = pipe.result()  # seals [50]

    ref = _oracle_dyn(CFG, K, [a, b])
    _assert_dyn_equal(got, ref)
    assert pipe.stats.batches == 2
    assert pipe.stats.partial_batches == 2


def test_kernel_route_bit_identical():
    trip = _elements(300, seed=5)
    pipe = ingest.dyn_pipeline(
        CFG, dyn_array.init(CFG, K),
        ingest.IngestConfig(batch_size=128), use_kernel=True,
    )
    pipe.push(*trip)
    _assert_dyn_equal(pipe.result(), _oracle_dyn(CFG, K, _partition(*trip, 128)))


def test_forced_backpressure_block_bit_identical():
    """Readiness pinned to 'never ready': every dispatch past queue_depth
    must take the block path (stall counters move), and the result is STILL
    bit-identical — backpressure may delay, never reorder or corrupt."""
    bsz, depth = 64, 2
    pipe = ingest.dyn_pipeline(
        CFG, dyn_array.init(CFG, K),
        ingest.IngestConfig(batch_size=bsz, queue_depth=depth, policy="block"),
    )
    pipe._ready = lambda t: False  # force the full-queue path deterministically
    trip = _elements(6 * bsz, seed=21)
    pipe.push(*trip)
    got = pipe.result()

    _assert_dyn_equal(got, _oracle_dyn(CFG, K, _partition(*trip, bsz)))
    assert pipe.stats.stalls == 6 - depth
    assert pipe.stats.stall_s >= 0.0
    assert pipe.stats.max_in_flight <= depth
    assert pipe.stats.dropped == 0


def test_drop_policy_deterministic_prefix():
    """Never-ready + policy='drop': exactly the first queue_depth batches
    are admitted; later seals (including the result() flush of the partial
    tail) are shed and counted."""
    bsz, depth = 64, 2
    pipe = ingest.dyn_pipeline(
        CFG, dyn_array.init(CFG, K),
        ingest.IngestConfig(batch_size=bsz, queue_depth=depth, policy="drop"),
    )
    pipe._ready = lambda t: False
    trip = _elements(5 * bsz + 17, seed=22)
    pipe.push(*trip)
    got = pipe.result()

    keys, ids, w = trip
    admitted = _partition(keys[: depth * bsz], ids[: depth * bsz],
                          w[: depth * bsz], bsz)
    _assert_dyn_equal(got, _oracle_dyn(CFG, K, admitted))
    assert pipe.stats.batches == depth
    assert pipe.stats.dropped == 3 * bsz + 17
    assert pipe.stats.pushed == 5 * bsz + 17


def test_window_rotation_interleaving_bit_identical():
    """Pushes interleaved with rotations: the retire barrier must order every
    earlier element into the pre-rotation epoch, matching the synchronous
    schedule on every ring/union leaf and the epoch clock."""
    bsz = 64
    rng = np.random.default_rng(31)
    pipe = ingest.window_pipeline(
        CFG, window_array.init(CFG, K, 4),
        ingest.IngestConfig(batch_size=bsz, queue_depth=3),
    )
    ref = window_array.init(CFG, K, 4)
    for ep in range(6):
        pending = []
        for i in range(int(rng.integers(1, 4))):
            trip = _elements(int(rng.integers(1, 2 * bsz)), seed=500 + 7 * ep + i)
            pipe.push(*trip)
            pending.append(trip)
        # Oracle: same element log, same partition, sealed at the rotate.
        keys, ids, w = (np.concatenate([t[j] for t in pending]) for j in range(3))
        for batch in _partition(keys, ids, w, bsz):
            ref = window_array.update_batch(
                CFG, ref, *(jnp.asarray(x) for x in batch)
            )
        pipe.rotate()
        ref = window_array.rotate(CFG, ref)
    _assert_window_equal(pipe.result(), ref)
    assert pipe.stats.rotations == 6


def test_rotate_requires_rotatable_container():
    pipe = ingest.dyn_pipeline(CFG, dyn_array.init(CFG, K))
    with pytest.raises(ValueError, match="without rotate"):
        pipe.rotate()


def test_push_validates_lane_lengths():
    pipe = ingest.dyn_pipeline(CFG, dyn_array.init(CFG, K))
    with pytest.raises(ValueError, match="equal-length"):
        pipe.push(np.zeros(3, np.int32), np.zeros(2, np.uint32))


def test_ingest_config_validation():
    with pytest.raises(ValueError):
        ingest.IngestConfig(batch_size=0)
    with pytest.raises(ValueError):
        ingest.IngestConfig(queue_depth=0)
    with pytest.raises(ValueError):
        ingest.IngestConfig(policy="spill")


# ---------------------------------------------------------------------------
# donation audit: in-place buffer reuse is real, not aspirational
# ---------------------------------------------------------------------------


def _ptrs(state):
    return {
        name: getattr(state, name).unsafe_buffer_pointer()
        for name in ("regs", "hists", "chats")
    }


def test_dyn_update_donation_reuses_buffers():
    keys, ids, w = (jnp.asarray(x) for x in _elements(256, seed=41))
    st = dyn_array.init(CFG, K)
    st = dyn_array.update_batch(CFG, st, keys, ids, w)  # settle shapes
    jax.block_until_ready(st.chats)
    before = _ptrs(st)
    ref = dyn_array.update_batch(CFG, st, keys, ids, w)  # non-donating copy
    out = dyn_array.update_batch(CFG, st, keys, ids, w, donate=True)
    after = _ptrs(out)
    for name, ptr in before.items():
        assert after[name] == ptr, f"{name} was copied despite donation"
    _assert_dyn_equal(out, ref)


def test_window_rotate_donation_reuses_buffers():
    keys, ids, w = (jnp.asarray(x) for x in _elements(256, seed=42))
    st = window_array.update_batch(CFG, window_array.init(CFG, K, 4), keys, ids, w)
    jax.block_until_ready(st.union_chats)
    before = st.regs.unsafe_buffer_pointer()
    ref = window_array.rotate(CFG, st)
    out = window_array.rotate(CFG, st, donate=True)
    assert out.regs.unsafe_buffer_pointer() == before
    _assert_window_equal(out, ref)


def test_kernel_op_donation_matches_core_path():
    keys, ids, w = (jnp.asarray(x) for x in _elements(256, seed=43))
    st = dyn_array.init(CFG, K)
    ref = dyn_array.update_batch(CFG, st, keys, ids, w)
    out = ops.dyn_array_update_op(CFG, st, keys, ids, w, donate=True)
    _assert_dyn_equal(out, ref)


# ---------------------------------------------------------------------------
# sharded fronts: same contracts on the 8-device host mesh
# ---------------------------------------------------------------------------


def test_sharded_dyn_pipeline_bit_identical(mesh):
    bsz = 64
    trip = _elements(5 * bsz + 13, seed=51)
    pipe = ingest.sharded_dyn_pipeline(
        CFG, mesh, sharded_dyn_array.init(CFG, K, mesh),
        ingest.IngestConfig(batch_size=bsz, queue_depth=3),
    )
    pipe.push(*trip)
    got = pipe.result()

    ref = sharded_dyn_array.init(CFG, K, mesh)
    for batch in _partition(*trip, bsz):
        ref = sharded_dyn_array.update_batch(
            CFG, mesh, ref, *(jnp.asarray(x) for x in batch)
        )
    _assert_dyn_equal(got, ref)


def test_sharded_window_pipeline_rotation_bit_identical(mesh):
    bsz = 64
    pipe = ingest.sharded_window_pipeline(
        CFG, mesh, sharded_window_array.init(CFG, K, 3, mesh),
        ingest.IngestConfig(batch_size=bsz),
    )
    ref = sharded_window_array.init(CFG, K, 3, mesh)
    for ep in range(4):
        trip = _elements(2 * bsz + 9, seed=600 + ep)
        pipe.push(*trip)
        for batch in _partition(*trip, bsz):
            ref = sharded_window_array.update_batch(
                CFG, mesh, ref, *(jnp.asarray(x) for x in batch)
            )
        pipe.rotate()
        ref = sharded_window_array.rotate(CFG, mesh, ref)
    _assert_window_equal(pipe.result(), ref)


def test_sharded_pipeline_counts_each_batch_by_owning_shard(mesh):
    """Each dispatched micro-batch's events, counted by the shard that owns
    their row (``ingest/shard_rows`` while tracing, and the registry
    counter), sum to the batch's live events; the batch is placed
    replicated on the mesh. A single-device pipeline counts nothing."""
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace

    bsz, s = 64, len(mesh.devices.ravel())
    trip = _elements(3 * bsz + 21, seed=71)
    rows = K // s
    obs_trace.configure(enabled=True)
    obs_trace.clear()
    try:
        pipe = ingest.sharded_window_pipeline(
            CFG, mesh, sharded_window_array.init(CFG, K, 3, mesh),
            ingest.IngestConfig(batch_size=bsz), name="shard_count_test",
        )
        placed = []
        real = pipe._update
        pipe._update = lambda st, *batch: placed.append(batch[0].sharding) or real(st, *batch)
        pipe.push(*trip)
        pipe.rotate()
        single = ingest.window_pipeline(
            CFG, window_array.init(CFG, K, 3), ingest.IngestConfig(batch_size=bsz),
            name="shard_count_single",
        )
        single.push(*trip)
        single.result()
        events = [e for e in obs_trace.events() if e["name"] == "ingest/shard_rows"]
    finally:
        obs_trace.configure(enabled=False)
        obs_trace.clear()
    want = [
        np.bincount(b[0] // rows, minlength=s).tolist()
        for b in _partition(*trip, bsz)
    ]
    assert [e["args"]["counts"] for e in events] == want  # one per batch, 4 of them
    assert [sum(c) for c in want] == [bsz, bsz, bsz, 21]
    assert all(p.is_fully_replicated and len(p.device_set) == s for p in placed)
    total = np.sum(want, axis=0).tolist()
    assert pipe.stats.shard_events == total
    assert pipe.stats.snapshot(delta=True)["shard_events"] == total
    assert pipe.stats.snapshot(delta=True)["shard_events"] == [0] * s
    assert "shard_events" not in single.stats.snapshot()
    fam = obs_metrics.default_registry().get("ingest_shard_events_total")
    if obs_metrics.enabled():
        got = {x.labels["shard"]: x.value for x in fam.series()
               if x.labels["pipe"] == "shard_count_test"}
        assert got == {str(i): c for i, c in enumerate(total)}
    assert not [x for x in fam.series() if x.labels["pipe"] == "shard_count_single"]
    pipe.stats.reset()
    assert pipe.stats.shard_events == [0] * s


@pytest.mark.parametrize("tracing", [False, True])
def test_sharded_pipeline_shard_counts_without_registry(mesh, tracing):
    """With the registry off, the per-shard counts fall back to the pipe's
    own stats while tracing, and are not taken at all with the tracer off
    too (telemetry only: the state is the same either way)."""
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace

    bsz, s = 64, len(mesh.devices.ravel())
    trip = _elements(2 * bsz + 9, seed=73)
    registry_was = obs_metrics.enabled()
    obs_metrics.configure(enabled=False)
    obs_trace.configure(enabled=tracing)
    try:
        pipe = ingest.sharded_window_pipeline(
            CFG, mesh, sharded_window_array.init(CFG, K, 3, mesh),
            ingest.IngestConfig(batch_size=bsz),
        )
        pipe.push(*trip)
        got = pipe.result()
    finally:
        obs_metrics.configure(enabled=registry_was)
        obs_trace.configure(enabled=False)
        obs_trace.clear()
    want = np.bincount(trip[0] // (K // s), minlength=s).tolist() if tracing else [0] * s
    assert pipe.stats.shard_events == want
    assert pipe.stats.snapshot()["shard_events"] == want
    ref = sharded_window_array.init(CFG, K, 3, mesh)
    for batch in _partition(*trip, bsz):
        ref = sharded_window_array.update_batch(CFG, mesh, ref, *(jnp.asarray(x) for x in batch))
    _assert_window_equal(got, ref)


# ---------------------------------------------------------------------------
# tenant front: routed ingest == synchronous route + update + rotate + evict
# ---------------------------------------------------------------------------


def test_tenant_window_ingest_matches_synchronous_routing():
    dcfg = DirectoryConfig(capacity=K, seed=CFG.seed)
    # Push size == batch_size so both schedules induce the same partition.
    bsz = 128
    tw = ingest.TenantWindowIngest(
        CFG, dcfg, n_epochs=3,
        icfg=ingest.IngestConfig(batch_size=bsz), evict_after=2,
    )
    ref_dir = key_directory.init(dcfg)
    ref = window_array.init(CFG, K, 3)
    rng = np.random.default_rng(71)
    for ep in range(4):
        tenants = rng.integers(0, 2**32, bsz, dtype=np.uint32)
        ids = rng.integers(0, 2**32, bsz, dtype=np.uint32)
        w = (rng.gamma(1.0, 2.0, bsz) + 1e-5).astype(np.float32)
        tw.push(tenants, ids, w)
        slots, ref_dir = key_directory.route(
            dcfg, ref_dir, tenants, epoch=jnp.int32(ep)
        )
        ref = window_array.update_batch(
            CFG, ref, slots, jnp.asarray(ids), jnp.asarray(w)
        )
        tw.rotate()
        ref = window_array.rotate(CFG, ref)
        ref_dir, _ = key_directory.evict_older_than(
            dcfg, ref_dir, jnp.int32(ep + 1 - 2)
        )
    _assert_window_equal(tw.result(), ref)
    np.testing.assert_array_equal(
        np.asarray(tw.directory.fingerprints), np.asarray(ref_dir.fingerprints)
    )
    met = tw.metrics()
    assert met["ingest_rotations"] == 4
    assert met["tenant_slots_claimed"] == int(
        jnp.sum((ref_dir.fingerprints != 0).astype(jnp.int32))
    )
    assert 0.0 <= met["tenant_collision_rate"] <= 1.0


def test_tenant_window_ingest_spans_leave_state_unchanged():
    """Traced, the front records one ``ingest/route`` and one
    ``ingest/route_wait`` per push and one ``ingest/fill`` per sealed
    micro-batch (flush / rotate seals flagged partial); its state is
    bit-identical to the untraced run."""
    from repro.obs import trace as obs_trace

    dcfg = DirectoryConfig(capacity=K, seed=CFG.seed)
    bsz, push = 96, 40  # pushes straddle batch boundaries

    def drive(traced):
        obs_trace.configure(enabled=traced)
        obs_trace.clear()
        try:
            tw = ingest.TenantWindowIngest(
                CFG, dcfg, n_epochs=3, icfg=ingest.IngestConfig(batch_size=bsz))
            rng = np.random.default_rng(83)
            for ep in range(3):
                for _ in range(7):
                    tw.push(rng.integers(0, 2**32, push, dtype=np.uint32),
                            rng.integers(0, 2**32, push, dtype=np.uint32),
                            (rng.gamma(1.0, 2.0, push) + 1e-5).astype(np.float32))
                tw.rotate()
            return tw.result(), tw.directory, obs_trace.events()
        finally:
            obs_trace.configure(enabled=False)
            obs_trace.clear()

    traced, traced_dir, events = drive(True)
    plain, plain_dir, none = drive(False)
    assert none == []
    _assert_window_equal(traced, plain)
    np.testing.assert_array_equal(np.asarray(traced_dir.fingerprints),
                                  np.asarray(plain_dir.fingerprints))
    names = [e["name"] for e in events]
    assert names.count("ingest/route") == names.count("ingest/route_wait") == 21
    assert names.count("ingest/push") == 21
    fills = [e["args"] for e in events if e["name"] == "ingest/fill"]
    # Per epoch 280 elements: two full batches of 96, then 88 sealed by rotate.
    assert [(f["n"], f["partial"]) for f in fills] == [(96, False), (96, False), (88, True)] * 3
    assert all(e["dur"] >= 0 for e in events)
