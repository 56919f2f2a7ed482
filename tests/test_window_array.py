"""WindowArray + AnomalyBank tests: element-log oracle bit-identity across
rotation boundaries, union-cache invariants, untouched/clamped-window guards,
kernel-vs-core bit-identity, directory aging, anomaly scoring, and the
WindowMonitor / train / serve threading.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SketchConfig, dyn_array, key_directory, window_array
from repro.core.key_directory import DirectoryConfig
from repro.kernels import ops
from repro.sketchstream import anomaly, monitor

# (batch, m, K, E) — ragged on purpose, matching the DynArray suite's habit.
SHAPES = [
    (256, 64, 8, 4),
    (100, 130, 7, 3),
    (513, 96, 16, 5),
]


def _keyed_stream(n, k, seed, wscale=1.0):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, k, n, dtype=np.int32)
    ids = rng.integers(0, 2**32, n, dtype=np.uint32)
    w = (rng.gamma(1.0, 2.0, n) * wscale).astype(np.float32) + 1e-5
    return jnp.asarray(keys), jnp.asarray(ids), jnp.asarray(w)


def _drive(cfg, k, e, n_epochs, batches_per_epoch=2, batch=512, seed=0):
    """Run n_epochs epochs (rotating between them), returning the final state
    and the per-epoch element logs for oracle rebuilds."""
    st = window_array.init(cfg, k, e)
    logs = []
    for ep in range(n_epochs):
        ep_log = []
        for i in range(batches_per_epoch):
            keys, ids, w = _keyed_stream(batch, k, seed=seed + 31 * ep + i)
            st = window_array.update_batch(cfg, st, keys, ids, w)
            ep_log.append((keys, ids, w))
        logs.append(ep_log)
        if ep < n_epochs - 1:
            st = window_array.rotate(cfg, st)
    return st, logs


def _oracle_window_estimate(cfg, k, logs, w):
    """Rebuild the last w retained epochs from their element logs, union the
    registers, estimate with the shared MLE — the element-log oracle."""
    union = jnp.full((k, cfg.m), cfg.r_min, jnp.int8)
    for ep_log in logs[-w:]:
        d = dyn_array.init(cfg, k)
        for keys, ids, wts in ep_log:
            d = dyn_array.update_batch(cfg, d, keys, ids, wts)
        union = jnp.maximum(union, d.regs)
    return np.asarray(dyn_array.estimate_mle_rows(cfg, union))


# Cases of the update written straight into the ring at (head, keys):
# (batch, m, K, E, rotations, masked share). After 6 rotations of E = 4 the
# head is 2 and every ring slot has been reused; few tenants and registers
# put many delta rows on one key in one scatter-add. Each runs on the fused
# and the donated path.
WRAPPED_HEAD_CASES = {
    "wrapped_head": (2048, 16, 3, 4, 6, 0.0),
    "wrapped_head_masked": (2048, 16, 3, 4, 6, 0.4),
}
ORACLE_CASES = [
    pytest.param(*shape, 0, 0.0, False, id="-".join(map(str, shape)))
    for shape in SHAPES
] + [
    pytest.param(*case, donate, id=f"{name}-{'donated' if donate else 'fused'}")
    for name, case in sorted(WRAPPED_HEAD_CASES.items())
    for donate in (False, True)
]


@pytest.mark.parametrize("batch,m,k,e,rotations,masked,donate", ORACLE_CASES)
def test_update_matches_k_loop_oracle(batch, m, k, e, rotations, masked, donate):
    """Windowed update == K-loop reference on every epoch plane AND the
    union, over two batches (the second reads warm histograms), after
    ``rotations`` rotations. Masked rows are routed, as the ingest pipeline
    pads, to key 0 with a live row's id."""
    cfg = SketchConfig(m=m, b=8, seed=batch + m + k)
    st = (
        _drive(cfg, k, e, n_epochs=rotations + 1, batch=256, seed=5)[0]
        if rotations
        else window_array.init(cfg, k, e)
    )
    assert int(st.head) == rotations % e
    ref = jax.tree.map(lambda x: jnp.array(x, copy=True), st)
    rng = np.random.default_rng(batch * 5 + k)
    for i in range(2):
        keys, ids, w = _keyed_stream(batch, k, seed=batch * 7 + k + i)
        mask = rng.random(batch) >= masked
        keys = jnp.where(mask, keys, 0)
        ids = jnp.where(mask, ids, ids[0])
        ref = window_array.update_reference(cfg, ref, keys, ids, w, mask=mask)
        st = window_array.update_batch(
            cfg, st, keys, ids, w, mask=jnp.asarray(mask), donate=donate
        )
        for f in ("regs", "hists", "union_regs", "union_hists", "head"):
            np.testing.assert_array_equal(
                np.asarray(getattr(st, f)), np.asarray(getattr(ref, f)), err_msg=f
            )
        for f in ("chats", "union_chats"):
            np.testing.assert_allclose(
                np.asarray(getattr(st, f)), np.asarray(getattr(ref, f)),
                rtol=1e-5, atol=1e-6, err_msg=f,
            )


def test_union_cache_invariant_across_rotations():
    """union_regs == max over epoch planes and union_hists == rebuild, at
    every point of an update/rotate schedule (incl. past ring wrap)."""
    cfg = SketchConfig(m=96, b=8, seed=6)
    k, e = 9, 4
    st = window_array.init(cfg, k, e)
    for i in range(e + 3):
        keys, ids, w = _keyed_stream(300, k, seed=40 + i)
        st = window_array.update_batch(cfg, st, keys, ids, w)
        np.testing.assert_array_equal(
            np.asarray(st.union_regs), np.asarray(st.regs).max(axis=0)
        )
        np.testing.assert_array_equal(
            np.asarray(st.union_hists),
            np.asarray(dyn_array.rebuild_hists(cfg, st.union_regs)),
        )
        st = window_array.rotate(cfg, st)


@pytest.mark.parametrize("batch,m,k,e", SHAPES)
def test_estimate_window_matches_element_log_oracle(batch, m, k, e):
    """The acceptance property: estimate_window(w) is bit-identical to the
    element-log rebuild for EVERY w <= E, across rotation boundaries (the
    ring has wrapped: epochs were evicted)."""
    cfg = SketchConfig(m=m, b=8, seed=batch + k)
    st, logs = _drive(cfg, k, e, n_epochs=e + 2, batch=batch, seed=batch)
    for w in range(1, e + 1):
        np.testing.assert_array_equal(
            np.asarray(window_array.estimate_window(cfg, st, w)),
            _oracle_window_estimate(cfg, k, logs, w),
        )


def test_full_ring_cached_path_matches_fresh_union():
    """w == E reads the maintained union_hists — same bits as unioning the
    epoch planes from scratch."""
    cfg = SketchConfig(m=64, b=8, seed=7)
    k, e = 11, 5
    st, _ = _drive(cfg, k, e, n_epochs=e + 1, seed=3)
    cached = np.asarray(window_array.estimate_window(cfg, st, e))
    fresh = np.asarray(
        dyn_array.estimate_mle_rows(cfg, window_array.window_union_regs(st, e))
    )
    np.testing.assert_array_equal(cached, fresh)


def test_rotation_evicts_oldest_epoch():
    """An epoch's traffic leaves the full-ring window after E rotations."""
    cfg = SketchConfig(m=64, b=8, seed=8)
    k, e = 4, 3
    st = window_array.init(cfg, k, e)
    keys, ids, w = _keyed_stream(2000, k, seed=1)
    st = window_array.update_batch(cfg, st, keys, ids, w)
    assert float(np.asarray(window_array.estimate_window(cfg, st, e)).sum()) > 0
    for _ in range(e):
        st = window_array.rotate(cfg, st)
    np.testing.assert_array_equal(
        np.asarray(window_array.estimate_window(cfg, st, e)), 0.0
    )
    np.testing.assert_array_equal(np.asarray(st.union_chats), 0.0)
    assert int(st.epoch_id) == e and int(st.filled) == e


def test_untouched_and_clamped_window_guards():
    """Fresh state: Ĉ = 0 for every w. w > filled clamps to the filled ring
    (unfilled epochs are no-ops); out-of-range w raises."""
    cfg = SketchConfig(m=64, b=8, seed=9)
    k, e = 5, 4
    st = window_array.init(cfg, k, e)
    for w in range(1, e + 1):
        np.testing.assert_array_equal(
            np.asarray(window_array.estimate_window(cfg, st, w)), 0.0
        )
        np.testing.assert_array_equal(
            np.asarray(ops.window_union_estimate_op(cfg, st, w, interpret=True)), 0.0
        )
    # One live epoch; every w >= 1 must equal w = 1 (clamped-window semantics).
    keys, ids, w_ = _keyed_stream(1500, k, seed=2)
    st = window_array.update_batch(cfg, st, keys, ids, w_)
    assert int(st.filled) == 1
    ref = np.asarray(window_array.estimate_window(cfg, st, 1))
    assert ref.sum() > 0
    for w in range(2, e + 1):
        np.testing.assert_array_equal(
            np.asarray(window_array.estimate_window(cfg, st, w)), ref
        )
    for bad in (0, e + 1, -1):
        with pytest.raises(ValueError, match="out of range"):
            window_array.estimate_window(cfg, st, bad)
        with pytest.raises(ValueError, match="out of range"):
            ops.window_union_estimate_op(cfg, st, bad, interpret=True)
    with pytest.raises(ValueError, match="k >= 1"):
        window_array.init(cfg, 0, e)
    with pytest.raises(ValueError, match="e >= 2"):
        window_array.init(cfg, k, 1)


@pytest.mark.parametrize("batch,m,k,e", SHAPES)
def test_window_union_op_bit_identity(batch, m, k, e):
    """Pallas (interpret) fused union+bincount vs the pure-JAX union path:
    BITWISE equal estimates for every w."""
    cfg = SketchConfig(m=m, b=8, seed=m + k)
    st, _ = _drive(cfg, k, e, n_epochs=e + 1, batch=batch, seed=k)
    for w in range(1, e + 1):
        np.testing.assert_array_equal(
            np.asarray(window_array.estimate_window(cfg, st, w)),
            np.asarray(ops.window_union_estimate_op(cfg, st, w, interpret=True)),
        )


def test_anytime_read_rebases_to_window_estimate_on_rotate():
    """After rotate, the running union martingale re-bases to exactly the
    full-ring MLE read (then diverges as new updates stream in)."""
    cfg = SketchConfig(m=64, b=8, seed=12)
    k, e = 6, 4
    st, _ = _drive(cfg, k, e, n_epochs=3, seed=5)
    st = window_array.rotate(cfg, st)
    np.testing.assert_array_equal(
        np.asarray(window_array.estimate_ring_anytime(st)),
        np.asarray(window_array.estimate_window(cfg, st, e)),
    )


def test_window_merge_is_rowwise_union():
    """Ring-aligned pod merge: per-epoch register max; misaligned rejected."""
    cfg = SketchConfig(m=64, b=8, seed=13)
    k, e = 5, 3
    sa, _ = _drive(cfg, k, e, n_epochs=2, seed=21)
    sb, _ = _drive(cfg, k, e, n_epochs=2, seed=22)
    merged = window_array.merge(cfg, sa, sb)
    np.testing.assert_array_equal(
        np.asarray(merged.regs),
        np.maximum(np.asarray(sa.regs), np.asarray(sb.regs)),
    )
    np.testing.assert_array_equal(
        np.asarray(merged.union_regs), np.asarray(merged.regs).max(axis=0)
    )
    # Merged chats re-estimate via the MLE — merging a state with itself
    # must not double anything.
    self_merged = window_array.merge(cfg, sa, sa)
    np.testing.assert_array_equal(
        np.asarray(self_merged.union_chats),
        np.asarray(window_array.estimate_window(cfg, sa, e)),
    )
    with pytest.raises(ValueError, match="matching"):
        window_array.merge(cfg, sa, window_array.init(cfg, k + 1, e))
    with pytest.raises(ValueError, match="ring-aligned"):
        window_array.merge(cfg, sa, window_array.rotate(cfg, sb))


def test_update_tenants_routes_and_stamps_epochs():
    cfg = SketchConfig(m=64, b=8, seed=16)
    dcfg = DirectoryConfig(capacity=16, seed=17)
    rng = np.random.default_rng(91)
    tkeys = key_directory.split_uint64(rng.integers(0, 2**64, 200, dtype=np.uint64))
    ids = jnp.asarray(rng.integers(0, 2**32, 200, dtype=np.uint32))
    w = jnp.asarray(rng.uniform(0.5, 1.5, 200).astype(np.float32))
    st = window_array.init(cfg, 16, 3)
    st = window_array.rotate(cfg, st)  # epoch_id = 1
    st, dstate = window_array.update_tenants(
        cfg, dcfg, st, key_directory.init(dcfg), tkeys, ids, w
    )
    slots = np.asarray(key_directory.route_slots(dcfg, tkeys))
    touched = np.unique(slots)
    np.testing.assert_array_equal(np.asarray(dstate.last_touch)[touched], 1)
    assert int(dstate.n_routed) == 200
    # Registers match the dense-slot path.
    ref = window_array.update_batch(
        cfg, window_array.rotate(cfg, window_array.init(cfg, 16, 3)),
        jnp.asarray(slots), ids, w,
    )
    np.testing.assert_array_equal(np.asarray(st.regs), np.asarray(ref.regs))
    with pytest.raises(ValueError, match="capacity"):
        window_array.update_tenants(
            cfg, dcfg, window_array.init(cfg, 8, 3), key_directory.init(dcfg),
            tkeys, ids, w,
        )


# ---------------------------------------------------------------------------
# key-directory aging
# ---------------------------------------------------------------------------


def test_directory_aging_evicts_cold_fingerprints():
    dcfg = DirectoryConfig(capacity=32, seed=5, pinned=(7,))
    rng = np.random.default_rng(3)
    hot = key_directory.split_uint64(rng.integers(0, 2**64, 50, dtype=np.uint64))
    cold = key_directory.split_uint64(rng.integers(0, 2**64, 50, dtype=np.uint64))
    pinned = key_directory.split_uint64(np.array([7], dtype=np.uint64))

    st = key_directory.init(dcfg)
    _, st = key_directory.route(dcfg, st, cold, epoch=0)
    _, st = key_directory.route(dcfg, st, pinned, epoch=0)
    _, st = key_directory.route(dcfg, st, hot, epoch=5)
    claimed_before = int(np.sum(np.asarray(st.fingerprints) != 0))

    st2, n_evicted = key_directory.evict_older_than(dcfg, st, 5)
    assert int(n_evicted) > 0
    assert int(np.sum(np.asarray(st2.fingerprints) != 0)) == claimed_before - int(n_evicted)
    # Hot slots the cold cohort never claimed keep their claims and stamps
    # (hot traffic COLLIDING with a cold ghost does not protect it — those
    # slots age out and the hot tenant re-claims on its next routing).
    hot_slots = np.unique(np.asarray(key_directory.route_slots(dcfg, hot)))
    cold_slots = np.unique(np.asarray(key_directory.route_slots(dcfg, cold)))
    owned_hot = np.setdiff1d(hot_slots, cold_slots)
    assert owned_hot.size > 0
    np.testing.assert_array_equal(np.asarray(st2.last_touch)[owned_hot], 5)
    assert all(np.asarray(st2.fingerprints)[owned_hot] != 0)
    # The pinned slot never ages, even when stone cold.
    assert np.asarray(st2.fingerprints)[0] != 0
    st3, _ = key_directory.evict_older_than(dcfg, st2, 10**6)
    assert np.asarray(st3.fingerprints)[0] != 0
    assert int(np.sum(np.asarray(st3.fingerprints) != 0)) == 1
    # Counters are cumulative history, never rewound.
    assert int(st3.n_routed) == int(st.n_routed)


def test_directory_aging_reclaim_avoids_ghost_collisions():
    """A fresh tenant landing on an evicted slot claims it first-contact —
    no collision against the departed tenant's ghost fingerprint."""
    dcfg = DirectoryConfig(capacity=4, seed=9)
    rng = np.random.default_rng(11)
    # Find two tenants that share a slot.
    cand = rng.integers(0, 2**64, 400, dtype=np.uint64)
    slots = np.asarray(key_directory.route_slots(dcfg, key_directory.split_uint64(cand)))
    a = cand[slots == 2][0]
    b = cand[slots == 2][1]

    st = key_directory.init(dcfg)
    _, st = key_directory.route(dcfg, st, key_directory.split_uint64([a]), epoch=0)
    # Without aging: b collides with a's claim.
    _, st_no = key_directory.route(dcfg, st, key_directory.split_uint64([b]), epoch=9)
    assert int(st_no.n_collisions) == 1
    # With aging first: the slot was released, b claims it fresh.
    st_aged, n = key_directory.evict_older_than(dcfg, st, 5)
    assert int(n) == 1
    _, st_yes = key_directory.route(dcfg, st_aged, key_directory.split_uint64([b]), epoch=9)
    assert int(st_yes.n_collisions) == 0


def test_colliding_traffic_does_not_keep_ghost_slot_warm():
    """Only owner/claim routings stamp last_touch: a departed tenant's slot
    under ACTIVE colliding traffic still ages out, releasing the ghost."""
    dcfg = DirectoryConfig(capacity=4, seed=9)
    rng = np.random.default_rng(11)
    cand = rng.integers(0, 2**64, 400, dtype=np.uint64)
    slots = np.asarray(key_directory.route_slots(dcfg, key_directory.split_uint64(cand)))
    a, b = cand[slots == 2][:2]
    slot = 2

    st = key_directory.init(dcfg)
    _, st = key_directory.route(dcfg, st, key_directory.split_uint64([a]), epoch=0)
    for ep in range(1, 5):  # b collides against a's ghost every epoch
        _, st = key_directory.route(dcfg, st, key_directory.split_uint64([b]), epoch=ep)
    assert int(st.n_collisions) == 4
    assert int(np.asarray(st.last_touch)[slot]) == 0  # collisions never stamp
    st, n = key_directory.evict_older_than(dcfg, st, 1)
    assert int(n) == 1
    # b now claims the released slot and its routings stop colliding.
    _, st = key_directory.route(dcfg, st, key_directory.split_uint64([b]), epoch=5)
    assert int(st.n_collisions) == 4
    assert int(np.asarray(st.last_touch)[slot]) == 5


def test_directory_merge_carries_stamps():
    dcfg = DirectoryConfig(capacity=16, seed=6)
    rng = np.random.default_rng(7)
    ka = key_directory.split_uint64(rng.integers(0, 2**64, 30, dtype=np.uint64))
    kb = key_directory.split_uint64(rng.integers(0, 2**64, 30, dtype=np.uint64))
    _, da = key_directory.route(dcfg, key_directory.init(dcfg), ka, epoch=2)
    _, db = key_directory.route(dcfg, key_directory.init(dcfg), kb, epoch=4)
    merged = key_directory.merge(da, db)
    np.testing.assert_array_equal(
        np.asarray(merged.last_touch),
        np.maximum(np.asarray(da.last_touch), np.asarray(db.last_touch)),
    )


# ---------------------------------------------------------------------------
# AnomalyBank
# ---------------------------------------------------------------------------


def _feed(bcfg, bank, series):
    scores = None
    for est in series:
        bank, scores = anomaly.step(bcfg, bank, jnp.asarray(est, jnp.float32))
    return bank, scores


def test_anomaly_warmup_never_alerts():
    bcfg = anomaly.AnomalyConfig(warmup=4)
    bank = anomaly.init(3)
    rng = np.random.default_rng(0)
    for _ in range(4):
        bank, scores = anomaly.step(
            bcfg, bank, jnp.asarray(rng.uniform(0, 1000, 3), jnp.float32)
        )
        np.testing.assert_array_equal(np.asarray(scores), 0.0)


def test_anomaly_flags_spike_and_only_spike():
    bcfg = anomaly.AnomalyConfig(warmup=3, min_weight=5.0)
    bank = anomaly.init(4)
    rng = np.random.default_rng(1)
    base = np.array([100.0, 500.0, 50.0, 0.0])  # tenant 3 is an empty slot
    series = [base * rng.normal(1.0, 0.03, 4) for _ in range(10)]
    bank, scores = _feed(bcfg, bank, series)
    assert anomaly.top_alerts(bcfg, scores) == []
    # Tenant 1 triples for three consecutive windows.
    for _ in range(3):
        obs = base * rng.normal(1.0, 0.03, 4)
        obs[1] *= 3.0
        bank, scores = anomaly.step(bcfg, bank, jnp.asarray(obs, jnp.float32))
    alerts = anomaly.top_alerts(bcfg, scores)
    assert [slot for slot, _ in alerts] == [1]
    # Dust slots below min_weight never score, whatever they do.
    assert float(scores[3]) == 0.0


def test_anomaly_scores_decay_and_baseline_recovers():
    """Zero-mean noise drains the CUSUM; a sustained level shift eventually
    re-baselines (freeze_factor > 0) instead of ratcheting forever."""
    bcfg = anomaly.AnomalyConfig(warmup=3, min_weight=1.0, alpha=0.3, freeze_factor=0.2)
    bank = anomaly.init(1)
    rng = np.random.default_rng(2)
    bank, _ = _feed(bcfg, bank, [[100 * rng.normal(1, 0.05)] for _ in range(8)])
    # Step change to 300 and stay there: alert fires...
    bank, scores = _feed(bcfg, bank, [[300.0]] * 3)
    assert float(scores[0]) > bcfg.cusum_h
    # ...and eventually clears once 300 is the new normal.
    for _ in range(200):
        bank, scores = anomaly.step(bcfg, bank, jnp.asarray([300.0], jnp.float32))
    assert float(scores[0]) <= bcfg.cusum_h
    assert float(bank.mean[0]) == pytest.approx(300.0, rel=0.05)


def test_anomaly_merge_disjoint_and_validation():
    bcfg = anomaly.AnomalyConfig(warmup=1)
    a, _ = _feed(bcfg, anomaly.init(4), [[10, 0, 20, 0]] * 5)
    b, _ = _feed(bcfg, anomaly.init(4), [[0, 30, 0, 40]] * 5)
    merged = anomaly.merge(a, b)
    np.testing.assert_allclose(np.asarray(merged.mean), [10, 30, 20, 40], rtol=1e-6)
    with pytest.raises(ValueError, match="matching"):
        anomaly.merge(a, anomaly.init(5))
    with pytest.raises(ValueError, match="alpha"):
        anomaly.AnomalyConfig(alpha=0.0)
    with pytest.raises(ValueError, match="warmup"):
        anomaly.AnomalyConfig(warmup=0)
    with pytest.raises(ValueError, match="freeze_factor"):
        anomaly.AnomalyConfig(freeze_factor=1.0)
    with pytest.raises(ValueError, match="k >= 1"):
        anomaly.init(0)


def test_anomaly_ranking_is_by_score():
    bcfg = anomaly.AnomalyConfig(warmup=1)
    scores = jnp.asarray([0.0, 9.0, 7.0, 100.0, 5.0], jnp.float32)
    assert anomaly.top_alerts(bcfg, scores, n=2) == [(3, 100.0), (1, 9.0)]
    assert anomaly.top_alerts(bcfg, scores, n=10) == [(3, 100.0), (1, 9.0), (2, 7.0)]


# ---------------------------------------------------------------------------
# monitor + train/serve threading
# ---------------------------------------------------------------------------


def test_window_monitor_roundtrip():
    cfg = SketchConfig(m=64, b=8, seed=61)
    mon = monitor.WindowMonitor.for_capacity(cfg, 8, 3, evict_after=2)
    rng = np.random.default_rng(26)
    # ~900 distinct per row: the well-loaded regime where the windowed MLE
    # read is specified (DESIGN.md §8.5 documents the light-load caveat).
    n = 8000
    tkeys = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
    ids = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
    w = jnp.asarray(rng.uniform(0.5, 1.5, n).astype(np.float32))
    mask = jnp.asarray(np.arange(n) < 7400)

    st = mon.update(mon.init(), tkeys, ids, w, mask=mask)
    assert int(st.n_seen) == 7400
    est = np.asarray(mon.estimate(st))  # anytime full-ring read
    assert est.shape == (8,)
    true_c = float(np.asarray(w, np.float64)[:7400].sum())
    assert abs(est.sum() - true_c) / true_c < 0.2  # martingale total tracks

    m = mon.metrics(st)
    assert int(m["tenant_elements_seen"]) == 7400
    assert int(m["tenant_window_epoch"]) == 0
    assert float(m["tenant_window_weight"]) == pytest.approx(float(est.sum()), rel=1e-6)

    # The windowed MLE read and the anytime read answer the same window.
    mle = np.asarray(mon.estimate(st, w=3))
    assert abs(mle.sum() - true_c) / true_c < 0.35

    # Rotate the live epoch out entirely: the window empties.
    for _ in range(3):
        st = mon.rotate(st)
    np.testing.assert_array_equal(np.asarray(mon.estimate(st)), 0.0)
    assert int(mon.metrics(st)["tenant_window_epoch"]) == 3
    # Aging (evict_after=2) released every fingerprint claimed at epoch 0.
    assert int(mon.metrics(st)["tenant_slots_claimed"]) == 0

    # Ring-aligned pod merge keeps the surface contract.
    st2 = mon.init()
    for _ in range(3):
        st2 = mon.rotate(st2)
    st2 = mon.update(st2, tkeys, ids, w, mask=mask)
    merged = mon.merge(st, st2)
    assert int(merged.n_seen) == 14800


def test_train_step_threads_window_tenant_telemetry():
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
    mon = monitor.WindowMonitor.for_capacity(skc, 256, 4)
    ocfg = optimizer.OptConfig(lr=1e-3, warmup_steps=0)
    step = jax.jit(ts.make_train_step(mcfg, ocfg, None, sketch_cfg=skc, tenant_monitor=mon))
    opt, comp, sk = ts.init_states(mcfg, ocfg, params, sketch_cfg=skc, tenant_monitor=mon)
    assert isinstance(sk, TelemetryState)

    _, _, _, sk, metrics = step(params, opt, comp, sk, batch)
    assert int(sk.tenants.n_seen) == 64  # 4 x 16 tokens through the array
    assert "tenant_window_weight" in metrics and "distinct_tokens_est" in metrics
    est = np.asarray(mon.estimate(sk.tenants))
    assert (est > 0).sum() == 4  # 4 documents -> exactly 4 live rows

    # The epoch clock lives OUTSIDE the jit'd step: rotate between steps.
    sk = TelemetryState(scalar=sk.scalar, tenants=mon.rotate(sk.tenants))
    _, _, _, sk, metrics = step(params, opt, comp, sk, batch)
    assert int(metrics["tenant_window_epoch"]) == 1
    assert int(sk.tenants.n_seen) == 128


def test_decode_step_threads_window_tenant_telemetry():
    from repro import configs
    from repro.models import common as mcommon, transformer
    from repro.train import serve_step

    mcfg = configs.smoke_config("h2o-danube-1.8b")
    params = mcommon.init_params(transformer.model_defs(mcfg), jax.random.PRNGKey(7))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), transformer.abstract_cache(mcfg, batch=2, max_len=16)
    )
    skc = SketchConfig(m=64, b=8, seed=65)
    mon = monitor.WindowMonitor.for_capacity(skc, 128, 3)
    dec = jax.jit(serve_step.make_decode_step(mcfg, None, sketch_cfg=skc, tenant_monitor=mon))

    sk = monitor.TelemetryState(scalar=monitor.init(skc), tenants=mon.init())
    _, _, sk = dec(
        params, cache, jnp.int32(0), jnp.zeros((2, 1), jnp.int32), sk,
        jnp.asarray([101, 202], jnp.uint32),  # session ids
        jnp.asarray([1.0, 3.0], jnp.float32),  # engagement weights
        None, None,
        jnp.asarray([7, 7], jnp.uint32),  # both sessions belong to tenant 7
    )
    assert int(sk.tenants.n_seen) == 2
    est = np.asarray(mon.estimate(sk.tenants))
    assert (est > 0).sum() == 1  # one tenant row live
    assert float(est.sum()) == pytest.approx(4.0, rel=0.5)  # ~1.0 + 3.0
