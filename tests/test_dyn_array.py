"""DynArray tests: K-loop bit-identity (incl. the fixed padded-duplicate
case), incremental-histogram equivalence, kernel-vs-core, anytime reads,
merge algebra, tenant routing, and the monitor / train / serve threading.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SketchConfig, dyn_array, estimators, key_directory, qsketch_dyn
from repro.core.key_directory import DirectoryConfig
from repro.core.types import DynArrayState
from repro.kernels import ops
from repro.sketchstream import monitor

# (batch, m, K) — ragged on purpose, matching the SketchArray suite's habit.
SHAPES = [
    (64, 64, 8),
    (100, 130, 7),
    (256, 96, 16),
    (513, 257, 33),
    (8, 64, 1),  # single row degenerates to qsketch_dyn
]


def _keyed_stream(n, k, seed, wscale=1.0):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, k, n, dtype=np.int32)
    ids = rng.integers(0, 2**32, n, dtype=np.uint32)
    w = (rng.gamma(1.0, 2.0, n) * wscale).astype(np.float32) + 1e-5
    return jnp.asarray(keys), jnp.asarray(ids), jnp.asarray(w)


def _assert_states_match(st, ref, chat_rtol=1e-5):
    """regs/hists bitwise; chats within f32 association-order rounding."""
    np.testing.assert_array_equal(np.asarray(st.regs), np.asarray(ref.regs))
    np.testing.assert_array_equal(np.asarray(st.hists), np.asarray(ref.hists))
    np.testing.assert_allclose(
        np.asarray(st.chats), np.asarray(ref.chats), rtol=chat_rtol, atol=1e-6
    )


# Cases of the commit's histogram row scatter-add: (batch, m, K, masked
# share). "hot_rows" puts 2048 events on each of 2 tenants (128 per
# register), so many delta rows of one batch land on the same key;
# "masked_padding" masks 30% of the rows; "hot_masked" does both. Each runs
# on the fused and the split donated (plan + commit) path.
ROW_SCATTER_CASES = {
    "hot_rows": (4096, 16, 2, 0.0),
    "masked_padding": (1024, 64, 12, 0.3),
    "hot_masked": (4096, 16, 3, 0.5),
}
ORACLE_CASES = [
    pytest.param(*shape, 0.0, False, id="-".join(map(str, shape)))
    for shape in SHAPES
] + [
    pytest.param(*case, donate, id=f"{name}-{'donated' if donate else 'fused'}")
    for name, case in sorted(ROW_SCATTER_CASES.items())
    for donate in (False, True)
]


@pytest.mark.parametrize("batch,m,k,masked,donate", ORACLE_CASES)
def test_update_matches_k_loop_oracle(batch, m, k, masked, donate):
    """Row r == a standalone qsketch_dyn.update_batch fed the key-r
    sub-stream, over a cold and a warm batch (q_R then reads nonzero
    histograms). Masked rows are routed, as the ingest pipeline pads, to
    key 0 with a live row's id."""
    cfg = SketchConfig(m=m, b=8, seed=batch + m + k)
    rng = np.random.default_rng(batch * 5 + k)
    st = dyn_array.init(cfg, k)
    ref = dyn_array.init(cfg, k)
    for i in range(2):
        keys, ids, w = _keyed_stream(batch, k, seed=batch * 7 + k + i)
        mask = rng.random(batch) >= masked
        keys = jnp.where(mask, keys, 0)
        ids = jnp.where(mask, ids, ids[0])
        ref = dyn_array.update_reference(cfg, ref, keys, ids, w, mask=mask)
        st = dyn_array.update_batch(
            cfg, st, keys, ids, w, mask=jnp.asarray(mask), donate=donate
        )
        _assert_states_match(st, ref)
    np.testing.assert_array_equal(
        np.asarray(st.hists), np.asarray(dyn_array.rebuild_hists(cfg, st.regs))
    )


def test_padded_duplicate_does_not_shadow_live_row():
    """The fixed dedup/mask contract, keyed form: a masked padding row sharing
    (key, id) with a live row cannot drop the live row's weight."""
    cfg = SketchConfig(m=64, b=8, seed=3)
    k = 5
    keys, ids, w = _keyed_stream(60, k, seed=9)
    pad_keys = jnp.concatenate([keys[:8], keys])
    pad_ids = jnp.concatenate([ids[:8], ids])
    pad_w = jnp.concatenate([jnp.ones(8, jnp.float32), w])
    mask = jnp.asarray(np.concatenate([np.zeros(8, bool), np.ones(60, bool)]))

    st = dyn_array.update_batch(
        cfg, dyn_array.init(cfg, k), pad_keys, pad_ids, pad_w, mask=mask
    )
    ref = dyn_array.update_reference(cfg, dyn_array.init(cfg, k), keys, ids, w)
    _assert_states_match(st, ref)
    # And against the padded K-loop oracle (mask threaded through).
    ref_pad = dyn_array.update_reference(
        cfg, dyn_array.init(cfg, k), pad_keys, pad_ids, pad_w, mask=np.asarray(mask)
    )
    _assert_states_match(st, ref_pad)


def test_same_id_under_two_keys_counts_twice():
    """Dedup is per (key, id): one element id observed under two keys is two
    distinct per-tenant elements and must land in both rows."""
    cfg = SketchConfig(m=64, b=8, seed=4)
    ids = jnp.asarray(np.full(2, 12345, np.uint32))
    keys = jnp.asarray(np.array([0, 1], np.int32))
    w = jnp.ones(2, jnp.float32)
    st = dyn_array.update_batch(cfg, dyn_array.init(cfg, 2), keys, ids, w)
    chats = np.asarray(st.chats)
    assert chats[0] > 0 and chats[1] > 0
    np.testing.assert_array_equal(np.asarray(st.regs[0]), np.asarray(st.regs[1]))


def test_incremental_hists_match_rebuild():
    cfg = SketchConfig(m=96, b=8, seed=6)
    k = 9
    st = dyn_array.init(cfg, k)
    for i in range(4):
        keys, ids, w = _keyed_stream(200, k, seed=20 + i)
        st = dyn_array.update_batch(cfg, st, keys, ids, w)
        np.testing.assert_array_equal(
            np.asarray(st.hists), np.asarray(dyn_array.rebuild_hists(cfg, st.regs))
        )


def scatter_hists(cfg, regs):
    """The per-row scatter-add histogram with bin 0 zeroed: the oracle the
    dense rebuild is held to."""
    return jax.vmap(lambda r: estimators.histogram(cfg, r))(regs).at[:, 0].set(0)


# (m, K): "m128" is the deployment's register width; "m_below_128" and
# "m_above_128" leave the register axis off the 128-lane tile; "one_row"
# and "odd_rows" give K no power-of-two factor. K is not a multiple of the
# row block in "m128" (256-row blocks) and "odd_rows" (2048-row blocks).
REBUILD_CASES = {
    "m128": (128, 1000),
    "m_below_128": (40, 517),
    "m_above_128": (257, 77),
    "one_row": (64, 1),
    "odd_rows": (16, 4099),
}


@pytest.mark.parametrize("m,k", list(REBUILD_CASES.values()), ids=list(REBUILD_CASES))
def test_rebuild_hists_matches_scatter_oracle(m, k):
    """The dense count == the per-row scatter, bin for bin, on random
    registers over the whole range: every row holds r_min and r_max, the
    second row is all r_min (an empty histogram) and the last all r_max.
    Both forms are held to it: the row-blocked one this backend lowers and
    the whole-array one a TPU lowers."""
    cfg = SketchConfig(m=m, b=8, seed=m + k)
    rng = np.random.default_rng(m * 3 + k)
    regs = rng.integers(cfg.r_min, cfg.r_max + 1, (k, m)).astype(np.int8)
    regs[:, 0], regs[:, -1] = cfg.r_min, cfg.r_max
    regs[min(1, k - 1)], regs[-1] = cfg.r_min, cfg.r_max
    got = np.asarray(jax.jit(lambda r: dyn_array.rebuild_hists(cfg, r))(regs))
    want = np.asarray(scatter_hists(cfg, jnp.asarray(regs)))
    assert got.shape == (k, cfg.num_bins) and got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    assert (got[:, 0] == 0).all() and got[-1, cfg.top_bin] == m
    np.testing.assert_array_equal(np.asarray(dyn_array._count_bins(cfg, jnp.asarray(regs))), want)


def test_estimate_all_is_anytime_read():
    """estimate_all returns the running chats array itself — no solve."""
    cfg = SketchConfig(m=256, b=8, seed=7)
    k = 6
    keys, ids, w = _keyed_stream(4000, k, seed=31)
    st = dyn_array.update_batch(cfg, dyn_array.init(cfg, k), keys, ids, w)
    assert dyn_array.estimate_all(st) is st.chats
    est = np.asarray(dyn_array.estimate_all(st))
    keys_np, w_np = np.asarray(keys), np.asarray(w, dtype=np.float64)
    for r in range(k):
        true_c = w_np[keys_np == r].sum()
        assert abs(est[r] - true_c) / true_c < 0.35  # m=256 statistical bound


def test_untouched_rows_estimate_zero():
    cfg = SketchConfig(m=64, b=8, seed=8)
    st = dyn_array.init(cfg, 4)
    np.testing.assert_array_equal(np.asarray(dyn_array.estimate_all(st)), 0.0)
    np.testing.assert_array_equal(np.asarray(dyn_array.estimate_mle_all(cfg, st)), 0.0)
    keys = jnp.full((400,), 2, jnp.int32)
    ids = jnp.asarray(np.arange(400, dtype=np.uint32))
    st = dyn_array.update_batch(cfg, st, keys, ids, jnp.ones((400,), jnp.float32))
    est = np.asarray(dyn_array.estimate_all(st))
    mle = np.asarray(dyn_array.estimate_mle_all(cfg, st))
    assert est[2] > 0 and mle[2] > 0
    untouched = np.arange(4) != 2
    np.testing.assert_array_equal(est[untouched], 0.0)
    np.testing.assert_array_equal(mle[untouched], 0.0)


def test_degenerate_weights_dropped():
    cfg = SketchConfig(m=64, b=8, seed=10)
    k = 3
    keys, ids, w = _keyed_stream(40, k, seed=11)
    bad_keys = jnp.concatenate([keys[:4], keys])
    bad_ids = jnp.concatenate([ids[:4], ids])
    bad_w = jnp.concatenate(
        [jnp.asarray(np.array([0.0, -2.0, np.nan, np.inf], np.float32)), w]
    )
    st = dyn_array.update_batch(cfg, dyn_array.init(cfg, k), bad_keys, bad_ids, bad_w)
    ref = dyn_array.update_batch(cfg, dyn_array.init(cfg, k), keys, ids, w)
    _assert_states_match(st, ref)


def test_merge_matches_single_sketch_merge_rowwise():
    """merge == qsketch_dyn.merge per row, bitwise (chats included — the MLE
    re-estimate is the same vmapped computation)."""
    cfg = SketchConfig(m=64, b=8, seed=12)
    k = 5
    ka, ia, wa = _keyed_stream(2000, k, seed=51)
    kb, ib, wb = _keyed_stream(2000, k, seed=52)
    sa = dyn_array.update_batch(cfg, dyn_array.init(cfg, k), ka, ia, wa)
    sb = dyn_array.update_batch(cfg, dyn_array.init(cfg, k), kb, ib, wb)
    merged = dyn_array.merge(cfg, sa, sb)
    for r in range(k):
        single = qsketch_dyn.merge(cfg, dyn_array.row(sa, r), dyn_array.row(sb, r))
        np.testing.assert_array_equal(np.asarray(merged.regs[r]), np.asarray(single.regs))
        np.testing.assert_array_equal(np.asarray(merged.hists[r]), np.asarray(single.hist))
        assert float(merged.chats[r]) == float(single.chat)
    with pytest.raises(ValueError, match="matching"):
        dyn_array.merge(cfg, sa, dyn_array.init(cfg, k + 1))


def test_merge_disjoint_adds_chats():
    """Key-partitioned fleets: disjoint streams merge by adding martingales —
    exact, no MLE — while registers still max-merge."""
    cfg = SketchConfig(m=128, b=8, seed=13)
    k = 4
    ka, ia, wa = _keyed_stream(1500, k, seed=53)
    kb, ib, wb = _keyed_stream(1500, k, seed=54)  # fresh ids: disjoint w.h.p.
    sa = dyn_array.update_batch(cfg, dyn_array.init(cfg, k), ka, ia, wa)
    sb = dyn_array.update_batch(cfg, dyn_array.init(cfg, k), kb, ib, wb)
    merged = dyn_array.merge_disjoint(cfg, sa, sb)
    np.testing.assert_array_equal(
        np.asarray(merged.regs),
        np.maximum(np.asarray(sa.regs), np.asarray(sb.regs)),
    )
    np.testing.assert_allclose(
        np.asarray(merged.chats), np.asarray(sa.chats) + np.asarray(sb.chats), rtol=1e-6
    )
    with pytest.raises(ValueError, match="matching"):
        dyn_array.merge_disjoint(cfg, sa, dyn_array.init(cfg, k + 1))


def test_chats_additive_across_disjoint_batches():
    """The keyed martingale telescopes: folding one stream in B-sized slices
    equals folding it whole, state-exactly (same chain, same q_R windows)."""
    cfg = SketchConfig(m=128, b=8, seed=14)
    k = 6
    keys, ids, w = _keyed_stream(1024, k, seed=55)
    whole = dyn_array.update_batch(cfg, dyn_array.init(cfg, k), keys, ids, w)
    sliced = dyn_array.init(cfg, k)
    for i in range(0, 1024, 256):
        sliced = dyn_array.update_batch(
            cfg, sliced, keys[i : i + 256], ids[i : i + 256], w[i : i + 256]
        )
    np.testing.assert_array_equal(np.asarray(whole.regs), np.asarray(sliced.regs))
    # Slicing refreshes q_R between slices (LESS stale): chats agree to the
    # staleness bound, not bitwise — ~170 distinct/key against m=128 registers
    # in ONE window is deep staleness, benchmarks/batch_bias.py territory.
    np.testing.assert_allclose(
        np.asarray(whole.chats), np.asarray(sliced.chats), rtol=0.15
    )


def test_row_extraction_and_bounds():
    cfg = SketchConfig(m=64, b=8, seed=15)
    keys, ids, w = _keyed_stream(200, 3, seed=61)
    st = dyn_array.update_batch(cfg, dyn_array.init(cfg, 3), keys, ids, w)
    sel = np.asarray(keys) == 1
    solo = qsketch_dyn.update_batch(
        cfg, qsketch_dyn.init(cfg), jnp.asarray(np.asarray(ids)[sel]), jnp.asarray(np.asarray(w)[sel])
    )
    r = dyn_array.row(st, 1)
    np.testing.assert_array_equal(np.asarray(r.regs), np.asarray(solo.regs))
    np.testing.assert_array_equal(np.asarray(r.hist), np.asarray(solo.hist))
    assert float(r.chat) == pytest.approx(float(solo.chat), rel=1e-5)
    with pytest.raises(IndexError):
        dyn_array.row(st, 3)
    with pytest.raises(ValueError, match="k >= 1"):
        dyn_array.init(cfg, 0)


def test_update_tenants_routes_like_directory():
    cfg = SketchConfig(m=64, b=8, seed=16)
    dcfg = DirectoryConfig(capacity=16, seed=17)
    rng = np.random.default_rng(91)
    tkeys = key_directory.split_uint64(rng.integers(0, 2**64, 200, dtype=np.uint64))
    ids = jnp.asarray(rng.integers(0, 2**32, 200, dtype=np.uint32))
    w = jnp.asarray(rng.uniform(0.5, 1.5, 200).astype(np.float32))
    st, dstate = dyn_array.update_tenants(
        cfg, dcfg, dyn_array.init(cfg, 16), key_directory.init(dcfg), tkeys, ids, w
    )
    slots = key_directory.route_slots(dcfg, tkeys)
    ref = dyn_array.update_batch(cfg, dyn_array.init(cfg, 16), slots, ids, w)
    _assert_states_match(st, ref)
    assert int(dstate.n_routed) == 200
    with pytest.raises(ValueError, match="capacity"):
        dyn_array.update_tenants(
            cfg, dcfg, dyn_array.init(cfg, 8), key_directory.init(dcfg), tkeys, ids, w
        )


# ---------------------------------------------------------------------------
# kernel path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("batch,m,k", SHAPES)
@pytest.mark.parametrize("b", [4, 8])
def test_kernel_vs_core_bit_identity(batch, m, k, b):
    """Pallas (interpret) q_R + shared tail vs core: BITWISE equal states."""
    cfg = SketchConfig(m=m, b=b, seed=batch + m)
    keys, ids, w = _keyed_stream(batch, k, seed=batch * 3 + m)
    st = dyn_array.update_batch(cfg, dyn_array.init(cfg, k), *_keyed_stream(batch, k, seed=1))
    out_kernel = ops.dyn_array_update_op(cfg, st, keys, ids, w, block_b=64, interpret=True)
    out_core = dyn_array.update_batch(cfg, st, keys, ids, w)
    np.testing.assert_array_equal(np.asarray(out_kernel.regs), np.asarray(out_core.regs))
    np.testing.assert_array_equal(np.asarray(out_kernel.hists), np.asarray(out_core.hists))
    np.testing.assert_array_equal(np.asarray(out_kernel.chats), np.asarray(out_core.chats))


def test_kernel_mask_and_tenants_bit_identity():
    cfg = SketchConfig(m=128, b=8, seed=22)
    dcfg = DirectoryConfig(capacity=9, seed=23)
    rng = np.random.default_rng(92)
    tkeys = key_directory.split_uint64(rng.integers(0, 2**64, 300, dtype=np.uint64))
    ids = jnp.asarray(rng.integers(0, 2**32, 300, dtype=np.uint32))
    w = jnp.asarray(rng.uniform(0.5, 1.5, 300).astype(np.float32))
    mask = jnp.asarray(rng.random(300) < 0.7)
    st_k, dir_k = ops.dyn_array_update_tenants_op(
        cfg, dcfg, dyn_array.init(cfg, 9), key_directory.init(dcfg),
        tkeys, ids, w, mask=mask, interpret=True,
    )
    st_c, dir_c = dyn_array.update_tenants(
        cfg, dcfg, dyn_array.init(cfg, 9), key_directory.init(dcfg),
        tkeys, ids, w, mask=mask,
    )
    np.testing.assert_array_equal(np.asarray(st_k.regs), np.asarray(st_c.regs))
    np.testing.assert_array_equal(np.asarray(st_k.chats), np.asarray(st_c.chats))
    np.testing.assert_array_equal(
        np.asarray(dir_k.fingerprints), np.asarray(dir_c.fingerprints)
    )
    assert int(dir_k.n_routed) == int(dir_c.n_routed)


# ---------------------------------------------------------------------------
# monitor + train/serve threading
# ---------------------------------------------------------------------------


def test_dyn_monitor_roundtrip():
    cfg = SketchConfig(m=64, b=8, seed=61)
    mon = monitor.DynArrayMonitor.for_capacity(cfg, 4)
    rng = np.random.default_rng(26)
    n = 2000
    tkeys = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
    ids = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
    w = jnp.asarray(rng.uniform(0.5, 1.5, n).astype(np.float32))
    mask = jnp.asarray(np.arange(n) < 1800)

    st = mon.update(mon.init(), tkeys, ids, w, mask=mask)
    assert int(st.n_seen) == 1800
    est = np.asarray(mon.estimate(st))
    assert est.shape == (4,)
    true_c = float(np.asarray(w, np.float64)[:1800].sum())
    assert abs(est.sum() - true_c) / true_c < 0.1  # martingale total tracks

    m = mon.metrics(st)
    assert int(m["tenant_elements_seen"]) == 1800
    assert int(m["tenant_slots_claimed"]) > 0
    assert float(m["tenant_weight_total"]) == pytest.approx(float(est.sum()), rel=1e-6)

    # Merge of two copies of the SAME stream must not double (MLE re-estimate,
    # not chat addition). Rows carry ~450 distinct elements against m=64
    # registers, the well-loaded regime where the Dyn MLE is specified
    # (DESIGN.md §8.4 documents the lightly-loaded caveat).
    st2 = mon.update(mon.init(), tkeys, ids, w, mask=mask)
    merged = mon.merge(st, st2)
    np.testing.assert_array_equal(np.asarray(merged.regs), np.asarray(st.regs))
    assert int(merged.n_seen) == 3600
    tot = float(np.asarray(mon.estimate(merged)).sum())
    assert abs(tot - true_c) / true_c < 0.35  # per-row MLE noise at m=64


def test_train_step_threads_dyn_tenant_telemetry():
    from repro import configs
    from repro.models import common as mcommon, transformer
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
    mon = monitor.DynArrayMonitor.for_capacity(skc, 256)
    ocfg = optimizer.OptConfig(lr=1e-3, warmup_steps=0)
    step = jax.jit(ts.make_train_step(mcfg, ocfg, None, sketch_cfg=skc, tenant_monitor=mon))
    opt, comp, sk = ts.init_states(mcfg, ocfg, params, sketch_cfg=skc, tenant_monitor=mon)
    assert isinstance(sk, monitor.TelemetryState)

    _, _, _, sk, metrics = step(params, opt, comp, sk, batch)
    assert int(sk.tenants.n_seen) == 64  # 4 x 16 tokens through the array
    assert "tenant_weight_total" in metrics and "distinct_tokens_est" in metrics
    est = np.asarray(mon.estimate(sk.tenants))
    assert (est > 0).sum() == 4  # 4 documents -> exactly 4 live rows


def test_decode_step_threads_dyn_tenant_telemetry():
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
    mon = monitor.DynArrayMonitor.for_capacity(skc, 128)
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
