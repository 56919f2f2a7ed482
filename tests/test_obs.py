"""qobs observability layer tests (src/repro/obs/, DESIGN.md §10).

Coverage per the PR 9 acceptance list:

* registry counter/gauge/histogram semantics (labels, delta snapshots,
  reset, declaration idempotence/mismatch),
* the disabled-mode no-op path (emissions ignored, snapshots empty),
* trace span nesting + the Chrome trace-event JSON contract Perfetto loads,
  spans as profiler annotations on the host plane of a ``.xplane.pb``, and
  compiles recorded with the span that caused them,
* ``health_report`` values against hand-built container states, including
  a deliberately top-bin-clamped int8 register plane,
* a Prometheus text-format golden,
* shimmed monitor ``metrics()`` key/value parity for every monitor, and
* the IngestStats lifetime fix: back-to-back pipelines report independent
  numbers, ``snapshot(delta=True)``/``reset()`` semantics.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SketchConfig, dyn_array, key_directory, qsketch, window_array
from repro.core.key_directory import DirectoryConfig
from repro.core.types import QSketchState, WindowArrayState
from repro.launch.mesh import make_sketch_mesh
from repro.obs import export as obs_export
from repro.obs import health as obs_health
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import Registry
from repro.obs.trace import Tracer
from repro.sketchstream import ingest, monitor

CFG = SketchConfig(m=64, b=6, seed=3)


def _stream(n, seed=0, keys_mod=None):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, keys_mod or 8, n, dtype=np.int32)
    ids = rng.integers(0, 2**32, n, dtype=np.uint32)
    w = rng.uniform(0.1, 2.0, n).astype(np.float32)
    return keys, ids, w


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_semantics():
    reg = Registry()
    c = reg.counter("t_requests", help="h")
    g = reg.gauge("t_depth")
    h = reg.histogram("t_lat", low_exp=0, high_exp=3)  # bounds 1,2,4,8 +inf

    c.inc()
    c.inc(4)
    g.set(7)
    g.set(3)
    g.set_max(2)  # below current -> no change
    g.set_max(9)
    for v in (0.5, 3.0, 100.0):
        h.observe(v)

    snap = reg.snapshot()
    assert snap["t_requests"] == 5
    assert snap["t_depth"] == 9
    hist = snap["t_lat"]
    assert hist["count"] == 3 and hist["sum"] == pytest.approx(103.5)
    # 0.5 -> le=1 bucket; 3.0 -> le=4; 100 -> overflow.
    assert hist["buckets"] == [1, 0, 1, 0, 1]
    assert hist["le"] == [1.0, 2.0, 4.0, 8.0, float("inf")]

    with pytest.raises(ValueError):
        c.inc(-1)


def test_labels_and_declaration_contract():
    reg = Registry()
    fam = reg.counter("t_pushed", labels=("pipe",))
    fam.labels(pipe="a").inc(2)
    fam.labels(pipe="b").inc(3)
    snap = reg.snapshot()
    assert snap == {'t_pushed{pipe="a"}': 2, 't_pushed{pipe="b"}': 3}
    # Re-declaration with matching signature is idempotent (same family)...
    assert reg.counter("t_pushed", labels=("pipe",)) is fam
    # ...a mismatched one raises, as do bad names / bad label sets.
    with pytest.raises(ValueError):
        reg.gauge("t_pushed", labels=("pipe",))
    with pytest.raises(ValueError):
        reg.counter("BadName")
    with pytest.raises(ValueError):
        fam.labels(nope="x")


def test_delta_snapshots_and_reset():
    reg = Registry()
    c = reg.counter("t_n")
    g = reg.gauge("t_g")
    c.inc(10)
    g.set(5)
    assert reg.snapshot(delta=True) == {"t_n": 10, "t_g": 5}
    c.inc(3)
    # Counter deltas report the interval; gauges stay point-in-time.
    assert reg.snapshot(delta=True) == {"t_n": 3, "t_g": 5}
    assert reg.snapshot(delta=True) == {"t_n": 0, "t_g": 5}
    assert reg.snapshot() == {"t_n": 13, "t_g": 5}  # cumulative untouched
    reg.reset()
    assert reg.snapshot() == {"t_n": 0, "t_g": 0}


def test_disabled_registry_is_noop():
    reg = Registry(enabled=False)
    c = reg.counter("t_n")
    h = reg.histogram("t_h")
    c.inc(100)
    h.observe(1.0)
    assert c.value == 0 and h._default.count == 0
    assert reg.snapshot() == {}
    # Re-enabling resumes recording from the frozen values.
    reg.configure(enabled=True)
    c.inc(2)
    assert reg.snapshot()["t_n"] == 2


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


def test_trace_nesting_and_chrome_json(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("outer", k=1):
        with tr.span("inner"):
            pass
    events = tr.events()
    assert [e["name"] for e in events] == ["inner", "outer"]  # exit order
    inner, outer = events
    assert inner["args"]["path"] == "outer/inner"
    assert outer["args"] == {"path": "outer", "k": 1}
    # Chrome trace-event contract: complete events, µs timestamps, and the
    # inner span nested inside the outer one's [ts, ts+dur) interval.
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3

    path = tmp_path / "trace.json"
    tr.save(str(path))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    assert {e["name"] for e in doc["traceEvents"]} == {"outer", "inner"}
    assert tr.stage_totals()["outer"] >= tr.stage_totals()["inner"]


def test_trace_disabled_and_under_jit_noop():
    tr = Tracer(enabled=False)
    assert tr.span("x") is obs_trace._NULL
    tr.configure(enabled=True)

    seen = []

    @jax.jit
    def f(x):
        # Under an active trace the span must degrade to the shared no-op.
        seen.append(tr.span("inside_jit"))
        return x + 1

    f(jnp.zeros(())).block_until_ready()
    assert seen[0] is obs_trace._NULL
    assert tr.events() == []


@pytest.fixture
def default_trace():
    """The default tracer enabled and empty; disabled and emptied after."""
    obs_trace.configure(enabled=True)
    obs_trace.clear()
    yield obs_trace
    obs_trace.configure(enabled=False)
    obs_trace.clear()


def _compiles(fun):
    return [e for e in obs_trace.events()
            if e["name"] == obs_trace.COMPILE_EVENT and e["args"]["fun"] == fun]


def test_span_is_a_profiler_annotation_on_the_host_plane(tmp_path, default_trace):
    import glob
    import time

    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.span("qobs_probe_span"):
            time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = [
        ev.duration_ns
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
        if ev.name == "qobs_probe_span"
    ]
    assert len(found) == 1
    (ev,) = [e for e in obs_trace.events() if e["name"] == "qobs_probe_span"]
    assert abs(found[0] / 1e3 - ev["dur"]) < 1e3  # µs: within 1 ms


def test_disabled_tracer_records_no_span_or_compile(default_trace):
    obs_trace.configure(enabled=False)
    assert obs_trace.span("x") is obs_trace._NULL

    def qobs_probe_off(x):
        return x * 5 - 1

    before = obs_trace._M_COMPILES.labels(span="", fun="jit(qobs_probe_off)").value
    jax.jit(qobs_probe_off)(np.ones(3, np.float32)).block_until_ready()
    assert obs_trace.events() == []
    assert obs_trace._M_COMPILES.labels(span="", fun="jit(qobs_probe_off)").value == before


def test_compile_is_recorded_with_its_enclosing_span(default_trace):
    def qobs_probe_fn(x):
        return x * 3 + 1

    fam = obs_trace._M_COMPILES.labels(span="outer", fun="jit(qobs_probe_fn)")
    before = fam.value
    f = jax.jit(qobs_probe_fn)
    with obs_trace.span("outer"):
        f(np.ones(4, np.float32)).block_until_ready()
    (ev,) = _compiles("jit(qobs_probe_fn)")
    assert ev["args"]["path"] == "outer" and ev["dur"] > 0
    (outer,) = [e for e in obs_trace.events() if e["name"] == "outer"]
    assert outer["ts"] <= ev["ts"] and ev["ts"] + ev["dur"] <= outer["ts"] + outer["dur"] + 1
    if obs_metrics.enabled():
        assert fam.value == before + 1
    # A second call hits the in-memory executable: no compile.
    with obs_trace.span("outer"):
        f(np.ones(4, np.float32)).block_until_ready()
    assert len(_compiles("jit(qobs_probe_fn)")) == 1


def test_compile_event_name_matches_jax():
    from jax._src import dispatch

    assert obs_trace.BACKEND_COMPILE_EVENT == dispatch.BACKEND_COMPILE_EVENT


def test_record_interval_event():
    import time

    tr = Tracer(enabled=False)
    t0 = time.perf_counter_ns()
    tr.record("fill", t0, n=3)
    assert tr.events() == []
    tr.configure(enabled=True)
    tr.record("fill", t0, n=3, partial=True)
    (ev,) = tr.events()
    assert ev["name"] == "fill" and ev["dur"] >= 0
    assert ev["args"] == {"path": "fill", "n": 3, "partial": True}


# ---------------------------------------------------------------------------
# health reports
# ---------------------------------------------------------------------------


def test_health_saturated_plane_warns_healthy_quiet():
    rng = np.random.default_rng(7)
    ids = jnp.asarray(rng.integers(0, 2**63, 800, dtype=np.int64))
    w = jnp.asarray(rng.uniform(0.1, 2.0, 800), jnp.float32)
    healthy = qsketch.update(CFG, qsketch.init(CFG), ids, w)
    rep = obs_health.health_report(CFG, healthy)
    assert rep["container"] == "qsketch" and rep["ok"], rep["warnings"]

    # Hand-built top-bin-clamped int8 plane: every register at r_max.
    clamped = QSketchState(regs=jnp.full((CFG.m,), CFG.r_max, jnp.int8))
    rep = obs_health.health_report(CFG, clamped)
    assert not rep["ok"] and "register_saturation_frac" in rep["warnings"]
    assert rep["checks"]["register_saturation_frac"]["value"] == 1.0
    # A fresh plane: zero saturation, zero occupancy.
    rep = obs_health.health_report(CFG, qsketch.init(CFG))
    assert rep["checks"]["register_saturation_frac"]["value"] == 0.0
    assert rep["checks"]["occupancy_frac"]["value"] == 0.0


def test_health_dyn_array_and_drift_threshold():
    k, n = 4, 4000
    keys, ids, w = _stream(n, seed=1, keys_mod=k)
    st = dyn_array.update_batch(
        CFG, dyn_array.init(CFG, k),
        jnp.asarray(keys), jnp.asarray(ids), jnp.asarray(w),
    )
    rep = obs_health.health_report(CFG, st)
    assert rep["container"] == "dyn_array" and rep["ok"], rep["warnings"]
    # Corrupt the martingales by 100x: the anytime-vs-MLE drift check is
    # exactly the probe that must fire.
    bad = st._replace(chats=st.chats * 100.0)
    rep = obs_health.health_report(CFG, bad)
    assert "anytime_mle_drift" in rep["warnings"]


def test_health_window_staleness_and_directory():
    k, e = 8, 3
    keys, ids, w = _stream(2000, seed=2, keys_mod=k)
    st = window_array.update_batch(
        CFG, window_array.init(CFG, k, e),
        jnp.asarray(keys), jnp.asarray(ids), jnp.asarray(w),
    )
    rep = obs_health.health_report(CFG, st)
    assert rep["container"] == "window_array"
    assert rep["checks"]["union_staleness_frac"]["value"] == 0.0
    # Corrupt the union cache: staleness must flag (threshold is 0).
    stale = st._replace(union_regs=jnp.zeros_like(st.union_regs))
    rep = obs_health.health_report(CFG, stale)
    assert "union_staleness_frac" in rep["warnings"]

    # Directory checks ride along when a directory is passed.
    dcfg = DirectoryConfig(capacity=8, seed=3)
    dstate = key_directory.init(dcfg)
    _, dstate = key_directory.route(
        dcfg, dstate, jnp.asarray(np.arange(64, dtype=np.uint32))
    )
    rep = obs_health.health_report(CFG, st, directory=dstate, dcfg=dcfg)
    assert "directory_load_factor" in rep["checks"]
    assert "directory_load_factor" in rep["warnings"]  # 64 keys into 8 slots


def test_health_virtual_pool_thresholds():
    """Satellite #4: the virtual tier's pool checks warn past their bounds
    and stay quiet inside them, and the hot tier folds in under hot_*."""
    from repro.core import virtual_dyn_array as vda
    from repro.core.virtual_dyn_array import VirtualConfig

    rng = np.random.default_rng(5)
    tk = jnp.asarray(rng.integers(0, 2**31, 600, dtype=np.int64), jnp.uint32)
    ids = jnp.asarray(rng.integers(0, 2**31, 600, dtype=np.int64), jnp.uint32)
    w = jnp.asarray(rng.uniform(0.5, 1.5, 600), jnp.float32)

    # Small pool -> load factor blows past the 0.5 default and warns.
    vcfg = VirtualConfig(pool_size=256, pinned=(7,))
    st = vda.update_tenants(CFG, vcfg, vda.init(CFG, vcfg), tk, ids, w)
    rep = obs_health.health_report(CFG, st, vcfg=vcfg)
    assert rep["container"] == "virtual_dyn_array"
    assert "pool_load_factor" in rep["warnings"]
    assert rep["checks"]["pool_load_factor"]["value"] == pytest.approx(
        float(vda.pool_load_factor(st))
    )
    # The reported floor is the estimator's own subtraction term.
    assert rep["checks"]["pool_noise_floor"]["value"] == pytest.approx(
        float(vda.noise_floor(CFG, vcfg, st)), rel=1e-6
    )
    assert not rep["checks"]["pool_noise_floor"]["warn"]  # no default bound
    assert rep["checks"]["pool_weight_total"]["value"] == pytest.approx(
        float(st.w_tail)
    )
    assert any(k.startswith("hot_") for k in rep["checks"])

    # Large pool -> same traffic is healthy; tight floor bound flips it.
    vcfg_big = VirtualConfig(pool_size=1 << 14, pinned=(7,))
    st_big = vda.update_tenants(
        CFG, vcfg_big, vda.init(CFG, vcfg_big), tk, ids, w
    )
    rep = obs_health.health_report(CFG, st_big, vcfg=vcfg_big)
    assert "pool_load_factor" not in rep["warnings"]
    tight = obs_health.Thresholds(pool_noise_floor=1e-3)
    rep = obs_health.health_report(CFG, st_big, vcfg=vcfg_big, thresholds=tight)
    assert "pool_noise_floor" in rep["warnings"]
    # An empty container is quiet under the defaults.
    rep = obs_health.health_report(CFG, vda.init(CFG, vcfg_big), vcfg=vcfg_big)
    assert rep["ok"], rep["warnings"]


def test_health_rejects_unknown_and_traced():
    with pytest.raises(TypeError):
        obs_health.health_report(CFG, object())

    @jax.jit
    def f(x):
        with pytest.raises(RuntimeError):
            obs_health.health_report(CFG, QSketchState(regs=x))
        return x

    f(jnp.zeros((CFG.m,), jnp.int8))


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_prometheus_text_golden(tmp_path):
    reg = Registry()
    reg.counter("t_reqs", help="requests", labels=("pipe",)).labels(pipe="0").inc(3)
    reg.gauge("t_depth").set(2)
    h = reg.histogram("t_lat", low_exp=0, high_exp=1)  # bounds 1, 2, +inf
    h.observe(0.5)
    h.observe(1.5)
    h.observe(9.0)
    golden = (
        '# HELP t_reqs requests\n'
        '# TYPE t_reqs counter\n'
        't_reqs{pipe="0"} 3\n'
        '# TYPE t_depth gauge\n'
        't_depth 2\n'
        '# TYPE t_lat histogram\n'
        't_lat_bucket{le="1"} 1\n'
        't_lat_bucket{le="2"} 2\n'
        't_lat_bucket{le="+Inf"} 3\n'
        't_lat_sum 11.0\n'
        't_lat_count 3\n'
    )
    assert obs_export.prometheus_text(reg) == golden
    path = tmp_path / "metrics.prom"
    obs_export.write_prometheus(str(path), reg)
    assert path.read_text() == golden
    assert obs_export.prometheus_text(Registry(enabled=False)) == ""


def test_jsonl_writer_delta(tmp_path):
    reg = Registry()
    c = reg.counter("t_n")
    path = tmp_path / "obs.jsonl"
    wr = obs_export.JsonlWriter(str(path), reg, delta=True)
    c.inc(5)
    wr.write(step=1)
    c.inc(2)
    wr.write(step=2)
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    assert [r["metrics"]["t_n"] for r in recs] == [5, 2]
    assert [r["step"] for r in recs] == [1, 2]
    assert all("ts" in r for r in recs)


# ---------------------------------------------------------------------------
# monitor metrics() shims: key/value parity with the historical dicts
# ---------------------------------------------------------------------------


def _tenant_stream(n, seed=0):
    rng = np.random.default_rng(seed)
    tenants = rng.integers(1, 6, n, dtype=np.uint32)
    ids = rng.integers(0, 2**32, n, dtype=np.uint32)
    w = rng.uniform(0.1, 2.0, n).astype(np.float32)
    return jnp.asarray(tenants), jnp.asarray(ids), jnp.asarray(w)


def _expect_base(state):
    return {
        "tenant_elements_seen": int(state.n_seen),
        "tenant_slots_claimed": int(
            jnp.sum((state.directory.fingerprints != 0).astype(jnp.int32))
        ),
        "tenant_collision_rate": float(
            key_directory.collision_rate(state.directory)
        ),
    }


@pytest.mark.parametrize("kind", ["dyn", "window", "sharded_array",
                                  "sharded_dyn", "sharded_window", "virtual"])
def test_monitor_metrics_parity(kind):
    tenants, ids, w = _tenant_stream(256, seed=11)
    if kind == "virtual":
        from repro.core import virtual_dyn_array as vda

        mon = monitor.VirtualDynMonitor.for_pool(CFG, 512, pinned=(1,))
        st = mon.update(mon.init(), tenants, ids, w)
        got = mon.metrics(st)
        # No directory telemetry (stateless tail routing) — pool pressure
        # replaces it; key order is the documented dict.
        expect = {
            "tenant_elements_seen": int(st.n_seen),
            "virtual_pool_load_factor": float(vda.pool_load_factor(st.array)),
            "virtual_pool_weight_total": float(st.array.w_tail),
            "virtual_tail_elements": int(st.array.n_tail),
            "tenant_weight_total": float(jnp.sum(st.array.hot.chats)),
        }
        assert list(got) == list(expect)
        for k, v in expect.items():
            assert float(got[k]) == pytest.approx(v), k
        if obs_metrics.enabled():
            snap = obs_metrics.snapshot()
            for k in expect:
                assert f'{k}{{monitor="virtual_dyn"}}' in snap, k
        return
    if kind == "dyn":
        mon = monitor.DynArrayMonitor.for_capacity(CFG, 16)
        expect_extra = lambda st: {
            "tenant_weight_total": float(jnp.sum(st.chats))
        }
    elif kind == "window":
        mon = monitor.WindowMonitor.for_capacity(CFG, 16, 3)
        expect_extra = lambda st: {
            "tenant_window_weight": float(jnp.sum(st.window.union_chats)),
            "tenant_window_epoch": int(st.window.epoch_id),
        }
    elif kind == "sharded_array":
        mon = monitor.ShardedArrayMonitor.for_mesh(CFG, 16, make_sketch_mesh(2))
        expect_extra = lambda st: {}
    elif kind == "sharded_dyn":
        mon = monitor.ShardedDynMonitor.for_mesh(CFG, 16, make_sketch_mesh(2))
        expect_extra = lambda st: {"tenant_weight_total": float(jnp.sum(st.array.chats))}
    else:
        mon = monitor.ShardedWindowMonitor.for_mesh(
            CFG, 16, 3, make_sketch_mesh(2)
        )
        expect_extra = lambda st: {
            "tenant_window_weight": float(jnp.sum(st.window.union_chats)),
            "tenant_window_epoch": int(st.window.epoch_id),
        }
    st = mon.update(mon.init(), tenants, ids, w)
    got = mon.metrics(st)
    expect = {**_expect_base(st), **expect_extra(st)}
    # Exact historical key ORDER and values.
    assert list(got) == list(expect)
    for k, v in expect.items():
        assert float(got[k]) == pytest.approx(v), k
    # The shim also mirrors into the default registry (when enabled).
    if obs_metrics.enabled():
        snap = obs_metrics.snapshot()
        for k in expect:
            key = f'{k}{{monitor="{_kind_label(kind)}"}}'
            assert key in snap, key


def _kind_label(kind):
    return {"dyn": "dyn_array", "window": "window",
            "sharded_array": "sharded_array", "sharded_dyn": "sharded_dyn",
            "sharded_window": "sharded_window"}[kind]


def test_monitor_metrics_traceable_under_jit():
    mon = monitor.DynArrayMonitor.for_capacity(CFG, 16)
    st = mon.init()

    @jax.jit
    def f(s):
        return mon.metrics(s)["tenant_collision_rate"]

    assert float(f(st)) == 0.0


# ---------------------------------------------------------------------------
# IngestStats lifetime semantics
# ---------------------------------------------------------------------------


def _run_pipe(n, seed):
    keys, ids, w = _stream(n, seed=seed, keys_mod=16)
    pipe = ingest.dyn_pipeline(
        CFG, dyn_array.init(CFG, 16), ingest.IngestConfig(batch_size=64)
    )
    pipe.push(keys, ids, w)
    pipe.result()
    return pipe


def test_ingest_stats_back_to_back_independent():
    a = _run_pipe(256, seed=1)
    b = _run_pipe(256, seed=2)
    # The historical bug: a second pipeline's counters continued from the
    # first one's totals. Each run must stand alone.
    assert a.stats.pushed == 256
    assert b.stats.pushed == 256
    assert b.stats.batches == 4
    assert b.metrics()["ingest_elements_pushed"] == 256


def test_ingest_stats_delta_snapshot_and_reset():
    pipe = _run_pipe(128, seed=3)
    s = pipe.stats
    first = s.snapshot(delta=True)
    assert first["pushed"] == 128
    # No traffic since the last delta snapshot -> counters read zero,
    # gauges stay point-in-time.
    second = s.snapshot(delta=True)
    assert second["pushed"] == 0
    assert second["max_in_flight"] == first["max_in_flight"]
    assert s.snapshot()["pushed"] == 128  # cumulative intact
    s.reset()
    assert s.snapshot()["pushed"] == 0
    assert s.pushed == 0


def test_ingest_metrics_dict_shape():
    pipe = _run_pipe(64, seed=4)
    m = pipe.metrics()
    assert list(m) == [
        "ingest_elements_pushed", "ingest_elements_dropped", "ingest_batches",
        "ingest_partial_batches", "ingest_stalls", "ingest_stall_s",
        "ingest_in_flight", "ingest_max_in_flight", "ingest_rotations",
        "ingest_barriers",
    ]
    assert isinstance(m["ingest_stall_s"], float)
    assert m["ingest_elements_pushed"] == 64
