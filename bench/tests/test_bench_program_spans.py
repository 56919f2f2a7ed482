"""Readers of the program's own spans and events (``repro.obs.trace``): on
hand-built runs and tracer events, the alignment of the program's clock to
the profiler's by pairing, and a traced tiny run through the harness."""

import json
import pathlib

import pytest

import harness
import run as bench_run
from repro.obs import trace as obs_trace
from tracekit import Event

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
HOST, DEV = "/host:CPU", "/device:TPU:0"
MS = 1e6  # ns


def _reader(name):
    return harness.load_module(METRICS / f"{name}.py", "metric")


def _ev(name, ts_ms, dur_ms, **args):
    return {"name": name, "cat": "qobs", "ph": "X", "ts": ts_ms * 1e3, "dur": dur_ms * 1e3,
            "pid": 0, "tid": 1, "args": {"path": name, **args}}


@pytest.fixture
def events(monkeypatch):
    """Stand-in for the default tracer's events."""
    evs = []
    monkeypatch.setattr(obs_trace, "events", lambda: list(evs))
    return evs


def test_route_readers_divide_span_seconds_by_batches():
    run = {"qobs": {"ingest/route": 0.5, "ingest/route_wait": 3.0, "ingest/push": 1.0}, "batches": 100}
    assert _reader("route_wait_ms_per_batch").read(run) == pytest.approx(30.0)
    assert _reader("route_dispatch_ms_per_batch").read(run) == pytest.approx(5.0)
    # A program without the spans (or an untraced run) reads nothing.
    for name in ("route_wait_ms_per_batch", "route_dispatch_ms_per_batch"):
        assert _reader(name).read({"qobs": {"ingest/push": 1.0}, "batches": 100}) is None
        assert _reader(name).read({"qobs": None, "batches": 100}) is None


def test_fill_p95_over_the_fill_events(events):
    fill = _reader("fill_p95_ms")
    run = {"qobs": {"ingest/push": 1.0}}
    assert fill.read(run) is None  # no fill events: a program without them
    events += [_ev("ingest/fill", 10 * i, float(i), n=4, partial=False) for i in range(1, 101)]
    events.append(_ev("ingest/push", 0, 5000.0))
    assert fill.read(run) == pytest.approx(95.05)


def test_compiles_in_window_counts_compile_events(events, capsys):
    comp = _reader("compiles_in_window")
    run = {"qobs": {"ingest/push": 1.0}}
    assert comp.read(run) == 0
    events.append({**_ev(obs_trace.COMPILE_EVENT, 1, 2), "args": {"path": "ingest/push", "fun": "jit(f)"}})
    assert comp.read(run) == 1
    assert "jit(f) under ingest/push" in capsys.readouterr().err
    assert comp.read({"qobs": None}) is None


def test_compiles_in_window_none_without_a_compile_listener(events, monkeypatch):
    monkeypatch.delattr(obs_trace, "COMPILE_EVENT")
    assert _reader("compiles_in_window").read({"qobs": {"ingest/push": 1.0}}) is None


class _Trace:
    """What the idle reader uses of ``tracekit.Trace``."""

    def __init__(self, host, busy, t0, t1):
        self.host, self._busy, self.t0, self.t1 = host, busy, t0, t1
        self.devices = [DEV]

    def busy_intervals(self, device):
        return self._busy


OFF = 1000 * MS  # the profiler's clock runs this far ahead of the program's


def _chunk(events, host, start_ms, route_ms, wait_ms, push_ms, end_skew_ms=0.0):
    """One arrival chunk through the window driver: the harness annotation
    around route, route_wait and push (with a dispatch inside push)."""
    r0 = start_ms
    events.append(_ev("ingest/route", r0, route_ms))
    events.append(_ev("ingest/route_wait", r0 + route_ms, wait_ms))
    p0 = r0 + route_ms + wait_ms
    events.append(_ev("ingest/push", p0, push_ms))
    events.append(_ev("ingest/dispatch", p0 + push_ms / 2, push_ms / 4))
    end = (p0 + push_ms + end_skew_ms) * MS + OFF
    host.append(Event(HOST, "t", "route+push", (start_ms - 0.001) * MS + OFF, end))


def test_idle_in_program_aligns_by_pairing_and_splits_by_innermost_span(events, capsys):
    idle = _reader("idle_in_program_ms_per_batch")
    host = []
    # Two chunks whose starts lie far apart from their annotations' starts
    # (a long route_wait), so only the ends pair.
    _chunk(events, host, 0.0, 2.0, 30.0, 8.0)  # route [0,2) wait [2,32) push [32,40)
    _chunk(events, host, 50.0, 2.0, 5.0, 8.0)  # route [50,52) wait [52,57) push [57,65)
    # Device busy [1, 20) and [33, 60) of a [0, 100) window: idle [0,1) under
    # route, [20,32) route_wait, [32,33) push self; [60,61) push self,
    # [61,63) dispatch, [63,65) push self, [65,100) outside every span.
    busy = [(1 * MS + OFF, 20 * MS + OFF), (33 * MS + OFF, 60 * MS + OFF)]
    run = {"trace": _Trace(host, busy, OFF, 100 * MS + OFF), "qobs": {"ingest/push": 0.016}, "batches": 2}
    s = idle.split(run)
    assert s == pytest.approx({"ingest/route": 1e-3, "ingest/route_wait": 12e-3, "ingest/push": 4e-3,
                               "ingest/dispatch": 2e-3, "outside": 35e-3}, abs=1e-9)
    assert idle.read(run) == pytest.approx(19.0 / 2)
    assert "idle by program span" in capsys.readouterr().err


def test_idle_in_program_none_where_the_pairs_do_not_pair(events):
    idle = _reader("idle_in_program_ms_per_batch")
    host = []
    _chunk(events, host, 0.0, 1.0, 1.0, 1.0)
    _chunk(events, host, 10.0, 1.0, 1.0, 1.0)
    busy = [(OFF, OFF + 5 * MS)]
    run = {"trace": _Trace(host, busy, OFF, OFF + 20 * MS), "qobs": {"ingest/push": 0.002}, "batches": 1}
    assert idle.read(run) is not None
    # A chunk with no program span inside its annotation: counts differ.
    counted = dict(run, trace=_Trace(host + [Event(HOST, "t", "route+push", OFF + 15 * MS, OFF + 16 * MS)],
                                     busy, OFF, OFF + 20 * MS))
    assert idle.read(counted) is None
    # Pairs whose ends disagree by more than 1 ms at the 95th percentile.
    events.clear()
    host = []
    _chunk(events, host, 0.0, 1.0, 1.0, 1.0)
    _chunk(events, host, 10.0, 1.0, 1.0, 1.0, end_skew_ms=3.0)
    assert idle.read(dict(run, trace=_Trace(host, busy, OFF, OFF + 20 * MS))) is None
    # No device trace, or an untraced run.
    assert idle.read(dict(run, trace=None)) is None
    assert idle.read(dict(run, qobs=None)) is None


def test_innermost_segments_of_nested_spans():
    idle = _reader("idle_in_program_ms_per_batch")
    segs = idle.innermost([(0, 10, "a"), (2, 4, "b"), (3, 4, "c"), (6, 8, "d"), (12, 13, "e")])
    assert segs == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 6, "a"), (6, 8, "d"), (8, 10, "a"),
                    (12, 13, "e")]
    assert idle.overlap([(1, 3), (7, 12.5)], segs) == {"a": 3, "b": 1, "d": 1, "e": 0.5}


@pytest.mark.parametrize("cell,present", [
    ("t_win_sat", {"route_wait_ms_per_batch", "route_dispatch_ms_per_batch", "compiles_in_window"}),
    ("t_dyn_sat", {"compiles_in_window"}),
    ("t_win_paced", {"fill_p95_ms"}),
])
def test_traced_tiny_run_reports_the_program_span_metrics(capsys, tiny_root, cell, present):
    rc = bench_run.main(["--workload", cell, "--seed", str(2**31 + 5), "--seconds", "0.6", "--trace", "1"],
                        root=tiny_root, require_chip=False)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert present <= set(out["metrics"])
    if "compiles_in_window" in present:
        assert out["metrics"]["compiles_in_window"]["value"] == 0
    assert not obs_trace.enabled()
