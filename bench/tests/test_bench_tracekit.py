"""The reduction from a profiler trace to idle share, per-module device time
and idle-gap attribution: on synthetic events, and on a small trace
recorded on a TPU v5e (``fixtures/win_sat_tiny.xplane.pb.gz``: a 0.3 s
``win_k20_sat`` window, seed 3000000031)."""

import gzip
import pathlib

import pytest

import tracekit
from tracekit import Event

FIXTURE = pathlib.Path(__file__).with_name("fixtures") / "win_sat_tiny.xplane.pb.gz"
DEV = "/device:TPU:0"


def _events():
    ms = 1e6
    return [
        Event("/host:CPU", "t", "window", 0, 100 * ms),
        Event("/host:CPU", "t", "route+push", 0, 30 * ms),
        Event("/host:CPU", "t", "rotate", 30 * ms, 50 * ms),
        Event("/host:CPU", "t", "gen_wait", 50 * ms, 100 * ms),
        Event("/host:CPU", "t", "other_thing", 0, 100 * ms),
        Event(DEV, tracekit.MODULES_LINE, "jit_fn(1)", 10 * ms, 40 * ms),
        Event(DEV, tracekit.OPS_LINE, "fusion.1", 10 * ms, 25 * ms),
        Event(DEV, tracekit.OPS_LINE, "scatter.2", 20 * ms, 40 * ms),
        Event(DEV, tracekit.MODULES_LINE, "jit__rotate_impl(2)", 90 * ms, 120 * ms),
        Event(DEV, tracekit.OPS_LINE, "reduce.3", 90 * ms, 120 * ms),
    ]


def test_idle_share_modules_and_gaps_on_synthetic_events():
    tr = tracekit.Trace(_events())
    assert tr.window_s == pytest.approx(0.1)
    # busy: [10, 40) and [90, 100) once clipped to the window
    assert tr.mean_busy_s() == pytest.approx(0.04)
    assert tr.idle_frac() == pytest.approx(0.6)
    assert tr.module_s(["jit_fn"]) == pytest.approx(0.03)
    assert tr.module_s(["jit__rotate_impl"]) == pytest.approx(0.01)
    assert tr.module_s(["absent"]) is None
    gaps = dict(tr.idle_gaps())
    # idle [0, 10) under route+push, [40, 50) under rotate, [50, 90) waiting
    assert gaps == pytest.approx({"route+push": 0.01, "rotate": 0.01, "gen_wait": 0.04})
    top = dict(tr.top_ops())
    assert top["reduce.3"] == pytest.approx(0.01) and top["scatter.2"] == pytest.approx(0.02)


def test_gap_not_covered_by_a_span_is_other():
    host = tracekit.HostSpans([(0.0, 5.0, "rotate")])
    assert host.attribute(2.0, 10.0) == {"rotate": 3.0, "other": 5.0}


def test_recorded_chip_trace(tmp_path):
    path = tmp_path / "trace.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    tr = tracekit.Trace.from_file(str(path))
    assert tr.devices and all(d.startswith("/device:TPU") for d in tr.devices)
    assert 0.0 < tr.idle_frac() < 1.0
    busy = tr.mean_busy_s()
    assert 0.0 < busy <= tr.window_s
    upd = tr.module_s(["jit_fn"])
    assert upd is not None and 0.0 < upd <= busy + 1e-9
    assert tr.module_s(["jit__rotate_impl"]) is None  # no rotation in 0.3 s
    gaps = tr.idle_gaps()
    assert sum(s for _, s in gaps) == pytest.approx(tr.window_s - busy, rel=1e-6)
