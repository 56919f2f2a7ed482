"""Bytes floors of the roofline readers, against counts made by hand."""

import pathlib

import numpy as np

import harness
import loadgen
from bench_fixtures import MIX, TINY_WINDOW
from reference import oracle

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"


def test_update_floor_counts_distinct_rows_per_batch():
    upd = harness.load_module(METRICS / "update_roofline.py", "metric")
    s = loadgen.Stream(MIX, TINY_WINDOW["k"], 9)
    plan = oracle.Plan(events=3000, rotations=[2048], batch=512, warm_events=512)
    slot = oracle.route(s.t_lo, s.t_hi, TINY_WINDOW["k"], TINY_WINDOW["directory_seed"])
    want = 0
    for a, b in [(0, 512), (512, 1024), (1024, 1536), (1536, 2048), (2048, 2560), (2560, 3000)]:
        want += len(set(slot[a:b].tolist())) * (16 + 4 * 256 + 4) * 2 + (b - a) * 12
    assert upd.floor_bytes(s, TINY_WINDOW, plan) == want
    dyn = dict(TINY_WINDOW, container="dyn")
    assert upd.floor_bytes(s, dyn, plan) == (want - 3000 * 12) // 2 + 3000 * 12
    assert upd.row_bytes(128, 8) == 1156


def test_read_floor_is_w_planes_of_registers_plus_estimates():
    rd = harness.load_module(METRICS / "read_roofline.py", "metric")
    conf = {"k": 2**20, "m": 128}
    assert rd.floor_bytes(conf, 2) == 2 * 2**20 * 128 + 4 * 2**20


def test_roofline_share_from_a_trace():
    """A reader of a share returns None with nothing to read, never 0."""
    upd = harness.load_module(METRICS / "update_roofline.py", "metric")
    run = {"trace": None, "peak": {"hbm_bytes_per_s": 819e9}}
    assert upd.read(run) is None
    np.testing.assert_allclose(upd.row_bytes(16, 8) * 1.0, 1044.0)
