"""The ``window_sharded`` driver at a tiny size on four of the CPU's host
devices, through the whole harness but its look for a chip: the window
row-sharded over a four-device mesh agrees with the plain reference, reports
the per-shard split of its micro-batches when traced, and fails the
comparison with one shard's rows dropped from the update."""

import json
import os

# Host devices for the mesh. XLA reads the flag at its first backend
# initialisation, so it is appended as the module is imported, before any
# test can start one (the suite's conftest appends the same flag).
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in (os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=8") if f
    )

import pytest  # noqa: E402

import run as bench_run  # noqa: E402
from bench_fixtures import BENCH, SAMPLE, TINY_WINDOW, make_root, write_json  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402
from repro.sketchstream import ingest  # noqa: E402

CELL, CHIPS, REAL = "t_win4_sat", 4, "win4_k22_sat"
TINY_WINDOW4 = dict(TINY_WINDOW, name="tiny_window4", chips=CHIPS)
SEED = 2**31 + 91


@pytest.fixture(scope="module")
def root4(tmp_path_factory):
    """The tiny checkout plus a four-device window cell, added by files and
    entries alone, that reports every metric the real four-chip cell lists."""
    root = make_root(tmp_path_factory.mktemp("bench4"))
    write_json(root / "bench" / "configs" / "tiny_window4.json", TINY_WINDOW4)
    write_json(root / "bench" / "cells" / f"{CELL}.json",
               {"driver": "window_sharded", "sample": SAMPLE,
                "limits": {"state_mismatch": 0.0, "chat_gap": 1e-3}})
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in real["end_to_end"] + real["per_layer"]
              if REAL in m.get("workloads", [])}
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_window4", "source": "test",
                            "file": "bench/configs/tiny_window4.json", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny_window4", "traffic": "tiny_closed",
                              "chips": CHIPS, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in listed:
            m["workloads"].append(CELL)
    write_json(root / "BENCHMARK.json", spec)
    return root


def _result(capsys, root, trace=0):
    rc = bench_run.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "0.6",
                         "--trace", str(trace)], root=root, require_chip=False)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    return out


def test_sound_sharded_run_is_correct(capsys, root4):
    out = _result(capsys, root4)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == CHIPS
    assert {"setup_s", "ingest_eps"} <= set(out["metrics"])


def test_traced_sharded_run_reports_the_shard_split(capsys, root4):
    out = _result(capsys, root4, trace=1)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert {"shard_skew", "route_wait_ms_per_batch", "compiles_in_window"} <= set(m)
    assert 1.0 <= m["shard_skew"]["value"] <= CHIPS
    assert m["compiles_in_window"]["value"] == 0
    assert not obs_trace.enabled()


def test_update_that_drops_one_shard_is_not_correct(capsys, monkeypatch, root4):
    real_factory = ingest._sharded_window_update_fn
    last = (CHIPS - 1) * TINY_WINDOW4["k"] // CHIPS

    def make(cfg, mesh, axis):
        real = real_factory(cfg, mesh, axis)

        def fn(state, keys, ids, w, mask):
            return real(state, keys, ids, w, mask & (keys < last))
        return fn

    monkeypatch.setattr(ingest, "_sharded_window_update_fn", make)
    out = _result(capsys, root4)
    assert not out["correct"], out["checks"]
    assert out["checks"]["state_mismatch"]["value"] > 0
