"""Cells, configurations, traffic mixes, drivers and metrics are found by
name from directory listings; a new cell needs only new files and entries."""

import json
import shutil

import harness
from bench_fixtures import SAMPLE, write_json


def test_every_benchmark_name_has_its_files():
    spec = harness.benchmark()
    cells = harness.listing("cells", ".json")
    assert {w["name"] for w in spec["workloads"]} <= set(cells)
    assert {w["traffic"] for w in spec["workloads"]} <= set(harness.listing("traffic", ".json"))
    metrics = set(harness.listing("metrics", ".py"))
    assert {m["name"] for m in spec["end_to_end"] + spec["per_layer"]} <= metrics
    drivers = set(harness.listing("drivers", ".py"))
    for w in spec["workloads"]:
        cell = harness.Cell(w["name"])
        assert cell.spec["driver"] in drivers
        assert callable(cell.driver.run)
        for m in cell.end_to_end + cell.per_layer:
            assert callable(cell.reader(m["name"]))
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_a_temporary_cell_is_added_by_files_and_entries_alone(tmp_path, tiny_root):
    root = tmp_path / "checkout"
    shutil.copytree(tiny_root, root)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    write_json(root / "bench" / "traffic" / "tiny_uniform.json",
               dict(json.loads((root / "bench" / "traffic" / "tiny_closed.json").read_text()), zipf_s=0.0))
    write_json(root / "bench" / "cells" / "t_win_uniform.json",
               {"driver": "window", "sample": SAMPLE, "limits": {"state_mismatch": 0.0}})
    (root / "bench" / "metrics" / "uniform_probe.py").write_text(
        "def read(run):\n    return run['events'] / 2\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "t_win_uniform", "config": "tiny_window",
                              "traffic": "tiny_uniform", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "uniform_probe", "unit": "events", "better": "higher",
                              "source": "host_clock", "layer": "entry", "moves": "ingest_eps",
                              "workloads": ["t_win_uniform"]})
    write_json(root / "BENCHMARK.json", spec)
    cell = harness.Cell("t_win_uniform", root)
    assert cell.mix["zipf_s"] == 0.0 and cell.config["name"] == "tiny_window"
    assert [m["name"] for m in cell.per_layer] == ["uniform_probe"]
    assert cell.reader("uniform_probe")({"events": 8}) == 4
    after = {p: p.read_bytes() for p in before}
    assert after == before  # no existing file changed
