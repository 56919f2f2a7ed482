"""Tiny cells for the benchmark's tests: a copy of ``bench/`` in a temporary
checkout whose ``BENCHMARK.json`` names tiny configurations, so nothing
under the real ``bench/`` changes."""

import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for _p in (BENCH, BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

TINY_WINDOW = {
    "name": "tiny_window", "source": "test", "container": "window", "k": 1024, "m": 16, "b": 8,
    "epochs": 4, "batch": 512, "queue_depth": 4, "directory_capacity": 1024, "epoch_s": 0.1,
    "chips": 1, "sketch_seed": 24301, "directory_seed": 24301, "reduced": [], "assumed": {},
}
TINY_DYN = dict(TINY_WINDOW, name="tiny_dyn", container="dyn", k=2048, directory_capacity=2048)
MIX = {
    "arrival": "closed", "chunk": 128, "pool_events": 4096, "zipf_s": 1.2, "burst_every": 4,
    "burst_frac": 0.5, "burst_tenants": 4, "id_pool_frac": 0.5,
    "weights": {"model": "lognormal", "mu": 6.0, "sigma": 1.0, "clip": [40, 65535]},
}
PACED = dict(MIX, arrival="open", rate_eps=20000, anytime_read_s=0.05, subring_read_s=0.3, subring_w=2)
GAMMA = dict(MIX, weights={"model": "gamma", "shape": 1.0, "scale": 2.0, "offset": 0.0001})
SAMPLE = {"hot": 4, "touched": 24, "untouched": 4}
CELLS = {
    "t_win_sat": ("tiny_window", "tiny_closed", "window", {"state_mismatch": 0.0, "chat_gap": 1e-3}),
    "t_win_paced": ("tiny_window", "tiny_paced", "window",
                    {"state_mismatch": 0.0, "chat_gap": 1e-3, "read_gap": 1e-3}),
    "t_dyn_sat": ("tiny_dyn", "tiny_gamma", "dyn", {"state_mismatch": 0.0, "chat_gap": 1e-3}),
}


def write_json(path: pathlib.Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1) + "\n")


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout holding a copy of bench/ and a BENCHMARK.json of tiny
    cells built from the real one's metrics."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("tests", "__pycache__"))
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for conf in (TINY_WINDOW, TINY_DYN):
        write_json(root / "bench" / "configs" / f"{conf['name']}.json", conf)
    for name, mix in (("tiny_closed", MIX), ("tiny_paced", PACED), ("tiny_gamma", GAMMA)):
        write_json(root / "bench" / "traffic" / f"{name}.json", mix)
    workloads = []
    for name, (conf, mix, driver, limits) in CELLS.items():
        write_json(root / "bench" / "cells" / f"{name}.json",
                   {"driver": driver, "sample": SAMPLE, "limits": limits})
        workloads.append({"name": name, "config": conf, "traffic": mix, "chips": 1, "why": "test"})
    spec = {
        "command": real["command"], "paths": real["paths"], "run_seconds": real["run_seconds"],
        "configs": [{"name": c["name"], "source": "test", "file": f"bench/configs/{c['name']}.json",
                     "reduced": [], "why": "test"} for c in (TINY_WINDOW, TINY_DYN)],
        "workloads": workloads,
        "end_to_end": [dict(m, workloads=_tiny(m)) if "workloads" in m else m for m in real["end_to_end"]],
        "per_layer": [dict(m, workloads=_tiny(m)) for m in real["per_layer"]],
    }
    write_json(root / "BENCHMARK.json", spec)
    return root


def _tiny(metric: dict) -> list:
    """The tiny cells standing in for the real cells a metric lists."""
    real = set(metric.get("workloads", []))
    out = []
    if real & {"win_k20_sat"}:
        out.append("t_win_sat")
    if real & {"dyn_k22_sat"}:
        out.append("t_dyn_sat")
    if real & {"win_k20_paced"}:
        out.append("t_win_paced")
    return out
