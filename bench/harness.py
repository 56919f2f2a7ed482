"""Shared plumbing of the benchmark: where things live, how a cell's files are
found by name, the compile cache, the device checks and the result line.

Everything that belongs to one configuration, traffic mix, driver or metric
sits in a file of its own under ``bench/`` and is found here by the name
that ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``  the deployment (sizes, hash seeds, guarantees);
* ``traffic/<mix>.json``     parameters for the one generator (``loadgen``);
* ``cells/<cell>.json``      which config, mix and driver, and the limits of
                             the correctness comparison;
* ``drivers/<driver>.py``    the loop that drives one public entry;
* ``metrics/<metric>.py``    one reader per metric, ``read(run) -> float|None``.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = ROOT / ".jax_cache"


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    """The parsed ``BENCHMARK.json`` at the root of the checkout."""
    return load_json(root / "BENCHMARK.json")


def load_module(path: pathlib.Path, prefix: str):
    """Import one plugin file by path (names may hold dots, e.g. a metric
    called ``idle_frac.ingest``)."""
    name = f"_bench_{prefix}_{path.stem.replace('.', '_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def listing(kind: str, suffix: str, bench: pathlib.Path = BENCH) -> list[str]:
    """Names of the plugin files of one kind, by directory listing."""
    return sorted(p.name[: -len(suffix)] for p in (bench / kind).glob(f"*{suffix}"))


class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved by name."""

    def __init__(self, name: str, root: pathlib.Path = ROOT):
        self.root = root
        bench = root / "bench"
        spec = benchmark(root)
        by_name = {w["name"]: w for w in spec["workloads"]}
        if name not in by_name:
            raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                             f"(have {sorted(by_name)})")
        self.name = name
        self.workload = by_name[name]
        self.chips = int(self.workload["chips"])
        confs = {c["name"]: c for c in spec["configs"]}
        self.config_entry = confs[self.workload["config"]]
        self.config = load_json(root / self.config_entry["file"])
        self.mix = load_json(bench / "traffic" / f"{self.workload['traffic']}.json")
        self.spec = load_json(bench / "cells" / f"{name}.json")
        self.driver = load_module(bench / "drivers" / f"{self.spec['driver']}.py", "driver")
        self.end_to_end = [m for m in spec["end_to_end"] if _reports(m, name)]
        self.per_layer = [m for m in spec["per_layer"] if _reports(m, name)]
        self._bench = bench

    def reader(self, metric: str):
        """The ``read(run)`` function of one metric."""
        return load_module(self._bench / "metrics" / f"{metric}.py", "metric").read


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (``.jax_cache/``), or where ``JAX_COMPILATION_CACHE_DIR`` says.
    Every program is cached, however fast it compiled, so a warm run
    compiles nothing."""
    import jax

    path = os.environ.get(CACHE_ENV) or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_devices(chips: int):
    """The first ``chips`` TPU devices, or SystemExit (no result is printed)
    when JAX finds no TPU or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, but JAX's first device is "
                         f"{devs[0].platform!r} ({devs[0].device_kind}); refusing to measure")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def device_info(devs) -> dict:
    """Platform, kind and count as JAX reports them, and the peak bytes in
    use on the fullest chip."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": max(peaks) if peaks else None,
    }


class Spans:
    """The harness's own spans: host-clock totals per name, and,
    while the profiler runs, a ``jax.profiler.TraceAnnotation`` so each span
    sits on the device trace's clock."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.total: dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        with ann:
            yield
        self.total[name] = self.total.get(name, 0.0) + time.perf_counter() - t0


def result_line(correct: bool, attempted: int, failed: int, metrics: dict, device: dict,
                checks: dict, breakdown: dict | None = None) -> str:
    """The run's last stdout line; ``checks`` (each compared number with its
    limit) comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)
