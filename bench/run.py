"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic mix,
driver and metric readers are files under ``bench/`` found by name
(``harness.py``). The run:

1. refuses to measure unless JAX's devices are TPUs, as many as the cell
   asks for (exit code non-zero, no result);
2. builds the traffic pool from ``--seed`` and the system, and warms every
   shape the window uses (set-up, ``setup_s``);
3. drives the system for ``--seconds`` (``loop.py``); with ``--trace 1``
   under the profiler and the program's qobs spans;
4. reads the device's peak memory, reads back the sampled tenant rows,
   frees the program's state and compares the rows with the plain
   reference (``reference/``), each number against the cell's limit;
5. prints the compared numbers on stderr, then one JSON line on stdout:
   ``correct``, ``attempted``, ``failed``, ``metrics`` (end-to-end ones, or
   with ``--trace 1`` the per-layer ones), ``device`` and, traced,
   ``breakdown``; ``checks`` (each number with its limit) comes last.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
for _p in (BENCH, BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

import harness  # noqa: E402

TRACE_DIR = BENCH.parent / ".bench_trace"


class Context:
    """What a driver sees: the cell, the stream, the window length, the
    harness spans, and the hooks around the window."""

    def __init__(self, cell, stream, seconds: float, trace: bool, t_proc: float,
                 marks: list | None = None):
        self.cell, self.stream, self.seconds, self.trace = cell, stream, seconds, trace
        self.t_proc = t_proc
        self.marks = list(marks or [])  # (step of set-up, perf_counter at its end)
        self.spans = harness.Spans(annotate=trace)
        self.setup_s = None
        self.qobs: dict | None = None

    def mark(self, step: str) -> None:
        """End of one step of set-up (for the set-up split on stderr)."""
        self.marks.append((step, time.perf_counter()))

    def setup_split(self) -> dict:
        """Seconds of each set-up step, from process start to the window."""
        out, t = {}, self.t_proc
        for step, t1 in self.marks:
            out[step] = t1 - t
            t = t1
        return out

    def window_starts(self) -> None:
        self.mark("warm_up")
        self.setup_s = time.perf_counter() - self.t_proc
        if self.trace:
            import jax
            from repro.obs import trace as obs_trace

            obs_trace.configure(enabled=True)
            obs_trace.clear()
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)

    def window_ends(self) -> None:
        if self.trace:
            import jax
            from repro.obs import trace as obs_trace

            jax.profiler.stop_trace()
            self.qobs = obs_trace.stage_totals()
            obs_trace.configure(enabled=False)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler's .xplane.pb here (by hand, for fixtures)")
    return ap.parse_args(argv)


def main(argv=None, *, root: pathlib.Path = harness.ROOT, require_chip: bool = True) -> int:
    """One run. ``require_chip=False`` is for the tests: it runs the rest of
    the harness on the CPU."""
    args = parse(argv)
    import jax

    marks = [("jax_import", time.perf_counter())]
    jax.devices()
    marks.append(("backend_init", time.perf_counter()))
    cell = harness.Cell(args.workload, root)
    marks.append(("program_import", time.perf_counter()))

    if require_chip:
        devs = harness.require_devices(cell.chips)
        peaks = harness.load_json(harness.BENCH / "peaks.json")
        if devs[0].device_kind not in peaks:
            raise SystemExit(f"bench: no peaks for device kind {devs[0].device_kind!r}")
        peak = peaks[devs[0].device_kind]
        harness.enable_compile_cache()
    else:
        devs, peak = jax.devices()[: cell.chips], None

    import loadgen
    import loop
    from reference import compare, oracle

    marks.append(("harness", time.perf_counter()))
    stream = loadgen.Stream(cell.mix, cell.config["k"], args.seed)
    marks.append(("traffic_pool", time.perf_counter()))
    ctx = Context(cell, stream, args.seconds, bool(args.trace), T_PROC, marks)
    run = cell.driver.run(ctx)
    w = run.window
    device = harness.device_info(devs)

    trace = None
    if args.trace:
        import tracekit

        path = tracekit.find_xplane(str(TRACE_DIR))
        if args.keep_trace:
            shutil.copy(path, args.keep_trace)
        trace = tracekit.Trace.from_file(path)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = trace.mean_busy_s()
        device["window_s"] = trace.window_s

    plan = oracle.Plan(events=w.events, rotations=list(w.rotations), batch=cell.config["batch"],
                       warm_events=cell.config["batch"],
                       reads=_last_reads(w.reads))
    sample = oracle.pick_sample(stream, cell.config, w.events, cell.spec["sample"], args.seed)
    prog = run.rows(sample)
    run.close()
    del run
    gc.collect()
    if cell.config["container"] == "window":
        ref = oracle.window(stream, cell.config, sample, plan, w_sub=int(cell.mix.get("subring_w", 2)))
    else:
        ref = oracle.dyn(stream, cell.config, sample, plan)
    correct, checks = compare.judge(compare.numbers(prog, ref), cell.spec["limits"])

    rec = {
        "setup_s": ctx.setup_s, "window_s": w.t1 - w.t0, "events": w.events,
        "batches": _window_batches(ctx, w), "rotations": len(w.rotations),
        "subring_s": list(w.subring_s), "late_s": np.array(w.late_s),
        "fresh_s": loop.freshness_s(w, float(cell.mix["rate_eps"])) if cell.mix["arrival"] == "open" else None,
        "spans": dict(ctx.spans.total), "qobs": ctx.qobs, "trace": trace, "peak": peak,
        "config": cell.config, "mix": cell.mix, "stream": stream, "plan": plan,
        "modules": harness.load_json(harness.BENCH / "modules.json"),
    }
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = cell.reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    breakdown = None
    if trace is not None:
        breakdown = {"device_ops": trace.top_ops(10), "idle_gaps": trace.idle_gaps(10)}
    harness.log("setup split " + " ".join(f"{k}={v!r}" for k, v in ctx.setup_split().items()))
    harness.log(f"window events {w.events} in {w.t1 - w.t0!r} s, rotations after events {list(w.rotations)}")
    for name, c in checks.items():
        harness.log(f"check {name} = {c['value']!r} limit {c['limit']!r}")
    print(harness.result_line(correct, w.events, 0, metrics, device, checks, breakdown), flush=True)
    return 0


def _last_reads(reads) -> list:
    """(kind, events included, epoch) of the last read of each kind."""
    last = {}
    for kind, n, epoch, _ in reads:
        last[kind] = (kind, n, epoch)
    return list(last.values())


def _window_batches(ctx, w) -> int:
    """Micro-batches dispatched in the window (whole and flushed)."""
    b = ctx.cell.config["batch"]
    bounds = [0] + list(w.rotations) + [w.events]
    return sum(-(-(y - x) // b) for x, y in zip(bounds[:-1], bounds[1:]))


if __name__ == "__main__":
    sys.exit(main())
