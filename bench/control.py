"""The control of the correctness comparison: the plain reference computed in
bfloat16 (the precision below the configuration's float32) put in the
program's place, compared with the reference as a run's output would be.
Its numbers are the upper readings the cells' limits are set below.

    python bench/control.py --workload <cell> --seeds 7,8,9 --events <n> [--seconds <s>] [--precision bfloat16]

``--events`` is a run's event count at the cell's size (a window's worth),
spread evenly over ``--seconds`` (the benchmark's ``run_seconds``) in a
closed loop, or due at the mix's rate in an open one. Rotations come
every ``epoch_s`` as in a run (``loop.py``), and an open-loop cell reads
as its window would: the window's last sub-ring read, and the anytime
read after the close. ``--precision float64`` puts the reference against
itself (every number reads 0).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
for _p in (BENCH, BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import harness  # noqa: E402
import loadgen  # noqa: E402
import loop  # noqa: E402
from reference import compare, oracle  # noqa: E402


def plan_for(cell: harness.Cell, events: int, seconds: float) -> oracle.Plan:
    conf, mix = cell.config, cell.mix
    chunk, batch = int(mix["chunk"]), conf["batch"]
    n = events // chunk
    open_loop = mix["arrival"] == "open"
    rate = float(mix["rate_eps"]) if open_loop else n * chunk / seconds
    epoch_s = float(conf.get("epoch_s") or 0) if conf["container"] == "window" else 0.0
    # Chunk c's time in the window, as loop.drive takes it: its due time,
    # or, closed, when its push starts at an even pace.
    t_of = (lambda c: (c + 1) * chunk / rate) if open_loop else (lambda c: c * chunk / rate)
    rotations, staged, seen, passed = [], 0, [(0, 1)], 0
    for c in range(n):
        staged = (staged + chunk) % batch
        if epoch_s and loop.boundaries_passed(t_of(c), epoch_s, passed) > passed:
            rotations.append((c + 1) * chunk)
            staged = 0
            passed = loop.boundaries_passed(t_of(c), epoch_s, passed)
        seen.append(((c + 1) * chunk - staged, 1 + len(rotations)))
    reads = []
    if open_loop:
        # The window's last sub-ring read, with the chunks due by then.
        sub_s, span = float(mix["subring_read_s"]), n * chunk / rate
        t_sub = sub_s * int(span / sub_s) if span > sub_s else span / 2
        reads = [("subring", *seen[min(int(t_sub * rate) // chunk, n)]),
                 ("anytime", n * chunk, 1 + len(rotations))]
    return oracle.Plan(events=n * chunk, rotations=rotations, batch=batch,
                       warm_events=batch, reads=reads)


def readings(cell: harness.Cell, seed: int, events: int, precision: str, seconds: float) -> dict:
    """The compared numbers of the control against the reference."""
    stream = loadgen.Stream(cell.mix, cell.config["k"], seed)
    plan = plan_for(cell, events, seconds)
    sample = oracle.pick_sample(stream, cell.config, plan.events, cell.spec["sample"], seed)
    if cell.config["container"] == "window":
        w = int(cell.mix.get("subring_w", 2))
        ref = oracle.window(stream, cell.config, sample, plan, w_sub=w)
        ctl = oracle.window(stream, cell.config, sample, plan, precision, w_sub=w)
    else:
        ref = oracle.dyn(stream, cell.config, sample, plan)
        ctl = oracle.dyn(stream, cell.config, sample, plan, precision)
    return compare.numbers(ctl, ref)


def main(argv=None, root: pathlib.Path = harness.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--events", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--precision", default="bfloat16")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, root)
    seconds = args.seconds or float(harness.benchmark(root)["run_seconds"])
    for seed in (int(s) for s in args.seeds.split(",")):
        nums = readings(cell, seed, args.events, args.precision, seconds)
        ok, _ = compare.judge(nums, cell.spec["limits"])
        print(json.dumps({"workload": args.workload, "seed": seed, "precision": args.precision,
                          "events": args.events, "numbers": nums, "passes_limits": ok}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
