"""Find the knee of an open-loop cell: run its full mix (reads included) at
several fixed rates in one process and print, per rate, how late the
generator ran at the start and at the end of the window. Run by hand on
the chip; the rate chosen goes into the traffic file as a number.

    python bench/sweep.py --workload win_k20_paced --rates 50000,70000,90000 --seconds 20

A rate is sustained while the generator's lateness stays flat: the median
lateness of the window's last third is no more than that of its first
third plus one chunk's arrival time.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
for _p in (BENCH, BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

import harness  # noqa: E402
import loadgen  # noqa: E402
import loop  # noqa: E402
from run import Context  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=101)
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    harness.require_devices(cell.chips)
    harness.enable_compile_cache()
    stream = loadgen.Stream(cell.mix, cell.config["k"], args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.mix["rate_eps"] = rate
        ctx = Context(cell, stream, args.seconds, False, time.perf_counter())
        run = cell.driver.run(ctx)
        w = run.window
        late = np.array(w.late_s)
        third = max(len(late) // 3, 1)
        first, last = float(np.median(late[:third])), float(np.median(late[-third:]))
        fresh = loop.freshness_s(w, rate)
        print(json.dumps({
            "rate_eps": rate, "events": w.events, "achieved_eps": w.events / (w.t1 - w.t0),
            "late_first_third_ms": first * 1e3, "late_last_third_ms": last * 1e3,
            "late_p95_ms": float(np.percentile(late, 95)) * 1e3,
            "flat": last <= first + stream.chunk_len / rate,
            "fresh_p95_ms": float(np.percentile(fresh, 95)) * 1e3,
            "subring_reads": len(w.subring_s),
            "window_read_ms": float(np.mean(w.subring_s)) * 1e3 if w.subring_s else None,
        }), flush=True)
        run.close()
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
