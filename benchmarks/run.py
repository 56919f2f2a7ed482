"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (harness contract) and writes
JSON result files under experiments/bench/. ``--full`` runs the paper-scale
sweeps (much slower); default is the quick profile used by bench_output.txt.
``--smoke`` is the CI tier-2 entry (scripts/test.sh --tier2): the quick
profile restricted to the fast suites, just enough to prove every exercised
benchmark path still runs end to end.

  python -m benchmarks.run [--full | --smoke] [--only accuracy,throughput,...]
"""

from __future__ import annotations

import argparse
import time

# Fast enough for CI while still covering the fused + sharded + Dyn +
# sliding-window paths (cumulative sweeps included so their JSON schema is
# exercised every run).
SMOKE_SUITES = (
    "sketch_array",
    "sketch_array_sharded",
    "dyn_array",
    "dyn_array_sharded",
    "estimation",
    "window_array",
    "window_array_sharded",
    "ingest",
    "virtual_dyn_array",
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sweeps")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: quick profile over the fast suite subset")
    ap.add_argument("--only", default="", help="comma list of benchmark names")
    args = ap.parse_args()
    if args.full and args.smoke:
        ap.error("--full and --smoke are mutually exclusive")

    from repro.launch import compile_cache

    compile_cache.enable()

    from . import (
        accuracy,
        batch_bias,
        dyn_array,
        estimation,
        ingest,
        kernels,
        netflow,
        register_size,
        sketch_array,
        throughput,
        virtual_dyn_array,
        window_array,
    )

    suite = {
        "accuracy": accuracy.run,  # Figs 2-4
        "register_size": register_size.run,  # Fig 5 / Thm 1
        "throughput": throughput.run,  # Figs 6-8
        "batch_bias": batch_bias.run,  # beyond-paper
        "netflow": netflow.run,  # App A.4 (CAIDA analogue)
        "kernels": kernels.run,  # kernel block sweep + core throughput
        "sketch_array": sketch_array.run,  # fused K-sketch vs naive loop
        "sketch_array_sharded": sketch_array.run_sharded,  # mesh-sharded K sweep
        "dyn_array": dyn_array.run,  # anytime reads vs Newton estimate_all
        "estimation": estimation.run,  # solver sweep: newton vs lut vs fused
        "dyn_array_sharded": dyn_array.run_sharded,  # sharded Dyn K sweep
        "window_array": window_array.run,  # sliding-window reads vs per-epoch Newton
        "window_array_sharded": window_array.run_sharded,  # sharded ring (K, E) sweep
        "ingest": ingest.run,  # sustained_mops headline: pipelined vs sync
        "virtual_dyn_array": virtual_dyn_array.run,  # register-sharing memory/accuracy headline
    }
    only = [s for s in args.only.split(",") if s]
    names = only or (list(SMOKE_SUITES) if args.smoke else list(suite))

    print("name,us_per_call,derived")
    t0 = time.time()
    for name in names:
        print(f"# --- {name} ---", flush=True)
        t = time.time()
        suite[name](quick=not args.full)
        print(f"# {name} done in {time.time()-t:.1f}s", flush=True)
    print(f"# total {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
