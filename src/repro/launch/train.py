"""End-to-end training driver: data -> train_step -> telemetry -> checkpoints.

Fault-tolerance behaviours (exercised by tests/test_train_loop.py):
  * atomic async checkpoints every --ckpt-every steps (+ final),
  * auto-resume from the newest complete checkpoint in --ckpt-dir,
  * SIGTERM/SIGINT trigger a final synchronous save before exit (preemption
    handling — the TPU-pod eviction path),
  * a step watchdog logs straggler steps (> --straggler-factor x EMA),
  * the data pipeline is (seed, step, shard)-keyed, so restarts and elastic
    host-count changes replay the exact global stream.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch small-lm-16m --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke --steps 10
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _extra_presets():
    """Small real-training presets (the assigned archs are dry-run scale)."""
    from repro.models import LayerSpec, ModelConfig

    def small(name, layers, d, heads, ff, vocab=32000):
        return ModelConfig(
            name=name, n_layers=layers, d_model=d, n_heads=heads,
            n_kv_heads=max(heads // 4, 1), d_ff=ff, vocab=vocab,
            pattern=(LayerSpec(),), act_dtype="float32", tie_embeddings=True,
        )

    return {
        "small-lm-16m": lambda: small("small-lm-16m", 4, 256, 4, 1024, vocab=8192),
        "small-lm-100m": lambda: small("small-lm-100m", 12, 768, 12, 3072),
    }


def build_config(arch: str, smoke: bool):
    from repro import configs

    presets = _extra_presets()
    if arch in presets:
        return presets[arch]()
    return configs.smoke_config(arch) if smoke else configs.get_config(arch)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="small-lm-16m")
    ap.add_argument("--smoke", action="store_true", help="use the reduced config of an assigned arch")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="checkpoints/run")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--quantized-opt", action="store_true")
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--no-sketch", action="store_true")
    ap.add_argument("--doc-window-capacity", type=int, default=0,
                    help="enable sliding-window per-document coverage telemetry "
                         "with this many tenant slots (0 = off)")
    ap.add_argument("--doc-window-epochs", type=int, default=4,
                    help="ring size E of the per-document window monitor")
    ap.add_argument("--rotate-every", type=int, default=20,
                    help="train steps per window epoch (rotation cadence)")
    ap.add_argument("--doc-window-shards", type=int, default=0,
                    help="shard the doc-window monitor's per-tenant state "
                         "over this many devices of a dedicated 'sketch' "
                         "mesh (0 = single-host WindowMonitor)")
    ap.add_argument("--ingest", action="store_true",
                    help="stream the doc-window telemetry through the async "
                         "micro-batching ingest pipeline (sketchstream/"
                         "ingest.py: donated updates, bounded retire queue) "
                         "instead of updating inside the jitted step; "
                         "requires --doc-window-capacity")
    ap.add_argument("--ingest-batch", type=int, default=32768,
                    help="ingest micro-batch size (fixed staging shape)")
    ap.add_argument("--ingest-queue-depth", type=int, default=4,
                    help="max in-flight ingest batches before backpressure")
    ap.add_argument("--ingest-policy", default="block", choices=("block", "drop"),
                    help="backpressure policy at a full ingest queue")
    ap.add_argument("--n-docs", type=int, default=512,
                    help="distinct document ids the token stream draws from "
                         "when the doc window is enabled")
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    ap.add_argument("--metrics-file", default="")
    ap.add_argument("--obs-jsonl", default="",
                    help="append a registry snapshot (delta JSONL) every "
                         "--log-every steps to this path")
    ap.add_argument("--obs-prom", default="",
                    help="write a Prometheus textfile snapshot here every "
                         "--log-every steps (overwritten in place)")
    ap.add_argument("--obs-trace", default="",
                    help="record stage spans and save a Perfetto-loadable "
                         "Chrome trace JSON here at exit")
    ap.add_argument("--abort-after", type=int, default=0,
                    help="simulate preemption: stop after N steps this invocation (tests)")
    args = ap.parse_args(argv)

    from repro.configs import paper_qsketch
    from repro.data.tokens import TokenStream
    from repro.launch.mesh import make_local_mesh, make_sketch_mesh
    from repro.models import common as mcommon, sharding as msharding, transformer
    from repro.obs import export as obs_export, trace as obs_trace
    from repro.sketchstream import monitor
    from repro.train import checkpoint, optimizer, train_step as ts

    # Observability sinks (DESIGN.md §10): spans record only when a trace
    # path is requested; the metrics registry is always live (QOBS_DISABLED
    # turns it off) and the JSONL writer logs per-interval deltas.
    if args.obs_trace:
        obs_trace.configure(enabled=True)
    obs_jsonl = (
        obs_export.JsonlWriter(args.obs_jsonl, delta=True)
        if args.obs_jsonl else None
    )

    mesh = make_local_mesh()
    cfg = build_config(args.arch, args.smoke)
    sketch_cfg = None if args.no_sketch else paper_qsketch.telemetry_default()
    # Sliding-window per-document telemetry (DESIGN.md §8.5): the train loop
    # owns the epoch clock — every --rotate-every steps the window rotates,
    # so "distinct tokens per document" is scoped to the trailing E epochs
    # and cold document fingerprints age out of the directory.
    # The monitor only needs a sketch geometry of its own — --no-sketch
    # (scalar token telemetry off) and the doc window compose independently.
    # With --doc-window-shards the same monitor surface runs row-sharded
    # over a dedicated "sketch" mesh (DESIGN.md §8.6): bit-identical
    # estimates, per-tenant state divided across the shard devices.
    # --ingest decouples that telemetry from the step: the jitted train step
    # carries NO tenant state (tenant_monitor=None below), and the per-token
    # (doc, token) elements are pushed host-side into a TenantWindowIngest —
    # micro-batched, donated, asynchronous (DESIGN.md §8.8). Rotation +
    # directory aging run behind the pipeline's retire barrier on the same
    # --rotate-every clock. The ingest window state is telemetry, not model
    # state: it is NOT checkpointed, and a resumed run restarts its window.
    tenant_mon = None
    doc_ingest = None
    if args.doc_window_capacity and args.ingest:
        from repro.core.key_directory import DirectoryConfig
        from repro.sketchstream import ingest as ingest_lib

        tcfg = paper_qsketch.telemetry_default()
        doc_ingest = ingest_lib.TenantWindowIngest(
            tcfg,
            DirectoryConfig(capacity=args.doc_window_capacity, seed=tcfg.seed),
            args.doc_window_epochs,
            ingest_lib.IngestConfig(
                batch_size=args.ingest_batch,
                queue_depth=args.ingest_queue_depth,
                policy=args.ingest_policy,
            ),
            mesh=(make_sketch_mesh(args.doc_window_shards)
                  if args.doc_window_shards else None),
            evict_after=args.doc_window_epochs,
        )
    elif args.doc_window_capacity:
        if args.doc_window_shards:
            tenant_mon = monitor.ShardedWindowMonitor.for_mesh(
                paper_qsketch.telemetry_default(), args.doc_window_capacity,
                args.doc_window_epochs, make_sketch_mesh(args.doc_window_shards),
                evict_after=args.doc_window_epochs,
            )
        else:
            tenant_mon = monitor.WindowMonitor.for_capacity(
                paper_qsketch.telemetry_default(), args.doc_window_capacity,
                args.doc_window_epochs, evict_after=args.doc_window_epochs,
            )
    ocfg = optimizer.OptConfig(
        lr=args.lr, total_steps=args.steps, warmup_steps=max(args.steps // 20, 1),
        quantized=args.quantized_opt,
    )

    defs = transformer.model_defs(cfg)
    print(f"[train] arch={cfg.name} params={transformer.count(cfg)/1e6:.1f}M "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}", flush=True)

    params = mcommon.init_params(defs, jax.random.PRNGKey(args.seed))
    shardings = msharding.sharding_tree(defs, mesh)
    params = jax.tree.map(lambda x, s: jax.device_put(x, s), params, shardings)
    opt_state, comp_state, sk_state = ts.init_states(
        cfg, ocfg, params, sketch_cfg=sketch_cfg, tenant_monitor=tenant_mon,
        compress=args.compress,
    )

    start_step = 0
    state_tree = {"params": params, "opt": opt_state, "comp": comp_state, "sk": sk_state}
    if not args.no_resume:
        latest = checkpoint.latest_step(args.ckpt_dir)
        if latest is not None:
            state_tree, manifest = checkpoint.restore(args.ckpt_dir, latest, state_tree)
            state_tree = {
                "params": jax.tree.map(lambda x, s: jax.device_put(x, s), state_tree["params"], shardings),
                "opt": jax.tree.map(jnp.asarray, state_tree["opt"]),
                "comp": jax.tree.map(jnp.asarray, state_tree["comp"]),
                "sk": jax.tree.map(jnp.asarray, state_tree["sk"]),
            }
            start_step = manifest["step"]
            print(f"[train] resumed from step {start_step}", flush=True)

    params, opt_state, comp_state, sk_state = (
        state_tree["params"], state_tree["opt"], state_tree["comp"], state_tree["sk"]
    )

    step_fn = jax.jit(
        ts.make_train_step(
            cfg, ocfg, mesh, sketch_cfg=sketch_cfg, tenant_monitor=tenant_mon,
            compress=args.compress, microbatches=args.microbatches,
        ),
        donate_argnums=(0, 1, 2, 3),
    )

    stream = TokenStream(
        cfg.vocab, args.batch, args.seq, seed=args.seed,
        n_docs=args.n_docs if (tenant_mon is not None or doc_ingest is not None) else 0,
    )
    ckpt = checkpoint.AsyncCheckpointer(args.ckpt_dir)
    metrics_f = open(args.metrics_file, "a") if args.metrics_file else None

    stop = {"flag": False}

    def _sig(_s, _f):
        stop["flag"] = True

    old_handlers = {}
    for s in (signal.SIGTERM, signal.SIGINT):
        try:
            old_handlers[s] = signal.signal(s, _sig)
        except ValueError:
            pass  # non-main thread (tests)

    ema = None
    step = start_step
    try:
        while step < args.steps and not stop["flag"]:
            batch = stream.batch_at(step)
            t0 = time.time()
            with obs_trace.span("train/step", step=step):
                params, opt_state, comp_state, sk_state, metrics = step_fn(
                    params, opt_state, comp_state, sk_state, batch
                )
                metrics = jax.tree.map(float, jax.device_get(metrics))
            dt = time.time() - t0
            ema = dt if ema is None else 0.9 * ema + 0.1 * dt
            if dt > args.straggler_factor * ema and step > start_step + 3:
                print(f"[watchdog] straggler step {step}: {dt:.2f}s vs ema {ema:.2f}s", flush=True)
            if doc_ingest is not None and "doc_ids" in batch:
                # Host-side ingest of the step's (doc, token) elements: one
                # tenant key per token (lo + hi uint32 words), pushed while
                # the NEXT step's device work proceeds — the async overlap
                # the in-step monitor can't have.
                shape = batch["tokens"].shape
                doc_ingest.push(
                    (np.broadcast_to(batch["doc_ids"][:, None], shape).ravel(),
                     np.broadcast_to(batch["doc_ids_hi"][:, None], shape).ravel()),
                    batch["tokens"].astype(np.uint32).ravel(),
                    mask=(batch["tokens_mask"].ravel()
                          if "tokens_mask" in batch else None),
                )
            step += 1
            if doc_ingest is not None and step % args.rotate_every == 0:
                # Epoch tick behind the retire barrier: every earlier element
                # lands in the pre-rotation epoch, then the ring rotates and
                # cold fingerprints age — the synchronous ordering.
                doc_ingest.rotate()
            if tenant_mon is not None and step % args.rotate_every == 0:
                # Epoch tick: rotate the document window (evicting the oldest
                # epoch + aging cold fingerprints) OUTSIDE the jit'd step.
                sk_state = monitor.TelemetryState(
                    scalar=sk_state.scalar,
                    tenants=tenant_mon.rotate(sk_state.tenants),
                )
            if step % args.log_every == 0 or step == args.steps:
                line = {"step": step, "time_s": round(dt, 4), **{k: round(v, 5) for k, v in metrics.items()}}
                if doc_ingest is not None:
                    line.update({
                        k: round(v, 5) if isinstance(v, float) else v
                        for k, v in doc_ingest.metrics().items()
                    })
                print(f"[train] {json.dumps(line)}", flush=True)
                if metrics_f:
                    metrics_f.write(json.dumps(line) + "\n")
                    metrics_f.flush()
                if obs_jsonl is not None:
                    obs_jsonl.write(step=step)
                if args.obs_prom:
                    obs_export.write_prometheus(args.obs_prom)
            if step % args.ckpt_every == 0:
                ckpt.save(step, {"params": params, "opt": opt_state, "comp": comp_state, "sk": sk_state})
            if args.abort_after and step - start_step >= args.abort_after:
                print(f"[train] simulated preemption at step {step}", flush=True)
                break
    finally:
        # Preemption/exit path: synchronous final save.
        checkpoint.save(args.ckpt_dir, step, jax.device_get(
            {"params": params, "opt": opt_state, "comp": comp_state, "sk": sk_state}
        ))
        ckpt.close()
        if metrics_f:
            metrics_f.close()
        if args.obs_prom:
            obs_export.write_prometheus(args.obs_prom)
        if args.obs_trace:
            obs_trace.save(args.obs_trace)
            print(f"[train] obs trace saved to {args.obs_trace} "
                  "(load at https://ui.perfetto.dev)", flush=True)
        for s, h in old_handlers.items():
            signal.signal(s, h)
    print(f"[train] done at step {step}", flush=True)
    return step


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
