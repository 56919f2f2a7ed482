"""Serving driver: batched prefill + decode loop with weighted-DAU telemetry.

Each request batch carries (session_id, engagement_weight); the decode loop
updates the QSketch-Dyn DAU monitor every step, so "weighted distinct
sessions served" — the paper's motivating metric — is available at any time
for O(2^b) work without touching request logs.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
      --batch 4 --prompt-len 12 --gen 16
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--obs-jsonl", default="",
                    help="append one final registry snapshot (JSONL) here")
    ap.add_argument("--obs-prom", default="",
                    help="write a Prometheus textfile snapshot here at exit")
    ap.add_argument("--obs-trace", default="",
                    help="record prefill/decode spans and save a Perfetto-"
                         "loadable Chrome trace JSON here at exit")
    args = ap.parse_args(argv)

    from repro import configs
    from repro.configs import paper_qsketch
    from repro.launch.mesh import make_local_mesh
    from repro.models import common as mcommon, transformer
    from repro.obs import export as obs_export, trace as obs_trace
    from repro.sketchstream import monitor
    from repro.train import serve_step

    if args.obs_trace:
        obs_trace.configure(enabled=True)

    mesh = make_local_mesh()
    cfg = configs.smoke_config(args.arch) if args.smoke else configs.get_config(args.arch)
    sketch_cfg = paper_qsketch.telemetry_default()

    rng = np.random.default_rng(args.seed)
    params = mcommon.init_params(transformer.model_defs(cfg), jax.random.PRNGKey(args.seed))
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (args.batch, args.prompt_len), dtype=np.int32))
    extra = None
    if cfg.frontend == "patches":
        extra = jnp.asarray(rng.normal(size=(args.batch, cfg.frontend_len, cfg.d_model)), jnp.float32)
    elif cfg.n_enc_layers:
        extra = jnp.asarray(rng.normal(size=(args.batch, cfg.enc_seq, cfg.d_model)), jnp.float32)

    session_ids = jnp.asarray(rng.integers(0, 2**32, args.batch, dtype=np.uint32))
    session_w = jnp.asarray(rng.uniform(0.5, 2.0, args.batch), jnp.float32)

    prefill_fn = jax.jit(serve_step.make_prefill(cfg, mesh, max_len=args.max_len))
    decode_fn = jax.jit(
        serve_step.make_decode_step(cfg, mesh, sketch_cfg=sketch_cfg, temperature=args.temperature),
        donate_argnums=(1,),
    )

    sk_state = monitor.init(sketch_cfg)
    t0 = time.time()
    with obs_trace.span("serve/prefill", batch=args.batch):
        if extra is not None:
            last_logits, cache = prefill_fn(params, prompts, extra)
        else:
            last_logits, cache = prefill_fn(params, prompts)
        last_logits = jax.block_until_ready(last_logits)
    tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)[:, None]
    generated = [tok]
    cur = args.prompt_len + (cfg.frontend_len if cfg.frontend == "patches" else 0)
    with obs_trace.span("serve/decode", steps=args.gen - 1):
        for i in range(args.gen - 1):
            tok, cache, sk_state = decode_fn(
                params, cache, jnp.int32(cur + i), tok, sk_state, session_ids, session_w
            )
            generated.append(tok)
    toks = jnp.concatenate(generated, axis=1)
    dt = time.time() - t0
    with obs_trace.span("serve/estimate"):
        dau = float(monitor.estimate(sketch_cfg, sk_state))
    true_dau = float(session_w.sum())
    print(f"[serve] {args.batch} sessions x {args.gen} tokens in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    print(f"[serve] weighted-DAU sketch estimate: {dau:.2f} (true {true_dau:.2f})")
    print(f"[serve] sample continuation ids: {np.asarray(toks[0])[:12].tolist()}")
    if args.obs_jsonl:
        obs_export.append_snapshot(
            args.obs_jsonl, dau_estimate=dau, tokens=args.batch * args.gen
        )
    if args.obs_prom:
        obs_export.write_prometheus(args.obs_prom)
    if args.obs_trace:
        obs_trace.save(args.obs_trace)
        print(f"[serve] obs trace saved to {args.obs_trace}", flush=True)
    return toks


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
