"""Dry-run machinery + roofline analyzer tests (8-device subprocess mesh)."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


def test_hlo_stats_loop_aware():
    """dot FLOPs and collective bytes must scale with scan trip count."""
    script = textwrap.dedent("""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.roofline import hlo_stats
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ('data', 'model'))
        def make(n):
            def f(x, w):
                def body(c, wi):
                    return jnp.einsum('bm,mn->bn', c, wi).astype(c.dtype), None
                out, _ = jax.lax.scan(body, x, w)
                return out.sum()
            xs = jax.ShapeDtypeStruct((64, 256), jnp.float32)
            ws = jax.ShapeDtypeStruct((n, 256, 256), jnp.float32)
            co = jax.jit(f, in_shardings=(NamedSharding(mesh, P('data', None)),
                                          NamedSharding(mesh, P(None, None, 'model')))).lower(xs, ws).compile()
            return hlo_stats.analyze(co.as_text())
        s7, s14 = make(7), make(14)
        assert abs(s7['dot_flops'] - 2*16*256*128*7) < 1e-6, s7['dot_flops']
        assert abs(s14['dot_flops'] - 2*s7['dot_flops']) < 1e-6
        ag7 = s7['collective_by_op'].get('all-gather', 0)
        ag14 = s14['collective_by_op'].get('all-gather', 0)
        assert abs(ag14 - 2*ag7) < 1e-6 and ag7 > 0
        print('HLO-STATS-OK')
    """)
    r = subprocess.run([sys.executable, "-c", script], env=ENV, capture_output=True, text=True, timeout=600)
    assert "HLO-STATS-OK" in r.stdout, r.stderr[-2000:]


def test_roofline_terms_and_bottleneck():
    from repro.roofline import analysis as ra

    terms, b = ra.roofline_terms(197e12, 819e9, 0.0, 256)
    assert abs(terms["compute_s"] - 1.0) < 1e-9
    assert abs(terms["memory_s"] - 1.0) < 1e-9
    assert b in ("compute", "memory")
    terms, b = ra.roofline_terms(1e12, 1e9, 500e9, 256)
    assert b == "collective"


def test_collective_regex_variants():
    from repro.roofline import analysis as ra

    hlo = """
      %ar = f32[1024]{0} all-reduce(%x), replica_groups={}
      %ag = (bf16[2,128]{1,0}, bf16[2,128]{1,0}) all-gather-start(%y, %z), dimensions={0}
      %d = f32[8] all-reduce-done(%ar2)
      %cp = u8[4096]{0} collective-permute(%w), source_target_pairs={{0,1}}
    """
    got = ra.parse_collective_bytes(hlo)
    assert got["all-reduce"] == 4096
    assert got["all-gather"] == 2 * 2 * 128 * 2
    assert got["collective-permute"] == 4096


def test_dryrun_cell_smoke_mesh():
    """run_cell end-to-end on an 8-device mesh with a reduced config."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, json, jax
        from repro import configs as C
        from repro.launch import dryrun_lib as dl
        smoke = {n: C.smoke_config(n) for n in C.list_archs()}
        C.get_config = lambda n: smoke[n]
        C.SHAPES.update({
            'train_4k': dataclasses.replace(C.SHAPES['train_4k'], seq=64, batch=8),
            'decode_32k': dataclasses.replace(C.SHAPES['decode_32k'], seq=64, batch=8),
        })
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ('data', 'model'))
        for arch, shape in [('qwen3-8b', 'train_4k'), ('kimi-k2-1t-a32b', 'train_4k'),
                            ('whisper-large-v3', 'decode_32k')]:
            rec = dl.run_cell(arch, shape, mesh)
            assert rec['status'] == 'ok', (arch, shape, rec.get('error'))
            assert rec['per_device']['flops'] > 0
            assert rec['roofline']['compute_s'] >= 0
            assert rec['bottleneck'] in ('compute', 'memory', 'collective')
            # The sketch monitor's Newton solve is a legitimately dynamic
            # while loop (convergence-bounded, tiny); everything structural
            # (layer scans, microbatches) must carry known trip counts.
            assert rec['per_device']['unknown_trip_whiles'] <= 2
        print('DRYRUN-CELL-OK')
    """)
    r = subprocess.run([sys.executable, "-c", script], env=ENV, capture_output=True, text=True, timeout=1200)
    assert "DRYRUN-CELL-OK" in r.stdout, (r.stdout[-500:], r.stderr[-2000:])


def test_production_records_exist_and_pass():
    """The committed dry-run artifacts: every non-skip cell is status ok,
    single-pod AND multi-pod, and the cell grid is complete (40 cells)."""
    import glob

    for tag, chips in [("_singlepod", 256), ("_multipod", 512)]:
        paths = glob.glob(os.path.join(REPO, "experiments/dryrun", f"*{tag}.json"))
        if not paths:
            pytest.skip("dry-run artifacts not generated yet")
        recs = [json.load(open(p)) for p in paths]
        assert len(recs) == 40, (tag, len(recs))
        ok = [r for r in recs if r["status"] == "ok"]
        skip = [r for r in recs if r["status"] == "skip"]
        assert len(ok) == 34 and len(skip) == 6, (tag, len(ok), len(skip))
        for r in ok:
            assert r["chips"] == chips
            assert r["per_device"]["flops"] > 0, (r["arch"], r["shape"])
