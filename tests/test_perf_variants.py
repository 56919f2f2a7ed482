"""Equivalence tests for the §Perf hillclimb knobs: every optimized path must
match its baseline numerically (the 'debug forward, keep the speedup' gate)."""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


def test_moe_a2a_matches_scatter_8dev():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.models import ModelConfig, LayerSpec, MoEConfig, moe, common
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ('data', 'model'))
        cfg = ModelConfig(name='t', n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                          d_ff=128, vocab=128, pattern=(LayerSpec(ffn='moe'),),
                          moe=MoEConfig(num_experts=8, top_k=2, d_ff=32, capacity_factor=8.0),
                          act_dtype='float32')
        params = jax.tree.map(lambda x: x.astype(jnp.float32),
                              common.init_params(moe.defs(cfg), jax.random.PRNGKey(0)))
        x = jax.random.normal(jax.random.PRNGKey(1), (128, 64), jnp.float32)
        y_sc, _ = jax.jit(lambda p, xx: moe.apply_scatter(p, xx, cfg, mesh))(params, x)
        cfg2 = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, impl='shard_map_a2a'))
        y_a2a, _ = jax.jit(lambda p, xx: moe.apply(p, xx, cfg2, mesh))(params, x)
        assert float(jnp.max(jnp.abs(y_sc - y_a2a))) == 0.0
        # And gradients flow identically through the router.
        def loss(p, impl_cfg):
            y, _ = moe.apply(p, x, impl_cfg, mesh)
            return jnp.sum(y ** 2)
        g1 = jax.grad(loss)(params, cfg)
        g2 = jax.grad(loss)(params, cfg2)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)
        print('MOE-A2A-OK')
    """)
    r = subprocess.run([sys.executable, "-c", script], env=ENV, capture_output=True, text=True, timeout=900)
    assert "MOE-A2A-OK" in r.stdout, (r.stdout[-400:], r.stderr[-2500:])


def test_sharded_xent_matches_gather():
    from repro.models import common

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(2, 8, 64)).astype(np.float32))
    targets = jnp.asarray(rng.integers(0, 64, (2, 8)), jnp.int32)
    a = common.softmax_xent(logits, targets)
    b = common.softmax_xent_sharded(logits, targets, mesh=None)
    assert abs(float(a) - float(b)) < 1e-6
    mask = jnp.asarray(rng.integers(0, 2, (2, 8)) > 0)
    a = common.softmax_xent(logits, targets, mask)
    b = common.softmax_xent_sharded(logits, targets, None, mask)
    assert abs(float(a) - float(b)) < 1e-6


@pytest.mark.parametrize("chunk,intra", [(8, "float32"), (4, "float32"), (8, "bfloat16")])
def test_ssd_chunk_and_dtype_variants(chunk, intra):
    """Chunk size must not change results (exact algebra); bf16 intra stays
    within bf16 tolerance of the f32 reference."""
    from repro.models import LayerSpec, ModelConfig, SSMConfig, common, ssm

    def build(chunk_, intra_):
        return ModelConfig(
            name="s", n_layers=1, d_model=32, n_heads=4, n_kv_heads=4, d_ff=0,
            vocab=64, pattern=(LayerSpec(mixer="mamba", ffn="none"),),
            ssm=SSMConfig(d_state=8, head_dim=8, chunk=chunk_, intra_dtype=intra_),
            act_dtype="float32",
        )

    ref_cfg = build(16, "float32")  # single chunk (seq=16)
    cfg = build(chunk, intra)
    params = jax.tree.map(
        lambda x: x.astype(jnp.float32),
        common.init_params(ssm.defs(ref_cfg), jax.random.PRNGKey(3)),
    )
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 32), jnp.float32) * 0.5
    y_ref = ssm.apply(params, x, ref_cfg)
    y = ssm.apply(params, x, cfg)
    tol = 1e-5 if intra == "float32" else 3e-2
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=tol, atol=tol)


def test_remat_policies_same_loss():
    from repro import configs
    from repro.models import common, transformer

    cfg = configs.smoke_config("qwen3-8b")
    params = common.init_params(transformer.model_defs(cfg), jax.random.PRNGKey(5))
    batch = {
        "tokens": jnp.zeros((2, 16), jnp.int32),
        "targets": jnp.ones((2, 16), jnp.int32),
    }
    losses = []
    for remat in [True, "dots", False]:
        l, _ = transformer.loss_fn(params, batch, cfg, remat=remat)
        losses.append(float(l))
    assert max(losses) - min(losses) < 1e-5, losses


def test_microbatch_grads_match_full_batch():
    from repro import configs
    from repro.models import common, transformer
    from repro.train import optimizer, train_step as ts

    cfg = configs.smoke_config("h2o-danube-1.8b")
    params = common.init_params(transformer.model_defs(cfg), jax.random.PRNGKey(6))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    rng = np.random.default_rng(1)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32),
    }
    ocfg = optimizer.OptConfig(lr=0.0, weight_decay=0.0, warmup_steps=0)

    outs = []
    for mb in (1, 2):
        step = ts.make_train_step(cfg, ocfg, None, microbatches=mb)
        opt, comp, sk = ts.init_states(cfg, ocfg, params)
        _, _, _, _, metrics = step(params, opt, comp, sk, batch)
        outs.append(float(metrics["loss"]))
    # Same mean loss across microbatch splits (grads averaged identically).
    assert abs(outs[0] - outs[1]) < 1e-4, outs


def test_padded_heads_equivalence():
    """Padded-head attention (llava/whisper/arctic shapes) must equal the
    unpadded computation on the real heads, with the ORIGINAL GQA wiring."""
    from repro.models import LayerSpec, ModelConfig, attention, common

    # GQA case: 56 q / 8 kv -> padded 64 q / 8 kv, g 7 -> 8 (interleaved).
    cfg = ModelConfig(name="p", n_layers=1, d_model=64, n_heads=56, n_kv_heads=8,
                      d_ff=0, vocab=64, d_head=4, act_dtype="float32")
    d = attention.defs(cfg)
    assert d["wq"].shape == (64, 64, 4)
    assert d["wk"].shape == (64, 8, 4)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          common.init_params(d, jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64), jnp.float32)
    y_pad, _ = attention.apply(params, x, cfg, LayerSpec(), positions=jnp.arange(8))

    # Reference: extract the real-head slots (slot j < 7 within each kv group
    # of 8) and compute without padding machinery.
    real_idx = np.array([k * 8 + j for k in range(8) for j in range(7)])
    p_ref = {"wq": params["wq"][:, real_idx], "wk": params["wk"], "wv": params["wv"],
             "wo": params["wo"][real_idx]}
    sin, cos = common.rope_tables(jnp.arange(8), cfg.head_dim, cfg.rope_theta)
    q = common.apply_rope(jnp.einsum("bse,ehd->bshd", x, p_ref["wq"]), sin, cos)
    k = common.apply_rope(jnp.einsum("bte,ehd->bthd", x, p_ref["wk"]), sin, cos)
    v = jnp.einsum("bte,ehd->bthd", x, p_ref["wv"])
    out = attention.chunked_attention(q, k, v, causal=True, window=None)
    y_ref = jnp.einsum("bshd,hde->bse", out, p_ref["wo"])
    np.testing.assert_allclose(np.asarray(y_pad), np.asarray(y_ref), rtol=1e-5, atol=1e-5)

    # MHA case: 20/20 -> 32/32, real iff head < 20.
    cfg2 = ModelConfig(name="p2", n_layers=1, d_model=80, n_heads=20, n_kv_heads=20,
                       d_ff=0, vocab=64, d_head=4, act_dtype="float32")
    d2 = attention.defs(cfg2)
    assert d2["wq"].shape == (80, 32, 4) and d2["wk"].shape == (80, 32, 4)
    params2 = jax.tree.map(lambda x: x.astype(jnp.float32),
                           common.init_params(d2, jax.random.PRNGKey(2)))
    x2 = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 80), jnp.float32)
    y2, _ = attention.apply(params2, x2, cfg2, LayerSpec(), positions=jnp.arange(8))
    p2_ref = {"wq": params2["wq"][:, :20], "wk": params2["wk"][:, :20],
              "wv": params2["wv"][:, :20], "wo": params2["wo"][:20]}
    sin, cos = common.rope_tables(jnp.arange(8), cfg2.head_dim, cfg2.rope_theta)
    q = common.apply_rope(jnp.einsum("bse,ehd->bshd", x2, p2_ref["wq"]), sin, cos)
    k = common.apply_rope(jnp.einsum("bte,ehd->bthd", x2, p2_ref["wk"]), sin, cos)
    v = jnp.einsum("bte,ehd->bthd", x2, p2_ref["wv"])
    out = attention.chunked_attention(q, k, v, causal=True, window=None)
    y2_ref = jnp.einsum("bshd,hde->bse", out, p2_ref["wo"])
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y2_ref), rtol=1e-5, atol=1e-5)
