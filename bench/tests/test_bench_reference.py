"""The plain reference restates the program's hash family, routing and
estimator independently; here the two are held side by side."""

import jax.numpy as jnp
import numpy as np

from reference import oracle
from repro.core import SketchConfig, estimators, hashing, key_directory


def test_hashes_and_routing_match_the_program():
    rng = np.random.default_rng(0)
    lo = rng.integers(0, 2**32, 4096, dtype=np.uint32)
    hi = rng.integers(0, 2**32, 4096, dtype=np.uint32)
    for salt in (0, 0x5EED, 0xFFFFFFFF):
        np.testing.assert_array_equal(oracle.hash_words((lo, hi), salt),
                                      np.asarray(hashing.hash_words((jnp.asarray(lo), jnp.asarray(hi)), salt)))
    for k in (1024, 2**20 + 7, 2**22):
        dcfg = key_directory.DirectoryConfig(capacity=k)
        np.testing.assert_array_equal(oracle.route(lo, hi, k, dcfg.seed),
                                      np.asarray(key_directory.route_slots(dcfg, (jnp.asarray(lo), jnp.asarray(hi)))))


def test_register_choice_and_value_match_the_program():
    cfg = SketchConfig(m=128, b=8)
    conf = {"m": 128, "b": 8, "sketch_seed": cfg.seed}
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 2**32, 2048, dtype=np.uint32)
    w = rng.gamma(1.0, 2.0, 2048).astype(np.float32)
    j, y = oracle.quantize(conf, ids, w, low=False)
    from repro.core import qsketch_dyn
    jp, yp = qsketch_dyn._choose_and_quantize(cfg, jnp.asarray(ids), jnp.zeros(2048, jnp.uint32), jnp.asarray(w))
    np.testing.assert_array_equal(j, np.asarray(jp))
    np.testing.assert_array_equal(y, np.asarray(yp))
    _, ylow = oracle.quantize(conf, ids, w, low=True)
    assert np.mean(ylow != y) > 0.01  # the control's precision moves registers


def test_mle_matches_the_float64_estimator():
    cfg = SketchConfig(m=64, b=8)
    rng = np.random.default_rng(2)
    rows = [np.clip(rng.integers(-20, 30, 64), cfg.r_min, cfg.r_max) for _ in range(8)]
    rows.append(np.full(64, cfg.r_min))
    hists = np.stack([np.bincount(r - cfg.r_min, minlength=256) for r in rows])
    got = oracle.mle(hists, 8, 64, oracle.Arith("float64"))
    want = np.array([64 * estimators.mle_numpy(cfg, r) for r in rows])
    np.testing.assert_allclose(got, want, rtol=1e-9)
    low = oracle.mle(hists, 8, 64, oracle.Arith("bfloat16"))
    assert np.max(np.abs(low[:-1] - want[:-1]) / want[:-1]) > 1e-4
