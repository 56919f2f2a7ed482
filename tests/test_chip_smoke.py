"""chip_smoke.py on the CPU: its phases at a tiny size, its reference checks,
and its refusal to run without a TPU.

The phases run with ``interpret=True`` passed explicitly (the Pallas kernels
execute in interpret mode); the script itself has no CPU switch.
"""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402

# m = 16 keeps the stream small while a few slots still reach the routed
# MLE's stated regime (MLE_MIN_LOAD distinct elements per register).
TINY = cs.Sizes(k=1024, m=16, batch=512, batches_per_epoch=4, n_hot=8, n_cold=8,
                n_untouched=4, min_in_regime=1)


@pytest.fixture(scope="module")
def phases():
    stream = cs.make_stream(TINY)
    ref = cs.reference(TINY, stream)
    win = cs.run_window(TINY, stream, interpret=True)
    dyn_k = cs.run_dyn(TINY, stream, use_kernel=True, interpret=True)
    dyn_j = cs.run_dyn(TINY, stream, use_kernel=False, interpret=True)
    return stream, ref, win, dyn_k, dyn_j


def test_stream_weights_are_a_function_of_the_element():
    stream = cs.make_stream(TINY)
    assert len(stream["ids"]) == TINY.n_elems
    order = np.argsort(stream["ids"], kind="stable")
    ids, w = stream["ids"][order], stream["w"][order]
    same = ids[1:] == ids[:-1]
    assert same.any() and np.array_equal(w[1:][same], w[:-1][same])


def test_phases_pass_every_reference_check(phases):
    stream, ref, win, dyn_k, dyn_j = phases
    chk = cs.Checks()
    cs.verify_window(TINY, stream, ref, win, chk)
    cs.verify_dyn(TINY, stream, ref, dyn_k, dyn_j, chk)
    assert chk.ok, chk.failed
    assert chk.n >= 30
    assert win["metrics"]["ingest_rotations"] == TINY.rotations
    assert win["metrics"]["ingest_elements_pushed"] == TINY.n_elems


def test_checks_catch_a_corrupted_register(phases):
    stream, ref, win, dyn_k, dyn_j = phases
    hot = int(ref["sample"][np.argmax(np.bincount(ref["slots"], minlength=TINY.k)[ref["sample"]])])
    st = dyn_k["state"]
    bad = dict(dyn_k, state=st._replace(regs=st.regs.at[hot, 0].add(1)))
    chk = cs.Checks()
    cs.verify_dyn(TINY, stream, ref, bad, dyn_j, chk)
    assert any(f.startswith("dyn kernel == jnp route: regs") for f in chk.failed)
    assert any(f.startswith("dyn rows == oracle: regs") for f in chk.failed)

    ws = win["state"]
    head = int(ws.head)
    bad_win = dict(win, state=ws._replace(regs=ws.regs.at[head, hot, 0].add(1)))
    chk = cs.Checks()
    cs.verify_window(TINY, stream, ref, bad_win, chk)
    assert any(f.startswith(f"epoch {TINY.rotations} regs == oracle") for f in chk.failed)


def test_estimate_check_flags_out_of_error_reads():
    chk = cs.Checks()
    exact = np.array([100.0, 200.0, 0.0])
    err = np.array([10.0, 10.0, 1.0])
    regime = np.array([True, True, False])
    cs.check_estimates(chk, "ok", [105.0, 190.0, 0.0], exact, err, regime, 2)
    assert chk.ok
    cs.check_estimates(chk, "far", [150.0, 200.0, 0.0], exact, err, regime, 2)
    cs.check_estimates(chk, "untouched", [100.0, 200.0, 3.0], exact, err, regime, 2)
    assert [f.split(":")[0] for f in chk.failed] == ["far", "untouched"]


def test_four_chip_phase_on_host_devices():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices (tests/conftest.py sets 8)")
    chk = cs.four_chips(dataclasses.replace(TINY, rotations=2), interpret=True)
    assert chk.ok, chk.failed
    assert chk.n == 15


def test_result_line_names_the_devices():
    line = cs.result_line(jax.devices())
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}


def _run_script(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_main_refuses_to_run_without_a_tpu():
    r = _run_script(ROOT)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_script_alone_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = _run_script(tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
