"""Each driver at a tiny size on the CPU (Pallas in interpret mode), through
the whole harness but its look for a chip: the comparison with the plain
reference passes, and fails with the timed path broken underneath."""

import json

import jax
import jax.numpy as jnp
import pytest

import run as bench_run
from repro.core import window_array
from repro.kernels import ops
from repro.sketchstream import ingest

SEED = 2**31 + 77


def _result(capsys, root, cell):
    rc = bench_run.main(["--workload", cell, "--seed", str(SEED), "--seconds", "0.6", "--trace", "0"],
                        root=root, require_chip=False)
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    out = json.loads(line)
    assert list(out)[-1] == "checks"
    return out


@pytest.mark.parametrize("cell", ["t_win_sat", "t_dyn_sat", "t_win_paced"])
def test_sound_run_is_correct(capsys, tiny_root, cell):
    out = _result(capsys, tiny_root, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2


def _wrap_update(monkeypatch, cell, make):
    """Replace the pipeline's update of the cell's container by make(real)."""
    name = "_dyn_update_fn" if cell == "t_dyn_sat" else "_window_update_fn"
    real_factory = getattr(ingest, name)
    monkeypatch.setattr(ingest, name, lambda cfg, *a: make(real_factory(cfg, *a)))


def _unchanged(real):
    def fn(state, keys, ids, w, mask):
        return state, jax.tree.leaves(state)[0].ravel()[0]
    return fn


def _half_batch(real):
    # Every other slot, so the fault shows in a micro-batch that a rotation
    # flushed before it filled.
    def fn(state, keys, ids, w, mask):
        keep = jnp.arange(mask.shape[0]) % 2 == 0
        return real(state, keys, ids, w, mask & keep)
    return fn


def _chats_altered(real):
    def fn(state, keys, ids, w, mask):
        out, ticket = real(state, keys, ids, w, mask)
        # Every running estimate the step leaves (all ring epochs' or the
        # DynArray's), so the fault shows however the window's last
        # rotation falls.
        return out._replace(chats=out.chats * 1.01), ticket
    return fn


@pytest.mark.parametrize("cell", ["t_win_sat", "t_dyn_sat"])
@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "answer_altered"])
def test_broken_update_is_not_correct(capsys, monkeypatch, tiny_root, cell, fault):
    _wrap_update(monkeypatch, cell, {"unchanged": _unchanged, "half_batch": _half_batch,
                                     "answer_altered": _chats_altered}[fault])
    out = _result(capsys, tiny_root, cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("read", ["anytime", "subring"])
def test_altered_read_is_not_correct(capsys, monkeypatch, tiny_root, read):
    if read == "anytime":
        real = window_array.estimate_ring_anytime
        monkeypatch.setattr(window_array, "estimate_ring_anytime", lambda st: real(st) * 1.01)
    else:
        real = ops.window_union_estimate_op
        monkeypatch.setattr(ops, "window_union_estimate_op", lambda *a, **k: real(*a, **k) * 1.01)
    out = _result(capsys, tiny_root, "t_win_paced")
    assert not out["correct"], out["checks"]


def _registers_lowered(real):
    def fn(state, keys, ids, w, mask):
        out, ticket = real(state, keys, ids, w, mask)
        touched = out.regs > -127
        return out._replace(regs=jnp.where(touched, out.regs - 1, out.regs).astype(out.regs.dtype)), ticket
    return fn


@pytest.mark.parametrize("cell", ["t_win_sat", "t_dyn_sat"])
def test_altered_register_is_not_correct(capsys, monkeypatch, tiny_root, cell):
    _wrap_update(monkeypatch, cell, _registers_lowered)
    out = _result(capsys, tiny_root, cell)
    assert not out["correct"]
    assert out["checks"]["state_mismatch"]["value"] > 0
