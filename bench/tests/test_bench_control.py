"""The control (the reference in bfloat16 in the program's place) fails the
limits of every tiny cell; the reference against itself reads 0, and in
the configuration's float32 stays within the limits."""

import pytest

import control
import harness

SECONDS = 0.6  # the tiny runs' window


@pytest.mark.parametrize("cell", ["t_win_sat", "t_dyn_sat", "t_win_paced"])
def test_control_fails_and_reference_agrees_with_itself(tiny_root, cell):
    c = harness.Cell(cell, tiny_root)
    for seed in (1, 2, 3):
        low = control.readings(c, seed, 6000, "bfloat16", SECONDS)
        ok, _ = harness_judge(low, c)
        assert not ok, low
    same = control.readings(c, 4, 6000, "float64", SECONDS)
    assert all(v == 0 for v in same.values()), same
    f32 = control.readings(c, 5, 6000, "float32", SECONDS)  # the configuration's precision
    assert harness_judge(f32, c)[0], f32


def harness_judge(nums, cell):
    from reference import compare

    return compare.judge(nums, cell.spec["limits"])
