"""The window's rotation clock: an open loop rotates on event time (the same
events in every run at the mix's rate), a closed loop on processing time."""

import contextlib
import types

import loadgen
import loop
from bench_fixtures import MIX, PACED


def _ctx(mix, seconds, epoch_s):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(mix=mix, config={"epoch_s": epoch_s}),
        stream=loadgen.Stream(mix, 1024, 3), seconds=seconds,
        spans=lambda name: contextlib.nullcontext(),
        window_starts=lambda: None, window_ends=lambda: None)


def _drive(mix, seconds, epoch_s):
    rotated_at = []
    reads = types.SimpleNamespace(anytime=lambda: None, subring=lambda: (None, 0.0))
    w = loop.drive(_ctx(mix, seconds, epoch_s), lambda ch: None, batch=512, settle=lambda: None,
                   flush=lambda: None, rotate=lambda: rotated_at.append(loop.clock()), reads=reads)
    return w, rotated_at


def test_open_loop_rotates_on_event_time():
    mix = dict(PACED, rate_eps=40000, anytime_read_s=10.0, subring_read_s=10.0)
    chunk = mix["chunk"]
    a, _ = _drive(mix, 0.5, 0.1)
    b, _ = _drive(mix, 0.5, 0.1)
    # The chunk whose due time first reaches k * 0.1 s closes epoch k.
    due = [-(-round(k * 0.1 * 40000) // chunk) * chunk for k in range(1, 5)]
    assert a.rotations == b.rotations == due


def test_closed_loop_rotates_on_processing_time():
    w, rotated_at = _drive(MIX, 0.55, 0.1)
    assert len(w.rotations) == 5
    gaps = [y - x for x, y in zip(rotated_at[:-1], rotated_at[1:])]
    assert all(0.05 < g < 0.15 for g in gaps), gaps
