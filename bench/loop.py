"""The measured window, shared by the drivers.

``drive`` pushes the stream chunk by chunk through a driver's ``push`` for
``--seconds``:

* closed loop (``arrival: closed``): the next chunk goes as soon as the
  last push returned; the window ends with ``settle()`` (every micro-batch
  retired), so only finished work counts;
* open loop (``arrival: open``): chunk c is due when its last event is due,
  at ``(c + 1) * chunk / rate_eps`` seconds into the window, whatever the
  system does; event i is due at ``i / rate_eps``. The driver's reads run
  on their own schedule (``anytime_read_s``, ``subring_read_s``). At the
  close the partial micro-batch is flushed and read once more, so every
  event has a read that includes it.

Rotations come every ``epoch_s`` seconds of the configuration's ring (the
window's slide). In an open loop that is event time: the ring rotates
after the chunk whose due time reaches the next epoch boundary, so the
rotations fall on the same events in every run at the mix's rate. A
closed loop has no due times, and rotates on processing time: after the
first chunk pushed once the window's clock has passed the boundary.
"""

from __future__ import annotations

import time

import numpy as np

clock = time.perf_counter


class Window:
    """What a window did: counts, the reads and their times."""

    def __init__(self):
        self.events = 0
        self.rotations: list[int] = []  # events pushed at each rotation
        self.reads: list[tuple] = []  # (kind, events included, epoch, t_done)
        self.subring_s: list[float] = []
        self.late_s: list[float] = []
        self.last: dict = {}  # kind -> host result of the last read
        self.t0 = self.t1 = 0.0

    @property
    def epoch(self) -> int:
        """Index of the current epoch (the warm-up closed epoch 0)."""
        return 1 + len(self.rotations)


def boundaries_passed(t: float, epoch_s: float, passed: int = 0) -> int:
    """Epoch boundaries (multiples of ``epoch_s``) at or before ``t``,
    counted on from ``passed``; multiplied out, not divided, so a boundary
    that a due time meets exactly counts."""
    while (passed + 1) * epoch_s <= t:
        passed += 1
    return passed


def drive(ctx, push, *, batch: int, settle, flush=None, rotate=None, reads=None) -> Window:
    """Run the window; returns its record. ``push(chunk)`` offers one
    arrival chunk, ``rotate()`` closes an epoch, ``flush()`` seals the
    partial micro-batch, ``settle()`` waits until every micro-batch has
    retired; ``reads`` (open loop only) has ``anytime()`` and ``subring()``
    returning host arrays."""
    mix, st, spans = ctx.cell.mix, ctx.stream, ctx.spans
    chunk = st.chunk_len
    epoch_s = float(ctx.cell.config.get("epoch_s") or 0) if rotate else 0.0
    w = Window()
    staged = 0
    passed = 0

    def offer(c, t):
        """Push chunk ``c``; ``t`` is its time in the window (due or now)."""
        nonlocal staged, passed
        with spans("route+push"):
            push(st.chunk(c))
        w.events += chunk
        staged = (staged + chunk) % batch
        if epoch_s and boundaries_passed(t, epoch_s, passed) > passed:
            with spans("rotate"):
                rotate()
            w.rotations.append(w.events)
            staged = 0
            passed = boundaries_passed(t, epoch_s, passed)

    def read(kind):
        included = w.events - staged
        with spans("read_" + kind):
            if kind == "subring":
                out, secs = reads.subring()
                w.subring_s.append(secs)
            else:
                out = reads.anytime()
        w.last[kind] = out
        w.reads.append((kind, included, w.epoch, clock()))

    ctx.window_starts()
    with spans("window"):
        w.t0 = t0 = clock()
        c = 0
        if mix["arrival"] == "closed":
            while (now := clock() - t0) < ctx.seconds:
                offer(c, now)
                c += 1
            settle()
        else:
            rate = float(mix["rate_eps"])
            any_s, sub_s = float(mix["anytime_read_s"]), float(mix["subring_read_s"])
            next_any, next_sub, end = t0 + any_s, t0 + sub_s, t0 + ctx.seconds
            while True:
                now = clock()
                if now >= end:
                    break
                if now >= next_sub:
                    read("subring")
                    while next_sub <= clock():
                        next_sub += sub_s
                    continue
                if now >= next_any:
                    read("anytime")
                    while next_any <= clock():
                        next_any += any_s
                    continue
                due_s = (c + 1) * chunk / rate
                due = t0 + due_s
                if now >= due:
                    w.late_s.append(now - due)
                    offer(c, due_s)
                    c += 1
                    continue
                wake = min(due, next_any, next_sub, end)
                with spans("gen_wait"):
                    if wake - now > 1e-3:
                        time.sleep(wake - now - 5e-4)
                    while clock() < wake:
                        pass
            flush()
            staged = 0
            read("anytime")
            settle()
        w.t1 = clock()
    ctx.window_ends()
    return w


def freshness_s(w: Window, rate: float) -> np.ndarray:
    """Per event of an open-loop window: seconds from its due time to the
    completion (result on the host) of the first read that includes it."""
    inc = np.array([r[1] for r in w.reads], np.int64)
    done = np.array([r[3] for r in w.reads], np.float64)
    # The first read whose state includes event i is the first with
    # included > i; reads are in time order and included never falls.
    i = np.arange(w.events, dtype=np.int64)
    k = np.searchsorted(inc, i, side="right")
    return done[k] - (w.t0 + i / rate)
