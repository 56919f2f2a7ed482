"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The trace is read with ``jax.profiler.ProfileData`` into plain events
(plane, line, name, start, end in ns), so the arithmetic below is checked
on a small trace recorded on a chip (``tests/fixtures``).

* device planes are the ``/device:TPU:<n>`` planes; on each, the ``XLA Ops``
  line holds one event per executed operation and ``XLA Modules`` one per
  executable run (named ``<module>(<id>)``);
* the traced window is the interval of the harness's ``window`` annotation
  on the host plane; everything is clipped to it;
* busy time of a device is the union of its op intervals; the idle share is
  1 - busy / window, averaged over the devices;
* a module's device time is the sum of its run intervals;
* each idle gap of a device is split among the harness annotations that
  cover it on the host (``gen_wait``, ``route+push``, ``rotate``,
  ``read_anytime``, ``read_subring``); what none covers is ``other``.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"
ANNOTATIONS = ("gen_wait", "route+push", "rotate", "read_anytime", "read_subring")


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start: float  # ns
    end: float  # ns


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def read_events(path: str) -> list[Event]:
    """Every event of the device planes' op and module lines, and every
    host event whose name is a harness annotation."""
    from jax.profiler import ProfileData

    keep = set(ANNOTATIONS) | {WINDOW}
    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = is_device_plane(plane.name)
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if dev or ev.name in keep:
                    s = float(ev.start_ns)
                    out.append(Event(plane.name, line.name, ev.name, s, s + float(ev.duration_ns)))
    return out


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


class Trace:
    """Reduced view of one traced window."""

    def __init__(self, events: list[Event]):
        host = [e for e in events if not is_device_plane(e.plane)]
        win = [e for e in host if e.name == WINDOW]
        if not win:
            raise ValueError("trace has no 'window' annotation")
        self.t0 = min(e.start for e in win)
        self.t1 = max(e.end for e in win)
        self.host = [e for e in host if e.name in ANNOTATIONS]
        dev = [e for e in events if is_device_plane(e.plane)]
        self.devices = sorted({e.plane for e in dev})
        self.ops = {d: self._clip([e for e in dev if e.plane == d and e.line == OPS_LINE])
                    for d in self.devices}
        self.modules = {d: self._clip([e for e in dev if e.plane == d and e.line == MODULES_LINE])
                        for d in self.devices}

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        return cls(read_events(path))

    def _clip(self, evs):
        out = []
        for e in evs:
            s, t = max(e.start, self.t0), min(e.end, self.t1)
            if t > s:
                out.append(Event(e.plane, e.line, e.name, s, t))
        return out

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def busy_s(self, device: str) -> float:
        return sum(b - a for a, b in self.busy_intervals(device)) / 1e9

    def busy_intervals(self, device: str) -> list[tuple[float, float]]:
        """Union of the device's op intervals (module runs where a device
        has no op line)."""
        evs = self.ops[device] or self.modules[device]
        return merge_intervals([(e.start, e.end) for e in evs])

    def mean_busy_s(self) -> float | None:
        if not self.devices:
            return None
        return float(np.mean([self.busy_s(d) for d in self.devices]))

    def idle_frac(self) -> float | None:
        b = self.mean_busy_s()
        return None if b is None else 1.0 - b / self.window_s

    def module_s(self, names, device: str | None = None) -> float | None:
        """Device seconds of the runs of modules whose name (before its
        ``(id)``) is in ``names``, summed over the devices (or one); None
        where no such module ran."""
        names = set(names)
        devs = self.devices if device is None else [device]
        hits = [e.end - e.start for d in devs for e in self.modules[d] if module_name(e.name) in names]
        return sum(hits) / 1e9 if hits else None

    def module_totals(self) -> dict[str, float]:
        tot: dict[str, float] = collections.Counter()
        for d in self.devices:
            for e in self.modules[d]:
                tot[module_name(e.name)] += (e.end - e.start) / 1e9
        return dict(tot)

    def top_ops(self, n: int = 10) -> list[list]:
        """Device operations that took the most time (seconds summed over
        the devices, divided by their count)."""
        tot: dict[str, float] = collections.Counter()
        for d in self.devices:
            for e in self.ops[d] or self.modules[d]:
                tot[op_label(e.name)] += (e.end - e.start) / 1e9
        k = max(len(self.devices), 1)
        return [[name, s / k] for name, s in sorted(tot.items(), key=lambda x: -x[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Idle device time by what the host was doing: seconds of idle
        gaps (averaged over the devices) per covering annotation."""
        tot: dict[str, float] = collections.Counter()
        host = HostSpans([(e.start, e.end, e.name) for e in self.host])
        for d in self.devices:
            for a, b in gaps(self.busy_intervals(d), self.t0, self.t1):
                for name, s in host.attribute(a, b).items():
                    tot[name] += s / 1e9
        k = max(len(self.devices), 1)
        return [[name, s / k] for name, s in sorted(tot.items(), key=lambda x: -x[1])[:n]]


def module_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def op_label(event_name: str, shape_width: int = 60) -> str:
    """An op event's HLO text cut to its name, result shape and opcode
    (``%fusion = s32[268435456]{0:T(1024)} fusion``)."""
    lhs, sep, rhs = event_name.partition(" = ")
    m = re.match(r"(.*?)\s([A-Za-z][\w.-]*)\(", rhs)
    if not m:
        return (lhs + sep + rhs)[: len(lhs) + len(sep) + shape_width]
    return f"{lhs}{sep}{m.group(1)[:shape_width]} {m.group(2)}"


def merge_intervals(iv):
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy, t0, t1):
    out, cur = [], t0
    for a, b in busy:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        out.append((cur, t1))
    return out


class HostSpans:
    """The harness's host spans, which follow one another on one thread."""

    def __init__(self, spans):
        spans = sorted(spans)
        self.start = np.array([s for s, _, _ in spans], np.float64)
        self.end = np.array([t for _, t, _ in spans], np.float64)
        self.name = [n for _, _, n in spans]

    def attribute(self, a: float, b: float) -> dict[str, float]:
        """Split the gap [a, b) among the spans that overlap it; what no
        span covers is ``other``."""
        out: dict[str, float] = collections.Counter()
        lo = int(np.searchsorted(self.end, a, side="right"))
        hi = int(np.searchsorted(self.start, b, side="left"))
        covered = 0.0
        for i in range(lo, hi):
            ov = min(self.end[i], b) - max(self.start[i], a)
            if ov > 0:
                out[self.name[i]] += ov
                covered += ov
        if b - a - covered > 0:
            out["other"] += b - a - covered
        return out


def describe(path: str) -> None:
    """Print the planes and lines of a trace, with event counts and the
    busiest module names (to look at a trace by hand)."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = [(line.name, sum(1 for _ in line.events)) for line in plane.lines]
        print(plane.name, lines[:12])
    tr = Trace.from_file(path)
    print("window_s", tr.window_s, "devices", tr.devices, "idle_frac", tr.idle_frac())
    print("modules", sorted(tr.module_totals().items(), key=lambda x: -x[1])[:15])
    print("top_ops", tr.top_ops(10))
    print("idle_gaps", tr.idle_gaps(10))


if __name__ == "__main__":
    import sys

    describe(sys.argv[1])
