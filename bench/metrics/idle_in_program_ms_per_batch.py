"""idle_in_program_ms_per_batch (ms, device trace): per micro-batch, the
device's idle time in the traced window while the host was inside one of
the program's ingest spans, averaged over the cell's devices.

The program's spans are on ``time.perf_counter`` (``repro.obs.trace``
events) and the device's busy intervals on the profiler's clock
(``run["trace"]``). The two are aligned by pairing: each arrival chunk
makes one harness ``route+push`` annotation (profiler clock) and, nested
inside it, one program ``ingest/push`` span, in both drivers. Both end
when the chunk is staged, a few microseconds apart, while their starts lie
a whole routing step apart in the window driver (its routing runs inside
the annotation and before ``ingest/push``). So the offset is the median of
the pairs' end differences; the reader returns None where the counts
differ or the pairs spread by more than 1 ms at the 95th percentile.

Each idle stretch is given to the innermost program span that covers it
(its self time); the split, with the idle outside every program span (the
driver's own work: in ``dyn_k22_sat`` its routing and slot readback), is
printed on stderr.
"""

import collections

import numpy as np

import harness
import tracekit
from repro.obs import trace as obs_trace

SPANS = ("ingest/route", "ingest/route_wait", "ingest/push", "ingest/seal", "ingest/dispatch",
         "ingest/stall", "ingest/retire", "ingest/rotate")
OUTER, INNER = "route+push", "ingest/push"
MAX_SPREAD_NS = 1e6


def program_spans() -> list[tuple[float, float, str]]:
    """(start, end, name) of the program's ingest spans, ns of perf_counter
    since the tracer was built."""
    return [(e["ts"] * 1e3, (e["ts"] + e["dur"]) * 1e3, e["name"])
            for e in obs_trace.events() if e["name"] in SPANS]


def offset_ns(host: list, spans: list) -> float | None:
    """Profiler-clock ns minus program-clock ns, from the paired ends of
    the harness's ``route+push`` annotations and the ``ingest/push`` spans;
    None where they do not pair."""
    outer = np.sort([e.end for e in host if e.name == OUTER])
    inner = np.sort([t for _, t, name in spans if name == INNER])
    if not len(outer) or len(outer) != len(inner):
        return None
    d = outer - inner
    off = float(np.median(d))
    if float(np.percentile(np.abs(d - off), 95)) > MAX_SPREAD_NS:
        return None
    return off


def innermost(spans: list) -> list[tuple[float, float, str]]:
    """Disjoint (start, end, name) segments, each named for the innermost
    of the nested spans that covers it."""
    out, stack, pos = [], [], 0.0

    def emit(a, b, name):
        if b > a:
            out.append((a, b, name))

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            emit(pos, end, top)
            pos = end
        if stack:
            emit(pos, s, stack[-1][1])
        pos = s
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    while stack:
        end, top = stack.pop()
        emit(pos, end, top)
        pos = end
    return out


def overlap(gaps: list, segs: list) -> dict[str, float]:
    """Length of the gaps covered by each segment name (both sorted and
    disjoint)."""
    tot: dict[str, float] = collections.Counter()
    i = 0
    for a, b in gaps:
        while i < len(segs) and segs[i][1] <= a:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < b:
            ov = min(b, segs[j][1]) - max(a, segs[j][0])
            if ov > 0:
                tot[segs[j][2]] += ov
            j += 1
    return dict(tot)


def split(run) -> dict[str, float] | None:
    """Idle seconds under each innermost program span, and ``outside``,
    averaged over the devices; None where nothing can be aligned."""
    t = run["trace"]
    if t is None or not t.devices or not run["qobs"]:
        return None
    spans = program_spans()
    off = offset_ns(t.host, spans)
    if off is None:
        return None
    segs = innermost([(s + off, e + off, name) for s, e, name in spans])
    tot: dict[str, float] = collections.Counter()
    idle = 0.0
    for d in t.devices:
        gaps = tracekit.gaps(t.busy_intervals(d), t.t0, t.t1)
        idle += sum(b - a for a, b in gaps)
        for name, ns in overlap(gaps, segs).items():
            tot[name] += ns
    out = {name: ns / 1e9 / len(t.devices) for name, ns in tot.items()}
    out["outside"] = idle / 1e9 / len(t.devices) - sum(out.values())
    return out


def read(run):
    s = split(run)
    if s is None or not run["batches"]:
        return None
    harness.log("idle by program span (s) " + " ".join(f"{k}={v!r}" for k, v in sorted(s.items())))
    return sum(v for k, v in s.items() if k != "outside") / run["batches"] * 1e3
