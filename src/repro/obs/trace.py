"""Span-based stage tracing with Chrome trace-event / Perfetto export.

Spans mark host-side pipeline stages (push/seal/dispatch/retire/rotate/
estimate/solve). Each ``span(name)`` context manager records one Chrome
"complete" event (``ph: "X"``) with microsecond start/duration; nesting is
tracked via ``contextvars`` so a span opened inside another carries its
full ``path`` in the event args and renders nested in Perfetto (load the
saved JSON at https://ui.perfetto.dev or chrome://tracing).

Two rules keep tracing honest in an async-dispatch JAX program:

* **Strictly outside jit.** A span inside a traced region would time the
  *trace*, not the run, and record exactly once. When tracing is enabled,
  ``span`` checks ``tracing_active()`` and degrades to a no-op
  under any active trace — so host helpers that are occasionally called
  from jitted code stay safe.
* **Host wall-time is not device time.** Dispatch returns before the
  device finishes, so a "dispatch" span measures enqueue cost only. The
  sampled sync hook (``maybe_sync``) closes the gap: every
  ``sync_every``-th tick it runs ``jax.block_until_ready`` under its own
  span, attributing accumulated device time to that point WITHOUT paying a
  pipeline-draining sync on every batch (the tradeoff is documented in
  DESIGN.md §10 — the sampled batch itself loses its overlap).

Disabled (the default), ``span`` returns a shared no-op context manager:
one function call + one branch per instrumentation point.
"""

from __future__ import annotations

import contextvars
import json
import threading
import time

import jax

# Nesting stack of span names for the current (context-local) execution.
_STACK: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "qobs_span_stack", default=()
)


class _NullSpan:
    """Shared no-op context manager returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


def tracing_active() -> bool:
    """True while any jax transformation is tracing (jit, vmap,
    shard_map, grad): the guard every host-side emission checks, so code
    that is sometimes called from a traced region records nothing there."""
    return not jax.core.trace_ctx.is_top_level()


class _Span:
    """One live span: records a Chrome 'X' event on exit."""

    __slots__ = ("_tracer", "name", "args", "_t0", "_token")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args

    def __enter__(self):
        self._token = _STACK.set(_STACK.get() + (self.name,))
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur_ns = time.perf_counter_ns() - self._t0
        stack = _STACK.get()
        _STACK.reset(self._token)
        self._tracer._record(
            self.name, self._t0, dur_ns, "/".join(stack), self.args
        )
        return False


class Tracer:
    """A span recorder: configuration + the accumulated event list."""

    def __init__(self, enabled: bool = False, sync_every: int = 0):
        self._enabled = bool(enabled)
        self.sync_every = int(sync_every)
        self._events: list[dict] = []
        self._lock = threading.Lock()
        self._epoch_ns = time.perf_counter_ns()

    @property
    def enabled(self) -> bool:
        """Whether spans record events."""
        return self._enabled

    def configure(self, *, enabled: bool | None = None,
                  sync_every: int | None = None) -> None:
        """Toggle recording and/or set the sampled-sync period (0 = never
        sync; N = block_until_ready every N-th ``maybe_sync`` tick)."""
        if enabled is not None:
            self._enabled = bool(enabled)
        if sync_every is not None:
            self.sync_every = int(sync_every)

    def span(self, name: str, **args):
        """Context manager timing one stage. No-op while disabled or while
        any jax trace is active (see module docstring)."""
        if not self._enabled or tracing_active():
            return _NULL
        return _Span(self, name, args)

    def maybe_sync(self, name: str, value, tick: int) -> bool:
        """Sampled device-time attribution: every ``sync_every``-th tick,
        ``block_until_ready(value)`` under a span named ``name`` (with
        ``sampled: True`` in its args). Returns True iff it synced."""
        if (
            not self._enabled
            or self.sync_every <= 0
            or tick % self.sync_every
            or tracing_active()
        ):
            return False
        with self.span(name, sampled=True, tick=tick):
            jax.block_until_ready(value)
        return True

    def _record(self, name, t0_ns, dur_ns, path, args) -> None:
        ev = {
            "name": name,
            "cat": "qobs",
            "ph": "X",
            "ts": (t0_ns - self._epoch_ns) / 1e3,  # µs, Chrome's unit
            "dur": dur_ns / 1e3,
            "pid": 0,
            "tid": threading.get_ident() & 0x7FFFFFFF,
            "args": {"path": path, **args},
        }
        with self._lock:
            self._events.append(ev)

    # -- export -----------------------------------------------------------

    def events(self) -> list[dict]:
        """The recorded Chrome trace events (copy)."""
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        """Drop all recorded events."""
        with self._lock:
            self._events.clear()

    def chrome_trace(self) -> dict:
        """The full Chrome trace-event JSON object Perfetto loads."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        """Write the Chrome trace JSON to ``path``; returns the path."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def stage_totals(self) -> dict:
        """Total seconds per span name — the per-stage profile the ingest
        benchmark folds into its cumulative JSON."""
        out: dict[str, float] = {}
        for ev in self.events():
            out[ev["name"]] = out.get(ev["name"], 0.0) + ev["dur"] / 1e6
        return out


_DEFAULT = Tracer()


def default_tracer() -> Tracer:
    """The process-default tracer the library instrumentation targets."""
    return _DEFAULT


def configure(*, enabled: bool | None = None, sync_every: int | None = None) -> None:
    """Configure the default tracer (see ``Tracer.configure``)."""
    _DEFAULT.configure(enabled=enabled, sync_every=sync_every)


def enabled() -> bool:
    """Whether the default tracer records."""
    return _DEFAULT.enabled


def span(name: str, **args):
    """A span on the default tracer (see ``Tracer.span``)."""
    return _DEFAULT.span(name, **args)


def maybe_sync(name: str, value, tick: int) -> bool:
    """Sampled sync on the default tracer (see ``Tracer.maybe_sync``)."""
    return _DEFAULT.maybe_sync(name, value, tick)


def events() -> list[dict]:
    """Events recorded by the default tracer."""
    return _DEFAULT.events()


def clear() -> None:
    """Drop the default tracer's events."""
    return _DEFAULT.clear()


def save(path: str) -> str:
    """Save the default tracer's Chrome trace JSON to ``path``."""
    return _DEFAULT.save(path)


def stage_totals() -> dict:
    """Per-stage total seconds from the default tracer."""
    return _DEFAULT.stage_totals()
