"""Span-based stage tracing on the profiler's clock, with Chrome trace-event /
Perfetto export.

Spans mark host-side pipeline stages (route/push/seal/dispatch/retire/
rotate/estimate/solve). Each ``span(name)`` context manager records one
Chrome "complete" event (``ph: "X"``) with microsecond start/duration;
nesting is tracked via ``contextvars`` so a span opened inside another
carries its full ``path`` in the event args and renders nested in Perfetto
(load the saved JSON at https://ui.perfetto.dev or chrome://tracing).

While the tracer is enabled, each span is also a
``jax.profiler.TraceAnnotation`` of the same name: while the JAX profiler
runs (``jax.profiler.start_trace``), the span sits on the host plane of
its ``.xplane.pb``, on the same clock as the device's operations, so xprof
or Perfetto shows each host stage beside the device work it launched or
waited for.

Two more kinds of event land in the same list, neither a span:

* ``record(name, t0_ns)`` — an interval that crosses calls (the ingest
  staging fill, from the first element entering a buffer to its seal):
  an event only, with no profiler annotation;
* ``jax/compile`` — one per XLA backend compile or persistent-cache load
  while the default tracer is enabled, with the compiled function's name
  (``fun``) and the enclosing span ``path``, from a ``jax.monitoring``
  listener registered on the first ``configure(enabled=True)``. The same
  compile increments the registry counter ``jax_compiles{span, fun}``. In
  a warmed-up loop the count stays at 0; a compile there is a shape the
  warm-up missed, named by the step that caused it.

Two rules keep tracing honest in an async-dispatch JAX program:

* **Strictly outside jit.** A span inside a traced region would time the
  *trace*, not the run, and record exactly once. When tracing is enabled,
  ``span`` checks ``tracing_active()`` and degrades to a no-op
  under any active trace — so host helpers that are occasionally called
  from jitted code stay safe.
* **Host wall-time is not device time.** Dispatch returns before the
  device finishes, so a "dispatch" span measures enqueue cost only, and a
  span around a host read of a device value measures the wait for
  everything queued on the device ahead of it. Device time itself is read
  from the profiler's device planes, which the annotations share a clock
  with; no span syncs the device.

Disabled (the default), ``span`` returns a shared no-op context manager:
one function call + one branch per instrumentation point, with no
annotation built and no clock read.
"""

from __future__ import annotations

import contextvars
import json
import threading
import time

import jax
from jax.profiler import TraceAnnotation

from repro.obs import metrics as obs_metrics

# Nesting stack of span names for the current (context-local) execution.
_STACK: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "qobs_span_stack", default=()
)

# The jax.monitoring duration event of one backend compile (or persistent
# cache load): ``jax._src.dispatch.BACKEND_COMPILE_EVENT`` in JAX 0.9.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# The tracer event recorded for each such compile.
COMPILE_EVENT = "jax/compile"

_M_COMPILES = obs_metrics.counter(
    "jax_compiles",
    "XLA compiles and persistent-cache loads while tracing is enabled",
    labels=("span", "fun"),
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
    """One live span: a profiler annotation while open, and a Chrome 'X'
    event on exit."""

    __slots__ = ("_tracer", "name", "args", "_t0", "_token", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args

    def __enter__(self):
        self._token = _STACK.set(_STACK.get() + (self.name,))
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur_ns = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        stack = _STACK.get()
        _STACK.reset(self._token)
        self._tracer._record(
            self.name, self._t0, dur_ns, "/".join(stack), self.args
        )
        return False


class Tracer:
    """A span recorder: configuration + the accumulated event list."""

    def __init__(self, enabled: bool = False):
        self._enabled = bool(enabled)
        self._events: list[dict] = []
        self._lock = threading.Lock()
        self._epoch_ns = time.perf_counter_ns()

    @property
    def enabled(self) -> bool:
        """Whether spans record events."""
        return self._enabled

    def configure(self, *, enabled: bool | None = None) -> None:
        """Toggle recording."""
        if enabled is not None:
            self._enabled = bool(enabled)

    def span(self, name: str, **args):
        """Context manager timing one stage. No-op while disabled or while
        any jax trace is active (see module docstring)."""
        if not self._enabled or tracing_active():
            return _NULL
        return _Span(self, name, args)

    def record(self, name: str, t0_ns: int, **args) -> None:
        """Record one event from ``t0_ns`` (a ``time.perf_counter_ns``
        reading) to now, for an interval that crosses calls and so cannot
        be a span; its ``path`` is its name, and it makes no profiler
        annotation. No-op while disabled."""
        if self._enabled:
            self._record(name, t0_ns, time.perf_counter_ns() - t0_ns, name, args)

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
        """The recorded Chrome trace events (copy). ``ts`` is microseconds
        of ``time.perf_counter`` since the tracer was built."""
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
        """Total seconds per event name — the per-stage profile the ingest
        benchmark folds into its cumulative JSON."""
        out: dict[str, float] = {}
        for ev in self.events():
            out[ev["name"]] = out.get(ev["name"], 0.0) + ev["dur"] / 1e6
        return out


_DEFAULT = Tracer()
_LISTENING = False


def _on_compile(event: str, duration_secs: float, **kwargs) -> None:
    """``jax.monitoring`` duration listener: one ``jax/compile`` event and
    one ``jax_compiles`` increment per backend compile, attributed to the
    span the compile happened in. Returns at once while disabled."""
    if not _DEFAULT._enabled or event != BACKEND_COMPILE_EVENT:
        return
    t1 = time.perf_counter_ns()
    path = "/".join(_STACK.get())
    fun = str(kwargs.get("fun_name", ""))
    _DEFAULT._record(
        COMPILE_EVENT, t1 - int(duration_secs * 1e9), int(duration_secs * 1e9),
        path, {"fun": fun},
    )
    _M_COMPILES.labels(span=path, fun=fun).inc()


def default_tracer() -> Tracer:
    """The process-default tracer the library instrumentation targets."""
    return _DEFAULT


def configure(*, enabled: bool | None = None) -> None:
    """Configure the default tracer (see ``Tracer.configure``). The first
    enable registers the compile listener (see module docstring)."""
    global _LISTENING
    _DEFAULT.configure(enabled=enabled)
    if _DEFAULT.enabled and not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _LISTENING = True


def enabled() -> bool:
    """Whether the default tracer records."""
    return _DEFAULT.enabled


def span(name: str, **args):
    """A span on the default tracer (see ``Tracer.span``)."""
    return _DEFAULT.span(name, **args)


def record(name: str, t0_ns: int, **args) -> None:
    """An interval event on the default tracer (see ``Tracer.record``)."""
    _DEFAULT.record(name, t0_ns, **args)


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
