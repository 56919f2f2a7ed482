"""qobs — host-side observability for the sketch stack (DESIGN.md §10).

Four parts, all strictly OUTSIDE jit (no module here may touch a traced
value — emissions are host Python, guarded by ``obs.trace.tracing_active``
wherever a caller might sit inside a traced region):

* ``obs.metrics`` — a process-local registry of counters, gauges, and
  log2-bucketed histograms (the paper's quantization idiom applied to
  telemetry) with namespaced snake_case names, per-series labels,
  delta/cumulative snapshots, and a no-op path when disabled.
* ``obs.trace``   — span-based stage tracing (route/push/seal/dispatch/
  retire/rotate/estimate/solve) with nesting via contextvars and Chrome
  trace-event JSON export loadable in Perfetto; while enabled each span is
  also a ``jax.profiler.TraceAnnotation``, so it sits on the profiler's
  host plane on the device trace's clock, and each XLA compile is
  recorded (``jax/compile``, counted in ``jax_compiles``) with the span
  that caused it.
* ``obs.health``  — sketch self-introspection over every container state
  (top-bin saturation, histogram occupancy, union-cache staleness,
  directory load, anytime-vs-MLE drift, CI width) behind one
  ``health_report`` with configurable warn thresholds.
* ``obs.export``  — Prometheus text-format and JSONL snapshot writers,
  wired into ``launch/train.py`` / ``launch/serve.py`` (``--obs-jsonl``,
  ``--obs-prom``) and the ``scripts/obs_dump.py`` CLI.
"""

from repro.obs import export, health, metrics, trace  # noqa: F401
from repro.obs.health import health_report  # noqa: F401
from repro.obs.metrics import default_registry  # noqa: F401
from repro.obs.trace import span  # noqa: F401
