"""Process-local metrics registry: counters, gauges, log2 histograms.

The naming contract (machine-checked by qlint's ``metric-names`` rule,
DESIGN.md §10): every metric is *declared* once, at module level, through
the module functions ``counter`` / ``gauge`` / ``histogram`` with a LITERAL
snake_case name unique across the repo — no stringly-typed ad-hoc
emissions. Call sites then emit through the returned handle, so the full
metric surface of the process is enumerable from the source alone.

Semantics:

* **Families and series.** A declaration creates a *family* (name, kind,
  help, label names). Emitting through ``family.labels(pipe="3")`` creates
  (memoizes) one *series* per label-value tuple — the Prometheus data
  model, which is how five monitor instances or N ingest pipelines share
  one declared name without colliding. A family with no label names has a
  single implicit series and the handle itself accepts ``inc``/``set``/
  ``observe``.
* **Histograms are log2-bucketed** — the same quantization idiom the
  sketch applies to register values (PAPER.md §4): bucket upper bounds are
  powers of two over a configurable exponent range, so a histogram costs a
  handful of ints however wide the value distribution is.
* **Snapshots are cumulative or delta.** ``snapshot()`` returns current
  values; ``snapshot(delta=True)`` returns the change since the *previous
  delta snapshot* (each series keeps its own baseline), which is what a
  scrape loop or a per-epoch report wants. ``reset()`` zeroes everything.
* **Disabled mode is a no-op path.** With ``enabled=False`` (constructor,
  ``configure``, or the ``QOBS_DISABLED`` env var for the default
  registry) every emission is one attribute load + branch and snapshots
  are empty. Components whose counters feed control flow must therefore
  keep them OUT of the registry (see ``sketchstream/ingest.py``'s local
  fallback).
* **Strictly outside jit.** Values are host Python numbers; handles must
  never receive traced values. Callers that may sit under a ``jax.jit``
  trace guard emissions with ``obs.trace.tracing_active()`` (the
  monitor layer does this for you).
"""

from __future__ import annotations

import os
import re
import threading

_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")
KINDS = ("counter", "gauge", "histogram")

# Default log2 bucket exponent range: 2^-10 (~1 ms if seconds) .. 2^20 (~1M
# if counts). Histogram declarations override per-metric.
DEFAULT_LOW_EXP = -10
DEFAULT_HIGH_EXP = 20


def _check_name(name: str, what: str = "metric") -> None:
    if not isinstance(name, str) or not _NAME_RE.match(name):
        raise ValueError(
            f"{what} name {name!r} must be snake_case "
            "(lowercase letters, digits, underscores; starts with a letter)"
        )


class Series:
    """One (family, label-values) time series: a mutable host-side value.

    Counters/gauges hold one number; histograms hold per-bucket counts plus
    a running sum and count. All mutation methods are cheap no-ops while
    the owning registry is disabled.
    """

    __slots__ = ("_reg", "kind", "labels", "value", "buckets", "sum", "count",
                 "_d_value", "_d_buckets", "_d_sum", "_d_count", "_bounds")

    def __init__(self, reg: "Registry", kind: str, labels: dict, bounds=None):
        self._reg = reg
        self.kind = kind
        self.labels = labels
        self.value = 0
        self._bounds = bounds  # histogram bucket upper bounds (powers of 2)
        self.buckets = [0] * (len(bounds) + 1) if bounds is not None else None
        self.sum = 0.0
        self.count = 0
        # Baselines of the previous delta snapshot.
        self._d_value = 0
        self._d_buckets = list(self.buckets) if self.buckets else None
        self._d_sum = 0.0
        self._d_count = 0

    # -- emission ---------------------------------------------------------

    def inc(self, n=1) -> None:
        """Counter increment by ``n`` (must be >= 0)."""
        if not self._reg._enabled:
            return
        if n < 0:
            raise ValueError(f"counter increment must be >= 0, got {n}")
        self.value += n

    def set(self, v) -> None:
        """Gauge assignment (last-write-wins)."""
        if not self._reg._enabled:
            return
        self.value = v

    def set_max(self, v) -> None:
        """Gauge high-water update: keep the max of the current value and
        ``v`` (the ``max_in_flight`` idiom)."""
        if not self._reg._enabled:
            return
        if v > self.value:
            self.value = v

    def observe(self, v) -> None:
        """Histogram observation: lands in the first log2 bucket whose
        upper bound is >= v (the overflow bucket catches the rest)."""
        if not self._reg._enabled:
            return
        i = 0
        bounds = self._bounds
        while i < len(bounds) and v > bounds[i]:
            i += 1
        self.buckets[i] += 1
        self.sum += v
        self.count += 1

    # -- reads ------------------------------------------------------------

    def read(self, delta: bool = False):
        """Snapshot payload of this series; ``delta=True`` additionally
        advances this series' delta baseline."""
        if self.kind == "histogram":
            if delta:
                out = {
                    "buckets": [a - b for a, b in zip(self.buckets, self._d_buckets)],
                    "sum": self.sum - self._d_sum,
                    "count": self.count - self._d_count,
                }
                self._d_buckets = list(self.buckets)
                self._d_sum, self._d_count = self.sum, self.count
            else:
                out = {
                    "buckets": list(self.buckets),
                    "sum": self.sum,
                    "count": self.count,
                }
            out["le"] = [float(b) for b in self._bounds] + [float("inf")]
            return out
        if delta and self.kind == "counter":
            out = self.value - self._d_value
            self._d_value = self.value
            return out
        if delta and self.kind == "gauge":
            # Gauges are point-in-time: a delta snapshot reports the current
            # value (set_max users re-arm their high-water with reset()).
            return self.value
        return self.value

    def reset(self) -> None:
        """Zero the series and its delta baseline."""
        self.value = 0
        self._d_value = 0
        if self.buckets is not None:
            self.buckets = [0] * len(self.buckets)
            self._d_buckets = list(self.buckets)
        self.sum = self._d_sum = 0.0
        self.count = self._d_count = 0


class Metric:
    """One declared family: name, kind, help text, label names, series."""

    def __init__(self, reg: "Registry", name: str, kind: str, help: str,
                 label_names: tuple, bounds=None):
        self.registry = reg
        self.name = name
        self.kind = kind
        self.help = help
        self.label_names = label_names
        self._bounds = bounds
        self._series: dict[tuple, Series] = {}
        if not label_names:
            self._default = self._make(())
        else:
            self._default = None

    def _make(self, key: tuple) -> Series:
        s = Series(self.registry, self.kind,
                   dict(zip(self.label_names, key)), self._bounds)
        self._series[key] = s
        return s

    def labels(self, **kv) -> Series:
        """The series for one label-value assignment (memoized). Every
        declared label name must be given; values are stringified."""
        if set(kv) != set(self.label_names):
            raise ValueError(
                f"metric {self.name!r} takes labels {self.label_names}, "
                f"got {tuple(sorted(kv))}"
            )
        key = tuple(str(kv[n]) for n in self.label_names)
        s = self._series.get(key)
        return s if s is not None else self._make(key)

    def series(self) -> list[Series]:
        """Every live series of this family, declaration-ordered."""
        return list(self._series.values())

    # Unlabeled convenience: delegate to the implicit series.
    def inc(self, n=1) -> None:
        """Counter increment on the label-less series."""
        self._default.inc(n)

    def set(self, v) -> None:
        """Gauge assignment on the label-less series."""
        self._default.set(v)

    def set_max(self, v) -> None:
        """Gauge high-water update on the label-less series."""
        self._default.set_max(v)

    def observe(self, v) -> None:
        """Histogram observation on the label-less series."""
        self._default.observe(v)

    @property
    def value(self):
        """Current value of the label-less series."""
        return self._default.value


def render_series_name(name: str, labels: dict) -> str:
    """Prometheus-style rendered series id: ``name{a="x",b="y"}``."""
    if not labels:
        return name
    inner = ",".join(f'{k}="{v}"' for k, v in labels.items())
    return f"{name}{{{inner}}}"


class Registry:
    """A process-local set of metric families (see module docstring).

    Thread-safe for declaration; emission is plain attribute mutation (the
    GIL makes int += atomic enough for telemetry — these are not
    correctness counters).
    """

    def __init__(self, enabled: bool = True):
        self._enabled = bool(enabled)
        self._families: dict[str, Metric] = {}
        self._lock = threading.Lock()

    # -- configuration ----------------------------------------------------

    @property
    def enabled(self) -> bool:
        """Whether emissions record and snapshots report."""
        return self._enabled

    def configure(self, *, enabled: bool) -> None:
        """Toggle the registry. Disabling mid-process freezes values in
        place (emissions no-op); re-enabling resumes from them."""
        self._enabled = bool(enabled)

    # -- declaration ------------------------------------------------------

    def _declare(self, name, kind, help, labels, bounds=None) -> Metric:
        _check_name(name)
        for ln in labels:
            _check_name(ln, "label")
        labels = tuple(labels)
        with self._lock:
            existing = self._families.get(name)
            if existing is not None:
                if existing.kind != kind or existing.label_names != labels:
                    raise ValueError(
                        f"metric {name!r} already declared as "
                        f"{existing.kind}{existing.label_names}, cannot "
                        f"redeclare as {kind}{labels}"
                    )
                return existing
            fam = Metric(self, name, kind, help, labels, bounds)
            self._families[name] = fam
            return fam

    def counter(self, name: str, help: str = "", labels: tuple = ()) -> Metric:
        """Declare (or fetch) a monotone counter family."""
        return self._declare(name, "counter", help, labels)

    def gauge(self, name: str, help: str = "", labels: tuple = ()) -> Metric:
        """Declare (or fetch) a last-write-wins gauge family."""
        return self._declare(name, "gauge", help, labels)

    def histogram(self, name: str, help: str = "", labels: tuple = (),
                  low_exp: int = DEFAULT_LOW_EXP,
                  high_exp: int = DEFAULT_HIGH_EXP) -> Metric:
        """Declare (or fetch) a log2-bucketed histogram family with bucket
        upper bounds ``2^low_exp .. 2^high_exp`` plus an overflow bucket."""
        if high_exp <= low_exp:
            raise ValueError("histogram needs high_exp > low_exp")
        bounds = [2.0 ** e for e in range(low_exp, high_exp + 1)]
        return self._declare(name, "histogram", help, labels, bounds)

    # -- introspection ----------------------------------------------------

    def families(self) -> list[Metric]:
        """Every declared family, declaration-ordered."""
        return list(self._families.values())

    def get(self, name: str) -> Metric | None:
        """Family by name (None if undeclared)."""
        return self._families.get(name)

    def snapshot(self, delta: bool = False) -> dict:
        """``{rendered series name: value}`` over every live series.

        Counters/gauges map to numbers; histograms to ``{"buckets": [...],
        "le": [...], "sum": s, "count": c}``. ``delta=True`` reports change
        since the previous delta snapshot and advances each series'
        baseline. Disabled registries snapshot empty.
        """
        if not self._enabled:
            return {}
        out = {}
        for fam in self._families.values():
            for s in fam.series():
                out[render_series_name(fam.name, s.labels)] = s.read(delta)
        return out

    def reset(self) -> None:
        """Zero every series and every delta baseline."""
        for fam in self._families.values():
            for s in fam.series():
                s.reset()


_DEFAULT = Registry(enabled=not os.environ.get("QOBS_DISABLED"))


def default_registry() -> Registry:
    """The process-default registry every library declaration lands in."""
    return _DEFAULT


def configure(*, enabled: bool) -> None:
    """Toggle the default registry (see ``Registry.configure``)."""
    _DEFAULT.configure(enabled=enabled)


def enabled() -> bool:
    """Whether the default registry records emissions."""
    return _DEFAULT.enabled


def counter(name: str, help: str = "", labels: tuple = ()) -> Metric:
    """Declare a counter on the default registry (the sanctioned, qlint-
    checked declaration point — literal snake_case name, unique repo-wide)."""
    return _DEFAULT.counter(name, help, labels)


def gauge(name: str, help: str = "", labels: tuple = ()) -> Metric:
    """Declare a gauge on the default registry (qlint-checked)."""
    return _DEFAULT.gauge(name, help, labels)


def histogram(name: str, help: str = "", labels: tuple = (),
              low_exp: int = DEFAULT_LOW_EXP,
              high_exp: int = DEFAULT_HIGH_EXP) -> Metric:
    """Declare a log2 histogram on the default registry (qlint-checked)."""
    return _DEFAULT.histogram(name, help, labels, low_exp, high_exp)


def snapshot(delta: bool = False) -> dict:
    """Snapshot the default registry (see ``Registry.snapshot``)."""
    return _DEFAULT.snapshot(delta)


def reset() -> None:
    """Zero the default registry."""
    return _DEFAULT.reset()
