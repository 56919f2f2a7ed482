"""Streaming ingest: micro-batched, double-buffered, donated device updates.

Every benchmark before this layer measured synchronous, already-batched
updates — the host blocks on each device step, so the repo had no honest
number for what one host sustains under unbounded traffic (the ROADMAP
"heavy traffic" north star; QSketch's O(1)-per-element claim, arXiv
2406.19143 §5, is only interesting if ingest keeps up). This module closes
the gap with a classic decoupled-pipeline structure (cf. the related repos'
issue-queue/ROB stages, structurally — not their code):

* **Staging (host).** (key, id, weight) triples accumulate into fixed-shape
  preallocated staging buffers — two of them, alternated per batch, so the
  device transfer of batch *i* never races the host filling batch *i+1*
  ("pinned" in the CUDA sense degenerates to ordinary page-locked-by-malloc
  numpy memory on the CPU backend; the double-buffer contract is what
  carries to accelerators).
* **Transfer + update (device).** A sealed batch is shipped as a freshly
  OWNED copy (CPU jax may defer or zero-copy-alias host bytes, and the
  staging buffer is rewritten on wrap-around — the copy is the transfer
  hop) and folded in by a state-DONATING update. The Dyn route runs it as
  two executables — a read-only plan and a scatter-only commit with
  ``donate_argnums`` on the container state (core/dyn_array.py,
  DESIGN.md §8.8) — so the scatters reuse the int8[K, m] + int32[K, 2^b]
  buffers in place instead of copying ~1 GiB per batch at K = 2^20.
  Dispatch is asynchronous — the host returns to staging while the device
  works, which is where the pipelining (and the sustained-Mops headline,
  benchmarks/ingest.py) comes from.
* **Backpressure.** In-flight batches are tracked by tiny per-batch tickets
  (scalars data-dependent on the updated state). When ``queue_depth``
  batches are unretired, ``policy="block"`` waits for the oldest (counting
  stall time), ``policy="drop"`` sheds the sealed batch (counting drops) —
  the load-shedding mode a real collector runs at saturation.
* **Retire barrier.** ``rotate()`` / ``barrier()`` first flush the partial
  staging buffer, then wait until every earlier batch has landed, and only
  then run the (donated) ``WindowArray.rotate`` — so an element pushed
  before the rotate is IN the pre-rotation epoch, an element pushed after
  is in the next one, exactly the synchronous ordering. Eviction clocks
  (``key_directory.evict_older_than``) hang off the same barrier.

Bit-identity: the pipeline partitions the push stream into the same
micro-batches a synchronous loop over ``update_batch`` would see (FIFO
fill, deterministic boundaries), calls the same jitted math, and orders
rotations with the barrier — so every state leaf is bit-identical to the
synchronous element-log oracle (tests/test_ingest.py, including a forced-
backpressure schedule; scatter-max order-insensitivity covers within-batch
permutations). Telemetry counters surface through ``metrics()`` in the
monitor-layer naming style.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import (
    dyn_array,
    key_directory,
    sharded_dyn_array,
    sharded_window_array,
    sharding,
    window_array,
)
from repro.core.types import SketchConfig
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.sketchstream import monitor

POLICIES = ("block", "drop")

# Declared metric families (one per counter, labeled by pipeline instance —
# the Prometheus data model lets N concurrent pipelines share each name).
_M_PUSHED = obs_metrics.counter(
    "ingest_elements_pushed", "elements accepted into staging", labels=("pipe",))
_M_DROPPED = obs_metrics.counter(
    "ingest_elements_dropped", "elements shed by the drop policy", labels=("pipe",))
_M_BATCHES = obs_metrics.counter(
    "ingest_batches", "micro-batches dispatched to the device", labels=("pipe",))
_M_PARTIAL = obs_metrics.counter(
    "ingest_partial_batches", "mask-padded dispatches (flush/rotate seals)",
    labels=("pipe",))
_M_STALLS = obs_metrics.counter(
    "ingest_stalls", "block-policy waits on a full queue", labels=("pipe",))
_M_STALL_S = obs_metrics.counter(
    "ingest_stall_s", "total seconds spent in backpressure waits", labels=("pipe",))
_M_MAX_IN_FLIGHT = obs_metrics.gauge(
    "ingest_max_in_flight", "high-water mark of the retire queue", labels=("pipe",))
_M_ROTATIONS = obs_metrics.counter(
    "ingest_rotations", "epoch rotations behind the retire barrier", labels=("pipe",))
_M_BARRIERS = obs_metrics.counter(
    "ingest_barriers", "retire barriers", labels=("pipe",))
_M_IN_FLIGHT = obs_metrics.gauge(
    "ingest_in_flight", "unretired in-flight batches", labels=("pipe",))
_M_SHARD_EVENTS = obs_metrics.counter(
    "ingest_shard_events_total",
    "dispatched events by the shard that owns their row (sharded pipelines)",
    labels=("pipe", "shard"))

_STAT_FAMILIES = {
    "pushed": _M_PUSHED,
    "dropped": _M_DROPPED,
    "batches": _M_BATCHES,
    "partial_batches": _M_PARTIAL,
    "stalls": _M_STALLS,
    "stall_s": _M_STALL_S,
    "max_in_flight": _M_MAX_IN_FLIGHT,
    "rotations": _M_ROTATIONS,
    "barriers": _M_BARRIERS,
}

_PIPE_SEQ = itertools.count()


@dataclasses.dataclass(frozen=True)
class IngestConfig:
    """Geometry + backpressure policy of an ingest pipeline.

    batch_size: elements per micro-batch (the fixed staging/device shape —
      one compiled executable serves every batch, partial flushes included
      via mask padding).
    queue_depth: max unretired in-flight batches before backpressure.
    policy: "block" (wait for the oldest in-flight batch; lossless) or
      "drop" (shed the sealed batch; lossy load-shedding — dropped elements
      are counted, never silently lost).
    """

    batch_size: int = 32768
    queue_depth: int = 4
    policy: str = "block"

    def __post_init__(self):
        if self.batch_size < 1:
            raise ValueError("ingest batch_size must be >= 1")
        if self.queue_depth < 1:
            raise ValueError("ingest queue_depth must be >= 1")
        if self.policy not in POLICIES:
            raise ValueError(f"ingest policy must be one of {POLICIES}")


class IngestStats:
    """Mutable telemetry counters of one pipeline (read via ``metrics()``).

    Fields: ``pushed`` (elements accepted into staging), ``dropped`` (shed
    by the drop policy), ``batches`` (micro-batches dispatched),
    ``partial_batches`` (mask-padded flush/rotate seals), ``stalls`` /
    ``stall_s`` (block-policy waits and their total seconds),
    ``max_in_flight`` (retire-queue high-water mark), ``rotations``,
    ``barriers``. All readable and assignable as plain attributes.

    Storage is dual-backend: when the default obs registry is enabled at
    construction, every field lives in a registry series under its declared
    ``ingest_*`` family (labeled ``pipe=<instance>``), so exporters see
    pipeline counters for free; when disabled, fields fall back to plain
    locals — ingest counters feed CONTROL FLOW (rotation cadence in
    ``benchmarks/ingest.py``), so unlike optional telemetry they must keep
    counting with observability off.

    Lifetime semantics (the PR 9 fix): counters no longer accumulate
    forever across runs — construction resets this instance's series, and
    ``snapshot(delta=True)`` / ``reset()`` give interval reads and explicit
    re-arming (the ``max_in_flight`` high-water and ``stall_s`` total are
    per-lifetime, not per-process).

    A pipe over ``shards`` row shards (the sharded fronts) also keeps
    ``shard_events``: dispatched events by the shard that owns their row,
    the ``ingest_shard_events_total{pipe, shard}`` family, and a
    ``shard_events`` list in snapshots. It is telemetry only, added while
    the registry or the tracer records (``add_shard_events``).
    """

    FIELDS = tuple(_STAT_FAMILIES)

    def __init__(self, pipe: str | None = None, shards: int = 0):
        self.pipe = str(next(_PIPE_SEQ)) if pipe is None else str(pipe)
        self.shards = int(shards)
        if obs_metrics.default_registry().enabled:
            self._series = {
                f: fam.labels(pipe=self.pipe) for f, fam in _STAT_FAMILIES.items()
            }
            self._shard_series = [
                _M_SHARD_EVENTS.labels(pipe=self.pipe, shard=i) for i in range(self.shards)
            ]
        else:
            self._series = None
        # A reused label (explicit pipe= names, or a restarted process
        # registry) must not inherit the previous lifetime's counts.
        self.reset()

    @property
    def shard_events(self) -> list[int]:
        """Events dispatched so far, by owning shard."""
        if self._series is not None:
            return [s.value for s in self._shard_series]
        return self._local_shards.tolist()

    def add_shard_events(self, counts: np.ndarray) -> None:
        """Add one micro-batch's events by owning shard."""
        if self._series is not None:
            for s, c in zip(self._shard_series, counts):
                s.inc(int(c))
        else:
            self._local_shards += counts

    def snapshot(self, delta: bool = False) -> dict:
        """``{field: value}``; ``delta=True`` reports change since the
        previous delta snapshot and advances the baseline."""
        if self._series is not None:
            out = {f: s.read(delta) for f, s in self._series.items()}
            shards = [s.read(delta) for s in self._shard_series]
        elif delta:
            out = {f: self._local[f] - self._delta[f] for f in self.FIELDS}
            # Gauge semantics match the registry backend: report current.
            out["max_in_flight"] = self._local["max_in_flight"]
            self._delta = dict(self._local)
            shards = (self._local_shards - self._delta_shards).tolist()
            self._delta_shards = self._local_shards.copy()
        else:
            out = dict(self._local)
            shards = self._local_shards.tolist()
        if self.shards:
            out["shard_events"] = shards
        return out

    def reset(self) -> None:
        """Zero every counter, the high-water mark, and delta baselines."""
        if self._series is not None:
            for s in (*self._series.values(), *self._shard_series):
                s.reset()
        else:
            self._local = dict.fromkeys(self.FIELDS, 0)
            self._local["stall_s"] = 0.0
            self._delta = dict(self._local)
            self._local_shards = np.zeros(self.shards, np.int64)
            self._delta_shards = self._local_shards.copy()


def _stat_property(field: str) -> property:
    def get(self):
        if self._series is not None:
            return self._series[field].value
        return self._local[field]

    def set_(self, v):
        if self._series is not None:
            self._series[field].value = v
        else:
            self._local[field] = v

    return property(get, set_, doc=f"the ``{field}`` counter (see class doc)")


for _f in IngestStats.FIELDS:
    setattr(IngestStats, _f, _stat_property(_f))
del _f


class IngestPipeline:
    """Micro-batching ingest front of one sketch container.

    Built by the module's engine constructors (``dyn_pipeline``,
    ``window_pipeline``, ``sharded_dyn_pipeline``,
    ``sharded_window_pipeline``) — they close the container config (and
    mesh) into a jitted, state-donating ``update_fn(state, keys, ids, w,
    mask) -> (state, ticket)`` plus an optional donated ``rotate_fn``.

    Host API: ``push`` (accumulate + auto-dispatch), ``flush`` (seal the
    partial batch), ``barrier`` (flush + wait for every in-flight batch),
    ``rotate`` (barrier + donated ring rotation), ``result`` (barrier +
    the settled state), ``metrics`` (telemetry counters). The internally
    threaded state is donated batch-to-batch: never retain references to
    ``.state`` across a push.

    On a ``mesh`` (the sharded constructors, with the container's K rows
    split over ``axis``) each sealed micro-batch is placed replicated on
    every device of the mesh. While the registry or the tracer records,
    its events are counted by the shard that owns their row:
    ``stats.shard_events`` and, while tracing, one ``ingest/shard_rows``
    event per micro-batch with the per-shard ``counts``.
    """

    def __init__(self, icfg: IngestConfig, state, update_fn, *, rotate_fn=None,
                 name: str | None = None, mesh=None, k: int | None = None,
                 axis: str = sharding.AXIS):
        self.icfg = icfg
        self._state = state
        self._update = update_fn
        self._rotate = rotate_fn
        # None: the default device, uncommitted (single-device fronts).
        self._placement = None if mesh is None else NamedSharding(mesh, P())
        self._k = k
        self.stats = IngestStats(
            pipe=name, shards=0 if mesh is None else sharding.num_shards(mesh, axis)
        )
        b = icfg.batch_size
        self._staging = [
            {
                "keys": np.zeros(b, np.int32),
                "ids": np.zeros(b, np.uint32),
                "w": np.ones(b, np.float32),
                "mask": np.zeros(b, bool),
            }
            for _ in range(2)
        ]
        self._cur = 0  # which staging buffer is filling
        self._fill = 0  # elements in the filling buffer
        # perf_counter_ns when the filling buffer took its first element,
        # kept only while tracing (the ``ingest/fill`` event at its seal).
        self._fill_t0 = None
        self._inflight: list = []  # retire queue of per-batch tickets
        # Readiness probe, overridable by tests to force backpressure
        # schedules deterministically.
        self._ready = lambda t: bool(t.is_ready())

    @property
    def state(self):
        """The container state as of the last dispatched batch (device-async;
        staging may still hold unsealed elements — use ``result()`` for the
        settled value)."""
        return self._state

    def push(self, keys, ids, weights=None) -> None:
        """Accept a host batch of (key, id, weight) triples, dispatching a
        micro-batch every time the staging buffer fills.

        keys: int array-like — dense slot indices in [0, K).
        ids: uint32 array-like element ids (64-bit streams pre-split their
          hi word into the key-directory layer; the staging lane is 32-bit).
        weights: float array-like, default 1.0 (unweighted streams).
        """
        keys = np.asarray(keys, np.int32).ravel()
        ids = np.asarray(ids, np.uint32).ravel()
        if weights is None:
            w = np.ones(keys.shape, np.float32)
        else:
            w = np.asarray(weights, np.float32).ravel()
        if not (keys.shape == ids.shape == w.shape):
            raise ValueError(
                f"push needs equal-length keys/ids/weights, got "
                f"{keys.shape}/{ids.shape}/{w.shape}"
            )
        self.stats.pushed += len(keys)
        b = self.icfg.batch_size
        off = 0
        tracing = obs_trace.enabled()
        with obs_trace.span("ingest/push", n=len(keys)):
            while off < len(keys):
                if tracing and not self._fill:
                    self._fill_t0 = time.perf_counter_ns()
                take = min(b - self._fill, len(keys) - off)
                buf = self._staging[self._cur]
                sl = slice(self._fill, self._fill + take)
                buf["keys"][sl] = keys[off : off + take]
                buf["ids"][sl] = ids[off : off + take]
                buf["w"][sl] = w[off : off + take]
                buf["mask"][sl] = True
                self._fill += take
                off += take
                if self._fill == b:
                    self._dispatch()

    def flush(self) -> None:
        """Seal and dispatch the partial staging buffer (mask-padded to the
        fixed batch shape — padding rows are no-ops by the mask contract)."""
        if self._fill:
            self._dispatch(partial=True)

    def barrier(self) -> None:
        """Flush, then wait until every dispatched batch has retired.

        This is the in-order retire barrier: after it returns, the threaded
        state reflects every element ever pushed (minus counted drops), and
        host-side consumers (rotation, eviction, checkpointing) may act on
        it without racing in-flight device work.
        """
        self.flush()
        with obs_trace.span("ingest/retire", in_flight=len(self._inflight)):
            if self._inflight:
                jax.block_until_ready(self._inflight)
                self._inflight.clear()
            jax.block_until_ready(jax.tree.leaves(self._state))
        self.stats.barriers += 1

    def rotate(self) -> None:
        """Close the container's current epoch behind the retire barrier.

        Flush + barrier first, so every earlier element lands in the
        pre-rotation epoch and the donated ``rotate_fn`` never aliases a
        buffer an in-flight update still reads — then rotate. Elements
        pushed afterwards open the next epoch: the synchronous ordering,
        by construction.
        """
        if self._rotate is None:
            raise ValueError("this pipeline fronts a container without rotate()")
        self.barrier()
        with obs_trace.span("ingest/rotate"):
            self._state = self._rotate(self._state)
        self.stats.rotations += 1

    def result(self):
        """Barrier, then return the settled container state."""
        self.barrier()
        return self._state

    def metrics(self) -> dict:
        """Telemetry counters in the monitor-layer style (queue depth, stall
        time, drops — the knobs an operator watches under load). Reading
        also refreshes this pipe's ``ingest_in_flight`` gauge, so registry
        exporters see the live queue depth."""
        s = self.stats
        if obs_metrics.enabled():
            _M_IN_FLIGHT.labels(pipe=s.pipe).set(len(self._inflight))
        return {
            "ingest_elements_pushed": s.pushed,
            "ingest_elements_dropped": s.dropped,
            "ingest_batches": s.batches,
            "ingest_partial_batches": s.partial_batches,
            "ingest_stalls": s.stalls,
            "ingest_stall_s": float(s.stall_s),
            "ingest_in_flight": len(self._inflight),
            "ingest_max_in_flight": s.max_in_flight,
            "ingest_rotations": s.rotations,
            "ingest_barriers": s.barriers,
        }

    # ------------------------------------------------------------------ #

    def _reap(self) -> None:
        """Retire completed batches from the head of the in-flight queue
        (in order — a later ticket never retires before an earlier one)."""
        while self._inflight and self._ready(self._inflight[0]):
            self._inflight.pop(0)

    def _admit(self) -> bool:
        """Apply backpressure; True iff the sealed batch may dispatch."""
        self._reap()
        while len(self._inflight) >= self.icfg.queue_depth:
            if self.icfg.policy == "drop":
                return False
            t0 = time.perf_counter()
            with obs_trace.span("ingest/stall", in_flight=len(self._inflight)):
                jax.block_until_ready(self._inflight.pop(0))
            self.stats.stall_s += time.perf_counter() - t0
            self.stats.stalls += 1
            self._reap()
        return True

    def _dispatch(self, partial: bool = False) -> None:
        n, buf = self._fill, self._staging[self._cur]
        if self._fill_t0 is not None:
            # Staging wait: first element in to seal, across pushes.
            obs_trace.record("ingest/fill", self._fill_t0, n=n, partial=partial)
            self._fill_t0 = None
        # Swap staging buffers BEFORE transfer: the next push fills the other
        # buffer while this one's bytes are (asynchronously) consumed.
        self._cur ^= 1
        self._fill = 0
        if not self._admit():
            self.stats.dropped += n
            buf["mask"][:] = False
            return
        if self.stats.shards and (obs_metrics.enabled() or obs_trace.enabled()):
            self._count_shards(buf["keys"][:n])
        # Hand jax freshly-OWNED copies: the CPU backend may defer (or
        # zero-copy alias) the host bytes passed to device_put until the
        # consuming executable runs, and this buffer is mutated again as
        # soon as push() wraps around to it — with queue_depth > 2 that is
        # before the in-flight batch is guaranteed to have read its inputs.
        # The memcpy IS the staging->transfer hop; jax holds the only
        # reference afterwards, so later staging writes can never race it.
        # On a mesh the copy goes to every device here, replicated, rather
        # than inside the update's dispatch.
        with obs_trace.span("ingest/seal", n=n, partial=partial):
            host = [buf[f].copy() for f in ("keys", "ids", "w", "mask")]
            keys, ids, w, mask = jax.device_put(host, self._placement)
            buf["mask"][:] = False  # pre-cleared for this buffer's next fill
        with obs_trace.span("ingest/dispatch", n=n):
            self._state, ticket = self._update(self._state, keys, ids, w, mask)
        self._inflight.append(ticket)
        self.stats.batches += 1
        self.stats.partial_batches += bool(partial)
        self.stats.max_in_flight = max(self.stats.max_in_flight, len(self._inflight))

    def _count_shards(self, keys: np.ndarray) -> None:
        """Events of one sealed micro-batch by the shard that owns their
        row: a host bincount of slots already on the host."""
        t0 = time.perf_counter_ns()
        counts = sharding.shard_counts(keys, self._k, self.stats.shards)
        self.stats.add_shard_events(counts)
        obs_trace.record("ingest/shard_rows", t0, counts=counts.tolist())


def _ticketed(update):
    """Wrap a pure state update into the pipeline's (state, ticket) form:
    the ticket is a scalar data-dependent on the new state, so its
    ``is_ready()`` / ``block_until_ready`` observe the whole batch having
    landed without holding a reference to any (donated) state buffer."""

    def fn(state, keys, ids, w, mask):
        out = update(state, keys, ids, w, mask)
        return out, jax.tree.leaves(out)[0].ravel()[0]

    return fn


@functools.lru_cache(maxsize=32)
def _dyn_update_fn(cfg: SketchConfig, use_kernel: bool, interpret: bool | None = None):
    if use_kernel:
        from repro.kernels import ops

        def upd(st, keys, ids, w, mask):
            return ops.dyn_array_update_op(
                cfg, st, keys, ids, w, mask=mask, interpret=interpret
            )

        return jax.jit(_ticketed(upd), donate_argnums=(0,))

    # The jnp route stays OUTSIDE any enclosing jit: donate=True runs the
    # update as two executables (read-only plan + scatter-only donating
    # commit, core/dyn_array.py), each timed on its own in a device trace.
    # The ticket is a third, O(1) dispatch chained on the committed state:
    # one element of the commit's output (an eager ``ravel`` would copy the
    # whole register plane into a new array every batch).
    def fn(st, keys, ids, w, mask):
        out = dyn_array.update_batch(cfg, st, keys, ids, w, mask, donate=True)
        return out, out.chats[0]

    return fn


def dyn_pipeline(
    cfg: SketchConfig, state, icfg: IngestConfig = IngestConfig(),
    *, use_kernel: bool = False, interpret: bool | None = None,
    name: str | None = None,
) -> IngestPipeline:
    """Ingest front of a DynArray: donated fused keyed updates, no rotate.

    ``use_kernel=True`` routes the q_R stage through the Pallas kernel
    (``kernels/ops.dyn_array_update_op``) inside the same donating jit;
    ``interpret`` is passed to it (None: interpret off the TPU backend).
    The jitted update closure is cached per cfg, so pipelines over the
    same geometry share one compiled executable.
    """
    return IngestPipeline(
        icfg, state, _dyn_update_fn(cfg, use_kernel, interpret), name=name
    )


@functools.lru_cache(maxsize=32)
def _window_update_fn(cfg: SketchConfig):
    def upd(st, keys, ids, w, mask):
        return window_array._update_batch_impl(cfg, st, keys, ids, w, mask)

    return jax.jit(_ticketed(upd), donate_argnums=(0,))


def window_pipeline(
    cfg: SketchConfig, state, icfg: IngestConfig = IngestConfig(),
    *, name: str | None = None,
) -> IngestPipeline:
    """Ingest front of a WindowArray: donated epoch+union updates, with
    ``rotate()`` running the donated ring rotation behind the retire
    barrier."""
    rot = lambda st: window_array.rotate(cfg, st, donate=True)
    return IngestPipeline(icfg, state, _window_update_fn(cfg), rotate_fn=rot, name=name)


@functools.lru_cache(maxsize=32)
def _sharded_dyn_update_fn(cfg: SketchConfig, mesh, axis: str):
    def upd(st, keys, ids, w, mask):
        return sharded_dyn_array.update_batch(
            cfg, mesh, st, keys, ids, w, mask=mask, axis=axis
        )

    return jax.jit(_ticketed(upd), donate_argnums=(0,))


def sharded_dyn_pipeline(
    cfg: SketchConfig, mesh, state, icfg: IngestConfig = IngestConfig(),
    *, axis: str = sharding.AXIS, name: str | None = None,
) -> IngestPipeline:
    """Ingest front of a ShardedDynArray: the staging batch, sealed
    replicated onto the mesh, is hash-routed shard-locally inside one
    donating jit per micro-batch."""
    return IngestPipeline(
        icfg, state, _sharded_dyn_update_fn(cfg, mesh, axis), name=name,
        mesh=mesh, k=sharded_dyn_array.num_sketches(state), axis=axis,
    )


@functools.lru_cache(maxsize=32)
def _sharded_window_update_fn(cfg: SketchConfig, mesh, axis: str):
    def upd(st, keys, ids, w, mask):
        return sharded_window_array.update_batch(
            cfg, mesh, st, keys, ids, w, mask=mask, axis=axis
        )

    return jax.jit(_ticketed(upd), donate_argnums=(0,))


def sharded_window_pipeline(
    cfg: SketchConfig, mesh, state, icfg: IngestConfig = IngestConfig(),
    *, axis: str = sharding.AXIS, name: str | None = None,
) -> IngestPipeline:
    """Ingest front of a ShardedWindowArray: the staging batch, sealed
    replicated onto the mesh, feeds hash-routed donated updates, plus the
    donated shard-local ring rotation behind the retire barrier."""
    rot = lambda st: sharded_window_array.rotate(cfg, mesh, st, axis=axis, donate=True)
    return IngestPipeline(
        icfg, state, _sharded_window_update_fn(cfg, mesh, axis), rotate_fn=rot,
        name=name, mesh=mesh, k=sharded_window_array.num_sketches(state), axis=axis,
    )


class TenantWindowIngest:
    """Sparse-tenant window telemetry through the ingest pipeline.

    The monitor layer's WindowMonitor routes + updates synchronously inside
    the caller's step; this front does the routing host-synchronously (the
    directory is tiny) but streams the heavy per-tenant window updates
    through an ``IngestPipeline`` — the ``--ingest`` mode of
    ``launch/train.py``. ``rotate()`` runs the ring rotation AND directory
    aging behind the retire barrier, keeping eviction ordered after every
    earlier element, exactly as the synchronous monitor.
    """

    def __init__(
        self,
        cfg: SketchConfig,
        dcfg: key_directory.DirectoryConfig,
        n_epochs: int,
        icfg: IngestConfig = IngestConfig(),
        *,
        mesh=None,
        axis: str = sharding.AXIS,
        evict_after: int = 0,
    ):
        self.cfg, self.dcfg = cfg, dcfg
        self.evict_after = int(evict_after)
        self.directory = key_directory.init(dcfg)
        self._epoch = 0
        if mesh is None:
            self.pipe = window_pipeline(
                cfg, window_array.init(cfg, dcfg.capacity, n_epochs), icfg
            )
        else:
            self.pipe = sharded_window_pipeline(
                cfg, mesh,
                sharded_window_array.init(cfg, dcfg.capacity, n_epochs, mesh, axis),
                icfg, axis=axis,
            )

    def push(self, tenant_keys, ids, weights=None, mask=None) -> None:
        """Route sparse 64-bit tenant ids (uint32 array or (lo, hi) pair)
        through the key directory, then stage the slot-keyed elements.
        Masked elements are filtered host-side before staging (identical
        results to in-batch masking by the mask no-op contract)."""
        with obs_trace.span("ingest/route"):
            slots, self.directory = key_directory.route(
                self.dcfg, self.directory, tenant_keys, mask=mask,
                epoch=jnp.int32(self._epoch),
            )
        # The host read of the slots waits for everything queued on the
        # device ahead of the route (earlier updates, a rotation).
        with obs_trace.span("ingest/route_wait"):
            slots = np.asarray(slots)
        slots = slots.ravel()
        ids = np.asarray(ids).ravel()
        w = None if weights is None else np.asarray(weights).ravel()
        if mask is not None:
            keep = np.asarray(mask).ravel()
            slots, ids = slots[keep], ids[keep]
            w = None if w is None else w[keep]
        self.pipe.push(slots, ids, w)

    def rotate(self) -> None:
        """Barrier + ring rotation + cold-fingerprint aging, in that order."""
        self.pipe.rotate()
        self._epoch += 1
        if self.evict_after:
            self.directory, _ = key_directory.evict_older_than(
                self.dcfg, self.directory,
                jnp.int32(self._epoch - self.evict_after),
            )

    def result(self):
        """Retire every in-flight batch; the settled window state."""
        return self.pipe.result()

    def metrics(self) -> dict:
        """Pipeline counters + directory collision telemetry, merged (same
        directory-health scalars the synchronous monitors report, via the
        shared helper — published under ``monitor="tenant_window_ingest"``)."""
        out = self.pipe.metrics()
        dm = monitor.directory_metrics(self.directory)
        out["tenant_slots_claimed"] = int(dm["tenant_slots_claimed"])
        out["tenant_collision_rate"] = float(dm["tenant_collision_rate"])
        monitor.publish_tenant_metrics(
            "tenant_window_ingest",
            {k: out[k] for k in ("tenant_slots_claimed", "tenant_collision_rate")},
        )
        return out
