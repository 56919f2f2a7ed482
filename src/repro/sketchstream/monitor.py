"""In-step stream telemetry: a QSketch threaded through train/serve steps,
merged across the mesh by max.

Design choice (vs QSketch-Dyn, documented in DESIGN.md §4.3): the in-step
monitor uses the FULL QSketch construction — every element updates all m
registers — rather than Dyn's one-register-per-element route, because:

  1. Exact mergeability. Dyn's running Ĉ is a per-shard martingale; shards
     that see the same element (token streams always do) can't just add
     their Ĉ's, and the register-histogram MLE fallback is misspecified
     whenever m ≳ n_distinct (an untouched Dyn register means "empty
     sub-stream", probability e^{-n/m}, which the quantized-Exp(C/m)
     likelihood cannot express — it drives the MLE to 0). QSketch registers
     are plain max-monoid elements: merge is exact at any scale.
  2. On TPU the m-wide update is ONE fused VPU kernel over the (batch, m)
     tile (kernels/qsketch_update.py) — at telemetry sizes (m=256) it costs
     ~1e9 integer lane-ops per 1M-token step, noise against the model's
     1e13+ FLOPs. The paper's O(1)-vs-O(m) distinction prices scalar CPUs,
     not 8x128 vector lanes; Dyn's O(1) update stays the right choice for
     the single-stream CPU setting and is benchmarked as such.
  3. Estimation stays O(2^b) via the histogram MLE (beyond-paper trick),
     cheap enough to log every step.

Streams monitored:
  * token coverage:   element = token id, weight 1 (distinct vocab touched)
  * weighted coverage: element = token id, weight supplied by the pipeline
  * MoE routing:      element = expert id, weight = routed prob mass
  * serving DAU:      element = session id, weight = engagement weight

Padding: pipeline tails carry dead rows. ``update`` takes an optional
boolean ``mask`` (same leading shape as ``ids``); masked-off rows neither
touch the sketch nor count toward ``n_seen``.

Per-key telemetry (the multi-tenant upgrade): ``ArrayMonitorState`` tracks K
independent sketches — one per expert / session bucket / flow — via
``core.sketch_array``. One ``update_array`` call folds a whole keyed batch
in a single fused segment scatter-max, and ``estimate_array`` returns all K
weighted cardinalities from one vmapped histogram-MLE. Merge stays the exact
max monoid row-wise, so per-key telemetry crosses the mesh the same way the
single sketch does.

Production scale (this file's third layer): ``ShardedArrayMonitor`` fronts
sparse 64-bit tenant ids with a key directory (collision telemetry, pinned
hot keys — core/key_directory.py) and shards the [K, m] register matrix over
a mesh axis (core/sharded_array.py), the path to K ~ 1e7 tenants. Train and
serve steps thread a ``TelemetryState`` (scalar sketch + tenant array) when
both monitors are on.

Anytime per-tenant reads (fourth layer): ``DynArrayMonitor`` swaps the
register matrix for ``core/dyn_array.py`` — per-key §4.3 martingales make
``estimate`` an O(K) read instead of the O(K·2^b) vmapped Newton. Same
init/update/estimate/merge/metrics surface, so train/serve steps accept
either tenant monitor unchanged.

Time-scoped per-tenant reads (fifth layer): ``WindowMonitor`` backs the same
sparse-key surface with ``core/window_array.py`` — a ring of E epoch
sub-states whose union answers "weighted distinct traffic in the last
w <= E epochs" instead of "since init". ``rotate`` advances the epoch clock
(evicting the oldest epoch and aging cold directory fingerprints on the same
tick), and the windowed estimate vector feeds ``sketchstream/anomaly.py``'s
per-tenant drift scoring — the paper's real-time anomaly-detection loop,
closed (DESIGN.md §8.5).

Sharded anytime / windowed reads (sixth layer): ``ShardedDynMonitor`` and
``ShardedWindowMonitor`` carry the Dyn and Window surfaces past one host —
the per-tenant state shards row-wise over a mesh axis via the shared
sharding layer (``core/sharding.py``, DESIGN.md §8.6) while the directory
telemetry and (for windows) the ring clock stay replicated. Same
init/update/estimate/merge/metrics (+rotate) surface, bit-identical
estimates to their single-host counterparts, so train/serve steps accept
any tenant monitor unchanged.

Register-sharing per-tenant telemetry (seventh layer): ``VirtualDynMonitor``
backs the sparse-key surface with ``core/virtual_dyn_array.py`` — pinned hot
tenants keep exact dedicated Dyn rows while the long tail shares one
physical register pool, cutting per-tail-tenant memory from O(m + 2^b) to
O(1) amortized (DESIGN.md §8.9). Tail reads are noise-cancelled estimates
(not bit-identical to dedicated sketches), so ``estimate`` takes the tenant
keys to read — the tail is never enumerated.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax.numpy as jnp

from repro.core import (
    SketchConfig,
    dyn_array,
    estimation,
    estimators,
    key_directory,
    qsketch,
    sharded_array,
    sharded_dyn_array,
    sharded_window_array,
    sharding,
    sketch_array,
    virtual_dyn_array,
    window_array,
)
from repro.core.key_directory import DirectoryConfig, DirectoryState
from repro.core.types import (
    DynArrayState,
    QSketchState,
    ShardedArrayState,
    ShardedDynArrayState,
    ShardedWindowArrayState,
    SketchArrayState,
    VirtualDynArrayState,
    WindowArrayState,
)
from repro.core.virtual_dyn_array import VirtualConfig
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

# Declared tenant-telemetry families, labeled by monitor instance kind — the
# five monitor classes (and the ingest-front TenantWindowIngest) publish
# through these instead of each hand-rolling its own dict plumbing.
_M_TENANT_SEEN = obs_metrics.gauge(
    "tenant_elements_seen", "live elements folded across all tenants",
    labels=("monitor",))
_M_TENANT_SLOTS = obs_metrics.gauge(
    "tenant_slots_claimed", "directory slots holding a fingerprint",
    labels=("monitor",))
_M_TENANT_COLLISIONS = obs_metrics.gauge(
    "tenant_collision_rate", "fraction of routed elements that collided",
    labels=("monitor",))
_M_TENANT_WEIGHT = obs_metrics.gauge(
    "tenant_weight_total", "sum of per-tenant anytime estimates",
    labels=("monitor",))
_M_TENANT_WINDOW_WEIGHT = obs_metrics.gauge(
    "tenant_window_weight", "sum of per-tenant windowed anytime estimates",
    labels=("monitor",))
_M_TENANT_WINDOW_EPOCH = obs_metrics.gauge(
    "tenant_window_epoch", "monotone epoch clock of the window ring",
    labels=("monitor",))
_M_VIRTUAL_POOL_LOAD = obs_metrics.gauge(
    "virtual_pool_load_factor", "fraction of shared-pool slots raised",
    labels=("monitor",))
_M_VIRTUAL_POOL_WEIGHT = obs_metrics.gauge(
    "virtual_pool_weight_total", "exact total live tail weight in the pool",
    labels=("monitor",))
_M_VIRTUAL_TAIL_ELEMENTS = obs_metrics.gauge(
    "virtual_tail_elements", "live tail element-occurrences folded",
    labels=("monitor",))

_TENANT_FAMILIES = {
    "tenant_elements_seen": _M_TENANT_SEEN,
    "tenant_slots_claimed": _M_TENANT_SLOTS,
    "tenant_collision_rate": _M_TENANT_COLLISIONS,
    "tenant_weight_total": _M_TENANT_WEIGHT,
    "tenant_window_weight": _M_TENANT_WINDOW_WEIGHT,
    "tenant_window_epoch": _M_TENANT_WINDOW_EPOCH,
    "virtual_pool_load_factor": _M_VIRTUAL_POOL_LOAD,
    "virtual_pool_weight_total": _M_VIRTUAL_POOL_WEIGHT,
    "virtual_tail_elements": _M_VIRTUAL_TAIL_ELEMENTS,
}


def directory_metrics(directory: DirectoryState) -> dict:
    """The two directory-health scalars every tenant surface reports."""
    return {
        "tenant_slots_claimed": jnp.sum(
            (directory.fingerprints != 0).astype(jnp.int32)
        ),
        "tenant_collision_rate": key_directory.collision_rate(directory),
    }


def publish_tenant_metrics(kind: str, values: dict) -> None:
    """Mirror a tenant ``metrics()`` dict into the obs registry.

    Values are jnp scalars; publication converts to host floats, which
    blocks on those (tiny) device values — fine on the host, fatal under a
    trace. Monitor ``metrics()`` is legitimately called INSIDE jitted train
    steps (launch/train_step.py threads it through the logged aux), so this
    no-ops under any active jax trace: the registry then simply reflects
    the last host-side read.
    """
    if not obs_metrics.enabled() or obs_trace.tracing_active():
        return
    for name, v in values.items():
        fam = _TENANT_FAMILIES.get(name)
        if fam is not None:
            fam.labels(monitor=kind).set(float(v))


def tenant_metrics(kind: str, n_seen, directory: DirectoryState, **extras) -> dict:
    """The shared tenant ``metrics()`` body: stream counter + directory
    health + per-backend extras, in the fixed key order the monitor layer
    has always reported, published to the registry under ``monitor=kind``.

    The returned values stay jnp scalars (callers inside jit keep tracing;
    host callers pay one tiny sync only if they convert)."""
    out = {"tenant_elements_seen": n_seen, **directory_metrics(directory)}
    out.update(extras)
    publish_tenant_metrics(kind, out)
    return out


class MonitorState(NamedTuple):
    """Scalar stream monitor: one full QSketch + an occurrence counter."""

    regs: jnp.ndarray  # int8[m]
    n_seen: jnp.ndarray  # int32 element counter (occurrences, not distinct)


def init(cfg: SketchConfig) -> MonitorState:
    """Fresh scalar monitor: empty QSketch, zero elements seen."""
    return MonitorState(regs=qsketch.init(cfg).regs, n_seen=jnp.int32(0))


def _flatten(ids, weights, mask):
    if isinstance(ids, tuple):  # sparse 64-bit element ids as a (lo, hi) pair
        lo, hi = ids
        ids = (lo.reshape(-1), hi.reshape(-1))
        n = ids[0].shape[0]
    else:
        ids = ids.reshape(-1)
        n = ids.shape[0]
    w = (
        jnp.ones((n,), jnp.float32)
        if weights is None
        else weights.reshape(-1).astype(jnp.float32)
    )
    mask = None if mask is None else mask.reshape(-1)
    n_live = n if mask is None else jnp.sum(mask.astype(jnp.int32))
    return ids, w, mask, n_live


def update(cfg: SketchConfig, state: MonitorState, ids, weights=None, mask=None) -> MonitorState:
    """Batched full-QSketch update (ids flattened; weight 1.0 if not given).

    ``mask`` (bool, same leading shape as ids) drops padding rows: they are
    no-ops in the sketch AND excluded from the ``n_seen`` occurrence count.
    """
    ids, w, mask, n_live = _flatten(ids, weights, mask)
    st = qsketch.update(cfg, QSketchState(regs=state.regs), ids, w, mask=mask)
    return MonitorState(regs=st.regs, n_seen=state.n_seen + n_live)


def estimate(cfg: SketchConfig, state: MonitorState) -> jnp.ndarray:
    """Weighted cardinality via the O(2^b) histogram MLE
    (``estimation.estimate_hist``, the in-step monitor's full-kind solve)."""
    hist = estimators.histogram(cfg, state.regs)
    return estimation.estimate_hist(cfg, hist, kind="full")


def merge(cfg: SketchConfig, a: MonitorState, b: MonitorState) -> MonitorState:
    """Exact union-stream merge (max monoid) — the cross-pod collective."""
    return MonitorState(regs=jnp.maximum(a.regs, b.regs), n_seen=a.n_seen + b.n_seen)


# ---------------------------------------------------------------------------
# Per-key telemetry: K sketches (experts / session buckets / flows) at once
# ---------------------------------------------------------------------------


class ArrayMonitorState(NamedTuple):
    """Per-key monitor: K QSketch rows + a live-element counter."""

    regs: jnp.ndarray  # int8[K, m]
    n_seen: jnp.ndarray  # int32 live-element counter across all keys


def init_array(cfg: SketchConfig, k: int) -> ArrayMonitorState:
    """Fresh per-key monitor: K empty sketch rows, zero elements seen."""
    return ArrayMonitorState(
        regs=sketch_array.init(cfg, k).regs, n_seen=jnp.int32(0)
    )


def _flatten_keys(keys):
    """Flatten dense-slot or (lo, hi) sparse tenant keys uniformly."""
    if isinstance(keys, tuple):
        lo, hi = keys
        return lo.reshape(-1), hi.reshape(-1)
    return keys.reshape(-1)


def update_array(
    cfg: SketchConfig,
    state: ArrayMonitorState,
    keys,
    ids,
    weights=None,
    mask=None,
    dcfg: DirectoryConfig | None = None,
) -> ArrayMonitorState:
    """One fused keyed update: element i lands in sketch row keys[i].

    keys/ids/weights/mask share a leading shape and are flattened, so MoE
    routing tensors ((batch, experts) ids + prob-mass weights) drop in
    directly.

    With ``dcfg`` set, ``keys`` are sparse 64-bit tenant ids (uint32 array or
    (lo, hi) pair) routed statelessly through the key directory; without it,
    they follow the dense-slot contract in [0, K). Collision telemetry lives
    in ``ShardedArrayMonitor`` — this path stays a single pytree in/out.
    """
    keys = _flatten_keys(keys)
    if dcfg is not None:
        keys = key_directory.route_slots(dcfg, keys)
    ids, w, mask, n_live = _flatten(ids, weights, mask)
    st = sketch_array.update(
        cfg, SketchArrayState(regs=state.regs), keys, ids, w, mask=mask
    )
    return ArrayMonitorState(regs=st.regs, n_seen=state.n_seen + n_live)


def estimate_array(cfg: SketchConfig, state: ArrayMonitorState) -> jnp.ndarray:
    """All K weighted cardinalities: one vmapped histogram-MLE, Ĉ[K]."""
    return sketch_array.estimate_all(cfg, SketchArrayState(regs=state.regs))


def merge_array(cfg: SketchConfig, a: ArrayMonitorState, b: ArrayMonitorState) -> ArrayMonitorState:
    """Row-wise exact union merge across shards/pods."""
    return ArrayMonitorState(
        regs=jnp.maximum(a.regs, b.regs), n_seen=a.n_seen + b.n_seen
    )


# ---------------------------------------------------------------------------
# Mesh-sharded per-tenant telemetry: sparse 64-bit keys, K beyond one host
# ---------------------------------------------------------------------------


class ShardedArrayMonitorState(NamedTuple):
    """Pytree state of a ShardedArrayMonitor (threads through jit/scan/ckpt)."""

    regs: jnp.ndarray  # int8[K, m], row-sharded over the monitor's mesh axis
    directory: DirectoryState  # key-collision telemetry
    n_seen: jnp.ndarray  # int32 live-element counter across all tenants


class TelemetryState(NamedTuple):
    """Combined sketch state a train/serve step threads when BOTH the scalar
    stream sketch and the per-tenant sharded array are enabled. Either field
    may be an empty dict when that monitor is off — the tuple stays a valid
    pytree for jit/donation/checkpointing either way."""

    scalar: Any  # MonitorState | {}
    tenants: Any  # ShardedArrayMonitorState | {}


class ShardedArrayMonitor:
    """Per-tenant weighted-cardinality telemetry at production K.

    Wraps the three-layer subsystem — key directory (sparse 64-bit tenant ids
    -> slots, collision counters, pinned hot keys), mesh-sharded register
    matrix (core/sharded_array.py), shard-local vmapped estimation — behind
    the same init/update/estimate/merge surface as the scalar monitor, so
    train/serve steps thread ONE more pytree and nothing else.

    The instance is configuration (closed over by jit); all mutable data
    lives in ``ShardedArrayMonitorState``. ``axis`` names the mesh axis the
    rows shard over: ``"sketch"`` on a dedicated monitoring mesh
    (launch/mesh.make_sketch_mesh), or an existing training-mesh axis (e.g.
    ``"data"``) when telemetry rides inside the train step's jit.
    """

    def __init__(self, cfg: SketchConfig, dcfg: DirectoryConfig, mesh, axis: str = sharded_array.AXIS):
        if dcfg.capacity % sharded_array.num_shards(mesh, axis):
            raise ValueError(
                f"directory capacity {dcfg.capacity} must be divisible by the "
                f"'{axis}' axis shard count ({sharded_array.num_shards(mesh, axis)}); "
                "use ShardedArrayMonitor.for_mesh to round it up"
            )
        self.cfg = cfg
        self.dcfg = dcfg
        self.mesh = mesh
        self.axis = axis

    @classmethod
    def for_mesh(cls, cfg: SketchConfig, capacity: int, mesh, *, axis: str = sharded_array.AXIS, seed: int | None = None, pinned: tuple = ()):
        """Build with ``capacity`` rounded up to a shard multiple."""
        cap = sharded_array.padded_k(capacity, mesh, axis)
        dcfg = DirectoryConfig(capacity=cap, seed=cfg.seed if seed is None else seed, pinned=pinned)
        return cls(cfg, dcfg, mesh, axis=axis)

    def init(self) -> ShardedArrayMonitorState:
        """Fresh sharded register matrix + empty directory telemetry."""
        return ShardedArrayMonitorState(
            regs=sharded_array.init(self.cfg, self.dcfg.capacity, self.mesh, axis=self.axis).regs,
            directory=key_directory.init(self.dcfg),
            n_seen=jnp.int32(0),
        )

    def update(self, state: ShardedArrayMonitorState, tenant_keys, ids, weights=None, mask=None) -> ShardedArrayMonitorState:
        """Fold a keyed batch: tenant_keys are sparse ids (uint32 or (lo, hi)
        pair), flattened together with ids/weights/mask like ``update``."""
        keys = _flatten_keys(tenant_keys)
        ids, w, mask, n_live = _flatten(ids, weights, mask)
        st, dir_state = sharded_array.update_tenants(
            self.cfg, self.dcfg, self.mesh,
            ShardedArrayState(regs=state.regs), state.directory,
            keys, ids, w, mask=mask, axis=self.axis,
        )
        return ShardedArrayMonitorState(
            regs=st.regs, directory=dir_state, n_seen=state.n_seen + n_live
        )

    def estimate(self, state: ShardedArrayMonitorState) -> jnp.ndarray:
        """Ĉ[K] — the vmapped Newton runs shard-local, no register gather."""
        return sharded_array.estimate_all(
            self.cfg, self.mesh, ShardedArrayState(regs=state.regs), axis=self.axis
        )

    def merge(self, a: ShardedArrayMonitorState, b: ShardedArrayMonitorState) -> ShardedArrayMonitorState:
        """Cross-pod union: all-max registers, directory telemetry merge."""
        regs = sharded_array.merge(
            ShardedArrayState(regs=a.regs), ShardedArrayState(regs=b.regs)
        ).regs
        return ShardedArrayMonitorState(
            regs=regs,
            directory=key_directory.merge(a.directory, b.directory),
            n_seen=a.n_seen + b.n_seen,
        )

    def metrics(self, state: ShardedArrayMonitorState) -> dict:
        """Cheap per-step scalars (NO estimation): stream + directory health."""
        return tenant_metrics("sharded_array", state.n_seen, state.directory)


# ---------------------------------------------------------------------------
# Anytime per-tenant telemetry: QSketch-Dyn martingales, O(1) per-key reads
# ---------------------------------------------------------------------------


class DynArrayMonitorState(NamedTuple):
    """Pytree state of a DynArrayMonitor (threads through jit/scan/ckpt)."""

    regs: jnp.ndarray  # int8[K, m]
    hists: jnp.ndarray  # int32[K, 2^b] batch-start q_R histograms
    chats: jnp.ndarray  # f32[K] running per-tenant estimates
    directory: DirectoryState  # key-collision telemetry
    n_seen: jnp.ndarray  # int32 live-element counter across all tenants


class DynArrayMonitor:
    """Per-tenant weighted-cardinality telemetry with O(1)-anytime reads.

    Same surface as ``ShardedArrayMonitor`` (init/update/estimate/merge/
    metrics, sparse 64-bit tenant ids through the key directory) but backed
    by ``core/dyn_array.py``: every update also advances a per-key §4.3
    martingale, so ``estimate`` is a pure O(K) read of the running chats
    instead of the O(K·2^b) vmapped Newton — the right trade at K ~ 1e6
    when estimates are consumed every step (per-tenant DAU dashboards,
    serving-time quota checks), at the cost of a heavier update (per-element
    q_R + histogram maintenance).

    Caveat (DESIGN.md §8.4): the running chats are per-STREAM martingales.
    They are exact across disjoint batches folded into one state, but two
    monitors that may have seen the same element must ``merge`` (register
    max + per-key MLE re-estimate), never add their chats.

    The instance is configuration (closed over by jit); all mutable data
    lives in ``DynArrayMonitorState``.
    """

    def __init__(self, cfg: SketchConfig, dcfg: DirectoryConfig):
        self.cfg = cfg
        self.dcfg = dcfg

    @classmethod
    def for_capacity(cls, cfg: SketchConfig, capacity: int, *, seed: int | None = None, pinned: tuple = ()):
        """Build with a fresh directory config of ``capacity`` slots."""
        dcfg = DirectoryConfig(capacity=capacity, seed=cfg.seed if seed is None else seed, pinned=pinned)
        return cls(cfg, dcfg)

    def init(self) -> DynArrayMonitorState:
        """Fresh DynArray + empty directory telemetry."""
        st = dyn_array.init(self.cfg, self.dcfg.capacity)
        return DynArrayMonitorState(
            regs=st.regs,
            hists=st.hists,
            chats=st.chats,
            directory=key_directory.init(self.dcfg),
            n_seen=jnp.int32(0),
        )

    def update(self, state: DynArrayMonitorState, tenant_keys, ids, weights=None, mask=None) -> DynArrayMonitorState:
        """Fold a keyed batch: tenant_keys are sparse ids (uint32 or (lo, hi)
        pair), flattened together with ids/weights/mask like ``update``."""
        keys = _flatten_keys(tenant_keys)
        ids, w, mask, n_live = _flatten(ids, weights, mask)
        st, dir_state = dyn_array.update_tenants(
            self.cfg, self.dcfg,
            DynArrayState(regs=state.regs, hists=state.hists, chats=state.chats),
            state.directory, keys, ids, w, mask=mask,
        )
        return DynArrayMonitorState(
            regs=st.regs, hists=st.hists, chats=st.chats,
            directory=dir_state, n_seen=state.n_seen + n_live,
        )

    def estimate(self, state: DynArrayMonitorState) -> jnp.ndarray:
        """Ĉ[K] — the anytime read; no Newton, no histogram walk."""
        return dyn_array.estimate_all(
            DynArrayState(regs=state.regs, hists=state.hists, chats=state.chats)
        )

    def merge(self, a: DynArrayMonitorState, b: DynArrayMonitorState) -> DynArrayMonitorState:
        """Cross-pod union: register max, per-key MLE re-estimated chats,
        directory telemetry merge."""
        st = dyn_array.merge(
            self.cfg,
            DynArrayState(regs=a.regs, hists=a.hists, chats=a.chats),
            DynArrayState(regs=b.regs, hists=b.hists, chats=b.chats),
        )
        return DynArrayMonitorState(
            regs=st.regs, hists=st.hists, chats=st.chats,
            directory=key_directory.merge(a.directory, b.directory),
            n_seen=a.n_seen + b.n_seen,
        )

    def metrics(self, state: DynArrayMonitorState) -> dict:
        """Cheap per-step scalars: stream + directory health, plus the total
        tracked weight — an O(K) sum of the anytime estimates, affordable
        every step precisely because no solve is involved."""
        return tenant_metrics(
            "dyn_array", state.n_seen, state.directory,
            tenant_weight_total=jnp.sum(state.chats),
        )


# ---------------------------------------------------------------------------
# Sliding-window per-tenant telemetry: epoch ring, time-scoped estimates
# ---------------------------------------------------------------------------


class WindowMonitorState(NamedTuple):
    """Pytree state of a WindowMonitor (threads through jit/scan/ckpt)."""

    window: WindowArrayState  # epoch ring + cached union (core/window_array)
    directory: DirectoryState  # key-collision telemetry + aging stamps
    n_seen: jnp.ndarray  # int32 live-element counter across all tenants


class WindowMonitor:
    """Per-tenant SLIDING-WINDOW weighted-cardinality telemetry.

    Same sparse-64-bit-tenant surface as ``DynArrayMonitor`` (init/update/
    estimate/merge/metrics, key-directory routing) backed by
    ``core/window_array.py``: estimates answer "weighted distinct traffic in
    the last w <= E epochs", not "since init" — what a real-time anomaly
    detector consumes. Two extra verbs beyond the shared surface:

    * ``rotate(state)`` — close the current epoch (the caller's clock: every
      N steps / T seconds). Evicts the oldest epoch once the ring is full and
      optionally ages cold directory fingerprints that have not been touched
      for ``evict_after`` epochs (0 disables aging).
    * ``estimate(state, w=None)`` — ``w=None`` is the O(K) anytime read of
      the full-ring window (running union martingales); an integer w is the
      windowed histogram-MLE read over the last w epochs.

    The instance is configuration (closed over by jit); all mutable data
    lives in ``WindowMonitorState``.
    """

    def __init__(self, cfg: SketchConfig, dcfg: DirectoryConfig, n_epochs: int, *, evict_after: int = 0):
        if evict_after < 0:
            raise ValueError("evict_after must be >= 0 (0 disables aging)")
        self.cfg = cfg
        self.dcfg = dcfg
        self.n_epochs = int(n_epochs)
        self.evict_after = int(evict_after)

    @classmethod
    def for_capacity(cls, cfg: SketchConfig, capacity: int, n_epochs: int, *, seed: int | None = None, pinned: tuple = (), evict_after: int = 0):
        """Build with a fresh directory config of ``capacity`` slots."""
        dcfg = DirectoryConfig(capacity=capacity, seed=cfg.seed if seed is None else seed, pinned=pinned)
        return cls(cfg, dcfg, n_epochs, evict_after=evict_after)

    def init(self) -> WindowMonitorState:
        """Fresh epoch ring + empty directory telemetry."""
        return WindowMonitorState(
            window=window_array.init(self.cfg, self.dcfg.capacity, self.n_epochs),
            directory=key_directory.init(self.dcfg),
            n_seen=jnp.int32(0),
        )

    def update(self, state: WindowMonitorState, tenant_keys, ids, weights=None, mask=None) -> WindowMonitorState:
        """Fold a keyed batch into the CURRENT epoch: tenant_keys are sparse
        ids (uint32 or (lo, hi) pair), flattened together with ids/weights/
        mask like ``update``. Routed slots are stamped with the window's
        epoch clock for directory aging."""
        keys = _flatten_keys(tenant_keys)
        ids, w, mask, n_live = _flatten(ids, weights, mask)
        win, dir_state = window_array.update_tenants(
            self.cfg, self.dcfg, state.window, state.directory,
            keys, ids, w, mask=mask,
        )
        return WindowMonitorState(
            window=win, directory=dir_state, n_seen=state.n_seen + n_live
        )

    def rotate(self, state: WindowMonitorState) -> WindowMonitorState:
        """Advance the epoch clock (evicting the oldest epoch once the ring
        is full); age cold directory fingerprints if configured."""
        win = window_array.rotate(self.cfg, state.window)
        directory = state.directory
        if self.evict_after:
            directory, _ = key_directory.evict_older_than(
                self.dcfg, directory, win.epoch_id - self.evict_after
            )
        return WindowMonitorState(
            window=win, directory=directory, n_seen=state.n_seen
        )

    def estimate(self, state: WindowMonitorState, w: int | None = None) -> jnp.ndarray:
        """Ĉ[K] over the trailing window. ``w=None``: the anytime O(K) read
        of the full-ring window; ``w`` an int in [1, E]: the union MLE read
        over the last w epochs."""
        if w is None:
            return window_array.estimate_ring_anytime(state.window)
        return window_array.estimate_window(self.cfg, state.window, w)

    def merge(self, a: WindowMonitorState, b: WindowMonitorState) -> WindowMonitorState:
        """Cross-pod union of ring-aligned windows (pods rotate on a shared
        clock): per-epoch register max + MLE re-estimates, directory merge."""
        return WindowMonitorState(
            window=window_array.merge(self.cfg, a.window, b.window),
            directory=key_directory.merge(a.directory, b.directory),
            n_seen=a.n_seen + b.n_seen,
        )

    def metrics(self, state: WindowMonitorState) -> dict:
        """Cheap per-step scalars: stream + directory health + the window
        clock and the total windowed weight (an O(K) sum of the anytime
        union reads — no solve)."""
        return tenant_metrics(
            "window", state.n_seen, state.directory,
            tenant_window_weight=jnp.sum(state.window.union_chats),
            tenant_window_epoch=state.window.epoch_id,
        )


# ---------------------------------------------------------------------------
# Sharded anytime / windowed per-tenant telemetry: Dyn + Window past one host
# ---------------------------------------------------------------------------


class ShardedDynMonitorState(NamedTuple):
    """Pytree state of a ShardedDynMonitor (threads through jit/scan/ckpt)."""

    array: ShardedDynArrayState  # row-sharded regs/hists/chats
    directory: DirectoryState  # replicated key-collision telemetry
    n_seen: jnp.ndarray  # int32 live-element counter across all tenants


class ShardedDynMonitor:
    """Per-tenant O(K)-anytime telemetry with the state sharded over a mesh.

    The ``DynArrayMonitor`` surface (init/update/estimate/merge/metrics,
    sparse 64-bit tenant ids through the key directory) backed by
    ``core/sharded_dyn_array.py``: registers, histograms and the running
    martingales all shard row-wise over ``axis``, so K scales with the
    fleet while ``estimate`` stays a pure O(K) read (of the sharded chats).
    Estimates are bit-identical to the single-host ``DynArrayMonitor`` fed
    the same stream.

    The instance is configuration (closed over by jit); all mutable data
    lives in ``ShardedDynMonitorState``.
    """

    def __init__(self, cfg: SketchConfig, dcfg: DirectoryConfig, mesh, axis: str = sharding.AXIS):
        if dcfg.capacity % sharding.num_shards(mesh, axis):
            raise ValueError(
                f"directory capacity {dcfg.capacity} must be divisible by the "
                f"'{axis}' axis shard count ({sharding.num_shards(mesh, axis)}); "
                "use ShardedDynMonitor.for_mesh to round it up"
            )
        self.cfg = cfg
        self.dcfg = dcfg
        self.mesh = mesh
        self.axis = axis

    @classmethod
    def for_mesh(cls, cfg: SketchConfig, capacity: int, mesh, *, axis: str = sharding.AXIS, seed: int | None = None, pinned: tuple = ()):
        """Build with ``capacity`` rounded up to a shard multiple."""
        cap = sharding.padded_k(capacity, mesh, axis)
        dcfg = DirectoryConfig(capacity=cap, seed=cfg.seed if seed is None else seed, pinned=pinned)
        return cls(cfg, dcfg, mesh, axis=axis)

    def init(self) -> ShardedDynMonitorState:
        """Fresh sharded array + empty directory telemetry."""
        return ShardedDynMonitorState(
            array=sharded_dyn_array.init(self.cfg, self.dcfg.capacity, self.mesh, axis=self.axis),
            directory=key_directory.init(self.dcfg),
            n_seen=jnp.int32(0),
        )

    def update(self, state: ShardedDynMonitorState, tenant_keys, ids, weights=None, mask=None) -> ShardedDynMonitorState:
        """Fold a keyed batch: tenant_keys are sparse ids (uint32 or (lo, hi)
        pair), flattened together with ids/weights/mask like ``update``."""
        keys = _flatten_keys(tenant_keys)
        ids, w, mask, n_live = _flatten(ids, weights, mask)
        st, dir_state = sharded_dyn_array.update_tenants(
            self.cfg, self.dcfg, self.mesh, state.array, state.directory,
            keys, ids, w, mask=mask, axis=self.axis,
        )
        return ShardedDynMonitorState(
            array=st, directory=dir_state, n_seen=state.n_seen + n_live
        )

    def estimate(self, state: ShardedDynMonitorState) -> jnp.ndarray:
        """Ĉ[K] — the anytime read of the sharded martingales."""
        return sharded_dyn_array.estimate_all(state.array)

    def merge(self, a: ShardedDynMonitorState, b: ShardedDynMonitorState) -> ShardedDynMonitorState:
        """Cross-pod union of possibly-overlapping streams: register max,
        shard-local per-key MLE re-estimated chats, directory merge."""
        return ShardedDynMonitorState(
            array=sharded_dyn_array.merge(self.cfg, self.mesh, a.array, b.array, axis=self.axis),
            directory=key_directory.merge(a.directory, b.directory),
            n_seen=a.n_seen + b.n_seen,
        )

    def metrics(self, state: ShardedDynMonitorState) -> dict:
        """Cheap per-step scalars: stream + directory health + total tracked
        weight (an O(K) sum of the sharded anytime estimates)."""
        return tenant_metrics(
            "sharded_dyn", state.n_seen, state.directory,
            tenant_weight_total=jnp.sum(state.array.chats),
        )


class ShardedWindowMonitorState(NamedTuple):
    """Pytree state of a ShardedWindowMonitor (threads through jit/scan/ckpt)."""

    window: ShardedWindowArrayState  # sharded epoch ring + union cache
    directory: DirectoryState  # replicated telemetry + aging stamps
    n_seen: jnp.ndarray  # int32 live-element counter across all tenants


class ShardedWindowMonitor:
    """Per-tenant SLIDING-WINDOW telemetry with the ring sharded over a mesh.

    The ``WindowMonitor`` surface (init/update/rotate/estimate/merge/
    metrics, key-directory routing with epoch-stamped aging) backed by
    ``core/sharded_window_array.py``: every per-tenant leaf of the epoch
    ring and the union cache shards row-wise over ``axis``; the ring clock
    stays replicated so all shards rotate in lockstep. Estimates are
    bit-identical to the single-host ``WindowMonitor`` fed the same stream
    and rotation schedule.

    The instance is configuration (closed over by jit); all mutable data
    lives in ``ShardedWindowMonitorState``.
    """

    def __init__(self, cfg: SketchConfig, dcfg: DirectoryConfig, n_epochs: int, mesh, *, axis: str = sharding.AXIS, evict_after: int = 0):
        if evict_after < 0:
            raise ValueError("evict_after must be >= 0 (0 disables aging)")
        if dcfg.capacity % sharding.num_shards(mesh, axis):
            raise ValueError(
                f"directory capacity {dcfg.capacity} must be divisible by the "
                f"'{axis}' axis shard count ({sharding.num_shards(mesh, axis)}); "
                "use ShardedWindowMonitor.for_mesh to round it up"
            )
        self.cfg = cfg
        self.dcfg = dcfg
        self.n_epochs = int(n_epochs)
        self.mesh = mesh
        self.axis = axis
        self.evict_after = int(evict_after)

    @classmethod
    def for_mesh(cls, cfg: SketchConfig, capacity: int, n_epochs: int, mesh, *, axis: str = sharding.AXIS, seed: int | None = None, pinned: tuple = (), evict_after: int = 0):
        """Build with ``capacity`` rounded up to a shard multiple."""
        cap = sharding.padded_k(capacity, mesh, axis)
        dcfg = DirectoryConfig(capacity=cap, seed=cfg.seed if seed is None else seed, pinned=pinned)
        return cls(cfg, dcfg, n_epochs, mesh, axis=axis, evict_after=evict_after)

    def init(self) -> ShardedWindowMonitorState:
        """Fresh sharded ring + empty directory telemetry."""
        return ShardedWindowMonitorState(
            window=sharded_window_array.init(
                self.cfg, self.dcfg.capacity, self.n_epochs, self.mesh, axis=self.axis
            ),
            directory=key_directory.init(self.dcfg),
            n_seen=jnp.int32(0),
        )

    def update(self, state: ShardedWindowMonitorState, tenant_keys, ids, weights=None, mask=None) -> ShardedWindowMonitorState:
        """Fold a keyed batch into the CURRENT epoch; routed slots are
        stamped with the window's epoch clock for directory aging."""
        keys = _flatten_keys(tenant_keys)
        ids, w, mask, n_live = _flatten(ids, weights, mask)
        win, dir_state = sharded_window_array.update_tenants(
            self.cfg, self.dcfg, self.mesh, state.window, state.directory,
            keys, ids, w, mask=mask, axis=self.axis,
        )
        return ShardedWindowMonitorState(
            window=win, directory=dir_state, n_seen=state.n_seen + n_live
        )

    def rotate(self, state: ShardedWindowMonitorState) -> ShardedWindowMonitorState:
        """Advance the epoch clock shard-locally (evicting the oldest epoch
        once the ring is full); age cold directory fingerprints if
        configured."""
        win = sharded_window_array.rotate(self.cfg, self.mesh, state.window, axis=self.axis)
        directory = state.directory
        if self.evict_after:
            directory, _ = key_directory.evict_older_than(
                self.dcfg, directory, win.epoch_id - self.evict_after
            )
        return ShardedWindowMonitorState(
            window=win, directory=directory, n_seen=state.n_seen
        )

    def estimate(self, state: ShardedWindowMonitorState, w: int | None = None) -> jnp.ndarray:
        """Ĉ[K] over the trailing window. ``w=None``: the O(K) anytime read
        of the sharded union martingales; ``w`` an int in [1, E]: the
        shard-local windowed histogram-MLE read."""
        if w is None:
            return sharded_window_array.estimate_ring_anytime(state.window)
        return sharded_window_array.estimate_window(
            self.cfg, self.mesh, state.window, w, axis=self.axis
        )

    def merge(self, a: ShardedWindowMonitorState, b: ShardedWindowMonitorState) -> ShardedWindowMonitorState:
        """Cross-pod union of ring-aligned sharded windows (pods rotate on a
        shared clock): shard-local register max + MLE re-estimates,
        directory merge."""
        return ShardedWindowMonitorState(
            window=sharded_window_array.merge(self.cfg, self.mesh, a.window, b.window, axis=self.axis),
            directory=key_directory.merge(a.directory, b.directory),
            n_seen=a.n_seen + b.n_seen,
        )

    def metrics(self, state: ShardedWindowMonitorState) -> dict:
        """Cheap per-step scalars: stream + directory health + the window
        clock and the total windowed weight (O(K) sum of the sharded
        anytime union reads)."""
        return tenant_metrics(
            "sharded_window", state.n_seen, state.directory,
            tenant_window_weight=jnp.sum(state.window.union_chats),
            tenant_window_epoch=state.window.epoch_id,
        )


# ---------------------------------------------------------------------------
# Register-sharing per-tenant telemetry: hot rows exact, long tail pooled
# ---------------------------------------------------------------------------


class VirtualDynMonitorState(NamedTuple):
    """Pytree state of a VirtualDynMonitor (threads through jit/scan/ckpt)."""

    array: VirtualDynArrayState  # shared pool + pinned dense hot rows
    n_seen: jnp.ndarray  # int32 live-element counter across all tenants


class VirtualDynMonitor:
    """Per-tenant telemetry where the long tail shares one register pool.

    Same sparse-64-bit-tenant surface as ``DynArrayMonitor`` (init/update/
    estimate/merge/metrics) backed by ``core/virtual_dyn_array.py``: the
    ``vcfg.pinned`` hot tenants keep dedicated dense Dyn rows — their reads
    are the exact anytime martingales, bit-identical to a dedicated
    ``DynArray`` — while every other tenant hashes its registers into one
    shared ``pool_size``-slot pool, so tail memory is O(pool) regardless of
    how many tenants exist. Tail reads are noise-CANCELLED estimates
    (Wang et al. 1811.09126; DESIGN.md §8.9), not exact sub-sketches, with a
    resolution floor of ``noise_floor()`` — the trade that buys the 10-100x
    memory reduction at matched tail accuracy.

    Two surface deltas against the dense monitors, both forced by pooling:

    * ``estimate(state, tenant_keys)`` takes the tenants to read — the tail
      is a hash range, not an enumerable axis, so there is no ``Ĉ[K]``
      vector read of "all" tenants.
    * No ``DirectoryState`` telemetry threads through: tail routing is
      stateless (every unpinned tenant shares one sentinel slot by design),
      so collision counters are meaningless here. ``metrics()`` reports
      pool pressure instead.

    ``promote(state, tenant)`` pins a tail tenant into the hot tier and
    returns a NEW (monitor, state) pair — the pinned set is static
    configuration, so jitted callees recompile once (semantics and residue
    handling: ``virtual_dyn_array.promote``).

    The instance is configuration (closed over by jit); all mutable data
    lives in ``VirtualDynMonitorState``.
    """

    def __init__(self, cfg: SketchConfig, vcfg: VirtualConfig):
        self.cfg = cfg
        self.vcfg = vcfg

    @classmethod
    def for_pool(cls, cfg: SketchConfig, pool_size: int, *, pinned: tuple = (), m_virtual: int | None = None, seed: int | None = None):
        """Build with a fresh virtual config of ``pool_size`` slots."""
        vcfg = VirtualConfig(
            pool_size=pool_size, m_virtual=m_virtual, pinned=pinned,
            seed=cfg.seed if seed is None else seed,
        )
        return cls(cfg, vcfg)

    def init(self) -> VirtualDynMonitorState:
        """Fresh pool + empty hot rows, zero elements seen."""
        return VirtualDynMonitorState(
            array=virtual_dyn_array.init(self.cfg, self.vcfg),
            n_seen=jnp.int32(0),
        )

    def update(self, state: VirtualDynMonitorState, tenant_keys, ids, weights=None, mask=None) -> VirtualDynMonitorState:
        """Fold a keyed batch: tenant_keys are sparse ids (uint32 or (lo, hi)
        pair), flattened together with ids/weights/mask like ``update``."""
        keys = _flatten_keys(tenant_keys)
        ids, w, mask, n_live = _flatten(ids, weights, mask)
        st = virtual_dyn_array.update_tenants(
            self.cfg, self.vcfg, state.array, keys, ids, w, mask=mask
        )
        return VirtualDynMonitorState(array=st, n_seen=state.n_seen + n_live)

    def estimate(self, state: VirtualDynMonitorState, tenant_keys) -> jnp.ndarray:
        """Ŵ[T] for the QUERIED tenants: exact martingale reads for pinned
        tenants, noise-cancelled virtual reads for the tail."""
        return virtual_dyn_array.estimate_tenants(
            self.cfg, self.vcfg, state.array, _flatten_keys(tenant_keys)
        )

    def merge(self, a: VirtualDynMonitorState, b: VirtualDynMonitorState) -> VirtualDynMonitorState:
        """Cross-pod union: pool max + hot-tier dense merge. Exact for
        disjoint shards; overlapping streams inflate ``w_tail`` and the
        tail reads go conservative (``virtual_dyn_array.merge``)."""
        return VirtualDynMonitorState(
            array=virtual_dyn_array.merge(self.cfg, self.vcfg, a.array, b.array),
            n_seen=a.n_seen + b.n_seen,
        )

    def promote(self, state: VirtualDynMonitorState, tenant, *, migrate: bool = False) -> tuple["VirtualDynMonitor", VirtualDynMonitorState]:
        """Pin ``tenant`` into the hot tier: -> (monitor', state'). The old
        monitor/state pair stays valid for already-traced callees; route new
        traffic through the returned pair."""
        vcfg, array = virtual_dyn_array.promote(
            self.cfg, self.vcfg, state.array, tenant, migrate=migrate
        )
        return (
            VirtualDynMonitor(self.cfg, vcfg),
            VirtualDynMonitorState(array=array, n_seen=state.n_seen),
        )

    def metrics(self, state: VirtualDynMonitorState) -> dict:
        """Cheap per-step scalars (NO solve): stream counter, pool pressure
        (load factor, exact pooled weight, tail occurrences) and the hot
        tier's total tracked weight (O(num_hot) sum of exact martingales)."""
        out = {
            "tenant_elements_seen": state.n_seen,
            "virtual_pool_load_factor": virtual_dyn_array.pool_load_factor(state.array),
            "virtual_pool_weight_total": state.array.w_tail,
            "virtual_tail_elements": state.array.n_tail,
            "tenant_weight_total": jnp.sum(state.array.hot.chats),
        }
        publish_tenant_metrics("virtual_dyn", out)
        return out
