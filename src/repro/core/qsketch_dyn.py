"""QSketch-Dyn (paper §4.3): O(1)-update anytime weighted-cardinality tracking.

Per element (x, w):
  1. pick ONE register j = g(x)                      (hash, not RandInt: the
     choice must be consistent per element or duplicates double-count);
  2. y = floor(-log2(-ln h_j(x) / w));
  3. if y > R[j]: move histogram mass T[R[j]] -> T[y'], set R[j] = y';
  4. Ĉ += 1(changed) * w / q_R, with the update probability
         q_R = 1 - (1/m) Σ_k T[k] e^{-w 2^{-(k+r_min+1)}}
     computed from the state BEFORE the update (Eq. 12 / Thm. 2).

NOTE on the paper's Alg. 3: lines 14–17 as printed compute q_R *after* the
register/histogram update and add w/q_R unconditionally. That contradicts
Eq. (12) and the unbiasedness proof of Thm. 2 (which conditions q_R^{(t)} on
R^{(t-1)} and carries the indicator). We implement Eq. (12); the accuracy
benchmarks reproduce the paper's reported behaviour with this reading.

Two execution modes (DESIGN.md §4.2):

* ``update_scan``  — exact sequential semantics via ``lax.scan`` (the
                     paper-faithful baseline; also the accuracy-benchmark path).
* ``update_batch`` — TPU-native: all q_R from the batch-start histogram,
                     one scatter-max + histogram rebuild. Within-batch
                     duplicates are removed exactly; the only deviation from
                     the exact chain is ≤B-element staleness of q_R, measured
                     in benchmarks/batch_bias.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import estimation, estimators, hashing
from .types import DynState, SketchConfig

_QR_FLOOR = 1e-12  # q_R guard; only reachable when sketch is fully saturated


def init(cfg: SketchConfig) -> DynState:
    """Fresh QSketch-Dyn: int8[m] registers at r_min, zero touched-register
    histogram, zero running martingale estimate."""
    return DynState(
        regs=jnp.full((cfg.m,), cfg.r_min, dtype=jnp.int8),
        hist=jnp.zeros((cfg.num_bins,), dtype=jnp.int32),
        chat=jnp.float32(0.0),
    )


def _choose_and_quantize(cfg: SketchConfig, lo, hi, w):
    """(j, y) per element: register choice g(x) and quantized value."""
    j = hashing.hash_mod((lo, hi), cfg.salt_g, cfg.m)
    e = hashing.neg_log_uniform((lo, hi, j.astype(jnp.uint32)), cfg.salt_h)
    y = jnp.floor(jnp.log2(w) - jnp.log2(e))
    # No r_min clip needed: y must exceed R[j] >= r_min to matter. Cap at r_max.
    y = jnp.minimum(y, float(cfg.r_max))
    # Guard against -inf/NaN from degenerate w; quantize to a harmless floor.
    y = jnp.where(jnp.isfinite(y), y, float(cfg.r_min))
    return j, y.astype(jnp.int32)


def fold_sum(x):
    """Sum over the last axis (a power of two) in one fixed order: add the
    upper half onto the lower half until one lane is left.

    XLA leaves the association order of a reduction to the backend, and a
    Pallas kernel reduces in its own order, so two routes that must agree
    bit for bit (``kernels/dyn_array_update.py``) both sum in this order:
    the kernel's lane butterfly (``lane_fold_sum``) leaves exactly this tree
    in lane 0.
    """
    n = x.shape[-1]
    while n > 1:
        n //= 2
        x = x[..., :n] + x[..., n : 2 * n]
    return x[..., 0]


def _q_update_prob(cfg: SketchConfig, hist, w):
    """q_R for weight(s) w given histogram T (paper §4.3, O(2^b)).

    Untouched registers (still r_min) are intentionally absent from T: their
    e^{-w 2^{-(r_min+1)}} term is ~0 (Alg. 3 inits T to zeros), so
    q_R = 1 - (1/m) Σ_k T[k] e^{-w s_k} automatically treats them as
    always-updatable. The bin sum runs in ``fold_sum`` order.
    """
    s = jnp.asarray(estimators._bin_scales(cfg))  # 2^{-(k+r_min+1)}
    w = jnp.asarray(w, jnp.float32)
    expo = jnp.exp(-w[..., None] * s)  # (..., 2^b)
    q = 1.0 - fold_sum(hist.astype(jnp.float32) * expo) / cfg.m
    return jnp.maximum(q, _QR_FLOOR)


@functools.partial(jax.jit, static_argnums=(0,))
def update_scan(cfg: SketchConfig, state: DynState, ids, weights, mask=None) -> DynState:
    """Exact sequential update of a batch (Alg. 3 semantics, Eq. 12 estimator).

    Degenerate (non-positive / non-finite) weights are dropped as if masked —
    same contract as ``update_batch``.
    """
    lo, hi = hashing.split_id64(ids)
    w = weights.astype(jnp.float32)
    mask = _live_weight_mask(w, mask)

    def step(carry, inp):
        regs, hist, chat = carry
        elo, ehi, ew, em = inp
        j, y = _choose_and_quantize(cfg, elo, ehi, ew)
        q = _q_update_prob(cfg, hist, ew)
        old = regs[j].astype(jnp.int32)
        changed = em & (y > old)
        # Histogram move: decrement old bin if tracked, increment new bin.
        old_bin = old - cfg.r_min
        new_bin = y - cfg.r_min
        dec = changed & (hist[old_bin] > 0)
        hist = hist.at[old_bin].add(jnp.where(dec, -1, 0))
        hist = hist.at[new_bin].add(jnp.where(changed, 1, 0))
        regs = regs.at[j].set(jnp.where(changed, y, old).astype(jnp.int8))
        chat = chat + jnp.where(changed, ew / q, 0.0)
        return (regs, hist, chat), None

    (regs, hist, chat), _ = jax.lax.scan(step, (state.regs, state.hist, state.chat), (lo, hi, w, mask))
    return DynState(regs=regs, hist=hist, chat=chat)


def _live_weight_mask(w, mask):
    """Rows that may touch the sketch: caller mask AND a usable weight.

    Non-positive / non-finite weights are *dropped as if masked* rather than
    quantized to a silent r_min floor: a degenerate w can never raise a
    register, but before this guard it still competed in the within-batch
    dedup, where a w=0 duplicate sorting first would shadow a live positive
    row of the same id out of the batch entirely.
    """
    live = jnp.isfinite(w) & (w > 0)
    return live if mask is None else live & mask


def _dedup_mask(lo, hi, live=None):
    """Exact within-batch first-occurrence mask via sort on the id pair.

    ``live`` joins the sort as the LAST lexsort key (after the id pair), so
    live rows order ahead of dead (padding / degenerate-weight) rows sharing
    their id: the first-occurrence winner of any id group that contains a
    live row is itself live. Computing first-occurrence over all rows and
    intersecting with the mask afterwards — the pre-fix behaviour — let a
    padded duplicate claim the slot and silently drop the live row's weight.
    Ties among live rows keep batch order (lexsort is stable).
    """
    dead = (
        jnp.zeros(lo.shape, jnp.uint32) if live is None else (~live).astype(jnp.uint32)
    )
    order = jnp.lexsort((dead, lo, hi))
    slo, shi = lo[order], hi[order]
    first = jnp.concatenate(
        [jnp.array([True]), (slo[1:] != slo[:-1]) | (shi[1:] != shi[:-1])]
    )
    mask = jnp.zeros_like(first).at[order].set(first)
    return mask


@functools.partial(jax.jit, static_argnums=(0,))
def update_batch(cfg: SketchConfig, state: DynState, ids, weights, mask=None) -> DynState:
    """Batch-stale update: q_R and change-indicators from the batch-start state.

    Exact within-batch dedup; register scatter-max; histogram rebuilt from
    registers (equivalent to the incremental moves because untouched
    registers hold r_min and bin 0 is pinned to zero).

    Dedup/mask ordering contract (DESIGN.md §4.2): first-occurrence is
    decided among *live* rows only — ``mask=False`` padding rows and
    degenerate (non-positive / non-finite) weights are dropped before they
    can shadow a live row sharing their id. Within-batch duplicates are
    assumed to carry the element's weight (weight is a function of the id,
    the paper's weighted-stream model); the first live occurrence wins.
    """
    lo, hi = hashing.split_id64(ids)
    w = weights.astype(jnp.float32)
    j, y = _choose_and_quantize(cfg, lo, hi, w)

    live = _live_weight_mask(w, mask)
    alive = _dedup_mask(lo, hi, live) & live

    old = state.regs[j].astype(jnp.int32)
    changed = alive & (y > old)
    q = _q_update_prob(cfg, state.hist, w)
    chat = state.chat + jnp.sum(jnp.where(changed, w / q, 0.0))

    y_eff = jnp.where(changed, y, jnp.int32(cfg.r_min))
    regs = state.regs.astype(jnp.int32).at[j].max(y_eff).astype(jnp.int8)

    # Rebuild histogram of touched registers (R > r_min); bin 0 stays 0.
    hist = jnp.zeros((cfg.num_bins,), jnp.int32).at[
        regs.astype(jnp.int32) - cfg.r_min
    ].add(1)
    hist = hist.at[0].set(0)
    return DynState(regs=regs, hist=hist, chat=chat)


def estimate(state: DynState) -> jnp.ndarray:
    """Anytime estimate: it's just the running martingale (O(0) per query)."""
    return state.chat


@functools.partial(jax.jit, static_argnums=(0,))
def estimate_mle(cfg: SketchConfig, state: DynState):
    """Histogram-MLE re-estimate from the registers.

    Used (a) after cross-shard merges, where local running Ĉ's can't just be
    added (shared elements would double-count), and (b) as a self-check.

    Unlike QSketch — where every element feeds every register, making each
    register quantized-Exp(C) — a Dyn register only hears the 1/m sub-stream
    g(x) routes to it, so its law is quantized-Exp(C_j) with C_j ≈ C/m
    (stochastic averaging over the multinomial split, the same argument
    HyperLogLog's analysis uses). The QSketch MLE therefore recovers C/m and
    is scaled by m. An r_min register is the 'sub-stream produced nothing
    above r_min' event, whose probability e^{-C_j 2^{-(r_min+1)}} is exactly
    the truncated-low bin of the same likelihood (empty sub-stream -> C_j=0
    -> probability 1), so untouched registers need no special-casing.

    Fully untouched state (all registers at r_min, hist all zero): Ĉ = 0 by
    contract. The ×m scaling and the untouched guard are the estimation
    layer's ``kind="routed"`` convention (core/estimation.py) — one home for
    a guard that used to be repeated here, in ``merge`` and in
    ``dyn_array.estimate_mle_hists``.
    """
    hist = estimators.histogram(cfg, state.regs)
    return estimation.estimate_hist(cfg, hist, kind="routed")


def merge(cfg: SketchConfig, a: DynState, b: DynState) -> DynState:
    """Merge sketches of disjoint/overlapping sub-streams.

    Registers: element-wise max (exact union semantics).
    Histogram: rebuilt. Running Ĉ: re-estimated via MLE — the local running
    estimates are NOT additive when sub-streams may share elements. Merging
    two fully untouched states yields Ĉ = 0 (empty union), not an MLE
    iteration on an empty histogram.
    """
    regs = jnp.maximum(a.regs, b.regs)
    hist = jnp.zeros((cfg.num_bins,), jnp.int32).at[
        regs.astype(jnp.int32) - cfg.r_min
    ].add(1)
    hist = hist.at[0].set(0)
    # Full histogram (including untouched registers in bin 0) for the MLE;
    # the stored hist keeps the Alg.-3 'touched only' convention.
    full_hist = hist.at[0].set(cfg.m - jnp.sum(hist))
    chat = estimation.estimate_hist(cfg, full_hist, kind="routed")
    return DynState(regs=regs, hist=hist, chat=chat)


# ---------------------------------------------------------------------------
# numpy oracle (exact Alg. 3 / Eq. 12 semantics) for tests
# ---------------------------------------------------------------------------


def update_numpy(cfg: SketchConfig, ids_lo, ids_hi, weights, mask=None):
    """Pure-numpy sequential reference; returns (regs, hist, chat).

    ``mask`` mirrors the jit'd paths; degenerate (non-positive / non-finite)
    weights are likewise dropped, so the oracle verifies the live-row
    contract and never evaluates log2 of a non-positive w.
    """
    regs = np.full(cfg.m, cfg.r_min, dtype=np.int64)
    hist = np.zeros(cfg.num_bins, dtype=np.int64)
    chat = 0.0
    ks = np.arange(cfg.num_bins, dtype=np.float64) + cfg.r_min + 1.0
    s = np.exp2(-ks)
    live = np.ones(np.asarray(ids_lo).shape, bool) if mask is None else np.asarray(mask)
    for xlo, xhi, w, lv in zip(
        np.asarray(ids_lo), np.asarray(ids_hi), np.asarray(weights), live
    ):
        if not (lv and np.isfinite(w) and w > 0):
            continue
        jl = hashing.hash_mod(
            (jnp.uint32(int(xlo)), jnp.uint32(int(xhi))), cfg.salt_g, cfg.m
        )
        j = int(jl)
        e = float(
            hashing.neg_log_uniform(
                (jnp.uint32(int(xlo)), jnp.uint32(int(xhi)), jnp.uint32(j)), cfg.salt_h
            )
        )
        y = int(np.floor(np.log2(w) - np.log2(e)))
        y = min(y, cfg.r_max)
        q = max(1.0 - float(np.sum(hist * np.exp(-w * s))) / cfg.m, _QR_FLOOR)
        if y > regs[j]:
            ob = regs[j] - cfg.r_min
            if hist[ob] > 0:
                hist[ob] -= 1
            hist[y - cfg.r_min] += 1
            regs[j] = y
            chat += w / q
    return regs, hist, chat
