"""Plain reference of the sketch semantics the benchmark's cells drive.

It imports nothing of the program under test and takes nothing it made. It
restates, from the configuration file and the QSketch / QSketch-Dyn papers
(arXiv 2406.19143, Alg. 3 and Eq. 12, with q_R taken from the micro-batch's
starting state as the system's batch contract states), what every sampled
tenant slot must hold after a run:

* routing: a tenant id (lo, hi) goes to slot floor(h * K / 2^32), h the
  murmur3-style mix of its two words under the directory salt; the slot's
  claim fingerprint is the largest (nonzero) fingerprint among the tenants
  that first reach it in one routed chunk;
* an element (id, w) raises register j = floor(h_g(id) * m / 2^32) to
  y = floor(log2 w - log2(-ln u)), u from h_h(id, j), capped at r_max;
  per micro-batch, duplicates of one (slot, id) count once (first
  occurrence), q_R = 1 - (1/m) sum_k T[k] exp(-w 2^-(k+r_min+1)) comes from
  the slot's histogram of touched registers at the start of the batch, and
  the running estimate adds w / q_R for every register raised;
* a window ring keeps the last E epochs; a rotation empties the oldest
  slot, rebuilds the full-ring union from the epochs that remain and
  re-bases its running estimate to m times the maximum-likelihood estimate
  of the union's register histogram;
* a sub-ring read of w epochs is m times the MLE of the register-wise max
  of those epochs.

Integer work (hashing, dedup, registers, histograms) is exact. Register
values use the device's own log (computed on the run's device, since a
host log differs from a TPU's in the last bit), weights and estimates are
float64. ``precision="bfloat16"`` computes the same in bfloat16: the control
that the comparison has to reject.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

U32 = np.uint32
QR_FLOOR = 1e-12
SERIES_Z = 1e-6  # below it s / expm1(C s) is taken as 1/C - s/2 (error < z^2/12)
QUANT_BLOCK = 1 << 18  # elements per device call of the quantizer (one shape)


# ------------------------------------------------------------------ hashing


def _mul(a, c):
    return (a.astype(np.uint64) * np.uint64(c) & np.uint64(0xFFFFFFFF)).astype(U32)


def _rotl(x, r):
    return ((x << U32(r)) | (x >> U32(32 - r))).astype(U32)


def hash_words(words, salt: int) -> np.ndarray:
    """murmur3-style mix of uint32 words (broadcast) under ``salt``."""
    words = [np.asarray(w, U32) for w in words]
    h = np.full(np.broadcast(*words).shape, (0x9E3779B9 ^ (salt & 0xFFFFFFFF)) & 0xFFFFFFFF, U32)
    for i, w in enumerate(words):
        k = _rotl(_mul(w, 0xCC9E2D51), 15)
        k = _mul(k, 0x1B873593)
        h = _rotl(h ^ k, 13)
        h = ((h.astype(np.uint64) * 5 + (0xE6546B64 + 0x9E3779B1 * i)) & 0xFFFFFFFF).astype(U32)
    h = h ^ (h >> U32(16))
    h = _mul(h, 0x85EBCA6B)
    h = h ^ (h >> U32(13))
    h = _mul(h, 0xC2B2AE35)
    return h ^ (h >> U32(16))


def hash_range(words, salt: int, n: int) -> np.ndarray:
    """floor(h * n / 2^32): uniform on [0, n)."""
    return ((hash_words(words, salt).astype(np.uint64) * np.uint64(n)) >> np.uint64(32)).astype(np.int64)


def salts(seed: int) -> dict:
    """Per-role salts derived from a configuration's hash seed."""
    base = seed * 0x9E3779B1
    return {"h": (base + 1) & 0xFFFFFFFF, "g": (base + 2) & 0xFFFFFFFF,
            "route": (base + 11) & 0xFFFFFFFF, "fp": (base + 12) & 0xFFFFFFFF}


def route(t_lo, t_hi, k: int, seed: int) -> np.ndarray:
    """Slot of each tenant (lo, hi) in a directory of ``k`` slots."""
    return hash_range((t_lo, t_hi), salts(seed)["route"], k)


def fingerprint(t_lo, t_hi, seed: int) -> np.ndarray:
    fp = hash_words((t_lo, t_hi), salts(seed)["fp"])
    return np.where(fp == 0, U32(1), fp)


# --------------------------------------------------------------- quantizer


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _quantize(bits, w, r_min: int, r_max: int, low: bool):
    u = (bits >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0**-24) + jnp.float32(2.0**-25)
    if low:
        u, w = u.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    e = -jnp.log(u)
    y = jnp.floor(jnp.log2(w) - jnp.log2(e))
    y = jnp.minimum(y, r_max)
    y = jnp.where(jnp.isfinite(y), y, r_min)
    return y.astype(jnp.int32)


def quantize(cfg: dict, ids: np.ndarray, w: np.ndarray, low: bool) -> tuple[np.ndarray, np.ndarray]:
    """(register j, value y) of every element; y on the default device."""
    s = salts(cfg["sketch_seed"])
    zero = np.zeros_like(ids, U32)
    j = hash_range((ids, zero), s["g"], cfg["m"])
    bits = hash_words((ids, zero, j.astype(U32)), s["h"])
    r_min, r_max = -(2 ** (cfg["b"] - 1)) + 1, 2 ** (cfg["b"] - 1) - 1
    y = np.empty(len(ids), np.int64)
    for a in range(0, len(ids), QUANT_BLOCK):
        n = min(QUANT_BLOCK, len(ids) - a)
        bb = np.zeros(QUANT_BLOCK, U32)
        ww = np.ones(QUANT_BLOCK, np.float32)
        bb[:n], ww[:n] = bits[a:a + n], w[a:a + n]
        y[a:a + n] = np.asarray(_quantize(jnp.asarray(bb), jnp.asarray(ww), r_min, r_max, low))[:n]
    return j, y


# -------------------------------------------------------------- estimation


class Arith:
    """float64, or every intermediate rounded to float32 or (the control)
    bfloat16."""

    DTYPES = {"float64": None, "float32": np.float32, "bfloat16": ml_dtypes.bfloat16}

    def __init__(self, precision: str):
        if precision not in self.DTYPES:
            raise ValueError(precision)
        self.dtype = self.DTYPES[precision]
        self.low = precision == "bfloat16"

    def __call__(self, x):
        x = np.asarray(x, np.float64)
        if self.dtype is None:
            return x
        with np.errstate(over="ignore"):
            return x.astype(self.dtype).astype(np.float64)


def mle(hist_full: np.ndarray, b: int, m: int, ar: Arith) -> np.ndarray:
    """m x the maximum-likelihood estimate per row of full register-value
    histograms (bin k counts registers at value k + r_min, rows sum to m);
    0 for a row with no register touched. The score is decreasing in C, so
    bisection on log C finds its root."""
    r_min, top = -(2 ** (b - 1)) + 1, 2**b - 2
    t = np.asarray(hist_full, np.float64)
    s = np.exp2(-(np.arange(2**b, dtype=np.float64) + r_min + 1.0))
    a = 2.0 * s[top]
    interior = np.ones(2**b, bool)
    interior[[0, top]] = False

    def score(c):
        with np.errstate(over="ignore", under="ignore", divide="ignore", invalid="ignore"):
            z = ar(c[:, None] * s[None, :])
            f = np.where(z < SERIES_Z, ar(ar(1.0 / c[:, None]) - ar(1.5 * s[None, :])),
                         ar(ar(s[None, :] / ar(np.expm1(z))) - s[None, :]))
            f = np.where(interior[None, :], f, 0.0)
            za = ar(c * a)
            ftop = np.where(za < SERIES_Z, ar(ar(1.0 / c) - 0.5 * a), ar(a / ar(np.expm1(za))))
            tot = ar(np.sum(ar(t * f), axis=1))
            return ar(tot + ar(t[:, top] * ftop) - ar(t[:, 0] * s[0]))

    lo = np.full(len(t), np.log(1e-30))
    hi = np.full(len(t), np.log(1e45))
    for _ in range(80):
        mid = 0.5 * (lo + hi)
        up = score(ar(np.exp(mid))) > 0
        lo, hi = np.where(up, mid, lo), np.where(up, hi, mid)
    c = ar(np.exp(0.5 * (lo + hi)))
    return np.where(t[:, 0] >= m, 0.0, ar(m * c))


# ------------------------------------------------------------ sketch rows


class Rows:
    """QSketch-Dyn state of the sampled slots (one row each)."""

    def __init__(self, n: int, cfg: dict, ar: Arith):
        self.m, self.b, self.ar = cfg["m"], cfg["b"], ar
        self.r_min = -(2 ** (self.b - 1)) + 1
        self.regs = np.full((n, self.m), self.r_min, np.int64)
        self.hists = np.zeros((n, 2**self.b), np.int64)
        self.chats = np.zeros(n, np.float64)
        self.scales = np.exp2(-(np.arange(2**self.b, dtype=np.float64) + self.r_min + 1.0))

    def update(self, row, ids, j, y, w) -> None:
        """One micro-batch of sampled elements, in arrival order."""
        if not len(row):
            return
        key = (row.astype(np.uint64) << np.uint64(32)) | ids.astype(np.uint64)
        _, first = np.unique(key, return_index=True)
        first = np.sort(first)
        row, j, y, w = row[first], j[first], y[first], w[first]
        ch = y > self.regs[row, j]
        row, j, y, w = row[ch], j[ch], y[ch], w[ch]
        if not len(row):
            return
        ar = self.ar
        wd = ar(w)
        expo = ar(np.exp(ar(-wd[:, None] * self.scales[None, :])))
        q = ar(1.0 - ar(ar(np.sum(ar(self.hists[row] * expo), axis=1)) / self.m))
        q = np.maximum(q, QR_FLOOR)
        inc = ar(wd / q)
        add = np.zeros_like(self.chats)
        np.add.at(add, row, inc)
        self.chats = ar(self.chats + add)
        np.maximum.at(self.regs, (row, j), y)
        touched = np.unique(row)
        self.hists[touched] = touched_hists(self.regs[touched], self.b)



def touched_hists(regs: np.ndarray, b: int) -> np.ndarray:
    """Histogram of register values per row, untouched (r_min) bin zeroed."""
    r_min = -(2 ** (b - 1)) + 1
    n = 2**b
    idx = (regs - r_min) + n * np.arange(len(regs))[:, None]
    h = np.bincount(idx.ravel(), minlength=n * len(regs)).reshape(len(regs), n)
    h[:, 0] = 0
    return h


def full_hists(regs: np.ndarray, b: int, m: int) -> np.ndarray:
    h = touched_hists(regs, b)
    h[:, 0] = m - h.sum(axis=1)
    return h


# ----------------------------------------------------------------- a run


@dataclasses.dataclass
class Plan:
    """What a run did, in event counts of the stream (warm-up excluded).

    events: events pushed; rotations: the event count at each rotation made
    in the window (the warm-up rotation closes the empty epoch 0 and is not
    listed); batch: micro-batch size; warm_events: the zero-weight warm-up
    events routed before the stream (the stream's own first tenants);
    reads: (kind, events included, epoch) of the reads to reproduce, kind
    "anytime" or "subring".
    """

    events: int
    rotations: list
    batch: int
    warm_events: int
    reads: list = dataclasses.field(default_factory=list)


class Sampled:
    """The sampled slots' sub-stream of a replayed stream."""

    def __init__(self, stream, cfg: dict, sample: np.ndarray):
        self.stream, self.cfg = stream, cfg
        self.sample = np.asarray(sample, np.int64)
        slot = route(stream.t_lo, stream.t_hi, cfg["k"], cfg["directory_seed"])
        self.pool_slot = slot
        row = np.full(cfg["k"], -1, np.int64)
        row[self.sample] = np.arange(len(self.sample))
        self.pool_row = row[slot]
        self.pool_sel = np.nonzero(self.pool_row >= 0)[0]

    def events(self, start: int, stop: int):
        """(global index, row, id, weight) of sampled events in [start, stop)."""
        p = self.stream.pool_events
        out = []
        for c in range(start // p, -(-stop // p)):
            g = c * p + self.pool_sel
            g = g[(g >= start) & (g < stop)]
            out.append(g)
        g = np.concatenate(out) if out else np.zeros(0, np.int64)
        if not len(g):
            e = np.zeros(0, np.int64)
            return e, e, np.zeros(0, U32), np.zeros(0, np.float32)
        ev = self.stream.events(int(g[0]), int(g[-1]) + 1)
        off = g - g[0]
        pos = g % p
        return g, self.pool_row[pos], ev.ids[off], ev.w[off]

    def fingerprints(self, plan: Plan) -> np.ndarray:
        """Claim fingerprint of each sampled slot after the run's routing:
        the warm-up routes the stream's first ``warm_events`` tenants, then
        the window routes ``plan.events``, chunk by chunk."""
        st, chunk = self.stream, self.stream.chunk_len
        fp_pool = fingerprint(st.t_lo, st.t_hi, self.cfg["directory_seed"])
        out = np.zeros(len(self.sample), U32)
        limit = min(max(plan.events, plan.warm_events), st.pool_events)
        pos = np.nonzero(self.pool_row[:limit] >= 0)[0]
        row = self.pool_row[pos]
        uniq, first = np.unique(row, return_index=True)
        claim_chunk = np.full(len(self.sample), -1)
        claim_chunk[uniq] = pos[first] // chunk
        hit = pos // chunk == claim_chunk[row]
        np.maximum.at(out, row[hit], fp_pool[pos[hit]])
        return out


def _batches(start: int, stop: int, size: int):
    a = start
    while a < stop:
        yield a, min(a + size, stop)
        a += size


def _feed(rows: list, sub, start: int, stop: int, batch: int, cfg: dict, low: bool, hook=None):
    """Feed events [start, stop) in micro-batches to every ``Rows`` in
    ``rows``; ``hook(batch_end)`` runs after each batch."""
    g, row, ids, w = sub.events(start, stop)
    j, y = quantize(cfg, ids, w, low)
    for a, b in _batches(start, stop, batch):
        lo, hi = np.searchsorted(g, [a, b])
        for r in rows:
            r.update(row[lo:hi], ids[lo:hi], j[lo:hi], y[lo:hi], w[lo:hi])
        if hook is not None:
            hook(b)


def dyn(stream, cfg: dict, sample, plan: Plan, precision: str = "float64") -> dict:
    """Reference state of the sampled DynArray rows after ``plan``."""
    ar = Arith(precision)
    sub = Sampled(stream, cfg, sample)
    rows = Rows(len(sample), cfg, ar)
    _feed([rows], sub, 0, plan.events, plan.batch, cfg, ar.low)
    return {"regs": rows.regs, "hists": rows.hists, "chats": rows.chats,
            "fingerprints": sub.fingerprints(plan),
            "scalars": np.array([plan.warm_events + plan.events])}


def window(stream, cfg: dict, sample, plan: Plan, precision: str = "float64", w_sub: int = 2) -> dict:
    """Reference state of the sampled WindowArray rows (every ring epoch and
    the union) after ``plan``, and the reads ``plan.reads`` asks for."""
    ar = Arith(precision)
    E, b, m = cfg["epochs"], cfg["b"], cfg["m"]
    sub = Sampled(stream, cfg, sample)
    bounds = [0] + list(plan.rotations) + [plan.events]
    last = len(bounds) - 1  # epochs 1..last hold events; epoch 0 is the warm-up
    first_read = min([r[2] for r in plan.reads], default=last)
    first = max(1, min(last, first_read) - E + 1)
    epochs: dict[int, Rows] = {}
    union = Rows(len(sample), cfg, ar)
    reads: dict[str, np.ndarray] = {}
    wanted = {(r[2], r[1]): r[0] for r in plan.reads}

    def ring_regs(t, w):
        regs = [epochs[u].regs for u in range(t - w + 1, t + 1) if u in epochs]
        return np.max(np.stack(regs), axis=0) if regs else np.full_like(union.regs, union.r_min)

    def snapshot(t, n):
        kind = wanted.get((t, n))
        if kind == "anytime":
            reads["anytime"] = union.chats.copy()
        elif kind == "subring":
            reads["subring"] = mle(full_hists(ring_regs(t, w_sub), b, m), b, m, ar)

    for t in range(first, last + 1):
        epochs[t] = Rows(len(sample), cfg, ar)
        epochs.pop(t - E, None)
        if t >= min(last, first_read):
            union.regs = ring_regs(t - 1, E - 1)
            union.hists = touched_hists(union.regs, b)
            union.chats = mle(full_hists(union.regs, b, m), b, m, ar)
        snapshot(t, bounds[t - 1])
        track = [epochs[t]] + ([union] if t >= min(last, first_read) else [])
        _feed(track, sub, bounds[t - 1], bounds[t], plan.batch, cfg, ar.low,
              hook=lambda n, t=t: snapshot(t, n))

    nil = Rows(len(sample), cfg, ar)
    ring = [epochs.get(t, nil) for t in range(last - E + 1, last + 1)]
    order = np.argsort([t % E for t in range(last - E + 1, last + 1)])
    ring = [ring[i] for i in order]
    return {
        "regs": np.stack([r.regs for r in ring]),
        "hists": np.stack([r.hists for r in ring]),
        "chats": np.stack([r.chats for r in ring]),
        "union_regs": union.regs, "union_hists": union.hists, "union_chats": union.chats,
        "fingerprints": sub.fingerprints(plan),
        "scalars": np.array([last % E, min(last + 1, E), last, plan.warm_events + plan.events]),
        "reads": reads,
    }


def pick_sample(stream, cfg: dict, events: int, spec: dict, seed: int) -> np.ndarray:
    """Slots to compare, drawn from the seed: the hottest of the window's
    events, random other touched slots, and random untouched slots."""
    k, p = cfg["k"], stream.pool_events
    slot = route(stream.t_lo, stream.t_hi, k, cfg["directory_seed"])
    counts = (events // p) * np.bincount(slot, minlength=k) + np.bincount(slot[: events % p], minlength=k)
    hot = np.argsort(-counts, kind="stable")[: spec["hot"]]
    rng = np.random.default_rng([seed, 1])
    touched = np.setdiff1d(np.nonzero(counts)[0], hot)
    cold = rng.choice(touched, min(spec["touched"], len(touched)), replace=False)
    idle = np.nonzero(counts == 0)[0]
    untouched = rng.choice(idle, min(spec["untouched"], len(idle)), replace=False)
    return np.sort(np.concatenate([hot, cold, untouched])).astype(np.int64)
