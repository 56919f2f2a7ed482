"""Smoke run of the tenant ingest -> windowed-estimate path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded window path, four chips

One chip. A Zipf(1.2)-bursty stream (``benchmarks.ingest.zipf_bursty_chunks``,
made from ``--seed``) of sparse 64-bit tenant ids is routed through the key
directory into a WindowArray of K = 2^20 tenant slots, m = 128, b = 8 and
E = 4 epochs by ``TenantWindowIngest`` (donated micro-batch updates, a
rotation behind the retire barrier every 16 micro-batches, 2E rotations).
It is read back three ways: the anytime full-ring read, the full-ring MLE
and a sub-ring window through the ``window_union`` kernel. The same stream
then runs through two K = 2^20 DynArray ``dyn_pipeline``s: the Pallas q_R
route and the jnp plan/commit route.

Checks (any failure exits non-zero):

* the kernel route's compiled executable holds a Mosaic ``tpu_custom_call``;
* the kernel and jnp DynArray states are bit-identical, and so are the
  kernel and jnp sub-ring reads;
* for a sample of hot, cold and untouched slots, every epoch's registers and
  histograms, the union's, and the DynArray rows equal an oracle (each
  slot's sub-stream, split out on the host, fed micro-batch by micro-batch
  to a single ``qsketch_dyn.update_batch`` sketch); per-epoch and DynArray
  chats agree to float32 association order;
* each estimate of a sampled slot lies within 4 of the sketch's stated
  standard errors of the exact weighted cardinality of its window, computed
  on the host from the stream. MLE reads use the stddev the estimator
  reports; anytime martingale reads use 1/sqrt(m). Windowed MLE reads (and
  the anytime read they re-base at each rotation) are stated only for rows
  with every register touched in the window (DESIGN.md §8.5), so slots
  outside that regime are held to the register checks alone; untouched
  slots must read exactly 0.

With ``--four-chips`` only the sharded path runs: ``TenantWindowIngest``
on a 4-way sketch mesh and on device 0 alone, same stream, and every state
leaf and read must be bit-identical, with the rows spread over all four
devices.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The run refuses to start without a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.ingest import zipf_bursty_chunks  # noqa: E402
from repro.core import (  # noqa: E402
    SketchConfig,
    dyn_array,
    estimation,
    estimators,
    key_directory,
    qsketch_dyn,
    sharded_window_array,
    window_array,
)
from repro.kernels import ops  # noqa: E402
from repro.sketchstream import ingest  # noqa: E402

Z = 4.0  # standard errors allowed between an estimate and the exact value
# Distinct elements per register from which the routed (Dyn) MLE is held to
# its stated error: below it the MLE is biased low (m = 128, gamma weights:
# -26% at 8 per register, -6% at 24, within 1% from 48; PERF.md).
MLE_MIN_LOAD = 32


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Deployment shape of one smoke run (defaults: the chip run)."""

    k: int = 2**20
    m: int = 128
    b: int = 8
    epochs: int = 4
    batch: int = 16384
    batches_per_epoch: int = 16
    rotations: int = 8
    n_hot: int = 32
    n_cold: int = 32
    n_untouched: int = 8
    min_in_regime: int = 8  # sampled slots each windowed read must cover
    zipf_s: float = 1.2
    seed: int = 0

    @property
    def epoch_elems(self) -> int:
        return self.batch * self.batches_per_epoch

    @property
    def n_elems(self) -> int:
        return self.epoch_elems * (self.rotations + 1)


def log(*a) -> None:
    print(*a, flush=True)


def require_tpu():
    """The devices, or SystemExit when JAX's first device is not a TPU."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX's first device is "
            f"{devs[0].platform!r} ({devs[0].device_kind}); refusing to run"
        )
    return devs


# --------------------------------------------------------------------- stream


def _splitmix64(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def make_stream(sz: Sizes) -> dict:
    """The seeded event stream, flat, in arrival order.

    Tenant popularity ranks from ``zipf_bursty_chunks`` become sparse
    64-bit tenant ids (split into uint32 lo/hi words). Each element id keeps
    one weight, the one of its first arrival: weight is a function of the
    element, the paper's weighted-stream model, so the exact weighted
    cardinality of any sub-stream is the weight sum over its distinct ids.
    """
    chunks = zipf_bursty_chunks(sz.k, sz.n_elems, s=sz.zipf_s, seed=sz.seed)
    ranks, ids, w = (np.concatenate([c[i] for c in chunks]) for i in range(3))
    tid = _splitmix64(ranks.astype(np.uint64) ^ np.uint64(sz.seed * 0x632BE59BD9B4E019))
    _, first, inv = np.unique(ids, return_index=True, return_inverse=True)
    return {
        "t_lo": (tid & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        "t_hi": (tid >> np.uint64(32)).astype(np.uint32),
        "ids": ids.astype(np.uint32),
        "w": w[first][inv].astype(np.float32),
        "chunk": len(chunks[0][0]),
    }


def host_slots(dcfg, stream) -> np.ndarray:
    """Slot of every element (the directory's stateless tenant hash)."""
    tid = stream["t_lo"].astype(np.uint64) | (stream["t_hi"].astype(np.uint64) << np.uint64(32))
    uniq, inv = np.unique(tid, return_inverse=True)
    pair = (
        jnp.asarray((uniq & np.uint64(0xFFFFFFFF)).astype(np.uint32)),
        jnp.asarray((uniq >> np.uint64(32)).astype(np.uint32)),
    )
    return np.asarray(key_directory.route_slots(dcfg, pair))[inv]


def pick_sample(sz: Sizes, slots: np.ndarray) -> np.ndarray:
    """Hottest slots, random touched slots and untouched slots, sorted."""
    counts = np.bincount(slots, minlength=sz.k)
    hot = np.argsort(-counts, kind="stable")[: sz.n_hot]
    rng = np.random.default_rng(sz.seed + 1)
    touched = np.setdiff1d(np.nonzero(counts)[0], hot)
    cold = rng.choice(touched, min(sz.n_cold, len(touched)), replace=False)
    untouched = rng.choice(np.nonzero(counts == 0)[0], sz.n_untouched, replace=False)
    return np.sort(np.concatenate([hot, cold, untouched])).astype(np.int64)


# --------------------------------------------------------------- ingest runs


def _time_compile(compile_s: dict, name: str, jitted, *args, **kwargs):
    """Compile ``jitted`` for these arguments ahead of the run (the later
    calls reuse the executable) and record the seconds it took."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args, **kwargs).compile()
    compile_s[name] = time.perf_counter() - t0
    return compiled


def _push_epochs(sz: Sizes, stream, push, rotate) -> None:
    """Push the stream in arrival chunks, rotating after each full epoch."""
    c = stream["chunk"]
    for e in range(sz.rotations + 1):
        end = (e + 1) * sz.epoch_elems
        for off in range(e * sz.epoch_elems, end, c):
            sl = slice(off, min(off + c, end))
            push((stream["t_lo"][sl], stream["t_hi"][sl]), stream["ids"][sl], stream["w"][sl])
        if e < sz.rotations:
            rotate()


def run_window(sz: Sizes, stream, *, interpret: bool, mesh=None) -> dict:
    """Ingest the stream into a WindowArray through ``TenantWindowIngest``
    and take its three reads. Returns the settled state, the reads, the
    compile seconds per executable and the ingest wall seconds."""
    cfg = SketchConfig(m=sz.m, b=sz.b)
    dcfg = key_directory.DirectoryConfig(capacity=sz.k)
    icfg = ingest.IngestConfig(batch_size=sz.batch, queue_depth=4)
    tw = ingest.TenantWindowIngest(cfg, dcfg, sz.epochs, icfg, mesh=mesh)

    compile_s: dict = {}
    st0 = tw.pipe.state
    b = sz.batch
    z = (jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.uint32), jnp.ones(b, jnp.float32),
         jnp.zeros(b, bool))
    c = stream["chunk"]
    pair = (jnp.zeros(c, jnp.uint32), jnp.zeros(c, jnp.uint32))
    _time_compile(compile_s, "directory_route", key_directory.route, dcfg, tw.directory,
                  pair, mask=None, epoch=jnp.int32(0))
    if mesh is None:
        _time_compile(compile_s, "window_update", ingest._window_update_fn(cfg), st0, *z)
        _time_compile(compile_s, "window_rotate", window_array._rotate_donated, cfg, st0)

    t0 = time.perf_counter()
    _push_epochs(sz, stream, tw.push, tw.rotate)
    state = tw.result()
    wall = time.perf_counter() - t0

    w_sub = sz.epochs // 2
    t1 = time.perf_counter()
    if mesh is None:
        reads = {
            "anytime": window_array.estimate_ring_anytime(state),
            "full_ring": window_array.estimate_window(cfg, state, sz.epochs),
            "sub_ring": ops.window_union_estimate_op(cfg, state, w_sub, interpret=interpret),
            "sub_ring_jnp": window_array.estimate_window(cfg, state, w_sub),
        }
    else:
        reads = {
            "anytime": sharded_window_array.estimate_ring_anytime(state),
            "full_ring": sharded_window_array.estimate_window(cfg, mesh, state, sz.epochs),
            "sub_ring": ops.sharded_window_union_estimate_op(
                cfg, mesh, state, w_sub, interpret=interpret
            ),
        }
    jax.block_until_ready(reads)
    return {
        "cfg": cfg, "dcfg": dcfg, "state": state, "reads": reads, "w_sub": w_sub,
        "compile_s": compile_s, "wall_s": wall, "read_s": time.perf_counter() - t1,
        "metrics": tw.metrics(),
    }


def run_dyn(sz: Sizes, stream, *, use_kernel: bool, interpret: bool) -> dict:
    """Ingest the stream into a DynArray through ``dyn_pipeline``: tenants
    routed by the key directory, then the kernel or the jnp route."""
    cfg = SketchConfig(m=sz.m, b=sz.b)
    dcfg = key_directory.DirectoryConfig(capacity=sz.k)
    icfg = ingest.IngestConfig(batch_size=sz.batch, queue_depth=4)
    pipe = ingest.dyn_pipeline(
        cfg, dyn_array.init(cfg, sz.k), icfg, use_kernel=use_kernel, interpret=interpret
    )
    directory = key_directory.init(dcfg)

    compile_s: dict = {}
    hlo = None
    b = sz.batch
    z = (jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.uint32), jnp.ones(b, jnp.float32),
         jnp.zeros(b, bool))
    if use_kernel:
        fn = ingest._dyn_update_fn(cfg, True, interpret)
        hlo = _time_compile(compile_s, "dyn_update_kernel", fn, pipe.state, *z).as_text()
    else:
        _time_compile(compile_s, "dyn_plan", dyn_array._plan_batch_jit, cfg, pipe.state, *z)
        shapes = jax.eval_shape(lambda *a: dyn_array._plan_batch(cfg, *a), pipe.state, *z)
        plan0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        _time_compile(compile_s, "dyn_commit", dyn_array._commit_donated, pipe.state, plan0)

    def push(tenant_pair, ids, w):
        nonlocal directory
        slots, directory = key_directory.route(dcfg, directory, tenant_pair)
        pipe.push(np.asarray(slots), ids, w)

    t0 = time.perf_counter()
    _push_epochs(sz, stream, push, lambda: None)
    state = pipe.result()
    return {"state": state, "hlo": hlo, "compile_s": compile_s,
            "wall_s": time.perf_counter() - t0}


# ------------------------------------------------------------------- oracle


def _bucket(n: int) -> int:
    return max(16, 1 << (n - 1).bit_length())


def oracle_rows(cfg, stream, slots, sample, batch_of, batches) -> dict:
    """Oracle for the sampled slots: each slot's sub-stream of the given
    micro-batches, split out on the host, fed batch by batch to a fresh
    single QSketch-Dyn (``qsketch_dyn.update_batch``). It runs on the run's
    own device: the TPU's log differs from the CPU's in the last bit, which
    moves about 1 in 10^4 quantized register values across an integer, so a
    CPU oracle cannot match TPU registers bit for bit. Sub-batches are
    padded to power-of-two lengths with masked rows (masked rows are no-ops
    by contract) so a handful of shapes compile."""
    sel = np.isin(slots, sample) & np.isin(batch_of, batches)
    idx = np.nonzero(sel)[0]
    order = np.lexsort((idx, batch_of[idx], slots[idx]))
    idx = idx[order]
    s_of, b_of = slots[idx], batch_of[idx]
    out = {"regs": [], "hists": [], "chats": []}
    for s in sample:
        st = qsketch_dyn.init(cfg)
        lo_s, hi_s = np.searchsorted(s_of, [s, s + 1])
        row = idx[lo_s:hi_s]
        rb = b_of[lo_s:hi_s]
        for bt in np.unique(rb):
            e = row[rb == bt]
            n, p = len(e), _bucket(len(e))
            ids = np.zeros(p, np.uint32)
            w = np.ones(p, np.float32)
            ids[:n], w[:n] = stream["ids"][e], stream["w"][e]
            st = qsketch_dyn.update_batch(
                cfg, st, jnp.asarray(ids), jnp.asarray(w), jnp.arange(p) < n
            )
        out["regs"].append(np.asarray(st.regs))
        out["hists"].append(np.asarray(st.hist))
        out["chats"].append(float(st.chat))
    return {k: np.stack(v) for k, v in out.items()}


def exact_cardinality(stream, slots, sample, elem_range):
    """(weight sum, count) over the distinct ids of each sampled slot's
    elements in ``elem_range`` (a slice of the arrival order)."""
    s = slots[elem_range]
    ids = stream["ids"][elem_range].astype(np.int64)
    w = stream["w"][elem_range].astype(np.float64)
    keep = np.isin(s, sample)
    pairs = np.stack([s[keep], ids[keep]], axis=1)
    _, first = np.unique(pairs, axis=0, return_index=True)
    row = np.searchsorted(sample, s[keep][first])
    tot = np.zeros(len(sample))
    np.add.at(tot, row, w[keep][first])
    return tot, np.bincount(row, minlength=len(sample))


def _touched_hists(cfg, regs: np.ndarray) -> np.ndarray:
    h = np.stack([np.bincount(r.astype(np.int64) - cfg.r_min, minlength=cfg.num_bins) for r in regs])
    h[:, 0] = 0
    return h.astype(np.int32)


# ------------------------------------------------------------------- checks


class Checks:
    """Collects named pass/fail results; ``ok`` iff every check passed."""

    def __init__(self):
        self.failed: list[str] = []
        self.n = 0

    def expect(self, name: str, ok: bool, detail: str = "") -> None:
        self.n += 1
        if not ok:
            self.failed.append(f"{name}: {detail}" if detail else name)
            log(f"CHECK FAILED {name} {detail}")

    def equal(self, name: str, got, want) -> None:
        got, want = np.asarray(got), np.asarray(want)
        bad = int(np.sum(got != want)) if got.shape == want.shape else -1
        self.expect(name, bad == 0, f"{bad} mismatches" if bad >= 0 else
                    f"shape {got.shape} != {want.shape}")

    def close(self, name: str, got, want, rtol=1e-5, atol=1e-6) -> None:
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        dev = np.abs(got - want)
        bad = dev > atol + rtol * np.abs(want)
        worst = float(np.max(dev / np.maximum(np.abs(want), atol))) if dev.size else 0.0
        self.expect(name, not bad.any(),
                    f"{int(bad.sum())} outside rtol={rtol}, worst rel {worst:.3g}")

    @property
    def ok(self) -> bool:
        return not self.failed


def check_estimates(chk: Checks, name, est, exact, err, regime, min_in_regime) -> None:
    """Rows in regime: |est - exact| <= Z * err. Untouched rows (exact 0)
    read exactly 0. At least ``min_in_regime`` sampled rows are in regime."""
    est = np.asarray(est, np.float64)
    untouched = exact == 0
    chk.expect(f"{name}: untouched slots read 0", bool(np.all(est[untouched] == 0.0)))
    rows = regime & ~untouched
    chk.expect(f"{name}: >= {min_in_regime} sampled slots in regime",
               int(rows.sum()) >= min_in_regime, f"{int(rows.sum())} in regime")
    dev = np.abs(est - exact)[rows] / np.maximum(err[rows], 1e-30)
    worst = float(dev.max()) if rows.any() else 0.0
    chk.expect(f"{name}: within {Z} stated errors", worst <= Z, f"worst {worst:.3f}")
    rel = np.abs(est - exact)[rows] / exact[rows]
    log(f"  {name}: {int(rows.sum())} slots in regime, worst {worst:.3f} errors, "
        f"median |rel err| {float(np.median(rel)) if rows.any() else 0.0:.4f}")


def check_mle_read(chk: Checks, cfg, name, est, oracle_regs) -> None:
    """A windowed MLE read equals the float64 reference MLE of the oracle's
    union registers (``estimators.mle_numpy``, scaled by m as the routed
    convention does) to the estimation layer's tolerance, on every sampled
    slot, in regime or not."""
    ref = np.array([cfg.m * estimators.mle_numpy(cfg, r) for r in oracle_regs])
    tol = cfg.m * estimation.ATOL_FLOOR + estimation.LUT_RTOL * np.abs(ref)
    bad = np.abs(np.asarray(est, np.float64) - ref) > tol
    chk.expect(f"{name} == float64 MLE of oracle registers", not bad.any(),
               f"{int(bad.sum())} slots outside tolerance")


def reference(sz: Sizes, stream) -> dict:
    """Host-side view of the stream the checks share: every element's slot
    and micro-batch, and the sampled slots."""
    slots = host_slots(key_directory.DirectoryConfig(capacity=sz.k), stream)
    sample = pick_sample(sz, slots)
    log(f"  sample: {len(sample)} slots ({sz.n_hot} hot, {sz.n_cold} cold, "
        f"{sz.n_untouched} untouched)")
    return {"slots": slots, "sample": sample,
            "batch_of": np.arange(sz.n_elems) // sz.batch}


def verify_dyn(sz: Sizes, stream, ref: dict, dyn_k: dict, dyn_j: dict, chk: Checks) -> None:
    """DynArray checks: the kernel route equals the jnp route bitwise, the
    sampled rows equal the oracle, the anytime reads the exact values."""
    cfg = SketchConfig(m=sz.m, b=sz.b)
    slots, sample = ref["slots"], ref["sample"]
    rows = jnp.asarray(sample)
    for leaf in ("regs", "hists", "chats"):
        a, b = getattr(dyn_k["state"], leaf), getattr(dyn_j["state"], leaf)
        chk.expect(f"dyn kernel == jnp route: {leaf}", bool(jnp.array_equal(a, b)))
    orc = oracle_rows(cfg, stream, slots, sample, ref["batch_of"],
                      np.arange(sz.n_elems // sz.batch))
    dk = dyn_k["state"]
    chk.equal("dyn rows == oracle: regs", dk.regs[rows], orc["regs"])
    chk.equal("dyn rows == oracle: hists", dk.hists[rows], orc["hists"])
    chk.close("dyn rows == oracle: chats", dk.chats[rows], orc["chats"])
    exact_all, _ = exact_cardinality(stream, slots, sample, slice(None))
    check_estimates(chk, "dyn anytime", dk.chats[rows], exact_all,
                    exact_all / np.sqrt(cfg.m), exact_all > 0, sz.min_in_regime)


def verify_window(sz: Sizes, stream, ref: dict, win: dict, chk: Checks) -> None:
    """WindowArray checks: every retained epoch and the union equal the
    oracle on the sampled slots, the kernel sub-ring read equals the jnp
    one, and the three reads sit within the stated error of the exact
    values (see the module docstring)."""
    cfg = win["cfg"]
    E, R, bpe = sz.epochs, sz.rotations, sz.batches_per_epoch
    slots, sample, batch_of = ref["slots"], ref["sample"], ref["batch_of"]
    rows = jnp.asarray(sample)
    # The retained epochs R-E+1..R live in ring slots t % E.
    st = win["state"]
    chk.expect("window head == R mod E", int(st.head) == R % E, f"head {int(st.head)}")
    epoch_regs = {}
    for t in range(R - E + 1, R + 1):
        o = oracle_rows(cfg, stream, slots, sample, batch_of, np.arange(t * bpe, (t + 1) * bpe))
        epoch_regs[t] = o["regs"]
        chk.equal(f"epoch {t} regs == oracle", st.regs[t % E][rows], o["regs"])
        chk.equal(f"epoch {t} hists == oracle", st.hists[t % E][rows], o["hists"])
        chk.close(f"epoch {t} chats == oracle", st.chats[t % E][rows], o["chats"])

    def union_of(first):
        return np.max(np.stack([epoch_regs[t] for t in range(first, R + 1)]), axis=0)

    ring = union_of(R - E + 1)
    chk.equal("union regs == max of oracle epochs", st.union_regs[rows], ring)
    chk.equal("union hists == oracle", st.union_hists[rows], _touched_hists(cfg, ring))
    chk.expect("sub-ring kernel read == jnp read (all K)",
               bool(jnp.array_equal(win["reads"]["sub_ring"], win["reads"]["sub_ring_jnp"])))
    reads = {k: np.asarray(v)[sample] for k, v in win["reads"].items()}

    def window(first, last=R):
        """Exact (weight, distinct count) of epochs first..last, and the
        rows where the routed MLE is stated: every register touched and at
        least MLE_MIN_LOAD distinct elements per register."""
        span = slice(first * sz.epoch_elems, (last + 1) * sz.epoch_elems)
        c, n = exact_cardinality(stream, slots, sample, span)
        regs = np.max(np.stack([epoch_regs[t] for t in range(first, last + 1)]), axis=0)
        return c, regs, np.all(regs > cfg.r_min, axis=1) & (n >= MLE_MIN_LOAD * cfg.m)

    def mle_std(regs):
        h = _touched_hists(cfg, regs)
        h[:, 0] = cfg.m - h.sum(axis=1)
        _, std, _ = estimation.estimate_hists_with_ci(cfg, jnp.asarray(h), kind="routed")
        return np.asarray(std, np.float64)

    c_ring, regs_ring, ok_ring = window(R - E + 1)
    check_mle_read(chk, cfg, "full-ring read", reads["full_ring"], regs_ring)
    check_estimates(chk, "full-ring MLE", reads["full_ring"], c_ring, mle_std(regs_ring),
                    ok_ring, sz.min_in_regime)
    # The anytime ring read re-bases to the MLE of the union at each
    # rotation; the last one saw epochs R-E+1..R-1.
    _, _, ok_rot = window(R - E + 1, R - 1)
    check_estimates(chk, "anytime ring", reads["anytime"], c_ring, c_ring / np.sqrt(cfg.m),
                    ok_rot, sz.min_in_regime)
    w = win["w_sub"]
    c_sub, regs_sub, ok_sub = window(R - w + 1)
    check_mle_read(chk, cfg, f"sub-ring (w={w}) kernel read", reads["sub_ring"], regs_sub)
    check_estimates(chk, f"sub-ring (w={w}) kernel read", reads["sub_ring"], c_sub,
                    mle_std(regs_sub), ok_sub, sz.min_in_regime)


# --------------------------------------------------------------------- main


def _state_bytes(state) -> int:
    return int(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state)))


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def one_chip(sz: Sizes, *, interpret: bool = False) -> Checks:
    """The one-chip phase: window ingest + reads and their checks, then
    both DynArray routes and theirs (the window state is dropped first, so
    the two never share the device's memory)."""
    chk = Checks()
    stream = make_stream(sz)
    log(f"stream: {sz.n_elems} events, {sz.rotations} rotations x "
        f"{sz.batches_per_epoch} micro-batches of {sz.batch}, zipf s={sz.zipf_s}, "
        f"seed {sz.seed}")
    ref = reference(sz, stream)

    win = run_window(sz, stream, interpret=interpret)
    log(f"window: K={sz.k} m={sz.m} b={sz.b} E={sz.epochs} state "
        f"{_state_bytes(win['state'])} B; ingest {win['wall_s']:.3f} s "
        f"({sz.n_elems / win['wall_s'] / 1e6:.4f} Mevents/s), reads {win['read_s']:.3f} s")
    log(f"  compile s: {json.dumps({k: round(v, 3) for k, v in win['compile_s'].items()})}")
    log(f"  metrics: {json.dumps(win['metrics'])}")
    verify_window(sz, stream, ref, win, chk)
    del win

    dyn_k = run_dyn(sz, stream, use_kernel=True, interpret=interpret)
    dyn_j = run_dyn(sz, stream, use_kernel=False, interpret=interpret)
    for name, d in (("kernel", dyn_k), ("jnp", dyn_j)):
        log(f"dyn[{name}]: K={sz.k} state {_state_bytes(d['state'])} B; ingest "
            f"{d['wall_s']:.3f} s ({sz.n_elems / d['wall_s'] / 1e6:.4f} Mevents/s); "
            f"compile s: {json.dumps({k: round(v, 3) for k, v in d['compile_s'].items()})}")
    if not interpret:
        chk.expect("dyn kernel route lowered to Mosaic (tpu_custom_call)",
                   "tpu_custom_call" in dyn_k["hlo"])
    verify_dyn(sz, stream, ref, dyn_k, dyn_j, chk)
    return chk


def four_chips(sz: Sizes, *, interpret: bool = False) -> Checks:
    """The four-chip phase: sharded vs single-device window ingest."""
    from repro.launch.mesh import make_sketch_mesh

    chk = Checks()
    stream = make_stream(sz)
    mesh = make_sketch_mesh(4)
    sharded = run_window(sz, stream, interpret=interpret, mesh=mesh)
    log(f"sharded window (4 shards): ingest {sharded['wall_s']:.3f} s, reads "
        f"{sharded['read_s']:.3f} s, state {_state_bytes(sharded['state'])} B")
    with jax.default_device(jax.devices()[0]):
        single = run_window(sz, stream, interpret=interpret)
    log(f"single-device window: ingest {single['wall_s']:.3f} s, reads "
        f"{single['read_s']:.3f} s")

    devs = set(mesh.devices.flat)
    for name, leaf in sharded["state"]._asdict().items():
        if leaf.ndim == 0:
            continue
        row_dim = 0 if name.startswith("union_") else 1  # epoch planes: [E, K, ...]
        shards = leaf.addressable_shards
        starts = sorted(s.index[row_dim].start or 0 for s in shards)
        chk.expect(f"sharded {name}: rows over 4 devices",
                   {s.device for s in shards} == devs and len(set(starts)) == 4
                   and all(s.data.shape[row_dim] == sz.k // 4 for s in shards),
                   f"starts {starts}")
        ref = jax.device_put(getattr(single["state"], name), leaf.sharding)
        chk.expect(f"sharded == single: {name}", bool(jnp.array_equal(leaf, ref)))
    for name in ("anytime", "full_ring", "sub_ring"):
        a = sharded["reads"][name]
        chk.expect(f"sharded == single read: {name}",
                   bool(jnp.array_equal(a, jax.device_put(single["reads"][name], a.sharding))))
    return chk


def result_line(devs) -> str:
    """The run's last stdout line: ok, and the devices as JAX reports them."""
    d = devs[0]
    return json.dumps(
        {"ok": True, "device": {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}}
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded-vs-single window comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = require_tpu()
    from repro.launch import compile_cache

    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; jax "
        f"{jax.__version__}; compile cache {compile_cache.enable()}")
    if args.four_chips and len(devs) < 4:
        raise SystemExit(f"chip_smoke --four-chips: needs 4 devices, found {len(devs)}")
    sz = Sizes(seed=args.seed)
    t0 = time.perf_counter()
    chk = four_chips(sz) if args.four_chips else one_chip(sz)
    for d in devs[: 4 if args.four_chips else 1]:
        log(f"peak_bytes_in_use[{d.id}]: {_peak_bytes(d)}")
    log(f"checks: {chk.n - len(chk.failed)}/{chk.n} passed; wall {time.perf_counter() - t0:.3f} s")
    if not chk.ok:
        log("FAILED: " + "; ".join(chk.failed))
        return 1
    print(result_line(devs))
    return 0


if __name__ == "__main__":
    sys.exit(main())
