"""Unified estimation layer: the histogram→Ĉ solve behind one API.

Every container in the repo ultimately answers the same question — "given
this sketch row's register-value histogram, what is the ML weighted
cardinality?" — but before this module the solve was copy-threaded through
nine call sites (single sketch, SketchArray, DynArray, WindowArray, the
three sharded fronts, the monitors, and ``kernels/ops.py``). This layer owns
that solve behind one API with a pluggable solver registry (DESIGN.md §8.7):

* ``estimate_rows(cfg, regs)``   — ``int8[K, m]`` register rows → ``Ĉ[K]``;
* ``estimate_hists(cfg, hists)`` — ``int32[K, 2^b]`` FULL histograms → ``Ĉ[K]``;
* ``estimate_with_ci(...)``      — the same plus the §4.2 observed-Fisher
  stddev and a converged flag (single-histogram and batched forms).

Solvers (``solver=`` on every entry point, default ``"newton"``):

``newton``
    The safeguarded Newton–Raphson from ``estimators.qsketch_mle``,
    unchanged — the bit-identity reference. A ``lax.while_loop`` per row;
    vmapped rows all run to the slowest row's iteration count, which is the
    ~65 s K=2^20 wall the ROADMAP records.
``lut``
    The batched precomputed solver exploiting the int8 register domain. The
    shift-invariance (R → R−Δ, C → C·2^Δ) documented in ``estimators.py``
    means the score's every histogram-bin term factors through ONE bounded
    function H(z) = z/expm1(z) of z = C·2^{-(v+1)} — and because register
    values are integers, rebasing each row by the integer octave of its own
    LM seed reduces every row to ONE fixed log₂C grid, where evaluating all
    scores is a single (K, W)×(W, G) matmul against a compile-time H
    lattice (H saturates to 1/0 outside a W = 30-octave window, so W ≪ 2^b
    columns suffice). The root is then bracketed per row by a binary sign
    search and polished on a 4-point cubic interpolant of the score — a
    fixed, fully unrolled recurrence with **no lax.while_loop**, so the
    sharded fronts keep ``check_vma=True`` on this path. O(2^b) work per
    row, all of it in BLAS-shaped ops, and a row's answer is independent of
    the batch it rides in (the grid is per-row, not per-batch).
``fused``
    The Pallas kernel ``kernels/estimate.py`` via ``ops.estimate_rows_op``:
    streams register rows through VMEM and emits bincount + a fixed-count
    vectorized Newton in one pass, never materializing the ``[K, 2^b]``
    histogram in HBM. Registers-only — ``estimate_hists(solver="fused")``
    raises (the kernel's whole point is fusing the bincount). Built for
    TPU; on CPU it runs in interpret mode (slow — use ``lut`` there).

Scaling conventions (``kind=``): ``"full"`` — every element feeds all m
registers (QSketch / SketchArray / the in-step monitor); the MLE *is* Ĉ.
``"routed"`` — one register per element (Dyn / Window rows); the MLE
recovers Ĉ/m, is scaled ×m, and untouched rows (full-histogram bin 0 == m)
report exactly 0.0. That untouched-row guard — previously repeated in
``qsketch_dyn.estimate_mle``, ``qsketch_dyn.merge`` and
``dyn_array.estimate_mle_hists`` — lives here and only here.

Tolerance semantics (tests/test_estimation.py enforces): ``lut``/``fused``
match the float64 reference MLE within ``LUT_RTOL`` relative error OR
``ATOL_FLOOR`` absolute. The absolute floor covers rows whose MLE
legitimately collapses toward 0 — any bin-0 mass alongside high-value mass
drives the score negative at every meaningful C, and the solvers land on
different denormal-scale representations of "zero". The relative bound
holds for rows whose MLE sits within ``GRID_MARGIN`` octaves of their LM
seed (true for max-stable register rows, i.e. every reachable sketch);
roots outside the grid clamp to its edge (documented saturation).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import estimators
from .types import SketchConfig

# Documented agreement bound of the lut/fused solvers vs the float64
# reference MLE: relative wherever the estimate is meaningful, absolute
# below the collapse floor.
LUT_RTOL = 2e-3
ATOL_FLOOR = 1e-6

SOLVERS = ("newton", "lut", "fused")

# LUT geometry. Every row is rebased by the integer octave of its own LM
# seed (the shift-invariance R → R−Δ, C → C·2^Δ), so ONE fixed grid
# u' ∈ [−GRID_MARGIN, +GRID_MARGIN] with GRID_POINTS samples serves every
# row: because register values are integers, an integer shift of log₂C is
# exactly a shift of the histogram window, and the H lattice over the grid
# is a compile-time table. The lattice rows cover the integer exponents
# ε = log₂z where H transitions (outside [_H_SAT_LO, _H_SAT_HI] ± the grid
# half-width H is saturated: 1 below — a ≤ 2^(_H_SAT_LO−1) ≈ 6e-5 relative
# error per count, far inside LUT_RTOL — and 0 above). Cubic interpolation
# error scales as the 4th power of the grid step (~0.53 octaves): ≲ 2e-4,
# independent of how heterogeneous the batch is, because the step never
# widens with the seed spread (tests/test_estimation.py measures this
# against the float64 reference). GRID_POINTS must be a power of two — the
# bracketing binary search descends through exact powers.
GRID_POINTS = 16
GRID_MARGIN = 4.0
_H_SAT_LO = -13.0  # log2 z below which H(z) is taken as 1
_H_SAT_HI = 6.0  # log2 z above which H(z) is taken as 0
# Lattice rows: integer ε from _EPS_HI down to _EPS_LO, one octave apart.
_EPS_HI = int(np.ceil(_H_SAT_HI + GRID_MARGIN)) + 1
_EPS_LO = int(np.floor(_H_SAT_LO - GRID_MARGIN)) - 1
WINDOW_BINS = _EPS_HI - _EPS_LO + 1
# The H == 1 saturation tail Σ_{k ≥ thresh} T_k·act_k is read from coarse
# per-group partial sums (folded into the constants GEMM as _TAIL_GROUP-lane
# indicator columns) plus one boundary-group gather — never a full-width
# masked reduction over the histogram block.
_TAIL_GROUP = 16
# Newton-on-cubic refinements after the bracketing search. Convergence is
# superlinear: measured worst error vs the float64 oracle is 1.7e-4 at two
# iterations and 1.8e-4 at three (the third is a no-op), so two buys the
# full accuracy the interpolation error floor allows.
_REFINE_ITERS = 2


@functools.lru_cache(maxsize=16)
def _lut_consts(num_bins: int, r_min: int, top_bin: int):
    """Per-config constants of the LUT solver (tabulated once).

    Returns (w_mat, h_tab) as numpy f32 arrays. ``w_mat`` (2^b, 3 + G_t)
    holds the per-row reductions the solver takes in ONE histogram GEMM:
    columns 0/1 the disjoint split-scaled weights whose two inner products
    reassemble the score's linear coefficient B = Σ_{k<top} T_k·2^{−v−1}
    without f32 overflow (column 0 carries 2^96), column 2 the indicator
    ``act`` of bins that contribute an H term (1..top), and columns 3+ the
    ``_TAIL_GROUP``-lane partial sums of ``act`` minus the top bin, from
    which the H == 1 saturation tail is assembled. ``h_tab`` is the
    (WINDOW_BINS, GRID_POINTS) lattice H(2^{ε_w + u'_g}) with integer rows
    ε_w = _EPS_HI − w and the fixed rebased grid u' — evaluated in float64
    so the f32 table is correctly rounded.
    """
    v = np.arange(num_bins, dtype=np.float64) + r_min
    lane = np.arange(num_bins)
    w_expo = -(v + 1.0)
    in_b = (lane < top_bin)  # interior bins AND bin 0 (its f-term is −T₀s₀)
    big = in_b & (w_expo > 30.0)
    sml = in_b & ~big
    w_big = np.where(big, np.exp2(w_expo - 96.0), 0.0)
    w_sml = np.where(sml, np.exp2(np.clip(w_expo, -149.0, 30.0)), 0.0)
    act = ((lane >= 1) & (lane <= top_bin)).astype(np.float64)
    act_nt = act * (lane != top_bin)
    n_groups = -(-num_bins // _TAIL_GROUP)
    groups = np.zeros((num_bins, n_groups))
    groups[lane, lane // _TAIL_GROUP] = act_nt
    w_mat = np.concatenate(
        [np.stack([w_big, w_sml, act], axis=1), groups], axis=1
    )
    up = -GRID_MARGIN + (2.0 * GRID_MARGIN / (GRID_POINTS - 1)) * np.arange(
        GRID_POINTS, dtype=np.float64
    )
    eps = _EPS_HI - np.arange(WINDOW_BINS, dtype=np.float64)
    z = np.exp2(eps[:, None] + up[None, :])
    with np.errstate(over="ignore"):
        h_tab = np.where(z < 1e-9, 1.0, z / np.expm1(np.minimum(z, 700.0)))
        h_tab = np.where(z > 700.0, 0.0, h_tab)
    return w_mat.astype(np.float32), h_tab.astype(np.float32)


@functools.lru_cache(maxsize=16)
def lut_family_consts(num_bins: int, r_min: int, top_bin: int):
    """Device-resident LUT tables, shared across a whole (m, b) config FAMILY.

    The solver tables depend on the sketch geometry only through
    (num_bins, r_min, top_bin) — the constants an (m, b) pair fixes — never
    on the seed or on which container instance is asking. Caching the
    ``jnp`` arrays at that key means every DynArray / WindowArray / monitor
    built from the same family reuses ONE tabulation and ONE device upload
    (the returned arrays are the literal same buffers, asserted by
    tests/test_estimation.py), instead of re-materializing the table per
    instance/trace. Values are exactly ``_lut_consts``' (the float64-
    evaluated, correctly-rounded f32 tables), so the LUT tolerance contract
    (``LUT_RTOL``) is untouched.
    """
    w_mat_np, h_np = _lut_consts(num_bins, r_min, top_bin)
    # Concrete even when first populated under a jit trace — a traced
    # asarray would cache a tracer and leak it into later traces.
    with jax.ensure_compile_time_eval():
        return jnp.asarray(w_mat_np), jnp.asarray(h_np)


def _log2_add(a, b):
    """log2(2^a + 2^b), finite for mismatched magnitudes (−inf allowed)."""
    hi = jnp.maximum(a, b)
    lo = jnp.minimum(a, b)
    d = jnp.clip(lo - hi, -60.0, 0.0)
    out = hi + jnp.log2(1.0 + jnp.exp2(d))
    return jnp.where(jnp.isfinite(hi), out, hi)


# Rows per LUT chunk: chunks are solved sequentially (lax.map) so the f32
# conversion and every GEMV/GEMM intermediate stays cache-resident — the
# only DRAM traffic is one pass over the int32 histogram block. Chunking is
# purely a residency optimization: the grid is per-row (seed-rebased), so a
# row's answer does not depend on its chunk.
_LUT_CHUNK = 8192


def _lut_hists_with_ci(cfg: SketchConfig, hists):
    """Batched LUT solve: (chat[K], stddev[K], converged[K]) from FULL
    histograms ``int*[K, 2^b]`` (rows sum to m). Unscaled — the MLE itself;
    callers apply the kind convention. Large batches are solved in
    ``_LUT_CHUNK``-row chunks (cache residency; batch-invariant results)."""
    k = hists.shape[0]
    if k <= _LUT_CHUNK:
        return _lut_chunk_solve(cfg, hists)
    nc = -(-k // _LUT_CHUNK)
    kp = nc * _LUT_CHUNK
    hp = hists if kp == k else jnp.pad(hists, ((0, kp - k), (0, 0)), mode="edge")
    out = jax.lax.map(
        lambda hc: _lut_chunk_solve(cfg, hc),
        hp.reshape(nc, _LUT_CHUNK, hists.shape[1]),
    )
    return jax.tree_util.tree_map(lambda x: x.reshape(kp)[:k], out)


def _lut_chunk_solve(cfg: SketchConfig, hists):
    """One-chunk LUT solve (see ``_lut_hists_with_ci``).

    Each row is rebased by the integer octave of its own LM seed,
    n = round(log₂Ĉ0): with u = n + u', the score c·f(c) = A(u) − B·2^u
    has A(n + u') = Σ_k T_k·H(2^{u' + e_k + n}), and because e_k = −(v+1)
    is an integer lattice, e_k + n indexes the SAME compile-time H table
    for every row — only the histogram window shifts (a per-row gather).
    A over the fixed u' grid is then one (K, W)×(W, G) matmul. Bracket by
    a binary sign search, polish with Newton on the cubic through the 4
    bracketing grid samples. Everything is fixed-trip-count, and a row's
    answer does not depend on which batch/chunk it rides in.
    """
    nb = cfg.num_bins
    m = cfg.m
    top = cfg.top_bin
    w_mat, h = lut_family_consts(nb, cfg.r_min, top)  # (nb, 3+G_t), (W, G)

    t = hists.astype(jnp.float32)  # (K, nb)

    # --- per-row constants: B (split-scaled), A0, seed, tail groups -------
    # One (K, nb) @ (nb, 3 + G_t) GEMM — a single pass over the histogram
    # block instead of a reduction per constant (at K = 2^20 the block is
    # ~1 GB; traffic, not FLOPs, dominates on hosts).
    g3 = t @ w_mat
    b_big, b_sml, a0 = g3[:, 0], g3[:, 1], g3[:, 2]
    gsum = g3[:, 3:]  # (K, G_t) coarse partial sums of T·act (minus top)
    l2_big = jnp.where(b_big > 0, jnp.log2(jnp.maximum(b_big, 1e-38)) + 96.0, -jnp.inf)
    l2_sml = jnp.where(b_sml > 0, jnp.log2(jnp.maximum(b_sml, 1e-38)), -jnp.inf)
    l2b = _log2_add(l2_big, l2_sml)  # log2 B, −inf when B == 0
    l2b_safe = jnp.where(jnp.isfinite(l2b), l2b, jnp.float32(-126.0))
    # LM seed Ĉ0 = (m−1)/(2·Σ_k T_k 2^{−v−1}) in log2 — the grid anchor and
    # the degenerate-high fallback (matches estimators.qsketch_init up to
    # the log-domain evaluation). Unlike B, the seed denominator includes
    # the top bin; fold it in as a log-domain correction.
    tt_f = t[:, top]
    l2_top_term = jnp.where(
        tt_f > 0, jnp.log2(jnp.maximum(tt_f, 1e-38)) - (top + cfg.r_min + 1.0), -jnp.inf
    )
    l2b_seed = _log2_add(l2b, l2_top_term)
    l2b_seed = jnp.where(jnp.isfinite(l2b_seed), l2b_seed, jnp.float32(-126.0))
    l2c0 = jnp.log2(jnp.float32(m - 1.0)) - 1.0 - l2b_seed

    # --- per-row rebase onto the fixed grid -------------------------------
    n_f = jnp.round(jnp.clip(l2c0, -126.0, 126.0))
    n_i = n_f.astype(jnp.int32)
    du = jnp.float32(2.0 * GRID_MARGIN / (GRID_POINTS - 1))
    lo = jnp.float32(-GRID_MARGIN)

    # --- A(u'_g) from the shifted histogram window ------------------------
    # Lattice row w holds ε_w = _EPS_HI − w; lane k lands on it when
    # ε_w = n + e_k with e_k = −(k + r_min + 1), i.e. k = n + w + c_off.
    # Lane 0 (act == 0) and the top lane (its e carries a +1 — the term
    # uses a = 2·s_top) are excluded from the generic gather; bins shifted
    # past the low-ε window edge are in H == 1 saturation → a constant.
    c_off = -cfg.r_min - 1 - _EPS_HI
    cols = n_i[:, None] + (jnp.arange(WINDOW_BINS, dtype=jnp.int32) + c_off)[None, :]
    valid = (cols >= 1) & (cols < nb) & (cols != top)
    t_w = jnp.where(
        valid, jnp.take_along_axis(t, jnp.clip(cols, 0, nb - 1), axis=1), 0.0
    )  # (K, W)
    # H == 1 tail Σ_{k ≥ thresh} T_k·act_k (minus top): the coarse group
    # suffix from the constants GEMM plus one boundary-group gather — no
    # full-width masked reduction.
    thresh = jnp.clip(n_i + c_off + WINDOW_BINS, 0, nb)
    n_groups = gsum.shape[1]
    g_t = thresh // _TAIL_GROUP  # in [0, n_groups]
    prefix = jnp.cumsum(gsum, axis=1)  # inclusive per-group prefix
    tot = prefix[:, -1]
    pre_g = jnp.take_along_axis(prefix, jnp.clip(g_t, 0, n_groups - 1)[:, None], axis=1)[:, 0]
    suffix = jnp.where(g_t >= n_groups, 0.0, tot - pre_g)  # groups past g_t
    bcols = g_t[:, None] * _TAIL_GROUP + jnp.arange(_TAIL_GROUP, dtype=jnp.int32)[None, :]
    bval = (bcols >= thresh[:, None]) & (bcols < nb) & (bcols >= 1) & (bcols != top)
    boundary = jnp.sum(
        jnp.where(bval, jnp.take_along_axis(t, jnp.clip(bcols, 0, nb - 1), axis=1), 0.0),
        axis=1,
    )
    a_const = suffix + boundary
    # Top-bin term: ε_top = n − (top + r_min) → lattice row per row of K.
    w_top = _EPS_HI + top + cfg.r_min - n_i
    h_top = h[jnp.clip(w_top, 0, WINDOW_BINS - 1), :]  # (K, G) row gather
    a_const = a_const + jnp.where(w_top >= WINDOW_BINS, tt_f, 0.0)
    in_w = (w_top >= 0) & (w_top < WINDOW_BINS)
    a = t_w @ h + jnp.where(in_w, tt_f, 0.0)[:, None] * h_top + a_const[:, None]

    # --- bracket + cubic polish ------------------------------------------
    # The score G(u) = A(u)·2^{−u} − B is strictly decreasing in u (A is
    # non-increasing, 2^{−u} strictly decreasing), so its sign over the grid
    # is a single crossing: bracket it by binary search with log2(G) probes
    # per row instead of a full (K, G) transcendental sign matrix. A probe
    # compares A(u_i) > B·2^{u_i} with the rhs clipped: A ≤ m, so any
    # log2-rhs above the bound decides the comparison without exp2 overflow.
    bound = jnp.float32(np.log2(max(m, 2)) + 2.0)

    def _probe(idx):
        a_g = jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]
        s = l2b + n_f + (lo + idx.astype(jnp.float32) * du)
        return (s < bound) & (a_g > jnp.exp2(jnp.minimum(s, bound)))

    j_raw = jnp.zeros(a.shape[:1], jnp.int32)
    step_sz = GRID_POINTS // 2
    while step_sz >= 1:
        cand = j_raw + step_sz
        j_raw = jnp.where(_probe(cand), cand, j_raw)
        step_sz //= 2
    below = ~_probe(jnp.zeros_like(j_raw))  # sign already negative at u[0]

    # Interpolation nodes j−1..j+2 at θ = −1,0,1,2; the root bracket
    # [u_j, u_{j+1}] is θ ∈ [0, 1] except at the clipped edges, where the
    # admissible θ range widens to keep the true bracket inside the nodes.
    j = jnp.clip(j_raw, 1, GRID_POINTS - 3)
    th_lo = jnp.where(j_raw < 1, jnp.float32(-1.0), jnp.float32(0.0))
    th_hi = jnp.where(j_raw > GRID_POINTS - 3, jnp.float32(2.0), jnp.float32(1.0))
    idx = j[:, None] + jnp.arange(-1, 3)[None, :]
    ai = jnp.take_along_axis(a, idx, axis=1)  # (K, 4)
    u_j = n_f + lo + j.astype(jnp.float32) * du  # absolute log2 c at node j

    ln2 = jnp.float32(np.log(2.0))
    # rhs = B·2^{u_j + θdu} = R0·2^{θdu}; near the bracket R0 ≈ A(u_root) ≤ m,
    # so the clip never binds where the value matters.
    r0 = jnp.exp2(jnp.clip(l2b_safe + u_j, -126.0, 30.0))
    theta = 0.5 * (th_lo + th_hi)
    a_th = da_th = jnp.zeros_like(theta)
    for _ in range(_REFINE_ITERS):
        th = theta
        l0 = -th * (th - 1.0) * (th - 2.0) / 6.0
        l1 = (th + 1.0) * (th - 1.0) * (th - 2.0) / 2.0
        l2 = -(th + 1.0) * th * (th - 2.0) / 2.0
        l3 = (th + 1.0) * th * (th - 1.0) / 6.0
        a_th = ai[:, 0] * l0 + ai[:, 1] * l1 + ai[:, 2] * l2 + ai[:, 3] * l3
        d0 = -(3.0 * th * th - 6.0 * th + 2.0) / 6.0
        d1 = (3.0 * th * th - 4.0 * th - 1.0) / 2.0
        d2 = -(3.0 * th * th - 2.0 * th - 2.0) / 2.0
        d3 = (3.0 * th * th - 1.0) / 6.0
        da_th = ai[:, 0] * d0 + ai[:, 1] * d1 + ai[:, 2] * d2 + ai[:, 3] * d3
        rhs = r0 * jnp.exp2(th * du)
        g = a_th - rhs
        gp = da_th - rhs * ln2 * du
        step = g / jnp.where(jnp.abs(gp) > 0, gp, jnp.float32(-1.0))
        theta = jnp.clip(th - step, th_lo, th_hi)
    u_root = u_j + theta * du

    # Root below the grid (score already negative at the left edge): the
    # small-z closed form A0/c = B ⇒ u = log2 A0 − log2 B. Above the grid:
    # clamp to the right edge (saturation, documented above).
    u_small = jnp.log2(jnp.maximum(a0, 1e-38)) - l2b_safe
    u_root = jnp.where(below, jnp.minimum(u_small, n_f + lo), u_root)

    chat = jnp.exp2(jnp.clip(u_root, -126.0, 127.0))

    # --- stddev from the interpolant: f'(c) = (dA/du/ln2 − A)/c² ----------
    c_root = jnp.maximum(chat, jnp.float32(1e-30))
    # dA/dc = (dA/dθ)/(du·ln2·c); f = A/c − B ⇒ f'(c) = (dA/du/ln2 − A)/c².
    fp = (da_th / (du * ln2) - a_th) / (c_root * c_root)
    fp = jnp.minimum(fp, jnp.float32(-1e-30))
    stddev = jnp.sqrt(jnp.maximum(-1.0 / fp, 0.0))

    # --- degenerates (replicating estimators.qsketch_mle) -----------------
    t0 = hists[:, 0]
    tt = hists[:, top]
    degenerate = (t0 == m) | (tt == m)
    c0 = jnp.exp2(jnp.clip(l2c0, -126.0, 127.0))
    chat = jnp.where(tt == m, c0, chat)
    chat = jnp.where(t0 == m, jnp.float32(0.0), chat)
    return chat, stddev, ~degenerate


# ---------------------------------------------------------------------------
# Solver dispatch
# ---------------------------------------------------------------------------


def _check_kind(kind: str) -> None:
    if kind not in ("full", "routed"):
        raise ValueError(f"unknown kind {kind!r}; expected 'full' or 'routed'")


def _check_solver(solver: str, *, hists_input: bool = False) -> None:
    if solver not in SOLVERS:
        raise ValueError(f"unknown solver {solver!r}; expected one of {SOLVERS}")
    if hists_input and solver == "fused":
        raise ValueError(
            "solver='fused' streams register rows (its point is fusing the "
            "bincount) — use estimate_rows, or solver='lut' on histograms"
        )


def _routed_chat(cfg: SketchConfig, hist0, chat):
    """The ×m scaling + untouched-row Ĉ=0 guard of the routed convention."""
    return jnp.where(hist0 == cfg.m, jnp.float32(0.0), chat * cfg.m)


# ---------------------------------------------------------------------------
# Public API — single histogram
# ---------------------------------------------------------------------------


def _hist_with_ci_impl(cfg: SketchConfig, hist, *, kind, solver):
    _check_kind(kind)
    _check_solver(solver, hists_input=True)
    if solver == "newton":
        chat, stddev, ok = estimators.qsketch_mle(cfg, hist)
    else:
        chat, stddev, ok = jax.tree_util.tree_map(
            lambda x: x[0], _lut_hists_with_ci(cfg, hist[None, :])
        )
    if kind == "routed":
        return _routed_chat(cfg, hist[0], chat), stddev * cfg.m, ok
    return chat, stddev, ok


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("kind", "solver"))
def estimate_hist(cfg: SketchConfig, hist, *, kind: str = "full", solver: str = "newton"):
    """Ĉ from ONE full 2^b-bin histogram (bins sum to m).

    Jitted over the Ĉ output alone so XLA dead-code-eliminates the stddev
    pipeline — callers that don't want the CI don't pay for it.
    """
    return _hist_with_ci_impl(cfg, hist, kind=kind, solver=solver)[0]


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("kind", "solver"))
def estimate_hist_with_ci(
    cfg: SketchConfig, hist, *, kind: str = "full", solver: str = "newton"
):
    """(Ĉ, stddev, converged) from ONE full histogram.

    kind="full": the MLE is Ĉ. kind="routed": Ĉ = m·MLE (0.0 exactly for an
    untouched row) and the stddev scales by the same m.
    """
    return _hist_with_ci_impl(cfg, hist, kind=kind, solver=solver)


# ---------------------------------------------------------------------------
# Public API — batched
# ---------------------------------------------------------------------------


def _hists_with_ci_impl(cfg: SketchConfig, hists, *, kind, solver):
    _check_kind(kind)
    _check_solver(solver, hists_input=True)
    if solver == "lut":
        chat, stddev, ok = _lut_hists_with_ci(cfg, hists)
        if kind == "routed":
            return _routed_chat(cfg, hists[:, 0], chat), stddev * cfg.m, ok
        return chat, stddev, ok
    if kind == "routed":

        def one(hist):
            chat, stddev, ok = estimators.qsketch_mle(cfg, hist)
            return _routed_chat(cfg, hist[0], chat), stddev * cfg.m, ok

    else:

        def one(hist):
            return estimators.qsketch_mle(cfg, hist)

    return _in_row_blocks(cfg, jax.vmap(one), hists)


# Rows per block of the batched Newton solve (see ``_in_row_blocks``).
NEWTON_BLOCK_ROWS = 2**16


def _in_row_blocks(cfg: SketchConfig, solve, hists):
    """``solve(hists)`` over blocks of ``NEWTON_BLOCK_ROWS`` rows, one after
    another (``lax.map``), when there are more rows than one block.

    The TPU compiler's arithmetic in the vmapped while-loop solve depends on
    the row count: at K = 2^20 about 5% of Newton results differ in the last
    bits from the same rows solved in K/4 slices (measured on a v5e). Solving
    in fixed-shape blocks makes every row's bits independent of K, so a
    sharded front (K/S rows per shard) reproduces the single-device solve
    bit for bit whenever both hold at least one block; below one block the
    solve is the plain vmap. It also bounds the solve's temporaries to one
    block. Pad rows are untouched histograms (bin 0 = m), solved at once.
    """
    k = hists.shape[0]
    if k <= NEWTON_BLOCK_ROWS:
        return solve(hists)
    nb = -(-k // NEWTON_BLOCK_ROWS)
    pad = nb * NEWTON_BLOCK_ROWS - k
    if pad:
        fill = jnp.zeros((pad, hists.shape[1]), hists.dtype).at[:, 0].set(cfg.m)
        hists = jnp.concatenate([hists, fill])
    out = jax.lax.map(solve, hists.reshape(nb, NEWTON_BLOCK_ROWS, -1))
    return jax.tree.map(lambda x: x.reshape(nb * NEWTON_BLOCK_ROWS)[:k], out)


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("kind", "solver"))
def estimate_hists(cfg: SketchConfig, hists, *, kind: str = "full", solver: str = "newton"):
    """Ĉ[K] from full histograms ``int32[K, 2^b]``.

    Jitted over the Ĉ output alone so XLA dead-code-eliminates the stddev
    pipeline — at K = 2^20 the CI costs a measurable fraction of the lut
    solve, and most batched readers (dashboards, anomaly scoring) only
    consume Ĉ.
    """
    return _hists_with_ci_impl(cfg, hists, kind=kind, solver=solver)[0]


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("kind", "solver"))
def estimate_hists_with_ci(
    cfg: SketchConfig, hists, *, kind: str = "full", solver: str = "newton"
):
    """(Ĉ[K], stddev[K], converged[K]) from full histograms.

    The newton forms reproduce the pre-refactor vmap expressions exactly
    (the bit-identity contract): kind="full" vmaps the bare solve;
    kind="routed" vmaps solve+guard as one function, exactly as
    ``dyn_array.estimate_mle_hists`` always did. The lut solver is natively
    batched; its per-row rebased grid makes every answer batch-independent.
    """
    return _hists_with_ci_impl(cfg, hists, kind=kind, solver=solver)


# ---------------------------------------------------------------------------
# Public API — pooled (virtual register sharing) solves
# ---------------------------------------------------------------------------


def pool_config(cfg: SketchConfig, pool_size: int) -> SketchConfig:
    """Pool-geometry config of a shared register pool: the same register
    family (b, and hence r_min/r_max/num_bins/top_bin) with m = M pool
    slots.

    The pool plane of a ``VirtualDynArray`` is itself ONE routed-convention
    sketch of the whole tail stream: each element raises exactly one of M
    slots, so the standard histogram MLE applies under this geometry. The
    LUT tables key on (num_bins, r_min, top_bin) only — a pool config of the
    same b shares the family tabulation with its dense siblings
    (``lut_family_consts``).
    """
    if pool_size <= cfg.m:
        raise ValueError(
            f"pool_size {pool_size} must exceed m {cfg.m} (alpha = m/M < 1)"
        )
    return SketchConfig(m=pool_size, b=cfg.b, seed=cfg.seed)


# Nested log2(u) grid of the compound-Poisson profile solve: a coarse sweep
# of the whole representable octave range, then two refinements around the
# running argmax. Final resolution 0.03125 octaves ≈ 2% in u — below the
# statistical error of any virtual row. Grid search (not Newton) because the
# mixture likelihood is multi-modal for near-empty rows and the solve must
# be deterministic across backends.
_VIRTUAL_GRID_STAGES = ((128, 2.0), (17, 0.25), (17, 0.03125))


def _virtual_loglik(cfg: SketchConfig, h, lam, log2_u):
    """Touched-bin log-likelihood of one full histogram under the
    compound-Poisson register law, for a batch of candidate log2(u).

    With per-slot element count N ~ Poisson(λ) and constant element weight
    u, the Poisson generating function collapses the N-mixture in closed
    form:  P(R ≤ v) = E_N[e^{−N·u·s(v)}] = exp(−λ·(1 − e^{−u·s(v)})),
    s(v) = 2^{−(v+1)}. Bin 0 (value r_min) is exactly the N = 0 mass e^{−λ}
    — constant in u — so it is omitted here and identifies λ separately
    (``_virtual_hists_impl``). Evaluated via expm1 twice: g = −expm1(−u·s)
    keeps small per-slot loads exact, and ln p_k = a_{k−1} +
    ln(expm1(a_k − a_{k−1})) (a_k = −λ·g_k, increasing in k) subtracts the
    two near-unity CDF values without f32 cancellation.
    """
    k = jnp.arange(cfg.num_bins, dtype=jnp.float32)
    log2_s = -(k + cfg.r_min + 1.0)
    us = jnp.exp2(log2_u[:, None] + log2_s[None, :])  # [G, bins]
    g = -jnp.expm1(-us)
    a = -lam * g  # increasing in k, in [−λ, 0]
    da = a[:, 1:] - a[:, :-1]  # ≥ 0
    lnp = a[:, :-1] + jnp.log(jnp.expm1(jnp.maximum(da, 1e-30)))
    hk = h[1:].astype(jnp.float32)
    return jnp.sum(jnp.where(hk[None, :] > 0, hk[None, :] * lnp, 0.0), axis=1)


def _virtual_hist_solve(cfg: SketchConfig, h):
    """Ŵ of ONE full histogram via the compound-Poisson profile MLE.

    λ̂ = ln(m / T₀) from occupancy (exact: bin 0 is the Poisson zero mass),
    clamped to ln(2m) on saturated rows (T₀ = 0 only bounds λ from below —
    the standard linear-counting cap); û from the nested-grid profile
    likelihood over the touched bins; Ŵ = m·λ̂·û estimates the row's total
    load Σ_j c_j.
    """
    t0 = h[0].astype(jnp.float32)
    lam = jnp.log(cfg.m / jnp.clip(t0, 0.5, None))
    center = jnp.float32(0.0)
    for npts, step in _VIRTUAL_GRID_STAGES:
        offs = (jnp.arange(npts, dtype=jnp.float32) - (npts - 1) / 2.0) * step
        grid = center + offs
        ll = _virtual_loglik(cfg, h, lam, grid)
        center = grid[jnp.argmax(ll)]
    u = jnp.exp2(center)
    return jnp.where(t0 >= cfg.m, jnp.float32(0.0), cfg.m * lam * u)


def _virtual_hists_impl(cfg: SketchConfig, hists, *, solver: str):
    """Compound-Poisson profile solve: Ĉ[K] from FULL histograms.

    The plain routed convention is misspecified for lightly-loaded rows
    (DESIGN.md §8.4) twice over. First, the quantized likelihood reads an
    untouched register (bin 0, value r_min) as "the row's whole load
    produced y ≤ r_min", whose probability e^{−C·2^{−(r_min+1)}} forces Ĉ
    toward 0 the moment ANY bin-0 mass coexists with touched registers.
    Second, even restricted to touched registers, a common-scale fit over
    slots whose true loads disperse (few elements per slot — the virtual
    regime) behaves like a geometric mean of the per-slot loads and lands
    well below the arithmetic total. Dense Dyn rows dodge both with the
    running martingale; a virtual row has no martingale, and both it and
    the shared pool plane are lightly loaded BY DESIGN.

    The fix models the dispersion instead of assuming it away (DESIGN.md
    §8.9): per-slot load is compound Poisson — N ~ Poisson(λ) elements of
    weight u — whose register law has the closed form
    P(R ≤ v) = exp(−λ·(1 − e^{−u·2^{−(v+1)}})) (``_virtual_loglik``). The
    joint MLE factorizes exactly: bin 0 is the N = 0 mass e^{−λ}, so
    occupancy identifies λ̂ = ln(m/T₀) alone, and the touched bins profile
    out û. Ĉ = m·λ̂·û. The limits are right: for u·s(v) ≪ 1 the law
    reduces to the plain routed family with c = λu (fully-loaded rows lose
    nothing), and a singleton-loaded row is exactly specified — one
    element of weight w gives its register the law e^{−w·2^{−(v+1)}}, the
    λ → 0 conditional of the mixture, so m·λ̂·û ≈ n·w̄. Untouched rows
    (T₀ = m) report exactly 0.0. The solve is a deterministic nested grid —
    ``solver`` is validated for API uniformity but "newton" and "lut"
    produce identical results here ("fused" is rejected: histogram input).
    """
    _check_solver(solver, hists_input=True)
    return jax.vmap(lambda h: _virtual_hist_solve(cfg, h))(hists)


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("solver",))
def estimate_hists_virtual(cfg: SketchConfig, hists, *, solver: str = "newton"):
    """Ĉ[K] from FULL histograms via the compound-Poisson profile solve —
    the light-load-safe read of the virtual tier (``_virtual_hists_impl``
    has the derivation). ``solver="fused"`` maps to newton (histogram
    input: nothing to fuse)."""
    solver = "newton" if solver == "fused" else solver
    return _virtual_hists_impl(cfg, hists, solver=solver)


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("solver",))
def estimate_rows_virtual(cfg: SketchConfig, regs, *, solver: str = "newton"):
    """Ĉ[K] from register rows ``int8[K, m]`` via the compound-Poisson
    profile solve (bincount each row, then ``estimate_hists_virtual``).

    This is the read for register planes WITHOUT maintained martingales or
    full per-row traffic — the virtual tier's gathered tenant rows — where
    the plain routed MLE collapses on bin-0 mass and a touched-only
    common-scale fit under-reads dispersed loads (see
    ``_virtual_hists_impl``). ``solver="fused"`` maps to newton: the fused
    kernel bakes in the plain routed guard, not the mixture law.
    """
    solver = "newton" if solver == "fused" else solver
    hists = jax.vmap(lambda r: estimators.histogram(cfg, r))(regs)
    return _virtual_hists_impl(cfg, hists, solver=solver)


def estimate_pool_hist(
    cfg: SketchConfig, pool_hist, pool_size: int, *, solver: str = "newton"
):
    """Ŵ_pool from the FULL pool histogram (bins sum to M): the pooled
    solve — one O(2^b) histogram read, no register walk.

    Runs the compound-Poisson virtual solve under the pool geometry
    (``pool_config``): the pool plane is one routed-convention sketch of
    the whole tail stream, and it is lightly loaded by design (load factor
    is held below ~0.5, obs/health.py), exactly the regime the plain routed
    MLE collapses in. Per-slot jump weights mix every tail tenant's
    register loads, so the constant-jump assumption is coarser here than on
    a single tenant's row — the exact ``w_tail`` accumulator remains the
    authoritative pool total; this solve is the register-only
    cross-check/telemetry read. ``solver="fused"`` maps to newton.
    """
    _check_solver(solver)
    pcfg = pool_config(cfg, pool_size)
    return estimate_hists_virtual(pcfg, pool_hist[None, :], solver=solver)[0]


def cancel_pool_noise(cfg: SketchConfig, chat_virtual, chat_pool, pool_size: int):
    """Noise-cancellation pre-pass of the virtual-sketch estimate
    (Wang et al., arXiv 1811.09126; DESIGN.md §8.9).

    A tail tenant's m gathered pool registers see its own stream plus an
    ~α = m/M sample of every other tenant's traffic, so the routed MLE of
    the gathered row satisfies E[Ŵ_v] ≈ W_t + α·(W_pool − W_t). Inverting:

        Ŵ_t = (Ŵ_v − α·W_pool) / (1 − α),  clamped at 0

    (the clamp: for light tenants the subtraction is noise-dominated and
    may go negative; weight is nonnegative). ``chat_pool`` is the total
    tail weight in the pool — callers should pass the exact ``w_tail``
    accumulator when they have it (``virtual_dyn_array.estimate_tenants``
    does); the pooled histogram MLE is an admissible but low-biased
    fallback under heterogeneous slot loads (DESIGN.md §8.9). Broadcasts
    over batched ``chat_virtual`` against a scalar ``chat_pool``.
    """
    if pool_size <= cfg.m:
        raise ValueError(
            f"pool_size {pool_size} must exceed m {cfg.m} (alpha = m/M < 1)"
        )
    alpha = jnp.float32(cfg.m / pool_size)
    cancelled = (chat_virtual - alpha * chat_pool) / (1.0 - alpha)
    return jnp.maximum(cancelled, 0.0)


def _rows_with_ci_impl(cfg: SketchConfig, regs, *, kind, solver):
    _check_kind(kind)
    _check_solver(solver)
    if solver == "fused":
        from repro.kernels import ops  # deferred: kernels imports core

        return ops.estimate_rows_op(cfg, regs, kind=kind)
    hists = jax.vmap(lambda r: estimators.histogram(cfg, r))(regs)
    return _hists_with_ci_impl(cfg, hists, kind=kind, solver=solver)


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("kind", "solver"))
def estimate_rows(cfg: SketchConfig, regs, *, kind: str = "routed", solver: str = "newton"):
    """Ĉ[K] from register rows ``int8[K, m]`` (CI pipeline dead-code-eliminated)."""
    return _rows_with_ci_impl(cfg, regs, kind=kind, solver=solver)[0]


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("kind", "solver"))
def estimate_rows_with_ci(
    cfg: SketchConfig, regs, *, kind: str = "routed", solver: str = "newton"
):
    """(Ĉ[K], stddev[K], converged[K]) from register rows ``int8[K, m]``.

    newton/lut bincount each row (``estimators.histogram``) then solve;
    fused never materializes the histograms — one Pallas pass does bincount
    + solve per VMEM-resident row block (``kernels/estimate.py``). Callers
    holding maintained histograms (DynArray, the window union cache) should
    call ``estimate_hists`` directly and skip the bincount.
    """
    return _rows_with_ci_impl(cfg, regs, kind=kind, solver=solver)
