"""Sketch self-introspection: one ``health_report`` over every container.

The failure modes an operator must see are implied by the paper's own
design (QSketch, arXiv 2406.19143) and the repo's extensions on top of it:

* **Top-bin saturation.** Registers are b-bit quantized with a truncation
  ceiling r_max; once a register clamps at the top bin the sketch can no
  longer distinguish further weight on that slot and the MLE biases low.
  A rising ``register_saturation_frac`` means the deployment outgrew its
  register width (raise b or re-scale weights).
* **Occupancy.** The MLE's variance contract assumes untouched registers
  remain (the routed-kind guard); near-full occupancy with the top bins
  filling is the saturation precursor, near-zero occupancy means the
  container is oversized for its traffic.
* **Anytime-vs-MLE drift.** The Dyn-family anytime martingale (§4.3) and
  the histogram MLE estimate the same quantity; their relative drift is a
  live consistency probe — a blowup flags a bug or an abused merge (chats
  added across overlapping streams, DESIGN.md §8.4). The routed MLE is
  *misspecified* when a row still has untouched registers (m ≳ n_distinct
  drives it to 0 — DESIGN.md §4), so drift is measured only over
  well-specified rows (every register touched) and the report carries the
  in-regime fraction as an informational check.
* **Union-cache staleness.** The window ring maintains a cached epoch
  union whose invariant (union_regs == max over live epoch planes) is
  cheap to verify; any mismatch is corruption.
* **Directory pressure.** Load factor and collision rate of the key
  directory — collisions silently merge tenants, so the warn threshold is
  tight.
* **CI width.** The estimator's own confidence interval
  (``estimate_*_with_ci``): a wide relative CI means the geometry (m) is
  too small for the observed cardinalities.

* **Pool pressure (virtual tier).** The shared tail pool's load factor
  drives cross-tenant collision noise, and the noise floor α·w_tail/(1−α)
  is the smallest tail weight a virtual read can resolve — past the load
  bound, grow the pool or pin the heaviest tail tenants (DESIGN.md §8.9).

``health_report(cfg, state)`` computes all applicable checks for any of
the 9 container state types and returns a plain dict with per-check
values, thresholds, and warn flags. It is host-only and on-demand — it
may sync the device and (for the drift/CI checks) run a solve, so call it
at health-probe cadence, never per batch.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.core import estimation, key_directory
from repro.core.types import (
    DynArrayState,
    DynState,
    QSketchState,
    ShardedArrayState,
    ShardedDynArrayState,
    ShardedWindowArrayState,
    SketchArrayState,
    SketchConfig,
    VirtualDynArrayState,
    WindowArrayState,
)
from repro.obs import trace


@dataclasses.dataclass(frozen=True)
class Thresholds:
    """Warn thresholds (a check warns when its value EXCEEDS the bound).

    Defaults are deliberately loose enough that a healthy fresh container
    is quiet; tighten per deployment via ``health_report(thresholds=...)``.
    """

    register_saturation_frac: float = 0.05
    # Occupancy is informational by default: with enough distinct items a
    # healthy sketch legitimately touches every register, so a warn bound
    # only makes sense per deployment (set it to e.g. 0.99 when the
    # workload is known-sparse).
    occupancy_frac: float | None = None
    union_staleness_frac: float = 0.0
    # Both estimators are ~1/sqrt(m)-noisy and batch-mode chats carry a
    # documented bias, so healthy drift runs tens of percent at small m;
    # the check exists to catch catastrophic inconsistency (abused merges,
    # corrupted hists — order-of-magnitude drift), not sampling noise.
    anytime_mle_drift: float = 1.0
    ci_rel_width: float = 0.5
    directory_load_factor: float = 0.9
    directory_collision_rate: float = 0.01
    # Virtual tier (VirtualDynArrayState): past ~0.5 pool load the per-slot
    # collision noise grows toward the signal and the cancellation's
    # variance bound degrades (DESIGN.md §8.9) — size the pool, or pin the
    # heaviest tail tenants.
    pool_load_factor: float = 0.5
    # The noise floor is workload-scaled (α·W_pool/(1−α) is an absolute
    # weight), so a universal default would be meaningless — set a bound
    # per deployment at the smallest tail weight the operator must resolve.
    pool_noise_floor: float | None = None


DEFAULT_THRESHOLDS = Thresholds()

_CONTAINER_NAMES = {
    QSketchState: "qsketch",
    DynState: "qsketch_dyn",
    SketchArrayState: "sketch_array",
    ShardedArrayState: "sharded_array",
    DynArrayState: "dyn_array",
    ShardedDynArrayState: "sharded_dyn_array",
    WindowArrayState: "window_array",
    ShardedWindowArrayState: "sharded_window_array",
    VirtualDynArrayState: "virtual_dyn_array",
}

_DYN_LIKE = (DynState, DynArrayState, ShardedDynArrayState)
_WINDOW_LIKE = (WindowArrayState, ShardedWindowArrayState)
_FULL_KIND = (QSketchState, SketchArrayState, ShardedArrayState)


def _full_hists(cfg: SketchConfig, hists) -> jnp.ndarray:
    """Maintained touched-register hists (bin 0 pinned to 0) -> full hists
    whose rows sum to m (the estimation layer's routed input contract)."""
    return hists.at[:, 0].set(cfg.m - jnp.sum(hists, axis=1))


def _check(checks, warnings, name, value, threshold):
    value = float(value)
    warn = threshold is not None and value > threshold
    checks[name] = {"value": value, "threshold": threshold, "warn": warn}
    if warn:
        warnings.append(name)


def _info(checks, name, value):
    checks[name] = {"value": float(value), "threshold": None, "warn": False}


def directory_health(dcfg, state, checks, warnings, thresholds) -> None:
    """Fold directory load-factor + collision-rate checks into a report."""
    _check(
        checks, warnings, "directory_load_factor",
        key_directory.occupancy(state), thresholds.directory_load_factor,
    )
    _check(
        checks, warnings, "directory_collision_rate",
        key_directory.collision_rate(state), thresholds.directory_collision_rate,
    )


def health_report(
    cfg: SketchConfig,
    state,
    *,
    directory=None,
    dcfg=None,
    vcfg=None,
    thresholds: Thresholds = DEFAULT_THRESHOLDS,
    solver: str = "newton",
) -> dict:
    """Uniform health report over any of the 9 container state types.

    Args:
      cfg: the container's SketchConfig (geometry of the estimation checks).
      state: one of QSketchState / DynState / SketchArrayState /
        ShardedArrayState / DynArrayState / ShardedDynArrayState /
        WindowArrayState / ShardedWindowArrayState / VirtualDynArrayState
        (monitor wrappers: pass the container leaf, plus ``directory=`` for
        the routing telemetry).
      directory: optional ``DirectoryState`` for load/collision checks
        (``dcfg`` is accepted for symmetry but not required).
      vcfg: optional ``VirtualConfig`` — only read for
        ``VirtualDynArrayState``, where the noise-floor check needs the
        virtual row width m_v (defaults to cfg.m when omitted).
      thresholds: warn bounds; every check warns when value > threshold.
      solver: estimation solver for the drift/CI checks ("newton" is the
        bit-exact default; pass "lut" at large K).

    Returns a plain dict: ``{"container", "checks": {name: {"value",
    "threshold", "warn"}}, "warnings": [...], "ok": bool}``. Host-only —
    raises if called under an active jax trace.
    """
    if trace.tracing_active():
        raise RuntimeError(
            "health_report is host-only (it syncs device values and runs "
            "solves) — never call it inside jit/shard_map"
        )
    name = _CONTAINER_NAMES.get(type(state))
    if name is None:
        raise TypeError(
            f"health_report: unsupported state type {type(state).__name__}; "
            f"expected one of {sorted(c.__name__ for c in _CONTAINER_NAMES)}"
        )
    checks: dict[str, dict] = {}
    warnings: list[str] = []

    # ---- virtual tier: pool-plane checks + the hot tier's dense report ---
    if isinstance(state, VirtualDynArrayState):
        pool_size = state.pool.shape[0]
        _check(
            checks, warnings, "pool_load_factor",
            1.0 - state.pool_hist[0].astype(jnp.float32) / pool_size,
            thresholds.pool_load_factor,
        )
        _check(
            checks, warnings, "register_saturation_frac",
            jnp.mean((state.pool == cfg.r_max).astype(jnp.float32)),
            thresholds.register_saturation_frac,
        )
        # Noise floor at the VIRTUAL row geometry: α = m_v/M with m_v from
        # vcfg when given (``virtual_dyn_array.noise_floor``), else the
        # dense cfg.m — callers with a widened tail row pass vcfg.
        m_v = cfg.m if vcfg is None else (vcfg.m_virtual or cfg.m)
        alpha = m_v / pool_size
        _check(
            checks, warnings, "pool_noise_floor",
            jnp.float32(alpha / (1.0 - alpha)) * state.w_tail,
            thresholds.pool_noise_floor,
        )
        _info(checks, "pool_weight_total", state.w_tail)
        _info(checks, "pool_tail_elements", state.n_tail)
        # The hot tier is a dense DynArray — reuse its full report with
        # every check folded in under a hot_ prefix. Directory telemetry is
        # routing-level, not tier-level, so it stays unprefixed here.
        hot = health_report(
            cfg, state.hot, thresholds=thresholds, solver=solver,
        )
        for cname, c in hot["checks"].items():
            checks[f"hot_{cname}"] = c
            if c["warn"]:
                warnings.append(f"hot_{cname}")
        if directory is not None:
            directory_health(dcfg, directory, checks, warnings, thresholds)
        return {
            "container": name,
            "checks": checks,
            "warnings": warnings,
            "ok": not warnings,
        }

    # ---- register-plane checks (every container has regs) ----------------
    if isinstance(state, _WINDOW_LIKE):
        regs = state.union_regs  # the headline plane: the full-ring union
        stale = jnp.mean(
            (jnp.max(state.regs, axis=0) != state.union_regs).astype(jnp.float32)
        )
        _check(checks, warnings, "union_staleness_frac", stale,
               thresholds.union_staleness_frac)
        _info(checks, "ring_fill_frac",
              state.filled.astype(jnp.float32) / state.regs.shape[0])
        _info(checks, "epoch_id", state.epoch_id)
    else:
        regs = state.regs
    rows = regs if regs.ndim == 2 else regs[None, :]
    _check(
        checks, warnings, "register_saturation_frac",
        jnp.mean((rows == cfg.r_max).astype(jnp.float32)),
        thresholds.register_saturation_frac,
    )
    _check(
        checks, warnings, "occupancy_frac",
        jnp.mean((rows > cfg.r_min).astype(jnp.float32)),
        thresholds.occupancy_frac,
    )

    # ---- estimation checks ----------------------------------------------
    with trace.span("health/solve", container=name):
        if isinstance(state, _DYN_LIKE) or isinstance(state, _WINDOW_LIKE):
            if isinstance(state, _WINDOW_LIKE):
                hists, chats = state.union_hists, state.union_chats
            elif isinstance(state, DynState):
                hists, chats = state.hist[None, :], state.chat[None]
            else:
                hists, chats = state.hists, state.chats
            full = _full_hists(cfg, hists)
            est, stddev, _ = estimation.estimate_hists_with_ci(
                cfg, full, kind="routed", solver=solver
            )
            # The routed MLE is misspecified while a row has untouched
            # registers (module docstring): drift and CI are only read over
            # well-specified rows; their fraction is reported alongside.
            well = full[:, 0] == 0
            drift_rows = jnp.where(
                well, jnp.abs(chats - est) / jnp.maximum(jnp.abs(est), 1.0), 0.0
            )
            _check(checks, warnings, "anytime_mle_drift",
                   jnp.max(drift_rows), thresholds.anytime_mle_drift)
            _info(checks, "mle_wellspec_rows_frac",
                  jnp.mean(well.astype(jnp.float32)))
            measurable = well
        else:
            kind = "full" if isinstance(state, _FULL_KIND) else "routed"
            est, stddev, _ = estimation.estimate_rows_with_ci(
                cfg, rows, kind=kind, solver=solver
            )
            measurable = jnp.ones(est.shape, dtype=bool)
        active = measurable & (est > 0)
        rel = jnp.where(active, stddev / jnp.maximum(est, 1.0), 0.0)
        n_active = jnp.maximum(jnp.sum(active.astype(jnp.float32)), 1.0)
        _check(
            checks, warnings, "ci_rel_width",
            jnp.sum(rel) / n_active, thresholds.ci_rel_width,
        )
        _info(checks, "active_rows_frac",
              jnp.mean((est > 0).astype(jnp.float32)))

    # ---- directory checks ------------------------------------------------
    if directory is not None:
        directory_health(dcfg, directory, checks, warnings, thresholds)

    return {
        "container": name,
        "checks": checks,
        "warnings": warnings,
        "ok": not warnings,
    }
