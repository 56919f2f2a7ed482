"""The comparison that decides ``correct``: the program's sampled rows, read
back after the window, against the plain reference (``oracle.py``).

Numbers compared (each against the limit that the cell file states):

* ``state_mismatch``: share of the integer cells that differ: every register
  and histogram bin of every sampled row (each ring epoch and the union, or
  the DynArray), each sampled slot's claim fingerprint, and the counters
  (ring head, filled, epoch clock; events routed).
* ``chat_gap``: the widest relative gap of a running estimate (per-epoch,
  union, DynArray, and the last anytime read of the window): |p - r| /
  max(|r|, 1), the weights being in units of bytes or of a gamma(1, 2).
* ``read_gap``: the same for the last sub-ring read of the window.
"""

from __future__ import annotations

import numpy as np

INT_KEYS = ("regs", "hists", "union_regs", "union_hists", "fingerprints", "scalars")
EST_KEYS = ("chats", "union_chats")


def _gap(p, r) -> float:
    p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
    if p.shape != r.shape:
        return float("inf")
    if not p.size:
        return 0.0
    g = np.abs(p - r) / np.maximum(np.abs(r), 1.0)
    return float(np.max(np.where(np.isfinite(g), g, np.inf)))


def numbers(prog: dict, ref: dict) -> dict:
    """The compared numbers of one run."""
    bad = tot = 0
    for k in INT_KEYS:
        if k not in ref:
            continue
        p, r = np.asarray(prog.get(k)), np.asarray(ref[k])
        tot += r.size
        bad += int(np.sum(p != r)) if p.shape == r.shape else r.size
    out = {"state_mismatch": bad / max(tot, 1)}
    gaps = [_gap(prog.get(k), ref[k]) for k in EST_KEYS if k in ref]
    reads_p, reads_r = prog.get("reads", {}), ref.get("reads", {})
    if "anytime" in reads_r:
        gaps.append(_gap(reads_p.get("anytime"), reads_r["anytime"]))
    out["chat_gap"] = max(gaps)
    if "subring" in reads_r:
        out["read_gap"] = _gap(reads_p.get("subring"), reads_r["subring"])
    return out


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): correct iff every number is at
    or below its limit, and every limit has its number."""
    checks = {k: {"value": nums.get(k), "limit": float(v)} for k, v in limits.items()}
    ok = all(c["value"] is not None and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
