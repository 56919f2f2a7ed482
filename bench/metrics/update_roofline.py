"""update_roofline (%, device trace): the update's bytes floor over the HBM
peak, as a share of its device time.

The floor counts the work, not an implementation: per micro-batch, every
distinct tenant row it touches is read or written once in each sub-state
the container updates (2 for a window: head epoch and union; 1 for a
DynArray), at m bytes of registers + 4 * 2^b of histogram + 4 of estimate,
plus 12 bytes (slot, id, weight) per input event. Rows are counted on the
host from the stream with the reference's routing.
"""

import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from reference import oracle  # noqa: E402

EVENT_BYTES = 12


def row_bytes(m: int, b: int) -> int:
    return m + 4 * 2**b + 4


def floor_bytes(stream, conf: dict, plan) -> int:
    """Bytes the plan's micro-batches must move at least."""
    slot = oracle.route(stream.t_lo, stream.t_hi, conf["k"], conf["directory_seed"])
    subs = 2 if conf["container"] == "window" else 1
    rb = row_bytes(conf["m"], conf["b"]) * subs
    bounds = [0] + list(plan.rotations) + [plan.events]
    total = 0
    for x, y in zip(bounds[:-1], bounds[1:]):
        for a in range(x, y, plan.batch):
            pos = np.arange(a, min(a + plan.batch, y)) % stream.pool_events
            total += len(np.unique(slot[pos])) * rb + len(pos) * EVENT_BYTES
    return total


def read(run):
    t = run["trace"]
    peak = run["peak"]
    if t is None or peak is None or not t.devices:
        return None
    s = t.module_s(run["modules"]["update"])
    if not s:
        return None
    per_dev = floor_bytes(run["stream"], run["config"], run["plan"]) / len(t.devices)
    return per_dev / peak["hbm_bytes_per_s"] / (s / len(t.devices)) * 100.0
