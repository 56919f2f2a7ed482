"""shard_skew (ratio, program spans): over the window's micro-batches, the
events of each batch's fullest shard, summed, over those of its mean shard,
summed; from the program's ``ingest/shard_rows`` events, one per
micro-batch of a row-sharded pipeline with its events counted by the shard
that owns their row. 1 is an even split. None where the program records no
such events (a single-device pipeline, or a program without them)."""

import numpy as np

from repro.obs import trace as obs_trace


def read(run):
    if not run["qobs"]:
        return None
    counts = [e["args"]["counts"] for e in obs_trace.events() if e["name"] == "ingest/shard_rows"]
    if not counts:
        return None
    c = np.asarray(counts, np.float64)
    mean = c.sum() / c.shape[1]
    return float(c.max(axis=1).sum() / mean) if mean else None
