"""fill_p95_ms (ms, program spans): 95th percentile over the window's
micro-batches of the program's ``ingest/fill`` events, each from the first
element entering a staging buffer to that buffer's seal (full, or flushed
by a rotation or the window's close): how long an event can sit in staging
before it is even dispatched."""

import numpy as np

from repro.obs import trace as obs_trace


def read(run):
    if not run["qobs"]:
        return None
    d = [e["dur"] for e in obs_trace.events() if e["name"] == "ingest/fill"]
    return float(np.percentile(d, 95)) / 1e3 if d else None
