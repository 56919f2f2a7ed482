"""fresh_p95_ms (ms, host clock): 95th percentile over every event of an
open-loop window of the time from its due time to the completion, with the
result on the host, of the first fleet read whose state includes it."""

import numpy as np


def read(run):
    f = run["fresh_s"]
    return None if f is None or not len(f) else float(np.percentile(f, 95)) * 1e3
