"""gen_late_p95_ms (ms, host clock): 95th percentile over arrival chunks of
how late the open-loop generator pushed each chunk after it was due."""

import numpy as np


def read(run):
    late = run["late_s"]
    return float(np.percentile(late, 95)) * 1e3 if len(late) else None
