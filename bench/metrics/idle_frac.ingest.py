"""idle_frac.ingest (fraction, device trace): 1 - busy / window of a
closed-loop window, busy the union of the device's op intervals, averaged
over the cell's devices."""


def read(run):
    t = run["trace"]
    if t is None or run["mix"]["arrival"] != "closed":
        return None
    return t.idle_frac()
