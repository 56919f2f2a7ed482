"""idle_frac.fresh (fraction, device trace): the idle share of an open-loop
window (see idle_frac.ingest)."""


def read(run):
    t = run["trace"]
    if t is None or run["mix"]["arrival"] != "open":
        return None
    return t.idle_frac()
