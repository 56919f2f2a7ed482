"""window_read_ms (ms, host clock): mean wall time of a fleet-wide sub-ring
read (all K tenants, result on the host), clock started once the
dispatched state is ready."""


def read(run):
    s = run["subring_s"]
    return sum(s) / len(s) * 1e3 if s else None
