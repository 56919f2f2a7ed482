"""read_dev_ms (ms, device trace): device time of the sub-ring read
executable (``modules.json`` "read_subring") per read."""


def read_s(run):
    t = run["trace"]
    if t is None or not t.devices or not run["subring_s"]:
        return None
    s = t.module_s(run["modules"]["read_subring"])
    return None if s is None else s / len(t.devices) / len(run["subring_s"])


def read(run):
    s = read_s(run)
    return None if s is None else s * 1e3
