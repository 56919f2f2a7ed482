"""update_ms_per_batch (ms, device trace): device time of the container's
update executables (``modules.json`` "update") per micro-batch, averaged
over the cell's devices."""


def update_s(run):
    t = run["trace"]
    if t is None or not t.devices:
        return None
    s = t.module_s(run["modules"]["update"])
    return None if s is None else s / len(t.devices)


def read(run):
    s = update_s(run)
    return None if s is None or not run["batches"] else s / run["batches"] * 1e3
