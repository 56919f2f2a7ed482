"""rotate_ms (ms, device trace): device time of the ring rotation
(``modules.json`` "rotate") per rotation, averaged over the devices."""


def read(run):
    t = run["trace"]
    if t is None or not t.devices or not run["rotations"]:
        return None
    s = t.module_s(run["modules"]["rotate"])
    return None if s is None else s / len(t.devices) / run["rotations"] * 1e3
