"""setup_s (s, host clock): process start to the first timed event:
imports, traffic pool, state on the device, warm-up (and compiles, when
the cache is cold)."""


def read(run):
    return run["setup_s"]
