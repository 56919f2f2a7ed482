"""read_roofline (%, device trace): the sub-ring read's bytes floor over the
HBM peak, as a share of its device time per read. The floor, whatever
kernel does the read: the w epochs' int8 registers read once (w * K * m
bytes) and Ĉ[K] written once (4 K bytes)."""

import importlib.util
import pathlib


def floor_bytes(conf: dict, w: int) -> int:
    return w * conf["k"] * conf["m"] + 4 * conf["k"]


def _read_s(run):
    path = pathlib.Path(__file__).with_name("read_dev_ms.py")
    spec = importlib.util.spec_from_file_location("_bench_read_dev_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read_s(run)


def read(run):
    s, peak = _read_s(run), run["peak"]
    if not s or peak is None:
        return None
    return floor_bytes(run["config"], int(run["mix"]["subring_w"])) / peak["hbm_bytes_per_s"] / s * 100.0
