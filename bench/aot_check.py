"""Compile each cell's executables at real size for a described TPU v5e,
without a chip, and print their memory analysis. Run by hand, not a test:

    JAX_PLATFORMS=cpu python bench/aot_check.py [cell ...]
    JAX_PLATFORMS=cpu python bench/aot_check.py --fleet-init <config> <K>

The executables are the ones a cell's window drives: the key-directory
route at the arrival chunk, the container update at the micro-batch (the
pipeline's donated WindowArray update, or the DynArray plan and donated
commit), the ring rotation, and the sub-ring read of an open-loop cell.

``--fleet-init`` compiles the first step of
``sharded_window_array.init`` for a window configuration at K tenants:
``window_array.init`` of the whole ring, on one device, before the rows
are spread over the mesh.
"""

from __future__ import annotations

import functools
import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = pathlib.Path(__file__).resolve().parent
for _p in (BENCH, BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import harness  # noqa: E402
from repro.core import SketchConfig, dyn_array, key_directory, window_array  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.sketchstream import ingest  # noqa: E402


def shapes(tree, sharding):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree)


def executables(cell: harness.Cell, one):
    """(name, jitted, args, kwargs) of the cell's executables."""
    conf, mix = cell.config, cell.mix
    cfg = SketchConfig(m=conf["m"], b=conf["b"], seed=conf["sketch_seed"])
    dcfg = key_directory.DirectoryConfig(capacity=conf["k"], seed=conf["directory_seed"])
    b, c = conf["batch"], int(mix["chunk"])
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    batch = (sd((b,), jnp.int32), sd((b,), jnp.uint32), sd((b,), jnp.float32), sd((b,), jnp.bool_))
    pair = (sd((c,), jnp.uint32), sd((c,), jnp.uint32))
    dstate = shapes(jax.eval_shape(functools.partial(key_directory.init, dcfg)), one)
    out = [("directory_route", key_directory.route, (dcfg, dstate, pair),
            {"mask": None, "epoch": sd((), jnp.int32)} if conf["container"] == "window" else {})]
    if conf["container"] == "window":
        st = shapes(jax.eval_shape(functools.partial(window_array.init, cfg, conf["k"], conf["epochs"])), one)
        out.append(("window_update", ingest._window_update_fn(cfg), (st, *batch), {}))
        out.append(("window_rotate", window_array._rotate_donated, (cfg, st), {}))
        if mix["arrival"] == "open":
            def window_subring_read(state):
                return ops.window_union_estimate_op(cfg, state, int(mix["subring_w"]), interpret=False)

            out.append(("window_subring_read", jax.jit(window_subring_read), (st,), {}))
    else:
        st = shapes(jax.eval_shape(functools.partial(dyn_array.init, cfg, conf["k"])), one)
        out.append(("dyn_plan", dyn_array._plan_batch_jit, (cfg, st, *batch), {}))
        plan = shapes(jax.eval_shape(lambda s, *a: dyn_array._plan_batch(cfg, s, *a), st, *batch), one)
        out.append(("dyn_commit", dyn_array._commit_donated, (st, plan), {}))
    return out


def report(label, fn, args, kw) -> None:
    t = time.perf_counter()
    ma = fn.lower(*args, **kw).compile().memory_analysis()
    print(f"  {label}: compiled in {time.perf_counter() - t:.1f} s; "
          f"arguments {ma.argument_size_in_bytes} B, outputs {ma.output_size_in_bytes} B, "
          f"aliased {ma.alias_size_in_bytes} B, temporaries {ma.temp_size_in_bytes} B, "
          f"code {ma.generated_code_size_in_bytes} B", flush=True)


def fleet_init(config: str, k: int, one) -> None:
    conf = harness.load_json(harness.BENCH / "configs" / f"{config}.json")
    cfg = SketchConfig(m=conf["m"], b=conf["b"], seed=conf["sketch_seed"])
    print(f"== fleet init of {config} at K = {k}, E = {conf['epochs']}, on one v5e device")
    init = jax.jit(functools.partial(window_array.init, cfg, k, conf["epochs"]), out_shardings=one)
    report("window_array.init", init, (), {})


def main(argv) -> int:
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    if argv[:1] == ["--fleet-init"]:
        fleet_init(argv[1], int(argv[2]), one)
        return 0
    names = argv or [w["name"] for w in harness.benchmark()["workloads"]]
    for name in names:
        cell = harness.Cell(name)
        print(f"== {name} ({cell.config['name']}, state {cell.config.get('state_bytes')} B)")
        for label, fn, args, kw in executables(cell, one):
            report(label, fn, args, kw)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
