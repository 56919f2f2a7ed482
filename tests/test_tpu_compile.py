"""Ahead-of-time compiles of the main-path executables for a TPU v5e.

Each kernel test lowers one Pallas entry at the widths of the chip
deployment (K = 2^20 tenant rows, m = 128 registers, 2^8 histogram bins,
E = 4 epochs, micro-batches of 16384 and 65536 elements) with
``interpret=False`` and compiles it for a described (not attached) v5e
chip: what Mosaic or the TPU compiler refuses fails here, without a chip.
The container-update tests compile the donated per-batch updates at the
same widths and check from the compiled module that they stay in place.
The four-chip tests compile the row-sharded window of the fleet deployment
(K = 2^22 over the 2x2 host's four chips): its init builds each shard in
place, and its pipeline update stays in place with no gathered state.
Nothing runs, so nothing about results or times is checked.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU compiler library, so describing it while
test modules are collected would break the other test workers. The
persistent compilation cache is off around the compiles (a compile for a
described chip cannot be read back without one).
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import SketchConfig, dyn_array, sharded_window_array, sharding, window_array
from repro.kernels import dyn_array_update, estimate, window_union
from repro.sketchstream import ingest

K, M, NB, E = 2**20, 128, 256, 4
R_MIN, TOP_BIN = -127, 254


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler can be loaded here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The described host's four chips as a 1-D ``"sketch"`` mesh, with the
    ``Auto`` axis type of ``launch.mesh.make_sketch_mesh``."""
    return Mesh(np.array(topo.devices[:4]), (sharding.AXIS,), axis_types=(AxisType.Auto,))


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the kernel lowered to Mosaic
    return compiled


@pytest.mark.parametrize("batch", [16384, 65536])
def test_dyn_array_qr_compiles_for_v5e(one_chip, batch):
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    _compile(
        lambda w, rows, scales: dyn_array_update.dyn_array_qr_padded(
            w, rows, scales, m=M, interpret=False
        ),
        s((batch, 1)), s((batch, NB)), s((1, NB)),
    )


def test_window_union_compiles_for_v5e(one_chip):
    compiled = _compile(
        lambda regs, include: window_union.window_union_padded(
            regs, include, m=M, nb_padded=NB, r_min=R_MIN, interpret=False
        ),
        jax.ShapeDtypeStruct((E, K, M), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((E,), jnp.int32, sharding=one_chip),
    )
    # Only the [K, 2^b] histogram leaves the kernel; the union stays in VMEM.
    assert compiled.memory_analysis().output_size_in_bytes == K * NB * 4


def test_estimate_rows_compiles_for_v5e(one_chip):
    _compile(
        lambda regs: estimate.estimate_rows_padded(
            regs, m=M, nb_padded=NB, r_min=R_MIN, top_bin=TOP_BIN, interpret=False
        ),
        jax.ShapeDtypeStruct((K, M), jnp.int8, sharding=one_chip),
    )


# Ops that move a whole state plane when their result is plane-sized: the
# TPU scatter's 1-D relayout (copy out, reshape back) and the epoch slice
# and write-back of a ring plane.
_PLANE_OPS = ("copy", "reshape", "dynamic-slice", "dynamic-update-slice")
# "%name = s32[1048576,256]{1,0:T(8,128)} reshape(...)": dims and opcode of
# every array-valued instruction, fused computations included.
_HLO_OP = re.compile(r"= [a-z][a-z0-9]*\[([\d,]*)\]\S* ([\w-]+)\(")


def _plane_passes(hlo: str, min_elems: int) -> list:
    """(opcode, dims) of every ``_PLANE_OPS`` instruction of an optimized
    HLO module whose result holds at least ``min_elems`` elements."""
    return [
        (op, dims)
        for dims, op in _HLO_OP.findall(hlo)
        if op in _PLANE_OPS
        and math.prod(int(d) for d in dims.split(",") if d) >= min_elems
    ]


def _update_executable(which, one_chip):
    cfg = SketchConfig(m=M, b=8, seed=0)
    b = 16384
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    shapes = lambda tree: jax.tree.map(lambda s: sd(s.shape, s.dtype), tree)
    batch = (sd((b,), jnp.int32), sd((b,), jnp.uint32), sd((b,), jnp.float32),
             sd((b,), jnp.bool_))
    if which == "dyn_commit":
        st = shapes(jax.eval_shape(functools.partial(dyn_array.init, cfg, K)))
        plan = shapes(jax.eval_shape(
            lambda s, *a: dyn_array._plan_batch(cfg, s, *a), st, *batch))
        return dyn_array._commit_donated.lower(st, plan).compile()
    st = shapes(jax.eval_shape(functools.partial(window_array.init, cfg, K, E)))
    return ingest._window_update_fn(cfg).lower(st, *batch).compile()


@pytest.mark.parametrize("which", ["dyn_commit", "window_update"])
def test_container_update_stays_in_place_on_v5e(one_chip, which):
    """The donated per-batch update writes only the rows the batch
    addresses: B-sized temporaries (the plane, 1 GiB here, is larger than
    on-chip memory, so a plane-sized temporary cannot hide there) and no
    plane-sized copy, reshape or epoch slice in the optimized module."""
    compiled = _update_executable(which, one_chip)
    plane_bytes = K * NB * 4
    assert compiled.memory_analysis().temp_size_in_bytes < plane_bytes // 16
    assert _plane_passes(compiled.as_text(), K * M) == []


# The fleet window: 2^22 source rows, a quarter on each chip.
K4 = 2**22
FLEET_CFG = SketchConfig(m=M, b=8, seed=0)
_FLEET_DIMS = jax.tree.leaves(sharded_window_array.DIMS, is_leaf=lambda d: d is None)


def _fleet_init():
    return window_array.init(FLEET_CFG, K4, E)


def test_sharded_window_init_builds_each_shard_in_place_on_v5e(four_chips):
    """``sharded_window_array.init``'s executable writes each chip's own
    K/4 rows and nothing else: no temporaries, a quarter of the state per
    device (the whole ring, 24.2e9 B, fits no single chip)."""
    fill, _ = sharding.init_rows_jit(_fleet_init, four_chips, sharded_window_array.DIMS)
    compiled = fill.lower().compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes == 0
    state = jax.tree.leaves(jax.eval_shape(_fleet_init))
    total = sum(x.size * x.dtype.itemsize for x in state)
    assert total > 16 * 2**30 > ma.output_size_in_bytes
    assert ma.output_size_in_bytes < total // 4 + 2**20
    for leaf, sh, d in zip(state, compiled.output_shardings, _FLEET_DIMS):
        want = leaf.shape if d is None else leaf.shape[:d] + (K4 // 4,) + leaf.shape[d + 1:]
        assert sh.shard_shape(leaf.shape) == want


# Collectives that would move state between chips.
_GATHERS = re.compile(r"\b(all-gather|all-to-all|collective-permute|reduce-scatter)[-a-z]*\(")
# "%x = u32[] all-reduce(...)": the result shape of every all-reduce.
_ALL_REDUCE = re.compile(r"= ([a-z][a-z0-9]*\[[\d,]*\])\S* all-reduce[-a-z]*\(")


def test_sharded_window_pipeline_update_stays_in_place_on_v5e(four_chips):
    """The donated sharded pipeline update, fed a replicated micro-batch,
    aliases its whole state, keeps B-sized temporaries (the one-chip
    update's), and crosses chips only by scalar all-reduces (the ticket):
    no state is gathered or exchanged."""
    b = 16384
    state = jax.tree.leaves(jax.eval_shape(_fleet_init))
    st = sharded_window_array.ShardedWindowArrayState(*[
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(four_chips, sharding.spec(d)))
        for x, d in zip(state, _FLEET_DIMS)
    ])
    rep = NamedSharding(four_chips, PartitionSpec())
    batch = [jax.ShapeDtypeStruct((b,), dt, sharding=rep)
             for dt in (jnp.int32, jnp.uint32, jnp.float32, jnp.bool_)]
    compiled = ingest._sharded_window_update_fn(FLEET_CFG, four_chips, sharding.AXIS).lower(
        st, *batch).compile()
    ma = compiled.memory_analysis()
    per_chip = sum(x.size * x.dtype.itemsize for x in state) // 4
    assert ma.alias_size_in_bytes >= per_chip - 2**10
    assert ma.temp_size_in_bytes < b * NB * 4 * 4  # a few [B, 2^b] int32 blocks
    text = compiled.as_text()
    assert _GATHERS.findall(text) == []
    reduced = _ALL_REDUCE.findall(text)
    assert reduced and all(re.fullmatch(r"[a-z0-9]+\[\]", r) for r in reduced)
    assert _plane_passes(text, K4 // 4 * M) == []


# "%s = s32[268435456]{0:T(1024)} scatter(" or "%sort = (s32[134217728]{...},
# s32[134217728]{...}) sort(": the first result shape of every scatter and
# sort, tuple results included.
_SCATTER_SORT = re.compile(r"= \(?[a-z][a-z0-9]*\[([\d,]*)\][^=\n]*? (scatter|sort)\(")
# Temporaries of the donated rotation when its union histograms came from a
# scatter-add of K·m ones into the flat plane and a sort of its indices:
# 1,615,419,904 B on one chip at K = 2^20, and per chip of the fleet window.
_SCATTER_ROTATE_TEMP_BYTES = 1_615_419_904


def _assert_rotation_rebuilds_densely(compiled, rows):
    """No scatter or sort of ``rows``·m elements or more in the optimized
    module, and temporaries no larger than the scatter-built rotation's."""
    big = [
        (op, dims)
        for dims, op in _SCATTER_SORT.findall(compiled.as_text())
        if math.prod(int(d) for d in dims.split(",") if d) >= rows * M
    ]
    assert big == []
    assert compiled.memory_analysis().temp_size_in_bytes <= _SCATTER_ROTATE_TEMP_BYTES


def test_window_rotation_rebuilds_union_densely_on_v5e(one_chip):
    """The donated rotation at the single-chip deployment's widths counts the
    union histograms densely: neither the scatter into the ``int32[K·2^b]``
    plane nor the sort of its K·m indices is compiled."""
    cfg = SketchConfig(m=M, b=8, seed=0)
    st = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(functools.partial(window_array.init, cfg, K, E)),
    )
    _assert_rotation_rebuilds_densely(window_array._rotate_donated.lower(cfg, st).compile(), K)


def test_sharded_window_rotation_rebuilds_union_densely_on_v5e(four_chips):
    """The fleet window's donated shard-local rotation (``window_array.rotate``
    per shard under ``shard_map``) compiles the same dense count on each
    chip's K/4 rows."""
    state = jax.tree.leaves(jax.eval_shape(_fleet_init))
    st = sharded_window_array.ShardedWindowArrayState(*[
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(four_chips, sharding.spec(d)))
        for x, d in zip(state, _FLEET_DIMS)
    ])
    compiled = sharded_window_array._rotate_donated.lower(
        FLEET_CFG, four_chips, sharding.AXIS, st
    ).compile()
    _assert_rotation_rebuilds_densely(compiled, K4 // 4)
