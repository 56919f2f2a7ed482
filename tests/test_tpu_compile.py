"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Each test lowers one kernel entry at the widths of the chip deployment
(K = 2^20 tenant rows, m = 128 registers, 2^8 histogram bins, E = 4 epochs,
micro-batches of 16384 and 65536 elements) with ``interpret=False`` and
compiles it for a described (not attached) v5e chip: what Mosaic or the TPU
compiler refuses fails here, without a chip. Nothing runs, so nothing about
results or times is checked.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU compiler library, so describing it while
test modules are collected would break the other test workers. The
persistent compilation cache is off around the compiles (a compile for a
described chip cannot be read back without one).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dyn_array_update, estimate, window_union

K, M, NB, E = 2**20, 128, 256, 4
R_MIN, TOP_BIN = -127, 254


@pytest.fixture(scope="module")
def one_chip():
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
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


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
