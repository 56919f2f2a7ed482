"""Production mesh builders (MULTI-POD DRY-RUN step 1).

A FUNCTION, not a module constant: importing this module never touches jax
device state (jax locks the platform/device count at first backend init, and
the dry-run must set XLA_FLAGS before that happens).

Every mesh in the repo is built here with ``Auto`` axes: shardings are
propagated by the compiler from the ``NamedSharding`` / ``PartitionSpec``
placements and ``with_sharding_constraint`` hints the models and sketch
containers already carry. (``jax.make_mesh`` defaults to ``Explicit`` axes,
under which every op must name its output sharding.)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types (see module docstring);
    ``devices`` defaults to the first ``prod(shape)`` visible devices."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), (AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int | None = None):
    """Whatever this host actually has (smoke tests / examples)."""
    n = len(jax.devices())
    model = model or (2 if n % 2 == 0 and n > 1 else 1)
    return make_mesh((n // model, model), ("data", "model"))


def make_sketch_mesh(n_shards: int | None = None):
    """1-D mesh over the ``"sketch"`` axis: tenant rows of a sharded sketch
    container (ShardedSketchArray, ShardedDynArray, sharded WindowArray).

    Every sharded front in ``core/`` partitions its per-tenant state
    row-wise over this axis via the shared layer (core/sharding.py);
    K ~ 1e7 tenants then cost K·state/n_shards bytes per device instead of
    one host's worth. Defaults to every visible device; an explicit
    ``n_shards`` must not exceed the host's device count (shard_map needs
    one device per shard) and takes the first ``n_shards`` devices.
    Telemetry embedded in a training step can instead reuse an existing
    mesh axis (``axis="data"`` on any sharded container) — this builder is
    for the standalone monitoring fleet / examples / benchmarks.
    """
    devices = jax.devices()
    n = n_shards or len(devices)
    if n > len(devices):
        raise ValueError(
            f"sketch mesh wants {n} shards but only {len(devices)} devices are "
            "visible (set XLA_FLAGS=--xla_force_host_platform_device_count "
            "for host-device smoke runs)"
        )
    return make_mesh((n,), ("sketch",), devices=devices[:n])
