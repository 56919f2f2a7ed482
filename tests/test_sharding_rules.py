"""Unit tests for the logical-axis -> mesh-axis resolver (no device mesh ops,
just spec construction against 2- and 3-axis meshes)."""

import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.models import sharding as ms
from repro.models.common import ParamDef


@pytest.fixture(scope="module")
def meshes():
    # Abstract meshes: no XLA device initialization issues on CPU (uses the
    # single real device repeated logically via AbstractMesh).
    two = AbstractMesh((16, 16), ("data", "model"))
    three = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return two, three


def test_model_class_divisibility(meshes):
    two, _ = meshes
    # heads=32 divides 16 -> sharded; heads=8 does not -> replicated dim.
    assert ms.resolve(("embed", "heads", None), two, (4096, 32, 128)) == P(("data",), "model", None)
    assert ms.resolve(("embed", "kv_heads", None), two, (4096, 8, 128)) == P(("data",), None, None)


def test_fsdp_class_divisibility_and_fallback(meshes):
    two, three = meshes
    # 4096 % 16 == 0 -> data-sharded.
    assert ms.resolve(("embed",), two, (4096,)) == P(("data",))
    # 4097 not divisible -> replicated.
    assert ms.resolve(("embed",), two, (4097,)) == P(None)
    # 3-axis: (pod,data) product 32; 64 divisible -> both axes.
    assert ms.resolve(("batch", None), three, (64, 7)) == P(("pod", "data"), None)
    # 2 only divisible by pod -> prefix fallback.
    assert ms.resolve(("batch", None), three, (2, 7)) == P(("pod",), None)


def test_seq_model_axis(meshes):
    two, _ = meshes
    assert ms.resolve(("batch", "seq_model", None, None), two, (128, 32768, 8, 128)) == P(
        ("data",), "model", None, None
    )


def test_unknown_axis_raises(meshes):
    two, _ = meshes
    with pytest.raises(ValueError):
        ms.resolve(("bogus",), two, (8,))


def test_spec_tree_structure(meshes):
    two, _ = meshes
    defs = {
        "a": ParamDef((4096, 32, 128), ("embed", "heads", None)),
        "n": {"b": ParamDef((256,), (None,))},
    }
    tree = ms.spec_tree(defs, two)
    assert tree["a"] == P(("data",), "model", None)
    assert tree["n"]["b"] == P(None)


def test_full_configs_have_no_duplicate_axes(meshes):
    """Every ParamDef in every full config must resolve to a valid spec
    (no mesh axis used twice in one spec) on both production meshes."""
    from repro import configs
    from repro.models import transformer
    from repro.models.common import _leaf_paths

    two, three = meshes
    for arch in configs.list_archs():
        cfg = configs.get_config(arch)
        for mesh in (two, three):
            for path, d in _leaf_paths(transformer.model_defs(cfg)):
                spec = ms.resolve(d.axes, mesh, d.shape)
                flat = [a for part in spec if part for a in (part if isinstance(part, tuple) else (part,))]
                assert len(flat) == len(set(flat)), (arch, path, spec)
            for path, d in _leaf_paths(transformer.cache_defs(cfg, 8, 64)):
                spec = ms.resolve(d.axes, mesh, d.shape)
                flat = [a for part in spec if part for a in (part if isinstance(part, tuple) else (part,))]
                assert len(flat) == len(set(flat)), (arch, "cache", path, spec)
