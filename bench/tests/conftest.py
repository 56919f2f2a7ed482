"""Fixtures of the benchmark's own tests: the harness on the CPU at tiny
sizes (``bench_fixtures.py``)."""

import pathlib

import pytest

import bench_fixtures


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> pathlib.Path:
    return bench_fixtures.make_root(tmp_path_factory.mktemp("bench"))
