"""Shared pieces of the benchmark's own tests: the tiny specification (cells
and configurations under ``fixtures/``; generators, references, metric
readers and the harness from ``benchmarks/``) and a device check that only
these tests relax. CPU only; nothing here describes a TPU topology."""

import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@pytest.fixture(scope="session")
def tiny_spec():
    from benchmarks.harness.spec import Spec

    return Spec(os.path.join(HERE, "fixtures", "BENCHMARK.tiny.json"))


@pytest.fixture(scope="session")
def real_spec():
    from benchmarks.harness.spec import Spec

    return Spec()


def relaxed_device_check(chips: int):
    """Whatever devices are here stand in for the cell's chips."""
    import jax

    devices = jax.devices()
    if len(devices) < chips:
        pytest.skip(f"needs {chips} devices")
    return devices[:chips]
