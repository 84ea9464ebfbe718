"""Test environment: force an 8-device virtual CPU mesh before jax initializes,
mirroring SURVEY §4's implication — multi-chip collective tests must run on a single
host the way the reference runs multi-process localhost PS tests.

Cross-device tier (the reference's tests/python/gpu/test_operator_gpu.py
pattern — the WHOLE op suite re-run against the accelerator): set
``MXTPU_TEST_PLATFORM=tpu`` to leave the real backend active instead of
the hermetic CPU mesh. Tests requiring >1 device are skipped there (one
chip); everything else exercises the identical code paths on real
hardware. Usage: ``MXTPU_TEST_PLATFORM=tpu python -m pytest
tests/test_operator.py tests/test_operator_sweep.py ...``.
"""
import os

_PLATFORM = os.environ.get("MXTPU_TEST_PLATFORM", "cpu")

if _PLATFORM == "cpu":
    # tests force the CPU so the suite is hermetic and the 8-device
    # virtual mesh is available; the environment variable, set before jax
    # is imported, is all it takes
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multidevice: needs the 8-device virtual CPU mesh or spawns a "
        "multi-process world; skipped on the single-chip TPU tier")
    config.addinivalue_line(
        "markers",
        "slow: exceeds the tier-1 wall-clock budget (interpret-mode "
        "Pallas kernels at real shapes etc.); tier-1 runs -m 'not slow'")


def pytest_collection_modifyitems(config, items):
    if _PLATFORM == "cpu":
        return
    # accelerator tier: a single real chip — skip tests explicitly marked
    # as needing the multi-device mesh (a name-substring heuristic used
    # here previously wrongly matched e.g. test_orde[ring])
    multi = pytest.mark.skip(
        reason="needs the 8-device virtual CPU mesh (MXTPU_TEST_PLATFORM)")
    for item in items:
        if item.get_closest_marker("multidevice") is not None:
            item.add_marker(multi)


@pytest.fixture(autouse=True)
def _seed():
    """@with_seed equivalent (ref: tests/python/unittest/common.py)."""
    np.random.seed(0)
    import mxtpu as mx
    mx.random.seed(0)
    yield
