import os
import sys

# This suite REQUIRES the 8-device host platform; it is launched by
# tests/test_multidevice.py in a subprocess with
# XLA_FLAGS=--xla_force_host_platform_device_count=8.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax  # noqa: E402
import pytest  # noqa: E402
from repro import compat  # noqa: E402


def pytest_collection_modifyitems(items):
    """Every test in this directory is part of the multi-device suite: tag
    it ``md`` so tier-1 can deselect explicitly (``-m "not md"``)."""
    for item in items:
        item.add_marker(pytest.mark.md)


@pytest.fixture(scope="session")
def mesh8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 host devices")
    return compat.make_mesh((2, 4), ("data", "model"))


@pytest.fixture(scope="session")
def mesh1d():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 host devices")
    return compat.make_mesh((8,), ("model",))
