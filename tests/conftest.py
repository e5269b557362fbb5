"""Test configuration: 8 emulated host devices for sharding/zebra tests.

Tests use a small fixed pool so meshes up to 2x4 are available.
"""

import os
import pathlib
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

try:  # offline container: fall back to the vendored deterministic stub
    import hypothesis  # noqa: F401
except ImportError:
    sys.path.insert(0, str(pathlib.Path(__file__).parent / "_stubs"))

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from repro.launch.mesh import make_mesh
    return make_mesh((2, 4), ("data", "model"))


@pytest.fixture(scope="session")
def mesh4():
    from repro.launch.mesh import make_mesh
    return make_mesh((2, 2), ("data", "model"))
