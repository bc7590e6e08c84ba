"""Test bootstrap: force an 8-device virtual CPU mesh before jax imports.

All kernel tests run on CPU devices so they are hermetic; the chip is
reached through `python chip_smoke.py` (one process owns it).
"""

import os

# Tests run on a virtual 8-device CPU mesh.  The pin is set in the
# environment too, so every process a test spawns inherits it.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def ctx():
    from ceph_tpu.common.context import Context
    return Context("client.test")
