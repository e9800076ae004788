"""Test configuration: force an 8-device virtual CPU mesh.

Tests must run without TPU hardware; multi-chip sharding paths are exercised
on a virtual CPU mesh (the driver separately dry-runs the multichip path via
``__graft_entry__.dryrun_multichip``).  Both settings are environment
variables JAX reads at import, so they are set before it.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

assert len(jax.devices()) == 8, (
    f"expected 8 virtual CPU devices, got {jax.devices()}"
)

import pytest  # noqa: E402

REFERENCE = "/root/reference"


@pytest.fixture(scope="session")
def reference_dir():
    return REFERENCE
