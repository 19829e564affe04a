"""Test configuration: force JAX onto a virtual 8-device CPU platform.

Multi-device tests use an 8-device CPU mesh standing in for a TPU pod slice
(the reference's analog is the 10-daemon in-process cluster,
functional_test.go:42-62).  Must run before any jax import.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Hermetic tests: no persistent compile cache (gubernator_tpu/ops would
# otherwise place one inside the checkout).  The env form also reaches the
# subprocesses tests spawn; tests of the cache rule itself clear it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402

from gubernator_tpu.core import clock as clock_mod  # noqa: E402

# raceguard: runtime lock-order + event-loop-stall detection, armed for
# the whole session (GUBGUARD_RACE=0 disarms).  The static counterpart
# is tools/gubguard; see docs/invariants.md.
pytest_plugins = ["gubernator_tpu.testing.raceguard"]


@pytest.fixture
def frozen_clock():
    """Freeze the default clock for the test (reference clock.Freeze seam,
    functional_test.go:160)."""
    clock_mod.freeze()
    yield clock_mod.default_clock()
    clock_mod.unfreeze()
