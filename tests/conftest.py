"""Test harness: fake an 8-chip TPU mesh with CPU devices.

The reference's tests run single-machine but fully distributed-mode —
real scheduler + server subprocesses on localhost (reference:
tests/meta_test.py:26-85). Our equivalent, per SURVEY §4: a virtual
8-device CPU mesh via XLA_FLAGS so every collective, sharding, and
multi-host code path executes for real, just on one host.
"""

import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("BPS_PARTITION_BYTES", "4096000")

import jax  # noqa: E402
import pytest  # noqa: E402

# Tier-1 runs on the 8-device virtual CPU mesh whatever the caller's
# environment says: no test may claim a chip (one process at a time owns
# it), and jax.config outranks a JAX_PLATFORMS a tool exported earlier.
jax.config.update("jax_platforms", "cpu")


@pytest.fixture(autouse=True)
def _fresh_bps():
    """Each test gets a clean runtime (reference: meta_test wraps each test
    in init/shutdown)."""
    yield
    import byteps_tpu as bps
    bps.shutdown()


@pytest.fixture
def mesh8():
    from byteps_tpu.parallel.mesh import make_mesh
    return make_mesh({"data": 8})
