"""Test configuration.

JAX runs on CPU with 8 virtual devices so multi-chip sharding logic is
exercised without TPU hardware (the driver separately dry-runs the multichip
path).  Must be set before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402

if os.environ.get("RT_TEST_LOG_LEVEL"):
    import logging
    logging.basicConfig(level=os.environ["RT_TEST_LOG_LEVEL"])
    logging.getLogger("jax").setLevel(logging.WARNING)


@pytest.fixture(scope="module")
def ray_start():
    """Module-scoped local cluster with 4 CPUs (reference: ray_start_regular)."""
    import ray_tpu
    # Generous CPU count: module-scoped tests accumulate long-lived actors.
    ray_tpu.init(num_cpus=16, _worker_env={"JAX_PLATFORMS": "cpu"},
                 log_level=os.environ.get("RT_TEST_LOG_LEVEL", "WARNING"))
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_fresh():
    """Function-scoped cluster for tests that mutate cluster state."""
    import ray_tpu
    ray_tpu.init(num_cpus=4, _worker_env={"JAX_PLATFORMS": "cpu"})
    yield
    ray_tpu.shutdown()
