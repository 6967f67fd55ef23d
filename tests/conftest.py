"""Test configuration: force an 8-device virtual CPU mesh before jax import.

Mirrors the reference's single-process multi-node testing strategy
(/root/reference/python/ray/tests/conftest.py ray_start_cluster): all
multi-"chip" sharding tests run against virtual CPU devices so no TPU pod is
needed.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
# No background jit prewarm under pytest: the warm grid (24 kernels per
# geometry, re-armed by every HeadServer's first sync) competes with the
# tests for the 1-2 cores CI runs on, and its interpreter-exit joins
# (scheduler/device._drain_prewarms) add up to ~30s of teardown tail to
# the suite. The persistent XLA compile cache keeps the inline first-touch
# compiles cheap across runs. Production keeps prewarm ON.
os.environ.setdefault("RAY_TPU_SCHED_PREWARM", "0")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Plugins (e.g. jaxtyping's pytest hook) import jax before this conftest, so
# the env var above can be too late for the platform choice — force it via
# config too (safe as long as no backend has initialized yet).
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture()
def local_cluster():
    """A small simulated multi-node cluster (single process)."""
    import ray_tpu

    ray_tpu.init(num_nodes=3, resources_per_node={"CPU": 4, "memory": 1 << 30})
    yield ray_tpu
    ray_tpu.shutdown()
