"""Test fixtures.

Multi-chip-without-TPUs strategy (SURVEY.md §4 implication): tests run JAX on
CPU with 8 virtual devices (`--xla_force_host_platform_device_count=8`), the
role KubeTestServer + testcontainers play in the reference — sharding and
collectives are exercised for real, just on host devices.
"""

import os

# Set before the first `import jax`: the platform and the persistent
# compilation cache are read from the environment at import. The suite is
# dominated by jit compiles of the same tiny-model programs, so a warm cache
# cuts a full run by minutes; the threshold is lowered because tiny programs
# compile in well under JAX's 1 s default. Where the cache goes is
# langstream_tpu/compile_cache.py's decision (JAX_COMPILATION_CACHE_DIR if
# set, else <repo>/.jax_cache) — point the variable at an empty directory
# to measure cold-compile behaviour.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.3")

from langstream_tpu.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()

import asyncio  # noqa: E402

import pytest  # noqa: E402

from langstream_tpu.runtime.memory_broker import MemoryBroker  # noqa: E402
from langstream_tpu.agents.vector import InMemoryVectorStore  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (multi-process or subprocess)"
    )


@pytest.fixture(autouse=True)
def _fresh_brokers():
    """Isolate broker + vector-store state between tests."""
    MemoryBroker.reset()
    InMemoryVectorStore.reset()
    yield
    MemoryBroker.reset()
    InMemoryVectorStore.reset()


@pytest.fixture
def run_async():
    def _run(coro):
        return asyncio.run(coro)

    return _run
