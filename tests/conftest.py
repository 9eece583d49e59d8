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


@pytest.fixture
def dense_reference_greedy():
    """Greedy tokens of the plain reference: a loop over ``llama_prefill`` and
    ``llama_decode_step`` on the dense cache of ``models/llama.py``, with the
    engine's own weights and FFN hook. The served (paged) programs are held
    to it; build the engine with ``model_dtype="float32"`` so that argmax
    does not depend on a program's shape. The prompt is right-padded to the
    engine's prefill bucket: a routed FFN's expert capacity follows the
    number of rows it is given, padding included."""

    def _run(engine, prompt_tokens: list[int], n: int) -> list[int]:
        import jax.numpy as jnp

        from langstream_tpu.models.llama import (
            init_kv_cache,
            llama_decode_step,
            llama_prefill,
        )
        from langstream_tpu.serving.engine import _prefill_bucket_rows

        c, params, ffn = engine.model_config, engine.params, engine._ffn
        cache_k, cache_v = init_kv_cache(c, 1)
        lengths = jnp.asarray([len(prompt_tokens)], jnp.int32)
        pad = _prefill_bucket_rows(
            len(prompt_tokens), c.max_seq_len) - len(prompt_tokens)
        logits, cache_k, cache_v = llama_prefill(
            c, params, jnp.asarray([prompt_tokens + [0] * pad], jnp.int32),
            lengths, cache_k, cache_v, jnp.asarray([0]), ffn=ffn,
        )
        out = []
        for _ in range(n):
            out.append(int(jnp.argmax(logits[0])))
            logits, cache_k, cache_v = llama_decode_step(
                c, params, jnp.asarray([out[-1]], jnp.int32), lengths,
                cache_k, cache_v, ffn=ffn,
            )
            lengths = lengths + 1
        return out

    return _run
