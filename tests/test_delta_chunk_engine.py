"""Where the chunked delta rule's kernel (ops/delta_chunk.py) is in the
engine's prefill program and where it is not: it follows the engine's one
selection for the recurrent state's kernels (``ssm_state_kernel``), engages
on the delta-rule block of every prefill program or not at all, and the
hybrid family's other two patterns trace nothing of it."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine


def lowered_prefill(model: str, selected: str) -> tuple[str, str, str | None]:
    """The engine's greedy prefill program of 2 x 32 tokens, lowered and as
    a jaxpr (which names a ``pallas_call`` the interpreter inlines), and the
    selection its ``stats()`` report."""
    async def main():
        engine = TpuServingEngine(ServingConfig(
            model=model, model_dtype="float32", slots=4, max_seq_len=256,
            kv_layout="paged", kv_block_size=16, prefix_cache=False,
            decode_chunk=8, decode_chunk_light=4, paged_kernel=selected))
        try:
            mode = engine._sampler_mode(
                np.zeros(1, np.float32), np.zeros(1, np.int32),
                np.ones(1, np.float32))
            sel = (jnp.asarray(engine.block_mgr.tables[:2]),
                   jnp.arange(2, dtype=jnp.int32))
            fn, args = engine._prefill_fn(mode), (
                engine.params, engine.cache_k, engine.cache_v, engine.state,
                jnp.zeros((2, 32), jnp.int32), jnp.full((2,), 20, jnp.int32),
                sel, jax.random.PRNGKey(0), jnp.zeros(2, jnp.float32),
                jnp.zeros(2, jnp.int32), jnp.ones(2, jnp.float32))
            return (fn.lower(*args).as_text(), str(jax.make_jaxpr(fn)(*args)),
                    engine.stats()["ssm_state_kernel"])
        finally:
            await engine.close()

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(main())
    finally:
        loop.close()


@pytest.fixture(scope="module")
def solar():
    return {k: lowered_prefill("solar-tiny", k) for k in ("xla", "pallas-interpret")}


def test_the_pallas_selection_puts_one_kernel_in_the_delta_rule_block(solar):
    text, jaxpr, reported = solar["pallas-interpret"]
    assert reported == "pallas-interpret"
    # the blocks are scanned: the delta-rule block's mixer is traced once
    assert jaxpr.count("name=delta_chunk_rule") == 1
    assert "triangular_solve" not in jaxpr and "triangular_solve" not in text


def test_the_xla_selection_keeps_the_expression(solar):
    text, jaxpr, reported = solar["xla"]
    assert reported == "xla"
    assert "delta_chunk_rule" not in jaxpr
    assert "triangular_solve" in jaxpr      # the UT transform, a chunk a head
    assert text != solar["pallas-interpret"][0]


@pytest.mark.parametrize("model", ["hybrid-tiny", "granite-tiny"])
def test_a_pattern_without_the_delta_rule_traces_nothing_of_it(model):
    xla, _, _ = lowered_prefill(model, "xla")
    pallas, jaxpr, reported = lowered_prefill(model, "pallas-interpret")
    assert reported == "pallas-interpret"
    assert "delta_chunk_rule" not in jaxpr and "triangular_solve" not in jaxpr
    # the one kernel the selection puts into this prefill is the commit's
    # (ops/pool_commit.py, PR 49: K and V in one call)
    assert jaxpr.count("pallas_call") == 1 and "name=pool_commit" in jaxpr
    assert pallas != xla
