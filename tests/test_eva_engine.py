"""The EVA family through ``engine.generate`` at the ``evabyte-tiny`` preset
on the CPU (float32: greedy streams are exactly shape-independent), by its
``Family`` record alone (``models/family.py``; no line of
``serving/engine.py`` knows it): two kinds of history at once. Concurrent
slots of unequal length (inside the first window of 32, on its edge, several
windows long) stream what each streams alone, under either read, while they
close chunks and windows inside decode chunks; a reused slot leaks no row;
admission reserves by the second kind's growth rule and a request that does
not fit waits; the two kinds' rows ride the flight samples; the programs
carry the family's scopes; and the engine refuses, for this model and by
name, every option that assumes a request's history is one table of K/V
blocks, each with this family's reason."""

import asyncio
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models.eva import FAMILY, EvaConfig
from langstream_tpu.serving.engine import (
    ServingConfig,
    TpuServingEngine,
    _family_of,
    _resolve_model_config,
)

# the window is 32 rows in chunks of 4, a block 8, a slot's ring 4 blocks
PROMPTS = [list(range(5, 5 + n)) for n in (9, 70, 32, 150, 31, 45)]


def config(**kw):
    base = dict(
        model="evabyte-tiny", model_dtype="float32", slots=4, max_seq_len=256,
        kv_layout="paged", kv_block_size=8, prefix_cache=False,
        decode_chunk=8, decode_chunk_light=4,
    )
    return ServingConfig(**{**base, **kw})


def greedy(max_tokens=48):
    return {"max-tokens": max_tokens, "temperature": 0}


@pytest.fixture(scope="module")
def run_async_module():
    loop = asyncio.new_event_loop()
    yield loop.run_until_complete
    loop.close()


@pytest.fixture(scope="module")
def alone(run_async_module):
    """Each prompt's stream when it is the only request: 48 tokens, more
    than a window."""
    async def main():
        engine = TpuServingEngine(config())
        try:
            return [(await engine.generate(p, greedy()))["tokens"]
                    for p in PROMPTS]
        finally:
            await engine.close()

    return run_async_module(main())


def test_the_engine_knows_the_new_names():
    family = _family_of("evabyte-tiny")
    assert family is FAMILY and family.name == "eva"
    assert _family_of("evabyte-6.5b-8l") is family
    real = _resolve_model_config("evabyte-6.5b-8l", 32768)
    assert real == EvaConfig.evabyte_6_5b_8l()
    assert (real.hidden, real.heads, real.kv_heads, real.head_dim,
            real.intermediate, real.vocab_size, real.pred_heads, real.window,
            real.chunk, real.layers) == (
        4096, 32, 32, 128, 11008, 320, 8, 2048, 16, 8)
    with pytest.raises(ValueError) as e:
        _resolve_model_config("no-such-model", 128)
    assert "evabyte-6.5b-8l" in str(e.value)


@pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
def test_concurrent_slots_of_unequal_length_stream_what_each_streams_alone(
        run_async, alone, kernel):
    async def main():
        engine = TpuServingEngine(config(paged_kernel=kernel))
        try:
            outs = await asyncio.gather(
                *(engine.generate(p, greedy()) for p in PROMPTS))
            return ([o["tokens"] for o in outs], engine.paged_read_kernel,
                    engine.family, engine.block_mgr.stats())
        finally:
            await engine.close()

    streams, read, family, kv = run_async(main())
    assert streams == alone and family == "eva" and read == kernel
    assert all(len(set(s)) > 2 for s in streams)
    # everything came back, both kinds; a ring is 4 blocks with no spare
    assert kv["live_blocks"] == kv["reserved_blocks"] == 0
    assert kv["window_ring_blocks"] == 4
    assert kv["window_num_blocks"] == 4 * 4 + 1
    assert kv["window_blocks_released"] == 6 * 4


def test_a_reused_slot_leaks_no_row_of_either_kind(run_async, alone):
    """One slot: every request runs in the blocks the last one left."""
    async def main():
        engine = TpuServingEngine(config(slots=1, kv_pool_blocks=9))
        try:
            out = []
            for i in (3, 0, 1, 0):
                out.append((await engine.generate(PROMPTS[i], greedy()))["tokens"])
            return out
        finally:
            await engine.close()

    assert run_async(main()) == [alone[3], alone[0], alone[1], alone[0]]


def test_admission_reserves_by_the_growth_rule_and_what_does_not_fit_waits(
        run_async, alone):
    """150 + 48 + 1 positions close six windows: six summary blocks and a
    ring. With seven summary blocks in all, a second such request waits for
    the first's release and then streams what it streams alone."""
    async def main():
        engine = TpuServingEngine(config(slots=2, kv_pool_blocks=8))
        try:
            m = engine.block_mgr
            first = asyncio.ensure_future(engine.generate(PROMPTS[3], greedy()))
            while not m.stats()["reserved_blocks"]:
                await asyncio.sleep(0.01)
            during = m.stats()
            second = await engine.generate(PROMPTS[3], greedy())
            return during, (await first)["tokens"], second["tokens"], m.stats()
        finally:
            await engine.close()

    during, first, second, after = run_async(main())
    assert during["reserved_blocks"] == 6 + 4
    assert during["window_reserved_blocks"] == 4
    assert first == second == alone[3]
    assert after["reserved_blocks"] == after["live_blocks"] == 0


def test_the_two_kinds_rows_ride_the_flight_samples(run_async):
    async def main():
        engine = TpuServingEngine(config())
        try:
            await asyncio.gather(
                *(engine.generate(p, greedy(20)) for p in PROMPTS[:4]))
            return (engine.flight.recent(64), engine.model_config,
                    engine._state_bytes, engine._kv_cache_bytes)
        finally:
            await engine.close()

    samples, mc, ring_bytes, summary_bytes = run_async(main())
    decode = [s for s in samples if s["phase"] == "decode"]
    assert decode and all("summary_rows" not in s for s in samples
                          if s["phase"] != "decode")
    for s in decode:
        # a slot reads its own window's rows, 32 at most, and 8 summary rows
        # a closed window
        assert 0 < s["window_rows"] <= s["active_at_dispatch"] * mc.window
        assert s["summary_rows"] % mc.per_window == 0
        assert s["pool_rows_held"] > 0 and s["pool_rows_plain_cache"] > 0
        assert 0 <= s["chunk_closes"] <= s["active_at_dispatch"]
        assert 0 <= s["window_closes"] <= s["chunk_closes"]
        assert s["ring_blocks_held"] <= 4 * 4
    longest = max(decode, key=lambda s: s["live_rows"])
    # 150 rows and more: four closed windows seen as 32 rows
    assert longest["summary_rows"] >= 4 * mc.per_window
    assert longest["pool_rows_held"] < longest["pool_rows_plain_cache"]
    assert longest["summary_blocks_held"] >= 5
    # the ring rides where the hybrid family's state does: 2 layers x
    # (4 slots x 4 + 1) blocks x 8 rows x 64 values, K and V
    assert ring_bytes == 2 * 2 * 17 * 8 * 64 * 4
    assert summary_bytes == 2 * 2 * (4 * 256 // 8 // 2) * 8 * 64 * 4


REFUSED = {
    "prefix-cache": (dict(prefix_cache=True), "reusable only whole"),
    "prefill-chunk": (dict(prefill_chunk=32), "summarised history"),
    "speculative-drafts": (dict(speculative_drafts=2),
                           "neither the ring nor the summaries"),
    "pool-role": (dict(pool_role="prefill"), "a ring's and a summary pool's"),
    "kv-quantize": (dict(kv_quantize="int8"), "a summary row is a mean"),
    "journal-dir": (dict(journal_dir="/nonexistent/journal"),
                    "two kinds of history"),
    "quantize": (dict(quantize="int8"), ""),
    "kv-layout": (dict(kv_layout="dense"), ""),
    "mesh": (dict(mesh=(("dp", 1),)), ""),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_every_option_that_assumes_one_table_of_kv_is_refused_by_name(option):
    kw, reason = REFUSED[option]
    with pytest.raises(ValueError, match=re.escape(option)) as e:
        TpuServingEngine(config(**kw))
    if option != "kv-layout":
        assert "evabyte-tiny" in str(e.value)
    assert reason in str(e.value)
    if reason:
        assert FAMILY.refusals[option] in str(e.value)


def test_the_lowered_programs_carry_the_family_s_scopes(run_async):
    async def main():
        engine = TpuServingEngine(config())
        try:
            slots = engine.config.slots
            mode = engine._sampler_mode(np.zeros(1, np.float32),
                                        np.zeros(1, np.int32),
                                        np.ones(1, np.float32))
            sampler = (jnp.zeros(slots, jnp.float32), jnp.zeros(slots, jnp.int32),
                       jnp.ones(slots, jnp.float32))
            args = (engine.params, engine.cache_k, engine.cache_v, engine.state,
                    jnp.zeros(slots, jnp.int32), jnp.ones(slots, jnp.int32),
                    jnp.ones(slots, bool), jnp.asarray(engine.block_mgr.tables),
                    jax.random.PRNGKey(0), *sampler)
            fn = engine._decode_fn(mode, 32, 4, False)
            prefill = engine._prefill_fn(mode).lower(
                engine.params, engine.cache_k, engine.cache_v, engine.state,
                jnp.zeros((1, 64), jnp.int32), jnp.full((1,), 50, jnp.int32),
                jnp.asarray(engine.block_mgr.tables[:1]),
                jax.random.PRNGKey(0), *(t[:1] for t in sampler))
            return (fn.lower(*args).as_text(debug_info=True), fn.__name__,
                    prefill.as_text(debug_info=True))
        finally:
            await engine.close()

    decode, name, prefill = run_async(main())
    assert "decode_chunk" in name
    for scope in ("eva_read", "eva_summarise", "eva_write", "kv_commit"):
        assert scope in decode, scope
    for scope in ("eva_flash", "eva_summarise_prefill", "eva_write",
                  "kv_write"):
        assert scope in prefill, scope
    assert "eva_summarise/" not in prefill.replace(
        "eva_summarise_prefill", "")
