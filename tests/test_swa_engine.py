"""The window-and-full family through ``engine.generate`` at the
``trinity-tiny`` preset on the CPU (float32: greedy streams are exactly
shape-independent): two pools at once, the window layers' a ring of blocks a
slot. Concurrent slots of unequal length (shorter than, at and several times
the window of 32) stream what each streams alone, under either read; a reused
slot leaks no row of the ring; a preempted request gives both kinds back and
resumes to the tokens of an undisturbed run; admission reserves a request's
worst case in both kinds; the chunk's expert loads and the pools' rows ride
the flight samples; the programs carry the new scopes; and the engine
refuses, for this model and by name, every option that assumes a request's
history is one table of K/V blocks."""

import asyncio
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models.swa import SwaConfig
from langstream_tpu.serving.engine import (
    ServingConfig,
    TpuServingEngine,
    _family_of,
    _resolve_model_config,
)

# the window is 32 rows, a block 8, a slot's ring 5 blocks (40 rows)
PROMPTS = [list(range(5, 5 + n)) for n in (9, 70, 32, 150, 20, 45)]


def config(**kw):
    base = dict(
        model="trinity-tiny", model_dtype="float32", slots=4, max_seq_len=256,
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
    than a turn of the ring."""
    async def main():
        engine = TpuServingEngine(config())
        try:
            return [(await engine.generate(p, greedy()))["tokens"]
                    for p in PROMPTS]
        finally:
            await engine.close()

    return run_async_module(main())


def test_the_engine_knows_the_new_names():
    swa = _family_of("trinity-tiny")
    assert (swa.name, swa.presets["trinity-tiny"]) == ("swa", "tiny")
    assert _family_of("trinity-large-preview-ep8") is swa and swa.presets[
        "trinity-large-preview-ep8"] == "trinity_large_preview_ep8"
    real = _resolve_model_config("trinity-large-preview-ep8", 16384)
    assert real == SwaConfig.trinity_large_preview_ep8()
    assert real.max_seq_len == 16384
    with pytest.raises(ValueError) as e:
        _resolve_model_config("no-such-model", 128)
    assert "trinity-large-preview-ep8" in str(e.value)


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
    assert streams == alone and family == "swa" and read == kernel
    assert all(len(set(s)) > 2 for s in streams)
    # everything came back, both kinds; no slot ever held more than a ring
    assert kv["live_blocks"] == kv["reserved_blocks"] == 0
    assert kv["window_ring_blocks"] == 5
    assert kv["window_num_blocks"] == 4 * 5 + 1
    assert kv["window_blocks_released"] == 6 * 5   # 9 + 48 rows: a ring too


def test_a_reused_slot_leaks_no_row_of_its_ring(run_async, alone):
    """One slot: every request runs in the ring the last one left."""
    async def main():
        engine = TpuServingEngine(config(slots=1, kv_pool_blocks=33))
        try:
            out = []
            for i in (3, 0, 1, 0):
                out.append((await engine.generate(PROMPTS[i], greedy()))["tokens"])
            return out
        finally:
            await engine.close()

    assert run_async(main()) == [alone[3], alone[0], alone[1], alone[0]]


def test_a_preempted_request_gives_both_kinds_back_and_resumes(run_async, alone):
    async def main():
        engine = TpuServingEngine(config(slots=2))
        try:
            seen = asyncio.Event()
            tokens = []

            def on_token(*chunk):
                tokens.append(chunk)
                if len(tokens) >= 3:
                    seen.set()

            task = asyncio.ensure_future(engine.generate(
                PROMPTS[1], greedy(), on_token=on_token))
            other = asyncio.ensure_future(engine.generate(PROMPTS[4], greedy()))
            await seen.wait()
            held = engine.block_mgr.stats()
            report = await engine.drain(grace_s=20)
            out = await task
            await other
            events = [e["kind"] for e in engine.flight.recent_events(64)]
            return out["tokens"], report, events, held, engine.block_mgr.stats()
        finally:
            await engine.close()

    stream, report, events, held, kv = run_async(main())
    assert stream == alone[1]
    assert report["requeued"] + report["completed"] >= 2 and report["shed"] == 0
    if report["requeued"]:
        assert "preempt" in events
    assert held["window_live_blocks"] > 0 and held["full_live_blocks"] > 0
    assert kv["live_blocks"] == 0 and kv["reserved_blocks"] == 0
    assert kv["window_live_blocks"] == 0 and kv["window_reserved_blocks"] == 0


def test_admission_reserves_the_worst_case_in_both_kinds(run_async):
    async def main():
        engine = TpuServingEngine(config(slots=2, kv_pool_blocks=2 * 32 + 1))
        try:
            m = engine.block_mgr
            before = m.stats()
            task = asyncio.ensure_future(
                engine.generate(PROMPTS[3], greedy(16)))
            while not m.stats()["reserved_blocks"]:
                await asyncio.sleep(0.01)
            during = m.stats()
            await task
            return before, during, m.stats()
        finally:
            await engine.close()

    before, during, after = run_async(main())
    assert before["num_blocks"] == 65 + 11 and before["reserved_blocks"] == 0
    # 150 + 16 + 1 rows: 21 blocks of the full kind, a whole ring of the other
    assert during["reserved_blocks"] == 21 + 5
    assert during["window_reserved_blocks"] == 5
    assert after["reserved_blocks"] == 0


def test_the_pools_rows_and_the_expert_loads_ride_the_flight_samples(run_async):
    async def main():
        engine = TpuServingEngine(config())
        try:
            await asyncio.gather(
                *(engine.generate(p, greedy(20)) for p in PROMPTS[:4]))
            return (engine.flight.recent(64), engine.model_config,
                    engine._state_bytes, engine._kv_cache_bytes)
        finally:
            await engine.close()

    samples, mc, window_bytes, full_bytes = run_async(main())
    decode = [s for s in samples if s["phase"] == "decode"]
    assert decode and all("window_rows" not in s for s in samples
                          if s["phase"] != "decode")
    for s in decode:
        # a window layer reads a slot's last 32 rows at most, a full one all
        assert 0 < s["window_rows"] <= s["live_rows"]
        assert s["window_rows"] <= s["active_at_dispatch"] * mc.window
        # 1 full + 4 window layers against 5 layers of every block
        assert s["pool_rows_held"] <= s["pool_rows_one_table"]
        assert s["window_slot_blocks_max"] <= 5
        # 2 winners of 8 experts, 4 held: at most 2 pairs a row a layer
        assert 0 <= s["routed_pairs"] <= s["steps"] * s["active_at_dispatch"] * 2 * 4
        assert s["state_bytes"] == 0
    longest = max(decode, key=lambda s: s["live_rows"])
    assert longest["pool_rows_held"] < longest["pool_rows_one_table"]
    assert any(s["routed_pairs"] > 0 for s in decode)
    # the window layers' pools ride where the hybrid family's state does:
    # 4 layers x (4 slots x 5 + 1) blocks x 8 rows x 32 values, K and V
    assert window_bytes == 2 * 4 * 21 * 8 * 32 * 4
    assert full_bytes == 2 * 1 * (4 * 256 // 8 // 2) * 8 * 32 * 4


REFUSED = {
    "prefix-cache": dict(prefix_cache=True),
    "prefix-store": dict(prefix_cache=True, prefix_store={"t1-bytes": 1 << 20}),
    "prefill-chunk": dict(prefill_chunk=32),
    "speculative-drafts": dict(speculative_drafts=2),
    "pool-role": dict(pool_role="prefill"),
    "adapter-store": dict(adapter_store={"t0-entries": 2, "rank": 4}),
    "quantize": dict(quantize="int8"),
    "kv-quantize": dict(kv_quantize="int8"),
    "kv-layout": dict(kv_layout="dense"),
    "mesh": dict(mesh=(("dp", 1),)),
    "journal-dir": dict(journal_dir="/nonexistent/journal"),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_every_option_that_assumes_one_table_of_kv_is_refused_by_name(option):
    from langstream_tpu.serving.adapters import AdapterStoreSpec
    from langstream_tpu.serving.prefixstore import PrefixStoreSpec

    kw = dict(REFUSED[option])
    if "prefix_store" in kw:
        kw["prefix_store"] = PrefixStoreSpec.from_dict(kw["prefix_store"])
    if "adapter_store" in kw:
        kw["adapter_store"] = AdapterStoreSpec.from_dict(kw["adapter_store"])
    if option == "prefix-store":
        option = "prefix-cache"     # the store needs it: refused first
    with pytest.raises(ValueError, match=re.escape(option)) as e:
        TpuServingEngine(config(**kw))
    if option not in ("kv-layout",):
        assert "trinity-tiny" in str(e.value)
    if option == "prefix-cache":
        assert "not reusable past the window" in str(e.value)


def lowered_programs(engine_config):
    """``(decode text, decode program's name, prefill text)`` of an engine's
    own greedy programs, with the scopes in the text."""
    async def main():
        engine = TpuServingEngine(engine_config)
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

    return main()


def test_the_lowered_programs_carry_the_two_kinds_scopes(run_async):
    decode_scopes = ("embed", "attn_qkv", "qk_norm", "rope", "swa_read",
                     "full_read", "attn_buf", "attn_gate", "attn_out",
                     "post_norm", "ffn", "moe_router", "moe_dispatch",
                     "moe_experts", "moe_shared", "moe_combine", "lm_head",
                     "sample", "kv_write", "swa_write")

    text, name, prefill = run_async(lowered_programs(
        config(paged_kernel="pallas-interpret")))
    assert "decode_chunk" in name          # what the trace readers look for
    for scope in decode_scopes:
        assert re.search(rf'[/"]{scope}/', text), scope
    assert "swa_read/paged_read" in text and "full_read/paged_read" in text
    for scope in ("swa_flash", "full_flash", "qk_norm", "rope", "attn_gate",
                  "post_norm", "ffn", "moe_experts", "swa_write", "kv_write"):
        assert re.search(rf'[/"]{scope}/', prefill), scope


# ---------------------------------------------------------------------------
# the family's second member (mellum-tiny: no gate, no post norm, no dense
# layer, no shared expert, all 8 experts held, YaRN on the full layers)
# ---------------------------------------------------------------------------

# under the window (32) to its end, past the ring's wrap (40 rows) while it
# decodes, far past both from the start, and two that wait for a freed slot
MELLUM_PROMPTS = [list(range(7, 7 + n)) for n in (5, 28, 120, 9, 60)]


def mellum_config(**kw):
    return config(**{"model": "mellum-tiny", "slots": 3, **kw})


@pytest.fixture(scope="module")
def mellum_alone(run_async_module):
    async def main():
        engine = TpuServingEngine(mellum_config())
        try:
            return [(await engine.generate(p, greedy(20)))["tokens"]
                    for p in MELLUM_PROMPTS]
        finally:
            await engine.close()

    return run_async_module(main())


def test_the_engine_knows_the_second_member_s_names():
    swa = _family_of("mellum-tiny")
    assert swa is _family_of("trinity-tiny") is _family_of(
        "mellum2-12b-a2.5b-8l")
    assert swa.presets["mellum2-12b-a2.5b-8l"] == "mellum2_12b_a2_5b_8l"
    real = _resolve_model_config("mellum2-12b-a2.5b-8l", 8768)
    assert real == SwaConfig.mellum2_12b_a2_5b_8l() and real.max_seq_len == 8768


@pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
def test_short_and_long_slots_and_a_freed_ring_in_one_batch(
        run_async, mellum_alone, kernel):
    """Three slots, five requests: a slot under the window beside one past
    its ring's wrap, and the fourth and fifth admitted into rings the first
    ones freed while the long one still runs."""
    async def main():
        engine = TpuServingEngine(mellum_config(paged_kernel=kernel))
        try:
            outs = await asyncio.gather(
                *(engine.generate(p, greedy(20)) for p in MELLUM_PROMPTS))
            return ([o["tokens"] for o in outs], engine.family,
                    engine.block_mgr.stats(), engine.flight.recent(128))
        finally:
            await engine.close()

    streams, family, kv, samples = run_async(main())
    assert streams == mellum_alone and family == "swa"
    assert all(len(set(s)) > 2 for s in streams)
    assert kv["live_blocks"] == kv["reserved_blocks"] == 0
    assert kv["window_ring_blocks"] == 5 and kv["window_num_blocks"] == 3 * 5 + 1
    decode = [s for s in samples if s["phase"] == "decode"]
    # slots shorter than the window ran beside longer ones
    assert any(0 < s["short_slots"] < s["active_at_dispatch"] for s in decode)
    for s in decode:
        assert 0 <= s["short_slots"] <= s["active_at_dispatch"]
        assert 0 < s["window_blocks_held"] <= 3 * 5
        assert s["window_slot_blocks_max"] <= 5
        # 2 winners of 8 experts, all held, 8 layers
        assert 0 < s["routed_pairs"] <= s["steps"] * s["active_at_dispatch"] * 2 * 8
    # the long slot's ring is full, a short slot's is not
    assert max(s["window_blocks_held"] for s in decode) > 5
    assert all("short_slots" not in s for s in samples if s["phase"] != "decode")


def test_the_first_member_s_samples_carry_the_two_gauges_too(run_async):
    async def main():
        engine = TpuServingEngine(config())
        try:
            await asyncio.gather(
                *(engine.generate(p, greedy(12)) for p in PROMPTS[:3]))
            return engine.flight.recent(32)
        finally:
            await engine.close()

    decode = [s for s in run_async(main()) if s["phase"] == "decode"]
    assert decode
    for s in decode:
        assert 0 <= s["short_slots"] <= s["active_at_dispatch"]
        assert 0 < s["window_blocks_held"] <= 4 * 5


def test_the_second_member_s_programs_name_its_own_scopes(run_async):
    text, _, prefill = run_async(lowered_programs(
        mellum_config(paged_kernel="pallas-interpret")))
    for scope in ("embed", "attn_qkv", "qk_norm", "rope", "rope_full",
                  "swa_read", "full_read", "attn_buf", "attn_out",
                  "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
                  "lm_head", "sample", "kv_write", "swa_write"):
        assert re.search(rf'[/"]{scope}/', text), scope
    for scope in ("swa_flash", "full_flash", "rope", "rope_full", "moe_experts"):
        assert re.search(rf'[/"]{scope}/', prefill), scope
    for scope in ("attn_gate", "post_norm", "moe_shared", "ffn"):
        assert not re.search(rf'[/"]{scope}/', text), scope
        assert not re.search(rf'[/"]{scope}/', prefill), scope


def test_the_second_member_refuses_the_same_options_by_name():
    with pytest.raises(ValueError) as e:
        TpuServingEngine(mellum_config(prefix_cache=True))
    assert "prefix-cache" in str(e.value) and "mellum-tiny" in str(e.value)
