"""The hybrid family through ``engine.generate`` at the tiny preset on the
CPU (float32: greedy streams are exactly shape-independent): two kinds of
per-request state side by side. Concurrent slots of unequal length stream
what each streams alone, a reused slot leaks no state, a preempted request
resumes to the same stream; the chunk's expert loads ride the packed fetch
into the flight samples; and every option that assumes a request's history
is its K/V blocks is refused by name."""

import asyncio
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.serving.engine import (
    ServingConfig,
    TpuServingEngine,
    _resolve_model_config,
)

PROMPTS = [list(range(5, 5 + n)) for n in (9, 70, 33, 51, 20, 45)]


def config(**kw):
    base = dict(
        model="hybrid-tiny", model_dtype="float32", slots=4, max_seq_len=256,
        kv_layout="paged", kv_block_size=16, prefix_cache=False,
        decode_chunk=8, decode_chunk_light=4,
    )
    return ServingConfig(**{**base, **kw})


def greedy(max_tokens=12):
    return {"max-tokens": max_tokens, "temperature": 0}


@pytest.fixture(scope="module")
def alone(run_async_module):
    """Each prompt's stream when it is the only request."""
    async def main():
        engine = TpuServingEngine(config())
        try:
            return [(await engine.generate(p, greedy()))["tokens"]
                    for p in PROMPTS]
        finally:
            await engine.close()

    return run_async_module(main())


@pytest.fixture(scope="module")
def run_async_module():
    loop = asyncio.new_event_loop()
    yield loop.run_until_complete
    loop.close()


def test_concurrent_slots_of_unequal_length_stream_what_each_streams_alone(
        run_async, alone):
    async def main():
        engine = TpuServingEngine(config())
        try:
            # six requests on four slots: two wait, then take reused slots
            outs = await asyncio.gather(
                *(engine.generate(p, greedy()) for p in PROMPTS))
            return [o["tokens"] for o in outs], engine.stats()
        finally:
            await engine.close()

    streams, stats = run_async(main())
    assert streams == alone
    assert all(len(s) == 12 for s in streams)
    # the accounting: state bytes beside pool bytes
    per_slot = _resolve_model_config("hybrid-tiny", 256)
    import dataclasses

    per_slot = dataclasses.replace(
        per_slot, dtype=jnp.float32).state_bytes_per_slot
    assert stats["kv"]["state_bytes"] == 4 * per_slot
    owners = stats["attribution"]["memory"]["hbm_bytes_by_owner"]
    assert owners["recurrent-state"] == 4 * per_slot


@pytest.mark.parametrize("selected, handed", [
    ("auto", "xla"), ("pallas-interpret", "pallas-interpret")])
def test_the_state_s_pass_follows_the_engine_s_one_kernel_selection(
        run_async, alone, selected, handed):
    """``stats()`` reports what ``mamba_step`` was handed, and the streams
    through the interpreted kernels are the XLA ones."""
    async def main():
        engine = TpuServingEngine(config(paged_kernel=selected))
        try:
            outs = await asyncio.gather(
                *(engine.generate(PROMPTS[i], greedy()) for i in (1, 3, 0)))
            return ([o["tokens"] for o in outs], engine.stats(),
                    engine.paged_read_kernel)
        finally:
            await engine.close()

    streams, stats, read = run_async(main())
    assert stats["ssm_state_kernel"] == read == handed
    assert streams == [alone[1], alone[3], alone[0]]


def test_a_family_without_the_state_reports_no_kernel_for_it(run_async):
    async def main():
        engine = TpuServingEngine(ServingConfig(
            model="tiny", model_dtype="float32", slots=2, max_seq_len=64,
            prefix_cache=False))
        try:
            return engine.stats()["ssm_state_kernel"], engine.paged_read_kernel
        finally:
            await engine.close()

    assert run_async(main()) == (None, "xla")


def test_a_reused_slot_leaks_no_state(run_async, alone):
    """One slot: every request runs in the rows the last one left."""
    async def main():
        engine = TpuServingEngine(config(slots=1))
        try:
            out = []
            for p in (PROMPTS[1], PROMPTS[0], PROMPTS[3], PROMPTS[0]):
                out.append((await engine.generate(p, greedy()))["tokens"])
            return out
        finally:
            await engine.close()

    streams = run_async(main())
    assert streams == [alone[1], alone[0], alone[3], alone[0]]


def test_a_preempted_request_resumes_to_the_same_stream(run_async, alone):
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
                PROMPTS[2], greedy(24), on_token=on_token))
            other = asyncio.ensure_future(engine.generate(PROMPTS[4], greedy(24)))
            await seen.wait()
            report = await engine.drain(grace_s=20)
            out = await task
            await other
            events = [e["kind"] for e in engine.flight.recent_events(64)]
            return out["tokens"], report, events, engine.stats()["kv"]
        finally:
            await engine.close()

    async def undisturbed():
        engine = TpuServingEngine(config(slots=2))
        try:
            return (await engine.generate(PROMPTS[2], greedy(24)))["tokens"]
        finally:
            await engine.close()

    stream, report, events, kv = run_async(main())
    assert stream == run_async(undisturbed())
    assert stream[:12] == alone[2]
    assert report["requeued"] + report["completed"] >= 2 and report["shed"] == 0
    if report["requeued"]:
        assert "preempt" in events
    assert kv["state_live_bytes"] == 0      # nothing runs: nothing is live


def test_the_chunk_s_expert_loads_ride_its_packed_fetch(run_async):
    async def main():
        engine = TpuServingEngine(config())
        try:
            await asyncio.gather(
                *(engine.generate(p, greedy(9)) for p in PROMPTS[:4]))
            return (engine.flight.recent(64),
                    engine.stats()["decode-chunks"],
                    engine.model_config)
        finally:
            await engine.close()

    samples, chunks, mc = run_async(main())
    decode = [s for s in samples if s["phase"] == "decode"]
    # one fetch a chunk at most (a chunk still in flight at close has none)
    assert decode and 0 < chunks["host_fetches_per_chunk"] <= 1.0
    for s in decode:
        assert s["state_bytes"] == s["active_at_dispatch"] * mc.state_bytes_per_slot
        # 3 winners of 8 experts, 2 held: a row sends at most 2 pairs here
        # in each of the 3 expert layers
        assert 0 <= s["routed_pairs"] <= s["steps"] * s["active_at_dispatch"] * 2 * 3
        assert s["expert_load_max"] <= s["steps"] * s["active_at_dispatch"]
        assert s["expert_load_max"] * 6 >= s["routed_pairs"]
    assert any(s["routed_pairs"] > 0 for s in decode)
    assert all("routed_pairs" not in s for s in samples if s["phase"] == "prefill")


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
def test_every_option_that_assumes_history_is_kv_is_refused_by_name(option):
    from langstream_tpu.serving.adapters import AdapterStoreSpec
    from langstream_tpu.serving.prefixstore import PrefixStoreSpec

    kw = dict(REFUSED[option])
    if "prefix_store" in kw:
        kw["prefix_store"] = PrefixStoreSpec.from_dict(kw["prefix_store"])
    if "adapter_store" in kw:
        kw["adapter_store"] = AdapterStoreSpec.from_dict(kw["adapter_store"])
    if option == "prefix-store":
        # the family's first refusal on this posture names prefix-cache,
        # which the store needs: both are refused, each by its own name
        option = "prefix-cache"
    with pytest.raises(ValueError, match=re.escape(option)):
        TpuServingEngine(config(**kw))


def test_the_prefix_store_alone_is_refused_by_its_name(monkeypatch):
    from langstream_tpu.serving.prefixstore import PrefixStoreSpec

    spec = PrefixStoreSpec.from_dict({"t1-bytes": 1 << 20})
    engine_config = config(prefix_store=spec)
    assert spec.enabled
    with pytest.raises(ValueError, match="prefix-store"):
        TpuServingEngine(engine_config)


def test_an_unknown_model_s_error_lists_the_hybrid_names():
    with pytest.raises(ValueError) as e:
        _resolve_model_config("no-such-model", 128)
    assert "nemotron-3-nano-30b-a3b-ep8" in str(e.value)
    assert "hybrid-tiny" in str(e.value)
    mc = _resolve_model_config("nemotron-3-nano-30b-a3b-ep8", 2048)
    assert (mc.hidden, mc.layers, mc.heads, mc.kv_heads, mc.head_dim,
            mc.intermediate, mc.vocab_size) == (2688, 52, 32, 2, 128, 1856, 16384)
    assert (len(mc.blocks), mc.attn_layers, mc.experts, mc.experts_held) == \
        (23, 6, 128, 16)
    assert mc.state_bytes_per_slot == 23 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)


def test_the_lowered_programs_carry_the_hybrid_scopes(run_async):
    scopes = ("embed", "ssm_in", "ssm_conv", "ssm_scan", "ssm_out", "attn_qkv",
              "kv_read", "attn_out", "moe_router", "moe_dispatch",
              "moe_experts", "moe_shared", "moe_combine", "lm_head", "sample")

    async def main():
        engine = TpuServingEngine(config(paged_kernel="pallas-interpret"))
        try:
            slots = engine.config.slots
            mode = engine._sampler_mode(np.zeros(1, np.float32),
                                        np.zeros(1, np.int32),
                                        np.ones(1, np.float32))
            args = (engine.params, engine.cache_k, engine.cache_v, engine.state,
                    jnp.zeros(slots, jnp.int32), jnp.ones(slots, jnp.int32),
                    jnp.ones(slots, bool), jnp.asarray(engine.block_mgr.tables),
                    jax.random.PRNGKey(0), jnp.zeros(slots, jnp.float32),
                    jnp.zeros(slots, jnp.int32), jnp.ones(slots, jnp.float32))
            fn = engine._decode_fn(mode, 2, 4, False)
            return fn.lower(*args).as_text(debug_info=True), fn.__name__
        finally:
            await engine.close()

    text, name = run_async(main())
    assert "decode_chunk" in name          # what the trace readers look for
    for scope in scopes:
        assert re.search(rf'[/"]{scope}/', text), scope
    assert "kv_read/paged_read" in text
    assert "ssm_scan/ssm_state_step" in text
