"""The ``solar_open2`` member of the hybrid family through ``engine.generate``
at the ``solar-tiny`` preset on the CPU (float32: greedy streams are exactly
shape-independent): the delta-rule state beside the paged pool. Concurrent
slots of unequal length stream what each streams alone, a reused slot leaks
no state, a preempted request resumes to the tokens of an undisturbed run;
the state kernel follows the engine's one selection; the chunk's expert loads
and the state's bytes ride the flight samples; the programs carry the new
scopes; and the engine refuses, for this member and by name, every option
that assumes a request's history is its K/V blocks."""

import asyncio
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models.hybrid import HybridConfig
from langstream_tpu.serving.engine import (
    ServingConfig,
    TpuServingEngine,
    _family_of,
    _resolve_model_config,
)

PROMPTS = [list(range(5, 5 + n)) for n in (9, 70, 33, 51, 20, 45)]


def config(**kw):
    base = dict(
        model="solar-tiny", model_dtype="float32", slots=4, max_seq_len=256,
        kv_layout="paged", kv_block_size=16, prefix_cache=False,
        decode_chunk=8, decode_chunk_light=4,
    )
    return ServingConfig(**{**base, **kw})


def greedy(max_tokens=12):
    return {"max-tokens": max_tokens, "temperature": 0}


@pytest.fixture(scope="module")
def run_async_module():
    loop = asyncio.new_event_loop()
    yield loop.run_until_complete
    loop.close()


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


def test_the_engine_knows_the_new_names():
    hybrid = _family_of("solar-tiny")
    assert (hybrid.name, hybrid.presets["solar-tiny"]) == ("hybrid", "solar_tiny")
    assert _family_of("solar-open2-250b-ep8") is hybrid and \
        hybrid.presets["solar-open2-250b-ep8"] == "solar_open2_ep8"
    real = _resolve_model_config("solar-open2-250b-ep8", 2048)
    assert real == HybridConfig.solar_open2_ep8() and real.max_seq_len == 2048
    with pytest.raises(ValueError) as e:
        _resolve_model_config("no-such-model", 128)
    assert "solar-open2-250b-ep8" in str(e.value) and "solar-tiny" in str(e.value)


def test_concurrent_slots_of_unequal_length_stream_what_each_streams_alone(
        run_async, alone):
    async def main():
        engine = TpuServingEngine(config())
        try:
            outs = await asyncio.gather(
                *(engine.generate(p, greedy()) for p in PROMPTS))
            return [o["tokens"] for o in outs], engine.stats()
        finally:
            await engine.close()

    streams, stats = run_async(main())
    assert streams == alone
    assert all(len(set(s)) > 2 for s in streams)
    assert stats["ssm_state_kernel"] == "xla"


@pytest.mark.parametrize("selected, handed", [
    ("xla", "xla"), ("pallas-interpret", "pallas-interpret")])
def test_the_state_s_pass_follows_the_engine_s_one_kernel_selection(
        run_async, alone, selected, handed):
    async def main():
        engine = TpuServingEngine(config(paged_kernel=selected))
        try:
            out = await asyncio.gather(
                *(engine.generate(p, greedy()) for p in PROMPTS[:3]))
            return [o["tokens"] for o in out], engine.ssm_state_kernel, \
                engine.paged_read_kernel
        finally:
            await engine.close()

    streams, state_kernel, read_kernel = run_async(main())
    assert state_kernel == read_kernel == handed
    assert streams == alone[:3]


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

    assert run_async(main()) == [alone[1], alone[0], alone[3], alone[0]]


def test_a_preempted_request_resumes_to_the_tokens_of_an_undisturbed_run(
        run_async, alone):
    """Preemption drops the delta-rule state with the blocks; the request
    prefills again over its prompt and what it had made."""
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


def test_the_chunk_s_expert_loads_and_state_bytes_ride_its_packed_fetch(run_async):
    async def main():
        engine = TpuServingEngine(config())
        try:
            await asyncio.gather(
                *(engine.generate(p, greedy(9)) for p in PROMPTS[:4]))
            return (engine.flight.recent(64), engine.model_config,
                    engine._state_bytes)
        finally:
            await engine.close()

    samples, mc, held = run_async(main())
    decode = [s for s in samples if s["phase"] == "decode"]
    assert decode
    # 3 delta-rule layers of 4 heads of (16, 16) float32 and their tails
    assert mc.state_bytes_per_slot == 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    for s in decode:
        assert s["state_bytes"] == s["active_at_dispatch"] * mc.state_bytes_per_slot
        # 3 winners of 8 experts, 4 held: a row sends at most 3 pairs here
        # in each of the 4 expert layers
        assert 0 <= s["routed_pairs"] <= s["steps"] * s["active_at_dispatch"] * 3 * 4
        assert s["expert_load_max"] <= s["steps"] * s["active_at_dispatch"]
    assert any(s["routed_pairs"] > 0 for s in decode)
    assert held == 4 * mc.state_bytes_per_slot       # the engine's four slots


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
        option = "prefix-cache"     # the store needs it: refused first
    with pytest.raises(ValueError, match=re.escape(option)):
        TpuServingEngine(config(**kw))


def test_the_lowered_programs_carry_the_delta_rule_s_scopes(run_async):
    decode_scopes = ("embed", "delta_in", "delta_conv", "delta_state",
                     "delta_out", "attn_qkv", "kv_read", "attn_gate",
                     "attn_out", "moe_router", "moe_dispatch", "moe_experts",
                     "moe_shared", "moe_combine", "lm_head", "sample")

    async def main():
        engine = TpuServingEngine(config(paged_kernel="pallas-interpret"))
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
            fn = engine._decode_fn(mode, 2, 4, False)
            sel = (jnp.asarray(engine.block_mgr.tables[:2]),
                   jnp.arange(2, dtype=jnp.int32))
            prefill = engine._prefill_fn(mode).lower(
                engine.params, engine.cache_k, engine.cache_v, engine.state,
                jnp.zeros((2, 32), jnp.int32), jnp.full((2,), 20, jnp.int32),
                sel, jax.random.PRNGKey(0), *(t[:2] for t in sampler))
            return (fn.lower(*args).as_text(debug_info=True), fn.__name__,
                    prefill.as_text(debug_info=True))
        finally:
            await engine.close()

    text, name, prefill = run_async(main())
    assert "decode_chunk" in name          # what the trace readers look for
    for scope in decode_scopes:
        assert re.search(rf'[/"]{scope}/', text), scope
    assert "kv_read/paged_read" in text
    assert "delta_state/delta_state_step" in text
    # no Mamba-2 layer: no ``ssm_*`` scope is traced (the scopes, not the
    # text: its table of source locations names whatever file first traced
    # a function this process has cached, ops/ssm_state.py among them when
    # tests/test_ssm_state.py ran in the same worker before)
    assert not re.search(r'[/"]ssm_\w+/', text)
    for scope in ("delta_in", "delta_conv", "delta_chunk", "delta_out",
                  "delta_state_write", "attn_gate", "moe_experts"):
        assert re.search(rf'[/"]{scope}/', prefill), scope
    # the Pallas selection: the chunked rule is the kernel, under its scope
    # (the XLA form's UT transform: tests/test_delta_chunk_engine.py)
    assert "delta_chunk/delta_chunk_rule" in prefill
    assert "triangular_solve" not in prefill
