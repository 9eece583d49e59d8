"""The latent-attention family through ``engine.generate`` at the tiny preset
on the CPU (float32: greedy streams are exactly shape-independent): one pool
of latent rows where the other families keep K and V. Concurrent slots of
unequal length stream what each streams alone, a preempted request resumes
to the same stream, a pool too small refuses what can never fit, the chunk's
expert loads and the rows its read covers ride into the flight samples, the
programs carry the family's scopes, and every option that assumes a
request's history is K and V rows is refused by name."""

import asyncio
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models.family import families
from langstream_tpu.serving.engine import (
    ServingConfig,
    TpuServingEngine,
    _family_of,
    _resolve_model_config,
)

PROMPTS = [list(range(5, 5 + n)) for n in (9, 70, 33, 51, 20, 45)]


def config(**kw):
    base = dict(
        model="deepseek-tiny", model_dtype="float32", slots=4, max_seq_len=256,
        kv_layout="paged", kv_block_size=16, prefix_cache=False,
        prefill_batch=1, decode_chunk=8, decode_chunk_light=4,
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


@pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
def test_concurrent_slots_of_unequal_length_stream_what_each_streams_alone(
        run_async, alone, kernel):
    async def main():
        engine = TpuServingEngine(config(paged_kernel=kernel, prefill_batch=2))
        try:
            # six requests on four slots: two wait, then take reused slots
            outs = await asyncio.gather(
                *(engine.generate(p, greedy()) for p in PROMPTS))
            return ([o["tokens"] for o in outs], engine.stats(),
                    engine.paged_read_kernel, engine.cache_v)
        finally:
            await engine.close()

    streams, stats, read_kernel, cache_v = run_async(main())
    assert streams == alone
    assert all(len(s) == 12 for s in streams)
    assert read_kernel == kernel and cache_v is None
    assert stats["kv"]["layout"] == "paged" and stats["ssm_state_kernel"] is None


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
            return out["tokens"], report, events
        finally:
            await engine.close()

    async def undisturbed():
        engine = TpuServingEngine(config(slots=2))
        try:
            return (await engine.generate(PROMPTS[2], greedy(24)))["tokens"]
        finally:
            await engine.close()

    stream, report, events = run_async(main())
    assert stream == run_async(undisturbed())
    assert stream[:12] == alone[2]
    assert report["requeued"] + report["completed"] >= 2 and report["shed"] == 0
    if report["requeued"]:
        assert "preempt" in events


def test_a_pool_too_small_queues_and_a_request_that_never_fits_is_refused(
        run_async, alone):
    async def main():
        # 9 blocks of 16 rows beside the scratch block: one 70-token prompt
        # and its answer take 6, so the requests run one or two at a time
        engine = TpuServingEngine(config(kv_pool_blocks=10))
        try:
            outs = await asyncio.gather(
                *(engine.generate(p, greedy()) for p in PROMPTS[:4]))
            with pytest.raises(Exception, match="(?i)pool|fit|blocks"):
                await engine.generate(list(range(3, 203)), greedy(40))
            return [o["tokens"] for o in outs], engine.block_mgr.stats()
        finally:
            await engine.close()

    streams, kv = run_async(main())
    assert streams == alone[:4]
    assert kv["live_blocks"] == 0


def test_the_chunk_s_loads_and_live_rows_ride_into_the_flight_samples(run_async):
    async def main():
        engine = TpuServingEngine(config())
        try:
            await asyncio.gather(
                *(engine.generate(p, greedy(9)) for p in PROMPTS[:4]))
            return (engine.flight.recent(64), engine.stats()["decode-chunks"],
                    engine.model_config)
        finally:
            await engine.close()

    samples, chunks, mc = run_async(main())
    decode = [s for s in samples if s["phase"] == "decode"]
    prefill = [s for s in samples if s["phase"] == "prefill"]
    assert decode and 0 < chunks["host_fetches_per_chunk"] <= 1.0
    for s in decode:
        # top 2 of the best group of 4, 4 held: at most 2 pairs a row a layer
        assert 0 <= s["routed_pairs"] <= \
            s["steps"] * s["active_at_dispatch"] * 2 * mc.sparse_layers
        assert s["expert_load_max"] <= s["steps"] * s["active_at_dispatch"]
        assert s["state_bytes"] == 0
        # the rows the read covers: each running slot's prompt (BOS and
        # the shortest prompt at least) and no more than its whole slot
        # (the blocks that hold them went with their last reader: PR 36)
        assert s["active_at_dispatch"] * (1 + min(map(len, PROMPTS))) \
            <= s["live_rows"] <= 4 * 256          # four slots of 256 rows
        assert "live_blocks" not in s
    assert any(s["routed_pairs"] > 0 for s in decode)
    # one prompt a dispatch: BOS and the prompt, no padding counted
    assert sorted(s["prompt_tokens"] for s in prefill) == \
        sorted(len(p) + 1 for p in PROMPTS[:4])
    assert all("routed_pairs" not in s and "live_rows" not in s for s in prefill)


REFUSED = {
    "prefix-cache": dict(prefix_cache=True),
    "prefix-store": dict(prefix_store={"t1-bytes": 1 << 20}),
    "prefill-chunk": dict(prefill_chunk=32),
    "speculative-drafts": dict(speculative_drafts=2),
    "pool-role": dict(pool_role="prefill"),
    "adapter-store": dict(adapter_store={"t0-entries": 2, "rank": 4}),
    "quantize": dict(quantize="int8"),
    "kv-quantize": dict(kv_quantize="int8"),
    "mesh": dict(mesh=(("dp", 1),)),
    "journal-dir": dict(journal_dir="/nonexistent/journal"),
    "checkpoint": dict(checkpoint="/nonexistent/checkpoint"),
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
    with pytest.raises(ValueError) as e:
        TpuServingEngine(config(**kw))
    assert re.search(rf"cannot serve with {re.escape(option)}:", str(e.value))
    assert "one pool of latent rows" in str(e.value)


def test_one_table_resolves_both_families_names():
    latent = _family_of("deepseek-tiny")
    assert (latent.name, latent.presets["deepseek-tiny"]) == ("latent", "tiny")
    assert _family_of("deepseek-v2-ep8") is latent and \
        latent.presets["deepseek-v2-ep8"] == "deepseek_v2_ep8"
    assert {f.name for f in families()} == {"hybrid", "latent", "swa", "eva"}
    assert _family_of("tiny") is None and _family_of("moe-tiny") is None
    with pytest.raises(ValueError) as e:
        _resolve_model_config("no-such-model", 128)
    assert "deepseek-v2-ep8" in str(e.value) and "hybrid-tiny" in str(e.value)
    mc = _resolve_model_config("deepseek-v2-ep8", 16384)
    # what bench/run.py compares with the configuration file's `widths`
    assert (mc.hidden, mc.layers, mc.heads, mc.kv_heads, mc.head_dim,
            mc.intermediate, mc.vocab_size, mc.rope_theta, mc.norm_eps) == \
        (5120, 5, 128, 128, 192, 12288, 12800, 10000.0, 1e-6)
    assert (mc.q_rank, mc.kv_rank, mc.nope_dim, mc.rope_dim, mc.v_dim) == \
        (1536, 512, 128, 64, 128)
    assert (mc.experts, mc.experts_held, mc.experts_per_token, mc.n_group,
            mc.topk_group, mc.routed_scale, mc.dense_layers) == \
        (160, 20, 6, 8, 3, 16.0, 1)
    assert mc.max_seq_len == 16384 and mc.state_bytes_per_slot == 0


def test_the_held_share_is_the_issue_s_arithmetic():
    from langstream_tpu.models.latent import init_latent_params

    shapes = jax.eval_shape(
        lambda: init_latent_params(_resolve_model_config("deepseek-v2-ep8", 64)))
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(held - 3145.5e6) < 1e6         # 6.29 GB in bfloat16


def _lowered(engine, which):
    slots = engine.config.slots
    mode = engine._sampler_mode(np.zeros(1, np.float32), np.zeros(1, np.int32),
                                np.ones(1, np.float32))
    caches = (engine.params, engine.cache_k, engine.cache_v)
    if which == "decode":
        fn = engine._decode_fn(mode, engine._read_blocks_for(1), 4, False)
        args = caches + (
            jnp.zeros(slots, jnp.int32), jnp.ones(slots, jnp.int32),
            jnp.ones(slots, bool), jnp.asarray(engine.block_mgr.tables),
            jax.random.PRNGKey(0), jnp.zeros(slots, jnp.float32),
            jnp.zeros(slots, jnp.int32), jnp.ones(slots, jnp.float32))
    else:
        fn = engine._prefill_fn(mode)
        args = caches + (
            jnp.zeros((1, 64), jnp.int32), jnp.full((1,), 40, jnp.int32),
            jnp.asarray(engine.block_mgr.tables[:1]), jax.random.PRNGKey(0),
            jnp.zeros(1, jnp.float32), jnp.zeros(1, jnp.int32),
            jnp.ones(1, jnp.float32))
    return fn.lower(*args).as_text(debug_info=True), fn.__name__


@pytest.mark.parametrize("which,scopes,kernel_op", [
    ("decode", ("embed", "mla_q", "mla_kv", "mla_absorb", "kv_read",
                "attn_out", "ffn", "moe_router", "moe_dispatch", "moe_experts",
                "moe_shared", "moe_combine", "lm_head", "sample"),
     "kv_read/latent_read"),
    ("prefill", ("embed", "mla_q", "mla_kv", "mla_expand", "kv_read",
                 "attn_out", "ffn", "moe_router", "moe_dispatch",
                 "moe_experts", "moe_shared", "moe_combine", "lm_head",
                 "sample"),
     "kv_read/flash_prefill"),
])
def test_the_lowered_programs_carry_the_latent_scopes(
        run_async, monkeypatch, which, scopes, kernel_op):
    monkeypatch.setenv("LS_TPU_FLASH", "interpret")

    async def main():
        engine = TpuServingEngine(config(paged_kernel="pallas-interpret"))
        try:
            # one decode program a chunk size: the whole slot is the window
            assert engine._read_blocks_for(1) == engine._read_blocks_for(200) \
                == engine.paged_layout.max_blocks_per_slot
            return _lowered(engine, which)
        finally:
            await engine.close()

    text, name = run_async(main())
    assert {"decode": "decode_chunk", "prefill": "prefill"}[which] in name
    for scope in scopes:
        assert re.search(rf'[/"]{scope}/', text), scope
    assert kernel_op in text
    # the accepted paged_read_roofline reader takes ops of these names
    assert not re.search(r"(?i)closed_call|custom-call|custom_call|paged",
                         "latent_read")


def _prefills_between_chunks(samples):
    """The longest run of prefill samples between two decode samples, after
    the first decode sample (before it nothing decodes and nothing waits)."""
    phases = [s["phase"] for s in samples if s["phase"] in ("prefill", "decode")]
    phases = phases[phases.index("decode"):]
    runs = "".join("p" if p == "prefill" else " " for p in phases).split()
    return max(map(len, runs), default=0)


@pytest.mark.parametrize("budget_s,most", [
    (2.0, 3),      # the serving budget: tiny prefills never reach it
    (1e-9, 2),     # spent by the first batch completed: that one and the
])                 # batch dispatched behind it are a round's prefills
def test_the_prefill_between_two_decode_chunks_is_bounded(
        run_async, alone, monkeypatch, budget_s, most):
    """One slot decodes a long answer while three short ones end together,
    over and over: every wave frees three slots with prompts waiting. Under
    the budget a wave's prefills are spread over rounds of one chunk each;
    every stream is what it is alone either way."""
    monkeypatch.setattr(TpuServingEngine, "_PREFILL_ROUND_S", budget_s)
    lengths = [40] + [4] * 11
    prompts = [PROMPTS[i % len(PROMPTS)] for i in range(len(lengths))]

    async def main():
        engine = TpuServingEngine(config())
        try:
            outs = await asyncio.gather(*(
                engine.generate(p, greedy(n)) for p, n in zip(prompts, lengths)))
            return [o["tokens"] for o in outs], engine.flight.recent(0)
        finally:
            await engine.close()

    streams, samples = run_async(main())
    for i, (stream, n) in enumerate(zip(streams, lengths)):
        assert stream[:12] == alone[i % len(PROMPTS)][:n] and len(stream) == n
    assert sum(s["phase"] == "prefill" for s in samples) == len(lengths)
    longest = _prefills_between_chunks(samples)
    assert longest == most, longest


def test_a_round_of_requests_that_end_at_their_first_token_is_over_without_a_burst(
        run_async, alone, monkeypatch):
    """Nothing decodes after such a round, so nothing would start the next
    one: admission has to, or the queue waits for ever on a spent budget."""
    monkeypatch.setattr(TpuServingEngine, "_PREFILL_ROUND_S", 1e-9)

    async def main():
        engine = TpuServingEngine(config())
        try:
            outs = await asyncio.wait_for(asyncio.gather(*(
                engine.generate(p, greedy(1)) for p in PROMPTS + PROMPTS)), 120)
            return [o["tokens"] for o in outs]
        finally:
            await engine.close()

    streams = run_async(main())
    assert streams == [a[:1] for a in alone + alone]
