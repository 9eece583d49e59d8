"""Serving engine + model tests on the 8-virtual-device CPU mesh."""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(autouse=True)
def _fresh_engines():
    from langstream_tpu.serving.engine import EmbeddingEngine, TpuServingEngine

    TpuServingEngine.reset_instances()
    EmbeddingEngine.reset_instances()
    yield
    TpuServingEngine.reset_instances()
    EmbeddingEngine.reset_instances()


# ---------------------------------------------------------------------------
# model-level invariants
# ---------------------------------------------------------------------------


def test_prefill_decode_equivalence():
    """Decoding token-by-token must match a fresh prefill over the same
    prefix (KV cache correctness)."""
    from langstream_tpu.models.llama import (
        LlamaConfig,
        init_kv_cache,
        init_llama_params,
        llama_decode_step,
        llama_prefill,
    )

    c = LlamaConfig.tiny(max_seq_len=32)
    params = init_llama_params(c, jax.random.PRNGKey(1))
    tokens = jnp.array([[5, 9, 17, 3, 11, 2, 7, 1]], dtype=jnp.int32)
    n = tokens.shape[1]

    # full prefill over n tokens
    ck, cv = init_kv_cache(c, slots=1, max_seq_len=32)
    logits_full, _, _ = llama_prefill(
        c, params, tokens, jnp.array([n]), ck, cv, jnp.array([0])
    )

    # prefill over n-1 then decode the last token
    ck, cv = init_kv_cache(c, slots=1, max_seq_len=32)
    _, ck, cv = llama_prefill(
        c, params, tokens[:, : n - 1], jnp.array([n - 1]), ck, cv, jnp.array([0])
    )
    logits_step, _, _ = llama_decode_step(
        c, params, tokens[:, n - 1], jnp.array([n - 1]), ck, cv
    )
    np.testing.assert_allclose(
        np.asarray(logits_full), np.asarray(logits_step), rtol=2e-2, atol=2e-2
    )


def test_prefill_padding_invariance():
    """Padding a prompt to a larger bucket must not change its logits."""
    from langstream_tpu.models.llama import (
        LlamaConfig,
        init_kv_cache,
        init_llama_params,
        llama_prefill,
    )

    c = LlamaConfig.tiny(max_seq_len=64)
    params = init_llama_params(c, jax.random.PRNGKey(2))
    prompt = [5, 9, 17, 3]

    def run(pad_to):
        t = np.zeros((1, pad_to), dtype=np.int32)
        t[0, : len(prompt)] = prompt
        ck, cv = init_kv_cache(c, slots=1, max_seq_len=64)
        logits, _, _ = llama_prefill(
            c, params, jnp.asarray(t), jnp.array([len(prompt)]), ck, cv, jnp.array([0])
        )
        return np.asarray(logits)

    np.testing.assert_allclose(run(8), run(32), rtol=2e-2, atol=2e-2)


def test_tp_sharded_decode_matches_single_device():
    """The TP-sharded model must produce the same logits as unsharded."""
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as P

    from langstream_tpu.models.llama import (
        LlamaConfig,
        init_kv_cache,
        init_llama_params,
        llama_decode_step,
        llama_param_specs,
        kv_cache_spec,
        llama_prefill,
    )
    from langstream_tpu.parallel.mesh import make_mesh

    # f32: the sharded/unsharded comparison is about layout, not rounding —
    # bf16 leaves it hostage to backend-dependent fusion differences
    c = dataclasses.replace(
        LlamaConfig.tiny(max_seq_len=32), dtype=jnp.float32
    )
    params = init_llama_params(c, jax.random.PRNGKey(3))
    tokens = jnp.array([[5, 9, 17, 3]], dtype=jnp.int32)

    ck, cv = init_kv_cache(c, slots=1, max_seq_len=32)
    ref_logits, ck1, cv1 = llama_prefill(
        c, params, tokens, jnp.array([4]), ck, cv, jnp.array([0])
    )

    mesh = make_mesh({"dp": 1, "tp": 2})
    specs = llama_param_specs(c)
    sharded = jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, specs, is_leaf=lambda x: isinstance(x, P),
    )
    cspec = NamedSharding(mesh, kv_cache_spec(mesh.axis_names))
    ck, cv = init_kv_cache(c, slots=1, max_seq_len=32)
    ck, cv = jax.device_put(ck, cspec), jax.device_put(cv, cspec)
    tp_logits, ck2, cv2 = llama_prefill(
        c, sharded, tokens, jnp.array([4]), ck, cv, jnp.array([0])
    )
    np.testing.assert_allclose(
        np.asarray(ref_logits), np.asarray(tp_logits), rtol=2e-2, atol=2e-2
    )

    # one decode step too
    ref_d, _, _ = llama_decode_step(
        c, params, jnp.array([7]), jnp.array([4]), ck1, cv1
    )
    tp_d, _, _ = llama_decode_step(
        c, sharded, jnp.array([7]), jnp.array([4]), ck2, cv2
    )
    np.testing.assert_allclose(
        np.asarray(ref_d), np.asarray(tp_d), rtol=2e-2, atol=2e-2
    )


def test_sp_ring_prefill_matches_dense():
    """Sequence-parallel (ring-attention) serving prefill over an sp×tp mesh
    matches the single-device dense prefill — logits AND the cache rows it
    fills (the long-context serving path: prefill FLOPs/activations split
    over sp while the cache keeps the engine's dp/tp layout)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from langstream_tpu.models.llama import (
        LlamaConfig,
        init_kv_cache,
        init_llama_params,
        llama_param_specs,
        llama_prefill,
        kv_cache_spec,
    )
    from langstream_tpu.parallel.mesh import make_mesh

    c = LlamaConfig.tiny(max_seq_len=64)
    params = init_llama_params(c, jax.random.PRNGKey(1))
    tokens = jnp.array([[5, 9, 17, 3, 11, 2, 7, 1] * 4], dtype=jnp.int32)  # P=32
    lengths = jnp.array([29])  # right-padded tail

    ck, cv = init_kv_cache(c, slots=1, max_seq_len=64)
    ref_logits, ref_ck, _ = llama_prefill(
        c, params, tokens, lengths, ck, cv, jnp.array([0]), use_flash=False
    )

    mesh = make_mesh({"dp": 1, "sp": 4, "tp": 2})
    sparams = jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, llama_param_specs(c), is_leaf=lambda x: isinstance(x, P),
    )
    ck2, cv2 = init_kv_cache(c, slots=1, max_seq_len=64)
    cspec = NamedSharding(mesh, kv_cache_spec(mesh.axis_names))
    ck2, cv2 = jax.device_put(ck2, cspec), jax.device_put(cv2, cspec)
    sp_logits, sp_ck, _ = llama_prefill(
        c, sparams, tokens, lengths, ck2, cv2, jnp.array([0]),
        use_flash=False, mesh=mesh,
    )
    # ring online-softmax reorders bf16 accumulation vs one dense softmax
    np.testing.assert_allclose(
        np.asarray(ref_logits), np.asarray(sp_logits), rtol=5e-2, atol=5e-2
    )
    np.testing.assert_allclose(
        np.asarray(ref_ck[:, :, :29]).astype(np.float32),
        np.asarray(sp_ck[:, :, :29]).astype(np.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_sp_ring_prefill_degrades_on_indivisible_batch():
    """B=1 prefill on a dp>1 mesh (one queued request) must replicate over
    dp instead of crashing — same graceful per-axis degradation as flash."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from langstream_tpu.models.llama import (
        LlamaConfig,
        init_kv_cache,
        init_llama_params,
        llama_param_specs,
        llama_prefill,
        kv_cache_spec,
    )
    from langstream_tpu.parallel.mesh import make_mesh

    c = LlamaConfig.tiny(max_seq_len=64)
    params = init_llama_params(c, jax.random.PRNGKey(1))
    mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    sparams = jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, llama_param_specs(c), is_leaf=lambda x: isinstance(x, P),
    )
    ck, cv = init_kv_cache(c, slots=2, max_seq_len=64)
    cspec = NamedSharding(mesh, kv_cache_spec(mesh.axis_names))
    ck, cv = jax.device_put(ck, cspec), jax.device_put(cv, cspec)
    tokens = jnp.array([[5, 9, 17, 3] * 4], dtype=jnp.int32)  # B=1, P=16
    logits, _, _ = llama_prefill(
        c, sparams, tokens, jnp.array([15]), ck, cv, jnp.array([0]),
        use_flash=False, mesh=mesh,
    )
    assert logits.shape == (1, c.vocab_size)


def test_sp_engine_generates_and_matches():
    """Engine with an sp axis in its mesh serves greedy tokens matching the
    single-device engine (decode ignores sp; prefill rides the ring)."""
    import asyncio

    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    def gen(mesh):
        async def run():
            eng = TpuServingEngine(
                ServingConfig(
                    model="tiny", slots=2, max_seq_len=64, decode_chunk=4,
                    mesh=mesh,
                )
            )
            try:
                return await eng.generate(
                    "a moderately long prompt for the ring", {"max-tokens": 8}
                )
            finally:
                await eng.close()

        return asyncio.run(run())

    r0 = gen(())
    r1 = gen((("dp", 1), ("sp", 4), ("tp", 2)))
    assert r0["tokens"][:6] == r1["tokens"][:6]


def test_chunked_decode_matches_stepwise():
    """The fused K-step chunk (two-segment KV) must reproduce greedy
    step-by-step decoding exactly."""
    import jax

    from langstream_tpu.models.llama import (
        LlamaConfig,
        init_kv_cache,
        init_llama_params,
        llama_decode_chunk,
        llama_decode_step,
        llama_prefill,
    )

    c = LlamaConfig.tiny(max_seq_len=64)
    params = init_llama_params(c, jax.random.PRNGKey(7))
    prompt = jnp.array([[5, 9, 17, 3]], dtype=jnp.int32)

    def greedy_sample(logits, key):
        t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return t, jnp.zeros_like(t, dtype=jnp.float32)

    # stepwise reference
    ck, cv = init_kv_cache(c, slots=1, max_seq_len=64)
    logits, ck, cv = llama_prefill(
        c, params, prompt, jnp.array([4]), ck, cv, jnp.array([0])
    )
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    ref = [int(tok[0])]
    lengths = jnp.array([4])
    for _ in range(6):
        logits, ck, cv = llama_decode_step(c, params, tok, lengths, ck, cv)
        lengths = lengths + 1
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ref.append(int(tok[0]))

    # chunked
    ck, cv = init_kv_cache(c, slots=1, max_seq_len=64)
    logits, ck, cv = llama_prefill(
        c, params, prompt, jnp.array([4]), ck, cv, jnp.array([0])
    )
    tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    chunk_t, _, ftok, flen, ck, cv = llama_decode_chunk(
        c, params, tok0, jnp.array([4]), jnp.array([True]),
        ck, cv, greedy_sample, jax.random.PRNGKey(0), 3,
    )
    got = [int(tok0[0])] + [int(x) for x in np.asarray(chunk_t)[:, 0]]
    # continue with a second chunk from committed state
    chunk_t2, _, _, _, ck, cv = llama_decode_chunk(
        c, params, ftok, flen, jnp.array([True]),
        ck, cv, greedy_sample, jax.random.PRNGKey(0), 3,
    )
    got += [int(x) for x in np.asarray(chunk_t2)[:, 0]]
    assert got == ref


def test_windowed_decode_chunk_matches_full():
    """A decode chunk with a static attention window covering every active
    sequence must produce exactly the full-cache results."""
    import jax

    from langstream_tpu.models.llama import (
        LlamaConfig,
        init_kv_cache,
        init_llama_params,
        llama_decode_chunk,
        llama_prefill,
    )

    c = LlamaConfig.tiny(max_seq_len=64)
    params = init_llama_params(c, jax.random.PRNGKey(7))
    prompt = jnp.array([[5, 9, 17, 3]], dtype=jnp.int32)

    def greedy_sample(logits, key):
        t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return t, jnp.zeros_like(t, dtype=jnp.float32)

    outs = {}
    for window in (None, 16):
        ck, cv = init_kv_cache(c, slots=1, max_seq_len=64)
        logits, ck, cv = llama_prefill(
            c, params, prompt, jnp.array([4]), ck, cv, jnp.array([0])
        )
        tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        chunk_t, _, ftok, flen, ck, cv = llama_decode_chunk(
            c, params, tok0, jnp.array([4]), jnp.array([True]),
            ck, cv, greedy_sample, jax.random.PRNGKey(0), 5, window=window,
        )
        # a second chunk ensures the windowed commit wrote the full cache
        chunk_t2, _, _, _, _, _ = llama_decode_chunk(
            c, params, ftok, flen, jnp.array([True]),
            ck, cv, greedy_sample, jax.random.PRNGKey(0), 5, window=window,
        )
        outs[window] = (
            [int(x) for x in np.asarray(chunk_t)[:, 0]]
            + [int(x) for x in np.asarray(chunk_t2)[:, 0]]
        )
    assert outs[None] == outs[16]


def test_int8_quantized_engine_generates(run_async):
    """quantize=int8: the engine runs end to end and greedy decoding stays
    deterministic. (Token-for-token equality with bf16 is NOT asserted: on a
    random-init tiny model the logit gaps are ~0, so any perturbation flips
    argmax — the numerical fidelity check lives in test_quantized_logits.)"""
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        config = ServingConfig(
            model="tiny", slots=2, max_seq_len=128, decode_chunk=4,
            default_max_tokens=8, quantize="int8",
        )
        engine = TpuServingEngine.get_or_create(config)
        r1 = await engine.generate("hello world", {"max-tokens": 8})
        r2 = await engine.generate("hello world", {"max-tokens": 8})
        await engine.close()
        assert r1["tokens"] == r2["tokens"]  # greedy determinism
        assert 0 < len(r1["tokens"]) <= 8

    run_async(main())


def test_quantized_logits_close_to_float():
    """Weight-only int8 must track the float logits closely (rank-1 match
    and high correlation on a float32 tiny model)."""
    import dataclasses

    import jax

    from langstream_tpu.models.llama import (
        LlamaConfig,
        init_kv_cache,
        init_llama_params,
        llama_prefill,
    )
    from langstream_tpu.models.quant import quantize_llama_params

    c = dataclasses.replace(LlamaConfig.tiny(max_seq_len=64), dtype=jnp.float32)
    params = init_llama_params(c)
    qparams = quantize_llama_params(params)
    ck, cv = init_kv_cache(c, slots=2)
    toks = jnp.array(
        [[1, 2, 3, 4, 0, 0, 0, 0], [5, 6, 7, 0, 0, 0, 0, 0]], dtype=jnp.int32
    )
    lens = jnp.array([4, 3], dtype=jnp.int32)
    sid = jnp.array([0, 1], dtype=jnp.int32)
    lo, _, _ = llama_prefill(c, params, toks, lens, ck, cv, sid, use_flash=False)
    lq, _, _ = llama_prefill(c, qparams, toks, lens, ck, cv, sid, use_flash=False)
    assert (lo.argmax(-1) == lq.argmax(-1)).all()
    corr = np.corrcoef(np.asarray(lo).ravel(), np.asarray(lq).ravel())[0, 1]
    assert corr > 0.999


def test_int8_tp_sharded_matches_single_device():
    """int8 weights under a TP mesh: scales shard with their weights
    (quantize_specs) and the sharded logits match the unsharded quantized
    ones — the serving-default posture in the north-star TP8 config."""
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as P

    from langstream_tpu.models.llama import (
        LlamaConfig,
        init_kv_cache,
        init_llama_params,
        llama_decode_step,
        llama_param_specs,
        llama_prefill,
        kv_cache_spec,
    )
    from langstream_tpu.models.quant import quantize_llama_params, quantize_specs
    from langstream_tpu.parallel.mesh import make_mesh

    c = dataclasses.replace(LlamaConfig.tiny(max_seq_len=32), dtype=jnp.float32)
    qparams = quantize_llama_params(init_llama_params(c, jax.random.PRNGKey(7)))
    tokens = jnp.array([[5, 9, 17, 3]], dtype=jnp.int32)
    lens = jnp.array([4])
    sid = jnp.array([0])

    ck, cv = init_kv_cache(c, slots=1, max_seq_len=32)
    ref_logits, rk, rv = llama_prefill(
        c, qparams, tokens, lens, ck, cv, sid, use_flash=False
    )

    mesh = make_mesh({"dp": 1, "tp": 2})
    specs = quantize_specs(llama_param_specs(c), qparams)
    sharded = jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        qparams, specs, is_leaf=lambda x: isinstance(x, P),
    )
    cspec = NamedSharding(mesh, kv_cache_spec(mesh.axis_names))
    ck, cv = init_kv_cache(c, slots=1, max_seq_len=32)
    ck, cv = jax.device_put(ck, cspec), jax.device_put(cv, cspec)
    tp_logits, sk, sv = llama_prefill(
        c, sharded, tokens, lens, ck, cv, sid, use_flash=False
    )
    np.testing.assert_allclose(
        np.asarray(ref_logits), np.asarray(tp_logits), rtol=2e-2, atol=2e-2
    )

    # one decode step too
    d_ref, _, _ = llama_decode_step(
        c, qparams, jnp.array([11]), lens, rk, rv
    )
    d_tp, _, _ = llama_decode_step(
        c, sharded, jnp.array([11]), lens, sk, sv
    )
    np.testing.assert_allclose(
        np.asarray(d_ref), np.asarray(d_tp), rtol=2e-2, atol=2e-2
    )


def test_int8_engine_runs_under_mesh(run_async):
    """The engine's serving-default int8 posture must construct and serve
    under a dp×tp mesh."""
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        config = ServingConfig(
            model="tiny", slots=2, max_seq_len=64, decode_chunk=2,
            default_max_tokens=4, quantize="int8",
            mesh=(("dp", 1), ("tp", 2)),
        )
        engine = TpuServingEngine.get_or_create(config)
        r = await engine.generate("mesh int8", {"max-tokens": 4})
        await engine.close()
        assert 0 < len(r["tokens"]) <= 4

    run_async(main())


def test_encoder_embeddings_normalised_and_padding_invariant():
    from langstream_tpu.models.encoder import (
        EncoderConfig,
        encode,
        init_encoder_params,
    )

    c = EncoderConfig.tiny()
    params = init_encoder_params(c, jax.random.PRNGKey(4))

    def run(pad_to):
        tokens = np.zeros((1, pad_to), dtype=np.int32)
        tokens[0, :3] = [5, 9, 17]
        mask = np.zeros((1, pad_to), dtype=np.int32)
        mask[0, :3] = 1
        return np.asarray(encode(c, params, jnp.asarray(tokens), jnp.asarray(mask)))

    e8, e16 = run(8), run(16)
    np.testing.assert_allclose(e8, e16, rtol=1e-4, atol=1e-5)
    assert abs(float(np.linalg.norm(e8[0])) - 1.0) < 1e-4


# ---------------------------------------------------------------------------
# engine-level behavior
# ---------------------------------------------------------------------------


def _engine(slots=4, max_seq_len=64):
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    return TpuServingEngine.get_or_create(
        ServingConfig(model="tiny", slots=slots, max_seq_len=max_seq_len)
    )


def test_engine_generates_and_streams(run_async):
    async def main():
        engine = _engine()
        seen: list[int] = []

        def on_token(token, logprob, last):
            seen.append(token)

        result = await engine.generate(
            "hello", {"max-tokens": 8}, on_token=on_token
        )
        assert len(result["tokens"]) <= 8
        assert result["tokens"] == seen[: len(result["tokens"])]
        assert result["num_prompt_tokens"] == len("hello") + 1  # BOS
        assert isinstance(result["text"], str)
        assert result["ttft"] >= 0
        await engine.close()

    run_async(main())


def test_engine_greedy_deterministic(run_async):
    async def main():
        engine = _engine()
        r1 = await engine.generate("abc", {"max-tokens": 6, "temperature": 0})
        r2 = await engine.generate("abc", {"max-tokens": 6, "temperature": 0})
        assert r1["tokens"] == r2["tokens"]
        await engine.close()

    run_async(main())


def test_engine_continuous_batching_concurrent(run_async):
    """More requests than slots: all complete; greedy results match the
    single-request baseline (slot interference would corrupt logits)."""

    async def main():
        engine = _engine(slots=2)
        baseline = await engine.generate("abc", {"max-tokens": 5, "temperature": 0})
        results = await asyncio.gather(
            *(engine.generate("abc", {"max-tokens": 5, "temperature": 0})
              for _ in range(5))
        )
        for r in results:
            assert r["tokens"] == baseline["tokens"]
        assert engine.stats()["active"] == 0
        await engine.close()

    run_async(main())


def test_engine_respects_max_tokens_and_seq_len(run_async):
    async def main():
        engine = _engine(slots=2, max_seq_len=32)
        r = await engine.generate("x" * 20, {"max-tokens": 100})
        # prompt ~21 tokens, seq cap 32 → at most ~11 generated
        assert len(r["tokens"]) <= 11
        await engine.close()

    run_async(main())


def test_adaptive_chunk_regimes(run_async):
    """A lone request decodes in short sequential chunks (the TTFT regime);
    saturating the slots flips bursts to pipelined heavy chunks. Chunking
    must not change the math: greedy tokens match across regimes and match
    a fixed-chunk engine."""

    async def main():
        from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

        engine = TpuServingEngine.get_or_create(
            ServingConfig(
                model="tiny", slots=4, max_seq_len=64,
                decode_chunk=8, decode_chunk_light=2, light_load_slots=1,
            )
        )
        r1 = await engine.generate("abc", {"max-tokens": 6, "temperature": 0})
        chunks = engine.stats()["decode-chunks"]
        assert chunks["light"] > 0 and chunks["heavy"] == 0
        results = await asyncio.gather(
            *(engine.generate("abc", {"max-tokens": 6, "temperature": 0})
              for _ in range(4))
        )
        assert engine.stats()["decode-chunks"]["heavy"] > 0
        for r in results:
            assert r["tokens"] == r1["tokens"]
        await engine.close()

        fixed = TpuServingEngine.get_or_create(
            ServingConfig(
                model="tiny", slots=4, max_seq_len=64,
                decode_chunk=8, decode_chunk_light=0,
            )
        )
        r2 = await fixed.generate("abc", {"max-tokens": 6, "temperature": 0})
        assert r2["tokens"] == r1["tokens"]
        assert fixed.stats()["decode-chunks"]["light"] == 0
        await fixed.close()

    run_async(main())


def test_warmup_on_start_compiles_both_regimes(run_async):
    """warmup-on-start: the first request triggers a lone probe plus a
    concurrent wave, so BOTH chunk regimes (and their jit variants) exist
    before real traffic — a first compile mid-traffic convoys the queue."""

    async def main():
        from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

        engine = TpuServingEngine.get_or_create(
            ServingConfig(
                model="tiny", slots=4, max_seq_len=128,
                decode_chunk=8, decode_chunk_light=2, light_load_slots=1,
                warmup_on_start=True,
            )
        )
        r = await engine.generate("abc", {"max-tokens": 4, "temperature": 0})
        assert r["tokens"]
        chunks = engine.stats()["decode-chunks"]
        assert chunks["light"] > 0 and chunks["heavy"] > 0
        k_variants = {key[2] for key in engine._decode_chunk_fns}
        assert {2, 8} <= k_variants
        # idempotent: an explicit warmup() call shares the gate's task and
        # does not re-run the probe/wave
        generated = engine.total_generated
        await engine.warmup()
        assert engine.total_generated == generated
        await engine.close()

    run_async(main())


def test_stop_sequences_truncate_and_free_slot(run_async):
    """Reference parity (`ChatCompletionsConfig.stop`): generation halts
    when a stop string appears; the final text excludes the match."""

    async def main():
        engine = _engine()
        base = await engine.generate("abc", {"max-tokens": 10, "temperature": 0})
        full = base["text"]
        assert len(full) >= 3
        stop = full[1:3]
        r = await engine.generate(
            "abc", {"max-tokens": 10, "temperature": 0, "stop": [stop]}
        )
        assert r["finish_reason"] == "stop"
        assert stop not in r["text"]
        assert r["text"] == full[: full.find(stop)]
        assert r["num_completion_tokens"] <= base["num_completion_tokens"]
        # a string form and a non-matching stop behave sanely
        r2 = await engine.generate(
            "abc", {"max-tokens": 10, "temperature": 0, "stop": stop}
        )
        assert r2["text"] == r["text"]
        r3 = await engine.generate(
            "abc",
            {"max-tokens": 10, "temperature": 0, "stop": [" unlikely"]},
        )
        assert r3["text"] == full
        await engine.close()

    run_async(main())


def test_long_context_pow2_window_lane(run_async):
    """Long-context serving: beyond 1024 rows the attention window buckets
    switch from 128-multiples to powers of two (engine._read_blocks_for,
    which counts them in block-table columns of 64 rows) — a
    long prompt must prefill, decode through the pow2 lane, and produce
    the same stream as a fresh engine (determinism across bucket growth)."""
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        cfg = ServingConfig(
            model="tiny", slots=2, max_seq_len=4096, decode_chunk=8
        )
        engine = TpuServingEngine(cfg)
        # window bucketing: 128-multiples below 1024, pow2 above
        assert engine._read_blocks_for(130) == 4    # 256 rows
        assert engine._read_blocks_for(900) == 16   # 1024 rows
        assert engine._read_blocks_for(1100) == 32  # 2048 rows
        assert engine._read_blocks_for(3000) == 64  # the whole table
        # prompt lands just under the 1024 boundary; 48 decoded tokens
        # carry the sequence across it, so decode re-dispatches under the
        # grown 2048 pow2 bucket MID-GENERATION — the transition the pow2
        # lane exists for
        prompt = "tpu. " * 204  # ~1021 byte-tokens with BOS
        r = await engine.generate(prompt, {"max-tokens": 48, "temperature": 0})
        assert 960 < r["num_prompt_tokens"] <= 1024
        assert r["num_prompt_tokens"] + len(r["tokens"]) > 1024
        assert len(r["tokens"]) == 48
        windows = {key[1] for key in engine._decode_chunk_fns}
        assert {16, 32} <= windows, sorted(windows)
        await engine.close()

        engine2 = TpuServingEngine(cfg)
        r2 = await engine2.generate(prompt, {"max-tokens": 48, "temperature": 0})
        assert r2["tokens"] == r["tokens"]
        await engine2.close()

    run_async(main())


def test_stop_window_covers_multibyte_stop_strings(run_async):
    """Regression (r3 advisor, medium): the per-token stop-detection window
    must be sized from the stop string's encoded BYTE length — under the
    byte-level tokenizer (one token per UTF-8 byte) a char-sized window
    missed any stop longer than a few multi-byte chars and generation ran
    to max-tokens."""

    async def main():
        from langstream_tpu.serving.engine import _Request

        engine = _engine()
        stop = "日本語のテスト"  # 7 chars, 21 UTF-8 bytes
        assert len(stop.encode("utf-8")) > len(stop) + 8  # would miss pre-fix
        req = _Request(
            prompt_tokens=[engine.tokenizer.bos_id], max_tokens=100,
            temperature=0.0, top_k=0, top_p=1.0, on_token=None,
            future=asyncio.get_event_loop().create_future(), stop=[stop],
        )
        engine.slots[0].request = req
        done = False
        for b in ("abc" + stop).encode("utf-8"):
            done = engine._emit_token(0, int(b), 0.0)
            if done:
                break
        assert done and req.stop_matched
        await engine.close()

    run_async(main())


def test_normalize_stop_coerces_non_strings():
    """YAML can hand over non-string stop entries (``stop: [42]``); they
    must be coerced up front, not TypeError on the per-token hot path."""
    from langstream_tpu.serving.engine import _normalize_stop

    assert _normalize_stop([42, "x", None, ""]) == ["42", "x"]
    assert _normalize_stop("abc") == ["abc"]
    assert _normalize_stop(None) == []


def test_presence_frequency_penalties():
    """Sampler-level: penalties shift the (greedy) distribution away from
    already-emitted tokens (reference: ChatCompletionsConfig penalties)."""
    from langstream_tpu.serving.sampler import sample_tokens

    V = 32
    logits = np.zeros((1, V), np.float32)
    logits[0, 5] = 10.0
    logits[0, 7] = 8.0
    counts = np.zeros((1, V), np.int32)
    counts[0, 5] = 3
    tokens, _ = sample_tokens(
        jnp.asarray(logits), jax.random.PRNGKey(0),
        jnp.zeros(1), jnp.zeros(1, jnp.int32), all_greedy=True,
        use_penalties=True,
        presences=jnp.asarray([1.0]), frequencies=jnp.asarray([5.0]),
        counts=jnp.asarray(counts),
    )
    # token 5: 10 - 1 - 5*3 = -6 < token 7's 8 -> argmax moves
    assert int(tokens[0]) == 7
    # zero penalties leave the argmax alone even with counts present
    tokens, _ = sample_tokens(
        jnp.asarray(logits), jax.random.PRNGKey(0),
        jnp.zeros(1), jnp.zeros(1, jnp.int32), all_greedy=True,
        use_penalties=True,
        presences=jnp.asarray([0.0]), frequencies=jnp.asarray([0.0]),
        counts=jnp.asarray(counts),
    )
    assert int(tokens[0]) == 5


FEATURES_OF_THE_POOL = {
    "pool-role": {"pool-role": "prefill"},
    "prefix-store": {"prefix-store": {"t1-bytes": 1 << 20}},
    "adapter-store": {"adapter-store": {"rank": 2, "t0-entries": 2}},
    "prefill-chunk": {"prefill-chunk": 16},
    "speculative-drafts": {"speculative-drafts": 4},
}


@pytest.mark.parametrize("feature", sorted(FEATURES_OF_THE_POOL))
def test_a_feature_of_the_pool_needs_no_layout_named(run_async, feature):
    """Each of these was refused until a configuration also said
    ``kv-layout: paged``; the engine has one layout, so the feature's own
    key is enough to construct and to serve a request."""
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        engine = TpuServingEngine(
            ServingConfig.from_dict(
                {"model": "tiny", "slots": 2, "max-seq-len": 128,
                 "kv-block-size": 16, **FEATURES_OF_THE_POOL[feature]}
            )
        )
        try:
            r = await engine.generate(
                "one request through the pool, forty characters long",
                {"max-tokens": 6, "temperature": 0},
            )
        finally:
            await engine.close()
        assert r["tokens"]
        # a prefill-role engine answers with the first token and a ticket
        assert (r["finish_reason"] == "handoff") == (feature == "pool-role")

    run_async(main())


@pytest.mark.parametrize(
    "value,says", [("dense", "removed at PR 29"), ("ragged", "unknown")]
)
@pytest.mark.parametrize("through", ["yaml", "constructor"])
def test_a_layout_other_than_the_pool_is_refused_by_name(value, says, through):
    from langstream_tpu.serving.engine import ServingConfig

    with pytest.raises(ValueError, match="kv-layout") as e:
        if through == "yaml":
            ServingConfig.from_dict({"model": "tiny", "kv-layout": value})
        else:
            ServingConfig(model="tiny", kv_layout=value)
    assert repr(value) in str(e.value) and says in str(e.value)
    # the constant itself is accepted and read back, as the cells write it
    assert ServingConfig.from_dict({"kv-layout": "paged"}).to_dict()[
        "kv-layout"
    ] == "paged"


def test_engine_frequency_penalty_prevents_repeats(run_async):
    """A strong frequency penalty makes every generated token distinct —
    each emission forbids that token for the rest of the stream (counts
    ride the decode-chunk carry; penalty bursts run sequentially)."""

    async def main():
        from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

        engine = TpuServingEngine.get_or_create(
            ServingConfig(
                model="tiny", slots=4, max_seq_len=64, decode_chunk=4,
                kv_block_size=16,
            )
        )
        r = await engine.generate(
            "abc",
            {"max-tokens": 12, "temperature": 0, "frequency-penalty": 100.0},
        )
        assert len(r["tokens"]) >= 8
        assert len(set(r["tokens"])) == len(r["tokens"]), r["tokens"]
        # an unpenalised engine run still works afterwards (variant cache
        # keys penalties separately)
        r2 = await engine.generate("abc", {"max-tokens": 6, "temperature": 0})
        assert r2["tokens"]
        await engine.close()

    run_async(main())


def test_stop_sequences_held_back_from_stream(run_async):
    """Streamed chunks never contain the stop text (hold-back + truncation
    in the provider's stream adapter)."""
    from langstream_tpu.agents.tpu_provider import _StreamAdapter
    from langstream_tpu.models.tokenizer import ByteTokenizer

    async def main():
        tok = ByteTokenizer()
        chunks: list = []

        def consumer(chunk):
            chunks.append(chunk)

        adapter = _StreamAdapter(tok, consumer, stop=["XY"])
        ids = [ord(c) for c in "abXYcd"]
        for i, t in enumerate(ids):
            await adapter.on_token(t, 0.0, last=(i == len(ids) - 1))
        text = "".join(c.text for c in chunks)
        assert text == "ab"
        assert chunks[-1].last
        # partial prefix at end-of-stream resolves (no match -> emitted)
        chunks2: list = []
        adapter2 = _StreamAdapter(tok, lambda c: chunks2.append(c), stop=["XY"])
        ids2 = [ord(c) for c in "abX"]
        for i, t in enumerate(ids2):
            await adapter2.on_token(t, 0.0, last=(i == len(ids2) - 1))
        assert "".join(c.text for c in chunks2) == "abX"

    run_async(main())


def test_engine_top_p_and_stream_termination(run_async):
    async def main():
        engine = _engine()
        events: list[tuple[int, bool]] = []

        def on_token(token, logprob, last):
            events.append((token, last))

        r = await engine.generate(
            "xyz", {"max-tokens": 5, "temperature": 0.9, "top-p": 0.8},
            on_token=on_token,
        )
        assert len(r["tokens"]) <= 5
        # the stream always terminates with a last=True emission
        assert events[-1][1] is True
        assert all(last is False for _, last in events[:-1])
        await engine.close()

    run_async(main())


def test_closed_engine_not_reused(run_async):
    async def main():
        from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

        cfg = ServingConfig(model="tiny", slots=2, max_seq_len=64)
        e1 = TpuServingEngine.get_or_create(cfg)
        await e1.generate("a", {"max-tokens": 2})
        await e1.close()
        e2 = TpuServingEngine.get_or_create(cfg)
        assert e2 is not e1
        r = await e2.generate("a", {"max-tokens": 2})
        assert len(r["tokens"]) <= 2
        await e2.close()

    run_async(main())


def test_non_power_of_two_max_seq(run_async):
    async def main():
        from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

        engine = TpuServingEngine.get_or_create(
            ServingConfig(model="tiny", slots=2, max_seq_len=48)
        )
        r = await engine.generate("y" * 40, {"max-tokens": 4})
        assert len(r["tokens"]) <= 7
        await engine.close()

    run_async(main())


def test_embedding_engine(run_async):
    async def main():
        from langstream_tpu.serving.engine import EmbeddingEngine

        engine = EmbeddingEngine.get_or_create(model="tiny")
        vecs = await engine.embed(["hello world", "hello world", "different"])
        assert len(vecs) == 3
        assert vecs[0] == vecs[1]
        assert vecs[0] != vecs[2]
        norm = sum(v * v for v in vecs[0]) ** 0.5
        assert abs(norm - 1.0) < 1e-3
        # batch-size padding: a different batch size reuses the same
        # power-of-two variant and padding rows don't leak into results
        solo = await engine.embed(["hello world"])
        assert len(solo) == 1
        assert solo[0] == pytest.approx(vecs[0], abs=1e-5)

    run_async(main())


# ---------------------------------------------------------------------------
# tpu provider end-to-end through an application
# ---------------------------------------------------------------------------

TPU_APP = """
topics:
  - name: "input-topic"
    creation-mode: create-if-not-exists
  - name: "output-topic"
    creation-mode: create-if-not-exists
  - name: "stream-topic"
    creation-mode: create-if-not-exists
pipeline:
  - name: "convert"
    type: "document-to-json"
    input: "input-topic"
    configuration:
      text-field: "question"
  - name: "chat"
    type: "ai-chat-completions"
    output: "output-topic"
    configuration:
      completion-field: "value.answer"
      stream-to-topic: "stream-topic"
      stream-response-completion-field: "value"
      min-chunks-per-message: 4
      max-tokens: 6
      messages:
        - role: user
          content: "{{ value.question }}"
"""

TPU_CONFIG = """
configuration:
  resources:
    - type: "tpu-serving-configuration"
      name: "tpu"
      configuration:
        model: "tiny"
        slots: 2
        max-seq-len: 64
"""

INSTANCE = """
instance:
  streamingCluster:
    type: "memory"
"""


def test_chat_agent_on_tpu_engine(tmp_path, run_async):
    async def main():
        from langstream_tpu.runtime.local_runner import LocalApplicationRunner

        (tmp_path / "pipeline.yaml").write_text(TPU_APP)
        (tmp_path / "configuration.yaml").write_text(TPU_CONFIG)
        runner = LocalApplicationRunner.from_directory(tmp_path, instance=INSTANCE)
        async with runner:
            await runner.produce("input-topic", "hi there")
            msgs = await runner.wait_for_messages("output-topic", 1, timeout=30)
            assert "answer" in msgs[0].value
            assert isinstance(msgs[0].value["answer"], str)

    run_async(main())


def test_profiler_hooks_trace_and_hlo_dump(tmp_path, run_async, monkeypatch):
    """Env-gated profiling: a trace of the first N decode chunks lands in
    LS_TPU_PROFILE_DIR; each compiled serving program dumps its HLO text
    into LS_TPU_HLO_DUMP_DIR (SURVEY §5.1's TPU-native observability)."""
    import os

    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    trace_dir = tmp_path / "trace"
    hlo_dir = tmp_path / "hlo"
    monkeypatch.setenv("LS_TPU_PROFILE_DIR", str(trace_dir))
    monkeypatch.setenv("LS_TPU_PROFILE_CHUNKS", "1")
    monkeypatch.setenv("LS_TPU_HLO_DUMP_DIR", str(hlo_dir))

    async def main():
        config = ServingConfig(
            model="tiny", slots=2, max_seq_len=64, decode_chunk=2,
            default_max_tokens=6,
        )
        engine = TpuServingEngine.get_or_create(config)
        # warm the decode program OUTSIDE the trace, and trace one chunk:
        # the auto-capture starts at the first decode chunk, tracing an
        # XLA compile on CPU multiplies its cost ~10x, and even one
        # traced dispatch pays ~14 s of fixed profiler overhead — while
        # the contract pinned here is only that the captured trace lands
        # on disk (chunk-count semantics are unit-tested with a fake
        # jax.profiler in test_profiling.py)
        engine.profiler._auto_remaining = 0
        await engine.generate("warm up", {"max-tokens": 6})
        engine.profiler._auto_remaining = 1
        await engine.generate("profile me", {"max-tokens": 6})
        engine.profiler.stop_trace()  # in case fewer than N chunks ran
        await engine.close()

    run_async(main())
    # jax.profiler writes a plugins/profile/<ts>/ tree with .xplane.pb files
    traces = [p for p in trace_dir.rglob("*") if p.is_file()]
    assert traces, "no profiler trace files captured"
    hlos = list(hlo_dir.glob("*.hlo.txt"))
    assert any("prefill" in p.name for p in hlos), hlos
    assert any("decode_chunk" in p.name for p in hlos), hlos
    assert all(p.stat().st_size > 1000 for p in hlos)


def test_decode_roofline_model():
    from langstream_tpu.models.llama import LlamaConfig
    from langstream_tpu.serving.profiling import decode_step_bytes

    c = LlamaConfig.llama_1b()
    r8 = decode_step_bytes(c, slots=64, window=256, quantize="int8")
    rb = decode_step_bytes(c, slots=64, window=256, quantize=None)
    # int8 halves weight traffic, cache unchanged
    assert rb.weight_bytes == 2 * r8.weight_bytes
    assert rb.cache_bytes_per_step == r8.cache_bytes_per_step
    # ~0.9B params -> ~0.9GB int8
    assert 0.8e9 < r8.weight_bytes < 1.1e9
    # cache window: L16 * 64 slots * 256 rows * 8 kvh * 128 d * 2B * 2(K,V)
    assert r8.cache_bytes_per_step == 16 * 64 * 256 * 8 * 128 * 2 * 2
    # on the CPU there is no roof: the fields that need a device's
    # published bandwidth are null, never another chip's
    assert r8.device_kind == "cpu" and r8.hbm_gbps is None
    assert r8.min_step_ms() is None and r8.utilization(10.0) is None
    import dataclasses

    v5e = dataclasses.replace(r8, device_kind="TPU v5 lite", hbm_gbps=819.0)
    assert v5e.min_step_ms() > 0
    assert 0 < v5e.utilization(achieved_step_ms=10 * v5e.min_step_ms()) <= 0.11


def test_mesh_engine_serves_with_kernels_on(run_async, monkeypatch):
    """TP engine with BOTH Pallas kernels enabled (flash prefill via
    shard_map + paged decode read via shard_map, interpret mode on CPU):
    the r2 special cases that disabled kernels under a mesh are gone."""
    monkeypatch.setenv("LS_TPU_FLASH", "interpret")
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        config = ServingConfig(
            model="tiny", slots=4, max_seq_len=64, decode_chunk=2,
            default_max_tokens=6, kv_layout="paged", kv_block_size=8,
            paged_kernel="pallas-interpret",
            mesh=(("dp", 2), ("tp", 2)),
        )
        engine = TpuServingEngine.get_or_create(config)
        results = await asyncio.gather(
            *(engine.generate(f"kernels on {i}", {"max-tokens": 6})
              for i in range(3))
        )
        await engine.close()
        for r in results:
            assert 0 < len(r["tokens"]) <= 6

    run_async(main())


def test_sampler_mode_specializations_agree():
    """The cheap compiled variants must equal the full sampler on inputs
    they claim to cover: all_greedy ≡ full path at temperature 0; dropping
    the top-k sweep is identity when no row requests top-k."""
    from langstream_tpu.serving.sampler import sample_tokens

    key = jax.random.PRNGKey(7)
    logits = jax.random.normal(jax.random.PRNGKey(1), (5, 301), jnp.float32)
    zero_t = jnp.zeros((5,), jnp.float32)
    no_k = jnp.zeros((5,), jnp.int32)

    full_tokens, full_lps = sample_tokens(logits, key, zero_t, no_k)
    fast_tokens, fast_lps = sample_tokens(
        logits, key, zero_t, no_k, use_top_k=False, all_greedy=True
    )
    np.testing.assert_array_equal(np.asarray(full_tokens), np.asarray(fast_tokens))
    np.testing.assert_allclose(np.asarray(full_lps), np.asarray(fast_lps), rtol=1e-6)

    # sampled path without top-k rows: dropping the sweep changes nothing
    temps = jnp.full((5,), 0.8, jnp.float32)
    with_k, _ = sample_tokens(logits, key, temps, no_k, use_top_k=True)
    without_k, _ = sample_tokens(logits, key, temps, no_k, use_top_k=False)
    np.testing.assert_array_equal(np.asarray(with_k), np.asarray(without_k))

    # top-k actually constrains when requested
    ks = jnp.full((5,), 2, jnp.int32)
    constrained, _ = sample_tokens(
        logits, jax.random.PRNGKey(9), jnp.full((5,), 5.0), ks, use_top_k=True
    )
    top2 = np.argsort(-np.asarray(logits), axis=-1)[:, :2]
    for row, token in enumerate(np.asarray(constrained)):
        assert token in top2[row]


def test_engine_sampler_mode_derivation():
    from langstream_tpu.serving.engine import TpuServingEngine

    mode = TpuServingEngine._sampler_mode(
        np.zeros(3, np.float32), np.zeros(3, np.int32), np.ones(3, np.float32)
    )
    assert mode == (False, False, True)  # pure greedy batch
    mode = TpuServingEngine._sampler_mode(
        np.array([0.0, 0.7], np.float32), np.array([0, 40], np.int32),
        np.ones(2, np.float32),
    )
    assert mode == (False, True, False)  # one sampling row with top-k
    mode = TpuServingEngine._sampler_mode(
        np.array([0.7], np.float32), np.array([0], np.int32),
        np.array([0.9], np.float32),
    )
    assert mode == (True, False, False)  # top-p requested


def test_cancelled_request_frees_slot():
    """A caller that cancels generate() mid-stream stops consuming its
    slot at the next emission; other requests keep streaming and new ones
    admit into the freed slot."""
    import asyncio

    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        eng = TpuServingEngine(
            ServingConfig(
                model="tiny", slots=2, max_seq_len=128, decode_chunk=2,
                kv_layout="paged", kv_block_size=16, paged_kernel="xla",
                kv_pool_blocks=20,  # room for the doomed worst case
            )
        )
        try:
            seen = asyncio.Event()

            async def on_token(token, logprob, last):
                seen.set()

            doomed = asyncio.ensure_future(
                eng.generate("a b c d", {"max-tokens": 100},
                             on_token=on_token)
            )
            survivor = asyncio.ensure_future(
                eng.generate("x y z", {"max-tokens": 16})
            )
            await asyncio.wait_for(seen.wait(), 120)
            doomed.cancel()
            out = await survivor
            # tolerant count: the random-init model may emit EOS early
            assert 0 < len(out["tokens"]) <= 16
            # the doomed slot must free well before its 100-token budget
            for _ in range(200):
                if eng.stats()["active"] == 0:
                    break
                await asyncio.sleep(0.05)
            assert eng.stats()["active"] == 0, eng.stats()
            # a follow-up request admits into the freed capacity
            out2 = await eng.generate("again", {"max-tokens": 4})
            assert 0 < len(out2["tokens"]) <= 4
        finally:
            await eng.close()

    asyncio.run(main())


def test_cancelled_chunked_prefill_releases_reservation():
    """Cancelling a request mid-chunked-prefill frees its slot and its
    worst-case block reservation — under paged backpressure that
    reservation is what blocks live admissions."""
    import asyncio

    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        eng = TpuServingEngine(
            ServingConfig(
                model="tiny", slots=2, max_seq_len=512, decode_chunk=2,
                kv_layout="paged", kv_block_size=16, paged_kernel="xla",
                prefill_chunk=32,
            )
        )
        try:
            doomed = asyncio.ensure_future(
                eng.generate("a long chunked prompt " * 16, {"max-tokens": 8})
            )
            # wait until the slot is claimed for chunked prefill
            for _ in range(400):
                if any(s.prefilling for s in eng.slots):
                    break
                await asyncio.sleep(0.02)
            assert any(s.prefilling for s in eng.slots)
            doomed.cancel()
            for _ in range(400):
                stats = eng.stats()
                if stats["kv"]["reserved_blocks"] == 0:
                    break
                await asyncio.sleep(0.05)
            assert eng.stats()["kv"]["reserved_blocks"] == 0, eng.stats()
            # capacity is genuinely free again
            out = await eng.generate("fresh", {"max-tokens": 4})
            assert 0 < len(out["tokens"]) <= 4
        finally:
            await eng.close()

    asyncio.run(main())
