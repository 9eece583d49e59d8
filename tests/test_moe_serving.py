"""MoE (Mixtral-family) models on the serving engine.

The MoE family plugs its routed-expert FFN into the shared llama layer math
(``moe_serving_ffn``), so every serving mode — the dense reference, paged
KV, int8, ep/tp meshes — must hold for MoE exactly as the dense suites pin them for
Llama. Capability anchor: the reference reaches MoE models only through
SaaS providers (``HuggingFaceProvider.java:47``); here they are in-tree.
"""

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


def _generate(cfg_kwargs, prompt="the quick brown fox", max_tokens=16,
              reference=None):
    """One greedy generation; with ``reference`` (the ``dense_reference_greedy``
    fixture) also the dense reference's tokens for the same prompt, from the
    same engine's weights and FFN hook."""
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def run():
        eng = TpuServingEngine(ServingConfig(**cfg_kwargs))
        try:
            tokens = eng.tokenizer.encode(prompt)
            out = await eng.generate(tokens, {"max-tokens": max_tokens})
            if reference is not None:
                out["reference"] = reference(eng, tokens, max_tokens)
            return out
        finally:
            await eng.close()

    return asyncio.run(run())


BASE = dict(model="moe-tiny", slots=4, max_seq_len=128, decode_chunk=8)


# ---------------------------------------------------------------------------
# model-level invariants
# ---------------------------------------------------------------------------


def test_moe_prefill_decode_equivalence():
    """Chunked MoE decode over the cache must match the cacheless
    ``moe_forward`` logits position by position (KV + routing correctness:
    a capacity/combine bug that changed decode-time routing would break
    this, since decode routes one token per step while the full forward
    routes the whole sequence at once).

    capacity_factor is raised so no expert ever overflows: GShard capacity
    dropping is batch-context-dependent by design (a token that overflows
    in a full-sequence batch is alone in its decode step), so exact
    equivalence only holds — and is only asserted — in the drop-free
    regime."""
    import dataclasses

    from langstream_tpu.models.llama import init_kv_cache, llama_prefill
    from langstream_tpu.models.llama import llama_decode_chunk
    from langstream_tpu.models.moe import (
        MoEConfig,
        init_moe_params,
        moe_forward,
        moe_serving_ffn,
    )

    c = dataclasses.replace(MoEConfig.tiny(max_seq_len=32), capacity_factor=4.0)
    params = init_moe_params(c, jax.random.PRNGKey(1))
    ffn = moe_serving_ffn(c)
    prompt = jnp.array([[5, 9, 17, 3, 11, 2]], dtype=jnp.int32)
    n = prompt.shape[1]
    steps = 6

    # reference: greedy continuation with the cacheless forward
    seq = prompt
    ref_tokens = []
    for _ in range(steps):
        logits, _aux = moe_forward(c, params, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        ref_tokens.append(int(nxt[0]))
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)

    # engine-path: prefill + one greedy decode chunk
    ck, cv = init_kv_cache(c, slots=1, max_seq_len=32)
    logits_p, ck, cv = llama_prefill(
        c, params, prompt, jnp.array([n]), ck, cv, jnp.array([0]), ffn=ffn
    )
    first = jnp.argmax(logits_p, axis=-1).astype(jnp.int32)
    assert int(first[0]) == ref_tokens[0]

    def greedy(logits, key):
        t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return t, jnp.zeros_like(t, dtype=jnp.float32)

    chunk_tokens, _lps, _ft, _fl, ck, cv = llama_decode_chunk(
        c, params, first, jnp.array([n]), jnp.array([True]), ck, cv,
        greedy, jax.random.PRNGKey(0), steps - 1, ffn=ffn,
    )
    got = [ref_tokens[0]] + [int(t) for t in np.asarray(chunk_tokens)[:, 0]]
    assert got == ref_tokens


def test_moe_prefill_padding_independence():
    """Prefill logits must not depend on the CONTENT beyond each row's
    length: padded positions are masked out of the top-2 gate, so they
    cannot consume expert capacity and evict real tokens (the GShard
    cumsum orders the flattened (B,S) tokens — row 0's pads come before
    every row-1 token). Same shapes and lengths in both batches, so the
    capacity constant and real-token contention are identical; only the
    garbage beyond ``lengths`` differs."""
    from langstream_tpu.models.llama import init_kv_cache, llama_prefill
    from langstream_tpu.models.moe import (
        MoEConfig,
        init_moe_params,
        moe_serving_ffn,
    )

    c = MoEConfig.tiny(max_seq_len=64)  # default tight capacity_factor=1.25
    params = init_moe_params(c, jax.random.PRNGKey(2))
    ffn = moe_serving_ffn(c)
    short = jnp.array([5, 9, 17], dtype=jnp.int32)
    long_ = jnp.arange(1, 33, dtype=jnp.int32) % 300
    lengths = jnp.array([3, 32])

    def run(pad_fill):
        row0 = jnp.concatenate([short, pad_fill])
        batch = jnp.stack([row0, long_])
        ck, cv = init_kv_cache(c, slots=2, max_seq_len=64)
        logits, _, _ = llama_prefill(
            c, params, batch, lengths, ck, cv, jnp.array([0, 1]), ffn=ffn
        )
        return np.asarray(logits)

    zeros = run(jnp.zeros(29, jnp.int32))
    junk = run((jnp.arange(29, dtype=jnp.int32) * 7 + 11) % 300)
    np.testing.assert_array_equal(zeros, junk)


def test_quantized_moe_params_shapes():
    from langstream_tpu.models.moe import MoEConfig, init_moe_params
    from langstream_tpu.models.quant import QTensor, quantize_moe_params

    c = MoEConfig.tiny()
    q = quantize_moe_params(init_moe_params(c))
    layers = q["layers"]
    assert isinstance(layers["w_gate"], QTensor)
    # per-(layer, expert, output-channel) scales: contraction axis reduced
    assert layers["w_gate"].s.shape == (c.layers, c.experts, 1, c.moe_intermediate)
    assert layers["w_down"].s.shape == (c.layers, c.experts, 1, c.hidden)
    assert not isinstance(layers["router"], QTensor)  # routing stays f32
    assert not isinstance(layers["attn_norm"], QTensor)


# ---------------------------------------------------------------------------
# engine-level
# ---------------------------------------------------------------------------


def test_moe_engine_generates_dense(dense_reference_greedy):
    """The dense reference loop generates with the routed FFN hook, and in
    float32 the engine's tokens are its tokens over the whole answer."""
    out = _generate(
        {**BASE, "model_dtype": "float32"}, reference=dense_reference_greedy
    )
    assert len(out["reference"]) == 16
    assert out["tokens"] == out["reference"][: len(out["tokens"])]


def test_moe_engine_generates_paged():
    out = _generate(BASE)
    assert len(out["tokens"]) == 16
    assert out["text"]


def test_moe_engine_int8_generates():
    out = _generate({**BASE, "quantize": "int8"})
    assert len(out["tokens"]) == 16


# Engine-variant comparisons assert a SHORT horizon: the two paths compute
# attention with different float orderings (two-segment online-softmax merge
# vs one concat softmax; all-to-all vs local einsum), and MoE's routing
# argmax amplifies that bf16 noise into divergent tokens after enough steps
# — the same reason production engines don't promise bitwise equality across
# kernel paths. Exact math is pinned by the model-level tests above.
_HORIZON = 6


def test_moe_engine_mesh_matches_single_device():
    """ep×tp-sharded MoE serving matches single-device greedy over the
    comparison horizon (the dispatch/combine all-to-alls and TP collectives
    must not change the math)."""
    r0 = _generate(BASE)
    r1 = _generate({**BASE, "mesh": (("dp", 1), ("ep", 2), ("tp", 2))})
    assert r0["tokens"][:_HORIZON] == r1["tokens"][:_HORIZON]


def test_moe_engine_paged_matches_dense(dense_reference_greedy):
    """bf16, as served: the paged engine against the dense reference loop
    over the comparison horizon."""
    out = _generate(BASE, reference=dense_reference_greedy)
    assert out["tokens"][:_HORIZON] == out["reference"][:_HORIZON]


def test_moe_checkpoint_roundtrip(tmp_path):
    """HF-Mixtral-format save → load reproduces the forward exactly (the
    layer-stack/expert/transpose conventions are the risky part; the MoE
    twin of the dense checkpoint round-trip test)."""
    from langstream_tpu.models.checkpoints import (
        load_moe_checkpoint,
        save_moe_checkpoint,
    )
    from langstream_tpu.models.moe import MoEConfig, init_moe_params, moe_forward

    c = MoEConfig.tiny(max_seq_len=32)
    params = init_moe_params(c, jax.random.PRNGKey(3))
    save_moe_checkpoint(params, c, str(tmp_path / "ckpt"))
    loaded = load_moe_checkpoint(str(tmp_path / "ckpt"), c)

    tokens = jnp.array([[5, 9, 17, 3, 11]], dtype=jnp.int32)
    ref, _ = moe_forward(c, params, tokens)
    got, _ = moe_forward(c, loaded, tokens)
    # save writes f32; load casts back to bf16 — bitwise for bf16 sources
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(got), rtol=1e-2, atol=1e-2
    )


def test_moe_engine_serves_from_checkpoint(tmp_path):
    from langstream_tpu.models.checkpoints import save_moe_checkpoint
    from langstream_tpu.models.moe import MoEConfig, init_moe_params

    c = MoEConfig.tiny(max_seq_len=128)
    save_moe_checkpoint(
        init_moe_params(c, jax.random.PRNGKey(4)), c, str(tmp_path / "ckpt")
    )
    out = _generate({**BASE, "checkpoint": str(tmp_path / "ckpt")})
    assert len(out["tokens"]) == 16
