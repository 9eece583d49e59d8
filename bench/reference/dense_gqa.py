"""The dense grouped-query decoder, written plainly.

A float32 ``jax.numpy`` forward pass of the architecture both configurations
publish (Mistral-7B-v0.3; InternLM2, whose fused ``wqkv`` is the same
mathematics as the separate projections used here): token embedding,
``layers`` × [RMSNorm → Q/K/V projections → rotary embedding (rotate-half,
as in the published modelling code) → causal grouped-query softmax attention
→ output projection → residual; RMSNorm → SwiGLU → residual], final RMSNorm,
untied head. No cache, no kernels, no batching, no bfloat16:
``default_matmul_precision("highest")`` because a float32 matmul on a TPU
runs in lower precision unless told otherwise.

It is fed the engine's own parameters one layer at a time (an int8 weight is
dequantised, ``q * s``, exactly as the program defines it, so weight
quantisation is not what the comparison measures), and so never holds a
second copy of a 7B model.

:func:`check_engine` is the comparison that a run's ``correct`` rests on:
one seeded sequence, prefilled through the paged cache by the program's own
prefill and then decoded a few steps by its decode program with the read
kernel the engine selected, against this file's full forward pass over the
same tokens.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# Tolerance, and why. Compared are float32 logits of shape (steps+1, vocab).
# With random weights the logits are about unit normal, so the error is
# judged against their spread: the root-mean-square error over the
# vocabulary as a share of the reference's standard deviation, and the
# correlation of the two logit vectors, at every compared position. The
# program computes in bfloat16 (8 bits of mantissa: 0.4% a rounding) through
# 2 x `layers` residual blocks, and an int8 posture also rounds each weight
# column and each stored K and V row to 8 bits; the errors add in quadrature
# and grow with depth, so what a posture should read is a property of the
# configuration. Each configuration's file therefore states its own limits
# under ``reference_tolerance`` (``rms_share``, ``min_correlation``), at
# about twice what the chip read for it (PERF.md, PR 23: 1.5% at InternLM2
# in bfloat16, 2.2-2.5% at Mistral in int8), so that a configuration served a
# step lower in precision than its file states is not `correct`. A dropped
# term (no RoPE, a wrong mask, one layer skipped, heads grouped wrongly)
# reads tens of percent to over 100% (tests/bench/test_bench_reference.py).

CHECK_PROMPT_TOKENS = 128
CHECK_DECODE_STEPS = 4


def to_f32(t):
    """An engine parameter as float32: a plain array, or an int8 weight with
    its scales (``q`` and ``s``: models/quant.py QTensor, ``q * s``)."""
    if hasattr(t, "q") and hasattr(t, "s"):
        return t.q.astype(jnp.float32) * t.s.astype(jnp.float32)
    return jnp.asarray(t, dtype=jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (T, heads, head_dim), position t = row t. Rotate-half."""
    T, _, D = x.shape
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def layer(x, w, *, heads, kv_heads, head_dim, rope_theta, norm_eps):
    """One decoder block on a whole sequence x: (T, hidden), float32."""
    T = x.shape[0]
    h = rms_norm(x, w["attn_norm"], norm_eps)
    q = rope((h @ w["wq"]).reshape(T, heads, head_dim), rope_theta)
    k = rope((h @ w["wk"]).reshape(T, kv_heads, head_dim), rope_theta)
    v = (h @ w["wv"]).reshape(T, kv_heads, head_dim)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)   # each kv head serves `group` q heads
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(head_dim)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(T, heads * head_dim) @ w["wo"]
    h = rms_norm(x, w["mlp_norm"], norm_eps)
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def forward_logits(config, params, tokens, positions):
    """Logits (len(positions), vocab) of the full forward pass over
    ``tokens``, from parameters in the engine's tree layout (stacked by
    layer), converted to float32 a layer at a time."""
    kw = dict(
        heads=config.heads, kv_heads=config.kv_heads,
        head_dim=config.head_dim, rope_theta=config.rope_theta,
        norm_eps=config.norm_eps,
    )
    step = jax.jit(lambda x, w: layer(x, w, **kw))
    take = jax.jit(lambda t, i: jax.tree.map(lambda a: a[i], t))
    with jax.default_matmul_precision("highest"):
        embed = params["embed"]
        ids = jnp.asarray(tokens)
        if hasattr(embed, "q"):
            x = embed.q[ids].astype(jnp.float32) * embed.s[ids].astype(jnp.float32)
        else:
            x = embed[ids].astype(jnp.float32)
        for i in range(config.layers):
            w = jax.tree.map(
                to_f32, take(params["layers"], i),
                is_leaf=lambda t: hasattr(t, "q") and hasattr(t, "s"),
            )
            x = step(x, w)
            x.block_until_ready()   # one layer's float32 weights alive at a time
            del w
        x = rms_norm(x[jnp.asarray(positions)], to_f32(params["final_norm"]),
                     config.norm_eps)
        return np.asarray(x @ to_f32(params["lm_head"]))


def compare(got: np.ndarray, want: np.ndarray, tolerance: dict) -> dict:
    """Per compared position: RMS error over the vocabulary as a share of
    the reference's spread, and the correlation of the two vectors, held to
    ``tolerance`` (``rms_share``, ``min_correlation``)."""
    rows = []
    for g, w in zip(got, want):
        rms = float(np.sqrt(np.mean((g - w) ** 2)) / np.std(w))
        corr = float(np.corrcoef(g, w)[0, 1])
        rows.append({"rms_share": rms, "correlation": corr})
    worst_rms = max(r["rms_share"] for r in rows)
    worst_corr = min(r["correlation"] for r in rows)
    return {
        "positions": rows, "worst_rms_share": worst_rms,
        "worst_correlation": worst_corr,
        "passed": bool(
            worst_rms <= tolerance["rms_share"]
            and worst_corr >= tolerance["min_correlation"]
        ),
        "tolerance": dict(tolerance),
    }


def check_engine(engine, seed: int, tolerance: dict, *,
                 prompt_tokens: int = CHECK_PROMPT_TOKENS,
                 steps: int = CHECK_DECODE_STEPS) -> dict:
    """The served model against the reference, outside any window.

    Uses the engine's parameters, its model configuration, its pool type
    (int8 or not) and the read kernel it selected; the pool here is a small
    scratch one of the same block size, so the engine's own pool and block
    manager are not touched."""
    from langstream_tpu.models.llama_paged import (
        llama_decode_chunk_paged,
        llama_prefill_paged,
    )
    from langstream_tpu.models.paged import (
        PagedLayout,
        init_paged_kv_cache,
        init_paged_kv_cache_int8,
    )

    c, cfg = engine.model_config, engine.config
    bs = cfg.kv_block_size
    per_slot = -(-(prompt_tokens + steps + 1) // bs)
    layout = PagedLayout(block_size=bs, num_blocks=per_slot + 1,
                         max_blocks_per_slot=per_slot)
    init = init_paged_kv_cache_int8 if cfg.kv_quantize == "int8" \
        else init_paged_kv_cache
    pool_k, pool_v = init(c, layout)
    # block 0 stays unused, as in the engine's pool
    tables = jnp.arange(1, per_slot + 1, dtype=jnp.int32)[None, :]
    rng = np.random.default_rng(int(seed) % (2 ** 32))
    prompt = rng.integers(0, c.vocab_size, size=prompt_tokens, dtype=np.int32)
    lengths = jnp.asarray([prompt_tokens], dtype=jnp.int32)

    prefill = jax.jit(
        lambda p, t, n, pk, pv, tb: llama_prefill_paged(c, p, t, n, pk, pv, tb)
    )
    logits0, pool_k, pool_v = prefill(
        engine.params, jnp.asarray(prompt)[None, :], lengths, pool_k, pool_v,
        tables,
    )
    first = jnp.argmax(logits0, axis=-1).astype(jnp.int32)  # (1,)

    def greedy_with_logits(logits, key):
        # the scan stacks the second output per step: the full logits
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits

    kernel = engine.paged_read_kernel
    decode = jax.jit(
        lambda p, t0, n, pk, pv, tb, key: llama_decode_chunk_paged(
            c, p, t0, n, jnp.ones((1,), dtype=bool), pk, pv, tb,
            greedy_with_logits, key, steps, per_slot, kernel=kernel,
        )
    )
    out = decode(engine.params, first, lengths, pool_k, pool_v, tables,
                 jax.random.PRNGKey(0))
    chunk_tokens, chunk_logits = np.asarray(out[0]), np.asarray(out[1])
    # the sequence the engine actually produced, for the reference to follow
    generated = [int(first[0])] + [int(t) for t in chunk_tokens[:-1, 0]]
    sequence = np.concatenate([prompt, np.asarray(generated, dtype=np.int32)])
    got = np.concatenate(
        [np.asarray(logits0, dtype=np.float32), chunk_logits[:, 0, :]]
    )
    positions = list(range(prompt_tokens - 1, prompt_tokens + steps))
    want = forward_logits(c, engine.params, sequence, positions)
    report = compare(got, want, tolerance)
    report.update({
        "prompt_tokens": prompt_tokens, "decode_steps": steps,
        "kernel": kernel, "kv_quantize": cfg.kv_quantize,
        "quantize": cfg.quantize,
    })
    return report
