"""Llama-family decoder, pure JAX, TPU-first.

Design choices (vs. a torch port):
- **Stacked layer params + ``lax.scan``**: one compiled layer body instead of
  N inlined layers — faster compiles, identical runtime (XLA unrolls DMA
  pipelining itself).
- **bfloat16 weights/activations, float32 softmax+norms**: MXU-native.
- **GQA attention via grouped einsum** — no KV head replication, so the KV
  cache stays small and HBM-bandwidth-friendly.
- **Static shapes everywhere**: prefill pads to length buckets; decode is a
  fixed (slots,) batch. No data-dependent control flow inside jit.
- **TP sharding rules** (Megatron-style, over the ``tp`` mesh axis):
  attention QKV and MLP up/gate are column-sharded, attention out and MLP
  down row-sharded; XLA inserts the psums on ICI. KV cache shards on the KV
  head axis; batch (slots) shards on ``dp``.

The dense-cache functions here (:func:`init_kv_cache`, :func:`kv_cache_spec`,
:func:`llama_prefill`, :func:`llama_decode_step`, :func:`llama_decode_chunk`)
have no production caller since PR 29: the serving engine runs the paged
programs of :mod:`langstream_tpu.models.llama_paged` only. They stay as the
plain reference the paged programs are tested against (``tests/test_paged.py``,
``test_kv_int8.py``, ``test_golden*.py``, ``test_moe_serving.py``) and that
``__graft_entry__.py`` compiles.

Capability parity: this is the model behind ``ai-chat-completions`` /
``ai-text-completions`` (reference: ``ChatCompletionsStep.java`` calling
OpenAI etc. — here the model is local).
"""

from __future__ import annotations

import dataclasses
import math
import os
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from langstream_tpu.models.kvquant import (
    cache_scores,
    cache_seq_len,
    cache_slice_window,
    cache_values,
    cache_write_rows,
    is_quant_cache,
    quantize_rows,
)
from langstream_tpu.models.quant import as_weight as _w, embedding_take


def _flash_mode(seq_len: int) -> str | None:
    """Whether prefill attention should use the Pallas flash kernel.

    ``LS_TPU_FLASH``: ``auto`` (default — compiled kernel on TPU for
    long-enough sequences), ``1``/``0`` force on/off, ``interpret`` runs the
    kernel in interpreter mode (CPU tests only: refused on a TPU backend).
    """
    env = os.environ.get("LS_TPU_FLASH", "auto").lower()
    if env == "interpret":
        if jax.default_backend() == "tpu":
            raise ValueError(
                "LS_TPU_FLASH=interpret runs the flash kernel in the Pallas "
                "interpreter, which exists for CPU tests; on a TPU use "
                "auto, 1 or 0"
            )
        return "interpret"
    if env in ("1", "true", "on"):
        return "compiled"
    if env in ("0", "false", "off"):
        return None
    return (
        "compiled"
        if jax.default_backend() == "tpu" and seq_len >= 512
        else None
    )


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden: int = 2048
    layers: int = 16
    heads: int = 16
    kv_heads: int = 8
    head_dim: int = 128
    intermediate: int = 5632
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16

    @classmethod
    def llama3_8b(cls, max_seq_len: int = 8192) -> "LlamaConfig":
        return cls(
            vocab_size=128256, hidden=4096, layers=32, heads=32, kv_heads=8,
            head_dim=128, intermediate=14336, rope_theta=500000.0,
            max_seq_len=max_seq_len,
        )

    @classmethod
    def llama3_70b(cls, max_seq_len: int = 8192) -> "LlamaConfig":
        return cls(
            vocab_size=128256, hidden=8192, layers=80, heads=64, kv_heads=8,
            head_dim=128, intermediate=28672, rope_theta=500000.0,
            max_seq_len=max_seq_len,
        )

    @classmethod
    def llama_1b(cls, max_seq_len: int = 2048) -> "LlamaConfig":
        """~1.2B params — the per-chip share of Llama-3-8B under TP8, used as
        the single-chip benchmark proxy (BASELINE.md config #2/#5)."""
        return cls(
            vocab_size=32000, hidden=2048, layers=16, heads=16, kv_heads=8,
            head_dim=128, intermediate=5632, max_seq_len=max_seq_len,
        )

    @classmethod
    def tiny(cls, max_seq_len: int = 128) -> "LlamaConfig":
        """Test-size config (CPU-mesh tests, dry runs). Vocab covers the
        byte-level tokenizer (256 bytes + specials)."""
        return cls(
            vocab_size=384, hidden=64, layers=2, heads=4, kv_heads=2,
            head_dim=16, intermediate=128, max_seq_len=max_seq_len,
        )


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_llama_params(config: LlamaConfig, key: jax.Array | None = None) -> dict:
    """Random-init params (stacked per-layer leading dim L)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    c = config
    keys = jax.random.split(key, 10)
    qkv_dim = c.heads * c.head_dim
    kv_dim = c.kv_heads * c.head_dim

    def norm_init(*shape):
        return jnp.ones(shape, dtype=c.dtype)

    def w_init(k, *shape, fan_in):
        scale = 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(k, shape, dtype=jnp.float32) * scale).astype(c.dtype)

    L = c.layers
    return {
        "embed": w_init(keys[0], c.vocab_size, c.hidden, fan_in=c.hidden),
        "layers": {
            "attn_norm": norm_init(L, c.hidden),
            "wq": w_init(keys[1], L, c.hidden, qkv_dim, fan_in=c.hidden),
            "wk": w_init(keys[2], L, c.hidden, kv_dim, fan_in=c.hidden),
            "wv": w_init(keys[3], L, c.hidden, kv_dim, fan_in=c.hidden),
            "wo": w_init(keys[4], L, qkv_dim, c.hidden, fan_in=qkv_dim),
            "mlp_norm": norm_init(L, c.hidden),
            "w_gate": w_init(keys[5], L, c.hidden, c.intermediate, fan_in=c.hidden),
            "w_up": w_init(keys[6], L, c.hidden, c.intermediate, fan_in=c.hidden),
            "w_down": w_init(keys[7], L, c.intermediate, c.hidden, fan_in=c.intermediate),
        },
        "final_norm": norm_init(c.hidden),
        "lm_head": w_init(keys[8], c.hidden, c.vocab_size, fan_in=c.hidden),
    }


def llama_param_specs(config: LlamaConfig) -> dict:
    """PartitionSpecs per param (Megatron TP over axis ``tp``)."""
    return {
        "embed": P("tp", None),          # vocab-sharded
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, "tp"),   # column (heads)
            "wk": P(None, None, "tp"),
            "wv": P(None, None, "tp"),
            "wo": P(None, "tp", None),   # row
            "mlp_norm": P(None, None),
            "w_gate": P(None, None, "tp"),
            "w_up": P(None, None, "tp"),
            "w_down": P(None, "tp", None),
        },
        "final_norm": P(None),
        "lm_head": P(None, "tp"),        # vocab-sharded logits
    }


def shard_llama_params(params: dict, config: LlamaConfig, mesh: Mesh) -> dict:
    specs = llama_param_specs(config)
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def kv_cache_spec(mesh_axes: tuple[str, ...]) -> P:
    """Cache (L, slots, S, kv_heads, head_dim): slots on dp, kv heads on tp."""
    dp = "dp" if "dp" in mesh_axes else None
    tp = "tp" if "tp" in mesh_axes else None
    return P(None, dp, None, tp, None)


def init_kv_cache(
    config: LlamaConfig, slots: int, max_seq_len: int | None = None
) -> tuple[jax.Array, jax.Array]:
    c = config
    seq = max_seq_len or c.max_seq_len
    shape = (c.layers, slots, seq, c.kv_heads, c.head_dim)
    return jnp.zeros(shape, dtype=c.dtype), jnp.zeros(shape, dtype=c.dtype)


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def _rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * w


def _rope(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for the given positions: (..., head_dim//2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(angles), jnp.sin(angles)


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """``(dim / 2,)`` float32 inverse frequencies of YaRN over a rotary
    width of ``dim``: ``1/theta_i`` where a dimension turns more than
    ``beta_fast`` times over the original length, ``1/(factor theta_i)``
    where it turns less than ``beta_slow`` times, a linear ramp between the
    two correction dimensions (the first rounded down, the second up)."""
    half = dim // 2
    thetas = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip(
        (np.arange(half) - low) / ((high if high != low else high + 0.001)
                                   - low), 0, 1)
    return ((1 / (factor * thetas)) * ramp
            + (1 / thetas) * (1 - ramp)).astype(np.float32)


def _apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., heads, head_dim); cos/sin broadcast over the heads axis."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(
        x.dtype
    )


def _swiglu(x, w_gate, w_up, w_down):
    gate = jax.nn.silu(jnp.einsum("...h,hi->...i", x, _w(w_gate)))
    up = jnp.einsum("...h,hi->...i", x, _w(w_up))
    return jnp.einsum("...i,ih->...h", gate * up, _w(w_down))


def _default_ffn(h, lp, valid=None):
    """The dense SwiGLU FFN sub-block. ``ffn`` hooks on the forward/prefill/
    decode entry points default to this; the MoE family swaps in its routed
    expert FFN (models/moe.py) and reuses every attention/cache path here.
    ``valid`` marks real positions — pointwise FFNs ignore it, routed ones
    must not let pad/inactive positions consume expert capacity."""
    return _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])


def attention_block(config, x, lp, cos, sin, attention):
    """Pre-norm attention sub-block + residual: the piece shared verbatim by
    the dense, MoE, and pipeline-stage forwards (they differ only in FFN and
    sharding hooks). ``config`` needs heads/kv_heads/head_dim/norm_eps — both
    LlamaConfig and MoEConfig qualify."""
    c = config
    B, S = x.shape[0], x.shape[1]
    h = _rms_norm(x, lp["attn_norm"], c.norm_eps)
    q = jnp.einsum("bph,hd->bpd", h, _w(lp["wq"])).reshape(B, S, c.heads, c.head_dim)
    k = jnp.einsum("bph,hd->bpd", h, _w(lp["wk"])).reshape(B, S, c.kv_heads, c.head_dim)
    v = jnp.einsum("bph,hd->bpd", h, _w(lp["wv"])).reshape(B, S, c.kv_heads, c.head_dim)
    q = _apply_rope(q, cos, sin)
    k = _apply_rope(k, cos, sin)
    out = attention(q, k, v).reshape(B, S, c.heads * c.head_dim)
    return x + jnp.einsum("bpd,dh->bph", out, _w(lp["wo"]))


# ---------------------------------------------------------------------------
# batched ragged LoRA (Punica/S-LoRA-style adapter gather)
# ---------------------------------------------------------------------------


def lora_delta(h: jax.Array, ids: jax.Array, a: jax.Array, b: jax.Array):
    """Per-slot low-rank delta ``h @ A[id] @ B[id]`` for one projection.

    ``a``/``b`` are one layer's slices of the stacked adapter buffers —
    ``(n_rows, d_in, rank)`` / ``(n_rows, rank, d_out)`` — and ``ids``
    is the per-slot ``(B,)`` int32 row index. Row 0 is all-zeros, so
    adapter-less slots compute the base model exactly; heterogeneous-
    adapter batches stay ONE jitted program (the gather is data, not
    structure — no per-adapter recompiles). The LoRA alpha/rank scale
    is folded into B at publish time (serving/adapters.py)."""
    a_sel = jnp.take(a, ids, axis=0)  # (B, d_in, rank)
    b_sel = jnp.take(b, ids, axis=0)  # (B, rank, d_out)
    if h.ndim == 2:
        t = jnp.einsum("bh,bhr->br", h, a_sel)
        return jnp.einsum("br,bro->bo", t, b_sel)
    t = jnp.einsum("bph,bhr->bpr", h, a_sel)
    return jnp.einsum("bpr,bro->bpo", t, b_sel)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def prefill_forward(
    config: LlamaConfig,
    params: dict,
    tokens: jax.Array,       # (B, P) int32, right-padded
    lengths: jax.Array,      # (B,) true lengths
    use_flash: bool | None = None,
    mesh: Mesh | None = None,  # flash under a mesh runs via shard_map
    ffn=None,                # (h (B,P,H), lp, valid=None) -> (B,P,H);
                             # default dense SwiGLU
    adapters: dict | None = None,  # {"ids": (B,) int32, "layers":
                             # {wq_a (L,N,H,r), wq_b (L,N,r,qd), ...}} —
                             # None keeps the seed jaxpr untouched
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Shared prompt forward (the single source of the prefill layer math):
    returns (last-token logits (B,V), ks, vs) where ks/vs are the roped
    per-layer K/V ``(L, B, P, Kh, D)`` for the caller's cache layout —
    dense (:func:`llama_prefill`) or paged (``llama_prefill_paged``)."""
    c = config
    if ffn is None:
        ffn = _default_ffn
    B, Pn = tokens.shape
    with jax.named_scope("embed"):
        x = embedding_take(params["embed"], tokens)  # (B, P, H)
    with jax.named_scope("attn_qkv"):
        positions = jnp.arange(Pn)[None, :].repeat(B, axis=0)
        cos, sin = _rope(positions, c.head_dim, c.rope_theta)
    # causal + padding mask: (B, 1, P, P)
    q_idx = jnp.arange(Pn)[:, None]
    k_idx = jnp.arange(Pn)[None, :]
    causal = q_idx >= k_idx
    valid = k_idx < lengths[:, None, None]  # (B, 1, P) keys within length
    mask = causal[None, :, :] & valid
    # (B, P) real-token mask for the FFN hook: routed (MoE) FFNs must not
    # let right-padding consume expert capacity
    pos_valid = jnp.arange(Pn)[None, :] < lengths[:, None]
    neg = jnp.finfo(jnp.float32).min

    flash = _flash_mode(Pn) if use_flash is None else ("compiled" if use_flash else None)

    # Sequence-parallel prefill: with an ``sp`` axis in the mesh the prompt's
    # sequence dimension shards over it and attention runs as a ring
    # collective (ppermute K/V rotation + online softmax, parallel/ring.py).
    # This is the long-context serving path: prefill FLOPs and activation
    # memory split ~sp-ways (the KV cache itself stays in the engine's
    # dp/tp layout — decode is unchanged). Takes priority over the Pallas
    # flash kernel, which keeps the sequence resident per device.
    sp_ring = (
        mesh is not None
        and "sp" in mesh.axis_names
        and mesh.shape["sp"] > 1
        and Pn % mesh.shape["sp"] == 0
    )
    if sp_ring:
        # degrade per-axis like the flash path: a batch that doesn't divide
        # dp (e.g. one queued request on a dp>1 mesh) replicates over dp
        # instead of crashing the prefill; heads that don't divide tp stay
        # unsharded in the ring
        sp_dp = (
            "dp"
            if "dp" in mesh.axis_names and B % mesh.shape["dp"] == 0
            else None
        )
        sp_tp = (
            "tp"
            if "tp" in mesh.axis_names
            and c.kv_heads % mesh.shape["tp"] == 0
            and c.heads % mesh.shape["tp"] == 0
            else None
        )
        x_spec = NamedSharding(mesh, P(sp_dp, "sp", None))
        x = jax.lax.with_sharding_constraint(x, x_spec)

    def layer(carry, layer_in):
        x = carry
        if adapters is None:
            lp = layer_in
        else:
            lp, al = layer_in
        with jax.named_scope("attn_qkv"):
            h = _rms_norm(x, lp["attn_norm"], c.norm_eps)
            q = jnp.einsum("bph,hd->bpd", h, _w(lp["wq"]))
            k = jnp.einsum("bph,hd->bpd", h, _w(lp["wk"]))
            v = jnp.einsum("bph,hd->bpd", h, _w(lp["wv"]))
            if adapters is not None:
                ids = adapters["ids"]
                q = q + lora_delta(h, ids, al["wq_a"], al["wq_b"])
                k = k + lora_delta(h, ids, al["wk_a"], al["wk_b"])
                v = v + lora_delta(h, ids, al["wv_a"], al["wv_b"])
            q = q.reshape(B, Pn, c.heads, c.head_dim)
            k = k.reshape(B, Pn, c.kv_heads, c.head_dim)
            v = v.reshape(B, Pn, c.kv_heads, c.head_dim)
            q = _apply_rope(q, cos, sin)
            k = _apply_rope(k, cos, sin)
        if sp_ring:
            with jax.named_scope("kv_read"):
                # causality alone hides right-padded keys from every real query
                # row (padded rows sit after all real rows); their outputs are
                # garbage the caller discards, their cache rows are overwritten
                # before ever being attended to (same argument as flash below)
                from langstream_tpu.parallel.ring import ring_attention

                out = ring_attention(
                    q, k, v, mesh, causal=True,
                    batch_axis=sp_dp, head_axis=sp_tp,
                )
                out = out.reshape(B, Pn, c.heads * c.head_dim)
        elif flash is not None:
            with jax.named_scope("flash"):
                # Pallas blocked attention: no (B,H,P,P) score matrix in HBM.
                # Causality alone hides right-padded keys from every real query
                # row; padded rows' outputs are garbage the caller discards.
                from langstream_tpu.ops.flash_attention import flash_attention

                out = flash_attention(
                    q, k, v, causal=True, interpret=(flash == "interpret"),
                    mesh=mesh,
                )
                out = out.reshape(B, Pn, c.heads * c.head_dim)
        else:
            with jax.named_scope("kv_read"):
                # grouped-query attention: heads = kv_heads * group
                G = c.heads // c.kv_heads
                qg = q.reshape(B, Pn, c.kv_heads, G, c.head_dim)
                scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
                scores = scores / math.sqrt(c.head_dim)
                scores = jnp.where(mask[:, None, None, :, :], scores, neg)
                probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
                out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
                out = out.reshape(B, Pn, c.heads * c.head_dim)
        with jax.named_scope("attn_out"):
            attn = jnp.einsum("bpd,dh->bph", out, _w(lp["wo"]))
            if adapters is not None:
                attn = attn + lora_delta(out, adapters["ids"], al["wo_a"], al["wo_b"])
            x = x + attn
        with jax.named_scope("ffn"):
            h2 = _rms_norm(x, lp["mlp_norm"], c.norm_eps)
            x = x + ffn(h2, lp, pos_valid)
        if sp_ring:
            x = jax.lax.with_sharding_constraint(x, x_spec)
        return x, (k, v)

    layer_xs = (
        params["layers"]
        if adapters is None
        else (params["layers"], adapters["layers"])
    )
    x, (ks, vs) = jax.lax.scan(layer, x, layer_xs)
    with jax.named_scope("lm_head"):
        x = _rms_norm(x, params["final_norm"], c.norm_eps)
        # logits for the last real token of each prompt
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].clip(0), axis=1
        ).squeeze(1)
        logits = jnp.einsum("bh,hv->bv", last, _w(params["lm_head"])).astype(jnp.float32)
    return logits, ks, vs


def llama_prefill(
    config: LlamaConfig,
    params: dict,
    tokens: jax.Array,       # (B, P) int32, right-padded
    lengths: jax.Array,      # (B,) true lengths
    cache_k: jax.Array,      # (L, slots, S, K, D)
    cache_v: jax.Array,
    slot_ids: jax.Array,     # (B,) which cache slots to fill
    use_flash: bool | None = None,  # None = auto (LS_TPU_FLASH)
    mesh: Mesh | None = None,  # kernel runs per-shard via shard_map
    ffn=None,                # pluggable FFN sub-block (MoE family hook)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Process prompts, fill the KV cache, return last-token logits (B, V).

    Only the first P rows of each slot are written; stale rows beyond are
    harmless — every decode read is masked to positions < length, and each
    new row is written before it is ever attended to.
    """
    Pn = tokens.shape[1]
    logits, ks, vs = prefill_forward(
        config, params, tokens, lengths, use_flash, mesh=mesh, ffn=ffn
    )
    idx = (slice(None), slot_ids, slice(None, Pn))
    new_k = cache_write_rows(cache_k, ks, idx)
    new_v = cache_write_rows(cache_v, vs, idx)
    return logits, new_k, new_v


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def llama_decode_step(
    config: LlamaConfig,
    params: dict,
    tokens: jax.Array,     # (B,) current token per slot
    lengths: jax.Array,    # (B,) tokens already in cache per slot
    cache_k: jax.Array,    # (L, B, S, K, D)
    cache_v: jax.Array,
    ffn=None,              # (h (B,H), lp, valid=None) -> (B,H); default SwiGLU
    active: jax.Array | None = None,  # (B,) bool — forwarded to the FFN hook
                                      # so routed (MoE) FFNs don't let dead
                                      # slots consume expert capacity
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step for every slot; returns logits (B, V) + new caches.

    The new K/V is written at position ``lengths`` per slot; attention spans
    positions 0..lengths inclusive. Inactive slots produce garbage logits
    the engine ignores (no dynamic shapes) — but with a routed FFN pass
    ``active`` too, or dead slots' garbage competes for expert capacity.
    """
    c = config
    if ffn is None:
        ffn = _default_ffn
    if active is None:
        active = jnp.ones(tokens.shape[0], dtype=bool)
    B = tokens.shape[0]
    S = cache_seq_len(cache_k)
    with jax.named_scope("embed"):
        x = embedding_take(params["embed"], tokens)  # (B, H)
    with jax.named_scope("attn_qkv"):
        cos, sin = _rope(lengths, c.head_dim, c.rope_theta)  # (B, half)
    k_idx = jnp.arange(S)[None, :]
    key_mask = k_idx <= lengths[:, None]  # (B, S)
    neg = jnp.finfo(jnp.float32).min
    G = c.heads // c.kv_heads
    batch_idx = jnp.arange(B)

    def layer(carry, layer_in):
        x = carry
        lp, ck_l, cv_l = layer_in
        with jax.named_scope("attn_qkv"):
            h = _rms_norm(x, lp["attn_norm"], c.norm_eps)
            q = (h @ _w(lp["wq"])).reshape(B, c.heads, c.head_dim)
            k = (h @ _w(lp["wk"])).reshape(B, c.kv_heads, c.head_dim)
            v = (h @ _w(lp["wv"])).reshape(B, c.kv_heads, c.head_dim)
            q = _apply_rope(q, cos, sin)
            k = _apply_rope(k, cos, sin)
            ck_l = cache_write_rows(ck_l, k, (batch_idx, lengths))
            cv_l = cache_write_rows(cv_l, v, (batch_idx, lengths))
        with jax.named_scope("kv_read"):
            qg = q.reshape(B, c.kv_heads, G, c.head_dim)
            scores = cache_scores(qg, ck_l) / math.sqrt(c.head_dim)
            scores = jnp.where(key_mask[:, None, None, :], scores, neg)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            out = cache_values(probs, cv_l)
            out = out.reshape(B, c.heads * c.head_dim)
        with jax.named_scope("attn_out"):
            x = x + out @ _w(lp["wo"])
        with jax.named_scope("ffn"):
            h2 = _rms_norm(x, lp["mlp_norm"], c.norm_eps)
            x = x + ffn(h2, lp, active)
        return x, (ck_l, cv_l)

    x, (new_k, new_v) = jax.lax.scan(
        layer, x, (params["layers"], cache_k, cache_v)
    )
    with jax.named_scope("lm_head"):
        x = _rms_norm(x, params["final_norm"], c.norm_eps)
        logits = (x @ _w(params["lm_head"])).astype(jnp.float32)
    return logits, new_k, new_v


def llama_decode_chunk(
    config: LlamaConfig,
    params: dict,
    tokens0: jax.Array,       # (B,) current token per slot
    base_lengths: jax.Array,  # (B,) tokens in cache at chunk start
    active: jax.Array,        # (B,) bool
    cache_k: jax.Array,       # (L, B, S, K, D) — READ-ONLY during the chunk
    cache_v: jax.Array,
    sample_fn,                # (logits, key) -> (tokens, logprobs)
    key: jax.Array,
    num_steps: int,
    window: int | None = None,  # static attention window: read only cache
                                # rows [0, window) — the host picks the
                                # smallest bucket covering max(base_lengths),
                                # so short sequences don't pay full-S HBM
                                # traffic (decode is cache-read bound)
    ffn=None,                   # (h (B,H), lp, valid=None) -> (B,H);
                                # default dense SwiGLU
    sample_extras=None,         # (presences, frequencies, counts0 (B, V)):
                                # penalty sampling — counts ride the step
                                # carry (each sampled token updates them);
                                # sample_fn is then called (logits, key,
                                # counts). None = plain (logits, key).
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """K fused decode steps with a two-segment KV layout.

    HBM discipline: the big cache is consumed read-only (no per-step
    rematerialisation); each step's new K/V lands in a small chunk buffer
    ``(L, B, num_steps, Kh, D)`` carried through the step scan AND the layer
    scan, which writes a step's rows of one layer into it in place; a single
    commit writes the buffer back into the cache at the end. Attention spans
    [cache rows < base_len] ∪ [buffer rows ≤ step]. Per-step HBM traffic is
    params + cache *read* only — the difference between ~1k and ~10k tok/s.

    Returns (chunk_tokens (K,B), chunk_logprobs (K,B), final_tokens,
    final_lengths, cache_k, cache_v) with the buffer committed.
    """
    c = config
    if ffn is None:
        ffn = _default_ffn
    B = tokens0.shape[0]
    full_k, full_v = cache_k, cache_v
    if window is not None and window < cache_seq_len(cache_k):
        # static slice: XLA reads only these rows; the commit below still
        # targets the full cache (valid because base_lengths < window)
        cache_k = cache_slice_window(cache_k, window)
        cache_v = cache_slice_window(cache_v, window)
    S = cache_seq_len(cache_k)
    G = c.heads // c.kv_heads
    adv = active.astype(jnp.int32)
    neg = jnp.finfo(jnp.float32).min
    cache_mask = (jnp.arange(S)[None, :] < base_lengths[:, None])  # (B, S) static per chunk
    kbuf0 = jnp.zeros((c.layers, B, num_steps, c.kv_heads, c.head_dim), c.dtype)
    vbuf0 = jnp.zeros_like(kbuf0)
    pen = sample_extras is not None
    counts0 = sample_extras[2] if pen else None

    def step(carry, step_idx):
        if pen:
            tokens, kbuf, vbuf, key, counts = carry
        else:
            tokens, kbuf, vbuf, key = carry
            counts = None
        with jax.named_scope("sample"):
            key, sub = jax.random.split(key)
        with jax.named_scope("embed"):
            x = embedding_take(params["embed"], tokens)  # (B, H)
        with jax.named_scope("attn_qkv"):
            positions = base_lengths + step_idx * adv
            cos, sin = _rope(positions, c.head_dim, c.rope_theta)
            buf_mask = (jnp.arange(num_steps)[None, :] <= step_idx)  # (1, K)

        def layer(carry, layer_in):
            # the chunk buffer rides the carry and takes the step's rows in
            # place (as xs/ys the stacked ys is a new array every step)
            x, kbuf, vbuf = carry
            lp, ck_l, cv_l, kv_l = layer_in
            with jax.named_scope("attn_qkv"):
                h = _rms_norm(x, lp["attn_norm"], c.norm_eps)
                q = (h @ _w(lp["wq"])).reshape(B, c.heads, c.head_dim)
                k = (h @ _w(lp["wk"])).reshape(B, c.kv_heads, c.head_dim)
                v = (h @ _w(lp["wv"])).reshape(B, c.kv_heads, c.head_dim)
                q = _apply_rope(q, cos, sin)
                k = _apply_rope(k, cos, sin)
                kbuf = jax.lax.dynamic_update_slice(
                    kbuf, k[None, :, None], (kv_l, 0, step_idx, 0, 0)
                )
                vbuf = jax.lax.dynamic_update_slice(
                    vbuf, v[None, :, None], (kv_l, 0, step_idx, 0, 0)
                )
                # the layer's slice, read after the write: the step's own
                # row is in it
                kbuf_l = jax.lax.dynamic_index_in_dim(kbuf, kv_l, keepdims=False)
                vbuf_l = jax.lax.dynamic_index_in_dim(vbuf, kv_l, keepdims=False)
            with jax.named_scope("kv_read"):
                qg = q.reshape(B, c.kv_heads, G, c.head_dim)
                s_cache = cache_scores(qg, ck_l)
                s_buf = jnp.einsum("bkgd,btkd->bkgt", qg, kbuf_l).astype(jnp.float32)
                scale = 1.0 / math.sqrt(c.head_dim)
                s_cache = jnp.where(
                    cache_mask[:, None, None, :], s_cache * scale, neg
                )
                s_buf = jnp.where(buf_mask[:, None, None, :], s_buf * scale, neg)
                s_all = jnp.concatenate([s_cache, s_buf], axis=-1)
                probs = jax.nn.softmax(s_all, axis=-1).astype(x.dtype)
                p_cache, p_buf = probs[..., :S], probs[..., S:]
                out = cache_values(p_cache, cv_l) + jnp.einsum(
                    "bkgt,btkd->bkgd", p_buf, vbuf_l
                )
                out = out.reshape(B, c.heads * c.head_dim)
            with jax.named_scope("attn_out"):
                x = x + out @ _w(lp["wo"])
            with jax.named_scope("ffn"):
                h2 = _rms_norm(x, lp["mlp_norm"], c.norm_eps)
                x = x + ffn(h2, lp, active)
            return (x, kbuf, vbuf), None

        (x, kbuf, vbuf), _ = jax.lax.scan(
            layer, (x, kbuf, vbuf),
            (params["layers"], cache_k, cache_v, jnp.arange(c.layers)),
        )
        with jax.named_scope("lm_head"):
            x = _rms_norm(x, params["final_norm"], c.norm_eps)
            logits = (x @ _w(params["lm_head"])).astype(jnp.float32)
        with jax.named_scope("sample"):
            if pen:
                nxt, lp = sample_fn(logits, sub, counts)
            else:
                nxt, lp = sample_fn(logits, sub)
            nxt = jnp.where(active, nxt, tokens)
        if pen:
            counts = counts.at[jnp.arange(B), nxt].add(adv)
            return (nxt, kbuf, vbuf, key, counts), (nxt, lp)
        return (nxt, kbuf, vbuf, key), (nxt, lp)

    carry0 = (
        (tokens0, kbuf0, vbuf0, key, counts0)
        if pen
        else (tokens0, kbuf0, vbuf0, key)
    )
    out_carry, (chunk_tokens, chunk_lps) = jax.lax.scan(
        step, carry0, jnp.arange(num_steps)
    )
    final_tokens, kbuf, vbuf = out_carry[0], out_carry[1], out_carry[2]

    # commit: one write of the chunk buffer into the cache per slot. The
    # buffer stays bf16 through the scan (it is tiny and re-read every
    # step); an int8 cache quantises it once here.
    def commit_leaf(full_leaf, buf_leaf):
        def commit_lb(c_lb, b_lb, start):  # (S, ...), (num_steps, ...)
            return jax.lax.dynamic_update_slice(
                c_lb, b_lb, (start,) + (0,) * (c_lb.ndim - 1)
            )

        f = jax.vmap(  # over layers
            jax.vmap(commit_lb, in_axes=(0, 0, 0)), in_axes=(0, 0, None)
        )
        return f(full_leaf, buf_leaf, base_lengths)

    if is_quant_cache(full_k):
        out_k = jax.tree.map(commit_leaf, full_k, quantize_rows(kbuf))
        out_v = jax.tree.map(commit_leaf, full_v, quantize_rows(vbuf))
    else:
        out_k = commit_leaf(full_k, kbuf)
        out_v = commit_leaf(full_v, vbuf)
    final_lengths = base_lengths + num_steps * adv
    return chunk_tokens, chunk_lps, final_tokens, final_lengths, out_k, out_v


def llama_forward(
    config: LlamaConfig,
    params: dict,
    tokens: jax.Array,  # (B, S) int32
    *,
    attention=None,   # (q (B,S,H,D), k, v (B,S,Kh,D)) -> (B,S,H,D); default
                      # dense causal GQA — callers swap in ring/Ulysses
    constrain=None,   # applied to activations after embed and each layer
) -> jax.Array:
    """All-position logits (B, S, V), no KV cache — the training-side
    forward (next-token loss) and the long-context prefill building block.

    One transformer body serves the dense and the sequence-parallel paths:
    they differ only in the ``attention`` callback and the activation
    ``constrain`` hook (see :func:`llama_forward_sp`).
    """
    c = config
    B, S = tokens.shape
    if attention is None:
        from langstream_tpu.parallel.ring import dense_attention

        attention = partial(
            dense_attention, causal=True, scale=1.0 / math.sqrt(c.head_dim)
        )
    if constrain is None:
        constrain = lambda x: x  # noqa: E731
    x = constrain(embedding_take(params["embed"], tokens))
    positions = jnp.arange(S)[None, :].repeat(B, axis=0)
    cos, sin = _rope(positions, c.head_dim, c.rope_theta)

    def layer(x, lp):
        x = attention_block(c, x, lp, cos, sin, attention)
        h2 = _rms_norm(x, lp["mlp_norm"], c.norm_eps)
        x = x + _swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"])
        return constrain(x), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms_norm(x, params["final_norm"], c.norm_eps)
    return jnp.einsum("bsh,hv->bsv", x, _w(params["lm_head"])).astype(jnp.float32)


def llama_forward_sp(
    config: LlamaConfig,
    params: dict,
    tokens: jax.Array,  # (B, S) int32, S divisible by the sp axis size
    mesh: Mesh,
    attn: str = "ring",
) -> jax.Array:
    """Sequence-parallel long-context forward: activations sharded on the
    ``sp`` mesh axis end to end; attention runs as a collective over ICI —
    ring attention (``ppermute`` K/V rotation + online softmax) or Ulysses
    (all-to-all head re-sharding). See :mod:`langstream_tpu.parallel.ring`.

    This is the context-parallel path for sequences that exceed one chip's
    HBM: per-device activation memory is ``S/sp``, and the full ``S×S``
    score matrix never materialises.
    """
    from langstream_tpu.parallel.ring import ring_attention, ulysses_attention

    attn_fn = {"ring": ring_attention, "ulysses": ulysses_attention}[attn]
    kwargs = {} if attn == "ulysses" else {"head_axis": "tp"}
    x_spec = NamedSharding(
        mesh, P("dp" if "dp" in mesh.axis_names else None, "sp", None)
    )
    return llama_forward(
        config, params, tokens,
        attention=lambda q, k, v: attn_fn(q, k, v, mesh, causal=True, **kwargs),
        constrain=lambda x: jax.lax.with_sharding_constraint(x, x_spec),
    )


def param_count(config: LlamaConfig) -> int:
    c = config
    per_layer = (
        c.hidden * c.heads * c.head_dim
        + 2 * c.hidden * c.kv_heads * c.head_dim
        + c.heads * c.head_dim * c.hidden
        + 3 * c.hidden * c.intermediate
        + 2 * c.hidden
    )
    return c.layers * per_layer + 2 * c.vocab_size * c.hidden + c.hidden
