"""int8 KV rows — the decode-bandwidth lever.

The row arithmetic (:func:`quantize_rows`, :func:`cache_scores`,
:func:`cache_values`) is shared by the paged int8 pool the engine serves
(:mod:`langstream_tpu.models.paged`, ``ops/paged_attention.py``); the
decode read's two products over a gathered window, bf16 or int8, are
:func:`window_scores` and :func:`window_values`. The
dense-cache helpers (:func:`init_kv_cache_int8`, :func:`cache_write_rows`,
:func:`cache_slice_window`, ...) serve the dense reference in
:mod:`langstream_tpu.models.llama`, which only tests call since PR 29.

Decode throughput is bounded by HBM reads of weights + the KV window
(serving/profiling.py roofline); at serving shapes the KV window is the
larger term. Per-row absmax int8 (one f32 scale per (position, kv-head)
row) halves that traffic at ~1e-2 relative error on attention logits.

TPU-first read path — the dequantisation never materialises a bf16 cache:

- **Scores**: the scale is constant along the contracted ``head_dim``, so
  ``q . dequant(k)`` == ``(q . k_int8) * scale`` — the int8→bf16 convert
  fuses into the dot operand and the scale multiplies the (small) score
  tensor.
- **Values**: the scale varies along the contracted ``seq`` axis, so it
  folds into the (small) probability tensor instead:
  ``probs . dequant(v)`` == ``(probs * scale) . v_int8``.

Cache representation: ``{"q": int8 (L, B, S, K, D), "s": f32 (L, B, S, K)}``
— a pytree that flows through jit/scan/donation/sharding like the plain
bf16 array it replaces. Write sites (prefill row fill, decode-chunk commit, single-step
write) quantise; prefill's own attention runs on the fresh bf16 K/V it
just computed, so quantisation error only enters through cross-step
cache reads.

Reference anchor: the reference has no serving engine at all (models are
SaaS HTTP calls, SURVEY §2.6) — this is net-new TPU capability on the
path of `ai-chat-completions`.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def is_quant_cache(cache: Any) -> bool:
    return isinstance(cache, dict) and "q" in cache and "s" in cache


def cache_seq_len(cache: Any) -> int:
    """Sequence-axis size of a dense cache in either layout."""
    return (cache["q"] if is_quant_cache(cache) else cache).shape[2]


def cache_slice_window(cache: Any, window: int) -> Any:
    """Static window slice over the sequence axis (axis 2 in both the
    (L,B,S,K,D) data and (L,B,S,K) scale leaves)."""
    slc = lambda a: jax.lax.slice_in_dim(a, 0, window, axis=2)
    return jax.tree.map(slc, cache) if is_quant_cache(cache) else slc(cache)


def quantize_rows(x: jax.Array) -> dict[str, jax.Array]:
    """Per-row absmax int8 over the trailing ``head_dim`` axis.

    ``x``: (..., D) bf16/f32 → {"q": int8 (..., D), "s": f32 (...,)}.
    """
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(xf / scale[..., None]), -127.0, 127.0
    ).astype(jnp.int8)
    return {"q": q, "s": scale}


def dequantize_rows(cache: dict[str, jax.Array], dtype=jnp.bfloat16) -> jax.Array:
    """Reference-path dequantisation (tests / debugging — the serving read
    path never calls this; it fuses the scales into scores/probs)."""
    return (
        cache["q"].astype(jnp.float32) * cache["s"][..., None]
    ).astype(dtype)


def init_kv_cache_int8(
    config, slots: int, max_seq_len: int | None = None
) -> tuple[dict[str, jax.Array], dict[str, jax.Array]]:
    """Zeroed int8 caches, same logical shape as :func:`init_kv_cache`."""
    c = config
    seq = max_seq_len or c.max_seq_len
    shape = (c.layers, slots, seq, c.kv_heads)
    make = lambda: {
        "q": jnp.zeros(shape + (c.head_dim,), dtype=jnp.int8),
        "s": jnp.zeros(shape, dtype=jnp.float32),
    }
    return make(), make()


def cache_write_rows(cache: Any, rows: jax.Array, index) -> Any:
    """Write bf16 ``rows`` into ``cache`` at ``index`` (an advanced-index
    tuple or slice over the leading cache axes), quantising when the cache
    is int8. Works for the plain-array cache too, so call sites stay
    layout-agnostic."""
    if not is_quant_cache(cache):
        return cache.at[index].set(rows.astype(cache.dtype))
    quant = quantize_rows(rows)
    return {
        "q": cache["q"].at[index].set(quant["q"]),
        "s": cache["s"].at[index].set(quant["s"]),
    }


def cache_scores(qg: jax.Array, ck_l: Any) -> jax.Array:
    """Attention scores of grouped queries against a cache layer slice.

    ``qg``: (B, K, G, D); ``ck_l``: (B, S, K, D) bf16 or int8 dict.
    Returns f32 (B, K, G, S) — unscaled by 1/sqrt(D) (caller applies)."""
    if not is_quant_cache(ck_l):
        return jnp.einsum("bkgd,bskd->bkgs", qg, ck_l).astype(jnp.float32)
    s = jnp.einsum(
        "bkgd,bskd->bkgs", qg, ck_l["q"].astype(qg.dtype)
    ).astype(jnp.float32)
    # scale is constant along D: factor it out of the dot
    return s * ck_l["s"].transpose(0, 2, 1)[:, :, None, :]


def cache_values(probs: jax.Array, cv_l: Any) -> jax.Array:
    """Value mix for a cache layer slice.

    ``probs``: (B, K, G, S) model dtype; ``cv_l``: (B, S, K, D) bf16 or
    int8 dict. Returns (B, K, G, D) in the probs dtype."""
    if not is_quant_cache(cv_l):
        return jnp.einsum("bkgs,bskd->bkgd", probs, cv_l)
    # scale varies along the contracted S axis: fold it into the probs
    scaled = (
        probs.astype(jnp.float32)
        * cv_l["s"].transpose(0, 2, 1)[:, :, None, :]
    ).astype(probs.dtype)
    return jnp.einsum(
        "bkgs,bskd->bkgd", scaled, cv_l["q"].astype(probs.dtype)
    )


def _own_head(kv_heads: int) -> jax.Array:
    """(1, Kh, 1, Kh, 1) bool: a kv head against itself, placed for the
    (B, Kh, *, Kh, *) views of the two window products."""
    return jnp.eye(kv_heads, dtype=bool)[None, :, None, :, None]


def window_scores(q: jax.Array, kw: Any, kv_heads: int) -> jax.Array:
    """Scores of one query a slot against a gathered window, in the layout
    the gather left it (``models/paged.py`` ``gather_kv``).

    ``q``: (B, H, D); ``kw``: (B, W, Kh*D) bf16 or the int8 pair ``{"q":
    (B, W, Kh*D), "s": (B, W, Kh)}``. Returns f32 (B, Kh, G, W), unscaled by
    1/sqrt(D) (the caller applies it).

    The window is contracted over ``Kh*D`` AS IT LIES, against a
    block-diagonal query (B, Kh*D, H): head h's D numbers in its own kv
    head's rows, zeros in the others' (they add nothing to a sum). Kh times
    the multiply-adds of a product a head, and no copy: splitting ``Kh*D``
    into a batch dimension of heads makes the compiler carry heads outward,
    two passes over a window of slots x rows x Kh*D bytes, where the
    product itself is bound by reading it once (ROADMAP S1). The int8 ->
    model-dtype convert rides in the product's operand; the scale is
    constant along D and multiplies the scores."""
    B, H, D = q.shape
    G = H // kv_heads
    quant = is_quant_cache(kw)
    data = kw["q"] if quant else kw
    W = data.shape[1]
    # (B, j, D, k, G): q[b, k, g, d] where j == k
    qt = q.reshape(B, kv_heads, G, D).transpose(0, 3, 1, 2)
    qbd = jnp.where(_own_head(kv_heads), qt[:, None], 0).reshape(
        B, kv_heads * D, H
    )
    s = jnp.einsum(
        "bwc,bch->bhw", data.astype(q.dtype), qbd,
        preferred_element_type=jnp.float32,
    ).reshape(B, kv_heads, G, W)
    if quant:
        s = s * kw["s"].transpose(0, 2, 1)[:, :, None, :]
    return s


def window_values(probs: jax.Array, vw: Any, kv_heads: int, dtype) -> jax.Array:
    """Value mix of a gathered window, in the layout the gather left it.

    ``probs``: f32 (B, Kh, G, W); ``vw``: (B, W, Kh*D) bf16 or the int8
    pair; ``dtype`` the model's (the product's operands). Returns f32
    (B, Kh, G, D).

    Every head is mixed with the whole ``Kh*D`` row, (B, H, W) against
    (B, W, Kh*D), and its own kv head's block of the result is kept: the
    twin of :func:`window_scores`. The int8 scale varies along the
    contracted W and folds into the probabilities."""
    B, Kh, G, W = probs.shape
    quant = is_quant_cache(vw)
    data = vw["q"] if quant else vw
    if quant:
        probs = probs * vw["s"].transpose(0, 2, 1)[:, :, None, :]
    mixed = jnp.einsum(
        "bhw,bwc->bhc", probs.astype(dtype).reshape(B, Kh * G, W),
        data.astype(dtype), preferred_element_type=jnp.float32,
    ).reshape(B, Kh, G, Kh, -1)
    return jnp.where(_own_head(Kh), mixed, 0.0).sum(axis=3)
