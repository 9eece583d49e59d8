"""Llama prefill/decode over the paged KV pool.

Same math as the dense paths in :mod:`langstream_tpu.models.llama`; only the
cache geometry changes: K/V rows live in pool blocks mapped by per-slot
block tables (:mod:`langstream_tpu.models.paged`). Decode attention runs in
two segments — the paged pool (Pallas kernel or XLA gather reference) and
the in-chunk KV buffer — merged with the associative online-softmax combine
(:func:`merge_partial_attention`).

Parity: the dense/paged pair mirrors the reference's single code path the
way vLLM relates to naive HF decoding — the capability (continuous batching
at fixed HBM) is SURVEY §7 build-order item 6.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

from langstream_tpu.models.llama import (
    LlamaConfig,
    _apply_rope,
    _default_ffn,
    _rms_norm,
    _rope,
    lora_delta,
)
from langstream_tpu.models.paged import gather_kv, write_rows_pair
from langstream_tpu.models.quant import as_weight as _w, embedding_take
from langstream_tpu.ops.paged_attention import (
    NEG_INF,
    merge_partial_attention,
    paged_attention_partial,
)


def llama_prefill_paged(
    config: LlamaConfig,
    params: dict,
    tokens: jax.Array,        # (B, P) int32, right-padded
    lengths: jax.Array,       # (B,) true lengths
    pool_k: jax.Array,        # (L, nb, bs, Kh*D)
    pool_v: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32 — rows for THIS batch
    use_flash: bool | None = None,
    mesh=None,
    ffn=None,                 # pluggable FFN sub-block (MoE family hook)
    adapters: dict | None = None,  # batched ragged LoRA (see lora_delta)
    kernel: str = "xla",      # the engine's one selection: the commit's form
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Prompt forward + paged cache fill: the shared
    :func:`~langstream_tpu.models.llama.prefill_forward` layer math with the
    K/V landing in pool blocks — one commit of K and V, from position 0
    (:func:`~langstream_tpu.models.paged.write_rows_pair`)."""
    from langstream_tpu.models.llama import prefill_forward

    c = config
    B, Pn = tokens.shape
    logits, ks, vs = prefill_forward(
        c, params, tokens, lengths, use_flash, mesh=mesh, ffn=ffn,
        adapters=adapters,
    )
    KhD = c.kv_heads * c.head_dim
    L = ks.shape[0]
    valid = (jnp.arange(Pn)[None, :] < lengths[:, None])
    pool_k, pool_v = write_rows_pair(
        (pool_k, pool_v), (a.reshape(L, B, Pn, KhD) for a in (ks, vs)),
        block_tables, None, valid, _commit_kernel(kernel, mesh))
    return logits, pool_k, pool_v


def _commit_kernel(kernel: str, mesh) -> str:
    """The selection handed to the commit: the read's, but the XLA scatter
    under any mesh (``pallas_call`` has no partition rule, and the read's
    ``shard_map`` wrapper is not the commit's)."""
    return kernel if mesh is None else "xla"


def llama_prefill_continue_paged(
    config: LlamaConfig,
    params: dict,
    tokens: jax.Array,         # (B, P2) SUFFIX tokens, right-padded
    start_lengths: jax.Array,  # (B,) tokens already in the pool per slot
    suffix_lengths: jax.Array, # (B,) true suffix lengths
    pool_k: jax.Array,         # (L, nb, bs, KhD)
    pool_v: jax.Array,
    block_tables: jax.Array,   # (B, max_blocks)
    num_read_blocks: int,      # static: block columns covering max(start)
    ffn=None,
    return_all_logits: bool = False,  # (B, P2, V) instead of last-token —
                                      # the speculative verify step scores
                                      # every draft position
    kernel: str = "xla",  # history-segment read: "xla" (blocked gather,
                          # every backend/mesh) | "pallas" |
                          # "pallas-interpret" (multi-query scalar-prefetch
                          # kernel; under a mesh it runs per-shard via
                          # shard_map — slots on dp, heads on tp)
    mesh=None,
    adapters: dict | None = None,  # batched ragged LoRA (see lora_delta)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Prefill CONTINUATION: process a prompt suffix whose prefix K/V is
    already in the paged pool (positions ``[0, start)`` per slot).

    Two uses: (a) **automatic prefix caching** — requests sharing a prompt
    prefix (system preambles, RAG templates, chat history) skip recomputing
    it, attending to the shared blocks instead; (b) **chunked prefill** —
    long prompts in bounded pieces. Attention per suffix query merges two
    segments with the online-softmax combine: the pool window masked to
    columns ``< start``, and causal self-attention among the suffix.
    Suffix K/V is committed at ``start`` offsets (the same
    :func:`write_rows_pair` the decode chunk uses). Returns the last REAL suffix
    token's logits plus the updated pools.

    No reference analogue: the reference's completions are SaaS calls
    (``ChatCompletionsStep.java``), so prompt caching was the provider's
    problem; in-tree serving makes it ours.
    """
    from langstream_tpu.models.llama import _default_ffn

    c = config
    if ffn is None:
        ffn = _default_ffn
    B, P2 = tokens.shape
    quant = isinstance(pool_k, dict)
    bs = (pool_k["q"] if quant else pool_k).shape[2]
    if quant and kernel != "xla":
        # the multi-query history-read kernel has no int8 twin (the decode
        # chunk's single-query kernel does); the caller selects "xla" for
        # int8 pools (engine: continuation_read_kernel) — nothing is
        # substituted here
        raise ValueError(
            f"kernel={kernel!r} cannot read an int8 pool in the "
            f"continuation path; pass kernel='xla'"
        )
    KhD = c.kv_heads * c.head_dim
    G = c.heads // c.kv_heads
    with jax.named_scope("embed"):
        x = embedding_take(params["embed"], tokens)  # (B, P2, H)
    with jax.named_scope("attn_qkv"):
        positions = start_lengths[:, None] + jnp.arange(P2)[None, :]
        cos, sin = _rope(positions, c.head_dim, c.rope_theta)
    pos_valid = jnp.arange(P2)[None, :] < suffix_lengths[:, None]  # (B, P2)
    scale = 1.0 / math.sqrt(c.head_dim)
    # suffix key-block size: online-softmax over key blocks bounds score
    # memory at O(P2·sbs) per step instead of O(P2·(start+P2)) — this is
    # what keeps arbitrarily long suffixes (chunked prefill) HBM-safe. The
    # block must divide P2 exactly (dynamic_slice clamps at the edge and
    # would misalign the position mask), so take gcd(P2, 128): power-of-two
    # engine buckets get the full 128; awkward widths degrade the block
    # size, never the memory bound.
    sbs = math.gcd(P2, 128)
    n_suffix_blocks = P2 // sbs

    def layer(x, layer_in):
        if adapters is None:
            lp, ck_l, cv_l = layer_in
        else:
            lp, al, ck_l, cv_l = layer_in
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
            q = q.reshape(B, P2, c.heads, c.head_dim)
            k = k.reshape(B, P2, c.kv_heads, c.head_dim)
            v = v.reshape(B, P2, c.kv_heads, c.head_dim)
            q = _apply_rope(q, cos, sin)
            k = _apply_rope(k, cos, sin)
        with jax.named_scope("kv_read"):
            qg = q.reshape(B, P2, c.kv_heads, G, c.head_dim)

            m0 = jnp.full((B, c.kv_heads, G, P2), NEG_INF, jnp.float32)
            l0 = jnp.zeros((B, c.kv_heads, G, P2), jnp.float32)
            o0 = jnp.zeros((B, c.kv_heads, G, P2, c.head_dim), jnp.float32)

            # the kvquant helpers work on (B, Kh, G', T/D) — fold the query
            # axis into G (one source of truth for the int8 scale-folding
            # identities; the reshapes touch only score-sized tensors)
            qg_flat = qg.transpose(0, 2, 3, 1, 4).reshape(
                B, c.kv_heads, G * P2, c.head_dim
            )

            def online_update(carry, k_blk, v_blk, mask_blk):
                # one flash-attention style block update: k/v (B, T, Kh, D) —
                # bf16 arrays, or int8 {"q","s"} pairs read through the fused
                # kvquant helpers — mask (B, 1, 1, P2?, T) broadcastable over
                # (B,Kh,G,P2,T)
                from langstream_tpu.models.kvquant import cache_scores, cache_values

                o, l, m = carry
                T = (k_blk["s"] if isinstance(k_blk, dict) else k_blk).shape[1]
                s = cache_scores(qg_flat, k_blk).reshape(
                    B, c.kv_heads, G, P2, T
                ) * scale
                s = jnp.where(mask_blk, s, NEG_INF)
                m_new = jnp.maximum(m, s.max(axis=-1))
                shift = jnp.where(m_new <= NEG_INF, 0.0, m_new)
                p = jnp.where(mask_blk, jnp.exp(s - shift[..., None]), 0.0)
                alpha = jnp.exp(jnp.where(m <= NEG_INF, NEG_INF, m - shift))
                l = l * alpha + p.sum(axis=-1)
                update = cache_values(
                    p.astype(qg.dtype).reshape(B, c.kv_heads, G * P2, T), v_blk
                ).reshape(B, c.kv_heads, G, P2, c.head_dim)
                o = o * alpha[..., None] + update.astype(jnp.float32)
                return o, l, m_new

            if kernel != "xla":
                # multi-query scalar-prefetch kernel: no densified gather, the
                # block table drives the DMA (ops/paged_attention.py)
                from langstream_tpu.ops.paged_attention import (
                    paged_attention_multiquery_partial,
                )

                # keep (t_block·G)-row MXU tiles even for narrow suffixes
                # (speculative verify runs D1 = 1+drafts wide): history
                # attention is mask-uniform across queries, so padded rows
                # compute harmless extra attention that is sliced away
                tb = min(16, -(-P2 // 8) * 8)
                P2p = -(-P2 // tb) * tb
                qk = (
                    jnp.pad(q, ((0, 0), (0, P2p - P2), (0, 0), (0, 0)))
                    if P2p != P2
                    else q
                )

                def mq_partial(q_, ck_, cv_, tables_, starts_, kv_heads):
                    return paged_attention_multiquery_partial(
                        q_, ck_, cv_, tables_, starts_,
                        num_read_blocks=num_read_blocks,
                        kv_heads=kv_heads, head_dim=c.head_dim, t_block=tb,
                        scale=scale, interpret=(kernel == "pallas-interpret"),
                    )

                if mesh is not None and len(mesh.devices.flatten()) > 1:
                    # pallas_call has no SPMD rule: shared mesh wrapper — slots
                    # on dp, heads on tp, per-axis degradation
                    from langstream_tpu.ops.paged_attention import (
                        shard_mapped_paged_read,
                    )

                    acc_h, m_h, l_h = shard_mapped_paged_read(
                        mq_partial, mesh,
                        kv_heads=c.kv_heads, batch=B,
                        q_spec_tail=(None, "tp", None),       # (B, P2p, H, D)
                        out_spec_tails=(
                            (None, "tp", None),               # acc (B,T,H,D)
                            (None, "tp"),                     # m (B,T,H)
                            (None, "tp"),                     # l (B,T,H)
                        ),
                    )(qk, ck_l, cv_l, block_tables, start_lengths)
                else:
                    acc_h, m_h, l_h = mq_partial(
                        qk, ck_l, cv_l, block_tables, start_lengths,
                        kv_heads=c.kv_heads,
                    )
                acc_h = acc_h[:, :P2]
                m_h, l_h = m_h[:, :P2], l_h[:, :P2]
                # (B, P2, H[, D]) → the (B, Kh, G, P2[, D]) carry layout
                carry = (
                    acc_h.reshape(B, P2, c.kv_heads, G, c.head_dim).transpose(
                        0, 2, 3, 1, 4
                    ),
                    l_h.reshape(B, P2, c.kv_heads, G).transpose(0, 2, 3, 1),
                    m_h.reshape(B, P2, c.kv_heads, G).transpose(0, 2, 3, 1),
                )
            else:
                # segment 1: pool history, ~128 rows of table columns per step
                # (one tiny per-pool-block step would serialize the sweep
                # ~128/bs-fold deeper for the same score memory)
                cps = max(1, 128 // bs)                         # columns/step
                n_hist_steps = -(-num_read_blocks // cps)

                def hist_step(carry, t):
                    col_idx = t * cps + jnp.arange(cps)         # (cps,)
                    safe = jnp.minimum(col_idx, num_read_blocks - 1)
                    cols = jnp.take(block_tables, safe, axis=1)  # (B, cps)

                    def take_blk(pool_l):
                        if isinstance(pool_l, dict):
                            return {
                                "q": jnp.take(pool_l["q"], cols, axis=0).reshape(
                                    B, cps * bs, c.kv_heads, c.head_dim
                                ),
                                "s": jnp.take(pool_l["s"], cols, axis=0).reshape(
                                    B, cps * bs, c.kv_heads
                                ),
                            }
                        return jnp.take(pool_l, cols, axis=0).reshape(
                            B, cps * bs, c.kv_heads, c.head_dim
                        )

                    k_blk = take_blk(ck_l)
                    v_blk = take_blk(cv_l)
                    # positions from the UNclamped indices: a clamped
                    # (duplicate) tail column computes positions ≥
                    # num_read_blocks·bs, which the < start mask never admits
                    w_pos = (
                        col_idx[:, None] * bs + jnp.arange(bs)[None, :]
                    ).reshape(-1)
                    mask = (w_pos[None, :] < start_lengths[:, None])[
                        :, None, None, None, :
                    ]
                    return online_update(carry, k_blk, v_blk, mask), None

                carry, _ = jax.lax.scan(
                    hist_step, (o0, l0, m0), jnp.arange(n_hist_steps)
                )

            # segment 2: causal self-attention among the suffix, key-blocked
            def suf_step(carry, t):
                k_blk = jax.lax.dynamic_slice_in_dim(k, t * sbs, sbs, axis=1)
                v_blk = jax.lax.dynamic_slice_in_dim(v, t * sbs, sbs, axis=1)
                k_pos = t * sbs + jnp.arange(sbs)
                mask = (
                    (jnp.arange(P2)[:, None] >= k_pos[None, :])[None]
                    & (k_pos[None, None, :] < suffix_lengths[:, None, None])
                )[:, None, None, :, :]
                return online_update(carry, k_blk, v_blk, mask), None

            (o, l, m), _ = jax.lax.scan(
                suf_step, carry, jnp.arange(n_suffix_blocks)
            )
            inv = jnp.where(l > 0.0, 1.0 / jnp.maximum(l, 1e-30), 0.0)
            out = (o * inv[..., None]).astype(x.dtype)  # (B, Kh, G, P2, D)
            out = out.transpose(0, 3, 1, 2, 4).reshape(B, P2, c.heads * c.head_dim)
        with jax.named_scope("attn_out"):
            attn = jnp.einsum("bpd,dh->bph", out, _w(lp["wo"]))
            if adapters is not None:
                attn = attn + lora_delta(out, adapters["ids"], al["wo_a"], al["wo_b"])
            x = x + attn
        with jax.named_scope("ffn"):
            h2 = _rms_norm(x, lp["mlp_norm"], c.norm_eps)
            x = x + ffn(h2, lp, pos_valid)
        return x, (k, v)

    layer_xs = (
        (params["layers"], pool_k, pool_v)
        if adapters is None
        else (params["layers"], adapters["layers"], pool_k, pool_v)
    )
    x, (ks, vs) = jax.lax.scan(layer, x, layer_xs)
    with jax.named_scope("lm_head"):
        x = _rms_norm(x, params["final_norm"], c.norm_eps)
        if return_all_logits:
            logits = jnp.einsum("bph,hv->bpv", x, _w(params["lm_head"])).astype(
                jnp.float32
            )
        else:
            last = jnp.take_along_axis(
                x, (suffix_lengths - 1)[:, None, None].clip(0), axis=1
            ).squeeze(1)
            logits = jnp.einsum("bh,hv->bv", last, _w(params["lm_head"])).astype(
                jnp.float32
            )
    L = c.layers
    pool_k, pool_v = write_rows_pair(
        (pool_k, pool_v), (a.reshape(L, B, P2, KhD) for a in (ks, vs)),
        block_tables, start_lengths, pos_valid, _commit_kernel(kernel, mesh))
    return logits, pool_k, pool_v


def pack_tokens_logprobs(tokens: jax.Array, logprobs: jax.Array) -> jax.Array:
    """Fold a chunk's host-bound outputs into ONE int32 buffer *inside*
    the decode program: tokens first, then the logprobs bit-cast to int32
    (lossless — the host views the tail back as float32). The engine's
    per-chunk host traffic is exactly this array's D2H copy; packing here
    rather than in a second jitted program removes the post-hoc pack
    dispatch from the decode tail."""
    return jnp.concatenate([
        tokens.astype(jnp.int32).reshape(-1),
        jax.lax.bitcast_convert_type(
            logprobs.astype(jnp.float32), jnp.int32
        ).reshape(-1),
    ])


def prompt_lookup_draft(
    ctx: jax.Array,         # (S,) int32 — [prompt | generated], zero-padded
    n: jax.Array,           # scalar int32 — valid tokens in ``ctx``
    num_drafts: int,
) -> tuple[jax.Array, jax.Array]:
    """Device twin of the engine's host bigram drafter: continue the
    context's most recent occurrence of its final bigram.

    Matches the host semantics exactly (the greedy speculative stream is
    byte-identity-pinned against plain decode, so the drafter must too):
    candidate positions are ``i in [1, n-2]`` with
    ``(ctx[i-1], ctx[i]) == (ctx[n-2], ctx[n-1])``, the LAST occurrence
    wins, and the draft is ``ctx[i+1 : i+1+num_drafts]`` clipped to the
    valid region and zero-padded. No match (or ``n < 3``) → all zeros
    with zero real drafts. Returns ``(drafts (num_drafts,), n_real)``.
    """
    S = ctx.shape[0]
    pos = jnp.arange(S, dtype=jnp.int32)
    last0 = ctx[jnp.maximum(n - 2, 0)]
    last1 = ctx[jnp.maximum(n - 1, 0)]
    prev = jnp.roll(ctx, 1)  # prev[i] = ctx[i-1]; prev[0] is masked out
    match = (prev == last0) & (ctx == last1) & (pos >= 1) & (pos <= n - 2)
    i = jnp.max(jnp.where(match, pos, -1))
    found = (i >= 0) & (n >= 3)
    start = i + 1
    offs = start + jnp.arange(num_drafts, dtype=jnp.int32)
    drafts = jnp.where(
        (offs < n) & found, ctx[jnp.clip(offs, 0, S - 1)], 0
    )
    n_real = jnp.where(found, jnp.clip(n - start, 0, num_drafts), 0)
    return drafts.astype(jnp.int32), n_real.astype(jnp.int32)


def llama_spec_step_paged(
    config: LlamaConfig,
    params: dict,
    ctx: jax.Array,            # (B, S) int32 device-resident context tokens
    current: jax.Array,        # (B,) last emitted token per slot
    base_lengths: jax.Array,   # (B,) tokens committed in the pool
    active: jax.Array,         # (B,) bool
    pool_k: jax.Array,
    pool_v: jax.Array,
    block_tables: jax.Array,
    num_drafts: int,
    num_read_blocks: int,
    ffn=None,
    kernel: str = "xla",
    mesh=None,
    key: jax.Array | None = None,
    temps: jax.Array | None = None,
    topks: jax.Array | None = None,
    topps: jax.Array | None = None,
    sampler_mode: tuple | None = None,
    adapters: dict | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused device-resident speculative step: prompt-lookup draft over
    the resident context rows, the verify forward, and the in-program
    context update — ONE dispatch and ONE packed host fetch per step.

    The context rows hold ``[prompt | generated]`` so ``n = lengths + 1``
    (``current`` is ``ctx[n-1]``, not yet committed to the pool). Drafts
    are computed per-row by :func:`prompt_lookup_draft`, verified by
    :func:`llama_verify_chunk_paged`, and the emitted run is scattered
    back into ``ctx`` at ``n .. n+adv-1`` so the next step drafts from an
    already-current device context — the host never ships tokens back.

    Returns ``(packed, ctx, pool_k, pool_v)`` where ``packed`` is the
    int32 single-fetch layout
    ``[emitted (B*D1) | adv (B) | next (B) | new_lengths (B) |
    n_real (B) | bitcast logprobs (B*D1)]``.
    """
    c = config
    B, S = ctx.shape
    n = base_lengths.astype(jnp.int32) + 1
    drafts, n_real = jax.vmap(
        lambda row, ln: prompt_lookup_draft(row, ln, num_drafts)
    )(ctx, n)
    drafts = jnp.where(active[:, None], drafts, 0)
    n_real = jnp.where(active, n_real, 0)
    tokens = jnp.concatenate([current[:, None], drafts], axis=1)  # (B, D1)
    emitted, adv, next_tokens, new_lengths, pool_k, pool_v, logprobs = (
        llama_verify_chunk_paged(
            c, params, tokens, base_lengths, active, pool_k, pool_v,
            block_tables, num_read_blocks, ffn=ffn, kernel=kernel,
            mesh=mesh, key=key, temps=temps, topks=topks, topps=topps,
            sampler_mode=sampler_mode, adapters=adapters,
        )
    )
    D1 = num_drafts + 1
    js = jnp.arange(D1, dtype=jnp.int32)[None, :]
    write_pos = n[:, None] + js                    # emitted[:, j] → ctx[n+j]
    rows = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None], (B, D1))
    # unemitted columns (and context-cap overruns) redirect to an OOB
    # column and drop — inactive rows have adv 0, so they never write
    cols = jnp.where(js < adv[:, None], write_pos, S)
    ctx = ctx.at[rows, cols].set(emitted.astype(jnp.int32), mode="drop")
    packed = jnp.concatenate([
        emitted.astype(jnp.int32).reshape(-1),
        adv.astype(jnp.int32),
        next_tokens.astype(jnp.int32),
        new_lengths.astype(jnp.int32),
        n_real.astype(jnp.int32),
        jax.lax.bitcast_convert_type(
            logprobs.astype(jnp.float32), jnp.int32
        ).reshape(-1),
    ])
    return packed, ctx, pool_k, pool_v


def llama_verify_chunk_paged(
    config: LlamaConfig,
    params: dict,
    tokens: jax.Array,         # (B, D1): [current, draft_0 .. draft_{D1-2}]
    base_lengths: jax.Array,   # (B,) tokens in the pool per slot
    active: jax.Array,         # (B,) bool
    pool_k: jax.Array,
    pool_v: jax.Array,
    block_tables: jax.Array,
    num_read_blocks: int,
    ffn=None,
    kernel: str = "xla",  # history read (see llama_prefill_continue_paged)
    mesh=None,
    key: jax.Array | None = None,
    temps: jax.Array | None = None,
    topks: jax.Array | None = None,
    topps: jax.Array | None = None,
    sampler_mode: tuple | None = None,  # (use_top_p, use_top_k, all_greedy)
    adapters: dict | None = None,  # batched ragged LoRA (see lora_delta)
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Speculative VERIFY step (prompt-lookup decoding).

    One forward over ``D1 = 1 + drafts`` positions per slot scores every
    draft in parallel. Two acceptance modes, selected by the static
    ``sampler_mode`` (None or ``all_greedy`` → greedy):

    - **Greedy** (the default): in-jit greedy acceptance keeps the longest
      prefix of drafts the model itself would have produced, plus the
      model's one bonus token after it. Drafts cost nothing when wrong
      (acceptance only ever emits model-argmax tokens, so on a bf16 pool
      output streams are IDENTICAL to plain greedy decode — speculation
      changes latency, never content). On an int8 pool the guarantee is
      per-forward, not cross-engine: a position reads as fresh bf16 before
      commit and as quantised int8 after, and verify commits at different
      boundaries than the fixed decode chunk — near-tie argmaxes may
      differ (~1e-2 logit scale) from a non-speculative engine's stream.
    - **Sampled** (``sampler_mode`` set and not all-greedy): rejection
      sampling against the deterministic prompt-lookup drafter
      (``sampler.speculative_accept``) — draft ``d_j`` survives with the
      target's filtered probability ``p_j(d_j)``; the first rejection
      emits a residual sample; full acceptance earns a bonus sample. The
      emitted stream is distributed exactly as plain sampling. Greedy
      rows inside a mixed batch degenerate to the greedy rule.

    Returns (emitted (B, D1) — the token to emit at each position,
    emit_counts (B,) — how many leading emitted tokens are real (1..D1),
    next_tokens (B,), new_lengths (B,), pool_k, pool_v, logprobs (B, D1)).

    K/V for all D1 positions is committed; rows past ``new_lengths`` hold
    rejected drafts but every read masks to < length and the next step's
    writes land exactly at ``new_lengths`` — the standard stale-row
    argument of the prefill paths.
    """
    c = config
    B, D1 = tokens.shape
    # inactive rows get suffix length 0: their writes redirect to the
    # scratch block instead of committing garbage through their REAL block
    # tables (a mid-chunked-prefill slot, or shared prefix blocks, would
    # otherwise be silently corrupted — the decode chunk masks its commit
    # with `active` for exactly this reason). Rows are also capped at the
    # context limit: positions ≥ max_seq_len would clamp to the slot's
    # LAST table column in write_rows_pair and overwrite committed K/V (the
    # engine's emit guard stops streams before any such position's token
    # is ever emitted, so capping the write loses nothing).
    room = jnp.maximum(c.max_seq_len - base_lengths, 0)
    suffix_lengths = jnp.where(
        active, jnp.minimum(D1, room), 0
    ).astype(jnp.int32)
    logits, pool_k, pool_v = llama_prefill_continue_paged(
        c, params, tokens, base_lengths,
        suffix_lengths, pool_k, pool_v, block_tables,
        num_read_blocks, ffn=ffn, return_all_logits=True, kernel=kernel,
        mesh=mesh, adapters=adapters,
    )  # logits (B, D1, V)
    with jax.named_scope("sample"):
        drafts = tokens[:, 1:]                                   # (B, D1-1)
        logits_f32 = logits.astype(jnp.float32)
        if sampler_mode is None or sampler_mode[2]:  # all-greedy
            model_next = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, D1)
            # draft j (= input position j+1) is accepted iff every earlier
            # draft matched and the model's token at position j equals it
            match = model_next[:, :-1] == drafts                 # (B, D1-1)
            accepted = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
            emitted = model_next
        else:
            from langstream_tpu.serving.sampler import speculative_accept

            use_top_p, use_top_k, _ = sampler_mode
            accepted, fallback = speculative_accept(
                logits_f32, drafts, key, temps, topks, topps,
                use_top_p=use_top_p, use_top_k=use_top_k,
            )
            # emit accepted drafts verbatim, then the residual/bonus sample at
            # the stop position (the only fallback column the engine reads)
            pos = jnp.arange(D1)[None, :]
            drafts_pad = jnp.pad(drafts, ((0, 0), (0, 1)))
            emitted = jnp.where(pos < accepted[:, None], drafts_pad, fallback)
            emitted = emitted.astype(jnp.int32)
        logprobs = jnp.take_along_axis(
            jax.nn.log_softmax(logits_f32, axis=-1), emitted[..., None], axis=-1
        ).squeeze(-1)
        adv = jnp.where(active, accepted + 1, 0)                 # tokens emitted
        new_lengths = base_lengths + adv
        next_tokens = jnp.where(
            active,
            jnp.take_along_axis(
                emitted, jnp.maximum(adv - 1, 0)[:, None], axis=1
            ).squeeze(1),
            tokens[:, 0],
        )
    return emitted, adv, next_tokens, new_lengths, pool_k, pool_v, logprobs


# The XLA read takes a window in passes of so many bytes of ONE pool's
# gathered rows (slots x rows x Kh*D): small enough that the TPU compiler
# keeps a pass's K rows, then its V rows, in VMEM (128 MiB on a v5e) from
# the gather that writes them to the product that reads them, so that a
# window's bytes cross HBM once, on their way out of the pool. At Mistral-7B's
# posture (64 slots, int8 rows of 1,024 B) a pass is 512 rows.
_XLA_READ_PASS_BYTES = 32 * 2**20


def _cache_partial_xla(
    c: LlamaConfig,
    q: jax.Array,             # (B, H, D)
    pool_k,                   # (L, nb, bs, KhD) array or int8 {"q","s"} pool
    pool_v,
    layer,                    # the layer's index in the stacked pools
    block_tables: jax.Array,  # (B, max_blocks)
    lengths: jax.Array,       # (B,)
    num_read_blocks: int,
    scale: float | None = None,   # None: 1/sqrt(head_dim)
    firsts: jax.Array | None = None,  # (B,) the first row a slot's query sees
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The XLA paged read: partial softmax statistics of one query a slot
    over the layer's window. Works on every backend, for bf16 and int8
    pools and under pjit meshes (gathers shard like any XLA op): what the
    CPU, every int8 pool and the tests' reference read through.

    A window's bytes move once (ROADMAP S1): :func:`gather_kv` takes the
    slots' blocks out of the STACKED pool at ``(layer, block)`` (no slice
    of the layer, no fill), the two products contract the rows in the
    layout the gather wrote (``kvquant.window_scores`` / ``window_values``:
    int8 converts in the product's operand, scales onto scores and
    probabilities), and the window goes in passes of
    ``_XLA_READ_PASS_BYTES`` whose rows never reach HBM again, their
    partials merged as the chunk buffer's is. It still sweeps the whole
    window bucket: reading a slot's live blocks alone is the Pallas
    driver's (``ops/paged_attention.py``). With ``firsts`` (a layer that
    attends a window of its last rows) a slot's query sees the rows
    ``[firsts[b], lengths[b])`` and the ``num_read_blocks`` columns swept are
    the slot's own, from the block that holds its first row."""
    from langstream_tpu.models.kvquant import window_scores, window_values
    from langstream_tpu.ops.paged_attention import merge_partials

    B, H, D = q.shape
    data = pool_k["q"] if isinstance(pool_k, dict) else pool_k
    bs, row_bytes = data.shape[2], data.shape[3] * data.dtype.itemsize
    cols = max(1, _XLA_READ_PASS_BYTES // (B * bs * row_bytes))

    def one_pass(first: int, n: int):
        """Table columns ``first .. first + n``: rows from ``first * bs``."""
        if firsts is None:
            tables = block_tables[:, first:first + n]
        else:
            col0 = firsts // bs
            cols = col0[:, None] + first + jnp.arange(n)[None, :]
            tables = jnp.take_along_axis(
                block_tables, jnp.minimum(cols, block_tables.shape[1] - 1),
                axis=1)
        kw = gather_kv(pool_k, tables, n, layer=layer)
        vw = gather_kv(pool_v, tables, n, layer=layer)
        s = window_scores(q, kw, c.kv_heads)                  # (B, Kh, G, W)
        s = s / math.sqrt(c.head_dim) if scale is None else s * scale
        rows = (first * bs + jnp.arange(n * bs))[None, :]
        if firsts is not None:
            rows = (col0 * bs)[:, None] + rows
        mask = rows < lengths[:, None]
        if firsts is not None:
            mask = mask & (rows >= firsts[:, None])
        mask = mask[:, None, None, :]
        s = jnp.where(mask, s, NEG_INF)
        m = jnp.max(s, axis=-1)                               # (B, Kh, G)
        shift = jnp.where(m <= NEG_INF, 0.0, m)
        p = jnp.exp(s - shift[..., None])
        p = jnp.where(mask, p, 0.0)
        l = jnp.sum(p, axis=-1)
        acc = window_values(p, vw, c.kv_heads, q.dtype)
        return acc.reshape(B, H, D), m.reshape(B, H), l.reshape(B, H)

    out = None
    for first in range(0, num_read_blocks, cols):
        part = one_pass(first, min(cols, num_read_blocks - first))
        out = part if out is None else merge_partials(out, part)
    return out


def llama_decode_chunk_paged(
    config: LlamaConfig,
    params: dict,
    tokens0: jax.Array,       # (B,)
    base_lengths: jax.Array,  # (B,)
    active: jax.Array,        # (B,) bool
    pool_k: jax.Array,        # (L, nb, bs, KhD) — read-only during the chunk
    pool_v: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks)
    sample_fn: Callable,
    key: jax.Array,
    num_steps: int,
    num_read_blocks: int,     # static block-sweep bucket (covers max length)
    kernel: str = "xla",      # "xla" | "pallas" | "pallas-interpret"
    mesh=None,                # Pallas kernel runs per-shard via shard_map
    ffn=None,                 # (h (B,H), lp, valid=None) -> (B,H);
                              # default dense SwiGLU
    sample_extras=None,       # (presences, frequencies, counts0) — see
                              # llama_decode_chunk
    adapters: dict | None = None,  # batched ragged LoRA (see lora_delta)
    return_packed: bool = False,
) -> tuple[jax.Array, ...]:
    """K fused decode steps against the paged pool; same two-segment
    discipline as the dense ``llama_decode_chunk`` (pool read-only, new K/V
    in a chunk buffer in both scans' carry, one scatter commit at the end).

    ``return_packed=True`` folds the chunk's host-bound outputs into the
    program itself (:func:`pack_tokens_logprobs`) and returns
    ``(packed, final_tokens, final_lengths, pool_k, pool_v)`` — the
    engine's whole per-chunk host traffic becomes that one array's D2H
    copy, with no post-hoc pack dispatch."""
    c = config
    if ffn is None:
        ffn = _default_ffn
    if (
        isinstance(pool_k, dict)
        and kernel != "xla"
        and mesh is not None
        and len(mesh.devices.flatten()) > 1
    ):
        # the shard_map Pallas wrapper carries no int8 scale specs;
        # multi-device int8 pools read through the (sharding-aware) XLA
        # gather, which the caller selects (the engine refuses this
        # combination at construction) — nothing is substituted here
        raise ValueError(
            f"kernel={kernel!r} cannot read an int8 pool under a "
            f"multi-device mesh; pass kernel='xla'"
        )
    B = tokens0.shape[0]
    KhD = c.kv_heads * c.head_dim
    adv = active.astype(jnp.int32)
    kbuf0 = jnp.zeros((c.layers, B, num_steps, c.kv_heads, c.head_dim), c.dtype)
    vbuf0 = jnp.zeros_like(kbuf0)
    pen = sample_extras is not None
    counts0 = sample_extras[2] if pen else None

    def _kernel_partial(q, pk, pv, layer_idx, tables, lengths, kv_heads):
        return paged_attention_partial(
            q, pk, pv, layer_idx, tables, lengths,
            num_read_blocks=num_read_blocks,
            kv_heads=kv_heads, head_dim=c.head_dim,
            scale=1.0 / math.sqrt(c.head_dim),
            interpret=(kernel == "pallas-interpret"),
        )

    def cache_partial(q, kv_l):
        # either read takes the stacked pool where it lies (read-only for
        # the whole chunk) and the layer's index: no slice of it exists
        if kernel == "xla":
            return _cache_partial_xla(
                c, q, pool_k, pool_v, kv_l, block_tables, base_lengths,
                num_read_blocks,
            )
        if mesh is not None and len(mesh.devices.flatten()) > 1:
            # pallas_call has no SPMD rule: shared mesh wrapper — slots on
            # dp, heads on tp, per-axis degradation
            from langstream_tpu.ops.paged_attention import (
                shard_mapped_paged_read,
            )

            return shard_mapped_paged_read(
                _kernel_partial, mesh,
                kv_heads=c.kv_heads, batch=B,
                q_spec_tail=("tp", None),                  # (B, H, D)
                out_spec_tails=(("tp", None), ("tp",), ("tp",)),
                stacked_pool=True,
            )(q, pool_k, pool_v, kv_l, block_tables, base_lengths)
        return _kernel_partial(
            q, pool_k, pool_v, kv_l, block_tables, base_lengths, c.kv_heads
        )

    def step(carry, step_idx):
        if pen:
            tokens, kbuf, vbuf, key, counts = carry
        else:
            tokens, kbuf, vbuf, key = carry
            counts = None
        with jax.named_scope("sample"):
            key, sub = jax.random.split(key)
        with jax.named_scope("embed"):
            x = embedding_take(params["embed"], tokens)
        with jax.named_scope("attn_qkv"):
            positions = base_lengths + step_idx * adv
            cos, sin = _rope(positions, c.head_dim, c.rope_theta)
            buf_mask = jnp.arange(num_steps)[None, :] <= step_idx  # (1, K)
        G = c.heads // c.kv_heads

        def layer(carry, layer_in):
            # the whole chunk buffer rides the carry and takes the step's
            # rows in place (as xs/ys the stacked ys is a new array: the
            # compiler copies the buffer whole every step)
            x, kbuf, vbuf = carry
            lp, al, kv_l = layer_in
            with jax.named_scope("attn_qkv"):
                h = _rms_norm(x, lp["attn_norm"], c.norm_eps)
                q = h @ _w(lp["wq"])
                k = h @ _w(lp["wk"])
                v = h @ _w(lp["wv"])
                if adapters is not None:
                    ids = adapters["ids"]
                    q = q + lora_delta(h, ids, al["wq_a"], al["wq_b"])
                    k = k + lora_delta(h, ids, al["wk_a"], al["wk_b"])
                    v = v + lora_delta(h, ids, al["wv_a"], al["wv_b"])
                q = q.reshape(B, c.heads, c.head_dim)
                k = k.reshape(B, c.kv_heads, c.head_dim)
                v = v.reshape(B, c.kv_heads, c.head_dim)
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
                # segment 1: paged pool (partial stats)
                acc_c, m_c, l_c = cache_partial(q, kv_l)
                # segment 2: in-chunk buffer (partial stats, tiny)
                qg = q.reshape(B, c.kv_heads, G, c.head_dim)
                s_buf = jnp.einsum("bkgd,btkd->bkgt", qg, kbuf_l).astype(jnp.float32)
                s_buf = s_buf / math.sqrt(c.head_dim)
                s_buf = jnp.where(buf_mask[:, None, None, :], s_buf, NEG_INF)
                m_b = jnp.max(s_buf, axis=-1)
                shift = jnp.where(m_b <= NEG_INF, 0.0, m_b)
                p_b = jnp.exp(s_buf - shift[..., None])
                p_b = jnp.where(buf_mask[:, None, None, :], p_b, 0.0)
                l_b = jnp.sum(p_b, axis=-1)
                acc_b = jnp.einsum(
                    "bkgt,btkd->bkgd", p_b.astype(vbuf_l.dtype), vbuf_l
                ).astype(jnp.float32)
                out = merge_partial_attention([
                    (acc_c, m_c, l_c),
                    (
                        acc_b.reshape(B, c.heads, c.head_dim),
                        m_b.reshape(B, c.heads),
                        l_b.reshape(B, c.heads),
                    ),
                ]).astype(x.dtype)
                out = out.reshape(B, c.heads * c.head_dim)
            with jax.named_scope("attn_out"):
                attn = out @ _w(lp["wo"])
                if adapters is not None:
                    attn = attn + lora_delta(
                        out, adapters["ids"], al["wo_a"], al["wo_b"]
                    )
                x = x + attn
            with jax.named_scope("ffn"):
                h2 = _rms_norm(x, lp["mlp_norm"], c.norm_eps)
                x = x + ffn(h2, lp, active)
            return (x, kbuf, vbuf), None

        layer_xs = (
            params["layers"],
            None if adapters is None else adapters["layers"],
            # the read and the buffer's write are handed the layer's index;
            # the read closes over the pool
            jnp.arange(c.layers),
        )
        (x, kbuf, vbuf), _ = jax.lax.scan(layer, (x, kbuf, vbuf), layer_xs)
        with jax.named_scope("lm_head"):
            x = _rms_norm(x, params["final_norm"], c.norm_eps)
            logits = (x @ _w(params["lm_head"])).astype(jnp.float32)
        with jax.named_scope("sample"):
            if pen:
                nxt, lp_ = sample_fn(logits, sub, counts)
            else:
                nxt, lp_ = sample_fn(logits, sub)
            nxt = jnp.where(active, nxt, tokens)
        if pen:
            counts = counts.at[jnp.arange(B), nxt].add(adv)
            return (nxt, kbuf, vbuf, key, counts), (nxt, lp_)
        return (nxt, kbuf, vbuf, key), (nxt, lp_)

    carry0 = (
        (tokens0, kbuf0, vbuf0, key, counts0)
        if pen
        else (tokens0, kbuf0, vbuf0, key)
    )
    out_carry, (chunk_tokens, chunk_lps) = jax.lax.scan(
        step, carry0, jnp.arange(num_steps)
    )
    final_tokens, kbuf, vbuf = out_carry[0], out_carry[1], out_carry[2]

    L = c.layers
    valid = jnp.broadcast_to(active[:, None], (B, num_steps))
    pool_k, pool_v = write_rows_pair(
        (pool_k, pool_v),
        (a.reshape(L, B, num_steps, KhD) for a in (kbuf, vbuf)),
        block_tables, base_lengths, valid, _commit_kernel(kernel, mesh))
    final_lengths = base_lengths + num_steps * adv
    if return_packed:
        packed = pack_tokens_logprobs(chunk_tokens, chunk_lps)
        return packed, final_tokens, final_lengths, pool_k, pool_v
    return chunk_tokens, chunk_lps, final_tokens, final_lengths, pool_k, pool_v
