"""Latent-attention decoder with group-limited routed experts (the
``deepseek_v2`` family), over a paged pool whose row is ONE latent.

A layer is ``h = x + Attn(RMSNorm(x))``, ``x' = h + FFN(RMSNorm(h))``; the
first ``dense_layers`` layers' FFN is one gated MLP, every later layer's the
routed experts beside a shared one. The programs scan the two runs of layers,
each over its own stack of weights.

**Multi-head latent attention, two paths over one cache.** A position's key
and value are both functions of one compressed row: ``[c | k_pe] = u W_kva``,
``c_kv = RMSNorm(c)``, and the pool keeps ``[c_kv | rot(k_pe) | 0..]``
(``kv_rank + rope_dim`` wide and zeros to the next lane tile, no head axis,
K and V the same bytes: :func:`langstream_tpu.models.paged.
init_latent_pool`).

- *Prefill expands*: ``k_nope[h] = c_kv W_UK[h]``, ``v[h] = c_kv W_UV[h]``
  for every head, the one rotated ``k_pe`` beside each head's ``k_nope``,
  then causal flash attention with keys of ``nope_dim + rope_dim`` and values
  of ``v_dim`` (:mod:`langstream_tpu.ops.flash_attention`).
- *Decode absorbs*: ``q_lat[h] = q_nope[h] W_UK[h]^T`` and ``o[h] =
  (sum_t p_t c_kv,t) W_UV[h]``, so a step reads the latent rows as they lie
  and never expands them (:func:`langstream_tpu.ops.paged_attention.
  latent_read`, or its XLA expression). The same function of the same
  weights: ``W_UK`` and ``W_UV`` are a head's two blocks of the published
  ``W_kvb``.

Rotary embedding is YaRN over the ``rope_dim`` slice alone (:func:`yarn_
inv_freq`), applied as the published code applies it: the slice's pairs are
de-interleaved, then half-rotated. The softmax scale carries YaRN's
``mscale**2``.

The expert layer serves one chip's share of an expert-parallel deployment,
as the hybrid family's does (``experts_held`` of ``experts`` from
``expert_first``; :func:`langstream_tpu.models.hybrid.moe_mixer` with the
``group_limited`` rule of :mod:`langstream_tpu.models.moe`).

The published fused projections are kept as their column blocks (``W_qb`` as
its nope and rotary parts, ``W_kva`` as the latent's and the rotary key's,
``W_kvb`` as ``W_UK`` and ``W_UV`` by head): no block's width straddles a
lane tile, and the absorbed path needs the two halves of ``W_kvb`` apart.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from langstream_tpu.models.family import Family
from langstream_tpu.models.hybrid import backend_kernel, moe_mixer
from langstream_tpu.models.llama import _flash_mode, _rms_norm
from langstream_tpu.models.llama import yarn_inv_freq as _yarn_inv_freq
from langstream_tpu.models.llama_paged import pack_tokens_logprobs
from langstream_tpu.models.moe import silu_gated
from langstream_tpu.models.paged import init_latent_pool, write_rows
from langstream_tpu.ops.paged_attention import (
    NEG_INF,
    latent_read,
    latent_read_xla,
    merge_partial_attention,
)

#: query rows x heads of one pass of a prefill's expanded attention: past
#: it the heads are taken a group at a time, so that the expanded queries,
#: keys and values of a 16k-row prompt (0.8 + 0.8 + 0.5 GB for all 128
#: heads at the published widths) never sit whole beside the resident pool.
#: Compiled for a described v5e, a 16,384-row prefill's scratch is 2.7 GB
#: at 4096 x 128, 2.0 at 2048 x 128 and 1.55 at this; two prefills may be
#: queued at once (engine.py ``_admit``) beside 12.2 GB resident of 16.9
EXPAND_ROWS_X_HEADS = 1024 * 128
#: query and key rows of one block of the prefill's flash kernel at this
#: family's widths (keys 192, values 128; tools/latent_probe.py --kernels
#: on the v5e, TFLOP/s on the true causal pairs of the longest prompt of
#: the 4,096 / 8,192 / 16,384 bucket, one head group, told the length):
#: 256 x 512 34 / 42 / 35, 512 x 512 (the kernel's default) 40 / 49 / 43,
#: 1024 x 512 38 / 48 / 46, 512 x 1024 52 / 67 / 59, 1024 x 1024 58 / 75 / 70
FLASH_BLOCK = 1024


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    # the fields the dense family's config has, under the same names
    vocab_size: int = 12800
    hidden: int = 5120
    layers: int = 5
    heads: int = 128
    kv_heads: int = 128              # published; the cache has no head axis
    head_dim: int = 192              # nope_dim + rope_dim: a key's width
    intermediate: int = 12288        # the dense layers' FFN
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    max_seq_len: int = 16384
    dtype: Any = jnp.bfloat16
    # latent attention
    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    # YaRN (rope_scaling)
    rope_factor: float = 40.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    rope_original_max: int = 4096
    # FFN
    dense_layers: int = 1            # first_k_dense_replace
    moe_intermediate: int = 1536     # one routed expert's width
    shared_intermediate: int = 3072  # n_shared_experts x moe_intermediate
    experts: int = 160
    experts_per_token: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scale: float = 16.0
    router_dtype: Any = jnp.float32  # published; lower only as a control
    # this chip's share of the expert-parallel deployment
    experts_held: int = 20
    expert_first: int = 0
    # what moe_mixer reads of a family
    router: str = "group_limited"
    expert_act: str = "silu_gated"
    #: recurrent state beside the pool: none
    state_bytes_per_slot: int = 0

    def __post_init__(self):
        if self.head_dim != self.nope_dim + self.rope_dim:
            raise ValueError("head_dim is nope_dim + rope_dim")
        if not 0 < self.dense_layers < self.layers:
            raise ValueError("at least one dense and one expert layer")
        if self.experts % self.n_group:
            raise ValueError("the groups divide the experts evenly")
        if not 0 <= self.expert_first <= self.experts - self.experts_held:
            raise ValueError("the held experts lie outside the router's")

    @classmethod
    def deepseek_v2_ep8(cls, max_seq_len: int = 16384) -> "LatentConfig":
        """deepseek-ai/DeepSeek-V2 as one chip of the eight that share each
        layer, rank 0 of pipeline stage 0 of twelve: layers 0-4 of 60 (the
        one dense layer and four expert layers), experts 0-19 of 160 (one
        whole routing group) and 12,800 of 102,400 vocabulary rows held
        here; attention, the shared expert and the router whole."""
        return cls(max_seq_len=max_seq_len)

    @classmethod
    def tiny(cls, max_seq_len: int = 128, expert_first: int = 0,
             experts_held: int = 4) -> "LatentConfig":
        """Test size of the same grammar: 1 dense + 2 expert layers, 2
        groups of 4 experts, top 2 of the best group."""
        return cls(
            vocab_size=384, hidden=64, layers=3, heads=4, kv_heads=4,
            head_dim=24, intermediate=96, q_rank=32, kv_rank=16, nope_dim=16,
            rope_dim=8, v_dim=16, dense_layers=1,
            moe_intermediate=32, shared_intermediate=64, experts=8,
            experts_per_token=2, n_group=2, topk_group=1,
            experts_held=experts_held, expert_first=expert_first,
            max_seq_len=max_seq_len,
        )

    @property
    def sparse_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def row_width(self) -> int:
        """One cache row: the normalised latent, the rotated rotary key, and
        zeros up to the next lane tile (128). The device keeps an array's
        last axis in whole lane tiles anyway, and a copy of part of one is
        refused (``Slice shape ... must be aligned to tiling (128)``), so
        the padding the layout would add is made part of the row: 640 lanes
        for the published 512 + 64, 1,280 bytes where 1,152 are data."""
        return -(-(self.kv_rank + self.rope_dim) // 128) * 128

    @property
    def row_pad(self) -> int:
        return self.row_width - self.kv_rank - self.rope_dim

    @property
    def attn_scale(self) -> float:
        """``head_dim**-0.5`` times YaRN's ``mscale(factor, mscale_all_dim)``
        squared."""
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return m * m / math.sqrt(self.head_dim)


# ---------------------------------------------------------------------------
# rotary embedding
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(c: LatentConfig) -> np.ndarray:
    """``(rope_dim / 2,)`` float32 inverse frequencies of the ``rope_dim``
    slice (:func:`langstream_tpu.models.llama.yarn_inv_freq`, the table the
    window-and-full family's YaRN layers take too)."""
    return _yarn_inv_freq(c.rope_dim, c.rope_theta, c.rope_factor,
                          c.rope_original_max, c.rope_beta_fast,
                          c.rope_beta_slow)


def rotate(c: LatentConfig, x: jax.Array, positions: jax.Array) -> jax.Array:
    """YaRN rotary embedding of ``x (..., rope_dim)`` at ``positions``
    (broadcast against ``x``'s leading axes): the pairs ``(x_2i, x_2i+1)``
    de-interleaved to ``[evens | odds]`` and then half-rotated, as the
    published code does; the cos/sin factor ``mscale / mscale_all_dim``."""
    angles = positions[..., None].astype(jnp.float32) * yarn_inv_freq(c)
    factor = (yarn_mscale(c.rope_factor, c.rope_mscale)
              / yarn_mscale(c.rope_factor, c.rope_mscale_all_dim))
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (c.rope_dim // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.concatenate(
        [even * cos - odd * sin, odd * cos + even * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_latent_params(config: LatentConfig, key: jax.Array | None = None) -> dict:
    """Random parameters from a key, one jitted draw a leaf. An expert's
    weights depend on its GLOBAL id and its layer, so the shares of one
    deployment are slices of the same experts."""
    c = config
    key = key if key is not None else jax.random.PRNGKey(0)
    H, names = c.hidden, iter(range(10 ** 6))

    def normal(shape, fan_in):
        k = jax.random.fold_in(key, next(names))
        scale = 1.0 / math.sqrt(fan_in)
        return jax.jit(
            lambda k: (jax.random.normal(k, shape, jnp.float32) * scale
                       ).astype(c.dtype)
        )(k)

    def attention(n):
        return {
            "norm": jnp.ones((n, H), c.dtype),
            "w_qa": normal((n, H, c.q_rank), H),
            "q_norm": jnp.ones((n, c.q_rank), c.dtype),
            "w_q_nope": normal((n, c.q_rank, c.heads * c.nope_dim), c.q_rank),
            "w_q_pe": normal((n, c.q_rank, c.heads * c.rope_dim), c.q_rank),
            "w_kv_c": normal((n, H, c.kv_rank), H),
            "w_k_pe": normal((n, H, c.rope_dim), H),
            "kv_norm": jnp.ones((n, c.kv_rank), c.dtype),
            # W_kvb by head: k_nope[h] = c_kv w_uk[h]^T, v[h] = c_kv w_uv[h]
            "w_uk": normal((n, c.heads, c.nope_dim, c.kv_rank), c.kv_rank),
            "w_uv": normal((n, c.heads, c.kv_rank, c.v_dim), c.kv_rank),
            "w_o": normal((n, c.heads * c.v_dim, H), c.heads * c.v_dim),
        }

    def experts(n, first_layer, shape, fan_in):
        """(n, held) + shape, expert e of layer i from (i, global e)."""
        k = jax.random.fold_in(key, next(names))
        scale = 1.0 / math.sqrt(fan_in)

        def one(i, e):
            ke = jax.random.fold_in(jax.random.fold_in(k, i), e)
            return (jax.random.normal(ke, shape, jnp.float32) * scale
                    ).astype(c.dtype)

        held = c.expert_first + jnp.arange(c.experts_held)
        return jax.jit(jax.vmap(
            lambda i: jax.vmap(lambda e: one(i, e))(held)
        ))(first_layer + jnp.arange(n))

    nd, ns = c.dense_layers, c.sparse_layers
    I, Ie, Is = c.intermediate, c.moe_intermediate, c.shared_intermediate
    params = {
        "embed": normal((c.vocab_size, H), 1.0),
        "final_norm": jnp.ones((H,), c.dtype),
        "lm_head": normal((H, c.vocab_size), H),
    }
    params["dense"] = {
        "attn": attention(nd),
        "ffn": {
            "norm": jnp.ones((nd, H), c.dtype),
            "w_up": normal((nd, H, 2 * I), H),       # [gate | up]
            "w_down": normal((nd, I, H), I),
        },
    }
    params["sparse"] = {
        "attn": attention(ns),
        "moe": {
            "norm": jnp.ones((ns, H), c.dtype),
            "router": normal((ns, H, c.experts), H),
            # (held, 2 I, H) and (held, I, H), as the hybrid family's
            # gated experts (models/moe.py dropless_experts)
            "w_up": experts(ns, nd, (2 * Ie, H), H),
            "w_down": experts(ns, nd, (Ie, H), Ie),
            "ws_up": normal((ns, H, 2 * Is), H),
            "ws_down": normal((ns, Is, H), Is),
        },
    }
    return params


# ---------------------------------------------------------------------------
# the attention's projections, shared by both paths
# ---------------------------------------------------------------------------


def _compressed_q(c: LatentConfig, ap: dict, h: jax.Array) -> jax.Array:
    return _rms_norm(h @ ap["w_qa"], ap["q_norm"], c.norm_eps)


def _queries(c: LatentConfig, c_q: jax.Array, w_nope: jax.Array,
             w_pe: jax.Array, positions: jax.Array):
    """``(q_nope (..., n, nope_dim), rotated q_pe (..., n, rope_dim))`` of
    the compressed queries ``c_q (..., q_rank)`` for the ``n`` heads whose
    columns ``w_nope`` and ``w_pe`` hold."""
    lead = c_q.shape[:-1]
    q_nope = (c_q @ w_nope).reshape(lead + (-1, c.nope_dim))
    q_pe = (c_q @ w_pe).reshape(lead + (-1, c.rope_dim))
    return q_nope, rotate(c, q_pe, positions[..., None])


def _padded(c: LatentConfig, parts: list[jax.Array]) -> jax.Array:
    """``parts`` side by side and zeros up to ``row_width``."""
    lead = parts[0].shape[:-1]
    return jnp.concatenate(
        parts + [jnp.zeros(lead + (c.row_pad,), parts[0].dtype)], axis=-1)


def _latent_rows(c: LatentConfig, ap: dict, h: jax.Array, positions: jax.Array):
    """The cache rows ``[c_kv | rot(k_pe) | 0..] (..., row_width)`` of ``h``."""
    c_kv = _rms_norm(h @ ap["w_kv_c"], ap["kv_norm"], c.norm_eps)
    return _padded(c, [c_kv, rotate(c, h @ ap["w_k_pe"], positions)])


def _dense_ffn(c: LatentConfig, fp: dict, x: jax.Array) -> jax.Array:
    with jax.named_scope("ffn"):
        h = _rms_norm(x, fp["norm"], c.norm_eps)
        return x + silu_gated(h @ fp["w_up"]) @ fp["w_down"]


def _logits(params: dict, x: jax.Array) -> jax.Array:
    return (x @ params["lm_head"]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# prefill: the expanded path
# ---------------------------------------------------------------------------


def latent_prefill_paged(
    config: LatentConfig,
    params: dict,
    tokens: jax.Array,        # (B, P) int32, right-padded
    lengths: jax.Array,       # (B,) true lengths
    pool: jax.Array,          # (layers, nb, bs, row_width)
    block_tables: jax.Array,  # (B, max_blocks): rows of THIS batch
    use_flash: bool | None = None,
    kernel: str | None = None,
):
    """Prompt forward through the expanded attention; every layer's latent
    rows land in the pool through :func:`langstream_tpu.models.paged.
    write_rows`, the one commit of every family. Returns ``(last-token
    logits (B, V), pool, routed)``; ``routed (expert layers, B, P, k)`` are
    the experts the router chose, for the reference check (a caller that
    drops it pays nothing for it). ``kernel`` is the engine's one selection,
    here the form of the routed experts' grouped pass
    (``moe_grouped_kernel``); a caller that hands none gets what the engine
    resolves on this backend (``moe_mixer``)."""
    c = config
    B, Pn = tokens.shape
    positions = jnp.arange(Pn)
    real = positions[None, :] < lengths[:, None]                   # (B, P)
    flash = (_flash_mode(Pn) if use_flash is None
             else ("compiled" if use_flash else None))
    if kernel is None:      # as moe_mixer resolves it; the commit reads it too
        kernel = backend_kernel()
    groups = 1
    while B * Pn * (c.heads // groups) > EXPAND_ROWS_X_HEADS and \
            c.heads % (groups * 2) == 0:
        groups *= 2
    hg = c.heads // groups

    def attend(q_nope, q_pe, rows, w_uk, w_uv):
        """``hg`` heads' attention over the expanded rows: (B, P, hg, v)."""
        c_kv = rows[..., :c.kv_rank]
        k_pe = rows[..., c.kv_rank:c.kv_rank + c.rope_dim]
        with jax.named_scope("mla_expand"):
            k = jnp.concatenate([
                jnp.einsum("bpc,hdc->bphd", c_kv, w_uk),
                jnp.broadcast_to(
                    k_pe[:, :, None, :], (B, Pn, hg, c.rope_dim)),
            ], axis=-1)
            v = jnp.einsum("bpc,hcd->bphd", c_kv, w_uv)
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
        with jax.named_scope("kv_read"):
            if flash is not None:
                # causality alone hides the right-padding from real rows;
                # the lengths spare the kernel the padding's blocks (a
                # prompt fills 3/4 of its power-of-two bucket in the mean,
                # 9/16 of the bucket's causal pairs)
                from langstream_tpu.ops.flash_attention import flash_attention

                return flash_attention(
                    q, k, v, causal=True, scale=c.attn_scale,
                    block_q=FLASH_BLOCK, block_k=FLASH_BLOCK,
                    interpret=(flash == "interpret"), lengths=lengths)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
            mask = (positions[:, None] >= positions[None, :])[None] \
                & real[:, None, :]
            s = jnp.where(mask[:, None], s * c.attn_scale, NEG_INF)
            return jnp.einsum(
                "bhqk,bkhd->bqhd", jax.nn.softmax(s, -1).astype(v.dtype), v)

    def by_columns(w, width):
        """``(rows, heads * width)`` as ``(groups, rows, hg * width)``."""
        return jnp.moveaxis(w.reshape(w.shape[0], groups, hg * width), 1, 0)

    def attention(x, ap):
        h = _rms_norm(x, ap["norm"], c.norm_eps)
        with jax.named_scope("mla_q"):
            c_q = _compressed_q(c, ap, h)
        with jax.named_scope("mla_kv"):
            rows = _latent_rows(c, ap, h, positions)               # (B,P,W)

        def group(outs, g):
            i, w_nope, w_pe, w_uk, w_uv = g
            with jax.named_scope("mla_q"):
                q_nope, q_pe = _queries(c, c_q, w_nope, w_pe, positions)
            out = attend(q_nope, q_pe, rows, w_uk, w_uv)
            with jax.named_scope("attn_out"):
                # the group's heads into their columns, in place
                return jax.lax.dynamic_update_slice_in_dim(
                    outs, out.reshape(B, Pn, hg * c.v_dim),
                    i * (hg * c.v_dim), axis=2), None

        by_heads = lambda w: w.reshape((groups, hg) + w.shape[1:])  # noqa: E731
        outs, _ = jax.lax.scan(
            group, jnp.zeros((B, Pn, c.heads * c.v_dim), x.dtype),
            (jnp.arange(groups, dtype=jnp.int32),
             by_columns(ap["w_q_nope"], c.nope_dim),
             by_columns(ap["w_q_pe"], c.rope_dim), by_heads(ap["w_uk"]),
             by_heads(ap["w_uv"])))
        with jax.named_scope("attn_out"):
            return x + jnp.einsum("bpd,dh->bph", outs, ap["w_o"]), rows

    def dense_layer(x, lp):
        x, rows = attention(x, lp["attn"])
        return _dense_ffn(c, lp["ffn"], x), rows

    moe = params["sparse"]["moe"]

    def sparse_layer(x, xs):
        ap, ep, i = xs
        x, rows = attention(x, ap)
        h = _rms_norm(x, ep["norm"], c.norm_eps).reshape(B * Pn, c.hidden)
        ep = dict(ep, w_up=moe["w_up"], w_down=moe["w_down"])
        out, _, chosen = moe_mixer(
            c, ep, h, real.reshape(-1), layer=i, kernel=kernel)
        return x + out.reshape(B, Pn, c.hidden), \
            (rows, chosen.reshape(B, Pn, -1))

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    x, rows_d = jax.lax.scan(dense_layer, x, params["dense"])
    x, (rows_s, routed) = jax.lax.scan(
        sparse_layer, x,
        # the routed experts' stacks stay out of what the scan slices: the
        # grouped pass reads them by the layer's index (moe.py)
        (params["sparse"]["attn"],
         {k: v for k, v in moe.items() if k not in ("w_up", "w_down")},
         jnp.arange(c.sparse_layers, dtype=jnp.int32)))
    with jax.named_scope("lm_head"):
        x = _rms_norm(x, params["final_norm"], c.norm_eps)
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].clip(0), axis=1).squeeze(1)
        logits = _logits(params, last)
    with jax.named_scope("mla_kv"):
        # (the rows begin at position 0: None says so to the commit)
        pool = write_rows(
            pool, jnp.concatenate([rows_d, rows_s], axis=0), block_tables,
            None, real, kernel)
    return logits, pool, routed


# ---------------------------------------------------------------------------
# decode: the absorbed path
# ---------------------------------------------------------------------------


def absorbed_attention(
    c: LatentConfig, ap: dict, x: jax.Array, positions: jax.Array,
    cache_partial: Callable, rowbuf: jax.Array, buf_mask: jax.Array,
    step_idx,
):
    """One decode step of one layer's attention over ``x (B, H)``: the
    latent rows of the pool through ``cache_partial(q (B, heads, row_width))
    -> (acc, m, l)``, the chunk's own rows ``rowbuf (B, K, row_width)`` (this
    step's written at ``step_idx``) under ``buf_mask (1, K)``. Returns ``(x
    + attention, this step's rows (B, row_width))``."""
    B = x.shape[0]
    h = _rms_norm(x, ap["norm"], c.norm_eps)
    with jax.named_scope("mla_q"):
        q_nope, q_pe = _queries(
            c, _compressed_q(c, ap, h), ap["w_q_nope"], ap["w_q_pe"],
            positions)
    with jax.named_scope("mla_kv"):
        row = _latent_rows(c, ap, h, positions)                    # (B, W)
        rb = jax.lax.dynamic_update_slice_in_dim(
            rowbuf, row[:, None], step_idx, axis=1)                # (B, K, W)
    with jax.named_scope("mla_absorb"):
        q = _padded(
            c, [jnp.einsum("bhd,hdc->bhc", q_nope, ap["w_uk"]), q_pe])
    with jax.named_scope("kv_read"):
        acc_c, m_c, l_c = cache_partial(q)
        s = jnp.einsum("bhw,btw->bht", q, rb).astype(jnp.float32)
        s = jnp.where(buf_mask[:, None, :], s * c.attn_scale, NEG_INF)
        m_b = jnp.max(s, axis=-1)
        p_b = jnp.where(buf_mask[:, None, :], jnp.exp(s - m_b[..., None]), 0.0)
        acc_b = jnp.einsum(
            "bht,btc->bhc", p_b.astype(rb.dtype), rb[..., :c.kv_rank]
        ).astype(jnp.float32)
        o_lat = merge_partial_attention([
            (acc_c, m_c, l_c), (acc_b, m_b, jnp.sum(p_b, axis=-1)),
        ]).astype(x.dtype)                                   # (B, heads, rank)
    with jax.named_scope("mla_absorb"):
        out = jnp.einsum("bhc,hcd->bhd", o_lat, ap["w_uv"])
    with jax.named_scope("attn_out"):
        return x + out.reshape(B, c.heads * c.v_dim) @ ap["w_o"], row


def latent_decode_chunk_paged(
    config: LatentConfig,
    params: dict,
    tokens0: jax.Array,       # (B,)
    base_lengths: jax.Array,  # (B,)
    active: jax.Array,        # (B,) bool
    pool: jax.Array,          # read-only during the chunk
    block_tables: jax.Array,  # (B, max_blocks)
    sample_fn: Callable,
    key: jax.Array,
    num_steps: int,
    num_read_blocks: int,
    kernel: str = "xla",      # "xla" | "pallas" | "pallas-interpret"
    sample_extras=None,       # (presences, frequencies, counts0)
    return_packed: bool = False,
):
    """K fused decode steps through the absorbed attention. The pool is
    read-only and the new latent rows gather in a chunk buffer (one scatter
    at the end, :func:`langstream_tpu.models.paged.write_rows`), as in the
    other families' chunks.

    Returns ``(chunk_tokens, chunk_logprobs, final_tokens, final_lengths,
    pool, load, routed)`` where ``load (expert layers, experts_held)`` counts
    the chosen pairs each held expert got over the chunk's active rows and
    ``routed (steps, expert layers, B, k)`` are the experts the router chose
    (for the reference check); ``return_packed=True`` folds tokens, logprobs
    and ``load`` into one int32 array in their place and leaves ``routed``
    out."""
    c = config
    B = tokens0.shape[0]
    nd, ns, W = c.dense_layers, c.sparse_layers, c.row_width
    adv = active.astype(jnp.int32)
    pen = sample_extras is not None
    counts0 = sample_extras[2] if pen else None

    def cache_partial(layer):
        """The pool's part of one layer's attention, through the read the
        engine selected."""
        read = latent_read_xla if kernel == "xla" else partial(
            latent_read, interpret=(kernel == "pallas-interpret"))
        return lambda q: read(
            q, pool, layer, block_tables, base_lengths,
            num_read_blocks=num_read_blocks, value_dim=c.kv_rank,
            scale=c.attn_scale)

    def step(carry, step_idx):
        tokens, rowbuf, key, load = carry[:4]
        counts = carry[4] if pen else None
        with jax.named_scope("sample"):
            key, sub = jax.random.split(key)
        with jax.named_scope("embed"):
            x = params["embed"][tokens]
        buf_mask = jnp.arange(num_steps)[None, :] <= step_idx      # (1, K)
        positions = base_lengths + step_idx

        def attention(x, ap, layer):
            return absorbed_attention(
                c, ap, x, positions, cache_partial(layer),
                jax.lax.dynamic_index_in_dim(rowbuf, layer, keepdims=False),
                buf_mask, step_idx)

        def dense_layer(x, xs):
            lp, layer = xs
            x, row = attention(x, lp["attn"], layer)
            return _dense_ffn(c, lp["ffn"], x), row

        def sparse_layer(x, xs):
            ap, ep, layer = xs
            x, row = attention(x, ap, layer)
            out, load_i, chosen = moe_mixer(
                c, ep, _rms_norm(x, ep["norm"], c.norm_eps), active)
            return x + out, (row, load_i, chosen)

        x, rows_d = jax.lax.scan(
            dense_layer, x, (params["dense"], jnp.arange(nd, dtype=jnp.int32)))
        x, (rows_s, load_step, chosen) = jax.lax.scan(
            sparse_layer, x,
            (params["sparse"]["attn"], params["sparse"]["moe"],
             nd + jnp.arange(ns, dtype=jnp.int32)))
        with jax.named_scope("mla_kv"):
            rowbuf = jax.lax.dynamic_update_slice_in_dim(
                rowbuf, jnp.concatenate([rows_d, rows_s])[:, :, None],
                step_idx, axis=2)
        with jax.named_scope("lm_head"):
            logits = _logits(
                params, _rms_norm(x, params["final_norm"], c.norm_eps))
        with jax.named_scope("sample"):
            nxt, lp_ = (sample_fn(logits, sub, counts) if pen
                        else sample_fn(logits, sub))
            nxt = jnp.where(active, nxt, tokens)
        out_carry = (nxt, rowbuf, key, load + load_step)
        if pen:
            out_carry += (counts.at[jnp.arange(B), nxt].add(adv),)
        return out_carry, (nxt, lp_, chosen)

    carry0 = (tokens0, jnp.zeros((c.layers, B, num_steps, W), c.dtype), key,
              jnp.zeros((ns, c.experts_held), jnp.int32))
    if pen:
        carry0 += (counts0,)
    out_carry, (chunk_tokens, chunk_lps, routed) = jax.lax.scan(
        step, carry0, jnp.arange(num_steps))
    final_tokens, rowbuf, _, load = out_carry[:4]
    with jax.named_scope("mla_kv"):
        pool = write_rows(
            pool, rowbuf, block_tables, base_lengths,
            jnp.broadcast_to(active[:, None], (B, num_steps)), kernel)
    final_lengths = base_lengths + num_steps * adv
    if return_packed:
        packed = jnp.concatenate(
            [pack_tokens_logprobs(chunk_tokens, chunk_lps), load.reshape(-1)])
        return packed, final_tokens, final_lengths, pool
    return (chunk_tokens, chunk_lps, final_tokens, final_lengths, pool, load,
            routed)


# ---------------------------------------------------------------------------
# the family, as the serving engine asks it (models/family.py)
# ---------------------------------------------------------------------------


def _family_prefill(mc, params, residents, tokens, lengths, tables,
                    use_flash=None, kernel=None):
    pool, cache_v = residents
    logits, pool, _routed = latent_prefill_paged(
        mc, params, tokens, lengths, pool, tables, use_flash=use_flash,
        kernel=kernel)
    return logits, (pool, cache_v)


def _family_decode_chunk(mc, params, residents, tokens, lengths, active,
                         tables, sample_fn, key, num_steps, **kernels):
    pool, cache_v = residents
    return latent_decode_chunk_paged(
        mc, params, tokens, lengths, active, pool, tables, sample_fn, key,
        num_steps, **kernels) + (cache_v,)


FAMILY = Family(
    name="latent",
    config_class=LatentConfig,
    presets={"deepseek-tiny": "tiny", "deepseek-v2-ep8": "deepseek_v2_ep8"},
    what="keeps one pool of latent rows, not K and V",
    refusals={
        "prefix-cache": "no continuation prefill over a latent history yet, "
                        "so an adopted prefix cannot be extended; set "
                        "prefix-cache: false",
        "prefill-chunk": "no continuation prefill over a latent history "
                         "yet; set prefill-chunk: 0",
        "speculative-drafts": "the verify step reads K/V history through the "
                              "multi-query kernel; set speculative-drafts: 0",
        "pool-role": "the handoff's payload carries a K and a V array; use "
                     "pool-role: combined",
        "kv-quantize": "int8 rows carry one scale a K/V head; a latent row "
                       "has no head axis",
        "journal-dir": "journal replay re-admits by K/V-era rules untested "
                       "over a latent pool",
    },
    init_params=init_latent_params,
    # one array of latent rows; nothing in the value pool's place
    init_pools=lambda mc, layout, slots: (
        lambda: init_latent_pool(mc, layout), None),
    prefill=_family_prefill,
    decode_chunk=_family_decode_chunk,
    # the dense family's signature (no state rides behind the caches);
    # cache_v is None, as init_latent_pool left it
    residents=2,
    donate=(1,),
    # one decode program a chunk size: the latent read fetches a slot's live
    # blocks and nothing else, whatever the window, and a slot of this
    # family is long (a window bucket every power of two would be four more
    # programs of its five-layer step)
    one_decode_window=True,
    expert_kernels=True,
)
