"""Decoder whose every attention layer keeps TWO kinds of history at once:
the exact K/V rows of the query's own block-aligned window, and one summary
row for every chunk of every window that has closed (EVA: Zheng, Yuan, Wang
and Kong, "Efficient Attention via Control Variates", ICLR 2023, in the
deterministic form the byte-level EvaByte release serves).

**The layer** (``d`` = ``head_dim``, ``W`` = ``window``, ``C`` = ``chunk``;
the residual ``x`` is float32, ``fp32_skip_add``):

1. ``h = RMSNorm(x; gain 1 + g)`` in the model's type (``norm_add_unit_
   offset``); ``q, k, v = h W_q, h W_k, h W_v``, one key head a query head;
   half-split rotary over the whole head at absolute positions.
2. **Summaries**, a head, from two learned vectors a head a layer, ``phi``
   and ``mu``: for a chunk ``c`` whose ``C`` positions are all written,
   ``a_j = softmax_{j in c}(phi . k_j)``, ``v~_c = sum_j a_j v_j``, ``k~_c =
   mean_{j in c} k_j + mu``. Keys are summarised rotated, as stored.
3. **Scores**, query ``i`` in window ``w = i // W``: exact ``q_i . k_j /
   sqrt(d)`` for ``j`` in ``[wW, i]``, summarised ``q_i . k~_c / sqrt(d)`` for
   every chunk ``c < (W / C) w`` (all chunks of closed windows, none of its
   own); ONE softmax over both sets, softmax and accumulation in float32.
   ``x <- x + o W_o``.
4. ``x <- x + W_down[silu(W_gate h') * (W_up h')]``, ``h' = RMSNorm(x; 1 + g')``.
5. Final norm, a head of ``pred_heads x vocab_size`` columns laid
   (prediction head, byte), logits float32 (``fp32_logits``); **what is
   served is prediction head 0's** (the next byte); heads 1.. (the release's
   multi-byte self-speculation) are computed and handed to whoever asks.

What the published config has no key for is written here as the reading
taken, with no field for the other; ``bench/configs/evabyte-6.5b-8l.json``
lists each under ``assumed`` beside the reference's fault that is the other
reading.

**Two pools** (:func:`langstream_tpu.models.paged.init_kv_pool`), both of
rows ``heads * head_dim`` wide over all layers:

- the *window* pool, a RING of ``W / block_size`` blocks a slot (logical
  block ``n`` in ring block ``n % ring``): the rows before ``wW`` are dead
  the moment window ``w`` opens, so the read is told ``firsts = wW`` and the
  ring needs no spare block;
- the *summary* pool, in which a slot holds every summary row it has made,
  row ``c`` at position ``c``, allocated a window ahead and READ up to
  ``(W / C) w`` rows whatever has been written past that.

:class:`langstream_tpu.models.paged.BlockManager` keeps both
(``summary_window`` / ``summary_chunk`` and ``window_ring``); the programs
are handed ONE table, ``[the summary kind's columns | the ring's columns]``.

**Decode** commits at every step: the step's K/V rows of all layers go into
the ring (one commit), and for the slots whose position closes a chunk
(``(i + 1) % C == 0``) the chunk's ``C`` rows are read back from the ring,
summarised, and ONE row a layer goes into the summary pool. Nothing else
marks a window's close than ``w`` in the two read lengths. A layer's read is
three partials under one softmax: the ring from ``firsts``, the summary pool
to ``(W / C) w``, and the step's own row.

**Prefill** is one program a bucket: the summaries of all chunks made once
from the rotated keys, attention through :func:`langstream_tpu.ops.
eva_flash.eva_flash` (windows causal, each against the summaries of the
windows before it), and what it writes is the LAST window's exact rows and
all the prompt's closed chunks' summaries.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from langstream_tpu.models.family import Family
from langstream_tpu.models.hybrid import backend_kernel
from langstream_tpu.models.llama import _apply_rope, _flash_mode, _rope
from langstream_tpu.models.llama_paged import (
    _cache_partial_xla,
    pack_tokens_logprobs,
)
from langstream_tpu.models.moe import silu_gated
from langstream_tpu.models.paged import (
    PagedLayout,
    init_kv_pool,
    write_rows_pair,
)
from langstream_tpu.models.swa import split_tables  # [first kind | ring]
from langstream_tpu.ops.paged_attention import (
    NEG_INF,
    merge_partial_attention,
    paged_attention_partial,
)

#: query and key rows of one block of the prefill's kernel (models/swa.py
#: FLASH_BLOCK)
FLASH_BLOCK = 1024
#: rows of one pass of the gated MLP in a prefill: ``[gate | up]`` of a
#: 32,768-row prompt at width 11,008 is 1.4 GB in bfloat16 whole
FFN_ROWS = 4096
#: rows of one pass of a prefill's summaries: the float32 copies of the
#: keys and values of a 32,768-row prompt are 0.54 GB each whole
SUMMARY_ROWS = 4096


@dataclasses.dataclass(frozen=True)
class EvaConfig:
    # the fields the dense family's config has, under the same names
    vocab_size: int = 320
    hidden: int = 4096
    layers: int = 8
    heads: int = 32
    kv_heads: int = 32
    head_dim: int = 128
    intermediate: int = 11008
    rope_theta: float = 100000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 32768
    dtype: Any = jnp.bfloat16
    # EVA
    window: int = 2048               # query i sees the rows [W (i // W), i]
    chunk: int = 16                  # positions a summary row stands for
    pred_heads: int = 8              # the head's predictions: bytes t+1 ..
    #: recurrent state beside the pools: none
    state_bytes_per_slot: int = 0

    def __post_init__(self):
        if self.kv_heads != self.heads:
            raise ValueError("one key-value head a query head")
        if self.window % self.chunk:
            raise ValueError("a window is whole chunks")

    @classmethod
    def evabyte_6_5b_8l(cls, max_seq_len: int = 32768) -> "EvaConfig":
        """EvaByte/EvaByte as stage 0 of four pipeline stages: layers 0-7 of
        32 whole, all heads, the whole vocabulary (the last norm and the head
        ride here so that the stage gives logits)."""
        return cls(max_seq_len=max_seq_len)

    @classmethod
    def tiny(cls, max_seq_len: int = 256) -> "EvaConfig":
        """Test size of the same grammar: a window of 32 rows in chunks of
        4, two prediction heads."""
        return cls(
            vocab_size=320, hidden=64, layers=2, heads=4, kv_heads=4,
            head_dim=16, intermediate=96, rope_theta=10000.0, window=32,
            chunk=4, pred_heads=2, max_seq_len=max_seq_len)

    @property
    def per_window(self) -> int:
        """Summary rows a closed window has."""
        return self.window // self.chunk

    def ring_blocks(self, block_size: int) -> int:
        """Blocks of a slot's ring: the window's rows, no spare (the windows
        are block-aligned: the ring is emptied at a window's edge)."""
        if self.window % block_size or block_size % self.chunk:
            raise ValueError(
                f"a window of {self.window} rows in chunks of {self.chunk} "
                f"does not lie in whole blocks of {block_size}")
        return self.window // block_size

    def summary_blocks(self, block_size: int) -> int:
        """Table columns the summary rows of a slot of ``max_seq_len`` use."""
        return -(-(self.max_seq_len // self.chunk) // block_size)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_eva_params(config: EvaConfig, key: jax.Array | None = None) -> dict:
    """Random parameters from a key, one jitted draw a leaf, at the fan-in's
    scale; the embedding at a spread of 1 (it enters the first layer as it
    is). A norm's stored gain ``g`` is uniform in [-0.5, 0.5] (the gain is
    ``1 + g``: leaving the offset out changes the logits); ``phi`` and
    ``mu`` are uniform in ``[-1, 1] / sqrt(head_dim)`` and NOT at zero, so
    that leaving either out moves the logits."""
    c = config
    key = key if key is not None else jax.random.PRNGKey(0)
    H, D, I, names = c.hidden, c.head_dim, c.intermediate, iter(range(10 ** 6))

    def normal(shape, fan_in):
        k = jax.random.fold_in(key, next(names))
        scale = 1.0 / math.sqrt(fan_in)
        return jax.jit(lambda k: (
            jax.random.normal(k, shape, jnp.float32) * scale).astype(c.dtype))(k)

    def uniform(shape, bound):
        k = jax.random.fold_in(key, next(names))
        return jax.random.uniform(
            k, shape, jnp.float32, -bound, bound).astype(c.dtype)

    layers = []
    for _ in range(c.layers):
        layers.append({
            "attn": {
                "norm": uniform((H,), 0.5),
                "wq": normal((H, c.heads * D), H),
                "wk": normal((H, c.heads * D), H),
                "wv": normal((H, c.heads * D), H),
                "wo": normal((c.heads * D, H), c.heads * D),
                "phi": uniform((c.heads, D), 1.0 / math.sqrt(D)),
                "mu": uniform((c.heads, D), 1.0 / math.sqrt(D)),
            },
            "ffn": {
                "norm": uniform((H,), 0.5),
                "w_up": normal((H, 2 * I), H),          # [gate | up]
                "w_down": normal((I, H), I),
            },
        })
    return {
        "embed": normal((c.vocab_size, H), 1),
        "final_norm": uniform((H,), 0.5),
        "lm_head": normal((H, c.pred_heads * c.vocab_size), H),
        "layers": layers,
    }


# ---------------------------------------------------------------------------
# what both programs share
# ---------------------------------------------------------------------------


def _norm(c: EvaConfig, x: jax.Array, g: jax.Array) -> jax.Array:
    """``RMSNorm(x; gain 1 + g)`` of the float32 residual, in the model's
    type."""
    scale = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + c.norm_eps)
    return (x * scale * (1.0 + g.astype(jnp.float32))).astype(c.dtype)


def _projections(c: EvaConfig, ap: dict, x: jax.Array, cos, sin):
    """``q, k, v (..., heads, D)`` of a layer's input, q and k rotated."""
    lead = x.shape[:-1]
    with jax.named_scope("attn_qkv"):
        h = _norm(c, x, ap["norm"])
        q, k, v = ((h @ ap[w]).reshape(lead + (c.heads, c.head_dim))
                   for w in ("wq", "wk", "wv"))
    with jax.named_scope("rope"):
        return _apply_rope(q, cos, sin), _apply_rope(k, cos, sin), v


def _attention_out(ap: dict, x: jax.Array, out: jax.Array) -> jax.Array:
    with jax.named_scope("attn_out"):
        return x + (out @ ap["wo"]).astype(jnp.float32)


def _ffn(c: EvaConfig, fp: dict, x: jax.Array) -> jax.Array:
    """``x + W_down[silu(W_gate h) * W_up h]`` over rows ``x (T, H)``,
    ``FFN_ROWS`` at a time."""
    with jax.named_scope("ffn"):
        h = _norm(c, x, fp["norm"])
        one = lambda rows: silu_gated(rows @ fp["w_up"]) @ fp["w_down"]  # noqa: E731
        T = h.shape[0]
        if T > FFN_ROWS and T % FFN_ROWS == 0:
            f = jax.lax.map(one, h.reshape(T // FFN_ROWS, FFN_ROWS, -1)
                            ).reshape(T, -1)
        else:
            f = one(h)
        return x + f.astype(jnp.float32)


def _logits(c: EvaConfig, params: dict, x: jax.Array) -> jax.Array:
    """Every prediction head's logits, float32: ``(..., pred_heads *
    vocab_size)``, laid (prediction head, byte)."""
    h = _norm(c, x, params["final_norm"])
    return jnp.dot(h, params["lm_head"], preferred_element_type=jnp.float32)


def summarise(phi: jax.Array, mu: jax.Array, k: jax.Array, v: jax.Array):
    """``(k~, v~) (..., heads, D)`` of chunks ``k, v (..., C, heads, D)``:
    ``v~`` the ``softmax_j(phi . k_j)``-weighted values, ``k~`` the mean key
    plus ``mu``; ``phi`` and ``mu`` broadcast against ``(..., heads, D)``.
    The softmax over a chunk's ``C`` keys and both sums in float32."""
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    phi, mu = phi.astype(jnp.float32), mu.astype(jnp.float32)
    s = jnp.sum(kf * phi[..., None, :, :], axis=-1)
    a = jax.nn.softmax(s, axis=-2)[..., None]              # (..., C, heads, 1)
    v_sum = jnp.sum(a * vf, axis=-3)
    k_sum = jnp.mean(kf, axis=-3) + mu
    return k_sum.astype(k.dtype), v_sum.astype(v.dtype)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def eva_prefill_paged(
    config: EvaConfig,
    params: dict,
    tokens: jax.Array,        # (B, P) int32, right-padded
    lengths: jax.Array,       # (B,) true lengths
    pool_k: jax.Array,        # the summary pool (layers, nb, bs, heads*D)
    pool_v: jax.Array,
    wpool: dict,              # the ring {"k", "v"}: (layers, ring nb, bs, heads*D)
    block_tables: jax.Array,  # (B, 2 x max_blocks): [summary | ring]
    use_flash: bool | None = None,
    kernel: str | None = None,
):
    """Prompt forward. Returns ``(head 0's last-token logits (B, V), pool_k,
    pool_v, wpool, every head's (B, pred_heads * V))``. Written: into the
    summary pool the rows of every chunk that lies whole inside the prompt,
    into the ring the rows of the last window that has a row (none where a
    window closes exactly at the prompt's end: its summaries stand for it)."""
    c = config
    B, Pn = tokens.shape
    W, C, D, HD = c.window, c.chunk, c.head_dim, c.heads * c.head_dim
    Wl = min(W, Pn)
    if Pn % Wl or Pn % C:
        raise ValueError(
            f"a bucket of {Pn} rows is not whole windows of {W} and chunks "
            f"of {C}")
    positions = jnp.arange(Pn)
    real = positions[None, :] < lengths[:, None]                   # (B, P)
    flash = (_flash_mode(Pn) if use_flash is None
             else ("compiled" if use_flash else None))
    if kernel is None:
        kernel = backend_kernel()
    cos, sin = _rope(positions, D, c.rope_theta)
    # the last window with a row, and its rows of the bucket
    first = (lengths // W) * W                                     # (B,)
    last_rows = jax.vmap(lambda a, f: jax.lax.dynamic_slice_in_dim(
        a, f, Wl, axis=0))

    def summaries(ap, k, v):
        """``(k~, v~) (B, P / C, heads, D)`` of every chunk of the bucket,
        ``SUMMARY_ROWS`` rows at a time."""
        passes = Pn // SUMMARY_ROWS if Pn % SUMMARY_ROWS == 0 else 1
        got = jax.lax.map(
            lambda kv: summarise(ap["phi"], ap["mu"], *kv),
            tuple(jnp.moveaxis(a.reshape(B, passes, -1, C, c.heads, D), 1, 0)
                  for a in (k, v)))
        return tuple(jnp.moveaxis(s, 0, 1).reshape(B, Pn // C, c.heads, D)
                     for s in got)

    def attend(q, k, v, k_sum, v_sum):
        if flash is not None:
            from langstream_tpu.ops.eva_flash import eva_flash

            return eva_flash(
                q, k, v, k_sum, v_sum, lengths, window=W,
                per_window=c.per_window, block_q=FLASH_BLOCK,
                block_k=FLASH_BLOCK, interpret=(flash == "interpret"))
        scale = 1.0 / math.sqrt(D)
        own = (positions[:, None] >= positions[None, :]) & (
            positions[None, :] >= (positions[:, None] // W) * W)
        seen = jnp.arange(Pn // C)[None, :] < (
            positions[:, None] // W) * c.per_window
        s = jnp.concatenate([
            jnp.where((own[None] & real[:, None, :])[:, None],
                      jnp.einsum("bqhd,bshd->bhqs", q, k
                                 ).astype(jnp.float32) * scale, NEG_INF),
            jnp.where(seen[None, None],
                      jnp.einsum("bqhd,bshd->bhqs", q, k_sum
                                 ).astype(jnp.float32) * scale, NEG_INF),
        ], axis=-1)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum(
            "bhqs,bshd->bqhd", p, jnp.concatenate([v, v_sum], axis=1))

    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.float32)
    ring_rows, summary_rows = ([], []), ([], [])
    for lp in params["layers"]:
        ap = lp["attn"]
        q, k, v = _projections(c, ap, x, cos, sin)
        with jax.named_scope("eva_summarise_prefill"):
            k_sum, v_sum = summaries(ap, k, v)
        with jax.named_scope("eva_flash"):
            out = attend(q, k, v, k_sum, v_sum)
        x = _attention_out(ap, x, out.reshape(B, Pn, HD))
        x = _ffn(c, lp["ffn"], x.reshape(B * Pn, c.hidden)
                 ).reshape(B, Pn, c.hidden)
        for held, rows in zip(ring_rows, (k, v)):
            held.append(last_rows(rows.reshape(B, Pn, HD), first))
        for held, rows in zip(summary_rows, (k_sum, v_sum)):
            held.append(rows.reshape(B, Pn // C, HD))
    with jax.named_scope("lm_head"):
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].clip(0), axis=1).squeeze(1)
        heads = _logits(c, params, last)
    summary_tables, ring_tables = split_tables(block_tables)
    with jax.named_scope("kv_write"):
        closed = jnp.arange(Pn // C)[None, :] < (lengths // C)[:, None]
        pool_k, pool_v = write_rows_pair(
            (pool_k, pool_v), (jnp.stack(r) for r in summary_rows),
            summary_tables, None, closed, kernel)
    with jax.named_scope("eva_write"):
        held = (first[:, None] + jnp.arange(Wl)[None, :]) < lengths[:, None]
        wpool = dict(zip("kv", write_rows_pair(
            (wpool["k"], wpool["v"]), (jnp.stack(r) for r in ring_rows),
            ring_tables, first, held, kernel)))
    return heads[:, :c.vocab_size], pool_k, pool_v, wpool, heads


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def eva_decode_chunk_paged(
    config: EvaConfig,
    params: dict,
    tokens0: jax.Array,       # (B,)
    base_lengths: jax.Array,  # (B,)
    active: jax.Array,        # (B,) bool
    pool_k: jax.Array,        # the summary pool
    pool_v: jax.Array,
    wpool: dict,              # the ring
    block_tables: jax.Array,  # (B, 2 x max_blocks): [summary | ring]
    sample_fn: Callable,
    key: jax.Array,
    num_steps: int,
    num_read_blocks: int,
    kernel: str = "xla",      # "xla" | "pallas" | "pallas-interpret"
    sample_extras=None,       # (presences, frequencies, counts0)
    return_packed: bool = False,
):
    """K fused decode steps, each of which commits (the module's docstring).
    A frozen lane (``active`` false) reads nothing and commits nothing.

    Returns ``(chunk_tokens, chunk_logprobs, final_tokens, final_lengths,
    pool_k, pool_v, wpool, heads)`` where ``heads (steps, B, pred_heads *
    V)`` are every prediction head's float32 logits;
    ``return_packed=True`` folds tokens and logprobs into one int32 array in
    their place and leaves ``heads`` out (they are computed all the same:
    the sampler reads head 0 of them behind a barrier)."""
    c = config
    B = tokens0.shape[0]
    W, C, D, HD = c.window, c.chunk, c.head_dim, c.heads * c.head_dim
    L, bs = pool_k.shape[0], pool_k.shape[2]
    scale = 1.0 / math.sqrt(D)
    adv = active.astype(jnp.int32)
    pen = sample_extras is not None
    counts0 = sample_extras[2] if pen else None
    summary_tables, ring_tables = split_tables(block_tables)
    ring, summary_cols = c.ring_blocks(bs), c.summary_blocks(bs)
    phi = jnp.stack([lp["attn"]["phi"] for lp in params["layers"]])[:, None]
    mu = jnp.stack([lp["attn"]["mu"] for lp in params["layers"]])[:, None]

    def read(q, pk, pv, layer, tables, lengths, firsts, columns):
        """One pool's part of a layer, through the read the engine selected."""
        if kernel == "xla":
            return _cache_partial_xla(
                c, q, pk, pv, layer, tables, lengths, columns, firsts=firsts)
        return paged_attention_partial(
            q, pk, pv, layer, tables, lengths,
            num_read_blocks=num_read_blocks, kv_heads=c.heads, head_dim=D,
            scale=scale, interpret=(kernel == "pallas-interpret"),
            firsts=firsts)

    def chunk_of(pool, block, offset):
        """``(L, B, C, heads * D)``: the ``C`` rows from ``offset`` of each
        slot's ``block``."""
        return jax.vmap(
            lambda b, o: jax.lax.dynamic_slice(
                pool, (0, b, o, 0), (L, 1, C, HD))[:, 0],
            out_axes=1)(block, offset)

    def step(carry, step_idx):
        tokens, pool_k, pool_v, ring_k, ring_v, key = carry[:6]
        counts = carry[6] if pen else None
        with jax.named_scope("sample"):
            key, sub = jax.random.split(key)
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(jnp.float32)
        i = base_lengths + step_idx          # the position of this step's row
        firsts = (i // W) * W
        # a slot whose window has just opened has no row behind it there; a
        # length of 0 is the reads' word for "nothing" (a first row at the
        # length would leave the kernel's walk without a tile to wait for)
        ring_rows = jnp.where(active & (i > firsts), i, 0)
        summary_rows = jnp.where(active, (i // W) * c.per_window, 0)
        cos, sin = _rope(i, D, c.rope_theta)
        ks, vs = [], []
        for layer, lp in enumerate(params["layers"]):
            ap = lp["attn"]
            q, k, v = _projections(c, ap, x, cos, sin)
            with jax.named_scope("eva_read"):
                own = jnp.sum(
                    q.astype(jnp.float32) * k.astype(jnp.float32), -1) * scale
                out = merge_partial_attention([
                    read(q, ring_k, ring_v, layer, ring_tables, ring_rows,
                         firsts, ring),
                    read(q, pool_k, pool_v, layer, summary_tables,
                         summary_rows, None, summary_cols),
                    (v.astype(jnp.float32), own, jnp.ones_like(own)),
                ]).astype(c.dtype).reshape(B, HD)
            x = _attention_out(ap, x, out)
            x = _ffn(c, lp["ffn"], x)
            ks.append(k.reshape(B, 1, HD))
            vs.append(v.reshape(B, 1, HD))
        with jax.named_scope("lm_head"):
            # every prediction head is computed; head 0 is what is served
            heads = jax.lax.optimization_barrier(_logits(c, params, x))
            logits = heads[:, :c.vocab_size]
        with jax.named_scope("sample"):
            nxt, lp_ = (sample_fn(logits, sub, counts) if pen
                        else sample_fn(logits, sub))
            nxt = jnp.where(active, nxt, tokens)
        with jax.named_scope("eva_write"):
            ring_k, ring_v = write_rows_pair(
                (ring_k, ring_v), (jnp.stack(r) for r in (ks, vs)),
                ring_tables, i, active[:, None], kernel)
        with jax.named_scope("eva_summarise"):
            # the slots whose row closes a chunk: its C rows back from the
            # ring (the commit above is among them), one row a layer out
            closes = active & ((i + 1) % C == 0)
            block = jnp.take_along_axis(
                ring_tables, (i // bs)[:, None], axis=1)[:, 0]
            offset = (i % bs) // C * C
            k_sum, v_sum = summarise(
                phi, mu,
                chunk_of(ring_k, block, offset).reshape(L, B, C, c.heads, D),
                chunk_of(ring_v, block, offset).reshape(L, B, C, c.heads, D))
            pool_k, pool_v = write_rows_pair(
                (pool_k, pool_v),
                (s.reshape(L, B, 1, HD) for s in (k_sum, v_sum)),
                summary_tables, i // C, closes[:, None], kernel)
        out_carry = (nxt, pool_k, pool_v, ring_k, ring_v, key)
        if pen:
            out_carry += (counts.at[jnp.arange(B), nxt].add(adv),)
        return out_carry, (nxt, lp_) + (() if return_packed else (heads,))

    carry0 = (tokens0, pool_k, pool_v, wpool["k"], wpool["v"], key)
    if pen:
        carry0 += (counts0,)
    out_carry, ys = jax.lax.scan(step, carry0, jnp.arange(num_steps))
    final_tokens, pool_k, pool_v, ring_k, ring_v = out_carry[:5]
    wpool = {"k": ring_k, "v": ring_v}
    final_lengths = base_lengths + num_steps * adv
    if return_packed:
        return (pack_tokens_logprobs(ys[0], ys[1]), final_tokens,
                final_lengths, pool_k, pool_v, wpool)
    return (ys[0], ys[1], final_tokens, final_lengths, pool_k, pool_v, wpool,
            ys[2])


# ---------------------------------------------------------------------------
# the family, as the serving engine asks it (models/family.py)
# ---------------------------------------------------------------------------


def _two_kinds(mc, layout, slots):
    """What the block manager learns of the family: the engine's layout is
    the SUMMARY pool's (a slot's rows grow at 1 / chunk of its positions and
    a window at a time), and beside it a ring of window / block_size blocks
    a slot, with room for every slot's."""
    ring = mc.ring_blocks(layout.block_size)
    return {
        "window_layout": PagedLayout(
            block_size=layout.block_size,
            num_blocks=slots * ring + 1,
            max_blocks_per_slot=layout.max_blocks_per_slot),
        "window_ring": ring,
        "summary_window": mc.window,
        "summary_chunk": mc.chunk,
    }


def _init_pools(mc, layout, slots):
    # the summary pool where every family's K and V pools are, the ring
    # behind them where the hybrid family's recurrent state is: donated and
    # re-bound with the caches
    ring_layout = _two_kinds(mc, layout, slots)["window_layout"]
    return (
        lambda: init_kv_pool(mc, layout, mc.layers),
        lambda: dict(zip("kv", init_kv_pool(mc, ring_layout, mc.layers))))


def _family_prefill(mc, params, residents, tokens, lengths, tables,
                    use_flash=None, kernel=None):
    pool_k, pool_v, wpool = residents
    logits, pk, pv, wp, _heads = eva_prefill_paged(
        mc, params, tokens, lengths, pool_k, pool_v, wpool, tables,
        use_flash=use_flash, kernel=kernel)
    return logits, (pk, pv, wp)


def _family_decode_chunk(mc, params, residents, tokens, lengths, active,
                         tables, sample_fn, key, num_steps, **kernels):
    pool_k, pool_v, wpool = residents
    return eva_decode_chunk_paged(
        mc, params, tokens, lengths, active, pool_k, pool_v, wpool, tables,
        sample_fn, key, num_steps, **kernels)


def _pool_rows(mc, block_mgr, rows):
    """What the two kinds of history add to a decode chunk's flight sample,
    from the running slots' ``rows`` (the rows a slot will hold at the end
    of the chunk's first step): ``window_rows``, the exact rows a step reads
    of each layer's ring; ``summary_rows``, the summary rows it reads;
    ``pool_rows_held``, what both kinds hold for the running slots over all
    layers, in whole blocks; ``pool_rows_plain_cache``, what ONE K/V table
    would hold for them; ``window_closes`` and ``chunk_closes``, the slots
    for which the NEXT row opens a window, or closes a chunk; and the two
    kinds' blocks in slots' hands now."""
    bs = block_mgr.layout.block_size
    W, C = mc.window, mc.chunk
    rows = np.maximum(rows, 1)
    windows = (rows - 1) // W
    blocks = -(-rows // bs)
    summary_blocks = -(-((windows + 1) * mc.per_window) // bs)
    held = (np.minimum(blocks, block_mgr.window_ring) + summary_blocks
            ).sum() * bs * mc.layers
    return {
        "window_rows": int((rows - W * windows).sum()),
        "summary_rows": int((mc.per_window * windows).sum()),
        "pool_rows_held": int(held),
        "pool_rows_plain_cache": int(blocks.sum() * bs * mc.layers),
        "window_closes": int((rows % W == 0).sum()),
        "chunk_closes": int((rows % C == 0).sum()),
        "summary_blocks_held": block_mgr.summary_blocks_held,
        "ring_blocks_held": block_mgr.window_blocks_held,
    }


FAMILY = Family(
    name="eva",
    config_class=EvaConfig,
    presets={
        "evabyte-tiny": "tiny",
        "evabyte-6.5b-8l": "evabyte_6_5b_8l",
    },
    what="keeps two kinds of history a layer, a ring of exact rows and a "
         "pool of chunk summaries",
    refusals={
        "prefix-cache": "a summarised window is reusable only whole, with "
                        "the ring of the window after it; no such adoption "
                        "yet; set prefix-cache: false",
        "prefill-chunk": "no continuation prefill over a summarised history "
                         "yet; set prefill-chunk: 0",
        "speculative-drafts": "the verify step reads ONE K/V pool through "
                              "the multi-query kernel, which knows neither "
                              "the ring nor the summaries; set "
                              "speculative-drafts: 0",
        "pool-role": "the handoff's payload carries one pool's blocks, not "
                     "a ring's and a summary pool's; use pool-role: combined",
        "kv-quantize": "the ring's read takes a first row, which the int8 "
                       "pool's read does not, and a summary row is a mean",
        "journal-dir": "journal replay re-admits by K/V-era rules untested "
                       "over two kinds of history",
    },
    init_params=init_eva_params,
    init_pools=_init_pools,
    prefill=_family_prefill,
    decode_chunk=_family_decode_chunk,
    residents=3,  # the summary pool's K and V, the ring's {"k", "v"}
    donate=(1, 2, 3),
    block_manager_kwargs=_two_kinds,
    # one decode program a chunk size: both reads walk live blocks only
    one_decode_window=True,
    pool_rows=_pool_rows,
)
