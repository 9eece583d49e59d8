"""Decoder whose attention layers are of two kinds in one stack, window and
full, over a paged pool a kind. Two members (:class:`SwaConfig` states what
each has; what a member lacks traces nothing and holds no weights):

- ``afmoe`` (Trinity-Large-Preview): ``x <- x + N_post(Attn(N_in(x)))``,
  ``x <- x + N_post(FFN(N_pre(x)))`` (sandwich norms: one before and one
  AFTER each sub-layer); an elementwise output gate, ``W_o [o * sigmoid(W_g
  h)]``; the first ``dense_layers`` layers' FFN one gated MLP, every later
  layer's the routed experts beside a shared one (sigmoid scores + a
  selection bias, the chosen scores renormalised and scaled); the embedding
  scaled by ``sqrt(hidden)``; window layers rotated, full layers not;
- ``mellum`` (Mellum2-12B-A2.5B): ``x <- x + Attn(N_in(x))``, ``x <- x +
  FFN(N_pre(x))``; no gate, no norm after a sub-layer, no dense layer, no
  shared expert, the embedding as it is; the experts' weights the softmax of
  the chosen logits; window layers rotated plainly and full layers by YaRN
  (a frequency table and an attention factor on cos and sin of their own).

The routed experts are ``moe_mixer`` of :mod:`langstream_tpu.models.hybrid`;
the head is untied.

**Attention, every layer**: grouped queries with an RMSNorm of each query
and key head (``qk_norm``: one gain of ``head_dim`` each, shared by the
heads). **What the layer's kind decides** (``layer_kinds``: ``W`` window,
``F`` full):

- its rotation (:class:`Rope`, one a kind, or none: half-split rotary over
  the whole head);
- a ``W`` layer's query ``i`` sees the keys ``j`` with ``0 <= i - j <
  window``, an ``F`` layer's every key before it.

**Two pools** (:func:`langstream_tpu.models.paged.init_kv_pool`, one a
kind): the full layers' ``(F layers, blocks, bs, Kh*D)``, in which a slot
holds every row it has written, and the window layers' ``(W layers, window
blocks, bs, Kh*D)``, in which a slot holds a RING of ``window / bs + 1``
blocks whatever its length (:class:`langstream_tpu.models.paged.
BlockManager`): logical block ``n`` lives in ring block ``n % ring``, so a
row written ``ring * bs`` positions after another overwrites it, and by then
it lies behind every later query's window. Keys are stored rotated, so a
window layer's read needs no order among its blocks, only which rows are
live: the rows ``[length + step - (window - 1), length)`` of the logical
table, which the read is told as a first row (:func:`langstream_tpu.ops.
paged_attention.paged_attention_partial` ``firsts``; its XLA twin
:func:`langstream_tpu.models.llama_paged._cache_partial_xla`). Both walk at
most ``ring`` blocks a slot. A prefill writes a window layer's last
``window`` rows only. The programs are handed ONE table, ``[the full kind's
columns | the window kind's columns]``, each half ``max_blocks`` wide and
indexed by logical block.

The layers are few (one pipeline stage's) and of unlike kinds, so the
programs walk them in Python; every layer's weights are leaves of their own.
The expert layer serves one chip's share of an expert-parallel deployment,
as the hybrid and latent families' do.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from langstream_tpu.models.family import Family
from langstream_tpu.models.hybrid import backend_kernel, moe_mixer
from langstream_tpu.models.llama import (
    _apply_rope,
    _flash_mode,
    _rms_norm,
    _rope,
    yarn_inv_freq,
)
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
from langstream_tpu.ops.paged_attention import (
    NEG_INF,
    merge_partial_attention,
    paged_attention_partial,
)
from langstream_tpu.ops.pool_commit import commit_form

#: query and key rows of one block of the prefill's flash kernel (keys and
#: values of 128; the latent family's measurement at 192 / 128 chose the
#: same: models/latent.py FLASH_BLOCK)
FLASH_BLOCK = 1024
#: rows of one pass of the dense layers' gated MLP in a prefill: its
#: ``[gate | up]`` of a 16,384-row prompt at width 12,288 is 0.8 GB in
#: bfloat16 whole
DENSE_FFN_ROWS = 4096


@dataclasses.dataclass(frozen=True)
class Rope:
    """One layer kind's rotation: plain rotary at ``theta``, or YaRN where
    ``factor`` is over 1 (its frequency table from ``original_max`` and the
    two betas, cos and sin multiplied by ``attention_factor``, so a layer's
    scores carry its square)."""

    theta: float = 10000.0
    factor: float = 1.0
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def cos_sin(self, positions: jax.Array, head_dim: int):
        if self.factor <= 1.0:
            return _rope(positions, head_dim, self.theta)
        angles = positions[..., None].astype(jnp.float32) * yarn_inv_freq(
            head_dim, self.theta, self.factor, self.original_max,
            self.beta_fast, self.beta_slow)
        return (jnp.cos(angles) * self.attention_factor,
                jnp.sin(angles) * self.attention_factor)


@dataclasses.dataclass(frozen=True)
class SwaConfig:
    # the fields the dense family's config has, under the same names
    vocab_size: int = 25024
    hidden: int = 3072
    layers: int = 5
    heads: int = 48
    kv_heads: int = 8
    head_dim: int = 128
    intermediate: int = 12288        # the dense layers' gated MLP
    norm_eps: float = 1e-5
    max_seq_len: int = 16384
    dtype: Any = jnp.bfloat16
    # the two kinds of attention layer
    window: int = 4096               # a W layer's query i sees i - j < window
    layer_kinds: str = "WWFWW"       # of the layers held: W window, F full
    window_rope: Rope | None = Rope()   # a kind's rotation, None for none
    full_rope: Rope | None = None
    # what a member's attention and residual have
    qk_norm: bool = True             # each query and key head RMS-normed
    output_gate: bool = True         # W_o [o * sigmoid(W_g h)]
    post_norms: bool = True          # a norm AFTER each sub-layer
    embed_scaled: bool = True        # the embedding times sqrt(hidden)
    # FFN
    dense_layers: int = 1            # leading layers with the gated MLP
    moe_intermediate: int = 3072     # one routed expert's width
    shared_intermediate: int = 3072  # the shared expert's width, 0 for none
    experts: int = 256
    experts_per_token: int = 4
    routed_scale: float = 2.448      # route_scale, on the renormalised scores
    router_dtype: Any = jnp.float32  # the logits'; lower only as a control
    # this chip's share of the expert-parallel deployment
    experts_held: int = 32
    expert_first: int = 0
    # what moe_mixer reads of a family
    router: str = "sigmoid"          # or "softmax": models/moe.py
    expert_act: str = "silu_gated"
    #: recurrent state beside the pools: none
    state_bytes_per_slot: int = 0

    def __post_init__(self):
        if len(self.layer_kinds) != self.layers or set(self.layer_kinds) - set("WF"):
            raise ValueError(
                f"layer_kinds {self.layer_kinds!r} names {self.layers} "
                f"layers as W (window) or F (full)")
        if not ("W" in self.layer_kinds and "F" in self.layer_kinds):
            raise ValueError("at least one window and one full layer")
        if not 0 <= self.dense_layers < self.layers:
            raise ValueError("at least one expert layer")
        if not 0 <= self.expert_first <= self.experts - self.experts_held:
            raise ValueError("the held experts lie outside the router's")

    @classmethod
    def trinity_large_preview_ep8(cls, max_seq_len: int = 16384) -> "SwaConfig":
        """arcee-ai/Trinity-Large-Preview as one chip of the eight that share
        each layer, rank 0 of the pipeline stage that holds layers 5-9 of 60:
        the last dense layer (5, window) and one whole period of the expert
        layers (6 window, 7 full, 8 and 9 window), experts 0-31 of 256 and
        rows 0-25,023 of the 200,192 of the embedding and of the untied head
        held here; attention, the dense MLP, the shared expert and the
        router whole."""
        return cls(max_seq_len=max_seq_len)

    @classmethod
    def tiny(cls, max_seq_len: int = 128, expert_first: int = 0,
             experts_held: int = 4) -> "SwaConfig":
        """Test size of the same grammar: one dense layer and a period of
        four, a window of 32 rows, half of 8 experts held."""
        return cls(
            vocab_size=384, hidden=64, layers=5, heads=4, kv_heads=2,
            head_dim=16, intermediate=96, window=32, layer_kinds="WWFWW",
            dense_layers=1, moe_intermediate=32, shared_intermediate=32,
            experts=8, experts_per_token=2, experts_held=experts_held,
            expert_first=expert_first, max_seq_len=max_seq_len,
        )

    @classmethod
    def mellum2_12b_a2_5b_8l(cls, max_seq_len: int = 8768) -> "SwaConfig":
        """JetBrains/Mellum2-12B-A2.5B-Instruct as stage 0 of four pipeline
        stages: layers 0-7 of 28 (two periods ``WWWF``) whole, all 64
        experts of each and the whole vocabulary (the last norm and the
        head ride here so that the stage gives logits). Both rotations at
        ``theta`` 500,000, the full layers' YaRN as published
        (``rope_parameters``); the published ``intermediate_size`` 7,168 is
        carried and unused: every layer's FFN is sparse."""
        return cls(
            vocab_size=98304, hidden=2304, layers=8, heads=32, kv_heads=4,
            head_dim=128, intermediate=7168, norm_eps=1e-6, window=1024,
            layer_kinds="WWWFWWWF", window_rope=Rope(theta=500000.0),
            full_rope=Rope(theta=500000.0, factor=16.0, original_max=8192,
                           beta_fast=32.0, beta_slow=1.0,
                           attention_factor=1.2772588722239782),
            output_gate=False, post_norms=False, embed_scaled=False,
            dense_layers=0, moe_intermediate=896, shared_intermediate=0,
            experts=64, experts_per_token=8, experts_held=64,
            router="softmax", max_seq_len=max_seq_len,
        )

    @classmethod
    def mellum_tiny(cls, max_seq_len: int = 256) -> "SwaConfig":
        """Test size of that member: two periods ``WWWF``, a window of 32
        rows, 8 experts top-2 all held, and YaRN over an original length of
        64 rows at ``theta`` 10,000 so that the ramp's three regions all
        occur among the head's 8 frequencies (dimension 0 kept, 1 and 2 on
        the ramp, 3-7 divided by the factor)."""
        return cls(
            vocab_size=384, hidden=64, layers=8, heads=4, kv_heads=2,
            head_dim=16, intermediate=96, norm_eps=1e-6, window=32,
            layer_kinds="WWWFWWWF", window_rope=Rope(theta=10000.0),
            full_rope=Rope(theta=10000.0, factor=8.0, original_max=64,
                           beta_fast=8.0, beta_slow=1.0,
                           attention_factor=1.2079441541679836),
            output_gate=False, post_norms=False, embed_scaled=False,
            dense_layers=0, moe_intermediate=32, shared_intermediate=0,
            experts=8, experts_per_token=2, experts_held=8,
            router="softmax", max_seq_len=max_seq_len,
        )

    @property
    def rope_theta(self) -> float:
        """The window layers' ``theta``, under the dense family's name."""
        return self.window_rope.theta if self.window_rope else 0.0

    def rope_of(self, kind: str) -> Rope | None:
        return self.window_rope if kind == "W" else self.full_rope

    @property
    def sparse_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def window_layers(self) -> int:
        return self.layer_kinds.count("W")

    @property
    def full_layers(self) -> int:
        return self.layer_kinds.count("F")

    @property
    def kind_index(self) -> tuple[int, ...]:
        """A layer's index among the layers of its own kind: its row of its
        kind's pool."""
        seen = {"W": 0, "F": 0}
        out = []
        for kind in self.layer_kinds:
            out.append(seen[kind])
            seen[kind] += 1
        return tuple(out)

    def ring_blocks(self, block_size: int) -> int:
        """Blocks of a slot's ring in the window kind's pool: the window's
        rows and the block the newest rows are being written into."""
        return -(-self.window // block_size) + 1


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_swa_params(config: SwaConfig, key: jax.Array | None = None) -> dict:
    """Random parameters from a key, one jitted draw a leaf. An expert's
    weights depend on its GLOBAL id and its layer, so the shares of one
    deployment are slices of the same experts. The embedding is drawn so
    that what enters the first layer has a spread of 1, as every sub-layer's
    normed output has (at ``1 / sqrt(hidden)`` where the program scales it:
    with a unit embedding the residual would be the token's own row fifty
    times over whatever the layers add, and no comparison of logits would
    see them). The gains of the query, key and post norms and the router's
    selection bias are drawn away from trivial values: a term left out
    changes the logits. A leaf the member lacks (the gate, a post norm, the
    selection bias, the shared expert) is not drawn."""
    c = config
    key = key if key is not None else jax.random.PRNGKey(0)
    H, D, names = c.hidden, c.head_dim, iter(range(10 ** 6))

    def normal(shape, fan_in, dtype=None):
        k = jax.random.fold_in(key, next(names))
        scale = 1.0 / math.sqrt(fan_in)
        return jax.jit(
            lambda k: (jax.random.normal(k, shape, jnp.float32) * scale
                       ).astype(dtype or c.dtype)
        )(k)

    def gain(shape):
        k = jax.random.fold_in(key, next(names))
        return jax.random.uniform(k, shape, jnp.float32, 0.5, 1.5).astype(c.dtype)

    def experts(layer, shape, fan_in):
        """(held,) + shape, expert e from (layer, global e)."""
        k = jax.random.fold_in(jax.random.fold_in(key, next(names)), layer)
        scale = 1.0 / math.sqrt(fan_in)
        held = c.expert_first + jnp.arange(c.experts_held)
        return jax.jit(jax.vmap(lambda e: (
            jax.random.normal(jax.random.fold_in(k, e), shape, jnp.float32)
            * scale).astype(c.dtype)))(held)

    def attention():
        ap = {
            "norm": jnp.ones((H,), c.dtype),
            "wq": normal((H, c.heads * D), H),
            "wk": normal((H, c.kv_heads * D), H),
            "wv": normal((H, c.kv_heads * D), H),
        }
        if c.output_gate:
            ap["wg"] = normal((H, c.heads * D), H)
        ap["wo"] = normal((c.heads * D, H), c.heads * D)
        if c.qk_norm:
            ap["q_norm"], ap["k_norm"] = gain((D,)), gain((D,))
        if c.post_norms:
            ap["post_norm"] = gain((H,))
        return ap

    I, Ie, Is = c.intermediate, c.moe_intermediate, c.shared_intermediate
    layers = []
    for layer in range(c.layers):
        lp = {"attn": attention()}
        if layer < c.dense_layers:
            sub = lp["ffn"] = {
                "norm": jnp.ones((H,), c.dtype),
                "w_up": normal((H, 2 * I), H),          # [gate | up]
                "w_down": normal((I, H), I),
            }
        else:
            kb = jax.random.fold_in(key, next(names))
            sub = lp["moe"] = {
                "norm": jnp.ones((H,), c.dtype),
                # the model's type; the logits are float32 (moe.py)
                "router": normal((H, c.experts), H),
            }
            if c.router == "sigmoid":
                # small beside the spread of the scores, as a trained bias
                # is: the scores decide the winners and the bias the ties
                sub["bias"] = jax.random.uniform(
                    kb, (c.experts,), jnp.float32, -0.02, 0.02)
            # (held, 2 I, H) and (held, I, H), as the other families'
            # gated experts (models/moe.py dropless_experts)
            sub["w_up"] = experts(layer, (2 * Ie, H), H)
            sub["w_down"] = experts(layer, (Ie, H), Ie)
            if Is:
                sub["ws_up"] = normal((H, 2 * Is), H)
                sub["ws_down"] = normal((Is, H), Is)
        if c.post_norms:
            sub["post_norm"] = gain((H,))
        layers.append(lp)
    return {
        "embed": normal((c.vocab_size, H), H if c.embed_scaled else 1),
        "final_norm": jnp.ones((H,), c.dtype),
        "lm_head": normal((H, c.vocab_size), H),
        "layers": layers,
    }


# ---------------------------------------------------------------------------
# what both programs share
# ---------------------------------------------------------------------------


def _embed(c: SwaConfig, params: dict, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    if not c.embed_scaled:
        return x
    return (x.astype(jnp.float32) * math.sqrt(c.hidden)).astype(x.dtype)


def _logits(params: dict, x: jax.Array) -> jax.Array:
    return (x @ params["lm_head"]).astype(jnp.float32)


def _projections(c: SwaConfig, ap: dict, x: jax.Array, kind: str,
                 positions: jax.Array):
    """``(q (..., heads, D), k, v (..., kv_heads, D), gate (..., heads * D)
    logits, or None without an output gate)`` of a layer's normed input:
    each query and key head normed (``qk_norm``), and rotated at
    ``positions`` by its kind's rotation, where it has one (scope ``rope`` on
    a window layer, ``rope_full`` on a full one)."""
    lead = x.shape[:-1]
    with jax.named_scope("attn_qkv"):
        h = _rms_norm(x, ap["norm"], c.norm_eps)
        q = (h @ ap["wq"]).reshape(lead + (c.heads, c.head_dim))
        k = (h @ ap["wk"]).reshape(lead + (c.kv_heads, c.head_dim))
        v = (h @ ap["wv"]).reshape(lead + (c.kv_heads, c.head_dim))
        gate = h @ ap["wg"] if c.output_gate else None
    if c.qk_norm:
        with jax.named_scope("qk_norm"):
            q = _rms_norm(q, ap["q_norm"], c.norm_eps)
            k = _rms_norm(k, ap["k_norm"], c.norm_eps)
    rope = c.rope_of(kind)
    if rope is not None:
        with jax.named_scope("rope" if kind == "W" else "rope_full"):
            cos, sin = rope.cos_sin(positions, c.head_dim)
            q, k = _apply_rope(q, cos, sin), _apply_rope(k, cos, sin)
    return q, k, v, gate


def _residual(c: SwaConfig, sub: dict, x: jax.Array, out: jax.Array):
    """``x + N_post(out)``, or ``x + out`` for a member without post norms;
    ``sub`` is the sub-layer's weights."""
    if not c.post_norms:
        return x + out
    with jax.named_scope("post_norm"):
        return x + _rms_norm(out, sub["post_norm"], c.norm_eps)


def _attention_out(c: SwaConfig, ap: dict, x: jax.Array, out: jax.Array,
                   gate: jax.Array | None) -> jax.Array:
    """``x + N_post(W_o [out * sigmoid(gate)])``, gate and norm where the
    member has them; ``out (..., heads * D)``."""
    if gate is not None:
        with jax.named_scope("attn_gate"):
            out = out * jax.nn.sigmoid(
                gate.astype(jnp.float32)).astype(out.dtype)
    with jax.named_scope("attn_out"):
        a = out @ ap["wo"]
    return _residual(c, ap, x, a)


def _dense_ffn(c: SwaConfig, fp: dict, x: jax.Array) -> jax.Array:
    """``x + N_post(W_down[silu(W_gate h) * W_up h])`` over rows ``x (T, H)``,
    ``DENSE_FFN_ROWS`` at a time."""
    with jax.named_scope("ffn"):
        h = _rms_norm(x, fp["norm"], c.norm_eps)
        one = lambda rows: silu_gated(rows @ fp["w_up"]) @ fp["w_down"]  # noqa: E731
        T = h.shape[0]
        if T > DENSE_FFN_ROWS and T % DENSE_FFN_ROWS == 0:
            f = jax.lax.map(
                one, h.reshape(T // DENSE_FFN_ROWS, DENSE_FFN_ROWS, -1)
            ).reshape(T, -1)
        else:
            f = one(h)
    return _residual(c, fp, x, f)


def _experts(c: SwaConfig, ep: dict, x: jax.Array, valid: jax.Array,
             kernel: str | None = None):
    """``(x + N_post(experts(N_pre(x))), load, chosen)`` over rows ``x (T,
    H)``; ``kernel`` is :func:`langstream_tpu.models.hybrid.moe_mixer`'s."""
    out, load, chosen = moe_mixer(
        c, ep, _rms_norm(x, ep["norm"], c.norm_eps), valid, kernel=kernel)
    return _residual(c, ep, x, out), load, chosen


def split_tables(block_tables: jax.Array):
    """``(the full kind's columns, the window kind's)`` of the one table the
    programs are handed (:class:`langstream_tpu.models.paged.BlockManager`)."""
    width = block_tables.shape[1] // 2
    return block_tables[:, :width], block_tables[:, width:]


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def swa_prefill_paged(
    config: SwaConfig,
    params: dict,
    tokens: jax.Array,        # (B, P) int32, right-padded
    lengths: jax.Array,       # (B,) true lengths
    pool_k: jax.Array,        # (full layers, nb, bs, Kh*D)
    pool_v: jax.Array,
    wpool: dict,              # {"k", "v"}: (window layers, window nb, bs, Kh*D)
    block_tables: jax.Array,  # (B, 2 x max_blocks): [full | window], THIS batch
    use_flash: bool | None = None,
    kernel: str | None = None,
):
    """Prompt forward: every layer's K and V rows land in its kind's pool
    through :func:`langstream_tpu.models.paged.write_rows_pair`, a full layer's
    all of them, a window layer's last ``window`` alone (the rows a later
    query can still see; an earlier one's ring block would be overwritten by
    a later one's within this very scatter). Returns ``(last-token logits
    (B, V), pool_k, pool_v, wpool, routed)``; ``routed (expert layers, B, P,
    k)`` are the experts the router chose, for the reference check (a caller
    that drops it pays nothing for it). ``kernel`` is the engine's one
    selection, here the form of the routed experts' grouped pass
    (``moe_grouped_kernel``); a caller that hands none gets what the engine
    resolves on this backend (``moe_mixer``)."""
    c = config
    B, Pn = tokens.shape
    KhD = c.kv_heads * c.head_dim
    G = c.heads // c.kv_heads
    positions = jnp.arange(Pn)
    real = positions[None, :] < lengths[:, None]                   # (B, P)
    flash = (_flash_mode(Pn) if use_flash is None
             else ("compiled" if use_flash else None))
    if kernel is None:      # as moe_mixer resolves it; the commit reads it too
        kernel = backend_kernel()

    def attend(q, k, v, kind):
        window = c.window if kind == "W" else None
        if flash is not None:
            # the lengths spare the kernel the padding's blocks, the window
            # the key blocks wholly behind it
            from langstream_tpu.ops.flash_attention import flash_attention

            return flash_attention(
                q, k, v, causal=True, block_q=FLASH_BLOCK,
                block_k=FLASH_BLOCK, interpret=(flash == "interpret"),
                lengths=lengths, window=window)
        qg = q.reshape(B, Pn, c.kv_heads, G, c.head_dim)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
        s = s / math.sqrt(c.head_dim)
        behind = positions[:, None] - positions[None, :]           # i - j
        mask = behind >= 0
        if window is not None:
            mask = mask & (behind < window)
        mask = mask[None] & real[:, None, :]
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        return jnp.einsum(
            "bkgqs,bskd->bqkgd", jax.nn.softmax(s, -1).astype(v.dtype), v)

    with jax.named_scope("embed"):
        x = _embed(c, params, tokens)
    rows = {"W": ([], []), "F": ([], [])}
    routed = []
    for layer, (lp, kind) in enumerate(zip(params["layers"], c.layer_kinds)):
        ap = lp["attn"]
        q, k, v, gate = _projections(c, ap, x, kind, positions)
        with jax.named_scope("swa_flash" if kind == "W" else "full_flash"):
            out = attend(q, k, v, kind)
        x = _attention_out(
            c, ap, x, out.reshape(B, Pn, c.heads * c.head_dim), gate)
        rows[kind][0].append(k.reshape(B, Pn, KhD))
        rows[kind][1].append(v.reshape(B, Pn, KhD))
        if layer < c.dense_layers:
            x = _dense_ffn(c, lp["ffn"], x.reshape(B * Pn, c.hidden)
                           ).reshape(B, Pn, c.hidden)
        else:
            x, _, chosen = _experts(
                c, lp["moe"], x.reshape(B * Pn, c.hidden), real.reshape(-1),
                kernel)
            x = x.reshape(B, Pn, c.hidden)
            routed.append(chosen.reshape(B, Pn, -1))
    with jax.named_scope("lm_head"):
        x = _rms_norm(x, params["final_norm"], c.norm_eps)
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].clip(0), axis=1).squeeze(1)
        logits = _logits(params, last)
    full_tables, window_tables = split_tables(block_tables)
    # the rows begin at position 0: None says so to the commit's kernel; the
    # scatter's zeros are made once for both kinds, as its text had them
    starts = (jnp.zeros((B,), jnp.int32)
              if commit_form(kernel, pool_k) == "xla" else None)
    with jax.named_scope("kv_write"):
        pool_k, pool_v = write_rows_pair(
            (pool_k, pool_v), (jnp.stack(r) for r in rows["F"]), full_tables,
            starts, real, kernel)
    with jax.named_scope("swa_write"):
        seen = real & (positions[None, :] >= (lengths - c.window)[:, None])
        wpool = dict(zip("kv", write_rows_pair(
            (wpool["k"], wpool["v"]), (jnp.stack(r) for r in rows["W"]),
            window_tables, starts, seen, kernel)))
    return logits, pool_k, pool_v, wpool, jnp.stack(routed)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def swa_decode_chunk_paged(
    config: SwaConfig,
    params: dict,
    tokens0: jax.Array,       # (B,)
    base_lengths: jax.Array,  # (B,)
    active: jax.Array,        # (B,) bool
    pool_k: jax.Array,        # read-only during the chunk
    pool_v: jax.Array,
    wpool: dict,
    block_tables: jax.Array,  # (B, 2 x max_blocks): [full | window]
    sample_fn: Callable,
    key: jax.Array,
    num_steps: int,
    num_read_blocks: int,
    kernel: str = "xla",      # "xla" | "pallas" | "pallas-interpret"
    sample_extras=None,       # (presences, frequencies, counts0)
    return_packed: bool = False,
):
    """K fused decode steps. Both pools are read-only and every layer's new
    K and V rows gather in a chunk buffer (one scatter a pool at the end,
    :func:`langstream_tpu.models.paged.write_rows_pair`; a window layer's rows
    overwrite, through the ring, rows that lay behind the window before the
    chunk began). A window layer's read is told the first row its query
    sees, ``length + step - (window - 1)``, and walks the blocks from there.

    Returns ``(chunk_tokens, chunk_logprobs, final_tokens, final_lengths,
    pool_k, pool_v, wpool, load, routed)`` where ``load (expert layers,
    experts_held)`` counts the chosen pairs each held expert got over the
    chunk's active rows and ``routed (steps, expert layers, B, k)`` are the
    experts the router chose (for the reference check);
    ``return_packed=True`` folds tokens, logprobs and ``load`` into one
    int32 array in their place and leaves ``routed`` out."""
    c = config
    B = tokens0.shape[0]
    KhD = c.kv_heads * c.head_dim
    G = c.heads // c.kv_heads
    scale = 1.0 / math.sqrt(c.head_dim)
    adv = active.astype(jnp.int32)
    pen = sample_extras is not None
    counts0 = sample_extras[2] if pen else None
    full_tables, window_tables = split_tables(block_tables)
    ring = c.ring_blocks(pool_k.shape[2])
    if num_steps > c.window:
        raise ValueError("a chunk's own rows have to lie inside the window")

    def cache_partial(q, kind, a, firsts):
        """The pool's part of layer ``a`` of its kind, through the read the
        engine selected; a window layer's from its first row."""
        pk, pv, tables = ((wpool["k"], wpool["v"], window_tables)
                          if kind == "W" else (pool_k, pool_v, full_tables))
        if kernel == "xla":
            return _cache_partial_xla(
                c, q, pk, pv, a, tables, base_lengths,
                min(ring, num_read_blocks) if kind == "W" else num_read_blocks,
                firsts=firsts)
        return paged_attention_partial(
            q, pk, pv, a, tables, base_lengths,
            num_read_blocks=num_read_blocks, kv_heads=c.kv_heads,
            head_dim=c.head_dim, scale=scale,
            interpret=(kernel == "pallas-interpret"), firsts=firsts)

    def step(carry, step_idx):
        tokens, kbufs, vbufs, key, load = carry[:5]
        counts = carry[5] if pen else None
        with jax.named_scope("sample"):
            key, sub = jax.random.split(key)
        with jax.named_scope("embed"):
            x = _embed(c, params, tokens)
        buf_mask = jnp.arange(num_steps)[None, :] <= step_idx      # (1, K)
        positions = base_lengths + step_idx
        firsts = jnp.maximum(positions - (c.window - 1), 0)
        kbufs, vbufs, loads, chose = list(kbufs), list(vbufs), [], []
        for layer, (lp, kind) in enumerate(zip(params["layers"], c.layer_kinds)):
            ap = lp["attn"]
            q, k, v, gate = _projections(c, ap, x, kind, positions)
            with jax.named_scope("attn_qkv"):
                # this layer's rows of the chunk so far, with this step's
                kb = kbufs[layer] = jax.lax.dynamic_update_slice_in_dim(
                    kbufs[layer], k[:, None], step_idx, axis=1)    # (B,K,Kh,D)
                vb = vbufs[layer] = jax.lax.dynamic_update_slice_in_dim(
                    vbufs[layer], v[:, None], step_idx, axis=1)
            with jax.named_scope("swa_read" if kind == "W" else "full_read"):
                part_c = cache_partial(
                    q, kind, c.kind_index[layer],
                    firsts if kind == "W" else None)
            with jax.named_scope("attn_buf"):
                # the chunk's own rows: all inside any window (K <= window)
                qg = q.reshape(B, c.kv_heads, G, c.head_dim)
                s = jnp.einsum("bkgd,btkd->bkgt", qg, kb).astype(jnp.float32)
                s = jnp.where(buf_mask[:, None, None, :], s * scale, NEG_INF)
                m_b = jnp.max(s, axis=-1)
                p_b = jnp.where(
                    buf_mask[:, None, None, :], jnp.exp(s - m_b[..., None]), 0.0)
                acc_b = jnp.einsum(
                    "bkgt,btkd->bkgd", p_b.astype(vb.dtype), vb
                ).astype(jnp.float32)
                out = merge_partial_attention([
                    part_c,
                    (acc_b.reshape(B, c.heads, c.head_dim),
                     m_b.reshape(B, c.heads),
                     jnp.sum(p_b, axis=-1).reshape(B, c.heads)),
                ]).astype(x.dtype).reshape(B, c.heads * c.head_dim)
            x = _attention_out(c, ap, x, out, gate)
            if layer < c.dense_layers:
                x = _dense_ffn(c, lp["ffn"], x)
            else:
                x, load_i, chosen = _experts(c, lp["moe"], x, active)
                loads.append(load_i)
                chose.append(chosen)
        with jax.named_scope("lm_head"):
            logits = _logits(
                params, _rms_norm(x, params["final_norm"], c.norm_eps))
        with jax.named_scope("sample"):
            nxt, lp_ = (sample_fn(logits, sub, counts) if pen
                        else sample_fn(logits, sub))
            nxt = jnp.where(active, nxt, tokens)
        out_carry = (nxt, tuple(kbufs), tuple(vbufs), key,
                     load + jnp.stack(loads))
        if pen:
            out_carry += (counts.at[jnp.arange(B), nxt].add(adv),)
        return out_carry, (nxt, lp_, jnp.stack(chose))

    buf0 = tuple(jnp.zeros((B, num_steps, c.kv_heads, c.head_dim), c.dtype)
                 for _ in range(c.layers))
    carry0 = (tokens0, buf0, buf0, key,
              jnp.zeros((c.sparse_layers, c.experts_held), jnp.int32))
    if pen:
        carry0 += (counts0,)
    out_carry, (chunk_tokens, chunk_lps, routed) = jax.lax.scan(
        step, carry0, jnp.arange(num_steps))
    final_tokens, kbufs, vbufs, _, load = out_carry[:5]
    valid = jnp.broadcast_to(active[:, None], (B, num_steps))

    def of_kind(bufs, kind):
        return jnp.stack([
            buf.reshape(B, num_steps, KhD)
            for buf, k in zip(bufs, c.layer_kinds) if k == kind])

    with jax.named_scope("kv_write"):
        pool_k, pool_v = write_rows_pair(
            (pool_k, pool_v), (of_kind(b, "F") for b in (kbufs, vbufs)),
            full_tables, base_lengths, valid, kernel)
    with jax.named_scope("swa_write"):
        wpool = dict(zip("kv", write_rows_pair(
            (wpool["k"], wpool["v"]),
            (of_kind(b, "W") for b in (kbufs, vbufs)), window_tables,
            base_lengths, valid, kernel)))
    final_lengths = base_lengths + num_steps * adv
    if return_packed:
        packed = jnp.concatenate(
            [pack_tokens_logprobs(chunk_tokens, chunk_lps), load.reshape(-1)])
        return packed, final_tokens, final_lengths, pool_k, pool_v, wpool
    return (chunk_tokens, chunk_lps, final_tokens, final_lengths, pool_k,
            pool_v, wpool, load, routed)


# ---------------------------------------------------------------------------
# the family, as the serving engine asks it (models/family.py)
# ---------------------------------------------------------------------------


def _window_kind(mc, layout, slots):
    """The second pool, for the layers that attend a window: a ring of
    window / block_size + 1 blocks a slot, whatever its length, and room for
    every slot's (models/paged.py BlockManager)."""
    ring = mc.ring_blocks(layout.block_size)
    return {
        "window_layout": PagedLayout(
            block_size=layout.block_size,
            num_blocks=slots * ring + 1,
            max_blocks_per_slot=layout.max_blocks_per_slot),
        "window_ring": ring,
    }


def _init_pools(mc, layout, slots):
    # the full layers' pool where every family's K and V pools are, the
    # window layers' behind them where the hybrid family's recurrent state
    # is: donated and re-bound with the caches
    window_layout = _window_kind(mc, layout, slots)["window_layout"]
    return (
        lambda: init_kv_pool(mc, layout, mc.full_layers),
        lambda: dict(zip("kv", init_kv_pool(
            mc, window_layout, mc.window_layers))))


def _family_prefill(mc, params, residents, tokens, lengths, tables,
                    use_flash=None, kernel=None):
    cache_k, cache_v, wpool = residents
    logits, ck, cv, wp, _routed = swa_prefill_paged(
        mc, params, tokens, lengths, cache_k, cache_v, wpool, tables,
        use_flash=use_flash, kernel=kernel)
    return logits, (ck, cv, wp)


def _family_decode_chunk(mc, params, residents, tokens, lengths, active,
                         tables, sample_fn, key, num_steps, **kernels):
    cache_k, cache_v, wpool = residents
    return swa_decode_chunk_paged(
        mc, params, tokens, lengths, active, cache_k, cache_v, wpool, tables,
        sample_fn, key, num_steps, **kernels)


def _pool_rows(mc, block_mgr, rows):
    """What a pool a layer kind adds to a decode chunk's flight sample, from
    the running slots' ``rows``: ``window_rows``, the rows a step reads of
    each WINDOW layer's pool (a slot's last ``window`` at most, where
    ``live_rows`` counts the full layers' whole history);
    ``pool_rows_held``, the rows both kinds hold for the running slots over
    all layers, in whole blocks; ``pool_rows_one_table``, what ONE table for
    all layers would hold for them (every layer every block);
    ``window_slot_blocks_max``, the most window blocks any slot holds (never
    more than the ring); ``short_slots``, the running slots whose rows are
    fewer than the window (their rings are not full and their window layers
    read what a full layer reads); and ``window_blocks_held``, the window
    kind's blocks in slots' rings now (of ``slots x ring``)."""
    bs = block_mgr.layout.block_size
    blocks = -(-rows // bs)
    held = (mc.full_layers * blocks
            + mc.window_layers * np.minimum(blocks, block_mgr.window_ring)
            ).sum() * bs
    return {
        "window_rows": int(np.minimum(rows, mc.window).sum()),
        "pool_rows_held": int(held),
        "pool_rows_one_table": int(blocks.sum() * bs * mc.layers),
        "window_slot_blocks_max": block_mgr.window_slot_blocks_max,
        "short_slots": int((rows < mc.window).sum()),
        "window_blocks_held": block_mgr.window_blocks_held,
    }


FAMILY = Family(
    name="swa",
    config_class=SwaConfig,
    presets={
        "trinity-tiny": "tiny",
        "trinity-large-preview-ep8": "trinity_large_preview_ep8",
        "mellum-tiny": "mellum_tiny",
        "mellum2-12b-a2.5b-8l": "mellum2_12b_a2_5b_8l",
    },
    what="keeps a second pool for its window layers, a ring of blocks a slot",
    refusals={
        "prefix-cache": "a window layer's cached block is overwritten once "
                        "its slot grows a ring past it and is not reusable "
                        "past the window; set prefix-cache: false",
        "prefill-chunk": "no continuation prefill over two kinds of history "
                         "yet; set prefill-chunk: 0",
        "speculative-drafts": "the verify step reads one K/V pool through "
                              "the multi-query kernel, which knows no "
                              "window; set speculative-drafts: 0",
        "pool-role": "the handoff's payload carries one pool's blocks, not "
                     "a ring's; use pool-role: combined",
        "kv-quantize": "the window read takes a first row, which the int8 "
                       "pool's read does not",
        "journal-dir": "journal replay re-admits by K/V-era rules untested "
                       "over two kinds of pool",
    },
    init_params=init_swa_params,
    init_pools=_init_pools,
    prefill=_family_prefill,
    decode_chunk=_family_decode_chunk,
    residents=3,  # cache_k, cache_v, the window layers' {"k", "v"}
    donate=(1, 2, 3),
    block_manager_kwargs=_window_kind,
    # one decode program a chunk size, as the latent family: its reads walk
    # live blocks too, its window layers' from their first row
    one_decode_window=True,
    expert_kernels=True,
    pool_rows=_pool_rows,
)
