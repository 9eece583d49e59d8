"""Hybrid decoder: Mamba-2 mixers, grouped-query attention and routed
experts in one stack (the ``nemotron_h`` and ``granitemoehybrid`` families),
over the paged pool.

Every sub-layer is ONE mixer with its own pre-norm and residual,
``x <- x + m * Mixer_kind(RMSNorm(x))``, its kind from the pattern (``M``
Mamba-2, ``*`` attention, ``E`` mixture of experts; ``m`` the family's
``residual_multiplier``, 1 where it has none). The pattern is a run of
blocks with at least one mixer and then the experts, ``ME``, ``M*E`` or
``*E``, and that block is what the programs scan. ``nemotron_h`` publishes
the pattern itself (blocks ``M [*] E``: attention is an extra, 23 blocks and
6 attention layers at the published depth); a ``granitemoehybrid`` layer is a
mixer and then the experts, so its ``layer_types`` read ``ME`` for "mamba"
and ``*E`` for "attention" (attention takes the Mamba-2 mixer's place). The
expert weights are stacked by block, the Mamba-2 and the attention weights
by their own layers; where every block has its Mamba-2 mixer the scan slices
its weights with the block, otherwise a block reaches them, as it does the
attention's, through its index and only when it has one.

What else differs between the two families are facts of the checkpoint and
fields of :class:`HybridConfig`, branched on in Python while a program is
traced: the router's rule, the expert's activation, the three multipliers,
the attention's scale and whether the head is the embedding.

Two kinds of per-request state live side by side:

- the attention layers' keys and values, in the paged pool
  (:mod:`langstream_tpu.models.paged`), read through the kernel the engine
  selected, exactly as the dense family's;
- a fixed-size recurrent state per slot for the Mamba-2 layers: the
  float32 state ``(heads, head_dim, state)`` and the last ``kernel - 1``
  inputs of the convolution. It is not paged: prefill writes a slot's rows
  whole, a decode step advances them in place. A block without a Mamba-2
  mixer holds no rows of it.

The expert layer serves one chip's share of an expert-parallel deployment
(``experts_held`` of ``experts`` from ``expert_first``): the router keeps
its published width and top-k, this chip computes the chosen pairs whose
expert it holds and the shared expert (models/moe.py, dropless), and the
partial result goes on to the next layer.

Attention applies no rotary embedding (the family's attention applies
none; ``rope_theta`` is carried because the published config has it).
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Callable

import jax
import jax.numpy as jnp

from langstream_tpu.models.llama import _flash_mode, _rms_norm
from langstream_tpu.models.llama_paged import (
    _cache_partial_xla,
    pack_tokens_logprobs,
)
from langstream_tpu.models.moe import (
    EXPERT_ACTS,
    dropless_experts,
    group_limited_softmax_routing,
    sigmoid_topk_routing,
    softmax_topk_routing,
)
from langstream_tpu.models.paged import write_rows
from langstream_tpu.ops.paged_attention import (
    NEG_INF,
    merge_partial_attention,
    paged_attention_partial,
)
from langstream_tpu.ops.ssm_state import ssm_state_step

NEMOTRON3_NANO_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
_BLOCK = r"(?:M\*?|\*)E"     # at least one mixer, then the experts


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    # the fields the dense family's config has, under the same names
    vocab_size: int = 16384
    hidden: int = 2688
    layers: int = 52
    heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    intermediate: int = 1856          # one routed expert's width
    rope_theta: float = 10000.0       # published; unused (no rotary)
    norm_eps: float = 1e-5
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    pattern: str = NEMOTRON3_NANO_PATTERN
    # Mamba-2
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    state_dtype: Any = jnp.float32
    # experts
    experts: int = 128
    experts_per_token: int = 6
    shared_intermediate: int = 3712
    routed_scale: float = 2.5         # the sigmoid router's alone
    router_dtype: Any = jnp.float32   # published; lower only as a control
    # this chip's share of the expert-parallel deployment
    experts_held: int = 16
    expert_first: int = 0
    # facts of the checkpoint that differ between the families (the defaults
    # are nemotron_h's; at 1.0 and None nothing is traced for them)
    router: str = "sigmoid"           # or "softmax_topk" (moe.py)
    expert_act: str = "relu2"         # or "silu_gated" (moe.py EXPERT_ACTS)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0  # on every residual branch
    logits_scaling: float = 1.0       # the logits are DIVIDED by it
    attention_scale: float | None = None   # None: 1/sqrt(head_dim)
    tied_head: bool = False           # the head is the embedding

    def __post_init__(self):
        if not re.fullmatch(f"(?:{_BLOCK})+", self.pattern):
            raise ValueError(
                f"pattern {self.pattern!r} is not a run of blocks ME, M*E "
                f"or *E (at least one mixer, then the experts)"
            )
        if self.router not in ("sigmoid", "softmax_topk"):
            raise ValueError(f"unknown router rule {self.router!r}")
        if self.expert_act not in EXPERT_ACTS:
            raise ValueError(f"unknown expert activation {self.expert_act!r}")
        if len(self.pattern) != self.layers:
            raise ValueError(
                f"layers={self.layers} but the pattern has "
                f"{len(self.pattern)}"
            )
        if not 0 <= self.expert_first <= self.experts - self.experts_held:
            raise ValueError("the held experts lie outside the router's")

    @classmethod
    def nemotron3_nano_ep8(cls, max_seq_len: int = 2048) -> "HybridConfig":
        """NVIDIA-Nemotron-3-Nano-30B-A3B as one chip of eight that share
        each layer: 16 of 128 experts and 16,384 of 131,072 vocabulary rows
        held here, mixers and the shared expert whole, all 52 layers."""
        return cls(max_seq_len=max_seq_len)

    @classmethod
    def granite4_h_small_ep2(cls, max_seq_len: int = 2048) -> "HybridConfig":
        """ibm-granite/granite-4.0-h-small as one chip of a pair that shares
        each layer, one pipeline stage of four: layers 0-9 of 40 (one whole
        period ``M M M M M A M M M M``, each followed by its experts), 36 of
        72 experts and 50,176 of 100,352 vocabulary rows held here, mixers
        and the shared expert whole."""
        return cls(
            vocab_size=50176, hidden=4096, layers=20, heads=32, kv_heads=8,
            head_dim=128, intermediate=768, pattern="ME" * 5 + "*E" + "ME" * 4,
            ssm_heads=128, ssm_head_dim=64, ssm_groups=1, ssm_state=128,
            experts=72, experts_per_token=10, shared_intermediate=1536,
            experts_held=36, max_seq_len=max_seq_len, **_GRANITE_FACTS,
        )

    @classmethod
    def granite_tiny(cls, max_seq_len: int = 128,
                     expert_first: int = 0) -> "HybridConfig":
        """Test size of the ``granitemoehybrid`` layer: two periods of
        ``M M A M``, half of the experts held."""
        return cls(
            vocab_size=384, hidden=64, layers=16, heads=4, kv_heads=2,
            head_dim=16, intermediate=32, pattern="MEME*EME" * 2,
            ssm_heads=16, ssm_head_dim=8, ssm_groups=1, ssm_state=16,
            chunk_size=16, experts=8, experts_per_token=3,
            shared_intermediate=48, experts_held=4,
            expert_first=expert_first, max_seq_len=max_seq_len,
            **_GRANITE_FACTS,
        )

    @classmethod
    def tiny(cls, max_seq_len: int = 128, expert_first: int = 0) -> "HybridConfig":
        """Test size: the same family, at least two of each kind."""
        return cls(
            vocab_size=384, hidden=64, layers=8, heads=4, kv_heads=2,
            head_dim=16, intermediate=32, pattern="MEM*EM*E",
            ssm_heads=8, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
            conv_kernel=4, chunk_size=16, experts=8, experts_per_token=3,
            shared_intermediate=48, experts_held=2,
            expert_first=expert_first, max_seq_len=max_seq_len,
        )

    @property
    def blocks(self) -> tuple[bool, ...]:
        """One entry a block: whether it has the attention."""
        return tuple("*" in b for b in re.findall(_BLOCK, self.pattern))

    @property
    def mamba_blocks(self) -> tuple[bool, ...]:
        """One entry a block: whether it has the Mamba-2 mixer."""
        return tuple("M" in b for b in re.findall(_BLOCK, self.pattern))

    @property
    def attn_layers(self) -> int:
        return self.pattern.count("*")

    @property
    def mamba_layers(self) -> int:
        return self.pattern.count("M")

    @property
    def attn_scale(self) -> float:
        return (1.0 / math.sqrt(self.head_dim) if self.attention_scale is None
                else self.attention_scale)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def state_bytes_per_slot(self) -> int:
        """Recurrent state and convolution tail of one slot, all Mamba-2
        layers."""
        n = self.mamba_layers
        ssm = (self.ssm_heads * self.ssm_head_dim * self.ssm_state
               * jnp.dtype(self.state_dtype).itemsize)
        conv = ((self.conv_kernel - 1) * self.conv_dim
                * jnp.dtype(self.dtype).itemsize)
        return n * (ssm + conv)


#: what a ``granitemoehybrid`` checkpoint fixes beside its sizes
#: (granite-4.0-h-small's config.json: attention_multiplier,
#: embedding_multiplier, residual_multiplier, logits_scaling,
#: tie_word_embeddings; its router takes the top k logits and then their
#: softmax, its experts are gated)
_GRANITE_FACTS = dict(
    router="softmax_topk", expert_act="silu_gated", embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=16.0,
    attention_scale=0.0078125, tied_head=True,
)

# ---------------------------------------------------------------------------
# parameters and state
# ---------------------------------------------------------------------------


def init_hybrid_params(config: HybridConfig, key: jax.Array | None = None) -> dict:
    """Random parameters from a key, one jitted draw a leaf so that the
    float32 draw of a big leaf never sits beside the whole tree. An
    expert's weights depend on its GLOBAL id, so the shares of one
    deployment are slices of the same experts. ``A_log``, ``dt_bias``,
    ``D`` and the router's correction bias are drawn well away from
    trivial values: a term left out changes the logits. So are the scales
    where a family's multipliers would flatten what random weights give:
    the queries' and keys' weights are drawn wider by what an attention
    scale under ``1/sqrt(head_dim)`` takes away (the scores keep a spread
    of about 1), and a tied embedding narrower, ``1 / (sqrt(hidden) x
    embedding_multiplier)``, so that a token's own row does not decide its
    logits (it adds about one spread of the rest to its own) and greedy
    decoding does not repeat itself."""
    c = config
    key = key if key is not None else jax.random.PRNGKey(0)
    n = len(c.blocks)
    nM, nA = c.mamba_layers, c.attn_layers
    H, I, Is = c.hidden, c.intermediate, c.shared_intermediate
    gated = 2 if c.expert_act == "silu_gated" else 1   # [a | b] in one
    qk_fan_in = H if c.attention_scale is None else (
        H * c.attention_scale * math.sqrt(c.head_dim))
    names = iter(range(10 ** 6))

    def normal(shape, fan_in, dtype=None):
        k = jax.random.fold_in(key, next(names))
        scale = 1.0 / math.sqrt(fan_in)
        return jax.jit(
            lambda k: (jax.random.normal(k, shape, jnp.float32) * scale
                       ).astype(dtype or c.dtype)
        )(k)

    def uniform(shape, lo, hi):
        k = jax.random.fold_in(key, next(names))
        return jax.random.uniform(k, shape, jnp.float32, lo, hi)

    def experts(shape, fan_in):
        """(blocks, held) + shape, expert e of block i from (i, global e)."""
        k = jax.random.fold_in(key, next(names))
        scale = 1.0 / math.sqrt(fan_in)

        def one(i, e):
            ke = jax.random.fold_in(jax.random.fold_in(k, i), e)
            return (jax.random.normal(ke, shape, jnp.float32) * scale
                    ).astype(c.dtype)

        held = c.expert_first + jnp.arange(c.experts_held)
        return jax.jit(jax.vmap(
            lambda i: jax.vmap(lambda e: one(i, e))(held)
        ))(jnp.arange(n))

    dt = jnp.exp(uniform((nM, c.ssm_heads), math.log(0.001), math.log(0.1)))
    params = {
        "embed": normal((c.vocab_size, H), 1.0 if not c.tied_head else
                        H * c.embedding_multiplier ** 2),
        "final_norm": jnp.ones((H,), c.dtype),
    }
    if not c.tied_head:
        params["lm_head"] = normal((H, c.vocab_size), H)
    params["mamba"] = {     # one row a Mamba-2 layer
        "norm": jnp.ones((nM, H), c.dtype),
        # [z | xBC | dt] = W_in u, the published fused projection as
        # its three column blocks: the fused width (10304 at nemotron_h's
        # published sizes) is no multiple of the 128-lane tile, and
        # the runtime then keeps the array transposed and the program
        # copies all of it back before every chunk
        "w_z": normal((nM, H, c.d_inner), H),
        "w_xbc": normal((nM, H, c.conv_dim), H),
        "w_dt": normal((nM, H, c.ssm_heads), H),
        "conv_w": normal((nM, c.conv_dim, c.conv_kernel), c.conv_kernel),
        "conv_b": normal((nM, c.conv_dim), 25.0),
        # softplus(dt_bias) is log-uniform in [0.001, 0.1]; A in [1, 16]
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(uniform((nM, c.ssm_heads), 1.0, 16.0)),
        "D": uniform((nM, c.ssm_heads), 0.5, 1.5),
        "gate_norm": jnp.ones((nM, c.d_inner), c.dtype),
        "w_out": normal((nM, c.d_inner, H), c.d_inner),
    }
    params["attn"] = {      # one row an attention layer
        "norm": jnp.ones((nA, H), c.dtype),
        "wq": normal((nA, H, c.heads * c.head_dim), qk_fan_in),
        "wk": normal((nA, H, c.kv_heads * c.head_dim), qk_fan_in),
        "wv": normal((nA, H, c.kv_heads * c.head_dim), H),
        "wo": normal((nA, c.heads * c.head_dim, H), c.heads * c.head_dim),
    }
    sigmoid = c.router == "sigmoid"
    moe = params["moe"] = {     # one row a block; drawn in this order
        "norm": jnp.ones((n, H), c.dtype),
        # float32 as nemotron_h publishes it; the softmax router's weights
        # are the model's type and its logits float32
        "router": normal((n, H, c.experts), H, jnp.float32 if sigmoid else None),
    }
    if sigmoid:
        # small beside the spread of the scores, as a trained bias is:
        # the scores decide the winners and the bias the near-ties
        moe["bias"] = uniform((n, c.experts), -0.02, 0.02)
    # both (held, I, H): the expert width (1856 at nemotron_h's sizes) is no
    # multiple of the lane tile either, so the up-projection is kept output-
    # major and contracts its last axis; a gated expert's is its two halves
    # [a | b], (held, 2 I, H)
    moe["w_up"] = experts((gated * I, H), H)
    moe["w_down"] = experts((I, H), I)
    moe["ws_up"] = normal((n, H, gated * Is), H)
    moe["ws_down"] = normal((n, Is, H), Is)
    return params


def init_hybrid_pool(config: HybridConfig, layout) -> tuple[jax.Array, jax.Array]:
    """The paged pool of the attention layers alone: ``(attention layers,
    num_blocks, block_size, Kh*D)`` for K and for V."""
    c = config
    shape = (c.attn_layers, layout.num_blocks, layout.block_size,
             c.kv_heads * c.head_dim)
    return jnp.zeros(shape, c.dtype), jnp.zeros(shape, c.dtype)


def init_hybrid_state(config: HybridConfig, slots: int) -> dict:
    """``{"ssm": (Mamba-2 layers, slots, heads, head_dim, state), "conv":
    (Mamba-2 layers, slots, kernel - 1, conv_dim)}``, zeros."""
    c = config
    n = c.mamba_layers
    return {
        "ssm": jnp.zeros(
            (n, slots, c.ssm_heads, c.ssm_head_dim, c.ssm_state), c.state_dtype
        ),
        "conv": jnp.zeros((n, slots, c.conv_kernel - 1, c.conv_dim), c.dtype),
    }


# ---------------------------------------------------------------------------
# Mamba-2
# ---------------------------------------------------------------------------


def _project_in(lp: dict, u: jax.Array):
    """``[z | xBC | dt] = W_in u`` by its three column blocks."""
    return u @ lp["w_z"], u @ lp["w_xbc"], u @ lp["w_dt"]


def _split_xbc(c: HybridConfig, xbc: jax.Array):
    lead = xbc.shape[:-1]
    gn = c.ssm_groups * c.ssm_state
    x = xbc[..., : c.d_inner].reshape(lead + (c.ssm_heads, c.ssm_head_dim))
    b = xbc[..., c.d_inner : c.d_inner + gn].reshape(
        lead + (c.ssm_groups, c.ssm_state))
    cm = xbc[..., c.d_inner + gn :].reshape(lead + (c.ssm_groups, c.ssm_state))
    return x, b, cm


def _gated_out(c: HybridConfig, lp: dict, y: jax.Array, z: jax.Array):
    """``W_out (RMSNorm_groups(y * silu(z)) * w)``; y float32 (..., d_inner)."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    lead = g.shape[:-1]
    g = g.reshape(lead + (c.ssm_groups, c.d_inner // c.ssm_groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + c.norm_eps)
    g = g.reshape(lead + (c.d_inner,)).astype(c.dtype) * lp["gate_norm"]
    return g @ lp["w_out"]


def ssd_chunked(
    x: jax.Array,     # (B, P, heads, head_dim)
    dt: jax.Array,    # (B, P, heads) float32, 0 where a row is padding
    A: jax.Array,     # (heads,) float32, negative
    Bm: jax.Array,    # (B, P, groups, state)
    Cm: jax.Array,    # (B, P, groups, state)
    chunk: int,
) -> tuple[jax.Array, jax.Array]:
    """The Mamba-2 recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``,
    ``y_t = h_t C_t`` over a whole prompt in chunks: inside a chunk as one
    masked matmul, between chunks as a scan over their states. Returns
    ``(y (B, P, heads, head_dim) float32, h_P (B, heads, head_dim, state)
    float32)``. A position with ``dt = 0`` neither decays nor feeds the
    state, so a right-padded row ends with the state after its last token."""
    Bsz, Pn, h, p = x.shape
    g, n = Bm.shape[2:]
    j = h // g
    L = min(chunk, Pn)
    nc = Pn // L
    f32 = jnp.float32
    a = (dt * A).reshape(Bsz, nc, L, g, j)
    dtc = dt.reshape(Bsz, nc, L, g, j)
    xc = x.reshape(Bsz, nc, L, g, j, p)
    Bc = Bm.reshape(Bsz, nc, L, g, n)
    Cc = Cm.reshape(Bsz, nc, L, g, n)
    a_cs = jnp.cumsum(a, axis=2)                          # (B,nc,L,g,j)
    # inside the chunk: (C B^T) x decay(l <- s) x dt_s, applied to x
    # float32 operands: a TPU's default precision multiplies them in one
    # bfloat16 pass and accumulates, and returns, float32
    xc, Bc, Cc = xc.astype(f32), Bc.astype(f32), Cc.astype(f32)
    cb = jnp.einsum("bclgn,bcsgn->bclsg", Cc, Bc)
    seg = a_cs[:, :, :, None] - a_cs[:, :, None, :]       # (B,nc,L,L,g,j)
    causal = (jnp.arange(L)[:, None] >= jnp.arange(L)[None, :])[
        None, None, :, :, None, None]
    w = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    w = w * cb[..., None] * dtc[:, :, None]
    y = jnp.einsum("bclsgj,bcsgjp->bclgjp", w, xc)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(a_cs[:, :, -1:] - a_cs) * dtc        # (B,nc,L,g,j)
    own = jnp.einsum("bclgjp,bclgn->bcgjpn", xc * to_end[..., None], Bc)
    chunk_decay = jnp.exp(a_cs[:, :, -1])                 # (B,nc,g,j)

    def carry_state(hc, inp):
        own_c, decay_c = inp
        return hc * decay_c[..., None, None] + own_c, hc

    h_last, h_in = jax.lax.scan(
        carry_state, jnp.zeros((Bsz, g, j, p, n), f32),
        (own.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)),
    )
    h_in = h_in.swapaxes(0, 1)                            # (B,nc,g,j,p,n)
    y = y + jnp.einsum(
        "bclgn,bcgjpn->bclgjp", Cc, h_in,
    ) * jnp.exp(a_cs)[..., None]
    return y.reshape(Bsz, Pn, h, p), h_last.reshape(Bsz, h, p, n)


def mamba_prefill(c: HybridConfig, lp: dict, u: jax.Array, lengths: jax.Array):
    """The Mamba-2 mixer over right-padded prompts ``u (B, P, H)`` (already
    normed). Returns ``(out (B, P, H), state (B, heads, head_dim, state),
    conv tail (B, kernel - 1, conv_dim))``, state and tail as they stand
    after each row's last real token."""
    B, Pn, _ = u.shape
    k = c.conv_kernel
    with jax.named_scope("ssm_in"):
        z, xbc, dt = _project_in(lp, u)
    with jax.named_scope("ssm_conv"):
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        conv = sum(
            padded[:, i : i + Pn].astype(jnp.float32)
            * lp["conv_w"][:, i].astype(jnp.float32)
            for i in range(k)
        ) + lp["conv_b"].astype(jnp.float32)
        x, Bm, Cm = _split_xbc(c, jax.nn.silu(conv).astype(c.dtype))
        # the last k-1 inputs of each row: padded[n .. n+k-2] are the
        # original positions n-k+1 .. n-1 (zeros before the prompt)
        tail = jnp.take_along_axis(
            padded, (lengths[:, None] + jnp.arange(k - 1)[None, :])[..., None],
            axis=1,
        )
    with jax.named_scope("ssm_scan"):
        real = jnp.arange(Pn)[None, :] < lengths[:, None]
        dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
        dt = jnp.where(real[..., None], dt, 0.0)
        y, state = ssd_chunked(x, dt, -jnp.exp(lp["A_log"]), Bm, Cm,
                               c.chunk_size)
        y = y + lp["D"][:, None] * x.astype(jnp.float32)
    with jax.named_scope("ssm_out"):
        out = _gated_out(c, lp, y.reshape(B, Pn, c.d_inner), z)
    return out, state.astype(c.state_dtype), tail


def mamba_step(c: HybridConfig, lp: dict, u: jax.Array, ssm: jax.Array,
               conv: jax.Array, i: jax.Array, active: jax.Array,
               kernel: str = "xla"):
    """One token a slot through Mamba-2 layer ``i``: ``u (B, H)`` normed,
    ``ssm (layers, B, heads, head_dim, state)`` and ``conv (layers, B,
    kernel - 1, conv_dim)`` the stacked state of every layer, of which this
    one's rows are read and replaced in place (under the scopes, so that a
    trace charges the state's traffic to ``ssm_scan`` and the tail's to
    ``ssm_conv``). A slot that is not active keeps its rows. ``kernel`` is
    the decode program's one selection (``"xla"``, ``"pallas"``,
    ``"pallas-interpret"``): how the state's pass is lowered
    (:func:`langstream_tpu.ops.ssm_state.ssm_state_step`)."""
    B = u.shape[0]
    with jax.named_scope("ssm_in"):
        z, xbc, dt = _project_in(lp, u)
    with jax.named_scope("ssm_conv"):
        tail = jax.lax.dynamic_index_in_dim(conv, i, keepdims=False)
        window = jnp.concatenate([tail, xbc[:, None]], axis=1)   # (B, k, C)
        out = jnp.einsum(
            "bkc,ck->bc", window.astype(jnp.float32),
            lp["conv_w"].astype(jnp.float32),
        ) + lp["conv_b"].astype(jnp.float32)
        x, Bm, Cm = _split_xbc(c, jax.nn.silu(out).astype(c.dtype))
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, jnp.where(active[:, None, None], window[:, 1:], tail), i, 0)
    with jax.named_scope("ssm_scan"):
        f32 = jnp.float32
        dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"])     # (B, heads)
        decay = jnp.exp(dt * -jnp.exp(lp["A_log"]))
        xf = x.astype(f32)
        # new = state * decay + (dt x) B^T; y = new C; the rows replaced
        y, ssm = ssm_state_step(
            ssm, i, decay, dt[..., None] * xf, Bm.astype(f32), Cm.astype(f32),
            active, kernel=kernel)
        y = y + lp["D"][:, None] * xf
    with jax.named_scope("ssm_out"):
        out = _gated_out(c, lp, y.reshape(B, c.d_inner), z)
    return out, ssm, conv


# ---------------------------------------------------------------------------
# experts
# ---------------------------------------------------------------------------


def moe_mixer(c, lp: dict, h: jax.Array, valid: jax.Array,
              layer: jax.Array | None = None):
    """Routed experts held here plus the shared expert over rows ``h (T,
    H)``; ``lp`` is one expert layer's weights, or with ``layer`` its
    ``w_up`` and ``w_down`` are the stacks of every layer's
    (models/moe.py ``dropless_experts_grouped``). Returns ``(out (T, H),
    load (experts_held,) int32, chosen experts (T, k))``; ``valid`` rows are
    the ones that count (padding and idle slots route nowhere). A gated
    expert's ``silu(a) * b`` lies between its matmuls, under their scope.
    ``c`` is this family's config or the latent family's (models/latent.py):
    what is read of it are the router's rule and numbers, the activation and
    the share held."""
    act = EXPERT_ACTS[c.expert_act]
    with jax.named_scope("moe_router"):
        if c.router == "sigmoid":
            experts, weights = sigmoid_topk_routing(
                h, lp["router"], lp["bias"], c.experts_per_token,
                c.routed_scale, c.router_dtype,
            )
        elif c.router == "group_limited":
            experts, weights = group_limited_softmax_routing(
                h, lp["router"], c.experts_per_token, c.n_group,
                c.topk_group, c.routed_scale, c.router_dtype)
        else:
            experts, weights = softmax_topk_routing(
                h, lp["router"], c.experts_per_token, c.router_dtype)
    routed, load = dropless_experts(
        h, experts, weights, lp["w_up"], lp["w_down"], c.expert_first, valid,
        layer=layer, act=act,
    )
    with jax.named_scope("moe_shared"):
        shared = act(h @ lp["ws_up"]) @ lp["ws_down"]
    with jax.named_scope("moe_combine"):
        return (routed + shared.astype(jnp.float32)).astype(h.dtype), load, experts


def _residual(c: HybridConfig, x: jax.Array, out: jax.Array) -> jax.Array:
    """``x + m * out``; at ``m`` = 1 no multiply is traced."""
    if c.residual_multiplier == 1.0:
        return x + out
    return x + out * c.residual_multiplier


def _embed(c: HybridConfig, params: dict, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    return x if c.embedding_multiplier == 1.0 else x * c.embedding_multiplier


def _logits(c: HybridConfig, params: dict, x: jax.Array) -> jax.Array:
    """Float32 logits of normed rows ``x (B, H)``: over the head, or over
    the embedding where the head is tied to it, divided by the family's
    ``logits_scaling``."""
    if c.tied_head:
        logits = jnp.einsum("bh,vh->bv", x, params["embed"])
    else:
        logits = x @ params["lm_head"]
    logits = logits.astype(jnp.float32)
    return logits if c.logits_scaling == 1.0 else logits / c.logits_scaling


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def _block_xs(c: HybridConfig, params: dict, experts_in_xs: bool = True):
    """What the scan over blocks slices a block at a time. A prefill keeps
    the routed experts' stacks out (``experts_in_xs=False``) and reads them
    by the block's index (:func:`moe_mixer` ``layer``). Where every block
    has its Mamba-2 mixer the first entry is the mixers' weights, sliced
    with the block; otherwise ``(has the mixer, its Mamba-2 layer)`` and the
    block reaches the weights by that index (:func:`_mamba_in_block`); a
    block without the mixer points past the last layer, where a write of
    state rows is dropped."""
    has = jnp.asarray(c.blocks)
    # a block without attention points at the spare row past the last layer
    idx = jnp.where(has, jnp.cumsum(has) - 1, c.attn_layers).astype(jnp.int32)
    moe = params["moe"] if experts_in_xs else {
        k: v for k, v in params["moe"].items() if k not in ("w_up", "w_down")}
    mamba = params["mamba"]
    if not all(c.mamba_blocks):
        has_m = jnp.asarray(c.mamba_blocks)
        mamba = (has_m, jnp.where(
            has_m, jnp.cumsum(has_m) - 1, c.mamba_layers).astype(jnp.int32))
    return (mamba, moe, has, idx, jnp.arange(len(c.blocks), dtype=jnp.int32))


def _layer_at(stack: dict, i: jax.Array) -> dict:
    return jax.tree.map(
        lambda t: jax.lax.dynamic_index_in_dim(t, i, keepdims=False), stack)


def _mamba_in_block(c: HybridConfig, params: dict, mp, i, mixer: Callable,
                    absent: Callable, *operands):
    """The block's Mamba-2 sub-layer: ``(its Mamba-2 layer, mixer(weights,
    that layer, *operands))``. ``mp`` and ``i`` are the block's slice of
    :func:`_block_xs`: the layer's weights and the block's index where every
    block has the mixer (nothing is traced around it), else ``(has the
    mixer, its layer)``, and a block without one gives ``absent(*operands)``
    in the mixer's place."""
    if all(c.mamba_blocks):
        return i, mixer(mp, i, *operands)
    has_m, m = mp
    return m, jax.lax.cond(
        has_m, lambda *a: mixer(_layer_at(params["mamba"], m), m, *a), absent,
        *operands)


def hybrid_prefill_paged(
    config: HybridConfig,
    params: dict,
    tokens: jax.Array,        # (B, P) int32, right-padded
    lengths: jax.Array,       # (B,) true lengths
    pool_k: jax.Array,        # (attention layers, nb, bs, Kh*D)
    pool_v: jax.Array,
    state: dict,              # init_hybrid_state: all slots
    block_tables: jax.Array,  # (B, max_blocks): rows of THIS batch
    slot_ids: jax.Array,      # (B,) the slots whose state rows are written
    use_flash: bool | None = None,
):
    """Prompt forward: the attention layers' K/V rows land in the pool, and
    each row's recurrent state and convolution tail, as they stand after its
    last real token, overwrite its slot's rows of ``state``. Returns
    ``(last-token logits (B, V), pool_k, pool_v, state, routed)``; ``routed
    (blocks, B, P, k)`` are the experts the router chose, for the reference
    check (a caller that drops it pays nothing for it).

    The commit is :func:`langstream_tpu.models.paged.write_rows`, the one
    every family uses: the attention layer is folded into the row index, so
    the pool is scattered where it lies and never copied."""
    c = config
    B, Pn = tokens.shape
    nA = c.attn_layers
    KhD = c.kv_heads * c.head_dim
    G = c.heads // c.kv_heads
    real = jnp.arange(Pn)[None, :] < lengths[:, None]             # (B, P)
    flash = (_flash_mode(Pn) if use_flash is None
             else ("compiled" if use_flash else None))
    with jax.named_scope("embed"):
        x = _embed(c, params, tokens)

    def attention(x, a):
        ap = _layer_at(params["attn"], a)
        with jax.named_scope("attn_qkv"):
            h = _rms_norm(x, ap["norm"], c.norm_eps)
            q = jnp.einsum("bph,hd->bpd", h, ap["wq"]).reshape(
                B, Pn, c.heads, c.head_dim)
            k = jnp.einsum("bph,hd->bpd", h, ap["wk"]).reshape(
                B, Pn, c.kv_heads, c.head_dim)
            v = jnp.einsum("bph,hd->bpd", h, ap["wv"]).reshape(
                B, Pn, c.kv_heads, c.head_dim)
        if flash is not None:
            with jax.named_scope("flash"):
                # causality alone hides the right-padding from real rows
                from langstream_tpu.ops.flash_attention import flash_attention

                out = flash_attention(
                    q, k, v, causal=True, scale=c.attention_scale,
                    interpret=(flash == "interpret"))
        else:
            with jax.named_scope("kv_read"):
                qg = q.reshape(B, Pn, c.kv_heads, G, c.head_dim)
                s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
                s = (s / math.sqrt(c.head_dim) if c.attention_scale is None
                     else s * c.attention_scale)
                mask = (jnp.arange(Pn)[:, None] >= jnp.arange(Pn)[None, :])[
                    None] & real[:, None, :]
                s = jnp.where(mask[:, None, None], s, NEG_INF)
                out = jnp.einsum(
                    "bkgqs,bskd->bqkgd", jax.nn.softmax(s, -1).astype(x.dtype), v)
        with jax.named_scope("attn_out"):
            out = out.reshape(B, Pn, c.heads * c.head_dim)
            x = _residual(c, x, jnp.einsum("bpd,dh->bph", out, ap["wo"]))
        return x, k.reshape(B, Pn, KhD), v.reshape(B, Pn, KhD)

    def no_attention(x, a):
        zero = jnp.zeros((B, Pn, KhD), x.dtype)
        return x, zero, zero

    def mamba(mp, m, x):
        return mamba_prefill(
            c, mp, _rms_norm(x, mp["norm"], c.norm_eps), lengths)

    def no_mamba(x):
        # nothing for the residual, and rows whose write is dropped
        return (jnp.zeros_like(x),
                jnp.zeros((B,) + state["ssm"].shape[2:], state["ssm"].dtype),
                jnp.zeros((B,) + state["conv"].shape[2:], state["conv"].dtype))

    def block(carry, xs):
        x, ks, vs, ssm_all, conv_all = carry
        mp, ep, has, a, i = xs
        m, (out, ssm, tail) = _mamba_in_block(
            c, params, mp, i, mamba, no_mamba, x)
        with jax.named_scope("ssm_state_write"):
            # this layer's rows of the batch's slots, in the carry: the
            # whole state is never stacked beside itself, and stays outside
            # the conditional a block without the mixer needs (a whole
            # state through its branches is copied whole; such a block's
            # layer lies past the last, and its rows are dropped)
            ssm_all = ssm_all.at[m, slot_ids].set(ssm, mode="drop")
            conv_all = conv_all.at[m, slot_ids].set(tail, mode="drop")
        x = _residual(c, x, out)
        x, k, v = jax.lax.cond(has, attention, no_attention, x, a)
        ks = jax.lax.dynamic_update_index_in_dim(ks, k, a, 0)
        vs = jax.lax.dynamic_update_index_in_dim(vs, v, a, 0)
        h = _rms_norm(x, ep["norm"], c.norm_eps).reshape(B * Pn, c.hidden)
        ep = dict(ep, w_up=params["moe"]["w_up"], w_down=params["moe"]["w_down"])
        out, _, chosen = moe_mixer(c, ep, h, real.reshape(-1), layer=i)
        x = _residual(c, x, out.reshape(B, Pn, c.hidden))
        return (x, ks, vs, ssm_all, conv_all), chosen.reshape(B, Pn, -1)

    spare = jnp.zeros((nA + 1, B, Pn, KhD), c.dtype)
    (x, ks, vs, ssm_all, conv_all), routed = jax.lax.scan(
        block, (x, spare, spare, state["ssm"], state["conv"]),
        _block_xs(c, params, experts_in_xs=False))
    with jax.named_scope("lm_head"):
        x = _rms_norm(x, params["final_norm"], c.norm_eps)
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].clip(0), axis=1).squeeze(1)
        logits = _logits(c, params, last)
    starts = jnp.zeros((B,), jnp.int32)
    with jax.named_scope("kv_write"):
        pool_k = write_rows(pool_k, ks[:nA], block_tables, starts, real)
        pool_v = write_rows(pool_v, vs[:nA], block_tables, starts, real)
    return logits, pool_k, pool_v, {"ssm": ssm_all, "conv": conv_all}, routed


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def hybrid_decode_chunk_paged(
    config: HybridConfig,
    params: dict,
    tokens0: jax.Array,       # (B,)
    base_lengths: jax.Array,  # (B,)
    active: jax.Array,        # (B,) bool
    pool_k: jax.Array,        # read-only during the chunk
    pool_v: jax.Array,
    state: dict,              # advanced in the scan's carry
    block_tables: jax.Array,  # (B, max_blocks)
    sample_fn: Callable,
    key: jax.Array,
    num_steps: int,
    num_read_blocks: int,
    kernel: str = "xla",
    sample_extras=None,       # (presences, frequencies, counts0)
    return_packed: bool = False,
):
    """K fused decode steps. The pool is read-only and the new K/V rows of
    the attention layers gather in a chunk buffer (one scatter at the end:
    :func:`langstream_tpu.models.paged.write_rows`, the layer in the row
    index, no copy of the pool), as in the dense family's chunk; the
    recurrent state rides the scan's carry and each Mamba-2 layer replaces
    its own rows of it in place.

    Returns ``(chunk_tokens, chunk_logprobs, final_tokens, final_lengths,
    pool_k, pool_v, state, load, routed)`` where ``load (blocks,
    experts_held)`` counts the chosen pairs each held expert got over the
    chunk's active rows and ``routed (steps, blocks, B, k)`` are the experts
    the router chose (for the reference check); ``return_packed=True`` folds
    tokens, logprobs and ``load`` into one int32 array in their place and
    leaves ``routed`` out."""
    c = config
    B = tokens0.shape[0]
    nA, nB = c.attn_layers, len(c.blocks)
    KhD = c.kv_heads * c.head_dim
    G = c.heads // c.kv_heads
    scale = c.attn_scale
    adv = active.astype(jnp.int32)
    pen = sample_extras is not None
    counts0 = sample_extras[2] if pen else None
    block_xs = _block_xs(c, params)

    def cache_partial(q, a):
        if kernel == "xla":
            # the stacked pool and the layer's index, as the kernel takes them
            return _cache_partial_xla(
                c, q, pool_k, pool_v, a, block_tables, base_lengths,
                num_read_blocks, scale=c.attention_scale,
            )
        return paged_attention_partial(
            q, pool_k, pool_v, a, block_tables, base_lengths,
            num_read_blocks=num_read_blocks, kv_heads=c.kv_heads,
            head_dim=c.head_dim, scale=scale,
            interpret=(kernel == "pallas-interpret"),
        )

    def step(carry, step_idx):
        tokens, kbuf, vbuf, key, ssm, conv, load = carry[:7]
        counts = carry[7] if pen else None
        with jax.named_scope("sample"):
            key, sub = jax.random.split(key)
        with jax.named_scope("embed"):
            x = _embed(c, params, tokens)
        buf_mask = jnp.arange(num_steps)[None, :] <= step_idx      # (1, K)

        def attention(x, a, kbuf, vbuf):
            ap = _layer_at(params["attn"], a)
            with jax.named_scope("attn_qkv"):
                h = _rms_norm(x, ap["norm"], c.norm_eps)
                q = (h @ ap["wq"]).reshape(B, c.heads, c.head_dim)
                k = (h @ ap["wk"]).reshape(B, c.kv_heads, c.head_dim)
                v = (h @ ap["wv"]).reshape(B, c.kv_heads, c.head_dim)
                # this layer's rows of the chunk so far, with this step's
                kb = jax.lax.dynamic_update_slice_in_dim(
                    jax.lax.dynamic_index_in_dim(kbuf, a, keepdims=False),
                    k[:, None], step_idx, axis=1)                  # (B,K,Kh,D)
                vb = jax.lax.dynamic_update_slice_in_dim(
                    jax.lax.dynamic_index_in_dim(vbuf, a, keepdims=False),
                    v[:, None], step_idx, axis=1)
            with jax.named_scope("kv_read"):
                acc_c, m_c, l_c = cache_partial(q, a)
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
                    (acc_c, m_c, l_c),
                    (acc_b.reshape(B, c.heads, c.head_dim),
                     m_b.reshape(B, c.heads),
                     jnp.sum(p_b, axis=-1).reshape(B, c.heads)),
                ]).astype(x.dtype).reshape(B, c.heads * c.head_dim)
            with jax.named_scope("attn_out"):
                return _residual(c, x, out @ ap["wo"]), k, v

        def no_attention(x, a, kbuf, vbuf):
            zero = jnp.zeros((B, c.kv_heads, c.head_dim), x.dtype)
            return x, zero, zero

        def mamba(mp, m, x, ssm, conv):
            out, ssm, conv = mamba_step(
                c, mp, _rms_norm(x, mp["norm"], c.norm_eps), ssm, conv, m,
                active, kernel)
            return _residual(c, x, out), ssm, conv

        def block(carry, xs):
            x, kbuf, vbuf, ssm, conv = carry
            mp, ep, has, a, i = xs
            _, (x, ssm, conv) = _mamba_in_block(
                c, params, mp, i, mamba, lambda *through: through, x, ssm,
                conv)
            x, k, v = jax.lax.cond(
                has, attention, no_attention, x, a, kbuf, vbuf)
            with jax.named_scope("attn_qkv"):
                kbuf = jax.lax.dynamic_update_slice(
                    kbuf, k[None, :, None], (a, 0, step_idx, 0, 0))
                vbuf = jax.lax.dynamic_update_slice(
                    vbuf, v[None, :, None], (a, 0, step_idx, 0, 0))
            out, load_i, chosen = moe_mixer(
                c, ep, _rms_norm(x, ep["norm"], c.norm_eps), active)
            return (_residual(c, x, out), kbuf, vbuf, ssm, conv), \
                (load_i, chosen)

        (x, kbuf, vbuf, ssm, conv), (load_step, chosen) = jax.lax.scan(
            block, (x, kbuf, vbuf, ssm, conv), block_xs)
        with jax.named_scope("lm_head"):
            x = _rms_norm(x, params["final_norm"], c.norm_eps)
            logits = _logits(c, params, x)
        with jax.named_scope("sample"):
            nxt, lp_ = (sample_fn(logits, sub, counts) if pen
                        else sample_fn(logits, sub))
            nxt = jnp.where(active, nxt, tokens)
        out_carry = (nxt, kbuf, vbuf, key, ssm, conv, load + load_step)
        if pen:
            out_carry += (counts.at[jnp.arange(B), nxt].add(adv),)
        return out_carry, (nxt, lp_, chosen)

    kbuf0 = jnp.zeros((nA + 1, B, num_steps, c.kv_heads, c.head_dim), c.dtype)
    carry0 = (tokens0, kbuf0, kbuf0, key, state["ssm"], state["conv"],
              jnp.zeros((nB, c.experts_held), jnp.int32))
    if pen:
        carry0 += (counts0,)
    out_carry, (chunk_tokens, chunk_lps, routed) = jax.lax.scan(
        step, carry0, jnp.arange(num_steps))
    final_tokens, kbuf, vbuf, _, ssm, conv, load = out_carry[:7]
    valid = jnp.broadcast_to(active[:, None], (B, num_steps))
    with jax.named_scope("kv_write"):
        pool_k = write_rows(
            pool_k, kbuf[:nA].reshape(nA, B, num_steps, KhD), block_tables,
            base_lengths, valid)
        pool_v = write_rows(
            pool_v, vbuf[:nA].reshape(nA, B, num_steps, KhD), block_tables,
            base_lengths, valid)
    final_lengths = base_lengths + num_steps * adv
    state = {"ssm": ssm, "conv": conv}
    if return_packed:
        packed = jnp.concatenate(
            [pack_tokens_logprobs(chunk_tokens, chunk_lps), load.reshape(-1)])
        return packed, final_tokens, final_lengths, pool_k, pool_v, state
    return (chunk_tokens, chunk_lps, final_tokens, final_lengths, pool_k,
            pool_v, state, load, routed)
