"""Mixture-of-Experts decoder (Mixtral-family), pure JAX, TPU-first.

Design (vs. a torch port of Mixtral):

- **Capacity-based top-2 dispatch as one-hot matmuls** (GShard style): the
  dispatch/combine tensors are einsummed on the MXU — no scatter/gather, no
  dynamic shapes, so XLA tiles everything. Tokens overflowing an expert's
  capacity fall through the residual (standard GShard semantics).
- **Expert parallelism over the ``ep`` mesh axis**: expert weights are
  sharded ``P("ep", ...)``; the dispatch einsum contracts a ``dp``-sharded
  token axis against an ``ep``-sharded expert axis, so XLA inserts the
  all-to-all over ICI — no hand-written collectives.
- **TP composes inside each expert**: expert up/gate column-sharded on
  ``tp``, down row-sharded, same Megatron rule as the dense model.
- Attention blocks are exactly the Llama ones (imported), so every
  parallelism mode of the dense path (ring/Ulysses sp, flash prefill)
  composes with MoE FFNs.

Capability parity: the reference serves MoE SaaS models (e.g. Mixtral via
Ollama/HF providers, ``HuggingFaceProvider.java:47``); here the MoE family
is in-tree and TPU-resident.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from langstream_tpu.models.llama import (
    _rms_norm,
    _rope,
    attention_block,
)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32000
    hidden: int = 4096
    layers: int = 32
    heads: int = 32
    kv_heads: int = 8
    head_dim: int = 128
    moe_intermediate: int = 14336
    experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16

    @classmethod
    def mixtral_8x7b(cls, max_seq_len: int = 4096) -> "MoEConfig":
        return cls(max_seq_len=max_seq_len)

    @classmethod
    def tiny(cls, max_seq_len: int = 128) -> "MoEConfig":
        return cls(
            vocab_size=384, hidden=64, layers=2, heads=4, kv_heads=2,
            head_dim=16, moe_intermediate=128, experts=4,
            experts_per_token=2, max_seq_len=max_seq_len,
        )

    def capacity(self, tokens: int) -> int:
        """Static per-expert capacity for a batch of ``tokens``."""
        return max(
            1,
            int(
                math.ceil(
                    self.experts_per_token * tokens * self.capacity_factor
                    / self.experts
                )
            ),
        )


def init_moe_params(config: MoEConfig, key: jax.Array | None = None) -> dict:
    key = key if key is not None else jax.random.PRNGKey(0)
    c = config
    keys = jax.random.split(key, 12)
    qkv_dim = c.heads * c.head_dim
    kv_dim = c.kv_heads * c.head_dim
    L, E, I = c.layers, c.experts, c.moe_intermediate

    def w_init(k, *shape, fan_in):
        scale = 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(k, shape, dtype=jnp.float32) * scale).astype(
            c.dtype
        )

    return {
        "embed": w_init(keys[0], c.vocab_size, c.hidden, fan_in=c.hidden),
        "layers": {
            "attn_norm": jnp.ones((L, c.hidden), dtype=c.dtype),
            "wq": w_init(keys[1], L, c.hidden, qkv_dim, fan_in=c.hidden),
            "wk": w_init(keys[2], L, c.hidden, kv_dim, fan_in=c.hidden),
            "wv": w_init(keys[3], L, c.hidden, kv_dim, fan_in=c.hidden),
            "wo": w_init(keys[4], L, qkv_dim, c.hidden, fan_in=qkv_dim),
            "mlp_norm": jnp.ones((L, c.hidden), dtype=c.dtype),
            # router stays float32: tiny, and routing decisions are
            # numerically delicate
            "router": jax.random.normal(
                keys[5], (L, c.hidden, E), dtype=jnp.float32
            ) * (1.0 / math.sqrt(c.hidden)),
            "w_gate": w_init(keys[6], L, E, c.hidden, I, fan_in=c.hidden),
            "w_up": w_init(keys[7], L, E, c.hidden, I, fan_in=c.hidden),
            "w_down": w_init(keys[8], L, E, I, c.hidden, fan_in=I),
        },
        "final_norm": jnp.ones((c.hidden,), dtype=c.dtype),
        "lm_head": w_init(keys[9], c.hidden, c.vocab_size, fan_in=c.hidden),
    }


def moe_param_specs(config: MoEConfig) -> dict:
    """Expert axis on ``ep``, Megatron TP inside each expert."""
    return {
        "embed": P(None, None),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, "tp"),
            "wk": P(None, None, "tp"),
            "wv": P(None, None, "tp"),
            "wo": P(None, "tp", None),
            "mlp_norm": P(None, None),
            "router": P(None, None, None),
            "w_gate": P(None, "ep", None, "tp"),
            "w_up": P(None, "ep", None, "tp"),
            "w_down": P(None, "ep", "tp", None),
        },
        "final_norm": P(None),
        "lm_head": P(None, "tp"),
    }


def shard_moe_params(params: dict, config: MoEConfig, mesh: Mesh) -> dict:
    specs = moe_param_specs(config)
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# top-2 gating + dispatch
# ---------------------------------------------------------------------------


def top2_gating(
    router_logits: jax.Array,  # (B, S, E) float32
    capacity: int,
    valid: jax.Array | None = None,  # (B, S) bool; invalid positions take no
                                     # capacity and get zero combine weight
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """GShard top-2 gating with static capacity.

    Returns (dispatch (B,S,E,C) bool, combine (B,S,E,C) float32,
    aux_loss scalar — the load-balancing loss from the GShard/Switch papers).

    ``valid`` matters under serving: right-padded prefill positions and
    inactive decode slots would otherwise queue for (and evict real tokens
    from) expert capacity, making a prompt's logits depend on its batch
    neighbours' padding.
    """
    B, S, E = router_logits.shape
    probs = jax.nn.softmax(router_logits, axis=-1)  # (B, S, E)

    idx1 = jnp.argmax(probs, axis=-1)                       # (B, S)
    mask1 = jax.nn.one_hot(idx1, E, dtype=probs.dtype)      # (B, S, E)
    if valid is not None:
        mask1 = mask1 * valid[..., None].astype(probs.dtype)
    p1 = jnp.sum(probs * mask1, axis=-1)                    # (B, S)

    probs2 = probs * (1.0 - mask1)
    idx2 = jnp.argmax(probs2, axis=-1)
    mask2 = jax.nn.one_hot(idx2, E, dtype=probs.dtype)
    if valid is not None:
        mask2 = mask2 * valid[..., None].astype(probs.dtype)
    p2 = jnp.sum(probs * mask2, axis=-1)

    # renormalise the two winners (Mixtral semantics)
    denom = p1 + p2 + 1e-9
    w1, w2 = p1 / denom, p2 / denom

    # position of each token within its expert's queue, flattened over (B,S)
    flat1 = mask1.reshape(B * S, E)
    flat2 = mask2.reshape(B * S, E)
    pos1 = jnp.cumsum(flat1, axis=0) * flat1 - flat1        # 0-based
    pos2 = (jnp.cumsum(flat2, axis=0) + flat1.sum(0, keepdims=True)) * flat2 - flat2
    keep1 = (pos1 < capacity) & (flat1 > 0)
    keep2 = (pos2 < capacity) & (flat2 > 0)

    oh1 = jax.nn.one_hot(pos1.astype(jnp.int32), capacity, dtype=probs.dtype)
    oh2 = jax.nn.one_hot(pos2.astype(jnp.int32), capacity, dtype=probs.dtype)
    combine_flat = (
        w1.reshape(-1, 1, 1) * keep1[..., None] * oh1
        + w2.reshape(-1, 1, 1) * keep2[..., None] * oh2
    )  # (B*S, E, C)
    combine = combine_flat.reshape(B, S, E, capacity)
    dispatch = combine > 0.0

    # load-balancing auxiliary loss: E * Σ_e fraction_tokens_e · mean_prob_e
    density = mask1.reshape(B * S, E).mean(axis=0)
    density_proxy = probs.reshape(B * S, E).mean(axis=0)
    aux_loss = jnp.sum(density * density_proxy) * (E * E) / 2.0
    return dispatch, combine, aux_loss


def moe_ffn(
    x: jax.Array,            # (B, S, H)
    router_w: jax.Array,     # (H, E) float32
    w_gate: jax.Array,       # (E, H, I)
    w_up: jax.Array,         # (E, H, I)
    w_down: jax.Array,       # (E, I, H)
    capacity: int,
    ep_constrain=None,       # applied to (E, C', H) expert-major tensors
    valid: jax.Array | None = None,  # (B, S) bool — see top2_gating
) -> tuple[jax.Array, jax.Array]:
    """Top-2 MoE feed-forward; returns (output (B,S,H), aux_loss).

    The two einsums flanking the expert computation are the all-to-alls:
    tokens (sharded ``dp``/``sp``) → expert-major (sharded ``ep``) and back.
    """
    B, S, H = x.shape
    router_logits = jnp.einsum(
        "bsh,he->bse", x.astype(jnp.float32), router_w
    )
    dispatch, combine, aux = top2_gating(router_logits, capacity, valid=valid)
    dispatch = dispatch.astype(x.dtype)
    if ep_constrain is None:
        ep_constrain = lambda t: t  # noqa: E731
    # dispatch all-to-all: tokens → (E, C, H) expert-major
    xe = ep_constrain(jnp.einsum("bsec,bsh->ech", dispatch, x))
    gate = jax.nn.silu(jnp.einsum("ech,ehi->eci", xe, w_gate))
    up = jnp.einsum("ech,ehi->eci", xe, w_up)
    ye = ep_constrain(jnp.einsum("eci,eih->ech", gate * up, w_down))
    # combine all-to-all: expert-major → tokens
    out = jnp.einsum("bsec,ech->bsh", combine.astype(x.dtype), ye)
    return out, aux


# ---------------------------------------------------------------------------
# forward (training / prefill building block)
# ---------------------------------------------------------------------------


def moe_forward(
    config: MoEConfig,
    params: dict,
    tokens: jax.Array,  # (B, S)
    *,
    attention=None,
    constrain=None,     # activations (B,S,H)
    ep_constrain=None,  # expert-major intermediates (E,C,H)
) -> tuple[jax.Array, jax.Array]:
    """All-position logits (B, S, V) + summed aux loss. Same shape contract
    as :func:`llama_forward`, plus the MoE auxiliary load-balancing loss the
    training step adds to the CE loss."""
    c = config
    B, S = tokens.shape
    if attention is None:
        from langstream_tpu.parallel.ring import dense_attention
        from functools import partial

        attention = partial(
            dense_attention, causal=True, scale=1.0 / math.sqrt(c.head_dim)
        )
    if constrain is None:
        constrain = lambda x: x  # noqa: E731
    capacity = c.capacity(B * S)

    x = constrain(jnp.take(params["embed"], tokens, axis=0))
    positions = jnp.arange(S)[None, :].repeat(B, axis=0)
    cos, sin = _rope(positions, c.head_dim, c.rope_theta)

    def layer(carry, lp):
        x, aux_total = carry
        x = attention_block(c, x, lp, cos, sin, attention)
        h2 = _rms_norm(x, lp["mlp_norm"], c.norm_eps)
        ffn, aux = moe_ffn(
            h2, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
            capacity, ep_constrain=ep_constrain,
        )
        x = x + ffn
        return (constrain(x), aux_total + aux), None

    (x, aux_total), _ = jax.lax.scan(layer, (x, jnp.float32(0.0)), params["layers"])
    x = _rms_norm(x, params["final_norm"], c.norm_eps)
    logits = jnp.einsum("bsh,hv->bsv", x, params["lm_head"]).astype(jnp.float32)
    return logits, aux_total


def moe_forward_sharded(
    config: MoEConfig,
    params: dict,
    tokens: jax.Array,
    mesh: Mesh,
) -> tuple[jax.Array, jax.Array]:
    """Mesh-annotated MoE forward: activations on dp/sp, expert-major
    intermediates on ep (XLA materialises the dispatch/combine all-to-alls
    over ICI at those constraints)."""
    axes = mesh.axis_names
    dp = "dp" if "dp" in axes else None
    sp = "sp" if "sp" in axes else None
    ep = "ep" if "ep" in axes else None
    x_spec = NamedSharding(mesh, P(dp, sp, None))
    e_spec = NamedSharding(mesh, P(ep, None, None))
    return moe_forward(
        config, params, tokens,
        constrain=lambda x: jax.lax.with_sharding_constraint(x, x_spec),
        ep_constrain=lambda t: jax.lax.with_sharding_constraint(t, e_spec),
    )


def moe_serving_ffn(config: MoEConfig, ep_constrain=None):
    """FFN callback for the shared llama serving paths (prefill_forward /
    llama_decode_chunk / the paged twins): routes each position through the
    top-2 expert mix. Accepts ``(B, H)`` decode activations or ``(B, S, H)``
    prefill activations; understands int8-quantized expert weights.

    This is what makes MoE a *served* family, not just a trainable one —
    the reference can only reach MoE models through SaaS providers
    (``HuggingFaceProvider.java:47``); here Mixtral-class models run on the
    same continuous-batching engine as the dense Llamas.
    """
    from langstream_tpu.models.quant import as_weight

    def ffn(h: jax.Array, lp: dict, valid: jax.Array | None = None) -> jax.Array:
        squeeze = h.ndim == 2
        x = h[:, None, :] if squeeze else h
        if valid is not None and valid.ndim == 1:
            valid = valid[:, None]  # decode: (B,) active → (B, 1)
        B, S, _H = x.shape
        capacity = config.capacity(B * S)
        out, _aux = moe_ffn(
            x,
            lp["router"],
            as_weight(lp["w_gate"]),
            as_weight(lp["w_up"]),
            as_weight(lp["w_down"]),
            capacity,
            ep_constrain=ep_constrain,
            valid=valid,
        )
        return out[:, 0, :] if squeeze else out

    return ffn


def moe_param_count(config: MoEConfig) -> int:
    c = config
    attn = (
        c.hidden * c.heads * c.head_dim
        + 2 * c.hidden * c.kv_heads * c.head_dim
        + c.heads * c.head_dim * c.hidden
    )
    experts = c.experts * 3 * c.hidden * c.moe_intermediate
    per_layer = attn + experts + c.hidden * c.experts + 2 * c.hidden
    return c.layers * per_layer + 2 * c.vocab_size * c.hidden + c.hidden


# ---------------------------------------------------------------------------
# dropless top-k routing over the experts this chip holds
# ---------------------------------------------------------------------------
#
# The capacity path above drops what overflows an expert, so a token's
# output depends on its batch neighbours. The functions below drop nothing:
# a router over ALL experts chooses k of them per token, and this chip
# computes the chosen pairs whose expert it holds (``[first, first+held)``).
# What the other chips' experts would add is left out here, as it would be
# before the exchange in an expert-parallel deployment.

#: rows up to which the dense pass over the held experts is taken: every
#: held expert's weights are streamed anyway at a decode batch, and the
#: pass has no sort, gather or scatter. Past it the grouped pass, whose form
#: is the programs' kernel selection (:func:`dropless_experts_grouped`).
#: Measured on the v5e, one layer, ms a call, dense / grouped as the XLA loop
#: of 256-row blocks / grouped through the kernel (PR 47; both probes build
#: the served engine and time its first expert layer under its own router).
#: At the published share of 16 experts of 2 x 2688 x 1856, top 6 of 128
#: (tools/hybrid_probe.py --crossover): 64 rows 0.49 / 1.11 / 0.53, 128 rows
#: 0.49 / 1.18 / 0.56, 256 rows 0.56 / 1.44 / 0.58, 384 rows 0.82 / 1.23 /
#: 0.60, 512 rows 1.08 / 1.25 / 0.63, 768 rows 1.55 / 1.30 / 0.70, 1,024
#: rows 2.33 / 1.97 / 0.86, 2,048 rows 4.36 / 1.47 / 1.40, 4,096 rows - /
#: 1.68 / 2.25, 8,192 rows - / 3.53 / 4.20 (there the loop's float32 result,
#: 44 and 88 MB, is kept in VMEM by the compiler and its scatter-adds cost a
#: third of what they cost in HBM; no cell of that share prefills more than
#: 2,048 rows; the third column there is a form of the kernel's pass that
#: sorted the held pairs alone and added their results 256 rows at a time,
#: never served and taken out again: commit f49ab30). At a WHOLE layer of
#: experts, 64 held of 64 of 3 x 896 x 2304,
#: top 8 (tools/swa_probe.py --config mellum2-12b-a2.5b-8l --crossover): 64
#: rows 1.13 / 3.71 / 1.19, 128 rows 1.14 / 3.67 / 1.23, 256 rows 1.27 / 3.78
#: / 1.29 (0.97 ms stream the layer's 793 MB), 384 rows 1.89 / 3.70 / 1.37,
#: 512 rows 2.66 / 4.55 / 1.44, 768 rows 4.14 / 3.84 / 1.78, 1,024 rows 5.70
#: / 3.94 / 2.01, 2,048 rows 13.1 / 6.28 / 3.17, 4,096 rows - / 10.4 / 5.20,
#: 8,192 rows - / 21.0 / 11.5. Both tables put the crossover between 256 and
#: 384 rows (it lay between 512 and 768 while the loop was the only grouped
#: form, whose 64 steps cost 58 us each whatever their rows: 0.83 of
#: dispatch, 1.61 of matmuls and 1.17 of scatter-adds at 1,024 rows, where
#: the kernel's pass has 0.26, 1.29 and 0.37), and every cell's decode batch
#: is 32 to 192 rows: bench/lib/roofline_wf.py prices the decode step by
#: this constant, under this name
DENSE_ROWS_MAX = 256
#: the same bound where the grouped pass is the XLA loop (the CPU, and a
#: chip that holds a SHARE of the experts: :func:`grouped_form`): the loop's
#: crossover with the dense pass, between 512 and 768 rows in both tables
#: above. bench/lib/roofline_wf.py prices a decode step by DENSE_ROWS_MAX
#: alone, which is right while every window-and-full cell holds all its
#: experts and decodes at most 192 rows: a configuration of that family
#: served at a share switches here, at 512, and its reader would have to
#: read the bound taken, ``grouped_form(...)[1]``
LOOP_DENSE_ROWS_MAX = 512
#: rows of one step of the grouped pass's XLA loop (one expert's weights a
#: step)
GROUP_BLOCK_ROWS = 256


def _rounded_to(dtype):
    """Identity for float32; below it (a control of the reference check,
    never served) a rounding to ``dtype`` that stays float32. Not a pair of
    converts: XLA keeps the excess precision of those."""
    if jnp.dtype(dtype) == jnp.float32:
        return lambda x: x
    info = jnp.finfo(dtype)
    return lambda x: jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _router_logits(h: jax.Array, router: jax.Array, rounded) -> jax.Array:
    f32 = jnp.float32
    return rounded(jnp.dot(
        rounded(h.astype(f32)), rounded(router.astype(f32)),
        precision=jax.lax.Precision.HIGHEST,
    ))


def sigmoid_topk_routing(
    h: jax.Array,          # (T, H)
    router: jax.Array,     # (H, E) float32
    bias: jax.Array,       # (E,) float32: e_score_correction_bias
    k: int,
    scale: float,
    dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """``(experts (T, k) int32, weights (T, k) float32)``: sigmoid scores
    over all experts in float32, the top ``k`` of score + bias chosen, the
    chosen scores (without the bias) normalised to sum 1 and scaled.
    ``dtype`` below float32 (a control of the reference check, never
    served) rounds the operands, the logits and the scores to it."""
    rounded = _rounded_to(dtype)
    scores = rounded(jax.nn.sigmoid(_router_logits(h, router, rounded)))
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-20) * scale
    return experts.astype(jnp.int32), weights


def softmax_topk_routing(
    h: jax.Array,          # (T, H)
    router: jax.Array,     # (H, E), the model's type
    k: int,
    dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """``(experts (T, k) int32, weights (T, k) float32)``: float32 logits
    over all experts, the top ``k`` LOGITS chosen, and the softmax taken over
    those ``k`` alone (so they sum to 1 wherever the winners live): no bias,
    no scale. ``dtype`` as in :func:`sigmoid_topk_routing`."""
    rounded = _rounded_to(dtype)
    top, experts = jax.lax.top_k(_router_logits(h, router, rounded), k)
    return experts.astype(jnp.int32), rounded(jax.nn.softmax(top, axis=-1))


def group_limited_softmax_routing(
    h: jax.Array,          # (T, H)
    router: jax.Array,     # (H, E), the model's type
    k: int,
    n_group: int,
    topk_group: int,
    scale: float,
    dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """``(experts (T, k) int32, weights (T, k) float32)``: float32 logits,
    the softmax over ALL experts, the experts in ``n_group`` runs of
    consecutive ids, a group scored by its best expert, the best
    ``topk_group`` groups kept and the rest zeroed, the top ``k`` of what is
    kept, and their own softmax scores times ``scale`` as the weights: not
    renormalised, so they do not sum to 1 (device-limited routing: with a
    group a device, a token's experts lie on ``topk_group`` devices at
    most). Ties go to the lower id, among groups and among experts.
    ``dtype`` as in :func:`sigmoid_topk_routing`."""
    rounded = _rounded_to(dtype)
    scores = rounded(jax.nn.softmax(_router_logits(h, router, rounded), axis=-1))
    T, E = scores.shape
    best = scores.reshape(T, n_group, E // n_group).max(axis=-1)
    _, groups = jax.lax.top_k(best, topk_group)                 # (T, topk_group)
    kept = (groups[..., None] == jnp.arange(n_group)).any(axis=1)   # (T, n_group)
    masked = jnp.where(jnp.repeat(kept, E // n_group, axis=1), scores, 0.0)
    weights, experts = jax.lax.top_k(masked, k)
    return experts.astype(jnp.int32), weights * scale


def relu2(up: jax.Array) -> jax.Array:
    """``relu(x W_up)^2``: the up-projection is ``(.., I)``."""
    return jnp.square(jax.nn.relu(up))


def silu_gated(up: jax.Array) -> jax.Array:
    """``silu(a) * b`` with ``[a | b] = x W_in``: the input projection is
    ``(.., 2 I)``, both halves one matmul."""
    a, b = jnp.split(up, 2, axis=-1)
    return jax.nn.silu(a) * b


#: an expert's activation by its published name: what stands between the
#: input projection ``w_up (held, I or 2 I, H)`` and ``w_down (held, I, H)``
EXPERT_ACTS = {"relu2": relu2, "silu_gated": silu_gated}


def dropless_experts_dense(
    x: jax.Array,          # (T, H)
    experts: jax.Array,    # (T, k) global expert ids
    weights: jax.Array,    # (T, k) float32
    w_up: jax.Array,       # (held, I or 2 I, H): output-major, contracts H
    w_down: jax.Array,     # (held, I, H)
    first: int,
    valid: jax.Array | None = None,   # (T,) rows that count
    act=relu2,
) -> tuple[jax.Array, jax.Array]:
    """Every held expert over every row, combined with the routing weight
    (zero for a pair that was not chosen): exact, and free of any
    dependence on the other rows. Returns ``(out (T, H) float32,
    load (held,) int32)``: the chosen pairs each held expert got."""
    held = w_up.shape[0]
    with jax.named_scope("moe_dispatch"):
        hit = (experts - first)[..., None] == jnp.arange(held)   # (T, k, held)
        if valid is not None:
            hit = hit & valid[:, None, None]
        combine = jnp.sum(jnp.where(hit, weights[..., None], 0.0), axis=1)
        load = hit.sum(axis=(0, 1)).astype(jnp.int32)
    with jax.named_scope("moe_experts"):
        up = jnp.einsum("th,eih->eti", x, w_up)
        down = jnp.einsum("eti,eih->eth", act(up), w_down)
    with jax.named_scope("moe_combine"):
        out = jnp.einsum("eth,te->th", down.astype(jnp.float32), combine)
    return out, load


def _pairs_by_expert(experts, first, held, valid):
    """The dispatch both grouped forms share: ``(here (T, k) bool, order
    (T*k,), counts (held,), sorted_start (held,))``: the pairs whose expert
    is held and whose row counts, their indices sorted by held expert (the
    others last), and each held expert's pairs and the first of them in that
    order."""
    local = experts - first
    here = (local >= 0) & (local < held)
    if valid is not None:
        here = here & valid[:, None]
    group = jnp.where(here, local, held).reshape(-1)          # (T*k,)
    order = jnp.argsort(group)          # stable: pairs by held expert
    counts = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    return here, order, counts, jnp.cumsum(counts) - counts


def dropless_experts_grouped(
    x: jax.Array, experts: jax.Array, weights: jax.Array,
    w_up: jax.Array, w_down: jax.Array, first: int,
    valid: jax.Array | None = None, block_rows: int | None = None,
    layer: jax.Array | None = None, act=relu2, kernel: str = "xla",
) -> tuple[jax.Array, jax.Array]:
    """The chosen pairs sorted by held expert and each run of one expert's
    rows through that expert's matmuls with ``act`` between them, scaled by
    the routing weight and summed a token: the work follows the pairs routed
    here and not the worst case. Same results as
    :func:`dropless_experts_dense`.

    ``kernel`` is the programs' one selection. ``"pallas"`` (``"pallas-
    interpret"`` on the CPU): the rows are permuted ONCE into the sorted
    order, one kernel walks the runs (``ops/grouped_experts.py``; ``block_rows``
    the rows of one of its products, its ``SUB_ROWS`` where None), and the
    results are permuted back once, a gather of each token's ``k``
    (:func:`_grouped_by_kernel`). ``"xla"``: a loop of one block of
    ``block_rows`` (default :data:`GROUP_BLOCK_ROWS`) a step, each expert's
    run padded to whole blocks: gather its rows, the matmuls, scatter-add;
    the CPU's form, the tests' second opinion, and what a chip that holds a
    share of the experts runs (:func:`grouped_form`).

    With ``layer``, ``w_up`` and ``w_down`` are the stacks ``(layers, held,
    I, H)`` of a model that scans its layers, and a step reads
    ``w_up[layer, e]`` from them: the scan's own slice of the layer would be
    a copy of all its held experts a layer (the loop inside cannot read
    through it), whoever is chosen."""
    if kernel != "xla":
        if kernel not in ("pallas", "pallas-interpret"):
            raise ValueError(f"dropless_experts_grouped: unknown kernel {kernel!r}")
        return _grouped_by_kernel(
            x, experts, weights, w_up, w_down, first, valid, block_rows,
            layer, act, interpret=(kernel == "pallas-interpret"))
    T, k = experts.shape
    held = w_up.shape[-3]
    at = (lambda w, e: w[e]) if layer is None else (lambda w, e: w[layer, e])
    R = block_rows or GROUP_BLOCK_ROWS
    with jax.named_scope("moe_dispatch"):
        _, order, counts, sorted_start = _pairs_by_expert(
            experts, first, held, valid)
        blocks_of = -(-counts // R)
        block_end = jnp.cumsum(blocks_of)
        num_blocks = block_end[-1]
        pair_weight = weights.reshape(-1)

    def step(b, out):
        with jax.named_scope("moe_dispatch"):
            e = jnp.searchsorted(block_end, b, side="right").astype(jnp.int32)
            within = (b - (block_end[e] - blocks_of[e])) * R + jnp.arange(R)
            live = within < counts[e]
            pair = order[jnp.clip(sorted_start[e] + within, 0, T * k - 1)]
            row = pair // k
            xb = x[row]
        with jax.named_scope("moe_experts"):
            up = jnp.einsum("th,ih->ti", xb, at(w_up, e))
            down = jnp.dot(act(up), at(w_down, e))
        with jax.named_scope("moe_combine"):
            scale = jnp.where(live, pair_weight[pair], 0.0)
            return out.at[jnp.where(live, row, T)].add(
                down.astype(jnp.float32) * scale[:, None], mode="drop"
            )

    out = jax.lax.fori_loop(
        0, num_blocks, step, jnp.zeros((T, x.shape[1]), jnp.float32)
    )
    return out, counts


#: bytes of the sorted rows one call of the kernel is handed (and returns):
#: a prefill of more is cut into equal chunks of its tokens, each a whole
#: pass of its own. 4,096 rows of Mellum's layer are 151 MB: one chunk
#: streams the layer's 793 MB of experts once, two of 2,048 rows twice (the
#: kernel 3.2 ms against 2 x 1.95, tools/routed_pass.py, PR 47)
GROUP_PIECE_BYTES = 160 * 1024 * 1024
#: bytes of the results one gather of the combine takes (a part of a chunk's
#: tokens, ``k`` rows each; the gather is an array of its own, in the
#: model's type and in float32, and costs the same 46 ns a row whatever its
#: size)
GROUP_PART_BYTES = 32 * 1024 * 1024


def _parts(n: int, fits) -> int:
    """The fewest equal parts of ``n`` for which ``fits(n // parts)``."""
    return next(p for p in range(1, n + 1) if n % p == 0 and fits(n // p))


def _loop(turns: int, body, carry):
    """``fori_loop`` from 0 (rolled: one turn's buffers at a time); a loop of
    ONE turn is no loop."""
    return body(0, carry) if turns == 1 else jax.lax.fori_loop(
        0, turns, body, carry)


def _grouped_by_kernel(x, experts, weights, w_up, w_down, first, valid,
                       block_rows, layer, act, interpret):
    """:func:`dropless_experts_grouped` with the runs in one kernel: see
    there. Two static loops around ONE float32 result, each of one turn
    wherever its bound allows, so that nothing beside the result is larger
    than :data:`GROUP_PIECE_BYTES`:

    - over equal chunks of the tokens, a chunk's ``k`` sorted rows a token
      within :data:`GROUP_PIECE_BYTES`: a chunk is a whole pass of its own
      (the dispatch, ONE gather of its rows into the sorted order, the kernel
      over its runs, the combine) and streams the experts its rows chose once
      more;
    - inside a chunk the combine, in float32, over parts of its tokens
      (:data:`GROUP_PART_BYTES` each), each token taking its ``k`` results
      times their routing weights: one gather a part, 46-70 ns a row against
      a scatter-add's 110-480 (tools/routed_pass.py, PR 47).

    Every pair has a sorted row, held here or not (the pairs of no held
    expert lie last, in no run): right at any share, sized for a chip that
    holds every expert, which is where :func:`grouped_form` serves it."""
    from langstream_tpu.ops import grouped_experts as ge

    T, k = experts.shape
    H = x.shape[1]
    held, inter = w_down.shape[-3], w_down.shape[-2]
    name = next(n for n, fn in EXPERT_ACTS.items() if fn is act)
    if layer is None:
        w_up, w_down, layer = w_up[None], w_down[None], 0
    if valid is None:
        valid = jnp.ones((T,), bool)
    tiles = ge.plan(H, inter, ge.GATED[name], x.dtype.itemsize)
    if block_rows is not None:
        tiles["tile_rows"] = tiles["tile_rows"] // tiles["sub_rows"] * block_rows
        tiles["sub_rows"] = block_rows
    R = tiles["tile_rows"]
    row_bytes = H * x.dtype.itemsize

    def fits(limit):    # tokens whose k rows each lie within limit (one does)
        return lambda rows: rows * k * row_bytes <= max(limit, k * row_bytes)

    chunks = _parts(T, fits(GROUP_PIECE_BYTES))
    Tc = T // chunks
    sorted_rows = -(-Tc * k // R) * R      # a chunk's, in whole tiles
    parts = _parts(Tc, fits(GROUP_PART_BYTES))
    Tp = Tc // parts
    rows_of = lambda a, at, n: jax.lax.dynamic_slice_in_dim(a, at, n)  # noqa: E731

    def one_chunk(c, carry):
        out, load = carry
        with jax.named_scope("moe_dispatch"):
            wc = rows_of(weights, c * Tc, Tc)
            here, order, counts, sorted_start = _pairs_by_expert(
                rows_of(experts, c * Tc, Tc), first, held,
                rows_of(valid, c * Tc, Tc))
            # where each pair lies in the sorted order, and the token of
            # each sorted row
            place = jnp.argsort(order).astype(jnp.int32).reshape(Tc, k)
            token = (order // k)[jnp.clip(jnp.arange(sorted_rows), 0, Tc * k - 1)]
            xs = rows_of(x, c * Tc, Tc)[token]
        with jax.named_scope("moe_experts"):
            ys = ge.grouped_experts(
                xs, w_up, w_down, layer, sorted_start, counts, act=name,
                interpret=interpret, **tiles)

        def tokens_take(i, out):
            with jax.named_scope("moe_combine"):
                # a row of no run holds whatever was there: selected away,
                # never multiplied by zero
                mixed = jnp.sum(jnp.where(
                    rows_of(here, i * Tp, Tp)[..., None],
                    ys[rows_of(place, i * Tp, Tp)].astype(jnp.float32)
                    * rows_of(wc, i * Tp, Tp)[..., None], 0.0), axis=1)
                return jax.lax.dynamic_update_slice_in_dim(
                    out, mixed, c * Tc + i * Tp, 0)

        return _loop(parts, tokens_take, out), load + counts

    return _loop(chunks, one_chunk, (
        jnp.zeros((T, H), jnp.float32), jnp.zeros((held,), jnp.int32)))


def grouped_form(kernel: str, held: int, of: int | None) -> tuple[str, int]:
    """``(the grouped pass's form, the rows up to which the dense pass is
    taken)`` for a pass handed ``kernel`` and ``held`` of ``of`` experts
    (None: all). The kernel's pass where the selection is a kernel AND every
    expert is held, from :data:`DENSE_ROWS_MAX` rows on; else the loop from
    :data:`LOOP_DENSE_ROWS_MAX` on, as before PR 47. A share keeps the loop
    because the kernel's pass sorts and gathers ALL ``T x k`` pairs, which
    at an eighth of the experts costs twice the kernel, and the form that
    sorted the held pairs alone (level with the loop in the probes, not
    ahead) added their results with a loop of 256-row scatter-adds whose
    program hangs the chip: ``nemotron_h``'s prefill of 8 x 512 rows compiles
    in 13 s and never returns from its RUN with that loop inside the scan
    over blocks, returns with the gather by token in its place, and returns
    with the loop where the program is compiled without the compiler's
    assignment of buffers to VMEM (PERF.md section 6, PR 47; the same
    assignment as ``models/hybrid.py`` ``_prefill_compiler_options``)."""
    if kernel != "xla" and (of is None or of == held):
        return kernel, DENSE_ROWS_MAX
    return "xla", LOOP_DENSE_ROWS_MAX


def dropless_experts(x, experts, weights, w_up, w_down, first, valid=None,
                     layer=None, act=relu2, kernel="xla", of=None):
    """Dropless routed experts: the dense pass for a decode batch, the
    grouped pass for a prefill's rows (``kernel`` and ``of``: which form of
    it, and from how many rows: :func:`grouped_form`). ``w_up`` and
    ``w_down`` are one layer's ``(held, I or 2 I, H)`` and ``(held, I, H)``,
    or with ``layer`` the stacks of every layer's."""
    kernel, dense_rows_max = grouped_form(kernel, w_up.shape[-3], of)
    if x.shape[0] > dense_rows_max:
        return dropless_experts_grouped(
            x, experts, weights, w_up, w_down, first, valid, layer=layer,
            act=act, kernel=kernel)
    if layer is not None:
        w_up, w_down = w_up[layer], w_down[layer]
    return dropless_experts_dense(
        x, experts, weights, w_up, w_down, first, valid, act=act)
