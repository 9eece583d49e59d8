"""Pipeline parallelism: GPipe microbatch schedule over the ``pp`` mesh axis.

TPU-first design (nothing like this exists in the reference — SURVEY.md §2.2
documents pipeline parallelism *across agents via topics*; this module is the
in-model counterpart over ICI):

- The stacked layer tensors ``(L, ...)`` shard their layer axis over ``pp``:
  each device (stage) owns ``L/pp`` contiguous layers. No weight gathers —
  weights never move, activations do.
- A GPipe schedule runs inside ``jax.shard_map`` *manual over pp only*
  (``axis_names={"pp"}``): at tick ``t`` stage ``s`` processes microbatch
  ``t-s``; activations hop stage→stage with a single ``ppermute`` per tick
  over ICI. dp/tp/ep stay automatic, so Megatron TP and MoE expert
  parallelism compose inside a stage.
- Bubble fraction is the usual ``(pp-1)/(M+pp-1)`` — callers pick the
  microbatch count ``M`` accordingly.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from langstream_tpu.models.llama import (
    LlamaConfig,
    _rms_norm,
    _swiglu,
    attention_block,
)
from langstream_tpu.models.llama import _rope as rope_tables


def gpipe(
    stage_fn: Callable[[jax.Array], tuple[jax.Array, jax.Array]],
    x_microbatches: jax.Array,  # (M, mb, S, H) — replicated over pp
    axis: str = "pp",
) -> tuple[jax.Array, jax.Array]:
    """Run the GPipe schedule; call INSIDE shard_map manual over ``axis``.

    ``stage_fn`` applies this stage's layers to one microbatch and returns
    ``(activations, aux_scalar)`` (aux = e.g. MoE load-balancing loss for
    the stage's layers; 0 when unused). Returns the fully-processed
    microbatches broadcast to every stage, plus the aux total summed over
    stages × microbatches.
    """
    pp = jax.lax.psum(1, axis)
    s = jax.lax.axis_index(axis)
    M = x_microbatches.shape[0]
    T = M + pp - 1  # total ticks (the (pp-1)/(M+pp-1) bubble)

    buf0 = jnp.zeros_like(x_microbatches[0])
    out0 = jnp.zeros_like(x_microbatches)
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    def tick(carry, t):
        buf, out, aux_acc = carry
        # stage 0 feeds microbatch t; later stages consume the previous
        # tick's ppermute delivery (stage s sees microbatch t-s)
        feed = jax.lax.dynamic_index_in_dim(
            x_microbatches, jnp.clip(t, 0, M - 1), 0, keepdims=False
        )
        inp = jnp.where(s == 0, feed, buf)
        y, aux = stage_fn(inp)
        # stage s holds a real microbatch only for ticks with 0 ≤ t-s < M
        valid = (t - s >= 0) & (t - s < M)
        aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
        # the last stage retires microbatch t-(pp-1)
        out_idx = t - (pp - 1)
        retired = jax.lax.dynamic_update_index_in_dim(
            out, y, jnp.clip(out_idx, 0, M - 1), 0
        )
        out = jnp.where((s == pp - 1) & (out_idx >= 0), retired, out)
        buf = jax.lax.ppermute(y, axis, perm)
        return (buf, out, aux_acc), None

    # scan (not fori_loop): the schedule must be reverse-differentiable so a
    # training step can backprop through the pipeline
    # the aux accumulator is rank-1, never a scalar: jax 0.4.x shard_map
    # partial-eval mis-names scalar residuals in the backward pass
    # (_SpecError from _check_names) — a (1,) carry sidesteps it
    (_, out, aux_acc), _ = jax.lax.scan(
        tick, (buf0, out0, jnp.zeros((1,), jnp.float32)), jnp.arange(T)
    )
    # results live on the last stage; psum broadcasts them (other stages
    # contribute zeros) so the head/loss runs identically everywhere.
    # the psum runs in f32: XLA's bf16 all-reduce promotion pass crashes on
    # CPU (and on TPU f32 accumulation is what we'd want anyway)
    dtype = out.dtype
    out = jnp.where(s == pp - 1, out, jnp.zeros_like(out)).astype(jnp.float32)
    out = jax.lax.psum(out, axis).astype(dtype)
    return out, jax.lax.psum(aux_acc, axis)


def pp_layer_specs(layer_specs: dict) -> dict:
    """Prepend ``pp`` on the stacked layer axis of each per-layer spec
    (e.g. ``P(None, None, 'tp')`` → ``P('pp', None, 'tp')``)."""
    return jax.tree.map(
        lambda spec: P("pp", *spec[1:]),
        layer_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _causal_attention(config):
    from langstream_tpu.parallel.ring import dense_attention

    return partial(
        dense_attention, causal=True, scale=1.0 / math.sqrt(config.head_dim)
    )


def _llama_layer(config: LlamaConfig, x: jax.Array, lp: dict, cos, sin):
    x = attention_block(config, x, lp, cos, sin, _causal_attention(config))
    h2 = _rms_norm(x, lp["mlp_norm"], config.norm_eps)
    return x + _swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"])


def llama_forward_pp(
    config: LlamaConfig,
    params: dict,
    tokens: jax.Array,  # (B, S), B divisible by num_microbatches
    mesh: Mesh,
    num_microbatches: int = 4,
) -> jax.Array:
    """Pipeline-parallel all-position logits. Embed/head run outside the
    pipelined region (replicated or tp-sharded by their own specs); the layer
    stack runs as pp stages."""
    c = config
    B, S = tokens.shape
    M = num_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    x = jnp.take(params["embed"], tokens, axis=0)
    # f32 across the shard_map boundary: the replicated input's cotangent is
    # psum'd over pp, and XLA-CPU's bf16 all-reduce promotion pass crashes
    x_mb = x.reshape(M, B // M, S, c.hidden).astype(jnp.float32)

    def stage(local_layers: dict, xm: jax.Array):
        xm = xm.astype(c.dtype)
        b = xm.shape[0]
        positions = jnp.arange(S)[None, :].repeat(b, axis=0)
        cos, sin = rope_tables(positions, c.head_dim, c.rope_theta)

        def body(x, lp):
            return _llama_layer(c, x, lp, cos, sin), None

        out, _ = jax.lax.scan(body, xm, local_layers)
        return out.astype(jnp.float32), jnp.float32(0.0)

    run = jax.shard_map(
        lambda layers, xm: gpipe(partial(stage, layers), xm)[0],
        mesh=mesh,
        in_specs=(
            jax.tree.map(
                lambda _: P("pp"), params["layers"],
            ),
            P(),
        ),
        out_specs=P(),
        axis_names={"pp"},
        check_vma=False,
    )
    x = run(params["layers"], x_mb).reshape(B, S, c.hidden)
    x = _rms_norm(x, params["final_norm"], c.norm_eps)
    return jnp.einsum("bsh,hv->bsv", x, params["lm_head"]).astype(jnp.float32)


def moe_forward_pp(
    config,  # MoEConfig
    params: dict,
    tokens: jax.Array,
    mesh: Mesh,
    num_microbatches: int = 4,
) -> tuple[jax.Array, jax.Array]:
    """Pipeline-parallel MoE forward: pp stages over layers, expert
    parallelism (ep) + TP automatic *inside* each stage. Returns (logits,
    aux load-balancing loss)."""
    from langstream_tpu.models.moe import moe_ffn
    from jax.sharding import NamedSharding

    c = config
    B, S = tokens.shape
    M = num_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    capacity = c.capacity((B // M) * S)
    axes = mesh.axis_names
    # in-stage ep constraints ride the partial-manual shard_map below (pp
    # manual via axis_names, ep/tp automatic)
    ep = "ep" if "ep" in axes else None
    e_spec = NamedSharding(mesh, P(ep, None, None))

    x = jnp.take(params["embed"], tokens, axis=0)
    # f32 boundary (see llama_forward_pp): bf16 pp-psum of the replicated
    # input's cotangent crashes XLA-CPU's promotion pass
    x_mb = x.reshape(M, B // M, S, c.hidden).astype(jnp.float32)

    def stage_fn(local_layers: dict, xm: jax.Array):
        xm = xm.astype(c.dtype)
        b = xm.shape[0]
        positions = jnp.arange(S)[None, :].repeat(b, axis=0)
        cos, sin = rope_tables(positions, c.head_dim, c.rope_theta)

        def body(carry, lp):
            x, aux_acc = carry
            x = attention_block(c, x, lp, cos, sin, _causal_attention(c))
            h2 = _rms_norm(x, lp["mlp_norm"], c.norm_eps)
            ffn, aux = moe_ffn(
                h2, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
                capacity,
                ep_constrain=(
                    (lambda t: jax.lax.with_sharding_constraint(t, e_spec))
                    if ep
                    else None
                ),
            )
            # the aux accumulator is shape (1,), not a scalar: jax 0.4.x
            # shard_map partial-eval mis-names scalar residuals in the
            # backward pass (_SpecError) — a rank-1 carry sidesteps it
            return (x + ffn, aux_acc + aux.reshape(1)), None

        (out, aux_total), _ = jax.lax.scan(
            body, (xm, jnp.zeros((1,), jnp.float32)), local_layers
        )
        return out.astype(jnp.float32), aux_total

    run = jax.shard_map(
        lambda layers, xm: gpipe(partial(stage_fn, layers), xm),
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P("pp"), params["layers"]),
            P(),
        ),
        out_specs=(P(), P()),
        axis_names={"pp"},
        check_vma=False,
    )
    x, aux_total = run(params["layers"], x_mb)
    x = x.reshape(B, S, c.hidden)
    x = _rms_norm(x, params["final_norm"], c.norm_eps)
    logits = jnp.einsum("bsh,hv->bsv", x, params["lm_head"]).astype(jnp.float32)
    return logits, aux_total.reshape(()) / M
