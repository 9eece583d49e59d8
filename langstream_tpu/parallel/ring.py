"""Ring attention + Ulysses all-to-all sequence/context parallelism.

Long-context support the TPU-first way: the sequence axis is sharded over a
mesh axis (``sp``) so each device holds ``S/n`` tokens, and attention runs as
a collective over ICI:

- **Ring attention** (:func:`ring_attention`): K/V shards rotate around the
  ``sp`` ring via ``jax.lax.ppermute`` while each device keeps its Q shard;
  softmax is accumulated online (running max / running sum, flash-attention
  style) so the full ``S x S`` score matrix never materialises. Per step the
  device overlaps one block of compute with one neighbour-to-neighbour ICI
  transfer — the canonical TPU ring schedule.
- **Ulysses** (:func:`ulysses_attention`): two ``all_to_all``s re-shard
  sequence→heads, run dense local attention, and re-shard back. Cheaper
  collectives for moderate context when heads ≥ ring size.

Both support GQA (separate Q-head and KV-head counts) and causal masking
with *global* positions (each device knows its block offset from
``lax.axis_index``).

Parity note: the reference has **no** long-context subsystem (SURVEY.md
§5.7 — context limits were the SaaS models'); this module fills that
capability gap as a first-class component rather than porting anything.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _axis_or_none(mesh: Mesh, name: str | None) -> str | None:
    if name is None or mesh is None:
        return None
    return name if name in mesh.axis_names else None


# ---------------------------------------------------------------------------
# ring attention
# ---------------------------------------------------------------------------


def _ring_attention_local(
    q: jax.Array,  # (B, Sq, H, D) local Q shard
    k: jax.Array,  # (B, Sk, Kh, D) local K shard (rotates)
    v: jax.Array,  # (B, Sk, Kh, D)
    *,
    axis_name: str,
    causal: bool,
    scale: float,
) -> jax.Array:
    """Per-device body run under ``shard_map``: online-softmax attention over
    all K/V blocks as they rotate around the ``axis_name`` ring."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, Sq, H, D = q.shape
    Sk, Kh = k.shape[1], k.shape[2]
    G = H // Kh
    qg = (q.astype(jnp.float32) * scale).reshape(B, Sq, Kh, G, D)
    q_pos = idx * Sq + jnp.arange(Sq)
    neg = jnp.float32(jnp.finfo(jnp.float32).min)

    # accumulators in (B, Kh, G, Sq, ...) layout
    m0 = jnp.full((B, Kh, G, Sq), neg, dtype=jnp.float32)
    l0 = jnp.zeros((B, Kh, G, Sq), dtype=jnp.float32)
    o0 = jnp.zeros((B, Kh, G, Sq, D), dtype=jnp.float32)

    def accumulate(o, l, m, k_blk, v_blk, s):
        j = (idx - s) % n  # global block index currently held
        scores = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, k_blk.astype(jnp.float32)
        )  # (B, Kh, G, Sq, Sk)
        if causal:
            k_pos = j * Sk + jnp.arange(Sk)
            mask = q_pos[:, None] >= k_pos[None, :]  # (Sq, Sk)
            scores = jnp.where(mask[None, None, None], scores, neg)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        # rows with no valid key yet keep m=neg; exp(neg-neg) would NaN, so
        # guard the shift. (The s=0 diagonal block always validates each row
        # in the causal case, so by the end m_new is finite everywhere.)
        shift = jnp.where(m_new <= neg, 0.0, m_new)
        p = jnp.exp(scores - shift[..., None])
        if causal:
            p = jnp.where(mask[None, None, None], p, 0.0)
        alpha = jnp.exp(jnp.where(m <= neg, neg, m - shift))
        l = l * alpha + p.sum(axis=-1)
        o = o * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, v_blk.astype(jnp.float32)
        )
        return o, l, m_new

    def maybe_accumulate(o, l, m, k_blk, v_blk, s):
        if not causal:
            return accumulate(o, l, m, k_blk, v_blk, s)
        # skip blocks entirely in the future (fully masked): without this,
        # causal ring attention burns ~2x the needed FLOPs — the masked
        # einsum/exp/matmul would still execute and then be zeroed
        j = (idx - s) % n
        needed = j * Sk <= idx * Sq + Sq - 1
        return lax.cond(
            needed,
            lambda args: accumulate(*args, s),
            lambda args: args[:3],
            (o, l, m, k_blk, v_blk),
        )

    def step(carry, s):
        o, l, m, k_blk, v_blk = carry
        o, l, m = maybe_accumulate(o, l, m, k_blk, v_blk, s)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (o, l, m, k_blk, v_blk), None

    # n-1 rotated steps, then the final block without the (wasted) rotation
    (o, l, m, k, v), _ = lax.scan(step, (o0, l0, m0, k, v), jnp.arange(n - 1))
    o, l, _ = maybe_accumulate(o, l, m, k, v, n - 1)
    out = o / jnp.maximum(l, 1e-30)[..., None]  # (B, Kh, G, Sq, D)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D)
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,  # (B, S, H, D) global
    k: jax.Array,  # (B, S, Kh, D)
    v: jax.Array,  # (B, S, Kh, D)
    mesh: Mesh,
    *,
    causal: bool = True,
    seq_axis: str = "sp",
    head_axis: str | None = "tp",
    batch_axis: str | None = "dp",
    scale: float | None = None,
) -> jax.Array:
    """Sequence-parallel attention: seq sharded over ``seq_axis``, heads over
    ``head_axis`` (if present in the mesh), batch over ``batch_axis``.

    Composable with tensor parallelism: with ``head_axis="tp"`` each device
    ring-attends over its own head shard (requires ``Kh % tp == 0``).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    ba = _axis_or_none(mesh, batch_axis)
    ha = _axis_or_none(mesh, head_axis)
    sa = _axis_or_none(mesh, seq_axis)
    if sa is None:
        raise ValueError(f"mesh {mesh.axis_names} has no sequence axis {seq_axis!r}")
    spec = P(ba, sa, ha, None)
    fn = jax.shard_map(
        partial(_ring_attention_local, axis_name=sa, causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Ulysses (all-to-all head/sequence re-sharding)
# ---------------------------------------------------------------------------


def dense_attention(q, k, v, *, causal: bool, scale: float, q_offset=0):
    """Dense GQA attention. q: (B, Sq, H, D); k/v: (B, Sk, Kh, D).

    Matmuls run in the input dtype (bf16 on the model path — full MXU rate)
    with f32 accumulation via ``preferred_element_type``; only the softmax
    itself is f32.
    """
    B, Sq, H, D = q.shape
    Sk, Kh = k.shape[1], k.shape[2]
    G = H // Kh
    qg = q.reshape(B, Sq, Kh, G, D)
    scores = (
        jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32
        )
        * scale
    )
    if causal:
        mask = (q_offset + jnp.arange(Sq))[:, None] >= jnp.arange(Sk)[None, :]
        scores = jnp.where(
            mask[None, None, None], scores, jnp.finfo(jnp.float32).min
        )
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgqs,bskd->bkgqd",
        probs.astype(q.dtype),
        v,
        preferred_element_type=jnp.float32,
    )
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D).astype(q.dtype)


# Backwards-compatible private alias (pre-public-API name).
_dense_attention = dense_attention


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool, scale: float):
    """Under shard_map: re-shard seq→heads, dense-attend, re-shard back."""
    n = lax.psum(1, axis_name)
    Kh = k.shape[2]
    if Kh < n:
        # fewer KV heads than ring size: expand GQA groups so the head
        # all-to-all divides evenly (costs replicated K/V bandwidth, like
        # every Ulysses implementation with GQA)
        reps = n // Kh
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    # (B, S/n, H, D) -> (B, S, H/n, D)
    q = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    k = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    v = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    out = dense_attention(q, k, v, causal=causal, scale=scale)
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    seq_axis: str = "sp",
    batch_axis: str | None = "dp",
    scale: float | None = None,
) -> jax.Array:
    """All-to-all sequence parallelism (Ulysses): seq-sharded in/out, dense
    attention over head-sharded tensors in the middle. Requires
    ``H % sp == 0``; KV heads are group-expanded when ``Kh < sp``."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    ba = _axis_or_none(mesh, batch_axis)
    sa = _axis_or_none(mesh, seq_axis)
    if sa is None:
        raise ValueError(f"mesh {mesh.axis_names} has no sequence axis {seq_axis!r}")
    spec = P(ba, sa, None, None)
    fn = jax.shard_map(
        partial(_ulysses_local, axis_name=sa, causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
