"""Prefill attention of an EVA layer as one Pallas TPU kernel: a query reads
the exact keys of its own block-aligned window, causally, and one summary
row for every chunk of every window before its own, under ONE softmax.

``models/eva.py`` says what the rows are. Here a prompt of ``P`` rows is
``P / window`` windows (one, of ``P`` rows, where the prompt is shorter than
a window); query block ``qb`` lies in window ``w = qb * block_q // window``
and its K sweep has two phases over one running max, sum and accumulator
(:mod:`langstream_tpu.ops.flash_attention`'s):

- the summary rows ``[0, per_window * w)``, whole blocks unmasked and the
  block that holds the edge masked; window 0 has none and skips the phase;
- the window's own rows, as the ragged causal kernel walks them: blocks
  under the diagonal whole, the block the diagonal crosses masked, nothing
  past the row's true length.

A block that is not computed is not fetched either (its index is clamped to
one that is). A padded query's output is zeros.

The rows come and go as the projections leave them, ``(B, P, H * D)``: a
head is a block of ``D`` lanes of its row, so nothing is transposed around
the kernel (four copies of 0.27 GB a layer at 32,768 rows of 32 heads).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from langstream_tpu.ops.flash_attention import (
    _online_update,
    _reset,
    _write_out,
)


def _eva_flash_kernel(
    lengths_ref,   # SMEM (B,) int32: true rows of each right-padded prompt
    q_ref,         # (1, 1, block_q, D): a head's lanes of block_q rows
    k_ref,         # (1, 1, block_k, D): the window's own rows
    v_ref,
    ks_ref,        # (1, 1, block_s, D): summary rows
    vs_ref,
    o_ref,         # (1, 1, block_q, D)
    m_ref, l_ref, acc_ref,
    *,
    scale: float,
    block_q: int,
    block_k: int,
    block_s: int,
    summary_blocks: int,   # K steps of the first phase
    window: int,           # rows of a window (the prompt's, if shorter)
    per_window: int,       # summary rows a closed window has
):
    b, qb, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    num_k = pl.num_programs(3)
    length = lengths_ref[b]
    q_start = qb * block_q
    seen = (q_start // window) * per_window   # summary rows this block sees

    pl.when(ki == 0)(lambda: _reset(m_ref, l_ref, acc_ref))

    def scores(ref):
        return jax.lax.dot_general(
            q_ref[0, 0], ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    # -- the summaries of the windows before this one ----------------------
    s_start = ki * block_s
    in_summaries = jnp.logical_and(ki < summary_blocks, q_start < length)
    whole = s_start + block_s <= seen

    @pl.when(jnp.logical_and(in_summaries, whole))
    def _summaries_whole():
        _online_update(scores(ks_ref), None, vs_ref[0, 0], m_ref, l_ref, acc_ref)

    @pl.when(jnp.logical_and(
        in_summaries, jnp.logical_and(s_start < seen, ~whole)))
    def _summaries_edge():
        cols = s_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_s), 1)
        _online_update(
            scores(ks_ref), cols < seen, vs_ref[0, 0], m_ref, l_ref, acc_ref)

    # -- the window's own rows, causal --------------------------------------
    k_start = (q_start // window) * window + (ki - summary_blocks) * block_k
    own = jnp.logical_and(
        ki >= summary_blocks,
        jnp.logical_and(q_start < length, k_start < length))
    below = k_start + block_k - 1 <= q_start
    reached = k_start <= q_start + block_q - 1

    @pl.when(jnp.logical_and(own, below))
    def _whole():
        _online_update(scores(k_ref), None, v_ref[0, 0], m_ref, l_ref, acc_ref)

    @pl.when(jnp.logical_and(own, jnp.logical_and(reached, ~below)))
    def _diagonal():
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        _online_update(
            scores(k_ref), rows >= cols, v_ref[0, 0], m_ref, l_ref, acc_ref)

    pl.when(ki == num_k - 1)(lambda: _write_out(o_ref, l_ref, acc_ref))


def eva_flash(
    q: jax.Array,        # (B, P, H, D)
    k: jax.Array,        # (B, P, H, D): one key head a query head
    v: jax.Array,
    k_sum: jax.Array,    # (B, P // chunk, H, D): a summary row a chunk
    v_sum: jax.Array,
    lengths: jax.Array,  # (B,) true rows
    *,
    window: int,         # rows of a window; P is one window or whole windows
    per_window: int,     # summary rows a closed window has (window // chunk)
    scale: float | None = None,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """``(B, P, H, D)``: row ``i`` of window ``w = i // window`` attends the
    keys ``[w * window, i]`` and the summary rows ``[0, per_window * w)``
    under one softmax."""
    B, P, H, D = q.shape
    window = min(window, P)
    if P % window:
        raise ValueError(f"a prompt of {P} rows is not whole windows of {window}")
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    block_q = min(block_q, window)
    block_k = min(block_k, window)
    if window % block_q or window % block_k:
        raise ValueError(
            f"blocks of {block_q} / {block_k} rows do not tile a window of "
            f"{window}")
    S = k_sum.shape[1]
    block_s = min(block_k, max(16, S))
    pad = -S % block_s
    # (B, 1, rows, H * D): the blocks below are (rows, one head's D lanes)
    lanes = lambda a: a.reshape(B, 1, a.shape[1], H * D)  # noqa: E731
    q, k, v, k_sum, v_sum = map(lanes, (q, k, v, k_sum, v_sum))
    if pad:
        k_sum, v_sum = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                        for a in (k_sum, v_sum))
    # no window of a prompt sees the last window's summaries
    summary_blocks = pl.cdiv(max(P // window - 1, 0) * per_window, block_s)
    own_blocks = window // block_k

    def last_q(b, lengths):
        return jnp.maximum(pl.cdiv(lengths[b], block_q) - 1, 0)

    def q_index(b, h, qb, ki, lengths):
        return (b, 0, jnp.minimum(qb, last_q(b, lengths)), h)

    def summary_index(b, h, qb, ki, lengths):
        q_start = jnp.minimum(qb, last_q(b, lengths)) * block_q
        last = jnp.maximum(
            pl.cdiv((q_start // window) * per_window, block_s) - 1, 0)
        return (b, 0, jnp.minimum(ki, last), h)

    def own_index(b, h, qb, ki, lengths):
        q_start = jnp.minimum(qb, last_q(b, lengths)) * block_q
        first = (q_start // window) * own_blocks
        # the diagonal's block, or the last block with a true row
        last = jnp.minimum(
            q_start + block_q - 1, jnp.maximum(lengths[b] - 1, 0)) // block_k
        return (b, 0, jnp.clip(first + ki - summary_blocks, first, last), h)

    kernel = functools.partial(
        _eva_flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        block_s=block_s, summary_blocks=summary_blocks, window=window,
        per_window=per_window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, P // block_q, summary_blocks + own_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_index),
            pl.BlockSpec((1, 1, block_k, D), own_index),
            pl.BlockSpec((1, 1, block_k, D), own_index),
            pl.BlockSpec((1, 1, block_s, D), summary_index),
            pl.BlockSpec((1, 1, block_s, D), summary_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D), lambda b, h, qb, ki, lengths: (b, 0, qb, h)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, P, H * D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="eva_flash",
    )(lengths.astype(jnp.int32), q, k, v, k_sum, v_sum)
    return out.reshape(B, P, H, D)
