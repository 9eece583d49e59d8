"""Blocked (flash) causal GQA attention as a Pallas TPU kernel.

Why a kernel: the einsum attention path materialises the full
``(B, heads, S, S)`` float32 score matrix in HBM — at S=4k, B=8, 32 heads
that is >16 GB of traffic per layer. This kernel streams K/V blocks through
VMEM with an online-softmax accumulator, so HBM traffic is O(S·D) and the
MXU sees back-to-back 128×128 tiles.

Scope: inference prefill / forward (no custom VJP — the training paths keep
the differentiable einsum attention). Causal masking only: for right-padded
self-attention batches, causality alone already hides the padded keys from
every real query row, so no per-row length input is needed (the engine
discards logits of padded rows).

Grid: ``(B, heads, num_q_blocks, num_k_blocks)`` with the K dimension
innermost; the running max / sum / accumulator live in VMEM scratch across
the K sweep and the output block is written on the last K step. Fully-masked
K blocks (``k_start > q_end``) are skipped via ``pl.when``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)


def _reset(m_ref, l_ref, acc_ref):
    """A query block's running max, sum and accumulator before its first
    K block."""
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)


def _write_out(o_ref, l_ref, acc_ref):
    """The block's output after its last K block: zeros for a row that saw
    nothing."""
    l = l_ref[:, 0]
    inv = jnp.where(l > 0.0, 1.0 / jnp.maximum(l, 1e-30), 0.0)
    o_ref[0, 0] = (acc_ref[:] * inv[:, None]).astype(o_ref.dtype)


def _online_update(s, mask, v, m_ref, l_ref, acc_ref):
    """One K block of the running softmax: scores ``s (block_q, block_k)``
    float32, ``mask`` of what a row may see (None: all of it), the block's
    values ``v``; the running max, sum and accumulator in their refs."""
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[:, 0]                       # (block_q,)
    l_prev = l_ref[:, 0]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    shift = jnp.where(m_new <= NEG_INF, 0.0, m_new)  # NaN guard
    p = jnp.exp(s - shift[:, None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(jnp.where(m_prev <= NEG_INF, NEG_INF, m_prev - shift))
    l_new = l_prev * alpha + jnp.sum(p, axis=1)
    acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new[:, None], l_ref.shape)


def _flash_kernel(
    q_ref,      # (1, 1, block_q, D)
    k_ref,      # (1, 1, block_k, D)
    v_ref,      # (1, 1, block_k, Dv): a value may be narrower than a key
    o_ref,      # (1, 1, block_q, Dv)
    m_ref,      # VMEM (block_q, 128) f32 — running max (broadcast cols)
    l_ref,      # VMEM (block_q, 128) f32 — running sum
    acc_ref,    # VMEM (block_q, Dv) f32
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    kv_len: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    num_k = pl.num_programs(3)

    pl.when(ki == 0)(lambda: _reset(m_ref, l_ref, acc_ref))

    q_start = qi * block_q
    k_start = ki * block_k
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1  # block not fully in the future

    @pl.when(run)
    def _accumulate():
        q = q_ref[0, 0]  # (block_q, D)
        k = k_ref[0, 0]  # (block_k, D)
        v = v_ref[0, 0]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # (block_q, block_k)
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        # kv_len bound hides right-padding from non-causal queries; the
        # causal mask subsumes it for self-attention but is cheap to keep
        mask = cols < kv_len
        if causal:
            mask = mask & (rows >= cols)
        _online_update(s, mask, v, m_ref, l_ref, acc_ref)

    pl.when(ki == num_k - 1)(lambda: _write_out(o_ref, l_ref, acc_ref))


def _flash_bhsd(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, Kh, Sk, D)
    v: jax.Array,  # (B, Kh, Sk, Dv)
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    kv_len: int,
    interpret: bool,
) -> jax.Array:
    B, H, Sq, D = q.shape
    Kh, Sk, Dv = k.shape[1], k.shape[2], v.shape[3]
    group = H // Kh
    grid = (B, H, pl.cdiv(Sq, block_q), pl.cdiv(Sk, block_k))
    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        kv_len=kv_len,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, D),
                lambda b, h, qi, ki: (b, h, qi, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, block_k, D),
                lambda b, h, qi, ki, g=group: (b, h // g, ki, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, block_k, Dv),
                lambda b, h, qi, ki, g=group: (b, h // g, ki, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, Dv),
            lambda b, h, qi, ki: (b, h, qi, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_prefill",
    )(q, k, v)


def _flash_ragged_kernel(
    lengths_ref,  # SMEM (B,) int32: true rows of each right-padded row
    q_ref,        # (1, 1, block_q, D)
    k_ref,        # (1, 1, block_k, D)
    v_ref,        # (1, 1, block_k, Dv)
    o_ref,        # (1, 1, block_q, Dv)
    m_ref, l_ref, acc_ref,
    *,
    scale: float,
    block_q: int,
    block_k: int,
    window: int | None = None,
):
    """Causal self-attention of right-padded rows whose true lengths are
    known: the blocks past a row's length, of queries and of keys, are not
    computed (their queries' output is zeros), and only a block the diagonal
    crosses pays for the mask. With ``window`` a query ``i`` sees the keys
    ``j`` with ``0 <= i - j < window``: a key block wholly behind the window
    of the query block's first row is skipped like one in the future, and a
    block the window's edge crosses is masked like one the diagonal crosses."""
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    num_k = pl.num_programs(3)
    length = lengths_ref[b]

    pl.when(ki == 0)(lambda: _reset(m_ref, l_ref, acc_ref))

    q_start = qi * block_q
    k_start = ki * block_k
    live = jnp.logical_and(q_start < length, k_start < length)
    below = k_start + block_k - 1 <= q_start   # every column under every row
    reached = k_start <= q_start + block_q - 1
    if window is not None:
        # some key of the block within the first (furthest-seeing) row's
        # window; every key within the last row's
        reached = jnp.logical_and(
            reached, k_start + block_k - 1 > q_start - window)
        below = jnp.logical_and(
            below, q_start + block_q - 1 - k_start < window)

    def scores():
        return jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(jnp.logical_and(live, below))
    def _whole():
        _online_update(scores(), None, v_ref[0, 0], m_ref, l_ref, acc_ref)

    @pl.when(jnp.logical_and(live, jnp.logical_and(reached, ~below)))
    def _diagonal():
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = scores()
        mask = rows >= cols
        if window is not None:
            mask = mask & (rows - cols < window)
        _online_update(s, mask, v_ref[0, 0], m_ref, l_ref, acc_ref)

    pl.when(ki == num_k - 1)(lambda: _write_out(o_ref, l_ref, acc_ref))


def _flash_bhsd_ragged(
    q: jax.Array,        # (B, H, S, D)
    k: jax.Array,        # (B, Kh, S, D)
    v: jax.Array,        # (B, Kh, S, Dv)
    lengths: jax.Array,  # (B,) int32
    *,
    scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
    window: int | None = None,
) -> jax.Array:
    B, H, S, D = q.shape
    Kh, Dv = k.shape[1], v.shape[3]
    group = H // Kh
    kernel = functools.partial(
        _flash_ragged_kernel, scale=scale, block_q=block_q, block_k=block_k,
        **({} if window is None else {"window": window}))

    def last_q(b, lengths):
        return jnp.maximum(pl.cdiv(lengths[b], block_q) - 1, 0)

    def q_index(b, h, qi, ki, lengths):
        # a block past the row's length asks for the last live one again:
        # an index that does not change is not fetched again
        return (b, h, jnp.minimum(qi, last_q(b, lengths)), 0)

    def kv_index(b, h, qi, ki, lengths):
        q_row = jnp.minimum(qi, last_q(b, lengths)) * block_q + block_q - 1
        last = jnp.minimum(q_row, jnp.maximum(lengths[b] - 1, 0)) // block_k
        if window is not None:
            # nor is a block behind the window of the block's first row
            first = jnp.maximum(q_row - block_q + 2 - window, 0) // block_k
            return (b, h // group, jnp.clip(ki, first, last), 0)
        return (b, h // group, jnp.minimum(ki, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, pl.cdiv(S, block_q), pl.cdiv(S, block_k)),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_index),
            pl.BlockSpec((1, 1, block_k, D), kv_index),
            pl.BlockSpec((1, 1, block_k, Dv), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, Dv), lambda b, h, qi, ki, lengths: (b, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_prefill",
    )(lengths.astype(jnp.int32), q, k, v)


def flash_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, Kh, D)
    v: jax.Array,  # (B, Sk, Kh, Dv): Dv may differ from D (latent attention)
    *,
    causal: bool = True,
    scale: float | None = None,
    # 512-blocks measured ~2.2x faster than XLA dense attention at S=8k on
    # v5e (and never slower down to S=1k); both clamp to the sequence length
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
    mesh=None,  # jax.sharding.Mesh: run the kernel per-shard via shard_map
    lengths: jax.Array | None = None,  # (B,) true rows of right-padded rows
    window: int | None = None,  # query i sees keys j with 0 <= i - j < window
) -> jax.Array:
    """Flash attention over ``(batch, seq, heads, head_dim)`` tensors.

    GQA: ``H`` may be a multiple of ``Kh``. Sequences are padded up to the
    block size internally (causal masking keeps padded keys invisible to
    real queries in the self-attention case ``Sq == Sk``).

    Under a ``mesh``, ``pallas_call`` has no SPMD partitioning rule, so the
    call is wrapped in ``shard_map`` with heads on the ``tp`` axis — each
    device runs the kernel on its own head shard (attention is
    embarrassingly parallel over heads; GQA group structure is preserved
    because Q heads and KV heads shard by the same factor).

    With ``lengths`` (causal self-attention of right-padded rows, one
    device): the kernel learns each row's true length and neither fetches
    nor computes the blocks past it, and masks only the blocks the diagonal
    crosses; a padded query's output is zeros where it lies in a block of
    padding alone, and as unread as ever otherwise. Without it the call is
    what it was. ``window`` (with ``lengths``) narrows the causal mask to the
    last ``window`` keys of each query and skips the key blocks wholly behind
    it, which are neither fetched nor computed.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if mesh is not None:
        from functools import partial as _partial

        from jax.sharding import PartitionSpec as P

        axes = mesh.axis_names
        H_, Kh_, B_ = q.shape[2], k.shape[2], q.shape[0]
        tp = (
            "tp"
            if "tp" in axes and mesh.shape["tp"] > 1
            and H_ % mesh.shape["tp"] == 0 and Kh_ % mesh.shape["tp"] == 0
            else None
        )
        dp = (
            "dp"
            if "dp" in axes and mesh.shape["dp"] > 1
            and B_ % mesh.shape["dp"] == 0
            else None
        )
        if tp is not None or dp is not None:
            spec = P(dp, None, tp, None)
            inner = _partial(
                flash_attention,
                causal=causal, scale=scale, block_q=block_q, block_k=block_k,
                interpret=interpret, mesh=None,
            )
            return jax.shard_map(
                inner, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False,
            )(q, k, v)
        # no shardable axis (tiny batch on a dp-only mesh): the plain call
        # below is replicated per device by pjit — correct, just not sharded
    if lengths is not None and (not causal or mesh is not None):
        raise ValueError(
            "flash attention takes lengths for causal self-attention on one "
            "device only")
    if window is not None and lengths is None:
        raise ValueError("flash attention takes a window with lengths only")
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    if causal and Sq != Sk:
        raise ValueError(
            f"causal flash attention expects self-attention (Sq == Sk), got "
            f"{Sq} vs {Sk}"
        )
    block_q = min(block_q, max(16, Sq))
    block_k = min(block_k, max(16, Sk))
    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    qt = jnp.transpose(q, (0, 2, 1, 3))  # (B, H, Sq, D)
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    if lengths is not None:
        out = _flash_bhsd_ragged(
            qt, kt, vt, lengths, scale=scale, block_q=block_q,
            block_k=block_k, interpret=interpret,
            **({} if window is None else {"window": window}))
    else:
        out = _flash_bhsd(
            qt, kt, vt,
            scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, kv_len=Sk, interpret=interpret,
        )
    if pad_q:
        out = out[:, :, :Sq]
    return jnp.transpose(out, (0, 2, 1, 3))
