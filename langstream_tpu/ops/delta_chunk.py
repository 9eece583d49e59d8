"""The chunked gated delta rule of a prompt, one kernel a layer (Pallas TPU).

What :func:`langstream_tpu.models.hybrid.delta_chunked` computes, ``S_t = (I
- b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T`` and ``o_t = S_t^T
q_t`` over right-padded prompts in chunks of ``chunk`` tokens, with a chunk's
whole working set on the chip. Written in XLA (``kernel="xla"``: the CPU path
and the tests' reference) the scan's body is about sixty ops a chunk and
every ``(B, heads, chunk, d)`` intermediate is an op's output in HBM; here a
grid step reads its chunk's ``q, k, v, g`` tiles and ``beta``, writes ``o``,
and the state ``(heads tile, dv, dk)`` float32 stays in VMEM across a row's
chunks (it is the resident output block: zeroed at the row's first chunk,
written out once after its last).

A grid step, for the heads of its tile :data:`INTERLEAVE` at a time, every
array with the heads as its leading axis (notation of ``delta_chunked``):

1. ``G``, the chunk's running sum of ``g`` (a product with the lower
   triangle of ones, float32 at the highest precision).
2. Inside a sub-block of :data:`SUB` rows the relative decays pair by pair,
   ``sum_d x_i k_j exp(G_i - G_j)`` for ``x`` = ``k`` and ``q``: a loop over
   ``j``, column ``j`` of every sub-block at once, whose row ``G_j``, ``k_j``
   is spread down its sub-block's sublanes, so that the exponent, the three
   multiplies and the masks are whole-register work on ``(rows i,
   channels)`` and the channel sum is one lane reduction a register of
   PRODUCTS.
3. Between sub-blocks the split at the later one's first row ``r``: ``(x_i
   exp(G_i - G_r)) (k_j exp(G_r - G_j))``, MXU products. Every exponent
   formed is <= 0.
4. The unit lower-triangular system ``(I + A) [U | W_k] = [b v | b k exp G]``
   by block inverses, float32 operands at the highest precision: the
   sub-blocks' ``(I + N)^-1 = (I - N)(I + N^2)(I + N^4)(I + N^8)`` (``N``
   nilpotent of order :data:`SUB`), then one level of ``T^-1 = D - D L D`` a
   doubling (``D`` the inverse of the diagonal blocks, ``L`` what lies
   between them) up to the chunk.
5. ``W = U - W_k S_0``, ``o = (q exp G) S_0 + A' W``, ``S = S_0 exp(G_L) +
   W^T (k exp(G_L - G))``: MXU products.

Precision: the products of 3 and 5 take operands rounded to bfloat16 and
accumulate in float32, as the XLA form's do on a TPU (the default matmul
precision on float32 operands); everything else is float32.

Told each row's length (``lengths``), a grid step whose chunk lies wholly
past it passes the state on and writes zeros for ``o``: real tokens' outputs
and the state are unchanged by it (such a chunk's rows have ``g = 0`` and
``beta = 0`` by the caller's contract).

Shapes (as the mixer makes them: a block is ``chunk`` rows of ``TH`` heads,
each row a tile of ``(TH, d)``, and a head's rows are gathered from the
tiles by strided loads inside the kernel; the form with the head axis folded
into the lanes, ``(B, P, heads * d)``, is no free view of it: XLA re-lays
all four inputs and ``o``, 28 ms a prefill of 8 x 1,024):
  q, k, g (B, P, heads, dk) f32; v (B, P, heads, dv) f32
  beta    (B, heads // TH, P, TH) f32
  lengths (B,) int32               [scalar prefetch]
  -> o (B, P, heads, dv) f32, state (B, heads, dv, dk) f32

Grid ``(B, heads // TH, P // chunk)``, the chunk axis last and sequential.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of a sub-block of a chunk (``models/hybrid.py`` ``DELTA_SUB``)
SUB = 16
#: heads of one grid step, at most
TILE_HEADS = 8
#: heads whose chains of dependent products are issued side by side: the
#: leading axis of every array inside a grid step
INTERLEAVE = 4

_HIGHEST = jax.lax.Precision.HIGHEST


def tile_heads(heads: int) -> int:
    """The most heads a grid step takes: the largest divisor of ``heads``
    that is at most :data:`TILE_HEADS`."""
    return max(d for d in range(1, TILE_HEADS + 1) if heads % d == 0)


def _dot32(a, b):
    """``a @ b`` on float32 operands at the highest precision, head by head
    where both have the heads as their leading axis."""
    if b.ndim == 2:
        return jnp.dot(a, b, precision=_HIGHEST, preferred_element_type=jnp.float32)
    return jax.lax.dot_general(
        a, b, (((2,), (1,)), ((0,), (0,))), precision=_HIGHEST,
        preferred_element_type=jnp.float32)


def _dot16(a, b, contract):
    """``a`` and ``b`` ``(heads, ., .)`` rounded to bfloat16, contracted head
    by head over ``contract`` (one axis of each), accumulated in float32."""
    return jax.lax.dot_general(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
        (((contract[0],), (contract[1],)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _delta_chunk_kernel(
    len_ref,    # SMEM (B,) int32
    q_ref,      # (L, TH, dk): a head's rows lie one a tile of (TH, dk)
    k_ref,      # (L, TH, dk)
    v_ref,      # (L, TH, dv)
    g_ref,      # (L, TH, dk)
    b_ref,      # (L, TH)
    o_ref,      # out (L, TH, dv)
    s_ref,      # out (TH, dv, dk): resident over the row's chunks
    q_scr,      # (TH, L, dk) f32: a head's rows gathered, head-major
    k_scr,      # (TH, L, dk) f32
    v_scr,      # (TH, L, dv) f32
    G_scr,      # (TH, L, dk) f32: the running sums
    *,
    sub: int,
    group: int,
):
    f32 = jnp.float32
    b, c = pl.program_id(0), pl.program_id(2)
    TH, Dv, Dk = s_ref.shape
    L = q_ref.shape[0]
    C, ns, GH = sub, L // sub, group

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, f32)

    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    ones_below = (row >= col).astype(f32)
    eye = (row == col).astype(f32)
    same = (row // C) == (col // C)
    before = (col // C) < (row // C)
    in_sub = col % C

    def rule(first):
        """The chunk of the heads ``first .. first + GH``, every array with
        the heads as its leading axis: a stage's products of the ``GH`` heads
        are issued side by side, so that one head's is in flight while the
        next head's is issued (a chain of dependent 64-row products a head
        otherwise waits out the MXU's latency each time: 17.0 ms a call of 8 x
        1,024 head by head, 9.5 by fours), and the kernel is traced once a
        group and not once a head (a prefill program is traced and lowered
        at every set-up, whatever the compile cache holds)."""
        hs = slice(first, first + GH)
        q, k, v, G = q_scr[hs], k_scr[hs], v_scr[hs], G_scr[hs]
        beta = jnp.stack(
            [b_ref[:, hh : hh + 1] for hh in range(first, first + GH)])
        # the sub-blocks' own pairs: column j of every sub-block at once, its
        # row G_j, k_j spread down the sub-block's sublanes
        split = (GH, ns, C, Dk)
        G4, k4 = G.reshape(split), k.reshape(split)
        kk = qk = jnp.zeros((GH, L, L), f32)
        for j in range(C):
            w = (jnp.exp(jnp.minimum(G4 - G4[:, :, j : j + 1], 0.0))
                 * k4[:, :, j : j + 1]).reshape(GH, L, Dk)
            hit = in_sub == j
            kk = jnp.where(hit, jnp.sum(k * w, axis=2, keepdims=True), kk)
            qk = jnp.where(hit, jnp.sum(q * w, axis=2, keepdims=True), qk)
        # between sub-blocks, split at the later one's first row
        firsts = [G[:, sb * C : sb * C + 1] for sb in range(ns)]  # (GH, 1, dk)
        into = jnp.concatenate(
            [jnp.exp(G[:, sb * C : (sb + 1) * C] - firsts[sb])
             for sb in range(ns)], axis=1)                    # exp(G_i - G_r)
        kd, qd = k * into, q * into
        cross_k = cross_q = [jnp.zeros((GH, C, L), f32)]
        for sb in range(1, ns):
            rows = slice(sb * C, (sb + 1) * C)
            # k_j exp(G_r - G_j): read where j lies before the sub-block
            kc = k * jnp.exp(jnp.minimum(firsts[sb] - G, 0.0))
            both = _dot16(
                jnp.concatenate([kd[:, rows], qd[:, rows]], axis=1), kc, (2, 2))
            cross_k, cross_q = cross_k + [both[:, :C]], cross_q + [both[:, C:]]
        kk = jnp.where(before, jnp.concatenate(cross_k, axis=1),
                       jnp.where(same & (col < row), kk, 0.0))
        qk = jnp.where(before, jnp.concatenate(cross_q, axis=1),
                       jnp.where(same & (col <= row), qk, 0.0))
        A = kk * beta
        eG = jnp.exp(G)
        # (I + A)^-1: the sub-blocks' by the nilpotent product, then doubled
        power = jnp.where(same, A, 0.0)
        inv = eye - power
        order = 2
        while order < C:                                      # N^2, N^4, ...
            power = _dot32(power, power)
            inv = _dot32(inv, eye + power)
            order *= 2
        size = C
        while size < L:
            between = (row // (2 * size) == col // (2 * size)) & (
                col // size < row // size)
            inv = inv - _dot32(inv, _dot32(jnp.where(between, A, 0.0), inv))
            size *= 2
        sol = _dot32(inv, jnp.concatenate(
            [v * beta, k * eG * beta], axis=2))               # [U | W_k]
        S0 = s_ref[hs]
        held = _dot16(jnp.concatenate([sol[:, :, Dv:], q * eG], axis=1),
                      S0, (2, 2))                             # [W_k; q e^G] S_0
        W = sol[:, :, :Dv] - held[:, :L]
        o = held[:, L:] + _dot16(qk, W, (2, 1))
        for i in range(GH):
            o_ref[:, first + i, :] = o[i]
        last = G[:, L - 1 : L]                                # (GH, 1, dk)
        s_ref[hs] = S0 * jnp.exp(last) + _dot16(
            W, k * jnp.exp(last - G), (1, 1))

    @pl.when(c * L < len_ref[b])
    def _():
        for hh in range(TH):        # a head's rows out of the (TH, d) tiles
            q_scr[hh] = q_ref[:, hh, :]
            k_scr[hh] = k_ref[:, hh, :]
            v_scr[hh] = v_ref[:, hh, :]
            G_scr[hh] = _dot32(ones_below, g_ref[:, hh, :])
        for first in range(0, TH, GH):
            rule(first)

    @pl.when(c * L >= len_ref[b])
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, f32)


def delta_chunk_rule(
    q: jax.Array,      # (B, P, heads, dk) float32
    k: jax.Array,      # (B, P, heads, dk) float32
    v: jax.Array,      # (B, P, heads, dv) float32
    g: jax.Array,      # (B, P, heads, dk) float32 <= 0; 0 where a row is padding
    beta: jax.Array,   # (B, P, heads) float32; 0 where a row is padding
    chunk: int,
    lengths: jax.Array | None = None,   # (B,) int32: chunks past it are skipped
    *,
    interpret: bool = False,
    heads_tile: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``delta_chunked``'s contract as one kernel: ``(o (B, P, heads, dv)
    float32, S_P^T (B, heads, dv, dk) float32)``."""
    f32 = jnp.float32
    B, Pn, H, Dk = k.shape
    Dv = v.shape[-1]
    L = min(chunk, Pn)
    if Pn % L:
        raise ValueError(f"delta_chunk_rule: {Pn} rows are no whole chunks of {L}")
    sub = min(SUB, L)
    if L % sub or (L // sub) & (L // sub - 1):
        raise ValueError(
            f"delta_chunk_rule: a chunk of {L} rows is not {sub} x a power of two")
    TH = heads_tile or tile_heads(H)
    if H % TH:
        raise ValueError(f"delta_chunk_rule: {H} heads in tiles of {TH}")
    group = max(d for d in range(1, INTERLEAVE + 1) if TH % d == 0)
    if lengths is None:
        lengths = jnp.full((B,), Pn, jnp.int32)
    rows = lambda d: pl.BlockSpec(  # noqa: E731
        (None, L, TH, d), lambda b, t, c, n: (b, c, t, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H // TH, Pn // L),
        in_specs=[
            rows(Dk), rows(Dk), rows(Dv), rows(Dk),
            pl.BlockSpec((None, None, L, TH), lambda b, t, c, n: (b, t, c, 0)),
        ],
        out_specs=[
            rows(Dv),
            pl.BlockSpec((None, TH, Dv, Dk), lambda b, t, c, n: (b, t, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((TH, L, Dk), f32),
            pltpu.VMEM((TH, L, Dk), f32),
            pltpu.VMEM((TH, L, Dv), f32),
            pltpu.VMEM((TH, L, Dk), f32),
        ],
    )
    o, state = pl.pallas_call(
        functools.partial(_delta_chunk_kernel, sub=sub, group=group),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Pn, H, Dv), f32),
            jax.ShapeDtypeStruct((B, H, Dv, Dk), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            # a row's chunks in order: the state stays resident
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
        name="delta_chunk_rule",
    )(
        lengths.astype(jnp.int32), q.astype(f32), k.astype(f32),
        v.astype(f32), g.astype(f32),
        beta.astype(f32).reshape(B, Pn, H // TH, TH).swapaxes(1, 2),
    )
    return o, state
