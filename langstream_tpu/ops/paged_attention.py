"""Paged-attention decode kernels (Pallas TPU).

One decode step reads each slot's KV *blocks* straight out of the shared
pool. The single-query read (:func:`paged_attention_partial`) takes the
layer-stacked pool as it lies in HBM and fetches, with its own DMAs, exactly
the blocks a slot's length makes live: no per-layer slice of the pool, no
densified gather copy (the XLA reference path :func:`gather_kv` pays one),
no ``slots × max_seq`` layout anywhere, and no visit to a table column past
a slot's length.

Online softmax over the block sweep, same discipline as
``flash_attention.py``. The kernel returns *partial* results
``(acc, m, l)`` — unnormalised accumulator, running max, running sum-exp —
because decode attends over two segments: the paged cache (here) and the
in-chunk KV buffer (tiny, handled in XLA). The caller merges the two with
the standard online-softmax combine (``merge_partial_attention``).

Shapes (the layer loop lives in the model's ``lax.scan``, which hands the
kernel the layer's index and closes over the pool):
  q             (B, H, D)
  k_pool/v_pool (L, nb, bs, Kh*D)         [stay in HBM]
  layer         () int32                  [scalar prefetch]
  block_tables  (B, max_blocks) int32     [scalar prefetch]
  lengths       (B,) int32                [scalar prefetch]
  → acc (B, H, D) f32, m (B, H, 128) f32, l (B, H, 128) f32
    (m/l broadcast along a 128-lane axis: TPU-friendly layout)

Grid ``(B,)``, one step a slot, in order. Inside a step a loop runs over the
slot's ``cdiv(length, bs)`` live blocks, a *tile* of several blocks at a
time: each live block of the tile is one ``make_async_copy`` from
``pool[layer, table[b, j]]`` into one of two VMEM tiles, and the next tile's
copies (the next live slot's first tile, at a slot's end) are in flight
while this one is computed. A slot of length 0 runs zero iterations and
starts no copy. What a call costs follows the live blocks, not
``num_read_blocks``, which only caps the rows a slot may attend.

A pool whose row is one latent, key and value in the same bytes and no head
axis (:func:`latent_read`), is read by the same walk (:func:`_live_tiles`).

The multi-query twin (:func:`paged_attention_multiquery_partial`,
continuation prefill and speculative verify) still sweeps a static
``(B, T-blocks, num_read_blocks)`` grid over one layer's pool slice.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)

# VMEM the single-query read's tiles may take, both buffers of K and of V:
# a tile is the largest whole number of blocks that fits (8 blocks =
# 512 rows at bs 64, Kh*D 1024, bf16). A constant of the kernel, sized
# against the 16 MiB of scoped VMEM a v5e kernel gets by default.
TILE_VMEM_BYTES = 4 * 1024 * 1024


def _live_tiles(
    layer_ref,    # SMEM (1,) int32
    tables_ref,   # SMEM (B, max_blocks) int32
    lengths_ref,  # SMEM (B,) int32
    pools,        # the pools in HBM, each (L, nb, bs, row width)
    tiles,        # one VMEM (2, T*bs, row width) a pool: two buffers of a tile
    sems,         # DMA (pools, 2): [pool, buffer]
    buf_ref,      # SMEM (1,) int32: the buffer the next tile to compute is in
    *,
    block_size: int,
    tile_blocks: int,
    num_read_blocks: int,
    firsts_ref=None,  # SMEM (B,) int32: the first row a slot's query sees
):
    """The walk over a slot's live blocks that the single-query reads share
    (:func:`_paged_read_kernel` over a K and a V pool, :func:`_latent_read_
    kernel` over one pool of latent rows): grid step ``b`` is slot ``b``;
    a tile is ``tile_blocks`` blocks, each live block of it one copy from
    ``pool[layer, table[b, j]]`` into one of a tile's two buffers, and the
    next tile's copies (the next live slot's first tile, at a slot's end)
    fly while this one is computed. Starts the first live slot's first
    tile at step 0 and returns ``(length, sweep)``: the slot's rows and
    ``sweep(compute, carry)``, which runs ``carry = compute(buf, start,
    carry)`` a tile in order, ``buf`` the buffer the tile's rows from
    ``start`` lie in. With ``firsts_ref`` (a layer that attends a window) the
    walk starts at the block that holds a slot's first row, not at block 0:
    the table columns before it are never visited, and the caller masks the
    rows of that block that lie before the first."""
    b = pl.program_id(0)
    B = pl.num_programs(0)
    bs, T = block_size, tile_blocks
    rows_t = T * bs
    layer = layer_ref[0]

    def rows_of(slot):
        return jnp.minimum(lengths_ref[slot], num_read_blocks * bs)

    def first_block(slot):
        return firsts_ref[slot] // bs

    def for_live_blocks(slot, t, buf, act):
        """``act`` on every pool's copy of each live block of tile ``t``
        of ``slot``; none for the table columns past the slot's length."""
        n = pl.cdiv(rows_of(slot), bs)

        def column(j):
            return (t * T + j if firsts_ref is None
                    else first_block(slot) + t * T + j)

        for j in range(T):
            @pl.when(column(j) < n)
            def _():
                blk = tables_ref[slot, column(j)]
                for i, (pool, tile) in enumerate(zip(pools, tiles)):
                    act(pltpu.make_async_copy(
                        pool.at[layer, blk],
                        tile.at[buf, pl.ds(j * bs, bs)],
                        sems.at[i, buf],
                    ))

    def next_live(after):
        return jax.lax.while_loop(
            lambda i: jnp.logical_and(
                i < B, lengths_ref[jnp.minimum(i, B - 1)] <= 0
            ),
            lambda i: i + 1,
            after + 1,
        )

    @pl.when(b == 0)
    def _first_fetch():
        buf_ref[0] = 0
        first = next_live(-1)

        @pl.when(first < B)
        def _():
            for_live_blocks(first, 0, 0, lambda c: c.start())

    length = rows_of(b)
    if firsts_ref is None:
        num_tiles = pl.cdiv(pl.cdiv(length, bs), T)
    else:
        num_tiles = pl.cdiv(
            jnp.maximum(pl.cdiv(length, bs) - first_block(b), 0), T)
        row0 = first_block(b) * bs
    after = next_live(b)

    def sweep(compute, carry):
        def tile_step(t, carry):
            buf = buf_ref[0]
            # the next tile's copies fly while this one is computed: the
            # slot's own next tile, or at its end the next live slot's first
            last = t + 1 >= num_tiles
            ahead_slot = jnp.where(last, after, b)
            ahead_tile = jnp.where(last, 0, t + 1)

            @pl.when(ahead_slot < B)
            def _():
                for_live_blocks(
                    ahead_slot, ahead_tile, 1 - buf, lambda c: c.start()
                )

            for_live_blocks(b, t, buf, lambda c: c.wait())
            carry = compute(
                buf, t * rows_t if firsts_ref is None else row0 + t * rows_t,
                carry)
            buf_ref[0] = 1 - buf
            return carry

        return jax.lax.fori_loop(0, num_tiles, tile_step, carry)

    return length, sweep


def _live_masks(start, rows_t: int, length, first=None):
    """``(live (1, rows), live_rows (rows, 1))`` of a tile from ``start``:
    the rows under ``length`` and, with ``first``, not before it."""
    across = start + jax.lax.broadcasted_iota(jnp.int32, (1, rows_t), 1)
    live = across < length
    down = start + jax.lax.broadcasted_iota(jnp.int32, (rows_t, 1), 0)
    live_rows = down < length
    if first is not None:
        live = live & (across >= first)
        live_rows = live_rows & (down >= first)
    return live, live_rows


def _online_softmax(s, live, m_prev, l_prev):
    """One tile of the running softmax over scores ``s (H, rows)``:
    ``(m_new, l_new, alpha, p)`` with ``p`` float32, 0 where not live."""
    s = jnp.where(live, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    shift = jnp.where(m_new <= NEG_INF, 0.0, m_new)
    p = jnp.where(live, jnp.exp(s - shift), 0.0)
    alpha = jnp.exp(jnp.where(m_prev <= NEG_INF, NEG_INF, m_prev - shift))
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    return m_new, l_new, alpha, p


def _paged_read_kernel(
    layer_ref,    # SMEM (1,) int32
    tables_ref,   # SMEM (B, max_blocks) int32
    lengths_ref,  # SMEM (B,) int32
    q_ref,        # (1, H, D)
    k_hbm,        # (L, nb, bs, KhD), in HBM
    v_hbm,
    acc_out,      # (1, H, D) f32
    m_out,        # (1, H, 128) f32
    l_out,        # (1, H, 128) f32
    k_tile,       # VMEM (2, T*bs, KhD): two buffers of one tile
    v_tile,
    sems,         # DMA (2, 2): [k|v, buffer]
    buf_ref,      # SMEM (1,) int32: the buffer the next tile to compute is in
    *,
    scale: float,
    block_size: int,
    tile_blocks: int,
    num_read_blocks: int,
    kv_heads: int,
    head_dim: int,
    firsts_ref=None,
):
    _, H, D = q_ref.shape
    G = H // kv_heads
    rows_t = tile_blocks * block_size
    length, sweep = _live_tiles(
        layer_ref, tables_ref, lengths_ref, (k_hbm, v_hbm), (k_tile, v_tile),
        sems, buf_ref, block_size=block_size, tile_blocks=tile_blocks,
        num_read_blocks=num_read_blocks,
        **({} if firsts_ref is None else {"firsts_ref": firsts_ref}),
    )
    first = None if firsts_ref is None else firsts_ref[pl.program_id(0)]
    q = q_ref[0]                                       # (H, D)

    def head(tile, buf, kh):
        # rows × one kv head: a lane-aligned slice (D = 128), so the dots
        # are plain 2-D matmuls on the stored layout. No (rows, Kh, D)
        # reshape and no batch dimension in the dot: that relayout is what
        # r5's chip attribution pinned the q8 lane's 62-vs-42 ms/step on.
        return tile[buf, :, kh * head_dim:(kh + 1) * head_dim]

    def compute(buf, start, carry):
        m_prev, l_prev, acc = carry                    # (H,1) (H,1) (H,D)
        live, live_rows = _live_masks(start, rows_t, length, first)
        s = jnp.concatenate([
            jax.lax.dot_general(
                q[kh * G:(kh + 1) * G], head(k_tile, buf, kh),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            )                                          # (G, rows)
            for kh in range(kv_heads)
        ], axis=0) * scale
        m_new, l_new, alpha, p = _online_softmax(s, live, m_prev, l_prev)
        p = p.astype(v_tile.dtype)
        pv = []
        for kh in range(kv_heads):
            # rows that are not live (the last block's tail, what an
            # earlier tile left in the buffer) are zeroed, not only given
            # probability 0: 0 × NaN is NaN
            v_h = head(v_tile, buf, kh)
            v_h = jnp.where(live_rows, v_h, jnp.zeros_like(v_h))
            pv.append(jax.lax.dot_general(
                p[kh * G:(kh + 1) * G], v_h,
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            ))                                         # (G, D)
        acc = acc * alpha + jnp.concatenate(pv, axis=0)
        return m_new, l_new, acc

    m, l, acc = sweep(compute, (
        jnp.full((H, 1), NEG_INF, jnp.float32),
        jnp.zeros((H, 1), jnp.float32),
        jnp.zeros((H, D), jnp.float32),
    ))
    acc_out[0] = acc
    m_out[0] = jnp.broadcast_to(m, m_out.shape[1:])
    l_out[0] = jnp.broadcast_to(l, l_out.shape[1:])


def _latent_read_kernel(
    layer_ref,    # SMEM (1,) int32
    tables_ref,   # SMEM (B, max_blocks) int32
    lengths_ref,  # SMEM (B,) int32
    q_ref,        # (1, H, W): [q absorbed into the latent | rotary part]
    pool_hbm,     # (L, nb, bs, W) latent rows [c_kv | k_pe], in HBM
    acc_out,      # (1, H, Dv) f32
    m_out,        # (1, H, 128) f32
    l_out,        # (1, H, 128) f32
    tile,         # VMEM (2, T*bs, W): two buffers of one tile
    sems,         # DMA (1, 2)
    buf_ref,      # SMEM (1,) int32
    *,
    scale: float,
    block_size: int,
    tile_blocks: int,
    num_read_blocks: int,
    value_dim: int,
):
    """Every head against the ONE row a position: the key is the whole row,
    the value its first ``value_dim`` lanes, the same bytes of the same
    tile. ``value_dim`` is a multiple of the lane tile, so both parts are
    lane-aligned slices and each tile is two score dots and one value dot
    of all the heads at once."""
    _, H, W = q_ref.shape
    Dv = value_dim
    rows_t = tile_blocks * block_size
    length, sweep = _live_tiles(
        layer_ref, tables_ref, lengths_ref, (pool_hbm,), (tile,), sems,
        buf_ref, block_size=block_size, tile_blocks=tile_blocks,
        num_read_blocks=num_read_blocks,
    )
    q = q_ref[0]                                       # (H, W)
    q_lat, q_pe = q[:, :Dv], q[:, Dv:]

    def compute(buf, start, carry):
        m_prev, l_prev, acc = carry                    # (H,1) (H,1) (H,Dv)
        live, live_rows = _live_masks(start, rows_t, length)
        # what is not live is zeroed, as in the K/V read: 0 × NaN is NaN
        c = tile[buf, :, :Dv]
        c = jnp.where(live_rows, c, jnp.zeros_like(c))             # (rows, Dv)
        pe = tile[buf, :, Dv:]                                     # (rows, W-Dv)
        dims = (((1,), (1,)), ((), ()))
        s = (
            jax.lax.dot_general(q_lat, c, dims,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(q_pe, pe, dims,
                                  preferred_element_type=jnp.float32)
        ) * scale                                                  # (H, rows)
        m_new, l_new, alpha, p = _online_softmax(s, live, m_prev, l_prev)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc

    m, l, acc = sweep(compute, (
        jnp.full((H, 1), NEG_INF, jnp.float32),
        jnp.zeros((H, 1), jnp.float32),
        jnp.zeros((H, Dv), jnp.float32),
    ))
    acc_out[0] = acc
    m_out[0] = jnp.broadcast_to(m, m_out.shape[1:])
    l_out[0] = jnp.broadcast_to(l, l_out.shape[1:])


def _paged_kernel_q8(
    # int8 twin of _paged_read_kernel, still on the static grid
    # (B, num_read_blocks) over one layer's pool slice (fully-masked blocks
    # are skipped with pl.when, their DMA of block 0 still happens): k/v
    # arrive as int8 blocks with per-(row, kv-head) f32 scales. The k scale
    # multiplies the SCORE (constant along D, factored out of the dot); the
    # v scale folds into the probabilities before the value dot — exactly
    # the fused-dequant discipline of the XLA path (models/kvquant.py
    # cache_scores/cache_values), so the two lanes are numerically
    # interchangeable.
    tables_ref,   # SMEM (B, max_blocks) int32
    lengths_ref,  # SMEM (B,) int32
    q_ref,        # (1, H, D)
    k_ref,        # (1, bs, KhD) int8
    ks_ref,       # (1, bs, Kh) f32
    v_ref,        # (1, bs, KhD) int8
    vs_ref,       # (1, bs, Kh) f32
    acc_out,      # (1, H, D) f32
    m_out,        # (1, H, 128) f32
    l_out,        # (1, H, 128) f32
    m_ref,        # VMEM (H, 128) f32
    l_ref,        # VMEM (H, 128) f32
    acc_ref,      # VMEM (H, D) f32
    *,
    scale: float,
    block_size: int,
    kv_heads: int,
    head_dim: int,
):
    b = pl.program_id(0)
    ji = pl.program_id(1)
    num_j = pl.num_programs(1)
    length = lengths_ref[b]
    start = ji * block_size

    @pl.when(ji == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(start < length)
    def _accumulate():
        H, D = acc_ref.shape
        G = H // kv_heads
        q = q_ref[0]                                   # (H, D) bf16
        ks = ks_ref[0]                                 # (bs, Kh) f32
        vs = vs_ref[0]
        # batch-LEADING discipline, transpose-free: the r5 chip attribution
        # pinned the q8 lane's 62-vs-42 ms/step loss on the per-block
        # (bs, Kh, D) → (Kh, bs, D) relayouts of BOTH operands, not the
        # gather. Unrolling the (static, small) kv-head axis turns each dot
        # into a plain 2D matmul over a contiguous lane slice of the int8
        # block — no batch dims at all, so Mosaic's "int8-converted operand
        # must carry the batch dim leading" constraint is vacuous and the
        # int8 rows stream into the MXU in their stored layout.
        s_heads = []
        for kh in range(kv_heads):
            k_h = k_ref[0][:, kh * head_dim:(kh + 1) * head_dim]  # (bs, D)
            s_h = jax.lax.dot_general(
                q[kh * G:(kh + 1) * G], k_h.astype(q.dtype),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                          # (G, bs)
            # dequant k: the scale is constant along D — apply to the score
            s_heads.append(s_h * ks[:, kh][None, :] * scale)
        s = jnp.concatenate(s_heads, axis=0)           # (H, bs)
        cols = start + jax.lax.broadcasted_iota(
            jnp.int32, (H, block_size), 1
        )
        mask = cols < length
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, 0]                           # (H,)
        l_prev = l_ref[:, 0]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        shift = jnp.where(m_new <= NEG_INF, 0.0, m_new)
        p = jnp.exp(s - shift[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(jnp.where(m_prev <= NEG_INF, NEG_INF, m_prev - shift))
        l_ref[:] = jnp.broadcast_to(
            (l_prev * alpha + jnp.sum(p, axis=1))[:, None], l_ref.shape
        )
        # dequant v: scale varies along the contracted row axis — fold it
        # into the probabilities; same per-head 2D dots, same stored layout
        pv_heads = []
        for kh in range(kv_heads):
            v_h = v_ref[0][:, kh * head_dim:(kh + 1) * head_dim]  # (bs, D)
            p_h = p[kh * G:(kh + 1) * G] * vs[:, kh][None, :]     # (G, bs)
            pv_heads.append(jax.lax.dot_general(
                p_h.astype(q.dtype), v_h.astype(q.dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))                                         # (G, D)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jnp.concatenate(
            pv_heads, axis=0
        )
        m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)

    @pl.when(ji == num_j - 1)
    def _finalize():
        acc_out[0] = acc_ref[:]
        m_out[0] = m_ref[:]
        l_out[0] = l_ref[:]


def paged_attention_partial(
    q: jax.Array,             # (B, H, D)
    k_pool,                   # (L, nb, bs, Kh*D) bf16, or int8 {"q","s"} pool
    v_pool,
    layer,                    # () int32 — which layer of the stacked pool
    block_tables: jax.Array,  # (B, max_blocks) int32
    lengths: jax.Array,       # (B,) int32 — cache rows to attend per slot
    *,
    num_read_blocks: int,     # static cap on the table columns a slot reads
    kv_heads: int,
    head_dim: int,
    scale: float | None = None,
    interpret: bool = False,
    firsts: jax.Array | None = None,  # (B,) int32: the first row a slot sees
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Partial (unnormalised) paged attention over the cache segment.

    Returns ``(acc (B,H,D) f32, m (B,H) f32, l (B,H) f32)`` for the caller
    to merge with other segments via :func:`merge_partial_attention`.

    The pool is the layer-stacked one, read in place: the kernel fetches
    ``pool[layer, table[b, j]]`` for the live ``j`` only. With ``firsts``
    (a layer that attends a window of its last rows) a slot's query sees the
    rows ``[firsts[b], lengths[b])``: the walk starts at the block that holds
    the first and the rows of it before the first are masked.

    int8 pools (``{"q": int8, "s": f32}`` dicts) still read through the
    static-grid twin on a slice of the layer: their ``(bs, Kh)`` scale rows
    are 8 lanes wide, and Mosaic refuses a hand-made copy of them ("slice
    shape must be aligned to tiling (128)"); see ROADMAP S3.
    """
    if isinstance(k_pool, dict) and firsts is not None:
        raise ValueError("the int8 pool's read takes no first row")
    if isinstance(k_pool, dict):
        at_layer = lambda a: jax.lax.dynamic_index_in_dim(  # noqa: E731
            a, layer, keepdims=False
        )
        return _paged_attention_partial_q8(
            q, jax.tree.map(at_layer, k_pool), jax.tree.map(at_layer, v_pool),
            block_tables, lengths,
            num_read_blocks=num_read_blocks, kv_heads=kv_heads,
            head_dim=head_dim, scale=scale, interpret=interpret,
        )
    B, H, D = q.shape
    _, _, bs, KhD = k_pool.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    tile_blocks = max(1, min(
        TILE_VMEM_BYTES // (4 * bs * KhD * k_pool.dtype.itemsize),
        num_read_blocks,
    ))
    kernel = functools.partial(
        _paged_read_kernel,
        scale=scale, block_size=bs, tile_blocks=tile_blocks,
        num_read_blocks=num_read_blocks, kv_heads=kv_heads, head_dim=head_dim,
    )
    prefetched = (
        jnp.asarray(layer, jnp.int32).reshape(1), block_tables, lengths)
    if firsts is not None:
        # a fourth prefetched scalar array, handed on under its name
        inner = kernel
        kernel = lambda layer, tables, lengths, firsts, *refs: inner(  # noqa: E731
            layer, tables, lengths, *refs, firsts_ref=firsts)
        prefetched += (firsts.astype(jnp.int32),)
    per_slot = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda b, *prefetched: (b, 0, 0)
    )
    tile = pltpu.VMEM((2, tile_blocks * bs, KhD), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetched),
        grid=(B,),
        in_specs=[
            per_slot((1, H, D)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            per_slot((1, H, D)), per_slot((1, H, 128)), per_slot((1, H, 128)),
        ],
        scratch_shapes=[
            tile, tile,
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # in order: a slot's step starts the next live slot's first tile
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_read",
    )(*prefetched, q, k_pool, v_pool)
    return acc, m[:, :, 0], l[:, :, 0]


#: blocks of one tile of the latent read (1,024 rows at bs 64: both buffers
#: 2.6 MiB of VMEM at a 640-lane bf16 row, the scores of 128 heads 512 KiB).
#: On the v5e, 96 slots of 484k rows in all, ms a call: 4 blocks 1.85,
#: 8 blocks 1.55, 16 blocks 1.45, against a floor of 0.68
#: (tools/latent_probe.py --kernels --tiles 4 8 16)
LATENT_TILE_BLOCKS = 16


def latent_read(
    q: jax.Array,             # (B, H, W): [absorbed query | rotary part]
    pool: jax.Array,          # (L, nb, bs, W): latent rows [c_kv | k_pe]
    layer,                    # () int32: which layer of the stacked pool
    block_tables: jax.Array,  # (B, max_blocks) int32
    lengths: jax.Array,       # (B,) int32: cache rows to attend per slot
    *,
    num_read_blocks: int,     # static cap on the table columns a slot reads
    value_dim: int,           # leading lanes of a row that are its value
    scale: float,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Partial (unnormalised) latent attention over the cache segment: every
    head's query against the one ``W``-wide row a position, whose first
    ``value_dim`` lanes are also the value (multi-head latent attention with
    the up-projections absorbed into the query and the output). Returns
    ``(acc (B, H, value_dim) f32, m (B, H) f32, l (B, H) f32)`` for
    :func:`merge_partial_attention`. The pool is read in place, live blocks
    only, as :func:`paged_attention_partial` reads its two."""
    B, H, W = q.shape
    bs = pool.shape[2]
    tile_blocks = max(1, min(LATENT_TILE_BLOCKS, num_read_blocks))
    kernel = functools.partial(
        _latent_read_kernel,
        scale=scale, block_size=bs, tile_blocks=tile_blocks,
        num_read_blocks=num_read_blocks, value_dim=value_dim,
    )
    per_slot = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda b, layer, tables, lengths: (b, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[per_slot((1, H, W)), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[
            per_slot((1, H, value_dim)), per_slot((1, H, 128)),
            per_slot((1, H, 128)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, tile_blocks * bs, W), pool.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, value_dim), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="latent_read",
    )(jnp.asarray(layer, jnp.int32).reshape(1), block_tables, lengths, q, pool)
    return acc, m[:, :, 0], l[:, :, 0]


def latent_read_xla(
    q: jax.Array, pool: jax.Array, layer, block_tables: jax.Array,
    lengths: jax.Array, *, num_read_blocks: int, value_dim: int, scale: float,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`latent_read` as an XLA expression: the window gathered densely
    from the layer's slice of the pool (a copy the kernel does not pay), the
    same partial softmax. Every backend; what the kernel is checked
    against."""
    B, H, W = q.shape
    bs = pool.shape[2]
    rows = jax.lax.dynamic_index_in_dim(pool, layer, keepdims=False)[
        block_tables[:, :num_read_blocks]
    ].reshape(B, num_read_blocks * bs, W)
    live = (jnp.arange(num_read_blocks * bs)[None, :] < lengths[:, None])
    s = jnp.einsum("bhw,btw->bht", q, rows).astype(jnp.float32) * scale
    s = jnp.where(live[:, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    shift = jnp.where(m <= NEG_INF, 0.0, m)
    p = jnp.where(live[:, None, :], jnp.exp(s - shift[..., None]), 0.0)
    values = jnp.where(live[..., None], rows[..., :value_dim], 0)
    acc = jnp.einsum("bht,btd->bhd", p.astype(q.dtype), values)
    return acc.astype(jnp.float32), m, jnp.sum(p, axis=-1)


def _paged_attention_partial_q8(
    q: jax.Array,
    k_pool: dict,
    v_pool: dict,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    num_read_blocks: int,
    kv_heads: int,
    head_dim: int,
    scale: float | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    B, H, D = q.shape
    nb, bs, KhD = k_pool["q"].shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    kernel = functools.partial(
        _paged_kernel_q8,
        scale=scale,
        block_size=bs,
        kv_heads=kv_heads,
        head_dim=head_dim,
    )
    block = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda b, j, tables, lengths: (tables[b, j], 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, num_read_blocks),
        in_specs=[
            pl.BlockSpec(
                (1, H, D), lambda b, j, tables, lengths: (b, 0, 0)
            ),
            block((1, bs, KhD)),          # k int8
            block((1, bs, kv_heads)),     # k scales
            block((1, bs, KhD)),          # v int8
            block((1, bs, kv_heads)),     # v scales
        ],
        out_specs=[
            pl.BlockSpec((1, H, D), lambda b, j, tables, lengths: (b, 0, 0)),
            pl.BlockSpec((1, H, 128), lambda b, j, tables, lengths: (b, 0, 0)),
            pl.BlockSpec((1, H, 128), lambda b, j, tables, lengths: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_read_q8",
    )(block_tables, lengths, q, k_pool["q"], k_pool["s"],
      v_pool["q"], v_pool["s"])
    return acc, m[:, :, 0], l[:, :, 0]


def _paged_mq_kernel(
    tables_ref,   # SMEM (B, max_blocks) int32
    starts_ref,   # SMEM (B,) int32 — history rows per slot
    q_ref,        # (1, tb, H, D)
    k_ref,        # (1, bs, KhD)
    v_ref,        # (1, bs, KhD)
    acc_out,      # (1, tb*H, D) f32
    m_out,        # (1, tb*H, 8) f32 — narrow HBM output, lane 0 is read
    l_out,        # (1, tb*H, 8) f32
    m_ref,        # VMEM (tb*H, 128) f32
    l_ref,        # VMEM (tb*H, 128) f32
    acc_ref,      # VMEM (tb*H, D) f32
    *,
    scale: float,
    block_size: int,
    kv_heads: int,
    head_dim: int,
    t_block: int,
):
    b = pl.program_id(0)
    ji = pl.program_id(2)
    num_j = pl.num_programs(2)
    length = starts_ref[b]
    start = ji * block_size

    @pl.when(ji == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(start < length)
    def _accumulate():
        T = t_block
        D = head_dim
        H = acc_ref.shape[0] // T
        G = H // kv_heads
        q = q_ref[0]                                     # (T, H, D)
        k = k_ref[0].reshape(block_size, kv_heads, D)
        v = v_ref[0].reshape(block_size, kv_heads, D)
        # rows per kv head: T query positions × G grouped heads — every
        # history key is visible to every suffix query (rows < start), so
        # unlike causal attention the mask is uniform across the T axis
        qg = (
            q.reshape(T, kv_heads, G, D)
            .transpose(1, 0, 2, 3)
            .reshape(kv_heads, T * G, D)
        )
        kb = k.transpose(1, 0, 2)                        # (Kh, bs, D)
        s = jax.lax.dot_general(
            qg, kb, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                        # (Kh, T*G, bs)
        cols = start + jax.lax.broadcasted_iota(
            jnp.int32, (kv_heads, T * G, block_size), 2
        )
        mask = cols < length
        s = jnp.where(mask, s, NEG_INF)
        # working layout (T*H,) = (Kh, T, G) flattened to match acc rows
        TH = T * kv_heads * G
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_cur = jnp.max(s, axis=2).reshape(TH)
        m_new = jnp.maximum(m_prev, m_cur)
        shift = jnp.where(m_new <= NEG_INF, 0.0, m_new)
        p = jnp.exp(s - shift.reshape(kv_heads, T * G)[..., None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(jnp.where(m_prev <= NEG_INF, NEG_INF, m_prev - shift))
        l_ref[:] = jnp.broadcast_to(
            (l_prev * alpha + jnp.sum(p, axis=2).reshape(TH))[:, None],
            l_ref.shape,
        )
        vb = v.transpose(1, 0, 2)                        # (Kh, bs, D)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), vb, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                # (Kh, T*G, D)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + pv.reshape(TH, D)
        m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)

    @pl.when(ji == num_j - 1)
    def _finalize():
        acc_out[0] = acc_ref[:]
        m_out[0] = m_ref[:, :8]
        l_out[0] = l_ref[:, :8]


def paged_attention_multiquery_partial(
    q: jax.Array,             # (B, T, H, D) — T suffix queries per slot
    k_pool: jax.Array,        # (nb, bs, Kh*D)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32
    starts: jax.Array,        # (B,) int32 — history rows per slot
    *,
    num_read_blocks: int,
    kv_heads: int,
    head_dim: int,
    t_block: int = 16,
    scale: float | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Multi-query twin of :func:`paged_attention_partial`: T suffix
    queries per slot attend the slot's paged HISTORY (rows ``< starts``) —
    the continuation-prefill / speculative-verify hot read. History is
    mask-uniform across the T axis (causality among the suffix itself is
    the caller's separate XLA segment), so one online-softmax sweep serves
    a block of queries with (T·G)-row MXU tiles instead of G-row ones. It
    takes one layer's pool slice and sweeps a static grid of
    ``num_read_blocks`` table columns, dead ones skipped by ``pl.when``.

    Returns ``(acc (B,T,H,D) f32, m (B,T,H) f32, l (B,T,H) f32)``.
    ``T`` must be a multiple of ``t_block``.
    """
    B, T, H, D = q.shape
    nb, bs, KhD = k_pool.shape
    if T % t_block:
        raise ValueError(f"T={T} must be a multiple of t_block={t_block}")
    nt = T // t_block
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    THb = t_block * H
    kernel = functools.partial(
        _paged_mq_kernel,
        scale=scale,
        block_size=bs,
        kv_heads=kv_heads,
        head_dim=head_dim,
        t_block=t_block,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nt, num_read_blocks),
        in_specs=[
            pl.BlockSpec(
                (1, t_block, H, D),
                lambda b, t, j, tables, starts: (b, t, 0, 0),
            ),
            pl.BlockSpec(
                (1, bs, KhD),
                lambda b, t, j, tables, starts: (tables[b, j], 0, 0),
            ),
            pl.BlockSpec(
                (1, bs, KhD),
                lambda b, t, j, tables, starts: (tables[b, j], 0, 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, THb, D), lambda b, t, j, tables, starts: (b, t, 0)
            ),
            # m/l outputs are narrow (callers read one lane): the scratch
            # keeps the 128-lane compute layout, but materializing
            # (B, T·H, 128) f32 in HBM would be a 16× transient that now
            # scales with the suffix length
            pl.BlockSpec(
                (1, THb, 8), lambda b, t, j, tables, starts: (b, t, 0)
            ),
            pl.BlockSpec(
                (1, THb, 8), lambda b, t, j, tables, starts: (b, t, 0)
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((THb, 128), jnp.float32),
            pltpu.VMEM((THb, 128), jnp.float32),
            pltpu.VMEM((THb, D), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, nt * THb, D), jnp.float32),
            jax.ShapeDtypeStruct((B, nt * THb, 8), jnp.float32),
            jax.ShapeDtypeStruct((B, nt * THb, 8), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_read_mq",
    )(block_tables, starts, q, k_pool, v_pool)
    # kernel rows are (Kh, t, G)-major per t-block → back to (B, T, H)
    G = H // kv_heads

    def unflatten(x, *trail):
        x = x.reshape(B, nt, kv_heads, t_block, G, *trail)
        x = x.transpose(0, 1, 3, 2, 4, *range(5, 5 + len(trail)))
        return x.reshape(B, T, H, *trail)

    acc = unflatten(acc, D)
    m = unflatten(m[:, :, 0])
    l = unflatten(l[:, :, 0])
    return acc, m, l


def shard_mapped_paged_read(
    fn,                       # per-shard partial fn(..., kv_heads=) → 3-tuple
    mesh,
    *,
    kv_heads: int,
    batch: int,
    q_spec_tail: tuple,       # q PartitionSpec entries AFTER the batch axis
    out_spec_tails: tuple,    # per-output spec entries after the batch axis
    stacked_pool: bool = False,  # pools (L, nb, bs, Kh·D) + a layer index
):
    """Shared mesh wrapper for the paged read kernels (decode single-query
    and continuation multi-query): slots on ``dp``, heads on ``tp`` (the
    pool's fused Kh·D axis splits on head boundaries), degrading an axis to
    replicated when the batch doesn't divide ``dp`` or the KV heads don't
    divide ``tp``. One copy so the two call sites can't drift."""
    from functools import partial as _partial

    from jax.sharding import PartitionSpec as P

    axes = mesh.axis_names
    dp = (
        "dp"
        if "dp" in axes and mesh.shape["dp"] > 1 and batch % mesh.shape["dp"] == 0
        else None
    )
    tp = (
        "tp"
        if "tp" in axes
        and mesh.shape["tp"] > 1
        and kv_heads % mesh.shape["tp"] == 0
        else None
    )
    tp_size = mesh.shape["tp"] if tp else 1

    def sub(entry):
        return {"dp": dp, "tp": tp}.get(entry, entry) if entry else None

    q_spec = P(dp, *(sub(e) for e in q_spec_tail))
    # the single-query read takes the layer-stacked pool and the layer's
    # index (replicated); the multi-query read one layer's slice
    pool_specs = (
        (P(None, None, None, tp), P(None, None, None, tp), P())
        if stacked_pool
        else (P(None, None, tp), P(None, None, tp))  # (nb, bs, Kh·D)
    )
    return jax.shard_map(
        _partial(fn, kv_heads=kv_heads // tp_size),
        mesh=mesh,
        in_specs=(
            q_spec,
            *pool_specs,
            P(dp, None),        # block tables (B, max_blocks)
            P(dp),              # lengths/starts (B,)
        ),
        out_specs=tuple(
            P(dp, *(sub(e) for e in tail)) for tail in out_spec_tails
        ),
        check_vma=False,
    )


def merge_partials(a, b):
    """Two ``(acc, m, l)`` partials of one softmax as one: the associative
    online-softmax merge (a side that attended nothing has ``m <= NEG_INF``
    and weighs 0)."""
    (acc, m, l), (acc2, m2, l2) = a, b
    m_new = jnp.maximum(m, m2)
    shift = jnp.where(m_new <= NEG_INF, 0.0, m_new)
    a1 = jnp.exp(jnp.where(m <= NEG_INF, NEG_INF, m - shift))
    a2 = jnp.exp(jnp.where(m2 <= NEG_INF, NEG_INF, m2 - shift))
    return acc * a1[..., None] + acc2 * a2[..., None], m_new, l * a1 + l2 * a2


def merge_partial_attention(
    parts: list[tuple[jax.Array, jax.Array, jax.Array]],
) -> jax.Array:
    """Combine per-segment ``(acc, m, l)`` partials into normalised attention
    output (:func:`merge_partials`, then the division by the sum)."""
    acc, m, l = parts[0]
    for part in parts[1:]:
        acc, m, l = merge_partials((acc, m, l), part)
    inv = jnp.where(l > 0.0, 1.0 / jnp.maximum(l, 1e-30), 0.0)
    return acc * inv[..., None]
