"""The commit of new rows into the paged pools of one kind (Pallas TPU), in
place.

``models/paged.py`` ``write_rows_pair`` puts a program's new K and V (or
latent) rows where each slot's block table says. As XLA scatters along the
folded pools' leading axis (``"xla"``: the CPU path, every int8 pool, any
mesh, and the tests' reference) the compiler issues a row at a time, 130 ns a
row whatever the row holds; the bytes are 2% of that. This kernel
(``"pallas"``) moves **runs of rows**: the pools stay in HBM and are their own
outputs (``input_output_aliases``), so nothing of them moves but the rows
committed. The pools of a kind (K and V) share tables, starts and interval,
so they are committed by ONE call: one trace and one Mosaic module a kind a
program. A program's first warm use IS its trace and lowering, at every
set-up of every cell, so what is traced here is kept small: no plan outside
the kernel (a tile's fields are scalar arithmetic on the prefetched
``tables``, ``starts``, ``lo``, ``hi``), no Python loop over layers, tiles or
buffers, and only the form the call site's ``starts`` need.

What it uses, all read off its arguments:

- every caller's ``valid`` is ONE interval of rows a slot (a prefill's
  ``[0, length)``, the window kind's ``[length - W, length)``, all of an
  active slot's rows in a decode chunk, none of an idle one's), handed here
  as ``lo`` / ``hi``; rows outside it are skipped, not sent to scratch;
- a position addresses the same row of every layer, so one strided copy
  carries a run's rows of ALL layers: ``rows[:, b, t0:t0+n]`` to
  ``pool[:, block, r0:r0+n]``;
- a block holds a whole number of row tiles. A **tile** is the rows of one
  32-bit sublane group: 8 rows of a 32-bit pool, 16 of a 16-bit one, which
  packs two rows a word. A copy can address whole tiles only;
- ``starts`` that the site states to be zero (``None``: every prefill):
  a tile of ``rows`` then IS a tile of the pool.

So the walk is over the **destination tiles** a slot's rows can touch, every
slot's in one loop, each written at most once, in the one of two forms the
site's ``starts`` ask for:

- **aligned** (``starts`` None: a tile of ``rows`` IS a tile of the pool). A
  tile the interval covers is a **direct** copy, HBM to HBM, one a pool,
  :data:`DIRECT_IN_FLIGHT` tiles flying. An edge of the interval (two a slot
  at most) is a **merge**: the tile of ``rows`` and the pool's tile come to
  VMEM, the rows inside the interval replace the pool's as 32-bit words under
  one mask, and the tile goes back, at once;
- **shifted** (a decode chunk, a continuation: a ``start`` anywhere). Every
  tile is a merge of the TWO tiles of ``rows`` that hold its rows (of the one
  tile, where the rows are no more: a chunk of 8 steps), moved into place
  first (a rotate along sublanes by ``shift // packing`` words; for an odd
  shift of a 16-bit pool each word takes the high half of one word and the
  low half of the next). :data:`MERGE_BUFFERS` tiles are in flight: the
  next one's loads and the last one's store fly while this one is merged. A
  ``start`` that happens to lie on a tile's edge takes the same path (a
  rotate by nothing): a second path would be traced into every decode
  program for one start in sixteen.

The pools after a commit are bit for bit what the scatter leaves in every
block but the scratch block 0, which nothing reads.

Shapes:
  pools   P x (L, nb, bs, tail)   [HBM; each aliased to its output]
  rows    P x (L, B, T, tail)     [HBM; T padded to whole tiles here]
  tables (B, columns), starts, lo, hi (B,) int32   [scalar prefetch]
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: direct tiles flying at once (their copies share one semaphore: equal sizes)
DIRECT_IN_FLIGHT = 8
#: shifted merge tiles in flight: one loading, one merged, one stored
MERGE_BUFFERS = 3
#: sublanes of a 32-bit tile: a merge works on this many words of a lane
_WORDS = 8
#: what the shifted walk carries of a destination tile from the step that
#: starts its loads to the step that merges it (:func:`_commit_kernel`)
_FIELDS = ("merge", "slot", "row0", "row1", "block", "at", "shift", "lo", "hi")
#: scoped VMEM a kernel gets without asking (v5e); the buffers of a deep
#: pool ask for more
_DEFAULT_VMEM = 16 * 1024 * 1024


def tile_rows(dtype) -> int:
    """Rows of one copyable tile of a pool of ``dtype``: 8 sublanes of
    32-bit words, ``packing`` rows a word (0: not a type the kernel moves)."""
    size = jnp.dtype(dtype).itemsize
    return _WORDS * (4 // size) if size in (2, 4) else 0


def commit_form(kernel: str, pool) -> str:
    """The form ``write_rows`` takes for ``pool`` under the engine's one
    kernel selection: the selection itself where the kernel can move this
    pool (an array of 16- or 32-bit rows whose blocks are whole tiles and,
    compiled, whose rows are whole lane tiles), ``"xla"`` otherwise (an int8
    ``{"q", "s"}`` pool, a block shorter than a tile)."""
    if kernel == "xla" or isinstance(pool, dict):
        return "xla"
    if kernel not in ("pallas", "pallas-interpret"):
        raise ValueError(f"unknown commit kernel {kernel!r}")
    tile = tile_rows(pool.dtype)
    lanes_ok = kernel == "pallas-interpret" or pool.shape[3] % 128 == 0
    return kernel if tile and pool.shape[2] % tile == 0 and lanes_ok else "xla"


def _commit_kernel(*refs, pools: int, tile: int, aligned: bool):
    """``refs``: tables (B, columns), [starts (B,) unless ``aligned``], lo,
    hi (B,) in SMEM; ``pools`` rows arrays (L, B, T, tail) and as many pools
    (L, nb, bs, tail) in HBM, the pools again as outputs (aliased); then
    ``buf`` VMEM (buffers, pools * L, (reads + 1) * tile, tail), a merge's
    ``reads`` tiles of rows and under them the pool's tile, and the DMA
    semaphores of the loads (buffers,), the stores (buffers,) and the direct
    copies ()."""
    P = pools
    tables_ref, *scalars = refs[:3 if aligned else 4]
    starts_ref = None if aligned else scalars[0]
    lo_ref, hi_ref = scalars[-2:]
    refs = refs[len(scalars) + 1:]
    rows_refs, pool_refs = refs[:P], refs[2 * P:3 * P]
    buf, load_sems, store_sems, direct_sem = refs[3 * P:]
    L, B, T, tail = rows_refs[0].shape
    bs = pool_refs[0].shape[2]
    columns = tables_ref.shape[1]
    packing = tile // _WORDS
    # tiles of rows under one pool tile: two where a ``start`` lies inside a
    # tile, unless the rows are one tile in all (a chunk of 16 steps or
    # fewer), which then lies under both of the pool's it can touch
    reads = 1 if aligned or T == tile else 2
    # the most tiles a slot's rows touch
    per_slot = T // tile + (0 if aligned else 1)
    total = B * per_slot
    buffers = buf.shape[0]
    i32, u32 = jnp.int32, jnp.uint32

    # scalar arithmetic through ``lax`` itself: an operator on a traced value
    # is a jitted ``jnp`` function, 1 ms of trace each, and a program's trace
    # is paid at every set-up
    add, sub, mul, div, rem = lax.add, lax.sub, lax.mul, lax.div, lax.rem
    both, lt, le, gt, ge = lax.bitwise_and, lax.lt, lax.le, lax.gt, lax.ge

    def fields(n):
        """Destination tile ``n`` of the walk, slot ``n // per_slot``'s
        ``n % per_slot``-th from the tile its row 0 lies in: whether it is a
        ``direct`` copy (aligned alone) or a ``merge`` (neither: outside the
        interval or past the walk's end); its ``slot``; where in ``rows`` the
        tiles begin that hold its rows, ``row0`` and (shifted) ``row1``,
        clamped into ``rows`` (what a clamp brings lies outside the
        interval); its ``block`` and first row ``at`` in it; the rows
        ``shift`` the first tile of ``rows`` is moved up by; the interval in
        rows of the tile, ``lo`` to ``hi`` (either may reach outside)."""
        m = lax.min(n, total - 1)
        b = div(m, per_slot)
        q = sub(m, mul(b, per_slot))          # the slot's tile, from 0
        lo, hi = lo_ref[b], hi_ref[b]
        whole = lambda r: pl.multiple_of(r, tile)  # noqa: E731
        if aligned:
            r0 = mul(q, tile)
            t = dict(row0=whole(r0))
        else:
            start = starts_ref[b]
            up = sub(mul(add(q, 1), tile), rem(start, tile))
            r0 = sub(up, tile)                # the row of ``rows`` at row 0
            q = add(div(start, tile), q)
            first = sub(div(up, tile), 1)     # -1: ``start`` inside the tile
            # (a live tile's ``first`` is -1 at least, and its last row in
            # ``rows``; what a clamp brings lies outside the interval)
            row0 = whole(mul(lax.max(first, 0), tile))
            t = dict(row0=row0, shift=rem(up, tile), row1=row0 if reads == 1
                     else whole(mul(lax.min(add(first, 1), T // tile - 1), tile)))
        end = up if not aligned else add(r0, tile)
        live = both(both(lt(n, total), gt(hi, lo)), both(lt(r0, hi), gt(end, lo)))
        if aligned:
            direct = both(live, both(ge(r0, lo), le(end, hi)))
            t.update(direct=direct, merge=both(live, lax.bitwise_not(direct)))
        else:
            t.update(merge=live)
        return dict(
            t, slot=b,
            block=tables_ref[b, lax.min(div(q, bs // tile), columns - 1)],
            at=whole(mul(rem(q, bs // tile), tile)),
            lo=sub(lo, r0), hi=sub(hi, r0))

    def rows_tile(p, t, which):
        return rows_refs[p].at[:, t["slot"], pl.ds(t[which], tile), :]

    def pool_tile(p, t):
        return pool_refs[p].at[:, t["block"], pl.ds(t["at"], tile), :]

    def merged(k):
        """The part of buffer ``k`` that holds the pools' tile, every
        layer's, of every pool."""
        return buf.at[k, :, pl.ds(reads * tile, tile), :]

    def of_pool(buffer, p):
        return buffer.at[pl.ds(p * L, L)]

    def loads(t, k):
        """Start the copies a merge waits for: the tiles of ``rows`` that
        hold its rows and the pools' tiles."""
        for p in range(P):
            for half, which in enumerate(("row0", "row1")[:reads]):
                pltpu.make_async_copy(
                    rows_tile(p, t, which),
                    of_pool(buf.at[k], p).at[:, pl.ds(half * tile, tile), :],
                    load_sems.at[k]).start()
            pltpu.make_async_copy(
                pool_tile(p, t), of_pool(merged(k), p), load_sems.at[k]).start()

    def stores(t, k):
        for p in range(P):
            pltpu.make_async_copy(
                of_pool(merged(k), p), pool_tile(p, t), store_sems.at[k]).start()

    def directs(t):
        for p in range(P):
            pltpu.make_async_copy(
                rows_tile(p, t, "row0"), pool_tile(p, t), direct_sem).start()

    def landed(sem, there):
        """Wait on ``sem`` for as many bytes as ``there`` holds: the copies
        started on it are told by their sizes alone, all of a step's by
        their sum."""
        pltpu.make_async_copy(there, there, sem).wait()

    def merge(t, k):
        """The pools' tile in buffer ``k`` takes the interval's rows of the
        tiles of ``rows`` above it, moved into place, as 32-bit words a
        lane; every layer of every pool alike."""
        word = lax.broadcasted_iota(i32, (_WORDS, tail), 0)
        bits = 32 // packing
        none = lax.full((_WORDS, tail), 0, u32)
        row = mul(word, packing)
        for half in range(packing):     # the rows of a word, low bits first
            inside = lax.select(
                both(ge(row, t["lo"]), lt(row, t["hi"])),
                lax.full_like(none, ((1 << bits) - 1) << (half * bits)), none)
            keep = lax.bitwise_or(keep, inside) if half else inside
            row = add(row, 1)
        lose = lax.bitwise_not(keep)
        if not aligned:
            # (rows of one tile in all are rotated within it: the row under
            # the pool's row j is row (shift + j) % tile of it either way)
            span = reads * _WORDS
            up = rem(sub(span, div(t["shift"], packing)), span)
            after = rem(add(up, span - 1), span)
            odd = lax.eq(lax.full_like(word, 1), rem(t["shift"], packing))

        def layer(l, carry):
            new = pltpu.bitcast(                       # (reads * 8, tail)
                buf[k, l, pl.ds(0, reads * tile)], u32)
            old = pltpu.bitcast(                       # (8, tail)
                buf[k, l, pl.ds(reads * tile, tile)], u32)
            if not aligned:
                put = pltpu.roll(new, up, axis=0)[:_WORDS]
                if packing == 2:
                    # an odd shift: a word's rows are the high half of one
                    # word of ``new`` and the low half of the next
                    low = pltpu.roll(new, after, axis=0)[:_WORDS]
                    put = lax.select(odd, lax.bitwise_or(
                        lax.shift_right_logical(put, lax.full_like(put, 16)),
                        lax.shift_left(low, lax.full_like(low, 16))), put)
                new = put
            buf[k, l, pl.ds(reads * tile, tile)] = pltpu.bitcast(
                lax.bitwise_or(both(new, keep), both(old, lose)), buf.dtype)
            return carry

        lax.fori_loop(0, P * L, layer, 0)

    if aligned:
        def step(n, flying):
            t = fields(n)

            @pl.when(t["merge"])
            def _():        # an edge of the interval: few, so done at once
                loads(t, 0)
                landed(load_sems.at[0], buf.at[0])
                merge(t, 0)
                stores(t, 0)
                landed(store_sems.at[0], merged(0))

            @pl.when(t["direct"])
            def _():
                @pl.when(ge(flying, DIRECT_IN_FLIGHT))
                def _():
                    landed(direct_sem, merged(0))

                directs(t)

            return lax.min(
                add(flying, lax.convert_element_type(t["direct"], i32)),
                DIRECT_IN_FLIGHT)

        def drained(_, carry):
            landed(direct_sem, merged(0))
            return carry

        lax.fori_loop(0, lax.fori_loop(0, total, step, i32(0)), drained, 0)
        return

    def step(j, carry):
        # step j works on tile n = j - buffers (the first on tile -1,
        # nothing, so that no ``rem`` sees a negative number). Tile n + 1
        # loads into the buffer of tile n + 1 - buffers, free once that
        # tile's store has landed; the walk's last stores land in the steps
        # past its end, which do nothing else
        t, stored = carry
        k_next = rem(add(j, 1), buffers)

        @pl.when(stored[0])
        def _():
            landed(store_sems.at[k_next], merged(k_next))

        ahead = fields(sub(j, buffers - 1))

        @pl.when(ahead["merge"])
        def _():
            loads(ahead, k_next)

        k = rem(j, buffers)

        @pl.when(t["merge"])
        def _():
            landed(load_sems.at[k], buf.at[k])
            merge(t, k)
            # (what the carry brought has lost what ``fields`` said of it)
            stores(dict(t, at=pl.multiple_of(t["at"], tile)), k)

        return ahead, stored[1:] + (t["merge"],)

    nothing = {f: False if f == "merge" else i32(0) for f in _FIELDS}
    lax.fori_loop(buffers - 1, total + 2 * buffers - 1, step,
                  (nothing, (False,) * (buffers - 1)))


def pool_commit(
    pools: tuple,              # P pools of one kind, each (L, nb, bs, tail)
    rows: tuple,               # P arrays (L, B, T, tail), a pool's new rows
    block_tables: jax.Array,   # (B, columns) int32
    starts,                    # (B,) the sequence position of rows[:, b, 0];
                               # None: zero, every slot's (a prefill)
    lo: jax.Array,             # (B,) first row of a slot's interval
    hi: jax.Array,             # (B,) one past its last row (<= lo: none)
    *,
    interpret: bool = False,
) -> tuple:
    """``pools`` with ``rows[p][:, b, lo[b]:hi[b]]`` at slot ``b``'s positions
    ``starts[b] + lo[b] ...`` of pool ``p``, every other row of them
    untouched (the pools are donated to the call: in place). A position past
    the table's last column lands in that column's block, as the scatter's
    clamp has it. ``starts`` None is the site's statement that its rows begin
    at position 0 (a prefill): only the aligned form is traced."""
    P = len(pools)
    pool = pools[0]
    L, nb, bs, tail = pool.shape
    T = rows[0].shape[2]
    tile = tile_rows(pool.dtype)
    if not tile or bs % tile or any(
            r.dtype != pool.dtype for r in rows) or any(
            p.shape != pool.shape or p.dtype != pool.dtype for p in pools):
        raise ValueError(
            f"pool_commit cannot move {[p.dtype for p in pools]} pools of "
            f"{[p.shape for p in pools]} ({[r.dtype for r in rows]} rows): "
            f"see commit_form")
    if T % tile:
        # a chunk shorter than a tile (8 steps of a 16-bit pool): its rows
        # are few; the padding lies outside every interval
        rows = tuple(
            jnp.pad(r, ((0, 0), (0, 0), (0, -T % tile), (0, 0))) for r in rows)
    aligned = starts is None
    buffers = 1 if aligned else MERGE_BUFFERS
    reads = 1 if aligned or rows[0].shape[2] == tile else 2
    held = buffers * P * L * (reads + 1) * tile * tail * pool.dtype.itemsize
    scalars = [a.astype(jnp.int32) for a in (
        (block_tables, lo, hi) if aligned
        else (block_tables, starts, lo, hi))]
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(1,),
        in_specs=[anywhere] * (2 * P),
        out_specs=[anywhere] * P,
        scratch_shapes=[
            pltpu.VMEM((buffers, P * L, (reads + 1) * tile, tail), pool.dtype),
            pltpu.SemaphoreType.DMA((buffers,)),
            pltpu.SemaphoreType.DMA((buffers,)),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    return tuple(pl.pallas_call(
        functools.partial(
            _commit_kernel, pools=P, tile=tile, aligned=aligned),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # operands: the scalars, the rows, the pools
        input_output_aliases={len(scalars) + P + p: p for p in range(P)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(
                None if 2 * held <= _DEFAULT_VMEM
                else held + _DEFAULT_VMEM // 2),
        ),
        interpret=interpret,
        name="pool_commit",
    )(*scalars, *rows, *pools))
