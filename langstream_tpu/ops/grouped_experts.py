"""The routed experts over rows in expert-contiguous order, one kernel a
layer (Pallas TPU).

What one step of :func:`langstream_tpu.models.moe.dropless_experts_grouped`'s
XLA loop computes for a block of rows, ``act(x W_up^T) W_down`` with ONE
expert's weights, for every block of a prefill's sorted rows in one call.
The caller has permuted the rows once (``xs``: the pairs sorted by held
expert, expert ``e``'s run at rows ``[starts[e], starts[e] + counts[e])``)
and permutes the result back once; nothing is gathered or scattered here.

The rows are walked in tiles of ``tile_rows``. A grid step is one (tile,
expert) pair whose run overlaps the tile: scalar-prefetched tables give each
step its tile, its expert and the rows ``[lo, hi)`` of the tile that are the
expert's. A tile that two runs share is visited once by each, back to back:
its block of the result stays in VMEM between the visits, and a visit stores
its own rows alone. A visit walks its rows in products of ``sub_rows`` from
its first row on (rounded down to a register's sublanes), wherever in the
tile that is, so a run costs its rows and one ragged product at its end,
not one at each end. Steps past the true count (the static grid is ``tiles + held``) are
skipped and point at the last live step's blocks, so nothing is fetched for
them: the work follows the pairs routed HERE.

Weights. ``w_up (layers, held, I or 2 I, H)`` and ``w_down (layers, held, I,
H)`` are read in place through the index map, ``[layer, expert]`` from the
prefetched scalars: no copy of a layer's held experts. Consecutive steps of
one expert find the block index unchanged and fetch nothing; the next
expert's weights are fetched under this step's products (the pipeline's
double buffer). Where an expert's weights are more than the buffers hold
(:func:`plan`), the expert width ``I`` is cut in ``i_tiles`` tiles, the
second grid axis: a tile's part of the output projection is added into a
float32 block in VMEM, rounded once after the last. The tiles are walked
back and forth, so a step that follows one of the same expert starts with
the tile that is already there.

Precision, as the dense pass (``dropless_experts_dense``) has it on a TPU:
operands in the model's type, the input projection's float32 accumulation
rounded to the model's type, the activation on that (``models/moe.py``
``relu2`` and ``silu_gated``: in float32 with one rounding to the model's
type, as the TPU's fused XLA forms have it; interpreted, op for op in the
model's type, as the CPU's have it), the output projection's float32
accumulation (over ALL of ``I``) rounded to the model's type. A gated expert's ``[a | b] = x W_in`` is
one projection of ``2 I`` rows of ``W_in``, read as the two halves of each
tile of ``I``.

Shapes:
  xs      (P, H), P a multiple of ``tile_rows``     the model's type
  w_up    (layers, held, I or 2 I, H); w_down (layers, held, I, H)
  layer, steps, tile, expert, lo, hi               [scalar prefetch]
  -> ys (P, H) the model's type; rows of no run are never written

Grid ``(P // tile_rows + held, i_tiles)``, both sequential.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the activations the kernel knows, by the name ``models/moe.py``
#: ``EXPERT_ACTS`` gives them; a gated one reads two halves of ``w_up``
GATED = {"relu2": False, "silu_gated": True}

#: bytes of ONE buffer of an expert's weight tiles (the pipeline holds two):
#: the largest tile of ``I`` under it is taken
WEIGHT_TILE_BYTES = 24 * 1024 * 1024
#: what the kernel may take of a v5e core's 128 MiB of VMEM
VMEM_LIMIT_BYTES = 100 * 1024 * 1024


#: rows of a tile (a grid step's block of the sorted rows and of the result)
#: and of one product inside it. Measured on the v5e at Mellum's layer (64
#: experts of 3 x 896 x 2304, top 8; the kernel alone, ms a call at 1,024 /
#: 2,048 / 4,096 rows, PR 47, with the products at fixed places in the
#: tile): 512 x 128 1.42 / 1.94 / 3.19, 256 x 128 1.52 / 1.99 / 3.20, 512 x
#: 256 1.72 / 2.34 / 3.45, 256 x 256 1.74 / 2.34 / 3.44, 128 x 128 1.77 /
#: 2.31 / 3.66, 512 x 512 2.72 / 3.24 / 4.29: a run's ragged end costs a
#: product, so the smallest product the MXU runs at its rate wins at every
#: row count, and four of them share a grid step's cost. The products have
#: walked from the run's first row since (1.30 and 2.76 ms at 1,024 and
#: 4,096 rows; at a share of 16 of 128, 192 rows a run, 0.58 for 0.98)
TILE_ROWS = 512
SUB_ROWS = 128


def plan(hidden: int, inter: int, gated: bool, itemsize: int) -> dict:
    """``{"tile_rows", "sub_rows", "i_tiles"}`` from the widths the pass is
    handed. ``i_tiles``: the fewest tiles of ``I``, each a multiple of 128
    lanes, whose weights fit :data:`WEIGHT_TILE_BYTES`; one, whatever it
    weighs, where ``I`` has no such divisor."""
    per_row = (3 if gated else 2) * hidden * itemsize
    i_tiles = next(
        (n for n in range(1, inter // 128 + 1)
         if inter % n == 0 and (inter // n) % 128 == 0
         and (inter // n) * per_row <= WEIGHT_TILE_BYTES), 1)
    return {"tile_rows": TILE_ROWS, "sub_rows": SUB_ROWS, "i_tiles": i_tiles}


def step_tables(starts: jax.Array, counts: jax.Array, rows: int,
                tile_rows: int) -> tuple[jax.Array, ...]:
    """The grid's tables for runs ``[starts[e], starts[e] + counts[e])`` of
    ``rows`` sorted rows: ``(steps (1,), tile, expert, lo, hi)``, the last
    four ``(rows // tile_rows + held,)`` int32. Step ``b`` computes rows
    ``[lo[b], hi[b])`` of tile ``tile[b]`` with expert ``expert[b]``; a step
    past ``steps`` repeats the last live one's tile and expert."""
    held = counts.shape[0]
    R = tile_rows
    ends = starts + counts
    first_tile = starts // R
    tiles_of = jnp.where(counts > 0, (ends - 1) // R - first_tile + 1, 0)
    step_end = jnp.cumsum(tiles_of)
    steps = step_end[-1]
    b = jnp.minimum(jnp.arange(rows // R + held), jnp.maximum(steps - 1, 0))
    e = jnp.minimum(
        jnp.searchsorted(step_end, b, side="right"), held - 1).astype(jnp.int32)
    tile = jnp.clip(
        first_tile[e] + b - (step_end[e] - tiles_of[e]), 0, rows // R - 1)
    lo = jnp.clip(starts[e] - tile * R, 0, R)
    hi = jnp.clip(ends[e] - tile * R, 0, R)
    i32 = lambda a: a.astype(jnp.int32)  # noqa: E731
    return i32(steps)[None], i32(tile), e, i32(lo), i32(hi)


def _weight_tile(b, j, steps_ref, i_tiles: int):
    """The tile of ``I`` that step ``(b, j)`` reads: forth on even steps,
    back on odd ones; a skipped step stays on the last live step's last."""
    if i_tiles == 1:
        return 0
    last = steps_ref[0] - 1
    live = b <= last
    bb = jnp.where(live, b, last)
    jj = jnp.where(live, j, i_tiles - 1)
    return jnp.where(bb % 2 == 0, jj, i_tiles - 1 - jj)


#: a product's first row inside its tile is a multiple of this: the
#: sublanes one register of the narrowest served type (bfloat16) packs
ROW_ALIGN = 16


def _kernel(layer_ref, steps_ref, tile_ref, expert_ref, lo_ref, hi_ref,
            x_ref, *refs, gated: bool, sub: int, i_tiles: int,
            exact_ops: bool):
    f32 = jnp.float32
    n_w = 3 if gated else 2
    w_refs, o_ref = refs[:n_w], refs[n_w]
    acc_ref = refs[n_w + 1] if i_tiles > 1 else None
    b, j = pl.program_id(0), pl.program_id(1)
    lo, hi = lo_ref[b], hi_ref[b]
    R = x_ref.shape[0]
    dt = o_ref.dtype
    nt = (((1,), (1,)), ((), ()))   # x (rows, H) . w (I, H)^T
    first = lo // ROW_ALIGN * ROW_ALIGN

    def product(i, _):
        # rows [at, at + sub) of the run, read where the tile has them: the
        # last product of a tile is pulled back inside it, and the rows it
        # shares with the one before are that one's
        at = first + i * sub
        r0 = pl.multiple_of(jnp.minimum(at, R - sub), ROW_ALIGN)
        rows = pl.ds(r0, sub)
        row = r0 + jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
        mine = (row >= jnp.maximum(lo, at)) & (row < hi)
        x = x_ref[rows, :]
        if gated:
            a = jax.lax.dot_general(
                x, w_refs[0][...], nt, preferred_element_type=f32).astype(dt)
            g = jax.lax.dot_general(
                x, w_refs[1][...], nt, preferred_element_type=f32).astype(dt)
            # the model's ops on the model's type where the interpreter runs
            # them (what the CPU's XLA forms compute, rounding for rounding:
            # an engine's program there gives its model function's tokens);
            # compiled, float32 and one rounding, as the TPU's fused XLA
            # forms keep it (and Mosaic has no bfloat16 logistic)
            mid = (jax.nn.silu(a) * g if exact_ops else
                   (jax.nn.silu(a.astype(f32)) * g.astype(f32)).astype(dt))
        else:
            up = jax.lax.dot_general(
                x, w_refs[0][...], nt, preferred_element_type=f32).astype(dt)
            mid = jnp.square(jax.nn.relu(up))
        down = jnp.dot(mid, w_refs[-1][...], preferred_element_type=f32)
        if i_tiles > 1:
            # this tile of I's part, added to the others' in float32
            down = jnp.where(j == 0, down, acc_ref[rows, :] + down)
            acc_ref[rows, :] = jnp.where(mine, down, acc_ref[rows, :])

        @pl.when(j == i_tiles - 1)
        def _():
            o_ref[rows, :] = jnp.where(mine, down.astype(dt), o_ref[rows, :])

    @pl.when(b < steps_ref[0])
    def _():
        jax.lax.fori_loop(0, pl.cdiv(hi - first, sub), product, None)


def grouped_experts(
    xs: jax.Array,        # (P, H): the rows in expert-contiguous order
    w_up: jax.Array,      # (layers, held, I or 2 I, H)
    w_down: jax.Array,    # (layers, held, I, H)
    layer: jax.Array,     # () int32
    starts: jax.Array,    # (held,) int32: the first row of each expert's run
    counts: jax.Array,    # (held,) int32: its rows
    *,
    act: str,             # a key of GATED
    tile_rows: int,
    sub_rows: int,
    i_tiles: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """``ys (P, H)``: row ``r`` of expert ``e``'s run is ``act(xs[r]
    w_up[layer, e]^T) w_down[layer, e]``. Rows in no run hold whatever was
    there: a caller reads the runs' rows alone."""
    P, H = xs.shape
    held, inter = w_down.shape[1], w_down.shape[2]
    gated = GATED[act]
    R, ti = tile_rows, inter // i_tiles
    if P % R or R % sub_rows or inter % i_tiles:
        raise ValueError(
            f"grouped_experts: {P} rows in tiles of {R}, sub-blocks of "
            f"{sub_rows}, {inter} in {i_tiles} tiles")
    if i_tiles > 1 and ti % 128:
        raise ValueError(f"grouped_experts: a tile of {ti} of I is no whole lanes")
    tables = step_tables(starts, counts, P, R)

    def weights(half):   # which half of a gated w_up's 2 I rows
        return pl.BlockSpec(
            (None, None, ti, H),
            lambda b, j, layer, steps, tile, expert, lo, hi: (
                layer[0], expert[b],
                half * i_tiles + _weight_tile(b, j, steps, i_tiles), 0))

    rows = pl.BlockSpec(
        (R, H), lambda b, j, layer, steps, tile, expert, lo, hi: (tile[b], 0))
    n_up = 2 if gated else 1
    itemsize = xs.dtype.itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(P // R + held, i_tiles),
        in_specs=[rows] + [weights(h) for h in range(n_up)] + [weights(0)],
        out_specs=rows,
        scratch_shapes=(
            [pltpu.VMEM((R, H), jnp.float32)] if i_tiles > 1 else []),
    )
    return pl.pallas_call(
        functools.partial(_kernel, gated=gated, sub=sub_rows, i_tiles=i_tiles,
                          exact_ops=interpret),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, H), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            # a tile's visits in order: its block of the result stays resident
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        cost_estimate=pl.CostEstimate(   # of P live rows: an upper bound
            flops=2 * P * (n_up + 1) * inter * H,
            transcendentals=P * inter if gated else 0,
            bytes_accessed=(2 * P * H + held * (n_up + 1) * inter * H) * itemsize,
        ),
        interpret=interpret,
        name="grouped_experts",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), *tables,
        xs, *([w_up] * n_up), w_down,
    )
