"""Paged KV cache: block manager, pool read/write, paged-vs-dense model
equivalence, Pallas kernel (interpret) vs XLA reference."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def greedy_sample(logits, key):
    t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return t, jnp.zeros_like(t, dtype=jnp.float32)


# ---------------------------------------------------------------------------
# BlockManager
# ---------------------------------------------------------------------------


def test_block_manager_reservation_and_release():
    from langstream_tpu.models.paged import BlockManager, PagedLayout

    layout = PagedLayout(block_size=16, num_blocks=9, max_blocks_per_slot=4)
    mgr = BlockManager(layout, slots=4)
    # 8 usable blocks (block 0 is scratch)
    assert mgr.can_admit(64)          # 4 blocks
    mgr.admit(0, 64)
    assert mgr.can_admit(64)
    mgr.admit(1, 64)
    assert not mgr.can_admit(16)      # 8 reserved, 0 left
    # lazy physical growth
    assert mgr.ensure_capacity(0, 20)  # 2 blocks
    assert mgr.stats()["live_blocks"] == 2
    assert (mgr.tables[0, :2] > 0).all()
    assert mgr.ensure_capacity(0, 64)
    assert mgr.stats()["live_blocks"] == 4
    # release frees blocks and reservation
    mgr.release(0)
    assert mgr.stats()["live_blocks"] == 0
    assert mgr.can_admit(64)
    # per-slot cap enforced
    assert not mgr.can_admit(layout.block_size * 5)


def test_block_manager_rejects_overlong():
    from langstream_tpu.models.paged import BlockManager, PagedLayout

    layout = PagedLayout.for_model(max_seq_len=128, slots=4, block_size=32)
    assert layout.max_blocks_per_slot == 4
    mgr = BlockManager(layout, slots=4)
    assert not mgr.can_admit(129)


# ---------------------------------------------------------------------------
# pool write/read round trip
# ---------------------------------------------------------------------------


def test_write_rows_and_gather_roundtrip():
    from langstream_tpu.models.paged import (
        BlockManager,
        PagedLayout,
        gather_kv,
        init_paged_kv_cache,
        write_rows,
    )
    from langstream_tpu.models.llama import LlamaConfig

    c = LlamaConfig.tiny(max_seq_len=64)
    layout = PagedLayout.for_model(64, slots=2, block_size=8, num_blocks=17)
    pool, _ = init_paged_kv_cache(c, layout)
    mgr = BlockManager(layout, slots=2)
    mgr.admit(0, 20)
    mgr.admit(1, 12)
    mgr.ensure_capacity(0, 20)   # 3 blocks
    mgr.ensure_capacity(1, 12)   # 2 blocks
    tables = jnp.asarray(mgr.tables)

    KhD = c.kv_heads * c.head_dim
    rows = jax.random.normal(
        jax.random.PRNGKey(0), (c.layers, 2, 20, KhD), dtype=c.dtype
    )
    valid = jnp.array(
        [[True] * 20, [True] * 12 + [False] * 8]
    )
    pool = write_rows(pool, rows, tables, jnp.zeros(2, jnp.int32), valid)
    dense = gather_kv(pool, tables, num_read_blocks=3)  # (L, 2, 24, KhD)
    np.testing.assert_array_equal(
        np.asarray(dense[:, 0, :20]), np.asarray(rows[:, 0])
    )
    np.testing.assert_array_equal(
        np.asarray(dense[:, 1, :12]), np.asarray(rows[:, 1, :12])
    )
    # appending at an offset (decode commit shape)
    more = jax.random.normal(
        jax.random.PRNGKey(1), (c.layers, 2, 4, KhD), dtype=c.dtype
    )
    mgr.ensure_capacity(1, 16)
    tables = jnp.asarray(mgr.tables)
    pool = write_rows(
        pool, more, tables,
        jnp.array([20, 12], jnp.int32), jnp.ones((2, 4), bool),
    )
    dense = gather_kv(pool, tables, num_read_blocks=3)
    np.testing.assert_array_equal(
        np.asarray(dense[:, 1, 12:16]), np.asarray(more[:, 1])
    )
    np.testing.assert_array_equal(  # earlier rows undisturbed
        np.asarray(dense[:, 0, :20]), np.asarray(rows[:, 0])
    )


# ---------------------------------------------------------------------------
# the commit: one scatter with the layer in the row index, held bit for bit
# to the form it replaced
# ---------------------------------------------------------------------------


def a_pool_at_a_time(commit):
    """A one-pool reference ``commit`` in ``write_rows_pair``'s place in a
    program: the pools of a kind in turn, each one's rows drawn right before
    its commit (as the scatter form does), a prefill's ``starts`` None as the
    zeros they state."""
    def pair(caches, rows, block_tables, starts, valid, kernel="xla"):
        if starts is None:
            starts = jnp.zeros((valid.shape[0],), jnp.int32)
        return tuple(commit(cache, new, block_tables, starts, valid)
                     for cache, new in zip(caches, rows))

    return pair


def reference_write_rows(cache, rows, block_tables, starts, valid,
                         kernel="xla"):
    """The commit as the parent (8e35ac5) wrote it, the plain reference
    (whatever selection the caller hands down):
    the pool seen as ``(L, nb*bs, tail)`` and scattered along its SECOND
    axis. Same rows to the same places; on the chip the compiler moved the
    layer axis inward and back out around it, a copy of the whole pool each
    way."""
    from langstream_tpu.models.kvquant import quantize_rows

    quant = isinstance(cache, dict)
    nb, bs, KhD = (cache["q"] if quant else cache).shape[1:]
    rows_data = rows["q"] if isinstance(rows, dict) else rows
    B, T = rows_data.shape[1], rows_data.shape[2]
    pos = starts[:, None] + jnp.arange(T)[None, :]
    block_idx = jnp.clip(pos // bs, 0, block_tables.shape[1] - 1)
    blocks = jnp.take_along_axis(block_tables, block_idx, axis=1)
    flat = jnp.where(valid, blocks * bs + pos % bs, 0).reshape(-1)

    def scatter(pool, new_rows):
        L = new_rows.shape[0]
        tail = pool.shape[3:]
        flat_cache = pool.reshape((L, nb * bs) + tail)
        flat_rows = new_rows.reshape((L, B * T) + tail)
        return flat_cache.at[:, flat].set(flat_rows).reshape(pool.shape)

    if not quant:
        return scatter(cache, rows)
    if isinstance(rows, dict):
        return {"q": scatter(cache["q"], rows["q"]),
                "s": scatter(cache["s"], rows["s"])}
    L = rows.shape[0]
    Kh = cache["s"].shape[3]
    q = quantize_rows(rows.reshape(L, B, T, Kh, KhD // Kh))
    return {"q": scatter(cache["q"], q["q"].reshape(L, B, T, KhD)),
            "s": scatter(cache["s"], q["s"])}


_COMMIT_BS, _COMMIT_NB, _COMMIT_COLS, _COMMIT_KH, _COMMIT_D = 8, 9, 3, 2, 16

# starts (B,), T, valid (B, T): two slots of three table columns of 8 rows
_COMMITS = {
    # a decode chunk's commit: each slot appends from inside a block
    "mid-block": ([3, 13], 4, np.ones((2, 4), bool)),
    # rows on both sides of a block edge, one slot across two of them
    "block-edge": ([6, 7], 11, np.ones((2, 11), bool)),
    # a padded prefill bucket and an idle slot: nine rows go to the scratch
    # row at once, and none of them may reach a live row
    "invalid-rows": ([0, 9], 6, np.array([[True] * 3 + [False] * 3,
                                          [False] * 6])),
    # positions past the table's last column (24 rows): invalid, and the
    # clamp keeps the lookup of their block inside the table
    "past-the-table": ([20, 23], 9, np.array([[True] * 4 + [False] * 5,
                                              [True] + [False] * 8])),
}


def _commit_case(kind, L, seed):
    """A pool with something in every row, rows to commit and the tables."""
    rng = np.random.default_rng(seed)
    KhD = _COMMIT_KH * _COMMIT_D
    shape = (L, _COMMIT_NB, _COMMIT_BS)
    if kind == "bf16":
        pool = jnp.asarray(rng.normal(size=shape + (KhD,)), jnp.bfloat16)
    else:
        pool = {
            "q": jnp.asarray(rng.integers(-127, 128, shape + (KhD,)), jnp.int8),
            "s": jnp.asarray(rng.uniform(0.01, 1.0, shape + (_COMMIT_KH,)),
                             jnp.float32),
        }
    tables = jnp.asarray([[4, 1, 7], [2, 8, 5]], jnp.int32)

    def rows(T):
        if kind == "int8-prequantised":
            return {
                "q": jnp.asarray(rng.integers(-127, 128, (L, 2, T, KhD)),
                                 jnp.int8),
                "s": jnp.asarray(rng.uniform(0.01, 1.0, (L, 2, T, _COMMIT_KH)),
                                 jnp.float32),
            }
        return jnp.asarray(rng.normal(size=(L, 2, T, KhD)), jnp.bfloat16)

    return pool, rows, tables


@pytest.mark.parametrize("L", [1, 3])
@pytest.mark.parametrize("commit", sorted(_COMMITS))
@pytest.mark.parametrize("kind", ["bf16", "int8", "int8-prequantised"])
def test_write_rows_writes_the_pool_the_parents_form_wrote(kind, commit, L):
    """Every bit of the pool (data and scales, the scratch block too) after
    the folded scatter is what the parent's scatter along the second axis
    left: bf16 pool, int8 pool quantising bf16 rows, int8 pool taking
    quantised rows verbatim."""
    from langstream_tpu.models.paged import write_rows

    starts, T, valid = _COMMITS[commit]
    pool, rows, tables = _commit_case(kind, L, seed=len(commit) + L)
    args = (pool, rows(T), tables, jnp.asarray(starts, jnp.int32),
            jnp.asarray(valid))
    got = jax.jit(write_rows)(*args)
    want = jax.jit(reference_write_rows)(*args)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w, before in zip(*map(jax.tree.leaves, (got, want, pool))):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        # and it did write (every commit here has a valid row)
        assert not np.array_equal(np.asarray(g), np.asarray(before))
    if kind == "int8-prequantised" and valid[0, 0]:
        # verbatim: slot 0's first row is the payload's, bit for bit
        block, row = int(tables[0, starts[0] // 8]), starts[0] % 8
        for leaf in ("q", "s"):
            np.testing.assert_array_equal(
                np.asarray(got[leaf][:, block, row]),
                np.asarray(args[1][leaf][:, 0, 0]))


# ---------------------------------------------------------------------------
# the commit's kernel (ops/pool_commit.py), interpreted, against the scatter
# ---------------------------------------------------------------------------


def _interval(B, T, lo, hi):
    t = np.arange(T)[None, :]
    return (t >= np.asarray(lo)[:, None]) & (t < np.asarray(hi)[:, None])


def _ring(blocks, cols):
    """A window kind's table: logical block ``n`` in ring block ``n % ring``."""
    return [[row[c % len(row)] for c in range(cols)] for row in blocks]


# dtype, block rows, row lanes, tables, starts, T, valid
_KERNEL_COMMITS = {
    # the scatter's four, as they are, on a float32 pool (a tile is 8 rows,
    # a block of 8 one tile) ...
    **{f"{name}-f32": ("float32", 8, 32, [[4, 1, 7], [2, 8, 5]], starts, T,
                       valid)
       for name, (starts, T, valid) in _COMMITS.items()},
    # ... and on a bfloat16 pool at twice the rows a block (a tile is 16)
    **{f"{name}-bf16": ("bfloat16", 16, 32, [[4, 1, 7], [2, 8, 5]],
                        [2 * s for s in starts], T, valid)
       for name, (starts, T, valid) in _COMMITS.items()},
    # the window kind's prefill: the last 128 rows of a prompt of 250, through
    # a ring of three blocks of 64 (an edge tile at either end, whole tiles
    # copied between them)
    "window-through-a-ring": (
        "bfloat16", 64, 128, _ring([[5, 2, 7]], 4), [0], 256,
        _interval(1, 256, [122], [250])),
    # a prefill of whole blocks and a ragged last one, beside a short one
    "prefill-ragged-last-block": (
        "bfloat16", 64, 128, [[3, 6, 1, 8], [2, 5, 4, 7]], [0, 0], 256,
        _interval(2, 256, [0, 0], [201, 17])),
    # a decode chunk with an idle slot between two live ones
    "idle-slot": (
        "bfloat16", 64, 128, [[3, 6], [2, 5], [4, 7]], [9, 40, 77], 32,
        _interval(3, 32, [0, 0, 0], [32, 0, 32])),
    # 8 and 32 rows across a block edge (8: less than a tile of the rows)
    "T8-across-a-block-edge": (
        "bfloat16", 64, 128, [[3, 6], [2, 5]], [60, 57], 8,
        np.ones((2, 8), bool)),
    "T32-across-a-block-edge": (
        "bfloat16", 64, 128, [[3, 6], [2, 5]], [50, 33], 32,
        np.ones((2, 32), bool)),
    # a start on a tile's edge: the rows' tiles ARE the pool's (direct)
    "T32-on-a-tile-edge": (
        "bfloat16", 64, 128, [[3, 6], [2, 5]], [16, 64], 32,
        np.ones((2, 32), bool)),
    # a run that starts and ends inside one 16-row tile (rows 69-75)
    "inside-one-tile": (
        "bfloat16", 64, 128, [[3, 6]], [67], 16, _interval(1, 16, [2], [9])),
    # a continuation: a start inside a tile and many tiles of rows
    "continuation": (
        "bfloat16", 64, 128, [[3, 6, 1, 8], [2, 5, 4, 7]], [13, 64], 96,
        _interval(2, 96, [0, 0], [96, 41])),
    # the served rows' widths: nemotron_h 256, Mellum 512, the latent pool's
    # 640, InternLM2 and Trinity 1,024
    **{f"tail-{tail}": (
        "bfloat16", 64, tail, [[3, 6], [2, 5]], [37, 90], 32,
        np.ones((2, 32), bool)) for tail in (256, 512, 640, 1024)},
}


@pytest.mark.parametrize("form", ["one", "pair", "aligned"])
@pytest.mark.parametrize("L", [1, 3])
@pytest.mark.parametrize("commit", sorted(_KERNEL_COMMITS))
def test_the_kernel_commits_what_the_scatter_commits(commit, L, form):
    """Under ``"pallas-interpret"`` the commit leaves every block but the
    scratch block 0 bit for bit as the scatter leaves it (the rows of the
    interval where the table says, every other row of every table's blocks
    and every block no table names as it was), and writes nothing of block
    0's neighbours: ``write_rows`` of ONE pool, ``write_rows_pair`` of K and
    V in one call (each pool its own rows), and the ALIGNED form of the pair
    (the case's intervals from position 0, ``starts`` handed as None, as
    every prefill hands them: whole tiles copied, an edge under one mask)."""
    from langstream_tpu.models.paged import write_rows, write_rows_pair
    from langstream_tpu.ops.pool_commit import commit_form

    dtype, bs, tail, tables, starts, T, valid = _KERNEL_COMMITS[commit]
    rng = np.random.default_rng(len(commit) + L)
    B, nb = len(tables), 9
    pools = tuple(jnp.asarray(rng.normal(size=(L, nb, bs, tail)), dtype)
                  for _ in range(1 if form == "one" else 2))
    rows = tuple(jnp.asarray(rng.normal(size=(L, B, T, tail)), dtype)
                 for _ in pools)
    tables, valid = jnp.asarray(tables, jnp.int32), jnp.asarray(valid)
    starts = None if form == "aligned" else jnp.asarray(starts, jnp.int32)
    assert commit_form("pallas-interpret", pools[0]) == "pallas-interpret"

    def commit_under(kernel):
        if form == "one":
            return jax.jit(lambda p, r: (write_rows(
                p, r, tables, starts, valid, kernel),))(pools[0], rows[0])
        return jax.jit(lambda p, r: write_rows_pair(
            p, iter(r), tables, starts, valid, kernel))(pools, rows)

    as_bits = lambda a: np.asarray(a).view(  # noqa: E731
        np.uint16 if dtype == "bfloat16" else np.uint32)
    named = {b for row in np.asarray(tables) for b in row}
    for got, want, pool in zip(
            commit_under("pallas-interpret"), commit_under("xla"), pools):
        assert got.dtype == pool.dtype and got.shape == pool.shape
        np.testing.assert_array_equal(as_bits(got)[:, 1:], as_bits(want)[:, 1:])
        for block in set(range(1, nb)) - named:
            np.testing.assert_array_equal(
                as_bits(got)[:, block], as_bits(pool)[:, block])
        if np.asarray(valid).any():
            assert not np.array_equal(
                as_bits(got)[:, 1:], as_bits(pool)[:, 1:])


def test_the_kernel_refuses_a_mask_with_a_gap_and_takes_the_pools_it_can():
    """The kernel commits ONE interval a slot: a mask it can read (not
    traced) with a gap is an error, not a wrong pool. The form follows the
    selection and the pool: an int8 pool, a block shorter than a tile and,
    compiled, a row that is not whole lane tiles keep the scatter; a mesh
    keeps it in the dense family's programs."""
    from langstream_tpu.models.llama_paged import _commit_kernel
    from langstream_tpu.models.paged import write_rows
    from langstream_tpu.ops.pool_commit import commit_form, tile_rows

    pool = jnp.zeros((1, 3, 16, 128), jnp.bfloat16)
    rows = jnp.ones((1, 1, 16, 128), jnp.bfloat16)
    tables, starts = jnp.asarray([[1, 2]], jnp.int32), jnp.zeros(1, jnp.int32)
    gap = jnp.asarray([[True] * 4 + [False] * 2 + [True] * 10])
    with pytest.raises(ValueError, match="ONE interval"):
        write_rows(pool, rows, tables, starts, gap, kernel="pallas-interpret")
    # the scatter takes any mask
    write_rows(pool, rows, tables, starts, gap)
    assert (tile_rows(jnp.bfloat16), tile_rows(jnp.float32),
            tile_rows(jnp.int8)) == (16, 8, 0)
    quant = {"q": jnp.zeros((1, 3, 64, 128), jnp.int8),
             "s": jnp.zeros((1, 3, 64, 2), jnp.float32)}
    for kernel in ("pallas", "pallas-interpret"):
        assert commit_form(kernel, pool) == kernel
        assert commit_form(kernel, quant) == "xla"
        assert commit_form(
            kernel, jnp.zeros((1, 3, 8, 128), jnp.bfloat16)) == "xla"
        assert commit_form("xla", pool) == "xla"
    narrow = jnp.zeros((1, 3, 16, 96), jnp.bfloat16)
    assert commit_form("pallas", narrow) == "xla"
    assert commit_form("pallas-interpret", narrow) == "pallas-interpret"
    with pytest.raises(ValueError, match="unknown commit kernel"):
        commit_form("mosaic", pool)
    assert _commit_kernel("pallas", None) == "pallas"
    assert _commit_kernel("pallas", object()) == "xla"


def _all_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it (jit, scan,
    cond, custom calls), in order."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _all_eqns(sub)


def _tiny_commit_programs():
    """(name, function, arguments) of the tiny prefill, continuation and
    decode chunk over a pool of 9 blocks x 8 rows a layer, in float32 (the
    CPU's scatter widens bfloat16: converts that say nothing of the chip)."""
    import dataclasses

    from langstream_tpu.models.llama import LlamaConfig, init_llama_params
    from langstream_tpu.models.llama_paged import (
        llama_decode_chunk_paged,
        llama_prefill_continue_paged,
        llama_prefill_paged,
    )

    c = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    params = init_llama_params(c, jax.random.PRNGKey(0))
    B = 2
    pool = jnp.zeros((c.layers, 9, 8, c.kv_heads * c.head_dim), c.dtype)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    key = jax.random.PRNGKey(0)

    def prefill(params, pool_k, pool_v):
        return llama_prefill_paged(
            c, params, jnp.zeros((B, 16), jnp.int32),
            jnp.asarray([9, 16], jnp.int32), pool_k, pool_v, tables)

    def cont(params, pool_k, pool_v):
        return llama_prefill_continue_paged(
            c, params, jnp.zeros((B, 8), jnp.int32),
            jnp.asarray([8, 11], jnp.int32), jnp.asarray([8, 3], jnp.int32),
            pool_k, pool_v, tables, num_read_blocks=2)

    def chunk(params, pool_k, pool_v):
        return llama_decode_chunk_paged(
            c, params, jnp.zeros((B,), jnp.int32),
            jnp.asarray([5, 17], jnp.int32), jnp.ones((B,), bool), pool_k,
            pool_v, tables, greedy_sample, key, 2, num_read_blocks=3,
            kernel="xla")

    return c, [(f.__name__, f, (params, pool, pool))
               for f in (prefill, cont, chunk)]


def test_the_commit_is_one_scatter_of_rows_into_the_pool_where_it_lies():
    """In the tiny prefill, continuation and decode chunk the only scatters
    into something of the pool's size take a rank-2 operand of ``L*nb*bs``
    rows (one for K, one for V), and no ``transpose`` or ``copy`` anywhere
    in the program touches a value of the pool's size."""
    c, programs = _tiny_commit_programs()
    KhD = c.kv_heads * c.head_dim
    pool_elems = c.layers * 9 * 8 * KhD
    for name, fn, args in programs:
        eqns = list(_all_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
        size = lambda v: int(np.prod(getattr(v.aval, "shape", ())))  # noqa: E731
        scatters = [e for e in eqns if e.primitive.name.startswith("scatter")
                    and size(e.invars[0]) >= pool_elems // c.layers]
        assert [e.invars[0].aval.shape for e in scatters] == \
            [(c.layers * 9 * 8, KhD)] * 2, name
        moved = [e.primitive.name for e in eqns
                 if e.primitive.name in ("transpose", "copy", "copy_p")
                 and any(size(v) >= pool_elems // c.layers
                         for v in (*e.invars, *e.outvars))]
        assert not moved, (name, moved)


def test_no_compiled_program_holds_a_second_pool(monkeypatch):
    """What the builder reads on the chip, at tiny shapes on the CPU
    (``tools/ops_of_shape.py``): with the pools donated, the optimised
    program has no instruction that computes or moves a value of the
    stacked pool's shape other than the scatter; with the parent's
    expression in its place the same reader finds the transposes."""
    from test_ops_of_shape import load_tool

    from langstream_tpu.models import llama_paged

    tool = load_tool()

    def pool_ops():
        c, programs = _tiny_commit_programs()
        KhD = c.kv_heads * c.head_dim
        shapes = [f"f32[{c.layers},9,8,{KhD}]", f"f32[{c.layers * 72},{KhD}]",
                  f"f32[{c.layers},72,{KhD}]", f"f32[72,{c.layers},{KhD}]"]
        found = {}
        for name, fn, args in programs:
            text = jax.jit(fn, donate_argnums=(1, 2)).lower(
                *args).compile().as_text()
            found[name] = tool.moved(tool.hlo_ops_of_shape(text, shapes))
        return found

    for name, ops in pool_ops().items():
        opcodes = [op for _, op, _ in ops]
        assert opcodes.count("scatter") == 2, (name, ops)
        # a fusion is the scatter's own wrapper; nothing else is there
        assert set(opcodes) <= {"scatter", "fusion"}, (name, ops)
    monkeypatch.setattr(llama_paged, "write_rows_pair",
                        a_pool_at_a_time(reference_write_rows))
    for name, ops in pool_ops().items():
        assert {"transpose", "copy"} & {op for _, op, _ in ops}, (name, ops)


# ---------------------------------------------------------------------------
# model equivalence: paged vs dense
# ---------------------------------------------------------------------------


def _setup_model(seed=7, max_seq=64):
    from langstream_tpu.models.llama import LlamaConfig, init_llama_params

    c = LlamaConfig.tiny(max_seq_len=max_seq)
    params = init_llama_params(c, jax.random.PRNGKey(seed))
    return c, params


def test_paged_prefill_matches_dense():
    from langstream_tpu.models.llama import init_kv_cache, llama_prefill
    from langstream_tpu.models.llama_paged import llama_prefill_paged
    from langstream_tpu.models.paged import (
        BlockManager, PagedLayout, gather_kv, init_paged_kv_cache,
    )

    c, params = _setup_model()
    prompts = jnp.array(
        [[5, 9, 17, 3, 0, 0, 0, 0], [8, 2, 4, 6, 11, 13, 0, 0]], jnp.int32
    )
    lengths = jnp.array([4, 6])

    ck, cv = init_kv_cache(c, slots=2, max_seq_len=64)
    dense_logits, ck, cv = llama_prefill(
        c, params, prompts, lengths, ck, cv, jnp.array([0, 1]), use_flash=False
    )

    layout = PagedLayout.for_model(64, slots=2, block_size=8)
    pk, pv = init_paged_kv_cache(c, layout)
    mgr = BlockManager(layout, slots=2)
    for s in (0, 1):
        mgr.admit(s, 24)
        mgr.ensure_capacity(s, int(lengths[s]))
    tables = jnp.asarray(mgr.tables)
    paged_logits, pk, pv = llama_prefill_paged(
        c, params, prompts, lengths, pk, pv, tables, use_flash=False
    )
    np.testing.assert_allclose(
        np.asarray(dense_logits), np.asarray(paged_logits), rtol=2e-2, atol=2e-2
    )
    # cache contents must match the dense cache rows (valid rows only: the
    # dense path also writes roped padding garbage, the paged path masks it)
    KhD = c.kv_heads * c.head_dim
    dense_rows = np.asarray(ck).reshape(c.layers, 2, 64, KhD)
    paged_rows = np.asarray(gather_kv(pk, tables, 1))  # first 8 rows
    for s, n in enumerate(np.asarray(lengths)):
        np.testing.assert_allclose(
            dense_rows[:, s, :n], paged_rows[:, s, :n], rtol=2e-2, atol=2e-2
        )


@pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
def test_paged_decode_chunk_matches_dense(kernel):
    """Two paged decode chunks (greedy) must reproduce the dense chunked
    decode token-for-token, for both the XLA reference read and the Pallas
    kernel (interpret mode on CPU)."""
    from langstream_tpu.models.llama import (
        init_kv_cache, llama_decode_chunk, llama_prefill,
    )
    from langstream_tpu.models.llama_paged import (
        llama_decode_chunk_paged, llama_prefill_paged,
    )
    from langstream_tpu.models.paged import (
        BlockManager, PagedLayout, init_paged_kv_cache,
    )

    c, params = _setup_model()
    prompts = jnp.array(
        [[5, 9, 17, 3, 0, 0, 0, 0], [8, 2, 4, 6, 11, 13, 0, 0]], jnp.int32
    )
    lengths = jnp.array([4, 6])
    K = 3

    # dense reference
    ck, cv = init_kv_cache(c, slots=2, max_seq_len=64)
    logits, ck, cv = llama_prefill(
        c, params, prompts, lengths, ck, cv, jnp.array([0, 1]), use_flash=False
    )
    tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    active = jnp.array([True, True])
    ref_tokens = []
    t, ln = tok0, lengths
    for _ in range(2):
        ct, _, t, ln, ck, cv = llama_decode_chunk(
            c, params, t, ln, active, ck, cv, greedy_sample,
            jax.random.PRNGKey(0), K,
        )
        ref_tokens.append(np.asarray(ct))
    ref = np.concatenate(ref_tokens, axis=0)  # (2K, B)

    # paged
    layout = PagedLayout.for_model(64, slots=2, block_size=8)
    pk, pv = init_paged_kv_cache(c, layout)
    mgr = BlockManager(layout, slots=2)
    for s in (0, 1):
        mgr.admit(s, 24)
        mgr.ensure_capacity(s, int(lengths[s]))
    tables = jnp.asarray(mgr.tables)
    plogits, pk, pv = llama_prefill_paged(
        c, params, prompts, lengths, pk, pv, tables, use_flash=False
    )
    pt0 = jnp.argmax(plogits, axis=-1).astype(jnp.int32)
    assert (np.asarray(pt0) == np.asarray(tok0)).all()

    got_tokens = []
    t, ln = pt0, lengths
    for _ in range(2):
        # grow blocks to cover base + K before the chunk, like the engine
        for s in (0, 1):
            mgr.ensure_capacity(s, int(ln[s]) + K)
        tables = jnp.asarray(mgr.tables)
        nrb = max(int(np.ceil((int(ln.max()) + K) / layout.block_size)), 1)
        ct, _, t, ln, pk, pv = llama_decode_chunk_paged(
            c, params, t, ln, active, pk, pv, tables, greedy_sample,
            jax.random.PRNGKey(0), K, num_read_blocks=nrb, kernel=kernel,
        )
        got_tokens.append(np.asarray(ct))
    got = np.concatenate(got_tokens, axis=0)
    np.testing.assert_array_equal(got, ref)


def _chunk_step_by_step(c, params, tokens, lengths, active, pool_k, pool_v,
                        tables, sample_fn, key, num_steps, num_read_blocks,
                        counts=None):
    """The decode chunk as one would write it down: a Python loop over the
    steps and, inside it, over the layers; a chunk buffer A LAYER that takes
    the step's rows with ``.at[].set``; the pool read through the XLA
    gather; one commit at the end. The layer math is the model's own."""
    import math

    from langstream_tpu.models.llama import (
        _apply_rope, _default_ffn, _rms_norm, _rope,
    )
    from langstream_tpu.models.llama_paged import _cache_partial_xla
    from langstream_tpu.models.paged import write_rows
    from langstream_tpu.models.quant import as_weight, embedding_take
    from langstream_tpu.ops.paged_attention import (
        NEG_INF, merge_partial_attention,
    )

    B, G = tokens.shape[0], c.heads // c.kv_heads
    adv = active.astype(jnp.int32)
    kbufs = [jnp.zeros((B, num_steps, c.kv_heads, c.head_dim), c.dtype)
             for _ in range(c.layers)]
    vbufs = [jnp.zeros_like(b) for b in kbufs]
    chunk_tokens, chunk_lps = [], []
    for step in range(num_steps):
        key, sub = jax.random.split(key)
        x = embedding_take(params["embed"], tokens)
        cos, sin = _rope(lengths + step * adv, c.head_dim, c.rope_theta)
        seen = (jnp.arange(num_steps) <= step)[None, None, None, :]
        for layer in range(c.layers):
            lp = jax.tree.map(lambda a: a[layer], params["layers"])
            h = _rms_norm(x, lp["attn_norm"], c.norm_eps)
            q = (h @ as_weight(lp["wq"])).reshape(B, c.heads, c.head_dim)
            k = (h @ as_weight(lp["wk"])).reshape(B, c.kv_heads, c.head_dim)
            v = (h @ as_weight(lp["wv"])).reshape(B, c.kv_heads, c.head_dim)
            q, k = _apply_rope(q, cos, sin), _apply_rope(k, cos, sin)
            kbufs[layer] = kbufs[layer].at[:, step].set(k)
            vbufs[layer] = vbufs[layer].at[:, step].set(v)
            pooled = _cache_partial_xla(
                c, q, pool_k, pool_v, layer, tables, lengths, num_read_blocks)
            qg = q.reshape(B, c.kv_heads, G, c.head_dim)
            s = jnp.einsum("bkgd,btkd->bkgt", qg, kbufs[layer])
            s = s.astype(jnp.float32) / math.sqrt(c.head_dim)
            s = jnp.where(seen, s, NEG_INF)
            m = jnp.max(s, axis=-1)
            p = jnp.exp(s - jnp.where(m <= NEG_INF, 0.0, m)[..., None])
            p = jnp.where(seen, p, 0.0)
            acc = jnp.einsum("bkgt,btkd->bkgd", p.astype(c.dtype),
                             vbufs[layer]).astype(jnp.float32)
            out = merge_partial_attention([pooled, (
                acc.reshape(B, c.heads, c.head_dim), m.reshape(B, c.heads),
                jnp.sum(p, axis=-1).reshape(B, c.heads),
            )]).astype(x.dtype).reshape(B, c.heads * c.head_dim)
            x = x + out @ as_weight(lp["wo"])
            x = x + _default_ffn(_rms_norm(x, lp["mlp_norm"], c.norm_eps),
                                 lp, active)
        x = _rms_norm(x, params["final_norm"], c.norm_eps)
        logits = (x @ as_weight(params["lm_head"])).astype(jnp.float32)
        if counts is None:
            nxt, lp_ = sample_fn(logits, sub)
        else:
            nxt, lp_ = sample_fn(logits, sub, counts)
        tokens = jnp.where(active, nxt, tokens)
        if counts is not None:
            counts = counts.at[jnp.arange(B), tokens].add(adv)
        chunk_tokens.append(tokens)
        chunk_lps.append(lp_)
    valid = jnp.broadcast_to(active[:, None], (B, num_steps))
    rows = lambda bufs: jnp.stack(bufs).reshape(  # noqa: E731
        c.layers, B, num_steps, c.kv_heads * c.head_dim)
    return (jnp.stack(chunk_tokens), jnp.stack(chunk_lps), tokens,
            lengths + num_steps * adv,
            write_rows(pool_k, rows(kbufs), tables, lengths, valid),
            write_rows(pool_v, rows(vbufs), tables, lengths, valid))


@pytest.mark.parametrize("penalties", [False, True], ids=["plain", "penalties"])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("num_steps", [8, 32])
def test_the_chunk_buffer_in_the_carry_is_the_step_by_step_chunk(
        num_steps, pool, penalties):
    """The chunk keeps ONE buffer of all layers in both scans' carry and
    writes a step's rows into it in place; tokens, log-probabilities, final
    tokens and lengths and both pools (an int8 pool's scales too) equal,
    bit for bit, those of the loop written down step by step with a buffer a
    layer. Slot 2 is inactive (it keeps its token, its length and its
    rows), slot 1 starts two rows before a block's edge and slot 3 crosses
    one block with 8 steps and four with 32."""
    from langstream_tpu.models.llama_paged import llama_decode_chunk_paged

    c, params = _setup_model(seed=11, max_seq=128)
    B, bs, cols = 4, 8, 8
    rng = np.random.default_rng(num_steps + penalties)
    shape = (c.layers, 1 + B * cols, bs, c.kv_heads * c.head_dim)
    if pool == "bf16":
        pools = [jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                 for _ in range(2)]
    else:
        pools = [{"q": jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
                  "s": jnp.asarray(rng.uniform(0.01, 0.05, shape[:3] + (
                      c.kv_heads,)), jnp.float32)} for _ in range(2)]
    tables = jnp.asarray(1 + rng.permutation(B * cols).reshape(B, cols),
                         jnp.int32)
    lengths = jnp.asarray([5, 30, 17, 3], jnp.int32)
    active = jnp.asarray([True, True, False, True])
    tokens = jnp.asarray([5, 9, 11, 200], jnp.int32)
    counts = (jnp.asarray(rng.integers(0, 2, (B, c.vocab_size)), jnp.int32)
              if penalties else None)

    def sample(logits, key, counts=None):
        if counts is not None:
            logits = logits - 0.6 * (counts > 0) - 0.2 * counts
        t = jax.random.categorical(key, logits / 0.8).astype(jnp.int32)
        return t, jnp.take_along_axis(
            jax.nn.log_softmax(logits), t[:, None], axis=1)[:, 0]

    key = jax.random.PRNGKey(1)
    # both run a primitive at a time: compiled whole, the CPU's compiler
    # drops a bfloat16 rounding between two ops it fuses and vectorises a
    # loop's body by its own lights, so two programs of one arithmetic
    # differ in the last bit by their form (the scans against the parent's
    # scans, compiled, are bit-equal: CHANGES.md, PR 42)
    with jax.disable_jit():
        got = llama_decode_chunk_paged(
            c, params, tokens, lengths, active, *pools, tables, sample, key,
            num_steps, num_read_blocks=cols, kernel="xla",
            sample_extras=(None, None, counts) if penalties else None)
        want = _chunk_step_by_step(
            c, params, tokens, lengths, active, *pools, tables, sample, key,
            num_steps, cols, counts)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    chunk_tokens, _, final_tokens, final_lengths, pool_k, _ = got
    # the inactive slot stood still; the others advanced a row a step
    np.testing.assert_array_equal(
        np.asarray(final_lengths), [5 + num_steps, 30 + num_steps, 17,
                                    3 + num_steps])
    assert int(final_tokens[2]) == 11
    assert (np.asarray(chunk_tokens)[:, 2] == 11).all()
    # and the commit reached the blocks past the edges, and no other
    data = lambda p: np.asarray(  # noqa: E731
        (p["q"] if isinstance(p, dict) else p).astype(jnp.float32))
    before, after = data(pools[0]), data(pool_k)
    for slot, first in enumerate([5, 30, None, 3]):
        rows = [] if first is None else range(first, first + num_steps)
        touched = {int(tables[slot, r // bs]) for r in rows}
        if rows:   # from inside a block over one edge, or four
            assert len(touched) == rows[-1] // bs - rows[0] // bs + 1 > 1
        for block in map(int, tables[slot]):
            changed = not np.array_equal(before[:, block], after[:, block])
            assert changed == (block in touched), (slot, block)


def test_paged_kernel_partial_matches_xla_reference():
    """paged_attention_partial (interpret) ≡ the XLA gather reference on
    random inputs with ragged lengths."""
    from langstream_tpu.models.llama import LlamaConfig
    from langstream_tpu.models.llama_paged import _cache_partial_xla
    from langstream_tpu.ops.paged_attention import (
        merge_partial_attention, paged_attention_partial,
    )

    c = LlamaConfig.tiny()
    B, H, D, Kh = 3, c.heads, c.head_dim, c.kv_heads
    bs, nb, nrb = 8, 10, 3
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, H, D), dtype=jnp.float32)
    pool_k = jax.random.normal(k2, (nb, bs, Kh * D), dtype=jnp.float32)
    pool_v = jax.random.normal(k3, (nb, bs, Kh * D), dtype=jnp.float32)
    tables = jnp.array([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9]], jnp.int32)
    lengths = jnp.array([20, 9, 24], jnp.int32)

    ref = _cache_partial_xla(
        c, q, pool_k[None], pool_v[None], 0, tables, lengths, nrb
    )
    got = paged_attention_partial(
        q, pool_k[None], pool_v[None], 0, tables, lengths,
        num_read_blocks=nrb, kv_heads=Kh, head_dim=D, interpret=True,
    )
    # compare the *normalised* outputs (partials differ by shift convention)
    out_ref = merge_partial_attention([ref])
    out_got = merge_partial_attention([got])
    np.testing.assert_allclose(
        np.asarray(out_ref), np.asarray(out_got), rtol=1e-5, atol=1e-5
    )


def test_paged_kernel_partial_q8_matches_xla_reference():
    """The int8 kernel twin (in-kernel fused dequant) ≡ the XLA gather
    path on the same int8 pool — the headline-posture read lane."""
    from langstream_tpu.models.llama import LlamaConfig
    from langstream_tpu.models.llama_paged import _cache_partial_xla
    from langstream_tpu.ops.paged_attention import (
        merge_partial_attention, paged_attention_partial,
    )

    c = LlamaConfig.tiny()
    B, H, D, Kh = 3, c.heads, c.head_dim, c.kv_heads
    bs, nb, nrb = 8, 10, 3
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.PRNGKey(1), 5)
    q = jax.random.normal(k1, (B, H, D), dtype=jnp.bfloat16)
    pool_k = {
        "q": jax.random.randint(k2, (nb, bs, Kh * D), -127, 128, jnp.int8),
        "s": jax.random.uniform(k3, (nb, bs, Kh), jnp.float32, 0.01, 0.1),
    }
    pool_v = {
        "q": jax.random.randint(k4, (nb, bs, Kh * D), -127, 128, jnp.int8),
        "s": jax.random.uniform(k5, (nb, bs, Kh), jnp.float32, 0.01, 0.1),
    }
    tables = jnp.array([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9]], jnp.int32)
    lengths = jnp.array([20, 9, 24], jnp.int32)

    add_l = lambda a: a[None]  # noqa: E731 — both reads take stacked pools
    pool_k, pool_v = jax.tree.map(add_l, pool_k), jax.tree.map(add_l, pool_v)
    ref = _cache_partial_xla(c, q, pool_k, pool_v, 0, tables, lengths, nrb)
    got = paged_attention_partial(
        q, pool_k, pool_v, 0, tables, lengths,
        num_read_blocks=nrb, kv_heads=Kh, head_dim=D, interpret=True,
    )
    out_ref = merge_partial_attention([ref])
    out_got = merge_partial_attention([got])
    np.testing.assert_allclose(
        np.asarray(out_ref, dtype=np.float32),
        np.asarray(out_got, dtype=np.float32),
        rtol=5e-2, atol=5e-2,  # bf16 math with blocked vs full softmax
                               # accumulation orders (abs diffs ~0.03 on
                               # O(1-4) outputs)
    )


def test_paged_kernel_q8_batch_leading_layout_pin():
    """The batch-leading q8 accumulate (per-head dot_general, no block
    transpose) ≡ the XLA gather path across the shapes the transpose
    used to normalize: multiple kv_heads with a wide GQA group, a batch
    larger than one sweep tile, ragged lengths including a sub-block row
    and an exact block-boundary row."""
    from langstream_tpu.models.llama import LlamaConfig
    from langstream_tpu.models.llama_paged import _cache_partial_xla
    from langstream_tpu.ops.paged_attention import (
        merge_partial_attention, paged_attention_partial,
    )
    import dataclasses

    c = dataclasses.replace(LlamaConfig.tiny(), heads=8, kv_heads=2)
    B, H, D, Kh = 6, c.heads, c.head_dim, c.kv_heads
    bs, nb, nrb = 8, 16, 2
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(k1, (B, H, D), dtype=jnp.bfloat16)
    pool_k = {
        "q": jax.random.randint(k2, (nb, bs, Kh * D), -127, 128, jnp.int8),
        "s": jax.random.uniform(k3, (nb, bs, Kh), jnp.float32, 0.01, 0.1),
    }
    pool_v = {
        "q": jax.random.randint(k4, (nb, bs, Kh * D), -127, 128, jnp.int8),
        "s": jax.random.uniform(k5, (nb, bs, Kh), jnp.float32, 0.01, 0.1),
    }
    tables = jnp.array(
        [[1, 2], [3, 4], [5, 6], [7, 8], [9, 10], [11, 12]], jnp.int32
    )
    # ragged: sub-block, block-exact, and full-sweep rows all in one batch
    lengths = jnp.array([3, 8, 11, 16, 5, 13], jnp.int32)

    add_l = lambda a: a[None]  # noqa: E731 — both reads take stacked pools
    pool_k, pool_v = jax.tree.map(add_l, pool_k), jax.tree.map(add_l, pool_v)
    ref = _cache_partial_xla(c, q, pool_k, pool_v, 0, tables, lengths, nrb)
    got = paged_attention_partial(
        q, pool_k, pool_v, 0, tables, lengths,
        num_read_blocks=nrb, kv_heads=Kh, head_dim=D, interpret=True,
    )
    out_ref = merge_partial_attention([ref])
    out_got = merge_partial_attention([got])
    np.testing.assert_allclose(
        np.asarray(out_ref, dtype=np.float32),
        np.asarray(out_got, dtype=np.float32),
        rtol=5e-2, atol=5e-2,
    )


# the single-query read against the XLA gather, case by case: a stacked pool
# whose layers differ, read at a layer other than 0 through shuffled,
# non-contiguous tables (bs 8, a window of 5 blocks = 40 rows)
_READ_BS, _READ_NRB, _READ_NB, _READ_LAYERS = 8, 5, 64, 3
_READ_WINDOW = _READ_BS * _READ_NRB


def _read_case(G, lengths, *, seed=0, dtype=jnp.float32):
    import dataclasses

    from langstream_tpu.models.llama import LlamaConfig

    c = dataclasses.replace(LlamaConfig.tiny(), heads=2 * G, kv_heads=2)
    B, KhD = len(lengths), c.kv_heads * c.head_dim
    rng = np.random.default_rng(seed)
    tables = rng.permutation(np.arange(1, _READ_NB))[: B * _READ_NRB]
    tables = tables.reshape(B, _READ_NRB).astype(np.int32)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (_READ_LAYERS, _READ_NB, _READ_BS, KhD)
    return (
        c,
        jax.random.normal(kq, (B, c.heads, c.head_dim), dtype),
        np.array(jax.random.normal(kk, shape, dtype)),
        np.array(jax.random.normal(kv, shape, dtype)),
        tables,
        np.asarray(lengths, np.int32),
    )


def _read_both(c, q, pool_k, pool_v, tables, lengths, layer):
    """Normalised outputs of the kernel (interpreter) and of the XLA read."""
    from langstream_tpu.models.llama_paged import _cache_partial_xla
    from langstream_tpu.ops.paged_attention import (
        merge_partial_attention, paged_attention_partial,
    )

    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    got = paged_attention_partial(
        q, jnp.asarray(pool_k), jnp.asarray(pool_v), layer, tables, lengths,
        num_read_blocks=_READ_NRB, kv_heads=c.kv_heads, head_dim=c.head_dim,
        interpret=True,
    )
    ref = _cache_partial_xla(
        c, q, jnp.asarray(pool_k), jnp.asarray(pool_v), layer,
        tables, lengths, _READ_NRB,
    )
    # a slot that attends nothing has no rows to sum: l is 0 on both sides
    np.testing.assert_array_equal(
        np.asarray(got[2]) > 0,
        np.broadcast_to(np.asarray(lengths)[:, None] > 0, got[2].shape),
    )
    return (
        np.asarray(merge_partial_attention([got])),
        np.asarray(merge_partial_attention([ref])),
    )


def _tile_blocks(monkeypatch, blocks, *, kv_heads=2, head_dim=16, itemsize=4):
    """Make the kernel's tile hold ``blocks`` blocks at the cases' shapes
    (its VMEM budget is a constant of the module, read at call time)."""
    from langstream_tpu.ops import paged_attention

    monkeypatch.setattr(
        paged_attention, "TILE_VMEM_BYTES",
        blocks * 4 * _READ_BS * kv_heads * head_dim * itemsize,
    )


@pytest.mark.parametrize("G", [1, 2, 4])
@pytest.mark.parametrize("lengths", [
    pytest.param((0, 17, 0), id="empty"),
    pytest.param((1, 17, 1), id="one-row"),
    pytest.param((_READ_BS - 1, 17, _READ_BS - 1), id="block-less-one"),
    pytest.param((_READ_BS, 17, _READ_BS), id="one-block"),
    pytest.param((_READ_BS + 1, 17, _READ_BS + 1), id="block-and-one"),
    pytest.param((_READ_WINDOW - 1, 17, _READ_WINDOW - 1), id="window-less-one"),
    pytest.param((_READ_WINDOW, 17, _READ_WINDOW), id="whole-window"),
    pytest.param((20, 0, 9, _READ_WINDOW, 1, 33, 0, 16), id="ragged"),
])
def test_paged_read_matches_xla_over_lengths(G, lengths, monkeypatch):
    """Lengths at every edge of a block and of the window, alone and mixed
    in one batch, for each query-group width; tiles of two blocks, so that
    the window's five end on a half-filled tile."""
    _tile_blocks(monkeypatch, 2)
    case = _read_case(G, lengths, seed=G)
    got, ref = _read_both(*case, layer=2)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("blocks", [1, 2, 3, 5, 64])
def test_paged_read_matches_xla_over_tile_sizes(blocks, layer, monkeypatch):
    """Tile boundaries inside a slot's blocks, at their end, and beyond
    them (one tile holds any slot; 64 is capped at the window): slots of
    1-5 blocks, some free, each layer of the stack giving its own answer."""
    _tile_blocks(monkeypatch, blocks)
    lengths = (40, 3, 0, 24, 16, 17, 0, 33, 32, 8)
    case = _read_case(2, lengths, seed=7)
    got, ref = _read_both(*case, layer=layer)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    other, _ = _read_both(*case, layer=2)
    assert np.abs(other - got).max() > 1e-2


@pytest.mark.parametrize("blocks", [1, 2, 5])
def test_paged_read_never_touches_a_dead_block_or_row(blocks, monkeypatch):
    """NaN in every block no live table entry names (block 0, where dead
    columns point, among them) and in the rows of each last block past the
    slot's length: the result is finite and is the clean pool's. Tiles of
    one, two and five blocks leave different rows of an earlier slot in
    the buffer."""
    from langstream_tpu.ops.paged_attention import (
        merge_partial_attention, paged_attention_partial,
    )

    _tile_blocks(monkeypatch, blocks)
    lengths = np.array((33, 0, 5, 40, 16, 9, 0, 25), np.int32)
    c, q, pool_k, pool_v, tables, _ = _read_case(2, lengths, seed=3)
    live_cols = -(-lengths // _READ_BS)
    tables = np.where(
        np.arange(_READ_NRB)[None, :] < live_cols[:, None], tables, 0
    )
    clean, ref = _read_both(c, q, pool_k, pool_v, tables, lengths, layer=1)
    np.testing.assert_allclose(clean, ref, rtol=1e-5, atol=1e-5)

    dead = np.ones(_READ_NB, bool)
    poisoned_k, poisoned_v = pool_k.copy(), pool_v.copy()
    for b, n in enumerate(lengths):
        for j in range(live_cols[b]):
            dead[tables[b, j]] = False
        if n % _READ_BS:
            last = tables[b, live_cols[b] - 1]
            poisoned_k[:, last, n % _READ_BS:] = np.nan
            poisoned_v[:, last, n % _READ_BS:] = np.nan
    assert dead[0] and dead.sum() > 10
    poisoned_k[:, dead] = np.nan
    poisoned_v[:, dead] = np.nan
    acc, m, l = paged_attention_partial(
        q, jnp.asarray(poisoned_k), jnp.asarray(poisoned_v), 1,
        jnp.asarray(tables), jnp.asarray(lengths),
        num_read_blocks=_READ_NRB, kv_heads=c.kv_heads, head_dim=c.head_dim,
        interpret=True,
    )
    assert all(np.isfinite(np.asarray(x)).all() for x in (acc, m, l))
    np.testing.assert_array_equal(
        np.asarray(merge_partial_attention([(acc, m, l)])), clean
    )


_XLA_READ_TOL = {"float32": 1e-5, "bfloat16": 2e-2, "int8": 2e-2}


@pytest.mark.parametrize("scale", [None, 0.2], ids=["rsqrt-d", "scale-0.2"])
@pytest.mark.parametrize("G", [1, 2, 4])
@pytest.mark.parametrize("cols", [None, 2], ids=["one-pass", "passes-2-2-1"])
@pytest.mark.parametrize("pool", ["float32", "bfloat16", "int8"])
def test_xla_read_is_a_plain_softmax_over_the_slot_s_rows(
        monkeypatch, pool, cols, G, scale):
    """``_cache_partial_xla`` (the gather on the stacked pool at (layer,
    block), the two products over the rows as they lie, the window in one
    pass or in passes of two table columns with a last one of one) against
    a plain float32 softmax over the same rows: accumulator, max and sum, at
    a layer other than 0 of a stack whose layers differ, through shuffled
    tables. Slots: a free one, one at the window's last row, one that ends
    inside a block (and before the last pass), one at a block's end, one of
    a single row; a table's columns past the slot's blocks hold 0, the
    scratch block, whose rows are loud."""
    from langstream_tpu.models import llama_paged
    from langstream_tpu.models.kvquant import dequantize_rows, quantize_rows
    from langstream_tpu.models.llama_paged import _cache_partial_xla
    from langstream_tpu.ops.paged_attention import NEG_INF

    lengths = np.array((0, _READ_WINDOW, 17, 16, 1), np.int32)
    dtype = jnp.float32 if pool == "float32" else jnp.bfloat16
    c, q, pool_k, pool_v, tables, _ = _read_case(G, lengths, seed=7, dtype=dtype)
    B, Kh, D, layer = len(lengths), c.kv_heads, c.head_dim, 2
    live_cols = -(-lengths // _READ_BS)
    tables = np.where(
        np.arange(_READ_NRB)[None, :] < live_cols[:, None], tables, 0
    )
    pool_k[:, 0], pool_v[:, 0] = 1e4, -1e4           # scratch: loud, finite
    rows = lambda a: jnp.asarray(a).reshape(  # noqa: E731
        _READ_LAYERS, _READ_NB, _READ_BS, Kh, D
    )
    if pool == "int8":
        fold = lambda t: {  # noqa: E731
            "q": t["q"].reshape(_READ_LAYERS, _READ_NB, _READ_BS, Kh * D),
            "s": t["s"],
        }
        qk, qv = quantize_rows(rows(pool_k)), quantize_rows(rows(pool_v))
        dense_k = dequantize_rows(qk, jnp.float32)
        dense_v = dequantize_rows(qv, jnp.float32)
        pool_k, pool_v = fold(qk), fold(qv)
    else:
        dense_k = rows(pool_k).astype(jnp.float32)
        dense_v = rows(pool_v).astype(jnp.float32)
        pool_k, pool_v = jnp.asarray(pool_k), jnp.asarray(pool_v)

    if cols:
        item = 1 if pool == "int8" else jnp.dtype(dtype).itemsize
        monkeypatch.setattr(
            llama_paged, "_XLA_READ_PASS_BYTES",
            cols * B * _READ_BS * Kh * D * item,
        )
    # the layer rides in as the decode scan's traced index does
    acc, m, l = jax.jit(
        lambda layer: _cache_partial_xla(
            c, q, pool_k, pool_v, layer, jnp.asarray(tables),
            jnp.asarray(lengths), _READ_NRB, scale=scale,
        )
    )(jnp.int32(layer))

    window = lambda dense: np.asarray(dense)[layer][tables].reshape(  # noqa: E731
        B, _READ_WINDOW, Kh, D
    )
    kw, vw = window(dense_k), window(dense_v)
    qg = np.asarray(q, np.float32).reshape(B, Kh, G, D)
    s = np.einsum("bkgd,bwkd->bkgw", qg, kw)
    s = s * (D ** -0.5 if scale is None else scale)
    live = (np.arange(_READ_WINDOW)[None, :] < lengths[:, None])[:, None, None]
    want_m = np.where(live, s, NEG_INF).max(axis=-1)
    p = np.exp(np.where(live, s - want_m[..., None], -np.inf))
    want_l = p.sum(axis=-1)
    want_acc = np.einsum("bkgw,bwkd->bkgd", p, vw)

    tol = _XLA_READ_TOL[pool]
    H = Kh * G
    np.testing.assert_allclose(
        np.asarray(m), want_m.reshape(B, H), rtol=tol, atol=tol)
    np.testing.assert_allclose(
        np.asarray(l), want_l.reshape(B, H), rtol=tol, atol=tol)
    np.testing.assert_allclose(
        np.asarray(acc), want_acc.reshape(B, H, D), rtol=tol,
        atol=tol * max(1.0, float(np.abs(want_acc).max())))
    # the free slot attends nothing
    assert (np.asarray(l)[0] == 0).all() and (np.asarray(acc)[0] == 0).all()
    assert (np.asarray(m)[0] <= NEG_INF).all()


def _tiny_chunk_layer_body(kernel):
    """The jaxpr of the layer scan's body in the tiny decode chunk (no
    lowering: ``kernel="pallas"`` traces on any backend), and the scan's
    equation in the step body."""
    from langstream_tpu.models.llama import LlamaConfig, init_llama_params
    from langstream_tpu.models.llama_paged import llama_decode_chunk_paged

    c = LlamaConfig.tiny()
    params = init_llama_params(c, jax.random.PRNGKey(0))
    B = 2
    pool = jnp.zeros((c.layers, 9, 8, c.kv_heads * c.head_dim), c.dtype)

    def chunk(params, tokens, lengths, active, pool_k, pool_v, tables, key):
        return llama_decode_chunk_paged(
            c, params, tokens, lengths, active, pool_k, pool_v, tables,
            greedy_sample, key, 2, num_read_blocks=3, kernel=kernel,
        )

    jaxpr = jax.make_jaxpr(chunk)(
        params, jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.int32),
        jnp.ones((B,), bool), pool, pool, jnp.zeros((B, 4), jnp.int32),
        jax.random.PRNGKey(0),
    ).jaxpr
    scan = lambda j: next(  # noqa: E731
        e for e in j.eqns if e.primitive.name == "scan"
    )
    layers = scan(scan(jaxpr).params["jaxpr"].jaxpr)
    return c, layers.params["jaxpr"].jaxpr


def test_pallas_read_takes_the_stacked_pool_in_place():
    """The layer body hands the kernel the pool as it lies: both pool
    operands have rank 4 and the layer count in front, and they are the
    body's own inputs (constants of the layer scan), not the result of a
    ``dynamic_slice``, ``squeeze``, ``gather`` or any other equation."""
    c, body = _tiny_chunk_layer_body("pallas")
    calls = [e for e in body.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1                       # one read a layer a step
    assert "kv_read" in str(calls[0].source_info.name_stack)
    pools = [v for v in calls[0].invars if len(v.aval.shape) == 4]
    assert [v.aval.shape for v in pools] == [
        (c.layers, 9, 8, c.kv_heads * c.head_dim)
    ] * 2
    made_here = {id(v) for e in body.eqns for v in e.outvars}
    assert all(id(v) not in made_here for v in pools)
    assert all(any(v is i for i in body.invars) for v in pools)
    # and nothing else in the body holds a pool-sized value
    assert not [
        e.primitive.name for e in body.eqns for v in e.outvars
        if getattr(v.aval, "shape", ())[-3:] == (9, 8, c.kv_heads * c.head_dim)
    ]


def test_xla_read_takes_the_stacked_pool_in_place():
    """The XLA read's mechanism, on the jaxpr (ROADMAP S1): the layer scan
    hands it the layer's index, as it hands the Pallas read, and the body
    closes over the stack; each pool is gathered once, at (layer, block),
    with its indices promised in bounds; no value of a layer's or of the
    stack's size is made in the body."""
    c, body = _tiny_chunk_layer_body("xla")
    stack = (c.layers, 9, 8, c.kv_heads * c.head_dim)
    assert [v.aval.shape for v in body.invars].count(stack) == 2
    assert [v.aval.shape for v in body.invars].count(stack[1:]) == 0
    pools = [v for v in body.invars if v.aval.shape == stack]
    gathers = [
        e for e in body.eqns
        if e.primitive.name == "gather"
        and "kv_read" in str(e.source_info.name_stack)
    ]
    assert len(gathers) == 2
    assert sorted(id(e.invars[0]) for e in gathers) == sorted(map(id, pools))
    for e in gathers:
        assert e.params["mode"] == jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS
        assert e.params["slice_sizes"] == (1, 1) + stack[2:]
    assert not [
        e.primitive.name for e in body.eqns for v in e.outvars
        if getattr(v.aval, "shape", ())[-3:] == stack[1:]
    ]


def _lowered_int8_chunk():
    """StableHLO text of the tiny decode chunk on an int8 pool (text only:
    nothing is compiled or run), with the pool's and a layer's types."""
    from langstream_tpu.models.llama import LlamaConfig, init_llama_params
    from langstream_tpu.models.llama_paged import llama_decode_chunk_paged

    c = LlamaConfig.tiny()
    params = init_llama_params(c, jax.random.PRNGKey(0))
    B, nb, bs, KhD = 2, 9, 8, c.kv_heads * c.head_dim
    pool = lambda: {  # noqa: E731
        "q": jnp.zeros((c.layers, nb, bs, KhD), jnp.int8),
        "s": jnp.zeros((c.layers, nb, bs, c.kv_heads), jnp.float32),
    }

    def chunk(params, tokens, lengths, active, pool_k, pool_v, tables, key):
        return llama_decode_chunk_paged(
            c, params, tokens, lengths, active, pool_k, pool_v, tables,
            greedy_sample, key, 2, num_read_blocks=3, kernel="xla",
        )

    text = jax.jit(chunk).lower(
        params, jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.int32),
        jnp.ones((B,), bool), pool(), pool(), jnp.zeros((B, 4), jnp.int32),
        jax.random.PRNGKey(0),
    ).as_text()
    return text, f"{c.layers}x{nb}x{bs}x{KhD}xi8", f"{nb}x{bs}x{KhD}xi8"


def test_int8_chunk_lowers_without_a_fill_or_a_layer_slice():
    """The lowered text of the int8 decode chunk (CPU, text only): the
    pool's data is gathered from the stack, twice a layer body (K and V);
    no ``select`` is fed by such a gather (``jnp.take``'s default
    ``mode="fill"`` put one over the whole window), and no ``dynamic_slice``
    makes a whole ``(nb, bs, Kh*D)`` layer beside the pool (the scan over
    the pools did, for K and for V, every layer of every step)."""
    import re

    text, stack, layer = _lowered_int8_chunk()
    gathers = 0
    assert "stablehlo.select" in text   # the masks on the scores are there
    for func in text.split("func.func")[1:]:   # a value's name is its
        gathered = re.findall(                 # function's own
            rf'(%\S+) = "stablehlo\.gather"\(%\S+, %\S+\).*\(tensor<{stack}>, ',
            func)
        gathers += len(gathered)
        selects = [ln for ln in func.splitlines() if "stablehlo.select" in ln]
        for name in gathered:
            assert not [ln for ln in selects if re.search(rf"{name}\b", ln)]
    assert gathers == 2
    assert not [
        ln for ln in text.splitlines()
        if "dynamic_slice" in ln and f"x{layer}>" in ln
    ]


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


@pytest.fixture(autouse=True)
def _fresh_engines():
    from langstream_tpu.serving.engine import TpuServingEngine

    TpuServingEngine.reset_instances()
    yield
    TpuServingEngine.reset_instances()


def test_paged_engine_matches_dense_engine(run_async, dense_reference_greedy):
    """Greedy generations from the engine (four slots at once, paged pool)
    must equal the dense reference's token-for-token: ``llama_prefill`` +
    ``llama_decode_step`` on the dense cache, same weights, float32."""
    import asyncio

    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    prompts = ["paged cache equivalence", "second prompt!", "a", "and a longer fourth prompt here"]

    async def run():
        engine = TpuServingEngine.get_or_create(
            ServingConfig(
                model="tiny", model_dtype="float32", slots=4, max_seq_len=128,
                decode_chunk=4, default_max_tokens=12, kv_block_size=16,
                kv_pool_fraction=0.75, paged_kernel="xla",
            )
        )
        tokens = [engine.tokenizer.encode(p) for p in prompts]
        results = await asyncio.gather(
            *(engine.generate(t, {"max-tokens": 12}) for t in tokens)
        )
        reference = [dense_reference_greedy(engine, t, 12) for t in tokens]
        await engine.close()
        return [r["tokens"] for r in results], reference

    paged, reference = run_async(run())
    for got, ref in zip(paged, reference):
        assert got and got == ref[: len(got)]
    assert any(len(got) == 12 for got in paged)


def test_paged_engine_backpressure_completes_all(run_async):
    """A pool too small for all slots at once must queue (not fail) excess
    requests and still complete every one."""
    import asyncio

    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        engine = TpuServingEngine.get_or_create(
            ServingConfig(
                model="tiny", slots=4, max_seq_len=128, decode_chunk=4,
                default_max_tokens=8, kv_layout="paged", kv_block_size=16,
                # 2 requests' worth of blocks: (~40 tokens -> 3 blocks) * 2 + scratch
                kv_pool_blocks=7, paged_kernel="xla",
            )
        )
        results = await asyncio.gather(
            *(engine.generate(f"req {i}", {"max-tokens": 8}) for i in range(6))
        )
        stats = engine.stats()
        await engine.close()
        assert all(0 < len(r["tokens"]) <= 8 for r in results)
        assert stats["kv"]["num_blocks"] == 7

    run_async(main())


def test_paged_pool_uses_less_hbm_than_dense():
    """The headline: at the same slot count the paged pool reserves a
    fraction of the dense cache's rows."""
    from langstream_tpu.models.llama import LlamaConfig
    from langstream_tpu.models.paged import PagedLayout

    c = LlamaConfig.llama_1b(max_seq_len=1024)
    slots = 64
    layout = PagedLayout.for_model(1024, slots, block_size=64)
    dense_rows = slots * 1024
    paged_rows = layout.num_blocks * layout.block_size
    assert paged_rows <= dense_rows * 0.51
    # and the same pool supports MORE slots at the same HBM: worst-case
    # short-request load (128-token budget) fits ~4x the slots
    per_request_blocks = -(-128 // 64)
    assert (layout.num_blocks - 1) // per_request_blocks >= slots * 3


def test_paged_kernel_sharded_matches_xla():
    """The Pallas paged read under a dp×tp mesh (shard_map: slots on dp,
    heads on tp) ≡ the XLA gather path — TP serving keeps the kernel."""
    import jax.random as jrandom

    from langstream_tpu.models.llama import LlamaConfig, init_llama_params
    from langstream_tpu.models.llama_paged import llama_decode_chunk_paged
    from langstream_tpu.parallel.mesh import make_mesh

    c = LlamaConfig.tiny(max_seq_len=64)
    mesh = make_mesh({"dp": 4, "tp": 2})
    params = init_llama_params(c)
    B, bs, nb, nrb, K = 4, 8, 12, 3, 4
    k1, k2 = jrandom.split(jrandom.PRNGKey(3))
    pool_k = jrandom.normal(k1, (c.layers, nb, bs, c.kv_heads * c.head_dim), c.dtype)
    pool_v = jrandom.normal(k2, (c.layers, nb, bs, c.kv_heads * c.head_dim), c.dtype)
    tables = jnp.asarray(
        [[1, 2, 0], [3, 4, 0], [5, 6, 7], [8, 9, 10]], jnp.int32
    )
    lengths = jnp.asarray([10, 16, 20, 5], jnp.int32)
    tokens0 = jnp.asarray([1, 2, 3, 4], jnp.int32)
    active = jnp.ones((B,), bool)

    def greedy(logits, key):
        t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return t, jnp.zeros_like(t, jnp.float32)

    # same kernel, sharded vs unsharded: shard_map must be numerically
    # transparent (token-exact); the xla-vs-pallas numeric tolerance is
    # covered by test_paged_kernel_partial_matches_xla_reference
    ref = llama_decode_chunk_paged(
        c, params, tokens0, lengths, active, pool_k, pool_v, tables,
        greedy, jrandom.PRNGKey(0), K, num_read_blocks=nrb,
        kernel="pallas-interpret",
    )
    got = llama_decode_chunk_paged(
        c, params, tokens0, lengths, active, pool_k, pool_v, tables,
        greedy, jrandom.PRNGKey(0), K, num_read_blocks=nrb,
        kernel="pallas-interpret", mesh=mesh,
    )
    np.testing.assert_array_equal(np.asarray(ref[0]), np.asarray(got[0]))


# ---------------------------------------------------------------------------
# automatic prefix caching
# ---------------------------------------------------------------------------


def test_prefill_continue_matches_full_prefill():
    """Prefilling a prefix then continuing with the suffix must reproduce
    the one-shot prefill — logits and committed pool rows. f32 so the
    comparison is tight (bf16 differs only by accumulation order between
    the dense softmax and the two-segment online-softmax merge)."""
    import dataclasses

    from langstream_tpu.models.llama import LlamaConfig, init_llama_params
    from langstream_tpu.models.llama_paged import (
        llama_prefill_continue_paged,
        llama_prefill_paged,
    )
    from langstream_tpu.models.paged import (
        BlockManager,
        PagedLayout,
        init_paged_kv_cache,
    )

    c = dataclasses.replace(
        LlamaConfig.tiny(max_seq_len=64), dtype=jnp.float32
    )
    params = init_llama_params(c, jax.random.PRNGKey(1))
    layout = PagedLayout.for_model(64, 2, block_size=8)
    prompt = jnp.array(
        [[5, 9, 17, 3, 11, 2, 7, 1, 13, 21, 6, 4, 19, 8]], jnp.int32
    )
    n = prompt.shape[1]

    bm = BlockManager(layout, 2)
    bm.admit(0, 32)
    bm.ensure_capacity(0, n)
    pk, pv = init_paged_kv_cache(c, layout)
    tables = jnp.asarray(bm.tables[[0]])
    ref_logits, pk1, pv1 = llama_prefill_paged(
        c, params, prompt, jnp.array([n]), pk, pv, tables
    )

    bm2 = BlockManager(layout, 2)
    bm2.admit(0, 32)
    bm2.ensure_capacity(0, n)
    pk2, pv2 = init_paged_kv_cache(c, layout)
    t2 = jnp.asarray(bm2.tables[[0]])
    _, pk2, pv2 = llama_prefill_paged(
        c, params, prompt[:, :8], jnp.array([8]), pk2, pv2, t2
    )
    suffix = jnp.zeros((1, 8), jnp.int32).at[:, :6].set(prompt[:, 8:])
    cont_logits, pk2, pv2 = llama_prefill_continue_paged(
        c, params, suffix, jnp.array([8]), jnp.array([6]), pk2, pv2, t2,
        num_read_blocks=1,
    )
    np.testing.assert_allclose(
        np.asarray(ref_logits), np.asarray(cont_logits), rtol=2e-4, atol=2e-4
    )
    b = np.asarray(t2[0, :2])
    np.testing.assert_allclose(
        np.asarray(pk1[:, b]), np.asarray(pk2[:, b]), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(pv1[:, b]), np.asarray(pv2[:, b]), rtol=2e-4, atol=2e-4
    )


def test_prefix_cache_engine_reuses_and_matches(run_async):
    """Second request with a shared system preamble adopts cached blocks
    (block tables share head entries; prefill runs on the suffix) and the
    generation matches a prefix-cache-off engine token-for-token."""
    import asyncio

    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    preamble = "you are a helpful assistant. answer briefly and precisely. "
    prompts = [preamble + "what is a tpu?", preamble + "name a jax transform."]

    def cfg(prefix_cache):
        return ServingConfig(
            model="tiny", slots=4, max_seq_len=128, decode_chunk=4,
            default_max_tokens=10, kv_layout="paged", kv_block_size=16,
            kv_pool_fraction=0.75, paged_kernel="xla",
            prefix_cache=prefix_cache,
        )

    async def run(prefix_cache):
        engine = TpuServingEngine.get_or_create(cfg(prefix_cache))
        outs = []
        for p in prompts:  # sequential: the 2nd must hit the 1st's blocks
            outs.append(await engine.generate(p, {"max-tokens": 10}))
        stats = engine.stats()
        await engine.close()
        return [o["tokens"] for o in outs], stats

    cached_tokens, stats = run_async(run(True))
    assert stats["kv"]["cached_prefix_blocks"] > 0
    plain_tokens, _ = run_async(run(False))
    # short horizon: the cached path computes attention via the two-segment
    # online-softmax merge while the plain path uses one dense softmax —
    # bf16 accumulation-order noise can flip a late near-tie argmax (the
    # exact math is pinned by test_prefill_continue_matches_full_prefill
    # in f32)
    assert [t[:6] for t in cached_tokens] == [t[:6] for t in plain_tokens]


def test_prefix_cache_config_parsing():
    """String config values must parse as booleans ('false' disables)."""
    from langstream_tpu.serving.engine import ServingConfig

    assert ServingConfig.from_dict({"prefix-cache": "false"}).prefix_cache is False
    assert ServingConfig.from_dict({"prefix-cache": "true"}).prefix_cache is True
    assert ServingConfig.from_dict({}).prefix_cache is True
    assert (
        ServingConfig.from_dict({"prefix-cache-max-suffix": "256"})
        .prefix_cache_max_suffix
        == 256
    )


def test_prefix_cache_eviction_under_pressure(run_async):
    """Cache-held blocks must never block admission: when the pool runs
    dry the LRU cache-only blocks are evicted and every request completes."""
    import asyncio

    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        engine = TpuServingEngine.get_or_create(
            ServingConfig(
                model="tiny", slots=4, max_seq_len=128, decode_chunk=4,
                default_max_tokens=8, kv_layout="paged", kv_block_size=16,
                kv_pool_blocks=7, paged_kernel="xla", prefix_cache=True,
            )
        )
        results = []
        for i in range(6):  # distinct prompts: every finish caches blocks
            results.append(
                await engine.generate(
                    f"request number {i} with some padding text", {"max-tokens": 8}
                )
            )
        await engine.close()
        assert all(0 < len(r["tokens"]) <= 8 for r in results)

    run_async(main())


def test_prefix_cache_leaf_first_eviction():
    """Eviction drains chains tail-first: dropping a chain HEAD would leave
    cached descendants unreachable (match walks from the head), pinning
    pool blocks that can never match again."""
    from langstream_tpu.models.paged import BlockManager, PagedLayout

    lay = PagedLayout(block_size=4, num_blocks=10, max_blocks_per_slot=8)
    bm = BlockManager(lay, 4)
    p = list(range(1, 13))  # 3 full blocks -> chain d0-d1-d2
    bm.admit(0, 12)
    bm.ensure_capacity(0, 12)
    bm.register_prefix(0, p)
    bm.release(0)
    assert bm.stats()["cached_prefix_blocks"] == 3
    assert bm._evict_one()
    _, reuse = bm.match_prefix(p)
    assert reuse == 8  # head d0,d1 still matchable; leaf d2 evicted
    assert bm._evict_one()
    _, reuse = bm.match_prefix(p)
    assert reuse == 4


def test_prefill_continue_long_suffix_blocked():
    """Multi-block suffix (suffix > sbs=128) through the blocked
    online-softmax continuation must match the one-shot prefill — the
    memory-bounded path that lets long suffixes keep the prefix cache."""
    import dataclasses

    from langstream_tpu.models.llama import LlamaConfig, init_llama_params
    from langstream_tpu.models.llama_paged import (
        llama_prefill_continue_paged,
        llama_prefill_paged,
    )
    from langstream_tpu.models.paged import (
        BlockManager,
        PagedLayout,
        init_paged_kv_cache,
    )

    c = dataclasses.replace(
        LlamaConfig.tiny(max_seq_len=512), dtype=jnp.float32
    )
    params = init_llama_params(c, jax.random.PRNGKey(2))
    layout = PagedLayout.for_model(512, 2, block_size=64)
    rng = np.random.RandomState(0)
    n = 64 + 250  # 64-token cached prefix + 250-token suffix (2 key blocks)
    prompt = jnp.asarray(rng.randint(1, 300, size=(1, n)), jnp.int32)

    bm = BlockManager(layout, 2)
    bm.admit(0, n + 8)
    bm.ensure_capacity(0, n)
    pk, pv = init_paged_kv_cache(c, layout)
    tables = jnp.asarray(bm.tables[[0]])
    ref_logits, _, _ = llama_prefill_paged(
        c, params, prompt, jnp.array([n]), pk, pv, tables, use_flash=False
    )

    bm2 = BlockManager(layout, 2)
    bm2.admit(0, n + 8)
    bm2.ensure_capacity(0, n)
    pk2, pv2 = init_paged_kv_cache(c, layout)
    t2 = jnp.asarray(bm2.tables[[0]])
    _, pk2, pv2 = llama_prefill_paged(
        c, params, prompt[:, :64], jnp.array([64]), pk2, pv2, t2,
        use_flash=False,
    )
    suffix = jnp.zeros((1, 256), jnp.int32).at[:, :250].set(prompt[:, 64:])
    cont_logits, _, _ = llama_prefill_continue_paged(
        c, params, suffix, jnp.array([64]), jnp.array([250]), pk2, pv2, t2,
        num_read_blocks=1,
    )
    np.testing.assert_allclose(
        np.asarray(ref_logits), np.asarray(cont_logits), rtol=5e-4, atol=5e-4
    )


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


def test_chunked_prefill_matches_monolithic(run_async):
    """prefill-chunk on must produce the same greedy tokens as the
    monolithic prefill (the chunks commit identical K/V; only scheduling
    changes)."""
    import asyncio

    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    long_prompt = "a long prompt that will be prefilled in chunks. " * 8

    def cfg(chunk):
        return ServingConfig(
            model="tiny", slots=4, max_seq_len=512, decode_chunk=4,
            default_max_tokens=10, kv_layout="paged", kv_block_size=16,
            paged_kernel="xla", prefill_chunk=chunk, prefix_cache=False,
        )

    async def run(chunk):
        engine = TpuServingEngine.get_or_create(cfg(chunk))
        try:
            return (await engine.generate(long_prompt, {"max-tokens": 10}))[
                "tokens"
            ]
        finally:
            await engine.close()

    mono = run_async(run(0))
    chunked = run_async(run(64))
    assert mono[:6] == chunked[:6]


def test_chunked_prefill_interleaves_with_decode(run_async):
    """While a long prompt prefills in chunks, an already-active short
    request keeps streaming tokens — the head-of-line-blocking fix. Proven
    by timestamps: the short request's tokens keep arriving AFTER the long
    request was submitted but BEFORE its first token."""
    import asyncio
    import time

    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        engine = TpuServingEngine.get_or_create(
            ServingConfig(
                model="tiny", slots=4, max_seq_len=512, decode_chunk=2,
                default_max_tokens=48, kv_layout="paged", kv_block_size=16,
                paged_kernel="xla", prefill_chunk=32, prefix_cache=False,
            )
        )
        short_times: list[float] = []

        async def on_short_token(token, logprob, last):
            short_times.append(time.monotonic())

        try:
            short_task = asyncio.ensure_future(
                engine.generate(
                    "short active request", {"max-tokens": 48},
                    on_token=on_short_token,
                )
            )
            # let the short request admit and start decoding
            while len(short_times) < 4:
                await asyncio.sleep(0.01)
            long_submit = time.monotonic()
            long_result = await engine.generate(
                "the long request arrives later. " * 32, {"max-tokens": 4}
            )
            long_first = long_submit + long_result["ttft"]
            await short_task
        finally:
            await engine.close()
        # short tokens produced inside the long request's prefill window
        during = [t for t in short_times if long_submit < t < long_first]
        assert during, (
            f"short stream stalled during chunked prefill "
            f"(window {long_first - long_submit:.3f}s)"
        )

    run_async(main())


def test_chunked_prefill_max_tokens_one_seeds_cache(run_async):
    """A chunked-prefill request finished by its FIRST token (max-tokens=1)
    must still publish its prompt blocks: registration runs before the
    emit that releases the slot."""
    import asyncio

    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        engine = TpuServingEngine.get_or_create(
            ServingConfig(
                model="tiny", slots=4, max_seq_len=512, decode_chunk=4,
                default_max_tokens=8, kv_layout="paged", kv_block_size=16,
                paged_kernel="xla", prefill_chunk=32, prefix_cache=True,
            )
        )
        prompt = "a shared classification template prompt. " * 8
        try:
            await engine.generate(prompt, {"max-tokens": 1})
            stats = engine.stats()
            assert stats["kv"]["cached_prefix_blocks"] > 0, stats
            # second identical request must hit the cache
            await engine.generate(prompt, {"max-tokens": 1})
        finally:
            await engine.close()

    run_async(main())


def test_multiquery_kernel_matches_xla_reference():
    """The multi-query paged kernel (interpret) reproduces the dense
    reference for history attention over block-mapped pools."""
    import math

    from langstream_tpu.models.paged import gather_kv
    from langstream_tpu.ops.paged_attention import (
        NEG_INF,
        merge_partial_attention,
        paged_attention_multiquery_partial,
    )

    rng = np.random.RandomState(0)
    B, T, H, D, Kh, bs, nb, nrb = 3, 32, 8, 16, 4, 8, 20, 3
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    kp = jnp.asarray(rng.randn(nb, bs, Kh * D), jnp.float32)
    vp = jnp.asarray(rng.randn(nb, bs, Kh * D), jnp.float32)
    tables = jnp.asarray(rng.randint(1, nb, size=(B, 6)), jnp.int32)
    starts = jnp.asarray([5, 17, 24], jnp.int32)

    acc, m, l = paged_attention_multiquery_partial(
        q, kp, vp, tables, starts, num_read_blocks=nrb,
        kv_heads=Kh, head_dim=D, t_block=8, interpret=True,
    )
    out = merge_partial_attention([(acc, m, l)])

    W = nrb * bs
    kw = gather_kv(kp[None], tables, nrb)[0].reshape(B, W, Kh, D)
    vw = gather_kv(vp[None], tables, nrb)[0].reshape(B, W, Kh, D)
    G = H // Kh
    qg = q.reshape(B, T, Kh, G, D)
    s = jnp.einsum("btkgd,bwkd->bkgtw", qg, kw) / math.sqrt(D)
    mask = (jnp.arange(W)[None, :] < starts[:, None])[:, None, None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ref = (
        jnp.einsum("bkgtw,bwkd->bkgtd", p, vw)
        .transpose(0, 3, 1, 2, 4)
        .reshape(B, T, H, D)
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
    )


def test_continuation_pallas_kernel_matches_xla():
    """Continuation prefill with the multi-query kernel (interpret) is
    position-exact against the XLA blocked path — logits and pools."""
    import dataclasses

    from langstream_tpu.models.llama import LlamaConfig, init_llama_params
    from langstream_tpu.models.llama_paged import (
        llama_prefill_continue_paged,
        llama_prefill_paged,
    )
    from langstream_tpu.models.paged import (
        BlockManager,
        PagedLayout,
        init_paged_kv_cache,
    )

    c = dataclasses.replace(LlamaConfig.tiny(max_seq_len=128), dtype=jnp.float32)
    params = init_llama_params(c, jax.random.PRNGKey(1))
    layout = PagedLayout.for_model(128, 2, block_size=16)
    rng = np.random.RandomState(3)
    n = 48 + 30
    prompt = jnp.asarray(rng.randint(1, 300, size=(1, n)), jnp.int32)

    def setup():
        bm = BlockManager(layout, 2)
        bm.admit(0, n + 8)
        bm.ensure_capacity(0, n)
        pk, pv = init_paged_kv_cache(c, layout)
        t = jnp.asarray(bm.tables[[0]])
        _, pk, pv = llama_prefill_paged(
            c, params, prompt[:, :48], jnp.array([48]), pk, pv, t,
            use_flash=False,
        )
        return pk, pv, t

    suffix = jnp.zeros((1, 32), jnp.int32).at[:, :30].set(prompt[:, 48:])
    outs = {}
    for kern in ("xla", "pallas-interpret"):
        pk, pv, t = setup()
        logits, pk, _ = llama_prefill_continue_paged(
            c, params, suffix, jnp.array([48]), jnp.array([30]), pk, pv, t,
            num_read_blocks=3, kernel=kern, return_all_logits=True,
        )
        outs[kern] = (np.asarray(logits), np.asarray(pk))
    np.testing.assert_allclose(
        outs["xla"][0], outs["pallas-interpret"][0], rtol=1e-4, atol=1e-4
    )
    # every block but the scratch block 0: the selection is the commit's
    # too, and the kernel skips the padded rows the scatter sends there
    np.testing.assert_allclose(
        outs["xla"][1][:, 1:], outs["pallas-interpret"][1][:, 1:],
        rtol=1e-4, atol=1e-4,
    )
    assert np.asarray(outs["xla"][1][:, 1:]).any()


def test_continuation_pallas_kernel_sharded_matches_xla():
    """The multi-query kernel under a dp×tp mesh (shard_map, interpret)
    matches the XLA continuation path — the TP-serving prefix-cache /
    verify read keeps the kernel."""
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as P

    from langstream_tpu.models.llama import (
        LlamaConfig,
        init_llama_params,
        llama_param_specs,
    )
    from langstream_tpu.models.llama_paged import (
        llama_prefill_continue_paged,
        llama_prefill_paged,
    )
    from langstream_tpu.models.paged import (
        BlockManager,
        PagedLayout,
        init_paged_kv_cache,
        paged_cache_spec,
    )
    from langstream_tpu.parallel.mesh import make_mesh

    c = dataclasses.replace(LlamaConfig.tiny(max_seq_len=128), dtype=jnp.float32)
    params = init_llama_params(c, jax.random.PRNGKey(1))
    layout = PagedLayout.for_model(128, 4, block_size=16)
    rng = np.random.RandomState(3)
    prompt = jnp.asarray(rng.randint(1, 300, size=(2, 48)), jnp.int32)
    suffix = jnp.asarray(rng.randint(1, 300, size=(2, 16)), jnp.int32)

    def setup(mesh=None):
        bm = BlockManager(layout, 4)
        for s in (0, 1):
            bm.admit(s, 72)
            bm.ensure_capacity(s, 64)
        pk, pv = init_paged_kv_cache(c, layout)
        t = jnp.asarray(bm.tables[[0, 1]])
        p = params
        if mesh is not None:
            p = jax.tree.map(
                lambda w, s: jax.device_put(w, NamedSharding(mesh, s)),
                params, llama_param_specs(c),
                is_leaf=lambda x: isinstance(x, P),
            )
            cspec = NamedSharding(mesh, paged_cache_spec(mesh.axis_names))
            pk, pv = jax.device_put(pk, cspec), jax.device_put(pv, cspec)
        _, pk, pv = llama_prefill_paged(
            c, p, prompt, jnp.array([48, 48]), pk, pv, t, use_flash=False
        )
        return p, pk, pv, t

    p0, pk, pv, t = setup()
    ref, _, _ = llama_prefill_continue_paged(
        c, p0, suffix, jnp.array([48, 48]), jnp.array([16, 16]), pk, pv, t,
        num_read_blocks=3, kernel="xla",
    )

    mesh = make_mesh({"dp": 2, "tp": 2})
    p1, pk, pv, t = setup(mesh)
    got, _, _ = llama_prefill_continue_paged(
        c, p1, suffix, jnp.array([48, 48]), jnp.array([16, 16]), pk, pv, t,
        num_read_blocks=3, kernel="pallas-interpret", mesh=mesh,
    )
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(got), rtol=1e-3, atol=1e-3
    )
