"""Plain reference of the EvaByte decoder (``model_type`` ``evabyte``, EVA
attention in the deterministic form the release serves), and the comparison
that decides ``correct`` in its cell.

Plain ``jax.numpy`` in float32 at ``highest`` matmul precision: no kernel, no
cache, no batching, and none of the program's code. For a whole sequence it
makes every chunk's summary row and then, a block of queries at a time, the
scores over the query's own window and over the chunks of the windows closed
before it, by masks from the query's position alone.

**The layer** (``d`` head size, ``W`` window, ``C`` chunk; ``x`` float32):

1. ``h = RMSNorm(x) * (1 + g)``; ``q, k, v = h W_q, h W_k, h W_v``; half-split
   rotary at ``theta`` over the whole head on ``q`` and ``k``.
2. A head's summaries, from ``phi`` and ``mu``: for a chunk ``c`` with all
   ``C`` positions written, ``a_j = softmax_{j in c}(phi . k_j)``, ``v~_c =
   sum_j a_j v_j``, ``k~_c = mean_j k_j + mu``.
3. Query ``i``, ``w = i // W``: scores ``q_i . k_j / sqrt(d)`` over ``j`` in
   ``[wW, i]`` and ``q_i . k~_c / sqrt(d)`` over ``c < (W / C) w``; ONE
   softmax over both; ``x <- x + o W_o``.
4. ``x <- x + W_down[silu(W_gate h') * (W_up h')]``.
5. Final norm ``(1 + g)``, head ``hidden -> pred_heads x V`` laid
   (prediction head, byte).

:data:`FAULTS` are departures from this the check has to tell from the
served model (``tools/eva_probe.py --faults``); each is one change below.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference.afmoe import (
    GREEDY,
    _bucket_of,
    _held_to_logits,
    _on_engine,
    below_bfloat16,
    f32,
    slot_plan,
)

FAULTS = (
    "weights_below_bfloat16", "rows_below_bfloat16", "bfloat16_residual",
    "bfloat16_logits", "no_mu", "no_phi", "weighted_summary_key",
    "chunk_softmax_norm_term", "chunk_of_15", "chunk_of_17",
    "window_one_block_short", "window_one_block_long", "sliding_window",
    "own_window_seen_twice", "summaries_a_window_early", "two_softmaxes",
    "norm_without_unit_offset", "head_1_served",
)
#: what no comparison of outputs can hold: the head's columns laid (byte,
#: prediction head) in place of (prediction head, byte), and the release's
#: tokenizer ids (its 64 specials first) in place of the repo's: a
#: permutation of random columns or rows is another draw of the same weights
UNOBSERVABLE = ("head_columns_byte_major", "release_token_ids")

#: tokens of the check's prompts: inside the first window; one position
#: before a window's edge (its first decode step closes the window) and one
#: after; mid-chunk, 86 steps before the second edge (crossed inside a
#: decode chunk of 32); and past thirteen closed windows
CHECK_PROMPTS = (1500, 2047, 2049, 4010, 28003)
CHECK_DECODE_STEPS = 160
QUERY_BLOCK = 512
HEAD_GROUP = 4
FFN_ROWS = 1024   # rows of one pass of whatever is row by row: a power of two


def _log(message: str) -> None:
    print(f"[evabyte check] {message}", flush=True)


def _geometry(c, faults=()):
    """``(window, chunk)`` as the reference reads them."""
    block = max(1, c.window // 32)
    window = c.window + block * (("window_one_block_long" in faults)
                                 - ("window_one_block_short" in faults))
    chunk = c.chunk + ("chunk_of_17" in faults) - ("chunk_of_15" in faults)
    return window, chunk


def rms_norm(x, g, eps, faults=()):
    gain = g if "norm_without_unit_offset" in faults else 1.0 + g
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rotate(x, positions, theta):
    """Half-split rotary of ``x (T, heads, D)`` at ``positions (T,)``."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    angles = f32(positions)[:, None] * f32(inv_freq)[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def summaries(k, v, phi, mu, chunk, faults=()):
    """``(k~, v~) (T // chunk, heads, D)`` of the chunks that lie whole in
    ``k, v (T, heads, D)``."""
    n = k.shape[0] // chunk
    kc = k[: n * chunk].reshape(n, chunk, *k.shape[1:])
    vc = v[: n * chunk].reshape(n, chunk, *v.shape[1:])
    if "no_phi" in faults:
        phi = jnp.zeros_like(phi)
    if "no_mu" in faults:
        mu = jnp.zeros_like(mu)
    s = jnp.einsum("nchd,hd->nch", kc, phi)
    if "chunk_softmax_norm_term" in faults:
        s = s - 0.5 * jnp.sum(kc * kc, axis=-1)
    a = jax.nn.softmax(s, axis=1)[..., None]
    v_sum = jnp.sum(a * vc, axis=1)
    k_sum = (jnp.sum(a * kc, axis=1) if "weighted_summary_key" in faults
             else jnp.mean(kc, axis=1)) + mu
    return k_sum, v_sum


def attend_block(q, k, v, k_sum, v_sum, first, lo, window, chunk, faults=()):
    """Queries ``q (n, heads, D)`` at positions ``first ..``, against the
    keys ``k, v (m, heads, D)`` at positions ``lo ..`` and every summary
    row; the masks are made from each query's position alone."""
    i = first + jnp.arange(q.shape[0])[:, None]              # (n, 1)
    j = lo + jnp.arange(k.shape[0])[None, :]                 # (1, m)
    c = jnp.arange(k_sum.shape[0])[None, :]                  # (1, chunks)
    start = (i // window) * window
    closed = (c + 1) * chunk <= start       # chunks of windows closed before
    own = (j <= i) & (j >= start)
    if "sliding_window" in faults:
        own = (j <= i) & (i - j < window)
    if "own_window_seen_twice" in faults:
        closed = (c + 1) * chunk <= i
    if "summaries_a_window_early" in faults:
        # a chunk is summarised the moment it closes, and seen as that alone
        closed = (c + 1) * chunk <= i
        own = own & (j >= (i // chunk) * chunk)
    scale = 1.0 / math.sqrt(q.shape[-1])
    s_own = jnp.where(own[None], jnp.einsum("qhd,shd->hqs", q, k) * scale,
                      -jnp.inf)
    s_sum = jnp.where(closed[None],
                      jnp.einsum("qhd,shd->hqs", q, k_sum) * scale, -jnp.inf)
    if "two_softmaxes" in faults:
        p_own = jax.nn.softmax(s_own, axis=-1)
        # a query with no closed window behind it has no second softmax
        p_sum = jnp.where(closed.any(-1)[None, :, None],
                          jax.nn.softmax(s_sum, axis=-1), 0.0)
    else:
        p = jax.nn.softmax(jnp.concatenate([s_own, s_sum], axis=-1), axis=-1)
        p_own, p_sum = p[..., : k.shape[0]], p[..., k.shape[0]:]
    return (jnp.einsum("hqs,shd->qhd", p_own, v)
            + jnp.einsum("hqs,shd->qhd", p_sum, v_sum))


@functools.lru_cache(maxsize=None)
def _layer_functions(c, faults: tuple):
    """The forward's three pieces for one reading of the layer (jitted, one
    trace a padded length): a layer's attention, its gated MLP, and the first
    layer's rows as a pool would hold them. The residual is updated in
    place and nothing else of its size is made: the check runs beside a
    resident engine (at the cell's 24 slots 1.4 GiB of the chip are free)."""
    window, chunk = _geometry(c, faults)
    lower = (below_bfloat16 if "weights_below_bfloat16" in faults
             else (lambda t: t))
    rows = (below_bfloat16 if "rows_below_bfloat16" in faults
            else (lambda t: t))
    residual = ((lambda t: f32(t.astype(jnp.bfloat16)))
                if "bfloat16_residual" in faults else (lambda t: t))
    D = c.head_dim

    def pieces(rows):
        """``rows (T, n)`` as ``(T // FFN_ROWS, FFN_ROWS, n)``."""
        n = min(FFN_ROWS, rows.shape[0])
        return rows.reshape(rows.shape[0] // n, n, -1)

    def group(x, ap, h0):
        """``(q, k, v, k~, v~)`` of ``HEAD_GROUP`` heads from ``h0``, the
        norm and the projections ``FFN_ROWS`` rows at a time (the check
        runs beside a resident engine: no second array of the residual's
        size is made)."""
        T = x.shape[0]
        of = lambda a, axis, n=1: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, h0 * n, HEAD_GROUP * n, axis)
        g = f32(ap["norm"])
        ws = [lower(f32(of(ap[w], 1, D))) for w in ("wq", "wk", "wv")]
        q, k, v = (a.reshape(T, -1, D) for a in jax.lax.map(
            lambda xb: tuple(rms_norm(xb, g, c.norm_eps, faults) @ w
                             for w in ws), pieces(x)))
        where = jnp.arange(T)
        q, k = rotate(q, where, c.rope_theta), rotate(k, where, c.rope_theta)
        k, v = rows(k), rows(v)
        k_sum, v_sum = summaries(
            k, v, f32(of(ap["phi"], 0)), f32(of(ap["mu"], 0)), chunk, faults)
        return q, k, v, rows(k_sum), rows(v_sum)

    def in_place(x, one, *others):
        """``x`` with ``one(piece of x, pieces of others)`` written over it
        a piece at a time (``x`` is donated)."""
        n = min(FFN_ROWS, x.shape[0])
        cut = lambda a, i: jax.lax.dynamic_slice_in_dim(a, i * n, n, 0)  # noqa: E731
        return jax.lax.fori_loop(
            0, x.shape[0] // n,
            lambda i, x: jax.lax.dynamic_update_slice_in_dim(
                x, one(cut(x, i), *(cut(a, i) for a in others)), i * n, 0), x)

    def attend(x, ap, h0):
        """``(T, HEAD_GROUP * D)``: what the heads from ``h0`` attend."""
        T = x.shape[0]
        reach = min(max(window, c.window) + QUERY_BLOCK, T)
        q, k, v, k_sum, v_sum = group(x, ap, h0)

        def one(first):
            # from the first key the block's first query can see, and
            # never so far that the slice would run off the end
            lo = jnp.clip(first - (reach - QUERY_BLOCK) + 1, 0, T - reach)
            return attend_block(
                jax.lax.dynamic_slice_in_dim(q, first, QUERY_BLOCK, 0),
                jax.lax.dynamic_slice_in_dim(k, lo, reach, 0),
                jax.lax.dynamic_slice_in_dim(v, lo, reach, 0),
                k_sum, v_sum, first, lo, window, chunk, faults)

        return jax.lax.map(one, jnp.arange(0, T, QUERY_BLOCK)).reshape(T, -1)

    def add_out(x, ap, *outs):
        """``x + [outs] W_o`` over ``x``: a head group's rows of ``W_o`` on
        its own output."""
        wo = lower(f32(ap["wo"])).reshape(len(outs), -1, x.shape[1])
        return in_place(
            x, lambda xb, *obs: residual(
                xb + sum(ob @ w for ob, w in zip(obs, wo))), *outs)

    def first_rows(x, ap, h0):
        """The rows of ``HEAD_GROUP`` heads from ``h0``: ``((T, 2, heads,
        D), (T // chunk, 2, heads, D))``, K before V."""
        _, k, v, k_sum, v_sum = group(x, ap, h0)
        return jnp.stack([k, v], axis=1), jnp.stack([k_sum, v_sum], axis=1)

    def ffn(x, fp):
        up, down = lower(f32(fp["w_up"])), lower(f32(fp["w_down"]))
        g = f32(fp["norm"])

        def one(xb):
            u = rms_norm(xb, g, c.norm_eps, faults) @ up
            half = u.shape[1] // 2
            return residual(
                xb + (jax.nn.silu(u[:, :half]) * u[:, half:]) @ down)

        return in_place(x, one)

    def attention(x, ap):
        # a head group a program: what one leaves behind is its output alone
        return add_out(x, ap, *(attend(x, ap, h0)
                                for h0 in range(0, c.heads, HEAD_GROUP)))

    attend, add_out, ffn = jax.jit(attend), jax.jit(
        add_out, donate_argnums=0), jax.jit(ffn, donate_argnums=0)
    return attention, ffn, jax.jit(first_rows)


def forward(config, params, tokens, positions, faults=()):
    """The full forward over ``tokens (T,)``: ``(every prediction head's
    logits at positions (len(positions), pred_heads * V), the first layer's
    rotated K and V rows (T, 2 x heads * D), its summary rows (T // C, 2 x
    heads * D))``, all numpy float32. The sequence is padded to a power of
    two behind its end (a causal model's later positions change nothing
    before them), so that a length's pieces are traced once."""
    c = config
    lower = (below_bfloat16 if "weights_below_bfloat16" in faults
             else (lambda t: t))
    attention, ffn, first_rows = _layer_functions(c, tuple(sorted(faults)))
    T = len(tokens)
    padded = np.zeros((max(QUERY_BLOCK, _bucket_of(T)),), np.int32)
    padded[:T] = tokens
    _, chunk = _geometry(c, faults)
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][jnp.asarray(padded)])
        groups = [tuple(map(np.asarray, first_rows(
            x, params["layers"][0]["attn"], h0)))
            for h0 in range(0, c.heads, HEAD_GROUP)]
        rows, summary = (
            np.concatenate([g[i] for g in groups], axis=2) for i in (0, 1))
        rows = rows.reshape(rows.shape[0], -1)[:T]
        summary = summary.reshape(summary.shape[0], -1)[: T // chunk]
        for lp in params["layers"]:
            x = attention(x, lp["attn"])
            x = ffn(x, lp["ffn"])
        x = rms_norm(x[jnp.asarray(positions)], f32(params["final_norm"]),
                     c.norm_eps, faults)
        logits = x @ lower(f32(params["lm_head"]))
        if "bfloat16_logits" in faults:
            logits = f32(logits.astype(jnp.bfloat16))
        logits = np.asarray(logits)
    if "head_1_served" in faults:
        logits = np.roll(logits, -c.vocab_size, axis=-1)
    return logits, rows, summary


# ---------------------------------------------------------------------------
# what the program computes, and the comparison
# ---------------------------------------------------------------------------


def served(engine, seed: int, *, prompts=CHECK_PROMPTS,
           steps: int = CHECK_DECODE_STEPS) -> dict:
    """What the program computes for the check's seeded prompts, at the
    ENGINE's shapes and in its two pools through its own block manager's
    tables (the engine has to be idle: every block is the check's while it
    runs and is returned at its end), the engine's own compiled programs
    beside the model's functions:

    - every live slot of ``reference/afmoe.py`` ``slot_plan`` (a period is
      each prompt once and an idle slot; every period five tokens shorter)
      is admitted and grown by the block manager and prefilled alone, the
      first period's by the model's prefill with every head's logits out and
      every slot by the engine's own greedy prefill program, whose token and
      log-probability are held to those logits;
    - then ``steps`` decode steps in the engine's chunks, all live slots in
      one batch: first the engine's own decode program over EVERY live slot,
      those whose window closes inside this chunk among them, then the
      model's function over the same steps with each step's logits out. Both
      programs commit as they go, and a slot that has crossed an edge has
      written its new window over the old one's first rows, so the ring
      blocks a chunk's rows fall in (two a slot where a chunk is no longer
      than a block) are copied before the engine's program and put back
      after it: the model's function starts from the rows the engine's
      did. The engine's tokens and log-probabilities are held to the
      model's logits step by step while the tokens agree, across an edge
      as anywhere else."""
    cfg, manager = engine.config, engine.block_mgr
    if not all(slot.free for slot in engine.slots):
        raise RuntimeError("the engine is serving: the check writes its pools")
    plan = slot_plan(cfg.slots, prompts)
    admitted = []
    try:
        for slot, _, size in plan:
            if not manager.can_admit(size + steps + 1):
                raise RuntimeError(
                    f"the check's prompts do not fit the pools: "
                    f"{manager.stats()}")
            manager.admit(slot, size + steps + 1)
            admitted.append(slot)
            manager.ensure_capacity(slot, size + steps + 1)
        return _served(engine, seed, prompts, plan, manager.tables.copy(),
                       steps)
    finally:
        for slot in admitted:
            manager.release(slot)


def _served(engine, seed, prompts, plan, tables, steps) -> dict:
    from langstream_tpu.models.eva import (
        eva_decode_chunk_paged,
        eva_prefill_paged,
    )

    c, cfg, layout = engine.model_config, engine.config, engine.paged_layout
    bs, slots, width = layout.block_size, cfg.slots, layout.max_blocks_per_slot
    W, C, V = c.window, c.chunk, c.vocab_size
    rng = np.random.default_rng(int(seed) % (2 ** 32))
    tokens = {slot: rng.integers(0, V, size=size, dtype=np.int32)
              for slot, _, size in plan}
    key = jax.random.PRNGKey(0)
    kernel = engine.paged_read_kernel

    def padded(slot):
        row = np.zeros((1, _bucket_of(tokens[slot].size)), np.int32)
        row[0, : tokens[slot].size] = tokens[slot]
        return (jnp.asarray(row), jnp.asarray([tokens[slot].size], jnp.int32),
                jnp.asarray(tables[slot][None]))

    model_prefill = jax.jit(
        lambda p, t, n, pk, pv, wp, tb: eva_prefill_paged(
            c, p, t, n, pk, pv, wp, tb, kernel=kernel),
        donate_argnums=(3, 4, 5))
    engine_prefill = engine._prefill_fn(GREEDY)

    def prefill_as_the_engine(slot):
        row, n, table = padded(slot)
        out = engine_prefill(
            engine.params, engine.cache_k, engine.cache_v, engine.state, row,
            n, table, key, jnp.zeros((1,), jnp.float32),
            jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.float32))
        engine.cache_k, engine.cache_v, engine.state = out[2], out[3], out[4]
        return int(np.asarray(out[0])[0]), float(np.asarray(out[1])[0])

    first = np.zeros((slots,), np.int32)
    lengths = np.zeros((slots,), np.int32)
    followed, heads0 = [], {}
    first_shortfall = first_error = 0.0
    for slot, _, size in plan:
        if slot < len(prompts):
            row, n, table = padded(slot)
            _, engine.cache_k, engine.cache_v, engine.state, heads = \
                model_prefill(engine.params, row, n, engine.cache_k,
                              engine.cache_v, engine.state, table)
            heads0[slot] = np.asarray(heads, np.float32)[0]
            followed.append(slot)
        token, logprob = _on_engine(
            engine, f"prefill program of the {_bucket_of(size)} bucket",
            prefill_as_the_engine, slot)
        if slot in heads0:
            shortfall, error = _held_to_logits(
                token, logprob, heads0[slot][:V], True)
            first_shortfall = max(first_shortfall, shortfall)
            first_error = max(first_error, error)
            token = int(heads0[slot][:V].argmax(-1))
        first[slot], lengths[slot] = token, size
    _log(f"{len(plan)} slots prefilled")

    live = lengths > 0
    tables_dev = jnp.asarray(tables)
    window = engine._read_blocks_for(int(lengths.max()) + steps)
    model_decode = jax.jit(
        lambda p, t0, n, pk, pv, wp, k: eva_decode_chunk_paged(
            c, p, t0, n, jnp.asarray(live), pk, pv, wp, tables_dev,
            lambda logits, key: (jnp.argmax(logits, -1).astype(jnp.int32),
                                 jnp.zeros(logits.shape[:1], jnp.float32)),
            key, k, window, kernel=kernel),
        static_argnums=6, donate_argnums=(3, 4, 5))
    sampler = (jnp.zeros((slots,), jnp.float32), jnp.zeros((slots,), jnp.int32),
               jnp.ones((slots,), jnp.float32))

    def decode_as_the_engine(t0, n, active, k):
        packed, _, _, engine.cache_k, engine.cache_v, engine.state = \
            engine._decode_fn(GREEDY, window, k)(
                engine.params, engine.cache_k, engine.cache_v, engine.state,
                t0, n, jnp.asarray(active), tables_dev, key, *sampler)
        flat = np.asarray(packed)       # tokens, then the logprobs' bits
        return (flat[: k * slots].reshape(k, slots),
                flat[k * slots : 2 * k * slots].view(np.float32).reshape(k, slots))

    live_slots = np.flatnonzero(live)

    def touched(at, k):
        """The ring's blocks that each live slot's rows ``at .. at + k - 1``
        fall in, by the table's columns from the first row's on."""
        cols = at[live_slots, None] // bs + np.arange((k + bs - 2) // bs + 1)
        return jnp.asarray(tables[
            live_slots[:, None], width + np.minimum(cols, width - 1)].ravel())

    # a block at a time, as slices: a gather along the pool's second axis
    # copies the whole pool first (3.2 GB at the cell's 24 slots)
    L, HD = c.layers, c.heads * c.head_dim

    @jax.jit
    def copy_blocks(ring, blocks):
        """``{"k", "v"}: (n, layers, 1, block, lanes)``."""
        return {a: jax.lax.map(lambda b: jax.lax.dynamic_slice(
            ring[a], (0, b, 0, 0), (L, 1, bs, HD)), blocks) for a in "kv"}

    @functools.partial(jax.jit, donate_argnums=0)
    def put_back(ring, blocks, held):
        return jax.lax.fori_loop(0, blocks.shape[0], lambda i, ring: {
            a: jax.lax.dynamic_update_slice(
                ring[a], held[a][i], (0, blocks[i], 0, 0)) for a in "kv"},
            ring)

    chunk = max(1, min(int(cfg.decode_chunk), steps))
    t0, n = jnp.asarray(first), jnp.asarray(lengths)
    made, chunk_heads = [], []
    decode_shortfall = decode_error = 0.0
    compared = parted = across = crossings = 0
    at = lengths.copy()
    for k in [chunk] * (steps // chunk) + [steps % chunk] * bool(steps % chunk):
        # the slots whose rows at + 0 .. at + k - 1 reach into a new window
        crosses = live & ((at + k - 1) // W > np.maximum(at - 1, 0) // W)
        crossings += int(crosses.sum())
        blocks = touched(at, k)
        held = copy_blocks(engine.state, blocks)
        theirs, their_logprobs = _on_engine(
            engine, f"decode program of {k} steps", decode_as_the_engine,
            t0, n, live, k)
        engine.state = put_back(engine.state, blocks, held)
        out = model_decode(engine.params, t0, n, engine.cache_k,
                           engine.cache_v, engine.state, k)
        t0, n, engine.cache_k, engine.cache_v, engine.state = out[2:7]
        ours = np.asarray(out[0])                                 # (k, slots)
        heads = np.asarray(out[7], np.float64)               # (k, slots, P V)
        agreed = np.cumprod(np.concatenate(
            [np.ones((1, slots), bool), theirs == ours])[:-1], axis=0) > 0
        agreed &= live[None]
        shortfall, error = _held_to_logits(
            theirs, their_logprobs, heads[..., :V], agreed)
        decode_shortfall = max(decode_shortfall, shortfall)
        decode_error = max(decode_error, error)
        compared += int(agreed.sum())
        parted += int((agreed & (theirs != ours)).sum())
        across += int(agreed[:, crosses].sum())
        made.append(ours[:, followed])
        chunk_heads.append(heads[:, followed, :V].astype(np.float32))
        at = at + k * live
    _log(f"{steps} decode steps in chunks of {chunk}")
    made, chunk_heads = np.concatenate(made), np.concatenate(chunk_heads)

    def held_rows(slot):
        """The first layer's rows as the pools hold them now: ``(ring
        positions, their K and V rows, summary rows' chunks, their rows)``:
        the open window's exact rows, and the summary rows of every closed
        window."""
        end = tokens[slot].size + steps
        positions = np.arange((end - 1) // W * W, end)
        blocks = tables[slot, width + positions // bs]
        chunks = np.arange((end - 1) // W * (W // C))
        sblocks = tables[slot, chunks // bs]
        take = jax.jit(lambda pool, b, r: pool[0, b, r].astype(jnp.float32))
        ring = np.concatenate([
            np.asarray(take(engine.state[a], blocks, positions % bs))
            for a in "kv"], axis=-1)
        summary = np.concatenate([
            np.asarray(take(pool, sblocks, chunks % bs))
            for pool in (engine.cache_k, engine.cache_v)], axis=-1)
        return positions, ring, chunks, summary

    return {
        "slots": [{
            "slot": slot,
            "sequence": np.concatenate(
                [tokens[slot], first[slot : slot + 1], made[:-1, i]]),
            "positions": list(range(
                tokens[slot].size - 1, tokens[slot].size + steps)),
            "logits": np.concatenate(
                [heads0[slot][None, :V], chunk_heads[:, i]]),
            "heads": heads0[slot],
            "rows": held_rows(slot),
        } for i, slot in enumerate(followed)],
        "engine": {
            "engine_first_token_shortfall": first_shortfall,
            "engine_first_logprob_error": first_error,
            "engine_decode_token_shortfall": decode_shortfall,
            "engine_decode_logprob_error": decode_error,
            "engine_decode_steps_compared": compared,
            "engine_decode_steps_parted": parted,
            "engine_decode_steps_across_an_edge": across,
        },
        "facts": {
            "prompts": [int(p) for p in prompts],
            "slots_live": len(plan), "slots_idle": slots - len(plan),
            "rows_live": int(lengths.sum()), "decode_steps": steps,
            "decode_chunk": chunk, "kernel": kernel,
            "window_edges_crossed": crossings,
            "chunk_closes": int(sum(
                (size + steps) // C - size // C for _, _, size in plan)),
            "kv_quantize": cfg.kv_quantize, "quantize": cfg.quantize,
        },
    }


def compare(slots: list, wants: list, tolerance: dict, vocab: int) -> dict:
    """Head 0's logits at every compared position (RMS error over the
    vocabulary as a share of the reference's spread, its mean over the
    positions, and correlation), all heads at the prefill's last position,
    the first layer's ring and summary rows as the pools hold them (RMS
    error as a share of the reference rows' RMS), and the share of either
    side's logits that lie on bfloat16's grid (``fp32_logits``: rounding 320
    logits to bfloat16 adds 0.16% in quadrature to an RMS share of 0.63%,
    which no limit on the share can tell)."""
    got = np.concatenate([s["logits"] for s in slots])
    want = np.concatenate([w["logits"][:, :vocab] for w in wants])
    rms = np.sqrt(np.mean((got - want) ** 2, axis=-1)) / np.std(want, axis=-1)
    corr = [float(np.corrcoef(g, w)[0, 1]) for g, w in zip(got, want)]
    heads_got = np.stack([s["heads"] for s in slots])
    heads_want = np.stack([w["logits"][0] for w in wants])

    def share(a, b):
        if not a.size:
            return 0.0
        return float(np.sqrt(np.mean((a - b) ** 2)) / np.sqrt(np.mean(b ** 2)))

    ring_got = np.concatenate([s["rows"][1] for s in slots])
    ring_want = np.concatenate([w["ring"] for w in wants])
    sum_got = np.concatenate([s["rows"][3] for s in slots])
    sum_want = np.concatenate([w["summary"] for w in wants])
    def on_grid(a):
        """The share of float32 values that bfloat16 holds exactly: all of
        them where the logits were made in bfloat16, a few in 65,536 where
        they were accumulated and left in float32."""
        bits = np.ascontiguousarray(a, np.float32).view(np.uint32)
        return float(np.mean((bits & 0xFFFF) == 0))

    report = {
        "positions": [{"rms_share": float(r), "correlation": c}
                      for r, c in zip(rms, corr)],
        "logits_bfloat16_grid_share": max(on_grid(got), on_grid(want)),
        "worst_rms_share": float(rms.max()),
        "mean_rms_share": float(rms.mean()),
        "worst_correlation": min(corr),
        "heads_rms_share": float(
            (np.sqrt(np.mean((heads_got - heads_want) ** 2, axis=-1))
             / np.std(heads_want, axis=-1)).max()),
        "ring_rows_rms_share": share(ring_got, ring_want),
        "ring_rows_compared": int(ring_got.shape[0]),
        "summary_rows_rms_share": share(sum_got, sum_want),
        "summary_rows_compared": int(sum_got.shape[0]),
        "tolerance": dict(tolerance),
    }
    report["passed"] = bool(
        report["worst_rms_share"] <= tolerance["rms_share"]
        and report["mean_rms_share"] <= tolerance["mean_rms_share"]
        and report["worst_correlation"] >= tolerance["min_correlation"]
        and report["heads_rms_share"] <= tolerance["heads_rms_share"]
        and report["logits_bfloat16_grid_share"]
        <= tolerance["logits_bfloat16_grid_share"]
        and report["ring_rows_rms_share"] <= tolerance["ring_rows_rms_share"]
        and report["summary_rows_rms_share"]
        <= tolerance["summary_rows_rms_share"])
    return report


def judge(engine, got: dict, tolerance: dict, faults=()) -> dict:
    """:func:`served` output against this file's full forward over each
    followed slot's tokens, held to ``tolerance``, with what :func:`served`
    read of the engine's own prefill and decode programs."""
    c = engine.model_config
    wants = []
    for slot in got["slots"]:
        logits, rows, summary = forward(
            c, engine.params, slot["sequence"], slot["positions"], faults)
        positions, _, chunks, _ = slot["rows"]
        # a reference that cuts other chunks has other rows: the comparison
        # is by position all the same, over what it has
        summary = np.concatenate([summary, np.zeros(
            (max(0, len(chunks) - summary.shape[0]), summary.shape[1]),
            np.float32)])
        wants.append({"logits": logits, "ring": rows[positions],
                      "summary": summary[chunks]})
        _log(f"the reference's forward over slot {slot['slot']}: "
             f"{len(slot['sequence'])} tokens")
    report = compare(got["slots"], wants, tolerance, c.vocab_size)
    report.update(got["engine"])
    report["passed"] = bool(report["passed"] and all(
        report[k] <= tolerance[k] for k in (
            "engine_first_token_shortfall", "engine_first_logprob_error",
            "engine_decode_token_shortfall", "engine_decode_logprob_error")))
    report.update(got["facts"])
    return report


def check_engine(engine, seed: int, tolerance: dict, **how) -> dict:
    """The served model against the reference, outside any window. An engine
    that serves another family under the configuration's name (a commit
    before the family existed) is refused at once."""
    if getattr(engine, "family", None) != "eva":
        raise RuntimeError(
            f"model {engine.config.model!r} is not served by the eva family "
            f"here: there is nothing to compare")
    # a test-size configuration's file may state smaller sizes for the check
    # beside its limits (tests/bench/fixtures/eva); the cell's states none
    if "check_prompts" in tolerance:
        how.setdefault("prompts", tuple(map(int, tolerance["check_prompts"])))
    if "check_decode_steps" in tolerance:
        how.setdefault("steps", int(tolerance["check_decode_steps"]))
    return judge(engine, served(engine, seed, **how), tolerance)
