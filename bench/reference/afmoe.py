"""The ``afmoe`` decoder (Trinity-Large-Preview: window and full attention in
one stack, sandwich norms, sigmoid-routed experts beside a shared one),
written plainly.

A float32 ``jax.numpy`` forward pass under
``default_matmul_precision("highest")``, one layer at a time from the
engine's own parameters: no cache, no kernels, no batching, every layer's
attention over the whole sequence under a full ``(T, T)`` mask built from the
layer's kind (taken a block of query rows at a time, so that it fits beside
the engine). From the published ``config.json`` (``hidden_act`` silu,
``rms_norm_eps`` 1e-5, ``rope_theta`` 10000, ``route_scale`` 2.448,
``mup_enabled``, no biases) and, for what its keys do not settle, the public
``transformers`` ``modeling_afmoe.py`` (the configuration's ``assumed``)::

    x0      = Embed[token] * sqrt(hidden)
    layer l : x <- x + N_post_attn(Attn_l(N_in(x)));  x <- x + N_post_mlp(FFN_l(N_pre_mlp(x)))
    logits  = N_final(x) @ Head                                         # untied

    Attn_l  : q = N_q(h W_q) (48 heads of 128), k = N_k(h W_k), v = h W_v (8 heads of 128), g = h W_g (6144)
              N_q, N_k: RMSNorm over each head's 128, one gain of 128 shared by the heads
              sliding_attention: rotate q and k (half-split rotary over the whole 128, theta 1e4);
                                 query i sees keys j with 0 <= i - j < 4096
              full_attention   : no rotation; query i sees every key j <= i
              softmax(q k^T / sqrt(128) + mask) v in float32, 6 query heads a key-value head
              W_o [ o * sigmoid(g) ]
    FFN_l   : dense layers: W_down[ silu(h W_gate) * (h W_up) ]                                     (12288)
              expert layers: s = sigmoid(h W_r) over all 256 (float32); the 4 largest of s + b chosen
              (b the expert_bias, in the choice alone); w_e = 2.448 * s_e / (sum of the four s + 1e-20);
              Shared(h) + sum_e w_e Expert_e(h), each gated of width 3072

The numbers of the rule (1e4, 1e-5, 2.448, 1e-20) are written below and not
read from the program's configuration, which gives the sizes, the counts,
the window and which layers are of which kind.

**The share**: only the chosen experts this chip holds (``[expert_first,
expert_first + experts_held)``) are computed, one after another, each cast
to float32 by itself; the router scores all 256 and the chosen scores are
renormalised over the four wherever they live, so the share needs nothing
of the other chips; what their experts would add is left out, as in the
program.

:func:`check_engine` is the comparison a run's ``correct`` rests on, made at
the ENGINE's shapes, in its own two pools through its own block manager's
tables and with its own compiled programs beside the model's functions
(:func:`served`): two seeded prompts past the window, one in the 8,192
bucket and one in the 16,384 bucket, repeated five tokens shorter over two
of every three slots with the third idle; every live slot prefilled alone by
the engine's own prefill program; then 160 decode steps over all slots in
the engine's chunks of 32 (five chunks: every slot's ring takes over at
least two blocks that the chunk before still read), each chunk through the
engine's own decode program and through the model's decode function with the
logits out. This file's forward follows the first two slots' tokens and the
program's expert choices: the logits at every compared position (never
tokens), the first window layer's K and V rows as its ring holds them at the
end (``window_rows_rms_share``: a row released or overwritten too early, or
a pool kept below bfloat16, shows here first), and each routing choice
against this file's own ranking. Three more readings: the program's router
alone on this file's float32 input of the first expert layer
(``router_alone_differing_share``); the ENGINE's prefill programs' token and
log-probability against the logits read above (``engine_first_*``); and the
ENGINE's decode program's tokens and log-probabilities of every live slot
and step against the logits the model's function read from the same state
(``engine_decode_*``).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

# Trinity-Large-Preview/config.json
ROPE_THETA = 10000.0
RMS_NORM_EPS = 1e-5
ROUTE_SCALE = 2.448
ROUTE_NORM_EPS = 1e-20

#: what the check has to tell from the served model: the forward with each
#: injected has to come out as not passed against the program's output
#: (tools/hybrid_probe.py --config trinity-large-preview-ep8 --faults)
FAULTS = (
    "window_one_block_short", "window_one_block_long", "rope_on_full",
    "no_rope_on_window", "no_post_norm", "no_route_scale", "no_qk_norm",
    "no_output_gate", "bfloat16_router", "rows_below_bfloat16",
)
#: what no comparison of outputs can hold: nothing of this layer, so far
UNOBSERVABLE = ()

#: tokens of the check's prompts: one in the 8,192 bucket and one in the
#: 16,384 bucket, both past the window; :func:`slot_plan` spreads them
CHECK_PROMPTS = (6000, 9000)
CHECK_LENGTH_STEP = 5
CHECK_DECODE_STEPS = 160
#: the engine's sampler mode (top-p, top-k, all greedy) of a batch at
#: temperature 0, what a window's programs are compiled for
GREEDY = (False, False, True)
#: query rows of one block of the reference's attention (one key-value
#: head's queries at a time)
QUERY_BLOCK = 512
#: seconds one of the engine's own programs may take (it compiles on its
#: first call)
ENGINE_PROGRAM_S = 600.0


def f32(t):
    return jnp.asarray(t, dtype=jnp.float32)


def rms_norm(x, w):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + RMS_NORM_EPS) * w


def rope(x, positions):
    """Half-split rotary embedding of ``x (T, heads, D)`` at ``positions
    (T,)``: the two halves of the head rotated against each other."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (ROPE_THETA ** (np.arange(half, dtype=np.float64) / half))
    angles = f32(positions)[:, None] * f32(inv_freq)[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def below_bfloat16(x):
    """``x`` rounded to 5 bits of mantissa: the nearest precision below the
    pool's bfloat16 (8 bits)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=4)


def window_of(c, kind: str, faults=()):
    """The keys a query of a layer of ``kind`` sees behind it: the window's
    rows (the query's own among them), or None for all of them."""
    if kind != "W":
        return None
    block = max(1, c.window // 64)
    return (c.window - block * ("window_one_block_short" in faults)
            + block * ("window_one_block_long" in faults))


def attention(u, w, c, kind: str, faults=()):
    """``(W_o [o * sigmoid(g)], K rows (T, Kh*D), V rows)`` of normed rows
    ``u (T, H)`` of one sequence; the rows are what the layer's pool keeps
    of a position (the keys normed, and rotated on a window layer)."""
    T = u.shape[0]
    D, Kh = c.head_dim, c.kv_heads
    G = c.heads // Kh
    positions = jnp.arange(T)
    q = (u @ w["wq"]).reshape(T, c.heads, D)
    k = (u @ w["wk"]).reshape(T, Kh, D)
    v = (u @ w["wv"]).reshape(T, Kh, D)
    if "no_qk_norm" not in faults:
        q, k = rms_norm(q, w["q_norm"]), rms_norm(k, w["k_norm"])
    if (kind == "W" and "no_rope_on_window" not in faults) or (
            kind == "F" and "rope_on_full" in faults):
        q, k = rope(q, positions), rope(k, positions)
    if "rows_below_bfloat16" in faults:
        k, v = below_bfloat16(k), below_bfloat16(v)
    window = window_of(c, kind, faults)
    pad = (-T) % QUERY_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        (T + pad) // QUERY_BLOCK, QUERY_BLOCK, Kh, G, D)

    def one_head(kh):
        kk, vv = k[:, kh], v[:, kh]                                # (T, D)

        def block(args):
            qb, i0 = args                                          # (Q, G, D)
            s = jnp.einsum("qgd,td->gqt", qb, kk) / math.sqrt(D)
            behind = (i0 + jnp.arange(QUERY_BLOCK))[:, None] - positions[None, :]
            mask = behind >= 0
            if window is not None:
                mask = mask & (behind < window)
            s = jnp.where(mask[None], s, -jnp.inf)
            # a padded query row past T sees its own position's keys: all
            # of them real, and dropped below
            return jnp.einsum("gqt,td->qgd", jax.nn.softmax(s, axis=-1), vv)

        return jax.lax.map(block, (
            qp[:, :, kh], jnp.arange(qp.shape[0]) * QUERY_BLOCK))  # (nb,Q,G,D)

    o = jnp.stack([one_head(kh) for kh in range(Kh)], axis=2)      # (nb,Q,Kh,G,D)
    o = o.reshape(T + pad, c.heads * D)[:T]
    if "no_output_gate" not in faults:
        o = o * jax.nn.sigmoid(u @ w["wg"])
    return o @ w["wo"], k.reshape(T, Kh * D), v.reshape(T, Kh * D)


def gated(u, w_in, w_out):
    """``(silu(a) * b) W_out`` with ``[a | b] = u W_in^T``; ``w_in (2 I,
    H)`` output-major, ``w_out (I, H)``."""
    a, b = jnp.split(u @ w_in.T, 2, axis=-1)
    return (jax.nn.silu(a) * b) @ w_out


def scores(u, w, faults=()):
    """Sigmoid scores ``(T, experts)`` of normed rows, float32 (the
    program's router weights are the model's type; their logits float32)."""
    if "bfloat16_router" in faults:
        bf = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        return bf(jax.nn.sigmoid(bf(bf(u) @ bf(w["router"]))))
    return jax.nn.sigmoid(u @ w["router"])


def gates(s, chosen, faults=()):
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + ROUTE_NORM_EPS)
    return weights if "no_route_scale" in faults else weights * ROUTE_SCALE


def route(u, w, c, faults=()):
    """``(chosen (T, k), weights (T, k))``: the ``k`` largest of score +
    bias, the chosen scores (without the bias) renormalised and scaled."""
    s = scores(u, w, faults)
    _, chosen = jax.lax.top_k(s + w["bias"], c.experts_per_token)
    return chosen, gates(s, chosen, faults)


def audit(u, w, c, forced, faults=()):
    """The program's choices ``forced (T, k)`` against this file's ranking:
    ``(chosen, weights, shortfall (T,), differs (T,))``; the forward then
    follows the program's experts with this file's weights for them.
    ``shortfall`` is how far the worst of the program's choices lies under
    this file's k-th score + bias; ``differs`` whether the two sets differ."""
    s = scores(u, w, faults)
    ranked = s + w["bias"]
    kth = jax.lax.top_k(ranked, c.experts_per_token)[0][:, -1]
    _, own = jax.lax.top_k(ranked, c.experts_per_token)
    worst = jnp.min(jnp.take_along_axis(ranked, forced, axis=-1), axis=-1)
    differs = jnp.any(jnp.sort(own, -1) != jnp.sort(forced, -1), axis=-1)
    return forced, gates(s, forced, faults), jnp.maximum(kth - worst, 0.0), differs


def experts(u, w, c, faults=(), first=None, held=None, forced=None,
            shared=True):
    """The chosen experts among ``held`` from ``first`` (this chip's share
    unless given), one after another, plus the shared expert (``shared``).
    ``w["w_up"]`` and ``w["w_down"]`` may be of any float type: each expert
    is cast to float32 by itself. ``w["w_up"][e]`` is expert ``first + e``.
    With ``forced (T, k)`` the experts are the ones given (:func:`audit`)."""
    first = c.expert_first if first is None else first
    held = c.experts_held if held is None else held
    if forced is None:
        chosen, weights = route(u, w, c, faults)
        report = None
    else:
        chosen, weights, shortfall, differs = audit(u, w, c, forced, faults)
        report = (shortfall, differs)
    out = jnp.zeros_like(u)
    for e in range(held):
        gate = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        out = out + gate[:, None] * gated(u, f32(w["w_up"][e]), f32(w["w_down"][e]))
    if shared:
        out = out + gated(u, w["ws_up"].T, w["ws_down"])
    return out, (chosen if report is None else report)


def forward(config, params, tokens, positions, faults=(), forced=None):
    """The full forward over ``tokens``: ``(logits (len(positions), V),
    routing, the first window layer's rows (T, 2 x Kh*D): K then V)``, all
    numpy. ``routing`` is the chosen experts ``(expert layers, T, k)``; or,
    with ``forced (expert layers, T, k)`` (the program's choices, which the
    forward then follows), the audit of them: ``{"shortfall", "differs":
    (expert layers, T), "first_input": (T, hidden)}``."""
    c = config
    routed_stacks = ("w_up", "w_down")     # cast an expert at a time
    cast = lambda t: jax.tree.map(f32, t)  # noqa: E731
    attend = {kind: jax.jit(lambda x, w, kind=kind: attention(
        rms_norm(x, w["norm"]), w, c, kind, faults)) for kind in "WF"}
    post = ((lambda a, w: a) if "no_post_norm" in faults
            else jax.jit(lambda a, w: rms_norm(a, w["post_norm"])))
    dense = jax.jit(lambda x, w: gated(
        rms_norm(x, w["norm"]), w["w_up"].T, w["w_down"]))
    norm = jax.jit(lambda x, w: rms_norm(x, w["norm"]))
    route_own = jax.jit(lambda u, w: experts(u, w, c, faults))
    route_forced = jax.jit(lambda u, w, f: experts(u, w, c, faults, forced=f))
    routing, window_rows, first_input = [], None, None
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][jnp.asarray(tokens)]) * math.sqrt(c.hidden)
        for layer, (lp, kind) in enumerate(zip(params["layers"], c.layer_kinds)):
            w = cast(lp["attn"])
            out, k_rows, v_rows = attend[kind](x, w)
            if kind == "W" and window_rows is None:
                window_rows = np.concatenate(
                    [np.asarray(k_rows), np.asarray(v_rows)], axis=-1)
            x = (x + post(out, w)).block_until_ready()
            del w, out, k_rows, v_rows
            if layer < c.dense_layers:
                w = cast(lp["ffn"])
                x = x + post(dense(x, w), w)
                continue
            i = layer - c.dense_layers
            w = {k: a if k in routed_stacks else f32(a)
                 for k, a in lp["moe"].items()}
            u = norm(x, w)
            if forced is None:
                out, chosen = route_own(u, w)
                routing.append(np.asarray(chosen))
            else:
                if i == 0:      # what the first router reads, in float32
                    first_input = np.asarray(u)
                out, report = route_forced(u, w, jnp.asarray(forced[i]))
                routing.append([np.asarray(r) for r in report])
            x = (x + post(out, w)).block_until_ready()
            del w, out
        x = rms_norm(x[jnp.asarray(positions)], f32(params["final_norm"]))
        logits = np.asarray(x @ f32(params["lm_head"]))
    if forced is not None:
        routing = {"shortfall": np.stack([r[0] for r in routing]),
                   "differs": np.stack([r[1] for r in routing]),
                   "first_input": first_input}
    else:
        routing = np.stack(routing)
    return logits, routing, window_rows


# ---------------------------------------------------------------------------
# what the program computes, and the comparison
# ---------------------------------------------------------------------------


def _log(message: str) -> None:
    print(f"[afmoe check] {message}", flush=True)


def _bucket_of(n: int) -> int:
    bucket = 32
    while bucket < n:
        bucket *= 2
    return bucket


def slot_plan(slots: int, prompts) -> list[tuple[int, int, int]]:
    """``(slot, prompt, tokens)`` of the check's live slots: slot ``s``
    holds prompt ``s % (len(prompts) + 1)``, the last of each such period
    stays idle, and each period's prompts are :data:`CHECK_LENGTH_STEP`
    tokens shorter than those of the period before, so that no two slots
    hold equal lengths. The first period's slots are the ones the reference
    follows."""
    period = len(prompts) + 1
    if slots < period:
        raise RuntimeError(f"{slots} slots cannot hold the check's "
                           f"{len(prompts)} prompts and an idle slot")
    return [(s, s % period,
             max(1, prompts[s % period] - CHECK_LENGTH_STEP * (s // period)))
            for s in range(slots) if s % period < len(prompts)]


def _on_engine(engine, what: str, fn, *args):
    """``fn(*args)`` on the engine's dispatch thread, as a window's
    dispatches run; a program that never returns is named."""
    try:
        return engine._executor.submit(fn, *args).result(
            timeout=ENGINE_PROGRAM_S)
    except TimeoutError:
        raise RuntimeError(f"the engine's {what} did not return in "
                           f"{ENGINE_PROGRAM_S:.0f} s") from None


def _held_to_logits(tokens, logprobs, logits, compared):
    """The engine's greedy ``tokens`` and ``logprobs`` (any shape) against
    ``logits (..., V)`` where ``compared``: ``(shortfall, error)``, how far
    the worst token lies under the best logit in spreads of the logits, and
    the worst log-probability against their log-softmax."""
    read = np.asarray(logits, np.float64)
    best = read.max(-1)
    picked = np.take_along_axis(read, np.asarray(tokens)[..., None], -1)[..., 0]
    lse = best + np.log(np.sum(np.exp(read - best[..., None]), -1))
    shortfall = np.where(compared, (best - picked) / read.std(-1), 0.0)
    error = np.where(compared, np.abs(np.asarray(logprobs) - (picked - lse)), 0.0)
    return float(shortfall.max(initial=0.0)), float(error.max(initial=0.0))


def served(engine, seed: int, *, prompts=CHECK_PROMPTS,
           steps: int = CHECK_DECODE_STEPS) -> dict:
    """What the program computes for the check's seeded prompts, at the
    ENGINE's shapes and in its own pools (the engine has to be idle: nothing
    a request reads is kept, every block is the check's while it runs and
    is returned at its end), the engine's own compiled programs beside the
    model's functions:

    - every live slot of :func:`slot_plan` is admitted and grown by the
      engine's own block manager (both kinds: the tables the programs are
      handed are its own) and prefilled by the engine's own greedy prefill
      program, one prompt a dispatch as its ``prefill-batch`` of 1
      dispatches them; the first period's prompts first by the model's
      prefill with the logits out, so that the engine's token and
      log-probability are held to those logits (``engine_first_*``) and the
      rows the pools keep are the engine program's;
    - then ``steps`` decode steps over all slots in chunks of the engine's
      ``decode-chunk``: each chunk first through the engine's own decode
      program (its slots, its table width, its packed fetch), then from the
      same tokens and lengths through the model's decode function with the
      logits out, which writes the rows the next chunk reads. The engine's
      tokens and log-probabilities of every live slot are held to those
      logits up to and at the first step where the two programs choose
      another token: ``engine_decode_*``.

    The reference then follows the first period's slots."""
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
    from langstream_tpu.models.swa import (
        swa_decode_chunk_paged,
        swa_prefill_paged,
    )

    c, cfg, layout = engine.model_config, engine.config, engine.paged_layout
    bs, slots = layout.block_size, cfg.slots
    width = layout.max_blocks_per_slot
    rng = np.random.default_rng(int(seed) % (2 ** 32))
    tokens = {slot: rng.integers(0, c.vocab_size, size=size, dtype=np.int32)
              for slot, _, size in plan}
    key = jax.random.PRNGKey(0)

    def padded(slot):
        row = np.zeros((1, _bucket_of(tokens[slot].size)), np.int32)
        row[0, : tokens[slot].size] = tokens[slot]
        return (jnp.asarray(row),
                jnp.asarray([tokens[slot].size], jnp.int32),
                jnp.asarray(tables[slot][None]))

    model_prefill = jax.jit(
        lambda p, t, n, pk, pv, wp, tb: swa_prefill_paged(
            c, p, t, n, pk, pv, wp, tb), donate_argnums=(3, 4, 5))
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
    followed, logits0, chose0, batches = [], {}, {}, []
    first_shortfall = first_error = 0.0
    for slot, _, size in plan:
        if slot < len(prompts):
            row, n, table = padded(slot)
            logits, engine.cache_k, engine.cache_v, engine.state, routed = \
                model_prefill(engine.params, row, n, engine.cache_k,
                              engine.cache_v, engine.state, table)
            logits0[slot] = np.asarray(logits, np.float32)[0]
            chose0[slot] = np.asarray(routed)[:, 0, :size]
            followed.append(slot)
            batches.append({"bucket": _bucket_of(size), "rows": 1})
        token, logprob = _on_engine(
            engine, f"prefill program of the {_bucket_of(size)} bucket",
            prefill_as_the_engine, slot)
        if slot in logits0:
            shortfall, error = _held_to_logits(
                token, logprob, logits0[slot], True)
            first_shortfall = max(first_shortfall, shortfall)
            first_error = max(first_error, error)
            token = int(logits0[slot].argmax(-1))
        first[slot], lengths[slot] = token, size
    _log(f"{len(plan)} slots prefilled")

    def greedy_with_logits(logits, key):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits

    kernel = engine.paged_read_kernel
    live = lengths > 0
    active, tables_dev = jnp.asarray(live), jnp.asarray(tables)
    window = engine._read_blocks_for(int(lengths.max()) + steps)
    model_decode = jax.jit(
        lambda p, t0, n, pk, pv, wp, k: swa_decode_chunk_paged(
            c, p, t0, n, active, pk, pv, wp, tables_dev, greedy_with_logits,
            key, k, window, kernel=kernel),
        static_argnums=6, donate_argnums=(3, 4, 5))
    sampler = (jnp.zeros((slots,), jnp.float32), jnp.zeros((slots,), jnp.int32),
               jnp.ones((slots,), jnp.float32))

    def decode_as_the_engine(t0, n, k):
        packed, _, _, engine.cache_k, engine.cache_v, engine.state = \
            engine._decode_fn(GREEDY, window, k)(
                engine.params, engine.cache_k, engine.cache_v, engine.state,
                t0, n, active, tables_dev, key, *sampler)
        flat = np.asarray(packed)   # tokens, then the logprobs' bits, then loads
        return (flat[: k * slots].reshape(k, slots),
                flat[k * slots : 2 * k * slots].view(np.float32).reshape(k, slots))

    chunk = max(1, min(int(cfg.decode_chunk), steps))
    t0, n = jnp.asarray(first), jnp.asarray(lengths)
    made, chunk_logits, chose, tokens_made = [], [], [], []
    decode_shortfall = decode_error = 0.0
    compared = parted = 0
    for k in [chunk] * (steps // chunk) + [steps % chunk] * bool(steps % chunk):
        theirs, their_logprobs = _on_engine(
            engine, f"decode program of {k} steps", decode_as_the_engine,
            t0, n, k)
        out = model_decode(engine.params, t0, n, engine.cache_k,
                           engine.cache_v, engine.state, k)
        t0, n, engine.cache_k, engine.cache_v, engine.state = out[2:7]
        ours, logits = np.asarray(out[0]), np.asarray(out[1])   # (k, slots[, V])
        # a step is compared while every step of the chunk before it agreed
        agreed = np.cumprod(np.concatenate(
            [np.ones((1, slots), bool), theirs == ours])[:-1], axis=0) > 0
        agreed &= live[None]
        shortfall, error = _held_to_logits(theirs, their_logprobs, logits, agreed)
        decode_shortfall = max(decode_shortfall, shortfall)
        decode_error = max(decode_error, error)
        compared += int(agreed.sum())
        parted += int((agreed & (theirs != ours)).sum())
        tokens_made.append(ours[:, live])
        made.append(ours[:, followed])
        chunk_logits.append(logits[:, followed])
        chose.append(np.asarray(out[8]).swapaxes(0, 1)[:, :, followed])
    _log(f"{steps} decode steps in chunks of {chunk}")
    made, chunk_logits, chose = (np.concatenate(made), np.concatenate(chunk_logits),
                                 np.concatenate(chose, axis=1))
    ring_rows = engine.block_mgr.window_ring * bs

    def window_rows(slot):
        """The first window layer's K and V rows as its ring holds them
        now: ``(positions, rows (len(positions), 2 x Kh*D))`` of every
        position that was written and not yet overwritten."""
        size, end = tokens[slot].size, tokens[slot].size + steps
        positions = np.arange(max(0, size - c.window, end - ring_rows), end)
        blocks = tables[slot, width + positions // bs]
        take = jax.jit(lambda pool: pool[0, blocks, positions % bs].astype(
            jnp.float32))
        return positions, np.concatenate(
            [np.asarray(take(engine.state["k"])),
             np.asarray(take(engine.state["v"]))], axis=-1)

    def held_a_token(chosen):
        """Of the ``k`` experts a token chose, how many this chip holds, in
        the mean over the tokens, by expert layer."""
        here = (chosen >= c.expert_first) & (
            chosen < c.expert_first + c.experts_held)
        return [round(float(x), 4) for x in here.sum(-1).reshape(
            here.shape[0], -1).mean(-1)]

    stats = engine.block_mgr.stats()
    return {
        "slots": [{
            "slot": slot,
            # the sequence the program produced, for the reference to follow
            "sequence": np.concatenate(
                [tokens[slot], first[slot : slot + 1], made[:-1, i]]),
            "positions": list(range(
                tokens[slot].size - 1, tokens[slot].size + steps)),
            "logits": np.concatenate([logits0[slot][None], chunk_logits[:, i]]),
            "rows": window_rows(slot),
            "chose": np.concatenate([chose0[slot], chose[:, :, i]], axis=1),
        } for i, slot in enumerate(followed)],
        "engine": {
            "engine_first_token_shortfall": first_shortfall,
            "engine_first_logprob_error": first_error,
            "engine_decode_token_shortfall": decode_shortfall,
            "engine_decode_logprob_error": decode_error,
            "engine_decode_steps_compared": compared,
            "engine_decode_steps_parted": parted,
        },
        "facts": {
            "prompts": [int(p) for p in prompts], "prefill_batches": batches,
            "slots_live": len(plan), "slots_idle": slots - len(plan),
            "rows_live": int(lengths.sum()),
            "decode_steps": steps, "decode_chunk": chunk,
            "decode_window_blocks": int(window), "kernel": kernel,
            "window_slot_blocks_max": int(stats["window_slot_blocks_max"]),
            "window_ring_blocks": int(stats["window_ring_blocks"]),
            "held_pairs_a_token_prompt": held_a_token(np.concatenate(
                [chose0[slot] for slot in followed], axis=1)),
            "held_pairs_a_token_decode": held_a_token(chose),
            "decode_tokens_distinct": int(np.unique(
                np.concatenate(tokens_made)).size),
            "decode_tokens": int(np.concatenate(tokens_made).size),
            "router_dtype": jnp.dtype(c.router_dtype).name,
            "kv_quantize": cfg.kv_quantize, "quantize": cfg.quantize,
        },
    }


def compare(got, want, tolerance: dict, rows_got, rows_want, routing) -> dict:
    """Per compared position the RMS error over the vocabulary as a share of
    the reference's spread and the correlation; the first window layer's
    rows' RMS error as a share of the reference rows' RMS, held to
    ``window_rows_rms_share``; the routing audit's worst shortfall, held to
    ``routing_margin``, with the share of the first expert layer's tokens
    whose chosen set differs, held to ``first_routing_differing_share``."""
    rms = np.sqrt(np.mean((got - want) ** 2, axis=-1)) / np.std(want, axis=-1)
    corr = [float(np.corrcoef(g, w)[0, 1]) for g, w in zip(got, want)]
    shortfall, differs = routing["shortfall"], routing["differs"]
    report = {
        "positions": [{"rms_share": float(r), "correlation": c}
                      for r, c in zip(rms, corr)],
        "worst_rms_share": float(rms.max()), "worst_correlation": min(corr),
        "window_rows_rms_share": float(
            np.sqrt(np.mean((rows_got - rows_want) ** 2))
            / np.sqrt(np.mean(rows_want ** 2))),
        "window_rows_compared": int(rows_got.shape[0]),
        "routing_decisions": int(differs.size),
        "routing_decisions_differing": int(differs.sum()),
        "worst_routing_shortfall": float(shortfall.max()),
        "first_routing_differing_share": float(differs[0].mean()),
        "tolerance": dict(tolerance),
    }
    report["passed"] = bool(
        report["worst_rms_share"] <= tolerance["rms_share"]
        and report["worst_correlation"] >= tolerance["min_correlation"]
        and report["window_rows_rms_share"] <= tolerance["window_rows_rms_share"]
        and report["worst_routing_shortfall"] <= tolerance["routing_margin"]
        and report["first_routing_differing_share"]
        <= tolerance["first_routing_differing_share"])
    return report


def router_alone(engine, inputs, dtype, faults=()) -> float:
    """The share of ``inputs (T, H)``, this file's float32 inputs of the
    first expert layer rounded to the model's type, for which the program's
    expert layer (``moe_mixer`` with the first expert layer's weights, its
    router computing in ``dtype``) chooses another set than this file's
    ranking of the same rounded inputs."""
    from langstream_tpu.models.hybrid import moe_mixer

    c = dataclasses.replace(engine.model_config, router_dtype=jnp.dtype(dtype))
    first = engine.params["layers"][c.dense_layers]["moe"]
    u = jnp.asarray(inputs).astype(c.dtype)
    theirs = jax.jit(lambda u: moe_mixer(
        c, first, u, jnp.ones((u.shape[0],), bool))[2])(u)
    with jax.default_matmul_precision("highest"):
        own, _ = route(f32(u), {"router": f32(first["router"]),
                                "bias": f32(first["bias"])}, c, faults)
    return float(jnp.mean(jnp.any(
        jnp.sort(own, -1) != jnp.sort(theirs, -1), axis=-1)))


def judge(engine, got: dict, tolerance: dict, faults=()) -> dict:
    """:func:`served` output against this file's full forward over each
    slot's tokens and the same chosen experts, held to ``tolerance``: the
    positions, window rows and routing decisions of all slots together; the
    program's router alone on this file's inputs; and what :func:`served`
    read of the engine's own prefill and decode programs."""
    want, rows_want, rows_got, shortfall, differs, inputs = [], [], [], [], [], []
    for slot in got["slots"]:
        logits, routing, window_rows = forward(
            engine.model_config, engine.params, slot["sequence"],
            slot["positions"], faults, forced=slot["chose"])
        positions, rows = slot["rows"]
        want.append(logits)
        rows_want.append(window_rows[positions])
        rows_got.append(rows)
        shortfall.append(routing["shortfall"])
        differs.append(routing["differs"])
        inputs.append(routing["first_input"])
        _log(f"the reference's forward over slot {slot['slot']}: "
             f"{len(slot['sequence'])} tokens")
    report = compare(
        np.concatenate([slot["logits"] for slot in got["slots"]]),
        np.concatenate(want), tolerance,
        np.concatenate(rows_got), np.concatenate(rows_want),
        {"shortfall": np.concatenate(shortfall, axis=1),
         "differs": np.concatenate(differs, axis=1)})
    report["router_alone_differing_share"] = router_alone(
        engine, np.concatenate(inputs), got["facts"]["router_dtype"], faults)
    report.update(got["engine"])
    report["passed"] = bool(report["passed"] and all(
        report[k] <= tolerance[k] for k in (
            "router_alone_differing_share", "engine_first_token_shortfall",
            "engine_first_logprob_error", "engine_decode_token_shortfall",
            "engine_decode_logprob_error")))
    report.update(got["facts"])
    return report


def check_engine(engine, seed: int, tolerance: dict, **how) -> dict:
    """The served model against the reference, outside any window. An
    engine that serves another family under the configuration's name (a
    commit before the family existed) is refused at once."""
    if getattr(engine, "family", None) != "swa":
        raise RuntimeError(
            f"model {engine.config.model!r} is not served by the swa "
            f"family's programs here: there are no two pools to compare")
    # a test-size configuration's file may state smaller sizes for the check
    # beside its limits (tests/bench/fixtures/swa); the cell's states none
    if "check_prompts" in tolerance:
        how.setdefault("prompts", tuple(map(int, tolerance["check_prompts"])))
    if "check_decode_steps" in tolerance:
        how.setdefault("steps", int(tolerance["check_decode_steps"]))
    return judge(engine, served(engine, seed, **how), tolerance)
