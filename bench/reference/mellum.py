"""The ``mellum`` decoder (Mellum2-12B-A2.5B: window and full attention in one
stack, each kind with a rotation of its own, 64 softmax-routed experts top-8
and no shared one), written plainly.

A float32 ``jax.numpy`` forward pass under
``default_matmul_precision("highest")``, one layer at a time from the
engine's own parameters: no cache, no kernels, no batching, every layer's
attention over the whole sequence under a full ``(T, T)`` mask built from the
layer's kind (taken a block of query rows at a time, so that 8k positions fit
beside the engine). From the published ``config.json`` (``hidden_act`` silu,
``rms_norm_eps`` 1e-6, ``rope_parameters``, ``norm_topk_prob``, no biases,
every layer ``sparse``)::

    x0      = Embed[token]                                              # unscaled
    layer l : x <- x + Attn_l(N_in(x));  x <- x + Experts_l(N_post(x))
    logits  = N_final(x) @ Head                                         # untied

    Attn_l  : q = N_q(h W_q) (32 heads of 128), k = N_k(h W_k), v = h W_v (4 heads of 128)
              N_q, N_k: RMSNorm over each head's 128, one gain of 128 shared by the heads
                        (the configuration's `assumed`: the Qwen3-MoE family's, whose keys these are)
              sliding_attention: half-split rotary over the whole 128, theta 5e5, no scaling;
                                 query i sees keys j with 0 <= i - j < 1024
              full_attention   : YaRN: f_i = theta^(-i/64), low = floor(18.08) = 18, high = ceil(34.98) = 35,
                                 r_i = clip((i - 18) / 17, 0, 1), inv_freq_i = f_i (1 - r_i) + (f_i / 16) r_i,
                                 cos and sin times 1.2772588722239782; query i sees every key j <= i
              softmax(q k^T / sqrt(128) + mask) v in float32, 8 query heads a key-value head;  o W_o
    Experts : logits h W_r over all 64 (float32), the 8 largest chosen, weights = the softmax over all 64
              renormalised over the eight = the softmax of the eight logits;
              sum_e w_e W_down,e [ silu(W_gate,e h) * (W_up,e h) ], each of width 896

The numbers of the rule (:data:`ROPE`, 1e-6) are written below and not read
from the program's configuration, which gives the sizes, the counts, the
window and which layers are of which kind. A test-size configuration states
its own rotation beside its limits (``check_rope_parameters``: at a head of
16 and a window of 32 the published lengths leave no dimension on the ramp
at an angle a comparison can see).

Every expert lies on this chip (``experts_held`` = ``experts``), so nothing is
left out of a layer's result: the logits are the model's own.

:func:`check_engine` is the comparison a run's ``correct`` rests on, made at
the ENGINE's shapes, in its own two pools through its own block manager's
tables and with its own compiled programs beside the model's functions
(:func:`_served`, as ``reference/afmoe.py`` serves the family's other member,
the model's decode function giving what is compared of its logits in place
of all of them): three seeded prompts, one
under the window that stays there (the 512 bucket), one that begins under
the window, passes it and wraps its ring while it decodes (1,024), and one
far past both in the 8,192 bucket, repeated five tokens shorter over the
live slots of :func:`slot_plan`; every live slot prefilled alone by the
engine's own prefill program; then 160 decode steps over all slots in the
engine's chunks, each chunk through the engine's own decode program and
through the model's decode function with the logits out. This file's forward
follows the first period's three slots and the program's expert choices:
logits at every compared position (never tokens), the first window layer's K
and V rows as its ring holds them at the end, each routing choice against
this file's own ranking, the program's router alone on this file's float32
input, and the ENGINE's programs' tokens and log-probabilities against the
logits read beside them.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import afmoe
from reference.afmoe import below_bfloat16, compare, f32

# Mellum2-12B-A2.5B-Instruct/config.json
RMS_NORM_EPS = 1e-6
ROPE = {
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000.0},
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0,
        "original_max_position_embeddings": 8192, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": 1.2772588722239782},
}
KINDS = {"W": "sliding_attention", "F": "full_attention"}

#: what the check has to tell from the served model: the forward with each
#: injected has to come out as not passed against the program's output
#: (tools/swa_probe.py --config mellum2-12b-a2.5b-8l --faults)
FAULTS = (
    "no_yarn_on_full", "no_attention_factor", "yarn_on_window",
    "window_one_block_short", "window_one_block_long", "no_qk_norm",
    "weights_not_renormalised", "ninth_expert", "yarn_ramp_unrounded",
    "bfloat16_router", "rows_below_bfloat16", "weights_below_bfloat16",
)
#: what no comparison of outputs can hold: nothing of this layer, so far
UNOBSERVABLE = ()

#: tokens of the check's prompts: under the window to the end (the 512
#: bucket), under it at first and past the window and the ring's wrap (1,088
#: rows) by the last step (1,024), far past both (8,192)
CHECK_PROMPTS = (300, 1000, 7000)
CHECK_DECODE_STEPS = 160
#: live periods of :func:`slot_plan` at most: 16 x (8 + 19 + 112) blocks of
#: the full kind's pool, a third of the cell's
CHECK_PERIODS = 16
QUERY_BLOCK = 512


def rms_norm(x, w):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + RMS_NORM_EPS) * w


def inv_freq(half: int, spec: dict, faults=()):
    """``(half,)`` float64 inverse frequencies of a rotation ``spec`` (one
    section of ``rope_parameters``) over a head of ``2 x half``, by the
    closed form above."""
    i = np.arange(half, dtype=np.float64)
    f = spec["rope_theta"] ** (-i / half)
    if spec["rope_type"] != "yarn":
        return f

    def turns_at(rotations):
        return (half * math.log(spec["original_max_position_embeddings"]
                                / (rotations * 2 * math.pi))
                / math.log(spec["rope_theta"]))

    low, high = turns_at(spec["beta_fast"]), turns_at(spec["beta_slow"])
    if "yarn_ramp_unrounded" not in faults:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, 2 * half - 1)
    r = np.clip((i - low) / (high - low), 0.0, 1.0)
    return f * (1 - r) + (f / spec["factor"]) * r


def rotate(x, positions, spec: dict, faults=()):
    """Half-split rotary embedding of ``x (T, heads, D)`` at ``positions
    (T,)``: the two halves of the head rotated against each other, cos and
    sin times the section's ``attention_factor`` where it has one."""
    half = x.shape[-1] // 2
    angles = f32(positions)[:, None] * f32(inv_freq(half, spec, faults))[None, :]
    factor = (1.0 if "no_attention_factor" in faults
              else spec.get("attention_factor", 1.0))
    cos = (jnp.cos(angles) * factor)[:, None, :]
    sin = (jnp.sin(angles) * factor)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def rotation_of(kind: str, rope: dict, faults=()) -> dict:
    """The section of ``rope_parameters`` a layer of ``kind`` rotates by."""
    if kind == "F" and "no_yarn_on_full" in faults:
        return rope["sliding_attention"]
    if kind == "W" and "yarn_on_window" in faults:
        return rope["full_attention"]
    return rope[KINDS[kind]]


def window_of(c, kind: str, faults=()):
    """The keys a query of a layer of ``kind`` sees behind it: the window's
    rows (the query's own among them), or None for all of them. A block of
    the faults is a sixteenth of the window: 64 rows, the pool's block, at
    the published 1,024."""
    if kind != "W":
        return None
    block = max(1, c.window // 16)
    return (c.window - block * ("window_one_block_short" in faults)
            + block * ("window_one_block_long" in faults))


def attention(u, w, c, kind: str, rope: dict, faults=()):
    """``(o W_o, K rows (T, Kh*D), V rows)`` of normed rows ``u (T, H)`` of
    one sequence; the rows are what the layer's pool keeps of a position
    (the keys normed and rotated, a full layer's scaled with it)."""
    T = u.shape[0]
    D, Kh = c.head_dim, c.kv_heads
    G = c.heads // Kh
    positions = jnp.arange(T)
    q = (u @ w["wq"]).reshape(T, c.heads, D)
    k = (u @ w["wk"]).reshape(T, Kh, D)
    v = (u @ w["wv"]).reshape(T, Kh, D)
    if "no_qk_norm" not in faults:
        q, k = rms_norm(q, w["q_norm"]), rms_norm(k, w["k_norm"])
    spec = rotation_of(kind, rope, faults)
    q, k = rotate(q, positions, spec, faults), rotate(k, positions, spec, faults)
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
    return o @ w["wo"], k.reshape(T, Kh * D), v.reshape(T, Kh * D)


def router_logits(u, w, faults=()):
    """Router logits ``(T, experts)`` of normed rows, float32 (the program's
    router weights are the model's type; their logits float32)."""
    if "bfloat16_router" in faults:
        bf = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        return bf(bf(u) @ bf(w["router"]))
    return u @ w["router"]


def gates(logits, chosen, faults=()):
    """The chosen experts' weights: the softmax over all renormalised over
    the chosen, which is the softmax of the chosen logits."""
    picked = jnp.take_along_axis(logits, chosen, axis=-1)
    if "weights_not_renormalised" in faults:
        return jnp.take_along_axis(jax.nn.softmax(logits, -1), chosen, axis=-1)
    return jax.nn.softmax(picked, axis=-1)


def route(u, w, c, faults=()):
    """``(chosen (T, k), weights (T, k))``: the ``k`` largest logits (one
    more under ``ninth_expert``)."""
    logits = router_logits(u, w, faults)
    _, chosen = jax.lax.top_k(
        logits, c.experts_per_token + ("ninth_expert" in faults))
    return chosen, gates(logits, chosen, faults)


def audit(u, w, c, forced, faults=()):
    """The program's choices ``forced (T, k)`` against this file's ranking:
    ``(chosen, weights, shortfall (T,), differs (T,))``; the forward then
    follows the program's experts with this file's weights for them (and,
    under ``ninth_expert``, the best of the others beside them).
    ``shortfall`` is how far the worst of the program's choices lies under
    this file's k-th logit; ``differs`` whether the two sets differ."""
    logits = router_logits(u, w, faults)
    top, own = jax.lax.top_k(logits, c.experts_per_token)
    worst = jnp.min(jnp.take_along_axis(logits, forced, axis=-1), axis=-1)
    differs = jnp.any(jnp.sort(own, -1) != jnp.sort(forced, -1), axis=-1)
    if "ninth_expert" in faults:
        taken = (forced[..., None] == jnp.arange(c.experts)).any(axis=1)
        ninth = jnp.argmax(jnp.where(taken, -jnp.inf, logits), axis=-1)
        forced = jnp.concatenate([forced, ninth[:, None].astype(forced.dtype)], -1)
    return (forced, gates(logits, forced, faults),
            jnp.maximum(top[:, -1] - worst, 0.0), differs)


def experts(u, w, c, faults=(), forced=None):
    """The chosen experts, all held here, one after another; each is cast to
    float32 by itself. With ``forced (T, k)`` the experts are the ones given
    (:func:`audit`)."""
    if forced is None:
        chosen, weights = route(u, w, c, faults)
        report = None
    else:
        chosen, weights, shortfall, differs = audit(u, w, c, forced, faults)
        report = (shortfall, differs)
    lower = (below_bfloat16 if "weights_below_bfloat16" in faults
             else (lambda t: t))

    def add_expert(e, out):
        gate = jnp.sum(jnp.where(chosen == c.expert_first + e, weights, 0.0), -1)
        return out + gate[:, None] * afmoe.gated(
            u, lower(f32(w["w_up"][e])), lower(f32(w["w_down"][e])))

    # one loop body for the 64: unrolled, the experts are most of what the
    # check's set-up spends compiling
    out = jax.lax.fori_loop(0, c.experts_held, add_expert, jnp.zeros_like(u))
    return out, (chosen if report is None else report)


def forward(config, params, tokens, positions, faults=(), forced=None,
            rope=None):
    """The full forward over ``tokens``: ``(logits (len(positions), V),
    routing, the first window layer's rows (T, 2 x Kh*D): K then V)``, all
    numpy. ``routing`` is the chosen experts ``(layers, T, k)``; or, with
    ``forced (layers, T, k)`` (the program's choices, which the forward then
    follows), the audit of them: ``{"shortfall", "differs": (layers, T),
    "first_input": (T, hidden)}``. ``rope`` stands in for :data:`ROPE` (a
    test-size configuration's)."""
    c, rope = config, rope or ROPE
    lower = (below_bfloat16 if "weights_below_bfloat16" in faults
             else (lambda t: t))
    matrices = ("wq", "wk", "wv", "wo")
    routed_stacks = ("w_up", "w_down")     # cast an expert at a time
    attend = {kind: jax.jit(lambda x, w, kind=kind: attention(
        rms_norm(x, w["norm"]), w, c, kind, rope, faults)) for kind in "WF"}
    norm = jax.jit(lambda x, w: rms_norm(x, w["norm"]))
    route_own = jax.jit(lambda u, w: experts(u, w, c, faults))
    route_forced = jax.jit(lambda u, w, f: experts(u, w, c, faults, forced=f))
    routing, window_rows, first_input = [], None, None
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][jnp.asarray(tokens)])
        for i, (lp, kind) in enumerate(zip(params["layers"], c.layer_kinds)):
            w = {k: lower(f32(a)) if k in matrices else f32(a)
                 for k, a in lp["attn"].items()}
            out, k_rows, v_rows = attend[kind](x, w)
            if kind == "W" and window_rows is None:
                window_rows = np.concatenate(
                    [np.asarray(k_rows), np.asarray(v_rows)], axis=-1)
            x = (x + out).block_until_ready()
            del w, out, k_rows, v_rows
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
            x = (x + out).block_until_ready()
            del w, out
        x = rms_norm(x[jnp.asarray(positions)], f32(params["final_norm"]))
        logits = np.asarray(x @ lower(f32(params["lm_head"])))
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
    print(f"[mellum check] {message}", flush=True)


def slot_plan(slots: int, prompts, periods: int = CHECK_PERIODS):
    """``(slot, prompt, tokens)`` of the check's live slots, as
    ``reference/afmoe.py`` ``slot_plan`` spreads them (a period is each
    prompt once and an idle slot, every period five tokens shorter), over
    the first ``periods`` periods alone: the slots behind them stay idle
    beside the live ones, as a decode batch's free slots do."""
    period = len(prompts) + 1
    return [row for row in afmoe.slot_plan(slots, prompts)
            if row[0] // period < periods]


def served(engine, seed: int, *, prompts=CHECK_PROMPTS,
           steps: int = CHECK_DECODE_STEPS, periods: int = CHECK_PERIODS):
    """What the program computes for the check's seeded prompts, at the
    ENGINE's shapes and in its own pools (``reference/afmoe.py`` ``served``
    with this file's plan of slots; the engine has to be idle)."""
    cfg, manager = engine.config, engine.block_mgr
    if not all(slot.free for slot in engine.slots):
        raise RuntimeError("the engine is serving: the check writes its pools")
    plan = slot_plan(cfg.slots, prompts, periods)
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
        row = np.zeros((1, afmoe._bucket_of(tokens[slot].size)), np.int32)
        row[0, : tokens[slot].size] = tokens[slot]
        return (jnp.asarray(row),
                jnp.asarray([tokens[slot].size], jnp.int32),
                jnp.asarray(tables[slot][None]))

    model_prefill = jax.jit(
        lambda p, t, n, pk, pv, wp, tb: swa_prefill_paged(
            c, p, t, n, pk, pv, wp, tb), donate_argnums=(3, 4, 5))
    engine_prefill = engine._prefill_fn(afmoe.GREEDY)

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
            batches.append({"bucket": afmoe._bucket_of(size), "rows": 1})
        token, logprob = afmoe._on_engine(
            engine, f"prefill program of the {afmoe._bucket_of(size)} bucket",
            prefill_as_the_engine, slot)
        if slot in logits0:
            shortfall, error = afmoe._held_to_logits(
                token, logprob, logits0[slot], True)
            first_shortfall = max(first_shortfall, shortfall)
            first_error = max(first_error, error)
            token = int(logits0[slot].argmax(-1))
        first[slot], lengths[slot] = token, size
    _log(f"{len(plan)} slots prefilled")

    follow = jnp.asarray(followed)

    def greedy_with_what_is_compared(theirs):
        """The model's sampler: the greedy token, and of a step's logits
        ``(slots, V)`` what the comparisons need. A chunk's logits whole are
        gigabytes at 192 slots and 98,304 ids: the followed slots' rows, and
        of every slot the best logit, the log-sum-exp, the spread, and the
        logits at the tokens the ENGINE's program made at each step of the
        chunk (``theirs (steps, slots)``: the sampler does not know which
        step it is, the caller takes step ``s`` of step ``s``)."""
        def sample(logits, key):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), {
                "followed": logits[follow], "best": logits.max(-1),
                "lse": jax.nn.logsumexp(logits, axis=-1),
                "std": logits.std(-1),
                "picked": jnp.take_along_axis(logits, theirs.T, axis=-1)}
        return sample

    kernel = engine.paged_read_kernel
    live = lengths > 0
    active, tables_dev = jnp.asarray(live), jnp.asarray(tables)
    window = engine._read_blocks_for(int(lengths.max()) + steps)
    model_decode = jax.jit(
        lambda p, t0, n, pk, pv, wp, theirs, k: swa_decode_chunk_paged(
            c, p, t0, n, active, pk, pv, wp, tables_dev,
            greedy_with_what_is_compared(theirs), key, k, window,
            kernel=kernel),
        static_argnums=7, donate_argnums=(3, 4, 5))
    sampler = (jnp.zeros((slots,), jnp.float32), jnp.zeros((slots,), jnp.int32),
               jnp.ones((slots,), jnp.float32))

    def decode_as_the_engine(t0, n, k):
        packed, _, _, engine.cache_k, engine.cache_v, engine.state = \
            engine._decode_fn(afmoe.GREEDY, window, k)(
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
        theirs, their_logprobs = afmoe._on_engine(
            engine, f"decode program of {k} steps", decode_as_the_engine,
            t0, n, k)
        out = model_decode(engine.params, t0, n, engine.cache_k,
                           engine.cache_v, engine.state, jnp.asarray(theirs), k)
        t0, n, engine.cache_k, engine.cache_v, engine.state = out[2:7]
        ours = np.asarray(out[0])                                 # (k, slots)
        read = {name: np.asarray(a, np.float64) for name, a in out[1].items()
                if name != "followed"}
        # a step is compared while every step of the chunk before it agreed
        agreed = np.cumprod(np.concatenate(
            [np.ones((1, slots), bool), theirs == ours])[:-1], axis=0) > 0
        agreed &= live[None]
        picked = read["picked"][np.arange(k), :, np.arange(k)]    # (k, slots)
        shortfall = float(np.where(
            agreed, (read["best"] - picked) / read["std"], 0.0).max(initial=0.0))
        error = float(np.where(agreed, np.abs(
            their_logprobs - (picked - read["lse"])), 0.0).max(initial=0.0))
        decode_shortfall = max(decode_shortfall, shortfall)
        decode_error = max(decode_error, error)
        compared += int(agreed.sum())
        parted += int((agreed & (theirs != ours)).sum())
        tokens_made.append(ours[:, live])
        made.append(ours[:, followed])
        chunk_logits.append(np.asarray(out[1]["followed"]))
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


def router_alone(engine, inputs, dtype, faults=()) -> float:
    """The share of ``inputs (T, H)``, this file's float32 inputs of the
    first layer's router rounded to the model's type, for which the
    program's expert layer (``moe_mixer`` with the first layer's weights,
    its router computing in ``dtype``) chooses another set than this file's
    ranking of the same rounded inputs."""
    from langstream_tpu.models.hybrid import moe_mixer

    c = dataclasses.replace(engine.model_config, router_dtype=jnp.dtype(dtype))
    first = engine.params["layers"][0]["moe"]
    u = jnp.asarray(inputs).astype(c.dtype)
    # the layer's weights as an argument: closed over, its 0.8 GB of experts
    # would be constants of the program
    theirs = jax.jit(lambda first, u: moe_mixer(
        c, first, u, jnp.ones((u.shape[0],), bool))[2])(first, u)
    with jax.default_matmul_precision("highest"):
        own, _ = route(f32(u), {"router": f32(first["router"])}, c,
                       tuple(f for f in faults if f != "ninth_expert"))
    return float(jnp.mean(jnp.any(
        jnp.sort(own, -1) != jnp.sort(theirs, -1), axis=-1)))


def judge(engine, got: dict, tolerance: dict, faults=()) -> dict:
    """:func:`served` output against this file's full forward over each
    followed slot's tokens and the same chosen experts, held to
    ``tolerance``: the positions, window rows and routing decisions of all
    slots together; the program's router alone on this file's inputs; and
    what :func:`served` read of the engine's own prefill and decode
    programs."""
    rope = tolerance.get("check_rope_parameters")
    want, rows_want, rows_got, shortfall, differs, inputs = [], [], [], [], [], []
    for slot in got["slots"]:
        logits, routing, window_rows = forward(
            engine.model_config, engine.params, slot["sequence"],
            slot["positions"], faults, forced=slot["chose"], rope=rope)
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
    commit before the preset existed) is refused at once, and so is a
    member of the family that is not this one."""
    c = engine.model_config
    if getattr(engine, "family", None) != "swa" or getattr(
            c, "output_gate", True) or c.experts_held != c.experts:
        raise RuntimeError(
            f"model {engine.config.model!r} is not served as the mellum "
            f"member of the swa family here: there is nothing to compare")
    # a test-size configuration's file may state smaller sizes for the check
    # beside its limits (tests/bench/fixtures/wf); the cell's states none
    if "check_prompts" in tolerance:
        how.setdefault("prompts", tuple(map(int, tolerance["check_prompts"])))
    if "check_decode_steps" in tolerance:
        how.setdefault("steps", int(tolerance["check_decode_steps"]))
    return judge(engine, served(engine, seed, **how), tolerance)
