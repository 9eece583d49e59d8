"""The ``deepseek_v2`` decoder (DeepSeek-V2: multi-head latent attention, one
dense layer, then 160 routed experts top-6 by group-limited routing beside a
shared expert), written plainly.

A float32 ``jax.numpy`` forward pass under
``default_matmul_precision("highest")``, one layer at a time from the
engine's own parameters: no cache, no kernels, no batching, and the attention
in its EXPANDED form only (the program's decode path absorbs the
up-projections into the query and the output and never expands a cached row;
that the two are one function is what the comparison shows). From the
published ``config.json`` (``hidden_act`` silu, ``rms_norm_eps`` 1e-6, no
biases)::

    x0      = Embed[token]
    layer l : h = x + Attn(RMSNorm(x));  x <- h + FFN_l(RMSNorm(h))
    logits  = RMSNorm_f(x) @ Head                                    # untied

    Attn    : c_q = RMSNorm(u W_qa) (1536);  [q_nope | q_pe] = c_q W_qb        (128 heads x (128 + 64))
              [c | k_pe] = u W_kva (512 + 64);  c_kv = RMSNorm(c)              # the cache row is [c_kv | rot(k_pe)]
              [k_nope | v] = c_kv W_kvb                                         (128 heads x (128 + 128))
              s = (q_nope . k_nope + rot(q_pe) . rot(k_pe)) * 192^-0.5 * m^2,  m = 0.1 * 0.707 * ln 40 + 1
              softmax(s + causal mask) v, heads side by side, then W_o          (16384 -> 5120)
    rot     : YaRN over the 64 rotary dims: inv_freq_i between 1/theta_i and 1/(40 theta_i) by a linear ramp over
              the correction dims of beta_fast 32 and beta_slow 1 at original length 4096; the pairs (x_2i, x_2i+1)
              de-interleaved to [evens | odds] and half-rotated (as the published code does); cos/sin factor 1
    FFN_0   : (silu(u W_g) * u W_u) W_d                                         (12288)
    FFN_l>0 : sigma = softmax(u W_r) over all 160 (float32); groups of 20 consecutive experts scored by their best
              sigma; the best 3 of 8 groups kept, the rest zeroed; top 6 of what is kept; g_e = 16 * sigma_e
              (not renormalised);  sum_e g_e E_e(u) + S(u),  E_e gated of width 1536, S gated of width 3072

The published fused matrices are read as the program keeps them, by column
blocks (``W_qb`` = ``[w_q_nope | w_q_pe]`` by head, ``W_kva`` = ``[w_kv_c |
w_k_pe]``, ``W_kvb`` = ``w_uk`` and ``w_uv`` by head): the same numbers. The
numbers of the rule (16, 40, 32, 1, 0.707, 4096, 1e4, 1e-6) are written below
and not read from the program's configuration, which only gives the sizes
and counts.

**The share**: only the chosen experts this chip holds (``[expert_first,
expert_first + experts_held)``) are computed, one after another, each cast
to float32 by itself; the softmax is over all 160 wherever they live and
nothing is renormalised, so the share needs nothing of the other chips; what
their experts would add is left out, as in the program.

:func:`check_engine` is the comparison a run's ``correct`` rests on, made at
the ENGINE's shapes, in its own pool and with its own compiled programs
beside the model's functions (:func:`served`): three seeded prompts at the
cell's sizes, one in each prefill bucket (3,000 tokens in 4,096, 6,000 in
8,192, 9,000 in 16,384), repeated five tokens shorter over three of every
four slots with the fourth idle; every live slot prefilled alone by the
engine's own prefill program, as its ``prefill-batch`` of 1 does; then 64
decode steps over all slots in the engine's chunks of 32, each chunk through
the engine's own decode program (all its slots, its table width, its packed
fetch) and through the model's decode function with the logits out. This
file's forward follows the first three slots' tokens and the program's
expert choices: the logits at every compared position (never tokens), the
first layer's latent rows as the pool holds them (``latent_rms_share``: what
a cache kept below bfloat16 moves first), and each routing choice against
this file's own ranking. Three more readings: the program's router alone on
this file's float32 input of the first expert layer
(``router_alone_differing_share``); the ENGINE's prefill programs' token
and log-probability against the logits read above (``engine_first_*``); and
the ENGINE's decode program's tokens and log-probabilities of every live
slot and step against the logits the model's function read from the same
state (``engine_decode_*``).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

# DeepSeek-V2/config.json
ROUTED_SCALING_FACTOR = 16.0
ROPE_THETA = 10000.0
RMS_NORM_EPS = 1e-6
YARN = {"factor": 40.0, "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096}

#: what the check has to tell from the served model: the forward with each
#: injected has to come out as not passed against the program's output
#: (tools/latent_probe.py --faults)
FAULTS = (
    "scale_without_mscale", "rope_without_yarn", "renormalised_gates",
    "ungrouped_top_k", "bfloat16_router", "latent_below_bfloat16",
)

#: tokens of the check's prompts, one in each of the cell's prefill buckets
#: (4,096 / 8,192 / 16,384); :func:`slot_plan` spreads them over the slots
CHECK_PROMPTS = (3000, 6000, 9000)
CHECK_LENGTH_STEP = 5
CHECK_DECODE_STEPS = 64
#: the engine's sampler mode (top-p, top-k, all greedy) of a batch at
#: temperature 0, what a window's programs are compiled for
GREEDY = (False, False, True)
#: heads and query rows of one block of the reference's attention
HEAD_BLOCK, QUERY_BLOCK = 16, 512
#: seconds one of the engine's own programs may take (it compiles on its
#: first call)
ENGINE_PROGRAM_S = 600.0


def f32(t):
    return jnp.asarray(t, dtype=jnp.float32)


def rms_norm(x, w):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + RMS_NORM_EPS) * w


def yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def inv_freq(dim: int, faults=()) -> np.ndarray:
    theta = ROPE_THETA ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if "rope_without_yarn" in faults:
        return (1.0 / theta).astype(np.float32)

    def correction_dim(rotations):
        return dim * math.log(YARN["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(ROPE_THETA))

    low = max(math.floor(correction_dim(YARN["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(YARN["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp              # 1 where the dimension is not stretched
    return (1.0 / (YARN["factor"] * theta) * (1 - mask)
            + 1.0 / theta * mask).astype(np.float32)


def rope(x, positions, faults=()):
    """``x (T, ..., dim)`` at ``positions (T,)``: pairs de-interleaved, then
    ``x cos + rotate_half(x) sin`` with ``cos``/``sin`` of ``[angles |
    angles]``."""
    dim = x.shape[-1]
    angles = positions.astype(jnp.float32)[:, None] * inv_freq(dim, faults)
    factor = (yarn_mscale(YARN["factor"], YARN["mscale"])
              / yarn_mscale(YARN["factor"], YARN["mscale_all_dim"]))
    emb = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(emb) * factor, jnp.sin(emb) * factor
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    half = dim // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def below_bfloat16(x):
    """Rounded to 3 bits of mantissa (an 8-bit float's) at float32's range."""
    return jax.lax.reduce_precision(x, 8, 3)


def attention(u, w, c, faults=()):
    """``u (T, hidden)`` normed. Returns ``(out (T, hidden), the cache rows
    [c_kv | rot(k_pe)] (T, kv_rank + rope_dim))``; heads and query rows a
    block at a time, so that a 6k-token prompt fits beside the served
    model."""
    T = u.shape[0]
    pos = jnp.arange(T)
    c_q = rms_norm(u @ w["w_qa"], w["q_norm"])
    q_nope = (c_q @ w["w_q_nope"]).reshape(T, c.heads, c.nope_dim)
    q_pe = rope((c_q @ w["w_q_pe"]).reshape(T, c.heads, c.rope_dim), pos, faults)
    c_kv = rms_norm(u @ w["w_kv_c"], w["kv_norm"])
    k_pe = rope(u @ w["w_k_pe"], pos, faults)
    if "latent_below_bfloat16" in faults:
        c_kv, k_pe = below_bfloat16(c_kv), below_bfloat16(k_pe)
    m = 1.0 if "scale_without_mscale" in faults else yarn_mscale(
        YARN["factor"], YARN["mscale_all_dim"])
    scale = (c.nope_dim + c.rope_dim) ** -0.5 * m * m
    hb = min(HEAD_BLOCK, c.heads)
    qb = min(QUERY_BLOCK, T)
    pad = (-T) % qb
    blocks = (T + pad) // qb

    def head_block(args):
        qn, qp, w_uk, w_uv = args                   # (T, hb, .), (hb, ., .)
        k_nope = jnp.einsum("tc,hdc->thd", c_kv, w_uk)
        v = jnp.einsum("tc,hcd->thd", c_kv, w_uv)

        def query_block(args):
            qn, qp, at = args                       # (qb, hb, .), (qb,)
            s = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                 + jnp.einsum("qhd,kd->hqk", qp, k_pe)) * scale
            s = jnp.where(at[:, None] >= pos[None, :], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

        rows = lambda t: jnp.pad(                                # noqa: E731
            t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)
        ).reshape((blocks, qb) + t.shape[1:])
        # a padded query row attends as the last real one (dropped below)
        at = jnp.minimum(jnp.arange(T + pad), T - 1).reshape(blocks, qb)
        out = jax.lax.map(query_block, (rows(qn), rows(qp), at))
        return out.reshape((T + pad, hb, c.v_dim))[:T]

    by_heads = lambda t, axis: jnp.moveaxis(                     # noqa: E731
        t.reshape(t.shape[:axis] + (c.heads // hb, hb) + t.shape[axis + 1:]),
        axis, 0)
    out = jax.lax.map(head_block, (
        by_heads(q_nope, 1), by_heads(q_pe, 1),
        by_heads(w["w_uk"], 0), by_heads(w["w_uv"], 0)))      # (G, T, hb, v)
    out = jnp.moveaxis(out, 0, 1).reshape(T, c.heads * c.v_dim)
    return out @ w["w_o"], jnp.concatenate([c_kv, k_pe], axis=-1)


def gated(u, w_in, w_out):
    """``(silu(a) * b) W_out`` with ``[a | b] = u W_in``; ``w_in (2 I, H)``
    and ``w_out (I, H)`` as the program keeps a routed expert's."""
    a, b = jnp.split(u @ w_in.T, 2, axis=-1)
    return (jax.nn.silu(a) * b) @ w_out


def scores(u, w, c, faults=()):
    """``(sigma (T, E), sigma with the dropped groups zeroed, each group's
    best sigma (T, n_group))``."""
    router = w["router"]
    if "bfloat16_router" in faults:
        u, router = f32(u.astype(jnp.bfloat16)), f32(router.astype(jnp.bfloat16))
    logits = u @ router
    if "bfloat16_router" in faults:
        logits = f32(logits.astype(jnp.bfloat16))
    sigma = jax.nn.softmax(logits, axis=-1)
    T, E = sigma.shape
    best = sigma.reshape(T, c.n_group, E // c.n_group).max(axis=-1)
    if "ungrouped_top_k" in faults:
        return sigma, sigma, best
    # ties to the lower group, as to the lower expert below: a stable sort
    groups = jnp.argsort(-best, axis=-1, stable=True)[:, : c.topk_group]
    kept = (groups[..., None] == jnp.arange(c.n_group)).any(axis=1)
    return sigma, jnp.where(
        jnp.repeat(kept, E // c.n_group, axis=1), sigma, 0.0), best


def gates(sigma, chosen, faults=()):
    g = jnp.take_along_axis(sigma, chosen, axis=-1)
    if "renormalised_gates" in faults:
        return g / (g.sum(axis=-1, keepdims=True) + 1e-20)
    return g * ROUTED_SCALING_FACTOR


def route(u, w, c, faults=()):
    """``(chosen experts (T, k), their weights (T, k))`` over ALL experts."""
    sigma, kept, _ = scores(u, w, c, faults)
    chosen = jnp.argsort(-kept, axis=-1, stable=True)[:, : c.experts_per_token]
    return chosen, gates(sigma, chosen, faults)


def audit(u, w, c, forced, faults=()):
    """The program's choices ``forced (T, k)`` against this file's own
    scores: ``(forced, their weights from this file's scores, shortfall
    (T,), differs (T,))``. ``shortfall`` is the least relative move of the
    scores under which the rule gives the program's choice: over every set
    of ``topk_group`` groups the program may have kept, the larger of how
    far the set's weakest group lies under the last group this file keeps
    (as a share of that group's score) and how far the worst forced expert
    lies under the k-th score inside the set (as a share of it; 1 for an
    expert outside the set), and of those the least. A rounding of the
    activations moves a near-tie of groups or of experts by a few
    hundredths; a wrong rule reads a large part of 1. ``differs``: whether
    the set differs from this file's own."""
    import itertools

    k = c.experts_per_token
    sigma, kept, best = scores(u, w, c, faults)
    T, E = sigma.shape
    size = E // c.n_group
    grouped = "ungrouped_top_k" not in faults
    sets = (list(itertools.combinations(range(c.n_group), c.topk_group))
            if grouped else [tuple(range(c.n_group))])
    member = jnp.asarray(
        [[g in chosen for g in range(c.n_group)] for chosen in sets])  # (S, G)
    group_cut = jnp.sort(best, axis=-1)[:, -c.topk_group if grouped else 0]
    weakest = jnp.min(jnp.where(member[:, None], best[None], jnp.inf), -1)
    group_short = jnp.maximum(group_cut[None] - weakest, 0.0) / group_cut[None]
    inside = jnp.repeat(member, size, axis=1)                       # (S, E)
    cut = jnp.sort(jnp.where(inside[:, None], sigma[None], 0.0), -1)[..., -k]
    mine = jnp.take_along_axis(sigma, forced, -1)                   # (T, k)
    allowed = inside[:, forced]                                     # (S, T, k)
    expert_short = jnp.where(
        allowed, jnp.maximum(cut[..., None] - mine[None], 0.0) / cut[..., None],
        1.0).max(-1)
    shortfall = jnp.maximum(group_short, expert_short).min(axis=0)  # (T,)
    own = jnp.argsort(-kept, axis=-1, stable=True)[:, :k]
    differs = jnp.any(jnp.sort(own, -1) != jnp.sort(forced, -1), axis=-1)
    return forced, gates(sigma, forced, faults), shortfall, differs


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
    routing, the first layer's cache rows (T, kv_rank + rope_dim))``, all
    numpy. ``routing`` is the chosen experts ``(expert layers, T, k)``; or,
    with ``forced (expert layers, T, k)`` (the program's choices, which the
    forward then follows), the audit of them: ``{"shortfall", "differs":
    (expert layers, T), "first_input": (T, hidden)}``."""
    c = config
    routed_stacks = ("w_up", "w_down")     # cast an expert at a time
    take = jax.jit(lambda t, i: jax.tree.map(lambda a: f32(a[i]), t))
    take_moe = jax.jit(lambda t, i: {
        k: a[i] if k in routed_stacks else f32(a[i]) for k, a in t.items()})
    attend = jax.jit(lambda x, w: attention(rms_norm(x, w["norm"]), w, c, faults))
    dense = jax.jit(lambda x, w: gated(
        rms_norm(x, w["norm"]), w["w_up"].T, w["w_down"]))
    norm = jax.jit(lambda x, w: rms_norm(x, w["norm"]))
    route_own = jax.jit(lambda u, w: experts(u, w, c, faults))
    route_forced = jax.jit(lambda u, w, f: experts(u, w, c, faults, forced=f))
    routing, first_rows, first_input = [], None, None
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][jnp.asarray(tokens)])
        for layer in range(c.layers):
            sparse = layer >= c.dense_layers
            group = params["sparse" if sparse else "dense"]
            i = layer - c.dense_layers if sparse else layer
            w = take(group["attn"], i)
            out, rows = attend(x, w)
            if layer == 0:
                first_rows = np.asarray(rows)
            x = (x + out).block_until_ready()
            del w, out
            if not sparse:
                x = x + dense(x, take(group["ffn"], i))
                continue
            w = take_moe(group["moe"], i)
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
        logits = np.asarray(x @ f32(params["lm_head"]))
    if forced is not None:
        routing = {"shortfall": np.stack([r[0] for r in routing]),
                   "differs": np.stack([r[1] for r in routing]),
                   "first_input": first_input}
    else:
        routing = np.stack(routing)
    return logits, routing, first_rows


# ---------------------------------------------------------------------------
# what the program computes, and the comparison
# ---------------------------------------------------------------------------


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
    ENGINE's shapes and in its own pool (the engine has to be idle: nothing
    a request reads is kept, every block is the check's while it runs), the
    engine's own compiled programs beside the model's functions:

    - every live slot of :func:`slot_plan` is prefilled by the engine's own
      greedy prefill program, one prompt a dispatch as its ``prefill-batch``
      of 1 dispatches them; the first period's prompts first by the model's
      prefill with the logits out, so that the engine's token and
      log-probability are held to those logits (``engine_first_*``) and the
      rows the pool keeps are the engine program's;
    - then ``steps`` decode steps over all slots in chunks of the engine's
      ``decode-chunk``: each chunk first through the engine's own decode
      program (its slots, its table width, its window, its packed fetch),
      then from the same tokens and lengths through the model's decode
      function with the logits out, which writes the rows the next chunk
      reads. The engine's tokens and log-probabilities of every live slot
      are held to those logits up to and at the first step where the two
      programs choose another token (after it the engine's chunk follows
      another sequence): ``engine_decode_*``.

    The reference then follows the first period's slots."""
    from langstream_tpu.models.latent import (
        latent_decode_chunk_paged,
        latent_prefill_paged,
    )

    c, cfg, layout = engine.model_config, engine.config, engine.paged_layout
    if not all(slot.free for slot in engine.slots):
        raise RuntimeError("the engine is serving: the check writes its pool")
    bs, slots, width = layout.block_size, cfg.slots, layout.max_blocks_per_slot
    plan = slot_plan(slots, prompts)
    tables = np.zeros((slots, width), np.int32)     # 0: the scratch block
    block = 1
    for slot, _, size in plan:
        need = -(-(size + steps + 1) // bs)
        tables[slot, :need] = np.arange(block, block + need)
        block += need
    if block > layout.num_blocks or max(
            size for _, _, size in plan) + steps + 1 > width * bs:
        raise RuntimeError(f"the check's prompts need {block - 1} blocks; "
                           f"the pool has {layout.num_blocks - 1}")
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

    model_prefill = jax.jit(lambda p, t, n, pool, tb: latent_prefill_paged(
        c, p, t, n, pool, tb), donate_argnums=(3,))
    engine_prefill = engine._prefill_fn(GREEDY)

    def prefill_as_the_engine(slot):
        row, n, table = padded(slot)
        out = engine_prefill(
            engine.params, engine.cache_k, engine.cache_v, row, n, table, key,
            jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), jnp.float32))
        engine.cache_k, engine.cache_v = out[2], out[3]
        return int(np.asarray(out[0])[0]), float(np.asarray(out[1])[0])

    first = np.zeros((slots,), np.int32)
    lengths = np.zeros((slots,), np.int32)
    followed, logits0, chose0, batches = [], {}, {}, []
    first_shortfall = first_error = 0.0
    for slot, _, size in plan:
        if slot < len(prompts):
            row, n, table = padded(slot)
            logits, engine.cache_k, routed = model_prefill(
                engine.params, row, n, engine.cache_k, table)
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

    def greedy_with_logits(logits, key):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits

    kernel = engine.paged_read_kernel
    live = lengths > 0
    active, tables_dev = jnp.asarray(live), jnp.asarray(tables)
    window = engine._read_blocks_for(int(lengths.max()) + steps)
    model_decode = jax.jit(
        lambda p, t0, n, pool, k: latent_decode_chunk_paged(
            c, p, t0, n, active, pool, tables_dev, greedy_with_logits, key, k,
            window, kernel=kernel),
        static_argnums=4, donate_argnums=(3,))
    sampler = (jnp.zeros((slots,), jnp.float32), jnp.zeros((slots,), jnp.int32),
               jnp.ones((slots,), jnp.float32))

    def decode_as_the_engine(t0, n, k):
        packed, _, _, engine.cache_k, engine.cache_v = engine._decode_fn(
            GREEDY, window, k)(
            engine.params, engine.cache_k, engine.cache_v, t0, n, active,
            tables_dev, key, *sampler)
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
        out = model_decode(engine.params, t0, n, engine.cache_k, k)
        t0, n, engine.cache_k = out[2:5]
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
        chose.append(np.asarray(out[6]).swapaxes(0, 1)[:, :, followed])
    made, chunk_logits, chose = (np.concatenate(made), np.concatenate(chunk_logits),
                                 np.concatenate(chose, axis=1))
    first_layer = jax.jit(lambda pool, blocks: pool[0, blocks].astype(jnp.float32))

    def held_a_token(chosen):
        """Of the ``k`` experts a token chose, how many this chip holds, in
        the mean over the tokens, by expert layer."""
        here = (chosen >= c.expert_first) & (
            chosen < c.expert_first + c.experts_held)
        return [round(float(x), 4) for x in here.sum(-1).reshape(
            here.shape[0], -1).mean(-1)]
    return {
        "slots": [{
            "slot": slot,
            # the sequence the program produced, for the reference to follow
            "sequence": np.concatenate(
                [tokens[slot], first[slot : slot + 1], made[:-1, i]]),
            "positions": list(range(
                tokens[slot].size - 1, tokens[slot].size + steps)),
            "logits": np.concatenate([logits0[slot][None], chunk_logits[:, i]]),
            # the first layer's cache rows of the whole sequence, as the
            # pool holds them (the last token made was never fed back)
            "rows": np.asarray(first_layer(
                engine.cache_k, jnp.asarray(tables[slot]))).reshape(
                    width * bs, -1)[
                : tokens[slot].size + steps, : c.kv_rank + c.rope_dim],
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
            # what the held experts see of the followed slots' tokens: the
            # router sends experts_per_token x held / experts a token here
            # in the mean over uniform choices
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
    the reference's spread and the correlation; the first layer's cache
    rows' RMS error as a share of the reference rows' RMS, held to
    ``latent_rms_share``; the routing audit's worst shortfall, held to
    ``routing_margin``, with the share of the first expert layer's tokens
    whose chosen set differs, held to ``first_routing_differing_share``."""
    rms = np.sqrt(np.mean((got - want) ** 2, axis=-1)) / np.std(want, axis=-1)
    corr = [float(np.corrcoef(g, w)[0, 1]) for g, w in zip(got, want)]
    shortfall, differs = routing["shortfall"], routing["differs"]
    report = {
        "positions": [{"rms_share": float(r), "correlation": c}
                      for r, c in zip(rms, corr)],
        "worst_rms_share": float(rms.max()), "worst_correlation": min(corr),
        "latent_rms_share": float(
            np.sqrt(np.mean((rows_got - rows_want) ** 2))
            / np.sqrt(np.mean(rows_want ** 2))),
        "routing_decisions": int(differs.size),
        "routing_decisions_differing": int(differs.sum()),
        "worst_routing_shortfall": float(shortfall.max()),
        "first_routing_differing_share": float(differs[0].mean()),
        "tolerance": dict(tolerance),
    }
    report["passed"] = bool(
        report["worst_rms_share"] <= tolerance["rms_share"]
        and report["worst_correlation"] >= tolerance["min_correlation"]
        and report["latent_rms_share"] <= tolerance["latent_rms_share"]
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
    first = {k: v[0] for k, v in engine.params["sparse"]["moe"].items()}
    u = jnp.asarray(inputs).astype(c.dtype)
    theirs = jax.jit(lambda u: moe_mixer(
        c, first, u, jnp.ones((u.shape[0],), bool))[2])(u)
    with jax.default_matmul_precision("highest"):
        own, _ = route(f32(u), {"router": f32(first["router"])}, c, faults)
    return float(jnp.mean(jnp.any(
        jnp.sort(own, -1) != jnp.sort(theirs, -1), axis=-1)))


def judge(engine, got: dict, tolerance: dict, faults=()) -> dict:
    """:func:`served` output against this file's full forward over each
    slot's tokens and the same chosen experts, held to ``tolerance``: the
    positions, cache rows and routing decisions of all slots together; the
    program's router alone on this file's inputs; and what :func:`served`
    read of the engine's own prefill and decode programs."""
    want, rows, shortfall, differs, inputs = [], [], [], [], []
    for slot in got["slots"]:
        logits, routing, first_rows = forward(
            engine.model_config, engine.params, slot["sequence"],
            slot["positions"], faults, forced=slot["chose"])
        want.append(logits)
        rows.append(first_rows)
        shortfall.append(routing["shortfall"])
        differs.append(routing["differs"])
        inputs.append(routing["first_input"])
    report = compare(
        np.concatenate([slot["logits"] for slot in got["slots"]]),
        np.concatenate(want), tolerance,
        np.concatenate([slot["rows"] for slot in got["slots"]]),
        np.concatenate(rows),
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
    if getattr(engine, "family", None) != "latent":
        raise RuntimeError(
            f"model {engine.config.model!r} is not served by the latent "
            f"family's programs here: there is no latent pool to compare")
    # a test-size configuration's file may state smaller sizes for the check
    # beside its limits (tests/bench/fixtures/latent); the cell's states none
    if "check_prompts" in tolerance:
        how.setdefault("prompts", tuple(map(int, tolerance["check_prompts"])))
    if "check_decode_steps" in tolerance:
        how.setdefault("steps", int(tolerance["check_decode_steps"]))
    return judge(engine, served(engine, seed, **how), tolerance)
