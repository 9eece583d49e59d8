"""The hybrid decoder (``nemotron_h``: Mamba-2 + attention + routed experts),
written plainly.

A float32 ``jax.numpy`` forward pass under
``default_matmul_precision("highest")``, one layer at a time from the
engine's own parameters: no cache, no kernels, no chunked scan, no batching.
Every layer ``l`` of the published pattern is ``x <- x + Mixer(RMSNorm(x))``:

- ``M`` Mamba-2: ``[z | xBC | dt] = W_in u``; ``xBC <- silu(conv1d(xBC) + b)``
  (causal, depthwise, kernel 4); ``[x | B | C] = xBC`` with head ``h`` using
  group ``h // (heads / groups)``; ``dt <- softplus(dt + dt_bias)``;
  ``A = -exp(A_log)``; one position after another
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t``;
  ``y <- RMSNorm_groups(y * silu(z)) * w``; ``out = W_out y``.
- ``*`` attention: 32 query / 2 key-value heads, causal softmax at
  ``1/sqrt(head_dim)``, NO rotary embedding.
- ``E`` experts: ``s = sigmoid(W_r x)``; the top k of ``s + bias``; weights
  the chosen ``s`` over their sum (+1e-20) times the routed scale; expert
  ``e``: ``W_down relu(W_up x)^2``; a shared expert of the same form, always.
  **The share**: only the chosen experts this chip holds
  (``[expert_first, expert_first + experts_held)``) are computed, one after
  another; what the others would add is left out, as in the program.

:func:`check_engine` is the comparison a run's ``correct`` rests on: three
seeded prompts of unequal length (none a multiple of 128, each across
several scan chunks and in a padded bucket) in three of four slots, two of
them prefilled as one batch of the 1024 bucket (2,048 rows: the grouped
expert pass) and one alone in the 512 bucket, by the program's own prefill
(pool and recurrent state), then decoded together by its decode program
with the selected read kernel in chunks of the engine's own size (the dense
expert pass), the fourth slot idle among them; against this file's full
forward over each slot's tokens and the same chosen experts: logits at
every compared position, the recurrent state each slot ends with, and each
of the program's routing choices against this file's own ranking.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# Tolerance, and why: as reference/dense_gqa.py says for the dense family,
# the error is judged against the logits' spread (RMS over the vocabulary as
# a share of the reference's standard deviation, and the correlation), and
# each configuration's file states its own limits. This family adds two
# readings.
#
# The routing. A router's top k of 128 sigmoid scores is a discrete choice
# with many near-ties: bfloat16 activations tip a few percent of them the
# other way (8% of the decisions at the published widths), and with random
# weights a tipped choice is another expert's unrelated output: left alone
# they read 50% of the logits' spread, which says nothing of whether the
# program computes the published function. So the choice is judged by
# itself and the arithmetic by itself: the reference's forward takes the
# experts the PROGRAM chose (and computes their weights from its own float32
# scores), and every choice is audited against the reference's own ranking:
# an expert the program chose may fall short of the reference's k-th ranked
# score (with the correction bias) by ``routing_margin`` at most. A program
# that ranks by another rule (no bias, another k) falls short by the size
# of the bias, or disagrees in the logits. Deep in the stack the program's
# bfloat16 activations move the scores themselves by more than a router of
# fewer bits would, so the router's own precision is read where its input
# is nearly exact: at the FIRST expert layer (one Mamba-2 mixer after the
# embedding) the share of tokens whose chosen set differs from the
# reference's is held to ``first_routing_differing_share``. A router whose
# operands, logits and scores are rounded to bfloat16 reads two and a half
# times the served one there, and one without the bias half of all tokens.
#
# The recurrent state. The state the program ends with against the state
# the reference ends with, as the RMS of the difference over the RMS of the
# reference's, by Mamba-2 layer, pooled over the check's slots; the limit
# ``state_rms_share`` is on the FIRST layer, whose input is the embedding
# itself, so that its state differs only by the mixer's own arithmetic. A
# state kept in bfloat16 rounds all of it at every token, which the logits
# of a few positions hardly see and this reading does once the decode is
# long enough: hence 512 decode steps.

#: (prompt tokens, slot) of the check: the first two share the 1024 bucket
#: and are prefilled as one batch, the third sits in the 512 bucket; slot 1
#: stays idle through the decode
CHECK_PROMPTS = ((600, 2), (530, 0), (300, 3))
CHECK_SLOTS = 4
CHECK_DECODE_STEPS = 512
FAULTS = (
    "no_convolution", "no_d_skip", "ungated_norm", "wrong_head_to_group",
    "no_correction_bias", "no_routed_scale", "no_shared_expert",
    "rotary_applied", "top_k_minus_one",
)


def f32(t):
    return jnp.asarray(t, dtype=jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mamba2(u, w, c, faults=()):
    """``u (T, hidden)`` normed. Returns ``(out (T, hidden), final state
    (heads, head_dim, state))``."""
    T = u.shape[0]
    heads, p, groups, n, k = (c.ssm_heads, c.ssm_head_dim, c.ssm_groups,
                              c.ssm_state, c.conv_kernel)
    d_inner = heads * p
    # the fused in_proj by its three column blocks, as the program holds it
    z, xbc, dt = u @ w["w_z"], u @ w["w_xbc"], u @ w["w_dt"]
    if "no_convolution" not in faults:
        padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
        # torch conv1d, padding k-1, cut to T: tap k-1 meets the current row
        xbc = sum(padded[i : i + T] * w["conv_w"][:, i] for i in range(k)) \
            + w["conv_b"]
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :d_inner].reshape(T, heads, p)
    B = xbc[:, d_inner : d_inner + groups * n].reshape(T, groups, n)
    C = xbc[:, d_inner + groups * n :].reshape(T, groups, n)
    group_of = np.arange(heads) // (heads // groups)
    if "wrong_head_to_group" in faults:
        group_of = np.arange(heads) % groups
    B, C = B[:, group_of], C[:, group_of]                  # (T, heads, n)
    dt = jax.nn.softplus(dt + w["dt_bias"])                # (T, heads)
    A = -jnp.exp(w["A_log"])

    def one(h, t):
        x_t, B_t, C_t, dt_t = t
        h = jnp.exp(dt_t * A)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, C_t)

    h, y = jax.lax.scan(one, jnp.zeros((heads, p, n)), (x, B, C, dt))
    if "no_d_skip" not in faults:
        y = y + w["D"][:, None] * x
    y = y.reshape(T, d_inner)
    if "ungated_norm" not in faults:
        y = y * jax.nn.silu(z)
    y = y.reshape(T, groups, d_inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + c.norm_eps)
    return (y.reshape(T, d_inner) * w["gate_norm"]) @ w["w_out"], h


def rotate_half(x, theta):
    T, _, D = x.shape
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    a = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(a)[:, None], jnp.sin(a)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(u, w, c, faults=()):
    T = u.shape[0]
    q = (u @ w["wq"]).reshape(T, c.heads, c.head_dim)
    k = (u @ w["wk"]).reshape(T, c.kv_heads, c.head_dim)
    v = (u @ w["wv"]).reshape(T, c.kv_heads, c.head_dim)
    if "rotary_applied" in faults:
        q, k = rotate_half(q, c.rope_theta), rotate_half(k, c.rope_theta)
    k = jnp.repeat(k, c.heads // c.kv_heads, axis=1)
    v = jnp.repeat(v, c.heads // c.kv_heads, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(c.head_dim)
    s = jnp.where(jnp.arange(T)[:, None] >= jnp.arange(T)[None, :], s, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return out.reshape(T, c.heads * c.head_dim) @ w["wo"]


def route(u, w, c, faults=()):
    """``(chosen experts (T, k), their weights (T, k))`` over ALL experts."""
    k = c.experts_per_token - ("top_k_minus_one" in faults)
    s = jax.nn.sigmoid(u @ w["router"])
    ranked = s if "no_correction_bias" in faults else s + w["bias"]
    chosen = jnp.argsort(-ranked, axis=-1)[:, :k]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    if "no_routed_scale" not in faults:
        weights = weights * c.routed_scale
    return chosen, weights


def audit(u, w, c, forced, faults=()):
    """The program's choices ``forced (T, k)`` against this file's own
    ranking: ``(weights (T, k') of the forced experts from this file's
    scores, shortfall (T,), differs (T,))``. ``shortfall`` is how far the
    worst of a row's forced experts ranks under the k-th of this file's own
    ranking (0 when all make the cut); ``differs`` whether the sets differ."""
    k = c.experts_per_token - ("top_k_minus_one" in faults)
    forced = forced[:, :k]            # the program ranks its choices
    s = jax.nn.sigmoid(u @ w["router"])
    ranked = s if "no_correction_bias" in faults else s + w["bias"]
    cut = jnp.sort(ranked, axis=-1)[:, -k]
    mine = jnp.take_along_axis(ranked, forced, axis=-1)
    shortfall = jnp.maximum(cut[:, None] - mine, 0.0).max(axis=-1)
    own = jnp.argsort(-ranked, axis=-1)[:, :k]
    differs = jnp.any(jnp.sort(own, -1) != jnp.sort(forced, -1), axis=-1)
    picked = jnp.take_along_axis(s, forced, axis=-1)
    weights = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    if "no_routed_scale" not in faults:
        weights = weights * c.routed_scale
    return forced, weights, shortfall, differs


def experts(u, w, c, faults=(), first=None, held=None, forced=None):
    """The chosen experts among ``held`` from ``first`` (this chip's share
    unless given), one after another, plus the shared expert. With
    ``forced (T, k)`` the experts are the ones given (:func:`audit`)."""
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
        act = jnp.square(jax.nn.relu(u @ w["w_up"][e].T))   # kept (I, H)
        out = out + gate[:, None] * (act @ w["w_down"][e])
    if "no_shared_expert" not in faults:
        out = out + jnp.square(jax.nn.relu(u @ w["ws_up"])) @ w["ws_down"]
    return out, (chosen if report is None else report)


def forward(config, params, tokens, positions, faults=(), forced=None):
    """The full forward over ``tokens``: ``(logits (len(positions), V),
    routing, final states (Mamba-2 layers, heads, head_dim, state))``, all
    numpy. ``routing`` is the chosen experts ``(expert layers, T, k)``; or,
    with ``forced (expert layers, T, k)`` (the program's choices, which the
    forward then follows), the audit of them: ``{"shortfall": (expert
    layers, T), "differs": (expert layers, T)}``."""
    c = config
    take = jax.jit(lambda t, i: jax.tree.map(lambda a: f32(a[i]), t))
    norm = lambda x, w: rms_norm(x, w["norm"], c.norm_eps)  # noqa: E731
    mamba = jax.jit(lambda x, w: mamba2(norm(x, w), w, c, faults))
    attend = jax.jit(lambda x, w: attention(norm(x, w), w, c, faults))
    route_own = jax.jit(lambda x, w: experts(norm(x, w), w, c, faults))
    route_forced = jax.jit(
        lambda x, w, f: experts(norm(x, w), w, c, faults, forced=f))
    stacks = {"M": "mamba", "*": "attn", "E": "moe"}
    seen = {"M": 0, "*": 0, "E": 0}
    routing, states = [], []
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][jnp.asarray(tokens)])
        for kind in c.pattern:
            i = seen[kind]
            w = take(params[stacks[kind]], i)            # one layer in float32
            seen[kind] += 1
            if kind == "M":
                out, h = mamba(x, w)
                states.append(np.asarray(h))
            elif kind == "*":
                out = attend(x, w)
            elif forced is None:
                out, chosen = route_own(x, w)
                routing.append(np.asarray(chosen))
            else:
                out, report = route_forced(x, w, jnp.asarray(forced[i]))
                routing.append([np.asarray(r) for r in report])
            x = (x + out).block_until_ready()
            del w
        x = rms_norm(x[jnp.asarray(positions)], f32(params["final_norm"]),
                     c.norm_eps)
        logits = np.asarray(x @ f32(params["lm_head"]))
    if forced is not None:
        routing = {"shortfall": np.stack([r[0] for r in routing]),
                   "differs": np.stack([r[1] for r in routing])}
    else:
        routing = np.stack(routing)
    return logits, routing, np.stack(states)


def compare(got, want, tolerance: dict, state_got=None, state_want=None,
            routing=None) -> dict:
    """Per compared position the RMS error over the vocabulary as a share of
    the reference's spread and the correlation; the recurrent state's RMS
    error as a share of the reference state's RMS, by layer (``state_*``:
    ``(layers, ...)``, whatever follows pooled), the first layer's held to
    ``state_rms_share``; and the routing audit's worst shortfall, held to
    ``routing_margin``, with the share of the first expert layer's tokens
    whose chosen set differs, held to ``first_routing_differing_share``."""
    rms = np.sqrt(np.mean((got - want) ** 2, axis=-1)) / np.std(want, axis=-1)
    corr = [float(np.corrcoef(g, w)[0, 1]) for g, w in zip(got, want)]
    rows = [{"rms_share": float(r), "correlation": c} for r, c in zip(rms, corr)]
    worst_rms, worst_corr = float(rms.max()), min(corr)
    passed = (worst_rms <= tolerance["rms_share"]
              and worst_corr >= tolerance["min_correlation"])
    report = {"positions": rows, "worst_rms_share": worst_rms,
              "worst_correlation": worst_corr, "tolerance": dict(tolerance)}
    if state_got is not None:
        share = lambda g, w: float(                          # noqa: E731
            np.sqrt(np.mean((g - w) ** 2)) / np.sqrt(np.mean(w ** 2)))
        state_got = np.asarray(state_got, np.float32)
        by_layer = [share(g, w) for g, w in zip(state_got, state_want)]
        report["state_rms_share_by_layer"] = by_layer
        report["first_state_rms_share"] = by_layer[0]
        report["worst_state_rms_share"] = max(by_layer)
        # (..., heads, head_dim, state): the first layer's by head
        report["first_state_rms_share_by_head"] = [
            share(g, w) for g, w in zip(np.moveaxis(state_got[0], -3, 0),
                                        np.moveaxis(state_want[0], -3, 0))]
        passed = passed and by_layer[0] <= tolerance["state_rms_share"]
    if routing is not None:
        shortfall, differs = routing["shortfall"], routing["differs"]
        report["routing_decisions"] = int(differs.size)
        report["routing_decisions_differing"] = int(differs.sum())
        report["worst_routing_shortfall"] = float(shortfall.max())
        report["first_routing_shortfall"] = float(shortfall[0].max())
        report["first_routing_differing_share"] = float(differs[0].mean())
        passed = (
            passed
            and report["worst_routing_shortfall"] <= tolerance["routing_margin"]
            and report["first_routing_differing_share"]
            <= tolerance["first_routing_differing_share"])
    report["passed"] = bool(passed)
    return report


def served(engine, seed: int, *, prompts=CHECK_PROMPTS,
           steps: int = CHECK_DECODE_STEPS, config=None) -> dict:
    """What the program computes for the check's seeded prompts: its own
    prefill, one batch a bucket (pool and recurrent state; a later batch
    finds the earlier slots' rows in the state it is given), then its decode
    program with the selected read kernel over all :data:`CHECK_SLOTS` slots
    in chunks of the engine's ``decode-chunk``, each slot following its own
    greedy choice; on a scratch pool and a scratch recurrent state of the
    engine's layout, so the engine's own are not touched. ``prompts`` are
    ``(tokens, slot)``; a slot without one stays idle. ``config`` replaces
    the engine's model configuration (a probe of what a lower precision
    reads). Returns ``{"slots": [one entry a prompt], "facts": {...}}``."""
    from langstream_tpu.models.hybrid import (
        hybrid_decode_chunk_paged,
        hybrid_prefill_paged,
        init_hybrid_pool,
        init_hybrid_state,
    )
    from langstream_tpu.models.paged import PagedLayout

    c, cfg = config or engine.model_config, engine.config
    bs = cfg.kv_block_size
    per_slot = -(-(max(n for n, _ in prompts) + steps + 1) // bs)
    layout = PagedLayout(block_size=bs, num_blocks=CHECK_SLOTS * per_slot + 1,
                         max_blocks_per_slot=per_slot)
    pool_k, pool_v = init_hybrid_pool(c, layout)
    state = init_hybrid_state(c, CHECK_SLOTS)
    tables = 1 + jnp.arange(
        CHECK_SLOTS * per_slot, dtype=jnp.int32).reshape(CHECK_SLOTS, per_slot)
    rng = np.random.default_rng(int(seed) % (2 ** 32))
    tokens = [rng.integers(0, c.vocab_size, size=n, dtype=np.int32)
              for n, _ in prompts]

    def bucket_of(n):
        bucket = 32
        while bucket < n:
            bucket *= 2
        return bucket

    prefill = jax.jit(lambda p, t, n, pk, pv, st, tb, s: hybrid_prefill_paged(
        c, p, t, n, pk, pv, st, tb, s))
    first = np.zeros((CHECK_SLOTS,), np.int32)
    lengths = np.zeros((CHECK_SLOTS,), np.int32)
    logits0, chose0, batches = {}, {}, []
    for bucket in sorted({bucket_of(n) for n, _ in prompts}, reverse=True):
        rows = [i for i, (n, _) in enumerate(prompts) if bucket_of(n) == bucket]
        slots = np.asarray([prompts[i][1] for i in rows], np.int32)
        padded = np.zeros((len(rows), bucket), np.int32)
        for r, i in enumerate(rows):
            padded[r, : prompts[i][0]] = tokens[i]
        n = np.asarray([prompts[i][0] for i in rows], np.int32)
        logits, pool_k, pool_v, state, routed = prefill(
            engine.params, jnp.asarray(padded), jnp.asarray(n), pool_k, pool_v,
            state, tables[slots], jnp.asarray(slots))
        logits, routed = np.asarray(logits, np.float32), np.asarray(routed)
        for r, i in enumerate(rows):
            logits0[i], chose0[i] = logits[r], routed[:, r, : prompts[i][0]]
        first[slots], lengths[slots] = logits.argmax(-1), n
        batches.append({"bucket": bucket, "rows": len(rows)})

    def greedy_with_logits(logits, key):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits

    kernel = engine.paged_read_kernel
    active = jnp.asarray(lengths > 0)
    decode = jax.jit(
        lambda p, t0, n, pk, pv, st, key, k: hybrid_decode_chunk_paged(
            c, p, t0, n, active, pk, pv, st, tables, greedy_with_logits, key,
            k, per_slot, kernel=kernel),
        static_argnums=7)
    chunk = max(1, min(int(cfg.decode_chunk), steps))
    t0, n = jnp.asarray(first), jnp.asarray(lengths)
    made, chunk_logits, chose = [], [], []
    for k in [chunk] * (steps // chunk) + [steps % chunk] * bool(steps % chunk):
        out = decode(engine.params, t0, n, pool_k, pool_v, state,
                     jax.random.PRNGKey(0), k)
        t0, n, pool_k, pool_v, state = out[2:7]
        made.append(np.asarray(out[0]))                    # (k, slots)
        chunk_logits.append(np.asarray(out[1]))            # (k, slots, V)
        chose.append(np.asarray(out[8]).swapaxes(0, 1))    # (blocks, k, slots, top)
    made, chunk_logits, chose = (np.concatenate(made), np.concatenate(chunk_logits),
                                 np.concatenate(chose, axis=1))
    ssm = np.asarray(state["ssm"], dtype=np.float32)
    live = [slot for _, slot in prompts]
    return {
        "slots": [{
            "slot": slot,
            # the sequence the program produced, for the reference to follow
            "sequence": np.concatenate(
                [tokens[i], first[slot : slot + 1], made[:-1, slot]]),
            "positions": list(range(size - 1, size + steps)),
            "logits": np.concatenate([logits0[i][None], chunk_logits[:, slot]]),
            "state": ssm[:, slot],
            # the experts the program chose, (expert layers, positions, k):
            # the prompt's from the prefill, each decoded position's from
            # its step
            "chose": np.concatenate([chose0[i], chose[:, :, slot]], axis=1),
        } for i, (size, slot) in enumerate(prompts)],
        "idle_state_untouched": bool(all(
            not ssm[:, s].any() for s in range(CHECK_SLOTS) if s not in live)),
        "facts": {
            "prompts": [list(p) for p in prompts], "prefill_batches": batches,
            "decode_steps": steps, "decode_chunk": chunk, "kernel": kernel,
            "state_dtype": jnp.dtype(c.state_dtype).name,
            "router_dtype": jnp.dtype(c.router_dtype).name,
            "kv_quantize": cfg.kv_quantize, "quantize": cfg.quantize,
        },
    }


def judge(engine, got: dict, tolerance: dict, faults=()) -> dict:
    """:func:`served` output against this file's full forward over each
    slot's tokens and the same chosen experts, held to ``tolerance``: the
    positions and routing decisions of all slots together, the states pooled
    by layer; and a slot that ran nothing keeps a state of zeros."""
    want, shortfall, differs, states = [], [], [], []
    for slot in got["slots"]:
        logits, routing, state = forward(
            engine.model_config, engine.params, slot["sequence"],
            slot["positions"], faults, forced=slot["chose"])
        want.append(logits)
        shortfall.append(routing["shortfall"])
        differs.append(routing["differs"])
        states.append(state)
    report = compare(
        np.concatenate([slot["logits"] for slot in got["slots"]]),
        np.concatenate(want), tolerance,
        np.stack([slot["state"] for slot in got["slots"]], axis=1),
        np.stack(states, axis=1),
        {"shortfall": np.concatenate(shortfall, axis=1),
         "differs": np.concatenate(differs, axis=1)})
    report["idle_state_untouched"] = got["idle_state_untouched"]
    report["passed"] = report["passed"] and got["idle_state_untouched"]
    report.update(got["facts"])
    return report


def check_engine(engine, seed: int, tolerance: dict, **how) -> dict:
    """The served model against the reference, outside any window."""
    return judge(engine, served(engine, seed, **how), tolerance)
