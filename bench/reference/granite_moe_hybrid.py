"""The ``granitemoehybrid`` decoder (granite-4.0-h-small: a Mamba-2 mixer or
attention, then 72 gated experts top-10 and a shared expert, in every
layer), written plainly.

A float32 ``jax.numpy`` forward pass under
``default_matmul_precision("highest")``, one sub-layer at a time from the
engine's own parameters: no cache, no kernels, no chunked scan, no batching.
From the published ``config.json`` (``hidden_act`` silu, ``rms_norm_eps``
1e-5, no bias but the convolution's), with ``m`` = ``residual_multiplier``::

    x0      = 12 * Embed[token]                               # embedding_multiplier
    layer l : x <- x + m * Mixer_l(RMSNorm_in(x))             # Mamba-2 if layer_types[l] == "mamba", else attention
              h  = RMSNorm_post(x)
              x <- x + m * ( Routed(h) + Shared(h) )
    logits  = (RMSNorm_f(x) @ Embed^T) / 16                   # tied head, logits_scaling

    attention: q, k, v = h Wq, h Wk, h Wv   (32 / 8 / 8 heads of 128; no rotary, no q/k norm)
               softmax(q k^T * 0.0078125 + causal mask) v, then Wo     # attention_multiplier, not 1/sqrt(128)
    Mamba-2  : [z | xBC | dt] = h W_in  (8192 | 8192 + 2*1*128 | 128);  xBC <- silu(conv1d_4(xBC) + b)
               dt = softplus(dt + dt_bias); A = -exp(A_log); per head (64 x 128 state):
               S <- exp(dt A) S + dt * x B^T;  y = S C + D x;  out = (RMSNorm(y * silu(z)) * w) W_out   # one group of 8192
    Routed   : l = h W_r (72 logits, float32); (top10, idx) = top_k(l, 10); g = softmax(top10)
               Routed(h) = sum_j g_j * ( silu(a_j) * b_j ) W_out[idx_j],  [a_j | b_j] = h W_in[idx_j]   (2 x 768)
    Shared   : ( silu(a) * b ) W_out_s,  [a | b] = h W_in_s   (2 x 1536)

The program writes a layer as two sub-layers of its pattern (``ME`` or
``*E``); this file walks the same pattern. The Mamba-2 mixer is the one
``reference/hybrid_ssm_moe.py`` spells (one position after another), taken
from there; everything else of the layer is here. The four multipliers are
the published numbers, written below and not read from the program's
configuration, which only gives the sizes.

**The share**: only the chosen experts this chip holds (``[expert_first,
expert_first + experts_held)``) are computed, one after another, each cast
to float32 by itself so that the layer fits beside the served model; the
softmax is over the ten winners wherever they live; what the other chip's
experts would add is left out, as in the program.

Assumed (the config does not fix them; the configuration's file lists them
too): the recurrent state float32 and the convolution's tail bfloat16; ``dt``
not clamped above; the router's weights in the model's type and its logits
float32; weights random.

:func:`check_engine` is the comparison a run's ``correct`` rests on, the one
``reference/hybrid_ssm_moe.py`` makes (its ``served`` and ``compare``): three
seeded prompts of 600, 530 and 300 tokens, inside the cell's 32-1024, in
three of four slots, the first two prefilled as one batch of the 1024 bucket
(2,048 rows: the grouped expert pass) and the third alone in the 512 bucket
(the dense pass), then 512 decode steps together in the engine's chunks
through the pool and the recurrent state, the fourth slot idle among them;
against this file's forward over each slot's tokens following the program's
expert choices: logits at every compared position, the first Mamba-2
layer's state, and each routing choice against this file's own ranking of
the 72 logits.

Two more readings. The first, for the router's precision alone
(``router_alone_differing_share``). The program's activations are bfloat16,
and at the first expert layer that alone sends about 5% of the tokens to
another set of ten than this file's float32 input would (the tenth and the
eleventh of 72 logits lie 0.06 apart on average): a router whose logits are
rounded to bfloat16 reads 5.1% there and is not told from the served one.
So the program's expert layer (``models/hybrid.py`` ``moe_mixer``, the
function both served programs call, under the configuration the program was
served with) is also given THIS file's input of the first expert layer,
rounded to the model's type as the mixer takes it, where nothing but the
router's own arithmetic can move a choice, and its sets are compared with
this file's ranking of the logits of that same rounded input.

The second, for the programs a window dispatches (``engine_first_token_
shortfall``, ``engine_first_logprob_error``). ``served`` compiles the
model's functions itself; a window dispatches the engine's own prefill
programs (the model's and then the sampler's), which return a token. So the
engine's own programs (``engine._prefill_fn``, on its dispatch thread, its
own pool and state donated and bound again as a dispatch does) prefill the
same prompts in the same batches and once more as one batch of the
engine's ``prefill-batch`` rows, written to the pool's scratch block and to
the state rows of the first slots (free while the check runs; a slot's rows
are written whole at its next admission), and each row's greedy token and
its log-probability are held against the logits ``served`` read for that
prompt. A program that
does not return within :data:`ENGINE_PROGRAM_S` fails the check.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from reference.hybrid_ssm_moe import compare, f32, mamba2, rms_norm, served

# granite-4.0-h-small/config.json
EMBEDDING_MULTIPLIER = 12.0
RESIDUAL_MULTIPLIER = 0.22
ATTENTION_MULTIPLIER = 0.0078125
LOGITS_SCALING = 16.0

#: what the check has to tell from the served model: each read against the
#: program's output has to come out as not passed (tools/hybrid_probe.py
#: --faults); the state and the router's logits kept in bfloat16 are
#: controls of the program's side (``served(config=...)``)
FAULTS = (
    "softmax_then_top_k", "residual_multiplier_one", "attention_scale_rsqrt",
    "no_logits_scaling", "no_shared_expert", "no_embedding_multiplier",
)


def attention(u, w, c, faults=()):
    T = u.shape[0]
    q = (u @ w["wq"]).reshape(T, c.heads, c.head_dim)
    k = (u @ w["wk"]).reshape(T, c.kv_heads, c.head_dim)
    v = (u @ w["wv"]).reshape(T, c.kv_heads, c.head_dim)
    k = jnp.repeat(k, c.heads // c.kv_heads, axis=1)
    v = jnp.repeat(v, c.heads // c.kv_heads, axis=1)
    scale = (c.head_dim ** -0.5 if "attention_scale_rsqrt" in faults
             else ATTENTION_MULTIPLIER)
    s = jnp.einsum("thd,shd->hts", q, k) * scale
    s = jnp.where(jnp.arange(T)[:, None] >= jnp.arange(T)[None, :], s, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return out.reshape(T, c.heads * c.head_dim) @ w["wo"]


def gates(logits, chosen, faults=()):
    """The weights of the ``chosen (T, k)`` experts: the softmax over their
    own logits."""
    if "softmax_then_top_k" in faults:
        return jnp.take_along_axis(jax.nn.softmax(logits, -1), chosen, axis=-1)
    return jax.nn.softmax(jnp.take_along_axis(logits, chosen, axis=-1), -1)


def route(u, w, c, faults=()):
    """``(chosen experts (T, k), their weights (T, k))`` over ALL experts."""
    logits = u @ w["router"]
    chosen = jnp.argsort(-logits, axis=-1)[:, : c.experts_per_token]
    return chosen, gates(logits, chosen, faults)


def audit(u, w, c, forced, faults=()):
    """The program's choices ``forced (T, k)`` against this file's own
    ranking of the logits: ``(forced, weights (T, k) of the forced experts
    from this file's logits, shortfall (T,), differs (T,))``. ``shortfall``
    is how far the worst of a row's forced experts ranks under the k-th of
    this file's own logits (0 when all make the cut); ``differs`` whether
    the sets differ."""
    k = c.experts_per_token
    logits = u @ w["router"]
    cut = jnp.sort(logits, axis=-1)[:, -k]
    mine = jnp.take_along_axis(logits, forced, axis=-1)
    shortfall = jnp.maximum(cut[:, None] - mine, 0.0).max(axis=-1)
    own = jnp.argsort(-logits, axis=-1)[:, :k]
    differs = jnp.any(jnp.sort(own, -1) != jnp.sort(forced, -1), axis=-1)
    return forced, gates(logits, forced, faults), shortfall, differs


def gated(u, w_in, w_out):
    """``(silu(a) * b) W_out`` with ``[a | b] = u W_in``; ``w_in (2 I, H)``
    and ``w_out (I, H)`` as the program keeps a routed expert's."""
    a, b = jnp.split(u @ w_in.T, 2, axis=-1)
    return (jax.nn.silu(a) * b) @ w_out


def experts(u, w, c, faults=(), first=None, held=None, forced=None):
    """The chosen experts among ``held`` from ``first`` (this chip's share
    unless given), one after another, plus the shared expert. ``w["w_up"]``
    and ``w["w_down"]`` may be of any float type: each expert is cast to
    float32 by itself. With ``forced (T, k)`` the experts are the ones given
    (:func:`audit`)."""
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
    if "no_shared_expert" not in faults:
        out = out + gated(u, w["ws_up"].T, w["ws_down"])
    return out, (chosen if report is None else report)


def forward(config, params, tokens, positions, faults=(), forced=None):
    """The full forward over ``tokens``: ``(logits (len(positions), V),
    routing, final states (Mamba-2 layers, heads, head_dim, state))``, all
    numpy. ``routing`` is the chosen experts ``(expert layers, T, k)``; or,
    with ``forced (expert layers, T, k)`` (the program's choices, which the
    forward then follows), the audit of them: ``{"shortfall": (expert
    layers, T), "differs": (expert layers, T)}``."""
    c = config
    m = 1.0 if "residual_multiplier_one" in faults else RESIDUAL_MULTIPLIER
    routed_stacks = ("w_up", "w_down")     # cast an expert at a time
    take = jax.jit(lambda t, i: {
        k: a[i] if k in routed_stacks else f32(a[i]) for k, a in t.items()})
    norm = lambda x, w: rms_norm(x, w["norm"], c.norm_eps)  # noqa: E731
    mamba = jax.jit(lambda x, w: mamba2(norm(x, w), w, c))
    attend = jax.jit(lambda x, w: attention(norm(x, w), w, c, faults))
    route_own = jax.jit(lambda x, w: experts(norm(x, w), w, c, faults))
    route_forced = jax.jit(
        lambda x, w, f: experts(norm(x, w), w, c, faults, forced=f))
    stacks = {"M": "mamba", "*": "attn", "E": "moe"}
    seen = {"M": 0, "*": 0, "E": 0}
    routing, states = [], []
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][jnp.asarray(tokens)])
        if "no_embedding_multiplier" not in faults:
            x = x * EMBEDDING_MULTIPLIER
        for kind in c.pattern:
            i = seen[kind]
            w = take(params[stacks[kind]], i)            # one sub-layer
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
                if i == 0:      # what the first router reads, in float32
                    first_input = np.asarray(norm(x, w))
                out, report = route_forced(x, w, jnp.asarray(forced[i]))
                routing.append([np.asarray(r) for r in report])
            x = (x + m * out).block_until_ready()
            del w
        x = rms_norm(x[jnp.asarray(positions)], f32(params["final_norm"]),
                     c.norm_eps)
        logits = x @ f32(params["embed"]).T              # the tied head
        if "no_logits_scaling" not in faults:
            logits = logits / LOGITS_SCALING
        logits = np.asarray(logits)
    if forced is not None:
        routing = {"shortfall": np.stack([r[0] for r in routing]),
                   "differs": np.stack([r[1] for r in routing]),
                   "first_input": first_input}
    else:
        routing = np.stack(routing)
    return logits, routing, np.stack(states)


def router_alone(engine, inputs, dtype, faults=()) -> float:
    """The share of ``inputs (T, H)``, this file's float32 inputs of the
    first expert layer rounded to the model's type, for which the program's
    expert layer (``models/hybrid.py`` ``moe_mixer`` with the first layer's
    weights, its router computing in ``dtype``) chooses another set than
    this file's ranking of the logits of the same rounded inputs."""
    from langstream_tpu.models.hybrid import moe_mixer

    c = dataclasses.replace(engine.model_config, router_dtype=jnp.dtype(dtype))
    first = {k: v[0] for k, v in engine.params["moe"].items()}
    u = jnp.asarray(inputs).astype(c.dtype)
    theirs = jax.jit(lambda u: moe_mixer(
        c, first, u, jnp.ones((u.shape[0],), bool))[2])(u)
    with jax.default_matmul_precision("highest"):
        own, _ = route(f32(u), {"router": f32(first["router"])}, c, faults)
    return float(jnp.mean(jnp.any(
        jnp.sort(own, -1) != jnp.sort(theirs, -1), axis=-1)))


#: seconds one of the engine's own prefill programs may take (it compiles
#: on its first call; a program that is there returns in under a second)
ENGINE_PROGRAM_S = 600.0


def engine_prefill(engine, got: dict) -> dict:
    """The engine's own greedy prefill programs over the prompts of ``got``
    (:func:`served`'s output): ``{"engine_first_token_shortfall": how far
    the worst row's token lies under the best logit ``served`` read for
    its prompt, in spreads of those logits, "engine_first_logprob_error":
    the worst row's log-probability against the log-softmax of the same,
    "engine_wrong_row_shortfall": the least shortfall a row would read with
    its neighbour's token (what the first limit has to stay under),
    "engine_prefill_batches": [{"bucket", "rows"}]}``. The batches are
    ``served``'s own and one of the engine's ``prefill-batch`` rows in the
    largest bucket, the prompts taken in turn. Nothing a request reads is
    written: every row's block table is the pool's scratch block, and row
    ``r`` writes the state rows of slot ``r``, which is free while the check
    runs and whose rows its next admission writes whole."""
    cfg = engine.config
    prompts = got["facts"]["prompts"]
    tokens = [slot["sequence"][:size] for slot, (size, _) in
              zip(got["slots"], prompts)]
    read = [np.asarray(slot["logits"][0], np.float64) for slot in got["slots"]]
    fn = engine._prefill_fn((False, False, True))
    blocks = engine.paged_layout.max_blocks_per_slot

    def dispatch(padded, lengths):
        rows = padded.shape[0]
        sel = (jnp.zeros((rows, blocks), jnp.int32),
               jnp.arange(rows, dtype=jnp.int32))
        out = fn(engine.params, engine.cache_k, engine.cache_v, engine.state,
                 jnp.asarray(padded), jnp.asarray(lengths), sel,
                 jax.random.PRNGKey(0), jnp.zeros((rows,), jnp.float32),
                 jnp.zeros((rows,), jnp.int32), jnp.ones((rows,), jnp.float32))
        engine.cache_k, engine.cache_v, engine.state = out[2], out[3], out[4]
        return np.asarray(out[0]), np.asarray(out[1], np.float64)

    batches = [(b["bucket"], [i for i, (n, _) in enumerate(prompts)
                              if _bucket_of(n) == b["bucket"]])
               for b in got["facts"]["prefill_batches"]]
    widest = max(bucket for bucket, _ in batches)
    batches.append((widest, [i % len(prompts)
                             for i in range(min(cfg.prefill_batch, cfg.slots))]))
    shortfall = error = 0.0
    wrong_row = np.inf      # the least a program that read another row would
    for bucket, rows in batches:
        padded = np.zeros((len(rows), bucket), np.int32)
        for r, i in enumerate(rows):
            padded[r, : len(tokens[i])] = tokens[i]
        lengths = np.asarray([len(tokens[i]) for i in rows], np.int32)
        try:
            chose, logprob = engine._executor.submit(
                dispatch, padded, lengths).result(timeout=ENGINE_PROGRAM_S)
        except TimeoutError:
            raise RuntimeError(
                f"the engine's prefill program of {len(rows)} rows of the "
                f"{bucket} bucket did not return in {ENGINE_PROGRAM_S:.0f} s"
            ) from None
        for r, i in enumerate(rows):
            want = read[i] - np.log(np.sum(np.exp(read[i] - read[i].max()))) \
                - read[i].max()
            shortfall = max(shortfall, float(
                (read[i].max() - read[i][chose[r]]) / read[i].std()))
            error = max(error, float(abs(logprob[r] - want[chose[r]])))
            other = chose[(r + 1) % len(rows)]
            if rows[(r + 1) % len(rows)] != i:
                wrong_row = min(wrong_row, float(
                    (read[i].max() - read[i][other]) / read[i].std()))
    return {"engine_first_token_shortfall": shortfall,
            "engine_first_logprob_error": error,
            "engine_wrong_row_shortfall": wrong_row,
            "engine_prefill_batches": [
                {"bucket": bucket, "rows": len(rows)} for bucket, rows in batches]}


def _bucket_of(n: int) -> int:
    bucket = 32
    while bucket < n:
        bucket *= 2
    return bucket


def judge(engine, got: dict, tolerance: dict, faults=()) -> dict:
    """``served`` output against this file's full forward over each slot's
    tokens and the same chosen experts, held to ``tolerance``: the positions
    and routing decisions of all slots together, the states pooled by layer;
    a slot that ran nothing keeps a state of zeros; the program's router
    alone on this file's inputs (:func:`router_alone`); and the engine's own
    prefill programs on the same prompts (:func:`engine_prefill`)."""
    want, shortfall, differs, states, inputs = [], [], [], [], []
    for slot in got["slots"]:
        logits, routing, state = forward(
            engine.model_config, engine.params, slot["sequence"],
            slot["positions"], faults, forced=slot["chose"])
        want.append(logits)
        shortfall.append(routing["shortfall"])
        differs.append(routing["differs"])
        states.append(state)
        inputs.append(routing["first_input"])
    report = compare(
        np.concatenate([slot["logits"] for slot in got["slots"]]),
        np.concatenate(want), tolerance,
        np.stack([slot["state"] for slot in got["slots"]], axis=1),
        np.stack(states, axis=1),
        {"shortfall": np.concatenate(shortfall, axis=1),
         "differs": np.concatenate(differs, axis=1)})
    report["idle_state_untouched"] = got["idle_state_untouched"]
    report["router_alone_differing_share"] = router_alone(
        engine, np.concatenate(inputs), got["facts"]["router_dtype"], faults)
    report.update(engine_prefill(engine, got))
    report["passed"] = bool(
        report["passed"] and got["idle_state_untouched"]
        and all(report[k] <= tolerance[k] for k in (
            "router_alone_differing_share", "engine_first_token_shortfall",
            "engine_first_logprob_error")))
    report.update(got["facts"])
    return report


def check_engine(engine, seed: int, tolerance: dict, **how) -> dict:
    """The served model against the reference, outside any window. An
    engine that serves another family under the configuration's name (a
    commit before the family had this layer) is refused at once."""
    if not getattr(engine, "is_hybrid", False):
        raise RuntimeError(
            f"model {engine.config.model!r} is not served by the hybrid "
            f"family's programs here: there is no recurrent state to compare")
    return judge(engine, served(engine, seed, **how), tolerance)
