"""The ``solar_open2`` layer in the hybrid family (models/hybrid.py,
ops/delta_state.py) at the ``solar-tiny`` preset, in float32 on the CPU: the
gated delta rule's chunked prefill against its token-by-token recurrence (at
lengths that are no multiple of the chunk, across a batch of unequal lengths,
under decays strong enough to overflow a quotient of cumulative decays), the
state kernel's two forms against each other, the served programs (prefill,
then decode through the paged pool and the delta-rule state) against the
plain reference's full forward, the attention's output gate on and off, and
the shares of one deployment against the uncut layer."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models import hybrid
from langstream_tpu.models.hybrid import (
    HybridConfig,
    delta_chunked,
    delta_prefill,
    hybrid_decode_chunk_paged,
    hybrid_prefill_paged,
    init_hybrid_params,
    init_hybrid_pool,
    init_hybrid_state,
    moe_mixer,
)
from langstream_tpu.models.paged import PagedLayout
from langstream_tpu.ops.delta_state import delta_state_step

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


@pytest.fixture(scope="module")
def c():
    return dataclasses.replace(HybridConfig.solar_tiny(max_seq_len=256),
                               dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(c):
    return init_hybrid_params(c)


# -- the configuration ---------------------------------------------------------


def test_the_presets_are_the_published_layers():
    real = HybridConfig.solar_open2_ep8()
    assert real.pattern == "*EKEKEKE" and real.layers == 8
    assert real.blocks == (True, False, False, False)
    assert real.delta_blocks == (False, True, True, True)
    assert (real.mamba_layers, real.delta_layers, real.attn_layers) == (0, 3, 1)
    assert (real.hidden, real.heads, real.kv_heads, real.head_dim) == \
        (4096, 64, 8, 128)
    assert (real.delta_heads, real.delta_head_dim, real.conv_kernel) == \
        (64, 128, 4)
    assert (real.experts, real.experts_held, real.experts_per_token,
            real.intermediate, real.shared_intermediate) == (320, 40, 8, 1280, 1280)
    assert real.vocab_size == 24576 and not real.tied_head and real.attn_gate
    assert real.router == "sigmoid" and real.routed_scale == 1.0
    # 3 x (64 x 128 x 128 float32 + a tail of 3 x 24,576 bfloat16): 13.0 MB
    assert real.state_bytes_per_slot == 3 * (64 * 128 * 128 * 4 + 3 * 24576 * 2)
    state = jax.eval_shape(lambda: init_hybrid_state(real, 192))
    assert set(state) == {"delta", "dconv"}
    assert state["delta"].shape == (3, 192, 64, 128, 128)
    assert state["delta"].dtype == jnp.float32
    assert state["dconv"].shape == (3, 192, 3, 24576)
    shapes = jax.eval_shape(lambda: init_hybrid_params(real))
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    # the configuration file's table: 137.7 M a delta-rule mixer (with its
    # norms), 109.1 M the gated attention, 3,308 M held in all
    assert abs(count(shapes["delta"]) / 3 - 137.7e6) < 0.1e6
    assert abs(count(shapes["attn"]) - 109.1e6) < 0.1e6
    assert abs(count(shapes) - 3308e6) < 1e6
    assert "mamba" not in shapes
    tiny = HybridConfig.solar_tiny()
    assert tiny.pattern == real.pattern and tiny.experts_held * 2 == tiny.experts


def test_the_older_patterns_hold_no_delta_state_and_draw_the_weights_they_drew():
    for name in ("tiny", "granite_tiny"):
        c = getattr(HybridConfig, name)()
        assert c.delta_layers == 0 and not c.attn_gate
        assert set(jax.eval_shape(lambda: init_hybrid_state(c, 2))) == \
            {"ssm", "conv"}
        shapes = jax.eval_shape(lambda: init_hybrid_params(c))
        assert "delta" not in shapes and "wg" not in shapes["attn"]


# -- the chunked delta rule against its recurrence ------------------------------


def recurrence(q, k, v, g, beta):
    """Token by token through the state step's XLA form."""
    B, P, H, D = k.shape
    S = jnp.zeros((1, B, H, v.shape[-1], D), jnp.float32)
    out = []
    for t in range(P):
        o, S = delta_state_step(
            S, 0, jnp.exp(g[:, t]), k[:, t], q[:, t], v[:, t], beta[:, t],
            jnp.ones((B,), bool))
        out.append(o)
    return jnp.stack(out, axis=1), S[0]


def inputs(seed, B, P, H, D, decay):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, P, H, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (B, P, H, D)))
    v = jax.random.normal(ks[2], (B, P, H, D))
    g = -decay * jax.random.uniform(ks[3], (B, P, H, D))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, P, H)))
    return q, k, v, g, beta


@pytest.mark.parametrize("P, chunk", [(48, 16), (64, 64), (32, 8), (96, 32)])
def test_the_chunked_form_is_the_recurrence(P, chunk):
    q, k, v, g, beta = inputs(P, 2, P, 3, 16, 0.2)
    o, S = delta_chunked(q, k, v, g, beta, chunk)
    want_o, want_S = recurrence(q, k, v, g, beta)
    np.testing.assert_allclose(o, want_o, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(S, want_S, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("decay", [8.0, 40.0])
def test_a_strong_decay_overflows_nothing(decay):
    """``sum g`` over a chunk far below -60: ``exp(-G)`` of the usual
    quotient ``k / Gamma`` is infinite in float32; every exponent the chunked
    form takes is <= 0."""
    q, k, v, g, beta = inputs(3, 2, 64, 2, 16, decay)
    over_chunk = np.asarray(g).reshape(2, 2, 32, 2, 16).sum(2)
    assert over_chunk.min() < -60 * decay / 8.0     # -480 at the stronger
    assert decay < 40 or -over_chunk.min() > np.log(np.finfo(np.float32).max)
    o, S = delta_chunked(q, k, v, g, beta, 32)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(S)).all()
    want_o, want_S = recurrence(q, k, v, g, beta)
    np.testing.assert_allclose(o, want_o, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(S, want_S, rtol=2e-4, atol=2e-5)


def test_the_family_s_own_decays_reach_the_hazard(c, params):
    """With ``A_log`` and ``dt_bias`` as the program draws them and the
    gate's input-dependent term, some channel's decay over a 64-token chunk
    passes exp(-60) at the tiny size already."""
    lp = jax.tree.map(lambda a: a[0], params["delta"])
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 64, c.hidden))
    g, beta = hybrid._delta_gates(c, lp, u)
    assert float(g.sum(axis=1).min()) < -60
    assert 0 < float(beta.min()) and float(beta.max()) < 2 and float(beta.max()) > 1


def test_a_batch_of_unequal_lengths_ends_each_row_at_its_last_token(c, params):
    """Right-padded rows of one bucket (lengths that are no multiple of the
    chunk of 16) give each row's outputs, final state and convolution tail as
    the row gives alone at its own length's bucket."""
    lp = jax.tree.map(lambda a: a[1], params["delta"])
    rng = np.random.default_rng(0)
    sizes = (37, 64, 9, 50)
    rows = [jnp.asarray(rng.normal(size=(n, c.hidden)), jnp.float32)
            for n in sizes]
    padded = jnp.stack([jnp.pad(r, ((0, 64 - r.shape[0]), (0, 0))) for r in rows])
    out, state, tail = delta_prefill(c, lp, padded, jnp.asarray(sizes))
    for r, (row, n) in enumerate(zip(rows, sizes)):
        bucket = 16 * -(-n // 16)
        alone = delta_prefill(
            c, lp, jnp.pad(row, ((0, bucket - n), (0, 0)))[None],
            jnp.asarray([n]))
        np.testing.assert_allclose(out[r, :n], alone[0][0, :n],
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(state[r], alone[1][0], rtol=2e-4, atol=2e-5)
        np.testing.assert_array_equal(tail[r], alone[2][0])
    # and against the plain reference's token-by-token layer
    from reference import solar_open2 as reference

    with jax.default_matmul_precision("highest"):
        want, states = reference.delta_rule(rows[0], lp, c, states_after=(37,))
    np.testing.assert_allclose(out[0, :37], want, rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(state[0], states[0], rtol=2e-3, atol=2e-5)


# -- the served programs against the plain reference ---------------------------


def serve(c, params, prompts, bucket, steps, chunk=4, kernel="xla"):
    """Prefill ``prompts`` as one batch of ``bucket``, then ``steps`` greedy
    decode steps in chunks through pool and state, slot 1 idle among them."""
    slots = len(prompts) + 1
    live = [0] + list(range(2, slots))
    per_slot = 18
    layout = PagedLayout(block_size=16, num_blocks=1 + slots * per_slot,
                         max_blocks_per_slot=per_slot)
    pool_k, pool_v = init_hybrid_pool(c, layout)
    state = init_hybrid_state(c, slots)
    tables = 1 + jnp.arange(slots * per_slot, dtype=jnp.int32).reshape(
        slots, per_slot)
    padded = np.zeros((len(prompts), bucket), np.int32)
    for r, p in enumerate(prompts):
        padded[r, : len(p)] = p
    n = np.asarray([len(p) for p in prompts], np.int32)
    logits, pool_k, pool_v, state, routed = jax.jit(
        lambda *a: hybrid_prefill_paged(c, *a))(
        params, jnp.asarray(padded), jnp.asarray(n), pool_k, pool_v, state,
        tables[jnp.asarray(live)], jnp.asarray(live, jnp.int32))
    after_prefill = jax.tree.map(np.asarray, state)
    first = np.zeros((slots,), np.int32)
    lengths = np.zeros((slots,), np.int32)
    first[live], lengths[live] = np.asarray(logits).argmax(-1), n
    active = jnp.asarray(lengths > 0)
    decode = jax.jit(lambda t0, ln, pk, pv, st: hybrid_decode_chunk_paged(
        c, params, t0, ln, active, pk, pv, st, tables,
        lambda lg, key: (jnp.argmax(lg, -1).astype(jnp.int32), lg),
        jax.random.PRNGKey(0), chunk, per_slot, kernel=kernel))
    t0, ln = jnp.asarray(first), jnp.asarray(lengths)
    made, step_logits, chose = [], [], []
    for _ in range(steps // chunk):
        out = decode(t0, ln, pool_k, pool_v, state)
        t0, ln, pool_k, pool_v, state = out[2:7]
        made.append(np.asarray(out[0]))
        step_logits.append(np.asarray(out[1]))
        chose.append(np.asarray(out[8]).swapaxes(0, 1))
    made, step_logits = np.concatenate(made), np.concatenate(step_logits)
    chose, routed = np.concatenate(chose, axis=1), np.asarray(routed)
    rows = []
    for r, slot in enumerate(live):
        sequence = np.concatenate(
            [prompts[r], first[slot : slot + 1], made[:-1, slot]])
        rows.append((sequence, np.concatenate(
            [np.asarray(logits)[r][None], step_logits[:, slot]]),
            np.concatenate([routed[:, r, : n[r]], chose[:, :, slot]], axis=1)))
    return rows, after_prefill, jax.tree.map(np.asarray, state)


@pytest.mark.parametrize("bucket, sizes", [
    (64, (37, 64, 9)),          # 192 rows: the dense expert pass
    (256, (200, 256, 131)),     # 768 rows: the grouped pass, stacks by layer
])
def test_prefill_then_paged_decode_is_the_reference_s_forward(
        c, params, bucket, sizes):
    from reference import solar_open2 as reference

    rng = np.random.default_rng(bucket)
    prompts = [rng.integers(0, c.vocab_size, size=n).astype(np.int32)
               for n in sizes]
    steps = 8
    rows, prefilled, state = serve(c, params, prompts, bucket, steps)
    for r, (sequence, got, chose) in enumerate(rows):
        size = len(prompts[r])
        want, audit, states = reference.forward(
            c, params, sequence, list(range(size - 1, size + steps)),
            forced=chose, states_after=(size, size + steps))
        assert got.shape == want.shape == (steps + 1, c.vocab_size)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3 * want.std())
        assert audit["shortfall"].shape == (len(c.blocks), size + steps)
        assert audit["shortfall"].max() < 1e-4 and audit["differs"].mean() < 0.01
        assert len(set(sequence[size:].tolist())) > 2
        slot = [0, 2, 3][r]
        assert states.shape == (2, 3) + state["delta"].shape[2:]
        np.testing.assert_allclose(
            prefilled["delta"][:, slot], states[0], rtol=2e-3, atol=2e-5)
        np.testing.assert_allclose(
            state["delta"][:, slot], states[1], rtol=2e-3, atol=2e-5)
    assert not state["delta"][:, 1].any() and not state["dconv"][:, 1].any()


def test_a_decode_chunk_through_the_kernels_is_the_xla_chunk(c, params):
    """The mixer under ``lax.cond`` (the first block has none): the stacked
    state goes through the conditional into the kernel and out, in place."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, c.vocab_size, size=n).astype(np.int32)
               for n in (20, 33, 7)]
    rows, before, after = serve(c, params, prompts, 64, 8)
    rows_k, _, after_k = serve(c, params, prompts, 64, 8,
                               kernel="pallas-interpret")
    for (seq, logits, _), (seq_k, logits_k, _) in zip(rows, rows_k):
        np.testing.assert_array_equal(seq_k, seq)
        np.testing.assert_allclose(logits_k, logits, rtol=2e-4, atol=2e-5)
    for name in ("delta", "dconv"):
        np.testing.assert_allclose(after_k[name], after[name],
                                   rtol=2e-4, atol=2e-5)
        assert not np.allclose(after_k[name][:, [0, 2, 3]],
                               before[name][:, [0, 2, 3]])
        np.testing.assert_array_equal(after_k[name][:, 1], before[name][:, 1])


@pytest.mark.parametrize("fault", [
    "beta_not_doubled", "decay_a_head", "k_not_normalised",
    "no_attention_gate", "gates_not_renormalised", "no_output_gate",
    "no_convolution", "no_shared_expert"])
def test_the_reference_with_a_term_changed_is_another_function(c, params, fault):
    from reference import solar_open2 as reference

    assert fault in reference.FAULTS
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, c.vocab_size, size=90).astype(np.int32)
    want, routing, _ = reference.forward(c, params, tokens, [60, 89])
    other, _, _ = reference.forward(
        c, params, tokens, [60, 89], faults=(fault,), forced=routing)
    rms = np.sqrt(np.mean((other - want) ** 2, -1)) / want.std(-1)
    assert rms.min() > 0.05, (fault, rms)


def test_the_length_of_q_is_divided_out_by_the_output_s_norm(c, params):
    """``o_t = S_t^T q_t`` is linear in ``q_t`` and the per-head RMSNorm
    after it divides its length out again: ``q`` left unnormalised changes
    the logits by the norm's epsilon alone, so no comparison of outputs can
    hold a program to that term of the published layer."""
    from reference import solar_open2 as reference

    assert "q_not_normalised" in reference.UNOBSERVABLE
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, c.vocab_size, size=90).astype(np.int32)
    want, routing, _ = reference.forward(c, params, tokens, [60, 89])
    other, _, _ = reference.forward(
        c, params, tokens, [60, 89], faults=("q_not_normalised",), forced=routing)
    rms = np.sqrt(np.mean((other - want) ** 2, -1)) / want.std(-1)
    assert rms.max() < 0.05


# -- the attention's output gate -------------------------------------------------


def test_the_gate_is_a_field_that_traces_nothing_where_it_is_off(c, params):
    off = dataclasses.replace(c, attn_gate=False)
    rng = np.random.default_rng(4)
    tokens = jnp.asarray(rng.integers(0, c.vocab_size, size=(1, 32)), jnp.int32)
    layout = PagedLayout(block_size=16, num_blocks=5, max_blocks_per_slot=4)

    def run(conf, p):
        pool_k, pool_v = init_hybrid_pool(conf, layout)
        fn = jax.jit(lambda p: hybrid_prefill_paged(
            conf, p, tokens, jnp.asarray([32]), pool_k, pool_v,
            init_hybrid_state(conf, 1), jnp.asarray([[1, 2, 3, 4]]),
            jnp.asarray([0])))
        return np.asarray(fn(p)[0]), fn.lower(p).as_text(debug_info=True)

    on, text_on = run(c, params)
    ungated, text_off = run(off, params)     # the same weights, wg unused
    assert "attn_gate" in text_on and "attn_gate" not in text_off
    assert np.abs(on - ungated).max() > 0.05 * on.std()
    # a gate of one (its weights at +inf in effect) is no gate: wg = 0 halves
    halves = dict(params, attn=dict(params["attn"],
                                    wg=jnp.zeros_like(params["attn"]["wg"])))
    doubled_wo = dict(params, attn=dict(params["attn"],
                                        wo=params["attn"]["wo"] * 0.5))
    np.testing.assert_allclose(run(c, halves)[0], run(off, doubled_wo)[0],
                               rtol=2e-4, atol=2e-5)


# -- the share against the model ---------------------------------------------


def test_the_two_shares_add_up_to_the_uncut_reference_layer(c):
    """Two chips of four experts each (``expert_first`` 0 and 4): what each
    share's routed experts give, plus the shared expert counted once, is the
    reference's layer over all eight experts."""
    from reference import solar_open2 as reference

    shares = [dataclasses.replace(c, expert_first=first)
              for first in range(0, c.experts, c.experts_held)]
    assert len(shares) == 2
    trees = [init_hybrid_params(s)["moe"] for s in shares]
    block = 2
    whole = {k: trees[0][k][block] for k in trees[0]}
    for k in ("w_up", "w_down"):        # the same eight experts, by global id
        whole[k] = jnp.concatenate([t[k][block] for t in trees])
        assert whole[k].shape[0] == c.experts
    rng = np.random.default_rng(13)
    h = jnp.asarray(rng.normal(size=(40, c.hidden)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.experts(h, whole, c, first=0, held=c.experts)
        shared_only, _ = reference.experts(h, whole, c, first=0, held=0)
    want, shared_only = np.asarray(want), np.asarray(shared_only)
    total = np.zeros_like(want)
    for share, tree in zip(shares, trees):
        out, load, chosen = moe_mixer(
            share, jax.tree.map(lambda a: a[block], tree), h,
            jnp.ones((40,), bool))
        part = np.asarray(out) - shared_only
        assert np.abs(part).max() > 0.05          # each share adds something
        total += part
        assert int(load.sum()) == int(
            ((chosen >= share.expert_first)
             & (chosen < share.expert_first + share.experts_held)).sum())
    assert np.abs(want - shared_only).max() > 0.1
    np.testing.assert_allclose(total + shared_only, want, rtol=2e-4, atol=2e-5)
