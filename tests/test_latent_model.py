"""The latent-attention family (models/latent.py: multi-head latent attention
over a pool of compressed rows, a dense layer, then group-limited routed
experts beside a shared one) at its tiny size on the CPU, against the plain
reference the benchmark keeps (bench/reference/deepseek_v2.py)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(ROOT, "bench"))

from reference import deepseek_v2 as ref  # noqa: E402

from langstream_tpu.models import latent  # noqa: E402
from langstream_tpu.models.hybrid import moe_mixer  # noqa: E402
from langstream_tpu.models.moe import (  # noqa: E402
    group_limited_softmax_routing,
)
from langstream_tpu.models.paged import PagedLayout, init_latent_pool  # noqa: E402
from langstream_tpu.ops.flash_attention import flash_attention  # noqa: E402
from langstream_tpu.ops.paged_attention import (  # noqa: E402
    latent_read,
    latent_read_xla,
    merge_partial_attention,
)

TINY = dataclasses.replace(latent.LatentConfig.tiny(), dtype=jnp.float32)
BS, PER_SLOT, SLOTS = 8, 16, 3


@pytest.fixture(scope="module")
def params():
    return latent.init_latent_params(TINY)


def _tables():
    return 1 + jnp.arange(SLOTS * PER_SLOT, dtype=jnp.int32).reshape(
        SLOTS, PER_SLOT)


def _pool(c=TINY):
    layout = PagedLayout(block_size=BS, num_blocks=SLOTS * PER_SLOT + 1,
                         max_blocks_per_slot=PER_SLOT)
    return init_latent_pool(c, layout)[0]


def _with_logits(logits, key):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits


def _serve(params, kernel, sizes=(37, 0, 21), chunks=(4, 4, 3), c=TINY):
    """Prefill each prompt alone, then decode chunks over all slots (slot 1
    idle): ``[(sequence, logits at positions size-1 ..)]`` a live slot."""
    rng = np.random.default_rng(5)
    pool, tables = _pool(c), _tables()
    first = np.zeros((SLOTS,), np.int32)
    prompts, logits0 = {}, {}
    for slot, size in enumerate(sizes):
        if not size:
            continue
        prompts[slot] = rng.integers(3, c.vocab_size, size=size, dtype=np.int32)
        padded = np.zeros((1, 64), np.int32)
        padded[0, :size] = prompts[slot]
        logits, pool, _ = latent.latent_prefill_paged(
            c, params, jnp.asarray(padded), jnp.asarray([size]), pool,
            tables[slot][None], use_flash=False)
        logits0[slot] = np.asarray(logits[0])
        first[slot] = logits0[slot].argmax()
    t0, n = jnp.asarray(first), jnp.asarray(sizes, jnp.int32)
    active = n > 0
    made, step_logits = [], []
    for k in chunks:
        out = latent.latent_decode_chunk_paged(
            c, params, t0, n, active, pool, tables, _with_logits,
            jax.random.PRNGKey(0), k, PER_SLOT, kernel=kernel)
        t0, n, pool = out[2:5]
        made.append(np.asarray(out[0]))
        step_logits.append(np.asarray(out[1]))
    made, step_logits = np.concatenate(made), np.concatenate(step_logits)
    return {
        slot: (np.concatenate([prompts[slot], first[slot:slot + 1],
                               made[:-1, slot]]),
               np.concatenate([logits0[slot][None], step_logits[:, slot]]))
        for slot in prompts
    }, np.asarray(pool)


@pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
def test_prefill_then_decode_chunks_through_the_pool_match_the_reference(
        params, kernel):
    served, _ = _serve(params, kernel)
    for slot, (sequence, got) in served.items():
        size = len(sequence) - 11
        want, _, _ = ref.forward(
            TINY, params, sequence, list(range(size - 1, size + 11)))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_an_idle_slot_writes_no_row_and_the_pool_holds_the_reference_rows(params):
    served, pool = _serve(params, "xla")
    tables = np.asarray(_tables())
    assert not pool[:, tables[1]].any()          # slot 1 ran nothing
    sequence, _ = served[0]
    _, _, rows = ref.forward(TINY, params, sequence, [0])
    width = TINY.kv_rank + TINY.rope_dim
    held = pool[0][tables[0]].reshape(PER_SLOT * BS, -1)
    np.testing.assert_allclose(
        held[: len(sequence) - 1, :width], rows[:-1], rtol=2e-4, atol=2e-5)
    assert not held[:, width:].any()             # the row's padding is zeros


def test_absorbed_decode_is_the_expanded_attention_on_one_cache(params):
    """One decode step (absorbed) of a sequence against the prefill
    (expanded) of the same sequence one token longer."""
    rng = np.random.default_rng(9)
    tokens = rng.integers(3, TINY.vocab_size, size=30, dtype=np.int32)
    pool, tables = _pool(), _tables()
    padded = np.zeros((1, 32), np.int32)
    padded[0, :29] = tokens[:29]
    _, pool, _ = latent.latent_prefill_paged(
        TINY, params, jnp.asarray(padded), jnp.asarray([29]), pool,
        tables[:1], use_flash=False)
    out = latent.latent_decode_chunk_paged(
        TINY, params, jnp.asarray(tokens[29:30]), jnp.asarray([29]),
        jnp.asarray([True]), pool, tables[:1], _with_logits,
        jax.random.PRNGKey(0), 1, PER_SLOT)
    padded[0, :30] = tokens
    expanded, _, _ = latent.latent_prefill_paged(
        TINY, params, jnp.asarray(padded), jnp.asarray([30]), _pool(),
        tables[:1], use_flash=False)
    np.testing.assert_allclose(out[1][0, 0], expanded[0], rtol=2e-4, atol=2e-4)


def test_prefill_by_head_groups_is_the_prefill_whole(params, monkeypatch):
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(3, 384, size=(1, 32)), jnp.int32)
    args = (TINY, params, tokens, jnp.asarray([32]), _pool(), _tables()[:1])
    whole, _, _ = latent.latent_prefill_paged(*args, use_flash=False)
    monkeypatch.setattr(latent, "EXPAND_ROWS_X_HEADS", 32)   # one head a group
    grouped, pool, _ = latent.latent_prefill_paged(
        TINY, params, tokens, jnp.asarray([32]), _pool(), _tables()[:1],
        use_flash=False)
    np.testing.assert_allclose(grouped, whole, rtol=1e-5, atol=1e-5)


def test_yarn_frequencies_and_scale_at_the_published_numbers():
    c = latent.LatentConfig.deepseek_v2_ep8()
    np.testing.assert_allclose(latent.yarn_inv_freq(c), ref.inv_freq(64),
                               rtol=1e-6)
    inv = latent.yarn_inv_freq(c)
    theta = 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], 1 / theta[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], 1 / (40 * theta[23:]), rtol=1e-6)
    assert abs(c.attn_scale - 0.11472) < 1e-5
    assert c.row_width == 640 and c.row_pad == 64


# -- the router ------------------------------------------------------------


def _reference_route(u, router, c, faults=()):
    with jax.default_matmul_precision("highest"):
        return ref.route(ref.f32(u), {"router": ref.f32(router)}, c, faults)


@pytest.mark.parametrize("case", ["random", "tied_groups", "tied_experts"])
def test_group_limited_routing_matches_the_reference_router(case):
    c = dataclasses.replace(
        TINY, experts=16, n_group=4, topk_group=2, experts_per_token=3,
        experts_held=4)
    rng = np.random.default_rng(11)
    u = jnp.asarray(rng.normal(size=(64, c.hidden)), jnp.float32)
    router = rng.normal(size=(c.hidden, c.experts)).astype(np.float32)
    if case == "tied_groups":
        # groups 1 and 2 are copies: their maxima tie on every row, and the
        # lower group wins
        router[:, 8:12] = router[:, 4:8]
    if case == "tied_experts":
        router[:, 1] = router[:, 0]
    router = jnp.asarray(router)
    experts, weights = group_limited_softmax_routing(
        u, router, c.experts_per_token, c.n_group, c.topk_group,
        c.routed_scale)
    own, own_weights = _reference_route(u, router, c)
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(own))
    np.testing.assert_allclose(weights, own_weights, rtol=1e-5)
    groups = np.asarray(experts) // (c.experts // c.n_group)
    assert all(len(set(row)) <= c.topk_group for row in groups)
    # not renormalised: 16 sigma_e, which does not sum to 16
    assert not np.allclose(np.asarray(weights).sum(-1), c.routed_scale)


def test_a_bfloat16_router_is_a_control_that_moves_choices():
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(512, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 16)) / 8, jnp.float32)
    full, _ = group_limited_softmax_routing(u, router, 3, 4, 2, 16.0)
    low, _ = group_limited_softmax_routing(
        u, router, 3, 4, 2, 16.0, dtype=jnp.bfloat16)
    assert (np.sort(np.asarray(full), -1) != np.sort(np.asarray(low), -1)).any()


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer():
    """Every chip of the deployment computes its held experts' part; the
    parts of all shares plus what every chip computes alike (the shared
    expert), counted once, equal the reference's layer over all experts."""
    c = TINY
    shares = c.experts // c.experts_held                # 2 at the tiny size
    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.normal(size=(24, c.hidden)), jnp.float32)
    valid = jnp.ones((24,), bool)
    parts, whole_w = [], None
    for rank in range(shares):
        cs = dataclasses.replace(c, expert_first=rank * c.experts_held)
        moe = jax.tree.map(
            lambda t: t[0], latent.init_latent_params(cs)["sparse"]["moe"])
        out, load, _ = moe_mixer(cs, moe, u, valid)
        shared = latent.silu_gated(u @ moe["ws_up"]) @ moe["ws_down"]
        parts.append(np.asarray(out - shared))
        assert int(load.sum()) <= 24 * c.experts_per_token
        if whole_w is None:
            whole_w = {k: np.asarray(v) for k, v in moe.items()}
        else:       # the shares are slices of the same experts
            for k in ("w_up", "w_down"):
                whole_w[k] = np.concatenate([whole_w[k], np.asarray(moe[k])])
            for k in ("router", "ws_up", "ws_down"):
                np.testing.assert_array_equal(whole_w[k], np.asarray(moe[k]))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(
            u, {k: ref.f32(v) for k, v in whole_w.items()}, c, first=0,
            held=c.experts)
    np.testing.assert_allclose(
        sum(parts) + np.asarray(shared), want, rtol=2e-4, atol=2e-4)


# -- the kernels -----------------------------------------------------------


@pytest.mark.parametrize("lengths", [
    (1, 0, 5), (8, 16, 0), (64, 9, 17), (127, 128, 1),
], ids=["one-row", "block-edges", "several-tiles", "whole-slot"])
def test_latent_read_interpreted_against_the_xla_expression(
        lengths, monkeypatch):
    from langstream_tpu.ops import paged_attention

    # four blocks a tile: a slot of 16 blocks is up to four tiles
    monkeypatch.setattr(paged_attention, "LATENT_TILE_BLOCKS", 4)
    H, W, Dv, L = 4, 128, 16, 3
    rng = np.random.default_rng(sum(lengths))
    pool = jnp.asarray(rng.normal(size=(L, SLOTS * PER_SLOT + 1, BS, W)),
                       jnp.float32)
    q = jnp.asarray(rng.normal(size=(SLOTS, H, W)), jnp.float32)
    kw = dict(num_read_blocks=PER_SLOT, value_dim=Dv, scale=0.3)
    n = jnp.asarray(lengths, jnp.int32)
    for layer in (0, 2):
        got = latent_read(q, pool, layer, _tables(), n, interpret=True, **kw)
        want = latent_read_xla(q, pool, layer, _tables(), n, **kw)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
        out = merge_partial_attention([got])
        for slot, rows in enumerate(lengths):
            if not rows:      # an idle slot: no copy, no row, zeros out
                assert not np.asarray(out[slot]).any()


@pytest.mark.parametrize("dims", [(24, 16), (16, 16), (48, 32)],
                         ids=["k24-v16", "k16-v16", "k48-v32"])
def test_flash_with_a_value_width_other_than_the_key_width(dims):
    D, Dv = dims
    rng = np.random.default_rng(D + Dv)
    q = jnp.asarray(rng.normal(size=(2, 40, 4, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 40, 2, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 40, 2, Dv)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, scale=0.2, block_q=16,
                          block_k=16, interpret=True)
    kk, vv = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * 0.2
    s = jnp.where(jnp.tril(jnp.ones((40, 40), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)
    assert got.shape == (2, 40, 4, Dv)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("lengths", [(40, 40), (1, 17), (16, 33), (0, 25)],
                         ids=["full", "one-row", "block-edges", "empty-row"])
def test_flash_told_the_lengths_skips_the_padding_and_nothing_else(lengths):
    rng = np.random.default_rng(sum(lengths))
    q = jnp.asarray(rng.normal(size=(2, 40, 4, 24)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 40, 2, 24)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 40, 2, 16)), jnp.float32)
    kw = dict(causal=True, scale=0.2, block_q=16, block_k=16, interpret=True)
    plain = np.asarray(flash_attention(q, k, v, **kw))
    told = np.asarray(flash_attention(
        q, k, v, lengths=jnp.asarray(lengths, jnp.int32), **kw))
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(told[row, :n], plain[row, :n],
                                   rtol=1e-5, atol=1e-6)
        # a block of padding alone is zeros, never what memory held
        first_dead = -(-n // 16) * 16
        assert not told[row, first_dead:].any()
        assert np.isfinite(told[row]).all()
    with pytest.raises(ValueError, match="lengths"):
        flash_attention(q, k, v, causal=False, lengths=jnp.asarray(lengths))


def test_prefill_through_the_interpreted_flash_kernel(params, monkeypatch):
    monkeypatch.setenv("LS_TPU_FLASH", "interpret")
    tokens = jnp.asarray(
        np.random.default_rng(8).integers(3, 384, size=(1, 32)), jnp.int32)
    flash, _, _ = latent.latent_prefill_paged(
        TINY, params, tokens, jnp.asarray([27]), _pool(), _tables()[:1])
    plain, _, _ = latent.latent_prefill_paged(
        TINY, params, tokens, jnp.asarray([27]), _pool(), _tables()[:1],
        use_flash=False)
    np.testing.assert_allclose(flash, plain, rtol=1e-4, atol=1e-4)


# -- the reference's faults ------------------------------------------------


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_fault_of_the_reference_moves_what_the_check_reads(params, fault):
    rng = np.random.default_rng(6)
    tokens = rng.integers(3, 384, size=48, dtype=np.int32)
    positions = list(range(40, 48))
    clean, routing, rows = ref.forward(TINY, params, tokens, positions)
    faulty, routing_f, rows_f = ref.forward(
        TINY, params, tokens, positions, faults=(fault,))
    moved = (np.abs(clean - faulty).max() > 1e-3
             or (routing != routing_f).any()
             or np.abs(rows - rows_f).max() > 1e-3)
    assert moved, fault
