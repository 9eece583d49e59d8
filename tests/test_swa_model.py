"""The window-and-full family (``models/swa.py``) at the ``trinity-tiny``
preset on the CPU, in float32: prefill and then decode through BOTH pools
against the reference's full forward (``bench/reference/afmoe.py``) with
prompts shorter than, equal to and three times the window and decode across
two turns of the window layers' ring; the Pallas read (interpreter) and the
XLA read against each other and against the plain product with a first row;
flash with a window against the masked product; the share test (the parts the
two halves of the experts give, the shared expert counted once, add up to the
uncut reference's layer); and the block manager with two kinds."""

import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models.llama_paged import _cache_partial_xla
from langstream_tpu.models.paged import (
    BlockManager,
    PagedLayout,
    init_kv_pool,
)
from langstream_tpu.models.swa import (
    SwaConfig,
    init_swa_params,
    swa_decode_chunk_paged,
    swa_prefill_paged,
)
from langstream_tpu.ops.flash_attention import flash_attention
from langstream_tpu.ops.paged_attention import (
    merge_partial_attention,
    paged_attention_partial,
)

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench"))
from reference import afmoe as reference  # noqa: E402

BS = 8          # rows of a block: the window of 32 is four of them
SLOTS = 4
MAX_LEN = 256


def tiny(**kw):
    return dataclasses.replace(
        SwaConfig.tiny(max_seq_len=MAX_LEN), dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def model():
    c = tiny()
    return c, init_swa_params(c, jax.random.PRNGKey(3))


def greedy_with_logits(logits, key):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits


PREFILL = jax.jit(
    lambda c, p, t, n, pk, pv, wp, tb: swa_prefill_paged(
        c, p, t, n, pk, pv, wp, tb, use_flash=False), static_argnums=0)
DECODE = jax.jit(
    lambda c, p, t0, n, active, pk, pv, wp, tb, key, k, kernel:
    swa_decode_chunk_paged(
        c, p, t0, n, active, pk, pv, wp, tb, greedy_with_logits, key, k,
        MAX_LEN // BS, kernel=kernel), static_argnums=(0, 10, 11))


def pools(c, slots=SLOTS):
    layout = PagedLayout(BS, slots * (MAX_LEN // BS) + 1, MAX_LEN // BS)
    ring = c.ring_blocks(BS)
    window_layout = PagedLayout(BS, slots * ring + 1, MAX_LEN // BS)
    manager = BlockManager(layout, slots, window_layout=window_layout,
                           window_ring=ring)
    pk, pv = init_kv_pool(c, layout, c.full_layers)
    wk, wv = init_kv_pool(c, window_layout, c.window_layers)
    return manager, pk, pv, {"k": wk, "v": wv}


def test_the_tiny_preset_is_the_published_grammar():
    c, real = SwaConfig.tiny(), SwaConfig.trinity_large_preview_ep8()
    assert c.layer_kinds == real.layer_kinds == "WWFWW"
    assert (c.dense_layers, c.sparse_layers) == (1, 4)
    assert c.ring_blocks(BS) == 5 and real.ring_blocks(64) == 65
    assert real.kind_index == (0, 1, 0, 2, 3)
    assert (real.hidden, real.heads, real.kv_heads, real.head_dim,
            real.intermediate, real.moe_intermediate, real.experts,
            real.experts_per_token, real.window, real.routed_scale) == (
        3072, 48, 8, 128, 12288, 3072, 256, 4, 4096, 2.448)
    assert (real.layers, real.experts_held, real.vocab_size) == (5, 32, 25024)
    with pytest.raises(ValueError):
        dataclasses.replace(c, layer_kinds="WWWWW")
    with pytest.raises(ValueError):
        dataclasses.replace(c, layer_kinds="WWF")


# prompts shorter than, equal to and three times the window (32), and one
# that ends in the middle of a block; 88 decode steps are more than two turns
# of a ring of 40 rows
@pytest.mark.parametrize("kernel, prompts, steps, chunk", [
    ("xla", (20, 32, 96), 88, 8),
    ("pallas-interpret", (20, 32, 96), 88, 8),
    ("xla", (45, 7, 64), 24, 12),
])
def test_prefill_then_decode_through_both_pools_match_the_reference(
        model, kernel, prompts, steps, chunk):
    c, params = model
    manager, pk, pv, wpool = pools(c)
    rng = np.random.default_rng(7)
    tokens = [rng.integers(0, c.vocab_size, size=n, dtype=np.int32)
              for n in prompts]
    for slot, n in enumerate(prompts):
        manager.admit(slot, n + steps + 1)
        manager.ensure_capacity(slot, n + steps + 1)
        assert len(manager._slot_ring[slot]) <= c.ring_blocks(BS)
    first = np.zeros((SLOTS,), np.int32)
    first_logits = {}
    for slot, row in enumerate(tokens):          # one prompt a program
        bucket = 32
        while bucket < row.size:
            bucket *= 2
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : row.size] = row
        logits, pk, pv, wpool, _ = PREFILL(
            c, params, jnp.asarray(padded), jnp.asarray([row.size]), pk, pv,
            wpool, jnp.asarray(manager.tables[slot][None]))
        first_logits[slot] = np.asarray(logits)[0]
        first[slot] = int(np.argmax(first_logits[slot]))
    lengths = np.array(list(prompts) + [0] * (SLOTS - len(prompts)), np.int32)
    active = jnp.asarray(lengths > 0)

    t0, n = jnp.asarray(first), jnp.asarray(lengths)
    made, logits_made = [], []
    for _ in range(steps // chunk):
        out = DECODE(
            c, params, t0, n, active, pk, pv, wpool,
            jnp.asarray(manager.tables), jax.random.PRNGKey(0), chunk, kernel)
        t0, n, pk, pv, wpool = out[2:7]
        made.append(np.asarray(out[0]))
        logits_made.append(np.asarray(out[1]))
    made, logits_made = np.concatenate(made), np.concatenate(logits_made)
    assert np.asarray(n)[: len(prompts)].tolist() == [p + steps for p in prompts]
    for slot, row in enumerate(tokens):
        sequence = np.concatenate([row, first[slot:slot + 1], made[:-1, slot]])
        positions = list(range(row.size - 1, row.size + steps))
        want, _, rows = reference.forward(c, params, sequence, positions)
        got = np.concatenate([first_logits[slot][None], logits_made[:, slot]])
        assert np.abs(got - want).max() < 2e-3 * want.std(), (slot, kernel)
        # what the ring holds of the first window layer is the reference's
        # rows of the positions not yet overwritten
        ring_rows = c.ring_blocks(BS) * BS
        end = row.size + steps
        held = np.arange(max(0, row.size - c.window, end - ring_rows), end)
        blocks = manager.window_tables[slot, held // BS]
        k_got = np.asarray(wpool["k"])[0, blocks, held % BS]
        v_got = np.asarray(wpool["v"])[0, blocks, held % BS]
        np.testing.assert_allclose(
            np.concatenate([k_got, v_got], -1), rows[held], atol=2e-4)


def test_a_prefill_writes_a_window_layer_s_last_rows_only(model):
    c, params = model
    manager, pk, pv, wpool = pools(c)
    n = 100                                   # three windows and a bit
    manager.admit(0, n + 1)
    manager.ensure_capacity(0, n + 1)
    row = np.zeros((1, 128), np.int32)
    row[0, :n] = np.arange(5, 5 + n)
    _, pk, pv, wpool, _ = PREFILL(
        c, params, jnp.asarray(row), jnp.asarray([n]), pk, pv, wpool,
        jnp.asarray(manager.tables[0][None]))
    # (block 0 is each pool's scratch: the padding's rows land there)
    written = np.abs(np.asarray(wpool["k"]))[:, 1:].sum(-1) > 0
    # 32 rows a window layer, in the slot's ring blocks and nowhere else
    assert written.sum(axis=(1, 2)).tolist() == [c.window] * c.window_layers
    ring = manager._slot_ring[0]
    assert len(ring) == 5 and not written[:, [b - 1 for b in range(
        1, written.shape[1] + 1) if b not in ring]].any()
    # the full layer holds every row
    assert (np.abs(np.asarray(pk))[:, 1:].sum(-1) > 0).sum() == n


@pytest.mark.parametrize("G", [1, 2, 3])
def test_the_pallas_read_and_the_xla_read_agree_with_a_first_row(G):
    Kh, D, bs, nb, width, B = 2, 16, 8, 30, 12, 4
    H = Kh * G
    rng = np.random.default_rng(G)
    pool_k = jnp.asarray(rng.normal(size=(3, nb, bs, Kh * D)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(3, nb, bs, Kh * D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, nb))[: B * 5].reshape(
        B, 5)[:, np.arange(width) % 5], jnp.int32)    # a ring of five blocks
    lengths = jnp.asarray([37, 0, 90, 8], jnp.int32)
    firsts = jnp.asarray([5, 0, 90 - 31, 0], jnp.int32)
    c = SwaConfig.tiny()
    c = dataclasses.replace(c, heads=H, kv_heads=Kh, head_dim=D)
    for layer in (0, 2):
        got = paged_attention_partial(
            q, pool_k, pool_v, layer, tables, lengths, num_read_blocks=width,
            kv_heads=Kh, head_dim=D, interpret=True, firsts=firsts)
        xla = _cache_partial_xla(
            c, q, pool_k, pool_v, layer, tables, lengths, 5, firsts=firsts)
        out_p = np.asarray(merge_partial_attention([got]))
        out_x = np.asarray(merge_partial_attention([xla]))
        for b in range(B):
            rows = np.arange(int(firsts[b]), int(lengths[b]))
            if not rows.size:
                assert float(np.abs(np.asarray(got[2][b])).max()) == 0.0
                continue
            kk = np.asarray(pool_k)[layer, np.asarray(tables)[b, rows // bs],
                                    rows % bs].reshape(-1, Kh, D)
            vv = np.asarray(pool_v)[layer, np.asarray(tables)[b, rows // bs],
                                    rows % bs].reshape(-1, Kh, D)
            qq = np.asarray(q)[b].reshape(Kh, G, D)
            s = np.einsum("kgd,tkd->kgt", qq, kk) / np.sqrt(D)
            p = np.exp(s - s.max(-1, keepdims=True))
            want = np.einsum("kgt,tkd->kgd", p / p.sum(-1, keepdims=True), vv)
            np.testing.assert_allclose(out_p[b], want.reshape(H, D), atol=2e-5)
            np.testing.assert_allclose(out_x[b], want.reshape(H, D), atol=2e-5)
    # without a first row the read is what it was
    plain = paged_attention_partial(
        q, pool_k, pool_v, 1, tables, lengths, num_read_blocks=width,
        kv_heads=Kh, head_dim=D, interpret=True)
    zero = paged_attention_partial(
        q, pool_k, pool_v, 1, tables, lengths, num_read_blocks=width,
        kv_heads=Kh, head_dim=D, interpret=True,
        firsts=jnp.zeros((B,), jnp.int32))
    for a, b in zip(plain, zero):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("window, block", [(32, 16), (40, 16), (16, 32), (100, 16)])
def test_flash_with_a_window_is_the_masked_product(window, block):
    B, S, H, Kh, D = 2, 96, 4, 2, 16
    rng = np.random.default_rng(window)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Kh, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Kh, D)), jnp.float32)
    lengths = jnp.asarray([96, 53], jnp.int32)
    got = np.asarray(flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block, interpret=True,
        lengths=lengths, window=window))
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    mask = (i - j >= 0) & (i - j < window)
    s = np.einsum("bqkgd,bskd->bkgqs", np.asarray(q).reshape(
        B, S, Kh, H // Kh, D), np.asarray(k)) / np.sqrt(D)
    s = np.where(mask[None, None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bkgqs,bskd->bqkgd", p / p.sum(-1, keepdims=True),
                     np.asarray(v)).reshape(B, S, H, D)
    for b, n in enumerate(np.asarray(lengths)):
        np.testing.assert_allclose(got[b, :n], want[b, :n], atol=2e-5)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, causal=True, interpret=True, window=window)


def test_the_halves_of_the_experts_add_up_to_the_uncut_layer(model):
    """Section 4's share test: what each half of the experts gives for the
    tokens routed to it, with the shared expert counted once, is what the
    uncut reference gives for the whole layer."""
    c, _ = model
    whole = dataclasses.replace(c, experts_held=c.experts, expert_first=0)
    params = init_swa_params(whole, jax.random.PRNGKey(5))
    halves = [dataclasses.replace(c, experts_held=4, expert_first=f)
              for f in (0, 4)]
    for half in halves:     # a share's experts are slices of the same experts
        lp = init_swa_params(half, jax.random.PRNGKey(5))["layers"][2]["moe"]
        np.testing.assert_array_equal(
            np.asarray(lp["w_up"]), np.asarray(
                params["layers"][2]["moe"]["w_up"])[half.expert_first:][:4])
    w = {k: reference.f32(v) for k, v in params["layers"][2]["moe"].items()}
    u = jnp.asarray(np.random.default_rng(1).normal(size=(50, c.hidden)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, chosen = reference.experts(u, w, whole)
        parts = []
        for i, half in enumerate(halves):
            share = dict(w, w_up=w["w_up"][half.expert_first:][:4],
                         w_down=w["w_down"][half.expert_first:][:4])
            part, _ = reference.experts(u, share, half, shared=(i == 0))
            parts.append(part)
    assert set(np.asarray(chosen).ravel()) - set(range(4)) and \
        set(np.asarray(chosen).ravel()) & set(range(4))     # both halves used
    np.testing.assert_allclose(
        np.asarray(parts[0] + parts[1]), np.asarray(uncut), atol=1e-5)
    # and the program's layer is the reference's for a share
    from langstream_tpu.models.hybrid import moe_mixer

    half = halves[1]
    lp = init_swa_params(half, jax.random.PRNGKey(5))["layers"][2]["moe"]
    got, load, _ = moe_mixer(half, lp, u, jnp.ones((50,), bool))
    want, _ = reference.experts(
        u, {k: reference.f32(v) for k, v in lp.items()}, half)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert int(load.sum()) == int(((np.asarray(chosen) >= 4)).sum())


# -- the block manager with two kinds --------------------------------------


def manager(slots=3, blocks=40, window_blocks=None, ring=5, width=32):
    layout = PagedLayout(BS, blocks, width)
    window_layout = PagedLayout(
        BS, slots * ring + 1 if window_blocks is None else window_blocks, width)
    return BlockManager(layout, slots, window_layout=window_layout,
                        window_ring=ring)


@pytest.mark.parametrize("tokens", [1, 8, 39, 40, 41, 200, 256])
def test_a_slot_s_window_footprint_never_passes_the_ring(tokens):
    m = manager()
    m.admit(0, tokens)
    for grown in range(1, tokens + 1, 7):
        m.ensure_capacity(0, grown)
        assert len(m._slot_ring[0]) == min(-(-grown // BS), 5)
    m.ensure_capacity(0, tokens)
    stats = m.stats()
    assert stats["window_live_blocks"] == min(-(-tokens // BS), 5)
    assert stats["window_slot_blocks_max"] <= stats["window_ring_blocks"] == 5
    assert stats["full_live_blocks"] == -(-tokens // BS)
    assert stats["live_blocks"] == (stats["full_live_blocks"]
                                    + stats["window_live_blocks"])
    # logical block n lives in ring block n % 5, in both halves' width
    half = m.tables.shape[1] // 2
    need = -(-tokens // BS)
    ring = m._slot_ring[0]
    for n in range(need):
        assert m.window_tables[0, n] == ring[n % 5] != 0
    assert m.tables[0, :need].tolist() == m._slot_blocks[0]
    assert half == m.layout.max_blocks_per_slot
    assert (m.tables[0, half:] == m.window_tables[0]).all()


def test_release_and_preemption_return_both_kinds():
    m = manager(blocks=60)
    free = m.stats()["free_blocks"]
    for slot, tokens in enumerate((100, 30, 256)):
        m.admit(slot, tokens)
        m.ensure_capacity(slot, tokens)
    held = m.stats()
    assert held["window_live_blocks"] == 5 + 4 + 5
    assert held["reserved_blocks"] == (13 + 4 + 32) + (5 + 4 + 5)
    m.release(1)                   # a preemption is a release
    after = m.stats()
    assert after["window_live_blocks"] == 10
    assert after["window_blocks_released"] == 4
    assert (m.tables[1] == 0).all()
    m.release(0)
    m.release(2)
    done = m.stats()
    assert done["free_blocks"] == free and done["reserved_blocks"] == 0
    assert done["live_blocks"] == 0 and done["window_blocks_released"] == 14
    # the freed ring blocks are taken again
    m.admit(0, 256)
    m.ensure_capacity(0, 256)
    assert m.stats()["window_live_blocks"] == 5


def test_the_reservation_refuses_what_does_not_fit_in_either_kind():
    # the full kind refuses: 39 usable blocks, two slots of 20
    m = manager(blocks=40)
    m.admit(0, 160)
    assert not m.can_admit(160) and m.can_admit(152)
    # the window kind refuses: room for one ring and a half
    m = manager(blocks=400, window_blocks=8)
    assert m.can_admit(256)
    m.admit(0, 256)
    assert m.stats()["window_reserved_blocks"] == 5
    assert not m.can_admit(24)            # three blocks, two left
    assert m.can_admit(16)
    with pytest.raises(RuntimeError):
        m.admit(1, 24)
    # the pressure admissions face is the fuller kind's
    assert m.used_ratio() == pytest.approx(5 / 7)
    # a budget reduction withholds the same share of both kinds, never
    # under one ring
    m = manager(slots=4, blocks=101, ring=5)
    assert m.window_usable_blocks == 20
    assert m.reduce_budget(50) == 50
    assert m.window_usable_blocks == 10
    m.reduce_budget(10 ** 6)       # to the full kind's floor: one slot's 32
    assert m.budget_reduction == 68 and m.window_usable_blocks == 6
    assert manager(slots=1, blocks=101, ring=5).window_usable_blocks == 5
    m.restore_budget()
    assert m.window_usable_blocks == 20
    with pytest.raises(ValueError):
        manager(window_blocks=5, ring=5)


def test_one_kind_is_what_it_was():
    m = BlockManager(PagedLayout(BS, 40, 32), 2)
    assert m.tables.shape == (2, 32) and m.window_ring == 0
    m.admit(0, 100)
    m.ensure_capacity(0, 100)
    assert "window_live_blocks" not in m.stats()
    assert m.stats()["live_blocks"] == 13 and m.window_blocks_needed(100) == 0


# ---------------------------------------------------------------------------
# the family's second member: no gate, no post norm, no dense layer, no
# shared expert, a rotation a layer kind (bench/reference/mellum.py)
# ---------------------------------------------------------------------------

from langstream_tpu.models.llama import yarn_inv_freq  # noqa: E402
from langstream_tpu.models.swa import Rope  # noqa: E402
from reference import mellum  # noqa: E402

#: the tiny member's rotations, as its bench fixture states them
TINY_ROPE = {
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0},
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 10000.0, "factor": 8.0,
        "original_max_position_embeddings": 64, "beta_fast": 8.0,
        "beta_slow": 1.0, "attention_factor": 1.2079441541679836},
}


@pytest.fixture(scope="module")
def mellum_model():
    c = dataclasses.replace(
        SwaConfig.mellum_tiny(max_seq_len=MAX_LEN), dtype=jnp.float32)
    return c, init_swa_params(c, jax.random.PRNGKey(5))


def test_the_mellum_presets_are_the_published_grammar():
    c, real = SwaConfig.mellum_tiny(), SwaConfig.mellum2_12b_a2_5b_8l()
    assert c.layer_kinds == real.layer_kinds == "WWWFWWWF"
    assert (real.dense_layers, real.sparse_layers, real.layers) == (0, 8, 8)
    assert real.kind_index == (0, 1, 2, 0, 3, 4, 5, 1)
    assert real.ring_blocks(64) == 17 and c.ring_blocks(BS) == 5
    assert (real.hidden, real.heads, real.kv_heads, real.head_dim,
            real.moe_intermediate, real.experts, real.experts_held,
            real.experts_per_token, real.window, real.vocab_size,
            real.norm_eps, real.router, real.shared_intermediate) == (
        2304, 32, 4, 128, 896, 64, 64, 8, 1024, 98304, 1e-6, "softmax", 0)
    assert real.rope_theta == real.full_rope.theta == 500000.0
    assert not (real.output_gate or real.post_norms or real.embed_scaled)
    assert real.qk_norm and c.qk_norm
    assert (c.experts, c.experts_held, c.experts_per_token) == (8, 8, 2)
    # the other member says what it has too, and is what it was
    trinity = SwaConfig.trinity_large_preview_ep8()
    assert trinity.window_rope == Rope(theta=10000.0) and \
        trinity.full_rope is None and trinity.rope_theta == 10000.0
    assert trinity.output_gate and trinity.post_norms and trinity.embed_scaled


def test_yarn_s_table_is_the_closed_form_at_the_published_numbers():
    real = SwaConfig.mellum2_12b_a2_5b_8l().full_rope
    table = yarn_inv_freq(128, real.theta, real.factor, real.original_max,
                          real.beta_fast, real.beta_slow)
    i = np.arange(64)
    f = 500000.0 ** (-i / 64)
    r = np.clip((i - 18) / 17, 0, 1)            # low 18, high 35
    np.testing.assert_allclose(table, f * (1 - r) + f / 16 * r, rtol=1e-6)
    np.testing.assert_allclose(table[:19], f[:19], rtol=1e-6)        # kept
    np.testing.assert_allclose(table[35:], f[35:] / 16, rtol=1e-6)   # divided
    assert np.all(table[19:35] < f[19:35]) and np.all(
        table[19:35] > f[19:35] / 16)                                # the ramp
    assert abs(real.attention_factor - (0.1 * np.log(16) + 1)) < 1e-12
    assert round(real.attention_factor, 5) == 1.27726
    # the reference's own table, from the published section alone
    np.testing.assert_allclose(
        mellum.inv_freq(64, mellum.ROPE["full_attention"]), table, rtol=1e-6)
    np.testing.assert_allclose(
        mellum.inv_freq(64, mellum.ROPE["sliding_attention"]), f, rtol=1e-12)
    # cos and sin carry the factor; a plain rotation none
    cos, sin = real.cos_sin(jnp.arange(3), 128)
    np.testing.assert_allclose(np.asarray(cos[0]), 1.2772588722239782, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(cos ** 2 + sin ** 2), 1.2772588722239782 ** 2, rtol=1e-5)


def test_the_tiny_ramp_has_its_three_regions():
    rope = SwaConfig.mellum_tiny().full_rope
    table = yarn_inv_freq(16, rope.theta, rope.factor, rope.original_max,
                          rope.beta_fast, rope.beta_slow)
    f = 10000.0 ** (-np.arange(8) / 8)
    ratio = table / f
    assert ratio[0] == pytest.approx(1.0)                 # kept
    assert 1 / 8 < ratio[2] < ratio[1] < 1.0              # on the ramp
    np.testing.assert_allclose(ratio[3:], 1 / 8, rtol=1e-6)   # divided
    np.testing.assert_allclose(
        mellum.inv_freq(8, TINY_ROPE["full_attention"]), table, rtol=1e-6)
    assert rope.attention_factor == pytest.approx(0.1 * np.log(8) + 1)


def test_a_member_holds_no_leaf_and_traces_no_op_it_lacks(mellum_model):
    c, params = mellum_model
    assert sorted(params["layers"][0]) == ["attn", "moe"]
    for lp in params["layers"]:
        assert sorted(lp["attn"]) == ["k_norm", "norm", "q_norm", "wk", "wo",
                                      "wq", "wv"]
        assert sorted(lp["moe"]) == ["norm", "router", "w_down", "w_up"]
    bare = init_swa_params(dataclasses.replace(c, qk_norm=False))
    assert "q_norm" not in bare["layers"][0]["attn"]
    # the embedding enters the first layer as it is, at a spread of 1
    assert 0.9 < float(jnp.std(params["embed"])) < 1.1
    manager, pk, pv, wpool = pools(c)
    tables = jnp.asarray(manager.tables)
    decode = DECODE.lower(
        c, params, jnp.zeros(SLOTS, jnp.int32), jnp.ones(SLOTS, jnp.int32),
        jnp.ones(SLOTS, bool), pk, pv, wpool, tables, jax.random.PRNGKey(0),
        4, "xla").as_text(debug_info=True)
    prefill = PREFILL.lower(
        c, params, jnp.zeros((1, 64), jnp.int32), jnp.full((1,), 50),
        pk, pv, wpool, tables[:1]).as_text(debug_info=True)
    for text in (decode, prefill):
        for scope in ("attn_gate", "post_norm", "moe_shared", "ffn"):
            assert not re.search(rf'[/"]{scope}/', text), scope
        for scope in ("rope", "rope_full", "qk_norm", "moe_router",
                      "moe_experts", "moe_combine"):
            assert re.search(rf'[/"]{scope}/', text), scope


# prompts shorter than the window (32) that stay there, one that passes it
# and the ring's wrap (40 rows) while it decodes, one three times it
@pytest.mark.parametrize("kernel, prompts, steps, chunk", [
    ("xla", (4, 28, 96), 24, 8),
    ("pallas-interpret", (4, 28, 96), 16, 8),
    ("xla", (45, 7, 64), 48, 12),
])
def test_the_second_member_s_prefill_then_decode_match_its_reference(
        mellum_model, kernel, prompts, steps, chunk):
    c, params = mellum_model
    manager, pk, pv, wpool = pools(c)
    rng = np.random.default_rng(11)
    tokens = [rng.integers(0, c.vocab_size, size=n, dtype=np.int32)
              for n in prompts]
    first = np.zeros((SLOTS,), np.int32)
    first_logits = {}
    for slot, row in enumerate(tokens):
        manager.admit(slot, row.size + steps + 1)
        manager.ensure_capacity(slot, row.size + steps + 1)
        bucket = 32
        while bucket < row.size:
            bucket *= 2
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : row.size] = row
        logits, pk, pv, wpool, _ = PREFILL(
            c, params, jnp.asarray(padded), jnp.asarray([row.size]), pk, pv,
            wpool, jnp.asarray(manager.tables[slot][None]))
        first_logits[slot] = np.asarray(logits)[0]
        first[slot] = int(np.argmax(first_logits[slot]))
    lengths = np.array(list(prompts) + [0] * (SLOTS - len(prompts)), np.int32)
    t0, n, active = jnp.asarray(first), jnp.asarray(lengths), jnp.asarray(lengths > 0)
    made, logits_made = [], []
    for _ in range(steps // chunk):
        out = DECODE(
            c, params, t0, n, active, pk, pv, wpool,
            jnp.asarray(manager.tables), jax.random.PRNGKey(0), chunk, kernel)
        t0, n, pk, pv, wpool = out[2:7]
        made.append(np.asarray(out[0]))
        logits_made.append(np.asarray(out[1]))
    made, logits_made = np.concatenate(made), np.concatenate(logits_made)
    for slot, row in enumerate(tokens):
        sequence = np.concatenate([row, first[slot:slot + 1], made[:-1, slot]])
        positions = list(range(row.size - 1, row.size + steps))
        want, _, rows = mellum.forward(c, params, sequence, positions,
                                       rope=TINY_ROPE)
        got = np.concatenate([first_logits[slot][None], logits_made[:, slot]])
        assert np.abs(got - want).max() < 2e-3 * want.std(), (slot, kernel)
        ring_rows = c.ring_blocks(BS) * BS
        end = row.size + steps
        held = np.arange(max(0, row.size - c.window, end - ring_rows), end)
        blocks = manager.window_tables[slot, held // BS]
        k_got = np.asarray(wpool["k"])[0, blocks, held % BS]
        v_got = np.asarray(wpool["v"])[0, blocks, held % BS]
        np.testing.assert_allclose(
            np.concatenate([k_got, v_got], -1), rows[held], atol=2e-4)
    # with the published numbers in the tiny preset's place the reference
    # is another model: the check holds the program to the file's numbers
    other, _, _ = mellum.forward(c, params, sequence, positions)
    assert np.abs(got - other).max() > 0.05 * other.std()


@pytest.mark.parametrize("fault", mellum.FAULTS)
def test_each_fault_changes_the_second_member_s_logits(mellum_model, fault):
    """The reference with one term changed gives other logits than itself
    (what the bench test then holds to the file's limits)."""
    c, params = mellum_model
    tokens = np.random.default_rng(2).integers(0, c.vocab_size, size=90)
    positions = [30, 60, 89]
    want, _, _ = mellum.forward(c, params, tokens, positions, rope=TINY_ROPE)
    got, _, _ = mellum.forward(c, params, tokens, positions, (fault,),
                               rope=TINY_ROPE)
    rms = np.sqrt(np.mean((got - want) ** 2, -1)) / want.std(-1)
    floor = 1e-4 if fault == "bfloat16_router" else 5e-3
    assert rms.max() > floor, (fault, rms)
