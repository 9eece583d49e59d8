"""The EVA family (``models/eva.py``) at the ``evabyte-tiny`` preset on the
CPU, each case against ``bench/reference/evabyte.py``'s full forward pass on
seeded random weights: prefill at lengths inside the first window, on a
window's edge, one past it, mid-chunk and past three windows, through the
XLA masks and through the interpreted kernel; prefill then decode through the
two pools in chunks, with slots of different phases in one batch, steps that
close a chunk and a window inside a chunk, a frozen lane, a slot freed and
its blocks reused by another; and the block manager's second kind."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "bench"))

from reference import evabyte as reference  # noqa: E402

from langstream_tpu.models import eva  # noqa: E402
from langstream_tpu.models.paged import (  # noqa: E402
    BlockManager,
    PagedLayout,
    init_kv_pool,
)
from langstream_tpu.ops.eva_flash import eva_flash  # noqa: E402

BS, SLOTS, MAX = 8, 4, 256
C = dataclasses.replace(eva.EvaConfig.tiny(max_seq_len=MAX), dtype=jnp.float32)
PARAMS = eva.init_eva_params(C, jax.random.PRNGKey(1))
GREEDY = lambda logits, key: (  # noqa: E731
    jnp.argmax(logits, -1).astype(jnp.int32), jnp.zeros(logits.shape[:1]))


class Pools:
    """The two pools and their manager, as the engine builds them."""

    def __init__(self, blocks=40):
        self.layout = PagedLayout(
            block_size=BS, num_blocks=blocks, max_blocks_per_slot=MAX // BS)
        kinds = eva._two_kinds(C, self.layout, SLOTS)
        self.manager = BlockManager(self.layout, SLOTS, **kinds)
        self.pool_k, self.pool_v = init_kv_pool(C, self.layout, C.layers)
        self.ring = dict(zip("kv", init_kv_pool(
            C, kinds["window_layout"], C.layers)))

    def prefill(self, slot, tokens, total, kernel="xla", use_flash=False):
        n = len(tokens)
        bucket = 32
        while bucket < n:
            bucket *= 2
        row = np.zeros((1, bucket), np.int32)
        row[0, :n] = tokens
        self.manager.admit(slot, total)
        self.manager.ensure_capacity(slot, total)
        logits, self.pool_k, self.pool_v, self.ring, heads = \
            eva.eva_prefill_paged(
                C, PARAMS, jnp.asarray(row), jnp.asarray([n]), self.pool_k,
                self.pool_v, self.ring,
                jnp.asarray(self.manager.tables[slot][None]),
                use_flash=use_flash, kernel=kernel)
        return np.asarray(logits)[0], np.asarray(heads)[0]

    def decode(self, tokens, lengths, active, steps, kernel="xla"):
        out = eva.eva_decode_chunk_paged(
            C, PARAMS, jnp.asarray(tokens), jnp.asarray(lengths),
            jnp.asarray(active), self.pool_k, self.pool_v, self.ring,
            jnp.asarray(self.manager.tables), GREEDY, jax.random.PRNGKey(0),
            steps, self.layout.max_blocks_per_slot, kernel=kernel)
        self.pool_k, self.pool_v, self.ring = out[4:7]
        return (np.asarray(out[0]), np.asarray(out[2]), np.asarray(out[3]),
                np.asarray(out[7]))


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, C.vocab_size, size=n).astype(np.int32)


# inside the first window, on its edge, one past it, mid-chunk, on the second
# edge, past three windows (and on the fourth's edge: the bucket's whole)
LENGTHS = [5, 31, 32, 33, 50, 64, 101, 128]


@pytest.mark.parametrize("flash", ["xla", "kernel"])
@pytest.mark.parametrize("n", LENGTHS)
def test_prefill_matches_the_reference_s_full_forward(n, flash, monkeypatch):
    if flash == "kernel":
        monkeypatch.setenv("LS_TPU_FLASH", "interpret")
    tokens = prompt(n, seed=n)
    pools = Pools()
    logits, heads = pools.prefill(
        0, tokens, n + 1, use_flash=None if flash == "kernel" else False)
    want, rows, summaries = reference.forward(C, PARAMS, tokens, [n - 1])
    assert heads.shape == (C.pred_heads * C.vocab_size,)
    np.testing.assert_allclose(heads, want[0], atol=2e-5)
    np.testing.assert_array_equal(logits, heads[: C.vocab_size])
    # what it wrote: the last window's exact rows, every closed chunk's
    # summary (layer 0's, where a table column holds them)
    table = pools.manager.tables[0]
    first = n // C.window * C.window
    for pos in range(first, n):
        got = np.concatenate([np.asarray(
            pools.ring[a][0, table[MAX // BS + pos // BS], pos % BS])
            for a in "kv"])
        np.testing.assert_allclose(got, rows[pos], atol=2e-5)
    held = len(pools.manager._slot_blocks[0]) * BS
    for c in range(min(n // C.chunk, held)):
        got = np.concatenate([np.asarray(pool[0, table[c // BS], c % BS])
                              for pool in (pools.pool_k, pools.pool_v)])
        np.testing.assert_allclose(got, summaries[c], atol=2e-5)


@pytest.mark.parametrize("blocks", [(8, 8), (16, 16), (8, 16), (16, 8), (32, 32)])
@pytest.mark.parametrize("length", [128, 77])
def test_the_kernel_is_its_masks_at_every_block_size(blocks, length):
    """The kernel alone against plain masks, with query and key blocks
    smaller than a window: blocks under the diagonal whole, the diagonal's
    masked, the summaries' edge block masked, the padding skipped."""
    rng = np.random.default_rng(7)
    B, P, H, D, W, Cn = 2, 128, 2, 16, 32, 4
    q, k, v = (jnp.asarray(rng.standard_normal((B, P, H, D)), jnp.float32)
               for _ in range(3))
    ks, vs = (jnp.asarray(rng.standard_normal((B, P // Cn, H, D)), jnp.float32)
              for _ in range(2))
    lengths = jnp.asarray([length, 128 - length // 2], jnp.int32)
    got = np.asarray(eva_flash(
        q, k, v, ks, vs, lengths, window=W, per_window=W // Cn,
        block_q=blocks[0], block_k=blocks[1], interpret=True))
    i = np.arange(P)
    own = (i[:, None] >= i[None, :]) & (i[None, :] >= (i[:, None] // W) * W)
    seen = np.arange(P // Cn)[None, :] < (i[:, None] // W) * (W // Cn)
    s = np.concatenate([
        np.where(own, np.einsum("bqhd,bshd->bhqs", q, k) / 4.0, -np.inf),
        np.where(seen, np.einsum("bqhd,bshd->bhqs", q, ks) / 4.0, -np.inf),
    ], axis=-1)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhqs,bshd->bqhd", p, np.concatenate([v, vs], axis=1))
    for b in range(B):
        n = int(lengths[b])
        np.testing.assert_allclose(got[b, :n], want[b, :n], atol=2e-5)


@pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
def test_decode_through_the_two_pools_matches_the_reference(kernel):
    """Three slots of different phases and an idle one in one batch: 5 + 40
    crosses the first edge, 31 + 40 closes a window at its first step and the
    next inside a chunk, 70 + 40 crosses the third edge mid-chunk; every slot
    closes ten chunks."""
    sizes, steps = {0: 5, 1: 31, 2: 70}, 40
    pools = Pools()
    tokens = {s: prompt(n, seed=s) for s, n in sizes.items()}
    first, lengths = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    for s, n in sizes.items():
        logits, _ = pools.prefill(s, tokens[s], n + steps + 1, kernel=kernel)
        first[s], lengths[s] = logits.argmax(), n
    active = lengths > 0
    made, heads = [], []
    t0, n = first, lengths
    for k in (16, 16, 8):
        chunk, t0, n, h = pools.decode(t0, n, active, k, kernel=kernel)
        made.append(chunk)
        heads.append(h)
    made, heads = np.concatenate(made), np.concatenate(heads)
    assert list(n) == [45, 71, 110, 0]
    for s, size in sizes.items():
        sequence = np.concatenate([tokens[s], first[s:s + 1], made[:-1, s]])
        want, _, _ = reference.forward(
            C, PARAMS, sequence, list(range(size, size + steps)))
        np.testing.assert_allclose(heads[:, s], want, atol=3e-5)
        np.testing.assert_array_equal(
            made[:, s], want[:, : C.vocab_size].argmax(-1))


def test_a_frozen_lane_commits_nothing_and_a_freed_slot_s_blocks_serve_another():
    pools = Pools()
    a, b = prompt(45, seed=1), prompt(20, seed=2)
    la, _ = pools.prefill(0, a, 45 + 17)
    lb, _ = pools.prefill(1, b, 20 + 17)
    before = [np.asarray(p) for p in (
        pools.pool_k, pools.pool_v, pools.ring["k"], pools.ring["v"])]
    first = np.array([la.argmax(), lb.argmax(), 0, 0], np.int32)
    lengths = np.array([45, 20, 0, 0], np.int32)
    # slot 1 frozen: its token, its length and every row of its blocks stand
    chunk, t0, n, _ = pools.decode(
        first, lengths, np.array([True, False, False, False]), 8)
    assert n[1] == 20 and t0[1] == first[1] and n[0] == 53
    mine = set(pools.manager._slot_blocks[1]), set(pools.manager._slot_ring[1])
    after = [np.asarray(p) for p in (
        pools.pool_k, pools.pool_v, pools.ring["k"], pools.ring["v"])]
    for pool_before, pool_after, blocks in zip(
            before, after, (mine[0], mine[0], mine[1], mine[1])):
        for block in blocks:
            np.testing.assert_array_equal(
                pool_before[:, block], pool_after[:, block])
    # slot 0 is freed; its blocks go to a new request in slot 2, whose
    # logits are the reference's whatever the blocks held
    held = set(pools.manager._slot_ring[0])
    pools.manager.release(0)
    c = prompt(70, seed=3)
    lc, _ = pools.prefill(2, c, 70 + 9)
    assert held & set(pools.manager._slot_ring[2])
    chunk, _, n, heads = pools.decode(
        np.array([0, 0, lc.argmax(), 0], np.int32),
        np.array([0, 0, 70, 0], np.int32),
        np.array([False, False, True, False]), 8)
    sequence = np.concatenate([c, [lc.argmax()], chunk[:-1, 2]])
    want, _, _ = reference.forward(C, PARAMS, sequence, list(range(70, 78)))
    np.testing.assert_allclose(heads[:, 2], want, atol=3e-5)


# -- the block manager's second kind ----------------------------------------


def manager(blocks=13, slots=3):
    layout = PagedLayout(block_size=BS, num_blocks=blocks,
                         max_blocks_per_slot=MAX // BS)
    return BlockManager(layout, slots, **eva._two_kinds(C, layout, slots))


@pytest.mark.parametrize("positions, summary, ring", [
    (1, 0, 1), (32, 0, 4), (33, 1, 4), (64, 1, 4), (65, 2, 4), (200, 6, 4),
    (256, 7, 4)])
def test_a_request_reserves_by_the_second_kind_s_growth_rule(
        positions, summary, ring):
    """``n`` positions need the summary blocks of the windows closed before
    the last position (8 rows = 1 block a window here) and the ring's blocks
    up to 4."""
    m = manager()
    assert m.blocks_needed(positions) == summary
    assert m.window_blocks_needed(positions) == ring
    m.admit(0, positions)
    assert m.stats()["reserved_blocks"] == summary + ring


def test_the_summary_kind_grows_a_window_at_a_time_and_a_window_ahead():
    m = manager()
    m.admit(0, 130)                      # four closed windows: 4 blocks
    grown = [m.ensure_capacity(0, n) for n in (1, 20, 32, 33, 64, 65, 130)]
    # 1 row: the first ring block and the open window's summary block
    assert grown == [2, 2, 1, 1, 0, 1, 1]
    assert m.summary_blocks_held == 4 and m.window_blocks_held == 4
    # the table: [summary columns | ring columns by logical block]
    width = MAX // BS
    assert (m.tables[0, :4] > 0).all() and (m.tables[0, 4:width] == 0).all()
    ring_columns = m.tables[0, width:]
    assert (ring_columns[:4] > 0).all()
    np.testing.assert_array_equal(ring_columns[:4], ring_columns[4:8])
    m.release(0)
    assert m.stats()["live_blocks"] == 0 and m.stats()["reserved_blocks"] == 0
    assert m.summary_blocks_held == 0 and m.window_blocks_held == 0
    assert not m.tables.any()


def test_admission_refuses_by_the_summary_kind_and_a_release_readmits():
    """12 summary blocks: two requests of six closed windows fill them; a
    third waits (``pool exhausted`` to a caller that admits all the same)
    until one is released, which is what a preemption is."""
    m = manager(blocks=13)
    assert m.can_admit(200) and m.fits_ever(256)
    m.admit(0, 200)
    m.admit(1, 200)
    assert not m.can_admit(64) and m.can_admit(32)   # 32: no closed window
    with pytest.raises(RuntimeError, match="pool exhausted"):
        m.admit(2, 64)
    assert m.used_ratio() == 1.0
    m.release(1)                                     # the preemption's path
    assert m.can_admit(200)
    m.admit(2, 200)
    m.ensure_capacity(2, 200)
    assert m.summary_blocks_held == 6
    # longer than a slot's table: never
    assert not m.fits_ever(MAX + 1)


def test_a_plain_manager_counts_as_it_did():
    layout = PagedLayout(block_size=BS, num_blocks=40, max_blocks_per_slot=32)
    m = BlockManager(layout, 2)
    assert [m.blocks_needed(n) for n in (1, 8, 9, 200)] == [1, 1, 2, 25]
    m.admit(0, 30)
    assert m.ensure_capacity(0, 30) == 4 and m.summary_blocks_held == 4
