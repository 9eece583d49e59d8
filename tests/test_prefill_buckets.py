"""The rows a prefill program is compiled for (``serving/engine.py``
``_prefill_bucket_rows``): powers of two up to 4,096 and, above, also the
midpoint under each (6,144; 12,288; 24,576). First the rule alone, and what
``plan_wave`` makes of two prompts either side of a midpoint; then each
long-prompt family's prefill at the tiny widths on the CPU, traced at the
true midpoints and run in a midpoint bucket against the power of two above
it (the row counts scaled to the tiny windows, the passes' constants with
them). The engine's side: ``test_prefill_buckets_engine.py``."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models import eva, latent, swa
from langstream_tpu.models.paged import (
    BlockManager,
    PagedLayout,
    init_kv_pool,
    init_latent_pool,
)
from langstream_tpu.serving.engine import (
    _bucket,
    _prefill_midpoints,
    _prefill_bucket_rows,
)
from langstream_tpu.serving.scheduler import plan_wave

POWERS = [32 << i for i in range(11)]                 # 32 .. 32,768
MIDPOINTS = [6144, 12288, 24576]


# -- the rule alone --------------------------------------------------------


@pytest.mark.parametrize("cap", [
    128, 2048, 4096, 5000, 6144, 8192, 10000, 16384, 32768])
def test_a_prompt_s_bucket_is_the_smallest_that_holds_it(cap):
    above = sorted(b for b in POWERS + MIDPOINTS if b > 4096)
    before = 0
    for n in range(1, 32769):
        got = _prefill_bucket_rows(n, cap)
        assert got >= min(n, cap) and got >= before     # holds it; monotone
        before = got
        if n <= 4096:
            assert got == _bucket(n, hi=cap)
        else:
            assert got == min(cap, next(b for b in above if b >= n))
    assert _prefill_midpoints(cap) == [m for m in MIDPOINTS if m <= cap]


def test_two_prompts_either_side_of_a_midpoint_are_two_programs():
    key = lambda n: (_prefill_bucket_rows(n, 16384), False)  # noqa: E731
    assert [key(n)[0] for n in (4097, 6144, 6145, 8192)] == [
        6144, 6144, 8192, 8192]
    assert plan_wave([key(6144), key(8192)], 2, 8) == [[0], [1]]
    assert plan_wave([key(5000), key(8000), key(6000)], 3, 8) == [[0, 2], [1]]


# -- each family's prefill at the true midpoints: traced, nothing computed --


def _eva_pools(c, bs, slots, blocks):
    layout = PagedLayout(bs, blocks, c.max_seq_len // bs)
    kinds = eva._two_kinds(c, layout, slots)
    return (layout, kinds, init_kv_pool(c, layout, c.layers),
            dict(zip("kv", init_kv_pool(c, kinds["window_layout"], c.layers))))


def _swa_pools(c, bs, slots):
    per = c.max_seq_len // bs
    layout = PagedLayout(bs, slots * per + 1, per)
    ring = c.ring_blocks(bs)
    window_layout = PagedLayout(bs, slots * ring + 1, per)
    manager = BlockManager(
        layout, slots, window_layout=window_layout, window_ring=ring)
    wk, wv = init_kv_pool(c, window_layout, c.window_layers)
    return manager, init_kv_pool(c, layout, c.full_layers), {"k": wk, "v": wv}


@pytest.mark.parametrize("rows", MIDPOINTS)
@pytest.mark.parametrize("family", ["latent", "swa", "mellum", "eva"])
def test_every_family_s_prefill_takes_the_true_midpoints(family, rows):
    """The reshapes, the passes and the head groups at 6,144, 12,288 and
    24,576 rows, at the tiny widths through the XLA masks (the kernels at
    the served widths: ``test_ssm_state.py`` and the chip)."""
    bs, seq = 64, 32768
    tokens = jax.ShapeDtypeStruct((1, rows), jnp.int32)
    lengths = jax.ShapeDtypeStruct((1,), jnp.int32)
    if family == "latent":
        c = latent.LatentConfig.tiny(max_seq_len=seq)
        params = jax.eval_shape(lambda: latent.init_latent_params(c))
        layout = PagedLayout(bs, seq // bs + 1, seq // bs)
        pool = jax.eval_shape(lambda: init_latent_pool(c, layout)[0])
        logits, _pool, routed = jax.eval_shape(
            lambda p, t, n, pool, tb: latent.latent_prefill_paged(
                c, p, t, n, pool, tb, use_flash=False),
            params, tokens, lengths, pool,
            jax.ShapeDtypeStruct((1, seq // bs), jnp.int32))
        assert routed.shape[1:3] == (1, rows)
    elif family == "eva":
        c = eva.EvaConfig.tiny(max_seq_len=seq)
        params = jax.eval_shape(lambda: eva.init_eva_params(c))
        bs = 32                      # a ring block lies inside the window
        _layout, _kinds, pools, ring = _eva_pools(c, bs, 1, 64)
        logits = jax.eval_shape(
            lambda p, t, n, pk, pv, wp, tb: eva.eva_prefill_paged(
                c, p, t, n, pk, pv, wp, tb, use_flash=False),
            params, tokens, lengths, *pools, ring,
            jax.ShapeDtypeStruct((1, 2 * seq // bs), jnp.int32))[0]
    else:
        c = (swa.SwaConfig.tiny if family == "swa"
             else swa.SwaConfig.mellum_tiny)(max_seq_len=seq)
        params = jax.eval_shape(lambda: swa.init_swa_params(c))
        _manager, pools, ring = _swa_pools(c, bs, 1)
        logits = jax.eval_shape(
            lambda p, t, n, pk, pv, wp, tb: swa.swa_prefill_paged(
                c, p, t, n, pk, pv, wp, tb, use_flash=False),
            params, tokens, lengths, *pools, ring,
            jax.ShapeDtypeStruct((1, 2 * seq // bs), jnp.int32))[0]
    assert logits.shape == (1, c.vocab_size)


# -- a midpoint bucket against the power of two above it, run --------------
#
# The tiny windows are 32 rows where the served ones are 2,048 and 4,096, so
# the buckets are 96 against 128 (6,144 against 8,192 is three windows of
# 2,048 against four), the kernels' blocks and the passes' rows scaled with
# them, through the interpreted kernels (LS_TPU_FLASH=interpret).


def _prompt(n, vocab, seed):
    return np.random.default_rng(seed).integers(
        3, vocab, size=n).astype(np.int32)


def _row(tokens, bucket):
    row = np.zeros((1, bucket), np.int32)
    row[0, :len(tokens)] = tokens
    return jnp.asarray(row), jnp.asarray([len(tokens)], jnp.int32)


def _same(got, want):
    """Logits, then every pool outside the scratch block 0."""
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    assert int(np.argmax(got[0])) == int(np.argmax(want[0]))
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(
            np.asarray(a)[:, 1:], np.asarray(b)[:, 1:], rtol=1e-5, atol=1e-5)


# a prompt one past the edge of the bucket's second window, in the third
# window mid-chunk, and the bucket whole
LENGTHS = [65, 70, 96]


@pytest.mark.parametrize("n", LENGTHS)
def test_latent_in_a_midpoint_bucket_is_latent_in_the_power_of_two(
        n, monkeypatch):
    """96 rows x 4 heads go a head at a time as 6,144 x 128 go in groups of
    16 under ``EXPAND_ROWS_X_HEADS``."""
    monkeypatch.setenv("LS_TPU_FLASH", "interpret")
    monkeypatch.setattr(latent, "EXPAND_ROWS_X_HEADS", 96)
    monkeypatch.setattr(latent, "FLASH_BLOCK", 32)
    c = dataclasses.replace(latent.LatentConfig.tiny(), dtype=jnp.float32)
    params = latent.init_latent_params(c)
    layout = PagedLayout(block_size=8, num_blocks=17, max_blocks_per_slot=16)
    tables = 1 + jnp.arange(16, dtype=jnp.int32)[None]
    tokens = _prompt(n, c.vocab_size, n)

    def run(bucket):
        logits, pool, routed = latent.latent_prefill_paged(
            c, params, *_row(tokens, bucket), init_latent_pool(c, layout)[0],
            tables)
        return np.asarray(logits)[0], pool, np.asarray(routed)[:, 0, :n]

    got, want = run(96), run(128)
    _same(got, want)
    np.testing.assert_array_equal(got[2], want[2])  # the router's choices


@pytest.mark.parametrize("preset, n", [
    ("tiny", 65), ("tiny", 96), ("mellum_tiny", 70)])
def test_swa_in_a_midpoint_bucket_is_swa_in_the_power_of_two(
        preset, n, monkeypatch):
    """Flash blocks of 8 rows against the window of 32 (1,024 against
    4,096), the ring written with the prompt's last window alone."""
    monkeypatch.setenv("LS_TPU_FLASH", "interpret")
    monkeypatch.setattr(swa, "FLASH_BLOCK", 8)
    c = dataclasses.replace(
        getattr(swa.SwaConfig, preset)(max_seq_len=128), dtype=jnp.float32)
    params = swa.init_swa_params(c, jax.random.PRNGKey(3))
    tokens = _prompt(n, c.vocab_size, n)

    def run(bucket):
        manager, pools, ring = _swa_pools(c, 8, 1)
        manager.admit(0, n + 1)
        manager.ensure_capacity(0, n + 1)
        logits, pk, pv, wp, routed = swa.swa_prefill_paged(
            c, params, *_row(tokens, bucket), *pools, ring,
            jnp.asarray(manager.tables[0][None]))
        return (np.asarray(logits)[0], (pk, pv, wp),
                np.asarray(routed)[:, 0, :n])

    got, want = run(96), run(128)
    _same(got, want)
    np.testing.assert_array_equal(got[2], want[2])


@pytest.mark.parametrize("n", LENGTHS)
def test_eva_in_a_midpoint_bucket_is_eva_in_the_power_of_two(n, monkeypatch):
    """96 rows are no multiple of the passes' 64 (6,144 of 4,096): the
    summaries and the MLP go whole there and in two passes at 128."""
    monkeypatch.setenv("LS_TPU_FLASH", "interpret")
    monkeypatch.setattr(eva, "FLASH_BLOCK", 16)
    monkeypatch.setattr(eva, "SUMMARY_ROWS", 64)
    monkeypatch.setattr(eva, "FFN_ROWS", 64)
    c = dataclasses.replace(
        eva.EvaConfig.tiny(max_seq_len=128), dtype=jnp.float32)
    params = eva.init_eva_params(c, jax.random.PRNGKey(1))
    tokens = _prompt(n, c.vocab_size, n)

    def run(bucket):
        layout, kinds, pools, ring = _eva_pools(c, 8, 1, 12)
        manager = BlockManager(layout, 1, **kinds)
        manager.admit(0, n + 1)
        manager.ensure_capacity(0, n + 1)
        logits, pk, pv, wp, heads = eva.eva_prefill_paged(
            c, params, *_row(tokens, bucket), *pools, ring,
            jnp.asarray(manager.tables[0][None]))
        return np.asarray(heads)[0], (pk, pv, wp)

    _same(run(96), run(128))
