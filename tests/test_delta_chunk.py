"""The chunked gated delta rule of a prefill as one Pallas kernel
(ops/delta_chunk.py), through the interpreter, against the XLA expression it
stands in for (``models/hybrid.py`` ``delta_chunked``) and against the
token-by-token recurrence. The kernel rounds the operands of its large
products to bfloat16, as the XLA form's are rounded on a TPU and are not on
the CPU, so the comparisons here are shares of a root mean square, not the
XLA form's 2e-4. (Mosaic's own build of it at the served shapes is in
tests/test_ssm_state.py, the one file whose fixture describes the chip.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models import hybrid
from langstream_tpu.models.hybrid import HybridConfig, delta_chunked
from langstream_tpu.ops import delta_chunk
from langstream_tpu.ops.delta_chunk import delta_chunk_rule
from test_solar_model import inputs, recurrence

TINY = HybridConfig.solar_tiny()
#: the kernel's distance from float32 products: two roundings of 2^-9 a
#: product, 0.22-0.33% of the root mean square in every case below
RMS_SHARE = 6e-3


def share(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def kernel(q, k, v, g, beta, chunk, lengths=None, **kw):
    return delta_chunk_rule(q, k, v, g, beta, chunk, lengths, interpret=True, **kw)


@pytest.mark.parametrize("P, chunk", [(48, 16), (64, 64), (32, 8), (96, 32)])
def test_the_kernel_is_the_chunked_form(P, chunk):
    q, k, v, g, beta = inputs(P, 2, P, 3, 16, 0.2)
    o, S = kernel(q, k, v, g, beta, chunk)
    want_o, want_S = delta_chunked(q, k, v, g, beta, chunk)
    assert o.shape == want_o.shape and S.shape == want_S.shape
    assert o.dtype == S.dtype == jnp.float32
    assert 1e-4 < share(o, want_o) < RMS_SHARE      # bfloat16 operands: not 0
    assert share(S, want_S) < RMS_SHARE


@pytest.mark.parametrize("P, chunk", [(48, 16), (64, 64), (32, 8), (96, 32)])
def test_the_kernel_is_the_recurrence(P, chunk):
    q, k, v, g, beta = inputs(P, 2, P, 3, 16, 0.2)
    o, S = kernel(q, k, v, g, beta, chunk)
    want_o, want_S = recurrence(q, k, v, g, beta)
    assert share(o, want_o) < RMS_SHARE and share(S, want_S) < RMS_SHARE


@pytest.mark.parametrize("decay", [8.0, 40.0])
def test_a_strong_decay_overflows_nothing(decay):
    """``sum g`` over a chunk far below -60 (``test_solar_model.py``'s case):
    every exponent the kernel forms is <= 0."""
    q, k, v, g, beta = inputs(3, 2, 64, 2, 16, decay)
    o, S = kernel(q, k, v, g, beta, 32)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(S)).all()
    want_o, want_S = recurrence(q, k, v, g, beta)
    assert share(o, want_o) < RMS_SHARE and share(S, want_S) < RMS_SHARE
    again, _ = delta_chunked(q, k, v, g, beta, 32)
    assert share(o, again) < RMS_SHARE


@pytest.mark.parametrize("told", [True, False])
def test_right_padded_rows_end_at_their_last_token(told):
    """Two rows of unequal lengths in one batch, the shorter with a wholly
    padded chunk: each row's state is the recurrence's after its last real
    token, told the lengths (the padded chunk skipped) or not (walked with
    ``g = 0`` and ``beta = 0``), and the real rows' outputs are the same."""
    P, chunk, lengths = 64, 16, jnp.asarray([64, 27], jnp.int32)
    q, k, v, g, beta = inputs(7, 2, P, 3, 16, 0.3)
    real = jnp.arange(P)[None, :] < lengths[:, None]
    g = jnp.where(real[..., None, None], g, 0.0)
    beta = jnp.where(real[..., None], beta, 0.0)
    o, S = kernel(q, k, v, g, beta, chunk, lengths if told else None)
    for row, n in enumerate((64, 27)):
        cut = lambda t: t[row : row + 1, :n]  # noqa: E731
        want_o, want_S = recurrence(*(cut(t) for t in (q, k, v, g, beta)))
        assert share(o[row : row + 1, :n], want_o) < RMS_SHARE
        assert share(S[row : row + 1], want_S) < RMS_SHARE
    if told:    # the skipped chunks' rows are zeros, not what was in VMEM
        assert not np.asarray(o[1, 32:]).any()
        untold, S_untold = kernel(q, k, v, g, beta, chunk)
        np.testing.assert_array_equal(o[1, :32], untold[1, :32])
        np.testing.assert_array_equal(S, S_untold)


@pytest.mark.parametrize("tile", [1, 2, 4])
def test_the_head_tile_changes_nothing(tile):
    q, k, v, g, beta = inputs(5, 2, 32, 4, 16, 0.2)
    whole = kernel(q, k, v, g, beta, 16, heads_tile=4)
    tiled = kernel(q, k, v, g, beta, 16, heads_tile=tile)
    for a, b in zip(whole, tiled):
        np.testing.assert_array_equal(a, b)


def test_the_tile_is_the_largest_divisor_under_its_bound():
    assert delta_chunk.tile_heads(64) == delta_chunk.TILE_HEADS == 8
    assert delta_chunk.tile_heads(TINY.delta_heads) == TINY.delta_heads == 4
    assert delta_chunk.tile_heads(6) == 6 and delta_chunk.tile_heads(14) == 7


@pytest.mark.parametrize("P, chunk, what", [
    (40, 16, "whole chunks"), (96, 48, "a power of two")])
def test_a_shape_the_kernel_cannot_cut_is_refused_by_name(P, chunk, what):
    q, k, v, g, beta = inputs(1, 1, P, 2, 16, 0.2)
    with pytest.raises(ValueError, match=what):
        kernel(q, k, v, g, beta, chunk)


@pytest.fixture(scope="module")
def layer():
    params = hybrid.init_hybrid_params(TINY, jax.random.PRNGKey(2))
    lp = jax.tree.map(lambda a: a[0], params["delta"])
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 48, TINY.hidden))
    return lp, u, jnp.asarray([48, 21], jnp.int32)


def test_the_mixer_s_prefill_is_the_same_under_either_selection(layer):
    lp, u, lengths = layer
    want = hybrid.delta_prefill(TINY, lp, u, lengths, "xla")
    got = hybrid.delta_prefill(TINY, lp, u, lengths, "pallas-interpret")
    real = np.asarray(jnp.arange(48)[None, :] < lengths[:, None])
    assert share(np.asarray(got[0])[real], np.asarray(want[0])[real]) < 2e-2
    assert share(got[1], want[1]) < RMS_SHARE
    np.testing.assert_array_equal(got[2], want[2])      # the convolutions' tail
    assert got[1].dtype == want[1].dtype == TINY.state_dtype


def test_the_mixer_refuses_a_selection_it_does_not_know(layer):
    lp, u, lengths = layer
    with pytest.raises(ValueError, match="unknown kernel 'cuda'"):
        hybrid.delta_prefill(TINY, lp, u, lengths, "cuda")


def test_the_call_sits_under_the_scope_the_reader_reads(layer):
    """``bench/layer_metrics/delta_chunk_mfu.py`` reads the prefill programs'
    time under ``delta_chunk``; the kernel is named so that no other reader's
    pattern takes it."""
    lp, u, lengths = layer
    text = jax.jit(lambda u: hybrid.delta_prefill(
        TINY, lp, u, lengths, "pallas-interpret")).lower(u).as_text(
            debug_info=True)
    assert "delta_chunk/" in text or 'delta_chunk"' in text
    jaxpr = str(jax.make_jaxpr(lambda u: hybrid.delta_prefill(
        TINY, lp, u, lengths, "pallas-interpret"))(u))
    assert jaxpr.count("name=delta_chunk_rule") == 1


def test_the_self_check_has_a_row_for_the_kernel():
    from langstream_tpu.ops import selfcheck

    row = selfcheck.check_delta_chunk_kernel(TINY, interpret=True)
    assert row["kernel"] == "_delta_chunk_kernel" and row["interpret"]
    assert row["ok"], row
    assert row["tol"] == selfcheck.CHUNK_TOLERANCE == 2e-2
    assert 1e-4 < row["max_abs_err"] < 1e-2
    # one chunk count above one, and a chunk that is skipped
    assert row["shape"]["tokens"] == 2 * TINY.delta_chunk
    assert row["shape"]["lengths"] == [2 * TINY.delta_chunk, TINY.delta_chunk - 3]
    assert row["shape"]["heads"] == TINY.delta_heads


def test_a_kernel_that_leaves_a_decay_out_fails_the_self_check(monkeypatch):
    """The fault: the carried state not decayed over the chunk (``exp`` of
    the chunk's last running sum, the one exponent taken of a single row)."""
    from langstream_tpu.ops import selfcheck

    class Faulty:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def exp(x):
            return jnp.ones_like(x) if x.shape[1] == 1 else jnp.exp(x)

    monkeypatch.setattr(delta_chunk, "jnp", Faulty())
    row = selfcheck.check_delta_chunk_kernel(TINY, interpret=True)
    assert not row["ok"] and row["max_abs_err"] > 5 * row["tol"], row


@pytest.mark.parametrize("handed, backend, got", [
    (None, "cpu", "xla"), (None, "tpu", "pallas"),
    ("xla", "tpu", "xla"), ("pallas-interpret", "cpu", "pallas-interpret")])
def test_a_prefill_handed_no_selection_takes_the_backend_s(
        monkeypatch, handed, backend, got):
    """``hybrid_prefill_paged`` runs the selection it is handed (the engine
    hands its own); handed none, as by the reference check's model function,
    it takes what the engine resolves on the backend, as ``use_flash``
    does."""
    seen = []

    def spy(c, lp, u, lengths, kernel="xla"):
        seen.append(kernel)
        return delta_prefill(c, lp, u, lengths, "xla")

    delta_prefill = hybrid.delta_prefill
    monkeypatch.setattr(hybrid, "delta_prefill", spy)
    monkeypatch.setattr(hybrid.jax, "default_backend", lambda: backend)
    params = hybrid.init_hybrid_params(TINY, jax.random.PRNGKey(2))
    B, P, bs = 2, 32, 16
    pool = jnp.zeros(
        (TINY.attn_layers, B * 3 + 1, bs, TINY.kv_heads * TINY.head_dim),
        TINY.dtype)
    tables = (1 + jnp.arange(B * 3, dtype=jnp.int32)).reshape(B, 3)
    jax.eval_shape(
        lambda p, pk, pv, st: hybrid.hybrid_prefill_paged(
            TINY, p, jnp.zeros((B, P), jnp.int32),
            jnp.asarray([P, 20], jnp.int32), pk, pv, st, tables,
            jnp.arange(B, dtype=jnp.int32), use_flash=False,
            **({} if handed is None else {"kernel": handed})),
        params, pool, pool, hybrid.init_hybrid_state(TINY, 4))
    assert seen and set(seen) == {got}
