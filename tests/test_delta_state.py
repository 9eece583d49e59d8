"""The delta-rule state's decode step as a Pallas kernel
(ops/delta_state.py), through the interpreter, against the XLA expression it
replaces (``kernel="xla"``): the same float32 arithmetic on the stacked,
transposed state in place. A slot that is not active keeps its rows bit for
bit, the other layers' rows are not written, the step is the paper's
recurrence, and the start-up self-check has a row for it. (Mosaic's own
build of it at the served shape is in tests/test_ssm_state.py, the one file
whose fixture describes the chip.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models.hybrid import HybridConfig
from langstream_tpu.ops import delta_state
from langstream_tpu.ops.delta_state import delta_state_step
from langstream_tpu.ops.ssm_state import tile_heads

TINY = HybridConfig.solar_tiny()

#: layers, slots, heads, head_dim
SHAPES = {
    "solar-tiny": (TINY.delta_layers, 3, TINY.delta_heads, TINY.delta_head_dim),
    # the served tile: (128, 128) a head; 32 heads a grid step, 2 steps a slot
    "heads64-dim128": (3, 2, 64, 128),
    "heads6-dim32": (2, 4, 6, 32),
}


def operands(shape, dtype=jnp.float32, idle=(1,)):
    L, B, heads, D = shape
    ks = jax.random.split(jax.random.PRNGKey(L * heads + B), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    state = jax.random.normal(ks[0], (L, B, heads, D, D), jnp.float32).astype(dtype)
    a = jax.random.uniform(ks[1], (B, heads, D), jnp.float32, 0.05, 1.0)
    k = unit(jax.random.normal(ks[2], (B, heads, D), jnp.float32))
    q = unit(jax.random.normal(ks[3], (B, heads, D), jnp.float32)) * D ** -0.5
    v = jax.random.normal(ks[4], (B, heads, D), jnp.float32)
    beta = jax.random.uniform(ks[5], (B, heads), jnp.float32, 0.0, 2.0)
    active = jnp.asarray([b not in idle for b in range(B)])
    return state, a, k, q, v, beta, active


def step(kernel, state, layer, *rest):
    return jax.jit(lambda s, i, *a: delta_state_step(s, i, *a, kernel=kernel))(
        state, jnp.asarray(layer, jnp.int32), *rest)


@pytest.mark.parametrize("where", ["first", "last"])
@pytest.mark.parametrize("name", list(SHAPES))
def test_the_kernel_is_the_xla_expression_in_place(name, where):
    shape = SHAPES[name]
    layer = {"first": 0, "last": shape[0] - 1}[where]
    state, *rest = operands(shape)
    want_o, want = step("xla", state, layer, *rest)
    o, got = step("pallas-interpret", state, layer, *rest)
    assert o.shape == want_o.shape == shape[1:] and o.dtype == jnp.float32
    assert got.shape == state.shape and got.dtype == state.dtype
    np.testing.assert_allclose(o, want_o, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    got, state = np.asarray(got), np.asarray(state)
    # the idle slot's rows of this layer, and every other layer's rows
    assert np.array_equal(got[layer, 1], state[layer, 1])
    assert np.array_equal(np.delete(got, layer, 0), np.delete(state, layer, 0))
    assert not np.array_equal(got[layer, 0], state[layer, 0])


@pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
def test_the_step_is_the_paper_s_recurrence_on_the_transposed_state(kernel):
    """``S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T``, ``o = S_t^T q`` in
    float64 numpy on ``S (dk, dv)``; the stack holds ``S^T``."""
    state, a, k, q, v, beta, active = operands(SHAPES["heads6-dim32"], idle=())
    o, got = step(kernel, state, 1, a, k, q, v, beta, active)
    S = np.asarray(state[1], np.float64).swapaxes(-1, -2)        # (B, h, dk, dv)
    a, k, q, v, beta = (np.asarray(t, np.float64) for t in (a, k, q, v, beta))
    eye = np.eye(k.shape[-1])
    kk = k[..., :, None] * k[..., None, :]
    S = (eye - beta[..., None, None] * kk) @ (a[..., None] * S) \
        + beta[..., None, None] * k[..., :, None] * v[..., None, :]
    np.testing.assert_allclose(got[1], S.swapaxes(-1, -2), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        o, np.einsum("bhkv,bhk->bhv", S, q), rtol=1e-4, atol=1e-5)


def test_a_bfloat16_state_is_rounded_once_as_the_expression_rounds_it():
    state, *rest = operands(SHAPES["solar-tiny"], jnp.bfloat16)
    want_o, want = step("xla", state, 1, *rest)
    o, got = step("pallas-interpret", state, 1, *rest)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(o, want_o, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-2, atol=1e-2)
    assert np.array_equal(np.asarray(got[1, 1]), np.asarray(state[1, 1]))


def test_an_unknown_kernel_is_refused_by_name():
    state, *rest = operands(SHAPES["solar-tiny"])
    with pytest.raises(ValueError, match="delta_state_step: unknown kernel"):
        delta_state_step(state, 0, *rest, kernel="cuda")


def test_the_tile_is_the_mamba_2_kernel_s_rule_at_this_state_s_shape():
    # 64 KiB a head: 32 heads fill the 2 MiB tile, two grid steps a slot
    assert tile_heads(64, 128, 128, 4) == 32
    assert tile_heads(TINY.delta_heads, 16, 16, 4) == TINY.delta_heads


def test_the_self_check_has_a_row_for_the_kernel():
    from langstream_tpu.ops import selfcheck

    row = selfcheck.check_delta_state_kernel(TINY, slots=3, interpret=True)
    assert row["kernel"] == "_delta_state_kernel" and row["interpret"]
    assert row["ok"], row
    assert row["tol"] == selfcheck.STATE_TOLERANCE
    assert row["max_abs_err"] < 1e-5
    assert row["shape"]["heads"] == TINY.delta_heads


def test_a_row_that_misses_its_tolerance_is_not_ok(monkeypatch):
    from langstream_tpu.ops import selfcheck

    def off_by_a_thousandth(state, layer, *rest, kernel):
        o, new = delta_state.delta_state_step_xla(state, layer, *rest)
        return (o, new) if kernel == "xla" else (o * 1.001, new)

    monkeypatch.setattr(delta_state, "delta_state_step", off_by_a_thousandth)
    row = selfcheck.check_delta_state_kernel(TINY, slots=2, interpret=True)
    assert not row["ok"] and 5e-4 < row["max_abs_err"] < 2e-3
