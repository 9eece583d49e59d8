"""The served ``solar_open2`` layer against its plain reference at the
``solar-tiny`` preset on the CPU: the comparison a run's ``correct`` rests on
(bench/reference/solar_open2.py ``check_engine``: in the engine's own pool
and state, with its own programs), in float32 and in the posture the cell
serves (bf16, the Pallas read and state kernel in the interpreter), its power
to see each term of the published equations changed, and its further limits'
power to see a delta-rule state kept in fewer bits."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from reference import solar_open2 as reference

TOLERANCE = {"rms_share": 0.06, "min_correlation": 0.999,
             "state_rms_share": 0.02, "prefill_state_rms_share": 0.02,
             "routing_margin": 0.05, "first_routing_differing_share": 0.05,
             "router_alone_differing_share": 1e-3,
             "engine_first_token_shortfall": 0.25,
             "engine_first_logprob_error": 0.05,
             "engine_decode_token_shortfall": 0.25,
             "engine_decode_logprob_error": 0.05,
             "engine_state_rms_share": 1e-3}
# two rows of the 128 bucket in one prefill, one in the 64 bucket, one in the
# 32 bucket; slot 1 idle; the last slot asked for is taken modulo the engine's
PROMPTS = ((100, 0), (90, 191), (45, 2), (20, 5))
POSTURES = {
    "float32": dict(model_dtype="float32"),
    "bf16-pallas": dict(paged_kernel="pallas-interpret"),
}
_engines = {}


def engine(posture="float32"):
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    if posture not in _engines:
        _engines[posture] = TpuServingEngine(ServingConfig(
            model="solar-tiny", slots=8, max_seq_len=256, kv_layout="paged",
            kv_block_size=16, prefix_cache=False, decode_chunk=8,
            **POSTURES[posture],
        ))
    return _engines[posture]


@pytest.mark.parametrize("posture", sorted(POSTURES))
def test_prefill_and_paged_decode_match_the_reference(posture):
    e = engine(posture)
    report = reference.check_engine(
        e, 2 ** 31 + 11, TOLERANCE, prompts=PROMPTS, steps=12)
    assert report["passed"], report
    assert len(report["positions"]) == 4 * 13
    assert report["prompts"] == [[100, 0], [90, 7], [45, 2], [20, 5]]
    assert report["prefill_batches"] == [
        {"bucket": 128, "rows": 2}, {"bucket": 64, "rows": 1},
        {"bucket": 32, "rows": 1}]
    assert report["decode_chunk"] == 8 and report["slots"] == 8
    assert len(report["state_rms_share_by_layer"]) == 3
    assert len(report["first_state_rms_share_by_head"]) == 4
    assert report["idle_state_untouched"]
    assert report["state_kernel"] == e.ssm_state_kernel
    if posture == "float32":
        assert report["worst_rms_share"] < 5e-3
        assert report["first_state_rms_share"] < 1e-4
        assert report["prefill_state_rms_share"] < 1e-4
        assert report["worst_routing_shortfall"] < 1e-4
    # the engine's own programs chose the model function's tokens and left
    # its state (the same kernel on the same rows)
    assert report["engine_decode_steps_compared"] > 0
    assert report["engine_state_rms_share"] < 1e-6


def test_the_check_leaves_the_engine_serving(run_async):
    """The check writes the engine's own pool and state; a request after it
    streams what it streams on a fresh engine (a slot's rows are written
    whole at admission)."""
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    e = engine("float32")
    reference.check_engine(e, 5, TOLERANCE, prompts=PROMPTS, steps=8)
    prompt, options = list(range(7, 60)), {"max-tokens": 10, "temperature": 0}

    async def main():
        fresh = TpuServingEngine(ServingConfig(
            model="solar-tiny", slots=8, max_seq_len=256, kv_layout="paged",
            kv_block_size=16, prefix_cache=False, decode_chunk=8,
            model_dtype="float32"))
        try:
            return ((await e.generate(prompt, options))["tokens"],
                    (await fresh.generate(prompt, options))["tokens"])
        finally:
            await fresh.close()

    after, want = run_async(main())
    assert after == want


def test_an_engine_that_is_serving_or_of_another_family_is_refused():
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    other = TpuServingEngine(ServingConfig(
        model="granite-tiny", slots=2, max_seq_len=128, kv_layout="paged",
        kv_block_size=16, prefix_cache=False))
    with pytest.raises(RuntimeError, match="delta-rule"):
        reference.check_engine(other, 1, TOLERANCE)
    dense = TpuServingEngine(ServingConfig(
        model="tiny", slots=2, max_seq_len=128, kv_layout="paged",
        kv_block_size=16, prefix_cache=False))
    with pytest.raises(RuntimeError, match="delta-rule"):
        reference.check_engine(dense, 1, TOLERANCE)
    with pytest.raises(RuntimeError, match="cannot hold"):
        reference.served(engine(), 1, prompts=((9, 0), (9, 8)), steps=2)


def test_the_layer_s_own_parts_follow_the_published_equations():
    """The delta rule against float64 numpy on one head; the router's gates
    renormalised over the chosen; the attention's gate elementwise."""
    e = engine()
    c = e.model_config
    lp = jax.tree.map(lambda a: jnp.asarray(a[0], jnp.float32), e.params["delta"])
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(10, c.hidden)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, states = reference.delta_rule(u, lp, c, states_after=(4, 10))
    assert out.shape == (10, c.hidden) and len(states) == 2
    assert states[0].shape == (c.delta_heads, c.delta_head_dim, c.delta_head_dim)
    # by hand, head 0, float64: S <- (I - b k k^T) Diag(a) S + b k v^T
    d, K = c.delta_head_dim, c.delta_inner
    u64 = np.asarray(u, np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    qkv = u64 @ w["w_qkv"]
    padded = np.concatenate([np.zeros((3, 3 * K)), qkv])
    conv = sum(padded[i : i + 10] * w["conv_w"][:, i] for i in range(4))
    act = conv / (1 + np.exp(-conv))
    q, k, v = (act[:, j * K : j * K + d] for j in range(3))
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    f = (u64 @ w["w_f_down"]) @ w["w_f_up"] + w["dt_bias"]
    g = -np.exp(w["A_log"][0]) * np.log1p(np.exp(f[:, :d]))
    beta = 2 / (1 + np.exp(-(u64 @ w["w_beta"][:, 0])))
    S = np.zeros((d, d))
    for t in range(10):
        S = np.exp(g[t])[:, None] * S
        S = S + beta[t] * np.outer(k[t], v[t] - S.T @ k[t])
        if t == 3:
            np.testing.assert_allclose(states[0][0], S.T, rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(states[1][0], S.T, rtol=1e-3, atol=1e-5)
    assert 0 < beta.min() and beta.max() < 2
    # the router: sigmoid scores, the bias for the choice alone, the chosen
    # scores renormalised to one
    first = {k: jnp.asarray(v[0], jnp.float32) for k, v in e.params["moe"].items()
             if k in ("router", "bias")}
    with jax.default_matmul_precision("highest"):
        chosen, weights = reference.route(u, first, c)
    assert chosen.shape == weights.shape == (10, c.experts_per_token)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-5)
    scores = 1 / (1 + np.exp(-(u64 @ np.asarray(first["router"], np.float64))))
    want = np.argsort(-(scores + np.asarray(first["bias"], np.float64)), -1)[:, :3]
    assert (np.sort(np.asarray(chosen), -1) == np.sort(want, -1)).all()


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_model_that_computes_something_else_fails(fault):
    e = engine()
    got = _served()
    report = reference.judge(e, got, TOLERANCE, faults=(fault,))
    assert not report["passed"], (fault, report["worst_rms_share"])


_got = {}


def _served():
    if "got" not in _got:
        _got["got"] = reference.served(engine(), 2 ** 31 + 3, prompts=PROMPTS,
                                       steps=12)
    return _got["got"]


def test_a_state_in_bfloat16_reads_worse_than_the_served_one():
    """The control the cell's ``state_rms_share`` has to tell apart: the
    delta-rule state stored in bfloat16 (the arithmetic stays float32)."""
    e = engine()
    served = reference.judge(e, _served(), TOLERANCE)
    low = reference.judge(e, reference.served(
        e, 2 ** 31 + 3, prompts=PROMPTS, steps=12,
        config=dataclasses.replace(e.model_config, state_dtype=jnp.bfloat16)),
        TOLERANCE)
    assert served["passed"] and served["first_state_rms_share"] < 1e-4
    assert low["first_state_rms_share"] > 20 * served["first_state_rms_share"]
    assert low["first_state_rms_share"] > 1e-3
    assert low["state_dtype"] == "bfloat16"
    # the engine's own state is float32 again after the control
    assert e.state["delta"].dtype == jnp.float32
