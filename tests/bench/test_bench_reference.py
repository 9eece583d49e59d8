"""The served model against the plain reference at the ``tiny`` size on the
CPU: the comparison a run's ``correct`` rests on (bench/reference/
dense_gqa.py ``check_engine``), in each posture the cells serve, and its
power to see a fault."""

import dataclasses

import numpy as np
import pytest

from reference import dense_gqa

TOLERANCE = {"rms_share": 0.03, "min_correlation": 0.9995}  # the tiny fixture's
POSTURES = {
    "int8-weights-int8-kv-xla": dict(quantize="int8", kv_quantize="int8"),
    "bf16-pallas-read": dict(paged_kernel="pallas-interpret"),
    "float32": dict(model_dtype="float32"),
}


def engine(**kw):
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    return TpuServingEngine(ServingConfig(
        model="tiny", slots=4, max_seq_len=512, kv_layout="paged",
        kv_block_size=16, **kw,
    ))


@pytest.mark.parametrize("posture", sorted(POSTURES))
def test_prefill_and_paged_decode_match_the_reference(posture):
    report = dense_gqa.check_engine(
        engine(**POSTURES[posture]), 2 ** 31 + 11, TOLERANCE,
        prompt_tokens=48, steps=4,
    )
    assert report["passed"], report
    assert len(report["positions"]) == 5     # the prefill's last, 4 decoded
    if posture == "float32":                 # same arithmetic: near exact
        assert report["worst_rms_share"] < 1e-4


def test_the_reference_follows_the_published_equations():
    """Against a second, independent spelling: one position at a time with
    an explicit loop over heads, no einsum."""
    e = engine(model_dtype="float32")
    c, p = e.model_config, e.params
    tokens = np.random.default_rng(3).integers(0, c.vocab_size, size=12)
    want = dense_gqa.forward_logits(c, p, tokens, [11])[0]

    f = lambda t: np.asarray(dense_gqa.to_f32(t), dtype=np.float64)
    x = f(p["embed"])[tokens]
    half = c.head_dim // 2
    freqs = 1.0 / (c.rope_theta ** (np.arange(half) / half))

    def rot(v, t):                           # v: (head_dim,)
        a = t * freqs
        return np.concatenate([v[:half] * np.cos(a) - v[half:] * np.sin(a),
                               v[:half] * np.sin(a) + v[half:] * np.cos(a)])

    def norm(v, w):
        return v / np.sqrt(np.mean(v * v, axis=-1, keepdims=True) + c.norm_eps) * w

    for i in range(c.layers):
        w = {k: f(v)[i] for k, v in p["layers"].items()}
        h = norm(x, w["attn_norm"])
        q = (h @ w["wq"]).reshape(len(tokens), c.heads, c.head_dim)
        k = (h @ w["wk"]).reshape(len(tokens), c.kv_heads, c.head_dim)
        v = (h @ w["wv"]).reshape(len(tokens), c.kv_heads, c.head_dim)
        out = np.zeros_like(q)
        for t in range(len(tokens)):
            for head in range(c.heads):
                kv = head // (c.heads // c.kv_heads)
                qt = rot(q[t, head], t)
                scores = np.array([qt @ rot(k[s, kv], s) for s in range(t + 1)])
                scores = scores / np.sqrt(c.head_dim)
                weights = np.exp(scores - scores.max())
                weights /= weights.sum()
                out[t, head] = weights @ v[: t + 1, kv]
        x = x + out.reshape(len(tokens), -1) @ w["wo"]
        h = norm(x, w["mlp_norm"])
        gate = h @ w["w_gate"]
        x = x + ((gate / (1 + np.exp(-gate))) * (h @ w["w_up"])) @ w["w_down"]
    logits = norm(x[11], f(p["final_norm"])) @ f(p["lm_head"])
    np.testing.assert_allclose(want, logits, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("fault", ["rope_theta", "one_layer_dropped", "kv_heads_swapped"])
def test_a_model_that_computes_something_else_fails(fault):
    """The tolerance is tight enough to see a dropped term."""
    e = engine()
    if fault == "rope_theta":
        e.model_config = dataclasses.replace(e.model_config, rope_theta=1e4)
        # the engine's programs take the config: prefill and decode now
        # rotate differently from the reference, which is fed the published
        # value through the same field — so perturb only the served side
        served = e.model_config
        real_forward = dense_gqa.forward_logits
        try:
            dense_gqa.forward_logits = lambda c, p, t, pos: real_forward(
                dataclasses.replace(served, rope_theta=500000.0), p, t, pos)
            report = dense_gqa.check_engine(e, 5, TOLERANCE, prompt_tokens=48,
                                            steps=2)
        finally:
            dense_gqa.forward_logits = real_forward
    else:
        real_forward = dense_gqa.forward_logits

        def wrong(c, p, t, pos):
            import jax

            if fault == "one_layer_dropped":
                c = dataclasses.replace(c, layers=c.layers - 1)
            else:
                p = dict(p, layers=dict(
                    p["layers"],
                    wk=jax.tree.map(lambda a: a[..., ::-1], p["layers"]["wk"]),
                ))
            return real_forward(c, p, t, pos)

        try:
            dense_gqa.forward_logits = wrong
            report = dense_gqa.check_engine(e, 5, TOLERANCE, prompt_tokens=48,
                                            steps=2)
        finally:
            dense_gqa.forward_logits = real_forward
    assert not report["passed"], report
    assert report["worst_rms_share"] > 0.12   # over twice the loosest limit a file states


def test_compare_reports_every_position():
    rng = np.random.default_rng(0)
    want = rng.normal(size=(3, 500)).astype(np.float32)
    good = dense_gqa.compare(want + 0.01 * rng.normal(size=want.shape), want,
                             TOLERANCE)
    bad = dense_gqa.compare(want + 0.5 * rng.normal(size=want.shape), want,
                            TOLERANCE)
    # the int8 posture's 2.3% passes its own file's limit and not the bf16 one's
    int8_error = want + 0.035 * rng.normal(size=want.shape)
    assert dense_gqa.compare(int8_error, want, {"rms_share": 0.05,
                                                "min_correlation": 0.9985})["passed"]
    assert not dense_gqa.compare(int8_error, want, TOLERANCE)["passed"]
    assert good["passed"] and len(good["positions"]) == 3
    assert good["worst_rms_share"] == pytest.approx(0.01, rel=0.2)
    assert not bad["passed"] and bad["worst_correlation"] < 0.995
