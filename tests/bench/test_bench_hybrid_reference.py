"""The served hybrid model against its plain reference at the tiny preset on
the CPU: the comparison a run's ``correct`` rests on
(bench/reference/hybrid_ssm_moe.py ``check_engine``), in float32 and in the
posture the cell serves (bf16, the Pallas read in the interpreter), its
power to see each term of the published equations dropped, and its further
limits' power to see a recurrent state or a router kept in fewer bits."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from reference import hybrid_ssm_moe as reference

TOLERANCE = {"rms_share": 0.03, "min_correlation": 0.9995,
             "state_rms_share": 0.02, "routing_margin": 0.02,
             "first_routing_differing_share": 0.03}
# two rows of the 512 bucket in one prefill (1,024 rows: the grouped expert
# pass), one in the 64 bucket (the dense pass); slot 1 idle
PROMPTS = ((300, 2), (290, 0), (45, 3))
POSTURES = {
    "float32": dict(model_dtype="float32"),
    "bf16-pallas-read": dict(paged_kernel="pallas-interpret"),
}
_engines = {}


def engine(posture="float32"):
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    if posture not in _engines:
        _engines[posture] = TpuServingEngine(ServingConfig(
            model="hybrid-tiny", slots=4, max_seq_len=512, kv_layout="paged",
            kv_block_size=16, prefix_cache=False, **POSTURES[posture],
        ))
    return _engines[posture]


@pytest.mark.parametrize("posture", sorted(POSTURES))
def test_prefill_and_paged_decode_match_the_reference(posture):
    """Prompts that are no multiple of the scan chunk (16 here), cross
    several chunks and sit in padded buckets, three slots of unequal length
    and an idle one among them, then 4 steps."""
    report = reference.check_engine(
        engine(posture), 2 ** 31 + 11, TOLERANCE, prompts=PROMPTS, steps=4)
    assert report["passed"], report
    assert len(report["positions"]) == 3 * 5
    assert report["prefill_batches"] == [{"bucket": 512, "rows": 2},
                                         {"bucket": 64, "rows": 1}]
    assert len(report["state_rms_share_by_layer"]) == 3
    assert len(report["first_state_rms_share_by_head"]) == 8
    assert report["routing_decisions"] == 3 * (300 + 290 + 45 + 3 * 4)
    assert report["idle_state_untouched"]
    if posture == "float32":                 # same arithmetic: near exact
        assert report["worst_rms_share"] < 1e-4
        assert report["worst_state_rms_share"] < 1e-4
        assert report["routing_decisions_differing"] == 0
        assert report["worst_routing_shortfall"] < 1e-5


def test_the_check_decodes_in_the_engines_chunks():
    e = engine()
    got = reference.served(e, 3, prompts=((40, 1), (20, 3)), steps=40)
    assert got["facts"]["decode_chunk"] == e.config.decode_chunk < 40
    assert [len(s["sequence"]) for s in got["slots"]] == [80, 60]
    assert reference.judge(e, got, TOLERANCE)["passed"]


def test_the_reference_follows_the_published_equations():
    """One Mamba-2 layer against a second, independent spelling: numpy,
    float64, explicit loops over positions, heads and taps."""
    e = engine()
    c = e.model_config
    w = {k: np.asarray(v[1], np.float64) for k, v in e.params["mamba"].items()}
    rng = np.random.default_rng(3)
    u = rng.normal(size=(11, c.hidden))
    want, state = reference.mamba2(
        jnp.asarray(u, jnp.float32), {k: jnp.asarray(v, jnp.float32)
                                      for k, v in w.items()}, c)
    heads, p, groups, n, k = (c.ssm_heads, c.ssm_head_dim, c.ssm_groups,
                              c.ssm_state, c.conv_kernel)
    z, xbc, dt = u @ w["w_z"], u @ w["w_xbc"], u @ w["w_dt"]
    conv = np.zeros_like(xbc)
    for t in range(11):
        for tap in range(k):
            src = t - (k - 1) + tap
            if src >= 0:
                conv[t] += w["conv_w"][:, tap] * xbc[src]
        conv[t] += w["conv_b"]
    xbc = conv / (1 + np.exp(-conv))
    h = np.zeros((heads, p, n))
    y = np.zeros((11, heads, p))
    for t in range(11):
        for head in range(heads):
            g = head // (heads // groups)
            x_t = xbc[t, head * p:(head + 1) * p]
            B_t = xbc[t, heads * p + g * n: heads * p + (g + 1) * n]
            C_t = xbc[t, heads * p + groups * n + g * n:
                      heads * p + groups * n + (g + 1) * n]
            step = np.log1p(np.exp(dt[t, head] + w["dt_bias"][head]))
            h[head] = np.exp(-step * np.exp(w["A_log"][head])) * h[head] \
                + step * np.outer(x_t, B_t)
            y[t, head] = h[head] @ C_t + w["D"][head] * x_t
    y = y.reshape(11, heads * p) * (z / (1 + np.exp(-z)))
    y = y.reshape(11, groups, -1)
    y = y / np.sqrt((y * y).mean(-1, keepdims=True) + c.norm_eps)
    out = (y.reshape(11, -1) * w["gate_norm"]) @ w["w_out"]
    np.testing.assert_allclose(want, out, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(state, h, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_model_that_computes_something_else_fails(fault):
    """The tolerance is tight enough to see each term dropped: the served
    program against a reference that leaves it out (or, for the three that
    are not in the equations, puts it in)."""
    e = engine("bf16-pallas-read")
    report = reference.judge(
        e, reference.served(e, 5, prompts=PROMPTS, steps=2), TOLERANCE,
        faults=(fault,))
    assert not report["passed"], (fault, report)
    if fault == "no_correction_bias":
        # the reference follows the program's choices, so the logits agree;
        # its own ranking without the bias does not make them
        assert report["first_routing_differing_share"] \
            > 3 * TOLERANCE["first_routing_differing_share"]
        assert report["routing_decisions_differing"] > 20
    else:
        assert report["worst_rms_share"] > 4 * TOLERANCE["rms_share"], fault


def test_a_state_kept_in_bfloat16_reads_worse_than_the_served_one():
    e = engine("bf16-pallas-read")
    how = dict(prompts=((100, 2), (90, 0), (60, 3)), steps=64)
    served = reference.check_engine(e, 17, TOLERANCE, **how)
    lower = reference.check_engine(
        e, 17, TOLERANCE, **how,
        config=dataclasses.replace(e.model_config, state_dtype=jnp.bfloat16))
    assert served["passed"] and served["state_dtype"] == "float32"
    assert lower["state_dtype"] == "bfloat16"
    assert lower["first_state_rms_share"] > 1.15 * served["first_state_rms_share"]


def test_a_router_in_bfloat16_reads_worse_at_the_first_expert_layer():
    """Where the router's input is exact (the float32 posture) its own
    precision is all the first expert layer's audit reads."""
    e = engine()
    tight = dict(TOLERANCE, first_routing_differing_share=2e-3)
    served = reference.check_engine(e, 5, tight, prompts=PROMPTS, steps=4)
    lower = reference.check_engine(
        e, 5, tight, prompts=PROMPTS, steps=4,
        config=dataclasses.replace(e.model_config, router_dtype=jnp.bfloat16))
    assert served["passed"] and served["router_dtype"] == "float32"
    assert lower["router_dtype"] == "bfloat16" and not lower["passed"]
    assert lower["first_routing_differing_share"] > 2e-3
    assert served["first_routing_differing_share"] == 0
    assert lower["worst_rms_share"] < TOLERANCE["rms_share"]
