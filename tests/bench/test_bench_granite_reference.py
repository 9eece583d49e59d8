"""The served ``granitemoehybrid`` layer against its plain reference at the
``granite-tiny`` preset on the CPU: the comparison a run's ``correct`` rests
on (bench/reference/granite_moe_hybrid.py ``check_engine``), in float32 and
in the posture the cell serves (bf16, the Pallas read in the interpreter),
its power to see each term of the published equations changed, and its
further limits' power to see a recurrent state or a router kept in fewer
bits."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from reference import granite_moe_hybrid as reference

# the router's margin is in LOGITS here (a spread of about 1), where the
# nemotron_h check's is in sigmoid scores
TOLERANCE = {"rms_share": 0.06, "min_correlation": 0.999,
             "state_rms_share": 0.02, "routing_margin": 0.3,
             "first_routing_differing_share": 0.03,
             "router_alone_differing_share": 1e-3,
             "engine_first_token_shortfall": 0.25,
             "engine_first_logprob_error": 0.05}
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
            model="granite-tiny", slots=4, max_seq_len=512, kv_layout="paged",
            kv_block_size=16, prefix_cache=False, **POSTURES[posture],
        ))
    return _engines[posture]


@pytest.mark.parametrize("posture", sorted(POSTURES))
def test_prefill_and_paged_decode_match_the_reference(posture):
    """Prompts that are no multiple of the scan chunk (16 here), cross
    several chunks and sit in padded buckets, three slots of unequal length
    and an idle one among them, then 4 steps."""
    e = engine(posture)
    report = reference.check_engine(
        e, 2 ** 31 + 11, TOLERANCE, prompts=PROMPTS, steps=4)
    assert report["passed"], report
    assert len(report["positions"]) == 3 * 5
    assert report["prefill_batches"] == [{"bucket": 512, "rows": 2},
                                         {"bucket": 64, "rows": 1}]
    # state rows by Mamba-2 layers (6 of the 8 layers), heads of the first
    assert len(report["state_rms_share_by_layer"]) == 6
    assert len(report["first_state_rms_share_by_head"]) == 16
    # an expert layer in every one of the 8 layers
    assert report["routing_decisions"] == 8 * (300 + 290 + 45 + 3 * 4)
    assert report["idle_state_untouched"]
    assert report["kernel"] == e.paged_read_kernel
    # the float32 router on the reference's own input: its ranking
    assert report["router_alone_differing_share"] == 0
    # the engine's own prefill programs (the sampler behind the head): the
    # check's batches and one of the engine's prefill-batch rows
    assert report["engine_prefill_batches"] == [
        {"bucket": 512, "rows": 2}, {"bucket": 64, "rows": 1},
        {"bucket": 512, "rows": 4}]
    assert report["engine_first_token_shortfall"] == 0
    assert report["engine_first_logprob_error"] < 1e-2
    assert report["engine_wrong_row_shortfall"] > 1
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


def test_a_dense_engine_under_the_name_is_refused():
    class Dense:
        is_hybrid = False
        config = type("C", (), {"model": "granite-4.0-h-small-ep2"})

    with pytest.raises(RuntimeError, match="not served by the hybrid"):
        reference.check_engine(Dense(), 1, TOLERANCE)


def test_the_layer_s_own_parts_follow_the_published_equations():
    """Attention, the router and the gated expert against a second,
    independent spelling: numpy, float64, explicit loops (the Mamba-2 mixer
    has its own in test_bench_hybrid_reference.py)."""
    e = engine()
    c = e.model_config
    rng = np.random.default_rng(3)
    u = rng.normal(size=(7, c.hidden))
    f64 = lambda tree, i: {  # noqa: E731
        k: np.asarray(v[i], np.float64) for k, v in tree.items()}
    as32 = lambda w: {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}  # noqa: E731
    # attention: 4 / 2 heads of 16, no rotary, scores x 0.0078125
    w = f64(e.params["attn"], 1)
    want = np.asarray(reference.attention(jnp.asarray(u, jnp.float32), as32(w), c))
    G, d = c.heads // c.kv_heads, c.head_dim
    q, k, v = u @ w["wq"], u @ w["wk"], u @ w["wv"]
    out = np.zeros((7, c.heads * d))
    for t in range(7):
        for head in range(c.heads):
            kv = head // G
            s = np.asarray([
                q[t, head * d:(head + 1) * d] @ k[j, kv * d:(kv + 1) * d]
                for j in range(t + 1)]) * 0.0078125
            p = np.exp(s - s.max())
            p /= p.sum()
            out[t, head * d:(head + 1) * d] = sum(
                p[j] * v[j, kv * d:(kv + 1) * d] for j in range(t + 1))
    np.testing.assert_allclose(want, out @ w["wo"], rtol=2e-4, atol=2e-5)
    # the experts: top 3 of 8 logits, softmax over those three, the held
    # half's gated experts and the shared one
    w = f64(e.params["moe"], 2)
    got, chosen = reference.experts(jnp.asarray(u, jnp.float32), as32(w), c)
    width, shared = c.intermediate, c.shared_intermediate
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731
    out = np.zeros((7, c.hidden))
    for t in range(7):
        logits = u[t] @ w["router"]
        top = np.argsort(-logits)[: c.experts_per_token]
        assert list(np.asarray(chosen)[t]) == list(top)
        gate = np.exp(logits[top] - logits[top].max())
        gate /= gate.sum()
        for g, expert in zip(gate, top):
            if c.expert_first <= expert < c.expert_first + c.experts_held:
                ab = w["w_up"][expert - c.expert_first] @ u[t]
                out[t] += g * (silu(ab[:width]) * ab[width:]) \
                    @ w["w_down"][expert - c.expert_first]
        ab = u[t] @ w["ws_up"]
        out[t] += (silu(ab[:shared]) * ab[shared:]) @ w["ws_down"]
    np.testing.assert_allclose(got, out, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_model_that_computes_something_else_fails(fault):
    """The tolerance is tight enough to see each term changed: the served
    program against a reference that leaves it out, or takes the other
    family's rule in its place."""
    e = engine("bf16-pallas-read")
    report = reference.judge(
        e, reference.served(e, 5, prompts=PROMPTS, steps=2), TOLERANCE,
        faults=(fault,))
    assert not report["passed"], (fault, report)
    assert report["worst_rms_share"] > 2.5 * TOLERANCE["rms_share"], fault


@pytest.mark.parametrize("what, posture, reading", [
    # the first Mamba-2 layer's state is what state_rms_share holds
    ("state_dtype", "bf16-pallas-read", "first_state_rms_share"),
    # where the router's input is exact (the float32 posture) its own
    # precision is all the first expert layer's audit reads
    ("router_dtype", "float32", "first_routing_differing_share"),
])
def test_a_state_or_a_router_in_bfloat16_reads_worse_than_the_served_one(
        what, posture, reading):
    e = engine(posture)
    if what == "state_dtype":
        # 64 steps at hidden 64 in bf16 read what the fixture's file allows
        seed, limits = 17, dict(TOLERANCE, rms_share=0.12, min_correlation=0.995)
        how = dict(prompts=((100, 2), (90, 0), (60, 3)), steps=64)
    else:
        seed, limits = 5, dict(TOLERANCE, first_routing_differing_share=1e-3,
                               router_alone_differing_share=1.0)
        how = dict(prompts=PROMPTS, steps=4)
    served = reference.check_engine(e, seed, limits, **how)
    lower = reference.check_engine(
        e, seed, limits, **how,
        config=dataclasses.replace(e.model_config, **{what: jnp.bfloat16}))
    assert served["passed"] and served[what] == "float32"
    assert lower[what] == "bfloat16"
    if what == "state_dtype":
        assert lower[reading] > 1.15 * served[reading]
    else:
        assert not lower["passed"]
        assert lower[reading] > 1e-3 and served[reading] == 0
        assert lower["worst_rms_share"] < TOLERANCE["rms_share"]


def test_the_router_alone_is_told_from_one_in_bfloat16_in_the_served_posture():
    """In the posture the cell serves the activations' rounding moves the
    first layer's choices as much as a bfloat16 router would; the router on
    the reference's own input is moved by its own precision alone."""
    e = engine("bf16-pallas-read")
    how = dict(prompts=PROMPTS, steps=4)
    served = reference.check_engine(e, 5, TOLERANCE, **how)
    lower = reference.check_engine(
        e, 5, TOLERANCE, **how,
        config=dataclasses.replace(e.model_config, router_dtype=jnp.bfloat16))
    assert served["passed"] and served["router_alone_differing_share"] == 0
    assert served["first_routing_differing_share"] > 0
    assert not lower["passed"]
    assert lower["router_alone_differing_share"] \
        > TOLERANCE["router_alone_differing_share"]
    only = dict(TOLERANCE, router_alone_differing_share=1.0)
    assert reference.judge(e, reference.served(
        e, 5, config=dataclasses.replace(
            e.model_config, router_dtype=jnp.bfloat16), **how), only)["passed"]


def test_the_engines_own_prefill_programs_are_held_to_the_served_logits():
    """A token that is not the best of the logits ``served`` read for its
    prompt, or a program that does not return, fails the check; of the pool
    it writes the scratch block alone."""
    e = engine()
    got = reference.served(e, 7, prompts=PROMPTS, steps=2)
    before = np.asarray(e.cache_k[:, 1:]).copy()
    report = reference.engine_prefill(e, got)
    assert report["engine_first_token_shortfall"] == 0
    np.testing.assert_array_equal(np.asarray(e.cache_k[:, 1:]), before)
    # the logits of another prompt in their place: the token is not theirs
    swapped = dict(got, slots=[
        dict(slot, logits=got["slots"][(i + 1) % 3]["logits"])
        for i, slot in enumerate(got["slots"])])
    wrong = reference.engine_prefill(e, swapped)
    assert wrong["engine_first_token_shortfall"] \
        > TOLERANCE["engine_first_token_shortfall"]
    assert not reference.judge(e, swapped, dict(TOLERANCE, rms_share=10.0,
                                                 min_correlation=-1.0))["passed"]
