"""The served latent-attention layer against its plain reference at the
``deepseek-tiny`` preset on the CPU: the comparison a run's ``correct`` rests
on (bench/reference/deepseek_v2.py ``check_engine``), in float32 and in the
posture the cell serves (bf16, the latent read in the interpreter), its power
to see each term of the published equations changed, and an independent
spelling of the parts the reference is made of."""

import numpy as np
import pytest

from reference import deepseek_v2 as reference

TOLERANCE = {"rms_share": 0.05, "min_correlation": 0.998,
             "latent_rms_share": 0.01, "routing_margin": 0.25,
             "first_routing_differing_share": 0.1,
             "router_alone_differing_share": 1e-3,
             "engine_first_token_shortfall": 0.25,
             "engine_first_logprob_error": 0.05,
             "engine_decode_token_shortfall": 0.25,
             "engine_decode_logprob_error": 0.05}
# one prompt in the 256 bucket, one in the 64; of the engine's 7 slots 0 and
# 1 hold them, 3 and 4 the same five tokens shorter, 6 ten, 2 and 5 are idle
PROMPTS = (200, 45)
POSTURES = {
    "float32": dict(model_dtype="float32"),
    "bf16-pallas-read": dict(paged_kernel="pallas-interpret"),
}
_engines = {}


def engine(posture="float32"):
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    if posture not in _engines:
        _engines[posture] = TpuServingEngine(ServingConfig(
            model="deepseek-tiny", slots=7, max_seq_len=512, kv_layout="paged",
            kv_block_size=16, prefix_cache=False, prefill_batch=1,
            decode_chunk=8, **POSTURES[posture],
        ))
    return _engines[posture]


@pytest.mark.parametrize("posture", sorted(POSTURES))
def test_prefill_and_paged_decode_match_the_reference(posture):
    e = engine(posture)
    report = reference.check_engine(
        e, 2 ** 31 + 11, TOLERANCE, prompts=PROMPTS, steps=20)
    assert report["passed"], {k: v for k, v in report.items()
                              if k != "positions"}
    assert len(report["positions"]) == 2 * 21
    assert report["prefill_batches"] == [{"bucket": 256, "rows": 1},
                                         {"bucket": 64, "rows": 1}]
    assert report["decode_chunk"] == 8 and report["decode_steps"] == 20
    # two expert layers, every position of both sequences but the last made
    assert report["routing_decisions"] == 2 * (200 + 45 + 2 * 20)
    assert report["kernel"] == e.paged_read_kernel
    assert report["router_alone_differing_share"] == 0
    assert report["engine_first_token_shortfall"] == 0
    # the engine's own decode program: five live slots of its seven, every
    # step of theirs up to the first where the two programs part
    assert (report["slots_live"], report["slots_idle"]) == (5, 2)
    assert report["rows_live"] == 200 + 45 + 195 + 40 + 190
    assert report["decode_window_blocks"] == 512 // 16
    assert report["engine_decode_steps_compared"] <= 5 * 20
    assert report["engine_decode_steps_compared"] >= 5 * 20 - 7 * \
        report["engine_decode_steps_parted"]
    if posture == "float32":                 # same arithmetic: near exact
        assert report["engine_decode_steps_parted"] == 0
        assert report["engine_decode_token_shortfall"] == 0
        assert report["engine_decode_logprob_error"] < 1e-4
        assert report["worst_rms_share"] < 1e-4
        assert report["latent_rms_share"] < 1e-5
        assert report["routing_decisions_differing"] == 0
        assert report["worst_routing_shortfall"] < 1e-5
    else:                                    # a bf16 row is rounded once
        assert 1e-4 < report["latent_rms_share"] < 0.01


def test_the_check_s_sizes_may_ride_in_a_test_size_file_s_limits():
    e = engine()
    report = reference.check_engine(e, 5, {
        **TOLERANCE, "check_prompts": [30], "check_decode_steps": 3})
    assert report["passed"] and report["prompts"] == [30]
    assert len(report["positions"]) == 4
    assert (report["slots_live"], report["slots_idle"]) == (4, 3)


def test_an_engine_of_another_family_under_the_name_is_refused():
    class Dense:
        family = "dense"
        config = type("C", (), {"model": "deepseek-v2-ep8"})

    with pytest.raises(RuntimeError, match="not served by the latent"):
        reference.check_engine(Dense(), 1, TOLERANCE)
    with pytest.raises(RuntimeError, match="not served by the latent"):
        reference.check_engine(
            type("Old", (), {"config": Dense.config})(), 1, TOLERANCE)


@pytest.fixture(scope="module")
def got():
    return reference.served(engine(), 7, prompts=PROMPTS, steps=8)


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_injected_fault_fails_at_least_one_limit(got, fault):
    report = reference.judge(engine(), got, TOLERANCE, faults=(fault,))
    assert not report["passed"], fault
    failed = {
        "rms_share": report["worst_rms_share"] > TOLERANCE["rms_share"],
        "latent_rms_share":
            report["latent_rms_share"] > TOLERANCE["latent_rms_share"],
        "routing_margin":
            report["worst_routing_shortfall"] > TOLERANCE["routing_margin"],
        "router_alone": report["router_alone_differing_share"]
            > TOLERANCE["router_alone_differing_share"],
        "first_routing": report["first_routing_differing_share"]
            > TOLERANCE["first_routing_differing_share"],
    }
    by = {"latent_below_bfloat16": "latent_rms_share",
          "ungrouped_top_k": "router_alone",
          "bfloat16_router": "router_alone"}.get(fault)
    if by:
        assert failed[by], (fault, report)
    assert any(failed.values())


def test_a_router_served_in_bfloat16_is_told_by_the_router_alone(got):
    """The control on the program's side: the served router computing in
    bfloat16, on the reference's own input."""
    e = engine()
    inputs = np.concatenate([
        reference.forward(e.model_config, e.params, slot["sequence"],
                          slot["positions"], forced=slot["chose"])[1]["first_input"]
        for slot in got["slots"]])
    assert reference.router_alone(e, inputs, "float32") == 0
    assert reference.router_alone(e, inputs, "bfloat16") > \
        TOLERANCE["router_alone_differing_share"]


def test_the_parts_follow_the_published_equations_in_a_second_spelling():
    """Rotary, attention and the router against numpy, float64, explicit
    loops over heads and positions."""
    import jax
    import jax.numpy as jnp

    e = engine()
    c = e.model_config
    rng = np.random.default_rng(3)
    T = 6
    u = rng.normal(size=(T, c.hidden))
    w = {k: np.asarray(v[0], np.float64)
         for k, v in e.params["dense"]["attn"].items()}
    with jax.default_matmul_precision("highest"):
        want, rows = reference.attention(
            jnp.asarray(u, jnp.float32),
            {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}, c)

    def norm(x, g):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6) * g

    def rot(x, t):          # (rope_dim,) at position t, as published
        inv = reference.inv_freq(c.rope_dim).astype(np.float64)
        x = np.concatenate([x[0::2], x[1::2]])
        cos = np.cos(np.concatenate([t * inv, t * inv]))
        sin = np.sin(np.concatenate([t * inv, t * inv]))
        half = c.rope_dim // 2
        return x * cos + np.concatenate([-x[half:], x[:half]]) * sin

    c_q = norm(u @ w["w_qa"], w["q_norm"])
    c_kv = norm(u @ w["w_kv_c"], w["kv_norm"])
    k_pe = np.stack([rot((u @ w["w_k_pe"])[t], t) for t in range(T)])
    np.testing.assert_allclose(rows, np.concatenate([c_kv, k_pe], -1),
                               rtol=1e-4, atol=1e-5)
    m = 0.1 * 0.707 * np.log(40.0) + 1
    scale = (c.nope_dim + c.rope_dim) ** -0.5 * m * m
    out = np.zeros((T, c.heads * c.v_dim))
    for h in range(c.heads):
        q_nope = (c_q @ w["w_q_nope"])[:, h * c.nope_dim:(h + 1) * c.nope_dim]
        q_pe = (c_q @ w["w_q_pe"])[:, h * c.rope_dim:(h + 1) * c.rope_dim]
        k_nope, v = c_kv @ w["w_uk"][h].T, c_kv @ w["w_uv"][h]
        for t in range(T):
            s = np.asarray([
                (q_nope[t] @ k_nope[j] + rot(q_pe[t], t) @ k_pe[j]) * scale
                for j in range(t + 1)])
            p = np.exp(s - s.max())
            out[t, h * c.v_dim:(h + 1) * c.v_dim] = (p / p.sum()) @ v[: t + 1]
    np.testing.assert_allclose(want, out @ w["w_o"], rtol=2e-4, atol=2e-5)
    # the router: softmax over all, the best group by its best expert, the
    # top 2 inside it, 16 sigma, not renormalised
    router = np.asarray(e.params["sparse"]["moe"]["router"][0], np.float64)
    with jax.default_matmul_precision("highest"):
        chosen, weights = reference.route(
            jnp.asarray(u, jnp.float32),
            {"router": jnp.asarray(router, jnp.float32)}, c)
    size = c.experts // c.n_group
    for t in range(T):
        z = u[t] @ router
        sigma = np.exp(z - z.max()) / np.exp(z - z.max()).sum()
        best = [sigma[g * size:(g + 1) * size].max() for g in range(c.n_group)]
        keep = sorted(range(c.n_group), key=lambda g: -best[g])[: c.topk_group]
        masked = [s if e_ // size in keep else 0.0
                  for e_, s in enumerate(sigma)]
        own = sorted(range(c.experts), key=lambda i: -masked[i])[
            : c.experts_per_token]
        assert list(np.asarray(chosen[t])) == own
        np.testing.assert_allclose(weights[t], 16 * sigma[own], rtol=1e-4)


def test_the_cell_s_check_fills_three_of_four_slots_in_every_bucket():
    import json
    import os

    with open(os.path.join(os.path.dirname(reference.__file__), os.pardir,
                           "configs", "deepseek-v2-ep8.json")) as f:
        serving = json.load(f)["serving"]
    plan = reference.slot_plan(serving["slots"], reference.CHECK_PROMPTS)
    assert len(plan) == 72 and {slot % 4 for slot, _, _ in plan} == {0, 1, 2}
    assert [size for _, _, size in plan[:3]] == [3000, 6000, 9000]
    assert len({size for _, _, size in plan}) == 72
    for prompt, bucket in enumerate((4096, 8192, 16384)):
        sizes = [size for _, p, size in plan if p == prompt]
        assert len(sizes) == 24
        assert {reference._bucket_of(size) for size in sizes} == {bucket}
    bs = serving["kv-block-size"]
    blocks = sum(-(-(size + reference.CHECK_DECODE_STEPS + 1) // bs)
                 for _, _, size in plan)
    assert blocks < serving["kv-pool-blocks"]
    with pytest.raises(RuntimeError, match="cannot hold"):
        reference.slot_plan(3, reference.CHECK_PROMPTS)


@pytest.mark.parametrize("fault", ["another_slot_s_token", "another_slot_s_rows"])
def test_a_fault_of_the_engine_s_own_decode_program_fails_its_limits(
        monkeypatch, fault):
    """The model's functions are untouched, so every reading but the
    engine's decode program's stays what it was."""
    import jax.numpy as jnp

    e = engine()
    real = e._decode_fn

    def faulty(*key):
        program = real(*key)

        def run(params, pool, cache_v, t0, n, active, tables, *rest):
            if fault == "another_slot_s_token":
                t0 = jnp.roll(t0, 1)
            else:                       # slot 6 reads through slot 3's table
                tables = tables.at[6].set(tables[3])
            return program(params, pool, cache_v, t0, n, active, tables, *rest)

        return run

    monkeypatch.setattr(e, "_decode_fn", faulty)
    report = reference.check_engine(e, 9, TOLERANCE, prompts=PROMPTS, steps=8)
    assert not report["passed"]
    assert report["worst_rms_share"] < 1e-4
    assert report["engine_first_token_shortfall"] == 0
    assert report["engine_decode_token_shortfall"] > \
        TOLERANCE["engine_decode_token_shortfall"] \
        or report["engine_decode_logprob_error"] > \
        TOLERANCE["engine_decode_logprob_error"]


def test_an_engine_that_is_serving_is_refused():
    e = engine()
    e.slots[0].request = object()
    try:
        with pytest.raises(RuntimeError, match="is serving"):
            reference.served(e, 1, prompts=PROMPTS, steps=2)
    finally:
        e.slots[0].request = None
