"""The window-and-full family's second member against its plain reference at
the ``mellum-tiny`` preset on the CPU: the comparison a run's ``correct``
rests on (bench/reference/mellum.py ``check_engine``), in float32 and in
bfloat16 (the type the cell serves), with a slot that stays under the window,
one that passes it and wraps its ring while it decodes and one far past both
in ONE batch, and its power to see each term of the published equations
changed."""

import json
import os

import pytest

from reference import mellum as reference

HERE = os.path.dirname(__file__)
with open(os.path.join(HERE, "fixtures", "wf", "configs",
                       "mellum-tiny.json")) as f:
    TOLERANCE = json.load(f)["reference_tolerance"]
PROMPTS, STEPS = (6, 28, 100), 24
POSTURES = {
    "float32": dict(model_dtype="float32"),
    "bf16": dict(),
}
_engines, _served = {}, {}


def engine(posture="float32"):
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    if posture not in _engines:
        _engines[posture] = TpuServingEngine(ServingConfig(
            model="mellum-tiny", slots=8, max_seq_len=512, kv_layout="paged",
            kv_block_size=8, prefix_cache=False, prefill_batch=1,
            decode_chunk=8, **POSTURES[posture],
        ))
    return _engines[posture]


def served(posture):
    if posture not in _served:
        _served[posture] = reference.served(
            engine(posture), 2 ** 31 + 11, prompts=PROMPTS, steps=STEPS)
    return _served[posture]


def test_the_fixture_states_the_check_s_sizes_and_rotation():
    assert tuple(TOLERANCE["check_prompts"]) == PROMPTS
    assert TOLERANCE["check_decode_steps"] == STEPS
    rope = TOLERANCE["check_rope_parameters"]
    assert rope["full_attention"]["original_max_position_embeddings"] == 64
    assert set(rope) == set(reference.ROPE)


@pytest.mark.parametrize("posture", sorted(POSTURES))
def test_prefill_and_paged_decode_match_the_reference(posture):
    e = engine(posture)
    report = reference.judge(e, served(posture), TOLERANCE)
    assert report["passed"], {k: v for k, v in report.items()
                              if k != "positions"}
    assert len(report["positions"]) == 3 * (STEPS + 1)
    assert report["prefill_batches"] == [{"bucket": 32, "rows": 1},
                                         {"bucket": 32, "rows": 1},
                                         {"bucket": 128, "rows": 1}]
    assert report["decode_chunk"] == 8 and report["decode_steps"] == STEPS
    # two periods of three live slots and an idle one
    assert report["slots_live"] == 6 and report["slots_idle"] == 2
    # eight expert layers, every position of the three sequences
    assert report["routing_decisions"] == 8 * (6 + 28 + 100 + 3 * STEPS)
    assert report["kernel"] == e.paged_read_kernel
    assert report["router_alone_differing_share"] == 0
    assert report["engine_first_token_shortfall"] == 0
    # the short slot's ring holds all its 30 rows, the others a ring of 40
    assert report["window_rows_compared"] == 30 + 2 * 40
    assert report["window_slot_blocks_max"] == report["window_ring_blocks"] == 5
    # every expert is held: a token's two choices are both here
    assert report["held_pairs_a_token_decode"] == [2.0] * 8
    stats = e.block_mgr.stats()
    assert stats["live_blocks"] == 0 and stats["reserved_blocks"] == 0
    if posture == "float32":
        assert report["worst_rms_share"] < 1e-4
        assert report["window_rows_rms_share"] < 1e-5
        assert report["engine_decode_steps_parted"] == 0
        assert report["engine_decode_steps_compared"] == 6 * STEPS


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_check_sees_each_term_changed(fault):
    """The float32 program against the reference with one term of the
    published equations changed: not passed, by at least one limit."""
    report = reference.judge(engine(), served("float32"), TOLERANCE, (fault,))
    assert not report["passed"], fault
    failed = [k for k, limit in (
        ("worst_rms_share", TOLERANCE["rms_share"]),
        ("window_rows_rms_share", TOLERANCE["window_rows_rms_share"]),
        ("worst_routing_shortfall", TOLERANCE["routing_margin"]),
        ("first_routing_differing_share",
         TOLERANCE["first_routing_differing_share"]),
        ("router_alone_differing_share",
         TOLERANCE["router_alone_differing_share"]),
    ) if report[k] > limit]
    assert failed or report["worst_correlation"] < TOLERANCE["min_correlation"]
    if fault == "rows_below_bfloat16":
        assert "window_rows_rms_share" in failed
    if fault == "bfloat16_router":
        assert failed == ["router_alone_differing_share"]
    if fault in ("no_yarn_on_full", "no_attention_factor",
                 "yarn_ramp_unrounded"):
        # a full layer's rotation leaves the first WINDOW layer's rows alone
        assert "window_rows_rms_share" not in failed


def test_nothing_is_listed_as_unobservable_and_the_list_is_the_issue_s():
    assert reference.UNOBSERVABLE == ()
    assert set(reference.FAULTS) >= {
        "no_yarn_on_full", "no_attention_factor", "yarn_on_window",
        "window_one_block_short", "window_one_block_long", "no_qk_norm",
        "weights_not_renormalised", "ninth_expert", "yarn_ramp_unrounded"}


@pytest.mark.parametrize("other", ["dense", "swa-gated"])
def test_an_engine_of_another_family_or_member_is_refused_at_once(other):
    class Other:
        family = "dense" if other == "dense" else "swa"

        class config:
            model = "mellum2-12b-a2.5b-8l"

        class model_config:
            output_gate = True
            experts = experts_held = 8

    with pytest.raises(RuntimeError) as e:
        reference.check_engine(Other(), 1, TOLERANCE)
    assert "mellum2-12b-a2.5b-8l" in str(e.value)


def test_a_serving_engine_is_refused():
    e = engine()
    e.slots[0].request = object()
    try:
        with pytest.raises(RuntimeError, match="is serving"):
            reference.served(e, 1, prompts=PROMPTS, steps=STEPS)
    finally:
        e.slots[0].request = None


def test_the_plan_keeps_the_first_periods_alone():
    plan = reference.slot_plan(192, reference.CHECK_PROMPTS)
    assert len(plan) == 3 * reference.CHECK_PERIODS
    assert plan[:3] == [(0, 0, 300), (1, 1, 1000), (2, 2, 7000)]
    assert plan[3] == (4, 0, 295) and max(s for s, _, _ in plan) < 64
    # full-kind blocks of the plan with its decode steps: under a third of
    # the cell's pool
    blocks = sum(-(-(size + reference.CHECK_DECODE_STEPS + 1) // 64)
                 for _, _, size in plan)
    assert blocks < 7200 / 3
