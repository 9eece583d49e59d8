"""The EVA family against its plain reference at the ``evabyte-tiny`` preset
on the CPU: the comparison a run's ``correct`` rests on
(bench/reference/evabyte.py ``check_engine``), in float32 and in bfloat16
(the type the cell serves), with slots that end inside the first window, one
position before and one after a window's edge, mid-chunk and past three
windows in ONE batch, every one crossing chunk edges and most a window's
while they decode; and its power to see each term of the layer changed."""

import json
import os

import pytest

from reference import evabyte as reference

HERE = os.path.dirname(__file__)
with open(os.path.join(HERE, "fixtures", "eva", "configs",
                       "evabyte-tiny.json")) as f:
    TOLERANCE = json.load(f)["reference_tolerance"]
PROMPTS, STEPS = (6, 31, 33, 50, 110), 24
POSTURES = {
    "float32": dict(model_dtype="float32"),
    "bf16": dict(),
}
LIMITS = (
    ("worst_rms_share", "rms_share"), ("mean_rms_share", "mean_rms_share"),
    ("heads_rms_share", "heads_rms_share"),
    ("ring_rows_rms_share", "ring_rows_rms_share"),
    ("summary_rows_rms_share", "summary_rows_rms_share"),
    ("logits_bfloat16_grid_share", "logits_bfloat16_grid_share"))
#: a residual rounded to bfloat16 moves the float32 program's logits by 0.6%
#: at hidden 64: under the limits the bfloat16 posture needs, over limits a
#: fortieth of them. At the published widths the cell's own
#: ``mean_rms_share`` tells it (``bench/configs/evabyte-6.5b-8l.json``)
NEEDS_TIGHT_LIMITS = ("bfloat16_residual",)
_engines, _served = {}, {}


def engine(posture="float32"):
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    if posture not in _engines:
        _engines[posture] = TpuServingEngine(ServingConfig(
            model="evabyte-tiny", slots=12, max_seq_len=256, kv_layout="paged",
            kv_block_size=8, prefix_cache=False, prefill_batch=1,
            decode_chunk=8, **POSTURES[posture],
        ))
    return _engines[posture]


def served(posture):
    if posture not in _served:
        _served[posture] = reference.served(
            engine(posture), 2 ** 31 + 11, prompts=PROMPTS, steps=STEPS)
    return _served[posture]


def test_the_fixture_states_the_check_s_sizes():
    assert tuple(TOLERANCE["check_prompts"]) == PROMPTS
    assert TOLERANCE["check_decode_steps"] == STEPS


@pytest.mark.parametrize("posture", sorted(POSTURES))
def test_prefill_and_paged_decode_match_the_reference(posture):
    e = engine(posture)
    report = reference.judge(e, served(posture), TOLERANCE)
    assert report["passed"], {k: v for k, v in report.items()
                              if k != "positions"}
    assert len(report["positions"]) == 5 * (STEPS + 1)
    assert report["decode_chunk"] == 8 and report["decode_steps"] == STEPS
    # two periods of five live slots and an idle one
    assert report["slots_live"] == 10 and report["slots_idle"] == 2
    assert report["kernel"] == e.paged_read_kernel
    assert report["engine_first_token_shortfall"] == 0
    # 6 + 24 stays in the first window; the four others cross one edge, and
    # three of the second period's do (1 + 26..28, 45, 105 + 24 rows)
    assert report["window_edges_crossed"] == 7
    # the engine's own program runs every live slot, the crossing ones
    # too, and its steps across an edge are compared like any other
    assert report["engine_decode_steps_compared"] >= 10 * STEPS - 8
    assert report["engine_decode_steps_across_an_edge"] >= 7 * 8 - 8
    # every slot closes six chunks of 4 in 24 steps
    assert report["chunk_closes"] == 10 * 6
    # the open window's rows of each followed slot, and 8 summary rows a
    # closed window: 0 + 1 + 1 + 2 + 4 windows
    assert report["ring_rows_compared"] == 30 + 23 + 25 + 10 + 6
    assert report["summary_rows_compared"] == 8 * (0 + 1 + 1 + 2 + 4)
    stats = e.block_mgr.stats()
    assert stats["live_blocks"] == 0 and stats["reserved_blocks"] == 0
    assert report["logits_bfloat16_grid_share"] < 0.05
    if posture == "float32":
        assert report["worst_rms_share"] < 1e-4
        assert report["ring_rows_rms_share"] < 1e-5
        assert report["summary_rows_rms_share"] < 1e-5
        assert report["engine_decode_steps_parted"] == 0


def test_a_program_of_the_engine_wrong_only_at_an_edge_is_not_passed(monkeypatch):
    """The engine's own decode program with the slots whose window closes in
    a chunk left where they were (what the check itself once asked of it):
    the model's function beside it is right, so every limit on the logits
    and the rows holds, and the engine's tokens across the edge do not."""
    import jax.numpy as jnp

    e = engine()
    W, real = e.model_config.window, e._decode_fn

    def stuck_at_the_edge(mode, window, k):
        fn = real(mode, window, k)

        def call(params, cache_k, cache_v, state, t0, n, active, *rest):
            crosses = (n + k - 1) // W > jnp.maximum(n - 1, 0) // W
            return fn(params, cache_k, cache_v, state, t0, n,
                      active & ~crosses, *rest)
        return call

    monkeypatch.setattr(e, "_decode_fn", stuck_at_the_edge)
    got = reference.served(e, 2 ** 31 + 11, prompts=PROMPTS, steps=STEPS)
    report = reference.judge(e, got, TOLERANCE)
    assert not report["passed"]
    assert all(report[k] <= TOLERANCE[limit] for k, limit in LIMITS)
    assert (report["engine_decode_token_shortfall"]
            > TOLERANCE["engine_decode_token_shortfall"])
    assert report["engine_decode_steps_across_an_edge"] < 7 * 8 - 8


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_check_sees_each_term_changed(fault):
    """The float32 program against the reference with one term of the layer
    changed: not passed, by at least one limit."""
    tolerance = dict(TOLERANCE)
    if fault in NEEDS_TIGHT_LIMITS:
        tolerance.update({limit: TOLERANCE[limit] / 40 for _, limit in LIMITS[:5]})
        assert reference.judge(engine(), served("float32"), tolerance)["passed"]
    report = reference.judge(engine(), served("float32"), tolerance, (fault,))
    assert not report["passed"], fault
    failed = [k for k, limit in LIMITS if report[k] > tolerance[limit]]
    assert failed or report["worst_correlation"] < tolerance["min_correlation"]
    if fault == "rows_below_bfloat16":
        assert {"ring_rows_rms_share", "summary_rows_rms_share"} <= set(failed)
    if fault in ("no_mu", "no_phi", "weighted_summary_key", "chunk_of_15",
                 "chunk_of_17", "chunk_softmax_norm_term"):
        # a summary's making leaves the exact rows alone
        assert "summary_rows_rms_share" in failed
        assert "ring_rows_rms_share" not in failed
    if fault == "bfloat16_logits":
        # 320 logits rounded to bfloat16: no RMS share tells 0.2%, the grid does
        assert failed == ["logits_bfloat16_grid_share"]
        assert report["logits_bfloat16_grid_share"] == 1.0
    if fault in ("sliding_window", "own_window_seen_twice", "two_softmaxes",
                 "summaries_a_window_early", "window_one_block_short",
                 "head_1_served", "bfloat16_logits"):
        # what a query sees leaves what the pools hold of layer 0 alone
        assert not {"ring_rows_rms_share", "summary_rows_rms_share"} & set(failed)


def test_what_no_comparison_can_hold_is_listed_with_the_issue_s_faults():
    assert set(reference.UNOBSERVABLE) == {
        "head_columns_byte_major", "release_token_ids"}
    assert set(reference.FAULTS) >= {
        "weights_below_bfloat16", "rows_below_bfloat16", "bfloat16_residual",
        "bfloat16_logits", "no_mu", "no_phi", "weighted_summary_key",
        "chunk_softmax_norm_term", "chunk_of_15", "chunk_of_17",
        "window_one_block_short", "window_one_block_long", "sliding_window",
        "own_window_seen_twice", "summaries_a_window_early", "two_softmaxes",
        "norm_without_unit_offset", "head_1_served"}


@pytest.mark.parametrize("other", ["dense", "swa"])
def test_an_engine_of_another_family_is_refused_at_once(other):
    class Other:
        family = other

        class config:
            model = "evabyte-6.5b-8l"

    with pytest.raises(RuntimeError) as e:
        reference.check_engine(Other(), 1, TOLERANCE)
    assert "evabyte-6.5b-8l" in str(e.value)


def test_a_serving_engine_is_refused():
    e = engine()
    e.slots[0].request = object()
    try:
        with pytest.raises(RuntimeError, match="is serving"):
            reference.served(e, 1, prompts=PROMPTS, steps=STEPS)
    finally:
        e.slots[0].request = None


def test_the_cell_s_check_crosses_what_the_issue_names():
    """Prompts inside the first window, one before and one after an edge,
    mid-chunk 86 steps before the second edge, and past thirteen windows; in
    24 slots four periods, with room in the pools."""
    prompts, steps = reference.CHECK_PROMPTS, reference.CHECK_DECODE_STEPS
    assert prompts == (1500, 2047, 2049, 4010, 28003) and steps >= 160
    assert prompts[3] % 16 and (4096 - prompts[3]) % 32
    assert prompts[4] // 2048 == 13
    plan = reference.slot_plan(24, prompts)
    assert len(plan) == 20 and plan[:2] == [(0, 0, 1500), (1, 1, 2047)]
    blocks = sum(2 * ((size + steps) // 2048) for _, _, size in plan)
    assert blocks < 480 / 3
