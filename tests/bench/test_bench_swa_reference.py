"""The served window-and-full layer against its plain reference at the
``trinity-tiny`` preset on the CPU: the comparison a run's ``correct`` rests
on (bench/reference/afmoe.py ``check_engine``), in float32 and in bfloat16
(the type the cell serves; its Pallas read is held to the XLA read and to the
reference in tests/test_swa_model.py, in the interpreter and in float32: five
unrolled layers of the interpreted kernel in two programs take minutes to
build), and its power to see each term of the published equations changed."""

import json
import os

import pytest

from reference import afmoe as reference

HERE = os.path.dirname(__file__)
with open(os.path.join(HERE, "fixtures", "swa", "configs",
                       "trinity-tiny.json")) as f:
    TOLERANCE = json.load(f)["reference_tolerance"]
PROMPTS, STEPS = (100, 200), 48
POSTURES = {
    "float32": dict(model_dtype="float32"),
    "bf16": dict(),
}
_engines, _served = {}, {}


def engine(posture="float32"):
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    if posture not in _engines:
        _engines[posture] = TpuServingEngine(ServingConfig(
            model="trinity-tiny", slots=6, max_seq_len=512, kv_layout="paged",
            kv_block_size=8, prefix_cache=False, prefill_batch=1,
            decode_chunk=8, **POSTURES[posture],
        ))
    return _engines[posture]


def served(posture):
    if posture not in _served:
        _served[posture] = reference.served(
            engine(posture), 2 ** 31 + 11, prompts=PROMPTS, steps=STEPS)
    return _served[posture]


@pytest.mark.parametrize("posture", sorted(POSTURES))
def test_prefill_and_paged_decode_match_the_reference(posture):
    e = engine(posture)
    report = reference.judge(e, served(posture), TOLERANCE)
    assert report["passed"], {k: v for k, v in report.items()
                              if k != "positions"}
    assert len(report["positions"]) == 2 * (STEPS + 1)
    assert report["prefill_batches"] == [{"bucket": 128, "rows": 1},
                                         {"bucket": 256, "rows": 1}]
    assert report["decode_chunk"] == 8 and report["decode_steps"] == STEPS
    # four expert layers, every position of both sequences
    assert report["routing_decisions"] == 4 * (100 + 200 + 2 * STEPS)
    assert report["kernel"] == e.paged_read_kernel
    assert report["router_alone_differing_share"] == 0
    assert report["engine_first_token_shortfall"] == 0
    # the ring of 40 rows holds what was written and not yet overwritten
    assert report["window_rows_compared"] == 2 * 40
    assert report["window_slot_blocks_max"] == report["window_ring_blocks"] == 5
    # the check gave its blocks back, both kinds
    stats = e.block_mgr.stats()
    assert stats["live_blocks"] == 0 and stats["reserved_blocks"] == 0
    if posture == "float32":
        assert report["worst_rms_share"] < 1e-4
        assert report["window_rows_rms_share"] < 1e-5
        assert report["engine_decode_steps_parted"] == 0
        assert report["engine_decode_steps_compared"] == 4 * STEPS


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


def test_an_engine_of_another_family_is_refused_at_once():
    class Other:
        family = "dense"

        class config:
            model = "trinity-large-preview-ep8"

    with pytest.raises(RuntimeError) as e:
        reference.check_engine(Other(), 1, TOLERANCE)
    assert "trinity-large-preview-ep8" in str(e.value)


def test_a_serving_engine_is_refused():
    e = engine()
    e.slots[0].request = object()
    try:
        with pytest.raises(RuntimeError, match="is serving"):
            reference.served(e, 1, prompts=PROMPTS, steps=STEPS)
    finally:
        e.slots[0].request = None
