"""The per-layer readers on made-up observations with known answers, and on
nothing: a reader that finds nothing to read gives nothing."""

import os

import pytest

from lib import observe, peaks, roofline

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "bench")
# the readers a rate cell brings with it live beside the fixture cell
ROOTS = [BENCH, os.path.join(os.path.dirname(__file__), "fixtures")]
LLAMA = {"layers": 2, "hidden": 64, "heads": 4, "kv_heads": 2, "head_dim": 16,
         "intermediate": 128, "vocab_size": 384}
V5E = peaks.peaks_for("TPU v5 lite")


def reader(name):
    return observe.load_metric(observe.find("layer_metrics", name, ROOTS))["read"]


def trace(decode_durations, decode_counts, ops=(), prefill=(), busy=3.0, window=4.0):
    return {
        "devices": 1, "busy_s": busy, "window_s": window,
        "planes": [{
            "device": "/device:TPU:0", "busy_s": busy, "span_s": window,
            "programs": {
                "jit__decode_chunk": {
                    "runs": len(decode_durations),
                    "total_s": sum(decode_durations),
                    "durations_s": list(decode_durations),
                    "op_counts": list(decode_counts),
                },
                "jit__prefill": {
                    "runs": len(prefill), "total_s": sum(prefill),
                    "durations_s": list(prefill), "op_counts": [1] * len(prefill),
                },
            },
            "ops": list(ops), "gaps": {},
        }],
    }


def obs(**kw):
    base = {
        "requests": [], "samples": [], "counters": {}, "trace": None,
        "polls": [{"active": 4, "live_blocks": 12, "used_share": 0.1},
                  {"active": 6, "live_blocks": 18, "used_share": 0.2}],
        "serving": {"decode-chunk": 32, "decode-chunk-light": 8},
        "llama": LLAMA, "paged_read_kernel": "pallas",
        "pool": {"block_size": 16, "num_blocks": 100},
        "shape": roofline.Shape.from_widths(LLAMA, weight_dtype_bytes=2.0,
                                            kv_quantized=False),
        "peaks": V5E,
    }
    return {**base, **kw}


@pytest.mark.parametrize("name", [
    "decode_dev_ms_step", "prefill_dev_ms_p50", "decode_roofline",
    "paged_read_roofline", "device_idle_share",
])
def test_a_trace_reader_without_a_trace_gives_nothing(name):
    assert reader(name)(obs()) is None


def test_decode_step_time_counts_the_steps_the_trace_shows():
    # a whole heavy run (32 steps x 2 layers = 64 calls of the layer body's
    # op) and one cut by the start of the trace to 10 steps
    t = trace([3.2, 1.0], [64, 21])
    assert reader("decode_dev_ms_step")(obs(trace=t)) == pytest.approx(
        1e3 * 4.2 / (32 + 10)
    )
    # a run of which less than one step is visible is left out
    assert reader("decode_dev_ms_step")(obs(trace=trace([0.01], [1]))) is None


def test_prefill_time_is_the_median_run():
    t = trace([], [], prefill=[0.05, 0.2, 0.1])
    assert reader("prefill_dev_ms_p50")(obs(trace=t)) == pytest.approx(100.0)


def test_idle_share():
    assert reader("device_idle_share")(obs(trace=trace([], []))) == pytest.approx(25.0)


def test_decode_roofline_divides_the_floor_by_the_measured_step():
    t = trace([0.32], [64])          # 32 steps in 0.32 s: 10 ms a step
    o = obs(trace=t)
    live_rows = 15 * 16 - 5 * 16 / 2   # mean blocks x block size - half a block a request
    floor = roofline.decode_step_floor(o["shape"], live_rows=live_rows,
                                       batch=5, peaks=V5E)
    assert reader("decode_roofline")(o) == pytest.approx(
        100 * floor["floor_s"] / 0.010
    )
    assert reader("decode_roofline")(obs(trace=t, polls=[])) is None


def test_paged_read_roofline_finds_the_kernel_in_the_decode_program():
    ops = [
        {"name": "closed_call.13_f32_128_16_128_", "program": "jit__decode_chunk",
         "total_s": 0.064, "calls": 64},
        {"name": "closed_call.9_bf16_8_16_2048_128_", "program": "jit__prefill",
         "total_s": 9.0, "calls": 3},        # the flash kernel: another program
        {"name": "fusion.1", "program": "jit__decode_chunk", "total_s": 1.0,
         "calls": 64},
    ]
    o = obs(trace=trace([0.32], [64], ops=ops))
    floor = roofline.paged_read_floor(o["shape"], live_rows=200.0, peaks=V5E)
    assert reader("paged_read_roofline")(o) == pytest.approx(
        100 * floor["floor_s"] / 0.001
    )
    # a posture that reads the pool through XLA has no such kernel
    assert reader("paged_read_roofline")({**o, "paged_read_kernel": "xla"}) is None


def test_a_metric_file_without_an_observation_is_left_out_of_the_report():
    got = observe.report(
        [("queue_wait_ms_p50", "ms"), ("decode_dev_ms_step", "ms"),
         ("preemptions", "count")],
        "layer_metrics", ROOTS,
        obs(requests=[{"queue_wait_ms": 3.0}, {"queue_wait_ms": 5.0}],
            counters={"preemptions": 0.0}),
    )
    assert got == {"queue_wait_ms_p50": {"value": 3.0, "unit": "ms"},
                   "preemptions": {"value": 0.0, "unit": "count"}}
    with pytest.raises(FileNotFoundError):
        observe.report([("no_such_metric", "ms")], "layer_metrics", [BENCH], obs())
