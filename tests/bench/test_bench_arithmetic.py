"""Percentiles, spreads, per-request times and the roofline arithmetic."""

import pytest

from lib import observe, peaks, roofline, stats


@pytest.mark.parametrize("values,q,want", [
    ([], 50, None),
    ([5.0], 95, 5.0),
    ([1, 2, 3, 4], 50, 2.0),
    ([1, 2, 3, 4, 5], 50, 3.0),
    (list(range(1, 101)), 95, 95.0),
    (list(range(1, 201)), 95, 190.0),
    ([3, 1, 2], 100, 3.0),
])
def test_percentile_is_nearest_rank(values, q, want):
    assert stats.percentile(values, q) == want


@pytest.mark.parametrize("name,want", [
    ("p50", 2.0), ("mean", 2.5), ("max", 4.0), ("min", 1.0), ("sum", 10.0),
    ("count", 4.0),
])
def test_stat_by_name(name, want):
    assert stats.stat([1, 2, 3, 4, None], name) == want
    assert stats.stat([], "count") == 0.0
    assert stats.stat([], "p50") is None
    with pytest.raises(ValueError):
        stats.stat([1], "median-ish")


def test_spread_is_the_interquartile_distance_over_the_median():
    import statistics

    values = [100, 101, 102, 103, 104, 110]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.iqr_share(values) == pytest.approx(
        (q3 - q1) / statistics.median(values)
    )
    assert stats.iqr_share([1.0]) is None


def test_per_request_times():
    open_r = {"due": 10.0, "sent": 10.002, "first": 10.5, "last": 12.5,
              "tokens": 41, "engine_ttft_ms": 470.0}
    closed_r = {"due": None, "sent": 20.0, "first": 20.25, "last": 20.25,
                "tokens": 1}
    observe.annotate([open_r], "open")
    observe.annotate([closed_r], "closed")
    assert open_r["ttft_ms"] == pytest.approx(500.0)     # from when it was due
    assert open_r["late_ms"] == pytest.approx(2.0)
    assert open_r["tpot_ms"] == pytest.approx(50.0)      # 2 s over 40 gaps
    assert open_r["hop_ms"] == pytest.approx(498.0 - 470.0)
    assert closed_r["ttft_ms"] == pytest.approx(250.0)
    assert "tpot_ms" not in closed_r and "late_ms" not in closed_r


def test_the_engine_is_held_to_the_posture_its_file_states():
    from types import SimpleNamespace

    import run

    def engine(quantize, kv_quantize, kernel):
        return SimpleNamespace(
            config=SimpleNamespace(quantize=quantize, kv_quantize=kv_quantize),
            paged_read_kernel=kernel)

    int8 = {"serving": {"quantize": "int8", "kv-quantize": "int8"},
            "selects": {"paged_read_kernel": "xla"}}
    bf16 = {"serving": {}, "selects": {"paged_read_kernel": "pallas"}}
    assert run.posture_differs(engine("int8", "int8", "xla"), int8) == {}
    assert run.posture_differs(engine(None, None, "pallas"), bf16) == {}
    # a bf16 configuration served with an int8 pool through the gather
    assert run.posture_differs(engine(None, "int8", "xla"), bf16) == {
        "kv-quantize": {"file": None, "engine": "int8"},
        "paged_read_kernel": {"file": "pallas", "engine": "xla"},
    }
    assert set(run.posture_differs(engine(None, "int8", "xla"), int8)) == {"quantize"}


def test_read_spec_filters_and_scales():
    obs = {"samples": [{"phase": "decode", "host_ms": 2.0},
                       {"phase": "prefill", "host_ms": 9.0},
                       {"phase": "decode", "host_ms": 4.0}],
           "counters": {"preemptions": 3.0}}
    assert observe.read_spec({"from": "samples", "phase": "decode",
                              "field": "host_ms", "stat": "max",
                              "scale": 10}, obs) == 40.0
    assert observe.read_spec({"from": "counters", "field": "preemptions"}, obs) == 3.0
    assert observe.read_spec({"from": "polls", "field": "active",
                              "stat": "mean"}, obs) is None


MISTRAL = {"layers": 32, "hidden": 4096, "heads": 32, "kv_heads": 8,
           "head_dim": 128, "intermediate": 14336, "vocab_size": 32768}
V5E = peaks.peaks_for("TPU v5 lite")


def test_shape_counts_the_published_parameters():
    shape = roofline.Shape.from_widths(MISTRAL, weight_dtype_bytes=1.0,
                                       kv_quantized=True)
    assert shape.param_count == pytest.approx(7.25e9, rel=0.005)
    assert shape.kv_row_bytes == 132
    bf16 = roofline.Shape.from_widths(MISTRAL, weight_dtype_bytes=2.0,
                                      kv_quantized=False)
    assert bf16.weight_bytes == 2 * bf16.param_count and bf16.kv_row_bytes == 256


def test_decode_floor_is_bytes_bound_and_grows_with_the_cache():
    shape = roofline.Shape.from_widths(MISTRAL, weight_dtype_bytes=1.0,
                                       kv_quantized=True)
    empty = roofline.decode_step_floor(shape, live_rows=0, batch=64, peaks=V5E)
    full = roofline.decode_step_floor(shape, live_rows=64 * 450, batch=64,
                                      peaks=V5E)
    assert empty["bound_by"] == "bytes"
    # 7.1e9 weight bytes (the embedding is gathered, not streamed) at 819 GB/s
    assert empty["floor_s"] == pytest.approx(7.1e9 / 819e9, rel=0.02)
    kv = 32 * 64 * 450 * 8 * 132 * 2
    assert full["bytes"] - empty["bytes"] == pytest.approx(kv)
    assert full["floor_s"] > empty["floor_s"]


def test_paged_read_floor_is_the_live_rows_once():
    shape = roofline.Shape.from_widths(
        {**MISTRAL, "layers": 24, "hidden": 2048, "heads": 16,
         "intermediate": 8192, "vocab_size": 92544},
        weight_dtype_bytes=2.0, kv_quantized=False)
    floor = roofline.paged_read_floor(shape, live_rows=40000, peaks=V5E)
    assert floor["bytes"] == 40000 * 8 * 256 * 2
    assert floor["flops"] == 4 * 16 * 128 * 40000
    assert floor["bound_by"] == "bytes"
    assert floor["floor_s"] == pytest.approx(floor["bytes"] / 819e9)


def test_an_unknown_device_is_an_error_not_a_default():
    with pytest.raises(peaks.UnknownDevice, match="TPU v9"):
        peaks.peaks_for("TPU v9")


def test_tokens_inside_spreads_a_request_over_its_stream():
    window = (100.0, 110.0)
    requests = [
        {"tokens": 11, "first": 101.0, "last": 106.0},    # wholly inside: 11
        {"tokens": 11, "first": 95.0, "last": 105.0},     # half of its 10 later tokens
        {"tokens": 21, "first": 108.0, "last": 118.0},    # first token + 2 s of 10 s
        {"tokens": 1, "first": 109.0, "last": 109.0},     # one token, inside
        {"tokens": 5, "first": 90.0, "last": 99.0},       # before the window
        {"tokens": None, "first": 101.0, "last": 102.0},  # never finished: unknown
        {"tokens": 41, "first": 90.0, "last": 130.0},     # across the whole window
    ]
    assert observe.tokens_inside(requests, *window) == pytest.approx(
        11 + 5 + (1 + 20 * 0.2) + 1 + 0 + 0 + 40 * 10 / 40
    )
