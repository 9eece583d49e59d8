"""The trace reduction against a small hand-written trace whose answers are
worked out in the fixture's own comments."""

import os

import pytest

from lib import xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "mini.xplane.txt")


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    with open(FIXTURE) as f:
        text = "\n".join(l for l in f.read().splitlines() if not l.startswith("#"))
    return xplane.reduce(ProfileData.from_text_proto(text), window_s=10e-6)


def test_only_device_planes_are_reduced(reduced):
    assert reduced["devices"] == 1
    assert reduced["planes"][0]["device"] == "/device:TPU:0"


def test_busy_is_the_union_of_op_intervals(reduced):
    assert reduced["busy_s"] == pytest.approx(8e-6)
    assert reduced["window_s"] == pytest.approx(10e-6)


@pytest.mark.parametrize("host_window_s,want", [
    (None, 10e-6),    # no host clock: the span of the operations
    (4e-6, 10e-6),    # the profiler collected past its stop call: the span
    (12e-6, 12e-6),   # the device idled at an edge: the host's clock
])
def test_busy_never_passes_the_window(host_window_s, want):
    from jax.profiler import ProfileData

    with open(FIXTURE) as f:
        text = "\n".join(l for l in f.read().splitlines() if not l.startswith("#"))
    out = xplane.reduce(ProfileData.from_text_proto(text), window_s=host_window_s)
    assert out["window_s"] == pytest.approx(want)
    assert 0 < out["busy_s"] <= out["window_s"]


def test_programs_are_pooled_by_name_without_the_id(reduced):
    decode = xplane.program(reduced, "decode_chunk")
    assert decode["runs"] == 2
    assert decode["total_s"] == pytest.approx(6e-6)
    assert sorted(decode["durations_s"]) == pytest.approx([2e-6, 4e-6])
    # the most frequent op of each run (fusion.1, twice a run)
    assert decode["op_counts"] == [2, 2]
    assert xplane.program(reduced, "prefill")["runs"] == 1


def test_ops_carry_shape_and_program(reduced):
    kernel = xplane.ops_in(reduced, "decode_chunk", r"closed_call")
    assert kernel["calls"] == 2
    assert kernel["total_s"] == pytest.approx(3e-6)
    assert kernel["names"] == ["closed_call.13_f32_128_16_128_"]
    assert xplane.ops_in(reduced, "prefill", r"closed_call")["calls"] == 0
    top = xplane.top_ops(reduced, 2)
    assert top[0][0] == "closed_call.13_f32_128_16_128_"
    assert top[0][1] == pytest.approx(3e-6)
    assert top[1] == ["fusion.1_bf16_64_4096_", pytest.approx(2.5e-6)]
    assert not any("while" in name for name, _ in xplane.top_ops(reduced, 10))


def test_idle_gaps_are_named_by_their_neighbours(reduced):
    gaps = dict((name, s) for name, s in xplane.top_gaps(reduced))
    assert gaps == {
        "jit__decode_chunk_-_jit__prefill__x1": pytest.approx(1e-6),
        "jit__prefill_-_jit__decode_chunk__x1": pytest.approx(1e-6),
    }
    assert sum(gaps.values()) + reduced["busy_s"] == pytest.approx(10e-6)


@pytest.mark.parametrize("intervals,total", [
    ([], 0.0),
    ([(0, 1), (2, 3)], 2.0),
    ([(0, 2), (1, 3)], 3.0),
    ([(0, 10), (2, 3), (9, 12)], 12.0),
])
def test_union_seconds(intervals, total):
    assert xplane.union_seconds(intervals) == pytest.approx(total)


def test_a_trace_without_device_ops_reduces_to_nothing():
    from jax.profiler import ProfileData

    empty = ProfileData.from_text_proto('planes { id: 1 name: "/host:CPU" }')
    out = xplane.reduce(empty, window_s=1.0)
    assert out["devices"] == 0 and out["busy_s"] == 0.0
    assert xplane.top_ops(out) == [] and xplane.top_gaps(out) == []


@pytest.mark.parametrize("event,stats,want", [
    ("%fusion.269 = bf16[64,4096]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[64] %x)", {},
     "fusion.269_bf16_64_4096_"),
    ("%broadcast_select_fusion.7 = (s8[1,64,32,64,1024]{4,3,2,1,0}, s8[1]{0}) fusion()",
     {}, "broadcast_select_fusion.7_s8_1_64_32_64_1024_"),
    ("%while.34 = (s32[]{:T(128)}, bf16[64,4096]{1,0}) while(%t)", {}, "while.34_s32_"),
    ("closed_call.13", {"shape_with_layout": "f32[128,16,128]{2,1,0}"},
     "closed_call.13_f32_128_16_128_"),
    ("dot_general.7", {}, "dot_general.7"),
])
def test_op_names_are_short_and_carry_the_result_shape(event, stats, want):
    assert xplane.op_name(event, stats) == want


def test_containers_are_not_operations():
    assert xplane.is_container("while.34_s32_")
    assert xplane.is_container("conditional.2")
    assert not xplane.is_container("fusion.1_bf16_64_4096_")
    assert not xplane.is_container("closed_call.13")
