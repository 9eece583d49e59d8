"""``bench/lib/roofline_hybrid.py``: the hybrid family's operations and bytes
from the configuration's file, against the numbers worked out by hand in the
issue that added the configuration; and the readers built on it, on a
hand-written trace."""

import json
import os

import pytest
from jax.profiler import ProfileData

from lib import hosttrace, hybridtrace, observe, peaks, roofline_hybrid, xplane

HERE = os.path.dirname(__file__)
BENCH = os.path.join(HERE, "..", "..", "bench")
PEAKS = peaks.peaks_for("TPU v5 lite")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs",
                           "nemotron-3-nano-30b-a3b-ep8.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def shape(config):
    return roofline_hybrid.HybridShape.from_config(config)


def test_the_layer_counts_follow_the_published_pattern(shape):
    assert (shape.mamba_layers, shape.moe_layers, shape.attn_layers) == (23, 23, 6)
    assert shape.d_inner == 4096 and shape.conv_dim == 6144
    assert (shape.experts, shape.experts_held, shape.experts_per_token) == \
        (128, 16, 6)


def test_parameters_by_layer_are_the_issue_s(shape):
    assert shape.mamba_layer_params == pytest.approx(38.7e6, rel=5e-3)
    assert shape.attn_layer_params == pytest.approx(23.4e6, rel=5e-3)
    assert shape.routed_params == 16 * 2 * 2688 * 1856
    assert shape.shared_params == pytest.approx(19.96e6, rel=1e-3)
    moe = shape.routed_params + shape.shared_params + shape.router_params
    assert moe == pytest.approx(180e6, rel=5e-3)
    total = (shape.layer_weight_bytes
             + 2 * 2 * shape.vocab * shape.hidden)      # embedding and head
    assert total == pytest.approx(10.5e9, rel=1e-2)


def test_state_and_pool_bytes_are_the_issue_s(shape, config):
    a_slot = shape.ssm_slot_bytes + shape.conv_slot_bytes
    assert a_slot == 23 * (2_097_152 + 36_864)
    assert 64 * a_slot == pytest.approx(3.14e9, rel=1e-2)
    assert shape.kv_row_bytes == 6144
    assert config["serving"]["slots"] * a_slot \
        + shape.kv_row_bytes * 64 * config["serving"]["kv-pool-blocks"] \
        + 10.5e9 == pytest.approx(14.5e9, rel=1e-2)


def test_the_floors_are_the_issue_s(shape):
    ssm = roofline_hybrid.ssm_state_floor(shape, slots=64, peaks=PEAKS)
    assert ssm["bound_by"] == "bytes"
    assert ssm["floor_s"] == pytest.approx(7.5e-3, rel=3e-2)  # 3.09 GB twice
    moe = roofline_hybrid.moe_experts_floor(
        shape, routed_pairs=23 * 48, batch=64, peaks=PEAKS)
    assert moe["bound_by"] == "bytes"
    assert moe["bytes"] == 2 * 23 * (16 * 2 * 2688 * 1856 + 2 * 2688 * 3712)
    step = roofline_hybrid.decode_step_floor(
        shape, live_rows=64 * 400, batch=64, routed_pairs=23 * 48,
        state_bytes=64 * 49_086_464, peaks=PEAKS)
    assert step["bound_by"] == "bytes"
    assert step["floor_s"] == pytest.approx(20e-3, rel=5e-2)
    # a far larger batch turns the experts' floor to operations
    big = roofline_hybrid.moe_experts_floor(
        shape, routed_pairs=23 * 48 * 400, batch=64 * 400, peaks=PEAKS)
    assert big["bound_by"] == "flops"


def test_a_dense_configuration_has_no_hybrid_shape():
    assert roofline_hybrid.shape_of({"serving": {"model": "internlm2-1.8b"}}) is None
    assert roofline_hybrid.shape_of({"serving": {}}) is None
    assert roofline_hybrid.shape_of(
        {"serving": {"model": "hybrid-tiny"}}).pattern == "MEM*EM*E"


# -- the readers, on the hand-written trace of test_bench_hosttrace.py ------

NEW = ["ssm_dev_ms_step", "ssm_state_roofline", "moe_dev_ms_step",
       "moe_experts_roofline", "hybrid_decode_roofline",
       "expert_load_max_over_mean"]
# what a hybrid decode program's HLO would say of the fixture's operations
SCOPES = {"jit__decode_chunk(111)": {
    "fusion.1": "ssm_scan", "paged_read.7": "kv_read", "copy.4": "ssm_conv",
    "fusion.5": "moe_experts"}}


def reader(name):
    return observe.load_metric(
        observe.find("layer_metrics", name, [BENCH]))["read"]


@pytest.fixture
def obs(monkeypatch, config):
    with open(os.path.join(HERE, "fixtures", "hosttrace.xplane.txt")) as f:
        profile = ProfileData.from_text_proto(f.read())
    # one Mamba-2 layer: each op of the fixture's decode runs is one step
    one_block = dict(config, hybrid_override_pattern="M*E")
    monkeypatch.setattr(roofline_hybrid, "config_of", lambda obs: one_block)
    dense = hosttrace.SCOPES
    hosttrace.SCOPES = dense + hybridtrace.SCOPES
    try:
        reduced = hosttrace.reduce(profile, SCOPES)
    finally:
        hosttrace.SCOPES = dense
    return {
        "trace": xplane.reduce(profile, 15e-6), "hybridtrace": reduced,
        "serving": {"model": "nemotron-3-nano-30b-a3b-ep8"}, "peaks": PEAKS,
        "pool": {"block_size": 64, "num_blocks": 2049},
        "polls": [{"active": 64, "live_blocks": 64 * 5}],
        "samples": [
            {"phase": "decode", "steps": 32, "active_at_dispatch": 64,
             "routed_pairs": 32 * 48, "expert_load_max": 160,
             "state_bytes": 64 * 49_086_464},
            {"phase": "decode", "steps": 16, "active_at_dispatch": 32,
             "routed_pairs": 16 * 24, "expert_load_max": 80,
             "state_bytes": 32 * 49_086_464},
            {"phase": "prefill", "steps": 0, "active_at_dispatch": 10},
        ],
    }


def test_the_scopes_are_found_and_the_dense_list_is_left_as_it_was(obs):
    by_scope = obs["hybridtrace"]["scopes"]["by_scope"]
    assert {k: round(v * 1e9) for k, v in by_scope.items()} == {
        "ssm_scan": 1500, "kv_read": 3000, "ssm_conv": 500, "moe_experts": 1000}
    assert "ssm_scan" not in hosttrace.SCOPES


def test_each_reader_reads_the_fixture(obs):
    # three decode runs, one step each (one block): 3 steps in 6.5 us
    assert roofline_hybrid.traced_steps(
        obs, roofline_hybrid.shape_of(obs)) == (pytest.approx(6.5e-9 * 1e3), 3)
    assert reader("ssm_dev_ms_step")(obs) == pytest.approx(1e3 * 2000e-9 / 3)
    assert reader("moe_dev_ms_step")(obs) == pytest.approx(1e3 * 1000e-9 / 3)
    shape = roofline_hybrid.shape_of(obs)
    slots = (64 * 32 + 32 * 16) / 48
    floor = roofline_hybrid.ssm_state_floor(shape, slots=slots, peaks=PEAKS)
    assert reader("ssm_state_roofline")(obs) == pytest.approx(
        100 * floor["floor_s"] / (1500e-9 / 3))
    floor = roofline_hybrid.moe_experts_floor(
        shape, routed_pairs=(32 * 48 + 16 * 24) / 48, batch=slots, peaks=PEAKS)
    assert reader("moe_experts_roofline")(obs) == pytest.approx(
        100 * floor["floor_s"] / (1000e-9 / 3))
    whole = reader("hybrid_decode_roofline")(obs)
    assert whole > 0
    # the state's bytes are the flight samples' counter
    for sample in obs["samples"][:2]:
        sample["state_bytes"] *= 2
    assert reader("hybrid_decode_roofline")(obs) > whole
    # (160 + 80) over the mean an expert of a layer got: 1920 / (1 x 16)
    assert reader("expert_load_max_over_mean")(obs) == pytest.approx(240 / 120)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_or_counters_gives_nothing(name):
    """A parent commit serves a dense stand-in under the configuration's
    name: no hybrid scope in its trace, no expert loads in its samples. And
    a run that was not traced, or of another family."""
    bare = {"serving": {"model": "nemotron-3-nano-30b-a3b-ep8"},
            "peaks": PEAKS, "trace": None,
            "samples": [{"phase": "decode", "steps": 8,
                         "active_at_dispatch": 4}],
            "polls": [], "pool": {"block_size": 64}}
    assert reader(name)(bare) is None
    assert reader(name)({**bare, "serving": {"model": "internlm2-1.8b"}}) is None
    assert reader(name)({"serving": {}, "samples": [], "trace": None}) is None
