"""``bench/lib/roofline_granite.py``: the ``granitemoehybrid`` family's
operations and bytes from the configuration's file, against the numbers
worked out by hand in the issue that added the configuration (4,757 M held,
38.2 MB of state a slot, a step's floor of about 21 ms at 96 slots); and the
readers built on it, on a hand-written trace."""

import json
import os

import pytest
from jax.profiler import ProfileData

from lib import (hosttrace, hybridtrace, observe, peaks, roofline,
                 roofline_granite, xplane)

HERE = os.path.dirname(__file__)
BENCH = os.path.join(HERE, "..", "..", "bench")
PEAKS = peaks.peaks_for("TPU v5 lite")
A_STATE, A_TAIL = 128 * 64 * 128 * 4, 3 * 8448 * 2     # one layer, one slot


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs",
                           "granite-4.0-h-small-ep2.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def shape(config):
    return roofline_granite.GraniteShape.from_config(config)


def test_the_layer_counts_follow_the_published_list_up_to_the_depth(shape, config):
    assert len(config["layer_types"]) == 40            # kept whole
    assert shape.layer_types == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (shape.layers, shape.mamba_layers, shape.attn_layers) == (10, 9, 1)
    assert shape.d_inner == 8192 and shape.conv_dim == 8448
    assert (shape.experts, shape.experts_held, shape.experts_per_token) == \
        (72, 36, 10)
    assert shape.head_dim == 128


@pytest.mark.parametrize("what, by_hand", [
    # in_proj 4096 x 16768, out_proj 8192 x 4096, conv 8448 x (4 + 1),
    # A_log, D, dt_bias, the gate's norm, the layer's norm
    ("mamba_layer_params", 68_681_728 + 33_554_432 + 42_240 + 384 + 8192 + 4096),
    # q, k, v, o and the norm
    ("attn_layer_params", 4096 * (4096 + 2 * 1024) + 4096 * 4096 + 4096),
    ("expert_params", 3 * 4096 * 768),                 # three matrices' worth
    ("routed_params", 36 * 9_437_184),
    ("shared_params", 3 * 4096 * 1536),
    ("router_params", 4096 * 72),
    ("held_params", 4_757_211_776),
])
def test_parameters_are_the_issue_s(shape, what, by_hand):
    assert getattr(shape, what) == by_hand


def test_held_parameters_are_the_program_s(shape):
    """The same count from the other side: the leaves the program's init
    would draw at the published widths."""
    import jax
    import numpy as np

    from langstream_tpu.models.hybrid import HybridConfig, init_hybrid_params

    shapes = jax.eval_shape(
        lambda: init_hybrid_params(HybridConfig.granite4_h_small_ep2()))
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert held == shape.held_params == pytest.approx(4757e6, rel=1e-4)


def test_state_and_pool_bytes_are_the_issue_s(shape, config):
    from langstream_tpu.models.hybrid import HybridConfig

    a_slot = shape.ssm_slot_bytes + shape.conv_slot_bytes
    assert a_slot == 9 * (A_STATE + A_TAIL) == 38_204_928       # 38.2 MB
    assert a_slot == HybridConfig.granite4_h_small_ep2().state_bytes_per_slot
    assert shape.kv_row_bytes == 4096
    serving = config["serving"]
    assert serving["kv-pool-blocks"] == serving["slots"] * 2048 // 64 + 1
    resident = (2 * shape.held_params + serving["slots"] * a_slot
                + shape.kv_row_bytes * 64 * serving["kv-pool-blocks"])
    assert resident == pytest.approx(13.99e9, rel=2e-3)
    assert resident / 16_909_336_064 == pytest.approx(0.83, abs=0.005)


def test_the_floors_are_the_issue_s(shape):
    ssm = roofline_granite.ssm_state_floor(shape, slots=96, peaks=PEAKS)
    assert ssm["bound_by"] == "bytes"
    assert ssm["bytes"] == 2 * 96 * 9 * A_STATE
    assert ssm["floor_s"] == pytest.approx(8.85e-3, rel=1e-2)
    experts = roofline_granite.experts_floor(
        shape, routed_pairs=10 * 96 * 5, batch=96, peaks=PEAKS)
    assert experts["bound_by"] == "bytes"
    assert experts["bytes"] == 2 * 10 * (36 * 9_437_184 + 18_874_368)
    assert experts["floor_s"] == pytest.approx(8.8e-3, rel=1e-2)
    # a routed pair is 6 x hidden x width operations
    assert experts["flops"] == (4800 * 6 * 4096 * 768
                                + 96 * 10 * 6 * 4096 * 1536)
    step = roofline_granite.decode_step_floor(
        shape, live_rows=96 * 400, batch=96, routed_pairs=4800,
        state_bytes=96 * 38_204_928, peaks=PEAKS)
    assert step["bound_by"] == "bytes"
    assert step["bytes"] == (2 * 4_757_211_776 + 2 * 96 * 38_204_928
                             + (96 * 400 + 96) * 4096)
    assert step["floor_s"] == pytest.approx(21e-3, rel=2e-2)
    # a far larger batch turns the experts' floor to operations
    big = roofline_granite.experts_floor(
        shape, routed_pairs=4800 * 400, batch=96 * 400, peaks=PEAKS)
    assert big["bound_by"] == "flops"


def test_another_family_has_no_granite_shape():
    shape_of = roofline_granite.shape_of
    assert shape_of({"serving": {"model": "internlm2-1.8b"}}) is None
    assert shape_of({"serving": {"model": "nemotron-3-nano-30b-a3b-ep8"}}) is None
    assert shape_of({"serving": {"model": "hybrid-tiny"}}) is None
    assert shape_of({"serving": {}}) is None
    tiny = shape_of({"serving": {"model": "granite-tiny"}})
    assert (tiny.layers, tiny.mamba_layers, tiny.attn_layers) == (8, 6, 2)
    assert (tiny.experts, tiny.experts_held) == (8, 4)


# -- the readers, on the hand-written trace of test_bench_hosttrace.py ------

NEW = ["granite_ssm_dev_ms_step", "granite_ssm_state_roofline",
       "granite_moe_dev_ms_step", "granite_experts_roofline",
       "granite_decode_roofline", "granite_expert_load_max_over_mean",
       "granite_paged_read_roofline"]
# what a hybrid decode program's HLO would say of the fixture's operations
SCOPES = {"jit__decode_chunk(111)": {
    "fusion.1": "ssm_scan", "paged_read.7": "kv_read", "copy.4": "ssm_conv",
    "fusion.5": "moe_experts"}}
A_SLOT = 38_204_928


def reader(name):
    return observe.load_metric(
        observe.find("layer_metrics", name, [BENCH]))["read"]


def trace_obs(monkeypatch, config, layers):
    with open(os.path.join(HERE, "fixtures", "hosttrace.xplane.txt")) as f:
        profile = ProfileData.from_text_proto(f.read())
    served = dict(config, num_hidden_layers=layers)
    monkeypatch.setattr(roofline_granite, "config_of", lambda obs: served)
    dense = hosttrace.SCOPES
    hosttrace.SCOPES = dense + hybridtrace.SCOPES
    try:
        reduced = hosttrace.reduce(profile, SCOPES)
    finally:
        hosttrace.SCOPES = dense
    return {
        "trace": xplane.reduce(profile, 15e-6), "hybridtrace": reduced,
        "serving": {"model": "granite-4.0-h-small-ep2"}, "peaks": PEAKS,
        "pool": {"block_size": 64, "num_blocks": 3073},
        "polls": [{"active": 96, "live_blocks": 96 * 5}],
        "samples": [
            {"phase": "decode", "steps": 32, "active_at_dispatch": 96,
             "routed_pairs": 32 * 480, "expert_load_max": 1600,
             "state_bytes": 96 * A_SLOT},
            {"phase": "decode", "steps": 16, "active_at_dispatch": 48,
             "routed_pairs": 16 * 240, "expert_load_max": 800,
             "state_bytes": 48 * A_SLOT},
            {"phase": "prefill", "steps": 0, "active_at_dispatch": 10},
        ],
    }


@pytest.fixture
def obs(monkeypatch, config):
    # one layer: each op of the fixture's decode runs is one step
    return trace_obs(monkeypatch, config, 1)


def test_steps_are_counted_by_the_scan_over_the_layers(monkeypatch, config, obs):
    """The scanned block is one published layer (its mixer, then its
    experts), so a run's most frequent op ran steps x layers times: a
    Mamba-2 op runs less often where attention takes a mixer's place, and
    does not decide the count."""
    shape = roofline_granite.shape_of(obs)
    assert shape.layers == 1
    runs = xplane.program(obs["trace"], "decode_chunk")
    assert runs["op_counts"] == [1, 1, 1]
    # three decode runs, one step each: 3 steps in 6.5 us
    assert roofline_granite.traced_steps(obs, shape) == (
        pytest.approx(6.5e-9 * 1e3), 3)
    # the same runs read as a program of two layers hold no whole step
    two = trace_obs(monkeypatch, config, 2)
    assert roofline_granite.traced_steps(
        two, roofline_granite.shape_of(two)) == (0.0, 0)
    assert reader("granite_ssm_dev_ms_step")(two) is None


def test_each_reader_reads_the_fixture(obs):
    by_scope = obs["hybridtrace"]["scopes"]["by_scope"]
    assert {k: round(v * 1e9) for k, v in by_scope.items()} == {
        "ssm_scan": 1500, "kv_read": 3000, "ssm_conv": 500, "moe_experts": 1000}
    assert reader("granite_ssm_dev_ms_step")(obs) == pytest.approx(
        1e3 * 2000e-9 / 3)
    assert reader("granite_moe_dev_ms_step")(obs) == pytest.approx(
        1e3 * 1000e-9 / 3)
    shape = roofline_granite.shape_of(obs)
    slots = (96 * 32 + 48 * 16) / 48
    floor = roofline_granite.ssm_state_floor(shape, slots=slots, peaks=PEAKS)
    assert reader("granite_ssm_state_roofline")(obs) == pytest.approx(
        100 * floor["floor_s"] / (1500e-9 / 3))
    floor = roofline_granite.experts_floor(
        shape, routed_pairs=(32 * 480 + 16 * 240) / 48, batch=slots,
        peaks=PEAKS)
    assert reader("granite_experts_roofline")(obs) == pytest.approx(
        100 * floor["floor_s"] / (1000e-9 / 3))
    whole = reader("granite_decode_roofline")(obs)
    assert whole > 0
    # the state's bytes are the flight samples' counter
    for sample in obs["samples"][:2]:
        sample["state_bytes"] *= 2
    assert reader("granite_decode_roofline")(obs) > whole
    # (1600 + 800) over the mean an expert of a layer got: 19200 / (1 x 36)
    assert reader("granite_expert_load_max_over_mean")(obs) == pytest.approx(
        2400 / (19200 / 36))


def test_the_paged_read_is_the_op_that_carries_the_kernel_s_name(obs, config):
    """The fixture's decode runs call ``paged_read.7`` twice, 3,000 ns in
    all. A cheap custom call beside it, as this family's decode program
    has them, is taken for the kernel by ``paged_read_roofline`` (every op
    that says custom-call) and not by this reader."""
    widths = {k.replace("num_", "").replace("attention_heads", "heads")
              .replace("key_value_heads", "kv_heads")
              .replace("hidden_size", "hidden")
              .replace("intermediate_size", "intermediate")
              .replace("hidden_layers", "layers"): v
              for k, v in config["widths"].items()}
    o = {**obs, "paged_read_kernel": "pallas",
         "shape": roofline.Shape.from_widths(
             widths, weight_dtype_bytes=2.0, kv_quantized=False)}
    live_rows = 96 * 5 * 64 - 96 * 64 / 2
    floor = roofline.paged_read_floor(o["shape"], live_rows=live_rows,
                                      peaks=PEAKS)
    want = 100 * floor["floor_s"] / (3000e-9 / 2)
    assert reader("granite_paged_read_roofline")(o) == pytest.approx(want)
    o["trace"]["planes"][0]["ops"].append(
        {"name": "custom-call.36_s32_1024_", "program": "jit__decode_chunk",
         "total_s": 300e-9, "calls": 3})
    assert reader("granite_paged_read_roofline")(o) == pytest.approx(want)
    assert reader("paged_read_roofline")(o) == pytest.approx(
        100 * floor["floor_s"] / (3300e-9 / 5))
    assert reader("granite_paged_read_roofline")(
        {**o, "paged_read_kernel": "xla"}) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_or_counters_gives_nothing(name):
    """A parent commit cannot serve the configuration at all; a run that was
    not traced, a program that names no scope and carries no expert loads,
    and a run of another family all give nothing and do not raise."""
    bare = {"serving": {"model": "granite-4.0-h-small-ep2"},
            "peaks": PEAKS, "trace": None,
            "samples": [{"phase": "decode", "steps": 8,
                         "active_at_dispatch": 4}],
            "polls": [], "pool": {"block_size": 64}}
    assert reader(name)(bare) is None
    assert reader(name)({**bare, "serving": {"model": "internlm2-1.8b"}}) is None
    assert reader(name)(
        {**bare, "serving": {"model": "nemotron-3-nano-30b-a3b-ep8"}}) is None
    assert reader(name)({"serving": {}, "samples": [], "trace": None}) is None
