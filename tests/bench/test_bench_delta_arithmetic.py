"""``bench/lib/roofline_delta.py``: the ``solar_open2`` family's operations
and bytes from the configuration's file, against the numbers worked out by
hand in the issue that added the configuration (3,308 M held = 6.62 GB, 13.0
MB of state a slot, a step's floor of about 14.4 ms at 192 slots); and the
readers built on it, on a hand-written trace."""

import json
import os
import sys

import pytest
from jax.profiler import ProfileData

from lib import observe, peaks, roofline_delta, xplane

HERE = os.path.dirname(__file__)
BENCH = os.path.join(HERE, "..", "..", "bench")
PEAKS = peaks.peaks_for("TPU v5 lite")
A_STATE, A_TAIL = 64 * 128 * 128 * 4, 3 * 24576 * 2     # one layer, one slot
A_SLOT = 3 * (A_STATE + A_TAIL)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "solar-open2-250b-ep8.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def shape(config):
    return roofline_delta.DeltaShape.from_config(config)


def test_the_layer_counts_follow_the_published_list_up_to_the_depth(shape, config):
    assert config["gqa_layers"] == list(range(0, 48, 4))     # kept whole
    assert (shape.layers, shape.attn_layers, shape.delta_layers) == (4, 1, 3)
    deeper = roofline_delta.DeltaShape.from_config(
        dict(config, num_hidden_layers=48))
    assert (deeper.attn_layers, deeper.delta_layers) == (12, 36)
    assert (shape.experts, shape.experts_held, shape.experts_per_token) == \
        (320, 40, 8)
    assert (shape.delta_heads, shape.delta_dim, shape.gate_rank,
            shape.conv_kernel) == (64, 128, 128, 4)


@pytest.mark.parametrize("what, by_hand", [
    # 3 x 4096 x 8192 + 8192 x 4096 + 2 x (4096 x 128 + 128 x 8192)
    # + 4096 x 64 + 3 x 8192 x 4, and 8192 + 64 + 128 + 4096 of biases and norms
    ("delta_layer_params", 100663296 + 33554432 + 3145728 + 262144 + 98304
     + 12480),
    # 4096 x (8192 + 2 x 1024) + 8192 x 4096 + the gate's 4096 x 8192, a norm
    ("attn_layer_params", 41943040 + 33554432 + 33554432 + 4096),
    ("expert_params", 3 * 4096 * 1280),
    ("routed_params", 40 * 15728640),
    ("shared_params", 15728640),
    ("router_params", 4096 * 320),
])
def test_parameters_are_the_issue_s(shape, what, by_hand):
    assert getattr(shape, what) == by_hand


def test_held_parameters_are_the_program_s(shape):
    """3,308 M, and leaf for leaf what ``init_hybrid_params`` makes."""
    import jax
    import numpy as np

    from langstream_tpu.models.hybrid import HybridConfig, init_hybrid_params

    c = HybridConfig.solar_open2_ep8()
    leaves = jax.tree.leaves(jax.eval_shape(lambda: init_hybrid_params(c)))
    assert shape.held_params == sum(int(np.prod(a.shape)) for a in leaves)
    assert shape.held_bytes == sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)
    assert 3.307e9 < shape.held_params < 3.309e9
    assert 6.62e9 < shape.held_bytes < 6.63e9


def test_state_and_pool_bytes_are_the_issue_s(shape, config):
    from langstream_tpu.models.hybrid import HybridConfig

    assert shape.delta_slot_bytes == 3 * A_STATE == 12582912
    assert shape.conv_slot_bytes == 3 * A_TAIL
    assert shape.delta_slot_bytes + shape.conv_slot_bytes == A_SLOT == \
        HybridConfig.solar_open2_ep8().state_bytes_per_slot
    assert shape.kv_row_bytes == 4096
    serving = config["serving"]
    pool = shape.kv_row_bytes * serving["kv-block-size"] * serving["kv-pool-blocks"]
    resident = shape.held_bytes + serving["slots"] * A_SLOT + pool
    assert 10.6e9 < resident < 10.8e9         # 63% of 16.9 GB


def test_the_floors_are_the_issue_s(shape):
    state = roofline_delta.delta_state_floor(shape, slots=192, peaks=PEAKS)
    assert state["bytes"] == 2 * 192 * 3 * A_STATE        # 4.83 GB
    assert state["bound_by"] == "bytes"
    assert state["floor_s"] == pytest.approx(4.8318e9 / 819e9, rel=1e-3)
    experts = roofline_delta.experts_floor(
        shape, routed_pairs=4 * 192, batch=192, peaks=PEAKS)
    assert experts["bytes"] == 2 * 4 * 41 * 15728640      # 5.16 GB
    assert experts["bound_by"] == "bytes"
    step = roofline_delta.decode_step_floor(
        shape, live_rows=192 * 400, batch=192, routed_pairs=4 * 192,
        state_bytes=192 * A_SLOT, peaks=PEAKS)
    # the weights (all but the embedding's rows no token gathers), the state
    # and its tails twice, the rows of 192 requests of 400 tokens
    assert step["bytes"] == pytest.approx(
        shape.held_bytes - 2 * 4096 * (24576 - 192) + 2 * 192 * A_SLOT
        + (192 * 400 + 192) * 4096)
    assert step["bound_by"] == "bytes"
    assert 0.0140 < step["floor_s"] < 0.0148               # the issue's 14.4 ms
    # a token's prefill: about 1.34 GFLOP, 27 MFLOP of it the chunked rule
    assert roofline_delta.chunk_flops(shape, 1) == 3 * 64 * (
        2 * 64 * 128 + 64 * 2 * 128 + 64 * 128 + 6 * 128 * 128)
    per_token = roofline_delta.prefill_flops(shape, 2400) / 2400
    assert 1.30e9 < per_token < 1.40e9
    # a batch's tokens as eight equal prompts hold the fewest causal pairs:
    # 8 x 300 x 301 / 2 where one prompt of 2,400 would hold 2,400 x 2,401 / 2
    one = roofline_delta.prefill_flops(shape, 2400, prompts=1)
    assert one - roofline_delta.prefill_flops(shape, 2400) == pytest.approx(
        4 * 64 * 128 * (2400 * 2401 / 2 - 8 * 300 * 301 / 2))


def test_another_family_has_no_delta_shape():
    shape_of = roofline_delta.shape_of
    assert shape_of({"serving": {"model": "internlm2-1.8b"}}) is None
    assert shape_of({"serving": {"model": "granite-4.0-h-small-ep2"}}) is None
    assert shape_of({"serving": {"model": "deepseek-v2-ep8"}}) is None
    assert shape_of({"serving": {}}) is None
    tiny = shape_of({"serving": {"model": "solar-tiny"}})
    assert (tiny.layers, tiny.delta_layers, tiny.attn_layers) == (4, 3, 1)
    assert (tiny.experts, tiny.experts_held) == (8, 4)


# -- the readers, on the hand-written trace of test_bench_hosttrace.py ------

NEW = ["delta_state_roofline", "delta_chunk_mfu", "delta_dev_ms_step",
       "delta_moe_dev_ms_step", "delta_experts_roofline",
       "delta_decode_roofline", "delta_prefill_mfu",
       "delta_expert_load_max_over_mean"]


def reader(name):
    return observe.load_metric(
        observe.find("layer_metrics", name, [BENCH]))["read"]


def trace_obs(monkeypatch, config, layers):
    with open(os.path.join(HERE, "fixtures", "hosttrace.xplane.txt")) as f:
        profile = ProfileData.from_text_proto(f.read())
    served = dict(config, num_hidden_layers=layers)
    monkeypatch.setattr(roofline_delta, "config_of", lambda obs: served)
    return {
        "trace": xplane.reduce(profile, 15e-6),
        # what the decode and the prefill programs' HLO would say of their
        # operations, already reduced (``roofline_delta._scopes`` keeps it so)
        "deltatrace.decode_chunk": {"by_scope": {
            "delta_state": 1500e-9, "delta_in": 500e-9, "kv_read": 3000e-9,
            "moe_experts": 800e-9, "moe_shared": 200e-9}, "unscoped": {}},
        "deltatrace.prefill": {"by_scope": {
            "delta_chunk": 2e-3, "delta_in": 1e-3, "moe_experts": 4e-3},
            "unscoped": {"copy.1": 1e-3}},
        "deltaprefills": [{"prompt_tokens": 1000, "seconds": 16e-3, "flash_s": 0},
                          {"prompt_tokens": 600, "seconds": 8e-3, "flash_s": 0}],
        "serving": {"model": "solar-open2-250b-ep8"}, "peaks": PEAKS,
        "pool": {"block_size": 64, "num_blocks": 6145},
        "polls": [{"active": 192, "live_blocks": 192 * 5}],
        "samples": [
            {"phase": "decode", "steps": 32, "active_at_dispatch": 192,
             "routed_pairs": 32 * 768, "expert_load_max": 1600,
             "state_bytes": 192 * A_SLOT},
            {"phase": "decode", "steps": 16, "active_at_dispatch": 96,
             "routed_pairs": 16 * 384, "expert_load_max": 800,
             "state_bytes": 96 * A_SLOT},
            {"phase": "prefill", "steps": 0, "active_at_dispatch": 10},
        ],
    }


@pytest.fixture
def obs(monkeypatch, config):
    # one layer: each op of the fixture's decode runs is one step
    return trace_obs(monkeypatch, config, 1)


def test_steps_are_counted_by_the_scan_over_the_layers(monkeypatch, config, obs):
    shape = roofline_delta.shape_of(obs)
    assert shape.layers == 1
    assert xplane.program(obs["trace"], "decode_chunk")["op_counts"] == [1, 1, 1]
    assert roofline_delta.traced_steps(obs, shape) == (
        pytest.approx(6.5e-9 * 1e3), 3)
    # the same runs read as a program of four layers hold no whole step
    four = trace_obs(monkeypatch, config, 4)
    assert roofline_delta.traced_steps(
        four, roofline_delta.shape_of(four)) == (0.0, 0)
    assert reader("delta_dev_ms_step")(four) is None


def test_each_reader_reads_the_fixture(obs):
    assert reader("delta_dev_ms_step")(obs) == pytest.approx(1e3 * 2000e-9 / 3)
    assert reader("delta_moe_dev_ms_step")(obs) == pytest.approx(
        1e3 * 1000e-9 / 3)
    shape = roofline_delta.shape_of(obs)
    slots = (192 * 32 + 96 * 16) / 48
    floor = roofline_delta.delta_state_floor(shape, slots=slots, peaks=PEAKS)
    assert reader("delta_state_roofline")(obs) == pytest.approx(
        100 * floor["floor_s"] / (1500e-9 / 3))
    floor = roofline_delta.experts_floor(
        shape, routed_pairs=(32 * 768 + 16 * 384) / 48, batch=slots,
        peaks=PEAKS)
    assert reader("delta_experts_roofline")(obs) == pytest.approx(
        100 * floor["floor_s"] / (1000e-9 / 3))
    whole = reader("delta_decode_roofline")(obs)
    assert whole > 0
    for sample in obs["samples"][:2]:       # the flight samples' counter
        sample["state_bytes"] *= 2
    assert reader("delta_decode_roofline")(obs) > whole
    # (1600 + 800) over the mean an expert of a layer got: 30720 / (1 x 40)
    assert reader("delta_expert_load_max_over_mean")(obs) == pytest.approx(
        2400 / (30720 / 40))
    # the prefill runs paired with their samples: 1,600 tokens in 24 ms, a
    # quarter of the prefill programs' device time under delta_chunk
    assert reader("delta_prefill_mfu")(obs) == pytest.approx(
        100 * (roofline_delta.prefill_flops(shape, 1000)
               + roofline_delta.prefill_flops(shape, 600)) / 197e12 / 24e-3)
    assert roofline_delta.prefill_scope_share(obs, ("delta_chunk",)) == \
        pytest.approx(0.25)
    assert reader("delta_chunk_mfu")(obs) == pytest.approx(
        100 * roofline_delta.chunk_flops(shape, 1600) / 197e12 / 6e-3)


def test_a_run_reads_its_own_trace_directory(monkeypatch, tmp_path):
    """``.bench_work/<cell>-<seed>-<trace>/trace`` by the arguments the
    process was started with; the newest trace of any run only where the
    process was started otherwise (PERF.md 7(m))."""
    from lib import hosttrace

    monkeypatch.setattr(hosttrace, "ROOT", str(tmp_path))
    mine = tmp_path / ".bench_work" / "solaropen2-chat-sat-2147483690-1" / "trace" / "p"
    other = tmp_path / ".bench_work" / "granite4hsmall-chat-sat-7-1" / "trace" / "p"
    for d in (mine, other):
        d.mkdir(parents=True)
    (mine / "a.xplane.pb").write_bytes(b"")
    newest = other / "b.xplane.pb"
    newest.write_bytes(b"")
    os.utime(newest, (2e9, 2e9))
    monkeypatch.setattr(sys, "argv", [
        "bench/run.py", "--workload", "solaropen2-chat-sat", "--seed",
        "2147483690", "--seconds", "51", "--trace", "1"])
    assert roofline_delta.own_trace() == str(mine / "a.xplane.pb")
    monkeypatch.setattr(sys, "argv", ["pytest"])
    assert roofline_delta.own_trace() == str(newest)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_or_counters_gives_nothing(name):
    """A parent commit cannot serve the configuration at all; a run that was
    not traced, a program that names no scope and carries no expert loads,
    and a run of another family all give nothing and do not raise."""
    bare = {"serving": {"model": "solar-open2-250b-ep8"},
            "peaks": PEAKS, "trace": None,
            "samples": [{"phase": "decode", "steps": 8,
                         "active_at_dispatch": 4}],
            "polls": [], "pool": {"block_size": 64}}
    assert reader(name)(bare) is None
    assert reader(name)({**bare, "serving": {"model": "internlm2-1.8b"}}) is None
    assert reader(name)(
        {**bare, "serving": {"model": "granite-4.0-h-small-ep2"}}) is None
    assert reader(name)({"serving": {}, "samples": [], "trace": None}) is None


@pytest.mark.parametrize("name", NEW)
def test_the_benchmark_lists_the_reader_for_the_new_cell_alone(name):
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    meta = observe.load_metric(observe.find("layer_metrics", name, [BENCH]))
    assert entry["workloads"] == ["solaropen2-chat-sat"]
    for key in ("unit", "better", "layer", "moves", "source"):
        assert entry[key] == meta[key], key
