"""``bench/lib/roofline_swa.py``: the operations and bytes of a model whose
attention layers are of two kinds, from the configuration's file, against the
numbers worked out by hand in the issue that added the configuration
(4,321.9 M held = 8.64 GB, 4,096 B a row a layer, a window layer's live rows
``min(length, 4096)``, the masked pairs of flash); and the readers built on
it, on a hand-built reduction of a trace."""

import json
import os

import pytest

from lib import observe, peaks, roofline_swa

HERE = os.path.dirname(__file__)
BENCH = os.path.join(HERE, "..", "..", "bench")
PEAKS = peaks.peaks_for("TPU v5 lite")
CELL = "trinitylarge-longctx-sat"
MODEL = "trinity-large-preview-ep8"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", f"{MODEL}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def shape(config):
    return roofline_swa.SwaShape.from_config(config)


def test_the_layer_kinds_follow_the_published_list_over_the_layers_held(
        shape, config):
    kinds = config["layer_types"]
    assert len(kinds) == 60 and kinds[3::4] == ["full_attention"] * 15
    assert kinds.count("sliding_attention") == 45          # kept whole
    assert config["first_layer"] == 5
    # layers 5-9: window, window, full, window, window
    assert (shape.window_layers, shape.full_layers, shape.layers) == (4, 1, 5)
    assert (shape.dense_layers, shape.sparse_layers) == (1, 4)
    whole = roofline_swa.SwaShape.from_config(dict(
        config, num_hidden_layers=60, num_dense_layers=6, first_layer=0))
    assert (whole.window_layers, whole.full_layers, whole.sparse_layers) == \
        (45, 15, 54)
    assert (shape.experts, shape.experts_held, shape.experts_per_token,
            shape.window) == (256, 32, 4, 4096)


@pytest.mark.parametrize("what, by_hand", [
    # q 3072 x 6144 + k, v 2 x 3072 x 1024 + o 6144 x 3072 + gate 3072 x 6144
    ("attn_matmul_params", 62914560),
    ("dense_ffn_params", 3 * 3072 * 12288),                 # 113.2 M
    ("expert_params", 3 * 3072 * 3072),                     # 28.3 M
    ("routed_params", 32 * 28311552),
    ("shared_params", 28311552),
    ("router_params", 3072 * 256),                          # 0.8 M
    ("row_bytes", 4096.0),
])
def test_parameters_are_the_issue_s(shape, what, by_hand):
    assert getattr(shape, what) == by_hand


def test_held_parameters_are_the_program_s(shape):
    """4,321.9 M = 8.64 GB, and leaf for leaf what ``init_swa_params``
    makes."""
    import jax
    import numpy as np

    from langstream_tpu.models.swa import SwaConfig, init_swa_params

    c = SwaConfig.trinity_large_preview_ep8()
    leaves = jax.tree.leaves(jax.eval_shape(lambda: init_swa_params(c)))
    assert shape.held_params == sum(int(np.prod(a.shape)) for a in leaves)
    assert shape.held_bytes == sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)
    assert 4.3215e9 < shape.held_params < 4.3225e9
    assert 8.64e9 < shape.held_bytes < 8.65e9


def test_the_pools_are_the_issue_s(shape, config):
    serving = config["serving"]
    full = serving["kv-pool-blocks"] * serving["kv-block-size"] * \
        shape.full_layers * shape.row_bytes
    ring = shape.window // serving["kv-block-size"] + 1
    window = (serving["slots"] * ring + 1) * serving["kv-block-size"] * \
        shape.window_layers * shape.row_bytes
    assert ring == 65
    assert 1.67e9 < full < 1.69e9 and 2.18e9 < window < 2.19e9
    assert 12.4e9 < shape.held_bytes + full + window < 12.6e9   # 74% of 16.9
    # ONE table for all five layers would need five times the full pool
    assert 5 * full > 8.3e9


@pytest.mark.parametrize("length, rows", [
    (100, 100), (4095, 4095), (4096, 4096), (4097, 4096), (16384, 4096)])
def test_a_window_layer_s_live_rows_under_at_and_over_the_window(length, rows):
    assert roofline_swa.window_rows(length, 4096) == rows


@pytest.mark.parametrize("tokens, window, pairs", [
    (1, None, 1), (3, None, 6), (3, 2, 5), (4, 4, 10), (5, 4, 14),
    (8192, None, 8192 * 8193 / 2),
    # the first 4,096 queries see 1..4,096 keys, the other 4,096 see 4,096
    (8192, 4096, 4096 * 4097 / 2 + 4096 * 4096),
    (14336, 4096, 4096 * 4097 / 2 + 10240 * 4096),
])
def test_the_pairs_inside_the_mask(tokens, window, pairs):
    assert roofline_swa.masked_pairs(tokens, window) == pairs
    if window is not None:      # counted one query at a time
        assert pairs == sum(min(i + 1, window) for i in range(tokens))


def test_the_floors(shape):
    # 32 slots of 9,100 rows: the full layer reads them all, each of the
    # four window layers 4,096 a slot: 25.5k rows a slot, 3.3 GB a step
    read = roofline_swa.read_floor(
        shape, full_rows=32 * 9100, window_rows=32 * 4096, peaks=PEAKS)
    assert read["bytes"] == (32 * 9100 + 4 * 32 * 4096) * 4096
    assert 3.3e9 < read["bytes"] < 3.4e9 and read["bound_by"] == "bytes"
    # one table would read 5 x 9,100 rows a slot: 6.0 GB
    assert 5 * 32 * 9100 * 4096 > 5.9e9
    # 32 rows x 4 choices x 32 / 256 = 16 pairs a layer over 32 held
    # experts touch 32 x (1 - (31 / 32) ** 16) = 12.7 of them
    assert roofline_swa.touched_experts(shape, 16) == pytest.approx(
        32 * (1 - (31 / 32) ** 16))
    assert 12.6 < roofline_swa.touched_experts(shape, 16) < 12.8
    assert roofline_swa.touched_experts(shape, 0) == 0
    assert roofline_swa.touched_experts(shape, 1) == pytest.approx(1)
    assert 31.9 < roofline_swa.touched_experts(shape, 4096) <= 32
    experts = roofline_swa.experts_floor(
        shape, routed_pairs=64, batch=32, peaks=PEAKS)
    touched = 4 * (roofline_swa.touched_experts(shape, 16) + 1) * 28311552
    assert experts["bytes"] == pytest.approx(2 * touched)    # 3.1 GB
    assert 3.0e9 < experts["bytes"] < 3.2e9
    # what the dense pass streams: every held expert, 7.5 GB
    assert 2 * 4 * 33 * 28311552 > 7.4e9
    step = roofline_swa.decode_step_floor(
        shape, full_rows=32 * 9100, window_rows=32 * 4096, batch=32,
        routed_pairs=64, peaks=PEAKS)
    assert step["bytes"] == pytest.approx(
        shape.held_bytes - 2 * 3072 * (25024 - 32)
        - 2 * (4 * 33 * 28311552 - touched)
        + (32 * 9100 + 4 * 32 * 4096 + 5 * 32) * 4096)
    assert step["bound_by"] == "bytes" and 0.0085 < step["floor_s"] < 0.0095
    # a token's prefill through the five layers: 1.2 GFLOP, attention apart
    per_token = (roofline_swa.prefill_flops(shape, [8192])
                 - roofline_swa.flash_flops(shape, [8192])
                 - 2 * 3072 * 25024) / 8192
    assert 1.15e9 < per_token < 1.25e9
    # the attention of an 8,192-token prompt: 3.3 TFLOP, of which the full
    # layer's causal pairs 0.82 and the four window layers' 0.62 each
    flash = roofline_swa.flash_flops(shape, [8192])
    assert flash == 4 * 48 * 128 * (
        8192 * 8193 / 2 + 4 * (4096 * 4097 / 2 + 4096 * 4096))
    assert 3.2e12 < flash < 3.4e12
    # five full layers would have cost 4.1 TFLOP
    assert 5 * 4 * 48 * 128 * 8192 * 8193 / 2 > 4.1e12


def test_another_family_has_no_such_shape():
    shape_of = roofline_swa.shape_of
    for model in ("internlm2-1.8b", "granite-4.0-h-small-ep2",
                  "deepseek-v2-ep8", "solar-open2-250b-ep8"):
        assert shape_of({"serving": {"model": model}}) is None
    assert shape_of({"serving": {}}) is None
    tiny = shape_of({"serving": {"model": "trinity-tiny"}})
    assert (tiny.window_layers, tiny.full_layers, tiny.window) == (4, 1, 32)
    assert (tiny.experts, tiny.experts_held) == (8, 4)


# -- the readers, on a hand-built reduction ---------------------------------

NEW = ["swa_read_roofline", "swa_attn_dev_ms_step", "swa_flash_mfu",
       "swa_moe_dev_ms_step", "swa_experts_roofline",
       "swa_expert_load_max_over_mean", "swa_decode_roofline",
       "swa_prefill_mfu", "swa_pool_rows_saved_share"]


def reader(name):
    return observe.load_metric(
        observe.find("layer_metrics", name, [BENCH]))["read"]


@pytest.fixture
def obs():
    decode = "jit__decode_chunk(7)"
    ops = [
        # 40 calls of the read kernel: 8 steps of 5 layers; a custom call of
        # another name is not the kernel
        {"program": decode, "name": "paged_read.3", "total_s": 32e-3, "calls": 40},
        {"program": decode, "name": "custom-call.9", "total_s": 1e-3, "calls": 80},
        {"program": decode, "name": "fusion.1", "total_s": 127e-3, "calls": 400},
        {"program": "jit__prefill(3)", "name": "flash_prefill.2",
         "total_s": 90e-3, "calls": 5},
    ]
    return {
        "trace": {"planes": [{"ops": ops, "programs": {}}]},
        "paged_read_kernel": "pallas",
        "swatrace.decode_chunk": {"by_scope": {
            "swa_read": 24e-3, "full_read": 9e-3, "attn_buf": 2e-3,
            "moe_experts": 60e-3, "moe_shared": 4e-3, "moe_router": 1e-3,
            "post_norm": 1e-3}, "unscoped": {"copy.1": 1e-3}},
        "swaprefills": [
            {"prompt_tokens": 8192, "seconds": 0.30, "flash_s": 0.10},
            {"prompt_tokens": 5000, "seconds": 0.20, "flash_s": 0.05}],
        "serving": {"model": MODEL}, "peaks": PEAKS,
        "samples": [
            {"phase": "decode", "steps": 32, "active_at_dispatch": 32,
             "live_rows": 32 * 9000, "window_rows": 32 * 4096,
             "routed_pairs": 32 * 64, "expert_load_max": 40,
             "state_bytes": 0, "pool_rows_held": 32 * (9024 + 4 * 4160),
             "pool_rows_one_table": 32 * 5 * 9024,
             "window_slot_blocks_max": 65},
            {"phase": "decode", "steps": 16, "active_at_dispatch": 16,
             "live_rows": 16 * 6000, "window_rows": 16 * 4096,
             "routed_pairs": 16 * 32, "expert_load_max": 20,
             "state_bytes": 0, "pool_rows_held": 16 * (6016 + 4 * 4160),
             "pool_rows_one_table": 16 * 5 * 6016,
             "window_slot_blocks_max": 65},
            {"phase": "prefill", "steps": 0, "active_at_dispatch": 10},
        ],
    }


def test_steps_are_the_read_kernel_s_calls_over_the_layers(obs):
    seconds, steps = roofline_swa.traced_steps(obs)
    assert steps == 8 and seconds == pytest.approx(160e-3)
    load = roofline_swa.per_step(obs)
    assert load["slots"] == pytest.approx((32 * 32 + 16 * 16) / 48)
    # a full layer's rows grow inside a chunk, a window layer's stand still
    assert load["full_rows"] == pytest.approx(
        ((32 * 9000 + 32 * 15.5) * 32 + (16 * 6000 + 16 * 7.5) * 16) / 48)
    assert load["window_rows"] == pytest.approx(
        (32 * 4096 * 32 + 16 * 4096 * 16) / 48)


def test_each_reader_reads_the_reduction(obs, shape):
    load = roofline_swa.per_step(obs)
    floor = roofline_swa.read_floor(
        shape, full_rows=load["full_rows"], window_rows=load["window_rows"],
        peaks=PEAKS)
    assert reader("swa_read_roofline")(obs) == pytest.approx(
        100 * floor["floor_s"] / (32e-3 / 8))
    assert reader("swa_attn_dev_ms_step")(obs) == pytest.approx(33 / 8)
    assert reader("swa_moe_dev_ms_step")(obs) == pytest.approx(65 / 8)
    floor = roofline_swa.experts_floor(
        shape, routed_pairs=load["routed_pairs"], batch=load["slots"],
        peaks=PEAKS)
    assert reader("swa_experts_roofline")(obs) == pytest.approx(
        100 * floor["floor_s"] / (64e-3 / 8))
    floor = roofline_swa.decode_step_floor(
        shape, full_rows=load["full_rows"], window_rows=load["window_rows"],
        batch=load["slots"], routed_pairs=load["routed_pairs"], peaks=PEAKS)
    assert reader("swa_decode_roofline")(obs) == pytest.approx(
        100 * floor["floor_s"] / (160e-3 / 8))
    assert 0 < reader("swa_decode_roofline")(obs) < 100
    # (40 + 20) over the mean an expert of a layer got: 2560 / (4 x 32)
    assert reader("swa_expert_load_max_over_mean")(obs) == pytest.approx(
        60 / (2560 / 128))
    assert reader("swa_flash_mfu")(obs) == pytest.approx(
        100 * roofline_swa.flash_flops(shape, [8192, 5000]) / 197e12 / 0.15)
    assert reader("swa_prefill_mfu")(obs) == pytest.approx(
        100 * roofline_swa.prefill_flops(shape, [8192, 5000]) / 197e12 / 0.5)
    held = 32 * 32 * (9024 + 4 * 4160) + 16 * 16 * (6016 + 4 * 4160)
    one = 32 * 32 * 5 * 9024 + 16 * 16 * 5 * 6016
    assert reader("swa_pool_rows_saved_share")(obs) == pytest.approx(
        100 * (1 - held / one))
    assert 40 < reader("swa_pool_rows_saved_share")(obs) < 50


def test_a_read_through_xla_has_no_kernel_to_time(obs):
    obs["paged_read_kernel"] = "xla"
    assert reader("swa_read_roofline")(obs) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_or_counters_gives_nothing(name):
    """A parent commit cannot serve the configuration at all; a run that was
    not traced, a program that names no scope and carries neither kind's
    rows, and a run of another family all give nothing and do not raise."""
    bare = {"serving": {"model": MODEL}, "peaks": PEAKS, "trace": None,
            "samples": [{"phase": "decode", "steps": 8,
                         "active_at_dispatch": 4}],
            "polls": [], "pool": {"block_size": 64}}
    assert reader(name)(bare) is None
    assert reader(name)({**bare, "serving": {"model": "internlm2-1.8b"}}) is None
    assert reader(name)(
        {**bare, "serving": {"model": "solar-open2-250b-ep8"}}) is None
    assert reader(name)({"serving": {}, "samples": [], "trace": None}) is None


@pytest.mark.parametrize("name", NEW)
def test_the_benchmark_lists_the_reader_for_the_new_cell_alone(name):
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    meta = observe.load_metric(observe.find("layer_metrics", name, [BENCH]))
    assert entry["workloads"] == [CELL]
    for key in ("unit", "better", "layer", "moves", "source"):
        assert entry[key] == meta[key], key


def test_the_cell_and_its_traffic_are_the_issue_s(config):
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        MODEL, "longctx-sat", 1)
    entry = next(c for c in bench["configs"] if c["name"] == MODEL)
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    with open(os.path.join(BENCH, "traffic", "longctx-sat.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients_per_slot"], mix["multiset"]) == (
        "closed", 1.5, 48)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 8192,
                                    "sigma": 0.4, "min": 4608, "max": 14336}
    assert mix["output_tokens"] == {"choices": [256, 512, 1024],
                                    "weights": [0.3, 0.4, 0.3]}
    assert mix["shared_prefix_tokens"] == 0
    from lib import traffic

    pairs = traffic.multiset(mix, 48)
    prompts = [p for p, _ in pairs]
    # every prompt is longer than the window; the longest fills 94% of a slot
    assert min(prompts) > config["sliding_window"]
    assert max(p + o for p, o in pairs) + 1 <= config["serving"]["max-seq-len"]
    assert 580 < sum(o for _, o in pairs) / 48 < 600
    # the five host-side lists PR 39 appended to, and not the three pinned
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert {"idle_attributed_share", "idle_admit_ms_s",
            "idle_prefill_host_ms_s", "idle_decode_host_ms_s",
            "occupancy_dispatch_mean"} <= listed
    assert not {"idle_hop_ms_s", "idle_loop_lag_ms_s", "loop_lag_ms_s"} & listed
    # every width as published
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["published_num_experts"], config["num_experts_per_tok"],
            config["sliding_window"], config["route_scale"]) == (
        3072, 48, 8, 128, 12288, 3072, 256, 4, 4096, 2.448)
