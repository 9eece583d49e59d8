"""``bench/lib/roofline_wf.py``: the operations and bytes of the window-and-
full family's plain member (no gate, no post norm, no dense layer, no shared
expert, every expert held), from the configuration's file, against the numbers
worked out by hand in the issue that added the configuration (21.23 M of
attention + 0.15 M of router + 64 x 6.193 M of experts = 417.7 M a layer,
3,795 M held = 7.59 GB, 2,048 B a row a layer, a ring of 17 blocks); and the
readers built on it, on a hand-built reduction of a trace."""

import json
import os

import pytest

from lib import observe, peaks, roofline_swa, roofline_wf

HERE = os.path.dirname(__file__)
BENCH = os.path.join(HERE, "..", "..", "bench")
PEAKS = peaks.peaks_for("TPU v5 lite")
CELL = "mellum2-codemix-sat"
MODEL = "mellum2-12b-a2.5b-8l"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", f"{MODEL}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def shape(config):
    return roofline_wf.WfShape.from_config(config)


def test_the_layer_kinds_follow_the_published_list_over_the_layers_held(
        shape, config):
    kinds = config["layer_types"]
    assert len(kinds) == 28 and kinds[3::4] == ["full_attention"] * 7
    assert config["mlp_layer_types"] == ["sparse"] * 28          # kept whole
    assert config["first_layer"] == 0 and config["num_hidden_layers"] == 8
    assert config["published_num_hidden_layers"] == 28
    # layers 0-7: two periods of three window layers and a full one
    assert (shape.window_layers, shape.full_layers, shape.layers) == (6, 2, 8)
    assert (shape.dense_layers, shape.sparse_layers) == (0, 8)
    assert (shape.experts, shape.experts_held, shape.experts_per_token,
            shape.window) == (64, 64, 8, 1024)


@pytest.mark.parametrize("what, by_hand", [
    # q 2304 x 4096 + k, v 2 x 2304 x 512 + o 4096 x 2304: no gate
    ("attn_matmul_params", 21233664),
    ("expert_params", 3 * 2304 * 896),                      # 6.193 M
    ("routed_params", 64 * 6193152),                        # 396.4 M
    ("router_params", 2304 * 64),                           # 0.15 M
    ("shared_params", 0), ("dense_ffn_params", 0), ("dense_layers", 0),
    ("row_bytes", 2048.0),
])
def test_parameters_are_the_issue_s(shape, what, by_hand):
    assert getattr(shape, what) == by_hand


def test_the_frozen_shape_would_count_what_this_member_lacks(shape, config):
    """``roofline_swa.SwaShape`` on the same widths counts a gate of 9.4 M a
    layer and two post norms: a floor from it would be too high."""
    swa = roofline_swa.SwaShape.from_config(dict(
        config, num_dense_layers=0, num_shared_experts=0))
    assert swa.attn_matmul_params - shape.attn_matmul_params == 2304 * 4096
    assert swa.held_params > shape.held_params + 8 * 9.4e6


def test_held_parameters_are_the_program_s(shape):
    """3,795 M = 7.59 GB, and leaf for leaf what ``init_swa_params`` makes."""
    import jax
    import numpy as np

    from langstream_tpu.models.swa import SwaConfig, init_swa_params

    c = SwaConfig.mellum2_12b_a2_5b_8l()
    leaves = jax.tree.leaves(jax.eval_shape(lambda: init_swa_params(c)))
    assert shape.held_params == sum(int(np.prod(a.shape)) for a in leaves)
    assert shape.held_bytes == sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)
    assert 3.794e9 < shape.held_params < 3.796e9
    assert 7.58e9 < shape.held_bytes < 7.60e9
    # a layer: 417.7 M, 0.836 GB
    layer = (shape.attn_layer_params + shape.routed_params
             + shape.router_params + shape.hidden)
    assert 417.6e6 < layer < 417.9e6


def test_the_pools_are_the_issue_s(shape, config):
    serving = config["serving"]
    block = serving["kv-block-size"]
    full = serving["kv-pool-blocks"] * block * shape.full_layers * shape.row_bytes
    ring = shape.window // block + 1
    window = (serving["slots"] * ring + 1) * block * \
        shape.window_layers * shape.row_bytes
    assert ring == 17 and serving["slots"] >= 128
    assert window == (serving["slots"] * 17 + 1) * 64 * 6 * 2048
    assert 0.70 * 16.9e9 < shape.held_bytes + full + window < 0.85 * 16.9e9
    # 13.4 MB a slot of window rings
    assert 13.3e6 < 17 * 64 * 6 * 2048 < 13.4e6
    # a slot holds the longest prompt, the longest answer and a row more
    assert serving["max-seq-len"] >= 8192 + 512 + 1
    assert serving["max-seq-len"] - (8192 + 512 + 1) < block


def test_the_floors(shape):
    # 192 slots of 1,550 rows: both full layers read them all, each of the
    # six window layers 1,024 a slot at most
    read = roofline_wf.read_floor(
        shape, full_rows=192 * 1550, window_rows=192 * 900, peaks=PEAKS)
    assert read["bytes"] == (2 * 192 * 1550 + 6 * 192 * 900) * 2048
    assert read["bound_by"] == "bytes"
    # 1,536 pairs a layer touch every one of the 64 experts
    assert 63.99 < roofline_swa.touched_experts(shape, 1536) <= 64
    experts = roofline_wf.experts_floor(
        shape, routed_pairs=8 * 1536, batch=192, peaks=PEAKS)
    assert experts["bytes"] == pytest.approx(2 * 8 * 64 * 6193152, rel=1e-6)
    assert experts["flops"] == 8 * 1536 * 2 * 6193152        # 8 of 64 a row
    assert experts["bound_by"] == "bytes"
    assert 0.0076 < experts["floor_s"] < 0.0078              # 6.34 GB a step
    # what the dense pass spends: 64 of 64 experts a row, 8 times as much
    dense = roofline_wf.dense_pass_flops(shape, 192)
    assert dense == 8 * experts["flops"] == 8 * 192 * 64 * 2 * 6193152
    assert 0.0060 < dense / PEAKS["bf16_flops_s"] < 0.0064   # 1.22 TFLOP
    args = dict(full_rows=192 * 1550, window_rows=192 * 900, batch=192,
                routed_pairs=8 * 1536, peaks=PEAKS)
    plain = roofline_wf.decode_step_floor(shape, **args)
    assert plain["bytes"] == pytest.approx(
        shape.held_bytes - 2 * 2304 * (98304 - 192)
        + (2 * 192 * 1550 + 6 * 192 * 900 + 8 * 192) * 2048, rel=1e-9)
    step = roofline_wf.decode_floor(shape, rows=192, **args)
    assert step["bytes"] == plain["bytes"]
    assert step["flops"] == pytest.approx(
        plain["flops"] + dense - experts["flops"])
    assert step["bound_by"] == "bytes" and step["floor_s"] == plain["floor_s"]
    # a batch past the dense pass's rows counts the routed pairs alone
    assert roofline_wf.decode_floor(shape, rows=1024, **args)["flops"] == \
        plain["flops"]
    # a token's prefill through the eight layers: 1.14 GFLOP, attention apart
    per_token = (roofline_wf.prefill_flops(shape, [4096])
                 - roofline_wf.flash_flops(shape, [4096])
                 - 2 * 2304 * 98304) / 4096
    assert per_token == 2 * 8 * (21233664 + 2304 * 64 + 8 * 6193152)
    assert 1.13e9 < per_token < 1.15e9
    flash = roofline_wf.flash_flops(shape, [4096])
    assert flash == 4 * 32 * 128 * (
        2 * 4096 * 4097 / 2 + 6 * (1024 * 1025 / 2 + 3072 * 1024))
    # a prompt under the window: every layer's pairs are the causal ones
    assert roofline_wf.flash_flops(shape, [500]) == \
        4 * 32 * 128 * 8 * 500 * 501 / 2


def test_another_family_or_member_has_no_such_shape():
    shape_of = roofline_wf.shape_of
    for model in ("internlm2-1.8b", "granite-4.0-h-small-ep2",
                  "deepseek-v2-ep8", "solar-open2-250b-ep8",
                  "trinity-large-preview-ep8", "trinity-tiny"):
        assert shape_of({"serving": {"model": model}}) is None
    assert shape_of({"serving": {}}) is None
    tiny = shape_of({"serving": {"model": "mellum-tiny"}})
    assert (tiny.window_layers, tiny.full_layers, tiny.window) == (6, 2, 32)
    assert (tiny.experts, tiny.experts_held) == (8, 8)


# -- the readers, on a hand-built reduction ---------------------------------

NEW = ["wf_decode_roofline", "wf_experts_roofline", "wf_read_roofline",
       "wf_flash_mfu", "wf_prefill_mfu", "wf_attn_dev_ms_step",
       "wf_moe_dev_ms_step", "wf_expert_load_max_over_mean",
       "wf_short_slots_share", "wf_window_blocks_used_share"]


def reader(name):
    return observe.load_metric(
        observe.find("layer_metrics", name, [BENCH]))["read"]


@pytest.fixture
def obs():
    decode = "jit__decode_chunk(7)"
    ops = [
        # 64 calls of the read kernel: 8 steps of 8 layers
        {"program": decode, "name": "paged_read.3", "total_s": 40e-3, "calls": 64},
        {"program": decode, "name": "custom-call.9", "total_s": 1e-3, "calls": 80},
        {"program": decode, "name": "fusion.1", "total_s": 119e-3, "calls": 400},
        {"program": "jit__prefill(3)", "name": "flash_prefill.2",
         "total_s": 90e-3, "calls": 8},
    ]
    return {
        "trace": {"planes": [{"ops": ops, "programs": {}}]},
        "paged_read_kernel": "pallas",
        "wftrace.decode_chunk": {"by_scope": {
            "swa_read": 28e-3, "full_read": 14e-3, "attn_buf": 2e-3,
            "rope": 1e-3, "rope_full": 0.5e-3,
            "moe_experts": 80e-3, "moe_router": 1e-3, "moe_dispatch": 2e-3,
            "moe_combine": 3e-3}, "unscoped": {"copy.1": 1e-3}},
        "wfprefills": [
            {"prompt_tokens": 7000, "seconds": 0.12, "flash_s": 0.04},
            {"prompt_tokens": 500, "seconds": 0.011, "flash_s": 0.0005}],
        "serving": {"model": MODEL, "slots": 192, "kv-block-size": 64},
        "peaks": PEAKS,
        "samples": [
            {"phase": "decode", "steps": 32, "active_at_dispatch": 192,
             "live_rows": 192 * 1500, "window_rows": 192 * 800,
             "routed_pairs": 32 * 192 * 8 * 8, "expert_load_max": 1000,
             "state_bytes": 0, "short_slots": 96, "window_blocks_held": 2400},
            {"phase": "decode", "steps": 16, "active_at_dispatch": 160,
             "live_rows": 160 * 1200, "window_rows": 160 * 700,
             "routed_pairs": 16 * 160 * 8 * 8, "expert_load_max": 440,
             "state_bytes": 0, "short_slots": 100, "window_blocks_held": 2000},
            {"phase": "prefill", "steps": 0, "active_at_dispatch": 10},
        ],
    }


def test_steps_are_the_read_kernel_s_calls_over_the_layers(obs):
    seconds, steps = roofline_wf.traced_steps(obs)
    assert steps == 8 and seconds == pytest.approx(160e-3)
    load = roofline_wf.per_step(obs)
    assert load["slots"] == pytest.approx((192 * 32 + 160 * 16) / 48)
    assert load["routed_pairs"] == pytest.approx(
        (32 * 192 + 16 * 160) * 64 / 48)


def test_each_reader_reads_the_reduction(obs, shape):
    load = roofline_wf.per_step(obs)
    floor = roofline_wf.read_floor(
        shape, full_rows=load["full_rows"], window_rows=load["window_rows"],
        peaks=PEAKS)
    assert reader("wf_read_roofline")(obs) == pytest.approx(
        100 * floor["floor_s"] / (40e-3 / 8))
    assert reader("wf_attn_dev_ms_step")(obs) == pytest.approx(42 / 8)
    assert reader("wf_moe_dev_ms_step")(obs) == pytest.approx(86 / 8)
    floor = roofline_wf.experts_floor(
        shape, routed_pairs=load["routed_pairs"], batch=load["slots"],
        peaks=PEAKS)
    assert reader("wf_experts_roofline")(obs) == pytest.approx(
        100 * floor["floor_s"] / (80e-3 / 8))
    floor = roofline_wf.decode_floor(
        shape, full_rows=load["full_rows"], window_rows=load["window_rows"],
        batch=load["slots"], routed_pairs=load["routed_pairs"], rows=192,
        peaks=PEAKS)
    assert reader("wf_decode_roofline")(obs) == pytest.approx(
        100 * floor["floor_s"] / (160e-3 / 8))
    for name in ("wf_decode_roofline", "wf_experts_roofline",
                 "wf_read_roofline"):
        assert 0 < reader(name)(obs) < 100, name
    # (1000 + 440) over the mean an expert of a layer got: pairs / (8 x 64)
    pairs = 32 * 192 * 64 + 16 * 160 * 64
    assert reader("wf_expert_load_max_over_mean")(obs) == pytest.approx(
        1440 / (pairs / 512))
    assert reader("wf_flash_mfu")(obs) == pytest.approx(
        100 * roofline_wf.flash_flops(shape, [7000, 500]) / 197e12 / 0.0405)
    assert reader("wf_prefill_mfu")(obs) == pytest.approx(
        100 * roofline_wf.prefill_flops(shape, [7000, 500]) / 197e12 / 0.131)
    assert 0 < reader("wf_prefill_mfu")(obs) < 100
    assert reader("wf_short_slots_share")(obs) == pytest.approx(
        100 * (96 / 192 * 32 + 100 / 160 * 16) / 48)
    assert reader("wf_window_blocks_used_share")(obs) == pytest.approx(
        100 * (2400 * 32 + 2000 * 16) / 48 / (192 * 17))


def test_a_read_through_xla_has_no_kernel_to_time(obs):
    obs["paged_read_kernel"] = "xla"
    assert reader("wf_read_roofline")(obs) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_or_counters_gives_nothing(name):
    """A parent commit cannot serve the configuration at all; a run that was
    not traced, a program that names no scope and carries neither gauge, and
    a run of another family or member all give nothing and do not raise."""
    bare = {"serving": {"model": MODEL, "slots": 192}, "peaks": PEAKS,
            "trace": None,
            "samples": [{"phase": "decode", "steps": 8,
                         "active_at_dispatch": 4}],
            "polls": [], "pool": {"block_size": 64}}
    assert reader(name)(bare) is None
    for other in ("internlm2-1.8b", "solar-open2-250b-ep8",
                  "trinity-large-preview-ep8"):
        assert reader(name)({**bare, "serving": {"model": other,
                                                 "slots": 32}}) is None
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
        MODEL, "codemix-sat", 1)
    assert bench["workloads"][-1] is cell and bench["configs"][-1]["name"] == MODEL
    entry = bench["configs"][-1]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"]
    with open(os.path.join(BENCH, "traffic", "codemix-sat.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients_per_slot"], mix["multiset"]) == (
        "closed", 1.5, 96)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.9, "min": 64, "max": 8192}
    assert mix["output_tokens"] == {"choices": [64, 128, 256, 512],
                                    "weights": [0.25, 0.3, 0.3, 0.15]}
    assert mix["shared_prefix_tokens"] == 0
    assert config["output_lengths"] == [64, 128, 256, 512]
    from lib import traffic

    pairs = traffic.multiset(mix, 96)
    prompts = sorted(p for p, _ in pairs)
    assert (prompts[0], prompts[-1]) == (108, 7122)
    assert sum(p < config["sliding_window"] for p in prompts) == 48
    assert sum(p > 2048 for p in prompts) == 20
    assert sum(p > 4096 for p in prompts) == 5
    assert 1428 < sum(prompts) / 96 < 1430
    assert 206 < sum(o for _, o in pairs) / 96 < 209
    buckets = {}
    for p in prompts:
        b = 32
        while b < p:
            b *= 2
        buckets[b] = buckets.get(b, 0) + 1
    assert buckets == {128: 1, 256: 5, 512: 15, 1024: 27, 2048: 28,
                       4096: 15, 8192: 5}
    assert max(p + o for p, o in pairs) + 1 <= config["serving"]["max-seq-len"]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert {"idle_attributed_share", "idle_admit_ms_s",
            "idle_prefill_host_ms_s", "idle_decode_host_ms_s",
            "occupancy_dispatch_mean"} | set(NEW) == listed
    # every width as published, and nothing of a layer shared between chips
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts"], config["num_experts_per_tok"],
            config["sliding_window"], config["vocab_size"],
            config["rms_norm_eps"]) == (
        2304, 32, 4, 128, 7168, 896, 64, 8, 1024, 98304, 1e-6)
    assert config["rope_parameters"]["full_attention"] == {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
    assert config["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 500000}


def test_the_file_holds_every_number_of_the_catalog_s_config(config):
    """Where the guides' catalog is at hand: every key of its entry but the
    one ``reduced`` names, under the same key."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["source_url"] == config["source"])
    differing = {k for k, v in entry["config"].items() if config.get(k) != v}
    assert differing == {"num_hidden_layers"}
    assert config["reference_tolerance"]["rms_share"] > 0
    # the reference states the same published numbers, by itself
    from reference import mellum

    assert mellum.ROPE == {
        k: {kk: (float(vv) if isinstance(vv, (int, float))
                 and kk != "original_max_position_embeddings" else vv)
            for kk, vv in v.items()}
        for k, v in config["rope_parameters"].items()}
