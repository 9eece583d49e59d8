"""``bench/lib/roofline_eva.py``: the operations and bytes of an EVA decoder
from the configuration's file, against the numbers worked out by hand in the
issue that added the configuration (202.4 M a layer: attention 67.1 M, SwiGLU
135.3 M; 1,631 M held = 3.26 GB; 16,384 B a row a layer; a ring of 32 blocks;
128 summary rows a window); and the readers built on it, on a hand-built
reduction of a trace."""

import json
import os

import pytest

from lib import observe, peaks, roofline_eva, traffic

HERE = os.path.dirname(__file__)
BENCH = os.path.join(HERE, "..", "..", "bench")
PEAKS = peaks.peaks_for("TPU v5 lite")
CELL = "evabyte-bytedoc-sat"
MODEL = "evabyte-6.5b-8l"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", f"{MODEL}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def shape(config):
    return roofline_eva.EvaShape.from_config(config)


@pytest.mark.parametrize("what, by_hand", [
    # four square projections, phi and mu, three of the gated MLP
    ("layer_params", 4 * 4096 * 4096 + 2 * 4096 + 3 * 4096 * 11008),
    ("head_params", 4096 * 8 * 320),                        # 10.5 M
    ("row_bytes", 16384), ("per_window", 128), ("layers", 8),
])
def test_parameters_are_the_issue_s(shape, what, by_hand):
    assert getattr(shape, what) == by_hand


def test_held_parameters_are_the_program_s(shape):
    """1,631 M = 3.26 GB, and leaf for leaf what ``init_eva_params`` makes
    (the norms' gains and the embedding beside what a step's matmuls touch)."""
    import jax
    import numpy as np

    from langstream_tpu.models.eva import EvaConfig, init_eva_params

    c = EvaConfig.evabyte_6_5b_8l()
    leaves = jax.tree.leaves(jax.eval_shape(lambda: init_eva_params(c)))
    held = sum(int(np.prod(a.shape)) for a in leaves)
    assert held == shape.step_params + 320 * 4096 + (2 * 8 + 1) * 4096
    assert 3.26e9 < 2 * held < 3.27e9


def test_the_pools_are_the_file_s(shape, config):
    from langstream_tpu.models.eva import EvaConfig

    serving = config["serving"]
    c = EvaConfig.evabyte_6_5b_8l()
    bs, slots = serving["kv-block-size"], serving["slots"]
    assert c.ring_blocks(bs) == 32 and c.summary_blocks(bs) == 32
    assert serving["max-seq-len"] == config["max_position_embeddings"] == 32768
    block = shape.layers * bs * shape.row_bytes                 # 8.39 MB
    ring = (slots * 32 + 1) * block
    summary = serving["kv-pool-blocks"] * block
    assert block == 8388608
    # what the file's memory line states: ring 6.45 GB, summaries 4.03 GB
    assert slots == 24 and serving["kv-pool-blocks"] == 481
    assert 6.44e9 < ring < 6.46e9 and 4.03e9 < summary < 4.04e9
    resident = 2 * (shape.step_params + 320 * 4096) + ring + summary
    assert 0.80 < resident / 16.909e9 < 0.82


def test_the_floors(shape):
    # 24 slots at 14k positions: 1,024 exact and 768 summary rows each
    read = roofline_eva.read_floor(
        shape, window_rows=24 * 1024, summary_rows=24 * 768, peaks=PEAKS)
    assert read["bytes"] == 8 * 24 * 1792 * 16384 and read["bound_by"] == "bytes"
    step = roofline_eva.decode_floor(
        shape, window_rows=24 * 1024, summary_rows=24 * 768, batch=24,
        peaks=PEAKS)
    assert step["bound_by"] == "bytes"
    assert step["bytes"] == pytest.approx(
        2 * shape.step_params + read["bytes"]
        + 8 * 24 * 16384 * (1 + 17 / 16))
    # the two-pool read is three fifths of the step's bytes there
    assert 0.58 < read["bytes"] / step["bytes"] < 0.65
    # a prompt of two windows and a half: each window causal, the second and
    # the half against the summaries before them
    exact, summary = roofline_eva.attended_pairs(shape, 2 * 2048 + 1000)
    assert exact == 2 * 2048 * 2049 / 2 + 1000 * 1001 / 2
    assert summary == 128 * (2048 * 1 + 1000 * 2)
    assert roofline_eva.flash_flops(shape, [5096]) == (
        8 * 32 * 4 * 128 * (exact + summary))
    flops = roofline_eva.prefill_flops(shape, [5096])
    assert flops > 2 * 5096 * 8 * shape.layer_params
    assert roofline_eva.flash_flops(shape, [5096]) / flops < 0.05


def test_another_family_has_no_such_shape():
    shape_of = roofline_eva.shape_of
    for model in ("internlm2-1.8b", "deepseek-v2-ep8", "solar-open2-250b-ep8",
                  "trinity-large-preview-ep8", "mellum2-12b-a2.5b-8l"):
        assert shape_of({"serving": {"model": model}}) is None
    assert shape_of({"serving": {}}) is None
    tiny = shape_of({"serving": {"model": "evabyte-tiny"}})
    assert (tiny.window, tiny.chunk, tiny.pred_heads) == (32, 4, 2)


# -- the readers, on a hand-built reduction ---------------------------------

NEW = ["eva_read_roofline", "eva_decode_roofline", "eva_attn_dev_ms_step",
       "eva_summarise_dev_ms_step", "eva_flash_mfu", "eva_prefill_mfu",
       "eva_pool_rows_saved_share", "eva_summary_rows_share"]


def reader(name):
    return observe.load_metric(
        observe.find("layer_metrics", name, [BENCH]))["read"]


@pytest.fixture
def obs():
    decode = "jit__decode_chunk(7)"
    ops = [
        # 128 calls of the read kernel: 8 steps of 8 layers of two reads
        {"program": decode, "name": "paged_read.3", "total_s": 60e-3, "calls": 128},
        {"program": decode, "name": "pool_commit.9", "total_s": 2e-3, "calls": 16},
        {"program": decode, "name": "fusion.1", "total_s": 58e-3, "calls": 400},
        {"program": "jit__prefill(3)", "name": "eva_flash.2",
         "total_s": 90e-3, "calls": 8},
    ]
    return {
        "trace": {"planes": [{"ops": ops, "programs": {}}]},
        "paged_read_kernel": "pallas",
        "evatrace.decode_chunk": {"by_scope": {
            "eva_read": 64e-3, "eva_summarise": 4e-3, "eva_write": 2e-3,
            "ffn": 30e-3, "attn_qkv": 10e-3}, "unscoped": {"copy.1": 1e-3}},
        "evaprefills": [
            {"prompt_tokens": 12000, "seconds": 0.42, "flash_s": 0.04},
            {"prompt_tokens": 5000, "seconds": 0.21, "flash_s": 0.015}],
        "serving": {"model": MODEL, "slots": 24, "kv-block-size": 64},
        "peaks": PEAKS,
        "samples": [
            {"phase": "decode", "steps": 32, "active_at_dispatch": 24,
             "live_rows": 24 * 14000, "window_rows": 24 * 1000,
             "summary_rows": 24 * 768, "pool_rows_held": 24 * 8 * 64 * 46,
             "pool_rows_plain_cache": 24 * 8 * 64 * 219, "chunk_closes": 1,
             "window_closes": 0},
            {"phase": "decode", "steps": 16, "active_at_dispatch": 18,
             "live_rows": 18 * 9000, "window_rows": 18 * 800,
             "summary_rows": 18 * 512, "pool_rows_held": 18 * 8 * 64 * 42,
             "pool_rows_plain_cache": 18 * 8 * 64 * 141, "chunk_closes": 2,
             "window_closes": 1},
            {"phase": "prefill", "steps": 0, "active_at_dispatch": 10},
        ],
    }


def test_steps_are_the_read_kernel_s_calls_over_two_reads_a_layer(obs):
    seconds, steps = roofline_eva.traced_steps(obs)
    assert steps == 8 and seconds == pytest.approx(120e-3)
    load = roofline_eva.per_step(obs)
    assert load["slots"] == pytest.approx((24 * 32 + 18 * 16) / 48)
    assert load["window_rows"] == pytest.approx(
        (24 * 1000 * 32 + 18 * 800 * 16) / 48)


def test_each_reader_reads_the_reduction(obs, shape):
    load = roofline_eva.per_step(obs)
    floor = roofline_eva.read_floor(
        shape, window_rows=load["window_rows"],
        summary_rows=load["summary_rows"], peaks=PEAKS)
    assert reader("eva_read_roofline")(obs) == pytest.approx(
        100 * floor["floor_s"] / (64e-3 / 8))
    assert reader("eva_attn_dev_ms_step")(obs) == pytest.approx(64 / 8)
    assert reader("eva_summarise_dev_ms_step")(obs) == pytest.approx(4 / 8)
    floor = roofline_eva.decode_floor(
        shape, window_rows=load["window_rows"],
        summary_rows=load["summary_rows"], batch=load["slots"], peaks=PEAKS)
    assert reader("eva_decode_roofline")(obs) == pytest.approx(
        100 * floor["floor_s"] / (120e-3 / 8))
    assert reader("eva_flash_mfu")(obs) == pytest.approx(
        100 * roofline_eva.flash_flops(shape, [12000, 5000]) / 197e12 / 0.055)
    assert reader("eva_prefill_mfu")(obs) == pytest.approx(
        100 * roofline_eva.prefill_flops(shape, [12000, 5000]) / 197e12 / 0.63)
    for name in ("eva_read_roofline", "eva_decode_roofline", "eva_flash_mfu",
                 "eva_prefill_mfu"):
        assert 0 < reader(name)(obs) < 100, name
    held = 24 * 46 * 32 + 18 * 42 * 16
    plain = 24 * 219 * 32 + 18 * 141 * 16
    assert reader("eva_pool_rows_saved_share")(obs) == pytest.approx(
        100 * (1 - held / plain))
    summary = 24 * 768 * 32 + 18 * 512 * 16
    exact = 24 * 1000 * 32 + 18 * 800 * 16
    assert reader("eva_summary_rows_share")(obs) == pytest.approx(
        100 * summary / (summary + exact))


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_or_counters_gives_nothing(name):
    """A parent commit cannot serve the configuration at all; a run that was
    not traced, a program that names no scope and carries no gauge, and a
    run of another family all give nothing and do not raise."""
    bare = {"serving": {"model": MODEL, "slots": 24}, "peaks": PEAKS,
            "trace": None,
            "samples": [{"phase": "decode", "steps": 8,
                         "active_at_dispatch": 4}],
            "polls": [], "pool": {"block_size": 64}}
    assert reader(name)(bare) is None
    for other in ("internlm2-1.8b", "solar-open2-250b-ep8",
                  "mellum2-12b-a2.5b-8l"):
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
    names = [m["name"] for m in bench["per_layer"]]
    assert [n for n in names if n.startswith("eva_")] == NEW


def test_the_cell_and_its_traffic_are_the_issue_s(config):
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    # (found by name: a later PR appends behind them)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        MODEL, "bytedoc-sat", 1)
    entry = next(c for c in bench["configs"] if c["name"] == MODEL)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"]
    assert [w["config"] for w in bench["workloads"]].count(MODEL) == 1
    for name in ("idle_attributed_share", "idle_admit_ms_s",
                 "idle_prefill_host_ms_s", "idle_decode_host_ms_s",
                 "occupancy_dispatch_mean"):
        listed = next(m for m in bench["per_layer"] if m["name"] == name)
        assert CELL in listed["workloads"]
    for name in ("idle_hop_ms_s", "idle_loop_lag_ms_s", "loop_lag_ms_s"):
        listed = next(m for m in bench["per_layer"] if m["name"] == name)
        assert CELL not in listed["workloads"]
    with open(os.path.join(BENCH, "traffic", "bytedoc-sat.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients_per_slot"], mix["multiset"],
            mix["shared_prefix_tokens"]) == ("closed", 1.5, 48, 0)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 12288,
                                    "sigma": 0.5, "min": 4096, "max": 28672}
    assert mix["output_tokens"] == {"choices": [512, 1024, 2048],
                                    "weights": [0.2, 0.3, 0.5]}
    plan = traffic.plan(mix, seed=7, seconds=51, slots=config["serving"]["slots"],
                        max_seq_len=32768, output_lengths=config["output_lengths"])
    prompts = sorted(r["prompt_tokens"] for r in plan["requests"][:48])
    outs = sorted(r["output_tokens"] for r in plan["requests"][:48])
    assert plan["clients"] == 36 and len(plan["requests"]) >= 48
    assert prompts[0] >= 4096 and prompts[-1] <= 28672
    assert [outs.count(n) for n in (512, 1024, 2048)] == [10, 14, 24]
    # every prompt past two windows; the buckets 8,192 / 16,384 / 32,768
    buckets = [sum(lo < p <= hi for p in prompts)
               for lo, hi in ((4095, 8192), (8192, 16384), (16384, 32768))]
    assert sum(buckets) == 48 and all(buckets)


def test_the_file_holds_every_number_of_the_catalog_s_config(config):
    """The published widths unchanged; ``reduced`` is the depth alone."""
    published = {
        "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
        "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
        "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
        "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
        "lazy_init": True, "max_position_embeddings": 32768,
        "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
        "norm_add_unit_offset": True, "num_attention_heads": 32,
        "num_chunks": None, "num_hidden_layers": 32,
        "num_key_value_heads": 32, "num_pred_heads": 8, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 100000,
        "tie_word_embeddings": False, "vocab_size": 320, "window_size": 2048}
    differs = {k for k, v in published.items() if config.get(k) != v}
    assert differs == set(config["reduced"]) == {"num_hidden_layers"}
    assert config["num_hidden_layers"] == 8
    assert config["published_num_hidden_layers"] == 32
    assert config["reference"] == config["architecture"] == "evabyte"
    assert len(config["assumed"]) >= 6
    for key in ("rms_share", "mean_rms_share", "min_correlation",
                "heads_rms_share", "ring_rows_rms_share",
                "summary_rows_rms_share", "logits_bfloat16_grid_share",
                "why"):
        assert key in config["reference_tolerance"]
