"""``bench/lib/roofline_latent.py``: a latent-attention model's operations
and bytes from the configuration's file, against the numbers worked out by
hand in the issue that added the configuration (3,145.5 M held = 6.29 GB,
1,152 B a row a layer, 278,528 operations a row of the read: 242 a byte), and
the nine readers built on it, on a hand-written trace."""

import json
import os

import pytest
from jax.profiler import ProfileData

from lib import (hosttrace, hybridtrace, observe, peaks, roofline_latent,
                 xplane)

HERE = os.path.dirname(__file__)
BENCH = os.path.join(HERE, "..", "..", "bench")
PEAKS = peaks.peaks_for("TPU v5 lite")
NEW = ["latent_read_roofline", "latent_flash_mfu", "latent_attn_dev_ms_step",
       "latent_moe_dev_ms_step", "latent_experts_roofline",
       "latent_decode_roofline", "latent_prefill_mfu",
       "latent_expert_load_max_over_mean", "latent_prefill_interleave_ms_step"]
# what a latent decode program's HLO would say of the fixture's operations
SCOPES = {"jit__decode_chunk(111)": {
    "fusion.1": "mla_q", "latent_read.3": "kv_read", "fusion.2": "mla_absorb",
    "fusion.5": "moe_experts", "fusion.6": "moe_router"}}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "deepseek-v2-ep8.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def shape(config):
    return roofline_latent.LatentShape.from_config(config)


def reader(name):
    return observe.load_metric(
        observe.find("layer_metrics", name, [BENCH]))["read"]


@pytest.mark.parametrize("what, by_hand", [
    # W_qa 5120 x 1536, W_qb 1536 x 128 x 192, W_kva 5120 x 576,
    # W_kvb 512 x 128 x 256, W_o 16384 x 5120, three norms
    ("attn_params", 7_864_320 + 37_748_736 + 2_949_120 + 16_777_216
     + 83_886_080 + 5120 + 1536 + 512),
    ("dense_ffn_params", 3 * 5120 * 12288 + 5120),
    ("expert_params", 3 * 5120 * 1536),
    ("routed_params", 20 * 23_592_960),
    ("shared_params", 3 * 5120 * 3072),
    ("router_params", 5120 * 160),
    ("row_values", 576),
    ("read_flops_row", 278_528),
    ("flash_flops_pair", 2 * 128 * (192 + 128)),
    ("sparse_layers", 4),
])
def test_parameters_are_the_issue_s(shape, what, by_hand):
    assert getattr(shape, what) == by_hand


def test_held_parameters_are_the_issue_s_and_the_program_s(shape):
    import jax

    from langstream_tpu.models.latent import LatentConfig, init_latent_params

    assert shape.held_params == pytest.approx(3145.5e6, abs=0.6e6)
    assert shape.held_params * 2 == pytest.approx(6.29e9, abs=0.005e9)
    leaves = jax.tree.leaves(jax.eval_shape(
        lambda: init_latent_params(LatentConfig.deepseek_v2_ep8())))
    program = 0
    for leaf in leaves:
        n = 1
        for d in leaf.shape:
            n *= d
        program += n
    assert shape.held_params == program


def test_the_configuration_file_keeps_the_published_numbers(config):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V2")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 20, 12800)
    assert (config["published_num_hidden_layers"],
            config["published_n_routed_experts"],
            config["published_vocab_size"]) == (60, 160, 102400)
    # the floors of a cut: a whole period and four layers after the dense
    # one, at least 8 experts, at least an eighth of the vocabulary
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published_vocab_size"]
    assert config["serving"]["max-seq-len"] == 16384
    assert config["output_lengths"] == [128, 256, 512]


def test_the_floors_are_the_issue_s(shape):
    # one call of the read over 623k live rows: 1.41 ns a row, bound by the
    # MXU by a hair (242 operations a byte against the v5e's ridge of 240)
    floor = roofline_latent.latent_read_floor(
        shape, live_rows=623_000, peaks=PEAKS)
    assert floor["bytes"] == 623_000 * 1152
    assert floor["flops"] / floor["bytes"] == pytest.approx(241.8, abs=0.1)
    assert floor["floor_s"] / 623_000 == pytest.approx(1.41e-9, rel=0.01)
    assert floor["bound_by"] == "flops"
    # five layers of it: 4.4 ms; every weight once: 7.5 ms
    assert 5 * floor["floor_s"] == pytest.approx(4.4e-3, rel=0.01)
    step = roofline_latent.decode_step_floor(
        shape, live_rows=623_000, batch=96, routed_pairs=96 * 0.75 * 4,
        peaks=PEAKS)
    weights_s = 2 * (shape.held_params - shape.vocab * shape.hidden) \
        / PEAKS["hbm_bytes_s"]
    assert weights_s == pytest.approx(7.5e-3, rel=0.02)
    assert step["floor_s"] == pytest.approx(weights_s + 4.38e-3, rel=0.02)
    # the experts: the held and the shared ones' weights of four layers once
    experts = roofline_latent.experts_floor(
        shape, routed_pairs=288, batch=96, peaks=PEAKS)
    assert experts["bound_by"] == "bytes"
    assert experts["bytes"] == 2 * 4 * (20 * 23_592_960 + 47_185_920)
    # a prefill in the mean over the mix's 48 prompts: 24.6 TFLOP, about
    # three quarters of it attention (per token at 6,350: projections 298 +
    # pairs 260 MFLOP a layer against 131 in an expert layer, 377 in the
    # dense one)
    from lib import traffic

    with open(os.path.join(BENCH, "traffic", "longdoc-sat.json")) as f:
        prompts = traffic.quantiles(json.load(f)["prompt_tokens"], 48)
    assert (min(prompts), max(prompts)) == (2688, 11939)
    flops = roofline_latent.prefill_flops(shape, prompts, 0.75) / 48
    assert flops == pytest.approx(24.6e12, rel=0.02)
    assert 2 * shape.attn_params == pytest.approx(298e6, rel=0.01)
    assert roofline_latent.prefill_flops(shape, [11939], 0.75) == \
        pytest.approx(57.8e12, rel=0.02)
    assert roofline_latent.mean_routed_pairs_token(shape) == 0.75
    flash = roofline_latent.flash_flops(shape, [6350])
    assert flash / 6350 / 5 == pytest.approx(260e6, rel=0.01)


def test_another_family_has_no_latent_shape():
    for model in ("granite-4.0-h-small-ep2", "internlm2-1.8b",
                  "nemotron-3-nano-30b-a3b-ep8", "no-such-model"):
        assert roofline_latent.shape_of({"serving": {"model": model}}) is None
    assert roofline_latent.shape_of({"serving": {}}) is None


#: the prefill flight samples of ``fixtures/latent/prefills.xplane.txt``:
#: dispatch ordinal -> true tokens (9 ended before the trace, 10 began
#: before it, 15 ends after it; 12 and 13 ran whole inside)
PREFILLS = {9: 5000, 10: 7000, 12: 6000, 13: 3000, 15: 9000}


@pytest.fixture
def obs():
    with open(os.path.join(HERE, "fixtures", "latent", "latent.xplane.txt")) as f:
        profile = ProfileData.from_text_proto(f.read())
    dense = hosttrace.SCOPES
    hosttrace.SCOPES = dense + hybridtrace.SCOPES + roofline_latent.SCOPES
    try:
        reduced = hosttrace.reduce(profile, SCOPES)["scopes"]
    finally:
        hosttrace.SCOPES = dense
    samples = [
        {"phase": "prefill", "steps": 0, "active_at_dispatch": 95,
         "wall_ms": 600.0, "dispatch": dispatch, "prompt_tokens": tokens}
        for dispatch, tokens in PREFILLS.items()]
    with open(os.path.join(HERE, "fixtures", "latent", "prefills.xplane.txt")) as f:
        paired = roofline_latent.paired_prefills(
            ProfileData.from_text_proto(f.read()), samples)
    return {
        "trace": xplane.reduce(profile, 30e-6), "latenttrace": reduced,
        "latentprefills": paired,
        "serving": {"model": "deepseek-v2-ep8"}, "peaks": PEAKS,
        "paged_read_kernel": "pallas",
        "samples": samples + [
            {"phase": "decode", "steps": 32, "active_at_dispatch": 96,
             "wall_ms": 500.0, "live_rows": 600_000, "routed_pairs": 32 * 288,
             "expert_load_max": 900, "state_bytes": 0},
            {"phase": "decode", "steps": 32, "active_at_dispatch": 96,
             "wall_ms": 500.0, "live_rows": 640_000, "routed_pairs": 32 * 300,
             "expert_load_max": 1100, "state_bytes": 0},
        ],
    }


def test_means_a_step_come_from_the_samples_and_steps_from_the_kernel_s_calls(obs):
    load = roofline_latent.per_step(obs)
    assert load["steps"] == 64 and load["slots"] == 96
    # a chunk's rows grow by a row a slot a step: its mean is 15.5 further
    assert load["live_rows"] == pytest.approx(620_000 + 96 * 15.5)
    assert load["routed_pairs"] == pytest.approx(294)
    # 19 us of operations inside the three decode runs, the third cut; the
    # read kernel was called three times there, and a step calls it once a
    # layer of five
    assert roofline_latent.traced_steps(obs) == (
        pytest.approx(19e-6), pytest.approx(3 / 5))
    # a read through XLA, or a trace with no decode program, has no call
    assert roofline_latent.traced_steps({**obs, "trace": None}) == (0.0, 0.0)


def chunk_trace(seen, *, steps=8, layers=5):
    """The text of a trace of decode runs of ``steps`` steps, each step the
    queries' projection (1 us), one call of the read kernel a layer (1 us
    each) and one expert matmul (3 us); ``seen`` gives, run by run, the steps the trace holds of it (a run
    cut by an end of the trace holds fewer)."""
    modules, ops, t = [], [], 0
    for n in seen:
        modules.append(f"events {{ metadata_id: 11 offset_ps: {t} "
                       f"duration_ps: {n * (layers + 4) * 1000000} }}")
        for _ in range(n):
            ops.append(f"events {{ metadata_id: 1 offset_ps: {t} "
                       f"duration_ps: 1000000 }}")
            t += 1000000
            for _ in range(layers):
                ops.append(f"events {{ metadata_id: 2 offset_ps: {t} "
                           f"duration_ps: 1000000 }}")
                t += 1000000
            ops.append(f"events {{ metadata_id: 4 offset_ps: {t} "
                       f"duration_ps: 3000000 }}")
            t += 3000000
        t += 50 * 1000000     # prefills between the chunks
    with open(os.path.join(HERE, "fixtures", "latent", "latent.xplane.txt")) as f:
        head = f.read().split("  lines {")[0]
    return (head + '  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000 '
            + " ".join(modules) + ' }\n  lines { id: 2 name: "XLA Ops" '
            "timestamp_ns: 1000 " + " ".join(ops) + " }\n}\n")


@pytest.mark.parametrize("seen", [[8, 8], [8, 5], [6], [3, 8, 7]],
                         ids=["whole", "one-cut", "only-a-cut-one", "both-ends"])
def test_a_run_cut_by_an_end_of_the_trace_reads_as_a_whole_one(obs, seen):
    """4 s of the cell hold one or two chunks, as a rule with one of them
    cut: the step's time may not follow how much of a run the trace saw (a
    chunk cut at two thirds read the experts at 113% of their floor)."""
    profile = ProfileData.from_text_proto(chunk_trace(seen))
    dense = hosttrace.SCOPES
    hosttrace.SCOPES = dense + hybridtrace.SCOPES + roofline_latent.SCOPES
    try:
        scopes = hosttrace.reduce(profile, SCOPES)["scopes"]
    finally:
        hosttrace.SCOPES = dense
    cut = {**obs, "trace": xplane.reduce(profile, 1e-3), "latenttrace": scopes}
    seconds, steps = roofline_latent.traced_steps(cut)
    assert steps == pytest.approx(sum(seen))
    assert seconds / steps == pytest.approx(9e-6)
    assert reader("latent_moe_dev_ms_step")(cut) == pytest.approx(3e-3)
    assert reader("latent_attn_dev_ms_step")(cut) == pytest.approx(6e-3)
    whole = {**cut, "trace": obs["trace"], "latenttrace": obs["latenttrace"]}
    for name in ("latent_experts_roofline", "latent_decode_roofline"):
        assert reader(name)(cut) == pytest.approx(
            reader(name)(whole) * {
                "latent_experts_roofline": (4000e-9 / 0.6) / 3e-6,
                "latent_decode_roofline": (19e-6 / 0.6) / 9e-6}[name])


def test_a_prefill_run_is_paired_with_the_sample_of_its_own_dispatch(obs):
    """Whole runs only, each with its own prompt: the run that began before
    the trace (no dispatch span), the fetch of a run that ended before it
    and the run whose fetch ends after it are left out; a fetch that ends
    half a millisecond before the device's clock says its run did is still
    that run's."""
    assert obs["latentprefills"] == [
        {"prompt_tokens": 6000, "seconds": pytest.approx(0.35),
         "flash_s": pytest.approx(0.2)},
        {"prompt_tokens": 3000, "seconds": pytest.approx(0.15),
         "flash_s": pytest.approx(0.06)}]
    assert roofline_latent.traced_prefills(obs) is obs["latentprefills"]
    with open(os.path.join(HERE, "fixtures", "latent", "prefills.xplane.txt")) as f:
        text = f.read()
    profile = ProfileData.from_text_proto(text)
    # a fetch the loop came to 65 ms after its run's end is nobody's
    late = text.replace("offset_ps: 133000000000 duration_ps: 348000000000",
                        "offset_ps: 133000000000 duration_ps: 412000000000")
    assert late != text
    assert [r["prompt_tokens"] for r in roofline_latent.paired_prefills(
        ProfileData.from_text_proto(late), obs["samples"])] == [3000]
    # a program that carries no prompt_tokens, or no ordinal, pairs nothing
    assert roofline_latent.paired_prefills(
        profile, [{"phase": "prefill", "dispatch": 12}]) == []
    assert roofline_latent.paired_prefills(
        profile, [{"phase": "prefill", "prompt_tokens": 6000}]) == []
    # a trace without the host's spans (the device plane alone)
    with open(os.path.join(HERE, "fixtures", "latent", "latent.xplane.txt")) as f:
        assert roofline_latent.paired_prefills(
            ProfileData.from_text_proto(f.read()),
            [{"phase": "prefill", "dispatch": 1, "prompt_tokens": 8}]) == []


def test_each_reader_reads_the_fixture(obs, shape):
    by_scope = obs["latenttrace"]["by_scope"]
    assert {k: round(v * 1e9) for k, v in by_scope.items()} == {
        "mla_q": 3000, "kv_read": 8000, "mla_absorb": 2000,
        "moe_experts": 4000, "moe_router": 2000}
    # the read kernel's three calls there are 3 / 5 of a step of five layers
    steps = 3 / 5
    assert reader("latent_attn_dev_ms_step")(obs) == pytest.approx(
        1e3 * 13000e-9 / steps)
    assert reader("latent_moe_dev_ms_step")(obs) == pytest.approx(
        1e3 * 6000e-9 / steps)
    load = roofline_latent.per_step(obs)
    floor = roofline_latent.latent_read_floor(
        shape, live_rows=load["live_rows"], peaks=PEAKS)
    # three calls of latent_read.3 in 8 us
    assert reader("latent_read_roofline")(obs) == pytest.approx(
        100 * floor["floor_s"] / (8000e-9 / 3))
    floor = roofline_latent.experts_floor(
        shape, routed_pairs=294, batch=96, peaks=PEAKS)
    assert reader("latent_experts_roofline")(obs) == pytest.approx(
        100 * floor["floor_s"] / (4000e-9 / steps))
    floor = roofline_latent.decode_step_floor(
        shape, live_rows=load["live_rows"], batch=96, routed_pairs=294,
        peaks=PEAKS)
    assert reader("latent_decode_roofline")(obs) == pytest.approx(
        100 * floor["floor_s"] / (19e-6 / steps))
    # the two whole prefill runs, 0.35 + 0.15 s with 0.2 + 0.06 s of the
    # flash kernel, against their own prompts' operations
    flash = roofline_latent.flash_flops(shape, [6000, 3000])
    assert reader("latent_flash_mfu")(obs) == pytest.approx(
        100 * flash / PEAKS["bf16_flops_s"] / 0.26)
    whole = roofline_latent.prefill_flops(shape, [6000, 3000], 0.75)
    assert reader("latent_prefill_mfu")(obs) == pytest.approx(
        100 * whole / PEAKS["bf16_flops_s"] / 0.5)
    assert 20 < reader("latent_prefill_mfu")(obs) < 100
    # the window's five prefill samples took 0.6 s each beside 64 steps
    assert reader("latent_prefill_interleave_ms_step")(obs) == pytest.approx(
        5 * 600.0 / 64)
    # (900 + 1100) over the mean an expert of a layer got: 18816 / (4 x 20)
    assert reader("latent_expert_load_max_over_mean")(obs) == pytest.approx(
        2000 / (18816 / 80))
    # the read through XLA has no kernel to hold to a floor
    assert reader("latent_read_roofline")(
        {**obs, "paged_read_kernel": "xla"}) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_or_counters_gives_nothing(name):
    """A parent commit cannot serve the configuration at all; a run that was
    not traced, a program that carries no such counters, and a run of
    another family all give nothing and do not raise."""
    bare = {"serving": {"model": "deepseek-v2-ep8"}, "peaks": PEAKS,
            "trace": None, "paged_read_kernel": "pallas",
            "samples": [{"phase": "decode", "steps": 8,
                         "active_at_dispatch": 4},
                        {"phase": "prefill", "steps": 0}],
            "polls": [], "pool": {"block_size": 64}}
    assert reader(name)(bare) is None
    assert reader(name)({**bare, "serving": {"model": "internlm2-1.8b"}}) is None
    assert reader(name)(
        {**bare, "serving": {"model": "granite-4.0-h-small-ep2"}}) is None
    assert reader(name)({"serving": {}, "samples": [], "trace": None}) is None
    # a trace that holds no device plane (a rehearsal on the CPU)
    assert reader(name)({**bare, "latenttrace": None, "latentprefills": [], "trace": {
        "devices": 0, "busy_s": 0.0, "window_s": 2.0, "planes": []}}) is None


def test_a_trace_of_another_family_s_program_gives_no_scope_time(obs):
    """The configuration's name with a program that names none of the latent
    scopes (a stand-in): the scope readers give nothing."""
    obs["latenttrace"] = {"by_scope": {"kv_read": 1e-6, "ffn": 2e-6},
                          "unscoped": {}}
    for name in ("latent_attn_dev_ms_step", "latent_moe_dev_ms_step",
                 "latent_experts_roofline"):
        assert reader(name)(obs) is None
