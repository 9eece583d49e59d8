"""A latent-attention cell defined by files alone (``fixtures/latent``: a
configuration of the ``deepseek-tiny`` preset and a cell list) walks
``bench/run.py``'s whole path on the CPU, traced: the model resolves by its
name in the program (the harness's Llama-shaped registration under that name
is never consulted), the posture and the widths agree with the file, the
family's own reference check passes, and the counter-fed reader reports."""

import json
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
FIXTURES = os.path.join(ROOT, "tests", "bench", "fixtures")


def test_a_latent_cell_from_files_alone_rehearses_traced():
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "deepseek-tiny-closed", "--seed", str(2 ** 31 + 43),
         "--seconds", "2", "--trace", "1", "--rehearse-cpu",
         "--benchmark", os.path.join(FIXTURES, "latent", "BENCHMARK.json"),
         "--data-dir", FIXTURES],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
             "BENCH_RUN": "3"},
    )
    assert done.returncode == 0, done.stderr[-3000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")
    result = json.loads(last[len("REHEARSAL "):])
    assert result["correct"] is True, done.stdout[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    metrics = result["metrics"]
    # the counter-fed reader finds the chunks' expert loads; the trace-fed
    # ones find no device plane on a CPU and are left out, never zero
    assert metrics["latent_expert_load_max_over_mean"]["value"] >= 1.0
    assert metrics["compiles_in_window"]["value"] == 0
    for name in ("latent_read_roofline", "latent_flash_mfu",
                 "latent_attn_dev_ms_step", "latent_moe_dev_ms_step",
                 "latent_experts_roofline", "latent_decode_roofline",
                 "latent_prefill_mfu"):
        assert name not in metrics
    # the window's prefill samples' wall time over its decode steps (the
    # rehearsal's line says REHEARSAL: a CPU's time, like host_exposed_ms_p50)
    assert metrics["latent_prefill_interleave_ms_step"]["value"] > 0
    assert "model deepseek-tiny" in done.stdout
    check = next(line for line in done.stdout.splitlines()
                 if "reference check" in line)
    assert '"passed": true' in check and "latent_rms_share" in check
    report = json.loads(check[check.index("{"):check.rindex("}") + 1])
    # the test-size file states the check's sizes beside its limits
    assert report["prompts"] == [200, 90]
    assert report["decode_steps"] == 16 and report["kernel"] == "xla"
    # the engine's own programs at its 8 slots, two of every three live
    assert (report["slots_live"], report["slots_idle"]) == (6, 2)
    assert report["engine_decode_steps_compared"] > 0
    assert len(report["held_pairs_a_token_decode"]) == 2


def test_the_fixture_list_is_a_benchmark_list():
    """The same shape as ``BENCHMARK.json``: every metric a file, the eight
    latent readers listed for the cell, the configuration named by file."""
    from lib import observe

    with open(os.path.join(FIXTURES, "latent", "BENCHMARK.json")) as f:
        b = json.load(f)
    assert [c["name"] for c in b["configs"]] == ["deepseek-tiny"]
    assert [w["name"] for w in b["workloads"]] == ["deepseek-tiny-closed"]
    roots = [FIXTURES, os.path.join(ROOT, "bench")]
    latent = [m["name"] for m in b["per_layer"]
              if m["name"].startswith("latent_")]
    assert len(latent) == 9
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        meta = observe.load_metric(
            observe.find("layer_metrics", m["name"], roots))
        assert {k: meta[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} == \
            {k: m[k] for k in ("unit", "better", "source", "layer", "moves")}
        assert m["moves"] in e2e
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    assert [m for m in real["per_layer"] if m["name"].startswith("latent_")] \
        == [dict(m, workloads=["deepseekv2-longdoc-sat"])
            for m in b["per_layer"] if m["name"].startswith("latent_")]
