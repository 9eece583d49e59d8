"""The window-and-full family's second member's cell defined by files alone
(``fixtures/wf``: a configuration of the ``mellum-tiny`` preset and a cell
list) walks ``bench/run.py``'s whole path on the CPU, traced: the model
resolves by its name in the program, the posture and the widths agree with
the file, the member's own reference check passes in the engine's own two
pools, the counter-fed readers report, and the trace-fed readers find no
device plane and are left out."""

import json
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
FIXTURES = os.path.join(ROOT, "tests", "bench", "fixtures")


def test_the_second_member_s_cell_from_files_alone_rehearses_traced():
    seed = 2 ** 31 + 59
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "mellum-tiny-closed", "--seed", str(seed),
         "--seconds", "2", "--trace", "1", "--rehearse-cpu",
         "--benchmark", os.path.join(FIXTURES, "wf", "BENCHMARK.json"),
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
    # the counter-fed readers find the chunks' expert loads and the gauges;
    # the trace-fed ones find no device plane on a CPU and are left out
    assert metrics["wf_expert_load_max_over_mean"]["value"] >= 1.0
    # prompts of 30-60 tokens and 4 or 8 answers: rings of 4 to 5 blocks
    # at most in 8 slots' 40, and slots on either side of the window
    assert 0.0 < metrics["wf_window_blocks_used_share"]["value"] <= 100.0
    assert 0.0 <= metrics["wf_short_slots_share"]["value"] <= 100.0
    assert metrics["compiles_in_window"]["value"] == 0
    for name in ("wf_read_roofline", "wf_attn_dev_ms_step", "wf_flash_mfu",
                 "wf_moe_dev_ms_step", "wf_experts_roofline",
                 "wf_decode_roofline", "wf_prefill_mfu"):
        assert name not in metrics
    assert "model mellum-tiny" in done.stdout
    check = next(line for line in done.stdout.splitlines()
                 if "reference check" in line)
    report = json.loads(check[check.index("{"):check.rindex("}") + 1])
    assert report["passed"] is True
    assert report["prefill_batches"] == [
        {"bucket": 32, "rows": 1}, {"bucket": 32, "rows": 1},
        {"bucket": 128, "rows": 1}]
    assert report["slots_live"] == 6 and report["decode_chunk"] == 8
    assert report["engine_decode_steps_compared"] >= 6 * 24 - 8
    assert report["window_ring_blocks"] == 5
    assert report["window_slot_blocks_max"] == 5
    assert report["window_rows_compared"] == 30 + 2 * 40
