"""A window-and-full cell defined by files alone (``fixtures/swa``: a
configuration of the ``trinity-tiny`` preset and a cell list) walks
``bench/run.py``'s whole path on the CPU, traced: the model resolves by its
name in the program, the posture and the widths agree with the file, the
family's own reference check passes in the engine's own two pools through its
block manager's tables, the counter-fed readers report, and the trace-fed
readers, handed the run's own trace directory, find no device plane there and
are left out."""

import json
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
FIXTURES = os.path.join(ROOT, "tests", "bench", "fixtures")


def test_a_window_and_full_cell_from_files_alone_rehearses_traced():
    seed = 2 ** 31 + 53
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "trinity-tiny-closed", "--seed", str(seed),
         "--seconds", "2", "--trace", "1", "--rehearse-cpu",
         "--benchmark", os.path.join(FIXTURES, "swa", "BENCHMARK.json"),
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
    # the counter-fed readers find the chunks' expert loads and the pools'
    # rows; the trace-fed ones find no device plane on a CPU and are left
    # out, never zero
    assert metrics["swa_expert_load_max_over_mean"]["value"] >= 1.0
    assert 0.0 <= metrics["swa_pool_rows_saved_share"]["value"] < 80.0
    assert metrics["compiles_in_window"]["value"] == 0
    for name in ("swa_read_roofline", "swa_attn_dev_ms_step", "swa_flash_mfu",
                 "swa_moe_dev_ms_step", "swa_experts_roofline",
                 "swa_decode_roofline", "swa_prefill_mfu"):
        assert name not in metrics
    assert "model trinity-tiny" in done.stdout
    check = next(line for line in done.stdout.splitlines()
                 if "reference check" in line)
    report = json.loads(check[check.index("{"):check.rindex("}") + 1])
    assert report["passed"] is True
    assert report["prefill_batches"] == [
        {"bucket": 128, "rows": 1}, {"bucket": 256, "rows": 1}]
    # four of the six slots live (two of every three), at the engine's own
    # chunk of 8: every step of theirs held to the model function's logits
    assert report["slots_live"] == 4 and report["decode_chunk"] == 8
    assert report["engine_decode_steps_compared"] >= 4 * 48 - 8
    # a ring of 32 / 8 + 1 blocks, and no slot ever held more
    assert report["window_ring_blocks"] == 5
    assert report["window_slot_blocks_max"] == 5
    assert report["window_rows_compared"] == 2 * 40
