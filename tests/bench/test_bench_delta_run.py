"""A ``solar_open2`` cell defined by files alone (``fixtures/delta``: a
configuration of the ``solar-tiny`` preset and a cell list) walks
``bench/run.py``'s whole path on the CPU, traced: the model resolves by its
name in the program, the posture and the widths agree with the file, the
family's own reference check passes in the engine's own pool and state, the
counter-fed reader reports, and the trace-fed readers, handed the run's own
trace directory, find no device plane there and are left out."""

import json
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
FIXTURES = os.path.join(ROOT, "tests", "bench", "fixtures")


def test_a_delta_rule_cell_from_files_alone_rehearses_traced():
    seed = 2 ** 31 + 47
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "solar-tiny-closed", "--seed", str(seed),
         "--seconds", "2", "--trace", "1", "--rehearse-cpu",
         "--benchmark", os.path.join(FIXTURES, "delta", "BENCHMARK.json"),
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
    assert metrics["delta_expert_load_max_over_mean"]["value"] >= 1.0
    assert metrics["compiles_in_window"]["value"] == 0
    for name in ("delta_dev_ms_step", "delta_moe_dev_ms_step",
                 "delta_state_roofline", "delta_experts_roofline",
                 "delta_decode_roofline", "delta_chunk_mfu",
                 "delta_prefill_mfu"):
        assert name not in metrics
    assert "model solar-tiny" in done.stdout
    check = next(line for line in done.stdout.splitlines()
                 if "reference check" in line)
    report = json.loads(check[check.index("{"):check.rindex("}") + 1])
    assert report["passed"] is True and report["idle_state_untouched"] is True
    assert report["state_dtype"] == "float32" and report["slots"] == 8
    # three delta-rule layers of the four hold state rows
    assert len(report["state_rms_share_by_layer"]) == 3
    assert report["prefill_batches"] == [
        {"bucket": 128, "rows": 2}, {"bucket": 64, "rows": 1},
        {"bucket": 32, "rows": 1}]
    # the engine's own programs, at its own 8 slots, were held to the logits
    assert report["engine_decode_steps_compared"] == 4 * 24
    assert report["engine_state_rms_share"] <= 1e-3
