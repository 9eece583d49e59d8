"""A ``granitemoehybrid`` cell defined by files alone (``fixtures/granite``:
a configuration of the ``granite-tiny`` preset and a cell list) walks
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


def test_a_granite_cell_from_files_alone_rehearses_traced():
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "granite-tiny-closed", "--seed", str(2 ** 31 + 43),
         "--seconds", "2", "--trace", "1", "--rehearse-cpu",
         "--benchmark", os.path.join(FIXTURES, "granite", "BENCHMARK.json"),
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
    assert metrics["granite_expert_load_max_over_mean"]["value"] >= 1.0
    assert metrics["compiles_in_window"]["value"] == 0
    for name in ("granite_ssm_dev_ms_step", "granite_moe_dev_ms_step",
                 "granite_ssm_state_roofline", "granite_experts_roofline",
                 "granite_decode_roofline"):
        assert name not in metrics
    assert "model granite-tiny" in done.stdout
    check = next(line for line in done.stdout.splitlines()
                 if "reference check" in line)
    assert '"passed": true' in check and "routing_decisions_differing" in check
    assert '"state_dtype": "float32"' in check
    # six Mamba-2 layers of the eight hold state rows
    report = json.loads(check[check.index("{"):check.rindex("}") + 1])
    assert len(report["state_rms_share_by_layer"]) == 6
