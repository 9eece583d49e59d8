"""The EVA family's cell defined by files alone (``fixtures/eva``: a
configuration of the ``evabyte-tiny`` preset and a cell list) walks
``bench/run.py``'s whole path on the CPU, traced: the model resolves by its
name in the program, the posture and the widths agree with the file, the
family's own reference check passes in the engine's own two pools, the
counter-fed readers report, and the trace-fed readers find no device plane
and are left out."""

import json
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
FIXTURES = os.path.join(ROOT, "tests", "bench", "fixtures")


def test_the_family_s_cell_from_files_alone_rehearses_traced():
    seed = 2 ** 31 + 59
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "evabyte-tiny-closed", "--seed", str(seed),
         "--seconds", "2", "--trace", "1", "--rehearse-cpu",
         "--benchmark", os.path.join(FIXTURES, "eva", "BENCHMARK.json"),
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
    # the counter-fed readers find the gauges: prompts of 30-60 tokens, so
    # a slot reads one closed window's 8 summary rows at most beside up to
    # 32 exact ones; the trace-fed ones find no device plane on a CPU
    assert 0.0 <= metrics["eva_summary_rows_share"]["value"] < 100.0
    assert -100.0 < metrics["eva_pool_rows_saved_share"]["value"] < 100.0
    assert metrics["compiles_in_window"]["value"] == 0
    for name in ("eva_read_roofline", "eva_decode_roofline",
                 "eva_attn_dev_ms_step", "eva_summarise_dev_ms_step",
                 "eva_flash_mfu", "eva_prefill_mfu"):
        assert name not in metrics
    assert "model evabyte-tiny" in done.stdout
    check = next(line for line in done.stdout.splitlines()
                 if "reference check" in line)
    report = json.loads(check[check.index("{"):check.rindex("}") + 1])
    assert report["passed"] is True
    assert report["slots_live"] == 10 and report["decode_chunk"] == 8
    assert report["window_edges_crossed"] == 7
    assert report["summary_rows_compared"] == 64
