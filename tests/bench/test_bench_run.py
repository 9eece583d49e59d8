"""``bench/run.py`` itself, on the CPU, with a ``tiny`` configuration and
two-second mixes that exist only under ``tests/bench/fixtures``: as the
driver calls it, it refuses; with ``--rehearse-cpu`` it walks the whole
path. The second rehearsal is the data-only proof: a cell defined by files
alone (a configuration, a mix, an entry in a cell list) runs without a line
of Python changing."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
FIXTURES = os.path.join(ROOT, "tests", "bench", "fixtures")
RUN = [sys.executable, os.path.join(ROOT, "bench", "run.py")]
TINY = ["--benchmark", os.path.join(FIXTURES, "BENCHMARK.json"),
        "--data-dir", FIXTURES]


def run(args, **env):
    environ = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
               "BENCH_RUN": "7", **env}
    return subprocess.run(
        RUN + args, cwd=ROOT, env=environ, capture_output=True, text=True,
        timeout=600,
    )


def test_as_the_driver_calls_it_it_finds_no_tpu_and_prints_no_metric():
    done = run(["--workload", "mistral7b-chat-sat", "--seed", str(2 ** 31 + 3),
                "--seconds", "45", "--trace", "0"])
    assert done.returncode != 0
    assert "not at a TPU" in done.stderr or "no TPU" in done.stderr
    assert "metrics" not in done.stdout and "correct" not in done.stdout


def test_without_the_variable_it_still_refuses_a_cpu():
    done = run(["--workload", "tiny-closed", "--seed", "1", "--seconds", "2",
                "--trace", "0"] + TINY, JAX_PLATFORMS="")
    assert done.returncode != 0
    assert "no TPU" in done.stderr
    assert "metrics" not in done.stdout


def test_an_unknown_workload_is_refused_by_name():
    done = run(["--workload", "no-such-cell", "--seed", "1", "--seconds", "2",
                "--trace", "0"])
    assert done.returncode != 0 and "no-such-cell" in done.stderr


def rehearse(cell, trace):
    done = run(["--workload", cell, "--seed", str(2 ** 31 + 29), "--seconds",
                "2", "--trace", str(trace), "--rehearse-cpu"] + TINY)
    assert done.returncode == 0, done.stderr[-3000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")        # never a result line
    with pytest.raises(ValueError):
        json.loads(last)
    return json.loads(last[len("REHEARSAL "):]), done.stdout


def test_rehearsal_walks_the_closed_loop():
    result, out = rehearse("tiny-closed", 0)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True, out[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"tpot_p50_ms", "out_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"     # and says so
    assert "reference check" in out and "window open" in out
    assert "dispatches in the window" in out
    # the CPU's allocator keeps no count: no memory reading, never a made-up one
    assert result["device"]["memory_peak_bytes"] == 0


def test_a_cell_defined_by_files_alone_rehearses_traced():
    """The fifth cell, a rate cell as a later PR would add one: its
    configuration, its mix, its end-to-end and per-layer metric files and
    its entry in a cell list are all under tests/bench/fixtures."""
    result, out = rehearse("tiny-open", 1)
    assert result["correct"] is True, out[-3000:]
    assert result["attempted"] == 10 and result["failed"] == 0
    # the open-loop cell's per-layer metrics; trace-derived ones find no
    # device plane on a CPU and are left out, never zero
    assert {"gen_late_ms_p95", "hop_ms_p50", "queue_wait_ms_p50",
            "compiles_in_window"} <= set(result["metrics"])
    assert "decode_dev_ms_step" not in result["metrics"]
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert "breakdown" in result and "window_s" in result["device"]


def test_the_rate_cell_is_judged_on_time_to_first_token():
    """End to end, the fixture's rate cell reports the TTFT metrics whose
    files it brought, and no tokens per second."""
    result, out = rehearse("tiny-open", 0)
    assert result["correct"] is True, out[-3000:]
    assert set(result["metrics"]) == {"ttft_p50_ms", "ttft_p95_ms",
                                      "tpot_p50_ms", "setup_s"}
    assert result["metrics"]["ttft_p95_ms"]["value"] >= \
        result["metrics"]["ttft_p50_ms"]["value"] > 0


def test_the_load_generator_never_imports_jax():
    with open(os.path.join(ROOT, "bench", "loadgen.py")) as f:
        source = f.read()
    assert "import jax" not in source and "langstream_tpu" not in source
