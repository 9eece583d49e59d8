"""Driver-contract test for ``bench.py``: the end-of-round benchmark must
leave a parseable JSON record as the LAST stdout line — and, since the r4
wedge-proofing, re-emit the record after every phase so a driver kill at any
point still finds one. Guards the record machinery — phase budgets, device
probe short-circuit, engine teardown between phases, os._exit — which
otherwise only runs on the real chip at round end. A failed probe or phase
fails the exit code: nothing stands in for the device."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest


def _bench_env(tmp_path, **overrides) -> dict:
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        BENCH_MODEL="tiny",
        BENCH_SLOTS="4",
        BENCH_MAX_SEQ="128",
        BENCH_MAX_TOKENS="8",
        BENCH_DECODE_CHUNK="4",
        BENCH_WARMUP_REQUESTS="2",
        BENCH_REQUESTS="8",
        # decode phase only: the gateway/prefix phases have their own
        # coverage (tools/gateway_bench.py main, tests/test_paged.py) and
        # would triple this test's runtime
        BENCH_GATEWAY="0",
        BENCH_PREFIX="0",
        BENCH_KV_INT8="0",
        BENCH_SPEC="0",
        BENCH_QOS="0",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
    )
    env.update(overrides)
    return env


def _repo() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _records(stdout: str) -> list[dict]:
    out = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


@pytest.mark.slow
def test_bench_record_last_line_parses(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(_repo(), "bench.py")],
        env=_bench_env(tmp_path),
        capture_output=True,
        text=True,
        timeout=600,
        cwd=_repo(),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    records = _records(proc.stdout)
    # wedge-proofing: the record is emitted after the headline phase AND at
    # the end — every intermediate line must already be a full record
    assert len(records) >= 2, proc.stdout
    for record in records:
        assert record["unit"] == "tok/s/chip"
    record = records[-1]
    assert record["value"] > 0
    # vs_baseline is rounded to 3 decimals in the record
    assert record["vs_baseline"] == pytest.approx(
        record["value"] / 2000.0, abs=5e-4
    )
    detail = record["detail"]
    assert detail["paged"]["tok_s"] == record["value"]
    assert "roofline" in detail["paged"]
    # CPU run: the device probe must not have failed the record
    assert detail["paged"].get("error") is None
    assert "device_probe" not in detail


@pytest.mark.slow
def test_bench_probe_failure_emits_record_immediately(tmp_path):
    """A device that does not answer leaves a parseable record that says
    so (round-3 failure mode: rc:124, parsed:null) — and FAILS the run:
    nothing is measured in the chip's place, so there is no
    ``degraded_cpu`` key and the exit code is non-zero."""
    env = _bench_env(tmp_path, BENCH_TOTAL_TIMEOUT_S="240")
    repo = _repo()
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "import bench; bench._probe_device = lambda *a, **k: "
            "'forced wedge (test)'; bench.main()",
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
        cwd=repo,
    )
    assert proc.returncode != 0, proc.stdout[-2000:]
    records = _records(proc.stdout)
    assert records, proc.stdout
    record = records[-1]
    assert record["value"] == 0.0
    assert record["detail"]["device_probe"] == "forced wedge (test)"
    assert "degraded_cpu" not in record["detail"]
    # the dead-chip record must never masquerade as a chip number
    assert record["vs_baseline"] == 0.0
