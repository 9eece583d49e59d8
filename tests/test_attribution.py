"""Device attribution plane tests (serving/attribution.py).

Layers covered: the analytical cost model pinned against hand-computed
bytes/FLOPs at the llama3-8b shape, the memory ledger's
sums-to-detected-limit invariant (unit and on a live CPU engine), the
``/attribution``/``/memory`` pod endpoints and their acceptance shape
(≥ 3 registered programs with expected bytes, measured p50, and
achieved-vs-expected), the control-plane scoping, the
``tools/perf_diff.py`` regression sentry (an injected 30% step-time regression flags exactly
that metric; identical rollups stay quiet), and the ``engine_top``
attribution panels + degraded-program flag."""

import asyncio
import importlib.util
import json
import socket
from pathlib import Path

import aiohttp
import pytest

from langstream_tpu.serving.attribution import (
    ModelShape,
    ProgramLedger,
    decode_cost,
    memory_ledger,
    prefill_cost,
    verify_cost,
)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _load_tool(name: str):
    path = Path(__file__).resolve().parents[1] / "tools" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# --------------------------------------------------------------------------
# cost model: pinned against hand-computed bytes/FLOPs (llama3-8b shape)
# --------------------------------------------------------------------------

# Llama-3-8B: 32L / 4096H / 32 heads / GQA-8 / 128 head-dim / 14336 FFN /
# 128256 vocab. Parameter count by hand:
#   per layer: wq 4096*4096 + wk,wv 2*4096*1024 + wo 4096*4096
#              + 3*4096*14336 (gate/up/down) + 2*4096 (norms)
#            = 16777216 + 8388608 + 16777216 + 176160768 + 8192
#            = 218111, *wait — spelled out below in numbers.
_P_LAYER = (
    4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 14336 + 2 * 4096
)
_N_PARAMS = 32 * _P_LAYER + 2 * 128256 * 4096 + 4096  # embed + head + norm

_SHAPE_8B_INT8 = ModelShape(
    layers=32,
    hidden=4096,
    heads=32,
    kv_heads=8,
    head_dim=128,
    intermediate=14336,
    vocab=128256,
    weight_bytes=_N_PARAMS,       # int8: 1 byte/param
    param_count=_N_PARAMS,
    kv_row_bytes=128 + 4,          # int8 KV row + f32 scale
    act_bytes=2,                   # bf16 activations
)


def test_param_count_hand_check_matches_model_helper():
    from langstream_tpu.models.llama import LlamaConfig, param_count

    assert param_count(LlamaConfig.llama3_8b()) == _N_PARAMS
    assert _N_PARAMS == 8_030_261_248  # ~8.03B, the published shape


def test_decode_cost_pinned_to_hand_computed_bytes():
    slots, window, k = 64, 512, 32
    cost = decode_cost(
        _SHAPE_8B_INT8, slots=slots, window_rows=window, k_steps=k,
        hbm_gbps=819.0,
    )
    # weights stream once per fused step
    assert cost.weight_bytes == k * _N_PARAMS
    # KV window read: K and V, every layer, every slot, int8 rows
    kv_row = 8 * (128 + 4) * 2
    assert cost.kv_read_bytes == k * 32 * slots * window * kv_row
    # one new row per slot per step
    assert cost.kv_write_bytes == k * 32 * slots * kv_row
    # activations: residual+norm (2H) + FFN intermediate per layer, plus
    # the logits row, bf16
    assert cost.act_bytes == (
        k * slots * 2 * (32 * (2 * 4096 + 14336) + 128256)
    )
    # FLOPs: 2*params per token plus the attention window sweep
    assert cost.flops == k * slots * (
        2 * _N_PARAMS + 4 * 32 * 128 * window
    )
    assert cost.total_bytes == (
        cost.weight_bytes + cost.kv_read_bytes + cost.kv_write_bytes
        + cost.act_bytes
    )
    # expected time is the HBM floor at the assumed bandwidth
    assert cost.expected_ms() == pytest.approx(
        cost.total_bytes / (819.0 * 1e9) * 1e3
    )
    # sanity: the dominant term at this shape is weight streaming — the
    # per-step floor must sit in the ~10ms/step regime BENCH_NOTES pins
    assert 8.0 < cost.expected_ms() / k < 16.0


def test_prefill_and_verify_costs_hand_computed():
    kv_row = 8 * (128 + 4) * 2
    cost = prefill_cost(
        _SHAPE_8B_INT8, rows=4, tokens_per_row=256, prefix_rows=0,
        hbm_gbps=819.0,
    )
    assert cost.kind == "prefill"
    assert cost.weight_bytes == _N_PARAMS  # once per dispatch, not per token
    assert cost.kv_read_bytes == 0
    assert cost.kv_write_bytes == 32 * 4 * 256 * kv_row
    cont = prefill_cost(
        _SHAPE_8B_INT8, rows=4, tokens_per_row=64, prefix_rows=512,
        hbm_gbps=819.0,
    )
    assert cont.kind == "prefill-continue"
    assert cont.kv_read_bytes == 32 * 4 * 512 * kv_row
    ver = verify_cost(
        _SHAPE_8B_INT8, slots=64, window_rows=512, drafts=4, hbm_gbps=819.0,
    )
    assert ver.kind == "verify"
    assert ver.kv_write_bytes == 32 * 64 * 5 * kv_row
    assert ver.tokens == 64 * 5


# --------------------------------------------------------------------------
# ledger units
# --------------------------------------------------------------------------


def test_program_ledger_report_and_census():
    ledger = ProgramLedger(window=4)
    cost = decode_cost(
        _SHAPE_8B_INT8, slots=4, window_rows=128, k_steps=8, hbm_gbps=819.0
    )
    ledger.register("decode:w128:k8:greedy", cost)
    ledger.register("decode:w128:k8:greedy", cost)  # idempotent
    for ms in (10.0, 20.0, 30.0):
        ledger.observe("decode:w128:k8:greedy", ms / 1000.0)
    ledger.observe("never-registered", 1.0)  # dropped, never raises
    report = ledger.report()
    assert len(report) == 1
    entry = report[0]
    assert entry["dispatches"] == 3
    assert entry["measured_ms_p50"] == pytest.approx(20.0)
    assert entry["expected"]["total_bytes"] == cost.total_bytes
    assert entry["achieved_vs_expected"] == pytest.approx(
        cost.expected_ms() / 20.0, rel=1e-3
    )
    assert ledger.census() == {"decode:w128:k8:greedy": 3}


def test_memory_ledger_slack_identity_and_sub_owner():
    out = memory_ledger(
        weights_bytes=1000,
        kv_pool_bytes=500,
        prefix_blocks=3,
        bytes_per_block=50,
        sampler_bytes=20,
        tables_bytes=30,
        limit_bytes=2000,
    )
    owners = out["hbm_bytes_by_owner"]
    assert out["accounted_bytes"] == 1550
    assert owners["slack"] == 450
    # owner sum (slack included) equals the detected limit EXACTLY
    assert sum(owners.values()) == 2000
    # prefix blocks are a sub-owner of the pool, never added to the sum
    assert out["kv_pool_prefix_bytes"] == 150
    # unknown capacity: slack is honest-None, not zero
    unknown = memory_ledger(
        weights_bytes=1, kv_pool_bytes=1, prefix_blocks=0,
        bytes_per_block=0, sampler_bytes=0, tables_bytes=0,
        limit_bytes=None,
    )
    assert unknown["slack_bytes"] is None
    assert "slack" not in unknown["hbm_bytes_by_owner"]


# --------------------------------------------------------------------------
# live CPU engine: the /attribution acceptance shape
# --------------------------------------------------------------------------


def test_live_engine_attribution_and_memory_invariant(run_async, monkeypatch):
    """≥ 3 distinct registered programs, each with expected bytes, a
    measured p50, and an achieved-vs-expected ratio; the memory ledger's
    owner sum equals the capacity within the reported slack; flight
    samples carry the program key."""
    import langstream_tpu.serving.engine as engine_mod
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    # a synthetic device: the CPU reports no allocator limit and has no
    # published peaks, and the invariant and the ratios need known
    # denominators (the engine resolves both once at construction)
    limit = 1 << 30
    monkeypatch.setattr(engine_mod, "detect_hbm_bytes", lambda: limit)
    monkeypatch.setattr(
        engine_mod, "device_peaks",
        lambda: ("test-device", {"hbm_gbps": 819.0}),
    )

    async def main():
        engine = TpuServingEngine.get_or_create(
            ServingConfig(
                model="tiny", slots=4, max_seq_len=128, kv_layout="paged",
                kv_block_size=16, decode_chunk=4, decode_chunk_light=0,
            )
        )
        try:
            prompts = ["attribution probe " * n for n in (1, 2, 6, 10)]
            await asyncio.gather(
                *(engine.generate(p, {"max-tokens": 12}) for p in prompts)
            )
            section = engine.stats()["attribution"]
            programs = section["programs"]
            assert len(programs) >= 3, [p["program"] for p in programs]
            kinds = {p["kind"] for p in programs}
            assert "decode" in kinds and (
                "prefill" in kinds or "prefill-continue" in kinds
            )
            for program in programs:
                assert program["expected"]["total_bytes"] > 0
                assert program["dispatches"] >= 1
                assert program["measured_ms_p50"] is not None
                assert program["achieved_vs_expected"] is not None
            # memory invariant: owner sum + slack == capacity, exactly
            memory = section["memory"]
            owners = memory["hbm_bytes_by_owner"]
            assert section["device_kind"] == "test-device"
            assert sum(owners.values()) == limit
            assert owners["slack"] == memory["slack_bytes"]
            assert memory["slack_bytes"] >= 0  # tiny model fits easily
            assert owners["weights"] > 0 and owners["kv-pool"] > 0
            assert memory["kv_pool_prefix_bytes"] <= owners["kv-pool"]
            # flight samples are keyed by program id
            keyed = [
                s for s in engine.flight.recent(0)
                if s["phase"] != "stall" and s.get("program")
            ]
            assert keyed, "dispatch samples carry the program key"
            assert any(
                s["program"].startswith("decode:") for s in keyed
            )
        finally:
            await engine.close()

    run_async(main())


def test_pod_serves_attribution_and_memory(run_async, monkeypatch):
    from langstream_tpu.runtime.pod import _serve_info
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        engine = TpuServingEngine.get_or_create(
            ServingConfig(model="tiny", slots=2, max_seq_len=64, decode_chunk=4)
        )
        port = free_port()
        monkeypatch.setenv("LS_HTTP_PORT", str(port))
        server = await _serve_info(None)
        try:
            await engine.generate("pod attribution probe", {"max-tokens": 4})
            async with aiohttp.ClientSession() as session:
                base = f"http://127.0.0.1:{port}"
                async with session.get(f"{base}/attribution") as resp:
                    assert resp.status == 200
                    assert resp.headers["Content-Type"] == "application/json"
                    report = await resp.json()
                entry = next(e for e in report if e["model"] == "tiny")
                assert entry["programs"]
                assert entry["memory"]["hbm_bytes_by_owner"]["weights"] > 0
                async with session.get(f"{base}/memory") as resp:
                    assert resp.status == 200
                    memory = await resp.json()
                entry = next(e for e in memory if e["model"] == "tiny")
                assert "programs" not in entry  # ledger-only view
                assert entry["memory"]["accounted_bytes"] > 0
        finally:
            server.close()
            await engine.close()

    run_async(main())


def test_dev_attribution_scoped_to_declared_models(monkeypatch):
    """Mirror of the /flight scoping: one tenant's attribution route
    must not read another's device economics off the process-global
    engine map."""
    import langstream_tpu.serving.engine as engine_mod
    from langstream_tpu.controlplane.server import LocalComputeRuntime

    monkeypatch.setattr(
        engine_mod,
        "attribution_report",
        lambda: [
            {"model": "tiny", "programs": [], "memory": {}},
            {"model": "llama-1b", "programs": [], "memory": {}},
        ],
    )

    class _Resource:
        def __init__(self, rtype, configuration):
            self.type = rtype
            self.configuration = configuration

    def runner_with(resources):
        class _App:
            pass

        class _Runner:
            pass

        _Runner.application = _App()
        _Runner.application.resources = resources
        return _Runner()

    compute = LocalComputeRuntime()
    compute.runners[("t", "app")] = runner_with(
        {"tpu": _Resource("tpu-serving-configuration", {"model": "tiny"})}
    )
    compute.runners[("t", "plain")] = runner_with({})
    assert [e["model"] for e in compute.attribution("t", "app")] == ["tiny"]
    assert compute.attribution("t", "plain") == []
    assert compute.attribution("t", "ghost") == []


# --------------------------------------------------------------------------
# tools/perf_diff.py: the regression sentry
# --------------------------------------------------------------------------


def _bench_record(step_ms: float) -> dict:
    return {
        "schema": 2,
        "metric": "tok/s/chip llama3-8b int8-weights decode",
        "value": 1500.0,
        "unit": "tok/s/chip",
        "vs_baseline": 0.75,
        "detail": {
            "paged": {
                "tok_s": 1500.0,
                "mean_step_ms": 40.0,
                "overlap_ratio": 0.5,
                "roofline": {"hbm_utilization": 0.291},
                "flight": {
                    "step_ms_p50": step_ms,
                    "recompile_count": 4,
                    "totals": {
                        "wall_ms": 1000.0,
                        "device_ms": 800.0,
                        "host_ms": 150.0,
                        "stall_ms": 50.0,
                        "steps_by_phase": {"decode": 20},
                    },
                },
                "programs": {"decode:w512:k32:greedy": 100},
            },
            "speculative": {"uplift": 1.2, "accepted_per_step": 3.0},
            "gateway_ttft_p50_s": 0.6,
        },
    }


def test_perf_diff_flags_exactly_the_injected_step_regression(tmp_path):
    perf_diff = _load_tool("perf_diff")
    base = tmp_path / "r05.json"
    new = tmp_path / "r06.json"
    base.write_text(json.dumps(_bench_record(40.0)))
    new.write_text(json.dumps(_bench_record(52.0)))  # +30% step time
    results, any_regression = perf_diff.diff_files([str(base), str(new)])
    assert any_regression
    (_b, _n, result), = results
    assert [r["metric"] for r in result["regressions"]] == ["step_ms_p50"]
    assert result["regressions"][0]["change"] == pytest.approx(0.3)
    assert result["improvements"] == []


def test_perf_diff_quiet_on_identical_rollups(tmp_path):
    perf_diff = _load_tool("perf_diff")
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(_bench_record(40.0)))
    b.write_text(json.dumps(_bench_record(40.0)))
    results, any_regression = perf_diff.diff_files([str(a), str(b)])
    assert not any_regression
    (_b, _n, result), = results
    assert result["regressions"] == []
    assert result["improvements"] == []
    assert result["notes"] == []


def test_perf_diff_direction_and_census_notes(tmp_path):
    perf_diff = _load_tool("perf_diff")
    base = _bench_record(40.0)
    new = _bench_record(40.0)
    # overlap collapse (lower is worse) + a census change
    new["detail"]["paged"]["overlap_ratio"] = 0.1
    new["detail"]["paged"]["programs"] = {"decode:w1024:k32:greedy": 90}
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(new))
    results, any_regression = perf_diff.diff_files([str(a), str(b)])
    (_b, _n, result), = results
    assert any_regression
    assert [r["metric"] for r in result["regressions"]] == ["overlap_ratio"]
    assert any("census" in note for note in result["notes"])
    # a faster step time is an improvement, never a regression
    faster = _bench_record(20.0)
    c = tmp_path / "c.json"
    c.write_text(json.dumps(faster))
    results, any_regression = perf_diff.diff_files([str(a), str(c)])
    (_b, _n, result), = results
    assert not any_regression
    assert [i["metric"] for i in result["improvements"]] == ["step_ms_p50"]


def test_perf_diff_reads_flight_dumps(tmp_path):
    perf_diff = _load_tool("perf_diff")

    def dump(step_ms):
        return [{
            "model": "tiny",
            "summary": {
                "totals": {"device_ms": 100.0, "recompiles": 2},
                "window": {"step_ms_p50": step_ms, "overlap_ratio": 0.4},
            },
        }]

    a = tmp_path / "old.json"
    b = tmp_path / "new.json"
    a.write_text(json.dumps(dump(10.0)))
    b.write_text(json.dumps(dump(14.0)))
    results, any_regression = perf_diff.diff_files([str(a), str(b)])
    assert any_regression
    (_b, _n, result), = results
    assert [r["metric"] for r in result["regressions"]] == ["step_ms_p50"]


def test_perf_diff_watches_analyzer_self_stats(tmp_path):
    """The bench record carries graftcheck self-stats (bench.py
    _analyzer_stats): a slower analyzer or suppression creep is a
    declared regression direction, not ignored drift."""
    perf_diff = _load_tool("perf_diff")
    assert perf_diff.METRICS["analyzer_wall_s"] == "up"
    assert perf_diff.METRICS["analyzer_suppressions"] == "up"
    base = _bench_record(40.0)
    new = _bench_record(40.0)
    base["detail"]["analyzer"] = {
        "analyzer_wall_s": 10.0, "suppressions": 10, "violations": 0,
    }
    new["detail"]["analyzer"] = {
        "analyzer_wall_s": 15.0, "suppressions": 10, "violations": 0,
    }
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(new))
    results, any_regression = perf_diff.diff_files([str(a), str(b)])
    (_b, _n, result), = results
    assert any_regression
    assert [r["metric"] for r in result["regressions"]] == [
        "analyzer_wall_s"
    ]


# --------------------------------------------------------------------------
# engine_top: attribution panels + degraded-program flag + cross-run diff
# --------------------------------------------------------------------------


def _attrib_entry(ratios: list[float]) -> dict:
    return {
        "model": "llama3-8b",
        "slots": 64,
        "programs": [
            {
                "program": f"decode:w{512 * (i + 1)}:k32:greedy",
                "kind": "decode",
                "dispatches": 20,
                "device_s_total": 1.0,
                "expected": {"total_bytes": 10**9, "expected_ms": 12.0},
                "measured_ms_p50": 40.0,
                "measured_ms_p95": 50.0,
                "achieved_vs_expected": ratio,
            }
            for i, ratio in enumerate(ratios)
        ],
        "memory": {
            "hbm_bytes_by_owner": {
                "weights": 8 * 2**30,
                "kv-pool": 4 * 2**30,
                "sampler-state": 1024,
                "device-lru": 2048,
                "slack": 4 * 2**30 - 3072,
            },
            "accounted_bytes": 12 * 2**30 + 3072,
            "kv_pool_prefix_bytes": 2**20,
            "limit_bytes": 16 * 2**30,
            "slack_bytes": 4 * 2**30 - 3072,
        },
    }


def _load_engine_top():
    return _load_tool("engine_top")


def test_engine_top_renders_attribution_payload():
    engine_top = _load_engine_top()
    frame = engine_top.render([_attrib_entry([0.3, 0.31, 0.29])])
    assert "hbm" in frame and "limit 16.0GB" in frame
    assert "decode:w512:k32:greedy" in frame
    assert "weights" in frame and "slack" in frame


def test_engine_top_analyze_flags_degraded_program():
    engine_top = _load_engine_top()
    out = engine_top.analyze([_attrib_entry([0.30, 0.28, 0.32, 0.05])])
    assert "program attribution gap" in out
    assert "decode:w2048:k32:greedy" in out
    # a uniform dump stays quiet
    quiet = engine_top.analyze([_attrib_entry([0.30, 0.28, 0.32])])
    assert "program attribution gap" not in quiet
    assert "no attribution anomalies flagged" in quiet


def test_engine_top_analyze_cross_run_diff(tmp_path, capsys):
    engine_top = _load_engine_top()
    a = tmp_path / "r05.json"
    b = tmp_path / "r06.json"
    a.write_text(json.dumps(_bench_record(40.0)))
    b.write_text(json.dumps(_bench_record(52.0)))
    rc = engine_top.main(["--analyze", str(a), str(b)])
    out = capsys.readouterr().out
    assert rc == 1  # regression flagged
    assert "REGRESSION step_ms_p50" in out
    # identical rounds: analyze both, diff quiet, rc 0
    c = tmp_path / "r07.json"
    c.write_text(json.dumps(_bench_record(52.0)))
    rc = engine_top.main(["--analyze", str(b), str(c)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "no regressions" in out
