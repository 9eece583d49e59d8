"""Pipelined engine loop (docs/PIPELINE.md): equivalence + accounting.

The depth-2 pipelined decode dispatch must be INVISIBLE in outputs —
greedy tokens and streamed text byte-identical to the sequential
reference loop (``pipeline=False`` / ``LS_TPU_PIPELINE=0``) across
multi-request mixed-length workloads, early EOS, and QoS preemption —
and VISIBLE in telemetry: the flight rollup's ``overlap_ratio`` /
``host_overlapped_ms`` split, the bounded device-upload caches in
``engine.stats()``, and the bench ablation's step-time win.

Engines here pin ``model_dtype=float32``: the pipelined and sequential
loops legitimately dispatch different chunk/window shapes (the frozen
finished-slot mask keeps a pipelined burst alive where the sequential
loop tears down and re-buckets), and f32 is what makes greedy argmax
exactly shape-independent (see ServingConfig.model_dtype).
"""

from __future__ import annotations

import asyncio
import importlib.util
import os

import pytest

from langstream_tpu.serving.flight import FlightRecorder, bench_rollup


@pytest.fixture(autouse=True)
def _fresh_engines():
    from langstream_tpu.serving.engine import TpuServingEngine

    TpuServingEngine.reset_instances()
    yield
    TpuServingEngine.reset_instances()


def _config(pipeline: bool, **overrides):
    from langstream_tpu.serving.engine import ServingConfig

    base = dict(
        model="tiny", slots=4, max_seq_len=128, decode_chunk=8,
        decode_chunk_light=0, model_dtype="float32", pipeline=pipeline,
    )
    base.update(overrides)
    return ServingConfig(**base)


# the mixed-length workload: more requests than slots, budgets straddling
# chunk boundaries, a couple of streaming consumers (the per-token slow
# path) next to fast-path requests
_WORKLOAD = [
    ("the quick brown fox", 5),
    ("pack my box with five dozen", 12),
    ("jumps over the lazy dog", 9),
    ("sphinx of black quartz", 16),
    ("judge my vow", 7),
    ("abcdefgh", 21),
]


async def _run_workload(engine, eos_id: int | None = None):
    """Run the mixed workload; returns (results, streamed token lists)."""
    if eos_id is not None:
        engine.tokenizer.eos_id = eos_id  # per-engine ByteTokenizer
    streams: dict[int, list] = {}

    def _collector(i):
        streams[i] = []

        def on_token(token, logprob, last):
            streams[i].append((token, last))

        return on_token

    results = await asyncio.gather(
        *(
            engine.generate(
                prompt,
                {"max-tokens": budget, "temperature": 0},
                # stream every other request: covers the per-token slow
                # path and the vectorized fast path in the same burst
                on_token=_collector(i) if i % 2 == 0 else None,
            )
            for i, (prompt, budget) in enumerate(_WORKLOAD)
        )
    )
    return results, streams


def test_config_pipeline_round_trip_and_env_gate(monkeypatch):
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    cfg = ServingConfig(model="tiny", slots=2, max_seq_len=64, pipeline=False)
    assert ServingConfig.from_dict(cfg.to_dict()) == cfg
    assert ServingConfig.from_dict({"pipeline": "false"}).pipeline is False
    assert ServingConfig.from_dict({}).pipeline is True

    # LS_TPU_PIPELINE=0 forces the sequential loop even when config says on
    monkeypatch.setenv("LS_TPU_PIPELINE", "0")
    engine = TpuServingEngine(_config(pipeline=True, slots=2, max_seq_len=64))
    assert engine._pipeline_on is False
    assert engine.stats()["pipeline"] is False
    monkeypatch.delenv("LS_TPU_PIPELINE")
    engine2 = TpuServingEngine(_config(pipeline=True, slots=2, max_seq_len=64))
    assert engine2._pipeline_on is True


def test_pipelined_greedy_byte_identity_mixed_lengths(run_async):
    """Tokens AND streamed emissions AND final text identical between the
    pipelined loop and the sequential reference on a multi-request
    mixed-length workload (slots finish mid-burst, freeze device-side,
    over-run tokens are discarded)."""
    from langstream_tpu.serving.engine import TpuServingEngine

    async def main():
        seq_engine = TpuServingEngine(_config(pipeline=False))
        try:
            seq_results, seq_streams = await _run_workload(seq_engine)
        finally:
            await seq_engine.close()

        pipe_engine = TpuServingEngine(_config(pipeline=True))
        try:
            pipe_results, pipe_streams = await _run_workload(pipe_engine)
            # the pipelined loop must actually have pipelined (heavy
            # chunks, no light regime configured)
            assert pipe_engine.stats()["pipeline"] is True
        finally:
            await pipe_engine.close()

        for i, (seq_r, pipe_r) in enumerate(zip(seq_results, pipe_results)):
            assert pipe_r["tokens"] == seq_r["tokens"], f"request {i}"
            assert pipe_r["text"] == seq_r["text"], f"request {i}"
            assert (
                pipe_r["num_completion_tokens"]
                == seq_r["num_completion_tokens"]
            )
            assert pipe_r["finish_reason"] == seq_r["finish_reason"]
        assert pipe_streams == seq_streams

    run_async(main())


def test_pipelined_early_eos_byte_identity(run_async):
    """EOS before max_tokens: requests that end mid-chunk (the stop-lag
    case — detection is one chunk late under the pipeline) still match
    the sequential loop exactly, tokens, text, and token counts."""
    from langstream_tpu.serving.engine import TpuServingEngine

    async def main():
        # learn a token the model actually emits (a probe on the
        # sequential engine itself — requests are independent), then make
        # it EOS so completions end early and mid-chunk deterministically
        seq_engine = TpuServingEngine(_config(pipeline=False))
        try:
            r = await seq_engine.generate(
                _WORKLOAD[0][0], {"max-tokens": 12, "temperature": 0}
            )
            assert len(r["tokens"]) >= 4
            fake_eos = r["tokens"][3]
            seq_results, seq_streams = await _run_workload(
                seq_engine, eos_id=fake_eos
            )
        finally:
            await seq_engine.close()
        pipe_engine = TpuServingEngine(_config(pipeline=True))
        try:
            pipe_results, pipe_streams = await _run_workload(
                pipe_engine, eos_id=fake_eos
            )
        finally:
            await pipe_engine.close()

        assert any(
            r["finish_reason"] == "stop" for r in seq_results
        ), "the synthetic EOS must fire for the case to mean anything"
        for seq_r, pipe_r in zip(seq_results, pipe_results):
            assert pipe_r["tokens"] == seq_r["tokens"]
            assert pipe_r["text"] == seq_r["text"]
            assert pipe_r["finish_reason"] == seq_r["finish_reason"]
        assert pipe_streams == seq_streams

    run_async(main())


def test_overrun_tokens_never_billed(run_async):
    """Over-run tokens (decoded for a finished slot inside an in-flight
    chunk) are discarded: completion counts equal the token lists, the
    QoS post-debit bills exactly the delivered tokens, and both match
    the sequential loop's accounting."""
    from langstream_tpu.serving.engine import TpuServingEngine
    from langstream_tpu.serving.qos import QosSpec

    qos = QosSpec.from_dict(
        {"tenants": {"*": {"requests-per-s": 10_000, "burst": 10_000,
                           "tokens-per-s": 1_000_000}}}
    )

    async def run_one(pipeline: bool):
        engine = TpuServingEngine(_config(pipeline=pipeline, qos=qos))
        try:
            results = await asyncio.gather(
                *(
                    engine.generate(
                        prompt,
                        {"max-tokens": budget, "temperature": 0,
                         "qos-tenant": "acct"},
                    )
                    for prompt, budget in _WORKLOAD
                )
            )
            debited = (
                engine.scheduler.limiter.stats()
                .get("acct", {})
                .get("tokens_debited", 0)
            )
            generated = engine.total_generated
        finally:
            await engine.close()
        return results, debited, generated

    async def main():
        seq_results, seq_debited, _ = await run_one(pipeline=False)
        pipe_results, pipe_debited, _ = await run_one(pipeline=True)
        for seq_r, pipe_r in zip(seq_results, pipe_results):
            assert pipe_r["tokens"] == seq_r["tokens"]
            assert len(pipe_r["tokens"]) == pipe_r["num_completion_tokens"]
        # the post-debit bills delivered tokens only — identical across
        # loops even though the pipelined one decoded over-run tokens
        assert pipe_debited == seq_debited
        assert pipe_debited == sum(
            len(r["tokens"]) for r in pipe_results
        )

    run_async(main())


def test_preemption_round_trip_under_pipelined_loop(run_async):
    """QoS preemption at the loop's safe point composes with the
    pipelined burst: the preempted-then-resumed request stays
    byte-identical to an unpreempted baseline (semantics unchanged)."""
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine
    from langstream_tpu.serving.qos import QosSpec

    def cfg(qos=None):
        return ServingConfig(
            model="tiny", slots=2, max_seq_len=256, decode_chunk=4,
            decode_chunk_light=0, model_dtype="float32",
            kv_layout="paged", kv_block_size=16, kv_pool_blocks=8,
            prefix_cache=False, pipeline=True, qos=qos,
        )

    batch_prompt = "quarterly report: revenue"  # 25 byte-tokens + BOS
    inter_prompt = "what should i check now?"

    async def main():
        baseline_engine = TpuServingEngine(cfg())
        try:
            baseline = await baseline_engine.generate(
                batch_prompt, {"max-tokens": 40}
            )
        finally:
            await baseline_engine.close()
        assert baseline["tokens"]

        engine = TpuServingEngine(cfg(QosSpec.from_dict({})))
        try:
            progressed = asyncio.Event()
            seen = 0

            def on_token(token, logprob, last):
                nonlocal seen
                seen += 1
                if seen >= 3:
                    progressed.set()

            batch_task = asyncio.create_task(
                engine.generate(
                    batch_prompt,
                    {"max-tokens": 40, "priority": "batch",
                     "qos-tenant": "bulk"},
                    on_token=on_token,
                )
            )
            await asyncio.wait_for(progressed.wait(), timeout=60)
            inter = await asyncio.wait_for(
                engine.generate(
                    inter_prompt,
                    {"max-tokens": 8, "priority": "interactive"},
                ),
                timeout=60,
            )
            assert inter["tokens"]
            resumed = await asyncio.wait_for(batch_task, timeout=60)
            assert resumed["tokens"] == baseline["tokens"]
            assert resumed["text"] == baseline["text"]
            stats = engine.stats()["scheduler"]
            assert stats["preempted"] == 1
            assert stats["resumed"] == 1
        finally:
            await engine.close()

    run_async(main())


# --------------------------------------------------------------------------
# overlap accounting (flight recorder)
# --------------------------------------------------------------------------


def test_flight_overlap_sample_accounting():
    """Overlapped host time is credited inside the device-busy share and
    reported separately — never double-counted, and the exact wall
    decomposition device + host(exposed) + stall survives."""
    recorder = FlightRecorder(slots=4, maxlen=32)
    import time as _time

    _time.sleep(0.03)
    s = recorder.sample("decode", device_s=0.01, overlapped_s=0.01, tokens=8)
    assert s["host_overlapped_ms"] == pytest.approx(10.0, abs=1.0)
    assert s["device_ms"] == pytest.approx(20.0, abs=2.0)  # wait + shadow
    assert s["wall_ms"] == pytest.approx(
        s["device_ms"] + s["host_ms"], abs=0.01
    )
    recorder.stall("queue-empty")
    totals = recorder.summary()["totals"]
    assert totals["wall_ms"] == pytest.approx(
        totals["device_ms"] + totals["host_ms"] + totals["stall_ms"],
        abs=0.01,
    )
    assert totals["host_overlapped_ms"] <= totals["device_ms"]


def test_flight_overlap_clamped_to_wall():
    """An overlap overestimate cannot push device_ms past wall or host_ms
    negative."""
    recorder = FlightRecorder(slots=1, maxlen=8)
    s = recorder.sample("decode", device_s=0.002, overlapped_s=999.0)
    assert s["device_ms"] <= s["wall_ms"]
    assert s["host_ms"] >= 0.0


def test_flight_overlap_ratio_in_window_and_rollup():
    recorder = FlightRecorder(slots=2, maxlen=32)
    import time as _time

    for _ in range(4):
        _time.sleep(0.004)
        recorder.sample("decode", device_s=0.001, overlapped_s=0.002)
    window = recorder.summary()["window"]
    assert window["overlap_ratio"] is not None
    assert 0.0 < window["overlap_ratio"] <= 1.0
    assert window["host_overlapped_ms_p50"] is not None
    assert window["host_exposed_ms_p50"] == window["host_overhead_ms_p50"]
    rollup = bench_rollup(recorder.summary())
    assert rollup["overlap_ratio"] == window["overlap_ratio"]
    assert rollup["totals"]["host_overlapped_ms"] > 0


# --------------------------------------------------------------------------
# bounded device-upload caches
# --------------------------------------------------------------------------


def test_device_lru_caps_and_counts_evictions(monkeypatch):
    from langstream_tpu.serving.engine import _DeviceLru

    lru = _DeviceLru(cap=2)
    assert lru.get_or_put(b"a", lambda: 1) == 1
    assert lru.get_or_put(b"b", lambda: 2) == 2
    assert lru.get_or_put(b"a", lambda: 99) == 1  # hit keeps the value
    lru.get_or_put(b"c", lambda: 3)  # evicts b (LRU)
    assert lru.get_or_put(b"b", lambda: 4) == 4  # re-inserted: was evicted
    stats = lru.stats()
    assert stats["cap"] == 2
    assert stats["size"] == 2
    assert stats["evictions"] == 2
    assert stats["hits"] == 1
    assert stats["misses"] == 4

    # the env knob sizes engine-constructed caches
    monkeypatch.setenv("LS_TPU_DEV_CACHE_CAP", "5")
    assert _DeviceLru().cap == 5
    monkeypatch.setenv("LS_TPU_DEV_CACHE_CAP", "junk")
    assert _DeviceLru().cap == 32


def test_engine_stats_carry_device_cache_counters(run_async):
    from langstream_tpu.serving.engine import TpuServingEngine

    async def main():
        engine = TpuServingEngine(_config(pipeline=True, slots=2))
        try:
            await engine.generate("abc", {"max-tokens": 4, "temperature": 0})
            cache_stats = engine.stats()["device-cache"]
            assert set(cache_stats) == {"tables", "sampler"}
            for entry in cache_stats.values():
                assert {"size", "cap", "hits", "misses", "evictions"} <= set(
                    entry
                )
                assert entry["size"] <= entry["cap"]
            assert cache_stats["sampler"]["misses"] >= 1
        finally:
            await engine.close()

    run_async(main())


# --------------------------------------------------------------------------
# the bench ablation: overlap visible + step win on CPU
# --------------------------------------------------------------------------


def _load_bench():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_for_pipeline_test", os.path.join(repo, "bench.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.slow
def test_bench_pipeline_ablation_records_overlap_and_step_win():
    """The paged phase's pipeline ablation on CPU: the pipelined leg's
    flight rollup shows overlap_ratio > 0, and its mean step wall beats
    the sequential leg's on the same workload (the ISSUE-5 acceptance,
    assertable off-chip)."""
    bench = _load_bench()
    bench.MODEL = "tiny"
    bench.SLOTS = 8
    # a longer context makes per-chunk device compute material even on
    # CPU, so the pipelined leg has real execution to hide host work
    # under — with a near-zero device term both legs are pure host and
    # the comparison measures noise
    bench.MAX_SEQ = 512
    bench.MAX_TOKENS = 64
    bench.DECODE_CHUNK = 8
    bench.WARMUP_REQUESTS = 8
    bench.QUANTIZE = None
    bench.KV_QUANT = None
    bench.PROMPT = "Benchmarking the TPU serving engine end to end. " * 8

    out = asyncio.run(bench.run_paged_pipeline_phase(requests=24))
    assert out["pipelined"]["pipeline"] is True
    assert out["sequential"]["pipeline"] is False
    # the overlap split is recorded in both legs' rollups. The ratio is
    # honest — bounded by device-readiness probes — so on CPU, where the
    # tiny model's chunk compute is sub-millisecond, there is genuinely
    # ~nothing to hide host work under and the ratio may read 0.0 (on
    # chips, device ~25ms/chunk vs host ~16ms makes it large); what CPU
    # can assert is presence, bounds, and the step win below
    assert out["pipelined"]["overlap_ratio"] is not None
    assert 0.0 <= out["pipelined"]["overlap_ratio"] <= 1.0
    assert out["pipelined"]["flight"]["totals"]["host_overlapped_ms"] >= 0
    # the sequential reference does no overlapped work by construction
    assert (out["sequential"]["overlap_ratio"] or 0.0) == 0.0
    # the win: median dispatched-step wall below the sequential
    # ablation's on the same workload (medians over the post-warmup
    # window — means are hostage to a single stray compile on CPU)
    pipe_p50 = out["pipelined"]["flight"]["step_ms_p50"]
    seq_p50 = out["sequential"]["flight"]["step_ms_p50"]
    assert pipe_p50 is not None and seq_p50 is not None
    assert pipe_p50 < seq_p50
    assert out["step_speedup"] > 1.0
    assert out["pipelined"]["mean_step_ms"] is not None


def test_engine_flight_shows_overlap_split_under_load(run_async):
    """A loaded multi-request run on the pipelined engine serves the
    overlap split through the live flight rollup: ratio present and
    bounded, per-sample fields present, and the wall decomposition
    still exact. The ratio's VALUE is honest (bounded by device-
    readiness probes): on CPU the tiny model's sub-millisecond chunks
    leave ~nothing to hide host work under, so it may read 0.0 — the
    recorder-level tests above pin the >0 crediting math, and chip runs
    (device ~25ms/chunk) are where the ratio is meaningfully large."""
    from langstream_tpu.serving.engine import TpuServingEngine

    async def main():
        engine = TpuServingEngine(
            _config(
                pipeline=True, slots=4, decode_chunk=8, max_seq_len=512
            )
        )
        prompt = "overlap probe sentence for the pipelined engine. " * 8
        try:
            await asyncio.gather(
                *(
                    engine.generate(
                        prompt + str(i),
                        {"max-tokens": 32, "temperature": 0},
                    )
                    for i in range(8)
                )
            )
            summary = engine.flight.summary()
            ratio = summary["window"]["overlap_ratio"]
            assert ratio is not None and 0.0 <= ratio <= 1.0
            decode = [
                s for s in engine.flight.recent(0) if s["phase"] == "decode"
            ]
            assert decode and all(
                "host_overlapped_ms" in s for s in decode
            )
            # exact decomposition survives the new bucket
            totals = summary["totals"]
            assert totals["host_overlapped_ms"] <= totals["device_ms"]
            assert totals["wall_ms"] == pytest.approx(
                totals["device_ms"] + totals["host_ms"]
                + totals["stall_ms"],
                abs=0.05,
            )
        finally:
            await engine.close()

    run_async(main())


# --------------------------------------------------------------------------
# engine_top: overlap rendering + collapse anomaly
# --------------------------------------------------------------------------


def _top():
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tools"))
    try:
        import engine_top
    finally:
        sys.path.pop(0)
    return engine_top


def test_engine_top_renders_overlap_split():
    engine_top = _top()
    report = [
        {
            "model": "tiny",
            "slots": 4,
            "summary": {
                "totals": {
                    "wall_ms": 1000.0, "device_ms": 700.0, "host_ms": 200.0,
                    "host_overlapped_ms": 150.0, "stall_ms": 100.0,
                    "steps_by_phase": {"decode": 10}, "recompiles": 0,
                },
                "window": {
                    "tok_s": 100.0, "step_ms_p50": 10.0, "step_ms_p95": 12.0,
                    "host_overhead_ms_p50": 2.0, "host_exposed_ms_p50": 2.0,
                    "host_overlapped_ms_p50": 1.5, "overlap_ratio": 0.43,
                    "device_ms_p50": 8.0,
                },
            },
            "samples": [],
            "events": [],
        }
    ]
    frame = engine_top.render(report)
    assert "overlap 43.0%" in frame
    assert "overlapped p50" in frame


def test_engine_top_analyze_flags_overlap_collapse():
    engine_top = _top()

    def sample(occ, overlapped):
        return {
            "phase": "decode", "wall_ms": 20.0, "device_ms": 10.0,
            "host_ms": 8.0, "host_overlapped_ms": overlapped,
            "occupancy": occ, "slots": 8, "tokens": 16, "queue_depth": 0,
            "stall": None, "kv_used": None, "prefix_hits": 0,
        }

    entry = {
        "model": "tiny",
        "summary": {
            "totals": {
                "wall_ms": 400.0, "device_ms": 200.0, "host_ms": 160.0,
                "host_overlapped_ms": 0.0, "stall_ms": 40.0,
                "steps_by_phase": {"decode": 20},
            },
            "window": {"overlap_ratio": 0.0},
        },
        "samples": [sample(7, 0.0) for _ in range(20)],
        "events": [],
    }
    flags = engine_top._anomalies(entry)
    assert any("overlap collapse" in f for f in flags)

    # healthy overlap: no flag
    entry["samples"] = [sample(7, 6.0) for _ in range(20)]
    assert not any(
        "overlap collapse" in f for f in engine_top._anomalies(entry)
    )

    # low occupancy (the light/sequential regime by design): no flag
    entry["samples"] = [sample(1, 0.0) for _ in range(20)]
    assert not any(
        "overlap collapse" in f for f in engine_top._anomalies(entry)
    )

    # a PRE-pipeline dump (samples never carried the split): absence is
    # not collapse — old payloads must not false-flag
    old_entry = {
        "model": "tiny",
        "summary": {"totals": dict(entry["summary"]["totals"]), "window": {}},
        "samples": [
            {
                k: v
                for k, v in sample(7, 0.0).items()
                if k != "host_overlapped_ms"
            }
            for _ in range(20)
        ],
        "events": [],
    }
    assert not any(
        "overlap collapse" in f for f in engine_top._anomalies(old_entry)
    )

    # rollup-only dump (bench record): the top-level ratio is the signal
    rollup_entry = {
        "overlap_ratio": 0.0,
        "host_exposed_ms_p50": 5.0,
        "totals": {
            "wall_ms": 900.0, "device_ms": 500.0, "host_ms": 400.0,
            "host_overlapped_ms": 0.0, "stall_ms": 0.0,
            "steps_by_phase": {"decode": 30},
        },
    }
    assert any(
        "overlap collapse" in f for f in engine_top._anomalies(rollup_entry)
    )


@pytest.mark.parametrize("budget, chunks", [(9, 1), (17, 2), (12, 2)])
def test_no_speculative_chunk_behind_the_last_one(run_async, budget, chunks):
    """When every running request ends inside the chunk in flight, a
    speculative chunk behind it could only compute discarded tokens (and
    would run ahead of whatever prefill is waiting): the burst fetches the
    one in flight and returns. A convoy of equal budgets, one token from
    the prefill and the rest in chunks of 8, costs exactly
    ceil((budget - 1) / 8) decode dispatches, and the tokens are the
    sequential loop's."""
    from langstream_tpu.serving.engine import TpuServingEngine

    prompts = ["the quick brown fox", "pack my box", "judge my vow"]

    async def run_one(pipeline: bool):
        engine = TpuServingEngine(_config(pipeline=pipeline))
        try:
            results = await asyncio.gather(*(
                engine.generate(p, {"max-tokens": budget, "temperature": 0})
                for p in prompts
            ))
            return [r["tokens"] for r in results], engine._decode_dispatches
        finally:
            await engine.close()

    async def main():
        seq_tokens, _ = await run_one(pipeline=False)
        pipe_tokens, dispatches = await run_one(pipeline=True)
        assert pipe_tokens == seq_tokens
        assert all(len(t) == budget for t in pipe_tokens)
        assert dispatches == chunks

    run_async(main())
