"""Gateway-path TTFT benchmark: the full serving path the north star
measures (BASELINE.md: p50 gateway TTFT < 200 ms) — websocket chat gateway
→ questions topic → ai-chat-completions on the TPU engine → streamed chunks
back through the consume side of the chat socket.

Requests arrive on a Poisson process at a configurable fraction of engine
capacity (sub-saturation — the regime the target is defined in; the r2
bench's 4.3 s "TTFT" was a saturated-queue artifact). TTFT is measured at
the CLIENT: time from sending the question on the socket to the first
streamed chunk arriving on it, including gateway hops and broker transport.

Parity anchor: ``ChatCompletionsStep.java:151`` (streaming chunk path),
``examples/applications/openai-completions/pipeline.yaml:40-49``.
"""

from __future__ import annotations

import asyncio
import json
import random
import socket
import time
from typing import Any

PIPELINE = """
topics:
  - name: "questions-topic"
    creation-mode: create-if-not-exists
  - name: "answers-topic"
    creation-mode: create-if-not-exists
  - name: "stream-topic"
    creation-mode: create-if-not-exists
pipeline:
  - name: "chat"
    type: "ai-chat-completions"
    input: "questions-topic"
    output: "answers-topic"
    configuration:
      completion-field: "value.answer"
      stream-to-topic: "stream-topic"
      stream-response-completion-field: "value"
      min-chunks-per-message: 4
      max-tokens: %MAX_TOKENS%
      messages:
        - role: user
          content: "{{ value.question }}"
"""

CONFIGURATION = """
configuration:
  resources:
    - type: "tpu-serving-configuration"
      name: "tpu"
      configuration:
%SERVING%
"""

GATEWAYS = """
gateways:
  - id: "chat"
    type: chat
    chat-options:
      questions-topic: "questions-topic"
      answers-topic: "stream-topic"
      headers:
        - key: "langstream-client-session-id"
          value-from-parameters: sessionId
"""

INSTANCE = """
instance:
  streamingCluster:
    type: memory
"""

# the streaming phase wants one gateway frame per decode chunk — chunk
# batching would average the very inter-frame intervals it measures
STREAM_PIPELINE = PIPELINE.replace(
    "min-chunks-per-message: 4", "min-chunks-per-message: 1"
)


def _pct(sorted_values, q: float):
    """Nearest-rank percentile of an already-sorted list (None when
    empty) — the ONE helper every phase quantiles with, so the rounding
    semantics can never drift between phases."""
    if not sorted_values:
        return None
    return sorted_values[
        min(len(sorted_values) - 1, int(q * len(sorted_values)))
    ]


def _yaml_serving(serving: dict[str, Any]) -> str:
    return "\n".join(
        f"        {key}: {json.dumps(value)}"
        for key, value in serving.items()
        if value is not None
    )


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


async def run_gateway_bench(
    serving: dict[str, Any],
    *,
    prompt: str,
    max_tokens: int = 48,
    requests: int = 64,
    warmup: int = 6,
    arrival_rate_hz: float = 4.0,
    seed: int = 7,
    instance_yaml: str | None = None,
) -> dict[str, Any]:
    """Returns {"gateway_ttft_p50_s", "gateway_ttft_p99_s", "e2e_p50_s",
    "arrival_rate_hz", "requests"}.

    ``instance_yaml`` overrides the streaming cluster (default: the memory
    broker) — ``BENCH_BROKER=tsb`` routes the whole chat path through a
    real tsbroker process so a recorded perf number includes a real broker
    transport."""
    import aiohttp

    from langstream_tpu.controlplane.server import (
        ControlPlaneServer,
        LocalComputeRuntime,
    )
    from langstream_tpu.controlplane.stores import InMemoryApplicationStore
    from langstream_tpu.gateway.server import GatewayRegistry, GatewayServer

    registry = GatewayRegistry()
    compute = LocalComputeRuntime(gateway_registry=registry)
    control = ControlPlaneServer(
        store=InMemoryApplicationStore(), compute=compute, port=_free_port()
    )
    gateway = GatewayServer(registry=registry, port=_free_port())
    await control.start()
    await gateway.start()
    session = aiohttp.ClientSession()
    try:
        api = f"http://127.0.0.1:{control.port}"
        async with session.put(f"{api}/api/tenants/bench") as resp:
            assert resp.status in (200, 201), await resp.text()
        payload = {
            "files": {
                "pipeline.yaml": PIPELINE.replace(
                    "%MAX_TOKENS%", str(max_tokens)
                ),
                "configuration.yaml": CONFIGURATION.replace(
                    "%SERVING%", _yaml_serving(serving)
                ),
                "gateways.yaml": GATEWAYS,
            },
            "instance": instance_yaml or INSTANCE,
        }
        async with session.post(
            f"{api}/api/applications/bench/chatapp", json=payload
        ) as resp:
            assert resp.status in (200, 201), await resp.text()

        ws_base = f"ws://127.0.0.1:{gateway.port}"

        async def one_request(i: int) -> dict[str, float]:
            url = f"{ws_base}/v1/chat/bench/chatapp/chat?param:sessionId=s{i}"
            async with session.ws_connect(url) as chat:
                t0 = time.monotonic()
                await chat.send_json({"value": {"question": prompt}})
                ttft = None
                while True:
                    msg = await asyncio.wait_for(chat.receive_json(), 600)
                    # ack for the produce; pushes carry the streamed chunks
                    if "record" not in msg:
                        continue
                    if ttft is None:
                        ttft = time.monotonic() - t0
                    headers = (msg.get("record") or {}).get("headers") or {}
                    if headers.get("stream-last-message") in ("true", True):
                        return {
                            "ttft": ttft,
                            "e2e": time.monotonic() - t0,
                        }

        from langstream_tpu.serving.engine import TpuServingEngine

        # warmup compiles prefill + decode variants: sequential requests
        # cover the light-load regime (and the engine's own warmup-on-start
        # wave, when configured), then a concurrent wave drives the active
        # slot count past the light threshold so the heavy-chunk burst and
        # padded prefill batches compile BEFORE measurement — a first
        # compile landing mid-run convoys every queued request behind it
        for i in range(warmup):
            await one_request(10_000 + i)
        if warmup > 0:
            wave = min(int(serving.get("slots", 8) or 8), 16)
            await asyncio.gather(
                *(one_request(20_000 + i) for i in range(wave))
            )

        # drop warmup requests from the engine-side timing samples so the
        # TTFT decomposition below covers only the measured window — and
        # from the journey ledger, which decomposes the same window per
        # request (serving/journey.py)
        from langstream_tpu.serving.journey import (
            JOURNEYS,
            segments as journey_segments,
        )

        with TpuServingEngine._instances_lock:
            engines = list(TpuServingEngine._instances.values())
        for engine in engines:
            engine.request_timings.clear()
        JOURNEYS.clear()

        rng = random.Random(seed)
        tasks: list[asyncio.Task] = []
        for i in range(requests):
            tasks.append(asyncio.ensure_future(one_request(i)))
            await asyncio.sleep(rng.expovariate(arrival_rate_hz))
        samples = await asyncio.gather(*tasks)
        ttfts = sorted(s["ttft"] for s in samples)
        e2es = sorted(s["e2e"] for s in samples)

        pct = _pct

        out = {
            "gateway_ttft_p50_s": round(pct(ttfts, 0.50), 4),
            "gateway_ttft_p99_s": round(pct(ttfts, 0.99), 4),
            "e2e_p50_s": round(pct(e2es, 0.50), 4),
            "arrival_rate_hz": arrival_rate_hz,
            "requests": requests,
        }
        # TTFT decomposition from the engine's per-request timestamps:
        # queue-wait (enqueue → slot admission), prefill (admission → first
        # token), first-chunk (everything after the engine emitted the
        # first token: stream adapter, broker hop, gateway push — the
        # client-measured p50 minus the engine-measured p50). A p50 16x
        # over target now names its component instead of one opaque number.
        # Re-snapshot _instances: with warmup=0 the engine is only lazily
        # created during the measured window, after the snapshot above.
        with TpuServingEngine._instances_lock:
            engines = list(TpuServingEngine._instances.values())
        timings = [t for e in engines for t in list(e.request_timings)]
        if timings:
            queue_waits = sorted(t["queue_wait"] for t in timings)
            prefills = sorted(t["prefill"] for t in timings)
            engine_ttfts = sorted(t["ttft"] for t in timings)
            out.update({
                "queue_wait_p50_s": round(pct(queue_waits, 0.50), 4),
                "queue_wait_p99_s": round(pct(queue_waits, 0.99), 4),
                "prefill_p50_s": round(pct(prefills, 0.50), 4),
                "engine_ttft_p50_s": round(pct(engine_ttfts, 0.50), 4),
                "first_chunk_p50_s": round(
                    max(0.0, pct(ttfts, 0.50) - pct(engine_ttfts, 0.50)), 4
                ),
            })
        # per-request journey segments (serving/journey.py): the same
        # TTFT decomposition as above, but per REQUEST and per lifecycle
        # edge — queue vs prefill vs (under split pools) transfer vs
        # decode-admission vs first-step — the instrument the split-pool
        # bench round compares against the combined baseline. Segments
        # absent from this run's topology (no handoffs on a combined
        # fleet) simply don't appear; perf_diff reports that as coverage
        # drift, never a regression.
        seg_samples: dict[str, list[float]] = {}
        for jid in JOURNEYS.ids():
            for seg in journey_segments(JOURNEYS.events(jid)):
                seg_samples.setdefault(seg["segment"], []).append(
                    seg["ms"] / 1000.0
                )
        journey_out: dict[str, Any] = {}
        for name in (
            "ingest", "queue", "prefix-hydrate", "adapter-hydrate",
            "prefill", "export",
            "handoff-wait", "transfer", "decode-admission", "first-step",
            "decode",
        ):
            values = sorted(seg_samples.get(name) or [])
            if values:
                journey_out[name] = {
                    "p50_s": round(pct(values, 0.50), 4),
                    "p99_s": round(pct(values, 0.99), 4),
                    "n": len(values),
                }
        if journey_out:
            out["journey_segments"] = journey_out
        # decode roofline: the HBM-bandwidth floor for one decode step at
        # this engine shape (profiling.decode_step_bytes), so a recorded
        # tok/s number carries its achieved-vs-possible context. Achieved
        # step time comes from the ENGINE-side decode phase over the
        # actual per-request step count — EOS can end generation well
        # before max_tokens, so dividing a client-side window by the token
        # budget would overstate utilization (even past 1.0).
        if engines and max_tokens > 1:
            from langstream_tpu.serving.profiling import decode_step_bytes

            engine = engines[0]
            cfg = engine.config
            try:
                window = cfg.max_seq_len
                roofline = decode_step_bytes(
                    engine.model_config,
                    slots=cfg.slots,
                    window=window,
                    quantize=cfg.quantize,
                    kv_dtype_bytes=4 if cfg.model_dtype == "float32" else 2,
                    kv_quantize=cfg.kv_quantize,
                )
            except Exception as e:
                # shapes the roofline model doesn't cover (MoE trees):
                # the bench result simply omits the roofline keys
                print(f"roofline unavailable for this model: {e}")
                roofline = None
            step_ms = sorted(
                t["decode"] / (t["tokens"] - 1) * 1000.0
                for t in timings
                if t.get("tokens", 0) > 1
            )
            if roofline is not None and step_ms:
                achieved_ms = pct(step_ms, 0.50)
                floor_ms = roofline.min_step_ms()
                utilization = roofline.utilization(achieved_ms)
                out.update({
                    # null off-TPU: there is no roof to hold a CPU run to
                    "roofline_min_step_ms": (
                        round(floor_ms, 4) if floor_ms is not None else None
                    ),
                    "achieved_step_ms_p50": round(achieved_ms, 4),
                    "hbm_utilization": (
                        round(utilization, 4)
                        if utilization is not None
                        else None
                    ),
                    # which roof: the device as JAX reports it + the
                    # allocator's limit (null off-TPU)
                    "device_kind": roofline.device_kind,
                    "hbm_bytes": roofline.hbm_bytes,
                })
        # flight-recorder rollup: attributes the TTFT gap — was the engine
        # stalled (and why), paying host overhead, or convoyed behind a
        # recompile — so BENCH can name the component instead of re-guessing
        if engines:
            from langstream_tpu.serving.flight import bench_rollup

            # the engine this bench configured; fall back to the first
            # live one, and record when other engines were present so a
            # single-engine rollup is never mistaken for the whole process
            chat_engine = next(
                (e for e in engines if e.config.model == serving.get("model")),
                engines[0],
            )
            out["flight"] = bench_rollup(chat_engine.flight.summary())
            if len(engines) > 1:
                out["flight"]["engines_observed"] = len(engines)
                out["flight"]["model"] = chat_engine.config.model
        return out
    finally:
        await session.close()
        await gateway.stop()
        await control.stop()
        await compute.close()


async def run_stream_phase(
    *,
    serving: dict[str, Any] | None = None,
    streams: int = 8,
    disconnects: int = 3,
    max_tokens: int = 32,
    warmup: int = 2,
    prompt: str = "please stream the full fleet status report",
    instance_yaml: str | None = None,
) -> dict[str, Any]:
    """Streaming-delivery phase (docs/OBSERVABILITY.md Streaming): N
    concurrent streaming WS clients against the in-process gateway +
    TBT-instrumented engine (``streaming: true``, one frame per decode
    chunk), measuring the SLO surface the tbt plane alerts on —
    client-observed time-between-frames p50/p99/max per priority class,
    first-frame TTFB, engine-side stall count — then a mid-stream
    disconnect burst whose verdict is the cancellation ledger:
    ``slots_reclaimed_on_disconnect`` (every disconnected stream's
    decode slot freed at a chunk boundary, ``stream-cancel`` logged with
    its wasted-token bill) — the zero-silent-loss shape of the streaming
    plane. ``perf_diff`` declares the worse-directions so a regression
    that stretches TBT, stalls streams, or leaks cancelled slots is
    flagged, not averaged away."""
    import aiohttp

    from langstream_tpu.controlplane.server import (
        ControlPlaneServer,
        LocalComputeRuntime,
    )
    from langstream_tpu.controlplane.stores import InMemoryApplicationStore
    from langstream_tpu.gateway.server import GatewayRegistry, GatewayServer
    from langstream_tpu.serving.engine import TpuServingEngine

    serving = dict(serving or {})
    serving.setdefault("model", "tiny")
    serving.setdefault("slots", 4)
    serving.setdefault("max-seq-len", 256)
    serving.setdefault("decode-chunk", 4)
    serving.setdefault("model-dtype", "float32")
    serving.setdefault("streaming", True)

    registry = GatewayRegistry()
    compute = LocalComputeRuntime(gateway_registry=registry)
    control = ControlPlaneServer(
        store=InMemoryApplicationStore(), compute=compute, port=_free_port()
    )
    gateway = GatewayServer(registry=registry, port=_free_port())
    await control.start()
    await gateway.start()
    session = aiohttp.ClientSession()
    t_start = time.monotonic()
    try:
        api = f"http://127.0.0.1:{control.port}"
        async with session.put(f"{api}/api/tenants/bench") as resp:
            assert resp.status in (200, 201), await resp.text()
        payload = {
            "files": {
                "pipeline.yaml": STREAM_PIPELINE.replace(
                    "%MAX_TOKENS%", str(max_tokens)
                ),
                "configuration.yaml": CONFIGURATION.replace(
                    "%SERVING%", _yaml_serving(serving)
                ),
                "gateways.yaml": GATEWAYS,
            },
            "instance": instance_yaml or INSTANCE,
        }
        async with session.post(
            f"{api}/api/applications/bench/streamapp", json=payload
        ) as resp:
            assert resp.status in (200, 201), await resp.text()

        ws_base = f"ws://127.0.0.1:{gateway.port}"

        async def one_stream(
            i: int, priority: str = "default", disconnect_after: int = 0
        ) -> dict[str, Any]:
            # option:streaming stamps the per-message stream-id header
            # the engine registers its future under (disconnect →
            # cancel); param:priority keys the per-class TBT digests
            url = (
                f"{ws_base}/v1/chat/bench/streamapp/chat"
                f"?param:sessionId=s{i}&option:streaming=true"
                f"&param:priority={priority}"
            )
            out: dict[str, Any] = {
                "frames": 0, "intervals": [], "priority": priority,
            }
            async with session.ws_connect(url) as chat:
                t0 = time.monotonic()
                await chat.send_json({"value": {"question": f"{prompt} #{i}"}})
                last_t = None
                while True:
                    msg = await asyncio.wait_for(chat.receive_json(), 600)
                    if "record" not in msg:
                        continue  # the produce ack; frames are pushes
                    now = time.monotonic()
                    out["frames"] += 1
                    if last_t is None:
                        out["ttfb"] = now - t0
                    else:
                        out["intervals"].append(now - last_t)
                    last_t = now
                    if disconnect_after and out["frames"] >= disconnect_after:
                        # leave mid-generation: the async-with teardown
                        # closes the socket, the gateway cancels the
                        # stream-key, the engine frees the slot at the
                        # next chunk boundary
                        out["disconnected"] = True
                        return out
                    headers = (msg.get("record") or {}).get("headers") or {}
                    if headers.get("stream-last-message") in ("true", True):
                        out["e2e"] = now - t0
                        return out

        # warmup compiles prefill + decode variants (sequential, then a
        # small concurrent wave) so no measured TBT interval carries an
        # XLA compile inside it
        for i in range(warmup):
            await one_stream(10_000 + i)
        if warmup > 0:
            wave = min(int(serving.get("slots", 4) or 4), 8)
            await asyncio.gather(
                *(one_stream(20_000 + i) for i in range(wave))
            )

        with TpuServingEngine._instances_lock:
            engines = list(TpuServingEngine._instances.values())
        assert engines, "no engine came up behind the streaming gateway"
        engine = engines[0]
        engine.request_timings.clear()
        base = dict(engine.stats().get("streaming") or {})

        # ---- measured wave: mixed priority classes -------------------
        classes = ("interactive", "default")
        results = await asyncio.gather(
            *(
                one_stream(i, priority=classes[i % len(classes)])
                for i in range(streams)
            )
        )

        # ---- disconnect burst: leave after the first frame -----------
        burst = await asyncio.gather(
            *(
                one_stream(50_000 + i, disconnect_after=1)
                for i in range(disconnects)
            )
        )
        # the cancel lands via the gateway's socket-teardown sweep and
        # the engine observes it at the next chunk boundary: wait the
        # ledger out instead of racing it
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            now_s = engine.stats().get("streaming") or {}
            if (
                now_s.get("reclaimed", 0) - base.get("reclaimed", 0)
                >= disconnects
            ):
                break
            await asyncio.sleep(0.05)

        streaming_now = dict(engine.stats().get("streaming") or {})
        cancel_events = [
            e
            for e in engine.flight.recent_events(0)
            if e["kind"] == "stream-cancel"
        ]

        pct = _pct
        ttfbs = sorted(r["ttfb"] for r in results if "ttfb" in r)
        intervals_by_class: dict[str, list[float]] = {}
        all_intervals: list[float] = []
        for r in results:
            intervals_by_class.setdefault(r["priority"], []).extend(
                r["intervals"]
            )
            all_intervals.extend(r["intervals"])
        all_intervals.sort()
        frames = sorted(r["frames"] for r in results)
        cancelled = streaming_now.get("cancelled", 0) - base.get(
            "cancelled", 0
        )
        reclaimed = streaming_now.get("reclaimed", 0) - base.get(
            "reclaimed", 0
        )
        out: dict[str, Any] = {
            "streams": streams,
            "disconnects": disconnects,
            "max_tokens": max_tokens,
            # client-observed: the ONLY vantage the SLO is defined at —
            # engine emit → broker hop → gateway push all inside it
            "gateway_stream_ttfb_s": round(pct(ttfbs, 0.50), 4),
            "gateway_stream_tbt_p50_s": round(pct(all_intervals, 0.50), 4),
            "gateway_stream_tbt_p99_s": round(pct(all_intervals, 0.99), 4),
            "gateway_stream_tbt_max_s": round(all_intervals[-1], 4)
            if all_intervals
            else None,
            "gateway_stream_frames_min": frames[0] if frames else 0,
            # the byte-identity acceptance rides on ≥2 incremental frames
            "multi_frame": bool(frames) and frames[0] >= 2,
            "tbt_by_class": {
                name: {
                    "p50_s": round(pct(sorted(vals), 0.50), 4),
                    "p99_s": round(pct(sorted(vals), 0.99), 4),
                    "max_s": round(max(vals), 4),
                    "n": len(vals),
                }
                for name, vals in sorted(intervals_by_class.items())
                if vals
            },
            # engine-side per-class digests (the stats()["streaming"]
            # surface): client TBT minus this is the transport share
            "engine_tbt_by_class": streaming_now.get("tbt") or {},
            "gateway_stream_stalls": streaming_now.get("stalls", 0)
            - base.get("stalls", 0),
            # the cancellation ledger (zero-silent-loss shape): every
            # disconnected stream cancelled AND its decode slot freed
            "gateway_stream_cancelled": cancelled,
            "gateway_stream_reclaimed": reclaimed,
            "gateway_stream_cancel_reclaim_fraction": round(
                reclaimed / disconnects, 4
            )
            if disconnects
            else None,
            "slots_reclaimed_on_disconnect": reclaimed >= disconnects,
            "gateway_stream_tokens_wasted": sum(
                int(e.get("tokens_wasted") or 0) for e in cancel_events
            ),
            "stream_cancel_events": len(cancel_events),
            "disconnected_streams": sum(
                1 for r in burst if r.get("disconnected")
            ),
            "wall_s": round(time.monotonic() - t_start, 3),
        }
        return out
    finally:
        await session.close()
        await gateway.stop()
        await control.stop()
        await compute.close()


async def run_warm_prefix_phase(
    *,
    serving: dict[str, Any] | None = None,
    tenants: int = 8,
    repeats: int = 2,
    system_chars: int = 640,
    max_tokens: int = 8,
    t2_dir: str | None = None,
) -> dict[str, Any]:
    """Warm-prefix phase for the tiered prefix store (docs/PREFIX.md):
    N tenants share one long system prompt across TWO replicas of the
    same fleet, routed by prefix affinity.

    Replica A takes the flood first (tenant prompts differ only in
    their short question suffix), so its T0 cache fills, the byte
    budgets demote the shared blocks T0→T1→T2, and the router pins the
    prompt's prefix digest to A. Then A drains and replica B — sharing
    only the T2 object store — serves the same prefix: its first
    request HYDRATES (T2→T1→T0) instead of recomputing, and the bench
    records the per-tier hit counts, the router's prefix counters, the
    ``prefix-hydrate`` journey segment, and cold-compute vs hydrated
    TTFT. Runs the engines in-process over a shared local-disk T2 —
    the cross-replica path without a second host."""
    import tempfile

    from langstream_tpu.gateway.router import ReplicaRouter
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine
    from langstream_tpu.serving.journey import (
        JOURNEYS,
        segments as journey_segments,
    )
    from langstream_tpu.serving.prefixstore import (
        PrefixStoreSpec,
        prefix_digest_for_text,
    )

    t2_dir = t2_dir or tempfile.mkdtemp(prefix="bench_prefix_t2_")
    serving = dict(serving or {})
    serving.setdefault("model", "tiny")
    serving.setdefault("slots", 4)
    serving.setdefault("max-seq-len", 1024)
    serving.setdefault("decode-chunk", 8)
    serving.setdefault("model-dtype", "float32")
    serving.setdefault("kv-layout", "paged")
    serving.setdefault("kv-block-size", 32)
    serving.setdefault("prefix-cache", True)
    # tight tier budgets so the shared blocks cascade to T2 within the
    # phase instead of needing HBM pressure: T0 keeps ~4 blocks, T1 is
    # pass-through (every demotion reaches object storage)
    serving["prefix-store"] = {
        "t0-bytes": None,  # per-replica below (A demotes, B may keep)
        "t1-bytes": 1,
        "t2": {"type": "local", "path": t2_dir},
        "hydrate-timeout-s": 10.0,
        "t2-rescan-s": 0.2,
    }

    def _config(t0_bytes: int | None) -> ServingConfig:
        # both replicas run with a zero T0 budget so shared blocks
        # demote promptly (and the warmup can exercise the hydrate
        # path on each side before anything is measured)
        spec = dict(serving["prefix-store"], **{"t0-bytes": t0_bytes})
        d = dict(serving)
        d["prefix-store"] = spec
        return ServingConfig.from_dict(d)

    system = ("All agents must follow the fleet prompt contract. " * 40)[
        :system_chars
    ]
    digest = prefix_digest_for_text(system)
    # a long freshness window: this phase drives the router directly
    # between compile-heavy generates, and a production poller would be
    # re-observing continuously — a stale pick here would only measure
    # the rig's compile time, not the routing semantics under test
    router = ReplicaRouter(fresh_s=3600.0)

    def _observe(a_draining: bool = False) -> None:
        router.observe([
            {
                "replica": "bench-ai-0", "queued": 0, "occupancy": 0,
                "slots": 4, "draining": a_draining,
            },
            {"replica": "bench-ai-1", "queued": 0, "occupancy": 0,
             "slots": 4},
        ])

    _observe()

    engine_a = TpuServingEngine(_config(0))
    replica_names = {"bench-ai-0": engine_a}
    ttfts: list[float] = []
    cold_ttft = None
    picks: dict[str, int] = {}

    async def _ask(engine, tenant_i: int) -> float:
        prompt = f"{system}\nTenant {tenant_i}: what is the fleet status?"
        result = await engine.generate(
            prompt, {"max-tokens": max_tokens, "temperature": 0}
        )
        return float(result["ttft"])

    async def _drain_store(engine, rounds: int) -> None:
        # wait the demotion cascade out: the chain unwinds leaf-first,
        # so the head digest — the one a cold replica must find first —
        # reaches object storage last
        for _ in range(rounds):
            st = engine.stats()["prefixstore"]
            if (
                st["t0"]["blocks"] == 0
                and st["t1"]["entries"] == 0
                and not st["t2"]["in_transit_bytes"]
                and not st["t2"]["pending_jobs"]
            ):
                return
            await asyncio.sleep(0.02)

    async def _warm_variants(engine, who: str) -> None:
        # compile BOTH prefill paths before any measured request — the
        # full prefill (cold-compute baseline) and the prefix
        # continuation (warm/hydrated requests) are differently-shaped
        # XLA programs, and a first compile landing inside a measured
        # TTFT would drown the tier effect it measures. The text is
        # replica-UNIQUE from its FIRST character (a shared leading
        # block would hydrate from T2 and skip the full-prefill compile
        # the cold baseline needs warmed), and slightly LONGER than the
        # measured system prompt so the continuation request's reused-
        # prefix window lands in the same read-blocks bucket as the
        # measured warm/hydrated requests.
        warm = (f"{who} variant warmup preamble, shared with no one. "
                * 40)[: system_chars + 48]
        first = f"{warm}\nTenant w: first?"
        await engine.generate(first, {"max-tokens": 2, "temperature": 0})
        await engine.generate(
            f"{warm}\nTenant w: again, reusing the cached prefix?",
            {"max-tokens": 2, "temperature": 0},
        )
        if engine.prefix_store.spec.t0_bytes == 0:
            # a zero T0 budget demotes the warmup chain to T2; one more
            # request on it then exercises hydrate → promote, compiling
            # the fetch/scatter programs the measured requests reuse
            await _drain_store(engine, 600)
            # the EXACT first prompt again: its whole registered chain
            # hydrates, so the continuation variant this compiles has
            # the same short-suffix bucket the measured repeats use
            await engine.generate(first, {"max-tokens": 2, "temperature": 0})
            # the promoted blocks now re-demote (t0-bytes=0): wait the
            # cascade out so ITS first gather/serialize compiles land
            # here, not inside a measured request's admission pass
            await _drain_store(engine, 600)

    await _warm_variants(engine_a, "replica-a")
    for r in range(repeats):
        for i in range(tenants):
            target = router.pick(f"tenant-{i}", prefix=digest)
            picks[target] = picks.get(target, 0) + 1
            ttft = await _ask(replica_names[target], i)
            if cold_ttft is None:
                cold_ttft = ttft
            else:
                ttfts.append(ttft)
    # let the demotion cascade drain FULLY to object storage before A
    # goes away (see _drain_store: the head digest lands last)
    await _drain_store(engine_a, 3000)
    stats_a = engine_a.stats()["prefixstore"]
    router_mid = dict(router.stats())
    await engine_a.close()
    TpuServingEngine.reset_instances()

    # replica B: same fleet, fresh HBM, shared T2. A is draining, so
    # the router breaks the prefix pin and re-pins onto B.
    engine_b = TpuServingEngine(_config(0))
    engine_b.prefix_store.flush(10.0)
    _observe(a_draining=True)
    await _warm_variants(engine_b, "replica-b")
    # cold-compute baseline on B: an equally long prompt that shares NO
    # prefix with anything in the tiers
    baseline_prompt = ("Entirely different preamble with no shared head. "
                       * 40)[:system_chars]
    cold_compute = float(
        (
            await engine_b.generate(
                f"{baseline_prompt}\nTenant x: what is the fleet status?",
                {"max-tokens": max_tokens, "temperature": 0},
            )
        )["ttft"]
    )
    JOURNEYS.clear()
    target = router.pick("tenant-0", prefix=digest)
    assert target == "bench-ai-1", target
    hydrated_ttft = await _ask(engine_b, 0)
    # repeat traffic (any tenant) now follows the prefix pin back to B
    repeat_target = router.pick("tenant-3", prefix=digest)
    stats_b = engine_b.stats()["prefixstore"]
    seg_samples: list[float] = []
    for jid in JOURNEYS.ids():
        for seg in journey_segments(JOURNEYS.events(jid)):
            if seg["segment"] == "prefix-hydrate":
                seg_samples.append(seg["ms"] / 1000.0)
    await engine_b.close()
    TpuServingEngine.reset_instances()

    ttfts.sort()
    pct = _pct

    out: dict[str, Any] = {
        "tenants": tenants,
        "repeats": repeats,
        "system_chars": system_chars,
        "prefix_cold_ttft_s": round(cold_ttft or 0.0, 4),
        "prefix_warm_ttft_p50_s": round(pct(ttfts, 0.50), 4) if ttfts else None,
        "prefix_warm_ttft_p99_s": round(pct(ttfts, 0.99), 4) if ttfts else None,
        # replica B: hydrate-vs-recompute, the cross-replica headline
        "cold_compute_ttft_s": round(cold_compute, 4),
        "prefix_hydrate_ttft_s": round(hydrated_ttft, 4),
        "prefix_hydrate_speedup": round(
            cold_compute / hydrated_ttft, 3
        ) if hydrated_ttft > 0 else None,
        "tier_hits": {
            "t0_warm_hits": stats_a["t0"]["hits"],
            "t1_promotions_b": stats_b["t1"]["hits"],
            "t2_hydrations_b": stats_b["hydrations"],
        },
        "replica_a": {
            "demotions_t0_t1": stats_a["demotions_t0_t1"],
            "demotions_t1_t2": stats_a["demotions_t1_t2"],
            "t2_entries": stats_a["t2"]["entries"],
            "ledger": stats_a["ledger"],
        },
        "replica_b": {
            "hydrations": stats_b["hydrations"],
            "promotions": stats_b["promotions"],
            "hydrate_failures": stats_b["hydrate_failures"],
            "ledger": stats_b["ledger"],
        },
        "router": {
            "prefix_hits": router.stats()["prefix_hits"],
            "prefix_rerouted": router.stats()["prefix_rerouted"],
            "pinned_prefixes": router.stats()["pinned_prefixes"],
            "warm_phase_prefix_hits": router_mid["prefix_hits"],
            "repeat_followed_pin": repeat_target == "bench-ai-1",
            "picks_by_replica": picks,
        },
    }
    if seg_samples:
        seg_samples.sort()
        out["journey_segments"] = {
            "prefix-hydrate": {
                "p50_s": round(pct(seg_samples, 0.50), 4),
                "p99_s": round(pct(seg_samples, 0.99), 4),
                "n": len(seg_samples),
            }
        }
    return out


async def run_multi_lora_phase(
    *,
    serving: dict[str, Any] | None = None,
    tenants: int = 6,
    adapters: int = 4,
    repeats: int = 3,
    max_tokens: int = 8,
    t2_dir: str | None = None,
) -> dict[str, Any]:
    """Multi-LoRA phase for the tiered adapter store (docs/ADAPTERS.md):
    N tenants spread over M named adapters with M > the device budget
    (``t0-entries``), so heterogeneous-adapter traffic churns the T0
    row LRU — load, evict, re-load — while half the fleet is ONLY
    published to the T2 origin and first-touches take the hydration
    path a cross-replica cold start takes.

    Records warm vs hydrate TTFT quantiles, the T0 hit ratio, eviction
    churn, the ``adapter-hydrate`` journey segment, the router's
    adapter-affinity counters, and the store's exact byte ledger with
    its conservation verdict (``t1 + in_transit + t2 == inserted +
    discovered - evicted``). ``perf_diff`` declares the worse-directions
    (TTFT p99 up, hit ratio down, evictions up) so adapter-plane
    regressions are flagged, not averaged away."""
    import tempfile

    from langstream_tpu.gateway.router import ReplicaRouter
    from langstream_tpu.serving.adapters import (
        make_lora_arrays,
        publish_adapter,
    )
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine
    from langstream_tpu.serving.journey import (
        JOURNEYS,
        segments as journey_segments,
    )

    t2_dir = t2_dir or tempfile.mkdtemp(prefix="bench_lora_t2_")
    serving = dict(serving or {})
    serving.setdefault("model", "tiny")
    serving.setdefault("slots", 4)
    serving.setdefault("max-seq-len", 256)
    serving.setdefault("decode-chunk", 4)
    serving.setdefault("model-dtype", "float32")
    serving.setdefault("kv-layout", "paged")
    serving.setdefault("kv-block-size", 16)
    t0_entries = max(2, adapters - 2)
    serving["adapter-store"] = {
        "rank": 4,
        # fewer device rows than adapters: the churn under test
        "t0-entries": t0_entries,
        "t1-bytes": 64 << 20,
        "t2": {"type": "local", "path": t2_dir},
        "hydrate-timeout-s": 10.0,
        "t2-rescan-s": 0.2,
    }
    config = ServingConfig.from_dict(serving)
    engine = TpuServingEngine(config)
    store = engine.adapter_store
    mc = engine.model_config
    fingerprint = engine.adapter_fingerprint()
    rank = config.adapter_store.rank
    names = [f"bench-lora-{m}" for m in range(adapters)]
    # even adapters install locally (T1); odd ones are published ONLY
    # to the shared T2 origin, as another replica (or an offline
    # publisher) would — their first touch exercises discover + hydrate
    published = []
    for m, name in enumerate(names):
        arrays = make_lora_arrays(
            layers=mc.layers, hidden=mc.hidden, heads=mc.heads,
            kv_heads=mc.kv_heads, head_dim=mc.head_dim, rank=rank,
            seed=101 + m,
        )
        if m % 2 == 0:
            engine.install_adapter(name, arrays)
        else:
            publish_adapter(
                {"type": "local", "path": t2_dir}, name, arrays, fingerprint
            )
            published.append(name)
    # wait for the hydrator's periodic rescan to discover the published
    # names (applying results here is loop-side: same event-loop thread
    # the engine's tier step uses)
    for _ in range(400):
        store.apply_results()
        if all(store.known(n) for n in names):
            break
        await asyncio.sleep(0.02)
    missing = [n for n in names if not store.known(n)]
    if missing:
        raise RuntimeError(f"T2 scan never discovered {missing}")

    async def _ask(tenant_i: int, name: str) -> float:
        result = await engine.generate(
            f"Tenant {tenant_i} asks via adapter {name}: status?",
            {"max-tokens": max_tokens, "temperature": 0, "adapter": name},
        )
        return float(result["ttft"])

    # warmup: compile the base path plus each device row's upload
    # program (.at[:, row].set is one XLA program per row index) —
    # first compiles must not land inside a measured TTFT
    await engine.generate(
        "warmup base path", {"max-tokens": 2, "temperature": 0}
    )
    installed = [n for i, n in enumerate(names) if i % 2 == 0]
    for name in (installed * t0_entries)[:t0_entries]:
        await _ask(-1, name)

    # a router beside the engine records the affinity semantics the
    # gateway would apply: first pick per adapter pins, repeats hit
    router = ReplicaRouter(fresh_s=3600.0)
    router.observe([
        {"replica": "bench-ai-0", "queued": 0, "occupancy": 0, "slots": 4},
        {"replica": "bench-ai-1", "queued": 0, "occupancy": 0, "slots": 4},
    ])

    JOURNEYS.clear()
    warm_ttfts: list[float] = []
    hydrate_ttfts: list[float] = []
    failures: list[str] = []
    submitted = 0
    t_start = time.monotonic()
    for _ in range(repeats):
        wave = []
        for i in range(tenants):
            name = names[i % adapters]
            router.pick(f"tenant-{i}", adapter=name)
            # resident => warm-path TTFT; not yet in T0/T1 => the TTFT
            # includes a hydration (classified at submit: concurrent
            # same-adapter requests ride the same fetch)
            resident = store.t1_has(name) or name in store.t0_resident()
            wave.append((resident, _ask(i, name)))
            submitted += 1
        results = await asyncio.gather(
            *(coro for _, coro in wave), return_exceptions=True
        )
        for (resident, _), result in zip(wave, results):
            if isinstance(result, BaseException):
                failures.append(f"{type(result).__name__}: {result}")
            elif resident:
                warm_ttfts.append(result)
            else:
                hydrate_ttfts.append(result)
    wall_s = time.monotonic() - t_start

    seg_samples: list[float] = []
    for jid in JOURNEYS.ids():
        for seg in journey_segments(JOURNEYS.events(jid)):
            if seg["segment"] == "adapter-hydrate":
                seg_samples.append(seg["ms"] / 1000.0)
    section = engine.stats()["adapters"]
    events = engine.flight.recent_events(0)
    event_counts: dict[str, int] = {}
    for e in events:
        if e["kind"].startswith("adapter-"):
            event_counts[e["kind"]] = event_counts.get(e["kind"], 0) + 1
    await engine.close()
    TpuServingEngine.reset_instances()

    def pct(values, q):
        v = _pct(values, q)
        return round(v, 4) if v is not None else None

    warm_ttfts.sort()
    hydrate_ttfts.sort()
    all_ttfts = sorted(warm_ttfts + hydrate_ttfts)
    t0 = section["t0"]
    ledger = section["ledger"]
    out: dict[str, Any] = {
        "tenants": tenants,
        "adapters": adapters,
        "t0_entries": t0_entries,
        "published_to_t2": len(published),
        "submitted": submitted,
        "completed": len(all_ttfts),
        "failures": failures,
        # zero silent loss: every request completed (a refused adapter
        # would surface here as a loud AdapterUnavailable)
        "zero_silent_loss": not failures and len(all_ttfts) == submitted,
        "multi_lora_ttft_p50_s": pct(all_ttfts, 0.50),
        "multi_lora_ttft_p99_s": pct(all_ttfts, 0.99),
        "multi_lora_warm_ttft_p50_s": pct(warm_ttfts, 0.50),
        "multi_lora_hydrate_ttft_p50_s": pct(hydrate_ttfts, 0.50),
        "multi_lora_hydrate_ttft_p99_s": pct(hydrate_ttfts, 0.99),
        "multi_lora_t0_hit_ratio": round(
            t0["hits"] / max(1, t0["hits"] + t0["loads"]), 4
        ),
        # eviction churn across every tier (T0 row churn + T1/T2)
        "multi_lora_evictions": t0["evictions"] + section["evictions"],
        "t0_evictions": t0["evictions"],
        "t0_loads": t0["loads"],
        "eviction_refusals": t0["eviction_refusals"],
        "hydrations": section["hydrations"],
        "hydrate_failures": section["hydrate_failures"],
        "fingerprint_refusals": section["fingerprint_refusals"],
        "ledger": ledger,
        "ledger_balanced": (
            ledger["t1_bytes"]
            + ledger["in_transit_bytes"]
            + ledger["t2_bytes"]
            == ledger["inserted_bytes"]
            + ledger["discovered_bytes"]
            - ledger["evicted_bytes"]
        ),
        "router": {
            "adapter_hits": router.stats()["adapter_hits"],
            "adapter_rerouted": router.stats()["adapter_rerouted"],
            "pinned_adapters": router.stats()["pinned_adapters"],
        },
        "flight_events": event_counts,
        "wall_s": round(wall_s, 3),
    }
    if seg_samples:
        seg_samples.sort()
        out["journey_segments"] = {
            "adapter-hydrate": {
                "p50_s": pct(seg_samples, 0.50),
                "p99_s": pct(seg_samples, 0.99),
                "n": len(seg_samples),
            }
        }
    return out


async def run_oom_storm_phase(
    *,
    serving: dict[str, Any] | None = None,
    requests: int = 24,
    max_tokens: int = 16,
    burst_after: int = 4,
    burst_count: int = 2,
) -> dict[str, Any]:
    """Survival phase (docs/RESILIENCE.md): flood one paged engine and
    inject a RESOURCE_EXHAUSTED burst at the pool-grow seam mid-phase
    (serving/faults.py), then record how the engine *adapted* — shrink
    and recover counts, shed rate, and the completed-vs-submitted
    ledger. The acceptance this phase instruments is zero silent loss:
    every submitted request either completes or is RateLimited with a
    retry hint; ``zero_silent_loss`` is the recorded verdict, and
    ``perf_diff`` declares the worse-directions so a regression that
    starts dropping work under pressure is flagged, not averaged away."""
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine
    from langstream_tpu.serving.faults import FaultPlan
    from langstream_tpu.serving.qos import RateLimited

    serving = dict(serving or {})
    serving.setdefault("model", "tiny")
    serving.setdefault("slots", 4)
    serving.setdefault("max-seq-len", 256)
    serving.setdefault("decode-chunk", 4)
    serving.setdefault("model-dtype", "float32")
    serving.setdefault("kv-layout", "paged")
    serving.setdefault("kv-block-size", 16)
    serving.setdefault("shrink-recovery-s", 0.5)
    serving["faults"] = [
        {
            "site": "pool-grow",
            "shape": "oom",
            "after": burst_after,
            "count": burst_count,
        }
    ]
    config = ServingConfig.from_dict(serving)
    engine = TpuServingEngine(config)
    t_start = time.monotonic()
    results = await asyncio.gather(
        *(
            engine.generate(
                f"oom storm request {i} reporting in",
                {"max-tokens": max_tokens, "temperature": 0},
            )
            for i in range(requests)
        ),
        return_exceptions=True,
    )
    completed = sum(1 for r in results if isinstance(r, dict))
    shed = sum(1 for r in results if isinstance(r, RateLimited))
    other_failures = requests - completed - shed
    ttfts = sorted(r["ttft"] for r in results if isinstance(r, dict))
    # wait out the recovery probe: the phase records whether the budget
    # actually came back, not just that it shrank
    for _ in range(200):
        if not engine.stats()["survival"].get("withheld_blocks", 0):
            break
        await asyncio.sleep(0.05)
    survival = engine.stats()["survival"]
    events = engine.flight.recent_events(0)
    shrink_events = [e for e in events if e["kind"] == "pool-shrink"]
    await engine.close()
    TpuServingEngine.reset_instances()

    def pct(values, q):
        v = _pct(values, q)
        return round(v, 4) if v is not None else None

    return {
        "submitted": requests,
        "completed": completed,
        "shed": shed,
        "other_failures": other_failures,
        "oom_storm_completed_fraction": round(completed / requests, 4),
        "oom_storm_shed_rate": round(shed / requests, 4),
        # the acceptance ledger: every miss is a loud RateLimited shed
        "zero_silent_loss": (completed + shed) == requests,
        "oom_storm_shrinks": survival["shrinks"],
        "oom_storm_restores": survival["restores"],
        "shrink_preempted": survival["shrink_preempted"],
        "budget_recovered": not survival.get("withheld_blocks", 0),
        "faults_injected": sum(
            1 for e in events if e["kind"] == "fault-injected"
        ),
        "shrink_evidence": [
            {
                k: e.get(k)
                for k in (
                    "site", "withheld_blocks", "freed_blocks",
                    "preempted", "budget_blocks", "configured_blocks",
                )
            }
            for e in shrink_events
        ],
        "oom_storm_ttft_p50_s": pct(ttfts, 0.50),
        "oom_storm_ttft_p99_s": pct(ttfts, 0.99),
        "wall_s": round(time.monotonic() - t_start, 3),
    }


async def run_partition_storm_phase(
    *,
    serving: dict[str, Any] | None = None,
    requests: int = 16,
    max_tokens: int = 10,
    drop_after: int = 2,
    drop_count: int = 3,
) -> dict[str, Any]:
    """Cross-replica failure phase (docs/RESILIENCE.md "Distributed
    failure domain"): a prefill pool hands every request off through the
    :class:`~langstream_tpu.serving.handoff.HandoffChainer` to a
    two-replica decode pool where one replica is DEAD (every offer
    refuses the connection) and the network additionally drops a burst
    of offers mid-phase (``http-import`` fault site). Records what the
    resilience plane *did* about it — re-handoffs, breaker opens,
    local-decode fallbacks, deadline sheds — and the completed-vs-
    submitted ledger. The acceptance this phase instruments: zero silent
    loss and a breaker that keeps the dead replica out of the rotation;
    ``perf_diff`` declares the worse-directions so a regression that
    starts shedding (or falling back) under partition is flagged."""
    from langstream_tpu.gateway.router import ReplicaRouter
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine
    from langstream_tpu.serving.handoff import (
        BreakerSpec,
        DeadlineExceeded,
        HandoffChainer,
        RetryPolicy,
    )
    from langstream_tpu.serving.qos import RateLimited

    serving = dict(serving or {})
    serving.setdefault("model", "tiny")
    serving.setdefault("slots", 4)
    serving.setdefault("max-seq-len", 256)
    serving.setdefault("decode-chunk", 4)
    serving.setdefault("model-dtype", "float32")
    serving.setdefault("kv-layout", "paged")
    serving.setdefault("kv-block-size", 16)
    serving.setdefault("prefix-cache", False)
    pre_cfg = ServingConfig.from_dict(
        {**serving, "pool-role": "prefill",
         # the mid-phase network partition: a burst of offers to the
         # LIVE replica drops too, so the chainer's backoff + re-route
         # discipline is exercised beyond the always-dead pod
         "faults": [{"site": "http-import", "shape": "drop",
                     "after": drop_after, "count": drop_count}]}
    )
    dec_cfg = ServingConfig.from_dict({**serving, "pool-role": "decode"})
    pre = TpuServingEngine(pre_cfg)
    dec = TpuServingEngine(dec_cfg)
    # open_s is SHORT so the live replica (whose offers the injected
    # drop burst also hits) rehabilitates through a half-open probe
    # mid-phase; the dead replica's probes keep failing, so it stays out
    # fresh_s: the phase observes once up front, and the first
    # generate pays the XLA compile — on a cold cache that alone
    # outlives the 15 s default, after which every pick would return
    # None and the whole phase would silently degenerate to local
    # fallbacks (the same guard the gateway phase's router carries)
    router = ReplicaRouter(
        fresh_s=3600.0, breaker=BreakerSpec(failures=2, open_s=0.25)
    )
    router.observe([
        {"replica": "pool-decode-0", "queued": 0, "occupancy": 0,
         "slots": serving["slots"], "pool": "decode"},
        {"replica": "pool-decode-1", "queued": 0, "occupancy": 0,
         "slots": serving["slots"], "pool": "decode"},
    ])

    async def transport(replica, payload, headers, timeout_s):
        if replica == "pool-decode-0":
            # the killed decode pod: connect refused, forever
            raise ConnectionError("connection refused (pod killed)")
        try:
            result = await dec.import_handoff(payload)
        except RateLimited as e:
            # the Transport contract (serving/handoff.py): sheds arrive
            # as HTTP answers, exactly as the pod handler maps them
            return 503, {"error": str(e), "retry_after_s": e.retry_after}, {}
        except DeadlineExceeded as e:
            return 504, {"error": str(e)}, {}
        return 200, result, {}

    chainer = HandoffChainer(
        pre, router=router, transport=transport,
        policy=RetryPolicy(attempts=4, backoff_s=0.01, backoff_cap_s=0.1),
    )
    t_start = time.monotonic()
    # bound in-flight handoffs to the pool's slot count: a local-decode
    # fallback needs a free slot, and an unbounded flood would convert
    # capacity waits into 503 sheds (imports shed rather than queue —
    # docs/DISAGG.md), which is not what this phase measures
    gate = asyncio.Semaphore(int(serving["slots"]))

    async def one(i: int) -> dict[str, Any]:
      async with gate:
        t0 = time.monotonic()
        ticket = await pre.generate(
            f"partition storm request {i} reporting in",
            {"max-tokens": max_tokens, "temperature": 0},
        )
        result = await chainer.chain(ticket)
        return {
            "wall_s": time.monotonic() - t0,
            "ttft_s": ticket.get("ttft", 0.0),
            "tokens": len(result.get("tokens") or ()),
        }

    results = await asyncio.gather(
        *(one(i) for i in range(requests)), return_exceptions=True
    )
    completed = [r for r in results if isinstance(r, dict)]
    shed = sum(
        1 for r in results if isinstance(r, (RateLimited, DeadlineExceeded))
    )
    other_failures = len(results) - len(completed) - shed
    ttfts = sorted(r["ttft_s"] for r in completed)
    walls = sorted(r["wall_s"] for r in completed)
    events = pre.flight.recent_events(0)
    survival = pre.stats()["survival"]
    rstats = router.stats()
    # the exclusion verdict reads the breaker STATE, not a post-phase
    # pick race: with open_s tuned short for mid-phase rehabilitation, a
    # pick can legitimately hand the dead replica a half-open PROBE —
    # what must never happen is its breaker closing (a probe succeeding)
    dead_state = rstats["breakers"].get("pool-decode-0", {}).get("state")
    await pre.close()
    await dec.close()
    TpuServingEngine.reset_instances()

    def pct(values, q):
        v = _pct(values, q)
        return round(v, 4) if v is not None else None

    return {
        "submitted": requests,
        "completed": len(completed),
        "shed": shed,
        "other_failures": other_failures,
        "partition_storm_completed_fraction": round(
            len(completed) / requests, 4
        ),
        "partition_storm_shed_rate": round(shed / requests, 4),
        "zero_silent_loss": (len(completed) + shed) == requests,
        # what the resilience plane did (the re-offer ledger)
        "partition_storm_rehandoffs": chainer.retries,
        "partition_storm_fallbacks": chainer.fallbacks,
        "partition_storm_breaker_opens": sum(
            b["opens"] for b in rstats["breakers"].values()
        ),
        "partition_storm_deadline_sheds": survival["deadline_sheds"],
        "breaker_open_replicas": rstats["breaker_open_replicas"],
        "dead_replica_excluded": dead_state in ("open", "half-open"),
        "faults_injected": sum(
            1 for e in events if e["kind"] == "fault-injected"
        ),
        "handoff_retry_events": sum(
            1 for e in events if e["kind"] == "handoff-retry"
        ),
        "partition_storm_ttft_p50_s": pct(ttfts, 0.50),
        "partition_storm_ttft_p99_s": pct(ttfts, 0.99),
        "partition_storm_wall_p99_s": pct(walls, 0.99),
        "wall_s": round(time.monotonic() - t_start, 3),
    }


if __name__ == "__main__":
    import sys
    from pathlib import Path

    # runnable from a checkout: `python tools/gateway_bench.py` (the same
    # bootstrap graftcheck/render_deploy use; bench.py imports us directly)
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

    from langstream_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    out = asyncio.run(
        run_gateway_bench(
            {
                "model": "tiny",
                "slots": 4,
                "max-seq-len": 128,
                "decode-chunk": 8,
            },
            prompt="ping",
            max_tokens=8,
            requests=12,
            warmup=2,
            arrival_rate_hz=8.0,
        )
    )
    print(json.dumps(out))
