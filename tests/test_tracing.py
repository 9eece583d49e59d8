"""End-to-end record tracing tests.

Layers covered: context parse/propagation unit tests, the span ring buffer
and JSONL export, broker header preservation (memory + kafka wire format),
composite stage spans, engine phase spans, the pod ``/traces`` endpoints,
the metrics histogram SPI with its no-prometheus fallback, and the
acceptance e2e — gateway → 2-agent pipeline → consume, with one trace_id
visible from every hop via both the pod endpoint and the control-plane
aggregation route."""

import asyncio
import json
import socket

import aiohttp
import pytest

from langstream_tpu.core import tracing
from langstream_tpu.core.tracing import (
    TRACE_HEADER,
    SpanBuffer,
    TraceContext,
    start_span,
)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture(autouse=True)
def _fresh_spans():
    tracing.SPANS.clear()
    yield
    tracing.SPANS.clear()


# --------------------------------------------------------------------------
# host spans on the profiler's clock: one helper, no JAX of its own
# --------------------------------------------------------------------------


def test_host_span_in_a_process_without_jax_is_one_shared_no_op():
    """A pod that never loaded JAX (a gateway with no engine) opens no-op
    spans, and the helper does not load JAX for them."""
    import os
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from langstream_tpu.core import tracing\n"
        "a = tracing.host_span('ls.hop.gw.send', records=3)\n"
        "b = tracing.host_span('ls.hop.topic')\n"
        "with a:\n"
        "    with b:\n"
        "        pass\n"
        "assert a is b is tracing._NO_SPAN, (a, b)\n"
        "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
        "print('no-op')\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=root, capture_output=True, text=True,
        timeout=120, env={**os.environ, "PYTHONPATH": root},
    )
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip() == "no-op"


def test_host_span_is_the_profiler_s_annotation_under_a_session(tmp_path):
    import jax.profiler

    from langstream_tpu.serving.flight import FlightRecorder

    # no session: the profiler's flag is read and nothing is built
    assert tracing.host_span("ls.hop.agent", records=2) is tracing._NO_SPAN
    with jax.profiler.trace(str(tmp_path)):
        span = tracing.host_span("ls.hop.agent", records=2)
        assert isinstance(span, jax.profiler.TraceAnnotation)
        with span:
            pass
    assert tracing.host_span("ls.hop.agent") is tracing._NO_SPAN
    # the engine's spans and the tenants' are the same helper
    assert FlightRecorder.span is tracing.host_span
    assert FlightRecorder(slots=1).span is tracing.host_span


@pytest.mark.parametrize("module", [
    "langstream_tpu.gateway.server", "langstream_tpu.runtime.runner",
    "langstream_tpu.runtime.memory_broker", "langstream_tpu.agents.ai",
    "langstream_tpu.serving.flight",
])
def test_every_layer_on_the_loop_opens_its_spans_through_the_one_helper(module):
    """No second wrapper: a module that opens ``ls.*`` spans binds
    ``core.tracing.host_span`` itself and never the profiler's class."""
    import importlib
    import inspect

    mod = importlib.import_module(module)
    assert mod.host_span is tracing.host_span
    assert "import TraceAnnotation" not in inspect.getsource(mod)


def test_a_gateway_s_answers_path_without_jax_opens_no_op_spans(
        run_async, monkeypatch):
    """The gateway's frames go through the helper: with JAX hidden from it
    (``sys.modules`` is what it consults) the spans of the answers topic's
    one reader's hand-out and of the socket's send are the shared no-op, and
    the frame is sent all the same."""
    import asyncio
    import sys

    from langstream_tpu.api.record import make_record
    from langstream_tpu.gateway import server

    monkeypatch.delitem(sys.modules, "jax")
    monkeypatch.setattr(tracing, "_session", None)
    opened = []

    def spy(name, **meta):
        span = tracing.host_span(name, **meta)
        opened.append((name, meta, span))
        return span

    monkeypatch.setattr(server, "host_span", spy)

    class Socket:
        closed = False
        sent: list = []

        async def send_json(self, frame):
            self.sent.append(frame)
            self.closed = True          # one frame, then the loop ends

    class Reader:
        batches = [[
            make_record(value="mine", headers={"session": "s1"}),
            make_record(value="another's", headers={"session": "s2"}),
        ]]

        async def start(self):
            pass

        async def read(self, timeout=None):
            if self.batches:
                return self.batches.pop()
            await asyncio.sleep(3600)

        async def close(self):
            pass

    class Runtime:
        async def close(self):
            pass

    async def main():
        gateway = server.GatewayServer.__new__(server.GatewayServer)
        answers = server._AnswersReader(("{}", "answers"), Runtime(), Reader())
        try:
            socket_ = Socket()
            await gateway._chat_send_loop(
                socket_, answers, answers.subscribe({"session": "s1"}))
        finally:
            await answers.stop()
        return socket_

    socket_ = run_async(main())
    assert [f["record"]["value"] for f in socket_.sent] == ["mine"]
    assert [(name, meta) for name, meta, _ in opened] == [
        ("ls.hop.gw.send", {"records": 2}),    # the reader's hand-out
        ("ls.hop.gw.send", {"records": 1})]    # the socket's own send
    assert all(span is tracing._NO_SPAN for _, _, span in opened)
    assert "jax" not in sys.modules


# --------------------------------------------------------------------------
# context + span units
# --------------------------------------------------------------------------


def test_context_header_roundtrip():
    ctx = TraceContext.new()
    header = ctx.to_header()
    assert header.startswith("00-") and header.endswith("-01")
    assert TraceContext.parse(header) == ctx


@pytest.mark.parametrize(
    "bad",
    [
        None,
        "",
        "not-a-traceparent",
        "00-zz-yy-01",
        "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # all-zero trace id
        "00-" + "a" * 31 + "-" + "b" * 16 + "-01",  # short trace id
        {"nested": "junk"},
        42,
    ],
)
def test_malformed_headers_parse_to_none(bad):
    assert TraceContext.parse(bad) is None


def test_start_span_parent_resolution():
    root = start_span("root", service="svc")
    assert root.parent_id is None
    child = start_span("child", service="svc", parent=root)
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    from_header = start_span(
        "h", service="svc", parent=root.context().to_header()
    )
    assert from_header.trace_id == root.trace_id
    # ambient contextvar fallback
    token = tracing.set_current(root.context())
    try:
        ambient = start_span("amb", service="svc")
    finally:
        tracing.reset_current(token)
    assert ambient.trace_id == root.trace_id
    # junk parent falls back to a fresh root, never raises
    junk = start_span("j", service="svc", parent="garbage")
    assert junk.parent_id is None


def test_span_end_idempotent_and_buffered():
    span = start_span("op", service="svc", attributes={"k": "v"})
    d1 = span.end()
    span.end(error="late")  # second end: no duplicate, no error overwrite
    spans = tracing.SPANS.spans(span.trace_id)
    assert len(spans) == 1
    assert spans[0]["name"] == "op"
    assert spans[0]["attributes"] == {"k": "v"}
    assert "error" not in spans[0]
    assert d1 >= 0


def test_ring_buffer_is_bounded_and_summarizes():
    buf = SpanBuffer(maxlen=4)
    for i in range(10):
        buf.add(
            {
                "trace_id": "t1",
                "span_id": f"s{i}",
                "parent_id": None,
                "name": f"op{i}",
                "service": "svc",
                "start_ms": float(i),
                "duration_ms": 1.0,
            }
        )
    assert len(buf.snapshot()) == 4
    summary = buf.summaries()
    assert len(summary) == 1
    assert summary[0]["trace_id"] == "t1"
    assert summary[0]["spans"] == 4
    assert summary[0]["services"] == ["svc"]


def test_jsonl_export(tmp_path, monkeypatch):
    path = tmp_path / "trace.jsonl"
    monkeypatch.setenv("LS_TPU_TRACE_LOG", str(path))
    buf = SpanBuffer(maxlen=8)
    buf.add({"trace_id": "t", "span_id": "a", "start_ms": 0, "duration_ms": 1})
    buf.add({"trace_id": "t", "span_id": "b", "start_ms": 1, "duration_ms": 1})
    # export is asynchronous (single daemon writer thread): drain first
    assert buf.drain_export(5.0)
    lines = path.read_text().splitlines()
    assert [json.loads(line)["span_id"] for line in lines] == ["a", "b"]


def test_jsonl_export_failure_disables_quietly(tmp_path, monkeypatch):
    monkeypatch.setenv("LS_TPU_TRACE_LOG", str(tmp_path / "no" / "dir" / "x"))
    buf = SpanBuffer(maxlen=8)
    buf.add({"trace_id": "t", "span_id": "a", "start_ms": 0, "duration_ms": 1})
    assert buf.drain_export(5.0)
    assert buf._export_broken is True
    buf.add({"trace_id": "t", "span_id": "b", "start_ms": 0, "duration_ms": 1})
    assert len(buf.snapshot()) == 2  # buffer unaffected by the broken sink


def test_record_span_retroactive_timing():
    import time

    ctx = TraceContext.new()
    t1 = time.monotonic() - 0.25
    tracing.record_span("phase", "svc", ctx, t1, t1 + 0.2)
    spans = tracing.SPANS.spans(ctx.trace_id)
    assert len(spans) == 1
    assert spans[0]["parent_id"] == ctx.span_id
    assert abs(spans[0]["duration_ms"] - 200.0) < 1.0


# --------------------------------------------------------------------------
# broker header preservation
# --------------------------------------------------------------------------


def test_memory_broker_preserves_trace_header(run_async):
    from langstream_tpu.api.record import make_record
    from langstream_tpu.runtime.memory_broker import (
        MemoryBroker,
        MemoryTopicConsumer,
        MemoryTopicProducer,
    )

    async def main():
        broker = MemoryBroker.get("trace-test")
        producer = MemoryTopicProducer(broker, "t")
        consumer = MemoryTopicConsumer(broker, "t", group="g")
        await consumer.start()
        ctx = TraceContext.new()
        await producer.write(
            make_record(value="v", headers={TRACE_HEADER: ctx.to_header()})
        )
        records = await consumer.read()
        assert records and records[0].header(TRACE_HEADER) == ctx.to_header()

    run_async(main())


def test_kafka_wire_format_preserves_trace_header():
    """The shared on-wire form (SDK + wire lanes) must round-trip the
    ``langstream-trace`` header like any string header — and keep dropping
    the transport-local ``__offset``."""
    from langstream_tpu.api.record import make_record
    from langstream_tpu.runtime.kafka_broker import (
        kafka_message_to_record,
        record_wire_payload,
    )

    ctx = TraceContext.new()
    record = make_record(
        value={"q": "hi"}, headers={TRACE_HEADER: ctx.to_header()}
    )
    key, value, headers = record_wire_payload(record)

    class _Msg:
        def headers(self):
            return headers

        def topic(self):
            return "t"

        def partition(self):
            return 0

        def offset(self):
            return 7

        def value(self):
            return value

        def key(self):
            return key

        def timestamp(self):
            return (1, record.timestamp)

    back = kafka_message_to_record(_Msg())
    assert back.header(TRACE_HEADER) == ctx.to_header()
    assert back.value == {"q": "hi"}


# --------------------------------------------------------------------------
# composite stage spans
# --------------------------------------------------------------------------


def test_composite_emits_stage_child_spans(run_async):
    from langstream_tpu.api.agent import (
        AgentContext,
        SingleRecordProcessor,
    )
    from langstream_tpu.api.record import make_record
    from langstream_tpu.runtime.composite import CompositeAgentProcessor

    class _Upper(SingleRecordProcessor):
        agent_type = "upper"
        agent_id = "upper-1"

        async def process_record(self, record):
            return [record.with_value(str(record.value).upper())]

    class _Suffix(SingleRecordProcessor):
        agent_type = "suffix"
        agent_id = "suffix-1"

        async def process_record(self, record):
            return [record.with_value(str(record.value) + "!")]

    async def main():
        composite = CompositeAgentProcessor([_Upper(), _Suffix()])
        await composite.setup(AgentContext(global_agent_id="app-node"))
        ctx = TraceContext.new()
        record = make_record(
            value="hi", headers={TRACE_HEADER: ctx.to_header()}
        )
        out = await composite._chain_one(record)
        assert [r.value for r in out] == ["HI!"]
        spans = tracing.SPANS.spans(ctx.trace_id)
        names = sorted(s["name"] for s in spans)
        assert names == ["stage.suffix-1", "stage.upper-1"]
        assert all(s["parent_id"] == ctx.span_id for s in spans)
        assert all(s["service"] == "app-node" for s in spans)

    run_async(main())


# --------------------------------------------------------------------------
# engine phase spans
# --------------------------------------------------------------------------


def test_engine_emits_phase_spans(run_async):
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        engine = TpuServingEngine(
            ServingConfig(model="tiny", slots=2, max_seq_len=64, decode_chunk=4)
        )
        ctx = TraceContext.new()
        token = tracing.set_current(ctx)
        try:
            result = await engine.generate("trace me", {"max-tokens": 4})
        finally:
            tracing.reset_current(token)
            await engine.close()
        assert result["tokens"]
        spans = tracing.SPANS.spans(ctx.trace_id)
        by_name = {s["name"]: s for s in spans}
        assert {"engine.queue", "engine.prefill", "engine.decode"} <= set(
            by_name
        )
        assert all(s["parent_id"] == ctx.span_id for s in spans)
        assert by_name["engine.decode"]["attributes"]["tokens"] == len(
            result["tokens"]
        )
        # phases are non-negative and anchored on one monotonic axis
        assert all(s["duration_ms"] >= 0 for s in spans)

    run_async(main())


def test_engine_without_ambient_context_stays_silent(run_async):
    """No per-record context (direct engine use, benches): no spans, and
    certainly no crash in the serving path."""
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        engine = TpuServingEngine(
            ServingConfig(model="tiny", slots=2, max_seq_len=64, decode_chunk=4)
        )
        try:
            before = len(tracing.SPANS.snapshot())
            await engine.generate("untraced", {"max-tokens": 4})
            assert len(tracing.SPANS.snapshot()) == before
        finally:
            await engine.close()

    run_async(main())


# --------------------------------------------------------------------------
# metrics: histogram SPI + no-prometheus fallback exposition
# --------------------------------------------------------------------------


def test_histogram_spi_records_observations():
    from langstream_tpu.api.metrics import PrometheusMetricsReporter, render_metrics

    reporter = PrometheusMetricsReporter(
        prefix="test_tracing_hist", agent_id="agent-h"
    )
    observe = reporter.histogram("latency_seconds", "test latencies")
    observe(0.003)
    observe(0.4)
    body = render_metrics().decode()
    assert "test_tracing_hist_latency_seconds" in body
    assert 'agent_id="agent-h"' in body


def test_fallback_registry_renders_exposition(monkeypatch):
    import langstream_tpu.api.metrics as metrics_mod

    monkeypatch.setattr(metrics_mod, "_HAVE_PROM", False)
    monkeypatch.setattr(metrics_mod, "_fallback", {})
    reporter = metrics_mod.PrometheusMetricsReporter(
        prefix="fb", agent_id="a1"
    )
    inc = reporter.counter("reqs", "requests")
    inc()
    inc(2)
    set_depth = reporter.gauge("depth", "queue depth")
    set_depth(3.5)
    observe = reporter.histogram("lat_seconds", "lat", buckets=(0.1, 1.0))
    observe(0.05)
    observe(5.0)
    body = metrics_mod.render_metrics().decode()
    assert body.strip(), "fallback exposition must never be empty"
    assert "# TYPE fb_reqs counter" in body
    assert 'fb_reqs{agent_id="a1"} 3.0' in body
    assert 'fb_depth{agent_id="a1"} 3.5' in body
    # bucket counts are cumulative and monotone up to +Inf == _count
    assert 'fb_lat_seconds_bucket{agent_id="a1",le="0.1"} 1' in body
    assert 'fb_lat_seconds_bucket{agent_id="a1",le="1.0"} 1' in body
    assert 'fb_lat_seconds_bucket{agent_id="a1",le="+Inf"} 2' in body
    assert 'fb_lat_seconds_count{agent_id="a1"} 2' in body


@pytest.mark.parametrize("have_prom", [True, False], ids=["client", "fallback"])
def test_a_counter_s_own_labels_ride_beside_the_agent_s(monkeypatch, have_prom):
    """``counter(labels=)``: one name, a series a label value
    (``langstream_engine_device_busy_seconds_total{phase}``), on either
    registry; a counter without labels renders as it did."""
    import langstream_tpu.api.metrics as metrics_mod

    if not have_prom:
        monkeypatch.setattr(metrics_mod, "_HAVE_PROM", False)
        monkeypatch.setattr(metrics_mod, "_fallback", {})
    elif not metrics_mod._HAVE_PROM:
        pytest.skip("prometheus_client is not installed")
    reporter = metrics_mod.PrometheusMetricsReporter(
        prefix=f"lbl{int(have_prom)}", agent_id="m1"
    )
    busy = {phase: reporter.counter("busy_seconds_total", "busy",
                                    labels={"phase": phase})
            for phase in ("decode", "prefill")}
    busy["decode"](1.5)
    busy["prefill"](0.25)
    busy["decode"](0.5)
    reporter.counter("idle_seconds_total", "idle")(0.125)
    body = metrics_mod.render_metrics().decode()
    name = f"lbl{int(have_prom)}"
    assert f'{name}_busy_seconds_total{{agent_id="m1",phase="decode"}} 2.0' in body
    assert f'{name}_busy_seconds_total{{agent_id="m1",phase="prefill"}} 0.25' in body
    assert f'{name}_idle_seconds_total{{agent_id="m1"}} 0.125' in body


# --------------------------------------------------------------------------
# pod endpoints: /traces, /traces/<id>, /metrics content type
# --------------------------------------------------------------------------


def test_pod_serves_traces_and_metrics(run_async, monkeypatch):
    from langstream_tpu.runtime.pod import _serve_info

    class _StubRunner:
        def info(self):
            return {"agent-id": "stub"}

    async def main():
        port = free_port()
        monkeypatch.setenv("LS_HTTP_PORT", str(port))
        span = start_span("pod-op", service="pod-svc")
        span.end()
        server = await _serve_info(_StubRunner())
        try:
            async with aiohttp.ClientSession() as session:
                base = f"http://127.0.0.1:{port}"
                async with session.get(f"{base}/metrics") as resp:
                    assert resp.status == 200
                    assert resp.headers["Content-Type"].startswith(
                        "text/plain; version=0.0.4"
                    )
                    assert (await resp.read()).strip()
                async with session.get(f"{base}/traces") as resp:
                    assert resp.status == 200
                    index = await resp.json()
                assert any(t["trace_id"] == span.trace_id for t in index)
                async with session.get(
                    f"{base}/traces/{span.trace_id}"
                ) as resp:
                    spans = await resp.json()
                assert [s["name"] for s in spans] == ["pod-op"]
        finally:
            server.close()

    run_async(main())


def test_controlplane_traces_scoped_by_exact_agent_ids():
    """Dash-prefixed sibling apps (``app`` vs ``app-b``) must not see each
    other's traces — the same leak shape pod_logs fixed in PR 1 — and the
    per-trace detail route must refuse traces the app never touched."""
    from langstream_tpu.controlplane.server import LocalComputeRuntime

    class _FakeAgentRunner:
        def __init__(self, agent_id):
            self.agent_id = agent_id

    class _FakeAppRunner:
        def __init__(self, agent_ids):
            self.runners = [_FakeAgentRunner(a) for a in agent_ids]

    compute = LocalComputeRuntime()
    compute.runners[("t", "app")] = _FakeAppRunner(["t-app-step"])
    compute.runners[("t", "app-b")] = _FakeAppRunner(["t-app-b-step"])

    span_a = start_span("agent.process", service="t-app-step")
    span_a.end()
    span_b = start_span("agent.process", service="t-app-b-step")
    span_b.end()

    index_a = [t["trace_id"] for t in compute.traces("t", "app")]
    index_b = [t["trace_id"] for t in compute.traces("t", "app-b")]
    assert index_a == [span_a.trace_id]
    assert index_b == [span_b.trace_id]
    # detail route: own trace readable, foreign trace refused
    assert compute.traces("t", "app", trace_id=span_a.trace_id)
    assert compute.traces("t", "app", trace_id=span_b.trace_id) == []
    # unknown application: nothing
    assert compute.traces("t", "ghost") == []


# --------------------------------------------------------------------------
# acceptance e2e: one trace_id across gateway → agent hops → consume
# --------------------------------------------------------------------------

PIPELINE = """
topics:
  - name: "input-topic"
    creation-mode: create-if-not-exists
  - name: "mid-topic"
    creation-mode: create-if-not-exists
  - name: "output-topic"
    creation-mode: create-if-not-exists
pipeline:
  - name: "step-one"
    id: "step-one"
    type: "compute"
    input: "input-topic"
    output: "mid-topic"
    configuration:
      fields:
        - name: "value.echo"
          expression: "fn:uppercase(value.q)"
  - name: "step-two"
    id: "step-two"
    type: "ai-chat-completions"
    input: "mid-topic"
    output: "output-topic"
    configuration:
      completion-field: "value.answer"
      messages:
        - role: user
          content: "{{ value.q }}"
"""

GATEWAYS = """
gateways:
  - id: "produce-input"
    type: produce
    topic: "input-topic"
    parameters: [sessionId]
    produce-options:
      headers:
        - key: "langstream-client-session-id"
          value-from-parameters: sessionId
  - id: "consume-output"
    type: consume
    topic: "output-topic"
    parameters: [sessionId]
    consume-options:
      filters:
        headers:
          - key: "langstream-client-session-id"
            value-from-parameters: sessionId
"""

INSTANCE = """
instance:
  streamingCluster:
    type: memory
"""


def test_e2e_single_trace_across_gateway_agents_and_controlplane(
    run_async, monkeypatch
):
    from langstream_tpu.controlplane.server import (
        ControlPlaneServer,
        LocalComputeRuntime,
    )
    from langstream_tpu.controlplane.stores import InMemoryApplicationStore
    from langstream_tpu.gateway.server import GatewayRegistry, GatewayServer
    from langstream_tpu.runtime.pod import _serve_info

    async def main():
        registry = GatewayRegistry()
        compute = LocalComputeRuntime(gateway_registry=registry)
        control = ControlPlaneServer(
            store=InMemoryApplicationStore(), compute=compute, port=free_port()
        )
        gateway = GatewayServer(registry=registry, port=free_port())
        pod_port = free_port()
        monkeypatch.setenv("LS_HTTP_PORT", str(pod_port))
        await control.start()
        await gateway.start()
        pod_server = await _serve_info(None)
        session = aiohttp.ClientSession()
        try:
            api = f"http://127.0.0.1:{control.port}"
            async with session.put(f"{api}/api/tenants/t1") as resp:
                assert resp.status == 200
            payload = {
                "files": {"pipeline.yaml": PIPELINE, "gateways.yaml": GATEWAYS},
                "instance": INSTANCE,
            }
            async with session.post(
                f"{api}/api/applications/t1/tracedapp", json=payload
            ) as resp:
                body = await resp.json()
                assert resp.status == 200, body
                assert body["status"]["status"] == "DEPLOYED", body

            ws_base = f"ws://127.0.0.1:{gateway.port}"
            consume_url = (
                f"{ws_base}/v1/consume/t1/tracedapp/consume-output"
                "?param:sessionId=s1&option:position=earliest"
            )
            produce_url = (
                f"{ws_base}/v1/produce/t1/tracedapp/produce-input"
                "?param:sessionId=s1"
            )
            async with session.ws_connect(consume_url) as consumer:
                async with session.ws_connect(produce_url) as producer:
                    await producer.send_json({"value": {"q": "hello trace"}})
                    ack = await producer.receive_json()
                    assert ack["status"] == "OK"
                    # the gateway echoes the injected trace context
                    trace_header = ack["trace"]
                    ctx = TraceContext.parse(trace_header)
                    assert ctx is not None
                push = await asyncio.wait_for(
                    consumer.receive_json(), timeout=10
                )
            record = push["record"]
            assert record["value"]["answer"]
            # the consumed record carries the same trace context end-to-end
            assert ctx.trace_id in record["headers"][TRACE_HEADER]

            # spans finish just after the final sink write; poll briefly
            async def gather_services():
                for _ in range(100):
                    spans = tracing.SPANS.spans(ctx.trace_id)
                    services = {s["service"] for s in spans}
                    if len(services) >= 3:
                        return spans, services
                    await asyncio.sleep(0.05)
                return tracing.SPANS.spans(ctx.trace_id), {
                    s["service"] for s in tracing.SPANS.spans(ctx.trace_id)
                }

            spans, services = await gather_services()
            # one trace_id with spans from the gateway AND both agent hops
            assert "gateway" in services, services
            agent_services = {
                s for s in services if s.startswith("t1-tracedapp-")
            }
            assert len(agent_services) == 2, services
            assert all(s["trace_id"] == ctx.trace_id for s in spans)
            hop_names = [s["name"] for s in spans]
            assert hop_names.count("agent.process") == 2
            assert "gateway.produce" in hop_names

            # retrievable via the pod /traces/<trace_id> endpoint
            pod_base = f"http://127.0.0.1:{pod_port}"
            async with session.get(
                f"{pod_base}/traces/{ctx.trace_id}"
            ) as resp:
                assert resp.status == 200
                pod_spans = await resp.json()
            assert {s["span_id"] for s in pod_spans} == {
                s["span_id"] for s in spans
            }

            # ... and via the control-plane aggregation route
            async with session.get(
                f"{api}/api/applications/t1/tracedapp/traces"
            ) as resp:
                assert resp.status == 200
                index = await resp.json()
            entry = next(
                t for t in index if t["trace_id"] == ctx.trace_id
            )
            assert entry["spans"] == len(spans)
            async with session.get(
                f"{api}/api/applications/t1/tracedapp/traces/{ctx.trace_id}"
            ) as resp:
                assert resp.status == 200
                cp_spans = await resp.json()
            assert {s["span_id"] for s in cp_spans} == {
                s["span_id"] for s in spans
            }
            # unknown trace id → 404
            async with session.get(
                f"{api}/api/applications/t1/tracedapp/traces/{'0' * 32}"
            ) as resp:
                assert resp.status == 404
        finally:
            await session.close()
            pod_server.close()
            await gateway.stop()
            await control.stop()

    run_async(main())
