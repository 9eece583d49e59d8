"""What a profile of the engine carries once the program names its own
work: the host spans (``serving/flight.py`` ``SPANS``) land in the profiler's
own trace with a ``seq`` that is a flight sample's ``dispatch``; the jitted
serving programs carry the scope names of the layer body's seams; and the
scopes are metadata only — the optimised HLO is the same instruction for
instruction with and without them."""

import asyncio
import contextlib
import glob
import os
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.serving.flight import HELD_SPANS, SPANS

LAYER_SCOPES = ("embed", "attn_qkv", "kv_read", "attn_out", "ffn", "lm_head",
                "sample")


def tiny_engine(**kw):
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    return TpuServingEngine(ServingConfig(
        model="tiny", model_dtype="float32", slots=4, max_seq_len=128,
        decode_chunk=4, kv_layout="paged", kv_block_size=16,
        prefix_cache=False, **kw,
    ))


# -- (2) a real capture, on the CPU backend ------------------------------


def host_spans(trace_dir, threads=False):
    """``(name, stats)`` of every ``ls.*`` event of the capture's host
    plane; with ``threads``, ``(name, stats, thread)`` (the plane's line)."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for thread, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("ls."):
                    spans.append((e.name, dict(e.stats))
                                 + ((thread,) if threads else ()))
    return spans


def test_a_capture_holds_the_engine_loop_s_spans(run_async, tmp_path):
    async def main():
        engine = tiny_engine()
        try:
            # compile outside the capture, so it holds serving and nothing else
            await engine.generate("warm the shapes", {"max-tokens": 6})
            with jax.profiler.trace(str(tmp_path)):
                await asyncio.gather(*(
                    engine.generate(f"span prompt {i}", {"max-tokens": 6})
                    for i in range(3)
                ))
        finally:
            # closing drains the chunk the pipelined burst left in flight,
            # so every dispatch the capture saw has its sample
            await engine.close()
        return engine.flight.recent(0)

    samples = run_async(main())
    spans = host_spans(str(tmp_path))
    names = {name for name, _ in spans}
    # nothing outside the documented vocabulary
    assert names <= set(SPANS), names - set(SPANS)
    assert {"ls.admit", "ls.prefill.pack", "ls.prefill.dispatch",
            "ls.prefill.fetch", "ls.prefill.emit", "ls.decode.prepare",
            "ls.decode.dispatch", "ls.decode.fetch", "ls.decode.process",
            "ls.decode.emit"} <= names
    dispatched = {s["dispatch"]: s for s in samples if "dispatch" in s}
    for wanted, phase in (("ls.decode.dispatch", "decode"),
                          ("ls.decode.fetch", "decode"),
                          ("ls.decode.process", "decode"),
                          ("ls.prefill.dispatch", "prefill"),
                          ("ls.prefill.fetch", "prefill")):
        seqs = [meta["seq"] for name, meta in spans if name == wanted]
        assert seqs, wanted
        # a span, its flight sample and its program share an identifier
        assert all(dispatched[seq]["phase"] == phase for seq in seqs), wanted
    for name, meta in spans:
        if name == "ls.decode.dispatch":
            sample = dispatched[meta["seq"]]
            assert meta["program"] == sample["program"]
            assert meta["steps"] == sample["steps"] > 0


# -- (2b) the loop's other tenants, and the wait's two halves ------------

CHAT_APP = {
    "configuration.yaml": """
configuration:
  resources:
    - type: "tpu-serving-configuration"
      name: "tpu"
      configuration:
        model: "tiny"
        model-dtype: "float32"
        slots: 4
        max-seq-len: 128
        kv-block-size: 16
        decode-chunk: 4
        prefix-cache: false
        streaming: true
""",
    "pipeline.yaml": """
topics:
  - name: "questions"
    creation-mode: create-if-not-exists
  - name: "answers"
    creation-mode: create-if-not-exists
pipeline:
  - name: "chat"
    type: "ai-chat-completions"
    input: "questions"
    output: "answers"
    configuration:
      model: "tiny"
      max-tokens: 10
      completion-field: "value.answer"
      stream-to-topic: "answers"
      stream-response-completion-field: "value"
      min-chunks-per-message: 1
      messages:
        - role: user
          content: "{{ value }}"
""",
    "gateways.yaml": """
gateways:
  - id: "chat"
    type: chat
    chat-options:
      questions-topic: "questions"
      answers-topic: "answers"
      headers:
        - key: "langstream-client-session-id"
          value-from-parameters: sessionId
""",
}
CHAT_INSTANCE = """
instance:
  streamingCluster:
    type: "memory"
  computeCluster:
    type: "local"
"""


@pytest.fixture(scope="module")
def chat_capture(tmp_path_factory):
    """One chat turn through the whole one-pod deployment (gateway, topic,
    runner, agent, engine: one event loop) under a profiler session: the
    capture's ``(name, stats, thread)`` spans."""
    import socket

    import aiohttp

    from langstream_tpu.controlplane.server import (
        ControlPlaneServer,
        LocalComputeRuntime,
    )
    from langstream_tpu.controlplane.stores import InMemoryApplicationStore
    from langstream_tpu.gateway.server import GatewayRegistry, GatewayServer
    from langstream_tpu.serving.engine import TpuServingEngine

    trace_dir = str(tmp_path_factory.mktemp("chat-capture"))

    def free_port():
        with socket.socket() as sock:
            sock.bind(("127.0.0.1", 0))
            return sock.getsockname()[1]

    async def ask(session, url, text):
        async with session.ws_connect(url) as chat:
            await chat.send_json({"value": text})
            while True:
                msg = await asyncio.wait_for(chat.receive_json(), 300)
                headers = (msg.get("record") or {}).get("headers") or {}
                if "langstream-completion-tokens" in headers:
                    return
                assert "record" in msg or msg.get("status") in (None, "OK"), msg

    async def main():
        registry = GatewayRegistry()
        compute = LocalComputeRuntime(gateway_registry=registry)
        control = ControlPlaneServer(
            store=InMemoryApplicationStore(), compute=compute, port=free_port())
        gateway = GatewayServer(registry=registry, port=free_port())
        await control.start()
        await gateway.start()
        session = aiohttp.ClientSession()
        try:
            api = f"http://127.0.0.1:{control.port}"
            async with session.put(f"{api}/api/tenants/prof") as resp:
                assert resp.status in (200, 201)
            async with session.post(
                f"{api}/api/applications/prof/chat",
                json={"files": CHAT_APP, "instance": CHAT_INSTANCE},
            ) as resp:
                assert resp.status in (200, 201), await resp.text()
            url = (f"ws://127.0.0.1:{gateway.port}/v1/chat/prof/chat/chat"
                   "?param:sessionId=s1")
            await ask(session, url, "warm the shapes")   # builds the engine
            with jax.profiler.trace(trace_dir):
                await ask(session, url, "what runs on this loop?")
        finally:
            await session.close()
            await gateway.stop()
            await control.stop()
            await compute.close()
            with TpuServingEngine._instances_lock:
                engines = list(TpuServingEngine._instances.values())
            for engine in engines:
                await engine.close()
            TpuServingEngine.reset_instances()

    asyncio.run(main())
    return host_spans(trace_dir, threads=True)


def test_every_span_of_a_chat_turn_is_in_the_vocabulary(chat_capture):
    names = {name for name, _, _ in chat_capture}
    assert names <= set(SPANS), names - set(SPANS)
    assert set(HELD_SPANS) <= set(SPANS)


@pytest.mark.parametrize("name", [n for n in SPANS if n.startswith("ls.hop.")])
def test_a_chat_turn_opens_every_tenant_s_span(chat_capture, name):
    assert any(n == name for n, _, _ in chat_capture), name


@pytest.mark.parametrize("name", HELD_SPANS + ("ls.prefill.wait",
                                               "ls.decode.wait"))
def test_the_held_spans_and_the_waits_carry_the_dispatch_s_seq(chat_capture,
                                                               name):
    seqs = [stats.get("seq") for n, stats, _ in chat_capture if n == name]
    assert seqs and all(isinstance(seq, int) and seq > 0 for seq in seqs), seqs


def test_the_tenants_run_on_the_loop_s_thread_and_the_waits_do_not(
        chat_capture):
    threads = {}
    for name, _, thread in chat_capture:
        threads.setdefault(name, set()).add(thread)
    (loop,) = threads["ls.decode.prepare"]        # the engine's coroutine
    for name in ("ls.hop.gw.send", "ls.hop.gw.recv", "ls.hop.topic",
                 "ls.hop.agent", "ls.hop.deliver", "ls.hop.runner",
                 "ls.prefill.handoff", "ls.prefill.fetch", "ls.decode.fetch"):
        assert threads[name] == {loop}, (name, threads[name], loop)
    (dispatch,) = threads["ls.decode.dispatch"]   # the dispatch thread
    assert dispatch != loop
    for name in ("ls.prefill.wait", "ls.decode.wait"):
        assert threads[name] == {dispatch}, (name, threads[name])


# the dispatch thread's jobs that the loop awaits under a held span (and
# the decode dispatch beside them), by the qualified name of their code
THREAD_JOBS = {
    "TpuServingEngine._dispatch_prefill.<locals>._run": "ls.prefill.dispatch",
    "TpuServingEngine._fetch_prefill.<locals>._run": "ls.prefill.wait",
    "TpuServingEngine._fetch_chunk": "ls.decode.wait",
    "TpuServingEngine._decode_burst.<locals>._dispatch": "ls.decode.dispatch",
}


class _ThreadSpan:
    """``flight.span`` for the test below: counts the spans open on the
    thread that opens them."""

    open_on = {}

    def __init__(self, name, **meta):
        self.name = name

    def __enter__(self):
        self.thread = threading.get_ident()
        self.open_on.setdefault(self.thread, []).append(self.name)

    def __exit__(self, *exc):
        self.open_on[self.thread].pop()


@pytest.mark.parametrize("job", sorted(THREAD_JOBS))
def test_a_dispatch_thread_job_does_nothing_outside_its_span(run_async, job):
    """What an idle instant under a held name means (the coroutine waits
    for its turn, nobody named) holds only if the dispatch thread's work
    has a name of its own from the job's first call to its last: every
    call a job makes lies inside the span its thread opens."""
    stray, seen = [], set()

    def watch(frame, event, arg):
        # the job's own frame: what it calls with no span open on this
        # thread, the span's own construction and entry apart
        if event not in ("call", "c_call"):
            return
        caller = frame.f_back if event == "call" else frame
        if caller is None or caller.f_code.co_qualname != job:
            return
        opened = _ThreadSpan.open_on.get(threading.get_ident())
        if opened:
            seen.add(opened[-1])
            return
        callee = (frame.f_code.co_qualname if event == "call"
                  else getattr(arg, "__qualname__", repr(arg)))
        # a span's construction (its meta) and entry are not work under it
        if not callee.startswith(("_ThreadSpan", "_span_meta")):
            stray.append(callee)

    async def main():
        engine = tiny_engine()
        engine.flight.span = _ThreadSpan

        def on_the_dispatch_thread(fn, *args):
            return asyncio.get_running_loop().run_in_executor(
                engine._executor, fn, *args)

        try:
            await engine.generate("warm the shapes", {"max-tokens": 6})
            await on_the_dispatch_thread(sys.setprofile, watch)
            try:
                await asyncio.gather(*(
                    engine.generate(f"span prompt {i}", {"max-tokens": 6})
                    for i in range(3)
                ))
            finally:
                await on_the_dispatch_thread(sys.setprofile, None)
        finally:
            await engine.close()

    run_async(main())
    assert THREAD_JOBS[job] in seen, (job, seen)   # the job ran, under its span
    assert not stray, (job, stray)


# -- (3) the scope names in the lowered programs -------------------------


def programs(engine):
    """(name, jitted function, arguments) of the engine's serving programs
    at the shapes a first request would use."""
    slots = engine.config.slots
    greedy = engine._sampler_mode(
        np.zeros(1, np.float32), np.zeros(1, np.int32), np.ones(1, np.float32))
    key = jax.random.PRNGKey(0)
    temps = jnp.zeros(slots, jnp.float32)
    topks = jnp.zeros(slots, jnp.int32)
    topps = jnp.ones(slots, jnp.float32)
    tables = jnp.asarray(engine.block_mgr.tables)
    caches = (engine.params, engine.cache_k, engine.cache_v)
    decode = caches + (
        jnp.zeros(slots, jnp.int32), jnp.ones(slots, jnp.int32),
        jnp.ones(slots, bool), tables, key, temps, topks, topps)
    prefill = caches + (
        jnp.zeros((1, 32), jnp.int32), jnp.full((1,), 5, jnp.int32),
        tables[:1], key, temps[:1], topks[:1], topps[:1])
    cont = caches + (
        jnp.zeros((1, 32), jnp.int32), jnp.full((1,), 16, jnp.int32),
        jnp.full((1,), 5, jnp.int32), tables[:1], key, temps[:1], topks[:1],
        topps[:1])
    return [
        ("decode", engine._decode_fn(greedy, 2, 4, False), decode),
        ("prefill", engine._prefill_fn(greedy), prefill),
        ("prefill-continue", engine._prefill_continue_fn(greedy, 1), cont),
    ]


@pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
def test_the_lowered_programs_carry_every_scope(run_async, kernel):
    async def main():
        engine = tiny_engine(paged_kernel=kernel)
        try:
            return {name: fn.lower(*args).as_text(debug_info=True)
                    for name, fn, args in programs(engine)}
        finally:
            await engine.close()

    texts = run_async(main())
    for name, text in texts.items():
        for scope in LAYER_SCOPES:
            assert re.search(rf'[/"]{scope}/', text), (name, scope)
    if kernel == "pallas-interpret":
        assert "kv_read/paged_read" in texts["decode"]


def test_the_flash_prefill_carries_its_scope_and_kernel_name(monkeypatch):
    from langstream_tpu.models.llama import (
        LlamaConfig, init_llama_params, prefill_forward)

    monkeypatch.setenv("LS_TPU_FLASH", "interpret")
    c = LlamaConfig.tiny(max_seq_len=256)
    params = init_llama_params(c)
    text = jax.jit(lambda p, t, n: prefill_forward(c, p, t, n)).lower(
        params, jnp.zeros((1, 256), jnp.int32), jnp.full((1,), 200, jnp.int32)
    ).as_text(debug_info=True)
    assert "flash/flash_prefill" in text or 'flash/' in text
    assert "flash_prefill" in text


# -- scopes are metadata, not calls --------------------------------------

_METADATA = re.compile(r",? ?metadata=\{[^{}]*\}")


def instructions(hlo_text):
    """The computations of an optimised HLO module, without what only names
    where an instruction came from: the ``metadata={...}`` of each and the
    tables of files and stack frames ahead of the first computation."""
    body = hlo_text[hlo_text.index("\n%"):] if "\n%" in hlo_text else hlo_text
    return _METADATA.sub("", body)


def optimised(engine):
    return {name: instructions(fn.lower(*args).compile().as_text())
            for name, fn, args in programs(engine)}


@pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
def test_scopes_change_no_instruction(run_async, monkeypatch, kernel):
    """The optimised HLO of each serving program with the scope and kernel
    names taken away (as the parent commit had it) is the program with them,
    instruction for instruction."""
    from jax.experimental import pallas as pl

    async def build():
        engine = tiny_engine(paged_kernel=kernel)
        try:
            return optimised(engine)
        finally:
            await engine.close()

    named = run_async(build())
    real_call = pl.pallas_call
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(
        pl, "pallas_call",
        lambda *a, name=None, **kw: real_call(*a, **kw))
    bare = run_async(build())
    assert set(named) == set(bare) == {"decode", "prefill", "prefill-continue"}
    for name in named:
        assert named[name] == bare[name], name
