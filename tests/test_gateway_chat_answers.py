"""The chat gateway's answers path: ONE reader an answers topic hands each
record to the sockets whose injected headers it carries (gateway/server.py
``_AnswersReader``), each socket sends from its own queue.

No engine and no agent: the test is the agent, it publishes records with the
sessions' headers straight to the answers topic (memory broker), so the
interleaving is the test's to choose. A socket is subscribed once its first
produce is acknowledged (the server subscribes it before it reads a client
frame), which is the only point a client can synchronise on."""

import asyncio
import re
import socket
import uuid

import aiohttp
import pytest

from langstream_tpu.api.metrics import render_metrics
from langstream_tpu.api.record import make_record
from langstream_tpu.core.parser import build_application_from_files
from langstream_tpu.gateway.server import (
    GatewayRegistry,
    GatewayServer,
    _AnswersReader,
)
from langstream_tpu.runtime.memory_broker import MemoryBroker
from langstream_tpu.serving.streaming import STREAMS

SESSION = "langstream-client-session-id"

GATEWAYS = """
gateways:
  - id: "chat"
    type: chat
    parameters: [sessionId]
    chat-options:
      questions-topic: "questions"
      answers-topic: "{answers}"
      headers:
        - key: "langstream-client-session-id"
          value-from-parameters: sessionId
  - id: "chat-user"
    type: chat
    parameters: [userId]
    chat-options:
      questions-topic: "questions"
      answers-topic: "{answers}"
      headers:
        - key: "user-id"
          value-from-parameters: userId
  - id: "chat-all"
    type: chat
    chat-options:
      questions-topic: "questions"
      answers-topic: "{answers}"
"""

PIPELINE = """
topics:
  - name: "questions"
    creation-mode: create-if-not-exists
  - name: "{answers}"
    creation-mode: create-if-not-exists
"""

INSTANCE = """
instance:
  streamingCluster:
    type: memory
    configuration:
      cluster: "{cluster}"
"""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def counter(name: str, topic: str) -> float:
    """A chat counter's value for one answers topic, off the scrape body."""
    m = re.search(
        rf'^langstream_gateway_{name}_total{{agent_id="{re.escape(topic)}"}} (\S+)$',
        render_metrics().decode(), re.M,
    )
    return float(m.group(1)) if m else 0.0


class Chat:
    """A gateway with the three chat gateways above on one answers topic of
    its own (the counters are the process's, by topic), and the agent's
    side of it: ``answer`` publishes a record to that topic."""

    async def __aenter__(self):
        self.cluster = f"chat-{uuid.uuid4().hex[:8]}"
        self.answers = f"answers-{uuid.uuid4().hex[:8]}"
        registry = GatewayRegistry()
        registry.register("t", "app", build_application_from_files(
            {
                "pipeline.yaml": PIPELINE.replace("{answers}", self.answers),
                "gateways.yaml": GATEWAYS.replace("{answers}", self.answers),
            },
            instance=INSTANCE.replace("{cluster}", self.cluster),
        ))
        self.gateway = GatewayServer(registry=registry, port=free_port())
        await self.gateway.start()
        self.session = aiohttp.ClientSession()
        self.broker = MemoryBroker.get(self.cluster)
        self.stopped = False
        return self

    async def __aexit__(self, *exc):
        await self.session.close()
        if not self.stopped:
            await self.gateway.stop()
        MemoryBroker.reset(self.cluster)

    async def open(self, gateway: str = "chat", **params):
        """A chat socket that the gateway has subscribed: its first produce
        is acknowledged."""
        query = "&".join(f"param:{k}={v}" for k, v in params.items())
        url = f"ws://127.0.0.1:{self.gateway.port}/v1/chat/t/app/{gateway}?{query}"
        ws = await self.session.ws_connect(url, max_msg_size=0)
        await ws.send_json({"value": "a question nobody consumes"})
        ack = await asyncio.wait_for(ws.receive_json(), 10)
        assert ack["status"] == "OK", ack
        return ws, ack

    async def answer(self, value, **headers) -> None:
        await self.broker.publish(
            self.answers, make_record(value=value, headers=headers)
        )

    def reader(self) -> _AnswersReader:
        (answers,) = self.gateway._answers.values()
        return answers

    def reads_a_frame(self) -> float:
        return (counter("chat_records_read", self.answers)
                / counter("chat_frames_sent", self.answers))


async def frames(ws, n: int, timeout: float = 10.0) -> list:
    return [
        (await asyncio.wait_for(ws.receive_json(), timeout))["record"]["value"]
        for _ in range(n)
    ]


async def silent(ws, seconds: float = 0.3) -> bool:
    try:
        await asyncio.wait_for(ws.receive(), seconds)
    except asyncio.TimeoutError:
        return True
    return False


async def thirty_two_sockets_each_their_own_frames_in_order():
    """(a) 32 sockets on one topic, every round one record a socket in turn:
    each gets exactly its own, in the topic's order, and the gateway reads
    about one record a frame sent (a reader a socket read 32)."""
    async with Chat() as chat:
        sockets = [(await chat.open(sessionId=f"s{i}"))[0] for i in range(32)]
        assert len(chat.gateway._answers) == 1
        for r in range(6):
            for i in range(32):
                await chat.answer([i, r], **{SESSION: f"s{i}"})
        got = await asyncio.gather(*(frames(ws, 6) for ws in sockets))
        for i, values in enumerate(got):
            assert values == [[i, r] for r in range(6)], (i, values)
        assert all(await asyncio.gather(*(silent(ws) for ws in sockets)))
        assert counter("chat_frames_sent", chat.answers) == 192
        assert chat.reads_a_frame() < 1.2
        for ws in sockets:
            await ws.close()


async def a_socket_joins_and_one_leaves_in_mid_burst():
    """(b) neither loses a frame nor is sent one that is not its own; the
    one that closed has its stream cancelled, its entry and its queue gone,
    and the last to leave takes the reader with it."""
    async with Chat() as chat:
        stays, _ = await chat.open(sessionId="stays")
        url = (f"ws://127.0.0.1:{chat.gateway.port}/v1/chat/t/app/chat"
               "?param:sessionId=leaves&option:streaming=true")
        leaves = await chat.session.ws_connect(url)
        await leaves.send_json({"value": "q"})
        ack = await asyncio.wait_for(leaves.receive_json(), 10)
        loop = asyncio.get_running_loop()
        stream = loop.create_future()
        STREAMS.register(ack["stream-id"], stream, loop)

        for n in range(10):
            for sid in ("stays", "leaves"):
                await chat.answer([sid, n], **{SESSION: sid})
        assert await frames(leaves, 4) == [["leaves", n] for n in range(4)]
        await leaves.close()  # with six of its frames still on their way
        for _ in range(100):
            if stream.cancelled():
                break
            await asyncio.sleep(0.02)
        assert stream.cancelled()
        by_values = chat.reader()._sockets[(SESSION,)]
        assert set(by_values) == {("stays",)}

        joins, _ = await chat.open(sessionId="joins")  # in mid-burst
        for n in range(10, 20):
            for sid in ("stays", "leaves", "joins"):
                await chat.answer([sid, n], **{SESSION: sid})
        assert await frames(stays, 20) == [["stays", n] for n in range(20)]
        assert await frames(joins, 10) == [["joins", n] for n in range(10, 20)]
        assert await silent(stays) and await silent(joins)

        reader = chat.reader()
        await stays.close()
        await joins.close()
        for _ in range(100):
            if not chat.gateway._answers:
                break
            await asyncio.sleep(0.02)
        assert not chat.gateway._answers and reader.task.done()


async def a_client_that_does_not_read_delays_nobody():
    """(c) the reader never awaits a socket's send: a socket whose client
    reads nothing fills its own queue, the others' frames pass."""
    async with Chat() as chat:
        slow, _ = await chat.open(sessionId="slow")
        fast, _ = await chat.open(sessionId="fast")
        big = "x" * (512 * 1024)
        for n in range(48):  # 24 MiB: more than the two ends' buffers hold
            await chat.answer([n, big], **{SESSION: "slow"})
        for n in range(10):
            await chat.answer(["fast", n], **{SESSION: "fast"})
        assert await frames(fast, 10, timeout=5) == [["fast", n] for n in range(10)]
        # the slow one IS held back, and alone: its send stands in mid-burst
        assert counter("chat_frames_sent", chat.answers) < 48 + 10
        # nothing of it was dropped: it reads late and reads everything
        assert [v[0] for v in await frames(slow, 48, timeout=30)] == list(range(48))
        await slow.close()
        await fast.close()


async def key_sets_of_two_gateways_and_a_socket_with_no_header():
    """(d) two gateways with different ``headers`` on one answers topic, a
    socket that injects nothing, two sockets with the same session: each
    sees what a reader of its own with today's filter saw."""
    async with Chat() as chat:
        s1, _ = await chat.open(sessionId="s1")
        s1_twin, _ = await chat.open(sessionId="s1")
        u1, _ = await chat.open("chat-user", userId="u1")
        everything, _ = await chat.open("chat-all")
        assert set(chat.reader()._sockets) == {(SESSION,), ("user-id",), ()}
        await chat.answer(1, **{SESSION: "s1"})
        await chat.answer(2, **{"user-id": "u1"})
        await chat.answer(3, **{SESSION: "s1", "user-id": "u1"})
        await chat.answer(4, **{SESSION: "s2"})
        await chat.answer(5)
        assert await frames(s1, 2) == [1, 3]
        assert await frames(s1_twin, 2) == [1, 3]
        assert await frames(u1, 2) == [2, 3]
        assert await frames(everything, 5) == [1, 2, 3, 4, 5]
        for ws in (s1, s1_twin, u1, everything):
            assert await silent(ws)
            await ws.close()
        # 5 records read, 11 frames sent
        assert chat.reads_a_frame() == pytest.approx(5 / 11)


async def the_reader_lives_while_a_socket_is_subscribed():
    """The first socket of a topic starts the reader at ``latest``, the
    last to leave drops it, the next socket gets a new one (again at
    ``latest``), and ``GatewayServer.stop`` stops what is left."""
    async with Chat() as chat:
        await chat.answer("before anyone", **{SESSION: "a"})
        a, _ = await chat.open(sessionId="a")
        first = chat.reader()
        await chat.answer("for a", **{SESSION: "a"})
        assert await frames(a, 1) == ["for a"]
        await a.close()
        for _ in range(100):
            if first.task.done():
                break
            await asyncio.sleep(0.02)
        assert first.task.done() and not chat.gateway._answers
        await chat.answer("between", **{SESSION: "a"})
        again, _ = await chat.open(sessionId="a")
        second = chat.reader()
        assert second is not first
        await chat.answer("for a again", **{SESSION: "a"})
        assert await frames(again, 1) == ["for a again"]
        await again.close()
        # a subscription that no handler is left to end (aiohttp's cleanup
        # waits a minute for an open socket's handler: not this test's)
        streaming = {"type": "memory",
                     "configuration": {"cluster": chat.cluster}}
        left = chat.gateway._answers_reader(streaming, chat.answers)
        assert left is chat.gateway._answers_reader(streaming, chat.answers)
        left.subscribe({})
        await left.ready.wait()
        await chat.gateway.stop()
        chat.stopped = True
        assert left.task.done() and left.closed and not chat.gateway._answers


CASES = [
    thirty_two_sockets_each_their_own_frames_in_order,
    a_socket_joins_and_one_leaves_in_mid_burst,
    a_client_that_does_not_read_delays_nobody,
    key_sets_of_two_gateways_and_a_socket_with_no_header,
    the_reader_lives_while_a_socket_is_subscribed,
]


@pytest.mark.parametrize("case", CASES, ids=lambda case: case.__name__)
def test_chat_answers_one_reader_a_topic(run_async, case):
    run_async(case())


class _NoReader:
    """A reader that never returns a record: the matching cases below hand
    ``_hand_out`` its records themselves."""

    async def start(self):
        pass

    async def read(self, timeout=None):
        await asyncio.sleep(3600)

    async def close(self):
        pass


class _NoRuntime:
    async def close(self):
        pass


# (what a socket injects, a record's headers, whether today's filter
# ``all(headers.get(k) == v for k, v in inject.items())`` passes it)
MATCHES = [
    ({"k": "v"}, {"k": "v", "other": 1}, True),
    ({"k": "v"}, {"k": "w"}, False),
    ({"k": "v"}, {}, False),
    ({}, {"k": "v"}, True),
    ({"k": "v", "tenant": "acme"}, {"tenant": "acme", "k": "v"}, True),
    ({"k": "v", "tenant": "acme"}, {"k": "v"}, False),
    ({"k": 1}, {"k": 1.0}, True),
    # a value no dictionary can key, on either side
    ({"groups": ["a", "b"]}, {"groups": ["a", "b"]}, True),
    ({"groups": ["a", "b"]}, {"groups": ["a"]}, False),
    ({"k": "v"}, {"k": ["v"]}, False),
    ({"k": frozenset({"v"})}, {"k": {"v"}}, True),
]


@pytest.mark.parametrize("inject, headers, passes", MATCHES)
def test_the_hand_out_matches_as_the_filter_did(run_async, inject, headers, passes):
    assert all(headers.get(k) == v for k, v in inject.items()) is passes

    async def main():
        answers = _AnswersReader(("{}", "unit-answers"), _NoRuntime(), _NoReader())
        try:
            socket_ = answers.subscribe(inject)
            other = answers.subscribe({"k": "nobody's"})
            record = make_record(value="r", headers=headers)
            answers._hand_out([record])
            assert socket_.queue.qsize() == (1 if passes else 0)
            assert other.queue.qsize() == 0
            answers.leave(socket_)
            answers.leave(other)
            assert answers.idle
        finally:
            await answers.stop()
        assert answers.closed

    run_async(main())
