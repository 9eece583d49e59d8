"""The load generator: a process of its own that never imports JAX.

    python3 bench/loadgen.py <plan.json> <results.json>

It talks to the gateway's chat WebSocket on localhost the way a chat client
does and keeps the clock: a generator on the engine's own event loop and GIL
would be timed by the thing it times. ``time.monotonic()`` is the machine's
CLOCK_MONOTONIC, so its timestamps compare with the serving process's.

It prints one JSON object a line on stdout as things happen
(``window_open``, ``window_close``, ``done``) and writes every request's
record to the results file: when it was due and sent (how late the generator
ran), first and last streamed frame, the frames, and the engine's own
account of the request from the headers of the final record
(``langstream-completion-tokens``, ``-ttft-ms``, ``-queue-wait-ms``,
``-prefill-ms``: agents/ai.py stamps them).
"""

from __future__ import annotations

import asyncio
import itertools
import json
import sys
import time

import aiohttp

CONNECT_LEAD_S = 0.75   # an open-loop socket opens this long before it is due
REQUEST_TIMEOUT_S = 120.0
DRAIN_CAP_S = 90.0      # a closed loop follows what streams at the close this long


def say(**event) -> None:
    print(json.dumps(event), flush=True)


class Generator:
    def __init__(self, plan: dict):
        self.plan = plan
        self.records: list[dict] = []
        self.nonce = itertools.count()
        self.closed = False                       # a closed loop's window is over
        self.current: dict[asyncio.Task, dict] = {}   # the request each task is on

    def url(self, request: dict) -> str:
        p = self.plan
        return (
            f"{p['ws_base']}/v1/chat/{p['tenant']}/{p['app']}/"
            f"chat-{request['output_tokens']}"
            f"?param:sessionId=s{request['id']}-{next(self.nonce)}"
        )

    async def one(self, session, request: dict, due: float | None) -> dict:
        """One request over one socket. ``due`` (absolute monotonic) is when
        an open loop wants it sent; a closed loop sends as soon as the socket
        is open."""
        rec = {
            "id": request["id"], "measured": request.get("measured", True),
            "prompt_tokens": request["prompt_tokens"],
            "output_tokens": request["output_tokens"],
            "due": due, "frames": 0, "error": None,
        }
        self.records.append(rec)
        self.current[asyncio.current_task()] = rec
        try:
            if due is not None:
                await asyncio.sleep(max(0.0, due - CONNECT_LEAD_S - time.monotonic()))
            async with session.ws_connect(self.url(request), heartbeat=None) as chat:
                if due is not None:
                    await asyncio.sleep(max(0.0, due - time.monotonic()))
                rec["sent"] = time.monotonic()
                await chat.send_json({"value": request["content"]})
                deadline = rec["sent"] + REQUEST_TIMEOUT_S
                while True:
                    msg = await chat.receive(timeout=max(0.1, deadline - time.monotonic()))
                    now = time.monotonic()
                    if msg.type != aiohttp.WSMsgType.TEXT:
                        raise RuntimeError(f"socket gave {msg.type!r}: {msg.data!r}")
                    body = json.loads(msg.data)
                    record = body.get("record")
                    if record is None:
                        if body.get("status") not in (None, "OK"):
                            raise RuntimeError(f"gateway said {body}")
                        continue  # the produce ack
                    headers = record.get("headers") or {}
                    if "langstream-completion-tokens" in headers:
                        # the agent's final record: the engine's account
                        rec["done"] = now
                        rec["tokens"] = int(headers["langstream-completion-tokens"])
                        rec["engine_prompt_tokens"] = int(
                            headers.get("langstream-prompt-tokens", 0)
                        )
                        for key, name in (
                            ("langstream-ttft-ms", "engine_ttft_ms"),
                            ("langstream-queue-wait-ms", "queue_wait_ms"),
                            ("langstream-prefill-ms", "prefill_ms"),
                        ):
                            if key in headers:
                                rec[name] = float(headers[key])
                        return rec
                    rec["frames"] += 1
                    rec.setdefault("first", now)
                    rec["last"] = now
                    if str(headers.get("stream-last-message")).lower() == "true":
                        rec["stream_closed"] = True
        except asyncio.CancelledError:
            rec["error"] = rec["error"] or "cut at the end of the window"
            rec["cut"] = True
            raise
        except Exception as e:  # a failed request is data, not a crash
            rec["error"] = f"{type(e).__name__}: {e}"
            return rec

    async def open_loop(self, session) -> dict:
        plan = self.plan
        t0 = time.monotonic() + plan["lead_in_s"] + CONNECT_LEAD_S + 0.5
        tasks = [
            asyncio.ensure_future(self.one(session, r, t0 + r["due_s"]))
            for r in plan["requests"]
        ]
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        say(event="window_open", t=t0)
        await asyncio.sleep(max(0.0, t0 + plan["seconds"] - time.monotonic()))
        say(event="window_close", t=t0 + plan["seconds"])
        await asyncio.wait(tasks, timeout=REQUEST_TIMEOUT_S)
        for task in tasks:
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        return {"open": t0, "close": t0 + plan["seconds"]}

    async def closed_loop(self, session) -> dict:
        plan = self.plan

        def passes():
            """The multiset again and again in the seed's order; from the
            second pass on each prompt's bytes are rotated, so that a prompt
            never repeats and the prefix cache is not what is measured."""
            for k in itertools.count():
                for request in plan["requests"]:
                    text = request["content"]
                    cut = k % len(text)
                    yield {**request, "id": request["id"] + k * len(plan["requests"]),
                           "content": text[cut:] + text[:cut]}

        feed = passes()
        completed = 0
        turnover = asyncio.Event()

        async def client() -> None:
            nonlocal completed
            while not self.closed:
                rec = await self.one(session, next(feed), None)
                completed += 1
                if completed >= plan["clients"]:
                    turnover.set()
                if rec["error"]:
                    await asyncio.sleep(0.05)  # never spin on a dead gateway

        tasks = [asyncio.ensure_future(client()) for _ in range(plan["clients"])]
        # the window opens once the batch has been full for one whole
        # turnover of requests: as many completions as there are clients
        await asyncio.wait_for(turnover.wait(), plan["ramp_timeout_s"])
        t_open = time.monotonic()
        say(event="window_open", t=t_open)
        await asyncio.sleep(plan["seconds"])
        t_close = time.monotonic()
        say(event="window_close", t=t_close)
        # Nothing new is sent after the close. A request that is streaming
        # across it is followed to its end, so that the tokens it made inside
        # the window can be counted; one that has no frame yet made none.
        self.closed = True
        for task, rec in list(self.current.items()):
            if "first" not in rec:
                task.cancel()
        await asyncio.wait(tasks, timeout=DRAIN_CAP_S)
        for task in tasks:
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        return {"open": t_open, "close": t_close}

    async def run(self) -> dict:
        connector = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=connector) as session:
            loop = self.open_loop if self.plan["loop"] == "open" else self.closed_loop
            window = await loop(session)
        return {"window": window, "requests": self.records}


def main(argv: list[str]) -> int:
    plan_path, out_path = argv[1], argv[2]
    with open(plan_path) as f:
        plan = json.load(f)
    if "jax" in sys.modules:
        raise RuntimeError("the load generator must not import JAX")
    result = asyncio.run(Generator(plan).run())
    with open(out_path, "w") as f:
        json.dump(result, f)
    say(event="done", requests=len(result["requests"]))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
