"""A wave's prefill batches are dispatched one ahead (engine ``_admit``):
batch N+1 is packed and queued on the device before batch N's first tokens
are fetched. What has to hold, on the CPU at the tiny sizes: the served
tokens and logprobs are the serial order's (the wave's plan, a batch at a
time), the flight samples say which batches were ahead, nothing is in
flight when ``_admit`` returns, and a fault with a batch in flight leaves
what the serial order left."""

from __future__ import annotations

import asyncio
from collections import deque

import pytest

from langstream_tpu.serving.faults import FaultPlan

#: eight prompts whose byte lengths alternate over the buckets 32, 64 and
#: 128, so no two neighbours share a program. The wave's plan
#: (``scheduler.plan_wave``) groups them by bucket and cuts at powers of
#: two: requests (0, 3), (1, 5), (2, 4), 6, 7: five batches
_LENGTHS = (10, 40, 90, 20, 70, 35, 12, 100)
_ROWS = [2, 2, 2, 1, 1]
PROMPTS = [
    "".join(chr(97 + (i * 7 + j) % 26) for j in range(n))
    for i, n in enumerate(_LENGTHS)
]

CONFIGS = {
    "bf16-pool": dict(model="tiny"),
    "int8-pool": dict(model="tiny", kv_quantize="int8"),
    "hybrid": dict(model="hybrid-tiny"),
}


def _config(**kw):
    from langstream_tpu.serving.engine import ServingConfig

    d = dict(
        model="tiny", slots=8, max_seq_len=256, model_dtype="float32",
        kv_block_size=16, decode_chunk=4, prefix_cache=False,
        shrink_recovery_s=0.3,
    )
    d.update(kw)
    return ServingConfig(**d)


def _serial(engine) -> None:
    """The plan's order with nothing dispatched ahead: each batch of a
    wave is fetched and emitted before the next is dispatched."""

    async def admit(loop):
        plan = deque()
        try:
            while True:
                if not plan:
                    plan.extend(await engine._admit_select(loop))
                if not plan:
                    break
                handle = await engine._admit_dispatch(
                    loop, plan.popleft(), False
                )
                await engine._admit_complete(loop, *handle)
        finally:
            engine._admit_return(plan)  # what a failure left undispatched

    engine._admit = admit


def _ledger(engine) -> dict:
    """Count the two halves as they finish, and at every return of
    ``_admit`` what was dispatched and not yet fetched."""
    seen = {"dispatched": 0, "fetched": 0, "open_at_return": []}
    dispatch, fetch, admit = (
        engine._dispatch_prefill, engine._fetch_prefill, engine._admit
    )

    async def counted_dispatch(*a, **kw):
        out = await dispatch(*a, **kw)
        seen["dispatched"] += 1
        return out

    async def counted_fetch(*a, **kw):
        out = await fetch(*a, **kw)
        seen["fetched"] += 1
        return out

    async def counted_admit(loop):
        try:
            await admit(loop)
        finally:
            seen["open_at_return"].append(
                seen["dispatched"] - seen["fetched"]
            )

    engine._dispatch_prefill = counted_dispatch
    engine._fetch_prefill = counted_fetch
    engine._admit = counted_admit
    return seen


async def _wave(config, temperature, serial=False, prompts=PROMPTS):
    """One wave through a fresh engine: every request is queued before the
    loop's first admission pass. Returns per request its tokens and
    logprobs (or the error it raised), the prefill flight samples, the
    ledger and the engine's last state."""
    from langstream_tpu.serving.engine import TpuServingEngine

    engine = TpuServingEngine(config)
    if serial:
        _serial(engine)
    seen = _ledger(engine)
    try:
        outs = await asyncio.gather(
            *(
                engine.generate(
                    p, {"max-tokens": 6, "temperature": temperature}
                )
                for p in prompts
            ),
            return_exceptions=True,
        )
        return {
            "outs": [
                o if isinstance(o, Exception)
                else (o["tokens"], o["logprobs"])
                for o in outs
            ],
            "prefill": [
                s for s in engine.flight.recent(0) if s["phase"] == "prefill"
            ],
            "seen": seen,
            "share": engine.flight.summary()["totals"]["prefill_ahead_share"],
            "stats_share": engine.stats()["prefill_ahead_share"],
            "free": [s.free for s in engine.slots],
            "reserved": engine.block_mgr.reserved_blocks,
            "survival": engine.stats()["survival"],
        }
    finally:
        await engine.close()
        TpuServingEngine.reset_instances()


@pytest.mark.parametrize("temperature", [0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("name", list(CONFIGS))
def test_a_wave_ahead_serves_the_serial_orders_tokens(
    run_async, name, temperature
):
    """Five batches over three buckets: tokens and logprobs equal the serial
    order's, request for request (the keys are split in dispatch order, and
    a batch's program gets the arguments it always got)."""
    config = _config(**CONFIGS[name])
    ahead = run_async(_wave(config, temperature))
    serial = run_async(_wave(config, temperature, serial=True))
    assert not any(isinstance(o, Exception) for o in ahead["outs"])
    assert ahead["outs"] == serial["outs"]
    assert [s["program"] for s in ahead["prefill"]] == [
        s["program"] for s in serial["prefill"]
    ]
    assert [s["tokens"] for s in ahead["prefill"]] == _ROWS
    assert all(len(tokens) == 6 for tokens, _ in ahead["outs"])


@pytest.mark.parametrize("name", list(CONFIGS))
def test_the_flight_samples_say_which_batches_were_ahead(run_async, name):
    """The first batch of a wave finds nothing unfetched before it, every
    later one does; the rollup and ``stats()`` give the share; and at every
    return of ``_admit`` each dispatched batch has been fetched."""
    ahead = run_async(_wave(_config(**CONFIGS[name]), 0))
    flags = [s["ahead"] for s in ahead["prefill"]]
    assert flags == [0] + [1] * (len(_ROWS) - 1)
    assert ahead["share"] == ahead["stats_share"] == round(4 / 5, 4)
    seen = ahead["seen"]
    assert seen["dispatched"] == seen["fetched"] == len(_ROWS)
    assert seen["open_at_return"] and set(seen["open_at_return"]) == {0}
    # the serial order reads 0 throughout
    serial = run_async(_wave(_config(**CONFIGS[name]), 0, serial=True))
    assert [s["ahead"] for s in serial["prefill"]] == [0] * len(_ROWS)
    assert serial["share"] == 0.0


def test_a_lone_batch_is_not_ahead(run_async):
    """One request, one batch: nothing to be ahead of, and the share says
    so (None before any batch, as the recorder's property has it)."""
    from langstream_tpu.serving.flight import FlightRecorder

    assert FlightRecorder().prefill_ahead_share is None
    lone = run_async(_wave(_config(), 0, prompts=PROMPTS[:1]))
    assert [s["ahead"] for s in lone["prefill"]] == [0]
    assert lone["share"] == 0.0


@pytest.mark.parametrize("serial", [False, True], ids=["ahead", "serial"])
def test_a_device_error_with_a_batch_in_flight_fails_both(run_async, serial):
    """The second batch's dispatch raises a device error that is no
    allocator refusal while the first is unfetched: the first is completed
    (its first token emitted) before the error leaves ``_admit``, then every
    request fails, queued ones too, as in the serial order; no slot stays
    claimed, no block reserved, and what was dispatched was fetched."""
    faults = (
        FaultPlan(site="prefill", after=1, count=1, message="device lost"),
    )
    got = run_async(_wave(_config(faults=faults), 0, serial=serial))
    assert all(isinstance(o, Exception) for o in got["outs"])
    assert all("device lost" in str(o) for o in got["outs"])
    assert all(got["free"]) and got["reserved"] == 0
    seen = got["seen"]
    assert seen["dispatched"] == seen["fetched"] == 1
    assert set(seen["open_at_return"]) == {0}
    # the one batch that ran was recorded, and was ahead of nothing
    assert [s["ahead"] for s in got["prefill"]] == [0]


@pytest.mark.parametrize("serial", [False, True], ids=["ahead", "serial"])
def test_an_allocator_refusal_with_a_batch_in_flight_requeues_the_second(
    run_async, serial
):
    """The second batch's dispatch is refused memory while the first is
    unfetched: the first batch's requests keep their slots and decode on,
    the second's are swept back to the queue by the shrink pass and
    prefilled again, what the plan still held returns to the queue, and
    every request ends with the tokens of an undisturbed run."""
    base = run_async(_wave(_config(), 0))
    faults = (FaultPlan(site="prefill", shape="oom", after=1, count=1),)
    got = run_async(_wave(_config(faults=faults), 0, serial=serial))
    assert got["outs"] == base["outs"]
    assert got["survival"]["shrinks"] >= 1
    assert all(got["free"]) and got["reserved"] == 0
    seen = got["seen"]
    # the first batch, then (the refused one uncounted, its two requests
    # swept back, the rest of the plan returned) the six left, planned
    # again: (1, 5), (2, 4), 6, 7
    assert seen["dispatched"] == seen["fetched"] == 5
    assert set(seen["open_at_return"]) == {0}


def test_a_failed_completion_still_fetches_the_batch_ahead(run_async):
    """The other half: batch N's completion raises with batch N+1 already
    on the device. N+1 is fetched before the error leaves, so the ledger
    closes, and the loop then fails what is in the slots."""
    from langstream_tpu.serving.engine import TpuServingEngine

    async def main():
        engine = TpuServingEngine(_config())
        seen = _ledger(engine)
        complete, calls = engine._admit_complete, []

        async def failing_complete(loop, *handle):
            calls.append(handle[1]["dispatch"])
            await complete(loop, *handle)
            if len(calls) == 1:
                raise RuntimeError("emit failed")

        engine._admit_complete = failing_complete
        try:
            outs = await asyncio.gather(
                *(engine.generate(p, {"max-tokens": 4}) for p in PROMPTS[:3]),
                return_exceptions=True,
            )
            return outs, seen, calls, [s.free for s in engine.slots], (
                engine.block_mgr.reserved_blocks
            )
        finally:
            await engine.close()
            TpuServingEngine.reset_instances()

    outs, seen, calls, free, reserved = run_async(main())
    assert all(isinstance(o, RuntimeError) for o in outs)
    # the first batch's completion failed; the second, dispatched ahead,
    # was still completed; the third was never selected
    assert calls == [1, 2]
    assert seen["dispatched"] == seen["fetched"] == 2
    assert all(free) and reserved == 0


def test_the_prefix_cache_misses_what_the_batch_in_flight_will_publish(
    run_async,
):
    """docs/PREFIX.md: batch N registers its prefix after batch N+1 was
    matched, so within one wave the second request of a shared prompt
    prefills it whole, as two requests of one batch do; a later wave hits."""
    from langstream_tpu.serving.engine import TpuServingEngine

    shared = "s" * 48
    first, second = shared + "a" * 4, shared + "b" * 40  # buckets 64, 128

    async def main():
        engine = TpuServingEngine(_config(prefix_cache=True))
        try:
            opts = {"max-tokens": 3, "temperature": 0}
            wave = await asyncio.gather(
                engine.generate(first, opts), engine.generate(second, opts)
            )
            hits_in_wave = engine.prefix_hits
            again = await engine.generate(second, opts)
            return wave, again, hits_in_wave, engine.prefix_hits, [
                s["ahead"] for s in engine.flight.recent(0)
                if s["phase"] == "prefill"
            ]
        finally:
            await engine.close()
            TpuServingEngine.reset_instances()

    wave, again, hits_in_wave, hits, flags = run_async(main())
    assert flags == [0, 1, 0]
    assert hits_in_wave == 0 and hits == 1
    assert again["tokens"] == wave[1]["tokens"]
