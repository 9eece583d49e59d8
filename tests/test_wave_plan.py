"""A wave's prefill batches are formed by bucket (``scheduler.plan_wave``,
engine ``_admit_select``): admission looks over the requests it is about to
admit and fills each prefill program. First the plan alone, a pure function
of the candidates' buckets, then the engine at the tiny sizes on the CPU:
the wave is the serial order's, the served tokens are what every request
gets alone, the pool's refusal and the round's budget end a wave where they
ended it, and the QoS scheduler's class order is kept."""

from __future__ import annotations

import asyncio
import json
import os
import random

import pytest

from langstream_tpu.serving.qos import QosSpec
from langstream_tpu.serving.scheduler import (
    FifoScheduler,
    QosScheduler,
    plan_wave,
)

HERE = os.path.dirname(__file__)
BUCKETS = (64, 128, 256, 512, 1024)


def _bucket(n: int, lo: int = 32) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _fifo_runs(buckets, prefill_batch: int) -> list[list[int]]:
    """The batches before the plan: the queue's head and its neighbours
    while they fall in the head's bucket."""
    out, i = [], 0
    while i < len(buckets):
        j = i
        while (j < len(buckets) and buckets[j] == buckets[i]
               and j - i < prefill_batch):
            j += 1
        out.append(list(range(i, j)))
        i = j
    return out


def _drawn(seed: int, n: int) -> list[int]:
    rng = random.Random(seed)
    return [rng.choice(BUCKETS) for _ in range(n)]


# -- the plan alone ------------------------------------------------------


def test_the_plan_of_a_mixed_wave():
    buckets = [64, 128, 64, 64, 256, 64, 64, 128]
    assert plan_wave(buckets, 8, 8) == [[0, 2, 3, 5], [1, 7], [4], [6]]
    # the free slots are the wave: the eighth candidate waits
    assert plan_wave(buckets, 7, 8) == [[0, 2, 3, 5], [1], [4], [6]]
    assert plan_wave(buckets, 0, 8) == plan_wave([], 8, 8) == []


@pytest.mark.parametrize("rows, cut", [
    (1, [1]), (2, [2]), (3, [2, 1]), (5, [4, 1]), (7, [4, 2, 1]), (8, [8]),
    (9, [8, 1]), (21, [8, 8, 4, 1]),
])
def test_a_bucket_is_cut_into_powers_of_two_largest_first(rows, cut):
    plan = plan_wave([256] * rows, rows, 8)
    assert [len(b) for b in plan] == cut
    assert [i for b in plan for i in b] == list(range(rows))


@pytest.mark.parametrize("prefill_batch, cut", [
    (1, [1] * 7), (2, [2, 2, 2, 1]), (4, [4, 2, 1]), (6, [4, 2, 1]),
    (16, [4, 2, 1]),
])
def test_no_batch_has_more_rows_than_prefill_batch(prefill_batch, cut):
    """A ``prefill-batch`` that is no power of two caps at the one below."""
    assert [len(b) for b in plan_wave([64] * 7, 7, prefill_batch)] == cut


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("wave", [5, 14, 21, 28])
def test_every_candidate_once_the_head_first_and_no_padded_row(seed, wave):
    buckets = _drawn(seed, wave + 3)
    plan = plan_wave(buckets, wave, 8)
    assert sorted(i for b in plan for i in b) == list(range(wave))
    assert plan[0][0] == 0   # the scheduler's head is in the first batch
    for batch in plan:
        assert len({buckets[i] for i in batch}) == 1
        assert batch == sorted(batch)   # arrival order inside a bucket
        assert len(batch) in (1, 2, 4, 8)   # a program's rows are requests
    # batches go out by their oldest member
    assert [b[0] for b in plan] == sorted(b[0] for b in plan)
    # and a bucket's requests leave in arrival order over its batches
    for bucket in set(buckets[:wave]):
        members = [i for b in plan for i in b if buckets[i] == bucket]
        assert members == sorted(members)


@pytest.mark.parametrize("seed", range(4))
def test_prefill_batch_one_is_the_arrival_order(seed):
    buckets = _drawn(seed, 17)
    assert plan_wave(buckets, 17, 1) == [[i] for i in range(17)]


@pytest.mark.parametrize("rows", [1, 4, 8, 13])
def test_a_wave_in_one_bucket_is_the_arrival_order(rows):
    plan = plan_wave([512] * rows, rows, 8)
    assert [i for b in plan for i in b] == list(range(rows))
    if rows != 13:   # 8 + 4 + 1 where the queue's order gave 8 + 5 of 8
        assert plan == _fifo_runs([512] * rows, 8)


def _chat_sat_buckets() -> list[int]:
    import sys

    bench = os.path.join(HERE, "..", "bench")
    sys.path.insert(0, bench)
    try:
        from lib import traffic
    finally:
        sys.path.remove(bench)
    with open(os.path.join(bench, "traffic", "chat-sat.json")) as f:
        mix = json.load(f)
    return [_bucket(p) for p, _o in traffic.multiset(mix, int(mix["multiset"]))]


def test_the_chat_traffic_s_buckets():
    counts = {b: _chat_sat_buckets().count(b) for b in BUCKETS}
    assert counts == {64: 1, 128: 7, 256: 17, 512: 16, 1024: 7}


def _dispatches(seed: int, wave: int) -> tuple[int, int, int, int]:
    """The 48 prompts of ``bench/traffic/chat-sat.json`` in a seeded order,
    cycled as a closed loop cycles them and cut into waves: dispatches and
    rows x bucket computed, in the queue's order and planned."""
    order = _chat_sat_buckets()
    random.Random(seed).shuffle(order)
    stream = order * 7
    fifo = planned = tokens_fifo = tokens_plan = 0
    for k in range(0, len(stream) - wave + 1, wave):
        candidates = stream[k:k + wave]
        runs = _fifo_runs(candidates, 8)
        plan = plan_wave(candidates, wave, 8)
        fifo += len(runs)
        planned += len(plan)
        # the queue's order pads a batch's rows to a power of two
        tokens_fifo += sum(
            candidates[r[0]] * (1 << (len(r) - 1).bit_length()) for r in runs)
        tokens_plan += sum(candidates[b[0]] * len(b) for b in plan)
    return fifo, planned, tokens_fifo, tokens_plan


# The share of the queue order's dispatches that the plan needs, by the
# wave's length: over the 20 orders 0.58 / 0.46 / 0.39 (the issue's
# reckoning: 7.1 of 15.4 at 21), the worst single order 0.66 / 0.53 / 0.47.
# "At most half" holds from waves of 21 on in the mean: at 14 a bucket's
# group is often 3 or 5, and the cut without a padded row leaves 2 + 1.
SHARES = {14: (0.60, 0.67), 21: (0.50, 0.55), 28: (0.42, 0.47)}


@pytest.mark.parametrize("seed", range(20))
@pytest.mark.parametrize("wave", list(SHARES))
def test_the_chat_traffic_needs_about_half_the_dispatches(seed, wave):
    fifo, planned, tokens_fifo, tokens_plan = _dispatches(seed, wave)
    assert planned <= SHARES[wave][1] * fifo, (planned, fifo)
    assert tokens_plan <= tokens_fifo   # never more tokens than before


@pytest.mark.parametrize("wave", list(SHARES))
def test_the_chat_traffic_s_dispatches_over_twenty_orders(wave):
    totals = [_dispatches(seed, wave) for seed in range(20)]
    fifo, planned = (sum(t[i] for t in totals) for i in (0, 1))
    assert planned <= SHARES[wave][0] * fifo, (planned, fifo)
    rows = wave * sum(len(_chat_sat_buckets()) * 7 // wave for _ in totals)
    assert 1.25 <= rows / fifo <= 1.45     # what the ledger reads today
    assert rows / planned >= 2.2


def test_the_grouping_stays_inside_a_run_of_one_class():
    buckets = [64, 128, 64, 128, 64, 128]
    classes = ["interactive", "interactive", "batch", "batch",
               "interactive", "interactive"]
    assert plan_wave(buckets, 6, 8, classes) == [
        [0], [1], [2], [3], [4], [5]]
    classes = ["interactive"] * 4 + ["batch"] * 2
    assert plan_wave(buckets, 6, 8, classes) == [[0, 2], [1, 3], [4], [5]]
    # one run (no classes): three of a bucket are cut 2 + 1 all the same
    assert plan_wave(buckets, 6, 8) == [[0, 2], [1, 3], [4], [5]]
    assert plan_wave(buckets + [64, 128], 8, 8) == [[0, 2, 4, 6], [1, 3, 5, 7]]
    assert plan_wave(buckets + [64, 128], 8, 8, classes + ["batch"] * 2) == [
        [0, 2], [1, 3], [4, 6], [5, 7]]


@pytest.mark.parametrize("seed", range(6))
def test_no_lower_class_is_planned_ahead_of_a_higher_one(seed):
    """Whatever order the scheduler yields the classes in, a request is
    dispatched in its run: the runs' order is the scheduler's."""
    rng = random.Random(seed)
    classes = [rng.choice(["interactive", "default", "batch"])
               for _ in range(24)]
    buckets = _drawn(seed, 24)
    run_of, run = [], 0
    for i, c in enumerate(classes):
        run += i > 0 and c != classes[i - 1]
        run_of.append(run)
    plan = plan_wave(buckets, 24, 8, classes)
    runs = [run_of[i] for b in plan for i in b]
    assert runs == sorted(runs)
    assert all(len({run_of[i] for i in b}) == 1 for b in plan)


# -- the scheduler's one new method --------------------------------------


class _Req:
    def __init__(self, name, priority="default", preemptions=0):
        self.name, self.priority = name, priority
        self.preemptions = preemptions
        self.enqueue_time = 0.0


def test_fifo_gives_back_in_order_and_counts_once():
    sched = FifoScheduler()
    reqs = [_Req(i) for i in range(5)]
    for r in reqs:
        sched.submit(r)
    taken = [sched.pop() for _ in range(4)]
    assert sched.admitted == 4
    sched.give_back(taken[1:])   # the first was dispatched
    assert sched.admitted == 1 and sched.qsize() == 4
    assert [sched.pop().name for _ in range(4)] == [1, 2, 3, 4]
    assert sched.admitted == 5
    sched.give_back([])
    assert sched.admitted == 5 and sched.empty()


def test_qos_gives_back_to_each_class_s_front_with_its_credit():
    spec = QosSpec.from_dict({"classes": {
        "interactive": {"weight": 2}, "batch": {"weight": 1}}})
    sched = QosScheduler(spec, clock=lambda: 1.0)
    reqs = [_Req(f"i{k}", "interactive") for k in range(4)] + [
        _Req(f"b{k}", "batch", preemptions=k) for k in range(2)]
    for r in reqs:
        sched.submit(r)
    order = []
    while not sched.empty():
        order.append(sched.pop())
    names = [r.name for r in order]
    for r in order:
        sched.submit(r)   # the same queue again
    for key in ("admitted", "resumed"):
        for cls in ("interactive", "batch"):
            sched.counters[cls][key] = 0
    taken = [sched.pop() for _ in range(5)]
    assert [r.name for r in taken] == names[:5]
    sched.give_back(taken[2:])
    assert sched.counters["interactive"]["admitted"] + \
        sched.counters["batch"]["admitted"] == 2
    assert sched.counters["batch"]["resumed"] == 0
    # every class yields what it held, in its order
    rest = []
    while not sched.empty():
        rest.append(sched.pop().name)
    for cls in ("i", "b"):
        assert [n for n in names[:2] + rest if n[0] == cls] == [
            n for n in names if n[0] == cls]
    assert sum(c["admitted"] for c in sched.counters.values()) == 6


# -- the engine ----------------------------------------------------------

#: eight prompts over the buckets 32, 64 and 128, no two neighbours alike:
#: the queue's order gave eight batches of one row
_LENGTHS = (10, 40, 90, 20, 70, 35, 12, 100)
PROMPTS = [
    "".join(chr(97 + (i * 7 + j) % 26) for j in range(n))
    for i, n in enumerate(_LENGTHS)
]
OPTS = {"max-tokens": 6, "temperature": 0}


def _config(**kw):
    from langstream_tpu.serving.engine import ServingConfig

    d = dict(
        model="tiny", slots=8, max_seq_len=256, model_dtype="float32",
        kv_block_size=16, decode_chunk=4, prefix_cache=False,
    )
    d.update(kw)
    return ServingConfig(**d)


async def _serve(config, prompts=PROMPTS, opts=OPTS, before=None):
    """Every request queued before the loop's first admission pass. Returns
    the tokens, the prefill samples, what each return of ``_admit`` found
    (queued prompts in order, reserved blocks, slots taken), the engine's
    events and the scheduler's statistics."""
    from langstream_tpu.serving.engine import TpuServingEngine

    engine = TpuServingEngine(config)
    if before is not None:
        before(engine)
    admit, passes = engine._admit, []
    options = opts if isinstance(opts, list) else [opts] * len(prompts)
    tokens = [tuple(engine.tokenizer.encode(p)) for p in prompts]
    prompt_of = dict(zip(tokens, prompts))
    blocks_of = {   # a request's reservation: prompt + answer + 1 rows
        p: -(-(len(t) + o["max-tokens"] + 1) // config.kv_block_size)
        for p, t, o in zip(prompts, tokens, options)
    }

    async def watched(loop):
        try:
            await admit(loop)
        finally:
            queue = getattr(engine.scheduler, "_queue", None)
            passes.append({
                "queued": [prompt_of[tuple(r.prompt_tokens)]
                           for r in queue or ()],
                "reserved": engine.block_mgr.reserved_blocks,
                "taken": sum(not s.free for s in engine.slots),
                "admitted": engine.scheduler.stats()["admitted"],
                "blocks_of": blocks_of,
            })

    engine._admit = watched
    try:
        outs = await asyncio.wait_for(asyncio.gather(*(
            engine.generate(p, o) for p, o in zip(prompts, options))), 240)
        return {
            "tokens": [o["tokens"] for o in outs],
            "prefill": [s for s in engine.flight.recent(0)
                        if s["phase"] == "prefill"],
            "passes": passes,
            "events": engine.flight.recent_events(0),
            "stats": engine.stats(),
            "reserved": engine.block_mgr.reserved_blocks,
        }
    finally:
        await engine.close()
        TpuServingEngine.reset_instances()


@pytest.fixture(scope="module")
def alone():
    """What the queue's order served: with ``prefill-batch`` 1 the plan is
    the arrival order, one request a program, as the bucket rule left these
    eight prompts before."""
    return asyncio.run(_serve(_config(prefill_batch=1)))


def test_prefill_batch_one_dispatches_the_arrival_order(alone):
    """Eight programs of one row, their buckets in the prompts' order."""
    assert [s["tokens"] for s in alone["prefill"]] == [1] * 8
    programs = [s["program"] for s in alone["prefill"]]
    assert [programs.index(p) for p in programs] == [
        [32, 64, 128].index(_bucket(n + 1)) for n in _LENGTHS]
    first = alone["passes"][0]
    assert first["queued"] == [] and first["taken"] == 8


@pytest.mark.parametrize("name", ["tiny", "hybrid-tiny"])
def test_a_mixed_wave_is_admitted_whole_and_serves_the_same_tokens(
    run_async, alone, name
):
    """Eight requests over three buckets: all of them are in slots when the
    first ``_admit`` returns, as in the queue's order, in five programs and
    not eight, and every request's greedy tokens are what it gets alone."""
    got = run_async(_serve(_config(model=name)))
    base = alone if name == "tiny" else run_async(
        _serve(_config(model=name, prefill_batch=1)))
    assert [s["tokens"] for s in base["prefill"]] == [1] * 8
    assert got["tokens"] == base["tokens"]
    first = got["passes"][0]
    assert first["queued"] == [] and first["taken"] == 8
    assert first["admitted"] == 8
    assert [s["tokens"] for s in got["prefill"]] == [2, 2, 2, 1, 1]
    assert got["stats"]["prefill_rows_mean"] == 1.6
    assert base["stats"]["prefill_rows_mean"] == 1.0
    assert got["reserved"] == 0


def test_a_wave_stopped_by_the_pool_admits_nobody_past_the_blocked_request(
    run_async, alone
):
    """The fourth request waits for blocks until the first decode burst:
    the three before it are admitted, and none of the four behind it,
    though their slots are free and their buckets have room in the
    dispatched programs."""

    def before(engine):
        can_admit = engine.block_mgr.can_admit
        blocked = (len(engine.tokenizer.encode(PROMPTS[3]))
                   + OPTS["max-tokens"] + 1)

        def gated(tokens):
            if (tokens == blocked
                    and not engine.flight.steps_by_phase.get("decode")):
                return False
            return can_admit(tokens)

        engine.block_mgr.can_admit = gated

    got = run_async(_serve(_config(), before=before))
    first = got["passes"][0]
    assert first["queued"] == PROMPTS[3:]
    assert first["taken"] == 3 and first["admitted"] == 3
    assert first["reserved"] == sum(
        first["blocks_of"][p] for p in PROMPTS[:3])
    # buckets 32, 64, 128: three programs of one row, then the other five
    assert [s["tokens"] for s in got["prefill"]] == [1, 1, 1, 2, 2, 1]
    assert got["tokens"] == alone["tokens"]
    assert got["reserved"] == 0


def test_a_cut_round_returns_what_it_planned_to_the_queue_s_front(
    run_async, alone, monkeypatch
):
    """The budget is spent by the first batch completed: that one and the
    batch dispatched behind it are the round's prefills, and the three
    batches the plan still held go back, in arrival order, their
    reservations released and their admission uncounted."""
    from langstream_tpu.serving.engine import TpuServingEngine

    monkeypatch.setattr(TpuServingEngine, "_PREFILL_ROUND_S", 1e-9)
    got = run_async(_serve(_config()))
    first = got["passes"][0]
    # the plan: (0, 3), (1, 5), (2, 4), 6, 7
    assert first["queued"] == [PROMPTS[i] for i in (2, 4, 6, 7)]
    assert first["taken"] == 4 and first["admitted"] == 4
    assert first["reserved"] == sum(first["blocks_of"][PROMPTS[i]]
                                    for i in (0, 3, 1, 5))
    cuts = [e for e in got["events"] if e["kind"] == "admit-cut"]
    assert cuts and cuts[0]["returned"] == 4
    assert got["tokens"] == alone["tokens"]
    assert got["stats"]["scheduler"]["admitted"] == 8
    assert got["reserved"] == 0


def test_under_qos_no_lower_class_overtakes_a_higher_one(run_async):
    """Twelve requests of two classes over three buckets, queued before the
    first pass: the dispatches follow the order the scheduler popped the
    classes' runs in, and group only inside a run."""
    qos = QosSpec.from_dict({"classes": {
        "interactive": {"weight": 3}, "batch": {"weight": 1}}})
    prompts = [PROMPTS[i % 8] + "qos"[: i // 8] for i in range(12)]
    opts = [
        {**OPTS, "priority": "batch" if i % 3 == 2 else "interactive"}
        for i in range(12)
    ]
    popped, dispatched = [], []

    def before(engine):
        pop, dispatch = engine.scheduler.pop, engine._admit_dispatch

        def counted_pop():
            request = pop()
            popped.append(request)
            return request

        async def counted_dispatch(loop, batch, ahead):
            dispatched.append([request for _s, request, _r in batch])
            return await dispatch(loop, batch, ahead)

        engine.scheduler.pop = counted_pop
        engine._admit_dispatch = counted_dispatch

    got = run_async(_serve(_config(slots=12, qos=qos), prompts, opts, before))
    assert len(popped) == 12 and sum(map(len, dispatched)) == 12
    run_of, run = {}, 0
    for i, request in enumerate(popped):
        run += i > 0 and request.priority != popped[i - 1].priority
        run_of[id(request)] = run
    assert run >= 2   # the classes did alternate
    runs = [run_of[id(r)] for batch in dispatched for r in batch]
    assert runs == sorted(runs)
    assert all(len({r.priority for r in batch}) == 1 for batch in dispatched)
    assert len(dispatched) < 12   # and inside a run the buckets fill
    assert all(len(t) == 6 for t in got["tokens"])
    admitted = got["stats"]["scheduler"]["classes"]
    assert admitted["interactive"]["admitted"] == 8
    assert admitted["batch"]["admitted"] == 4
