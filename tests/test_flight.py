"""Engine flight recorder tests.

Layers covered: the recorder ring (bounded size, drop accounting, rollup
math), the engine integration on the CPU backend under concurrent load
(the acceptance decomposition: device + host + stall sums to the measured
wall clock), recompile-event detection via a fake compile-cache miss, the
pod ``/flight`` endpoints, the control-plane fan-in over the memory broker
(mirroring ``test_tracing.py``'s e2e shape), the k8s fan-in pod tagging,
and the ``engine_top --analyze`` post-mortem on a canned dump."""

import asyncio
import importlib.util
import json
import re
import socket
import time
from pathlib import Path

import aiohttp
import pytest

from langstream_tpu.serving.flight import FlightRecorder, bench_rollup


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


async def _close_engines():
    from langstream_tpu.serving.engine import TpuServingEngine

    with TpuServingEngine._instances_lock:
        engines = list(TpuServingEngine._instances.values())
    for engine in engines:
        await engine.close()


def _load_engine_top():
    path = Path(__file__).resolve().parents[1] / "tools" / "engine_top.py"
    spec = importlib.util.spec_from_file_location("engine_top", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# --------------------------------------------------------------------------
# recorder units: bounded ring, drop accounting, rollup math
# --------------------------------------------------------------------------


def test_ring_is_bounded_and_counts_drops():
    recorder = FlightRecorder(slots=4, maxlen=8)
    for _ in range(20):
        recorder.sample("decode", device_s=0.001, tokens=4)
    assert len(recorder.recent(0)) == 8
    assert recorder.recorded == 20
    assert recorder.dropped == 12
    # cumulative totals survive eviction
    assert recorder.tokens == 80
    assert recorder.steps_by_phase == {"decode": 20}


def test_no_drops_below_capacity():
    recorder = FlightRecorder(slots=4, maxlen=64)
    for _ in range(63):
        recorder.sample("decode")
    assert recorder.dropped == 0
    summary = recorder.summary()
    assert summary["dropped"] == 0
    assert summary["recorded"] == 63


def test_buffer_size_env(monkeypatch):
    monkeypatch.setenv("LS_TPU_FLIGHT_BUFFER", "100")
    assert FlightRecorder().capacity == 100
    monkeypatch.setenv("LS_TPU_FLIGHT_BUFFER", "3")  # clamped to the floor
    assert FlightRecorder().capacity == 64
    monkeypatch.setenv("LS_TPU_FLIGHT_BUFFER", "junk")
    assert FlightRecorder().capacity == 4096


def test_rollup_decomposition_is_exact():
    """wall == device + host per dispatch sample, and the totals tile the
    timeline: dispatch walls + stall walls == total wall."""
    recorder = FlightRecorder(slots=2, maxlen=32)
    time.sleep(0.02)
    recorder.sample("prefill", device_s=0.005, tokens=2)
    time.sleep(0.03)
    recorder.sample("decode", device_s=0.01, tokens=16, stall="no-free-slot")
    time.sleep(0.01)
    recorder.stall("queue-empty")
    totals = recorder.summary()["totals"]
    # each total is independently rounded to 3 decimals for JSON, so the
    # identity holds to rounding precision
    assert totals["wall_ms"] == pytest.approx(
        totals["device_ms"] + totals["host_ms"] + totals["stall_ms"], abs=0.01
    )
    assert totals["tokens"] == 18
    assert totals["steps_by_phase"] == {"prefill": 1, "decode": 1}
    # two disjoint attributions: idle gaps are STALL (decompose stall_ms),
    # annotated busy dispatches are BLOCKED (queue pressure while decoding)
    assert set(totals["stall_s_by_reason"]) == {"queue-empty"}
    assert set(totals["blocked_s_by_reason"]) == {"no-free-slot"}
    assert totals["blocked_s_by_reason"]["no-free-slot"] >= 0.03
    # the dict rounds to 4 decimals of seconds (0.1 ms steps), stall_ms to
    # 3 decimals of ms — equal up to half a rounding step
    assert sum(totals["stall_s_by_reason"].values()) * 1000 == pytest.approx(
        totals["stall_ms"], abs=0.06
    )


def test_device_time_clamped_to_wall():
    """A device_s overestimate (overlapped pipelined fetch) must not drive
    host_ms negative."""
    recorder = FlightRecorder(slots=1, maxlen=8)
    sample = recorder.sample("decode", device_s=999.0)
    assert sample["device_ms"] <= sample["wall_ms"]
    assert sample["host_ms"] >= 0.0


def test_events_ring_and_counters():
    recorder = FlightRecorder(slots=1, maxlen=8)
    recorder.event("recompile", what="decode", variant="w128")
    recorder.event("pool-grow", slots=3)
    recorder.event("warmup", stage="begin")
    assert recorder.recompiles == 1
    assert recorder.events_by_type == {
        "recompile": 1, "pool-grow": 1, "warmup": 1,
    }
    kinds = [e["kind"] for e in recorder.recent_events()]
    assert kinds == ["recompile", "pool-grow", "warmup"]


def test_a_span_without_a_profiler_session_leaves_the_recorder_untouched():
    """The span helper is a profiler annotation and nothing else: with no
    session it records nothing, and it never touches the recorder's rings,
    counters or timeline boundary."""
    from langstream_tpu.serving.flight import SPANS

    recorder = FlightRecorder(slots=4)
    recorder.sample("decode", device_s=0.001, tokens=4)
    before = {k: (list(v) if hasattr(v, "append") else
                  dict(v) if isinstance(v, dict) else v)
              for k, v in vars(recorder).items()}
    for name in SPANS:
        with recorder.span(name, seq=7, program="decode:w128:k4:greedy"):
            pass
    after = {k: (list(v) if hasattr(v, "append") else
                 dict(v) if isinstance(v, dict) else v)
             for k, v in vars(recorder).items()}
    assert after == before
    assert recorder.recorded == 1 and len(recorder.recent(0)) == 1


@pytest.mark.parametrize("fields, expected", [
    (dict(dispatch=9, steps=32, active_at_dispatch=61),
     {"dispatch": 9, "steps": 32, "active_at_dispatch": 61}),
    (dict(dispatch=10, active_at_dispatch=3),           # a prefill: no steps
     {"dispatch": 10, "steps": 0, "active_at_dispatch": 3}),
    (dict(dispatch=11, steps=32, active_at_dispatch=97,  # a paged decode
          live_rows=44_100),
     {"dispatch": 11, "steps": 32, "active_at_dispatch": 97,
      "live_rows": 44_100}),
    (dict(), {}),                       # no dispatch named: schema unchanged
])
def test_sample_carries_what_the_dispatch_knew(fields, expected):
    recorder = FlightRecorder(slots=64)
    entry = recorder.sample("decode", device_s=0.001, occupancy=58, **fields)
    carried = ("dispatch", "steps", "active_at_dispatch", "live_rows")
    assert {k: entry[k] for k in carried if k in entry} == expected
    assert entry["occupancy"] == 58     # stays what it was for its readers


def test_bench_rollup_carries_the_record_keys():
    recorder = FlightRecorder(slots=2, maxlen=32)
    recorder.sample("decode", device_s=0.001, tokens=8, stall="no-kv-blocks")
    recorder.event("recompile", what="decode")
    rollup = bench_rollup(recorder.summary())
    assert set(rollup) == {
        "host_overhead_ms_p50", "host_exposed_ms_p50", "overlap_ratio",
        "step_ms_p50", "stall_s_by_reason", "blocked_s_by_reason",
        "queue_depth_p95", "recompile_count", "totals",
    }
    assert rollup["recompile_count"] == 1
    # the annotated dispatch sample is queue pressure, not engine stall
    assert "no-kv-blocks" in rollup["blocked_s_by_reason"]
    assert rollup["stall_s_by_reason"] == {}
    assert set(rollup["totals"]) == {
        "wall_ms", "device_ms", "host_ms", "host_overlapped_ms", "stall_ms",
        "tokens", "steps_by_phase",
    }
    # rollups must be JSON-clean for the bench record line
    json.dumps(rollup)


# --------------------------------------------------------------------------
# engine integration (CPU backend): the acceptance decomposition
# --------------------------------------------------------------------------
# the device's clock: gap_ms, program_ms, seen_by, resume_lag_ms (PR 36, 52)
# --------------------------------------------------------------------------


class _Ticks:
    """A clock that reads what it is told, for ``flight.time`` or a
    ``DispatchClock``."""

    def __init__(self, ticks):
        self.ticks = list(ticks)

    def monotonic(self):
        return self.ticks.pop(0)

    __call__ = monotonic

    @staticmethod
    def time():
        return 1_700_000_000.0


def _tiles(times):
    """Sum of gap_ms + program_ms of the programs seen complete."""
    return sum(t["gap_ms"] + t["program_ms"] for t in times if "done_t" in t)


def _play(clock, script, times, waited=None):
    """A script of (op, program[, t]) against a clock WITHOUT its thread:
    ``enq`` / ``settle`` / ``rdy`` are the dispatch thread's, ``watch`` is
    what the watcher does after its wait, ``stamp`` writes a watcher's stamp
    read at ``t`` (one read before a stamp that is already there)."""
    for op, i, *t in script:
        if op == "enq":
            clock.enqueued(times[i], handle=i)
        elif op == "settle":
            clock.settle(times[i], waited.append)
        elif op == "watch":
            clock.seen(times[i])
        elif op == "stamp":
            times[i].setdefault("seen", (t[0], "watch"))
        else:
            clock.ready(times[i])


@pytest.mark.parametrize("script, expected, seen_by", [
    # (op, program) in the order the stamps are taken, the clock a second a
    # call. In order, fetched at once: a gap wherever the next was enqueued
    # late.
    ([("enq", 0), ("rdy", 0), ("enq", 1), ("rdy", 1)],
     [(0.0, 1000.0), (1000.0, 1000.0)], "ff"),
    # one ahead: 1 was enqueued while 0 ran, so no gap, and 1 starts at 0's end
    ([("enq", 0), ("enq", 1), ("rdy", 0), ("rdy", 1)],
     [(0.0, 2000.0), (0.0, 1000.0)], "ff"),
    # a completion NOBODY saw until later: 0 (a pending decode chunk) is
    # waited for only after its successor 1 was: 0 ends where 1's wait ended,
    # 1 gets what is left (nothing), nothing is counted twice, and 2 starts
    # from there
    ([("enq", 0), ("enq", 1), ("rdy", 1), ("rdy", 0), ("enq", 2), ("rdy", 2)],
     [(0.0, 2000.0), (0.0, 0.0), (1000.0, 1000.0)], "fff"),
    # ... unless it is settled first: 1's wait sees 0 complete on the way
    ([("enq", 0), ("enq", 1), ("settle", 1), ("rdy", 1), ("rdy", 0)],
     [(0.0, 2000.0), (0.0, 1000.0)], "ff"),
    # one ahead, and the device ran dry before 2's dispatch: the WATCHER was
    # waiting when 1 ended, so 1 ends there whenever its fetch comes, and
    # the device's wait from 1's end to 2's dispatch is 2's gap
    ([("enq", 0), ("enq", 1), ("rdy", 0), ("watch", 1), ("enq", 2),
      ("rdy", 1), ("rdy", 2)],
     [(0.0, 2000.0), (0.0, 1000.0), (1000.0, 2000.0)], "fwf"),
    # the dispatch thread saw it first: the watcher's later stamp changes
    # nothing
    ([("enq", 0), ("rdy", 0), ("watch", 0), ("enq", 1), ("rdy", 1)],
     [(0.0, 1000.0), (2000.0, 1000.0)], "ff"),
    # the pending chunk again, now watched: it ends at the watcher's stamp
    # and its successor gets its own time without a settle
    ([("enq", 0), ("enq", 1), ("watch", 0), ("rdy", 1), ("rdy", 0)],
     [(0.0, 2000.0), (0.0, 1000.0)], "wf"),
    # a stamp read before a later one and written after it (the watcher lost
    # the GIL between its clock read and its store... of the predecessor):
    # 1's earlier stamp is moved up to 0's, so no program's time is negative
    ([("enq", 0), ("enq", 1), ("stamp", 0, 105.0), ("rdy", 1)],
     [(0.0, 5000.0), (0.0, 0.0)], "wf"),
], ids=["in-order", "one-ahead", "seen-late", "settled",
        "watched-before-the-next-dispatch", "fetch-first", "watched-pending",
        "stamp-written-late"])
def test_the_dispatch_clock_tiles_the_device_s_time(script, expected, seen_by):
    from langstream_tpu.serving.flight import DispatchClock

    clock = DispatchClock(clock=_Ticks(range(100, 200)))
    times = [{} for _ in expected]
    waited = []
    _play(clock, script, times, waited)
    got = [(t["gap_ms"], t["program_ms"]) for t in times]
    assert got == [pytest.approx(e) for e in expected]
    assert "".join(t["seen_by"][0] for t in times) == seen_by
    # no double count, no hole: the fields tile first enqueue to last done
    assert _tiles(times) == pytest.approx(
        (clock.last_done_t - clock.first_enqueued_t) * 1e3)
    assert waited == ([0] if ("settle", 1) in script else [])
    assert not clock._open and clock._watcher is None   # no thread was asked for


#: the dispatch thread's stamps of three programs, the second dispatched one
#: ahead and fetched after the third's dispatch; the watcher's three stamps
#: fall anywhere after each program's enqueue, in the device's order
_DISPATCH_THREAD = [("enq", 0), ("enq", 1), ("rdy", 0), ("enq", 2), ("rdy", 1),
                    ("rdy", 2)]


def _interleavings():
    import itertools

    n = len(_DISPATCH_THREAD) + 3
    for at in itertools.combinations(range(n), 3):
        script, thread, watched = [], iter(_DISPATCH_THREAD), iter(range(3))
        for k in range(n):
            script.append(("watch", next(watched)) if k in at else next(thread))
        if all(script.index(("watch", i)) > script.index(("enq", i))
               for i in range(3)):
            yield script


@pytest.mark.parametrize(
    "script", list(_interleavings()),
    ids=lambda s: "".join("w" if op == "watch" else "d" for op, _ in s))
def test_the_two_observers_tile_the_device_s_time_however_they_interleave(script):
    from langstream_tpu.serving.flight import DispatchClock

    clock = DispatchClock(clock=_Ticks(range(100, 200)))
    times = [{}, {}, {}]
    _play(clock, script, times)
    assert _tiles(times) == pytest.approx(
        (clock.last_done_t - clock.first_enqueued_t) * 1e3)
    done = [t["done_t"] for t in times]
    assert done == sorted(done)
    for i, t in enumerate(times):
        assert t["gap_ms"] >= 0 and t["program_ms"] >= 0
        # the first stamp stands: the watcher's, unless a fetch of this
        # program or of a later one came before it
        first = min(script.index(("watch", i)),
                    *(script.index(("rdy", j)) for j in range(i, 3)))
        assert t["seen_by"] == ("watch" if script[first][0] == "watch"
                                else "fetch")
        assert t["done_t"] == 100 + first or t["done_t"] == done[i - 1]


@pytest.mark.parametrize("times", [{}, {"enqueued_t": 1.0, "done_t": 2.0}],
                         ids=["never-enqueued", "already-seen"])
def test_the_dispatch_clock_ignores_what_it_did_not_enqueue(times):
    from langstream_tpu.serving.flight import DispatchClock

    clock = DispatchClock(clock=_Ticks(range(10)))
    other: dict = {}
    clock.enqueued(other)
    before = dict(times)
    clock.settle(times, lambda handle: 1 / 0)
    clock.ready(times)
    assert times == before and len(clock._open) == 1


class _Hand:
    """A clock a test moves by hand, and the fake handles' blocking call."""

    def __init__(self):
        self.now = 0.0
        self.failed = []

    def __call__(self):
        return self.now

    def wait(self, handle):
        import threading

        assert handle.wait(10.0)
        if getattr(handle, "fails", False):
            self.failed.append(threading.current_thread().name)
            raise RuntimeError("the program failed")

    @staticmethod
    def stamped(times, timeout=10.0):
        """Wait for the watcher's stamp of ``times`` (its thread is real)."""
        deadline = time.monotonic() + timeout
        while "seen" not in times and time.monotonic() < deadline:
            time.sleep(0.001)
        return times.get("seen")


def _watchers():
    import threading

    return [t for t in threading.enumerate() if t.name == "tpu-engine-watch"]


def _watched_clock():
    import threading

    from langstream_tpu.serving.flight import DispatchClock

    hand = _Hand()
    return hand, DispatchClock(clock=hand, watch=hand.wait), threading.Event


def test_a_batch_that_ends_under_its_successor_s_dispatch_is_the_watcher_s():
    """0 runs 0-2 s; 1 (one ahead) runs 2-3 s and ends while the dispatch
    thread packs 2, enqueued at 5 s; 1's fetch comes at 6 s. The watcher was
    waiting: 1 ends at 3 s and the device's 2 s without work are 2's gap."""
    hand, clock, handle = _watched_clock()
    before = len(_watchers())
    try:
        times, hs = [{}, {}, {}], [handle(), handle(), handle()]
        clock.enqueued(times[0], hs[0], seq=7)
        assert len(_watchers()) == before + 1   # started at the first enqueue
        hand.now = 1.0
        clock.enqueued(times[1], hs[1], seq=8)
        hand.now = 2.0
        hs[0].set()
        assert hand.stamped(times[0]) == (2.0, "watch")
        clock.ready(times[0])
        hand.now = 3.0
        hs[1].set()
        assert hand.stamped(times[1]) == (3.0, "watch")
        hand.now = 5.0
        clock.enqueued(times[2], hs[2], seq=9)
        hand.now = 6.0
        clock.ready(times[1])
        hand.now = 8.0
        clock.ready(times[2])            # the fetch saw 2 first
        hs[2].set()
        assert [(t["gap_ms"], t["program_ms"], t["seen_by"]) for t in times] == [
            (0.0, 2000.0, "watch"), (0.0, 1000.0, "watch"),
            (2000.0, 3000.0, "fetch")]
        assert _tiles(times) == pytest.approx(8000.0)
        assert len(_watchers()) == before + 1   # one thread, not one a program
    finally:
        clock.close()
    assert len(_watchers()) == before


def test_the_watcher_s_later_stamp_changes_nothing():
    hand, clock, handle = _watched_clock()
    try:
        first, second = {}, {}
        h1, h2 = handle(), handle()
        clock.enqueued(first, h1)
        hand.now = 1.0
        clock.ready(first)               # the dispatch thread saw it first
        wrote = dict(first)
        hand.now = 4.0
        h1.set()
        clock.enqueued(second, h2)       # the watcher reaches it after h1
        h2.set()
        assert hand.stamped(second) == (4.0, "watch")
        assert first == wrote and first["seen_by"] == "fetch"
        assert first["seen"] == (1.0, "fetch")
    finally:
        clock.close()


def test_a_failed_program_is_stamped_not_raised_and_the_watcher_lives():
    hand, clock, handle = _watched_clock()
    try:
        bad, good = handle(), handle()
        bad.fails = True
        failed, after = {}, {}
        clock.enqueued(failed, bad)
        hand.now = 1.5
        bad.set()
        assert hand.stamped(failed) == (1.5, "watch")
        assert hand.failed == ["tpu-engine-watch"]
        with pytest.raises(RuntimeError):    # raised once, where the fetch waits
            hand.wait(bad)
        clock.ready(failed)
        assert failed["seen_by"] == "watch" and failed["program_ms"] == 1500.0
        hand.now = 2.0
        clock.enqueued(after, good)
        hand.now = 2.5
        good.set()
        assert hand.stamped(after) == (2.5, "watch")
        assert clock._watcher.is_alive()
    finally:
        clock.close()


def test_close_ends_the_watcher_and_a_dropped_clock_s_ends_too():
    import gc

    hand, clock, handle = _watched_clock()
    before = len(_watchers())
    h = handle()
    h.set()
    times = {}
    clock.enqueued(times, h)
    assert hand.stamped(times)
    watcher = clock._watcher
    clock.close()
    assert not watcher.is_alive() and len(_watchers()) == before
    later = {}
    clock.enqueued(later, h)             # closed: the dispatch thread alone
    clock.ready(later)
    assert later["seen_by"] == "fetch" and len(_watchers()) == before
    # an engine nobody closed: its clock's thread ends with the clock
    hand, dropped, handle = _watched_clock()
    dropped.enqueued({}, h)
    watcher = dropped._watcher
    del dropped
    gc.collect()
    watcher.join(5.0)
    assert not watcher.is_alive()


#: (phase, device_s, overlapped_s, tokens, ahead) of a recorded sequence, the
#: clock's reading at each record, and what the PARENT's recorder (commit
#: 5171d91, before the clock fields) wrote for it: wall, device, host,
#: overlapped of each sample, and the rollup's totals
_RECORDED = [
    ("prefill", 0.0312, 0.0, 3, 0), ("prefill", 0.0047, 0.0, 2, 1),
    ("decode", 0.4181, 0.0, 96, None), ("decode", 0.0009, 0.2875, 128, None),
    ("stall", None, None, 0, None), ("prefill", 3.4125, 0.0, 1, 0),
    ("decode", 999.0, 0.5, 64, None),
]
_RECORDED_TICKS = [100.0, 100.0413, 100.0602, 100.5127, 101.0391, 101.0519,
                   104.4711, 104.9999]
_PARENT_WROTE = [[41.3, 31.2, 10.1, 0.0], [18.9, 4.7, 14.2, 0.0],
                 [452.5, 418.1, 34.4, 0.0], [526.4, 288.4, 238.0, 287.5],
                 [12.8, 0.0, 0.0, 0.0], [3419.2, 3412.5, 6.7, 0.0],
                 [528.8, 528.8, 0.0, 0.0]]
_PARENT_TOTALS = {"wall_ms": 4999.9, "device_ms": 4683.7, "host_ms": 303.4,
                  "host_overlapped_ms": 287.5, "stall_ms": 12.8,
                  "prefill_ahead_share": 0.3333}


@pytest.mark.parametrize("clock", [
    None, {}, {"gap_ms": 7.25, "program_ms": 911.5, "resume_lag_ms": 0.4},
    {"resume_lag_ms": 3.0},
], ids=["no-clock", "empty", "all-three", "lag-only"])
def test_the_clock_fields_leave_the_wall_decomposition_as_the_parent_wrote_it(
        monkeypatch, clock):
    """``device_ms``, ``host_ms``, ``ahead`` and the totals the round budget
    and every older reader use are byte-equal to the parent's on a recorded
    sequence, whatever the dispatch's clock carried."""
    from langstream_tpu.serving import flight

    monkeypatch.setattr(flight, "time", _Ticks(_RECORDED_TICKS))
    recorder = flight.FlightRecorder(slots=8, maxlen=64)
    wrote = []
    for phase, device_s, overlapped_s, tokens, ahead in _RECORDED:
        if phase == "stall":
            entry = recorder.stall("queue-empty")
        else:
            extra = {"ahead": ahead} if ahead is not None else {}
            entry = recorder.sample(
                phase, device_s=device_s, overlapped_s=overlapped_s,
                tokens=tokens, clock=dict(clock) if clock is not None else None,
                **extra)
            for key in ("gap_ms", "program_ms", "resume_lag_ms"):
                assert entry.get(key) == (clock or {}).get(key)
        wrote.append([entry["wall_ms"], entry["device_ms"], entry["host_ms"],
                      entry["host_overlapped_ms"]])
    assert json.dumps(wrote) == json.dumps(_PARENT_WROTE)
    totals = recorder.summary()["totals"]
    assert json.dumps({k: totals[k] for k in _PARENT_TOTALS}) == json.dumps(
        _PARENT_TOTALS)
    # the cumulative twins: six dispatch samples carried the clock
    n = 6 if clock else 0
    assert totals["gap_ms"] == pytest.approx(n * (clock or {}).get("gap_ms", 0))
    assert sum(totals["program_ms_by_phase"].values()) == pytest.approx(
        n * (clock or {}).get("program_ms", 0))
    assert totals["resume_lag_ms"] == pytest.approx(
        n * (clock or {}).get("resume_lag_ms", 0))


def _tiny_engine():
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    return TpuServingEngine(ServingConfig(
        model="tiny", model_dtype="float32", slots=4, max_seq_len=128,
        decode_chunk=4, kv_block_size=16, prefix_cache=False,
    ))


def test_an_engine_s_samples_tile_the_dispatch_thread_s_account(run_async):
    """Over a run of the tiny engine (prefill batches one ahead, pipelined
    chunks, a chunk left pending under the next round's prefills): every
    dispatch's sample carries the four fields, and gap_ms + program_ms of
    consecutive samples tile from the first program's enqueue to the last
    one's completion. The engine's one watcher thread ends with close()."""
    before = len(_watchers())

    async def main():
        engine = _tiny_engine()
        try:
            await engine.generate("warm the shapes", {"max-tokens": 6})
            await asyncio.gather(*(
                engine.generate(f"tile the device's time {i} " * (1 + i % 3),
                                {"max-tokens": 4 + 3 * i})
                for i in range(7)))
            assert len(_watchers()) == before + 1
        finally:
            await engine.close()
        return engine.flight, engine.attribution.report()

    flight, programs = run_async(main())
    assert len(_watchers()) == before      # joined in close()
    samples = [s for s in flight.recent(0) if s["phase"] != "stall"]
    assert len(samples) >= 8
    for s in samples:
        assert s["gap_ms"] >= 0 and s["program_ms"] >= 0, s
        assert s["resume_lag_ms"] >= 0, s
        assert s["seen_by"] in ("watch", "fetch"), s
    clock = flight.clock
    assert not clock._open                 # every program was seen complete
    span_ms = (clock.last_done_t - clock.first_enqueued_t) * 1e3
    tiled = sum(s["gap_ms"] + s["program_ms"] for s in samples)
    assert tiled == pytest.approx(span_ms, abs=0.001 * len(samples))  # rounding
    totals = flight.summary()["totals"]
    assert totals["gap_ms"] + sum(
        totals["program_ms_by_phase"].values()) == pytest.approx(span_ms, abs=0.01)
    assert set(totals["program_ms_by_phase"]) == {"prefill", "decode"}
    assert sum(totals["completions_seen_by"].values()) == len(samples)
    # the attribution ledger's measured side is the programs' own time
    assert sum(p["device_s_total"] for p in programs) == pytest.approx(
        sum(s["program_ms"] for s in samples if "program" in s) / 1e3, abs=1e-3)
    # and /metrics mirrors the totals, the busy time by phase
    from langstream_tpu.api.metrics import render_metrics

    scrape = render_metrics().decode()
    idle = re.search(r'langstream_engine_device_idle_seconds_total'
                     r'\{agent_id="tiny"\} (\S+)', scrape)
    busy = dict(re.findall(r'langstream_engine_device_busy_seconds_total'
                           r'\{agent_id="tiny",phase="(\w+)"\} (\S+)', scrape))
    assert float(idle.group(1)) >= totals["gap_ms"] / 1e3 - 1e-6
    assert {"prefill", "decode"} <= set(busy)
    assert all(float(busy[k]) >= v / 1e3 - 1e-6
               for k, v in totals["program_ms_by_phase"].items())
    assert totals["resume_lag_ms"] == pytest.approx(
        sum(s["resume_lag_ms"] for s in samples), abs=0.001 * len(samples))
    # a program's own time is never more than the wall its sample tiles plus
    # what ran before the sample's slice began; its wait (device_ms) is
    assert all(s["device_ms"] <= s["wall_ms"] for s in samples)


@pytest.mark.parametrize("blocked_s", [0.0, 0.05], ids=["idle-loop", "a-tenant"])
def test_resume_lag_is_the_loop_s_ready_queue(run_async, blocked_s):
    """With nothing else on the loop the coroutine runs again within a
    wake-up of the dispatch thread's return; with a tenant that holds the
    loop synchronously for ``blocked_s`` at a time, some dispatch waits at
    least that long for its turn, and the sample says so."""
    async def tenant(stop):
        while not stop.is_set():
            time.sleep(blocked_s)          # synchronous: the loop stands
            await asyncio.sleep(0.001)

    async def main():
        engine = _tiny_engine()
        stop = asyncio.Event()
        try:
            await engine.generate("warm the shapes", {"max-tokens": 6})
            mark = engine.flight.recorded
            beside = (asyncio.ensure_future(tenant(stop)) if blocked_s
                      else None)
            await asyncio.gather(*(
                engine.generate(f"who holds the loop {i}", {"max-tokens": 8})
                for i in range(3)))
            stop.set()
            if beside is not None:
                await beside
        finally:
            await engine.close()
        return engine.flight.recent(engine.flight.recorded - mark)

    lags = [s["resume_lag_ms"] for s in run_async(main())
            if s["phase"] != "stall"]
    assert lags
    if blocked_s:
        # a fetch of some milliseconds returns while the tenant sleeps: the
        # coroutine is ready and waits out what is left of the sleep
        assert max(lags) >= 0.5 * blocked_s * 1e3, lags
        assert sum(lags) >= blocked_s * 1e3, lags
    else:
        assert sorted(lags)[len(lags) // 2] < 5.0, lags   # "0": a wake-up


def test_paged_engine_under_load_decomposes_wall_time(run_async):
    """A paged engine under concurrent generate(): the flight rollup's
    device + host + stall components sum to within 10% of the measured
    wall time, at least one recompile event lands during the (implicit)
    warmup wave, and nothing is dropped below buffer capacity."""
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        engine = TpuServingEngine(
            ServingConfig(
                model="tiny", slots=4, max_seq_len=128, decode_chunk=8,
                kv_layout="paged", prefix_cache=True,
            )
        )
        t0 = time.monotonic()
        try:
            results = await asyncio.gather(
                *(
                    engine.generate(
                        f"flight recorder load prompt {i}", {"max-tokens": 16}
                    )
                    for i in range(12)
                )
            )
            elapsed = time.monotonic() - t0
            assert all(r["tokens"] for r in results)
            summary = engine.flight.summary()
            totals = summary["totals"]
            covered_s = (
                totals["device_ms"] + totals["host_ms"] + totals["stall_ms"]
            ) / 1000.0
            # the samples tile the engine-loop timeline, so the decomposed
            # components must reproduce the measured wall clock
            assert covered_s == pytest.approx(elapsed, rel=0.10)
            # ... and the decomposition itself is internally exact (up to
            # the per-total JSON rounding)
            assert totals["wall_ms"] / 1000.0 == pytest.approx(
                covered_s, abs=1e-4
            )
            # first-sight compiles (the warmup wave) are recorded as events
            recompiles = [
                e for e in engine.flight.recent_events()
                if e["kind"] == "recompile"
            ]
            assert recompiles, "warmup compiles must surface as events"
            assert totals["recompiles"] == len(recompiles)
            assert summary["dropped"] == 0
            assert totals["tokens"] == sum(len(r["tokens"]) for r in results)
            # every dispatch phase the run used shows up in the step counts
            assert totals["steps_by_phase"].get("prefill", 0) >= 1
            assert totals["steps_by_phase"].get("decode", 0) >= 1
            # stats() mirrors the per-phase counts for live introspection
            assert engine.stats()["steps"] == totals["steps_by_phase"]
        finally:
            await engine.close()

    run_async(main())


def test_engine_samples_name_their_dispatch(run_async):
    """Decode and prefill samples carry the dispatch's ordinal, the steps
    it fused and the slots running when it was made: taken at dispatch and
    carried to the sample, which is recorded when the result is processed
    (after finished slots were freed)."""
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        engine = TpuServingEngine(
            ServingConfig(
                model="tiny", slots=4, max_seq_len=128, decode_chunk=4,
                kv_layout="paged", prefix_cache=False,
            )
        )
        try:
            await asyncio.gather(*(
                engine.generate(f"dispatch fields {i}", {"max-tokens": 9})
                for i in range(4)
            ))
        finally:
            await engine.close()
        return engine.flight.recent(0)

    samples = [s for s in run_async(main()) if s["phase"] != "stall"]
    assert samples and all("dispatch" in s for s in samples)
    ordinals = [s["dispatch"] for s in samples]
    assert len(set(ordinals)) == len(ordinals)        # one sample a dispatch
    decode = [s for s in samples if s["phase"] == "decode"]
    prefill = [s for s in samples if s["phase"] == "prefill"]
    assert decode and prefill
    assert all(s["steps"] == 0 for s in prefill)
    for s in decode:
        # the steps are the program's own chunk size
        assert f":k{s['steps']}:" in s["program"] and s["steps"] > 0
        assert 1 <= s["active_at_dispatch"] <= 4
        # the paged read's work: the rows it has to fetch (a prompt of ~20
        # bytes and up to 9 answers a slot; the blocks that hold them and
        # the window's table columns, once written beside them, had no
        # reader and went: PR 36)
        assert s["active_at_dispatch"] <= s["live_rows"] \
            <= 64 * s["active_at_dispatch"]
        assert "table_blocks" not in s and "live_blocks" not in s
    assert all("live_rows" not in s for s in prefill)
    # a chunk in which requests finish is recorded after their slots were
    # freed: what was running at dispatch is the larger number
    assert any(s["active_at_dispatch"] > s["occupancy"] for s in decode)


def test_timeline_mark_recompile_events_and_idle_stall(run_async):
    """One engine, three recorder behaviors (shared to keep tier-1 wall
    time down): the loop re-marks the timeline at start so an idle
    deploy's construction→first-request gap isn't billed as host time; a
    fake compile-cache miss surfaces as exactly one recompile event; and
    idle gaps are recorded as queue-empty stall."""
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        engine = TpuServingEngine(
            ServingConfig(model="tiny", slots=2, max_seq_len=64, decode_chunk=4)
        )
        try:
            await asyncio.sleep(0.6)  # idle deploy: no loop, no samples
            t0 = time.monotonic()
            await engine.generate("late first request", {"max-tokens": 4})
            elapsed = time.monotonic() - t0
            totals = engine.flight.summary()["totals"]
            # without the loop-start mark the first sample would absorb
            # the 0.6 s pre-request gap
            assert totals["wall_ms"] / 1000.0 <= elapsed + 0.2

            # fake a compile-cache miss: forget a variant and re-request it
            before = engine.flight.recompiles
            engine._decode_chunk_fns.clear()
            engine._compiled_shapes.clear()
            engine._decode_fn((False, False, True), None)
            assert engine.flight.recompiles == before + 1
            newest = engine.flight.recent_events()[-1]
            assert newest["kind"] == "recompile"
            assert newest["what"] == "decode"
            # the same variant again is NOT a new compile
            engine._decode_fn((False, False, True), None)
            assert engine.flight.recompiles == before + 1

            # let the loop hit its idle wait once (1s wake timeout)
            await asyncio.sleep(1.2)
            assert engine.flight.stall_s_by_reason.get("queue-empty", 0.0) > 0
        finally:
            await engine.close()

    run_async(main())


def test_draft_tokens_report_real_draft_count(run_async):
    """Padding zeros are not drafts: the rejected-drafts accounting counts
    only genuine prompt-lookup continuations."""
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        engine = TpuServingEngine(
            ServingConfig(
                model="tiny", slots=2, max_seq_len=64, decode_chunk=4,
                kv_layout="paged", speculative_drafts=4,
            )
        )
        try:
            from langstream_tpu.serving.engine import _Request

            def fake_request(prompt):
                return _Request(
                    prompt_tokens=prompt, max_tokens=8, temperature=0.0,
                    top_k=0, top_p=1.0, on_token=None, future=None,
                )

            # repeated bigram (1,2): the continuation [3,1,2] drafts 3 real
            # tokens, padded to 4
            engine.slots[0].request = fake_request([1, 2, 3, 1, 2])
            drafts, n_real = engine._draft_tokens(0, 4)
            assert drafts == [3, 1, 2, 0]
            assert n_real == 3
            # no bigram repeats: zero real drafts, all padding
            engine.slots[1].request = fake_request([5, 6, 7, 8])
            drafts, n_real = engine._draft_tokens(1, 4)
            assert drafts == [0, 0, 0, 0]
            assert n_real == 0
            engine.slots[0].request = None
            engine.slots[1].request = None
        finally:
            await engine.close()

    run_async(main())


# --------------------------------------------------------------------------
# pod /flight endpoints
# --------------------------------------------------------------------------


def test_pod_serves_flight_and_summary(run_async, monkeypatch):
    from langstream_tpu.runtime.pod import _serve_info
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        # get_or_create registers the engine in the instance map the
        # /flight endpoint reports (direct construction stays private)
        engine = TpuServingEngine.get_or_create(
            ServingConfig(model="tiny", slots=2, max_seq_len=64, decode_chunk=4)
        )
        port = free_port()
        monkeypatch.setenv("LS_HTTP_PORT", str(port))
        server = await _serve_info(None)
        try:
            await engine.generate("pod flight probe", {"max-tokens": 4})
            async with aiohttp.ClientSession() as session:
                base = f"http://127.0.0.1:{port}"
                async with session.get(f"{base}/flight") as resp:
                    assert resp.status == 200
                    assert resp.headers["Content-Type"] == "application/json"
                    report = await resp.json()
                entry = next(e for e in report if e["model"] == "tiny")
                assert entry["samples"], "full report carries samples"
                assert entry["events"], "…and the event tail"
                assert entry["summary"]["totals"]["steps_by_phase"]
                async with session.get(f"{base}/flight/summary") as resp:
                    assert resp.status == 200
                    summaries = await resp.json()
                entry = next(e for e in summaries if e["model"] == "tiny")
                assert "samples" not in entry  # rollups only
                assert entry["summary"]["totals"]["wall_ms"] > 0
        finally:
            server.close()
            await engine.close()

    run_async(main())


# --------------------------------------------------------------------------
# control-plane fan-in e2e over the memory broker
# --------------------------------------------------------------------------

PIPELINE = """
topics:
  - name: "input-topic"
    creation-mode: create-if-not-exists
  - name: "output-topic"
    creation-mode: create-if-not-exists
pipeline:
  - name: "chat"
    id: "chat"
    type: "ai-chat-completions"
    input: "input-topic"
    output: "output-topic"
    configuration:
      completion-field: "value.answer"
      max-tokens: 8
      messages:
        - role: user
          content: "{{ value.q }}"
"""

# a real (tiny) TPU engine behind the agent — without the resource the
# agent resolves the mock provider and no flight recorder exists; the
# slo section exercises the declared-objective path end to end
CONFIGURATION = """
configuration:
  resources:
    - type: "tpu-serving-configuration"
      name: "tpu"
      configuration:
        model: "tiny"
        slots: 2
        max-seq-len: 128
        decode-chunk: 4
        slo:
          objectives:
            availability:
              target: 0.999
            ttft:
              target: 0.99
              threshold-ms: 60000
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


def test_e2e_flight_via_pod_and_controlplane(run_async, monkeypatch):
    """Gateway → ai-chat-completions over the memory broker, then the same
    flight data from the pod endpoint and the control-plane fan-in route
    (the ``test_tracing.py`` e2e shape, pointed at /flight)."""
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
                "files": {
                    "pipeline.yaml": PIPELINE,
                    "configuration.yaml": CONFIGURATION,
                    "gateways.yaml": GATEWAYS,
                },
                "instance": INSTANCE,
            }
            async with session.post(
                f"{api}/api/applications/t1/flightapp", json=payload
            ) as resp:
                body = await resp.json()
                assert resp.status == 200, body

            ws_base = f"ws://127.0.0.1:{gateway.port}"
            consume_url = (
                f"{ws_base}/v1/consume/t1/flightapp/consume-output"
                "?param:sessionId=s1&option:position=earliest"
            )
            produce_url = (
                f"{ws_base}/v1/produce/t1/flightapp/produce-input"
                "?param:sessionId=s1"
            )
            async with session.ws_connect(consume_url) as consumer:
                async with session.ws_connect(produce_url) as producer:
                    await producer.send_json({"value": {"q": "hello flight"}})
                    ack = await producer.receive_json()
                    assert ack["status"] == "OK"
                push = await asyncio.wait_for(
                    consumer.receive_json(), timeout=30
                )
            assert push["record"]["value"]["answer"]

            # the pod endpoint serves the engine that just ran
            pod_base = f"http://127.0.0.1:{pod_port}"
            async with session.get(f"{pod_base}/flight") as resp:
                assert resp.status == 200
                pod_report = await resp.json()
            assert pod_report, "a live engine must be reported"
            assert any(
                e["summary"]["totals"]["tokens"] > 0 for e in pod_report
            )

            # ... and the control-plane route fans in the same engines
            async with session.get(
                f"{api}/api/applications/t1/flightapp/flight"
            ) as resp:
                assert resp.status == 200
                cp_report = await resp.json()
            assert {e["model"] for e in cp_report} == {
                e["model"] for e in pod_report
            }
            entry = cp_report[0]
            assert entry["summary"]["totals"]["steps_by_phase"]
            assert "samples" in entry  # dev-mode fan-in carries the window

            # ... and the health/slo routes judge the same engines: the
            # served request left a healthy watchdog verdict and SLO
            # evidence (availability good, TTFT under its 60s threshold)
            async with session.get(
                f"{api}/api/applications/t1/flightapp/health"
            ) as resp:
                assert resp.status == 200
                health = await resp.json()
            assert health["status"] == "ok"
            assert health["pods"], "dev mode reports in-process members"
            engine_health = health["pods"][0]["engines"][0]
            assert engine_health["state"] == "ok"
            assert engine_health["ready"] is True
            async with session.get(
                f"{api}/api/applications/t1/flightapp/slo"
            ) as resp:
                assert resp.status == 200
                slo = await resp.json()
            assert "availability" in slo["configured"]["tpu"]["objectives"]
            engine_slo = next(
                e["slo"] for e in slo["engines"] if e["model"] == "tiny"
            )
            assert engine_slo["objectives"]["availability"]["window_good"] >= 1
            assert engine_slo["alerting"] == []

            # a malformed slo section fails the deploy with 400
            bad = {
                **payload,
                "files": {
                    **payload["files"],
                    "configuration.yaml": CONFIGURATION.replace(
                        "availability:", "uptime:"
                    ),
                },
            }
            async with session.post(
                f"{api}/api/applications/t1/badslo", json=bad
            ) as resp:
                assert resp.status == 400
                assert "slo" in (await resp.text())

            # an app this control plane never deployed reports nothing
            async with session.get(
                f"{api}/api/applications/t1/ghost/flight"
            ) as resp:
                assert resp.status == 200
                assert await resp.json() == []
        finally:
            await session.close()
            pod_server.close()
            await gateway.stop()
            await control.stop()
            await _close_engines()

    run_async(main())


def test_dev_flight_scoped_to_declared_models(monkeypatch):
    """Dev-mode engines are process-global: an app's flight route must
    only show the models its own serving resources declare (a sibling
    tenant's engine telemetry must not leak), and an app with no TPU
    resource (mock provider) sees nothing."""
    import langstream_tpu.serving.engine as engine_mod
    from langstream_tpu.controlplane.server import LocalComputeRuntime

    monkeypatch.setattr(
        engine_mod,
        "flight_report",
        lambda **kw: [
            {"model": "tiny", "summary": {}},
            {"model": "llama-1b", "summary": {}},
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
    assert [e["model"] for e in compute.flight("t", "app")] == ["tiny"]
    assert compute.flight("t", "plain") == []
    assert compute.flight("t", "ghost") == []


def test_k8s_flight_fanin_tags_pods():
    """The k8s compute runtime concatenates per-pod /flight entries and
    tags each with its pod (engines don't merge across pods the way trace
    rollups do)."""
    from langstream_tpu.k8s.compute import KubernetesComputeRuntime

    class _Stub:
        def _pod_json_fanin(self, tenant, name, path):
            assert path == "/flight"
            return [
                ("app-chat-0", [{"model": "tiny", "summary": {}}]),
                ("app-chat-1", [{"model": "tiny", "summary": {}}, "junk"]),
                ("app-chat-2", []),
            ]

    report = KubernetesComputeRuntime.flight(_Stub(), "t", "app")
    assert [e["pod"] for e in report] == ["app-chat-0", "app-chat-1"]
    assert all(e["model"] == "tiny" for e in report)


# --------------------------------------------------------------------------
# engine_top: render + --analyze golden on a canned dump
# --------------------------------------------------------------------------


def _canned_entry() -> dict:
    return {
        "model": "llama3-8b",
        "slots": 64,
        "summary": {
            "capacity": 4096,
            "recorded": 120,
            "dropped": 0,
            "totals": {
                "wall_ms": 4800.0,
                "device_ms": 2952.0,
                "host_ms": 1608.0,
                "stall_ms": 240.0,
                "tokens": 7680,
                "steps_by_phase": {"decode": 110, "prefill": 10},
                "stall_s_by_reason": {
                    "no-kv-blocks": 0.18,
                    "queue-empty": 0.06,
                },
                "recompiles": 4,
                "events_by_type": {"recompile": 4, "pool-grow": 7},
                "spec_accepted": 0,
                "spec_rejected": 0,
            },
            "window": {
                "samples": 120,
                "span_s": 4.8,
                "tokens": 7680,
                "tok_s": 1600.0,
                "step_ms_p50": 40.0,
                "step_ms_p95": 66.0,
                "host_overhead_ms_p50": 13.4,
                "device_ms_p50": 24.6,
                "queue_depth_p95": 9,
                "occupancy_mean": 61.5,
                "kv_used_ratio_last": 0.97,
            },
        },
        "samples": [
            {
                "seq": i, "t_ms": 1000.0 + 40.0 * i, "phase": "decode",
                "wall_ms": 40.0, "device_ms": 24.6, "host_ms": 15.4,
                "occupancy": 60, "slots": 64, "tokens": 64,
                "queue_depth": 1 + i // 10, "stall": None, "kv_used": 0.97,
                "prefix_hits": 0,
            }
            for i in range(120)
        ],
        "events": [
            {"seq": 3, "t_ms": 1100.0, "kind": "recompile", "what": "decode"},
            {"seq": 4, "t_ms": 1600.0, "kind": "recompile", "what": "decode"},
            {"seq": 5, "t_ms": 2100.0, "kind": "recompile", "what": "prefill"},
            {"seq": 9, "t_ms": 3000.0, "kind": "pool-grow", "slots": 4},
        ],
    }


def test_engine_top_analyze_golden(capsys, tmp_path):
    engine_top = _load_engine_top()
    text = engine_top.analyze([_canned_entry()])
    # decomposition: the three components with their shares
    assert "device  61.5%" in text
    assert "host    33.5%" in text
    assert "stall    5.0%" in text
    # mean step = busy wall (wall − stall) / steps: (4800−240)/120
    assert "mean step 38.0ms" in text
    assert "stall[no-kv-blocks] 0.18s" in text
    # anomaly windows: compiles clustered within 2 s + pool pressure
    assert "recompile storm" in text
    assert "KV pool" in text
    # queue depth grows 1 → 12 across the canned window
    assert "queue growth" in text

    # the CLI path: same analysis from a file, exit 0
    dump = tmp_path / "dump.json"
    dump.write_text(json.dumps([_canned_entry()]))
    assert engine_top.main(["--analyze", str(dump)]) == 0
    assert "device  61.5%" in capsys.readouterr().out


def test_engine_top_analyze_accepts_bench_record():
    """A bench JSON whose detail carries the flight rollup (no raw
    samples) still decomposes without error."""
    engine_top = _load_engine_top()
    record = {
        "metric": "tok/s/chip",
        "value": 1600.0,
        "detail": {
            "paged": {
                "tok_s": 1600.0,
                "flight": {
                    "host_overhead_ms_p50": 13.4,
                    "stall_s_by_reason": {"no-free-slot": 2.0},
                    "queue_depth_p95": 30,
                    "recompile_count": 2,
                    "totals": {
                        "wall_ms": 10000.0,
                        "device_ms": 6000.0,
                        "host_ms": 3000.0,
                        "stall_ms": 1000.0,
                        "tokens": 30000,
                        "steps_by_phase": {"decode": 200},
                    },
                },
            }
        },
    }
    text = engine_top.analyze(record)
    assert "device  60.0%" in text
    assert "host    30.0%" in text
    assert "stall   10.0%" in text
    assert "stall[no-free-slot] 2.00s" in text

    with pytest.raises(ValueError):
        engine_top.analyze({"no": "flight here"})


def test_engine_top_render_smoke():
    engine_top = _load_engine_top()
    frame = engine_top.render([_canned_entry()])
    assert "engine llama3-8b" in frame
    assert "60/64" in frame          # occupancy
    assert "tok/s 1600.0" in frame
    assert "recompiles 4" in frame
    assert "kv pool" in frame
    # empty report renders a hint, not a crash
    assert "no live engines" in engine_top.render([])


@pytest.mark.parametrize("totals, line", [
    ({"gap_ms": 50.0, "program_ms_by_phase": {"decode": 700.0, "prefill": 250.0},
      "completions_seen_by": {"watch": 30, "fetch": 12}},
     "device   idle 5.0%  decode 70.0%  prefill 25.0%  "
     "(stamped by watch 30 / fetch 12)"),
    # a payload from before the watcher: the fields were bounds, not shown
    ({"gap_ms": 50.0, "program_ms_by_phase": {"decode": 700.0}}, None),
    ({}, None),
], ids=["the-device-s", "bounds", "nothing"])
def test_engine_top_shows_the_device_s_idle_share_beside_the_host_s(totals, line):
    engine_top = _load_engine_top()
    assert engine_top._render_device_clock(totals) == line
    entry = _canned_entry()
    entry["summary"]["totals"].update(totals)
    frame = engine_top.render([entry])
    assert (line in frame) if line else ("device   idle" not in frame)
