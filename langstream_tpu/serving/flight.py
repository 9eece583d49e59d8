"""Engine flight recorder: per-dispatch telemetry ring + stall attribution.

The vLLM-style engine stats loop, grown into a bounded time series: PR-2's
traces explain *one request's* journey; this module records *every
dispatched burst* the engine runs — the aggregate signal that localizes
systemic stalls (Dapper's lesson: per-request traces don't find the 16 ms
of host overhead that every step pays).

One :class:`FlightRecorder` per engine. The engine loop records a **sample**
per dispatched decode/prefill/verify burst and a **stall** sample for every
idle gap, so the samples tile the engine-loop timeline contiguously:

- ``wall_ms`` — time since the previous recorded boundary (the full slice
  of engine-loop wall clock this burst accounts for);
- ``device_ms`` — the slice of wall spent *under device execution*: the
  blocked device wait (measured at the dispatch's block boundary — the
  fetch/``block_until_ready`` call) PLUS any host work the pipelined loop
  ran in the shadow of an in-flight dispatch (``host_overlapped_ms``,
  also carried per sample). Host time hidden behind device compute costs
  nothing, so it is credited to the device-busy share rather than to
  host overhead — and reported separately so the overlap win is visible;
- ``host_overlapped_ms`` — the host share of ``device_ms``: detokenize/
  stop-check/emit work the pipelined loop ran while the next chunk
  executed on device (0 for the sequential loop). The engine bounds the
  credit with non-blocking device-readiness probes (``is_ready``), so
  host work that outlives the shadowing dispatch stays EXPOSED — a
  host-bound engine cannot masquerade as device-bound. Never
  double-counted: it lives inside ``device_ms``, never inside
  ``host_ms``;
- ``host_ms`` — ``wall − device`` (clamped ≥ 0): the *exposed* host time
  — Python dispatch, numpy packing, emit callbacks, block accounting
  that ran with the device idle — the "unattributed host overhead"
  bucket BENCH r05 could not see;
- ``stall`` — why queued work is not being admitted at this boundary
  (``no-free-slot`` / ``no-kv-blocks`` / ``prefill-in-flight`` /
  ``queue-empty``), plus batch occupancy, queue depth, tokens emitted,
  KV-pool reserved ratio (the admission pressure), prefix-cache hits,
  and speculative accept/reject.

Because the samples tile the timeline, the rollup decomposes total wall
time **exactly** into ``device + host + stall`` — the property the bench
acceptance checks against its own measured wall clock. Stall attribution
is kept in two disjoint dictionaries so a saturated engine never reads
as "stalled": ``stall_s_by_reason`` (engine-loop idle time; sums to
``stall_ms``) vs ``blocked_s_by_reason`` (busy-dispatch wall during
which queued work could not be admitted — queue pressure).

Discrete **events** ride a second small ring: ``recompile`` (a jit variant
or prefill bucket compiled for the first time — the 30 s mid-traffic
convoy-maker on TPU), ``pool-grow`` (decode-time KV block allocation),
``warmup``, ``preempt`` (a QoS preemption under KV pressure, or in-flight
work failed — the ``reason`` field tells them apart), ``resume`` (a
preempted request re-admitted), ``shed`` (a request refused by QoS
policy: tenant throttle or full class queue), ``lockstep-divergence``,
``health`` (a watchdog state transition — ok/degraded/wedged, with the
stall evidence; serving/health.py), ``alert`` (an SLO objective's
multi-window burn rate crossed the page threshold, or recovered), and
the device-survival plane's events (docs/RESILIENCE.md): ``pool-shrink``
(a device allocator failure shrank the KV admission budget — site,
withheld/freed bytes, victims preempted, the new budget),
``pool-restore`` (the recovery probe returned a shrink quantum),
``fault-injected`` (a chaos-drill fault fired at an engine seam —
serving/faults.py), and ``journal-replay``/``journal-evict`` (the
crash-requeue journal replayed recovered work / shed its oldest entry
at the bound).
Under a QoS scheduler each sample additionally carries ``queue_by_class``
(per-priority-class queue depths — what ``engine_top --analyze`` watches
for sustained interactive-class growth).

Hot-path discipline (graftcheck rule OBS503 gates this): the record path
is append-only on GIL-atomic deques — **no locks, no I/O, nothing that can
block the engine loop**. Rollups snapshot with ``list(deque)``.

Host **spans** (:meth:`FlightRecorder.span`, which is
``core/tracing.py`` ``host_span``: the one helper, shared with the loop's
other tenants) mark the same dispatch boundaries on the profiler's clock: a
``jax.profiler.TraceAnnotation`` and nothing else. With no profiler session
a span records nothing and leaves the recorder untouched; under one
(``/profile/start``, the benchmark's ``--trace 1``) it lands in the
``/host:CPU`` plane of the same ``.xplane.pb`` as the device operations,
where ``bench/lib/hosttrace.py`` lays it against the device's idle gaps.
The vocabulary is :data:`SPANS` (docs/OBSERVABILITY.md, "Device
profiling"); a dispatch's ``seq`` is the ``dispatch`` field of its flight
sample.

The **device's clock** (:class:`DispatchClock`) is the same account with
the tracing off, over the whole life of the engine. The dispatch thread
stamps each program as its jitted call returns; its completion is stamped
where it is FIRST seen: by that thread where it waits for the result, or by
one watcher thread of the engine that waits on every program's result in the
device's order (its wait is the span ``dev.watch``, the one name outside
``ls.``: no idle time is attributed to it). Each dispatch's sample carries
``gap_ms`` (the device stood with nothing queued before this program: its
idle time), ``program_ms`` (the program's own time on the device),
``seen_by`` (``"watch"`` or ``"fetch"``: who stamped the completion) and
``resume_lag_ms`` (how long the engine's coroutine waited in the loop's
ready queue after the dispatch thread had returned). A stamp needs the GIL,
so it can be late by what another thread holds it for; the benchmark's
``device_clock_late_ms_p95`` measures that against a trace.

Sizing: ``LS_TPU_FLIGHT_BUFFER`` samples (default 4096, min 64). Cumulative
totals (wall/device/host/stall, per-phase step counts, stall seconds by
reason, token counts) are plain counters maintained alongside the ring, so
the rollup stays exact even after the ring starts evicting; percentiles
and rates come from the retained window.

Exposure: the pod serves ``/flight`` (recent samples + events + rollup)
and ``/flight/summary`` next to ``/metrics`` and ``/traces``; the control
plane fans pods in under ``/api/applications/{t}/{n}/flight``; and
``tools/engine_top.py`` renders the same payload as a live console or a
post-mortem breakdown. See ``docs/OBSERVABILITY.md``.
"""

from __future__ import annotations

import os
import queue
import threading
import time
import weakref
from collections import deque
from typing import Any, Callable

from langstream_tpu.core.tracing import host_span

#: admission-stall reasons a sample may carry (the attribution vocabulary)
STALL_REASONS = (
    "no-free-slot",
    "no-kv-blocks",
    "prefill-in-flight",
    "queue-empty",
)

#: dispatch phases (a "stall" sample is the fifth, non-dispatch kind)
PHASES = ("prefill", "decode", "verify")

#: host spans on the profiler's clock (the whole vocabulary; every name a
#: reader of the profile may meet): the engine loop's, the dispatch
#: thread's two blocking waits (``*.wait``), and ``ls.hop.*`` around the
#: synchronous stretches of everything else that runs on the engine's loop
#: between two of its dispatches. Beside them :data:`WATCH_SPAN`, the one
#: name that is NOT attributed: ``bench/lib/hosttrace.py`` gives each idle
#: instant to the ``ls.*`` span that started last, whatever its thread, so a
#: third thread's wait under that prefix would take idle time from the
#: spans of the code that caused it
SPANS = (
    "ls.admit",
    "ls.prefill.pack",
    "ls.prefill.dispatch",
    "ls.prefill.handoff",
    "ls.prefill.fetch",
    "ls.prefill.wait",
    "ls.prefill.emit",
    "ls.decode.prepare",
    "ls.decode.dispatch",
    "ls.decode.fetch",
    "ls.decode.wait",
    "ls.decode.process",
    "ls.decode.emit",
    "ls.release",
    "ls.idle",
    "ls.hop.deliver",
    "ls.hop.agent",
    "ls.hop.topic",
    "ls.hop.gw.recv",
    "ls.hop.gw.send",
    "ls.hop.runner",
)

#: the watcher thread's wait for one program's result (``seq`` is the
#: dispatch's): it ends where :class:`DispatchClock` stamps the completion
WATCH_SPAN = "dev.watch"

#: the spans held across an ``await`` of the dispatch thread: once that
#: thread's own span (``*.dispatch``, ``*.wait``) has ended, what is left
#: under one of these is the engine's coroutine waiting for its turn on the
#: loop, unless a tenant's ``ls.hop.*`` span started meanwhile
HELD_SPANS = ("ls.prefill.handoff", "ls.prefill.fetch", "ls.decode.fetch")


def _buffer_size() -> int:
    try:
        return max(64, int(os.environ.get("LS_TPU_FLIGHT_BUFFER", "4096")))
    except ValueError:
        return 4096


def _pct(sorted_values: list, q: float):
    """Nearest-rank percentile of an already-sorted list (None when empty)."""
    if not sorted_values:
        return None
    return sorted_values[min(len(sorted_values) - 1, int(q * len(sorted_values)))]


def _watch(inbox: "queue.SimpleQueue", wait: Callable[[Any], Any],
           clock: Callable[[], float]) -> None:
    """The watcher thread of one :class:`DispatchClock`: wait on each
    program's result in the device's order (the GIL released while it
    blocks) and stamp the completion unless the dispatch thread has. It
    holds no reference to the clock, and to a handle only while it waits on
    it. A failed program is over too: its error is raised once, where the
    dispatch thread waits for it, and swallowed here."""
    while True:
        item = inbox.get()
        if item is None:
            return
        times, handle, seq = item
        del item
        with host_span(WATCH_SPAN, **({} if seq is None else {"seq": seq})):
            try:
                wait(handle)
            # graftcheck: disable=EXC402 the program's error is raised once, where the dispatch thread waits for it
            except Exception:
                pass
            del handle
            times.setdefault("seen", (clock(), "watch"))
        del times


class DispatchClock:
    """The device's clock, kept with the tracing off. One thread hands
    every program to the device, which runs them in that order, so two
    ``time.monotonic()`` stamps a program tile the device's time:
    ``enqueued_t`` when the jitted call has returned (:meth:`enqueued`),
    ``done_t`` where its completion is first seen. Two observers see
    completions: the dispatch thread, where it makes the blocking call on a
    result (:meth:`ready`, :meth:`settle`), and the watcher, one daemon
    thread that waits on every program's result in the device's order
    (``watch``, the blocking call it makes: ``jax.block_until_ready``) and so
    is waiting when a batch dispatched one ahead ends while the dispatch
    thread dispatches its successor (:meth:`seen`). Whoever stamps first
    wins: one ``dict.setdefault`` of ``times["seen"]``, ``(t, "watch" |
    "fetch")``. The watcher stamps in the device's order; the dispatch
    thread, seeing a program complete, also stamps every program enqueued
    before it that nobody has. The arithmetic is the dispatch thread's alone
    (:meth:`ready`), which closes every program before its sample is
    recorded. Each program's ``times`` (the ``clock`` of
    its engine ticket) then holds

    - ``gap_ms``: ``enqueued_t`` less the ``done_t`` of the program
      enqueued before it, whatever its phase, where that is positive: the
      device stood with nothing queued, its idle time;
    - ``program_ms``: ``done_t`` less the later of ``enqueued_t`` and that
      predecessor's ``done_t``: the program's own time on the device;
    - ``seen_by``: who stamped ``done_t``.

    The sums of the two fields tile from :attr:`first_enqueued_t` to
    :attr:`last_done_t` under any interleaving of the two observers (a stamp
    read before a predecessor's and written after it is moved up to it). A
    program whose completion nobody has seen when a LATER one's is waited
    for (a decode chunk left pending under an admission round's prefills)
    is waited for first (:meth:`settle`), so its time is not its
    successor's. What is left between the device and these figures is the
    stamp's lateness: an observer needs the GIL to stamp, and waits for it
    as long as another thread runs Python (the switch interval, 5 ms, at
    most; ``device_clock_late_ms_p95`` of the benchmark measures it). A
    completion seen late lengthens its program and shortens the gap after
    it by as much. Record path: clock reads, a deque, one
    ``queue.SimpleQueue.put`` and dictionary stores; no lock."""

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 watch: Callable[[Any], Any] | None = None):
        self._clock = clock
        #: the watcher's blocking call on a handle; None: no watcher (a
        #: recorder without an engine, a clock that was closed)
        self._watch = watch
        self._inbox: queue.SimpleQueue | None = None
        self._watcher: threading.Thread | None = None
        #: (times, handle) of the programs enqueued and not yet closed, in
        #: the device's order (at most three: a decode chunk and two prefill
        #: batches); the dispatch thread's alone
        self._open: deque[tuple[dict, Any]] = deque()
        self.first_enqueued_t: float | None = None
        self.last_done_t: float | None = None

    def enqueued(self, times: dict, handle: Any = None,
                 seq: int | None = None) -> None:
        """The jitted call returned: the program is the device's. ``handle``
        is something of its result to wait on (:meth:`settle`, the watcher),
        ``seq`` the dispatch's ordinal for the watcher's span."""
        now = self._clock()
        times["enqueued_t"] = now
        if self.first_enqueued_t is None:
            self.first_enqueued_t = now
        self._open.append((times, handle))
        if self._watch is not None and handle is not None:
            if self._watcher is None:
                self._start()
            self._inbox.put((times, handle, seq))

    def _start(self) -> None:
        """The watcher, at the first enqueue. It ends with :meth:`close`,
        or when nothing refers to this clock any more."""
        self._inbox = queue.SimpleQueue()
        weakref.finalize(self, self._inbox.put, None)
        self._watcher = threading.Thread(
            target=_watch, args=(self._inbox, self._watch, self._clock),
            name="tpu-engine-watch", daemon=True)
        self._watcher.start()

    def seen(self, times: dict, by: str = "watch") -> None:
        """An observer other than :meth:`ready` saw ``times``' program
        complete: the stamp stands unless one was there (what the watcher
        does after its wait; a test's hand on the second observer)."""
        times.setdefault("seen", (self._clock(), by))

    def settle(self, times: dict, wait: Callable[[Any], Any]) -> None:
        """Before the blocking call on ``times``' own program: wait, in the
        device's order, for each program enqueued before it whose completion
        this thread has not seen (it cannot end later than this one, so the
        thread blocks no longer than it would have)."""
        if "enqueued_t" not in times or "done_t" in times:
            return  # not the device's through this clock: nothing to order
        while self._open and self._open[0][0] is not times:
            earlier, handle = self._open[0]
            try:
                if handle is not None:
                    wait(handle)
            finally:  # a failed program is over too: raise it once, here
                self.ready(earlier)

    def ready(self, times: dict) -> None:
        """The blocking call on ``times``' program returned. Programs
        enqueued before it have completed too: each one open is closed
        here, at the watcher's stamp where it has one, else now."""
        if "enqueued_t" not in times or "done_t" in times:
            return
        now = self._clock()
        while self._open:
            earlier, _handle = self._open.popleft()
            done, by = earlier.setdefault("seen", (now, "fetch"))
            before = self.last_done_t
            start = earlier["enqueued_t"]
            earlier["gap_ms"] = 0.0
            if before is not None:
                earlier["gap_ms"] = max(0.0, start - before) * 1e3
                start = max(start, before)
            done = max(done, start)
            earlier["program_ms"] = (done - start) * 1e3
            earlier["done_t"] = done
            earlier["seen_by"] = by
            self.last_done_t = done
            if earlier is times:
                return

    def close(self, timeout: float = 2.0) -> None:
        """End the watcher and wait for it (a wedged device's wait is left
        to the daemon flag after ``timeout``). Later enqueues are stamped by
        the dispatch thread alone."""
        self._watch = None
        watcher, self._watcher = self._watcher, None
        if watcher is not None:
            self._inbox.put(None)
            watcher.join(timeout)


def resumed(times: dict) -> None:
    """First statement of the engine's coroutine after an ``await`` of the
    dispatch thread, whose last stamp before returning is
    ``times["returned_t"]``: what lies between is the wake-up and the loop's
    ready queue. Summed over a dispatch's awaits into
    ``times["resume_lag_ms"]``."""
    times["resume_lag_ms"] = times.get("resume_lag_ms", 0.0) + max(
        0.0, time.monotonic() - times["returned_t"]
    ) * 1e3


class FlightRecorder:
    """Bounded per-engine telemetry ring. Single writer (the engine loop;
    events may also arrive from the dispatch thread), many readers."""

    def __init__(self, slots: int = 0, maxlen: int | None = None,
                 watch: Callable[[Any], Any] | None = None):
        self.slots = slots
        #: ``watch``: the blocking call on a program's result, for the
        #: clock's watcher thread (the engine gives
        #: ``jax.block_until_ready``); without it the dispatch thread
        #: stamps alone
        self.clock = DispatchClock(watch=watch)
        self.capacity = maxlen if maxlen is not None else _buffer_size()
        self._samples: deque[dict[str, Any]] = deque(maxlen=self.capacity)
        self._events: deque[dict[str, Any]] = deque(maxlen=512)
        self._seq = 0
        self._event_seq = 0
        self._last_mark = time.monotonic()
        # cumulative counters: exact over the engine's whole life, immune
        # to ring eviction (plain attributes — engine loop is the only
        # sample writer, and CPython attribute updates don't interleave)
        self.recorded = 0
        self.wall_ms = 0.0
        self.device_ms = 0.0
        self.host_ms = 0.0
        self.host_overlapped_ms = 0.0
        self.stall_ms = 0.0
        self.tokens = 0
        self.recompiles = 0
        self.steps_by_phase: dict[str, int] = {}
        # two distinct attributions (they must not be conflated, or a
        # saturated engine reads as 100% stalled):
        # - stall_s_by_reason: engine-loop STALL time (stall samples only)
        #   — decomposes totals.stall_ms exactly;
        # - blocked_s_by_reason: wall time of dispatch samples annotated
        #   with an admission-stall reason — the engine was BUSY, but
        #   queued work waited that long for that reason (queue pressure)
        self.stall_s_by_reason: dict[str, float] = {}
        self.blocked_s_by_reason: dict[str, float] = {}
        self.events_by_type: dict[str, int] = {}
        self.spec_accepted = 0
        self.spec_rejected = 0
        self.prefill_ahead = 0
        self.prefill_rows = 0
        # the rows the prefill batches' programs ran (bucket x requests)
        # and the true tokens of their prompts
        self.prefill_bucket_rows = 0
        self.prefill_prompt_tokens = 0
        # cumulative twins of the samples' gap_ms / program_ms / seen_by /
        # resume_lag_ms (the device's clock, DispatchClock): the device's
        # idle time, its busy time by phase, who stamped the completions
        self.gap_ms = 0.0
        self.program_ms_by_phase: dict[str, float] = {}
        self.completions_seen_by: dict[str, int] = {}
        self.resume_lag_ms = 0.0

    # -- recording (engine hot path: appends + counter bumps only) -------

    def mark(self) -> None:
        """Reset the timeline boundary (e.g. when the engine loop starts
        after a long construction gap, so the gap isn't billed as host)."""
        self._last_mark = time.monotonic()

    #: a host span on the profiler's clock: a context manager around
    #: synchronous code (or the one ``await`` one of :data:`HELD_SPANS`
    #: names). ``name`` is one of :data:`SPANS`; ``meta`` become the event's
    #: stats. Outside a profiler session this checks a flag and records
    #: nothing. The one helper every layer on the loop uses.
    span = staticmethod(host_span)

    def sample(
        self,
        phase: str,
        *,
        device_s: float = 0.0,
        overlapped_s: float = 0.0,
        tokens: int = 0,
        occupancy: int = 0,
        queue_depth: int = 0,
        stall: str | None = None,
        kv_used: float | None = None,
        spec_accepted: int = 0,
        spec_rejected: int = 0,
        queue_by_class: dict[str, int] | None = None,
        program: str | None = None,
        dispatch: int | None = None,
        steps: int = 0,
        active_at_dispatch: int | None = None,
        live_rows: int | None = None,
        routed_pairs: int | None = None,
        expert_load_max: int | None = None,
        state_bytes: int | None = None,
        ahead: int | None = None,
        prompt_tokens: int | None = None,
        bucket: int | None = None,
        clock: dict | None = None,
        pool_rows: dict | None = None,
    ) -> dict[str, Any]:
        """Record one dispatched burst. ``wall`` is the time since the
        previous boundary. ``overlapped_s`` is host work the pipelined
        loop ran under an in-flight dispatch's device shadow: it is
        credited to the device-busy share (``device = wait + overlapped``,
        clamped to wall) and reported per sample, so
        ``host = wall − device`` stays the *exposed* host time and the
        wall decomposition remains exact. ``queue_by_class`` (QoS engines
        only) keeps the sample schema unchanged for FIFO engines by being
        omitted when None. ``program`` keys the sample by the compiled
        program variant that ran (the attribution ledger's id,
        serving/attribution.py) — omitted when unknown so pre-attribution
        consumers see an unchanged schema. ``dispatch`` (the ordinal the
        engine gave the dispatch, the ``seq`` of its host spans), ``steps``
        (decode steps it fused; 0 for a prefill) and ``active_at_dispatch``
        (slots running when it was dispatched) were taken at dispatch, not
        at this later boundary where ``occupancy`` is read; omitted
        together when the caller has no dispatch to name. ``live_rows``
        (a paged decode chunk only) are the rows its read had to fetch of
        each layer's pool, summed over the slots.
        ``routed_pairs``, ``expert_load_max`` and ``state_bytes`` (a hybrid
        model's decode chunk only, models/hybrid.py) are the (token, expert)
        pairs the chunk's active rows sent to the experts held here, the most
        any one expert of any one layer got of them, and the bytes of
        recurrent state of the slots it advanced (read and written once a
        step). ``ahead`` (a prefill batch only) is 1 when the batch was
        dispatched while its predecessor's first tokens were unfetched, so
        its ``device_s`` is what was left of the program when the host came
        to wait for it, not the program's run time. ``prompt_tokens`` (a
        prefill batch only) are the true tokens its rows prefilled,
        ``bucket`` (beside them) the rows its program ran for each request.
        ``pool_rows`` (a decode chunk of a model with a pool a layer kind,
        models/swa.py) joins the sample key by key: ``window_rows``,
        ``pool_rows_held``, ``pool_rows_one_table``,
        ``window_slot_blocks_max``, ``short_slots``,
        ``window_blocks_held`` (engine.py ``_pool_rows``).
        ``clock`` is the dispatch's times as :class:`DispatchClock` and
        :func:`resumed` left them: the sample's ``gap_ms``, ``program_ms``,
        ``seen_by`` and ``resume_lag_ms``, each omitted where it was not
        taken."""
        now = time.monotonic()
        wall_ms = (now - self._last_mark) * 1000.0
        self._last_mark = now
        wait_ms = max(0.0, min(device_s * 1000.0, wall_ms))
        overlapped_ms = max(0.0, min(overlapped_s * 1000.0, wall_ms - wait_ms))
        device_ms = wait_ms + overlapped_ms
        host_ms = wall_ms - device_ms
        self._seq += 1
        entry: dict[str, Any] = {
            "seq": self._seq,
            # wall-clock anchor for display alignment across pods only;
            # every duration above is monotonic
            # graftcheck: disable=OBS501 display anchor, never subtracted
            "t_ms": round(time.time() * 1000.0, 3),
            "phase": phase,
            "wall_ms": round(wall_ms, 3),
            "device_ms": round(device_ms, 3),
            "host_ms": round(host_ms, 3),
            "host_overlapped_ms": round(overlapped_ms, 3),
            "occupancy": occupancy,
            "slots": self.slots,
            "tokens": tokens,
            "queue_depth": queue_depth,
            "stall": stall,
            "kv_used": round(kv_used, 4) if kv_used is not None else None,
        }
        if spec_accepted or spec_rejected:
            entry["spec_accepted"] = spec_accepted
            entry["spec_rejected"] = spec_rejected
        if queue_by_class is not None:
            entry["queue_by_class"] = dict(queue_by_class)
        if program is not None:
            entry["program"] = program
        if dispatch is not None:
            entry["dispatch"] = dispatch
            entry["steps"] = steps
            entry["active_at_dispatch"] = active_at_dispatch
        if live_rows is not None:
            entry["live_rows"] = live_rows
        if routed_pairs is not None:
            entry["routed_pairs"] = routed_pairs
            entry["expert_load_max"] = expert_load_max
            entry["state_bytes"] = state_bytes
        if ahead is not None:
            entry["ahead"] = ahead
            self.prefill_ahead += ahead
        if phase == "prefill":
            self.prefill_rows += tokens
        if prompt_tokens is not None:
            entry["prompt_tokens"] = prompt_tokens
        if bucket is not None:
            entry["bucket"] = bucket
            self.prefill_bucket_rows += bucket * tokens
            self.prefill_prompt_tokens += prompt_tokens or 0
        if pool_rows is not None:
            entry.update(pool_rows)
        if clock:
            if "program_ms" in clock:
                entry["gap_ms"] = round(clock["gap_ms"], 3)
                entry["program_ms"] = round(clock["program_ms"], 3)
                if "seen_by" in clock:
                    by = entry["seen_by"] = clock["seen_by"]
                    self.completions_seen_by[by] = (
                        self.completions_seen_by.get(by, 0) + 1)
                self.gap_ms += clock["gap_ms"]
                self.program_ms_by_phase[phase] = (
                    self.program_ms_by_phase.get(phase, 0.0)
                    + clock["program_ms"]
                )
            if "resume_lag_ms" in clock:
                entry["resume_lag_ms"] = round(clock["resume_lag_ms"], 3)
                self.resume_lag_ms += clock["resume_lag_ms"]
        self._samples.append(entry)
        self.recorded += 1
        self.wall_ms += wall_ms
        self.device_ms += device_ms
        self.host_ms += host_ms
        self.host_overlapped_ms += overlapped_ms
        self.tokens += tokens
        self.steps_by_phase[phase] = self.steps_by_phase.get(phase, 0) + 1
        if stall:
            # the engine dispatched work this slice, so this is BLOCKED
            # (queued work waiting while busy), not engine stall
            self.blocked_s_by_reason[stall] = (
                self.blocked_s_by_reason.get(stall, 0.0) + wall_ms / 1000.0
            )
        self.spec_accepted += spec_accepted
        self.spec_rejected += spec_rejected
        return entry

    def stall(
        self,
        reason: str,
        *,
        occupancy: int = 0,
        queue_depth: int = 0,
        kv_used: float | None = None,
        queue_by_class: dict[str, int] | None = None,
    ) -> dict[str, Any]:
        """Record an idle/blocked gap (no dispatch): its whole wall slice
        is stall time attributed to ``reason``."""
        now = time.monotonic()
        wall_ms = (now - self._last_mark) * 1000.0
        self._last_mark = now
        self._seq += 1
        entry: dict[str, Any] = {
            "seq": self._seq,
            # graftcheck: disable=OBS501 display anchor, never subtracted
            "t_ms": round(time.time() * 1000.0, 3),
            "phase": "stall",
            "wall_ms": round(wall_ms, 3),
            "device_ms": 0.0,
            "host_ms": 0.0,
            "host_overlapped_ms": 0.0,
            "occupancy": occupancy,
            "slots": self.slots,
            "tokens": 0,
            "queue_depth": queue_depth,
            "stall": reason,
            "kv_used": round(kv_used, 4) if kv_used is not None else None,
        }
        if queue_by_class is not None:
            entry["queue_by_class"] = dict(queue_by_class)
        self._samples.append(entry)
        self.recorded += 1
        self.wall_ms += wall_ms
        self.stall_ms += wall_ms
        self.stall_s_by_reason[reason] = (
            self.stall_s_by_reason.get(reason, 0.0) + wall_ms / 1000.0
        )
        return entry

    def event(self, kind: str, **detail: Any) -> None:
        """Record a discrete event (recompile / pool-grow / warmup /
        preempt / lockstep-divergence). Safe from any thread."""
        self.events_by_type[kind] = self.events_by_type.get(kind, 0) + 1
        if kind == "recompile":
            self.recompiles += 1
        # per-recorder monotonic event sequence: same-millisecond events
        # stay totally ordered, so tail consumers (the watchdog's 256-event
        # window, incident capture) dedup by seq instead of timestamp ties
        self._event_seq += 1
        self._events.append(
            {
                "seq": self._event_seq,
                # graftcheck: disable=OBS501 display anchor, never subtracted
                "t_ms": round(time.time() * 1000.0, 3),
                # monotonic stamp for the live health predicates
                # (serving/health.py recompile_storm): recency judgments
                # must survive NTP steps, which t_ms cannot
                "m_s": round(time.monotonic(), 3),
                "kind": kind,
                **detail,
            }
        )

    # -- reading (snapshots; never block the writer) ---------------------
    #
    # Cross-thread safety: readers snapshot with list(deque) / dict(d) —
    # single C-level copies of containers holding plain dicts, which never
    # release the GIL or call back into Python, so a concurrent append
    # from the engine loop or dispatch thread cannot interleave mid-copy.
    # All derived math then runs on the snapshot.

    def recent(self, n: int = 240) -> list[dict[str, Any]]:
        samples = list(self._samples)
        return samples[-n:] if n else samples

    def recent_events(self, n: int = 64) -> list[dict[str, Any]]:
        events = list(self._events)
        return events[-n:] if n else events

    @property
    def prefill_ahead_share(self) -> float | None:
        """Prefill batches dispatched one ahead over all prefill batches
        (cumulative; None before the first)."""
        batches = self.steps_by_phase.get("prefill", 0)
        return round(self.prefill_ahead / batches, 4) if batches else None

    @property
    def prefill_rows_mean(self) -> float | None:
        """Requests a prefill batch carried, mean over all prefill batches
        (the samples' ``tokens``; cumulative; None before the first)."""
        batches = self.steps_by_phase.get("prefill", 0)
        return round(self.prefill_rows / batches, 4) if batches else None

    @property
    def prefill_padded_rows_share(self) -> float | None:
        """Rows the prefill batches' programs ran (the samples' ``bucket``
        x ``tokens``) over the true tokens of their prompts (cumulative;
        None before the first)."""
        true = self.prefill_prompt_tokens
        return round(self.prefill_bucket_rows / true, 4) if true else None

    @property
    def dropped(self) -> int:
        """Samples evicted from the ring (0 until ``recorded`` exceeds
        ``LS_TPU_FLIGHT_BUFFER``)."""
        return self.recorded - len(self._samples)

    def summary(self) -> dict[str, Any]:
        """Rollup: exact cumulative totals + window percentiles/rates.

        ``totals.device_ms + totals.host_ms + totals.stall_ms ==
        totals.wall_ms`` by construction — the decomposition the bench
        acceptance compares against its measured wall clock.
        """
        window = list(self._samples)
        dispatch = [s for s in window if s["phase"] != "stall"]
        walls = sorted(s["wall_ms"] for s in dispatch)
        hosts = sorted(s["host_ms"] for s in dispatch)
        devices = sorted(s["device_ms"] for s in dispatch)
        overlaps = sorted(
            s.get("host_overlapped_ms", 0.0) for s in dispatch
        )
        # window overlap ratio: the share of host work the pipelined loop
        # hid behind device compute (None when the window did no host work)
        overlapped_sum = sum(overlaps)
        host_sum = overlapped_sum + sum(hosts)
        overlap_ratio = (
            round(overlapped_sum / host_sum, 4) if host_sum > 0 else None
        )
        queue_depths = sorted(s["queue_depth"] for s in window)
        # the samples tile the timeline, so the retained window's span is
        # the (monotonic) sum of its wall slices — no wall-clock arithmetic
        span_s = sum(s["wall_ms"] for s in window) / 1000.0
        window_tokens = sum(s["tokens"] for s in dispatch)
        kv_last = next(
            (s["kv_used"] for s in reversed(window) if s["kv_used"] is not None),
            None,
        )
        out: dict[str, Any] = {
            "capacity": self.capacity,
            "recorded": self.recorded,
            "dropped": self.dropped,
            "totals": {
                "wall_ms": round(self.wall_ms, 3),
                "device_ms": round(self.device_ms, 3),
                "host_ms": round(self.host_ms, 3),
                "host_overlapped_ms": round(self.host_overlapped_ms, 3),
                "stall_ms": round(self.stall_ms, 3),
                "tokens": self.tokens,
                "steps_by_phase": dict(self.steps_by_phase),
                "stall_s_by_reason": {
                    k: round(v, 4) for k, v in self.stall_s_by_reason.items()
                },
                "blocked_s_by_reason": {
                    k: round(v, 4)
                    for k, v in self.blocked_s_by_reason.items()
                },
                "recompiles": self.recompiles,
                "events_by_type": dict(self.events_by_type),
                "spec_accepted": self.spec_accepted,
                "spec_rejected": self.spec_rejected,
                "prefill_ahead_share": self.prefill_ahead_share,
                "prefill_rows_mean": self.prefill_rows_mean,
                "gap_ms": round(self.gap_ms, 3),
                "program_ms_by_phase": {
                    k: round(v, 3)
                    for k, v in self.program_ms_by_phase.items()
                },
                "completions_seen_by": dict(self.completions_seen_by),
                "resume_lag_ms": round(self.resume_lag_ms, 3),
            },
            "window": {
                "samples": len(window),
                "span_s": round(span_s, 3),
                "tokens": window_tokens,
                "tok_s": round(window_tokens / span_s, 1) if span_s else None,
                "step_ms_p50": _pct(walls, 0.50),
                "step_ms_p95": _pct(walls, 0.95),
                "host_overhead_ms_p50": _pct(hosts, 0.50),
                # the pipelined-loop naming of the same split: exposed =
                # host_ms (kept under its legacy key above for old
                # consumers), overlapped = host work under device shadow
                "host_exposed_ms_p50": _pct(hosts, 0.50),
                "host_overlapped_ms_p50": _pct(overlaps, 0.50),
                "overlap_ratio": overlap_ratio,
                "device_ms_p50": _pct(devices, 0.50),
                "queue_depth_p95": _pct(queue_depths, 0.95),
                "occupancy_mean": (
                    round(sum(s["occupancy"] for s in dispatch) / len(dispatch), 2)
                    if dispatch
                    else None
                ),
                "kv_used_ratio_last": kv_last,
            },
        }
        return out


def bench_rollup(summary: dict[str, Any]) -> dict[str, Any]:
    """The subset of a flight summary a bench record snapshots (BENCH_r06
    keys — enough for ``engine_top --analyze`` to decompose a run)."""
    totals = summary.get("totals", {})
    window = summary.get("window", {})
    return {
        "host_overhead_ms_p50": window.get("host_overhead_ms_p50"),
        "host_exposed_ms_p50": window.get("host_exposed_ms_p50"),
        "overlap_ratio": window.get("overlap_ratio"),
        "step_ms_p50": window.get("step_ms_p50"),
        "stall_s_by_reason": totals.get("stall_s_by_reason"),
        "blocked_s_by_reason": totals.get("blocked_s_by_reason"),
        "queue_depth_p95": window.get("queue_depth_p95"),
        "recompile_count": totals.get("recompiles"),
        "totals": {
            k: totals.get(k)
            for k in (
                "wall_ms",
                "device_ms",
                "host_ms",
                "host_overlapped_ms",
                "stall_ms",
                "tokens",
                "steps_by_phase",
            )
        },
    }
