"""The device's own clock, read from the flight samples of the whole window.

The engine stamps every program it hands to the device when the jitted call
returns and where the program's completion is first seen: by the dispatch
thread where it waits for the result, or by a watcher thread that waits on
every result in the device's order (``langstream_tpu/serving/flight.py``
``DispatchClock``). Each dispatch's flight sample then carries

- ``gap_ms``: the device stood with nothing queued before this program;
- ``program_ms``: the program's own time on the device;
- ``seen_by``: ``"watch"`` or ``"fetch"``, who stamped the completion.

The two times tile the window, with the profiler off. A program before this
clock had a watcher wrote ``gap_ms`` / ``program_ms`` too, as the dispatch
thread's lower and upper bound: only samples that carry ``seen_by`` are read
here (:func:`clocked`), so a parent commit's bounds are never taken for the
device's figures and its readers give nothing.

In a traced run both clocks exist. The watcher's wait is the host span
``dev.watch`` (with the dispatch's ``seq``; outside the ``ls.`` prefix, so
``lib/hosttrace.py`` attributes no idle time to it), the dispatch thread's
are ``ls.decode.wait`` / ``ls.prefill.wait``; a span ends where the stamp was
taken. :func:`traced` pairs each stamp with the end of its own program's run
on the device's ``XLA Modules`` line: how LATE the clock is (it needs the GIL
to stamp), and what it and the trace make of the same stretch.
"""

from __future__ import annotations

import bisect
import json

from lib import hosttrace, stats, xplane

WATCH_SPAN = "dev.watch"
#: the dispatch thread's blocking waits, by the phase of their program
WAIT_SPANS = {"ls.decode.wait": "decode", "ls.prefill.wait": "prefill"}
#: a part of the program's name on the ``XLA Modules`` line, by phase
PROGRAMS = {"decode": "decode_chunk", "prefill": "prefill"}
#: a stamp takes the run of its phase that ended last before it, unless
#: that was longer ago (``lib/roofline_latent.py`` ``paired_prefills``); the
#: two timelines may still disagree by ``SKEW_NS`` after the correction
LATE_NS = 50e6
SKEW_NS = 2e6
#: the most the two timelines are taken to disagree by (``hosttrace``'s own
#: window: ``SKEW_WINDOW_NS``)
SKEW_MAX_NS = 5e6


def clocked(obs: dict, phase: str | None = None) -> list[dict]:
    """The window's dispatch samples stamped by the device's clock (those
    that carry ``seen_by``), of one phase or of all."""
    return [s for s in obs.get("samples") or []
            if s.get("seen_by") and s.get("program_ms") is not None
            and s.get("gap_ms") is not None
            and (phase is None or s.get("phase") == phase)]


def stamps(profile) -> dict[int, dict[str, float]]:
    """``{seq: {"watch" | "fetch": end_ns}}`` of the spans that end at a
    stamp of the clock."""
    out: dict[int, dict[str, float]] = {}
    for plane in profile.planes:
        if plane.name != hosttrace.HOST_PLANE:
            continue
        for line in plane.lines:
            for start, dur, name, meta in xplane._events(line):
                by = ("watch" if name == WATCH_SPAN
                      else "fetch" if name in WAIT_SPANS else None)
                if by is None:
                    continue
                try:
                    seq = int(meta["seq"])
                except (KeyError, TypeError, ValueError):
                    continue
                out.setdefault(seq, {})[by] = start + dur
    return out


def runs_and_gaps(profile) -> tuple[list, list]:
    """``[(start_ns, end_ns, phase)]`` of the decode and prefill program
    runs of the first device that ran any, in the device's order, and that
    device's idle gaps (``hosttrace.device_gaps``): on the device's own
    timeline, not yet moved."""
    for plane in xplane.device_planes(profile):
        lines = {line.name: line for line in plane.lines}
        if xplane.MODULES_LINE not in lines:
            continue
        runs = []
        for start, dur, name, _ in xplane._events(lines[xplane.MODULES_LINE]):
            program = xplane.program_name(name)
            for phase, part in PROGRAMS.items():
                if part in program:
                    runs.append((start, start + dur, phase))
        if runs:
            return sorted(runs), hosttrace.device_gaps(plane)
    return [], []


def align(stamped: dict[int, float], phases: dict[int, str],
          runs: list) -> dict[int, float] | None:
    """``{seq: end_ns of its own run}`` by ORDER: the device runs programs
    in the order of their ``seq``, so dispatch ``first + i`` ran as
    ``runs[k + i]`` for one ``k``. That ``k`` is the one under which every
    phase agrees and all the stamps but a few lie within ``SKEW_MAX_NS``
    before and ``LATE_NS`` after their run's end: as many as can, and among
    equals the ``k`` whose distances spread least. The few (two, or a
    quarter of the stamps if that is fewer) are a run the trace began
    inside, which is not on the line, and a decode chunk left pending that
    the dispatch thread closed inside a prefill's wait while the watcher's
    span began before the trace: its own ``ls.decode.wait`` ends where it
    was fetched, long after it ended; they stay unpaired. It asks nothing of
    how the two timelines stand to each other beyond those bounds. None
    where no ``k`` does."""
    seqs = sorted(stamped)
    if not seqs or not runs:
        return None
    first = seqs[0]
    best = None
    for k in range(-(seqs[-1] - first), len(runs)):
        ends, ok = {}, True
        for q in range(first, seqs[-1] + 1):
            i = k + q - first
            if not 0 <= i < len(runs):
                continue
            if q in phases and phases[q] != runs[i][2]:
                ok = False
                break
            if q in stamped and \
                    -SKEW_MAX_NS <= stamped[q] - runs[i][1] <= LATE_NS:
                ends[q] = runs[i][1]
        off = [stamped[q] - end for q, end in ends.items()]
        if not ok or not off or len(off) < len(seqs) - min(2, len(seqs) // 4):
            continue
        score = (-len(off), max(off) - min(off))
        if best is None or score < best[0]:
            best = (score, ends)
    return best[1] if best else None


def pair(profile, samples: list[dict], skew_ns: float,
         slack_ns: float | None = None) -> dict | None:
    """Each traced stamp against its program's run, and both clocks'
    account of the stretch between the first and the last traced stamp:

    - ``late_ms``: stamp less the end of its own run, less the clocks'
      disagreement, one a paired dispatch. A stamp's own run is found by
      order (:func:`align`); where no order fits, it is the run of its phase
      that ended last before it (up to ``slack_ns`` after it, at most
      ``LATE_NS`` before), with the device's timeline moved by ``skew_ns``.
      The disagreement is ``skew_ns`` (``hosttrace.clock_skew_ns``) or the
      smallest stamp-less-end of the trace where that is smaller: no
      completion is seen before it happened, so the device's clock cannot
      read earlier than that (``skew_used_ms``);
    - ``clock_idle_ms`` / ``clock_busy_ms``: ``gap_ms`` / ``program_ms`` of
      the samples after the first paired one up to the last (by the tiling:
      from the first one's stamp to the last one's);
    - ``trace_idle_between_ms`` / ``trace_idle_inside_ms``: the device's
      idle gaps inside that stretch, between two programs (what the clock
      can see) and inside one (what it cannot).

    None where the trace holds no stamp of a clocked sample."""
    slack_ns = SKEW_NS if slack_ns is None else slack_ns
    by_seq = {s["dispatch"]: s for s in samples
              if s.get("dispatch") is not None}
    stamped: dict[int, float] = {}
    for seq, seen in stamps(profile).items():
        if seq not in by_seq:
            continue
        # the stamp that stood: the watcher's span where it won; else the
        # fetch's, or the watcher's where it ended sooner (a program the
        # dispatch thread closed inside another program's wait)
        stamp = (seen.get("watch") if by_seq[seq]["seen_by"] == "watch"
                 else min(seen.values()))
        if stamp is not None:
            stamped[seq] = stamp
    runs, gaps = runs_and_gaps(profile)
    ends = align(stamped, {q: s.get("phase") for q, s in by_seq.items()}, runs)
    by_order = ends is not None
    if ends is None:            # by time, on the moved timeline
        ends = {}
        for seq, stamp in stamped.items():
            of_phase = [end for _, end, phase in runs
                        if phase == by_seq[seq].get("phase")]
            i = bisect.bisect_right(of_phase, stamp - skew_ns + slack_ns) - 1
            if i >= 0 and stamp - skew_ns - of_phase[i] <= LATE_NS:
                ends[seq] = of_phase[i]
    if not ends:
        return None
    skew = min(skew_ns, min(stamped[q] - end for q, end in ends.items()))
    paired = [(q, stamped[q], stamped[q] - ends[q] - skew) for q in sorted(ends)]
    (first, t0, _), (last, t1, _) = paired[0], paired[-1]
    between = [by_seq[q] for q in range(first + 1, last + 1) if q in by_seq]
    idle = {"between": 0.0, "inside": 0.0}
    for a, b, label in gaps:
        kind = "inside" if label.startswith("inside_") else "between"
        idle[kind] += max(0.0, min(b + skew, t1) - max(a + skew, t0))
    late_by: dict[str, list[float]] = {}
    for seq, _, late in paired:
        late_by.setdefault(by_seq[seq]["seen_by"], []).append(late / 1e6)
    return {
        "late_ms": [late / 1e6 for _, _, late in paired],
        "late_ms_by": late_by,
        "paired_by": "order" if by_order else "time",
        "skew_host_ms": skew_ns / 1e6,
        "skew_used_ms": skew / 1e6,
        "stretch_ms": (t1 - t0) / 1e6,
        "dispatches": last - first,
        "missing": last - first - len(between),
        "clock_idle_ms": sum(s.get("gap_ms") or 0.0 for s in between),
        "clock_busy_ms": sum(s.get("program_ms") or 0.0 for s in between),
        "trace_idle_between_ms": idle["between"] / 1e6,
        "trace_idle_inside_ms": idle["inside"] / 1e6,
        "seen_by": {by: len(v) for by, v in late_by.items()},
    }


def traced(obs: dict) -> dict | None:
    """:func:`pair` of this run's trace, computed once and kept in ``obs``
    (and said once beside the run's other notes); None for a run
    that was not traced, whose trace is not found, or whose samples carry no
    ``seen_by`` (a parent commit)."""
    if "devclock" not in obs:
        obs["devclock"] = None
        samples = clocked(obs)
        reduced = hosttrace.of(obs) if samples else None
        path = hosttrace.find_trace() if reduced else None
        if path:
            obs["devclock"] = pair(
                xplane.load(path), samples, reduced["clock_skew_ns"])
        if obs["devclock"]:
            read = obs["devclock"]
            print("[bench] the device's clock beside the trace: " + json.dumps({
                **{k: v for k, v in read.items()
                   if k not in ("late_ms", "late_ms_by")},
                "stamps": len(read["late_ms"]),
                "late_ms_p50": stats.stat(read["late_ms"], "p50"),
                "late_ms_p95": stats.stat(read["late_ms"], "p95"),
                "late_ms_max": max(read["late_ms"]),
                "late_ms_p95_by": {by: stats.stat(v, "p95")
                                   for by, v in read["late_ms_by"].items()},
            }), flush=True)
    return obs["devclock"]
