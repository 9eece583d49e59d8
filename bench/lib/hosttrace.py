"""The engine loop's host spans against the device's idle gaps, and device
time by scope inside the decode program: what a profile says once the
program names its own work.

The engine opens ``jax.profiler.TraceAnnotation`` spans at its dispatch
boundaries (``langstream_tpu/serving/flight.py`` ``SPANS``: ``ls.admit``,
``ls.prefill.pack|dispatch|fetch|emit``,
``ls.decode.prepare|dispatch|fetch|process|emit``, ``ls.idle``), and its
jitted programs carry ``jax.named_scope`` names at the layer body's seams
(``SCOPES``). Under a profiler session both land in the same ``.xplane.pb``
as the device's operations, on one clock. :func:`reduce` gives:

- ``spans``: every ``ls.*`` span of every host thread;
- ``idle``: each idle gap of the device (between its merged operation
  intervals, as ``lib/xplane.py`` finds them) split among the innermost
  ``ls.*`` span covering each instant, the rest under ``none``; by span
  name, and by the programs on either side of the gap. The two timelines
  can disagree by a millisecond; ``clock_skew_ns`` is how far, found from
  causality (:func:`clock_skew_ns`), and the gaps are moved by it first;
- ``scopes``: device seconds of the operations inside the decode-chunk
  program by the scope their HLO metadata names, the rest by operation.

A program that opens no spans and names no scopes (a parent commit) gives
empty tables; the readers built on this then give nothing and never raise.

``obs`` carries only the reduced device planes and no path, so :func:`of`
finds the run's trace itself: the newest ``*.xplane.pb`` under the
checkout's ``.bench_work/*/trace``.

As a script, on a directory holding a trace (an operator's after
``/profile/stop``), it prints the two tables::

    python3 bench/lib/hosttrace.py <dir>
"""

from __future__ import annotations

import bisect
import glob
import os
import sys
from collections import defaultdict

if __package__ in (None, ""):  # run as a script: make ``lib`` importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import xplane

SPAN_PREFIX = "ls."
HOST_PLANE = "/host:CPU"
DECODE = "decode_chunk"
NONE = "none"
#: ``jax.named_scope`` names of the layer body's seams
#: (langstream_tpu/models/llama_paged.py, llama.py)
SCOPES = ("embed", "attn_qkv", "kv_read", "flash", "attn_out", "ffn",
          "lm_head", "sample")
#: the plane whose event metadata hold each executed program's ``HloProto``
#: (stat ``Hlo Proto``), under the name its runs have on the device's
#: ``XLA Modules`` line
HLO_PLANE = "/host:metadata"
#: the spans whose first statement is the engine's key split
#: (``serving/engine.py`` ``_split_key``: ``jax.random.split``, which runs
#: as the program ``jit__threefry_split``), for :func:`clock_skew_ns`
SPLIT_SPANS = ("ls.prefill.dispatch", "ls.decode.prepare")
SPLIT_PROGRAM = "threefry_split"
SKEW_WINDOW_NS = 5e6
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def find_trace(root: str | None = None) -> str | None:
    """The newest ``*.xplane.pb`` under ``root`` (any depth); without a
    root, under the checkout's ``.bench_work/*/trace``."""
    pattern = (os.path.join(root, "**", "*.xplane.pb") if root else
               os.path.join(ROOT, ".bench_work", "*", "trace", "**", "*.xplane.pb"))
    paths = glob.glob(pattern, recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


# -- (a) host spans ------------------------------------------------------


def host_spans(profile) -> list[dict]:
    """Every ``ls.*`` span of every host thread, by start:
    ``{"name", "thread", "start_ns", "end_ns", "meta"}``."""
    spans = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for thread, line in enumerate(plane.lines):
            for start, dur, name, stats in xplane._events(line):
                if name.startswith(SPAN_PREFIX):
                    spans.append({"name": name, "thread": thread,
                                  "start_ns": start, "end_ns": start + dur,
                                  "meta": stats})
    spans.sort(key=lambda s: s["start_ns"])
    return spans


# -- (b) idle gaps, by host span -----------------------------------------


def _run_at(modules: list, starts: list[float], t: float) -> int | None:
    """Index of the program run (an ``XLA Modules`` event) that holds
    instant ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= modules[i][0] + modules[i][1]:
        return i
    return None


def device_gaps(plane) -> list[tuple[float, float, str]]:
    """``(start_ns, end_ns, label)`` of the idle gaps of one device plane:
    between its merged operation intervals, labelled by the programs on
    either side as ``lib/xplane.py`` labels them."""
    lines = {line.name: line for line in plane.lines}
    if xplane.OPS_LINE not in lines:
        return []
    modules = (xplane._events(lines[xplane.MODULES_LINE])
               if xplane.MODULES_LINE in lines else [])
    starts = [m[0] for m in modules]
    merged: list[list[float]] = []
    for start, dur, _, _ in xplane._events(lines[xplane.OPS_LINE]):
        if dur <= 0:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    gaps = []
    for (_, end_a), (start_b, _) in zip(merged, merged[1:]):
        a = _run_at(modules, starts, end_a - 1e-3)
        b = _run_at(modules, starts, start_b)
        if a is not None and a == b:
            label = f"inside_{xplane.program_name(modules[a][2])}"
        else:
            na = xplane.program_name(modules[a][2]) if a is not None else NONE
            nb = xplane.program_name(modules[b][2]) if b is not None else NONE
            label = f"{na}_-_{nb}"
        gaps.append((end_a, start_b, label))
    return gaps


def clock_skew_ns(gaps: list[tuple[float, float, str]],
                  spans: list[dict]) -> float:
    """How far the device's timeline reads early against the host's, from
    causality: the key split the engine issues first thing inside a span of
    :data:`SPLIT_SPANS` runs at once on an idle device
    (``jit__threefry_split``, a few microseconds long), so a split program
    that ends an idle gap cannot have started before the span that issued
    it. The median, over such gaps, of span start minus program start; 0
    where the program starts inside its span (the clocks agree) or the trace
    holds no such pair. Two of the first three traces read this way on the
    v5e were 1.0-1.2 ms early."""
    starts = sorted(s["start_ns"] for s in spans if s["name"] in SPLIT_SPANS)
    early = []
    for _, end, label in gaps:
        if not label.endswith(f"_-_jit__{SPLIT_PROGRAM}") or not starts:
            continue
        i = bisect.bisect_left(starts, end)
        near = min(starts[max(0, i - 1):i + 1], key=lambda t: abs(t - end))
        if abs(near - end) < SKEW_WINDOW_NS:
            early.append(near - end)
    if not early:
        return 0.0
    early.sort()
    return max(0.0, early[len(early) // 2])


def split_gap(start: float, end: float,
              spans: list[dict]) -> list[tuple[float, float, str]]:
    """[start, end) cut into ``(from, to, span name)`` pieces: every instant
    goes to the innermost span covering it (the one that started last; on a
    tie the one that ends first), whatever thread it is on, and to ``none``
    where no span covers it. ``spans`` need only hold those that overlap."""
    cover = [s for s in spans if s["start_ns"] < end and s["end_ns"] > start]
    cuts = sorted({start, end}
                  | {s["start_ns"] for s in cover if start < s["start_ns"] < end}
                  | {s["end_ns"] for s in cover if start < s["end_ns"] < end})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        inner = [s for s in cover if s["start_ns"] <= mid < s["end_ns"]]
        name = (max(inner, key=lambda s: (s["start_ns"], -s["end_ns"]))["name"]
                if inner else NONE)
        out.append((a, b, name))
    return out


def attribute(gaps: list[tuple[float, float, str]], spans: list[dict]) -> dict:
    """Idle seconds by span name, and by gap label and span name. Time no
    span covers is also given by the spans that ended last before it and
    started first after it (``none_between``): the code that ran there."""
    by_span: dict[str, float] = defaultdict(float)
    by_gap: dict[str, dict] = defaultdict(
        lambda: {"count": 0, "total_s": 0.0, "by_span": defaultdict(float)})
    between: dict[str, float] = defaultdict(float)
    spans = sorted(spans, key=lambda s: s["start_ns"])
    starts = [s["start_ns"] for s in spans]
    ends = sorted((s["end_ns"], s["name"]) for s in spans)
    end_times = [e[0] for e in ends]
    # a span can only overlap a gap if it started before the gap's end; the
    # longest span bounds how far back to look
    longest = max((s["end_ns"] - s["start_ns"] for s in spans), default=0.0)
    for start, end, label in gaps:
        lo = bisect.bisect_left(starts, start - longest)
        hi = bisect.bisect_left(starts, end)
        row = by_gap[label]
        row["count"] += 1
        row["total_s"] += (end - start) / 1e9
        for a, b, name in split_gap(start, end, spans[lo:hi]):
            by_span[name] += (b - a) / 1e9
            row["by_span"][name] += (b - a) / 1e9
            if name == NONE:
                i = bisect.bisect_right(end_times, a) - 1
                k = bisect.bisect_left(starts, b)
                before = ends[i][1] if i >= 0 else NONE
                after = spans[k]["name"] if k < len(spans) else NONE
                between[f"{before}_-_{after}"] += (b - a) / 1e9
    idle = sum(by_span.values())
    return {
        "idle_s": idle,
        "attributed_s": idle - by_span.get(NONE, 0.0),
        "by_span": dict(by_span),
        "by_gap": {k: {**v, "by_span": dict(v["by_span"])}
                   for k, v in by_gap.items()},
        "none_between": dict(between),
    }


# -- (c) device time by scope --------------------------------------------


def _varint(buf, i: int) -> tuple[int, int]:
    """The varint at ``buf[i:]`` and the index after it."""
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message: an int for
    a varint or a fixed field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = int.from_bytes(buf[i:i + size], "little")
            i += size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def hlo_scopes(hlo_proto) -> dict[str, str]:
    """``{instruction name: scope}`` of one ``HloProto`` (field numbers:
    hlo_module 1; of a module: computations 3; of a computation:
    instructions 2; of an instruction: name 1, metadata 7 with op_name 2,
    id 35, operand_ids 36). An instruction's scope is the innermost of
    :data:`SCOPES` in its ``op_name``; one that has none — a copy the
    compiler put in, the layer scan's slice of its stacked inputs — takes
    the scope of the instructions that read its result, when they agree:
    the pool slice a ``paged_read`` call reads is part of ``kv_read``."""
    names, own, users = {}, {}, defaultdict(set)
    for n, w, module in _fields(hlo_proto):
        if n != 1 or w != 2:
            continue
        for n2, w2, computation in _fields(module):
            if n2 != 3 or w2 != 2:
                continue
            for n3, w3, instruction in _fields(computation):
                if n3 != 2 or w3 != 2:
                    continue
                name, op_name, ident, operands = "", "", None, []
                for n4, w4, value in _fields(instruction):
                    if n4 == 1 and w4 == 2:
                        name = _text(value)
                    elif n4 == 7 and w4 == 2:
                        op_name = next((_text(v) for k, kw, v in _fields(value)
                                        if k == 2 and kw == 2), "")
                    elif n4 == 35 and w4 == 0:
                        ident = value
                    elif n4 == 36 and w4 == 0:
                        operands.append(value)
                    elif n4 == 36 and w4 == 2:   # packed
                        operands += _varints(value)
                if ident is None:
                    continue
                names[ident] = name
                own[ident] = scope_of(op_name)
                for operand in operands:
                    users[operand].add(ident)
    scope = dict(own)
    for _ in range(8):  # through chains of copies, bitcasts, tuple elements
        changed = False
        for ident in names:
            if scope[ident] is None and users[ident]:
                read_by = {scope.get(u) for u in users[ident]}
                if len(read_by) == 1 and None not in read_by:
                    scope[ident] = read_by.pop()
                    changed = True
        if not changed:
            break
    return {names[i]: sc for i, sc in scope.items() if sc}


def _varints(buf) -> list[int]:
    """A packed repeated varint field."""
    out, i = [], 0
    while i < len(buf):
        value, i = _varint(buf, i)
        out.append(value)
    return out


def op_scopes(path: str) -> dict[str, dict[str, str]]:
    """``{program run name: {instruction name: scope}}`` for the programs
    whose HLO the trace file holds (:data:`HLO_PLANE`), read from the
    ``.xplane.pb`` itself with the few field numbers of ``XSpace`` this
    needs (planes 1; of a plane: name 2, event_metadata 4; of an event's
    metadata: name 2, stats 5; of a stat: bytes_value 6):
    ``jax.profiler.ProfileData`` hands out an event's own stats, not those
    of its metadata, and on the TPU runtime an op event's name is its HLO
    instruction without the ``metadata={op_name=...}`` tail."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, dict[str, str]] = {}
    for number, wire, plane in _fields(space):
        if number != 1 or wire != 2:
            continue
        name, programs = "", []
        for n, w, value in _fields(plane):
            if n == 2 and w == 2:
                name = _text(value)
            elif n == 4 and w == 2:      # map entry: key 1, XEventMetadata 2
                programs += [v for k, kw, v in _fields(value) if k == 2 and kw == 2]
        if name != HLO_PLANE:
            continue
        for program in programs:
            run_name, proto = "", None
            for n, w, value in _fields(program):
                if n == 2 and w == 2:
                    run_name = _text(value)
                elif n == 5 and w == 2:
                    proto = next((v for k, kw, v in _fields(value)
                                  if k == 6 and kw == 2), proto)
            if run_name and proto is not None:
                out[run_name] = hlo_scopes(proto)
    return out


def scope_of(path: str | None) -> str | None:
    """The innermost of :data:`SCOPES` in a name stack."""
    if not path:
        return None
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return None


def scope_seconds(plane, scopes: dict[str, dict[str, str]],
                  program_part: str = DECODE) -> dict:
    """Device seconds of the operations inside runs of the programs whose
    name contains ``program_part``, by scope (``scopes``:
    :func:`op_scopes`); operations under no scope by their own name.
    Containers (``while``, ``conditional``, ``call``) are left out as in
    ``lib/xplane.py``: their time is their children's."""
    lines = {line.name: line for line in plane.lines}
    if xplane.OPS_LINE not in lines or xplane.MODULES_LINE not in lines:
        return {"total_s": 0.0, "by_scope": {}, "unscoped": {}}
    modules = xplane._events(lines[xplane.MODULES_LINE])
    starts = [m[0] for m in modules]
    by_scope: dict[str, float] = defaultdict(float)
    unscoped: dict[str, float] = defaultdict(float)
    total = 0.0
    for start, dur, name, stats in xplane._events(lines[xplane.OPS_LINE]):
        i = _run_at(modules, starts, start)
        if dur <= 0 or i is None:
            continue
        if program_part not in xplane.program_name(modules[i][2]):
            continue
        short = xplane.op_name(name, stats)
        if xplane.is_container(short):
            continue
        total += dur / 1e9
        m = xplane._HLO_TEXT.match(name)
        scope = scopes.get(modules[i][2].strip(), {}).get(
            m.group("name") if m else name)
        if scope:
            by_scope[scope] += dur / 1e9
        else:
            unscoped[short] += dur / 1e9
    return {"total_s": total, "by_scope": dict(by_scope),
            "unscoped": dict(unscoped)}


# -- the whole trace -----------------------------------------------------


def reduce(profile, scopes: dict[str, dict[str, str]] | None = None) -> dict:
    """Spans, idle by span and device time by scope, pooled over the
    device planes of the trace. ``scopes`` is :func:`op_scopes` of the file
    the profile was read from; without it every operation is under no
    scope."""
    spans = host_spans(profile)
    gaps: list = []  # moved by the clocks' disagreement before the split
    pooled = {"total_s": 0.0, "by_scope": defaultdict(float),
              "unscoped": defaultdict(float)}
    for plane in xplane.device_planes(profile):
        gaps += device_gaps(plane)
        one = scope_seconds(plane, scopes or {})
        pooled["total_s"] += one["total_s"]
        for key in ("by_scope", "unscoped"):
            for name, seconds in one[key].items():
                pooled[key][name] += seconds
    skew = clock_skew_ns(gaps, spans)
    gaps = [(start + skew, end + skew, label) for start, end, label in gaps]
    return {
        "spans": spans,
        "clock_skew_ns": skew,
        "idle": attribute(gaps, spans),
        "scopes": {"total_s": pooled["total_s"],
                   "by_scope": dict(pooled["by_scope"]),
                   "unscoped": dict(pooled["unscoped"])},
    }


def of(obs: dict) -> dict | None:
    """The reduction of this run's trace, computed once and kept in
    ``obs["hosttrace"]``; None for a run that was not traced or whose
    trace is not found."""
    if "hosttrace" not in obs:
        obs["hosttrace"] = None
        path = find_trace() if obs.get("trace") else None
        if path:
            obs["hosttrace"] = reduce(xplane.load(path), op_scopes(path))
    return obs["hosttrace"]


def idle_under(obs: dict, prefix: str) -> float | None:
    """Idle milliseconds under the spans whose name starts with ``prefix``
    per traced second; None where the trace holds no span at all."""
    reduced = of(obs)
    window = (obs.get("trace") or {}).get("window_s")
    if not reduced or not reduced["spans"] or not window:
        return None
    seconds = sum(s for name, s in reduced["idle"]["by_span"].items()
                  if name.startswith(prefix))
    return 1e3 * seconds / window


def decode_steps(obs: dict) -> int:
    """Decode steps in the trace, as ``decode_dev_ms_step`` counts them."""
    import importlib

    runs = importlib.import_module("layer_metrics.decode_dev_ms_step").decode_runs(obs)
    return sum(steps for _, steps in runs)


# -- as a script ---------------------------------------------------------


def tables(reduced: dict) -> str:
    idle, scopes = reduced["idle"], reduced["scopes"]
    out = [f"host spans: {len(reduced['spans'])}; device idle "
           f"{idle['idle_s'] * 1e3:.3f} ms, under a span "
           f"{idle['attributed_s'] * 1e3:.3f} ms; the device's clock read "
           f"{reduced['clock_skew_ns'] / 1e3:.0f} us early (corrected)", "",
           "idle by host span (ms):"]
    for name, s in sorted(idle["by_span"].items(), key=lambda kv: -kv[1]):
        out.append(f"  {name:24s} {s * 1e3:10.3f}")
    out += ["", "idle by the programs on either side, split by span (ms):"]
    for label, row in sorted(idle["by_gap"].items(),
                             key=lambda kv: -kv[1]["total_s"])[:12]:
        split = ", ".join(f"{n} {s * 1e3:.3f}" for n, s in
                          sorted(row["by_span"].items(), key=lambda kv: -kv[1]))
        out.append(f"  {label} x{row['count']}: {row['total_s'] * 1e3:.3f} ({split})")
    if idle["none_between"]:
        out += ["", "idle under no span, by the span that ended before and "
                    "the one that started after (ms):"]
        for label, s in sorted(idle["none_between"].items(),
                               key=lambda kv: -kv[1])[:8]:
            out.append(f"  {label:48s} {s * 1e3:10.3f}")
    scoped = sum(scopes["by_scope"].values())
    out += ["", f"device time inside {DECODE} programs: "
                f"{scopes['total_s']:.6f} s, under a scope "
                f"{100 * scoped / scopes['total_s'] if scopes['total_s'] else 0:.2f}%"]
    for name, s in sorted(scopes["by_scope"].items(), key=lambda kv: -kv[1]):
        out.append(f"  {name:24s} {s:12.6f} s")
    for name, s in sorted(scopes["unscoped"].items(), key=lambda kv: -kv[1])[:10]:
        out.append(f"  (no scope) {name:40s} {s:12.6f} s")
    return "\n".join(out)


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print(__doc__.split("\n\n")[-1], file=sys.stderr)
        return 2
    path = argv[0] if argv[0].endswith(".pb") else find_trace(argv[0])
    if not path:
        print(f"no *.xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    print(path)
    print(tables(reduce(xplane.load(path), op_scopes(path))))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
