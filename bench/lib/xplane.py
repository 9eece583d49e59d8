"""From a profiler trace to numbers: the one reduction every PR is read by.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData`` reads
it with nothing but JAX. A device plane (``/device:TPU:n``) carries a line of
executed programs (``XLA Modules``: one event a dispatch, named
``jit__decode_chunk(<id>)``) and a line of the operations inside them
(``XLA Ops``: one event an executed HLO op, loop bodies once an iteration).

:func:`reduce` gives, per device and averaged over the devices used:

- ``busy_s``: the union of the intervals in which an operation ran;
- per program (module name without its id): runs, total and each duration;
- per operation (its HLO name and result shape; loop and call containers are
  left out, their time being their children's): total seconds and calls,
  with the program it ran in; and per program run the calls of its most
  frequent operation, from which a reader tells how many loop iterations
  the run made (a run cut by the start of the trace shows only what ran
  inside it);
- idle gaps between operations, named by the programs on either side
  (``inside_<program>`` when both neighbours belong to one run).

Nothing here knows a model or a cell; the layer-metric readers do.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_ID_SUFFIX = re.compile(r"\(\d+\)$")
_SHAPE_STATS = ("shape_with_layout", "shape")
# the TPU runtime names an op event by its whole HLO instruction:
#   %fusion.269 = bf16[64,4096]{1,0:T(8,128)(2,1)S(1)} fusion(...)
_HLO_TEXT = re.compile(r"^%(?P<name>[\w.\-]+) = (?P<shape>\(?[a-z0-9]+\[[0-9,]*\])?")
# ops that only contain other ops of the same line (their time is their
# children's): counted as busy time, never as an operation of their own
CONTAINERS = ("while", "conditional", "call")


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def program_name(module_event_name: str) -> str:
    """``jit__decode_chunk(1234567)`` → ``jit__decode_chunk``."""
    return _ID_SUFFIX.sub("", module_event_name.strip())


def _sanitize(shape: str) -> str:
    return "_" + re.sub(r"[^A-Za-z0-9]+", "_", shape).strip("_") + "_"


def op_name(event_name: str, stats: dict) -> str:
    """A short, comparable name: the HLO op's own name and the shape of
    its (first) result, ``fusion.269_bf16_64_4096_``."""
    m = _HLO_TEXT.match(event_name)
    if m:
        return m.group("name") + (_sanitize(m.group("shape")) if m.group("shape") else "")
    return event_name + _shape_suffix(stats)


def is_container(short_name: str) -> bool:
    return short_name.split(".")[0].split("_")[0] in CONTAINERS


def _shape_suffix(stats: dict) -> str:
    for key in _SHAPE_STATS:
        value = stats.get(key)
        if isinstance(value, str) and value:
            return _sanitize(value.split("{")[0])
    return ""


def _events(line) -> list[tuple[float, float, str, dict]]:
    out = []
    for e in line.events:
        stats = {}
        try:
            stats = {k: v for k, v in e.stats}
        except Exception:  # a stat the binding cannot convert is no reason to fail
            pass
        out.append((float(e.start_ns), float(e.duration_ns), e.name, stats))
    out.sort(key=lambda t: t[0])
    return out


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Total length of the union of (start, end) intervals, any unit in,
    the same unit out."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def device_planes(profile) -> list:
    planes = [p for p in profile.planes if p.name.startswith("/device:")]
    return sorted(planes, key=lambda p: p.name)


def reduce_plane(plane) -> dict | None:
    lines = {line.name: line for line in plane.lines}
    if OPS_LINE not in lines:
        return None
    ops = _events(lines[OPS_LINE])
    modules = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
    mod_starts = [m[0] for m in modules]

    def run_of(t: float) -> int | None:
        """Index of the program run that contains time ``t``."""
        i = bisect.bisect_right(mod_starts, t) - 1
        if i >= 0 and t <= modules[i][0] + modules[i][1]:
            return i
        return None

    programs: dict[str, dict] = defaultdict(
        lambda: {"runs": 0, "total_s": 0.0, "durations_s": [], "op_counts": []}
    )
    run_op_counts: list[dict] = [defaultdict(int) for _ in modules]
    for start, dur, name, _ in modules:
        p = programs[program_name(name)]
        p["runs"] += 1
        p["total_s"] += dur / 1e9
        p["durations_s"].append(dur / 1e9)

    op_totals: dict[tuple[str, str], dict] = defaultdict(
        lambda: {"total_s": 0.0, "calls": 0}
    )
    intervals = []
    for start, dur, name, stats in ops:
        if dur <= 0:
            continue
        intervals.append((start, start + dur))
        short = op_name(name, stats)
        if is_container(short):
            continue
        run = run_of(start)
        prog = program_name(modules[run][2]) if run is not None else ""
        if run is not None:
            run_op_counts[run][short] += 1
        entry = op_totals[(short, prog)]
        entry["total_s"] += dur / 1e9
        entry["calls"] += 1
    for (start, dur, name, _), counts in zip(modules, run_op_counts):
        programs[program_name(name)]["op_counts"].append(
            max(counts.values()) if counts else 0
        )

    # idle gaps between the merged busy intervals, named by their neighbours
    gaps: dict[str, dict] = defaultdict(lambda: {"total_s": 0.0, "count": 0})
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    for (_, end_a), (start_b, _) in zip(merged, merged[1:]):
        a, b = run_of(end_a - 1e-3), run_of(start_b)
        if a is not None and a == b:
            label = f"inside_{program_name(modules[a][2])}"
        else:
            na = program_name(modules[a][2]) if a is not None else "none"
            nb = program_name(modules[b][2]) if b is not None else "none"
            label = f"{na}_-_{nb}"
        gaps[label]["total_s"] += (start_b - end_a) / 1e9
        gaps[label]["count"] += 1

    return {
        "device": plane.name,
        "busy_s": union_seconds(intervals) / 1e9,
        "span_s": ((merged[-1][1] - merged[0][0]) / 1e9) if merged else 0.0,
        "programs": {k: dict(v) for k, v in programs.items()},
        "ops": [
            {"name": name, "program": prog, **v}
            for (name, prog), v in op_totals.items()
        ],
        "gaps": {k: dict(v) for k, v in gaps.items()},
    }


def reduce(profile, window_s: float | None = None) -> dict:
    """The whole trace. ``window_s`` is what the host's clock read between
    the profiler's start returning and its stop being called. The profiler
    goes on collecting for a moment inside its stop, so on a device that
    never idles the operations span more than that: the traced window is
    the longer of the two, and ``busy_s`` can never pass it."""
    planes = [r for r in map(reduce_plane, device_planes(profile)) if r]
    if not planes:
        return {"devices": 0, "busy_s": 0.0, "window_s": window_s or 0.0,
                "planes": []}
    busy = sum(p["busy_s"] for p in planes) / len(planes)
    window = max(window_s or 0.0, max(p["span_s"] for p in planes))
    return {
        "devices": len(planes), "busy_s": busy, "window_s": window,
        "planes": planes,
    }


def top_ops(reduced: dict, n: int = 10) -> list[list]:
    """[name, seconds] of the operations that took most device time, over
    all devices (a name is an op with its shape where the trace gives it)."""
    totals: dict[str, float] = defaultdict(float)
    for plane in reduced["planes"]:
        for op in plane["ops"]:
            totals[op["name"]] += op["total_s"]
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, seconds] for name, seconds in top]


def top_gaps(reduced: dict, n: int = 10) -> list[list]:
    """[label__x<count>, seconds] of the idle gaps that took most time."""
    totals: dict[str, list] = defaultdict(lambda: [0.0, 0])
    for plane in reduced["planes"]:
        for label, gap in plane["gaps"].items():
            totals[label][0] += gap["total_s"]
            totals[label][1] += gap["count"]
    top = sorted(totals.items(), key=lambda kv: -kv[1][0])[:n]
    return [[f"{label}__x{count}", seconds] for label, (seconds, count) in top]


def program(reduced: dict, name_part: str) -> dict:
    """Runs of every program whose name contains ``name_part``, pooled over
    devices: ``{"runs", "total_s", "durations_s", "op_counts"}``."""
    out = {"runs": 0, "total_s": 0.0, "durations_s": [], "op_counts": []}
    for plane in reduced["planes"]:
        for name, p in plane["programs"].items():
            if name_part in name:
                out["runs"] += p["runs"]
                out["total_s"] += p["total_s"]
                out["durations_s"] += p["durations_s"]
                out["op_counts"] += p["op_counts"]
    return out


def ops_in(reduced: dict, program_part: str, name_pattern: str) -> dict:
    """Total seconds and calls of the operations inside programs whose name
    contains ``program_part`` and whose own name matches ``name_pattern``."""
    rx = re.compile(name_pattern)
    out = {"total_s": 0.0, "calls": 0, "names": set()}
    for plane in reduced["planes"]:
        for op in plane["ops"]:
            if program_part in op["program"] and rx.search(op["name"]):
                out["total_s"] += op["total_s"]
                out["calls"] += op["calls"]
                out["names"].add(op["name"])
    out["names"] = sorted(out["names"])
    return out
