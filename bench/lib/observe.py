"""From what a run observed to the numbers it reports.

A metric is a file: ``<name>.json`` with a ``read`` object that names one of
the harness's observations, or ``<name>.py`` with ``read(obs)``. A reader
that finds nothing to read gives None and the metric is left out.

Observations (``obs``):

- ``requests``: the measured requests as the load generator recorded them,
  with ``ttft_ms``, ``tpot_ms``, ``late_ms``, ``hop_ms``, ``queue_wait_ms``,
  ``prefill_ms``, ``tokens`` added here;
- ``samples``: the engine's flight-recorder samples of the window;
- ``polls``: the harness's polls of the block manager during the window;
- ``counters``: numbers the harness computed over the window;
- ``trace``: the reduced profiler trace, when the run was traced.
"""

from __future__ import annotations

import importlib.util
import json
import os

from lib import stats


def annotate(requests: list[dict], loop: str) -> None:
    """Per request, the times a metric can ask for (milliseconds)."""
    for r in requests:
        start = r.get("due") if loop == "open" else r.get("sent")
        if r.get("first") is not None and start is not None:
            r["ttft_ms"] = 1e3 * (r["first"] - start)
        if r.get("due") is not None and r.get("sent") is not None:
            r["late_ms"] = 1e3 * (r["sent"] - r["due"])
        tokens = r.get("tokens")
        if tokens and tokens > 1 and r.get("last", 0) > r.get("first", 0):
            r["tpot_ms"] = 1e3 * (r["last"] - r["first"]) / (tokens - 1)
        if r.get("engine_ttft_ms") is not None and r.get("first") is not None \
                and r.get("sent") is not None:
            # client's first frame since it sent, less the engine's own
            r["hop_ms"] = 1e3 * (r["first"] - r["sent"]) - r["engine_ttft_ms"]


def tokens_inside(requests: list[dict], opened: float, closed: float) -> float:
    """Output tokens made inside [opened, closed]: a request's first token
    at its first frame, the others spread evenly from its first frame to its
    last. Completions come in lumps at the ends of the engine's fused chunks
    (seconds apart), so counting whole requests by when they completed makes
    a window's count swing by a lump; this is the same work over the same
    time without the lumps. Wants every request that streamed inside the
    window followed to its end."""
    total = 0.0
    for r in requests:
        tokens, first, last = r.get("tokens"), r.get("first"), r.get("last")
        if not tokens or first is None or last is None:
            continue
        if opened <= first <= closed:
            total += 1.0
        if tokens > 1 and last > first:
            overlap = min(last, closed) - max(first, opened)
            if overlap > 0:
                total += (tokens - 1) * overlap / (last - first)
    return total


def read_spec(spec: dict, obs: dict):
    source = spec["from"]
    if source == "counters":
        return obs["counters"].get(spec["field"])
    rows = obs.get(source) or []
    if "phase" in spec:
        rows = [r for r in rows if r.get("phase") == spec["phase"]]
    values = [r[spec["field"]] for r in rows if r.get(spec["field"]) is not None]
    value = stats.stat(values, spec["stat"])
    if value is not None and "scale" in spec:
        value *= spec["scale"]
    return value


def find(kind: str, name: str, roots: list[str]) -> str | None:
    """``<root>/<kind>/<name>.json`` or ``.py`` in the first root that has it."""
    for root in roots:
        for ext in (".json", ".py"):
            path = os.path.join(root, kind, name + ext)
            if os.path.exists(path):
                return path
    return None


def load_metric(path: str) -> dict:
    """``{"unit", ..., "read": callable(obs)}`` of one metric file."""
    if path.endswith(".json"):
        with open(path) as f:
            meta = json.load(f)
        spec = meta["read"]
        return {**meta, "read": lambda obs: read_spec(spec, obs)}
    name = os.path.splitext(os.path.basename(path))[0]
    module_spec = importlib.util.spec_from_file_location(f"_metric_{name}", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return {**module.META, "read": module.read}


def report(names: list[tuple[str, str]], kind: str, roots: list[str],
           obs: dict) -> dict:
    """``{name: {"value", "unit"}}`` for (name, unit) pairs; a metric whose
    reader gives nothing is left out."""
    out = {}
    for name, unit in names:
        path = find(kind, name, roots)
        if path is None:
            raise FileNotFoundError(
                f"no {kind}/{name}.json or .py under {roots}"
            )
        value = load_metric(path)["read"](obs)
        if value is not None:
            out[name] = {"value": float(value), "unit": unit}
    return out
