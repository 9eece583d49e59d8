"""Observability rules: clock and hot-loop discipline in ``serving/``.

OBS501 flags ``time.time()`` inside ``serving/`` and ``runtime/`` — the
packages whose timings feed spans, ``request_timings``, and the latency
histograms. Wall clock is not monotonic (NTP slews and steps it), so a
duration computed from it can be negative or wildly wrong exactly when an
operator is debugging a latency incident. Durations and deadlines there
must use ``time.monotonic()``; code that genuinely needs a wall-clock
*timestamp* (record ``timestamp`` fields, display anchoring) suppresses
with a reason, which is the audit trail that the use really is a
timestamp and never enters a subtraction.

OBS502/OBS503 keep the flight-recorder/metrics paths inside the engine
hot loops non-blocking — the observability-must-not-perturb contract:

- **OBS502**: a synchronous (``threading``) lock held across an ``await``
  in ``serving/``. The lock blocks the whole event-loop thread while the
  awaited dispatch runs, serializing every in-flight request behind it —
  exactly the host-overhead class the flight recorder exists to expose.
  ``async with`` on an ``asyncio.Lock`` is loop-native and stays silent.
- **OBS503**: file/socket/subprocess I/O (or ``print``) inside the engine
  hot-loop methods or anywhere in ``serving/flight.py``. Telemetry there
  must be an in-memory append; export belongs off-loop (the pod HTTP
  endpoint, the JSONL export thread in core/tracing.py).

OBS504 keeps the *health plane* wait-free — the dual of OBS503: where
telemetry must not perturb the engine, the health checker must not
DEPEND on it. A liveness probe that syncs the device
(``block_until_ready`` / ``device_get`` / ``.item()``) hangs exactly
when the device does — the one moment it must answer; a probe that
acquires a lock can queue behind the wedged dispatch holding it; and
blocking I/O stalls the probe on a resource unrelated to the verdict.
Scope: everything in ``serving/health.py`` (predicates and trackers),
the pod probe handlers (``_probe_healthz``/``_probe_ready`` in
``runtime/pod.py``), and the engine's health-surface methods
(``health``/``slo_status``/``_slo_record``/``_slo_record_latency``/
``_slo_emit``/``health_report``/``kick_warmups`` in ``serving/`` —
``_HEALTH_FUNCS_BY_FILE`` below is the authoritative list). Nested defs
are exempt everywhere: they are deferred work (warmup tasks, factories)
the probe only creates, never runs inline. The sanctioned pattern is
snapshot reads (``list(deque)``, attribute loads) plus arithmetic.

OBS505 extends the same wait-free contract to the *attribution plane*
(OBS504's shape, different scope): everything in
``serving/attribution.py`` (the program cost ledger and memory ledger —
writes are engine-loop container mutations, reads are poll-time
snapshots), the pod ``/attribution``/``/memory`` payload builders
(``_attribution_payload``/``_memory_payload`` in ``runtime/pod.py``),
and the engine's attribution surface
(``attribution_section``/``attribution_report``/``_memory_ledger``/
``device_bytes`` in ``serving/``). A ledger poll that syncs the device
or takes a lock hangs or queues exactly when an operator asks which
program owns the stall.

OBS506 extends it once more to the *request journey plane*: everything
in ``serving/journey.py`` (the per-request lifecycle ledger — writes
are GIL-atomic appends at the engine's flight-event sites, on the
dispatch path; reads are ``list()`` snapshots plus stitch arithmetic),
the pod ``/journey`` payload builder (``_journey_payload`` in
``runtime/pod.py``), and the dev-mode control-plane payload builder
(``journey`` in ``controlplane/server.py``). A journey write that took
a lock would serialize the engine loop behind readers; a journey read
that synced the device would hang exactly when an operator asks where
a wedged request's time went. (The k8s compute runtime's ``journey``
fan-in is excluded by scope: it is pod HTTP I/O by design and runs in
a worker thread, like the traces fan-in.)
"""

from __future__ import annotations

import ast
from typing import Iterator

from langstream_tpu.analysis.core import (
    Finding,
    Module,
    Rule,
    call_name,
    dotted_name,
)
from langstream_tpu.analysis.rules_async import _BLOCKING_CALLS

#: package prefixes where every timing is latency-bearing
_MEASURED_PATHS = (
    "langstream_tpu/serving/",
    "langstream_tpu/runtime/",
)


def _imports_bare_time_fn(mod: Module) -> bool:
    """True when the module does ``from time import time`` (so a bare
    ``time()`` call is the wall clock)."""
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ImportFrom) and node.module == "time":
            for alias in node.names:
                if alias.name == "time" and (alias.asname or "time") == "time":
                    return True
    return False


def check_wall_clock_in_measured_paths(mod: Module) -> Iterator[Finding]:
    if not any(p in mod.path for p in _MEASURED_PATHS):
        return
    bare_time = _imports_bare_time_fn(mod)
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        if name == "time.time" or (bare_time and name == "time"):
            yield mod.finding(
                "OBS501",
                node,
                "time.time() in a latency-measured package: wall clock is "
                "not monotonic, so durations built on it break under NTP "
                "adjustment — use time.monotonic() for spans/timings, or "
                "suppress with a reason if this really is a wall-clock "
                "timestamp",
            )


#: engine methods on the per-burst dispatch path: everything here runs on
#: the single engine event-loop thread between device dispatches, so one
#: blocking call stalls every active stream
_HOT_LOOP_FUNCS = {
    "_run_loop",
    "_decode_burst",
    "_speculative_burst",
    "_advance_prefills",
    "_admit",
    "_admit_select",
    "_admit_candidates",
    "_admit_return",
    "_admit_dispatch",
    "_admit_complete",
    "_dispatch_prefill",
    "_fetch_prefill",
    # the dispatch's clock (flight.py DispatchClock, resumed): the chunk's
    # blocking fetch on the dispatch thread, the loop's wait for it, the
    # ticket they write into, the delivery under ``ls.hop.deliver``
    "_fetch_chunk",
    "_await_chunk",
    "_drain_pending",
    "_ticket",
    "_deliver_chunk",
    "_process_chunk",
    "_emit_token",
    "_flush_emits",
    "_flight_record",
    "_flight_stall",
    "_note_compile",
    "_admission_stall",
}

#: the flight-recorder module is hot-path by contract: EVERY function in it
#: may be called from the engine loop or the dispatch thread ...
_RECORDER_MODULE = "langstream_tpu/serving/flight.py"

#: ... but for the device clock's watcher (``_watch``: a thread of its own
#: whose whole work is to block on a queue and on each program's result)
#: and the two methods that start and end it (``DispatchClock._start`` at
#: the first enqueue, ``close`` from the engine's ``close()``, after the
#: loop task and the dispatch thread are gone)
_RECORDER_OFF_PATH = {"_watch", "_start", "close"}

#: extra blocking calls beyond the async-rule table: stdout can block on a
#: full pipe, and open() is disk I/O wherever it runs
_EXTRA_BLOCKING = {"open", "print"}

_FILE_IO_ATTRS = {"read_text", "read_bytes", "write_text", "write_bytes"}


def _lockish(expr: ast.AST) -> bool:
    """True when a with-item context looks like a lock (name or call chain
    containing 'lock' — the same heuristic ASYNC205's guard check uses)."""
    if isinstance(expr, ast.Call):
        text = call_name(expr) or ""
    else:
        text = dotted_name(expr) or ""
    return "lock" in text.lower()


def check_lock_across_await(mod: Module) -> Iterator[Finding]:
    if "langstream_tpu/serving/" not in mod.path:
        return
    for node in ast.walk(mod.tree):
        # sync `with` only: `async with` on an asyncio.Lock yields the loop
        # while waiting and never blocks the thread
        if not isinstance(node, ast.With):
            continue
        if not any(_lockish(item.context_expr) for item in node.items):
            continue
        # awaits inside nested function defs aren't held under THIS with
        nested: set[int] = set()
        for inner in ast.walk(node):
            if isinstance(inner, (ast.FunctionDef, ast.AsyncFunctionDef)):
                nested.update(id(n) for n in ast.walk(inner))
        for inner in ast.walk(node):
            if isinstance(inner, ast.Await) and id(inner) not in nested:
                yield mod.finding(
                    "OBS502",
                    inner,
                    "threading lock held across await in serving/: the "
                    "event-loop thread blocks inside the lock while the "
                    "awaited work runs, serializing every in-flight "
                    "request — release before awaiting, or use an "
                    "asyncio.Lock with `async with`",
                )
                break


def _hot_functions(mod: Module) -> Iterator[ast.AST]:
    whole_module_hot = mod.path.endswith(_RECORDER_MODULE)
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if whole_module_hot and node.name in _RECORDER_OFF_PATH:
            continue
        if whole_module_hot or node.name in _HOT_LOOP_FUNCS:
            yield node


def check_blocking_in_hot_loop(mod: Module) -> Iterator[Finding]:
    if "langstream_tpu/serving/" not in mod.path:
        return
    for fn in _hot_functions(mod):
        # nested defs run elsewhere (the dispatch-thread `_run`/`_dispatch`
        # closures) — the engine loop never blocks on their bodies directly
        nested: set[int] = set()
        for inner in ast.walk(fn):
            if (
                isinstance(inner, (ast.FunctionDef, ast.AsyncFunctionDef))
                and inner is not fn
            ):
                nested.update(id(n) for n in ast.walk(inner))
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) or id(node) in nested:
                continue
            name = call_name(node)
            offender = None
            if name in _BLOCKING_CALLS or name in _EXTRA_BLOCKING:
                offender = f"{name}()"
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _FILE_IO_ATTRS
            ):
                offender = f".{node.func.attr}()"
            if offender is not None:
                yield mod.finding(
                    "OBS503",
                    node,
                    f"blocking call {offender} on the engine hot path "
                    f"(`{fn.name}`): flight-recorder/metrics work there "
                    f"must be an in-memory append — no file/socket/"
                    f"subprocess I/O, no stdout; export off-loop instead",
                )


#: the health-plane module: EVERY function in it is a health predicate or
#: tracker that probe handlers may run inline
_HEALTH_MODULE = "langstream_tpu/serving/health.py"

#: named health-plane functions outside that module: the pod probe
#: handlers and the engine's health-surface methods
_HEALTH_FUNCS_BY_FILE = {
    "langstream_tpu/runtime/pod.py": {"_probe_healthz", "_probe_ready"},
    "langstream_tpu/serving/": {
        "health",
        "slo_status",
        "_slo_record",
        "_slo_record_latency",
        "_slo_emit",
        "health_report",
        "kick_warmups",
    },
}

#: unambiguous device syncs (PERF701's table minus np.asarray/np.array —
#: health math runs numpy on host snapshots, and a probe has no device
#: arrays to convert; the sync spellings below have no host-only reading)
_DEVICE_SYNC_CALLS = {
    "jax.block_until_ready",
    "jax.device_get",
    "block_until_ready",
    "device_get",
}

_DEVICE_SYNC_ATTRS = {"block_until_ready", "item", "copy_to_host"}


def _scoped_functions(
    mod: Module,
    module_suffix: str,
    funcs_by_file: dict[str, set[str]],
) -> Iterator[ast.AST]:
    """The shared scope iterator behind OBS504/OBS505/OBS506: every
    top-level function of the plane's own module (``module_suffix``),
    plus the named functions of the other files in ``funcs_by_file``.
    Nested defs are deferred work (warmup tasks, factories, dispatch
    closures) and get their own exemption in the checker — never yield
    them as policed functions in their own right, or whole-module mode
    would re-scan exactly the bodies the exemption excludes."""
    whole_module = mod.path.endswith(module_suffix)
    named: set[str] = set()
    for prefix, names in funcs_by_file.items():
        if prefix in mod.path or mod.path.endswith(prefix):
            named = names
            break
    if not whole_module and not named:
        return
    nested_fns: set[int] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for inner in ast.walk(node):
                if inner is not node and isinstance(
                    inner, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    nested_fns.add(id(inner))
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if id(node) in nested_fns:
            continue
        if whole_module or node.name in named:
            yield node


def _health_functions(mod: Module) -> Iterator[ast.AST]:
    return _scoped_functions(mod, _HEALTH_MODULE, _HEALTH_FUNCS_BY_FILE)


def _waitfree_violations(
    fn: ast.AST,
) -> Iterator[tuple[ast.AST, str, str]]:
    """(node, offender, kind) for everything in ``fn`` that can wait:
    device syncs, blocking I/O, lock acquisition — the shared scanner
    behind OBS504 (health plane) and OBS505 (attribution plane). Nested
    defs are deferred work (warmup tasks, factories) — the caller never
    runs their bodies inline, so they are exempt (the same exemption
    OBS503 grants dispatch closures)."""
    nested: set[int] = set()
    for inner in ast.walk(fn):
        if (
            isinstance(inner, (ast.FunctionDef, ast.AsyncFunctionDef))
            and inner is not fn
        ):
            nested.update(id(n) for n in ast.walk(inner))
    for node in ast.walk(fn):
        if id(node) in nested:
            continue
        offender = kind = None
        if isinstance(node, ast.Call):
            name = call_name(node)
            if name in _DEVICE_SYNC_CALLS:
                offender, kind = f"{name}()", "device sync"
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _DEVICE_SYNC_ATTRS
            ):
                offender, kind = f".{node.func.attr}()", "device sync"
            elif name in _BLOCKING_CALLS or name in _EXTRA_BLOCKING:
                offender, kind = f"{name}()", "blocking call"
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _FILE_IO_ATTRS
            ):
                offender, kind = f".{node.func.attr}()", "blocking call"
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "acquire"
            ):
                offender, kind = f"{name or '.acquire'}()", "lock"
        elif isinstance(node, ast.With):
            if any(_lockish(item.context_expr) for item in node.items):
                offender, kind = "with <lock>", "lock"
        if offender is not None:
            yield node, offender, kind


def check_blocking_in_health_plane(mod: Module) -> Iterator[Finding]:
    for fn in _health_functions(mod):
        for node, offender, kind in _waitfree_violations(fn):
            yield mod.finding(
                "OBS504",
                node,
                f"{kind} {offender} in a health-check/watchdog path "
                f"(`{fn.name}`): probes must stay wait-free — a "
                f"device sync hangs with the device, a lock queues "
                f"behind the wedged dispatch holding it, blocking "
                f"I/O stalls the verdict; use snapshot reads "
                f"(list(deque), attribute loads) and arithmetic only",
            )


#: the attribution-plane module: EVERY function in it is either a ledger
#: write on the engine loop (container mutation only) or a read path a
#: /attribution poll runs inline — both must be wait-free
_ATTRIBUTION_MODULE = "langstream_tpu/serving/attribution.py"

#: named attribution read paths outside that module: the pod endpoint
#: payload builders and the engine's attribution surface
_ATTRIBUTION_FUNCS_BY_FILE = {
    "langstream_tpu/runtime/pod.py": {
        "_attribution_payload",
        "_memory_payload",
    },
    "langstream_tpu/serving/": {
        "attribution_section",
        "attribution_report",
        "_memory_ledger",
        "device_bytes",
    },
}


def _attribution_functions(mod: Module) -> Iterator[ast.AST]:
    return _scoped_functions(
        mod, _ATTRIBUTION_MODULE, _ATTRIBUTION_FUNCS_BY_FILE
    )


def check_blocking_in_attribution_plane(mod: Module) -> Iterator[Finding]:
    for fn in _attribution_functions(mod):
        for node, offender, kind in _waitfree_violations(fn):
            yield mod.finding(
                "OBS505",
                node,
                f"{kind} {offender} in an attribution/ledger read path "
                f"(`{fn.name}`): the attribution plane must stay "
                f"wait-free — a /attribution or /memory poll that syncs "
                f"the device hangs exactly when the operator asks which "
                f"program owns the stall, a lock queues behind the "
                f"wedged dispatch holding it, and blocking I/O stalls "
                f"the ledger; use snapshot reads (list()/dict() copies, "
                f"attribute loads) and arithmetic only",
            )


#: the journey-plane module: EVERY function in it is either a ledger
#: write on the engine dispatch path (container appends only) or a read
#: the /journey endpoints and the control-plane stitcher run inline
_JOURNEY_MODULE = "langstream_tpu/serving/journey.py"

#: named journey read paths outside that module: the pod endpoint
#: payload builder and the dev-mode control-plane stitcher (the k8s
#: runtime's journey fan-in is pod HTTP I/O by design, off this scope)
_JOURNEY_FUNCS_BY_FILE = {
    "langstream_tpu/runtime/pod.py": {"_journey_payload"},
    "langstream_tpu/controlplane/server.py": {"journey"},
}


def _journey_functions(mod: Module) -> Iterator[ast.AST]:
    return _scoped_functions(mod, _JOURNEY_MODULE, _JOURNEY_FUNCS_BY_FILE)


def check_blocking_in_journey_plane(mod: Module) -> Iterator[Finding]:
    for fn in _journey_functions(mod):
        for node, offender, kind in _waitfree_violations(fn):
            yield mod.finding(
                "OBS506",
                node,
                f"{kind} {offender} in a request-journey ledger path "
                f"(`{fn.name}`): the journey plane must stay wait-free "
                f"— a ledger write that takes a lock serializes the "
                f"engine dispatch path behind readers, a /journey read "
                f"that syncs the device hangs exactly when the operator "
                f"asks where a wedged request's time went; use "
                f"GIL-atomic appends, list()/dict() snapshot copies, "
                f"and arithmetic only",
            )


RULES = [
    Rule(
        id="OBS501",
        family="obs",
        summary="wall-clock time.time() inside serving/ or runtime/ "
        "(use time.monotonic() for durations)",
        check=check_wall_clock_in_measured_paths,
    ),
    Rule(
        id="OBS502",
        family="obs",
        summary="threading lock held across await in serving/ "
        "(blocks the event loop; use asyncio.Lock or release first)",
        check=check_lock_across_await,
    ),
    Rule(
        id="OBS503",
        family="obs",
        summary="blocking I/O in an engine hot-loop method or the flight "
        "recorder (telemetry must be non-blocking)",
        check=check_blocking_in_hot_loop,
    ),
    Rule(
        id="OBS504",
        family="obs",
        summary="device sync, blocking I/O, or lock acquisition in a "
        "health-check/watchdog path (probes must be wait-free)",
        check=check_blocking_in_health_plane,
    ),
    Rule(
        id="OBS505",
        family="obs",
        summary="device sync, blocking I/O, or lock acquisition in an "
        "attribution/ledger read path (serving/attribution.py and the "
        "/attribution//memory handlers must be wait-free)",
        check=check_blocking_in_attribution_plane,
    ),
    Rule(
        id="OBS506",
        family="obs",
        summary="device sync, blocking I/O, or lock acquisition in a "
        "request-journey ledger path (serving/journey.py and the "
        "/journey payload builders must be wait-free)",
        check=check_blocking_in_journey_plane,
    ),
]
