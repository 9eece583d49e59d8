"""Performance rules: dispatch-path fetch discipline in the engine.

PERF701 polices the pipelined engine loop's one-transfer-per-chunk
contract (docs/PIPELINE.md): on the decode dispatch path, device→host
synchronization is allowed ONLY inside the designated fetch stages —
``_fetch_chunk`` (the deferred packed-chunk wait) and the off-loop
``_run`` dispatch closures (where the one per-dispatch
``block_until_ready`` is timed as the sample's ``device_ms``). A
synchronous fetch anywhere else on the path — ``jax.block_until_ready``,
``np.asarray``/``np.array`` on a device array, ``jax.device_get``,
``.item()`` — silently serializes the host against the device and
re-creates exactly the exposed-host-time class the depth-2 pipeline
exists to hide (what one stray synchronous fetch costs on a locally
attached chip: not measured).

Exemptions, by design:

- functions named ``_fetch_chunk``/``_fetch*`` and ``_run`` — the fetch
  stages themselves;
- code under an ``if self._lockstep ...`` branch — the lockstep
  broadcast ships host bytes by protocol; its key/state fetches are the
  cost of multi-host replay, not an accident (and run on the dispatch
  thread);
- the device clock's watcher (``serving/flight.py`` ``_watch``, handed
  ``jax.block_until_ready`` by the engine's constructor): it waits on
  every program's result, on a thread of its own that neither the loop
  nor the dispatch thread ever waits for;
- everything outside the dispatch-path methods (host-side numpy on
  already-fetched chunks in ``_process_chunk`` uses numpy *array math*,
  not ``np.asarray`` conversions, so the rule stays quiet there).
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

#: the one file whose dispatch path the rule guards
_ENGINE_FILE = "serving/engine.py"

#: engine methods on the per-burst dispatch path (nested closures like
#: ``_dispatch``/``_grow_blocks`` inherit the scope through the enclosing
#: method)
_DISPATCH_FUNCS = {
    "_decode_burst",
    "_drain_pending",
    "_speculative_burst",
    "_advance_prefills",
    "_admit",
    "_admit_select",
    "_admit_candidates",
    "_admit_return",
    "_admit_dispatch",
    "_admit_complete",
    "_dispatch_prefill",
    "_await_chunk",
    "_deliver_chunk",
    "_process_chunk",
    "_emit_token",
    "_flush_emits",
    "_tables_device",
    "_sampler_device",
}

#: designated fetch stages: the only places a device→host sync belongs
_FETCH_STAGES = ("_fetch", "_run")

#: direct-call spellings of a synchronous device fetch
_SYNC_CALLS = {
    "jax.block_until_ready",
    "jax.device_get",
    "np.asarray",
    "np.array",
    "numpy.asarray",
    "numpy.array",
    "onp.asarray",
    "onp.array",
}

#: method spellings (``x.block_until_ready()`` / ``x.item()``)
_SYNC_ATTRS = {"block_until_ready", "item"}


def _is_fetch_stage(name: str) -> bool:
    return any(name.startswith(p) for p in _FETCH_STAGES)


def _under_lockstep_branch(mod: Module, node: ast.AST) -> bool:
    """True when the node sits under an ``if`` whose test mentions the
    lockstep channel (`self._lockstep is not None` and variants)."""
    cur = mod.parents.get(node)
    while cur is not None:
        if isinstance(cur, ast.If):
            for sub in ast.walk(cur.test):
                if (dotted_name(sub) or "").endswith("_lockstep"):
                    return True
        cur = mod.parents.get(cur)
    return False


def check_sync_fetch_on_dispatch_path(mod: Module) -> Iterator[Finding]:
    if not mod.path.endswith(_ENGINE_FILE):
        return
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        offender = None
        if name in _SYNC_CALLS:
            offender = f"{name}()"
        elif (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in _SYNC_ATTRS
        ):
            offender = f".{node.func.attr}()"
        if offender is None:
            continue
        # scope walk: the innermost function decides fetch-stage status;
        # any enclosing function on the dispatch path makes it in-scope
        in_dispatch = False
        innermost_fn = None
        for scope in mod.scopes(node):
            if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if innermost_fn is None:
                    innermost_fn = scope
                if scope.name in _DISPATCH_FUNCS:
                    in_dispatch = True
        if not in_dispatch:
            continue
        if innermost_fn is not None and _is_fetch_stage(innermost_fn.name):
            continue  # the designated fetch stage
        if _under_lockstep_branch(mod, node):
            continue  # broadcast protocol ships host bytes by design
        yield mod.finding(
            "PERF701",
            node,
            f"synchronous device fetch {offender} on the engine dispatch "
            f"path outside the designated fetch stage: it serializes the "
            f"host against the device and defeats the pipelined loop's "
            f"overlap — move it into _fetch_chunk / the off-loop _run "
            f"closure (where the one per-dispatch sync is timed), or keep "
            f"the data device-resident",
        )


RULES = [
    Rule(
        id="PERF701",
        family="perf",
        summary="synchronous device fetch (block_until_ready / np.asarray "
        "/ .item()) on the engine dispatch path outside the designated "
        "fetch stage",
        check=check_sync_fetch_on_dispatch_path,
    ),
]
