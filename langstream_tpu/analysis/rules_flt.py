"""Fault-tolerance rules: allocator-failure handling in the engine.

FLT901 polices the degrade-don't-die contract (docs/RESILIENCE.md): on
the engine's device-dispatch paths, a broad ``except Exception`` (or a
bare ``except``) that swallows the error without either **consulting the
RESOURCE_EXHAUSTED classifier** (``_resource_exhausted`` — the one
function every catch site must agree with) or **re-raising** is a
finding. A handler like that turns a device allocator failure into a
silent no-op: the shrink machinery never fires, the request neither
completes nor sheds, and the exact r03/r04 failure class ("engine died /
work vanished with no evidence") comes back one convenience ``except``
at a time.

Sanctioned shapes, by design:

- ``except Exception as e: if self._resource_exhausted(e): ... else:
  raise`` — the classify-then-adapt pattern every dispatch-path catch
  must follow (``_apply_imports``, the engine loop's shrink edge);
- a handler that re-raises on any path (``raise`` / ``raise X``) — the
  error still surfaces;
- narrow handlers (``except RuntimeError``, ``except AttributeError``)
  — catching a *named* failure is a decision, not a swallow; EXC401/402
  already police genuinely-discarded narrow catches tree-wide.

Out of scope by design: the device clock's watcher
(``serving/flight.py`` ``_watch``) swallows a failed program's exception
after stamping its completion — the same error is raised once, on the
dispatch thread, where ``_fetch_chunk`` / ``_fetch_prefill`` wait for
that program, and reaches the classifier from there.

Scope: ``serving/engine.py`` only, inside the dispatch-path method set
(the same surface PERF701 guards, plus the loop itself and the
import/export/prefix seams that touch the device).
"""

from __future__ import annotations

import ast
from typing import Iterator

from langstream_tpu.analysis.core import Finding, Module, Rule

#: the one file whose dispatch paths the rule guards
_ENGINE_FILE = "serving/engine.py"

#: engine functions on the device-dispatch path (nested closures like
#: ``_run``/``_dispatch``/``_grow_blocks`` inherit the scope through the
#: enclosing method)
_DISPATCH_FUNCS = {
    "_run_loop",
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
    "_fetch_prefill",
    "_await_chunk",
    "_apply_imports",
    "_export_ready_slots",
    "_export_slot",
    "_promote_prefix",
    "_demote_prefix_blocks",
    "_fetch_chunk",
}

#: call spellings that count as consulting the classifier
_CLASSIFIER_NAMES = {"_resource_exhausted"}


def _is_broad(handler: ast.ExceptHandler) -> bool:
    """Bare ``except:`` or any clause naming Exception/BaseException
    (directly or inside a tuple)."""
    t = handler.type
    if t is None:
        return True
    names = []
    for node in [t] + (list(t.elts) if isinstance(t, ast.Tuple) else []):
        if isinstance(node, ast.Name):
            names.append(node.id)
        elif isinstance(node, ast.Attribute):
            names.append(node.attr)
    return any(n in ("Exception", "BaseException") for n in names)


def _handler_consults_or_reraises(handler: ast.ExceptHandler) -> bool:
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Call):
            fn = node.func
            name = (
                fn.attr
                if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name) else ""
            )
            if name in _CLASSIFIER_NAMES:
                return True
    return False


def check_swallowed_dispatch_exception(mod: Module) -> Iterator[Finding]:
    if not mod.path.endswith(_ENGINE_FILE):
        return
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if not _is_broad(node):
            continue
        in_dispatch = False
        for scope in mod.scopes(node):
            if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if scope.name in _DISPATCH_FUNCS:
                    in_dispatch = True
                    break
        if not in_dispatch:
            continue
        if _handler_consults_or_reraises(node):
            continue
        yield mod.finding(
            "FLT901",
            node,
            "broad except on the engine device-dispatch path swallows "
            "the error without consulting _resource_exhausted or "
            "re-raising: a device allocator failure becomes a silent "
            "no-op — the pool-shrink adaptation never fires and the "
            "request neither completes nor sheds. Classify first "
            "(`if self._resource_exhausted(e): <adapt/shed>`) and "
            "`raise` everything else",
        )


RULES = [
    Rule(
        id="FLT901",
        family="flt",
        summary="broad except swallowing a device-dispatch error without "
        "consulting _resource_exhausted or re-raising (the allocator-"
        "failure adaptation path silently disabled)",
        check=check_swallowed_dispatch_exception,
    ),
]
