"""Flow-sensitive project rules (FLOW1001-1004), built on the dataflow
layer (``analysis/dataflow.py``) composed with the :class:`ProjectIndex`
call graph.

The per-file rules ask "does this syntax appear"; the RACE/INV rules ask
"who runs where". The FLOW family asks the remaining question — *what
happens to a value along each path*:

- **FLOW1001 — use-after-donate.** A value passed at a
  ``donate_argnums`` position of a jitted call is a dead buffer the
  moment the call dispatches: XLA reuses its memory for the outputs, and
  a later read returns garbage (or raises on a deleted array). The rule
  tracks donating callables interprocedurally — through factory returns
  (``_make_decode`` → the jitted closure), through the compiled-variant
  caches (``self._decode_chunk_fns[key] = self._make_decode(...)``),
  through locals bound from getter calls (``fn = self._decode_fn(...)``)
  and through ``functools.partial`` into dispatch-closure parameters —
  then path-searches the caller's CFG: any read of the donated ref
  reachable after the call with no intervening rebind fires. The
  sanctioned pattern is the engine's rebind-on-the-spot:
  ``out = fn(params, self.cache_k, self.cache_v, ...);
  self.cache_k, self.cache_v = out[2], out[3]``.

- **FLOW1002 — recompile taint.** Request/record-derived values (and
  ``len()`` of per-request sequences, and queue items) must never reach
  a shape-determining sink — ``np``/``jnp`` array-constructor dims, the
  compiled-variant cache keys (``self._*_fns[...]``), the
  specialization-getter arguments (``self._decode_fn(...)``) — without
  passing through a sanctioned bucketing function first. Each distinct
  raw value compiles a fresh XLA program (~30 s on TPU): the flight
  recorder's ``recompile`` event ring observes these storms at runtime;
  this rule rejects them at review time. Taint propagates through the
  CFG to a fixpoint and cross-function along the call graph (a tainted
  argument reaching a callee parameter that flows to a sink fires at
  the call site).

- **FLOW1003 — unretained task.** The event loop keeps only a weak
  reference to scheduled tasks: a handle that never escapes its frame
  can be garbage-collected mid-flight, and its exception is never
  observed. ASYNC204 catches the bare-statement spelling; this rule
  catches the flow-sensitive ones — a handle assigned to a local that
  is never used again, or (in a *sync* function, whose frame dies at
  return) used only for receiver calls like ``.add_done_callback(...)``
  that do not retain it. Route through
  ``core/asyncutil.spawn_retained`` instead.

- **FLOW1004 — lock-order cycles.** The project-wide lock-acquisition
  graph: a ``with <lock B>`` entered while lock A is held — lexically,
  or anywhere in the call graph reachable from a call made under A —
  adds edge A→B. A cycle means two threads can acquire the locks in
  opposite orders and deadlock. Complements RACE801's single-attribute
  view; nested *same-order* acquisition everywhere is the sanctioned
  shape and stays silent.

Scope: FLOW1001 follows donation wherever ``donate_argnums`` appears;
FLOW1002 is scoped to ``serving/`` (the only package that shapes jit
inputs); FLOW1003 to ``serving/``, ``gateway/``, ``runtime/``; FLOW1004
is package-wide. Known limits, precision over recall as always: the
donating-callable and taint propagation resolve positional arguments
only; donating calls inside branch *headers* are not scanned; a handle
aliased through a container is assumed retained.
"""

from __future__ import annotations

import ast
from typing import Iterator

from langstream_tpu.analysis import dataflow as df
from langstream_tpu.analysis.core import Finding, dotted_name
from langstream_tpu.analysis.rules_async import TASK_SPAWNERS
from langstream_tpu.analysis.project import (
    FunctionInfo,
    ProjectIndex,
    ProjectRule,
    RawCall,
)

#: bucketing helpers whose *return value* is sanctioned as a jit shape /
#: specialization key: they collapse the per-request value onto a small
#: static lattice. To sanction a new helper, add it here (and a TN
#: fixture pinning it — docs/ANALYSIS.md, "sanctioning a bucketing
#: function"); any function whose name contains "bucket" is sanctioned
#: by convention.
SANCTIONED_BUCKETING = {
    "_pow2",
    "_bucket",
    "_read_blocks_for",
    "_sampler_mode",
}

#: identifier spellings whose attribute/name reads are request-derived
#: taint sources
_REQUEST_MARKERS = {"request", "record", "req"}

#: np/jnp constructors whose first argument is a shape
_SHAPE_CTORS = {"zeros", "ones", "full", "empty"}
_ARRAY_MODULES = {"np", "jnp", "numpy", "onp"}

_MAX_FIXPOINT_ROUNDS = 12


def _in_packages(path: str, *pkgs: str) -> bool:
    return any(path.startswith(f"{p}/") or f"/{p}/" in path for p in pkgs)


def _flow_functions(
    index: ProjectIndex, paths: list[str]
) -> Iterator[df.FlowFunction]:
    for path in paths:
        src = index.sources.get(path)
        if src is None:
            continue
        try:
            ff = df.flow_index(path, src)
        except SyntaxError:
            continue  # the per-file scan owns reporting parse errors
        yield from ff.functions.values()


def _stmt_nodes(cfg: df.CFG) -> Iterator[df.CFGNode]:
    for node in cfg.nodes:
        if node.kind == "stmt" and node.ast_node is not None:
            yield node


def _calls_in_stmt(stmt: ast.AST) -> Iterator[ast.Call]:
    """Call expressions in one simple statement, nested defs excluded."""
    stack = [stmt]
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda, ast.ClassDef)):
            continue
        if isinstance(node, ast.Call):
            yield node
        stack.extend(ast.iter_child_nodes(node))


def _raw_for_callee(expr: ast.AST) -> RawCall | None:
    """A resolver descriptor for a callee/callable expression, matching
    the project indexer's vocabulary."""
    if isinstance(expr, ast.Name):
        return RawCall(kind="name", name=expr.id, line=expr.lineno)
    if isinstance(expr, ast.Attribute):
        if (
            isinstance(expr.value, ast.Name)
            and expr.value.id in ("self", "cls")
        ):
            return RawCall(kind="self", name=expr.attr, line=expr.lineno)
        d = dotted_name(expr)
        if d is not None:
            return RawCall(kind="dotted", name=d, line=expr.lineno)
    return None


def _resolve_callee(
    index: ProjectIndex, fn_info: FunctionInfo | None, expr: ast.AST
) -> str | None:
    if fn_info is None:
        return None
    raw = _raw_for_callee(expr)
    if raw is None:
        return None
    return index.resolve_call(raw, fn_info)


# ==========================================================================
# FLOW1001 — use-after-donate
# ==========================================================================


def _donate_positions_of_wrapper(call: ast.AST) -> frozenset[int] | None:
    """``partial(jax.jit, donate_argnums=...)`` / ``jax.jit(...,
    donate_argnums=...)`` → the donated positions."""
    if not isinstance(call, ast.Call):
        return None
    fname = dotted_name(call.func) or ""
    leaf = fname.split(".")[-1]
    if leaf == "partial":
        if not call.args:
            return None
        inner = dotted_name(call.args[0]) or ""
        if inner.split(".")[-1] != "jit":
            return None
    elif leaf != "jit":
        return None
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            vals = {
                el.value
                for el in ast.walk(kw.value)
                if isinstance(el, ast.Constant) and isinstance(el.value, int)
            }
            if vals:
                return frozenset(vals)
    return None


def _donating_def_positions(fn_node: ast.AST) -> frozenset[int] | None:
    for deco in getattr(fn_node, "decorator_list", []):
        pos = _donate_positions_of_wrapper(deco)
        if pos:
            return pos
    return None


class _DonationWorld:
    """Interprocedural donating-callable facts, grown to a fixpoint.

    - ``returns_donating[qname]`` — calling this function *yields* a
      donating callable (factories, variant-cache getters);
    - ``donating_attrs[(path, attr)]`` — ``self.<attr>`` (or a subscript
      of it) holds donating callables;
    - ``factory_attrs[(path, attr)]`` — ``self.<attr>`` holds a
      *factory*: calling it yields a donating callable (the engine's
      ``self._make_decode = _make_decode`` indirection);
    - ``donating_params[(qname, param)]`` — this parameter receives a
      donating callable from some call site (partials unwrapped).
    """

    def __init__(self) -> None:
        self.returns_donating: dict[str, frozenset[int]] = {}
        self.donating_attrs: dict[tuple[str, str], frozenset[int]] = {}
        self.factory_attrs: dict[tuple[str, str], frozenset[int]] = {}
        self.donating_params: dict[tuple[str, str], frozenset[int]] = {}
        # per function qname: donating nested defs / donating local binds
        # — consulted along the LEXICAL parent chain, because the engine
        # binds `fn = self._decode_fn(...)` in the method and calls it
        # inside the `_run`/`_dispatch` closure
        self.local_defs_by_fn: dict[str, dict[str, frozenset[int]]] = {}
        self.local_binds_by_fn: dict[str, dict[str, frozenset[int]]] = {}
        self.changed = False

    def _merge(self, table: dict, key, pos: frozenset[int]) -> None:
        old = table.get(key, frozenset())
        new = old | pos
        if new != old:
            table[key] = new
            self.changed = True

    def value_positions(
        self,
        expr: ast.AST,
        fn: df.FlowFunction,
        index: ProjectIndex,
        fn_info: FunctionInfo | None,
    ) -> frozenset[int]:
        """Donated positions when ``expr`` evaluates to a donating
        callable, else the empty set."""
        direct = _donate_positions_of_wrapper(expr)
        if direct:
            # jax.jit(f, donate_argnums=...) IS a donating callable
            return direct
        if isinstance(expr, ast.Name):
            # lexical chain: the closure sees its enclosing functions'
            # donating defs, bindings, and parameters
            parts = fn.qname.split(".")
            for i in range(len(parts), 0, -1):
                q = ".".join(parts[:i])
                pos = (
                    self.local_defs_by_fn.get(q, {}).get(expr.id)
                    or self.local_binds_by_fn.get(q, {}).get(expr.id)
                    or self.donating_params.get((q, expr.id))
                )
                if pos:
                    return pos
            return frozenset()
        if isinstance(expr, ast.Call):
            callee = _resolve_callee(index, fn_info, expr.func)
            if callee is not None:
                return self.returns_donating.get(callee, frozenset())
            f = expr.func
            if (
                isinstance(f, ast.Attribute)
                and isinstance(f.value, ast.Name)
                and f.value.id in ("self", "cls")
            ):
                # calling an instance-attr factory yields a donating fn
                return self.factory_attrs.get(
                    (fn.path, f.attr), frozenset()
                )
            return frozenset()
        base = expr
        if isinstance(base, ast.Subscript):
            base = base.value
        if (
            isinstance(base, ast.Attribute)
            and isinstance(base.value, ast.Name)
            and base.value.id in ("self", "cls")
        ):
            return self.donating_attrs.get(
                (fn.path, base.attr), frozenset()
            )
        return frozenset()

    def factory_positions(
        self, expr: ast.AST, fn: df.FlowFunction
    ) -> frozenset[int]:
        """Positions when ``expr`` evaluates to a *factory* — a function
        whose call yields a donating callable."""
        if isinstance(expr, ast.Name):
            return self.returns_donating.get(
                f"{fn.qname}.{expr.id}", frozenset()
            )
        if (
            isinstance(expr, ast.Attribute)
            and isinstance(expr.value, ast.Name)
            and expr.value.id in ("self", "cls")
        ):
            return self.factory_attrs.get((fn.path, expr.attr), frozenset())
        return frozenset()


def _function_body_stmts(fn_node: ast.AST) -> Iterator[ast.stmt]:
    """Statements of a function at any nesting EXCEPT inside nested
    defs (those are separate flow functions)."""
    stack = list(fn_node.body)
    while stack:
        stmt = stack.pop()
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        yield stmt
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, ast.stmt):
                stack.append(child)
            elif isinstance(child, ast.excepthandler):
                stack.extend(child.body)


def _body_stmts(fn: df.FlowFunction) -> list[ast.stmt]:
    got = fn.memo.get("body_stmts")
    if got is None:
        got = list(_function_body_stmts(fn.node))
        fn.memo["body_stmts"] = got
    return got


def _body_calls(fn: df.FlowFunction) -> list[ast.Call]:
    got = fn.memo.get("body_calls")
    if got is None:
        got = [c for s in _body_stmts(fn) for c in _calls_in_stmt(s)]
        fn.memo["body_calls"] = got
    return got


def _cfg_calls(fn: df.FlowFunction) -> list[tuple[int, ast.Call]]:
    """(cfg node idx, call expr) pairs for every call in a simple
    statement — the donating-call / tainted-arg scan substrate."""
    got = fn.memo.get("cfg_calls")
    if got is None:
        got = [
            (node.idx, call)
            for node in _stmt_nodes(fn.cfg)
            for call in _calls_in_stmt(node.ast_node)
        ]
        fn.memo["cfg_calls"] = got
    return got


def _nested_donating_defs(fn: df.FlowFunction) -> dict[str, frozenset[int]]:
    got = fn.memo.get("donating_defs")
    if got is None:
        got = {}
        for child in ast.walk(fn.node):
            if (
                isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef))
                and child is not fn.node
            ):
                pos = _donating_def_positions(child)
                if pos:
                    got[child.name] = pos
        fn.memo["donating_defs"] = got
    return got


def _donation_pass(
    world: _DonationWorld,
    fns: list[tuple[df.FlowFunction, FunctionInfo | None]],
    index: ProjectIndex,
    report: bool,
) -> list[Finding]:
    """One round: refresh the donating-world tables from every function
    and (when ``report`` is set, on the final round) emit the
    use-after-donate findings."""
    findings: list[Finding] = []
    for fn, fn_info in fns:
        # nested donating jit defs, by local name (any depth: a def two
        # closures down is still lexically visible under that name only
        # where it is bound, but the over-approximation is harmless)
        local_defs = world.local_defs_by_fn.setdefault(fn.qname, {})
        for name, pos in _nested_donating_defs(fn).items():
            world._merge(local_defs, name, pos)

        # flow-insensitive local bindings: name = <donating expr>
        local_binds = world.local_binds_by_fn.setdefault(fn.qname, {})
        for stmt in _body_stmts(fn):
            if not isinstance(stmt, ast.Assign):
                continue
            fpos = world.factory_positions(stmt.value, fn)
            if fpos:
                for target in stmt.targets:
                    if (
                        isinstance(target, ast.Attribute)
                        and isinstance(target.value, ast.Name)
                        and target.value.id in ("self", "cls")
                    ):
                        world._merge(
                            world.factory_attrs,
                            (fn.path, target.attr), fpos,
                        )
            pos = world.value_positions(stmt.value, fn, index, fn_info)
            if not pos:
                continue
            for target in stmt.targets:
                if isinstance(target, ast.Name):
                    world._merge(local_binds, target.id, pos)
                else:
                    base = target
                    if isinstance(base, ast.Subscript):
                        base = base.value
                    if (
                        isinstance(base, ast.Attribute)
                        and isinstance(base.value, ast.Name)
                        and base.value.id in ("self", "cls")
                    ):
                        world._merge(
                            world.donating_attrs,
                            (fn.path, base.attr), pos,
                        )

        # returns: does calling this function yield a donating callable?
        for stmt in _body_stmts(fn):
            if isinstance(stmt, ast.Return) and stmt.value is not None:
                pos = world.value_positions(stmt.value, fn, index, fn_info)
                if pos:
                    world._merge(world.returns_donating, fn.qname, pos)

        # params receiving donating callables (partial(...) unwrapped)
        for call in _body_calls(fn):
            fname = dotted_name(call.func) or ""
            args = call.args
            if fname.split(".")[-1] == "partial" and call.args:
                target_expr, args = call.args[0], call.args[1:]
            else:
                target_expr = call.func
            callee = _resolve_callee(index, fn_info, target_expr)
            if callee is None:
                continue
            callee_flow = _flow_fn_for(index, callee)
            if callee_flow is None:
                continue
            params = df.param_refs(callee_flow.node)
            if params and params[0] in ("self", "cls"):
                params = params[1:]
            for i, arg in enumerate(args):
                if i >= len(params):
                    break
                pos = world.value_positions(arg, fn, index, fn_info)
                if pos:
                    world._merge(
                        world.donating_params,
                        (callee, params[i]), pos,
                    )

        if not report:
            continue
        findings.extend(
            _check_use_after_donate(world, fn, fn_info, index)
        )
    return findings


def _flow_fn_for(index: ProjectIndex, qname: str) -> df.FlowFunction | None:
    info = index.functions.get(qname)
    if info is None:
        return None
    src = index.sources.get(info.path)
    if src is None:
        return None
    try:
        return df.flow_index(info.path, src).functions.get(qname)
    except SyntaxError:
        return None


def _tuple_candidates(
    expr: ast.AST,
    cfg: df.CFG,
    rd_in: list[set[df.Definition]],
    at_idx: int,
    depth: int = 0,
) -> list[list[ast.AST]] | None:
    """Element candidates of a tuple-valued expression (for ``fn(*args)``
    donation mapping): a Tuple literal, an IfExp over tuples, tuple
    concatenation, or a Name resolved through its reaching definitions.
    Each slot is the list of expressions that may occupy it."""
    if depth > 5:
        return None

    def _pad_merge(a, b):
        # branches may disagree on LENGTH (the engine's paged tuple
        # carries an extra block-table slot) — merge the common prefix
        # and keep the longer tail single-branch
        return [
            (a[i] if i < len(a) else []) + (b[i] if i < len(b) else [])
            for i in range(max(len(a), len(b)))
        ]

    if isinstance(expr, ast.Tuple):
        return [[el] for el in expr.elts]
    if isinstance(expr, ast.IfExp):
        a = _tuple_candidates(expr.body, cfg, rd_in, at_idx, depth + 1)
        b = _tuple_candidates(expr.orelse, cfg, rd_in, at_idx, depth + 1)
        if a is None or b is None:
            return None
        return _pad_merge(a, b)
    if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Add):
        left = _tuple_candidates(expr.left, cfg, rd_in, at_idx, depth + 1)
        right = _tuple_candidates(expr.right, cfg, rd_in, at_idx, depth + 1)
        if left is None or right is None:
            return None
        return left + right
    if isinstance(expr, ast.Name):
        merged: list[list[ast.AST]] | None = None
        for ref, def_idx in rd_in[at_idx]:
            if ref != expr.id:
                continue
            def_node = cfg.nodes[def_idx].ast_node
            if not isinstance(def_node, ast.Assign):
                return None
            got = _tuple_candidates(
                def_node.value, cfg, rd_in, def_idx, depth + 1
            )
            if got is None:
                return None
            merged = got if merged is None else _pad_merge(merged, got)
        return merged
    return None


def _check_use_after_donate(
    world: _DonationWorld,
    fn: df.FlowFunction,
    fn_info: FunctionInfo | None,
    index: ProjectIndex,
) -> Iterator[Finding]:
    cfg = fn.cfg
    rd_in: list[set[df.Definition]] | None = None
    for node_idx, call in _cfg_calls(fn):
        node = cfg.nodes[node_idx]
        pos = world.value_positions(call.func, fn, index, fn_info)
        if not pos:
            continue
        # map donated positions to argument expressions
        donated: list[ast.AST] = []
        if len(call.args) == 1 and isinstance(call.args[0], ast.Starred):
            if rd_in is None:
                rd_in = df.reaching_definitions(
                    cfg, df.param_refs(fn.node)
                )
            cands = _tuple_candidates(
                call.args[0].value, cfg, rd_in, node.idx
            )
            if cands is None:
                continue
            for p in sorted(pos):
                if p < len(cands):
                    donated.extend(cands[p])
        else:
            for p in sorted(pos):
                if p < len(call.args) and not isinstance(
                    call.args[p], ast.Starred
                ):
                    donated.append(call.args[p])
        donated_refs = sorted(
            {r for r in (df.ref_of(a) for a in donated) if r is not None}
        )
        for ref in donated_refs:
            reads = df.reads_before_rebind(cfg, node.idx, ref)
            for _idx, line in reads:
                yield Finding(
                    rule="FLOW1001",
                    path=fn.path,
                    line=line,
                    symbol=fn.symbol(),
                    message=(
                        f"`{ref}` was donated to the jitted call on "
                        f"line {node.line} (donate_argnums) and is "
                        f"read here without being rebound: the "
                        f"buffer's memory now backs the call's "
                        f"outputs, so this read returns garbage or "
                        f"raises on a deleted array — rebind from "
                        f"the call's outputs first (`self.cache_k, "
                        f"self.cache_v = out[...]`, the engine "
                        f"pattern), or drop the stale reference"
                    ),
                )
            if (
                not reads
                and ref.startswith("self.")
                and df.exits_without_rebind(cfg, node.idx, ref)
            ):
                # the quiet half: nothing HERE reads the dead
                # buffer, but the instance attr outlives the frame
                # still bound to donated memory — the next reader
                # anywhere gets garbage (the PR-6 bug class)
                yield Finding(
                    rule="FLOW1001",
                    path=fn.path,
                    line=node.line,
                    symbol=fn.symbol(),
                    message=(
                        f"`{ref}` is donated to this jitted call "
                        f"(donate_argnums) but not rebound on every "
                        f"path before the function returns: the "
                        f"attribute outlives this frame still "
                        f"pointing at donated memory, so the next "
                        f"read anywhere in the engine gets garbage "
                        f"— rebind from the call's outputs on all "
                        f"paths (`self.cache_k, self.cache_v = "
                        f"out[...]`)"
                    ),
                )


def check_use_after_donate(index: ProjectIndex) -> Iterator[Finding]:
    # seed scope: files whose AST actually spells a donate_argnums
    # keyword (the substring prefilter keeps the parse set small; the
    # AST check drops files that merely mention it in strings — this
    # module's own vocabulary, fixture registries); grown below with
    # files that call a returns-donating function (the variant caches
    # live one file over)
    seed_paths = []
    for p, src in index.sources.items():
        if "donate_argnums" not in src:
            continue
        try:
            if df.flow_index(p, src).has_donation:
                seed_paths.append(p)
        except SyntaxError:
            continue
    if not seed_paths:
        return
    fns = [
        (fn, index.functions.get(fn.qname))
        for fn in _flow_functions(index, seed_paths)
    ]
    world = _DonationWorld()
    for _ in range(_MAX_FIXPOINT_ROUNDS):
        world.changed = False
        _donation_pass(world, fns, index, report=False)
        if not world.changed:
            break
    # widen to callers of returns-donating functions before reporting
    donating_qnames = set(world.returns_donating)
    extra_paths = {
        fn.path
        for fn in index.functions.values()
        if fn.path not in seed_paths and (fn.calls & donating_qnames)
    }
    if extra_paths:
        fns += [
            (fn, index.functions.get(fn.qname))
            for fn in _flow_functions(index, sorted(extra_paths))
        ]
        for _ in range(_MAX_FIXPOINT_ROUNDS):
            world.changed = False
            _donation_pass(world, fns, index, report=False)
            if not world.changed:
                break
    world.changed = False
    yield from _donation_pass(world, fns, index, report=True)


# ==========================================================================
# FLOW1002 — recompile taint
# ==========================================================================


class _RecompileSpec(df.TaintSpec):
    """Sources: request/record attribute chains, names spelled like a
    request, queue-item fetches. Sanctioners: the bucketing registry."""

    def source_label(self, expr: ast.AST) -> str | None:
        if isinstance(expr, ast.Attribute) and expr.attr in _REQUEST_MARKERS:
            return f"{expr.attr}-derived"
        if isinstance(expr, ast.Name) and expr.id in _REQUEST_MARKERS:
            return f"`{expr.id}`"
        if (
            isinstance(expr, ast.Call)
            and isinstance(expr.func, ast.Attribute)
            and expr.func.attr in ("get", "get_nowait")
            and "queue" in (dotted_name(expr.func.value) or "").lower()
        ):
            return "queue item"
        return None

    def is_sanctioner(self, call: ast.Call) -> bool:
        name = dotted_name(call.func) or ""
        leaf = name.split(".")[-1]
        return leaf in SANCTIONED_BUCKETING or "bucket" in leaf.lower()


def _shape_sink_args(stmt: ast.AST) -> Iterator[tuple[ast.AST, str]]:
    """(expression, sink description) pairs whose taint means a
    per-request recompile."""
    for call in _calls_in_stmt(stmt):
        fname = dotted_name(call.func) or ""
        parts = fname.split(".")
        # np.zeros((n, d)) / jnp.full(shape, v) — dims are static under jit
        if (
            len(parts) == 2
            and parts[0] in _ARRAY_MODULES
            and parts[1] in _SHAPE_CTORS
            and call.args
        ):
            yield call.args[0], f"{fname}(...) shape"
        # specialization getters: self._decode_fn(mode, window, ...) —
        # every distinct argument tuple compiles a fresh variant
        if (
            isinstance(call.func, ast.Attribute)
            and isinstance(call.func.value, ast.Name)
            and call.func.value.id in ("self", "cls")
            and call.func.attr.endswith("_fn")
        ):
            for arg in call.args:
                yield arg, f"self.{call.func.attr}(...) specialization key"
    # compiled-variant cache keys: self._decode_chunk_fns[key]
    stack = [stmt]
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda, ast.ClassDef)):
            continue
        if (
            isinstance(node, ast.Subscript)
            and isinstance(node.value, ast.Attribute)
            and isinstance(node.value.value, ast.Name)
            and node.value.value.id in ("self", "cls")
            and node.value.attr.endswith("_fns")
        ):
            yield node.slice, f"self.{node.value.attr}[...] variant key"
        stack.extend(ast.iter_child_nodes(node))


def check_recompile_taint(index: ProjectIndex) -> Iterator[Finding]:
    spec = _RecompileSpec()
    paths = sorted(
        p for p in index.sources if _in_packages(p, "serving")
    )
    #: (qname, param) -> sink description the param reaches
    sink_params: dict[tuple[str, str], str] = {}
    #: call-site evidence: (fn, line, callee, param, labels)
    call_args: list[tuple[df.FlowFunction, int, str, str,
                          frozenset[str]]] = []
    findings: dict[tuple[str, int, str], Finding] = {}

    fns = list(_flow_functions(index, paths))
    for fn in fns:
        fn_info = index.functions.get(fn.qname)
        cfg = fn.cfg
        state = fn.memo.get("recompile_taint")
        if state is None:
            params = df.param_refs(fn.node)
            seed = {
                p: frozenset({f"param:{p}"})
                for p in params
                if p not in ("self", "cls")
            }
            # the fixpoint is pure in this function's source — memoized
            # on the content-hash-cached FlowFunction so repeat scans
            # (the tier-1 gate plus the CLI smoke) pay it once
            state = df.run_taint(cfg, spec, seed=seed)
            fn.memo["recompile_taint"] = state
        sinks = fn.memo.get("shape_sinks")
        if sinks is None:
            sinks = [
                (node.idx, node.line, expr, sink)
                for node in _stmt_nodes(cfg)
                for expr, sink in _shape_sink_args(node.ast_node)
            ]
            fn.memo["shape_sinks"] = sinks
        for node_idx, line, expr, sink in sinks:
            labels = state.expr_labels(expr, node_idx)
            for label in sorted(labels):
                if label.startswith("param:"):
                    sink_params.setdefault(
                        (fn.qname, label[len("param:"):]), sink
                    )
                else:
                    key = (fn.path, line, sink)
                    findings.setdefault(key, Finding(
                        rule="FLOW1002", path=fn.path, line=line,
                        symbol=fn.symbol(),
                        message=_recompile_message(label, sink),
                    ))
        # record tainted positional args for the cross-function pass
        for node_idx, call in _cfg_calls(fn):
            callee = _resolve_callee(index, fn_info, call.func)
            if callee is None:
                continue
            callee_flow = _flow_fn_for(index, callee)
            if callee_flow is None:
                continue
            cparams = df.param_refs(callee_flow.node)
            if cparams and cparams[0] in ("self", "cls"):
                cparams = cparams[1:]
            line = cfg.nodes[node_idx].line
            for i, arg in enumerate(call.args):
                if i >= len(cparams) or isinstance(arg, ast.Starred):
                    break
                labels = state.expr_labels(arg, node_idx)
                if labels:
                    call_args.append(
                        (fn, line, callee, cparams[i], labels)
                    )

    # cross-function: tainted arg -> callee sink-param, to a fixpoint
    for _ in range(_MAX_FIXPOINT_ROUNDS):
        grown = False
        for fn, line, callee, param, labels in call_args:
            sink = sink_params.get((callee, param))
            if sink is None:
                continue
            for label in sorted(labels):
                if label.startswith("param:"):
                    key = (fn.qname, label[len("param:"):])
                    if key not in sink_params:
                        sink_params[key] = sink
                        grown = True
                else:
                    key2 = (fn.path, line, sink)
                    if key2 not in findings:
                        findings[key2] = Finding(
                            rule="FLOW1002", path=fn.path, line=line,
                            symbol=fn.symbol(),
                            message=_recompile_message(
                                label, sink, via=callee.split(".")[-1]
                            ),
                        )
                        grown = True
        if not grown:
            break
    yield from findings.values()


def _recompile_message(label: str, sink: str, via: str | None = None) -> str:
    hop = f" (through `{via}`)" if via else ""
    return (
        f"{label} value reaches the shape-determining sink {sink}{hop} "
        f"without passing a sanctioned bucketing function "
        f"({', '.join(sorted(SANCTIONED_BUCKETING))}, or any `*bucket*` "
        f"helper): every distinct raw value compiles a fresh XLA variant "
        f"— the recompile storms the flight recorder counts at runtime; "
        f"bucket the value first (docs/ANALYSIS.md, recompile taint)"
    )


# ==========================================================================
# FLOW1003 — unretained task handle
# ==========================================================================


def _is_task_spawn(call: ast.Call) -> str | None:
    name = dotted_name(call.func) or ""
    leaf = name.split(".")[-1]
    return leaf if leaf in TASK_SPAWNERS else None


def _name_escapes(name: str, stmt: ast.AST) -> bool:
    """Does ``stmt`` let ``name`` outlive the frame — passed as an
    argument, returned/yielded, aliased into another binding or a
    container/attribute store? Receiver-only method calls
    (``t.add_done_callback(...)``, ``t.cancel()``) do NOT retain."""
    for node in ast.walk(stmt):
        if isinstance(node, (ast.Return, ast.Yield, ast.YieldFrom)):
            if node.value is not None and _mentions(node.value, name):
                return True
        elif isinstance(node, ast.Call):
            for arg in node.args:
                target = (
                    arg.value if isinstance(arg, ast.Starred) else arg
                )
                if _mentions(target, name):
                    return True
            if any(_mentions(kw.value, name) for kw in node.keywords):
                return True
        elif isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            if node.value is not None and _mentions_outside_receiver(
                node.value, name
            ):
                return True
        elif isinstance(node, (ast.List, ast.Tuple, ast.Set, ast.Dict)):
            if any(
                isinstance(el, ast.Name) and el.id == name
                for el in ast.walk(node)
            ):
                return True
    return False


def _mentions(expr: ast.AST, name: str) -> bool:
    return any(
        isinstance(n, ast.Name) and n.id == name for n in ast.walk(expr)
    )


def _mentions_outside_receiver(expr: ast.AST, name: str) -> bool:
    """``name`` used in ``expr`` other than as a method-call receiver."""
    if (
        isinstance(expr, ast.Call)
        and isinstance(expr.func, ast.Attribute)
        and isinstance(expr.func.value, ast.Name)
        and expr.func.value.id == name
    ):
        return any(_mentions(a, name) for a in expr.args)
    return _mentions(expr, name)


def check_unretained_task(index: ProjectIndex) -> Iterator[Finding]:
    paths = sorted(
        p for p in index.sources
        if _in_packages(p, "serving", "gateway", "runtime")
    )
    for fn in _flow_functions(index, paths):
        cfg = fn.cfg
        chains: dict[df.Definition, set[int]] | None = None
        for node in _stmt_nodes(cfg):
            stmt = node.ast_node
            if not isinstance(stmt, ast.Assign):
                continue  # bare-statement spawns are ASYNC204's turf
            if not isinstance(stmt.value, ast.Call):
                continue
            spawner = _is_task_spawn(stmt.value)
            if spawner is None:
                continue
            if len(stmt.targets) != 1 or not isinstance(
                stmt.targets[0], ast.Name
            ):
                continue  # attribute/subscript stores retain by design
            name = stmt.targets[0].id
            if chains is None:
                chains = df.def_use_chains(cfg, df.param_refs(fn.node))
            uses = chains.get((name, node.idx), set())
            if not uses:
                yield Finding(
                    rule="FLOW1003", path=fn.path, line=node.line,
                    symbol=fn.symbol(),
                    message=(
                        f"task handle `{name}` from {spawner}(...) is "
                        f"never used again: the event loop keeps only a "
                        f"weak reference, so the task can be "
                        f"garbage-collected mid-flight and its exception "
                        f"is never observed — route it through "
                        f"core/asyncutil.spawn_retained (holds the "
                        f"handle until done and logs failures)"
                    ),
                )
                continue
            if fn.is_async:
                continue  # a live coroutine frame retains its locals
            if any(
                _name_escapes(name, cfg.nodes[u].ast_node)
                for u in uses
                if cfg.nodes[u].ast_node is not None
            ):
                continue
            yield Finding(
                rule="FLOW1003", path=fn.path, line=node.line,
                symbol=fn.symbol(),
                message=(
                    f"task handle `{name}` from {spawner}(...) never "
                    f"escapes this synchronous frame (only receiver "
                    f"calls like .add_done_callback/.cancel, which do "
                    f"not retain it): when the function returns, the "
                    f"event loop's weak reference is all that is left "
                    f"and the task can be garbage-collected mid-flight "
                    f"— route it through core/asyncutil.spawn_retained"
                ),
            )


# ==========================================================================
# FLOW1004 — lock-order cycles
# ==========================================================================


def _norm_lock(raw: str, fn: FunctionInfo) -> str:
    if raw.startswith(("self.", "cls.")):
        owner = fn.cls or fn.qname
        return f"{owner}.{raw.split('.', 1)[1]}"
    return f"{fn.module}.{raw}"


def check_lock_order(index: ProjectIndex) -> Iterator[Finding]:
    #: (A, B): lock B acquired while A held -> first observed site
    edges: dict[tuple[str, str], tuple[str, int, str]] = {}

    def _edge(a: str, b: str, path: str, line: int, via: str) -> None:
        if a != b:
            edges.setdefault((a, b), (path, line, via))

    # direct lexical nesting
    for fn in index.functions.values():
        for acq in fn.lock_acquires:
            b = _norm_lock(acq.lock, fn)
            for held in acq.held:
                _edge(_norm_lock(held, fn), b, fn.path, acq.line,
                      "nested with")

    # call-graph composition: a call made under lock A reaches a
    # function (transitively) that acquires B
    closure_cache: dict[str, frozenset[str]] = {}

    def acquires_closure(qname: str) -> frozenset[str]:
        hit = closure_cache.get(qname)
        if hit is not None:
            return hit
        out: set[str] = set()
        for q in index.reachable([qname]):
            f = index.functions.get(q)
            if f is None:
                continue
            for acq in f.lock_acquires:
                out.add(_norm_lock(acq.lock, f))
        result = frozenset(out)
        closure_cache[qname] = result
        return result

    for fn in index.functions.values():
        for callee, held, line in fn.calls_under_lock:
            inner = acquires_closure(callee)
            if not inner:
                continue
            for b in inner:
                for h in held:
                    _edge(_norm_lock(h, fn), b, fn.path, line,
                          f"call into {callee.split('.')[-1]}")

    # cycle detection: report each strongly connected component once
    adjacency: dict[str, set[str]] = {}
    for (a, b) in edges:
        adjacency.setdefault(a, set()).add(b)
        adjacency.setdefault(b, set())
    for scc in _sccs(adjacency):
        if len(scc) < 2:
            continue
        cyc = sorted(scc)
        # anchor on the smallest in-cycle edge site
        sites = sorted(
            (site, (a, b))
            for (a, b), site in edges.items()
            if a in scc and b in scc
        )
        (path, line, via), (a, b) = sites[0]
        order = " -> ".join(cyc + [cyc[0]])
        yield Finding(
            rule="FLOW1004",
            path=path,
            line=line,
            symbol="<lock-order>",
            message=(
                f"lock-order cycle {order}: here `{b}` is acquired "
                f"while `{a}` is held ({via}), and the reverse order "
                f"exists elsewhere in the call graph — two threads "
                f"taking the locks in opposite orders deadlock; pick "
                f"one global order (acquire "
                f"{' before '.join(cyc)}) or collapse to one lock"
            ),
        )


def _sccs(adjacency: dict[str, set[str]]) -> list[set[str]]:
    """Tarjan, iterative (lock graphs are tiny but recursion limits are
    not worth trusting)."""
    idx_of: dict[str, int] = {}
    low: dict[str, int] = {}
    on_stack: set[str] = set()
    stack: list[str] = []
    out: list[set[str]] = []
    counter = [0]

    for root in adjacency:
        if root in idx_of:
            continue
        work: list[tuple[str, Iterator[str]]] = [
            (root, iter(adjacency.get(root, ())))
        ]
        idx_of[root] = low[root] = counter[0]
        counter[0] += 1
        stack.append(root)
        on_stack.add(root)
        while work:
            node, it = work[-1]
            advanced = False
            for nxt in it:
                if nxt not in idx_of:
                    idx_of[nxt] = low[nxt] = counter[0]
                    counter[0] += 1
                    stack.append(nxt)
                    on_stack.add(nxt)
                    work.append((nxt, iter(adjacency.get(nxt, ()))))
                    advanced = True
                    break
                if nxt in on_stack:
                    low[node] = min(low[node], idx_of[nxt])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == idx_of[node]:
                scc: set[str] = set()
                while True:
                    top = stack.pop()
                    on_stack.discard(top)
                    scc.add(top)
                    if top == node:
                        break
                out.append(scc)
    return out


RULES = [
    ProjectRule(
        id="FLOW1001",
        family="flow",
        summary="donated jit argument read after the call without "
        "rebinding — the buffer's memory backs the call's outputs",
        check=check_use_after_donate,
    ),
    ProjectRule(
        id="FLOW1002",
        family="flow",
        summary="request/record-derived value reaches a jit "
        "shape-determining sink without a sanctioned bucketing function",
        check=check_recompile_taint,
    ),
    ProjectRule(
        id="FLOW1003",
        family="flow",
        summary="create_task/ensure_future handle that never escapes its "
        "frame — route through core/asyncutil.spawn_retained",
        check=check_unretained_task,
    ),
    ProjectRule(
        id="FLOW1004",
        family="flow",
        summary="lock-order cycle in the project-wide lock-acquisition "
        "graph (with-spans composed with the call graph)",
        check=check_lock_order,
    ),
]
