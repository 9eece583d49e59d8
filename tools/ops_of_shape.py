"""Every op whose result has a given shape, from a compiled program's text
or from a profiler trace, with its time where there is one.

    python3 tools/ops_of_shape.py --shape 'bf16[24,901,64,1024]' \\
        [--hlo program.txt ...] [--trace <dir or .xplane.pb> ...]

What "no copy of the pool" is read from: a commit of a few hundred K/V rows
(``models/paged.py`` ``write_rows``) may leave one op of the stacked pool's
shape in a serving program, the scatter itself, in place on the donated
pool. ``--hlo`` takes optimised HLO (``jitted.lower(...).compile().as_text()``)
and prints ``<name> <opcode> <shape>`` of each instruction with such a
result, whatever computation it sits in; ``--trace`` takes what
``jax.profiler`` wrote (a pod's ``/profile/start?dir=...``; ``bench/run.py``
removes its own when it ends, so there hand :func:`trace_ops_of_shape` the
run's reduction instead) and prints each such device op with its seconds,
calls and program, through the benchmark's own reduction
(``bench/lib/xplane.py``). A shape is spelt as HLO spells it, without the
layout: ``s8[32,1228,64,1024]``. The last line is the count found.
"""

from __future__ import annotations

import argparse
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# %copy.36 = bf16[24,901,64,1024]{3,2,1,0:T(8,128)(2,1)} copy(%p), ...
# ROOT %fusion.3 = (bf16[2,8]{1,0}, s32[4]{0}) fusion(...), kind=kLoop, ...
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+) = (?P<result>.*?) (?P<opcode>[a-z][\w\-]*)\(")
_ARRAY = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
# what names a value without computing one: no time, no bytes moved
FREE = frozenset({"parameter", "get-tuple-element", "tuple", "bitcast",
                  "constant", "while", "conditional", "call"})


def hlo_ops_of_shape(hlo_text: str, shapes) -> list[tuple[str, str, str]]:
    """``(name, opcode, shape)`` of every instruction of an HLO module's
    text one of whose results (a tuple's elements each count) has one of
    ``shapes``, in the text's order."""
    wanted = {s.replace(" ", "") for s in shapes}
    found = []
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        for shape in _ARRAY.findall(m.group("result")):
            if shape in wanted:
                found.append((m.group("name"), m.group("opcode"), shape))
                break
    return found


def moved(ops) -> list[tuple[str, str, str]]:
    """The ops of :func:`hlo_ops_of_shape` that compute or move a value."""
    return [op for op in ops if op[1] not in FREE]


def _bench_lib():
    """``bench/lib``'s trace readers (the benchmark's own reduction)."""
    bench = os.path.join(ROOT, "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from lib import hosttrace, xplane

    return hosttrace, xplane


def reduce_trace(path: str) -> dict:
    """The benchmark's reduction of the trace at ``path``, a ``.xplane.pb``
    or a directory with one somewhere under it (the newest)."""
    hosttrace, xplane = _bench_lib()
    if os.path.isdir(path):
        found = hosttrace.find_trace(path)
        if found is None:
            raise FileNotFoundError(f"no *.xplane.pb under {path}")
        path = found
    return xplane.reduce(xplane.load(path))


def trace_ops_of_shape(reduced: dict, shapes) -> list[dict]:
    """``{"name", "total_s", "calls", "program"}`` of every device op of a
    reduced trace whose (first) result has one of ``shapes``, longest
    first."""
    suffixes = tuple(_bench_lib()[1]._sanitize(s) for s in shapes)
    found = [
        {k: op[k] for k in ("name", "total_s", "calls", "program")}
        for plane in reduced["planes"] for op in plane["ops"]
        if op["name"].endswith(suffixes)
    ]
    return sorted(found, key=lambda op: -op["total_s"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", action="append", required=True)
    ap.add_argument("--hlo", action="append", default=[])
    ap.add_argument("--trace", action="append", default=[])
    args = ap.parse_args(argv)
    count = 0
    for path in args.hlo:
        with open(path) as f:
            ops = hlo_ops_of_shape(f.read(), args.shape)
        for name, opcode, shape in ops:
            free = " (free)" if opcode in FREE else ""
            print(f"{path}: {name} {opcode} {shape}{free}")
        count += len(moved(ops))
    for path in args.trace:
        ops = trace_ops_of_shape(reduce_trace(path), args.shape)
        for op in ops:
            print(f"{path}: {op['name']} {op['total_s']:.6f} s in "
                  f"{op['calls']} calls of {op['program']}")
        count += len(ops)
    print(f"{count} ops of shape {' or '.join(args.shape)} that move or compute")
    return 0


if __name__ == "__main__":
    sys.exit(main())
