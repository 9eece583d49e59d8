"""Device time of a hybrid model's decode program by scope.

``lib/hosttrace.py`` reduces a trace by the scope names of the dense
family's layer body (its ``SCOPES``). The hybrid family's programs
(``langstream_tpu/models/hybrid.py``, ``models/moe.py``) name more seams,
and this is the same reduction with those names added: nothing is parsed
twice, ``hosttrace``'s own functions run with the longer list for the
length of one call.

A program that names none of them (a parent commit serving a dense stand-in
under the configuration's name) gives the dense scopes alone, and the
readers built on this find nothing to read and return nothing.
"""

from __future__ import annotations

from lib import hosttrace, xplane

#: ``jax.named_scope`` names the hybrid programs add to ``hosttrace.SCOPES``
SCOPES = ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out", "ssm_state_write",
          "moe_router", "moe_dispatch", "moe_experts", "moe_shared",
          "moe_combine")
find_trace = hosttrace.find_trace


def reduce(path: str) -> dict:
    """``hosttrace.reduce`` of the trace at ``path`` with the hybrid
    scopes known."""
    dense = hosttrace.SCOPES
    hosttrace.SCOPES = dense + SCOPES
    try:
        return hosttrace.reduce(xplane.load(path), hosttrace.op_scopes(path))
    finally:
        hosttrace.SCOPES = dense


def of(obs: dict) -> dict | None:
    """This run's reduction, computed once and kept in ``obs``; None for a
    run that was not traced or whose trace is not found."""
    if "hybridtrace" not in obs:
        obs["hybridtrace"] = None
        path = find_trace() if obs.get("trace") else None
        if path:
            obs["hybridtrace"] = reduce(path)
    return obs["hybridtrace"]


def under(obs: dict, prefixes: tuple[str, ...]) -> float | None:
    """Device seconds of the decode programs under the scopes whose name
    starts with one of ``prefixes``; None where the trace names none."""
    reduced = of(obs)
    if not reduced:
        return None
    seconds = [s for name, s in reduced["scopes"]["by_scope"].items()
               if name.startswith(prefixes)]
    return sum(seconds) if seconds else None
