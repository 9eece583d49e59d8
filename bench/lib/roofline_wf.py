"""Operations and bytes the decode step and the prefill of the window-and-full
family's plain member need (``langstream_tpu/models/swa.py`` with no output
gate, no norm after a sub-layer, no dense layer and no shared expert: grouped
queries with normed heads, a rotation a layer kind, softmax-routed gated
experts in every layer, all of them held), from the configuration file's
published keys alone, and the least time a chip could take for them. Named
for the mechanism (window and full), not for a model.

``roofline_swa.SwaShape`` counts a gate's ``hidden x heads x head_dim`` a
layer, two post norms, a dense layer and a shared expert that this member
does not have, and is frozen; :class:`WfShape` replaces the counts and keeps
the names, so that every function of ``roofline_swa`` that is handed a shape
(``read_floor``, ``flash_flops``, ``prefill_flops``, ``touched_experts``,
``experts_floor``, ``decode_step_floor``) is taken as it is. The floors count
DATA bytes only and the algorithm's operations on the TRUE tokens and on the
pairs INSIDE a layer's mask, so that no share can read over 100%.

The functions that are handed a run (``obs``) find the shape themselves, so
they are this file's: the same rules (steps are the paged read kernel's
calls over the layers, a prefill run is paired with its flight sample), one
scope more (``rope_full``, the full layers' rotation) and two gauges more
(``short_slots``, ``window_blocks_held``: ``models/swa.py`` ``_pool_rows``).
A program that has none of them (a parent commit) gives nothing.
"""

from __future__ import annotations

import dataclasses

from lib import roofline_swa
from lib.roofline_delta import own_trace
from lib.roofline_hybrid import _floor, config_of
from lib.roofline_swa import (
    DECODE_PROGRAM,
    READ_KERNEL,
    decode_step_floor,
    experts_floor,
    flash_flops,
    per_step,
    prefill_flops,
    read_floor,
    read_kernel,
)

__all__ = ["WfShape", "SCOPES", "shape_of", "per_step", "read_floor",
           "flash_flops", "prefill_flops", "experts_floor", "dense_pass_flops",
           "decode_step_floor", "decode_floor", "read_kernel", "traced_steps",
           "scope_ms_step", "traced_prefills", "gauge_share"]

#: ``jax.named_scope`` names of the family's programs, with this member's
SCOPES = roofline_swa.SCOPES + ("rope_full",)


@dataclasses.dataclass(frozen=True)
class WfShape:
    """Static facts of the served stage, from the configuration's file."""

    window_layers: int
    full_layers: int
    hidden: int
    vocab: int                      # rows of the embedding and of the head
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    experts: int                    # the router's outputs, all held
    experts_per_token: int
    expert_width: int
    weight_bytes: float = 2.0       # bf16, the router's weights too
    # what this member lacks, under the names ``roofline_swa`` reads
    dense_layers: int = 0
    dense_ffn_params: int = 0
    shared_params: int = 0

    @classmethod
    def from_config(cls, config: dict) -> "WfShape":
        layers = config["num_hidden_layers"]
        first = config.get("first_layer", 0)
        # the published list, read over the layers served here
        kinds = config["layer_types"][first:first + layers]
        return cls(
            window_layers=kinds.count("sliding_attention"),
            full_layers=kinds.count("full_attention"),
            hidden=config["hidden_size"], vocab=config["vocab_size"],
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"], window=config["sliding_window"],
            experts=config["num_experts"],
            experts_per_token=config["num_experts_per_tok"],
            expert_width=config["moe_intermediate_size"],
        )

    @property
    def layers(self) -> int:
        return self.window_layers + self.full_layers

    @property
    def sparse_layers(self) -> int:
        return self.layers

    @property
    def experts_held(self) -> int:
        return self.experts

    @property
    def attn_matmul_params(self) -> int:
        """One layer's attention: queries, keys, values and the output
        projection."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return self.hidden * (q + 2 * kv) + q * self.hidden

    @property
    def attn_layer_params(self) -> int:
        """With the input norm's gains and the query's and the key's."""
        return self.attn_matmul_params + self.hidden + 2 * self.head_dim

    @property
    def expert_params(self) -> int:
        """One gated expert: ``[a | b] = x W_in`` and ``W_out``."""
        return 3 * self.hidden * self.expert_width

    @property
    def routed_params(self) -> int:
        return self.experts * self.expert_params

    @property
    def router_params(self) -> int:
        return self.hidden * self.experts

    @property
    def held_params(self) -> int:
        """Every weight held here: the layers (an expert layer's norm with
        it), the embedding's rows and the untied head's, the last norm."""
        return (self.layers * (self.attn_layer_params + self.routed_params
                               + self.router_params + self.hidden)
                + 2 * self.vocab * self.hidden + self.hidden)

    @property
    def held_bytes(self) -> float:
        return self.weight_bytes * self.held_params

    @property
    def row_bytes(self) -> float:
        """One position's K and V rows of ONE layer."""
        return 2 * self.kv_heads * self.head_dim * self.weight_bytes


def shape_of(obs: dict) -> WfShape | None:
    """The served shape, or None for a configuration of another member or
    family: this member's file has a rotation a layer kind
    (``rope_parameters``) and no ``num_dense_layers``."""
    config = config_of(obs)
    if not config or "sliding_window" not in config \
            or "rope_parameters" not in config or "num_dense_layers" in config:
        return None
    return WfShape.from_config(config)


def dense_pass_rows_max() -> int:
    """Rows up to which the program's routed pass is the dense one, read
    from the program (``models/moe.py``; the parent commit has it too)."""
    from langstream_tpu.models import moe

    return int(moe.DENSE_ROWS_MAX)


def dense_pass_flops(shape: WfShape, rows: float) -> float:
    """What the DENSE routed pass spends on ``rows`` rows of a decode
    batch in every layer: every held expert over every row
    (``models/moe.py`` ``dropless_experts_dense``), ``experts /
    experts_per_token`` times the routed pairs' operations."""
    return shape.layers * rows * shape.experts * 2 * shape.expert_params


def decode_floor(shape: WfShape, *, full_rows: float, window_rows: float,
                 batch: float, routed_pairs: float, rows: int,
                 peaks: dict) -> dict:
    """One whole decode step: ``roofline_swa.decode_step_floor``'s bytes
    (every held weight a step touches once, both kinds' live rows once), or
    the operations of the pass the program takes, whichever is longer: where
    the program's batch of ``rows`` rows takes the dense routed pass, its
    operations stand in for the routed pairs'."""
    floor = decode_step_floor(
        shape, full_rows=full_rows, window_rows=window_rows, batch=batch,
        routed_pairs=routed_pairs, peaks=peaks)
    flops = floor["flops"]
    if rows <= dense_pass_rows_max():
        flops += (dense_pass_flops(shape, rows)
                  - routed_pairs * 2 * shape.expert_params)
    return _floor(floor["bytes"], flops, peaks)


# -- what the flight samples say -------------------------------------------


def gauge_share(obs: dict, field: str, of) -> float | None:
    """The mean over the window's decode steps of the flight samples'
    ``field`` as a share of ``of(sample)``, weighted by the chunks' steps;
    None where no sample carries the field (a parent commit)."""
    rows = [s for s in obs.get("samples") or []
            if s.get("phase") == "decode" and s.get("steps")
            and s.get(field) is not None and of(s)]
    steps = sum(s["steps"] for s in rows)
    if not steps:
        return None
    return sum(s[field] / of(s) * s["steps"] for s in rows) / steps


# -- what a traced run's trace says ----------------------------------------


def traced_steps(obs: dict) -> tuple[float, float]:
    """``(device seconds, decode steps)`` of the decode programs as far as
    the trace holds them (``roofline_swa.traced_steps``'s rule: the read
    kernel's calls over the layers)."""
    from lib import xplane

    trace, shape = obs.get("trace"), shape_of(obs)
    if not trace or shape is None:
        return 0.0, 0.0
    calls = xplane.ops_in(trace, DECODE_PROGRAM, READ_KERNEL)["calls"]
    return (xplane.ops_in(trace, DECODE_PROGRAM, "")["total_s"],
            calls / shape.layers)


def scope_seconds(path: str, program_part: str) -> dict:
    """``{"by_scope", "unscoped"}``: device seconds of the operations inside
    the programs whose name holds ``program_part``, by scope, :data:`SCOPES`
    known beside the dense and the expert layers'."""
    from lib import hosttrace, roofline_latent

    known = hosttrace.SCOPES
    hosttrace.SCOPES = known + SCOPES
    try:
        return roofline_latent.scope_seconds(path, program_part)
    finally:
        hosttrace.SCOPES = known


def _scopes(obs: dict, program_part: str) -> dict | None:
    """This run's programs of one kind by scope, from its OWN trace,
    computed once and kept in ``obs``; None for a run that was not traced,
    whose trace is not found, or whose programs name neither kind's read
    nor flash."""
    key = f"wftrace.{program_part}"
    if key not in obs:
        obs[key] = None
        path = own_trace() if obs.get("trace") else None
        if path:
            obs[key] = scope_seconds(path, program_part)
    reduced = obs[key]
    if not reduced or not any(
            name.startswith(("swa_", "full_")) for name in reduced["by_scope"]):
        return None
    return reduced


def scope_ms_step(obs: dict, prefixes: tuple[str, ...]) -> float | None:
    """Device milliseconds a decode step spends under the scopes whose name
    starts with one of ``prefixes``, over the steps in the trace; None for
    another member, an untraced run, or a program that names none of them."""
    if shape_of(obs) is None or not obs.get("trace"):
        return None
    reduced = _scopes(obs, DECODE_PROGRAM)
    _, steps = traced_steps(obs)
    if not reduced or not steps:
        return None
    seconds = [s for name, s in reduced["by_scope"].items()
               if name.startswith(prefixes)]
    return 1e3 * sum(seconds) / steps if seconds else None


def traced_prefills(obs: dict) -> list[dict]:
    """The prefill program runs that lie whole in the trace, each with the
    true tokens of the prompt it prefilled and its flash kernels' seconds
    (``roofline_latent.paired_prefills``), from this run's own trace,
    computed once and kept in ``obs``. The cell dispatches one prompt a
    program (``prefill-batch`` 1), so a run's tokens are one prompt's."""
    from lib import roofline_latent, xplane

    if "wfprefills" not in obs:
        obs["wfprefills"] = []
        path = own_trace() if obs.get("trace") and shape_of(obs) else None
        if path:
            obs["wfprefills"] = roofline_latent.paired_prefills(
                xplane.load(path), obs.get("samples") or [])
    return obs["wfprefills"]
