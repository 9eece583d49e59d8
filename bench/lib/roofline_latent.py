"""Operations and bytes of a latent-attention model's programs (multi-head
latent attention, a leading dense layer, then group-limited routed experts
beside a shared one; ``langstream_tpu/models/latent.py``), from the
configuration file's published keys alone, and the least time a chip could
take for them.

The floors count what the algorithm needs at the share held, never what the
program happens to do: a latent row is the ``kv_lora_rank + qk_rope_head_dim``
values that are data (the pool pads a row to whole lane tiles; the padding
is the layout's cost, not the algorithm's), every weight held here once a
decode step, the live latent rows of every layer once a step.

Decode steps in a trace are the calls of the read kernel inside the decode
programs over the layers: every layer of every step calls it once, in the
dense stack's scan and in the expert stack's, so the count needs no run to
lie whole in the trace (4 s hold one or two chunks of 32 steps, as a rule
with one of them cut by an end). The window's means a step (slots, live
rows, routed pairs) come from the flight samples' ``steps``.

A configuration of another family, an untraced run and a program that names
none of the scopes (a parent commit) give nothing: every function here
returns None or zeros there, and the readers built on them leave their
metric out.
"""

from __future__ import annotations

import bisect
import dataclasses
import re

from lib import hybridtrace
from lib.roofline_hybrid import _floor, chunk_samples, config_of

#: ``jax.named_scope`` names the latent programs add to the dense family's
#: and the expert layer's (``lib/hosttrace.py``, ``lib/hybridtrace.py``)
SCOPES = ("mla_q", "mla_kv", "mla_expand", "mla_absorb")
#: the scopes of a decode step's attention block
ATTENTION_SCOPES = ("mla_", "kv_read", "attn_out")
#: the host spans around a prefill dispatch (``serving/flight.py`` SPANS),
#: the program they dispatch and its flash kernel's operation
DISPATCH_SPAN, FETCH_SPAN = "ls.prefill.dispatch", "ls.prefill.fetch"
PREFILL_PROGRAM = "prefill"
FLASH_KERNEL = r"^flash_prefill[._]"
#: the decode programs, and the read kernel each of their layers calls once
#: a step (``ops/paged_attention.py`` ``latent_read``)
DECODE_PROGRAM = "decode_chunk"
READ_KERNEL = r"^latent_read[._]"
#: how far the host's and the device's timelines may disagree
#: (``lib/hosttrace.py`` ``clock_skew_ns`` read 1.0-1.2 ms on the v5e)
SKEW_NS = 2e6
#: how long after its run's end a fetch may end and still be that run's
LATE_NS = 50e6


@dataclasses.dataclass(frozen=True)
class LatentShape:
    """Static facts of the served share, from the configuration's file."""

    layers: int
    dense_layers: int
    hidden: int
    vocab: int                  # rows of the embedding and of the head held
    heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    dense_width: int
    experts: int                # the router's outputs
    experts_held: int
    experts_per_token: int
    expert_width: int
    shared_width: int
    weight_bytes: float = 2.0   # bf16, the router's too

    @classmethod
    def from_config(cls, config: dict) -> "LatentShape":
        return cls(
            layers=config["num_hidden_layers"],
            dense_layers=config["first_k_dense_replace"],
            hidden=config["hidden_size"], vocab=config["vocab_size"],
            heads=config["num_attention_heads"],
            q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
            nope_dim=config["qk_nope_head_dim"],
            rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
            dense_width=config["intermediate_size"],
            experts=config.get("published_n_routed_experts",
                               config["n_routed_experts"]),
            experts_held=config["n_routed_experts"],
            experts_per_token=config["num_experts_per_tok"],
            expert_width=config["moe_intermediate_size"],
            shared_width=(config["n_shared_experts"]
                          * config["moe_intermediate_size"]),
        )

    @property
    def sparse_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def row_values(self) -> int:
        """One position's cache row, one layer: what is data."""
        return self.kv_rank + self.rope_dim

    @property
    def row_bytes(self) -> float:
        return self.row_values * self.weight_bytes

    @property
    def attn_params(self) -> int:
        """One layer's attention: W_qa, W_qb, W_kva, W_kvb, W_o, the norms."""
        return (self.hidden * self.q_rank
                + self.q_rank * self.heads * (self.nope_dim + self.rope_dim)
                + self.hidden * (self.kv_rank + self.rope_dim)
                + self.kv_rank * self.heads * (self.nope_dim + self.v_dim)
                + self.heads * self.v_dim * self.hidden
                + self.hidden + self.q_rank + self.kv_rank)

    @property
    def dense_ffn_params(self) -> int:
        return 3 * self.hidden * self.dense_width + self.hidden

    @property
    def expert_params(self) -> int:
        return 3 * self.hidden * self.expert_width

    @property
    def routed_params(self) -> int:
        """The experts held here, one layer."""
        return self.experts_held * self.expert_params

    @property
    def shared_params(self) -> int:
        return 3 * self.hidden * self.shared_width

    @property
    def router_params(self) -> int:
        return self.hidden * self.experts

    @property
    def held_params(self) -> int:
        """All of it: the layers, embedding and head rows, the last norm."""
        return (self.layers * self.attn_params
                + self.dense_layers * self.dense_ffn_params
                + self.sparse_layers * (
                    self.routed_params + self.shared_params
                    + self.router_params + self.hidden)
                + 2 * self.vocab * self.hidden + self.hidden)

    @property
    def read_flops_row(self) -> int:
        """One layer's absorbed read of one cached row: every head's score
        over the row and its value over the latent."""
        return 2 * self.heads * (self.row_values + self.kv_rank)

    @property
    def flash_flops_pair(self) -> int:
        """One (query, key) pair of one layer's expanded attention: every
        head's score over a key and its value."""
        return 2 * self.heads * (self.nope_dim + self.rope_dim + self.v_dim)


def shape_of(obs: dict) -> LatentShape | None:
    """The served shape, or None for a configuration of another family."""
    config = config_of(obs)
    if not config or "kv_lora_rank" not in config \
            or "n_routed_experts" not in config:
        return None
    return LatentShape.from_config(config)


# -- floors ---------------------------------------------------------------


def latent_read_floor(shape: LatentShape, *, live_rows: float,
                      peaks: dict) -> dict:
    """One call of the latent read (one layer, one step): the live rows
    once, or every head's operations over them, whichever takes longer (at
    the published widths 242 operations a byte, beside the v5e's 240)."""
    return _floor(live_rows * shape.row_bytes,
                  live_rows * shape.read_flops_row, peaks)


def flash_flops(shape: LatentShape, prompts: list[int]) -> float:
    """Every layer's expanded attention over the prompts' causal pairs."""
    return shape.layers * shape.flash_flops_pair * sum(
        p * (p + 1) / 2.0 for p in prompts)


def prefill_flops(shape: LatentShape, prompts: list[int],
                  routed_pairs_token: float) -> float:
    """The model's operations for the prompts' tokens at the share held:
    the projections (W_kvb expands every row to every head), the causal
    pairs, the dense FFN, the shared expert and the router on every token,
    the held experts on ``routed_pairs_token`` pairs a token a layer (the
    router sends experts_per_token x held / experts here in the mean), and
    the head on each prompt's last token."""
    tokens = float(sum(prompts))
    per_token = 2 * (
        shape.layers * shape.attn_params
        + shape.dense_layers * shape.dense_ffn_params
        + shape.sparse_layers * (
            shape.shared_params + shape.router_params
            + routed_pairs_token * shape.expert_params))
    return (tokens * per_token + flash_flops(shape, prompts)
            + len(prompts) * 2 * shape.hidden * shape.vocab)


def mean_routed_pairs_token(shape: LatentShape) -> float:
    return shape.experts_per_token * shape.experts_held / shape.experts


def experts_floor(shape: LatentShape, *, routed_pairs: float, batch: float,
                  peaks: dict) -> dict:
    """One decode step's expert matmuls in every expert layer: the held and
    the shared experts' weights once, or the operations of the routed pairs
    (``routed_pairs`` a step over all layers) and of the shared expert on
    ``batch`` rows, whichever takes longer."""
    bytes_ = shape.weight_bytes * shape.sparse_layers * (
        shape.routed_params + shape.shared_params)
    flops = (routed_pairs * 2 * shape.expert_params
             + batch * shape.sparse_layers * 2 * shape.shared_params)
    return _floor(bytes_, flops, peaks)


def decode_step_floor(shape: LatentShape, *, live_rows: float, batch: float,
                      routed_pairs: float, peaks: dict) -> dict:
    """One whole decode step over ``batch`` running requests whose contexts
    hold ``live_rows`` tokens in all: every weight held once (the head reads
    all its rows; of the embedding the step gathers ``batch`` rows), the
    live latent rows of every layer read and ``batch`` new ones written; the
    matmuls on ``batch`` rows (a step multiplies a row by all of a layer's
    attention: W_kvb as W_UK on the query and W_UV on the output), the
    routed pairs, and the reads' operations."""
    weights = shape.held_params - shape.vocab * shape.hidden \
        + batch * shape.hidden
    bytes_ = (shape.weight_bytes * weights
              + (live_rows + batch) * shape.layers * shape.row_bytes)
    per_row = 2 * (
        shape.layers * shape.attn_params
        + shape.dense_layers * shape.dense_ffn_params
        + shape.sparse_layers * (shape.shared_params + shape.router_params)
        + shape.hidden * shape.vocab)
    flops = (batch * per_row + routed_pairs * 2 * shape.expert_params
             + live_rows * shape.layers * shape.read_flops_row)
    return _floor(bytes_, flops, peaks)


# -- what a run's samples and trace say ------------------------------------


def per_step(obs: dict) -> dict | None:
    """Means over the window's decode steps, from the flight samples:
    ``slots`` running at dispatch, ``live_rows`` (cached rows a step reads
    of each layer, summed over the slots) and ``routed_pairs`` a step."""
    rows = [s for s in chunk_samples(obs) if s.get("live_rows") is not None]
    steps = sum(s["steps"] for s in rows)
    if not steps:
        return None
    return {
        "steps": steps,
        "slots": sum(s["active_at_dispatch"] * s["steps"] for s in rows) / steps,
        # rows grow by one a slot a step inside a chunk: the chunk's mean
        "live_rows": sum(
            (s["live_rows"] + s["active_at_dispatch"] * (s["steps"] - 1) / 2)
            * s["steps"] for s in rows) / steps,
        "routed_pairs": sum(s["routed_pairs"] for s in rows) / steps,
    }


def traced_steps(obs: dict) -> tuple[float, float]:
    """``(device seconds, decode steps)`` of the decode programs as far as
    the trace holds them: the seconds of the operations inside their runs,
    and the calls of the read kernel among them over the layers. A run cut
    by an end of the trace counts for what was seen of it in both, so no run
    has to be whole (a rule by the runs' lengths took a chunk cut at two
    thirds for a whole one and read the experts at 113% of their floor: my
    chip run, PR 34). A read through XLA has no kernel to count: nothing."""
    from lib import xplane

    trace, shape = obs.get("trace"), shape_of(obs)
    if not trace or shape is None:
        return 0.0, 0.0
    calls = xplane.ops_in(trace, DECODE_PROGRAM, READ_KERNEL)["calls"]
    return (xplane.ops_in(trace, DECODE_PROGRAM, "")["total_s"],
            calls / shape.layers)


def scope_seconds(path: str, program_part: str = DECODE_PROGRAM) -> dict:
    """``{"by_scope", "unscoped"}``: device seconds of the operations inside
    the programs whose name holds ``program_part``, by scope, the latent
    and the expert layer's scopes known beside the dense family's
    (``lib/hosttrace.py`` ``scope_seconds`` with a longer list, for the
    length of one call; that file's list is not a parameter yet)."""
    from lib import hosttrace, xplane

    known = hosttrace.SCOPES
    hosttrace.SCOPES = known + hybridtrace.SCOPES + SCOPES
    try:
        scopes = hosttrace.op_scopes(path)
        pooled: dict = {"by_scope": {}, "unscoped": {}}
        for plane in xplane.device_planes(xplane.load(path)):
            one = hosttrace.scope_seconds(plane, scopes, program_part)
            for kind in pooled:
                for name, seconds in one[kind].items():
                    pooled[kind][name] = pooled[kind].get(name, 0.0) + seconds
        return pooled
    finally:
        hosttrace.SCOPES = known


def decode_scopes(obs: dict) -> dict | None:
    """This run's decode programs by scope, computed once and kept in
    ``obs``; None for a run that was not traced or whose trace is not
    found."""
    if "latenttrace" not in obs:
        obs["latenttrace"] = None
        path = hybridtrace.find_trace() if obs.get("trace") else None
        if path:
            obs["latenttrace"] = scope_seconds(path)
    return obs["latenttrace"]


def scope_ms_step(obs: dict, prefixes: tuple[str, ...]) -> float | None:
    """Device milliseconds a decode step spends under the scopes whose name
    starts with one of ``prefixes``, over the decode steps in the trace
    (:func:`traced_steps`: operations and steps of the same runs, cut or
    whole)."""
    if shape_of(obs) is None or not obs.get("trace"):
        return None
    reduced = decode_scopes(obs)
    _, steps = traced_steps(obs)
    if not reduced or not steps:
        return None
    by_scope = reduced["by_scope"]
    if not any(name.startswith(SCOPES) for name in by_scope):
        return None          # a program of another family
    seconds = [s for name, s in by_scope.items() if name.startswith(prefixes)]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / steps


def paired_prefills(profile, samples: list[dict]) -> list[dict]:
    """The prefill program runs that lie whole in the trace, each with the
    true tokens of the batch it prefilled and the seconds of its flash
    kernel: ``[{"prompt_tokens", "seconds", "flash_s"}]``.

    The engine opens ``ls.prefill.dispatch`` before it hands a batch to the
    device and closes ``ls.prefill.fetch`` when it has the batch's first
    tokens, both with the dispatch's ordinal (``seq``), which the flight
    sample that carries ``prompt_tokens`` has too (``dispatch``). A batch's
    fetch ends when its run has (3 ms after it in a probe's trace), and the
    next run ends a prefill's length later (0.15 s and more): so a fetch
    takes the run that ended last before it, unless that was more than
    :data:`LATE_NS` before (a fetch the loop came late to, or one whose run
    ended before the trace began). A run is kept when the trace holds its
    dispatch span too, opened before the run began: it then began and ended
    inside the trace (a span opened before the trace began is not in it,
    so the run that was under way when the trace began is never paired).
    The two timelines may disagree by :data:`SKEW_NS`."""
    from lib import hosttrace, xplane

    tokens = {s["dispatch"]: s["prompt_tokens"] for s in samples
              if s.get("phase") == "prefill" and s.get("prompt_tokens")
              and s.get("dispatch") is not None}
    dispatched: dict = {}
    fetched: list[tuple[float, int]] = []
    for span in hosttrace.host_spans(profile):
        try:
            seq = int(span["meta"]["seq"])
        except (KeyError, TypeError, ValueError):
            continue
        if span["name"] == DISPATCH_SPAN:
            dispatched[seq] = min(span["start_ns"],
                                  dispatched.get(seq, span["start_ns"]))
        elif span["name"] == FETCH_SPAN:
            fetched.append((span["end_ns"], seq))
    fetched.sort()
    kernel = re.compile(FLASH_KERNEL)
    out = []
    for plane in xplane.device_planes(profile):
        lines = {line.name: line for line in plane.lines}
        if xplane.MODULES_LINE not in lines or xplane.OPS_LINE not in lines:
            continue
        runs = [(start, start + dur)
                for start, dur, name, _ in xplane._events(lines[xplane.MODULES_LINE])
                if PREFILL_PROGRAM in xplane.program_name(name)]
        flash = [(start, dur) for start, dur, name, stats
                 in xplane._events(lines[xplane.OPS_LINE])
                 if dur > 0 and kernel.search(xplane.op_name(name, stats))]
        starts = [start for start, _ in flash]
        ends = [end for _, end in runs]
        for end_ns, seq in fetched:
            i = bisect.bisect_right(ends, end_ns + SKEW_NS) - 1
            if i < 0 or end_ns - ends[i] > LATE_NS:
                continue
            start, end = runs[i]
            if seq not in tokens or seq not in dispatched \
                    or start < dispatched[seq] - SKEW_NS:
                continue
            inside = flash[bisect.bisect_left(starts, start):
                           bisect.bisect_left(starts, end)]
            out.append({"prompt_tokens": tokens[seq],
                        "seconds": (end - start) / 1e9,
                        "flash_s": sum(dur for _, dur in inside) / 1e9})
    return out


def traced_prefills(obs: dict) -> list[dict]:
    """:func:`paired_prefills` of this run's trace, computed once and kept
    in ``obs``; nothing for a run that was not traced, whose trace is not
    found, or whose program opens no such spans or carries no
    ``prompt_tokens`` (a parent commit)."""
    from lib import xplane

    if "latentprefills" not in obs:
        obs["latentprefills"] = []
        path = hybridtrace.find_trace() if obs.get("trace") else None
        if path:
            obs["latentprefills"] = paired_prefills(
                xplane.load(path), obs.get("samples") or [])
    return obs["latentprefills"]
