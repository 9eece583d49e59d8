"""Operations and bytes a ``solar_open2`` model's decode step and prefill
need (a gated delta-rule mixer with a decay a key channel or a gated
attention, then gated routed experts and a shared expert, in every layer;
``langstream_tpu/models/hybrid.py``), from the configuration file's published
keys alone, and the least time a chip could take for them.

``lib/roofline_granite.py`` reads the ``granitemoehybrid`` keys; this
family's file has others (``linear_attn_config``, ``gqa_layers``,
``moe_intermediate_size``, ``n_routed_experts``), a second recurrent state of
``heads x dk x dv`` float32 a layer a slot, an untied head and a prefill that
is a chunked kernel. What is family-free is taken from ``roofline_hybrid``
(``_floor``, ``chunk_samples``, ``per_step``, ``config_of``) and from
``roofline_latent`` (the pairing of a prefill run with its flight sample).
The floors count DATA bytes only (each weight held here once a step, each
running slot's state read once and written once, the live K and V rows once)
and the algorithm's operations on the TRUE tokens, so that no share can read
over 100%.

Steps in a trace are counted by the programs' scan over the model's layers:
the scanned block is one layer (its mixer, then its experts), so inside a run
of the decode-chunk program the most frequent op ran ``steps x layers`` times
(an op of a mixer runs less often: three layers of four have the delta rule,
the fourth the attention).
"""

from __future__ import annotations

import dataclasses
import os
import sys

from lib import hybridtrace
from lib.roofline_hybrid import _floor, chunk_samples, config_of, per_step

__all__ = ["DeltaShape", "SCOPES", "shape_of", "chunk_samples", "per_step",
           "delta_state_floor", "experts_floor", "decode_step_floor",
           "chunk_flops", "prefill_flops", "traced_steps", "scope_ms_step",
           "traced_prefills", "prefill_scope_share"]

#: ``jax.named_scope`` names the delta-rule programs add to the hybrid
#: family's (``lib/hybridtrace.py`` ``SCOPES``)
SCOPES = ("delta_in", "delta_conv", "delta_chunk", "delta_state", "delta_out",
          "delta_state_write", "attn_gate")
DECODE_PROGRAM = "decode_chunk"
PREFILL_PROGRAM = "prefill"
#: tokens of a chunk of the program's prefill (``HybridConfig.delta_chunk``)
CHUNK = 64


@dataclasses.dataclass(frozen=True)
class DeltaShape:
    """Static facts of the served share, from the configuration's file."""

    layers: int                     # published layers held: a mixer + experts
    attn_layers: int                # of them, those in ``gqa_layers``
    hidden: int
    vocab: int                      # rows of the embedding and of the head held
    heads: int
    kv_heads: int
    head_dim: int
    delta_heads: int
    delta_dim: int                  # keys and values alike
    gate_rank: int
    conv_kernel: int
    experts: int                    # the router's outputs
    experts_held: int
    experts_per_token: int
    expert_width: int
    shared_width: int
    weight_bytes: float = 2.0       # bf16
    router_bytes: float = 4.0       # the sigmoid router's weights are float32
    state_bytes: float = 4.0        # float32 recurrent state

    @classmethod
    def from_config(cls, config: dict) -> "DeltaShape":
        linear = config["linear_attn_config"]
        layers = config["num_hidden_layers"]
        return cls(
            layers=layers,
            # the published list, read up to the depth served here
            attn_layers=sum(1 for i in config["gqa_layers"] if i < layers),
            hidden=config["hidden_size"], vocab=config["vocab_size"],
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            delta_heads=linear["num_heads"], delta_dim=linear["head_dim"],
            gate_rank=config.get("kda_gate_rank", linear["head_dim"]),
            conv_kernel=linear["short_conv_kernel_size"],
            experts=config.get("published_n_routed_experts",
                               config["n_routed_experts"]),
            experts_held=config["n_routed_experts"],
            experts_per_token=config["num_experts_per_tok"],
            expert_width=config["moe_intermediate_size"],
            shared_width=(config["moe_intermediate_size"]
                          * config["n_shared_experts"]),
        )

    @property
    def delta_layers(self) -> int:
        return self.layers - self.attn_layers

    @property
    def delta_inner(self) -> int:
        return self.delta_heads * self.delta_dim

    @property
    def delta_layer_params(self) -> int:
        """One delta-rule mixer: q, k, v and output projections, the decay's
        and the output gate's two low-rank factors each, beta, the three
        convolutions, ``dt_bias`` and ``A_log``, the two norms' weights."""
        H, K, r = self.hidden, self.delta_inner, self.gate_rank
        return (4 * H * K + 2 * (H * r + r * K) + H * self.delta_heads
                + 3 * K * self.conv_kernel + K + self.delta_heads
                + self.delta_dim + H)

    @property
    def delta_matmul_params(self) -> int:
        """The part of it a token multiplies by."""
        H, K, r = self.hidden, self.delta_inner, self.gate_rank
        return 4 * H * K + 2 * (H * r + r * K) + H * self.delta_heads

    @property
    def attn_layer_params(self) -> int:
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return self.hidden * (2 * q + 2 * kv) + q * self.hidden + self.hidden

    @property
    def expert_params(self) -> int:
        """One gated expert: ``[a | b] = x W_in`` and ``W_out``."""
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
    def held_bytes(self) -> float:
        """Every weight held here: the layers, the embedding's rows and the
        untied head's, the last norm."""
        bf16 = (self.delta_layers * self.delta_layer_params
                + self.attn_layers * self.attn_layer_params
                + self.layers * (self.routed_params + self.shared_params
                                 + self.hidden)
                + 2 * self.vocab * self.hidden + self.hidden)
        # float32: the router's weights and bias, ``dt_bias`` and ``A_log``
        # (counted at two bytes above)
        return (self.weight_bytes * bf16
                + self.router_bytes * self.layers * (
                    self.router_params + self.experts)
                + (4.0 - self.weight_bytes) * self.delta_layers * (
                    self.delta_inner + self.delta_heads))

    @property
    def held_params(self) -> int:
        return (self.delta_layers * self.delta_layer_params
                + self.attn_layers * self.attn_layer_params
                + self.layers * (self.routed_params + self.shared_params
                                 + self.router_params + self.experts
                                 + self.hidden)
                + 2 * self.vocab * self.hidden + self.hidden)

    @property
    def delta_slot_bytes(self) -> float:
        """One slot's delta-rule state, all delta-rule layers (no tail)."""
        return (self.delta_layers * self.delta_heads * self.delta_dim ** 2
                * self.state_bytes)

    @property
    def conv_slot_bytes(self) -> float:
        return (self.delta_layers * (self.conv_kernel - 1) * 3
                * self.delta_inner * self.weight_bytes)

    @property
    def kv_row_bytes(self) -> float:
        """One position's K and V rows, all attention layers."""
        return (self.attn_layers * 2 * self.kv_heads * self.head_dim
                * self.weight_bytes)


def shape_of(obs: dict) -> DeltaShape | None:
    """The served shape, or None for a configuration of another family."""
    config = config_of(obs)
    if not config or "linear_attn_config" not in config \
            or "gqa_layers" not in config:
        return None
    return DeltaShape.from_config(config)


def delta_state_floor(shape: DeltaShape, *, slots: float, peaks: dict) -> dict:
    """One decode step's recurrences: each running slot's state of every
    delta-rule layer read once and written once; per state element the
    decay's multiply and a multiply-add each for ``u = S'^T k``, the rank-1
    update and ``o = S^T q``."""
    elements = slots * shape.delta_slot_bytes / shape.state_bytes
    return _floor(2 * slots * shape.delta_slot_bytes, 7 * elements, peaks)


def experts_floor(shape: DeltaShape, *, routed_pairs: float, batch: float,
                  peaks: dict) -> dict:
    """One decode step's expert matmuls in every layer: the held and the
    shared experts' weights once, or the operations of the routed pairs
    (``routed_pairs`` a step over all layers, three matrices' worth each)
    and of the shared expert on ``batch`` rows, whichever takes longer."""
    bytes_ = shape.weight_bytes * shape.layers * (
        shape.routed_params + shape.shared_params)
    flops = (routed_pairs * 2 * shape.expert_params
             + batch * shape.layers * 2 * shape.shared_params)
    return _floor(bytes_, flops, peaks)


def decode_step_floor(shape: DeltaShape, *, live_rows: float, batch: float,
                      routed_pairs: float, state_bytes: float,
                      peaks: dict) -> dict:
    """One whole decode step over ``batch`` running requests whose contexts
    hold ``live_rows`` tokens in all: every held weight once (the head reads
    all its rows, the step gathers ``batch`` of the embedding's: the rest of
    the embedding is NOT counted), the ``state_bytes`` of recurrent state and
    convolution tail the dispatched slots hold (the flight samples' counter)
    read and written, the live K and V rows read and ``batch`` new ones
    written."""
    unread = shape.weight_bytes * shape.hidden * max(shape.vocab - batch, 0)
    bytes_ = (shape.held_bytes - unread + 2 * state_bytes
              + (live_rows + batch) * shape.kv_row_bytes)
    dense_params = (
        shape.delta_layers * shape.delta_matmul_params
        + shape.attn_layers * (shape.attn_layer_params - shape.hidden)
        + shape.layers * (shape.shared_params + shape.router_params)
        + shape.hidden * shape.vocab
    )
    flops = (
        batch * 2 * dense_params
        + routed_pairs * 2 * shape.expert_params
        + 7 * batch * shape.delta_slot_bytes / shape.state_bytes
        + 4 * shape.heads * shape.head_dim * live_rows * shape.attn_layers
    )
    return _floor(bytes_, flops, peaks)


def chunk_flops(shape: DeltaShape, tokens: float, chunk: int = CHUNK) -> float:
    """The chunked delta rule's operations for ``tokens`` true tokens, every
    delta-rule layer and head: per token the chunk's two causal products
    over the key channels (``A`` and ``A'``: half of ``chunk`` pairs each in
    the mean), the unit lower-triangular system's substitution for ``[U |
    W_k]``, ``A' W``, and the three products with the state (``W_k S_0``,
    ``q S_0``, the state's update)."""
    d = shape.delta_dim
    per_token_head = (2 * chunk * d          # A and A': 2 x (chunk / 2) x 2 d
                      + chunk * 2 * d        # the system: (chunk / 2) x 2 x 2 d
                      + chunk * d            # A' W
                      + 6 * d * d)           # with the state, three products
    return tokens * shape.delta_layers * shape.delta_heads * per_token_head


def mean_routed_pairs_token(shape: DeltaShape) -> float:
    return shape.experts_per_token * shape.experts_held / shape.experts


#: rows of the engine's largest prefill batch (``ServingConfig.prefill_batch``)
PREFILL_ROWS = 8


def prefill_flops(shape: DeltaShape, tokens: float,
                  prompts: int = PREFILL_ROWS) -> float:
    """The model's operations for the ``tokens`` true tokens of one prefill
    batch at the share held: the mixers' projections, the chunked delta
    rule, the shared expert and the router on every token, the held experts
    on the pairs the router sends here in the mean, the head on a last
    token. The attention layer's causal pairs need each prompt's own
    length, and a batch's flight sample carries the batch's tokens as one
    number: they are counted as if the tokens were ``prompts`` equal
    prompts (the most rows a batch has), the least they can be, and the head
    for one prompt."""
    per_token = 2 * (
        shape.delta_layers * shape.delta_matmul_params
        + shape.attn_layers * (shape.attn_layer_params - shape.hidden)
        + shape.layers * (
            shape.shared_params + shape.router_params
            + mean_routed_pairs_token(shape) * shape.expert_params))
    each = tokens / max(prompts, 1)
    pairs = prompts * each * (each + 1) / 2.0
    return (tokens * per_token + chunk_flops(shape, tokens)
            + shape.attn_layers * 4 * shape.heads * shape.head_dim * pairs
            + 2 * shape.hidden * shape.vocab)


# -- what a traced run's trace says ----------------------------------------


def traced_steps(obs: dict, shape: DeltaShape) -> tuple[float, int]:
    """``(device seconds, decode steps)`` of the decode-chunk runs in the
    trace: inside a run the most frequent op ran ``steps x layers`` times; a
    run cut by an end of the trace is charged the steps that ran inside it."""
    from lib import xplane

    trace = obs.get("trace")
    if not trace:
        return 0.0, 0
    runs = xplane.program(trace, DECODE_PROGRAM)
    seconds, steps = 0.0, 0
    for duration, count in zip(runs["durations_s"], runs["op_counts"]):
        if count >= shape.layers:
            seconds += duration
            steps += round(count / shape.layers)
    return seconds, steps


def scope_seconds(path: str, program_part: str) -> dict:
    """``{"by_scope", "unscoped"}``: device seconds of the operations inside
    the programs whose name holds ``program_part``, by scope, this family's
    scopes known beside the hybrid and the dense families'
    (``lib/roofline_latent.py`` ``scope_seconds`` with a longer list, for the
    length of one call; ``hosttrace``'s list is not a parameter yet)."""
    from lib import hosttrace, roofline_latent

    known = hosttrace.SCOPES
    hosttrace.SCOPES = known + SCOPES
    try:    # which pools the planes, with the hybrid family's scopes known too
        return roofline_latent.scope_seconds(path, program_part)
    finally:
        hosttrace.SCOPES = known


def own_trace() -> str | None:
    """This run's own trace file: ``bench/run.py`` writes it under
    ``.bench_work/<cell>-<seed>-<trace>/trace``, named from the arguments this
    process was started with, and hands the readers no path (``obs`` carries
    none). ``hosttrace.find_trace()`` without a root takes the NEWEST trace
    under ``.bench_work/*/trace``, any run's, and two traced runs that overlap
    in one checkout then read each other's file while it is written (PERF.md
    7(m)). A process started otherwise (a test that calls a reader) falls back
    to that."""
    args = dict(zip(sys.argv[1:], sys.argv[2:]))
    if "--workload" in args and "--seed" in args:
        work = os.path.join(
            hybridtrace.hosttrace.ROOT, ".bench_work",
            f"{args['--workload']}-{int(args['--seed'])}-"
            f"{int(args.get('--trace', 0))}", "trace")
        if os.path.isdir(work):
            return hybridtrace.find_trace(work)
    return hybridtrace.find_trace()


def _scopes(obs: dict, program_part: str) -> dict | None:
    """This run's programs of one kind by scope, computed once and kept in
    ``obs``; None for a run that was not traced, whose trace is not found, or
    whose programs name none of this family's scopes (a parent commit, a
    program of another family)."""
    key = f"deltatrace.{program_part}"
    if key not in obs:
        obs[key] = None
        path = own_trace() if obs.get("trace") else None
        if path:
            obs[key] = scope_seconds(path, program_part)
    reduced = obs[key]
    if not reduced or not any(
            name.startswith("delta_") for name in reduced["by_scope"]):
        return None
    return reduced


def scope_ms_step(obs: dict, prefixes: tuple[str, ...]) -> float | None:
    """Device milliseconds a decode step spends under the scopes whose name
    starts with one of ``prefixes``, over the steps in the trace; None for
    another family, an untraced run, or a program that names none of them."""
    shape = shape_of(obs)
    if shape is None or not obs.get("trace"):
        return None
    reduced = _scopes(obs, DECODE_PROGRAM)
    _, steps = traced_steps(obs, shape)
    if not reduced or not steps:
        return None
    seconds = [s for name, s in reduced["by_scope"].items()
               if name.startswith(prefixes)]
    return 1e3 * sum(seconds) / steps if seconds else None


def traced_prefills(obs: dict) -> list[dict]:
    """The prefill program runs that lie whole in the trace, each with the
    true tokens of the batch it prefilled (``lib/roofline_latent.py``
    ``paired_prefills``: paired through the engine's host spans), from this
    run's own trace, computed once and kept in ``obs``."""
    from lib import roofline_latent, xplane

    if "deltaprefills" not in obs:
        obs["deltaprefills"] = []
        path = own_trace() if obs.get("trace") else None
        if path:
            obs["deltaprefills"] = roofline_latent.paired_prefills(
                xplane.load(path), obs.get("samples") or [])
    return obs["deltaprefills"]


def prefill_scope_share(obs: dict, prefixes: tuple[str, ...]) -> float | None:
    """The share of the prefill programs' device time (all their operations
    in the trace, scoped or not) spent under the scopes whose name starts
    with one of ``prefixes``."""
    if shape_of(obs) is None or not obs.get("trace"):
        return None
    reduced = _scopes(obs, PREFILL_PROGRAM)
    if not reduced:
        return None
    total = sum(reduced["by_scope"].values()) + sum(reduced["unscoped"].values())
    under = sum(s for name, s in reduced["by_scope"].items()
                if name.startswith(prefixes))
    return under / total if total and under else None
