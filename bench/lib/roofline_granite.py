"""Operations and bytes a ``granitemoehybrid`` model's decode step needs (a
Mamba-2 mixer or attention, then gated routed experts and a shared expert,
in every layer; ``langstream_tpu/models/hybrid.py``), from the configuration
file's published keys alone, and the least time a chip could take for them.

``lib/roofline_hybrid.py`` reads the ``nemotron_h`` keys, counts two
matrices an expert and one sub-layer a layer; this family's file has other
keys (``layer_types``, ``mamba_n_heads``, ...), three matrices' worth an
expert (the input projection is two, ``[a | b]``), experts in EVERY layer
and a head that is the embedding. What is family-free is taken from there
(``_floor``, ``chunk_samples``, ``per_step``, ``config_of``). The floors
count what the algorithm needs: every weight byte held here once a step (at
a decode batch every held expert is chosen by some row), each running
slot's recurrent state read once and written once, the live K and V rows of
the attention layers once.

Steps in a trace are counted by the programs' scan over the model's layers:
the scanned block is one layer (its mixer, then its experts), so inside a
run of the decode-chunk program the most frequent op ran ``steps x layers``
times (an op of the Mamba-2 mixer runs less often: a layer may have the
attention in its place).
"""

from __future__ import annotations

import dataclasses

from lib.roofline_hybrid import _floor, chunk_samples, config_of, per_step

__all__ = ["GraniteShape", "shape_of", "chunk_samples", "per_step",
           "ssm_state_floor", "experts_floor", "decode_step_floor",
           "traced_steps", "scope_ms_step"]


@dataclasses.dataclass(frozen=True)
class GraniteShape:
    """Static facts of the served share, from the configuration's file."""

    layer_types: tuple[str, ...]    # "mamba" or "attention", a layer
    hidden: int
    vocab: int                      # rows of the tied embedding held here
    heads: int
    kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    ssm_state: int
    conv_kernel: int
    experts: int                    # the router's outputs
    experts_held: int
    experts_per_token: int
    expert_width: int
    shared_width: int
    weight_bytes: float = 2.0       # bf16, the router's too
    state_bytes: float = 4.0        # float32 recurrent state

    @classmethod
    def from_config(cls, config: dict) -> "GraniteShape":
        heads = config["num_attention_heads"]
        return cls(
            # the published list, read up to the depth served here
            layer_types=tuple(
                config["layer_types"][: config["num_hidden_layers"]]),
            hidden=config["hidden_size"], vocab=config["vocab_size"],
            heads=heads, kv_heads=config["num_key_value_heads"],
            head_dim=config.get("head_dim") or config["hidden_size"] // heads,
            ssm_heads=config["mamba_n_heads"],
            ssm_head_dim=config["mamba_d_head"],
            ssm_groups=config["mamba_n_groups"],
            ssm_state=config["mamba_d_state"],
            conv_kernel=config["mamba_d_conv"],
            experts=config.get("published_num_local_experts",
                               config["num_local_experts"]),
            experts_held=config["num_local_experts"],
            experts_per_token=config["num_experts_per_tok"],
            expert_width=config["intermediate_size"],
            shared_width=config["shared_intermediate_size"],
        )

    @property
    def layers(self) -> int:
        """Published layers: each a mixer and then the experts."""
        return len(self.layer_types)

    @property
    def mamba_layers(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def attn_layers(self) -> int:
        return self.layer_types.count("attention")

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def mamba_layer_params(self) -> int:
        in_width = 2 * self.d_inner + 2 * self.ssm_groups * self.ssm_state \
            + self.ssm_heads
        return (self.hidden * in_width + self.d_inner * self.hidden
                + self.conv_dim * (self.conv_kernel + 1) + 3 * self.ssm_heads
                + self.d_inner + self.hidden)

    @property
    def attn_layer_params(self) -> int:
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return self.hidden * (q + 2 * kv) + q * self.hidden + self.hidden

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
    def layer_params(self) -> int:
        """Every layer's weights held here, without the embedding."""
        return (
            self.mamba_layers * self.mamba_layer_params
            + self.attn_layers * self.attn_layer_params
            + self.layers * (self.routed_params + self.shared_params
                             + self.router_params + self.hidden)
        )

    @property
    def held_params(self) -> int:
        """All of it: the layers, the tied embedding's rows, the last norm."""
        return self.layer_params + self.vocab * self.hidden + self.hidden

    @property
    def ssm_slot_bytes(self) -> float:
        """One slot's recurrent state, all Mamba-2 layers (no tail)."""
        return (self.mamba_layers * self.ssm_heads * self.ssm_head_dim
                * self.ssm_state * self.state_bytes)

    @property
    def conv_slot_bytes(self) -> float:
        return (self.mamba_layers * (self.conv_kernel - 1) * self.conv_dim
                * self.weight_bytes)

    @property
    def kv_row_bytes(self) -> float:
        """One position's K and V rows, all attention layers."""
        return (self.attn_layers * 2 * self.kv_heads * self.head_dim
                * self.weight_bytes)


def shape_of(obs: dict) -> GraniteShape | None:
    """The served shape, or None for a configuration of another family."""
    config = config_of(obs)
    if not config or "layer_types" not in config \
            or "mamba_n_heads" not in config:
        return None
    return GraniteShape.from_config(config)


def ssm_state_floor(shape: GraniteShape, *, slots: float, peaks: dict) -> dict:
    """One decode step's recurrences: each running slot's state of every
    Mamba-2 layer read once and written once; per state element a decay
    multiply, an update multiply-add and the output's multiply-add."""
    elements = slots * shape.ssm_slot_bytes / shape.state_bytes
    return _floor(2 * slots * shape.ssm_slot_bytes, 5 * elements, peaks)


def experts_floor(shape: GraniteShape, *, routed_pairs: float, batch: float,
                  peaks: dict) -> dict:
    """One decode step's expert matmuls in every layer: the held and the
    shared experts' weights once, or the operations of the routed pairs
    (``routed_pairs`` a step over all layers, 6 x hidden x width each: three
    matrices' worth) and of the shared expert on ``batch`` rows, whichever
    takes longer."""
    bytes_ = shape.weight_bytes * shape.layers * (
        shape.routed_params + shape.shared_params)
    flops = (routed_pairs * 2 * shape.expert_params
             + batch * shape.layers * 2 * shape.shared_params)
    return _floor(bytes_, flops, peaks)


def decode_step_floor(shape: GraniteShape, *, live_rows: float, batch: float,
                      routed_pairs: float, state_bytes: float,
                      peaks: dict) -> dict:
    """One whole decode step over ``batch`` running requests whose contexts
    hold ``live_rows`` tokens in all: every layer's weights once and the
    tied embedding once (the head reads all its rows; the ``batch`` rows the
    step gathers from it are among them), the ``state_bytes`` of recurrent
    state and convolution tail the dispatched slots hold (the flight
    samples' counter) read and written, the live K and V rows read and
    ``batch`` new ones written."""
    bytes_ = (shape.weight_bytes * shape.held_params + 2 * state_bytes
              + (live_rows + batch) * shape.kv_row_bytes)
    dense_params = (
        shape.mamba_layers * shape.mamba_layer_params
        + shape.attn_layers * shape.attn_layer_params
        + shape.layers * (shape.shared_params + shape.router_params)
        + shape.hidden * shape.vocab
    )
    flops = (
        batch * 2 * dense_params
        + routed_pairs * 2 * shape.expert_params
        + 5 * batch * shape.ssm_slot_bytes / shape.state_bytes
        + 4 * shape.heads * shape.head_dim * live_rows * shape.attn_layers
    )
    return _floor(bytes_, flops, peaks)


# -- what a traced run's trace says ----------------------------------------


def traced_steps(obs: dict, shape: GraniteShape) -> tuple[float, int]:
    """``(device seconds, decode steps)`` of the decode-chunk runs in the
    trace: inside a run the most frequent op ran ``steps x layers`` times
    (the scanned block is a layer); a run cut by an end of the trace is
    charged the steps that ran inside it."""
    from lib import xplane

    trace = obs.get("trace")
    if not trace:
        return 0.0, 0
    runs = xplane.program(trace, "decode_chunk")
    seconds, steps = 0.0, 0
    for duration, count in zip(runs["durations_s"], runs["op_counts"]):
        if count >= shape.layers:
            seconds += duration
            steps += round(count / shape.layers)
    return seconds, steps


def scope_ms_step(obs: dict, prefixes: tuple[str, ...]) -> float | None:
    """Device milliseconds a decode step spends under the scopes whose name
    starts with one of ``prefixes`` (``lib/hybridtrace.py``), over the steps
    in the trace; None for another family, an untraced run, or a program
    that names none of them (a parent commit)."""
    from lib import hybridtrace

    shape = shape_of(obs)
    if shape is None:
        return None
    seconds = hybridtrace.under(obs, prefixes)
    _, steps = traced_steps(obs, shape)
    if seconds is None or not steps:
        return None
    return 1e3 * seconds / steps
