"""Operations and bytes a hybrid model's decode step needs (Mamba-2 +
attention + routed experts, ``langstream_tpu/models/hybrid.py``), from the
configuration file's published keys alone, and the least time a chip could
take for them. ``lib/roofline.py`` counts a dense GQA decoder and would
reckon a fifth of what this model's chip holds; the floors here count what
the algorithm needs, as those do: every weight byte held here once a step
(the router's and the experts' too: at a decode batch every held expert is
chosen by some row), each running slot's recurrent state read once and
written once, the live K and V rows of the attention layers once.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass(frozen=True)
class HybridShape:
    """Static facts of the served share, from the configuration's file."""

    pattern: str
    hidden: int
    vocab: int                  # rows of the embedding and head held here
    heads: int
    kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    ssm_state: int
    conv_kernel: int
    experts: int                # the router's outputs
    experts_held: int
    experts_per_token: int
    expert_width: int
    shared_width: int
    weight_bytes: float = 2.0   # bf16
    state_bytes: float = 4.0    # float32 recurrent state
    router_bytes: float = 4.0   # float32 router

    @classmethod
    def from_config(cls, config: dict) -> "HybridShape":
        return cls(
            pattern=config["hybrid_override_pattern"],
            hidden=config["hidden_size"], vocab=config["vocab_size"],
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            ssm_heads=config["mamba_num_heads"],
            ssm_head_dim=config["mamba_head_dim"],
            ssm_groups=config["n_groups"], ssm_state=config["ssm_state_size"],
            conv_kernel=config["conv_kernel"],
            experts=config.get("published_n_routed_experts",
                               config["n_routed_experts"]),
            experts_held=config["n_routed_experts"],
            experts_per_token=config["num_experts_per_tok"],
            expert_width=config["moe_intermediate_size"],
            shared_width=config["moe_shared_expert_intermediate_size"],
        )

    @property
    def mamba_layers(self) -> int:
        return self.pattern.count("M")

    @property
    def moe_layers(self) -> int:
        return self.pattern.count("E")

    @property
    def attn_layers(self) -> int:
        return self.pattern.count("*")

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
    def routed_params(self) -> int:
        """The experts held here, one layer."""
        return self.experts_held * 2 * self.hidden * self.expert_width

    @property
    def shared_params(self) -> int:
        return 2 * self.hidden * self.shared_width

    @property
    def router_params(self) -> int:
        return self.hidden * self.experts + self.experts

    @property
    def layer_weight_bytes(self) -> float:
        """Every layer's weights as served, without embedding and head."""
        return (
            self.weight_bytes * (
                self.mamba_layers * self.mamba_layer_params
                + self.attn_layers * self.attn_layer_params
                + self.moe_layers * (self.routed_params + self.shared_params
                                     + self.hidden)
            ) + self.router_bytes * self.moe_layers * self.router_params
        )

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


def config_of(obs: dict) -> dict | None:
    """The file of the configuration a run served, found by the name in its
    ``serving`` block: under ``bench/configs``, or a test's fixtures."""
    name = (obs.get("serving") or {}).get("model")
    if not name:
        return None
    for pattern in (os.path.join(BENCH, "configs", f"{name}.json"),
                    os.path.join(ROOT, "tests", "bench", "fixtures", "**",
                                 "configs", f"{name}.json")):
        for path in sorted(glob.glob(pattern, recursive=True)):
            with open(path) as f:
                return json.load(f)
    return None


def shape_of(obs: dict) -> HybridShape | None:
    """The served shape, or None for a configuration of another family."""
    config = config_of(obs)
    if not config or "hybrid_override_pattern" not in config:
        return None
    return HybridShape.from_config(config)


def _floor(bytes_: float, flops: float, peaks: dict) -> dict:
    t_bytes = bytes_ / peaks["hbm_bytes_s"]
    t_flops = flops / peaks["bf16_flops_s"]
    return {"bytes": bytes_, "flops": flops, "floor_s": max(t_bytes, t_flops),
            "bound_by": "bytes" if t_bytes >= t_flops else "flops"}


def ssm_state_floor(shape: HybridShape, *, slots: float, peaks: dict) -> dict:
    """One decode step's recurrences: each running slot's state of every
    Mamba-2 layer read once and written once; per state element a decay
    multiply, an update multiply-add and the output's multiply-add."""
    elements = slots * shape.ssm_slot_bytes / shape.state_bytes
    return _floor(2 * slots * shape.ssm_slot_bytes, 5 * elements, peaks)


def moe_experts_floor(shape: HybridShape, *, routed_pairs: float,
                      batch: float, peaks: dict) -> dict:
    """One decode step's expert matmuls in every MoE layer: the held and
    the shared experts' weights once, or the operations of the routed pairs
    (``routed_pairs`` a step over all layers) and of the shared expert on
    ``batch`` rows, whichever takes longer."""
    bytes_ = shape.weight_bytes * shape.moe_layers * (
        shape.routed_params + shape.shared_params)
    flops = (routed_pairs * 4 * shape.hidden * shape.expert_width
             + batch * shape.moe_layers * 2 * shape.shared_params)
    return _floor(bytes_, flops, peaks)


def decode_step_floor(shape: HybridShape, *, live_rows: float, batch: float,
                      routed_pairs: float, state_bytes: float,
                      peaks: dict) -> dict:
    """One whole decode step over ``batch`` running requests whose contexts
    hold ``live_rows`` tokens in all: every layer's weights and the head
    once (the embedding is gathered: ``batch`` rows of it), the
    ``state_bytes`` of recurrent state and convolution tail the dispatched
    slots hold (the flight samples' counter) read and written, the live K
    and V rows read and ``batch`` new ones written."""
    head = shape.weight_bytes * shape.hidden * (shape.vocab + 1 + batch)
    state = 2 * state_bytes
    kv = (live_rows + batch) * shape.kv_row_bytes
    bytes_ = shape.layer_weight_bytes + head + state + kv
    dense_params = (
        shape.mamba_layers * shape.mamba_layer_params
        + shape.attn_layers * shape.attn_layer_params
        + shape.moe_layers * (shape.shared_params + shape.router_params)
        + shape.hidden * shape.vocab
    )
    flops = (
        batch * 2 * dense_params
        + routed_pairs * 4 * shape.hidden * shape.expert_width
        + 5 * batch * shape.ssm_slot_bytes / shape.state_bytes
        + 4 * shape.heads * shape.head_dim * live_rows * shape.attn_layers
    )
    return _floor(bytes_, flops, peaks)


# -- what a traced run's counters and trace say ---------------------------


def chunk_samples(obs: dict) -> list[dict]:
    """The window's decode samples that carry a hybrid chunk's counters."""
    return [s for s in obs.get("samples") or []
            if s.get("phase") == "decode" and s.get("steps")
            and s.get("routed_pairs") is not None]


def per_step(obs: dict) -> dict | None:
    """Means over the window's decode steps, from the flight samples:
    ``slots`` running at dispatch, their ``state_bytes`` and the
    ``routed_pairs`` a step."""
    rows = chunk_samples(obs)
    steps = sum(s["steps"] for s in rows)
    if not steps:
        return None
    return {
        "steps": steps,
        "slots": sum(s["active_at_dispatch"] * s["steps"] for s in rows) / steps,
        "state_bytes": sum(s["state_bytes"] * s["steps"] for s in rows) / steps,
        "routed_pairs": sum(s["routed_pairs"] for s in rows) / steps,
    }


def traced_steps(obs: dict, shape: HybridShape) -> tuple[float, int]:
    """``(device seconds, decode steps)`` of the decode-chunk runs in the
    trace. The programs scan the model's blocks ``M [*] E`` (one a Mamba-2
    layer), so inside a run the most frequent op ran ``steps x Mamba-2
    layers`` times; a run cut by an end of the trace is charged the steps
    that ran inside it."""
    from lib import xplane

    trace = obs.get("trace")
    if not trace:
        return 0.0, 0
    runs = xplane.program(trace, "decode_chunk")
    seconds, steps = 0.0, 0
    for duration, count in zip(runs["durations_s"], runs["op_counts"]):
        if count >= shape.mamba_layers:
            seconds += duration
            steps += round(count / shape.mamba_layers)
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
