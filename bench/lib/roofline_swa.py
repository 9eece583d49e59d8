"""Operations and bytes the decode step and the prefill of a model need whose
attention layers are of two kinds in one stack, window and full, over a pool
a kind (``langstream_tpu/models/swa.py``: gated grouped-query attention with
normed heads, rotated on the window layers alone; a dense gated MLP in the
leading layers, then sigmoid-routed gated experts beside a shared one), from
the configuration file's published keys alone, and the least time a chip
could take for them. Named for the mechanism, not for a model.

What is family-free is taken from ``roofline_hybrid`` (``_floor``,
``chunk_samples``, ``config_of``), from ``roofline_latent`` (the pairing of a
prefill run with its flight sample, the scopes of a trace by program) and
from ``roofline_delta`` (this run's own trace). The floors count DATA bytes
only (each weight held here once a step, the live K and V rows once: a
window layer's are a slot's last ``sliding_window`` at most, a full layer's
all of them) and the algorithm's operations on the TRUE tokens and on the
pairs INSIDE a layer's mask, so that no share can read over 100%.

Steps in a trace are the paged read kernel's calls inside the decode
programs over the layers (every layer of every step calls it once, whatever
its kind), so no run has to lie whole in the trace (``roofline_latent``'s
rule). The programs trace no conditional: no container's time is in any
total here.
"""

from __future__ import annotations

import dataclasses

from lib.roofline_delta import own_trace
from lib.roofline_hybrid import _floor, chunk_samples, config_of

__all__ = ["SwaShape", "SCOPES", "shape_of", "window_rows", "masked_pairs",
           "read_floor", "flash_flops", "prefill_flops", "touched_experts",
           "experts_floor",
           "decode_step_floor", "per_step", "traced_steps", "scope_ms_step",
           "traced_prefills", "rows_saved_share"]

#: ``jax.named_scope`` names the programs add to the dense and the expert
#: layers' (``lib/hosttrace.py`` ``SCOPES``, ``lib/hybridtrace.py``)
SCOPES = ("swa_read", "full_read", "swa_flash", "full_flash", "qk_norm",
          "rope", "attn_gate", "attn_buf", "post_norm", "swa_write",
          "kv_write")
DECODE_PROGRAM = "decode_chunk"
PREFILL_PROGRAM = "prefill"
#: the op that IS the paged read kernel, ``paged_read.N`` (PERF.md 7 (j))
READ_KERNEL = r"^paged_read[._]"


@dataclasses.dataclass(frozen=True)
class SwaShape:
    """Static facts of the served share, from the configuration's file."""

    window_layers: int
    full_layers: int
    dense_layers: int               # leading layers with the gated MLP
    hidden: int
    vocab: int                      # rows of the embedding and of the head held
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    dense_width: int
    experts: int                    # the router's outputs
    experts_held: int
    experts_per_token: int
    expert_width: int
    shared_width: int
    weight_bytes: float = 2.0       # bf16, the router's weights too

    @classmethod
    def from_config(cls, config: dict) -> "SwaShape":
        layers = config["num_hidden_layers"]
        first = config.get("first_layer", 0)
        # the published list, read over the layers served here
        kinds = config["layer_types"][first:first + layers]
        return cls(
            window_layers=kinds.count("sliding_attention"),
            full_layers=kinds.count("full_attention"),
            dense_layers=config["num_dense_layers"],
            hidden=config["hidden_size"], vocab=config["vocab_size"],
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"], window=config["sliding_window"],
            dense_width=config["intermediate_size"],
            experts=config.get("published_num_experts", config["num_experts"]),
            experts_held=config["num_experts"],
            experts_per_token=config["num_experts_per_tok"],
            expert_width=config["moe_intermediate_size"],
            shared_width=(config["moe_intermediate_size"]
                          * config["num_shared_experts"]),
        )

    @property
    def layers(self) -> int:
        return self.window_layers + self.full_layers

    @property
    def sparse_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def attn_matmul_params(self) -> int:
        """One layer's attention: queries, keys, values, the output gate and
        the output projection."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return self.hidden * (2 * q + 2 * kv) + q * self.hidden

    @property
    def attn_layer_params(self) -> int:
        """With the input norm's and the post norm's gains (``hidden`` each)
        and the query's and the key's (``head_dim`` each)."""
        return self.attn_matmul_params + 2 * self.hidden + 2 * self.head_dim

    @property
    def dense_ffn_params(self) -> int:
        return 3 * self.hidden * self.dense_width

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
    def held_params(self) -> int:
        """Every weight held here: the layers (an FFN's two norms with it),
        the embedding's rows and the untied head's, the last norm."""
        return (self.layers * self.attn_layer_params
                + self.dense_layers * (self.dense_ffn_params + 2 * self.hidden)
                + self.sparse_layers * (
                    self.routed_params + self.shared_params
                    + self.router_params + self.experts + 2 * self.hidden)
                + 2 * self.vocab * self.hidden + self.hidden)

    @property
    def held_bytes(self) -> float:
        """The model's type throughout, but the selection bias (one value an
        expert a layer) in float32."""
        return (self.weight_bytes * self.held_params
                + (4.0 - self.weight_bytes) * self.sparse_layers * self.experts)

    @property
    def row_bytes(self) -> float:
        """One position's K and V rows of ONE layer."""
        return 2 * self.kv_heads * self.head_dim * self.weight_bytes


def shape_of(obs: dict) -> SwaShape | None:
    """The served shape, or None for a configuration of another family."""
    config = config_of(obs)
    if not config or "sliding_window" not in config \
            or "layer_types" not in config:
        return None
    return SwaShape.from_config(config)


def window_rows(length: float, window: int) -> float:
    """The rows of a slot of ``length`` that a window layer's query sees
    (and its pool has to give up): the last ``window`` at most."""
    return min(length, window)


def masked_pairs(tokens: int, window: int | None = None) -> float:
    """The (query, key) pairs inside the mask of a prompt of ``tokens``:
    query ``i`` sees ``i + 1`` keys under the causal mask and ``min(i + 1,
    window)`` under a window."""
    if window is None or tokens <= window:
        return tokens * (tokens + 1) / 2.0
    return window * (window + 1) / 2.0 + (tokens - window) * float(window)


def read_floor(shape: SwaShape, *, full_rows: float, window_rows: float,
               peaks: dict) -> dict:
    """One decode step's paged reads over both kinds: the live rows of every
    layer once (``full_rows`` of each full layer, ``window_rows`` of each
    window layer, both summed over the slots), or every head's two products
    over them, whichever is longer."""
    rows = shape.full_layers * full_rows + shape.window_layers * window_rows
    return _floor(rows * shape.row_bytes,
                  4 * shape.heads * shape.head_dim * rows, peaks)


def flash_flops(shape: SwaShape, prompts: list[int]) -> float:
    """The attention's operations of a prefill over the pairs INSIDE each
    layer's mask, for prompts of these true lengths: a score and a value
    product a pair a head."""
    per_pair = 4 * shape.heads * shape.head_dim
    return per_pair * sum(
        shape.full_layers * masked_pairs(n)
        + shape.window_layers * masked_pairs(n, shape.window)
        for n in prompts)


def mean_routed_pairs_token(shape: SwaShape) -> float:
    return shape.experts_per_token * shape.experts_held / shape.experts


def prefill_flops(shape: SwaShape, prompts: list[int]) -> float:
    """The model's operations for prompts of these true lengths at the share
    held: the attention's projections, the dense MLP, the shared expert and
    the router on every token, the held experts on the pairs the router
    sends here in the mean, the attention over the pairs inside each layer's
    mask, the head on each prompt's last token."""
    per_token = 2 * (
        shape.layers * shape.attn_matmul_params
        + shape.dense_layers * shape.dense_ffn_params
        + shape.sparse_layers * (
            shape.shared_params + shape.router_params
            + mean_routed_pairs_token(shape) * shape.expert_params))
    return (sum(prompts) * per_token + flash_flops(shape, prompts)
            + len(prompts) * 2 * shape.hidden * shape.vocab)


def touched_experts(shape: SwaShape, pairs_a_layer: float) -> float:
    """The held experts of one layer that ``pairs_a_layer`` routed pairs
    touch in the mean, the pairs falling evenly over the experts held: the
    flight samples carry a chunk's pairs and its fullest expert, not which
    experts got any. With few rows a step most of a share's experts get no
    pair (16 pairs over 32 experts touch 12.7), and the least a step has to
    read is the touched ones' weights, whatever the pass streams."""
    held = shape.experts_held
    if held <= 1:
        return float(min(held, pairs_a_layer))
    return held * (1.0 - (1.0 - 1.0 / held) ** max(pairs_a_layer, 0.0))


def touched_expert_params(shape: SwaShape, routed_pairs: float) -> float:
    """Parameters of the routed experts a step touches over all expert
    layers (``routed_pairs`` a step over all of them) and of the shared
    expert of each."""
    per_layer = routed_pairs / max(shape.sparse_layers, 1)
    return shape.sparse_layers * (
        touched_experts(shape, per_layer) * shape.expert_params
        + shape.shared_params)


def experts_floor(shape: SwaShape, *, routed_pairs: float, batch: float,
                  peaks: dict) -> dict:
    """One decode step's expert matmuls in every expert layer: the weights
    of the experts a step touches (:func:`touched_expert_params`) once, or
    the operations of the routed pairs (``routed_pairs`` a step over all
    layers) and of the shared expert on ``batch`` rows, whichever takes
    longer. A pass that streams every held expert whoever is chosen reads
    more than this floor counts."""
    bytes_ = shape.weight_bytes * touched_expert_params(shape, routed_pairs)
    flops = (routed_pairs * 2 * shape.expert_params
             + batch * shape.sparse_layers * 2 * shape.shared_params)
    return _floor(bytes_, flops, peaks)


def decode_step_floor(shape: SwaShape, *, full_rows: float,
                      window_rows: float, batch: float, routed_pairs: float,
                      peaks: dict) -> dict:
    """One whole decode step over ``batch`` running requests: every weight a
    step touches once (the head reads all its rows, the step gathers
    ``batch`` of the embedding's and touches the routed experts that got a
    pair: the rest of the embedding and the untouched experts are NOT
    counted), the live K and V rows of both kinds read and ``batch`` new
    ones a layer written."""
    unread = shape.weight_bytes * (
        shape.hidden * max(shape.vocab - batch, 0)
        + shape.sparse_layers * (shape.routed_params + shape.shared_params)
        - touched_expert_params(shape, routed_pairs))
    rows = (shape.full_layers * full_rows + shape.window_layers * window_rows
            + shape.layers * batch)
    bytes_ = shape.held_bytes - unread + rows * shape.row_bytes
    dense_params = (
        shape.layers * shape.attn_matmul_params
        + shape.dense_layers * shape.dense_ffn_params
        + shape.sparse_layers * (shape.shared_params + shape.router_params)
        + shape.hidden * shape.vocab)
    flops = (batch * 2 * dense_params
             + routed_pairs * 2 * shape.expert_params
             + 4 * shape.heads * shape.head_dim * (rows - shape.layers * batch))
    return _floor(bytes_, flops, peaks)


# -- what the flight samples say -------------------------------------------


def per_step(obs: dict) -> dict | None:
    """Means over the window's decode steps, from the flight samples that
    carry both kinds' rows: ``slots`` running at dispatch, ``full_rows``
    (cached rows a step reads of each FULL layer, summed over the slots:
    ``live_rows``), ``window_rows`` (of each WINDOW layer) and
    ``routed_pairs`` a step."""
    rows = [s for s in chunk_samples(obs) if s.get("live_rows") is not None
            and s.get("window_rows") is not None]
    steps = sum(s["steps"] for s in rows)
    if not steps:
        return None
    return {
        "steps": steps,
        "slots": sum(s["active_at_dispatch"] * s["steps"] for s in rows) / steps,
        # a full layer's rows grow by one a slot a step inside a chunk: the
        # chunk's mean; a window layer's stand still past the window
        "full_rows": sum(
            (s["live_rows"] + s["active_at_dispatch"] * (s["steps"] - 1) / 2)
            * s["steps"] for s in rows) / steps,
        "window_rows": sum(s["window_rows"] * s["steps"] for s in rows) / steps,
        "routed_pairs": sum(s["routed_pairs"] for s in rows) / steps,
    }


def rows_saved_share(obs: dict) -> float | None:
    """``1 - rows held / rows one table would hold`` over the window's
    decode chunks, weighted by their steps: what the window kind's ring
    spares the pools (``pool_rows_held``, ``pool_rows_one_table``:
    ``serving/engine.py`` ``_pool_rows``)."""
    rows = [s for s in obs.get("samples") or []
            if s.get("phase") == "decode" and s.get("steps")
            and s.get("pool_rows_one_table")]
    one = sum(s["pool_rows_one_table"] * s["steps"] for s in rows)
    if not one:
        return None
    return 1.0 - sum(s["pool_rows_held"] * s["steps"] for s in rows) / one


# -- what a traced run's trace says ----------------------------------------


def traced_steps(obs: dict) -> tuple[float, float]:
    """``(device seconds, decode steps)`` of the decode programs as far as
    the trace holds them: the seconds of the operations inside their runs,
    and the calls of the read kernel among them over the layers. A run cut
    by an end of the trace counts for what was seen of it in both. A read
    through XLA has no kernel to count: nothing."""
    from lib import xplane

    trace, shape = obs.get("trace"), shape_of(obs)
    if not trace or shape is None:
        return 0.0, 0.0
    calls = xplane.ops_in(trace, DECODE_PROGRAM, READ_KERNEL)["calls"]
    return (xplane.ops_in(trace, DECODE_PROGRAM, "")["total_s"],
            calls / shape.layers)


def read_kernel(obs: dict) -> dict | None:
    """Total seconds and calls of the paged read kernel inside the decode
    programs (both kinds of layer call the one kernel), or None."""
    from lib import xplane

    trace = obs.get("trace")
    if not trace or obs.get("paged_read_kernel") != "pallas":
        return None
    kernel = xplane.ops_in(trace, DECODE_PROGRAM, READ_KERNEL)
    return kernel if kernel["calls"] else None


def scope_seconds(path: str, program_part: str) -> dict:
    """``{"by_scope", "unscoped"}``: device seconds of the operations inside
    the programs whose name holds ``program_part``, by scope, this family's
    scopes known beside the dense and the expert layers'
    (``lib/roofline_latent.py`` ``scope_seconds`` with a longer list, for the
    length of one call)."""
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
    nor flash (a parent commit, a program of another family)."""
    key = f"swatrace.{program_part}"
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
    another family, an untraced run, or a program that names none of them."""
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
    (``lib/roofline_latent.py`` ``paired_prefills``: paired through the
    engine's host spans), from this run's own trace, computed once and kept
    in ``obs``. The cell dispatches one prompt a program (``prefill-batch``
    1), so a run's tokens are one prompt's length."""
    from lib import roofline_latent, xplane

    if "swaprefills" not in obs:
        obs["swaprefills"] = []
        path = own_trace() if obs.get("trace") and shape_of(obs) else None
        if path:
            obs["swaprefills"] = roofline_latent.paired_prefills(
                xplane.load(path), obs.get("samples") or [])
    return obs["swaprefills"]
