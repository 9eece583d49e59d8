"""Operations and bytes the decode step and the prefill of an EVA decoder
need (``langstream_tpu/models/eva.py``: every layer reads the exact rows of
its query's own block-aligned window and one summary row a chunk of every
closed window, under one softmax), from the configuration file's published
keys alone, and the least time a chip could take for them. Named for the
mechanism, not for a model.

The floors count DATA bytes only (weights once, the live ring rows and the
visible summary rows once, the rows a step commits) and the algorithm's
operations on the TRUE tokens and on the pairs a query actually attends, so
that no share can read over 100% whatever implements them.

The functions that are handed a run (``obs``) read the family's gauges off
the flight samples (``window_rows``, ``summary_rows``, ``pool_rows_held``,
``pool_rows_plain_cache``: ``models/eva.py`` ``_pool_rows``) and its scopes
off the run's own trace (``eva_read``, ``eva_summarise``, ``eva_flash``,
``eva_summarise_prefill``, ``eva_write``). A program that has none of them
(a parent commit) gives nothing.
"""

from __future__ import annotations

import dataclasses

from lib.roofline_delta import own_trace
from lib.roofline_hybrid import _floor, config_of

__all__ = ["EvaShape", "SCOPES", "shape_of", "per_step", "read_floor",
           "decode_floor", "flash_flops", "prefill_flops", "traced_steps",
           "scope_ms_step", "scope_s_step", "traced_prefills",
           "rows_saved_share", "summary_rows_share"]

#: ``jax.named_scope`` names of the family's programs
SCOPES = ("eva_read", "eva_summarise", "eva_flash", "eva_summarise_prefill",
          "eva_write", "rope", "kv_write")
DECODE_PROGRAM = "decode_chunk"
#: the op that IS the paged read kernel, ``paged_read.N``: two calls a layer
#: a step here (the ring, the summary pool)
READ_KERNEL = r"^paged_read[._]"
#: the op that IS the prefill's attention kernel (``ops/eva_flash.py``)
FLASH_KERNEL = r"^eva_flash[._]"


@dataclasses.dataclass(frozen=True)
class EvaShape:
    """Static facts of the served stage, from the configuration's file."""

    layers: int
    hidden: int
    heads: int
    head_dim: int
    intermediate: int
    vocab: int
    pred_heads: int
    window: int
    chunk: int
    weight_bytes: int = 2     # bfloat16 weights and pool rows

    @classmethod
    def from_config(cls, config: dict) -> "EvaShape":
        return cls(
            layers=int(config["num_hidden_layers"]),
            hidden=int(config["hidden_size"]),
            heads=int(config["num_attention_heads"]),
            head_dim=int(config.get("head_dim") or config["hidden_size"]
                         // config["num_attention_heads"]),
            intermediate=int(config["intermediate_size"]),
            vocab=int(config["vocab_size"]),
            pred_heads=int(config["num_pred_heads"]),
            window=int(config["window_size"]),
            chunk=int(config["chunk_size"]),
        )

    @property
    def per_window(self) -> int:
        return self.window // self.chunk

    @property
    def layer_params(self) -> int:
        """Attention (four square projections, ``phi`` and ``mu``) and the
        gated MLP of one layer."""
        width = self.heads * self.head_dim
        return (4 * self.hidden * width + 2 * width
                + 3 * self.hidden * self.intermediate)

    @property
    def head_params(self) -> int:
        return self.hidden * self.pred_heads * self.vocab

    @property
    def step_params(self) -> int:
        """Every weight a decode step touches: the layers and the head (the
        embedding's rows are a row a slot)."""
        return self.layers * self.layer_params + self.head_params

    @property
    def row_bytes(self) -> int:
        """One position's K and V rows of ONE layer (a summary row's too)."""
        return 2 * self.heads * self.head_dim * self.weight_bytes


def shape_of(obs: dict) -> EvaShape | None:
    """The served shape, or None for a configuration of another family:
    this family's file has ``attention_class`` ``eva``."""
    config = config_of(obs)
    if not config or config.get("attention_class") != "eva":
        return None
    return EvaShape.from_config(config)


# -- the floors --------------------------------------------------------------


def read_floor(shape: EvaShape, *, window_rows: float, summary_rows: float,
               peaks: dict) -> dict:
    """One step's reads over all layers: the live ring rows and the visible
    summary rows once (``window_rows`` and ``summary_rows`` are sums over
    the slots of ONE layer's), or a multiply-add a query head a row's
    element for the scores and one for the values."""
    rows = shape.layers * (window_rows + summary_rows)
    return _floor(rows * shape.row_bytes,
                  rows * 4 * shape.heads * shape.head_dim, peaks)


def decode_floor(shape: EvaShape, *, window_rows: float, summary_rows: float,
                 batch: float, peaks: dict) -> dict:
    """One whole decode step: every weight it touches once, both pools'
    live rows once, the step's rows committed (a K/V row a slot a layer
    into the ring, and a chunk's ``C`` rows read back and one summary row
    written a slot every ``C`` steps); or its operations, whichever is
    longer."""
    read = read_floor(shape, window_rows=window_rows,
                      summary_rows=summary_rows, peaks=peaks)
    committed = shape.layers * batch * shape.row_bytes * (
        1 + (shape.chunk + 1) / shape.chunk)
    bytes_ = shape.weight_bytes * shape.step_params + read["bytes"] + committed
    flops = 2 * batch * shape.step_params + read["flops"]
    return _floor(bytes_, flops, peaks)


def attended_pairs(shape: EvaShape, tokens: int) -> tuple[float, float]:
    """``(exact pairs, summary pairs)`` a head attends over a prompt of
    ``tokens`` true tokens: each window causal over its own rows, and every
    row against the summaries of the windows closed before its own."""
    W, full, rest = shape.window, tokens // shape.window, tokens % shape.window
    exact = full * W * (W + 1) / 2 + rest * (rest + 1) / 2
    summary = shape.per_window * (W * full * (full - 1) / 2 + rest * full)
    return exact, summary


def flash_flops(shape: EvaShape, prompts: list[int]) -> float:
    """The prefill attention's operations over the pairs actually attended
    (scores and values: 4 a pair a head's element), all layers."""
    pairs = sum(sum(attended_pairs(shape, n)) for n in prompts)
    return shape.layers * shape.heads * 4 * shape.head_dim * pairs


def prefill_flops(shape: EvaShape, prompts: list[int]) -> float:
    """A whole prefill's operations for the prompts' TRUE tokens: the
    layers' weights on every token, the attention over the attended pairs,
    the chunks' summaries (a multiply-add an element for the chunk's scores
    and two for its sums) and the head on the last token."""
    tokens = sum(prompts)
    width = shape.heads * shape.head_dim
    return (2 * tokens * shape.layers * shape.layer_params
            + flash_flops(shape, prompts)
            + shape.layers * tokens * 6 * width
            + 2 * len(prompts) * shape.head_params)


# -- what the flight samples say ---------------------------------------------


def chunk_samples(obs: dict) -> list[dict]:
    return [s for s in obs.get("samples") or []
            if s.get("phase") == "decode" and s.get("steps")
            and s.get("summary_rows") is not None
            and s.get("window_rows") is not None]


def per_step(obs: dict) -> dict | None:
    """Means over the window's decode steps, from the flight samples that
    carry the family's gauges: ``slots`` running at dispatch, ``window_rows``
    (exact rows a step reads of each layer's ring, summed over the slots; a
    slot's grow by one a step inside a chunk: the chunk's mean, less what a
    window's close empties, which the next sample shows) and
    ``summary_rows``."""
    rows = chunk_samples(obs)
    steps = sum(s["steps"] for s in rows)
    if not steps:
        return None
    return {
        "steps": steps,
        "slots": sum(s["active_at_dispatch"] * s["steps"] for s in rows) / steps,
        "window_rows": sum(s["window_rows"] * s["steps"] for s in rows) / steps,
        "summary_rows": sum(s["summary_rows"] * s["steps"] for s in rows) / steps,
    }


def rows_saved_share(obs: dict) -> float | None:
    """``1 - rows held / rows a plain K/V cache would hold`` over the
    window's decode chunks, weighted by their steps."""
    rows = [s for s in chunk_samples(obs) if s.get("pool_rows_plain_cache")]
    plain = sum(s["pool_rows_plain_cache"] * s["steps"] for s in rows)
    if not plain:
        return None
    return 1.0 - sum(s["pool_rows_held"] * s["steps"] for s in rows) / plain


def summary_rows_share(obs: dict) -> float | None:
    """Summary rows of all the rows a step reads, over the window's decode
    steps."""
    load = per_step(obs)
    if not load or not load["window_rows"] + load["summary_rows"]:
        return None
    return load["summary_rows"] / (load["window_rows"] + load["summary_rows"])


# -- what a traced run's trace says ------------------------------------------


def traced_steps(obs: dict) -> tuple[float, float]:
    """``(device seconds, decode steps)`` of the decode programs as far as
    the trace holds them: the steps are the paged read kernel's calls over
    two reads a layer."""
    from lib import xplane

    trace, shape = obs.get("trace"), shape_of(obs)
    if not trace or shape is None:
        return 0.0, 0.0
    calls = xplane.ops_in(trace, DECODE_PROGRAM, READ_KERNEL)["calls"]
    return (xplane.ops_in(trace, DECODE_PROGRAM, "")["total_s"],
            calls / (2 * shape.layers))


def scope_seconds(path: str, program_part: str) -> dict:
    """``{"by_scope", "unscoped"}``: device seconds of the operations inside
    the programs whose name holds ``program_part``, by scope, :data:`SCOPES`
    known beside the dense family's."""
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
    whose trace is not found, or whose programs name none of the family's
    scopes."""
    key = f"evatrace.{program_part}"
    if key not in obs:
        obs[key] = None
        path = own_trace() if obs.get("trace") else None
        if path:
            obs[key] = scope_seconds(path, program_part)
    reduced = obs[key]
    if not reduced or not any(
            name.startswith("eva_") for name in reduced["by_scope"]):
        return None
    return reduced


def scope_s_step(obs: dict, prefixes: tuple[str, ...]) -> float | None:
    """Device seconds a decode step spends under the scopes whose name
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
    return sum(seconds) / steps if seconds else None


def scope_ms_step(obs: dict, prefixes: tuple[str, ...]) -> float | None:
    seconds = scope_s_step(obs, prefixes)
    return None if seconds is None else 1e3 * seconds


def traced_prefills(obs: dict) -> list[dict]:
    """The prefill program runs that lie whole in the trace, each with the
    true tokens of the prompt it prefilled and its attention kernel's
    seconds (``roofline_latent.paired_prefills`` told this family's kernel),
    from this run's own trace, computed once and kept in ``obs``. The cell
    dispatches one prompt a program (``prefill-batch`` 1)."""
    from lib import roofline_latent, xplane

    if "evaprefills" not in obs:
        obs["evaprefills"] = []
        path = own_trace() if obs.get("trace") and shape_of(obs) else None
        if path:
            known = roofline_latent.FLASH_KERNEL
            roofline_latent.FLASH_KERNEL = FLASH_KERNEL
            try:
                obs["evaprefills"] = roofline_latent.paired_prefills(
                    xplane.load(path), obs.get("samples") or [])
            finally:
                roofline_latent.FLASH_KERNEL = known
    return obs["evaprefills"]
