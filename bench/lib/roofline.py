"""Operations and bytes a step needs, from shapes alone (the benchmark's
copy of the arithmetic in ``serving/attribution.py``), and the least time a
chip could take for them. Divided by *device* durations from the trace, never
by a host wait.

The floors count what the algorithm needs, not what a program happens to
read: every weight byte once per decode step, and the K and V rows that are
live (tokens already in the context of the running requests), not the padded
window of every slot. So a share of the roofline can only pass 100% if the
shapes here are wrong.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Shape:
    """Static facts of a served dense-GQA model. ``weight_bytes`` is the
    bytes of the parameter tree as served (int8 weights with their scales,
    or bf16); ``kv_row_bytes`` the bytes of one (position, kv-head) row of K
    or of V as the pool stores it (int8: head_dim + 4-byte scale)."""

    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    intermediate: int
    vocab: int
    weight_bytes: int
    param_count: int
    kv_row_bytes: int

    @classmethod
    def from_widths(cls, w: dict, *, weight_dtype_bytes: float,
                    kv_quantized: bool) -> "Shape":
        qkv = w["heads"] * w["head_dim"]
        kv = w["kv_heads"] * w["head_dim"]
        per_layer = (
            w["hidden"] * (qkv + 2 * kv) + qkv * w["hidden"]
            + 3 * w["hidden"] * w["intermediate"]
        )
        params = (
            w["layers"] * per_layer + 2 * w["vocab_size"] * w["hidden"]
            + (2 * w["layers"] + 1) * w["hidden"]
        )
        return cls(
            layers=w["layers"], hidden=w["hidden"], heads=w["heads"],
            kv_heads=w["kv_heads"], head_dim=w["head_dim"],
            intermediate=w["intermediate"], vocab=w["vocab_size"],
            weight_bytes=int(params * weight_dtype_bytes),
            param_count=int(params),
            kv_row_bytes=(w["head_dim"] + 4) if kv_quantized
            else 2 * w["head_dim"],
        )


def decode_step_floor(shape: Shape, *, live_rows: float, batch: float,
                      peaks: dict) -> dict:
    """One decode step over ``batch`` running requests whose contexts hold
    ``live_rows`` tokens in all. The embedding table is gathered, not
    streamed, so only ``batch`` of its rows are counted."""
    embed_bytes = shape.vocab * shape.hidden * (
        shape.weight_bytes / max(shape.param_count, 1)
    )
    weight = shape.weight_bytes - embed_bytes
    kv_row = shape.kv_heads * shape.kv_row_bytes * 2  # K and V
    kv_read = shape.layers * live_rows * kv_row
    kv_write = shape.layers * batch * kv_row
    flops = batch * 2 * (shape.param_count - shape.vocab * shape.hidden) \
        + 4 * shape.heads * shape.head_dim * live_rows * shape.layers
    bytes_ = weight + kv_read + kv_write
    return _floor(bytes_, flops, peaks)


def paged_read_floor(shape: Shape, *, live_rows: float, peaks: dict) -> dict:
    """One call of the paged attention read (one layer, one decode step):
    the live K and V rows once, 4·H·D operations a row."""
    bytes_ = live_rows * shape.kv_heads * shape.kv_row_bytes * 2
    flops = 4 * shape.heads * shape.head_dim * live_rows
    return _floor(bytes_, flops, peaks)


def _floor(bytes_: float, flops: float, peaks: dict) -> dict:
    t_bytes = bytes_ / peaks["hbm_bytes_s"]
    t_flops = flops / peaks["bf16_flops_s"]
    return {
        "bytes": bytes_, "flops": flops,
        "floor_s": max(t_bytes, t_flops),
        "bound_by": "bytes" if t_bytes >= t_flops else "flops",
    }
