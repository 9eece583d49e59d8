"""KV-block handoff plane: serialize a request's paged-KV state for
disaggregated prefill/decode pools (docs/DISAGG.md).

Production engines split prefill and decode into separate pools
(DistServe/Splitwise): a prefill replica computes a prompt's KV blocks,
then hands the request to a decode replica so one long prompt can never
steal a decode step. This module is the wire between the pools — the
serialization half of ROADMAP item 3, carried over the existing pod HTTP
plane (``POST /kv/import`` / ``GET /kv/export/{request}``; a
device-to-device path can ride the same header later).

Wire format (version |WIRE_VERSION|)::

    b"LSKV" | u32 version | u32 header_len | header JSON | raw arrays

The JSON header carries the **layout fingerprint** (model, dtype,
kv-quantize mode, block size, cache geometry — the facts that decide
whether a foreign pool's rows can land in ours at all), the **prompt
digest** (chained blake2b, same construction as the prefix cache's
block digests), the generated-token snapshot, the per-request sampling
params, the **trace context** (``trace``: the ``langstream-trace``
header value, so the decode pool's ``engine.kv-import``/``engine.decode``
spans join the prefill-side trace; ``journey``: the request-journey
ledger key, serving/journey.py) with the prefill-side span ``timings``
(queue-wait / prefill / ttft), and an array manifest
(name/dtype/shape/byte offsets). Arrays
follow as raw bytes in manifest order: the K and V rows of the slot's
live positions, gathered dense from the paged pool — ``{"k","v"}`` for
bf16/f32 pools, ``{"k.q","k.s","v.q","v.s"}`` for int8 pools (the
quantized rows travel verbatim, so an export→import round trip is
bit-exact: no dequant/requant ever happens in transit).

Import is admission, not prefill: the receiving engine allocates blocks
through its :class:`~langstream_tpu.models.paged.BlockManager`, scatters
the rows back with :func:`~langstream_tpu.models.paged.write_rows`, and
the request joins the decode batch directly — greedy output is
byte-identical to a co-located run (pinned by test; the generated
tokens + sampling params + KV rows ARE the complete state, exactly the
invariant the QoS preemption snapshot already proved).

Hot-path discipline (graftcheck POOL701, OBS504's shape over this
module): serialization is header JSON plus ``tobytes`` on HOST arrays —
no blocking I/O, no locks, and the ONE device sync lives in the
sanctioned fetch point :func:`fetch_rows` (called on the engine's
dispatch thread and timed, like the engine's ``_fetch_chunk``).
"""

from __future__ import annotations

import hashlib
import json
import time
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from langstream_tpu.models.paged import gather_kv, write_rows

WIRE_MAGIC = b"LSKV"
WIRE_VERSION = 1

#: fingerprint keys that must match exactly between pools — a mismatch
#: on any of them means the raw rows are garbage in the other layout
FINGERPRINT_KEYS = (
    "model",
    "dtype",
    "kv-quantize",
    "kv-block-size",
    "layers",
    "kv-heads",
    "head-dim",
    "max-seq-len",
)


class LayoutMismatch(ValueError):
    """The payload cannot land in this engine: wrong magic/version, or a
    layout fingerprint that disagrees on any geometry/dtype fact. The
    pod ``/kv/import`` handler maps this to HTTP 409 — a refusal, never
    a retry (no decode replica of the same fleet will accept it either)."""


def trace_context(header: dict[str, Any]):
    """The handoff header's trace coordinate back as a
    :class:`~langstream_tpu.core.tracing.TraceContext` (None when the
    header carries none, or a malformed one — a bad trace must never
    refuse a handoff the layout accepts)."""
    from langstream_tpu.core.tracing import TraceContext

    return TraceContext.parse(header.get("trace"))


def journey_id(header: dict[str, Any]) -> str | None:
    """The request-journey ledger key riding the header: the explicit
    ``journey`` field, falling back to the trace id (they are the same
    value for traced requests — serving/journey.py)."""
    jid = header.get("journey")
    if isinstance(jid, str) and jid:
        return jid
    ctx = trace_context(header)
    return ctx.trace_id if ctx is not None else None


def prompt_digest(tokens) -> str:
    """Content digest of a prompt (blake2b over int64 token bytes) — the
    header's identity check and the flight events' request key."""
    h = hashlib.blake2b(digest_size=16)
    h.update(np.asarray(list(tokens), dtype=np.int64).tobytes())
    return h.hexdigest()


def check_fingerprint(ours: dict[str, Any], theirs: dict[str, Any]) -> None:
    """Raise :class:`LayoutMismatch` naming every disagreeing key."""
    bad = [
        k
        for k in FINGERPRINT_KEYS
        if ours.get(k) != theirs.get(k)
    ]
    if bad:
        detail = ", ".join(
            f"{k}: ours={ours.get(k)!r} theirs={theirs.get(k)!r}" for k in bad
        )
        raise LayoutMismatch(f"KV layout fingerprint mismatch ({detail})")


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------


def _np_dtype(name: str) -> np.dtype:
    """Resolve a manifest dtype string, including the ml_dtypes names
    (``bfloat16``) numpy alone does not know. An unresolvable name is a
    :class:`LayoutMismatch` — a refusal the pod maps to 409 — never a
    raw AttributeError that would drop the connection with no HTTP
    answer (the prefill side must be able to tell "don't retry" from
    "pod crashed")."""
    try:
        return np.dtype(name)
    except TypeError:
        try:
            import ml_dtypes

            return np.dtype(getattr(ml_dtypes, str(name)))
        except (AttributeError, TypeError, ImportError) as e:
            raise LayoutMismatch(
                f"unknown handoff array dtype {name!r}: {e}"
            ) from e


def serialize_handoff(
    header: dict[str, Any], arrays: dict[str, np.ndarray]
) -> bytes:
    """Pack header + arrays into the versioned wire format. Array order
    is the manifest order (sorted by name, so the bytes are a pure
    function of the content)."""
    manifest = []
    chunks: list[bytes] = []
    for name in sorted(arrays):
        arr = np.ascontiguousarray(arrays[name])
        manifest.append(
            {
                "name": name,
                "dtype": arr.dtype.name,
                "shape": list(arr.shape),
                "nbytes": int(arr.nbytes),
            }
        )
        chunks.append(arr.tobytes())
    full = {**header, "arrays": manifest}
    hjson = json.dumps(full, separators=(",", ":")).encode()
    head = (
        WIRE_MAGIC
        + WIRE_VERSION.to_bytes(4, "little")
        + len(hjson).to_bytes(4, "little")
    )
    return head + hjson + b"".join(chunks)


def peek_header(data: bytes) -> dict[str, Any]:
    """Parse and return the JSON header only (cheap, wait-free) —
    validates magic + version, never touches the array bytes."""
    if len(data) < 12 or data[:4] != WIRE_MAGIC:
        raise LayoutMismatch(
            "not a KV handoff payload (bad magic; expected LSKV)"
        )
    version = int.from_bytes(data[4:8], "little")
    if version != WIRE_VERSION:
        raise LayoutMismatch(
            f"unsupported KV handoff wire version {version} "
            f"(this engine speaks {WIRE_VERSION})"
        )
    hlen = int.from_bytes(data[8:12], "little")
    if len(data) < 12 + hlen:
        raise LayoutMismatch("truncated KV handoff payload (header)")
    try:
        header = json.loads(data[12 : 12 + hlen])
    except ValueError as e:
        raise LayoutMismatch(f"malformed KV handoff header: {e}") from e
    if not isinstance(header, dict):
        raise LayoutMismatch("malformed KV handoff header: not an object")
    return header


def deserialize_handoff(
    data: bytes, header: dict[str, Any] | None = None
) -> tuple[dict[str, Any], dict[str, np.ndarray]]:
    """Header + named arrays back from the wire. Arrays are zero-copy
    read-only views over ``data`` (the scatter's ``jnp.asarray`` copies
    to device anyway). A caller that already ran :func:`peek_header`
    (the pod's engine-routing step) passes it back so the header JSON —
    which embeds the full token lists — parses exactly once per
    import."""
    if header is None:
        header = peek_header(data)
    hlen = int.from_bytes(data[8:12], "little")
    offset = 12 + hlen
    arrays: dict[str, np.ndarray] = {}
    for entry in header.get("arrays") or []:
        nbytes = int(entry["nbytes"])
        if len(data) < offset + nbytes:
            raise LayoutMismatch(
                f"truncated KV handoff payload (array {entry['name']!r})"
            )
        arrays[entry["name"]] = np.frombuffer(
            data, dtype=_np_dtype(entry["dtype"]),
            count=int(np.prod(entry["shape"], dtype=np.int64)),
            offset=offset,
        ).reshape(entry["shape"])
        offset += nbytes
    return header, arrays


# ---------------------------------------------------------------------------
# gather (export side) — jit-pure + the sanctioned fetch point
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("num_blocks",))
def _gather_one(cache, tables, num_blocks: int):
    """Densify one slot's first ``num_blocks`` blocks (the paged
    reference read, batch of one)."""
    return gather_kv(cache, tables, num_blocks)


def gather_slot(cache_k, cache_v, table_row: np.ndarray, num_blocks: int):
    """Async-dispatch the gather of one slot's K and V blocks. Returns
    device arrays ``(L, 1, num_blocks*bs, KhD)`` (int8 pools: the
    ``{"q","s"}`` tree each) — call :func:`fetch_rows` to sync + slice."""
    tables = jnp.asarray(
        np.asarray(table_row, dtype=np.int32)[None, :num_blocks]
    )
    return (
        _gather_one(cache_k, tables, num_blocks),
        _gather_one(cache_v, tables, num_blocks),
    )


def _fetch_rows(gathered_k, gathered_v, rows: int):
    """The designated device fetch of the export path (graftcheck
    POOL701 polices syncs anywhere else in this module; the ``_fetch``
    prefix marks it a fetch stage for the whole-graph INV902 too): ONE
    timed block-and-copy per export, run on the engine's dispatch thread
    like ``_fetch_chunk``. Returns ``({name: host array},
    device_seconds)`` with arrays sliced to the slot's live ``rows``
    positions."""
    t_dev = time.monotonic()
    jax.block_until_ready((gathered_k, gathered_v))
    device_s = time.monotonic() - t_dev

    def _host(tree, prefix: str) -> dict[str, np.ndarray]:
        if isinstance(tree, dict):
            return {
                f"{prefix}.{leaf}": np.asarray(tree[leaf])[:, 0, :rows]
                for leaf in sorted(tree)
            }
        return {prefix: np.asarray(tree)[:, 0, :rows]}

    arrays = {**_host(gathered_k, "k"), **_host(gathered_v, "v")}
    return arrays, device_s


#: public spelling of the sanctioned fetch stage
fetch_rows = _fetch_rows


# ---------------------------------------------------------------------------
# scatter (import side) — jit-pure, donates the pools
# ---------------------------------------------------------------------------


@partial(jax.jit, donate_argnums=(0, 1), static_argnames=("kernel",))
def _scatter_pools(cache_k, cache_v, k_rows, v_rows, tables, starts, valid,
                   kernel="xla"):
    """Write one imported slot's rows into both pools (donated — the
    caller rebinds, same contract as every engine dispatch); ``valid`` is
    the interval ``[0, rows)``, ``kernel`` the commit's form
    (:func:`~langstream_tpu.models.paged.write_rows`)."""
    return (
        write_rows(cache_k, k_rows, tables, starts, valid, kernel),
        write_rows(cache_v, v_rows, tables, starts, valid, kernel),
    )


def _rows_tree(
    arrays: dict[str, np.ndarray], prefix: str, rows: int, padded: int
):
    """Rebuild one cache's row payload from the manifest arrays, padded
    to ``padded`` positions (pad rows are masked to the scratch block by
    ``valid``). int8 pools travel as the quantized ``{"q","s"}`` pair and
    scatter verbatim — bit-exact in transit."""

    def _pad(a: np.ndarray) -> jnp.ndarray:
        L = a.shape[0]
        out = np.zeros((L, 1, padded) + a.shape[2:], dtype=a.dtype)
        out[:, 0, :rows] = a[:, :rows]
        return jnp.asarray(out)

    if prefix in arrays:
        return _pad(arrays[prefix])
    quant = {
        leaf: _pad(arrays[f"{prefix}.{leaf}"])
        for leaf in ("q", "s")
        if f"{prefix}.{leaf}" in arrays
    }
    if set(quant) != {"q", "s"}:
        raise LayoutMismatch(
            f"handoff payload missing {prefix!r} rows "
            f"(have {sorted(arrays)})"
        )
    return quant


def scatter_slot(
    cache_k,
    cache_v,
    arrays: dict[str, np.ndarray],
    table_row: np.ndarray,
    rows: int,
    padded_rows: int,
    kernel: str = "xla",
):
    """Scatter an imported slot's rows into the (donated) pools via the
    slot's freshly allocated block table, in the form the engine's commits
    take (``kernel``: its ``pool_commit_kernel``). Returns the new pool
    handles — async dispatch; the caller's dispatch-thread closure
    syncs/times."""
    k_rows = _rows_tree(arrays, "k", rows, padded_rows)
    v_rows = _rows_tree(arrays, "v", rows, padded_rows)
    tables = jnp.asarray(np.asarray(table_row, dtype=np.int32)[None, :])
    starts = jnp.zeros((1,), dtype=jnp.int32)
    valid = jnp.asarray((np.arange(padded_rows) < rows)[None, :])
    return _scatter_pools(
        cache_k, cache_v, k_rows, v_rows, tables, starts, valid,
        kernel=kernel,
    )
