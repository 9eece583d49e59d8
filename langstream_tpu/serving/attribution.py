"""Device attribution plane: per-program cost ledger + HBM memory ledger.

The flight recorder (PR 3) and the health/SLO plane (PR 8) decompose
*wall* time exactly — but BENCH_r05's 40.6 ms/step against an 11.8 ms
roofline (hbm_utilization 0.291) is a *device-side* gap, and one blended
roofline number cannot say which program, which phase of that program,
or which resident bytes own it. This module is the ledger that turns the
one-number roofline into per-program, per-owner truth — the TPU-native
analogue of the LangStream reference's per-agent runtime servlet
(``AgentInfoServlet``), but for XLA programs instead of JVM stats.

Two ledgers, one contract:

**Program cost ledger** (:class:`ProgramLedger`): for every jitted
serving variant the engine dispatches (prefill buckets, decode chunk
fns, continuation/verify programs), an *analytical* cost model —
weight bytes streamed, KV bytes read/written (paged layout and int8
aware), activation bytes, FLOPs — computed from the model config and
the program's static shape, paired with *measured* per-dispatch device
time (the flight recorder already times the dispatch's block-boundary
wait; samples are keyed by program id). ``/attribution`` then reports
achieved-vs-expected per program: the roofline gap decomposes into
named programs with their own rooflines.

**HBM memory ledger** (:func:`memory_ledger`): a live
``hbm_bytes_by_owner`` breakdown — weights, KV pool, sampler state,
device-LRU caches, and ``slack`` (detected limit minus accounted:
compiled programs, XLA scratch, allocator overhead — everything the
engine cannot see from host). Prefix-cache blocks live *inside* the KV
pool arrays, so they are reported as a sub-owner
(``kv_pool_prefix_bytes``), never double-counted: the owner sum plus
slack equals the allocator's ``bytes_limit`` exactly.

Cost-model assumptions (documented limits, not hidden ones):

- Decode/verify stream every live weight byte per fused step (the
  batch shares one pass); int8 weights count 1 byte/param with scales
  folded into the measured tree bytes.
- KV traffic counts the *window* actually swept by the program variant
  (the static bucket the jit specialized on), K and V both, one row
  written per new token; int8 KV rows are ``head_dim + 4`` bytes (the
  per-row f32 scale).
- Activation bytes are a lower bound: residual + norm + FFN
  intermediate per layer plus the logits row — enough to matter at a
  128k vocab, deliberately excluding XLA temporaries (those belong to
  the measured-vs-expected *gap*, which is the point).
- FLOPs are ``2 × params`` per token plus the attention window sweep —
  reported for context; the expected time is the HBM-bytes floor
  (decode is bandwidth-bound; a program whose achieved-vs-expected
  ratio is low while FLOP-heavy is compute-bound instead, and
  ``bench/lib/hosttrace.py`` is the post-mortem for that disagreement).
- MoE engines approximate: every expert's weights count as streamed
  (routed-expert reads are data-dependent; the host cannot know which
  experts fired). Ratios there are a *floor* on efficiency.

Hot-path discipline (graftcheck OBS505, the attribution twin of
OBS503/OBS504): registration and observation run on the engine loop —
plain dict/deque mutation, no locks, no I/O, no device syncs; readers
(:meth:`ProgramLedger.report`, the ``/attribution``/``/memory``
handlers) snapshot with ``dict()``/``list()`` copies and arithmetic
only, so an attribution poll can never perturb — or hang with — the
engine it measures.

Exposure: ``engine.stats()["attribution"]``, the pod ``/attribution``
and ``/memory`` endpoints, the control-plane fan-in beside ``/flight``,
``langstream_serving_hbm_bytes_*`` Prometheus gauges, and the
``engine_top`` attribution panels. See ``docs/OBSERVABILITY.md``.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any

import jax

#: program kinds the ledger knows (mirrors flight PHASES + the
#: continuation split the cost model needs)
PROGRAM_KINDS = ("decode", "prefill", "prefill-continue", "verify")


def tree_device_bytes(tree: Any) -> int:
    """Total device bytes of a pytree of arrays (0 for None/empty).
    Attribute reads only — never a device sync — so it is safe on the
    attribution read path (OBS505)."""
    if tree is None:
        return 0
    total = 0
    for leaf in jax.tree.leaves(tree):
        total += int(getattr(leaf, "nbytes", 0) or 0)
    return total


@dataclasses.dataclass(frozen=True)
class ModelShape:
    """The static model facts every program cost derives from — built
    once per engine so cost registration is pure arithmetic."""

    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    intermediate: int
    vocab: int
    #: total streamed weight bytes (measured from the live param tree,
    #: so int8 scales and MoE experts are included exactly)
    weight_bytes: int
    #: parameter count (exact for llama trees; estimated from bytes for
    #: MoE) — feeds the FLOPs term only
    param_count: int
    #: bytes per (position, kv-head) cache row, K or V (int8: head_dim
    #: + 4-byte scale; otherwise head_dim × dtype width)
    kv_row_bytes: int
    #: activation dtype width (2 bf16 / 4 f32)
    act_bytes: int


@dataclasses.dataclass(frozen=True)
class ProgramCost:
    """Analytical per-dispatch cost of one compiled program variant."""

    kind: str
    weight_bytes: int
    kv_read_bytes: int
    kv_write_bytes: int
    act_bytes: int
    flops: int
    #: the device's published HBM bandwidth; None off-TPU, where there is
    #: no roof and every expectation derived from it reads None
    hbm_gbps: float | None
    #: tokens the dispatch advances when fully active (normalization)
    tokens: int

    @property
    def total_bytes(self) -> int:
        return (
            self.weight_bytes + self.kv_read_bytes + self.kv_write_bytes
            + self.act_bytes
        )

    def expected_ms(self) -> float | None:
        """The HBM-bandwidth floor for one dispatch of this program."""
        if self.hbm_gbps is None:
            return None
        return self.total_bytes / (self.hbm_gbps * 1e9) * 1e3

    def to_dict(self) -> dict[str, Any]:
        return {
            "weight_bytes": self.weight_bytes,
            "kv_read_bytes": self.kv_read_bytes,
            "kv_write_bytes": self.kv_write_bytes,
            "act_bytes": self.act_bytes,
            "total_bytes": self.total_bytes,
            "flops": self.flops,
            "tokens": self.tokens,
            "expected_ms": (
                round(expected, 4)
                if (expected := self.expected_ms()) is not None
                else None
            ),
        }


def decode_cost(
    shape: ModelShape,
    *,
    slots: int,
    window_rows: int,
    k_steps: int,
    hbm_gbps: float | None,
) -> ProgramCost:
    """One decode-chunk dispatch: ``k_steps`` fused steps over the full
    ``slots`` batch, each streaming every weight byte and sweeping a
    ``window_rows`` KV window per slot (K and V), writing one new row
    per slot per step."""
    weight = k_steps * shape.weight_bytes
    kv_row = shape.kv_heads * shape.kv_row_bytes * 2  # K and V
    kv_read = k_steps * shape.layers * slots * window_rows * kv_row
    kv_write = k_steps * shape.layers * slots * kv_row
    act = k_steps * slots * shape.act_bytes * (
        shape.layers * (2 * shape.hidden + shape.intermediate) + shape.vocab
    )
    flops = k_steps * slots * (
        2 * shape.param_count
        + 4 * shape.heads * shape.head_dim * window_rows
    )
    return ProgramCost(
        kind="decode",
        weight_bytes=weight,
        kv_read_bytes=kv_read,
        kv_write_bytes=kv_write,
        act_bytes=act,
        flops=flops,
        hbm_gbps=hbm_gbps,
        tokens=k_steps * slots,
    )


def prefill_cost(
    shape: ModelShape,
    *,
    rows: int,
    tokens_per_row: int,
    prefix_rows: int,
    hbm_gbps: float | None,
) -> ProgramCost:
    """One (possibly batched) prefill dispatch: ``rows`` padded batch
    rows of ``tokens_per_row`` new tokens each. ``prefix_rows`` > 0 is
    the continuation path (suffix prefill against cached history): the
    program additionally reads that many KV rows per batch row."""
    kind = "prefill-continue" if prefix_rows else "prefill"
    weight = shape.weight_bytes  # streamed once for the whole batch
    kv_row = shape.kv_heads * shape.kv_row_bytes * 2
    kv_read = shape.layers * rows * prefix_rows * kv_row
    kv_write = shape.layers * rows * tokens_per_row * kv_row
    act = rows * shape.act_bytes * (
        tokens_per_row * shape.layers
        * (2 * shape.hidden + shape.intermediate)
        + shape.vocab  # logits at the last position only
    )
    # dense FLOPs for every new token, plus the causal attention sweep
    # (each new token attends its prefix: ~tokens/2 new + prefix_rows)
    flops = rows * tokens_per_row * (
        2 * shape.param_count
        + 4 * shape.heads * shape.head_dim
        * (tokens_per_row // 2 + prefix_rows)
    )
    return ProgramCost(
        kind=kind,
        weight_bytes=weight,
        kv_read_bytes=kv_read,
        kv_write_bytes=kv_write,
        act_bytes=act,
        flops=flops,
        hbm_gbps=hbm_gbps,
        tokens=rows,
    )


def verify_cost(
    shape: ModelShape,
    *,
    slots: int,
    window_rows: int,
    drafts: int,
    hbm_gbps: float | None,
) -> ProgramCost:
    """One speculative verify dispatch: every slot advances ``drafts+1``
    positions in one forward over the full KV window."""
    positions = drafts + 1
    weight = shape.weight_bytes
    kv_row = shape.kv_heads * shape.kv_row_bytes * 2
    kv_read = shape.layers * slots * window_rows * kv_row
    kv_write = shape.layers * slots * positions * kv_row
    act = slots * positions * shape.act_bytes * (
        shape.layers * (2 * shape.hidden + shape.intermediate) + shape.vocab
    )
    flops = slots * positions * (
        2 * shape.param_count
        + 4 * shape.heads * shape.head_dim * window_rows
    )
    return ProgramCost(
        kind="verify",
        weight_bytes=weight,
        kv_read_bytes=kv_read,
        kv_write_bytes=kv_write,
        act_bytes=act,
        flops=flops,
        hbm_gbps=hbm_gbps,
        tokens=slots * positions,
    )


def _pct(sorted_values: list, q: float):
    if not sorted_values:
        return None
    return sorted_values[
        min(len(sorted_values) - 1, int(q * len(sorted_values)))
    ]


class ProgramLedger:
    """Per-program achieved-vs-expected ledger.

    Single writer (the engine loop registers at dispatch preparation and
    observes at each flight record); many readers. Same cross-thread
    contract as the flight recorder: writes are plain dict/deque
    mutations (GIL-atomic container ops, no locks), readers snapshot
    with C-level ``dict()``/``list()`` copies before doing math
    (graftcheck OBS505 polices the read path)."""

    def __init__(self, window: int = 512):
        self.window = window
        # per program id: measured device-ms ring, dispatch count,
        # cumulative device seconds — registered BEFORE the cost entry
        # so a reader iterating _costs always finds the companions
        self._times: dict[str, deque] = {}
        self._dispatches: dict[str, int] = {}
        self._device_s: dict[str, float] = {}
        self._costs: dict[str, ProgramCost] = {}

    # -- writes (engine loop only; arithmetic + container ops) ----------

    def known(self, program: str) -> bool:
        return program in self._costs

    def register(self, program: str, cost: ProgramCost) -> None:
        if program in self._costs:
            return
        self._times[program] = deque(maxlen=self.window)
        self._dispatches[program] = 0
        self._device_s[program] = 0.0
        # published LAST: once visible in _costs, every companion exists
        self._costs[program] = cost

    def observe(self, program: str, device_s: float) -> None:
        """Record one dispatch's measured device time: the program's own
        time by the device's clock where it gave one (the flight sample's
        ``program_ms``, flight.py ``DispatchClock``: a batch dispatched one
        ahead reads its whole run, not what was left of it when the host
        came to wait), else the dispatch thread's wait plus the host work
        in its shadow. Unregistered ids are dropped (a registration always
        precedes the dispatch on the same thread, so this only guards torn
        test doubles)."""
        times = self._times.get(program)
        if times is None:
            return
        times.append(device_s * 1000.0)
        self._dispatches[program] = self._dispatches.get(program, 0) + 1
        self._device_s[program] = (
            self._device_s.get(program, 0.0) + device_s
        )

    # -- reads (snapshot + arithmetic; wait-free by contract) ------------

    def report(self) -> list[dict[str, Any]]:
        """One entry per registered program: the analytical expectation,
        the measured device-time distribution, and their ratio —
        heaviest (by cumulative device time) first."""
        out: list[dict[str, Any]] = []
        for program, cost in list(self._costs.items()):
            samples = sorted(list(self._times.get(program) or ()))
            dispatches = self._dispatches.get(program, 0)
            device_s = self._device_s.get(program, 0.0)
            measured_p50 = _pct(samples, 0.50)
            expected = cost.expected_ms()
            entry: dict[str, Any] = {
                "program": program,
                "kind": cost.kind,
                "dispatches": dispatches,
                "device_s_total": round(device_s, 4),
                "expected": cost.to_dict(),
                "measured_ms_p50": (
                    round(measured_p50, 4) if measured_p50 is not None else None
                ),
                "measured_ms_p95": (
                    round(p95, 4)
                    if (p95 := _pct(samples, 0.95)) is not None
                    else None
                ),
                # the per-program roofline: expected (bytes floor) over
                # measured — 1.0 means the program runs at the assumed
                # HBM bandwidth; low means THIS program owns gap
                "achieved_vs_expected": (
                    round(expected / measured_p50, 6)
                    if measured_p50 and expected is not None
                    else None
                ),
            }
            out.append(entry)
        out.sort(key=lambda e: -e["device_s_total"])
        return out

    def census(self) -> dict[str, int]:
        """Compact program-variant census (``{program: dispatches}``) —
        what bench records stamp so ``perf_diff`` can align rounds
        across code changes."""
        return dict(self._dispatches)


def memory_ledger(
    *,
    weights_bytes: int,
    kv_pool_bytes: int,
    prefix_blocks: int,
    bytes_per_block: int,
    sampler_bytes: int,
    tables_bytes: int,
    limit_bytes: int | None,
    in_transit_bytes: int = 0,
    kv_withheld_bytes: int = 0,
    recurrent_state_bytes: int = 0,
) -> dict[str, Any]:
    """Assemble the ``hbm_bytes_by_owner`` breakdown.

    ``slack`` is the detected limit minus every accounted owner —
    compiled programs, XLA scratch, allocator overhead: resident bytes
    the host cannot attribute. By construction the owner sum (slack
    included) equals ``limit_bytes`` exactly when a limit is known; a
    *negative* slack is reported honestly (the accounting is wrong and
    the operator must see it).
    Prefix-cache blocks live inside the KV pool arrays, so they are a
    sub-owner (``kv_pool_prefix_bytes``), never added to the sum — and
    so are budget blocks withheld by an adaptive pool-shrink
    (``kv_pool_withheld_bytes``, docs/RESILIENCE.md): the arrays stay
    allocated through a shrink, only the admission budget moves, so the
    owner sum is identical across shrink/restore by construction."""
    owners: dict[str, int] = {
        "weights": weights_bytes,
        "kv-pool": kv_pool_bytes,
        "sampler-state": sampler_bytes,
        "device-lru": tables_bytes,
        # KV handoff payloads serialized but not yet picked up by the
        # decode pool (docs/DISAGG.md): host-resident, but accounted in
        # the same ledger so a stalled handoff pipeline names its bytes
        "in-transit": in_transit_bytes,
    }
    if recurrent_state_bytes:
        # a hybrid model's per-slot recurrent state beside its pool
        # (models/hybrid.py): allocated once for every slot, never paged
        owners["recurrent-state"] = recurrent_state_bytes
    accounted = sum(owners.values())
    slack = None
    if limit_bytes is not None:
        slack = limit_bytes - accounted
        owners["slack"] = slack
    return {
        "hbm_bytes_by_owner": owners,
        "accounted_bytes": accounted,
        "kv_pool_prefix_bytes": prefix_blocks * bytes_per_block,
        "kv_pool_withheld_bytes": kv_withheld_bytes,
        "limit_bytes": limit_bytes,
        "slack_bytes": slack,
    }
