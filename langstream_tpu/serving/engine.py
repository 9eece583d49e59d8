"""Continuous-batching serving engine.

Execution model:

- A fixed pool of ``slots`` (the decode batch dimension). Each active slot
  owns blocks of the paged KV pool ``(L, blocks, block_size, K*D)`` through
  its row of the block table (models/paged.py).
- **Admission**: a queued request prefilles into a free slot (prompt padded
  to a power-of-two bucket → few compiled shapes) and immediately joins the
  decode batch. No stop-the-world: decode keeps a fixed batch shape, so a
  new arrival never recompiles anything.
- **Decode**: one jitted step advances *all* active slots one token;
  sampling happens in-jit (see sampler.py), only (B,) token ids come back.
- **At-least-once friendly**: generation is driven by the agent layer's
  record loop; the engine itself is agnostic to commits.
- **Sharding**: with a mesh, params are TP-sharded (Megatron), the pool
  shards KV heads on ``tp`` and is replicated over ``dp`` (any slot may use
  any block); XLA places the collectives on ICI.
  An ``sp`` axis makes long prefills sequence-parallel (ring attention);
  ``ep`` shards MoE experts.
- **Serving schedulers over the pool**: automatic prefix
  caching (shared prompt prefixes adopt content-addressed blocks; suffix-
  only prefill), chunked prefill (long prompts interleave with decode
  bursts), and prompt-lookup speculative decoding (greedy bursts verify
  drafted continuations — streams bit-identical to plain decode).

JAX calls are dispatched through a single-thread executor so the asyncio
event loop (broker I/O, gateways) never blocks on device execution —
compute/IO overlap comes free.

Parity anchor: replaces the external-HTTP ``CompletionsService`` /
``EmbeddingsService`` providers (``OpenAIServiceProvider.java:26`` etc.) with
an in-tree engine.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import os
import re
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Any, Awaitable, Callable

import jax
import jax.numpy as jnp
import numpy as np

from langstream_tpu.api.metrics import PrometheusMetricsReporter
from langstream_tpu.core.tracing import (
    TraceContext,
    current_context,
    fresh_trace_id,
    record_span,
)
from langstream_tpu.models.llama import (
    LlamaConfig,
    init_llama_params,
    llama_param_specs,
)
from langstream_tpu.models.encoder import (
    EncoderConfig,
    encode,
    encoder_param_specs,
    init_encoder_params,
)
from langstream_tpu.models.moe import grouped_form
from langstream_tpu.models.tokenizer import Tokenizer, load_tokenizer
from langstream_tpu.ops.pool_commit import commit_form
from langstream_tpu.serving.attribution import (
    ModelShape,
    ProgramLedger,
    decode_cost,
    memory_ledger,
    prefill_cost,
    tree_device_bytes,
    verify_cost,
)
from langstream_tpu.serving.faults import (
    FaultInjector,
    FaultPlan,
    InjectedFault,
    plans_from_env,
)
from langstream_tpu.serving.flight import PHASES, FlightRecorder, resumed
from langstream_tpu.serving.incident import (
    IncidentRecorder,
    adapter_eviction_storm,
    breaker_storm,
    worst_journeys,
)
from langstream_tpu.serving.handoff import (
    DeadlineExceeded,
    parse_deadline,
    remaining_s,
)
from langstream_tpu.serving.journal import RequestJournal, request_entry
from langstream_tpu.serving.journey import JOURNEYS
from langstream_tpu.serving.health import (
    EngineWatchdog,
    SloObjective,
    SloSpec,
    SloTracker,
)
from langstream_tpu.serving.streaming import STREAMS, TbtDigest
from langstream_tpu.serving.adapters import (
    AdapterStore,
    AdapterStoreSpec,
    AdapterUnavailable,
)
from langstream_tpu.serving.prefixstore import PrefixStore, PrefixStoreSpec
from langstream_tpu.serving.profiling import (
    ProfilerHooks,
    detect_hbm_bytes,
    device_peaks,
)
from langstream_tpu.serving.qos import (
    PRIORITY_CLASSES,
    QosSpec,
    RateLimited,
    normalize_priority,
    priority_rank,
)
from langstream_tpu.serving.sampler import sample_tokens
from langstream_tpu.serving.scheduler import make_scheduler, plan_wave

log = logging.getLogger(__name__)

_MODEL_CONFIGS = {
    "tiny": LlamaConfig.tiny,
    "llama-1b": LlamaConfig.llama_1b,
    "llama3-8b": LlamaConfig.llama3_8b,
    "llama-3-8b": LlamaConfig.llama3_8b,
    "llama3-70b": LlamaConfig.llama3_70b,
    "llama-3-70b": LlamaConfig.llama3_70b,
}

# MoE (Mixtral-family) models serve on the same engine: identical attention
# and cache geometry, routed-expert FFN plugged into the shared layer math
# (models/moe.py `moe_serving_ffn`). Lazy: moe.py imports only when used.
_MOE_MODELS = ("moe-tiny", "moe-8x7b", "mixtral-8x7b")

#: the names above, as the engine ships them: asking for one imports no
#: family's module (the benchmark writes further names into _MODEL_CONFIGS
#: from outside, a family's among them, and a family's name is its family's)
_DENSE_NAMES = frozenset(_MODEL_CONFIGS) | frozenset(_MOE_MODELS)


def _family_of(name: str):
    """The description of the family that serves ``name`` with programs of
    its own beside the paged pool (models/family.py: one ``FAMILY`` at the
    end of models/hybrid.py, latent.py and swa.py), None for the engine's
    own dense arm."""
    if name in _DENSE_NAMES:
        return None
    from langstream_tpu.models.family import family_of

    return family_of(name)


#: adaptive pool-shrink (docs/RESILIENCE.md): preempt-and-retry rounds a
#: stranded (never-prefilled) request gets before its failure stops
#: being treated as transient pressure and it is shed loudly — the
#: bound that keeps a deterministically failing dispatch from
#: livelocking the loop in an admit→OOM→requeue cycle
_SHRINK_RETRY_CAP = 3

#: jaxlib/XLA allocator-failure spellings (plus the BlockManager's own
#: "pool exhausted") — the classifier behind the degrade-don't-die path
#: (docs/RESILIENCE.md). One compiled regex so every catch site agrees.
_RESOURCE_EXHAUSTED_RE = re.compile(
    r"RESOURCE_EXHAUSTED"
    r"|pool exhausted"
    r"|Out of memory"
    r"|Failed to allocate"
    r"|Allocation .* exceeds"
)


def _resolve_model_config(name: str, max_seq_len: int):
    family = _family_of(name)
    if family is not None:
        return family.config(name, max_seq_len)
    if name in _MOE_MODELS:
        from langstream_tpu.models.moe import MoEConfig

        factory = {
            "moe-tiny": MoEConfig.tiny,
            "moe-8x7b": MoEConfig.mixtral_8x7b,
            "mixtral-8x7b": MoEConfig.mixtral_8x7b,
        }[name]
        return factory(max_seq_len=max_seq_len)
    if name not in _MODEL_CONFIGS:
        from langstream_tpu.models.family import families

        known = sorted(_MODEL_CONFIGS) + sorted(_MOE_MODELS) + sorted(
            n for f in families() for n in f.presets)
        raise ValueError(f"unknown model {name!r}; known: {known}")
    return _MODEL_CONFIGS[name](max_seq_len=max_seq_len)


def _parse_bool(v: Any) -> bool:
    """YAML/env values arrive as strings; bool("false") is True, so parse."""
    if isinstance(v, str):
        return v.strip().lower() in ("1", "true", "yes", "on")
    return bool(v)


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    model: str = "tiny"
    slots: int = 8
    max_seq_len: int = 512
    tokenizer: str | None = None       # None/"byte" or local HF path
    checkpoint: str | None = None      # local weights dir (gated; random init otherwise)
    mesh: tuple[tuple[str, int], ...] = ()  # e.g. (("dp",1),("tp",8)); () = single device
    default_max_tokens: int = 128
    seed: int = 0
    # decode steps fused into one jitted lax.scan per host round-trip —
    # the host sync (not device compute) dominates per-step cost, so K
    # steps per sync multiplies throughput by ~K at a K-token batching
    # cost in streaming latency
    decode_chunk: int = 16
    # adaptive decode chunking for the TTFT regime: while the active slot
    # count is <= light_load_slots (default slots // 8 — well under
    # capacity, where admission latency matters and throughput headroom is
    # free), bursts fuse only decode_chunk_light steps and dispatch them
    # SEQUENTIALLY (no speculative chunk in flight), so a newly arrived
    # request waits at most decode_chunk_light steps for prefill instead
    # of up to 2 x decode_chunk. Past the threshold the engine reverts to
    # pipelined decode_chunk bursts. 0 disables (always heavy chunks).
    decode_chunk_light: int = 8
    light_load_slots: int | None = None
    # pre-compile the serving-path jit variants on the first request (a
    # lone probe + a concurrent wave past the light-load threshold): real
    # traffic then never waits on a compile. First-compiles on TPU are
    # tens of seconds — one landing mid-traffic convoys the whole queue.
    warmup_on_start: bool = False
    # max requests prefilled in one batched call
    prefill_batch: int = 8
    # model compute/param dtype override: None keeps the model's default
    # (bf16), "float32" runs params + activations in f32. f32 makes
    # greedy streams exactly shape-independent — decode, verify, and
    # sharded paths reduce to the same argmax regardless of XLA fusion —
    # which bf16 only approximates (near-tie logits can flip between
    # differently-shaped programs, backend-dependent). Dev/CPU posture
    # and exactness tests; 2x the param+cache HBM of bf16 on chips.
    model_dtype: str | None = None
    # weight-only quantization: None (bf16) or "int8" (scales TP-shard
    # with their weights, so the mesh posture keeps the int8 default)
    quantize: str | None = None
    # KV-pool quantization: None (bf16) or "int8" — per-(position,
    # head)-row absmax int8 halves the cache-read HBM traffic that
    # dominates the decode roofline; the scale folds into scores/probs so
    # no bf16 cache is ever materialised (models/kvquant.py). Which kernel
    # reads an int8 pool: see paged_kernel
    kv_quantize: str | None = None
    # A constant, not an option: the engine serves the paged block pool
    # (models/paged.py: kv_pool_fraction of slots x max_seq_len rows, with
    # worst-case admission reservations) and nothing else. The keyword
    # stays because bench/configs/*.json and tests/bench pass it; any
    # other value is refused in __post_init__
    kv_layout: str = "paged"
    kv_block_size: int = 64
    kv_pool_fraction: float = 0.5
    kv_pool_blocks: int | None = None  # explicit pool size override
    # paged read path. "auto": on TPU the Pallas kernel for bf16 pools
    # (per-shard via shard_map under a mesh) and the fused XLA gather for
    # int8 pools; the XLA gather off-TPU. "xla" | "pallas" select one —
    # "pallas" on an int8 pool is the in-kernel dequant twin (single
    # device; refused under a mesh). A selected kernel that cannot be
    # built raises; nothing falls back. Continuation prefill / verify
    # follow the same choice, except that int8 pools read history through
    # XLA (the multi-query kernel has no int8 twin).
    # "pallas-interpret" runs the kernel in the Pallas interpreter: CPU
    # tests only, refused on a TPU backend.
    paged_kernel: str = "auto"
    # automatic prefix caching: full prompt blocks are
    # content-addressed; requests sharing a prefix (system preambles, RAG
    # templates, chat history) adopt the cached blocks read-only and
    # prefill just the suffix — the TTFT lever for shared-prefix traffic
    prefix_cache: bool = True
    # prompt-lookup speculative decoding (greedy bursts):
    # each step drafts N continuation tokens by matching the
    # context's last bigram earlier in the context (strong on RAG /
    # summarization / code where output copies input) and verifies them in
    # ONE forward; greedy acceptance emits only tokens the model would
    # have produced anyway, so streams are bit-identical to plain decode —
    # accepted drafts just arrive ~k tokens per step. 0 disables.
    speculative_drafts: int = 0
    # chunked prefill: prompts whose to-prefill length
    # exceeds this are admitted immediately but prefilled prefill_chunk
    # tokens at a time through the continuation path, INTERLEAVED with
    # decode bursts — a long prompt no longer stalls every active stream
    # for its whole prefill (head-of-line blocking). 0 disables.
    prefill_chunk: int = 0
    # multi-tenant QoS (serving/qos.py, serving/scheduler.py): None keeps
    # the FIFO admission queue (the pre-QoS engine, bit for bit); a
    # QosSpec switches admission to priority classes with WDRR dequeue,
    # bounded per-class queues, per-tenant token buckets, and preemptive
    # load shedding under KV pressure (docs/SCHEDULING.md)
    qos: QosSpec | None = None
    # depth-2 pipelined decode dispatch (docs/PIPELINE.md): heavy bursts
    # overlap the host's fetch/detokenize/stop-check of chunk N with the
    # device's execution of chunk N+1, freeze finished slots device-side
    # instead of tearing the burst down, carry the in-flight chunk across
    # the burst boundary so prefill dispatches interleave under it, and
    # report the overlapped-vs-exposed host split in the flight rollup.
    # False (or LS_TPU_PIPELINE=0 in the environment) falls back to the
    # sequential loop — the reference the equivalence tests compare
    # against. Greedy output is byte-identical across the two loops with
    # model_dtype=float32 (exactly shape-independent argmax); under the
    # bf16 default the loops legitimately run differently-shaped
    # programs (frozen-slot bursts vs teardown/re-bucket), so near-tie
    # logits can flip — the same caveat model_dtype documents above.
    pipeline: bool = True
    # engine watchdog (serving/health.py): the engine is declared WEDGED
    # (liveness probe fails, k8s reschedules the pod) when no loop-boundary
    # progress occurs for this many seconds while work is queued or in
    # flight. Must exceed the worst single loop gap — on TPU the first XLA
    # compile of a variant (tens of seconds); warmup-on-start pods, whose
    # compiles land inside the readiness window, can run it much tighter.
    wedge_window_s: float = 60.0
    # SLO objectives (serving/health.py SloSpec): targets for TTFT /
    # queue-wait quantiles, shed rate, and availability, evaluated
    # engine-side with multi-window burn rates; None disables tracking
    slo: SloSpec | None = None
    # streaming token delivery + TBT plane (docs/OBSERVABILITY.md
    # Streaming & TBT): False (the default) keeps every pre-streaming
    # surface pinned bit for bit — no new flight-event kinds, no new
    # Prometheus series, no stats() section. True activates the
    # per-chunk telemetry around on_chunk consumers: the bounded TBT
    # digest into request_timings, stream-emit/stream-stall/
    # stream-cancel flight events, stats()["streaming"], per-QoS-class
    # langstream_stream_tbt_seconds histograms, and (with qos classes
    # declaring tbt-p99-s) per-class burn trackers behind the health()
    # tbt_burn predicate. Chunk DELIVERY itself needs no flag — the
    # flag gates observability, not the API.
    streaming: bool = False
    # stall line (seconds between chunk emissions) for classes without
    # their own tbt-p99-s target: an inter-emit gap past this records a
    # stream-stall flight event
    stream_stall_s: float = 2.0
    # disaggregated prefill/decode pools (docs/DISAGG.md): "combined"
    # (default) serves both phases in one engine — every pre-existing
    # behavior, bit for bit. "prefill" runs admission/prefill (chunked,
    # prefix-cache-aware) then EXPORTS the request's KV blocks over the
    # handoff plane (serving/kvtransfer.py) instead of decoding;
    # "decode" additionally accepts imports that join the decode batch
    # directly, skipping prefill. Deployed pods get the
    # role from the StatefulSet split's LS_POOL_ROLE env (from_dict
    # fallback) so both pools share one agent config secret.
    pool_role: str = "combined"
    # tiered prefix-KV store (serving/prefixstore.py, docs/PREFIX.md):
    # None keeps the single-replica HBM-only prefix cache, bit for bit.
    # A spec layers T1 (host-RAM spill under a byte budget) and T2
    # (object storage via the kvtransfer wire format) under the T0
    # cache: eviction demotes T0→T1→T2, admission promotes/hydrates on
    # hit, and cross-replica cold starts of shared system prompts
    # hydrate instead of recomputing. Requires prefix-cache on.
    prefix_store: "PrefixStoreSpec | None" = None
    # multi-LoRA adapter store (serving/adapters.py, docs/ADAPTERS.md):
    # None keeps the single-model engine, bit for bit — no stacked
    # buffers, no new jit arguments, no new surfaces. A spec gives the
    # paged decode program a stacked per-layer A/B factor buffer with
    # t0-entries device-resident adapter rows (row 0 = zeros for
    # adapter-less slots), a T1 host-RAM spill, and a T2 object-storage
    # origin; requests name adapters via the langstream-adapter header
    # and admission blocks on hydration like the prefix stash.
    # Incompatible with multi-host lockstep (followers replay positional
    # descriptors that carry no adapter rows).
    adapter_store: "AdapterStoreSpec | None" = None
    # device-survival plane (docs/RESILIENCE.md): a device allocator
    # failure (RESOURCE_EXHAUSTED and its jaxlib spellings) at a
    # pool-grow/prefill/scatter seam no longer fails every in-flight
    # request — the engine SHRINKS its effective KV admission budget by
    # shrink-fraction of the configured pool, preempts the lowest-class
    # victims to free their worst-case reservations (resume is the PR 4
    # byte-identical path), and schedules a recovery probe that restores
    # one shrink quantum per quiet shrink-recovery-s window. Repeated
    # shrinks inside one window escalate to DEGRADED health.
    shrink_fraction: float = 0.125
    shrink_recovery_s: float = 30.0
    # fault injection (serving/faults.py — TESTS AND CHAOS DRILLS ONLY):
    # declared FaultPlans arm the engine's device-touching seams to
    # raise synthetic RESOURCE_EXHAUSTED errors or stall a dispatch.
    # Empty (the default) leaves the hot path bit-for-bit unchanged —
    # every seam check is one attribute test against None. The
    # LS_TPU_FAULTS env var (JSON list of plans) arms a deployed pod.
    faults: tuple = ()
    # crash-requeue journal (serving/journal.py): a directory where every
    # accepted submission is journaled at admit and retired at
    # finish/shed/fail; a restarting engine replays the live entries
    # front-of-class, so an engine death no longer silently drops
    # accepted work. None (default) disables — hot path unchanged.
    journal_dir: str | None = None
    # incident capture plane (serving/incident.py): a directory where an
    # SLO/health breach snapshots a bounded evidence bundle (flight
    # summary + event tail, worst-K journeys, attribution, streaming
    # digests, config fingerprint) the moment the predicate trips.
    # None (default) disables — observe paths unchanged.
    incident_dir: str | None = None
    # suffixes longer than this skip the cache and take the full prefill.
    # The continuation path is memory-bounded (blocked online softmax), so
    # this is a kernel-efficiency trade, not an OOM guard: the full prefill
    # rides the Pallas flash kernel / sp ring, the continuation path is XLA
    # einsums — past the cap, recomputing the prefix on the faster kernel
    # beats skipping it on the slower one
    prefix_cache_max_suffix: int = 4096

    def __post_init__(self) -> None:
        if self.kv_layout != "paged":
            what = (
                "was removed at PR 29" if self.kv_layout == "dense"
                else "is unknown"
            )
            raise ValueError(
                f"kv-layout {self.kv_layout!r} {what}: the engine serves "
                f"the paged pool; drop the key"
            )

    def to_dict(self) -> dict[str, Any]:
        """Kebab-case dict that :meth:`from_dict` round-trips — the lockstep
        handshake ships this so followers build the identical engine."""
        return {
            "model": self.model,
            "slots": self.slots,
            "max-seq-len": self.max_seq_len,
            "tokenizer": self.tokenizer,
            "checkpoint": self.checkpoint,
            "mesh": dict(self.mesh),
            "max-tokens": self.default_max_tokens,
            "seed": self.seed,
            "decode-chunk": self.decode_chunk,
            "decode-chunk-light": self.decode_chunk_light,
            "light-load-slots": self.light_load_slots,
            "warmup-on-start": self.warmup_on_start,
            "prefill-batch": self.prefill_batch,
            "quantize": self.quantize,
            "kv-quantize": self.kv_quantize,
            "kv-layout": self.kv_layout,
            "kv-block-size": self.kv_block_size,
            "kv-pool-fraction": self.kv_pool_fraction,
            "kv-pool-blocks": self.kv_pool_blocks,
            "paged-kernel": self.paged_kernel,
            "prefix-cache": self.prefix_cache,
            "prefix-cache-max-suffix": self.prefix_cache_max_suffix,
            "prefix-store": (
                self.prefix_store.to_dict()
                if self.prefix_store is not None
                else None
            ),
            "adapter-store": (
                self.adapter_store.to_dict()
                if self.adapter_store is not None
                else None
            ),
            "prefill-chunk": self.prefill_chunk,
            "speculative-drafts": self.speculative_drafts,
            "model-dtype": self.model_dtype,
            "qos": self.qos.to_dict() if self.qos is not None else None,
            "pool-role": self.pool_role,
            "pipeline": self.pipeline,
            "wedge-window-s": self.wedge_window_s,
            "slo": self.slo.to_dict() if self.slo is not None else None,
            "streaming": self.streaming,
            "stream-stall-s": self.stream_stall_s,
            "shrink-fraction": self.shrink_fraction,
            "shrink-recovery-s": self.shrink_recovery_s,
            "faults": [p.to_dict() for p in self.faults],
            "journal-dir": self.journal_dir,
            "incident-dir": self.incident_dir,
        }

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "ServingConfig":
        mesh = tuple((k, int(v)) for k, v in (d.get("mesh") or {}).items())
        return cls(
            model_dtype=d.get("model-dtype", d.get("model_dtype")),
            quantize=d.get("quantize"),
            kv_quantize=d.get("kv-quantize", d.get("kv_quantize")),
            model=d.get("model", "tiny"),
            slots=int(d.get("slots", 8)),
            max_seq_len=int(d.get("max-seq-len", d.get("max_seq_len", 512))),
            tokenizer=d.get("tokenizer"),
            checkpoint=d.get("checkpoint"),
            mesh=mesh,
            default_max_tokens=int(d.get("max-tokens", 128)),
            seed=int(d.get("seed", 0)),
            decode_chunk=int(d.get("decode-chunk", 16)),
            decode_chunk_light=int(
                d.get("decode-chunk-light", d.get("decode_chunk_light", 8))
            ),
            light_load_slots=(
                int(lls)
                if (lls := d.get("light-load-slots", d.get("light_load_slots")))
                is not None
                else None
            ),
            warmup_on_start=_parse_bool(
                d.get("warmup-on-start", d.get("warmup_on_start", False))
            ),
            prefill_batch=int(d.get("prefill-batch", 8)),
            kv_layout=d.get("kv-layout", d.get("kv_layout", "paged")),
            kv_block_size=int(d.get("kv-block-size", d.get("kv_block_size", 64))),
            kv_pool_fraction=float(
                d.get("kv-pool-fraction", d.get("kv_pool_fraction", 0.5))
            ),
            kv_pool_blocks=(
                int(d.get("kv-pool-blocks") or d.get("kv_pool_blocks"))
                if (d.get("kv-pool-blocks") or d.get("kv_pool_blocks"))
                else None
            ),
            paged_kernel=d.get("paged-kernel", d.get("paged_kernel", "auto")),
            prefix_cache=_parse_bool(
                d.get("prefix-cache", d.get("prefix_cache", True))
            ),
            prefix_cache_max_suffix=int(
                d.get(
                    "prefix-cache-max-suffix",
                    d.get("prefix_cache_max_suffix", 4096),
                )
            ),
            prefix_store=PrefixStoreSpec.from_dict(
                d.get("prefix-store", d.get("prefix_store"))
            ),
            adapter_store=AdapterStoreSpec.from_dict(
                d.get("adapter-store", d.get("adapter_store"))
            ),
            prefill_chunk=int(
                d.get("prefill-chunk", d.get("prefill_chunk", 0))
            ),
            speculative_drafts=int(
                d.get("speculative-drafts", d.get("speculative_drafts", 0))
            ),
            qos=QosSpec.from_dict(d.get("qos")),
            pool_role=str(
                d.get(
                    "pool-role",
                    d.get(
                        "pool_role",
                        os.environ.get("LS_POOL_ROLE") or "combined",
                    ),
                )
            ),
            pipeline=_parse_bool(d.get("pipeline", True)),
            wedge_window_s=float(
                d.get("wedge-window-s", d.get("wedge_window_s", 60.0))
            ),
            slo=SloSpec.from_dict(d.get("slo")),
            streaming=_parse_bool(d.get("streaming", False)),
            stream_stall_s=float(
                d.get("stream-stall-s", d.get("stream_stall_s", 2.0))
            ),
            shrink_fraction=float(
                d.get("shrink-fraction", d.get("shrink_fraction", 0.125))
            ),
            shrink_recovery_s=float(
                d.get("shrink-recovery-s", d.get("shrink_recovery_s", 30.0))
            ),
            faults=tuple(
                FaultPlan.from_dict(p) for p in (d.get("faults") or ())
            ),
            journal_dir=(
                d.get(
                    "journal-dir",
                    d.get(
                        "journal_dir",
                        os.environ.get("LS_TPU_JOURNAL_DIR") or None,
                    ),
                )
            ),
            incident_dir=(
                d.get(
                    "incident-dir",
                    d.get(
                        "incident_dir",
                        os.environ.get("LS_TPU_INCIDENT_DIR") or None,
                    ),
                )
            ),
        )


@dataclasses.dataclass
class _Slot:
    request: "_Request | None" = None
    # chunked prefill: tokens committed so far / mid-prefill flag (the slot
    # holds its reservation but is excluded from decode until done)
    prefilling: bool = False
    prefill_done: int = 0

    @property
    def free(self) -> bool:
        return self.request is None


@dataclasses.dataclass
class _Request:
    prompt_tokens: list[int]
    max_tokens: int
    temperature: float
    top_k: int
    top_p: float
    on_token: Callable[[int, float, bool], Awaitable[None] | None] | None
    future: asyncio.Future
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    generated: list[int] = dataclasses.field(default_factory=list)
    logprobs: list[float] = dataclasses.field(default_factory=list)
    loop: asyncio.AbstractEventLoop | None = None
    enqueue_time: float = 0.0
    # TTFT decomposition: enqueue → admit (queue wait) → first token
    # (prefill); the remainder to the client's first chunk is transport
    admit_time: float | None = None
    first_token_time: float | None = None
    # prompt-lookup speculation: bigram -> most recent first-element index,
    # maintained incrementally (amortized O(1)/token; a backward rescan per
    # verify step would be O(context) on the event-loop thread)
    bigram_index: dict = dataclasses.field(default_factory=dict)
    bigram_covered: int = 0
    # stop sequences (reference: ChatCompletionsConfig.stop): generation
    # halts when any string appears in the decoded output; the final text
    # is truncated at the match (the match itself excluded, OpenAI-style)
    stop: list = dataclasses.field(default_factory=list)
    stop_matched: bool = False
    # trace context captured at enqueue (the caller's ambient per-record
    # context): parents the engine.queue/prefill/decode spans
    trace: Any = None
    # warmup probes skip the latency histograms: their TTFT is XLA compile
    # time and Prometheus histograms are cumulative — one warmup wave would
    # poison the p99 forever (trace=None alone can't tell warmup apart
    # from an untraced real request)
    warmup: bool = False
    # QoS identity (serving/qos.py): the priority class drives WDRR
    # dequeue and preemption eligibility; the tenant keys the token
    # buckets. Both default to the unprivileged middle ground so a
    # QoS-off engine behaves exactly as before.
    tenant: str = ""
    priority: str = "default"
    # preemptive load shedding: times preempted so far (capped by
    # qos.max-preemptions) and, while requeued, when the preemption
    # happened (feeds the resume-latency histogram)
    preemptions: int = 0
    preempt_time: float | None = None
    # tiered prefix store (serving/prefixstore.py): True once admission
    # has stashed this request for a T2 hydration — it never stashes
    # twice, so a failed/timed-out hydration falls back to cold compute
    hydrate_attempted: bool = False
    # multi-LoRA adapter serving (serving/adapters.py): the adapter the
    # request named (gateway-stamped langstream-adapter header, "" =
    # base model), the device row its slot decodes against, whether a
    # T2 hydration stash already happened (one stash, then cold
    # refusal — unlike a prefix miss there is no recompute fallback),
    # and whether this request holds a pin on the adapter's row
    adapter: str = ""
    adapter_row: int = 0
    adapter_hydrate_attempted: bool = False
    adapter_pinned: bool = False
    # KV handoff (docs/DISAGG.md): True for a request admitted through
    # /kv/import on a decode-pool engine — its KV state arrived over the
    # wire, so admission skipped prefill entirely (request_timings carry
    # the marker the disagg e2e asserts on)
    imported: bool = False
    # request-journey ledger key (serving/journey.py): the trace id when
    # the request is traced, a fresh trace-id-shaped local id otherwise;
    # None for warmup probes (no journey). Rides the kvtransfer header
    # so the decode pool's edges land in the SAME journey.
    journey_id: "str | None" = None
    # decode-pool marker: the first NEW token emitted after a KV import
    # closes the decode-admission/first-step journey edge exactly once;
    # import_base_tokens pins how many generated tokens ARRIVED with the
    # handoff, so the edge fires on genuinely new work
    first_step_noted: bool = False
    import_base_tokens: int = 0
    # end-to-end deadline (serving/handoff.py, docs/RESILIENCE.md):
    # absolute WALL-CLOCK epoch seconds — the one clock every replica
    # on the request's path can compare against. None = no deadline,
    # every check one attribute test (the default-config pin).
    deadline: "float | None" = None
    # streaming chunk delivery (docs/OBSERVABILITY.md Streaming & TBT):
    # on_chunk(new_token_ids, new_text, is_final) fires once per decode
    # chunk at the _flush_emits safe point (sync or async). The sent
    # counters drive delta computation (chunks tile the final text
    # byte-exactly); stream_tbt is the bounded inter-emit digest (only
    # allocated on streaming-configured engines); stream_key is the
    # gateway's langstream-stream-id, the handle disconnect-cancellation
    # grabs.
    on_chunk: "Callable[[list, str, bool], Any] | None" = None
    stream_key: "str | None" = None
    stream_sent_tokens: int = 0
    stream_sent_chars: int = 0
    stream_first_emit: "float | None" = None
    stream_last_emit: "float | None" = None
    stream_emits: int = 0
    stream_stalls: int = 0
    stream_closed: bool = False
    stream_tbt: "TbtDigest | None" = None

    @property
    def context_tokens(self) -> list[int]:
        """Full model context: prompt plus everything generated so far.
        Equals ``prompt_tokens`` until a preemption; a resumed request
        re-prefills this to rebuild its KV state, so with greedy
        sampling the continuation is bit-identical to an unpreempted
        run (the generated tokens + per-request sampling params ARE the
        snapshot — greedy decode carries no other state)."""
        if not self.generated:
            return self.prompt_tokens
        return self.prompt_tokens + self.generated


def _deadline_from_options(options: dict) -> float | None:
    """The request's absolute epoch deadline out of its options:
    ``deadline`` (epoch seconds — the forwarded ``langstream-deadline``
    header) wins over ``deadline-s`` (caller-relative budget). Malformed
    values degrade to None — a garbage deadline must never refuse work
    the budget allows (the same posture as :func:`parse_deadline`)."""
    deadline = parse_deadline(options.get("deadline"))
    if deadline is not None:
        return deadline
    rel = options.get("deadline-s")
    if rel is None:
        return None
    try:
        rel = float(rel)
    except (TypeError, ValueError):
        return None
    # a non-positive relative budget means "expired on arrival" — the
    # admission check refuses it loudly rather than dropping the field
    return time.time() + max(0.0, rel)  # graftcheck: disable=OBS501 deadlines are wall-clock by design (cross-replica epoch stamps)


def _normalize_stop(value) -> list[str]:
    """One normalization for every stop-sequence consumer (engine + stream
    adapter): a string becomes a singleton list, falsy entries drop, and
    non-string truthy entries (e.g. ``stop: [42]`` from YAML) are coerced —
    they would otherwise raise TypeError mid-request on the per-token
    ``s in tail`` hot path."""
    if not value:
        return []
    if isinstance(value, str):
        value = [value]
    return [s if isinstance(s, str) else str(s) for s in value if s]


def _span_meta(ticket: dict) -> dict:
    """A dispatch's ticket as the meta of its ``*.dispatch`` span."""
    return {"seq": ticket["dispatch"], "program": ticket["program"]}


def _pow2(n: int) -> int:
    """Smallest power of two >= n (batch-row padding: compiling one jit
    variant per exact row count is a compile per new size)."""
    p = 1
    while p < n:
        p *= 2
    return p


def _bucket(n: int, lo: int = 32, hi: int = 32768) -> int:
    b = lo
    while b < n and b < hi:
        b *= 2
    return min(b, hi)  # hi may not be a power of two (user max_seq_len)


# The rows past which a prefill program also exists at the midpoint under
# every power of two (6,144; 12,288; 24,576). A bucket has to earn its place:
# a program costs 2-3 s to load at every set-up (ROADMAP S5). Past 4,096 rows
# a prefill is one prompt a program and its time is flat a row, so a padded
# row costs what a true one does and the midpoints take a sixth of the rows
# off; up to 4,096 the cells batch their prefills 8 rows wide, and an engine
# with ``max-seq-len`` 2,048 keeps exactly the programs it has.
_PREFILL_MIDPOINTS_ABOVE = 4096


def _prefill_bucket_rows(n: int, hi: int) -> int:
    """The rows of the prefill program that takes ``n`` rows: the smallest
    of the powers of two and, above ``_PREFILL_MIDPOINTS_ABOVE``, the
    midpoints under them that holds ``n``, capped at ``hi`` as
    :func:`_bucket` caps (which it equals up to that threshold)."""
    b = max(32, _pow2(n))
    mid = b // 4 * 3
    if n <= mid and mid > _PREFILL_MIDPOINTS_ABOVE:
        b = mid
    return min(b, hi)


def _prefill_midpoints(hi: int) -> list[int]:
    """The buckets of :func:`_prefill_bucket_rows` up to ``hi`` that are no
    power of two: the programs the rule adds to what a caller who warms by
    power-of-two range reaches."""
    out, mid = [], _PREFILL_MIDPOINTS_ABOVE // 2 * 3
    while mid <= hi:
        out.append(mid)
        mid *= 2
    return out


def _dev_cache_cap() -> int:
    try:
        return max(1, int(os.environ.get("LS_TPU_DEV_CACHE_CAP", "32")))
    except ValueError:
        return 32


class _DeviceLru:
    """Content-keyed device-upload cache with an LRU bound.

    A single-entry cache saves the upload only when two consecutive
    bursts share the exact same content; multi-tenant traffic alternating
    between a few slot populations re-uploads on every flip. Cost of an
    upload on a locally attached chip: not measured (ROADMAP S2).
    Keeping the last N contents fixes the flip-flop — and the bound plus
    eviction counter (``engine.stats()["device-cache"]``) keeps a
    long-lived engine from pinning one device buffer per distinct block
    table it ever saw. One instance is touched from the engine loop, the
    other from the dispatch thread, and ``stats()``/``clear()`` run on
    whichever thread asks — so the OrderedDict bookkeeping (a multi-step
    read-modify-write, not a single GIL-atomic op) sits behind a plain
    ``threading.Lock``. The lock is uncontended in steady state and never
    held across I/O or device calls, so the OBS503 hot-path discipline
    holds; graftcheck RACE801 polices exactly this shape."""

    def __init__(self, cap: int | None = None):
        from collections import OrderedDict

        self.cap = cap if cap is not None else _dev_cache_cap()
        self._lock = threading.Lock()
        self._entries: Any = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get_or_put(self, key: bytes, factory: Callable[[], Any]) -> Any:
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                # graftcheck: disable=RACE801 device_bytes reads via a single C-level list() snapshot (the OBS505 lock-free reader contract above); the locked writes here never leave a torn view for it to observe
                self._entries.move_to_end(key)
                self.hits += 1
                return entry
            self.misses += 1
        # the factory (a device upload) runs OUTSIDE the lock; a lost
        # race uploads twice, which is the pre-LRU behavior, not a bug
        entry = factory()
        with self._lock:
            self._entries[key] = entry
            while len(self._entries) > self.cap:
                self._entries.popitem(last=False)
                self.evictions += 1
        return entry

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def device_bytes(self) -> int:
        """Device bytes pinned by the cached entries — the memory
        ledger's ``device-lru``/``sampler-state`` owners. Lock-FREE by
        design (graftcheck OBS505): the attribution read path must never
        queue behind a dispatch holding the LRU lock, so the entries are
        snapshotted with a single C-level ``list()`` copy (the same
        reader contract the flight recorder uses) and summed with
        attribute reads only."""
        entries = list(self._entries.values())
        total = 0
        for entry in entries:
            total += tree_device_bytes(entry)
        return total

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {
                "size": len(self._entries),
                "cap": self.cap,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
            }


class TpuServingEngine:
    """One engine per (model, mesh) — shared across agents in the process.

    Public API:
      await engine.generate(prompt, options, on_token=...) -> GenerationResult
    """

    _instances: dict[Any, "TpuServingEngine"] = {}
    _instances_lock = threading.Lock()

    @classmethod
    def get_or_create(cls, config: ServingConfig) -> "TpuServingEngine":
        with cls._instances_lock:
            if config not in cls._instances:
                cls._instances[config] = cls(config)
            return cls._instances[config]

    @classmethod
    def reset_instances(cls) -> None:
        with cls._instances_lock:
            cls._instances.clear()

    def __init__(self, config: ServingConfig, lockstep_role: str | None = None):
        self.config = config
        self.model_config = _resolve_model_config(
            config.model, config.max_seq_len
        )
        if config.model_dtype is not None:
            dtypes = {
                "float32": jnp.float32, "f32": jnp.float32,
                "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
            }
            if config.model_dtype not in dtypes:
                raise ValueError(
                    f"unknown model_dtype {config.model_dtype!r}; "
                    f"known: {sorted(dtypes)}"
                )
            self.model_config = dataclasses.replace(
                self.model_config, dtype=dtypes[config.model_dtype]
            )
        self.is_moe = config.model in _MOE_MODELS
        #: which programs serve the model: None for the dense arm
        #: (models/llama_paged.py, the MoE FFN plugged into it), or the
        #: family's own description (models/family.py); ``family`` is its
        #: name, for logs
        self._fam = _family_of(config.model)
        self.family = "dense" if self._fam is None else self._fam.name
        # bench/reference/granite_moe_hybrid.py and solar_open2.py ask this
        # before they compare a recurrent state; the engine asks ``_fam``
        self.is_hybrid = self._fam is not None and self._fam.name == "hybrid"
        self.tokenizer: Tokenizer = load_tokenizer(config.tokenizer)
        if self.tokenizer.vocab_size > self.model_config.vocab_size:
            raise ValueError(
                f"tokenizer vocab {self.tokenizer.vocab_size} exceeds model "
                f"vocab {self.model_config.vocab_size}"
            )

        self.mesh = None
        if config.mesh:
            from langstream_tpu.parallel.mesh import make_mesh

            self.mesh = make_mesh(dict(config.mesh))

        # multi-host slice: process 0 leads (broadcasts every dispatch over
        # the lockstep channel, serving/lockstep.py); followers are built by
        # LockstepFollower with lockstep_role="follower" and replay them.
        # Every process then issues identical jit calls — the requirement of
        # JAX multi-controller execution (SURVEY §7 hard part (c)).
        self._lockstep = None
        if (
            lockstep_role != "follower"
            and self.mesh is not None
            and jax.process_count() > 1
        ):
            import json as _json
            import os as _os

            from langstream_tpu.serving.lockstep import LockstepLeader

            port = int(_os.environ.get("LS_LOCKSTEP_PORT", "0")) or None
            self._lockstep = LockstepLeader(
                {"config_json": _json.dumps(config.to_dict())},
                expected_followers=jax.process_count() - 1,
                port=port,
                token=_os.environ.get("LS_LOCKSTEP_TOKEN", ""),
            )
            log.info(
                "lockstep leader on :%d awaiting %d followers",
                self._lockstep.port, jax.process_count() - 1,
            )
            self._lockstep.wait_ready()

        self._init_model()

        self.slots = [_Slot() for _ in range(config.slots)]
        # admission policy: FIFO by default; a qos spec swaps in the
        # priority/WDRR/token-bucket scheduler (serving/scheduler.py)
        self.scheduler = make_scheduler(config.qos)
        self._qos_enabled = config.qos is not None and config.qos.enabled
        self._wake = asyncio.Event()
        self._stop = False
        self._loop_task: asyncio.Task | None = None
        # one dedicated thread: JAX dispatch is serialised, asyncio stays live
        self._executor = ThreadPoolExecutor(max_workers=1, thread_name_prefix="tpu-engine")
        self._key = jax.random.PRNGKey(config.seed)
        # decode-side state mirrors (host copies, device arrays built per step)
        self._lengths = np.zeros(config.slots, dtype=np.int32)
        self._current = np.zeros(config.slots, dtype=np.int32)
        self._temps = np.zeros(config.slots, dtype=np.float32)
        self._topks = np.zeros(config.slots, dtype=np.int32)
        self._topps = np.ones(config.slots, dtype=np.float32)
        self._pres = np.zeros(config.slots, dtype=np.float32)
        self._freq = np.zeros(config.slots, dtype=np.float32)
        self._pending_emits: list = []
        self._finished_requests: list = []
        # drain-before-terminate (docs/FLEET.md): once draining, new
        # submissions shed with a Retry-After while already-accepted work
        # is preempted-and-requeued at the loop's safe point and served
        # to completion — the pod /drain endpoint and the autoscaler's
        # scale-down path both land here
        self._draining = False
        self._drain_pass_done = False
        self._drain_requeued = 0
        self._drain_shed = 0
        self._drain_base_completed = 0
        self._drain_report: dict[str, Any] | None = None
        # disaggregated pools (docs/DISAGG.md): the handoff plane's
        # engine-side state. Exports are finished-prefill payloads keyed
        # by request id, awaiting pickup via /kv/export/{request}
        # (bounded: an abandoned handoff must not pin host memory
        # forever); imports queue here and are applied by the engine
        # loop at its safe point, exactly like admission. The in-transit
        # byte counter feeds the HBM ledger's `in-transit` owner so a
        # handoff's cost is never invisible.
        self._pool_role = config.pool_role
        self._exports: "OrderedDict[str, dict[str, Any]]" = OrderedDict()
        self._export_seq = 0
        self._export_cap = max(
            8, int(os.environ.get("LS_TPU_KV_EXPORT_CAP", "256") or 256)
        )
        self._pending_imports: deque = deque()
        self._kv_in_transit_bytes = 0
        self.kv_exports_total = 0
        self.kv_exports_evicted = 0
        self.kv_imports_total = 0
        self.kv_import_sheds = 0
        self.kv_export_bytes = 0
        self.kv_import_bytes = 0
        self.completed_requests = 0
        # per-request {queue_wait, prefill, ttft} seconds, newest last —
        # the gateway bench reads this to attribute client-measured TTFT
        self.request_timings: deque[dict[str, float]] = deque(maxlen=4096)
        self.total_generated = 0
        # Prometheus serving metrics (ride the pod's /metrics endpoint next
        # to the per-agent counters; labeled by model)
        reporter = PrometheusMetricsReporter(
            prefix="langstream_serving", agent_id=config.model
        )
        self._m_tokens = reporter.counter(
            "tokens_generated_total", "tokens generated by the engine"
        )
        self._m_requests = reporter.counter(
            "requests_completed_total", "completed generation requests"
        )
        self._m_ttft = reporter.gauge(
            "last_ttft_seconds", "time to first token of the last request"
        )
        # real distributions, not counter-of-sums: p50/p99 TTFT and queue
        # wait are what the gateway bench and dashboards quantile over.
        # Exemplar-capable: traced requests stamp their journey id on the
        # bucket they land in, so a p99 scrape names a journey
        # `tools/journey.py --trace` can open (untraced traffic records
        # exactly as before — the scrape stays byte-identical)
        self._m_ttft_hist = reporter.exemplar_histogram(
            "ttft_seconds", "engine time-to-first-token (enqueue to token 1)"
        )
        self._m_queue_wait_hist = reporter.histogram(
            "queue_wait_seconds", "enqueue to slot admission"
        )
        self._m_active = reporter.gauge(
            "slots_active", "decode slots currently generating"
        )
        self._m_queued = reporter.gauge(
            "queued_requests", "requests awaiting a free slot"
        )
        self._m_prefix_hits = reporter.counter(
            "prefix_cache_hits_total",
            "admissions that adopted cached prefix blocks",
        )
        self._m_prefix_tokens = reporter.counter(
            "prefix_cache_tokens_reused_total",
            "prompt tokens served from cached prefix blocks (prefill skipped)",
        )
        self._m_spec_steps = reporter.counter(
            "speculative_steps_total", "speculative verify steps run"
        )
        self._m_spec_accepted = reporter.counter(
            "speculative_drafts_accepted_total",
            "draft tokens accepted by verify steps (free extra tokens)",
        )
        self.spec_steps = 0
        self.spec_accepted = 0
        self.spec_rejected = 0
        # device-resident speculation state (PR 20): per-slot context token
        # rows on device (lazy — allocated at the first speculative burst)
        # and the host ledger of how many leading entries per row are
        # known-correct for the slot's CURRENT request. Plain-decode paths
        # never touch the ledger, so their slots read as stale and re-sync
        # at the next burst entry; slot release resets to 0.
        self._ctx_dev = None
        self._ctx_synced = np.zeros(config.slots, dtype=np.int64)
        # fetch/dispatch conservation counters: the one-host-fetch-per-
        # chunk acceptance rides on these (stats() exposes the ratio)
        self._decode_dispatches = 0
        self._decode_fetches = 0
        self._spec_dispatches = 0
        self._spec_fetches = 0
        # measured-uplift auto-disable: rolling (tokens, seconds) windows
        # for speculative steps and plain-decode chunks. Uplift = spec
        # tok/s over plain tok/s; < 1 over a full window flips speculation
        # off with a spec-auto-disable flight event. Plain samples come
        # from the periodic in-burst calibration chunk (wall-measured at
        # matched posture) and, while disabled, from ordinary decode
        # chunks — which also count toward the re-enable probe.
        _win = int(os.environ.get("LS_TPU_SPEC_UPLIFT_WINDOW", "32"))
        self._spec_window: deque = deque(maxlen=max(_win, 1))
        self._plain_window: deque = deque(maxlen=max(_win, 1))
        self._spec_cal_every = int(
            os.environ.get("LS_TPU_SPEC_CALIBRATE_EVERY", "32")
        )
        self._spec_retry_plain = int(
            os.environ.get("LS_TPU_SPEC_RETRY_CHUNKS", "256")
        )
        self._spec_steps_since_cal = 0
        self._spec_auto_disabled = False
        self._spec_plain_since_disable = 0
        self._spec_last_uplift: float | None = None
        self._spec_flips: list[tuple[float, str]] = []
        # host mirrors of the prefix-cache counters (flight samples carry
        # them; the metric closures above are write-only)
        self.prefix_hits = 0
        self.prefix_tokens = 0
        # adaptive-chunk observability: dispatches per regime
        self._light_chunks = 0
        self._heavy_chunks = 0
        # flight recorder: one sample per dispatched burst + stall gaps +
        # discrete events; served by the pod /flight endpoints and the
        # engine_top console (serving/flight.py)
        # the device's clock waits on each program's result on a thread of
        # its own (flight.py DispatchClock; ended in close())
        self.flight = FlightRecorder(
            slots=config.slots, watch=jax.block_until_ready)
        # engine watchdog: heartbeat stamped at every flight boundary,
        # judged (wait-free) by probes/stats via health() — the layer that
        # turns a wedged device into a failed k8s liveness probe
        self.watchdog = EngineWatchdog(wedge_window_s=config.wedge_window_s)
        # SLO burn-rate tracker (None without a declared slo section):
        # completions/sheds/failures recorded on the engine loop, burn
        # rates surfaced via stats()/flight and the gauges below, `alert`
        # flight events on fast-burn transitions
        self.slo = SloTracker(config.slo) if config.slo is not None else None
        self._m_slo_burn: dict[str, Any] = {}
        self._m_slo_budget: dict[str, Any] = {}
        if self.slo is not None:
            for objective in config.slo.objectives:
                self._m_slo_burn[objective.name] = reporter.gauge(
                    f"slo_burn_rate_{objective.name}",
                    f"fast-window error-budget burn rate for the "
                    f"{objective.name} objective (1.0 = budget exhausts "
                    f"exactly at the window's end)",
                )
                self._m_slo_budget[objective.name] = reporter.gauge(
                    f"slo_budget_remaining_{objective.name}",
                    f"slow-window error budget remaining for the "
                    f"{objective.name} objective (1 - slow burn; negative "
                    f"= overspent)",
                )
        # streaming + TBT plane (docs/OBSERVABILITY.md Streaming & TBT):
        # empty/zero on non-streaming engines — the default Prometheus
        # scrape surface and flight-event set stay pinned bit for bit.
        # Per-class digests/histograms are created lazily on the first
        # finished stream of each class (classes are clamped to the QoS
        # vocabulary, so the maps stay bounded); the per-class burn
        # trackers exist only for classes declaring tbt-p99-s.
        self.stream_emits_total = 0
        self.stream_stalls_total = 0
        self.stream_cancels_total = 0
        self.stream_reclaims_total = 0
        self._stream_tbt_by_class: dict[str, TbtDigest] = {}
        self._m_tbt_hist: dict[str, Any] = {}
        self._stream_slo: dict[str, SloTracker] = {}
        if config.streaming and config.qos is not None:
            for policy in config.qos.classes:
                if policy.tbt_p99_s is None:
                    continue
                # one single-objective tracker per declaring class: the
                # same multi-window burn machinery TTFT uses, windowed
                # like the engine's own slo section when one is declared
                self._stream_slo[policy.name] = SloTracker(
                    SloSpec(
                        objectives=(
                            SloObjective(
                                "tbt", 0.99, policy.tbt_p99_s * 1000.0
                            ),
                        ),
                        fast_window_s=(
                            config.slo.fast_window_s
                            if config.slo is not None
                            else 300.0
                        ),
                        slow_window_s=(
                            config.slo.slow_window_s
                            if config.slo is not None
                            else 3600.0
                        ),
                        fast_burn=(
                            config.slo.fast_burn
                            if config.slo is not None
                            else 14.4
                        ),
                    )
                )
        # shapes already compiled (jit-variant keys AND prefill bucket/row
        # shapes): a miss here is a fresh XLA compile — tens of seconds on
        # TPU, the event every recompile-storm diagnosis starts from
        self._compiled_shapes: set = set()
        self._m_step_hist = {
            "decode": reporter.histogram(
                "decode_step_seconds", "wall time per dispatched decode chunk"
            ),
            "prefill": reporter.histogram(
                "prefill_step_seconds", "wall time per dispatched prefill batch"
            ),
            "verify": reporter.histogram(
                "verify_step_seconds", "wall time per speculative verify step"
            ),
        }
        self._m_host_overhead = reporter.histogram(
            "host_overhead_seconds",
            "host-side share of each dispatched burst (wall - device wait)",
        )
        self._m_kv_used = reporter.gauge(
            "kv_pool_used_ratio",
            "paged KV block-pool RESERVED fraction (0-1): the admission "
            "pressure that produces no-kv-blocks, not physical fullness",
        )
        self._m_stall = {
            reason: reporter.counter(
                f"admission_stall_{reason.replace('-', '_')}_seconds_total",
                f"seconds admission could not proceed: {reason} (accrues "
                f"while the engine is busy decoding too — queue pressure, "
                f"not engine idleness; the flight rollup's stall_ms is "
                f"the idle component)",
            )
            for reason in (
                "no-free-slot", "no-kv-blocks", "prefill-in-flight",
                "queue-empty",
            )
        }
        # the device's clock on /metrics (flight.py DispatchClock): an
        # operator reads the device's idle share from two rates
        device = PrometheusMetricsReporter(
            prefix="langstream_engine", agent_id=config.model
        )
        self._m_device_idle = device.counter(
            "device_idle_seconds_total",
            "seconds the device stood with no program queued (the flight "
            "samples' gap_ms)",
        )
        self._m_device_busy = {
            phase: device.counter(
                "device_busy_seconds_total",
                "seconds the device ran programs, by phase (the flight "
                "samples' program_ms)",
                labels={"phase": phase},
            )
            for phase in PHASES
        }
        self._m_spec_rejected = reporter.counter(
            "speculative_drafts_rejected_total",
            "draft tokens rejected by verify steps",
        )
        self._m_spec_ratio = reporter.gauge(
            "speculative_accept_ratio",
            "accepted / drafted ratio over the engine's life",
        )
        self._m_spec_uplift = reporter.gauge(
            "speculative_uplift",
            "rolling measured speculative-vs-plain tokens/s ratio (the "
            "auto-disable verdict input; 0 until the first full window)",
        )
        self._m_recompiles = reporter.counter(
            "recompiles_total",
            "jit program variants/shapes compiled (bucket or sampler-mode "
            "misses; each is a potential mid-traffic convoy)",
        )
        # QoS observability (created only with a qos spec so a FIFO
        # engine's /metrics surface is unchanged): per-class queue-depth
        # gauges, shed/preempt counters, preemption/resume histograms
        self._m_class_depth: dict[str, Any] = {}
        self._m_shed = None
        self._m_preempted = None
        self._m_resume_hist = None
        self._m_preempt_hist = None
        if self._qos_enabled:
            self._m_class_depth = {
                cls: reporter.gauge(
                    f"qos_queue_depth_{cls}",
                    f"requests queued in the {cls} priority class",
                )
                for cls in PRIORITY_CLASSES
            }
            self._m_shed = reporter.counter(
                "qos_shed_total",
                "requests refused by QoS policy (tenant throttle or a "
                "full class queue)",
            )
            self._m_preempted = reporter.counter(
                "qos_preempted_total",
                "running requests preempted under KV pressure (snapshot + "
                "requeue for transparent resume)",
            )
            self._m_resume_hist = reporter.histogram(
                "qos_resume_seconds",
                "preemption → re-admission wall time (how long preempted "
                "work waited to resume)",
            )
            self._m_preempt_hist = reporter.histogram(
                "qos_preempted_run_seconds",
                "how long a victim had been running when preempted (the "
                "decode progress the preemption put at risk)",
            )
        # KV handoff observability (split-pool engines only, so a
        # combined engine's /metrics surface stays unchanged): transfer
        # time histograms + byte/count totals — the handoff cost must
        # never be invisible (docs/DISAGG.md)
        self._m_kv_export_hist = None
        self._m_kv_import_hist = None
        self._m_kv_export_bytes = None
        self._m_kv_import_bytes = None
        # cross-replica failure domain (serving/handoff.py,
        # docs/RESILIENCE.md "Distributed failure domain"): handoff
        # re-offer/fallback counters fed by the chainer, deadline
        # shed/overrun counters fed by the admission and finish paths.
        # The Prometheus spellings register below for split-pool engines
        # only (retry/fallback) or lazily on first use (deadline) — a
        # combined-pool, deadline-less engine keeps the exact
        # pre-existing scrape surface (the default-config pin).
        self.handoff_retries = 0
        self.handoff_fallbacks = 0
        self.deadline_sheds = 0
        self.deadline_overruns = 0
        # exported-but-unsettled handoffs: request id -> journal id. An
        # entry retires only when the chainer confirms the decode side
        # ANSWERED (completion or terminal refusal) — a decode pod that
        # dies mid-handoff leaves the entry live, so a restart replays
        # the request as fresh work instead of losing it invisibly.
        self._handoff_journal: "OrderedDict[str, str]" = OrderedDict()
        self._reporter = reporter
        self._m_handoff_retries = None
        self._m_handoff_fallbacks = None
        self._m_deadline_shed = None
        self._m_breaker_open = None
        if self._pool_role != "combined":
            self._m_handoff_retries = reporter.counter(
                "handoff_retries_total",
                "KV handoff offers re-routed to another decode replica "
                "after a timeout/refusal/shed (serving/handoff.py)",
            )
            self._m_handoff_fallbacks = reporter.counter(
                "handoff_fallbacks_total",
                "KV handoffs decoded LOCALLY after the re-offer cap "
                "(every decode replica dead, held, or refusing)",
            )
            self._m_kv_export_hist = reporter.exemplar_histogram(
                "kv_export_seconds",
                "device gather + serialization wall time per KV handoff "
                "export (prefill pool)",
            )
            self._m_kv_import_hist = reporter.exemplar_histogram(
                "kv_import_seconds",
                "block allocation + device scatter wall time per KV "
                "handoff import (decode pool)",
            )
            self._m_kv_export_bytes = reporter.counter(
                "kv_export_bytes_total",
                "serialized KV handoff bytes exported to decode replicas",
            )
            self._m_kv_import_bytes = reporter.counter(
                "kv_import_bytes_total",
                "serialized KV handoff bytes imported from prefill replicas",
            )
        self._warmup_task: asyncio.Task | None = None
        # device-side upload caches (content-keyed, LRU-bounded): block
        # tables and the sampler/active-mask tuple change rarely between
        # chunks, so a chunk whose content was seen recently reuses the
        # device buffer instead of uploading again. Cost of an upload on a
        # locally attached chip: not measured (ROADMAP S2).
        self._tables_dev_cache = _DeviceLru()
        self._sampler_dev_cache = _DeviceLru()
        # pipelined engine loop (docs/PIPELINE.md): config + env escape
        # hatch; LS_TPU_PIPELINE=0 forces the sequential reference loop
        self._pipeline_on = config.pipeline and (
            os.environ.get("LS_TPU_PIPELINE", "1") != "0"
        )
        # a dispatched-but-unprocessed decode chunk carried across the
        # burst boundary so admission prefills dispatch under its device
        # shadow: (out, active slot ids, request identities at capture, K,
        # the dispatch's ticket)
        self._pending_chunk: tuple | None = None
        # ordinal of the last dispatch made (prefill or decode): the `seq`
        # of its host spans and the `dispatch` of its flight sample
        self._dispatch_seq = 0
        # inside a pipelined burst, finished slots' block releases are
        # DEFERRED to burst exit: an in-flight chunk still commits via the
        # tables captured at its dispatch, and a mid-burst re-allocation
        # of those blocks to a live slot would let the stale commit land
        # on top of live K/V (the post-burst prefill overwrite that makes
        # immediate release safe between bursts does not exist mid-burst)
        self._defer_release = False
        self._deferred_releases: list[int] = []
        # jax.profiler trace + HLO dump hooks (env-gated, off by default)
        self.profiler = ProfilerHooks()
        # device attribution plane (serving/attribution.py): the per-
        # program cost ledger fed from the loop's flight records, plus
        # the static facts the HBM memory ledger needs. Weight/cache
        # byte totals are computed ONCE here — the cache handles are
        # donated and rebound on the dispatch thread, so readers must
        # never walk the live arrays (their shapes are fixed for the
        # engine's life anyway).
        mc = self.model_config
        self.attribution = ProgramLedger()
        self._weights_bytes = tree_device_bytes(self.params)
        self._kv_cache_bytes = tree_device_bytes(
            self.cache_k
        ) + tree_device_bytes(self.cache_v)
        self._kv_block_bytes = (
            self._kv_cache_bytes // self.paged_layout.num_blocks
        )
        self._state_bytes = tree_device_bytes(self.state)
        act_bytes = np.dtype(mc.dtype).itemsize
        if self.is_moe or self._fam is not None:
            # routed experts: the host can't know which experts fire, so
            # the FLOPs term estimates params from the measured bytes —
            # divided by the ACTUAL weight width (int8 → 1, else the
            # model dtype's itemsize, so model_dtype=float32 doesn't
            # double the estimate)
            n_params = self._weights_bytes // (
                1 if self.config.quantize == "int8" else act_bytes
            )
        else:
            from langstream_tpu.models.llama import param_count

            n_params = param_count(mc)
        if self.config.kv_quantize == "int8":
            kv_row_bytes = mc.head_dim + 4  # int8 row + f32 scale
        else:
            kv_row_bytes = mc.head_dim * act_bytes
        self._prog_shape = ModelShape(
            layers=mc.layers,
            hidden=mc.hidden,
            heads=mc.heads,
            kv_heads=mc.kv_heads,
            head_dim=mc.head_dim,
            intermediate=getattr(
                mc, "intermediate", getattr(mc, "moe_intermediate", 0)
            ),
            vocab=mc.vocab_size,
            weight_bytes=self._weights_bytes,
            param_count=n_params,
            kv_row_bytes=kv_row_bytes,
            act_bytes=act_bytes,
        )
        # device identity is fixed for the engine's life: capacity (the
        # allocator's bytes_limit) and the published bandwidth resolve
        # once, never on the attribution read path. Off-TPU both are None
        # and every expectation derived from them reads None.
        self._hbm_limit = detect_hbm_bytes()
        self._device_kind, peaks = device_peaks()
        self._hbm_gbps = peaks["hbm_gbps"] if peaks else None
        # hbm_bytes_by_owner Prometheus mirrors (refreshed whenever the
        # attribution section is computed: stats(), /attribution, /memory)
        self._m_hbm_owner = {
            owner: reporter.gauge(
                f"hbm_bytes_{owner.replace('-', '_')}",
                f"resident HBM bytes attributed to {owner} "
                f"(serving/attribution.py memory ledger; slack = detected "
                f"limit minus every accounted owner)",
            )
            for owner in (
                "weights", "kv-pool", "sampler-state", "device-lru",
                "in-transit", "slack",
            )
        }
        # tiered prefix store (serving/prefixstore.py, docs/PREFIX.md):
        # T1 host-RAM spill + T2 object storage under the T0 prefix
        # cache. Constructed only with the cache on (validated above);
        # requests stalled on a T2 hydration are
        # stashed OFF the scheduler so they never head-block admission.
        self.prefix_store: PrefixStore | None = None
        self._prefix_hydrating: list = []  # (request, deadline_m, digests)
        self.prefix_t0_evictions = 0
        self._m_prefix_tier: dict[str, Any] = {}
        if (
            config.prefix_store is not None
            and config.prefix_store.enabled
            and config.prefix_cache
        ):
            self.prefix_store = PrefixStore(
                config.prefix_store,
                fingerprint=self.kv_fingerprint(),
                block_bytes=self._kv_block_bytes,
                rows_per_block=self.paged_layout.block_size,
            )
            # pool-pressure evictions bypass demotion: record the loss
            self.block_mgr.on_prefix_evict = self._note_prefix_pool_evict
            self._m_prefix_tier = {
                "t0_bytes": reporter.gauge(
                    "prefix_tier_t0_bytes",
                    "HBM bytes held by cached prefix blocks (the paged "
                    "pool's prefix sub-owner; budget = prefix-store "
                    "t0-bytes)",
                ),
                "t1_bytes": reporter.gauge(
                    "prefix_tier_t1_bytes",
                    "host-RAM bytes held by T1 spilled prefix blocks",
                ),
                "t2_bytes": reporter.gauge(
                    "prefix_tier_t2_bytes",
                    "object-storage payload bytes indexed in T2",
                ),
                "t1_hits": reporter.counter(
                    "prefix_t1_promotions_total",
                    "prefix blocks promoted T1→T0 at admission",
                ),
                "t2_hits": reporter.counter(
                    "prefix_t2_hydrations_total",
                    "prefix blocks hydrated T2→T1 for an admission",
                ),
                "demotions": reporter.counter(
                    "prefix_demotions_total",
                    "prefix blocks demoted down-tier (T0→T1 and T1→T2)",
                ),
                "evictions": reporter.counter(
                    "prefix_evictions_total",
                    "prefix blocks evicted from any tier (bytes left the "
                    "store — counted, never silent)",
                ),
            }
        # tiered multi-LoRA adapter store (serving/adapters.py,
        # docs/ADAPTERS.md): device-resident stacked A/B rows (T0) over
        # host-RAM spill (T1) and an object-storage origin (T2). Same
        # off-scheduler hydration stash discipline as the prefix store;
        # requests stalled on a cold adapter never head-block admission.
        # Disabled (the default) the engine is byte-identical to seed:
        # no store, no gauges, no stats section, no extra jit kwargs.
        self.adapter_store: AdapterStore | None = None
        self._adapter_hydrating: list = []  # (request, deadline_m, name)
        self.adapter_refusals = 0  # cold refusals (unknown or timed out)
        self._m_adapters: dict[str, Any] = {}
        if config.adapter_store is not None and config.adapter_store.enabled:
            self.adapter_store = AdapterStore(
                config.adapter_store,
                fingerprint=self.adapter_fingerprint(),
                entry_bytes=self._adapter_entry_bytes(),
            )
            self._m_adapters = {
                "t0_bytes": reporter.gauge(
                    "adapter_tier_t0_bytes",
                    "HBM bytes held by device-resident LoRA adapter rows "
                    "(budget = adapter-store t0-entries x entry bytes)",
                ),
                "t1_bytes": reporter.gauge(
                    "adapter_tier_t1_bytes",
                    "host-RAM bytes held by T1 spilled LoRA adapters",
                ),
                "t2_bytes": reporter.gauge(
                    "adapter_tier_t2_bytes",
                    "object-storage payload bytes indexed in adapter T2",
                ),
                "loads": reporter.counter(
                    "adapter_loads_total",
                    "LoRA adapter rows loaded into the device buffers "
                    "(T1→T0 promotions)",
                ),
                "hydrations": reporter.counter(
                    "adapter_hydrations_total",
                    "LoRA adapters hydrated T2→T1 for an admission",
                ),
                "demotions": reporter.counter(
                    "adapter_demotions_total",
                    "LoRA adapters demoted T1→T2 under host-RAM pressure",
                ),
                "evictions": reporter.counter(
                    "adapter_evictions_total",
                    "LoRA adapters evicted from any tier (bytes left the "
                    "store — counted, never silent)",
                ),
            }
        # device-survival plane (docs/RESILIENCE.md): fault injection,
        # adaptive pool-shrink, crash-requeue journal. Default config
        # keeps the hot path bit-for-bit: _faults is None (every seam
        # check is one attribute test), the journal is None, and the
        # recovery probe's loop check is one None test per pass.
        if not 0.0 < config.shrink_fraction <= 1.0:
            raise ValueError("shrink_fraction must be in (0, 1]")
        if config.shrink_recovery_s <= 0:
            raise ValueError("shrink_recovery_s must be > 0")
        plans = tuple(config.faults) or plans_from_env()
        self._faults = FaultInjector(plans) if plans else None
        if self.prefix_store is not None and self._faults is not None:
            # the t2-get network seam (serving/faults.py): the hydrator
            # consults the SAME injector the device seams use, so one
            # chaos plan scripts both failure domains
            self.prefix_store._fault_injector = self._faults
        if self.adapter_store is not None and self._faults is not None:
            # the adapter hydrator shares the t2-get seam too — one plan
            # scripts prefix AND adapter origin fetches
            self.adapter_store._fault_injector = self._faults
        # fired faults hand off loop-ward through a deque: the seams
        # span both thread roles, the flight ring's emission is loop-side
        self._fault_fired: deque = deque()
        self.pool_shrinks = 0
        self.pool_restores = 0
        self.shrink_preempted = 0
        self._shrink_recover_at: float | None = None
        # preempts/sheds performed INLINE at a catch site (the chunked-
        # prefill grow handler) before the loop-level shrink pass runs:
        # the pass folds them into its evidence and its did-we-adapt
        # verdict — a tiny pool whose budget is already at its floor
        # must still count an inline requeue as adaptation, not fall
        # through to failing every in-flight request
        self._shrink_inline_preempted = 0
        self._shrink_inline_shed = 0
        self._m_shrinks = reporter.counter(
            "pool_shrinks_total",
            "adaptive KV-budget shrinks after a device allocator "
            "failure (degrade-don't-die: evidence rides the "
            "pool-shrink flight events)",
        )
        self._m_restores = reporter.counter(
            "pool_restores_total",
            "shrink quanta restored by the recovery probe after a "
            "quiet window",
        )
        self._m_budget = reporter.gauge(
            "kv_budget_blocks",
            "live paged-KV admission budget in blocks (configured "
            "pool minus blocks withheld by adaptive shrink)",
        )
        self._m_budget(self.block_mgr.usable_blocks)
        self.journal: RequestJournal | None = None
        self._m_journal_depth = None
        if config.journal_dir:
            self.journal = RequestJournal(
                config.journal_dir,
                on_evict=lambda rid: self.flight.event(
                    "journal-evict", request=rid
                ),
                # identity stamp: entries journaled under a different
                # model/tokenizer are refused at replay — their token
                # ids mean nothing to this engine (the dir itself is
                # engine-private by contract)
                fingerprint={
                    "model": config.model,
                    "tokenizer": config.tokenizer or "byte",
                },
            )
            self._m_journal_depth = reporter.gauge(
                "journal_depth",
                "admitted-but-unfinished requests in the crash-requeue "
                "journal",
            )
            self._journal_replay_pending = self.journal.pending()
        else:
            self._journal_replay_pending = []
        # incident capture plane (serving/incident.py): breach-triggered
        # evidence bundles. None (the default) keeps every observe path
        # one attribute test against None — byte-identical to pre-plane.
        self.incidents: IncidentRecorder | None = None
        if config.incident_dir:
            self.incidents = IncidentRecorder(
                config.incident_dir,
                on_evict=lambda bid: self.flight.event(
                    "incident-evict", bundle=bid
                ),
            )

    # ------------------------------------------------------------------
    # model + jit setup
    # ------------------------------------------------------------------

    def _init_model(self) -> None:
        mc = self.model_config
        self._ffn = None  # default dense SwiGLU inside the llama layer math
        # random-init + int8 postures generate the quantized tree DIRECTLY
        # (init_llama_params_q8): the init→quantize sequence peaks at the
        # full-precision tree PLUS the int8 copy (>= 24 GB at the 8B shape
        # — certain OOM on a 16 GB chip, round-4 bench root cause)
        quantized_at_init = False
        if self._fam is not None:
            self._refuse_what_assumes_history_is_kv()
            log.warning(
                "model %r: using random-init weights (offline/dev mode)",
                self.config.model,
            )
            self.params = self._fam.init_params(mc)
        elif self.is_moe:
            from langstream_tpu.models.moe import init_moe_params, moe_serving_ffn

            ep_constrain = None
            if self.mesh is not None and "ep" in self.mesh.axis_names:
                # pin expert-major (E, C, H) intermediates to the ep axis so
                # GSPMD resolves the flanking einsums as token all-to-alls
                # over ICI instead of all-gathering the expert weights
                # (mirrors moe_forward_sharded's training-side constraints)
                from jax.sharding import NamedSharding, PartitionSpec as P

                e_spec = NamedSharding(self.mesh, P("ep", None, None))
                ep_constrain = lambda t: jax.lax.with_sharding_constraint(  # noqa: E731
                    t, e_spec
                )
            self._ffn = moe_serving_ffn(mc, ep_constrain=ep_constrain)
            if self.config.checkpoint:
                from langstream_tpu.models.checkpoints import load_moe_checkpoint

                self.params = load_moe_checkpoint(self.config.checkpoint, mc)
            else:
                log.warning(
                    "model %r: using random-init weights (offline/dev mode)",
                    self.config.model,
                )
                if self.config.quantize == "int8":
                    from langstream_tpu.models.quant import init_moe_params_q8

                    self.params = init_moe_params_q8(mc)
                    quantized_at_init = True
                else:
                    self.params = init_moe_params(mc)
        elif self.config.checkpoint:
            from langstream_tpu.models.checkpoints import load_llama_checkpoint

            self.params = load_llama_checkpoint(self.config.checkpoint, mc)
        else:
            log.warning(
                "no checkpoint configured for model %r: using random-init "
                "weights (offline/dev mode)", self.config.model,
            )
            if self.config.quantize == "int8":
                from langstream_tpu.models.quant import init_llama_params_q8

                self.params = init_llama_params_q8(mc)
                quantized_at_init = True
            else:
                self.params = init_llama_params(mc)
        if self.config.quantize == "int8":
            if not quantized_at_init:  # checkpoint / bf16-random-init trees
                from langstream_tpu.models.quant import (
                    quantize_llama_params,
                    quantize_moe_params,
                )

                quantize = (
                    quantize_moe_params if self.is_moe else quantize_llama_params
                )
                self.params = quantize(self.params)
        elif self.config.quantize not in (None, "none"):
            raise ValueError(f"unknown quantize mode {self.config.quantize!r}")

        if self.config.kv_quantize not in (None, "none", "int8"):
            raise ValueError(
                f"unknown kv_quantize mode {self.config.kv_quantize!r}"
            )
        if self.config.pool_role not in ("combined", "prefill", "decode"):
            raise ValueError(
                f"unknown pool_role {self.config.pool_role!r}; known: "
                f"combined, prefill, decode"
            )
        if (
            self.config.prefix_store is not None
            and self.config.prefix_store.enabled
            and not self.config.prefix_cache
        ):
            raise ValueError(
                "prefix-store requires prefix-cache=true (T0 IS the "
                "automatic prefix cache; without it there is nothing "
                "to demote or promote)"
            )
        if (
            self.config.adapter_store is not None
            and self.config.adapter_store.enabled
            and jax.process_count() > 1
        ):
            raise ValueError(
                "adapter-store is incompatible with multi-host "
                "lockstep (followers replay positional dispatch "
                "descriptors that carry no adapter rows)"
            )
        if (
            self.config.speculative_drafts > 0
            and self.config.kv_quantize == "int8"
        ):
            # speculation's "never changes content" guarantee is weaker
            # here: verify quantizes KV at different commit boundaries than
            # the non-speculative path, so greedy streams may diverge
            # bit-for-bit from speculation-off runs (documented at the
            # model level, llama_paged.py) — surface it where the config is
            # chosen, once per engine
            log.info(
                "speculative-drafts with kv-quantize=int8: greedy streams "
                "may diverge from non-speculative runs (int8 KV commit-"
                "boundary quantization differs under the verify path)"
            )
        init_state = lambda: None  # noqa: E731  (the hybrid family has one)
        from langstream_tpu.models.paged import (
            BlockManager,
            PagedLayout,
            init_paged_kv_cache,
        )

        self.paged_layout = PagedLayout.for_model(
            mc.max_seq_len,
            self.config.slots,
            block_size=self.config.kv_block_size,
            hbm_fraction_of_dense=self.config.kv_pool_fraction,
            num_blocks=self.config.kv_pool_blocks,
        )
        self.block_mgr = BlockManager(
            self.paged_layout, self.config.slots,
            **({} if self._fam is None else self._fam.block_manager_kwargs(
                mc, self.paged_layout, self.config.slots)),
        )
        if self._fam is not None:
            # the family's pools, and what rides behind them in ``state``
            # (a recurrent state, a second kind's pools, nothing)
            init_cache, family_state = self._fam.init_pools(
                mc, self.paged_layout, self.config.slots)
            init_state = family_state or init_state
        elif self.config.kv_quantize == "int8":
            from langstream_tpu.models.paged import init_paged_kv_cache_int8

            init_cache = partial(
                init_paged_kv_cache_int8, mc, self.paged_layout
            )
        else:
            init_cache = partial(init_paged_kv_cache, mc, self.paged_layout)
        # The read kernels are resolved HERE, once, from what the
        # engine can observe (backend, pool dtype, mesh) — the model
        # functions run exactly the kernel they are handed and raise
        # on one they cannot, so no path gives way silently.
        kernel = self.config.paged_kernel
        quant_pool = self.config.kv_quantize == "int8"
        if kernel not in ("auto", "xla", "pallas", "pallas-interpret"):
            raise ValueError(f"unknown paged_kernel {kernel!r}")
        self._refuse_interpreter_on_tpu("paged_kernel", kernel)
        if kernel == "auto":
            # bf16 pools read through the Pallas kernel on TPU (under
            # a mesh per-shard via shard_map: slots on dp, heads on
            # tp): it fetches only the live blocks, from the stacked
            # pool in place. int8 pools read through the XLA gather
            # (llama_paged._cache_partial_xla: one gather a pool on the
            # stacked pool, the window written once and read once by
            # the product that contracts it as it lies, ROADMAP S1).
            # Their Pallas twin _paged_kernel_q8 (static grid over a
            # layer's slice, checked by chip_smoke.py, never timed) goes
            # onto the bf16 kernel's driver later; pallas selects it now.
            kernel = (
                "pallas"
                if jax.default_backend() == "tpu" and not quant_pool
                else "xla"
            )
        elif (
            kernel != "xla"
            and quant_pool
            and self.mesh is not None
            and self.mesh.size > 1
        ):
            raise ValueError(
                f"paged_kernel={kernel!r} with kv-quantize=int8 under "
                f"a mesh: the shard_map wrapper of the paged read "
                f"carries no specs for the int8 scales; use "
                f"paged_kernel=xla (or auto) for sharded int8 pools"
            )
        self.paged_read_kernel = kernel
        # the hybrid family's other kernels: the recurrent state's pass
        # (Mamba-2's ops/ssm_state.py, the delta rule's ops/delta_state.py
        # and a prefill's chunked rule, ops/delta_chunk.py) follows it
        self.ssm_state_kernel = (
            kernel if self._fam is not None and self._fam.state_kernels
            else None)
        # and so does the form of a prefill's routed experts' grouped pass
        # (models/moe.py dropless_experts_grouped: ops/grouped_experts.py,
        # or the XLA loop of one block a step): the family's programs are
        # handed the ONE selection, and what the pass does with it at this
        # model's share of the experts, and from how many rows, is
        # models/moe.py grouped_form's to say. (pallas_call has no rule to
        # partition the kernel, and no family with experts serves under a
        # mesh: _refuse_what_assumes_history_is_kv, above)
        self.moe_grouped_kernel, self._grouped_rows_over = (
            grouped_form(kernel, mc.experts_held, mc.experts)
            if self._fam is not None and self._fam.expert_kernels
            else (None, None))
        # prefill programs dispatched, and those of them whose rows took the
        # grouped pass
        self._prefill_dispatches = 0
        self._prefill_dispatches_midpoint = 0
        self._prefill_dispatches_grouped = 0
        # continuation prefill / speculative verify read history
        # through the multi-query kernel, which has no int8 twin:
        # int8 pools take the XLA history sweep there, by selection
        self.continuation_read_kernel = "xla" if quant_pool else kernel

        self._refuse_cache_that_cannot_fit(
            lambda: (init_cache(), init_state())
        )
        cache_k, cache_v = init_cache()
        # and the form of every program's commit of new rows into this pool
        # (models/paged.py write_rows, ops/pool_commit.py): the selection
        # where the kernel can move the pool (bf16, no mesh: pallas_call has
        # no partition rule), the XLA scatter for an int8 pool or a mesh
        self.pool_commit_kernel = commit_form(
            kernel if self.mesh is None else "xla", cache_k)
        # recurrent state beside the pool: None for every family but the
        # hybrid one; donated and re-bound with the caches
        self.state = init_state()

        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from langstream_tpu.models.quant import quantize_specs
            from langstream_tpu.parallel.mesh import put_global

            if self.is_moe:
                from langstream_tpu.models.moe import moe_param_specs

                base_specs = moe_param_specs(mc)
            else:
                base_specs = llama_param_specs(mc)
            # ONLY the optional "ep" axis is forgiven when absent (an MoE
            # engine on a pure-tp mesh keeps experts replicated — a
            # legitimate, if memory-hungry, layout). Any other missing spec
            # axis is a misconfigured mesh and must fail loudly, not
            # silently replicate the weights.
            axes = set(self.mesh.axis_names)

            def _present(entry):
                if entry is None:
                    return None
                names = entry if isinstance(entry, tuple) else (entry,)
                missing = [a for a in names if a not in axes]
                for a in missing:
                    if a != "ep":
                        raise ValueError(
                            f"model {self.config.model!r} shards over mesh "
                            f"axis {a!r} but the configured mesh has axes "
                            f"{sorted(axes)}; add {a!r} to the mesh"
                        )
                    log.warning(
                        "mesh has no 'ep' axis: expert weights will be "
                        "replicated on every device"
                    )
                kept = tuple(a for a in names if a in axes)
                if isinstance(entry, tuple):
                    return kept or None
                return kept[0] if kept else None

            base_specs = jax.tree.map(
                lambda p: P(*(_present(e) for e in p)) if isinstance(p, P) else p,
                base_specs,
                is_leaf=lambda x: isinstance(x, P),
            )
            specs = quantize_specs(base_specs, self.params)
            self.params = jax.tree.map(
                lambda p, s: put_global(p, NamedSharding(self.mesh, s)),
                self.params,
                specs,
                is_leaf=lambda x: isinstance(x, P),
            )
            from langstream_tpu.models.paged import paged_cache_spec

            cspec = NamedSharding(
                self.mesh, paged_cache_spec(self.mesh.axis_names)
            )
            if isinstance(cache_k, dict):
                # the same (..., tp) spec fits both leaves: data ends in
                # the fused Kh*D axis, scales in Kh — both shard on tp
                place = lambda cache: jax.tree.map(
                    lambda a: put_global(a, cspec), cache
                )
                cache_k, cache_v = place(cache_k), place(cache_v)
            else:
                cache_k = put_global(cache_k, cspec)
                cache_v = put_global(cache_v, cspec)
        self.cache_k, self.cache_v = cache_k, cache_v

        # stacked device LoRA buffers (docs/ADAPTERS.md): row 0 is the
        # permanent zero adapter (adapter-less slots gather zeros, so one
        # jitted program serves heterogeneous-adapter batches), rows
        # 1..t0_entries back the AdapterStore's T0 tier. The buffers are
        # NOT donated — loads rebuild one row functionally (`.at[:, row]
        # .set`) on the dispatch thread, so an in-flight dispatch keeps
        # its snapshot. `_ad_rows` is the loop-side slot→row mirror.
        self._ad_layers: dict[str, Any] | None = None
        self._ad_rows: np.ndarray | None = None
        spec_ad = self.config.adapter_store
        if spec_ad is not None and spec_ad.enabled:
            n_rows = spec_ad.t0_entries + 1
            r = spec_ad.rank
            q_dim = mc.heads * mc.head_dim
            kv_dim = mc.kv_heads * mc.head_dim
            shapes = {
                "wq_a": (mc.layers, n_rows, mc.hidden, r),
                "wq_b": (mc.layers, n_rows, r, q_dim),
                "wk_a": (mc.layers, n_rows, mc.hidden, r),
                "wk_b": (mc.layers, n_rows, r, kv_dim),
                "wv_a": (mc.layers, n_rows, mc.hidden, r),
                "wv_b": (mc.layers, n_rows, r, kv_dim),
                "wo_a": (mc.layers, n_rows, q_dim, r),
                "wo_b": (mc.layers, n_rows, r, mc.hidden),
            }
            self._ad_layers = {
                k: jnp.zeros(s, dtype=mc.dtype) for k, s in shapes.items()
            }
            self._ad_rows = np.zeros(self.config.slots, dtype=np.int32)

        mc_static = mc
        ffn_static = self._ffn  # None = dense SwiGLU; MoE routes experts

        # sampled tokens/logprobs come back to the leader host every chunk;
        # under a (possibly multi-host) mesh they inherit the dp sharding of
        # the logits, which a multi-controller leader cannot fetch — pin them
        # replicated (XLA: one tiny all-gather on ICI per chunk)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            _rep = NamedSharding(self.mesh, P())

            def _fetchable(*arrays):
                return tuple(
                    jax.lax.with_sharding_constraint(a, _rep) for a in arrays
                )
        else:
            def _fetchable(*arrays):
                return arrays

        # None = auto (LS_TPU_FLASH env); under a mesh the kernel runs
        # per-shard through shard_map (heads on tp), so TP serving keeps it
        prefill_flash = None
        mesh_static = self.mesh

        def _make_decode(sampler_mode: tuple, window: int,
                         k_steps: int = 0, use_pen: bool = False):
            """``window``: number of block-table columns to sweep. ``k_steps``:
            fused steps per dispatch (0 → config.decode_chunk); light-load
            bursts compile a short variant. ``use_pen``: the variant takes
            (presences, frequencies, counts) after topps and samples with
            presence/frequency penalties."""
            use_top_p, use_top_k, all_greedy = sampler_mode
            K = k_steps or self.config.decode_chunk

            def _sample_fn_for(temps, topks, topps, pres=None, freq=None):
                # ONE definition for both families' decode programs — they
                # must sample identically
                if use_pen:
                    def sample_fn(logits, sub, counts):
                        return sample_tokens(
                            logits, sub, temps, topks,
                            use_top_p=use_top_p, top_ps=topps,
                            use_top_k=use_top_k, all_greedy=all_greedy,
                            use_penalties=True, presences=pres,
                            frequencies=freq, counts=counts,
                        )
                else:
                    def sample_fn(logits, sub):
                        return sample_tokens(
                            logits, sub, temps, topks,
                            use_top_p=use_top_p, top_ps=topps,
                            use_top_k=use_top_k, all_greedy=all_greedy,
                        )

                return sample_fn

            def _extras(pres, freq, counts):
                return (pres, freq, counts) if use_pen else None

            if self._fam is not None:
                fam, n = self._fam, self._fam.residents

                # ONE closure for every family with programs of its own:
                # what stays on the device rides behind params (``n`` of
                # them, the family's ``donate`` of them donated), the rest
                # is the dense arm's; the family's module makes the call
                @partial(jax.jit, donate_argnums=fam.donate)
                def _decode_chunk(params, *rest):
                    (tokens, lengths, active, tables, key, temps, topks,
                     topps, *pen) = rest[n:]
                    pres, freq, counts = pen or (None, None, None)
                    return fam.decode_chunk(
                        mc_static, params, rest[:n], tokens, lengths, active,
                        tables,
                        _sample_fn_for(temps, topks, topps, pres, freq),
                        key, K, num_read_blocks=window,
                        kernel=self.paged_read_kernel,
                        sample_extras=_extras(pres, freq, counts),
                        return_packed=True,
                    )

                return _decode_chunk

            @partial(jax.jit, donate_argnums=(1, 2))
            def _decode_chunk(params, cache_k, cache_v, tokens, lengths,
                              active, tables, key, temps, topks, topps,
                              pres=None, freq=None, counts=None,
                              ad_layers=None, ad_ids=None):
                from langstream_tpu.models.llama_paged import (
                    llama_decode_chunk_paged,
                )

                # kwargs default to None so the adapter-less engine traces
                # the exact seed jaxpr — adapters ride in only when the
                # store is enabled and the dispatch passes them explicitly
                adapters = (
                    None if ad_ids is None
                    else {"ids": ad_ids, "layers": ad_layers}
                )
                sample_fn = _sample_fn_for(temps, topks, topps, pres, freq)
                # return_packed folds the tokens+bitcast-logprobs pack
                # into the decode program itself: the chunk's whole
                # host traffic is out[0]'s D2H copy, with no post-hoc
                # pack dispatch (pre-fusion _pack_chunk) behind it
                out = llama_decode_chunk_paged(
                    mc_static, params, tokens, lengths, active,
                    cache_k, cache_v, tables, sample_fn, key, K,
                    num_read_blocks=window,
                    kernel=self.paged_read_kernel,
                    mesh=mesh_static, ffn=ffn_static,
                    sample_extras=_extras(pres, freq, counts),
                    adapters=adapters,
                    return_packed=True,
                )
                return _fetchable(out[0]) + out[1:]

            return _decode_chunk

        self._make_decode = _make_decode

        def _make_prefill(sampler_mode: tuple):
            use_top_p, use_top_k, all_greedy = sampler_mode
            if self._fam is not None:
                fam, n = self._fam, self._fam.residents

                # ONE closure, as in _make_decode; ``sel`` is the batch's
                # block tables, with its slot ids where the family selects
                # by them (``prefill_selects_slots``)
                @partial(jax.jit, donate_argnums=fam.donate,
                         compiler_options=fam.prefill_compiler_options(
                             mc_static, jax.default_backend()))
                def _prefill(params, *rest):
                    tokens, lengths, sel, key, temps, topks, topps = rest[n:]
                    logits, residents = fam.prefill(
                        mc_static, params, rest[:n], tokens, lengths, sel,
                        use_flash=prefill_flash,
                        # the one selection, for whichever kernels the
                        # family's prefill has (reported as ssm_state_kernel
                        # and moe_grouped_kernel)
                        kernel=self.paged_read_kernel)
                    with jax.named_scope("sample"):
                        next_tokens, logprobs = sample_tokens(
                            logits, key, temps, topks,
                            use_top_p=use_top_p, top_ps=topps,
                            use_top_k=use_top_k, all_greedy=all_greedy,
                        )
                    return (next_tokens, logprobs) + tuple(residents)

                return _prefill

            @partial(jax.jit, donate_argnums=(1, 2))
            def _prefill(params, cache_k, cache_v, tokens, lengths, tables,
                         key, temps, topks, topps,
                         ad_layers=None, ad_ids=None):
                from langstream_tpu.models.llama_paged import (
                    llama_prefill_paged,
                )

                adapters = (
                    None if ad_ids is None
                    else {"ids": ad_ids, "layers": ad_layers}
                )
                logits, ck, cv = llama_prefill_paged(
                    mc_static, params, tokens, lengths, cache_k, cache_v,
                    tables, use_flash=prefill_flash, mesh=mesh_static,
                    ffn=ffn_static, adapters=adapters,
                    kernel=self.paged_read_kernel,
                )
                with jax.named_scope("sample"):
                    next_tokens, logprobs = _fetchable(
                        *sample_tokens(
                            logits, key, temps, topks,
                            use_top_p=use_top_p, top_ps=topps,
                            use_top_k=use_top_k, all_greedy=all_greedy,
                        )
                    )
                return next_tokens, logprobs, ck, cv

            return _prefill

        self._make_prefill = _make_prefill

        def _make_prefill_continue(sampler_mode: tuple, nrb: int):
            """Suffix prefill against cached prefix blocks:
            the automatic-prefix-caching fast path. ``nrb`` is the static
            block-window bucket covering the longest reused prefix."""
            use_top_p, use_top_k, all_greedy = sampler_mode

            @partial(jax.jit, donate_argnums=(1, 2))
            def _prefill_cont(params, cache_k, cache_v, tokens, starts,
                              suffix_lengths, tables, key, temps, topks, topps,
                              ad_layers=None, ad_ids=None):
                from langstream_tpu.models.llama_paged import (
                    llama_prefill_continue_paged,
                )

                adapters = (
                    None if ad_ids is None
                    else {"ids": ad_ids, "layers": ad_layers}
                )
                logits, ck, cv = llama_prefill_continue_paged(
                    mc_static, params, tokens, starts, suffix_lengths,
                    cache_k, cache_v, tables, num_read_blocks=nrb,
                    ffn=ffn_static, kernel=self.continuation_read_kernel,
                    mesh=mesh_static, adapters=adapters,
                )
                with jax.named_scope("sample"):
                    next_tokens, logprobs = _fetchable(
                        *sample_tokens(
                            logits, key, temps, topks,
                            use_top_p=use_top_p, top_ps=topps,
                            use_top_k=use_top_k, all_greedy=all_greedy,
                        )
                    )
                return next_tokens, logprobs, ck, cv

            return _prefill_cont

        self._make_prefill_continue = _make_prefill_continue

        def _make_spec_step(nrb: int, sampler_mode: tuple):
            """Fused device-resident speculative step (prompt-lookup
            decoding): draft over the resident context rows + verify +
            in-program context update, ONE dispatch per step. The draft
            count is static (config), the acceptance rule (greedy vs
            rejection-sampled) specializes via ``sampler_mode``. The
            host reads exactly one packed array back per step."""
            D = self.config.speculative_drafts

            @partial(jax.jit, donate_argnums=(1, 2, 3))
            def _spec_step(params, cache_k, cache_v, ctx, current, lengths,
                           active, tables, key, temps, topks, topps,
                           ad_layers=None, ad_ids=None):
                from langstream_tpu.models.llama_paged import (
                    llama_spec_step_paged,
                )

                adapters = (
                    None if ad_ids is None
                    else {"ids": ad_ids, "layers": ad_layers}
                )
                out = llama_spec_step_paged(
                    mc_static, params, ctx, current, lengths, active,
                    cache_k, cache_v, tables, num_drafts=D,
                    num_read_blocks=nrb, ffn=ffn_static,
                    kernel=self.continuation_read_kernel, mesh=mesh_static,
                    key=key, temps=temps, topks=topks, topps=topps,
                    sampler_mode=sampler_mode, adapters=adapters,
                )
                # the leader host reads ONLY the packed array each step
                return _fetchable(out[0]) + out[1:]

            return _spec_step

        self._make_spec_step = _make_spec_step
        # the sampler's expensive passes (top-p vocab sort, top-k selection
        # sweep, any sampling at all for greedy-only batches) are compiled
        # in only when an active request needs them; decode additionally
        # specialises per attention window bucket. All variants compile
        # lazily on first use.
        self._decode_chunk_fns: dict[tuple[tuple, int, int], Any] = {}
        self._prefill_fns: dict[tuple, Any] = {}
        self._prefill_continue_fns: dict[tuple[tuple, int], Any] = {}
        self._spec_step_fns: dict[tuple[int, tuple], Any] = {}

    def _refuse_what_assumes_history_is_kv(self) -> None:
        """Every feature that adopts, rolls back, moves or replays a
        request's history as blocks of K and V rows alone would serve wrong
        tokens (or none) where the history is something else: a hybrid
        model's is K/V blocks AND a recurrent state that has no snapshot, a
        latent model's one array of compressed rows that the K/V-shaped
        paths (continuation prefill, the handoff's two pools, the int8
        rows' per-head scales) cannot read. Each is refused here by the name
        of its option, with the family's own reason; nothing falls back."""
        cfg = self.config
        family = self.family
        why = {  # the reasons both families share
            "prefix-store": "its tiers hold K/V blocks only",
            "adapter-store": f"the {family} programs apply no adapters",
            "quantize": f"the {family} programs read bf16 weights only",
            "mesh": "this family serves one chip's share of its "
                    "deployment; no mesh",
            "checkpoint": "no checkpoint loader for this family",
        }
        why.update(self._fam.refusals)
        on = {  # in the order they are refused
            "prefix-cache": cfg.prefix_cache,
            "prefix-store": (
                cfg.prefix_store is not None and cfg.prefix_store.enabled),
            "prefill-chunk": cfg.prefill_chunk > 0,
            "speculative-drafts": cfg.speculative_drafts > 0,
            "pool-role": cfg.pool_role != "combined",
            "adapter-store": (
                cfg.adapter_store is not None and cfg.adapter_store.enabled),
            "quantize": cfg.quantize not in (None, "none"),
            "kv-quantize": cfg.kv_quantize not in (None, "none"),
            "mesh": bool(cfg.mesh),
            "journal-dir": bool(cfg.journal_dir),
            "checkpoint": bool(cfg.checkpoint),
        }
        for option, is_on in on.items():
            if is_on:
                raise ValueError(
                    f"model {cfg.model!r} {self._fam.what} and cannot serve "
                    f"with {option}: {why[option]}"
                )

    def _refuse_cache_that_cannot_fit(self, init_cache) -> None:
        """Refuse, in words and before the allocator has to, a KV cache
        that cannot sit beside the weights in what the device reports as
        its limit (``memory_stats()["bytes_limit"]``; a mesh holds
        1/devices of both per device). The check is the sum of the two
        resident trees only — compiled programs and their scratch need
        room on top, so a configuration that passes here can still be
        too tight; one that fails here can never run."""
        limit = detect_hbm_bytes()
        if limit is None:
            return  # the backend reports no limit (CPU)
        cache_bytes = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(jax.eval_shape(init_cache))
        )
        weight_bytes = tree_device_bytes(self.params)
        devices = self.mesh.size if self.mesh is not None else 1
        if (weight_bytes + cache_bytes) / devices <= limit:
            return
        cfg = self.config
        raise ValueError(
            f"model {cfg.model!r} does not fit the device: weights "
            f"{weight_bytes / 1e9:.2f} GB + KV cache {cache_bytes / 1e9:.2f} "
            f"GB ({cfg.kv_quantize or 'bf16'} pool, "
            f"{cfg.slots} slots x {cfg.max_seq_len} rows) over {devices} "
            f"device(s) exceed the {limit / 1e9:.2f} GB the allocator "
            f"reports (bytes_limit). Lower slots or max-seq-len, or use "
            f"kv-quantize: int8 and a smaller kv-pool-fraction"
        )

    @staticmethod
    def _refuse_interpreter_on_tpu(option: str, kernel: str) -> None:
        if kernel == "pallas-interpret" and jax.default_backend() == "tpu":
            raise ValueError(
                f"{option}=pallas-interpret runs the kernel in the Pallas "
                f"interpreter, which exists for CPU tests; on a TPU select "
                f"pallas, xla or auto"
            )

    def _decode_fn(self, sampler_mode: tuple, window: int,
                   k_steps: int = 0, use_pen: bool = False):
        k_steps = k_steps or self.config.decode_chunk
        key = (sampler_mode, window, k_steps, use_pen)
        if key not in self._decode_chunk_fns:
            self._note_compile("decode", key)
            self._decode_chunk_fns[key] = self._make_decode(
                sampler_mode, window, k_steps, use_pen
            )
        return self._decode_chunk_fns[key]

    def _light_threshold(self) -> int:
        """Active-slot count at or below which bursts run short sequential
        chunks (the TTFT regime); 0 when adaptive chunking is disabled or
        the light chunk wouldn't actually be shorter."""
        cfg = self.config
        if cfg.decode_chunk_light <= 0 or cfg.decode_chunk_light >= cfg.decode_chunk:
            return 0
        if cfg.light_load_slots is not None:
            return cfg.light_load_slots
        return max(1, cfg.slots // 8)

    def _prefill_fn(self, sampler_mode: tuple):
        if sampler_mode not in self._prefill_fns:
            self._prefill_fns[sampler_mode] = self._make_prefill(sampler_mode)
        return self._prefill_fns[sampler_mode]

    def _prefill_continue_fn(self, sampler_mode: tuple, nrb: int):
        key = (sampler_mode, nrb)
        if key not in self._prefill_continue_fns:
            self._prefill_continue_fns[key] = self._make_prefill_continue(
                sampler_mode, nrb
            )
        return self._prefill_continue_fns[key]

    def _spec_step_fn(self, nrb: int, sampler_mode: tuple):
        key = (nrb, sampler_mode)
        if key not in self._spec_step_fns:
            self._note_compile("spec_step", key)
            self._spec_step_fns[key] = self._make_spec_step(nrb, sampler_mode)
        return self._spec_step_fns[key]

    # ------------------------------------------------------------------
    # flight recorder plumbing
    # ------------------------------------------------------------------

    def _note_compile(self, kind: str, key) -> None:
        """Record a recompile event the first time a (kind, shape) pair is
        dispatched: jit-variant cache misses AND new prefill bucket/row
        shapes (the same Python variant re-traces per padded shape). Runs
        on the engine loop or the dispatch thread; append-only."""
        shape_key = (kind, repr(key))
        if shape_key in self._compiled_shapes:
            return
        self._compiled_shapes.add(shape_key)
        self.flight.event("recompile", what=kind, variant=repr(key))
        self._m_recompiles(1)

    # ------------------------------------------------------------------
    # attribution-ledger plumbing (serving/attribution.py)
    # ------------------------------------------------------------------

    @staticmethod
    def _sampler_code(sampler_mode: tuple) -> str:
        """Compact sampler-variant tag for program ids."""
        use_top_p, use_top_k, all_greedy = sampler_mode
        if all_greedy:
            return "greedy"
        tag = "sample"
        if use_top_k:
            tag += "-tk"
        if use_top_p:
            tag += "-tp"
        return tag

    def _program_decode(
        self, window: int, k_steps: int, sampler_mode: tuple,
        pen: bool,
    ) -> str:
        """Program id for a decode-chunk variant; registers its cost
        model on first sight (arithmetic only — loop-thread safe)."""
        # variants specialize on block-table columns: the rows a slot's
        # read sweeps
        rows = window * self.paged_layout.block_size
        program = (
            f"decode:w{rows}:k{k_steps}:{self._sampler_code(sampler_mode)}"
            + (":pen" if pen else "")
        )
        if not self.attribution.known(program):
            self.attribution.register(
                program,
                decode_cost(
                    self._prog_shape,
                    slots=self.config.slots,
                    window_rows=rows,
                    k_steps=k_steps,
                    hbm_gbps=self._hbm_gbps,
                ),
            )
        return program

    def _program_prefill(
        self, bucket: int, rows: int, sampler_mode: tuple,
    ) -> str:
        program = (
            f"prefill:p{bucket}:b{rows}:{self._sampler_code(sampler_mode)}"
        )
        if not self.attribution.known(program):
            self.attribution.register(
                program,
                prefill_cost(
                    self._prog_shape,
                    rows=rows,
                    tokens_per_row=bucket,
                    prefix_rows=0,
                    hbm_gbps=self._hbm_gbps,
                ),
            )
        return program

    def _program_prefill_continue(
        self, nrb: int, rows: int, chunk: int, sampler_mode: tuple,
    ) -> str:
        program = (
            f"prefill-continue:nrb{nrb}:b{rows}:c{chunk}:"
            f"{self._sampler_code(sampler_mode)}"
        )
        if not self.attribution.known(program):
            self.attribution.register(
                program,
                prefill_cost(
                    self._prog_shape,
                    rows=rows,
                    tokens_per_row=chunk,
                    prefix_rows=nrb * self.paged_layout.block_size,
                    hbm_gbps=self._hbm_gbps,
                ),
            )
        return program

    def _program_spec_step(self, nrb: int, sampler_mode: tuple) -> str:
        """Program id for the fused draft+verify step. A NEW census family
        (``specstep:``, replacing the pre-fusion ``verify:`` ids): the
        program now contains the prompt-lookup draft and the context
        update, so schema-2 records must not conflate its measured cost
        with the old verify-only program's. The cost model stays the
        verify forward — the draft scan and ctx scatter are noise next to
        the D+1-position forward."""
        drafts = self.config.speculative_drafts
        program = (
            f"specstep:nrb{nrb}:d{drafts}:{self._sampler_code(sampler_mode)}"
        )
        if not self.attribution.known(program):
            self.attribution.register(
                program,
                verify_cost(
                    self._prog_shape,
                    slots=self.config.slots,
                    window_rows=nrb * self.paged_layout.block_size,
                    drafts=drafts,
                    hbm_gbps=self._hbm_gbps,
                ),
            )
        return program

    def _admission_stall(self) -> str | None:
        """Why queued work is not being admitted right now (None when the
        queue is empty or admission would succeed on the next pass)."""
        if self.scheduler.empty():
            return None
        if not any(s.free for s in self.slots):
            return "no-free-slot"
        head = self.scheduler.peek()  # engine-loop only
        if head is None:
            return None
        if not self.block_mgr.can_admit(
            len(head.prompt_tokens) + head.max_tokens + 1
        ):
            return "no-kv-blocks"
        if self._has_prefilling():
            return "prefill-in-flight"
        return None

    def _flight_record(
        self,
        phase: str,
        device_s: float,
        tokens: int = 0,
        overlapped_s: float = 0.0,
        spec_accepted: int = 0,
        spec_rejected: int = 0,
        program: str | None = None,
        dispatch: int | None = None,
        steps: int = 0,
        active_at_dispatch: int | None = None,
        live_rows: int | None = None,
        routed_pairs: int | None = None,
        expert_load_max: int | None = None,
        state_bytes: int | None = None,
        ahead: int | None = None,
        prompt_tokens: int | None = None,
        bucket: int | None = None,
        clock: dict | None = None,
        pool_rows: dict | None = None,
    ) -> None:
        """One flight sample per dispatched burst, plus its Prometheus
        mirrors. ``program``, ``dispatch``, ``steps``,
        ``active_at_dispatch`` and ``live_rows`` are the dispatch's
        :meth:`_ticket`, taken when it was made; ``clock`` is
        the ticket's times, stamped on the dispatch thread as the program
        was handed to the device and seen complete and by the loop after
        each of the dispatch's awaits (flight.py ``DispatchClock``,
        ``resumed``: the sample's ``gap_ms``, ``program_ms``,
        ``resume_lag_ms``);
        ``routed_pairs``, ``expert_load_max`` and ``state_bytes`` (a hybrid
        model's decode chunk) joined it when the chunk's packed fetch
        landed (:meth:`_await_chunk`). ``ahead`` (a prefill batch) is 1 when
        it was dispatched with its predecessor unfetched (:meth:`_admit`),
        ``prompt_tokens`` the true tokens its rows prefilled (without the
        padding to the bucket and without an adopted prefix), ``bucket`` the
        rows its program ran for each of them (:meth:`_prefill_bucket`).
        ``overlapped_s`` is host work the pipelined loop ran
        under an in-flight dispatch's device shadow (see flight.py).
        ``program`` keys the sample by the compiled variant that ran and
        feeds the attribution ledger's measured side (achieved-vs-
        expected per program, serving/attribution.py) — credited with
        the program's own time on the device where the clock gave one
        (``program_ms``), else with the blocked wait PLUS the overlapped
        host share: under the pipelined loop the device keeps executing
        while the host works in its shadow, so the wait alone would
        systematically understate device time and flatter the per-program
        ratio exactly when pipelining is on. Hot-path discipline
        (graftcheck OBS503): deque appends and counter bumps only — no
        I/O, no locks."""
        timed = bool(clock) and "program_ms" in clock
        if program is not None:
            self.attribution.observe(
                program,
                clock["program_ms"] / 1e3 if timed
                else device_s + overlapped_s,
            )
        if timed:  # /metrics twins of the summary's gap_ms / program_ms
            self._m_device_idle(clock["gap_ms"] / 1e3)
            self._m_device_busy[phase](clock["program_ms"] / 1e3)
        stall = self._admission_stall()
        kv_used = self.block_mgr.used_ratio()
        depths = self.scheduler.depths()
        sample = self.flight.sample(
            phase,
            device_s=device_s,
            overlapped_s=overlapped_s,
            tokens=tokens,
            occupancy=sum(1 for s in self.slots if not s.free),
            queue_depth=self.scheduler.qsize(),
            stall=stall,
            kv_used=kv_used,
            spec_accepted=spec_accepted,
            spec_rejected=spec_rejected,
            queue_by_class=depths,
            program=program,
            dispatch=dispatch,
            steps=steps,
            active_at_dispatch=active_at_dispatch,
            live_rows=live_rows,
            routed_pairs=routed_pairs,
            expert_load_max=expert_load_max,
            state_bytes=state_bytes,
            ahead=ahead,
            prompt_tokens=prompt_tokens,
            bucket=bucket,
            clock=clock,
            pool_rows=pool_rows,
        )
        # watchdog heartbeat: a recorded dispatch IS step progress
        self.watchdog.beat(sample["queue_depth"])
        if (
            phase == "decode"
            and self._spec_auto_disabled
            and self.config.speculative_drafts > 0
        ):
            # measured-uplift backoff: after enough plain chunks, give
            # speculation another audition (the workload's copy-from-
            # context affinity can change mid-stream — RAG turns end,
            # code-edit turns begin)
            self._spec_plain_since_disable += 1
            if self._spec_plain_since_disable >= self._spec_retry_plain:
                self._spec_auto_disabled = False
                self._spec_plain_since_disable = 0
                self._spec_steps_since_cal = self._spec_cal_every
                self._spec_window.clear()
                self._plain_window.clear()
                self._spec_flips.append((time.monotonic(), "enable"))
                self.flight.event(
                    "spec-auto-enable",
                    plain_chunks=self._spec_retry_plain,
                )
        if depths:
            for cls, gauge in self._m_class_depth.items():
                gauge(depths.get(cls, 0))
        hist = self._m_step_hist.get(phase)
        if hist is not None:
            hist(sample["wall_ms"] / 1000.0)
        self._m_host_overhead(sample["host_ms"] / 1000.0)
        self._m_kv_used(kv_used)
        if stall is not None:
            self._m_stall[stall](sample["wall_ms"] / 1000.0)

    def _ticket(
        self, program: str, steps: int, active: int,
        live_rows: int | None = None, pool_rows: dict | None = None,
    ) -> dict:
        """What a dispatch knows when it is made and its flight sample,
        recorded when the result is processed, no longer does: the program
        variant, the dispatch's ordinal (the ``seq`` of its host spans),
        the decode steps it fuses (0 for a prefill), the slots running and,
        for a decode chunk, the rows its read has to fetch
        (:meth:`_read_rows`; a model with a pool a layer kind adds
        :meth:`_pool_rows`). ``clock`` starts empty:
        the dispatch thread stamps it (``flight.clock``) and the loop adds
        its resume lag after each await (``flight.resumed``).
        Made on the loop thread; rides to :meth:`_flight_record` as
        keywords."""
        self._dispatch_seq += 1
        return {
            "program": program, "dispatch": self._dispatch_seq,
            "steps": steps, "active_at_dispatch": active,
            "live_rows": live_rows, "clock": {},
            **({} if pool_rows is None else {"pool_rows": pool_rows}),
        }

    def _pool_rows(self, active: list[int], ahead: int) -> dict | None:
        """A family's own gauge on a decode chunk's flight sample
        (``Family.pool_rows``; models/swa.py: the window layers' rows and
        what both kinds of pool hold), from the running slots' rows,
        ``ahead`` further. None where the family has none."""
        if self._fam is None or self._fam.pool_rows is None:
            return None
        rows = self._lengths.astype(np.int64)
        rows[active] += ahead
        return self._fam.pool_rows(
            self.model_config, self.block_mgr, rows[active])

    def _read_rows(self, active: list[int], ahead: int, window: int) -> int:
        """``live_rows`` of a decode chunk's paged read: the rows a step of
        the chunk reads of each layer's pool, summed over every slot of the
        batch from the host's lengths (``ahead`` rows further for the
        running slots, whose in-flight chunk the host has not processed)."""
        rows = self._lengths.astype(np.int64)
        rows[active] += ahead
        return int(np.minimum(
            rows, window * self.paged_layout.block_size).sum())

    def _flight_stall(self, reason: str) -> None:
        """Record an idle/blocked engine-loop gap as stall time."""
        sample = self.flight.stall(
            reason,
            occupancy=sum(1 for s in self.slots if not s.free),
            queue_depth=self.scheduler.qsize(),
            kv_used=self.block_mgr.used_ratio(),
            queue_by_class=self.scheduler.depths(),
        )
        # heartbeat on idle gaps too: an idle engine beats ~once a second,
        # so queue-empty idleness can never read as a wedge
        self.watchdog.beat(sample["queue_depth"])
        self._m_stall[reason](sample["wall_ms"] / 1000.0)

    def _slo_record(self, objective: str, good: bool) -> None:
        """Record one event against an SLO objective (engine loop only;
        no-op without a declared spec or for undeclared objectives)."""
        if self.slo is not None:
            self._slo_emit(objective, self.slo.record(objective, good))

    def _slo_record_latency(self, objective: str, seconds: float) -> None:
        """Record a measured latency; the tracker judges it against the
        objective's declared threshold (no-op when undeclared)."""
        if self.slo is not None:
            self._slo_emit(
                objective, self.slo.record_latency(objective, seconds * 1000.0)
            )

    def _slo_emit(self, objective: str, verdict: dict | None) -> None:
        """Mirror one SLO evaluation onto the burn/budget gauges and
        emit an ``alert`` flight event when the multi-window fast-burn
        condition transitions — alerts fire at record time, not scrape
        time, so an unwatched engine still leaves the evidence in its
        event ring."""
        if verdict is None:
            return
        gauge = self._m_slo_burn.get(objective)
        if gauge is not None:
            gauge(verdict["burn_rate_fast"] or 0.0)
        gauge = self._m_slo_budget.get(objective)
        if gauge is not None:
            gauge(verdict["budget_remaining"])
        if verdict["transition"]:
            self.flight.event(
                "alert",
                objective=objective,
                state="firing" if verdict["alerting"] else "resolved",
                burn_rate_fast=verdict["burn_rate_fast"],
                burn_rate_slow=verdict["burn_rate_slow"],
                budget_remaining=verdict["budget_remaining"],
                target=verdict["target"],
            )
            if verdict["alerting"] and self.incidents is not None:
                # page-threshold crossing: snapshot the evidence at the
                # breach instant (per-objective cooldown in the recorder)
                self._incident_capture(
                    "slo-fast-burn",
                    {
                        "source": "slo",
                        "objective": objective,
                        "burn_rate_fast": verdict["burn_rate_fast"],
                        "burn_rate_slow": verdict["burn_rate_slow"],
                        "budget_remaining": verdict["budget_remaining"],
                        "target": verdict["target"],
                    },
                    dedup_key=objective,
                )

    def health(self) -> dict[str, Any]:
        """Wait-free health snapshot (OBS504: callable from probe
        handlers while the engine is wedged — snapshot reads and
        arithmetic only, no device work, no locks). Judges the watchdog
        heartbeat against the live queue/occupancy and runs the
        degradation predicates over the flight window; a state
        transition is recorded as a ``health`` flight event with the
        stall evidence."""
        queued = self.scheduler.qsize()
        occupancy = sum(1 for s in self.slots if not s.free)
        # streaming TBT burn predicate (wait-free: committed-alert dict
        # reads): classes whose tbt-p99-s error budget is fast-burning
        # degrade the engine exactly like the watchdog's own predicates
        tbt_burn = [
            name
            for name, tracker in self._stream_slo.items()
            if tracker.alerting.get("tbt")
        ]
        verdict = self.watchdog.evaluate(
            queued=queued,
            occupancy=occupancy,
            extra_reasons=tuple(
                f"tbt burn-rate alert: class {name!r} is burning its "
                f"tbt-p99-s error budget at page rate"
                for name in sorted(tbt_burn)
            ),
            samples=self.flight.recent(240),
            # 256, not the display tail's 64: the shrink-pressure
            # predicate compares pool-shrink events across a whole
            # recovery window, and a busy engine emits >64 events
            # (pool-grows, the shrink's own preempt/resume pairs)
            # between two shrinks — a short tail would age the first
            # one out exactly under the sustained pressure the
            # escalation exists to flag (the ring holds 512)
            events=self.flight.recent_events(256),
            # a lockstep-broken engine stays registered but refuses all
            # requests: only a pod restart recovers the slice, so it
            # reports wedged and the liveness probe does the recycling
            stopped=self._stop,
        )
        if verdict.pop("transition"):
            self.flight.event(
                "health",
                state=verdict["state"],
                previous=verdict["previous"],
                reasons=list(verdict["reasons"]),
                last_step_age_s=verdict["last_step_age_s"],
                queued=queued,
                occupancy=occupancy,
            )
            if self.incidents is not None and verdict["state"] in (
                "degraded",
                "wedged",
            ):
                # a worsening transition is a page: classify the trigger
                # by the dominant reason so the bundle's worst-K journeys
                # rank by the segment that reason indicts
                reasons = list(verdict["reasons"])
                if verdict["state"] == "wedged":
                    kind = "health-wedged"
                elif any("memory pressure" in r for r in reasons):
                    kind = "shrink-pressure"
                elif any("tbt burn" in r for r in reasons):
                    kind = "tbt-burn"
                else:
                    kind = "health-degraded"
                self._incident_capture(
                    kind,
                    {
                        "source": "health",
                        "state": verdict["state"],
                        "previous": verdict["previous"],
                        "reasons": reasons,
                        "queued": queued,
                        "occupancy": occupancy,
                    },
                )
        if self.incidents is not None:
            # breaker-storm predicate over the already-snapshotted event
            # tail (router breaker events mirror into this ring): fires
            # independently of watchdog transitions — a replica fanout
            # melting down is an incident even while this engine's own
            # loop is healthy
            storm = breaker_storm(
                self.flight.recent_events(256), time.monotonic()
            )
            if storm is not None:
                self._incident_capture(
                    "breaker-storm", {"source": "health", **storm}
                )
            if self.adapter_store is not None:
                # adapter eviction-storm predicate (docs/ADAPTERS.md):
                # one adapter bouncing out of the tiers repeatedly
                # inside a single hydrate window — thrash the next
                # request re-pays — over the same snapshotted tail
                thrash = adapter_eviction_storm(
                    self.flight.recent_events(256),
                    time.monotonic(),
                    window_s=self.adapter_store.spec.hydrate_timeout_s,
                )
                if thrash is not None:
                    self._incident_capture(
                        "adapter-storm",
                        {"source": "health", **thrash},
                        dedup_key=thrash["adapter"],
                    )
        warmup = self._warmup_state()
        # a draining engine is alive but must take no new traffic: ready
        # drops (the router and the readiness probe both key off it)
        ready = (
            warmup not in ("pending", "running", "failed")
            and verdict["state"] != "wedged"
            and not self._draining
        )
        out = {
            "model": self.config.model,
            "slots": self.config.slots,
            **verdict,
            "warmup": warmup,
            "draining": self._draining,
            "ready": ready,
            # adaptive pool-shrink posture (docs/RESILIENCE.md): blocks
            # currently withheld from the KV admission budget — the pod
            # probes surface it so an operator reading /healthz sees a
            # degraded-capacity replica without another round trip
            "budget_withheld": self.block_mgr.budget_reduction,
        }
        if self.config.streaming:
            # which classes are currently fast-burning their tbt-p99-s
            # budget (empty list when healthy) — keyed off the same
            # committed-alert reads that fed extra_reasons above, so the
            # list and the DEGRADED verdict can never disagree
            out["tbt_burn"] = sorted(tbt_burn)
        return out

    def _incident_capture(
        self,
        kind: str,
        evidence: dict[str, Any],
        dedup_key: str | None = None,
    ) -> None:
        """Assemble one incident bundle at the breach site and hand it to
        the recorder's writer thread. Wait-free end to end (graftcheck
        INC1601): the cooldown gate is GIL-atomic dict ops, every section
        is wait-free by its own contract (flight summary, journey-ledger
        snapshots, attribution/survival/kvtransfer, SLO status), and the
        handoff is a deque append — this runs inside ``health()`` (probe
        handlers, OBS504's domain) and the finish path."""
        rec = self.incidents
        if rec is None or not rec.should_capture(kind, dedup_key):
            return
        # event-tail slice: only events past the recorder's seq
        # high-water mark, so overlapping captures dedup exactly
        events = self.flight.recent_events(256)
        watermark = rec.last_event_seq
        fresh = [e for e in events if e.get("seq", 0) > watermark]
        if events:
            rec.last_event_seq = max(watermark, events[-1].get("seq", 0))
        bundle: dict[str, Any] = {
            # wall anchor for cross-pod timeline alignment only
            # graftcheck: disable=OBS501 display anchor, never subtracted
            "captured_at_ms": round(time.time() * 1000.0, 3),
            "model": self.config.model,
            "trigger": {"kind": kind, **evidence},
            "flight": self.flight.summary(),
            "events": fresh,
            "worst_journeys": worst_journeys(kind),
            "attribution": self.attribution_section(),
            "survival": self.survival_section(),
            "kvtransfer": self.kv_transfer_section(),
            "breakers": {
                "open": self.flight.events_by_type.get("breaker-open", 0),
                "close": self.flight.events_by_type.get("breaker-close", 0),
            },
            "slo": self.slo_status(),
            "streaming": (
                self.streaming_section() if self.config.streaming else None
            ),
            "config": self.config.to_dict(),
        }
        if self.adapter_store is not None:
            # tier residency + ledger at the breach instant (key absent
            # on adapter-less engines: their bundles stay byte-identical
            # to a pre-adapter build)
            bundle["adapters"] = self.adapter_store_section()
        bundle_id = rec.submit(bundle)
        self.flight.event("incident", bundle=bundle_id, trigger=kind)

    def _warmup_state(self) -> str:
        """``not-required`` (no warmup_on_start), ``pending`` (gate armed
        but nothing triggered it yet), ``running``, ``done``, or
        ``failed`` (done with an exception: the engine is not ready and
        every request raises the warm-up's error — a program that did
        not build at warm-up will not build lazily either)."""
        if not self.config.warmup_on_start:
            return "not-required"
        task = self._warmup_task
        if task is None:
            return "pending"
        if not task.done():
            return "running"
        if task.cancelled() or task.exception() is not None:
            return "failed"
        return "done"

    def slo_status(self) -> dict[str, Any] | None:
        """The SLO section for ``stats()`` / ``/flight/summary`` (None
        without a declared spec). Wait-free like :meth:`health`."""
        if self.slo is None:
            return None
        return self.slo.status()

    def streaming_section(self) -> dict[str, Any]:
        """The streaming-delivery payload for ``stats()["streaming"]``
        (streaming-configured engines only — the default stats surface
        stays pinned without the flag). Wait-free by the same contract
        as :meth:`attribution_section`: counter snapshots and digest
        walks only, no locks, no awaits — a stats poll must answer while
        a stream is mid-emit."""
        return {
            # streams currently holding a decode slot (the cancellation
            # leak detector in tools/engine_top.py compares this against
            # cancelled-vs-reclaimed below)
            "active": sum(
                1
                for s in self.slots
                if not s.free
                and s.request is not None
                and s.request.on_chunk is not None
            ),
            "emits": self.stream_emits_total,
            "stalls": self.stream_stalls_total,
            "cancelled": self.stream_cancels_total,
            "reclaimed": self.stream_reclaims_total,
            # per-class inter-token-interval digests — bounded summaries
            # (count/p50/p99/max/mean), never raw interval lists
            "tbt": {
                name: digest.summary()
                for name, digest in sorted(self._stream_tbt_by_class.items())
            },
            "tbt_burn": sorted(
                name
                for name, tracker in self._stream_slo.items()
                if tracker.alerting.get("tbt")
            ),
        }

    def speculative_section(self) -> dict[str, Any]:
        """The speculation payload for ``stats()["speculative"]`` and the
        ``/flight/summary`` entry (speculative-configured engines only —
        the default surfaces stay pinned without the flag). Wait-free:
        counter snapshots only. Carries the fused-tail plumbing counters
        (dispatches/fetches must track 1:1 — one packed fetch per fused
        draft+verify step) and the measured-uplift plane that drives
        auto-disable, so engine_top's speculation panel and ``--analyze``
        need no extra engine surface."""
        return {
            "steps": self.spec_steps,
            "drafts_accepted": self.spec_accepted,
            # rejected drafts make a spec slowdown decomposable from a
            # live engine: high reject ratio = wasted verify FLOPs, not
            # host overhead
            "rejected": self.spec_rejected,
            "dispatches": self._spec_dispatches,
            "fetches": self._spec_fetches,
            "uplift": self._spec_last_uplift,
            "auto_disabled": self._spec_auto_disabled,
            "flips": len(self._spec_flips),
            "window_steps": len(self._spec_window),
            "window_plain": len(self._plain_window),
        }

    def attribution_section(self) -> dict[str, Any]:
        """The device-attribution payload: per-program achieved-vs-
        expected ledger plus the HBM memory ledger — what
        ``stats()["attribution"]``, the pod ``/attribution``/``/memory``
        endpoints, and the control-plane fan-in serve. Wait-free by
        contract (graftcheck OBS505, the attribution twin of OBS504):
        snapshot reads and arithmetic only — an attribution poll must
        answer even while the engine is wedged mid-dispatch. The
        ``hbm_bytes_by_owner`` Prometheus gauges refresh here, so any
        reader keeps the scrape surface current."""
        memory = self._memory_ledger()
        owners = memory["hbm_bytes_by_owner"]
        for owner, gauge in self._m_hbm_owner.items():
            gauge(owners.get(owner) or 0)
        return {
            "model": self.config.model,
            "slots": self.config.slots,
            "device_kind": self._device_kind,
            "hbm_gbps_published": self._hbm_gbps,
            "programs": self.attribution.report(),
            "memory": memory,
        }

    def _memory_ledger(self) -> dict[str, Any]:
        """Live ``hbm_bytes_by_owner`` breakdown (serving/attribution.py
        :func:`memory_ledger`). Weight/pool totals were computed once at
        init (the shapes are fixed; the live handles are donated and
        rebound on the dispatch thread, so readers never touch them);
        the LRU and prefix-cache terms are snapshot reads."""
        return memory_ledger(
            weights_bytes=self._weights_bytes,
            kv_pool_bytes=self._kv_cache_bytes,
            prefix_blocks=self.block_mgr.prefix_block_count(),
            bytes_per_block=self._kv_block_bytes,
            sampler_bytes=self._sampler_dev_cache.device_bytes(),
            tables_bytes=self._tables_dev_cache.device_bytes(),
            # serialized handoff payloads awaiting pickup (host bytes,
            # attributed so a stalled handoff pipeline is visible in the
            # same ledger operators already watch)
            in_transit_bytes=self._kv_in_transit_bytes,
            limit_bytes=self._hbm_limit,
            # adaptive pool-shrink: budget blocks withheld after a device
            # allocator failure — a sub-owner of the (unchanged) pool
            # bytes, so the owner sum is identical across shrink/restore
            kv_withheld_bytes=(
                self.block_mgr.budget_reduction * self._kv_block_bytes
            ),
            recurrent_state_bytes=self._state_bytes,
        )

    @staticmethod
    def _sampler_mode(temps, topks, topps) -> tuple:
        """(use_top_p, use_top_k, all_greedy) for the given active rows —
        the static specialization key for compiled sampler variants."""
        use_top_p = bool((topps < 1.0).any())
        use_top_k = bool((topks > 0).any())
        all_greedy = bool((temps <= 0).all()) and not use_top_p and not use_top_k
        return (use_top_p, use_top_k, all_greedy)

    def _read_blocks_for(self, max_len: int) -> int:
        """Block-table columns a decode or verify variant sweeps: the
        smallest bucketed window of rows covering ``max_len`` (the chunk's
        new tokens live in the chunk buffer, not the window), in blocks.

        Decode is cache-read bound, so window granularity is read traffic:
        power-of-two buckets read up to 2× the needed rows near bucket
        edges. Hybrid granularity bounds BOTH costs: 128-multiples up to
        1024 rows (excess <128 rows/slot where most serving lengths live),
        powers of two beyond (a long-context engine would otherwise compile
        a fresh ~30s decode variant every 128 generated tokens)."""
        if self._fam is not None and self._fam.one_decode_window:
            # one decode program a chunk size (the family's module says why)
            return self.paged_layout.max_blocks_per_slot
        if max_len <= 1024:
            window = max(128, -(-max_len // 128) * 128)
        else:
            window = 2048
            while window < max_len:
                window *= 2
        window = min(window, self.model_config.max_seq_len)
        bs = self.paged_layout.block_size
        return max(1, min(-(-window // bs), self.paged_layout.max_blocks_per_slot))

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    async def generate(
        self,
        prompt: str | list[int],
        options: dict[str, Any] | None = None,
        on_token: Callable[[int, float, bool], Any] | None = None,
        on_chunk: Callable[[list, str, bool], Any] | None = None,
        _warmup_probe: bool = False,
    ) -> dict[str, Any]:
        """Generate a completion. ``on_token(token_id, logprob, last)`` fires
        per token (sync or async). ``on_chunk(new_token_ids, new_text,
        is_final)`` fires once per committed decode chunk at the burst-flush
        safe point — ``new_text`` deltas concatenate byte-identically to the
        non-streaming ``text`` (UTF-8 partials and possible stop-sequence
        prefixes are held back until they resolve). Returns
        ``{"tokens", "text", "logprobs", "num_prompt_tokens", "ttft"}``.

        ``options["stream-key"]`` (the gateway's ``langstream-stream-id``)
        registers the request with the process-wide stream-cancel registry
        so a client disconnect observed at the gateway cancels this future;
        the decode loop frees the slot at the next chunk boundary.

        ``_warmup_probe`` is internal: warmup()'s own generate calls skip
        the warmup gate below (they ARE the warmup)."""
        if self._stop:
            # closed, or stopped after a broken lockstep group: enqueueing
            # would hang forever (the restarted loop exits immediately and
            # never resolves the future) — fail loudly instead so the pod
            # restarts the slice
            raise RuntimeError(
                "serving engine is stopped (closed or lockstep group broken)"
            )
        options = options or {}
        if self.config.warmup_on_start and not _warmup_probe:
            # one shared task (also credited to explicit warmup() calls):
            # every early arrival awaits it, so the probe/wave shapes
            # aren't perturbed by real traffic and real requests only
            # start once the variants exist. A warm-up that failed raises
            # here, for this request and every later one: nothing serves
            # from an engine whose programs did not build.
            await asyncio.shield(self._warmup_begun())
        tokens = (
            self.tokenizer.encode(prompt) if isinstance(prompt, str) else list(prompt)
        )
        max_prompt = self.model_config.max_seq_len - 2
        if len(tokens) > max_prompt:
            tokens = tokens[-max_prompt:]
        top_k = int(options.get("top-k", 0))
        if top_k > 64:
            log.warning("top-k %d exceeds the compiled window of 64; clamping", top_k)
            top_k = 64
        max_tokens = min(
            int(options.get("max-tokens", self.config.default_max_tokens)),
            self.model_config.max_seq_len - len(tokens) - 1,
        )
        if not self.block_mgr.fits_ever(len(tokens) + max_tokens + 1):
            raise ValueError(
                f"request needs {len(tokens) + max_tokens + 1} tokens of KV, "
                f"more than the paged pool can ever hold "
                f"({self.block_mgr.stats()['num_blocks']} blocks of "
                f"{self.paged_layout.block_size}); lower max-tokens or grow "
                f"kv-pool-blocks/kv-pool-fraction"
            )
        stop = _normalize_stop(options.get("stop"))
        adapter = str(options.get("adapter", "") or "")
        if adapter and self.adapter_store is None:
            # refused loudly at submit: a silently-ignored adapter would
            # serve base-model output under the tenant's fine-tune name
            raise ValueError(
                f"request names adapter {adapter!r} but this engine has "
                "no adapter store configured (serving adapter-store)"
            )
        request = _Request(
            prompt_tokens=tokens,
            max_tokens=max_tokens,
            temperature=float(options.get("temperature", 0.0)),
            top_k=top_k,
            top_p=float(options.get("top-p", 1.0)),
            on_token=on_token,
            future=asyncio.get_running_loop().create_future(),
            loop=asyncio.get_running_loop(),
            enqueue_time=time.monotonic(),
            # warmup probes must not attach synthetic phase spans to
            # whichever record's task happened to trigger the warmup gate
            trace=None if _warmup_probe else current_context(),
            warmup=_warmup_probe,
            stop=stop,
            presence_penalty=float(options.get("presence-penalty", 0.0)),
            frequency_penalty=float(options.get("frequency-penalty", 0.0)),
            tenant=str(options.get("qos-tenant", "") or ""),
            priority=normalize_priority(options.get("priority")),
            # end-to-end deadline (docs/RESILIENCE.md): "deadline" is
            # the absolute epoch stamp the gateway/agent forwarded from
            # the langstream-deadline header; "deadline-s" a caller-
            # relative budget. Malformed values degrade to None.
            deadline=_deadline_from_options(options),
            on_chunk=on_chunk,
            stream_key=(
                str(options["stream-key"])
                if options.get("stream-key")
                else None
            ),
            adapter=adapter,
        )
        if on_chunk is not None and self.config.streaming:
            # bounded per-request TBT digest (never the raw interval
            # list); only streaming-configured engines pay for the plane
            request.stream_tbt = TbtDigest()
        if request.stream_key is not None and not _warmup_probe:
            # disconnect-as-cancellation bridge: the gateway cancels by
            # this key from its socket teardown; the entry self-cleans
            # when the future resolves either way
            STREAMS.register(
                request.stream_key, request.future, request.loop
            )
        if not _warmup_probe:
            # journey ledger key: the trace id when traced (the one id
            # that already spans gateway → broker → engine and now rides
            # the kvtransfer header), a fresh same-shaped id otherwise
            request.journey_id = (
                request.trace.trace_id
                if request.trace is not None
                else fresh_trace_id()
            )
            self._journey(
                request, "submit",
                model=self.config.model, role=self._pool_role,
                prompt_tokens=len(tokens), max_tokens=max_tokens,
            )
        if request.deadline is not None and not _warmup_probe:
            left = remaining_s(request.deadline)
            if left <= 0.0:
                # the deadline acceptance contract: an unmeetable budget
                # is refused with an explicit event BEFORE the request
                # ever queues — never a silent late completion
                raise self._note_deadline_shed(request, "submit", left)
        try:
            if self._draining and not _warmup_probe:
                # drain-before-terminate: admission is closed. The shed
                # is EXPLICIT (Retry-After) so the gateway/router resends
                # to a live replica instead of losing the request into a
                # dying pod's queue.
                raise RateLimited(
                    "draining", 1.0,
                    "engine is draining (scale-down or pod termination in "
                    "progress); retry against another replica",
                )
            self.scheduler.submit(request)
        except RateLimited as e:
            # load shed / tenant throttle: refused before any slot or
            # block was touched — callers (gateway, agents) map this to
            # 429 + Retry-After
            self.flight.event(
                "shed", reason=e.reason, tenant=request.tenant,
                priority=request.priority,
                retry_after_s=e.retry_after,
            )
            self._journey(
                request, "shed", reason=e.reason,
                retry_after_s=e.retry_after,
            )
            if e.reason == "draining":
                self._drain_shed += 1
            if self._m_shed is not None:
                self._m_shed(1)
            if not _warmup_probe:
                self._slo_record("shed-rate", False)
            raise
        if not _warmup_probe:
            # the shed-rate objective counts every submission: admitted =
            # good, refused = bad (recorded in the except arm above)
            self._slo_record("shed-rate", True)
            if self.journal is not None:
                # crash-requeue journal (docs/RESILIENCE.md): the work is
                # accepted NOW — journaled before the caller ever sees a
                # future, retired when finish/shed/fail answers it
                self.journal.admit(request_entry(request))
                if self._m_journal_depth is not None:
                    self._m_journal_depth(self.journal.depth())
        self._ensure_loop()
        self._wake.set()
        return await request.future

    def _warmup_begun(self) -> "asyncio.Task":
        """The one shared warmup task: created on first need (explicit
        warmup() call or the warmup_on_start gate), credited to both — an
        explicit pre-warm means the gate has nothing left to do."""
        if self._warmup_task is None:
            self._warmup_task = asyncio.ensure_future(self._do_warmup())

            def _log_done(task: asyncio.Task) -> None:
                if task.cancelled():
                    return
                if task.exception() is not None:
                    log.error(
                        "engine warmup failed; requests are refused and "
                        "the engine reports not ready",
                        exc_info=task.exception(),
                    )
                else:
                    log.info("engine warmup complete: %s", task.result())

            self._warmup_task.add_done_callback(_log_done)
        return self._warmup_task

    async def warmup(self) -> dict[str, int]:
        """Compile the serving-path jit variants before real traffic (see
        :meth:`_do_warmup`). Idempotent: shares one task with the
        warmup_on_start gate, so pre-warming explicitly never repeats the
        probe/wave."""
        return await asyncio.shield(self._warmup_begun())

    async def _do_warmup(self) -> dict[str, int]:
        """A lone greedy request (light-regime burst, single-row prefill),
        then a concurrent wave one past the light-load threshold
        (heavy-regime burst, power-of-two padded prefill rows,
        prefix-cache continuation when enabled). Greedy only — non-greedy
        sampler variants compile on first use; greedy is what the
        latency-sensitive paths serve. Then one greedy prefill of one row
        at every bucket of :func:`_prefill_bucket_rows` that is no power of
        two (:meth:`_warmup_midpoints`; none with ``max-seq-len`` up to 4,096):
        the programs the engine's rule adds are the engine's to load, since
        a caller who warms by the lengths it will send, the longest of each
        power-of-two range, does not reach them. Prompts in other
        prefill-length buckets, and other row counts at every bucket, still
        pay one compile on first sight. Warmup tokens count toward engine
        metrics (they ran on the chips)."""
        text = "engine warmup probe text. " * 4
        k = max(self.config.decode_chunk, self.config.decode_chunk_light) + 1
        opts = {"max-tokens": k, "temperature": 0}
        self.flight.event("warmup", stage="begin")
        await self.generate(text, dict(opts), _warmup_probe=True)
        wave = min(
            self.config.slots,
            max(2, self._light_threshold() + 1, self.config.prefill_batch),
        )
        await asyncio.gather(
            *(
                self.generate(text, dict(opts), _warmup_probe=True)
                for _ in range(wave)
            )
        )
        midpoints = self._warmup_midpoints()
        ids = self.tokenizer.encode(text)
        for i, rows in enumerate(midpoints.values()):
            # in turn: two of them at once are two programs' scratch; each
            # starts at another token, so that none continues a prefix
            await self.generate(
                [ids[(i + j) % len(ids)] for j in range(rows)],
                {"max-tokens": 1, "temperature": 0}, _warmup_probe=True,
            )
        result = {
            "decode_variants": len(self._decode_chunk_fns),
            "prefill_variants": len(self._prefill_fns),
        }
        self.flight.event(
            "warmup", stage="end", **result, prefill_midpoints=list(midpoints)
        )
        return result

    def _warmup_midpoints(self) -> dict[int, int]:
        """``{bucket: prompt rows}`` of the warm-up's midpoint prefills: the
        buckets of :func:`_prefill_bucket_rows` that are no power of two, each
        with the shortest prompt it takes (flash follows the true length),
        where this engine prefills such a prompt whole and its pool can hold
        it. None with ``max-seq-len`` up to 4,096."""
        out = {}
        for bucket in _prefill_midpoints(self.model_config.max_seq_len):
            rows = bucket // 3 * 2 + 1  # one past the power of two under it
            if 0 < self.config.prefill_chunk < rows:
                continue  # prefilled in chunks: another program
            if self.block_mgr.fits_ever(rows + 2):
                out[bucket] = rows
        return out

    def stats(self) -> dict[str, Any]:
        out = {
            "model": self.config.model,
            "slots": self.config.slots,
            "active": sum(1 for s in self.slots if not s.free),
            "queued": self.scheduler.qsize(),
            "total-generated": self.total_generated,
            # admission-policy counters (per-class queued/admitted/shed/
            # preempted under QoS; plain FIFO totals otherwise) — the
            # control-plane /qos route reads these off /flight/summary
            "scheduler": self.scheduler.stats(),
            "decode-chunks": {
                "light": self._light_chunks,
                "heavy": self._heavy_chunks,
                # the one-fetch invariant, observable live: a ratio above
                # 1.0 means the decode tail is re-crossing the host
                # boundary (regression canary for the fused sampler)
                "dispatched": self._decode_dispatches,
                "fetched": self._decode_fetches,
                "host_fetches_per_chunk": (
                    round(self._decode_fetches / self._decode_dispatches, 4)
                    if self._decode_dispatches else 0.0
                ),
            },
            # pipelined loop posture + the bounded device-upload caches
            # (size/hits/misses/evictions — the eviction counter is the
            # long-lived-engine leak canary the LRU bound exists for)
            "pipeline": self._pipeline_on,
            "device-cache": {
                "tables": self._tables_dev_cache.stats(),
                "sampler": self._sampler_dev_cache.stats(),
            },
            # per-phase dispatched-step counts (flight recorder): lets a
            # running engine decompose where its dispatches go without a
            # bench run
            "steps": dict(self.flight.steps_by_phase),
            # the share of prefill batches dispatched one ahead, and the
            # requests a batch carried in the mean (_admit)
            "prefill_ahead_share": self.flight.prefill_ahead_share,
            "prefill_rows_mean": self.flight.prefill_rows_mean,
            # how a decode step's pass over the Mamba-2 state is lowered
            # (what mamba_step was handed; None without such state)
            "ssm_state_kernel": self.ssm_state_kernel,
            # the form of every program's commit of new rows into the pool
            # (write_rows: it engages on every commit or on none)
            "pool_commit_kernel": self.pool_commit_kernel,
            # the form a prefill's routed experts' grouped pass takes
            # (models/moe.py grouped_form of what the programs are handed
            # and the share of the experts held; None without experts), and
            # how often it engages: the prefill programs dispatched and
            # those whose rows exceed that form's bound
            "moe_grouped_kernel": self.moe_grouped_kernel,
            "prefill_dispatches": self._prefill_dispatches,
            "prefill_dispatches_grouped": (
                None if self.moe_grouped_kernel is None
                else self._prefill_dispatches_grouped),
            # those whose bucket is a midpoint (_prefill_bucket_rows), and
            # the rows the prefill batches' programs ran over their prompts'
            # true tokens
            "prefill_dispatches_midpoint": self._prefill_dispatches_midpoint,
            "prefill_padded_rows_share": self.flight.prefill_padded_rows_share,
            # watchdog verdict + warmup/readiness posture (serving/health.py)
            "health": self.health(),
            # drain-before-terminate posture + last drain's counts
            # (docs/FLEET.md): the autoscaler's evidence trail
            "drain": self._drain_section(),
            # disaggregated-pool posture + handoff counters
            # (docs/DISAGG.md): combined engines report role=combined
            # with zeroed counters
            "kvtransfer": self.kv_transfer_section(),
            # device attribution plane: per-program achieved-vs-expected
            # ledger + hbm_bytes_by_owner (serving/attribution.py)
            "attribution": self.attribution_section(),
            # device-survival plane (docs/RESILIENCE.md): live KV budget
            # vs configured, shrink/restore counters, fault-injection
            # state, crash-requeue journal depth
            "survival": self.survival_section(),
        }
        slo = self.slo_status()
        if slo is not None:
            out["slo"] = slo
        if self.config.streaming:
            # streaming delivery plane: active streams, emit/stall/cancel
            # counters, per-class TBT digests (docs/OBSERVABILITY.md)
            out["streaming"] = self.streaming_section()
        if self.prefix_store is not None:
            # tiered prefix store: per-tier bytes/budgets, hit and
            # demotion/eviction counters, exact byte ledger
            # (docs/PREFIX.md)
            out["prefixstore"] = self.prefix_store_section()
        if self.adapter_store is not None:
            # tiered multi-LoRA adapter store: per-tier bytes/budgets,
            # hit/load/eviction counters, resident rows, exact byte
            # ledger (docs/ADAPTERS.md)
            out["adapters"] = self.adapter_store_section()
        out["kv"] = {"layout": "paged", **self.block_mgr.stats()}
        if self.config.speculative_drafts > 0:
            out["speculative"] = self.speculative_section()
        if self.incidents is not None:
            # incident capture plane: captured/suppressed/evicted counts
            # plus the bounded bundle index (docs/OBSERVABILITY.md)
            out["incidents"] = self.incidents.stats()
        return out

    async def close(self) -> None:
        self._stop = True
        self._wake.set()
        if self._loop_task is not None:
            await self._loop_task
        if self._lockstep is not None:
            self._lockstep.close()
        if self.prefix_store is not None:
            self.prefix_store.close()
        if self.adapter_store is not None:
            self.adapter_store.close()
        if self.journal is not None:
            # flush the retire tail: a clean shutdown leaves a journal
            # that replays exactly the work this process never answered
            self.journal.close()
        if self.incidents is not None:
            # flush any in-flight bundle: evidence captured moments
            # before a shutdown is exactly the evidence worth keeping
            self.incidents.close()
        # wait=True: the loop task above is done, so the executor queue is
        # empty or finishing its last closure — joining it here is what
        # makes the reference drops below race-free (the dispatch thread
        # no longer exists when they run)
        self._executor.shutdown(wait=True)
        # the clock's watcher thread waits on results of programs the
        # dispatch thread enqueued: with that thread gone nothing feeds it
        self.flight.clock.close()
        # evict from the singleton cache: a closed engine must not be handed
        # out again (its loop would exit immediately, stranding requests)
        with self._instances_lock:
            for key, inst in list(self._instances.items()):
                if inst is self:
                    del self._instances[key]
        # drop the HBM-heavy references NOW: a closed engine object can
        # outlive close() (caller locals, task frames) and at the 8B shape
        # its weights+KV are ~12GB — a second engine in the same process
        # (speculation on/off comparison, model reload) must not OOM
        # against a ghost (r5: the speculative bench child died exactly
        # this way)
        # graftcheck: disable=RACE801 loop task awaited + executor joined (wait=True): no dispatch closure can still run
        self.params = None
        # graftcheck: disable=RACE801 loop task awaited + executor joined (wait=True): no dispatch closure can still run
        self.cache_k = self.cache_v = None
        # graftcheck: disable=RACE801 loop task awaited + executor joined (wait=True): no dispatch closure can still run
        self.state = None
        # graftcheck: disable=RACE801 loop task awaited + executor joined (wait=True): no dispatch closure can still run
        self._ad_layers = None
        self._decode_chunk_fns.clear()
        self._pending_chunk = None
        # graftcheck: disable=RACE801 loop task awaited + executor joined (wait=True): no dispatch closure can still run
        self._tables_dev_cache.clear()
        self._sampler_dev_cache.clear()

    # ------------------------------------------------------------------
    # drain-before-terminate (docs/FLEET.md)
    # ------------------------------------------------------------------

    async def drain(self, grace_s: float = 30.0) -> dict[str, Any]:
        """Drain this engine for termination: stop admitting new work
        (submissions shed with ``Retry-After``), preempt-and-requeue
        every running generation at the loop's safe point (the PR 4 QoS
        machinery: generated tokens + sampling params ARE the snapshot,
        resume is byte-identical), then serve the backlog — queued plus
        requeued — to completion. When the grace budget expires with
        work still in flight, the leftovers are failed *explicitly* with
        :class:`RateLimited` (never silently dropped): the caller knows
        to retry elsewhere.

        Returns ``{"requeued", "completed", "shed", "duration_s"}`` —
        also emitted as a ``drain`` flight event and surfaced in
        ``stats()["drain"]``. Idempotent: a second call joins the wait
        with its own grace budget. Draining is terminal for admission
        (the pod is going away); the engine still answers stats/health.
        """
        if self._stop:
            return {
                "requeued": 0, "completed": 0, "shed": 0,
                "duration_s": 0.0, "stopped": True,
            }
        start = time.monotonic()
        if not self._draining:
            self._draining = True
            self._drain_pass_done = False
            self._drain_requeued = 0
            self._drain_shed = 0
            self._drain_base_completed = self.completed_requests
            self._drain_report = None
            self.flight.event(
                "drain", stage="begin",
                queued=self.scheduler.qsize(),
                inflight=sum(1 for s in self.slots if not s.free),
            )
        self._ensure_loop()
        self._wake.set()
        deadline = start + grace_s
        while time.monotonic() < deadline:
            if (
                self.scheduler.empty()
                and all(s.free for s in self.slots)
                and self._pending_chunk is None
                and not self._prefix_hydrating
            ):
                break
            await asyncio.sleep(0.02)
        leftovers = (
            self.scheduler.qsize()
            + len(self._prefix_hydrating)
            + sum(
                1
                for s in self.slots
                if s.request is not None and not s.request.future.done()
            )
        )
        if leftovers:
            # grace exhausted: shed the remainder loudly. _fail_inflight
            # releases every slot/block and fails queued + running
            # futures, so nothing is ever silently lost — the error
            # carries retry_after for the 429 mapping.
            self._fail_inflight(
                RateLimited(
                    "draining", 1.0,
                    f"engine drained with {leftovers} requests unfinished "
                    f"after {grace_s:.1f}s grace; retry another replica",
                )
            )
            self._drain_shed += leftovers
        report = {
            "requeued": self._drain_requeued,
            "completed": self.completed_requests - self._drain_base_completed,
            "shed": self._drain_shed,
            "duration_s": round(time.monotonic() - start, 3),
        }
        self._drain_report = report
        self.flight.event("drain", stage="end", **report)
        return report

    def _drain_preempt_pass(self) -> int:
        """One-shot preempt-and-requeue of every occupied slot, run by
        the loop at its safe point (no dispatch in flight, pending chunk
        drained — the same invariant :meth:`_maybe_preempt` relies on).
        Requeued work resumes front-of-class and completes during the
        drain wait; the preempt/resume round-trip is what makes a
        drained generation byte-identical to an undisturbed one."""
        requeued = 0
        # requests stashed awaiting a T2 prefix hydration rejoin the
        # queue NOW (cold compute if their blobs never landed): a drain
        # must serve or shed every accepted request, and a stash that
        # outlives the loop would strand its future. Reversed: each
        # requeues at the FRONT, so newest-first keeps arrival order.
        for request, _deadline, _digests in reversed(self._prefix_hydrating):
            if request.future.done():
                continue
            self._journey(request, "hydrate-done", timeout=True, drain=True)
            self.scheduler.requeue_front(request)
            requeued += 1
        self._prefix_hydrating = []
        for slot_id, slot in enumerate(self.slots):
            request = slot.request
            if request is None or request.future.done():
                continue
            self._preempt_slot(slot_id, reason="drain")
            requeued += 1
        return requeued

    def _drain_section(self) -> dict[str, Any]:
        """The ``stats()["drain"]`` section: final report once the drain
        finished, live counters while it runs."""
        out: dict[str, Any] = {"draining": self._draining}
        if self._drain_report is not None:
            out.update(self._drain_report)
        elif self._draining:
            out.update(
                {
                    "requeued": self._drain_requeued,
                    "completed": (
                        self.completed_requests - self._drain_base_completed
                    ),
                    "shed": self._drain_shed,
                }
            )
        return out

    # ------------------------------------------------------------------
    # KV handoff plane: disaggregated prefill/decode pools (docs/DISAGG.md)
    # ------------------------------------------------------------------

    def kv_fingerprint(self) -> dict[str, Any]:
        """The layout facts a KV handoff must agree on end to end —
        serialized into every export header and checked on import
        (mismatch → :class:`~langstream_tpu.serving.kvtransfer.
        LayoutMismatch` → HTTP 409). Pure attribute reads (POOL701)."""
        mc = self.model_config
        return {
            "model": self.config.model,
            "dtype": str(np.dtype(mc.dtype).name),
            "kv-quantize": self.config.kv_quantize or None,
            "kv-block-size": self.config.kv_block_size,
            "layers": mc.layers,
            "kv-heads": mc.kv_heads,
            "head-dim": mc.head_dim,
            "max-seq-len": mc.max_seq_len,
        }

    def adapter_fingerprint(self) -> dict[str, Any]:
        """The facts a LoRA adapter blob must agree on before its
        factors may touch the device A/B buffers — serialized into
        every T2 wire header and checked on fetch (mismatch → the blob
        is refused AND deleted, never installed). Pure attribute reads
        (POOL701)."""
        mc = self.model_config
        spec = self.config.adapter_store
        return {
            "model": self.config.model,
            "dtype": str(np.dtype(mc.dtype).name),
            "rank": spec.rank if spec is not None else 0,
            "layers": mc.layers,
            "hidden": mc.hidden,
            "heads": mc.heads,
            "kv-heads": mc.kv_heads,
            "head-dim": mc.head_dim,
        }

    def _adapter_entry_bytes(self) -> int:
        """Device bytes one resident adapter row occupies across the
        eight stacked factor buffers (all layers, model dtype)."""
        mc = self.model_config
        r = self.config.adapter_store.rank
        q_dim = mc.heads * mc.head_dim
        kv_dim = mc.kv_heads * mc.head_dim
        per_layer = (
            (mc.hidden * r + r * q_dim)        # wq_a / wq_b
            + (mc.hidden * r + r * kv_dim)     # wk_a / wk_b
            + (mc.hidden * r + r * kv_dim)     # wv_a / wv_b
            + (q_dim * r + r * mc.hidden)      # wo_a / wo_b
        )
        return mc.layers * per_layer * np.dtype(mc.dtype).itemsize

    def kv_transfer_section(self) -> dict[str, Any]:
        """The ``stats()["kvtransfer"]`` / flight-summary section:
        transfer counters + in-transit posture. Wait-free (POOL701):
        attribute reads and ``len`` only."""
        return {
            "role": self._pool_role,
            "exports": self.kv_exports_total,
            "exports_evicted": self.kv_exports_evicted,
            "imports": self.kv_imports_total,
            "import_sheds": self.kv_import_sheds,
            "export_bytes": self.kv_export_bytes,
            "import_bytes": self.kv_import_bytes,
            "pending_exports": len(self._exports),
            "pending_imports": len(self._pending_imports),
            "in_transit_bytes": self._kv_in_transit_bytes,
            # cross-replica failure domain (serving/handoff.py): chainer
            # re-offers/fallbacks and handoffs awaiting the decode
            # side's answer (their journal entries stay live)
            "retries": self.handoff_retries,
            "fallbacks": self.handoff_fallbacks,
            "unsettled_handoffs": len(self._handoff_journal),
        }

    def handoff_settled(self, request_id: str) -> None:
        """The decode side ANSWERED this handoff — a completed result or
        a terminal refusal (409/504, which the decode side recorded) —
        so the prefill-side journal entry retires. Until this call the
        entry stays live: a decode pod dying mid-handoff leaves it to
        replay as a fresh request on restart (docs/RESILIENCE.md).
        Wait-free: a dict pop + the journal's deque handoff."""
        journal_id = self._handoff_journal.pop(request_id, None)
        if journal_id is not None and self.journal is not None:
            self.journal.retire(journal_id)
            if self._m_journal_depth is not None:
                self._m_journal_depth(self.journal.depth())

    def note_handoff_retry(
        self, request_id: str, replica: str | None = None,
        attempt: int = 0, reason: str = "",
    ) -> None:
        """One chainer re-offer (serving/handoff.py): counter + flight
        event, so a retry storm is visible in the ring and engine_top's
        ``--analyze`` can flag it. Wait-free."""
        self.handoff_retries += 1
        if self._m_handoff_retries is not None:
            self._m_handoff_retries(1)
        self.flight.event(
            "handoff-retry", request=request_id, replica=replica,
            attempt=attempt, reason=str(reason)[:160],
        )

    def note_handoff_fallback(self, request_id: str, attempts: int = 0) -> None:
        """The chainer gave up on the decode pool and is importing the
        payload locally: counter + flight event (never invisible — a
        fallback means this prefill replica now pays a decode)."""
        self.handoff_fallbacks += 1
        if self._m_handoff_fallbacks is not None:
            self._m_handoff_fallbacks(1)
        self.flight.event(
            "handoff-fallback", request=request_id, attempts=attempts,
        )

    def note_breaker_open(self, open_replicas: int = 0) -> None:
        """Mirror of the router's breaker pressure: a lazily-registered
        gauge (first breaker event only — a fleet that never trips one
        keeps the pre-existing scrape surface)."""
        if self._m_breaker_open is None:
            self._m_breaker_open = self._reporter.gauge(
                "breaker_open_replicas",
                "replicas currently excluded from routing by an OPEN "
                "circuit breaker (gateway/router.py; docs/RESILIENCE.md)",
            )
        self._m_breaker_open(open_replicas)

    def note_fault_fired(self, **detail: Any) -> None:
        """Loop-side spelling of the ``fault-injected`` evidence event
        for the NETWORK seams (the chainer runs on the event loop, so
        no deque handoff is needed — cause still lands in the ring
        before the retry/fallback it triggers)."""
        self.flight.event("fault-injected", **detail)

    def take_export_entry(
        self, request_id: str, settle: bool = True
    ) -> dict[str, Any] | None:
        """Pop one export entry (payload + the stashed trace/journey
        coordinates — what the pod ``GET /kv/export/{request}`` handler
        needs to echo the trace header). Wait-free (POOL701): dict pops
        and journey-ledger appends only; the payload leaves the
        in-transit ledger here and the pickup lands as an
        ``export-taken`` journey edge (the handoff-wait/transfer split).

        ``settle`` (default True — the PULL model): the pickup is the
        last event this engine will ever see for the handoff, so the
        journal entry retires here, exactly as it did pre-chainer. The
        chainer passes ``settle=False``: it stays in the loop and
        settles on the decode side's actual answer, so a decode pod
        dying after pickup still replays from this journal."""
        if self._faults is not None:
            # http-export network fault seam (serving/faults.py): the
            # pickup "never arrives" — drop answers None (the pod maps
            # it to 404) WITHOUT popping, so a retried pickup can still
            # succeed once the fault disarms; the journal keeps the
            # entry live either way (chaos drills only)
            action = self._faults.fire("http-export")
            if action is not None:
                self._fault_fired.append(
                    {"site": "http-export", "shape": action.shape,
                     "fire": action.seq, "hang_ms": None}
                )
                if action.shape == "delay-ms":
                    # injected pickup stall (tests/chaos only; unarmed
                    # engines never reach this branch)
                    time.sleep(action.hang_ms / 1000.0)
                elif action.shape == "error":
                    raise RuntimeError(action.message)
                else:
                    return None
        entry = self._exports.pop(request_id, None)
        if entry is None:
            return None
        self._kv_in_transit_bytes -= entry["bytes"]
        if settle:
            self.handoff_settled(request_id)
        JOURNEYS.record(
            entry.get("journey"), "export-taken",
            handoff=request_id, bytes=entry["bytes"],
        )
        return entry

    def take_export(self, request_id: str) -> bytes | None:
        """Pop one serialized handoff payload (bytes-only spelling of
        :meth:`take_export_entry` — the tests' and chainers' surface)."""
        entry = self.take_export_entry(request_id)
        return None if entry is None else entry["payload"]

    async def _export_ready_slots(self, loop) -> None:
        """Prefill-pool half of the handoff: every slot whose prefill
        completed (it would join decode on a combined engine) exports
        its KV blocks + request snapshot and releases, so the slot and
        its reservation immediately serve the next prompt. Runs at the
        loop's safe point — no dispatch in flight."""
        for slot_id, slot in enumerate(self.slots):
            request = slot.request
            if request is None or slot.prefilling:
                continue
            if request.imported:
                # a local-fallback import (serving/handoff.py): this
                # request already WENT through the handoff plane and
                # every decode replica refused it — it decodes here,
                # on the combined path, and must never re-export
                continue
            if request.future.cancelled():
                # caller gave up between prefill and export: nothing to
                # hand off — free the slot + reservation. The tenant
                # post-debit still happens (same rule as _flush_emits:
                # cancelled requests' tokens burned engine capacity)
                slot.request = None
                slot.prefill_done = 0
                self._lengths[slot_id] = 0
                self._adapter_release(request)
                if self._ad_rows is not None:
                    self._ad_rows[slot_id] = 0
                self.block_mgr.release(slot_id)
                self.scheduler.on_finished(request)
                self._journey(request, "cancelled")
                continue
            if request.future.done():
                continue
            await self._export_slot(loop, slot_id, request)

    async def _export_slot(self, loop, slot_id: int, request) -> None:
        """Export one finished-prefill slot: gather its pool rows (the
        one device sync lives in kvtransfer's sanctioned ``_fetch_rows``
        stage, on the dispatch thread, timed), serialize, stash the
        payload for pickup, release the slot, and resolve the caller's
        future with the handoff ticket."""
        from langstream_tpu.serving import kvtransfer

        t_start = time.monotonic()
        rows = int(self._lengths[slot_id])
        nrb = self._read_blocks_for(max(rows, 1))
        blocks_live = self.block_mgr.blocks_needed(max(rows, 1))
        table_row = self.block_mgr.tables[slot_id].copy()

        def _run():
            gathered_k, gathered_v = kvtransfer.gather_slot(
                self.cache_k, self.cache_v, table_row, nrb
            )
            return kvtransfer._fetch_rows(gathered_k, gathered_v, rows)

        arrays, device_s = await loop.run_in_executor(self._executor, _run)
        self._export_seq += 1
        rid = f"{self.config.model}-{self._export_seq:08d}"
        now = time.monotonic()
        first = request.first_token_time or now
        admit = request.admit_time or first
        timings = {
            "queue_wait": admit - request.enqueue_time,
            "prefill": first - admit,
            "ttft": first - request.enqueue_time,
        }
        header = {
            "fingerprint": self.kv_fingerprint(),
            "request": rid,
            # trace continuity (docs/OBSERVABILITY.md "Request journey
            # plane"): the decode pool parents its kv-import/decode spans
            # under the prefill-side trace, and its journey edges land in
            # the SAME per-request ledger — one trace_id end to end
            "trace": (
                request.trace.to_header()
                if request.trace is not None
                else None
            ),
            "journey": request.journey_id,
            "prompt-digest": kvtransfer.prompt_digest(request.prompt_tokens),
            "prompt-tokens": list(request.prompt_tokens),
            "generated": list(request.generated),
            "logprobs": list(request.logprobs),
            "current-token": int(self._current[slot_id]),
            "kv-rows": rows,
            "max-tokens": request.max_tokens,
            "temperature": request.temperature,
            "top-k": request.top_k,
            "top-p": request.top_p,
            "presence-penalty": request.presence_penalty,
            "frequency-penalty": request.frequency_penalty,
            "stop": list(request.stop),
            "tenant": request.tenant,
            "priority": request.priority,
            # the end-to-end deadline rides the wire beside the trace:
            # the decode pool enforces the SAME budget the gateway
            # stamped (docs/RESILIENCE.md)
            "deadline": request.deadline,
            "timings": {k: round(v, 6) for k, v in timings.items()},
        }
        payload = kvtransfer.serialize_handoff(header, arrays)
        # release BEFORE stashing: the slot serves the next prompt now;
        # published prefix blocks stay cached (the cache holds its refs)
        slot = self.slots[slot_id]
        slot.request = None
        slot.prefilling = False
        slot.prefill_done = 0
        self._lengths[slot_id] = 0
        self._adapter_release(request)
        if self._ad_rows is not None:
            self._ad_rows[slot_id] = 0
        self.block_mgr.release(slot_id)
        if not request.warmup:
            self._exports[rid] = {
                "payload": payload,
                "bytes": len(payload),
                "blocks": blocks_live,
                "m_s": now,
                # stashed so the pod's /kv/export pickup can echo the
                # trace header and close the journey's handoff-wait edge
                # without re-parsing the payload header
                "trace": header["trace"],
                "journey": request.journey_id,
                # the chainer derives every offer's socket timeout from
                # this (serving/handoff.py socket_timeout_s)
                "deadline": request.deadline,
            }
            self._kv_in_transit_bytes += len(payload)
            while len(self._exports) > self._export_cap:
                evicted_rid, evicted = self._exports.popitem(last=False)
                self._kv_in_transit_bytes -= evicted["bytes"]
                # an evicted export is a LOST handoff (its blocks were
                # released at export time): the decode pool's pickup
                # will 404 and the caller must re-prefill — loud by
                # contract, the handoff cost is never invisible
                self.kv_exports_evicted += 1
                self.flight.event(
                    "kv-export-dropped",
                    request=evicted_rid,
                    bytes=evicted["bytes"],
                    age_s=round(now - evicted["m_s"], 3),
                    cap=self._export_cap,
                )
            self.kv_exports_total += 1
            self.kv_export_bytes += len(payload)
            self.request_timings.append(
                {**{k: round(v, 6) for k, v in timings.items()},
                 "decode": 0.0,
                 "tokens": float(len(request.generated)),
                 "handoff": 1.0}
            )
            # exemplar: traced requests stamp their journey id on the
            # TTFT bucket (None for untraced — the scrape stays pinned)
            self._m_ttft_hist(
                timings["ttft"],
                request.journey_id if request.trace is not None else None,
            )
            self._m_queue_wait_hist(timings["queue_wait"])
            self._slo_record("availability", True)
            self._slo_record_latency("ttft", timings["ttft"])
            self._slo_record_latency("queue-wait", timings["queue_wait"])
        if self._m_kv_export_hist is not None:
            self._m_kv_export_hist(
                time.monotonic() - t_start,
                request.journey_id if request.trace is not None else None,
            )
        if self._m_kv_export_bytes is not None and not request.warmup:
            self._m_kv_export_bytes(len(payload))
        self.flight.event(
            "kv-export",
            request=rid,
            bytes=len(payload),
            blocks=blocks_live,
            rows=rows,
            ms=round((time.monotonic() - t_start) * 1000.0, 3),
            device_ms=round(device_s * 1000.0, 3),
            warmup=request.warmup,
        )
        self._journey(
            request, "export", handoff=rid, bytes=len(payload), rows=rows,
            ms=round((time.monotonic() - t_start) * 1000.0, 3),
            device_ms=round(device_s * 1000.0, 3),
            model=self.config.model, role=self._pool_role,
        )
        if request.trace is not None and not request.warmup:
            # a handoff request never reaches _flush_emits' finish path,
            # so its prefill-side phase spans materialize HERE — the
            # trace the decode pool's kv-import/decode spans join
            svc = f"engine:{self.config.model}"
            record_span("engine.queue", svc, request.trace,
                        request.enqueue_time, admit)
            record_span("engine.prefill", svc, request.trace, admit, first,
                        attributes={
                            "prompt-tokens": len(request.prompt_tokens)
                        })
            record_span("engine.kv-export", svc, request.trace, t_start,
                        time.monotonic(),
                        attributes={"bytes": len(payload), "rows": rows})
        self.scheduler.on_finished(request)
        self.completed_requests += 1
        # the handoff is NOT this request's end of life for the journal:
        # the decode side can still die before completion, and retiring
        # here made that loss invisible (the PR 15 satellite fix). The
        # entry stays live, keyed under the handoff id, until the
        # chainer confirms the decode side ANSWERED (handoff_settled) —
        # a crash anywhere in between replays the request as fresh work
        # from the prefill-side journal. Bounded: overflow drops the
        # MAPPING loudly (replay-over-loss — the entry stays live and
        # the journal's own bound is the final backstop).
        if self.journal is not None and not request.warmup:
            self._handoff_journal[rid] = request.journey_id
            while len(self._handoff_journal) > 4 * self._export_cap:
                old_rid, _old_jid = self._handoff_journal.popitem(last=False)
                self.flight.event("handoff-settle-evict", request=old_rid)
        if not request.future.done():
            request.future.set_result(
                {
                    "handoff": rid,
                    "tokens": list(request.generated),
                    "text": self.tokenizer.decode(request.generated),
                    "logprobs": list(request.logprobs),
                    "num_prompt_tokens": len(request.prompt_tokens),
                    "num_completion_tokens": len(request.generated),
                    "ttft": timings["ttft"],
                    "queue_wait": timings["queue_wait"],
                    "prefill": timings["prefill"],
                    "finish_reason": "handoff",
                }
            )

    async def import_handoff(
        self,
        payload: bytes,
        header: dict[str, Any] | None = None,
        trace_header: str | None = None,
        deadline: float | None = None,
        local_fallback: bool = False,
    ) -> dict[str, Any]:
        """Decode-pool half of the handoff: admit a request whose KV
        state arrived over the wire — blocks allocate through the
        BlockManager, rows scatter back via ``write_rows``, and the
        request joins the decode batch directly (prefill skipped; the
        ``request_timings`` entry carries ``imported`` so the skip is
        assertable). The wire header's ``trace``/``journey`` (falling
        back to ``trace_header``, the pod's ``langstream-trace`` request
        header) join this engine's spans and journey edges to the
        prefill-side trace — one trace_id end to end. Raises
        :class:`~langstream_tpu.serving.kvtransfer.LayoutMismatch` on a
        wire/fingerprint mismatch (pod → 409) and :class:`RateLimited`
        when the pool cannot take it right now (pod → 503 +
        Retry-After; the router retries the next decode replica)."""
        from langstream_tpu.serving import kvtransfer

        if self._stop:
            raise RuntimeError(
                "serving engine is stopped (closed or lockstep group broken)"
            )
        if self._pool_role == "prefill" and not local_fallback:
            # local_fallback is the chainer's escape hatch (serving/
            # handoff.py): when every decode replica is dead/held/
            # refusing, the prefill engine imports its OWN payload and
            # the request rejoins the combined decode path — the
            # serialized snapshot is the complete state, so the result
            # is byte-identical to the disaggregated path
            raise kvtransfer.LayoutMismatch(
                "prefill-role engine does not accept KV imports"
            )
        header, arrays = kvtransfer.deserialize_handoff(payload, header)
        kvtransfer.check_fingerprint(
            self.kv_fingerprint(), header.get("fingerprint") or {}
        )
        if self._draining:
            raise RateLimited(
                "draining", 1.0,
                "engine is draining; retry another decode replica",
            )
        prompt = [int(t) for t in header.get("prompt-tokens") or []]
        generated = [int(t) for t in header.get("generated") or []]
        rows = int(header.get("kv-rows") or 0)
        max_tokens = int(header.get("max-tokens") or 0)
        if rows < 1 or rows >= self.model_config.max_seq_len:
            raise kvtransfer.LayoutMismatch(
                f"handoff kv-rows {rows} outside (0, "
                f"{self.model_config.max_seq_len})"
            )
        for name, arr in arrays.items():
            if arr.shape[0] != self.model_config.layers or arr.shape[1] < rows:
                raise kvtransfer.LayoutMismatch(
                    f"handoff array {name!r} shape {arr.shape} does not "
                    f"cover {self.model_config.layers} layers x {rows} rows"
                )
        if not self.block_mgr.fits_ever(len(prompt) + max_tokens + 1):
            raise ValueError(
                f"imported request needs {len(prompt) + max_tokens + 1} "
                f"tokens of KV, more than this pool can ever hold"
            )
        # trace continuity: the wire header's context first (the prefill
        # engine stamped it), then the pod HTTP header (a chainer that
        # forwarded langstream-trace without a trace-aware payload)
        trace = kvtransfer.trace_context(header)
        if trace is None:
            trace = TraceContext.parse(trace_header)
        request = _Request(
            prompt_tokens=prompt,
            max_tokens=max_tokens,
            temperature=float(header.get("temperature") or 0.0),
            top_k=int(header.get("top-k") or 0),
            top_p=float(header.get("top-p") or 1.0),
            on_token=None,
            future=asyncio.get_running_loop().create_future(),
            loop=asyncio.get_running_loop(),
            enqueue_time=time.monotonic(),
            presence_penalty=float(header.get("presence-penalty") or 0.0),
            frequency_penalty=float(header.get("frequency-penalty") or 0.0),
            generated=generated,
            logprobs=[float(x) for x in header.get("logprobs") or []],
            stop=_normalize_stop(header.get("stop")),
            tenant=str(header.get("tenant") or ""),
            priority=normalize_priority(header.get("priority")),
            imported=True,
            trace=trace,
            # deadline continuity: the wire header's stamp (the prefill
            # side carried the ORIGINAL budget) wins over the pod HTTP
            # header's copy — both are the same epoch clock, and
            # parse_deadline only ever returns None or a positive stamp
            deadline=(
                parse_deadline(header.get("deadline"))
                or parse_deadline(deadline)
            ),
        )
        request.import_base_tokens = len(generated)
        request.journey_id = kvtransfer.journey_id(header) or (
            trace.trace_id if trace is not None else fresh_trace_id()
        )
        self._journey(
            request, "import-received", bytes=len(payload),
            handoff=header.get("request"),
            model=self.config.model, role=self._pool_role,
        )
        if (
            request.deadline is not None
            and remaining_s(request.deadline) <= 0.0
        ):
            # expired in transit: refuse 504-shaped BEFORE queueing the
            # scatter (the pod maps this to HTTP 504; an overrun this
            # early must never burn blocks/device work). After the
            # journey id is bound, so the refusal lands as a terminal
            # edge in the request's ledger instead of vanishing.
            raise self._note_deadline_shed(request, "kv-import", 0.0)
        self._pending_imports.append(
            (header, arrays, request, len(payload))
        )
        self._ensure_loop()
        self._wake.set()
        return await request.future

    @staticmethod
    def _resource_exhausted(error: BaseException) -> bool:
        """True for a device allocator failure or the BlockManager's
        pool-exhaustion RuntimeError — the refusals ROADMAP item 5 wants
        adapted to, not died from. Covers every jaxlib allocator
        spelling observed across backends/versions (the canonical
        ``RESOURCE_EXHAUSTED:`` status prefix, the BFC allocator's
        ``Out of memory while trying to allocate``, the PJRT client's
        ``Failed to allocate request``, and TFRT's ``Allocation ...
        exceeds`` phrasing) — a spelling this misses dies instead of
        adapting, so each one is pinned by a unit test."""
        text = f"{type(error).__name__}: {error}"
        return bool(_RESOURCE_EXHAUSTED_RE.search(text))

    def _fault(self, site: str) -> None:
        """Fault-injection seam check (serving/faults.py — tests/chaos
        drills only). Production engines carry ``_faults = None``, so
        this is ONE attribute test on the hot path. A fired fault is
        stashed on the ``_fault_fired`` handoff deque (the seams span
        the loop AND the dispatch thread; the flight ring's counters are
        loop-side state, so emission happens at the loop's safe point —
        chaos assertions read the emitted ``fault-injected`` events,
        never guess), then the action runs: a synthetic
        RESOURCE_EXHAUSTED raise, or a stall of whichever thread hit
        the seam."""
        faults = self._faults
        if faults is None:
            return
        action = faults.fire(site)
        if action is None:
            return
        self._fault_fired.append(
            {
                "site": site,
                "shape": action.shape,
                "fire": action.seq,
                "hang_ms": (
                    action.hang_ms if action.shape == "hang" else None
                ),
            }
        )
        if action.shape == "hang":
            # the r03 shape: the dispatch goes quiet. The watchdog
            # heartbeat stops while work stays pending, so /healthz
            # must flip WEDGED until the stall resolves.
            time.sleep(action.hang_ms / 1000.0)
            return
        raise InjectedFault(site, action.message)

    def _drain_fault_events(self) -> None:
        """Emit stashed ``fault-injected`` events at the loop's safe
        point (and before any ``pool-shrink`` evidence, so the ring
        reads cause-then-effect)."""
        while self._fault_fired:
            self.flight.event("fault-injected", **self._fault_fired.popleft())

    def _note_deadline_shed(
        self, request, where: str, left: float, estimate: float = 0.0
    ) -> DeadlineExceeded:
        """Record one deadline refusal (counter + lazy metric + a
        ``deadline-exceeded`` flight event with the budget evidence) and
        build the 504-shaped error the caller raises/sets. The metric
        registers on FIRST use so a deadline-less engine's scrape
        surface stays byte-identical (the default-config pin)."""
        self.deadline_sheds += 1
        if self._m_deadline_shed is None:
            self._m_deadline_shed = self._reporter.counter(
                "deadline_shed_total",
                "requests refused because the remaining langstream-"
                "deadline budget could not cover the admission estimate "
                "(504-shaped; docs/RESILIENCE.md)",
            )
        self._m_deadline_shed(1)
        self.flight.event(
            "deadline-exceeded",
            where=where,
            remaining_s=round(left, 6),
            estimate_s=round(estimate, 6),
            tenant=request.tenant,
            priority=request.priority,
        )
        self._journey(
            request, "deadline-exceeded", where=where,
            remaining_s=round(left, 6),
        )
        if not request.warmup:
            self._slo_record("shed-rate", False)
        return DeadlineExceeded(
            f"deadline exceeded at {where}: {left:.3f}s of budget left, "
            f"admission estimate {estimate:.3f}s",
            overrun_s=max(0.0, estimate - left),
        )

    def _admit_estimate_s(self) -> float:
        """The admission-time cost estimate a deadline must still cover:
        the median recent prefill time (enqueue-side work the engine is
        ABOUT to spend on the device). No history → 0.0, so a fresh
        engine only sheds already-expired budgets — the estimate
        tightens as evidence accumulates, never guesses ahead of it."""
        vals = sorted(
            t.get("prefill", 0.0)
            for t in list(self.request_timings)[-32:]
            if not t.get("imported")
        )
        return vals[len(vals) // 2] if vals else 0.0

    def _shed_import(self, request, reason: str, detail: str) -> None:
        """Refuse one pending import explicitly: RateLimited with a retry
        hint, so the pod handler answers 503 + Retry-After and the router
        retries the next decode replica (never a silent loss)."""
        self.kv_import_sheds += 1
        self.flight.event(
            "shed", reason=reason, tenant=request.tenant,
            priority=request.priority, retry_after_s=1.0, imported=True,
        )
        self._journey(request, "shed", reason=reason, imported=True)
        if not request.future.done():
            request.future.set_exception(RateLimited(reason, 1.0, detail))

    async def _apply_imports(self, loop) -> None:
        """Admit every queued KV import at the loop's safe point. Each
        import needs a free slot and a worst-case block reservation —
        exactly admission's contract; refusals are explicit 503-shaped
        sheds (the decode pool is saturated and the router should spread
        the handoff), and a RESOURCE_EXHAUSTED during block allocation
        sheds instead of failing the request."""
        from langstream_tpu.serving import kvtransfer

        while self._pending_imports:
            header, arrays, request, nbytes = self._pending_imports.popleft()
            if request.future.done():
                continue  # caller gave up while queued
            if request.deadline is not None:
                # the deadline rode the wire header: an import whose
                # budget died in transit is refused 504-shaped before
                # any block allocation or scatter (the pod handler maps
                # DeadlineExceeded to HTTP 504; the chainer treats it
                # as terminal — no sibling replica has more budget)
                left = remaining_s(request.deadline)
                if left <= 0.0:
                    err = self._note_deadline_shed(
                        request, "kv-import", left
                    )
                    if not request.future.done():
                        request.future.set_exception(err)
                    continue
            if self._draining:
                self._shed_import(
                    request, "draining",
                    "engine is draining; retry another decode replica",
                )
                continue
            free = next(
                (i for i, s in enumerate(self.slots) if s.free), None
            )
            total = len(request.prompt_tokens) + request.max_tokens + 1
            if free is None:
                self._shed_import(
                    request, "no-free-slot",
                    "decode pool has no free slot; retry another replica",
                )
                continue
            if not self.block_mgr.can_admit(total):
                self._shed_import(
                    request, "kv-import-capacity",
                    "decode pool cannot reserve the request's worst-case "
                    "KV blocks; retry another replica",
                )
                continue
            rows = int(header["kv-rows"])
            t_start = time.monotonic()
            try:
                self.block_mgr.admit(free, total)
                self.block_mgr.ensure_capacity(free, rows)
            except RuntimeError as e:
                # the first slice of the RESOURCE_EXHAUSTED adaptation
                # story (ROADMAP item 5): allocator refusal is a shed,
                # never a request failure
                self.block_mgr.release(free)
                if self._resource_exhausted(e):
                    self._shed_import(
                        request, "kv-import-capacity",
                        f"block allocation failed ({e}); retry another "
                        f"replica",
                    )
                    continue
                raise
            table_row = self.block_mgr.tables[free].copy()
            padded = _bucket(rows, hi=self.model_config.max_seq_len)

            def _run(arrays=arrays, table_row=table_row, rows=rows,
                     padded=padded):
                self._fault("scatter")
                out_k, out_v = kvtransfer.scatter_slot(
                    self.cache_k, self.cache_v, arrays, table_row, rows,
                    padded, kernel=self.pool_commit_kernel,
                )
                # donated pools re-bound on the dispatch thread — the
                # same side every dispatch closure reads them (RACE801)
                self.cache_k, self.cache_v = out_k, out_v
                t_dev = time.monotonic()
                # graftcheck: disable=JAX104 the one per-import sync, off-loop and timed
                jax.block_until_ready((out_k, out_v))
                return time.monotonic() - t_dev

            try:
                device_s = await loop.run_in_executor(self._executor, _run)
            except Exception as e:
                self.block_mgr.release(free)
                if self._resource_exhausted(e):
                    self._shed_import(
                        request, "kv-import-oom",
                        f"device allocation failed mid-scatter ({e}); "
                        f"retry another replica",
                    )
                    continue
                raise
            slot = self.slots[free]
            slot.request = request
            slot.prefilling = False
            slot.prefill_done = 0
            self._lengths[free] = rows
            self._current[free] = int(header["current-token"])
            self._temps[free] = request.temperature
            self._topks[free] = request.top_k
            self._topps[free] = request.top_p
            self._pres[free] = request.presence_penalty
            self._freq[free] = request.frequency_penalty
            now = time.monotonic()
            # prefill is SKIPPED: admit == first-token boundary (the
            # handoff's first token was produced on the prefill pool)
            request.admit_time = now
            request.first_token_time = now
            self.kv_imports_total += 1
            self.kv_import_bytes += nbytes
            if self._m_kv_import_hist is not None:
                self._m_kv_import_hist(
                    time.monotonic() - t_start,
                    request.journey_id
                    if request.trace is not None
                    else None,
                )
            if self._m_kv_import_bytes is not None:
                self._m_kv_import_bytes(nbytes)
            self.flight.event(
                "kv-import",
                request=header.get("request"),
                digest=header.get("prompt-digest"),
                bytes=nbytes,
                blocks=self.block_mgr.blocks_needed(max(rows, 1)),
                rows=rows,
                ms=round((time.monotonic() - t_start) * 1000.0, 3),
                device_ms=round(device_s * 1000.0, 3),
            )
            self._journey(
                request, "import", bytes=nbytes, rows=rows,
                ms=round((time.monotonic() - t_start) * 1000.0, 3),
                device_ms=round(device_s * 1000.0, 3),
                model=self.config.model, role=self._pool_role,
            )
            if request.trace is not None:
                # the decode-pool spans join the prefill-side trace: the
                # import (block admit + scatter) as its own child, the
                # decode phase via the usual completion-time spans
                record_span(
                    "engine.kv-import", f"engine:{self.config.model}",
                    request.trace, t_start, now,
                    attributes={"bytes": nbytes, "rows": rows},
                )

    # ------------------------------------------------------------------
    # engine loop
    # ------------------------------------------------------------------

    def _ensure_loop(self) -> None:
        if self._loop_task is None or self._loop_task.done():
            self._loop_task = asyncio.ensure_future(self._run_loop())

    def _split_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def _has_prefilling(self) -> bool:
        return any(s.prefilling for s in self.slots)

    async def _run_loop(self) -> None:
        loop = asyncio.get_running_loop()
        # reset the flight timeline: the loop starts lazily on the first
        # generate(), and the construction→first-request gap (an hour for
        # an idle deploy) must not be billed to the first sample as host
        # time — from here on the loop itself records every gap
        self.flight.mark()
        # fresh heartbeat at loop start: the wedge window measures from
        # here, not from engine construction
        self.watchdog.beat(self.scheduler.qsize())
        if self._journal_replay_pending:
            # crash-requeue (docs/RESILIENCE.md): the previous process
            # died with accepted work unfinished — replay it through the
            # QoS front-of-class resume path before any new admission
            self._replay_journal(loop)
        while not self._stop:
            try:
                if self._fault_fired:
                    # chaos-drill evidence first: injected faults land in
                    # the ring before whatever they caused this pass
                    self._drain_fault_events()
                if self._shrink_recover_at is not None:
                    # pool-shrink recovery probe: one quiet window with
                    # no further allocator failures restores one shrink
                    # quantum (wait-free check; docs/RESILIENCE.md)
                    self._shrink_step()
                if self.prefix_store is not None:
                    # tier bookkeeping first: hydrations that landed
                    # requeue at class front, so the admission passes
                    # below see them immediately (docs/PREFIX.md)
                    self._prefix_tier_step()
                if self.adapter_store is not None:
                    # adapter hydrations settle at the same safe point
                    # (requeue at class front or cold-refuse loudly —
                    # docs/ADAPTERS.md)
                    self._adapter_tier_step()
                if self._pending_imports:
                    # KV handoff imports land at the loop's safe point,
                    # exactly like admission: a free slot + a worst-case
                    # block reservation, then the wire rows scatter in
                    # and the request joins decode with NO prefill
                    # (docs/DISAGG.md)
                    await self._apply_imports(loop)
                if not self.scheduler.empty():
                    await self._admit(loop)
                # a pipelined burst may have left a decode chunk in
                # flight: drained only AFTER admission so the prefill
                # above dispatched under its device shadow, and BEFORE
                # preemption so a victim's slot state is settled when the
                # snapshot is taken
                await self._drain_pending(loop)
                if self._draining and not self._drain_pass_done:
                    # drain-before-terminate: one preempt-and-requeue
                    # sweep at the safe point (pending chunk settled);
                    # the requeued work re-admits below and finishes
                    # under drain()'s grace budget
                    self._drain_pass_done = True
                    self._drain_requeued += self._drain_preempt_pass()
                if not self.scheduler.empty():
                    # slots the drained chunk just freed are admission
                    # opportunities NOW, not one burst later
                    await self._admit(loop)
                    # QoS preemption: admission stalled on KV pressure
                    # with a higher-priority request waiting → preempt
                    # the policy-chosen victim (its blocks free NOW) and
                    # re-run admission so the waiter lands this pass
                    if self._maybe_preempt():
                        await self._admit(loop)
                if self.prefix_store is not None:
                    # T0 byte-budget demotions ride the same safe point
                    # (the pending chunk above is settled, so the gather
                    # reads stable pool contents)
                    await self._demote_prefix_blocks(loop)
                if self._has_prefilling():
                    # one bounded chunk per loop pass: long prefills make
                    # progress without stalling the decode bursts below
                    await self._advance_prefills(loop)
                if self._pool_role == "prefill":
                    # disaggregated prefill pool: every slot whose
                    # prefill just finished exports its KV blocks and
                    # releases instead of decoding — the decode pool
                    # picks the payload up over the pod HTTP plane
                    await self._export_ready_slots(loop)
                active = [
                    i
                    for i, s in enumerate(self.slots)
                    if not s.free and not s.prefilling
                ]
                self._m_active(len(active))
                self._m_queued(self.scheduler.qsize())
                if not active:
                    # nobody waits for a token (the admitted all ended at
                    # their first): the round is over without a burst
                    self._prefill_round_s, self._admit_cut = 0.0, False
                    if self.scheduler.empty() and not self._has_prefilling():
                        self._wake.clear()
                        # a stashed hydration resolves on the hydrator
                        # thread, and a T0 cache over its byte budget
                        # has demotions to drain: poll tightly while
                        # either is pending so TTFT pays milliseconds
                        # (hydration) and spilled blocks reach the
                        # durable tier promptly instead of one bounded
                        # batch per idle second
                        idle_s = (
                            0.02
                            if self._prefix_hydrating
                            or self._adapter_hydrating
                            or self._prefix_demote_pending()
                            else 1.0
                        )
                        with self.flight.span("ls.idle"):
                            try:
                                await asyncio.wait_for(
                                    self._wake.wait(), timeout=idle_s
                                )
                            except asyncio.TimeoutError:
                                pass
                        # the whole gap was engine idle time: record it so
                        # the flight timeline stays contiguous and the
                        # rollup's stall component is exact
                        self._flight_stall("queue-empty")
                    continue
                if (
                    self.config.speculative_drafts > 0
                    # measured-uplift auto-disable parks the engine on the
                    # plain pipelined loop until the retry window elapses
                    and not self._spec_auto_disabled
                    # greedy bursts use argmax acceptance; sampled bursts
                    # use rejection sampling against the filtered target
                    # distribution (distribution-exact). Penalties alone
                    # stay on plain decode: they change the distribution
                    # per EMITTED token and the verify step has no counts.
                    and not (
                        (self._pres[active] != 0).any()
                        or (self._freq[active] != 0).any()
                    )
                ):
                    self._prefill_round_s, self._admit_cut = 0.0, False
                    await self._speculative_burst(loop, active)
                else:
                    # a round ends here: what admission was cut short for
                    # is one chunk, fetched before the next prefill
                    lone, self._admit_cut = self._admit_cut, False
                    self._prefill_round_s = 0.0
                    await self._decode_burst(loop, active, lone_chunk=lone)
            except Exception as e:  # device/runtime error: fail in-flight work,
                # free the slots, keep serving (callers see the exception)
                if (
                    self._lockstep is None
                    and self._resource_exhausted(e)
                    and self._maybe_pool_shrink(e)
                ):
                    # degrade-don't-die (docs/RESILIENCE.md): device
                    # memory pressure is a load signal. The budget
                    # shrank, the victims requeued front-of-class, and
                    # the loop keeps serving — nothing was failed.
                    continue
                log.exception("serving engine step failed")
                from langstream_tpu.serving.lockstep import LockstepBroken

                if self._lockstep is not None and not isinstance(e, LockstepBroken):
                    # leading a multi-host group: ANY step failure is
                    # group-fatal — followers may have replayed collectives
                    # this process aborted mid-step (e.g. the coordination
                    # service poisoned a pending collective after a member
                    # died), so surviving state is unknowable. Wrap so
                    # callers see one loud type either way.
                    e = LockstepBroken(
                        f"multi-host step failed: {type(e).__name__}: {e}"
                    )
                self._fail_inflight(e)
                if isinstance(e, LockstepBroken):
                    # a lost follower is unrecoverable for this process
                    # group — stop serving so the slice restarts as a unit
                    log.error("lockstep group broken; engine stops serving")
                    self.flight.event(
                        "lockstep-divergence", error=str(e)[:200]
                    )
                    self._stop = True
        if self._pending_chunk is not None:
            # a stop that lands between a pipelined burst and the next
            # loop pass leaves one dispatched chunk in flight: drain it so
            # the dispatch/fetch ledger closes 1:1 (the one-fetch-per-
            # chunk canary) and the flight timeline stays contiguous —
            # finished slots' tokens are identity-filtered as always
            await self._drain_pending(loop)

    def _fail_inflight(self, error: Exception) -> None:
        self.flight.event(
            "preempt",
            error=f"{type(error).__name__}: {error}"[:200],
            inflight=sum(1 for s in self.slots if not s.free),
        )
        # a pending pipelined chunk belongs to the failed dispatch stream:
        # drop it (every slot below is failed + released uniformly anyway)
        self._pending_chunk = None
        self._defer_release = False
        self._deferred_releases.clear()
        # stale inline-adaptation counters must not leak into a later,
        # unrelated shrink pass's evidence
        self._shrink_inline_preempted = 0
        self._shrink_inline_shed = 0
        error_text = f"{type(error).__name__}: {error}"[:160]
        for slot_id, slot in enumerate(self.slots):
            request = slot.request
            if request is not None:
                if not request.future.done():
                    request.future.set_exception(error)
                    self._journey(request, "fail", error=error_text)
                    if not request.warmup:
                        self._slo_record("availability", False)
                # an explicitly failed request was ANSWERED — retire its
                # journal entry so a restart never replays served errors
                self._journal_retire(request)
                self._adapter_release(request)
            slot.request = None
            slot.prefilling = False
            slot.prefill_done = 0
            self.block_mgr.release(slot_id)
        self._lengths[:] = 0
        if self._ad_rows is not None:
            self._ad_rows[:] = 0
        for request in self.scheduler.drain():
            if not request.future.done():
                request.future.set_exception(error)
                self._journey(request, "fail", error=error_text)
                if not request.warmup:
                    self._slo_record("availability", False)
            self._journal_retire(request)
        for pending in list(self._pending_imports):
            request = pending[2]
            if not request.future.done():
                request.future.set_exception(error)
                self._journey(request, "fail", error=error_text)
        self._pending_imports.clear()
        for stashed in self._prefix_hydrating:
            request = stashed[0]
            if not request.future.done():
                request.future.set_exception(error)
                self._journey(request, "fail", error=error_text)
                if not request.warmup:
                    self._slo_record("availability", False)
            self._journal_retire(request)
        self._prefix_hydrating.clear()
        for stashed in self._adapter_hydrating:
            request = stashed[0]
            if not request.future.done():
                request.future.set_exception(error)
                self._journey(request, "fail", error=error_text)
                if not request.warmup:
                    self._slo_record("availability", False)
            self._journal_retire(request)
        self._adapter_hydrating.clear()
        self._pending_emits.clear()
        self._finished_requests.clear()

    def _journey(self, request: "_Request", kind: str, **detail: Any) -> None:
        """Append one lifecycle edge to the request's journey ledger
        (serving/journey.py). Wait-free appends on the dispatch path by
        OBS506's contract; warmup probes carry no journey id and record
        nothing."""
        if request.journey_id is not None:
            JOURNEYS.record(request.journey_id, kind, **detail)

    def _maybe_preempt(self) -> bool:
        """Preemptive load shedding under KV pressure: when admission is
        stalled on ``no-kv-blocks`` and the scheduler's cost model names
        a running victim (strictly lower class than the stalled head,
        preemptions left, more deadline slack than the waiter), preempt
        it so the waiter's blocks free immediately. Returns True when a
        slot was preempted (the caller re-runs admission). Runs at the
        loop's safe point — no dispatch is in flight."""
        if not self._qos_enabled:
            return False
        if self._admission_stall() != "no-kv-blocks":
            return False
        head = self.scheduler.peek()
        if head is None:
            return False
        running = [
            (i, s.request)
            for i, s in enumerate(self.slots)
            if s.request is not None and not s.prefilling
        ]
        victim = self.scheduler.preempt_candidate(head, running)
        if victim is None:
            return False
        self._preempt_slot(victim)
        return True

    def _preempt_slot(self, slot_id: int, reason: str = "no-kv-blocks") -> None:
        """Preempt one running request: its generated tokens + sampling
        params ARE the snapshot (greedy resume re-prefills
        ``context_tokens`` and continues bit-identically — see
        ``_Request.context_tokens``). Free the slot and its worst-case
        block reservation, then requeue at the front of its class so
        resume latency is bounded by the pressure, not the backlog.
        ``reason`` labels the flight event: ``no-kv-blocks`` (the QoS
        pressure path) or ``drain`` (drain-before-terminate)."""
        slot = self.slots[slot_id]
        request = slot.request
        now = time.monotonic()
        slot.request = None
        slot.prefilling = False
        slot.prefill_done = 0
        self._lengths[slot_id] = 0
        # drop the adapter pin across the preemption (the slot frees and
        # its row may evict); re-admission re-resolves — and may re-
        # hydrate, so the one-shot attempt flag resets too
        self._adapter_release(request)
        request.adapter_hydrate_attempted = False
        if self._ad_rows is not None:
            self._ad_rows[slot_id] = 0
        self.block_mgr.release(slot_id)
        request.preemptions += 1
        request.preempt_time = now
        self.scheduler.note_preempted(request)
        self.scheduler.requeue_front(request)
        if self._m_preempted is not None:
            self._m_preempted(1)
        if self._m_preempt_hist is not None and request.admit_time is not None:
            self._m_preempt_hist(now - request.admit_time)
        self.flight.event(
            "preempt",
            reason=reason,
            priority=request.priority,
            tenant=request.tenant,
            generated=len(request.generated),
        )
        self._journey(
            request, "preempt", reason=reason,
            generated=len(request.generated),
        )
        if request.trace is not None:
            record_span(
                "engine.preempt", f"engine:{self.config.model}",
                request.trace, now, now,
                attributes={"generated": len(request.generated)},
            )

    def _note_resume(self, request: "_Request") -> None:
        """A preempted request was just re-admitted: close the resume
        accounting (histogram + flight/trace events)."""
        if request.preempt_time is None:
            return
        now = time.monotonic()
        waited = now - request.preempt_time
        if self._m_resume_hist is not None:
            self._m_resume_hist(waited)
        self.flight.event(
            "resume",
            priority=request.priority,
            tenant=request.tenant,
            generated=len(request.generated),
            waited_ms=round(waited * 1000.0, 3),
        )
        self._journey(
            request, "resume", waited_ms=round(waited * 1000.0, 3),
            generated=len(request.generated),
        )
        if request.trace is not None:
            record_span(
                "engine.resume", f"engine:{self.config.model}",
                request.trace, request.preempt_time, now,
                attributes={"generated": len(request.generated)},
            )
        request.preempt_time = None

    # ------------------------------------------------------------------
    # device-survival plane: adaptive pool-shrink + crash-requeue
    # (docs/RESILIENCE.md)
    # ------------------------------------------------------------------

    def _shed_stranded(self, slot_id: int, error: Exception) -> None:
        """Shed one stranded (never-prefilled) request whose dispatch
        keeps failing past the shrink retry cap: the device demonstrably
        cannot serve it right now, so the answer is an explicit
        ``RateLimited`` + Retry-After — the gateway/router resends to a
        replica with memory — never an unbounded admit→OOM→requeue
        livelock and never a silent drop."""
        slot = self.slots[slot_id]
        request = slot.request
        slot.request = None
        slot.prefilling = False
        slot.prefill_done = 0
        self._lengths[slot_id] = 0
        self._adapter_release(request)
        if self._ad_rows is not None:
            self._ad_rows[slot_id] = 0
        self.block_mgr.release(slot_id)
        self.flight.event(
            "shed", reason="device-oom", tenant=request.tenant,
            priority=request.priority, retry_after_s=2.0,
            retries=request.preemptions,
        )
        self._journey(
            request, "shed", reason="device-oom",
            retries=request.preemptions,
        )
        if self._m_shed is not None:
            self._m_shed(1)
        if not request.warmup:
            self._slo_record("availability", False)
        self._journal_retire(request)
        if not request.future.done():
            request.future.set_exception(
                RateLimited(
                    "device-oom", 2.0,
                    f"device memory pressure persisted across "
                    f"{request.preemptions} adaptation retries "
                    f"({type(error).__name__}: {error}); retry another "
                    f"replica",
                )
            )

    def _shrink_victim(self) -> int | None:
        """The next preemption victim under device memory pressure: the
        occupied slot in the LOWEST priority class, breaking ties on
        least generated progress (cheapest byte-identical resume).
        Prefilling slots are eligible — their worst-case reservations
        are exactly the bytes the shrink needs back."""
        best = None
        best_key = None
        for slot_id, slot in enumerate(self.slots):
            request = slot.request
            if request is None:
                continue
            key = (
                -priority_rank(request.priority),  # lowest class first
                len(request.generated),            # cheapest redo
            )
            if best_key is None or key < best_key:
                best, best_key = slot_id, key
        return best

    def _maybe_pool_shrink(self, error: Exception) -> bool:
        """Adapt to a device allocator failure instead of dying: withhold
        one shrink quantum from the KV admission budget, preempt the
        lowest-class victims until the surviving reservations fit it
        (requeued FRONT-of-class — resume is the PR 4 byte-identical
        path), and arm the recovery probe. Runs on the loop thread from
        the loop's exception edge — no dispatch is in flight (the failed
        one already raised; an abandoned pipelined chunk re-derives on
        the next dispatch from unchanged host state, greedy-identically).
        Returns False when nothing could be adapted (budget at its floor
        AND nothing to preempt) — the caller falls through to the loud
        ``_fail_inflight`` path, never a silent retry loop."""
        bm = self.block_mgr
        if bm is None:
            return False
        # cause before effect in the event ring: a fault injected on the
        # dispatch thread emits here, ahead of its pool-shrink evidence
        self._drain_fault_events()
        quantum = max(
            1, int(bm.configured_blocks * self.config.shrink_fraction)
        )
        reduced = bm.reduce_budget(quantum)
        reserved_before = bm.reserved_blocks
        # adaptation a catch site already performed inline this pass
        preempted = self._shrink_inline_preempted
        shed = self._shrink_inline_shed
        self._shrink_inline_preempted = 0
        self._shrink_inline_shed = 0
        # FIRST: sweep slots whose monolithic prefill never completed —
        # the failed dispatch may have been their prefill, so no KV was
        # ever written (_lengths still 0, prefilling False). Left in
        # place they would join the next decode burst and emit garbage
        # from unwritten cache rows; requeued they re-prefill correctly.
        # (Chunked prefills are excluded by prefilling=True and resume
        # from their committed prefill_done either way.) Retries are
        # BOUNDED: a request whose dispatch keeps failing even as the
        # budget hits its floor would otherwise livelock the loop in an
        # admit→OOM→requeue cycle forever — past the cap it is shed
        # LOUDLY (RateLimited + Retry-After: another replica may have
        # the memory this one demonstrably does not).
        for slot_id, slot in enumerate(self.slots):
            if (
                slot.request is not None
                and not slot.prefilling
                and int(self._lengths[slot_id]) == 0
            ):
                if slot.request.preemptions >= _SHRINK_RETRY_CAP:
                    self._shed_stranded(slot_id, error)
                    shed += 1
                else:
                    self._preempt_slot(slot_id, reason="pool-shrink")
                    preempted += 1
        while bm.reserved_blocks > bm.usable_blocks:
            victim = self._shrink_victim()
            if victim is None:
                break
            self._preempt_slot(victim, reason="pool-shrink")
            preempted += 1
        if reduced == 0 and preempted == 0 and shed == 0:
            return False
        now = time.monotonic()
        self.pool_shrinks += 1
        self.shrink_preempted += preempted
        self._shrink_recover_at = now + self.config.shrink_recovery_s
        self._m_shrinks(1)
        self._m_budget(bm.usable_blocks)
        # the evidence event PRECEDES any admission against the reduced
        # budget (same loop pass): site + error text, what was withheld,
        # what preemption freed, and the budget admissions now face
        self.flight.event(
            "pool-shrink",
            site=getattr(error, "fault_site", None) or "device",
            error=f"{type(error).__name__}: {error}"[:160],
            withheld_blocks=reduced,
            withheld_bytes=reduced * self._kv_block_bytes,
            freed_blocks=reserved_before - bm.reserved_blocks,
            freed_bytes=(
                (reserved_before - bm.reserved_blocks)
                * self._kv_block_bytes
            ),
            preempted=preempted,
            shed=shed,
            budget_blocks=bm.usable_blocks,
            configured_blocks=bm.configured_blocks,
            recovery_s=self.config.shrink_recovery_s,
        )
        log.warning(
            "device memory pressure (%s): KV budget shrunk to %d/%d "
            "blocks, %d victims requeued front-of-class",
            type(error).__name__, bm.usable_blocks, bm.configured_blocks,
            preempted,
        )
        return True

    def _shrink_step(self) -> None:
        """Recovery probe (loop safe point, wait-free): after one quiet
        ``shrink_recovery_s`` window — no further allocator failures,
        which would have pushed ``_shrink_recover_at`` out — restore one
        shrink quantum. Staged, not all-at-once: if the pressure is
        still there, the next failure re-shrinks immediately and the
        thrash is visible in the event ring (engine_top --analyze flags
        it) instead of oscillating the whole budget."""
        at = self._shrink_recover_at
        bm = self.block_mgr
        if at is None or bm is None or time.monotonic() < at:
            return
        quantum = max(
            1, int(bm.configured_blocks * self.config.shrink_fraction)
        )
        restored = bm.restore_budget(quantum)
        if restored:
            self.pool_restores += 1
            self._m_restores(1)
            self._m_budget(bm.usable_blocks)
            self.flight.event(
                "pool-restore",
                restored_blocks=restored,
                restored_bytes=restored * self._kv_block_bytes,
                budget_blocks=bm.usable_blocks,
                configured_blocks=bm.configured_blocks,
            )
        if bm.budget_reduction == 0:
            self._shrink_recover_at = None
            self._wake.set()  # restored headroom is an admission signal
        else:
            self._shrink_recover_at = (
                time.monotonic() + self.config.shrink_recovery_s
            )

    def _replay_journal(self, loop) -> None:
        """Requeue the previous process's admitted-but-unfinished
        journal entries FRONT-of-class (the drain/preemption resume
        path), ahead of anything this process accepted since. The
        original callers' futures died with their process — each replay
        gets a fresh future whose completion (or explicit failure)
        retires the entry, so the journal converges to empty exactly
        once per entry."""
        entries, self._journal_replay_pending = (
            self._journal_replay_pending, []
        )
        replayed = 0
        # reversed: each requeues at the FRONT of its class, so
        # newest-first preserves the original admit order
        for entry in reversed(entries):
            try:
                tokens = [int(t) for t in entry["prompt"]]
                # the same clamps generate() applies at accept time: the
                # restarted engine may run a smaller max-seq-len/pool
                # than the one that journaled the entry
                max_prompt = self.model_config.max_seq_len - 2
                if len(tokens) > max_prompt:
                    tokens = tokens[-max_prompt:]
                max_tokens = min(
                    int(entry["max-tokens"]),
                    self.model_config.max_seq_len - len(tokens) - 1,
                )
                if max_tokens < 1 or not self.block_mgr.fits_ever(
                    len(tokens) + max_tokens + 1
                ):
                    # generate() refuses never-fitting requests up front
                    # and admission relies on that invariant — a replayed
                    # entry that can no longer fit would head-block
                    # admission FOREVER (and re-wedge every restart, as
                    # it is never answered and so never retired). Refuse
                    # it loudly instead.
                    raise ValueError(
                        "request no longer fits the restarted engine's "
                        "KV pool"
                    )
                request = _Request(
                    prompt_tokens=tokens,
                    max_tokens=max_tokens,
                    temperature=float(entry.get("temperature", 0.0)),
                    top_k=int(entry.get("top-k", 0)),
                    top_p=float(entry.get("top-p", 1.0)),
                    on_token=None,
                    future=loop.create_future(),
                    loop=loop,
                    enqueue_time=time.monotonic(),
                    stop=_normalize_stop(entry.get("stop")),
                    presence_penalty=float(
                        entry.get("presence-penalty", 0.0)
                    ),
                    frequency_penalty=float(
                        entry.get("frequency-penalty", 0.0)
                    ),
                    tenant=str(entry.get("tenant", "") or ""),
                    priority=normalize_priority(entry.get("priority")),
                    # the original end-to-end budget replays with the
                    # entry: the admission deadline gate sheds it loudly
                    # if the crash already spent it
                    deadline=parse_deadline(entry.get("deadline")),
                )
            except (KeyError, TypeError, ValueError) as e:
                # a corrupt entry is retired loudly, never replayed as
                # garbage and never left to wedge every future restart
                log.error("journal entry unreplayable (%s): %r", e, entry)
                self.journal.retire(entry.get("id"))
                continue
            request.journey_id = entry.get("id")
            # nobody awaits a replayed future: swallow its outcome so a
            # shed replay can't die as "exception never retrieved"
            request.future.add_done_callback(
                lambda f: f.exception() if not f.cancelled() else None
            )
            self._journey(request, "journal-replay")
            self.scheduler.requeue_front(request)
            replayed += 1
        if replayed:
            self.journal.note_replayed(replayed)
            self.flight.event("journal-replay", requests=replayed)
            log.info(
                "journal replay: %d admitted-but-unfinished requests "
                "requeued front-of-class", replayed,
            )

    def _journal_retire(self, request: "_Request") -> None:
        """Retire one request's journal entry (finish/shed/fail — every
        path that ANSWERS the caller). Wait-free: a deque append."""
        if self.journal is not None and not request.warmup:
            self.journal.retire(request.journey_id)
            if self._m_journal_depth is not None:
                self._m_journal_depth(self.journal.depth())

    def survival_section(self) -> dict[str, Any]:
        """The ``stats()["survival"]`` / flight-summary section: live
        budget posture, shrink/restore counters, fault-injection state,
        journal depth. Wait-free (attribute reads + small copies) — the
        autoscaler's fan-in and ``engine_top`` read it from
        ``/flight/summary``."""
        bm = self.block_mgr
        out: dict[str, Any] = {
            "shrinks": self.pool_shrinks,
            "restores": self.pool_restores,
            "shrink_preempted": self.shrink_preempted,
            "recovery_s": self.config.shrink_recovery_s,
            "recovering": self._shrink_recover_at is not None,
            # cross-replica failure domain (docs/RESILIENCE.md
            # "Distributed failure domain"): 504-shaped deadline
            # refusals and post-hoc overruns, chainer re-offers and
            # local-decode fallbacks — engine_top's panel reads these
            "deadline_sheds": self.deadline_sheds,
            "deadline_overruns": self.deadline_overruns,
            "handoff_retries": self.handoff_retries,
            "handoff_fallbacks": self.handoff_fallbacks,
        }
        if bm is not None:
            out["budget_blocks"] = bm.usable_blocks
            out["configured_blocks"] = bm.configured_blocks
            out["withheld_blocks"] = bm.budget_reduction
            out["withheld_bytes"] = (
                bm.budget_reduction * self._kv_block_bytes
            )
        if self._faults is not None:
            out["faults"] = self._faults.stats()
        if self.journal is not None:
            out["journal"] = self.journal.stats()
        return out

    # ------------------------------------------------------------------
    # tiered prefix store (serving/prefixstore.py, docs/PREFIX.md)
    # ------------------------------------------------------------------

    def _note_prefix_pool_evict(self, digest_hex: str, block: int) -> None:
        """Pool pressure organically evicted a cached prefix block with
        no demotion (BlockManager._evict_one): record the T0 loss so the
        tier ledgers never lose bytes silently. Wait-free: a counter
        bump and a flight append."""
        self.prefix_t0_evictions += 1
        if self._m_prefix_tier:
            self._m_prefix_tier["evictions"](1)
        self.flight.event(
            "prefix-evict",
            tier="t0",
            digest=digest_hex[:16],
            bytes=self._kv_block_bytes,
            reason="pool-pressure",
        )

    def _emit_prefix_events(self) -> None:
        """Drain the prefix store's pending event feed (see
        :meth:`_emit_store_events` for the shared emission path)."""
        self._emit_store_events(self.prefix_store.drain_events())

    def _emit_store_events(self, events) -> None:
        """Drain a tiered store's pending event feed into the flight
        ring and mirror each transition onto its Prometheus counter —
        the ONE dynamic emission path in the engine (both the prefix
        and the adapter store drain through this call site; the
        event-vocabulary conformance test pins it), so the scrape
        surface can never drift from the flight events (wait-free:
        appends + counter bumps, PFX801/LORA1701)."""
        for kind, detail in events:
            self.flight.event(kind, **detail)
            if kind.startswith("adapter-"):
                if not self._m_adapters:
                    continue
                if kind == "adapter-evict":
                    self._m_adapters["evictions"](1)
                elif kind == "adapter-demote":
                    self._m_adapters["demotions"](1)
                elif kind == "adapter-load":
                    self._m_adapters["loads"](1)
                elif (
                    kind == "adapter-hydrate"
                    and detail.get("stage") == "fetched"
                ):
                    self._m_adapters["hydrations"](1)
                continue
            if not self._m_prefix_tier:
                continue
            if kind == "prefix-demote":
                self._m_prefix_tier["demotions"](1)
            elif kind == "prefix-evict":
                self._m_prefix_tier["evictions"](1)
            elif kind == "prefix-promote":
                self._m_prefix_tier["t1_hits"](detail.get("blocks") or 1)
            elif (
                kind == "prefix-hydrate"
                and detail.get("stage") == "fetched"
            ):
                self._m_prefix_tier["t2_hits"](1)

    def _prefix_tier_step(self) -> None:
        """Loop-safe-point tier bookkeeping (wait-free, PFX801): apply
        the hydrator's results, emit the store's pending flight events,
        and settle the hydration stash — a request whose T2 fetches
        landed in T1 (or timed out / failed) requeues at the FRONT of
        its class so the admission pass right after this finds it."""
        store = self.prefix_store
        if store is None:
            return
        store.apply_results()
        self._emit_prefix_events()
        if not self._prefix_hydrating:
            return
        now = time.monotonic()
        still_waiting = []
        # reversed: each settled request requeues at the FRONT, so
        # walking newest-first leaves the oldest stashed request at the
        # actual head — arrival order survives a same-pass settle burst
        for request, deadline, digests in reversed(self._prefix_hydrating):
            if request.future.cancelled():
                self._journey(request, "cancelled", stage="prefix-hydrate")
                continue
            ready = all(store.t1_has(d) for d in digests)
            pending = any(store.hydrating(d) for d in digests)
            if not ready and pending and now < deadline:
                still_waiting.append((request, deadline, digests))
                continue
            # ready, failed, or timed out: admission decides what the
            # T1 tier can actually cover — a partial hydration still
            # promotes its landed blocks and prefills the rest
            hit = sum(1 for d in digests if store.t1_has(d))
            timed_out = not ready and now >= deadline
            if timed_out:
                store.hydrate_failures += 1
            self.flight.event(
                "prefix-hydrate",
                stage="timeout" if timed_out else "done",
                blocks=hit,
                requested=len(digests),
            )
            self._journey(
                request, "hydrate-done",
                blocks=hit, requested=len(digests),
                timeout=timed_out,
            )
            self.scheduler.requeue_front(request)
        still_waiting.reverse()  # restore arrival order in the stash
        self._prefix_hydrating = still_waiting

    def _prefix_demote_pending(self) -> bool:
        """Whether the T0 prefix cache sits over its byte budget with
        demotion candidates available — the loop polls tightly while
        true so spill drains promptly. Wait-free (PFX801)."""
        store = self.prefix_store
        if store is None or store.spec.t0_bytes is None:
            return False
        if (
            self.block_mgr.prefix_block_count() * self._kv_block_bytes
            <= store.spec.t0_bytes
        ):
            return False
        return bool(self.block_mgr.evictable_prefixes(1))

    def _chain_t2_candidates(self, chain: list[bytes]) -> list[str]:
        """The prompt-chain digests an admission should WAIT for: the
        consecutive run, starting where T0+T1 coverage ends, of digests
        the T2 index knows. ``chain`` is the admission's shared
        :meth:`BlockManager.chain_digests` walk. Empty = nothing worth
        stashing for. Wait-free: dict membership only (PFX801)."""
        store = self.prefix_store
        out: list[str] = []
        for d in chain:
            if self.block_mgr.prefix_has(d):
                continue
            h = d.hex()
            if store.t1_has(h):
                continue
            if store.t2_has(h) or store.hydrating(h):
                out.append(h)
            else:
                break  # chain gap: deeper links are unreachable anyway
        return out

    async def _promote_prefix(
        self, loop, request: "_Request", chain: list[bytes]
    ) -> int:
        """Promote the T1 run extending this prompt's T0 chain back into
        freshly allocated pool blocks (T1→T0): take the entries, install
        cache-owned blocks, and scatter the host rows in on the dispatch
        thread (the kvtransfer pack path — one timed dispatch, donated
        pools rebound there like every other dispatch closure). After
        this, the ordinary ``match_prefix`` walk sees the longer chain
        and the suffix prefill shrinks accordingly. Returns the number
        of blocks promoted (0 = nothing to do or no pool space)."""
        store = self.prefix_store
        run: list[tuple[bytes, bytes]] = []  # (digest, parent)
        prev = b""
        for d in chain:
            if self.block_mgr.prefix_has(d):
                prev = d
                continue
            if run or store.t1_has(d.hex()):
                if not store.t1_has(d.hex()):
                    break
                run.append((d, prev))
                prev = d
            else:
                break
        if not run:
            return 0
        entries = []
        for d, _parent in run:
            entry = store.take_t1(d.hex())
            if entry is None:  # raced with a shrink: stop the run here
                run = run[: len(entries)]
                break
            entries.append(entry)
        if not entries:
            return 0
        blocks = self.block_mgr.install_prefix_chain(run)
        if blocks is None:
            # no pool space even after eviction: put the entries back
            # (MRU — they were just wanted) and compute cold
            for (d, parent), entry in zip(run, entries):
                store.insert_t1(
                    d.hex(), parent.hex() if parent else "",
                    entry["arrays"], source="t2",
                )
            return 0
        bs = self.paged_layout.block_size
        nbytes = sum(e["nbytes"] for e in entries)
        rows = len(blocks) * bs
        # shape-static scatter: rows pad to the same power-of-two bucket
        # and the table row to the full slot width, so promotions of any
        # run length share the import path's jit variants instead of
        # compiling one program per chain length (pad rows mask to the
        # scratch block exactly like /kv/import)
        padded = _bucket(rows, hi=self.model_config.max_seq_len)
        table_row = np.zeros(
            self.paged_layout.max_blocks_per_slot, dtype=np.int32
        )
        table_row[: len(blocks)] = blocks

        def _run():
            from langstream_tpu.serving import kvtransfer

            # one scatter covering the whole promoted run: concatenate
            # the per-block rows in chain order and write them through
            # the slot-shaped pack path with a block-table row of the
            # freshly installed blocks
            names = sorted(entries[0]["arrays"])
            arrays = {
                name: np.concatenate(
                    [e["arrays"][name] for e in entries], axis=1
                )
                for name in names
            }
            out_k, out_v = kvtransfer.scatter_slot(
                self.cache_k, self.cache_v, arrays,
                table_row, rows, padded, kernel=self.pool_commit_kernel,
            )
            # donated pools re-bound on the dispatch thread (RACE801:
            # single thread role, same contract as every dispatch)
            self.cache_k, self.cache_v = out_k, out_v
            t_dev = time.monotonic()
            # graftcheck: disable=JAX104 the one per-dispatch sync, moved off-loop and timed
            jax.block_until_ready((out_k, out_v))
            return time.monotonic() - t_dev

        device_s = await loop.run_in_executor(self._executor, _run)
        store.note_promoted(len(blocks), nbytes, device_ms=device_s * 1e3)
        self._emit_prefix_events()
        return len(blocks)

    async def _demote_prefix_blocks(self, loop) -> None:
        """T0 byte-budget enforcement at the loop's safe point: while
        the prefix cache sits over ``t0-bytes``, gather LRU cache-only
        leaf blocks to host (ONE timed dispatch-thread fetch for the
        batch) and hand their rows to the T1 tier, then free the pool
        blocks. Bounded per pass so a storm never starves admission."""
        store = self.prefix_store
        budget = store.spec.t0_bytes
        if budget is None:
            return
        t0_bytes = self.block_mgr.prefix_block_count() * self._kv_block_bytes
        over = t0_bytes - budget
        if over <= 0 or self._kv_block_bytes <= 0:
            return
        want = min(4, -(-over // self._kv_block_bytes))
        candidates = self.block_mgr.evictable_prefixes(want)
        if not candidates:
            return
        bs = self.paged_layout.block_size

        def _run():
            from langstream_tpu.serving import kvtransfer

            out = []
            for digest, block, parent in candidates:
                gathered_k, gathered_v = kvtransfer.gather_slot(
                    self.cache_k, self.cache_v,
                    np.asarray([block], dtype=np.int32), 1,
                )
                arrays, device_s = kvtransfer._fetch_rows(
                    gathered_k, gathered_v, bs
                )
                arrays = {
                    name: np.ascontiguousarray(a)
                    for name, a in arrays.items()
                }
                out.append((digest, parent, arrays, device_s))
            return out

        gathered = await loop.run_in_executor(self._executor, _run)
        for digest, parent, arrays, _device_s in gathered:
            if self.block_mgr.drop_prefix(digest) is None:
                continue  # re-referenced while gathering: keep it in T0
            store.insert_t1(
                digest.hex(), parent.hex() if parent else "", arrays
            )
        self._emit_prefix_events()

    def prefix_store_section(self) -> dict[str, Any]:
        """``stats()["prefixstore"]`` / flight-summary section: per-tier
        bytes vs budget, hit/demotion/eviction counters, and the exact
        byte ledger. Wait-free (PFX801): snapshot reads + arithmetic;
        the tier gauges refresh here so any reader keeps the scrape
        surface current."""
        store = self.prefix_store
        t0_blocks = self.block_mgr.prefix_block_count()
        t0_bytes = t0_blocks * self._kv_block_bytes
        section = {
            "t0": {
                "blocks": t0_blocks,
                "bytes": t0_bytes,
                "budget_bytes": store.spec.t0_bytes,
                "hits": self.prefix_hits,
                "tokens_reused": self.prefix_tokens,
                "pool_evictions": self.prefix_t0_evictions,
            },
            "hydrating_requests": len(self._prefix_hydrating),
            **store.stats(),
        }
        if self._m_prefix_tier:
            self._m_prefix_tier["t0_bytes"](t0_bytes)
            self._m_prefix_tier["t1_bytes"](store.t1_bytes)
            self._m_prefix_tier["t2_bytes"](store.t2_bytes)
        return section

    # ------------------------------------------------------------------
    # multi-LoRA adapter tier plumbing (serving/adapters.py,
    # docs/ADAPTERS.md)
    # ------------------------------------------------------------------

    def install_adapter(
        self, name: str, arrays: dict[str, np.ndarray]
    ) -> None:
        """Install LoRA factors into the store's T1 tier directly (the
        local load path: tests, bench seeding, a sidecar that fetched
        out-of-band). Shapes are checked against the model HERE so a
        wrong-rank adapter fails at install, not mid-decode."""
        if self.adapter_store is None:
            raise ValueError(
                "adapter store not configured (serving adapter-store)"
            )
        mc = self.model_config
        r = self.config.adapter_store.rank
        q_dim = mc.heads * mc.head_dim
        kv_dim = mc.kv_heads * mc.head_dim
        expect = {
            "wq_a": (mc.layers, mc.hidden, r),
            "wq_b": (mc.layers, r, q_dim),
            "wk_a": (mc.layers, mc.hidden, r),
            "wk_b": (mc.layers, r, kv_dim),
            "wv_a": (mc.layers, mc.hidden, r),
            "wv_b": (mc.layers, r, kv_dim),
            "wo_a": (mc.layers, q_dim, r),
            "wo_b": (mc.layers, r, mc.hidden),
        }
        for k, shape in expect.items():
            got = tuple(np.asarray(arrays[k]).shape) if k in arrays else None
            if got != shape:
                raise ValueError(
                    f"adapter {name!r} factor {k}: shape {got}, "
                    f"model expects {shape}"
                )
        self.adapter_store.install(name, arrays)

    async def _resolve_adapter(self, loop, request: "_Request") -> str:
        """Admission-side adapter resolve. Returns one of:

        - ``"ready"``    — a device row holds the adapter; the request is
          pinned against eviction and carries the row index.
        - ``"wait"``     — the adapter is hydrating T2→T1; the request was
          popped and stashed OFF the scheduler (same discipline as the
          prefix hydration stash — it never head-blocks admission).
        - ``"refused"``  — unknown adapter or a spent hydration attempt:
          the request was popped and failed loudly (AdapterUnavailable).
        - ``"backpressure"`` — every T0 row is pinned by in-flight
          requests; the caller breaks the admission pass and retries
          after decode frees pins.

        Wait-free on the loop side apart from the one awaited device
        row-copy dispatch (LORA1701: the T2 I/O lives on the hydrator)."""
        store = self.adapter_store
        name = request.adapter
        row = store.t0_row(name)
        if row is None and store.t1_has(name):
            row = store.t0_assign(name)
            if row is None:
                return "backpressure"
            await self._load_adapter_row(loop, name, row)
        if row is not None:
            request.adapter_row = row
            store.pin(name)
            request.adapter_pinned = True
            return "ready"
        if (
            not request.adapter_hydrate_attempted
            and not self._draining
            and (store.t2_has(name) or store.hydrating(name))
        ):
            request.adapter_hydrate_attempted = True
            if store.request_hydration([name]):
                self.scheduler.pop()
                deadline = (
                    time.monotonic() + store.spec.hydrate_timeout_s
                )
                self._adapter_hydrating.append((request, deadline, name))
                store.hydrations += 1
                self.flight.event(
                    "adapter-hydrate", stage="begin", adapter=name
                )
                self._journey(request, "adapter-hydrate", adapter=name)
                return "wait"
        self.scheduler.pop()
        self.adapter_refusals += 1
        self.flight.event("adapter-refused", adapter=name)
        self._journal_retire(request)
        if not request.future.done():
            request.future.set_exception(
                AdapterUnavailable(
                    f"adapter {name!r} unavailable: not resident in any "
                    "tier (install it or publish it to the T2 origin)"
                )
            )
        return "refused"

    async def _load_adapter_row(self, loop, name: str, row: int) -> None:
        """Copy a T1-resident adapter's factors into device row ``row``
        (T1→T0). Runs on the dispatch thread — the only thread that
        touches ``_ad_layers`` — as a functional per-row rebuild
        (``.at[:, row].set``): in-flight dispatches keep the buffer
        snapshot they captured, exactly like the donated caches."""
        store = self.adapter_store
        entry = store.t1_peek(name)
        arrays = entry["arrays"]
        dtype = self.model_config.dtype

        def _run():
            t0 = time.monotonic()
            new = {
                k: buf.at[:, row].set(jnp.asarray(arrays[k], dtype=dtype))
                for k, buf in self._ad_layers.items()
            }
            # graftcheck: disable=JAX104 one timed per-load sync, on the dispatch thread
            jax.block_until_ready(list(new.values()))
            self._ad_layers = new
            return (time.monotonic() - t0) * 1000.0

        device_ms = await loop.run_in_executor(self._executor, _run)
        store.note_loaded(name, row, device_ms)

    def _adapter_release(self, request: "_Request") -> None:
        """Release a finished/failed request's pin on its adapter row.
        Wait-free: dict arithmetic (LORA1701)."""
        if request.adapter_pinned:
            request.adapter_pinned = False
            if self.adapter_store is not None:
                self.adapter_store.unpin(request.adapter)

    def _adapter_tier_step(self) -> None:
        """Loop-safe-point adapter bookkeeping (wait-free, LORA1701):
        apply the hydrator's results, emit the store's pending flight
        events through the shared drain, and settle the hydration
        stash. A request whose adapter landed in T1 requeues at the
        FRONT of its class; a timed-out or failed hydration is a COLD
        REFUSAL (AdapterUnavailable) — unlike a prefix miss there is no
        cheaper fallback compute, so requeueing would just spin."""
        store = self.adapter_store
        if store is None:
            return
        store.apply_results()
        self._emit_store_events(store.drain_events())
        if not self._adapter_hydrating:
            return
        now = time.monotonic()
        still_waiting = []
        # reversed: settled requests requeue at the FRONT, so walking
        # newest-first leaves the oldest at the actual head
        for request, deadline, name in reversed(self._adapter_hydrating):
            if request.future.cancelled():
                self._journey(request, "cancelled", stage="adapter-hydrate")
                self._journal_retire(request)
                continue
            if store.t1_has(name):
                self.flight.event(
                    "adapter-hydrate", stage="done", adapter=name
                )
                self._journey(request, "adapter-hydrate-done", adapter=name)
                self.scheduler.requeue_front(request)
                continue
            if store.hydrating(name) and now < deadline:
                still_waiting.append((request, deadline, name))
                continue
            # failed or timed out: refuse cold — loudly, never silently
            store.hydrate_failures += 1
            self.adapter_refusals += 1
            self.flight.event(
                "adapter-hydrate", stage="timeout", adapter=name
            )
            self.flight.event("adapter-refused", adapter=name)
            self._journey(
                request, "adapter-hydrate-done", adapter=name, timeout=True
            )
            self._journal_retire(request)
            if not request.future.done():
                request.future.set_exception(
                    AdapterUnavailable(
                        f"adapter {name!r} hydration timed out after "
                        f"{store.spec.hydrate_timeout_s:.1f}s"
                    )
                )
        still_waiting.reverse()  # restore arrival order in the stash
        self._adapter_hydrating = still_waiting

    def adapter_store_section(self) -> dict[str, Any]:
        """``stats()["adapters"]`` / flight-summary section: per-tier
        bytes vs budget, hit/load/eviction counters, the resident row
        map, and the exact byte ledger. Wait-free (LORA1701): snapshot
        reads + arithmetic; the tier gauges refresh here so any reader
        keeps the scrape surface current."""
        store = self.adapter_store
        section = {
            "hydrating_requests": len(self._adapter_hydrating),
            "refusals": self.adapter_refusals,
            **store.stats(),
        }
        if self._m_adapters:
            self._m_adapters["t0_bytes"](section["t0"]["bytes"])
            self._m_adapters["t1_bytes"](store.t1_bytes)
            self._m_adapters["t2_bytes"](store.t2_bytes)
        return section

    def _draft_tokens(
        self, slot_id: int, num_drafts: int
    ) -> tuple[list[int], int]:
        """Prompt-lookup draft: continue the context's most recent bigram
        match. Unmatched slots get zero drafts — greedy verify accepts a
        draft only when the model would have emitted it anyway, so a bad
        draft costs nothing but the verified position. Returns the padded
        draft row and the number of REAL drafts in it (padding zeros are
        not drafts — counting them as rejected would deflate the accept
        ratio on workloads where lookup rarely matches)."""
        request = self.slots[slot_id].request
        ctx = request.prompt_tokens + request.generated
        n = len(ctx)
        # index new bigrams whose SECOND element sits at <= n-2 (the final
        # bigram is the query; it enters the index once the context grows)
        idx = request.bigram_index
        for i in range(max(request.bigram_covered, 1), n - 1):
            idx[(ctx[i - 1], ctx[i])] = i - 1
        request.bigram_covered = max(request.bigram_covered, n - 1)
        if n >= 3:
            pos = idx.get((ctx[-2], ctx[-1]))
            if pos is not None:
                cont = ctx[pos + 2 : pos + 2 + num_drafts]
                padded = list(cont) + [0] * (num_drafts - len(cont))
                return padded, len(cont)
        return [0] * num_drafts, 0

    def _sync_ctx_rows(
        self, live: list[int]
    ) -> tuple[np.ndarray, np.ndarray] | tuple[None, None]:
        """Host-side payload for re-syncing stale context rows of the
        device-resident token buffer the fused drafter reads. The ledger
        ``_ctx_synced[slot]`` holds the number of valid tokens in the
        slot's device row; a row is current when it equals ``lengths+1``
        (history plus the pending current token). The fused spec step
        extends rows in-program as drafts are accepted, so under a pure
        speculative run NOTHING re-syncs — only freshly-prefilled slots
        and slots advanced by a plain decode chunk (calibration, or an
        auto-disabled interval), each with one full-row upload. Loop-
        thread only (host truth, ledger update); the device write itself
        happens in the dispatch closure, which also broadcasts this
        payload so lockstep followers apply the identical update."""
        S = self.model_config.max_seq_len
        rows: list[int] = []
        vals: list[np.ndarray] = []
        for slot_id in live:
            request = self.slots[slot_id].request
            n = min(int(self._lengths[slot_id]) + 1, S)
            if int(self._ctx_synced[slot_id]) == n:
                continue
            ctx = request.prompt_tokens + request.generated
            row = np.zeros(S, dtype=np.int32)
            m = min(n, len(ctx))
            row[:m] = ctx[:m]
            rows.append(slot_id)
            vals.append(row)
            self._ctx_synced[slot_id] = n
        if not rows:
            return None, None
        return np.fromiter(rows, dtype=np.int32, count=len(rows)), np.stack(vals)

    def _fetch_spec(
        self, packed, d1: int
    ) -> tuple[np.ndarray, ...]:
        """Designated fetch stage for the fused speculative step: ONE
        device→host transfer per step carries emitted tokens, per-slot
        advance counts, the next-token feedback, new lengths, real-draft
        counts, and bitcast logprobs."""
        B = self.config.slots
        nE = B * d1
        self._fault("fetch")
        flat = np.asarray(packed)
        self._spec_fetches += 1
        return (
            flat[:nE].reshape(B, d1),
            flat[nE:nE + B],
            flat[nE + B:nE + 2 * B],
            flat[nE + 2 * B:nE + 3 * B],
            flat[nE + 3 * B:nE + 4 * B],
            flat[nE + 4 * B:].view(np.float32).reshape(B, d1),
        )

    def _spec_note_step(self, tokens: int, wall_s: float) -> None:
        if tokens > 0 and wall_s > 0:
            self._spec_window.append((tokens, wall_s))

    def _spec_note_plain(self, tokens: int, wall_s: float) -> None:
        if tokens > 0 and wall_s > 0:
            self._plain_window.append((tokens, wall_s))

    def _spec_uplift(self) -> float | None:
        """Rolling measured uplift: speculative tokens/s over plain
        tokens/s, None until the spec window is full AND at least one
        plain (calibration) sample exists — a half-window verdict would
        flap on warmup jitter."""
        if len(self._spec_window) < (self._spec_window.maxlen or 1):
            return None
        if not self._plain_window:
            return None
        spec_n = sum(n for n, _ in self._spec_window)
        spec_t = sum(w for _, w in self._spec_window)
        plain_n = sum(n for n, _ in self._plain_window)
        plain_t = sum(w for _, w in self._plain_window)
        if spec_t <= 0 or plain_t <= 0 or plain_n <= 0:
            return None
        return (spec_n / spec_t) / (plain_n / plain_t)

    def _spec_check_uplift(self) -> bool:
        """Flip speculation off when the measured uplift drops below 1 —
        the honest answer to BENCH_r05's 0.23x speculative slowdown: a
        high accept ratio is NOT a win if the per-step cost eats it.
        Returns True when the flip happened (the burst must return to the
        plain decode loop). Re-enable is time-served: see the
        ``spec-auto-enable`` branch in :meth:`_flight_record`."""
        uplift = self._spec_uplift()
        if uplift is None:
            return False
        self._spec_last_uplift = uplift
        self._m_spec_uplift(uplift)
        if uplift >= 1.0:
            return False
        self._spec_auto_disabled = True
        self._spec_plain_since_disable = 0
        self._spec_flips.append((time.monotonic(), "disable"))
        self.flight.event(
            "spec-auto-disable",
            uplift=round(uplift, 4),
            window_steps=len(self._spec_window),
            plain_samples=len(self._plain_window),
        )
        self._spec_window.clear()
        self._plain_window.clear()
        return True

    def _spec_cal_due(self) -> bool:
        return self._spec_steps_since_cal >= self._spec_cal_every

    async def _spec_calibration_chunk(
        self, loop, live: list[int], active_mask: np.ndarray,
        sampler_mode: tuple, tables: np.ndarray, nrb: int,
    ) -> bool:
        """One plain K=1 decode chunk, wall-timed end to end, feeding the
        plain-throughput window the uplift verdict divides by. Greedy
        streams stay byte-identical: a single plain greedy step emits
        exactly the token the spec step's first verified position would.
        Returns True when any slot finished (the burst tears down, same
        as the sequential decode loop)."""
        K = 1
        fn = self._decode_fn(sampler_mode, nrb, K, False)
        ticket = self._ticket(
            self._program_decode(nrb, K, sampler_mode, False), K, len(live)
        )
        amask, temps, topks, topps = self._sampler_device(active_mask)
        lengths_np = self._lengths.copy()
        current_np = self._current.copy()
        temps_np = self._temps.copy()
        topks_np = self._topks.copy()
        topps_np = self._topps.copy()
        ad_np = self._ad_rows.copy() if self._ad_rows is not None else None
        key = self._split_key()

        def _run():
            if self._lockstep is not None:
                self._lockstep.broadcast(
                    {
                        "op": "decode",
                        "sampler_mode": list(sampler_mode),
                        "window": nrb,
                        "k": K,
                        "key": np.asarray(key),
                        "active": active_mask,
                        "tables": tables,
                        "tokens": current_np,
                        "lengths": lengths_np,
                        "temps": temps_np,
                        "topks": topks_np,
                        "topps": topps_np,
                    }
                )
            self.profiler.on_decode_chunk()
            tables_dev = self._tables_device(tables)
            ad_kw = (
                {}
                if ad_np is None
                else {"ad_layers": self._ad_layers,
                      "ad_ids": jnp.asarray(ad_np)}
            )
            packed, _t, _l, ck, cv = fn(
                self.params, self.cache_k, self.cache_v,
                jnp.asarray(current_np), jnp.asarray(lengths_np),
                amask, tables_dev, key, temps, topks, topps, **ad_kw,
            )
            self.flight.clock.enqueued(
                ticket["clock"], packed, ticket["dispatch"])
            self.cache_k, self.cache_v = ck, cv
            self._decode_dispatches += 1
            self._start_fetch(packed)
            return self._fetch_chunk(packed, K, ticket)[:3]

        t_wall = time.monotonic()
        chunk_t, chunk_lp, fetch_s = await loop.run_in_executor(
            self._executor, _run
        )
        resumed(ticket["clock"])
        gen_before = self.total_generated
        finished = self._process_chunk(chunk_t, chunk_lp, live)
        self._spec_note_plain(
            self.total_generated - gen_before, time.monotonic() - t_wall
        )
        self._flight_record(
            "decode", device_s=fetch_s,
            tokens=self.total_generated - gen_before, **ticket,
        )
        await self._flush_emits(live)
        return finished

    async def _speculative_burst(self, loop, active: list[int]) -> None:
        """Device-resident prompt-lookup speculative decoding: per step,
        ONE fused dispatch drafts each slot's continuation from the
        device-resident context rows, verifies D+1 positions, extends the
        context rows in-program, and packs everything the host needs into
        a single array — zero host syncs inside the dispatch closure
        (graftcheck HOT1401/HOT1402), one packed fetch per step. Streams
        are identical to plain greedy decode — only the tokens-per-step
        ratio changes. A rolling measured-uplift window (calibrated by
        periodic plain K=1 chunks) flips speculation off with a
        ``spec-auto-disable`` flight event when the fused step is not
        actually paying for itself."""
        D = self.config.speculative_drafts
        D1 = D + 1
        S = self.model_config.max_seq_len
        while True:
            if self._spec_auto_disabled:
                return
            live = [
                i for i in active
                if self.slots[i].request is not None
                and not self.slots[i].prefilling
            ]
            if not live:
                return
            self._fault("pool-grow")
            grown_blocks = grown_slots = 0
            for slot_id in live:
                n = self.block_mgr.ensure_capacity(
                    slot_id, min(int(self._lengths[slot_id]) + D1, S)
                )
                grown_blocks += n
                grown_slots += bool(n)
            if grown_blocks:
                self.flight.event(
                    "pool-grow", slots=grown_slots, blocks=grown_blocks,
                    bytes=grown_blocks * self._kv_block_bytes,
                    phase="verify",
                )
            tables = self.block_mgr.tables.copy()
            active_mask = np.zeros(self.config.slots, dtype=bool)
            active_mask[live] = True
            nrb = self._read_blocks_for(
                max(int(self._lengths[live].max()) if live else 1, 1)
            )
            sampler_mode = self._sampler_mode(
                self._temps[active_mask], self._topks[active_mask],
                self._topps[active_mask],
            )
            if self._spec_cal_due():
                finished = await self._spec_calibration_chunk(
                    loop, live, active_mask, sampler_mode, tables, nrb
                )
                self._spec_steps_since_cal = 0
                if self._spec_check_uplift():
                    return
                if (
                    finished
                    or not self.scheduler.empty()
                    or self._stop
                    or self._has_prefilling()
                    or (self._draining and not self._drain_pass_done)
                ):
                    return
                continue  # re-derive live/lengths: the chunk advanced them
            ctx_rows, ctx_vals = self._sync_ctx_rows(live)
            fn = self._spec_step_fn(nrb, sampler_mode)
            program = self._program_spec_step(nrb, sampler_mode)
            # host state snapshotted on the LOOP thread: the spec step
            # yields to admission between iterations, which rewrites the
            # sampler arrays — the dispatch closure must not re-read
            # mutable engine fields mid-flight (RACE801)
            lengths_np = self._lengths.copy()
            current_np = self._current.copy()
            temps_np = self._temps.copy()
            topks_np = self._topks.copy()
            topps_np = self._topps.copy()
            ad_np = (
                self._ad_rows.copy() if self._ad_rows is not None else None
            )
            key = self._split_key()
            times: dict = {}  # the step's gap_ms / program_ms

            def _run():
                if self._lockstep is not None:
                    # drafting moved on-device: followers replay the same
                    # fused jit from control-plane state only — current
                    # tokens, lengths, and any context rows the leader
                    # re-synced this step (device rows chain otherwise)
                    desc: dict[str, Any] = {
                        "op": "spec_step",
                        "nrb": nrb,
                        "sampler_mode": list(sampler_mode),
                        "current": current_np,
                        "lengths": lengths_np,
                        "active": active_mask,
                        "tables": tables,
                        "key": np.asarray(key),
                        "temps": temps_np,
                        "topks": topks_np,
                        "topps": topps_np,
                    }
                    if ctx_rows is not None:
                        desc["ctx_rows"] = ctx_rows
                        desc["ctx_vals"] = ctx_vals
                    self._lockstep.broadcast(desc)
                ad_kw = (
                    {}
                    if ad_np is None
                    else {"ad_layers": self._ad_layers,
                          "ad_ids": jnp.asarray(ad_np)}
                )
                # the context buffer lives on the dispatch thread, like
                # the KV caches: created lazily, patched with the loop
                # thread's re-sync payload, then chained through the
                # fused program's donated output
                if self._ctx_dev is None:
                    self._ctx_dev = jnp.zeros(
                        (self.config.slots, self.model_config.max_seq_len),
                        dtype=jnp.int32,
                    )
                if ctx_rows is not None:
                    self._ctx_dev = self._ctx_dev.at[
                        jnp.asarray(ctx_rows)
                    ].set(jnp.asarray(ctx_vals))
                out = fn(
                    self.params, self.cache_k, self.cache_v, self._ctx_dev,
                    jnp.asarray(current_np), jnp.asarray(lengths_np),
                    jnp.asarray(active_mask), jnp.asarray(tables),
                    key, jnp.asarray(temps_np), jnp.asarray(topks_np),
                    jnp.asarray(topps_np), **ad_kw,
                )
                self.flight.clock.enqueued(times, out[0])
                self._ctx_dev = out[1]
                self.cache_k, self.cache_v = out[2], out[3]
                self._spec_dispatches += 1
                self._start_fetch(out[0])
                # dispatch returned async; the fetch below blocks until
                # the device finishes — that wait is the step's device time
                t_dev = time.monotonic()
                fetched = self._fetch_spec(out[0], D1)
                self.flight.clock.ready(times)
                return fetched + (time.monotonic() - t_dev,)

            t_wall = time.monotonic()
            emitted, adv, nxt, new_lengths, n_real, logprobs, device_s = (
                await loop.run_in_executor(self._executor, _run)
            )
            self._m_spec_steps(1)
            self.spec_steps += 1
            self._spec_steps_since_cal += 1
            finished = False
            emitted_before = self.total_generated  # _emit_token counts each
            accepted_before = self.spec_accepted
            rejected_step = 0
            for slot_id in live:
                a = int(adv[slot_id])
                base = int(self._lengths[slot_id])
                done = False
                acc_slot = 0
                for j in range(a):
                    # advance the length BEFORE each emit so the emit-side
                    # max_seq_len stop guard sees the true context size
                    # (plain decode increments per step; a stale base would
                    # let accepted drafts run past the cap and diverge from
                    # the bit-identical-to-greedy invariant)
                    self._lengths[slot_id] = base + j + 1
                    done = self._emit_token(
                        slot_id,
                        int(emitted[slot_id, j]),
                        float(logprobs[slot_id, j]),
                    )
                    if j > 0:
                        self._m_spec_accepted(1)
                        self.spec_accepted += 1
                        acc_slot += 1
                    if done:
                        finished = True
                        break
                if not done:
                    self._current[slot_id] = int(nxt[slot_id])
                    # the fused step appended this slot's accepted tokens
                    # to its device context row in-program
                    self._ctx_synced[slot_id] = base + a + 1
                # only REAL drafts count as rejected (padding zeros never
                # were drafts); drafts left unconsumed by a mid-burst
                # stop/EOS were still wasted verify positions
                rejected_step += max(0, int(n_real[slot_id]) - acc_slot)
            self._m_tokens(self.total_generated - emitted_before)
            accepted_step = self.spec_accepted - accepted_before
            self.spec_rejected += rejected_step
            self._m_spec_rejected(rejected_step)
            drafted = self.spec_accepted + self.spec_rejected
            if drafted:
                self._m_spec_ratio(self.spec_accepted / drafted)
            self._spec_note_step(
                self.total_generated - emitted_before,
                time.monotonic() - t_wall,
            )
            self._flight_record(
                "verify",
                device_s=device_s,
                tokens=self.total_generated - emitted_before,
                spec_accepted=accepted_step,
                spec_rejected=rejected_step,
                program=program,
                clock=times,
            )
            if self._spec_check_uplift():
                await self._flush_emits(live)
                return
            await self._flush_emits(live)
            if (
                finished
                or not self.scheduler.empty()
                or self._stop
                or self._has_prefilling()
                # a pending drain preempts at the loop's safe point
                or (self._draining and not self._drain_pass_done)
            ):
                return

    def _burst_should_yield(self, finished: bool, pipelined: bool = False) -> bool:
        """End the decode burst only when the engine loop can actually make
        progress elsewhere: a slot just freed (admission now possible),
        queued work can land in an already-free slot, the engine is
        stopping, or a prefill is mid-flight. A non-empty queue with ZERO
        free slots must NOT end the burst — returning would tear down the
        pipelined chunk stream and re-pay the per-burst device uploads on
        every chunk (a saturated engine holds a full admission queue for
        its whole run, so every chunk would become its own burst). Cost of
        those uploads on a locally attached chip: not measured (ROADMAP
        S2).

        Pipelined bursts additionally survive a finish when nobody is
        queued: the finished slot is frozen in the device-side active mask
        from the next dispatch on (its over-run tokens discarded host-side,
        never billed), so mixed-length workloads don't tear the pipeline
        down — and re-pay its teardown/rebuild — once per completion. The
        sequential reference loop keeps the yield-on-finish behavior."""
        if self._stop or self._has_prefilling():
            return True
        if self._draining and not self._drain_pass_done:
            # a pending drain must reach the loop's safe point NOW: the
            # preempt-and-requeue sweep snapshots every running request
            # after the current chunk, not after the whole burst
            return True
        if finished:
            # a freed slot is an admission opportunity the moment anyone
            # is queued; otherwise the pipelined loop freezes it in place
            return not (pipelined and self.scheduler.empty())
        if self.scheduler.empty():
            return False
        if os.environ.get("LS_TPU_STICKY_BURSTS", "1") == "0":
            return True  # pre-r5 behavior (A/B knob): yield on any queue
        return any(s.free for s in self.slots)

    def _fetch_chunk(
        self, packed, k_steps: int, ticket: dict
    ) -> tuple[np.ndarray, np.ndarray, float, np.ndarray]:
        """The designated fetch stage (graftcheck PERF701 polices syncs
        anywhere else on the dispatch path): ONE device→host transfer per
        chunk — tokens and bitcast logprobs ride the same packed array,
        whose D2H copy the dispatch already started asynchronously. The
        third element is the seconds this call spent blocked on the
        device — the chunk's un-overlapped device wait, which the flight
        recorder subtracts from wall time to expose the host share. The
        fourth is whatever counters the program packed behind them (a
        hybrid model's expert loads; empty otherwise). ``ticket`` is the
        chunk's: the blocking call is its ``ls.decode.wait`` span, and its
        ``clock`` gets the completion (``ready``) and this thread's last
        stamp before returning (``returned_t``, what the loop's resume lag
        is counted from)."""
        B = self.config.slots
        n = k_steps * B
        times = ticket["clock"]
        # everything this thread does for the fetch lies under its span, so
        # that what is left under the loop's held ``ls.decode.fetch`` is
        # the coroutine's wait for its turn alone
        with self.flight.span("ls.decode.wait", seq=ticket["dispatch"]):
            self._fault("fetch")
            t_dev = time.monotonic()
            flat = np.asarray(packed)
            fetch_s = time.monotonic() - t_dev
            self.flight.clock.ready(times)
            self._decode_fetches += 1
            fetched = (
                flat[:n].reshape(k_steps, B),
                flat[n:2 * n].view(np.float32).reshape(k_steps, B),
                fetch_s,
                flat[2 * n:],
            )
            times["returned_t"] = time.monotonic()
        return fetched

    async def _await_chunk(self, loop, packed, k_steps: int, ticket: dict):
        """The loop thread's wait for a dispatched chunk's tokens, from
        handing :meth:`_fetch_chunk` to the dispatch thread until this
        coroutine runs again: the ``ls.decode.fetch`` span, held across
        the ``await`` (flight.py ``HELD_SPANS``)."""
        with self.flight.span("ls.decode.fetch", seq=ticket["dispatch"]):
            chunk_t, chunk_lp, fetch_s, loads = await loop.run_in_executor(
                self._executor,
                partial(self._fetch_chunk, packed, k_steps, ticket),
            )
            resumed(ticket["clock"])
        if loads.size:
            # a hybrid chunk's expert loads, by (layer, held expert)
            ticket.update(
                routed_pairs=int(loads.sum()),
                expert_load_max=int(loads.max()),
                state_bytes=ticket["active_at_dispatch"]
                * self.model_config.state_bytes_per_slot,
            )
        return chunk_t, chunk_lp, fetch_s

    @staticmethod
    def _chunk_ready(packed) -> bool:
        """Non-blocking completion probe for an in-flight packed chunk
        (overlap accounting only — never a sync): True once the device
        has finished producing it. Backends without the probe report
        not-ready, i.e. the pre-readiness-bounded accounting."""
        try:
            return bool(packed.is_ready())
        except AttributeError:
            return False

    @staticmethod
    def _start_fetch(packed) -> None:
        """Begin the packed chunk's device→host copy without blocking, so
        the transfer rides under the next dispatch's device shadow and the
        deferred wait in :meth:`_fetch_chunk` finds the bytes already in
        flight (or landed)."""
        try:
            packed.copy_to_host_async()
        except AttributeError:  # backends without async D2H: fetch blocks
            pass

    def _tables_device(self, tables: np.ndarray):
        """Device copy of the block tables, re-uploaded only on a content
        miss (most chunks allocate no new blocks). LRU-bounded: see
        :class:`_DeviceLru`."""
        return self._tables_dev_cache.get_or_put(
            tables.tobytes(), lambda: jnp.asarray(tables)
        )

    def _sampler_device(self, active_mask: np.ndarray):
        """Device copies of (active mask, temps, topks, topps), re-uploaded
        only on a content miss (4 uploads per burst otherwise) —
        LRU-bounded, so the pipelined loop's finished-slot mask refreshes
        flip between populations without re-uploading each time."""
        raw = (
            active_mask.tobytes() + self._temps.tobytes()
            + self._topks.tobytes() + self._topps.tobytes()
        )
        return self._sampler_dev_cache.get_or_put(
            raw,
            lambda: (
                jnp.asarray(active_mask),
                jnp.asarray(self._temps),
                jnp.asarray(self._topks),
                jnp.asarray(self._topps),
            ),
        )

    async def _decode_burst(
        self, loop, active: list[int], lone_chunk: bool = False
    ) -> None:
        """Depth-2 pipelined chunk decoding (docs/PIPELINE.md): chunk k+1
        is dispatched from chunk k's *device-resident* outputs before k's
        tokens reach the host (the sampler feedback never round-trips),
        the packed fetch is started asynchronously at dispatch, and the
        host's fetch/detokenize/stop-check/emit work for chunk k runs
        under chunk k+1's device shadow — recorded as the sample's
        ``host_overlapped_ms``. Slots that finish inside an in-flight
        chunk are frozen in the device-side active mask from the next
        dispatch on; their over-run tokens are discarded host-side and
        never billed. The burst ends when admission work appears, leaving
        its in-flight chunk pending so the admission prefill dispatches
        under that chunk's shadow (drained identity-filtered afterwards —
        see :meth:`_drain_pending`). It also ends, with nothing pending,
        when every running request's budget ends inside the chunk in
        flight: the next chunk could only compute over-run tokens.

        Light-load regime (active slots <= ``_light_threshold``): the burst
        fuses only ``decode_chunk_light`` steps per dispatch and runs them
        SEQUENTIALLY — no speculative chunk in flight — so an arriving
        request reaches prefill after at most one short chunk instead of
        two long ones. The device idles for one host round-trip between
        chunks, which is free precisely when the engine is under-loaded;
        past the threshold the pipelined big-chunk path takes over. The
        same sequential loop serves penalty bursts and the
        ``pipeline=False`` / ``LS_TPU_PIPELINE=0`` escape hatch — it is
        the reference the pipelined loop's greedy byte-identity is tested
        against.

        ``lone_chunk``: admission stopped at its round's prefill budget
        with slots free and work waiting (:meth:`_admit`), so the burst
        would end after its first chunk anyway. It then dispatches no
        second chunk behind it: the prefills held back run after ONE
        chunk, not two, and the budget bounds the time between a running
        request's tokens."""
        # host spans (flight.SPANS): the loop-thread work before each
        # dispatch is ``ls.decode.prepare``
        with self.flight.span("ls.decode.prepare", active=len(active)):
            key1 = self._split_key()
            active_mask = np.zeros(self.config.slots, dtype=bool)
            active_mask[active] = True
            amask, temps, topks, topps = self._sampler_device(active_mask)
            sampler_mode = self._sampler_mode(
                self._temps[active_mask], self._topks[active_mask],
                self._topps[active_mask],
            )
            light = len(active) <= self._light_threshold()
            K = (
                self.config.decode_chunk_light if light
                else self.config.decode_chunk
            )
            # never fuse far past the longest remaining budget: a 96-step
            # chunk serving 48-token answers burns half its steps on
            # finished slots (and doubles head-of-line latency for queued
            # arrivals). Halving buckets keep the compile-variant count
            # logarithmic.
            max_remaining = 1
            for slot_id in active:
                request = self.slots[slot_id].request
                if request is not None:
                    max_remaining = max(
                        max_remaining,
                        request.max_tokens - len(request.generated),
                    )
            while K >= 2 * max(
                max_remaining, self.config.decode_chunk_light, 1
            ):
                K //= 2
            # presence/frequency penalties: the in-chunk token counts evolve
            # in the scan carry but are NOT returned (the host rebuilds them
            # from request.generated before each dispatch) — so penalty
            # bursts run the SEQUENTIAL path: a pipelined speculative chunk
            # would need the previous chunk's final counts before the host
            # has its tokens
            pen = bool(
                (self._pres[active_mask] != 0).any()
                or (self._freq[active_mask] != 0).any()
            )
            # penalty state snapshotted on the LOOP thread: _admit/
            # _advance_prefills rewrite these arrays between bursts, and the
            # dispatch thread must never re-read engine fields mid-flight
            # (RACE801)
            pres_np = self._pres.copy() if pen else None
            freq_np = self._freq.copy() if pen else None
            # host-tracked longest active sequence: each dispatched chunk
            # grows it by K; the attention window bucket follows
            base_max = int(self._lengths[active].max())

        def _build_counts() -> np.ndarray:
            counts = np.zeros(
                (self.config.slots, self.model_config.vocab_size),
                dtype=np.int32,
            )
            for slot_id in active:
                request = self.slots[slot_id].request
                if request is not None:
                    for t in request.generated:
                        counts[slot_id, t] += 1
            return counts

        def _grow_blocks(pending_chunks: int) -> np.ndarray:
            """Allocate blocks covering this dispatch's chunk plus
            the ``pending_chunks`` dispatched-but-unprocessed chunks whose
            tokens host ``_lengths`` doesn't reflect yet (0 in the
            sequential path — lengths are current at each re-dispatch; 1
            for a pipelined speculative dispatch). Indexing by cumulative
            chunk count instead would over-reserve by one chunk per
            processed chunk and needlessly evict shared prefix-cache
            blocks. Returns a host snapshot of the block tables (the
            dispatch converts it device-side — keeping it numpy here lets
            the lockstep broadcast ship it without a device→host
            round-trip)."""
            self._fault("pool-grow")
            S = self.model_config.max_seq_len
            grown_blocks = grown_slots = 0
            for slot_id in active:
                request = self.slots[slot_id].request
                if request is not None:
                    # the reservation can never need to exceed the request's
                    # own budget: without this cap the pipelined lookahead
                    # (+2K) overshoots into pool exhaustion on the last
                    # chunks — on the r5 chip run that eviction churn cost
                    # more than the pipelining won
                    cap = len(request.prompt_tokens) + request.max_tokens + 1
                    need = min(
                        int(self._lengths[slot_id]) + (pending_chunks + 1) * K,
                        cap, S,
                    )
                    n = self.block_mgr.ensure_capacity(slot_id, need)
                    grown_blocks += n
                    grown_slots += bool(n)
            if grown_blocks:
                self.flight.event(
                    "pool-grow", slots=grown_slots, blocks=grown_blocks,
                    bytes=grown_blocks * self._kv_block_bytes,
                    phase="decode",
                )
            return self.block_mgr.tables.copy()

        def _dispatch(tokens, lengths, key, window, tables, decode_fn,
                      ticket, counts_np=None, first=False, ad_np=None):
            # async JAX dispatch: returns device arrays without blocking.
            # Everything the closure needs (the resolved jit variant, the
            # penalty snapshot, the block tables) was prepared on the loop
            # thread by _submit — the dispatch thread reads no mutable
            # engine fields outside the lockstep protocol branch (RACE801).
            # The thread's span covers everything it does for the dispatch
            # (the followers' broadcast, the uploads, the call into the
            # jitted chunk): what is left under a held span of the loop is
            # the coroutine's wait for its turn
            with self.flight.span(
                "ls.decode.dispatch", **_span_meta(ticket), steps=K
            ):
                if self._lockstep is not None:
                    # runs on the single dispatch thread → broadcast order is
                    # dispatch order. Speculative chunks ("decode_cont") carry
                    # only control (plus the active mask, so a mid-burst
                    # finished-slot freeze reaches followers): followers chain
                    # their own device-resident tokens/lengths outputs, so
                    # nothing syncs to host here.
                    desc: dict[str, Any] = {
                        "op": "decode" if first else "decode_cont",
                        "sampler_mode": list(sampler_mode),
                        "window": window,
                        "k": K,
                        "key": np.asarray(key),
                        "active": active_mask,
                        "tables": tables,  # host snapshot from _grow_blocks
                    }
                    if pen:
                        # penalty bursts are sequential, so every chunk ships
                        # fresh host state (counts are (slots, vocab) — heavy,
                        # but penalties are a per-request opt-in)
                        desc.update(
                            pen=True,
                            pres=pres_np,
                            freq=freq_np,
                            counts=counts_np,
                        )
                    if first:
                        desc.update(
                            tokens=np.asarray(self._current),
                            lengths=np.asarray(self._lengths),
                            temps=np.asarray(self._temps),
                            topks=np.asarray(self._topks),
                            topps=np.asarray(self._topps),
                        )
                    self._lockstep.broadcast(desc)
                self.profiler.on_decode_chunk()
                tables_dev = self._tables_device(tables)
                caches = (self.params, self.cache_k, self.cache_v) + (
                    () if self.state is None else (self.state,)
                )
                args = caches + (
                    tokens, lengths, amask, tables_dev, key, temps, topks, topps,
                )
                if pen:
                    args = args + (
                        jnp.asarray(pres_np), jnp.asarray(freq_np),
                        jnp.asarray(counts_np),
                    )
                # adapter rows ride as kwargs only when the store is enabled:
                # the default engine's trace (and its jaxpr) stays the seed's.
                # _ad_layers is touched only on this (dispatch) thread, so the
                # snapshot here serializes after any in-flight row load.
                ad_kw = (
                    {}
                    if ad_np is None
                    else {"ad_layers": self._ad_layers,
                          "ad_ids": jnp.asarray(ad_np)}
                )
                self.profiler.dump_hlo(
                    f"decode_chunk_w{window}_s{sampler_mode}", decode_fn, *args
                )
                packed, t, l, ck, cv, *st = decode_fn(*args, **ad_kw)
                self.flight.clock.enqueued(
                    ticket["clock"], packed, ticket["dispatch"])
                self.cache_k, self.cache_v = ck, cv
                if st:
                    self.state = st[0]
                # tokens+logprobs were packed INSIDE the decode program
                # (sample-in-program): start their D2H copy now, so by the
                # time the deferred _fetch_chunk wait runs, the transfer has
                # been riding under this dispatch's own device shadow
                self._decode_dispatches += 1
                self._start_fetch(packed)
                ticket["clock"]["returned_t"] = time.monotonic()
                return packed, t, l

        # tickets (program id, dispatch ordinal, steps, slots running) of
        # dispatched-but-unrecorded chunks, FIFO (≤ 2 in flight under the
        # depth-2 pipeline): each fetch pops the oldest so measured device
        # time lands on the variant that ran it
        prog_q: list[dict] = []

        def _submit(tokens, lengths, key, window, pending, first=False):
            """Loop-thread half of a chunk dispatch: resolve the jit
            variant (so the ``_decode_chunk_fns``/``_compiled_shapes``
            bookkeeping never runs on the dispatch thread), rebuild the
            penalty counts from host truth, bump the regime counters,
            then hand the fully-prepared closure to the dispatch thread.
            Returns the executor future — awaited immediately by the
            sequential path, left in flight by the pipelined one."""
            tables = _grow_blocks(pending)
            decode_fn = self._decode_fn(sampler_mode, window, K, pen)
            ticket = self._ticket(
                self._program_decode(window, K, sampler_mode, pen),
                K, len(active),
                self._read_rows(active, pending * K, window),
                self._pool_rows(active, pending * K),
            )
            prog_q.append(ticket)
            counts_np = _build_counts() if pen else None
            # slot→adapter-row mirror snapshotted on the LOOP thread
            # (RACE801): admission rewrites _ad_rows between bursts
            ad_np = self._ad_rows.copy() if self._ad_rows is not None else None
            if light:
                self._light_chunks += 1
            else:
                self._heavy_chunks += 1
            return loop.run_in_executor(
                self._executor,
                partial(_dispatch, tokens, lengths, key, window, tables,
                        decode_fn, ticket, counts_np, first=first,
                        ad_np=ad_np),
            )

        with self.flight.span(
            "ls.decode.prepare", active=len(active), steps=K
        ):
            first_out = _submit(
                jnp.asarray(self._current), jnp.asarray(self._lengths),
                key1, self._read_blocks_for(base_max), 0, first=True,
            )
        out = await first_out
        resumed(prog_q[0]["clock"])
        chunk_index = 0
        if light or pen or not self._pipeline_on:
            # the SEQUENTIAL reference loop (also the light-load / penalty
            # posture): one chunk in flight at a time, burst torn down on
            # any finish — byte-identical greedy output is defined here,
            # and the pipelined loop below is equivalence-tested against it
            while True:
                ticket = prog_q.pop(0)
                chunk_t, chunk_lp, fetch_s = await self._await_chunk(
                    loop, out[0], K, ticket
                )
                gen_before = self.total_generated
                with self.flight.span(
                    "ls.decode.process", seq=ticket["dispatch"],
                    tokens=chunk_t.size,
                ):
                    finished = self._process_chunk(chunk_t, chunk_lp, active)
                    self._flight_record(
                        "decode", device_s=fetch_s,
                        tokens=self.total_generated - gen_before, **ticket,
                    )
                await self._flush_emits(active)
                if self._burst_should_yield(finished):
                    return
                base_max += K
                chunk_index += 1
                # sequential: the chunk just processed is in _lengths, so
                # blocks grow with a fixed one-chunk lookahead
                with self.flight.span(
                    "ls.decode.prepare", active=len(active), steps=K
                ):
                    next_out = _submit(
                        out[1], out[2], self._split_key(),
                        self._read_blocks_for(base_max), 0,
                    )
                out = await next_out
                resumed(prog_q[0]["clock"])

        async def _drain(out, expected, overlapped_s: float = 0.0) -> None:
            """Fetch + apply one dispatched chunk (the burst's tail or an
            all-finished over-run): identity-filtered so tokens never land
            on a request the slot no longer runs."""
            ticket = prog_q.pop(0)
            chunk_t, chunk_lp, fetch_s = await self._await_chunk(
                loop, out[0], K, ticket
            )
            gen_before = self.total_generated
            with self.flight.span(
                "ls.decode.process", seq=ticket["dispatch"],
                tokens=chunk_t.size,
            ):
                self._process_chunk(
                    chunk_t, chunk_lp, active, expected=expected
                )
                self._flight_record(
                    "decode", device_s=fetch_s, overlapped_s=overlapped_s,
                    tokens=self.total_generated - gen_before, **ticket,
                )
            await self._flush_emits(active)

        # the PIPELINED depth-2 loop: chunk N+1 executes on device while
        # the host fetches/processes chunk N under its shadow. Finished
        # slots' block releases are deferred to burst exit — an in-flight
        # chunk commits via the tables captured at its dispatch, and no
        # mid-burst allocation may reuse those blocks under it.
        self._defer_release = True
        finished = False
        try:
            while True:
                if finished:
                    # device-side finished-slot mask: slots that completed
                    # inside chunk N freeze in place from the next dispatch
                    # on (the decode jit holds their token/length wherever
                    # ``active`` is False); their in-flight over-run tokens
                    # are discarded host-side and never billed
                    live = [
                        i for i in active
                        if self.slots[i].request is not None
                    ]
                    if not live:
                        await _drain(out, [None] * len(active))
                        return
                    if len(live) != len(active):
                        active = live
                        active_mask = np.zeros(self.config.slots, dtype=bool)
                        active_mask[active] = True
                        amask, temps, topks, topps = self._sampler_device(
                            active_mask
                        )
                running = [self.slots[i].request for i in active]
                if lone_chunk or all(
                    r is None or r.max_tokens - len(r.generated) <= K
                    for r in running
                ):
                    # every running request ends inside the chunk in flight
                    # (its budget is at most K tokens away): a speculative
                    # chunk behind it would run K steps for tokens that are
                    # all discarded, ahead of whatever prefill is waiting.
                    # Fetch the one in flight and give the loop back.
                    await _drain(out, running)
                    return
                # speculate the next chunk from device state
                base_max += K
                chunk_index += 1
                ticket = prog_q.pop(0)  # of the chunk about to be fetched
                with self.flight.span(
                    "ls.decode.prepare", active=len(active), steps=K
                ):
                    key_next = self._split_key()
                    # pipelined: exactly one dispatched chunk is still
                    # unprocessed when the speculative chunk is dispatched
                    next_out_task = _submit(
                        out[1], out[2], key_next,
                        self._read_blocks_for(base_max), 1,
                    )
                chunk_t, chunk_lp, fetch_s = await self._await_chunk(
                    loop, out[0], K, ticket
                )
                # the dispatch ran before the fetch on the single executor
                # thread, so this await resolves instantly — we just need
                # the in-flight chunk's handle for the readiness probes
                out = await next_out_task
                gen_before = self.total_generated
                # host work from here to the sample runs under chunk N+1's
                # device shadow — but credit it as overlapped only while
                # the device was ACTUALLY still executing (the readiness
                # probes below), or host-heavy workloads would overstate
                # the device share and overlap_ratio could never collapse
                t_overlap = time.monotonic()
                in_flight = not self._chunk_ready(out[0])
                with self.flight.span(
                    "ls.decode.process", seq=ticket["dispatch"],
                    tokens=chunk_t.size,
                ):
                    finished = self._process_chunk(chunk_t, chunk_lp, active)
                await self._flush_emits(active)
                elapsed = time.monotonic() - t_overlap
                if not in_flight:
                    overlapped_s = 0.0  # device finished before we started
                elif not self._chunk_ready(out[0]):
                    overlapped_s = elapsed  # device outlived all our work
                else:
                    overlapped_s = elapsed / 2.0  # finished mid-span
                self._flight_record(
                    "decode", device_s=fetch_s,
                    overlapped_s=overlapped_s,
                    tokens=self.total_generated - gen_before, **ticket,
                )
                if self._burst_should_yield(finished, pipelined=True):
                    if not self._stop:
                        # carry the in-flight chunk across the burst
                        # boundary: the loop runs admission FIRST, so
                        # prefill dispatches interleave under this chunk's
                        # device execution, and _drain_pending applies it
                        # afterwards (identity-filtered per slot)
                        self._pending_chunk = (
                            out, list(active),
                            [self.slots[i].request for i in active], K,
                            prog_q.pop(0),
                        )
                        return
                    # stopping: nothing will drain a pending chunk — do it
                    # inline so the flight timeline stays contiguous
                    await _drain(
                        out, [self.slots[i].request for i in active]
                    )
                    return
        finally:
            self._defer_release = False
            if self._deferred_releases:
                # a finished slot's blocks go back a table column at a time
                # (190 of them behind a 12k-token prompt): between the
                # burst's last emit and the next admission
                with self.flight.span(
                    "ls.release", slots=len(self._deferred_releases)
                ):
                    for slot_id in self._deferred_releases:
                        self.block_mgr.release(slot_id)
                    self._deferred_releases.clear()

    async def _drain_pending(self, loop) -> None:
        """Apply the decode chunk the previous pipelined burst left in
        flight. Runs AFTER admission in the engine loop, so the admission
        batch's prefill was dispatched under this chunk's device shadow
        (the "prefill interleave" overlap). Identity-filtered: a slot that
        finished and was re-admitted between the chunk's dispatch and now
        must not receive the old request's tokens."""
        pending = self._pending_chunk
        if pending is None:
            return
        self._pending_chunk = None
        out, active, expected, k_steps, ticket = pending
        chunk_t, chunk_lp, fetch_s = await self._await_chunk(
            loop, out[0], k_steps, ticket
        )
        gen_before = self.total_generated
        with self.flight.span(
            "ls.decode.process", seq=ticket["dispatch"], tokens=chunk_t.size
        ):
            self._process_chunk(chunk_t, chunk_lp, active, expected=expected)
            self._flight_record(
                "decode", device_s=fetch_s,
                tokens=self.total_generated - gen_before, **ticket,
            )
        await self._flush_emits(active)

    def _release_blocks(self, slot_id: int) -> None:
        """Free a finished slot's block reservation — immediately between
        bursts, DEFERRED to burst exit inside a pipelined burst (the
        in-flight chunk still commits via tables captured at dispatch;
        reusing its blocks mid-burst would land stale K/V on a live
        slot — between bursts the adopting prefill's overwrite makes the
        immediate release safe)."""
        # the slot's device-resident context row is dead with the request:
        # the next occupant re-syncs from host truth
        self._ctx_synced[slot_id] = 0
        if self._defer_release:
            self._deferred_releases.append(slot_id)
        else:
            self.block_mgr.release(slot_id)

    async def _dispatch_prefill(
        self, loop, ticket: dict, mode, tokens, lengths, sel_np, sel,
        temps, topks, topps, ad_np, starts=None, nrb=None,
    ):
        """The first half of a prefill batch: resolve its program (the
        continuation variant when ``starts`` is given) and split its key
        here, then on the dispatch thread tell the followers, upload, call
        the program and re-bind what it donated. Returns the first tokens
        and their logprobs still on the device: nothing here waits for the
        program, so :meth:`_admit` packs and dispatches the next batch
        before :meth:`_fetch_prefill` asks for this one."""
        with self.flight.span("ls.prefill.dispatch", **_span_meta(ticket)):
            fn = (
                self._prefill_fn(mode) if starts is None
                else self._prefill_continue_fn(mode, nrb)
            )
            key = self._split_key()
            self._prefill_dispatches += 1
            self._prefill_dispatches_midpoint += tokens.shape[1] in (
                _prefill_midpoints(self.model_config.max_seq_len))
            self._prefill_dispatches_grouped += (
                self._grouped_rows_over is not None
                and tokens.size > self._grouped_rows_over)

        times = ticket["clock"]

        def _run():
            # the thread's span covers everything it does for the dispatch
            # (the followers' broadcast, the uploads, the call, the
            # re-binding): what is left under the loop's held
            # ``ls.prefill.handoff`` is the coroutine's wait for its turn
            with self.flight.span(
                "ls.prefill.dispatch", **_span_meta(ticket)
            ):
                self._fault("prefill")
                if self._lockstep is not None:
                    desc = {
                        "op": "prefill",
                        "sampler_mode": list(mode),
                        "tokens": tokens,
                        "lengths": lengths,
                        "sel": np.asarray(sel_np),
                        "key": np.asarray(key),
                        "temps": temps,
                        "topks": topks,
                        "topps": topps,
                    }
                    if starts is not None:
                        desc.update(op="prefill_continue", starts=starts, nrb=nrb)
                    self._lockstep.broadcast(desc)
                # the hybrid family's state rides behind the caches, a
                # continuation's starts behind the tokens (never both)
                args = (self.params, self.cache_k, self.cache_v) + (
                    () if self.state is None else (self.state,)
                ) + (jnp.asarray(tokens),) + (
                    () if starts is None else (jnp.asarray(starts),)
                ) + (
                    jnp.asarray(lengths), sel, key, jnp.asarray(temps),
                    jnp.asarray(topks), jnp.asarray(topps),
                )
                # adapter rows of the batch rows; None when the store is
                # disabled keeps the seed trace
                ad_kw = (
                    {}
                    if ad_np is None
                    else {"ad_layers": self._ad_layers,
                          "ad_ids": jnp.asarray(ad_np)}
                )
                variant = f"_cont_nrb{nrb}" if starts is not None else ""
                self.profiler.dump_hlo(
                    f"prefill_p{tokens.shape[1]}_b{tokens.shape[0]}{variant}",
                    fn, *args,
                )
                out = fn(*args, **ad_kw)
                self.flight.clock.enqueued(times, out[0], ticket["dispatch"])
                # the donated caches are re-bound HERE, on the dispatch thread
                # — the same side that reads them in every dispatch closure, so
                # cache_k/cache_v stay single-thread-role (RACE801)
                self.cache_k, self.cache_v = out[2], out[3]
                # the hybrid family's recurrent state is donated with them
                self.state = out[4] if len(out) > 4 else None
                times["returned_t"] = time.monotonic()
            return out[0], out[1]

        # held across the await, as the ``*.fetch`` spans are (flight.py
        # HELD_SPANS): until this coroutine runs again, a device with
        # nothing queued waits for it
        with self.flight.span("ls.prefill.handoff", seq=ticket["dispatch"]):
            out = await loop.run_in_executor(self._executor, _run)
            resumed(times)
        return out

    async def _fetch_prefill(self, loop, ticket: dict, out):
        """The second half: wait on the dispatch thread for a dispatched
        batch's first tokens and logprobs (the caches it returned may
        already be donated to the batch dispatched after it, so only these
        two are waited on). ``device_s`` is this wait: the program's run
        time for a lone batch, what was left of it for a batch that had a
        successor packed and dispatched meanwhile."""

        times = ticket["clock"]

        def _run():
            # the thread's span covers the pending chunk's wait, the
            # batch's own and the two host copies: what is left under the
            # loop's held ``ls.prefill.fetch`` is the coroutine's wait for
            # its turn alone
            with self.flight.span("ls.prefill.wait", seq=ticket["dispatch"]):
                t_dev = time.monotonic()
                # a decode chunk left pending ahead of this batch is the
                # device's first: its completion is seen here, inside the
                # same timed wait (no further sync: a program enqueued
                # before this one ends no later), so that its time is not
                # taken for this program's
                self.flight.clock.settle(times, jax.block_until_ready)
                # the ONE per-dispatch sync, on the dispatch thread and
                # timed (the sample's device_ms); the token/logprob fetch
                # rides the same stop so the loop thread never blocks on
                # the device
                # graftcheck: disable=JAX104 the one per-dispatch sync, moved off-loop and timed
                jax.block_until_ready(out)
                device_s = time.monotonic() - t_dev
                self.flight.clock.ready(times)
                first = np.asarray(out[0]), np.asarray(out[1])
                times["returned_t"] = time.monotonic()
            return first + (device_s,)

        with self.flight.span("ls.prefill.fetch", seq=ticket["dispatch"]):
            fetched = await loop.run_in_executor(self._executor, _run)
            resumed(times)
        return fetched

    async def _advance_prefills(self, loop) -> None:
        """One bounded chunk of progress for every mid-prefill slot, batched
        through the continuation path. Intermediate chunks commit K/V only;
        the FINAL chunk's sampled token (from the prompt's last position) is
        the request's first generated token — the slot then joins decode."""
        # a cancelled caller's prefill stops here: release the slot AND its
        # worst-case block reservation instead of burning the remaining
        # chunks for a dead request (under pool backpressure that
        # reservation is exactly what blocks live admissions)
        for i, s in enumerate(self.slots):
            if s.prefilling and s.request.future.cancelled():
                self._journal_retire(s.request)
                self._adapter_release(s.request)
                s.request = None
                s.prefilling = False
                s.prefill_done = 0
                if self._ad_rows is not None:
                    self._ad_rows[i] = 0
                self.block_mgr.release(i)
        pre = [i for i, s in enumerate(self.slots) if s.prefilling]
        if not pre:
            return
        with self.flight.span(
            "ls.prefill.pack", rows=len(pre),
            bucket=self.config.prefill_chunk,
        ):
            C = self.config.prefill_chunk
            Bp = _pow2(len(pre))
            tokens = np.zeros((Bp, C), dtype=np.int32)
            starts = np.zeros(Bp, dtype=np.int32)
            suffix_lens = np.zeros(Bp, dtype=np.int32)
            slot_ids = np.zeros(Bp, dtype=np.int32)
            temps = np.zeros(Bp, dtype=np.float32)
            topks = np.zeros(Bp, dtype=np.int32)
            topps = np.ones(Bp, dtype=np.float32)
            for i in range(Bp):
                slot_id = pre[min(i, len(pre) - 1)]
                slot = self.slots[slot_id]
                request = slot.request
                chunk = request.context_tokens[
                    slot.prefill_done : slot.prefill_done + C
                ]
                tokens[i, : len(chunk)] = chunk
                starts[i] = slot.prefill_done
                suffix_lens[i] = len(chunk)
                slot_ids[i] = slot_id
                temps[i] = request.temperature
                topks[i] = request.top_k
                topps[i] = request.top_p
        mode = self._sampler_mode(temps, topks, topps)
        nrb = self._read_blocks_for(max(int(starts.max()), 1))
        # the continuation variant re-traces per (rows, chunk, window) shape
        self._note_compile("prefill-continue", (mode, nrb, Bp, C))
        ticket = self._ticket(
            self._program_prefill_continue(nrb, Bp, C, mode), 0,
            sum(1 for s in self.slots if not s.free and not s.prefilling),
        )
        sel_np = self.block_mgr.tables[slot_ids]
        out = await self._dispatch_prefill(
            loop, ticket, mode, tokens, suffix_lens, sel_np,
            jnp.asarray(sel_np), temps, topks, topps,
            # adapter rows for the CHUNK batch rows (loop-thread snapshot,
            # RACE801)
            self._ad_rows[slot_ids].copy()
            if self._ad_rows is not None else None,
            starts=starts, nrb=nrb,
        )
        next_np, logprob_np, device_s = await self._fetch_prefill(
            loop, ticket, out
        )
        with self.flight.span("ls.prefill.emit", rows=len(pre)):
            now = time.monotonic()
            done_slots = []
            for i, slot_id in enumerate(pre):
                slot = self.slots[slot_id]
                request = slot.request
                slot.prefill_done += int(suffix_lens[i])
                if slot.prefill_done >= len(request.context_tokens):
                    self._lengths[slot_id] = len(request.context_tokens)
                    self._current[slot_id] = int(next_np[i])
                    self._temps[slot_id] = request.temperature
                    self._topks[slot_id] = request.top_k
                    self._topps[slot_id] = request.top_p
                    self._pres[slot_id] = request.presence_penalty
                    self._freq[slot_id] = request.frequency_penalty
                    if request.first_token_time is None:
                        # a resumed request keeps its ORIGINAL first-token
                        # time: TTFT measures the client-visible first token
                        request.first_token_time = now
                        self._journey(request, "first-token")
                    slot.prefilling = False
                    # register BEFORE emitting: a max-tokens=1 / instant-EOS
                    # request is released inside _emit_token, and registering
                    # against a released slot's empty table publishes nothing.
                    # Resumed contexts stay out of the prefix cache — their
                    # block chains mix generated content into what looks like
                    # a prompt prefix. Adapter contexts stay out too: their
                    # KV is adapter-colored (docs/ADAPTERS.md).
                    if (
                        self.config.prefix_cache
                        and not request.preemptions
                        and not request.adapter
                    ):
                        self.block_mgr.register_prefix(
                            slot_id, request.prompt_tokens
                        )
                    self._emit_token(
                        slot_id, int(next_np[i]), float(logprob_np[i])
                    )
                    done_slots.append(slot_id)
                    self._m_tokens(1)
            self._flight_record(
                "prefill", device_s=device_s, tokens=len(done_slots),
                ahead=0, **ticket,
            )
        if done_slots:
            await self._flush_emits(done_slots, "ls.prefill.emit")

    # The most prefill, in seconds of the device, that admission dispatches
    # between two decode chunks. A running request waits for every prefill
    # of a round, so without a bound a wave of long prompts (0.15-0.75 s
    # each at 4k-16k tokens) holds every stream for as many seconds as
    # slots were freed, and an empty engine with a backlog decodes nothing
    # until every slot is filled. Seconds and not a count or tokens: a wave
    # of short batches (12 of 16 ms) or of a few long ones (4 of 460 ms)
    # stays whole under every model. The batch in flight and the one
    # dispatched behind it run past the budget.
    _PREFILL_ROUND_S = 2.0
    _prefill_round_s = 0.0  # device seconds of prefill since the last burst
    _admit_cut = False  # the round's admission stopped at the budget

    async def _admit(self, loop) -> None:
        """Admit queued requests in batched prefill calls: a wave at a time
        (:meth:`_admit_select`: every request the free slots, the pool and
        the queue allow, in the scheduler's order), cut into batches by
        prompt-length bucket (``scheduler.plan_wave``: a bucket's requests
        together, in powers of two, so no row of a program is padding).

        With the prefix cache on, each request first matches its
        prompt against cached block chains; matched requests adopt the
        shared blocks and prefill only the SUFFIX (grouped by suffix-length
        bucket, dispatched through the continuation path).

        A wave's batches are dispatched ONE ahead: batch N+1 is packed and
        handed to the device (:meth:`_admit_dispatch`) before batch N's
        first tokens are waited for and emitted (:meth:`_admit_complete`),
        so the device runs N+1 while the host fetches, emits and flushes N
        and packs N+2. Keys are split in dispatch order; the depth is one
        and nothing is in flight when this returns (or raises: what was
        dispatched is completed first, and what was planned and not
        dispatched is back in the queue, so the loop's failure paths find
        every request in a slot or in the scheduler). A wave is matched
        against the prefix cache before its first batch is dispatched
        (docs/PREFIX.md): its requests miss what the wave itself is about
        to publish, as two requests of one batch miss each other; and a
        request that ends at its first token frees its slot for the next
        wave, selected when this one's plan is spent.

        A round's prefills are bounded: once the batches completed since
        the last decode burst have taken ``_PREFILL_ROUND_S`` of the
        device, no further batch is dispatched: what the plan still holds
        returns to the queue's front in arrival order, reservations
        released, and the slots still free are filled after the next chunk
        (``_admit_cut`` tells the burst to make it one)."""
        flying = None  # the batch on the device: (batch, ticket, out, ahead)
        plan: deque = deque()  # the wave's batches not yet dispatched
        try:
            while True:
                nxt = None
                if self._prefill_round_s >= self._PREFILL_ROUND_S:
                    if plan:
                        self.flight.event(
                            "admit-cut", returned=sum(map(len, plan))
                        )
                    self._admit_return(plan)
                    # what ends a burst at its first chunk
                    # (_burst_should_yield): work waiting and a slot free
                    self._admit_cut = not self.scheduler.empty() and any(
                        s.free for s in self.slots
                    )
                else:
                    if not plan:
                        plan.extend(await self._admit_select(loop))
                    if plan:
                        nxt = await self._admit_dispatch(
                            loop, plan.popleft(), flying is not None
                        )
                done, flying = flying, nxt
                if done is not None:
                    await self._admit_complete(loop, *done)
                if nxt is None:
                    return
        except Exception:
            if flying is not None:
                await self._admit_complete(loop, *flying)
            raise
        finally:
            self._admit_return(plan)

    def _admit_return(self, plan) -> None:
        """Give what a wave claimed and did not dispatch back to the
        scheduler's front, in arrival order (slots were handed out in
        that order), with the reservations released. Empties ``plan``, an
        iterable of batches of (slot, request, reuse)."""
        if not plan:
            return
        entries = sorted(
            (entry for batch in plan for entry in batch),
            key=lambda entry: entry[0],
        )
        plan.clear()
        for slot_id, _request, _reuse in entries:
            self.block_mgr.release(slot_id)
        self.scheduler.give_back([request for _s, request, _r in entries])

    async def _admit_select(self, loop) -> list:
        """Select and claim a wave, and plan its batches: the requests the
        scheduler yields until the free slots, the pool's reservations or
        the queue run out, each popped with its slot and its reservation
        (and its matched prefix adopted), then cut into prefill batches of
        (slot, request, reuse) by ``plan_wave``. Empty when the queue, the
        free slots or the pool yield none."""
        wave: list[tuple[int, _Request, int]] = []  # (slot, req, reuse)
        try:
            await self._admit_candidates(loop, wave)
        except BaseException:
            # popped and reserved but in no slot: invisible to every
            # failure path (the shrink sweep and _fail_inflight walk slots)
            self._admit_return([wave])
            raise
        # what decides a candidate's program: its bucket, and whether it
        # continues an adopted prefix (plain rows stay on the flash path)
        programs = [
            (self._prefill_bucket(request, reuse), reuse > 0)
            for _slot, request, reuse in wave
        ]
        classes = (
            [request.priority for _slot, request, _reuse in wave]
            if self.scheduler.depths() is not None else None
        )
        return [
            [wave[i] for i in batch]
            for batch in plan_wave(
                programs, len(wave), self.config.prefill_batch, classes
            )
        ]

    def _prefill_bucket(self, request, reuse: int) -> int:
        return _prefill_bucket_rows(
            len(request.context_tokens) - reuse,
            hi=self.model_config.max_seq_len,
        )

    async def _admit_candidates(self, loop, wave: list) -> None:
        """The selection pass of :meth:`_admit_select`: everything per
        candidate, in the scheduler's order, until the wave ends at the
        first candidate the pool cannot admit (nobody is admitted past a
        request that waits for blocks). Appends to ``wave``, so that what
        was claimed is known to the caller when this raises."""
        use_prefix = self.config.prefix_cache
        free = [i for i, s in enumerate(self.slots) if s.free]
        while not self.scheduler.empty() and len(wave) < len(free):
            # ``ls.admit`` spans the synchronous stretches of this
            # pass; its two awaits (adapter resolve, prefix promotion)
            # run outside any span
            with self.flight.span("ls.admit"):
                # the scheduler names the next admission candidate (FIFO
                # head by default; the WDRR-selected class head under QoS)
                request = self.scheduler.peek()
                if request is None:
                    break
                if request.future.cancelled():
                    self.scheduler.pop()  # caller gave up while queued
                    # the caller walked away — answered by cancellation,
                    # so a restart must not replay it
                    self._journal_retire(request)
                    continue
                if request.deadline is not None:
                    # deadline gate (docs/RESILIENCE.md): shed BEFORE
                    # any device work when the remaining budget cannot
                    # cover the admission estimate — an explicit
                    # 504-shaped refusal beats a silent late completion
                    left = remaining_s(request.deadline)
                    estimate = self._admit_estimate_s()
                    if left <= estimate:
                        self.scheduler.pop()
                        err = self._note_deadline_shed(
                            request, "admission", left, estimate
                        )
                        self._journal_retire(request)
                        if not request.future.done():
                            request.future.set_exception(err)
                        continue
            if self.adapter_store is not None and request.adapter:
                # multi-LoRA resolve (docs/ADAPTERS.md): the request
                # admits only once its adapter holds a device row.
                # "wait" stashed it off-scheduler (like the prefix
                # hydration stash), "refused" failed it loudly —
                # both popped it, so the pass moves on.
                verdict = await self._resolve_adapter(loop, request)
                if verdict == "backpressure":
                    # every T0 row pinned by in-flight requests;
                    # finishing slots release pins — retry next pass
                    break
                if verdict != "ready":
                    continue
            with self.flight.span("ls.admit"):
                # one chain-digest walk per admission attempt, shared by
                # the hydration check, the promotion, and match_prefix
                # below — the admission path hashes the prompt ONCE
                chain = (
                    self.block_mgr.chain_digests(request.context_tokens)
                    if self.prefix_store is not None
                    and use_prefix
                    and not request.preemptions
                    and not request.adapter
                    else None
                )
                if (
                    chain is not None
                    and not request.hydrate_attempted
                    and not self._draining
                ):
                    # tiered prefix store: when the prompt's chain
                    # extends into T2 (object storage), stash the
                    # request OFF the queue while the background
                    # hydrator pulls the blobs into T1 — it requeues at
                    # class front the moment they land (or the timeout
                    # falls it back to cold compute). Never head-blocks:
                    # the loop moves on to the next admission candidate.
                    request.hydrate_attempted = True
                    missing = self._chain_t2_candidates(chain)
                    if missing and self.prefix_store.request_hydration(
                        missing
                    ):
                        self.scheduler.pop()
                        deadline = (
                            time.monotonic()
                            + self.prefix_store.spec.hydrate_timeout_s
                        )
                        self._prefix_hydrating.append(
                            (request, deadline, missing)
                        )
                        self.flight.event(
                            "prefix-hydrate", stage="begin",
                            blocks=len(missing),
                        )
                        self._journey(
                            request, "hydrate-begin", blocks=len(missing)
                        )
                        continue
                if not self.block_mgr.can_admit(
                    len(request.prompt_tokens) + request.max_tokens + 1
                ):
                    # pool backpressure: the worst case doesn't fit the
                    # pool right now; finished slots will free reservations.
                    # (Requests that could NEVER fit are rejected up front in
                    # generate(), so this always unblocks eventually. The
                    # QoS loop may also preempt a lower-class victim to
                    # unblock this head — see _maybe_preempt.)
                    break
            # a resumed request's prefill content is its full context
            # (prompt + generated so far), rebuilding the KV state the
            # preemption dropped; untouched requests see ctx == prompt
            ctx = request.context_tokens
            # adapter requests bypass the shared prefix plane both
            # ways: their KV is colored by the adapter's attention
            # projections, so reusing a base/other-adapter chain
            # would splice foreign KV under this request — and
            # registering theirs would poison adapter-less traffic
            # (docs/ADAPTERS.md)
            shared = (
                use_prefix and not request.preemptions
                and not request.adapter
            )
            if shared and chain is not None:
                # promote the T1 run extending this prompt's T0
                # chain back into pool blocks, so the match
                # below sees the longer chain (docs/PREFIX.md)
                await self._promote_prefix(loop, request, chain)
            with self.flight.span("ls.admit"):
                if shared:
                    blocks, reuse = self.block_mgr.match_prefix(
                        ctx, digests=chain
                    )
                    if (
                        reuse
                        and len(ctx) - reuse
                        > self.config.prefix_cache_max_suffix
                    ):
                        # long suffix, small saving: the flash/ring full
                        # prefill beats the XLA continuation path
                        blocks, reuse = [], 0
                else:
                    blocks, reuse = [], 0
                to_prefill = len(ctx) - reuse
                if (
                    self.config.prefill_chunk > 0
                    and to_prefill > self.config.prefill_chunk
                ):
                    # chunked prefill: claim the slot + reservation now, but
                    # feed the prompt through _advance_prefills one bounded
                    # chunk per loop pass instead of one monolithic prefill
                    slot_id = free.pop(len(wave))
                    self.scheduler.pop()
                    self.block_mgr.admit(
                        slot_id,
                        len(request.prompt_tokens) + request.max_tokens + 1,
                    )
                    if blocks:
                        self.block_mgr.adopt_prefix(slot_id, blocks)
                    slot = self.slots[slot_id]
                    # slot claimed BEFORE the physical grow: an allocator
                    # failure below is then recoverable (a popped request
                    # in no slot would be invisible to every failure
                    # path). The chunked claim must undo ITSELF on a
                    # grow failure: a prefilling slot whose table never
                    # grew would scatter its chunks into the scratch
                    # block (silent corruption), and the shrink sweep
                    # deliberately leaves prefilling slots alone —
                    # requeue (or shed past the retry cap) HERE, then
                    # re-raise so the loop's shrink pass still adapts.
                    slot.request = request
                    slot.prefilling = True
                    slot.prefill_done = reuse
                    if self._ad_rows is not None:
                        self._ad_rows[slot_id] = request.adapter_row
                    try:
                        self._fault("pool-grow")
                        self.block_mgr.ensure_capacity(slot_id, len(ctx))
                    except Exception as e:
                        # (the wave's monolithic members, popped and not
                        # yet slotted, are returned by _admit_select, to
                        # the front of this one)
                        if not self._resource_exhausted(e):
                            raise
                        if request.preemptions >= _SHRINK_RETRY_CAP:
                            self._shed_stranded(slot_id, e)
                            self._shrink_inline_shed += 1
                        else:
                            self._preempt_slot(
                                slot_id, reason="pool-shrink"
                            )
                            self._shrink_inline_preempted += 1
                        raise
                    request.admit_time = time.monotonic()
                    self._note_resume(request)
                    self._journey(request, "admit", chunked=True)
                    if reuse:
                        self.prefix_hits += 1
                        self.prefix_tokens += reuse
                        self._m_prefix_hits(1)
                        self._m_prefix_tokens(reuse)
                    continue
                slot_id = free[len(wave)]
                self.scheduler.pop()
                # reserve at pop time so the NEXT peek's can_admit sees
                # this wave member's reservation
                self.block_mgr.admit(
                    slot_id, len(request.prompt_tokens) + request.max_tokens + 1
                )
                if blocks:
                    self.block_mgr.adopt_prefix(slot_id, blocks)
                wave.append((slot_id, request, reuse))

    async def _admit_dispatch(self, loop, batch, ahead: bool):
        """Slot, pack and dispatch one planned prefill batch. ``ahead``
        says its predecessor is still unfetched (the flight sample's
        ``ahead``)."""
        bucket = self._prefill_bucket(batch[0][1], batch[0][2])
        with self.flight.span(
            "ls.admit", queued=self.scheduler.qsize(),
            admitted=len(batch),
        ):
            admit_now = time.monotonic()
            for slot_id, request, _reuse in batch:
                self.slots[slot_id].request = request
                if self._ad_rows is not None:
                    self._ad_rows[slot_id] = request.adapter_row
                request.admit_time = admit_now
                self._note_resume(request)
                self._journey(request, "admit")
            # physical grows AFTER every batch member owns its slot: an
            # allocator failure here is then recoverable by the shrink
            # pass's preempt-and-requeue sweep (a popped request in no
            # slot would be invisible to every failure path)
            self._fault("pool-grow")
            for slot_id, request, _reuse in batch:
                self.block_mgr.ensure_capacity(
                    slot_id, len(request.context_tokens)
                )
        with self.flight.span(
            "ls.prefill.pack", rows=len(batch), bucket=bucket
        ):
            Bp = _pow2(len(batch))
            use_continue = any(r > 0 for _, _, r in batch)
            padded = np.zeros((Bp, bucket), dtype=np.int32)
            lengths = np.zeros(Bp, dtype=np.int32)
            starts = np.zeros(Bp, dtype=np.int32)
            slot_ids = np.zeros(Bp, dtype=np.int32)
            temps = np.zeros(Bp, dtype=np.float32)
            topks = np.zeros(Bp, dtype=np.int32)
            topps = np.ones(Bp, dtype=np.float32)
            for i in range(Bp):
                slot_id, request, reuse = batch[min(i, len(batch) - 1)]
                suffix = request.context_tokens[reuse:]
                padded[i, : len(suffix)] = suffix
                lengths[i] = len(suffix)
                starts[i] = reuse
                slot_ids[i] = slot_id
                temps[i] = request.temperature
                topks[i] = request.top_k
                topps[i] = request.top_p
            prefill_mode = self._sampler_mode(temps, topks, topps)
            # per-batch-row adapter rows (loop-thread snapshot, RACE801)
            ad_np = (
                self._ad_rows[slot_ids].copy()
                if self._ad_rows is not None else None
            )

            # per-batch-row block tables (duplicate padded rows write
            # identical values to identical blocks — harmless)
            sel_np = self.block_mgr.tables[slot_ids]
            sel = jnp.asarray(sel_np)
            if self._fam is not None and self._fam.prefill_selects_slots:
                # the recurrent state's rows are the slots' own
                sel = (sel, jnp.asarray(slot_ids))
            if use_continue:
                nrb = self._read_blocks_for(int(starts.max()))
                self._note_compile(
                    "prefill-continue", (prefill_mode, nrb, Bp, bucket)
                )
                program = self._program_prefill_continue(
                    nrb, Bp, bucket, prefill_mode
                )
            else:
                # same Python variant, fresh XLA program per (bucket, rows)
                self._note_compile("prefill", (prefill_mode, bucket, Bp))
                program = self._program_prefill(bucket, Bp, prefill_mode)
        ticket = self._ticket(
            program, 0,
            sum(1 for s in self.slots if not s.free and not s.prefilling)
            - len(batch),
        )
        ticket["bucket"] = bucket
        out = await self._dispatch_prefill(
            loop, ticket, prefill_mode, padded, lengths, sel_np, sel,
            temps, topks, topps, ad_np,
            starts=starts if use_continue else None,
            nrb=nrb if use_continue else None,
        )
        return batch, ticket, out, int(ahead)

    async def _admit_complete(self, loop, batch, ticket, out, ahead) -> None:
        """Wait for a dispatched batch's first tokens, publish its
        prefixes, set its slots' host state and emit."""
        next_np, logprob_np, device_s = await self._fetch_prefill(
            loop, ticket, out
        )
        with self.flight.span("ls.prefill.emit", rows=len(batch)):
            if self.config.prefix_cache:
                for slot_id, request, reuse in batch:
                    if request.preemptions or request.adapter:
                        # resumed contexts stay out of the prefix cache
                        # (generated content is not a shareable prompt);
                        # adapter contexts too — their KV is colored by
                        # the adapter's projections (docs/ADAPTERS.md)
                        continue
                    self.block_mgr.register_prefix(
                        slot_id, request.prompt_tokens
                    )
                    if reuse:
                        self.prefix_hits += 1
                        self.prefix_tokens += reuse
                        self._m_prefix_hits(1)
                        self._m_prefix_tokens(reuse)
            now = time.monotonic()
            admitted_slots = []
            for i, (slot_id, request, _reuse) in enumerate(batch):
                self._lengths[slot_id] = len(request.context_tokens)
                self._current[slot_id] = int(next_np[i])
                self._temps[slot_id] = request.temperature
                self._topks[slot_id] = request.top_k
                self._topps[slot_id] = request.top_p
                self._pres[slot_id] = request.presence_penalty
                self._freq[slot_id] = request.frequency_penalty
                if request.first_token_time is None:
                    request.first_token_time = now
                    self._journey(request, "first-token")
                self._emit_token(slot_id, int(next_np[i]), float(logprob_np[i]))
                admitted_slots.append(slot_id)
            self._m_tokens(len(batch))
            self._prefill_round_s += device_s
            self._flight_record(
                "prefill", device_s=device_s, tokens=len(batch),
                ahead=ahead, **ticket,
                prompt_tokens=sum(
                    len(request.context_tokens) - reuse
                    for _slot, request, reuse in batch
                ),
            )
        await self._flush_emits(admitted_slots, "ls.prefill.emit")

    def _process_chunk(
        self,
        chunk_tokens: np.ndarray,
        chunk_lps: np.ndarray,
        active: list[int],
        expected: list | None = None,
    ) -> bool:
        """Apply a chunk's tokens to host state; queue emissions. Returns
        True if any slot finished (→ admission opportunity).

        ``expected`` (the pipelined drain path) pins each slot to the
        request it ran when the chunk was dispatched: a slot re-admitted
        in between (the prefill-interleave window) silently drops the old
        request's over-run tokens instead of corrupting the new one."""
        K = chunk_tokens.shape[0]
        finished_any = False
        emitted_before = self.total_generated
        eos = self.tokenizer.eos_id
        for pos, slot_id in enumerate(active):
            slot = self.slots[slot_id]
            request = slot.request
            if request is None:
                continue
            if expected is not None and request is not expected[pos]:
                continue
            if (
                request.stop
                or request.on_token is not None
                or request.on_chunk is not None
                or request.future.cancelled()
            ):
                # slow path: per-token semantics (stop-string windows,
                # stream emissions, cancellation checks)
                for k in range(K):
                    if slot.request is None:
                        break  # finished mid-chunk; discard the tail
                    self._lengths[slot_id] += 1
                    token = int(chunk_tokens[k, slot_id])
                    self._current[slot_id] = token
                    if self._emit_token(
                        slot_id, token, float(chunk_lps[k, slot_id])
                    ):
                        finished_any = True
                continue
            # fast path — the saturated-decode hot loop: one numpy pass per
            # slot instead of K Python iterations (at 64 slots x 96 steps
            # the per-token loop costs hundreds of ms per chunk on the
            # single-threaded engine, rivaling the device time itself).
            # Exact same semantics as _emit_token for this request shape:
            # consume until eos / max-tokens / context-window, then finish.
            toks = chunk_tokens[:, slot_id]
            lengths0 = int(self._lengths[slot_id])
            # consuming the t-th token (1-based): finishes at t == remaining
            # (budget) or t == max_seq cap (window), whichever first
            fin_at = min(
                request.max_tokens - len(request.generated),
                self.model_config.max_seq_len - 1 - lengths0,
            )
            upto = min(K, max(fin_at, 0))
            eos_hits = np.nonzero(toks[:upto] == eos)[0]
            if eos_hits.size:
                consumed = int(eos_hits[0]) + 1
                n_gen = consumed - 1  # the eos token itself is not emitted
                done = True
            else:
                consumed = upto
                n_gen = consumed
                done = consumed == fin_at
            if consumed:
                request.generated.extend(toks[:n_gen].tolist())
                request.logprobs.extend(
                    chunk_lps[:n_gen, slot_id].tolist()
                )
                self.total_generated += consumed
                self._lengths[slot_id] += consumed
                self._current[slot_id] = int(toks[consumed - 1])
            if done:
                finished_any = True
                slot.request = None
                slot.prefilling = False
                slot.prefill_done = 0
                self._lengths[slot_id] = 0
                self._adapter_release(request)
                if self._ad_rows is not None:
                    self._ad_rows[slot_id] = 0
                self._release_blocks(slot_id)
                self._finished_requests.append(
                    (request, bool(eos_hits.size))
                )
        # one prometheus update per chunk, not per token (host hot path)
        self._m_tokens(self.total_generated - emitted_before)
        return finished_any

    def _emit_token(self, slot_id: int, token: int, logprob: float) -> bool:
        """Synchronous part of emission; async callbacks are deferred to
        :meth:`_flush_emits`. Returns True when the slot finished."""
        slot = self.slots[slot_id]
        request = slot.request
        if request is None:
            return False
        is_eos = token == self.tokenizer.eos_id
        if not is_eos:
            request.generated.append(token)
            request.logprobs.append(logprob)
        stop_matched = False
        if request.stop and not is_eos:
            # decode only a tail WINDOW per token — a full re-decode would
            # be O(n^2) per request on the single-threaded emit hot path.
            # Any new match must involve the newest token; every token
            # decodes from at least one UTF-8 byte, so a window of
            # max-stop-BYTES tokens (plus margin for tokenizer boundary
            # effects) always covers it — char count would undersize the
            # window for multi-byte stop strings under the byte-level
            # tokenizer (1 token per byte) and silently miss the stop. The
            # authoritative truncation re-finds on the full final decode in
            # _flush_emits.
            window = max(len(s.encode("utf-8")) for s in request.stop) + 8
            tail = self.tokenizer.decode(request.generated[-window:])
            if any(s in tail for s in request.stop):
                request.stop_matched = True
                stop_matched = True
        self.total_generated += 1
        done = bool(
            is_eos
            or stop_matched
            or len(request.generated) >= request.max_tokens
            or self._lengths[slot_id] + 1 >= self.model_config.max_seq_len
            # caller gave up (client disconnect / task cancel): stop
            # burning the slot on tokens nobody will read
            or request.future.cancelled()
        )
        # streaming consumers always get a final last=True emission (the
        # tokenizer hides the EOS id itself), so chunk streams terminate
        if request.on_token is not None or request.on_chunk is not None:
            self._pending_emits.append((request, token, logprob, done))
        if done:
            slot.request = None
            slot.prefilling = False
            slot.prefill_done = 0
            self._lengths[slot_id] = 0
            self._adapter_release(request)
            if self._ad_rows is not None:
                self._ad_rows[slot_id] = 0
            # release is safe while a speculative chunk is in flight (it
            # writes via the tables captured at its dispatch, and those
            # writes land before any re-allocation's prefill — single
            # executor thread); INSIDE a pipelined burst the release is
            # deferred to burst exit instead (see _release_blocks)
            self._release_blocks(slot_id)
            self._finished_requests.append((request, is_eos))
        return done

    def _final_text(self, request: _Request) -> str:
        """The authoritative completion text: full decode, truncated at
        the earliest stop match (OpenAI semantics — the match itself
        excluded). One helper so the finish path and the streaming final
        chunk produce byte-identical text."""
        text = self.tokenizer.decode(request.generated)
        if request.stop_matched:
            hits = [
                i for i in (text.find(s) for s in request.stop) if i >= 0
            ]
            if hits:
                text = text[: min(hits)]
        return text

    def _stream_text(self, request: _Request, is_final: bool) -> str:
        """The stream-safe decoded prefix of the generated text. Final →
        :meth:`_final_text` (so chunk deltas concatenate byte-identically
        to the non-streaming completion). Mid-stream → the full decode
        minus a trailing UTF-8 partial (the replacement char a cut
        multi-byte sequence renders as) and minus any tail that could
        still grow into a stop match — the same holdback contract the
        agents' _StreamAdapter keeps per token, applied per chunk."""
        if is_final:
            return self._final_text(request)
        text = self.tokenizer.decode(request.generated)
        if text.endswith("�"):
            text = text[:-1]
        if request.stop:
            hits = [
                i for i in (text.find(s) for s in request.stop) if i >= 0
            ]
            if hits:
                return text[: min(hits)]
            hold = 0
            for s in request.stop:
                for k in range(min(len(s) - 1, len(text)), 0, -1):
                    if s.startswith(text[-k:]):
                        hold = max(hold, k)
                        break
            if hold:
                text = text[: len(text) - hold]
        return text

    def _stream_tbt_hist(self, cls_name: str):
        """Per-QoS-class ``tbt_seconds`` histogram closure
        (``langstream_stream_tbt_seconds{agent_id="<class>"}`` — the
        class rides the reporter's agent_id label, the gateway's
        _count_throttle pattern). Lazily created on a class's first
        measured interval; class names are clamped to the QoS vocabulary
        so the map stays bounded. Streaming-configured engines only —
        the default scrape surface never grows."""
        h = self._m_tbt_hist.get(cls_name)
        if h is None:
            h = PrometheusMetricsReporter(
                prefix="langstream_stream", agent_id=cls_name
            ).exemplar_histogram(
                "tbt_seconds",
                "streaming inter-chunk interval (time between token "
                "deliveries) by QoS class",
            )
            self._m_tbt_hist[cls_name] = h
        return h

    def _stream_stall_threshold(self, cls_name: str) -> float:
        """The stall line for one class: its declared tbt-p99-s target
        when it has one, the engine-wide stream-stall-s default
        otherwise."""
        if self.config.qos is not None:
            tbt = self.config.qos.class_policy(cls_name).tbt_p99_s
            if tbt is not None:
                return tbt
        return self.config.stream_stall_s

    async def _deliver_chunk(
        self, request: _Request, is_final: bool, now: float,
        span: str = "ls.decode.emit",
    ) -> None:
        """Deliver one committed decode chunk to the request's on_chunk
        consumer and record its telemetry. Runs at the burst-flush safe
        point between device dispatches — wait-free apart from awaiting
        the consumer itself (graftcheck STRM1501 polices this body the
        way OBS503 polices the emit hot loop)."""
        # everything but the consumer's own coroutine
        with self.flight.span(span):
            if request.stream_closed:
                return
            if request.future.cancelled():
                # the client is gone — deliver nothing; the finished drain
                # records the stream-cancel evidence below
                request.stream_closed = True
                return
            safe = self._stream_text(request, is_final)
            delta = safe[request.stream_sent_chars:]
            new_ids = request.generated[request.stream_sent_tokens:]
            if not delta and not new_ids and not is_final:
                return  # the holdback ate the whole chunk; nothing surfaced
            request.stream_sent_chars = max(
                request.stream_sent_chars, len(safe)
            )
            request.stream_sent_tokens = len(request.generated)
            if request.stream_tbt is not None:
                if request.stream_first_emit is None:
                    request.stream_first_emit = now
                    self._journey(request, "first-emit")
                else:
                    interval = now - (request.stream_last_emit or now)
                    request.stream_tbt.add(interval)
                    digest = self._stream_tbt_by_class.get(request.priority)
                    if digest is None:
                        digest = TbtDigest()
                        self._stream_tbt_by_class[request.priority] = digest
                    digest.add(interval)
                    self._stream_tbt_hist(request.priority)(
                        interval,
                        request.journey_id
                        if request.trace is not None
                        else None,
                    )
                    threshold = self._stream_stall_threshold(request.priority)
                    if interval > threshold:
                        request.stream_stalls += 1
                        self.stream_stalls_total += 1
                        self.flight.event(
                            "stream-stall",
                            request=request.journey_id,
                            interval_s=round(interval, 6),
                            threshold_s=threshold,
                            priority=request.priority,
                            tokens=len(request.generated),
                        )
                request.stream_last_emit = now
                request.stream_emits += 1
                self.stream_emits_total += 1
            if is_final:
                request.stream_closed = True
                if request.stream_tbt is not None:
                    # ONE summarized event per stream, never one per chunk
                    # (a 4k-token stream would otherwise flood the ring)
                    summary = request.stream_tbt.summary()
                    self.flight.event(
                        "stream-emit",
                        request=request.journey_id,
                        emits=request.stream_emits,
                        tokens=len(request.generated),
                        tbt_p50_s=summary["p50"],
                        tbt_p99_s=summary["p99"],
                        tbt_max_s=summary["max"],
                        stalls=request.stream_stalls,
                        priority=request.priority,
                    )
                    self._journey(
                        request, "last-emit", emits=request.stream_emits
                    )
            result = request.on_chunk(new_ids, delta, is_final)
        if asyncio.iscoroutine(result):
            # the consumer's own coroutine: the one hop span held across an
            # await (what runs inside it opens ``ls.hop.agent``, ``.topic``)
            with self.flight.span("ls.hop.deliver"):
                await result

    async def _flush_emits(
        self, active: list[int], span: str = "ls.decode.emit"
    ) -> None:
        """Deliver what the burst committed and settle finished requests.
        ``span`` names the host spans around the synchronous parts (a
        prefill's first tokens pass ``ls.prefill.emit``); the consumers'
        own coroutines run under ``ls.hop.deliver`` (one span a flush for
        the per-token subscribers, one a request for the per-chunk ones)."""
        emits, self._pending_emits = self._pending_emits, []
        # per-request chunk grouping, first-appearance order: on_token
        # subscribers keep exact per-token delivery; on_chunk subscribers
        # get ONE delivery per request per flush with everything that
        # committed in this burst
        chunks: "OrderedDict[int, list]" = OrderedDict()
        per_token = []
        with self.flight.span(span, tokens=len(emits)):
            for emit in emits:
                request, done = emit[0], emit[3]
                if request.on_token is not None:
                    per_token.append(emit)
                if request.on_chunk is not None:
                    entry = chunks.get(id(request))
                    if entry is None:
                        chunks[id(request)] = [request, done]
                    elif done:
                        entry[1] = True
        if per_token:
            with self.flight.span("ls.hop.deliver", frames=len(per_token)):
                for request, token, logprob, done in per_token:
                    result = request.on_token(token, logprob, done)
                    if asyncio.iscoroutine(result):
                        await result
        if chunks:
            # one clock per flush: chunk emission is the granularity the
            # client observes, so inter-EMIT gaps are what TBT digests
            now = time.monotonic()
            for request, done in chunks.values():
                await self._deliver_chunk(request, done, now, span)
        with self.flight.span(span, frames=len(chunks)):
            # decode-pool first-step edge: the first NEW token after a KV
            # import closes the decode-admission segment (the emits list
            # above only carries on_token subscribers; imported handoffs
            # stream nothing, so the finished/slot scan below is the spot
            # that sees every request). One attribute check per emit batch.
            for slot in self.slots:
                request = slot.request
                if (
                    request is not None
                    and request.imported
                    and not request.first_step_noted
                    and len(request.generated) > request.import_base_tokens
                ):
                    request.first_step_noted = True
                    self._journey(request, "first-step")
            finished, self._finished_requests = self._finished_requests, []
            for request, is_eos in finished:
                # tenant tokens/s accounting (QoS post-debit): cancelled
                # requests debit too — their tokens burned engine capacity
                self.scheduler.on_finished(request)
                # crash-requeue journal: the request is ANSWERED (result,
                # cancellation — either way nothing is left to replay)
                self._journal_retire(request)
                if request.imported and not request.first_step_noted:
                    # finished inside its first emit batch: the slot is
                    # already released, so the scan above never saw it
                    request.first_step_noted = True
                    self._journey(request, "first-step")
                if request.future.cancelled():
                    # aborted by the caller: not a served request — keep it out
                    # of the request-rate/TTFT metrics (a disconnect storm must
                    # not read as healthy throughput) and skip the decode
                    if request.on_chunk is not None and self.config.streaming:
                        # disconnect-as-cancellation evidence: the slot was
                        # freed in _emit_token's done branch, i.e. within one
                        # chunk boundary of the cancel landing. tokens_wasted
                        # is the decode work nobody consumed (generated but
                        # never delivered — the engine-visible waste).
                        self.stream_cancels_total += 1
                        self.stream_reclaims_total += 1
                        self.flight.event(
                            "stream-cancel",
                            request=request.journey_id,
                            tokens_generated=len(request.generated),
                            tokens_delivered=request.stream_sent_tokens,
                            tokens_wasted=(
                                len(request.generated)
                                - request.stream_sent_tokens
                            ),
                            emits=request.stream_emits,
                            priority=request.priority,
                            tenant=request.tenant,
                            slot_reclaimed=True,
                        )
                    self._journey(request, "cancelled")
                    continue
                self.completed_requests += 1
                self._m_requests()
                if request.first_token_time is not None:
                    self._m_ttft(request.first_token_time - request.enqueue_time)
                # OpenAI semantics: the stop match itself is excluded. The
                # token list keeps every generated token (they are in the
                # KV cache and were streamed); only the text truncates. The
                # find runs on the FINAL decode (the detection window can
                # render boundary chars differently) — shared with the
                # streaming final chunk so deltas concatenate to this exact
                # string.
                text = self._final_text(request)
                done_t = time.monotonic()
                first = request.first_token_time or done_t
                admit = request.admit_time or first
                if request.deadline is not None:
                    # the deadline acceptance's second half: a request that
                    # completes PAST its budget still answers (the work is
                    # done; discarding it helps nobody) but the overrun is
                    # recorded — never a silent late completion
                    overrun = time.time() - request.deadline  # graftcheck: disable=OBS501 deadline overrun compares epoch stamps, not a latency
                    if overrun > 0:
                        self.deadline_overruns += 1
                        self.flight.event(
                            "deadline-overrun",
                            overrun_s=round(overrun, 6),
                            tokens=len(request.generated),
                            tenant=request.tenant,
                        )
                        self._journey(
                            request, "deadline-overrun",
                            overrun_s=round(overrun, 6),
                        )
                timing = {
                    "queue_wait": admit - request.enqueue_time,
                    "prefill": first - admit,
                    "ttft": first - request.enqueue_time,
                    # decode phase + its step count: the bench derives achieved
                    # step time from these (EOS can end a request well before
                    # max_tokens, so the client can't know the step count)
                    "decode": done_t - first,
                    "tokens": float(len(request.generated)),
                }
                if request.imported:
                    # KV-import admission skipped prefill entirely: the
                    # marker the disagg acceptance asserts on (queue_wait/
                    # prefill here are decode-pod-local and ~0 by design —
                    # the prefill pool's share rode the handoff header)
                    timing["imported"] = 1.0
                if request.stream_tbt is not None and request.stream_tbt.count:
                    # bounded TBT record (p50/p99/max + count, NEVER the raw
                    # interval list): what the gateway bench and perf_diff
                    # read off request_timings
                    summary = request.stream_tbt.summary()
                    timing["tbt_p50"] = summary["p50"]
                    timing["tbt_p99"] = summary["p99"]
                    timing["tbt_max"] = summary["max"]
                    timing["tbt_count"] = float(summary["count"])
                if not request.warmup:
                    # warmup probes never enter the latency record: their TTFT
                    # is XLA compile time, which would poison both the
                    # cumulative histograms and the bench's request_timings
                    # decomposition (a warmup_on_start engine created lazily
                    # inside the measured window)
                    self.request_timings.append(timing)
                    # exemplar: a traced request's journey id rides the TTFT
                    # bucket it lands in (None for untraced traffic — the
                    # default scrape stays byte-identical)
                    self._m_ttft_hist(
                        timing["ttft"],
                        request.journey_id
                        if request.trace is not None
                        else None,
                    )
                    self._m_queue_wait_hist(timing["queue_wait"])
                    # SLO evidence (no-ops without a declared objective): a
                    # served request is availability-good, and the tracker
                    # judges the measured latencies against the declared
                    # thresholds
                    self._slo_record("availability", True)
                    self._slo_record_latency("ttft", timing["ttft"])
                    self._slo_record_latency("queue-wait", timing["queue_wait"])
                    if (
                        request.stream_tbt is not None
                        and request.stream_tbt.count
                    ):
                        # one tbt event per finished stream: the request's
                        # own p99 inter-emit interval, judged against (a)
                        # the engine-wide slo.tbt objective when declared
                        # and (b) the class's tbt-p99-s burn tracker — the
                        # health() tbt_burn predicate reads the latter
                        p99 = request.stream_tbt.quantile(0.99)
                        self._slo_record_latency("tbt", p99)
                        tracker = self._stream_slo.get(request.priority)
                        if tracker is not None:
                            verdict = tracker.record_latency(
                                "tbt", p99 * 1000.0
                            )
                            if verdict is not None and verdict["transition"]:
                                self.flight.event(
                                    "alert",
                                    objective=f"tbt:{request.priority}",
                                    state=(
                                        "firing"
                                        if verdict["alerting"]
                                        else "resolved"
                                    ),
                                    burn_rate_fast=verdict["burn_rate_fast"],
                                    burn_rate_slow=verdict["burn_rate_slow"],
                                    budget_remaining=verdict[
                                        "budget_remaining"
                                    ],
                                    target=verdict["target"],
                                )
                                if verdict["alerting"]:
                                    # the streaming SLO paged: capture at the
                                    # breach, keyed per class so one flapping
                                    # class can't spam (cooldown + dedup in
                                    # the recorder; no-op without
                                    # incident-dir)
                                    self._incident_capture(
                                        "tbt-burn",
                                        {
                                            "source": "stream-slo",
                                            "objective": (
                                                f"tbt:{request.priority}"
                                            ),
                                            "tbt_p99_s": p99,
                                            "burn_rate_fast": verdict[
                                                "burn_rate_fast"
                                            ],
                                            "budget_remaining": verdict[
                                                "budget_remaining"
                                            ],
                                            "target": verdict["target"],
                                        },
                                        dedup_key=request.priority,
                                    )
                self._journey(
                    request, "finish",
                    reason=(
                        "stop" if is_eos or request.stop_matched else "length"
                    ),
                    tokens=len(request.generated),
                    model=self.config.model,
                )
                if request.trace is not None:
                    # materialize the request's phases as child spans from the
                    # timestamps above — no extra clocks in the decode loop,
                    # and record_span never raises into the serving path
                    svc = f"engine:{self.config.model}"
                    record_span("engine.queue", svc, request.trace,
                                request.enqueue_time, admit)
                    record_span("engine.prefill", svc, request.trace, admit, first,
                                attributes={
                                    "prompt-tokens": len(request.prompt_tokens)
                                })
                    record_span("engine.decode", svc, request.trace, first, done_t,
                                attributes={"tokens": len(request.generated)})
                if not request.future.done():
                    request.future.set_result(
                        {
                            "tokens": request.generated,
                            "text": text,
                            "logprobs": request.logprobs,
                            "num_prompt_tokens": len(request.prompt_tokens),
                            "num_completion_tokens": len(request.generated),
                            "ttft": timing["ttft"],
                            "queue_wait": timing["queue_wait"],
                            "prefill": timing["prefill"],
                            "finish_reason": (
                                "stop"
                                if is_eos or request.stop_matched
                                else "length"
                            ),
                        }
                    )


def flight_report(
    summary_only: bool = False, samples: int = 240
) -> list[dict[str, Any]]:
    """Flight-recorder payload for every live engine (the pod's ``/flight``
    and ``/flight/summary`` endpoints serve this; the control plane fans it
    in per application). One entry per engine: model, rollup summary, and —
    unless ``summary_only`` — the recent sample window and event tail."""
    with TpuServingEngine._instances_lock:
        engines = list(TpuServingEngine._instances.values())
    report: list[dict[str, Any]] = []
    for engine in engines:
        entry: dict[str, Any] = {
            "model": engine.config.model,
            "slots": engine.config.slots,
            "summary": engine.flight.summary(),
            # admission-policy state (per-class counters + tenant throttle
            # counts under QoS): included in /flight/summary too, so the
            # control-plane /qos route needs no extra engine surface
            "scheduler": engine.scheduler.stats(),
            # watchdog verdict (serving/health.py): rides /flight/summary
            # so the control-plane /health route and engine_top need no
            # extra engine surface — and a saved dump self-diagnoses a
            # wedge post mortem (engine_top --analyze)
            "health": engine.health(),
            # drain posture: the autoscaler's fan-in reads draining/shed
            # counts off the same summary (no extra engine surface)
            "drain": engine._drain_section(),
            # pool role + handoff counters: the router and per-pool
            # autoscalers classify replicas off this same summary
            "pool_role": engine.config.pool_role,
            "kvtransfer": engine.kv_transfer_section(),
            # device-survival posture (docs/RESILIENCE.md): the
            # autoscaler reads pool-shrink pressure off this same
            # summary, engine_top renders the survival panel from it
            "survival": engine.survival_section(),
        }
        if engine.prefix_store is not None:
            # tier hit/byte/budget posture: rides /flight/summary so
            # engine_top's prefix panel and the control-plane fan-in
            # need no extra engine surface
            entry["prefixstore"] = engine.prefix_store_section()
        if engine.adapter_store is not None:
            # multi-LoRA tier posture: rides /flight/summary so
            # engine_top's adapters panel and the router's affinity
            # fan-in need no extra engine surface
            entry["adapters"] = engine.adapter_store_section()
        if engine.config.streaming:
            # per-class TBT digests + the cancellation ledger: rides
            # /flight/summary so engine_top's streaming panel and
            # --analyze need no extra engine surface. Streaming-
            # configured engines only — the default payload stays
            # byte-identical (the non-streaming pin)
            entry["streaming"] = engine.streaming_section()
        if engine.config.speculative_drafts > 0:
            # fused decode-tail speculation posture: accept/uplift/
            # auto-disable state rides /flight/summary so engine_top's
            # speculation panel and --analyze thrash detection need no
            # extra engine surface. Spec-configured engines only — the
            # default payload stays byte-identical
            entry["speculative"] = engine.speculative_section()
        if engine.incidents is not None:
            # incident-capture posture (docs/OBSERVABILITY.md "Incident
            # bundles & exemplars"): rides /flight/summary so engine_top's
            # incidents panel and the control-plane fan-in need no extra
            # engine surface. Present only when incident-dir is
            # configured — the default payload stays byte-identical
            entry["incidents"] = {
                **engine.incidents.stats(),
                "recent": engine.incidents.list()[-4:],
            }
        slo = engine.slo_status()
        if slo is not None:
            entry["slo"] = slo
        if not summary_only:
            entry["samples"] = engine.flight.recent(samples)
            entry["events"] = engine.flight.recent_events()
        report.append(entry)
    return report


def attribution_report() -> list[dict[str, Any]]:
    """Per-engine device-attribution payloads for the pod
    ``/attribution`` and ``/memory`` endpoints and the control-plane
    fan-in. Wait-free by contract (graftcheck OBS505): the instance map
    is snapshotted WITHOUT ``_instances_lock`` — the same rationale as
    :func:`health_report` (a ledger poll during an incident must never
    queue behind an engine constructor holding the lock), and a torn
    read at worst misses a brand-new engine for one poll."""
    return [
        engine.attribution_section()
        for engine in list(TpuServingEngine._instances.values())
    ]


def health_report() -> list[dict[str, Any]]:
    """Per-engine health verdicts for the pod's ``/healthz``/``/ready``
    probes. Wait-free by contract (graftcheck OBS504): the instance map
    is snapshotted WITHOUT ``_instances_lock`` — a liveness probe must
    never queue behind an engine constructor/close holding it (the probe
    runs exactly when the process is suspect), and a torn read of the
    dict copy at worst reports an engine twice or a brand-new one not at
    all, both harmless for a health poll."""
    return [
        engine.health() for engine in list(TpuServingEngine._instances.values())
    ]


def incident_report(bundle_id: str | None = None) -> list[dict[str, Any]]:
    """Per-engine incident payloads for the pod ``GET /incidents``
    endpoint and the control-plane fan-in: the bounded bundle index per
    engine (plus capture stats), or — with ``bundle_id`` — the full
    bundle from whichever engine holds it. The instance map is
    snapshotted WITHOUT ``_instances_lock`` (the :func:`health_report`
    rationale — an evidence poll during an incident is exactly when the
    lock might be held); the recorder's own table lock is the serving
    thread's, never the hot path's."""
    report: list[dict[str, Any]] = []
    for engine in list(TpuServingEngine._instances.values()):
        rec = engine.incidents
        if rec is None:
            continue
        entry: dict[str, Any] = {"model": engine.config.model}
        if bundle_id is not None:
            bundle = rec.get(bundle_id)
            if bundle is None:
                continue
            entry["bundle"] = bundle
        else:
            entry["incidents"] = rec.list()
            entry["stats"] = rec.stats()
        report.append(entry)
    return report


def kick_warmups() -> None:
    """Begin warmup for every ``warmup_on_start`` engine that hasn't
    started it yet. The readiness probe calls this: a freshly scheduled
    serving pod compiles its variants inside the not-ready window
    instead of on the first real request, and ``/ready`` flips 200 only
    once the warmup task completes. Task creation only — non-blocking
    (OBS504); must run on the engines' event loop (in-pod there is one
    loop)."""
    for engine in list(TpuServingEngine._instances.values()):
        if (
            engine.config.warmup_on_start
            and engine._warmup_task is None
            and not engine._stop
        ):
            engine._warmup_begun()


async def drain_engines(grace_s: float = 30.0) -> dict[str, Any]:
    """Drain every live serving engine (the pod ``/drain`` endpoint and
    the k8s preStop hook land here): per-model drain reports, each with
    requeued/completed/shed counts. ``grace_s`` budgets the WHOLE pod,
    not each engine: every preStop/terminationGracePeriod/drain-HTTP
    timeout upstream is sized to one grace, so a multi-model pod must
    fit the same envelope — each engine drains under the time remaining
    to the shared deadline (a small floor keeps the last engines' sweep:
    their leftovers still fail explicitly, never silently). Engines
    drain sequentially — they share one event loop and one device, so a
    concurrent drain buys nothing and interleaves the flight evidence."""
    with TpuServingEngine._instances_lock:
        engines = list(TpuServingEngine._instances.values())
    deadline = time.monotonic() + grace_s
    reports: dict[str, Any] = {}
    for engine in engines:
        remaining = max(0.5, deadline - time.monotonic())
        reports[engine.config.model] = await engine.drain(remaining)
    return reports


def take_kv_export(request_id: str) -> dict[str, Any] | None:
    """Pop one KV handoff export entry — ``{"payload", "bytes",
    "trace", "journey", ...}`` — from whichever live engine holds it
    (the pod ``GET /kv/export/{request}`` handler; the stashed trace
    rides back as the response's ``langstream-trace`` header). Wait-free
    (POOL701): instance-map snapshot + one dict pop per engine."""
    for engine in list(TpuServingEngine._instances.values()):
        entry = engine.take_export_entry(request_id)
        if entry is not None:
            return entry
    return None


async def import_kv_handoff(
    payload: bytes,
    trace_header: str | None = None,
    deadline_header: str | None = None,
) -> dict[str, Any]:
    """Route one KV handoff payload to this pod's matching engine (the
    ``POST /kv/import`` handler): the header's fingerprint model picks
    the engine, decode-role engines first (a combined engine also
    accepts — the dev/test posture). ``trace_header`` is the pod HTTP
    request's ``langstream-trace`` value — the fallback trace parent
    when the payload header carries none. The result echoes the
    effective trace so the chainer (and the pod response header) can
    keep propagating it. Raises
    :class:`~langstream_tpu.serving.kvtransfer.LayoutMismatch` when no
    engine here can take it."""
    from langstream_tpu.serving.kvtransfer import LayoutMismatch, peek_header

    header = peek_header(payload)
    model = (header.get("fingerprint") or {}).get("model")
    candidates = [
        engine
        for engine in list(TpuServingEngine._instances.values())
        if engine.config.model == model
        and engine.config.pool_role != "prefill"
    ]
    if not candidates:
        raise LayoutMismatch(
            f"no decode-capable engine for model {model!r} in this pod"
        )
    candidates.sort(
        key=lambda e: 0 if e.config.pool_role == "decode" else 1
    )
    # the peeked header rides along so the token-list JSON parses once;
    # the pod's langstream-deadline request header is the fallback
    # budget when the wire header predates the deadline plane
    result = await candidates[0].import_handoff(
        payload, header=header, trace_header=trace_header,
        deadline=parse_deadline(deadline_header),
    )
    trace = header.get("trace") or trace_header
    if trace and "trace" not in result:
        result = {**result, "trace": trace}
    return result


def profile_engines(action: str, trace_dir: str | None = None) -> dict[str, bool]:
    """Start/stop jax.profiler capture on every live engine (the pod's
    ``/profile/{start,stop}`` debug endpoint drives this)."""
    if action not in ("start", "stop"):
        raise ValueError(f"unknown profile action {action!r} (start|stop)")
    with TpuServingEngine._instances_lock:
        engines = list(TpuServingEngine._instances.values())
    results: dict[str, bool] = {}
    for engine in engines:
        if action == "start":
            results[engine.config.model] = engine.profiler.start_trace(trace_dir)
        else:
            results[engine.config.model] = engine.profiler.stop_trace()
    return results


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------


class EmbeddingEngine:
    """Batched encoder serving (drives ``compute-ai-embeddings``)."""

    _instances: dict[Any, "EmbeddingEngine"] = {}
    _instances_lock = threading.Lock()

    @classmethod
    def get_or_create(cls, model: str = "minilm-l6", tokenizer: str | None = None,
                      checkpoint: str | None = None, mesh: dict | None = None) -> "EmbeddingEngine":
        key = (model, tokenizer, checkpoint, tuple((mesh or {}).items()))
        with cls._instances_lock:
            if key not in cls._instances:
                cls._instances[key] = cls(model, tokenizer, checkpoint, mesh)
            return cls._instances[key]

    @classmethod
    def reset_instances(cls) -> None:
        with cls._instances_lock:
            cls._instances.clear()

    def __init__(self, model: str, tokenizer: str | None, checkpoint: str | None,
                 mesh: dict | None):
        if model in ("tiny", "tiny-encoder"):
            self.config = EncoderConfig.tiny()
        else:
            self.config = EncoderConfig.minilm_l6()
        self.tokenizer = load_tokenizer(tokenizer)
        if checkpoint:
            from langstream_tpu.models.encoder import load_from_sentence_transformers

            self.config, self.params = load_from_sentence_transformers(checkpoint)
        else:
            self.params = init_encoder_params(self.config)
        self.mesh = None
        if mesh:
            from langstream_tpu.parallel.mesh import make_mesh
            from jax.sharding import NamedSharding, PartitionSpec as P

            self.mesh = make_mesh(dict(mesh))
            specs = encoder_param_specs(self.config)
            self.params = jax.tree.map(
                lambda p, s: jax.device_put(p, NamedSharding(self.mesh, s)),
                self.params,
                specs,
                is_leaf=lambda x: isinstance(x, P),
            )
        self._executor = ThreadPoolExecutor(max_workers=1, thread_name_prefix="tpu-embed")
        self._m_embeddings = PrometheusMetricsReporter(
            prefix="langstream_serving", agent_id=model
        ).counter("embeddings_total", "embedding vectors computed")
        cfg = self.config

        @jax.jit
        def _encode(params, tokens, mask):
            return encode(cfg, params, tokens, mask)

        self._encode_fn = _encode

    async def embed(self, texts: list[str]) -> list[list[float]]:
        if not texts:
            return []
        max_pos = self.config.max_position
        ids = [self.tokenizer.encode(t)[: max_pos] for t in texts]
        # clip ids into the encoder vocab (byte fallback on a tiny vocab)
        V = self.config.vocab_size
        ids = [[t % V for t in row] for row in ids]
        bucket = _bucket(max(len(r) for r in ids), lo=16, hi=max_pos)
        B = len(ids)
        # pad rows to a power of two: the time-flushed micro-batcher emits
        # arbitrary batch sizes, and compiling one encoder per exact size
        # is a mid-traffic compile per new size (tens of seconds on TPU) —
        # log2 buckets bound the variants. All-zero-mask padding rows are
        # safe (pooling and norm are guarded) and sliced off below.
        Bp = _pow2(B)
        tokens = np.zeros((Bp, bucket), dtype=np.int32)
        mask = np.zeros((Bp, bucket), dtype=np.int32)
        for i, row in enumerate(ids):
            tokens[i, : len(row)] = row
            mask[i, : len(row)] = 1
        loop = asyncio.get_running_loop()
        out = await loop.run_in_executor(
            self._executor,
            lambda: np.asarray(
                self._encode_fn(self.params, jnp.asarray(tokens), jnp.asarray(mask))
            ),
        )
        self._m_embeddings(len(texts))
        return out[:B].tolist()
