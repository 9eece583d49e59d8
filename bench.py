"""Benchmark: the recorded serving numbers, one JSON line (re-emitted).

Process architecture (round-5 redesign — VERDICT r4 next #1): the PARENT
process never initializes a JAX backend. Every device-touching phase — the
probe included — runs in a FRESH CHILD process (`BENCH_PHASE=<name>` re-exec
of this file) with its own JAX context:

- an OOM'd / wedged / killed child costs exactly its own phase budget and
  frees its HBM by exiting — no cross-phase contamination (round 4's 8B OOM
  cascaded through every later in-process phase because caught exceptions
  pinned the dead engine's buffers);
- the parent owns the record and the deadline; children are killed by
  process group (SIGKILL) on timeout, so a gateway child's broker subprocess
  can't outlive it;
- children share the persistent XLA compilation cache
  (langstream_tpu/compile_cache.py), so re-compiles across phases are disk
  hits, not recompiles;
- one process per chip: the parent never touches JAX (a parent that had
  would hold the chip and every child would fail or hang), and children
  run strictly in turn.

Wedge-proofing contract (the driver kills the bench at ~1500s wall):
- The record line is printed + flushed EARLY and REWRITTEN as phases land —
  first right after the device probe (value 0.0 if the probe failed, with
  ``detail.device_probe`` explaining why), again after the headline phase,
  and again after every subsequent phase. A kill at ANY point leaves the
  last printed line as a parseable record; the final line is authoritative.
- ``BENCH_TOTAL_TIMEOUT_S`` defaults to 1150s — inside the driver window.
- Nothing stands in for a device that did not answer or a phase that did
  not finish: a failed probe ends the run at once, a failed phase is
  recorded under its own key with ``error``, and either makes the process
  exit non-zero. No CPU pass, no other kernel, no smaller model.

Phases (BASELINE.md targets: >= 2000 tok/s/chip, p50 gateway TTFT < 200ms):
1. **Headline decode throughput**: saturated continuous-batching decode.
   On a live TPU backend the model defaults to the REAL Llama-3-8B shape
   (32L/4096H/GQA-8/128256-vocab, random-init) in the full serving
   posture — int8 weights (~8GB, generated DIRECTLY quantized — the full
   bf16 tree never exists, models/quant.py init_llama_params_q8) + paged
   int8 KV — which fits a 16GB v5e chip. Off-TPU the model must be named
   (``BENCH_MODEL=tiny`` is the tool's own CPU self-test); nothing is
   picked for a device that is not there. ``vs_baseline`` = value / 2000.
2. **Gateway TTFT**: websocket chat gateway → topic → engine → streamed
   chunks, Poisson arrivals at a sub-saturation rate, measured at the
   client socket (tools/gateway_bench.py).
3. **int8-KV decode** (1b proxy path only — the 8B headline already
   runs int8): the same workload on the int8 pool. The **pipeline
   ablation** (``run_paged_pipeline_phase``: the same workload through
   the sequential reference loop, ``pipeline=False``) is no phase of a
   run since PR 29, when the dense headline it ran beside went;
   ``tests/test_pipeline.py`` calls it.
4. **Speculative decode** on a context-copying workload: uplift vs off.
5. **Prefix-cache TTFT**: cold vs warm TTFT for requests sharing a long
   preamble (paged layout; warm requests adopt cached prefix blocks).
6. **QoS mix** (`--qos-mix` scenario, BENCH_QOS=0 skips): one batch
   tenant flooding the engine at saturating load while an interactive
   tenant trickles requests through the WDRR scheduler — records
   per-class TTFT/throughput plus shed/preempt counts next to the
   flight rollup keys, the number that shows whether priority admission
   actually bounds interactive latency under contention.

Env knobs: BENCH_MODEL (tiny|llama-1b|llama3-8b|...), BENCH_SLOTS,
BENCH_DECODE_CHUNK, BENCH_QUANTIZE (int8|none),
BENCH_KV_QUANT (int8|none), BENCH_GATEWAY=0 /
BENCH_PREFIX=0 / BENCH_KV_INT8=0 / BENCH_SPEC=0 / BENCH_QOS=0 /
BENCH_OOM=0 / BENCH_PARTITION=0 / BENCH_STREAM=0 / BENCH_LORA=0 to
skip phases.

Offline note: weights are random-init (no checkpoint files in this
environment) — identical FLOPs/bytes to trained weights, so throughput is
representative.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
import traceback

# imports no JAX: the parent must stay off the chip (see the docstring)
from langstream_tpu.compile_cache import configure_compile_cache

_BENCH_PATH = os.path.abspath(__file__)
_IS_CHILD = bool(os.environ.get("BENCH_PHASE"))


SLOTS = int(os.environ.get("BENCH_SLOTS", "64"))
# model is finalized AFTER the device probe (live TPU -> real 8B shape);
# BENCH_MODEL pins it explicitly
MODEL = os.environ.get("BENCH_MODEL", "")
MAX_SEQ = int(os.environ.get("BENCH_MAX_SEQ", "1024"))
MAX_TOKENS = int(os.environ.get("BENCH_MAX_TOKENS", "192"))
# chip-swept default (r5): 32-step chunks beat 96 by ~19% — the device
# step cost is nearly K-flat (24.6ms@K=32 vs 27.3ms@K=96 device-side) but
# big K inflates block reservations (pool pressure) and host batch size
DECODE_CHUNK = int(os.environ.get("BENCH_DECODE_CHUNK", "32"))
WARMUP_REQUESTS = int(os.environ.get("BENCH_WARMUP_REQUESTS", "8"))
BENCH_REQUESTS = int(os.environ.get("BENCH_REQUESTS", "192"))
BASELINE_TOK_S = 2000.0
# weight-only int8 is the engine's serving default posture (≈ lossless);
# BENCH_QUANTIZE=none reverts to bf16
_quant_env = os.environ.get("BENCH_QUANTIZE", "int8").strip().lower()
QUANTIZE = None if _quant_env in ("", "none", "bf16") else _quant_env
KV_LAYOUT = "paged"  # the engine serves the paged pool and nothing else
_kvq_env = os.environ.get("BENCH_KV_QUANT", "").strip().lower()
KV_QUANT = None if _kvq_env in ("", "none", "bf16") else _kvq_env
# an explicit env pin wins over the model-based default (an explicit "none"
# is a pin too — it must not be re-defaulted to int8 for the 8B posture)
KV_QUANT_PINNED = "BENCH_KV_QUANT" in os.environ
RUN_GATEWAY = os.environ.get("BENCH_GATEWAY", "1") != "0"
RUN_PREFIX = os.environ.get("BENCH_PREFIX", "1") != "0"
RUN_PREFIX_WARM = os.environ.get("BENCH_PREFIX_WARM", "1") != "0"
RUN_KV_INT8 = os.environ.get("BENCH_KV_INT8", "1") != "0"
RUN_SPEC = os.environ.get("BENCH_SPEC", "1") != "0"
RUN_QOS = os.environ.get("BENCH_QOS", "1") != "0"
RUN_OOM = os.environ.get("BENCH_OOM", "1") != "0"
RUN_PARTITION = os.environ.get("BENCH_PARTITION", "1") != "0"
RUN_STREAM = os.environ.get("BENCH_STREAM", "1") != "0"
RUN_LORA = os.environ.get("BENCH_LORA", "1") != "0"

PROMPT = "Benchmarking the TPU serving engine end to end. " * 4

# Record schema version (BENCH_NOTES.md "Record format"): stamped on
# every emitted record so tools/perf_diff.py can align rounds across
# code changes. Bump when a record key changes meaning, not when keys
# are merely added. v2 = schema stamp + per-phase program-variant
# census (rounds r01–r05 are implicitly v1).
BENCH_SCHEMA = 2


# Wall-clock budget per phase (a wedged device hangs inside JAX calls —
# the parent SIGKILLs the child's process group at the budget) and
# for the whole record. TOTAL must sit well inside the driver's ~1500s kill
# window.
PHASE_BUDGET_S = float(os.environ.get("BENCH_PHASE_TIMEOUT_S", "420"))
TOTAL_BUDGET_S = float(os.environ.get("BENCH_TOTAL_TIMEOUT_S", "1150"))
PROBE_TIMEOUT_S = float(os.environ.get("BENCH_PROBE_TIMEOUT_S", "120"))
_DEADLINE = time.monotonic() + TOTAL_BUDGET_S

# filled by _probe_device from the probe child's report (backend + HBM);
# empty when the probe failed or was monkeypatched
_PROBE_INFO: dict = {}


def _emit(record: dict) -> None:
    """Print + flush the record line. Called after every phase: the last
    line on stdout is always the freshest parseable record."""
    print(json.dumps(record), flush=True)


def _remaining() -> float:
    return _DEADLINE - time.monotonic()


# ---------------------------------------------------------------------------
# child-process plumbing (parent side)
# ---------------------------------------------------------------------------


def _run_child(
    phase: str, budget_s: float, env_overrides: dict | None = None
) -> dict:
    """Run one phase in a fresh child process; kill its whole process group
    at ``budget_s``. Returns the child's JSON result, always annotated with
    ``child`` = {rc, elapsed_s}; on failure carries ``error`` (+ a stderr
    tail for diagnostics)."""
    env = dict(os.environ)
    env["BENCH_PHASE"] = phase
    fd, out_path = tempfile.mkstemp(prefix=f"bench_{phase}_", suffix=".json")
    os.close(fd)
    env["BENCH_PHASE_OUT"] = out_path
    # the child's own asyncio guard fires first so it can write a partial
    # result and exit cleanly before the parent's SIGKILL
    env["BENCH_PHASE_TIMEOUT_S"] = str(max(int(budget_s) - 30, 30))
    env.update(env_overrides or {})
    t0 = time.monotonic()
    rc: int | str
    stderr_tail = ""
    try:
        proc = subprocess.Popen(
            [sys.executable, _BENCH_PATH],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            start_new_session=True,  # group kill reaches broker grandchildren
        )
        try:
            out, _ = proc.communicate(timeout=budget_s)
            rc = proc.returncode
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                proc.kill()
            out, _ = proc.communicate()
            rc = f"killed after {budget_s:.0f}s"
        stderr_tail = (out or "")[-1200:]
    except Exception as e:  # pragma: no cover - spawn failure
        rc = f"spawn failed: {type(e).__name__}: {e}"
        out = ""
    elapsed = time.monotonic() - t0

    result: dict = {}
    try:
        with open(out_path) as f:
            text = f.read().strip()
        if text:
            result = json.loads(text)
    except Exception:
        result = {}
    finally:
        try:
            os.unlink(out_path)
        except OSError:
            pass
    if not result:
        result = {"error": f"phase child produced no result (rc={rc})"}
    if "error" in result and stderr_tail:
        result["log_tail"] = stderr_tail[-600:]
        print(
            f"[bench] phase {phase} failed (rc={rc}):\n{stderr_tail}",
            file=sys.stderr,
        )
    result["child"] = {"rc": rc, "elapsed_s": round(elapsed, 1)}
    return result


def _probe_device(timeout_s: float = PROBE_TIMEOUT_S) -> str | None:
    """Probe the device in a CHILD process (compile + run one tiny op and
    fetch it). Returns None when the device answered, else a diagnostic
    string. The child's backend/HBM report lands in ``_PROBE_INFO`` so the
    parent learns the platform without ever importing jax itself."""
    global _PROBE_INFO
    res = _run_child("probe", budget_s=timeout_s + 60)
    _PROBE_INFO = res
    if res.get("ok"):
        return None
    return res.get(
        "error", f"device probe failed (rc={res.get('child', {}).get('rc')})"
    )


def _finalize_model_choice() -> None:
    """Pick the benchmark model once the device answered.

    Live TPU → the real Llama-3-8B shape in the full serving posture
    (int8 weights + paged int8 KV: ~8GB + ~4.3GB in 16GB HBM). Off-TPU
    nothing is picked: the caller names the model (the tool's own CPU
    self-test) or the run fails. Explicit BENCH_MODEL /
    BENCH_KV_QUANT win."""
    global MODEL, KV_QUANT
    if not MODEL:
        if _PROBE_INFO.get("backend") != "tpu":
            raise RuntimeError(
                f"no TPU (JAX reports backend "
                f"{_PROBE_INFO.get('backend')!r}) and no BENCH_MODEL: "
                f"nothing is benchmarked in place of the chip"
            )
        MODEL = "llama3-8b"
    if not KV_QUANT_PINNED and MODEL in ("llama3-8b", "llama-3-8b"):
        KV_QUANT = "int8"


def _posture_env() -> dict:
    """Env pins handing the parent's finalized model/posture to a child."""
    return {
        "BENCH_MODEL": MODEL,
        "BENCH_KV_QUANT": KV_QUANT or "none",
    }


def _record(headline: dict, detail: dict) -> dict:
    wdtype = "int8-weights" if QUANTIZE == "int8" else "bf16"
    kv_desc = f"{KV_LAYOUT}{' int8' if KV_QUANT == 'int8' else ''} KV"
    # the device as the probe child's JAX reported it — never assumed
    kind = _PROBE_INFO.get("device_kind") or "no device"
    if MODEL in ("llama3-8b", "llama-3-8b"):
        shape = f"real Llama-3-8B shape single chip, {kv_desc}, {kind}"
    else:
        shape = f"per-chip shard proxy of Llama-3-8B TP8, {kv_desc}, {kind}"
    tok_s = headline.get("tok_s", 0.0)
    return {
        "schema": BENCH_SCHEMA,
        "metric": f"tok/s/chip {MODEL or 'unselected'} {wdtype} decode ({shape})",
        "value": tok_s,
        "unit": "tok/s/chip",
        "vs_baseline": round(tok_s / BASELINE_TOK_S, 3),
        "detail": detail,
    }


def _analyzer_stats() -> dict:
    """graftcheck self-stats for the record (stdlib-only, safe in the
    no-JAX parent): the tier-1 gate pays the analyzer's wall time on
    every run, so its cost and escape-hatch counts are a perf surface
    perf_diff should watch like any other."""
    try:
        from langstream_tpu.analysis import (
            ALL_RULES,
            PROJECT_RULES,
            PROJECT_RULES_BY_ID,
            RULES_BY_ID,
            iter_py_files,
            load_baseline,
        )
        from langstream_tpu.analysis import run as run_analysis
        from langstream_tpu.analysis.core import (
            PACKAGE_ROOT,
            Module,
            parse_suppressions,
        )

        report = run_analysis(ALL_RULES, project_rules=PROJECT_RULES)
        families: dict[str, int] = {}
        for f in report.new + report.baselined:
            rule = RULES_BY_ID.get(f.rule) or PROJECT_RULES_BY_ID.get(f.rule)
            fam = rule.family if rule is not None else "framework"
            families[fam] = families.get(fam, 0) + 1
        suppressions = 0
        for path in iter_py_files(PACKAGE_ROOT):
            try:
                by_line, _ = parse_suppressions(
                    Module(path.as_posix(), path.read_text())
                )
            except (OSError, SyntaxError, UnicodeDecodeError):
                continue
            suppressions += len(by_line)
        return {
            "analyzer_wall_s": round(report.analysis_seconds, 3),
            "violations": len(report.new),
            "findings_by_family": dict(sorted(families.items())),
            "suppressions": suppressions,
            "baseline_entries": len(load_baseline()),
        }
    except Exception as e:  # the bench record never dies to its own meta
        return {"error": str(e)[:200]}


def run_bench() -> dict:
    """Parent orchestration: probe, then one child per phase, re-emitting
    the record as each lands. No JAX in this process — ever."""
    detail: dict = {
        "decode_chunk": DECODE_CHUNK,
        "slots": SLOTS,
        "max_tokens": MAX_TOKENS,
        "isolation": "fresh child process per phase",
    }
    if _remaining() > 180:
        detail["analyzer"] = _analyzer_stats()
    headline: dict = {"tok_s": 0.0}

    probe = _probe_device()
    if probe is None:
        try:
            _finalize_model_choice()
        except RuntimeError as e:
            probe = str(e)
    if probe is not None:
        # the device did not answer (or is not the one asked for): the
        # record says so and the run ends here, failed. No phase runs.
        detail["device_probe"] = probe
        print(f"device probe failed: {probe}", file=sys.stderr)
        headline = {"tok_s": 0.0, "error": f"device probe failed: {probe}"}
        return _record(headline, detail)

    detail["device"] = {
        # as the probe child's JAX reported them
        "backend": _PROBE_INFO.get("backend"),
        "device_kind": _PROBE_INFO.get("device_kind"),
        "device_count": _PROBE_INFO.get("device_count"),
        # allocator stats (memory_stats()); null where the backend
        # reports none (CPU)
        "hbm": _PROBE_INFO.get("hbm"),
    }

    # ---- headline decode: one attempt, in a fresh child -----------------
    budget = min(PHASE_BUDGET_S, max(_remaining() - 60, 60))
    headline = _run_child("decode", budget, _posture_env())
    if "error" in headline:
        headline["tok_s"] = 0.0
    detail[KV_LAYOUT] = headline
    _emit(_record(headline, detail))  # headline locked in — flush it

    # ---- optional phases, each its own child --------------------------
    def optional(phase: str, condition: bool, detail_key: str | None = None,
                 budget_cap: float | None = None) -> None:
        if not condition or _remaining() < 120:
            return
        budget = min(
            budget_cap or PHASE_BUDGET_S, max(_remaining() - 60, 60)
        )
        key = detail_key or phase
        detail[key] = _run_child(phase, budget, _posture_env())
        if phase == "gateway" and "gateway_ttft_p50_s" in detail[key]:
            detail["gateway_ttft_p50_s"] = detail[key]["gateway_ttft_p50_s"]
        _emit(_record(headline, detail))

    optional("gateway", RUN_GATEWAY)
    # same saturated workload on the int8 KV cache: halved cache-read bytes
    # halve the roofline floor — this records what that buys
    optional("kv_int8", RUN_KV_INT8 and KV_QUANT != "int8")
    # context-copying workload: the regime where prompt-lookup speculation
    # must EARN its number (uplift > 1x), not just exist
    optional("speculative", RUN_SPEC)
    # --qos-mix: batch tenant floods, interactive tenant trickles; records
    # per-class TTFT + shed/preempt counts under the WDRR scheduler
    optional("qos_mix", RUN_QOS)
    # detail key kept from rounds 1-4 ("prefix_cache") for record tooling
    optional("prefix", RUN_PREFIX, detail_key="prefix_cache",
             budget_cap=min(PHASE_BUDGET_S, 300))
    # tiered prefix store (docs/PREFIX.md): N tenants share one system
    # prompt across 2 replicas; records per-tier hits + hydrate-vs-
    # recompute TTFT + router prefix-affinity counters
    optional("prefix_warm", RUN_PREFIX_WARM,
             budget_cap=min(PHASE_BUDGET_S, 300))
    # device-survival storm (docs/RESILIENCE.md): injected
    # RESOURCE_EXHAUSTED burst mid-flood; records shrink/recover counts,
    # shed rate, and the zero-silent-loss completed-vs-submitted ledger
    optional("oom_storm", RUN_OOM, budget_cap=min(PHASE_BUDGET_S, 240))
    # cross-replica failure storm (docs/RESILIENCE.md "Distributed
    # failure domain"): a dead decode replica + injected offer drops
    # mid-handoff; records re-handoffs, breaker opens, local-decode
    # fallbacks, deadline sheds, and the zero-silent-loss ledger
    optional("partition_storm", RUN_PARTITION,
             budget_cap=min(PHASE_BUDGET_S, 240))
    # streaming-delivery phase (docs/OBSERVABILITY.md Streaming): N
    # streaming WS clients against the TBT-instrumented engine; records
    # client-observed TBT p50/p99 per class, first-frame TTFB, stall
    # count, and the disconnect-burst cancellation ledger (every
    # dropped stream's decode slot reclaimed at a chunk boundary)
    optional("gateway_stream", RUN_STREAM,
             budget_cap=min(PHASE_BUDGET_S, 240))
    # multi-LoRA adapter phase (docs/ADAPTERS.md): N tenants over M
    # adapters with M > the device row budget; records warm vs hydrate
    # TTFT, the T0 hit ratio, eviction churn, and the byte-ledger
    # conservation verdict
    optional("multi_lora", RUN_LORA, budget_cap=min(PHASE_BUDGET_S, 300))

    return _record(headline, detail)


# ---------------------------------------------------------------------------
# child side: one phase per process
# ---------------------------------------------------------------------------


def _mem_snapshot() -> dict | None:
    """Device allocator stats; None where the backend reports none (CPU)."""
    import jax

    ms = jax.local_devices()[0].memory_stats()
    if not ms:
        return None
    return {
        k: ms[k]
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
        if k in ms
    }


def _child_probe() -> dict:
    """Compile + run one tiny op and fetch it, bounded by PROBE_TIMEOUT_S.

    Runs in a daemon thread: if the device is wedged the JAX call blocks
    forever and can't be cancelled — the probe thread is abandoned and the
    process exits (os._exit) out from under it."""
    result: dict = {}

    def _go():
        try:
            import jax
            import jax.numpy as jnp
            import numpy as np

            x = jnp.ones((128, 128))
            np.asarray(jax.jit(lambda a: a @ a)(x))  # true host fence
            from langstream_tpu.serving.profiling import device_peaks

            # an unknown TPU device_kind raises here: no roofline is ever
            # judged against another chip's peaks
            result["device_kind"], _ = device_peaks()
            result["backend"] = jax.default_backend()
            result["device_count"] = jax.device_count()
            result["hbm"] = _mem_snapshot()
            result["ok"] = True
        except Exception as e:  # pragma: no cover - device-dependent
            result["error"] = f"{type(e).__name__}: {e}"

    t = threading.Thread(target=_go, daemon=True)
    t.start()
    t.join(PROBE_TIMEOUT_S)
    if result.get("ok"):
        return result
    if t.is_alive():
        return {
            "error": f"device unresponsive after {PROBE_TIMEOUT_S:.0f}s"
        }
    return {"error": result.get("error", "device probe failed")}


async def _phase(coro, budget_s: float | None = None):
    """Child-side asyncio guard under the per-phase budget (fires before
    the parent's process-group SIGKILL so a partial result still lands)."""
    budget = min(
        budget_s or PHASE_BUDGET_S, max(_DEADLINE - time.monotonic(), 30.0)
    )
    try:
        return await asyncio.wait_for(coro, timeout=budget)
    except asyncio.TimeoutError:
        raise TimeoutError(
            f"phase exceeded {budget:.0f}s wall budget (device hang?)"
        ) from None


async def _close_all_engines() -> None:
    """Fully close every live engine (reset_instances only clears the
    registry — it would leave loops, executors, and HBM caches alive)."""
    from langstream_tpu.serving.engine import TpuServingEngine

    with TpuServingEngine._instances_lock:
        engines = list(TpuServingEngine._instances.values())
    for engine in engines:
        try:
            await engine.close()
        except Exception:
            pass


async def _cleanup_engines() -> None:
    """Bounded engine teardown between intra-phase runs (speculative off/on
    comparison): closing an engine whose loop is blocked on a wedged device
    would itself hang; give up after 60s and move on."""
    from langstream_tpu.serving.engine import TpuServingEngine

    try:
        await asyncio.wait_for(
            _close_all_engines(), timeout=min(60.0, max(_remaining(), 5.0))
        )
    except Exception:
        TpuServingEngine.reset_instances()


def _round(value: float | None, digits: int) -> float | None:
    return None if value is None else round(value, digits)


def _serving_config(kv_layout: str, kv_quantize: str | None = None,
                    model: str | None = None, pipeline: bool = True):
    from langstream_tpu.serving.engine import ServingConfig

    return ServingConfig(
        model=model or MODEL,
        slots=SLOTS,
        max_seq_len=MAX_SEQ,
        default_max_tokens=MAX_TOKENS,
        decode_chunk=DECODE_CHUNK,
        # saturated-throughput phases pin the heavy chunk length: adaptive
        # light chunks are the sub-saturation TTFT posture (gateway phase)
        decode_chunk_light=0,
        quantize=QUANTIZE,
        kv_layout=kv_layout,
        kv_quantize=kv_quantize,
        # pipeline=False is the paged phase's ablation leg: the sequential
        # reference loop on the same workload (docs/PIPELINE.md)
        pipeline=pipeline,
    )


async def run_decode_bench(
    kv_layout: str, requests: int, kv_quantize: str | None = None,
    model: str | None = None, pipeline: bool = True,
) -> dict:
    """Saturated decode throughput for one KV layout."""
    from langstream_tpu.serving.engine import TpuServingEngine

    engine = TpuServingEngine.get_or_create(
        _serving_config(kv_layout, kv_quantize, model=model,
                        pipeline=pipeline)
    )

    # warmup at FULL length: the decode window bucket grows with sequence
    # length, so short warmups would leave later buckets to compile inside
    # the measured run (a 30s stall mid-measurement)
    await asyncio.gather(
        *(
            engine.generate(PROMPT, {"max-tokens": MAX_TOKENS})
            for _ in range(WARMUP_REQUESTS)
        )
    )
    # fresh flight ring for the measured window: warmup's compile storms
    # and first-touch costs must not pollute the recorded rollup (the
    # pipeline ablation compares rollups across legs, and the first leg
    # in a child otherwise absorbs every process-global one-time cost)
    from langstream_tpu.serving.flight import FlightRecorder

    engine.flight = FlightRecorder(slots=SLOTS)

    start = time.monotonic()
    results = await asyncio.gather(
        *(
            engine.generate(PROMPT, {"max-tokens": MAX_TOKENS})
            for _ in range(requests)
        )
    )
    elapsed = time.monotonic() - start
    total_tokens = sum(r["num_completion_tokens"] for r in results)
    tok_s = total_tokens / elapsed

    # roofline: decode streams weights + the KV window every step; report
    # achieved HBM utilization against that floor (profiling.py model)
    from langstream_tpu.serving.profiling import decode_step_bytes

    prompt_tokens = results[0]["num_prompt_tokens"]
    mean_len = prompt_tokens + MAX_TOKENS / 2
    window = (
        engine._read_blocks_for(int(mean_len)) * engine.paged_layout.block_size
    )
    roof = decode_step_bytes(
        engine.model_config, slots=SLOTS, window=window, quantize=QUANTIZE,
        kv_quantize=kv_quantize,
    )
    achieved_step_ms = SLOTS / tok_s * 1e3  # all slots advance one token/step
    # flight-recorder rollup: decomposes the achieved-vs-roofline gap into
    # device/host/stall instead of leaving it "unattributed host overhead"
    # (the r05 16 ms/step mystery), and records recompiles/queue depth so
    # the record can tell a compile convoy from a genuinely slow step
    from langstream_tpu.serving.flight import bench_rollup

    flight = bench_rollup(engine.flight.summary())
    # program-variant census + per-program achieved-vs-expected
    # (serving/attribution.py): stamps WHICH compiled programs served
    # this leg, so perf_diff can align rounds across code changes and a
    # step-time shift reads against the variant set that produced it
    attribution = engine.attribution.report()
    programs = {p["program"]: p["dispatches"] for p in attribution}
    # mean dispatched-step wall excluding idle gaps (the engine_top
    # convention): the number the pipeline ablation compares across legs
    totals = flight.get("totals") or {}
    steps = sum((totals.get("steps_by_phase") or {}).values())
    busy_ms = (totals.get("wall_ms") or 0.0) - (totals.get("stall_ms") or 0.0)
    out = {
        "model": model or MODEL,
        "kv_layout": kv_layout,
        **({"kv_quantize": kv_quantize} if kv_quantize else {}),
        "pipeline": pipeline,
        "mean_step_ms": round(busy_ms / steps, 3) if steps else None,
        # the pipelined loop's headline observability: how much host work
        # was hidden under device compute, and what stayed exposed
        "overlap_ratio": flight.get("overlap_ratio"),
        "host_exposed_ms_p50": flight.get("host_exposed_ms_p50"),
        "tok_s": round(tok_s, 1),
        "requests": requests,
        "total_tokens": total_tokens,
        "elapsed_s": round(elapsed, 2),
        # the fused-tail invariant on the record: one packed host fetch
        # per dispatched decode chunk (perf_diff flags drift upward)
        "decode_host_fetches_per_chunk": (
            (engine.stats().get("decode-chunks") or {})
            .get("host_fetches_per_chunk")
        ),
        # the roof this run was judged against: the published bandwidth of
        # the device JAX reports. Off-TPU there is none and the derived
        # fields are null — a CPU run is never held to a chip's roof.
        "roofline": {
            "device_kind": roof.device_kind,
            "hbm_gbps_published": roof.hbm_gbps,
            "hbm_bytes": roof.hbm_bytes,
            "bytes_per_step": roof.total_bytes_per_step,
            "min_step_ms": _round(roof.min_step_ms(), 3),
            "achieved_step_ms": round(achieved_step_ms, 3),
            "hbm_utilization": _round(roof.utilization(achieved_step_ms), 3),
        },
        "flight": flight,
        "programs": programs,
        "attribution": attribution,
    }
    await engine.close()
    return out


async def run_speculative_phase() -> dict:
    """Context-copying workload (the regime prompt-lookup speculation is
    FOR — RAG answers quoting sources, code edits, summaries): accepted-
    draft rate and tok/s uplift vs speculation-off on the same workload
    and engine posture. Greedy requests on a highly repetitive prompt:
    greedy continuations of repetitive context loop, and the bigram
    drafter predicts loops — representative acceptance without trained
    weights."""
    import dataclasses as _dc

    from langstream_tpu.serving.engine import TpuServingEngine

    sentence = (
        "The quarterly report shows revenue grew twelve percent while "
        "costs fell. "
    )
    # size the prompt to ~1/3 of the context so completions keep real room:
    # a prompt that truncates to max_seq_len leaves max-tokens ≈ 1 and every
    # request finishes at prefill — zero decode steps, meaningless numbers
    repeats = max(2, (MAX_SEQ // 3) // len(sentence))
    prompt = sentence * repeats + "Quote the report verbatim: "
    reqs = max(16, BENCH_REQUESTS // 6)
    room = MAX_SEQ - len(prompt) - 16
    if room < 16:
        # context too small for a decode-phase measurement: a truncated
        # prompt leaves max-tokens ≈ 1, every request finishes at prefill,
        # and any "uplift" would be prefill-throughput noise
        return {
            "skipped": f"max_seq_len {MAX_SEQ} leaves {room} decode tokens "
                       f"after the copying prompt; need >= 16"
        }
    toks = min(96, MAX_TOKENS, room)

    async def run_one(drafts: int) -> dict:
        cfg = _dc.replace(
            _serving_config("paged", KV_QUANT), speculative_drafts=drafts
        )
        engine = TpuServingEngine.get_or_create(cfg)
        await asyncio.gather(
            *(engine.generate(prompt, {"max-tokens": toks}) for _ in range(4))
        )
        start = time.monotonic()
        results = await asyncio.gather(
            *(engine.generate(prompt, {"max-tokens": toks}) for _ in range(reqs))
        )
        elapsed = time.monotonic() - start
        total = sum(r["num_completion_tokens"] for r in results)
        stats = engine.stats()
        await engine.close()
        out = {"tok_s": round(total / elapsed, 1)}
        if drafts:
            out["speculative"] = stats.get("speculative")
        return out

    off = await run_one(0)
    await _cleanup_engines()
    on = await run_one(int(os.environ.get("BENCH_SPEC_DRAFTS", "4")))
    spec = on.get("speculative") or {}
    steps = spec.get("steps") or 0
    accepted = spec.get("drafts_accepted") or 0
    return {
        "off_tok_s": off["tok_s"],
        "on_tok_s": on["tok_s"],
        # a speculation-attributed uplift requires verify steps to have
        # actually run; otherwise the ratio is just engine-to-engine noise
        "uplift": (
            round(on["tok_s"] / off["tok_s"], 2)
            if off["tok_s"] and steps else None
        ),
        "verify_steps": steps,
        "drafts_accepted": accepted,
        "accepted_per_step": round(accepted / steps, 2) if steps else 0.0,
        "requests": reqs,
        "max_tokens": toks,
        # the engine's own speculation section (fused-tail dispatch/fetch
        # counters, rolling measured uplift, auto-disable posture) rides
        # the record so perf_diff can extract it schema-2-aligned
        "engine": spec or None,
    }


async def run_paged_pipeline_phase(requests: int | None = None) -> dict:
    """The paged phase with its ``pipeline`` ablation: the same saturated
    workload once through the depth-2 pipelined loop and once through the
    ``LS_TPU_PIPELINE=0``-equivalent sequential reference
    (``pipeline=False``), fresh engine each. Records both legs' rollups
    plus the step-time ratio — the measured answer to "what did
    overlapping host work under device compute buy", with
    ``overlap_ratio``/``host_exposed_ms_p50`` from the flight rollup
    showing how much host time the pipeline actually hid."""
    n = requests if requests is not None else max(8, BENCH_REQUESTS // 2)
    pipelined = await run_decode_bench("paged", n, pipeline=True)
    await _cleanup_engines()
    sequential = await run_decode_bench("paged", n, pipeline=False)
    # median step over the measured window (post-warmup flight reset):
    # robust to the stray mid-measurement compile that makes means lie
    pipe_step = (pipelined.get("flight") or {}).get("step_ms_p50")
    seq_step = (sequential.get("flight") or {}).get("step_ms_p50")
    return {
        # headline keys mirror the pipelined leg so record tooling that
        # reads detail.paged.tok_s keeps working
        **pipelined,
        "pipelined": pipelined,
        "sequential": sequential,
        "step_speedup": (
            round(seq_step / pipe_step, 3)
            if pipe_step and seq_step else None
        ),
        "tok_s_uplift": (
            round(pipelined["tok_s"] / sequential["tok_s"], 3)
            if sequential.get("tok_s") else None
        ),
    }


async def run_qos_mix_phase() -> dict:
    """The ``--qos-mix`` scenario: one batch tenant flooding at saturating
    load while an interactive tenant trickles closed-loop requests through
    the WDRR scheduler. Records per-class TTFT/throughput and the
    scheduler's shed/preempt counters next to the flight rollup — the
    number that shows whether priority admission bounds interactive
    latency while batch still receives its guaranteed share."""
    import dataclasses as _dc

    from langstream_tpu.serving.engine import TpuServingEngine
    from langstream_tpu.serving.flight import bench_rollup
    from langstream_tpu.serving.qos import QosSpec

    qos = QosSpec.from_dict(
        {
            "classes": {
                "interactive": {"weight": 8},
                "batch": {
                    "weight": 1,
                    "queue-limit": max(64, BENCH_REQUESTS * 2),
                },
            },
        }
    )
    cfg = _dc.replace(_serving_config(KV_LAYOUT, KV_QUANT), qos=qos)
    engine = TpuServingEngine.get_or_create(cfg)
    await asyncio.gather(
        *(
            engine.generate(PROMPT, {"max-tokens": MAX_TOKENS})
            for _ in range(WARMUP_REQUESTS)
        )
    )

    batch_n = BENCH_REQUESTS
    inter_n = max(8, BENCH_REQUESTS // 8)
    inter_tokens = min(16, MAX_TOKENS)
    start = time.monotonic()
    batch_done = asyncio.gather(
        *(
            engine.generate(
                PROMPT,
                {"max-tokens": MAX_TOKENS, "priority": "batch",
                 "qos-tenant": "bulk"},
            )
            for _ in range(batch_n)
        )
    )
    # closed-loop trickle: one interactive request in flight at a time —
    # the "low rate" side of the mix, measured while the flood saturates
    inter_results = []
    for _ in range(inter_n):
        inter_results.append(
            await engine.generate(
                PROMPT,
                {"max-tokens": inter_tokens, "priority": "interactive",
                 "qos-tenant": "live"},
            )
        )
    batch_results = await batch_done
    elapsed = time.monotonic() - start

    def _pct(results, q: float) -> float:
        ttfts = sorted(r["ttft"] for r in results)
        return round(ttfts[min(len(ttfts) - 1, int(q * len(ttfts)))], 4)

    scheduler = engine.stats()["scheduler"]
    flight = bench_rollup(engine.flight.summary())
    out = {
        "elapsed_s": round(elapsed, 2),
        "interactive": {
            "requests": inter_n,
            "ttft_p50_s": _pct(inter_results, 0.50),
            "ttft_p95_s": _pct(inter_results, 0.95),
            "tok_s": round(
                sum(r["num_completion_tokens"] for r in inter_results)
                / elapsed, 1,
            ),
        },
        "batch": {
            "requests": batch_n,
            "ttft_p50_s": _pct(batch_results, 0.50),
            "ttft_p95_s": _pct(batch_results, 0.95),
            "tok_s": round(
                sum(r["num_completion_tokens"] for r in batch_results)
                / elapsed, 1,
            ),
        },
        "shed": scheduler.get("shed", 0),
        "preempted": scheduler.get("preempted", 0),
        "resumed": scheduler.get("resumed", 0),
        "queue_wait_by_class": {
            cls: {
                "p50_s": info.get("queue_wait_p50_s"),
                "p95_s": info.get("queue_wait_p95_s"),
            }
            for cls, info in (scheduler.get("classes") or {}).items()
        },
        "flight": flight,
    }
    await engine.close()
    return out


async def run_prefix_cache_phase() -> dict:
    """Cold vs warm TTFT with a shared preamble (paged layout).

    The preamble is most of the prompt, so a warm request prefills only
    its short question suffix — the ratio is the shared-prefix TTFT win."""
    from langstream_tpu.serving.engine import TpuServingEngine

    engine = TpuServingEngine.get_or_create(_serving_config("paged", KV_QUANT))
    preamble = "You are a careful assistant. " * 64  # ~hundreds of tokens
    questions = [f"Question {i}: what should I check first?" for i in range(7)]

    # compile-warm both code paths on a DIFFERENT preamble so the measured
    # cold request pays prefill compute, not compilation
    warm_pre = "Compile warmup preamble text. " * 64
    await engine.generate(warm_pre + questions[0], {"max-tokens": 4})
    await engine.generate(warm_pre + questions[1], {"max-tokens": 4})

    cold = await engine.generate(preamble + questions[0], {"max-tokens": 4})
    warm_ttfts = []
    for q in questions[1:]:
        r = await engine.generate(preamble + q, {"max-tokens": 4})
        warm_ttfts.append(r["ttft"])
    warm_ttfts.sort()
    stats = engine.stats()
    await engine.close()
    warm_p50 = warm_ttfts[len(warm_ttfts) // 2]
    return {
        "cold_ttft_s": round(cold["ttft"], 4),
        "warm_ttft_p50_s": round(warm_p50, 4),
        "speedup": round(cold["ttft"] / warm_p50, 2) if warm_p50 > 0 else None,
        "cached_prefix_blocks": stats["kv"].get("cached_prefix_blocks"),
    }


async def run_gateway_phase() -> dict:
    sys.path.insert(0, os.path.join(os.path.dirname(_BENCH_PATH), "tools"))
    from gateway_bench import run_gateway_bench

    broker_proc = None
    instance_yaml = None
    broker_kind = os.environ.get("BENCH_BROKER", "memory").strip().lower()
    if broker_kind == "tpustream":
        broker_kind = "tsb"  # streaming-cluster type name, same transport
    if broker_kind not in ("memory", "tsb"):
        # never stamp an unrecognized broker name onto a memory-broker
        # measurement — fail the phase loudly instead
        raise ValueError(
            f"BENCH_BROKER={broker_kind!r} not supported (memory|tsb)"
        )
    if broker_kind == "tsb":
        # route the whole chat path through the native tsbroker so the
        # recorded TTFT includes a real broker transport (README testing
        # honesty: tsb is the e2e-proven broker in this image)
        from langstream_tpu.native import BrokerProcess

        broker_proc = BrokerProcess().start()
        instance_yaml = (
            "instance:\n"
            "  streamingCluster:\n"
            "    type: \"tpustream\"\n"
            "    configuration:\n"
            f"      bootstrap: \"127.0.0.1:{broker_proc.port}\"\n"
        )

    serving = {
        "model": MODEL,
        "slots": SLOTS,
        "max-seq-len": MAX_SEQ,
        "max-tokens": MAX_TOKENS,
        "decode-chunk": DECODE_CHUNK,
        # TTFT phase: short sequential chunks under light load, and the
        # engine pre-compiles both regimes before the first real request
        "decode-chunk-light": 8,
        "warmup-on-start": True,
        "quantize": QUANTIZE,
        "kv-layout": KV_LAYOUT,
        **({"kv-quantize": KV_QUANT} if KV_QUANT else {}),
    }
    # sub-saturation: ~4000 tok/s at 48-token answers supports ~80 req/s;
    # drive at 4/s so queueing is negligible and TTFT measures the path
    try:
        out = await run_gateway_bench(
            serving,
            prompt=PROMPT,
            max_tokens=48,
            requests=64,
            warmup=6,
            arrival_rate_hz=4.0,
            instance_yaml=instance_yaml,
        )
        out["broker"] = broker_kind
        return out
    finally:
        if broker_proc is not None:
            broker_proc.stop()


def _stream_tbt_gate(out: dict) -> dict:
    """ROADMAP item 5's leftover wired in: the streaming phase's measured
    client-observed TBT p99 is judged against an absolute per-token
    latency budget (``BENCH_TBT_P99_BUDGET_S``, seconds; default 0.25 —
    the 4 Hz floor a reading human perceives as continuous) and the
    verdict rides the phase output. Together with perf_diff's relative
    ``gateway_stream_tbt_p99_s`` gate (±10% round-over-round), decode-
    chunk tuning is held to the product-latency guarantee in the record
    itself, not just observed."""
    if not isinstance(out, dict):
        return out
    budget = float(os.environ.get("BENCH_TBT_P99_BUDGET_S", "0.25") or 0)
    if budget <= 0:
        return out  # record-only posture: gate explicitly disabled
    tbt = out.get("gateway_stream_tbt_p99_s")
    out["tbt_p99_budget_s"] = budget
    out["tbt_p99_within_budget"] = (
        tbt is not None and float(tbt) <= budget
    )
    if not out["tbt_p99_within_budget"]:
        out["gate_violation"] = (
            f"gateway_stream_tbt_p99_s {tbt} over the "
            f"{budget}s product budget"
        )
    return out


async def _child_phase(phase: str) -> dict:
    if phase == "decode":
        return await _phase(
            run_decode_bench(
                KV_LAYOUT, BENCH_REQUESTS, kv_quantize=KV_QUANT
            )
        )
    if phase == "kv_int8":
        return await _phase(
            run_decode_bench(KV_LAYOUT, BENCH_REQUESTS // 2, kv_quantize="int8")
        )
    if phase == "gateway":
        return await _phase(run_gateway_phase())
    if phase == "speculative":
        return await _phase(run_speculative_phase())
    if phase == "qos_mix":
        return await _phase(run_qos_mix_phase())
    if phase == "prefix":
        return await _phase(
            run_prefix_cache_phase(), budget_s=min(PHASE_BUDGET_S, 300)
        )
    if phase == "prefix_warm":
        sys.path.insert(0, os.path.join(os.path.dirname(_BENCH_PATH), "tools"))
        from gateway_bench import run_warm_prefix_phase

        return await _phase(
            run_warm_prefix_phase(), budget_s=min(PHASE_BUDGET_S, 300)
        )
    if phase == "oom_storm":
        sys.path.insert(0, os.path.join(os.path.dirname(_BENCH_PATH), "tools"))
        from gateway_bench import run_oom_storm_phase

        return await _phase(
            run_oom_storm_phase(), budget_s=min(PHASE_BUDGET_S, 240)
        )
    if phase == "partition_storm":
        sys.path.insert(0, os.path.join(os.path.dirname(_BENCH_PATH), "tools"))
        from gateway_bench import run_partition_storm_phase

        return await _phase(
            run_partition_storm_phase(), budget_s=min(PHASE_BUDGET_S, 240)
        )
    if phase == "gateway_stream":
        sys.path.insert(0, os.path.join(os.path.dirname(_BENCH_PATH), "tools"))
        from gateway_bench import run_stream_phase

        out = await _phase(
            run_stream_phase(), budget_s=min(PHASE_BUDGET_S, 240)
        )
        return _stream_tbt_gate(out)
    if phase == "multi_lora":
        sys.path.insert(0, os.path.join(os.path.dirname(_BENCH_PATH), "tools"))
        from gateway_bench import run_multi_lora_phase

        return await _phase(
            run_multi_lora_phase(), budget_s=min(PHASE_BUDGET_S, 300)
        )
    raise ValueError(f"unknown bench phase {phase!r}")


def _child_main() -> None:
    phase = os.environ["BENCH_PHASE"]
    out_path = os.environ.get("BENCH_PHASE_OUT")
    try:
        if phase == "probe":
            result = _child_probe()
        else:
            result = asyncio.run(_child_phase(phase))
            if isinstance(result, dict) and "hbm" not in result:
                hbm = _mem_snapshot()
                if hbm:
                    result["hbm"] = hbm
    except Exception as e:
        traceback.print_exc(file=sys.stderr)
        result = {"error": f"{type(e).__name__}: {e}"}
    payload = json.dumps(result)
    if out_path:
        # atomic write: a SIGKILL mid-write must not leave partial JSON
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            f.write(payload)
        os.replace(tmp, out_path)
    else:  # standalone debugging: BENCH_PHASE=decode python bench.py
        print(payload, flush=True)
    sys.stderr.flush()
    # abandoned phase threads (blocked on a wedged device) are non-daemon;
    # a normal interpreter exit would join them forever — the result is
    # written, leave unconditionally
    os._exit(0)


def _failed(record: dict) -> list[str]:
    """Names of what did not run to an end: the probe, or any phase whose
    result carries ``error``."""
    detail = record["detail"]
    failed = ["device_probe"] if "device_probe" in detail else []
    failed += [
        key for key, value in detail.items()
        if isinstance(value, dict) and "error" in value
        and key != "analyzer"  # the record's own meta, not a phase
    ]
    return failed


def main() -> None:
    configure_compile_cache()  # before any child's first `import jax`
    if _IS_CHILD:
        _child_main()
        return  # unreachable (os._exit)
    result = run_bench()
    _emit(result)
    failed = _failed(result)
    if failed:
        print(f"bench failed: {', '.join(failed)}", file=sys.stderr)
    sys.stderr.flush()
    os._exit(1 if failed else 0)


if __name__ == "__main__":
    main()
