"""An EVA decoder (``--config evabyte-6.5b-8l``) at its published widths on
the chip, without the benchmark's harness around it (``tools/swa_probe.py``'s
twin for ``models/eva.py``): builds the engine from the configuration's
``serving`` block, says what the device holds against the file's arithmetic,
runs the reference check (``bench/reference/evabyte.py``) over ``--seeds``,
with ``--faults`` judges each seed's served output against each faulty
reference (each has to come out as not passed), then (unless
``--checks-only``) one prefill of every bucket the cell's prompts reach and a
full batch of decodes at long contexts, with the allocator's peak after each
and the flight samples' device milliseconds.

    chiprun -- python3 tools/eva_probe.py [--config <name or file>]
        [--seeds n ...] [--faults] [--checks-only] [--no-checks]
        [--no-warmup]

``--rehearse-cpu`` walks the path at the ``evabyte-tiny`` preset here
(``tests/bench/fixtures/eva``). Refuses to run off a TPU otherwise. Prints
one JSON line last and writes it to ``chiprun_out/eva_probe.json``."""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))
sys.path.insert(1, ROOT)

REPORT_KEYS = (
    "passed", "worst_rms_share", "mean_rms_share", "worst_correlation",
    "heads_rms_share", "ring_rows_rms_share", "summary_rows_rms_share",
    "logits_bfloat16_grid_share", "engine_first_token_shortfall",
    "engine_first_logprob_error",
    "engine_decode_token_shortfall", "engine_decode_logprob_error",
    "engine_decode_steps_compared", "engine_decode_steps_parted",
    "engine_decode_steps_across_an_edge", "ring_rows_compared",
    "summary_rows_compared", "slots_live", "rows_live", "window_edges_crossed",
    "chunk_closes")


def memory(stage: str) -> dict:
    import jax

    st = jax.devices()[0].memory_stats() or {}
    row = {k: round(st.get(k, 0) / 1e9, 3)
           for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
    print(f"[probe] memory after {stage}: {row}", flush=True)
    return row


async def run(args) -> dict:
    import jax
    import numpy as np

    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine
    from reference import evabyte as reference

    with open(args.config) as f:
        config = json.load(f)
    if args.rehearse_cpu:
        with open(os.path.join(ROOT, "tests", "bench", "fixtures", "eva",
                               "configs", "evabyte-tiny.json")) as f:
            config = json.load(f)
        config["serving"]["model-dtype"] = "float32"
    if args.no_warmup:
        config["serving"]["warmup-on-start"] = False
    out: dict = {"device": jax.devices()[0].device_kind}
    t = time.monotonic()
    engine = TpuServingEngine(ServingConfig.from_dict(config["serving"]))
    out["build_s"] = round(time.monotonic() - t, 1)
    out["kernel"] = engine.paged_read_kernel
    out["memory_built"] = memory("engine build")
    out["pools"] = {k: v for k, v in engine.block_mgr.stats().items()
                    if "num_blocks" in k or "ring" in k}
    tolerance = config["reference_tolerance"]
    how = {k: v for k, v in (
        ("prompts", tolerance.get("check_prompts")),
        ("steps", tolerance.get("check_decode_steps"))) if v}
    for seed in [] if args.no_checks else args.seeds:
        t = time.monotonic()
        got = await asyncio.to_thread(
            lambda: reference.served(engine, seed, **how))
        served_s = time.monotonic() - t
        report = await asyncio.to_thread(reference.judge, engine, got, tolerance)
        row = {k: report.get(k) for k in REPORT_KEYS}
        print(f"[probe] check, as served, seed {seed}: {json.dumps(row)} "
              f"(served {served_s:.1f} s, in all {time.monotonic() - t:.1f} s)",
              flush=True)
        out.setdefault("checks", []).append(row)
        memory(f"reference check, seed {seed}")
        for fault in (args.only_faults or reference.FAULTS) * args.faults:
            t = time.monotonic()
            report = await asyncio.to_thread(
                reference.judge, engine, got, tolerance, (fault,))
            row = {k: report.get(k) for k in REPORT_KEYS[:8]}
            print(f"[probe] seed {seed}, fault {fault}: {json.dumps(row)} "
                  f"({time.monotonic() - t:.1f} s)", flush=True)
            out.setdefault("faults", {}).setdefault(str(seed), {})[fault] = row
    if args.checks_only:
        await engine.close()
        return out
    rng = np.random.default_rng(args.seeds[0] % 2 ** 32)
    vocab = engine.model_config.vocab_size
    slots = int(config["serving"]["slots"])
    longest = int(config["serving"]["max-seq-len"])

    async def wave(n, prompt, max_tokens):
        before = engine.flight.recorded
        t = time.monotonic()
        await asyncio.gather(*(
            engine.generate([int(x) for x in rng.integers(3, vocab, size=prompt)],
                            {"max-tokens": max_tokens, "temperature": 0})
            for _ in range(n)))
        seconds = time.monotonic() - t
        rows = {}
        for s in engine.flight.recent(engine.flight.recorded - before):
            r = rows.setdefault(s["phase"], {"n": 0, "device_ms": 0.0, "steps": 0})
            r["n"] += 1
            r["device_ms"] += s["device_ms"]
            r["steps"] += s.get("steps", 0)
        print(f"[probe] wave {n} x {prompt} tokens, max-tokens {max_tokens}: "
              f"{seconds:.2f} s {json.dumps(rows)}", flush=True)
        out.setdefault("waves", []).append(
            {"n": n, "prompt": prompt, "seconds": seconds, "phases": rows,
             "memory": memory(f"wave {n} x {prompt}")})

    buckets, b = [], 64 if args.rehearse_cpu else 4096
    while b <= longest:
        buckets.append(b)
        b *= 2
    steps = 17 if args.rehearse_cpu else 129
    # twice a bucket: the first run compiles where the engine's warm-up did
    # not reach
    for b in buckets:
        for _ in range(2):
            await wave(1, b - b // 8, 2)
    # a full batch at the traffic's median and near its cap
    for prompt in ([100, 200] if args.rehearse_cpu else [12288, 26000]):
        await wave(slots, prompt, steps)
    out["memory_end"] = memory("end")
    await engine.close()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="evabyte-6.5b-8l",
                    help="a configuration of the family: a file, or the name "
                         "of one of bench/configs")
    ap.add_argument("--seeds", type=int, nargs="+", default=[2 ** 31 + 41])
    ap.add_argument("--faults", action="store_true",
                    help="also judge the program against each faulty reference")
    ap.add_argument("--only-faults", nargs="+", default=None,
                    help="with --faults: these of the reference's FAULTS alone")
    ap.add_argument("--checks-only", action="store_true")
    ap.add_argument("--no-checks", action="store_true")
    ap.add_argument("--no-warmup", action="store_true",
                    help="build the engine without its warm-up of every "
                         "shape: each program compiles when first met")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the same path at the tiny preset on the CPU")
    args = ap.parse_args()
    if not os.path.exists(args.config):
        args.config = os.path.join(
            ROOT, "bench", "configs", f"{args.config}.json")
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        print("[probe] REHEARSAL on the CPU at the tiny preset", flush=True)
    from langstream_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    if jax.default_backend() != "tpu" and not args.rehearse_cpu:
        print("tools/eva_probe.py: no TPU; nothing was run", file=sys.stderr)
        return 3
    out = asyncio.run(run(args))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "eva_probe.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
