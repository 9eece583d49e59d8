"""A model of the window-and-full family (``--config``: ``trinity-large-
preview-ep8``, ``mellum2-12b-a2.5b-8l``) at its published widths on the chip,
without the benchmark's harness around it (``tools/hybrid_probe.py``'s twin
for ``models/swa.py``): builds the engine from the configuration's
``serving`` block, runs the reference check the file names
(``bench/reference/afmoe.py``, ``mellum.py``) over ``--seeds``, with
``--faults`` judges the last seed's served output against each faulty
reference (each has to come out as not passed), with ``--crossover`` times
one expert layer's dense pass and both forms of its grouped pass by rows
(``tools/routed_pass.py``), then one prefill of every
bucket a slot can hold and two full batches of decodes, with the allocator's
peak after each: the numbers the configuration's ``memory`` quotes.

    chiprun -- python3 tools/swa_probe.py [--config <name or file>]
        [--seeds n ...] [--faults] [--crossover] [--checks-only]
        [--no-checks] [--no-warmup] [--trace 1]

``--trace 1`` ends with the device time of a decode step and of a prefill by
scope (``moe_ms``: a prefill's ``moe_dispatch`` / ``moe_experts`` /
``moe_combine``). ``--rehearse-cpu`` walks the path at the configuration's tiny preset
here. Refuses to run off a TPU otherwise. Prints one JSON line last."""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))
sys.path.insert(1, ROOT)
sys.path.insert(2, os.path.join(ROOT, "tools"))

#: the member's test-size twin by the reference its file names (the other
#: member's is its bench test's fixture: :func:`tiny_of`)
TINY = {
    "reference": "afmoe",
    "serving": {"model": "trinity-tiny", "slots": 6, "max-seq-len": 512,
                "kv-layout": "paged", "kv-block-size": 8, "prefix-cache": False,
                "prefill-batch": 1, "decode-chunk": 8, "model-dtype": "float32"},
    "reference_tolerance": {
        "rms_share": 0.05, "min_correlation": 0.998,
        "window_rows_rms_share": 0.01, "routing_margin": 0.25,
        "first_routing_differing_share": 0.1,
        "router_alone_differing_share": 1e-3,
        "engine_first_token_shortfall": 0.25,
        "engine_first_logprob_error": 0.05,
        "engine_decode_token_shortfall": 0.25,
        "engine_decode_logprob_error": 0.05,
        "check_prompts": [100, 200], "check_decode_steps": 48},
}
REPORT_KEYS = (
    "passed", "worst_rms_share", "worst_correlation", "window_rows_rms_share",
    "worst_routing_shortfall", "first_routing_differing_share",
    "router_alone_differing_share", "engine_first_token_shortfall",
    "engine_first_logprob_error", "engine_decode_token_shortfall",
    "engine_decode_logprob_error", "engine_decode_steps_compared",
    "engine_decode_steps_parted", "window_slot_blocks_max",
    "window_ring_blocks", "slots_live", "rows_live", "prefill_batches",
    "held_pairs_a_token_decode", "decode_tokens_distinct")


def tiny_of(config: dict) -> dict:
    if config["reference"] == "afmoe":
        return TINY
    with open(os.path.join(ROOT, "tests", "bench", "fixtures", "wf", "configs",
                           "mellum-tiny.json")) as f:
        tiny = json.load(f)
    tiny["serving"]["model-dtype"] = "float32"
    return tiny


def crossover(engine, rows_list=None, trace_rows=(1024, 4096),
              interpret=False) -> list[dict]:
    """One expert layer's routed pass at the served widths and share by
    rows, the dense pass, the grouped XLA loop and the grouped kernel
    (``tools/routed_pass.py`` ``crossover`` over this engine's first expert
    layer and its router): milliseconds a call, and at ``trace_rows`` each
    grouped form by scope. ``models/moe.py`` ``DENSE_ROWS_MAX`` is held to
    this."""
    import jax.numpy as jnp

    import routed_pass
    from langstream_tpu.models import moe

    c = engine.model_config
    lp = engine.params["layers"][c.dense_layers]["moe"]

    def route(h):
        if c.router == "sigmoid":
            return moe.sigmoid_topk_routing(
                h, lp["router"], lp["bias"], c.experts_per_token, c.routed_scale)
        return moe.softmax_topk_routing(h, lp["router"], c.experts_per_token)

    return routed_pass.crossover(
        c.hidden, c.dtype, route, lp["w_up"][None], lp["w_down"][None],
        jnp.int32(0), c.expert_first, moe.EXPERT_ACTS[c.expert_act],
        rows_list or routed_pass.ROWS, trace_rows, interpret=interpret)


def memory(stage: str) -> dict:
    import jax

    st = jax.devices()[0].memory_stats() or {}
    row = {k: round(st.get(k, 0) / 1e9, 3)
           for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
    print(f"[probe] memory after {stage}: {row}", flush=True)
    return row


def by_scope(path: str, program: str, over: float) -> dict:
    """Device milliseconds of ``program``'s operations by scope over
    ``over`` (steps, or runs), the sixteen largest."""
    from lib import roofline_wf

    # the family's scopes and the full layers' rotation's
    reduced = roofline_wf.scope_seconds(path, program)
    per = lambda table: {  # noqa: E731
        k: round(1e3 * v / over, 3)
        for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:16]}
    return {"by_scope_ms": per(reduced["by_scope"]),
            # the routed experts' three, whatever their rank: the gather in
            # (and the sort), the matmuls, the gather back
            "moe_ms": {k: round(1e3 * reduced["by_scope"].get(k, 0.0) / over, 3)
                       for k in ("moe_dispatch", "moe_experts", "moe_combine")},
            "unscoped_ms": per(reduced["unscoped"]),
            "total_ms": round(1e3 * (sum(reduced["by_scope"].values())
                                     + sum(reduced["unscoped"].values()))
                              / over, 3)}


async def run(args) -> dict:
    import jax
    import numpy as np

    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    with open(args.config) as f:
        config = json.load(f)
    if args.rehearse_cpu:
        config = tiny_of(config)
    if args.no_warmup:
        config["serving"]["warmup-on-start"] = False
    reference = importlib.import_module(f"reference.{config['reference']}")
    out: dict = {"device": jax.devices()[0].device_kind}
    t = time.monotonic()
    engine = TpuServingEngine(ServingConfig.from_dict(config["serving"]))
    out["build_s"] = round(time.monotonic() - t, 1)
    out["kernel"] = engine.paged_read_kernel
    out["memory_built"] = memory("engine build")
    out["pools"] = {k: v for k, v in engine.block_mgr.stats().items()
                    if "num_blocks" in k or "ring" in k}
    tolerance = config["reference_tolerance"]
    if args.crossover:
        sizes = ((16, 64), (), True) if args.rehearse_cpu else ()
        out["crossover"] = await asyncio.to_thread(crossover, engine, *sizes)
    got = None
    for seed in [] if args.no_checks else args.seeds:
        t = time.monotonic()
        got = await asyncio.to_thread(
            lambda: reference.served(engine, seed, **{
                k: v for k, v in (
                    ("prompts", tolerance.get("check_prompts")),
                    ("steps", tolerance.get("check_decode_steps"))) if v}))
        report = await asyncio.to_thread(reference.judge, engine, got, tolerance)
        row = {k: report.get(k) for k in REPORT_KEYS}
        print(f"[probe] check, as served, seed {seed}: {json.dumps(row)} "
              f"({time.monotonic() - t:.1f} s)", flush=True)
        out.setdefault("checks", []).append(row)
        memory(f"reference check, seed {seed}")
    if args.faults and got is not None:
        # the LAST seed's served output against the reference with each term
        # of the published equations changed in turn
        for fault in reference.FAULTS:
            t = time.monotonic()
            report = await asyncio.to_thread(
                reference.judge, engine, got, tolerance, (fault,))
            row = {k: report.get(k) for k in REPORT_KEYS[:7]}
            print(f"[probe] fault {fault}: {json.dumps(row)} "
                  f"({time.monotonic() - t:.1f} s)", flush=True)
            out.setdefault("faults", {})[fault] = row
    if args.checks_only:
        await engine.close()
        return out
    rng = np.random.default_rng(args.seeds[0] % 2 ** 32)
    vocab = engine.model_config.vocab_size

    async def wave(n, prompt, max_tokens):
        t = time.monotonic()
        await asyncio.gather(*(
            engine.generate([int(x) for x in rng.integers(3, vocab, size=prompt)],
                            {"max-tokens": max_tokens, "temperature": 0})
            for _ in range(n)))
        return time.monotonic() - t

    slots = int(config["serving"]["slots"])
    longest = int(config["serving"]["max-seq-len"])
    # one prompt at seven eighths of every prefill bucket a slot can hold,
    # then two full batches: at half and at the whole of a slot's share of
    # the full kind's pool
    buckets, b = [], 32 if args.rehearse_cpu else 128
    while b - b // 8 + 2 < longest:
        buckets.append(b)
        b *= 2
    share = min(longest, engine.paged_layout.num_blocks
                * engine.paged_layout.block_size // slots) - 140
    waves = [(1, b - b // 8, 2) for b in buckets] + [
        (slots, share // 2, 33 if args.rehearse_cpu else 65),
        (slots, share, 33 if args.rehearse_cpu else 129)]
    for n, prompt, max_tokens in waves:
        before = engine.flight.recorded
        seconds = await wave(n, prompt, max_tokens)
        samples = engine.flight.recent(engine.flight.recorded - before)
        rows = {}
        for s in samples:
            r = rows.setdefault(s["phase"], {"n": 0, "device_ms": 0.0, "steps": 0})
            r["n"] += 1
            r["device_ms"] += s["device_ms"]
            r["steps"] += s.get("steps", 0)
        print(f"[probe] wave {n} x {prompt} tokens, max-tokens {max_tokens}: "
              f"{seconds:.2f} s {json.dumps(rows)}", flush=True)
        out.setdefault("waves", []).append(
            {"n": n, "prompt": prompt, "seconds": seconds, "phases": rows,
             "memory": memory(f"wave {n} x {prompt}")})
    if args.trace:
        from lib import hybridtrace, xplane

        trace_dir = os.path.join(ROOT, "chiprun_out", "swa_probe_trace")
        # one prefill of the 8,192 bucket, then decode chunks of the full
        # batch over long slots, inside one trace
        task = asyncio.ensure_future(wave(slots, share - 140, 257))
        await asyncio.sleep(args.trace_after)
        await asyncio.to_thread(jax.profiler.start_trace, trace_dir)
        one = await wave(1, min(8000, buckets[-1] - 200), 2)
        await asyncio.sleep(2.5)
        await asyncio.to_thread(jax.profiler.stop_trace)
        await task
        path = hybridtrace.find_trace(trace_dir)
        plain = xplane.reduce(xplane.load(path), 2.5)
        calls = xplane.ops_in(plain, "decode_chunk", r"^paged_read[._]")["calls"]
        steps = calls / engine.model_config.layers
        prefills = xplane.program(plain, "prefill")
        out["trace"] = {
            "decode_steps": steps, "prefill_s": one,
            "decode": by_scope(path, "decode_chunk", steps) if steps else None,
            "prefill_runs": prefills["runs"],
            "prefill_durations_s": prefills["durations_s"],
            "prefill": (by_scope(path, "prefill", prefills["runs"])
                        if prefills["runs"] else None),
            "top_ops": xplane.top_ops(plain, 14),
        }
        print("[probe] trace: " + json.dumps(out["trace"]), flush=True)
    out["memory_end"] = memory("end")
    await engine.close()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="trinity-large-preview-ep8",
                    help="a configuration of the family: a file, or the name "
                         "of one of bench/configs")
    ap.add_argument("--seeds", type=int, nargs="+", default=[2 ** 31 + 41])
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--trace-after", type=float, default=25.0,
                    help="seconds of the traced wave before the trace starts "
                         "(its prefills are over by then)")
    ap.add_argument("--faults", action="store_true",
                    help="also judge the program against each faulty reference")
    ap.add_argument("--crossover", action="store_true",
                    help="also time the dense and the grouped expert pass by rows")
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
        print("tools/swa_probe.py: no TPU; nothing was run", file=sys.stderr)
        return 3
    out = asyncio.run(run(args))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "swa_probe.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
