"""A hybrid model (``nemotron-3-nano-30b-a3b-ep8`` or, with ``--config``,
``granite-4.0-h-small-ep2`` or ``solar-open2-250b-ep8``) at its published widths on the chip, without the
benchmark's harness around it: builds the engine from a configuration's
``serving`` block, runs the reference check as the file states it (the
reference the file names) and again
with the recurrent state, then the router, kept in bfloat16 (the readings
the file's ``state_rms_share`` and ``first_routing_differing_share`` have to lie
under), then a wave of long prompts and a full batch of decodes with the
device's memory after each.

    chiprun -- python3 tools/hybrid_probe.py [--config <name or file>]
        [--seeds n ...] [--controls n] [--faults] [--crossover]
        [--checks-only] [--no-warmup] [--trace 1]

``--trace 1`` ends with the device time of a decode step by scope
(``by_scope_ms_step``; steps counted as the benchmark's readers count them).
Refuses to run off a TPU. Prints one JSON line last."""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))
sys.path.insert(1, ROOT)
sys.path.insert(2, os.path.join(ROOT, "tools"))


def memory(stage: str) -> dict:
    import jax

    st = jax.devices()[0].memory_stats() or {}
    row = {k: round(st.get(k, 0) / 1e9, 3)
           for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
    print(f"[probe] memory after {stage}: {row}", flush=True)
    return row


def crossover(engine) -> list[dict]:
    """One expert layer's routed pass at the served widths and share by
    rows, the dense pass, the grouped XLA loop and the grouped kernel
    (``tools/routed_pass.py`` ``crossover`` over this engine's first expert
    layer, read from the stacks, and its router): milliseconds a call, and
    at 1,024 and 4,096 rows each grouped form by scope. ``models/moe.py``
    ``DENSE_ROWS_MAX`` is held to this. At a share the kernel's pass is NOT
    what is served (``moe.grouped_form`` keeps the loop there) and gives
    every pair a sorted row, held here or not: the third column of PR 47's
    table at 16 of 128 was read with a form that sorted the held pairs alone
    and added them 256 rows at a time (commit f49ab30, taken out again)."""
    import jax.numpy as jnp

    import routed_pass
    from langstream_tpu.models import moe

    c, lp = engine.model_config, engine.params["moe"]

    def route(h):
        if c.router == "sigmoid":
            return moe.sigmoid_topk_routing(
                h, lp["router"][0], lp["bias"][0], c.experts_per_token,
                c.routed_scale)
        return moe.softmax_topk_routing(h, lp["router"][0], c.experts_per_token)

    return routed_pass.crossover(
        c.hidden, c.dtype, route, lp["w_up"], lp["w_down"], jnp.int32(0),
        c.expert_first, moe.EXPERT_ACTS[c.expert_act],
        trace_rows=(1024, 4096))


async def run(args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine
    with open(args.config) as f:
        config = json.load(f)
    if args.no_warmup:
        config["serving"]["warmup-on-start"] = False
    reference = importlib.import_module(f"reference.{config['reference']}")
    out: dict = {"device": jax.devices()[0].device_kind}
    t = time.monotonic()
    engine = TpuServingEngine(ServingConfig.from_dict(config["serving"]))
    out["build_s"] = round(time.monotonic() - t, 1)
    out["kernel"] = engine.paged_read_kernel
    out["ssm_state_kernel"] = engine.stats()["ssm_state_kernel"]
    out["memory_built"] = memory("engine build")
    tolerance = config["reference_tolerance"]
    mc = engine.model_config
    from langstream_tpu.ops import selfcheck

    # the family's state kernel at this model's shapes: Mamba-2's or, for a
    # pattern of delta-rule layers, the delta rule's
    out["kernel_check"] = (
        selfcheck.check_delta_state_kernel if mc.delta_layers
        else selfcheck.check_state_kernel)(mc, slots=8)
    print(f"[probe] kernel: {json.dumps(out['kernel_check'])[:600]}", flush=True)
    if mc.delta_layers:     # and a prefill's chunked rule (ops/delta_chunk.py)
        out["chunk_kernel_check"] = selfcheck.check_delta_chunk_kernel(mc)
        print(f"[probe] kernel: {json.dumps(out['chunk_kernel_check'])[:600]}",
              flush=True)
    controls = (
        ("as served", None),
        # the readings the file's state_rms_share and first_routing_differing_share
        # have to lie under: each has to come out as not passed
        ("state bfloat16", dataclasses.replace(mc, state_dtype=jnp.bfloat16)),
        ("router bfloat16", dataclasses.replace(mc, router_dtype=jnp.bfloat16)),
    )
    for name, variant in controls:
        if args.only and args.only not in name:
            continue
        t = time.monotonic()
        for seed in args.seeds if variant is None else args.seeds[:args.controls]:
            report = await asyncio.to_thread(
                reference.check_engine, engine, seed, tolerance, config=variant)
            report.pop("positions")
            for k in ("state_rms_share_by_layer", "first_state_rms_share_by_head"):
                report[k] = [round(v, 5) for v in report[k]]
            print(f"[probe] check, {name}, seed {seed}: "
                  f"{json.dumps(report)} ({time.monotonic() - t:.1f} s)",
                  flush=True)
            out.setdefault(f"check, {name}", []).append(report)
    memory("reference checks")
    if args.crossover:
        out["crossover"] = await asyncio.to_thread(crossover, engine)
    if args.faults:
        # the served program once, against the reference with each term of
        # the published equations left out (or put in) in turn: each has to
        # come out as not passed
        got = await asyncio.to_thread(
            reference.served, engine, args.seeds[0],
            **getattr(reference, "FAULT_CHECK", {}))
        for fault in reference.FAULTS:
            report = await asyncio.to_thread(
                reference.judge, engine, got, tolerance, (fault,))
            row = {k: report.get(k) for k in (
                "passed", "worst_rms_share", "worst_correlation",
                "first_state_rms_share", "prefill_state_rms_share",
                "worst_routing_shortfall",
                "first_routing_shortfall", "first_routing_differing_share",
                "routing_decisions_differing")}
            print(f"[probe] fault {fault}: {json.dumps(row)}", flush=True)
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
    for n, prompt, max_tokens in ((1, 60, 2), (8, 1000, 1), (slots, 200, 65),
                                  (slots, 200, 65)):
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
            {"n": n, "prompt": prompt, "seconds": seconds, "phases": rows})
        memory(f"wave {n} x {prompt}")
    if args.trace:
        from lib import hybridtrace, xplane

        if mc.delta_layers:     # the delta-rule programs name more seams
            from lib import roofline_delta

            hybridtrace.SCOPES = hybridtrace.SCOPES + roofline_delta.SCOPES
        trace_dir = os.path.join(ROOT, "chiprun_out", "hybrid_probe_trace")
        # long enough at either configuration that the trace, 4 s in, falls
        # on decode chunks (96 slots' prefill and 128 steps were over by
        # then), and once untraced first: the longer rows' decode windows
        # compile on their first use, and a trace over a compile is empty
        await wave(slots, 200, 257)
        task = asyncio.ensure_future(wave(slots, 200, 257))
        await asyncio.sleep(4.0)
        await asyncio.to_thread(jax.profiler.start_trace, trace_dir)
        await asyncio.sleep(2.5)
        await asyncio.to_thread(jax.profiler.stop_trace)
        await task
        path = hybridtrace.find_trace(trace_dir)
        reduced = hybridtrace.reduce(path)
        plain = xplane.reduce(xplane.load(path), 2.5)
        runs = xplane.program(plain, "decode_chunk")
        # the program scans the model's blocks: inside a run the most
        # frequent op ran steps x blocks times (a run cut by an end of the
        # trace counts the steps that ran inside it)
        blocks = len(mc.blocks)
        steps = sum(round(n / blocks) for n in runs["op_counts"] if n >= blocks)
        per_step = lambda table: {  # noqa: E731
            k: round(1e3 * v / steps, 3)
            for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:16]
        } if steps else {}
        # a conditional's own event spans its children's, which are counted
        # under their scopes (the reduction does not know it for a container)
        conds = sum(v for k, v in reduced["scopes"]["unscoped"].items()
                    if k.startswith("cond."))
        out["trace"] = {
            "steps": steps,
            "step_ms": round(
                1e3 * (reduced["scopes"]["total_s"] - conds) / steps, 3)
            if steps else None,
            "by_scope_ms_step": per_step(reduced["scopes"]["by_scope"]),
            "unscoped_ms_step": per_step(reduced["scopes"]["unscoped"]),
            "by_scope_s": reduced["scopes"]["by_scope"],
            "unscoped_s": dict(sorted(reduced["scopes"]["unscoped"].items(),
                                      key=lambda kv: -kv[1])[:12]),
            "decode_total_s": reduced["scopes"]["total_s"],
            "decode_runs": runs["runs"], "op_counts": runs["op_counts"],
            "durations_s": runs["durations_s"],
            "top_ops": xplane.top_ops(plain, 14),
            "flight_steps": [s.get("steps") for s in engine.flight.recent(12)
                             if s["phase"] == "decode"],
        }
        print("[probe] trace: " + json.dumps(out["trace"]), flush=True)
    out["memory_end"] = memory("end")
    await engine.close()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="nemotron-3-nano-30b-a3b-ep8",
                    help="a hybrid configuration: a file, or the name of one "
                         "of bench/configs")
    ap.add_argument("--seeds", type=int, nargs="+", default=[2 ** 31 + 17])
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--faults", action="store_true",
                    help="also judge the program against each faulty reference")
    ap.add_argument("--controls", type=int, default=1,
                    help="seeds each lower-precision control is checked with")
    ap.add_argument("--only", default="",
                    help="run only the checks whose name holds this "
                         "('as served', 'state bfloat16', 'router bfloat16')")
    ap.add_argument("--crossover", action="store_true",
                    help="also time the dense and the grouped expert pass by rows")
    ap.add_argument("--checks-only", action="store_true",
                    help="stop after the reference checks")
    ap.add_argument("--no-warmup", action="store_true",
                    help="build the engine without its warm-up of every "
                         "shape: each program compiles when first met")
    args = ap.parse_args()
    if not os.path.exists(args.config):
        args.config = os.path.join(
            ROOT, "bench", "configs", f"{args.config}.json")
    from langstream_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    if jax.default_backend() != "tpu":
        print("tools/hybrid_probe.py: no TPU; nothing was run", file=sys.stderr)
        return 3
    out = asyncio.run(run(args))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "hybrid_probe.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
