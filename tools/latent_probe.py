"""A latent-attention model (``deepseek-v2-ep8``) at its published widths on
the chip, without the benchmark's harness around it: where a decode step's
and a prefill's time goes, before the harness says it.

    chiprun -- python3 tools/latent_probe.py [--config <name or file>]
        [--kernels] [--tiles n ...] [--flash-blocks q,k ...] [--seeds n ...]
        [--faults] [--checks-only] [--no-checks] [--trace 1]

``--kernels`` (before the engine is built, the chip to themselves): the two
kernels of the family alone, compiled at the served shapes against their
XLA expressions (``ops/selfcheck.py`` ``check_latent_kernels``), then timed:
the latent read over a full batch of slots of the cell's lengths against
its floor (``bench/lib/roofline_latent.py``), at each ``--tiles`` (blocks a
tile), and the flash kernel at the model's key and value widths on one
head group of each prefill bucket, told the true length, at each
``--flash-blocks``. Then the engine from the configuration's ``serving``
block, the reference check as the file states it, with ``--faults`` the
program against each faulty reference (each has to come out as not passed)
and the router served in bfloat16, then one prefill a bucket and a full
batch of decodes (with the tokens' and the experts' spread), and with
``--trace 1`` a decode step and a prefill by scope.

``--rehearse-cpu`` walks the path here at the tiny preset with the kernels
interpreted (says REHEARSAL; no time it prints means anything). Refuses to
run off a TPU otherwise. Prints one JSON line last."""

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

#: (true tokens, bucket) of one prefill a bucket: the cell's longest of each
PREFILLS = ((4000, 4096), (8067, 8192), (11939, 16384))


def say(message: str) -> None:
    print(f"[probe] {message}", flush=True)


def memory(stage: str) -> dict:
    import jax

    st = jax.devices()[0].memory_stats() or {}
    row = {k: round(st.get(k, 0) / 1e9, 3)
           for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
    say(f"memory after {stage}: {row}")
    return row


def timed(call, *args, repeats: int = 5) -> float:
    """Milliseconds a call: the mean of ``repeats`` after one warm-up."""
    import jax

    jax.block_until_ready(call(*args))
    t = time.monotonic()
    for _ in range(repeats):
        out = call(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.monotonic() - t) / repeats


def kernels(mc, config: dict, args) -> dict:
    """The family's two kernels alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lib import peaks, roofline_latent
    from langstream_tpu.ops import paged_attention, selfcheck
    from langstream_tpu.ops.flash_attention import flash_attention

    interpret = args.rehearse_cpu
    serving = config["serving"]
    bs = int(serving["kv-block-size"])
    out: dict = {"selfcheck": selfcheck.check_latent_kernels(
        mc, block_size=bs, read_blocks=4 if interpret else 24,
        batch=4 if interpret else 8, flash_seq=64 if interpret else 1024,
        interpret=interpret)}
    for row in out["selfcheck"]:
        say(f"kernel check: {json.dumps(row)[:600]}")
    slots = 4 if interpret else int(serving["slots"])
    per_slot = 4 if interpret else 104            # 6,656 rows a slot
    rng = np.random.default_rng(0)
    lengths = rng.integers(per_slot * bs // 2, per_slot * bs, size=slots)
    tables = 1 + np.arange(slots * per_slot).reshape(slots, per_slot)
    pool = jax.random.normal(
        jax.random.PRNGKey(1), (2, slots * per_slot + 1, bs, mc.row_width),
        mc.dtype)
    q = jax.random.normal(jax.random.PRNGKey(2),
                          (slots, mc.heads, mc.row_width), mc.dtype)
    rows = int(lengths.sum())
    floor_ms = None
    if not interpret:
        floor_ms = 1e3 * roofline_latent.latent_read_floor(
            roofline_latent.LatentShape.from_config(config), live_rows=rows,
            peaks=peaks.peaks_for(jax.devices()[0].device_kind))["floor_s"]
    out["latent_read"] = {"slots": slots, "live_rows": rows,
                          "floor_ms": floor_ms, "by_tile_blocks": {}}
    for tile in args.tiles:
        paged_attention.LATENT_TILE_BLOCKS = tile
        call = jax.jit(lambda q, p, t, n: paged_attention.latent_read(
            q, p, 1, t, n, num_read_blocks=per_slot, value_dim=mc.kv_rank,
            scale=mc.attn_scale, interpret=interpret))
        try:
            ms = timed(call, q, pool, jnp.asarray(tables, jnp.int32),
                       jnp.asarray(lengths, jnp.int32))
        except Exception as e:    # the compiler's words are the finding
            ms = f"{type(e).__name__}: {e}"[:300]
        out["latent_read"]["by_tile_blocks"][tile] = ms
        say(f"latent read, {slots} slots, {rows} rows, {tile} blocks a "
            f"tile: {ms} ms a call (floor {floor_ms})")
    del pool, q
    groups_of = {4096: 32, 8192: 16, 16384: 8}    # heads a group, a bucket
    out["flash"] = []
    for real, bucket in (((40, 64),) if interpret else PREFILLS):
        heads = 2 if interpret else groups_of[bucket]
        qf, kf, vf = (jax.random.normal(
            jax.random.PRNGKey(i), (1, bucket, heads, d), mc.dtype)
            for i, d in enumerate((mc.head_dim, mc.head_dim, mc.v_dim)))
        pairs = real * (real + 1) / 2 * heads
        flops = 2 * pairs * (mc.head_dim + mc.v_dim)
        for bq, bk in args.flash_blocks:
            row = {"real": real, "bucket": bucket, "heads": heads,
                   "block_q": bq, "block_k": bk}
            for name, n in (("told_lengths", jnp.asarray([real], jnp.int32)),
                            ("untold", None)):
                call = jax.jit(lambda q, k, v, n=n: flash_attention(
                    q, k, v, causal=True, scale=mc.attn_scale, block_q=bq,
                    block_k=bk, interpret=interpret, lengths=n))
                try:
                    ms = timed(call, qf, kf, vf, repeats=3)
                    row[f"{name}_ms"] = round(ms, 3)
                    row[f"{name}_tflops_true_pairs"] = round(
                        flops / ms / 1e9, 1)
                except Exception as e:
                    row[f"{name}_ms"] = f"{type(e).__name__}: {e}"[:300]
            say(f"flash: {json.dumps(row)}")
            out["flash"].append(row)
    return out


async def run(args) -> dict:
    import jax
    import numpy as np

    from langstream_tpu.serving.engine import (
        ServingConfig,
        TpuServingEngine,
        _resolve_model_config,
    )

    with open(args.config) as f:
        config = json.load(f)
    serving = config["serving"]
    if args.rehearse_cpu:
        serving.update(paged_kernel="pallas-interpret")
        os.environ["LS_TPU_FLASH"] = "interpret"
    reference = importlib.import_module(f"reference.{config['reference']}")
    out: dict = {"device": jax.devices()[0].device_kind}
    mc = _resolve_model_config(serving["model"], int(serving["max-seq-len"]))
    if args.kernels:
        out["kernels"] = await asyncio.to_thread(kernels, mc, config, args)
        memory("kernels alone")
    t = time.monotonic()
    engine = TpuServingEngine(ServingConfig.from_dict(serving))
    out["build_s"] = round(time.monotonic() - t, 1)
    out["kernel"] = engine.paged_read_kernel
    out["memory_built"] = memory("engine build")
    tolerance = config["reference_tolerance"]
    keep = ("passed", "worst_rms_share", "worst_correlation",
            "latent_rms_share", "worst_routing_shortfall",
            "first_routing_differing_share", "routing_decisions",
            "routing_decisions_differing", "router_alone_differing_share",
            "engine_first_token_shortfall", "engine_first_logprob_error",
            "engine_decode_token_shortfall", "engine_decode_logprob_error",
            "engine_decode_steps_compared", "engine_decode_steps_parted",
            "slots_live", "rows_live", "held_pairs_a_token_prompt",
            "held_pairs_a_token_decode", "decode_tokens_distinct",
            "decode_tokens")
    got = None
    if not args.no_checks:
        for seed in args.seeds:
            t = time.monotonic()
            got = await asyncio.to_thread(reference.served, engine, seed)
            served_s = time.monotonic() - t
            report = await asyncio.to_thread(
                reference.judge, engine, got, tolerance)
            row = {k: report.get(k) for k in keep}
            say(f"check, as served, seed {seed}: {json.dumps(row)} (served "
                f"{served_s:.1f} s, all {time.monotonic() - t:.1f} s)")
            out.setdefault("checks", []).append(row)
        memory("reference checks")
    if args.faults and got is not None:
        # the served program once, against the reference with each term of
        # the published equations changed in turn, and the program's own
        # router in bfloat16 on the reference's input
        for fault in reference.FAULTS:
            report = await asyncio.to_thread(
                reference.judge, engine, got, tolerance, (fault,))
            row = {k: report.get(k) for k in keep}
            say(f"fault {fault}: {json.dumps(row)}")
            out.setdefault("faults", {})[fault] = row
        inputs = np.concatenate([
            reference.forward(engine.model_config, engine.params,
                              slot["sequence"], slot["positions"],
                              forced=slot["chose"])[1]["first_input"]
            for slot in got["slots"][-1:]])
        out["router_served_in_bfloat16"] = reference.router_alone(
            engine, inputs, "bfloat16")
        say(f"router served in bfloat16, alone: "
            f"{out['router_served_in_bfloat16']}")
    if args.checks_only:
        await engine.close()
        return out
    rng = np.random.default_rng(args.seeds[0] % 2 ** 32)
    vocab = 384 if args.byte_tokens else engine.model_config.vocab_size
    made: list = []

    async def wave(n, prompt, max_tokens):
        before = engine.flight.recorded
        t = time.monotonic()
        outs = await asyncio.gather(*(
            engine.generate([int(x) for x in rng.integers(3, vocab, size=prompt)],
                            {"max-tokens": max_tokens, "temperature": 0})
            for _ in range(n)))
        seconds = time.monotonic() - t
        made[:] = [o["tokens"] for o in outs]
        rows = {}
        for s in engine.flight.recent(engine.flight.recorded - before):
            r = rows.setdefault(s["phase"], {
                "n": 0, "device_ms": 0.0, "steps": 0, "routed_pairs": 0,
                "expert_load_max": 0})
            r["n"] += 1
            r["device_ms"] = round(r["device_ms"] + s["device_ms"], 1)
            for k in ("steps", "routed_pairs", "expert_load_max"):
                r[k] += s.get(k) or 0
        say(f"wave {n} x {prompt} tokens, max-tokens {max_tokens}: "
            f"{seconds:.2f} s {json.dumps(rows)}")
        out.setdefault("waves", []).append(
            {"n": n, "prompt": prompt, "seconds": seconds, "phases": rows})
        memory(f"wave {n} x {prompt}")

    slots = int(serving["slots"])
    small = args.rehearse_cpu
    for real, _ in (((40, 64),) if small else PREFILLS):
        await wave(1, real, 1)          # compiles the bucket
        await wave(1, real, 1)          # its time
    long_prompt, steps = (40, 9) if small else (6000, 65)
    await wave(slots, long_prompt, steps)
    out["decode_token_spread"] = {
        "distinct_tokens": len({t for row in made for t in row}),
        "of": sum(len(row) for row in made),
        "distinct_first_tokens": len({row[0] for row in made})}
    say(f"decoded tokens: {json.dumps(out['decode_token_spread'])}")
    if args.trace:
        from lib import hybridtrace, roofline_latent, xplane

        trace_dir = os.path.join(ROOT, "chiprun_out", "latent_probe_trace")
        # a batch one short of full decoding, and a prefill into the slot
        # left: traced together once every prompt of the wave is prefilled
        before = engine.flight.steps_by_phase.get("prefill", 0)
        task = asyncio.ensure_future(wave(slots - 1, long_prompt, 6 * steps))
        while engine.flight.steps_by_phase.get("prefill", 0) - before \
                < slots - 1 and not task.done():
            await asyncio.sleep(0.2)
        await asyncio.to_thread(jax.profiler.start_trace, trace_dir)
        await asyncio.sleep(0.1 if small else 1.2)
        await wave(1, PREFILLS[1][0] if not small else 40, 1)
        await asyncio.sleep(0.1 if small else 1.2)
        await asyncio.to_thread(jax.profiler.stop_trace)
        await task
        path = hybridtrace.find_trace(trace_dir)
        if path:
            plain = xplane.reduce(xplane.load(path))
            by_program = {}
            for part in ("decode_chunk", "prefill"):
                pooled = roofline_latent.scope_seconds(path, part)
                table = {**pooled["by_scope"], **{
                    f"(none) {k}": v for k, v in pooled["unscoped"].items()}}
                runs = xplane.program(plain, part)
                by_program[part] = {
                    "runs": runs["runs"],
                    "durations_s": [round(d, 4) for d in runs["durations_s"]],
                    "seconds_by_scope": {
                        k: round(v, 5) for k, v in sorted(
                            table.items(), key=lambda kv: -kv[1])[:18]},
                }
            # steps as the benchmark's readers count them: the read kernel's
            # calls inside the decode programs over the layers
            steps = xplane.ops_in(
                plain, roofline_latent.DECODE_PROGRAM,
                roofline_latent.READ_KERNEL)["calls"] / engine.model_config.layers
            seconds = xplane.ops_in(
                plain, roofline_latent.DECODE_PROGRAM, "")["total_s"]
            out["trace"] = {
                **by_program,
                "decode_steps": round(steps, 1),
                "decode_step_ms": round(1e3 * seconds / steps, 3) if steps else None,
                "top_ops": xplane.top_ops(plain, 16),
            }
            say("trace: " + json.dumps(out["trace"]))
    out["memory_end"] = memory("end")
    await engine.close()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="deepseek-v2-ep8",
                    help="a latent configuration: a file, or the name of one "
                         "of bench/configs")
    ap.add_argument("--seeds", type=int, nargs="+", default=[2 ** 31 + 17])
    ap.add_argument("--kernels", action="store_true",
                    help="first time the family's kernels alone")
    ap.add_argument("--tiles", type=int, nargs="+", default=[8],
                    help="blocks a tile of the latent read to time")
    ap.add_argument("--flash-blocks", nargs="+", default=["512,512"],
                    help="block_q,block_k pairs of the flash kernel to time")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--faults", action="store_true",
                    help="also judge the program against each faulty reference")
    ap.add_argument("--checks-only", action="store_true",
                    help="stop after the reference checks")
    ap.add_argument("--no-checks", action="store_true",
                    help="skip the reference checks")
    ap.add_argument("--byte-tokens", action="store_true",
                    help="draw the waves' prompts from the byte tokenizer's "
                         "ids alone, as the benchmark's traffic does")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="REHEARSAL at the tiny preset, kernels interpreted")
    args = ap.parse_args()
    args.flash_blocks = [tuple(int(x) for x in pair.split(","))
                         for pair in args.flash_blocks]
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        args.config = os.path.join(
            ROOT, "tests", "bench", "fixtures", "latent", "configs",
            "deepseek-tiny.json")
        args.flash_blocks = [(16, 16)]
    elif not os.path.exists(args.config):
        args.config = os.path.join(
            ROOT, "bench", "configs", f"{args.config}.json")
    from langstream_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    if args.rehearse_cpu:
        say("REHEARSAL on the CPU: no time below means anything")
    elif jax.default_backend() != "tpu":
        print("tools/latent_probe.py: no TPU; nothing was run", file=sys.stderr)
        return 3
    out = asyncio.run(run(args))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "latent_probe.json"), "w") as f:
        json.dump(out, f)
    print(("REHEARSAL " if args.rehearse_cpu else "") + json.dumps(out),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
