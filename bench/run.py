"""One run of one cell of the benchmark.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One new process, in this order: refuse unless JAX's first device is a TPU
with a row in the benchmark's table of peaks; read the cell, its
configuration and its traffic from their files; register the configuration's
widths with the engine's table of models; start control plane, gateway and
the in-process compute runtime and deploy the chat application
(``lib/app.py``) whose serving resource is the configuration's ``serving``
block; build the engine with a first request; check the served model against
its plain reference; warm up every prefill and decode shape the cell's
lengths can reach; start the load generator (a child process that never
imports JAX); measure for ``--seconds``; print one JSON object as the last
line of stdout; exit.

Everything before the window opens is ``setup_s``. ``--trace 1`` wraps a few
seconds in the middle of the window in ``jax.profiler.trace`` and reports the
per-layer metrics and a breakdown instead of the end-to-end metrics.

``--rehearse-cpu`` walks the same path on the CPU (for a configuration small
enough) and says REHEARSAL in place of a result line: it proves the harness,
never the system. ``--benchmark`` and ``--data-dir`` let a test (or a later
PR trying its files) point at another cell list and another root for
``traffic/``, ``layer_metrics/`` and ``end_to_end/`` files.
"""

from __future__ import annotations

import argparse
import asyncio
import glob
import json
import os
import shutil
import sys
import time
import traceback

_T0 = time.monotonic()  # set-up counts from process start
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)   # lib/, reference/, layer_metrics/
sys.path.insert(1, ROOT)    # langstream_tpu

POLL_S = 0.5
TRACE_S = 4.0
DEADLINE_S = 340.0  # a warm run must be out within 360 s
WIDTH_KEYS = {  # published config.json key -> the program's LlamaConfig field
    "vocab_size": "vocab_size", "hidden_size": "hidden",
    "num_hidden_layers": "layers", "num_attention_heads": "heads",
    "num_key_value_heads": "kv_heads", "head_dim": "head_dim",
    "intermediate_size": "intermediate", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
}


def log(message: str) -> None:
    print(f"[bench] {message}", flush=True)


def die(message: str, code: int = 3) -> "NoReturn":
    print(f"bench/run.py: {message}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def llama_fields(widths: dict) -> dict:
    return {WIDTH_KEYS[k]: v for k, v in widths.items() if k in WIDTH_KEYS}


def read_cell(args) -> dict:
    bench_file = args.benchmark or os.path.join(ROOT, "BENCHMARK.json")
    bench = load_json(bench_file)
    base = os.path.dirname(os.path.abspath(bench_file))
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        die(f"no workload {args.workload!r} in {bench_file}; known: "
            f"{sorted(cells)}", 2)
    cell = cells[args.workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(base, configs[cell["config"]]["file"]))
    roots = [r for r in (args.data_dir, BENCH) if r]
    from lib import observe

    traffic_path = observe.find("traffic", cell["traffic"], roots)
    if traffic_path is None:
        die(f"no traffic/{cell['traffic']}.json under {roots}", 2)

    def listed(kind):
        return [
            (m["name"], m["unit"]) for m in bench[kind]
            if "workloads" not in m or cell["name"] in m["workloads"]
        ]

    return {
        "cell": cell, "config": config, "traffic": load_json(traffic_path),
        "roots": roots, "end_to_end": listed("end_to_end"),
        "per_layer": listed("per_layer"),
    }


def register_model(config: dict) -> None:
    """The configuration's widths under its name in the engine's table of
    models — from here, so that no file of the program is edited."""
    from langstream_tpu.models.llama import LlamaConfig
    from langstream_tpu.serving import engine as engine_mod

    fields = llama_fields(config["widths"])
    engine_mod._MODEL_CONFIGS[config["serving"]["model"]] = (
        lambda max_seq_len: LlamaConfig(**fields, max_seq_len=max_seq_len)
    )


def posture_differs(engine, config: dict) -> dict:
    """What the engine serves against what the configuration's file states:
    the weight and pool types of its ``serving`` block and the read kernel
    under ``selects``. Empty when they agree."""
    serving = config["serving"]
    stated = {
        "quantize": serving.get("quantize"),
        "kv-quantize": serving.get("kv-quantize"),
        "paged_read_kernel": config["selects"]["paged_read_kernel"],
    }
    served = {
        "quantize": engine.config.quantize,
        "kv-quantize": engine.config.kv_quantize,
        "paged_read_kernel": engine.paged_read_kernel,
    }
    return {k: {"file": stated[k], "engine": served[k]}
            for k in stated if (stated[k] or None) != (served[k] or None)}


class Compiles:
    """When JAX lowered each new program."""

    # fires once for every new program (function and shapes), whether or
    # not the persistent cache then has its executable
    LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring

        self.at: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == self.LOWERED:
            self.at.append(time.monotonic())

    def lowered_between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.at if t0 <= t <= t1)


def _pow2_bucket(n: int, lo: int = 32) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def warmup_waves(plan: dict, serving: dict) -> list[dict]:
    """The waves that touch every prefill and decode shape the cell's
    lengths can reach (serving/engine.py: prefill programs by power-of-two
    prompt bucket and power-of-two batch rows; decode programs by window —
    128-multiples to 1024, then the whole slot — and by chunk size: the
    heavy chunk, its halvings down to the light one for short remainders,
    and the light chunk while few slots are active)."""
    prompts = [r["prompt_tokens"] for r in plan["requests"]]
    totals = [r["prompt_tokens"] + r["output_tokens"] for r in plan["requests"]]
    slots = int(serving["slots"])
    rows_max = min(int(serving.get("prefill-batch", 8)), slots)
    heavy = int(serving.get("decode-chunk", 16))
    light = int(serving.get("decode-chunk-light", 8))
    light_slots = serving.get("light-load-slots")
    threshold = (0 if light <= 0 or light >= heavy
                 else int(light_slots) if light_slots is not None
                 else max(1, slots // 8))
    crowd = min(slots, threshold + 1)
    # A closed loop keeps min(clients, slots) requests running from the end
    # of its ramp on. While that is over the light-load threshold no light
    # chunk is dispatched, and a heavy chunk is halved only when every one
    # of them is within half a chunk of its end at once, which a batch of
    # 16 or more with these output lengths does not do. The ramp itself
    # lies before the window, where a first use may load its program.
    # compiles_in_window polices the judgement: a run that met such a
    # shape inside its window is not correct.
    steady = min(plan.get("clients", 0), slots) if plan["loop"] == "closed" else 0
    small_chunks = steady < max(16, threshold + 1)
    waves = []
    buckets = sorted({_pow2_bucket(p) for p in prompts})
    rows = 1
    while rows <= rows_max:
        for b in buckets:
            longest = max(p for p in prompts if _pow2_bucket(p) == b)
            waves.append({"n": rows, "prompt": longest, "max_tokens": 1,
                          "why": f"prefill bucket {b} x {rows} rows"})
        rows *= 2
    # chunk sizes a burst can take (engine `_decode_burst`): the heavy chunk,
    # halved while it is at least twice the longest remaining budget, never
    # under the light chunk
    sizes, k = [heavy], heavy
    while small_chunks and k // 2 >= max(light, 1) and k > max(light, 1):
        k //= 2
        sizes.append(k)
    if not small_chunks:
        threshold = 0
    lo, hi = min(prompts) + 1, max(totals)
    windows = sorted({min(-(-n // 128) * 128, 1152) for n in range(lo, hi + 1, 16)}
                     | {min(-(-hi // 128) * 128, 1152)})
    for w in windows:  # 1152 stands for "past 1024": the whole-slot window
        if w <= 1024:
            prompt, cross = max(8, w - 120), 0
        else:  # reach past 1024 rows with heavy chunks first
            prompt = min(max(prompts), 1016)
            cross = max(0, -(-(1024 - prompt) // heavy) * heavy)
        # all of a wave's requests have the same remaining budget, so the
        # chunk size is decided by it: heavy, then the next size down
        first = heavy + (sizes[1] if len(sizes) > 1 else 0)
        waves.append({"n": crowd, "prompt": prompt,
                      "max_tokens": 1 + cross + first,
                      "why": f"decode window {w}, chunks {sizes[:2]}, {crowd} active"})
        for s in sizes[2:]:
            lone = bool(threshold) and s == light
            waves.append({"n": 1 if lone else crowd, "prompt": prompt + cross,
                          "max_tokens": 1 + s,
                          "why": f"decode window {w}, chunk {s}"})
        if threshold and light not in sizes[2:]:
            waves.append({"n": 1, "prompt": prompt + cross,
                          "max_tokens": 1 + light,
                          "why": f"decode window {w}, light chunk {light}"})
    return waves


async def run_waves(engine, waves: list[dict], seed: int, vocab: int) -> None:
    import numpy as np

    rng = np.random.default_rng((int(seed) + 1) % (2 ** 32))
    for wave in waves:
        t = time.monotonic()
        await asyncio.gather(*(
            engine.generate(
                # ids 3.. : distinct random prompts, so no prefix is shared
                [int(x) for x in rng.integers(3, vocab, size=wave["prompt"])],
                {"max-tokens": wave["max_tokens"], "temperature": 0},
            )
            for _ in range(wave["n"])
        ))
        log(f"warm-up: {wave['why']} ({wave['n']} x {wave['prompt']} tokens, "
            f"max-tokens {wave['max_tokens']}): {time.monotonic() - t:.2f} s")


async def ask_once(session, ws_base: str, gateway: str, text: str) -> None:
    """One request over a chat socket, to its final record (set-up only)."""
    from lib import app

    url = (f"{ws_base}/v1/chat/{app.TENANT}/{app.APP}/{gateway}"
           f"?param:sessionId=setup-{gateway}")
    async with session.ws_connect(url) as chat:
        await chat.send_json({"value": text})
        while True:
            msg = await asyncio.wait_for(chat.receive_json(), 1100)
            headers = (msg.get("record") or {}).get("headers") or {}
            if "langstream-completion-tokens" in headers:
                return
            if "record" not in msg and msg.get("status") not in (None, "OK"):
                raise RuntimeError(f"gateway said {msg}")


def memory_note(stage: str) -> None:
    import jax

    st = jax.devices()[0].memory_stats() or {}
    if st.get("bytes_limit"):
        log(f"memory after {stage}: in use {st.get('bytes_in_use', 0) / 1e9:.2f} GB, "
            f"peak {st.get('peak_bytes_in_use', 0) / 1e9:.2f} GB of "
            f"{st['bytes_limit'] / 1e9:.2f} GB")


def bytes_in_use() -> int:
    """Device memory in use now, on the fullest chip (0 where the backend
    keeps no count, as the CPU's does not)."""
    import jax

    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in jax.devices())


def engine_instance():
    from langstream_tpu.serving.engine import TpuServingEngine

    with TpuServingEngine._instances_lock:
        engines = list(TpuServingEngine._instances.values())
    if len(engines) != 1:
        raise RuntimeError(f"expected one engine, found {len(engines)}")
    return engines[0]


async def child_events(proc, on_event) -> None:
    while True:
        line = await proc.stdout.readline()
        if not line:
            return
        try:
            event = json.loads(line)
        except ValueError:
            log(f"loadgen: {line.decode(errors='replace').rstrip()}")
            continue
        on_event(event)


async def serve(args, spec: dict, device: dict, compiles: Compiles) -> dict:
    import aiohttp
    import jax

    from langstream_tpu.controlplane.server import (
        ControlPlaneServer,
        LocalComputeRuntime,
    )
    from langstream_tpu.controlplane.stores import InMemoryApplicationStore
    from langstream_tpu.gateway.server import GatewayRegistry, GatewayServer
    from langstream_tpu.serving.engine import TpuServingEngine

    from lib import app, observe, peaks, roofline, traffic, xplane

    config, mix, cell = spec["config"], spec["traffic"], spec["cell"]
    serving = dict(config["serving"])
    lengths = list(config.get("output_lengths") or app.OUTPUT_LENGTHS)
    plan = traffic.plan(
        mix, seed=args.seed, seconds=args.seconds, slots=int(serving["slots"]),
        max_seq_len=int(serving["max-seq-len"]), output_lengths=lengths,
    )
    TENANT, APP = app.TENANT, app.APP

    def free_port() -> int:
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    registry = GatewayRegistry()
    compute = LocalComputeRuntime(gateway_registry=registry)
    control = ControlPlaneServer(
        store=InMemoryApplicationStore(), compute=compute, port=free_port()
    )
    gateway = GatewayServer(registry=registry, port=free_port())
    await control.start()
    await gateway.start()
    session = aiohttp.ClientSession()
    proc = None
    work = os.path.join(ROOT, ".bench_work", f"{cell['name']}-{args.seed}-{args.trace}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    notes: list[str] = []
    try:
        api = f"http://127.0.0.1:{control.port}"
        async with session.put(f"{api}/api/tenants/{TENANT}") as resp:
            if resp.status not in (200, 201):
                raise RuntimeError(f"tenant: {resp.status} {await resp.text()}")
        async with session.post(
            f"{api}/api/applications/{TENANT}/{APP}",
            json=app.payload(serving, lengths),
        ) as resp:
            if resp.status not in (200, 201):
                raise RuntimeError(f"deploy: {resp.status} {await resp.text()}")
        ws_base = f"ws://127.0.0.1:{gateway.port}"

        # the first request builds the engine: weights, the program's own
        # warm-up wave
        t = time.monotonic()
        await ask_once(session, ws_base, f"chat-{lengths[0]}", "hello")
        engine = engine_instance()
        log(f"engine built and first answer: {time.monotonic() - t:.1f} s; "
            f"model {engine.config.model}, read kernel "
            f"{engine.paged_read_kernel}")
        memory_note("engine build")
        mc = engine.model_config
        served = {k: getattr(mc, k) for k in llama_fields(config["widths"])}
        if served != llama_fields(config["widths"]):
            raise RuntimeError(f"served widths {served} are not the file's")
        differs = posture_differs(engine, config)
        if differs:
            raise RuntimeError(f"the engine does not serve the posture the "
                               f"configuration's file states: {differs}")

        # the served model against its plain reference
        t = time.monotonic()
        import importlib

        reference = importlib.import_module(f"reference.{config['reference']}")
        check = await asyncio.to_thread(
            reference.check_engine, engine, args.seed,
            config["reference_tolerance"],
        )
        log("reference check: " + json.dumps(
            {k: v for k, v in check.items() if k != "positions"}
        ) + f" ({time.monotonic() - t:.1f} s)")
        memory_note("reference check")

        # every shape the cell's lengths can reach, then each gateway once
        t = time.monotonic()
        await run_waves(engine, warmup_waves(plan, serving), args.seed,
                        mc.vocab_size)
        await asyncio.gather(*(
            ask_once(session, ws_base, f"chat-{n}", "warm the path")
            for n in lengths
        ))
        log(f"warm-up waves: {time.monotonic() - t:.1f} s")
        memory_note("warm-up")

        # the window: the generator is a process of its own
        base = {"ws_base": ws_base, "tenant": TENANT, "app": APP,
                "ramp_timeout_s": 200.0}

        async def window(plan: dict, trace: bool):
            """One measured window of ``plan``: (results, marks, polls,
            traced)."""
            nonlocal proc
            plan_path = os.path.join(work, "plan.json")
            out_path = os.path.join(work, "results.json")
            with open(plan_path, "w") as f:
                json.dump({**plan, **base}, f)
            marks: dict = {}
            polls: list[dict] = []

            def snapshot() -> dict:
                return {
                    "recorded": engine.flight.recorded,
                    "preempt": engine.flight.events_by_type.get("preempt", 0),
                    "recompile": engine.flight.events_by_type.get("recompile", 0),
                }

            def on_event(event: dict) -> None:
                if event.get("event") == "window_open":
                    marks["open"] = event["t"]
                    marks["open_snap"] = snapshot()
                    log(f"window open; {event['t'] - _T0:.1f} s since start")
                elif event.get("event") == "window_close":
                    marks["close"] = event["t"]
                    marks["close_snap"] = snapshot()
                    marks["samples"] = engine.flight.recent(
                        max(1, marks["close_snap"]["recorded"]
                            - marks["open_snap"]["recorded"])
                    )

            env = dict(os.environ)
            env.pop("BENCH_RUN", None)
            proc = await asyncio.create_subprocess_exec(
                sys.executable, os.path.join(BENCH, "loadgen.py"), plan_path,
                out_path, stdout=asyncio.subprocess.PIPE, env=env,
            )
            reader = asyncio.ensure_future(child_events(proc, on_event))

            async def poll() -> None:
                while "close" not in marks:
                    if "open" in marks:
                        st = engine.block_mgr.stats() if engine.block_mgr else {}
                        total = st.get("num_blocks") or 1
                        polls.append({
                            "t": time.monotonic(),
                            "active": sum(1 for s in engine.slots if not s.free),
                            "live_blocks": st.get("live_blocks", 0),
                            "used_share": st.get("live_blocks", 0) / total,
                            "bytes_in_use": bytes_in_use(),
                        })
                    await asyncio.sleep(POLL_S)

            poller = asyncio.ensure_future(poll())
            traced = None
            if trace:
                while "open" not in marks and proc.returncode is None:
                    await asyncio.sleep(0.05)
                trace_s = min(TRACE_S, plan["seconds"] / 3)
                await asyncio.sleep(max(0.0, marks.get("open", time.monotonic())
                                        + (plan["seconds"] - trace_s) / 2
                                        - time.monotonic()))
                trace_dir = os.path.join(work, "trace")
                t_start = time.monotonic()
                await asyncio.to_thread(jax.profiler.start_trace, trace_dir)
                t_on = time.monotonic()
                await asyncio.sleep(trace_s)
                t_off = time.monotonic()
                await asyncio.to_thread(jax.profiler.stop_trace)
                log(f"traced {t_off - t_on:.2f} s (start {t_on - t_start:.2f} s, "
                    f"stop {time.monotonic() - t_off:.2f} s)")
                traced = (trace_dir, t_off - t_on)
            await asyncio.wait_for(proc.wait(), DEADLINE_S)
            await reader
            poller.cancel()
            if proc.returncode != 0 or "close" not in marks:
                raise RuntimeError(f"load generator exited {proc.returncode}")
            return load_json(out_path), marks, polls, traced

        results, marks, polls, traced = await window(plan, bool(args.trace))

        # what the run observed
        opened, closed = results["window"]["open"], results["window"]["close"]
        window_s = closed - opened
        if plan["loop"] == "open":
            measured = [r for r in results["requests"] if r.get("measured")]
        else:  # by completion (or failure) inside the window
            measured = [
                r for r in results["requests"]
                if not (r.get("cut") and "first" not in r)
                and opened <= (r.get("last") or r.get("done")
                               or r.get("sent") or 0) <= closed
            ]
        observe.annotate(measured, plan["loop"])
        failed = [r for r in measured
                  if r.get("error") or r.get("tokens") is None]
        done = [r for r in measured if r not in failed]
        bad = [
            r for r in done
            if r["frames"] < 1 or not r.get("stream_closed")
            or r["tokens"] < 1 or r["tokens"] > r["output_tokens"]
            or r.get("engine_prompt_tokens") != r["prompt_tokens"]
        ]
        asked = sum(r["output_tokens"] for r in done)
        delivered = sum(r["tokens"] for r in done)
        short = sum(1 for r in done if r["tokens"] < r["output_tokens"])
        log(f"requests: {len(measured)} measured, {len(failed)} failed, "
            f"{len(bad)} malformed; tokens delivered {delivered} of {asked} "
            f"asked; {short} ended early by EOS")
        # where the window's wall time went, by the engine's own account: a
        # run that reads far off shows here whether it dispatched more
        # prefills, waited longer on the host, or ran slower on the device
        phases: dict = {}
        for sample in marks.get("samples") or []:
            row = phases.setdefault(sample["phase"], {"n": 0, "wall_s": 0.0,
                                                      "device_s": 0.0, "host_s": 0.0})
            row["n"] += 1
            for key in ("wall", "device", "host"):
                row[f"{key}_s"] += sample.get(f"{key}_ms", 0.0) / 1e3
        log("dispatches in the window: " + json.dumps(
            {k: {f: round(v, 3) for f, v in row.items()}
             for k, row in sorted(phases.items())}))
        for r in (failed + bad)[:5]:
            notes.append(f"request {r['id']}: {r.get('error') or 'malformed'} "
                         f"{ {k: r.get(k) for k in ('frames', 'tokens', 'output_tokens', 'engine_prompt_tokens', 'prompt_tokens')} }")
        # Serving's own memory: the most in use at any poll of the window.
        # The allocator's peak_bytes_in_use cannot be reset and is set during
        # set-up by the reference check's float32 layer, which serves nobody.
        memory_peak = max((p["bytes_in_use"] for p in polls), default=0)
        bytes_limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
        lowered = compiles.lowered_between(opened, closed)
        recompiled = (marks["close_snap"]["recompile"]
                      - marks["open_snap"]["recompile"])
        counters = {
            "setup_s": opened - _T0,
            "out_tok_s": observe.tokens_inside(
                [r for r in results["requests"] if not r.get("error")],
                opened, closed) / window_s,
            "preemptions": float(marks["close_snap"]["preempt"]
                                 - marks["open_snap"]["preempt"]),
            "compiles_in_window": float(max(lowered, recompiled)),
            "hbm_peak_share": (100.0 * memory_peak / bytes_limit
                               if bytes_limit and memory_peak else None),
        }
        fields = llama_fields(config["widths"])
        obs = {
            "requests": done, "samples": marks.get("samples") or [],
            "polls": polls, "counters": counters, "trace": None,
            "serving": serving, "llama": fields,
            "paged_read_kernel": engine.paged_read_kernel,
            "pool": {
                "block_size": int(serving.get("kv-block-size", 64)),
                "num_blocks": (engine.block_mgr.stats()["num_blocks"]
                               if engine.block_mgr else 0),
            },
            "shape": roofline.Shape.from_widths(
                fields,
                weight_dtype_bytes=1.0 if serving.get("quantize") == "int8" else 2.0,
                kv_quantized=serving.get("kv-quantize") == "int8",
            ),
            "peaks": (peaks.peaks_for(device["kind"])
                      if device["platform"] == "tpu" else None),
        }
        breakdown = None
        if traced:
            paths = glob.glob(os.path.join(traced[0], "**", "*.xplane.pb"),
                              recursive=True)
            if paths:
                reduced = await asyncio.to_thread(
                    lambda: xplane.reduce(xplane.load(paths[0]), traced[1])
                )
                obs["trace"] = reduced
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = reduced["window_s"]
                breakdown = {"device_ops": xplane.top_ops(reduced),
                             "idle_gaps": xplane.top_gaps(reduced)}
        kind = "per_layer" if args.trace else "end_to_end"
        folder = "layer_metrics" if args.trace else "end_to_end"
        metrics = observe.report(spec[kind], folder, spec["roots"], obs)
        if args.trace:  # what the traced run read end to end, for the overhead
            log("traced run, end to end (not judged): " + json.dumps(
                observe.report(spec["end_to_end"], "end_to_end",
                               spec["roots"], obs)))
        else:
            log("per-layer counters (untraced): " + json.dumps(observe.report(
                spec["per_layer"], "layer_metrics", spec["roots"], obs)))
        correct = bool(
            (device["platform"] == "tpu" or args.rehearse_cpu)
            and counters["compiles_in_window"] == 0
            and not bad and done
            and check["passed"]
        )
        if counters["compiles_in_window"]:
            new_shapes = [
                f"{e.get('what')} {e.get('variant')}"
                for e in engine.flight.recent_events(512)
                if e.get("kind") == "recompile"
            ][-max(1, recompiled):] if recompiled else []
            notes.append(f"{lowered} programs lowered and {recompiled} engine "
                         f"recompile events inside the window {new_shapes}")
        if not check["passed"]:
            notes.append("the served model disagrees with its reference")
        for note in notes:
            log(f"NOT CORRECT: {note}" if not correct else f"note: {note}")
        device["memory_peak_bytes"] = memory_peak
        result = {
            "correct": correct, "attempted": len(measured),
            "failed": len(failed) + len(bad), "metrics": metrics,
            "device": device,
        }
        if breakdown:
            result["breakdown"] = breakdown
        return result
    finally:
        if proc is not None and proc.returncode is None:
            proc.kill()
            await proc.wait()
        await session.close()
        await gateway.stop()
        await control.stop()
        await compute.close()
        with TpuServingEngine._instances_lock:
            leftover = list(TpuServingEngine._instances.values())
        for engine in leftover:
            await engine.close()
        TpuServingEngine.reset_instances()
        shutil.rmtree(work, ignore_errors=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="REHEARSAL on the CPU: proves the harness, prints no result")
    ap.add_argument("--benchmark", default="",
                    help="another BENCHMARK.json (tests, a later PR's trial)")
    ap.add_argument("--data-dir", default="",
                    help="a root searched before bench/ for traffic/, "
                         "layer_metrics/ and end_to_end/ files")
    args = ap.parse_args(argv)

    try:
        from langstream_tpu.compile_cache import configure_compile_cache
    except ImportError as e:
        die(f"not a langstream-tpu checkout ({e}); run from the root of the "
            f"repository", 2)
    spec = read_cell(args)
    cache_dir = configure_compile_cache()  # <checkout>/.jax_cache unless placed
    platforms = os.environ.get("JAX_PLATFORMS")
    if args.rehearse_cpu:
        log("REHEARSAL on the CPU: this proves the harness, not the system.")
        os.environ["JAX_PLATFORMS"] = "cpu"
    elif platforms and platforms.split(",")[0].strip().lower() != "tpu":
        die(f"JAX_PLATFORMS={platforms!r} points JAX at "
            f"{platforms.split(',')[0]!r}, not at a TPU; nothing was run")

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    log(f"device {json.dumps(device)}; compile cache {cache_dir}; cell "
        f"{spec['cell']['name']} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace}")
    if not args.rehearse_cpu:
        if device["platform"] != "tpu":
            die(f"JAX found no TPU: first device is platform "
                f"{device['platform']!r} ({device['kind']}); nothing was run")
        if len(devices) < int(spec["cell"]["chips"]):
            die(f"the cell asks for {spec['cell']['chips']} chips, JAX sees "
                f"{len(devices)}; nothing was run")
        from lib import peaks

        try:
            peaks.peaks_for(device["kind"])
        except peaks.UnknownDevice as e:
            die(str(e))
    register_model(spec["config"])
    compiles = Compiles()
    try:
        result = asyncio.run(asyncio.wait_for(
            serve(args, spec, device, compiles), timeout=1150.0
        ))
    except Exception as e:  # the run's boundary: anything raised fails it
        traceback.print_exc()
        die(f"the run failed: {type(e).__name__}: {e}", 1)
    sys.stdout.flush()
    if args.rehearse_cpu:
        print("REHEARSAL " + json.dumps(result), flush=True)
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
