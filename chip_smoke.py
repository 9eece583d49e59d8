"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py

One process — the one that holds the chip — starts the control plane, the
gateway and the in-process compute runtime, deploys
``examples/applications/chat-completions`` AS SHIPPED through the control
plane's REST API (the payload ``cli apps deploy`` sends), and asks it a few
questions over the gateway's chat WebSocket: a lone one (which builds the
engine: Llama-3-8B at its published widths, int8 weights, random init from
the configured seed, then the warm-up wave), a second lone one, a
concurrent wave past the light-load threshold, and one prompt long enough
for the flash-prefill bucket. Then, in the same process, it builds every
Pallas kernel the engine can select at the served model's shapes and
compares each with the XLA read it replaces (``langstream_tpu/ops/
selfcheck.py``).

It fails — exit code other than 0 — when JAX's first device is not a
``tpu`` (saying what it saw), when a request fails or streams nothing, when
the engine's warm-up is not ``done``, when the engine recorded a pool
shrink or failed in-flight work, when a read kernel is not the one the
configuration selects, when a kernel does not build or disagrees with XLA,
and when anything raised on the way. It prints what it observed (set-up
time apart from request times — a smoke's observations, not metrics) and,
as the LAST line of stdout and only on success, one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--rehearse-cpu`` walks the same path with the ``tiny`` model and
interpreted kernels on the CPU, to debug the script before spending chip
time. It says REHEARSAL in its output and its last line is not the result
above; it is never what runs with no argument.

``--mesh tp=4`` (a host with several chips; one process drives them all)
serves the same application with that mesh in its serving resource and
additionally fails unless a weight and the KV pool are spread over every
device of the mesh; it prints each device's ``bytes_in_use``.
"""

from __future__ import annotations

import argparse
import ast
import asyncio
import json
import os
import sys
import time
import traceback
from pathlib import Path

_T0 = time.monotonic()  # set-up and total times count from process start
ROOT = Path(__file__).resolve().parent
APP_DIR = ROOT / "examples" / "applications" / "chat-completions"
INSTANCE = ROOT / "examples" / "instances" / "memory.yaml"
TENANT, APP, GATEWAY = "smoke", "chat", "chat"

# the repo's llama3-8b at its published widths: depth and width uncut
PUBLISHED_8B = {
    "layers": 32, "hidden": 4096, "heads": 32, "kv_heads": 8,
    "head_dim": 128, "intermediate": 14336, "vocab_size": 128256,
}
# the hard limit is 1200 s; leave room to tear down and report
DEADLINE_S = 1080.0
WAVE = 12          # concurrent requests: past the example's light-load
                   # threshold (64 slots // 8), so heavy chunks serve traffic
LONG_PROMPT_CHARS = 600  # byte tokenizer: > 512 tokens → the flash bucket


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def log(message: str) -> None:
    print(f"[chip_smoke] {message}", flush=True)


def _override_serving(payload: dict, overrides: dict) -> None:
    """Set keys of the shipped application's serving resource (the
    rehearsal's model cut, ``--mesh``); the no-argument smoke never does."""
    import yaml

    conf = yaml.safe_load(payload["files"]["configuration.yaml"])
    for resource in conf["configuration"]["resources"]:
        if resource["type"] == "tpu-serving-configuration":
            resource["configuration"].update(overrides)
    payload["files"]["configuration.yaml"] = yaml.safe_dump(conf)


def _check_spread(engine, observed: dict) -> list[str]:
    """``--mesh``: the weights and the pool really are spread."""
    import jax

    n = engine.mesh.size
    weight = jax.tree.leaves(engine.params["layers"]["wq"])[0]
    pool = jax.tree.leaves(engine.cache_k)[0]
    observed["spread"] = {
        "mesh": dict(engine.mesh.shape),
        # mesh order is jax.devices() order (parallel/mesh.py make_mesh)
        "device_coords": [
            list(getattr(d, "coords", ())) for d in engine.mesh.devices.flat
        ],
        "weight_devices": len(weight.sharding.device_set),
        "weight_shard_shape": list(weight.sharding.shard_shape(weight.shape)),
        "pool_devices": len(pool.sharding.device_set),
        "pool_shard_shape": list(pool.sharding.shard_shape(pool.shape)),
        "bytes_in_use": [
            (d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()
        ],
    }
    log(f"spread: {json.dumps(observed['spread'])}")
    return [
        f"{name} sits on {count} of {n} mesh devices"
        for name, count in (
            ("a layer weight", observed["spread"]["weight_devices"]),
            ("the KV pool", observed["spread"]["pool_devices"]),
        )
        if count != n
    ]


async def _ask(session, ws_base: str, index: int, question: str) -> dict:
    """One question over the chat socket; returns what the client saw."""
    url = (
        f"{ws_base}/v1/chat/{TENANT}/{APP}/{GATEWAY}"
        f"?param:sessionId=smoke-{index}"
    )
    t0 = time.monotonic()
    frames, text = 0, ""
    async with session.ws_connect(url) as chat:
        await chat.send_json({"value": question})
        while True:
            msg = await asyncio.wait_for(chat.receive_json(), DEADLINE_S)
            record = msg.get("record")
            if record is None:
                if msg.get("status") not in (None, "OK"):
                    raise RuntimeError(f"request {index}: gateway said {msg}")
                continue  # the produce ack
            frames += 1
            value = record.get("value")
            text += value if isinstance(value, str) else ""
            headers = record.get("headers") or {}
            if headers.get("stream-last-message") in ("true", True):
                return {
                    "index": index,
                    "frames": frames,
                    "chars": len(text),
                    "wall_s": round(time.monotonic() - t0, 3),
                }


def _check_engine(engine, requests: int, rehearsal: bool) -> list[str]:
    """Everything the engine must say about itself after the requests."""
    failures: list[str] = []
    mc, cfg = engine.model_config, engine.config
    if not rehearsal:
        widths = {k: getattr(mc, k) for k in PUBLISHED_8B}
        if widths != PUBLISHED_8B or cfg.quantize != "int8":
            failures.append(
                f"served model is not Llama-3-8B at published widths with "
                f"int8 weights: {widths}, quantize={cfg.quantize}"
            )
    if engine._warmup_state() != "done":
        failures.append(f"warm-up state is {engine._warmup_state()!r}")
    events = engine.flight.events_by_type
    if engine.pool_shrinks or events.get("pool-shrink"):
        failures.append(f"engine shrank its pool {engine.pool_shrinks}x")
    failed_inflight = [
        e for e in engine.flight.recent_events(512)
        if e.get("kind") == "preempt" and e.get("error")
    ]
    if failed_inflight:
        failures.append(f"engine failed in-flight work: {failed_inflight[:2]}")
    # the kernel the configuration selects, resolved the way the engine
    # documents it (ServingConfig.paged_kernel)
    quant_pool = cfg.kv_quantize == "int8"
    selected = cfg.paged_kernel
    if selected == "auto":
        on_tpu = not rehearsal
        selected = "pallas" if on_tpu and not quant_pool else "xla"
    if engine.paged_read_kernel != selected:
        failures.append(
            f"paged read kernel {engine.paged_read_kernel!r}, configuration "
            f"selects {selected!r}"
        )
    continuation = "xla" if quant_pool else selected
    if engine.continuation_read_kernel != continuation:
        failures.append(
            f"continuation read kernel {engine.continuation_read_kernel!r}, "
            f"configuration selects {continuation!r}"
        )
    # the commit follows the same selection where it can move the pool
    # (ops/pool_commit.py commit_form: a bf16 pool; no mesh)
    from langstream_tpu.ops.pool_commit import commit_form

    commit = commit_form(
        selected if engine.mesh is None else "xla", engine.cache_k)
    if engine.pool_commit_kernel != commit:
        failures.append(
            f"pool commit kernel {engine.pool_commit_kernel!r}, "
            f"configuration selects {commit!r}"
        )
    served = [t for t in engine.request_timings if t.get("tokens", 0) > 0]
    if len(served) < requests:
        failures.append(
            f"{len(served)} of {requests} requests produced tokens "
            f"engine-side"
        )
    return failures


async def _serve(rehearsal: bool, mesh: dict, observed: dict) -> list[str]:
    """Phases 1 and 2: the served requests, then the kernels. Returns the
    failures; raises only what the caller records as one."""
    import aiohttp

    from langstream_tpu.cli.main import _app_payload
    from langstream_tpu.controlplane.server import (
        ControlPlaneServer,
        LocalComputeRuntime,
    )
    from langstream_tpu.controlplane.stores import InMemoryApplicationStore
    from langstream_tpu.gateway.server import GatewayRegistry, GatewayServer
    from langstream_tpu.models.llama import _flash_mode
    from langstream_tpu.ops.selfcheck import check_kernels
    from langstream_tpu.serving.engine import TpuServingEngine

    failures: list[str] = []
    payload = _app_payload(str(APP_DIR), str(INSTANCE), None)
    if rehearsal:
        _override_serving(
            payload, {"model": "tiny", "slots": 16, "max-seq-len": 1024}
        )
        payload["instance"] = payload["instance"].replace("llama3-8b", "tiny")
    if mesh:
        _override_serving(payload, {"mesh": mesh})

    registry = GatewayRegistry()
    compute = LocalComputeRuntime(gateway_registry=registry)
    control = ControlPlaneServer(
        store=InMemoryApplicationStore(), compute=compute, port=_free_port()
    )
    gateway = GatewayServer(registry=registry, port=_free_port())
    await control.start()
    await gateway.start()
    session = aiohttp.ClientSession()
    try:
        api = f"http://127.0.0.1:{control.port}"
        async with session.put(f"{api}/api/tenants/{TENANT}") as resp:
            if resp.status not in (200, 201):
                raise RuntimeError(f"tenant: {resp.status} {await resp.text()}")
        async with session.post(
            f"{api}/api/applications/{TENANT}/{APP}", json=payload
        ) as resp:
            if resp.status not in (200, 201):
                raise RuntimeError(f"deploy: {resp.status} {await resp.text()}")
        log(f"deployed {APP_DIR.relative_to(ROOT)} as shipped")
        ws_base = f"ws://127.0.0.1:{gateway.port}"

        # set-up: everything up to the first answer — weights from the
        # seed, the warm-up wave's compiles, the first request itself
        first = await _ask(session, ws_base, 0, "What is a tensor core?")
        observed["setup_s"] = round(time.monotonic() - _T0, 1)
        log(f"set-up (start to first answer): {observed['setup_s']} s")
        results = [first, await _ask(session, ws_base, 1, "And a systolic array?")]
        results += await asyncio.gather(*(
            _ask(session, ws_base, 10 + i, f"Question {i}: why is the sky blue?")
            for i in range(WAVE)
        ))
        long_prompt = ("Summarise this log line by line. " * 40)[:LONG_PROMPT_CHARS]
        results.append(await _ask(session, ws_base, 99, long_prompt))
        observed["requests"] = results
        for r in results:
            if r["frames"] < 1:
                failures.append(f"request {r['index']} streamed nothing")
        log(
            "request wall seconds: "
            + ", ".join(f"#{r['index']}={r['wall_s']}" for r in results)
        )

        with TpuServingEngine._instances_lock:
            engines = list(TpuServingEngine._instances.values())
        if len(engines) != 1:
            raise RuntimeError(f"expected one engine, found {len(engines)}")
        engine = engines[0]
        failures += _check_engine(engine, len(results), rehearsal)
        if mesh:
            failures += _check_spread(engine, observed)
        nrbs = sorted({
            key[1] for key in engine._decode_chunk_fns if key[1] is not None
        })
        # (kind, repr((sampler mode, bucket, rows))) per compiled prefill
        prefill_buckets = sorted({
            ast.literal_eval(key)[1]
            for kind, key in engine._compiled_shapes if kind == "prefill"
        })
        if max(prefill_buckets) < 512:
            failures.append(
                f"no prompt reached the flash-prefill bucket: {prefill_buckets}"
            )
        elif not rehearsal and _flash_mode(max(prefill_buckets)) != "compiled":
            failures.append("the long prompt's prefill did not select flash")
        observed["engine"] = {
            "model": engine.config.model,
            "slots": engine.config.slots,
            "warmup": engine._warmup_state(),
            "paged_read_kernel": engine.paged_read_kernel,
            "continuation_read_kernel": engine.continuation_read_kernel,
            "pool_commit_kernel": engine.pool_commit_kernel,
            "decode_read_block_buckets": nrbs,
            "prefill_buckets": prefill_buckets,
            "prefix_hits": engine.prefix_hits,
            "total_generated": engine.total_generated,
            "recompiles": engine.flight.events_by_type.get("recompile", 0),
        }
        log(f"engine: {json.dumps(observed['engine'])}")

        # phase 2, same process: every selectable Pallas kernel, compiled,
        # at the served head geometry and the window buckets just used
        rows = check_kernels(
            engine.model_config,
            block_size=engine.config.kv_block_size,
            read_blocks=tuple(nrbs),
            batch=min(engine.config.slots, 8),
            interpret=rehearsal,
        )
        for row in rows:
            log(f"kernel: {json.dumps(row)[:600]}")
            if not row["ok"]:
                failures.append(
                    f"{row['kernel']} {row['shape']}: "
                    f"{row.get('error') or row.get('max_abs_err')}"
                )
    finally:
        await session.close()
        await gateway.stop()
        await control.stop()
        await compute.close()
        with TpuServingEngine._instances_lock:
            leftover = list(TpuServingEngine._instances.values())
        for engine in leftover:
            await engine.close()
        TpuServingEngine.reset_instances()
    return failures


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="REHEARSAL: tiny model, interpreted kernels, CPU; proves the "
             "script, not the system on the chip",
    )
    ap.add_argument(
        "--mesh", default="", metavar="AXIS=N[,AXIS=N]",
        help="serve with this mesh (e.g. tp=4 on a four-chip host) and "
             "check that weights and pool are spread over it",
    )
    args = ap.parse_args(argv)
    mesh = {
        axis: int(size)
        for axis, size in (p.split("=") for p in args.mesh.split(",") if p)
    }
    observed: dict = {}

    # decided before JAX is imported: the cache directory, and that the
    # environment does not point JAX away from the chip
    sys.path.insert(0, str(ROOT))
    try:
        from langstream_tpu.compile_cache import configure_compile_cache
    except ImportError as e:
        print(
            f"chip_smoke: not a langstream-tpu checkout ({e}); run from the "
            f"root of the repository", file=sys.stderr,
        )
        return 2
    cache_dir = configure_compile_cache()
    platforms = os.environ.get("JAX_PLATFORMS")
    if args.rehearse_cpu:
        log("REHEARSAL on the CPU — tiny model, interpreted kernels. "
            "This is not the smoke.")
        os.environ["JAX_PLATFORMS"] = "cpu"
    elif platforms and platforms.split(",")[0].strip().lower() != "tpu":
        print(
            f"chip_smoke: JAX_PLATFORMS={platforms!r} points JAX at "
            f"platform {platforms.split(',')[0]!r}, not at a TPU; nothing "
            f"was run", file=sys.stderr,
        )
        return 3

    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    log(f"device: {json.dumps(device)}  JAX_PLATFORMS={platforms!r}  "
        f"compile cache: {cache_dir}")
    if device["platform"] != "tpu" and not args.rehearse_cpu:
        print(
            f"chip_smoke: JAX found no accelerator: first device is "
            f"platform {device['platform']!r} ({device['kind']}); nothing "
            f"was run", file=sys.stderr,
        )
        return 3

    try:
        failures = asyncio.run(
            asyncio.wait_for(
                _serve(args.rehearse_cpu, mesh, observed), timeout=DEADLINE_S
            )
        )
    except Exception as e:  # the smoke's boundary: anything raised fails it
        traceback.print_exc()
        failures = [f"{type(e).__name__}: {e}"]
    stats = devices[0].memory_stats() or {}
    observed["memory"] = {
        k: stats.get(k)
        for k in ("bytes_limit", "peak_bytes_in_use", "bytes_in_use")
    }
    observed["total_s"] = round(time.monotonic() - _T0, 1)
    print(json.dumps({"observed": observed}), flush=True)
    if failures:
        for failure in failures:
            print(f"chip_smoke: FAILED: {failure}", file=sys.stderr)
        print(json.dumps({"ok": False, "failures": failures, "device": device}))
        return 1
    if args.rehearse_cpu:
        print(json.dumps({"rehearsal": True, "passed": True, "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
