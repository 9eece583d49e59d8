"""Two-process lockstep serving on virtual CPU devices — the executable
proof that multi-host TP serving actually runs (leader consumes + samples,
follower replays collective programs; both execute the same jitted steps on
a mesh spanning both processes).

Run as two processes (the test and ``dryrun_multichip`` spawn these):

    python -m langstream_tpu.serving.lockstep_demo \
        --index 0 --num-processes 2 --coordinator-port P --lockstep-port Q \
        --out /tmp/leader.json
    python -m langstream_tpu.serving.lockstep_demo \
        --index 1 --num-processes 2 --coordinator-port P --lockstep-port Q

Each process owns 4 virtual CPU devices; the engine shards over the global
(dp=2, tp=4) mesh, so every prefill/decode crosses the process boundary
through XLA collectives. The leader writes its generated token streams to
``--out`` for the caller to compare against a single-process run.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
from pathlib import Path


def _force_cpu(devices_per_proc: int) -> None:
    """The demo is CPU-only by construction: a chip belongs to one process
    and the group is two. ``python -m`` imports the package, and with it
    jax, before this runs, so the platform goes through ``jax.config``
    (the variable is only read at import); ``XLA_FLAGS`` is read at
    backend init, so setting it here is still in time."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={devices_per_proc}"
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " " + flag).strip()


PROMPTS = ["hello tpu world", "lockstep decode", "multi host serving"]


async def _drive(engine) -> list[list[int]]:
    max_tokens = int(os.environ.get("LS_DEMO_MAX_TOKENS", "6"))
    results = await asyncio.gather(
        *(engine.generate(p, {"max-tokens": max_tokens}) for p in PROMPTS)
    )
    if os.environ.get("LS_DEMO_LEADER_ABRUPT_EXIT") == "1":
        # leader-death injection: skip close() — a clean close broadcasts a
        # "stop" frame, which is exactly what a crashed leader never sends
        return [r["tokens"] for r in results]
    await engine.close()
    return [r["tokens"] for r in results]


def run_process(
    index: int,
    num_processes: int,
    coordinator_port: int,
    lockstep_port: int,
    out_path: str | None = None,
    devices_per_proc: int = 4,
) -> None:
    _force_cpu(devices_per_proc)
    import jax

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{coordinator_port}",
        num_processes=num_processes,
        process_id=index,
    )
    # force backend init NOW: the multi-process topology exchange needs every
    # process to bring its backend up; a follower that first waits for the
    # lockstep handshake would deadlock the leader's own backend init
    jax.devices()
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    config = demo_config(num_processes * devices_per_proc)
    if index == 0:
        from langstream_tpu.serving.lockstep import LockstepBroken

        os.environ["LS_LOCKSTEP_PORT"] = str(lockstep_port)
        engine = TpuServingEngine(config)
        try:
            tokens = asyncio.run(_drive(engine))
        except LockstepBroken as e:
            # fail-loud contract (VERDICT r3 #8): in-flight work already
            # failed with this error; exit nonzero so the StatefulSet
            # restarts the whole slice together
            print(
                f"leader saw LockstepBroken: {e}; engine stopped serving: "
                f"{engine._stop}",
                file=sys.stderr, flush=True,
            )
            # os._exit: a normal exit would run jax.distributed's shutdown
            # barrier, which (with a dead member) aborts the process and
            # replaces this deliberate exit code
            os._exit(5)
        if out_path:
            Path(out_path).write_text(json.dumps(tokens))
        if os.environ.get("LS_DEMO_LEADER_ABRUPT_EXIT") == "1":
            # fault injection: die without broadcasting "stop" — what a
            # crashed leader pod looks like to the followers
            print("fault injection: leader abrupt exit", file=sys.stderr, flush=True)
            os._exit(4)
    else:
        from langstream_tpu.serving.lockstep import LockstepFollower

        die_after = int(os.environ.get("LS_DEMO_FOLLOWER_DIE_AFTER", "0"))
        steps = LockstepFollower("127.0.0.1", lockstep_port).run(
            die_after_steps=die_after or None
        )
        print(f"follower replayed {steps} steps", file=sys.stderr)


def demo_config(total_devices: int):
    from langstream_tpu.serving.engine import ServingConfig

    # the block-pool cache crosses the process boundary (block tables
    # ride the lockstep descriptors); LS_DEMO_SPEC=N additionally runs
    # greedy bursts speculatively (the "verify" descriptor replays host
    # drafts on the followers)
    spec = int(os.environ.get("LS_DEMO_SPEC", "0"))
    return ServingConfig(
        model="tiny",
        slots=4,
        max_seq_len=64,
        decode_chunk=4,
        prefill_batch=2,
        seed=0,
        kv_block_size=16,
        speculative_drafts=spec,
        # tiny model: 2 kv heads caps tp at 2; the rest of the devices go dp
        mesh=(("dp", total_devices // 2), ("tp", 2)),
    )


def run_single_process_reference(total_devices: int = 8) -> list[list[int]]:
    """The same workload on one process with ``total_devices`` virtual
    devices — the golden stream the 2-process run must reproduce."""
    from langstream_tpu.serving.engine import TpuServingEngine

    engine = TpuServingEngine(demo_config(total_devices))
    return asyncio.run(_drive(engine))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reference", action="store_true",
                    help="single-process golden run instead of a group role")
    ap.add_argument("--index", type=int, default=0)
    ap.add_argument("--num-processes", type=int, default=2)
    ap.add_argument("--coordinator-port", type=int, default=0)
    ap.add_argument("--lockstep-port", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--devices-per-proc", type=int, default=4)
    args = ap.parse_args()
    if args.reference:
        total = args.num_processes * args.devices_per_proc
        _force_cpu(total)
        tokens = run_single_process_reference(total)
        if args.out:
            Path(args.out).write_text(json.dumps(tokens))
        return
    run_process(
        args.index, args.num_processes, args.coordinator_port,
        args.lockstep_port, args.out, args.devices_per_proc,
    )


if __name__ == "__main__":
    main()
