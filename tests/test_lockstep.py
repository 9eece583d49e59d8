"""Multi-host lockstep serving: two real OS processes, each owning 4
virtual CPU devices, form a JAX distributed group; the leader serves
requests while the follower replays the leader's step descriptors — and the
generated token streams must equal a single-process run of the identical
config (SURVEY §7 hard part (c); VERDICT r2 item 1).
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _sub_env() -> dict[str, str]:
    """Subprocess env: the demo module forces its own CPU platform and
    4-device flag — the parent's test flags must not leak in."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.mark.slow
@pytest.mark.parametrize("spec", [0, 4])
def test_two_process_lockstep_decode_matches_single_process(tmp_path, spec):
    coordinator_port = _free_port()
    lockstep_port = _free_port()
    out = tmp_path / "leader_tokens.json"
    env = _sub_env()
    env["LS_DEMO_SPEC"] = str(spec)

    follower = subprocess.Popen(
        [
            sys.executable, "-m", "langstream_tpu.serving.lockstep_demo",
            "--index", "1", "--coordinator-port", str(coordinator_port),
            "--lockstep-port", str(lockstep_port),
        ],
        env=env, stderr=subprocess.PIPE,
    )
    leader = subprocess.Popen(
        [
            sys.executable, "-m", "langstream_tpu.serving.lockstep_demo",
            "--index", "0", "--coordinator-port", str(coordinator_port),
            "--lockstep-port", str(lockstep_port), "--out", str(out),
        ],
        env=env, stderr=subprocess.PIPE,
    )
    try:
        _, leader_err = leader.communicate(timeout=300)
        _, follower_err = follower.communicate(timeout=60)
    except subprocess.TimeoutExpired:
        leader.kill()
        follower.kill()
        raise
    assert leader.returncode == 0, leader_err.decode()[-2000:]
    assert follower.returncode == 0, follower_err.decode()[-2000:]
    assert b"follower replayed" in follower_err

    lockstep_tokens = json.loads(out.read_text())
    # same config, one process, all 8 devices local: the golden stream
    from langstream_tpu.serving.lockstep_demo import (
        run_single_process_reference,
    )

    os.environ["LS_DEMO_SPEC"] = str(spec)
    try:
        reference_tokens = run_single_process_reference(8)
    finally:
        os.environ.pop("LS_DEMO_SPEC", None)
    assert lockstep_tokens == reference_tokens
    assert len(lockstep_tokens) == 3
    assert all(len(stream) > 0 for stream in lockstep_tokens)


# ---------------------------------------------------------------------------
# failure semantics (VERDICT r3 #8): the happy path above is proven; these
# pin the fail-loud promises of serving/lockstep.py — a lost member must
# surface as LockstepBroken / a prompt exit, never a hang
# ---------------------------------------------------------------------------


def test_broadcast_raises_lockstep_broken_after_follower_death():
    """Channel level, real sockets: a follower that dies abruptly (socket
    torn down by the kernel, no goodbye) poisons the group — broadcast
    raises LockstepBroken within a bounded number of sends (TCP buffering
    allows a send or two before the RST lands), and every broadcast after
    the first failure fails immediately."""
    from langstream_tpu.serving.lockstep import (
        LockstepBroken,
        LockstepLeader,
        encode_descriptor,
        read_frame,
    )

    leader = LockstepLeader(
        {"config_json": "{}"}, expected_followers=1, port=0, token="t"
    )
    try:
        sock = socket.create_connection(("127.0.0.1", leader.port))
        sock.sendall(encode_descriptor({"op": "join", "token": "t"}))
        assert read_frame(sock)["op"] == "handshake"
        leader.wait_ready(timeout=10)
        leader.broadcast({"op": "decode", "step": 0})
        assert read_frame(sock)["step"] == 0  # follower replayed it
        sock.close()  # death: no more reads ever
        with pytest.raises(LockstepBroken):
            for step in range(50):
                leader.broadcast({"op": "decode", "step": step})
                time.sleep(0.05)
        # the group stays poisoned: instant failure, no half-broadcasts
        with pytest.raises(LockstepBroken):
            leader.broadcast({"op": "stop"})
    finally:
        leader.close()


def test_engine_fails_inflight_and_stops_on_lockstep_broken(run_async):
    """Engine level: when a broadcast fails mid-serving, in-flight
    generate() callers get LockstepBroken (not a hang), the engine stops
    serving, and later submissions fail fast."""
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine
    from langstream_tpu.serving.lockstep import LockstepBroken

    class _DyingLockstep:
        def __init__(self):
            self.sent = 0

        def broadcast(self, desc):
            self.sent += 1
            if self.sent >= 2:  # first frame lands, then the follower dies
                raise LockstepBroken("injected follower loss")

        def close(self):
            pass

    async def main():
        engine = TpuServingEngine(
            ServingConfig(model="tiny", slots=4, max_seq_len=64)
        )
        engine._lockstep = _DyingLockstep()
        with pytest.raises(LockstepBroken):
            await engine.generate("hello", {"max-tokens": 8})
        assert engine._stop, "engine must stop serving after a broken group"
        with pytest.raises(RuntimeError, match="stopped"):
            await engine.generate("again", {"max-tokens": 2})

    run_async(main())


def test_follower_exits_promptly_when_leader_dies():
    """Follower level: a leader that dies without the 'stop' frame leaves
    the follower blocked in read_frame — the closed socket must surface as
    ConnectionError promptly (the pod exits nonzero and the StatefulSet
    restarts the slice), never a silent hang."""
    from langstream_tpu.serving.lockstep import (
        LockstepFollower,
        encode_descriptor,
        read_frame,
    )

    server = socket.socket()
    server.bind(("127.0.0.1", 0))
    server.listen(1)
    port = server.getsockname()[1]
    config_json = json.dumps({"model": "tiny", "slots": 2, "max-seq-len": 64})

    def fake_leader():
        conn, _ = server.accept()
        read_frame(conn)  # join
        conn.sendall(
            encode_descriptor({"op": "handshake", "config_json": config_json})
        )
        time.sleep(0.5)
        conn.close()  # leader dies mid-serving, no stop frame

    t = threading.Thread(target=fake_leader, daemon=True)
    t.start()
    follower = LockstepFollower("127.0.0.1", port)
    start = time.monotonic()
    with pytest.raises((ConnectionError, OSError)):
        follower.run()
    assert time.monotonic() - start < 60
    server.close()


@pytest.mark.slow
def test_follower_death_mid_burst_leader_fails_loud(tmp_path):
    """Full 2-process proof: the follower is OOM-kill-simulated mid-burst
    (os._exit after 4 replayed descriptors); the leader must surface
    LockstepBroken to in-flight work, stop serving, and exit nonzero for
    the StatefulSet to restart the slice."""
    coordinator_port = _free_port()
    lockstep_port = _free_port()
    env = _sub_env()
    env["LS_DEMO_MAX_TOKENS"] = "40"  # many bursts: death lands mid-stream
    fenv = dict(env)
    fenv["LS_DEMO_FOLLOWER_DIE_AFTER"] = "4"

    follower = subprocess.Popen(
        [
            sys.executable, "-m", "langstream_tpu.serving.lockstep_demo",
            "--index", "1", "--coordinator-port", str(coordinator_port),
            "--lockstep-port", str(lockstep_port),
        ],
        env=fenv, stderr=subprocess.PIPE,
    )
    leader = subprocess.Popen(
        [
            sys.executable, "-m", "langstream_tpu.serving.lockstep_demo",
            "--index", "0", "--coordinator-port", str(coordinator_port),
            "--lockstep-port", str(lockstep_port),
        ],
        env=env, stderr=subprocess.PIPE,
    )
    try:
        _, leader_err = leader.communicate(timeout=300)
        _, follower_err = follower.communicate(timeout=60)
    except subprocess.TimeoutExpired:
        leader.kill()
        follower.kill()
        raise
    assert follower.returncode == 3, follower_err.decode()[-2000:]
    assert leader.returncode == 5, leader_err.decode()[-2000:]
    assert b"LockstepBroken" in leader_err
    assert b"engine stopped serving: True" in leader_err


@pytest.mark.slow
def test_leader_death_follower_exits_promptly(tmp_path):
    """Full 2-process proof: the leader dies abruptly after serving (no
    'stop' frame); the follower must notice the closed channel and exit
    nonzero promptly instead of hanging in read_frame."""
    coordinator_port = _free_port()
    lockstep_port = _free_port()
    env = _sub_env()
    env["LS_DEMO_LEADER_ABRUPT_EXIT"] = "1"

    follower = subprocess.Popen(
        [
            sys.executable, "-m", "langstream_tpu.serving.lockstep_demo",
            "--index", "1", "--coordinator-port", str(coordinator_port),
            "--lockstep-port", str(lockstep_port),
        ],
        env=env, stderr=subprocess.PIPE,
    )
    leader = subprocess.Popen(
        [
            sys.executable, "-m", "langstream_tpu.serving.lockstep_demo",
            "--index", "0", "--coordinator-port", str(coordinator_port),
            "--lockstep-port", str(lockstep_port),
        ],
        env=env, stderr=subprocess.PIPE,
    )
    try:
        _, leader_err = leader.communicate(timeout=300)
        assert leader.returncode == 4, leader_err.decode()[-2000:]
        death = time.monotonic()
        _, follower_err = follower.communicate(timeout=120)
        elapsed = time.monotonic() - death
    except subprocess.TimeoutExpired:
        leader.kill()
        follower.kill()
        raise
    assert follower.returncode not in (0, None), follower_err.decode()[-2000:]
    assert elapsed < 120
    # two valid detectors may fire first: the lockstep channel (read_frame
    # raises on the closed socket) or jax.distributed's coordination
    # service (leader heartbeat lost) — either way the exit is prompt+loud
    assert (
        b"ConnectionError" in follower_err
        or b"lockstep peer closed" in follower_err
        or b"CoordinationService" in follower_err
        or b"Socket closed" in follower_err
        or b"coordination" in follower_err
    ), follower_err.decode()[-2000:]
