"""Multi-host lockstep execution for the serving engine.

The problem (SURVEY §7 hard part (c)): a serving engine sharded over a
multi-host TPU slice is a JAX *multi-controller* program — *every* process
in the group must execute the same jitted computation in the same order, or
the first cross-host collective hangs. But only one host (the slice leader)
consumes requests from the broker, admits them into slots, and samples; the
followers know nothing about arrivals.

The design here: the leader broadcasts a compact **step descriptor** over a
TCP side channel before every jitted dispatch — the op kind (prefill /
decode variant), the static specialization (prompt bucket, attention window,
top-p flag) and the host-side inputs (token ids, lengths, slot masks,
sampling params, the split RNG key). Followers replay each descriptor as the
identical jit call on their shards of the same global arrays. Ordering is
TCP FIFO; the device collectives themselves ride ICI as usual — the side
channel carries only a few hundred bytes of control per chunk, so it is
never the bottleneck (one descriptor per ``decode_chunk`` steps, not per
token).

Why a TCP channel and not device-collective broadcast
(``multihost_utils.broadcast_one_to_all``): descriptor shapes vary by op
(prefill buckets, batch sizes), which a device broadcast must know ahead of
time on every host; a byte stream has no such constraint, keeps the control
plane off the devices entirely, and fails loudly (socket error) instead of
hanging a collective when a host dies.

Wire format (no pickle — the channel crosses pod boundaries):
``u32 big-endian frame length | JSON header | concatenated raw array
bytes``; the header maps argument names to dtype/shape/offset.
"""

from __future__ import annotations

import json
import logging
import os
import socket
import struct
import threading
import time
from typing import Any

import numpy as np

log = logging.getLogger(__name__)

DEFAULT_PORT = 7077


class LockstepBroken(RuntimeError):
    """The lockstep group lost a member (or the channel failed) — partial
    frame delivery is unrecoverable (survivors would run collectives the
    others never heard about), so the slice must restart as a unit. The
    engine fails in-flight work and stops serving when it sees this."""


# ---------------------------------------------------------------------------
# framing
# ---------------------------------------------------------------------------


def encode_descriptor(desc: dict[str, Any]) -> bytes:
    """``desc``: flat dict of scalars (str/int/float/bool/None) and numpy
    arrays. Arrays are shipped raw; everything else rides the JSON header."""
    scalars: dict[str, Any] = {}
    arrays: dict[str, dict[str, Any]] = {}
    blobs: list[bytes] = []
    offset = 0
    for key, value in desc.items():
        if isinstance(value, np.ndarray):
            raw = np.ascontiguousarray(value)
            blob = raw.tobytes()
            arrays[key] = {
                "dtype": str(raw.dtype),
                "shape": list(raw.shape),
                "offset": offset,
                "nbytes": len(blob),
            }
            blobs.append(blob)
            offset += len(blob)
        else:
            scalars[key] = value
    header = json.dumps({"scalars": scalars, "arrays": arrays}).encode()
    payload = struct.pack(">I", len(header)) + header + b"".join(blobs)
    return struct.pack(">I", len(payload)) + payload


def decode_descriptor(payload: bytes) -> dict[str, Any]:
    (header_len,) = struct.unpack(">I", payload[:4])
    header = json.loads(payload[4 : 4 + header_len])
    out: dict[str, Any] = dict(header["scalars"])
    base = 4 + header_len
    for key, meta in header["arrays"].items():
        start = base + meta["offset"]
        out[key] = np.frombuffer(
            payload[start : start + meta["nbytes"]], dtype=meta["dtype"]
        ).reshape(meta["shape"])
    return out


def _read_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("lockstep peer closed the channel")
        buf.extend(chunk)
    return bytes(buf)


def read_frame(sock: socket.socket) -> dict[str, Any]:
    (length,) = struct.unpack(">I", _read_exact(sock, 4))
    return decode_descriptor(_read_exact(sock, length))


# ---------------------------------------------------------------------------
# leader
# ---------------------------------------------------------------------------


class LockstepLeader:
    """Process-0 side: accepts follower connections, handshakes the serving
    config, then fans every descriptor out in order. ``broadcast`` is called
    from the engine's single dispatch thread, so frames reach every follower
    in dispatch order.

    Membership is fixed at slice start: a follower that dies cannot rejoin
    (its JAX process left the distributed group; collectives with a fresh
    process would hang) — the slice restarts as a unit, which is the
    StatefulSet's job. Late/extra connectors get an explicit reject frame
    instead of a silent hang. Joins are authenticated with the shared
    ``token`` (``LS_LOCKSTEP_TOKEN``, injected by the manifest factory) so
    an arbitrary in-cluster connector can neither read prompt descriptors
    nor steal a membership slot."""

    def __init__(self, serving_config_dict: dict[str, Any],
                 expected_followers: int, port: int | None = None,
                 token: str = ""):
        self.expected = expected_followers
        self.handshake = serving_config_dict
        self.token = token
        self._followers: list[socket.socket] = []
        self._lock = threading.Lock()
        self._broken = False
        self._server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._server.bind(("0.0.0.0", port if port is not None else DEFAULT_PORT))
        self._server.listen(max(expected_followers, 1))
        self.port = self._server.getsockname()[1]
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="lockstep-accept", daemon=True
        )
        self._accept_thread.start()

    def _accept_loop(self) -> None:
        while True:
            try:
                conn, addr = self._server.accept()
            except OSError:
                return  # closed
            try:
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                join = read_frame(conn)
                if join.get("op") != "join" or join.get("token", "") != self.token:
                    log.warning("lockstep: rejecting unauthenticated %s", addr)
                    conn.sendall(encode_descriptor(
                        {"op": "reject", "reason": "bad token"}
                    ))
                    conn.close()
                    continue
                with self._lock:
                    if self._broken or len(self._followers) >= self.expected:
                        # a restarted follower is a fresh JAX process the
                        # group cannot re-admit — tell it so, loudly
                        conn.sendall(encode_descriptor({
                            "op": "reject",
                            "reason": "slice membership is full or broken; "
                                      "the whole slice must restart together",
                        }))
                        conn.close()
                        continue
                    conn.sendall(
                        encode_descriptor({"op": "handshake", **self.handshake})
                    )
                    self._followers.append(conn)
                    joined = len(self._followers)
                log.info(
                    "lockstep follower %s joined (%d/%d)",
                    addr, joined, self.expected,
                )
            except (OSError, ConnectionError) as e:
                log.warning("lockstep accept of %s failed: %s", addr, e)
                try:
                    conn.close()
                except OSError:
                    pass

    def wait_ready(self, timeout: float = 600.0) -> None:
        """Block until every follower is connected — the first multi-host
        dispatch would otherwise broadcast into the void and hang the
        devices waiting for processes that never heard about the step."""
        deadline = time.monotonic() + timeout
        while len(self._followers) < self.expected:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"only {len(self._followers)}/{self.expected} lockstep "
                    f"followers joined within {timeout}s"
                )
            time.sleep(0.05)

    def broadcast(self, desc: dict[str, Any]) -> None:
        """Send to every follower. Any send failure poisons the group:
        surviving followers may have replayed frames a dead one never saw,
        so the only safe outcome is a loud LockstepBroken — the engine
        stops serving and the slice restarts together."""
        frame = encode_descriptor(desc)
        failed: list[str] = []
        with self._lock:
            if self._broken:
                raise LockstepBroken("lockstep group already failed")
            for conn in self._followers:
                try:
                    conn.sendall(frame)
                except OSError as e:
                    failed.append(str(e))
            if failed:
                self._broken = True
                for conn in self._followers:
                    try:
                        conn.close()
                    except OSError:
                        pass
                self._followers.clear()
        if failed:
            raise LockstepBroken(
                f"lost lockstep follower(s): {failed}; slice must restart"
            )

    def close(self) -> None:
        try:
            self.broadcast({"op": "stop"})
        except (OSError, LockstepBroken):
            pass
        with self._lock:
            for conn in self._followers:
                try:
                    conn.close()
                except OSError:
                    pass
            self._followers.clear()
        try:
            self._server.close()
        except OSError:
            pass


# ---------------------------------------------------------------------------
# follower
# ---------------------------------------------------------------------------


class LockstepFollower:
    """Non-leader host: connects to the leader, builds the *same* engine
    state (params, caches, compiled functions — identical construction path,
    so identical global arrays), then replays descriptors as jit calls until
    the leader says stop. Runs synchronously; call from the follower pod's
    main thread."""

    def __init__(self, leader_host: str, port: int | None = None,
                 connect_timeout: float = 600.0, token: str = ""):
        self.addr = (leader_host, port if port is not None else DEFAULT_PORT)
        self.connect_timeout = connect_timeout
        self.token = token
        self.engine = None
        # stop() is a cross-thread signal: the pod's event loop calls it
        # while run() blocks in recv on the replay thread — the flag is a
        # threading.Event (a designated handoff, RACE801) and the socket
        # handle is guarded so stop() never races the assignment in run()
        self._sock_lock = threading.Lock()
        self._sock: socket.socket | None = None
        self._stopping = threading.Event()

    def stop(self) -> None:
        """Unblock a blocked ``run`` (SIGTERM path): closing the socket
        makes the pending recv raise, and ``run`` returns cleanly. Safe to
        call from any thread (the pod's loop calls it on SIGTERM while
        the replay thread owns the socket)."""
        self._stopping.set()
        with self._sock_lock:
            sock = self._sock
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass

    def _connect(self) -> socket.socket:
        deadline = time.monotonic() + self.connect_timeout
        while True:
            if self._stopping.is_set():
                # stop() landed while we were still retrying the connect:
                # there is no socket to close yet, so the flag is the only
                # way out of the retry loop
                raise ConnectionAbortedError("lockstep follower stopping")
            try:
                sock = socket.create_connection(self.addr, timeout=10.0)
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                sock.settimeout(None)
                return sock
            except OSError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.5)

    def run(self, die_after_steps: int | None = None) -> int:
        """Returns the number of descriptors replayed (for tests/logs).

        ``die_after_steps`` is fault injection (the failure tests' analogue
        of the reference's mock fail-on-content agents): after replaying N
        descriptors the process dies via ``os._exit`` — no socket shutdown,
        no goodbye — exactly what a follower pod being OOM-killed mid-burst
        looks like to the leader."""
        import jax.numpy as jnp

        from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

        try:
            sock = self._connect()
        except ConnectionAbortedError:
            return 0  # stop() before any connection: nothing replayed
        with self._sock_lock:
            self._sock = sock
            stopping = self._stopping.is_set()
        if stopping:
            # stop() ran between _connect and the assignment above: it saw
            # _sock as None and closed nothing — close here or the recv
            # loop below would block forever with the flag already set
            try:
                sock.close()
            except OSError:
                pass
            return 0
        sock.sendall(encode_descriptor({"op": "join", "token": self.token}))
        handshake = read_frame(sock)
        if handshake.get("op") == "reject":
            raise RuntimeError(
                f"lockstep join rejected: {handshake.get('reason')}"
            )
        if handshake.get("op") != "handshake":
            raise RuntimeError(f"expected handshake, got {handshake.get('op')}")
        config = ServingConfig.from_dict(json.loads(handshake["config_json"]))
        # identical construction path as the leader's engine → identical
        # sharded params/caches/compiled fns on this host's shards
        self.engine = engine = TpuServingEngine(config, lockstep_role="follower")
        steps = 0
        log.info("lockstep follower ready (model %s)", config.model)
        # burst-scoped state: a "decode" descriptor opens a burst with full
        # host inputs; "decode_cont" chunks chain this process's own
        # device-resident tokens/lengths outputs, mirroring the leader's
        # speculative pipeline without any host round-trip
        burst: dict[str, Any] = {}
        carry_tokens = carry_lengths = None
        while True:
            try:
                desc = read_frame(sock)
            except (ConnectionError, OSError):
                if self._stopping.is_set():
                    break  # stop() closed the socket: clean local shutdown
                raise
            op = desc.get("op")
            if op == "stop":
                break
            if op in ("decode", "decode_cont"):
                if op == "decode":
                    burst = {
                        "sampler_mode": tuple(bool(x) for x in desc["sampler_mode"]),
                        "active": jnp.asarray(desc["active"]),
                        "temps": jnp.asarray(desc["temps"]),
                        "topks": jnp.asarray(desc["topks"]),
                        "topps": jnp.asarray(desc["topps"]),
                    }
                    tokens = jnp.asarray(desc["tokens"])
                    lengths = jnp.asarray(desc["lengths"])
                else:
                    tokens, lengths = carry_tokens, carry_lengths
                    if "active" in desc:
                        # pipelined finished-slot freeze: the leader
                        # refreshes the active mask mid-burst; followers
                        # must apply the same mask or their frozen slots'
                        # device state diverges from the leader's
                        burst["active"] = jnp.asarray(desc["active"])
                window = desc.get("window")
                pen = bool(desc.get("pen"))
                fn = engine._decode_fn(
                    burst["sampler_mode"], window, int(desc.get("k", 0)), pen
                )
                args = [
                    engine.params, engine.cache_k, engine.cache_v,
                    tokens, lengths, burst["active"],
                    jnp.asarray(desc["tables"]),
                    jnp.asarray(desc["key"]), burst["temps"],
                    burst["topks"], burst["topps"],
                ]
                if pen:
                    # penalty bursts are sequential on the leader, so every
                    # frame carries fresh pres/freq/counts host state
                    args += [
                        jnp.asarray(desc["pres"]), jnp.asarray(desc["freq"]),
                        jnp.asarray(desc["counts"]),
                    ]
                out = fn(*args)
                # out[0] is the packed tokens+logprobs array (sample-in-
                # program): followers never fetch it — only the leader
                # crosses the host boundary
                carry_tokens, carry_lengths = out[1], out[2]
                engine.cache_k, engine.cache_v = out[3], out[4]
            elif op == "prefill":
                fn = engine._prefill_fn(
                    tuple(bool(x) for x in desc["sampler_mode"])
                )
                out = fn(
                    engine.params, engine.cache_k, engine.cache_v,
                    jnp.asarray(desc["tokens"]), jnp.asarray(desc["lengths"]),
                    jnp.asarray(desc["sel"]), jnp.asarray(desc["key"]),
                    jnp.asarray(desc["temps"]), jnp.asarray(desc["topks"]),
                    jnp.asarray(desc["topps"]),
                )
                engine.cache_k, engine.cache_v = out[2], out[3]
            elif op == "spec_step":
                # fused draft+verify: drafting reads the device-resident
                # context rows, so the descriptor carries only control
                # state plus whichever rows the leader re-synced this step
                # — replay the same jit (same key, so sampled acceptance
                # matches bit-for-bit)
                if engine._ctx_dev is None:
                    engine._ctx_dev = jnp.zeros(
                        (engine.config.slots,
                         engine.model_config.max_seq_len),
                        dtype=jnp.int32,
                    )
                if "ctx_rows" in desc:
                    engine._ctx_dev = engine._ctx_dev.at[
                        jnp.asarray(desc["ctx_rows"])
                    ].set(jnp.asarray(desc["ctx_vals"]))
                fn = engine._spec_step_fn(
                    int(desc["nrb"]),
                    tuple(bool(x) for x in desc["sampler_mode"]),
                )
                out = fn(
                    engine.params, engine.cache_k, engine.cache_v,
                    engine._ctx_dev,
                    jnp.asarray(desc["current"]), jnp.asarray(desc["lengths"]),
                    jnp.asarray(desc["active"]), jnp.asarray(desc["tables"]),
                    jnp.asarray(desc["key"]), jnp.asarray(desc["temps"]),
                    jnp.asarray(desc["topks"]), jnp.asarray(desc["topps"]),
                )
                engine._ctx_dev = out[1]
                engine.cache_k, engine.cache_v = out[2], out[3]
            elif op == "prefill_continue":
                # prefix-cache suffix prefill: block adoption is host state
                # the leader already resolved — the follower just replays
                # the same jit with the same tables/starts
                fn = engine._prefill_continue_fn(
                    tuple(bool(x) for x in desc["sampler_mode"]),
                    int(desc["nrb"]),
                )
                out = fn(
                    engine.params, engine.cache_k, engine.cache_v,
                    jnp.asarray(desc["tokens"]), jnp.asarray(desc["starts"]),
                    jnp.asarray(desc["lengths"]), jnp.asarray(desc["sel"]),
                    jnp.asarray(desc["key"]), jnp.asarray(desc["temps"]),
                    jnp.asarray(desc["topks"]), jnp.asarray(desc["topps"]),
                )
                engine.cache_k, engine.cache_v = out[2], out[3]
            else:
                raise RuntimeError(f"unknown lockstep op {op!r}")
            steps += 1
            if die_after_steps is not None and steps >= die_after_steps:
                log.error("fault injection: follower dying after %d steps", steps)
                os._exit(3)
        sock.close()
        return steps
