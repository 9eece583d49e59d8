"""Teaching fixtures for ``--explain RULEID``: per rule, a minimal
true-positive tree (the bug fires), a true-negative tree (the sanctioned
spelling stays silent), and the fix pattern a red gate should point at.

These are *live* fixtures, not prose: ``tests/test_graftcheck.py``
re-runs every entry through the real analyzer and asserts the TP fires
and the TN stays clean, so ``--explain`` can never teach a pattern the
rules stopped recognizing. Keep each example as small as honesty allows
— the point is that a builder staring at a red gate can read the whole
thing in one screen.

Trees are ``{rel path: source}`` dicts (project rules need real paths:
scope filters key off ``serving/``/``gateway/``/``runtime/``). Entries
are optional for per-file rules (``--explain`` falls back to the rule
summary and check docstring) but required for every FLOW rule — the
flow findings are the ones whose fix is least obvious from the message
alone.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class RuleExample:
    rule: str
    tp: dict[str, str]        # fixture tree where the rule fires
    tn: dict[str, str]        # fixture tree pinning the sanctioned shape
    fix: str                  # the sanctioned fix pattern, as prose


EXAMPLES: dict[str, RuleExample] = {}


def _register(example: RuleExample) -> None:
    EXAMPLES[example.rule] = example


_register(RuleExample(
    rule="FLOW1001",
    tp={
        "langstream_tpu/serving/engine.py": '''\
from functools import partial
import jax

class Engine:
    def step(self, tokens, debug):
        @partial(jax.jit, donate_argnums=(1, 2))
        def _decode(params, cache_k, cache_v, tokens):
            return tokens, cache_k, cache_v

        out = _decode(self.params, self.cache_k, self.cache_v, tokens)
        if debug:
            stale = self.cache_k.sum()   # donated buffer read on a branch
        self.cache_k, self.cache_v = out[1], out[2]
        return out[0]
''',
    },
    tn={
        "langstream_tpu/serving/engine.py": '''\
from functools import partial
import jax

class Engine:
    def step(self, tokens):
        @partial(jax.jit, donate_argnums=(1, 2))
        def _decode(params, cache_k, cache_v, tokens):
            return tokens, cache_k, cache_v

        out = _decode(self.params, self.cache_k, self.cache_v, tokens)
        # the engine pattern: rebind from the outputs BEFORE any read
        self.cache_k, self.cache_v = out[1], out[2]
        return self.cache_k
''',
    },
    fix=(
        "Rebind the donated refs from the call's outputs immediately "
        "after the jitted call, on every path that can read them again "
        "(`self.cache_k, self.cache_v = out[...]` — see the engine's "
        "_run/_dispatch closures). If the old value is genuinely needed "
        "afterwards, copy it before the call or stop donating that "
        "argument."
    ),
))

_register(RuleExample(
    rule="FLOW1002",
    tp={
        "langstream_tpu/serving/engine.py": '''\
import numpy as np

class Engine:
    def admit(self, request):
        rows = len(request.context_tokens)     # per-request value...
        return np.zeros((rows, 4), dtype=np.int32)   # ...shapes a buffer
''',
    },
    tn={
        "langstream_tpu/serving/engine.py": '''\
import numpy as np

def _pow2(n):
    p = 1
    while p < n:
        p *= 2
    return p

class Engine:
    def admit(self, request):
        rows = _pow2(len(request.context_tokens))    # bucketed first
        return np.zeros((rows, 4), dtype=np.int32)
''',
    },
    fix=(
        "Pass the request-derived value through a sanctioned bucketing "
        "function (SANCTIONED_BUCKETING in analysis/rules_flow.py: "
        "_pow2 / _bucket / _read_blocks_for / "
        "_sampler_mode, or any `*bucket*` helper) before it reaches a "
        "shape, a specialization-getter argument, or a `self._*_fns[...]` "
        "key. To sanction a new helper, add it to the registry AND a TN "
        "fixture pinning it (docs/ANALYSIS.md)."
    ),
))

_register(RuleExample(
    rule="FLOW1003",
    tp={
        "langstream_tpu/runtime/agent.py": '''\
import asyncio

class Processor:
    def process(self, records, sink):
        for record in records:
            task = asyncio.ensure_future(self._one(record))
            task.add_done_callback(lambda t: sink.emit(t.result()))
            # the frame returns here: only the loop's weak ref is left
''',
    },
    tn={
        "langstream_tpu/runtime/agent.py": '''\
import asyncio
import logging

from langstream_tpu.core.asyncutil import spawn_retained

log = logging.getLogger(__name__)

class Processor:
    def __init__(self):
        self._tasks = set()

    def process(self, records, sink):
        for record in records:
            task = spawn_retained(
                self._one(record), self._tasks, log, "chain failed",
            )
            task.add_done_callback(lambda t: sink.emit(t.result()))
''',
    },
    fix=(
        "Route the coroutine through core/asyncutil.spawn_retained with "
        "an instance-owned task set: the set holds a strong reference "
        "until the task finishes and a failure is logged instead of "
        "vanishing. Storing the handle on `self`, in a collection, or "
        "awaiting it also retains it."
    ),
))

_register(RuleExample(
    rule="FLOW1004",
    tp={
        "langstream_tpu/serving/state.py": '''\
class State:
    def snapshot(self):
        with self._table_lock:
            with self._stats_lock:      # order: table -> stats
                return dict(self._stats)

    def record(self):
        with self._stats_lock:
            self._refresh()

    def _refresh(self):
        with self._table_lock:          # order: stats -> table (cycle!)
            self._tables += 1
''',
    },
    tn={
        "langstream_tpu/serving/state.py": '''\
class State:
    def snapshot(self):
        with self._table_lock:
            with self._stats_lock:      # one global order everywhere:
                return dict(self._stats)

    def record(self):
        with self._table_lock:
            with self._stats_lock:      # table -> stats again
                self._stats["n"] += 1
''',
    },
    fix=(
        "Pick one global acquisition order for the locks in the cycle "
        "and make every path (including helpers reached through the "
        "call graph while a lock is held) follow it — or collapse the "
        "two locks into one. The finding's message lists the cycle; the "
        "anchor line is one of its edges."
    ),
))

_register(RuleExample(
    rule="GC001",
    tp={
        "langstream_tpu/serving/util.py": '''\
import time

def measure(step):
    # graftcheck: disable=OBS501 legacy timing path
    t0 = time.monotonic()      # the code was fixed; the escape lingers
    step()
    return time.monotonic() - t0
''',
    },
    tn={
        "langstream_tpu/serving/util.py": '''\
import time

def stamp():
    # graftcheck: disable=OBS501 wall-clock timestamp for the audit log
    return time.time()         # the suppression still silences a finding
''',
    },
    fix=(
        "Delete the stale `# graftcheck: disable=...` comment (or the "
        "regression it was hiding). A suppression that silences nothing "
        "would mask whatever fires on that line next."
    ),
))

_register(RuleExample(
    rule="OBS504",
    tp={
        "langstream_tpu/serving/health.py": '''\
import jax

def check_engine(engine):
    # a liveness probe that syncs the device hangs exactly when the
    # device does — the one moment it must answer
    jax.block_until_ready(engine.last_logits)
    with engine.dispatch_lock:
        return engine.state
''',
    },
    tn={
        "langstream_tpu/serving/health.py": '''\
def check_engine(engine, clock):
    # the sanctioned shape: snapshot reads + arithmetic, nothing that
    # can wait on the device, a lock, or I/O
    samples = list(engine.ring)
    age = clock() - engine.last_step
    return "wedged" if age > 60.0 and engine.queued > 0 else "ok"
''',
    },
    fix=(
        "Make the checker judge host-side evidence the engine loop "
        "already recorded (heartbeat stamps, flight-ring snapshots) "
        "instead of touching the device or its locks: list(deque) "
        "copies, attribute loads, and arithmetic are the whole "
        "sanctioned vocabulary (see serving/health.py)."
    ),
))

_register(RuleExample(
    rule="OBS505",
    tp={
        "langstream_tpu/serving/attribution.py": '''\
import jax

class ProgramLedger:
    def report(self, engine):
        # an attribution poll that syncs the device hangs exactly when
        # the operator asks which program owns the stall — and the lock
        # queues behind the wedged dispatch holding it
        jax.block_until_ready(engine.last_out)
        with engine.dispatch_lock:
            return dict(self.costs)
''',
    },
    tn={
        "langstream_tpu/serving/attribution.py": '''\
class ProgramLedger:
    def report(self):
        # the sanctioned shape: C-level snapshot copies + arithmetic —
        # nothing that can wait on the device, a lock, or I/O
        out = []
        for program, cost in list(self.costs.items()):
            samples = sorted(list(self.times.get(program) or ()))
            out.append({"program": program, "n": len(samples)})
        return out
''',
    },
    fix=(
        "Attribution reads must judge evidence the engine loop already "
        "recorded: snapshot containers with list()/dict() copies, read "
        "byte totals computed once at engine init (never walk live "
        "donated arrays), and do arithmetic on the snapshot. If a "
        "number needs the device or a lock to compute, record it on "
        "the engine loop at dispatch time and let the read path "
        "snapshot it (see serving/attribution.py and "
        "_DeviceLru.device_bytes)."
    ),
))

_register(RuleExample(
    rule="OBS506",
    tp={
        "langstream_tpu/serving/journey.py": '''\
import jax

class JourneyLedger:
    def events(self, journey_id, engine):
        # a /journey read that syncs the device hangs exactly when the
        # operator asks where a wedged request's time went — and the
        # lock queues the stitcher behind the dispatch holding it
        jax.block_until_ready(engine.last_out)
        with engine.dispatch_lock:
            return list(self._entries[journey_id])
''',
    },
    tn={
        "langstream_tpu/serving/journey.py": '''\
class JourneyLedger:
    def record(self, journey_id, kind):
        # writes: GIL-atomic container appends + counter bumps only
        entry = self._entries.get(journey_id)
        if entry is not None:
            entry.append({"kind": kind})
            self.recorded_events += 1

    def events(self, journey_id):
        # reads: list() snapshot copies + arithmetic, nothing that waits
        entry = self._entries.get(journey_id)
        return list(entry) if entry is not None else []
''',
    },
    fix=(
        "Journey writes must be GIL-atomic container appends at the "
        "sites where the engine already records flight events — never "
        "behind a lock, never touching the device. Journey reads (the "
        "pod /journey payload builder, the control-plane stitcher) "
        "snapshot with list()/dict() copies and do pure arithmetic "
        "(stitch/segments in serving/journey.py). Anything that needs "
        "the device or a lock must be recorded at dispatch time and "
        "snapshotted later, the flight-recorder pattern."
    ),
))

_register(RuleExample(
    rule="POOL701",
    tp={
        "langstream_tpu/serving/kvtransfer.py": '''\
import jax

def serialize_handoff(header, gathered):
    # a device sync inside serialization stalls the engine loop against
    # the device on EVERY export — and a lock queues the handoff behind
    # whatever dispatch holds it
    jax.block_until_ready(gathered)
    with header["engine"].dispatch_lock:
        return bytes(header["request"], "utf-8")
''',
    },
    tn={
        "langstream_tpu/serving/kvtransfer.py": '''\
import jax

def serialize_handoff(header, arrays):
    # the sanctioned shape: header JSON + host-array bytes, no waits
    chunks = [arrays[name].tobytes() for name in sorted(arrays)]
    return b"LSKV" + b"".join(chunks)

def _fetch_rows(gathered):
    # the ONE sanctioned sync point: a _fetch* stage, run on the
    # dispatch thread and timed (mirrors the engine's _fetch_chunk)
    jax.block_until_ready(gathered)
    return gathered
''',
    },
    fix=(
        "Keep kv-transfer serialization to header JSON plus tobytes() on "
        "HOST arrays, and confine the one device sync to a dispatch-"
        "thread _fetch* stage (kvtransfer._fetch_rows), timed like the "
        "engine's _fetch_chunk. Locks and blocking I/O have no place on "
        "the handoff path — a /kv/export pickup must answer even while "
        "the engine is mid-dispatch (docs/DISAGG.md)."
    ),
))

_register(RuleExample(
    rule="PFX801",
    tp={
        "langstream_tpu/serving/prefixstore.py": '''\
import jax

class PrefixStore:
    def take_t1(self, digest_hex, engine):
        # a T1 promotion take that syncs the device queues EVERY
        # admission behind the dispatch in flight — and the lock queues
        # the lookup behind whatever holds it
        jax.block_until_ready(engine.last_out)
        with self._lock:
            return self._t1.pop(digest_hex, None)

    def _shrink_t1(self, storage):
        while self.t1_bytes > self.budget:
            digest, entry = self._t1.popitem(last=False)
            # blocking T2 I/O inside the eviction DECISION: every
            # byte-budget walk becomes a per-pass host stall
            storage.put(digest, open("/tmp/x", "rb").read())
''',
    },
    tn={
        "langstream_tpu/serving/prefixstore.py": '''\
class PrefixStore:
    def take_t1(self, digest_hex):
        # the sanctioned shape: GIL-atomic container ops + arithmetic
        entry = self._t1.pop(digest_hex, None)
        if entry is not None:
            self.t1_bytes -= entry["nbytes"]
        return entry

    def _shrink_t1(self):
        # the eviction DECISION only moves the entry onto the handoff
        # deque; the background hydrator does the object-storage I/O
        while self.t1_bytes > self.budget and self._t1:
            digest, entry = self._t1.popitem(last=False)
            self.t1_bytes -= entry["nbytes"]
            self._jobs.append(("put", digest, entry))
            self._kick.set()

    def _io_put(self, storage, digest, entry):
        # hydrator thread: T2 I/O is exempt HERE by design
        storage.put(digest, entry["blob"])
''',
    },
    fix=(
        "Keep every T0/T1 lookup, promotion take, and eviction decision "
        "to GIL-atomic container ops plus arithmetic — they run at the "
        "engine loop's safe point, on the admission path. Anything that "
        "must touch object storage becomes a job on the hydrator's "
        "handoff deque (PrefixStore._io_* processes it on the "
        "background thread and hands the result back through the "
        "results deque for apply_results to apply loop-side). Device "
        "syncs belong only in the dispatch-thread closures the engine "
        "already times (the promote scatter / demote gather _run "
        "closures — docs/PREFIX.md)."
    ),
))

_register(RuleExample(
    rule="LORA1701",
    tp={
        "langstream_tpu/serving/adapters.py": '''\
import jax

class AdapterStore:
    def t0_assign(self, name, engine):
        # a T0 row-assignment that syncs the device queues EVERY
        # admission behind the dispatch in flight — and the lock queues
        # the resolve behind whatever holds it
        jax.block_until_ready(engine.last_out)
        with self._lock:
            return self._rows.pop(name, None)

    def _shrink_t1(self, storage):
        while self.t1_bytes > self.budget:
            name, entry = self._t1.popitem(last=False)
            # blocking T2 I/O inside the eviction DECISION: every
            # byte-budget walk becomes a per-pass host stall
            storage.put(name, open("/tmp/x", "rb").read())
''',
    },
    tn={
        "langstream_tpu/serving/adapters.py": '''\
class AdapterStore:
    def t0_assign(self, name):
        # the sanctioned shape: GIL-atomic container ops + arithmetic
        for row, holder in self._rows.items():
            if holder is None:
                self._rows[row] = name
                return row
        return None

    def _shrink_t1(self):
        # the eviction DECISION only moves the entry onto the handoff
        # deque; the background hydrator does the object-storage I/O
        while self.t1_bytes > self.budget and self._t1:
            name, entry = self._t1.popitem(last=False)
            self.t1_bytes -= entry["nbytes"]
            self._jobs.append(("put", name, entry))
            self._kick.set()

    def _io_put(self, storage, name, entry):
        # hydrator thread: T2 I/O is exempt HERE by design
        storage.put(name, entry["blob"])
''',
    },
    fix=(
        "Keep every adapter resolve — T0 row lookup/assignment, pin "
        "bookkeeping, T1 take, hydration request — and every eviction "
        "decision to GIL-atomic container ops plus arithmetic: they "
        "run at the engine loop's safe point, on the admission path, "
        "ahead of adapter-less traffic too. Anything that must touch "
        "object storage becomes a job on the hydrator's handoff deque "
        "(AdapterStore._io_* processes it on the background thread and "
        "hands results back for apply_results to apply loop-side). The "
        "one device wait is the row-upload closure the engine's "
        "_load_adapter_row runs and times on the dispatch thread — "
        "docs/ADAPTERS.md."
    ),
))

_register(RuleExample(
    rule="STRM1501",
    tp={
        "langstream_tpu/gateway/server.py": '''\
import jax

class GatewayServer:
    async def _stream_push_loop(self, ws, reader, active):
        while not ws.closed:
            records = await reader.read(timeout=0.5)
            for record in records:
                # a lock inside the frame-writer loop: one slow client
                # head-of-line blocks every stream on this connection
                with self._frames_lock:
                    self._frame_count += 1
                # a device sync per frame stalls the emit path against
                # the device — the wait lands in the client's TBT
                jax.block_until_ready(record.value)
                await ws.send_json({"record": record.value})
''',
    },
    tn={
        "langstream_tpu/gateway/server.py": '''\
class GatewayServer:
    async def _stream_push_loop(self, ws, reader, active):
        # the sanctioned shape: reads, header matches, frame writes —
        # counter bumps are GIL-atomic, no locks, nothing that waits
        while not ws.closed:
            records = await reader.read(timeout=0.5)
            for record in records:
                sid = record.header_map().get("langstream-stream-id")
                if sid is None or sid not in active:
                    continue
                await ws.send_json(self._record_json(record))
''',
    },
    fix=(
        "Keep every per-token delivery — the engine's burst-flush chunk "
        "delivery, TbtDigest.add, the gateway frame-writer loops — to "
        "container ops, digest bumps, and frame writes. Per-emit "
        "telemetry is the bounded interval digest (binary search + "
        "counter bumps), never a lock-guarded structure; anything that "
        "can wait (device syncs, file/socket I/O beyond the client "
        "frame write itself) moves off the emit path. The cancel "
        "registry's small lock is fine — it runs per disconnect, not "
        "per token (docs/OBSERVABILITY.md Streaming)."
    ),
))

_register(RuleExample(
    rule="FLEET601",
    tp={
        "langstream_tpu/controlplane/autoscaler.py": '''\
class FleetAutoscaler:
    def step(self, backend, decision, now):
        if decision.action == "up":
            # replica write with no cooldown gate: one noisy signal
            # flip-flops the fleet
            backend.set_replicas(decision.target)
''',
    },
    tn={
        "langstream_tpu/controlplane/autoscaler.py": '''\
class FleetAutoscaler:
    def _cooldown_ok(self, now):
        return (
            self._last_scale_t is None
            or now - self._last_scale_t >= self.spec.cooldown_s
        )

    def step(self, backend, decision, now):
        if decision.action == "up":
            if self._cooldown_ok(now):
                backend.set_replicas(decision.target)
                self._last_scale_t = now
''',
    },
    fix=(
        "Gate every replica-count write under an `if` whose condition "
        "names the cooldown (`if self._cooldown_ok(now): "
        "backend.set_replicas(...)`), and stamp the scale time inside "
        "the gate. The gate must be visible AT the write site — a "
        "rate limit enforced three callers up is invisible to the "
        "reader auditing the scale path."
    ),
))

_register(RuleExample(
    rule="FLEET602",
    tp={
        "langstream_tpu/controlplane/autoscaler.py": '''\
import urllib.request

class FleetAutoscaler:
    def decide(self, observations, now):
        # I/O inside the decision: one wedged pod freezes the judgment
        extra = urllib.request.urlopen("http://pod:8080/flight/summary")
        with self._lock:
            return "up" if len(observations) < 2 else "none"
''',
    },
    tn={
        "langstream_tpu/controlplane/autoscaler.py": '''\
class FleetAutoscaler:
    def decide(self, observations, now):
        # the sanctioned shape: pure arithmetic over snapshots the
        # backend's observe() already fetched
        queued = sum(o["queued"] for o in observations)
        if queued > 8 * max(1, len(observations)):
            return "up"
        return "none"
''',
    },
    fix=(
        "Keep decide() and its pressure/idle/cooldown helpers pure over "
        "the observation list: the backend's observe() does the pod "
        "fan-in BEFORE judgment, apply does the writes AFTER it. If "
        "the decision needs more evidence, extend the observation "
        "shape, never fetch mid-decide."
    ),
))

_register(RuleExample(
    rule="FLT901",
    tp={
        "langstream_tpu/serving/engine.py": '''\
class TpuServingEngine:
    async def _decode_burst(self, loop, active):
        try:
            out = await loop.run_in_executor(self._executor, self._step)
        except Exception:
            # swallowed: an allocator failure becomes a silent no-op —
            # no shrink, no shed, the request just never answers
            return
        self._apply(out)
''',
    },
    tn={
        "langstream_tpu/serving/engine.py": '''\
class TpuServingEngine:
    async def _decode_burst(self, loop, active):
        try:
            out = await loop.run_in_executor(self._executor, self._step)
        except Exception as e:
            # the sanctioned shape: classify, adapt, re-raise the rest
            if self._resource_exhausted(e):
                self._shed_or_shrink(e)
                return
            raise
        self._apply(out)
''',
    },
    fix=(
        "On the engine's device-dispatch paths, every broad except must "
        "first consult self._resource_exhausted(e) — allocator failures "
        "route to the pool-shrink/shed adaptation (docs/RESILIENCE.md) — "
        "and `raise` everything it does not explicitly handle. A broad "
        "handler that returns/passes turns device memory pressure into "
        "silent request loss."
    ),
))

_register(RuleExample(
    rule="NET1201",
    tp={
        "langstream_tpu/serving/chainer_client.py": '''\
import urllib.request


def offer_handoff(url: str, payload: bytes) -> bytes:
    # no timeout: a dead decode pod parks this thread in recv forever
    with urllib.request.urlopen(url, data=payload) as resp:
        return resp.read()
''',
    },
    tn={
        "langstream_tpu/serving/chainer_client.py": '''\
import urllib.request

from langstream_tpu.serving.handoff import socket_timeout_s


def offer_handoff(url: str, payload: bytes, deadline: float | None) -> bytes:
    # the sanctioned shape: every blocking hop carries an explicit bound,
    # derived from the request's remaining deadline budget when one rides
    with urllib.request.urlopen(
        url, data=payload, timeout=socket_timeout_s(deadline)
    ) as resp:
        return resp.read()
''',
    },
    fix=(
        "Every blocking HTTP/socket call on a serving/gateway/"
        "k8s-compute path passes an explicit timeout= argument. When "
        "the request carries a langstream-deadline, derive the bound "
        "from the remaining budget (serving/handoff.py "
        "socket_timeout_s); otherwise pick a finite cap. A call with "
        "no bound turns one dead peer into a stuck thread — the "
        "stranded-handoff failure class docs/RESILIENCE.md refuses."
    ),
))

_register(RuleExample(
    rule="SPMD1301",
    tp={
        "langstream_tpu/serving/lockstep.py": '''\
import time

class LockstepFollower:
    def run(self, engine, steps):
        for step in steps:
            # host-local clock read decides control flow AHEAD of the
            # jitted dispatch: each replica reads a different clock, so
            # one follower returns early while the leader dispatches —
            # the collective inside the computation deadlocks the mesh
            if time.monotonic() > step.deadline:
                return
            fn = engine._decode_fn(step.batch)
            fn(step.tokens)
''',
    },
    tn={
        "langstream_tpu/serving/lockstep.py": '''\
class LockstepFollower:
    def run(self, engine, steps):
        for step in steps:
            # the sanctioned shape: the guard is lockstep-replicated
            # state (broadcast by the leader), identical on every
            # replica, so all replicas take the same branch
            if step.lockstep_stop:
                return
            fn = engine._decode_fn(step.batch)
            fn(step.tokens)
''',
    },
    fix=(
        "A branch ahead of a lockstep dispatch may only consult "
        "replicated state: values the leader broadcast over the "
        "lockstep channel (spell it so — `step.lockstep_stop`, "
        "`self._stopping_lockstep`). Host-local reads (time.*, "
        "random.*, os.environ, socket.gethostname) diverge per "
        "replica; move them to the leader, broadcast the decision, "
        "and branch on the broadcast result."
    ),
))

_register(RuleExample(
    rule="SPMD1302",
    tp={
        "langstream_tpu/serving/engine.py": '''\
import time

class TpuServingEngine:
    def _decode_loop(self, tokens):
        self._lockstep.broadcast(len(tokens))
        # a host-local value as the specialization key: replicas hash
        # different keys, compile different programs, and the lockstep
        # mesh dispatches mismatched executables
        fn = self._decode_fn(int(time.time()) % 7)
        return fn(tokens)
''',
    },
    tn={
        "langstream_tpu/serving/engine.py": '''\
class TpuServingEngine:
    def _decode_loop(self, tokens):
        self._lockstep.broadcast(len(tokens))
        # the sanctioned shape: the key is derived from the request
        # batch every replica received identically
        fn = self._decode_fn(len(tokens))
        return fn(tokens)
''',
    },
    fix=(
        "Specialization-getter arguments (_decode_fn / _prefill_fn / "
        "_spec_step_fn) are jit cache keys: every replica must compute "
        "the same key or the mesh compiles divergent programs. Derive "
        "keys from the (broadcast) batch shape, never from host-local "
        "sources (time.*, random.*, os.environ, hostname) — and note "
        "casts do not launder divergence: int(time.time()) is still "
        "per-replica."
    ),
))

_register(RuleExample(
    rule="SPMD1303",
    tp={
        "langstream_tpu/serving/engine.py": '''\
class TpuServingEngine:
    def _decode_loop(self, batch):
        # a hot-path dispatch with NO lockstep broadcast anywhere in
        # the method tree: followers replaying the schedule have no
        # way to learn this step's shape, so the mesh diverges
        fn = self._decode_fn(batch.rows)
        return fn(batch.tokens)
''',
    },
    tn={
        "langstream_tpu/serving/engine.py": '''\
class TpuServingEngine:
    def _decode_loop(self, batch):
        # the sanctioned shape: the leader broadcasts the step
        # descriptor over the lockstep channel before dispatching
        rows = self._lockstep.broadcast(batch.rows)
        fn = self._decode_fn(rows)
        return fn(batch.tokens)
''',
    },
    fix=(
        "Every engine hot-path method tree that dispatches through a "
        "specialization getter must broadcast the step descriptor over "
        "the lockstep channel first (`self._lockstep.broadcast(...)`), "
        "so followers replay the identical dispatch sequence. The "
        "check is method-granular: the broadcast belongs in the same "
        "outermost method tree as the dispatch it describes."
    ),
))

_register(RuleExample(
    rule="HOT1401",
    tp={
        "langstream_tpu/serving/engine.py": '''\
import jax.numpy as jnp

from langstream_tpu.serving.sample import pick

class TpuServingEngine:
    def _decode_loop(self):
        logits = jnp.zeros((4,))
        return pick(logits)
''',
        "langstream_tpu/serving/sample.py": '''\
import jax.numpy as jnp
import numpy as np

def pick(logits):
    idx = jnp.argmax(logits)
    # blocking materialization INSIDE the hot loop (reached from
    # _decode_loop): the host stalls against the device every token
    return int(np.asarray(idx))
''',
    },
    tn={
        "langstream_tpu/serving/engine.py": '''\
import jax.numpy as jnp
import numpy as np

class TpuServingEngine:
    def _decode_loop(self):
        self._pending = jnp.zeros((4,))
        return self._fetch_chunk()

    def _fetch_chunk(self):
        # the ONE sanctioned sync point: a _fetch* stage, run on the
        # dispatch thread and timed — materialization is its job
        return np.asarray(self._pending)
''',
    },
    fix=(
        "Materialization (np.asarray / .item() / float() / .tolist() / "
        "block_until_ready) on a device value reachable from the "
        "decode hot loop belongs in a sanctioned fetch stage: a "
        "`_fetch*` method (or a dispatch closure's `_run`), where the "
        "engine overlaps the sync with the next dispatch and times it. "
        "Keep the hot loop itself submit-only."
    ),
))

_register(RuleExample(
    rule="HOT1402",
    tp={
        "langstream_tpu/serving/engine.py": '''\
import jax.numpy as jnp

class TpuServingEngine:
    def _decode_loop(self, tokens):
        done = jnp.any(tokens == 0)
        # implicit __bool__ on a device value: the innocuous-looking
        # `if` blocks the hot loop against the device every iteration
        if done:
            return None
        return tokens
''',
    },
    tn={
        "langstream_tpu/serving/engine.py": '''\
import jax.numpy as jnp

class TpuServingEngine:
    def _decode_loop(self, tokens):
        # the sanctioned shape: the fetch stage materializes ONCE and
        # the hot loop branches on the host-side result
        done = self._fetch_done(tokens)
        if done:
            return None
        return tokens

    def _fetch_done(self, tokens):
        return bool(jnp.any(tokens == 0))
''',
    },
    fix=(
        "Never let a device value reach `if`/`while`/`assert` in the "
        "hot loop — each implicit __bool__ is a hidden "
        "block_until_ready. Materialize once in a `_fetch*` stage "
        "(`bool(...)` there is sanctioned) and branch on the returned "
        "host value, or restructure so the branch happens inside the "
        "jitted computation (jnp.where / lax.cond)."
    ),
))

_register(RuleExample(
    rule="INC1601",
    tp={
        "langstream_tpu/serving/incident.py": '''\
import json
import time

class IncidentRecorder:
    def should_capture(self, kind, dedup_key=None):
        key = kind if dedup_key is None else f"{kind}:{dedup_key}"
        # a lock on the breach-observe path: health() and the finish
        # path now contend with the writer thread's disk latency at
        # the exact moment the engine is degraded
        with self._lock:
            last = self._last_capture.get(key)
            now = time.monotonic()
            if last is not None and now - last < self.cooldown_s:
                return False
            self._last_capture[key] = now
        return True

    def submit(self, bundle):
        bundle_id = f"incident-{self._seq:06d}"
        # file I/O inline at the breach site: the probe handler that
        # tripped the trigger is now waiting on the disk
        with open(self._path_for(bundle_id), "w") as fh:
            json.dump(bundle, fh)
        return bundle_id
''',
    },
    tn={
        "langstream_tpu/serving/incident.py": '''\
import time

class IncidentRecorder:
    def should_capture(self, kind, dedup_key=None):
        # the sanctioned shape: GIL-atomic dict ops on a vocabulary-
        # bounded dict; a racing duplicate capture is dedup'd by the
        # writer, never waited for here
        key = kind if dedup_key is None else f"{kind}:{dedup_key}"
        last = self._last_capture.get(key)
        now = time.monotonic()
        if last is not None and now - last < self.cooldown_s:
            self.suppressed[kind] = self.suppressed.get(kind, 0) + 1
            return False
        self._last_capture[key] = now
        return True

    def submit(self, bundle):
        # deque handoff to the writer thread, same shape journal.admit
        # proved: append + wake, zero waits
        self.captured += 1
        self._pending.append(bundle)
        self._wake.set()
        return f"incident-{self._seq + self.captured:06d}"
''',
    },
    fix=(
        "Keep the breach-observe side (should_capture, submit, the "
        "breaker-storm/worst-journeys predicates, the engine's "
        "_incident_capture assembly) to GIL-atomic container ops and a "
        "deque handoff; all file I/O and the bundle-table lock live on "
        "the dedicated writer thread (`_run_writer`/`_drain`), exactly "
        "the journal.py split. If evidence assembly needs a section "
        "that can wait, snapshot it from state the hot path already "
        "maintains instead of computing it at the breach site "
        "(docs/OBSERVABILITY.md, Incident bundles & exemplars)."
    ),
))
