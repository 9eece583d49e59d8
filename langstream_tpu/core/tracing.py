"""End-to-end record tracing: propagated context, spans, ring buffer.

The Dapper-style counterpart of the per-agent Prometheus counters: a
record picks up a ``langstream-trace`` header at the first hop (gateway
produce, or the runner when a source-originated record has none) and every
layer it crosses — gateway, agent hops, composite stages, the serving
engine — contributes spans sharing the header's ``trace_id``. With it, a
3 s client TTFT decomposes into named per-hop spans instead of one opaque
number (see ``docs/OBSERVABILITY.md``).

Design constraints (this module is on the record hot path):

- **zero dependencies** — stdlib only, importable from every layer
  (:func:`host_span` uses JAX's profiler only where JAX is already loaded);
- **always-on-cheap** — a span is one small object and one deque append;
  ids come from ``os.urandom``; durations from ``time.monotonic()``
  (wall clock is for display anchoring only, never measurement);
- **never raises** — span finishing and JSONL export swallow their own
  failures; tracing must not take down serving;
- **bounded** — finished spans land in a process-global ring buffer
  (``LS_TPU_TRACE_BUFFER`` entries, default 2048) served by the pod's
  ``/traces`` endpoints; optional durable export appends JSONL lines to
  ``LS_TPU_TRACE_LOG``.

Header format (W3C ``traceparent``-compatible):
``00-<32 hex trace_id>-<16 hex span_id>-01``.

Context propagates two ways:

- **on the record** — the ``langstream-trace`` header rides the record
  through brokers exactly like any other string header (the kafka lanes
  serialize headers reversibly; the memory broker passes them through);
- **ambiently** — a :data:`contextvars.ContextVar` set by the runtime
  around per-record processing, so deep callees (the serving engine's
  ``generate``) can parent their spans without any signature plumbing.
  ``asyncio`` tasks snapshot the context at creation, which is exactly
  the per-record task boundary the runtime uses.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import logging
import os
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any

log = logging.getLogger(__name__)

#: the record header carrying the trace context across hops (preserved by
#: every broker runtime the way ``OFFSET_HEADER`` is transport-local)
TRACE_HEADER = "langstream-trace"

_VERSION = "00"
_FLAGS = "01"  # sampled


def _hex_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


def fresh_trace_id() -> str:
    """A new 32-hex trace-id-shaped identifier. The journey ledger
    (serving/journey.py) keys untraced requests with one of these so a
    journey id is always trace-id-shaped — ``/journey/{id}`` consumers
    never need to care whether the request was traced."""
    return _hex_id(16)


@dataclass(frozen=True)
class TraceContext:
    """One (trace, parent-span) coordinate — what the header encodes."""

    trace_id: str  # 32 hex chars
    span_id: str   # 16 hex chars

    @classmethod
    def new(cls) -> "TraceContext":
        return cls(trace_id=_hex_id(16), span_id=_hex_id(8))

    @classmethod
    def parse(cls, header: Any) -> "TraceContext | None":
        """Parse a ``langstream-trace`` / traceparent value; None when the
        value is absent or malformed (a bad client header must not 500 the
        gateway — it just starts a fresh trace)."""
        if not isinstance(header, str):
            return None
        parts = header.strip().split("-")
        if len(parts) != 4:
            return None
        version, trace_id, span_id, _flags = parts
        if len(version) != 2 or len(trace_id) != 32 or len(span_id) != 16:
            return None
        try:
            int(trace_id, 16), int(span_id, 16)
        except ValueError:
            return None
        if trace_id == "0" * 32 or span_id == "0" * 16:
            return None
        return cls(trace_id=trace_id, span_id=span_id)

    def to_header(self) -> str:
        return f"{_VERSION}-{self.trace_id}-{self.span_id}-{_FLAGS}"

    def child(self) -> "TraceContext":
        """Same trace, fresh span id (the id a new child span takes)."""
        return TraceContext(trace_id=self.trace_id, span_id=_hex_id(8))


# ---------------------------------------------------------------------------
# ambient context (per-record, task-scoped)
# ---------------------------------------------------------------------------

_current: contextvars.ContextVar[TraceContext | None] = contextvars.ContextVar(
    "langstream_trace_context", default=None
)


def current_context() -> TraceContext | None:
    return _current.get()


def set_current(ctx: TraceContext | None) -> contextvars.Token:
    return _current.set(ctx)


def reset_current(token: contextvars.Token) -> None:
    try:
        _current.reset(token)
    except ValueError:
        # token from another context (callback crossed tasks): best-effort
        _current.set(None)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


class Span:
    """One timed operation. ``end()`` is idempotent and never raises; an
    unfinished span simply never reaches the buffer (no half-open junk in
    ``/traces``)."""

    __slots__ = (
        "trace_id", "span_id", "parent_id", "name", "service",
        "attributes", "error", "_start_mono", "_start_wall_ms", "_ended",
    )

    def __init__(
        self,
        name: str,
        service: str,
        trace_id: str,
        span_id: str,
        parent_id: str | None,
        attributes: dict[str, Any] | None = None,
    ):
        self.name = name
        self.service = service
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.attributes = dict(attributes) if attributes else {}
        self.error: str | None = None
        self._start_mono = time.monotonic()
        # wall clock anchors the span on a human timeline only; durations
        # below are monotonic-only (OBS501 is the gate for that rule)
        self._start_wall_ms = time.time() * 1000.0
        self._ended = False

    def context(self) -> TraceContext:
        """This span as a parent coordinate — what gets stamped into the
        record header so downstream spans nest under it."""
        return TraceContext(trace_id=self.trace_id, span_id=self.span_id)

    def set_attribute(self, key: str, value: Any) -> None:
        self.attributes[key] = value

    def end(self, error: BaseException | str | None = None) -> float:
        """Finish the span; returns its duration in seconds. Idempotent:
        a second end keeps the first timing."""
        duration_s = time.monotonic() - self._start_mono
        if self._ended:
            return duration_s
        self._ended = True
        if isinstance(error, BaseException):
            self.error = str(error) or error.__class__.__name__
        elif error is not None:
            self.error = str(error)
        try:
            SPANS.add(self._to_dict(duration_s))
        except Exception:  # tracing must never break the traced path
            log.debug("span buffer append failed", exc_info=True)
        return duration_s

    def _to_dict(self, duration_s: float) -> dict[str, Any]:
        out: dict[str, Any] = {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "service": self.service,
            "start_ms": round(self._start_wall_ms, 3),
            "duration_ms": round(duration_s * 1000.0, 3),
        }
        if self.attributes:
            out["attributes"] = self.attributes
        if self.error:
            out["error"] = self.error
        return out

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end(error=exc)


def start_span(
    name: str,
    service: str,
    parent: "TraceContext | Span | str | None" = None,
    attributes: dict[str, Any] | None = None,
) -> Span:
    """Open a span. ``parent`` may be a context, another span, a raw header
    value, or None — None falls back to the ambient context, then to a
    fresh root trace."""
    if isinstance(parent, Span):
        ctx: TraceContext | None = parent.context()
    elif isinstance(parent, TraceContext):
        ctx = parent
    else:
        # raw header value (or junk a client sent): parse returns None on
        # anything malformed, falling back to ambient/new-root below
        ctx = TraceContext.parse(parent)
    if ctx is None:
        ctx = current_context()
    if ctx is None:
        return Span(
            name, service,
            trace_id=_hex_id(16), span_id=_hex_id(8), parent_id=None,
            attributes=attributes,
        )
    return Span(
        name, service,
        trace_id=ctx.trace_id, span_id=_hex_id(8), parent_id=ctx.span_id,
        attributes=attributes,
    )


def record_span(
    name: str,
    service: str,
    parent: "TraceContext | Span | str | None",
    start_monotonic: float,
    end_monotonic: float,
    attributes: dict[str, Any] | None = None,
) -> None:
    """Record a span retroactively from monotonic timestamps already taken
    (the serving engine's queue/prefill/decode phases are measured by its
    own request timestamps; spans are materialized at completion). Never
    raises."""
    try:
        span = start_span(name, service, parent=parent, attributes=attributes)
        duration_s = max(0.0, end_monotonic - start_monotonic)
        # re-anchor: start_ms was stamped "now"; shift it back to the real
        # phase start on the shared monotonic axis
        span._start_wall_ms -= (time.monotonic() - start_monotonic) * 1000.0
        span._ended = True
        SPANS.add(span._to_dict(duration_s))
    except Exception:
        log.debug("record_span failed", exc_info=True)


# ---------------------------------------------------------------------------
# host spans on the profiler's clock
# ---------------------------------------------------------------------------

#: what :func:`host_span` hands out where no profiler session can be open
_NO_SPAN = contextlib.nullcontext()
_annotation = None  # jax.profiler.TraceAnnotation, once JAX is loaded
_session = None  # its ``is_enabled``: whether a profiler session is open


def host_span(name: str, **meta: Any):
    """A host span on the PROFILER's clock (not this module's ring): a
    ``jax.profiler.TraceAnnotation`` around synchronous code, so that under
    a profiler session (``/profile/start``, the benchmark's ``--trace 1``)
    the stretch lands in the ``/host:CPU`` plane of the same ``.xplane.pb``
    as the device's operations. ``name`` is one of
    ``serving/flight.py`` ``SPANS`` (the whole vocabulary); ``meta`` become
    the event's stats. Outside a session this checks the profiler's flag and
    hands out one shared no-op context manager; so it does in a process that
    never loaded JAX (a gateway pod with no engine): the helper never
    imports JAX itself."""
    global _annotation, _session
    if _session is None:
        if "jax" not in sys.modules:
            return _NO_SPAN
        from jax.profiler import TraceAnnotation as _annotation

        _session = _annotation.is_enabled
    if not _session():
        return _NO_SPAN
    return _annotation(name, **meta)


# ---------------------------------------------------------------------------
# span ring buffer + JSONL export
# ---------------------------------------------------------------------------


class SpanBuffer:
    """Bounded, thread-safe buffer of finished spans (as plain dicts).

    Process-global by design: one pod = one process = one buffer, which is
    what the pod's ``/traces`` endpoints serve; in dev mode every in-process
    agent shares it, which is what the control plane aggregates."""

    def __init__(self, maxlen: int = 2048):
        self._spans: deque[dict[str, Any]] = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self._export_path = os.environ.get("LS_TPU_TRACE_LOG")
        self._export_file = None
        self._export_broken = False
        # JSONL export is decoupled from span ends by a bounded queue and
        # one daemon writer thread: a slow/contended disk must not stall
        # the event loop per span (spans end on the gateway/engine loops),
        # and a single writer is what keeps lines from interleaving
        self._export_queue: deque[dict[str, Any]] = deque(maxlen=8192)
        self._export_wake = threading.Event()
        self._export_idle = threading.Event()
        self._export_idle.set()
        self._export_thread: threading.Thread | None = None

    def add(self, span: dict[str, Any]) -> None:
        with self._lock:
            self._spans.append(span)
            if self._export_path and not self._export_broken:
                self._export_queue.append(span)
                self._export_idle.clear()
                if self._export_thread is None:
                    self._export_thread = threading.Thread(
                        target=self._export_loop,
                        name="ls-tpu-trace-export",
                        daemon=True,
                    )
                    self._export_thread.start()
                self._export_wake.set()

    def snapshot(self) -> list[dict[str, Any]]:
        with self._lock:
            return list(self._spans)

    def spans(self, trace_id: str) -> list[dict[str, Any]]:
        """All buffered spans of one trace, oldest first."""
        return [s for s in self.snapshot() if s.get("trace_id") == trace_id]

    def summaries(self) -> list[dict[str, Any]]:
        """Per-trace rollup for the ``/traces`` index: span count, services
        touched, the root-most span name, and total wall span."""
        by_trace: dict[str, list[dict[str, Any]]] = {}
        for span in self.snapshot():
            by_trace.setdefault(span["trace_id"], []).append(span)
        out = []
        for trace_id, spans in by_trace.items():
            ids = {s["span_id"] for s in spans}
            roots = [s for s in spans if s.get("parent_id") not in ids]
            root = min(
                roots or spans, key=lambda s: s.get("start_ms", 0.0)
            )
            start = min(s.get("start_ms", 0.0) for s in spans)
            end = max(
                s.get("start_ms", 0.0) + s.get("duration_ms", 0.0)
                for s in spans
            )
            out.append(
                {
                    "trace_id": trace_id,
                    "spans": len(spans),
                    "root": root.get("name"),
                    "services": sorted({s.get("service", "") for s in spans}),
                    "start_ms": start,
                    "duration_ms": round(end - start, 3),
                    "errors": sum(1 for s in spans if s.get("error")),
                }
            )
        out.sort(key=lambda t: t["start_ms"])
        return out

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    def drain_export(self, timeout: float = 5.0) -> bool:
        """Block until every queued span reached the JSONL file (or the
        sink broke). For tests and orderly shutdown; True when drained."""
        return self._export_idle.wait(timeout)

    def _export_loop(self) -> None:
        while True:
            self._export_wake.wait()
            self._export_wake.clear()
            while True:
                with self._lock:
                    if not self._export_queue:
                        self._export_idle.set()
                        break
                    span = self._export_queue.popleft()
                # the write itself runs outside the lock: span ends only
                # contend on a queue append, never on disk
                self._write_line(span)

    def _write_line(self, span: dict[str, Any]) -> None:
        if self._export_broken:
            return
        try:
            if self._export_file is None:
                self._export_file = open(  # noqa: SIM115 — long-lived sink
                    self._export_path, "a", encoding="utf-8"
                )
            self._export_file.write(json.dumps(span) + "\n")
            self._export_file.flush()
        except OSError as e:
            # one warning, then stay silent: an unwritable trace log must
            # not turn into a per-span error storm in the serving path
            self._export_broken = True
            with self._lock:
                self._export_queue.clear()
            log.warning("trace JSONL export disabled (%s): %s",
                        self._export_path, e)


def _buffer_size() -> int:
    try:
        return max(64, int(os.environ.get("LS_TPU_TRACE_BUFFER", "2048")))
    except ValueError:
        return 2048


#: the process-global buffer the pod ``/traces`` endpoints serve
SPANS = SpanBuffer(maxlen=_buffer_size())
