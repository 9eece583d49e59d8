"""Prometheus metrics reporter.

Parity: ``MetricsReporter`` SPI + ``PrometheusMetricsReporter``
(``langstream-runtime-impl/.../agent/metrics/PrometheusMetricsReporter.java:23``)
— counters/gauges/histograms labeled by agent, exposed over the runtime's
HTTP ``/metrics`` endpoint.

When ``prometheus_client`` is absent (minimal images), a tiny in-tree
registry records the same series and :func:`render_metrics` renders them in
the text exposition format — the endpoint always answers a well-formed
``text/plain; version=0.0.4`` body, so scraper probes don't read an empty
response as a dead target.

**Exemplars** (docs/OBSERVABILITY.md, *Incident bundles & exemplars*):
histograms registered via :meth:`PrometheusMetricsReporter.exemplar_histogram`
keep one bounded last-wins ``(trace_id, value, ts)`` slot per bucket —
the most recent *traced* observation that landed there — and
:func:`render_metrics` appends them to the matching ``_bucket`` lines in
OpenMetrics exemplar syntax (`` # {trace_id="..."} <value> <ts>``), so a
p99 bucket on the scrape names a journey id ``tools/journey.py --trace``
can open. The slot store is written with single GIL-atomic dict stores
(wait-free — observation sites sit on the engine's finish path) and
bounded by construction (one slot per declared bucket). Engines that
never observe a traced request leave every slot empty, and an empty
store leaves the scrape body **byte-identical** to the pre-exemplar
format — Prometheus' text parser never sees the comment unless an
exemplar exists.
"""

from __future__ import annotations

import re
import threading
import time
from typing import Callable

from langstream_tpu.api.agent import MetricsReporter

try:
    from prometheus_client import (
        Counter,
        Gauge,
        Histogram,
        REGISTRY,
        generate_latest,
    )

    _HAVE_PROM = True
except ImportError:  # pragma: no cover - prometheus_client is in the image
    _HAVE_PROM = False

_metric_lock = threading.Lock()
_counters: dict[str, "Counter"] = {}
_gauges: dict[str, "Gauge"] = {}
_histograms: dict[str, "Histogram"] = {}

#: seconds-scale latency buckets (sub-ms broker hops up to multi-second
#: saturated-queue waits — the range the serving TTFT decomposition spans)
LATENCY_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0,
)

#: exemplar slots: full metric name → agent label → bucket upper bound
#: (``float('inf')`` for +Inf) → ``(trace_id, value, unix ts)``. Written
#: last-wins by the observe closures (GIL-atomic dict stores, no lock —
#: the sites sit on the engine finish path); read by the renderer.
_exemplars: dict[str, dict[str, dict[float, tuple[str, float, float]]]] = {}

_BUCKET_LINE = re.compile(
    r"^(?P<name>[A-Za-z_:][A-Za-z0-9_:]*)_bucket\{(?P<labels>[^}]*)\} "
    r"(?P<value>\S+)$"
)


def _label_value(labels: str, key: str) -> str | None:
    m = re.search(re.escape(key) + r'="([^"]*)"', labels)
    return m.group(1) if m else None


def _have_exemplars() -> bool:
    return any(
        slots
        for per_agent in _exemplars.values()
        for slots in per_agent.values()
    )


def _annotate_exemplars(body: bytes) -> bytes:
    """Append OpenMetrics exemplar comments to the ``_bucket`` lines that
    have a recorded slot. With no exemplars recorded the body passes
    through BYTE-IDENTICAL — the default scrape surface is pinned."""
    if not _have_exemplars():
        return body
    out: list[str] = []
    for line in body.decode("utf-8").split("\n"):
        m = None if line.startswith("#") else _BUCKET_LINE.match(line)
        if m is not None:
            per_agent = _exemplars.get(m.group("name"))
            if per_agent is not None:
                labels = m.group("labels")
                slots = per_agent.get(_label_value(labels, "agent_id") or "")
                le = _label_value(labels, "le")
                if slots is not None and le is not None:
                    bound = float("inf") if le == "+Inf" else float(le)
                    ex = slots.get(bound)
                    if ex is not None:
                        trace_id, value, ts = ex
                        line = (
                            f'{line} # {{trace_id="{trace_id}"}} '
                            f"{value} {ts}"
                        )
        out.append(line)
    return "\n".join(out).encode("utf-8")


# ---------------------------------------------------------------------------
# stdlib fallback registry (prometheus_client absent)
# ---------------------------------------------------------------------------


class _FallbackMetric:
    """One metric family: name → {label value → state}."""

    def __init__(self, kind: str, help: str, buckets: tuple[float, ...] = ()):
        self.kind = kind
        self.help = help
        self.buckets = buckets
        self.series: dict[str, object] = {}


_fallback: dict[str, _FallbackMetric] = {}


def _fallback_counter(full: str, help: str, label) -> Callable[[int], None]:
    with _metric_lock:
        metric = _fallback.setdefault(full, _FallbackMetric("counter", help))
        metric.series.setdefault(label, 0.0)

    def _inc(n: int = 1) -> None:
        with _metric_lock:
            metric.series[label] += n  # type: ignore[operator]

    return _inc


def _fallback_gauge(full: str, help: str, label: str) -> Callable[[float], None]:
    with _metric_lock:
        metric = _fallback.setdefault(full, _FallbackMetric("gauge", help))
        metric.series.setdefault(label, 0.0)

    def _set(v: float) -> None:
        with _metric_lock:
            metric.series[label] = float(v)

    return _set


def _fallback_histogram(
    full: str, help: str, label: str, buckets: tuple[float, ...]
) -> Callable[[float], None]:
    with _metric_lock:
        metric = _fallback.setdefault(
            full, _FallbackMetric("histogram", help, buckets)
        )
        # the family's buckets win (same as the prometheus_client path,
        # which keeps the first registration): sizing a series from a
        # caller's differing tuple would desync observe()'s iteration
        metric.series.setdefault(
            label,
            {"count": 0, "sum": 0.0, "buckets": [0] * len(metric.buckets)},
        )

    def _observe(v: float) -> None:
        with _metric_lock:
            state: dict = metric.series[label]  # type: ignore[assignment]
            state["count"] += 1
            state["sum"] += float(v)
            # per-bucket (non-cumulative) counts; the renderer cumulates
            for i, le in enumerate(metric.buckets):
                if v <= le:
                    state["buckets"][i] += 1
                    break

    return _observe


def _sel(label) -> str:
    """One series' label selector: ``label`` is its agent, or for a counter
    with labels of its own ``(agent, ((name, value), ...))``."""
    if isinstance(label, tuple):
        agent, extra = label
        pairs = ([("agent_id", agent)] if agent else []) + list(extra)
        return "{" + ",".join(f'{k}="{v}"' for k, v in pairs) + "}"
    return f'{{agent_id="{label}"}}' if label else ""


def _render_fallback() -> bytes:
    lines: list[str] = []
    with _metric_lock:
        families = {name: m for name, m in _fallback.items()}
        for name in sorted(families):
            metric = families[name]
            lines.append(f"# HELP {name} {metric.help or name}")
            lines.append(f"# TYPE {name} {metric.kind}")
            for label, state in metric.series.items():
                sel = _sel(label)
                if metric.kind in ("counter", "gauge"):
                    lines.append(f"{name}{sel} {state}")
                    continue
                hist: dict = state  # type: ignore[assignment]
                cumulative = 0
                for le, n in zip(metric.buckets, hist["buckets"]):
                    cumulative += n
                    bsel = (
                        f'{{agent_id="{label}",le="{le}"}}'
                        if label
                        else f'{{le="{le}"}}'
                    )
                    lines.append(f"{name}_bucket{bsel} {cumulative}")
                isel = (
                    f'{{agent_id="{label}",le="+Inf"}}'
                    if label
                    else '{le="+Inf"}'
                )
                lines.append(f"{name}_bucket{isel} {hist['count']}")
                lines.append(f"{name}_count{sel} {hist['count']}")
                lines.append(f"{name}_sum{sel} {hist['sum']}")
    return ("\n".join(lines) + "\n").encode()


# ---------------------------------------------------------------------------
# reporter
# ---------------------------------------------------------------------------


class PrometheusMetricsReporter(MetricsReporter):
    def __init__(self, prefix: str = "langstream", agent_id: str = ""):
        self.prefix = prefix
        self.agent_id = agent_id

    def with_prefix(self, prefix: str) -> "PrometheusMetricsReporter":
        return PrometheusMetricsReporter(f"{self.prefix}_{prefix}", self.agent_id)

    def _full(self, name: str) -> str:
        return f"{self.prefix}_{name}".replace("-", "_").replace(".", "_")

    def counter(
        self, name: str, help: str = "", labels: dict[str, str] | None = None
    ) -> Callable[[int], None]:
        """``labels`` (every series of one name gives the same keys) split
        a counter by a dimension of its own beside the agent:
        ``device_busy_seconds_total{phase=...}``."""
        full = self._full(name)
        extra = tuple(sorted((labels or {}).items()))
        if not _HAVE_PROM:
            return _fallback_counter(
                full, help, (self.agent_id, extra) if extra else self.agent_id
            )
        with _metric_lock:
            if full not in _counters:
                _counters[full] = Counter(
                    full, help or full, ["agent_id", *(k for k, _ in extra)]
                )
            c = _counters[full].labels(agent_id=self.agent_id, **dict(extra))
        return lambda n=1: c.inc(n)

    def gauge(self, name: str, help: str = "") -> Callable[[float], None]:
        full = self._full(name)
        if not _HAVE_PROM:
            return _fallback_gauge(full, help, self.agent_id)
        with _metric_lock:
            if full not in _gauges:
                _gauges[full] = Gauge(full, help or full, ["agent_id"])
            g = _gauges[full].labels(agent_id=self.agent_id)
        return lambda v: g.set(v)

    def histogram(
        self,
        name: str,
        help: str = "",
        buckets: tuple[float, ...] | None = None,
    ) -> Callable[[float], None]:
        full = self._full(name)
        buckets = buckets or LATENCY_BUCKETS
        if not _HAVE_PROM:
            return _fallback_histogram(full, help, self.agent_id, buckets)
        with _metric_lock:
            if full not in _histograms:
                _histograms[full] = Histogram(
                    full, help or full, ["agent_id"], buckets=buckets
                )
            h = _histograms[full].labels(agent_id=self.agent_id)
        return lambda v: h.observe(v)

    def exemplar_histogram(
        self,
        name: str,
        help: str = "",
        buckets: tuple[float, ...] | None = None,
    ) -> Callable[..., None]:
        """A histogram whose observe callable also accepts an optional
        ``trace_id``: ``observe(v)`` behaves exactly like
        :meth:`histogram`'s (untraced traffic changes nothing), while
        ``observe(v, trace_id)`` additionally stamps the value's bucket
        slot last-wins — one bounded ``(trace_id, value, ts)`` exemplar
        per bucket, emitted by :func:`render_metrics` in OpenMetrics
        exemplar syntax. The extra work on the traced path is one tuple
        store into a pre-sized dict — wait-free."""
        full = self._full(name)
        bounds = tuple(buckets or LATENCY_BUCKETS)
        observe = self.histogram(name, help, bounds)
        with _metric_lock:
            slots = _exemplars.setdefault(full, {}).setdefault(
                self.agent_id, {}
            )

        def _observe(v: float, trace_id: str | None = None) -> None:
            observe(v)
            if trace_id:
                le = next(
                    (b for b in bounds if v <= b), float("inf")
                )
                slots[le] = (str(trace_id), float(v), time.time())

        return _observe


def render_metrics() -> bytes:
    """Text exposition of every registered series. Always non-empty and
    well-formed — the pod ``/metrics`` endpoint serves this verbatim with
    ``text/plain; version=0.0.4`` regardless of which registry backed it."""
    if not _HAVE_PROM:
        body = _render_fallback()
        body = body if body.strip() else b"# no metrics registered yet\n"
    else:
        body = generate_latest(REGISTRY)
    return _annotate_exemplars(body)
