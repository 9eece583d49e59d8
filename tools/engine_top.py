#!/usr/bin/env python3
"""engine top: live flight-recorder console + post-mortem analyzer.

Live mode polls a pod's ``/flight`` endpoint (or the control plane's
``/api/applications/{tenant}/{name}/flight`` fan-in — any URL returning the
flight report shape works) and renders a one-screen view per engine:
occupancy bar, tok/s, a step-time sparkline, the engine watchdog's
health verdict (ok/DEGRADED/WEDGED with its stall evidence,
serving/health.py) and the SLO burn panel (per-objective fast/slow burn
rates + budget remaining, ALERT on fast burn), the device/host/stall
decomposition with the pipelined loop's overlapped-vs-exposed host split
(``overlap_ratio``), admission-stall breakdown by reason, KV-pool
utilization,
the QoS scheduler state (per-class queue depths, per-tenant throttle
counts, shed/preempt tallies plus their event tail), the incident-
capture panel (bundles captured/suppressed with their trigger kinds,
for incident-dir-configured engines — docs/OBSERVABILITY.md "Incident
bundles & exemplars"), and the discrete-event tail (recompiles, pool
growth, warmup, preemptions). Control-plane fan-ins mark timed-out pods
``UNREACHABLE`` instead of omitting them. ``--json`` emits one frame as
machine-readable JSON: per engine, every rendered panel's lines, the
raw section it rendered from, and the anomaly flags.

    python tools/engine_top.py                          # localhost:8080
    python tools/engine_top.py --url http://pod:8080/flight --interval 2
    python tools/engine_top.py --once                   # one frame, no clear
    python tools/engine_top.py --json                   # one frame, JSON

Pointing ``--url`` at the control plane's autoscaler route
(``/api/applications/{t}/{n}/autoscaler``) renders the FLEET panel
instead: per-replica occupancy/queue/health rows plus the autoscaler's
last decisions with their evidence (docs/FLEET.md).

Post-mortem mode decomposes a saved dump — either a raw ``/flight``
payload (``curl pod:8080/flight > dump.json``) or a bench record whose
``flight`` rollup rode along (BENCH_r06+) — into mean-step device/host/
stall shares and flags anomaly windows: recompile storms, KV-pool
exhaustion, unbounded queue growth, pipeline overlap collapse
(sustained ``overlap_ratio`` near 0 while occupancy is high), the
wedged-device flag (no step progress while work is queued — the r03
hang shape, read from the dump's ``health`` section), SLO objectives in
fast burn, — for saved autoscaler payloads — scale thrash (≥3
direction changes inside one cooldown window), handoff retry storms
(one request re-offered ≥3 times) and breaker flapping (one replica's
breaker opening ≥3 times in the event window — docs/RESILIENCE.md
"Distributed failure domain"), incident capture storms (≥3 bundles in
one event window, or the cooldown suppressing far more captures than it
admits), and — for stitched
request-journey payloads (``/api/applications/{t}/{n}/journey/{id}``,
tools/journey.py) — per-segment TTFT totals with a transfer-dominated
flag when the handoff cost exceeds prefill at p50 (disaggregation
costing more than it saves).

    python tools/engine_top.py --analyze dump.json
    python tools/engine_top.py --analyze BENCH_r06.json

Zero dependencies (stdlib only), plain-refresh rendering (ANSI clear) so
it works over any terminal a pod exec gives you.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

SPARK = "▁▂▃▄▅▆▇█"


def _fmt_bytes(n) -> str:
    if n is None:
        return "-"
    sign = "-" if n < 0 else ""
    n = abs(n)
    for unit in ("B", "KB", "MB", "GB"):
        if n < 1024:
            return f"{sign}{n:.1f}{unit}" if unit != "B" else f"{sign}{n:.0f}B"
        n /= 1024
    return f"{sign}{n:.1f}TB"


def _bar(frac: float | None, width: int = 24) -> str:
    frac = min(max(frac or 0.0, 0.0), 1.0)
    n = int(round(frac * width))
    return "█" * n + "·" * (width - n)


def _spark(values, width: int = 48) -> str:
    vals = [v for v in list(values)[-width:] if v is not None]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    span = (hi - lo) or 1.0
    top = len(SPARK) - 1
    return "".join(SPARK[min(top, int((v - lo) / span * top))] for v in vals)


def _fmt_ms(ms) -> str:
    if ms is None:
        return "-"
    if ms >= 10_000:
        return f"{ms / 1000:.1f}s"
    return f"{ms:.1f}ms"


def _shares(totals: dict) -> tuple[float, float, float, float]:
    """(wall_ms, device%, host%, stall%) from a totals dict."""
    device = totals.get("device_ms") or 0.0
    host = totals.get("host_ms") or 0.0
    stall = totals.get("stall_ms") or 0.0
    wall = totals.get("wall_ms") or (device + host + stall)
    denom = wall or 1.0
    return wall, 100 * device / denom, 100 * host / denom, 100 * stall / denom


def _render_device_clock(totals: dict) -> str | None:
    """The device's own clock (serving/flight.py DispatchClock): its idle
    share and its busy time by phase over the engine's life, with no
    profile, and who stamped the completions. None on a payload from
    before the clock had its watcher (its two fields were bounds)."""
    seen_by = totals.get("completions_seen_by")
    busy = totals.get("program_ms_by_phase") or {}
    idle = totals.get("gap_ms") or 0.0
    total = idle + sum(busy.values())
    if not seen_by or not total:
        return None
    phases = "  ".join(
        f"{phase} {100 * ms / total:.1f}%"
        for phase, ms in sorted(busy.items(), key=lambda kv: -kv[1])
    )
    return (
        f"device   idle {100 * idle / total:.1f}%  "
        f"{phases}  (stamped by watch {seen_by.get('watch', 0)} / "
        f"fetch {seen_by.get('fetch', 0)})"
    )


# ---------------------------------------------------------------------------
# live rendering
# ---------------------------------------------------------------------------


def render(report: list[dict]) -> str:
    lines: list[str] = []
    if not report:
        return "no live engines (has the first request arrived yet?)"
    for entry in report:
        if entry.get("unreachable"):
            # control-plane fan-in marker: the pod timed out — the most
            # important line on the screen during an incident
            lines.append(f"== pod {entry.get('pod', '?')} UNREACHABLE ==")
            lines.append("")
            continue
        if "summary" not in entry and (
            entry.get("programs") is not None
            or entry.get("memory") is not None
        ):
            # /attribution payload entry (no flight summary): render the
            # attribution panels alone
            pod = f" @ {entry['pod']}" if entry.get("pod") else ""
            lines.append(f"== engine {entry.get('model', '?')}{pod} ==")
            lines.extend(_render_memory(entry.get("memory")))
            lines.extend(_render_programs(entry.get("programs")))
            lines.append("")
            continue
        summary = entry.get("summary", {})
        totals = summary.get("totals", {})
        window = summary.get("window", {})
        samples = entry.get("samples") or []
        events = entry.get("events") or []
        dispatch = [s for s in samples if s.get("phase") != "stall"]
        slots = entry.get("slots") or (samples[-1]["slots"] if samples else 0)
        occupancy = samples[-1]["occupancy"] if samples else 0
        queue_depth = samples[-1]["queue_depth"] if samples else 0
        pod = f" @ {entry['pod']}" if entry.get("pod") else ""
        lines.append(f"== engine {entry.get('model', '?')}{pod} ==")
        lines.append(
            f"slots    [{_bar(occupancy / slots if slots else 0)}] "
            f"{occupancy}/{slots}   queue {queue_depth}   "
            f"tok/s {window.get('tok_s') if window.get('tok_s') is not None else '-'}"
        )
        lines.append(
            f"step     p50 {_fmt_ms(window.get('step_ms_p50'))}  "
            f"p95 {_fmt_ms(window.get('step_ms_p95'))}  "
            f"host-overhead p50 {_fmt_ms(window.get('host_overhead_ms_p50'))}  "
            f"device p50 {_fmt_ms(window.get('device_ms_p50'))}"
        )
        # pipelined-loop host split: exposed (device idle) vs overlapped
        # (hidden under an in-flight dispatch) — absent on old payloads
        if window.get("overlap_ratio") is not None or window.get(
            "host_overlapped_ms_p50"
        ) is not None:
            ratio = window.get("overlap_ratio")
            lines.append(
                f"host     exposed p50 "
                f"{_fmt_ms(window.get('host_exposed_ms_p50'))}  "
                f"overlapped p50 "
                f"{_fmt_ms(window.get('host_overlapped_ms_p50'))}  "
                f"overlap "
                + (f"{100 * ratio:.1f}%" if ratio is not None else "-")
            )
        device_line = _render_device_clock(totals)
        if device_line:
            lines.append(device_line)
        lines.extend(_render_health(entry.get("health")))
        lines.extend(_render_slo(entry.get("slo")))
        wall, device_pct, host_pct, stall_pct = _shares(totals)
        lines.append(
            f"decomp   device {device_pct:.1f}%  host {host_pct:.1f}%  "
            f"stall {stall_pct:.1f}%  (of {_fmt_ms(wall)} recorded wall)"
        )
        for label, by_reason in (
            ("stalls", totals.get("stall_s_by_reason")),
            ("blocked", totals.get("blocked_s_by_reason")),
        ):
            if by_reason:
                breakdown = "  ".join(
                    f"{reason} {seconds:.2f}s"
                    for reason, seconds in sorted(
                        by_reason.items(), key=lambda kv: -kv[1]
                    )
                )
                lines.append(f"{label:8s} {breakdown}")
        kv_used = window.get("kv_used_ratio_last")
        if kv_used is not None:
            lines.append(f"kv pool  [{_bar(kv_used)}] {100 * kv_used:.1f}% used")
        lines.extend(_render_scheduler(entry.get("scheduler"), events))
        lines.extend(
            _render_pool(entry.get("pool_role"), entry.get("kvtransfer"),
                         summary)
        )
        lines.extend(_render_prefix(entry.get("prefixstore"), events))
        lines.extend(_render_adapters(entry.get("adapters"), events))
        lines.extend(_render_survival(entry.get("survival"), events))
        lines.extend(_render_streaming(entry.get("streaming"), events))
        lines.extend(_render_incidents(entry.get("incidents"), events))
        lines.extend(_render_speculative(entry.get("speculative"), events))
        spec_acc = totals.get("spec_accepted") or 0
        spec_rej = totals.get("spec_rejected") or 0
        # legacy totals-based line for old payloads without the
        # speculation section — superseded by the panel above
        if (spec_acc or spec_rej) and not isinstance(
            entry.get("speculative"), dict
        ):
            drafted = spec_acc + spec_rej
            lines.append(
                f"spec     accepted {spec_acc}/{drafted} "
                f"({100 * spec_acc / drafted:.1f}%)"
            )
        if dispatch:
            lines.append(
                f"step ms  {_spark([s['wall_ms'] for s in dispatch])}"
            )
        lines.append(
            f"steps    {totals.get('steps_by_phase')}   "
            f"recompiles {totals.get('recompiles', 0)}   "
            f"samples {summary.get('recorded', 0)} "
            f"(dropped {summary.get('dropped', 0)})"
        )
        for event in events[-6:]:
            detail = {
                k: v
                for k, v in event.items()
                if k not in ("kind", "t_ms", "seq")
            }
            lines.append(f"event    {event.get('kind')} {detail}")
        lines.append("")
    return "\n".join(lines).rstrip()


def _render_pool(
    pool_role, kvtransfer: dict | None, summary: dict
) -> list[str]:
    """Disaggregated-pool panel (docs/DISAGG.md): role, transfer rates,
    and in-transit bytes. Silent for combined engines with no handoff
    activity — pre-disagg payloads render unchanged."""
    kvtransfer = kvtransfer or {}
    role = pool_role or kvtransfer.get("role") or "combined"
    transfers = (kvtransfer.get("exports") or 0) + (
        kvtransfer.get("imports") or 0
    )
    if role == "combined" and not transfers:
        return []
    span_s = (summary.get("window") or {}).get("span_s") or 0
    rate = f"{transfers / span_s:.2f}/s" if span_s else "-"
    lines = [
        f"pool     role {role.upper()}   transfers {transfers} ({rate})   "
        f"in-transit {_fmt_bytes(kvtransfer.get('in_transit_bytes') or 0)} "
        f"({kvtransfer.get('pending_exports') or 0} pending)"
    ]
    if kvtransfer.get("exports"):
        lines.append(
            f"pool     exports {kvtransfer['exports']} "
            f"({_fmt_bytes(kvtransfer.get('export_bytes') or 0)})"
        )
    if kvtransfer.get("imports") or kvtransfer.get("import_sheds"):
        lines.append(
            f"pool     imports {kvtransfer.get('imports') or 0} "
            f"({_fmt_bytes(kvtransfer.get('import_bytes') or 0)})  "
            f"sheds {kvtransfer.get('import_sheds') or 0}"
        )
    return lines


def _render_prefix(prefixstore: dict | None, events: list[dict]) -> list[str]:
    """Tiered-prefix-store panel (docs/PREFIX.md): per-tier bytes vs
    budget bars, hit ratios, and the eviction tail. Silent for engines
    without a prefix-store section — pre-tier payloads render
    unchanged."""
    if not prefixstore:
        return []
    lines: list[str] = []
    t0 = prefixstore.get("t0") or {}
    t1 = prefixstore.get("t1") or {}
    t2 = prefixstore.get("t2") or {}

    def _tier_line(name: str, section: dict, extra: str) -> str:
        used = section.get("bytes") or 0
        budget = section.get("budget_bytes")
        if budget is not None:
            frac = 1.0 if not budget else min(1.0, used / budget)
            if not used and not budget:
                frac = 0.0
            bar = f"[{_bar(frac, 16)}] {_fmt_bytes(used)}/{_fmt_bytes(budget)}"
        else:
            bar = f"{_fmt_bytes(used)} (unbudgeted)"
        return f"prefix   {name} {bar}  {extra}"

    t0_hits = t0.get("hits") or 0
    lines.append(
        _tier_line(
            "T0", t0,
            f"blocks {t0.get('blocks') or 0}  hits {t0_hits}  "
            f"reused {t0.get('tokens_reused') or 0} tok",
        )
    )
    t1_hits = t1.get("hits") or 0
    t1_misses = t1.get("misses") or 0
    t1_looked = t1_hits + t1_misses
    t1_ratio = f"{100 * t1_hits / t1_looked:.0f}%" if t1_looked else "-"
    lines.append(
        _tier_line(
            "T1", t1,
            f"entries {t1.get('entries') or 0}  hit {t1_ratio} "
            f"({t1_hits}/{t1_looked})",
        )
    )
    if t2.get("enabled"):
        lines.append(
            _tier_line(
                "T2", t2,
                f"entries {t2.get('entries') or 0}  hydrations "
                f"{prefixstore.get('hydrations') or 0}  in-transit "
                f"{_fmt_bytes(t2.get('in_transit_bytes') or 0)}",
            )
        )
    lines.append(
        f"prefix   demote {prefixstore.get('demotions_t0_t1') or 0}"
        f"→T1 {prefixstore.get('demotions_t1_t2') or 0}→T2   "
        f"promote {prefixstore.get('promotions') or 0}   evict "
        f"{prefixstore.get('evictions') or 0}   refused "
        f"{prefixstore.get('fingerprint_refusals') or 0}"
    )
    tail = [
        e for e in events
        if str(e.get("kind", "")).startswith("prefix-evict")
    ][-3:]
    for event in tail:
        lines.append(
            f"prefix   evict {event.get('tier')} {event.get('digest')} "
            f"{_fmt_bytes(event.get('bytes') or 0)} "
            f"({event.get('reason')})"
        )
    return lines


def _render_adapters(adapters: dict | None, events: list[dict]) -> list[str]:
    """Multi-LoRA adapter-store panel (docs/ADAPTERS.md): per-tier
    bytes-vs-budget bars, hit ratios, the device-resident row set, and
    the eviction tail. Silent for engines without an adapters section —
    adapter-less payloads render unchanged."""
    if not adapters:
        return []
    lines: list[str] = []
    t0 = adapters.get("t0") or {}
    t1 = adapters.get("t1") or {}
    t2 = adapters.get("t2") or {}

    def _tier_line(name: str, section: dict, extra: str) -> str:
        used = section.get("bytes") or 0
        budget = section.get("budget_bytes")
        if budget is not None:
            frac = 1.0 if not budget else min(1.0, used / budget)
            if not used and not budget:
                frac = 0.0
            bar = f"[{_bar(frac, 16)}] {_fmt_bytes(used)}/{_fmt_bytes(budget)}"
        else:
            bar = f"{_fmt_bytes(used)} (unbudgeted)"
        return f"adapter  {name} {bar}  {extra}"

    t0_hits = t0.get("hits") or 0
    t0_loads = t0.get("loads") or 0
    t0_looked = t0_hits + t0_loads
    t0_ratio = f"{100 * t0_hits / t0_looked:.0f}%" if t0_looked else "-"
    lines.append(
        _tier_line(
            "T0", t0,
            f"rows {t0.get('entries') or 0}/{t0.get('budget_entries') or 0}"
            f"  hit {t0_ratio} ({t0_hits}/{t0_looked})  evict "
            f"{t0.get('evictions') or 0} (refused "
            f"{t0.get('eviction_refusals') or 0})",
        )
    )
    t1_hits = t1.get("hits") or 0
    t1_misses = t1.get("misses") or 0
    t1_looked = t1_hits + t1_misses
    t1_ratio = f"{100 * t1_hits / t1_looked:.0f}%" if t1_looked else "-"
    lines.append(
        _tier_line(
            "T1", t1,
            f"entries {t1.get('entries') or 0}  hit {t1_ratio} "
            f"({t1_hits}/{t1_looked})",
        )
    )
    if t2.get("enabled"):
        lines.append(
            _tier_line(
                "T2", t2,
                f"entries {t2.get('entries') or 0}  hydrations "
                f"{adapters.get('hydrations') or 0}  in-transit "
                f"{_fmt_bytes(t2.get('in_transit_bytes') or 0)}",
            )
        )
    resident = t0.get("resident") or []
    pinned = t0.get("pinned") or {}
    if resident:
        shown = ", ".join(
            f"{name}({pinned[name]})" if pinned.get(name) else str(name)
            for name in resident[:6]
        )
        more = f" +{len(resident) - 6}" if len(resident) > 6 else ""
        lines.append(f"adapter  resident {shown}{more}  (pins in parens)")
    lines.append(
        f"adapter  rank {adapters.get('rank')}  installs "
        f"{adapters.get('installs') or 0}   demote "
        f"{adapters.get('demotions_t1_t2') or 0}→T2   evict "
        f"{adapters.get('evictions') or 0}   refused cold "
        f"{adapters.get('refusals') or 0}   fingerprint-refused "
        f"{adapters.get('fingerprint_refusals') or 0}"
    )
    tail = [
        e for e in events if str(e.get("kind", "")) == "adapter-evict"
    ][-3:]
    for event in tail:
        lines.append(
            f"adapter  evict {event.get('tier')} {event.get('adapter')} "
            f"{_fmt_bytes(event.get('bytes') or 0)} "
            f"({event.get('reason')})"
        )
    return lines


def _render_survival(survival: dict | None, events: list[dict]) -> list[str]:
    """Device-survival panel (docs/RESILIENCE.md): the live KV admission
    budget vs configured (an active shrink is the line an operator must
    see during an OOM storm), shrink/restore counters, crash-requeue
    journal depth, and the most recent pool-shrink's evidence."""
    if not isinstance(survival, dict):
        return []
    shrinks = survival.get("shrinks") or 0
    journal = survival.get("journal")
    budget = survival.get("budget_blocks")
    configured = survival.get("configured_blocks")
    if not shrinks and not journal and not survival.get("faults"):
        return []  # nothing survival-relevant has happened on this engine
    lines: list[str] = []
    if budget is not None and configured:
        frac = budget / configured
        withheld = survival.get("withheld_blocks") or 0
        lines.append(
            f"budget   [{_bar(frac)}] {budget}/{configured} blocks"
            + (
                f"   WITHHELD {withheld} "
                f"({_fmt_bytes(survival.get('withheld_bytes') or 0)})"
                if withheld
                else ""
            )
        )
    tail = (
        f"shrinks {shrinks}  restores {survival.get('restores') or 0}  "
        f"preempted {survival.get('shrink_preempted') or 0}"
    )
    if survival.get("recovering"):
        tail += f"  recovering (window {survival.get('recovery_s')}s)"
    if isinstance(journal, dict):
        tail += (
            f"  journal {journal.get('live', 0)} live"
            f"/{journal.get('replayed', 0)} replayed"
        )
    lines.append(f"survive  {tail}")
    last = next(
        (
            e
            for e in reversed(events)
            if e.get("kind") == "pool-shrink"
        ),
        None,
    )
    if last is not None:
        lines.append(
            f"shrink   site {last.get('site')}  withheld "
            f"{last.get('withheld_blocks')} blk  freed "
            f"{last.get('freed_blocks')} blk  preempted "
            f"{last.get('preempted')}  -> budget "
            f"{last.get('budget_blocks')}/{last.get('configured_blocks')}"
        )
    # cross-replica failure domain (docs/RESILIENCE.md "Distributed
    # failure domain"): deadline refusals/overruns and the handoff
    # chainer's re-offer/fallback ledger — rendered only once any of it
    # has happened, so a quiet engine's panel is unchanged
    deadline_sheds = survival.get("deadline_sheds") or 0
    overruns = survival.get("deadline_overruns") or 0
    retries = survival.get("handoff_retries") or 0
    fallbacks = survival.get("handoff_fallbacks") or 0
    if deadline_sheds or overruns or retries or fallbacks:
        line = (
            f"xreplica deadline sheds {deadline_sheds}  "
            f"overruns {overruns}  re-handoffs {retries}  "
            f"local fallbacks {fallbacks}"
        )
        breaker = next(
            (
                e for e in reversed(events)
                if e.get("kind") in ("breaker-open", "breaker-close")
            ),
            None,
        )
        if breaker is not None:
            line += (
                f"  breakers open {breaker.get('open_replicas', 0)}"
                f" (last {breaker.get('kind')}: {breaker.get('replica')})"
            )
        lines.append(line)
    return lines


def _render_streaming(streaming: dict | None, events: list[dict]) -> list[str]:
    """Streaming panel (docs/OBSERVABILITY.md Streaming): active stream
    count, emit/stall totals, the disconnect-cancellation ledger
    (cancelled vs reclaimed — any daylight between them is a leaked
    decode slot), and one TBT digest bar per QoS class (bar = that
    class's p99 against the slowest class, so the class burning its
    tbt budget is the longest bar on the panel). Rendered only for
    streaming-configured engines — the section is absent otherwise."""
    if not isinstance(streaming, dict):
        return []
    lines: list[str] = []
    cancelled = streaming.get("cancelled") or 0
    reclaimed = streaming.get("reclaimed") or 0
    line = (
        f"stream   active {streaming.get('active', 0)}  "
        f"emits {streaming.get('emits', 0)}  "
        f"stalls {streaming.get('stalls', 0)}  "
        f"cancelled {cancelled}/reclaimed {reclaimed}"
    )
    burn = streaming.get("tbt_burn") or []
    if burn:
        line += f"  TBT BURN {','.join(burn)}"
    lines.append(line)
    tbt = streaming.get("tbt") or {}
    digests = {
        name: d for name, d in tbt.items()
        if isinstance(d, dict) and d.get("count")
    }
    if digests:
        scale = max(d.get("p99") or 0.0 for d in digests.values()) or 1.0
        width = max(len(name) for name in digests)
        for name, d in sorted(digests.items()):
            lines.append(
                f"tbt      {name:{width}s} "
                f"[{_bar((d.get('p99') or 0.0) / scale, 16)}] "
                f"p50 {_fmt_ms((d.get('p50') or 0.0) * 1000)}  "
                f"p99 {_fmt_ms((d.get('p99') or 0.0) * 1000)}  "
                f"max {_fmt_ms((d.get('max') or 0.0) * 1000)}  "
                f"(n={d.get('count')})"
            )
    last = next(
        (e for e in reversed(events) if e.get("kind") == "stream-cancel"),
        None,
    )
    if last is not None:
        lines.append(
            f"cancel   request {last.get('request')}  delivered "
            f"{last.get('tokens_delivered')}/{last.get('tokens_generated')} "
            f"tok  wasted {last.get('tokens_wasted')}  "
            f"class {last.get('priority')}"
        )
    return lines


def _render_incidents(incidents: dict | None, events: list[dict]) -> list[str]:
    """Incident-capture panel (docs/OBSERVABILITY.md "Incident bundles &
    exemplars"): captured/written/evicted tallies, the cooldown's
    suppression count, and the most recent bundles with their trigger
    kinds — so the operator staring at a DEGRADED header knows whether
    evidence was already snapshotted and under which bundle id. Rendered
    only for incident-dir-configured engines — the section is absent
    otherwise and default payloads render unchanged."""
    if not isinstance(incidents, dict):
        return []
    suppressed = incidents.get("suppressed") or {}
    sup_total = sum(suppressed.values()) if isinstance(suppressed, dict) else 0
    lines = [
        f"incident captured {incidents.get('captured', 0)}  "
        f"written {incidents.get('written', 0)} "
        f"({incidents.get('live', 0)} live/{incidents.get('max_bundles', 0)} "
        f"cap)  evicted {incidents.get('evicted', 0)}  "
        f"suppressed {sup_total}  cooldown {incidents.get('cooldown_s', 0):g}s"
    ]
    if incidents.get("write_errors"):
        lines.append(
            f"incident !! {incidents['write_errors']} bundle write "
            f"error(s) — evidence is being lost; check incident-dir"
        )
    for bundle in (incidents.get("recent") or [])[-3:]:
        lines.append(
            f"incident {bundle.get('id')}  trigger {bundle.get('kind')}  "
            f"events {bundle.get('events', 0)}  "
            f"journeys {bundle.get('journeys', 0)}"
        )
    return lines


def _render_speculative(
    speculative: dict | None, events: list[dict]
) -> list[str]:
    """Speculation panel (docs/OBSERVABILITY.md): fused decode-tail
    posture — accept ratio, the dispatch/fetch ledger (1:1 by the one-
    packed-fetch-per-step contract, so daylight between them is a host
    fetch leak), the measured spec-vs-plain uplift with the rolling
    window fill, the auto-disable state, and the most recent
    enable/disable flip event. Rendered only for speculative-configured
    engines — the section is absent otherwise and default payloads
    render unchanged."""
    if not isinstance(speculative, dict):
        return []
    lines: list[str] = []
    acc = speculative.get("drafts_accepted") or 0
    rej = speculative.get("rejected") or 0
    drafted = acc + rej
    lines.append(
        f"spec     steps {speculative.get('steps', 0)}  accepted "
        f"{acc}/{drafted}"
        + (f" ({100 * acc / drafted:.1f}%)" if drafted else "")
        + f"  dispatch/fetch {speculative.get('dispatches', 0)}/"
        f"{speculative.get('fetches', 0)}"
    )
    uplift = speculative.get("uplift")
    lines.append(
        "spec     uplift "
        + (f"{uplift:.2f}x" if uplift is not None else "- (calibrating)")
        + ("  auto-DISABLED" if speculative.get("auto_disabled")
           else "  auto on")
        + f"  flips {speculative.get('flips', 0)}  window "
        f"{speculative.get('window_steps', 0)} spec/"
        f"{speculative.get('window_plain', 0)} plain"
    )
    last = next(
        (
            e for e in reversed(events)
            if e.get("kind") in ("spec-auto-disable", "spec-auto-enable")
        ),
        None,
    )
    if last is not None:
        detail = {
            k: v for k, v in last.items() if k not in ("kind", "t_ms", "seq")
        }
        lines.append(f"spec     last flip {last.get('kind')} {detail}")
    return lines


def render_fleet(payload: dict) -> str:
    """Fleet panel: the autoscaler status payload
    (``/api/applications/{t}/{n}/autoscaler``) — declared policy, one
    line per replica (occupancy bar, queue, health/drain posture), and
    the decision tail with its evidence. Disaggregated apps answer one
    status per pool (docs/DISAGG.md): each renders as its own fleet
    block, headed by the pool name."""
    if not payload.get("enabled", True):
        return "fleet    autoscaler not active for this application"
    if payload.get("pools"):
        blocks = []
        for pool in sorted(payload["pools"]):
            status = payload["pools"][pool]
            blocks.append(
                f"== pool {pool.upper()} ==\n{render_fleet(status)}"
            )
        return "\n".join(blocks)
    lines: list[str] = []
    spec = payload.get("spec") or {}
    lines.append(
        f"== fleet ==  replicas {len(payload.get('replicas') or [])} "
        f"(min {spec.get('min-replicas', '?')} / max "
        f"{spec.get('max-replicas', '?')})   "
        f"ups {payload.get('scale_ups', 0)}  downs "
        f"{payload.get('scale_downs', 0)}   cooldown "
        f"{payload.get('cooldown_remaining_s', 0):g}s left"
    )
    pressure = payload.get("pressure_for_s")
    idle = payload.get("idle_for_s")
    if pressure is not None:
        lines.append(
            f"fleet    scale-up pressure sustained {pressure:g}s "
            f"(window {spec.get('scale-up-window-s', '?')}s)"
        )
    if idle is not None:
        lines.append(
            f"fleet    idle {idle:g}s "
            f"(scale-down window {spec.get('scale-down-window-s', '?')}s)"
        )
    for replica in payload.get("replicas") or []:
        name = replica.get("replica", "?")
        if replica.get("unreachable"):
            lines.append(f"replica  {name:24s} UNREACHABLE")
            continue
        slots = replica.get("slots") or 0
        occ = replica.get("occupancy") or 0
        state = replica.get("state", "ok")
        badges = []
        pool = replica.get("pool") or "combined"
        if pool != "combined":
            badges.append(pool.upper())
        if state != "ok":
            badges.append(state.upper())
        if replica.get("draining"):
            badges.append("DRAINING")
        if replica.get("slo_alerting"):
            badges.append(f"SLO:{','.join(replica['slo_alerting'])}")
        lines.append(
            f"replica  {name:24s} [{_bar(occ / slots if slots else 0, 12)}] "
            f"{occ}/{slots}  queue {replica.get('queued', 0)}"
            + (f"  {' '.join(badges)}" if badges else "")
        )
    for decision in (payload.get("decisions") or [])[-6:]:
        reasons = "; ".join(decision.get("reasons") or []) or "-"
        drain = decision.get("drain")
        lines.append(
            f"scale    {decision.get('action')} "
            f"{decision.get('from')}->{decision.get('to')} "
            f"[{decision.get('outcome')}] {reasons}"
            + (f"  drain={drain}" if drain else "")
        )
    return "\n".join(lines)


def _render_health(health: dict | None) -> list[str]:
    """Watchdog panel: state (upper-cased when not ok so a wedge jumps
    off the screen), last-step age vs the wedge window, queued/in-flight
    work, warmup posture, and the degradation reasons. Absent on
    pre-health payloads."""
    if not health:
        return []
    state = health.get("state", "?")
    shown = state if state == "ok" else state.upper()
    line = (
        f"health   {shown}  last step "
        f"{health.get('last_step_age_s', 0):.1f}s ago "
        f"(window {health.get('wedge_window_s', 0):g}s)  "
        f"queued {health.get('queued', 0)}  "
        f"in-flight {health.get('occupancy', 0)}"
    )
    warmup = health.get("warmup")
    if warmup and warmup != "not-required":
        line += f"  warmup {warmup}"
    lines = [line]
    for reason in health.get("reasons") or []:
        lines.append(f"         ! {reason}")
    return lines


def _render_slo(slo: dict | None) -> list[str]:
    """SLO burn panel: per objective, the fast/slow-window burn rates
    and the remaining slow-window budget; alerting objectives are
    flagged. Absent when the app declared no slo section."""
    if not slo or not slo.get("objectives"):
        return []
    lines = []
    for name, obj in slo["objectives"].items():
        fast = obj.get("burn_rate_fast")
        slow = obj.get("burn_rate_slow")
        budget = obj.get("budget_remaining")
        lines.append(
            f"slo      {name:13s} burn "
            f"{fast if fast is not None else '-'}/"
            f"{slow if slow is not None else '-'} (fast/slow)  budget "
            + (f"{100 * budget:.1f}%" if budget is not None else "-")
            + ("  ALERT" if obj.get("alerting") else "")
        )
    return lines


def _render_scheduler(scheduler: dict | None, events: list[dict]) -> list[str]:
    """QoS lines for one engine: per-class queue depths + admitted/shed/
    preempted tallies, per-tenant throttle counts, and a dedicated tail
    of the shed/preempt/resume events (the generic event tail can be
    drowned out by recompiles/pool-grows during an incident)."""
    if not scheduler or scheduler.get("policy") != "qos":
        return []
    lines: list[str] = []
    classes = scheduler.get("classes") or {}
    parts = []
    for cls in ("interactive", "default", "batch"):
        info = classes.get(cls)
        if info is None:
            continue
        parts.append(
            f"{cls[:3]} q={info.get('depth', 0)}"
            f"/{info.get('queue_limit', '?')} adm={info.get('admitted', 0)}"
        )
    lines.append(
        f"qos      {'  '.join(parts)}  | shed {scheduler.get('shed', 0)}"
        f"  preempted {scheduler.get('preempted', 0)}"
        f"  resumed {scheduler.get('resumed', 0)}"
    )
    tenants = scheduler.get("tenants") or {}
    throttled = {
        t: c.get("throttled", 0)
        for t, c in tenants.items()
        if c.get("throttled", 0)
    }
    if throttled:
        lines.append(
            "tenants  "
            + "  ".join(
                f"{t or '<anonymous>'} throttled={n}"
                for t, n in sorted(throttled.items(), key=lambda kv: -kv[1])
            )
        )
    qos_events = [
        e for e in events if e.get("kind") in ("shed", "preempt", "resume")
    ]
    for event in qos_events[-4:]:
        detail = {
            k: v for k, v in event.items() if k not in ("kind", "t_ms", "seq")
        }
        lines.append(f"qos ev   {event.get('kind')} {detail}")
    return lines


def _render_memory(memory: dict | None) -> list[str]:
    """HBM memory-ledger panel: one bar per owner against the allocator's
    limit, plus the prefix-cache sub-owner and the
    slack line. Absent on pre-attribution payloads."""
    if not memory:
        return []
    owners = memory.get("hbm_bytes_by_owner") or {}
    limit = memory.get("limit_bytes")
    lines = [
        f"hbm      limit {_fmt_bytes(limit)}  accounted "
        f"{_fmt_bytes(memory.get('accounted_bytes'))}"
    ]
    for owner, owned in sorted(
        owners.items(), key=lambda kv: -(kv[1] or 0)
    ):
        frac = (owned or 0) / limit if limit else 0.0
        lines.append(
            f"  {owner:13s} [{_bar(frac, 16)}] {_fmt_bytes(owned)}"
        )
    prefix = memory.get("kv_pool_prefix_bytes")
    if prefix:
        lines.append(
            f"  {'^ prefix-cache':13s} {_fmt_bytes(prefix)} of the kv-pool "
            f"holds cached prefix blocks"
        )
    return lines


def _render_programs(programs: list | None, top: int = 8) -> list[str]:
    """Per-program attribution panel: expected bytes, measured p50, and
    the achieved-vs-expected ratio (the per-program roofline), heaviest
    programs first."""
    if not programs:
        return []
    lines = [
        "program                                   disp   expect   "
        "meas-p50   ach/exp"
    ]
    for program in programs[:top]:
        expected = program.get("expected") or {}
        ratio = program.get("achieved_vs_expected")
        measured = program.get("measured_ms_p50")
        lines.append(
            f"  {str(program.get('program', '?'))[:38]:38s} "
            f"{program.get('dispatches', 0):6d} "
            f"{_fmt_ms(expected.get('expected_ms')):>8s} "
            f"{_fmt_ms(measured):>10s} "
            + (f"{ratio:9.3f}" if ratio is not None else "        -")
        )
    return lines


def _degraded_programs(programs: list, min_dispatches: int = 8) -> list[str]:
    """Programs whose achieved/expected ratio degrades vs the rest of
    the dump: flagged when a program with a meaningful dispatch count
    runs below half the median ratio of its peers — the roofline gap
    has a name, not a blend."""
    rated = [
        p for p in programs
        if p.get("achieved_vs_expected") is not None
        and p.get("dispatches", 0) >= min_dispatches
    ]
    if len(rated) < 2:
        return []
    ratios = sorted(p["achieved_vs_expected"] for p in rated)
    median = ratios[len(ratios) // 2]
    if median <= 0:
        return []
    flags = []
    for program in rated:
        ratio = program["achieved_vs_expected"]
        if ratio < 0.5 * median:
            flags.append(
                f"program attribution gap: {program.get('program')} runs at "
                f"{ratio} of its expected roofline vs a {median} median "
                f"across the dump ({program.get('dispatches')} dispatches, "
                f"measured p50 {program.get('measured_ms_p50')}ms) — this "
                f"program owns a disproportionate share of the device gap; "
                f"profile it (bench/lib/hosttrace.py over a /profile "
                f"capture) before blaming the blended roofline"
            )
    return flags


# ---------------------------------------------------------------------------
# post-mortem analysis
# ---------------------------------------------------------------------------


def _collect_flight_dicts(obj, found: list[dict], label: str = "") -> None:
    """Recursively find anything flight-shaped: full report entries (have
    ``summary.totals``) or bare bench rollups (have ``totals`` with a
    device/host split)."""
    if isinstance(obj, dict):
        totals = (obj.get("summary") or {}).get("totals") or obj.get("totals")
        if isinstance(totals, dict) and "device_ms" in totals:
            found.append({"label": label or obj.get("model", ""), "src": obj})
            return
        for key, value in obj.items():
            _collect_flight_dicts(
                value, found, f"{label}.{key}" if label else str(key)
            )
    elif isinstance(obj, list):
        for i, value in enumerate(obj):
            _collect_flight_dicts(value, found, f"{label}[{i}]")


def _collect_attrib_dicts(obj, found: list[dict], label: str = "") -> None:
    """Recursively find device-attribution payloads (dicts carrying a
    ``programs`` list next to a ``memory`` ledger — the shape
    ``/attribution`` serves and ``stats()["attribution"]`` embeds)."""
    if isinstance(obj, dict):
        if isinstance(obj.get("programs"), list) and isinstance(
            obj.get("memory"), dict
        ):
            found.append({"label": label or obj.get("model", ""), "src": obj})
            return
        for key, value in obj.items():
            _collect_attrib_dicts(
                value, found, f"{label}.{key}" if label else str(key)
            )
    elif isinstance(obj, list):
        for i, value in enumerate(obj):
            _collect_attrib_dicts(value, found, f"{label}[{i}]")


def _collect_fleet_dicts(obj, found: list[dict], label: str = "") -> None:
    """Recursively find autoscaler status payloads (dicts carrying a
    ``decisions`` list + ``spec``) — the shape an operator saves with
    ``curl .../autoscaler > fleet.json``."""
    if isinstance(obj, dict):
        if isinstance(obj.get("decisions"), list) and isinstance(
            obj.get("spec"), dict
        ):
            found.append({"label": label or "fleet", "src": obj})
            return
        for key, value in obj.items():
            _collect_fleet_dicts(
                value, found, f"{label}.{key}" if label else str(key)
            )
    elif isinstance(obj, list):
        for i, value in enumerate(obj):
            _collect_fleet_dicts(value, found, f"{label}[{i}]")


def _collect_journey_dicts(obj, found: list[dict], label: str = "") -> None:
    """Recursively find stitched request-journey payloads (dicts carrying
    a ``segments`` list next to an ``events`` list — the shape the
    control plane's ``/journey/{id}`` route and tools/journey.py
    serve)."""
    if isinstance(obj, dict):
        if isinstance(obj.get("segments"), list) and isinstance(
            obj.get("events"), list
        ):
            found.append(
                {"label": label or str(obj.get("journey", "")), "src": obj}
            )
            return
        for key, value in obj.items():
            _collect_journey_dicts(
                value, found, f"{label}.{key}" if label else str(key)
            )
    elif isinstance(obj, list):
        for i, value in enumerate(obj):
            _collect_journey_dicts(value, found, f"{label}[{i}]")


def _journey_tool():
    """The sibling journey tool (tools/journey.py), loaded the way the
    multi-dump diff loads perf_diff — so the segment tables and flag
    thresholds stay single-sourced across the two CLIs."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import journey

    return journey


def _pct_ms(values: list) -> float | None:
    values = sorted(v for v in values if v is not None)
    if not values:
        return None
    return values[min(len(values) - 1, int(0.50 * len(values)))]


def _scale_thrash(decisions: list, cooldown_s: float) -> str | None:
    """≥3 scale direction changes inside one cooldown window. With the
    cooldown enforced this is impossible — so when it fires, something
    bypassed or misconfigured the gate (cooldown near zero, two scalers
    fighting over one StatefulSet, manual kubectl patches racing the
    loop), and the fleet paid a schedule+warmup / drain per flip."""
    window = cooldown_s if cooldown_s > 0 else 300.0
    scaled = sorted(
        (
            d
            for d in decisions
            if d.get("outcome") == "scaled"
            and d.get("action") in ("up", "down")
            and d.get("m_s") is not None
        ),
        key=lambda d: d["m_s"],
    )
    changes = [
        d["m_s"]
        for prev, d in zip(scaled, scaled[1:])
        if d["action"] != prev["action"]
    ]
    for i in range(len(changes) - 2):
        if changes[i + 2] - changes[i] <= window:
            return (
                f"scale thrash: >=3 direction changes within one cooldown "
                f"window ({window:g}s) — the cooldown gate is being "
                f"bypassed or is configured too small; each flip pays a "
                f"pod schedule + warmup up and a drain down"
            )
    return None


def _growth(series: list) -> tuple[float, float] | None:
    """(head mean, tail mean) of the first/last quarter when the tail
    exceeds max(2, 2*head) — the shared sustained-growth detector for
    total queue depth and the per-class series."""
    if len(series) < 8:
        return None
    q4 = max(1, len(series) // 4)
    head = sum(series[:q4]) / q4
    tail = sum(series[-q4:]) / q4
    if tail > max(2.0, 2.0 * head):
        return head, tail
    return None


def _anomalies(entry: dict) -> list[str]:
    flags: list[str] = []
    summary = entry.get("summary") or entry
    totals = summary.get("totals") or {}
    samples = entry.get("samples") or []
    events = entry.get("events") or []
    # bench rollups carry these at the top level, full reports inside
    # totals — accept both
    for key in ("stall_s_by_reason", "blocked_s_by_reason"):
        fallback = entry.get(key)
        if key not in totals and isinstance(fallback, dict):
            totals = {**totals, key: fallback}
    if "recompiles" not in totals and entry.get("recompile_count") is not None:
        totals = {**totals, "recompiles": entry["recompile_count"]}
    # recompile storm: compiles clustered in time (each is a potential
    # multi-second convoy on TPU) — needs the event tail; fall back to a
    # count heuristic when only rollups survived
    recompile_ts = sorted(
        e["t_ms"] for e in events if e.get("kind") == "recompile"
    )
    for i in range(len(recompile_ts) - 2):
        if recompile_ts[i + 2] - recompile_ts[i] <= 2000.0:
            flags.append(
                "recompile storm: >=3 compiles within 2s — check for "
                "unbounded shape variety (prompt buckets, sampler modes)"
            )
            break
    else:
        steps = sum((totals.get("steps_by_phase") or {}).values())
        recompiles = totals.get("recompiles", 0)
        if steps and recompiles > max(8, steps // 4):
            flags.append(
                f"recompile-heavy run: {recompiles} compiles over {steps} "
                f"steps"
            )
    # pool pressure shows up as engine stall OR as blocked admission
    # while decode keeps running — either way it's the same fix. Floored
    # so a single transient blip doesn't tell the operator to resize a
    # healthy pool: flag only when a material share of the recorded wall
    # was pool-blocked
    pool_s = (totals.get("stall_s_by_reason") or {}).get(
        "no-kv-blocks", 0.0
    ) + (totals.get("blocked_s_by_reason") or {}).get("no-kv-blocks", 0.0)
    wall_s = (totals.get("wall_ms") or 0.0) / 1000.0
    if pool_s > max(0.5, 0.02 * wall_s):
        flags.append(
            f"KV pool exhaustion: {pool_s:.2f}s of admission blocked on "
            f"no-kv-blocks — grow kv-pool-blocks/kv-pool-fraction or "
            f"lower max-tokens"
        )
    if samples:
        kv_hot = sum(
            1 for s in samples if (s.get("kv_used") or 0.0) > 0.95
        )
        if kv_hot > len(samples) // 4:
            flags.append(
                f"KV pool near capacity in {kv_hot}/{len(samples)} samples"
            )
        total_growth = _growth([s.get("queue_depth", 0) for s in samples])
        if total_growth is not None:
            head_q, tail_q = total_growth
            flags.append(
                f"queue growth: depth {head_q:.1f} -> {tail_q:.1f} across "
                f"the window — arrival rate exceeds service rate"
            )
        # QoS engines: sustained interactive-class growth is the signal
        # that matters even when total depth looks flat (a batch flood
        # draining can mask the latency-sensitive class backing up)
        inter_growth = _growth(
            [
                s["queue_by_class"].get("interactive", 0)
                for s in samples
                if isinstance(s.get("queue_by_class"), dict)
            ]
        )
        if inter_growth is not None:
            head_i, tail_i = inter_growth
            flags.append(
                f"interactive-class queue growth: depth {head_i:.1f} -> "
                f"{tail_i:.1f} across the window — the latency class is "
                f"backing up; raise its weight, add slots/replicas, or "
                f"shed batch harder"
            )
    collapse = _overlap_collapse(entry, summary, totals, samples)
    if collapse:
        flags.append(collapse)
    # shrink-recover thrash (docs/RESILIENCE.md): >=3 pool-shrink events
    # inside ONE recovery window — the budget oscillates (shrink, recover,
    # immediately re-shrink), meaning the pressure is structural (pool too
    # small for the workload / a leak) and the adaptation is just hiding
    # it. Uses the events' own recovery_s so a tuned window still flags.
    shrink_events = [
        e for e in events if e.get("kind") == "pool-shrink"
    ]
    if len(shrink_events) >= 3:
        window_ms = max(
            float(e.get("recovery_s") or 30.0) for e in shrink_events
        ) * 1000.0
        stamps = sorted(
            e["t_ms"] for e in shrink_events if e.get("t_ms") is not None
        )
        for i in range(len(stamps) - 2):
            if stamps[i + 2] - stamps[i] <= window_ms:
                flags.append(
                    f"shrink-recover thrash: >=3 pool-shrink events inside "
                    f"one {window_ms / 1000.0:.0f}s recovery window — the "
                    f"KV budget is oscillating; the device pressure is "
                    f"structural (grow kv-pool-blocks, lower max-tokens, "
                    f"or scale out), not transient"
                )
                break
    # adapter thrash (docs/ADAPTERS.md): >=3 evictions of ONE adapter
    # inside a single hydrate window — distinct adapters cycling through
    # the T0 rows is the LRU working; the SAME adapter bouncing means
    # every bounce re-pays a device load or a T2 hydration and the tier
    # budgets are undersized for the live adapter mix. Uses the
    # section's own hydrate_timeout_s so a tuned window still flags.
    adapter_evicts: dict = {}
    for e in events:
        if e.get("kind") == "adapter-evict" and e.get("adapter"):
            if e.get("t_ms") is not None:
                adapter_evicts.setdefault(str(e["adapter"]), []).append(
                    e["t_ms"]
                )
    if adapter_evicts:
        window_s = float(
            (entry.get("adapters") or {}).get("hydrate_timeout_s") or 30.0
        )
        for name in sorted(adapter_evicts):
            stamps = sorted(adapter_evicts[name])
            for i in range(len(stamps) - 2):
                if stamps[i + 2] - stamps[i] <= window_s * 1000.0:
                    flags.append(
                        f"adapter thrash: adapter {name!r} evicted >=3 "
                        f"times inside one {window_s:.0f}s hydrate window "
                        f"— the tier budgets are undersized for the live "
                        f"adapter mix (grow adapter-store t0-entries / "
                        f"t1-bytes, or pin the hot adapters to dedicated "
                        f"replicas via tenant adapter affinity)"
                    )
                    break
    # retry storm (docs/RESILIENCE.md "Distributed failure domain"):
    # one request re-offered >=3 times means the decode pool is not
    # taking handoffs (dead/held/refusing replicas) and the chainer is
    # burning its cap per request — the fleet is partitioned or
    # under-provisioned, and local fallbacks are about to eat the
    # prefill pool's decode capacity
    retry_by_request: dict = {}
    for e in events:
        if e.get("kind") == "handoff-retry":
            key = e.get("request") or "?"
            retry_by_request[key] = retry_by_request.get(key, 0) + 1
    stormy = {k: n for k, n in retry_by_request.items() if n >= 3}
    if stormy:
        worst = max(stormy.items(), key=lambda kv: kv[1])
        flags.append(
            f"handoff retry storm: {len(stormy)} request(s) re-offered "
            f">=3 times (worst {worst[0]}: {worst[1]} re-offers) — the "
            f"decode pool is refusing/dead; check breaker states and "
            f"pool capacity before local fallbacks saturate prefill"
        )
    # breaker flapping: >=3 opens of ONE replica in the event tail means
    # the half-open probes keep succeeding into a replica that keeps
    # failing — the failure is load-shaped (saturation), not death, and
    # the fix is capacity/holds, not exclusion
    opens_by_replica: dict = {}
    for e in events:
        if e.get("kind") == "breaker-open":
            key = e.get("replica") or "?"
            opens_by_replica[key] = opens_by_replica.get(key, 0) + 1
    flapping = {k: n for k, n in opens_by_replica.items() if n >= 3}
    if flapping:
        worst = max(flapping.items(), key=lambda kv: kv[1])
        flags.append(
            f"breaker flapping: replica {worst[0]} opened {worst[1]}x in "
            f"the event window — half-open probes keep re-admitting a "
            f"replica that keeps failing; the failure is load-shaped "
            f"(use Retry-After holds / scale the pool), not a dead pod"
        )
    # speculation enable/disable thrash (docs/OBSERVABILITY.md): >=3
    # spec-auto-* flips inside one event window means the measured
    # uplift is hovering at the 1.0 boundary — every flip re-pays a
    # calibration chunk and a cold draft window, so the engine is
    # oscillating between two equally-slow modes instead of settling.
    # Falls back to the section's cumulative flip counter when only a
    # rollup survived (no event tail).
    spec_flip_events = [
        e for e in events
        if e.get("kind") in ("spec-auto-disable", "spec-auto-enable")
    ]
    spec_section = entry.get("speculative")
    section_flips = (
        spec_section.get("flips") or 0
        if isinstance(spec_section, dict) else 0
    )
    if len(spec_flip_events) >= 3 or (
        not events and section_flips >= 3
    ):
        uplifts = [
            e.get("uplift") for e in spec_flip_events
            if e.get("uplift") is not None
        ]
        detail = (
            f" (recent uplift {', '.join(f'{u:.2f}' for u in uplifts[-3:])})"
            if uplifts else ""
        )
        flip_count = (
            len(spec_flip_events) if spec_flip_events else section_flips
        )
        flags.append(
            f"speculation thrash: {flip_count} enable/disable "
            f"flips in the event window{detail} — measured uplift is "
            f"hovering at the 1.0 boundary and every flip re-pays a "
            f"calibration chunk; pin speculation off "
            f"(speculative-drafts 0) for this workload or widen "
            f"LS_TPU_SPEC_UPLIFT_WINDOW so the estimate stops oscillating"
        )
    # stream stall storm (docs/OBSERVABILITY.md Streaming): one request
    # tripping the stall line >=3 times means its client repeatedly sat
    # past the class's TBT budget mid-stream — a convoyed decode loop or
    # a choked frame path, not a one-off hiccup; the TBT burn alert will
    # page on exactly this if it keeps up
    stalls_by_request: dict = {}
    for e in events:
        if e.get("kind") == "stream-stall":
            key = e.get("request") or "?"
            stalls_by_request[key] = stalls_by_request.get(key, 0) + 1
    stall_storm = {k: n for k, n in stalls_by_request.items() if n >= 3}
    if stall_storm:
        worst = max(stall_storm.items(), key=lambda kv: kv[1])
        flags.append(
            f"stream stall storm: {len(stall_storm)} stream(s) tripped "
            f"the stall line >=3 times (worst {worst[0]}: {worst[1]} "
            f"stalls) — inter-chunk gaps keep exceeding the class TBT "
            f"budget; check decode convoys (recompiles, KV pressure) and "
            f"the gateway frame path before the tbt burn alert pages"
        )
    # cancellation leak: every disconnect-cancel must free its decode
    # slot at the next chunk boundary — cancelled streams outnumbering
    # reclaimed slots means a cancelled request is still holding (and
    # decoding into) a slot nobody is reading
    streaming = entry.get("streaming")
    if isinstance(streaming, dict):
        cancelled = streaming.get("cancelled") or 0
        reclaimed = streaming.get("reclaimed") or 0
        if cancelled > reclaimed:
            flags.append(
                f"stream cancellation leak: {cancelled} stream(s) "
                f"cancelled but only {reclaimed} decode slot(s) "
                f"reclaimed — {cancelled - reclaimed} cancelled "
                f"request(s) still occupy slots, burning decode capacity "
                f"on tokens nobody will read"
            )
    survival = entry.get("survival")
    if isinstance(survival, dict) and survival.get("withheld_blocks"):
        flags.append(
            f"KV budget withheld: {survival['withheld_blocks']} of "
            f"{survival.get('configured_blocks')} blocks held back after "
            f"a device allocator failure — capacity is degraded until "
            f"the recovery probe restores it"
        )
    # wedged device (the r03 hang shape): the health section a /flight
    # dump carries self-diagnoses — no step progress while work was
    # queued/in flight. Flag on the recorded verdict, and re-derive from
    # the evidence too (a dump captured with a generous window still
    # shows the stalled heartbeat)
    health = entry.get("health")
    if isinstance(health, dict):
        age = health.get("last_step_age_s") or 0.0
        window = health.get("wedge_window_s") or 60.0
        pending = (health.get("queued") or 0) + (health.get("occupancy") or 0)
        if health.get("state") == "wedged" or (age > window and pending > 0):
            flags.append(
                f"wedged device: no step progress for {age:.1f}s with "
                f"{health.get('queued', 0)} queued and "
                f"{health.get('occupancy', 0)} in flight — the engine loop "
                f"is stuck in a dispatch that never returned; expect the "
                f"liveness probe to fail and k8s to reschedule the pod"
            )
        for reason in health.get("reasons") or []:
            if health.get("state") == "degraded":
                flags.append(f"degraded: {reason}")
    slo = entry.get("slo")
    if isinstance(slo, dict):
        for name in slo.get("alerting") or []:
            obj = (slo.get("objectives") or {}).get(name, {})
            flags.append(
                f"SLO fast burn on {name!r}: burn "
                f"{obj.get('burn_rate_fast')}/{obj.get('burn_rate_slow')} "
                f"(fast/slow) against target {obj.get('target')} — error "
                f"budget {obj.get('budget_remaining')} remaining"
            )
    # incident capture storm (docs/OBSERVABILITY.md "Incident bundles &
    # exemplars"): >=3 bundles in the event tail means distinct trigger
    # kinds (or dedup keys) keep breaching past each other's cooldowns —
    # the engine is failing along several axes at once, and the bounded
    # incident-dir is churning through its eviction budget on one episode
    incident_events = [e for e in events if e.get("kind") == "incident"]
    if len(incident_events) >= 3:
        by_trigger: dict = {}
        for e in incident_events:
            key = e.get("trigger") or "?"
            by_trigger[key] = by_trigger.get(key, 0) + 1
        triggers = "  ".join(
            f"{k}x{n}" for k, n in sorted(
                by_trigger.items(), key=lambda kv: -kv[1]
            )
        )
        flags.append(
            f"incident capture storm: {len(incident_events)} bundles in "
            f"the event window ({triggers}) — multiple trigger kinds are "
            f"breaching past each other's cooldowns; one episode is "
            f"churning the bounded incident-dir, read the FIRST bundle "
            f"of the window before eviction rotates it out"
        )
    incidents = entry.get("incidents")
    if isinstance(incidents, dict):
        suppressed = incidents.get("suppressed") or {}
        sup_total = (
            sum(suppressed.values()) if isinstance(suppressed, dict) else 0
        )
        captured = incidents.get("captured") or 0
        if sup_total >= max(3, 3 * captured):
            flags.append(
                f"incident cooldown absorbing a storm: {sup_total} "
                f"suppressed captures vs {captured} taken — breach "
                f"predicates are re-firing continuously inside the "
                f"cooldown window; the captured bundles bracket a "
                f"sustained episode, not isolated blips"
            )
    return flags


def _overlap_collapse(
    entry: dict, summary: dict, totals: dict, samples: list
) -> str | None:
    """Pipeline overlap collapse: a loaded engine whose host work is all
    EXPOSED (sustained ``overlap_ratio`` near 0 while occupancy is high)
    has lost the depth-2 pipeline — a penalty-sampling workload pinning
    the sequential path, ``LS_TPU_PIPELINE=0`` left on after a debug
    session, or a regression serializing fetches. Light load is exempt:
    the engine runs the sequential light-chunk regime there by design."""
    window = summary.get("window") or {}
    decode_samples = [s for s in samples if s.get("phase") == "decode"]
    if decode_samples and not any(
        "host_overlapped_ms" in s for s in decode_samples
    ):
        # pre-pipeline dump: the split was never recorded — absence is
        # not collapse (the render path guards old payloads the same way)
        return None
    if len(decode_samples) >= 8:
        # sustained, from the raw window: decode host time overwhelmingly
        # exposed while the batch is more than half full
        overlapped = sum(
            s.get("host_overlapped_ms") or 0.0 for s in decode_samples
        )
        host = sum(s.get("host_ms") or 0.0 for s in decode_samples)
        slots = max((s.get("slots") or 0) for s in decode_samples)
        occ = sum(s.get("occupancy") or 0 for s in decode_samples) / len(
            decode_samples
        )
        if (
            host + overlapped > 0
            and overlapped / (host + overlapped) < 0.05
            and slots
            and occ > slots / 2
        ):
            return (
                f"pipeline overlap collapse: {overlapped:.1f}ms of "
                f"{host + overlapped:.1f}ms decode host time overlapped "
                f"(<5%) at occupancy {occ:.1f}/{slots} — check "
                f"LS_TPU_PIPELINE/pipeline config, or whether the "
                f"workload pins the sequential (penalty/light) path"
            )
        return None
    # rollup-only dumps (bench records): overlap_ratio survives at the
    # top level, occupancy doesn't — require a material decode run
    ratio = window.get("overlap_ratio", entry.get("overlap_ratio"))
    steps = (totals.get("steps_by_phase") or {}).get("decode", 0)
    host_ms = (totals.get("host_ms") or 0.0) + (
        totals.get("host_overlapped_ms") or 0.0
    )
    if ratio is not None and ratio < 0.05 and steps >= 8 and host_ms > 50.0:
        return (
            f"pipeline overlap collapse: overlap_ratio {ratio} over "
            f"{steps} decode steps ({host_ms:.0f}ms host) — check "
            f"LS_TPU_PIPELINE/pipeline config, or whether the workload "
            f"pins the sequential (penalty/light) path"
        )
    return None


def analyze(dump) -> str:
    """Decompose a flight dump (raw /flight payload, control-plane fan-in,
    or a bench record carrying the ``flight`` rollup) into per-engine mean-
    step device/host/stall shares plus anomaly flags."""
    found: list[dict] = []
    _collect_flight_dicts(dump, found)
    fleet_found: list[dict] = []
    _collect_fleet_dicts(dump, fleet_found)
    attrib_found: list[dict] = []
    _collect_attrib_dicts(dump, attrib_found)
    journey_found: list[dict] = []
    _collect_journey_dicts(dump, journey_found)
    if not found and not fleet_found and not attrib_found and not journey_found:
        raise ValueError(
            "no flight data found in the dump (expected a /flight payload, "
            "a bench record with a 'flight' rollup, an /attribution "
            "payload, an autoscaler status payload, or a stitched "
            "/journey payload)"
        )
    lines: list[str] = []
    for item in fleet_found:
        payload = item["src"]
        decisions = payload.get("decisions") or []
        spec = payload.get("spec") or {}
        lines.append(f"== fleet {item['label']} ==")
        lines.append(
            f"replicas {len(payload.get('replicas') or [])}  decisions "
            f"{len(decisions)}  ups {payload.get('scale_ups', 0)}  downs "
            f"{payload.get('scale_downs', 0)}"
        )
        thrash = _scale_thrash(
            decisions, float(spec.get("cooldown-s", 0) or 0)
        )
        if thrash:
            lines.append(f"  !! {thrash}")
        else:
            lines.append("  no scale anomalies flagged")
        lines.append("")
    for item in found:
        entry = item["src"]
        summary = entry.get("summary") or entry
        totals = summary.get("totals") or {}
        label = entry.get("model") or item["label"] or "engine"
        pod = f" @ {entry['pod']}" if entry.get("pod") else ""
        wall, device_pct, host_pct, stall_pct = _shares(totals)
        steps = sum((totals.get("steps_by_phase") or {}).values())
        # mean step excludes idle/stall gaps: a mostly-idle deploy's hour
        # of queue-empty waits must not inflate its 40 ms decode steps
        busy_ms = wall - (totals.get("stall_ms") or 0.0)
        mean_step = busy_ms / steps if steps else 0.0
        lines.append(f"== {label}{pod} ==")
        lines.append(
            f"recorded wall {_fmt_ms(wall)} over {steps} dispatched steps "
            f"(mean step {_fmt_ms(mean_step)})"
        )
        lines.append(
            f"  device {device_pct:5.1f}%  "
            f"({_fmt_ms(totals.get('device_ms'))})"
        )
        lines.append(
            f"  host   {host_pct:5.1f}%  ({_fmt_ms(totals.get('host_ms'))})"
        )
        if totals.get("host_overlapped_ms"):
            # inside the device share, reported separately (never
            # double-counted): host work hidden under device compute
            lines.append(
                f"  ^ overlapped host "
                f"{_fmt_ms(totals.get('host_overlapped_ms'))} rode inside "
                f"the device share"
            )
        lines.append(
            f"  stall  {stall_pct:5.1f}%  ({_fmt_ms(totals.get('stall_ms'))})"
        )
        for label, by_reason in (
            ("stall", totals.get("stall_s_by_reason")
                or entry.get("stall_s_by_reason")),
            ("blocked", totals.get("blocked_s_by_reason")
                or entry.get("blocked_s_by_reason")),
        ):
            for reason, seconds in sorted(
                (by_reason or {}).items(), key=lambda kv: -kv[1]
            ):
                lines.append(f"    {label}[{reason}] {seconds:.2f}s")
        if totals.get("tokens"):
            lines.append(f"  tokens {totals['tokens']}")
        rollup_keys = {
            k: entry.get(k)
            for k in (
                "host_overhead_ms_p50",
                "queue_depth_p95",
                "recompile_count",
            )
            if entry.get(k) is not None
        }
        if rollup_keys:
            lines.append(f"  rollup {rollup_keys}")
        scheduler = entry.get("scheduler")
        if scheduler and scheduler.get("policy") == "qos":
            lines.append(
                f"  qos    shed {scheduler.get('shed', 0)}  preempted "
                f"{scheduler.get('preempted', 0)}  resumed "
                f"{scheduler.get('resumed', 0)}"
            )
        streaming = entry.get("streaming")
        if isinstance(streaming, dict):
            for line in _render_streaming(
                streaming, entry.get("events") or []
            ):
                lines.append(f"  {line}")
        speculative = entry.get("speculative")
        if isinstance(speculative, dict):
            for line in _render_speculative(
                speculative, entry.get("events") or []
            ):
                lines.append(f"  {line}")
        flags = _anomalies(entry)
        for flag in flags:
            lines.append(f"  !! {flag}")
        if not flags:
            lines.append("  no anomaly windows flagged")
        lines.append("")
    for item in attrib_found:
        entry = item["src"]
        label = entry.get("model") or item["label"] or "engine"
        pod = f" @ {entry['pod']}" if entry.get("pod") else ""
        lines.append(f"== attribution {label}{pod} ==")
        lines.extend(_render_memory(entry.get("memory")))
        lines.extend(_render_programs(entry.get("programs")))
        memory = entry.get("memory") or {}
        slack = memory.get("slack_bytes")
        limit = memory.get("limit_bytes")
        flagged = False
        if slack is not None and slack < 0:
            flagged = True
            lines.append(
                f"  !! memory ledger overcommitted: accounted owners "
                f"exceed the detected limit by {_fmt_bytes(-slack)} — the "
                f"capacity table or the accounting is wrong; expect "
                f"RESOURCE_EXHAUSTED"
            )
        for flag in _degraded_programs(entry.get("programs") or []):
            flagged = True
            lines.append(f"  !! {flag}")
        if not flagged:
            lines.append("  no attribution anomalies flagged")
        lines.append("")
    if journey_found:
        jt = _journey_tool()
        journeys = [item["src"] for item in journey_found]
        handoff_p50s, prefill_p50s = [], []
        for item in journey_found:
            journey = item["src"]
            totals = jt.by_segment(journey)
            handoff = sum(
                totals.get(s, 0.0) for s in jt.HANDOFF_SEGMENTS
            )
            if handoff:
                handoff_p50s.append(handoff)
            if totals.get("prefill"):
                prefill_p50s.append(totals["prefill"])
            label = journey.get("journey") or item["label"] or "journey"
            lines.append(f"== journey {label} ==")
            lines.append(
                f"total {_fmt_ms(journey.get('total_ms'))} over "
                f"{len(journey.get('events') or [])} events"
            )
            for name, ms in sorted(
                totals.items(), key=lambda kv: -kv[1]
            ):
                lines.append(f"  {name:18s} {_fmt_ms(ms)}")
            flags = jt.journey_flags(journey)
            for flag in flags:
                lines.append(f"  !! {flag}")
            if not flags:
                lines.append("  no journey anomalies flagged")
            lines.append("")
        # the aggregate view: transfer-dominated TTFT at p50 across the
        # dump's journeys (one slow handoff is noise; the p50 crossing
        # prefill means disaggregation costs more than it saves)
        handoff_p50 = _pct_ms(handoff_p50s)
        prefill_p50 = _pct_ms(prefill_p50s)
        if (
            len(journeys) > 1
            and handoff_p50 is not None
            and prefill_p50 is not None
            and handoff_p50 > prefill_p50
        ):
            lines.append(
                f"!! transfer-dominated TTFT at p50 across "
                f"{len(journeys)} journeys: handoff "
                f"{_fmt_ms(handoff_p50)} > prefill {_fmt_ms(prefill_p50)} "
                f"— the disaggregated split is costing more than it "
                f"saves; co-locate, batch the transfers, or move to a "
                f"device-to-device path (docs/DISAGG.md)"
            )
            lines.append("")
    return "\n".join(lines).rstrip()


def render_json(report: list[dict]) -> list[dict]:
    """Machine-readable mirror of :func:`render`: one object per engine
    carrying every rendered panel under its name, as the exact lines the
    console prints plus the raw section the panel was rendered from — so
    a script (or a paging runbook) can pull one panel without scraping
    an ANSI frame, and the snapshot test pins the panel inventory.
    Panels that would be silent on the console are omitted here too."""
    out: list[dict] = []
    for entry in report:
        if entry.get("unreachable"):
            out.append({"pod": entry.get("pod"), "unreachable": True})
            continue
        events = entry.get("events") or []
        summary = entry.get("summary") or {}
        sections = {
            "health": entry.get("health"),
            "slo": entry.get("slo"),
            "scheduler": entry.get("scheduler"),
            "pool": entry.get("kvtransfer"),
            "prefix": entry.get("prefixstore"),
            "adapters": entry.get("adapters"),
            "survival": entry.get("survival"),
            "streaming": entry.get("streaming"),
            "incidents": entry.get("incidents"),
            "speculative": entry.get("speculative"),
            "memory": entry.get("memory"),
            "programs": entry.get("programs"),
        }
        rendered = {
            "health": _render_health(sections["health"]),
            "slo": _render_slo(sections["slo"]),
            "scheduler": _render_scheduler(sections["scheduler"], events),
            "pool": _render_pool(
                entry.get("pool_role"), sections["pool"], summary
            ),
            "prefix": _render_prefix(sections["prefix"], events),
            "adapters": _render_adapters(sections["adapters"], events),
            "survival": _render_survival(sections["survival"], events),
            "streaming": _render_streaming(sections["streaming"], events),
            "incidents": _render_incidents(sections["incidents"], events),
            "speculative": _render_speculative(
                sections["speculative"], events
            ),
            "memory": _render_memory(sections["memory"]),
            "programs": _render_programs(sections["programs"]),
        }
        out.append(
            {
                "model": entry.get("model"),
                "pod": entry.get("pod"),
                "panels": {
                    name: {"lines": lines, "section": sections[name]}
                    for name, lines in rendered.items()
                    if lines
                },
                "anomalies": _anomalies(entry),
            }
        )
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def _fetch(url: str, timeout: float = 5.0):
    """The /flight report list — or the autoscaler status dict when the
    URL points at the control plane's /autoscaler route (main() renders
    the fleet panel for dict payloads)."""
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        payload = json.loads(resp.read())
    if isinstance(payload, (list, dict)):
        return payload
    return []


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        description="live engine flight-recorder console / dump analyzer"
    )
    parser.add_argument(
        "--url",
        default="http://127.0.0.1:8080/flight",
        help="pod /flight endpoint (or control-plane flight fan-in URL)",
    )
    parser.add_argument(
        "--interval", type=float, default=2.0, help="poll interval seconds"
    )
    parser.add_argument(
        "--once", action="store_true", help="print one frame and exit"
    )
    parser.add_argument(
        "--json",
        action="store_true",
        help="print one frame as machine-readable JSON (per engine, every "
        "rendered panel's lines + its raw section + anomaly flags) and "
        "exit",
    )
    parser.add_argument(
        "--analyze",
        metavar="DUMP_JSON",
        nargs="+",
        help="post-mortem: decompose a saved /flight payload or bench "
        "record; TWO OR MORE dumps run the cross-run perf diff "
        "(tools/perf_diff.py) on top, oldest first",
    )
    args = parser.parse_args(argv)

    if args.json:
        try:
            payload = _fetch(args.url)
        except (OSError, ValueError) as e:
            print(f"fetch {args.url} failed: {e}", file=sys.stderr)
            return 2
        if isinstance(payload, dict):
            # autoscaler route: the fleet frame's lines, still structured
            print(json.dumps(
                {"fleet": render_fleet(payload).splitlines()}, indent=2
            ))
        else:
            print(json.dumps(render_json(payload), indent=2))
        return 0

    if args.analyze:
        dumps: list[tuple[str, dict]] = []
        try:
            for path in args.analyze:
                with open(path) as f:
                    dump = json.load(f)
                dumps.append((path, dump))
                if len(args.analyze) > 1:
                    print(f"---- {path} ----")
                print(analyze(dump))
        except (OSError, ValueError) as e:
            print(f"analyze failed: {e}", file=sys.stderr)
            return 2
        if len(dumps) > 1:
            # cross-run regression sentry: same diff perf_diff runs,
            # loaded from the sibling tool so the noise bands and
            # direction table stay single-sourced; the already-parsed
            # payloads are handed over, never re-read from disk
            sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
            import perf_diff

            print()
            results, any_regression = perf_diff.diff_payloads(dumps)
            for base_path, new_path, result in results:
                print(perf_diff.render(
                    base_path, new_path, result, perf_diff.DEFAULT_THRESHOLD
                ))
            return 1 if any_regression else 0
        return 0

    try:
        while True:
            try:
                payload = _fetch(args.url)
                frame = (
                    render_fleet(payload)
                    if isinstance(payload, dict)
                    else render(payload)
                )
            except (OSError, ValueError) as e:
                frame = f"fetch {args.url} failed: {e}"
            if args.once:
                print(frame)
                return 0
            # plain-refresh: clear + home, then the frame (works over any
            # pod-exec terminal; no curses dependency)
            sys.stdout.write("\x1b[2J\x1b[H" + frame + "\n")
            sys.stdout.flush()
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    raise SystemExit(main())
