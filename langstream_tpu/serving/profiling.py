"""Profiling and tracing hooks for the serving engine.

The TPU-native analogue of the reference's per-agent observability servlet
(``AgentInfoServlet.java`` / ``AgentRunner.java:604-624``): instead of JVM
stats, we capture device truth — ``jax.profiler`` traces (op-level timeline
viewable in TensorBoard/Perfetto) and the compiled HLO of the hot programs.

Activation (all off by default, zero overhead when unset):

- ``LS_TPU_PROFILE_DIR=/path``: the engine captures a trace of the first
  ``LS_TPU_PROFILE_CHUNKS`` (default 4) decode chunks after startup into
  ``/path``. Inspect with TensorBoard's profile plugin or Perfetto.
- ``LS_TPU_HLO_DUMP_DIR=/path``: each jitted serving program (prefill
  buckets, decode chunk variants) writes its optimized HLO text next to its
  first execution — the ground truth for "what did XLA fuse".
- Engine methods :meth:`ProfilerHooks.start_trace` / ``stop_trace`` expose
  the same capture programmatically (the pod's ``/profile`` debug endpoint
  drives these).

Also here: the decode roofline model. Decode is HBM-bandwidth bound: each
step must stream every live weight byte plus the attention-window slice of
the KV cache. ``decode_step_bytes`` computes that floor so benches can
report achieved-vs-roofline utilization instead of a bare tok/s.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
from typing import Any

log = logging.getLogger(__name__)


class ProfilerHooks:
    """Owns trace capture state for one engine instance.

    Capture state is touched from two threads: the pod's ``/profile``
    debug endpoint drives :meth:`start_trace`/:meth:`stop_trace` from the
    event loop while :meth:`on_decode_chunk` runs on the engine dispatch
    thread — so the start/stop/auto-countdown read-modify-writes sit
    behind a lock (graftcheck RACE801 polices the shape). The lock guards
    only the state transitions: the ``_tracing`` flag is flipped as a
    *reservation* and the filesystem / ``jax.profiler`` calls run outside
    it, so the event-loop thread can never stall on a lock held across
    I/O (the OBS502/OBS503 failure mode). A concurrent start+stop can
    therefore observe the reservation before the profiler actually
    started — the losing call's jax error is caught and logged, never
    raised into serving, which is this class's contract anyway."""

    def __init__(self) -> None:
        self.profile_dir = os.environ.get("LS_TPU_PROFILE_DIR")
        self.auto_chunks = int(os.environ.get("LS_TPU_PROFILE_CHUNKS", "4"))
        self.hlo_dir = os.environ.get("LS_TPU_HLO_DUMP_DIR")
        self._state_lock = threading.Lock()
        self._tracing = False
        self._auto_remaining = self.auto_chunks if self.profile_dir else 0
        self._dumped: set[str] = set()

    # -- trace capture --------------------------------------------------

    def start_trace(self, trace_dir: str | None = None) -> bool:
        """Begin a jax.profiler capture (idempotent). Returns True if a
        capture started. The profiler is process-global while hooks are
        per-engine, so a capture already running elsewhere (another engine)
        is tolerated, never raised into the serving path."""
        target = trace_dir or self.profile_dir
        if not target:
            return False
        with self._state_lock:
            if self._tracing:
                return False
            self._tracing = True  # reserve: concurrent callers back off
        import jax

        try:
            os.makedirs(target, exist_ok=True)
            jax.profiler.start_trace(target)
        except Exception as e:  # profiling must never break serving
            log.warning(
                "profiler trace start failed (already active?): %s", e
            )
            with self._state_lock:
                self._tracing = False
                self._auto_remaining = 0
            return False
        log.info("jax profiler trace started -> %s", target)
        return True

    def stop_trace(self) -> bool:
        with self._state_lock:
            if not self._tracing:
                return False
            self._tracing = False
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception as e:
            log.warning("profiler trace stop failed: %s", e)
            return False
        log.info("jax profiler trace stopped")
        return True

    def on_decode_chunk(self) -> None:
        """Called once per dispatched decode chunk: drives the env-var
        auto-capture of the first N chunks."""
        with self._state_lock:
            if self._auto_remaining <= 0:
                return
            need_start = not self._tracing
        if need_start and not self.start_trace():
            return  # start failed/disabled; _auto_remaining already zeroed
        with self._state_lock:
            if self._auto_remaining <= 0:
                return
            self._auto_remaining -= 1
            should_stop = self._auto_remaining == 0
        if should_stop:
            self.stop_trace()

    # -- HLO dumps ------------------------------------------------------

    def dump_hlo(self, name: str, jitted: Any, *args: Any, **kwargs: Any) -> str | None:
        """Write ``jitted``'s HLO for the given example args to
        ``<hlo_dir>/<name>.hlo.txt`` (once per name).

        Default dump is the (cheap) pre-optimization lowering — AOT
        ``compile()`` results don't populate the jit dispatch cache, so
        compiling here would double every program's warm-up. Set
        ``LS_TPU_HLO_OPTIMIZED=1`` to pay one extra compile per program and
        dump the post-fusion optimized HLO instead."""
        if not self.hlo_dir or name in self._dumped:
            return None
        self._dumped.add(name)
        try:
            lowered = jitted.lower(*args, **kwargs)
            if os.environ.get("LS_TPU_HLO_OPTIMIZED") == "1":
                text = lowered.compile().as_text()
            else:
                text = lowered.as_text()
        except Exception as e:  # profiling must never break serving
            log.warning("HLO dump %s failed: %s", name, e)
            return None
        os.makedirs(self.hlo_dir, exist_ok=True)
        path = os.path.join(self.hlo_dir, f"{name}.hlo.txt")
        with open(path, "w") as f:
            f.write(text)
        log.info("HLO dump: %s", path)
        return path


# ---------------------------------------------------------------------------
# roofline model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DecodeRoofline:
    weight_bytes: int          # streamed once per step (all slots share it)
    cache_bytes_per_step: int  # KV window read across all slots
    total_bytes_per_step: int
    # the device the roof belongs to, as JAX reports it; off-TPU there is
    # no roof: hbm_gbps is None and the derived fields come out None too
    device_kind: str
    hbm_gbps: float | None
    hbm_bytes: int | None = None    # allocator bytes_limit when reported

    def min_step_ms(self) -> float | None:
        if self.hbm_gbps is None:
            return None
        return self.total_bytes_per_step / (self.hbm_gbps * 1e9) * 1e3

    def utilization(self, achieved_step_ms: float) -> float | None:
        floor = self.min_step_ms()
        if floor is None:
            return None
        return floor / max(achieved_step_ms, 1e-9)


class UnknownDeviceError(RuntimeError):
    """A ``tpu`` device whose ``device_kind`` has no row in
    :data:`DEVICE_PEAKS`: an error, never a default."""


# Published peaks, keyed by ``jax.devices()[0].device_kind`` exactly as the
# runtime reports it. One row per device this repo has actually run on —
# add a row (with its source) when it meets another; a guess for an unseen
# device would put one chip's roof under another's numbers.
DEVICE_PEAKS: dict[str, dict[str, Any]] = {
    "TPU v5 lite": {
        "hbm_gbps": 819.0,
        "bf16_tflops": 197.0,
        "int8_tops": 393.0,
        "ici_gbit_s": 1600.0,
        "source": 'Google Cloud documentation, "TPU v5e" system architecture',
    },
}


def device_peaks() -> tuple[str, dict[str, Any] | None]:
    """``(device_kind, peaks row)`` of the first local device. Off-TPU the
    row is None (there is no roofline to report against a CPU); a ``tpu``
    device that is not in the table raises :class:`UnknownDeviceError`."""
    import jax

    device = jax.local_devices()[0]
    if device.platform != "tpu":
        return device.device_kind, None
    peaks = DEVICE_PEAKS.get(device.device_kind)
    if peaks is None:
        raise UnknownDeviceError(
            f"no published peaks for TPU device_kind {device.device_kind!r}; "
            f"known: {sorted(DEVICE_PEAKS)}. Add a row with its source to "
            f"langstream_tpu/serving/profiling.py DEVICE_PEAKS"
        )
    return device.device_kind, peaks


def detect_hbm_bytes() -> int | None:
    """Device memory the allocator will hand out
    (``memory_stats()["bytes_limit"]``), or None where the backend reports
    none (CPU)."""
    import jax

    stats = jax.local_devices()[0].memory_stats()
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    return None


def decode_step_bytes(
    model_config: Any,
    slots: int,
    window: int,
    quantize: str | None = None,
    kv_dtype_bytes: int = 2,
    kv_quantize: str | None = None,
) -> DecodeRoofline:
    """Bytes that MUST cross HBM for one decode step of ``slots`` slots with
    an attention window of ``window`` cache rows per slot.

    Weight traffic: every parameter once (int8 → 1 byte + per-channel f32
    scales, negligible). Cache traffic: K and V windows for every slot and
    layer. Activations are negligible at decode batch sizes.
    """
    c = model_config
    from langstream_tpu.models.llama import param_count

    n_params = param_count(c)
    wbytes = n_params * (1 if quantize == "int8" else 2)
    if kv_quantize == "int8":
        # int8 row + one f32 scale per (position, head) row
        row_bytes = c.head_dim + 4
    else:
        row_bytes = c.head_dim * kv_dtype_bytes
    cache = c.layers * slots * window * c.kv_heads * row_bytes * 2
    kind, peaks = device_peaks()
    return DecodeRoofline(
        weight_bytes=wbytes,
        cache_bytes_per_step=cache,
        total_bytes_per_step=wbytes + cache,
        device_kind=kind,
        hbm_gbps=peaks["hbm_gbps"] if peaks else None,
        hbm_bytes=detect_hbm_bytes(),
    )
