"""How late the device's clock stamps a completion: the error bar of
``device_idle_window_share``, ``decode_dev_ms_step_window``,
``prefill_dev_ms_step_window`` and ``prefill_window_mfu``, read in the
traced seconds, where both clocks exist. Each ``dev.watch`` or fetch-side
stamp (the end of the watcher's span, or of the dispatch thread's
``ls.*.wait``, by the dispatch's ``seq``) less the end of its own program's
run on the ``XLA Modules`` line (found by the dispatches' order), less the
two timelines' disagreement (``hosttrace.clock_skew_ns``, or what causality
allows where that is less: no completion is seen before it happened); the
95th percentile (``lib/devclock.py`` ``pair``). An observer needs the GIL to
stamp, so it is late by what another thread holds it for (the switch
interval, 5 ms, at most). Nothing on an untraced run or on a program without
the watcher."""

META = {"unit": "ms", "better": "lower", "layer": "device",
        "moves": "out_tok_s", "source": "device_trace"}


def read(obs):
    from lib import devclock, stats

    paired = devclock.traced(obs)
    return stats.stat(paired["late_ms"], "p95") if paired else None
