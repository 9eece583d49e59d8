"""Device time of the prefill programs per decode step over the WHOLE
window, with the profiler off: the prefill samples' ``program_ms`` (the
device's clock, ``flight.py`` ``DispatchClock``; ``lib/devclock.py``) over
the decode samples' ``steps``. What a running request waits for OTHER
requests' prefills between two of its tokens: with
``decode_dev_ms_step_window`` and the gaps a step it sums, by the clock's
tiling, to the window's wall a step. The twin of
``prefill_interleave_ms_step``. Only samples that carry ``seen_by`` are
read."""

META = {"unit": "ms", "better": "lower", "layer": "admission and scheduler",
        "moves": "tpot_p50_ms", "source": "program_span"}


def read(obs):
    from lib import devclock

    steps = sum(s.get("steps") or 0 for s in devclock.clocked(obs, "decode"))
    if not steps:
        return None
    return sum(s["program_ms"] for s in devclock.clocked(obs, "prefill")) / steps
