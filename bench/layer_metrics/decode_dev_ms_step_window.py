"""Device time of one decode step over the WHOLE window, with the profiler
off: the decode samples' ``program_ms`` (the device's clock,
``flight.py`` ``DispatchClock``; ``lib/devclock.py``) over the ``steps``
their chunks fused. Every family's twin of ``decode_dev_ms_step``, which
counts steps from the operations of a traced run and so reads the dense
programs only. Only samples that carry ``seen_by`` are read."""

META = {"unit": "ms", "better": "lower", "layer": "jitted programs",
        "moves": "tpot_p50_ms", "source": "program_span"}


def read(obs):
    from lib import devclock

    samples = devclock.clocked(obs, "decode")
    steps = sum(s.get("steps") or 0 for s in samples)
    return sum(s["program_ms"] for s in samples) / steps if steps else None
