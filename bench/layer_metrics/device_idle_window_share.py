"""Share of the WHOLE window in which the device stood with no program
queued, with the profiler off: the window's flight samples' ``gap_ms`` over
their ``gap_ms`` + ``program_ms`` (the device's clock,
``langstream_tpu/serving/flight.py`` ``DispatchClock``; ``lib/devclock.py``).
The untraced twin of ``device_idle_share``, which reads 4 traced seconds of
the window under a profiler that widens the gaps. Like the trace's gap
labels it counts the engine's microsecond key-split programs as idle; idle
inside a program it cannot see. Only samples that carry ``seen_by`` are
read: on a program without the clock's watcher this gives nothing."""

META = {"unit": "%", "better": "lower", "layer": "device",
        "moves": "out_tok_s", "source": "program_span"}


def read(obs):
    from lib import devclock

    samples = devclock.clocked(obs)
    idle = sum(s["gap_ms"] for s in samples)
    total = idle + sum(s["program_ms"] for s in samples)
    return 100.0 * idle / total if total else None
