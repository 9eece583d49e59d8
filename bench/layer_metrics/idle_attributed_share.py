"""Share of the device's idle time in the trace that falls under any of the
engine loop's host spans (``ls.*``, ``langstream_tpu/serving/flight.py``
``SPANS``): how much of the idle has a host cause by name. The rest ran
between spans: the consumers' coroutines, other tasks of the event loop,
the hand-over between the dispatch thread and the loop."""

META = {
    "unit": "%", "better": "higher", "layer": "device",
    "moves": "tpot_p50_ms", "source": "program_span",
}


def read(obs):
    from lib import hosttrace

    reduced = hosttrace.of(obs)
    if not reduced or not reduced["spans"] or not reduced["idle"]["idle_s"]:
        return None
    idle = reduced["idle"]
    return 100.0 * idle["attributed_s"] / idle["idle_s"]
