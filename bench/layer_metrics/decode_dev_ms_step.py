"""Device time of one decode step: the device durations of the decode-chunk
programs in the trace over the steps they ran.

How many steps a run made, the trace says itself: inside a run every op of
the layer body executes once a layer a step, so the most frequent op of the
run ran ``steps x layers`` times. A run cut by the start or the end of the
trace shows, and is charged, only the steps that ran inside it."""

META = {
    "unit": "ms", "better": "lower", "layer": "jitted programs",
    "moves": "tpot_p50_ms", "source": "device_trace",
}
PROGRAM = "decode_chunk"


def decode_runs(obs):
    """[(device seconds, steps)] of the decode-chunk runs in the trace."""
    from lib import xplane

    trace = obs.get("trace")
    if not trace:
        return []
    layers = obs["llama"]["layers"]
    runs = xplane.program(trace, PROGRAM)
    return [
        (seconds, round(count / layers))
        for seconds, count in zip(runs["durations_s"], runs["op_counts"])
        if count >= layers
    ]


def read(obs):
    runs = decode_runs(obs)
    steps = sum(s for _, s in runs)
    if not steps:
        return None
    return 1e3 * sum(t for t, _ in runs) / steps
