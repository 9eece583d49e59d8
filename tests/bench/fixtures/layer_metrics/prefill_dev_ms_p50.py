"""Device duration of one prefill program in the trace, median over runs."""

META = {
    "unit": "ms", "better": "lower", "layer": "jitted programs",
    "moves": "ttft_p50_ms", "source": "device_trace",
}


def read(obs):
    from lib import stats, xplane

    trace = obs.get("trace")
    if not trace:
        return None
    runs = xplane.program(trace, "prefill")
    p50 = stats.percentile(runs["durations_s"], 50)
    return None if p50 is None else 1e3 * p50
