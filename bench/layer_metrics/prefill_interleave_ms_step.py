"""Device time of everything that is not a decode chunk, per decode step:
the durations of every program run in the trace other than the decode-chunk
programs (prefill, prefill-continue, ``threefry_split``, ``unstack``) over
the decode steps in the trace. The missing term between
``decode_dev_ms_step`` and ``tpot_p50_ms``: a running request's next token
waits while these hold the device. Reads the program line the reduction
already holds, so a parent commit reports it too."""

META = {
    "unit": "ms", "better": "lower", "layer": "admission and scheduler",
    "moves": "tpot_p50_ms", "source": "device_trace",
}
DECODE = "decode_chunk"


def read(obs):
    from lib import hosttrace

    trace = obs.get("trace")
    if not trace:
        return None
    steps = hosttrace.decode_steps(obs)
    if not steps:
        return None
    other = sum(p["total_s"] for plane in trace["planes"]
                for name, p in plane["programs"].items() if DECODE not in name)
    return 1e3 * other / steps
