"""Share of the traced window in which no operation ran on the device."""

META = {
    "unit": "%", "better": "lower", "layer": "device",
    "moves": "tpot_p50_ms", "source": "device_trace",
}


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace.get("window_s") or not trace.get("busy_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
