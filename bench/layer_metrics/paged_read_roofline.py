"""The Pallas paged-attention read against its roofline: the least time for
one call (the live K and V rows of one layer once, 4.H.D operations a row)
over the kernel's mean device time a call.

The kernel is the custom call inside the decode-chunk program: its trace
event has no stable name yet (``closed_call.N``; stable names are the tracing
issue's), so it is found as the op of that program whose name says
closed_call, custom-call or paged. A posture that reads the pool through XLA
has no such op, and this reader returns nothing."""

META = {
    "unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace",
}
KERNEL = r"(?i)closed_call|custom-call|custom_call|paged"


def read(obs):
    import importlib

    from lib import roofline, xplane

    trace = obs.get("trace")
    if not trace or obs.get("paged_read_kernel") != "pallas":
        return None
    kernel = xplane.ops_in(trace, "decode_chunk", KERNEL)
    load = importlib.import_module("layer_metrics.decode_roofline").live(obs)
    if not kernel["calls"] or load is None:
        return None
    rows, _ = load
    floor = roofline.paged_read_floor(obs["shape"], live_rows=rows,
                                      peaks=obs["peaks"])
    return 100.0 * floor["floor_s"] / (kernel["total_s"] / kernel["calls"])
