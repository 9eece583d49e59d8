"""A ``solar_open2`` model's whole decode step against its roofline: the
least time the chip could take for one step (every held weight once, the
dispatched slots' recurrent state read and written, the live K and V rows of
the attention layer; or the operations, whichever is longer:
``lib/roofline_delta.py`` ``decode_step_floor``) over the device time of a
decode step, which is the decode-chunk programs' durations over the steps
they ran (``traced_steps``).

Live rows and running requests are means over the harness's polls of the
block manager, as ``decode_roofline`` takes them; routed pairs a step and
the bytes of state the dispatched slots hold come from the flight samples."""

META = {
    "unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace",
}


def read(obs):
    import importlib

    from lib import roofline_delta

    shape = roofline_delta.shape_of(obs)
    load = roofline_delta.per_step(obs)
    live = importlib.import_module("layer_metrics.decode_roofline").live(obs)
    if shape is None or load is None or live is None or not obs.get("peaks"):
        return None
    seconds, steps = roofline_delta.traced_steps(obs, shape)
    if not steps:
        return None
    rows, batch = live
    floor = roofline_delta.decode_step_floor(
        shape, live_rows=rows, batch=batch,
        routed_pairs=load["routed_pairs"], state_bytes=load["state_bytes"],
        peaks=obs["peaks"])
    return 100.0 * floor["floor_s"] / (seconds / steps)
