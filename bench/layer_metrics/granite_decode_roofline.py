"""A ``granitemoehybrid`` model's whole decode step against its roofline:
the least time the chip could take for one step (every held weight and the
tied embedding once, the dispatched slots' recurrent state read and written,
the live K and V rows of the attention layers; or the operations, whichever
is longer: ``lib/roofline_granite.py`` ``decode_step_floor``) over the device
time of a decode step, which is the decode-chunk programs' durations over
the steps they ran (``traced_steps``). ``hybrid_decode_roofline`` is its twin
for the ``nemotron_h`` cells, ``decode_roofline`` for the dense ones.

Live rows and running requests are means over the harness's polls of the
block manager, as ``decode_roofline`` takes them; routed pairs a step and
the bytes of state the dispatched slots hold come from the flight samples."""

META = {
    "unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace",
}


def read(obs):
    import importlib

    from lib import roofline_granite

    shape = roofline_granite.shape_of(obs)
    load = roofline_granite.per_step(obs)
    live = importlib.import_module("layer_metrics.decode_roofline").live(obs)
    if shape is None or load is None or live is None or not obs.get("peaks"):
        return None
    seconds, steps = roofline_granite.traced_steps(obs, shape)
    if not steps:
        return None
    rows, batch = live
    floor = roofline_granite.decode_step_floor(
        shape, live_rows=rows, batch=batch,
        routed_pairs=load["routed_pairs"], state_bytes=load["state_bytes"],
        peaks=obs["peaks"])
    return 100.0 * floor["floor_s"] / (seconds / steps)
