"""The WHOLE decode step of an EVA decoder against its roofline: the least
time the chip could take for one step (every weight a step touches once, both
pools' live rows once, the rows a step commits; or its operations, whichever
is longer: ``lib/roofline_eva.py`` ``decode_floor``) over the device time of
a decode step, which is the seconds of every operation inside the decode
programs over the steps in the trace (``traced_steps``). It bounds whatever
a later change claims inside the step."""

META = {"unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace"}


def read(obs):
    from lib import roofline_eva

    shape, load = roofline_eva.shape_of(obs), roofline_eva.per_step(obs)
    if shape is None or load is None or not obs.get("peaks"):
        return None
    seconds, steps = roofline_eva.traced_steps(obs)
    if not steps or not seconds:
        return None
    floor = roofline_eva.decode_floor(
        shape, window_rows=load["window_rows"],
        summary_rows=load["summary_rows"], batch=load["slots"],
        peaks=obs["peaks"])
    return 100.0 * floor["floor_s"] / (seconds / steps)
