"""The WHOLE decode step of a model of the window-and-full family against its
roofline: the least time the chip could take for one step (every held weight
once, the live K and V rows of both kinds once; or the operations, whichever
is longer: ``lib/roofline_swa.py`` ``decode_step_floor``) over the device
time of a decode step, which is the seconds of every operation inside the
decode programs over the steps in the trace (``traced_steps``). It bounds
whatever a later change claims inside the step."""

META = {"unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace"}


def read(obs):
    from lib import roofline_swa

    shape, load = roofline_swa.shape_of(obs), roofline_swa.per_step(obs)
    if shape is None or load is None or not obs.get("peaks"):
        return None
    seconds, steps = roofline_swa.traced_steps(obs)
    if not steps or not seconds:
        return None
    floor = roofline_swa.decode_step_floor(
        shape, full_rows=load["full_rows"], window_rows=load["window_rows"],
        batch=load["slots"], routed_pairs=load["routed_pairs"],
        peaks=obs["peaks"])
    return 100.0 * floor["floor_s"] / (seconds / steps)
