"""The two-pool read of an EVA layer's decode step (the scope ``eva_read`` of
``models/eva.py``: the paged read of the ring from the window's first row,
the paged read of the summary pool up to the closed windows' rows, the
step's own row and the merge of the three under one softmax) against its
roofline: the least time for one step's reads over all layers (the live ring
rows and the visible summary rows once, or the heads' operations over them:
``lib/roofline_eva.py`` ``read_floor``) over the scope's device time a step.
The rows are the flight samples' ``window_rows`` and ``summary_rows``."""

META = {"unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace"}


def read(obs):
    from lib import roofline_eva

    shape, load = roofline_eva.shape_of(obs), roofline_eva.per_step(obs)
    if shape is None or load is None or not obs.get("peaks"):
        return None
    seconds = roofline_eva.scope_s_step(obs, ("eva_read",))
    if not seconds:
        return None
    floor = roofline_eva.read_floor(
        shape, window_rows=load["window_rows"],
        summary_rows=load["summary_rows"], peaks=obs["peaks"])
    return 100.0 * floor["floor_s"] / seconds
