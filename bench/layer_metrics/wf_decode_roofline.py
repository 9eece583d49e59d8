"""The WHOLE decode step of the window-and-full family's plain member (no
gate, no post norm, no dense layer, no shared expert, every expert held)
against its roofline: the least time the chip could take for one step (every
held weight a step touches once, the live K and V rows of both kinds once; or
the operations of the pass the program takes, the dense routed pass's where
the batch takes it, whichever is longer: ``lib/roofline_wf.py``
``decode_floor``) over the device time of a decode step, which is the
seconds of every operation inside the decode programs over the steps in the
trace (``traced_steps``). It bounds whatever a later change claims inside
the step."""

META = {"unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace"}


def read(obs):
    from lib import roofline_wf

    shape, load = roofline_wf.shape_of(obs), roofline_wf.per_step(obs)
    if shape is None or load is None or not obs.get("peaks"):
        return None
    seconds, steps = roofline_wf.traced_steps(obs)
    if not steps or not seconds:
        return None
    floor = roofline_wf.decode_floor(
        shape, full_rows=load["full_rows"], window_rows=load["window_rows"],
        batch=load["slots"], routed_pairs=load["routed_pairs"],
        rows=int(obs["serving"]["slots"]), peaks=obs["peaks"])
    return 100.0 * floor["floor_s"] / (seconds / steps)
