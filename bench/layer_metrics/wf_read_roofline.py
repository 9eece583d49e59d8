"""The paged read at 8 query heads a key-value head over a pool a layer kind
(``ops/paged_attention.py`` ``paged_attention_partial``: a full layer's walk
from row 0, a window layer's from the first row its query sees, which is row
0 for a slot shorter than the window) against its roofline, over both kinds:
the least time for one step's reads (the live rows once, ``min(length,
sliding_window)`` of a slot on a window layer and ``length`` on a full one,
or the heads' operations over them: ``lib/roofline_swa.py`` ``read_floor``)
over the kernel's device time a step, which is the seconds of the op
``paged_read.N`` in the decode programs over the steps in the trace. The
rows are the flight samples' ``live_rows`` and ``window_rows``."""

META = {"unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace"}


def read(obs):
    from lib import roofline_wf

    shape, load = roofline_wf.shape_of(obs), roofline_wf.per_step(obs)
    kernel = roofline_wf.read_kernel(obs)
    if shape is None or load is None or kernel is None or not obs.get("peaks"):
        return None
    _, steps = roofline_wf.traced_steps(obs)
    if not steps:
        return None
    floor = roofline_wf.read_floor(
        shape, full_rows=load["full_rows"], window_rows=load["window_rows"],
        peaks=obs["peaks"])
    return 100.0 * floor["floor_s"] / (kernel["total_s"] / steps)
