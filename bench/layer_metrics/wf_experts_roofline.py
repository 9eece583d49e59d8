"""The expert matmuls of a decode step, every expert of every layer held,
against their roofline: the least time for the weights of the experts a step
touches (with a full batch on 64 experts, all of them) or for the routed
pairs' operations, 8 of 64 experts a row, whichever is longer
(``lib/roofline_swa.py`` ``experts_floor`` on ``lib/roofline_wf.py``'s
shape), over the device time a step spends under ``moe_experts``. The dense
pass the program takes up to ``DENSE_ROWS_MAX`` rows spends ``experts /
experts_per_token`` = 8 times those operations (``roofline_wf.
dense_pass_flops``: at 192 rows 1.22 TFLOP a step, 6.2 ms at the MXU's peak
beside 7.7 ms to stream the experts): the floor does not count them."""

META = {"unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace"}


def read(obs):
    from lib import roofline_wf

    shape, load = roofline_wf.shape_of(obs), roofline_wf.per_step(obs)
    ms = roofline_wf.scope_ms_step(obs, ("moe_experts",))
    if shape is None or load is None or not ms or not obs.get("peaks"):
        return None
    floor = roofline_wf.experts_floor(
        shape, routed_pairs=load["routed_pairs"], batch=load["slots"],
        peaks=obs["peaks"])
    return 100.0 * floor["floor_s"] / (ms / 1e3)
