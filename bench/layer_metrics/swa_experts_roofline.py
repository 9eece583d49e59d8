"""The expert matmuls of a decode step against their roofline: the least time
for the weights of the experts a step touches (the dense pass streams every
held expert and the shared one of every expert layer once) or for the routed
pairs' operations, whichever is longer (``lib/roofline_swa.py``
``experts_floor``), over the device time a step spends under ``moe_experts``
and ``moe_shared``."""

META = {"unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace"}


def read(obs):
    from lib import roofline_swa

    shape, load = roofline_swa.shape_of(obs), roofline_swa.per_step(obs)
    ms = roofline_swa.scope_ms_step(obs, ("moe_experts", "moe_shared"))
    if shape is None or load is None or not ms or not obs.get("peaks"):
        return None
    floor = roofline_swa.experts_floor(
        shape, routed_pairs=load["routed_pairs"], batch=load["slots"],
        peaks=obs["peaks"])
    return 100.0 * floor["floor_s"] / (ms / 1e3)
