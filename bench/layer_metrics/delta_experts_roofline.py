"""A ``solar_open2`` model's expert matmuls against their roofline: the
least time for one decode step's held and shared experts in every layer
(their weights once, or the operations of the routed pairs and the shared
expert's rows: ``lib/roofline_delta.py`` ``experts_floor``) over the device
time a step spends under the scopes ``moe_experts`` and ``moe_shared``.
Routed pairs a step come from the flight samples."""

META = {
    "unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace",
}
SCOPES = ("moe_experts", "moe_shared")


def read(obs):
    from lib import roofline_delta

    shape = roofline_delta.shape_of(obs)
    load = roofline_delta.per_step(obs)
    if shape is None or load is None or not obs.get("peaks"):
        return None
    step_ms = roofline_delta.scope_ms_step(obs, SCOPES)
    if not step_ms:
        return None
    floor = roofline_delta.experts_floor(
        shape, routed_pairs=load["routed_pairs"], batch=load["slots"],
        peaks=obs["peaks"])
    return 100.0 * floor["floor_s"] / (step_ms / 1e3)
