"""The gated experts' matmuls of a latent model's decode step against their
roofline: the least time for one step's (the held and the shared experts'
weights of every expert layer once, or the operations of the routed pairs
and the shared expert, whichever is longer: ``lib/roofline_latent.py``
``experts_floor``) over the device time a step spends under the scopes
``moe_experts`` and ``moe_shared``. Routed pairs and rows a step come from
the flight samples' ``routed_pairs`` and ``active_at_dispatch``."""

META = {
    "unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace",
}
SCOPES = ("moe_experts", "moe_shared")


def read(obs):
    from lib import roofline_latent

    shape = roofline_latent.shape_of(obs)
    load = roofline_latent.per_step(obs)
    if shape is None or load is None or not obs.get("peaks"):
        return None
    step_ms = roofline_latent.scope_ms_step(obs, SCOPES)
    if not step_ms:
        return None
    floor = roofline_latent.experts_floor(
        shape, routed_pairs=load["routed_pairs"], batch=load["slots"],
        peaks=obs["peaks"])
    return 100.0 * floor["floor_s"] / (step_ms / 1e3)
