"""The Mamba-2 state update of a ``granitemoehybrid`` model against its
roofline: the least time for one decode step's recurrences (the recurrent
state of the slots dispatched, all Mamba-2 layers, read once and written
once; ``lib/roofline_granite.py`` ``ssm_state_floor``) over the device time
a step spends under the scope ``ssm_scan``. Slots dispatched are the flight
samples' ``active_at_dispatch`` weighted by the steps each chunk fused."""

META = {
    "unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace",
}
SCOPES = ("ssm_scan",)


def read(obs):
    from lib import roofline_granite

    shape = roofline_granite.shape_of(obs)
    load = roofline_granite.per_step(obs)
    if shape is None or load is None or not obs.get("peaks"):
        return None
    step_ms = roofline_granite.scope_ms_step(obs, SCOPES)
    if not step_ms:
        return None
    floor = roofline_granite.ssm_state_floor(
        shape, slots=load["slots"], peaks=obs["peaks"])
    return 100.0 * floor["floor_s"] / (step_ms / 1e3)
