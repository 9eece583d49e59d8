"""The delta-rule state update of a ``solar_open2`` model against its
roofline: the least time for one decode step's recurrences (the delta-rule
state of the slots dispatched, all delta-rule layers, read once and written
once; ``lib/roofline_delta.py`` ``delta_state_floor``) over the device time a
step spends under the scope ``delta_state`` (``ops/delta_state.py``'s
kernel and the small products around it). Slots dispatched are the flight
samples' ``active_at_dispatch`` weighted by the steps each chunk fused; the
kernel reads and writes every slot of the engine, running or not, so the
share falls with the occupancy."""

META = {
    "unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace",
}
SCOPES = ("delta_state",)


def read(obs):
    from lib import roofline_delta

    shape = roofline_delta.shape_of(obs)
    load = roofline_delta.per_step(obs)
    if shape is None or load is None or not obs.get("peaks"):
        return None
    step_ms = roofline_delta.scope_ms_step(obs, SCOPES)
    if not step_ms:
        return None
    floor = roofline_delta.delta_state_floor(
        shape, slots=load["slots"], peaks=obs["peaks"])
    return 100.0 * floor["floor_s"] / (step_ms / 1e3)
