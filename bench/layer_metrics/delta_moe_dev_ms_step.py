"""Device time a ``solar_open2`` decode step spends in the expert layers:
the operations of the decode-chunk programs under the scopes ``moe_router``,
``moe_dispatch``, ``moe_experts``, ``moe_shared`` and ``moe_combine``
(``models/hybrid.py`` ``moe_mixer``, ``models/moe.py``) over the decode steps
in the trace (``lib/roofline_delta.py`` ``traced_steps``)."""

META = {
    "unit": "ms", "better": "lower", "layer": "jitted programs",
    "moves": "tpot_p50_ms", "source": "device_trace",
}
SCOPES = ("moe_",)


def read(obs):
    from lib import roofline_delta

    return roofline_delta.scope_ms_step(obs, SCOPES)
