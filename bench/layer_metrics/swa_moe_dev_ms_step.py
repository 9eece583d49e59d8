"""Device milliseconds a decode step spends in the expert layers of a model
of the window-and-full family (the scopes ``moe_*`` of ``models/moe.py`` and
``models/hybrid.py`` ``moe_mixer``, the names the other expert cells use),
over the steps in the trace (``lib/roofline_swa.py``)."""

META = {"unit": "ms", "better": "lower", "layer": "jitted programs",
    "moves": "tpot_p50_ms", "source": "device_trace"}


def read(obs):
    from lib import roofline_swa

    return roofline_swa.scope_ms_step(obs, ("moe_",))
