"""Device milliseconds a decode step spends in the expert layers (the scopes
``moe_*`` of ``models/moe.py`` and ``models/hybrid.py`` ``moe_mixer``, the
names the other expert cells use; this member traces no ``moe_shared``),
over the steps in the trace (``lib/roofline_wf.py``)."""

META = {"unit": "ms", "better": "lower", "layer": "jitted programs",
    "moves": "tpot_p50_ms", "source": "device_trace"}


def read(obs):
    from lib import roofline_wf

    return roofline_wf.scope_ms_step(obs, ("moe_",))
