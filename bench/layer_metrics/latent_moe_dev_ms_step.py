"""Device time a latent model's decode step spends in the expert layers:
the operations of the decode-chunk programs under the scopes
``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_shared`` and
``moe_combine`` (``models/hybrid.py`` ``moe_mixer``, ``models/moe.py``: the
code the hybrid cells run) over the decode steps in the trace
(``lib/roofline_latent.py`` ``scope_ms_step``). ``moe_dev_ms_step`` and
``granite_moe_dev_ms_step`` are its twins for the hybrid cells.

A program that names no latent scope gives nothing."""

META = {
    "unit": "ms", "better": "lower", "layer": "jitted programs",
    "moves": "tpot_p50_ms", "source": "device_trace",
}
SCOPES = ("moe_",)


def read(obs):
    from lib import roofline_latent

    return roofline_latent.scope_ms_step(obs, SCOPES)
