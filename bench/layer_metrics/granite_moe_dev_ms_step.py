"""Device time a ``granitemoehybrid`` decode step spends in the expert
sub-layers: the operations of the decode-chunk programs under the scopes
``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_shared`` and
``moe_combine`` (``langstream_tpu/models/hybrid.py``, ``models/moe.py``)
over the decode steps in the trace (``lib/roofline_granite.py``
``traced_steps``). ``moe_dev_ms_step`` is its twin for the ``nemotron_h``
cells.

A program that names no such scope gives nothing."""

META = {
    "unit": "ms", "better": "lower", "layer": "jitted programs",
    "moves": "tpot_p50_ms", "source": "device_trace",
}
SCOPES = ("moe_",)


def read(obs):
    from lib import roofline_granite

    return roofline_granite.scope_ms_step(obs, SCOPES)
