"""Device milliseconds a decode step spends in the two-pool read (the scope
``eva_read`` of ``models/eva.py``: both paged reads, the step's own row and
the merge, all layers), over the steps in the trace (the read kernel's calls
over two reads a layer: ``lib/roofline_eva.py`` ``traced_steps``)."""

META = {"unit": "ms", "better": "lower", "layer": "jitted programs",
    "moves": "tpot_p50_ms", "source": "device_trace"}


def read(obs):
    from lib import roofline_eva

    return roofline_eva.scope_ms_step(obs, ("eva_read",))
