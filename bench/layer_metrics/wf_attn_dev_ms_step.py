"""Device milliseconds a decode step spends in the two kinds' paged reads (the
scopes ``swa_read`` and ``full_read`` of ``models/swa.py``: the read kernel
and what is traced beside it), over the steps in the trace (the read
kernel's calls over the layers: ``lib/roofline_wf.py`` ``traced_steps``)."""

META = {"unit": "ms", "better": "lower", "layer": "jitted programs",
    "moves": "tpot_p50_ms", "source": "device_trace"}


def read(obs):
    from lib import roofline_wf

    return roofline_wf.scope_ms_step(obs, ("swa_read", "full_read"))
