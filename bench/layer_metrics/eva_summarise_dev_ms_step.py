"""Device milliseconds a decode step spends closing chunks (the scope
``eva_summarise`` of ``models/eva.py``: a chunk's rows read back from the
ring, the chunk's softmax and two sums, one row a layer committed to the
summary pool, for the slots whose row closes a chunk and masked for the
others), over the steps in the trace."""

META = {"unit": "ms", "better": "lower", "layer": "jitted programs",
    "moves": "tpot_p50_ms", "source": "device_trace"}


def read(obs):
    from lib import roofline_eva

    return roofline_eva.scope_ms_step(obs, ("eva_summarise",))
