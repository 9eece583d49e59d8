"""Device time a ``granitemoehybrid`` decode step spends in the Mamba-2
mixers: the operations of the decode-chunk programs under the scopes
``ssm_in``, ``ssm_conv``, ``ssm_scan`` and ``ssm_out``
(``langstream_tpu/models/hybrid.py``) over the decode steps in the trace,
counted by the programs' scan over the model's layers
(``lib/roofline_granite.py`` ``traced_steps``). ``ssm_dev_ms_step`` is its
twin for the ``nemotron_h`` cells.

A program that names no such scope (a parent commit, another family) gives
nothing."""

META = {
    "unit": "ms", "better": "lower", "layer": "jitted programs",
    "moves": "tpot_p50_ms", "source": "device_trace",
}
SCOPES = ("ssm_",)


def read(obs):
    from lib import roofline_granite

    return roofline_granite.scope_ms_step(obs, SCOPES)
