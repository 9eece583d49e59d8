"""Device time a decode step spends in the Mamba-2 mixers: the operations of
the decode-chunk programs under the scopes ``ssm_in``, ``ssm_conv``,
``ssm_scan`` and ``ssm_out`` (``langstream_tpu/models/hybrid.py``) over the
decode steps in the trace, counted by the programs' scan over the model's
blocks (``lib/roofline_hybrid.py`` ``traced_steps``).

A program that names no such scope (a parent commit, another family) gives
nothing."""

META = {
    "unit": "ms", "better": "lower", "layer": "jitted programs",
    "moves": "tpot_p50_ms", "source": "device_trace",
}
SCOPES = ("ssm_",)


def read(obs):
    from lib import roofline_hybrid

    return roofline_hybrid.scope_ms_step(obs, SCOPES)
