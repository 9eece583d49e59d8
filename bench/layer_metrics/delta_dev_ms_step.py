"""Device time a ``solar_open2`` decode step spends in the delta-rule
mixers: the operations of the decode-chunk programs under the scopes
``delta_in``, ``delta_conv``, ``delta_state`` and ``delta_out``
(``langstream_tpu/models/hybrid.py``) over the decode steps in the trace,
counted by the programs' scan over the model's layers
(``lib/roofline_delta.py`` ``traced_steps``).

A program that names no such scope (a parent commit, another family) gives
nothing."""

META = {
    "unit": "ms", "better": "lower", "layer": "jitted programs",
    "moves": "tpot_p50_ms", "source": "device_trace",
}
SCOPES = ("delta_",)


def read(obs):
    from lib import roofline_delta

    return roofline_delta.scope_ms_step(obs, SCOPES)
