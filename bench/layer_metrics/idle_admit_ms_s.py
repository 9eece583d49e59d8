"""Milliseconds of device idle under ``ls.admit`` (the scheduler pop, QoS,
block reservation and prefix lookup of ``serving/engine.py`` ``_admit``) per
traced second."""

META = {
    "unit": "ms/s", "better": "lower", "layer": "admission and scheduler",
    "moves": "out_tok_s", "source": "program_span",
}


def read(obs):
    from lib import hosttrace

    return hosttrace.idle_under(obs, "ls.admit")
