"""Milliseconds of device idle under the ``ls.decode.*`` spans (preparing
and dispatching a decode chunk, the wait for its tokens, processing and
emitting them) per traced second."""

META = {
    "unit": "ms/s", "better": "lower", "layer": "jitted programs",
    "moves": "tpot_p50_ms", "source": "program_span",
}


def read(obs):
    from lib import hosttrace

    return hosttrace.idle_under(obs, "ls.decode.")
