"""Milliseconds of device idle under the ``ls.prefill.*`` spans (packing a
prefill batch, the upload and call into the jitted prefill, the wait for the
first tokens, their emit) per traced second."""

META = {
    "unit": "ms/s", "better": "lower", "layer": "jitted programs",
    "moves": "tpot_p50_ms", "source": "program_span",
}


def read(obs):
    from lib import hosttrace

    return hosttrace.idle_under(obs, "ls.prefill.")
