"""Device time a decode step spends getting from the pool to the attention
output, whichever read path serves it: the operations of the decode-chunk
programs whose HLO metadata names the scope ``kv_read``
(``langstream_tpu/models/llama_paged.py``: the XLA gather of the window, its
dequantise and softmax on one path; the Pallas ``paged_read`` kernel and the
merge with the chunk's own rows on the other) over the decode steps in the
trace, counted as ``decode_dev_ms_step`` counts them.

A program that names no scopes (a parent commit) gives nothing."""

META = {
    "unit": "ms", "better": "lower", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace",
}
SCOPE = "kv_read"


def read(obs):
    from lib import hosttrace

    reduced = hosttrace.of(obs)
    if not reduced or SCOPE not in reduced["scopes"]["by_scope"]:
        return None
    steps = hosttrace.decode_steps(obs)
    if not steps:
        return None
    return 1e3 * reduced["scopes"]["by_scope"][SCOPE] / steps
