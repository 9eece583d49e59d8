"""Device time a latent model's decode step spends in attention: the
operations of the decode-chunk programs under the scopes ``mla_q``,
``mla_kv``, ``mla_absorb`` (the products with ``W_UK`` and ``W_UV`` that
take the cache's expansion's place), ``kv_read`` (the latent read and the
chunk's own rows) and ``attn_out`` (``langstream_tpu/models/latent.py``),
over the decode steps in the trace (``lib/roofline_latent.py``
``scope_ms_step``: the steps are the read kernel's calls inside those
programs over the layers, so a run cut by an end of the trace counts for
what was seen of it).

A program that names no such scope gives nothing."""

META = {
    "unit": "ms", "better": "lower", "layer": "jitted programs",
    "moves": "tpot_p50_ms", "source": "device_trace",
}


def read(obs):
    from lib import roofline_latent

    return roofline_latent.scope_ms_step(
        obs, roofline_latent.ATTENTION_SCOPES)
