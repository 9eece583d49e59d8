"""The chunked delta rule of a ``solar_open2`` model's prefill
(``models/hybrid.py`` ``delta_chunked``, scope ``delta_chunk``) against the
chip's peak: its operations for the TRUE tokens of the prefill runs that lie
whole in the trace (``lib/roofline_delta.py`` ``chunk_flops``: the chunk's
two causal products, the triangular system, the products with the state)
over those runs' device time under the scope, which is the runs' seconds
(each paired with the flight sample of its own dispatch) times the share of
the prefill programs' device time the trace charges to ``delta_chunk``. The
relative decays formed pair by pair inside a sub-block are vector work the
count leaves out, so they count against the share."""

META = {
    "unit": "%", "better": "higher", "layer": "kernels",
    "moves": "out_tok_s", "source": "device_trace",
}
SCOPES = ("delta_chunk",)


def read(obs):
    from lib import roofline_delta

    shape = roofline_delta.shape_of(obs)
    if shape is None or not obs.get("peaks"):
        return None
    runs = roofline_delta.traced_prefills(obs)
    share = roofline_delta.prefill_scope_share(obs, SCOPES)
    seconds = sum(r["seconds"] for r in runs)
    if not seconds or not share:
        return None
    flops = roofline_delta.chunk_flops(
        shape, sum(r["prompt_tokens"] for r in runs))
    return 100.0 * flops / obs["peaks"]["bf16_flops_s"] / (seconds * share)
