"""A ``solar_open2`` model's prefill programs against the chip's peak: the
model's operations for the TRUE tokens of a batch at the share held (the
mixers' projections, the chunked delta rule, the shared expert and router,
the held experts on the pairs the router sends here in the mean, the
attention's causal pairs at their least (a batch's sample carries its tokens
as one number: eight equal prompts), the head on one last token:
``lib/roofline_delta.py`` ``prefill_flops``) over the program's device time,
run by run: each prefill program that ran whole inside the trace is paired
with the flight sample of its own dispatch through the engine's host spans
(``lib/roofline_latent.py`` ``paired_prefills``). The padding to the bucket
and to the batch is work the program does and the model does not need, so it
counts against the share."""

META = {
    "unit": "%", "better": "higher", "layer": "jitted programs",
    "moves": "out_tok_s", "source": "device_trace",
}


def read(obs):
    from lib import roofline_delta

    shape = roofline_delta.shape_of(obs)
    if shape is None or not obs.get("peaks"):
        return None
    runs = roofline_delta.traced_prefills(obs)
    seconds = sum(r["seconds"] for r in runs)
    if not seconds:
        return None
    flops = sum(roofline_delta.prefill_flops(shape, r["prompt_tokens"])
                for r in runs)
    return 100.0 * flops / obs["peaks"]["bf16_flops_s"] / seconds
