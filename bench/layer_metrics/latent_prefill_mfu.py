"""A latent model's prefill programs against the chip's peak: the model's
operations for the TRUE tokens of a prompt at the share held (projections,
causal pairs, the dense FFN, shared expert and router, the held experts on
the pairs the router sends here in the mean, the head on the last token:
``lib/roofline_latent.py`` ``prefill_flops``) over the program's device
time, run by run: each prefill program that ran whole inside the trace is
paired with the flight sample of its own dispatch through the engine's host
spans (``paired_prefills``), so the operations and the seconds are those of
the same prompts whatever buckets the traced seconds happened to hold. The
padding to the bucket is work the program does and the model does not need,
so it counts against the share."""

META = {
    "unit": "%", "better": "higher", "layer": "jitted programs",
    "moves": "out_tok_s", "source": "device_trace",
}


def read(obs):
    from lib import roofline_latent

    shape = roofline_latent.shape_of(obs)
    if shape is None or not obs.get("peaks"):
        return None
    runs = roofline_latent.traced_prefills(obs)
    seconds = sum(r["seconds"] for r in runs)
    if not seconds:
        return None
    flops = roofline_latent.prefill_flops(
        shape, [r["prompt_tokens"] for r in runs],
        roofline_latent.mean_routed_pairs_token(shape))
    return 100.0 * flops / obs["peaks"]["bf16_flops_s"] / seconds
