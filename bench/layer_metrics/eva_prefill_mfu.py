"""The WHOLE prefill of an EVA decoder against the chip's peak: the model's
operations for the prompt's TRUE tokens (the projections and the gated MLP,
the attention over the attended pairs, the chunks' summaries, the head on the
last token: ``lib/roofline_eva.py`` ``prefill_flops``), over the prefill
program's device time from its first operation to its last, run by run (each
run that lies whole in the trace, paired with its flight sample). It bounds
whatever a later change claims inside the prefill."""

META = {"unit": "%", "better": "higher", "layer": "jitted programs",
    "moves": "out_tok_s", "source": "device_trace"}


def read(obs):
    from lib import roofline_eva

    shape = roofline_eva.shape_of(obs)
    if shape is None or not obs.get("peaks"):
        return None
    runs = [r for r in roofline_eva.traced_prefills(obs) if r["seconds"]]
    if not runs:
        return None
    flops = roofline_eva.prefill_flops(
        shape, [r["prompt_tokens"] for r in runs])
    return 100.0 * flops / obs["peaks"]["bf16_flops_s"] / sum(
        r["seconds"] for r in runs)
