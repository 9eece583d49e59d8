"""The WHOLE prefill of a model of the window-and-full family against the
chip's peak: the model's operations for the prompt's TRUE tokens at the
share held, the attention over the pairs inside each layer's mask
(``lib/roofline_swa.py`` ``prefill_flops``), over the prefill program's
device time from its first operation to its last, run by run (each run that
lies whole in the trace, paired with its flight sample:
``roofline_latent.paired_prefills``). It bounds whatever a later change
claims inside the prefill."""

META = {"unit": "%", "better": "higher", "layer": "jitted programs",
    "moves": "out_tok_s", "source": "device_trace"}


def read(obs):
    from lib import roofline_swa

    shape = roofline_swa.shape_of(obs)
    if shape is None or not obs.get("peaks"):
        return None
    runs = [r for r in roofline_swa.traced_prefills(obs) if r["seconds"]]
    if not runs:
        return None
    flops = roofline_swa.prefill_flops(
        shape, [r["prompt_tokens"] for r in runs])
    return 100.0 * flops / obs["peaks"]["bf16_flops_s"] / sum(
        r["seconds"] for r in runs)
