"""The flash kernel of a prefill whose layers attend a window or every row
(``ops/flash_attention.py`` ``flash_prefill``, told each row's length and, on
a window layer, the window) against the chip's peak: the attention's
operations over the pairs INSIDE each layer's mask for the prompt's TRUE
tokens (``lib/roofline_swa.py`` ``flash_flops``: the key blocks behind a
window are work the algorithm does not need and the kernel does not do; the
padding to the bucket neither) over the kernel's device time, run by run:
each prefill program that ran whole inside the trace is paired with the
flight sample of its own dispatch (``roofline_latent.paired_prefills``)."""

META = {"unit": "%", "better": "higher", "layer": "kernels",
    "moves": "out_tok_s", "source": "device_trace"}


def read(obs):
    from lib import roofline_swa

    shape = roofline_swa.shape_of(obs)
    if shape is None or not obs.get("peaks"):
        return None
    runs = [r for r in roofline_swa.traced_prefills(obs) if r["flash_s"]]
    if not runs:
        return None
    flops = roofline_swa.flash_flops(shape, [r["prompt_tokens"] for r in runs])
    return 100.0 * flops / obs["peaks"]["bf16_flops_s"] / sum(
        r["flash_s"] for r in runs)
