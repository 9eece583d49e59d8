"""The flash kernel of a prefill whose layers attend a window of 1,024 rows
or every row, over prompts from under the window to eight times it, against
the chip's peak: the attention's operations over the pairs INSIDE each
layer's mask for the prompt's TRUE tokens (``lib/roofline_swa.py``
``flash_flops``) over the kernel's device time, run by run: each prefill
program that ran whole inside the trace is paired with the flight sample of
its own dispatch (``roofline_latent.paired_prefills``)."""

META = {"unit": "%", "better": "higher", "layer": "kernels",
    "moves": "out_tok_s", "source": "device_trace"}


def read(obs):
    from lib import roofline_wf

    shape = roofline_wf.shape_of(obs)
    if shape is None or not obs.get("peaks"):
        return None
    runs = [r for r in roofline_wf.traced_prefills(obs) if r["flash_s"]]
    if not runs:
        return None
    flops = roofline_wf.flash_flops(shape, [r["prompt_tokens"] for r in runs])
    return 100.0 * flops / obs["peaks"]["bf16_flops_s"] / sum(
        r["flash_s"] for r in runs)
