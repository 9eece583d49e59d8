"""The attention kernel of an EVA prefill (``ops/eva_flash.py``: each window
causal over its own rows and against the summaries of the windows before it,
one softmax) against the chip's peak: the attention's operations over the
pairs actually ATTENDED by the prompt's TRUE tokens, not the bucket's
(``lib/roofline_eva.py`` ``flash_flops``), over the kernel's device time, run
by run: each prefill program that ran whole inside the trace is paired with
the flight sample of its own dispatch."""

META = {"unit": "%", "better": "higher", "layer": "kernels",
    "moves": "out_tok_s", "source": "device_trace"}


def read(obs):
    from lib import roofline_eva

    shape = roofline_eva.shape_of(obs)
    if shape is None or not obs.get("peaks"):
        return None
    runs = [r for r in roofline_eva.traced_prefills(obs) if r["flash_s"]]
    if not runs:
        return None
    flops = roofline_eva.flash_flops(shape, [r["prompt_tokens"] for r in runs])
    return 100.0 * flops / obs["peaks"]["bf16_flops_s"] / sum(
        r["flash_s"] for r in runs)
