"""The flash kernel of a latent model's prefill (keys of ``qk_nope_head_dim
+ qk_rope_head_dim``, values of ``v_head_dim``: ``ops/flash_attention.py``
``flash_prefill``) against the chip's peak: the operations of every layer's
expanded attention over the causal pairs of a prompt's TRUE tokens
(``lib/roofline_latent.py`` ``flash_flops``; the padding to the bucket is
work the kernel does and the algorithm does not need) over the kernel's
device time, run by run: each prefill program that ran whole inside the
trace is paired with the flight sample of its own dispatch through the
engine's host spans (``paired_prefills``), and the kernel's operations
inside that run are set against that prompt's pairs."""

META = {
    "unit": "%", "better": "higher", "layer": "kernels",
    "moves": "out_tok_s", "source": "device_trace",
}


def read(obs):
    from lib import roofline_latent

    shape = roofline_latent.shape_of(obs)
    if shape is None or not obs.get("peaks"):
        return None
    runs = [r for r in roofline_latent.traced_prefills(obs) if r["flash_s"]]
    if not runs:
        return None
    flops = roofline_latent.flash_flops(
        shape, [r["prompt_tokens"] for r in runs])
    return 100.0 * flops / obs["peaks"]["bf16_flops_s"] / sum(
        r["flash_s"] for r in runs)
