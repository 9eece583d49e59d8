"""The WHOLE prefill of the window-and-full family's plain member against the
chip's peak: the model's operations for the prompt's TRUE tokens (the
projections, the router and 8 of 64 experts a token, the attention over the
pairs inside each layer's mask, the head on the last token:
``lib/roofline_swa.py`` ``prefill_flops`` on ``lib/roofline_wf.py``'s shape),
over the prefill program's device time from its first operation to its
last, run by run (each run that lies whole in the trace, paired with its
flight sample). A prompt of 512 rows or fewer takes the dense routed pass,
which spends 8 times the experts' operations counted here. It bounds
whatever a later change claims inside the prefill."""

META = {"unit": "%", "better": "higher", "layer": "jitted programs",
    "moves": "out_tok_s", "source": "device_trace"}


def read(obs):
    from lib import roofline_wf

    shape = roofline_wf.shape_of(obs)
    if shape is None or not obs.get("peaks"):
        return None
    runs = [r for r in roofline_wf.traced_prefills(obs) if r["seconds"]]
    if not runs:
        return None
    flops = roofline_wf.prefill_flops(
        shape, [r["prompt_tokens"] for r in runs])
    return 100.0 * flops / obs["peaks"]["bf16_flops_s"] / sum(
        r["seconds"] for r in runs)
