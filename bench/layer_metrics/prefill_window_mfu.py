"""The prefills of the WHOLE window against the chip's peak, with the
profiler off: the family's own ``prefill_flops`` on the true tokens of every
prefill program of the window (its flight sample's ``prompt_tokens``: one
prompt a program in the cells listed), exactly as that family's
``*_prefill_mfu`` reader calls it, over the sum of those samples'
``program_ms`` (the device's clock, ``flight.py`` ``DispatchClock``;
``lib/devclock.py``). It reads every prefill of the window (over a hundred)
where the ``*_prefill_mfu`` readers read the 0-2 that lie whole in 4 traced
seconds. Only samples that carry ``seen_by`` are read; a family without a
``prefill_flops`` gives nothing."""

META = {"unit": "%", "better": "higher", "layer": "jitted programs",
        "moves": "out_tok_s", "source": "program_span"}


def _flops(obs, prompts):
    """The operations of ``prompts`` by whichever family's shape answers
    (the more particular member first: Mellum's file would answer the
    window family's reader too)."""
    from lib import roofline_eva, roofline_latent, roofline_swa, roofline_wf

    shape = roofline_latent.shape_of(obs)
    if shape is not None:
        return roofline_latent.prefill_flops(
            shape, prompts, roofline_latent.mean_routed_pairs_token(shape))
    for family in (roofline_eva, roofline_wf, roofline_swa):
        try:
            shape = family.shape_of(obs)
        except (KeyError, TypeError, ValueError):
            continue     # another member's file, which this one cannot read
        if shape is not None:
            return family.prefill_flops(shape, prompts)
    return None


def read(obs):
    from lib import devclock

    if not obs.get("peaks"):
        return None
    runs = [s for s in devclock.clocked(obs, "prefill")
            if s.get("prompt_tokens") and s["program_ms"] > 0]
    if not runs:
        return None
    flops = _flops(obs, [s["prompt_tokens"] for s in runs])
    if flops is None:
        return None
    seconds = sum(s["program_ms"] for s in runs) / 1e3
    return 100.0 * flops / obs["peaks"]["bf16_flops_s"] / seconds
