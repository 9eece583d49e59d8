"""A latent model's whole decode step against its roofline: the least time
the chip could take for one step (every held weight once, the live latent
rows of every layer once; or the operations, the reads' among them,
whichever is longer: ``lib/roofline_latent.py`` ``decode_step_floor``) over
the device time of a decode step: the seconds of the decode-chunk programs'
operations over the steps the trace holds of them (``traced_steps``: the
read kernel's calls there over the layers; no run has to be whole). Live rows, running requests and routed pairs a step are means
over the window's flight samples."""

META = {
    "unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace",
}


def read(obs):
    from lib import roofline_latent

    shape = roofline_latent.shape_of(obs)
    load = roofline_latent.per_step(obs)
    if shape is None or load is None or not obs.get("peaks"):
        return None
    seconds, steps = roofline_latent.traced_steps(obs)
    if not steps:
        return None
    floor = roofline_latent.decode_step_floor(
        shape, live_rows=load["live_rows"], batch=load["slots"],
        routed_pairs=load["routed_pairs"], peaks=obs["peaks"])
    return 100.0 * floor["floor_s"] / (seconds / steps)
