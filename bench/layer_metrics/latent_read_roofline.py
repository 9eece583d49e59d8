"""The Pallas latent read (``ops/paged_attention.py`` ``latent_read``: every
query head against the one compressed row a position, the value the row's
first lanes) against its roofline: the least time for one call (the live
rows of one layer once at the ``kv_lora_rank + qk_rope_head_dim`` values
that are data, or every head's operations over them, whichever is longer:
``lib/roofline_latent.py`` ``latent_read_floor``) over the kernel's mean
device time a call. The kernel is the op that carries its own name,
``latent_read.N``, in the decode-chunk programs; the live rows a step are
the flight samples' ``live_rows`` (``serving/engine.py`` ``_read_blocks``).

A posture that reads the pool through XLA, a program of another family and
a run that was not traced give nothing."""

META = {
    "unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace",
}


def read(obs):
    from lib import roofline_latent, xplane

    trace, shape = obs.get("trace"), roofline_latent.shape_of(obs)
    load = roofline_latent.per_step(obs)
    if (not trace or shape is None or load is None or not obs.get("peaks")
            or obs.get("paged_read_kernel") != "pallas"):
        return None
    kernel = xplane.ops_in(trace, roofline_latent.DECODE_PROGRAM,
                           roofline_latent.READ_KERNEL)
    if not kernel["calls"]:
        return None
    floor = roofline_latent.latent_read_floor(
        shape, live_rows=load["live_rows"], peaks=obs["peaks"])
    return 100.0 * floor["floor_s"] / (kernel["total_s"] / kernel["calls"])
