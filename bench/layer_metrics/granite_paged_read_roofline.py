"""The Pallas paged-attention read of a ``granitemoehybrid`` model (one
attention layer a period, G = 4, the scale passed in) against its roofline:
the least time for one call (the live K and V rows of one layer once,
4.H.D operations a row; ``lib/roofline.py`` ``paged_read_floor``) over the
kernel's mean device time a call.

``paged_read_roofline.py`` takes for the kernel every op of the decode
program whose name says closed_call, custom-call or paged. This family's
decode program has other custom calls (``AllocateBuffer``,
``ConcatBitcast``, ``GatherScatterIndicesBitpacked``: a few hundred
nanoseconds each), and counted as calls of the kernel they bring its mean
time a call down and the share over 100%. Here the kernel is the op that
carries its own name, ``paged_read.N`` (``ops/paged_attention.py``
``pallas_call(name="paged_read")``). A posture that reads the pool through
XLA, a program of another family and a run that was not traced give
nothing."""

META = {
    "unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace",
}
KERNEL = r"^paged_read[._]"


def read(obs):
    import importlib

    from lib import roofline, roofline_granite, xplane

    trace = obs.get("trace")
    if (not trace or obs.get("paged_read_kernel") != "pallas"
            or roofline_granite.shape_of(obs) is None
            or not obs.get("peaks") or not obs.get("shape")):
        return None
    kernel = xplane.ops_in(trace, "decode_chunk", KERNEL)
    load = importlib.import_module("layer_metrics.decode_roofline").live(obs)
    if not kernel["calls"] or load is None:
        return None
    rows, _ = load
    floor = roofline.paged_read_floor(obs["shape"], live_rows=rows,
                                      peaks=obs["peaks"])
    return 100.0 * floor["floor_s"] / (kernel["total_s"] / kernel["calls"])
