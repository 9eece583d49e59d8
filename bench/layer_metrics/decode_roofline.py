"""The whole decode step against its roofline: the least time the chip could
take for one step (every weight byte once, the live K and V rows once; or the
operations, whichever is longer) over ``decode_dev_ms_step``.

Live rows and running requests are means over the harness's polls of the
block manager during the window (twice a second): distinct blocks held by
running requests x block size, less half a block a request for the unfilled
tail; and the slots in use."""

META = {
    "unit": "%", "better": "higher", "layer": "kernels",
    "moves": "tpot_p50_ms", "source": "device_trace",
}


def live(obs):
    """(mean live rows, mean running requests) over the window's polls of
    the block manager, or None."""
    polls = obs.get("polls") or []
    if not polls:
        return None
    bs = obs["pool"]["block_size"]
    active = sum(p["active"] for p in polls) / len(polls)
    blocks = sum(p["live_blocks"] for p in polls) / len(polls)
    return max(0.0, blocks * bs - active * bs / 2), active


def read(obs):
    import importlib

    from lib import roofline

    step_ms = importlib.import_module("layer_metrics.decode_dev_ms_step").read(obs)
    load = live(obs)
    if not step_ms or load is None:
        return None
    rows, batch = load
    floor = roofline.decode_step_floor(
        obs["shape"], live_rows=rows, batch=batch, peaks=obs["peaks"]
    )
    return 100.0 * floor["floor_s"] / (step_ms / 1e3)
