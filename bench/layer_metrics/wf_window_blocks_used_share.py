"""The window kind's blocks in slots' rings over the ``slots x ring`` the pool
is sized for (a ring of ``sliding_window / block + 1`` blocks a slot whatever
its length: ``models/paged.py`` ``BlockManager``): the mean over the window's
decode steps of the flight samples' ``window_blocks_held`` (``models/swa.py``
``_pool_rows``). What it leaves under 100% is what a window pool sized under
``slots x ring`` could give back."""

META = {"unit": "%", "better": "higher", "layer": "block manager",
    "moves": "out_tok_s", "source": "program_counter"}


def read(obs):
    from lib import roofline_wf

    shape = roofline_wf.shape_of(obs)
    if shape is None:
        return None
    block = int(obs["serving"].get("kv-block-size", 64))
    ring = -(-shape.window // block) + 1
    sized = int(obs["serving"]["slots"]) * ring
    share = roofline_wf.gauge_share(
        obs, "window_blocks_held", lambda s: sized)
    return None if share is None else 100.0 * share
