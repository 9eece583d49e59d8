"""What a pool a layer kind spares: 1 - the rows the two pools hold for the
running slots (every block of a full layer, a ring of ``sliding_window /
block + 1`` blocks at most of a window layer) over the rows ONE table for all
layers would hold for them (every layer every block), over the window's
decode chunks. From the flight samples' ``pool_rows_held`` and
``pool_rows_one_table`` (``serving/engine.py`` ``_pool_rows``; the block
manager's accounting, ``models/paged.py``)."""

META = {"unit": "%", "better": "higher", "layer": "block manager",
    "moves": "out_tok_s", "source": "program_counter"}


def read(obs):
    from lib import roofline_swa

    share = roofline_swa.rows_saved_share(obs)
    return None if share is None else 100.0 * share
