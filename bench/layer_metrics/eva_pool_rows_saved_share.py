"""What two kinds of history spare: 1 - the rows the ring and the summary
pool hold for the running slots (a ring of ``window_size / block`` blocks at
most, and the summary blocks of every window closed or open) over the rows a
plain K/V cache would hold for them (every layer every block), over the
window's decode chunks. From the flight samples' ``pool_rows_held`` and
``pool_rows_plain_cache`` (``models/eva.py`` ``_pool_rows``; the block
manager's accounting, ``models/paged.py``)."""

META = {"unit": "%", "better": "higher", "layer": "block manager",
    "moves": "out_tok_s", "source": "program_counter"}


def read(obs):
    from lib import roofline_eva

    if roofline_eva.shape_of(obs) is None:
        return None
    share = roofline_eva.rows_saved_share(obs)
    return None if share is None else 100.0 * share
