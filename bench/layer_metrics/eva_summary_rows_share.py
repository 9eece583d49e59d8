"""Summary rows of all the rows a decode step reads: the mean over the
window's decode steps of the flight samples' ``summary_rows`` over
``summary_rows + window_rows`` (``models/eva.py`` ``_pool_rows``). Neither
better nor worse by itself: it says how much of the step's read is compressed
history at the contexts the step's other numbers were read at."""

META = {"unit": "%", "better": "higher", "layer": "block manager",
    "moves": "tpot_p50_ms", "source": "program_counter"}


def read(obs):
    from lib import roofline_eva

    if roofline_eva.shape_of(obs) is None:
        return None
    share = roofline_eva.summary_rows_share(obs)
    return None if share is None else 100.0 * share
