"""The share of a decode step's running slots whose rows are fewer than the
window: their rings are not full, their window layers' read starts at row 0
and reads what a full layer's reads. The mean over the window's decode steps
of the flight samples' ``short_slots`` over ``active_at_dispatch``
(``models/swa.py`` ``_pool_rows``). Neither better nor worse by itself: it
says what mix of slots the step's other numbers were read at."""

META = {"unit": "%", "better": "higher", "layer": "block manager",
    "moves": "tpot_p50_ms", "source": "program_counter"}


def read(obs):
    from lib import roofline_wf

    if roofline_wf.shape_of(obs) is None:
        return None
    share = roofline_wf.gauge_share(
        obs, "short_slots", lambda s: s.get("active_at_dispatch"))
    return None if share is None else 100.0 * share
