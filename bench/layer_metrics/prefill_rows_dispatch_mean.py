"""Requests a prefill program carried, mean over the window's prefill
dispatches: the flight sample's ``tokens`` of the ``prefill`` samples
(``serving/engine.py`` ``_admit_complete``: the rows of the batch that are
requests, padding left out). 1.2-1.4 where a closed loop's arrivals are
batched in queue order; what the wave's plan by bucket
(``serving/scheduler.py`` ``plan_wave``) raises. A window without a prefill
sample gives nothing."""

META = {
    "unit": "rows", "better": "higher", "layer": "admission and scheduler",
    "moves": "out_tok_s", "source": "program_counter",
}


def read(obs):
    rows = [s["tokens"] for s in obs.get("samples") or []
            if s.get("phase") == "prefill" and s.get("tokens") is not None]
    if not rows:
        return None
    return sum(rows) / len(rows)
