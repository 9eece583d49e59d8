"""Slots running when a decode chunk was dispatched, mean over the window's
decode dispatches weighted by the steps each fused: the flight sample's
``active_at_dispatch`` and ``steps`` (``serving/engine.py`` ``_ticket``),
taken at dispatch and not when the chunk's result has already freed its
finished slots. A program whose samples lack the fields gives nothing."""

META = {
    "unit": "slots", "better": "higher", "layer": "admission and scheduler",
    "moves": "out_tok_s", "source": "program_counter",
}


def read(obs):
    rows = [s for s in obs.get("samples") or []
            if s.get("phase") == "decode" and s.get("steps")
            and s.get("active_at_dispatch") is not None]
    steps = sum(s["steps"] for s in rows)
    if not steps:
        return None
    return sum(s["active_at_dispatch"] * s["steps"] for s in rows) / steps
