"""How unevenly the router loads the experts held here: over the window's
decode chunks, the most (token, expert) pairs any one expert of any one
layer got in a chunk, over the mean an expert got (the chunk's routed pairs
over layers x experts held), weighted by the chunks' pairs. 1 is an even
load; the grouped and the dense expert passes both take as long as their
fullest expert. From the flight samples' ``expert_load_max`` and
``routed_pairs`` (``serving/engine.py`` ``_await_chunk``)."""

META = {
    "unit": "ratio", "better": "lower", "layer": "jitted programs",
    "moves": "out_tok_s", "source": "program_counter",
}


def read(obs):
    from lib import roofline_hybrid

    shape = roofline_hybrid.shape_of(obs)
    rows = roofline_hybrid.chunk_samples(obs)
    pairs = sum(s["routed_pairs"] for s in rows)
    if shape is None or not pairs:
        return None
    cells = shape.moe_layers * shape.experts_held
    return sum(s["expert_load_max"] for s in rows) / (pairs / cells)
