"""How unevenly the softmax router loads the experts, all of them held: over
the window's decode chunks, the most (token, expert) pairs any one expert of
any one layer got in a chunk, over the mean an expert got (the chunk's routed
pairs over layers x experts), weighted by the chunks' pairs. 1 is an even
load. From the flight samples' ``expert_load_max`` and ``routed_pairs``
(``serving/engine.py`` ``_await_chunk``)."""

META = {"unit": "ratio", "better": "lower", "layer": "jitted programs",
    "moves": "out_tok_s", "source": "program_counter"}


def read(obs):
    from lib import roofline_wf
    from lib.roofline_hybrid import chunk_samples

    shape = roofline_wf.shape_of(obs)
    rows = chunk_samples(obs)
    pairs = sum(s["routed_pairs"] for s in rows)
    if shape is None or not pairs:
        return None
    cells = shape.sparse_layers * shape.experts_held
    return sum(s["expert_load_max"] for s in rows) / (pairs / cells)
