"""Milliseconds of device idle per traced second whose innermost host span
is one of the three the engine's coroutine HOLDS across an ``await`` of its
dispatch thread (``langstream_tpu/serving/flight.py`` ``HELD_SPANS``:
``ls.prefill.handoff``, ``ls.prefill.fetch``, ``ls.decode.fetch``). While
that thread is still uploading, calling or blocked, the instant is its own
span's (``ls.*.dispatch``, ``ls.*.wait``: they started later); once it has
returned, what is left under a held name is the coroutine waiting for its
turn on the loop with nobody named in its way (a tenant that runs meanwhile
takes the instant under ``ls.hop.*``: ``idle_hop_ms_s``). The instants stay
inside ``idle_prefill_host_ms_s`` / ``idle_decode_host_ms_s``, whose
prefixes cover the held names.

On a program from before the ``*.wait`` spans a held name covers the blocked
wait too, which is another quantity: a trace without a ``*.wait`` span gives
nothing."""

META = {
    "unit": "ms/s", "better": "lower", "layer": "admission and scheduler",
    "moves": "out_tok_s", "source": "program_span",
}

HELD = ("ls.prefill.handoff", "ls.prefill.fetch", "ls.decode.fetch")
WAITS = ("ls.prefill.wait", "ls.decode.wait")


def read(obs):
    from lib import hosttrace

    reduced = hosttrace.of(obs)
    window = (obs.get("trace") or {}).get("window_s")
    if not reduced or not window or not any(
            s["name"] in WAITS for s in reduced["spans"]):
        return None
    by_span = reduced["idle"]["by_span"]
    return 1e3 * sum(by_span.get(name, 0.0) for name in HELD) / window
