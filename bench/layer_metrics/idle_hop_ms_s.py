"""Milliseconds of device idle per traced second while the innermost host
span is a TENANT's of the engine's event loop: ``ls.hop.*``
(``langstream_tpu/serving/flight.py`` ``SPANS``), opened through
``core/tracing.py`` ``host_span`` around the synchronous stretches of the
delivery path that shares the loop with the engine in a one-pod deployment:
the consumer's coroutine (``ls.hop.deliver``), the agent's stream writer
(``ls.hop.agent``), the topic's write and a reader's wake (``ls.hop.topic``),
the gateway's frames in and out (``ls.hop.gw.recv``, ``ls.hop.gw.send``),
the runner's bookkeeping (``ls.hop.runner``). An instant such a span takes
leaves ``idle_prefill_host_ms_s`` or ``idle_decode_host_ms_s``, or was under
no span before. A trace without any ``ls.hop.*`` span (a program from before
the spans) gives nothing."""

META = {
    "unit": "ms/s", "better": "lower", "layer": "gateway, topic, agent runner",
    "moves": "out_tok_s", "source": "program_span",
}

PREFIX = "ls.hop."


def read(obs):
    from lib import hosttrace

    reduced = hosttrace.of(obs)
    if not reduced or not any(s["name"].startswith(PREFIX)
                              for s in reduced["spans"]):
        return None
    return hosttrace.idle_under(obs, PREFIX)
