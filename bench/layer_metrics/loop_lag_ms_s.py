"""Milliseconds a second the engine's coroutine stood in its event loop's
ready queue after its dispatch thread had returned: the sum of the window's
flight samples' ``resume_lag_ms`` (``serving/flight.py`` ``resumed``: over a
dispatch's awaits, the coroutine's first statement after the ``await`` less
the dispatch thread's last stamp before returning) over the seconds the
samples tile (their ``wall_ms``). The whole window with the tracing off,
where ``idle_loop_lag_ms_s`` + ``idle_hop_ms_s`` read the same wait in 4
traced seconds, and only where the device was idle under it. Samples
without the field (a program from before it) give nothing."""

META = {
    "unit": "ms/s", "better": "lower", "layer": "admission and scheduler",
    "moves": "out_tok_s", "source": "program_counter",
}


def read(obs):
    samples = obs.get("samples") or []
    lags = [s["resume_lag_ms"] for s in samples
            if s.get("resume_lag_ms") is not None]
    seconds = sum(s.get("wall_ms") or 0.0 for s in samples) / 1e3
    if not lags or not seconds:
        return None
    return sum(lags) / seconds
