"""The engine loop's time in prefill dispatches per decode step, for a
latent model: the wall time of the window's ``prefill`` flight samples (a
sample's ``wall_ms`` runs from the boundary before its dispatch to the one
after its fetch, so the batches of one admission add up to the time the loop
spent admitting) over the decode steps of its ``decode`` samples. The
missing term between the decode step's device time and ``tpot_p50_ms``: a
running request's next token waits while the prefills of the requests
admitted beside it hold the device, which is busy all the while (idle under
2%).

Taken from the whole window's samples and not from the trace: requests end
in waves (answers of 4, 8 or 16 chunks), so the traced 4 s hold anything
from no decode chunk and eight prefills to six chunks and three prefills
(my five traces, PR 34), and a share of the trace swings with where it
falls. The accepted ``prefill_interleave_ms_step`` counts an operation of
the dense family's step in the trace, which this family's programs do not
have."""

META = {
    "unit": "ms", "better": "lower", "layer": "admission and scheduler",
    "moves": "tpot_p50_ms", "source": "program_span",
}


def read(obs):
    from lib import roofline_latent

    if roofline_latent.shape_of(obs) is None:
        return None
    samples = obs.get("samples") or []
    steps = sum(s.get("steps") or 0 for s in samples
                if s.get("phase") == "decode")
    prefill_ms = sum(s.get("wall_ms") or 0.0 for s in samples
                     if s.get("phase") == "prefill")
    if not steps or not prefill_ms:
        return None
    return prefill_ms / steps
