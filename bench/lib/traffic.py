"""One general traffic generator: a mix is a data file, never code.

A traffic file (``bench/traffic/<mix>.json``) fixes a *multiset* of
(prompt tokens, output tokens) pairs — the quantiles of the distributions it
states — and, for an open loop, the *set* of arrival instants (order
statistics of uniform draws from a key that belongs to the mix, so the
arrivals are irregular as independent users' are, and the same in every
run). The seed permutes the multiset over the instants and draws the prompt
bytes; it never resamples lengths or arrivals, so every run of a cell sends
the same work at the same instants in another order.

Keys of a traffic file:

- ``loop``: ``"open"`` (arrivals on a schedule, ``rate`` requests a second,
  optional ``lead_in_s`` of unmeasured arrivals before the window) or
  ``"closed"`` (``clients`` in flight, or ``clients_per_slot`` times the
  configuration's slots).
- ``multiset``: closed loop only, the number of pairs (cycled in the seed's
  order). An open loop's multiset has exactly ``round(rate * seconds)`` pairs.
- ``prompt_tokens``: ``{"dist": "lognormal", "median", "sigma", "min",
  "max"}``, ``{"dist": "uniform", "min", "max"}`` or ``{"choices": [...],
  "weights": [...]}``. Lengths count the whole prompt as the engine sees it:
  BOS, chat template and content.
- ``output_tokens``: the same forms; values must be among the application's
  ``output_lengths`` (``max-tokens`` is a setting of the agent).
- ``shared_prefix_tokens``: leading content bytes that every request shares.
"""

from __future__ import annotations

import math
import random
import statistics

# BOS + "<|user|>\n" + content + "\n" + "<|assistant|>\n"
# (agents/tpu_provider.py _render_chat_prompt, byte tokenizer: 1 token a byte)
TEMPLATE_TOKENS = 1 + 9 + 1 + 14
ALPHABET = "abcdefghijklmnopqrstuvwxyz "
_PAIRING_KEY = 0x5EED_0F_0DD5  # pairs prompts with outputs; never the run's seed
_ARRIVALS_KEY = 0xA7717A15     # draws an open loop's arrival instants; the same


def quantiles(spec: dict, n: int) -> list[int]:
    """``n`` values at the mid-quantiles of the distribution ``spec`` states."""
    if "choices" in spec:
        weights = spec.get("weights") or [1.0] * len(spec["choices"])
        total = float(sum(weights))
        exact = [w / total * n for w in weights]
        counts = [int(math.floor(e)) for e in exact]
        # largest remainder, ties to the earlier choice: counts sum to n
        order = sorted(range(len(exact)),
                       key=lambda i: (-(exact[i] - counts[i]), i))
        for i in order[: n - sum(counts)]:
            counts[i] += 1
        return [int(c) for c, k in zip(spec["choices"], counts) for _ in range(k)]
    lo, hi = float(spec["min"]), float(spec["max"])
    us = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "uniform":
        return [int(round(lo + u * (hi - lo))) for u in us]
    if spec["dist"] == "lognormal":
        mu, sigma = math.log(float(spec["median"])), float(spec["sigma"])
        normal = statistics.NormalDist(mu, sigma)
        f_lo, f_hi = normal.cdf(math.log(lo)), normal.cdf(math.log(hi))
        return [
            int(min(hi, max(lo, round(math.exp(
                normal.inv_cdf(f_lo + u * (f_hi - f_lo))
            )))))
            for u in us
        ]
    raise ValueError(f"unknown distribution {spec!r}")


def multiset(mix: dict, n: int) -> list[tuple[int, int]]:
    """The ``n`` (prompt tokens, output tokens) pairs of a mix. The pairing
    is fixed by the mix alone, so every seed sends the same pairs."""
    prompts = quantiles(mix["prompt_tokens"], n)
    outputs = quantiles(mix["output_tokens"], n)
    random.Random(_PAIRING_KEY).shuffle(outputs)
    return list(zip(prompts, outputs))


def clients_for(mix: dict, slots: int) -> int:
    if "clients" in mix:
        return int(mix["clients"])
    return int(round(float(mix["clients_per_slot"]) * slots))


def _content(rng: random.Random, tokens: int, shared: str) -> str:
    n = tokens - TEMPLATE_TOKENS
    if n < 1:
        raise ValueError(
            f"a prompt of {tokens} tokens leaves no content after the "
            f"{TEMPLATE_TOKENS} tokens of BOS and template"
        )
    head = shared[:n]
    return head + "".join(rng.choices(ALPHABET, k=n - len(head)))


def arrivals(rng: random.Random, n: int, start: float,
             length: float) -> list[float]:
    """``n`` due times in [start, start + length), ascending: the order
    statistics of uniform draws."""
    return sorted(start + rng.random() * length for _ in range(n))


def plan(mix: dict, *, seed: int, seconds: float, slots: int,
         max_seq_len: int, output_lengths: list[int]) -> dict:
    """Everything the load generator sends in one run, from the seed."""
    rng = random.Random(int(seed))
    shared = "".join(
        rng.choices(ALPHABET, k=int(mix.get("shared_prefix_tokens", 0)))
    )
    loop = mix["loop"]
    if loop == "open":
        n = int(round(float(mix["rate"]) * seconds))
        lead_s = float(mix.get("lead_in_s", 0.0))
        n_lead = int(round(float(mix["rate"]) * lead_s))
    elif loop == "closed":
        n, lead_s, n_lead = int(mix["multiset"]), 0.0, 0
    else:
        raise ValueError(f"loop must be open or closed, not {loop!r}")
    pairs = multiset(mix, n)
    for p, o in pairs:
        if o not in output_lengths:
            raise ValueError(
                f"output length {o} is not one of the application's "
                f"{output_lengths}"
            )
        if p + o + 1 > max_seq_len:
            raise ValueError(
                f"prompt {p} + output {o} + 1 exceeds the {max_seq_len} "
                f"rows of a slot"
            )
    order = pairs[:]
    rng.shuffle(order)
    lead = pairs[:]
    rng.shuffle(lead)
    lead = [lead[i % len(lead)] for i in range(n_lead)]
    requests = []
    for index, (p, o) in enumerate(lead + order):
        requests.append({
            "id": index,
            "measured": index >= n_lead,
            "prompt_tokens": p,
            "output_tokens": o,
            "content": _content(rng, p, shared),
        })
    out = {
        "loop": loop, "seconds": float(seconds), "requests": requests,
        "lead_in_s": lead_s,
    }
    if loop == "open":
        fixed = random.Random(_ARRIVALS_KEY)  # the mix's instants, not the run's
        due = arrivals(fixed, n_lead, -lead_s, lead_s) + \
            arrivals(fixed, n, 0.0, float(seconds))
        for request, t in zip(requests, due):
            request["due_s"] = t
    else:
        out["clients"] = clients_for(mix, slots)
    return out
