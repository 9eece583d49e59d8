"""The traffic generator: the seed permutes, it does not resample."""

import json
import os
import statistics

import pytest

from lib import traffic

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "bench")
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
OPEN = "two-second-open"   # the committed cells are closed loops
LENGTHS = [16, 48, 96, 128, 192, 384]
SEEDS = [0, 7, 2 ** 31 + 11]


def mix(name):
    root = FIXTURES if name == OPEN else BENCH
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def plan(name, seed, seconds=45.0, slots=64):
    return traffic.plan(mix(name), seed=seed, seconds=seconds, slots=slots,
                        max_seq_len=2048,
                        output_lengths=[4, 8] if name == OPEN else LENGTHS)


def pairs(p, measured_only=True):
    return sorted((r["prompt_tokens"], r["output_tokens"])
                  for r in p["requests"] if r["measured"] or not measured_only)


@pytest.mark.parametrize("name", ["chat-sat", OPEN, "rag-sat"])
def test_every_seed_sends_the_same_multiset_in_another_order(name):
    plans = [plan(name, s) for s in SEEDS]
    assert pairs(plans[0]) == pairs(plans[1]) == pairs(plans[2])
    orders = [[(r["prompt_tokens"], r["output_tokens"]) for r in p["requests"]
               if r["measured"]] for p in plans]
    assert orders[0] != orders[1] and orders[1] != orders[2]
    contents = [p["requests"][-1]["content"] for p in plans]
    assert len(set(contents)) == 3          # the seed draws the bytes
    again = plan(name, SEEDS[2])
    assert again == plans[2]                # and the same seed, the same run


def test_prompt_bytes_make_the_stated_token_count():
    for r in plan("chat-sat", 3)["requests"]:
        assert len(r["content"].encode()) + traffic.TEMPLATE_TOKENS \
            == r["prompt_tokens"]
        assert set(r["content"]) <= set(traffic.ALPHABET)


def test_chat_multiset_is_the_stated_distribution():
    ms = traffic.multiset(mix("chat-sat"), mix("chat-sat")["multiset"])
    prompts = [p for p, _ in ms]
    outputs = [o for _, o in ms]
    assert 32 <= min(prompts) and max(prompts) <= 1024
    assert 230 <= statistics.median(prompts) <= 270
    assert statistics.median(outputs) == 128
    assert 140 <= statistics.mean(outputs) <= 150
    assert len(ms) == 48   # two passes are in flight at once on 64 slots
    assert set(outputs) == set(LENGTHS)


def test_rag_requests_fit_a_slot():
    for p, o in traffic.multiset(mix("rag-sat"), mix("rag-sat")["multiset"]):
        assert 1024 <= p <= 1856 and o in (48, 96) and p + o + 1 <= 2048


def test_open_loop_count_and_due_times():
    m = mix(OPEN)
    instants = {tuple(r["due_s"] for r in plan(OPEN, s)["requests"])
                for s in SEEDS}
    assert len(instants) == 1               # every seed: the same arrivals
    gaps = [b - a for a, b in zip(*(lambda d: (d, d[1:]))(list(instants.pop())))]
    assert max(gaps) > 3 * (sum(gaps) / len(gaps))   # irregular, not a grid
    for seed in SEEDS:
        p = plan(OPEN, seed, seconds=45.0)
        measured = [r for r in p["requests"] if r["measured"]]
        lead = [r for r in p["requests"] if not r["measured"]]
        assert len(measured) == round(m["rate"] * 45.0)
        assert len(lead) == round(m["rate"] * m["lead_in_s"])
        assert all(0.0 <= r["due_s"] < 45.0 for r in measured)
        assert all(-m["lead_in_s"] <= r["due_s"] < 0.0 for r in lead)
        due = [r["due_s"] for r in p["requests"]]
        assert due == sorted(due)           # arrivals are order statistics


def test_closed_loop_clients_follow_the_slots():
    assert plan("chat-sat", 1, slots=64)["clients"] == 96
    assert plan("chat-sat", 1, slots=128)["clients"] == 192
    assert plan("rag-sat", 1, slots=128)["clients"] == 32


def test_the_committed_mixes_state_no_key_the_generator_ignores():
    known = {"why", "loop", "rate", "lead_in_s", "clients", "clients_per_slot",
             "multiset", "prompt_tokens", "output_tokens",
             "shared_prefix_tokens"}
    for name in ("chat-sat", "rag-sat", OPEN, "two-second-closed"):
        root = BENCH if name.endswith("-sat") else FIXTURES
        with open(os.path.join(root, "traffic", f"{name}.json")) as f:
            assert set(json.load(f)) <= known, name


def test_shared_prefix_is_shared_and_counted():
    m = dict(mix("chat-sat"), shared_prefix_tokens=24,
             prompt_tokens={"dist": "uniform", "min": 100, "max": 200})
    p = traffic.plan(m, seed=9, seconds=10, slots=8, max_seq_len=2048,
                     output_lengths=LENGTHS)
    heads = {r["content"][:24] for r in p["requests"]}
    assert len(heads) == 1
    assert all(len(r["content"]) + traffic.TEMPLATE_TOKENS == r["prompt_tokens"]
               for r in p["requests"])


@pytest.mark.parametrize("change,match", [
    ({"output_tokens": {"choices": [17]}}, "not one of"),
    ({"prompt_tokens": {"dist": "uniform", "min": 1900, "max": 2000}}, "exceeds"),
    ({"loop": "spiral"}, "open or closed"),
    ({"prompt_tokens": {"dist": "uniform", "min": 10, "max": 20}}, "no content"),
])
def test_a_mix_that_cannot_be_served_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        traffic.plan({**mix("chat-sat"), **change}, seed=1, seconds=10,
                     slots=8, max_seq_len=2048, output_lengths=LENGTHS)


def test_quantiles_of_choices_keep_the_weights():
    q = traffic.quantiles({"choices": [1, 2, 3], "weights": [0.5, 0.25, 0.25]}, 8)
    assert q == [1, 1, 1, 1, 2, 2, 3, 3]
    assert len(traffic.quantiles({"choices": [1, 2, 3]}, 10)) == 10
