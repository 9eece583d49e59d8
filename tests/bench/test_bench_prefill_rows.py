"""``bench/layer_metrics/prefill_rows_dispatch_mean.py``: on hand-written
flight samples with known answers (``fixtures/flight/prefill_rows.samples.json``),
on nothing, on what an engine records at the tiny sizes, and its entry in
``BENCHMARK.json``."""

import asyncio
import json
import os

import pytest

from lib import observe

HERE = os.path.dirname(__file__)
ROOT = os.path.join(HERE, "..", "..")
BENCH = os.path.join(ROOT, "bench")
NAME = "prefill_rows_dispatch_mean"


@pytest.fixture(scope="module")
def metric():
    return observe.load_metric(observe.find("layer_metrics", NAME, [BENCH]))


@pytest.fixture(scope="module")
def windows():
    path = os.path.join(HERE, "fixtures", "flight", "prefill_rows.samples.json")
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("window, dispatches, mean", [
    ("planned", 6, 20 / 6), ("queue_order", 15, 20 / 15),
])
def test_the_reader_on_the_fixture(metric, windows, window, dispatches, mean):
    samples = windows[window]
    assert sum(s["phase"] == "prefill" for s in samples) == dispatches
    assert metric["read"]({"samples": samples}) == pytest.approx(mean)
    # the decode chunks' and the stall's tokens are no rows of a prefill
    only = [s for s in samples if s["phase"] == "prefill"]
    assert metric["read"]({"samples": only}) == pytest.approx(mean)


@pytest.mark.parametrize("obs", [
    {}, {"samples": None}, {"samples": []},
    {"samples": [{"phase": "decode", "steps": 32, "tokens": 3200},
                 {"phase": "stall"}]},
    {"samples": [{"phase": "prefill"}]},      # a sample without its rows
    {"trace": None, "samples": [], "llama": {"layers": 1}},
], ids=["empty", "none", "no-samples", "no-prefill", "no-rows", "untraced"])
def test_a_window_without_a_prefill_sample_gives_nothing(metric, obs):
    assert metric["read"](obs) is None


def test_the_report_leaves_the_metric_out_where_nothing_is_read(windows):
    names = [(NAME, "rows")]
    got = observe.report(names, "layer_metrics", [BENCH],
                         {"samples": windows["planned"]})
    assert got == {NAME: {"value": pytest.approx(20 / 6), "unit": "rows"}}
    assert observe.report(names, "layer_metrics", [BENCH], {"samples": []}) == {}


def test_the_benchmark_s_entry_is_the_reader_s_and_lists_no_cells(metric):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "admission and scheduler",
        "moves": "out_tok_s",
    }
    assert {k: metric[k] for k in entry if k != "name"} == {
        k: v for k, v in entry.items() if k != "name"}
    # no list of cells: every cell reports out_tok_s, and every engine
    # records a prefill sample's rows, so every cell reports this
    out_tok_s = next(m for m in bench["end_to_end"] if m["name"] == "out_tok_s")
    assert "workloads" not in out_tok_s
    model = next(m for m in bench["per_layer"]
                 if m["name"] == "occupancy_dispatch_mean")
    assert model["layer"] == entry["layer"]
    assert model["source"] == entry["source"]


@pytest.mark.parametrize("prefill_batch, mean", [(8, 8 / 5), (1, 1.0)])
def test_the_reader_on_an_engine_s_own_samples(metric, prefill_batch, mean):
    """Eight prompts over three buckets, queued before the first admission
    pass: the plan sends five programs (2, 2, 2, 1, 1 rows), the arrival
    order with ``prefill-batch`` 1 eight; the engine's own counter agrees."""
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    prompts = [
        "".join(chr(97 + (i * 7 + j) % 26) for j in range(n))
        for i, n in enumerate((10, 40, 90, 20, 70, 35, 12, 100))
    ]

    async def main():
        engine = TpuServingEngine(ServingConfig(
            model="tiny", slots=8, max_seq_len=256, model_dtype="float32",
            kv_block_size=16, decode_chunk=4, prefix_cache=False,
            prefill_batch=prefill_batch,
        ))
        try:
            await asyncio.gather(*(
                engine.generate(p, {"max-tokens": 3, "temperature": 0})
                for p in prompts))
            return (engine.flight.recent(0),
                    engine.stats()["prefill_rows_mean"],
                    engine.flight.summary()["totals"]["prefill_rows_mean"])
        finally:
            await engine.close()
            TpuServingEngine.reset_instances()

    samples, counter, rollup = asyncio.run(main())
    assert metric["read"]({"samples": samples}) == pytest.approx(mean)
    assert counter == rollup == pytest.approx(mean)
