"""The engine's side of the prefill buckets (``serving/engine.py``
``_prefill_bucket_rows``; the rule and the families' programs:
``test_prefill_buckets.py``): the warm-up loads the programs the rule adds,
once, a prompt then finds them, the counters say so, and an engine that
cannot reach a midpoint compiles and dispatches what it did."""

from __future__ import annotations

import asyncio

import pytest

from langstream_tpu.serving import engine as engine_module
from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

GREEDY = (False, False, True)                         # the sampler's mode


def _engine(**kw):
    return TpuServingEngine(ServingConfig(**{**dict(
        model="tiny", model_dtype="float32", slots=2, kv_block_size=16,
        prefix_cache=False), **kw}))


def _prefills(engine):
    return sorted(key for kind, key in engine._compiled_shapes
                  if kind == "prefill")


def _loads(engine, bucket):
    return [e for e in engine.flight.recent_events(0)
            if e["kind"] == "recompile" and e["what"] == "prefill"
            and e["variant"] == repr((GREEDY, bucket, 1))]


def test_the_warm_up_loads_the_midpoint_program_once_and_a_prompt_finds_it():
    async def main():
        engine = _engine(max_seq_len=8192)
        try:
            await engine.warmup()
            warm = _prefills(engine)
            end = [e for e in engine.flight.recent_events(0)
                   if e["kind"] == "warmup"][-1]
            before = engine.stats()
            # 5,000 tokens: the 8,192 bucket of the power-of-two rule
            out = await engine.generate(
                [5 + i % 200 for i in range(5000)],
                {"max-tokens": 1, "temperature": 0})
            sample = [s for s in engine.flight.recent(0)
                      if s["phase"] == "prefill"][-1]
            return (warm, end, before, _prefills(engine), sample,
                    engine.stats(), len(_loads(engine, 6144)), out)
        finally:
            await engine.close()
            TpuServingEngine.reset_instances()

    warm, end, before, after, sample, stats, loads, out = asyncio.run(main())
    assert repr((GREEDY, 6144, 1)) in warm and loads == 1
    assert end["stage"] == "end" and end["prefill_midpoints"] == [6144]
    assert before["prefill_dispatches_midpoint"] == 1
    assert after == warm                    # the prompt compiled nothing
    assert sample["bucket"] == 6144 and sample["tokens"] == 1
    assert 5000 <= sample["prompt_tokens"] <= 5001
    assert stats["prefill_dispatches_midpoint"] == 2
    assert stats["prefill_dispatches"] == before["prefill_dispatches"] + 1
    # (128 + 2 x 128 + 2 x 6,144) rows for the warm-up's and the prompt's
    assert 1.3 < stats["prefill_padded_rows_share"] < 1.4
    assert out["num_prompt_tokens"] == 5000


def test_an_engine_of_2048_rows_compiles_and_dispatches_what_it_did():
    """The probe and the wave of two, their one bucket at one and two rows:
    what the parent's warm-up left in ``_compiled_shapes``."""
    async def main():
        engine = _engine(max_seq_len=2048)
        try:
            result = await engine.warmup()
            end = [e for e in engine.flight.recent_events(0)
                   if e["kind"] == "warmup"][-1]
            return (result, end, sorted(engine._compiled_shapes),
                    engine.stats(), engine._warmup_midpoints())
        finally:
            await engine.close()
            TpuServingEngine.reset_instances()

    result, end, shapes, stats, midpoints = asyncio.run(main())
    assert result == {"decode_variants": 2, "prefill_variants": 1}
    assert end["prefill_midpoints"] == [] and midpoints == {}
    assert shapes == [
        ("decode", repr((GREEDY, 8, 16, False))),
        ("decode", repr((GREEDY, 8, 8, False))),
        ("prefill", repr((GREEDY, 128, 1))),
        ("prefill", repr((GREEDY, 128, 2))),
    ]
    assert stats["prefill_dispatches"] == 2
    assert stats["prefill_dispatches_midpoint"] == 0
    assert stats["prefill_padded_rows_share"] == 1.2075   # 384 / 318 rows


@pytest.mark.parametrize("kw, want", [
    (dict(max_seq_len=4096), {}),
    (dict(max_seq_len=6144), {6144: 4097}),
    (dict(max_seq_len=32768, kv_pool_blocks=2048), {
        6144: 4097, 12288: 8193, 24576: 16385}),
    # a pool that can never hold 8,195 rows; prompts prefilled in chunks
    (dict(max_seq_len=32768, kv_pool_blocks=400), {6144: 4097}),
    (dict(max_seq_len=16384, prefill_chunk=2048), {}),
])
def test_the_warm_up_s_midpoints_are_those_a_prompt_can_reach(kw, want):
    engine = _engine(**kw)
    try:
        assert engine._warmup_midpoints() == want
    finally:
        TpuServingEngine.reset_instances()


@pytest.mark.parametrize("model, kw", [
    ("deepseek-tiny", dict(prefill_batch=1)),
    ("trinity-tiny", dict(kv_block_size=8)),
    ("evabyte-tiny", dict(kv_block_size=8)),
])
def test_a_family_s_engine_serves_the_same_tokens_from_a_midpoint_bucket(
        model, kw, monkeypatch):
    """The rule's threshold brought down to two of the tiny windows (as
    4,096 is two of EvaByte's): a prompt of 70 rows goes in the 96 bucket,
    which the warm-up loaded, and streams what it streams from the 128
    one."""
    prompt = list(range(5, 75))

    async def main():
        engine = _engine(model=model, max_seq_len=256, decode_chunk=8,
                         decode_chunk_light=4, **kw)
        try:
            await engine.warmup()
            warm = _prefills(engine)
            out = await engine.generate(
                prompt, {"max-tokens": 40, "temperature": 0})
            bucket = [s for s in engine.flight.recent(0)
                      if s["phase"] == "prefill"][-1]["bucket"]
            return out["tokens"], bucket, warm, _prefills(engine)
        finally:
            await engine.close()
            TpuServingEngine.reset_instances()

    want, bucket, _warm, _after = asyncio.run(main())
    assert bucket == 128
    monkeypatch.setattr(engine_module, "_PREFILL_MIDPOINTS_ABOVE", 64)
    got, bucket, warm, after = asyncio.run(main())
    assert bucket == 96 and got == want
    assert repr((GREEDY, 96, 1)) in warm and repr((GREEDY, 192, 1)) in warm
    assert after == warm
