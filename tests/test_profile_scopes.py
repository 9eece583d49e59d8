"""What a profile of the engine carries once the program names its own
work: the host spans (``serving/flight.py`` ``SPANS``) land in the profiler's
own trace with a ``seq`` that is a flight sample's ``dispatch``; the jitted
serving programs carry the scope names of the layer body's seams; and the
scopes are metadata only — the optimised HLO is the same instruction for
instruction with and without them."""

import asyncio
import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.serving.flight import SPANS

LAYER_SCOPES = ("embed", "attn_qkv", "kv_read", "attn_out", "ffn", "lm_head",
                "sample")


def tiny_engine(**kw):
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    return TpuServingEngine(ServingConfig(
        model="tiny", model_dtype="float32", slots=4, max_seq_len=128,
        decode_chunk=4, kv_layout="paged", kv_block_size=16,
        prefix_cache=False, **kw,
    ))


# -- (2) a real capture, on the CPU backend ------------------------------


def host_spans(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("ls."):
                    spans.append((e.name, dict(e.stats)))
    return spans


def test_a_capture_holds_the_engine_loop_s_spans(run_async, tmp_path):
    async def main():
        engine = tiny_engine()
        try:
            # compile outside the capture, so it holds serving and nothing else
            await engine.generate("warm the shapes", {"max-tokens": 6})
            with jax.profiler.trace(str(tmp_path)):
                await asyncio.gather(*(
                    engine.generate(f"span prompt {i}", {"max-tokens": 6})
                    for i in range(3)
                ))
        finally:
            # closing drains the chunk the pipelined burst left in flight,
            # so every dispatch the capture saw has its sample
            await engine.close()
        return engine.flight.recent(0)

    samples = run_async(main())
    spans = host_spans(str(tmp_path))
    names = {name for name, _ in spans}
    # nothing outside the documented vocabulary
    assert names <= set(SPANS), names - set(SPANS)
    assert {"ls.admit", "ls.prefill.pack", "ls.prefill.dispatch",
            "ls.prefill.fetch", "ls.prefill.emit", "ls.decode.prepare",
            "ls.decode.dispatch", "ls.decode.fetch", "ls.decode.process",
            "ls.decode.emit"} <= names
    dispatched = {s["dispatch"]: s for s in samples if "dispatch" in s}
    for wanted, phase in (("ls.decode.dispatch", "decode"),
                          ("ls.decode.fetch", "decode"),
                          ("ls.decode.process", "decode"),
                          ("ls.prefill.dispatch", "prefill"),
                          ("ls.prefill.fetch", "prefill")):
        seqs = [meta["seq"] for name, meta in spans if name == wanted]
        assert seqs, wanted
        # a span, its flight sample and its program share an identifier
        assert all(dispatched[seq]["phase"] == phase for seq in seqs), wanted
    for name, meta in spans:
        if name == "ls.decode.dispatch":
            sample = dispatched[meta["seq"]]
            assert meta["program"] == sample["program"]
            assert meta["steps"] == sample["steps"] > 0


# -- (3) the scope names in the lowered programs -------------------------


def programs(engine):
    """(name, jitted function, arguments) of the engine's serving programs
    at the shapes a first request would use."""
    slots = engine.config.slots
    greedy = engine._sampler_mode(
        np.zeros(1, np.float32), np.zeros(1, np.int32), np.ones(1, np.float32))
    key = jax.random.PRNGKey(0)
    temps = jnp.zeros(slots, jnp.float32)
    topks = jnp.zeros(slots, jnp.int32)
    topps = jnp.ones(slots, jnp.float32)
    tables = jnp.asarray(engine.block_mgr.tables)
    caches = (engine.params, engine.cache_k, engine.cache_v)
    decode = caches + (
        jnp.zeros(slots, jnp.int32), jnp.ones(slots, jnp.int32),
        jnp.ones(slots, bool), tables, key, temps, topks, topps)
    prefill = caches + (
        jnp.zeros((1, 32), jnp.int32), jnp.full((1,), 5, jnp.int32),
        tables[:1], key, temps[:1], topks[:1], topps[:1])
    cont = caches + (
        jnp.zeros((1, 32), jnp.int32), jnp.full((1,), 16, jnp.int32),
        jnp.full((1,), 5, jnp.int32), tables[:1], key, temps[:1], topks[:1],
        topps[:1])
    return [
        ("decode", engine._decode_fn(greedy, 2, 4, False), decode),
        ("prefill", engine._prefill_fn(greedy), prefill),
        ("prefill-continue", engine._prefill_continue_fn(greedy, 1), cont),
    ]


@pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
def test_the_lowered_programs_carry_every_scope(run_async, kernel):
    async def main():
        engine = tiny_engine(paged_kernel=kernel)
        try:
            return {name: fn.lower(*args).as_text(debug_info=True)
                    for name, fn, args in programs(engine)}
        finally:
            await engine.close()

    texts = run_async(main())
    for name, text in texts.items():
        for scope in LAYER_SCOPES:
            assert re.search(rf'[/"]{scope}/', text), (name, scope)
    if kernel == "pallas-interpret":
        assert "kv_read/paged_read" in texts["decode"]


def test_the_flash_prefill_carries_its_scope_and_kernel_name(monkeypatch):
    from langstream_tpu.models.llama import (
        LlamaConfig, init_llama_params, prefill_forward)

    monkeypatch.setenv("LS_TPU_FLASH", "interpret")
    c = LlamaConfig.tiny(max_seq_len=256)
    params = init_llama_params(c)
    text = jax.jit(lambda p, t, n: prefill_forward(c, p, t, n)).lower(
        params, jnp.zeros((1, 256), jnp.int32), jnp.full((1,), 200, jnp.int32)
    ).as_text(debug_info=True)
    assert "flash/flash_prefill" in text or 'flash/' in text
    assert "flash_prefill" in text


# -- scopes are metadata, not calls --------------------------------------

_METADATA = re.compile(r",? ?metadata=\{[^{}]*\}")


def instructions(hlo_text):
    """The computations of an optimised HLO module, without what only names
    where an instruction came from: the ``metadata={...}`` of each and the
    tables of files and stack frames ahead of the first computation."""
    body = hlo_text[hlo_text.index("\n%"):] if "\n%" in hlo_text else hlo_text
    return _METADATA.sub("", body)


def optimised(engine):
    return {name: instructions(fn.lower(*args).compile().as_text())
            for name, fn, args in programs(engine)}


@pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
def test_scopes_change_no_instruction(run_async, monkeypatch, kernel):
    """The optimised HLO of each serving program with the scope and kernel
    names taken away (as the parent commit had it) is the program with them,
    instruction for instruction."""
    from jax.experimental import pallas as pl

    async def build():
        engine = tiny_engine(paged_kernel=kernel)
        try:
            return optimised(engine)
        finally:
            await engine.close()

    named = run_async(build())
    real_call = pl.pallas_call
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(
        pl, "pallas_call",
        lambda *a, name=None, **kw: real_call(*a, **kw))
    bare = run_async(build())
    assert set(named) == set(bare) == {"decode", "prefill", "prefill-continue"}
    for name in named:
        assert named[name] == bare[name], name
