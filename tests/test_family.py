"""The seam between the engine and a program family (``models/family.py``).

A fourth family, defined in THIS file alone, is found by the engine's one
lookup and served through a prefill and two decode chunks with the greedy
tokens of the family whose model functions it wraps, and nothing in
``serving/engine.py`` knows its name. Three of them, one for each shape of
what stays on the device: three residents with a recurrent state, two with
nothing in the value pool's place, three with a second kind of pool. And
every name the three real families serve resolves to the configuration the
engine's table resolved it to before the table went.
"""

import asyncio
import sys
import types

import pytest

from langstream_tpu.models import family as family_mod
from langstream_tpu.models import eva, hybrid, latent, swa
from langstream_tpu.models.family import Family
from langstream_tpu.models.paged import PagedLayout, init_kv_pool, init_latent_pool
from langstream_tpu.serving import engine as engine_mod
from langstream_tpu.serving.engine import (
    ServingConfig,
    TpuServingEngine,
    _family_of,
    _resolve_model_config,
)

PROMPTS = [list(range(5, 5 + n)) for n in (9, 40, 23)]
NOTHING_REFUSED = dict(what="is a test's", refusals={})


# --- three residents, the third a recurrent state ---------------------------

def _state_prefill(mc, params, residents, tokens, lengths, sel,
                   use_flash=None, kernel=None):
    cache_k, cache_v, state = residents
    tables, slot_ids = sel
    logits, ck, cv, st, _ = hybrid.hybrid_prefill_paged(
        mc, params, tokens, lengths, cache_k, cache_v, state, tables,
        slot_ids, use_flash=use_flash, kernel=kernel)
    return logits, (ck, cv, st)


def _state_decode(mc, params, residents, tokens, lengths, active, tables,
                  sample_fn, key, steps, **kernels):
    return hybrid.hybrid_decode_chunk_paged(
        mc, params, tokens, lengths, active, *residents, tables, sample_fn,
        key, steps, **kernels)


WITH_A_STATE = Family(
    name="fourth-state", config_class=hybrid.HybridConfig,
    presets={"fourth-state-tiny": "tiny"}, **NOTHING_REFUSED,
    init_params=hybrid.init_hybrid_params,
    init_pools=lambda mc, layout, slots: (
        lambda: hybrid.init_hybrid_pool(mc, layout),
        lambda: hybrid.init_hybrid_state(mc, slots)),
    prefill=_state_prefill, decode_chunk=_state_decode,
    residents=3, donate=(1, 2, 3),
    prefill_selects_slots=True, state_kernels=True,
)


# --- two residents, the second None -----------------------------------------

def _none_prefill(mc, params, residents, tokens, lengths, tables,
                  use_flash=None, kernel=None):
    pool, nothing = residents
    logits, pool, _ = latent.latent_prefill_paged(
        mc, params, tokens, lengths, pool, tables, use_flash=use_flash)
    return logits, (pool, nothing)


def _none_decode(mc, params, residents, tokens, lengths, active, tables,
                 sample_fn, key, steps, **kernels):
    pool, nothing = residents
    return latent.latent_decode_chunk_paged(
        mc, params, tokens, lengths, active, pool, tables, sample_fn, key,
        steps, **kernels) + (nothing,)


WITH_A_NONE = Family(
    name="fourth-none", config_class=latent.LatentConfig,
    presets={"fourth-none-tiny": "tiny"}, **NOTHING_REFUSED,
    init_params=latent.init_latent_params,
    init_pools=lambda mc, layout, slots: (
        lambda: init_latent_pool(mc, layout), None),
    prefill=_none_prefill, decode_chunk=_none_decode,
    residents=2, donate=(1,), one_decode_window=True,
)


# --- three residents, the third a second kind of pool ------------------------

def _ring(mc, layout, slots):
    ring = mc.ring_blocks(layout.block_size)
    return {"window_ring": ring, "window_layout": PagedLayout(
        block_size=layout.block_size, num_blocks=slots * ring + 1,
        max_blocks_per_slot=layout.max_blocks_per_slot)}


def _pool_prefill(mc, params, residents, tokens, lengths, tables,
                  use_flash=None, kernel=None):
    logits, ck, cv, wp, _ = swa.swa_prefill_paged(
        mc, params, tokens, lengths, *residents, tables, use_flash=use_flash)
    return logits, (ck, cv, wp)


def _pool_decode(mc, params, residents, tokens, lengths, active, tables,
                 sample_fn, key, steps, **kernels):
    return swa.swa_decode_chunk_paged(
        mc, params, tokens, lengths, active, *residents, tables, sample_fn,
        key, steps, **kernels)


WITH_A_SECOND_POOL = Family(
    name="fourth-pool", config_class=swa.SwaConfig,
    presets={"fourth-pool-tiny": "tiny"}, **NOTHING_REFUSED,
    init_params=swa.init_swa_params,
    init_pools=lambda mc, layout, slots: (
        lambda: init_kv_pool(mc, layout, mc.full_layers),
        lambda: dict(zip("kv", init_kv_pool(
            mc, _ring(mc, layout, slots)["window_layout"],
            mc.window_layers)))),
    prefill=_pool_prefill, decode_chunk=_pool_decode,
    residents=3, donate=(1, 2, 3), block_manager_kwargs=_ring,
    one_decode_window=True,
)


FOURTH = {  # the test's family, and the real one whose functions it wraps
    "a state": (WITH_A_STATE, "hybrid-tiny"),
    "a None": (WITH_A_NONE, "deepseek-tiny"),
    "a second pool": (WITH_A_SECOND_POOL, "trinity-tiny"),
}


@pytest.fixture
def registered(monkeypatch):
    """What a new family's PR does: a module that ends in ``FAMILY``, and its
    name in ``family.MODULES``."""
    def register(fam):
        module = f"a_test_s_family_{fam.name.replace('-', '_')}"
        monkeypatch.setitem(
            sys.modules, module, types.SimpleNamespace(FAMILY=fam))
        monkeypatch.setattr(
            family_mod, "MODULES", family_mod.MODULES + [module])
        return fam

    return register


async def _streams(model):
    engine = TpuServingEngine(ServingConfig(
        model=model, model_dtype="float32", slots=4, max_seq_len=128,
        kv_layout="paged", kv_block_size=8, prefix_cache=False,
        prefill_batch=1, decode_chunk=4, decode_chunk_light=4,
    ))
    try:
        # a prefill's token and two decode chunks of four
        outs = await asyncio.gather(*(
            engine.generate(p, {"max-tokens": 9, "temperature": 0})
            for p in PROMPTS))
        return [o["tokens"] for o in outs], {
            "decodes": sum(1 for s in engine.flight.recent(1000)
                           if s["phase"] == "decode"),
            "family": (engine._fam, engine.family),
            # what stays on the device, where the engine's loop expects it
            "nothing_behind": (engine.cache_v is None, engine.state is None),
            "state_kernel": engine.ssm_state_kernel,
        }
    finally:
        await engine.close()


@pytest.mark.parametrize("shape", sorted(FOURTH))
def test_a_family_defined_here_alone_is_served_as_the_one_it_wraps(
        run_async, registered, shape):
    fam, wrapped = FOURTH[shape]
    registered(fam)
    name, = fam.presets
    assert _family_of(name) is fam
    assert _resolve_model_config(name, 128) == _resolve_model_config(wrapped, 128)
    assert name not in open(engine_mod.__file__).read()

    theirs, _ = run_async(_streams(wrapped))
    ours, facts = run_async(_streams(name))
    assert facts["family"] == (fam, fam.name)
    assert facts["decodes"] >= 2 and all(len(t) == 9 for t in ours)
    assert ours == theirs
    assert facts["nothing_behind"] == (fam.residents == 2,) * 2
    assert facts["state_kernel"] == ("xla" if fam.state_kernels else None)


async def _long_and_short(model, kernel):
    """A prompt of 70 tokens (a prefill of 128 rows) and one of 20 (of 32)
    through an engine under ``kernel``."""
    engine = TpuServingEngine(ServingConfig(
        model=model, model_dtype="float32", slots=2, max_seq_len=128,
        kv_layout="paged", kv_block_size=8, prefix_cache=False,
        prefill_batch=1, decode_chunk=4, decode_chunk_light=4,
        paged_kernel=kernel,
    ))
    try:
        outs = [await engine.generate(
            list(range(5, 5 + n)), {"max-tokens": 5, "temperature": 0})
            for n in (70, 20)]
        return [o["tokens"] for o in outs], engine.stats()
    finally:
        await engine.close()


@pytest.mark.parametrize("model, form", [
    ("mellum-tiny", "pallas-interpret"),    # every expert held: the kernel
    ("hybrid-tiny", "xla"), ("deepseek-tiny", "xla")])      # a share: the loop
def test_the_one_selection_reaches_the_routed_pass_of_a_whole_layer(
        run_async, monkeypatch, model, form):
    """``paged_kernel`` is the routed experts' selection too: every family's
    prefill is handed it, ``models/moe.py`` ``grouped_form`` takes the kernel
    where every expert is held and keeps the loop at a share, and the engine
    reports the form taken and how many prefill programs had the rows for it
    (more than 32 here, for the test's time: 256 and 512 as served). The
    kernel (interpreted here) streams what the loop streams."""
    from langstream_tpu.models import moe

    monkeypatch.setattr(moe, "DENSE_ROWS_MAX", 32)
    monkeypatch.setattr(moe, "LOOP_DENSE_ROWS_MAX", 32)
    kernel, stats = run_async(_long_and_short(model, "pallas-interpret"))
    assert stats["moe_grouped_kernel"] == form
    assert (stats["prefill_dispatches"], stats["prefill_dispatches_grouped"]) == (2, 1)
    assert all(len(t) == 5 for t in kernel)
    if form != "xla":      # the loop's engine, once
        loop, stats = run_async(_long_and_short(model, "auto"))
        assert stats["moe_grouped_kernel"] == "xla"      # auto, on the CPU
        assert (stats["prefill_dispatches"], stats["prefill_dispatches_grouped"]) == (2, 1)
        assert kernel == loop


# name -> (family, classmethod): the engine's table as PR 43 had it
TABLE_BEFORE = {
    "hybrid-tiny": ("hybrid", "tiny"),
    "nemotron-3-nano-30b-a3b-ep8": ("hybrid", "nemotron3_nano_ep8"),
    "granite-tiny": ("hybrid", "granite_tiny"),
    "granite-4.0-h-small-ep2": ("hybrid", "granite4_h_small_ep2"),
    "solar-tiny": ("hybrid", "solar_tiny"),
    "solar-open2-250b-ep8": ("hybrid", "solar_open2_ep8"),
    "deepseek-tiny": ("latent", "tiny"),
    "deepseek-v2-ep8": ("latent", "deepseek_v2_ep8"),
    "trinity-tiny": ("swa", "tiny"),
    "trinity-large-preview-ep8": ("swa", "trinity_large_preview_ep8"),
    # PR 46: the first names to land in a family that was there
    "mellum-tiny": ("swa", "mellum_tiny"),
    "mellum2-12b-a2.5b-8l": ("swa", "mellum2_12b_a2_5b_8l"),
    # PR 50: the first family added as a module and a line of ``MODULES``
    "evabyte-tiny": ("eva", "tiny"),
    "evabyte-6.5b-8l": ("eva", "evabyte_6_5b_8l"),
}
CONFIG_CLASS = {"hybrid": hybrid.HybridConfig, "latent": latent.LatentConfig,
                "swa": swa.SwaConfig, "eva": eva.EvaConfig}


def test_the_four_modules_serve_the_table_s_names_and_no_other():
    served = {name: (fam.name, method) for fam in family_mod.families()
              for name, method in fam.presets.items()}
    assert served == TABLE_BEFORE


@pytest.mark.parametrize("name", sorted(TABLE_BEFORE))
def test_a_served_name_resolves_to_the_configuration_it_did(name):
    family, method = TABLE_BEFORE[name]
    fam = _family_of(name)
    assert fam.name == family and fam.config_class is CONFIG_CLASS[family]
    # field for field: the configurations are frozen dataclasses
    assert _resolve_model_config(name, 384) == \
        getattr(CONFIG_CLASS[family], method)(max_seq_len=384)


def test_a_family_s_name_is_its_family_s_whoever_else_registers_it(monkeypatch):
    """``bench/run.py`` writes every cell's name into ``_MODEL_CONFIGS``,
    Llama-shaped, a family's cell's too: the family's own answer stands."""
    from langstream_tpu.models.llama import LlamaConfig

    monkeypatch.setitem(engine_mod._MODEL_CONFIGS, "granite-tiny", LlamaConfig.tiny)
    assert _resolve_model_config("granite-tiny", 128) == \
        hybrid.HybridConfig.granite_tiny(max_seq_len=128)
    # and a name of the engine's own asks no family
    assert _family_of("tiny") is None and _family_of("moe-tiny") is None
    with pytest.raises(ValueError) as e:
        _resolve_model_config("no-such-model", 128)
    assert all(n in str(e.value) for n in list(TABLE_BEFORE) + ["tiny", "moe-tiny"])
