"""With ``paged_kernel="xla"`` the commit is the parent's scatter to the
letter: the tiny presets' prefill and decode programs lower to the text they
had before ``write_rows`` took a selection (PR 47's tree, commit 08a2773;
``tests/fixtures/lowered_programs_pr47.json`` holds a hash a program, made by
the same walk on that tree). A cell whose pool the kernel cannot move
(``mistral7b-chat-sat``'s int8 pool) serves these forms."""

import asyncio
import hashlib
import json
import os

import pytest

from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

DUMP = os.path.join(os.path.dirname(__file__), "fixtures",
                    "lowered_programs_pr47.json")
PRESETS = ["tiny", "hybrid-tiny", "granite-tiny", "solar-tiny",
           "deepseek-tiny", "trinity-tiny", "mellum-tiny"]


def all_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it (jit, scan,
    cond, a kernel's body), in order."""
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else [value]:
                if hasattr(inner, "eqns") or hasattr(inner, "jaxpr"):
                    yield from all_eqns(inner)


#: ``pool_commit`` calls by program, of the last :func:`lowered_programs`
calls: dict[str, int] = {}


def lowered_programs(model: str, **config):
    """``({program: hash of its lowered text}, {program: its ops' scopes name
    the commit's kernel}, the engine's stats, the tokens)`` of the prefill and
    decode programs one short greedy generation dispatches."""
    found: dict[str, str] = {}
    scoped: dict[str, bool] = {}
    calls.clear()

    async def main():
        engine = TpuServingEngine(ServingConfig(
            model=model, model_dtype="float32", slots=4, max_seq_len=256,
            kv_block_size=8, prefix_cache=False, decode_chunk=8,
            decode_chunk_light=4, warmup_on_start=False,
            **({"prefill_batch": 1} if model == "deepseek-tiny" else {}),
            **config))
        try:
            for name in ("_make_prefill", "_make_decode"):
                make = getattr(engine, name)

                def wrapped(*key, make=make, name=name):
                    fn = make(*key)

                    def call(*args, **kw):
                        program = f"{model}:{name[6:]}:{key}"
                        if program not in found:
                            lowered = fn.lower(*args, **kw)
                            found[program] = hashlib.sha256(
                                lowered.as_text().encode()).hexdigest()[:16]
                            # the kernel's call under the commit's scope
                            scoped[program] = "kv_commit/pool_commit" in \
                                lowered.as_text(debug_info=True)
                            calls[program] = sum(
                                eqn.primitive.name == "pallas_call"
                                and eqn.params["name"] == "pool_commit"
                                for eqn in all_eqns(fn.trace(*args, **kw).jaxpr))
                        return fn(*args, **kw)

                    return call

                setattr(engine, name, wrapped)
            out = await engine.generate(
                "a prompt of a few words", {"max-tokens": 12, "temperature": 0})
            return engine.stats(), out["tokens"]
        finally:
            await engine.close()

    stats, tokens = asyncio.run(main())
    return found, scoped, stats, tokens


@pytest.mark.parametrize("model", PRESETS)
def test_with_xla_the_programs_lower_to_the_parents_text(model):
    with open(DUMP) as f:
        parents = {k: v for k, v in json.load(f).items()
                   if k.startswith(model + ":")}
    got, scoped, stats, _ = lowered_programs(model, paged_kernel="xla")
    assert stats["pool_commit_kernel"] == "xla" and not any(scoped.values())
    assert len(parents) == 2 and got == parents


#: pool kinds of a family's programs: K and V of a kind are ONE call
KINDS = {"tiny": 1, "hybrid-tiny": 1, "deepseek-tiny": 1}


@pytest.mark.parametrize("model", sorted(KINDS))
def test_the_one_selection_reaches_every_program_s_commit(model):
    """One member of three families (the fourth's engine under the same
    selection: tests/test_swa_engine.py): handed a kernel (interpreted here)
    every prefill and decode program commits through ``pool_commit`` under
    the scope ``kv_commit``, ONE call a pool kind (K and V together: what is
    traced and lowered is paid at every set-up), the engine reports the form
    beside its other kernels', and the tokens are the scatter's."""
    got, scoped, stats, tokens = lowered_programs(
        model, paged_kernel="pallas-interpret")
    assert stats["pool_commit_kernel"] == "pallas-interpret"
    assert len(scoped) == 2 and all(scoped.values()), scoped
    assert list(calls.values()) == [KINDS[model]] * 2, calls
    _, _, xla_stats, xla_tokens = lowered_programs(model, paged_kernel="xla")
    assert xla_stats["pool_commit_kernel"] == "xla"
    assert not any(calls.values()), calls
    assert tokens == xla_tokens and len(tokens) == 12


def _a_pair_s_commit(form, kernel="pallas"):
    import jax
    import jax.numpy as jnp

    from langstream_tpu.models.paged import write_rows_pair

    on = jax.ShapeDtypeStruct
    pool, rows = (on((24, 41, 64, 1024), jnp.bfloat16),
                  on((24, 4, 512, 1024), jnp.bfloat16))

    def commit(pool_k, pool_v, ks, vs, tables, starts, valid):
        return write_rows_pair(
            (pool_k, pool_v), (ks, vs), tables,
            None if form == "aligned" else starts, valid, kernel)

    return jax.jit(commit).trace(
        pool, pool, rows, rows, on((4, 32), jnp.int32), on((4,), jnp.int32),
        on((4, 512), jnp.bool_))


#: what a pair's commit traces to today, kernel and all that leads to it
#: (PR 49: aligned 90, shifted 113; PR 48's was 324 a POOL) plus a tenth: a
#: program's first warm use is its trace and lowering, at every set-up of
#: every cell, and an equation here is in every program
EQUATIONS_MOST = {"aligned": 99, "shifted": 124}


@pytest.mark.parametrize("form", sorted(EQUATIONS_MOST))
def test_a_pair_s_commit_stays_a_hundred_equations(form):
    """Counted, not timed: the commit of K and V is one ``pallas_call`` whose
    jaxpr, with what prepares its operands, stays under the ceiling set when
    Gate 1 (``tools/commit_probe.py --lowering``) passed; the aligned form
    (a prefill's: ``starts`` None) holds no rotate and no second tile of
    rows, which only a shifted commit needs."""
    eqns = list(all_eqns(_a_pair_s_commit(form).jaxpr))
    names = [eqn.primitive.name for eqn in eqns]
    assert names.count("pallas_call") == 1
    assert len(eqns) <= EQUATIONS_MOST[form], len(eqns)
    assert ("roll" in names) == (form == "shifted")
    # K and V: two tiles of rows (aligned: one) and the pool's for a merge,
    # its store, and (aligned) the direct copy; ONE wait whatever was started
    assert names.count("dma_start") == 8
    assert names.count("dma_wait") == (4 if form == "aligned" else 2)


def test_a_pair_s_commit_lowers_to_one_custom_call_for_a_tpu():
    """The lowered text of a program's K and V commit, for a TPU from here:
    ONE Mosaic call named ``pool_commit`` (no ``paged`` in its name: the
    benchmark's read-kernel metric tells kernels by name), both pools
    aliased to its outputs, no scatter."""
    for form in EQUATIONS_MOST:
        text = _a_pair_s_commit(form).lower(
            lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == 1
        assert text.count("pool_commit") == 1 and "paged" not in text
        assert "scatter" not in text
        assert "output_operand_alias" in text
