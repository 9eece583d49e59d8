"""Prompt-lookup speculative decoding (paged).

The invariants everything rests on: greedy acceptance emits only tokens the
model's own argmax produces, so greedy speculative streams are IDENTICAL to
plain decode; sampled requests use rejection sampling against the filtered
target distribution, so their streams are distributed EXACTLY as plain
sampling — speculation changes tokens-per-forward, never content (greedy)
or distribution (sampled). No reference analogue (completions were SaaS
calls); this is in-tree serving tech on the TPU engine.
"""

import asyncio
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(autouse=True)
def _fresh_engines():
    from langstream_tpu.serving.engine import TpuServingEngine

    TpuServingEngine.reset_instances()
    yield
    TpuServingEngine.reset_instances()


def greedy(logits, key):
    t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return t, jnp.zeros_like(t, dtype=jnp.float32)


# ---------------------------------------------------------------------------
# verify chunk (model level, f32 for exactness)
# ---------------------------------------------------------------------------


def test_verify_chunk_acceptance_semantics():
    """Correct drafts advance len(drafts)+1 in one forward; wrong drafts
    degrade to exactly one plain greedy step; the committed cache continues
    the reference stream either way."""
    from langstream_tpu.models.llama import LlamaConfig, init_llama_params
    from langstream_tpu.models.llama_paged import (
        llama_decode_chunk_paged,
        llama_prefill_paged,
        llama_verify_chunk_paged,
    )
    from langstream_tpu.models.paged import (
        BlockManager,
        PagedLayout,
        init_paged_kv_cache,
    )

    c = dataclasses.replace(LlamaConfig.tiny(max_seq_len=128), dtype=jnp.float32)
    params = init_llama_params(c, jax.random.PRNGKey(5))
    layout = PagedLayout.for_model(128, 2, block_size=16)
    prompt = jnp.array([[5, 9, 17, 3, 11, 2, 7, 1]], jnp.int32)
    n = 8

    def fresh():
        bm = BlockManager(layout, 2)
        bm.admit(0, 40)
        bm.ensure_capacity(0, 24)
        pk, pv = init_paged_kv_cache(c, layout)
        t = jnp.asarray(bm.tables[[0]])
        logits, pk, pv = llama_prefill_paged(
            c, params, prompt, jnp.array([n]), pk, pv, t, use_flash=False
        )
        return logits, pk, pv, t

    # reference greedy continuation
    logits, pk, pv, t = fresh()
    tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    ct, _, _, _, pk, pv = llama_decode_chunk_paged(
        c, params, tok0, jnp.array([n]), jnp.array([True]), pk, pv, t,
        greedy, jax.random.PRNGKey(0), 6, num_read_blocks=2,
    )
    ref = [int(tok0[0])] + [int(x) for x in np.asarray(ct)[:, 0]]

    # all-correct drafts: adv = drafts+1, emits = ref continuation
    _, pk2, pv2, t2 = fresh()
    good = jnp.array([[ref[0]] + ref[1:5]], jnp.int32)
    em, adv, nxt, nl, pk2, pv2, _ = llama_verify_chunk_paged(
        c, params, good, jnp.array([n]), jnp.array([True]), pk2, pv2, t2, 2
    )
    assert int(adv[0]) == 5
    assert [int(x) for x in np.asarray(em)[0]] == ref[1:6]
    assert int(nxt[0]) == ref[5] and int(nl[0]) == n + 5
    # the committed cache continues the reference stream
    ct2, _, _, _, _, _ = llama_decode_chunk_paged(
        c, params, jnp.asarray([ref[5]]), jnp.array([n + 5]),
        jnp.array([True]), pk2, pv2, t2, greedy, jax.random.PRNGKey(0), 1,
        num_read_blocks=2,
    )
    assert int(np.asarray(ct2)[0, 0]) == ref[6]

    # wrong drafts: exactly one plain step
    _, pk3, pv3, t3 = fresh()
    wrong = jnp.array([[ref[0], 333, 334, 335, 336]], jnp.int32)
    em, adv, nxt, nl, _, _, _ = llama_verify_chunk_paged(
        c, params, wrong, jnp.array([n]), jnp.array([True]), pk3, pv3, t3, 2
    )
    assert int(adv[0]) == 1
    assert int(np.asarray(em)[0, 0]) == ref[1] and int(nl[0]) == n + 1


# ---------------------------------------------------------------------------
# engine level
# ---------------------------------------------------------------------------

BASE = dict(
    model="tiny", slots=4, max_seq_len=256, decode_chunk=4,
    kv_layout="paged", kv_block_size=16, paged_kernel="xla",
    # f32 for exactness (same reason as the model-level tests above):
    # the identical-streams invariant is bitwise, and bf16 near-tie
    # argmax can flip between the differently-shaped decode and verify
    # programs depending on the backend's fusion choices
    model_dtype="float32",
)
REPETITIVE = "the cat sat on the mat. " * 6


def _gen(cfg_kwargs, prompt, options):
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def run():
        eng = TpuServingEngine(ServingConfig(**cfg_kwargs))
        try:
            out = await eng.generate(prompt, options)
            return out, eng.stats()
        finally:
            await eng.close()

    return asyncio.run(run())


def test_speculative_stream_identical_and_accepts():
    r0, _ = _gen(BASE, REPETITIVE, {"max-tokens": 24})
    r1, stats = _gen(
        {**BASE, "speculative_drafts": 4}, REPETITIVE, {"max-tokens": 24}
    )
    assert r0["tokens"] == r1["tokens"]
    assert stats["speculative"]["steps"] > 0
    # repetitive text: fewer forwards than tokens (drafts accepted)
    assert stats["speculative"]["drafts_accepted"] > 0
    assert stats["speculative"]["steps"] < 24


def test_speculative_sampled_requests_speculate():
    """Non-greedy requests ALSO speculate (rejection sampling against the
    filtered target); on a repetitive workload drafts land and steps are
    fewer than tokens."""
    r, stats = _gen(
        {**BASE, "speculative_drafts": 4},
        REPETITIVE,
        {"max-tokens": 12, "temperature": 0.8, "top-k": 20},
    )
    assert len(r["tokens"]) > 0
    assert stats["speculative"]["steps"] > 0


def test_speculative_penalty_requests_fall_back():
    """Presence/frequency penalties change the distribution per EMITTED
    token — the verify step has no running counts, so these route to the
    plain decode burst and must still complete."""
    r, stats = _gen(
        {**BASE, "speculative_drafts": 4},
        REPETITIVE,
        {"max-tokens": 8, "temperature": 0.8, "presence-penalty": 0.5},
    )
    assert len(r["tokens"]) > 0
    assert stats["speculative"]["steps"] == 0


def test_speculative_accept_first_token_distribution_exact():
    """The rejection sampler is distribution-exact for a deterministic
    drafter: over many keys, the first emitted token's histogram matches
    direct sampling from the filtered target (and, conditional on the
    first draft surviving, the second position matches too)."""
    from langstream_tpu.serving.sampler import (
        filtered_logits,
        speculative_accept,
    )

    V, D1 = 8, 3
    rng = np.random.RandomState(0)
    logits_np = rng.randn(1, D1, V) * 2.0
    logits = jnp.asarray(logits_np, jnp.float32)
    # draft 0 = the mode of position 0 so acceptance is common enough to
    # measure the conditional position-1 histogram; draft 1 arbitrary
    drafts = jnp.array([[int(logits_np[0, 0].argmax()), 5]], jnp.int32)
    temps = jnp.array([0.9], jnp.float32)
    topks = jnp.array([0], jnp.int32)
    topps = jnp.array([1.0], jnp.float32)

    N = 8000
    keys = jax.random.split(jax.random.PRNGKey(1), N)

    def step(key):
        acc, fb = speculative_accept(
            logits, drafts, key, temps, topks, topps,
            use_top_p=False, use_top_k=False,
        )
        first = jnp.where(acc[0] >= 1, drafts[0, 0], fb[0, 0])
        second = jnp.where(acc[0] >= 2, drafts[0, 1], fb[0, 1])
        return first, second, acc[0]

    firsts, seconds, accs = jax.vmap(step)(keys)
    firsts, seconds, accs = map(np.asarray, (firsts, seconds, accs))

    def target(pos):
        return np.asarray(
            jax.nn.softmax(
                filtered_logits(logits[:, pos], temps, topks, use_top_k=False)
            )
        )[0]

    hist1 = np.bincount(firsts, minlength=V) / N
    np.testing.assert_allclose(hist1, target(0), atol=0.03)
    # conditional on draft 0 surviving, position 1 must follow its target
    sel = accs >= 1
    assert sel.sum() > 500  # the drafted token has real mass under seed 0
    hist2 = np.bincount(seconds[sel], minlength=V) / sel.sum()
    np.testing.assert_allclose(hist2, target(1), atol=0.05)


def test_sampled_verify_greedy_rows_degenerate_to_argmax():
    """A greedy row inside the SAMPLED verify variant (mixed batch) must
    behave exactly like the pure-greedy variant: acceptance is
    draft == argmax and every fallback is the argmax."""
    from langstream_tpu.models.llama import LlamaConfig, init_llama_params
    from langstream_tpu.models.llama_paged import (
        llama_prefill_paged,
        llama_verify_chunk_paged,
    )
    from langstream_tpu.models.paged import (
        BlockManager,
        PagedLayout,
        init_paged_kv_cache,
    )

    c = dataclasses.replace(LlamaConfig.tiny(max_seq_len=128), dtype=jnp.float32)
    params = init_llama_params(c, jax.random.PRNGKey(5))
    layout = PagedLayout.for_model(128, 2, block_size=16)
    prompt = jnp.array([[5, 9, 17, 3, 11, 2, 7, 1]], jnp.int32)
    n = 8
    drafts = jnp.array([[1, 333, 334, 335, 336]], jnp.int32)

    def verify(sampler_mode):
        bm = BlockManager(layout, 2)
        bm.admit(0, 40)
        bm.ensure_capacity(0, 24)
        pk, pv = init_paged_kv_cache(c, layout)
        t = jnp.asarray(bm.tables[[0]])
        logits, pk, pv = llama_prefill_paged(
            c, params, prompt, jnp.array([n]), pk, pv, t, use_flash=False
        )
        tokens = drafts.at[0, 0].set(jnp.argmax(logits[0]).astype(jnp.int32))
        return llama_verify_chunk_paged(
            c, params, tokens, jnp.array([n]), jnp.array([True]), pk, pv,
            t, 2, key=jax.random.PRNGKey(7),
            temps=jnp.array([0.0], jnp.float32),
            topks=jnp.array([0], jnp.int32),
            topps=jnp.array([1.0], jnp.float32),
            sampler_mode=sampler_mode,
        )

    em_g, adv_g, nxt_g, nl_g, _, _, _ = verify((False, False, True))
    em_s, adv_s, nxt_s, nl_s, _, _, _ = verify((False, False, False))
    a = int(adv_g[0])
    assert int(adv_s[0]) == a
    assert int(nxt_s[0]) == int(nxt_g[0]) and int(nl_s[0]) == int(nl_g[0])
    # only the first adv positions are ever read by the engine
    assert (
        np.asarray(em_s)[0, :a].tolist() == np.asarray(em_g)[0, :a].tolist()
    )


def test_speculative_concurrent_requests_complete():
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    async def main():
        eng = TpuServingEngine(
            ServingConfig(**{**BASE, "speculative_drafts": 4})
        )
        try:
            outs = await asyncio.gather(
                *(
                    eng.generate(REPETITIVE + f" q{i}", {"max-tokens": 10})
                    for i in range(6)
                )
            )
        finally:
            await eng.close()
        assert all(len(o["tokens"]) == 10 for o in outs)

    asyncio.run(main())


def test_speculative_with_chunked_prefill_and_prefix_cache():
    """All three schedulers at once: a long prompt chunk-prefills while
    another slot decodes speculatively; the verify step's commits must not
    touch the mid-prefill slot's blocks (inactive rows redirect to
    scratch). Both streams must equal a plain engine's."""
    from langstream_tpu.serving.engine import ServingConfig, TpuServingEngine

    short = REPETITIVE
    long_ = "copy this exact phrase again and again. " * 24

    def run(spec, chunk):
        async def main():
            eng = TpuServingEngine(
                ServingConfig(
                    model="tiny", slots=4, max_seq_len=2048, decode_chunk=2,
                    kv_layout="paged", kv_block_size=16, paged_kernel="xla",
                    speculative_drafts=spec, prefill_chunk=chunk,
                    prefix_cache=True, model_dtype="float32",
                )
            )
            try:
                short_task = asyncio.ensure_future(
                    eng.generate(short, {"max-tokens": 24})
                )
                await asyncio.sleep(0.05)  # short request starts decoding
                long_out = await eng.generate(long_, {"max-tokens": 12})
                short_out = await short_task
            finally:
                await eng.close()
            return short_out["tokens"], long_out["tokens"]

        return asyncio.run(main())

    plain = run(0, 0)
    combined = run(4, 64)
    assert plain[0][:8] == combined[0][:8]   # short stream unchanged
    assert plain[1][:8] == combined[1][:8]   # long stream unchanged


def test_speculative_at_context_cap_matches_plain():
    """Near max_seq_len, a verify chunk wider than the remaining room must
    not write past the cap (write_rows' block clamp would overwrite
    committed rows in the slot's last block): streams stay identical to
    plain greedy decode right up to the forced stop."""
    cfg = dict(
        model="tiny", slots=2, max_seq_len=64, decode_chunk=2,
        kv_layout="paged", kv_block_size=16, paged_kernel="xla",
        kv_pool_blocks=12,  # room for a full-context request + scratch
        model_dtype="float32",  # bitwise stream comparison (see BASE)
    )
    # prompt long enough that generation runs into the context cap
    prompt = "the cat sat on the mat. the cat sat on the "
    r0, _ = _gen(cfg, prompt, {"max-tokens": 60})
    r1, _ = _gen({**cfg, "speculative_drafts": 4}, prompt, {"max-tokens": 60})
    assert r0["tokens"] == r1["tokens"]


def test_speculative_with_pallas_interpret_kernel():
    """The engine's speculative path with the multi-query Pallas kernel
    (interpret mode) produces the same stream as the XLA path."""
    r0, _ = _gen(BASE, REPETITIVE, {"max-tokens": 12})
    r1, stats = _gen(
        {**BASE, "speculative_drafts": 4, "paged_kernel": "pallas-interpret"},
        REPETITIVE,
        {"max-tokens": 12},
    )
    assert r0["tokens"] == r1["tokens"]
    assert stats["speculative"]["steps"] > 0
