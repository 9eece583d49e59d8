"""Device-only attribution of the decode-chunk roofline gap.

Times ``llama_decode_chunk`` variants on the real chip with the engine's
bench shape (llama-1b, B=64 slots, window 512, K=96) and ablations that
isolate each suspect:

- int8 vs bf16 weights        → is the dequant fusing, or inflating traffic?
- window sweep (128..1024)    → slope = effective cache read bandwidth;
                                intercept = weights + fixed overhead
- batch sweep (8..64)         → cache traffic scales with B, weights don't
- greedy-only sampler         → top-k lax.top_k cost
- K sweep (8..96)             → per-chunk fixed cost vs per-step cost

Usage: python tools/decode_microbench.py [--iters 5] [--model MODEL]
``--model`` picks the shape: ``llama-1b`` (default, the round-2/3 bench
shape above), ``llama3-8b`` (the round-4 headline shape, same sweep), or
``tiny`` (a CPU smoke of the tool itself — tiny shapes, xla kernels only).
Prints one JSON line per variant: {"name", "step_ms", "chunk_ms"}.
"""

from __future__ import annotations

import argparse
import json
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from langstream_tpu.compile_cache import configure_compile_cache
from langstream_tpu.models.llama import (
    LlamaConfig,
    init_kv_cache,
    init_llama_params,
    llama_decode_chunk,
)
from langstream_tpu.models.quant import init_llama_params_q8


def _params(mc, quantize):
    # quantized trees are generated directly (int8 + scales): init->quantize
    # peaks above 16 GB at the 8B shape (engine parity, models/quant.py)
    if quantize:
        return init_llama_params_q8(mc)
    return init_llama_params(mc)
from langstream_tpu.serving.sampler import sample_tokens


def build(mc, B, K, window, quantize, sampler):
    params = _params(mc, quantize)
    cache_k, cache_v = init_kv_cache(mc, B)

    if sampler == "full":
        def sample_fn(logits, sub):
            return sample_tokens(
                logits, sub,
                jnp.full((B,), 0.7, jnp.float32),
                jnp.full((B,), 40, jnp.int32),
            )
    else:
        def sample_fn(logits, sub):
            t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            lp = jnp.take_along_axis(
                jax.nn.log_softmax(logits, axis=-1), t[:, None], axis=1
            ).squeeze(1)
            return t, lp

    @jax.jit
    def run(params, ck, cv, tokens, lengths, active, key):
        return llama_decode_chunk(
            mc, params, tokens, lengths, active, ck, cv,
            sample_fn, key, K, window=window,
        )

    tokens = jnp.zeros((B,), jnp.int32)
    lengths = jnp.full((B,), 64, jnp.int32)
    active = jnp.ones((B,), bool)
    key = jax.random.PRNGKey(0)
    return run, params, cache_k, cache_v, tokens, lengths, active, key


def measure(name, mc, B, K, window, quantize, sampler, iters):
    run, params, ck, cv, tokens, lengths, active, key = build(
        mc, B, K, window, quantize, sampler
    )
    jax.block_until_ready(run(params, ck, cv, tokens, lengths, active, key))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = run(params, ck, cv, tokens, lengths, active, key)
    jax.block_until_ready(out)
    chunk_ms = (time.perf_counter() - t0) / iters * 1e3
    print(json.dumps({
        "name": name, "B": B, "K": K, "window": window,
        "quant": quantize, "sampler": sampler,
        "chunk_ms": round(chunk_ms, 2),
        "step_ms": round(chunk_ms / K, 3),
    }), flush=True)
    del run, params, ck, cv, out


def measure_continuation(name, mc, B, start, suffix, quantize, kernel, iters):
    """Time the prefix-cache continuation / chunked-prefill forward (and,
    at suffix=D1-small widths, the speculative verify shape) against the
    paged pool, for the XLA and multi-query-Pallas history reads."""
    from langstream_tpu.models.llama_paged import llama_prefill_continue_paged
    from langstream_tpu.models.paged import (
        BlockManager,
        PagedLayout,
        init_paged_kv_cache,
    )

    params = _params(mc, quantize)
    # size the pool for exactly this shape: the default half-of-dense pool
    # can't hold B slots of start+suffix tokens at the wider shapes, and
    # reservations past max_seq_len can never fit any pool
    need = min(start + suffix + 8, mc.max_seq_len)
    blocks_per_slot = -(-need // 64)
    layout = PagedLayout.for_model(
        mc.max_seq_len, B, block_size=64, num_blocks=B * blocks_per_slot + 1
    )
    bm = BlockManager(layout, B)
    for s in range(B):
        bm.admit(s, need)
        bm.ensure_capacity(s, start + suffix)
    tables = jnp.asarray(bm.tables)
    pk, pv = init_paged_kv_cache(mc, layout)
    tokens = jnp.zeros((B, suffix), jnp.int32)
    starts = jnp.full((B,), start, jnp.int32)
    sufl = jnp.full((B,), suffix, jnp.int32)
    nrb = max(1, -(-start // layout.block_size))

    @jax.jit
    def run(params, pk, pv, tokens, starts, sufl, tables):
        return llama_prefill_continue_paged(
            mc, params, tokens, starts, sufl, pk, pv, tables,
            num_read_blocks=nrb, kernel=kernel,
        )

    jax.block_until_ready(run(params, pk, pv, tokens, starts, sufl, tables))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = run(params, pk, pv, tokens, starts, sufl, tables)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t0) / iters * 1e3
    print(json.dumps({
        "name": name, "B": B, "start": start, "suffix": suffix,
        "kernel": kernel, "quant": quantize, "call_ms": round(ms, 2),
    }), flush=True)
    del run, params, pk, pv, out


def measure_fused_tail(name, mc, B, K, window, quantize, iters):
    """Leg-1 ablation (``--fused-sampler``): the fused tail packs tokens +
    bitcast logprobs INSIDE the decode program — the host's per-chunk work
    is one fetch of an already-materialized array. The split tail (the
    pre-fusion engine) gets the same decode outputs but pays a separate
    pack dispatch before its fetch. Both run at equal K; ``host_tail_ms``
    times ONLY the post-program host work (everything after a device
    fence), which is the quantity the fusion deletes."""
    from langstream_tpu.models.llama_paged import pack_tokens_logprobs

    params = _params(mc, quantize)
    cache_k, cache_v = init_kv_cache(mc, B)

    def sample_fn(logits, sub):
        t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lp = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), t[:, None], axis=1
        ).squeeze(1)
        return t, lp

    @jax.jit
    def run_split(params, ck, cv, tokens, lengths, active, key):
        return llama_decode_chunk(
            mc, params, tokens, lengths, active, ck, cv,
            sample_fn, key, K, window=window,
        )

    # the pre-fusion engine's separate pack program
    pack = jax.jit(lambda t, l: jnp.concatenate([
        t.reshape(-1),
        jax.lax.bitcast_convert_type(l, jnp.int32).reshape(-1),
    ]))

    @jax.jit
    def run_fused(params, ck, cv, tokens, lengths, active, key):
        out = llama_decode_chunk(
            mc, params, tokens, lengths, active, ck, cv,
            sample_fn, key, K, window=window,
        )
        return (pack_tokens_logprobs(out[0], out[1]),) + out[2:]

    tokens = jnp.zeros((B,), jnp.int32)
    lengths = jnp.full((B,), 64, jnp.int32)
    active = jnp.ones((B,), bool)
    key = jax.random.PRNGKey(0)

    for tail, runner in (("split", run_split), ("fused", run_fused)):
        out = runner(params, cache_k, cache_v, tokens, lengths, active, key)
        if tail == "split":
            np.asarray(pack(out[0], out[1]))  # warm the pack variant too
        else:
            np.asarray(out[0])
        np.asarray(out[2])
        t0 = time.perf_counter()
        host_s = 0.0
        for _ in range(iters):
            out = runner(
                params, cache_k, cache_v, tokens, lengths, active, key
            )
            if tail == "split":
                # fence the decode program, then time the host tail the
                # split design pays: pack dispatch + packed fetch
                np.asarray(out[2])
                th = time.perf_counter()
                np.asarray(pack(out[0], out[1]))
                host_s += time.perf_counter() - th
            else:
                np.asarray(out[2])
                th = time.perf_counter()
                np.asarray(out[0])
                host_s += time.perf_counter() - th
        chunk_ms = (time.perf_counter() - t0) / iters * 1e3
        host_ms = host_s / iters * 1e3
        print(json.dumps({
            "name": f"{name}-{tail}", "B": B, "K": K, "window": window,
            "quant": quantize,
            "chunk_ms": round(chunk_ms, 2),
            "host_tail_ms": round(host_ms, 3),
            "host_tail_ms_per_step": round(host_ms / K, 4),
        }), flush=True)
    del params, cache_k, cache_v


def measure_device_draft(name, B, S, D, steps):
    """Leg-2 ablation (``--device-draft``): steady-state per-step drafting
    cost for B slots — the engine's incremental host bigram loop (dict
    update + lookup + slice, per slot, per step) vs ONE jitted vmapped
    ``prompt_lookup_draft`` dispatch over the device-resident context
    rows. ``match`` cross-checks the two drafters token-for-token on the
    final step (the fused engine path relies on this equivalence)."""
    from langstream_tpu.models.llama_paged import prompt_lookup_draft

    rng = np.random.default_rng(0)
    half = S // 2
    ctx = rng.integers(1, 97, size=(B, S)).astype(np.int32)
    ctx[:, half:] = ctx[:, : S - half]  # repetitive: lookups actually hit
    n0 = S - steps - 1

    # --- host bigram loop (engine._draft_tokens semantics) ---
    idxs: list[dict] = []
    for b in range(B):
        row, idx = ctx[b], {}
        for i in range(1, n0 - 1):
            idx[(int(row[i - 1]), int(row[i]))] = i - 1
        idxs.append(idx)
    host_drafts = np.zeros((B, D), np.int32)
    t0 = time.perf_counter()
    for s in range(steps):
        n = n0 + s
        for b in range(B):
            row, idx = ctx[b], idxs[b]
            idx[(int(row[n - 2]), int(row[n - 1]))] = n - 2
            pos = idx.get((int(row[n - 1]), int(row[n])))
            if pos is not None:
                cont = row[pos + 2 : pos + 2 + D]
                host_drafts[b, : len(cont)] = cont
                host_drafts[b, len(cont):] = 0
            else:
                host_drafts[b] = 0
    host_ms = (time.perf_counter() - t0) / steps * 1e3

    # --- jitted device drafter (one dispatch for all B slots) ---
    draft_fn = jax.jit(
        jax.vmap(lambda row, ln: prompt_lookup_draft(row, ln, D))
    )
    ctx_dev = jnp.asarray(ctx)
    out = draft_fn(ctx_dev, jnp.full((B,), n0 + 1, jnp.int32))
    np.asarray(out[0])  # warm
    t0 = time.perf_counter()
    for s in range(steps):
        out = draft_fn(ctx_dev, jnp.full((B,), n0 + s + 1, jnp.int32))
    dev_drafts = np.asarray(out[0])
    dev_ms = (time.perf_counter() - t0) / steps * 1e3
    print(json.dumps({
        "name": name, "B": B, "ctx": S, "drafts": D, "steps": steps,
        "host_ms_per_step": round(host_ms, 4),
        "dispatch_ms_per_step": round(dev_ms, 4),
        "match": bool((host_drafts == dev_drafts).all()),
    }), flush=True)


def main():
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument(
        "--phase", choices=["decode", "continuation", "all"], default="all"
    )
    ap.add_argument(
        "--fused-sampler", action="store_true",
        help="run ONLY the leg-1 ablation: fused in-program sample+pack "
             "tail vs the pre-fusion split tail, at equal K",
    )
    ap.add_argument(
        "--device-draft", action="store_true",
        help="run ONLY the leg-2 ablation: host bigram drafting loop vs "
             "one jitted prompt-lookup dispatch (no model forward)",
    )
    ap.add_argument(
        "--model", choices=["llama-1b", "llama3-8b", "tiny"],
        default="llama-1b",
        help="tiny = CPU smoke of the tool itself; 8B = the r4 headline shape",
    )
    args = ap.parse_args()
    # full-size sweep shapes (identical for 1b and 8B so the ablation
    # columns stay comparable across model sizes); tiny overrides all
    B, K, W = 64, 96, 512
    windows, batches, ksteps = (128, 256, 1024), (8, 16, 32), (8, 32)
    if args.model == "tiny":
        mc = LlamaConfig.tiny(max_seq_len=256)
        B, K, W = 4, 8, 128
        windows, batches, ksteps = (128,), (2,), (4,)
    elif args.model == "llama3-8b":
        mc = LlamaConfig.llama3_8b(max_seq_len=1024)
    else:
        mc = LlamaConfig.llama_1b(max_seq_len=1024)

    def safe(fn, name, *a):
        # one variant's failure (OOM at an ablation shape) must not lose
        # the rest of the sweep's attribution columns
        try:
            fn(name, *a)
        except Exception as e:
            print(json.dumps(
                {"name": name, "error": f"{type(e).__name__}: {e}"}
            ), flush=True)

    if args.fused_sampler or args.device_draft:
        # targeted ablations replace the sweep: each prints its own JSON
        # rows and exits so a CI smoke can assert on exactly one leg
        if args.fused_sampler:
            quant = None if args.model == "tiny" else "int8"
            safe(
                measure_fused_tail, "fused-tail", mc, B, K, W, quant,
                args.iters,
            )
        if args.device_draft:
            # draft width 4 matches the engine's speculative default
            # shape; steps large enough for a steady-state per-step mean
            safe(
                measure_device_draft, "device-draft", B,
                mc.max_seq_len, 4, 16 if args.model == "tiny" else 64,
            )
        return

    if args.phase in ("decode", "all"):
        # bench shape baseline
        safe(measure, "baseline-int8", mc, B, K, W, "int8", "full", args.iters)
        if args.model != "llama3-8b":
            # 8B bf16 weights alone are ~16 GB — cannot coexist with a KV
            # cache on one v5e; the dequant-fusion ablation rides the 1b run
            safe(measure, "bf16", mc, B, K, W, None, "full", args.iters)
        safe(measure, "greedy-sampler", mc, B, K, W, "int8", "greedy", args.iters)
        for w in windows:
            safe(measure, f"window-{w}", mc, B, K, w, "int8", "full", args.iters)
        for b in batches:
            safe(measure, f"batch-{b}", mc, b, K, W, "int8", "full", args.iters)
        for k in ksteps:
            safe(measure, f"ksteps-{k}", mc, B, k, W, "int8", "full", args.iters)

    if args.phase in ("continuation", "all"):
        kernels = ("xla",) if args.model == "tiny" else ("xla", "pallas")
        # prior-round comparability: the full-size cont-hit shape stays
        # 512-prefix/64-suffix exactly as rounds 2-3 recorded it
        prefix, chunk, hit_suffix = (
            (64, 16, 16) if args.model == "tiny" else (512, 512, 64)
        )
        # prefix-cache hit: long cached prefix, short question suffix
        for kern in kernels:
            safe(
                measure_continuation,
                f"cont-hit-{kern}", mc, min(B, 16), prefix, hit_suffix,
                "int8", kern, args.iters,
            )
            # chunked-prefill chunk: mid prompt, full-width chunk
            safe(
                measure_continuation,
                f"cont-chunk-{kern}", mc, min(B, 8), prefix, chunk, "int8",
                kern, args.iters,
            )
            # speculative verify shape: D1 = 5
            safe(
                measure_continuation,
                f"verify-d5-{kern}", mc, B, prefix, 8, "int8", kern,
                args.iters,
            )


if __name__ == "__main__":
    main()
