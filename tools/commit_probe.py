"""The commit of new rows into the pools of a kind alone: what it costs a
program to run (on the chip) and to trace and lower (here, without one).

    chiprun -- python3 tools/commit_probe.py [--shapes chat-chunk rag-prefill ...]
        [--calls 8] [--seed 7]
    JAX_PLATFORMS=cpu python3 tools/commit_probe.py --lowering
        [--against <the parent's checkout>] [--repeat 5]
        [--tree <another checkout>] [--no-programs | --no-commits]

``models/paged.py`` ``write_rows_pair`` alone on donated bf16 K and V pools
at the served shapes, under both selections: ``"xla"`` (one scatter a folded
pool, a row at a time) and ``"pallas"`` (``ops/pool_commit.py``: runs of
rows, all layers of a run in one copy, both pools in one call). One JSON line
a shape: ms a call of each, their ratio, the rows committed and what their
bytes take at 819 GB/s in each direction, and whether the pools are equal bit
for bit in every block but the scratch block 0. The gate of a change to
either form: the kernel at a quarter of the scatter's time or less at every
shape. Refuses to run off a TPU; ``--rehearse-cpu`` walks the path here at
small shapes with the kernel interpreted (its times mean nothing).

``--lowering`` (no chip: ``jax.jit(f).trace(avals).lower(
lowering_platforms=("tpu",))`` from abstract shapes, after a first call at
another shape has paid the imports) prints seconds of trace and of lowering:
a program's K and V commits alone at five prefill and two chunk shapes under
either selection, then whole serving programs (InternLM2's prefill and decode
chunk, Mellum's and ``nemotron_h``'s prefill) as this tree traces them. A
program's first warm use IS this, at every set-up of every cell (0.35 s a
prefill program on the chip's host for 0.17 s on the sandbox's): the gate is
the commits at 0.08 s or under in the mean and a whole prefill program within
0.04 s of the parent's (``--against <its checkout>``: the two trees are timed
in turn, a process each, because the sandbox's cores are shared and their
load drifts: two runs one after the other differ by more than the gate).
"""

from __future__ import annotations

import argparse
import functools
import gc
import inspect
import json
import os
import statistics
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--tree" in sys.argv:        # another checkout's programs (the parent's)
    sys.path.insert(0, os.path.abspath(sys.argv[sys.argv.index("--tree") + 1]))

import jax
import jax.numpy as jnp
import numpy as np

HBM_BYTES_S = 819e9
BS = 64
#: layers, pool blocks, row lanes, slots, rows a slot, table columns, and
#: what the slots commit: ``chunk`` (every row of an active slot from a start
#: anywhere, one slot in sixteen idle), ``prefill`` (rows ``[0, length)``,
#: length drawn from the range), ``window`` (the last ``W`` rows of a prompt
#: as long as the bucket less a few, through a ring of ``W / 64 + 1`` blocks)
SHAPES = {
    "chat-chunk": (24, 901, 1024, 128, 32, 32, ("chunk",)),
    "chat-chunk8": (24, 901, 1024, 128, 8, 32, ("chunk",)),
    "chat-prefill": (24, 901, 1024, 4, 512, 32, ("prefill", 130, 512)),
    "rag-prefill": (24, 901, 1024, 8, 2048, 32, ("prefill", 1024, 1856)),
    "mellum-window-1024": (6, 3265, 512, 1, 1024, 128, ("window", 1024)),
    "mellum-window-8192": (6, 3265, 512, 1, 8192, 128, ("window", 1024)),
    "mellum-full-8192": (2, 7201, 512, 1, 8192, 128, ("prefill", 4100, 8192)),
    "mellum-chunk": (6, 3265, 512, 192, 32, 128, ("chunk",)),
    "trinity-window": (4, 2081, 1024, 1, 16384, 256, ("window", 4096)),
    "latent-prefill": (5, 4001, 640, 1, 8192, 256, ("prefill", 4100, 8192)),
    "nemotron-prefill": (6, 2001, 256, 8, 512, 32, ("prefill", 130, 512)),
    "nemotron-chunk": (6, 2001, 256, 64, 32, 32, ("chunk",)),
}


def case(name, rng, small):
    """(pool shape, rows shape, tables, starts, valid) of one shape."""
    L, nb, tail, B, T, cols, (kind, *par) = SHAPES[name]
    if small:
        L, nb, B, T = min(L, 2), 40, min(B, 3), min(T, 128)
        par = [min(p, T) for p in par]
    t = np.arange(T)[None, :]
    tables = np.zeros((B, cols), np.int32)
    if kind == "window":
        W = min(par[0], T)
        ring = W // BS + 1
        lengths = T - rng.integers(0, BS, B)
        blocks = rng.permutation(np.arange(1, nb))[:B * ring].reshape(B, ring)
        tables = blocks[:, np.arange(cols) % ring].astype(np.int32)
        starts = np.zeros(B, np.int32)
        valid = (t < lengths[:, None]) & (t >= (lengths - W)[:, None])
    else:
        held = min(cols, (nb - 1) // B)
        tables[:, :held] = rng.permutation(
            np.arange(1, nb))[:B * held].reshape(B, held)
        if kind == "chunk":
            starts = rng.integers(1, held * BS - T, B).astype(np.int32)
            valid = np.broadcast_to(
                (np.arange(B) % 16 != 5)[:, None], (B, T)).copy()
        else:
            lengths = rng.integers(par[0], par[1] + 1, B)
            starts = np.zeros(B, np.int32)
            valid = t < lengths[:, None]
    # a prefill's rows begin at position 0, and its site says so (None)
    return ((L, nb, BS, tail), (L, B, T, tail), jnp.asarray(tables),
            jnp.asarray(starts) if kind == "chunk" else None,
            jnp.asarray(valid))


@functools.partial(jax.jit, static_argnames=("shape",))
def filled(seed, shape):
    """A bf16 pool with something in every row (whole numbers from -128 to
    127 by a hash of the index and ``seed``), made where it lies: a normal
    draw passes through float32, twice the pool beside it, and four pools of
    chat's size are most of the chip."""
    x = seed.astype(jnp.uint32)
    for axis, prime in enumerate(
            (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F)):
        x = x + jax.lax.broadcasted_iota(
            jnp.uint32, shape, axis) * jnp.uint32(prime)
    x = (x ^ (x >> 15)) * jnp.uint32(0x2C1B3C6D)
    return ((x >> 12) & 0xFF).astype(jnp.float32).astype(jnp.bfloat16) - 128


def commits(pools, rows, tables, starts, valid, kernel):
    """A kind's commit as this tree's programs make it: ``write_rows_pair``
    where the tree has it, a ``write_rows`` a pool before it did (a tree from
    before the kernel takes no selection: the scatter)."""
    from langstream_tpu.models import paged

    if hasattr(paged, "write_rows_pair"):
        return paged.write_rows_pair(
            pools, rows, tables, starts, valid, kernel)
    if starts is None:
        starts = jnp.zeros((valid.shape[0],), jnp.int32)
    extra = (kernel,) if "kernel" in inspect.signature(
        paged.write_rows).parameters else ()
    return tuple(paged.write_rows(pool, new, tables, starts, valid, *extra)
                 for pool, new in zip(pools, rows))


# -- what a program's commits cost to trace and lower (no chip) -------------

#: slots x rows of the K and V commits timed alone: a prefill's (from row 0,
#: ragged lengths) and a decode chunk's (from anywhere, some slots idle), into
#: InternLM2's pools: 24 layers, 901 blocks of 64 rows of 1,024 lanes
LOWERED_COMMITS = {
    "prefill": [(1, 64), (2, 256), (4, 512), (1, 1024), (8, 2048)],
    "chunk": [(128, 32), (128, 8)],
}


def seconds_to_lower(fn, *avals, **jit):
    """(seconds to trace, seconds to lower for a TPU) of ``fn`` at abstract
    operands it has not been traced at before: what a program's first warm
    use costs its host (a second trace at the same shapes finds the ``jnp``
    functions' own traces kept, as no program of a set-up does)."""
    gc.collect()
    t0 = time.perf_counter()
    traced = jax.jit(fn, **jit).trace(*avals)
    t1 = time.perf_counter()
    lowered = traced.lower(lowering_platforms=("tpu",))
    t2 = time.perf_counter()
    LOWERED_TEXT[:] = [lowered.as_text()]
    return t1 - t0, t2 - t1


#: the text the last :func:`seconds_to_lower` lowered to (a test hashes it:
#: tests/test_eva_programs.py)
LOWERED_TEXT: list = []


def lowered_commits(kind, B, T, kernel, L=24, nb=901, tail=1024, cols=32):
    on = jax.ShapeDtypeStruct
    pool, rows = on((L, nb, BS, tail), jnp.bfloat16), on(
        (L, B, T, tail), jnp.bfloat16)

    def prefill(pool_k, pool_v, ks, vs, tables, lengths):
        valid = jnp.arange(T)[None, :] < lengths[:, None]
        return commits((pool_k, pool_v), (ks, vs), tables, None, valid, kernel)

    def chunk(pool_k, pool_v, ks, vs, tables, lengths, active):
        valid = jnp.broadcast_to(active[:, None], (B, T))
        return commits(
            (pool_k, pool_v), (ks, vs), tables, lengths, valid, kernel)

    avals = [pool, pool, rows, rows, on((B, cols), jnp.int32),
             on((B,), jnp.int32)]
    if kind == "chunk":
        avals.append(on((B,), jnp.bool_))
    return seconds_to_lower(
        prefill if kind == "prefill" else chunk, *avals, donate_argnums=(0, 1))


def lowered_program(name, B, T):
    """Seconds to trace and lower one whole serving program of this tree
    under the chip's selections (the Pallas read, flash from 512 rows, the
    commit's kernel where the tree has one): ``internlm2`` (a prefill, or
    with ``T`` 32 the decode chunk of 128 slots), ``mellum`` and
    ``nemotron`` (a prefill)."""
    on = jax.ShapeDtypeStruct
    abstract = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: on(a.shape, a.dtype), tree)
    ints = lambda *shape: on(shape, jnp.int32)  # noqa: E731

    def handed(fn, **kw):
        """``kw`` as far as this tree's ``fn`` takes it."""
        return {k: v for k, v in kw.items()
                if k in inspect.signature(fn).parameters}

    if name == "internlm2":
        from langstream_tpu.models.llama import LlamaConfig, init_llama_params
        from langstream_tpu.models.llama_paged import (
            llama_decode_chunk_paged,
            llama_prefill_paged,
        )

        c = LlamaConfig(vocab_size=92544, hidden=2048, layers=24, heads=16,
                        kv_heads=8, head_dim=128, intermediate=8192,
                        rope_theta=1e6, max_seq_len=2048)
        params = abstract(jax.eval_shape(lambda: init_llama_params(c)))
        pool = on((24, 901, BS, 1024), jnp.bfloat16)
        if T == 32:
            def greedy(logits, key):
                return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                        jnp.max(jax.nn.log_softmax(logits), axis=-1))

            def chunk(params, tokens, lengths, active, pool_k, pool_v,
                      tables, key):
                return llama_decode_chunk_paged(
                    c, params, tokens, lengths, active, pool_k, pool_v,
                    tables, greedy, key, T, num_read_blocks=32,
                    kernel="pallas", return_packed=True)

            return seconds_to_lower(
                chunk, params, ints(B), ints(B), on((B,), jnp.bool_), pool,
                pool, ints(B, 32), on((2,), jnp.uint32),
                donate_argnums=(4, 5))

        def prefill(params, tokens, lengths, pool_k, pool_v, tables):
            return llama_prefill_paged(
                c, params, tokens, lengths, pool_k, pool_v, tables,
                use_flash=True, **handed(llama_prefill_paged, kernel="pallas"))

        return seconds_to_lower(
            prefill, params, ints(B, T), ints(B), pool, pool, ints(B, 32),
            donate_argnums=(3, 4))
    if name == "mellum":
        from langstream_tpu.models.swa import (
            SwaConfig,
            init_swa_params,
            swa_prefill_paged,
        )

        c = SwaConfig.mellum2_12b_a2_5b_8l()
        params = abstract(jax.eval_shape(lambda: init_swa_params(c)))
        kinds = c.layer_kinds
        full = on((kinds.count("F"), 7201, BS, c.kv_heads * c.head_dim),
                  jnp.bfloat16)
        window = on((kinds.count("W"), 3265, BS, c.kv_heads * c.head_dim),
                    jnp.bfloat16)

        def prefill(params, tokens, lengths, pool_k, pool_v, wk, wv, tables):
            return swa_prefill_paged(
                c, params, tokens, lengths, pool_k, pool_v,
                {"k": wk, "v": wv}, tables, use_flash=True, kernel="pallas")

        return seconds_to_lower(
            prefill, params, ints(B, T), ints(B), full, full, window, window,
            ints(B, 2 * -(-c.max_seq_len // BS)), donate_argnums=(3, 4, 5, 6))
    if name == "deepseek":
        from langstream_tpu.models.latent import (
            LatentConfig,
            init_latent_params,
            latent_prefill_paged,
        )

        c = LatentConfig.deepseek_v2_ep8()
        params = abstract(jax.eval_shape(lambda: init_latent_params(c)))
        pool = on((c.layers, 2001, BS, c.row_width), jnp.bfloat16)

        def prefill(params, tokens, lengths, pool, tables):
            return latent_prefill_paged(
                c, params, tokens, lengths, pool, tables, use_flash=True,
                kernel="pallas")

        return seconds_to_lower(
            prefill, params, ints(B, T), ints(B), pool,
            ints(B, -(-c.max_seq_len // BS)), donate_argnums=(3,))
    if name == "evabyte":
        from langstream_tpu.models.eva import (
            EvaConfig,
            eva_prefill_paged,
            init_eva_params,
        )

        c = EvaConfig.evabyte_6_5b_8l()
        params = abstract(jax.eval_shape(lambda: init_eva_params(c)))
        width = c.heads * c.head_dim
        summary = on((c.layers, 401, BS, width), jnp.bfloat16)
        ring = on((c.layers, 20 * c.ring_blocks(BS) + 1, BS, width),
                  jnp.bfloat16)

        def prefill(params, tokens, lengths, pool_k, pool_v, rk, rv, tables):
            return eva_prefill_paged(
                c, params, tokens, lengths, pool_k, pool_v,
                {"k": rk, "v": rv}, tables, use_flash=True, kernel="pallas")

        return seconds_to_lower(
            prefill, params, ints(B, T), ints(B), summary, summary, ring,
            ring, ints(B, 2 * -(-c.max_seq_len // BS)),
            donate_argnums=(3, 4, 5, 6))
    from langstream_tpu.models.hybrid import (
        HybridConfig,
        hybrid_prefill_paged,
        init_hybrid_params,
        init_hybrid_state,
    )

    c = HybridConfig.nemotron3_nano_ep8()
    params = abstract(jax.eval_shape(lambda: init_hybrid_params(c)))
    state = abstract(jax.eval_shape(lambda: init_hybrid_state(c, 64)))
    pool = on((c.attn_layers, 2001, BS, c.kv_heads * c.head_dim),
              jnp.bfloat16)

    def prefill(params, tokens, lengths, pool_k, pool_v, state, tables, ids):
        return hybrid_prefill_paged(
            c, params, tokens, lengths, pool_k, pool_v, state, tables, ids,
            use_flash=True, kernel="pallas")

    return seconds_to_lower(
        prefill, params, ints(B, T), ints(B), pool, pool, state, ints(B, 32),
        ints(B), donate_argnums=(3, 4, 5))


#: the whole programs Gate 1 holds to the parent's: (family, slots, rows)
LOWERED_PROGRAMS = [
    ("internlm2", 4, 512), ("internlm2", 8, 2048), ("internlm2", 128, 32),
    ("mellum", 1, 1024), ("nemotron", 8, 512), ("deepseek", 1, 4096),
    ("evabyte", 1, 8192),
]


def lowering(args) -> int:
    """Gate 1: seconds of trace and of lowering, a JSON line each; with
    ``--repeat n`` the medians of ``n`` processes' lines (the sandbox's cores
    are shared: one reading in three is 30% off)."""
    if args.repeat > 1 or args.against:
        mine, skip = [], False
        for arg in sys.argv[1:]:
            if not skip and arg not in ("--repeat", "--against"):
                mine.append(arg)
            skip = not skip and arg in ("--repeat", "--against")
        # the commits and the programs each in a process of their own: what
        # one has traced is not there for the other to find
        parts = [p for p in ("--no-programs", "--no-commits") if not (
            {"--no-programs", "--no-commits"} - {p}) & set(mine)]

        def once(tree):
            return [json.loads(row) for part in parts for row in subprocess.run(
                [sys.executable, __file__, *mine, part, *tree], check=True,
                text=True, stdout=subprocess.PIPE).stdout.splitlines()]

        # the two trees in turn, so that the machine's load drifts under both
        runs = [(once([]), once(["--tree", args.against]) if args.against
                 else None) for _ in range(args.repeat)]
        median = lambda values: round(statistics.median(values), 4)  # noqa: E731
        for n, first in enumerate(runs[0][0]):
            row = {k: v if isinstance(v, str) else median(
                run[0][n][k] for run in runs) for k, v in first.items()}
            same = lambda run: next((  # noqa: E731
                r for r in run[1] if r["what"] == "program" and (
                    r["program"], r["shape"]) == (
                    first["program"], first["shape"])), None)
            if args.against and first["what"] == "program" and same(runs[0]):
                # (the commits' lines are a tree's own: one without the
                # kernel has no such line; nor has one without the family)
                timed = [k for k, v in first.items() if not isinstance(v, str)]
                row.update({f"against_{k}": median(
                    same(run)[k] for run in runs) for k in timed})
                row["over_s"] = median(
                    run[0][n]["total_s"] - same(run)["total_s"]
                    for run in runs)
            print(json.dumps(row), flush=True)
        return 0

    def line(**kv):
        print(json.dumps({k: round(v, 4) if isinstance(v, float) else v
                          for k, v in kv.items()}), flush=True)

    has_kernel = commits_take_a_kernel()
    kernels = ("xla", "pallas") if has_kernel else ("xla",)
    if args.no_commits:
        kernels = ()
    for kernel in kernels:      # the imports, paid at a shape of their own
        for kind in LOWERED_COMMITS:
            lowered_commits(kind, 3, 48, kernel)
    for kernel in kernels:
        seconds = []
        for kind, shapes in LOWERED_COMMITS.items():
            for B, T in shapes:
                trace, lower = lowered_commits(kind, B, T, kernel)
                seconds.append(trace + lower)
                line(what="commits", kind=kind, shape=f"{B}x{T}",
                     kernel=kernel, trace_s=trace, lower_s=lower,
                     total_s=trace + lower)
        line(what="commits", kernel=kernel, mean_s=sum(seconds) / len(seconds))
    if args.no_programs:
        return 0
    paid = set()
    for name, B, T in LOWERED_PROGRAMS:
        try:
            if name not in paid:    # a family's imports, at a shape of their own
                lowered_program(name, 2, 512)
                paid.add(name)
            trace, lower = lowered_program(name, B, T)
        except ImportError:     # a tree from before the family
            continue
        line(what="program", program=name, shape=f"{B}x{T}",
             commit="pallas" if has_kernel else "xla", trace_s=trace,
             lower_s=lower, total_s=trace + lower)
    return 0


def commits_take_a_kernel() -> bool:
    from langstream_tpu.models import paged

    return "kernel" in inspect.signature(paged.write_rows).parameters


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES))
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--lowering", action="store_true",
                    help="no chip: seconds to trace and to lower a program's "
                         "commits and whole programs for a TPU (Gate 1)")
    ap.add_argument("--tree", help="with --lowering: the checkout whose "
                                   "langstream_tpu is timed (the parent's)")
    ap.add_argument("--against", help="with --lowering: a second checkout "
                    "(the parent's) timed in turn with this one, a process "
                    "each; a program's line then ends with the other's "
                    "figures and over_s, the median of the pairs' differences")
    ap.add_argument("--repeat", type=int, default=1,
                    help="with --lowering: the medians of so many processes")
    ap.add_argument("--no-programs", action="store_true",
                    help="with --lowering: the commits alone")
    ap.add_argument("--no-commits", action="store_true",
                    help="with --lowering: the whole programs alone")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="walk the path off the chip at small shapes, the "
                         "kernel interpreted; its times mean nothing")
    args = ap.parse_args()
    if args.lowering:
        return lowering(args)
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse_cpu:
        print("tools/commit_probe.py: no TPU; nothing was run",
              file=sys.stderr)
        return 3
    ok = True
    for name in args.shapes:
        rng = np.random.default_rng(args.seed)
        pool_shape, rows_shape, tables, starts, valid = case(
            name, rng, small=not on_chip)
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        rows = tuple(jax.random.normal(k, rows_shape, jnp.bfloat16)
                     for k in keys[:2])
        committed = int(valid.sum()) * rows_shape[0]
        line = {
            "shape": name, "pools": [2, *pool_shape], "rows": list(rows_shape),
            "rows_committed": 2 * committed,
            "bytes_ms": round(
                2 * 2 * committed * rows_shape[3] * 2 / HBM_BYTES_S * 1e3, 3),
        }
        pools = {}
        for kernel in ("xla", "pallas"):
            fn = jax.jit(
                lambda p, r, k=kernel: commits(
                    p, r, tables, starts, valid,
                    k if on_chip or k == "xla" else "pallas-interpret"),
                donate_argnums=(0,))
            # something in every row, so that a row moved by mistake shows
            pool = tuple(filled(k[0], pool_shape) for k in keys[2:])
            pool = jax.block_until_ready(fn(pool, rows))
            t0 = time.perf_counter()
            for _ in range(args.calls):
                pool = fn(pool, rows)
            jax.block_until_ready(pool)
            line[f"{kernel}_ms"] = round(
                (time.perf_counter() - t0) / args.calls * 1e3, 3)
            pools[kernel] = pool
        line["pallas_over_xla"] = round(line["pallas_ms"] / line["xla_ms"], 3)
        # (jitted: the slices fuse into the comparison and copy nothing)
        equal = jax.jit(lambda a, b: jnp.all(a[:, 1:] == b[:, 1:]))
        line["equal_but_block_0"] = all(
            bool(equal(a, b)) for a, b in zip(pools["xla"], pools["pallas"]))
        ok = ok and line["equal_but_block_0"]
        pools = pool = None
        print(json.dumps(line), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
