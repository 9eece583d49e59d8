"""Time the single-query paged read alone on the chip: the Pallas kernel whole
and stripped, or (``--read xla``) the XLA gather read of an int8 pool.

    chiprun -- python3 tools/paged_read_probe.py [--read xla]

Three builds of ``ops/paged_attention.py``'s kernel at InternLM2-1.8B's
serving shapes (128 slots, 16/8 heads of 128, 24 layers, 901 blocks of 64
rows, bf16), each called once a layer from a ``lax.scan`` as the decode
program calls it:

- ``whole``: the kernel as served;
- ``copies``: the block copies with no matmul (every ``dot_general`` in the
  kernel returns zeros: what HBM and the copy issue cost);
- ``arithmetic``: the matmuls, masks and softmax on whatever the VMEM tile
  holds, with no copy started or awaited (what the MXU and VPU cost).

over two sets of slot lengths drawn like the benchmark's cells: ``chat``
(97 live slots of 100-900 rows, 31 free) and ``rag`` (28 live slots of
1,100-1,900 rows, 100 free). One JSON line a build and a set: ms a call,
live blocks, and the HBM floor of the live blocks at the chip's 819 GB/s.
The stripping is done here by patching names for the length of a trace;
the kernel has no option for it. Refuses to run off a TPU.

``--read xla`` times ``models/llama_paged.py`` ``_cache_partial_xla`` at
Mistral-7B's serving posture (64 slots, 32/8 heads of 128, 32 layers, 1,228
blocks of 64 rows, int8 data with float32 scales; 56 live slots of 100-1,300
rows, 8 free), called once a layer from a ``lax.scan`` over the stacked pool
as the decode program calls it, at the windows the engine's warm-up keeps
for such lengths (1,024 rows and the whole slot): ms a call, the window's
bytes (K and V, data and scales) and their time at 819 GB/s, and how many
such one-way passes the call took. The read sweeps the window whatever the
lengths, so the live rows' time is given beside it, not as its floor.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from langstream_tpu.ops import paged_attention as pa

B, H, KH, D, L, NB, BS, NRB = 128, 16, 8, 128, 24, 901, 64, 32
HBM_BYTES_S = 819e9


class _NoCopy:
    def start(self):
        pass

    def wait(self):
        pass


@contextlib.contextmanager
def stripped(build: str):
    """Patch what the kernel's trace looks up; restored on exit."""
    copy, dot = pa.pltpu.make_async_copy, jax.lax.dot_general
    if build == "arithmetic":
        pa.pltpu.make_async_copy = lambda *a, **k: _NoCopy()
    elif build == "copies":
        def no_dot(a, b, dims, preferred_element_type=None, **_):
            (ca, cb), _batch = dims
            free = [n for i, n in enumerate(a.shape) if i not in ca] + [
                n for i, n in enumerate(b.shape) if i not in cb
            ]
            return jnp.zeros(free, preferred_element_type or a.dtype)

        jax.lax.dot_general = no_dot
    try:
        yield
    finally:
        pa.pltpu.make_async_copy, jax.lax.dot_general = copy, dot


def lengths_and_tables(rng, live: int, lo: int, hi: int, slots=B, blocks=NB):
    lengths = np.zeros(slots, np.int32)
    taken = rng.permutation(slots)[:live]
    lengths[taken] = rng.integers(lo, hi + 1, live)
    # distinct, shuffled blocks; a slot's dead columns name the scratch block
    tables = np.zeros((slots, NRB), np.int32)
    ids = iter(rng.permutation(np.arange(1, blocks)))
    for s in taken:
        n = -(-int(lengths[s]) // BS)
        tables[s, :n] = [next(ids) for _ in range(n)]
    return lengths, tables


def timed(fn, args, calls: int, reps: int) -> float:
    """ms a call of ``fn(*args)``, which makes ``calls`` of them."""
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / (reps * calls) * 1e3


def xla_read(dev) -> int:
    from langstream_tpu.models.llama import LlamaConfig
    from langstream_tpu.models.llama_paged import _cache_partial_xla

    b, h, kh, d, layers, nb, bs = 64, 32, 8, 128, 32, 1228, BS
    c = LlamaConfig(heads=h, kv_heads=kh, head_dim=d, layers=layers)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(keys[0], (b, h, d), jnp.bfloat16)
    pool = lambda kq, ks: {  # noqa: E731
        "q": jax.random.randint(kq, (layers, nb, bs, kh * d), -127, 128, jnp.int8),
        "s": jax.random.uniform(ks, (layers, nb, bs, kh), jnp.float32, 0.01, 0.02),
    }
    pool_k, pool_v = pool(keys[1], keys[2]), pool(keys[3], keys[4])
    lengths, tables = lengths_and_tables(
        np.random.default_rng(0), 56, 100, 1300, slots=b, blocks=nb)
    row_bytes = 2 * kh * (d + 4)              # K and V: int8 data, f32 scales
    live_ms = int(lengths.sum()) * row_bytes / HBM_BYTES_S * 1e3
    for nrb in (16, 32):
        window_lengths = jnp.asarray(np.minimum(lengths, nrb * bs))

        def layers_of(q, pk, pv, tables, lengths, nrb=nrb):
            def one(total, layer):
                acc, m, l = _cache_partial_xla(
                    c, q, pk, pv, layer, tables, lengths, nrb)
                return total + acc.sum() + l.sum() + m.max(), None

            return jax.lax.scan(one, 0.0, jnp.arange(layers))[0]

        args = (q, pool_k, pool_v, jnp.asarray(tables), window_lengths)
        fn = jax.jit(layers_of).lower(*args).compile()
        ms = timed(fn, args, layers, reps=5)
        window_ms = b * nrb * bs * row_bytes / HBM_BYTES_S * 1e3
        print(json.dumps({
            "read": "xla", "pool": "int8", "window_rows": nrb * bs,
            "ms_a_call": round(ms, 4), "ms_a_step": round(ms * layers, 2),
            "window_bytes": b * nrb * bs * row_bytes,
            "window_hbm_ms": round(window_ms, 4),
            "passes": round(ms / window_ms, 2),
            "live_rows_hbm_ms": round(live_ms, 4),
            "device": dev.device_kind,
        }), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--read", choices=("pallas", "xla"), default="pallas")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"refused: {dev.platform} is not a TPU", file=sys.stderr)
        return 1
    if args.read == "xla":
        return xla_read(dev)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (B, H, D), jnp.bfloat16)
    pool_k = jax.random.normal(keys[1], (L, NB, BS, KH * D), jnp.bfloat16)
    pool_v = jax.random.normal(keys[2], (L, NB, BS, KH * D), jnp.bfloat16)
    rng = np.random.default_rng(0)
    for kind, live in (("chat", (97, 100, 900)), ("rag", (28, 1100, 1900))):
        lengths, tables = map(jnp.asarray, lengths_and_tables(rng, *live))
        blocks = int(np.sum(-(-np.asarray(lengths) // BS)))
        floor_ms = blocks * 2 * BS * KH * D * 2 / HBM_BYTES_S * 1e3
        for build in ("whole", "copies", "arithmetic"):
            def layers(q, pk, pv, tables, lengths):
                def one(total, layer):
                    acc, m, l = pa.paged_attention_partial(
                        q, pk, pv, layer, tables, lengths,
                        num_read_blocks=NRB, kv_heads=KH, head_dim=D,
                    )
                    return total + acc.sum() + m.sum() + l.sum(), None

                return jax.lax.scan(one, 0.0, jnp.arange(L))[0]

            with stripped(build):
                fn = jax.jit(layers).lower(
                    q, pool_k, pool_v, tables, lengths
                ).compile()
            ms = timed(fn, (q, pool_k, pool_v, tables, lengths), L, reps=20)
            print(json.dumps({
                "lengths": kind, "build": build, "ms_a_call": round(ms, 4),
                "live_blocks": blocks, "hbm_floor_ms": round(floor_ms, 4),
                "device": dev.device_kind,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
