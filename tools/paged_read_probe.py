"""Time the single-query paged read alone, whole and stripped, on the chip.

    chiprun -- python3 tools/paged_read_probe.py

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
"""

from __future__ import annotations

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


def lengths_and_tables(kind: str, rng):
    live, lo, hi = (97, 100, 900) if kind == "chat" else (28, 1100, 1900)
    lengths = np.zeros(B, np.int32)
    slots = rng.permutation(B)[:live]
    lengths[slots] = rng.integers(lo, hi + 1, live)
    # distinct, shuffled blocks; a slot's dead columns name the scratch block
    tables = np.zeros((B, NRB), np.int32)
    ids = iter(rng.permutation(np.arange(1, NB)))
    for s in slots:
        n = -(-int(lengths[s]) // BS)
        tables[s, :n] = [next(ids) for _ in range(n)]
    return jnp.asarray(lengths), jnp.asarray(tables)


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"refused: {dev.platform} is not a TPU", file=sys.stderr)
        return 1
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (B, H, D), jnp.bfloat16)
    pool_k = jax.random.normal(keys[1], (L, NB, BS, KH * D), jnp.bfloat16)
    pool_v = jax.random.normal(keys[2], (L, NB, BS, KH * D), jnp.bfloat16)
    rng = np.random.default_rng(0)
    for kind in ("chat", "rag"):
        lengths, tables = lengths_and_tables(kind, rng)
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
            fn(q, pool_k, pool_v, tables, lengths).block_until_ready()
            reps = 20
            t0 = time.perf_counter()
            for _ in range(reps):
                out = fn(q, pool_k, pool_v, tables, lengths)
            out.block_until_ready()
            ms = (time.perf_counter() - t0) / (reps * L) * 1e3
            print(json.dumps({
                "lengths": kind, "build": build, "ms_a_call": round(ms, 4),
                "live_blocks": blocks, "hbm_floor_ms": round(floor_ms, 4),
                "device": dev.device_kind,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
