"""Time one decode step's pass over the Mamba-2 state alone, on the chip.

    chiprun -- python3 tools/ssm_state_probe.py [--shapes nemotron granite]
        [--steps 8] [--tiles 2] [--unrolls 4]

``ops/ssm_state.py``'s step at the two served shapes (``nemotron``: 23 layers
x 64 slots x 64 heads in 8 groups; ``granite``: 9 layers x 96 slots x 128
heads in 1 group, the mixer under ``lax.cond`` in a period of ten blocks as
the decode program has it), called once a layer from a scan over the blocks
inside a scan over steps, the stacked state in the carry and donated, as
``hybrid_decode_chunk_paged`` calls it:

- ``xla``: today's expression (update fusion, then the product with ``C``);
- ``xla-old-state``: ``y`` taken from the state before the update, so that
  update and product both consume it (the route that needs no kernel);
- ``pallas``: the kernel, at each ``--tiles`` (MiB a tile) and ``--unrolls``;
- ``copy``: the kernel's blocks read and written back with no arithmetic
  (what the pipeline's copies alone cost at that tile).

One JSON line a build: ms a layer call, ms a step (x layers), the step's HBM
floor (the state once in each direction at 819 GB/s), the share of it, the
device's peak memory after the call (a copy of the stack shows here) and the
largest difference from ``xla``. Tile, unroll and the ``copy`` body are
set on the module (``TILE_BYTES``, ``UNROLL``, ``_ssm_state_kernel``) for the
length of one build and put back. Refuses to run off a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from langstream_tpu.ops import ssm_state as S

HBM_BYTES_S = 819e9
SHAPES = {
    # layers, slots, heads, head_dim, state, groups, blocks' mixer flags
    "nemotron": (23, 64, 64, 64, 128, 8, None),
    "granite": (9, 96, 128, 64, 128, 1, (1, 1, 1, 1, 1, 0, 1, 1, 1, 1)),
}


def old_state_step(ssm, layer, decay, dtx, Bm, Cm, active):
    """``y = decay * (h C) + dtx * (B . C)``: the same value up to rounding,
    with both consumers on the state as it was read."""
    f32 = jnp.float32
    j = ssm.shape[2] // Bm.shape[1]
    state = jax.lax.dynamic_index_in_dim(ssm, layer, keepdims=False)
    h = state.astype(f32)
    Bh = jnp.repeat(Bm, j, axis=1)
    Ch = jnp.repeat(Cm, j, axis=1)
    new = h * decay[..., None, None] + dtx[..., None] * Bh[:, :, None, :]
    y = (decay[..., None] * jnp.einsum("bhpn,bhn->bhp", h, Ch)
         + dtx * jnp.sum(Bh * Ch, axis=-1)[..., None])
    ssm = jax.lax.dynamic_update_index_in_dim(
        ssm,
        jnp.where(active[:, None, None, None], new.astype(ssm.dtype), state),
        layer, 0)
    return y, ssm


def copy_kernel(layer_ref, active_ref, decay_ref, dtx_ref, b_ref, c_ref,
                s_ref, y_ref, o_ref, *, heads_per_group):
    y_ref[...] = dtx_ref[...]
    o_ref[...] = s_ref[...]


def program(step_fn, layers, mixers, steps):
    """``steps`` decode steps' worth of calls: a scan over the blocks in a
    scan over steps, the stack and a running sum of ``y`` in the carry."""

    def run(ssm, decay, dtx, Bm, Cm, active):
        def block(carry, xs):
            ssm, acc = carry
            has, m = xs

            def mixer(ssm, acc):
                y, ssm = step_fn(ssm, m, decay, dtx, Bm, Cm, active)
                return ssm, acc + y

            if mixers is None:
                return mixer(ssm, acc), None
            return jax.lax.cond(has, mixer, lambda *a: a, ssm, acc), None

        flags = jnp.ones((layers,), bool) if mixers is None else (
            jnp.asarray(mixers, bool))
        idx = (jnp.cumsum(flags) - 1).astype(jnp.int32)

        def step(carry, _):
            return jax.lax.scan(block, carry, (flags, idx))[0], None

        return jax.lax.scan(
            step, (ssm, jnp.zeros(dtx.shape, jnp.float32)), None,
            length=steps)[0]

    return jax.jit(run, donate_argnums=(0,))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES))
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--tiles", type=float, nargs="+", default=[2])
    ap.add_argument("--unrolls", type=int, nargs="+", default=[S.UNROLL])
    ap.add_argument("--builds", nargs="+",
                    default=["xla", "xla-old-state", "pallas", "copy"])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="walk the path off the chip: 2 slots, 8 heads, the "
                         "kernel interpreted; its times mean nothing")
    args = ap.parse_args()
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse_cpu:
        print("tools/ssm_state_probe.py: no TPU; nothing was run",
              file=sys.stderr)
        return 3
    rows = []
    for name in args.shapes:
        L, B, Hh, P, N, G, mixers = SHAPES[name]
        if not on_chip:
            B, Hh, G = 2, 8, min(G, 2)
        ks = jax.random.split(jax.random.PRNGKey(7), 5)
        decay = jax.random.uniform(ks[0], (B, Hh), jnp.float32, 0.9, 1.0)
        dtx = 0.05 * jax.random.normal(ks[1], (B, Hh, P), jnp.float32)
        Bm = jax.random.normal(ks[2], (B, G, N), jnp.float32)
        Cm = jax.random.normal(ks[3], (B, G, N), jnp.float32)
        active = jnp.arange(B) % 7 != 3
        floor_ms = 2 * L * B * Hh * P * N * 4 / HBM_BYTES_S * 1e3
        builds = []
        for b in args.builds:
            if b in ("pallas", "copy"):
                builds += [(b, t, u) for t in args.tiles
                           for u in (args.unrolls if b == "pallas" else [1])]
            else:
                builds.append((b, None, None))
        want = None
        for build, tile, unroll in builds:
            kernel, tile_bytes, unroll0 = (
                S._ssm_state_kernel, S.TILE_BYTES, S.UNROLL)
            if tile is not None:
                S.TILE_BYTES, S.UNROLL = int(tile * 2 ** 20), unroll
            if build == "copy":
                S._ssm_state_kernel = copy_kernel
            step_fn = {
                "xla": S.ssm_state_step_xla, "xla-old-state": old_state_step,
            }.get(build, lambda *a: S.ssm_state_step(
                *a, kernel="pallas" if on_chip else "pallas-interpret"))
            row = {"shape": name, "build": build, "tile_mib": tile,
                   "unroll": unroll}
            try:
                fresh = lambda: jax.random.normal(  # noqa: E731
                    ks[4], (L, B, Hh, P, N), jnp.float32)
                operands = (decay, dtx, Bm, Cm, active)
                fn = program(step_fn, L, mixers, args.steps).lower(
                    fresh(), *operands).compile()
                row["temp_gb"] = round(
                    fn.memory_analysis().temp_size_in_bytes / 1e9, 3)
                ssm, acc = fn(fresh(), decay, dtx, Bm, Cm, active)
                got = (jax.device_get(acc), jax.device_get(ssm[L // 2, 1, 3]))
                ssm, acc = None, None
                times = []
                for _ in range(3):
                    state = fresh()
                    state.block_until_ready()
                    t = time.monotonic()
                    out = fn(state, decay, dtx, Bm, Cm, active)
                    jax.block_until_ready(out)
                    times.append(time.monotonic() - t)
                    out = state = None
                ms_step = min(times) / args.steps * 1e3
                if build == "xla":
                    want = got
                row.update(
                    rehearsal=not on_chip, ms_call=round(ms_step / L, 4),
                    ms_step=round(ms_step, 3),
                    floor_ms_step=round(floor_ms, 3),
                    floor_share=round(floor_ms / ms_step, 3),
                    peak_gb=round((jax.devices()[0].memory_stats() or {}).get(
                        "peak_bytes_in_use", 0) / 1e9, 3))
                if want is not None and build != "copy":
                    row["max_diff_y"] = float(abs(got[0] - want[0]).max())
                    row["max_diff_state"] = float(abs(got[1] - want[1]).max())
            except Exception as e:  # the compiler's words are the finding
                row["error"] = f"{type(e).__name__}: {e}"[:1500]
            finally:
                S._ssm_state_kernel, S.TILE_BYTES, S.UNROLL = (
                    kernel, tile_bytes, unroll0)
            print(json.dumps(row), flush=True)
            rows.append(row)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ssm_state_probe.json", "w") as f:
        json.dump(rows, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
