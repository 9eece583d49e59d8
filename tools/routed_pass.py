"""One expert layer's routed pass on the chip by rows: the dense pass, the
grouped pass as an XLA loop and the grouped pass through the kernel
(``models/moe.py`` ``dropless_experts_*``; ``ops/grouped_experts.py``), in
milliseconds a call, and one traced call of each by scope (``moe_dispatch``
/ ``moe_experts`` / ``moe_combine``). ``models/moe.py`` ``DENSE_ROWS_MAX``
is held to its tables.

Alone it makes ONE layer's experts at a configuration's widths and share
from a seed, with no engine around them (a minute a call):

    chiprun -- python3 tools/routed_pass.py [--widths mellum ...]
        [--rows n ...] [--trace-rows n ...] [--block-rows n ...]

``tools/swa_probe.py --crossover`` and ``tools/hybrid_probe.py --crossover``
run :func:`crossover` over their engine's own first expert layer.
``--rehearse-cpu`` walks it here at a tiny size with the kernel interpreted.
Refuses to run off a TPU otherwise."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))
sys.path.insert(1, ROOT)

#: the six expert cells' layers: hidden, expert width, experts held, of,
#: experts a token, activation (``bench/configs/*.json``)
WIDTHS = {
    "mellum": (2304, 896, 64, 64, 8, "silu_gated"),
    "nemotron": (2688, 1856, 16, 128, 6, "relu2"),
    "granite": (4096, 768, 36, 72, 10, "silu_gated"),
    "solar": (4096, 1280, 40, 320, 8, "silu_gated"),
    "deepseek": (5120, 1536, 20, 160, 6, "silu_gated"),
    "trinity": (3072, 3072, 32, 256, 4, "silu_gated"),
    "tiny": (128, 128, 4, 8, 3, "silu_gated"),
}
ROWS = (64, 128, 256, 384, 512, 768, 1024, 2048, 4096, 8192)
#: the dense pass computes every held expert over every row: past this many
#: rows it is minutes of the chip and teaches nothing
DENSE_ROWS = 2048


def by_scope(call, args, program: str) -> dict:
    """Device milliseconds of one call by scope, the mean of three traced
    calls (``bench/lib``'s reduction of the profiler's trace)."""
    import jax

    from lib import hybridtrace, roofline_latent

    trace_dir = os.path.join(ROOT, "chiprun_out", "routed_pass_trace", program)
    jax.profiler.start_trace(trace_dir)
    for _ in range(3):
        call(*args).block_until_ready()
    jax.profiler.stop_trace()
    reduced = roofline_latent.scope_seconds(
        hybridtrace.find_trace(trace_dir), program)
    ms = lambda table: {  # noqa: E731
        k: round(1e3 * v / 3, 3)
        for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:8]}
    return {"by_scope_ms": ms(reduced["by_scope"]),
            "unscoped_ms": ms(reduced["unscoped"])}


def crossover(hidden: int, dtype, route, w_up, w_down, layer, first: int,
              act, rows_list=ROWS, trace_rows=(), block_rows=(),
              interpret: bool = False) -> list[dict]:
    """``route(h) -> (experts, weights)`` over ``rows`` random rows of
    ``hidden``, then each pass with the stacks ``w_up`` / ``w_down`` at
    ``layer`` (the experts held from ``first`` on): milliseconds a call, the
    mean of 10 after a warm-up; at ``trace_rows`` also the grouped forms by
    scope. ``block_rows``: the kernel pass again at each of these rows a
    product, beside the rule's choice."""
    import jax

    from langstream_tpu.models import moe

    kernel = "pallas-interpret" if interpret else "pallas"
    passes = {
        "dense": lambda h, e, w, up, down: moe.dropless_experts_dense(
            h, e, w, up[layer], down[layer], first, act=act)[0],
        "grouped_xla": lambda h, e, w, up, down: moe.dropless_experts_grouped(
            h, e, w, up, down, first, layer=layer, act=act)[0],
        "grouped_kernel": lambda h, e, w, up, down: moe.dropless_experts_grouped(
            h, e, w, up, down, first, layer=layer, act=act, kernel=kernel)[0],
    }
    for b in block_rows:
        passes[f"grouped_kernel_{b}"] = (
            lambda h, e, w, up, down, b=b: moe.dropless_experts_grouped(
                h, e, w, up, down, first, layer=layer, act=act, kernel=kernel,
                block_rows=b)[0])
    out = []
    for rows in rows_list:
        h = jax.random.normal(jax.random.PRNGKey(rows), (rows, hidden), dtype)
        experts, weights = route(h)
        # the weights as arguments: closed over, a layer of them would be
        # constants of every one of these programs
        args = (h, experts, weights, w_up, w_down)
        row: dict = {"rows": rows}
        results = {}
        for name, fn in passes.items():
            if name == "dense" and rows > DENSE_ROWS:
                continue
            fn.__name__ = f"routed_{name}_{rows}"
            call = jax.jit(fn)
            results[name] = call(*args).block_until_ready()
            t = time.monotonic()
            for _ in range(10):
                y = call(*args)
            y.block_until_ready()
            row[f"{name}_ms"] = round((time.monotonic() - t) * 100, 3)
            if rows in trace_rows and name != "dense":
                row[f"{name}_scopes"] = by_scope(call, args, fn.__name__)
        worst = abs(results["grouped_xla"]).max()
        row["kernel_error_share"] = round(float(
            abs(results["grouped_kernel"] - results["grouped_xla"]).max()
            / worst), 5)
        print(f"[probe] routed pass, one layer: {json.dumps(row)}", flush=True)
        out.append(row)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--widths", nargs="+", default=["mellum"],
                    choices=sorted(WIDTHS))
    ap.add_argument("--rows", type=int, nargs="+", default=list(ROWS))
    ap.add_argument("--trace-rows", type=int, nargs="*", default=[1024, 4096])
    ap.add_argument("--block-rows", type=int, nargs="*", default=[])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        args.widths, args.rows, args.trace_rows = ["tiny"], [96, 600], []
        print("[probe] REHEARSAL on the CPU at a tiny size", flush=True)
    import jax
    import jax.numpy as jnp

    from langstream_tpu.models import moe

    if jax.default_backend() != "tpu" and not args.rehearse_cpu:
        print("tools/routed_pass.py: no TPU; nothing was run", file=sys.stderr)
        return 3
    dtype = jnp.float32 if args.rehearse_cpu else jnp.bfloat16
    out = {"device": jax.devices()[0].device_kind}
    for name in args.widths:
        hidden, inter, held, of, k, act = WIDTHS[name]
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        wide = inter * (2 if act == "silu_gated" else 1)
        w_up = (jax.random.normal(keys[0], (1, held, wide, hidden), jnp.float32)
                / hidden ** 0.5).astype(dtype)
        w_down = (jax.random.normal(keys[1], (1, held, inter, hidden), jnp.float32)
                  / inter ** 0.5).astype(dtype)
        router = jax.random.normal(keys[2], (hidden, of), jnp.float32)
        print(f"[probe] {name}: hidden {hidden}, expert width {inter}, "
              f"{held} of {of} held, top {k}, {act}", flush=True)
        out[name] = crossover(
            hidden, dtype, lambda h: moe.softmax_topk_routing(h, router, k),
            w_up, w_down, 0, 0, moe.EXPERT_ACTS[act], args.rows,
            args.trace_rows, args.block_rows, interpret=args.rehearse_cpu)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "routed_pass.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
