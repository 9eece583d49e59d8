"""Build every Pallas kernel the engine can select at a served model's
shapes and compare each with the XLA read it replaces.

The CPU tests only ever meet these kernels through the Pallas interpreter,
which accepts layouts Mosaic refuses. This module is the compiled
counterpart: ``chip_smoke.py`` runs it in the process that holds the chip,
after the served requests, at the served model's head geometry and the
window buckets the requests used. ``interpret=True`` exists for the CPU
rehearsal only and is said so in every result row.

Each row: ``{"kernel", "shape", "interpret", "ok", "max_abs_err", "tol"}``
plus ``"error"`` (the compiler's or runtime's own words, trimmed) when the
kernel did not build or run. Tolerance: inputs are bf16 (int8 pools carry
f32 scales), outputs are O(1) softmax averages of unit-normal values, and
the kernels accumulate in f32 over blocks where XLA reduces over the whole
window — 3e-2 absolute covers the bf16 probability rounding on both sides
(the interpreted CPU tests see ~3e-2 on O(1–4) outputs, tests/test_paged.py).
The recurrent state's passes (:func:`check_state_kernel` for Mamba-2's,
:func:`check_delta_state_kernel` for the delta rule's) are float32 on both
sides: their rows compare at 1e-4 of the expression's largest value. The
chunked delta rule of a prefill (:func:`check_delta_chunk_kernel`) rounds the
operands of its large products to bfloat16, as the XLA form's are rounded on
a TPU and are NOT on the CPU: its row compares at 2e-2 of the expression's
largest value (the interpreted kernel reads 4e-3 against float32 products; a
decay factor left out reads 0.3).
"""

from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

TOLERANCE = 3e-2
STATE_TOLERANCE = 1e-4
CHUNK_TOLERANCE = 2e-2


def _int8_pool(key, shape_rows: tuple, kv_heads: int, head_dim: int) -> dict:
    kq, ks = jax.random.split(key)
    return {
        "q": jax.random.randint(
            kq, (*shape_rows, kv_heads * head_dim), -127, 128, jnp.int8
        ),
        "s": jax.random.uniform(
            ks, (*shape_rows, kv_heads), jnp.float32, 0.005, 0.02
        ),
    }


def _tables_and_lengths(batch: int, read_blocks: int, block_size: int, nb: int):
    """Distinct blocks per slot (block 0 is the scratch block, as in the
    engine) and ragged lengths: a sub-block row, an exact block boundary,
    the rest spread up to the full window."""
    ids = 1 + np.arange(batch * read_blocks) % (nb - 1)
    tables = ids.reshape(batch, read_blocks).astype(np.int32)
    window = read_blocks * block_size
    lengths = np.linspace(1, window, batch).astype(np.int32)
    lengths[0] = max(1, block_size // 2 - 3)
    if batch > 1:
        lengths[1] = block_size
    lengths[-1] = window
    return jnp.asarray(tables), jnp.asarray(lengths)


def _row(kernel: str, shape: dict, interpret: bool,
         run: Callable[[], tuple[Any, Any]],
         tol: float = TOLERANCE) -> dict[str, Any]:
    row: dict[str, Any] = {
        "kernel": kernel, "shape": shape, "interpret": interpret,
        "tol": tol,
    }
    try:
        got, ref = run()
        got = np.asarray(jax.block_until_ready(got), dtype=np.float32)
        ref = np.asarray(jax.block_until_ready(ref), dtype=np.float32)
    except Exception as e:  # the row IS the boundary: the compiler's words
        # are the finding, and the caller fails on ok=False
        row.update(ok=False, error=f"{type(e).__name__}: {e}"[:2000])
        return row
    err = float(np.max(np.abs(got - ref)))
    row.update(
        ok=bool(np.isfinite(got).all() and err <= tol),
        max_abs_err=float(f"{err:.3g}"),
    )
    return row


def check_state_kernel(model_config, *, slots: int,
                       interpret: bool = False) -> dict[str, Any]:
    """One row: the Mamba-2 state's decode step (``ops/ssm_state.py``) at a
    hybrid model's heads, groups and tile, on a stack of two layers of
    ``slots`` slots of which the second layer is advanced and one slot is
    idle, against the XLA expression; the output ``y`` and the stack are
    each compared as shares of the expression's largest value."""
    from langstream_tpu.ops.ssm_state import ssm_state_step

    c = model_config
    heads, P, N, G = c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups
    ks = jax.random.split(jax.random.PRNGKey(22), 5)
    ssm = jax.random.normal(ks[0], (2, slots, heads, P, N)).astype(c.state_dtype)
    operands = (
        jax.random.uniform(ks[1], (slots, heads), jnp.float32, 0.5, 1.0),
        jax.random.normal(ks[2], (slots, heads, P), jnp.float32),
        jax.random.normal(ks[3], (slots, G, N), jnp.float32),
        jax.random.normal(ks[4], (slots, G, N), jnp.float32),
        jnp.arange(slots) != 1,
    )
    return _state_row(
        "_ssm_state_kernel",
        {"layers": 2, "slots": slots, "heads": heads, "head_dim": P,
         "state": N, "groups": G},
        ssm_state_step, ssm, operands, interpret)


def _state_row(name: str, shape: dict, step: Callable, state, operands,
               interpret: bool) -> dict[str, Any]:
    """A state kernel's row: ``step(state, 1, *operands, kernel=...)`` through
    the kernel against its XLA form, the output and the stack each compared
    as shares of the expression's largest value."""
    def run():
        got, ref = (
            jax.jit(lambda s, *a, kernel=kernel: step(
                s, 1, *a, kernel=kernel))(state, *operands)
            for kernel in ("pallas-interpret" if interpret else "pallas", "xla"))
        scales = [jnp.max(jnp.abs(r.astype(jnp.float32))) for r in ref]
        flat = lambda out: jnp.concatenate([  # noqa: E731
            (r.astype(jnp.float32) / s).ravel() for r, s in zip(out, scales)])
        return flat(got), flat(ref)

    return _row(name, shape, interpret, run, tol=STATE_TOLERANCE)


def check_delta_state_kernel(model_config, *, slots: int,
                             interpret: bool = False) -> dict[str, Any]:
    """One row: the delta-rule state's decode step (``ops/delta_state.py``)
    at a hybrid model's delta-rule heads and tile, on a stack of two layers
    of ``slots`` slots of which the second layer is advanced and one slot is
    idle, against the XLA expression, as :func:`check_state_kernel`."""
    from langstream_tpu.ops.delta_state import delta_state_step

    c = model_config
    heads, D = c.delta_heads, c.delta_head_dim
    ks = jax.random.split(jax.random.PRNGKey(23), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    state = jax.random.normal(ks[0], (2, slots, heads, D, D)).astype(c.state_dtype)
    operands = (
        jax.random.uniform(ks[1], (slots, heads, D), jnp.float32, 0.2, 1.0),
        unit(jax.random.normal(ks[2], (slots, heads, D), jnp.float32)),
        unit(jax.random.normal(ks[3], (slots, heads, D), jnp.float32)),
        jax.random.normal(ks[4], (slots, heads, D), jnp.float32),
        jax.random.uniform(ks[5], (slots, heads), jnp.float32, 0.0, 2.0),
        jnp.arange(slots) != 1,
    )
    return _state_row(
        "_delta_state_kernel",
        {"layers": 2, "slots": slots, "heads": heads, "head_dim": D},
        delta_state_step, state, operands, interpret)


def check_delta_chunk_kernel(model_config, *, rows: int = 2,
                             interpret: bool = False) -> dict[str, Any]:
    """One row: the chunked delta rule of a prefill (``ops/delta_chunk.py``)
    at a hybrid model's delta-rule heads and chunk, ``rows`` right-padded
    prompts of two chunks (the first whole; the others end inside their first
    chunk, so their second is skipped), against the XLA expression
    (``models/hybrid.py`` ``delta_chunked``): the real rows' output and the
    state, each as shares of the expression's largest value."""
    from langstream_tpu.models.hybrid import delta_chunked
    from langstream_tpu.ops.delta_chunk import delta_chunk_rule

    c = model_config
    heads, D, chunk = c.delta_heads, c.delta_head_dim, c.delta_chunk
    Pn = 2 * chunk
    ks = jax.random.split(jax.random.PRNGKey(24), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    lengths = jnp.full((rows,), chunk - 3, jnp.int32).at[0].set(Pn)
    real = jnp.arange(Pn)[None, :] < lengths[:, None]
    q = unit(jax.random.normal(ks[0], (rows, Pn, heads, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (rows, Pn, heads, D)))
    v = jax.random.normal(ks[2], (rows, Pn, heads, D))
    g = jnp.where(real[..., None, None], -jax.random.uniform(
        ks[3], (rows, Pn, heads, D), jnp.float32, 0.0, 0.5), 0.0)
    beta = jnp.where(real[..., None], jax.random.uniform(
        ks[4], (rows, Pn, heads), jnp.float32, 0.0, 2.0), 0.0)

    def run():
        got = jax.jit(lambda *a: delta_chunk_rule(
            *a, chunk, lengths, interpret=interpret))(q, k, v, g, beta)
        ref = jax.jit(lambda *a: delta_chunked(*a, chunk))(q, k, v, g, beta)
        scales = [jnp.max(jnp.abs(r)) for r in ref]
        flat = lambda out: jnp.concatenate([  # noqa: E731
            (jnp.where(real[..., None, None], out[0], 0.0) / scales[0]).ravel(),
            (out[1] / scales[1]).ravel()])
        return flat(got), flat(ref)

    return _row(
        "_delta_chunk_kernel",
        {"rows": rows, "tokens": Pn, "chunk": chunk, "heads": heads,
         "head_dim": D, "lengths": [Pn] + [chunk - 3] * (rows - 1)},
        interpret, run, tol=CHUNK_TOLERANCE)


def check_kernels(
    model_config,
    *,
    block_size: int,
    read_blocks: tuple[int, ...],
    batch: int,
    flash_seq: int = 512,
    interpret: bool = False,
) -> list[dict[str, Any]]:
    """One row per (kernel, window bucket). ``model_config`` supplies
    heads / kv_heads / head_dim; pools are random, made from a fixed seed."""
    from langstream_tpu.models.llama import _flash_mode
    from langstream_tpu.models.llama_paged import _cache_partial_xla
    from langstream_tpu.models.paged import gather_kv
    from langstream_tpu.ops.flash_attention import flash_attention
    from langstream_tpu.ops.paged_attention import (
        NEG_INF,
        merge_partial_attention,
        paged_attention_multiquery_partial,
        paged_attention_partial,
    )

    c = model_config
    H, Kh, D = c.heads, c.kv_heads, c.head_dim
    nb = batch * max(read_blocks) + 1
    keys = jax.random.split(jax.random.PRNGKey(21), 8)
    q1 = jax.random.normal(keys[0], (batch, H, D), jnp.bfloat16)
    pool_k = jax.random.normal(keys[1], (nb, block_size, Kh * D), jnp.bfloat16)
    pool_v = jax.random.normal(keys[2], (nb, block_size, Kh * D), jnp.bfloat16)
    pool_k8 = _int8_pool(keys[3], (nb, block_size), Kh, D)
    pool_v8 = _int8_pool(keys[4], (nb, block_size), Kh, D)
    t_block = 16
    qT = jax.random.normal(keys[5], (batch, t_block, H, D), jnp.bfloat16)
    rows: list[dict[str, Any]] = []

    for nrb in read_blocks:
        tables, lengths = _tables_and_lengths(batch, nrb, block_size, nb)
        shape = {
            "B": batch, "H": H, "Kh": Kh, "D": D, "block": block_size,
            "read_blocks": nrb,
        }

        def single(pk, pv, nrb=nrb, tables=tables, lengths=lengths):
            # both reads take the layer-stacked pool in place: layer 1 of
            # two holds the pool, layer 0 zeros
            stack = lambda a: jnp.stack([jnp.zeros_like(a), a])  # noqa: E731
            pk, pv = jax.tree.map(stack, pk), jax.tree.map(stack, pv)
            got = jax.jit(
                lambda q, k, v, t, n: merge_partial_attention([
                    paged_attention_partial(
                        q, k, v, 1, t, n, num_read_blocks=nrb, kv_heads=Kh,
                        head_dim=D, interpret=interpret,
                    )
                ])
            )(q1, pk, pv, tables, lengths)
            ref = jax.jit(
                lambda q, k, v, t, n: merge_partial_attention([
                    _cache_partial_xla(c, q, k, v, 1, t, n, nrb)
                ])
            )(q1, pk, pv, tables, lengths)
            return got, ref

        rows.append(_row(
            "_paged_read_kernel", shape, interpret,
            lambda: single(pool_k, pool_v),
        ))
        rows.append(_row(
            "_paged_kernel_q8", shape, interpret,
            lambda: single(pool_k8, pool_v8),
        ))

        def multi(nrb=nrb, tables=tables, lengths=lengths):
            got = jax.jit(
                lambda q, k, v, t, n: merge_partial_attention([
                    paged_attention_multiquery_partial(
                        q, k, v, t, n, num_read_blocks=nrb, kv_heads=Kh,
                        head_dim=D, t_block=t_block, interpret=interpret,
                    )
                ])
            )(qT, pool_k, pool_v, tables, lengths)

            def xla_history(q, k, v, t, n):
                # the read the kernel replaces: densify the window, every
                # suffix query attends the history rows < start
                window = lambda pool: gather_kv(  # noqa: E731
                    pool[None], t, nrb, layer=0
                ).astype(jnp.float32).reshape(batch, -1, Kh, D)
                kw, vw = window(k), window(v)
                W = kw.shape[1]
                qg = q.astype(jnp.float32).reshape(
                    batch, t_block, Kh, H // Kh, D
                )
                s = jnp.einsum("btkgd,bwkd->btkgw", qg, kw) / math.sqrt(D)
                mask = (jnp.arange(W)[None, :] < n[:, None])[
                    :, None, None, None, :
                ]
                p = jax.nn.softmax(jnp.where(mask, s, NEG_INF), axis=-1)
                out = jnp.einsum("btkgw,bwkd->btkgd", p, vw)
                return out.reshape(batch, t_block, H, D)

            ref = jax.jit(xla_history)(qT, pool_k, pool_v, tables, lengths)
            return got, ref

        rows.append(_row(
            "_paged_mq_kernel", {**shape, "T": t_block}, interpret, multi,
        ))

    def flash():
        mode = "interpret" if interpret else _flash_mode(flash_seq)
        if mode is None:
            raise RuntimeError(
                f"_flash_mode({flash_seq}) selected no kernel on backend "
                f"{jax.default_backend()!r}"
            )
        kq, kk, kv = jax.random.split(keys[6], 3)
        q = jax.random.normal(kq, (1, flash_seq, H, D), jnp.bfloat16)
        k = jax.random.normal(kk, (1, flash_seq, Kh, D), jnp.bfloat16)
        v = jax.random.normal(kv, (1, flash_seq, Kh, D), jnp.bfloat16)
        got = jax.jit(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, interpret=(mode == "interpret")
            )
        )(q, k, v)

        def xla_attention(q, k, v):
            # the einsum branch of models/llama.py prefill_forward
            qg = q.reshape(1, flash_seq, Kh, H // Kh, D)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
            s = s / math.sqrt(D)
            causal = (
                jnp.arange(flash_seq)[:, None] >= jnp.arange(flash_seq)[None, :]
            )
            s = jnp.where(causal[None, None, None], s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            out = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
            return out.reshape(1, flash_seq, H, D)

        return got, jax.jit(xla_attention)(q, k, v)

    rows.append(_row(
        "_flash_kernel",
        {"B": 1, "S": flash_seq, "H": H, "Kh": Kh, "D": D}, interpret, flash,
    ))
    rows += check_commit_kernel(
        c, block_size=block_size, batch=batch, prefill_rows=flash_seq,
        interpret=interpret)
    return rows


def check_commit_kernel(model_config, *, block_size: int, batch: int,
                        prefill_rows: int = 512,
                        interpret: bool = False) -> list[dict[str, Any]]:
    """Two rows: the commit of new K and V rows into bf16 pools
    (``ops/pool_commit.py`` under ``models/paged.py`` ``write_rows_pair``,
    both pools in ONE call) at the model's row width on stacks of two
    layers, against the scatter, bit for bit in every block but the scratch
    block 0 (tolerance 0), in each form a program takes: **shifted**, a
    decode chunk's 32 rows a slot from starts on every side of a tile's and
    a block's edge with one slot idle, and **aligned**, a prefill's ragged
    lengths from row 0 with ``starts`` stated as None. No CPU run lowers the
    kernel: this is where Mosaic compiles it."""
    from langstream_tpu.models.paged import write_rows_pair

    c = model_config
    tail = c.kv_heads * c.head_dim
    # (room for a chunk's 32 rows from the latest start, 3 blocks less one)
    columns = -(-max(prefill_rows, 3 * block_size + 32) // block_size)
    nb = batch * columns + 1
    keys = jax.random.split(jax.random.PRNGKey(24), 4)
    pools = tuple(jax.random.normal(k, (2, nb, block_size, tail), jnp.bfloat16)
                  for k in keys[:2])
    tables = jnp.asarray(
        1 + np.arange(batch * columns).reshape(batch, columns), jnp.int32)
    slots = np.arange(batch)
    cases = {
        # starts 0, 17, 34, ...: aligned, odd and even shifts, a block's edge
        "shifted": (32, jnp.asarray(
            (17 * slots) % (3 * block_size), jnp.int32),
            np.where(slots == 1, 0, 32)),
        "aligned": (prefill_rows, None,
                    np.linspace(1, prefill_rows, batch).astype(np.int64)),
    }
    rows = []
    for name, (T, starts, counts) in cases.items():
        new = tuple(jax.random.normal(k, (2, batch, T, tail), jnp.bfloat16)
                    for k in keys[2:])
        valid = jnp.asarray(np.arange(T)[None, :] < counts[:, None])

        def run(new=new, starts=starts, valid=valid):
            got, ref = (
                jax.jit(lambda p, r, kernel=kernel: write_rows_pair(
                    p, r, tables, starts, valid, kernel))(pools, new)
                for kernel in ("pallas-interpret" if interpret else "pallas",
                               "xla"))
            return (jnp.stack(got)[:, :, 1:], jnp.stack(ref)[:, :, 1:])

        rows.append(_row(
            "_pool_commit_kernel",
            {"form": name, "pools": 2, "L": 2, "B": batch, "T": T,
             "tail": tail, "block": block_size}, interpret, run, tol=0.0))
    return rows


def check_latent_kernels(model_config, *, block_size: int, read_blocks: int,
                         batch: int, flash_seq: int = 512,
                         interpret: bool = False) -> list[dict[str, Any]]:
    """Two rows for a latent-attention model (models/latent.py): the latent
    read (``ops/paged_attention.py`` ``latent_read``) at the model's heads
    and row width on a stack of two layers, the second one read, ragged
    lengths and an idle slot, against its XLA expression; and the flash
    kernel with the model's key width and its narrower value width, told
    the true length of a right-padded row, against the einsum on the real
    rows."""
    from langstream_tpu.ops.flash_attention import flash_attention
    from langstream_tpu.ops.paged_attention import (
        NEG_INF,
        latent_read,
        latent_read_xla,
        merge_partial_attention,
    )

    c = model_config
    H, W, Dv = c.heads, c.row_width, c.kv_rank
    nb = batch * read_blocks + 1
    keys = jax.random.split(jax.random.PRNGKey(23), 5)
    # scores of about unit spread, as the K/V rows' of check_kernels are
    # (1/sqrt(D) there): the tolerance is for probabilities that bfloat16
    # rounds on both sides, not for a softmax sharpened by the row's width
    q = (jax.random.normal(keys[0], (batch, H, W), jnp.float32)
         / (c.attn_scale * math.sqrt(W))).astype(jnp.bfloat16)
    pool = jax.random.normal(keys[1], (2, nb, block_size, W), jnp.bfloat16)
    tables, lengths = _tables_and_lengths(batch, read_blocks, block_size, nb)
    if batch > 2:
        lengths = lengths.at[2].set(0)          # an idle slot
    kw = dict(num_read_blocks=read_blocks, value_dim=Dv, scale=c.attn_scale)

    def read():
        got = jax.jit(lambda q, p, t, n: merge_partial_attention([
            latent_read(q, p, 1, t, n, interpret=interpret, **kw)]))(
            q, pool, tables, lengths)
        ref = jax.jit(lambda q, p, t, n: merge_partial_attention([
            latent_read_xla(q, p, 1, t, n, **kw)]))(q, pool, tables, lengths)
        return got, ref

    rows = [_row(
        "_latent_read_kernel",
        {"B": batch, "H": H, "W": W, "Dv": Dv, "block": block_size,
         "read_blocks": read_blocks}, interpret, read)]

    D, dv, heads = c.head_dim, c.v_dim, min(H, 8)
    real = flash_seq - flash_seq // 3

    def flash():
        qf = jax.random.normal(keys[2], (1, flash_seq, heads, D), jnp.bfloat16)
        kf = jax.random.normal(keys[3], (1, flash_seq, heads, D), jnp.bfloat16)
        vf = jax.random.normal(keys[4], (1, flash_seq, heads, dv), jnp.bfloat16)
        got = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, scale=c.attn_scale, interpret=interpret,
            lengths=jnp.asarray([real], jnp.int32)))(qf, kf, vf)

        def xla_attention(q, k, v):
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
            causal = (jnp.arange(flash_seq)[:, None]
                      >= jnp.arange(flash_seq)[None, :])
            s = jnp.where(causal[None, None], s * c.attn_scale, NEG_INF)
            return jnp.einsum(
                "bhqk,bkhd->bqhd", jax.nn.softmax(s, -1).astype(q.dtype), v)

        ref = jax.jit(xla_attention)(qf, kf, vf)
        return got[:, :real], ref[:, :real]

    rows.append(_row(
        "_flash_ragged_kernel",
        {"B": 1, "S": flash_seq, "real": real, "H": heads, "D": D, "Dv": dv},
        interpret, flash))
    return rows
