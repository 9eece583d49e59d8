"""One decode step of a Mamba-2 layer's recurrent state (Pallas TPU).

A decode step of layer ``i`` does, for every slot ``b`` and head ``h`` of the
stacked state ``ssm (Mamba-2 layers, slots, heads, head_dim, state)``::

    new = ssm[i, b, h] * decay[b, h] + dtx[b, h][:, None] * B[b, g(h)][None, :]
    y[b, h] = new @ C[b, g(h)]
    ssm[i, b, h] = new if active[b] else ssm[i, b, h]

in float32 throughout. Written in XLA (:func:`ssm_state_step` with
``kernel="xla"``: the CPU path and the tests' reference) the update is one
in-place fusion and the product with ``C`` another that reads ``new`` back
from HBM: the state crosses HBM three times a step. The kernel
(``kernel="pallas"``) reads each ``(head_dim, state)`` tile once, writes it
back **to the same buffer** and reduces ``y`` while the tile is in VMEM: once
in each direction.

As in ``paged_attention.py`` the whole stack is the operand and the layer's
index a prefetched scalar that the index maps read, so nothing is sliced out
first; here the stack is also the output (``input_output_aliases``) and only
layer ``i``'s blocks are visited, so the other layers' rows are not written.
A slot that is not active is still read and written (its own rows, bit for
bit): no lane is skipped.

Shapes:
  ssm     (L, B, heads, P, N)    [stays in HBM; aliased to the output]
  layer   () int32               [scalar prefetch]
  active  (B,) bool              [scalar prefetch, as int32]
  decay   (B, heads) f32         [SMEM: one scalar a tile]
  dtx     (B, heads, P) f32      dt * x
  Bm, Cm  (B, groups, N) f32     shared by heads // groups heads
  -> y (B, heads, P) f32, ssm

Grid ``(B, heads // TH)``: a step holds ``TH`` heads of one slot, ``TH`` the
most heads whose tile stays under :data:`TILE_BYTES` (the state's shape
decides it, nothing else). Inside, ``P`` lies on sublanes and ``N`` on lanes,
so ``dtx[b, h]`` has to be spread along lanes and ``y[b, h]`` gathered along
them. Both go through a ``(P, heads)`` panel with the heads on lanes (the
caller's small transposes, outside the kernel): a head's column is taken from
the panel, and its ``y`` put into it, by a lane mask, so no value changes
layout inside the kernel and every sum is exact float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The state tile of one grid step: with the pipeline's two buffers each of
# the operand and of the output, four of them are in VMEM at a time.
TILE_BYTES = 2 * 1024 * 1024
# heads of a tile traced side by side in one loop body, so that one head's
# lane reductions overlap the next one's loads and multiplies
UNROLL = 4


def ssm_state_step_xla(ssm, layer, decay, dtx, Bm, Cm, active):
    """The step in XLA: what :func:`ssm_state_step` computes, and its
    reference. Returns ``(y (B, heads, P) f32, ssm)``."""
    f32 = jnp.float32
    j = ssm.shape[2] // Bm.shape[1]
    state = jax.lax.dynamic_index_in_dim(ssm, layer, keepdims=False)
    Bh = jnp.repeat(Bm, j, axis=1)                           # (B, heads, N)
    Ch = jnp.repeat(Cm, j, axis=1)
    new = (state.astype(f32) * decay[..., None, None]
           + dtx[..., None] * Bh[:, :, None, :])
    y = jnp.einsum("bhpn,bhn->bhp", new, Ch)
    ssm = jax.lax.dynamic_update_index_in_dim(
        ssm,
        jnp.where(active[:, None, None, None], new.astype(ssm.dtype), state),
        layer, 0)
    return y, ssm


def _ssm_state_kernel(
    layer_ref,    # SMEM (1,) int32 (read by the index maps)
    active_ref,   # SMEM (B,) int32
    decay_ref,    # SMEM (B * heads,) f32
    dtx_ref,      # (1, P, heads): every head of the slot, heads on lanes
    b_ref,        # (1, groups, N)
    c_ref,        # (1, groups, N)
    s_ref,        # (TH, P, N): this step's heads of layer, slot
    y_ref,        # out (1, P, heads), resident over the slot's steps
    o_ref,        # out (TH, P, N): the same rows of the same buffer
    *,
    heads_per_group: int,
):
    b, t = pl.program_id(0), pl.program_id(1)
    TH, P, _ = s_ref.shape
    heads = dtx_ref.shape[-1]
    f32 = jnp.float32
    act = active_ref[b] != 0
    dtx = dtx_ref[0]                                         # (P, heads)
    lane = jax.lax.broadcasted_iota(jnp.int32, (P, heads), 1)

    @pl.when(t == 0)
    def _():
        y_ref[0] = jnp.zeros((P, heads), f32)

    def head(hh, y):
        h = t * TH + hh
        g = h // heads_per_group
        mine = lane == h
        old = s_ref[hh].astype(f32)                          # (P, N)
        # dtx[b, h] down the sublanes, the same in every lane
        col = jnp.sum(jnp.where(mine, dtx, 0.0), axis=1, keepdims=True)
        new = old * decay_ref[b * heads + h] + col * b_ref[0, pl.ds(g, 1), :]
        o_ref[hh] = jnp.where(act, new, old).astype(o_ref.dtype)
        out = jnp.sum(new * c_ref[0, pl.ds(g, 1), :], axis=1, keepdims=True)
        return jnp.where(mine, out, y)

    # Mosaic's loop unrolls whole or not at all: UNROLL heads an iteration
    U = max(d for d in range(1, UNROLL + 1) if TH % d == 0)

    def heads_of(k, y):
        for u in range(U):
            y = head(k * U + u, y)
        return y

    y_ref[0] = jax.lax.fori_loop(0, TH // U, heads_of, y_ref[0])


def tile_heads(heads: int, head_dim: int, state: int, itemsize: int) -> int:
    """Heads of one grid step: the largest divisor of ``heads`` whose
    ``(TH, head_dim, state)`` tile is at most :data:`TILE_BYTES`."""
    fit = max(1, TILE_BYTES // (head_dim * state * itemsize))
    return max(d for d in range(1, heads + 1) if heads % d == 0 and d <= fit)


def ssm_state_step(
    ssm: jax.Array,      # (L, B, heads, P, N)
    layer,               # () int32: which layer of the stack
    decay: jax.Array,    # (B, heads) f32
    dtx: jax.Array,      # (B, heads, P) f32
    Bm: jax.Array,       # (B, groups, N) f32
    Cm: jax.Array,       # (B, groups, N) f32
    active: jax.Array,   # (B,) bool
    *,
    kernel: str = "xla",
) -> tuple[jax.Array, jax.Array]:
    """Layer ``layer``'s rows of ``ssm`` advanced one token, in place.
    Returns ``(y (B, heads, P) f32, ssm)``. ``kernel`` is the engine's one
    selection for the decode program's kernels: ``"xla"``, ``"pallas"`` or
    ``"pallas-interpret"``."""
    if kernel == "xla":
        return ssm_state_step_xla(ssm, layer, decay, dtx, Bm, Cm, active)
    if kernel not in ("pallas", "pallas-interpret"):
        raise ValueError(f"ssm_state_step: unknown kernel {kernel!r}")
    _, B, heads, P, N = ssm.shape
    groups = Bm.shape[1]
    TH = tile_heads(heads, P, N, ssm.dtype.itemsize)
    tile = pl.BlockSpec(
        (None, None, TH, P, N),
        lambda b, t, layer, active: (layer[0], b, t, 0, 0))
    per_slot = lambda rows, lanes: pl.BlockSpec(  # noqa: E731
        (1, rows, lanes), lambda b, t, layer, active: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, heads // TH),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            per_slot(P, heads), per_slot(groups, N), per_slot(groups, N),
            tile,
        ],
        out_specs=[per_slot(P, heads), tile],
    )
    y, ssm = pl.pallas_call(
        functools.partial(_ssm_state_kernel, heads_per_group=heads // groups),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, P, heads), jnp.float32),
            jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
        ],
        # operands count the two prefetched scalars: the stack is the sixth
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            # a slot's head tiles in order: y's panel stays resident
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=4 * TILE_BYTES + 8 * 1024 * 1024,
        ),
        interpret=(kernel == "pallas-interpret"),
        name="ssm_state_step",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), active.astype(jnp.int32),
        decay.reshape(-1), dtx.swapaxes(1, 2), Bm, Cm, ssm,
    )
    return y.swapaxes(1, 2), ssm
