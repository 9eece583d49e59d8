"""One decode step of a gated delta-rule layer's recurrent state (Pallas TPU).

A decode step of layer ``i`` does, for every slot ``b`` and head ``h`` of the
stacked state ``delta (delta-rule layers, slots, heads, dv, dk)``, which holds
the TRANSPOSE ``T = S^T`` of the paper's ``S (dk, dv)``::

    T'  = T * a[b, h][None, :]                   decay, one a key channel
    u   = T' @ k[b, h]                           what the state holds for k
    new = T' + (beta[b, h] * (v[b, h] - u))[:, None] * k[b, h][None, :]
    o[b, h] = new @ q[b, h]
    delta[i, b, h] = new if active[b] else delta[i, b, h]

in float32 throughout: ``S_t = (I - beta k k^T) Diag(a) S_{t-1} + beta k v^T``
and ``o_t = S_t^T q_t``. Written in XLA (:func:`delta_state_step` with
``kernel="xla"``: the CPU path and the tests' reference) the state crosses
HBM once for each of the two products and the update; the kernel
(``kernel="pallas"``) reads each ``(dv, dk)`` tile once, writes it back **to
the same buffer** and forms ``u`` and ``o`` while the tile is in VMEM.

It is ``ops/ssm_state.py``'s kernel with another rule behind the same
stacked-state layout: the whole stack is the operand and the output
(``input_output_aliases``), the layer's index a prefetched scalar that the
index maps read, only layer ``i``'s blocks are visited, and a slot that is
not active is read and written back bit for bit.

The state is kept transposed so that the three vectors indexed by the key
channel (``a``, ``k``, ``q``) are rows, spread down the sublanes by the load
that reads them; what remains to cross lanes are the two products (a sum
along lanes each), ``v``'s column taken from a ``(dv, heads)`` panel by a
lane mask and ``o``'s put into one, as ``ssm_state.py`` does for its two.

Shapes:
  delta   (L, B, heads, dv, dk)  [stays in HBM; aliased to the output]
  layer   () int32               [scalar prefetch]
  active  (B,) bool              [scalar prefetch, as int32]
  beta    (B, heads) f32         [SMEM: one scalar a tile]
  a, k, q (B, heads, dk) f32
  v       (B, heads, dv) f32
  -> o (B, heads, dv) f32, delta

Grid ``(B, heads // TH)``, ``TH`` from :func:`ssm_state.tile_heads`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from langstream_tpu.ops.ssm_state import TILE_BYTES, UNROLL, tile_heads


def delta_state_step_xla(delta, layer, a, k, q, v, beta, active):
    """The step in XLA: what :func:`delta_state_step` computes, and its
    reference. Returns ``(o (B, heads, dv) f32, delta)``. The products are
    multiplies and sums, not dots: float32 whatever the backend's matmul
    precision."""
    f32 = jnp.float32
    state = jax.lax.dynamic_index_in_dim(delta, layer, keepdims=False)
    decayed = state.astype(f32) * a[:, :, None, :]
    u = jnp.sum(decayed * k[:, :, None, :], axis=-1)
    new = decayed + (beta[..., None] * (v - u))[..., None] * k[:, :, None, :]
    o = jnp.sum(new * q[:, :, None, :], axis=-1)
    delta = jax.lax.dynamic_update_index_in_dim(
        delta,
        jnp.where(active[:, None, None, None], new.astype(delta.dtype), state),
        layer, 0)
    return o, delta


def _delta_state_kernel(
    layer_ref,    # SMEM (1,) int32 (read by the index maps)
    active_ref,   # SMEM (B,) int32
    beta_ref,     # SMEM (B * heads,) f32
    a_ref,        # (1, heads, dk): every head of the slot
    k_ref,        # (1, heads, dk)
    q_ref,        # (1, heads, dk)
    v_ref,        # (1, dv, heads): heads on lanes
    s_ref,        # (TH, dv, dk): this step's heads of layer, slot
    y_ref,        # out (1, dv, heads), resident over the slot's steps
    o_ref,        # out (TH, dv, dk): the same rows of the same buffer
):
    b, t = pl.program_id(0), pl.program_id(1)
    TH, Dv, _ = s_ref.shape
    heads = v_ref.shape[-1]
    f32 = jnp.float32
    act = active_ref[b] != 0
    v = v_ref[0]                                             # (dv, heads)
    lane = jax.lax.broadcasted_iota(jnp.int32, (Dv, heads), 1)

    @pl.when(t == 0)
    def _():
        y_ref[0] = jnp.zeros((Dv, heads), f32)

    def head(hh, y):
        h = t * TH + hh
        mine = lane == h
        old = s_ref[hh].astype(f32)                          # (dv, dk)
        k = k_ref[0, pl.ds(h, 1), :]                         # (1, dk)
        decayed = old * a_ref[0, pl.ds(h, 1), :]
        u = jnp.sum(decayed * k, axis=1, keepdims=True)      # (dv, 1)
        col = jnp.sum(jnp.where(mine, v, 0.0), axis=1, keepdims=True)
        new = decayed + (beta_ref[b * heads + h] * (col - u)) * k
        o_ref[hh] = jnp.where(act, new, old).astype(o_ref.dtype)
        out = jnp.sum(new * q_ref[0, pl.ds(h, 1), :], axis=1, keepdims=True)
        return jnp.where(mine, out, y)

    U = max(d for d in range(1, UNROLL + 1) if TH % d == 0)

    def heads_of(i, y):
        for j in range(U):
            y = head(i * U + j, y)
        return y

    y_ref[0] = jax.lax.fori_loop(0, TH // U, heads_of, y_ref[0])


def delta_state_step(
    delta: jax.Array,    # (L, B, heads, dv, dk)
    layer,               # () int32: which layer of the stack
    a: jax.Array,        # (B, heads, dk) f32 in (0, 1]
    k: jax.Array,        # (B, heads, dk) f32
    q: jax.Array,        # (B, heads, dk) f32
    v: jax.Array,        # (B, heads, dv) f32
    beta: jax.Array,     # (B, heads) f32
    active: jax.Array,   # (B,) bool
    *,
    kernel: str = "xla",
) -> tuple[jax.Array, jax.Array]:
    """Layer ``layer``'s rows of ``delta`` advanced one token, in place.
    Returns ``(o (B, heads, dv) f32, delta)``. ``kernel`` is the engine's one
    selection for the decode program's kernels: ``"xla"``, ``"pallas"`` or
    ``"pallas-interpret"``."""
    if kernel == "xla":
        return delta_state_step_xla(delta, layer, a, k, q, v, beta, active)
    if kernel not in ("pallas", "pallas-interpret"):
        raise ValueError(f"delta_state_step: unknown kernel {kernel!r}")
    _, B, heads, Dv, Dk = delta.shape
    TH = tile_heads(heads, Dv, Dk, delta.dtype.itemsize)
    tile = pl.BlockSpec(
        (None, None, TH, Dv, Dk),
        lambda b, t, layer, active: (layer[0], b, t, 0, 0))
    per_slot = lambda rows, lanes: pl.BlockSpec(  # noqa: E731
        (1, rows, lanes), lambda b, t, layer, active: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, heads // TH),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            per_slot(heads, Dk), per_slot(heads, Dk), per_slot(heads, Dk),
            per_slot(Dv, heads), tile,
        ],
        out_specs=[per_slot(Dv, heads), tile],
    )
    f32 = jnp.float32
    y, delta = pl.pallas_call(
        _delta_state_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Dv, heads), f32),
            jax.ShapeDtypeStruct(delta.shape, delta.dtype),
        ],
        # operands count the two prefetched scalars: the stack is the eighth
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            # a slot's head tiles in order: o's panel stays resident
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=4 * TILE_BYTES + 8 * 1024 * 1024,
        ),
        interpret=(kernel == "pallas-interpret"),
        name="delta_state_step",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), active.astype(jnp.int32),
        beta.astype(f32).reshape(-1), a.astype(f32), k.astype(f32),
        q.astype(f32), v.astype(f32).swapaxes(1, 2), delta,
    )
    return y.swapaxes(1, 2), delta
