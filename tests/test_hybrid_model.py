"""The hybrid family's mixers (models/hybrid.py, the dropless routing of
models/moe.py) against plain spellings of the same equations, in float32 on
the CPU: the chunked prefill scan, the sequential recurrence and the
step-by-step decode are one function; padding and batch neighbours change
nothing; routing drops nothing and depends on no other row; the shares of
one deployment add up to the uncut layer."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models import moe
from langstream_tpu.models.hybrid import (
    HybridConfig,
    hybrid_prefill_paged,
    init_hybrid_params,
    init_hybrid_pool,
    init_hybrid_state,
    mamba_prefill,
    mamba_step,
    moe_mixer,
    ssd_chunked,
)
from langstream_tpu.models.paged import PagedLayout

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


@pytest.fixture(scope="module")
def c():
    return dataclasses.replace(HybridConfig.tiny(max_seq_len=256),
                               dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(c):
    return init_hybrid_params(c)


def layer(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def sequential(x, dt, A, Bm, Cm):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T; y_t = h_t C_t, one
    position after another, in numpy float64."""
    Bsz, T, h, p = x.shape
    g, n = Bm.shape[2:]
    group = np.arange(h) // (h // g)
    state = np.zeros((Bsz, h, p, n))
    ys = np.zeros((Bsz, T, h, p))
    for t in range(T):
        decay = np.exp(dt[:, t] * A)[:, :, None, None]
        state = decay * state + (dt[:, t, :, None] * x[:, t])[..., None] \
            * Bm[:, t, group][:, :, None, :]
        ys[:, t] = np.einsum("bhpn,bhn->bhp", state, Cm[:, t, group])
    return ys, state


@pytest.mark.parametrize("tokens, chunk", [(48, 16), (64, 16), (16, 16), (8, 16)])
def test_the_chunked_scan_is_the_sequential_recurrence(tokens, chunk):
    rng = np.random.default_rng(tokens)
    h, p, g, n = 8, 8, 2, 16
    x = rng.normal(size=(2, tokens, h, p)).astype(np.float32)
    dt = rng.uniform(0.001, 0.3, size=(2, tokens, h)).astype(np.float32)
    A = -rng.uniform(1, 16, size=h).astype(np.float32)
    Bm = rng.normal(size=(2, tokens, g, n)).astype(np.float32)
    Cm = rng.normal(size=(2, tokens, g, n)).astype(np.float32)
    y, state = ssd_chunked(*map(jnp.asarray, (x, dt, A, Bm, Cm)), chunk)
    want_y, want_state = sequential(*(a.astype(np.float64)
                                      for a in (x, dt, A, Bm, Cm)))
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-5)


def test_prefill_then_steps_is_one_long_prefill(c, params):
    """State and convolution tail after a prefill of n tokens, advanced one
    token at a time, are those of a prefill of the longer sequence; and the
    outputs along the way are the same."""
    lp = layer(params["mamba"], 1)
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.normal(size=(2, 64, c.hidden)), jnp.float32)
    whole, state_w, tail_w = mamba_prefill(c, lp, u, jnp.asarray([64, 64]))
    out, state, tail = mamba_prefill(c, lp, u[:, :32], jnp.asarray([27, 32]))
    state, tail = state[None], tail[None]      # a stack of this one layer
    # row 0 stops at 27 inside the bucket of 32: advance it from there
    for t in range(27, 64):
        active = jnp.asarray([True, t >= 32])
        col = jnp.stack([u[0, t], u[1, max(t, 32)]])
        step_out, state, tail = mamba_step(c, lp, col, state, tail, 0, active)
        np.testing.assert_allclose(step_out[0], whole[0, t], rtol=2e-4, atol=2e-5)
        if t >= 32:
            np.testing.assert_allclose(step_out[1], whole[1, t], rtol=2e-4,
                                       atol=2e-5)
    np.testing.assert_allclose(out[0, :27], whole[0, :27], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(state[0], state_w, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(tail[0], tail_w, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
def test_an_idle_slot_keeps_its_state(c, params, kernel):
    lp = layer(params["mamba"], 0)
    rng = np.random.default_rng(6)
    state = jnp.asarray(rng.normal(size=(3, 2, 8, 8, 16)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(3, 2, 3, c.conv_dim)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(2, c.hidden)), jnp.float32)
    _, new_state, new_tail = mamba_step(
        c, lp, u, state, tail, 1, jnp.asarray([True, False]), kernel)
    assert not np.allclose(new_state[1, 0], state[1, 0])
    np.testing.assert_array_equal(new_state[1, 1], state[1, 1])
    np.testing.assert_array_equal(new_tail[1, 1], tail[1, 1])
    for other in (0, 2):                      # the other layers' rows stay
        np.testing.assert_array_equal(new_state[other], state[other])
        np.testing.assert_array_equal(new_tail[other], tail[other])


def prefill(c, params, rows, bucket, slots=4):
    layout = PagedLayout(block_size=16, num_blocks=1 + len(rows) * 16,
                         max_blocks_per_slot=16)
    pool_k, pool_v = init_hybrid_pool(c, layout)
    tokens = np.zeros((len(rows), bucket), np.int32)
    for i, row in enumerate(rows):
        tokens[i, : len(row)] = row
    tables = 1 + np.arange(len(rows) * 16, dtype=np.int32).reshape(len(rows), 16)
    return jax.jit(lambda *a: hybrid_prefill_paged(c, *a))(
        params, jnp.asarray(tokens),
        jnp.asarray([len(r) for r in rows], jnp.int32), pool_k, pool_v,
        init_hybrid_state(c, slots), jnp.asarray(tables),
        jnp.arange(len(rows), dtype=jnp.int32))


def decode_chunk_by(c, params, kernel, steps=6):
    """Three prompts prefilled, then one chunk of ``steps`` greedy decode
    steps with the fourth slot idle, the decode program's kernels (the paged
    read and the state's pass) lowered as ``kernel`` says. Returns ``(tokens
    (steps, slots), log-probabilities, the state before, the state after)``."""
    from langstream_tpu.models.hybrid import hybrid_decode_chunk_paged

    rng = np.random.default_rng(11)
    rows = [rng.integers(0, c.vocab_size, size=n) for n in (21, 32, 9)]
    logits, pool_k, pool_v, state, _ = prefill(c, params, rows, 32)
    tables = np.zeros((4, 16), np.int32)
    tables[:3] = 1 + np.arange(48).reshape(3, 16)
    first = np.zeros(4, np.int32)
    first[:3] = np.asarray(logits).argmax(-1)
    lengths = np.asarray([21, 32, 9, 0], np.int32)
    out = jax.jit(lambda pk, pv, st: hybrid_decode_chunk_paged(
        c, params, jnp.asarray(first), jnp.asarray(lengths),
        jnp.asarray(lengths > 0), pk, pv, st, jnp.asarray(tables),
        lambda lg, key: (jnp.argmax(lg, -1).astype(jnp.int32),
                         jnp.max(jax.nn.log_softmax(lg), -1)),
        jax.random.PRNGKey(0), steps, num_read_blocks=3, kernel=kernel))(
        pool_k, pool_v, state)
    return np.asarray(out[0]), np.asarray(out[1]), state, out[6]


def assert_a_chunk_is_the_same_through_the_kernels(c, params):
    """``kernel="pallas-interpret"`` against ``kernel="xla"`` over a chunk:
    the scan's carry takes the kernel's aliased output step after step."""
    tokens, lps, before, after = decode_chunk_by(c, params, "xla")
    tokens_k, lps_k, _, after_k = decode_chunk_by(c, params, "pallas-interpret")
    np.testing.assert_array_equal(tokens_k, tokens)
    np.testing.assert_allclose(lps_k, lps, rtol=2e-4, atol=2e-5)
    for name in ("ssm", "conv"):
        np.testing.assert_allclose(after_k[name], after[name], rtol=2e-4, atol=2e-5)
        # every live slot's rows of every Mamba-2 layer moved; the idle
        # slot's did not
        assert not np.allclose(after_k[name][:, :3], before[name][:, :3])
        np.testing.assert_array_equal(after_k[name][:, 3], before[name][:, 3])


def test_a_decode_chunk_through_the_kernels_is_the_xla_chunk(c, params):
    assert all(c.mamba_blocks)              # the mixer in every block
    assert_a_chunk_is_the_same_through_the_kernels(c, params)


def test_a_padded_bucket_and_batch_neighbours_change_nothing(c, params):
    rng = np.random.default_rng(7)
    mine = rng.integers(0, c.vocab_size, size=37)
    others = [rng.integers(0, c.vocab_size, size=n) for n in (128, 90, 3, 77)]
    alone = prefill(c, params, [mine], 64)             # 64 rows: the dense pass
    # 5 x 128 = 640 rows: the grouped pass, reading the experts' stacks by layer
    crowd = prefill(c, params, [others[0], mine, *others[1:]], 128, slots=6)
    np.testing.assert_allclose(alone[0][0], crowd[0][1], rtol=2e-4, atol=2e-5)
    for key in ("ssm", "conv"):
        np.testing.assert_allclose(alone[3][key][:, 0], crowd[3][key][:, 1],
                                   rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(alone[4][:, 0, :37], crowd[4][:, 1, :37])
    # slot 5 was no row of either batch: its state stays zero
    assert not np.asarray(crowd[3]["ssm"][:, 5]).any()


# -- dropless routing ------------------------------------------------------


def loop_over_experts(x, experts, weights, w_up, w_down, first):
    out = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for e, w in zip(experts[t], weights[t]):
            if first <= e < first + w_up.shape[0]:
                up = np.maximum(w_up[e - first] @ x[t], 0.0) ** 2
                out[t] += w * (up @ w_down[e - first])
    return out


@pytest.fixture(scope="module")
def routed():
    rng = np.random.default_rng(11)
    T, H, I, E, held, k = 600, 32, 24, 16, 4, 3
    x = rng.normal(size=(T, H)).astype(np.float32)
    router = rng.normal(size=(H, E)).astype(np.float32) / np.sqrt(H)
    bias = rng.uniform(-0.2, 0.2, size=E).astype(np.float32)
    w_up = rng.normal(size=(held, I, H)).astype(np.float32) / np.sqrt(H)
    w_down = rng.normal(size=(held, I, H)).astype(np.float32) / np.sqrt(I)
    experts, weights = moe.sigmoid_topk_routing(
        jnp.asarray(x), jnp.asarray(router), jnp.asarray(bias), k, 2.5)
    return x, np.asarray(experts), np.asarray(weights), w_up, w_down


def test_routing_follows_the_published_rule(routed):
    x, experts, weights, _, _ = routed
    assert experts.shape == (600, 3) and weights.shape == (600, 3)
    assert all(len(set(row)) == 3 for row in experts)     # distinct winners
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)


@pytest.mark.parametrize("first", [0, 4, 12])
@pytest.mark.parametrize("path", ["dense", "grouped", "grouped-stacked"])
def test_both_passes_equal_a_loop_over_the_chosen_held_experts(routed, path, first):
    x, experts, weights, w_up, w_down = routed
    stack = lambda w: jnp.stack([jnp.zeros_like(w), jnp.asarray(w)])  # noqa: E731
    fn = {
        "dense": moe.dropless_experts_dense,
        "grouped": lambda *a: moe.dropless_experts_grouped(*a, block_rows=64),
        # the experts' weights as layer 1 of a stack of two
        "grouped-stacked": lambda x, e, w, up, down, f: moe.dropless_experts_grouped(
            x, e, w, stack(up), stack(down), f, block_rows=64, layer=1),
    }[path]
    out, load = jax.jit(fn, static_argnums=5)(
        *map(jnp.asarray, (x, experts, weights, w_up, w_down)), first)
    want = loop_over_experts(x.astype(np.float64), experts, weights,
                             w_up.astype(np.float64), w_down.astype(np.float64),
                             first)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(
        load, [(experts == first + e).sum() for e in range(4)])


def test_a_row_does_not_depend_on_its_batch_neighbours(routed):
    x, experts, weights, w_up, w_down = routed
    args = lambda rows: map(jnp.asarray, (  # noqa: E731
        x[rows], experts[rows], weights[rows], w_up, w_down))
    full, _ = moe.dropless_experts_grouped(*args(slice(None)), 4, block_rows=64)
    few, _ = moe.dropless_experts_grouped(*args(slice(100, 420)), 4, block_rows=64)
    one, _ = moe.dropless_experts_dense(*args(slice(123, 124)), 4)
    np.testing.assert_allclose(few, full[100:420], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(one[0], full[123], rtol=1e-5, atol=1e-6)


def test_rows_that_do_not_count_route_nowhere(routed):
    x, experts, weights, w_up, w_down = routed
    valid = np.arange(600) % 3 != 0
    for fn in (moe.dropless_experts_dense, moe.dropless_experts_grouped):
        out, load = fn(*map(jnp.asarray, (x, experts, weights, w_up, w_down)),
                       0, jnp.asarray(valid))
        assert not np.asarray(out)[~valid].any()
        assert int(load.sum()) == int((experts[valid] < 4).sum())


def _kernel_case(seed=5, T=600, k=3, E=16, held=4, H=128, I=128,
                 dtype=np.float32):
    """``(x, experts (T, k) distinct, weights, w_up relu2, w_up gated,
    w_down)`` at lane-aligned widths."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(T, H)).astype(dtype)
    experts = np.argsort(rng.normal(size=(T, E)), axis=1)[:, :k].astype(np.int32)
    weights = rng.uniform(0.1, 1.0, size=(T, k)).astype(np.float32)
    up = rng.normal(size=(held, 2 * I, H)).astype(dtype) / np.sqrt(H).astype(dtype)
    down = rng.normal(size=(held, I, H)).astype(dtype) / np.sqrt(I).astype(dtype)
    return x, experts, weights, up, down


#: each case: what differs from 600 rows choosing 3 of 16 experts, 4 held
#: from the fifth on, gated, 128 rows a product (a held subset is right
#: through the kernel's pass, though served through the loop: grouped_form)
KERNEL_CASES = {
    "relu2": dict(act="relu2"),
    "gated-256-rows-a-product": dict(block_rows=256),
    "relu2-256-rows-a-product": dict(act="relu2", block_rows=256),
    "rows-by-the-rule": dict(block_rows=None),
    "held-from-the-first": dict(first=0),
    "a-valid-mask": dict(valid=True),
    "rows-that-fill-no-block": dict(T=70),
    "an-expert-with-no-pair": dict(avoid=5),
    "one-expert-with-every-pair": dict(force=6),
    "no-pair-held-here": dict(force=1),
    "the-stacked-form": dict(layer=1),
    "the-stacked-form-relu2-masked": dict(layer=1, act="relu2", valid=True),
    "expert-width-in-tiles": dict(I=384, weight_tile_bytes=3 * 128 * 128 * 4),
    "expert-width-in-tiles-relu2": dict(
        I=384, act="relu2", weight_tile_bytes=2 * 128 * 128 * 4),
    "chunks-of-the-tokens": dict(piece_bytes=1024 * 128 * 4),
    "chunks-of-the-tokens-relu2-masked-stacked": dict(
        piece_bytes=1024 * 128 * 4, act="relu2", valid=True, layer=1),
    "parts-of-a-chunk-s-tokens": dict(part_bytes=64 * 3 * 128 * 4),
    "chunks-and-parts": dict(
        piece_bytes=1024 * 128 * 4, part_bytes=64 * 3 * 128 * 4, valid=True),
    "chunks-one-expert-with-every-pair": dict(
        piece_bytes=1024 * 128 * 4, force=6, T=900),
    "every-expert-held": dict(first=0, held=16),
    "every-expert-held-relu2-256-rows": dict(
        first=0, held=16, act="relu2", block_rows=256, valid=True),
    "bfloat16": dict(dtype="bfloat16", valid=True),
    "bfloat16-relu2-chunks": dict(
        dtype="bfloat16", act="relu2", piece_bytes=1024 * 128 * 2),
    "rows-that-fill-no-block-relu2-stacked": dict(T=70, act="relu2", layer=1),
    "every-expert-held-one-with-no-pair": dict(first=0, held=16, avoid=5),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_kernel_pass_equals_the_dense_pass_and_the_loop(monkeypatch, case):
    """``kernel="pallas-interpret"`` (models/moe.py ``_grouped_by_kernel``,
    ops/grouped_experts.py) against ``dropless_experts_dense`` and against
    the ``"xla"`` grouped form: the result, the load, and rows that do not
    count exactly zero."""
    from langstream_tpu.ops import grouped_experts as ge

    o = dict(act="silu_gated", block_rows=128, first=4, T=600, I=128,
             dtype="float32")
    o.update(KERNEL_CASES[case])
    if "weight_tile_bytes" in o:
        monkeypatch.setattr(ge, "WEIGHT_TILE_BYTES", o["weight_tile_bytes"])
        assert ge.plan(128, o["I"], o["act"] == "silu_gated", 4)["i_tiles"] == 3
    if "piece_bytes" in o:
        monkeypatch.setattr(moe, "GROUP_PIECE_BYTES", o["piece_bytes"])
    if "part_bytes" in o:
        monkeypatch.setattr(moe, "GROUP_PART_BYTES", o["part_bytes"])
    x, experts, weights, up, down = _kernel_case(
        T=o["T"], I=o["I"], held=o.get("held", 4))
    if o["act"] == "relu2":
        up = up[:, :o["I"]]
    if "avoid" in o:
        experts = np.where(experts == o["avoid"], 15, experts)
    if "force" in o:
        experts = np.full_like(experts, o["force"])
    valid = jnp.asarray(np.arange(o["T"]) % 3 != 0) if o.get("valid") else None
    dt = jnp.dtype(o["dtype"])
    x, up, down = (jnp.asarray(a).astype(dt) for a in (x, up, down))
    args = (x, jnp.asarray(experts), jnp.asarray(weights))
    act = moe.EXPERT_ACTS[o["act"]]
    dense, load = moe.dropless_experts_dense(
        *args, up, down, o["first"], valid, act=act)
    layer = o.get("layer")
    if layer is not None:
        up, down = (jnp.stack([jnp.zeros_like(w), w]) for w in (up, down))
    grouped = lambda kernel, block_rows: jax.jit(  # noqa: E731
        lambda *a: moe.dropless_experts_grouped(
            *a, o["first"], valid, block_rows=block_rows, layer=layer, act=act,
            kernel=kernel))(*args, up, down)
    loop, load_loop = grouped("xla", 64)
    got, load_got = grouped("pallas-interpret", o["block_rows"])
    assert got.dtype == jnp.float32 and got.shape == dense.shape
    tol = dict(rtol=2e-4, atol=2e-5) if dt == jnp.float32 else dict(
        rtol=0, atol=0.02 * float(jnp.abs(dense).max()))
    np.testing.assert_allclose(got, dense, **tol)
    np.testing.assert_allclose(got, loop, **tol)
    np.testing.assert_array_equal(load_got, load)
    np.testing.assert_array_equal(load_got, load_loop)
    if "force" not in o:
        assert float(jnp.abs(dense).max()) > 0.1
    if case == "no-pair-held-here":
        assert not np.asarray(got).any() and int(load_got.sum()) == 0
    if valid is not None:
        assert not np.asarray(got)[~np.asarray(valid)].any()


@pytest.mark.parametrize("hidden, inter, gated, i_tiles", [
    # the six expert cells' layers (bench/configs)
    (2304, 896, True, 1),       # Mellum: 12 MB an expert
    (2688, 1856, False, 1),     # Nemotron: 20 MB, and 1,856 is no whole lanes
    (4096, 768, True, 1),       # Granite: 19 MB
    (4096, 1280, True, 2),      # Solar: 31 MB
    (5120, 1536, True, 2),      # DeepSeek-V2: 47 MB
    (3072, 3072, True, 3),      # Trinity: 57 MB
])
def test_the_kernel_s_tiles_follow_the_widths(hidden, inter, gated, i_tiles):
    from langstream_tpu.ops import grouped_experts as ge

    got = ge.plan(hidden, inter, gated, 2)
    assert got == {"tile_rows": 512, "sub_rows": 128, "i_tiles": i_tiles}
    weights = (3 if gated else 2) * (inter // i_tiles) * hidden * 2
    assert weights <= ge.WEIGHT_TILE_BYTES
    assert i_tiles == 1 or weights * i_tiles > ge.WEIGHT_TILE_BYTES


@pytest.mark.parametrize("kernel, held, of, want", [
    ("pallas", 64, 64, ("pallas", 256)), ("pallas", 64, None, ("pallas", 256)),
    ("pallas-interpret", 8, 8, ("pallas-interpret", 256)),
    ("pallas", 16, 128, ("xla", 512)), ("pallas", 36, 72, ("xla", 512)),
    ("xla", 64, 64, ("xla", 512)), ("xla", 16, 128, ("xla", 512)),
])
def test_the_kernel_s_pass_is_served_where_every_expert_is_held(
        kernel, held, of, want):
    """A share keeps the loop and its bound, as before the kernel: a
    ``nemotron_h`` prefill with the kernel's pass never returned on the chip
    (models/moe.py ``grouped_form``)."""
    assert moe.grouped_form(kernel, held, of) == want


def test_a_share_s_prefill_traces_the_loop_whatever_the_selection(routed):
    x, experts, weights, w_up, w_down = routed      # 600 rows, 4 of 16 held
    args = tuple(map(jnp.asarray, (x, experts, weights, w_up, w_down)))
    by = lambda **kw: str(jax.make_jaxpr(  # noqa: E731
        lambda *a: moe.dropless_experts(*a, 4, **kw))(*args))
    assert "grouped_experts" not in by(kernel="pallas-interpret", of=16)
    assert by(kernel="pallas-interpret", of=16) == by(kernel="xla", of=16)
    assert "grouped_experts" in by(kernel="pallas-interpret", of=4)


def test_an_unknown_selection_is_not_taken_for_the_experts_kernel(routed):
    x, experts, weights, w_up, w_down = routed
    with pytest.raises(ValueError, match="unknown kernel"):
        moe.dropless_experts_grouped(
            *map(jnp.asarray, (x, experts, weights, w_up, w_down)), 0,
            kernel="mosaic")


def test_the_shares_add_up_to_the_uncut_reference_layer(c):
    """Four chips of two experts each: what every share's routed experts
    give, plus the shared expert counted once, is the reference's layer
    over all eight experts."""
    from reference import hybrid_ssm_moe as reference

    shares = [dataclasses.replace(c, expert_first=first)
              for first in range(0, c.experts, c.experts_held)]
    assert len(shares) == 4
    trees = [init_hybrid_params(s)["moe"] for s in shares]
    whole = {k: trees[0][k][0] for k in trees[0]}
    for k in ("w_up", "w_down"):        # the same eight experts, by global id
        whole[k] = jnp.concatenate([t[k][0] for t in trees])
        assert whole[k].shape[0] == c.experts
    rng = np.random.default_rng(13)
    h = jnp.asarray(rng.normal(size=(40, c.hidden)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.experts(h, whole, c, first=0, held=c.experts)
        shared_only, _ = reference.experts(h, whole, c, first=0, held=0)
    total = np.zeros_like(np.asarray(want))
    for share, tree in zip(shares, trees):
        out, load, _ = moe_mixer(share, layer(tree, 0), h,
                                 jnp.ones((40,), bool))
        total += np.asarray(out) - np.asarray(shared_only)
    assert np.abs(np.asarray(want) - np.asarray(shared_only)).max() > 0.1
    np.testing.assert_allclose(total + np.asarray(shared_only), want,
                               rtol=2e-4, atol=2e-5)


# -- the commit is the one every family uses -------------------------------


def parents_hybrid_write_rows(pool, rows, block_tables, starts, valid,
                             kernel="xla"):
    """The hybrid's own commit as the parent (8e35ac5) had it in
    ``models/hybrid.py``: what ``models/paged.py`` ``write_rows`` has to
    trace to for a plain-array pool."""
    L, nb, bs, KhD = pool.shape
    B, T = rows.shape[1:3]
    pos = starts[:, None] + jnp.arange(T)[None, :]
    block = jnp.take_along_axis(
        block_tables, jnp.clip(pos // bs, 0, block_tables.shape[1] - 1), axis=1)
    flat = jnp.where(valid, block * bs + pos % bs, 0).reshape(-1)
    index = (jnp.arange(L)[:, None] * (nb * bs) + flat[None, :]).reshape(-1)
    return pool.reshape(L * nb * bs, KhD).at[index].set(
        rows.reshape(L * B * T, KhD)).reshape(pool.shape)


def _hybrid_programs(c, params):
    """Jaxpr and optimised HLO (without the metadata that names source
    lines) of the tiny prefill and decode chunk, pools and state donated."""
    from test_profile_scopes import instructions

    from langstream_tpu.models.hybrid import hybrid_decode_chunk_paged

    layout = PagedLayout(block_size=16, num_blocks=9, max_blocks_per_slot=4)
    pool_k, pool_v = init_hybrid_pool(c, layout)
    state = init_hybrid_state(c, 2)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)

    def prefill(params, pool_k, pool_v, state):
        return hybrid_prefill_paged(
            c, params, jnp.zeros((2, 32), jnp.int32),
            jnp.asarray([20, 32], jnp.int32), pool_k, pool_v, state, tables,
            jnp.arange(2, dtype=jnp.int32))

    def chunk(params, pool_k, pool_v, state):
        return hybrid_decode_chunk_paged(
            c, params, jnp.zeros((2,), jnp.int32),
            jnp.asarray([20, 33], jnp.int32), jnp.asarray([True, True]),
            pool_k, pool_v, state, tables,
            lambda logits, key: (jnp.argmax(logits, -1).astype(jnp.int32),
                                 jnp.zeros(logits.shape[:1], jnp.float32)),
            jax.random.PRNGKey(0), 4, num_read_blocks=3, return_packed=True)

    out = {}
    for fn in (prefill, chunk):
        args = (params, pool_k, pool_v, state)
        text = jax.jit(fn, donate_argnums=(1, 2, 3)).lower(
            *args).compile().as_text()
        out[fn.__name__] = (str(jax.make_jaxpr(fn)(*args)), instructions(text))
    return out


def test_the_shared_commit_leaves_the_hybrid_programs_as_they_were(
        c, params, monkeypatch):
    """``models/hybrid.py`` no longer has a ``write_rows`` of its own; with
    the one of ``models/paged.py`` its prefill and decode chunk are, jaxpr
    and optimised HLO instruction for instruction, what they were with the
    parent's own."""
    from test_paged import a_pool_at_a_time

    from langstream_tpu.models import hybrid, paged

    assert hybrid.write_rows_pair is paged.write_rows_pair
    assert "def write_rows" not in open(hybrid.__file__).read()
    shared = _hybrid_programs(c, params)
    monkeypatch.setattr(hybrid, "write_rows_pair",
                        a_pool_at_a_time(parents_hybrid_write_rows))
    own = _hybrid_programs(c, params)
    for name in ("prefill", "chunk"):
        assert "scatter" in shared[name][0]
        assert shared[name][0] == own[name][0], name
        assert shared[name][1] == own[name][1], name
