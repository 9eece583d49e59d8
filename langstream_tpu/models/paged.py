"""Paged KV cache: block-table layout + pure read/write/attention helpers.

Why paged: the dense cache ``(L, slots, S, Kh, D)`` reserves
``slots × max_seq_len`` rows of HBM up front, so slot count is capped by the
*worst-case* sequence length even when every live request is short. Paging
(vLLM-style) slices the cache into fixed ``block_size``-row blocks shared
from one pool; a slot holds ``ceil(len/bs)`` blocks, mapped by a small
host-managed block table. Capacity then scales with *actual* tokens
resident, not slots × S (reference parity: SURVEY §7 build-order item 6).

TPU-first layout: the pool is ``(L, num_blocks, block_size, Kh*D)`` — the
trailing two dims ``(block_size, Kh*D)`` are clean (8,128)-multiples, so
both XLA scatters/gathers and the Pallas kernel DMA whole tiles. All
functions here are jit-pure; the host side (free lists, reservations) lives
in :class:`BlockManager`.

Read paths:
- :func:`gather_kv` — XLA read: gathers a slot's blocks from the stacked
  pool into a dense window. Correct everywhere (CPU tests, sharded meshes,
  int8 pools); the window is written once and read once by the product
  that contracts it.
- :mod:`langstream_tpu.ops.paged_attention` — Pallas kernel that walks the
  block table directly via scalar prefetch; no gathered copy. Single-chip
  TPU fast path.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Static geometry of the paged pool."""

    block_size: int
    num_blocks: int
    max_blocks_per_slot: int

    @classmethod
    def for_model(
        cls,
        max_seq_len: int,
        slots: int,
        block_size: int = 64,
        hbm_fraction_of_dense: float = 0.5,
        num_blocks: int | None = None,
    ) -> "PagedLayout":
        """Size the pool to ``hbm_fraction_of_dense`` of what the dense
        cache would reserve (the whole point: same slot count, less HBM —
        or more slots at the same HBM)."""
        max_blocks_per_slot = -(-max_seq_len // block_size)
        if num_blocks is None:
            dense_rows = slots * max_seq_len
            num_blocks = max(
                slots + 1, int(dense_rows * hbm_fraction_of_dense) // block_size
            )
        return cls(
            block_size=block_size,
            num_blocks=num_blocks,
            max_blocks_per_slot=max_blocks_per_slot,
        )


def init_paged_kv_cache(
    config, layout: PagedLayout
) -> tuple[jax.Array, jax.Array]:
    """Pool arrays ``(L, num_blocks, block_size, Kh*D)`` for K and V."""
    return init_kv_pool(config, layout, config.layers)


def init_latent_pool(config, layout: PagedLayout) -> tuple[jax.Array, None]:
    """The pool of a latent-attention model (models/latent.py): ONE array
    ``(L, num_blocks, block_size, row_width)`` whose row is a position's
    compressed latent and rotary key, with no head axis, key and value in
    the same bytes; nothing stands in the value pool's place. Blocks, tables
    and :func:`write_rows` know nothing of a row's width."""
    c = config
    shape = (c.layers, layout.num_blocks, layout.block_size, c.row_width)
    return jnp.zeros(shape, dtype=c.dtype), None


def init_kv_pool(config, layout: PagedLayout, layers: int):
    """``(K, V)`` pools ``(layers, layout.num_blocks, block_size, Kh*D)`` of
    ONE kind of attention layer of a model that has two (models/swa.py: the
    layers that attend every row, and the layers that attend the last
    ``window`` rows, whose blocks a slot holds as a ring in a pool and a
    layout of their own; :class:`BlockManager`). Each pool has its own block
    0 as scratch."""
    c = config
    shape = (layers, layout.num_blocks, layout.block_size,
             c.kv_heads * c.head_dim)
    return jnp.zeros(shape, c.dtype), jnp.zeros(shape, c.dtype)


def init_paged_kv_cache_int8(
    config, layout: PagedLayout
) -> tuple[dict, dict]:
    """int8 pools: data as :func:`init_paged_kv_cache` plus one f32 scale
    per (block row, kv-head) — the paged twin of
    :func:`langstream_tpu.models.kvquant.init_kv_cache_int8`."""
    c = config
    base = (c.layers, layout.num_blocks, layout.block_size)
    make = lambda: {
        "q": jnp.zeros(base + (c.kv_heads * c.head_dim,), dtype=jnp.int8),
        "s": jnp.zeros(base + (c.kv_heads,), dtype=jnp.float32),
    }
    return make(), make()


def paged_cache_spec(mesh_axes: tuple[str, ...]):
    """Pool (L, nb, bs, Kh*D): the trailing fused head axis shards on tp.
    Blocks are NOT sharded on dp (any slot may use any block), so paged
    serving shards the model, not the pool rows."""
    from jax.sharding import PartitionSpec as P

    tp = "tp" if "tp" in mesh_axes else None
    return P(None, None, None, tp)


# ---------------------------------------------------------------------------
# jit-pure read/write
# ---------------------------------------------------------------------------


def write_rows(
    cache,                  # (L, nb, bs, KhD) array, or int8 {"q","s"} pools
    rows: jax.Array,        # (L, B, T, KhD) — new bf16 K or V rows per slot
    block_tables: jax.Array,  # (B, max_blocks) int32
    starts,                 # (B,) first sequence position of rows[;, b]
    valid: jax.Array,       # (B, T) bool — rows beyond a slot's true count
    kernel: str = "xla",    # the engine's one kernel selection, handed down
):
    """ONE pool's commit (the K/V handoff's import, the tests):
    :func:`write_rows_pair` of a pair of one."""
    return write_rows_pair(
        (cache,), (rows,), block_tables, starts, valid, kernel)[0]


def write_rows_pair(
    caches: tuple,          # the pools of ONE kind: (K, V), or (latent,)
    rows,                   # as many (L, B, T, KhD) arrays of new rows,
                            # drawn a pool at a time (see below)
    block_tables: jax.Array,  # (B, max_blocks) int32
    starts,                 # (B,) first sequence position of rows[;, b];
                            # None: 0, every slot's (a prefill's statement)
    valid: jax.Array,       # (B, T) bool — rows beyond a slot's true count
    kernel: str = "xla",    # the engine's one kernel selection, handed down
) -> tuple:
    """Put ``rows`` into the pools at each slot's block-mapped positions.

    The one commit of every program family (dense prefill, continuation,
    decode chunk and verify, the K/V handoff's import, the hybrid's
    attention layers), under the scope ``kv_commit``, in one of two forms
    (:func:`langstream_tpu.ops.pool_commit.commit_form`: the selection the
    read kernels follow, and the pool's type):

    ``"xla"`` (the CPU, every int8 pool, any mesh; the tests' reference):
    each pool is scattered as ``(L * nb * bs, tail)`` with the layer folded
    into the row index, ``l * nb * bs + row``: one scatter along the leading
    axis, in place on a donated pool. Scattered along its second axis
    (``.at[:, row]``) the compiler moves the layer axis inward and back out,
    a copy of the whole pool each way for K and for V; the reshapes here
    move nothing (``bs`` is a multiple of every row tile). Invalid rows are
    redirected to their layer's scratch row (block 0 never backs live data;
    see BlockManager) so the scatter stays shape-static, and each row is
    issued alone: 130 ns a row on the v5e whatever it holds. ``rows`` is
    drawn a pool at a time, right before the pool's scatter: a site that
    hands a generator (``(a.reshape(...) for a in (ks, vs))``) keeps what
    makes a pool's rows next to its scatter in the program's lowered text,
    where it was before the pools were committed by one call.

    ``"pallas"`` (a bf16 pool on a TPU): ONE call of ``ops/pool_commit.py``
    moves runs of rows of every pool of the kind, all layers of a run in one
    copy, in place. **``valid`` has to be ONE interval of rows a slot**
    (every caller's is: a prefill's ``[0, length)``, the window kind's last
    ``W`` rows, all or none of a decode chunk's); the rows outside it are
    skipped, so the pools are what the scatter leaves in every block but the
    scratch block 0. A prefill states that its rows begin at position 0 by
    handing ``starts`` None, and only the commit of whole tiles of ``rows``
    is traced for it (what is traced is paid at every set-up: a program's
    first warm use is its trace and lowering).

    An int8 pool quantises the rows here — write sites stay
    layout-agnostic. Rows that are ALREADY quantized (an int8 ``{"q","s"}``
    pair, e.g. a KV handoff payload from another replica's identical pool)
    scatter verbatim, so a transfer never pays a dequant/requant round trip.
    """
    from langstream_tpu.ops.pool_commit import commit_form, pool_commit

    with jax.named_scope("kv_commit"):
        form = commit_form(kernel, caches[0])
        if form == "xla":
            if starts is None:
                starts = jnp.zeros((valid.shape[0],), jnp.int32)
            return tuple(
                _scatter_rows(cache, new, block_tables, starts, valid)
                for cache, new in zip(caches, rows))
        if not isinstance(valid, jax.core.Tracer):
            held = np.asarray(valid)
            edges = np.diff(held.astype(np.int8), axis=1, prepend=0, append=0)
            if (np.abs(edges).sum(axis=1) > 2).any():
                raise ValueError(
                    "write_rows: the kernel commits ONE interval of rows a "
                    "slot; this mask has a gap")
        held = valid.astype(jnp.int32)
        lo = jax.lax.argmax(held, 1, jnp.int32)
        return pool_commit(
            caches, tuple(rows), block_tables, starts, lo,
            lo + jax.lax.reduce_sum(held, (1,)),
            interpret=form == "pallas-interpret")


def _scatter_rows(cache, rows, block_tables, starts, valid):
    """:func:`write_rows` as one XLA scatter on the folded pool."""
    quant = isinstance(cache, dict)
    L, nb, bs, KhD = (cache["q"] if quant else cache).shape
    B, T = (rows["q"] if isinstance(rows, dict) else rows).shape[1:3]
    pos = starts[:, None] + jnp.arange(T)[None, :]          # (B, T)
    # clamp: invalid rows may compute positions past the table; they're
    # redirected to scratch below, the clamp just keeps indexing in-bounds
    block = jnp.take_along_axis(
        block_tables, jnp.clip(pos // bs, 0, block_tables.shape[1] - 1), axis=1
    )
    # invalid rows land in block 0 (reserved scratch, never allocated), so
    # the scatter stays shape-static and garbage never touches live data
    flat = jnp.where(valid, block * bs + pos % bs, 0).reshape(-1)  # (B*T,)
    index = (jnp.arange(L)[:, None] * (nb * bs) + flat[None, :]).reshape(-1)

    def scatter(pool, new_rows):  # trailing dim: KhD / Kh
        tail = pool.shape[3]
        return pool.reshape(L * nb * bs, tail).at[index].set(
            new_rows.reshape(L * B * T, tail)
        ).reshape(pool.shape)

    if not quant:
        return scatter(cache, rows)
    if not isinstance(rows, dict):
        from langstream_tpu.models.kvquant import quantize_rows

        Kh = cache["s"].shape[3]
        rows = quantize_rows(rows.reshape(L, B, T, Kh, KhD // Kh))
    # pre-quantized rows (KV handoff) pass through bit-exact
    return {
        "q": scatter(cache["q"], rows["q"]),
        "s": scatter(cache["s"], rows["s"]),
    }


def gather_kv(
    cache,                    # (L, nb, bs, KhD) array or int8 {"q","s"} pool
    block_tables: jax.Array,  # (B, max_blocks)
    num_read_blocks: int,     # static: table columns to read (window bucket)
    layer=None,               # None: every layer; else one layer's index
):
    """XLA read: the first ``num_read_blocks`` blocks of every slot as a
    dense window, ``(L, B, num_read_blocks*bs, KhD)``, or with ``layer``
    (an int or a traced scalar, the decode scan's) ``(B, num_read_blocks*bs,
    KhD)`` of that layer alone (int8 pools gather data and scales alike:
    trailing dims pass through).

    One gather on the STACKED pool, indexed ``(layer, block)``: no slice of
    a layer exists beside the pool, and the window leaves with the pool's
    own minor dimension, so a product that contracts ``Kh*D`` as it lies
    (``kvquant.window_scores`` / ``window_values``) reads what the gather
    wrote and nothing relays it (ROADMAP S1). The indices are promised in
    bounds: a table holds block ids of the pool, or 0, the scratch block
    (:class:`BlockManager`), so a fill guards nothing and its select would
    pass over the whole window; rows past a slot's length are masked on
    the scores by the caller."""
    tables = block_tables[:, :num_read_blocks]               # (B, nrb)
    index = (slice(None) if layer is None else layer, tables)

    def gather(pool):
        # ([L,] B, nrb, bs, tail) -> ([L,] B, nrb*bs, tail)
        got = pool.at[index].get(mode="promise_in_bounds")
        lead = got.ndim - pool.ndim + 1
        return got.reshape(
            got.shape[:lead] + (num_read_blocks * pool.shape[2],)
            + pool.shape[3:]
        )

    if isinstance(cache, dict):
        return jax.tree.map(gather, cache)
    return gather(cache)


# ---------------------------------------------------------------------------
# host-side block management
# ---------------------------------------------------------------------------


class BlockManager:
    """Free-list + worst-case reservation accounting (no preemption needed:
    admission only passes when the request's worst case fits, while physical
    blocks are handed out lazily as generation grows).

    Block 0 is reserved as the scatter scratch target for masked writes and
    is never allocated.

    **Two kinds of layer** (``window_layout`` and ``window_ring``; a model
    whose layers attend either every row or the last W, models/swa.py): a
    second pool with its own free list and scratch block, of which a slot
    holds at most ``window_ring`` blocks (``W / block_size + 1``) whatever
    its length, as a RING: logical block ``n`` of the slot lives in its ring
    block ``n % window_ring``, taken from the free list the first time the
    slot grows into it and overwritten in place from then on (the rows it
    held lie behind every later query's window). ``tables`` is then ``[the
    full kind's columns | the window kind's columns]``, both
    ``max_blocks_per_slot`` wide and both indexed by LOGICAL block, so a
    program finds a row's block the same way in either; a ring block fills
    every column it will ever serve when it is taken, so the table of a slot
    that has grown past the ring never changes again. Admission reserves a
    request's worst case in both kinds and refuses what does not fit in
    either; release (and a preemption, which is a release) returns both.
    Why a ring and not blocks returned to the free list as the window moves
    on: the window pool is sized for every slot's ring, so a returned block
    buys no admission, and a table that changes costs an edit and an upload
    every second chunk a slot.

    **Automatic prefix caching** (vLLM-style): full blocks of committed
    prompts are content-addressed by a chained digest of their tokens.
    A new request whose prompt starts with a cached chain adopts those
    blocks read-only (refcounted — decode never writes below its start
    position, so sharing is safe) and prefills only the suffix. Cache-only
    blocks (refcount held just by the cache) are evicted LRU when the free
    list runs dry, so caching never reduces admissible capacity.
    """

    def __init__(self, layout: PagedLayout, slots: int,
                 state_bytes_per_slot: int = 0,
                 window_layout: PagedLayout | None = None,
                 window_ring: int = 0,
                 summary_window: int = 0,
                 summary_chunk: int = 0):
        self.layout = layout
        # the growth rule of a pool of chunk summaries (models/eva.py): see
        # blocks_needed
        self.summary_window = summary_window
        self.summary_chunk = summary_chunk
        self.window_layout = window_layout
        self.window_ring = window_ring if window_layout is not None else 0
        if window_layout is not None and not (
                0 < window_ring < window_layout.num_blocks):
            raise ValueError(
                f"a slot's ring of {window_ring} blocks does not fit a "
                f"window pool of {window_layout.num_blocks} (block 0 is "
                f"scratch)")
        # a hybrid model keeps a fixed-size recurrent state per slot beside
        # its pool rows (models/hybrid.py): allocated once for every slot,
        # never paged; a slot's rows are live from its admission to its
        # release (a preemption drops them: the request prefills again)
        self.state_bytes_per_slot = state_bytes_per_slot
        self._state_live = [False] * slots
        self._free = list(range(layout.num_blocks - 1, 0, -1))  # block 0 reserved
        self._reserved = 0
        # adaptive pool-shrink (docs/RESILIENCE.md): blocks withheld from
        # the admission budget after a device allocator failure. Purely a
        # LOGICAL reduction — the pool arrays stay allocated; admission
        # just reserves against a smaller usable count until the engine's
        # recovery probe restores it. Floored so the largest admissible
        # request can still ever fit (a shrunk pool must degrade, never
        # deadlock the queue).
        self._budget_reduction = 0
        # tiered prefix store hook (serving/prefixstore.py): called with
        # (digest_hex, block) when pool pressure organically evicts a
        # cached prefix block WITHOUT a demotion — the tier ledgers must
        # see every byte leave, never silently
        self.on_prefix_evict = None
        # per-slot: shared (adopted, refcounted) prefix blocks + owned tail
        self._slot_shared: list[list[int]] = [[] for _ in range(slots)]
        self._slot_blocks: list[list[int]] = [[] for _ in range(slots)]
        self._slot_reservation = [0] * slots
        width = layout.max_blocks_per_slot
        self.tables = np.zeros(
            (slots, width * (2 if window_layout is not None else 1)),
            dtype=np.int32,
        )
        # the window kind: its free list, a slot's ring blocks in ring
        # order, its reservations, and the table's second half (a view)
        self._wfree = (
            list(range(window_layout.num_blocks - 1, 0, -1))
            if window_layout is not None else [])
        self._wreserved = 0
        self._slot_ring: list[list[int]] = [[] for _ in range(slots)]
        self._slot_wreservation = [0] * slots
        self.window_tables = self.tables[:, width:]
        #: ring blocks returned to the free list at releases, ever
        self.window_blocks_released = 0
        # prefix cache: chain digest -> block id (insertion order = LRU),
        # block refcounts (slot adoptions + cache membership), reverse map,
        # and the chain topology (parent digest + child count) so eviction
        # is leaf-first — evicting a chain HEAD would orphan its cached
        # descendants (match_prefix walks from the head and stops at the
        # first miss), leaving unreachable blocks pinned in the pool
        self._prefix: dict[bytes, int] = {}
        self._refs: dict[int, int] = {}
        self._block_digest: dict[int, bytes] = {}
        self._parent: dict[bytes, bytes] = {}
        self._nchildren: dict[bytes, int] = {}

    # -- prefix cache --------------------------------------------------

    def _digests(self, prompt_tokens):
        """Chained content digests, one per FULL block of the prompt.
        Lazy: callers that stop early (first cache miss, table bound) pay
        only for the digests they actually walk."""
        import hashlib

        bs = self.layout.block_size
        prev = b""
        for i in range(len(prompt_tokens) // bs):
            block = prompt_tokens[i * bs : (i + 1) * bs]
            h = hashlib.blake2b(digest_size=16)
            h.update(prev)
            h.update(np.asarray(block, dtype=np.int64).tobytes())
            prev = h.digest()
            yield prev

    def chain_digests(self, prompt_tokens, limit: int | None = None):
        """The prompt's chained full-block digests as a list (the lazy
        :meth:`_digests` walk, bounded). ``limit`` defaults to the same
        ``(len(prompt)-1)//block_size`` bound :meth:`match_prefix` uses —
        at least one token must prefill to produce logits. Wait-free
        beyond the hashing itself (PFX801's T0 lookup path)."""
        bs = self.layout.block_size
        if limit is None:
            limit = (len(prompt_tokens) - 1) // bs
        out: list[bytes] = []
        for i, d in enumerate(self._digests(prompt_tokens)):
            if i >= limit:
                break
            out.append(d)
        return out

    def prefix_has(self, digest: bytes) -> bool:
        """Whether the T0 cache holds a block for this chain digest."""
        return digest in self._prefix

    def match_prefix(
        self, prompt_tokens, digests=None
    ) -> tuple[list[int], int]:
        """Longest cached chain covering at most ``len(prompt)-1`` tokens
        (at least one token must prefill to produce logits). Returns
        (blocks, reused_token_count) WITHOUT claiming them — call
        :meth:`adopt_prefix` after admission. ``digests`` lets a caller
        that already walked :meth:`chain_digests` (the tiered store's
        admission path hashes the chain once and shares it) skip
        re-hashing the prompt."""
        bs = self.layout.block_size
        limit = (len(prompt_tokens) - 1) // bs
        blocks: list[int] = []
        walk = digests if digests is not None else self._digests(prompt_tokens)
        for i, d in enumerate(walk):
            if i >= limit:
                break
            b = self._prefix.get(d)
            if b is None:
                break
            blocks.append(b)
        return blocks, len(blocks) * bs

    def adopt_prefix(self, slot: int, blocks: list[int]) -> None:
        """Install shared prefix blocks at the head of a slot's table."""
        assert not self._slot_shared[slot] and not self._slot_blocks[slot]
        for i, b in enumerate(blocks):
            self._refs[b] = self._refs.get(b, 0) + 1
            self.tables[slot, i] = b
            # LRU touch
            d = self._block_digest.get(b)
            if d is not None and d in self._prefix:
                self._prefix[d] = self._prefix.pop(d)
        self._slot_shared[slot] = list(blocks)

    def register_prefix(self, slot: int, prompt_tokens) -> None:
        """After a committed prefill: publish the slot's full prompt blocks
        into the cache (first writer wins per digest)."""
        table = self._slot_shared[slot] + self._slot_blocks[slot]
        prev = b""
        for i, d in enumerate(self._digests(prompt_tokens)):
            if i >= len(table):
                break
            if d in self._prefix:
                self._prefix[d] = self._prefix.pop(d)  # LRU touch
                prev = d
                continue
            b = table[i]
            if b in self._block_digest:
                break  # block already published under another digest:
                       # deeper chain links would dangle — stop here
            self._prefix[d] = b
            self._block_digest[b] = d
            self._refs[b] = self._refs.get(b, 0) + 1
            self._parent[d] = prev
            self._nchildren.setdefault(d, 0)
            if prev:
                self._nchildren[prev] = self._nchildren.get(prev, 0) + 1
            prev = d

    def _evict_one(self) -> bool:
        """Drop the least-recently-used cache-only LEAF block (no cached
        children) to the free list — heads stay until their chains drain."""
        for d, b in list(self._prefix.items()):  # insertion order = LRU
            if self._refs.get(b, 0) != 1:  # a slot still reads it
                continue
            if self._nchildren.get(d, 0) > 0:  # interior: would orphan tail
                continue
            del self._prefix[d]
            del self._block_digest[b]
            parent = self._parent.pop(d, b"")
            self._nchildren.pop(d, None)
            if parent and parent in self._nchildren:
                self._nchildren[parent] -= 1
            self._unref(b)
            if self.on_prefix_evict is not None:
                # pool pressure dropped a cached block with no demotion:
                # the tier ledgers record the loss (serving/prefixstore.py)
                self.on_prefix_evict(d.hex(), b)
            return True
        return False

    # -- tiered prefix store surface (serving/prefixstore.py) ----------
    # Demotion picks LRU cache-only LEAF blocks (the same candidates
    # _evict_one would drop), the engine gathers their rows to host on
    # the dispatch thread, then drop_prefix() frees them; promotion
    # allocates fresh blocks via install_prefix_chain() and the engine
    # scatters the T1 rows back in. All decision paths are wait-free
    # (PFX801): dict walks and list ops, no I/O, no device syncs.

    def evictable_prefixes(
        self, max_n: int
    ) -> list[tuple[bytes, int, bytes]]:
        """Up to ``max_n`` demotion candidates, LRU-first: cache-only
        (refcount 1) leaf blocks as ``(digest, block, parent_digest)``.
        Pure read — nothing is claimed until :meth:`drop_prefix`."""
        out: list[tuple[bytes, int, bytes]] = []
        for d, b in self._prefix.items():  # insertion order = LRU
            if len(out) >= max_n:
                break
            if self._refs.get(b, 0) != 1:
                continue
            if self._nchildren.get(d, 0) > 0:
                continue
            out.append((d, b, self._parent.get(d, b"")))
        return out

    def drop_prefix(self, digest: bytes) -> int | None:
        """Targeted :meth:`_evict_one`: free ONE cached block by digest
        (cache-only leaves only — a block a slot still reads, or an
        interior chain link, refuses with ``None``). The demotion path
        calls this only AFTER the block's rows are safely on host."""
        b = self._prefix.get(digest)
        if b is None:
            return None
        if self._refs.get(b, 0) != 1:
            return None
        if self._nchildren.get(digest, 0) > 0:
            return None
        del self._prefix[digest]
        del self._block_digest[b]
        parent = self._parent.pop(digest, b"")
        self._nchildren.pop(digest, None)
        if parent and parent in self._nchildren:
            self._nchildren[parent] -= 1
        self._unref(b)
        return b

    def install_prefix_chain(
        self, chain: list[tuple[bytes, bytes]]
    ) -> list[int] | None:
        """Allocate + publish fresh cache-owned blocks for a promoted
        chain segment (``[(digest, parent_digest), ...]`` in chain
        order; the first parent must already be cached or empty). The
        engine scatters the promoted rows into the returned blocks
        before any admission adopts them. All-or-nothing: an allocation
        failure mid-chain rolls the published links back and returns
        ``None`` (the promotion falls back to cold compute)."""
        if not chain:
            return []
        first_parent = chain[0][1]
        if first_parent and first_parent not in self._prefix:
            return None  # broken linkage: would orphan the whole segment
        installed: list[tuple[bytes, int, bytes]] = []
        try:
            for digest, parent in chain:
                if digest in self._prefix:
                    # raced with a concurrent register: keep the cached
                    # block, roll back our partial segment
                    raise RuntimeError("digest already cached")
                # mark the parent interior BEFORE allocating: _alloc may
                # evict a cache-only leaf to find space, and the parent
                # must not be that leaf or the new link would orphan
                if parent:
                    self._nchildren[parent] = (
                        self._nchildren.get(parent, 0) + 1
                    )
                try:
                    b = self._alloc()  # refcount 1: cache-owned
                except RuntimeError:
                    if parent and parent in self._nchildren:
                        self._nchildren[parent] -= 1
                    raise
                self._prefix[digest] = b
                self._block_digest[b] = digest
                self._parent[digest] = parent
                self._nchildren.setdefault(digest, 0)
                installed.append((digest, b, parent))
        except RuntimeError:
            for digest, b, parent in reversed(installed):
                del self._prefix[digest]
                del self._block_digest[b]
                self._parent.pop(digest, None)
                self._nchildren.pop(digest, None)
                if parent and parent in self._nchildren:
                    self._nchildren[parent] -= 1
                self._unref(b)
            return None
        return [b for _, b, _ in installed]

    # -- refcounted block lifecycle (every live block holds ≥1 ref:
    # its owning/adopting slots and, once published, the cache) ---------

    def _alloc(self) -> int:
        if not self._free and not self._evict_one():
            raise RuntimeError(
                "paged KV pool exhausted despite reservation accounting"
            )
        b = self._free.pop()
        self._refs[b] = 1
        return b

    def _unref(self, b: int) -> None:
        n = self._refs.get(b, 0) - 1
        if n <= 0:
            self._refs.pop(b, None)
            self._free.append(b)
        else:
            self._refs[b] = n

    # -- admission -----------------------------------------------------

    def _position_blocks(self, total_tokens: int) -> int:
        return -(-total_tokens // self.layout.block_size)

    def blocks_needed(self, total_tokens: int) -> int:
        """Blocks of this layout's pool that a slot of ``total_tokens``
        positions reads: a block a ``block_size`` positions, or, where the
        pool holds chunk SUMMARIES (``summary_window`` W and
        ``summary_chunk`` C, models/eva.py: one row a chunk of C positions,
        visible a window of W at a time once the window has closed), the
        blocks of the ``W / C`` rows of every window closed before the last
        position's."""
        if not self.summary_window:
            return self._position_blocks(total_tokens)
        closed = max(total_tokens - 1, 0) // self.summary_window
        return self._position_blocks(
            closed * (self.summary_window // self.summary_chunk))

    def window_blocks_needed(self, total_tokens: int) -> int:
        """Window-kind blocks a slot of ``total_tokens`` rows holds: its
        blocks up to the ring, and never more (0 without the kind)."""
        return min(self._position_blocks(total_tokens), self.window_ring)

    def fits_ever(self, total_tokens: int) -> bool:
        """Whether a request of this worst-case size could EVER be admitted
        (even into an empty pool) — callers must reject oversized requests
        up front or they would queue forever."""
        return self.blocks_needed(total_tokens) <= min(
            self.layout.num_blocks - 1, self.layout.max_blocks_per_slot
        ) and (not self.summary_window or self._position_blocks(
            total_tokens) <= self.layout.max_blocks_per_slot)

    def can_admit(self, total_tokens: int) -> bool:
        need = self.blocks_needed(total_tokens)
        return (
            self._reserved + need <= self.usable_blocks
            and need <= self.layout.max_blocks_per_slot
            and self._wreserved + self.window_blocks_needed(total_tokens)
            <= self.window_usable_blocks
        )

    # -- adaptive budget (pool-shrink, docs/RESILIENCE.md) --------------

    @property
    def configured_blocks(self) -> int:
        """The configured usable pool (block 0 is scratch)."""
        return self.layout.num_blocks - 1

    @property
    def usable_blocks(self) -> int:
        """The LIVE admission budget: configured minus withheld."""
        return self.configured_blocks - self._budget_reduction

    @property
    def window_usable_blocks(self) -> int:
        """The window kind's live admission budget: its configured blocks
        less the share of them that :meth:`reduce_budget` withholds of the
        full kind's (one reduction, both kinds), never under one ring."""
        if self.window_layout is None:
            return 0
        configured = self.window_layout.num_blocks - 1
        withheld = -(-self._budget_reduction * configured
                     // max(1, self.configured_blocks))
        return max(self.window_ring, configured - withheld)

    @property
    def window_slot_blocks_max(self) -> int:
        """The most window-kind blocks any slot holds now (never more than
        the ring); a walk of the slots' rings, cheap enough for a dispatch."""
        return max((len(ring) for ring in self._slot_ring), default=0)

    @property
    def summary_blocks_held(self) -> int:
        """Blocks of this layout's pool in slots' hands now (the summary
        kind's, for a model that keeps one: models/eva.py)."""
        return sum(map(len, self._slot_blocks))

    @property
    def window_blocks_held(self) -> int:
        """Window-kind blocks in the slots' rings now."""
        return sum(len(ring) for ring in self._slot_ring)

    @property
    def budget_reduction(self) -> int:
        return self._budget_reduction

    @property
    def reserved_blocks(self) -> int:
        return self._reserved

    def _budget_floor(self) -> int:
        """Never shrink below one max-size slot's worth: requests that
        passed ``fits_ever`` must stay admissible *eventually* or they
        would queue forever under a shrink that never fully restores."""
        return min(self.layout.max_blocks_per_slot, self.configured_blocks)

    def reduce_budget(self, blocks: int) -> int:
        """Withhold up to ``blocks`` from the admission budget (clamped
        to the floor). Returns the blocks actually withheld — 0 means
        the budget is already at its floor. Existing reservations may
        transiently exceed the new budget; ``can_admit`` simply refuses
        new work until completions (or preemptions) drain them."""
        actual = max(0, min(int(blocks), self.usable_blocks - self._budget_floor()))
        self._budget_reduction += actual
        return actual

    def restore_budget(self, blocks: int | None = None) -> int:
        """Return withheld blocks to the budget (all of them when
        ``blocks`` is None). Returns the blocks actually restored."""
        actual = (
            self._budget_reduction
            if blocks is None
            else max(0, min(int(blocks), self._budget_reduction))
        )
        self._budget_reduction -= actual
        return actual

    def admit(self, slot: int, total_tokens: int) -> None:
        need = self.blocks_needed(total_tokens)
        if not self.can_admit(total_tokens):
            raise RuntimeError("paged KV pool exhausted (admission bug)")
        self._slot_reservation[slot] = need
        self._reserved += need
        self._slot_wreservation[slot] = self.window_blocks_needed(total_tokens)
        self._wreserved += self._slot_wreservation[slot]
        self._state_live[slot] = True

    # -- growth --------------------------------------------------------

    def ensure_capacity(self, slot: int, tokens: int) -> int:
        """Allocate physical blocks so ``tokens`` positions fit. Returns
        the number of blocks allocated (0 = table unchanged; truthy
        exactly when it changed, so boolean callers keep working — and
        the pool-grow flight events can carry block/byte counts).

        Growth is capped at the slot's admission reservation: speculative
        decode chunks may request coverage past the request's true maximum,
        and capping keeps the reservation invariant (those excess writes are
        redirected to the scratch block by the unallocated table columns).
        """
        # a summary pool grows a window AHEAD: the open window's chunks
        # are written as they close and read once the window has
        need = self.blocks_needed(tokens + self.summary_window)
        if self._slot_reservation[slot] or self.summary_window:
            need = min(need, self._slot_reservation[slot])
        grown = 0
        while len(self._slot_shared[slot]) + len(self._slot_blocks[slot]) < need:
            b = self._alloc()
            idx = len(self._slot_shared[slot]) + len(self._slot_blocks[slot])
            self._slot_blocks[slot].append(b)
            self.tables[slot, idx] = b
            grown += 1
        ring = self._slot_ring[slot]
        while len(ring) < min(
                self.window_blocks_needed(tokens),
                self._slot_wreservation[slot] or self.window_ring):
            if not self._wfree:
                raise RuntimeError(
                    "paged KV window pool exhausted despite reservation "
                    "accounting")
            b = self._wfree.pop()
            # every logical block this ring block will ever serve
            self.window_tables[slot, len(ring)::self.window_ring] = b
            ring.append(b)
            grown += 1
        return grown

    def release(self, slot: int) -> None:
        for b in self._slot_shared[slot] + self._slot_blocks[slot]:
            self._unref(b)
        self._reserved -= self._slot_reservation[slot]
        self._slot_reservation[slot] = 0
        self._slot_shared[slot] = []
        self._slot_blocks[slot] = []
        self._wfree.extend(reversed(self._slot_ring[slot]))
        self.window_blocks_released += len(self._slot_ring[slot])
        self._slot_ring[slot] = []
        self._wreserved -= self._slot_wreservation[slot]
        self._slot_wreservation[slot] = 0
        self.tables[slot, :] = 0        # both kinds' columns
        self._state_live[slot] = False

    # -- stats ---------------------------------------------------------

    def used_ratio(self) -> float:
        """Admission-relevant pool pressure: the RESERVED fraction of the
        usable pool (block 0 is scratch). Admission gates on worst-case
        reservations, so a pool can refuse admissions while mostly
        unallocated — an allocated-fullness gauge would read near empty
        exactly when ``no-kv-blocks`` stalls fire. Physical allocation
        (free/live/cached) lives in :meth:`stats`. Cheap enough for the
        flight recorder to sample per burst. Measured against the LIVE
        budget: a shrunk pool reports the pressure admissions actually
        face, not the configured capacity they temporarily lost."""
        usable = self.usable_blocks
        ratio = self._reserved / usable if usable > 0 else 1.0
        if self.window_layout is not None:
            # the kind that refuses first is the pressure admissions face
            ratio = max(ratio, self._wreserved / self.window_usable_blocks)
        return ratio

    def prefix_block_count(self) -> int:
        """Blocks currently pinned by the content-addressed prefix cache
        — a single GIL-atomic ``len``, so the attribution memory ledger
        can read it wait-free from any thread (OBS505)."""
        return len(self._prefix)

    def stats(self) -> dict:
        stats = self._full_kind_stats()
        if self.window_layout is None:
            return stats
        # both kinds in the totals a poll of the pool reads (the share of
        # the pools' blocks that back a slot's rows), the window kind's own
        # beside them
        held = self.window_blocks_held
        stats.update(
            num_blocks=stats["num_blocks"] + self.window_layout.num_blocks,
            free_blocks=stats["free_blocks"] + len(self._wfree),
            reserved_blocks=stats["reserved_blocks"] + self._wreserved,
            live_blocks=stats["live_blocks"] + held,
            full_num_blocks=stats["num_blocks"],
            full_live_blocks=stats["live_blocks"],
            window_num_blocks=self.window_layout.num_blocks,
            window_live_blocks=held,
            window_reserved_blocks=self._wreserved,
            window_budget_blocks=self.window_usable_blocks,
            window_ring_blocks=self.window_ring,
            window_slot_blocks_max=self.window_slot_blocks_max,
            window_blocks_released=self.window_blocks_released,
        )
        return stats

    def _full_kind_stats(self) -> dict:
        return {
            "num_blocks": self.layout.num_blocks,
            "free_blocks": len(self._free),
            "reserved_blocks": self._reserved,
            # adaptive pool-shrink posture: the live admission budget vs
            # what the config sized (withheld > 0 = shrunk right now)
            "budget_blocks": self.usable_blocks,
            "withheld_blocks": self._budget_reduction,
            # distinct physical blocks: shared prefix blocks adopted by
            # several slots count once (live + free + cache-only ≤ usable)
            "live_blocks": len(
                {
                    b
                    for s, o in zip(self._slot_shared, self._slot_blocks)
                    for b in (*s, *o)
                }
            ),
            "cached_prefix_blocks": len(self._prefix),
            # recurrent state beside the pool (0 for a model that has none)
            "state_bytes": self.state_bytes_per_slot * len(self._state_live),
            "state_live_bytes": self.state_bytes_per_slot
            * sum(self._state_live),
        }
