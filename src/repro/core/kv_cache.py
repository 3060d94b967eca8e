"""KV caches: vanilla, masked-DMS (reference), and slot-compacted DMS (production).

Two DMS cache implementations with identical attention semantics:

* :class:`MaskedDMSCache` — logical cache of the full sequence length with a
  ``retained`` bitmap.  Simple, used as the correctness oracle.
* :class:`SlotDMSCache` — *physically compacted* cache with ``P << S`` slots,
  a free-list ring allocator, and a pending-eviction ring implementing the
  paper's **delayed eviction** (§3.3): the decision made at step *t* frees the
  slot at step *t + w*.  Evicted slots are overwritten by incoming tokens, so
  DMS adds no KV read/write traffic.  Keys are stored post-RoPE ("with
  positional information", §3.3).

All caches are registered pytrees and fully functional (update returns a new
cache), so they pass through ``jax.jit`` / ``lax.scan`` / pjit unscathed.

Layout: ``k, v``: (B, Hkv, P, Dh); per-slot metadata (B, Hkv, P); ``length``
is **per lane** (B,) — batch rows are independent *lanes* that may sit at
different sequence positions (continuous batching: staggered admission,
chunked prefill, EOS early-exit all advance lanes independently).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import block_pool

INVALID_POS = jnp.iinfo(jnp.int32).max


class LaneSliceable:
    """Per-lane snapshot/restore for lane-leading cache pytrees.

    Every cache in this repo stores *all* of its per-lane state in array
    leaves whose lane (batch) axis is leading (or at ``axis`` when the cache
    is stacked over superblocks), so one lane's complete state at a token
    boundary — arena contents, free lists, pending eviction rings, score
    accumulators, page metadata — is exactly the width-1 slice of every leaf.
    That is the invariant the cross-request prefix cache relies on: a slice
    taken after prefilling L tokens, written back into a pristine lane,
    continues bitwise-identically to a cold prefill of those L tokens.

    Mixed into every cache class (``kv_cache`` / ``baselines`` /
    ``keyformer``); a cache with non-lane-leading state must override both
    methods together (the same override point as ``KVPolicy.fork_cache``).
    """

    def export_lane(self, lane, *, axis: int = 0):
        """Width-1 slice of lane ``lane`` (traced int32 ok) of every leaf."""
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, lane, 1, axis=axis),
            self)

    def import_lane(self, snap, lane, *, axis: int = 0):
        """Write a width-1 snapshot back into lane ``lane`` of every leaf."""
        return jax.tree_util.tree_map(
            lambda a, s: jax.lax.dynamic_update_slice_in_dim(
                a, s.astype(a.dtype), lane, axis=axis),
            self, snap)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m if m else x


def _tree_dataclass(cls):
    """Dataclass + pytree registration; fields with metadata {'static': True}
    go into aux_data (hashable, not traced).  Children are keyed by field name
    so sharding rules can match on tree paths."""
    cls = dataclass(cls)
    child_names = [f.name for f in dataclasses.fields(cls) if not f.metadata.get("static")]
    static_names = [f.name for f in dataclasses.fields(cls) if f.metadata.get("static")]

    def flatten_with_keys(o):
        return (
            [(jax.tree_util.GetAttrKey(n), getattr(o, n)) for n in child_names],
            tuple(getattr(o, n) for n in static_names),
        )

    def flatten(o):
        return (
            tuple(getattr(o, n) for n in child_names),
            tuple(getattr(o, n) for n in static_names),
        )

    def unflatten(aux, children):
        kw = dict(zip(child_names, children))
        kw.update(zip(static_names, aux))
        return cls(**kw)

    jax.tree_util.register_pytree_with_keys(cls, flatten_with_keys, unflatten,
                                            flatten_func=flatten)
    return cls


# ---------------------------------------------------------------------------
# Block tables: compacted live-block indices for the flash-decode kernel
# ---------------------------------------------------------------------------


@_tree_dataclass
class BlockTable:
    """Per-(lane, kv-head) compacted index table of *live* KV blocks.

    The flash-decode kernel walks this table instead of the raw arena: its
    scalar-prefetched entries drive the kernel's K/V block copies, so blocks
    with zero live slots are never DMA'd — decode HBM traffic scales with
    live tokens, not arena capacity (see docs/kernels.md).

    Maintained **incrementally**: :meth:`insert` / :meth:`evict` are O(NB)
    vector ops fired once per cache mutation (a slot turning live/dead), not
    a per-step O(P) reduction over the arena.  The table is an unordered
    compacted list — eviction swaps the last entry into the hole — which is
    fine because flash attention is order-invariant.  Invariant (pinned by
    ``tests/test_block_tables.py``): ``{tbl[..., :n]}`` equals the set of
    blocks with at least one live slot, and ``count`` equals the per-block
    live-slot population of the arena's ``valid`` bitmap.

    ``block_p == 0`` disables the machinery entirely (zero-width arrays, all
    updates no-ops): the legacy dense-streaming configuration.
    """

    count: jnp.ndarray   # (B, H, NB) int32 — live slots per block
    tbl: jnp.ndarray     # (B, H, NB) int32 — live block ids, first n entries
    pos: jnp.ndarray     # (B, H, NB) int32 — block id -> index in tbl, or -1
    n: jnp.ndarray       # (B, H) int32 — number of live blocks
    block_p: int = dataclasses.field(metadata={"static": True}, default=0)

    @staticmethod
    def init(batch: int, kv_heads: int, num_slots: int, block_p: int
             ) -> "BlockTable":
        nb = num_slots // block_p if block_p else 0
        z = jnp.zeros((batch, kv_heads, nb), jnp.int32)
        return BlockTable(count=z, tbl=z,
                          pos=jnp.full((batch, kv_heads, nb), -1, jnp.int32),
                          n=jnp.zeros((batch, kv_heads), jnp.int32),
                          block_p=block_p)

    def spec(self):
        """The ``(block_tbl, block_n, block_p)`` triple an ``AttendSpec``
        carries to the kernel; ``(None, None, 0)`` when tables are off."""
        if not self.block_p:
            return None, None, 0
        return self.tbl, self.n, self.block_p

    @staticmethod
    def from_valid(valid: jnp.ndarray, block_p: int) -> "BlockTable":
        """Recompute the canonical table from a ``valid`` bitmap (one O(P)
        pass — prefill import and the test oracle, never the step path).
        Canonical order: live block ids ascending."""
        b, h, p = valid.shape
        if not block_p:
            return BlockTable.init(b, h, 0, 0)
        nb = p // block_p
        count = jnp.sum(valid.reshape(b, h, nb, block_p), axis=-1
                        ).astype(jnp.int32)
        live = count > 0
        tbl = jnp.argsort(~live, axis=-1, stable=True).astype(jnp.int32)
        rank = jnp.cumsum(live, axis=-1).astype(jnp.int32) - 1
        return BlockTable(count=count, tbl=tbl,
                          pos=jnp.where(live, rank, -1),
                          n=jnp.sum(live, axis=-1).astype(jnp.int32),
                          block_p=block_p)

    # -- O(NB) scatter helpers (one-hot writes, shapes fixed) ---------------

    @staticmethod
    def _take(arr, idx):
        return jnp.take_along_axis(arr, idx[..., None], axis=2)[..., 0]

    @staticmethod
    def _put(arr, idx, val, mask):
        nb = arr.shape[2]
        hit = (jnp.arange(nb)[None, None] == idx[..., None]) & mask[..., None]
        if hasattr(val, "ndim") and val.ndim == 2:
            val = val[..., None]
        return jnp.where(hit, val, arr)

    def insert(self, slot: jnp.ndarray, mask: jnp.ndarray) -> "BlockTable":
        """A slot turned live.  ``slot``/``mask``: (B, H); where ``mask`` is
        False nothing happened this step (no-op lanes/heads)."""
        return self.insert_ex(slot, mask)[0]

    def insert_ex(self, slot: jnp.ndarray, mask: jnp.ndarray
                  ) -> Tuple["BlockTable", jnp.ndarray]:
        """:meth:`insert` plus the per-(lane, head) *block turned live* event
        mask — the paged pool's page-allocation trigger."""
        if not self.block_p or self.count.shape[2] == 0:
            return self, jnp.zeros_like(mask)
        nb = self.count.shape[2]
        blk = jnp.clip(slot // self.block_p, 0, nb - 1)
        new_live = mask & (self._take(self.count, blk) == 0)
        count = self._put(self.count, blk, self._take(self.count, blk) + 1,
                          mask)
        tbl = self._put(self.tbl, jnp.minimum(self.n, nb - 1), blk, new_live)
        pos = self._put(self.pos, blk, self.n, new_live)
        return dataclasses.replace(self, count=count, tbl=tbl, pos=pos,
                                   n=self.n + new_live.astype(jnp.int32)), \
            new_live

    def evict(self, slot: jnp.ndarray, mask: jnp.ndarray) -> "BlockTable":
        """A slot turned dead.  When its block's population hits zero the
        block leaves the table: the last table entry swaps into its place."""
        return self.evict_ex(slot, mask)[0]

    def evict_ex(self, slot: jnp.ndarray, mask: jnp.ndarray
                 ) -> Tuple["BlockTable", jnp.ndarray]:
        """:meth:`evict` plus the per-(lane, head) *block turned dead* event
        mask — the paged pool's page-free trigger."""
        if not self.block_p or self.count.shape[2] == 0:
            return self, jnp.zeros_like(mask)
        nb = self.count.shape[2]
        blk = jnp.clip(slot // self.block_p, 0, nb - 1)
        cnt_after = self._take(self.count, blk) - 1
        count = self._put(self.count, blk, cnt_after, mask)
        dead = mask & (cnt_after == 0)
        hole = self._take(self.pos, blk)                       # index in tbl
        hole = jnp.clip(hole, 0, nb - 1)
        last_i = jnp.clip(self.n - 1, 0, nb - 1)
        last_blk = self._take(self.tbl, last_i)
        tbl = self._put(self.tbl, hole, last_blk, dead)
        pos = self._put(self.pos, last_blk, hole, dead)
        pos = self._put(pos, blk, -1, dead)    # after: blk==last_blk -> -1
        return dataclasses.replace(self, count=count, tbl=tbl, pos=pos,
                                   n=self.n - dead.astype(jnp.int32)), dead


class HasBlockTable:
    """Mixin for caches whose ``blocks`` field is an incrementally-maintained
    :class:`BlockTable`: exposes the uniform ``block_spec()`` the policy
    layer reads (see ``repro.core.policy._attend_spec``)."""

    def block_spec(self):
        return self.blocks.spec()


def prefix_block_spec(length: jnp.ndarray, num_slots: int, block_p: int,
                      kv_heads: int):
    """Derived block table for prefix-shaped occupancy (vanilla/DMC): live
    slots are exactly ``[0, length)`` per lane, so the table is just the
    first ``ceil(length / block_p)`` block ids — O(NB) from a scalar, no
    stored state.  Returns ``(tbl (B,H,NB) int32, n (B,H) int32)`` or
    ``(None, None)`` when tables are disabled."""
    if not block_p:
        return None, None
    nb = num_slots // block_p
    b = length.shape[0]
    length = length.reshape(b, -1)                      # (B,1) or (B,H)
    n = jnp.broadcast_to(-(-jnp.minimum(length, num_slots) // block_p),
                         (b, kv_heads)).astype(jnp.int32)
    tbl = jnp.broadcast_to(jnp.arange(nb, dtype=jnp.int32)[None, None],
                           (b, kv_heads, nb))
    return tbl, n


# ---------------------------------------------------------------------------
# Paged-pool plumbing shared by every cache class
# ---------------------------------------------------------------------------
#
# In paged mode a cache's dense ``k``/``v`` arenas are allocated with a
# ZERO-width head axis (B, H, P, 0): every shape-derived invariant (valid
# masks, positions, LaneSliceable, block specs) keeps working, the in-place
# arena writes become free no-ops, and the actual bytes live in the shared
# :class:`~repro.core.block_pool.BlockPool` addressed through ``phys``.


def init_paged(batch: int, kv_heads: int, padded_slots: int, head_dim: int,
               block_p: int, dtype, pool_blocks: Optional[int]):
    """(pool, phys, zero-width arena) for a paged cache; validates block_p."""
    if not block_p:
        raise ValueError("paged KV cache requires block_p > 0")
    nb = padded_slots // block_p
    pool = block_pool.BlockPool.init(
        pool_blocks or batch * kv_heads * nb, block_p, head_dim, dtype)
    phys = jnp.full((batch, kv_heads, nb), -1, jnp.int32)
    return pool, phys, jnp.zeros((batch, kv_heads, padded_slots, 0), dtype)


def event_mask(active, shape) -> jnp.ndarray:
    """Broadcast the scheduler's per-lane ``active`` mask (B,) over event
    shape (B, H[, T]); None = all lanes live.  Pool mutations MUST be gated
    on this: the pool is shared state that ``lane_select`` cannot roll back,
    so inactive lanes may not allocate, free, or write pages."""
    if active is None:
        return jnp.ones(shape, bool)
    return jnp.broadcast_to(active.reshape((-1,) + (1,) * (len(shape) - 1)),
                            shape)


def cache_block_p(cache) -> int:
    """Kernel block granularity of any cache class (stored field, incremental
    table, or Quest's page size)."""
    bp = getattr(cache, "block_p", None)
    if bp is None and hasattr(cache, "blocks"):
        bp = cache.blocks.block_p
    if bp is None:
        bp = getattr(cache, "page_size", 0)
    return bp


def pack_dense(cache, pool_blocks: Optional[int] = None):
    """Convert a fixed-arena cache into its pooled twin (prefill import).

    Pages are allocated for every block holding at least one live slot and
    the dense arena content is copied page-by-page; dead blocks simply don't
    exist.  The result is bitwise-equivalent under attention (garbage in
    unmapped blocks is masked in both layouts)."""
    bp = cache_block_p(cache)
    b, h, p, dh = cache.k.shape
    if not bp:
        raise ValueError("pack_dense requires block_p > 0")
    nb = p // bp
    pool = block_pool.BlockPool.init(pool_blocks or b * h * nb, bp, dh,
                                     cache.k.dtype)
    valid = jnp.broadcast_to(cache.valid_mask(), (b, h, p))
    need = jnp.any(valid.reshape(b, h, nb, bp), axis=-1).reshape(-1)
    pool, page, ok = block_pool.alloc(pool, need)
    phys = jnp.where(need & ok, page, -1).reshape(b, h, nb)
    dst = jnp.where(need & ok, page, pool.num_blocks)
    pool = dataclasses.replace(
        pool,
        k=pool.k.at[dst].set(cache.k.reshape(b * h * nb, bp, dh),
                             mode="drop"),
        v=pool.v.at[dst].set(cache.v.reshape(b * h * nb, bp, dh),
                             mode="drop"))
    return dataclasses.replace(cache, k=cache.k[..., :0], v=cache.v[..., :0],
                               pool=pool, phys=phys)


# ---------------------------------------------------------------------------
# Vanilla (dense, append-only) cache
# ---------------------------------------------------------------------------


@_tree_dataclass
class VanillaCache(LaneSliceable):
    k: jnp.ndarray      # (B, Hkv, S, Dh) — S padded to a block_p multiple
    v: jnp.ndarray
    length: jnp.ndarray  # (B,) int32 — tokens written, per lane
    # kernel block granularity; 0 = no block tables (exact legacy arena).
    # Occupancy is a length-prefix, so the live-block table is *derived*
    # (prefix_block_spec) rather than stored.
    block_p: int = dataclasses.field(metadata={"static": True}, default=0)
    # paged mode: shared page arena + per-(lane, head) page map; the dense
    # k/v above are zero-width placeholders (see init_paged)
    pool: Optional[block_pool.BlockPool] = None
    phys: Optional[jnp.ndarray] = None       # (B, H, NB) int32, -1 = unmapped

    @staticmethod
    def init(batch: int, kv_heads: int, max_len: int, head_dim: int,
             dtype=jnp.bfloat16, block_p: int = 0, paged: bool = False,
             pool_blocks: Optional[int] = None):
        pool = phys = None
        if paged:
            pool, phys, z = init_paged(batch, kv_heads,
                                       _round_up(max_len, block_p), head_dim,
                                       block_p, dtype, pool_blocks)
        else:
            z = jnp.zeros(
                (batch, kv_heads, _round_up(max_len, block_p), head_dim),
                dtype)
        return VanillaCache(z, z, jnp.zeros((batch,), jnp.int32),
                            block_p=block_p, pool=pool, phys=phys)

    def block_spec(self):
        tbl, n = prefix_block_spec(self.length, self.k.shape[2], self.block_p,
                                   self.k.shape[1])
        return tbl, n, self.block_p

    def append(self, k_new: jnp.ndarray, v_new: jnp.ndarray,
               active=None) -> "VanillaCache":
        """k_new, v_new: (B, Hkv, T_new, Dh) written at [length, length+T_new)
        of each lane (per-lane offsets: a vmapped dynamic-slice scatter)."""
        t_new = k_new.shape[2]
        if self.pool is not None:
            b, h = self.k.shape[:2]
            slot = jnp.broadcast_to(
                self.length[:, None, None] + jnp.arange(t_new)[None, None],
                (b, h, t_new))
            pool, phys = block_pool.token_write(
                self.pool, self.phys, slot, k_new, v_new,
                event_mask(active, (b, h, t_new)))
            return dataclasses.replace(self, pool=pool, phys=phys,
                                       length=self.length + t_new)

        def upd(buf, new, off):
            return jax.lax.dynamic_update_slice_in_dim(buf, new, off, axis=1)

        k = jax.vmap(upd)(self.k, k_new.astype(self.k.dtype), self.length)
        v = jax.vmap(upd)(self.v, v_new.astype(self.v.dtype), self.length)
        return dataclasses.replace(self, k=k, v=v, length=self.length + t_new)

    def valid_mask(self) -> jnp.ndarray:
        # lazy (B, 1, S): broadcast happens inside the consumer's `where`
        s = self.k.shape[2]
        return jnp.arange(s)[None, None, :] < self.length[:, None, None]

    def positions(self) -> jnp.ndarray:
        s = self.k.shape[2]
        return jnp.arange(s, dtype=jnp.int32)[None, None, :]

    def retained_tokens(self) -> jnp.ndarray:
        b, h = self.k.shape[:2]
        return jnp.broadcast_to(self.length[:, None], (b, h))


# ---------------------------------------------------------------------------
# Masked DMS cache (reference semantics)
# ---------------------------------------------------------------------------


@_tree_dataclass
class MaskedDMSCache(LaneSliceable, HasBlockTable):
    k: jnp.ndarray          # (B, Hkv, S, Dh) — S padded to a block_p multiple
    v: jnp.ndarray
    retained: jnp.ndarray   # (B, Hkv, S) bool — False once evicted
    alpha: jnp.ndarray      # (B, Hkv, S) bool — recorded eviction decisions
    length: jnp.ndarray     # (B,) int32 — per lane
    blocks: BlockTable      # incremental live-block table (flash-decode)
    window: int = dataclasses.field(metadata={"static": True})
    pool: Optional[block_pool.BlockPool] = None
    phys: Optional[jnp.ndarray] = None       # (B, H, NB) int32, -1 = unmapped

    @staticmethod
    def init(batch: int, kv_heads: int, max_len: int, head_dim: int,
             window: int, dtype=jnp.bfloat16, block_p: int = 0,
             paged: bool = False, pool_blocks: Optional[int] = None):
        s = _round_up(max_len, block_p)
        pool = phys = None
        if paged:
            pool, phys, z = init_paged(batch, kv_heads, s, head_dim, block_p,
                                       dtype, pool_blocks)
        else:
            z = jnp.zeros((batch, kv_heads, s, head_dim), dtype)
        f = jnp.zeros((batch, kv_heads, s), bool)
        return MaskedDMSCache(z, z, f, f, jnp.zeros((batch,), jnp.int32),
                              BlockTable.init(batch, kv_heads, s, block_p),
                              window, pool=pool, phys=phys)

    def step(self, k_new, v_new, alpha_new, active=None) -> "MaskedDMSCache":
        """Append ONE token per head; execute the eviction scheduled w steps ago.

        k_new/v_new: (B, Hkv, 1, Dh); alpha_new: (B, Hkv) bool.
        """
        t = self.length                                     # (B,)
        s = self.k.shape[2]
        idx = jnp.arange(s)
        at_t = idx[None, None, :] == t[:, None, None]       # (B, 1, S)
        if self.pool is None:
            k = jnp.where(at_t[..., None], k_new.astype(self.k.dtype), self.k)
            v = jnp.where(at_t[..., None], v_new.astype(self.v.dtype), self.v)
        else:
            k, v = self.k, self.v       # zero-width placeholders; bytes go
            #                             to the pool below
        retained = jnp.where(at_t, True, self.retained)
        alpha = jnp.where(at_t, alpha_new[..., None], self.alpha)
        # execute eviction of token t - w (if it was marked)
        j = t - self.window                                 # (B,)
        evict_now = (idx[None, None, :] == j[:, None, None]) & alpha \
            & (j >= 0)[:, None, None]
        retained = retained & ~evict_now
        b, h = self.retained.shape[:2]
        ins = jnp.broadcast_to((t < s)[:, None], (b, h))
        blocks = self.blocks.insert(
            jnp.broadcast_to(t[:, None], (b, h)), ins)
        blocks, dead = blocks.evict_ex(
            jnp.broadcast_to(j[:, None], (b, h)),
            jnp.any(evict_now, axis=2))
        pool, phys = self.pool, self.phys
        if pool is not None:
            act = event_mask(active, (b, h))
            pool, phys = block_pool.token_write(
                pool, phys,
                jnp.broadcast_to(t[:, None, None], (b, h, 1)),
                k_new, v_new, (ins & act)[..., None])
            pool, phys = block_pool.free_block(
                pool, phys,
                jnp.broadcast_to(jnp.clip(j, 0, s - 1)[:, None], (b, h)),
                dead & act)
        return dataclasses.replace(self, k=k, v=v, retained=retained,
                                   alpha=alpha, length=t + 1, blocks=blocks,
                                   pool=pool, phys=phys)

    def valid_mask(self) -> jnp.ndarray:
        s = self.k.shape[2]
        written = jnp.arange(s)[None, None, :] < self.length[:, None, None]
        return self.retained & written

    def positions(self) -> jnp.ndarray:
        s = self.k.shape[2]
        pos = jnp.arange(s, dtype=jnp.int32)
        return jnp.broadcast_to(pos[None, None], self.k.shape[:2] + (s,))

    def retained_tokens(self) -> jnp.ndarray:
        return jnp.sum(self.valid_mask(), axis=-1)


# ---------------------------------------------------------------------------
# Slot-compacted DMS cache (production)
# ---------------------------------------------------------------------------


@_tree_dataclass
class SlotDMSCache(LaneSliceable, HasBlockTable):
    """Physically compacted cache: P slots per (batch, kv head).

    Allocation uses a ring free-list; the pending ring holds the last ``w``
    (slot, α) pairs so that decisions execute exactly ``w`` steps late.
    If the arena overflows (model under-evicts vs. provisioned CR) the
    allocator evicts the oldest *marked-for-eviction* slot early; as a last
    resort it recycles the oldest slot (StreamingLLM-style safety valve) and
    flags ``overflowed`` for observability.
    """

    k: jnp.ndarray            # (B, H, P, Dh) — post-RoPE keys; P padded to
    #                           a block_p multiple, slots >= `slots` are
    #                           physical padding (never allocated)
    v: jnp.ndarray            # (B, H, P, Dh)
    pos: jnp.ndarray          # (B, H, P) int32 — logical position; INVALID_POS = empty
    valid: jnp.ndarray        # (B, H, P) bool
    free_ring: jnp.ndarray    # (B, H, P) int32 — circular buffer of free slot ids
    free_head: jnp.ndarray    # (B, H) int32 — index of next free slot in ring
    free_count: jnp.ndarray   # (B, H) int32
    pending_slot: jnp.ndarray   # (B, H, w) int32
    pending_alpha: jnp.ndarray  # (B, H, w) bool
    length: jnp.ndarray       # (B,) int32 — logical tokens written, per lane
    overflowed: jnp.ndarray   # (B, H) bool
    blocks: BlockTable        # incremental live-block table (flash-decode)
    window: int = dataclasses.field(metadata={"static": True})
    #: logical arena capacity — overflow/window semantics key off this, NOT
    #: the (padded) physical extent of ``k``
    slots: int = dataclasses.field(metadata={"static": True})
    # False = plain ring-buffer use (local-attention window cache): eviction
    # decisions are never predicted, overflow recycling does the windowing
    dms_active: bool = dataclasses.field(metadata={"static": True}, default=True)
    pool: Optional[block_pool.BlockPool] = None
    phys: Optional[jnp.ndarray] = None       # (B, H, NB) int32, -1 = unmapped

    @staticmethod
    def init(batch: int, kv_heads: int, num_slots: int, head_dim: int,
             window: int, dtype=jnp.bfloat16, dms_active: bool = True,
             block_p: int = 0, paged: bool = False,
             pool_blocks: Optional[int] = None):
        p = _round_up(num_slots, block_p)
        pool = phys = None
        if paged:
            pool, phys, z = init_paged(batch, kv_heads, p, head_dim, block_p,
                                       dtype, pool_blocks)
        else:
            z = jnp.zeros((batch, kv_heads, p, head_dim), dtype)
        return SlotDMSCache(
            k=z, v=z,
            pos=jnp.full((batch, kv_heads, p), INVALID_POS, jnp.int32),
            valid=jnp.zeros((batch, kv_heads, p), bool),
            # ring contents are always *logical* slot ids; capacity is the
            # physical extent but occupancy never exceeds `num_slots`
            free_ring=jnp.broadcast_to(
                jnp.arange(p, dtype=jnp.int32) % num_slots,
                (batch, kv_heads, p)).copy(),
            free_head=jnp.zeros((batch, kv_heads), jnp.int32),
            free_count=jnp.full((batch, kv_heads), num_slots, jnp.int32),
            pending_slot=jnp.full((batch, kv_heads, window), -1, jnp.int32),
            pending_alpha=jnp.zeros((batch, kv_heads, window), bool),
            length=jnp.zeros((batch,), jnp.int32),
            overflowed=jnp.zeros((batch, kv_heads), bool),
            blocks=BlockTable.init(batch, kv_heads, p, block_p),
            window=window,
            slots=num_slots,
            dms_active=dms_active,
            pool=pool,
            phys=phys,
        )

    @staticmethod
    def provision_slots(seq_len: int, cr: float, window: int) -> int:
        """P = ceil(S / CR) + w + slack — the arena size for a target CR."""
        return int(seq_len / cr) + window + 16

    # -- internals ----------------------------------------------------------

    def _execute_pending(self, active=None) -> "SlotDMSCache":
        """Execute the eviction decision made ``w`` steps ago (ring slot t mod w)."""
        t = self.length                                     # (B,)
        w = self.window
        b, h = self.valid.shape[:2]
        ring_idx = jnp.broadcast_to(jnp.mod(t, w)[:, None, None], (b, h, 1))
        slot = jnp.take_along_axis(self.pending_slot, ring_idx, axis=2)[..., 0]
        alpha = jnp.take_along_axis(self.pending_alpha, ring_idx, axis=2)[..., 0]
        do_evict = (t >= w)[:, None] & alpha & (slot >= 0)
        # still-valid guard (overflow may have recycled it already)
        slot_c = jnp.clip(slot, 0, self.valid.shape[2] - 1)
        was_valid = jnp.take_along_axis(self.valid, slot_c[..., None], axis=2)[..., 0]
        do_evict = do_evict & was_valid

        p_idx = jnp.arange(self.valid.shape[2])
        hit = (p_idx[None, None] == slot_c[..., None]) & do_evict[..., None]
        valid = self.valid & ~hit
        pos = jnp.where(hit, INVALID_POS, self.pos)
        # push freed slot onto the free ring
        tail = jnp.mod(self.free_head + self.free_count, self.free_ring.shape[2])
        free_ring = jnp.where(
            (p_idx[None, None] == tail[..., None]) & do_evict[..., None],
            slot_c[..., None], self.free_ring)
        free_count = self.free_count + do_evict.astype(jnp.int32)
        blocks, dead = self.blocks.evict_ex(slot_c, do_evict)
        pool, phys = self.pool, self.phys
        if pool is not None:
            pool, phys = block_pool.free_block(
                pool, phys, slot_c, dead & event_mask(active, (b, h)))
        return dataclasses.replace(
            self, valid=valid, pos=pos, free_ring=free_ring,
            free_count=free_count, blocks=blocks, pool=pool, phys=phys)

    def _allocate(self) -> Tuple["SlotDMSCache", jnp.ndarray]:
        """Pop a slot per (B, H).  Returns (cache, slot (B,H))."""
        p = self.free_ring.shape[2]
        have_free = self.free_count > 0
        head_slot = jnp.take_along_axis(self.free_ring, self.free_head[..., None], axis=2)[..., 0]
        # overflow path: recycle the oldest valid slot
        oldest_pos = jnp.where(self.valid, self.pos, INVALID_POS)
        oldest_slot = jnp.argmin(oldest_pos, axis=2).astype(jnp.int32)
        slot = jnp.where(have_free, head_slot, oldest_slot)
        free_head = jnp.where(have_free, jnp.mod(self.free_head + 1, p), self.free_head)
        free_count = jnp.where(have_free, self.free_count - 1, self.free_count)
        overflowed = self.overflowed | ~have_free
        cache = dataclasses.replace(
            self, free_head=free_head, free_count=free_count, overflowed=overflowed)
        return cache, slot

    # -- public API ----------------------------------------------------------

    def step(self, k_new, v_new, alpha_new, active=None) -> "SlotDMSCache":
        """Append one token per (batch, head); execute delayed evictions.

        k_new/v_new: (B, H, 1, Dh) post-RoPE; alpha_new: (B, H) bool.
        """
        cache = self._execute_pending(active)
        cache, slot = cache._allocate()
        t = cache.length                                                  # (B,)
        p_idx = jnp.arange(cache.valid.shape[2])
        hit = p_idx[None, None] == slot[..., None]                        # (B,H,P)
        # overflow recycling overwrites a still-live slot: only a dead->live
        # transition is a block-table insert event
        was_valid = jnp.take_along_axis(cache.valid, slot[..., None],
                                        axis=2)[..., 0]
        blocks = cache.blocks.insert(slot, ~was_valid)
        if cache.pool is None:
            k = jnp.where(hit[..., None], k_new.astype(cache.k.dtype), cache.k)
            v = jnp.where(hit[..., None], v_new.astype(cache.v.dtype), cache.v)
        else:
            k, v = cache.k, cache.v     # zero-width; bytes go to the pool
        pos = jnp.where(hit, t[:, None, None], cache.pos)
        valid = cache.valid | hit
        ring_idx = jnp.mod(t, cache.window)                               # (B,)
        w_idx = jnp.arange(cache.window)
        ring_hit = w_idx[None, None, :] == ring_idx[:, None, None]        # (B,1,w)
        pending_slot = jnp.where(ring_hit, slot[..., None], cache.pending_slot)
        pending_alpha = jnp.where(ring_hit, alpha_new[..., None], cache.pending_alpha)
        pool, phys = cache.pool, cache.phys
        if pool is not None:
            act = event_mask(active, slot.shape)
            pool, phys = block_pool.token_write(
                pool, phys, slot[..., None], k_new, v_new, act[..., None])
        return dataclasses.replace(
            cache, k=k, v=v, pos=pos, valid=valid,
            pending_slot=pending_slot, pending_alpha=pending_alpha,
            length=t + 1, blocks=blocks, pool=pool, phys=phys)

    def valid_mask(self) -> jnp.ndarray:
        return self.valid

    def positions(self) -> jnp.ndarray:
        return self.pos

    def retained_tokens(self) -> jnp.ndarray:
        return jnp.sum(self.valid, axis=-1)

    @staticmethod
    def from_prefill(k, v, positions, retained, window: int, num_slots: int,
                     alpha_bin: Optional[jnp.ndarray] = None,
                     block_p: int = 0) -> "SlotDMSCache":
        """Build a compacted cache from prefill outputs.

        k/v: (B, H, T, Dh) post-RoPE; retained: (B, H, T) bool;
        positions: (T,).  Retained tokens are packed into the first slots
        (stable order).  Tokens still inside the delay window whose α = 1 are
        entered into the pending ring so they get evicted on schedule.
        """
        b, h, t, d = k.shape
        p = _round_up(num_slots, block_p)
        # stable pack: order retained tokens by position
        order_key = jnp.where(retained, positions[None, None, :], INVALID_POS)
        order = jnp.argsort(order_key, axis=2)                      # (B,H,T) token idx by slot
        n_keep = jnp.minimum(jnp.sum(retained, axis=2), num_slots)  # (B,H)
        slot_ids = jnp.arange(p)

        def gather(x, fill):
            idx = order[..., :p] if t >= p else jnp.pad(order, ((0, 0), (0, 0), (0, p - t)))
            g = jnp.take_along_axis(x, idx[..., None] if x.ndim == 4 else idx, axis=2)
            live = slot_ids[None, None] < n_keep[..., None]
            if x.ndim == 4:
                return jnp.where(live[..., None], g, fill)
            return jnp.where(live, g, fill)

        kc = gather(k, jnp.zeros((), k.dtype))
        vc = gather(v, jnp.zeros((), v.dtype))
        pos_full = jnp.broadcast_to(positions[None, None, :], (b, h, t)).astype(jnp.int32)
        posc = gather(pos_full, INVALID_POS)
        valid = slot_ids[None, None] < n_keep[..., None]
        free_count = num_slots - n_keep
        # free ring: logical slots [n_keep, num_slots) are free
        free_ring = jnp.mod(n_keep[..., None] + slot_ids[None, None],
                            num_slots).astype(jnp.int32)
        cache = SlotDMSCache(
            k=kc, v=vc, pos=posc, valid=valid,
            free_ring=free_ring,
            free_head=jnp.zeros((b, h), jnp.int32),
            free_count=free_count.astype(jnp.int32),
            pending_slot=jnp.full((b, h, window), -1, jnp.int32),
            pending_alpha=jnp.zeros((b, h, window), bool),
            length=jnp.full((b,), t, jnp.int32),
            overflowed=jnp.zeros((b, h), bool),
            blocks=BlockTable.from_valid(valid, block_p),
            window=window,
            slots=num_slots,
        )
        if alpha_bin is not None:
            # tokens in (t-w, t] have un-executed decisions -> fill pending ring
            w = window
            tok = jnp.arange(t)
            in_window = tok > (t - 1 - w)
            # slot of token j = its rank among retained (all in-window tokens are retained)
            rank = jnp.cumsum(retained, axis=2) - 1                  # (B,H,T)
            ring_pos = jnp.mod(tok, w)
            pend_slot = jnp.full((b, h, w), -1, jnp.int32)
            pend_alpha = jnp.zeros((b, h, w), bool)
            idx = jnp.where(in_window, ring_pos, w)  # w = dumped
            pend_slot = pend_slot.at[..., :].set(
                jnp.zeros((b, h, w), jnp.int32) - 1)
            # scatter (padded with an extra dump column)
            ps = jnp.concatenate([pend_slot, jnp.zeros((b, h, 1), jnp.int32)], axis=2)
            pa = jnp.concatenate([pend_alpha, jnp.zeros((b, h, 1), bool)], axis=2)
            bi = jnp.arange(b)[:, None, None]
            hi = jnp.arange(h)[None, :, None]
            ps = ps.at[bi, hi, idx[None, None, :]].set(
                jnp.where(in_window[None, None, :], rank, -1).astype(jnp.int32))
            pa = pa.at[bi, hi, idx[None, None, :]].set(
                jnp.where(in_window[None, None, :], alpha_bin, False))
            cache = dataclasses.replace(cache, pending_slot=ps[..., :w], pending_alpha=pa[..., :w])
        return cache
