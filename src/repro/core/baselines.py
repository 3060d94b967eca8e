"""Training-free KV-cache baselines (paper §2.2) + DMC (§2.3).

Implemented with the same functional-cache conventions as :mod:`kv_cache` so
they slot into the identical decode loop and budget accounting:

* **TOVA** (Oren et al., 2024): keep a budget of tokens; at each step evict the
  token with the lowest *current* attention weight, summed over query heads.
* **H2O** (Zhang et al., 2023a): budget split between a recency window and
  "heavy hitters" (highest cumulative attention); evict the lowest-cumulative
  non-recent token.
* **Quest** (Tang et al., 2024): keeps the full cache; per page (fixed-size
  block) stores elementwise min/max key metadata; at each step selects the
  top-k pages by an upper-bound score and attends only to them — reducing
  memory *reads*, not memory *size*.
* **DMC** (Nawrot et al., 2024): append-or-merge. When α=1 the new (k, v) is
  accumulated into the last cache entry by a running weighted average.
* **Window** (StreamingLLM-ish): sliding window + attention sinks.

These are decode-time policies; the paper evaluates them with a standard dense
prefill up to the budget (§F.1), which we mirror in the serving engine.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import block_pool
from repro.core.kv_cache import (BlockTable, HasBlockTable,
                                 LaneSliceable, _append_slots, _round_up,
                                 _tree_dataclass, event_mask, init_paged,
                                 prefix_block_spec, read_rows, store_token,
                                 write_rows, INVALID_POS)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# TOVA
# ---------------------------------------------------------------------------


@_tree_dataclass
class TOVACache(LaneSliceable, HasBlockTable):
    k: jnp.ndarray       # (B, H, P, D) — P padded to a block_p multiple
    v: jnp.ndarray
    pos: jnp.ndarray     # (B, H, P)
    valid: jnp.ndarray   # (B, H, P)
    length: jnp.ndarray  # (B,) — per lane
    blocks: BlockTable   # incremental live-block table (flash-decode)
    slots: int = dataclasses.field(metadata={"static": True})  # logical arena
    pool: Optional[block_pool.BlockPool] = None
    phys: Optional[jnp.ndarray] = None       # (B, H, NB) int32, -1 = unmapped

    @staticmethod
    def init(batch, kv_heads, budget, head_dim, dtype=jnp.bfloat16,
             block_p: int = 0, paged: bool = False,
             pool_blocks: Optional[int] = None):
        p = _round_up(budget, block_p)
        pool = phys = None
        if paged:
            pool, phys, z = init_paged(batch, kv_heads, p, head_dim, block_p,
                                       dtype, pool_blocks)
        else:
            z = jnp.zeros((batch, kv_heads, p, head_dim), dtype)
        return TOVACache(z, z,
                         jnp.full((batch, kv_heads, p), INVALID_POS, jnp.int32),
                         jnp.zeros((batch, kv_heads, p), bool),
                         jnp.zeros((batch,), jnp.int32),
                         BlockTable.init(batch, kv_heads, p, block_p),
                         budget, pool=pool, phys=phys)

    @property
    def budget(self) -> int:
        return self.slots - 1   # arena is budget + 1 (room to insert-then-evict)

    def insert(self, k_new, v_new, active=None) -> "TOVACache":
        """Insert the new token into a free *logical* slot (the arena always
        has one; physical padding slots are never allocated)."""
        p = self.k.shape[2]
        free = ~self.valid & (jnp.arange(p)[None, None] < self.slots)
        slot = jnp.argmax(free, axis=2).astype(jnp.int32)         # first free
        hit = (jnp.arange(p)[None, None] == slot[..., None])
        newly = jnp.take_along_axis(free, slot[..., None], axis=2)[..., 0]
        k, v, pool, phys = store_token(self, slot, k_new, v_new, active)
        return dataclasses.replace(
            self,
            k=k, v=v,
            pos=jnp.where(hit, self.length[:, None, None], self.pos),
            valid=self.valid | hit,
            length=self.length + 1,
            blocks=self.blocks.insert(slot, newly),
            pool=pool, phys=phys,
        )

    def evict(self, attn_weights, active=None) -> "TOVACache":
        """attn_weights: (B, H, P) current-step post-softmax weights summed
        over the query heads of each group (§2.2: TOVA victim = argmin)."""
        p = self.k.shape[2]
        n_valid = jnp.sum(self.valid, axis=2)
        over = n_valid > self.budget
        scores = jnp.where(self.valid, attn_weights.astype(jnp.float32), jnp.inf)
        victim = jnp.argmin(scores, axis=2).astype(jnp.int32)
        hit = (jnp.arange(p)[None, None] == victim[..., None]) & over[..., None]
        blocks, dead = self.blocks.evict_ex(victim, over)
        pool, phys = self.pool, self.phys
        if pool is not None:
            pool, phys = block_pool.free_block(
                pool, phys, victim, dead & event_mask(active, victim.shape))
        return dataclasses.replace(
            self,
            pos=jnp.where(hit, INVALID_POS, self.pos),
            valid=self.valid & ~hit,
            blocks=blocks, pool=pool, phys=phys,
        )

    def valid_mask(self):
        return self.valid

    def positions(self):
        return self.pos

    def retained_tokens(self):
        return jnp.sum(self.valid, axis=-1)


# ---------------------------------------------------------------------------
# H2O
# ---------------------------------------------------------------------------


@_tree_dataclass
class H2OCache(LaneSliceable, HasBlockTable):
    k: jnp.ndarray       # (B, H, P, D) — P padded to a block_p multiple
    v: jnp.ndarray
    pos: jnp.ndarray
    valid: jnp.ndarray
    acc: jnp.ndarray       # (B, H, P) cumulative attention mass
    length: jnp.ndarray    # (B,) — per lane
    blocks: BlockTable     # incremental live-block table (flash-decode)
    recent_window: int = dataclasses.field(metadata={"static": True})
    slots: int = dataclasses.field(metadata={"static": True})  # logical arena
    pool: Optional[block_pool.BlockPool] = None
    phys: Optional[jnp.ndarray] = None       # (B, H, NB) int32, -1 = unmapped

    @staticmethod
    def init(batch, kv_heads, budget, head_dim, recent_window=None,
             dtype=jnp.bfloat16, block_p: int = 0, paged: bool = False,
             pool_blocks: Optional[int] = None):
        p = _round_up(budget, block_p)
        pool = phys = None
        if paged:
            pool, phys, z = init_paged(batch, kv_heads, p, head_dim, block_p,
                                       dtype, pool_blocks)
        else:
            z = jnp.zeros((batch, kv_heads, p, head_dim), dtype)
        rw = recent_window if recent_window is not None else budget // 2
        return H2OCache(z, z,
                        jnp.full((batch, kv_heads, p), INVALID_POS, jnp.int32),
                        jnp.zeros((batch, kv_heads, p), bool),
                        jnp.zeros((batch, kv_heads, p), jnp.float32),
                        jnp.zeros((batch,), jnp.int32),
                        BlockTable.init(batch, kv_heads, p, block_p),
                        rw, budget, pool=pool, phys=phys)

    @property
    def budget(self) -> int:
        return self.slots - 1

    def insert(self, k_new, v_new, active=None) -> "H2OCache":
        p = self.k.shape[2]
        free = ~self.valid & (jnp.arange(p)[None, None] < self.slots)
        slot = jnp.argmax(free, axis=2).astype(jnp.int32)
        hit = (jnp.arange(p)[None, None] == slot[..., None])
        newly = jnp.take_along_axis(free, slot[..., None], axis=2)[..., 0]
        k, v, pool, phys = store_token(self, slot, k_new, v_new, active)
        return dataclasses.replace(
            self,
            k=k, v=v,
            pos=jnp.where(hit, self.length[:, None, None], self.pos),
            valid=self.valid | hit,
            acc=jnp.where(hit, 0.0, self.acc),
            length=self.length + 1,
            blocks=self.blocks.insert(slot, newly),
            pool=pool, phys=phys,
        )

    def evict(self, attn_weights, active=None) -> "H2OCache":
        """Accumulate attention mass; evict the lowest-cumulative token outside
        the recency window when over budget (§2.2)."""
        p = self.k.shape[2]
        acc = self.acc + jnp.where(self.valid, attn_weights.astype(jnp.float32), 0.0)
        over = jnp.sum(self.valid, axis=2) > self.budget
        recent = self.pos >= (self.length - self.recent_window)[:, None, None]
        scores = jnp.where(self.valid & ~recent, acc, jnp.inf)
        any_evictable = jnp.any(jnp.isfinite(scores), axis=2)
        oldest = jnp.argmin(jnp.where(self.valid, self.pos, INVALID_POS), axis=2)
        victim = jnp.where(any_evictable, jnp.argmin(scores, axis=2), oldest).astype(jnp.int32)
        hit = (jnp.arange(p)[None, None] == victim[..., None]) & over[..., None]
        blocks, dead = self.blocks.evict_ex(victim, over)
        pool, phys = self.pool, self.phys
        if pool is not None:
            pool, phys = block_pool.free_block(
                pool, phys, victim, dead & event_mask(active, victim.shape))
        return dataclasses.replace(
            self,
            pos=jnp.where(hit, INVALID_POS, self.pos),
            valid=self.valid & ~hit,
            acc=jnp.where(hit, 0.0, acc),
            blocks=blocks, pool=pool, phys=phys,
        )

    def valid_mask(self):
        return self.valid

    def positions(self):
        return self.pos

    def retained_tokens(self):
        return jnp.sum(self.valid, axis=-1)


# ---------------------------------------------------------------------------
# Quest
# ---------------------------------------------------------------------------


@_tree_dataclass
class QuestCache(LaneSliceable):
    """Full cache + per-page min/max key metadata.  Pages are contiguous.

    ``page_size`` and ``top_pages`` are static; the *reads* accounting (what
    Quest actually saves) is ``top_pages * page_size`` per step per head.
    """

    k: jnp.ndarray        # (B, H, S, D)
    v: jnp.ndarray
    kmin: jnp.ndarray     # (B, H, S/page, D)
    kmax: jnp.ndarray
    length: jnp.ndarray   # (B,) — per lane
    page_size: int = dataclasses.field(metadata={"static": True})
    top_pages: int = dataclasses.field(metadata={"static": True})
    pool: Optional[block_pool.BlockPool] = None
    phys: Optional[jnp.ndarray] = None       # (B, H, NP) int32, -1 = unmapped

    @staticmethod
    def init(batch, kv_heads, max_len, head_dim, page_size, top_pages,
             dtype=jnp.bfloat16, paged: bool = False,
             pool_blocks: Optional[int] = None):
        assert max_len % page_size == 0
        n_pages = max_len // page_size
        pool = phys = None
        if paged:
            # pool page granularity == Quest's page_size, so the selected-page
            # block table indexes pool pages directly
            pool, phys, z = init_paged(batch, kv_heads, max_len, head_dim,
                                       page_size, dtype, pool_blocks)
        else:
            z = jnp.zeros((batch, kv_heads, max_len, head_dim), dtype)
        return QuestCache(
            z, z,
            jnp.full((batch, kv_heads, n_pages, head_dim), jnp.inf, jnp.float32),
            jnp.full((batch, kv_heads, n_pages, head_dim), -jnp.inf, jnp.float32),
            jnp.zeros((batch,), jnp.int32), page_size, top_pages,
            pool=pool, phys=phys)

    def append(self, k_new, v_new, active=None) -> "QuestCache":
        """k_new/v_new: (B, H, 1, D), written at each lane's own length."""
        t = self.length                                     # (B,)
        pool, phys = self.pool, self.phys
        if pool is not None:
            b, h = self.k.shape[:2]
            slot = jnp.broadcast_to(t[:, None, None], (b, h, 1))
            pool, phys = block_pool.token_write(
                pool, phys, slot, k_new, v_new,
                event_mask(active, (b, h, 1)))
            k, v = self.k, self.v       # zero-width; bytes go to the pool
        else:
            slot, act = _append_slots(t, 1, self.k.shape[:3], active)
            k = write_rows(self.k, slot, k_new, act)
            v = write_rows(self.v, slot, v_new, act)
        page = t // self.page_size                          # (B,)
        kf = k_new[..., 0, :].astype(jnp.float32)
        n_pages = self.kmin.shape[2]
        hit = (jnp.arange(n_pages)[None, :] == page[:, None])[:, None, :, None]
        kmin = jnp.where(hit, jnp.minimum(self.kmin, kf[..., None, :]), self.kmin)
        kmax = jnp.where(hit, jnp.maximum(self.kmax, kf[..., None, :]), self.kmax)
        return dataclasses.replace(self, k=k, v=v, kmin=kmin, kmax=kmax,
                                   length=t + 1, pool=pool, phys=phys)

    def select_pages(self, q: jnp.ndarray) -> jnp.ndarray:
        """Upper-bound page scores (§2.2): sum_d max(q_d*kmin_d, q_d*kmax_d).

        q: (B, H, D) — per-KV-head (group-pooled) query.  Returns a bool page
        mask (B, H, n_pages) marking the top-k live pages.
        """
        qf = q.astype(jnp.float32)[..., None, :]
        ub = jnp.sum(jnp.maximum(qf * self.kmin, qf * self.kmax), axis=-1)  # (B,H,P)
        n_pages = self.kmin.shape[2]
        live = (jnp.arange(n_pages)[None, :] * self.page_size) \
            < self.length[:, None]                          # (B, n_pages)
        ub = jnp.where(live[:, None], ub, -jnp.inf)
        k = min(self.top_pages, n_pages)
        thresh = jax.lax.top_k(ub, k)[0][..., -1:]
        sel = (ub >= thresh) & live[:, None]
        return sel

    def token_mask_from_pages(self, page_mask: jnp.ndarray) -> jnp.ndarray:
        s = self.k.shape[2]
        token_pages = jnp.arange(s) // self.page_size
        tok = jnp.take(page_mask, token_pages, axis=2)
        written = jnp.arange(s)[None, None, :] < self.length[:, None, None]
        return tok & written

    def block_table_from_pages(self, page_mask: jnp.ndarray):
        """Compact the selected-page bool mask into a flash-decode block
        table ``(tbl (B,H,NP) int32, n (B,H) int32)``: selected page ids
        first (ascending), so the kernel fetches exactly the top-k pages —
        Quest's reads-sparsity realized as HBM traffic, not just metering.
        Kept full-width (NP, not top_pages) because threshold ties can
        select more than ``top_pages`` pages; the kernel's per-(b,h) ``n``
        early-exits the unselected tail either way."""
        tbl = jnp.argsort(~page_mask, axis=-1, stable=True).astype(jnp.int32)
        n = jnp.sum(page_mask, axis=-1).astype(jnp.int32)
        return tbl, n

    def valid_mask(self):
        # length-prefix occupancy; mapped pool pages == blocks with any live
        # slot, the invariant the generic pooled prefix-import relies on
        s = self.k.shape[2]
        return jnp.arange(s)[None, None, :] < self.length[:, None, None]

    def positions(self):
        s = self.k.shape[2]
        return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, None],
                                self.k.shape[:2] + (s,))

    def retained_tokens(self):
        # memory footprint is FULL — that is Quest's trade-off
        s = self.k.shape[2]
        written = jnp.minimum(self.length, s)               # (B,)
        return jnp.broadcast_to(written[:, None], self.k.shape[:2])

    def reads_per_step(self):
        n_live_pages = jnp.minimum((self.length + self.page_size - 1) // self.page_size,
                                   self.top_pages)
        return n_live_pages * self.page_size                # (B,)


# ---------------------------------------------------------------------------
# DMC (append-or-merge)
# ---------------------------------------------------------------------------


@_tree_dataclass
class DMCCache(LaneSliceable):
    """Dynamic Memory Compression inference cache (Nawrot et al., 2024).

    α=1 ⇒ accumulate (k, v) into the most recent entry by weighted average
    with running weight z;  α=0 ⇒ append a fresh entry.
    """

    k: jnp.ndarray        # (B, H, P, D) fp32 accumulators — P padded to a
    #                       block_p multiple; occupancy is a count-prefix so
    #                       the live-block table is derived, not stored
    v: jnp.ndarray
    z: jnp.ndarray        # (B, H, P) accumulation weights
    count: jnp.ndarray    # (B, H) number of live entries
    length: jnp.ndarray   # (B,) — per lane
    pos: jnp.ndarray      # (B, H, P) newest-contribution position per entry
    block_p: int = dataclasses.field(metadata={"static": True}, default=0)
    pool: Optional[block_pool.BlockPool] = None   # fp32 pages (accumulators)
    phys: Optional[jnp.ndarray] = None       # (B, H, NB) int32, -1 = unmapped

    @staticmethod
    def init(batch, kv_heads, num_slots, head_dim, block_p: int = 0,
             paged: bool = False, pool_blocks: Optional[int] = None):
        p = _round_up(num_slots, block_p)
        pool = phys = None
        if paged:
            pool, phys, z4 = init_paged(batch, kv_heads, p, head_dim, block_p,
                                        jnp.float32, pool_blocks)
        else:
            z4 = jnp.zeros((batch, kv_heads, p, head_dim), jnp.float32)
        return DMCCache(z4, z4,
                        jnp.zeros((batch, kv_heads, p), jnp.float32),
                        jnp.zeros((batch, kv_heads), jnp.int32),
                        jnp.zeros((batch,), jnp.int32),
                        jnp.zeros((batch, kv_heads, p), jnp.int32), block_p,
                        pool=pool, phys=phys)

    def block_spec(self):
        tbl, n = prefix_block_spec(self.count, self.k.shape[2], self.block_p,
                                   self.k.shape[1])
        return tbl, n, self.block_p

    def step(self, k_new, v_new, alpha, omega=None, active=None) -> "DMCCache":
        """alpha: (B, H) bool merge decision; omega: optional (B, H) importance
        weight for the weighted average (defaults to 1)."""
        b, h, p = self.k.shape[:3]
        if omega is None:
            omega = jnp.ones((b, h), jnp.float32)
        kf = k_new[..., 0, :].astype(jnp.float32)
        vf = v_new[..., 0, :].astype(jnp.float32)
        merge = alpha & (self.count > 0)
        tgt = jnp.where(merge, jnp.maximum(self.count - 1, 0), self.count)  # slot index
        p_idx = jnp.arange(p)
        hit = p_idx[None, None] == tgt[..., None]
        pool, phys = self.pool, self.phys

        def blend(k_old, v_old):
            """The merge target's rows (B, H, Dh) after absorbing this
            token: their weighted average with the new (k, v), or the new
            (k, v) alone where the step appends."""
            z_tgt = jnp.take_along_axis(self.z, tgt[..., None], axis=2)[..., 0]
            z_old = jnp.where(merge, z_tgt, 0.0)
            z_new = z_old + omega
            k_row = (jnp.where(merge[..., None], k_old, 0.0) * z_old[..., None]
                     + kf * omega[..., None]) / z_new[..., None]
            v_row = (jnp.where(merge[..., None], v_old, 0.0) * z_old[..., None]
                     + vf * omega[..., None]) / z_new[..., None]
            return k_row[..., None, :], v_row[..., None, :]

        # tgt == P (arena full) writes nothing
        wm = (event_mask(active, (b, h)) & (tgt < p))[..., None]
        if pool is not None:
            k_row, v_row = blend(
                block_pool.gather_rows(pool.k, phys, tgt, self.block_p),
                block_pool.gather_rows(pool.v, phys, tgt, self.block_p))
            pool, phys = block_pool.token_write(pool, phys, tgt[..., None],
                                                k_row, v_row, wm)
            k, v = self.k, self.v       # zero-width; bytes go to the pool
        else:
            k_row, v_row = blend(read_rows(self.k, tgt),
                                 read_rows(self.v, tgt))
            k = write_rows(self.k, tgt[..., None], k_row, wm)
            v = write_rows(self.v, tgt[..., None], v_row, wm)
        z = jnp.where(hit, jnp.where(merge[..., None], self.z, 0.0) + omega[..., None],
                      self.z)
        count = jnp.where(merge, self.count, self.count + 1)
        # a merged entry is "as recent as" its newest contribution: stamp the
        # touched slot with the current position so layer_map window layers
        # can mask DMC entries (small leaves: the lane freeze rolls them back)
        pos = jnp.where(hit, self.length[:, None, None], self.pos)
        return dataclasses.replace(self, k=k, v=v, z=z, count=count,
                                   length=self.length + 1, pos=pos,
                                   pool=pool, phys=phys)

    def valid_mask(self):
        p = self.k.shape[2]
        return jnp.arange(p)[None, None] < self.count[..., None]

    def positions(self):
        return self.pos

    def retained_tokens(self):
        return self.count
