"""Keyformer (Adnan et al., 2024): score-based KV eviction with
Gumbel-softmax regularization.

Intuition: post-softmax attention weights are a biased importance signal —
once tokens are dropped, the softmax renormalises over survivors and
over-weights recency.  Keyformer regularises the per-step score with Gumbel
noise and a temperature ``tau`` before accumulating, which both smooths the
distribution and injects the stochastic tie-breaking the paper shows matters
for long-tail retention.  A recency window is always protected (like H2O);
outside it, the token with the lowest accumulated regularised score is
evicted when over budget.

This module is the registry's worked extension example: it defines its own
cache pytree and plugs in purely through ``@register_policy`` + the
``KVPolicy`` lifecycle — zero edits to ``models/`` or ``serving/`` (see
docs/policies.md for the walkthrough).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import block_pool
from repro.core.config import ArchConfig, KVPolicyConfig
from repro.core.kv_cache import (INVALID_POS, BlockTable, HasBlockTable,
                                 LaneSliceable, _round_up, _tree_dataclass,
                                 event_mask, init_paged, store_token)
from repro.core.policy import KVPolicy, _attend_spec, register_policy

_SCORE_EPS = 1e-9
_NOISE_SEED = 0x5EED  # fixed: decode must be reproducible per (seed, step)


@_tree_dataclass
class KeyformerCache(LaneSliceable, HasBlockTable):
    k: jnp.ndarray       # (B, H, P, D) — P padded to a block_p multiple
    v: jnp.ndarray
    pos: jnp.ndarray     # (B, H, P) int32
    valid: jnp.ndarray   # (B, H, P) bool
    score: jnp.ndarray   # (B, H, P) f32 — accumulated regularised scores
    length: jnp.ndarray  # (B,) — per lane
    salt: jnp.ndarray    # (B,) uint32 — per-layer noise salt (see insert)
    blocks: BlockTable   # incremental live-block table (flash-decode)
    recent_window: int = dataclasses.field(metadata={"static": True})
    slots: int = dataclasses.field(metadata={"static": True})  # logical arena
    tau: float = dataclasses.field(metadata={"static": True}, default=1.0)
    pool: Optional[block_pool.BlockPool] = None
    phys: Optional[jnp.ndarray] = None       # (B, H, NB) int32, -1 = unmapped

    @staticmethod
    def init(batch, kv_heads, budget, head_dim, recent_window, tau,
             dtype=jnp.bfloat16, block_p: int = 0, paged: bool = False,
             pool_blocks=None):
        p = _round_up(budget, block_p)
        pool = phys = None
        if paged:
            pool, phys, z = init_paged(batch, kv_heads, p, head_dim, block_p,
                                       dtype, pool_blocks)
        else:
            z = jnp.zeros((batch, kv_heads, p, head_dim), dtype)
        return KeyformerCache(
            z, z,
            jnp.full((batch, kv_heads, p), INVALID_POS, jnp.int32),
            jnp.zeros((batch, kv_heads, p), bool),
            jnp.zeros((batch, kv_heads, p), jnp.float32),
            jnp.zeros((batch,), jnp.int32),
            jnp.zeros((batch,), jnp.uint32),
            BlockTable.init(batch, kv_heads, p, block_p),
            recent_window, budget, tau, pool=pool, phys=phys)

    @property
    def budget(self) -> int:
        return self.slots - 1   # arena is budget + 1 (insert-then-evict)

    def insert(self, k_new, v_new, active=None,
               salt=None) -> "KeyformerCache":
        p = self.k.shape[2]
        free = ~self.valid & (jnp.arange(p)[None, None] < self.slots)
        slot = jnp.argmax(free, axis=2).astype(jnp.int32)         # first free
        hit = (jnp.arange(p)[None, None] == slot[..., None])
        newly = jnp.take_along_axis(free, slot[..., None], axis=2)[..., 0]
        k, v, pool, phys = store_token(self, slot, k_new, v_new, active)
        # Stash the layer salt for this step's Gumbel draw.  It must be
        # derived from something bit-identical between the kernel and
        # reference attention paths — activations (k_new, attention weights)
        # differ by float ulps at layers > 0 and a bitcast salt would fork
        # the whole noise stream — so the policy passes a per-layer PARAM
        # scalar (decorrelating layers) and the draw folds it with the
        # per-lane logical step (decorrelating steps; see
        # ``accumulate_and_evict``).
        if salt is None:
            salt = jnp.zeros((), jnp.uint32)
        salt = jnp.broadcast_to(jnp.asarray(salt, jnp.uint32),
                                self.length.shape)
        return dataclasses.replace(
            self,
            k=k, v=v,
            pos=jnp.where(hit, self.length[:, None, None], self.pos),
            valid=self.valid | hit,
            score=jnp.where(hit, 0.0, self.score),
            length=self.length + 1,
            salt=salt,
            blocks=self.blocks.insert(slot, newly),
            pool=pool, phys=phys)

    def accumulate_and_evict(self, attn_weights, active=None) -> "KeyformerCache":
        """attn_weights: (B, H, P) group-summed post-softmax weights.

        Score update (Keyformer §4): softmax((log w + Gumbel noise) / tau)
        over live slots, accumulated; evict argmin outside the recency window
        when over budget.  Noise is derived from a fixed key folded with the
        logical step, so jitted decode stays deterministic and scan-safe.
        """
        p = self.k.shape[2]
        w = attn_weights.astype(jnp.float32)
        # Noise is derived PER LANE from (lane step, layer salt): lanes are
        # independent streams under continuous batching, so the draw must not
        # see other lanes (batch invariance — a forked chain replays exactly
        # the same noise as an independently-prefilled one).  The layer salt
        # (stored by ``insert`` from a per-layer param scalar) decorrelates
        # layers while staying attention-implementation-independent, which
        # is what keeps ``use_kernel`` decode token-equal to the reference.
        base = jax.random.PRNGKey(_NOISE_SEED)

        def draw(len_b, salt_b):
            k = jax.random.fold_in(base, len_b)
            k = jax.random.fold_in(k, salt_b)
            return jax.random.bits(k, w.shape[1:], jnp.uint32)

        bits = jax.vmap(draw)(self.length, self.salt)
        # bits -> uniform via exact steps only: mantissa-fill to [1, 2),
        # the exact -1.0, and a clip.  ``jax.random.uniform``'s affine
        # minval/maxval rescale FMA-fuses differently at different batch
        # shapes, breaking the bitwise fork == tiled-prefill contract.
        u01 = jax.lax.bitcast_convert_type(
            (bits >> 9) | jnp.uint32(0x3F800000), jnp.float32) - 1.0
        u = jnp.clip(u01, _SCORE_EPS, 1.0 - _SCORE_EPS)
        gumbel = -jnp.log(-jnp.log(u))
        logits = jnp.where(self.valid, jnp.log(w + _SCORE_EPS) + gumbel, -jnp.inf)
        reg = jax.nn.softmax(logits / self.tau, axis=-1)
        score = self.score + jnp.where(self.valid, reg, 0.0)

        over = jnp.sum(self.valid, axis=2) > self.budget
        recent = self.pos >= (self.length - self.recent_window)[:, None, None]
        cand = jnp.where(self.valid & ~recent, score, jnp.inf)
        any_evictable = jnp.any(jnp.isfinite(cand), axis=2)
        oldest = jnp.argmin(jnp.where(self.valid, self.pos, INVALID_POS), axis=2)
        victim = jnp.where(any_evictable, jnp.argmin(cand, axis=2),
                           oldest).astype(jnp.int32)
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
            score=jnp.where(hit, 0.0, score),
            blocks=blocks, pool=pool, phys=phys)

    def valid_mask(self):
        return self.valid

    def positions(self):
        return self.pos

    def retained_tokens(self):
        return jnp.sum(self.valid, axis=-1)


@register_policy("keyformer")
class KeyformerPolicy(KVPolicy):
    def init_cache(self, arch: ArchConfig, batch: int, max_len: int,
                   cfg: KVPolicyConfig, *, layer_window, dtype):
        a = arch.attn
        budget = cfg.budget or max(int(max_len / cfg.cr), 1)
        return KeyformerCache.init(batch, a.num_kv_heads, budget + 1,
                                   a.head_dim, max(budget // 2, 1),
                                   cfg.keyformer_tau, dtype,
                                   block_p=cfg.block_p, paged=cfg.paged,
                                   pool_blocks=cfg.pool_blocks)

    def decode_update(self, cache, q, k_new, v_new, aux):
        cache = cache.insert(k_new, v_new, active=aux.get("active"),
                             salt=aux.get("layer_salt"))
        return cache, _attend_spec(cache, needs_weights=True)

    def post_attend(self, cache, weights, active=None):
        return cache.accumulate_and_evict(weights, active=active)
