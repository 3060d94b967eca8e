"""Unified, pluggable KV cache-policy API: the ``KVPolicy`` registry.

The paper's hyper-scaling results hinge on *which* compression policy runs
(DMS vs. training-free baselines vs. DMC), so the policy abstraction must be
a first-class, extensible contract rather than ``if policy.kind == ...``
chains smeared across the model and engine.  This module defines that
contract; every policy owns its full lifecycle:

* ``init_cache(arch, batch, max_len, cfg, layer_window, dtype)`` — provision
  the cache arena for one attention layer.
* ``decode_update(cache, q, k_new, v_new, aux) -> (cache, AttendSpec)`` —
  absorb one decoded token and describe what this step's attention should
  read (keys/values, visibility, positions, whether post-softmax weights are
  needed back).
* ``post_attend(cache, weights)`` — optional second phase for policies whose
  eviction depends on the current step's attention weights (TOVA, H2O,
  Keyformer).
* ``prefill_import(...)`` — build the cache from full-attention prefill
  outputs (e.g. :meth:`SlotDMSCache.from_prefill`), including un-executed
  delayed-eviction decisions.
* ``fork_cache(cache, width)`` / ``gather_cache(cache, src)`` — the
  shared-prefill fork: prefill a prompt once, clone the cache pytree into W
  hyper-scaling chains instead of re-prefilling W times (``fork_cache``
  widens the batch; ``gather_cache`` is the in-place lane shuffle the
  scheduler uses inside its fixed lane arena).
* ``reclaim_cache(cache, reset_mask, fresh)`` — per-lane arena reset: lanes
  where ``reset_mask`` is True return to the pristine ``fresh`` state (EOS
  early-exit frees a lane's slots for the next admitted request).
* ``export_prefix(cache, lane)`` / ``import_prefix(cache, snap, lane)`` — the
  cross-request prefix lifecycle: snapshot one lane's complete state at a
  token boundary (everything needed to continue decoding, including pending
  eviction rings and score accumulators) and restore it into a pristine lane
  later, so even compressed/evicting caches can reuse a shared prompt prefix
  across requests (see :mod:`repro.serving.prefix_cache`).
* ``metrics(cache)`` — the paper's two budget axes, policy-defined instead of
  engine-guessed: ``live_tokens`` (peak-memory axis), ``reads_tokens``
  (KV-reads axis; differs from live for Quest) and ``peak_bytes`` (physical
  arena bytes, static).

Policies register by name with :func:`register_policy`; the model/engine
dispatch purely through the registry via the :class:`PolicyCache` pytree
wrapper, whose ``policy`` name rides in static (hashable) aux data — so
``jax.jit`` re-traces per policy but the *code* is policy-agnostic.  Adding a
new policy (see :mod:`repro.core.keyformer`) requires zero edits to
``models/`` or ``serving/``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import block_pool
from repro.core.baselines import DMCCache, H2OCache, QuestCache, TOVACache
from repro.core.config import ArchConfig, KVPolicyConfig
from repro.core.kv_cache import (MaskedDMSCache, SlotDMSCache, VanillaCache,
                                 _tree_dataclass, pack_dense)


# ---------------------------------------------------------------------------
# wire types
# ---------------------------------------------------------------------------


@dataclass
class AttendSpec:
    """What one decode step's attention should read.

    ``k``/``v``: (B, Hkv, P, Dh); ``visible``: (B, Hkv, P) bool — canonical:
    construction broadcasts lazily-shaped masks (VanillaCache's (B, 1, P))
    up to the full per-head shape so the reference einsum, the kernel
    dispatch, and the weights-out scatter all see one mask layout.
    ``positions``: per-slot logical positions for local-window masking, or
    ``None`` when no positions are available.
    ``needs_weights`` requests the group-summed post-softmax weights back via
    :meth:`KVPolicy.post_attend`.

    ``block_tbl``/``block_n``/``block_p`` are the **block-table contract**
    with the flash-decode kernel (docs/kernels.md): ``block_tbl`` (B, Hkv,
    NB) int32 lists the arena's live ``block_p``-sized K/V blocks per (lane,
    kv head), compacted into the first ``block_n`` (B, Hkv) entries.  The
    kernel's scalar-prefetched table drives its copies of exactly those
    blocks, so decode HBM traffic scales with live tokens instead of arena capacity.
    Every *visible* slot must be covered by a listed block (a listed block
    may still contain dead slots — the kernel masks those via ``visible``);
    ``block_p == 0`` means "no table" and the kernel falls back to streaming
    the whole arena.  When ``block_p > 0`` the arena extent P must be a
    ``block_p`` multiple (caches allocate pre-padded; see
    ``KVPolicyConfig.block_p``).

    ``pool_k``/``pool_v``/``phys`` are set for paged caches (same dtype as
    ``k``): the flash kernel then streams pool pages directly — ``block_tbl``
    entries are *logical* block ids translated through ``phys`` at dispatch
    (see :func:`repro.kernels.ops.dms_decode_attention`) — while ``k``/``v``
    hold the gathered dense view for the reference path (dead code under the
    kernel).
    """

    k: jnp.ndarray
    v: jnp.ndarray
    visible: jnp.ndarray
    positions: Optional[jnp.ndarray] = None
    needs_weights: bool = False
    block_tbl: Optional[jnp.ndarray] = None
    block_n: Optional[jnp.ndarray] = None
    block_p: int = 0
    pool_k: Optional[jnp.ndarray] = None     # (NPOOL, block_p, Dh)
    pool_v: Optional[jnp.ndarray] = None
    phys: Optional[jnp.ndarray] = None       # (B, Hkv, NB) int32

    def __post_init__(self):
        # canonicalize lazy (B, 1, P) visibility masks to (B, Hkv, P) at the
        # single construction chokepoint — a broadcast is free under jit and
        # both attention paths (and the weights scatter) rely on the shape
        tgt = self.k.shape[:3]
        if self.visible.shape != tgt:
            self.visible = jnp.broadcast_to(self.visible, tgt)


@_tree_dataclass
class PolicyCache:
    """Pytree wrapper binding a cache state to its policy *by name*.

    The name lives in static aux data, so dispatch inside jitted code is a
    trace-time registry lookup — no isinstance chains, and the cache pytree
    stays an opaque, shardable container for the engine.
    """

    cache: Any
    policy: str = dataclasses.field(metadata={"static": True}, default="vanilla")

    @property
    def length(self) -> jnp.ndarray:
        return self.cache.length


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, "KVPolicy"] = {}


def register_policy(name: str) -> Callable[[type], type]:
    """Class decorator: instantiate and register a :class:`KVPolicy` by name."""

    def deco(cls: type) -> type:
        if name in _REGISTRY:
            raise ValueError(
                f"KV policy {name!r} already registered "
                f"(by {type(_REGISTRY[name]).__name__})")
        pol = cls()
        pol.name = name
        _REGISTRY[name] = pol
        return cls

    return deco


def get_policy(name: str) -> "KVPolicy":
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown KV policy {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def available_policies() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def init_policy_cache(arch: ArchConfig, batch: int, max_len: int,
                      cfg: KVPolicyConfig, *, layer_kind: str = "attn",
                      layer_window: Optional[int] = None,
                      dtype=None) -> PolicyCache:
    """Provision one attention layer's cache through the registry."""
    name = cfg.kind_for_layer(layer_kind)
    pol = get_policy(name)
    dtype = dtype or jnp.dtype(arch.dtype)
    inner = pol.init_cache(arch, batch, max_len, cfg,
                           layer_window=layer_window, dtype=dtype)
    return PolicyCache(cache=inner, policy=name)


def iter_policy_caches(tree: Any) -> Iterator[PolicyCache]:
    """Yield every :class:`PolicyCache` node in a decode-state pytree."""
    leaves = jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, PolicyCache))
    for leaf in leaves:
        if isinstance(leaf, PolicyCache):
            yield leaf


def map_pooled_caches(state: Any, fn: Callable[[int, Any], Any]) -> Any:
    """Rebuild a decode state with ``fn(pooled_idx, cache)`` applied to every
    *pooled* cache (non-pooled caches pass through untouched).

    ``pooled_idx`` counts pooled caches in :func:`iter_policy_caches` order —
    the same order the scheduler's ``_pool_descs`` and the fault injector's
    ghost-ref ledgers use, so per-pool host arrays line up by index."""
    counter = [0]

    def visit(node):
        if isinstance(node, PolicyCache) \
                and getattr(node.cache, "pool", None) is not None:
            idx = counter[0]
            counter[0] += 1
            return dataclasses.replace(node, cache=fn(idx, node.cache))
        return node

    return jax.tree_util.tree_map(
        visit, state, is_leaf=lambda x: isinstance(x, PolicyCache))


def state_peak_bytes(state: Any) -> int:
    """Physical KV arena bytes of a decode state (uniform metrics contract).

    Works on both per-layer caches and the stacked (superblock-leading)
    decode state — ``peak_bytes`` is purely shape-derived.
    """
    return sum(get_policy(pc.policy).peak_bytes(pc.cache)
               for pc in iter_policy_caches(state))


def state_pool_stats(state: Any) -> Optional[Dict[str, Any]]:
    """Aggregate paged-pool counters across every pooled cache in a decode
    state (host-side; call outside jit).  None when nothing is paged.

    ``live_tokens`` comes from each cache's incremental BlockTable ``count``
    (live slots per block — sums shape-safely whatever the leading stacking),
    so ``fragmentation`` is the global share of *mapped page capacity* not
    holding a live token: padded-vs-packed waste inside allocated pages."""
    out: Optional[Dict[str, Any]] = None
    mapped_cap = 0
    for pc in iter_policy_caches(state):
        pool = getattr(pc.cache, "pool", None)
        if pool is None:
            continue
        s = block_pool.stats(pool, pc.cache.phys,
                             live_tokens=pc.cache.blocks.count)
        mapped_cap += s["mapped_entries"] * pool.block_p
        if out is None:
            out = dict(s)
            out["pools"] = 1
        else:
            for key in ("pool_blocks", "allocated_blocks", "free_blocks",
                        "shared_blocks", "cow_copies", "alloc_events",
                        "high_water_blocks", "superblocks", "mapped_entries",
                        "live_tokens"):
                out[key] += s[key]
            out["exhausted"] = out["exhausted"] or s["exhausted"]
            out["pools"] += 1
    if out is not None:
        out["fragmentation"] = (1.0 - out["live_tokens"] / mapped_cap
                                if mapped_cap else 0.0)
    return out


def _nbytes(a) -> int:
    n = 1
    for s in a.shape:
        n *= int(s)
    return n * jnp.dtype(a.dtype).itemsize


def _budget_tokens(cfg: KVPolicyConfig, max_len: int) -> int:
    return cfg.budget or max(int(max_len / cfg.cr), 1)


# ---------------------------------------------------------------------------
# the protocol
# ---------------------------------------------------------------------------


class KVPolicy:
    """Base contract.  Subclass, implement the lifecycle, decorate with
    ``@register_policy("name")`` — the model/engine pick it up untouched."""

    name: str = ""
    #: "none" — policy never sees eviction decisions;
    #: "dms"  — extract binarised DMS α when ``arch.dms.enabled``;
    #: "always" — extract α from the borrowed neuron unconditionally (DMC).
    alpha_mode: str = "none"

    # -- lifecycle -----------------------------------------------------------

    def init_cache(self, arch: ArchConfig, batch: int, max_len: int,
                   cfg: KVPolicyConfig, *, layer_window: Optional[int],
                   dtype) -> Any:
        raise NotImplementedError

    def decode_update(self, cache: Any, q: jnp.ndarray, k_new: jnp.ndarray,
                      v_new: jnp.ndarray, aux: Dict[str, Any]
                      ) -> Tuple[Any, AttendSpec]:
        """q: (B, 1, Hq, Dh) post-RoPE; k_new/v_new: (B, Hkv, 1, Dh) post-RoPE.

        aux carries ``alpha_bin`` ((B, Hkv) bool or None), ``pos_t``,
        ``attn_cfg``, ``arch`` and ``dtype``.
        """
        raise NotImplementedError

    def post_attend(self, cache: Any, weights: jnp.ndarray,
                    active: Optional[jnp.ndarray] = None) -> Any:
        """Second phase when ``AttendSpec.needs_weights``; ``weights`` is the
        group-summed post-softmax distribution (B, Hkv, P).  ``active`` is
        the scheduler's per-lane live mask — paged caches gate pool mutation
        on it (shared pool state cannot be rolled back by lane_select)."""
        return cache

    def prefill_import(self, arch: ArchConfig, cfg: KVPolicyConfig,
                       k: jnp.ndarray, v: jnp.ndarray,
                       positions: jnp.ndarray, retained: Optional[jnp.ndarray],
                       alpha_bin: Optional[jnp.ndarray], *, max_len: int,
                       layer_window: Optional[int] = None, dtype=None) -> Any:
        """Build a cache from full-attention prefill outputs (k/v:
        (B, Hkv, T, Dh) post-RoPE, e.g. ``make_prefill_step``'s ``layer_kv``).

        ``Engine`` currently teacher-forces prompts through the decode path
        (exact eviction semantics for every policy); this hook is for callers
        that run a dense prefill and import the result — policies without an
        import path raise."""
        raise NotImplementedError(f"{self.name}: no prefill import path")

    # -- lane lifecycle (continuous batching / hyperscale fork) --------------

    def fork_cache(self, cache: Any, width: int, *, axis: int = 0) -> Any:
        """Clone every lane of ``cache`` into ``width`` adjacent lanes.

        The shared-prefill fork: prefill once at batch B, fork to B·W chains
        — forked chains see bitwise-identical cache contents, so their first
        decode step matches W independent prefills while the prefill-phase
        KV reads drop by W×.  The default tiles the lane axis of every array
        leaf (all caches are lane-leading pytrees); policies with non-lane
        state override.  ``axis`` selects the lane axis (1 for decode states
        stacked over superblocks).

        Paged caches fork **copy-on-write**: only the per-lane page map
        tiles and refcounts are recomputed — zero pool bytes move until a
        forked chain's first divergent write (token_write's CoW path)."""
        pool = getattr(cache, "pool", None)
        if pool is None:
            return jax.tree_util.tree_map(
                lambda a: jnp.repeat(a, width, axis=axis), cache)
        body = dataclasses.replace(cache, pool=None)
        body = jax.tree_util.tree_map(
            lambda a: jnp.repeat(a, width, axis=axis), body)
        return dataclasses.replace(
            body, pool=block_pool.set_refcounts(pool, body.phys))

    def gather_cache(self, cache: Any, src: jnp.ndarray, *,
                     axis: int = 0) -> Any:
        """Lane shuffle: new lane ``l`` takes old lane ``src[l]``'s state —
        how the scheduler forks a prefilled lane into free lanes of a
        fixed-size arena (``src`` is the identity except forked targets).
        Same override point as :meth:`fork_cache` for policies whose state
        is not purely lane-leading.

        Paged: the page map shuffles like any per-lane leaf, then refcounts
        are recomputed — duplicated lanes become CoW sharers, dropped lanes'
        pages fall back to the free list."""
        pool = getattr(cache, "pool", None)
        if pool is None:
            return jax.tree_util.tree_map(
                lambda a: jnp.take(a, src, axis=axis), cache)
        body = dataclasses.replace(cache, pool=None)
        body = jax.tree_util.tree_map(
            lambda a: jnp.take(a, src, axis=axis), body)
        return dataclasses.replace(
            body, pool=block_pool.set_refcounts(pool, body.phys))

    # -- prefix lifecycle (cross-request radix prefix cache) -----------------

    def export_prefix(self, cache: Any, lane, *, axis: int = 0) -> Any:
        """Snapshot one lane's complete cache state at a token boundary.

        Returns a width-1-lane pytree of the same structure as ``cache``
        (static fields ride along), suitable for host storage in the
        cross-request prefix cache and later re-import.  The contract: for a
        lane that has consumed exactly the L prefix tokens, the snapshot holds
        *everything* the policy needs to continue decoding — arena contents,
        free lists, pending eviction rings, score accumulators, page metadata
        — so ``import_prefix`` + suffix prefill is bitwise-equal to a cold
        prefill of the full prompt.  All built-in caches keep their per-lane
        state lane-leading (:class:`~repro.core.kv_cache.LaneSliceable`), so
        the default is a pure lane slice; policies with non-lane state must
        override both hooks together (same override point as
        :meth:`fork_cache`).  ``lane`` may be a traced int32 scalar.

        Paged caches **densify** on export: the lane's pool pages are
        gathered into a fixed-arena-shaped snapshot (``pool``/``phys`` =
        None) — byte-compatible with snapshots from a fixed-arena engine, so
        the prefix cache stores one format."""
        pool = getattr(cache, "pool", None)
        if pool is None:
            return cache.export_lane(lane, axis=axis)
        if axis:
            return jax.vmap(
                lambda c: self.export_prefix(c, lane, axis=0))(cache)
        phys_l = jax.lax.dynamic_slice_in_dim(cache.phys, lane, 1, axis=0)
        k, v = block_pool.dense_kv(pool, phys_l)             # (1, H, P, Dh)
        snap = dataclasses.replace(cache, pool=None, phys=None
                                   ).export_lane(lane, axis=0)
        return dataclasses.replace(snap, k=k, v=v)

    def import_prefix(self, cache: Any, snap: Any, lane, *, axis: int = 0
                      ) -> Any:
        """Restore an :meth:`export_prefix` snapshot into lane ``lane``.

        The target lane must be pristine (just reclaimed/initialised); the
        snapshot overwrites every leaf's lane slice, so the lane continues
        exactly where the exporting request's prefill stood.

        Paged caches re-page the dense snapshot: pages are allocated for
        every block with a live slot, snapshot block contents scatter into
        them, and the lane's page map + refcounts are rebuilt.  Pool
        exhaustion drops the affected blocks (reads as zeros, masked) and
        latches ``pool.exhausted``."""
        pool = getattr(cache, "pool", None)
        if pool is None:
            return cache.import_lane(snap, lane, axis=axis)
        if axis:
            return jax.vmap(
                lambda c, s: self.import_prefix(c, s, lane, axis=0)
            )(cache, snap)
        bp = pool.block_p
        _, hh, nbb = cache.phys.shape
        p, dh = snap.k.shape[2], snap.k.shape[3]
        valid = jnp.broadcast_to(snap.valid_mask(), (1, hh, p))
        need = jnp.any(valid.reshape(hh, nbb, bp), axis=-1).reshape(-1)
        pool, page, ok = block_pool.alloc(pool, need)
        dst = jnp.where(need & ok, page, pool.num_blocks)
        pool = dataclasses.replace(
            pool,
            k=pool.k.at[dst].set(
                snap.k.reshape(hh * nbb, bp, dh).astype(pool.k.dtype),
                mode="drop"),
            v=pool.v.at[dst].set(
                snap.v.reshape(hh * nbb, bp, dh).astype(pool.v.dtype),
                mode="drop"))
        phys_lane = jnp.where(need & ok, page, -1).reshape(1, hh, nbb)
        phys = jax.lax.dynamic_update_slice_in_dim(cache.phys, phys_lane,
                                                   lane, axis=0)
        pool = dataclasses.replace(
            pool, ref=block_pool.recount(phys, pool.num_blocks))
        body = dataclasses.replace(cache, pool=None, phys=None)
        snap_z = dataclasses.replace(
            snap, pool=None, phys=None,
            k=snap.k[..., :0].astype(cache.k.dtype),
            v=snap.v[..., :0].astype(cache.v.dtype))
        body = body.import_lane(snap_z, lane, axis=0)
        return dataclasses.replace(body, pool=pool, phys=phys)

    def import_slab(self, slab: Any, snap: Any, slot, *, axis: int = 0
                    ) -> Any:
        """Device-side variant of :meth:`import_prefix` for the hot-tier
        snapshot slab: write a width-1 snapshot into storage slot ``slot``.

        The slab is *storage*, not a decode cache — it is ``slots`` stacked
        copies of whatever pytree :meth:`export_prefix` returns (see
        :func:`repro.models.transformer.init_snapshot_slab`), so the default
        is a pure ``dynamic_update_slice`` on the snapshot's own leaves.
        Runs jitted with both operands device-resident: a deferred export
        costs zero host↔device bytes.  A policy whose ``export_prefix``
        snapshot is not a width-1-lane pytree must override this pair
        alongside the prefix pair."""
        return jax.tree_util.tree_map(
            lambda d, s: jax.lax.dynamic_update_slice_in_dim(
                d, s.astype(d.dtype), slot, axis=axis), slab, snap)

    def export_slab(self, slab: Any, slot, *, axis: int = 0) -> Any:
        """Device-side variant of :meth:`export_prefix`: fetch the snapshot
        stored in slab slot ``slot`` (the zero-copy hot-hit path — the
        result feeds :meth:`import_prefix` device-to-device)."""
        return jax.tree_util.tree_map(
            lambda d: jax.lax.dynamic_slice_in_dim(d, slot, 1, axis=axis),
            slab)

    def reclaim_cache(self, cache: Any, reset_mask: jnp.ndarray,
                      fresh: Any, *, axis: int = 0) -> Any:
        """Reset lanes where ``reset_mask`` (B,) is True to the pristine
        ``fresh`` cache: the EOS-reclamation hook.  A reclaimed lane's arena
        reads as empty (``live_tokens`` ≈ 0) and its free list is full, so
        the scheduler can admit the next request into it.

        Paged: the reclaimed lane's page-map rows reset to -1 and refcounts
        are recomputed, so its pages return to the free list the moment no
        CoW sharer still maps them.  The pool itself (bytes + counters) is
        kept — counters are monotone observability state."""

        def sel(cur, init):
            m = reset_mask.reshape((1,) * axis + (-1,)
                                   + (1,) * (cur.ndim - axis - 1))
            return jnp.where(m, init, cur)

        pool = getattr(cache, "pool", None)
        if pool is None:
            return jax.tree_util.tree_map(sel, cache, fresh)
        body = jax.tree_util.tree_map(
            sel, dataclasses.replace(cache, pool=None),
            dataclasses.replace(fresh, pool=None))
        return dataclasses.replace(
            body, pool=block_pool.set_refcounts(pool, body.phys))

    # -- accounting ----------------------------------------------------------

    def metrics(self, cache: Any) -> Dict[str, Any]:
        """Budget accounting, policy-defined.  ``live_tokens``/``reads_tokens``
        are (B,) arrays (mean over kv heads); ``peak_bytes`` is a static int
        (physical arena size, valid under tracing as a constant)."""
        live = cache.retained_tokens().astype(jnp.float32).mean(axis=-1)
        return {"live_tokens": live, "reads_tokens": live,
                "peak_bytes": self.peak_bytes(cache)}

    def peak_bytes(self, cache: Any) -> int:
        pool = getattr(cache, "pool", None)
        if pool is not None:
            # paged: the device footprint IS the pool; per-lane arenas are
            # zero-width placeholders
            return _nbytes(pool.k) + _nbytes(pool.v)
        return _nbytes(cache.k) + _nbytes(cache.v)


# ---------------------------------------------------------------------------
# built-in policies
# ---------------------------------------------------------------------------


def _attend_spec(cache, **kw) -> AttendSpec:
    """Uniform spec builder: attach the cache's live-block table when it
    maintains one (``block_spec`` is the cache-side half of the kernel's
    block-table contract — see docs/kernels.md).

    Paged caches additionally pass the pool arena through for the kernel and
    gather a dense view for the reference path (DCE'd under the kernel)."""
    tbl, n, bp = cache.block_spec() if hasattr(cache, "block_spec") \
        else (None, None, 0)
    pool = getattr(cache, "pool", None)
    if pool is not None:
        k, v = block_pool.dense_kv(pool, cache.phys)
        return AttendSpec(k, v, cache.valid_mask(), cache.positions(),
                          block_tbl=tbl, block_n=n, block_p=bp,
                          pool_k=pool.k, pool_v=pool.v, phys=cache.phys, **kw)
    return AttendSpec(cache.k, cache.v, cache.valid_mask(), cache.positions(),
                      block_tbl=tbl, block_n=n, block_p=bp, **kw)


class _SlotRingMixin:
    """Shared decode path for slot-arena caches (dms / vanilla-local / window)."""

    @staticmethod
    def _slot_update(cache, k_new, v_new, aux):
        cfg = aux["attn_cfg"]
        b = k_new.shape[0]
        alpha = aux.get("alpha_bin")
        if alpha is None:
            alpha = jnp.zeros((b, cfg.num_kv_heads), bool)
        cache = cache.step(k_new, v_new, alpha, active=aux.get("active"))
        return cache, _attend_spec(cache)


@register_policy("vanilla")
class VanillaPolicy(_SlotRingMixin, KVPolicy):
    """Dense append-only cache; local-attention layers get a ring buffer
    (overflow recycling == sliding window) so memory stays O(window)."""

    def init_cache(self, arch, batch, max_len, cfg, *, layer_window, dtype):
        a = arch.attn
        if layer_window is not None:
            eff_len = min(max_len, layer_window + 1)
            return SlotDMSCache.init(batch, a.num_kv_heads, eff_len, a.head_dim,
                                     max(arch.dms.window, 1), dtype,
                                     dms_active=False, block_p=cfg.block_p,
                                     paged=cfg.paged,
                                     pool_blocks=cfg.pool_blocks)
        return VanillaCache.init(batch, a.num_kv_heads, max_len, a.head_dim,
                                 dtype, block_p=cfg.block_p, paged=cfg.paged,
                                 pool_blocks=cfg.pool_blocks)

    def decode_update(self, cache, q, k_new, v_new, aux):
        if isinstance(cache, VanillaCache):
            cache = cache.append(k_new, v_new, active=aux.get("active"))
            return cache, _attend_spec(cache)
        return self._slot_update(cache, k_new, v_new, aux)

    def prefill_import(self, arch, cfg, k, v, positions, retained, alpha_bin,
                       *, max_len, layer_window=None, dtype=None):
        a = arch.attn
        dtype = dtype or jnp.dtype(arch.dtype)
        if layer_window is not None:
            raise NotImplementedError("vanilla: no local-window import path")
        b, h, t, d = k.shape
        cache = VanillaCache.init(b, a.num_kv_heads, max_len, a.head_dim,
                                  dtype, block_p=cfg.block_p, paged=cfg.paged,
                                  pool_blocks=cfg.pool_blocks)
        return cache.append(k, v)


@register_policy("window")
class WindowPolicy(_SlotRingMixin, KVPolicy):
    """StreamingLLM-style sliding window via ring-buffer overflow recycling."""

    def init_cache(self, arch, batch, max_len, cfg, *, layer_window, dtype):
        a = arch.attn
        budget = _budget_tokens(cfg, max_len)
        return SlotDMSCache.init(batch, a.num_kv_heads, budget + 1, a.head_dim,
                                 max(arch.dms.window, 1), dtype,
                                 dms_active=False, block_p=cfg.block_p,
                                 paged=cfg.paged, pool_blocks=cfg.pool_blocks)

    def decode_update(self, cache, q, k_new, v_new, aux):
        return self._slot_update(cache, k_new, v_new, aux)


@register_policy("dms")
class DMSPolicy(_SlotRingMixin, KVPolicy):
    """The paper's policy: slot-compacted arena, delayed eviction (§3.3).

    In a sliding-window layer the arena is provisioned for the window, and
    the cache frees each position as it leaves the window (``attn_window``):
    a token leaves at its DMS eviction or at the window's edge, whichever
    comes first, and its slot returns to the free ring."""

    alpha_mode = "dms"

    def init_cache(self, arch, batch, max_len, cfg, *, layer_window, dtype):
        a = arch.attn
        eff_len = (min(max_len, layer_window + 1) if layer_window is not None
                   else max_len)
        slots = SlotDMSCache.provision_slots(eff_len, cfg.cr, arch.dms.window)
        return SlotDMSCache.init(batch, a.num_kv_heads, min(slots, eff_len + 1),
                                 a.head_dim, arch.dms.window, dtype,
                                 block_p=cfg.block_p, paged=cfg.paged,
                                 pool_blocks=cfg.pool_blocks,
                                 attn_window=layer_window or 0)

    def decode_update(self, cache, q, k_new, v_new, aux):
        return self._slot_update(cache, k_new, v_new, aux)

    def prefill_import(self, arch, cfg, k, v, positions, retained, alpha_bin,
                       *, max_len, layer_window=None, dtype=None):
        eff_len = (min(max_len, layer_window + 1) if layer_window is not None
                   else max_len)
        slots = SlotDMSCache.provision_slots(eff_len, cfg.cr, arch.dms.window)
        if layer_window is not None:
            # what decode's window rule has freed by now: every position
            # at or below t - W, t = k.shape[2] the next position stored
            retained = retained & (positions >= k.shape[2] - layer_window)
        cache = SlotDMSCache.from_prefill(
            k, v, positions, retained, arch.dms.window,
            min(slots, eff_len + 1), alpha_bin=alpha_bin,
            block_p=cfg.block_p)
        if layer_window is not None:
            cache = dataclasses.replace(cache, attn_window=layer_window)
        if cfg.paged:
            cache = pack_dense(cache, cfg.pool_blocks)
        return cache


@register_policy("dms_masked")
class MaskedDMSPolicy(_SlotRingMixin, KVPolicy):
    """Full-length cache with a retained bitmap — the correctness oracle."""

    alpha_mode = "dms"

    def init_cache(self, arch, batch, max_len, cfg, *, layer_window, dtype):
        a = arch.attn
        return MaskedDMSCache.init(batch, a.num_kv_heads, max_len, a.head_dim,
                                   arch.dms.window, dtype,
                                   block_p=cfg.block_p, paged=cfg.paged,
                                   pool_blocks=cfg.pool_blocks)

    def decode_update(self, cache, q, k_new, v_new, aux):
        return self._slot_update(cache, k_new, v_new, aux)


class _WeightEvictPolicy(KVPolicy):
    """Shared insert→attend→evict shape for weight-driven policies."""

    def decode_update(self, cache, q, k_new, v_new, aux):
        cache = cache.insert(k_new, v_new, active=aux.get("active"))
        return cache, _attend_spec(cache, needs_weights=True)

    def post_attend(self, cache, weights, active=None):
        return cache.evict(weights, active=active)


@register_policy("tova")
class TOVAPolicy(_WeightEvictPolicy):
    def init_cache(self, arch, batch, max_len, cfg, *, layer_window, dtype):
        a = arch.attn
        budget = _budget_tokens(cfg, max_len)
        return TOVACache.init(batch, a.num_kv_heads, budget + 1, a.head_dim,
                              dtype, block_p=cfg.block_p, paged=cfg.paged,
                              pool_blocks=cfg.pool_blocks)


@register_policy("h2o")
class H2OPolicy(_WeightEvictPolicy):
    def init_cache(self, arch, batch, max_len, cfg, *, layer_window, dtype):
        a = arch.attn
        budget = _budget_tokens(cfg, max_len)
        return H2OCache.init(batch, a.num_kv_heads, budget + 1, a.head_dim,
                             max(budget // 2, 1), dtype, block_p=cfg.block_p,
                             paged=cfg.paged, pool_blocks=cfg.pool_blocks)


@register_policy("quest")
class QuestPolicy(KVPolicy):
    """Page-sparse reads over a full cache: the policy whose two budget axes
    diverge — ``reads_tokens`` shrinks, ``live_tokens`` does not."""

    def init_cache(self, arch, batch, max_len, cfg, *, layer_window, dtype):
        a = arch.attn
        ps = cfg.quest_page_size
        ml = ((max_len + ps - 1) // ps) * ps
        top = cfg.quest_top_pages or max(int(ml / cfg.cr) // ps, 1)
        return QuestCache.init(batch, a.num_kv_heads, ml, a.head_dim, ps, top,
                               dtype, paged=cfg.paged,
                               pool_blocks=cfg.pool_blocks)

    def decode_update(self, cache, q, k_new, v_new, aux):
        cfg = aux["attn_cfg"]
        b = q.shape[0]
        cache = cache.append(k_new, v_new, active=aux.get("active"))
        g = cfg.q_per_kv
        q_pool = q[:, 0].reshape(b, cfg.num_kv_heads, g, cfg.head_dim).mean(axis=2)
        pages = cache.select_pages(q_pool)
        tok_mask = cache.token_mask_from_pages(pages)
        # the top-k page selection IS a block table: with use_kernel the
        # flash-decode kernel fetches exactly the selected pages, turning
        # Quest's reads-tokens metering into real HBM traffic
        tbl, n = cache.block_table_from_pages(pages)
        if cache.pool is not None:
            kd, vd = block_pool.dense_kv(cache.pool, cache.phys)
            return cache, AttendSpec(kd, vd, tok_mask, cache.positions(),
                                     block_tbl=tbl, block_n=n,
                                     block_p=cache.page_size,
                                     pool_k=cache.pool.k, pool_v=cache.pool.v,
                                     phys=cache.phys)
        return cache, AttendSpec(cache.k, cache.v, tok_mask, cache.positions(),
                                 block_tbl=tbl, block_n=n,
                                 block_p=cache.page_size)

    def metrics(self, cache):
        live = cache.retained_tokens().astype(jnp.float32).mean(axis=-1)
        reads = jnp.broadcast_to(cache.reads_per_step().astype(jnp.float32),
                                 live.shape)
        return {"live_tokens": live, "reads_tokens": reads,
                "peak_bytes": self.peak_bytes(cache)}

    def peak_bytes(self, cache):
        if cache.pool is not None:
            return (_nbytes(cache.pool.k) + _nbytes(cache.pool.v)
                    + _nbytes(cache.kmin) + _nbytes(cache.kmax))
        return (_nbytes(cache.k) + _nbytes(cache.v)
                + _nbytes(cache.kmin) + _nbytes(cache.kmax))


@register_policy("dmc")
class DMCPolicy(KVPolicy):
    """Dynamic Memory Compression: α=1 merges into the newest entry."""

    alpha_mode = "always"

    def init_cache(self, arch, batch, max_len, cfg, *, layer_window, dtype):
        a = arch.attn
        slots = int(max_len / cfg.cr) + 16
        return DMCCache.init(batch, a.num_kv_heads, slots, a.head_dim,
                             block_p=cfg.block_p, paged=cfg.paged,
                             pool_blocks=cfg.pool_blocks)

    def decode_update(self, cache, q, k_new, v_new, aux):
        cfg = aux["attn_cfg"]
        b = k_new.shape[0]
        alpha = aux.get("alpha_bin")
        if alpha is None:
            alpha = jnp.zeros((b, cfg.num_kv_heads), bool)
        cache = cache.step(k_new, v_new, alpha, active=aux.get("active"))
        # the fp32 accumulators are read where they lie (the arena, or the
        # pool's pages) at the query's precision, so nothing is cast here.
        # Merged entries carry their newest contribution's position, so
        # layer_map window layers mask DMC slots like every other policy
        # (a merged entry is "as recent as" its last absorbed token)
        return cache, _attend_spec(cache)


# autoload policies that live in their own modules (each registers itself on
# import — the same mechanism downstream plugins use)
from repro.core import keyformer as _keyformer  # noqa: E402,F401
