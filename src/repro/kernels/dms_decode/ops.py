"""jit'd wrapper for the block-table flash-decode kernel (inference only).

Two call modes (docs/kernels.md):

* **Block-table mode** (``block_tbl``/``block_n``/``block_p`` given — what
  every registry policy's :class:`~repro.core.policy.AttendSpec` supplies):
  the arena is already allocated pre-padded to a ``block_p`` multiple in the
  kernel-native per-(lane, kv-head) layout, so this wrapper is **copy-free**
  — the (B, Hkv, …) → (B·Hkv, …) merges are metadata-only reshapes, there is
  no ``jnp.pad``, no ``valid`` dtype cast, and no full-arena liveness
  reduction on the step path.  HBM traffic ∝ live blocks.

  With ``pool_k``/``pool_v``/``phys`` also given (paged caches — see
  ``repro.core.block_pool``) the kernel streams the shared page arena
  directly: the logical table is translated through the page map (one
  (B, Hkv, NB_tbl) gather of int32 ids), ``valid`` is gathered into table
  order (bool rows — bytes, not Dh-wide), and the dense per-lane k/v views
  a paged AttendSpec carries for the reference path are never touched
  (dead code under jit).  Zero page bytes move on dispatch.
* **Legacy/dense mode** (no table — encoder-memory cross-attention, direct
  kernel tests on arbitrary shapes): a table covering every written block is
  derived from ``valid`` (one O(P) reduction) and the arena is padded to a
  block multiple.  Traffic ∝ arena capacity; fine for dense encoder memory,
  a pitfall for compacted caches (see docs/kernels.md — don't reintroduce).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.dms_decode.dms_decode import DecodeConfig, decode_fwd

DEFAULT_BLOCK_P = 128


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.lru_cache(maxsize=None)
def _default_interpret() -> bool:
    """Resolve the backend once per process (trace-time constant), not per
    decode call — ``jax.default_backend()`` walks the platform registry."""
    return jax.default_backend() == "cpu"


def kernel_by_default() -> bool:
    """Whether serving decodes through this kernel when the caller does not
    say: on the TPU yes (compiled), elsewhere the reference einsum."""
    return jax.default_backend() == "tpu"


def modeled_hbm_bytes(block_n, block_p: int, head_dim: int,
                      k_dtype, v_dtype) -> int:
    """K/V bytes the kernel fetches for one decode step: ``sum(n)`` live
    blocks × block bytes.  Exact by construction — the kernel copies
    precisely the first ``n`` table entries per (lane, kv head), one block of
    K and one of V each, and nothing past them.  The benchmark's
    traffic model (``benchmarks/decode_path.py``) asserts this scales with
    live tokens, not arena capacity."""
    per_slot = head_dim * (jnp.dtype(k_dtype).itemsize
                           + jnp.dtype(v_dtype).itemsize)
    return int(jnp.sum(block_n)) * block_p * per_slot


def dms_decode_attention(
    q: jnp.ndarray,       # (B, 1, Hq, Dh)
    k: jnp.ndarray,       # (B, Hkv, P, Dh)
    v: jnp.ndarray,
    valid: jnp.ndarray,   # (B, Hkv, P) bool (stored dtype — never cast here)
    *,
    block_tbl: Optional[jnp.ndarray] = None,   # (B, Hkv, NB) int32
    block_n: Optional[jnp.ndarray] = None,     # (B, Hkv) int32
    block_p: Optional[int] = None,
    logit_cap: Optional[float] = None,
    interpret: Optional[bool] = None,
    pool_k: Optional[jnp.ndarray] = None,      # (NPOOL, block_p, Dh) page arena
    pool_v: Optional[jnp.ndarray] = None,
    phys: Optional[jnp.ndarray] = None,        # (B, Hkv, NB) page map, -1 free
    need_weights: bool = False,
) -> jnp.ndarray:
    b, _, hq, dh = q.shape
    hkv, p = k.shape[1], k.shape[2]
    g = hq // hkv
    if interpret is None:
        interpret = _default_interpret()
    shared_kv = False

    if block_tbl is not None:
        # block-table fast path: zero full-arena copies — reshapes only
        if p % block_p:
            raise ValueError(
                f"arena extent {p} not a multiple of block_p {block_p}; "
                "caches must allocate pre-padded (KVPolicyConfig.block_p)")
        bp = block_p
        tblf = block_tbl.reshape(b * hkv, -1)
        nf = block_n.reshape(b * hkv)
        ltbl = tblf             # LOGICAL arena rows — weights scatter target
        p_arena = p
        if pool_k is not None:
            # paged: stream the shared page arena.  Translate logical block
            # ids -> pool page ids through the page map (the one-liner twin
            # of block_pool.translate_table, inlined so kernels don't import
            # core); stale tail entries may map to -1 — clamp, the kernel's
            # live-count guard never dereferences them.
            shared_kv = True
            npool, pool_bp = pool_k.shape[0], pool_k.shape[1]
            if pool_bp != bp:
                raise ValueError(
                    f"pool page size {pool_bp} != block_p {bp}")
            nb = phys.shape[-1]
            ptbl = jnp.take_along_axis(
                phys, jnp.clip(block_tbl, 0, nb - 1), axis=2)
            tblf = jnp.clip(ptbl, 0, npool - 1).reshape(b * hkv, -1)
            kf = pool_k.reshape(1, npool * bp, dh)
            vf = pool_v.reshape(1, npool * bp, dh)
            # valid rides pre-gathered into table order so the kernel reads
            # its rows by table entry, with no second indirection (bool
            # rows — cheap)
            valf = jnp.take_along_axis(
                valid.reshape(b, hkv, p // bp, bp),
                jnp.clip(block_tbl, 0, p // bp - 1)[..., None], axis=2,
            ).reshape(b * hkv, -1)
        else:
            kf, vf = k.reshape(b * hkv, p, dh), v.reshape(b * hkv, p, dh)
            valf = valid.reshape(b * hkv, p)
    else:
        # legacy/dense path: derive a written-prefix-of-blocks table from
        # `valid` (O(P) reduction + pad — NOT the policy step path)
        bp = min(block_p or DEFAULT_BLOCK_P, _round_up(p, 8))
        pp = _round_up(p, bp)
        kf = jnp.pad(k.reshape(b * hkv, p, dh), ((0, 0), (0, pp - p), (0, 0)))
        vf = jnp.pad(v.reshape(b * hkv, p, dh), ((0, 0), (0, pp - p), (0, 0)))
        valf = jnp.pad(valid.reshape(b * hkv, p), ((0, 0), (0, pp - p)))
        nb = pp // bp
        blk_live = jnp.any(valf.reshape(b * hkv, nb, bp) != 0, axis=-1)
        tblf = jnp.argsort(~blk_live, axis=-1, stable=True).astype(jnp.int32)
        nf = jnp.sum(blk_live, axis=-1).astype(jnp.int32)
        ltbl = tblf
        p_arena = pp

    qf = q[:, 0].reshape(b, hkv, g, dh).reshape(b * hkv, g, dh)
    cfg = DecodeConfig(orig_dh=dh, g=g, block_p=bp, logit_cap=logit_cap,
                       interpret=bool(interpret), shared_kv=shared_kv,
                       weights_out=need_weights)
    if not need_weights:
        out = decode_fwd(qf, kf, vf, valf, tblf, nf, cfg)
        return out.reshape(b, hkv, g, dh).reshape(b, 1, hq, dh)

    out, w_blk, m_blk, m_out, l_out = decode_fwd(qf, kf, vf, valf, tblf, nf,
                                                 cfg)
    # Renormalize per table row: each block's weights were emitted as
    # exp(s - m_blk) with m_blk the running max at that block; the true
    # softmax weight is exp(s - m_out) / l_out.  For live rows
    # m_blk <= m_out always, so the clamp is the identity there — it only
    # silences dead-tail/empty-head garbage (masked to zero below anyway)
    # from overflowing the exp.  Per-g rescale BEFORE the group sum: the
    # query heads of a group have distinct (m, l).
    nb_tbl = tblf.shape[1]
    l_safe = jnp.where(l_out <= 0.0, 1.0, l_out)                  # (BH, G)
    corr = jnp.exp(jnp.minimum(m_blk - m_out[:, None, :], 0.0)) \
        / l_safe[:, None, :]                                      # (BH, NB, G)
    w_tbl = jnp.sum(w_blk * corr[..., None], axis=2)              # (BH, NB, BP)
    row_live = jnp.arange(nb_tbl)[None, :] < nf[:, None]
    w_tbl = jnp.where(row_live[..., None], w_tbl, 0.0)
    # Scatter table rows back to LOGICAL arena rows.  Weight bytes written
    # ∝ table width; the zeros init is (B·Hkv, P) f32 — group-summed, not
    # Dh-wide, so it stays under the arena-traffic lint threshold.  Dead
    # rows route to the out-of-range dump index and are dropped, so a stale
    # duplicate table id can never clobber a live row.
    nb_arena = p_arena // bp
    safe_rows = jnp.where(row_live, jnp.clip(ltbl, 0, nb_arena - 1), nb_arena)
    w_arena = jnp.zeros((b * hkv, nb_arena, bp), jnp.float32)
    w_arena = w_arena.at[jnp.arange(b * hkv)[:, None], safe_rows].set(
        w_tbl, mode="drop")
    weights = w_arena.reshape(b, hkv, nb_arena * bp)[:, :, :p]
    return out.reshape(b, hkv, g, dh).reshape(b, 1, hq, dh), weights
