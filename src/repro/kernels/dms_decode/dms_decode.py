"""Pallas TPU kernel: block-table flash-decode over compacted KV arenas.

The production win of KV compression at decode time is **HBM read traffic**:
at CR× compression the kernel must move CR× fewer K/V bytes, not merely skip
CR× of the compute.  This kernel makes that structural via *block-table
indirection*: the grid runs over a per-(lane, kv-head) **compacted table of
live block ids** (scalar-prefetched, maintained incrementally by the caches
— see ``repro.core.kv_cache.BlockTable`` and docs/kernels.md), and the K/V
``BlockSpec`` index maps read the table, so a block with zero live slots is
**never DMA'd into VMEM**.  Iterations past a head's live count ``n`` clamp
the index map to the last live block — Pallas's pipeline skips the copy when
the block index does not change — and ``@pl.when`` skips their compute, so
the tail costs neither bandwidth nor FLOPs.  Slot-level holes *inside* a
live block are masked via the ``valid`` bitmap (kept in its stored dtype;
any integer/bool dtype works — the kernel only tests ``!= 0``).

Grid: ``(B·Hkv, NB_tbl)`` — one pass over (at most) the table width per kv
head; the G query heads of the group ride along as rows of the (G, Dh) q
tile so GQA reuses each streamed KV block across the whole group (the main
arithmetic-intensity lever at decode time).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Scalar memory of one TPU v5e core; the scalar-prefetched block table lives
# there.  The reserve is a margin for the scalars the compiler places there
# itself.
SMEM_BYTES = 1 << 20
SMEM_RESERVE = 4 << 10


class DecodeConfig(NamedTuple):
    orig_dh: int
    g: int                      # query heads per kv head
    block_p: int
    logit_cap: Optional[float]
    interpret: bool
    shared_kv: bool = False     # paged mode: k/v are ONE shared page arena
                                # (1, NPOOL*block_p, Dh); table entries are
                                # pool page ids and `valid` rides pre-gathered
                                # in table order (bh, NB_tbl*block_p)
    weights_out: bool = False   # also emit per-block unnormalized post-softmax
                                # weights (table order) + per-block running max
                                # + final per-head (m, l) — the wrapper
                                # renormalizes host-side (see ops.py)


def _decode_kernel(tbl_ref, n_ref, q_ref, k_ref, v_ref, valid_ref,
                   o_ref, *rest, cfg: DecodeConfig):
    if cfg.weights_out:
        w_ref, mb_ref, mo_ref, lo_ref, acc_ref, m_ref, l_ref = rest
    else:
        acc_ref, m_ref, l_ref = rest
    h, i = pl.program_id(0), pl.program_id(1)
    ni = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(i < n_ref[h])
    def _body():
        q = q_ref[0].astype(jnp.float32)                  # (G, Dh)
        k = k_ref[0].astype(jnp.float32)                  # (BP, Dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (cfg.orig_dh ** -0.5)
        if cfg.logit_cap is not None:
            s = cfg.logit_cap * jnp.tanh(s / cfg.logit_cap)
        live = valid_ref[0, 0] != 0                       # (1, BP)
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(live, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        if cfg.weights_out:
            # unnormalized weights relative to the running max at THIS block;
            # the wrapper rescales by exp(m_blk - m_final) / l_final
            w_ref[0, 0] = p                               # (G, BP)
            mb_ref[0, 0] = m_new                          # (G, 1)

    @pl.when(i == ni - 1)
    def _finish():
        l = l_ref[...]
        l_safe = jnp.where(l <= 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        if cfg.weights_out:
            mo_ref[0] = m_ref[...]                        # (G, 1)
            lo_ref[0] = l_ref[...]                        # (G, 1)


def _live_i(h, i, n_ref):
    """Grid step ``i`` clamped to the last live table entry — a repeated
    index means the pipeline issues NO new DMA for the dead tail (and
    ``@pl.when`` skips its compute)."""
    return jnp.minimum(i, jnp.maximum(n_ref[h] - 1, 0))


def _live_block(h, i, tbl_ref, n_ref):
    """The arena block this grid step streams: table entry ``i`` (clamped —
    see :func:`_live_i`).  In fixed-arena mode the entry indexes the head's
    own arena; in ``shared_kv`` (paged) mode it is a pool page id into the
    one shared arena."""
    return tbl_ref[h, _live_i(h, i, n_ref)]


def smem_table_bytes(bh: int, nb_tbl: int) -> int:
    """SMEM bytes the two scalar-prefetched operands take on the TPU: the
    (BHkv, NB_tbl) int32 table and the (BHkv,) live counts, with rows padded
    to 8 and the table's columns to 128 (what the TPU compiler allocates)."""
    rows = -(-bh // 8) * 8
    return rows * (-(-nb_tbl // 128) * 128) * 4 + rows * 4


def check_smem(bh: int, nb_tbl: int) -> None:
    """Refuse a block table the chip's scalar memory cannot hold, with its
    size, instead of the compiler's opaque out-of-SMEM error."""
    need = smem_table_bytes(bh, nb_tbl)
    if need > SMEM_BYTES - SMEM_RESERVE:
        raise ValueError(
            f"dms_decode block table ({bh} lane-heads x {nb_tbl} blocks, "
            f"int32) needs {need} bytes of SMEM; the TPU has {SMEM_BYTES} "
            f"({SMEM_RESERVE} kept for the compiler). Use a larger block_p "
            f"or fewer lanes / a shorter arena.")


def decode_fwd(q, k, v, valid, block_tbl, block_n, cfg: DecodeConfig):
    """q: (BHkv, G, Dh); block_n: (BHkv,) int32 live counts.
    Returns (BHkv, G, Dh) — or, with ``cfg.weights_out``, the tuple
    ``(out, w_blk, m_blk, m_out, l_out)`` where ``w_blk`` is
    (BHkv, NB_tbl, G, block_p) per-block unnormalized post-softmax weights
    (``exp(s - m_blk)``, table order), ``m_blk`` (BHkv, NB_tbl, G) the
    running max when each block was processed, and ``m_out``/``l_out``
    (BHkv, G) the final flash statistics.  The normalized weight of a slot
    in table row ``i`` is ``w_blk[i] * exp(m_blk[i] - m_out) / l_out`` —
    a per-(row, g) scalar rescale the wrapper applies host-side, writing
    weight bytes ∝ table width (never arena capacity).

    Fixed-arena mode: k/v (BHkv, P, Dh) with P a block_p multiple; valid
    (BHkv, P) in its stored dtype (bool/int — only ``!= 0`` is used);
    block_tbl (BHkv, NB_tbl) int32 compacted live block ids into the head's
    own arena.

    ``cfg.shared_kv`` (paged) mode: k/v are the ONE global page pool
    (1, NPOOL*block_p, Dh) shared by every (lane, kv head); block_tbl
    entries are *pool page ids* (the cache's logical table translated
    through its page map) and ``valid`` arrives pre-gathered into table
    order (BHkv, NB_tbl*block_p) so its index map needs no indirection.

    Either way only blocks listed in the table are fetched: HBM traffic per
    head is ``n * block_p * Dh * (itemsize_k + itemsize_v)`` regardless of
    arena/pool capacity."""
    bh, g, dh = q.shape
    nb_tbl = block_tbl.shape[1]
    bp = cfg.block_p
    check_smem(bh, nb_tbl)
    # TPU block rule: each block's last two dims must be (8, 128)-divisible
    # or span the array's own last two dims.  So `valid` rides as
    # (BHkv, NB, 1, block_p) and the per-row stats as (..., G, 1) — trailing
    # dims a block covers whole.  Metadata-only reshapes, here and on return.
    valid = valid.reshape(bh, -1, 1, bp)

    if cfg.shared_kv:
        # one shared arena: the leading axis is a singleton, the table entry
        # IS the page id; `valid` is table-ordered so it indexes by (h, i)
        kv_map = lambda h, i, tbl, n: (0, _live_block(h, i, tbl, n), 0)
        val_map = lambda h, i, tbl, n: (h, _live_i(h, i, n), 0, 0)
    else:
        kv_map = lambda h, i, tbl, n: (h, _live_block(h, i, tbl, n), 0)
        val_map = lambda h, i, tbl, n: (h, _live_block(h, i, tbl, n), 0, 0)

    out_specs = pl.BlockSpec((1, g, dh), lambda h, i, tbl, n: (h, 0, 0))
    out_shape = jax.ShapeDtypeStruct((bh, g, dh), q.dtype)
    if cfg.weights_out:
        # per-block outputs revisit the same (clamped) table row on the dead
        # tail — like the K/V inputs, a repeated index means no new copy; the
        # wrapper masks rows ≥ n so tail garbage never escapes.
        blk_map = lambda h, i, tbl, n: (h, _live_i(h, i, n), 0, 0)
        stat = lambda h, i, tbl, n: (h, 0, 0)
        out_specs = [
            out_specs,
            pl.BlockSpec((1, 1, g, bp), blk_map),
            pl.BlockSpec((1, 1, g, 1), blk_map),
            pl.BlockSpec((1, g, 1), stat),
            pl.BlockSpec((1, g, 1), stat),
        ]
        out_shape = [
            out_shape,
            jax.ShapeDtypeStruct((bh, nb_tbl, g, bp), jnp.float32),
            jax.ShapeDtypeStruct((bh, nb_tbl, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, g, 1), jnp.float32),
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nb_tbl),
        in_specs=[
            pl.BlockSpec((1, g, dh), lambda h, i, tbl, n: (h, 0, 0)),
            pl.BlockSpec((1, bp, dh), kv_map),
            pl.BlockSpec((1, bp, dh), kv_map),
            pl.BlockSpec((1, 1, 1, bp), val_map),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((g, dh), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    res = pl.pallas_call(
        functools.partial(_decode_kernel, cfg=cfg),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=cfg.interpret,
        name="dms_decode",
    )(block_tbl, block_n, q, k, v, valid)
    if not cfg.weights_out:
        return res
    out, w_blk, m_blk, m_out, l_out = res
    return (out, w_blk, m_blk[..., 0], m_out[..., 0], l_out[..., 0])
