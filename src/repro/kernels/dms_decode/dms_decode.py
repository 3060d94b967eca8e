"""Pallas TPU kernel: block-table flash-decode over compacted KV arenas.

The production win of KV compression at decode time is **HBM read traffic**:
at CR× compression the kernel must move CR× fewer K/V bytes, not merely skip
CR× of the compute.  This kernel makes that structural via *block-table
indirection*: each (lane, kv-head) carries a **compacted table of live
block ids** (scalar-prefetched, maintained incrementally by the caches —
see ``repro.core.kv_cache.BlockTable`` and docs/kernels.md), and the kernel
copies exactly the blocks its table lists, so a block with zero live slots
is **never DMA'd into VMEM**.  Slot-level holes *inside* a live block are
masked via the ``valid`` bitmap (kept in its stored dtype; any integer/bool
dtype works — the kernel only tests ``!= 0``).

Grid: ``(B·Hkv,)`` — one step per (lane, kv head).  A step reads its live
count ``n`` and walks the first ``n`` table entries in passes of
``C = max(1, 128 // block_p)`` entries (:func:`blocks_per_pass`), so one
pass fills a 128-slot row of scores.  Each pass gathers its blocks from HBM
with one async copy per entry, for K and for V, into a double-buffered VMEM
tile of ``C·block_p`` slots — the next pass's copies fly while this one
computes — and then runs one online-softmax update over the whole tile.
Entries past ``n`` take no grid step, no copy and no compute, so the
kernel's cost follows live blocks, not the table's width.  The G query
heads of the group ride along as rows of the (G, Dh) q tile so GQA reuses
each gathered KV tile across the whole group (the main arithmetic-intensity
lever at decode time).

The fixed arena arrives as the whole layer-stacked buffer the decode scan
carries, with the layer's index scalar-prefetched: the copies read that
layer's rows where they lie, so no per-layer slice of the arena is ever
copied out for the call.
"""
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


def blocks_per_pass(block_p: int) -> int:
    """Table entries one pass of the kernel gathers: enough blocks to fill a
    128-slot row of scores (the TPU's lane width), and at least one."""
    return max(1, 128 // block_p)


def _decode_kernel(tbl_ref, n_ref, layer_ref, q_ref, k_hbm, v_hbm,
                   valid_ref, o_ref, *rest, cfg: DecodeConfig):
    if cfg.weights_out:
        w_ref, mb_ref, mo_ref, lo_ref, kbuf, vbuf, sems = rest
    else:
        kbuf, vbuf, sems = rest
    bp, c = cfg.block_p, blocks_per_pass(cfg.block_p)
    h = pl.program_id(0)
    n = n_ref[h]
    n_pass = (n + c - 1) // c
    # fixed mode: the head's own arena row in its layer's part of the
    # layer-stacked arena; paged: the one shared page arena
    src_row = 0 if cfg.shared_kv else layer_ref[0] * pl.num_programs(0) + h

    def pass_copies(p, slot, op):
        """``op`` ("start" or "wait") the K and V copies of every live entry
        of pass ``p``, into buffer ``slot``: none for a pass past ``n``."""
        def one(j, carry):
            blk = tbl_ref[h, p * c + j]
            src, dst = pl.ds(blk * bp, bp), pl.ds(j * bp, bp)
            for kv, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                getattr(pltpu.make_async_copy(
                    hbm.at[src_row, src], buf.at[slot, dst],
                    sems.at[kv, slot]), op)()
            return carry

        jax.lax.fori_loop(0, jnp.clip(n - p * c, 0, c), one, 0)

    q = q_ref[0].astype(jnp.float32)                      # (G, Dh)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, c * bp), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (c * bp, 1), 0)

    def body(p, carry):
        m_prev, l_prev, acc_prev = carry
        slot = p % 2

        pass_copies(p + 1, 1 - slot, "start")
        pass_copies(p, slot, "wait")
        live_n = n - p * c            # entries of this pass that were copied
        rows = []
        for j in range(c):
            # `valid` is indexed by arena block (fixed) or by table entry
            # (paged: pre-gathered in table order); an entry past `n` reads
            # the last live one's row, masked below
            e = jnp.minimum(p * c + j, n - 1)
            r = valid_ref[0, pl.ds(e if cfg.shared_kv else tbl_ref[h, e], 1),
                          :]
            rows.append(r.astype(jnp.int32))
        live = (jnp.concatenate(rows, axis=-1) != 0) & (lane < live_n * bp)

        # K and V are read at no more than the query's precision (a wider
        # arena, DMC's float32 accumulators, rounds as it is read)
        k = kbuf[slot].astype(q_ref.dtype).astype(jnp.float32)  # (C·BP, Dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (cfg.orig_dh ** -0.5)
        if cfg.logit_cap is not None:
            s = cfg.logit_cap * jnp.tanh(s / cfg.logit_cap)
        s = jnp.where(live, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        w = jnp.where(live, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(w, axis=-1, keepdims=True)
        # a slot never copied holds stale or uninitialised VMEM: its weight
        # is 0, but 0 * NaN is not, so its V rows are selected away
        v = jnp.where(row < live_n * bp,
                      vbuf[slot].astype(q_ref.dtype).astype(jnp.float32), 0.0)
        acc_new = acc_prev * corr + jax.lax.dot_general(
            w, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if cfg.weights_out:
            # unnormalized weights relative to the running max after THIS
            # pass; the wrapper rescales by exp(m_blk - m_final) / l_final
            w_ref[0, p] = w                               # (G, C·BP)
            mb_ref[0, p] = m_new                          # (G, 1)
        return m_new, l_new, acc_new

    pass_copies(0, 0, "start")
    g, dh = q.shape
    m, l, acc = jax.lax.fori_loop(
        0, n_pass, body,
        (jnp.full((g, 1), NEG_INF, jnp.float32),
         jnp.zeros((g, 1), jnp.float32), jnp.zeros((g, dh), jnp.float32)))
    l_safe = jnp.where(l <= 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    if cfg.weights_out:
        mo_ref[0] = m                                     # (G, 1)
        lo_ref[0] = l                                     # (G, 1)


def smem_table_bytes(bh: int, nb_tbl: int) -> int:
    """SMEM bytes the two per-head scalar-prefetched operands take on the
    TPU: the (BHkv, NB_tbl) int32 table and the (BHkv,) live counts, with
    rows padded to 8 and the table's columns to 128 (what the TPU compiler
    allocates).  The one-int32 layer index rides in the reserve."""
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


def decode_fwd(q, k, v, valid, block_tbl, block_n, cfg: DecodeConfig,
               layer=0):
    """q: (BHkv, G, Dh); block_n: (BHkv,) int32 live counts.
    Returns (BHkv, G, Dh) — or, with ``cfg.weights_out``, the tuple
    ``(out, w_blk, m_blk, m_out, l_out)`` where ``w_blk`` is
    (BHkv, NB_tbl, G, block_p) per-block unnormalized post-softmax weights
    (``exp(s - m_blk)``, table order), ``m_blk`` (BHkv, NB_tbl, G) the
    running max when each block was processed, and ``m_out``/``l_out``
    (BHkv, G) the final flash statistics.  The normalized weight of a slot
    in table row ``i`` is ``w_blk[i] * exp(m_blk[i] - m_out) / l_out`` —
    a per-(row, g) scalar rescale the wrapper applies host-side, writing
    weight bytes ∝ table width (never arena capacity).  Rows at or past a
    head's ``n`` hold garbage; the wrapper masks them.

    Fixed-arena mode: k/v (L·BHkv, P, Dh) with P a block_p multiple — the
    layer-stacked arena the decode scan carries, of which layer ``layer``
    (an int32 scalar, prefetched) is read in place: head ``h`` reads HBM
    row ``layer·BHkv + h`` (L = 1, ``layer`` 0 for a single arena); valid
    (BHkv, P) in its stored dtype (bool/int — only ``!= 0`` is used);
    block_tbl (BHkv, NB_tbl) int32 compacted live block ids into the head's
    own arena.

    ``cfg.shared_kv`` (paged) mode: k/v are the ONE global page pool
    (1, NPOOL*block_p, Dh) shared by every (lane, kv head); block_tbl
    entries are *pool page ids* (the cache's logical table translated
    through its page map) and ``valid`` arrives pre-gathered into table
    order (BHkv, NB_tbl*block_p).

    Either way only blocks listed in the table are fetched: HBM traffic per
    head is ``n * block_p * Dh * (itemsize_k + itemsize_v)`` regardless of
    arena/pool capacity."""
    bh, g, dh = q.shape
    nb_tbl = block_tbl.shape[1]
    bp = cfg.block_p
    c = blocks_per_pass(bp)
    n_pass = -(-nb_tbl // c)
    check_smem(bh, nb_tbl)
    # TPU block rule: each block's last two dims must be (8, 128)-divisible
    # or span the array's own last two dims.  So each head's `valid` rides
    # whole as (1, NB, block_p) — one row per arena block (fixed) or table
    # entry (paged) — and the per-row stats as (..., G, 1).  Metadata-only
    # reshapes, here and on return.
    valid = valid.reshape(bh, -1, bp)
    head = lambda h, tbl, n, layer: (h, 0, 0)
    hbm = pl.BlockSpec(memory_space=pl.ANY)

    out_specs = pl.BlockSpec((1, g, dh), head)
    out_shape = jax.ShapeDtypeStruct((bh, g, dh), q.dtype)
    if cfg.weights_out:
        # one row of weights per pass, written whole with the head's output
        passes = lambda h, tbl, n, layer: (h, 0, 0, 0)
        out_specs = [
            out_specs,
            pl.BlockSpec((1, n_pass, g, c * bp), passes),
            pl.BlockSpec((1, n_pass, g, 1), passes),
            pl.BlockSpec((1, g, 1), head),
            pl.BlockSpec((1, g, 1), head),
        ]
        out_shape = [
            out_shape,
            jax.ShapeDtypeStruct((bh, n_pass, g, c * bp), jnp.float32),
            jax.ShapeDtypeStruct((bh, n_pass, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, g, 1), jnp.float32),
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bh,),
        in_specs=[
            pl.BlockSpec((1, g, dh), head),
            hbm,
            hbm,
            pl.BlockSpec((1, valid.shape[1], bp), head),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, c * bp, dh), k.dtype),
            pltpu.VMEM((2, c * bp, dh), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    res = pl.pallas_call(
        functools.partial(_decode_kernel, cfg=cfg),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=cfg.interpret,
        name="dms_decode",
    )(block_tbl, block_n, jnp.asarray(layer, jnp.int32).reshape(1), q, k, v,
      valid)
    if not cfg.weights_out:
        return res
    out, w_pass, m_pass, m_out, l_out = res
    # pass rows -> table rows: slot j·BP + t of pass p is entry p·C + j
    w_blk = w_pass.reshape(bh, n_pass, g, c, bp).transpose(0, 1, 3, 2, 4)
    w_blk = w_blk.reshape(bh, n_pass * c, g, bp)[:, :nb_tbl]
    m_blk = jnp.repeat(m_pass[..., 0], c, axis=1)[:, :nb_tbl]
    return (out, w_blk, m_blk, m_out[..., 0], l_out[..., 0])
