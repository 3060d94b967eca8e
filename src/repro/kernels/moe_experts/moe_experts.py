"""Pallas TPU kernel: the held experts' SwiGLU FFN over one step's routed
(token, expert) pairs.

At decode a step holds one token per lane, and each token routes ``k`` of
``E`` experts, so a held expert sees a handful of tokens while its three
matrices are megabytes: the kernel is bound by streaming the weights.  It
therefore reads each held expert's weights once a call, and only for the
experts some pair was routed to, and touches no row it does not compute —
no per-lane capacity, no padded dispatch buffer in HBM.

Inputs (``ops.group_pairs`` builds the pair list):

* ``x`` (N, D) float32: the step's token rows, resident in VMEM for the
  whole call (N is the lane count at decode);
* ``w_gate``, ``w_up`` (L, E_h, D, F), ``w_down`` (L, E_h, F, D): the
  held experts' weights of L stacked layers, of which layer ``layer`` is
  streamed in ``(1, D, tile_f)`` / ``(1, tile_f, D)`` blocks, read in place
  from the stack: handed a per-layer slice, XLA makes a copy of all E_h
  experts' matrices before the call (it staged gate and up in VMEM), and
  the kernel would stream only what is left;
* scalar-prefetched: ``group_off`` (E_h + 1,) — pair slots
  ``[group_off[e], group_off[e + 1])`` belong to held expert ``e`` —,
  ``pair_tok`` (P,) the token row of each pair slot, ``pair_w`` (P,) its
  combine weight, ``fetch`` (E_h,) the expert whose weights grid step
  ``e`` streams (``e`` itself, or for an expert with no pairs the one
  streamed last, so its step moves no bytes), and ``layer`` (1,).

Grid ``(E_h, F // tile_f)``.  At an expert's first F tile its pair rows are
gathered from ``x`` into a VMEM tile; each F tile adds
``(silu(x Wg) * (x Wu)) Wd`` over its columns into a float32 accumulator,
in chunks of ``ROWS`` rows, for as many chunks as the expert has pairs; at
the last F tile each pair's row, times its weight, is added into its
token's row of the output ``y`` (N, D) float32, which stays resident and is
written back once.  ``y[n] = sum over pairs (n, e) of w * FFN_e(x[n])``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 32          # pair rows per matmul chunk (f32 sublane multiple)


class ExpertsConfig(NamedTuple):
    tile_f: int
    interpret: bool


def _kernel(off_ref, tok_ref, w_ref, fetch_ref, layer_ref, x_ref, wg_ref,
            wu_ref, wd_ref, y_ref, xg, acc):
    del fetch_ref, layer_ref
    e, j = pl.program_id(0), pl.program_id(1)
    last_j = pl.num_programs(1) - 1
    start = off_ref[e]
    count = off_ref[e + 1] - start
    n_chunks = (count + ROWS - 1) // ROWS

    @pl.when((e == 0) & (j == 0))
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)
        # rows past an expert's count are computed and never added: keep
        # them finite
        xg[...] = jnp.zeros_like(xg)

    @pl.when(j == 0)
    def _gather():
        def row(i, c):
            t = tok_ref[start + i]
            xg[pl.ds(i, 1), :] = x_ref[pl.ds(t, 1), :]
            return c
        jax.lax.fori_loop(0, count, row, 0)

    def chunk(c, carry):
        r0 = pl.multiple_of(c * ROWS, ROWS)
        xb = xg[pl.ds(r0, ROWS), :].astype(wg_ref.dtype)
        g = jnp.dot(xb, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(xb, wu_ref[0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(wd_ref.dtype)
        part = jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)
        prev = acc[pl.ds(r0, ROWS), :]
        acc[pl.ds(r0, ROWS), :] = jnp.where(j == 0, 0.0, prev) + part
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk, 0)

    @pl.when(j == last_j)
    def _combine():
        def row(i, c):
            t = tok_ref[start + i]
            y_ref[pl.ds(t, 1), :] += w_ref[start + i] * acc[pl.ds(i, 1), :]
            return c
        jax.lax.fori_loop(0, count, row, 0)


def vmem_bytes(n: int, d: int, tile_f: int, w_itemsize: int) -> int:
    """VMEM the call needs: double-buffered x, y and weight blocks, and the
    gathered-row and accumulator tiles."""
    rows = -(-n // ROWS) * ROWS
    return (2 * n * d * 4 * 2                  # x, y (f32), two buffers each
            + 2 * 3 * d * tile_f * w_itemsize  # gate, up, down blocks
            + 2 * rows * d * 4)                # xg, acc


def experts_fwd(x, w_gate, w_up, w_down, group_off, pair_tok, pair_w, fetch,
                layer, cfg: ExpertsConfig):
    n, d = x.shape
    _, eh, _, f = w_gate.shape
    tf = cfg.tile_f
    if f % tf:
        raise ValueError(f"expert width {f} not a multiple of tile_f {tf}")
    nf = f // tf
    rows = -(-n // ROWS) * ROWS

    def tile_j(e, j, off):
        # an expert with no pairs keeps the block streamed last
        return jnp.where(off[e + 1] > off[e], j, nf - 1)

    def gate_up(e, j, off, tok, w, fetch, layer):
        return (layer[0], fetch[e], 0, tile_j(e, j, off))

    def down(e, j, off, tok, w, fetch, layer):
        return (layer[0], fetch[e], tile_j(e, j, off), 0)

    whole = lambda e, j, *_: (0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(eh, nf),
        in_specs=[
            pl.BlockSpec((n, d), whole),
            pl.BlockSpec((None, 1, d, tf), gate_up),
            pl.BlockSpec((None, 1, d, tf), gate_up),
            pl.BlockSpec((None, 1, tf, d), down),
        ],
        out_specs=pl.BlockSpec((n, d), whole),
        scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32),
                        pltpu.VMEM((rows, d), jnp.float32)],
    )
    limit = vmem_bytes(n, d, tf, jnp.dtype(w_gate.dtype).itemsize)
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, limit + (8 << 20))),
        interpret=cfg.interpret,
        name="moe_experts",
    )(group_off, pair_tok, pair_w, fetch, layer, x, w_gate, w_up, w_down)
