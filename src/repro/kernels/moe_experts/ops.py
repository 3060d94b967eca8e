"""The held experts' part of an MoE layer over one step's routed pairs.

:func:`group_pairs` turns a step's top-k routing over all ``E`` experts into
the pair list of the experts this chip holds — slots sorted by held expert,
with group offsets, and nothing for the picks of experts held elsewhere —
and :func:`moe_experts` runs the held experts' SwiGLU FFN over it: the
Pallas kernel (``moe_experts.py``; interpret mode on the CPU) or, with
``use_kernel=False``, its jnp oracle (``ref.py``).  Both return the
float32 ``(N, D)`` sum of each token's held experts' outputs, each times
its routing weight.  docs/kernels.md states the contract.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dms_decode import ops as decode_ops
from repro.kernels.moe_experts.moe_experts import ExpertsConfig, experts_fwd
from repro.kernels.moe_experts.ref import moe_experts_ref


def group_pairs(top_e: jnp.ndarray, top_p: jnp.ndarray,
                held: Sequence[int], num_experts: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``top_e`` / ``top_p`` (N, k): each token's picks and weights over all
    ``num_experts``.  Returns ``group_off`` (E_h + 1,) int32, ``pair_tok``
    (P,) int32 and ``pair_w`` (P,) float32, P = N · min(k, E_h) (the most
    pairs the held experts can get): the pairs routed to held expert
    ``held[e]`` fill slots ``[group_off[e], group_off[e + 1])`` in token
    order; slots from ``group_off[E_h]`` on are empty (token 0, weight 0).
    No sort: a pair's slot is its rank among its expert's pairs (a running
    count) plus its group's offset."""
    n, k = top_e.shape
    eh = len(held)
    lut = np.full((num_experts,), eh, np.int32)
    lut[np.asarray(held, np.int64)] = np.arange(eh, dtype=np.int32)
    local = jnp.asarray(lut)[top_e].reshape(-1)                  # (N·k,)
    seen = jnp.cumsum(jax.nn.one_hot(local, eh + 1, dtype=jnp.int32), axis=0)
    counts = seen[-1, :eh]
    group_off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                 jnp.cumsum(counts).astype(jnp.int32)])
    rank = jnp.take_along_axis(seen, local[:, None], axis=1)[:, 0] - 1
    p = n * min(k, eh)
    dest = jnp.where(local < eh, group_off[jnp.minimum(local, eh - 1)] + rank,
                     p)
    tok = jnp.arange(n * k, dtype=jnp.int32) // k
    pair_tok = jnp.zeros((p,), jnp.int32).at[dest].set(tok, mode="drop")
    pair_w = jnp.zeros((p,), jnp.float32).at[dest].set(
        top_p.reshape(-1).astype(jnp.float32), mode="drop")
    return group_off, pair_tok, pair_w


def fetch_order(group_off: jnp.ndarray) -> jnp.ndarray:
    """(E_h,) the expert whose weights grid step ``e`` streams: ``e`` where
    it has pairs, else the last expert with pairs before it (the first one
    after it for leading empty experts), so an empty expert's step reuses
    the weights already in VMEM and moves no bytes."""
    eh = group_off.shape[0] - 1
    has = group_off[1:] > group_off[:-1]
    idx = jnp.where(has, jnp.arange(eh, dtype=jnp.int32), -1)
    last = jax.lax.cummax(idx, axis=0)
    first = jnp.argmax(has).astype(jnp.int32)
    return jnp.where(last < 0, first, last).astype(jnp.int32)


def moe_experts(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
                w_down: jnp.ndarray, group_off: jnp.ndarray,
                pair_tok: jnp.ndarray, pair_w: jnp.ndarray, *,
                layer: Optional[jnp.ndarray] = None,
                use_kernel: bool = True, tile_f: Optional[int] = None,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """``x`` (N, D); ``w_gate`` / ``w_up`` (E_h, D, F); ``w_down``
    (E_h, F, D), or, with ``layer`` (a scalar int), each with a leading
    axis of stacked layers, of which the kernel reads layer ``layer`` in
    place; the pair list of :func:`group_pairs`.  Returns (N, D) float32.
    ``tile_f`` (default F: each expert's matrices in one block) is the F
    columns a kernel grid step streams."""
    ws = (w_gate, w_up, w_down)
    if layer is None:
        ws, layer = tuple(w[None] for w in ws), 0
    if not use_kernel:
        return moe_experts_ref(x, *(w[layer] for w in ws), group_off,
                               pair_tok, pair_w)
    if interpret is None:
        # one platform switch for the decode path's kernels, resolved once
        # a process (a trace-time constant)
        interpret = decode_ops._default_interpret()
    cfg = ExpertsConfig(tile_f=tile_f or w_gate.shape[-1],
                        interpret=bool(interpret))
    return experts_fwd(x.astype(jnp.float32), *ws, group_off, pair_tok,
                       pair_w, fetch_order(group_off),
                       jnp.reshape(layer, (1,)).astype(jnp.int32), cfg)
