"""Unified model: decoder-only / hybrid / SSM / MoE / encoder-decoder LMs.

One code path covers every assigned architecture.  Layers are grouped into
*superblocks* (one repetition of ``arch.layer_pattern``); superblocks are
scanned with ``jax.lax.scan`` so HLO size and compile time stay bounded at
full depth (64-layer Mamba-2 compiles the same graph as a 2-layer one).

Params layout::

    {"embed": (V, D),
     "blocks": {"0": <stacked over superblocks>, "1": ...},   # per pattern pos
     "enc_blocks": {...},                                     # enc-dec only
     "final_norm": {...}, "lm_head": (D, V)?}

Caches for decode mirror the same structure: ``{"0": stacked-cache, ...}``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import kv_cache
from repro.core import policy as policy_lib
from repro.core.config import ArchConfig, KVPolicyConfig
from repro.models import attention as attn_lib
from repro.models import rglru as rglru_lib
from repro.models import ssd as ssd_lib
from repro.models.layers import (EXPERT_MATRICES, init_mlp, init_norm, mlp_apply,
                                 norm_apply, softcap)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_block(key, arch: ArchConfig, kind: str, cross: bool = False) -> dict:
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {}
    d = arch.d_model
    if kind in ("attn", "attn_local"):
        p["attn_norm"] = init_norm(d, arch.norm)
        p["attn"] = attn_lib.init_attention(ks[0], d, arch.attn)
        if arch.post_norm:
            p["attn_post_norm"] = init_norm(d, arch.norm)
        if cross:
            p["cross_norm"] = init_norm(d, arch.norm)
            p["cross"] = attn_lib.init_attention(ks[1], d, arch.attn)
        if arch.mlp is not None:
            p["mlp_norm"] = init_norm(d, arch.norm)
            p["mlp"] = init_mlp(ks[2], d, arch.mlp)
            if arch.post_norm:
                p["mlp_post_norm"] = init_norm(d, arch.norm)
    elif kind == "ssd":
        p["norm"] = init_norm(d, arch.norm)
        p["ssd"] = ssd_lib.init_ssd(ks[0], d, arch.ssm)
    elif kind == "rglru":
        p["rglru_norm"] = init_norm(d, arch.norm)
        p["rglru"] = rglru_lib.init_rglru(ks[0], d, arch.rglru)
        if arch.mlp is not None:
            p["mlp_norm"] = init_norm(d, arch.norm)
            p["mlp"] = init_mlp(ks[2], d, arch.mlp)
    else:
        raise ValueError(kind)
    return p


def _stack_layers(n: int, make) -> Any:
    """Stack the trees ``make(0) .. make(n-1)`` leaf by leaf, dropping each
    leaf's per-layer arrays once its stacked copy exists: the peak is one
    stacked leaf above the per-layer trees, not a second copy of them all
    (at qwen-r1-1.5b width on a 16 GB chip, the difference between ~9 and
    ~14.5 GB)."""
    flat = [jax.tree_util.tree_flatten(make(s)) for s in range(n)]
    treedef = flat[0][1]
    per_layer = [leaves for leaves, _ in flat]
    del flat
    stacked = []
    for j in range(len(per_layer[0])):
        stacked.append(jnp.stack([leaves[j] for leaves in per_layer]))
        for leaves in per_layer:
            leaves[j] = None
    return jax.tree_util.tree_unflatten(treedef, stacked)


def init_model(key, arch: ArchConfig) -> dict:
    ks = jax.random.split(key, 6)
    vp = arch.padded_vocab
    params: Dict[str, Any] = {
        "embed": jax.random.normal(ks[0], (vp, arch.d_model), jnp.float32) * 0.02,
        "final_norm": init_norm(arch.d_model, arch.norm),
    }
    if not arch.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            ks[1], (arch.d_model, vp), jnp.float32) * (arch.d_model ** -0.5)

    nsb = arch.num_superblocks
    blocks: Dict[str, Any] = {}
    for pi, kind in enumerate(arch.layer_pattern):
        layer_keys = jax.random.split(jax.random.fold_in(ks[2], pi), nsb)
        blocks[str(pi)] = _stack_layers(nsb, lambda s: _init_block(
            layer_keys[s], arch, kind, cross=arch.cross_attention))
    params["blocks"] = blocks

    if arch.encoder_layers:
        ne = arch.encoder_layers // arch.pattern_period
        enc_blocks: Dict[str, Any] = {}
        for pi, kind in enumerate(arch.layer_pattern):
            layer_keys = jax.random.split(jax.random.fold_in(ks[3], pi), ne)
            enc_blocks[str(pi)] = _stack_layers(
                ne, lambda s: _init_block(layer_keys[s], arch, kind))
        params["enc_blocks"] = enc_blocks
        params["enc_final_norm"] = init_norm(arch.d_model, arch.norm)
    return params


# ---------------------------------------------------------------------------
# block application (full sequence)
# ---------------------------------------------------------------------------


def _layer_window(arch: ArchConfig, kind: str) -> Optional[int]:
    if kind == "attn_local":
        return arch.attn.window
    return None


def _layer_attn(arch: ArchConfig, kind: str):
    """The attention config a layer of ``kind`` runs: YaRN applies to full
    layers only, windowed layers keep the plain RoPE."""
    if kind == "attn_local" and arch.attn.yarn is not None:
        return dataclasses.replace(arch.attn, yarn=None)
    return arch.attn


def _apply_block_full(
    p: dict, x: jnp.ndarray, arch: ArchConfig, kind: str, *,
    mode: str, rng, positions, neuron_scale, use_kernel, collect_kv,
    causal: bool, enc_out: Optional[jnp.ndarray], attn_impl=None,
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    aux: Dict[str, Any] = {}
    if kind in ("attn", "attn_local"):
        acfg = _layer_attn(arch, kind)
        if not causal:
            acfg = dataclasses.replace(acfg, causal=False)
        h = norm_apply(p["attn_norm"], x, arch.norm, arch.norm_eps)
        a_out, a_aux = attn_lib.full_attention(
            p["attn"], h, acfg, arch,
            layer_window=_layer_window(arch, kind),
            mode=mode, dms_rng=rng, positions=positions,
            neuron_scale=neuron_scale, use_kernel=use_kernel,
            attn_impl=attn_impl, collect_kv=collect_kv)
        if arch.post_norm:
            a_out = norm_apply(p["attn_post_norm"], a_out, arch.norm, arch.norm_eps)
        x = x + a_out
        aux.update(a_aux)
        if enc_out is not None and "cross" in p:
            h = norm_apply(p["cross_norm"], x, arch.norm, arch.norm_eps)
            dtype = jnp.dtype(arch.dtype)
            ek = (enc_out.astype(dtype) @ p["cross"]["wk"].astype(dtype)).reshape(
                enc_out.shape[0], enc_out.shape[1], acfg.num_kv_heads, acfg.head_dim)
            ev = (enc_out.astype(dtype) @ p["cross"]["wv"].astype(dtype)).reshape(
                enc_out.shape[0], enc_out.shape[1], acfg.num_kv_heads, acfg.head_dim)
            c_out, _ = attn_lib.full_attention(
                p["cross"], h, dataclasses.replace(acfg, causal=False, rope="none"),
                arch, mode="vanilla", positions=positions, kv_override=(ek, ev))
            x = x + c_out
        if arch.mlp is not None:
            h = norm_apply(p["mlp_norm"], x, arch.norm, arch.norm_eps)
            m_out, m_aux = mlp_apply(p["mlp"], h, arch.mlp, jnp.dtype(arch.dtype))
            if arch.post_norm:
                m_out = norm_apply(p["mlp_post_norm"], m_out, arch.norm, arch.norm_eps)
            x = x + m_out
            aux.update(m_aux)
    elif kind == "ssd":
        h = norm_apply(p["norm"], x, arch.norm, arch.norm_eps)
        s_out, _ = ssd_lib.ssd_forward(p["ssd"], h, arch)
        x = x + s_out
    elif kind == "rglru":
        h = norm_apply(p["rglru_norm"], x, arch.norm, arch.norm_eps)
        r_out, _ = rglru_lib.rglru_forward(p["rglru"], h, arch)
        x = x + r_out
        if arch.mlp is not None:
            h = norm_apply(p["mlp_norm"], x, arch.norm, arch.norm_eps)
            m_out, m_aux = mlp_apply(p["mlp"], h, arch.mlp, jnp.dtype(arch.dtype))
            x = x + m_out
            aux.update(m_aux)
    return x, aux


def _scan_blocks(blocks, x, arch: ArchConfig, *, mode, rng, positions,
                 neuron_scale, use_kernel, collect_kv, causal, enc_out,
                 num_sb: int, remat: bool, scan_layers: bool = True,
                 attn_impl=None):
    """Apply all superblocks; accumulate DMS/MoE stats; optionally emit KV.

    ``scan_layers=True`` uses ``lax.scan`` (bounded HLO size / compile time);
    ``False`` unrolls (exact per-layer cost analysis for the dry-run roofline —
    XLA's cost model counts while-loop bodies once)."""
    if rng is None:
        rng = jax.random.PRNGKey(0)
    sb_rngs = jax.random.split(rng, num_sb * arch.pattern_period).reshape(
        num_sb, arch.pattern_period, 2)

    def body(carry, xs):
        x, a_sum, a_cnt, moe_aux = carry
        blk, rngs = xs
        ys = {}
        for pi, kind in enumerate(arch.layer_pattern):
            x, aux = _apply_block_full(
                blk[str(pi)], x, arch, kind,
                mode=mode, rng=rngs[pi], positions=positions,
                neuron_scale=neuron_scale, use_kernel=use_kernel,
                attn_impl=attn_impl, collect_kv=collect_kv, causal=causal,
                enc_out=enc_out)
            a_sum = a_sum + aux.get("alpha_sum", 0.0)
            a_cnt = a_cnt + aux.get("alpha_count", 0.0)
            moe_aux = moe_aux + aux.get("moe_aux_loss", 0.0)
            if collect_kv:
                ys[str(pi)] = {k: aux[k] for k in ("k_rope", "v", "retained", "alpha_bin")
                               if k in aux}
        return (x, a_sum, a_cnt, moe_aux), ys

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    zero = jnp.zeros((), jnp.float32)
    if scan_layers:
        (x, a_sum, a_cnt, moe_aux), ys = jax.lax.scan(
            body, (x, zero, zero, zero), (blocks, sb_rngs))
    else:
        carry = (x, zero, zero, zero)
        ys_list = []
        for s in range(num_sb):
            blk_s = jax.tree_util.tree_map(lambda a: a[s], blocks)
            carry, y = body(carry, (blk_s, sb_rngs[s]))
            ys_list.append(y)
        (x, a_sum, a_cnt, moe_aux) = carry
        ys = (jax.tree_util.tree_map(lambda *zs: jnp.stack(zs), *ys_list)
              if collect_kv and ys_list and ys_list[0] else {})
    return x, {"alpha_sum": a_sum, "alpha_count": a_cnt, "moe_aux_loss": moe_aux,
               "layer_kv": ys if collect_kv else None}


# ---------------------------------------------------------------------------
# public forward
# ---------------------------------------------------------------------------


def embed_tokens(params, tokens, arch: ArchConfig,
                 frontend_embeds: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(arch.dtype))
    if arch.embedding_multiplier != 1.0:
        x = x * jnp.asarray(arch.embedding_multiplier, x.dtype)
    if frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
    return x


def lm_logits(params, x, arch: ArchConfig) -> jnp.ndarray:
    h = norm_apply(params["final_norm"], x, arch.norm, arch.norm_eps)
    dtype = jnp.dtype(arch.dtype)
    w = params["embed"].T if arch.tie_embeddings else params["lm_head"]
    logits = h.astype(dtype) @ w.astype(dtype)
    logits = softcap(logits.astype(jnp.float32), arch.logit_softcap)
    if arch.padded_vocab != arch.vocab_size:   # mask pad rows (see padded_vocab)
        live = jnp.arange(arch.padded_vocab) < arch.vocab_size
        logits = jnp.where(live, logits, -1e30)
    return logits


def encode(params, enc_embeds: jnp.ndarray, arch: ArchConfig, *,
           use_kernel: bool = False, scan_layers: bool = True,
           attn_impl=None) -> jnp.ndarray:
    """Encoder stack (bidirectional) over precomputed frontend embeddings."""
    ne = arch.encoder_layers // arch.pattern_period
    t = enc_embeds.shape[1]
    x, _ = _scan_blocks(
        params["enc_blocks"], enc_embeds.astype(jnp.dtype(arch.dtype)), arch,
        mode="vanilla", rng=None, positions=jnp.arange(t, dtype=jnp.int32),
        neuron_scale=0.0, use_kernel=use_kernel, collect_kv=False,
        causal=not arch.encoder_bidirectional, enc_out=None,
        num_sb=ne, remat=False, scan_layers=scan_layers, attn_impl=attn_impl)
    return norm_apply(params["enc_final_norm"], x, arch.norm, arch.norm_eps)


def model_forward(
    params: dict,
    tokens: jnp.ndarray,                       # (B, T_text) int32
    arch: ArchConfig,
    *,
    mode: str = "vanilla",                     # vanilla | dms_train | dms_eval | dms_phase1
    rng: Optional[jax.Array] = None,
    positions: Optional[jnp.ndarray] = None,
    neuron_scale: float = 0.0,
    use_kernel: bool = False,
    collect_kv: bool = False,
    remat: bool = False,
    scan_layers: bool = True,
    attn_impl: Optional[str] = None,
    frontend_embeds: Optional[jnp.ndarray] = None,   # (B, F, D) modality stub
    enc_embeds: Optional[jnp.ndarray] = None,        # (B, S_enc, D) enc-dec stub
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Full forward.  Returns (logits (B, T, V), aux)."""
    enc_out = None
    if arch.encoder_layers and enc_embeds is not None:
        enc_out = encode(params, enc_embeds, arch, use_kernel=use_kernel,
                         scan_layers=scan_layers, attn_impl=attn_impl)
    x = embed_tokens(params, tokens, arch, frontend_embeds)
    t = x.shape[1]
    if positions is None:
        positions = jnp.arange(t, dtype=jnp.int32)
    x, aux = _scan_blocks(
        params["blocks"], x, arch, mode=mode, rng=rng, positions=positions,
        neuron_scale=neuron_scale, use_kernel=use_kernel, collect_kv=collect_kv,
        causal=True, enc_out=enc_out, num_sb=arch.num_superblocks, remat=remat,
        scan_layers=scan_layers, attn_impl=attn_impl)
    logits = lm_logits(params, x, arch)
    if enc_out is not None:
        aux["enc_out"] = enc_out
    return logits, aux


# ---------------------------------------------------------------------------
# decode state
# ---------------------------------------------------------------------------


def _init_layer_cache(arch: ArchConfig, kind: str, batch: int, max_len: int,
                      policy: KVPolicyConfig, dtype):
    if kind == "ssd":
        return ssd_lib.init_ssd_state(batch, arch.d_model, arch.ssm)
    if kind == "rglru":
        return rglru_lib.init_rglru_state(batch, arch.d_model, arch.rglru)
    # attention layers: every policy comes from the KVPolicy registry — the
    # model never special-cases a cache class (see repro.core.policy)
    return policy_lib.init_policy_cache(
        arch, batch, max_len, policy, layer_kind=kind,
        layer_window=_layer_window(arch, kind), dtype=dtype)


def init_decode_state(arch: ArchConfig, batch: int, max_len: int,
                      policy: KVPolicyConfig, dtype=None) -> Dict[str, Any]:
    """Provision the full decode state: one cache per layer-pattern position,
    stacked over superblocks (lane axis at position 1 on every leaf).

    KV arenas come out of the registry pre-padded to ``policy.block_p``
    multiples in the flash-decode kernel's native layout, with each cache's
    live-block table (docs/kernels.md) riding as ordinary lane-leading state
    — so fork/gather/reclaim/snapshot below need no block-table-specific
    code, and the decode step path never pads or reshapes an arena."""
    dtype = dtype or jnp.dtype(arch.dtype)
    nsb = arch.num_superblocks
    state: Dict[str, Any] = {}
    for pi, kind in enumerate(arch.layer_pattern):
        one = _init_layer_cache(arch, kind, batch, max_len, policy, dtype)
        state[str(pi)] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (nsb,) + a.shape), one)
    return state


# -- lane lifecycle over whole decode states --------------------------------
#
# Decode-state leaves are stacked over superblocks (axis 0) with the lane
# (batch) axis at position 1.  PolicyCache nodes dispatch through their
# policy's fork/reclaim lifecycle hooks; raw recurrent states (SSD / RG-LRU)
# fork and reset generically.


def _is_policy_cache(x) -> bool:
    return isinstance(x, policy_lib.PolicyCache)


def fork_decode_state(state: Dict[str, Any], width: int) -> Dict[str, Any]:
    """Shared-prefill fork: clone every lane into ``width`` chains.

    Prefill a prompt once, fork the whole decode state into W hyper-scaling
    chains — forked chains carry bitwise-identical cache/recurrent state, so
    step-0 decode logits match W independent prefills at 1/W of the
    prefill-phase KV reads."""

    def f(node):
        if _is_policy_cache(node):
            pol = policy_lib.get_policy(node.policy)
            return dataclasses.replace(
                node, cache=pol.fork_cache(node.cache, width, axis=1))
        return jnp.repeat(node, width, axis=1)

    return jax.tree_util.tree_map(f, state, is_leaf=_is_policy_cache)


def reclaim_lanes(state: Dict[str, Any], reset_mask: jnp.ndarray,
                  fresh: Dict[str, Any]) -> Dict[str, Any]:
    """EOS reclamation: lanes where ``reset_mask`` (B,) is True return to the
    pristine ``fresh`` state (arena empty, free list full, position 0)."""

    def f(node, init):
        if _is_policy_cache(node):
            pol = policy_lib.get_policy(node.policy)
            return dataclasses.replace(
                node, cache=pol.reclaim_cache(node.cache, reset_mask,
                                              init.cache, axis=1))
        m = reset_mask.reshape((1, -1) + (1,) * (node.ndim - 2))
        return jnp.where(m, init, node)

    return jax.tree_util.tree_map(f, state, fresh, is_leaf=_is_policy_cache)


def export_lane_state(state: Dict[str, Any], lane) -> Dict[str, Any]:
    """Snapshot one lane's complete decode state (cross-request prefix cache).

    Returns a width-1-lane pytree of the same structure: PolicyCache nodes
    dispatch through :meth:`KVPolicy.export_prefix`, raw recurrent states
    (SSD / RG-LRU) slice generically — a hybrid model's prefix snapshot
    carries its recurrent state too.  ``lane`` may be a traced int32 scalar,
    so one jit covers every lane."""

    def f(node):
        if _is_policy_cache(node):
            pol = policy_lib.get_policy(node.policy)
            return dataclasses.replace(
                node, cache=pol.export_prefix(node.cache, lane, axis=1))
        return jax.lax.dynamic_slice_in_dim(node, lane, 1, axis=1)

    return jax.tree_util.tree_map(f, state, is_leaf=_is_policy_cache)


def import_lane_state(state: Dict[str, Any], snap: Dict[str, Any],
                      lane) -> Dict[str, Any]:
    """Restore an :func:`export_lane_state` snapshot into lane ``lane``.

    The lane must be pristine (reclaimed); after the import it sits exactly
    where the exporting request's prefill stood, so chunk-prefilling only the
    suffix is bitwise-equal to a cold full prefill."""

    def f(node, s):
        if _is_policy_cache(node):
            pol = policy_lib.get_policy(node.policy)
            return dataclasses.replace(
                node, cache=pol.import_prefix(node.cache, s.cache, lane,
                                              axis=1))
        return jax.lax.dynamic_update_slice_in_dim(
            node, s.astype(node.dtype), lane, axis=1)

    return jax.tree_util.tree_map(f, state, snap, is_leaf=_is_policy_cache)


def init_snapshot_slab(snap: Dict[str, Any], slots: int) -> Dict[str, Any]:
    """Pre-allocate a device slab holding ``slots`` lane snapshots.

    ``snap`` is an :func:`export_lane_state` exemplar (lane axis width 1,
    position 1 on every leaf); the slab is the same pytree with the lane
    axis widened to ``slots`` — pure storage for the prefix cache's hot
    tier, written/read by :func:`store_lane_snapshot` /
    :func:`fetch_lane_snapshot` without ever leaving the device."""

    def f(a):
        return jnp.zeros(a.shape[:1] + (int(slots),) + a.shape[2:], a.dtype)

    return jax.tree_util.tree_map(f, snap)


def store_lane_snapshot(slab: Dict[str, Any], snap: Dict[str, Any],
                        slot) -> Dict[str, Any]:
    """Write a width-1 snapshot into slab slot ``slot`` — the device-side
    half of a *deferred* export: the freshly exported device snapshot is
    copied device-to-device into the slab and only materialized to host if
    the hot tier later demotes it.  PolicyCache nodes dispatch through
    :meth:`KVPolicy.import_slab`; raw recurrent state updates generically.
    ``slot`` may be a traced int32 scalar, so one jit covers every slot."""

    def f(node, s):
        if _is_policy_cache(node):
            pol = policy_lib.get_policy(node.policy)
            return dataclasses.replace(
                node, cache=pol.import_slab(node.cache, s.cache, slot,
                                            axis=1))
        return jax.lax.dynamic_update_slice_in_dim(
            node, s.astype(node.dtype), slot, axis=1)

    return jax.tree_util.tree_map(f, slab, snap, is_leaf=_is_policy_cache)


def fetch_lane_snapshot(slab: Dict[str, Any], slot) -> Dict[str, Any]:
    """Read the snapshot in slab slot ``slot`` — the zero-copy hot-hit path:
    the returned device pytree feeds :func:`import_lane_state` directly, so
    a hot prefix hit moves no host↔device bytes at all (dispatches through
    :meth:`KVPolicy.export_slab`)."""

    def f(node):
        if _is_policy_cache(node):
            pol = policy_lib.get_policy(node.policy)
            return dataclasses.replace(
                node, cache=pol.export_slab(node.cache, slot, axis=1))
        return jax.lax.dynamic_slice_in_dim(node, slot, 1, axis=1)

    return jax.tree_util.tree_map(f, slab, is_leaf=_is_policy_cache)


def lane_state_signature(state: Dict[str, Any]) -> Tuple:
    """Hashable shape signature of one lane's snapshot of ``state``.

    Two decode states produce interchangeable prefix snapshots iff their
    signatures match (same tree structure, same per-leaf shapes with the lane
    axis collapsed, same dtypes) — the prefix cache keys its radix trees by
    this, so snapshots from a scheduler with a different ``max_len``, policy
    config, or arch are never imported into an incompatible arena."""
    leaves, treedef = jax.tree_util.tree_flatten(state)
    return (str(treedef),
            tuple((a.shape[:1] + (1,) + a.shape[2:], str(jnp.dtype(a.dtype)))
                  for a in leaves))


def gather_lanes(state: Dict[str, Any], src: jnp.ndarray) -> Dict[str, Any]:
    """Lane shuffle: new lane ``l`` takes old lane ``src[l]``'s full state.

    This is how the scheduler forks a prefilled lane into W free lanes inside
    a fixed-size batch (``src`` is the identity except forked targets).
    PolicyCache nodes dispatch through :meth:`KVPolicy.gather_cache` — the
    same override point as ``fork_cache`` for policies with non-lane state."""

    def f(node):
        if _is_policy_cache(node):
            pol = policy_lib.get_policy(node.policy)
            return dataclasses.replace(
                node, cache=pol.gather_cache(node.cache, src, axis=1))
        return jnp.take(node, src, axis=1)

    return jax.tree_util.tree_map(f, state, is_leaf=_is_policy_cache)


def decode_step(
    params: dict,
    token: jnp.ndarray,               # (B, 1) int32
    state: Dict[str, Any],
    arch: ArchConfig,
    pos_t: jnp.ndarray,               # scalar int32 OR per-lane (B,)
    *,
    use_kernel: bool = False,
    scan_layers: bool = True,
    enc_out: Optional[jnp.ndarray] = None,
    enc_valid: Optional[jnp.ndarray] = None,
    embed_override: Optional[jnp.ndarray] = None,
    active: Optional[jnp.ndarray] = None,   # (B,) bool — lane mask
) -> Tuple[jnp.ndarray, Dict[str, Any], Dict[str, Any]]:
    """One decode step.  Returns (logits (B, V), new_state, aux).

    Batch rows are independent *lanes*: ``pos_t`` may be per-lane and
    ``active`` masks lanes out of the step entirely — an inactive lane's
    cache/recurrent state is left untouched (the compute still runs, batched,
    but the state write is discarded) and it contributes zero to the
    ``reads_tokens`` budget axis.  This is what makes continuous batching
    honest: finished or idle lanes neither mutate state nor inflate meters.

    The fixed K/V arenas ride the layer scan's carry as the layer-stacked
    buffers they are stored as (:func:`_split_arenas`): each layer writes
    its token rows into its part in place, lane-masked, and the kernel
    reads the layer there.  So no step copies, stacks or selects a whole
    arena; the lane freeze below selects every other leaf.
    """
    x = (embed_override if embed_override is not None
         else embed_tokens(params, token, arch))
    impls = set()   # trace-time: attention implementations actually traced
    blocks, experts = _split_experts(params["blocks"], arch)

    def mlp(p, pi, h, li):
        mp, layer = p["mlp"], None
        if str(pi) in experts:
            mp, layer = dict(mp, **experts[str(pi)]), li
        with jax.named_scope("mlp"):
            return mlp_apply(mp, h, arch.mlp, jnp.dtype(arch.dtype),
                             use_kernel=use_kernel, layer=layer)

    def body(carry, xs):
        x_t, live, reads, pairs, arenas = carry
        blk, cache, li = xs
        new_caches, arenas = {}, dict(arenas)
        for pi, kind in enumerate(arch.layer_pattern):
            p = blk[str(pi)]
            if kind in ("attn", "attn_local"):
                h = norm_apply(p["attn_norm"], x_t, arch.norm, arch.norm_eps)
                c = cache[str(pi)]
                if str(pi) in arenas:     # read and write layer li in place
                    c = _with_kv(c, *(kv_cache.LayerArena(a, li)
                                      for a in arenas[str(pi)]))
                a_out, new_c, aux = attn_lib.decode_attention(
                    p["attn"], h, c, _layer_attn(arch, kind), arch,
                    layer_window=_layer_window(arch, kind), pos_t=pos_t,
                    use_kernel=use_kernel, active=active)
                if str(pi) in arenas:
                    new_c, arenas[str(pi)] = _take_arenas(new_c)
                impls.add(aux["attn_impl"])
                if arch.post_norm:
                    a_out = norm_apply(p["attn_post_norm"], a_out, arch.norm, arch.norm_eps)
                x_t = x_t + a_out
                live = live + aux["live_tokens"]
                reads = reads + aux["reads_tokens"]
                if enc_out is not None and "cross" in p:
                    h = norm_apply(p["cross_norm"], x_t, arch.norm, arch.norm_eps)
                    dtype = jnp.dtype(arch.dtype)
                    a = arch.attn
                    ek = (enc_out.astype(dtype) @ p["cross"]["wk"].astype(dtype)).reshape(
                        enc_out.shape[0], enc_out.shape[1], a.num_kv_heads, a.head_dim)
                    ev = (enc_out.astype(dtype) @ p["cross"]["wv"].astype(dtype)).reshape(
                        enc_out.shape[0], enc_out.shape[1], a.num_kv_heads, a.head_dim)
                    vmask = (enc_valid if enc_valid is not None else
                             jnp.ones(ek.shape[:2], bool))
                    c_out, _, _ = attn_lib.decode_attention(
                        p["cross"], h, None,
                        dataclasses.replace(a, causal=False, rope="none"), arch,
                        pos_t=pos_t,
                        cross_kv=(ek.transpose(0, 2, 1, 3), ev.transpose(0, 2, 1, 3),
                                  jnp.broadcast_to(vmask[:, None, :],
                                                   (ek.shape[0], a.num_kv_heads, ek.shape[1]))))
                    x_t = x_t + c_out
                if arch.mlp is not None:
                    h = norm_apply(p["mlp_norm"], x_t, arch.norm, arch.norm_eps)
                    m_out, m_aux = mlp(p, pi, h, li)
                    pairs = pairs + m_aux.get("moe_pairs", 0)
                    if arch.post_norm:
                        m_out = norm_apply(p["mlp_post_norm"], m_out, arch.norm, arch.norm_eps)
                    x_t = x_t + m_out
            elif kind == "ssd":
                h = norm_apply(p["norm"], x_t, arch.norm, arch.norm_eps)
                s_out, new_c = ssd_lib.ssd_decode_step(p["ssd"], h, cache[str(pi)], arch)
                x_t = x_t + s_out
            elif kind == "rglru":
                h = norm_apply(p["rglru_norm"], x_t, arch.norm, arch.norm_eps)
                r_out, new_c = rglru_lib.rglru_decode_step(p["rglru"], h, cache[str(pi)], arch)
                x_t = x_t + r_out
                if arch.mlp is not None:
                    h = norm_apply(p["mlp_norm"], x_t, arch.norm, arch.norm_eps)
                    m_out, m_aux = mlp(p, pi, h, li)
                    pairs = pairs + m_aux.get("moe_pairs", 0)
                    x_t = x_t + m_out
            new_caches[str(pi)] = new_c
        return (x_t, live, reads, pairs, arenas), new_caches

    b = x.shape[0]
    zero = jnp.zeros((b,), jnp.float32)
    rest, arenas = _split_arenas(state)
    carry0 = (x, zero, zero, jnp.zeros((b,), jnp.int32), arenas)
    nsb = arch.num_superblocks
    # each superblock's index: the layer the arenas and the expert layers'
    # weights are read at
    if scan_layers:
        (x, live, reads, pairs, arenas), new_rest = jax.lax.scan(
            body, carry0, (blocks, rest, jnp.arange(nsb)))
    else:
        carry = carry0
        outs = []
        for s in range(nsb):
            blk_s = jax.tree_util.tree_map(lambda a: a[s], blocks)
            st_s = jax.tree_util.tree_map(lambda a: a[s], rest)
            carry, y = body(carry, (blk_s, st_s, jnp.int32(s)))
            outs.append(y)
        (x, live, reads, pairs, arenas) = carry
        new_rest = jax.tree_util.tree_map(lambda *zs: jnp.stack(zs), *outs)
    if active is not None:
        # the arenas took lane-masked row writes: only the rest is frozen
        with jax.named_scope("lane_select"):
            new_rest = lane_select(active, new_rest, rest)
        reads = reads * active.astype(reads.dtype)
    new_state = _merge_arenas(new_rest, arenas)
    with jax.named_scope("sample"):
        logits = lm_logits(params, x, arch)[:, 0]
    # static int (i32 under jit, lint-clean): 1 iff every attention layer
    # traced the Pallas kernel — a requested kernel that silently fell back
    # to the reference einsum is visible in the step metrics
    kernel_only = 1 if (impls and impls == {"kernel"}) else 0
    # moe_pairs (B,): (token, expert) pairs the expert layers computed for
    # each lane, summed over layers (zeros for a dense model)
    return logits, new_state, {"live_tokens": live, "reads_tokens": reads,
                               "attn_impl_kernel": kernel_only,
                               "moe_pairs": pairs}


def _split_arenas(state: Dict[str, Any]) -> Tuple[Dict[str, Any], dict]:
    """(the decode state with each fixed K/V arena taken out, those arenas
    as ``{pattern position: (k, v)}``, still stacked over superblocks).

    A cache's arenas are taken when they have a row width, which is what a
    fixed arena has and a paged cache's zero-width placeholders lack (the
    pool keeps its own route through the scan)."""
    rest, arenas = dict(state), {}
    for pi, node in state.items():
        if _is_policy_cache(node) and node.cache.k.shape[-1]:
            rest[pi], arenas[pi] = _take_arenas(node)
    return rest, arenas


def _merge_arenas(rest: Dict[str, Any], arenas: dict) -> Dict[str, Any]:
    """The inverse of :func:`_split_arenas`."""
    return {**rest, **{pi: _with_kv(rest[pi], *kv)
                       for pi, kv in arenas.items()}}


def _take_arenas(node):
    """(``node`` with its cache's ``k``/``v`` set to None, the two arrays —
    a LayerArena gives back its stack)."""
    kv = tuple(getattr(a, "stack", a) for a in (node.cache.k, node.cache.v))
    return _with_kv(node, None, None), kv


def _with_kv(node, k, v):
    return dataclasses.replace(node, cache=dataclasses.replace(
        node.cache, k=k, v=v))


def _split_experts(blocks: dict, arch: ArchConfig) -> Tuple[dict, dict]:
    """(blocks without the expert matrices of the MoE layers, those
    matrices by pattern position, still stacked over superblocks): the
    decode scan hands each layer its index, and the expert kernel reads
    that layer's blocks from the stack, where a per-layer slice would be
    copied whole before the call."""
    if arch.mlp is None or arch.mlp.moe is None:
        return blocks, {}
    rest, experts = dict(blocks), {}
    for pi, blk in blocks.items():
        mlp = blk.get("mlp", {})
        if all(n in mlp for n in EXPERT_MATRICES):
            experts[pi] = {n: mlp[n] for n in EXPERT_MATRICES}
            rest[pi] = dict(blk, mlp={n: v for n, v in mlp.items()
                                      if n not in EXPERT_MATRICES})
    return rest, experts


def lane_select(mask: jnp.ndarray, on_true: Any, on_false: Any) -> Any:
    """Per-lane select over two decode-state pytrees.

    Every array leaf of a decode state carries the batch (lane) axis at
    position 1 — leaves are stacked over superblocks first (see
    :func:`init_decode_state`) — so a (B,) bool mask broadcasts as
    (1, B, 1, ...).  Used for: freezing inactive lanes' state, reclaiming
    finished lanes back to a pristine arena, and scheduler lane admission.

    :class:`~repro.core.block_pool.BlockPool` nodes are lane-*shared* state
    with no lane axis: the updated pool is kept unconditionally — its
    mutation helpers already took the lane event mask, so inactive lanes
    produced no pool events to roll back (their per-lane ``phys`` page map
    rolls back here like any other leaf).  :func:`decode_step` freezes the
    state with its fixed K/V arenas taken out, for the same reason: their
    row writes took the mask.
    """

    def sel(a, b):
        if isinstance(a, policy_lib.block_pool.BlockPool):
            return a
        m = mask.reshape((1, -1) + (1,) * (a.ndim - 2))
        return jnp.where(m, a, b)

    return jax.tree_util.tree_map(
        sel, on_true, on_false,
        is_leaf=lambda x: isinstance(x, policy_lib.block_pool.BlockPool))
