"""GQA attention with pluggable KV-cache policies; DMS is a first-class mode.

Three entry points:

* :func:`full_attention`  — full-sequence forward (training / prefill).  In
  DMS mode it extracts α from the borrowed query neuron, relaxes it with
  Gumbel-sigmoid (train) or binarises it (prefill), and applies the delayed-
  eviction mask.  Dispatches to the Pallas flash kernel when requested.
* :func:`decode_attention` — single-token decode against any cache class from
  :mod:`repro.core.kv_cache` / :mod:`repro.core.baselines`.
* :func:`attention_ref`    — the O(T²) masked-softmax oracle both paths and
  the kernels are tested against.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import dms as dms_lib
from repro.core import policy as policy_lib
from repro.core.kv_cache import arena_stack, arena_view
from repro.core.config import ArchConfig, AttentionConfig
from repro.models.layers import apply_rope, dense_init, softcap

NEG_INF = dms_lib.NEG_INF


def init_attention(key, d_model: int, cfg: AttentionConfig) -> dict:
    ks = jax.random.split(key, 4)
    dh = cfg.head_dim
    return {
        "wq": dense_init(ks[0], d_model, cfg.num_heads * dh),
        "wk": dense_init(ks[1], d_model, cfg.num_kv_heads * dh),
        "wv": dense_init(ks[2], d_model, cfg.num_kv_heads * dh),
        "wo": dense_init(ks[3], cfg.num_heads * dh, d_model),
    }


def project_qkv(p: dict, x: jnp.ndarray, cfg: AttentionConfig, dtype):
    b, t, _ = x.shape
    xd = x.astype(dtype)
    q = (xd @ p["wq"].astype(dtype)).reshape(b, t, cfg.num_heads, cfg.head_dim)
    k = (xd @ p["wk"].astype(dtype)).reshape(b, t, cfg.num_kv_heads, cfg.head_dim)
    v = (xd @ p["wv"].astype(dtype)).reshape(b, t, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def attention_ref(
    q: jnp.ndarray,           # (B, Tq, Hq, Dh)
    k: jnp.ndarray,           # (B, Tk, Hkv, Dh)
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray],   # (B, Hkv, Tq, Tk) additive, or None
    logit_cap: Optional[float] = None,
) -> jnp.ndarray:
    """Masked-softmax GQA oracle.  fp32 statistics."""
    b, tq, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, tq, hkv, g, dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores * (dh ** -0.5)
    scores = softcap(scores, logit_cap)
    if mask is not None:
        scores = scores + mask[:, :, None].astype(jnp.float32)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v.astype(jnp.float32))
    return out.reshape(b, tq, hq, dh).astype(q.dtype)


def attention_chunked(
    q: jnp.ndarray,           # (B, Tq, Hq, Dh)
    k: jnp.ndarray,           # (B, Tk, Hkv, Dh)
    v: jnp.ndarray,
    alpha: Optional[jnp.ndarray],   # (B, Hkv, Tk) or None
    *,
    dms_delay: int = 0,
    causal: bool = True,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
    chunk_q: int = 2048,
    chunk_k: int = 2048,
) -> jnp.ndarray:
    """Flash-style chunked attention in pure JAX (online softmax, unrolled
    chunk loops).  Never materialises T×T — the live intermediate is
    (chunk_q × chunk_k).  Statically skips chunks dead by causality/window.
    This is the dry-run lowering path: same FLOPs/memory shape as the Pallas
    kernel, but expressible to XLA's cost model (loops unrolled, not scanned).
    """
    b, tq, hq, dh = q.shape
    tk = k.shape[1]
    hkv = k.shape[2]
    g = hq // hkv
    cq, ck = min(chunk_q, tq), min(chunk_k, tk)
    nq, nk = -(-tq // cq), -(-tk // ck)
    scale = dh ** -0.5
    qg = q.reshape(b, tq, hkv, g, dh)
    log_surv = (dms_lib.eviction_log_survival(alpha) if alpha is not None else None)

    out_rows = []
    for qi in range(nq):
        q0, q1 = qi * cq, min((qi + 1) * cq, tq)
        qc = qg[:, q0:q1].astype(k.dtype)
        m = jnp.full((b, hkv, g, q1 - q0), NEG_INF, jnp.float32)
        l = jnp.zeros((b, hkv, g, q1 - q0), jnp.float32)
        acc = jnp.zeros((b, hkv, g, q1 - q0, dh), jnp.float32)
        for ki in range(nk):
            k0, k1 = ki * ck, min((ki + 1) * ck, tk)
            if causal and k0 > q1 - 1:
                continue                                   # static causal skip
            if window is not None and k1 - 1 < q0 - window + 1:
                continue                                   # static window skip
            kc = k[:, k0:k1]
            vc = v[:, k0:k1]
            # bf16 operands / fp32 accumulation (MXU semantics — no converts)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            if logit_cap is not None:
                s = logit_cap * jnp.tanh(s / logit_cap)
            ids_q = jnp.arange(q0, q1)[:, None]
            ids_k = jnp.arange(k0, k1)[None, :]
            if log_surv is not None and dms_delay > 0:
                zone = (ids_q - ids_k) >= dms_delay
                s = s + jnp.where(zone[None, None, None],
                                  log_surv[:, :, None, None, k0:k1], 0.0)
            dead = jnp.zeros_like(s, bool)
            if causal:
                dead |= (ids_k > ids_q)[None, None, None]
            if window is not None:
                dead |= (ids_q - ids_k >= window)[None, None, None]
            s = jnp.where(dead, NEG_INF, s)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = corr * l + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32)
            m = m_new
        l = jnp.where(l <= 0.0, 1.0, l)
        out_rows.append((acc / l[..., None]).transpose(0, 3, 1, 2, 4))
    out = jnp.concatenate(out_rows, axis=1)               # (B, Tq, Hkv, G, Dh)
    return out.reshape(b, tq, hq, dh).astype(q.dtype)


def attention_chunked_scan(
    q, k, v, alpha, *, dms_delay: int = 0, causal: bool = True,
    window: Optional[int] = None, logit_cap: Optional[float] = None,
    chunk_q: int = 1024, chunk_k: int = 1024,
) -> jnp.ndarray:
    """Same math as :func:`attention_chunked` but with ``lax.scan`` over both
    chunk loops — sequential by construction, so buffer liveness (and thus the
    dry-run memory pass) reflects a TPU-style schedule.  Used only where
    memory realism matters; the unrolled variant feeds the FLOP analysis."""
    b, tq, hq, dh = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    cq, ck = min(chunk_q, tq), min(chunk_k, tk)
    nq, nk = -(-tq // cq), -(-tk // ck)
    tqp, tkp = nq * cq, nk * ck
    scale = dh ** -0.5
    qp = jnp.pad(q, ((0, 0), (0, tqp - tq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, tkp - tk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, tkp - tk), (0, 0), (0, 0)))
    log_surv = (dms_lib.eviction_log_survival(alpha) if alpha is not None else None)
    if log_surv is not None:
        log_surv = jnp.pad(log_surv, ((0, 0), (0, 0), (0, tkp - tk)),
                           constant_values=NEG_INF)
        ls_blk = log_surv.reshape(b, hkv, nk, ck).transpose(2, 0, 1, 3)
    else:
        ls_blk = jnp.zeros((nk, b, hkv, ck), jnp.float32)
    qb = qp.reshape(b, nq, cq, hkv, g, dh).transpose(1, 0, 3, 4, 2, 5)  # (nq,B,H,G,cq,D)
    kb = kp.reshape(b, nk, ck, hkv, dh).transpose(1, 0, 3, 2, 4)        # (nk,B,H,ck,D)
    vb = vp.reshape(b, nk, ck, hkv, dh).transpose(1, 0, 3, 2, 4)

    def q_step(_, qx):
        qi, qc = qx

        def k_step(carry, kx):
            m, l, acc = carry
            ki, kc, vc, ls = kx
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            if logit_cap is not None:
                s = logit_cap * jnp.tanh(s / logit_cap)
            ids_q = qi * cq + jnp.arange(cq)[:, None]
            ids_k = ki * ck + jnp.arange(ck)[None, :]
            if dms_delay > 0:
                zone = (ids_q - ids_k) >= dms_delay
                s = s + jnp.where(zone[None, None, None],
                                  ls[:, :, None, None, :], 0.0)
            dead = (ids_k >= tk)
            if causal:
                dead = dead | (ids_k > ids_q)
            if window is not None:
                dead = dead | (ids_q - ids_k >= window)
            s = jnp.where(dead[None, None, None], NEG_INF, s)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = corr * l + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        m0 = jnp.full((b, hkv, g, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, cq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, cq, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            k_step, (m0, l0, a0),
            (jnp.arange(nk), kb, vb, ls_blk))
        l = jnp.where(l <= 0.0, 1.0, l)
        out = (acc / l[..., None]).astype(q.dtype)          # (B,H,G,cq,D)
        return None, out

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qb))
    # (nq, B, H, G, cq, D) -> (B, nq, cq, H, G, D) -> (B, Tq, Hq, D)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, tqp, hq, dh)
    return out[:, :tq]


def _causal_mask(tq: int, tk: int, q_offset: int = 0) -> jnp.ndarray:
    i = jnp.arange(tq)[:, None] + q_offset
    j = jnp.arange(tk)[None, :]
    return jnp.where(j <= i, 0.0, NEG_INF)


def _window_mask(tq: int, tk: int, window: int, q_offset: int = 0) -> jnp.ndarray:
    i = jnp.arange(tq)[:, None] + q_offset
    j = jnp.arange(tk)[None, :]
    return jnp.where((i - j) < window, 0.0, NEG_INF)


# ---------------------------------------------------------------------------
# full-sequence forward
# ---------------------------------------------------------------------------


def full_attention(
    p: dict,
    x: jnp.ndarray,
    cfg: AttentionConfig,
    arch: ArchConfig,
    *,
    layer_window: Optional[int] = None,
    mode: str = "vanilla",           # vanilla | dms_train | dms_eval | dms_phase1
    dms_rng: Optional[jax.Array] = None,
    positions: Optional[jnp.ndarray] = None,
    neuron_scale: float = 0.0,
    use_kernel: bool = False,
    attn_impl: Optional[str] = None,     # 'ref' | 'chunked' | 'kernel'
    collect_kv: bool = False,
    kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,   # cross-attn
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Full-sequence attention; returns (output (B,T,D), aux).

    aux keys: alpha_sum / alpha_count (DMS loss), alpha (relaxed or binary),
    and optionally post-RoPE k, v + retained map for cache construction.
    """
    dtype = jnp.dtype(arch.dtype)
    b, t, _ = x.shape
    if positions is None:
        positions = jnp.arange(t, dtype=jnp.int32)
    q_raw, k, v = project_qkv(p, x, cfg, dtype)
    if kv_override is not None:
        k, v = kv_override

    aux: Dict[str, Any] = {}
    alpha = None
    dms = arch.dms
    if mode == "dms_train" and dms.enabled:
        alpha, q_raw = dms_lib.train_alphas(q_raw, cfg.num_kv_heads, dms, dms_rng)
        aux["alpha_sum"] = jnp.sum(alpha)
        # static python float: alpha.size is shape-derived — materializing it
        # as a traced f32 scalar per layer per step is exactly what the
        # literal-materialize lint (repro.analysis) flags
        aux["alpha_count"] = float(alpha.size)
    elif mode == "dms_eval" and dms.enabled:
        alpha_bin, q_raw = dms_lib.infer_alphas(q_raw, cfg.num_kv_heads, dms)
        alpha = alpha_bin.astype(jnp.float32)
        aux["alpha_bin"] = alpha_bin
        aux["alpha_sum"] = jnp.sum(alpha)
        aux["alpha_count"] = float(alpha.size)     # static (see above)
    elif mode == "dms_phase1" and dms.enabled:
        # phase-1 retrofit: gradually zero the borrowed neuron, no masking yet
        q_raw = dms_lib.zero_borrowed_neuron(q_raw, cfg.num_kv_heads, neuron_scale)

    if cfg.rope != "none":
        rope_pos = positions
        if cfg.rope == "mrope" and positions.ndim == 1:
            rope_pos = jnp.broadcast_to(positions, (3,) + positions.shape)
        q = apply_rope(q_raw, rope_pos, cfg.rope_theta, cfg.rope,
                       cfg.mrope_sections, cfg.yarn)
        k = apply_rope(k, rope_pos, cfg.rope_theta, cfg.rope,
                       cfg.mrope_sections, cfg.yarn) \
            if kv_override is None else k
    else:
        q = q_raw

    window = layer_window if layer_window is not None else cfg.window
    impl = attn_impl or ("kernel" if use_kernel else "ref")

    if impl == "kernel" and kv_override is None:
        from repro.kernels.dms_attention import ops as kops
        out = kops.dms_flash_attention(
            q, k, v, alpha,
            window=window, dms_window=dms.window if (alpha is not None) else 0,
            causal=cfg.causal, logit_cap=cfg.logit_softcap,
            immediate=dms.immediate_eviction,
        )
    elif impl in ("chunked", "chunked_scan") and kv_override is None:
        delay = (1 if dms.immediate_eviction else dms.window) if alpha is not None else 0
        if impl == "chunked_scan":
            out = attention_chunked_scan(
                q, k, v, alpha, dms_delay=delay, causal=cfg.causal,
                window=window, logit_cap=cfg.logit_softcap)
        else:
            chunk = max(2048, t // 8)  # bound unrolled chunk pairs (compile time)
            out = attention_chunked(
                q, k, v, alpha, dms_delay=delay, causal=cfg.causal,
                window=window, logit_cap=cfg.logit_softcap,
                chunk_q=chunk, chunk_k=chunk)
    else:
        mask = None
        if cfg.causal:
            mask = _causal_mask(t, k.shape[1])
        if window is not None:
            wm = _window_mask(t, k.shape[1], window)
            mask = wm if mask is None else mask + wm
        if mask is not None:
            mask = jnp.broadcast_to(mask[None, None], (b, cfg.num_kv_heads, t, k.shape[1]))
        if alpha is not None:
            dmask = dms_lib.build_dms_mask(
                alpha, positions if positions.ndim == 1 else jnp.arange(t),
                jnp.arange(k.shape[1]), dms, causal=False)
            mask = dmask if mask is None else mask + dmask
        out = attention_ref(q, k, v, mask, cfg.logit_softcap)

    y = out.reshape(b, t, cfg.num_heads * cfg.head_dim) @ p["wo"].astype(dtype)

    if collect_kv:
        aux["k_rope"] = k.transpose(0, 2, 1, 3)    # (B, Hkv, T, Dh)
        aux["v"] = v.transpose(0, 2, 1, 3)
        if "alpha_bin" in aux:
            aux["retained"] = dms_lib.retained_after_prefill(aux["alpha_bin"], t, dms)
    return y.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def decode_attention(
    p: dict,
    x_t: jnp.ndarray,              # (B, 1, D)
    cache: Any,
    cfg: AttentionConfig,
    arch: ArchConfig,
    *,
    layer_window: Optional[int] = None,
    pos_t: Optional[jnp.ndarray] = None,   # scalar int32 OR per-lane (B,)
    use_kernel: bool = False,
    cross_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]] = None,
    active: Optional[jnp.ndarray] = None,  # (B,) scheduler live-lane mask
) -> Tuple[jnp.ndarray, Any, Dict[str, Any]]:
    """One decode step against a :class:`repro.core.policy.PolicyCache`.

    All policy behaviour (cache update, visibility, eviction, budget
    accounting) is dispatched through the KVPolicy registry keyed by the
    cache's static policy name — this function contains no per-policy code.

    ``pos_t`` may be a scalar (lockstep batch) or a per-lane (B,) vector:
    continuous batching runs lanes at different sequence positions (staggered
    admission / chunked prefill), so RoPE and window masking are per lane.

    Returns (output (B,1,D), new_cache, aux).  aux["live_tokens"] feeds the
    hyper-scaling peak-memory axis; aux["reads_tokens"] the KV-reads axis
    (the two differ for reads-sparse policies like Quest).
    """
    dtype = jnp.dtype(arch.dtype)
    b = x_t.shape[0]
    dms = arch.dms
    q_raw, k_new, v_new = project_qkv(p, x_t, cfg, dtype)
    if pos_t is None:
        pos_t = _cache_length(cache)                      # (B,) per lane
    pos_lane = jnp.broadcast_to(jnp.asarray(pos_t, jnp.int32), (b,))
    pos_arr = pos_lane[:, None]                           # (B, 1) for RoPE

    # cache is a PolicyCache (or None for encoder-memory cross-attention);
    # its static policy name is the only dispatch key
    pol = None if cache is None else policy_lib.get_policy(cache.policy)

    alpha_bin = None
    if pol is not None and pol.alpha_mode == "dms" and dms.enabled:
        alpha_bin, q_raw = dms_lib.infer_alphas(q_raw, cfg.num_kv_heads, dms)
        alpha_bin = alpha_bin[..., 0]                     # (B, Hkv)
    elif pol is not None and pol.alpha_mode == "always":
        logits = dms_lib.alpha_logits_from_q(q_raw, cfg.num_kv_heads, dms.logit_bias)
        alpha_bin = dms_lib.binary_alpha(logits)[..., 0]
        q_raw = dms_lib.zero_borrowed_neuron(q_raw, cfg.num_kv_heads)

    if cfg.rope != "none":
        rpos = (jnp.broadcast_to(pos_arr[None], (3, b, 1))
                if cfg.rope == "mrope" else pos_arr)
        q = apply_rope(q_raw, rpos, cfg.rope_theta, cfg.rope,
                       cfg.mrope_sections, cfg.yarn)
        k_new = apply_rope(k_new, rpos, cfg.rope_theta, cfg.rope,
                           cfg.mrope_sections, cfg.yarn)
    else:
        q = q_raw

    k_new_c = k_new.transpose(0, 2, 1, 3)                 # (B, Hkv, 1, Dh)
    v_new_c = v_new.transpose(0, 2, 1, 3)

    aux: Dict[str, Any] = {}
    window = layer_window if layer_window is not None else cfg.window

    if cross_kv is not None:
        k_all, v_all, valid = cross_kv                    # encoder memory: no update
        out, _, _ = _masked_decode(
            q, policy_lib.AttendSpec(k_all, v_all, valid), None, cfg,
            use_kernel)
        y = out.reshape(b, 1, cfg.num_heads * cfg.head_dim) @ p["wo"].astype(dtype)
        aux["live_tokens"] = jnp.sum(valid, axis=-1).mean(axis=-1)
        aux["reads_tokens"] = aux["live_tokens"]
        return y.astype(x_t.dtype), cache, aux

    if pol is None:
        raise TypeError(f"decode_attention needs a PolicyCache, got {type(cache)}")

    # Per-layer noise salt for stochastic policies (Keyformer): a param
    # scalar is distinct per layer (incl. across superblocks — params are
    # never broadcast) yet bit-identical between the kernel and reference
    # attention paths, so policy noise streams decorrelate across layers
    # WITHOUT forking on float-ulp differences in activations.
    pol_aux = {"alpha_bin": alpha_bin, "pos_t": pos_lane, "attn_cfg": cfg,
               "arch": arch, "dtype": dtype, "active": active,
               "layer_salt": jax.lax.bitcast_convert_type(
                   p["wo"].reshape(-1)[0].astype(jnp.float32), jnp.uint32)}
    # named scopes split the chunk program's device time by layer in a
    # profiler trace (HLO metadata only: the compiled ops are unchanged)
    with jax.named_scope("kv_write"):
        inner, spec = pol.decode_update(cache.cache, q, k_new_c, v_new_c,
                                        pol_aux)
    with jax.named_scope("attend"):
        out, w_group, impl = _masked_decode(
            q, spec, window if spec.positions is not None else None, cfg,
            use_kernel, pos_lane, need_weights=spec.needs_weights)
    if spec.needs_weights:
        inner = pol.post_attend(inner, w_group, active=active)
    cache = dataclasses.replace(cache, cache=inner)

    y = out.reshape(b, 1, cfg.num_heads * cfg.head_dim) @ p["wo"].astype(dtype)
    metrics = pol.metrics(inner)
    aux["live_tokens"] = metrics["live_tokens"]
    aux["reads_tokens"] = metrics["reads_tokens"]
    # trace-time constant ("kernel" | "ref"): which attention implementation
    # this layer actually traced — decode_step aggregates it so a requested
    # kernel that silently fell back is loud in the step metrics
    aux["attn_impl"] = impl
    return y.astype(x_t.dtype), cache, aux


def _masked_decode(q, spec, window, cfg, use_kernel,
                   pos_t=None, need_weights=False):
    """q: (B,1,Hq,Dh); ``spec``: an :class:`repro.core.policy.AttendSpec`
    (k/v: (B,Hkv,P,Dh) arenas — inside the decode scan, LayerArena views of
    the layer-stacked arena — visible: (B,Hkv,P) bool, optional block
    table); pos_t: per-lane (B,) current positions (or scalar).  K and V
    are read at no more than the query's precision: a wider arena (DMC's
    float32 accumulators) rounds to the query's dtype as it is read.

    Local-window layers additionally hide slots with position <= t - window
    (a *subset* restriction of ``spec.visible``, so the spec's live-block
    table stays a valid cover — the kernel masks the hidden slots in-block).
    Returns (out (B,1,Hq,Dh), group-summed weights (B,Hkv,P) or None, and
    the implementation actually traced — the static string "kernel" | "ref").
    """
    k, v, valid, pos = spec.k, spec.v, spec.visible, spec.positions
    b, _, hq, dh = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    vis = valid
    if window is not None and pos is not None and pos_t is not None:
        ptl = jnp.broadcast_to(jnp.asarray(pos_t, jnp.int32), (b,))
        vis = vis & (pos > (ptl[:, None, None] - window))
    if use_kernel:
        from repro.kernels.dms_decode import ops as dkops
        # the kernel reads the layer from its stack in place
        (k, layer), (v, _) = arena_stack(k), arena_stack(v)
        res = dkops.dms_decode_attention(
            q, k, v, vis, block_tbl=spec.block_tbl, block_n=spec.block_n,
            block_p=spec.block_p or None, logit_cap=cfg.logit_softcap,
            pool_k=spec.pool_k, pool_v=spec.pool_v, phys=spec.phys,
            need_weights=need_weights, layer=layer)
        if need_weights:
            out, weights = res
            return out, weights, "kernel"
        return res, None, "kernel"
    k, v = (_at_most(arena_view(a), q.dtype) for a in (k, v))
    # MXU-style mixed precision: bf16 operands, fp32 accumulation — the cache
    # is never converted/materialised in fp32 (that would double decode traffic)
    qg = q[:, 0].reshape(b, hkv, g, dh).astype(k.dtype)
    scores = jnp.einsum("bhgd,bhpd->bhgp", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (dh ** -0.5)
    scores = softcap(scores, cfg.logit_softcap)
    scores = jnp.where(vis[:, :, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgp,bhpd->bhgd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out.reshape(b, 1, hq, dh).astype(q.dtype)
    return out, (jnp.sum(w, axis=2) if need_weights else None), "ref"


def _at_most(a: jnp.ndarray, dtype) -> jnp.ndarray:
    """``a`` rounded to ``dtype`` where ``dtype`` is the narrower."""
    if jnp.dtype(a.dtype).itemsize > jnp.dtype(dtype).itemsize:
        return a.astype(dtype)
    return a


def _cache_length(cache) -> jnp.ndarray:
    return cache.length
