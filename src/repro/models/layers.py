"""Common neural-net layers: norms, RoPE variants, MLPs, MoE.

Pure-functional: ``init_*`` builds a params dict, ``*_apply`` consumes it.
All matmuls run in the config compute dtype (bf16 by default); norms and
softmax statistics in fp32.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import ArchConfig, MLPConfig, YarnConfig


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def dense_init(key, d_in: int, d_out: int, scale: Optional[float] = None, dtype=jnp.float32):
    scale = scale if scale is not None else d_in ** -0.5
    return jax.random.normal(key, (d_in, d_out), dtype) * scale


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(d: int, kind: str) -> dict:
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def norm_apply(p: dict, x: jnp.ndarray, kind: str, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


def softcap(x: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# RoPE (full / half / M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float, rotary_dim: Optional[int] = None) -> jnp.ndarray:
    rd = rotary_dim or head_dim
    return 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))


def yarn_freqs(head_dim: int, theta: float, yarn: YarnConfig) -> np.ndarray:
    """YaRN inverse frequencies (Dh/2,), float32, as transformers'
    ``_compute_yarn_parameters`` (``truncate`` true): the correction range
    from ``beta_fast`` / ``beta_slow`` rotations, floored and ceiled, and a
    linear ramp from extrapolated (below ``low``) to interpolated (÷
    ``factor``, above ``high``) frequencies.  Static: computed on the host
    once per trace."""
    def corr_dim(rot):
        return (head_dim * math.log(yarn.original_max_position_embeddings
                                    / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                          / head_dim)
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extra = 1.0 - ramp                  # share of the extrapolated frequency
    inv = (1.0 / (yarn.factor * pos_freqs)) * (1 - extra) \
        + (1.0 / pos_freqs) * extra
    return inv.astype(np.float32)


def apply_rope(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    theta: float,
    kind: str = "full",
    mrope_sections: Tuple[int, ...] = (),
    yarn: Optional[YarnConfig] = None,
) -> jnp.ndarray:
    """x: (..., T, H, Dh); positions: (..., T) int — or (3, ..., T) for mrope.

    * full: rotate all head dims (with ``yarn``: YaRN frequencies, and cos
      and sin scaled by its ``attention_factor``).
    * half: rotate the first Dh/2 dims only (ChatGLM-style 2-d RoPE).
    * mrope: Qwen2-VL multimodal RoPE — the Dh/2 frequency slots are split
      into sections (temporal, height, width), each driven by its own
      position stream.
    """
    dh = x.shape[-1]
    if kind == "none":
        return x
    if kind == "half":
        rot, keep = x[..., : dh // 2], x[..., dh // 2:]
        rotated = _rotate(rot, positions.astype(jnp.float32), theta)
        return jnp.concatenate([rotated, keep], axis=-1)
    if kind == "mrope":
        freqs = rope_freqs(dh, theta)                       # (Dh/2,)
        # section id per frequency slot
        sec_id = jnp.repeat(
            jnp.arange(len(mrope_sections)),
            jnp.array(mrope_sections),
            total_repeat_length=dh // 2,
        )
        pos = positions.astype(jnp.float32)                 # (3, ..., T)
        pos_per_freq = pos[sec_id]                          # (Dh/2, ..., T)
        ang = jnp.moveaxis(pos_per_freq, 0, -1) * freqs     # (..., T, Dh/2)
        return _apply_angles(x, ang)
    if yarn is not None:
        ang = positions.astype(jnp.float32)[..., None] * yarn_freqs(
            dh, theta, yarn)
        return _apply_angles(x, ang, yarn.attention_factor)
    return _rotate(x, positions.astype(jnp.float32), theta)


def _rotate(x: jnp.ndarray, pos: jnp.ndarray, theta: float) -> jnp.ndarray:
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)
    ang = pos[..., None] * freqs                             # (..., T, Dh/2)
    return _apply_angles(x, ang)


def _apply_angles(x: jnp.ndarray, ang: jnp.ndarray,
                  scale: float = 1.0) -> jnp.ndarray:
    """ang: (..., T, Dh_rot/2); x: (..., T, H, Dh_rot)."""
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    if scale != 1.0:
        sin, cos = sin * scale, cos * scale
    sin, cos = sin[..., None, :], cos[..., None, :]          # broadcast over heads
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., ::2], xf[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (dense + MoE)
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, cfg: MLPConfig) -> dict:
    ks = jax.random.split(key, 4)
    f = cfg.d_ff
    if cfg.moe is None:
        if cfg.kind in ("swiglu", "geglu"):
            return {
                "w_gate": dense_init(ks[0], d_model, f),
                "w_up": dense_init(ks[1], d_model, f),
                "w_down": dense_init(ks[2], f, d_model),
            }
        return {"w_up": dense_init(ks[0], d_model, f), "w_down": dense_init(ks[1], f, d_model)}
    e = cfg.moe.num_held            # expert weights: the held ones alone
    def einit(k, a, b):
        return jax.random.normal(k, (e, a, b), jnp.float32) * (a ** -0.5)
    p = {"router": dense_init(ks[3], d_model, cfg.moe.num_experts,
                              scale=0.02)}
    if cfg.kind in ("swiglu", "geglu"):
        p.update(
            w_gate=einit(ks[0], d_model, f),
            w_up=einit(ks[1], d_model, f),
            w_down=einit(ks[2], f, d_model),
        )
    else:
        p.update(w_up=einit(ks[0], d_model, f), w_down=einit(ks[1], f, d_model))
    return p


def _act(x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "swiglu":
        return jax.nn.silu(x)
    if kind == "geglu":
        return jax.nn.gelu(x)
    return jax.nn.gelu(x)


def _tp_divides(dim: int) -> bool:
    try:
        mesh = jax._src.mesh.thread_resources.env.physical_mesh
        return (not mesh.empty) and "model" in mesh.axis_names \
            and dim % mesh.shape["model"] == 0
    except Exception:
        return False


def _maybe_shard(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """Best-effort sharding constraint: applies when tracing under a mesh
    context (pjit/dry-run), no-op otherwise (CPU unit tests)."""
    try:
        from jax.sharding import PartitionSpec as P
        import jax.interpreters.pxla  # noqa: F401
        mesh = jax._src.mesh.thread_resources.env.physical_mesh
        if mesh.empty:
            return x
        from jax.sharding import NamedSharding
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))
    except Exception:
        return x


def mlp_apply(p: dict, x: jnp.ndarray, cfg: MLPConfig, dtype,
              use_kernel: bool = False,
              layer: Optional[jnp.ndarray] = None
              ) -> Tuple[jnp.ndarray, dict]:
    """Returns (y, aux) — aux carries the MoE load-balancing loss, and for
    the expert layer of :func:`moe_share_apply` the pairs it computed.

    An MoE layer runs :func:`moe_share_apply` at decode (one token a row)
    and wherever the chip holds a share of the experts; full sequences of a
    layer that holds them all keep :func:`moe_apply`.  ``layer``: the
    expert matrices in ``p`` are stacked over layers, and this is the one
    to apply (:func:`moe_share_apply`)."""
    if cfg.moe is None:
        xd = x.astype(dtype)
        if cfg.kind in ("swiglu", "geglu"):
            h = _act(xd @ p["w_gate"].astype(dtype), cfg.kind) * (xd @ p["w_up"].astype(dtype))
        else:
            h = _act(xd @ p["w_up"].astype(dtype), cfg.kind)
        return (h @ p["w_down"].astype(dtype)).astype(x.dtype), {}
    if cfg.moe.held_experts is not None or x.shape[1] == 1:
        return moe_share_apply(p, x, cfg, dtype, use_kernel=use_kernel,
                               layer=layer)
    return moe_apply(p, x, cfg, dtype)


EXPERT_MATRICES = ("w_gate", "w_up", "w_down")


def moe_share_apply(p: dict, x: jnp.ndarray, cfg: MLPConfig, dtype,
                    use_kernel: bool = False,
                    layer: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, dict]:
    """The expert layer as one chip of an expert-parallel deployment sees
    it: route each token over all ``num_experts``, compute only the pairs
    routed to the experts this chip holds (``moe.held_experts``; all when
    None), and return their part of the result,
    ``y = sum over the token's top-k picks e that are held of p_e FFN_e(x)``
    (the other chips' parts would arrive through the all-to-all, which one
    chip does without).  The pairs run through the ``moe_experts`` kernel
    (``use_kernel``) or its jnp oracle — no per-row capacity, no dispatch
    buffer, no dropped token.  aux ``moe_pairs`` (B,) counts each row's
    pairs computed here.  With ``layer``, the expert matrices in ``p``
    carry a leading axis of stacked layers, and the kernel reads layer
    ``layer``'s blocks in place (a slice handed to it would be copied
    whole first); stored in another dtype, the layer is sliced and cast."""
    from repro.kernels.moe_experts import ops as moe_ops
    moe = cfg.moe
    if cfg.kind != "swiglu":
        raise NotImplementedError(f"expert layer: {cfg.kind} experts")
    b, t, d = x.shape
    held = (tuple(range(moe.num_experts)) if moe.held_experts is None
            else moe.held_experts)
    xt = x.reshape(b * t, d).astype(dtype)
    with jax.named_scope("moe_router"):
        # softmax over all the router's logits, top-k, renormalised
        logits = jnp.dot(xt, p["router"].astype(dtype),
                         preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, moe.top_k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        groups = moe_ops.group_pairs(top_e, top_p, held, moe.num_experts)
    ws = tuple(p[n] for n in EXPERT_MATRICES)
    if layer is not None and any(w.dtype != dtype for w in ws):
        ws, layer = tuple(w[layer] for w in ws), None
    with jax.named_scope("moe_experts"):
        y = moe_ops.moe_experts(
            xt, *(w.astype(dtype) for w in ws), *groups, layer=layer,
            use_kernel=use_kernel)
    is_held = jnp.isin(top_e, jnp.asarray(held, jnp.int32))
    frac_tokens = jnp.mean(jnp.sum(jax.nn.one_hot(top_e, moe.num_experts),
                                   axis=1), axis=0)
    aux = {"moe_aux_loss": moe.aux_loss_weight * moe.num_experts
           * jnp.sum(frac_tokens * jnp.mean(probs, axis=0)),
           "moe_pairs": jnp.sum(is_held.reshape(b, t * moe.top_k), axis=1)}
    return y.reshape(b, t, d).astype(x.dtype), aux


def moe_apply(p: dict, x: jnp.ndarray, cfg: MLPConfig, dtype,
              capacity_factor: float = 1.25) -> Tuple[jnp.ndarray, dict]:
    """Token-choice top-k MoE with *per-row* capacity dispatch (GShard groups
    = sequences).  Each batch row packs its own expert queues of capacity
    ``C = ceil(capacity_factor · T · k / E)`` so the dispatch buffers stay
    data-parallel-local — no global cumsum across shards.  Over-capacity
    tokens drop that expert (combine weight renormalised over survivors).
    Buffers are EP-sharded on experts when E divides the model axis (the
    scatter lowers to the EP all-to-all), else sharded on the hidden dim.
    """
    moe = cfg.moe
    b, t, d = x.shape
    e, k = moe.num_experts, moe.top_k
    xt = x.astype(dtype)                                             # (B, T, D)

    logits = (xt @ p["router"].astype(dtype)).astype(jnp.float32)    # (B, T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                           # (B, T, k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

    capacity = max(int(capacity_factor * t * k / e), 4)
    # position of each (token, slot) within its (row, expert) queue
    onehot = jax.nn.one_hot(top_e, e, dtype=jnp.int32)               # (B, T, k, E)
    flat = onehot.reshape(b, t * k, e)
    pos_in_e = jnp.cumsum(flat, axis=1) - 1                          # (B, T*k, E)
    pos = jnp.sum(pos_in_e.reshape(b, t, k, e) * onehot, axis=-1)    # (B, T, k)
    keep = pos < capacity
    top_p = jnp.where(keep, top_p, 0.0)

    nk = t * k
    b_idx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, nk)).reshape(-1)
    tok_idx = jnp.broadcast_to(jnp.arange(t)[None, :, None], (b, t, k)).reshape(-1)
    e_idx = top_e.reshape(-1)
    c_idx = jnp.clip(pos.reshape(-1), 0, capacity - 1)
    w_disp = keep.reshape(-1).astype(dtype)
    buf = jnp.zeros((b, e, capacity, d), dtype)
    # EP on experts when E divides the model axis; otherwise shard the
    # capacity dim — a pure batch dim of the expert einsum, so the FFN stays
    # collective-free and only the (small) scatter/gather crosses shards
    buf = _maybe_shard(buf, None, "model", None, None) if _tp_divides(e) else \
        _maybe_shard(buf, None, None, "model", None)
    buf = buf.at[b_idx, e_idx, c_idx].add(xt[b_idx, tok_idx] * w_disp[:, None])

    # expert FFN: (B, E, C, D) x (E, D, F)
    if cfg.kind in ("swiglu", "geglu"):
        h = _act(jnp.einsum("becd,edf->becf", buf, p["w_gate"].astype(dtype)), cfg.kind)
        h = h * jnp.einsum("becd,edf->becf", buf, p["w_up"].astype(dtype))
    else:
        h = _act(jnp.einsum("becd,edf->becf", buf, p["w_up"].astype(dtype)), cfg.kind)
    out_buf = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(dtype))

    # combine: gather each (row, token, slot)'s expert output, weight, sum
    gathered = out_buf[b_idx, e_idx, c_idx]                          # (B*T*k, D)
    w_comb = (top_p.reshape(-1).astype(dtype) * w_disp)[:, None]
    y = jnp.zeros((b, t, d), dtype).at[b_idx, tok_idx].add(gathered * w_comb)

    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    frac_tokens = jnp.mean(jnp.sum(onehot, axis=2).reshape(b * t, e), axis=0)
    frac_probs = jnp.mean(probs.reshape(b * t, e), axis=0)
    aux = {"moe_aux_loss": moe.aux_loss_weight * e * jnp.sum(frac_tokens * frac_probs),
           "moe_drop_frac": 1.0 - jnp.mean(keep.astype(jnp.float32))}
    return y.astype(x.dtype), aux
