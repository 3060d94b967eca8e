"""Plain float32 reference of a dense GQA decoder (Qwen2-style: RMSNorm,
interleaved-pair RoPE, grouped-query attention, SwiGLU, tied embeddings)
with the KV policies the benchmark serves:

* ``vanilla``: every earlier token stays visible.
* ``dms``: Dynamic Memory Sparsification at inference.  Token j's eviction
  decision is ``q_raw[j, first head of its group, 0] + logit_bias > 0``
  (the borrowed neuron, pre-RoPE, then zeroed in the query); a marked token
  leaves the cache ``window`` steps after it entered.  The lane's arena holds
  ``arena_slots`` tokens per KV head: a token that finds it full recycles
  the oldest live token first.

It imports nothing of the program and makes its weights again from the seed
(``perfbench.weights``), one layer at a time, so that it fits next to
nothing else on the chip.  ``score`` runs whole sequences teacher-forced and
returns, per scored position, the best logit, its token, and the logits of
the tokens asked about.  ``precision="fp8"`` is the control: every matmul
operand (weights, activations, queries, keys, values) rounded to float8
e4m3 with a per-tensor (weights) or per-row (activations) scale.
"""
from __future__ import annotations

from collections import deque
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights

NEVER = np.iinfo(np.int32).max
_QBLOCK = 512          # query rows per attention block
_TBUCKET = 512         # sequences are padded to a multiple of this


def weight_specs(hf: dict) -> List[weights.Spec]:
    d, f = hf["hidden_size"], hf["intermediate_size"]
    hq, hkv, dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    return [
        ("embed", (hf["vocab_size"], d), 0.02, False),
        ("final_norm", (d,), None, False),
        ("attn_norm", (d,), None, True),
        ("wq", (d, hq * dh), d ** -0.5, True),
        ("wk", (d, hkv * dh), d ** -0.5, True),
        ("wv", (d, hkv * dh), d ** -0.5, True),
        ("wo", (hq * dh, d), (hq * dh) ** -0.5, True),
        ("mlp_norm", (d,), None, True),
        ("w_gate", (d, f), d ** -0.5, True),
        ("w_up", (d, f), d ** -0.5, True),
        ("w_down", (f, d), f ** -0.5, True),
    ]


# -- DMS arena -----------------------------------------------------------------


def evict_times(alpha: np.ndarray, window: int, slots: int) -> np.ndarray:
    """Step at which each token leaves one KV head's arena (``NEVER`` if it
    stays).  ``alpha``: (T,) bool eviction decisions.  At step t the
    decision of token t - window executes first; then token t takes a slot,
    recycling the oldest live token if all ``slots`` are taken."""
    t_len = alpha.shape[0]
    idx = np.arange(t_len)
    e = np.where(alpha & (idx + window < t_len), idx + window, NEVER)
    # live tokens just before token t is stored, with no recycling
    evicted_by = np.concatenate(
        [np.zeros(window, np.int64), np.cumsum(alpha)[:max(0, t_len - window)]]
    )[:t_len]
    if t_len == 0 or int((idx - evicted_by).max()) < slots:
        return e.astype(np.int64)
    e = np.full(t_len, NEVER, np.int64)
    dead = np.zeros(t_len, bool)
    live: deque = deque()
    count = 0
    for t in range(t_len):
        j = t - window
        if j >= 0 and alpha[j] and not dead[j]:
            dead[j], e[j] = True, t
            count -= 1
        if count == slots:
            while dead[live[0]]:
                live.popleft()
            o = live.popleft()
            dead[o], e[o] = True, t
            count -= 1
        live.append(t)
        count += 1
    return e


# -- forward ---------------------------------------------------------------------


def _q8(x, axis=None):
    """Round to float8 e4m3 under a max-abs scale (per tensor, or per row
    along ``axis``), back in float32."""
    m = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.where(m > 0, m / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ops(precision: str):
    if precision == "f32":
        return (lambda w: w), (lambda a: a)
    if precision == "fp8":
        return _q8, (lambda a: _q8(a, axis=-1))
    raise ValueError(f"unknown precision {precision!r}")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """Interleaved pairs (x[2i], x[2i+1]) rotate by pos * theta^(-2i/dh)."""
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, :, None, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _qkv(w, x, hf, precision, dms):
    wq8, a8 = _ops(precision)
    s, t, _ = x.shape
    hq, hkv, dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    h = a8(_rms(x, w["attn_norm"], hf["rms_norm_eps"]))
    q = (h @ wq8(w["wq"])).reshape(s, t, hq, dh)
    k = (h @ wq8(w["wk"])).reshape(s, t, hkv, dh)
    v = (h @ wq8(w["wv"])).reshape(s, t, hkv, dh)
    alpha_logit = None
    if dms is not None:
        g = hq // hkv
        alpha_logit = q[:, :, ::g, 0] + dms["logit_bias"]       # (S, T, Hkv)
        q = q.at[:, :, ::g, 0].set(0.0)
    return q, k, v, alpha_logit


def _alpha_logits(w, x, hf, precision, dms):
    return _qkv(w, x, hf, precision, dms)[3]


def _layer(w, x, evict, hf, precision, dms):
    """One decoder layer over (S, T, D); ``evict`` (S, T, Hkv) int32."""
    wq8, a8 = _ops(precision)
    s, t, d = x.shape
    hq, hkv, dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    g = hq // hkv
    q, k, v, _ = _qkv(w, x, hf, precision, dms)
    pos = jnp.broadcast_to(jnp.arange(t)[None], (s, t))
    q = a8(_rope(q, pos, hf["rope_theta"]))
    k = a8(_rope(k, pos, hf["rope_theta"]))
    v = a8(v)
    qg = q.reshape(s, t, hkv, g, dh)
    j = jnp.arange(t)

    def block(i0):
        qi = jax.lax.dynamic_slice_in_dim(qg, i0, _QBLOCK, axis=1)
        i = i0 + jnp.arange(_QBLOCK)
        sc = jnp.einsum("sqhgd,skhd->shgqk", qi, k) * dh ** -0.5
        vis = (j[None, None, None, :] <= i[None, None, :, None]) & (
            i[None, None, :, None]
            < evict.transpose(0, 2, 1)[:, :, None, :])           # (S,H,q,k)
        sc = jnp.where(vis[:, :, None], sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("shgqk,skhd->sqhgd", p, v)

    o = jax.lax.map(block, jnp.arange(0, t, _QBLOCK))     # (nb,S,q,H,G,dh)
    o = o.transpose(1, 0, 2, 3, 4, 5).reshape(s, t, hq * dh)
    x = x + a8(o) @ wq8(w["wo"])
    h = a8(_rms(x, w["mlp_norm"], hf["rms_norm_eps"]))
    m = jax.nn.silu(h @ wq8(w["w_gate"])) * (h @ wq8(w["w_up"]))
    return x + a8(m) @ wq8(w["w_down"])


def _head_stats(embed, final_norm, x, served, probe, hf, precision):
    """Per position: best logit, its token, logit of ``served`` and of
    ``probe`` (token ids, -1 = none), in blocks of ``_QBLOCK`` positions."""
    wq8, a8 = _ops(precision)
    w = wq8(embed).T
    s, t, d = x.shape
    rows = lambda a: a.reshape((s * t // _QBLOCK, _QBLOCK) + a.shape[2:])

    def block(args):
        xb, sv, pb = args
        logits = a8(_rms(xb, final_norm, hf["rms_norm_eps"])) @ w
        at = lambda ids: jnp.take_along_axis(
            logits, jnp.maximum(ids, 0)[:, None], -1)[:, 0]
        return (jnp.max(logits, -1), jnp.argmax(logits, -1).astype(jnp.int32),
                at(sv), at(pb))

    outs = jax.lax.map(block, (rows(x), rows(served), rows(probe)))
    return tuple(o.reshape(s, t) for o in outs)


def score(seed: int, cfg: dict, seqs: Sequence[dict],
          precision: str = "f32") -> List[Dict[str, np.ndarray]]:
    """Teacher-forced forward over each ``seq``.

    A seq is ``{"tokens": (T,) int, "start": s, "probe": optional (T-s,)}``:
    positions ``s .. T-2`` are scored, each against the token that follows
    it.  Returns per seq ``best``, ``arg``, ``served`` (logit of the token
    that follows) and ``probe`` (logit of the probed token), each of length
    ``T - 1 - s``."""
    hf = cfg["hf"]
    pol = cfg["policy"]
    dms = ({"logit_bias": cfg["dms"]["logit_bias"]}
           if pol["kind"] == "dms" else None)
    specs = weight_specs(hf)
    t_max = max(len(q["tokens"]) for q in seqs)
    t_pad = -(-t_max // _TBUCKET) * _TBUCKET
    s_n = len(seqs)
    toks = np.zeros((s_n, t_pad), np.int32)
    nxt = np.full((s_n, t_pad), -1, np.int32)
    probe = np.full((s_n, t_pad), -1, np.int32)
    for i, q in enumerate(seqs):
        tk = np.asarray(q["tokens"], np.int32)
        toks[i, :len(tk)] = tk
        st = q["start"]
        nxt[i, st:len(tk) - 1] = tk[st + 1:]
        if q.get("probe") is not None:
            probe[i, st:len(tk) - 1] = q["probe"]
    base = weights.base_key(seed)
    with jax.default_matmul_precision("highest"):
        glob = jax.jit(lambda b: {n: weights.leaf(b, n, sh, sc)
                                  for n, sh, sc, lay in specs if not lay})(
            base)
        x = jnp.take(glob["embed"], jnp.asarray(toks), axis=0)
        wl = jax.jit(lambda b, l: weights.make_layer(b, specs, l))
        alpha_fn = jax.jit(lambda w, x: _alpha_logits(w, x, hf, precision,
                                                      dms))
        layer_fn = jax.jit(lambda w, x, e: _layer(w, x, e, hf, precision,
                                                  dms))
        no_evict = jnp.full((s_n, t_pad, hf["num_key_value_heads"]), NEVER,
                            jnp.int32)
        for layer in range(hf["num_hidden_layers"]):
            w = wl(base, jnp.int32(layer))
            evict = no_evict
            if dms is not None:
                a = np.asarray(alpha_fn(w, x)) > 0                # (S,T,H)
                e = np.stack([np.stack([
                    evict_times(a[i, :, h], pol["window"],
                                cfg["arena_slots"])
                    for h in range(a.shape[2])], -1) for i in range(s_n)])
                evict = jnp.asarray(np.minimum(e, NEVER).astype(np.int32))
            x = layer_fn(w, x, evict)
            del w
        best, arg, served, prb = jax.jit(
            lambda e, g, x, s, p: _head_stats(e, g, x, s, p, hf, precision))(
            glob["embed"], glob["final_norm"], x, jnp.asarray(nxt),
            jnp.asarray(probe))
    best, arg, served, prb = (np.asarray(a) for a in (best, arg, served, prb))
    out = []
    for i, q in enumerate(seqs):
        sl = slice(q["start"], len(q["tokens"]) - 1)
        out.append({"best": best[i, sl], "arg": arg[i, sl],
                    "served": served[i, sl], "probe": prb[i, sl]})
    return out


def borrowed_neuron_logits(seed: int, cfg: dict, tokens: np.ndarray,
                           layers: Optional[int] = None) -> np.ndarray:
    """(layers, T, Hkv) eviction logits of one sequence, float32, with the
    ``dms`` semantics above (for calibrating ``logit_bias``)."""
    hf = cfg["hf"]
    dms = {"logit_bias": cfg["dms"]["logit_bias"]}
    specs = weight_specs(hf)
    base = weights.base_key(seed)
    n_layers = layers or hf["num_hidden_layers"]
    t_pad = -(-len(tokens) // _TBUCKET) * _TBUCKET
    toks = np.zeros((1, t_pad), np.int32)
    toks[0, :len(tokens)] = tokens
    out = []
    with jax.default_matmul_precision("highest"):
        embed = jax.jit(lambda b: weights.leaf(b, "embed", specs[0][1],
                                               specs[0][2]))(base)
        x = jnp.take(embed, jnp.asarray(toks), axis=0)
        del embed
        wl = jax.jit(lambda b, l: weights.make_layer(b, specs, l))
        alpha_fn = jax.jit(lambda w, x: _alpha_logits(w, x, hf, "f32", dms))
        layer_fn = jax.jit(lambda w, x, e: _layer(w, x, e, hf, "f32", dms))
        for layer in range(n_layers):
            w = wl(base, jnp.int32(layer))
            a = np.asarray(alpha_fn(w, x))
            out.append(a[0, :len(tokens)])
            e = np.stack([evict_times(a[0, :, h] > 0,
                                      cfg["policy"]["window"],
                                      cfg["arena_slots"])
                          for h in range(a.shape[2])], -1)
            x = layer_fn(w, x, jnp.asarray(np.minimum(e, NEVER)[None]
                                           .astype(np.int32)))
    return np.stack(out)
