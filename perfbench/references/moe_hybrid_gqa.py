"""Plain float32 reference of a hybrid sliding/full GQA decoder with sparse
experts in every layer (Mellum2-style), as one chip of an expert-parallel
deployment computes it, with the ``dms`` KV policy on every layer:

* layers follow ``hf["layer_types"]``: a ``sliding_attention`` layer lets
  query i see key j only if ``0 <= i - j < sliding_window``, with plain
  RoPE; a ``full_attention`` layer sees every earlier key, with YaRN RoPE
  (``rope_parameters["full_attention"]``: frequencies ramped between
  interpolated and extrapolated, cos and sin times ``attention_factor``).
  RoPE rotates interleaved pairs, as ``dense_gqa`` does.
* the MLP: softmax over the router's ``num_experts_published`` logits, the
  top ``num_experts_per_tok``, renormalised; the output is the sum, over the
  token's picks that this chip holds (``deployment.held_experts``), of the
  pick's weight times that expert's SwiGLU.  The absent experts' part is
  left out, as the chip leaves it out.
* ``dms``: token j's eviction decision is ``q_raw[j, first head of its
  group, 0] + logit_bias > 0`` (the borrowed neuron, pre-RoPE, then zeroed);
  a marked token leaves ``window`` steps after it entered, and in a sliding
  layer every token leaves as it falls out of the window.  A layer's arena
  holds ``arena_slots`` (sliding) or ``arena_slots_full`` tokens a KV head;
  a token that finds it full recycles the oldest live token first.

Weights are made again from the seed (``perfbench.weights``), one layer at a
time, and rounded to bfloat16 as the program stores them; everything else is
float32 at ``default_matmul_precision("highest")``, attention in blocks of
query rows.  It imports nothing of the program.  ``precision="fp8"`` is the
control: every matmul operand rounded to float8 e4m3 (``dense_gqa._q8``).
"""
from __future__ import annotations

import math
from collections import deque
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights
from perfbench.references.dense_gqa import NEVER, _ops, _rms

_QBLOCK = 256          # query rows per attention block
_TBUCKET = 512         # sequences are padded to a multiple of this


def weight_specs(hf: dict) -> List[weights.Spec]:
    """The chip's weights: every attention weight, the router at its
    published width, and the held experts (``num_experts`` of them)."""
    d, f = hf["hidden_size"], hf["moe_intermediate_size"]
    hq, hkv, dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    eh, ep = hf["num_experts"], hf["num_experts_published"]
    return [
        ("embed", (hf["vocab_size"], d), 0.02, False),
        ("lm_head", (d, hf["vocab_size"]), d ** -0.5, False),
        ("final_norm", (d,), None, False),
        ("attn_norm", (d,), None, True),
        ("wq", (d, hq * dh), d ** -0.5, True),
        ("wk", (d, hkv * dh), d ** -0.5, True),
        ("wv", (d, hkv * dh), d ** -0.5, True),
        ("wo", (hq * dh, d), (hq * dh) ** -0.5, True),
        ("mlp_norm", (d,), None, True),
        ("router", (d, ep), d ** -0.5, True),
        ("w_gate", (eh, d, f), d ** -0.5, True),
        ("w_up", (eh, d, f), d ** -0.5, True),
        ("w_down", (eh, f, d), f ** -0.5, True),
    ]


def sliding(hf: dict, layer: int) -> bool:
    return hf["layer_types"][layer] == "sliding_attention"


# -- DMS arena -----------------------------------------------------------------


def evict_times(alpha: np.ndarray, delay: int, slots: int,
                attn_window: Optional[int] = None) -> np.ndarray:
    """Step at which each token leaves one KV head's arena (``NEVER`` if it
    stays).  ``alpha``: (T,) bool eviction decisions.  At step t the decision
    of token t - delay executes first, then (sliding layers) token
    t - attn_window leaves the window; then token t takes a slot, recycling
    the oldest live token if all ``slots`` are taken."""
    t_len = alpha.shape[0]
    idx = np.arange(t_len)
    e = np.where(alpha & (idx + delay < t_len), idx + delay, NEVER)
    if attn_window is not None:
        e = np.where(idx + attn_window < t_len,
                     np.minimum(e, idx + attn_window), e)
    # live tokens just before token t is stored, with no recycling
    change = np.zeros(t_len + 1, np.int64)
    np.add.at(change, np.minimum(idx + 1, t_len), 1)
    np.add.at(change, np.minimum(e, t_len), -1)
    if t_len == 0 or int(np.cumsum(change)[:t_len].max()) < slots:
        return e.astype(np.int64)
    e = np.full(t_len, NEVER, np.int64)
    dead = np.zeros(t_len, bool)
    live: deque = deque()
    count = 0
    for t in range(t_len):
        leaving = [t - delay] if t >= delay and alpha[t - delay] else []
        if attn_window is not None and t >= attn_window:
            leaving.append(t - attn_window)
        for j in leaving:
            if not dead[j]:
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


def inv_freqs(hf: dict, kind: str) -> tuple:
    """(inverse frequencies (Dh/2,), cos/sin scale) of a layer kind, from
    ``rope_parameters`` (transformers' default and YaRN rope types)."""
    rp = hf["rope_parameters"][kind]
    dh, theta = hf["head_dim"], float(rp["rope_theta"])
    pos_freqs = theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    if rp["rope_type"] == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope type {rp['rope_type']!r}")
    orig = rp["original_max_position_embeddings"]

    def corr(rot):
        return dh * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(
            theta))

    low = max(math.floor(corr(rp["beta_fast"])), 0)
    high = min(math.ceil(corr(rp["beta_slow"])), dh - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dh // 2) - low) / (high - low), 0, 1)
    extrapolated = 1.0 - ramp
    inv = (1.0 / (rp["factor"] * pos_freqs)) * (1 - extrapolated) \
        + (1.0 / pos_freqs) * extrapolated
    return inv.astype(np.float32), float(rp["attention_factor"])


def _rope(x, pos, inv, scale):
    ang = pos[:, :, None, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang) * scale, jnp.cos(ang) * scale
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
    g = hq // hkv
    alpha_logit = q[:, :, ::g, 0] + dms["logit_bias"]            # (S, T, Hkv)
    q = q.at[:, :, ::g, 0].set(0.0)
    return q, k, v, alpha_logit


def _alpha_logits(w, x, hf, precision, dms):
    return _qkv(w, x, hf, precision, dms)[3]


def moe(w, h, hf, held: Sequence[int], precision: str = "f32"):
    """The held experts' part of the expert layer over ``h`` (..., D), the
    normed input: softmax over all router logits, top-k, renormalised; the
    sum over the picks in ``held`` (expert ids, in the order of ``w``'s
    expert axis) of weight × SwiGLU."""
    wq8, a8 = _ops(precision)
    h = a8(h)
    probs = jax.nn.softmax(h @ wq8(w["router"]), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, hf["num_experts_per_tok"])
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    out = jnp.zeros_like(h)
    for i, e in enumerate(held):
        p = jnp.sum(jnp.where(top_e == e, top_p, 0.0), axis=-1)
        m = jax.nn.silu(h @ wq8(w["w_gate"][i])) * (h @ wq8(w["w_up"][i]))
        out = out + p[..., None] * (a8(m) @ wq8(w["w_down"][i]))
    return out


def _layer(w, x, evict, hf, precision, dms, held, rope):
    """One decoder layer over (S, T, D); ``evict`` (S, T, Hkv) int32."""
    wq8, a8 = _ops(precision)
    s, t, d = x.shape
    hq, hkv, dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    g = hq // hkv
    inv, scale = rope
    q, k, v, _ = _qkv(w, x, hf, precision, dms)
    pos = jnp.broadcast_to(jnp.arange(t)[None], (s, t))
    q = a8(_rope(q, pos, inv, scale))
    k = a8(_rope(k, pos, inv, scale))
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
    h = _rms(x, w["mlp_norm"], hf["rms_norm_eps"])
    return x + moe(w, h, hf, held, precision)


def _head_stats(lm_head, final_norm, x, served, probe, hf, precision):
    """Per position: best logit, its token, logit of ``served`` and of
    ``probe`` (token ids, -1 = none), in blocks of ``_QBLOCK`` positions."""
    wq8, a8 = _ops(precision)
    w = wq8(lm_head)
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


class _Model:
    """The chip's weights, one layer at a time, as stored (bfloat16) and
    computed on (float32)."""

    def __init__(self, seed: int, cfg: dict, precision: str):
        self.hf, self.pol, self.precision = cfg["hf"], cfg["policy"], precision
        self.cfg = cfg
        self.dms = {"logit_bias": cfg["dms"]["logit_bias"]}
        self.held = tuple(cfg["deployment"]["held_experts"])
        self.specs = weight_specs(self.hf)
        self.base = weights.base_key(seed)
        rounded = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        self.wl = jax.jit(lambda b, l: jax.tree_util.tree_map(
            rounded, weights.make_layer(b, self.specs, l)))
        self.glob = jax.jit(lambda b: {
            n: rounded(weights.leaf(b, n, sh, sc))
            for n, sh, sc, lay in self.specs if not lay})
        self.alpha_fn = jax.jit(lambda w, x: _alpha_logits(
            w, x, self.hf, precision, self.dms))
        self.layer_fns = {}

    def layer_fn(self, kind: str):
        if kind not in self.layer_fns:
            rope = inv_freqs(self.hf, kind)
            self.layer_fns[kind] = jax.jit(lambda w, x, e: _layer(
                w, x, e, self.hf, self.precision, self.dms, self.held, rope))
        return self.layer_fns[kind]

    def evict(self, alpha: np.ndarray, layer: int) -> np.ndarray:
        """(S, T, Hkv) int32 leave steps from (S, T, Hkv) decisions."""
        sl = sliding(self.hf, layer)
        slots = self.cfg["arena_slots" if sl else "arena_slots_full"]
        win = self.hf["sliding_window"] if sl else None
        e = np.stack([np.stack([
            evict_times(alpha[i, :, h], self.pol["window"], slots, win)
            for h in range(alpha.shape[2])], -1)
            for i in range(alpha.shape[0])])
        return np.minimum(e, NEVER).astype(np.int32)

    def run_layer(self, layer: int, x):
        """(x after the layer, the layer's eviction logits)."""
        w = self.wl(self.base, jnp.int32(layer))
        a = np.asarray(self.alpha_fn(w, x))
        kind = self.hf["layer_types"][layer]
        x = self.layer_fn(kind)(w, x, jnp.asarray(self.evict(a > 0, layer)))
        return x, a


def score(seed: int, cfg: dict, seqs: Sequence[dict],
          precision: str = "f32") -> List[Dict[str, np.ndarray]]:
    """Teacher-forced forward over each ``seq``, as ``dense_gqa.score``:
    a seq is ``{"tokens": (T,) int, "start": s, "probe": optional
    (T-s,)}``; positions ``s .. T-2`` are scored.  Returns per seq
    ``best``, ``arg``, ``served`` and ``probe``, each of length
    ``T - 1 - s``."""
    hf = cfg["hf"]
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
    with jax.default_matmul_precision("highest"):
        m = _Model(seed, cfg, precision)
        glob = m.glob(m.base)
        x = jnp.take(glob["embed"], jnp.asarray(toks), axis=0)
        for layer in range(hf["num_hidden_layers"]):
            x, _ = m.run_layer(layer, x)
        best, arg, served, prb = jax.jit(
            lambda e, g, x, s, p: _head_stats(e, g, x, s, p, hf, precision))(
            glob["lm_head"], glob["final_norm"], x, jnp.asarray(nxt),
            jnp.asarray(probe))
    best, arg, served, prb = (np.asarray(a) for a in (best, arg, served, prb))
    out = []
    for i, q in enumerate(seqs):
        sl = slice(q["start"], len(q["tokens"]) - 1)
        out.append({"best": best[i, sl], "arg": arg[i, sl],
                    "served": served[i, sl], "probe": prb[i, sl]})
    return out


def logits(seed: int, cfg: dict, tokens: np.ndarray,
           precision: str = "f32") -> np.ndarray:
    """(T, V) float32 logits of one teacher-forced sequence over the whole
    vocabulary (for small sizes: the tests' comparison with the
    program)."""
    hf = cfg["hf"]
    t_pad = -(-len(tokens) // _TBUCKET) * _TBUCKET
    toks = np.zeros((1, t_pad), np.int32)
    toks[0, :len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        m = _Model(seed, cfg, precision)
        glob = m.glob(m.base)
        x = jnp.take(glob["embed"], jnp.asarray(toks), axis=0)
        for layer in range(hf["num_hidden_layers"]):
            x, _ = m.run_layer(layer, x)
        wq8, a8 = _ops(precision)
        out = a8(_rms(x, glob["final_norm"], hf["rms_norm_eps"])) @ wq8(
            glob["lm_head"])
    return np.asarray(out[0, :len(tokens)])


def borrowed_neuron_logits(seed: int, cfg: dict, tokens: np.ndarray,
                           layers: Optional[int] = None) -> np.ndarray:
    """(layers, T, Hkv) eviction logits of one sequence, float32, with the
    ``dms`` semantics above (for calibrating ``logit_bias``)."""
    hf = cfg["hf"]
    t_pad = -(-len(tokens) // _TBUCKET) * _TBUCKET
    toks = np.zeros((1, t_pad), np.int32)
    toks[0, :len(tokens)] = tokens
    out = []
    with jax.default_matmul_precision("highest"):
        m = _Model(seed, cfg, "f32")
        x = jnp.take(m.glob(m.base)["embed"], jnp.asarray(toks), axis=0)
        for layer in range(layers or hf["num_hidden_layers"]):
            x, a = m.run_layer(layer, x)
            out.append(a[0, :len(tokens)])
    return np.stack(out)
