"""Pure-jnp oracle for the ``moe_experts`` kernel, over the same pair list:
``y[n] = sum over live pair slots i with pair_tok[i] = n of
pair_w[i] * FFN_e(x[n])``, e the held expert whose slot range holds i, in
the kernel's precision (weight-dtype operands, float32 accumulation, the
SwiGLU product rounded to the weight dtype before the down projection)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def moe_experts_ref(x, w_gate, w_up, w_down, group_off, pair_tok, pair_w):
    n, d = x.shape
    eh = w_gate.shape[0]
    p = pair_tok.shape[0]
    slot = jnp.arange(p)
    expert = jnp.searchsorted(group_off, slot, side="right") - 1
    live = slot < group_off[eh]
    xp = x[pair_tok].astype(w_gate.dtype)                       # (P, D)
    out = jnp.zeros((p, d), jnp.float32)
    for e in range(eh):
        g = jnp.dot(xp, w_gate[e], preferred_element_type=jnp.float32)
        u = jnp.dot(xp, w_up[e], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(w_down.dtype)
        o = jnp.dot(h, w_down[e], preferred_element_type=jnp.float32)
        out = jnp.where((expert == e)[:, None], o, out)
    w = jnp.where(live, pair_w, 0.0)
    return jnp.zeros((n, d), jnp.float32).at[pair_tok].add(w[:, None] * out)
