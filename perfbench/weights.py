"""Seeded weights, made by the benchmark and not by the program.

Every leaf is a function of ``(seed, leaf name, layer)`` alone, so the
program's parameters are made on the device in one jitted call, and the
reference makes any single layer again later without keeping them.  A leaf
spec is ``(name, shape, scale, layered)``: ``scale`` None means ones (norm
gains), otherwise ``normal * scale``; a layered leaf has a leading layer
axis, one independent draw per layer.  ``base`` is ``PRNGKey(seed)``.
"""
from __future__ import annotations

import zlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

Spec = Tuple[str, Tuple[int, ...], Optional[float], bool]


def base_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(seed)


def _key(base: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(base, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def leaf(base: jax.Array, name: str, shape, scale: Optional[float],
         layer=None) -> jax.Array:
    """A leaf with no layer axis, or layer ``layer``'s slice of a layered
    one (``layer`` may be traced)."""
    if scale is None:
        return jnp.ones(shape, jnp.float32)
    k = _key(base, name)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return jax.random.normal(k, shape, jnp.float32) * scale


def make(base: jax.Array, specs: List[Spec],
         layers: int) -> Dict[str, jax.Array]:
    """Every leaf of ``specs``; layered leaves stacked ``(layers, *shape)``.
    Call under ``jax.jit`` to make them on the device in one program."""
    out = {}
    for name, shape, scale, layered in specs:
        if layered:
            out[name] = jax.vmap(
                lambda l, n=name, s=shape, c=scale: leaf(base, n, s, c, l))(
                jnp.arange(layers))
        else:
            out[name] = leaf(base, name, shape, scale)
    return out


def make_layer(base: jax.Array, specs: List[Spec],
               layer) -> Dict[str, jax.Array]:
    """Layer ``layer``'s slice of every layered leaf of ``specs``."""
    return {name: leaf(base, name, shape, scale, layer)
            for name, shape, scale, layered in specs if layered}
