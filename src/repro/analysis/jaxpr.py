"""Shared jaxpr walking and traffic counting.

This is the machinery `benchmarks/decode_path.py` and
`benchmarks/paged_arena.py` grew independently; it lives here so the lint
passes, the benchmarks, and the tests all count the same ops the same way.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Iterator

import jax
import jax.numpy as jnp
import numpy as np


def walk_eqns(jaxpr, skip=()) -> Iterator[Any]:
    """Yield every eqn in ``jaxpr``, recursing through the sub-jaxprs hiding
    in eqn params (``scan``/``cond``/``while``/``pjit``/``custom_vjp``/...),
    except those of the primitives named in ``skip``."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name in skip:
            continue
        for val in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(
                    val, is_leaf=lambda x: isinstance(x, (Jaxpr, ClosedJaxpr))):
                if isinstance(sub, ClosedJaxpr):
                    yield from walk_eqns(sub.jaxpr, skip)
                elif isinstance(sub, Jaxpr):
                    yield from walk_eqns(sub, skip)


def trace_jaxpr(fn: Callable, *args, **kwargs):
    """``jax.make_jaxpr`` of an entry point, unwrapped to the raw Jaxpr."""
    return jax.make_jaxpr(fn, **kwargs)(*args).jaxpr


def dce(jaxpr):
    """Dead-code-eliminate a jaxpr so lints see what XLA will actually run.

    ``make_jaxpr`` keeps every traced eqn — e.g. the reference-path dense
    pool gather a paged cache builds alongside the kernel path (DCE'd in
    compilation when the kernel consumes the pool directly).  Linting the
    un-DCE'd program would flag traffic that never executes."""
    from jax._src.interpreters import partial_eval as pe
    new_jaxpr, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
    return new_jaxpr


def out_elems(eqn) -> int:
    """Largest output element count of one eqn (0 for token-only outputs)."""
    sizes = [int(np.prod(v.aval.shape)) for v in eqn.outvars
             if hasattr(v.aval, "shape")]
    return max(sizes) if sizes else 0


def count_arena_copies(fn: Callable, *args, arena_elems: int) -> Dict[str, int]:
    """Count full-arena copy ops in ``fn``'s jaxpr: ``pad``/``concatenate``
    whose output is arena-sized or larger (the seed wrapper's per-step
    re-pad), and ``convert_element_type`` on arena-sized *integer/bool*
    operands (the seed's ``valid.astype(int32)`` recast).  The block-table
    step path must show zero of each."""
    jaxpr = trace_jaxpr(fn, *args)
    pads = casts = 0
    for eqn in walk_eqns(jaxpr):
        big = out_elems(eqn) >= arena_elems
        if eqn.primitive.name in ("pad", "concatenate") and big:
            pads += 1
        elif eqn.primitive.name == "convert_element_type" and big and \
                not jnp.issubdtype(eqn.invars[0].aval.dtype, jnp.floating):
            casts += 1
    return {"arena_pad_copies": pads, "valid_recasts": casts}


def count_big_float_ops(jaxpr, min_elems: int) -> int:
    """Float ops with ≥ ``min_elems`` output elements = actual K/V bytes
    moving.  Integer metadata at any size is deliberately not counted (e.g.
    the paged pool's refcount recompute builds a pool-squared int32 one-hot
    — bookkeeping, not arena traffic)."""
    return sum(
        1 for eqn in walk_eqns(jaxpr)
        for v in eqn.outvars
        if hasattr(v.aval, "shape")
        and jnp.issubdtype(v.aval.dtype, jnp.floating)
        and int(np.prod(v.aval.shape)) >= min_elems)
