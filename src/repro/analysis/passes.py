"""Finding type, pass registry, and the decode-path traffic lints.

A *pass* is a function ``(jaxpr, ctx) -> iterable[Finding]`` registered under
a rule name; :func:`run_passes` runs every registered pass (or a subset) over
one traced entry point and applies the allowlist.  Passes see the fully
recursed eqn stream (:func:`repro.analysis.jaxpr.walk_eqns`), so ops hiding
inside ``scan``/``cond``/``pjit`` bodies are linted like top-level ops.

Severity policy (docs/analysis.md):

* ``error`` — a known-pathological traffic pattern on the decode/fork/reclaim
  path (full-arena copy, arena-sized recast, KV upcast, whole-arena gather or
  select in table mode).  Always gates the audit.
* ``warn``  — suspicious but occasionally legitimate (a scalar float
  returned from a traced step).  Gates the audit unless allowlisted.
* ``info``  — an allowlisted finding, kept visible in reports, never gates.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.analysis.jaxpr import out_elems, trace_jaxpr, walk_eqns


@dataclasses.dataclass(frozen=True)
class Finding:
    """One audit finding, anchored to a traced eqn or a pytree leaf."""

    severity: str          # "error" | "warn" | "info"
    rule: str              # registered pass / checker name
    message: str
    eqn: str = ""          # offending primitive + shape summary ("" = tree-level)
    nbytes: int = 0        # bytes the offending op materializes (0 if n/a)
    path: str = ""         # entry point or pytree path the finding anchors to

    def __str__(self) -> str:
        loc = f" [{self.path}]" if self.path else ""
        op = f" {self.eqn}" if self.eqn else ""
        nb = f" ({self.nbytes} B)" if self.nbytes else ""
        return f"{self.severity}:{self.rule}{loc}{op}{nb} — {self.message}"


@dataclasses.dataclass(frozen=True)
class LintContext:
    """Per-entry-point lint parameters.

    ``arena_elems`` is the element count of the smallest fully-provisioned
    KV arena reachable from the entry point: any op materializing that many
    elements (or more) is touching a whole arena, which the block-table
    contract forbids on the step path.  ``table_mode`` is True when auditing
    the block-table/kernel path, where even a *gather* over the provisioned
    arena indicates the wrapper re-materializing table order.
    """

    arena_elems: int
    table_mode: bool = False
    allow: Tuple[str, ...] = ()        # rule names allowlisted for this entry
    #: elements of ONE layer's K/V arena (``arena_elems`` counts a whole
    #: superblock-stacked arena); 0 = ``arena_elems``.  ``arena-select``
    #: measures against it: a step may touch rows of a layer, never a layer
    layer_elems: int = 0


_PASSES: Dict[str, Callable] = {}


def register_pass(name: str):
    """Register ``fn(jaxpr, ctx) -> iterable[Finding]`` under ``name``."""
    def deco(fn):
        if name in _PASSES:
            raise ValueError(f"duplicate analysis pass {name!r}")
        _PASSES[name] = fn
        return fn
    return deco


def available_passes() -> Tuple[str, ...]:
    return tuple(sorted(_PASSES))


def _eqn_str(eqn) -> str:
    outs = ",".join(f"{v.aval.dtype}{list(v.aval.shape)}" for v in eqn.outvars
                    if hasattr(v.aval, "shape"))
    return f"{eqn.primitive.name}->{outs}"


def _out_nbytes(eqn) -> int:
    return sum(int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
               for v in eqn.outvars if hasattr(v.aval, "shape"))


def run_passes(fn_or_jaxpr, ctx: LintContext, *args,
               passes: Optional[Iterable[str]] = None,
               path: str = "") -> List[Finding]:
    """Run lint passes over one entry point (callable + example args, or an
    already-traced jaxpr).  Allowlisted rules are downgraded to ``info``."""
    jaxpr = (fn_or_jaxpr if not callable(fn_or_jaxpr)
             else trace_jaxpr(fn_or_jaxpr, *args))
    names = tuple(passes) if passes is not None else available_passes()
    out: List[Finding] = []
    for name in names:
        for f in _PASSES[name](jaxpr, ctx):
            if f.rule in ctx.allow:
                f = dataclasses.replace(
                    f, severity="info",
                    message=f.message + " (allowlisted)")
            out.append(dataclasses.replace(f, path=f.path or path))
    return out


def gating(findings: Iterable[Finding]) -> List[Finding]:
    """The findings that fail an audit (everything not downgraded to info)."""
    return [f for f in findings if f.severity in ("error", "warn")]


# ---------------------------------------------------------------------------
# traffic lints
# ---------------------------------------------------------------------------


@register_pass("arena-pad")
def _arena_pad(jaxpr, ctx):
    """Full-arena ``pad``/``concatenate`` on the step path: the seed wrapper
    re-padded the whole provisioned arena every step of every layer — the
    copy the block-table layout exists to remove (docs/kernels.md)."""
    for eqn in walk_eqns(jaxpr):
        if eqn.primitive.name in ("pad", "concatenate") \
                and out_elems(eqn) >= ctx.arena_elems:
            yield Finding("error", "arena-pad",
                          "arena-sized copy materialized on a step path",
                          eqn=_eqn_str(eqn), nbytes=_out_nbytes(eqn))


@register_pass("arena-cast")
def _arena_cast(jaxpr, ctx):
    """Arena-sized ``convert_element_type`` of integer/bool metadata (the
    seed's per-step ``valid.astype(int32)`` recast of the whole bitmap)."""
    for eqn in walk_eqns(jaxpr):
        if eqn.primitive.name == "convert_element_type" \
                and out_elems(eqn) >= ctx.arena_elems \
                and not jnp.issubdtype(eqn.invars[0].aval.dtype, jnp.floating):
            yield Finding("error", "arena-cast",
                          "arena-sized metadata recast on a step path",
                          eqn=_eqn_str(eqn), nbytes=_out_nbytes(eqn))


@register_pass("kv-upcast")
def _kv_upcast(jaxpr, ctx):
    """Arena-sized dtype *upcast* of a floating KV leaf (e.g. bf16 → f32).

    Accumulating in f32 is correct — but via ``preferred_element_type`` on
    the dot, never by converting the cache itself: an arena-sized upcast
    doubles both the HBM read and the materialized footprint per step.
    Downcasts (DMC's f32 accumulators → model dtype) are by design.
    """
    for eqn in walk_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = eqn.invars[0].aval.dtype
        dst = eqn.outvars[0].aval.dtype
        if jnp.issubdtype(src, jnp.floating) \
                and jnp.issubdtype(dst, jnp.floating) \
                and dst.itemsize > src.itemsize \
                and out_elems(eqn) >= ctx.arena_elems:
            yield Finding("error", "kv-upcast",
                          f"KV arena upcast {src} -> {dst} on a step path",
                          eqn=_eqn_str(eqn), nbytes=_out_nbytes(eqn))


@register_pass("arena-gather")
def _arena_gather(jaxpr, ctx):
    """In table mode, ``gather``/``dynamic_slice`` whose *operand* is the
    whole provisioned arena and whose output is a layer's arena or more:
    the kernel consumes the arena in place through the scalar-prefetched
    block table, so a step-path gather over it means the wrapper is
    re-materializing table order (the dead-block-DMA pitfall reintroduced
    one level up), or slicing a layer out of the stack for the kernel.  A
    read of a few rows (DMC's merge target, one row a lane-head) is not.

    Rank-<3 operands are exempt: KV arenas and page pools are always ≥3-D
    ((B,H,S,Dh) / (NPOOL,bp,Dh)), while per-token lookups into big 2-D
    tables (the vocab embedding) are the normal decode front-end."""
    if not ctx.table_mode:
        return
    for eqn in walk_eqns(jaxpr):
        if eqn.primitive.name not in ("gather", "dynamic_slice"):
            continue
        op = eqn.invars[0].aval
        if hasattr(op, "shape") and len(op.shape) >= 3 \
                and int(np.prod(op.shape)) >= ctx.arena_elems \
                and out_elems(eqn) >= (ctx.layer_elems or ctx.arena_elems) \
                and jnp.issubdtype(op.dtype, jnp.floating):
            yield Finding("error", "arena-gather",
                          "gather/slice over the whole provisioned arena "
                          "in table mode",
                          eqn=_eqn_str(eqn), nbytes=_out_nbytes(eqn))


@register_pass("arena-select")
def _arena_select(jaxpr, ctx):
    """In table (kernel) mode, a decode step touches a fixed K/V arena only
    by lane-masked row writes, which XLA performs in place, and by the
    kernel's reads (docs/policies.md).  Two shapes of whole-arena traffic
    are flagged, each at one layer's arena or more:

    * an elementwise select (``select_n``) producing a float arena — a
      token write as ``where(hit, k_new, arena)``, or a lane freeze that
      selects the K/V arenas against their old values, each reads and
      rewrites every slot;
    * a ``dynamic_update_slice`` or scatter whose *update* is a whole
      layer's arena — writing a layer back into a stack (a scan's stacked
      output, ``stack.at[l].set(layer)``) copies it.

    Row scatters (updates of a few rows) pass, and so does the kernel's own
    body, whose selects act on VMEM tiles.  The reference attention path
    materializes arenas by design (the layer's slice, a paged dense view),
    so the pass runs in table mode only."""
    if not ctx.table_mode:
        return
    elems = ctx.layer_elems or ctx.arena_elems
    for eqn in walk_eqns(jaxpr, skip=("pallas_call",)):
        name = eqn.primitive.name
        if name == "select_n":
            big = out_elems(eqn) >= elems
            dtype = eqn.outvars[0].aval.dtype
            what = "whole-arena select"
        elif name == "dynamic_update_slice" or name.startswith("scatter"):
            upd = eqn.invars[1 if name == "dynamic_update_slice" else 2].aval
            big = int(np.prod(upd.shape)) >= elems
            dtype = upd.dtype
            what = "whole-layer arena written into a stack"
        else:
            continue
        if big and jnp.issubdtype(dtype, jnp.floating):
            yield Finding("error", "arena-select",
                          f"{what} on a decode step (write rows in place)",
                          eqn=_eqn_str(eqn), nbytes=_out_nbytes(eqn))


@register_pass("ref-fallback")
def _ref_fallback(jaxpr, ctx):
    """In table (kernel) mode, the decode step must trace the Pallas decode
    kernel — a policy that requested ``use_kernel`` but traced the reference
    einsum instead used to be a *silent* fallback (the pre-weights-out
    ``needs_weights`` bypass), lying about HBM traffic for every score-based
    policy.  Two signals, both gating:

    * no ``pallas_call`` anywhere in the step program — attention fell back
      wholesale;
    * a ``dot_general`` with ≥2 batch dims over an arena-sized float operand
      — the reference ``bhgd,bhpd->bhgp`` score einsum streaming the whole
      provisioned arena (param matmuls have 0 batch dims; Quest's page
      scoring has small sub-arena operands — neither trips this).
    """
    if not ctx.table_mode:
        return
    saw_kernel = False
    for eqn in walk_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "pallas_call":
            saw_kernel = True
            continue
        if name != "dot_general":
            continue
        batch_dims = eqn.params["dimension_numbers"][1]
        if len(batch_dims[0]) < 2:
            continue
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape") \
                    and jnp.issubdtype(aval.dtype, jnp.floating) \
                    and int(np.prod(aval.shape)) >= ctx.arena_elems:
                yield Finding("error", "ref-fallback",
                              "reference attention einsum traced where the "
                              "kernel was requested",
                              eqn=_eqn_str(eqn), nbytes=_out_nbytes(eqn))
                break
    if not saw_kernel:
        yield Finding("error", "ref-fallback",
                      "no pallas_call in the decode program in kernel mode "
                      "— attention silently fell back to the reference path")


@register_pass("scalar-output")
def _scalar_output(jaxpr, ctx):
    """Size-1 float *outputs* of the traced step (e.g. the old
    ``aux["alpha_count"] = jnp.asarray(alpha.size, jnp.float32)``):
    shape-derived bookkeeping is static — returning it as a device scalar
    allocates a tiny array per step and invites a ``.item()`` host sync
    downstream.  Return a Python float, or allowlist with a comment (a
    genuine in-graph reduction that must live on device).

    Top-level outvars only: scalar intermediates inside scan/cond bodies
    (attention scales, carry counters) are fused away by XLA and fine."""
    for v in jaxpr.outvars:
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "shape") \
                and int(np.prod(aval.shape)) == 1 \
                and jnp.issubdtype(aval.dtype, jnp.floating):
            yield Finding("warn", "scalar-output",
                          "scalar float returned from a traced step "
                          "(static bookkeeping should be a host value)",
                          eqn=f"outvar {aval.dtype}{list(aval.shape)}")
