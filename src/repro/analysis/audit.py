"""``python -m repro.analysis.audit`` — the static-analysis CI gate.

Sweeps every registered KV policy × {ref, kernel} × {fixed, paged}:

* traffic lints over the traced (and DCE'd) decode / fork / reclaim jaxprs
  (full-arena pads/casts, KV upcasts, whole-arena gathers in table mode,
  literal materialization, and — in kernel mode — the ``ref-fallback`` lint
  proving the decode program actually traced the Pallas kernel rather than
  the reference einsum);
* tree-state invariance of ``decode_step`` (leaf avals stable across steps);
* the KVPolicy lifecycle contract per policy;
* sharding-rule coverage of every decode-state leaf.

Then drives real mini scheduler traces under the retrace sentinel (exactly
one chunk compile) and the host-sync tripwire (no unsanctioned d2h): a
mixed-length width-2-fork trace, a forced preempt→resume round-trip, and a
generated burst workload through the SLO overload ladder (shed +
width-throttle coverage — the control projections are host arithmetic and
must add zero syncs/compiles).

Exits nonzero on any gating finding.  Intentional exceptions are declared
in ``ALLOW`` below with a comment — see docs/analysis.md for the policy.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import contracts
from repro.analysis.hostsync import HostSyncTripwire
from repro.analysis.jaxpr import dce, trace_jaxpr
from repro.analysis.passes import Finding, LintContext, gating, run_passes
from repro.analysis.retrace import RetraceSentinel, engine_jits
from repro.configs import get_smoke
from repro.core import policy as policy_lib
from repro.core.config import KVPolicyConfig
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as tfm

B, MAX_LEN, BLOCK_P = 2, 32, 8

#: rule allowlist per entry-point kind, with the reason it is intentional.
#: (An allowlisted rule is reported as info and does not gate.)
ALLOW: Dict[str, Tuple[str, ...]] = {
    "decode": (),
    "fork": (
        # the FIXED-arena fork legitimately gathers whole per-lane arenas
        # (that is the copy the paged CoW fork removes — the contrast is
        # pinned by benchmarks/paged_arena.py, so it must stay visible
        # there, not fail the audit here)
        "arena-pad",
    ),
    "reclaim": (),
    # preempt-snapshot/resume entry points: a preemption must move ONE
    # lane's state, never a full arena — any arena-sized pad/cast/gather in
    # these programs means eviction copies scale with the pool, not the lane
    "export": (),
    "import": (),
}

#: leaf names where the sharding fallback is an explicit decision.
SHARDING_ALLOW: Tuple[str, ...] = ()


def tiny_arch():
    arch = get_smoke("qwen-r1-1.5b")
    return dataclasses.replace(
        arch, dms=dataclasses.replace(arch.dms, window=4, target_cr=4.0,
                                      steps_per_cr_unit=5))


def policy_cfg(policy: str, paged: bool) -> KVPolicyConfig:
    return KVPolicyConfig(kind=policy, cr=2.0, window=4, block_p=BLOCK_P,
                          paged=paged, quest_page_size=BLOCK_P,
                          quest_top_pages=2)


def _arena_elems(state, *, layer: bool = False) -> int:
    """Smallest fully-provisioned KV arena in the state (stacked over
    superblocks; with ``layer``, one layer's): any op at this many elements
    (or more) touches a whole arena."""
    sizes = []
    for pc in policy_lib.iter_policy_caches(state):
        pool = getattr(pc.cache, "pool", None)
        arr = pool.k if pool is not None else pc.cache.k
        sizes.append(int(np.prod(arr.shape[1:] if layer else arr.shape)))
    return min(sizes)


def audit_combo(arch, params, policy: str, paged: bool,
                use_kernel: bool) -> List[Finding]:
    """Traffic lints for one (policy, layout, path) combo."""
    cfg = policy_cfg(policy, paged)
    state = tfm.init_decode_state(arch, B, MAX_LEN, cfg)
    elems = _arena_elems(state)
    tag = f"{policy}/{'paged' if paged else 'fixed'}" \
          f"/{'kernel' if use_kernel else 'ref'}"
    tok = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    act = jnp.ones((B,), bool)
    src = jnp.zeros((B,), jnp.int32)
    mask = jnp.zeros((B,), bool)

    findings: List[Finding] = []

    def lint(kind: str, fn, *args, table_mode: bool = False):
        jaxpr = dce(trace_jaxpr(fn, *args))
        ctx = LintContext(arena_elems=elems, table_mode=table_mode,
                          allow=ALLOW.get(kind, ()),
                          layer_elems=_arena_elems(state, layer=True))
        findings.extend(run_passes(jaxpr, ctx, path=f"{tag}/{kind}"))

    lint("decode",
         lambda s, t, p, a: tfm.decode_step(params, t, s, arch, p,
                                            use_kernel=use_kernel, active=a),
         state, tok, pos, act, table_mode=use_kernel)
    if not use_kernel:       # fork/reclaim/tree checks are kernel-independent
        lint("fork", tfm.gather_lanes, state, src)
        fresh = tfm.init_decode_state(arch, B, MAX_LEN, cfg)
        lint("reclaim", tfm.reclaim_lanes, state, mask, fresh)
        # preempt snapshot/resume programs (scheduler._preempt/_resume)
        lane = jnp.zeros((), jnp.int32)
        lint("export", tfm.export_lane_state, state, lane)
        snap = jax.eval_shape(tfm.export_lane_state, state, lane)
        snap = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), snap)
        lint("import", tfm.import_lane_state, state, snap, lane)
        findings.extend(contracts.check_tree_invariance(
            lambda s: tfm.decode_step(params, tok, s, arch, pos,
                                      active=act)[1],
            state, path=f"{tag}/decode "))
    return findings


def audit_contracts(arch, policy: str, paged: bool) -> List[Finding]:
    cfg = policy_cfg(policy, paged)
    findings = contracts.check_policy_lifecycle(
        policy, arch, cfg, batch=B, max_len=MAX_LEN)
    mesh = make_local_mesh()
    state = jax.eval_shape(
        lambda: tfm.init_decode_state(arch, B, MAX_LEN, cfg))
    findings += contracts.check_sharding_coverage(
        state, mesh, B, arch, allow=SHARDING_ALLOW)
    return [dataclasses.replace(
        f, path=f"{policy}/{'paged' if paged else 'fixed'} {f.path}")
        for f in findings]


def audit_scheduler(arch, params, paged: bool) -> List[Finding]:
    """Drive a real mini trace under the retrace sentinel + host-sync
    tripwire: mixed prompt lengths, a width-2 fork, budget exhaustion."""
    from repro.serving.engine import Engine
    from repro.serving.scheduler import Request

    cfg = policy_cfg("dms", paged)
    eng = Engine(arch, params, cfg, chunk=4)
    sched = eng.scheduler(num_lanes=3, max_len=MAX_LEN)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 50, size=n).astype(np.int32)
               for n in (3, 7, 5)]
    sched.submit(Request(uid=0, prompt=prompts[0], max_new=4))
    sched.submit(Request(uid=1, prompt=prompts[1], max_new=3, width=2,
                         arrival=1))
    sched.submit(Request(uid=2, prompt=prompts[2], max_new=4, arrival=3))
    with RetraceSentinel(engine_jits(eng),
                         exact={"chunk": 1},
                         budget={"gather": 1, "reset": 1, "prefill": 0,
                                 "export": 0, "import": 0}) as sentinel, \
            HostSyncTripwire() as tripwire:
        results = sched.run()
    tag = f"scheduler/{'paged' if paged else 'fixed'}"
    findings = [dataclasses.replace(f, path=f"{tag}:{f.path}")
                for f in sentinel.findings() + tripwire.violations()]
    if len(results) != 3:
        findings.append(Finding("error", "scheduler",
                                f"expected 3 results, got {len(results)}",
                                path=tag))
    return findings


def audit_preempt(arch, params, paged: bool) -> List[Finding]:
    """Drive a forced preempt→resume round-trip under the retrace sentinel
    and host-sync tripwire: the snapshot/resume path must compile its
    export/import programs exactly once, never retrace the chunk fn, and
    read back device state only at sanctioned boundaries
    (``preempt-snapshot`` / ``pool-pressure`` / ``tick-boundary``)."""
    from repro.serving.engine import Engine
    from repro.serving.faults import Fault, FaultPlan
    from repro.serving.scheduler import Request

    cfg = policy_cfg("dms", paged)
    eng = Engine(arch, params, cfg, chunk=4)
    plan = FaultPlan([Fault("preempt", tick=1, lane=0)])
    sched = eng.scheduler(num_lanes=2, max_len=MAX_LEN, faults=plan)
    prompt = np.random.default_rng(1).integers(
        1, 50, size=7).astype(np.int32)
    sched.submit(Request(uid=0, prompt=prompt, max_new=4))
    with RetraceSentinel(engine_jits(eng),
                         exact={"chunk": 1},
                         budget={"gather": 0, "reset": 1, "prefill": 0,
                                 "export": 1, "import": 1}) as sentinel, \
            HostSyncTripwire() as tripwire:
        results = sched.run()
    tag = f"preempt/{'paged' if paged else 'fixed'}"
    findings = [dataclasses.replace(f, path=f"{tag}:{f.path}")
                for f in sentinel.findings() + tripwire.violations()]
    if (len(results) != 1 or results[0].status != "ok"
            or results[0].preempt_count != 1):
        findings.append(Finding(
            "error", "scheduler",
            f"expected 1 ok result with preempt_count=1, got "
            f"{[(r.status, r.preempt_count) for r in results]}", path=tag))
    return findings


def audit_slo(arch, params, paged: bool) -> List[Finding]:
    """Drive a generated burst workload through the SLO overload ladder
    under the retrace sentinel + host-sync tripwire: the shed and
    width-throttle projections are pure host arithmetic, so an overloaded
    controlled trace must compile the chunk fn exactly once and add ZERO
    device syncs beyond the sanctioned tick boundary."""
    from repro.serving import workload
    from repro.serving.engine import Engine
    from repro.serving.scheduler import SLOSpec

    cfg = policy_cfg("dms", paged)
    eng = Engine(arch, params, cfg, chunk=4)
    spec = workload.WorkloadSpec(
        vocab=50, max_len=MAX_LEN, prompt_len=(6, 10), max_new=(3, 6),
        widths=(1, 2), deadline=10)
    reqs = workload.burst_trace(0, 8, rate=2.0, on_ticks=3, off_ticks=5,
                                spec=spec)
    slo = SLOSpec(ttft_ticks=5, max_queue=4, min_width=1, cooldown_ticks=4)
    sched = eng.scheduler(num_lanes=2, max_len=MAX_LEN, slo=slo)
    for r in reqs:
        sched.submit(r)
    with RetraceSentinel(engine_jits(eng),
                         exact={"chunk": 1},
                         budget={"gather": 1, "reset": 1, "prefill": 0,
                                 "export": 0, "import": 0}) as sentinel, \
            HostSyncTripwire() as tripwire:
        results = sched.run()
    tag = f"slo/{'paged' if paged else 'fixed'}"
    findings = [dataclasses.replace(f, path=f"{tag}:{f.path}")
                for f in sentinel.findings() + tripwire.violations()]
    life = sched.lifecycle_stats()
    # the trace must actually exercise the ladder, or the sync/compile
    # guarantee above is vacuous
    if len(results) != len(reqs) or life["shed"] < 1 \
            or life["degraded"] < 1:
        findings.append(Finding(
            "error", "scheduler",
            f"SLO trace lost coverage: {len(results)}/{len(reqs)} results, "
            f"shed={life['shed']}, degraded={life['degraded']} "
            "(need >=1 each)", path=tag))
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--policies", default=None,
                    help="comma-separated subset (default: all registered)")
    ap.add_argument("--skip-scheduler", action="store_true",
                    help="jaxpr/contract passes only (no execution)")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="also print info-level (allowlisted) findings")
    args = ap.parse_args(argv)

    arch = tiny_arch()
    params = tfm.init_model(jax.random.PRNGKey(0), arch)
    policies = (tuple(args.policies.split(","))
                if args.policies else policy_lib.available_policies())

    findings: List[Finding] = []
    for policy in policies:
        for paged in (False, True):
            for use_kernel in (False, True):
                findings += audit_combo(arch, params, policy, paged,
                                        use_kernel)
            findings += audit_contracts(arch, policy, paged)
            print(f"  audited {policy}/{'paged' if paged else 'fixed'} "
                  f"(ref+kernel)", flush=True)
    if not args.skip_scheduler:
        for paged in (False, True):
            findings += audit_scheduler(arch, params, paged)
            print(f"  audited scheduler/{'paged' if paged else 'fixed'}",
                  flush=True)
            findings += audit_preempt(arch, params, paged)
            print(f"  audited preempt/{'paged' if paged else 'fixed'}",
                  flush=True)
            findings += audit_slo(arch, params, paged)
            print(f"  audited slo/{'paged' if paged else 'fixed'}",
                  flush=True)

    bad = gating(findings)
    shown = findings if args.verbose else bad
    for f in shown:
        print(f)
    n_info = sum(1 for f in findings if f.severity == "info")
    print(f"audit: {len(bad)} gating finding(s), {n_info} allowlisted, "
          f"{len(policies)} policies x {{ref,kernel}} x {{fixed,paged}}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
