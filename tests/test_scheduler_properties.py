"""Property tests: random scheduler traces vs the solo-run oracle.

For ANY trace of requests (mixed prompt lengths, hyperscale widths, EOS
positions, submit ticks) the continuous-batching scheduler must:

* complete every request (no starvation, no deadlock, no lost lanes),
* conserve the lane arena (after the run every lane is idle, unowned and
  reset — nothing leaks across the trace),
* meter every request EXACTLY as a solo run of that request on a fresh
  arena would (per-lane independence: co-residents never pollute each
  other's tokens or budget axes), so per-request meters sum to the
  lockstep oracle's totals.

The checker is plain code shared by two drivers: a seeded deterministic
test and a hypothesis ``@given`` fuzzer.

The chaos half of the file turns the same oracle discipline on the failure
path: random :class:`~repro.serving.faults.FaultPlan` schedules (pool
shrinkage, CoW storms, NaN logits, clock stalls, forced preemptions) against
random traces, on both fixed and paged arenas, asserting the four serving
robustness invariants — termination, lane+pool conservation
(``ref == recount(phys) + ghost``), a definite status per request, and fault
isolation (every ``ok`` request is token-equal to its solo oracle, which
also makes preempt→resume round-trips bitwise).
"""
import dataclasses

import jax
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import get_smoke
from repro.core import block_pool, policy as policy_lib
from repro.core.config import KVPolicyConfig
from repro.core.policy import available_policies
from repro.models import transformer as tfm
from repro.serving.engine import Engine
from repro.serving.faults import Fault, FaultPlan
from repro.serving.scheduler import Request

NUM_LANES = 3
MAX_LEN = 24
CHUNK = 4

_CTX = {}


def _prime(arch, params) -> None:
    """Bind the module's shared engine to the session tiny model.  One engine
    for every example: the chunk/reset/gather jits compile once per (lanes,
    chunk) and are shared across all trace and oracle runs."""
    if "eng" not in _CTX:
        _CTX["arch"] = arch
        _CTX["params"] = params
        _CTX["eng"] = Engine(arch, params,
                             KVPolicyConfig(kind="dms", cr=2.0,
                                            window=arch.dms.window),
                             chunk=CHUNK)


def _engine() -> Engine:
    if "eng" not in _CTX:
        # the fuzz test ran without the seeded tests: build the model directly
        arch = get_smoke("qwen-r1-1.5b")
        arch = dataclasses.replace(
            arch, dms=dataclasses.replace(arch.dms, window=4, target_cr=4.0))
        _prime(arch, tfm.init_model(jax.random.PRNGKey(0), arch))
    return _CTX["eng"]


def _prompt(n, seed):
    vocab = _CTX["arch"].vocab_size
    return np.random.default_rng(seed).integers(
        3, vocab, size=(n,)).astype(np.int32)


def _solo(eng, req: Request):
    """The oracle: the same request alone on a fresh width-sized arena."""
    sched = eng.scheduler(num_lanes=req.width, max_len=MAX_LEN)
    sched.submit(dataclasses.replace(req, arrival=0))
    return sched.run()[0]


def check_trace(spec):
    """spec: list of (plen, width, max_new, arrival, eos_pos|None) tuples."""
    eng = _engine()
    reqs = []
    for i, (plen, width, max_new, arrival, eos_pos) in enumerate(spec):
        req = Request(uid=i, prompt=_prompt(plen, seed=1000 + i),
                      max_new=max_new, width=width, arrival=arrival)
        if eos_pos is not None:
            # pick a token the request actually emits, so EOS early-exit
            # genuinely triggers (same eos in oracle and trace)
            free = _solo(eng, req)
            chain = free.tokens[0][:int(free.lengths[0])]
            req = dataclasses.replace(
                req, eos_id=int(chain[min(eos_pos, len(chain) - 1)]))
        reqs.append(req)

    sched = eng.scheduler(num_lanes=NUM_LANES, max_len=MAX_LEN)
    for r in reqs:
        sched.submit(r)
    results = {r.uid: r for r in sched.run()}

    # 1. every request completes, within budget
    assert sorted(results) == list(range(len(reqs)))
    for r in reqs:
        got = results[r.uid]
        assert got.tokens.shape == (r.width, r.max_new)
        assert all(1 <= int(l) <= r.max_new for l in got.lengths)
        if r.eos_id is None:
            assert all(int(l) == r.max_new for l in got.lengths)

    # 2. lane accounting conserves the arena: every lane idle + reset
    assert not sched.queue and not sched.active_reqs
    assert all(o is None for o in sched.owner)
    assert not sched.decoding.any() and not sched.finished.any()
    assert (sched.pos == 0).all()
    for pc in policy_lib.iter_policy_caches(sched.state):
        live = np.asarray(pc.cache.retained_tokens())
        assert (live == 0).all(), "reclaimed lane arena not empty"

    # 3. per-request meters + tokens == the solo oracle, exactly
    tot = {"pre": 0.0, "dec": 0.0, "gen": 0}
    oracle_tot = {"pre": 0.0, "dec": 0.0, "gen": 0}
    for r in reqs:
        got, ref = results[r.uid], _solo(eng, r)
        np.testing.assert_array_equal(got.tokens, ref.tokens, err_msg=str(r.uid))
        np.testing.assert_array_equal(got.lengths, ref.lengths)
        assert got.prefill_meter.kv_reads == pytest.approx(
            ref.prefill_meter.kv_reads), r.uid
        assert got.decode_meter.kv_reads == pytest.approx(
            ref.decode_meter.kv_reads), r.uid
        assert got.decode_meter.generated_tokens == \
            ref.decode_meter.generated_tokens, r.uid
        tot["pre"] += got.prefill_meter.kv_reads
        tot["dec"] += got.decode_meter.kv_reads
        tot["gen"] += got.meter.generated_tokens
        oracle_tot["pre"] += ref.prefill_meter.kv_reads
        oracle_tot["dec"] += ref.decode_meter.kv_reads
        oracle_tot["gen"] += ref.meter.generated_tokens
    assert tot == pytest.approx(oracle_tot)


def _spec_from_rng(rng, n):
    spec = []
    for _ in range(n):
        max_new = int(rng.integers(1, 7))
        plen = int(rng.integers(1, MAX_LEN - max_new))
        width = int(rng.integers(1, NUM_LANES + 1))
        arrival = int(rng.integers(0, 7))
        eos_pos = int(rng.integers(0, max_new)) if rng.random() < 0.5 else None
        spec.append((plen, width, max_new, arrival, eos_pos))
    return spec


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_trace_matches_solo_oracle_seeded(seed, tiny_arch, tiny_params):
    """Deterministic case.  Reuses the shared tiny model from conftest (the
    fuzz test below primes itself only when run alone)."""
    _prime(tiny_arch, tiny_params)
    rng = np.random.default_rng(seed)
    check_trace(_spec_from_rng(rng, n=int(rng.integers(2, 5))))


_req_strategy = st.tuples(
    st.integers(min_value=1, max_value=16),       # plen (<= MAX_LEN - max_new)
    st.integers(min_value=1, max_value=NUM_LANES),  # width
    st.integers(min_value=1, max_value=6),        # max_new
    st.integers(min_value=0, max_value=6),        # arrival tick
    st.one_of(st.none(), st.integers(min_value=0, max_value=5)),  # eos pos
)


@settings(max_examples=10, deadline=None)
@given(st.lists(_req_strategy, min_size=1, max_size=5))
def test_random_trace_matches_solo_oracle_fuzzed(spec):
    """Hypothesis driver: same checker, adversarially-shrunk traces."""
    spec = [(min(plen, MAX_LEN - max_new - 1) or 1, width, max_new, arr, eos)
            for (plen, width, max_new, arr, eos) in spec]
    check_trace(spec)


# -- chaos: fault injection vs the robustness invariants ---------------------

# policy sample for the chaos fuzz (the bitwise preempt→resume sweep below
# covers the full registry); engines are cached per (kind, paged) so every
# seed/fuzz example reuses the compiled chunk/export/import jits
CHAOS_POLICIES = ("dms", "tova", "quest")
POOL_BLOCKS = 12
_CHAOS = {}


def _chaos_engine(kind, paged):
    key = (kind, paged)
    if key not in _CHAOS:
        _engine()                      # make sure _CTX carries arch + params
        arch = _CTX["arch"]
        cfg = KVPolicyConfig(kind=kind, cr=2.0, budget=12,
                             window=arch.dms.window, quest_page_size=4,
                             paged=paged, block_p=8,
                             pool_blocks=POOL_BLOCKS if paged else None)
        _CHAOS[key] = Engine(arch, _CTX["params"], cfg, chunk=CHUNK)
    return _CHAOS[key]


def _solo_chaos(eng, req: Request):
    """Fault-free oracle on the same engine and lane count (shared jits)."""
    sched = eng.scheduler(num_lanes=NUM_LANES, max_len=MAX_LEN)
    sched.submit(dataclasses.replace(req, arrival=0, deadline=None))
    return sched.run()[0]


def check_chaos(seed, paged, kind):
    """One chaos episode: a seeded request trace under a seeded FaultPlan."""
    eng = _chaos_engine(kind, paged)
    rng = np.random.default_rng(seed)
    reqs = [Request(uid=i,
                    prompt=_prompt(int(rng.integers(4, 13)),
                                   seed=2000 + 10 * seed + i),
                    max_new=int(rng.integers(3, 8)),
                    arrival=int(rng.integers(0, 5)), deadline=40)
            for i in range(3)]
    plan = FaultPlan.random(seed, lanes=NUM_LANES, paged=paged)

    sched = eng.scheduler(num_lanes=NUM_LANES, max_len=MAX_LEN, faults=plan)
    for r in reqs:
        sched.submit(r)
    results = {r.uid: r for r in sched.run()}   # invariant 1: terminates

    # invariant 2: exactly one result per request, with a definite status
    assert sorted(results) == [0, 1, 2]
    for uid, got in results.items():
        assert got.status in ("ok", "failed", "timeout"), (uid, got.status)

    # invariant 3: conservation — lanes idle + reset, pool refcounts exactly
    # the recount of live mappings plus the injector's ghost ledger, and the
    # exhausted latch never survives the run
    assert not sched.queue and not sched.active_reqs
    assert all(o is None for o in sched.owner)
    assert not sched.decoding.any() and not sched.finished.any()
    pooled = [pc for pc in policy_lib.iter_policy_caches(sched.state)
              if getattr(pc.cache, "pool", None) is not None]
    for idx, pc in enumerate(pooled):
        pool = pc.cache.pool
        want = np.asarray(block_pool.recount(pc.cache.phys,
                                             pool.ref.shape[-1]))
        ghost = plan.ghosts.get(idx)
        if ghost is not None:
            want = want + ghost
        np.testing.assert_array_equal(np.asarray(pool.ref), want,
                                      err_msg=f"pool {idx} refcount leak")
        assert not bool(np.asarray(pool.exhausted).any())

    # invariant 4: fault isolation — every ok request (preempted or not) is
    # bitwise what its solo run produces; a token from a poisoned chunk or a
    # dropped-write lane must never have reached a result
    for r in reqs:
        got = results[r.uid]
        if got.status != "ok":
            continue
        ref = _solo_chaos(eng, r)
        np.testing.assert_array_equal(got.tokens, ref.tokens,
                                      err_msg=f"uid {r.uid} diverged")
        np.testing.assert_array_equal(got.lengths, ref.lengths)


@pytest.mark.parametrize("paged", [False, True], ids=["fixed", "paged"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chaos_faults_keep_invariants_seeded(seed, paged, tiny_arch,
                                             tiny_params):
    """Deterministic chaos driver — runs in every environment."""
    _prime(tiny_arch, tiny_params)
    check_chaos(seed, paged, CHAOS_POLICIES[seed % len(CHAOS_POLICIES)])


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=0, max_value=10 ** 6), st.booleans(),
       st.sampled_from(CHAOS_POLICIES))
def test_chaos_faults_keep_invariants_fuzzed(seed, paged, kind):
    """Hypothesis chaos driver: same invariants, adversarial seeds."""
    check_chaos(seed, paged, kind)


# -- bitwise preempt→resume, full policy registry ----------------------------


@pytest.mark.parametrize("paged", [False, True], ids=["fixed", "paged"])
@pytest.mark.parametrize("kind", sorted(available_policies()))
def test_preempt_resume_bitwise_per_policy(kind, paged, tiny_arch,
                                           tiny_params):
    """Acceptance: for every registry policy, on fixed and paged arenas, a
    request force-preempted mid-prefill (tick 1) AND mid-decode (tick 5)
    resumes from its host snapshot and finishes bitwise-identical to an
    undisturbed run — zero re-prefill, greedy decode carries no RNG."""
    _prime(tiny_arch, tiny_params)
    eng = _chaos_engine(kind, paged)
    req = Request(uid=0, prompt=_prompt(9, seed=77), max_new=6)
    oracle = _solo_chaos(eng, req)

    plan = FaultPlan([Fault("preempt", tick=1, lane=0),
                      Fault("preempt", tick=5, lane=0)])
    sched = eng.scheduler(num_lanes=NUM_LANES, max_len=MAX_LEN, faults=plan)
    sched.submit(req)
    got = sched.run()[0]

    assert got.status == "ok"
    assert got.preempt_count == 2, plan.log
    np.testing.assert_array_equal(got.tokens, oracle.tokens, err_msg=kind)
    np.testing.assert_array_equal(got.lengths, oracle.lengths)
    assert sched.lifecycle_stats() == {
        "preemptions": 2, "resumes": 2, "completed": 1,
        "failures": 0, "timeouts": 0, "rejected": 0, "shed": 0,
        "degraded": 0}


@pytest.mark.parametrize("paged", [False, True], ids=["fixed", "paged"])
def test_preempt_resume_bitwise_hyperscale_width(paged, tiny_arch,
                                                 tiny_params):
    """A width-2 hyperscale request preempts as a unit (both lanes snapshot,
    both resume) and still matches its undisturbed fork bitwise."""
    _prime(tiny_arch, tiny_params)
    eng = _chaos_engine("dms", paged)
    req = Request(uid=0, prompt=_prompt(8, seed=78), max_new=5, width=2)
    oracle = _solo_chaos(eng, req)

    plan = FaultPlan([Fault("preempt", tick=2, lane=0)])
    sched = eng.scheduler(num_lanes=NUM_LANES, max_len=MAX_LEN, faults=plan)
    sched.submit(req)
    got = sched.run()[0]

    assert got.status == "ok" and got.preempt_count == 1
    np.testing.assert_array_equal(got.tokens, oracle.tokens)
    np.testing.assert_array_equal(got.lengths, oracle.lengths)


# -- chaos under bursty overload + SLO control --------------------------------


def check_burst_chaos(seed, paged):
    """Fault isolation when everything lands at once: a bursty workload
    trace (repro.serving.workload), a FaultPlan drawn *near the burst
    arrivals* (the ``arrivals`` hook — stalls/preempts overlap in-flight
    requests instead of idle ticks), AND the SLO ladder armed (shed +
    width-throttle live alongside the fault injector).  Invariants: the run
    terminates, every request has a definite status (now including
    ``rejected``), lanes conserve, shed requests burned zero prefill, and
    every ``ok`` request — degraded or not — is token-equal to its solo
    oracle at the width it was actually served."""
    from repro.serving import workload
    from repro.serving.scheduler import SLOSpec

    eng = _chaos_engine("dms", paged)
    spec = workload.WorkloadSpec(
        vocab=_CTX["arch"].vocab_size, max_len=MAX_LEN - 4,
        prompt_len=(4, 10), max_new=(3, 6), widths=(1, 2), deadline=40)
    reqs = workload.burst_trace(seed, 4, rate=1.5, on_ticks=3, off_ticks=4,
                                spec=spec)
    plan = FaultPlan.random(seed, lanes=NUM_LANES, paged=paged,
                            arrivals=[r.arrival for r in reqs])
    slo = SLOSpec(ttft_ticks=20, min_width=1, cooldown_ticks=3)

    sched = eng.scheduler(num_lanes=NUM_LANES, max_len=MAX_LEN, faults=plan,
                          slo=slo)
    for r in reqs:
        sched.submit(r)
    results = {r.uid: r for r in sched.run()}   # terminates

    assert sorted(results) == [r.uid for r in reqs]
    for uid, got in results.items():
        assert got.status in ("ok", "failed", "timeout", "rejected"), \
            (uid, got.status)
        if got.status == "rejected":
            assert got.admitted_tick == -1
            assert got.prefill_meter.kv_reads == 0

    assert not sched.queue and not sched.active_reqs
    assert all(o is None for o in sched.owner)

    for r in reqs:
        got = results[r.uid]
        if got.status != "ok":
            continue
        served_w = len(got.lengths)
        assert got.degraded == (served_w < r.width)
        ref = _solo_chaos(eng, dataclasses.replace(r, width=served_w))
        np.testing.assert_array_equal(got.tokens, ref.tokens,
                                      err_msg=f"uid {r.uid} diverged")
        np.testing.assert_array_equal(got.lengths, ref.lengths)


@pytest.mark.parametrize("paged", [False, True], ids=["fixed", "paged"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_burst_chaos_with_slo_keeps_isolation_seeded(seed, paged, tiny_arch,
                                                     tiny_params):
    """Deterministic burst-chaos driver — runs in every environment."""
    _prime(tiny_arch, tiny_params)
    check_burst_chaos(seed, paged)


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=0, max_value=10 ** 6), st.booleans())
def test_burst_chaos_with_slo_keeps_isolation_fuzzed(seed, paged):
    """Hypothesis burst-chaos driver: same invariants, adversarial seeds."""
    check_burst_chaos(seed, paged)


def test_faultplan_random_arrivals_hook_targets_bursts():
    """The ``arrivals`` hook: every drawn fault tick lands within the jitter
    window of some arrival, and omitting the hook replays the legacy
    uniform draw bit-identically (same seed, same plan)."""
    from repro.serving import workload

    arr = workload.burst_arrivals(3, 20, rate=2.0, on_ticks=3, off_ticks=9)
    for seed in range(5):
        plan = FaultPlan.random(seed, lanes=2, arrivals=arr)
        for f in plan.faults:
            assert any(a <= f.tick <= a + 2 for a in arr) or f.tick == 1, \
                (f.kind, f.tick)
        a = FaultPlan.random(seed, lanes=2)
        b = FaultPlan.random(seed, lanes=2)
        assert [(f.kind, f.tick, f.lane, f.blocks, f.duration, f.release)
                for f in a.faults] == \
               [(f.kind, f.tick, f.lane, f.blocks, f.duration, f.release)
                for f in b.faults]
