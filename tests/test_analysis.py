"""Decode-path program auditor: every lint pass has a red (seeded-bad) and a
green (real-path) test, the contract checkers catch deliberately broken
implementations, and the retrace sentinel + host-sync tripwire hold over a
randomized mixed scheduler trace.

Acceptance criteria pinned here:
* Each traffic lint fires on a minimal reproduction of the pathology it
  names (seed-era re-pad, metadata recast, KV upcast, whole-arena gather,
  device-scalar bookkeeping) and stays silent on the healthy equivalent.
* The real decode/fork/reclaim entry points lint clean — the audit CLI's
  green sweep is not vacuous.
* A policy violating the lifecycle contract (aval drift, missing metrics)
  is caught by name; the registered nine all pass.
* A randomized scheduler trace (mixed prompt lengths, widths, arrivals,
  EOS) compiles the chunk step exactly once and never syncs the host
  outside the sanctioned tick boundary.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis
from repro.analysis import contracts
from repro.analysis.hostsync import HostSyncTripwire, sanctioned
from repro.analysis.jaxpr import count_big_float_ops, dce, trace_jaxpr
from repro.analysis.passes import LintContext, gating, run_passes
from repro.analysis.retrace import RetraceSentinel, engine_jits
from repro.core.config import KVPolicyConfig
from repro.core.policy import _REGISTRY, available_policies, get_policy
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as tfm
from repro.serving.engine import Engine
from repro.serving.scheduler import Request

ARENA = (2, 2, 16, 4)                       # (B, Hkv, S, Dh) toy arena
ELEMS = int(np.prod(ARENA))


def _findings(fn, *args, table_mode=False, passes=None):
    ctx = LintContext(arena_elems=ELEMS, table_mode=table_mode)
    return run_passes(fn, ctx, *args, passes=passes, path="test")


def _rules(findings):
    return sorted({f.rule for f in gating(findings)})


# -- traffic lints: red on the seeded pathology, green on the healthy twin --


class TestArenaPad:
    def test_red_per_step_repad(self):
        def step(arena, kn):
            # the seed wrapper: re-pad the whole arena every step
            return jnp.concatenate([arena[:, :, 1:], kn], axis=2)

        arena = jnp.zeros(ARENA)
        kn = jnp.zeros((2, 2, 1, 4))
        assert _rules(_findings(step, arena, kn)) == ["arena-pad"]

    def test_green_in_place_write(self):
        def step(arena, kn, pos):
            return jax.lax.dynamic_update_slice(arena, kn, (0, 0, pos, 0))

        arena = jnp.zeros(ARENA)
        assert not _findings(step, arena, jnp.zeros((2, 2, 1, 4)),
                             jnp.int32(3))


class TestArenaCast:
    def test_red_valid_bitmap_recast(self):
        def step(valid):
            # seed-era: astype(int32) of the whole validity bitmap per step
            return valid.astype(jnp.int32).sum(axis=-1)

        valid = jnp.zeros(ARENA, bool)
        assert _rules(_findings(step, valid)) == ["arena-cast"]

    def test_green_small_cast(self):
        def step(length):
            return length.astype(jnp.int32)

        assert not _findings(step, jnp.zeros((2,), jnp.int8))


class TestKVUpcast:
    def test_red_bf16_arena_to_f32(self):
        def step(arena):
            return arena.astype(jnp.float32) * 2.0

        assert _rules(_findings(step, jnp.zeros(ARENA, jnp.bfloat16))) \
            == ["kv-upcast"]

    def test_green_downcast_is_by_design(self):
        def step(acc):
            # DMC writes its f32 accumulators back at model dtype
            return acc.astype(jnp.bfloat16)

        assert not _findings(step, jnp.zeros(ARENA, jnp.float32))


class TestArenaGather:
    @staticmethod
    def _dense_rematerialize(arena, idx):
        # the wrapper re-materializing table order around the kernel
        return jnp.take(arena, idx, axis=2)

    def test_red_table_mode(self):
        # pass-scoped: a kernel-free toy program also (correctly) trips the
        # ref-fallback lint in table mode, exercised by its own tests below
        arena = jnp.zeros(ARENA)
        idx = jnp.arange(ARENA[2])
        got = _findings(self._dense_rematerialize, arena, idx,
                        table_mode=True, passes=["arena-gather"])
        assert _rules(got) == ["arena-gather"]

    def test_green_ref_mode_gathers_allowed(self):
        arena = jnp.zeros(ARENA)
        idx = jnp.arange(ARENA[2])
        assert not _findings(self._dense_rematerialize, arena, idx)

    def test_green_embedding_lookup_exempt(self):
        # per-token lookups into big 2-D tables are the decode front-end,
        # not arena traffic (rank-<3 exemption)
        embed = jnp.zeros((ELEMS * 2, 8))
        tok = jnp.zeros((2, 1), jnp.int32)
        assert not _findings(lambda e, t: e[t], embed, tok,
                             table_mode=True, passes=["arena-gather"])


class TestArenaSelect:
    @staticmethod
    def _where_write(arena, kn, slot):
        # a token write as a select over the whole arena
        hit = jnp.arange(ARENA[2])[None, None, :, None] == slot
        return jnp.where(hit, kn, arena)

    @staticmethod
    def _select(fn, *args, table_mode=True, **ctx):
        ctx = LintContext(arena_elems=ctx.pop("arena_elems", ELEMS),
                          table_mode=table_mode, **ctx)
        return run_passes(fn, ctx, *args, passes=["arena-select"])

    def test_red_where_token_write(self):
        got = self._select(self._where_write, jnp.zeros(ARENA),
                           jnp.zeros((2, 2, 1, 4)), jnp.int32(3))
        assert _rules(got) == ["arena-select"]

    def test_red_layer_written_into_stack(self):
        # a layer's arena written back whole: a scan's stacked output
        def step(stack, arena, layer):
            return jax.lax.dynamic_update_index_in_dim(stack, arena, layer, 0)

        got = self._select(step, jnp.zeros((3,) + ARENA), jnp.zeros(ARENA),
                           jnp.int32(1), arena_elems=3 * ELEMS,
                           layer_elems=ELEMS)
        assert _rules(got) == ["arena-select"]

    def test_red_lane_freeze_over_fixed_arenas(self, fixed_state):
        # the lane freeze selecting the K/V arenas against their old values
        _, state = fixed_state
        elems, layer = _lint_elems(state)
        got = self._select(tfm.lane_select, jnp.ones((2,), bool), state,
                           state, arena_elems=elems, layer_elems=layer)
        assert _rules(got) == ["arena-select"]

    def test_green_in_place_row_write(self):
        # one row per (lane, head) scattered into the layer of the stack
        from repro.core.kv_cache import LayerArena, write_rows

        def step(stack, kn, slot, layer):
            return write_rows(LayerArena(stack, layer), slot, kn,
                              jnp.ones(slot.shape, bool)).stack

        got = self._select(step, jnp.zeros((3,) + ARENA),
                           jnp.zeros((2, 2, 1, 4)),
                           jnp.full((2, 2, 1), 3, jnp.int32), jnp.int32(1),
                           arena_elems=3 * ELEMS, layer_elems=ELEMS)
        assert not got

    def test_green_ref_mode_is_silent(self):
        assert not self._select(self._where_write, jnp.zeros(ARENA),
                                jnp.zeros((2, 2, 1, 4)), jnp.int32(3),
                                table_mode=False)


class TestRefFallback:
    @staticmethod
    def _ref_attention(q, arena):
        # the reference bhgd,bhpd->bhgp score einsum over the whole arena
        return jnp.einsum("bhgd,bhpd->bhgp", q, arena)

    def test_red_reference_einsum_in_kernel_mode(self):
        q = jnp.zeros((2, 2, 2, 4))
        got = _findings(self._ref_attention, q, jnp.zeros(ARENA),
                        table_mode=True, passes=["ref-fallback"])
        assert _rules(got) == ["ref-fallback"]
        # both signals fire: the arena-sized score einsum itself, and the
        # absence of any pallas_call in the program
        assert len(gating(got)) == 2

    def test_green_ref_mode_is_silent(self):
        q = jnp.zeros((2, 2, 2, 4))
        assert not _findings(self._ref_attention, q, jnp.zeros(ARENA),
                             passes=["ref-fallback"])

    def test_param_matmul_not_flagged_as_einsum_fallback(self):
        # 0-batch-dim matmuls (the MLP/projection path) never trip the
        # einsum signal, however large — only the missing-kernel signal
        # remains for this (kernel-free) toy program
        w = jnp.zeros((ELEMS, 8))
        x = jnp.zeros((2, ELEMS))
        got = _findings(lambda x, w: x @ w, x, w, table_mode=True,
                        passes=["ref-fallback"])
        msgs = [f.message for f in gating(got)]
        assert msgs and all("no pallas_call" in m for m in msgs)

    def test_red_real_reference_decode_in_table_mode(self, tiny_arch,
                                                     tiny_params,
                                                     paged_state):
        # the actual pre-fix pathology: a decode program that traced the
        # reference einsum where the kernel was requested is caught
        cfg, state = paged_state
        elems = min(int(np.prod((pc.cache.pool.k if pc.cache.pool is not None
                                 else pc.cache.k).shape))
                    for pc in analysis_iter(state))
        tok = jnp.zeros((2, 1), jnp.int32)
        pos = jnp.zeros((2,), jnp.int32)
        jaxpr = dce(trace_jaxpr(
            lambda s: tfm.decode_step(tiny_params, tok, s, tiny_arch, pos,
                                      use_kernel=False), state))
        ctx = LintContext(arena_elems=elems, table_mode=True)
        got = run_passes(jaxpr, ctx, passes=("ref-fallback",))
        assert _rules(got) == ["ref-fallback"]


class TestScalarOutput:
    def test_red_device_scalar_bookkeeping(self):
        def step(arena):
            # the old aux["alpha_count"]: static size returned as f32[]
            return arena * 2.0, jnp.float32(arena.size) + arena.sum() * 0

        got = _findings(step, jnp.zeros(ARENA))
        assert _rules(got) == ["scalar-output"]

    def test_green_vector_metrics(self):
        def step(arena):
            return arena * 2.0, arena.sum(axis=(1, 2, 3))  # per-lane (B,)

        assert not _findings(step, jnp.zeros(ARENA))


def test_allowlist_downgrades_to_info():
    def step(arena):
        return jnp.concatenate([arena, arena], axis=2)

    ctx = LintContext(arena_elems=ELEMS, allow=("arena-pad",))
    got = run_passes(step, ctx, jnp.zeros(ARENA))
    assert got and not gating(got)
    assert all(f.severity == "info" for f in got)


# -- green on the real entry points (the audit sweep is not vacuous) --------


@pytest.fixture(scope="module")
def paged_state(tiny_arch):
    cfg = KVPolicyConfig(kind="dms", cr=2.0, window=4, block_p=8, paged=True)
    return cfg, tfm.init_decode_state(tiny_arch, 2, 32, cfg)


@pytest.fixture
def fixed_state(tiny_arch):
    cfg = KVPolicyConfig(kind="dms", cr=2.0, window=4, block_p=8)
    return cfg, tfm.init_decode_state(tiny_arch, 2, 32, cfg)


def _lint_elems(state):
    """(smallest whole stacked arena or pool, smallest one layer's) in
    elements, over the state's caches."""
    arrs = [pc.cache.pool.k if pc.cache.pool is not None else pc.cache.k
            for pc in analysis_iter(state)]
    return (min(int(np.prod(a.shape)) for a in arrs),
            min(int(np.prod(a.shape[1:])) for a in arrs))


def test_decode_step_lints_clean(tiny_arch, tiny_params, paged_state,
                                 fixed_state):
    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    act = jnp.array([True, False])
    for (_, state), use_kernel in [(s, k) for s in (paged_state, fixed_state)
                                   for k in (False, True)]:
        elems, layer = _lint_elems(state)
        jaxpr = dce(trace_jaxpr(
            lambda s: tfm.decode_step(tiny_params, tok, s, tiny_arch, pos,
                                      use_kernel=use_kernel, active=act),
            state))
        ctx = LintContext(arena_elems=elems, table_mode=use_kernel,
                          layer_elems=layer)
        assert not gating(run_passes(jaxpr, ctx)), use_kernel


def analysis_iter(state):
    from repro.core import policy as policy_lib
    return policy_lib.iter_policy_caches(state)


def test_fork_reclaim_lint_clean(tiny_arch, paged_state):
    cfg, state = paged_state
    elems = int(np.prod(next(iter(analysis_iter(state))).cache.pool.k.shape))
    ctx = LintContext(arena_elems=elems)
    src = jnp.zeros((2,), jnp.int32)
    assert not gating(run_passes(tfm.gather_lanes, ctx, state, src))
    fresh = tfm.init_decode_state(tiny_arch, 2, 32, cfg)
    assert not gating(run_passes(tfm.reclaim_lanes, ctx, state,
                                 jnp.zeros((2,), bool), fresh))


def test_shared_counters_match_benchmark_semantics():
    # the deduped counters still see through scan into sub-jaxprs
    def scanned_pad(arena):
        def body(c, _):
            return jnp.concatenate(
                [c[:, :, 1:], jnp.ones((2, 2, 1, 4))], axis=2), None
        return jax.lax.scan(body, arena, None, length=3)[0]

    arena = jnp.zeros(ARENA)
    got = analysis.count_arena_copies(scanned_pad, arena, arena_elems=ELEMS)
    assert got["arena_pad_copies"] == 1          # one eqn inside the body
    jaxpr = trace_jaxpr(scanned_pad, arena)
    assert count_big_float_ops(jaxpr, ELEMS) >= 1


# -- contract checkers ------------------------------------------------------


def test_tree_invariance_red_and_green():
    tree = {"k": jnp.zeros((2, 4), jnp.bfloat16), "n": jnp.int32(0)}
    assert not contracts.check_tree_invariance(lambda t: t, tree)
    got = contracts.check_tree_invariance(
        lambda t: {"k": t["k"].astype(jnp.float32), "n": t["n"]}, tree)
    assert _rules(got) == ["tree-state"]
    # structure drift is also a finding, not a crash
    got = contracts.check_tree_invariance(lambda t: {"k": t["k"]}, tree)
    assert _rules(got) == ["tree-state"]


def test_policy_lifecycle_green_all_registered(tiny_arch):
    for name in available_policies():
        cfg = KVPolicyConfig(kind=name, cr=2.0, window=4, block_p=8,
                             quest_page_size=8, quest_top_pages=2)
        got = contracts.check_policy_lifecycle(name, tiny_arch, cfg,
                                               batch=2, max_len=32)
        assert not got, (name, [str(f) for f in got])


def test_policy_lifecycle_red_aval_drift(tiny_arch):
    class Broken(type(get_policy("vanilla"))):
        def decode_update(self, cache, q, k_new, v_new, aux):
            cache, spec = super().decode_update(cache, q, k_new, v_new, aux)
            return jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float16), cache), spec

        def metrics(self, cache):
            return {"live_tokens": np.zeros(())}    # wrong shape + missing

    pol = Broken()
    pol.name = "broken-test"
    _REGISTRY["broken-test"] = pol
    try:
        cfg = KVPolicyConfig(kind="vanilla", cr=2.0, window=4)
        got = contracts.check_policy_lifecycle("broken-test", tiny_arch, cfg,
                                               batch=2, max_len=16)
    finally:
        del _REGISTRY["broken-test"]
    rules = _rules(got)
    assert rules == ["policy-protocol", "tree-state"]
    msgs = " ".join(f.message for f in got)
    assert "live_tokens" in msgs and "reads_tokens" in msgs


def test_sharding_coverage_red_unknown_leaf(tiny_arch):
    mesh = make_local_mesh()
    state = {"mystery_blob": jax.ShapeDtypeStruct((3, 2, 5, 7, 2),
                                                  jnp.float32)}
    got = contracts.check_sharding_coverage(state, mesh, 2, tiny_arch)
    assert _rules(got) == ["sharding-coverage"]
    assert not contracts.check_sharding_coverage(
        state, mesh, 2, tiny_arch, allow=("mystery_blob",))


def test_sharding_coverage_green_real_state(tiny_arch, paged_state):
    _, state = paged_state
    mesh = make_local_mesh()
    assert not contracts.check_sharding_coverage(state, mesh, 2, tiny_arch)


# -- retrace sentinel -------------------------------------------------------


def test_retrace_sentinel_red_shape_retrace():
    @jax.jit
    def f(x):
        return x * 2

    f(jnp.zeros((2,)))                           # warm outside the region
    with RetraceSentinel({"f": f}, exact={"f": 1}) as s:
        f(jnp.zeros((3,)))
        f(jnp.zeros((4,)))                       # second compile = retrace
    assert s.compiles == {"f": 2}
    assert _rules(s.findings()) == ["retrace"]


def test_retrace_sentinel_green_stable_shapes():
    @jax.jit
    def f(x):
        return x + 1

    with RetraceSentinel({"f": f}, budget=1) as s:
        for _ in range(4):
            f(jnp.zeros((5,)))
    assert s.compiles == {"f": 1} and not s.findings()


def test_retrace_sentinel_rejects_non_jit():
    with pytest.raises(TypeError):
        RetraceSentinel({"f": lambda x: x})


# -- host-sync tripwire -----------------------------------------------------


def test_hostsync_red_each_kind():
    x = jnp.arange(4)
    with HostSyncTripwire() as tw:
        np.asarray(x)                            # __array__
        x[0].item()                              # .item()
        jax.device_get(x)                        # device_get
    kinds = [e[0] for e in tw.events]
    assert kinds == ["np.asarray", ".item()", "device_get"]
    assert len(tw.violations()) == 3
    assert all(f.rule == "host-sync" for f in tw.violations())


def test_hostsync_sanctioned_tags():
    x = jnp.arange(4)
    with HostSyncTripwire() as tw:
        with sanctioned("tick-boundary"):
            np.asarray(x)                        # allowed: info, not gating
        with sanctioned("rogue-tag"):
            np.asarray(x)                        # unknown tag still gates
    assert not gating(tw.findings()[:1])
    assert len(tw.violations()) == 1
    assert "rogue-tag" in tw.violations()[0].message


def test_hostsync_unarmed_is_free():
    x = jnp.arange(4)
    with sanctioned("tick-boundary"):
        assert int(np.asarray(x)[0]) == 0        # no tripwire: plain numpy
    tw = HostSyncTripwire()
    assert tw.events == [] and not tw.findings()


# -- the serving contract, end to end ---------------------------------------


def test_scheduler_trace_compile_budget_and_no_host_sync(tiny_arch,
                                                         tiny_params):
    """Randomized mixed trace: prompt lengths, widths, arrivals, and EOS
    vary per request — none of it may retrace the chunk step or sync the
    host outside the tick boundary."""
    rng = np.random.default_rng(11)
    cfg = KVPolicyConfig(kind="dms", cr=2.0, window=4, block_p=8, paged=True)
    eng = Engine(tiny_arch, tiny_params, cfg, chunk=4)
    sched = eng.scheduler(num_lanes=4, max_len=48)
    n_req = 6
    for uid in range(n_req):
        w = int(rng.choice([1, 1, 2]))
        sched.submit(Request(
            uid=uid,
            prompt=rng.integers(1, 97, size=int(rng.integers(2, 11)))
                      .astype(np.int32),
            max_new=int(rng.integers(2, 6)),
            width=w,
            eos_id=(3 if uid % 2 else None),     # EOS may or may not fire
            arrival=int(rng.integers(0, 4))))
    with RetraceSentinel(engine_jits(eng),
                         exact={"chunk": 1},
                         budget={"gather": 1, "reset": 1, "prefill": 0,
                                 "export": 0, "import": 0}) as sentinel, \
            HostSyncTripwire() as tripwire:
        results = sched.run()
    assert len(results) == n_req
    assert sentinel.compiles["chunk"] == 1, sentinel.compiles
    assert not sentinel.findings(), sentinel.compiles
    assert not tripwire.violations(), \
        [f.path for f in tripwire.violations()]
    # the sanctioned tick-boundary sync did happen (the trace is not dead)
    assert any(tag == "tick-boundary" for _, tag, _ in tripwire.events)
