"""The ``moe_hybrid_gqa`` configuration family: its reference against the
program at smoke size (logits through prefill and decode, and the whole
check), its model of the windowed DMS arena, the configuration file against
the program's own Mellum2 configuration, and the readers of the two new
metrics."""
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import costs_moe, harness, spec
from perfbench.models import moe_hybrid_gqa as glue
from perfbench.references import moe_hybrid_gqa as ref
from perfbench.tests import moe_smoke
from repro.configs import get_arch
from repro.core import policy as policy_lib
from repro.core.kv_cache import SlotDMSCache
from repro.models import transformer as tfm
from repro.models.layers import mlp_apply, yarn_freqs

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
CELL = "mellum2-12b-a2.5b.ep8-dms-cr8.reasoning-w4"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return spec.Bench(moe_smoke.build(str(tmp_path_factory.mktemp("bench"))))


def test_sound_run_is_correct_and_float8_is_not(bench):
    rec = harness.run(bench, "smoke.moe.closed", 1, 1.0, False,
                      time.perf_counter(), compile_cache=False)
    assert rec["correct"], rec["check"]
    assert rec["check"]["tokens"] > 100
    ctl = harness.run(bench, "smoke.moe.closed", 3, 1.0, False,
                      time.perf_counter(), compile_cache=False, control=True)
    assert not ctl["correct"]


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["reference_attention", "kernels"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_program_logits_match_the_reference(seed, use_kernel):
    """Prompt then decode through the program's DMS caches (48 tokens: past
    the 16-token window and the 4-step delay) against the reference's full
    forward, logit by logit.  The limit on the mean |difference|, 0.02,
    lies above the program's readings (0.0055-0.0080: bfloat16 activations
    and caches against float32, on the same bfloat16 weights) and below the
    float8 reference's (0.082-0.098); the widest difference is not held to
    it: a DMS decision or a routing pick within rounding of its threshold
    goes the other way in one of the two (0.25-0.47 at the worst position
    with the reference attention)."""
    cfg = moe_smoke.config()
    arch, pol = glue.program_arch(cfg), glue.program_policy(cfg)
    params = glue.program_params(seed, cfg)
    toks = np.random.default_rng(seed).integers(3, 512, 48).astype(np.int32)
    want = ref.logits(seed, cfg, toks)
    state = tfm.init_decode_state(arch, 1, cfg["max_len"], pol)
    step = jax.jit(lambda p, t, s, i: tfm.decode_step(
        p, t, s, arch, i, use_kernel=use_kernel))
    got = []
    for i in range(len(toks)):
        lg, state, _ = step(params, jnp.asarray(toks[None, i:i + 1]), state,
                            jnp.int32(i))
        got.append(np.asarray(lg[0]))
    diff = np.abs(np.stack(got) - want)
    assert diff.mean() < 0.02, diff.mean()


@pytest.mark.parametrize("p_evict,slots,window", [
    (0.9, 24, 12), (0.3, 24, 12), (0.0, 10, 12), (0.7, 40, 30)])
def test_evict_times_match_the_windowed_slot_arena(p_evict, slots, window):
    """The reference's leave steps are the program's: delayed eviction, the
    window's edge, and overflow recycling (``slots`` < what the window
    keeps) in a sliding layer's arena."""
    delay, steps, heads = 6, 120, 3
    rng = np.random.default_rng(int(p_evict * 10) + slots)
    alpha = rng.random((steps, heads)) < p_evict
    cache = SlotDMSCache.init(1, heads, slots, 4, delay, jnp.float32,
                              attn_window=window)
    e = np.stack([ref.evict_times(alpha[:, h], delay, slots, window)
                  for h in range(heads)], -1)
    for t in range(steps):
        k = jnp.full((1, heads, 1, 4), float(t))
        cache = cache.step(k, k, jnp.asarray(alpha[t][None]))
        live = np.zeros((steps, heads), bool)
        pos, valid = np.asarray(cache.pos[0]), np.asarray(cache.valid[0])
        for h in range(heads):
            live[pos[h][valid[h]], h] = True
        want = (np.arange(steps)[:, None] <= t) & (t < e)
        assert (live == want).all(), f"step {t}"


def test_reference_expert_layer_is_the_programs():
    """The reference's expert layer and the program's, on one share of the
    smoke model's weights."""
    cfg = moe_smoke.config()
    hf, arch = cfg["hf"], glue.program_arch(cfg)
    w = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
        ref.weights.make_layer(ref.weights.base_key(5),
                               ref.weight_specs(hf), 0))
    h = jax.random.normal(jax.random.PRNGKey(1), (7, 1, hf["hidden_size"]))
    h = h.astype(jnp.bfloat16).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(w, h, hf, (0, 1))
    got, _ = mlp_apply({k: w[k] for k in ("router", "w_gate", "w_up",
                                          "w_down")},
                       h, arch.mlp, jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=0.03)


def test_reference_rope_is_the_programs():
    cfg = spec.load_json(os.path.join(
        ROOT, "perfbench/configs/mellum2-12b-a2.5b.ep8-dms-cr8.json"))
    arch = glue.program_arch(cfg)
    inv, scale = ref.inv_freqs(cfg["hf"], "full_attention")
    np.testing.assert_allclose(inv, yarn_freqs(128, 5e5, arch.attn.yarn),
                               rtol=1e-6)
    assert scale == arch.attn.yarn.attention_factor
    inv, scale = ref.inv_freqs(cfg["hf"], "sliding_attention")
    np.testing.assert_allclose(
        inv, 1.0 / 5e5 ** (np.arange(0, 128, 2) / 128), rtol=1e-6)
    assert scale == 1.0


def test_config_file_is_the_programs_mellum2():
    """The benchmark's configuration builds the program's registered
    ``mellum2-12b-a2.5b`` (published widths, the EP-8 share), and states
    the arenas the program provisions."""
    cfg = spec.Bench(ROOT).config(spec.Bench(ROOT).workload(CELL)["config"])
    arch, pol = glue.program_arch(cfg), glue.program_policy(cfg)
    mine = dataclasses.replace(get_arch("mellum2-12b-a2.5b"),
                               name=cfg["name"], dms=arch.dms)
    assert arch == mine
    state = jax.eval_shape(lambda: tfm.init_decode_state(
        arch, 2, cfg["max_len"], pol))
    slots = [pc.cache.slots for pc in policy_lib.iter_policy_caches(state)]
    assert slots == [cfg["arena_slots"]] * 3 + [cfg["arena_slots_full"]]
    assert cfg["reduced"] == ["num_experts"]
    assert cfg["hf"]["num_experts"] == len(cfg["deployment"]["held_experts"])
    shapes = glue.param_shapes(cfg)
    n = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert 2.40e9 < n < 2.48e9                     # 4.9 GB in bfloat16


def _run(act, trace):
    hf = spec.load_json(os.path.join(
        ROOT, "perfbench/configs/mellum2-12b-a2.5b.ep8-dms-cr8.json"))["hf"]
    return {"hf": hf, "device_kind": "TPU v5 lite", "trace": trace,
            "counters_traced": {"act": act,
                                "live": np.full(act.shape, 1000.0)}}


def _trace(kernel_s, window_s=2.0):
    ns = 1e9
    return {"XLA Ops": [["%moe_experts.3 = f32[104,2304] custom-call()",
                         0.5 * ns, kernel_s * ns],
                        ["%fusion.1 = f32[1] fusion()", 0.1 * ns, 0.2 * ns]],
            "XLA Modules": [], "device": "/device:TPU:0",
            "host": [["bench.window", 0, window_s * ns]]}


def test_new_readers():
    bench = spec.Bench(ROOT)
    act = np.ones((40, 104), bool)
    flops, nbytes = costs_moe.experts_flops_bytes(_run(act, None)["hf"], act)
    # 104 lanes: 104 pairs (8 picks a token, 1 in 8 held here) a call,
    # hitting 8 (1 - (7/8)^104) of the 8 held experts
    calls = 40 * 28
    hit = 8 * (1 - (1 - 8 / 64) ** 104)
    assert 7.99 < hit < 8
    assert np.isclose(flops, calls * 104 * 6 * 2304 * 896)
    assert np.isclose(nbytes, calls * (hit * 3 * 2304 * 896 * 2
                                       + 104 * 2 * 2304 * 2))
    # one active lane: 8 picks, one pair here, 8 (1 - 7/8) = 1 expert hit
    one = np.zeros((1, 104), bool)
    one[0, 0] = True
    _, one_bytes = costs_moe.experts_flops_bytes(_run(one, None)["hf"], one)
    assert np.isclose(one_bytes, 28 * (3 * 2304 * 896 * 2
                                       + 1 * 2 * 2304 * 2))
    # a step with no active lane needs nothing
    idle = act.copy()
    idle[:10] = False
    _, idle_bytes = costs_moe.experts_flops_bytes(_run(idle, None)["hf"],
                                                  idle)
    assert np.isclose(idle_bytes, nbytes * 30 / 40)
    least = nbytes / 819e9
    run = _run(act, _trace(least * 4))
    got = bench.reader("moe_experts_roofline").read(run)
    assert np.isclose(got, 25.0)
    mfu = bench.reader("moe_step_mfu").read(run)
    want = costs_moe.step_flops(run["hf"], act, run["counters_traced"][
        "live"]) / (2.0 * 197e12) * 100
    assert np.isclose(mfu, want) and 0 < mfu < 100
    # a dense model's run, or no trace: nothing to read
    dense = dict(run, hf={k: v for k, v in run["hf"].items()
                          if k != "num_experts_published"})
    for r in (dense, _run(act, None)):
        assert bench.reader("moe_experts_roofline").read(r) is None
        assert bench.reader("moe_step_mfu").read(r) is None


def test_the_cell_reports_the_expert_metrics():
    bench = spec.Bench(ROOT)
    names = [m["name"] for m in bench.metrics(CELL, True)]
    for n in ("moe_experts_roofline", "moe_step_mfu", "dms_decode_roofline",
              "chunk_step_ms.reasoning", "compile_s"):
        assert n in names
    assert "step_mfu" not in names
    assert [m["name"] for m in bench.metrics(CELL, False)] == [
        "tokens_per_s", "setup_s"]
