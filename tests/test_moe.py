"""The expert layer as one chip of an expert-parallel deployment, the
``moe_experts`` kernel, YaRN RoPE, and DMS in sliding-window layers
(Mellum2-12B-A2.5B's mechanisms), at smoke sizes on the CPU."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, get_smoke
from repro.core import policy as policy_lib
from repro.core.config import KVPolicyConfig, MLPConfig, MoEConfig
from repro.core.kv_cache import SlotDMSCache
from repro.kernels.moe_experts import ops as moe_ops
from repro.models import transformer as tfm
from repro.models.layers import apply_rope, mlp_apply, yarn_freqs
from repro.serving.engine import Engine
from repro.serving.scheduler import Request


# -- the moe_experts kernel ---------------------------------------------------


def _weights(key, eh, d, f):
    ks = jax.random.split(key, 3)
    return ((jax.random.normal(ks[0], (eh, d, f)) * d ** -0.5
             ).astype(jnp.bfloat16),
            (jax.random.normal(ks[1], (eh, d, f)) * d ** -0.5
             ).astype(jnp.bfloat16),
            (jax.random.normal(ks[2], (eh, f, d)) * f ** -0.5
             ).astype(jnp.bfloat16))


def _direct(x, w, top_e, top_p, held):
    """sum over each token's picks that are held of p * FFN_e(x), token by
    token, in the kernel's precision."""
    wg, wu, wd = w
    y = np.zeros(x.shape, np.float32)
    for n in range(x.shape[0]):
        for e, p in zip(np.asarray(top_e[n]), np.asarray(top_p[n])):
            if int(e) not in held:
                continue
            i = held.index(int(e))
            xn = x[n:n + 1].astype(jnp.bfloat16)
            h = jax.nn.silu(jnp.dot(xn, wg[i],
                                    preferred_element_type=jnp.float32)) \
                * jnp.dot(xn, wu[i], preferred_element_type=jnp.float32)
            y[n] += float(p) * np.asarray(jnp.dot(
                h.astype(jnp.bfloat16), wd[i],
                preferred_element_type=jnp.float32))[0]
    return y


@pytest.mark.parametrize("n,held,tile_f,empty", [
    (13, (0, 1, 2, 3), 256, ()),
    (40, (4, 5, 6, 7), 128, ()),
    (9, (0, 1, 2, 3), 128, (0, 2)),       # experts 0 and 2 get no pair
    (5, (0, 1, 2, 3), 256, (0, 1, 2, 3)),  # no pair at all
])
def test_moe_experts_kernel_matches_ref(n, held, tile_f, empty):
    """Interpret-mode kernel == the jnp oracle == a token-by-token sum,
    with experts that receive no pair (their step streams no weights and
    adds nothing) and groups longer than the kernel's row chunk."""
    d, f, e_all, k = 128, 256, 16, 4
    key = jax.random.PRNGKey(n)
    x = jax.random.normal(key, (n, d)).astype(jnp.bfloat16)
    w = _weights(jax.random.fold_in(key, 1), len(held), d, f)
    logits = jax.random.normal(jax.random.fold_in(key, 2), (n, e_all))
    logits = logits.at[:, list(empty)].set(-1e9)
    probs = jax.nn.softmax(logits)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    groups = moe_ops.group_pairs(top_e, top_p, held, e_all)
    ref = moe_ops.moe_experts(x, *w, *groups, use_kernel=False)
    got = moe_ops.moe_experts(x, *w, *groups, use_kernel=True, tile_f=tile_f,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref),
                               _direct(x, w, top_e, top_p, list(held)),
                               atol=1e-4)
    counts = np.diff(np.asarray(groups[0]))
    assert all(counts[held.index(e)] == 0 for e in empty if e in held)
    # the same layer read in place from a stack of three (the decode scan's
    # form): the kernel and the oracle both pick layer 1
    stack = tuple(jnp.stack([-wi, wi, 2 * wi]) for wi in w)
    for use_kernel in (True, False):
        got = moe_ops.moe_experts(x, *stack, *groups, layer=jnp.int32(1),
                                  use_kernel=use_kernel, tile_f=tile_f,
                                  interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(
            ref if not use_kernel else moe_ops.moe_experts(
                x, *w, *groups, tile_f=tile_f, interpret=True)))


def test_group_pairs_sorts_by_held_expert():
    top_e = jnp.asarray([[5, 1, 0], [1, 7, 2], [0, 1, 6]], jnp.int32)
    top_p = jnp.asarray([[.5, .3, .2], [.6, .3, .1], [.7, .2, .1]])
    off, tok, w = moe_ops.group_pairs(top_e, top_p, (0, 1), 8)
    assert off.tolist() == [0, 2, 5]                 # expert 0: 2, 1: 3
    assert tok.tolist()[:5] == [0, 2, 0, 1, 2]       # token order per group
    np.testing.assert_allclose(np.asarray(w)[:5], [.2, .7, .3, .6, .2],
                               rtol=1e-6)
    assert tok.shape == (6,)                         # N · min(k, E_h)
    assert moe_ops.fetch_order(jnp.asarray([0, 0, 3, 3, 5])).tolist() == [
        1, 1, 1, 3]


# -- the expert layer -----------------------------------------------------------


def _uncut_layer(p, x, moe, f_dtype=jnp.bfloat16):
    """The whole layer, every expert: softmax, top-k, renormalise, sum."""
    logits = jnp.dot(x.astype(f_dtype), p["router"].astype(f_dtype),
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    top_p, top_e = jax.lax.top_k(probs, moe.top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    w = tuple(p[n].astype(f_dtype) for n in ("w_gate", "w_up", "w_down"))
    return _direct(x, w, top_e, top_p, list(range(moe.num_experts)))


def test_eight_shares_sum_to_the_uncut_layer():
    """Each of 8 chips holds 2 of 16 experts and returns its part; the parts
    add up to the whole layer (no shared expert: nothing is counted
    twice)."""
    d, f = 64, 32
    cfg = MLPConfig(d_ff=f, kind="swiglu",
                    moe=MoEConfig(num_experts=16, top_k=4))
    key = jax.random.PRNGKey(3)
    p = {"router": jax.random.normal(key, (d, 16)) * d ** -0.5}
    w_all = _weights(jax.random.fold_in(key, 1), 16, d, f)
    p.update(w_gate=w_all[0], w_up=w_all[1], w_down=w_all[2])
    x = jax.random.normal(jax.random.fold_in(key, 2), (6, 1, d)
                          ).astype(jnp.bfloat16)
    total = np.zeros((6, d), np.float32)
    pairs = 0
    for share in range(8):
        held = (2 * share, 2 * share + 1)
        scfg = MLPConfig(d_ff=f, kind="swiglu", moe=MoEConfig(
            num_experts=16, top_k=4, held_experts=held))
        sp = dict(p, **{n: p[n][np.asarray(held)]
                        for n in ("w_gate", "w_up", "w_down")})
        y, aux = mlp_apply(sp, x.astype(jnp.float32), scfg, jnp.bfloat16)
        total += np.asarray(y[:, 0], np.float32)
        pairs += int(aux["moe_pairs"].sum())
    want = _uncut_layer(p, x[:, 0], cfg.moe)
    # float32 partial outputs summed in another order than the oracle's
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert pairs == 6 * 4                      # every pick, on one chip


def test_decode_uses_both_kernels():
    """Serving decode traces ``dms_decode`` in every attention layer and
    ``moe_experts`` in every expert layer."""
    arch = get_smoke("mellum2-12b-a2.5b")
    params = tfm.init_model(jax.random.PRNGKey(0), arch)
    state = tfm.init_decode_state(arch, 2, 24, KVPolicyConfig(kind="dms"))
    step = lambda s: tfm.decode_step(params, jnp.zeros((2, 1), jnp.int32), s,
                                     arch, jnp.zeros((2,), jnp.int32),
                                     use_kernel=True)
    text = str(jax.make_jaxpr(step)(state))
    assert text.count("name=moe_experts") == len(arch.layer_pattern)
    _, _, aux = step(state)
    assert int(aux["attn_impl_kernel"]) == 1
    assert aux["moe_pairs"].shape == (2,)


def test_engine_serves_mellum_and_counts_pairs():
    """Engine → Scheduler with dms on every layer: chains come back whole,
    and the tick log counts the expert layers' pairs (every pick routed to
    a held expert, every lane, every step, every layer)."""
    arch = get_smoke("mellum2-12b-a2.5b")
    params = tfm.init_model(jax.random.PRNGKey(0), arch)
    eng = Engine(arch, params, KVPolicyConfig(kind="dms", cr=2.0))
    sched = eng.scheduler(num_lanes=4, max_len=64)
    sched.submit(Request(uid=0, prompt=np.arange(3, 23, dtype=np.int32),
                         max_new=24, width=2))
    (res,) = sched.run()
    assert res.status == "ok" and res.lengths.tolist() == [24, 24]
    assert sum(r.moe_pairs for r in sched.tick_log) > 0
    kinds = [pc.cache.attn_window
             for pc in policy_lib.iter_policy_caches(sched.state)]
    assert kinds == [16, 16, 16, 0]


# -- YaRN ---------------------------------------------------------------------


def _yarn_transcribed(dim, base, factor, orig, beta_fast, beta_slow):
    """transformers' ``_compute_yarn_parameters`` (truncate=True), line by
    line, in numpy."""
    def find_correction_dim(num_rotations, dim, base, max_pos):
        return (dim * math.log(max_pos / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    def find_correction_range(low_rot, high_rot, dim, base, max_pos):
        low = math.floor(find_correction_dim(low_rot, dim, base, max_pos))
        high = math.ceil(find_correction_dim(high_rot, dim, base, max_pos))
        return max(low, 0), min(high, dim - 1)

    def linear_ramp_factor(lo, hi, dim):
        if lo == hi:
            hi += 0.001
        return np.clip((np.arange(dim, dtype=np.float32) - lo) / (hi - lo),
                       0, 1)

    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)
    low, high = find_correction_range(beta_fast, beta_slow, dim, base, orig)
    inv_freq_extrapolation_factor = 1 - linear_ramp_factor(low, high,
                                                           dim // 2)
    return (inv_freq_interpolation * (1 - inv_freq_extrapolation_factor)
            + inv_freq_extrapolation * inv_freq_extrapolation_factor)


@pytest.mark.parametrize("name", ["full", "smoke"])
def test_yarn_frequencies_match_transformers(name):
    arch = (get_arch if name == "full" else get_smoke)("mellum2-12b-a2.5b")
    a, y = arch.attn, arch.attn.yarn
    want = _yarn_transcribed(a.head_dim, a.rope_theta, y.factor,
                             y.original_max_position_embeddings,
                             y.beta_fast, y.beta_slow)
    np.testing.assert_allclose(yarn_freqs(a.head_dim, a.rope_theta, y), want,
                               rtol=1e-6)
    # the high frequencies extrapolate, the low ones interpolate (÷ factor)
    plain = 1.0 / a.rope_theta ** (np.arange(0, a.head_dim, 2) / a.head_dim)
    np.testing.assert_allclose(want[0], plain[0], rtol=1e-6)
    np.testing.assert_allclose(want[-1], plain[-1] / y.factor, rtol=1e-6)


def test_yarn_scales_q_and_k_by_attention_factor():
    """At position 0 the rotation is the identity: what is left is cos
    times ``attention_factor``; sliding layers keep plain RoPE."""
    arch = get_arch("mellum2-12b-a2.5b")
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 3, 2, 128))
    pos = jnp.zeros((1, 3), jnp.int32)
    out = apply_rope(x, pos, 5e5, "full", yarn=arch.attn.yarn)
    np.testing.assert_allclose(out, x * arch.attn.yarn.attention_factor,
                               rtol=1e-6)
    assert tfm._layer_attn(arch, "attn_local").yarn is None
    assert tfm._layer_attn(arch, "attn").yarn == arch.attn.yarn


# -- DMS in sliding-window layers ---------------------------------------------


def _visible(cache, t, window):
    """What query t sees: the arena's live slots that the attention's window
    mask (``attention._masked_decode``: pos > t - window) lets through."""
    pos, valid = np.asarray(cache.pos[0, 0]), np.asarray(cache.valid[0, 0])
    return set(pos[valid & (pos > t - window)].tolist()), set(
        pos[valid].tolist())


@pytest.mark.parametrize("policy", ["dms", "vanilla"])
def test_window_boundary(policy):
    """Key j is visible to query i iff 0 <= i - j < W (transformers'
    sliding mask): at i - j = W - 1 yes, at W no.  The DMS arena also drops
    the slot of position i - W and returns it to the free ring; the vanilla
    ring's W + 1 slots hold one position past the window, hidden by the
    mask."""
    w = 8
    arch = get_smoke("mellum2-12b-a2.5b")
    arch = arch.__class__(**{**arch.__dict__, "dms": arch.dms.__class__(
        enabled=True, window=3)})
    cache = policy_lib.init_policy_cache(
        arch, 1, 64, KVPolicyConfig(kind=policy, cr=1.0), layer_kind="attn_local",
        layer_window=w, dtype=jnp.float32).cache
    for t in range(30):
        kv = jnp.full((1, 2, 1, 16), float(t))
        cache = cache.step(kv, kv, jnp.zeros((1, 2), bool))
        vis, stored = _visible(cache, t, w)
        assert vis == set(range(max(0, t - w + 1), t + 1)), t
        assert t - (w - 1) in vis or t < w - 1
        assert t - w not in vis
        if policy == "dms":
            assert stored == vis                     # freed, not just hidden
            assert int(cache.free_count[0, 0]) == cache.slots - len(stored)
        else:
            assert cache.slots == w + 1 and stored - vis <= {t - w}


def test_windowed_dms_keeps_only_what_the_window_and_dms_allow():
    """With eviction marks, a token leaves at its DMS eviction (delay
    steps after it entered) or as it leaves the window, whichever is first,
    and the arena never overflows."""
    w, delay = 12, 4
    rng = np.random.default_rng(0)
    alpha = rng.random(80) < 0.6
    cache = SlotDMSCache.init(1, 1, 20, 4, delay, jnp.float32, attn_window=w)
    for t in range(80):
        kv = jnp.full((1, 1, 1, 4), float(t))
        cache = cache.step(kv, kv, jnp.asarray([[alpha[t]]]))
        pos, valid = np.asarray(cache.pos[0, 0]), np.asarray(cache.valid[0, 0])
        want = {j for j in range(max(0, t - w + 1), t + 1)
                if not (alpha[j] and t - j >= delay)}
        assert set(pos[valid].tolist()) == want, t
    assert not bool(cache.overflowed.any())


def test_windowed_prefill_import_matches_decode():
    """A windowed DMS cache built from a prompt holds what decoding the
    prompt token by token leaves: nothing at or below t - W, and DMS's
    pending decisions; the two stay equal through further decode."""
    w, delay, t0 = 8, 3, 20
    arch = get_smoke("mellum2-12b-a2.5b")
    arch = arch.__class__(**{**arch.__dict__, "dms": arch.dms.__class__(
        enabled=True, window=delay)})
    cfg = KVPolicyConfig(kind="dms", cr=2.0)
    alpha = np.random.default_rng(1).random(t0 + 15) < 0.5
    stepped = policy_lib.init_policy_cache(
        arch, 1, 64, cfg, layer_kind="attn_local", layer_window=w,
        dtype=jnp.float32).cache
    kv = jnp.arange(t0, dtype=jnp.float32)[None, None, :, None] * jnp.ones(
        (1, 2, 1, 16))
    for t in range(t0):
        stepped = stepped.step(kv[:, :, t:t + 1], kv[:, :, t:t + 1],
                               jnp.full((1, 2), bool(alpha[t])))
    done = np.arange(t0) <= t0 - 1 - delay           # decisions executed
    retained = jnp.broadcast_to(jnp.asarray(~(alpha[:t0] & done)), (1, 2, t0))
    imported = policy_lib.get_policy("dms").prefill_import(
        arch, cfg, kv, kv, jnp.arange(t0, dtype=jnp.int32), retained,
        jnp.broadcast_to(jnp.asarray(alpha[:t0]), (1, 2, t0)), max_len=64,
        layer_window=w, dtype=jnp.float32)
    assert imported.slots == stepped.slots

    def live(c):
        pos, valid = np.asarray(c.pos[0, 0]), np.asarray(c.valid[0, 0])
        return set(pos[valid].tolist())

    for t in range(t0, t0 + 15):
        assert live(imported) == live(stepped), t
        assert min(live(imported)) > t - 1 - w
        x = jnp.full((1, 2, 1, 16), float(t))
        a = jnp.full((1, 2), bool(alpha[t]))
        imported, stepped = imported.step(x, x, a), stepped.step(x, x, a)
    assert live(imported) == live(stepped)


def test_full_layers_keep_no_window():
    arch = get_arch("mellum2-12b-a2.5b")
    state = jax.eval_shape(lambda: tfm.init_decode_state(
        arch, 2, 4608, KVPolicyConfig(kind="dms", cr=8.0, block_p=16)))
    caches = list(policy_lib.iter_policy_caches(state))
    assert [c.cache.slots for c in caches] == [400, 400, 400, 848]
    assert [c.cache.attn_window for c in caches] == [1024, 1024, 1024, 0]
