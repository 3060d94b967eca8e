"""Cache semantics: slot-compacted DMS == masked reference; baselines; the
decode step's arena contract (lane-masked row writes into carried arenas)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import baselines, block_pool, kv_cache
from repro.core import policy as policy_lib
from repro.core.config import KVPolicyConfig
from repro.core.kv_cache import MaskedDMSCache, SlotDMSCache, VanillaCache
from repro.models import transformer as tfm


def _stream(seed, t, b=1, h=2, dh=4, p_evict=0.5):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    k = jax.random.normal(ks[0], (t, b, h, 1, dh))
    v = jax.random.normal(ks[1], (t, b, h, 1, dh))
    a = jax.random.bernoulli(ks[2], p_evict, (t, b, h))
    return k, v, a


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 1000), st.integers(1, 6), st.integers(4, 24),
       st.floats(0.0, 0.95))
def test_slot_cache_equals_masked_cache(seed, w, t, p_evict):
    """Property: for any decision stream, the physically-compacted cache
    retains exactly the same (position) set as the masked oracle."""
    k, v, a = _stream(seed, t, p_evict=p_evict)
    mc = MaskedDMSCache.init(1, 2, t, 4, w)
    sc = SlotDMSCache.init(1, 2, t + 1, 4, w)     # ample arena: no overflow
    for i in range(t):
        mc = mc.step(k[i], v[i], a[i])
        sc = sc.step(k[i], v[i], a[i])
    assert (mc.retained_tokens() == sc.retained_tokens()).all()
    for b in range(1):
        for h in range(2):
            mpos = set(np.where(np.asarray(mc.valid_mask()[b, h]))[0].tolist())
            spos = set(np.asarray(sc.pos[b, h])[np.asarray(sc.valid[b, h])].tolist())
            assert mpos == spos
    assert not bool(sc.overflowed.any())


def test_slot_cache_kv_content_preserved():
    t, w = 12, 3
    k, v, a = _stream(7, t)
    sc = SlotDMSCache.init(1, 2, t + 1, 4, w)
    for i in range(t):
        sc = sc.step(k[i], v[i], a[i])
    for h in range(2):
        valid = np.asarray(sc.valid[0, h])
        pos = np.asarray(sc.pos[0, h])[valid]
        kv = np.asarray(sc.k[0, h])[valid]
        for p, row in zip(pos, kv):
            np.testing.assert_allclose(row, np.asarray(k[p, 0, h, 0]), rtol=1e-2)


def test_slot_cache_overflow_recycles_oldest():
    """Arena smaller than the stream with alpha=0: ring-buffer semantics."""
    t, p = 10, 4
    k, v, _ = _stream(3, t)
    a0 = jnp.zeros((t, 1, 2), bool)
    sc = SlotDMSCache.init(1, 2, p, 4, 2)
    for i in range(t):
        sc = sc.step(k[i], v[i], a0[i])
    assert bool(sc.overflowed.all())
    pos = np.sort(np.asarray(sc.pos[0, 0])[np.asarray(sc.valid[0, 0])])
    np.testing.assert_array_equal(pos, np.arange(t - p, t))   # newest P retained


def test_memory_saving_at_target_cr():
    """The provisioned arena is ~S/CR + w — the physical memory claim."""
    slots = SlotDMSCache.provision_slots(4096, cr=8.0, window=256)
    assert slots < 4096 * 0.2
    assert slots >= 4096 // 8 + 256


def test_vanilla_cache_append_and_mask():
    c = VanillaCache.init(2, 2, 8, 4)
    k = jnp.ones((2, 2, 3, 4))
    c = c.append(k, k)
    np.testing.assert_array_equal(np.asarray(c.length), [3, 3])  # per-lane
    m = np.asarray(c.valid_mask())[0, 0]
    np.testing.assert_array_equal(m, [1, 1, 1, 0, 0, 0, 0, 0])


def test_tova_evicts_lowest_weight():
    c = baselines.TOVACache.init(1, 1, budget=3 + 1, head_dim=2)
    k = jnp.ones((1, 1, 1, 2))
    for i in range(4):
        c = c.insert(k * i, k * i)
        w = jnp.ones((1, 1, 4))
        if i == 3:
            w = w.at[0, 0, 1].set(0.01)      # slot 1 = weakest
            c = c.evict(w)
        else:
            c = c.evict(w * 0 + jnp.arange(4) + 1.0)
    valid = np.asarray(c.valid[0, 0])
    assert valid.sum() == 3
    assert not valid[1]


def test_h2o_protects_recent_window():
    c = baselines.H2OCache.init(1, 1, budget=4 + 1, head_dim=2, recent_window=2)
    k = jnp.ones((1, 1, 1, 2))
    for i in range(6):
        c = c.insert(k, k)
        w = jnp.ones((1, 1, 5)) * 0.2
        c = c.evict(w)
    pos = np.asarray(c.pos[0, 0])[np.asarray(c.valid[0, 0])]
    # the two most recent tokens are always alive
    assert {4, 5}.issubset(set(pos.tolist()))


def test_quest_selects_relevant_pages():
    page, top = 4, 1
    c = baselines.QuestCache.init(1, 1, 16, 4, page, top)
    key = jax.random.PRNGKey(0)
    for i in range(16):
        val = jnp.ones((1, 1, 1, 4)) * (10.0 if 8 <= i < 12 else 0.1)
        c = c.append(val, val)
    q = jnp.ones((1, 1, 4))
    pages = np.asarray(c.select_pages(q))[0, 0]
    assert pages[2] and pages.sum() == 1          # page 2 = tokens 8..11
    # memory footprint is full (Quest trades memory for reads)
    assert int(c.retained_tokens()[0, 0]) == 16
    assert int(c.reads_per_step()[0]) == top * page


def test_dmc_merges_with_weighted_average():
    c = baselines.DMCCache.init(1, 1, 4, 2)
    one = jnp.ones((1, 1, 1, 2))
    c = c.step(one * 2.0, one * 2.0, jnp.zeros((1, 1), bool))   # append [2]
    c = c.step(one * 4.0, one * 4.0, jnp.ones((1, 1), bool))    # merge -> 3
    assert int(c.count[0, 0]) == 1
    np.testing.assert_allclose(np.asarray(c.k[0, 0, 0]), [3.0, 3.0], rtol=1e-6)
    c = c.step(one * 9.0, one * 9.0, jnp.zeros((1, 1), bool))   # append
    assert int(c.count[0, 0]) == 2


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 500))
def test_slot_cache_under_jit_and_scan(seed):
    """The cache must be scan/jit transparent (registered pytree)."""
    t, w = 8, 2
    k, v, a = _stream(seed, t)
    sc = SlotDMSCache.init(1, 2, t + 1, 4, w)

    def body(c, xs):
        kk, vv, aa = xs
        return c.step(kk, vv, aa), c.retained_tokens()

    final, _ = jax.jit(lambda c: jax.lax.scan(body, c, (k, v, a)))(sc)
    ref = sc
    for i in range(t):
        ref = ref.step(k[i], v[i], a[i])
    assert (final.retained_tokens() == ref.retained_tokens()).all()


# ---------------------------------------------------------------------------
# The decode step's arena contract: every fixed-arena K/V write is a
# lane-masked row write into the layer-stacked arena the scan carries
# ---------------------------------------------------------------------------

LANES, MAX_LEN = 3, 32


def _policy_state(arch, policy, paged):
    cfg = KVPolicyConfig(kind=policy, cr=2.0, window=4, block_p=8,
                         paged=paged, quest_page_size=8, quest_top_pages=2)
    return tfm.init_decode_state(arch, LANES, MAX_LEN, cfg)


def _stepper(params, arch, use_kernel=False, scan_layers=True):
    return jax.jit(lambda s, tok, pos, act: tfm.decode_step(
        params, tok, s, arch, pos, use_kernel=use_kernel, active=act,
        scan_layers=scan_layers))


def _lane_leaves(state):
    """Every per-lane leaf of the decode state (lane axis 1), with a paged
    cache's K/V as the dense view each lane's page map reaches."""
    out = []
    for pc in policy_lib.iter_policy_caches(state):
        c = pc.cache
        out += jax.tree_util.tree_leaves(dataclasses.replace(c, pool=None))
        if c.pool is not None:
            out += list(jax.vmap(block_pool.dense_kv)(c.pool, c.phys))
    return out


@pytest.mark.parametrize("paged", [False, True], ids=["fixed", "paged"])
@pytest.mark.parametrize("policy", policy_lib.available_policies())
def test_decode_step_leaves_inactive_lanes_unchanged(tiny_arch, tiny_params,
                                                     policy, paged):
    """After a few steps with every lane live, one step under a random
    lane mask leaves every leaf of the inactive lanes bit for bit as it
    was: the K/V rows they would have written are never written, and no
    whole-arena select is left to roll them back."""
    rng = np.random.default_rng(sum(map(ord, policy)) + paged)
    step = _stepper(tiny_params, tiny_arch)
    state = _policy_state(tiny_arch, policy, paged)
    pos = np.zeros(LANES, np.int32)
    tok = lambda: jnp.asarray(rng.integers(0, tiny_arch.vocab_size,
                                           (LANES, 1)), jnp.int32)
    for _ in range(5):
        _, state, _ = step(state, tok(), jnp.asarray(pos),
                           jnp.ones(LANES, bool))
        pos += 1
    active = rng.permutation([True, False] + [bool(rng.integers(2))]
                             * (LANES - 2))
    _, new, _ = step(state, tok(), jnp.asarray(pos), jnp.asarray(active))
    for old, cur in zip(_lane_leaves(state), _lane_leaves(new)):
        np.testing.assert_array_equal(np.asarray(cur)[:, ~active],
                                      np.asarray(old)[:, ~active])
    lengths = [np.asarray(pc.length)[0] for pc in
               policy_lib.iter_policy_caches(new)]
    assert all((n == pos + active).all() for n in lengths)


def _where_rows(arena, slot, rows, mask):
    """The token write of the per-layer route: a select over the whole
    arena, every lane written (the lane freeze rolls inactive lanes back)."""
    hit = jnp.arange(arena.shape[2]) == slot[..., :1]          # (B, H, P)
    return jnp.where(hit[..., None], rows[:, :, :1].astype(arena.dtype),
                     arena)


@pytest.mark.parametrize("policy,use_kernel,scan_layers", [
    (p, False, True) for p in policy_lib.available_policies()] + [
    ("dms", True, True), ("tova", True, True), ("dms", False, False),
    ("vanilla", False, False)])
def test_carried_arenas_match_per_layer_step(tiny_arch, tiny_params, policy,
                                             use_kernel, scan_layers,
                                             monkeypatch):
    """Over several steps under random lane masks, the decode step that
    carries the layer-stacked arenas through the scan and writes rows in
    place gives the active lanes bit-for-bit the logits, and the whole
    state bit-for-bit the contents, of the per-layer route: each layer's
    arena sliced into the scan, written by a whole-arena select, stacked
    back, and every leaf frozen by the lane select.  ``scan_layers=False``
    (layers unrolled) takes the same path."""
    rng = np.random.default_rng(7)
    steps = 6
    toks = rng.integers(0, tiny_arch.vocab_size, (steps, LANES, 1))
    masks = rng.random((steps, LANES)) < 0.7
    masks[0], masks[-1, 1] = True, False   # the last step leaves a lane out
    state0 = _policy_state(tiny_arch, policy, False)

    def run():
        step = _stepper(tiny_params, tiny_arch, use_kernel, scan_layers)
        state, pos, logits = state0, np.zeros(LANES, np.int32), []
        for tok, act in zip(toks, masks):
            out, state, _ = step(state, jnp.asarray(tok, jnp.int32),
                                 jnp.asarray(pos), jnp.asarray(act))
            logits.append(np.asarray(out)[act])
            pos += act
        return logits, jax.tree_util.tree_leaves(state)

    carried = run()
    monkeypatch.setattr(tfm, "_split_arenas", lambda s: (s, {}))
    for mod in (kv_cache, baselines):
        monkeypatch.setattr(mod, "write_rows", _where_rows)
    per_layer = run()
    for got, want in zip(carried[0] + carried[1],
                         per_layer[0] + per_layer[1]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

