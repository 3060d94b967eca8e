"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.dms_attention import ops as fops
from repro.kernels.dms_attention import ref as fref
from repro.kernels.dms_decode import ops as dops
from repro.kernels.dms_decode import ref as dref

SHAPES = [
    # (B, T, Hq, Hkv, Dh)
    (1, 16, 2, 1, 8),
    (2, 48, 4, 2, 16),
    (1, 64, 8, 2, 32),
    (2, 33, 6, 3, 8),       # non-divisible T (padding path)
    (1, 24, 8, 1, 16),      # deep GQA: 8 query heads share one kv head
    (1, 40, 12, 2, 8),      # 6:1 group ratio
    (1, 37, 4, 2, 16),      # odd T, no block divides it
    (2, 51, 6, 1, 8),       # odd T + MQA
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _inputs(shape, dtype, seed=0):
    b, t, hq, hkv, dh = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, t, hq, dh), dtype)
    k = jax.random.normal(ks[1], (b, t, hkv, dh), dtype)
    v = jax.random.normal(ks[2], (b, t, hkv, dh), dtype)
    alpha = jax.random.uniform(ks[3], (b, hkv, t), jnp.float32, 0.02, 0.9)
    return q, k, v, alpha


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_fwd_matches_ref(shape, dtype):
    q, k, v, alpha = _inputs(shape, dtype)
    out = fops.dms_flash_attention(q, k, v, alpha, dms_window=4,
                                   block_q=16, block_k=16)
    ref = fref.dms_attention_ref(q, k, v, jnp.log1p(-alpha), dms_window=4)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window,cap", [(None, None), (16, None), (None, 30.0),
                                        (8, 50.0)])
def test_flash_fwd_window_softcap(window, cap):
    q, k, v, alpha = _inputs((2, 48, 4, 2, 16), jnp.float32)
    out = fops.dms_flash_attention(q, k, v, alpha, dms_window=4, window=window,
                                   logit_cap=cap, block_q=16, block_k=16)
    ref = fref.dms_attention_ref(q, k, v, jnp.log1p(-alpha), dms_window=4,
                                 window=window, logit_cap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_vanilla_no_alpha():
    q, k, v, _ = _inputs((2, 32, 4, 2, 16), jnp.float32)
    out = fops.dms_flash_attention(q, k, v, None, block_q=16, block_k=16)
    ref = fref.dms_attention_ref(q, k, v, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("seed", [0, 1])
def test_flash_bwd_matches_autodiff(seed):
    q, k, v, alpha = _inputs((1, 32, 4, 2, 16), jnp.float32, seed)
    tgt = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def loss_k(q, k, v, a):
        o = fops.dms_flash_attention(q, k, v, a, dms_window=4,
                                     block_q=16, block_k=16)
        return jnp.sum(o * tgt)

    def loss_r(q, k, v, a):
        o = fref.dms_attention_ref(q, k, v, jnp.log1p(-a), dms_window=4)
        return jnp.sum(o * tgt)

    gk = jax.grad(loss_k, argnums=(0, 1, 2, 3))(q, k, v, alpha)
    gr = jax.grad(loss_r, argnums=(0, 1, 2, 3))(q, k, v, alpha)
    for name, a, b in zip("q k v alpha".split(), gk, gr):
        rel = float(jnp.max(jnp.abs(a - b))) / (float(jnp.max(jnp.abs(b))) + 1e-9)
        assert rel < 1e-4, (name, rel)


def test_flash_skip_blocks_binary_alpha():
    """Dead-block skipping must be exact for binarised decisions."""
    b, t, hq, hkv, dh = 1, 64, 2, 1, 8
    q, k, v, _ = _inputs((b, t, hq, hkv, dh), jnp.float32)
    alpha_bin = jnp.zeros((b, hkv, t), bool).at[:, :, 4:40].set(True)
    out = fops.dms_flash_attention_prefill(q, k, v, alpha_bin, dms_window=8,
                                           block_q=16, block_k=16)
    ls = jnp.maximum(jnp.log1p(-alpha_bin.astype(jnp.float32)), -1e30)
    ref = fref.dms_attention_ref(q, k, v, ls, dms_window=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", [(2, 4, 2, 40, 16), (1, 8, 1, 100, 32),
                                   (3, 6, 3, 24, 8), (2, 8, 4, 17, 8),
                                   (1, 8, 1, 23, 16),    # deep GQA, odd P
                                   (2, 12, 2, 19, 8),    # 6:1 groups, odd P
                                   (1, 16, 2, 37, 32)])  # wide groups
@pytest.mark.parametrize("dtype", DTYPES)
def test_decode_kernel_matches_ref(shape, dtype):
    b, hq, hkv, p, dh = shape
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (b, 1, hq, dh), dtype)
    k = jax.random.normal(ks[1], (b, hkv, p, dh), dtype)
    v = jax.random.normal(ks[2], (b, hkv, p, dh), dtype)
    valid = jax.random.bernoulli(ks[3], 0.6, (b, hkv, p)).at[:, :, 0].set(True)
    out = dops.dms_decode_attention(q, k, v, valid, block_p=16)
    ref = dref.dms_decode_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_decode_kernel_softcap():
    b, hq, hkv, p, dh = 1, 4, 2, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (b, 1, hq, dh))
    k = jax.random.normal(ks[1], (b, hkv, p, dh))
    v = jax.random.normal(ks[2], (b, hkv, p, dh))
    valid = jnp.ones((b, hkv, p), bool)
    out = dops.dms_decode_attention(q, k, v, valid, logit_cap=30.0, block_p=16)
    ref = dref.dms_decode_ref(q, k, v, valid, logit_cap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_decode_kernel_all_blocks_dead_but_one():
    """Block-level liveness: only one live slot far into the arena."""
    b, hq, hkv, p, dh = 1, 2, 1, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, 1, hq, dh))
    k = jax.random.normal(ks[1], (b, hkv, p, dh))
    v = jax.random.normal(ks[2], (b, hkv, p, dh))
    valid = jnp.zeros((b, hkv, p), bool).at[:, :, 50].set(True)
    out = dops.dms_decode_attention(q, k, v, valid, block_p=16)
    ref = dref.dms_decode_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# (B, Hq, Hkv, P, Dh), optionally with the case's options: `bp` block_p
# (C = 128 // bp entries a pass), `n` per-(lane, head) live counts over an
# unordered table, `paged`, `weights`, `cap`
_BT = (1, 4, 2, 256, 16)
BLOCK_TABLE_CASES = [
    (2, 4, 2, 64, 16), (1, 8, 1, 96, 32), (2, 12, 2, 32, 8),
    pytest.param(_BT + (dict(n=[[0, 16]]),), id="n0-and-full"),
    pytest.param(_BT + (dict(n=[[11, 3]]),), id="n-off-pass"),
    pytest.param((1, 4, 2, 208, 16, dict(n=[[13, 9]])), id="n-full-table-off-pass"),
    pytest.param(_BT + (dict(n=[[12, 7]], paged=True),), id="paged"),
    pytest.param(_BT + (dict(n=[[12, 0]], weights=True),), id="weights"),
    pytest.param(_BT + (dict(n=[[12, 7]], paged=True, weights=True),),
                 id="paged-weights"),
    pytest.param(_BT + (dict(n=[[12, 7]], cap=1.5),), id="logit-cap"),
    pytest.param(_BT + (dict(bp=128, n=[[2, 1]]),), id="c1"),
    pytest.param(_BT + (dict(bp=64, n=[[3, 4]]),), id="c2"),
    pytest.param(_BT + (dict(bp=8, n=[[21, 32]]),), id="c16"),
]


def _unordered_table(key, b, hkv, nb, n):
    """Per head a random order of every block, the first ``n`` listed: what
    swap-removal leaves (unordered, stale ids past ``n``)."""
    keys = jax.random.split(key, b * hkv)
    tbl = jnp.stack([jax.random.permutation(k_, nb) for k_ in keys])
    tbl = tbl.reshape(b, hkv, nb).astype(jnp.int32)
    n = jnp.asarray(n, jnp.int32)
    listed = jnp.zeros((b, hkv, nb), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(hkv)[None, :, None],
        tbl].set(jnp.arange(nb)[None, None] < n[..., None])
    return tbl, n, listed


def _paged_args(key, k, v, bp):
    """Scatter the arena's blocks to random pages of one shared pool."""
    b, hkv, p, dh = k.shape
    nb = p // bp
    phys = jax.random.permutation(key, b * hkv * nb).astype(jnp.int32)
    pool = lambda a: jnp.zeros((b * hkv * nb, bp, dh), a.dtype).at[phys].set(
        a.reshape(-1, bp, dh))
    return dict(pool_k=pool(k), pool_v=pool(v), phys=phys.reshape(b, hkv, nb))


def _ref_weights(q, k, vis, cap=None):
    """Post-softmax weights over the visible slots, summed over each kv
    head's query group: (B, Hkv, P)."""
    b, _, hq, dh = q.shape
    hkv = k.shape[1]
    qg = q[:, 0].reshape(b, hkv, hq // hkv, dh).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhpd->bhgp", qg, k.astype(jnp.float32)) * dh ** -0.5
    if cap is not None:
        s = cap * jnp.tanh(s / cap)
    s = jnp.where(vis[:, :, None], s, -1e30)
    return jnp.sum(jax.nn.softmax(s, axis=-1) * vis[:, :, None], axis=2)


@pytest.mark.parametrize("shape", BLOCK_TABLE_CASES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_decode_kernel_block_table_mode(shape, dtype):
    """Explicit block-table mode (the policy step path): fragmented valid,
    compacted table — same output as the oracle, no pad/derive in the
    wrapper.  The cases walk every shape of a head's walk: no live block,
    a last pass part full, a full table, unordered tables, the paged pool,
    weights out, a logit cap, and 1, 2, 8 and 16 blocks a pass."""
    from repro.core.kv_cache import BlockTable
    opts = shape[5] if len(shape) == 6 else {}
    b, hq, hkv, p, dh = shape[:5]
    bp = opts.get("bp", 16)
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    q = jax.random.normal(ks[0], (b, 1, hq, dh), dtype)
    k = jax.random.normal(ks[1], (b, hkv, p, dh), dtype)
    v = jax.random.normal(ks[2], (b, hkv, p, dh), dtype)
    valid = jax.random.bernoulli(ks[3], 0.4, (b, hkv, p)).at[:, :, 0].set(True)
    if "n" in opts:
        tbl, n, listed = _unordered_table(ks[4], b, hkv, p // bp, opts["n"])
        vis = valid & jnp.repeat(listed, bp, axis=2)
    else:
        bt = BlockTable.from_valid(valid, bp)
        tbl, n, vis = bt.tbl, bt.n, valid
    extra = _paged_args(ks[5], k, v, bp) if opts.get("paged") else {}
    cap = opts.get("cap")
    res = dops.dms_decode_attention(
        q, k, v, valid, block_tbl=tbl, block_n=n, block_p=bp, logit_cap=cap,
        need_weights=opts.get("weights", False), **extra)
    out, weights = res if opts.get("weights") else (res, None)
    ref = dref.dms_decode_ref(q, k, v, vis, logit_cap=cap)
    # a head with no live block attends to nothing: the kernel writes zeros
    ref = jnp.where(jnp.any(vis, axis=-1)[:, None, :, None, None],
                    ref.reshape(b, 1, hkv, -1, dh), 0).reshape(ref.shape)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))
    if weights is not None:
        np.testing.assert_allclose(
            np.asarray(weights), np.asarray(_ref_weights(q, k, vis, cap)),
            **_tol(dtype))


@pytest.mark.parametrize("mode", ["fixed", "paged", "weights"])
def test_decode_kernel_never_reads_unlisted_blocks(mode):
    """K/V of every block the table does not list — the entries past ``n``
    included — is NaN and marked valid: the output stays finite and equal
    to the oracle over the listed blocks, so the kernel copies exactly the
    blocks its table lists, whatever its grid."""
    b, hq, hkv, p, dh, bp = 2, 4, 2, 256, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(ks[0], (b, 1, hq, dh))
    k = jax.random.normal(ks[1], (b, hkv, p, dh))
    v = jax.random.normal(ks[2], (b, hkv, p, dh))
    tbl, n, listed = _unordered_table(ks[3], b, hkv, p // bp,
                                      [[5, 13], [16, 1]])
    dead = ~jnp.repeat(listed, bp, axis=2)[..., None]
    k_nan, v_nan = jnp.where(dead, jnp.nan, k), jnp.where(dead, jnp.nan, v)
    valid = jnp.ones((b, hkv, p), bool)
    vis = valid & ~dead[..., 0]
    extra = _paged_args(ks[4], k_nan, v_nan, bp) if mode == "paged" else {}
    res = dops.dms_decode_attention(
        q, k_nan, v_nan, valid, block_tbl=tbl, block_n=n, block_p=bp,
        need_weights=mode == "weights", **extra)
    out, weights = res if mode == "weights" else (res, None)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dref.dms_decode_ref(q, k, v, vis)),
        rtol=2e-5, atol=2e-5)
    if weights is not None:
        np.testing.assert_allclose(np.asarray(weights),
                                   np.asarray(_ref_weights(q, k, vis)),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("weights", [False, True], ids=["plain", "weights"])
def test_decode_kernel_reads_layer_of_stacked_arena(weights):
    """Given the layer-stacked arena (L, B, Hkv, P, Dh) and a traced layer
    index, the kernel reads that layer in place (its rows sit at
    ``layer·B·Hkv + h``): output and weights equal the kernel on the
    layer's own slice, bit for bit, for every layer."""
    from repro.core.kv_cache import BlockTable
    n_layers, b, hq, hkv, p, dh, bp = 3, 2, 4, 2, 64, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    q = jax.random.normal(ks[0], (b, 1, hq, dh))
    k = jax.random.normal(ks[1], (n_layers, b, hkv, p, dh))
    v = jax.random.normal(ks[2], (n_layers, b, hkv, p, dh))
    valid = jax.random.bernoulli(ks[3], 0.4, (b, hkv, p)).at[:, :, 0].set(True)
    bt = BlockTable.from_valid(valid, bp)
    kw = dict(block_tbl=bt.tbl, block_n=bt.n, block_p=bp, need_weights=weights)
    stacked = jax.jit(lambda layer: dops.dms_decode_attention(
        q, k, v, valid, layer=layer, **kw))
    sliced = jax.jit(lambda k_l, v_l: dops.dms_decode_attention(
        q, k_l, v_l, valid, **kw))
    for layer in range(n_layers):
        got = stacked(jnp.int32(layer))
        want = sliced(k[layer], v[layer])
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_decode_kernel_partial_table_page_sparse():
    """A table listing only SOME live blocks (Quest top-k pages): the kernel
    must attend exactly to the listed blocks' visible slots."""
    from repro.core.kv_cache import BlockTable
    b, hq, hkv, p, dh, bp = 1, 4, 2, 64, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (b, 1, hq, dh))
    k = jax.random.normal(ks[1], (b, hkv, p, dh))
    v = jax.random.normal(ks[2], (b, hkv, p, dh))
    page_mask = jnp.zeros((b, hkv, p // bp), bool).at[:, :, ::2].set(True)
    vis = jnp.repeat(page_mask, bp, axis=2)           # selected pages only
    bt = BlockTable.from_valid(vis, bp)
    out = dops.dms_decode_attention(q, k, v, vis, block_tbl=bt.tbl,
                                    block_n=bt.n, block_p=bp)
    ref = dref.dms_decode_ref(q, k, v, vis)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_decode_kernel_table_mode_rejects_unpadded():
    with pytest.raises(ValueError, match="not a multiple"):
        b, hkv, p, dh = 1, 1, 20, 8
        q = jnp.zeros((b, 1, 2, dh))
        k = jnp.zeros((b, hkv, p, dh))
        dops.dms_decode_attention(
            q, k, k, jnp.ones((b, hkv, p), bool),
            block_tbl=jnp.zeros((b, hkv, 2), jnp.int32),
            block_n=jnp.ones((b, hkv), jnp.int32), block_p=16)


def test_chunked_impls_match_kernel():
    """The dry-run lowering paths agree with the Pallas kernel."""
    from repro.models.attention import attention_chunked, attention_chunked_scan
    q, k, v, alpha = _inputs((2, 40, 4, 2, 16), jnp.float32)
    ker = fops.dms_flash_attention(q, k, v, alpha, dms_window=4,
                                   block_q=16, block_k=16)
    ch = attention_chunked(q, k, v, alpha, dms_delay=4, chunk_q=16, chunk_k=16)
    cs = attention_chunked_scan(q, k, v, alpha, dms_delay=4)
    np.testing.assert_allclose(np.asarray(ch), np.asarray(ker), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(cs), np.asarray(ker), rtol=2e-5, atol=2e-5)


def test_scheduler_smoke_with_kernel(tiny_arch, tiny_params):
    """End-to-end: continuous-batching serve (chunked prefill + decode)
    through the Pallas decode kernel (interpret mode on CPU) — and greedy
    generations match the pure-jnp reference decode path."""
    from repro.core.config import KVPolicyConfig
    from repro.serving.engine import Engine

    prompts = np.random.default_rng(5).integers(
        3, tiny_arch.vocab_size, size=(2, 11)).astype(np.int32)
    cfg = KVPolicyConfig(kind="dms", cr=2.0, window=tiny_arch.dms.window)
    res_k = Engine(tiny_arch, tiny_params, cfg,
                   use_kernel=True).generate(prompts, 5)
    assert res_k.tokens.shape == (2, 5)
    assert np.isfinite(res_k.meter.kv_reads)
    assert res_k.meter.peak_tokens > 0
    res_r = Engine(tiny_arch, tiny_params, cfg).generate(prompts, 5)
    np.testing.assert_array_equal(res_k.tokens, res_r.tokens)
