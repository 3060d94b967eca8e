"""Per-architecture smoke tests (deliverable f): reduced same-family configs,
one forward/train step on CPU, output shapes + no NaNs; decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, PAPER_ARCHS, get_arch, get_smoke
from repro.core.config import KVPolicyConfig
from repro.models import transformer as tfm
from repro.launch import steps as steps_lib
from repro.optim import adamw


def _batch_for(arch, b=2, t=32, key=None):
    key = key or jax.random.PRNGKey(0)
    kwargs = {}
    tokens = jax.random.randint(key, (b, t), 0, arch.vocab_size)
    if arch.frontend == "vision_patches" and arch.frontend_tokens:
        kwargs["frontend_embeds"] = jnp.zeros((b, arch.frontend_tokens, arch.d_model),
                                              jnp.bfloat16)
        tokens = tokens[:, : t - arch.frontend_tokens]
    if arch.encoder_layers:
        kwargs["enc_embeds"] = jax.random.normal(key, (b, 16, arch.d_model)) * 0.02
    return tokens, kwargs


@pytest.mark.parametrize("name", ASSIGNED_ARCHS)
def test_smoke_forward(name):
    arch = get_smoke(name)
    params = tfm.init_model(jax.random.PRNGKey(0), arch)
    tokens, kwargs = _batch_for(arch)
    mode = "dms_train" if arch.dms.enabled else "vanilla"
    logits, aux = tfm.model_forward(params, tokens, arch, mode=mode,
                                    rng=jax.random.PRNGKey(1), **kwargs)
    b = tokens.shape[0]
    t_total = tokens.shape[1] + (arch.frontend_tokens
                                 if arch.frontend == "vision_patches" else 0)
    assert logits.shape == (b, t_total, arch.padded_vocab)
    assert not bool(jnp.isnan(logits).any()), name
    if arch.dms.enabled and arch.attn is not None:
        assert float(aux["alpha_count"]) > 0


@pytest.mark.parametrize("name", ASSIGNED_ARCHS)
def test_smoke_train_step(name):
    arch = get_smoke(name)
    params = tfm.init_model(jax.random.PRNGKey(0), arch)
    opt_state = adamw.init(params)
    step_fn = steps_lib.make_train_step(
        arch, adamw.AdamWConfig(lr=1e-3), dms_train=arch.dms.enabled)
    tokens, kwargs = _batch_for(arch)
    t_total = tokens.shape[1] + (arch.frontend_tokens
                                 if arch.frontend == "vision_patches" else 0)
    batch = {"tokens": tokens,
             "labels": jax.random.randint(jax.random.PRNGKey(2),
                                          (tokens.shape[0], t_total), 0,
                                          arch.vocab_size), **kwargs}
    p2, o2, metrics = step_fn(params, opt_state, batch, jnp.asarray(0, jnp.int32))
    assert np.isfinite(float(metrics["loss"])), name
    # params actually changed
    changed = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree_util.tree_leaves(params)[:4],
                        jax.tree_util.tree_leaves(p2)[:4]))
    assert changed, name


@pytest.mark.parametrize("name", ["phi3-mini-3.8b", "gemma2-2b",
                                  "recurrentgemma-2b", "mamba2-2.7b",
                                  "seamless-m4t-large-v2"])
def test_decode_matches_full_forward(name):
    """Teacher-forced decode == full forward (vanilla policy)."""
    arch = get_smoke(name)
    params = tfm.init_model(jax.random.PRNGKey(0), arch)
    b, t = 2, 12
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, t), 0, arch.vocab_size)
    kwargs = {}
    enc_out = None
    if arch.encoder_layers:
        enc = jax.random.normal(jax.random.PRNGKey(3), (b, 8, arch.d_model)) * 0.02
        kwargs["enc_embeds"] = enc
        enc_out = tfm.encode(params, enc, arch)
    full, _ = tfm.model_forward(params, tokens, arch, **kwargs)
    state = tfm.init_decode_state(arch, b, t, KVPolicyConfig(kind="vanilla"))
    outs = []
    for i in range(t):
        lg, state, _ = tfm.decode_step(params, tokens[:, i:i + 1], state, arch,
                                       jnp.asarray(i, jnp.int32), enc_out=enc_out)
        outs.append(lg)
    dec = jnp.stack(outs, 1)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full, np.float32), rtol=0.12, atol=0.12)


def test_dms_decode_matches_masked_reference():
    """SlotDMSCache decode == MaskedDMSCache decode for the same model."""
    arch = get_smoke("phi3-mini-3.8b")
    params = tfm.init_model(jax.random.PRNGKey(0), arch)
    b, t = 1, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, t), 0, arch.vocab_size)
    s_slot = tfm.init_decode_state(arch, b, t, KVPolicyConfig(kind="dms", cr=1.0))
    s_mask = tfm.init_decode_state(arch, b, t, KVPolicyConfig(kind="dms_masked"))
    for i in range(t):
        l1, s_slot, _ = tfm.decode_step(params, tokens[:, i:i + 1], s_slot, arch,
                                        jnp.asarray(i, jnp.int32))
        l2, s_mask, _ = tfm.decode_step(params, tokens[:, i:i + 1], s_mask, arch,
                                        jnp.asarray(i, jnp.int32))
        np.testing.assert_allclose(np.asarray(l1, np.float32),
                                   np.asarray(l2, np.float32), rtol=0.05, atol=0.05)


@pytest.mark.parametrize("name", PAPER_ARCHS)
def test_paper_archs_smoke(name):
    arch = get_smoke(name)
    params = tfm.init_model(jax.random.PRNGKey(0), arch)
    tokens, kwargs = _batch_for(arch)
    logits, _ = tfm.model_forward(params, tokens, arch, mode="dms_train",
                                  rng=jax.random.PRNGKey(1), **kwargs)
    assert not bool(jnp.isnan(logits).any())


def test_stack_layers_matches_stacking_all_at_once():
    """Leaf-by-leaf stacking (per-layer arrays dropped as it goes) builds the
    same tree, bit for bit and dtype for dtype, as stacking whole trees."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)

    def make(s):
        return {"w": jax.random.normal(keys[s], (4, 5)) * 0.02,
                "norm": {"g": jnp.full((5,), s, jnp.bfloat16)}}

    got = tfm._stack_layers(3, make)
    want = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                  *[make(s) for s in range(3)])
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("name", ASSIGNED_ARCHS)
def test_full_configs_match_assignment(name):
    """The full configs carry the exact assigned hyper-parameters."""
    a = get_arch(name)
    expect = {
        "mamba2-2.7b": dict(num_layers=64, d_model=2560, vocab_size=50280),
        "granite-moe-3b-a800m": dict(num_layers=32, d_model=1536, vocab_size=49155),
        "granite-moe-1b-a400m": dict(num_layers=24, d_model=1024, vocab_size=49155),
        "recurrentgemma-2b": dict(num_layers=26, d_model=2560, vocab_size=256000),
        "qwen2-vl-7b": dict(num_layers=28, d_model=3584, vocab_size=152064),
        "gemma2-2b": dict(num_layers=26, d_model=2304, vocab_size=256000),
        "chatglm3-6b": dict(num_layers=28, d_model=4096, vocab_size=65024),
        "phi3-mini-3.8b": dict(num_layers=32, d_model=3072, vocab_size=32064),
        "minitron-4b": dict(num_layers=32, d_model=3072, vocab_size=256000),
        "seamless-m4t-large-v2": dict(num_layers=24, d_model=1024, vocab_size=256206),
    }[name]
    for k, v in expect.items():
        assert getattr(a, k) == v, (name, k)
    heads = {
        "granite-moe-3b-a800m": (24, 8), "granite-moe-1b-a400m": (16, 8),
        "recurrentgemma-2b": (10, 1), "qwen2-vl-7b": (28, 4),
        "gemma2-2b": (8, 4), "chatglm3-6b": (32, 2),
        "phi3-mini-3.8b": (32, 32), "minitron-4b": (24, 8),
        "seamless-m4t-large-v2": (16, 16),
    }
    if a.attn is not None:
        assert (a.attn.num_heads, a.attn.num_kv_heads) == heads[name]
    if name.startswith("granite"):
        assert a.mlp.moe.top_k == 8
        assert a.mlp.moe.num_experts == (40 if "3b" in name else 32)
    if name == "mamba2-2.7b":
        assert a.ssm.d_state == 128 and a.attn is None
