"""Hybrid sliding/full GQA decoders with sparse experts in every layer
(Mellum2-style) in the program, as one chip of an expert-parallel
deployment: the ``ArchConfig`` and ``KVPolicyConfig`` a configuration file
describes, and the benchmark's seeded weights laid out as the program's
parameter tree, in bfloat16 as the checkpoint stores them."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from perfbench import weights
from perfbench.references import moe_hybrid_gqa as ref
from repro.core.config import (ArchConfig, AttentionConfig, DMSConfig,
                               KVPolicyConfig, MLPConfig, MoEConfig,
                               YarnConfig)

_KINDS = {"sliding_attention": "attn_local", "full_attention": "attn"}


def layer_pattern(hf: dict) -> tuple:
    """The shortest period of ``hf["layer_types"]``, as program layer
    kinds."""
    kinds = [_KINDS[t] for t in hf["layer_types"]]
    n = len(kinds)
    for p in range(1, n + 1):
        if n % p == 0 and kinds == kinds[:p] * (n // p):
            return tuple(kinds[:p])
    raise AssertionError("unreachable")


def program_arch(cfg: dict) -> ArchConfig:
    hf, dms = cfg["hf"], cfg.get("dms", {})
    rope = hf["rope_parameters"]
    full, slide = rope["full_attention"], rope["sliding_attention"]
    if full["rope_theta"] != slide["rope_theta"]:
        raise ValueError("one rope_theta for both layer kinds")
    return ArchConfig(
        name=cfg["name"],
        num_layers=hf["num_hidden_layers"],
        d_model=hf["hidden_size"],
        vocab_size=hf["vocab_size"],
        attn=AttentionConfig(
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=hf["head_dim"], rope="full",
            rope_theta=float(full["rope_theta"]),
            window=hf["sliding_window"],
            yarn=YarnConfig(
                factor=float(full["factor"]),
                original_max_position_embeddings=full[
                    "original_max_position_embeddings"],
                beta_fast=float(full["beta_fast"]),
                beta_slow=float(full["beta_slow"]),
                attention_factor=float(full["attention_factor"]))),
        mlp=MLPConfig(
            d_ff=hf["moe_intermediate_size"], kind="swiglu",
            moe=MoEConfig(
                num_experts=hf["num_experts_published"],
                top_k=hf["num_experts_per_tok"],
                held_experts=tuple(cfg["deployment"]["held_experts"]))),
        layer_pattern=layer_pattern(hf),
        norm_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        dms=dataclasses.replace(
            DMSConfig(enabled=True), window=cfg["policy"]["window"],
            target_cr=float(cfg["policy"]["cr"]),
            logit_bias=float(dms.get("logit_bias", DMSConfig.logit_bias))),
        dtype=cfg["compute_dtype"],
        family="moe",
    )


def program_policy(cfg: dict) -> KVPolicyConfig:
    p = cfg["policy"]
    return KVPolicyConfig(kind=p["kind"], cr=float(p["cr"]),
                          window=p["window"], block_p=p["block_p"],
                          paged=p["paged"])


def _tree(base, cfg: dict) -> dict:
    """The program's layout (``repro.models.transformer.init_model``): one
    stack per pattern position, layer ``s * period + i`` at ``[s]`` of
    position ``i``; each leaf made in the stored type."""
    hf = cfg["hf"]
    specs = ref.weight_specs(hf)
    dt = jnp.dtype(cfg["param_dtype"])
    glob = {n: weights.leaf(base, n, sh, sc).astype(dt)
            for n, sh, sc, lay in specs if not lay}
    period = len(layer_pattern(hf))
    blocks = {}
    for i in range(period):
        ids = jnp.arange(i, hf["num_hidden_layers"], period)
        w = jax.vmap(lambda l: jax.tree_util.tree_map(
            lambda a: a.astype(dt), weights.make_layer(base, specs, l)))(ids)
        blocks[str(i)] = {
            "attn_norm": {"scale": w["attn_norm"]},
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "mlp_norm": {"scale": w["mlp_norm"]},
            "mlp": {k: w[k] for k in ("router", "w_gate", "w_up", "w_down")},
        }
    return {"embed": glob["embed"], "lm_head": glob["lm_head"],
            "final_norm": {"scale": glob["final_norm"]}, "blocks": blocks}


def program_params(seed: int, cfg: dict):
    """The parameter tree, made on the device in one jitted program."""
    return jax.jit(lambda b: _tree(b, cfg))(weights.base_key(seed))


def param_shapes(cfg: dict):
    return jax.eval_shape(lambda b: _tree(b, cfg), weights.base_key(0))
