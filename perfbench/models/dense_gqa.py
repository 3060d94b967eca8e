"""Dense GQA decoders (Qwen2-style) in the program: the ``ArchConfig`` and
``KVPolicyConfig`` a configuration file describes, and the benchmark's
seeded weights laid out as the program's parameter tree."""
from __future__ import annotations

import dataclasses

import jax

from perfbench import weights
from perfbench.references import dense_gqa as ref
from repro.core.config import (ArchConfig, AttentionConfig, DMSConfig,
                               KVPolicyConfig, MLPConfig)


def program_arch(cfg: dict) -> ArchConfig:
    hf, dms = cfg["hf"], cfg.get("dms", {})
    return ArchConfig(
        name=cfg["name"],
        num_layers=hf["num_hidden_layers"],
        d_model=hf["hidden_size"],
        vocab_size=hf["vocab_size"],
        attn=AttentionConfig(num_heads=hf["num_attention_heads"],
                             num_kv_heads=hf["num_key_value_heads"],
                             head_dim=hf["head_dim"], rope="full",
                             rope_theta=float(hf["rope_theta"])),
        mlp=MLPConfig(d_ff=hf["intermediate_size"], kind="swiglu"),
        layer_pattern=("attn",),
        norm_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        dms=dataclasses.replace(
            DMSConfig(enabled=True), window=cfg["policy"]["window"],
            target_cr=float(cfg["policy"]["cr"]),
            logit_bias=float(dms.get("logit_bias", DMSConfig.logit_bias))),
        dtype=cfg["compute_dtype"],
        family="dense",
    )


def program_policy(cfg: dict) -> KVPolicyConfig:
    p = cfg["policy"]
    return KVPolicyConfig(kind=p["kind"], cr=float(p["cr"]),
                          window=p["window"], block_p=p["block_p"],
                          paged=p["paged"])


def _tree(w: dict) -> dict:
    """The program's layout (``repro.models.transformer.init_model``)."""
    return {
        "embed": w["embed"],
        "final_norm": {"scale": w["final_norm"]},
        "blocks": {"0": {
            "attn_norm": {"scale": w["attn_norm"]},
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "mlp_norm": {"scale": w["mlp_norm"]},
            "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
        }},
    }


def program_params(seed: int, cfg: dict):
    """The parameter tree, made on the device in one jitted program, in the
    type the program stores (float32)."""
    specs = ref.weight_specs(cfg["hf"])
    layers = cfg["hf"]["num_hidden_layers"]
    return jax.jit(lambda b: _tree(weights.make(b, specs, layers)))(
        weights.base_key(seed))


def param_shapes(cfg: dict):
    specs = ref.weight_specs(cfg["hf"])
    layers = cfg["hf"]["num_hidden_layers"]
    return jax.eval_shape(lambda b: _tree(weights.make(b, specs, layers)),
                          weights.base_key(0))
