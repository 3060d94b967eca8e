"""A smoke-size hybrid sliding/full MoE configuration (``moe_hybrid_gqa``)
beside ``smoke.py``'s dense ones: one period of the layer pattern, one of
eight expert shares, a window and a DMS delay that short requests cross."""
from __future__ import annotations

import json
import os

from perfbench.tests import smoke

HF = {"hidden_size": 64, "moe_intermediate_size": 32, "intermediate_size": 128,
      "num_hidden_layers": 4, "num_attention_heads": 4,
      "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
      "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
      "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
      "sliding_window": 16,
      "rope_parameters": {
          "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                             "factor": 4, "original_max_position_embeddings": 32,
                             "beta_fast": 32, "beta_slow": 1,
                             "attention_factor": 1.1386294361119891},
          "sliding_attention": {"rope_type": "default",
                                "rope_theta": 500000}},
      "num_experts": 2, "num_experts_published": 16,
      "num_experts_per_tok": 4, "norm_topk_prob": True}
# the smoke dense dms model's bias (smoke.DMS_BIAS): few decisions lie near
# the threshold
DMS_BIAS = 3.0


def config(name: str = "smoke.moe", held=(0, 1)) -> dict:
    max_len = 96
    return {"name": name, "source": "smoke", "model": "moe_hybrid_gqa",
            "hf": dict(HF, num_experts=len(held)), "reduced": ["num_experts"],
            "deployment": {"held_experts": list(held)},
            "compute_dtype": "bfloat16", "param_dtype": "bfloat16",
            "policy": {"kind": "dms", "cr": 8.0, "window": 4, "block_p": 16,
                       "paged": False},
            "chunk": 8, "max_len": max_len, "lanes": 8,
            # min(provision_slots(17, 8, 4), 17 + 1); provision_slots(96, 8, 4)
            "arena_slots": 18, "arena_slots_full": 32,
            "dms": {"logit_bias": DMS_BIAS},
            "check": {"limits": smoke.LIMITS}}


def build(dst: str) -> str:
    """``smoke.build``'s tree plus the cell ``smoke.moe.closed``."""
    smoke.build(dst)
    cfg = config()
    with open(os.path.join(dst, "perfbench/configs/smoke.moe.json"),
              "w") as f:
        json.dump(cfg, f)
    path = os.path.join(dst, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "smoke.moe", "source": "smoke",
                           "file": "perfbench/configs/smoke.moe.json",
                           "reduced": ["num_experts"], "why": "smoke"})
    doc["workloads"].append({"name": "smoke.moe.closed", "config": "smoke.moe",
                             "traffic": "smoke-closed", "chips": 1,
                             "why": "smoke"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "smoke.dms.closed" in m.get("workloads", ()):
            m["workloads"].append("smoke.moe.closed")
        if m["name"].startswith("moe_"):
            m["workloads"] = ["smoke.moe.closed"]
    with open(path, "w") as f:
        json.dump(doc, f)
    return dst
