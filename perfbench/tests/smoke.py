"""A smoke-size copy of the benchmark: the real harness, readers and
generator, with tiny configurations and mixes, for CPU tests."""
from __future__ import annotations

import json
import os
import shutil

PKG = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PKG)

HF = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "vocab_size": 512, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
      "tie_word_embeddings": True}
# The smoke dms model's eviction bias.  At bias 0.5 a few seeds in twelve
# hold a decision within bfloat16 rounding of the threshold (|logit| under
# 0.001); the program and the float32 reference then keep different tokens,
# and a 2-layer model's logits move by up to 0.18 from there on, more than
# the float8 control moves them.  At 3.0 the marked share is 0.9987 (a token
# leaves the cache ``window`` steps after it entered) and the threshold
# lies where few decisions do.
DMS_BIAS = 3.0
# Limit of the smoke models' mean served-logit gap, the number the cell
# holds, from readings on the CPU (sound program, seeds 1-12 / float8
# control, seeds 1-4): at most 0.00011 / at least 0.0019 (dms closed),
# 0.00014 / 0.0025 (vanilla closed), 0.00018 / 0.0027 (dms open).  The
# widest gap also separates at this size (0.0089 / 0.030) but not at full
# width (PERF.md).
LIMITS = {"mean_gap": 0.0006}


def config(name: str, kind: str, bias=None) -> dict:
    max_len = 96
    d = {"name": name, "source": "smoke", "model": "dense_gqa", "hf": HF,
         "reduced": [], "compute_dtype": "bfloat16",
         "policy": {"kind": kind, "cr": 8.0 if kind == "dms" else 1.0,
                    "window": 8, "block_p": 16, "paged": False},
         "chunk": 8, "max_len": max_len, "lanes": 8,
         "arena_slots": max_len // 8 + 8 + 16 if kind == "dms" else max_len,
         "check": {"limits": LIMITS}}
    if bias is not None:
        d["dms"] = {"logit_bias": bias}
    return d


CLOSED = {"arrival": {"kind": "closed", "requests": 200},
          "prompt_len": {"dist": "loguniform", "min": 8, "max": 24},
          "max_new": {"dist": "lognormal", "median": 32, "sigma": 0.5,
                      "min": 16, "max": 64},
          "width": 4, "check": {"requests": 2, "max_tokens": 4000}}
OPEN = {"arrival": {"kind": "poisson", "rate_per_s": 4.0},
        "prompt_len": {"dist": "lognormal", "median": 16, "sigma": 1.0,
                       "min": 4, "max": 48},
        "max_new": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                    "min": 4, "max": 40},
        "width": 1, "check": {"requests": 4, "max_tokens": 4000}}


def build(dst: str) -> str:
    """A benchmark tree at ``dst`` with cells ``smoke.dms.closed``,
    ``smoke.vanilla.closed`` and ``smoke.dms.open``; returns ``dst``."""
    shutil.copytree(PKG, os.path.join(dst, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfgs = [config("smoke.dms", "dms", DMS_BIAS), config("smoke.vanilla",
                                                    "vanilla")]
    for c in cfgs:
        _dump(c, dst, f"perfbench/configs/{c['name']}.json")
    _dump(CLOSED, dst, "perfbench/traffic/smoke-closed.json")
    _dump(OPEN, dst, "perfbench/traffic/smoke-open.json")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"] = [{"name": c["name"], "source": "smoke",
                       "file": f"perfbench/configs/{c['name']}.json",
                       "reduced": [], "why": "smoke"} for c in cfgs]
    doc["workloads"] = [
        {"name": "smoke.dms.closed", "config": "smoke.dms",
         "traffic": "smoke-closed", "chips": 1, "why": "smoke"},
        {"name": "smoke.vanilla.closed", "config": "smoke.vanilla",
         "traffic": "smoke-closed", "chips": 1, "why": "smoke"},
        {"name": "smoke.dms.open", "config": "smoke.dms",
         "traffic": "smoke-open", "chips": 1, "why": "smoke"}]
    closed, opened = ["smoke.dms.closed", "smoke.vanilla.closed"], [
        "smoke.dms.open"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (closed if "reasoning" in m["workloads"][0]
                              else opened)
    _dump(doc, dst, "BENCHMARK.json")
    return dst


def _dump(obj, dst: str, rel: str) -> None:
    path = os.path.join(dst, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)
