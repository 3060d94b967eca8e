"""Calibrating DMS's ``logit_bias`` on seed-random weights.

    JAX_PLATFORMS=cpu python perfbench/calibrate.py \
        perfbench/configs/qwen-r1-1.5b.dms-cr8.json --seed 0 --tokens 1024

On random weights the borrowed neuron sits near 0, so the program's default
bias of -5 marks almost nothing for eviction, and only the arena's overflow
recycling would compress — no trained model runs that way.  This finds the
bias at which the share of (layer, KV head, token) decisions that mark a
token for eviction is ``1 - 1/cr``, the rate of a model trained to that
compression: the quantile of the neuron's values under the reference
(float32, published widths, one layer at a time), refined once since the
bias changes what later layers see.  It prints the bias and the share it
gives, with the spread over heads.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def eviction_share(cfg: dict, seed: int, tokens: np.ndarray) -> dict:
    """Share of decisions that mark a token at ``cfg``'s bias, over all
    layers and KV heads, and per head."""
    from perfbench import spec
    ref = spec.reference_module(cfg)
    logits = ref.borrowed_neuron_logits(seed, cfg, tokens)   # (L, T, Hkv)
    marked = logits > 0
    per_head = marked.mean(axis=1)
    return {"share": float(marked.mean()), "raw": logits - cfg["dms"][
        "logit_bias"], "head_min": float(per_head.min()),
            "head_max": float(per_head.max())}


def calibrate(cfg: dict, seed: int, n_tokens: int) -> dict:
    target = 1.0 - 1.0 / cfg["policy"]["cr"]
    tokens = np.random.default_rng(seed).integers(
        3, cfg["hf"]["vocab_size"], size=(n_tokens,)).astype(np.int32)
    bias = float(cfg["dms"]["logit_bias"])
    for _ in range(2):
        got = eviction_share(cfg, seed, tokens)
        bias = float(-np.quantile(got["raw"], 1.0 - target))
        cfg = dict(cfg, dms=dict(cfg["dms"], logit_bias=bias))
    got = eviction_share(cfg, seed, tokens)
    return {"logit_bias": bias, "share": got["share"], "target": target,
            "head_min": got["head_min"], "head_max": got["head_max"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tokens", type=int, default=1024)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT]
    from perfbench import spec
    print(json.dumps(calibrate(spec.load_json(args.config), args.seed,
                               args.tokens)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
