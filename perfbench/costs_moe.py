"""Operations and bytes of a model whose every MLP is a sparse expert layer,
as one chip of an expert-parallel deployment computes it (the configuration
file's ``hf``: ``num_experts`` held here of ``num_experts_published``,
``num_experts_per_tok`` picks a token, experts of width
``moe_intermediate_size``).  Counted at bf16, from shapes and the chunk
program's ``act`` (steps, lanes); the chip's peaks are ``costs.PEAKS``."""
from __future__ import annotations

import numpy as np

from perfbench.costs import BF16


def _experts(hf: dict) -> tuple:
    return (hf["num_experts"], hf["num_experts_published"],
            hf["num_experts_per_tok"])


def matmul_params(hf: dict) -> float:
    """Weights a token multiplies by on this chip: attention, the router
    at its published width, the held share of its k experts on average
    (k · E_h / E_p of them), every layer, plus the lm head."""
    d, f = hf["hidden_size"], hf["moe_intermediate_size"]
    hq, hkv, dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    eh, ep, k = _experts(hf)
    layer = (d * hq * dh * 2 + 2 * d * hkv * dh
             + k * eh / ep * 3 * d * f + d * ep)
    return hf["num_hidden_layers"] * layer + d * hf["vocab_size"]


def step_flops(hf: dict, act: np.ndarray, live: np.ndarray) -> float:
    """Model FLOPs of the active lane-steps (``costs.step_flops`` with this
    chip's expert share): ``live`` (steps, lanes) tokens attended, summed
    over layers."""
    per_token = 2.0 * matmul_params(hf)
    attn = 4.0 * hf["num_attention_heads"] * hf["head_dim"]
    a = act.astype(np.float64)
    return float((a * (per_token + attn * live)).sum())


def experts_flops_bytes(hf: dict, act: np.ndarray) -> tuple:
    """What the ``moe_experts`` kernel must do over the steps of ``act``,
    at least: one call a layer a step at ``a`` active lanes (one token
    each), each lane's k picks spread evenly over the E_p experts.  Pairs
    P = a k E_h / E_p a call; held experts hit H = E_h (1 - (1 - k/E_p)^a),
    each read once.  Bytes: H experts' three matrices and each pair's row
    in and out; FLOPs: three matmuls a pair.  (Forks of one request serve
    the same greedy tokens, so a step of 104 lanes holds 26 distinct
    tokens, which hit 7.75 held experts where H counts 7.99: the bytes
    are counted up to 3% high.)"""
    d, f = hf["hidden_size"], hf["moe_intermediate_size"]
    eh, ep, k = _experts(hf)
    a = act.astype(np.float64).sum(axis=1)                  # (steps,)
    pairs = a * k * eh / ep
    hit = eh * (1.0 - (1.0 - k / ep) ** a)
    calls = hf["num_hidden_layers"]
    nbytes = calls * float((hit * 3 * d * f * BF16
                            + pairs * 2 * d * BF16).sum())
    flops = calls * float((pairs * 6 * d * f).sum())
    return flops, nbytes
