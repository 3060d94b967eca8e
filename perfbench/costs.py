"""Peaks of the chip and the operations and bytes the served model needs,
from shapes.  Counted at bf16, the precision of the matmuls and the cache
(never at the float32 the parameters are stored in)."""
from __future__ import annotations

import numpy as np

# One chip, by ``device_kind`` (Google Cloud documentation, "TPU v5e"):
# bf16 FLOP/s, HBM bytes/s.  A kind not listed is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9},
}
BF16 = 2


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[kind]


def matmul_params(hf: dict) -> int:
    """Weights every token multiplies by: all layers plus the lm head."""
    d, f = hf["hidden_size"], hf["intermediate_size"]
    hq, hkv, dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    layer = d * hq * dh * 2 + 2 * d * hkv * dh + 3 * d * f
    return hf["num_hidden_layers"] * layer + d * hf["vocab_size"]


def step_flops(hf: dict, act: np.ndarray, live: np.ndarray) -> float:
    """Model FLOPs of the active lane-steps: ``act`` (steps, lanes) bool,
    ``live`` (steps, lanes) tokens attended, summed over layers (mean over
    KV heads)."""
    per_token = 2.0 * matmul_params(hf)
    attn = 4.0 * hf["num_attention_heads"] * hf["head_dim"]
    a = act.astype(np.float64)
    return float((a * (per_token + attn * live)).sum())


def kernel_flops_bytes(hf: dict, live: np.ndarray) -> tuple:
    """What ``dms_decode`` must do over ``live`` (steps, lanes) — every lane
    of every step, active or not, since the kernel runs the whole batch:
    QK and PV over the live keys, and at least the live keys' K and V read
    once plus the query in and the output out."""
    hq, hkv, dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    layers = hf["num_hidden_layers"]
    keys = float(live.astype(np.float64).sum())       # summed over layers
    calls = live.size * layers                        # lane-layer-steps
    flops = 4.0 * hq * dh * keys
    nbytes = 2 * BF16 * hkv * dh * keys + 2 * BF16 * hq * dh * calls
    return flops, nbytes
