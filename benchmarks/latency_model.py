"""Benchmark ↔ paper Appendix G / Fig. 7: share of decode latency attributable
to KV-cache reads, re-derived for TPU v5e and validated against the compiled
dry-run artifacts where available.

Paper Eq. (2)-(6) with our constants:
    FLOPS(B, L) ≈ n·B·(6·d·d_ff·g + 4·d² + 4·d·d_kv + 4·d_kv·L·r) + 2·B·d·V
    Reads(B, L) ≈ params_bytes + 2·n·B·L·d_kv·2
    latency ≈ max(FLOPS / peak, Reads / hbm_bw)
"""
from __future__ import annotations


from benchmarks.common import emit, save_json
from repro.configs import get_arch
from repro.launch.roofline import DRYRUN_DEVICE_KIND, device_peaks


def decode_step_model(arch, batch, seq_len, cr=1.0):
    a = arch.attn
    d = arch.d_model
    n = arch.num_layers
    d_kv = (a.num_kv_heads * a.head_dim) if a else 0
    d_q = (a.num_heads * a.head_dim) if a else 0
    if arch.mlp is not None:
        glu = 3 if arch.mlp.kind in ("swiglu", "geglu") else 2
        moe = arch.mlp.moe
        d_ff_active = arch.mlp.d_ff * (moe.top_k if moe else 1)
    else:
        glu, d_ff_active = 0, 0
    l_eff = seq_len / cr
    flops = n * batch * (2 * glu * d * d_ff_active + 2 * d * d_q + 2 * d_q * d
                         + 4 * d * d_kv + 4 * d_kv * l_eff) \
        + 2 * batch * d * arch.vocab_size
    params_bytes = arch.param_count(active_only=True) * 2
    kv_bytes = 2 * n * batch * l_eff * d_kv * 2
    reads = params_bytes + kv_bytes
    peaks = device_peaks(DRYRUN_DEVICE_KIND)
    lat = max(flops / peaks["flops"], reads / peaks["hbm_bw"])
    return {
        "latency_s": lat,
        "kv_share": kv_bytes / reads,
        "kv_dominates": kv_bytes > params_bytes,
        "flops": flops, "reads": reads,
    }


def run(quick=False):
    out = {}
    for arch_name in ["qwen-r1-1.5b", "qwen-r1-7b", "phi3-mini-3.8b"]:
        arch = get_arch(arch_name)
        for batch in (1, 32, 256):
            for seq in (8192, 32768):
                for cr in (1.0, 4.0, 8.0):
                    m = decode_step_model(arch, batch, seq, cr)
                    key = f"{arch_name}/b{batch}/s{seq}/cr{cr:g}"
                    out[key] = m
                    emit(f"latency_model/{key}", m["latency_s"] * 1e6,
                         {"kv_share": round(m["kv_share"], 4)})
    # paper's headline check (§5.1): at batch 256 / long seq the KV share of
    # memory reads exceeds 80-90% for the small Qwen models at CR=1
    share = out["qwen-r1-1.5b/b256/s32768/cr1"]["kv_share"]
    emit("latency_model/headline", 0.0,
         {"qwen1.5b_b256_s32k_kv_share": round(share, 4), "gt_0.9": share > 0.9})
    save_json("latency_model", {k: {kk: float(vv) for kk, vv in v.items()}
                                for k, v in out.items()})
    return out


if __name__ == "__main__":
    run()
