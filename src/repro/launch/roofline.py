"""Roofline-term extraction from compiled XLA artifacts (deliverable g).

Three terms per (arch × shape × mesh), in seconds:

    compute    = HLO_FLOPs_per_device        / peak_FLOPs_per_chip
    memory     = HLO_bytes_per_device        / HBM_bw_per_chip
    collective = wire_bytes_per_device       / link_bw

``cost_analysis()`` reports the per-device partitioned module, so dividing by
per-chip peaks is equivalent to the spec's global/(chips × peak) form.
Collective wire bytes are parsed from the HLO text with ring-algorithm
effective-traffic factors:

    all-reduce      2(n-1)/n · bytes       all-gather      (n-1)/n · out_bytes
    reduce-scatter  (n-1) · out_bytes      all-to-all      (n-1)/n · bytes
    collective-permute  1 · bytes

Collectives whose replica-group size exceeds one pod (256 chips) cross DCI
and are tallied separately (`dci_bytes`).
"""
from __future__ import annotations

import dataclasses
import re
from dataclasses import dataclass, field
from typing import Dict, Optional

# Per-chip peaks keyed by ``jax.Device.device_kind``.  Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
# inter-chip interconnect (four links, 50 GB/s each).
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
# the chip the production-mesh dry-run targets (launch/dryrun.py)
DRYRUN_DEVICE_KIND = "TPU v5 lite"
POD_CHIPS = 256


def device_peaks(kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``kind``; a kind not in the table is an error."""
    if kind not in DEVICE_PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(DEVICE_PEAKS)}")
    return DEVICE_PEAKS[kind]

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(pred|[suf]\d+|bf16|f8e4m3fn|f8e5m2|c64|c128)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"all-reduce-start|all-gather-start|collective-permute-start|ragged-all-to-all)"
    r"[\s(]")
_GROUPS_RE = re.compile(r"replica_groups=(\{\{[^}]*\}[^=]*?\}|\[[\d,]+\]<=\[[\d,]+\])")


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of every shape literal in an HLO result snippet."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if not m:
        return default
    g = m.group(1)
    if g.startswith("{{"):
        first = g[2:].split("}")[0]
        return max(len([x for x in first.split(",") if x.strip() != ""]), 1)
    # iota form [g0,g1,...]<=[N]: groups of size = product(dims[1:])
    dims = [int(x) for x in g[1:g.index("]")].split(",")]
    if len(dims) == 1:
        return dims[0]
    n = 1
    for d in dims[1:]:
        n *= d
    return n


@dataclass
class CollectiveStats:
    wire_bytes: float = 0.0
    dci_bytes: float = 0.0
    op_bytes: Dict[str, float] = field(default_factory=dict)
    op_counts: Dict[str, int] = field(default_factory=dict)

    def add(self, op: str, bytes_: float, crosses_pod: bool):
        self.wire_bytes += bytes_
        if crosses_pod:
            self.dci_bytes += bytes_
        self.op_bytes[op] = self.op_bytes.get(op, 0.0) + bytes_
        self.op_counts[op] = self.op_counts.get(op, 0) + 1


def parse_collectives(hlo_text: str, num_devices: int) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group(1).replace("-start", "")
        # result shapes sit between '=' and the op name; the instruction's own
        # name ('%all-reduce.133 = ...') must not be parsed as a shape source
        lhs = line[:m.start(1)]
        eq = lhs.find("=")
        lhs = lhs[eq + 1:] if eq >= 0 else lhs
        out_bytes = _shape_bytes(lhs)
        n = _group_size(line, num_devices)
        if n <= 1:
            continue
        if op == "all-reduce":
            wire = 2.0 * (n - 1) / n * out_bytes
        elif op == "all-gather":
            wire = (n - 1) / n * out_bytes
        elif op == "reduce-scatter":
            wire = (n - 1) * out_bytes
        elif op in ("all-to-all", "ragged-all-to-all"):
            wire = (n - 1) / n * out_bytes
        else:  # collective-permute
            wire = float(out_bytes)
        stats.add(op, wire, crosses_pod=n > POD_CHIPS)
    return stats


def modeled_bytes_per_device(arch, shape, kind: str, *, num_devices: int,
                             tp: int, dp: int, policy: str = "vanilla",
                             cr: float = 1.0, accum: int = 8,
                             remat: bool = True) -> Dict[str, float]:
    """Analytic per-device HBM traffic for one step, assuming TPU-native
    execution (bf16 matmul operands, flash-attention kernels keeping tiles in
    VMEM, fused elementwise chains).  The HLO 'bytes accessed' number from the
    CPU backend systematically over-counts — its float-normalization pass
    rewrites bf16 ops as convert→f32→convert and its cost model charges every
    fusion-internal flow — so this model is the memory term used for
    bottleneck calls; the HLO number is reported alongside as an upper bound.
    """
    p_total = arch.param_count(active_only=False)
    p_active = arch.param_count(active_only=True)
    p_dev = p_total / tp * 2.0                     # bf16 shard
    d = arch.d_model
    l = arch.num_layers + arch.encoder_layers
    b_loc = max(shape.global_batch / dp, 1.0)
    t = shape.seq_len

    if kind == "train":
        mb_tokens = b_loc * t / accum
        act_coeff = 30.0 if remat else 22.0        # r+w per token-dim, fwd+bwd(+remat)
        act = l * mb_tokens * d * 2.0 * act_coeff * accum
        grads = p_total / tp * 4.0 * 2.0 * accum   # fp32 accumulate r+w
        opt = p_total / (tp * dp) * 4.0 * 3.0 * 2.0  # m, v, master r+w
        logits = mb_tokens * arch.vocab_size / tp * 4.0 * 4.0 * accum
        total = 3.0 * p_dev + grads + opt + act + logits
        return {"params": 3.0 * p_dev, "grads": grads, "opt": opt,
                "activations": act, "logits": logits, "total": total}
    if kind == "prefill":
        a = arch.attn
        act = l * b_loc * t * d * 2.0 * 8.0
        cache_w = (0 if a is None else
                   2.0 * l * b_loc * t * a.num_kv_heads * a.head_dim * 2.0
                   / max(tp // max(a.num_kv_heads, 1), 1) / cr)
        # flash kernel streams K/V once per q block (q tiles resident in VMEM)
        blk = 2048.0
        attn_stream = (0 if a is None else
                       b_loc * max(a.num_kv_heads / tp, 1.0 / tp) * tp / tp *
                       (t * t / 2.0 / blk) * a.head_dim * 2.0 * 2.0 * l / cr)
        total = p_dev + act + cache_w + attn_stream
        return {"params": p_dev, "activations": act, "cache_write": cache_w,
                "attn_stream": attn_stream, "total": total}
    # decode
    a = arch.attn
    cache = 0.0
    if a is not None:
        n_attn = sum(1 for i in range(arch.num_layers)
                     if arch.layer_pattern[i % len(arch.layer_pattern)]
                     in ("attn", "attn_local"))
        n_local = sum(1 for i in range(arch.num_layers)
                      if arch.layer_pattern[i % len(arch.layer_pattern)] == "attn_local")
        h_shard = max(a.num_kv_heads / tp, 1.0) if shape.global_batch >= dp else a.num_kv_heads
        seq_fact = 1.0 if shape.global_batch >= dp else 1.0 / dp
        eff_len_g = min(t, a.window or t)
        full_len = t / cr
        cache = 2.0 * 2.0 * h_shard * a.head_dim * b_loc * seq_fact * (
            (n_attn - n_local) * full_len + n_local * min(eff_len_g, full_len))
    ssm_state = 0.0
    if arch.ssm is not None:
        nh = arch.ssm.num_heads(d) / tp
        ssm_state = (arch.num_layers * b_loc * nh * arch.ssm.head_dim
                     * arch.ssm.d_state * 4.0 * 2.0)
    if arch.rglru is not None:
        n_rg = sum(1 for k in arch.layer_pattern if k == "rglru")
        ssm_state += (arch.num_layers * n_rg / len(arch.layer_pattern)
                      * b_loc * (arch.rglru.lru_width or d) / tp * 4.0 * 2.0)
    act = l * b_loc * d * 2.0 * 8.0
    total = 2.0 * p_active / tp + cache + ssm_state + act
    return {"params": 2.0 * p_active / tp, "kv_cache": cache,
            "state": ssm_state, "activations": act, "total": total}


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    num_devices: int
    flops_per_device: float
    bytes_per_device: float           # HLO 'bytes accessed' (upper bound)
    modeled_bytes_per_dev: float      # analytic TPU-native traffic model
    wire_bytes_per_device: float
    dci_bytes_per_device: float
    compute_s: float
    memory_s: float                   # from HLO bytes (upper bound)
    memory_model_s: float             # from the analytic model (used for calls)
    collective_s: float
    bottleneck: str
    model_flops: float
    useful_flops_ratio: float
    step_time_s: float            # max(compute, memory_model, collective)
    hw_util: float                # model_flops / (chips * peak * step_time)
    memory_analysis: Dict[str, float] = field(default_factory=dict)
    memory_breakdown: Dict[str, float] = field(default_factory=dict)
    collective_ops: Dict[str, float] = field(default_factory=dict)
    collective_counts: Dict[str, int] = field(default_factory=dict)

    def as_dict(self):
        return dataclasses.asdict(self)


def analyze(compiled, *, arch: str, shape: str, mesh_desc: str, num_devices: int,
            model_flops: float, device_kind: str, hlo_text: Optional[str] = None,
            modeled: Optional[Dict[str, float]] = None) -> RooflineReport:
    peaks = device_peaks(device_kind)
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = parse_collectives(text, num_devices)
    modeled = modeled or {"total": byts}

    compute_s = flops / peaks["flops"]
    memory_s = byts / peaks["hbm_bw"]
    memory_model_s = modeled["total"] / peaks["hbm_bw"]
    collective_s = coll.wire_bytes / peaks["ici_bw"]
    terms = {"compute": compute_s, "memory": memory_model_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    step_time = max(terms.values())
    total_flops = flops * num_devices
    ratio = model_flops / total_flops if total_flops else 0.0
    util = (model_flops / (num_devices * peaks["flops"] * step_time)
            if step_time > 0 else 0.0)

    ma = {}
    try:
        m = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            ma[k] = float(getattr(m, k, 0.0))
    except Exception:
        pass

    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_desc, num_devices=num_devices,
        flops_per_device=flops, bytes_per_device=byts,
        modeled_bytes_per_dev=float(modeled["total"]),
        wire_bytes_per_device=coll.wire_bytes,
        dci_bytes_per_device=coll.dci_bytes,
        compute_s=compute_s, memory_s=memory_s,
        memory_model_s=memory_model_s, collective_s=collective_s,
        bottleneck=bottleneck, model_flops=model_flops,
        useful_flops_ratio=ratio, step_time_s=step_time, hw_util=util,
        memory_analysis=ma,
        memory_breakdown={k: float(v) for k, v in modeled.items()},
        collective_ops=coll.op_bytes,
        collective_counts=coll.op_counts)


def model_flops_for(arch, shape, kind: str) -> float:
    """MODEL_FLOPS: 6·N·D (train) / 2·N·D (forward-only), N = active params."""
    n_active = arch.param_count(active_only=True)
    if kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch * 1
    return 2.0 * n_active * tokens
