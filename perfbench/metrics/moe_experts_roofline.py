"""Share of its roofline the ``moe_experts`` kernel reaches over the traced
window, in % (bytes-bound: the held experts a call hits, each read once,
and the pairs' rows at bf16 over HBM bandwidth,
``costs_moe.experts_flops_bytes``)."""
from perfbench import costs, costs_moe
from perfbench import trace as tr


def read(run):
    c = run.get("counters_traced")
    if not run.get("trace") or c is None \
            or "num_experts_published" not in run["hf"]:
        return None
    secs, n = tr.op_seconds(run["trace"], "moe_experts")
    if not n or secs <= 0:
        return None
    flops, nbytes = costs_moe.experts_flops_bytes(run["hf"], c["act"])
    pk = costs.peaks(run["device_kind"])
    return max(flops / pk["flops"], nbytes / pk["hbm_bw"]) / secs * 100
