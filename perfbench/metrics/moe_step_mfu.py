"""Model FLOPs of every active lane-step of the traced ticks, counting the
expert layer as this chip computes it (the held share of the k experts and
the router at its published width, ``costs_moe.step_flops``), over the
traced window's seconds x peak bf16 FLOP/s, in %."""
from perfbench import costs, costs_moe
from perfbench import trace as tr


def read(run):
    c = run.get("counters_traced")
    if not run.get("trace") or c is None \
            or "num_experts_published" not in run["hf"]:
        return None
    flops = costs_moe.step_flops(run["hf"], c["act"], c["live"])
    peak = costs.peaks(run["device_kind"])["flops"]
    return flops / (tr.window_s(run["trace"]) * peak) * 100
