"""Arithmetic shared by the metric readers in ``metrics/``.  A rate is all
the work of the window over all of its time; a tail is over every request
due in the window, a failed or unfinished one counting as missing (its
time infinite)."""
from __future__ import annotations

import math
from typing import List, Optional

import numpy as np

from perfbench import costs
from perfbench import trace as tr


def p95(values: List[float]) -> Optional[float]:
    """95th percentile (linear between ranks); None when there is nothing
    to read or the tail is a missing request."""
    if not values:
        return None
    v = float(np.percentile(np.asarray(values, np.float64), 95))
    return None if math.isinf(v) else v


def _missing(r: dict, key: str) -> bool:
    return r["status"] != "ok" or r[key] is None or r["due"] is None


def ttft_s(run: dict) -> List[float]:
    return [math.inf if _missing(r, "first_token")
            else r["first_token"] - r["due"] for r in run["requests"]]


def tpot_s(run: dict) -> List[float]:
    out = []
    for r in run["requests"]:
        if _missing(r, "finished") or r["first_token"] is None:
            out.append(math.inf)
        elif r["tokens"] > 1:
            out.append((r["finished"] - r["first_token"]) / (r["tokens"] - 1))
    return out


def queue_wait_s(run: dict) -> List[float]:
    return [math.inf if _missing(r, "admitted") else r["admitted"] - r["due"]
            for r in run["requests"]]


def chunk_step_ms(run: dict) -> Optional[float]:
    """Device time of the chunk program per scan step, from the trace."""
    if not run.get("trace"):
        return None
    secs, n = tr.op_seconds(run["trace"], "chunk_fn", tr.MODULES)
    return secs / (n * run["chunk"]) * 1e3 if n else None


def device_idle_pct(run: dict) -> Optional[float]:
    if not run.get("trace"):
        return None
    return (1.0 - tr.busy_s(run["trace"]) / tr.window_s(run["trace"])) * 100


def step_mfu_pct(run: dict) -> Optional[float]:
    """Model FLOPs of the traced ticks over the traced window's seconds at
    peak.  The traced window and not the whole one: stopping the profiler
    holds the host for seconds inside the whole window."""
    c = run.get("counters_traced")
    if not run.get("trace") or c is None:
        return None
    flops = costs.step_flops(run["hf"], c["act"], c["live"])
    peak = costs.peaks(run["device_kind"])["flops"]
    return flops / (tr.window_s(run["trace"]) * peak) * 100


def kernel_roofline_pct(run: dict, kernel: str) -> Optional[float]:
    """Least time the chip could take for the kernel's work (the larger of
    FLOPs over peak FLOP/s and bytes over HBM bandwidth) over the traced
    kernel time."""
    c = run.get("counters_traced")
    if not run.get("trace") or c is None:
        return None
    secs, n = tr.op_seconds(run["trace"], kernel)
    if not n or secs <= 0:
        return None
    flops, nbytes = costs.kernel_flops_bytes(run["hf"], c["live"])
    pk = costs.peaks(run["device_kind"])
    return max(flops / pk["flops"], nbytes / pk["hbm_bw"]) / secs * 100
