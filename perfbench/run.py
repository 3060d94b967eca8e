"""The benchmark's entry point.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chip this process finds, and
prints as its last stdout line one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer ones with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and last ``checks`` (each number compared, beside its
limit).  The checks are also the last lines on stderr.  With no TPU, or
fewer chips than the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def result_line(bench, workload: str, trace: bool, rec: dict) -> dict:
    from perfbench import spec
    from perfbench import trace as tr
    device = {"platform": rec["platform"], "kind": rec["device_kind"],
              "count": rec["device_count"],
              "memory_peak_bytes": rec["peak_bytes"]}
    out = {"correct": bool(rec["correct"]), "attempted": rec["attempted"],
           "failed": rec["failed"],
           "metrics": spec.read_metrics(bench, workload, trace, rec),
           "device": device}
    if trace and rec.get("trace"):
        device["busy_s"] = tr.busy_s(rec["trace"])
        device["window_s"] = tr.window_s(rec["trace"])
        out["breakdown"] = {"device_ops": tr.top_ops(rec["trace"]),
                            "idle_gaps": tr.idle_gaps(rec["trace"])}
    out["checks"] = checks(rec)
    return out


def checks(rec: dict) -> dict:
    out = {k: {"value": rec["check"][k], "limit": v}
           for k, v in rec["limits"].items()}
    out["requests_not_ok"] = {"value": len(rec["faults"]), "limit": 0}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from perfbench import spec
    bench = spec.Bench(ROOT)
    cell = bench.workload(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"perfbench: cell {args.workload} needs {cell['chips']} TPU "
              f"chip(s), found {len(devices)} {devices[0].platform!r} "
              "device(s); no CPU fallback", file=sys.stderr)
        return 1

    from perfbench import harness
    rec = harness.run(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START)
    out = result_line(bench, args.workload, bool(args.trace), rec)
    print(json.dumps({"run": {
        "setup_s": rec["setup_s"], "compile_s": rec["compile_s"],
        "compiles_in_window": rec["compiles_in_window"],
        "window_s": rec["window_s"], "check": rec["check"],
        "faults": rec["faults"][:5], "bytes_limit": rec["bytes_limit"],
        "lateness_s": _lateness(rec), "ticks": rec["ticks"]}}), flush=True)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


def _lateness(rec: dict):
    """How late the open-loop generator submitted requests (max, mean)."""
    late = [r["submitted"] - r["due"] for r in rec["requests"]
            if r["due"] is not None and r["submitted"] is not None]
    if not late:
        return None
    return {"max": max(late), "mean": sum(late) / len(late)}


if __name__ == "__main__":
    sys.exit(main())
