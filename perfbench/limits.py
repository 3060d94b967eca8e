"""Readings that set a cell's limit on the served-logit gap, on the chip.

    python3 perfbench/limits.py --workload <cell> --seconds <s> \
        --seeds 11 12 13 --control-seeds 11 12

For each seed, in one process (set-up is long and compiles once): a whole
run of the cell as the benchmark makes it (``harness.run``, tracing off),
printing the program's widest served-logit gap under the float32
reference; and for the control seeds, the gap of the token that the float8
reference puts first at the same positions of the same sequences.  The
limit goes above the largest sound reading and below the smallest control
reading (``PERF.md`` keeps them).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from perfbench import check, harness, spec
    if jax.devices()[0].platform != "tpu":
        print("limits: needs a TPU", file=sys.stderr)
        return 1
    bench = spec.Bench(ROOT)
    cfg = bench.config(bench.workload(args.workload)["config"])
    ref = spec.reference_module(cfg)
    for seed in args.seeds:
        rec = harness.run(bench, args.workload, seed, args.seconds, False,
                          time.perf_counter())
        row = {"workload": args.workload, "seed": seed,
               "program": rec["check"], "faults": rec["faults"][:3],
               "setup_s": rec["setup_s"], "window_s": rec["window_s"],
               "tokens_per_s": rec["tokens"] / rec["window_s"],
               "peak_bytes": rec["peak_bytes"]}
        if seed in args.control_seeds:
            t0 = time.perf_counter()
            row["control"] = check.control_gap(ref, seed, cfg, rec["seqs"])
            row["control_s"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
