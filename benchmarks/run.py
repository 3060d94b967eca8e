"""Benchmark orchestrator — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (harness contract).

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME] [--check]

``--check`` is regression mode: suites run as usual but their saved metric
payloads are captured (baselines under ``artifacts/bench`` are NOT
overwritten) and compared against those baselines with tolerances —
wall-clock keys are skipped, everything else (saved-reads identities,
hit-path byte-traffic counters, hit rates) must agree within ``--rtol``.
Exit 1 on drift; with ``--only`` a missing baseline is also a failure
(the explicit gate must not be vacuous), a full sweep skips suites whose
baselines aren't committed.  Re-record a baseline by running the suite
WITHOUT ``--check`` and committing the JSON
(``artifacts/bench/prefix_cache.json`` and
``artifacts/bench/decode_path.json`` are git-tracked today).

Suites (↔ paper artifact):
    latency_model     Appendix G / Fig. 7 (TPU re-derivation)
    roofline_table    40-cell dry-run roofline collation (§Roofline)
    cr_profile        Fig. 6 (CR vs position, per-layer retention)
    ablation_eviction Fig. 5 left (delayed vs immediate)
    data_efficiency   Fig. 5 right (DMS vs immediate/DMC objective)
    cr_sweep          Table 1 (method × CR on needle task)
    pareto            Fig. 3 / Fig. 4 (accuracy vs budget frontiers)
    continuous_batching  serving: scheduler vs lockstep, shared-prefill fork
    prefix_cache      serving: cross-request radix prefix reuse (shared
                      system prompt, two-tier hot path, single-shot export
                      gating, multi-turn chat traces)
    decode_path       kernel: block-table flash-decode HBM traffic ∝ live
                      tokens (fill/CR/fragmentation sweeps, zero-copy step
                      path — see docs/kernels.md)
    paged_arena       serving: paged KV block pool — footprint ∝ live
                      tokens, 4x lanes per byte budget, zero-copy CoW fork
                      (see docs/serving.md)
    preemption        serving: preemptive lane eviction under an
                      oversubscribed pool — bitwise snapshot resume, zero
                      re-prefill, deterministic lifecycle counters (see
                      docs/serving.md "Failure semantics & preemption")
    slo_harness       serving: SLO-driven overload control — the same 2x
                      burst trace with and without the degradation ladder;
                      gates the goodput win, zero-prefill sheds, and solo
                      token equality of degraded requests (see
                      docs/serving.md "SLO & overload control")
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced step counts (CI mode)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--check", action="store_true",
                    help="compare fresh metrics against artifacts/bench "
                         "baselines instead of overwriting them")
    ap.add_argument("--rtol", type=float, default=0.1,
                    help="relative tolerance for --check comparisons")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    from benchmarks import common
    from benchmarks import (ablation_eviction, continuous_batching, cr_profile,
                            cr_sweep, data_efficiency, decode_path,
                            latency_model, paged_arena, pareto, preemption,
                            prefix_cache, roofline_table, slo_harness)
    suites = {
        "latency_model": latency_model.run,
        "roofline_table": roofline_table.run,
        "cr_profile": cr_profile.run,
        "ablation_eviction": ablation_eviction.run,
        "data_efficiency": data_efficiency.run,
        "cr_sweep": cr_sweep.run,
        "pareto": pareto.run,
        "continuous_batching": continuous_batching.run,
        "prefix_cache": prefix_cache.run,
        "decode_path": decode_path.run,
        "paged_arena": paged_arena.run,
        "preemption": preemption.run,
        "slo_harness": slo_harness.run,
    }
    if args.only:
        suites = {k: v for k, v in suites.items() if k == args.only}
    common.set_check_mode(args.check)
    failed = []
    for name, fn in suites.items():
        t0 = time.time()
        print(f"# === {name} ===", file=sys.stderr)
        try:
            fn(quick=args.quick)
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception as e:
            traceback.print_exc()
            failed.append((name, repr(e)))
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        return 1
    if args.check:
        import json
        problems = []
        compared = 0
        for name, payload in sorted(common.CAPTURED.items()):
            base_path = common.ARTIFACTS / f"{name}.json"
            if not base_path.exists():
                # with --only the caller explicitly asked to gate THIS
                # suite: a vacuously-green gate is worse than a red one.
                # A full sweep just skips suites with no committed baseline.
                if args.only:
                    problems.append(f"{name}: no baseline at {base_path} "
                                    "(run without --check to record it)")
                else:
                    print(f"# check: no baseline for {name} — skipped",
                          file=sys.stderr)
                continue
            baseline = json.loads(base_path.read_text())
            problems += common.compare_to_baseline(name, payload, baseline,
                                                   rtol=args.rtol)
            compared += 1
        if problems:
            print("# CHECK FAILED:", file=sys.stderr)
            for p in problems:
                print(f"#   {p}", file=sys.stderr)
            return 1
        print(f"# check OK: {compared} suite payload(s) within "
              f"rtol={args.rtol} of artifacts/bench baselines",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
