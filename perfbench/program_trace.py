"""The program's own tracing, reduced for the benchmark.

The scheduler records each tick itself (``Scheduler.tick_log``, one
``TickRecord`` a tick on its clock) and writes ``sched.*`` host spans into
the profiler's trace; the chunk program names its layers with
``jax.named_scope`` (``SCOPES``).  This module reduces both:

* ``read(profile_dir, hlo_text)`` is ``trace.read`` plus the ``sched.``
  host spans, in the same ``host`` list (so ``trace.idle_gaps`` puts each
  idle gap down to a program phase), and ``scopes``: each device op's name
  (``%fusion.32``) mapped to its scope, from the ``metadata={op_name=...}``
  of the chunk program's compiled HLO text.  An op whose root instruction
  lies outside every scope (scan plumbing, such as the
  ``dynamic-update-slice`` that stacks per-layer caches) has no entry and
  counts as unscoped.
* ``scope_seconds``, ``device_scopes`` and ``scope_step_ms`` split the
  chunk program's device time by scope; ``idle_by_phase`` splits the
  device's idle time by the phase the host was in; ``host_ms_per_tick``
  and ``longest_ticks`` read ``TickRecord`` rows (as dicts).

The functions take a run record with ``trace`` (as ``read`` returns it),
``sched_ticks`` (the window's rows) and ``chunk``.  The harness's record
does not carry the last two yet;

    python3 -m perfbench.program_trace --workload <cell> --seed <n> \
        --seconds <s> [--trace 0|1] [--fixture <path>]

runs a cell with them added, on the chip, and prints what they give beside
the accepted result line (with ``--trace 0`` the tick rows only: a long
tick's phase split in an untraced run).
"""
from __future__ import annotations

import bisect
import glob
import re
import statistics
import sys
import time
from typing import Dict, List, Optional, Tuple

from perfbench import trace as tr

SCOPES = ("kv_write", "attend", "mlp", "lane_select", "sample")
UNSCOPED = "unscoped"
PROGRAM = "chunk_fn"
SPAN = "sched."
# the phases of a tick, each from its stamp to the next one's
PHASES = (("admit", "start", "prepare"), ("prepare", "prepare", "dispatch"),
          ("dispatch", "dispatch", "returned"),
          ("readback", "returned", "ready"), ("book", "ready", "end"))
_INSTR = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = .*?"
                    r"metadata=\{[^}]*op_name=\"([^\"]*)\"")


def scope_of(path: str) -> Optional[str]:
    """The innermost of ``SCOPES`` on a named-scope path
    (``jit(chunk_fn)/while/body/.../kv_write/select_n`` → ``kv_write``)."""
    found = [p for p in path.split("/") if p in SCOPES]
    return found[-1] if found else None


def op_key(event_name: str) -> str:
    """``%fusion.32 = bf16[...] fusion(...)`` → ``%fusion.32``."""
    return event_name.split(" = ")[0]


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Each instruction of a compiled HLO module whose own ``op_name`` lies
    under one of ``SCOPES``, mapped to that scope."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            scope = scope_of(m.group(2))
            if scope is not None:
                out[m.group(1)] = scope
    return out


def read(profile_dir: str, hlo_text: str) -> dict:
    """``trace.read`` with the program's spans, and the scopes of the ops
    of the chunk program whose compiled HLO is ``hlo_text`` (a TPU's op
    events carry no ``op_name`` among their stats)."""
    from jax.profiler import ProfileData
    out = tr.read(profile_dir)
    (path,) = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events
                                if e.name.startswith(SPAN)]
    out["scopes"] = hlo_scopes(hlo_text)
    return out


def _program_ops(t: dict) -> List[Tuple[str, float]]:
    """Own time of every device op in the window that runs inside a call of
    the chunk program (op names are unique within one program only)."""
    lo, hi = tr.window(t)
    calls = sorted((a, b) for name, a, b in tr._clip(t[tr.MODULES], lo, hi)
                   if PROGRAM in name)
    starts = [a for a, _ in calls]
    ops = []
    for e in tr._clip(t[tr.OPS], lo, hi):
        i = bisect.bisect_right(starts, e[1]) - 1
        if i >= 0 and e[2] <= calls[i][1]:
            ops.append(e)
    return tr._self_times(ops)


def device_scopes(t: dict) -> Dict[str, float]:
    """Device seconds of the chunk program's ops in the window, by scope,
    and of the ops under none (``unscoped``)."""
    scopes = t.get("scopes", {})
    out = dict.fromkeys(SCOPES + (UNSCOPED,), 0.0)
    for name, own in _program_ops(t):
        out[scopes.get(op_key(name), UNSCOPED)] += own / 1e9
    return out


def scope_seconds(t: dict, scope: str) -> float:
    return device_scopes(t)[scope]


def unscoped_ops(t: dict, n: int = 8) -> List[list]:
    """The chunk program's ops with no scope that took most time, summed by
    name, as ``[short name, seconds]``."""
    scopes = t.get("scopes", {})
    tot: Dict[str, float] = {}
    for name, own in _program_ops(t):
        if op_key(name) not in scopes:
            key = tr.short_name(name)
            tot[key] = tot.get(key, 0.0) + own / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def scope_step_ms(run: dict, scope: str) -> Optional[float]:
    """Device ms of one scope per scan step: its seconds over the chunk
    program's calls in the window times ``chunk`` (``chunk_step_ms``'s
    denominator).  None without a trace that holds scopes."""
    t = run.get("trace")
    if not t or not t.get("scopes"):
        return None
    _, n = tr.op_seconds(t, PROGRAM, tr.MODULES)
    return scope_seconds(t, scope) / (n * run["chunk"]) * 1e3 if n else None


def phases(row: dict) -> Dict[str, float]:
    """A tick row's seconds in each phase that ran."""
    return {name: row[b] - row[a] for name, a, b in PHASES
            if row.get(a) is not None and row.get(b) is not None}


def host_ms_per_tick(run: dict) -> Optional[float]:
    """Median over the window's dispatching ticks of the host time outside
    the device wait: (dispatch return - loop start) + (end - readback
    done), in ms."""
    host = [(r["returned"] - r["start"]) + (r["end"] - r["ready"])
            for r in run.get("sched_ticks") or []
            if r.get("returned") is not None and r.get("ready") is not None]
    return statistics.median(host) * 1e3 if host else None


def longest_ticks(rows: List[dict], n: int = 3) -> List[dict]:
    """The ``n`` longest ticks, each with its phase split in seconds."""
    top = sorted(rows, key=lambda r: r["start"] - r["end"])[:n]
    return [{"tick": r["tick"], "seconds": r["end"] - r["start"],
             "phases": phases(r)} for r in top]


def idle_split(t: dict) -> Dict[str, float]:
    """Idle seconds of the window by the span that labels each gap, and the
    share of them labelled by a ``sched.`` span."""
    gaps = dict(tr.idle_gaps(t, n=1000))
    total = sum(gaps.values())
    gaps["sched_share"] = (sum(v for k, v in gaps.items()
                               if k.startswith(SPAN)) / total
                           if total else None)
    return gaps


def idle_by_phase(t: dict) -> Dict[str, float]:
    """Idle seconds of the window cut at every ``sched.`` span boundary and
    put down, piece by piece, to the innermost span over each piece (``none``
    where no span is): unlike ``trace.idle_gaps``, which gives a whole gap
    to the span at its midpoint, this splits one gap across the phases it
    spans."""
    lo, hi = tr.window(t)
    busy = tr._union(tr._clip(t[tr.OPS], lo, hi))
    spans = [(float(s), float(s) + float(d), name) for name, s, d in t["host"]
             if name.startswith(SPAN)]
    cuts = sorted({x for a, b, _ in spans for x in (a, b)})
    out: Dict[str, float] = {}
    prev = lo
    for a, b in busy + [(hi, hi)]:
        if a > prev:
            edges = [prev] + [c for c in cuts if prev < c < a] + [a]
            for x, y in zip(edges, edges[1:]):
                mid = (x + y) / 2
                cover = [sp for sp in spans if sp[0] <= mid < sp[1]]
                name = (min(cover, key=lambda sp: sp[1] - sp[0])[2]
                        if cover else "none")
                out[name] = out.get(name, 0.0) + (y - x) / 1e9
        prev = max(prev, b)
    return out


def one_tick(run: dict, k: int = 1) -> dict:
    """A copy of ``run`` whose trace is cut to the ``k``-th dispatching
    ``sched.tick`` span of the window, with short op names: a small
    recorded fixture."""
    t = run["trace"]
    lo, hi = tr.window(t)
    ticks = sorted((s, s + d) for name, s, d in t["host"]
                   if name == SPAN + "tick" and lo <= s and s + d <= hi)
    disp = [s for name, s, _ in t["host"] if name == SPAN + "dispatch"]
    busy = [(a, b) for a, b in ticks if any(a <= s < b for s in disp)]
    a, b = busy[min(k, len(busy) - 1)]

    def keep(events, full=True):
        return [[e[0] if full else _short(e[0]), e[1], e[2]] for e in events
                if float(e[1]) < b and float(e[1]) + float(e[2]) > a]

    ops = keep(t[tr.OPS], full=False)
    names = {op_key(e[0]) for e in ops}
    host = [e for e in keep(t["host"]) if e[0] != tr.WINDOW]
    cut = {tr.OPS: ops, tr.MODULES: keep(t[tr.MODULES]),
           "host": [[tr.WINDOW, a, b - a]] + host, "device": t["device"],
           "scopes": {k: v for k, v in t["scopes"].items() if k in names}}
    return {"trace": cut, "sched_ticks": run["sched_ticks"],
            "chunk": run["chunk"]}


def _short(name: str) -> str:
    m = tr._OP.match(name)
    return f"{m.group(1)} = {m.group(2)}" if m else name


def record_cost_us(sched, ticks: int = 20000) -> float:
    """Host microseconds a tick spends on its record and spans, with the
    profiler off: a fresh row, the six phase helpers a busy tick opens, its
    counters and the append, on ``sched`` (a scheduler done serving; its
    ``tick_log`` is overwritten), timed over ``ticks`` ticks."""
    from repro.serving.scheduler import TickRecord
    phase = type(sched)._phase
    t0 = time.perf_counter()
    for k in range(ticks):
        sched._row = TickRecord(k, sched.clock(), tokens=0)
        with phase(sched, "sched.tick"):
            with phase(sched, "sched.admit"):
                pass
            with phase(sched, "sched.prepare", "prepare"):
                pass
            with phase(sched, "sched.dispatch", "dispatch"):
                pass
            with phase(sched, "sched.readback", "returned"):
                pass
            with phase(sched, "sched.book", "ready"):
                sched._row.active_lanes = sched._row.prefill_lanes = 0
                sched._row.tokens += 1
        sched._row.end = sched.clock()
        sched.tick_log.append(sched._row)
    return (time.perf_counter() - t0) / ticks * 1e6


def measure(bench, workload: str, seed: int, seconds: float,
            t_start: float, trace: bool = True,
            compile_cache: bool = True) -> dict:
    """One run of ``workload`` through the accepted harness, with the
    program's tick rows, and with ``trace`` its scoped trace, added to its
    record."""
    import dataclasses

    from perfbench import harness

    got: dict = {}

    def capture(engine, sched):
        got["sched"], got["jit"] = sched, sched._chunk_jit

    class Profiler(harness._Profiler):
        def reduce(self):
            if self.done:
                got["trace"] = read(self.dir, _chunk_hlo(got["sched"],
                                                         got["jit"]))
            return super().reduce()

    accepted = harness._Profiler
    harness._Profiler = Profiler
    try:
        rec = harness.run(bench, workload, seed, seconds, trace, t_start,
                          compile_cache=compile_cache, break_program=capture)
    finally:
        harness._Profiler = accepted
    if "trace" in got:
        rec["trace"] = got["trace"]
    lo, hi = rec["t_open"], rec["t_open"] + rec["window_s"]
    rec["sched_ticks"] = [dataclasses.asdict(r) for r in got["sched"].tick_log
                          if lo <= r.start and r.end <= hi]
    rec["record_cost_us"] = record_cost_us(got["sched"])
    return rec


def _chunk_hlo(sched, jit) -> str:
    """The compiled chunk program's HLO text (the harness has wrapped the
    scheduler's jitted step by now, so lower the one captured before)."""
    wrapped, sched._chunk_jit = sched._chunk_jit, jit
    try:
        return sched.lower_chunk().compile().as_text()
    finally:
        sched._chunk_jit = wrapped


def summary(rec: dict) -> dict:
    """What the program's tracing gives for one run record."""
    out = {"host_ms_per_tick": host_ms_per_tick(rec),
           "kv_write_step_ms": scope_step_ms(rec, "kv_write"),
           "lane_select_step_ms": scope_step_ms(rec, "lane_select"),
           "longest_ticks": longest_ticks(rec["sched_ticks"]),
           "window_ticks": len(rec["sched_ticks"])}
    t = rec.get("trace")
    if t and t.get("scopes") is not None:
        out["device_scopes"] = device_scopes(t)
        out["unscoped_ops"] = unscoped_ops(t)
        out["idle_gaps"] = idle_split(t)
        out["idle_by_phase"] = idle_by_phase(t)
        out["scoped_ops"] = len(t["scopes"])
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="one run of a cell with the "
                                 "program's tick rows (and scopes, traced)")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--fixture", help="write one tick of the trace here")
    args = ap.parse_args(argv)
    sys.path[:0] = [root, os.path.join(root, "src")]
    import jax

    from perfbench import run as bench_run
    from perfbench import spec
    bench = spec.Bench(root)
    cell = bench.workload(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"program_trace: cell {args.workload} needs {cell['chips']} "
              f"TPU chip(s), found {devices[0].platform!r}", file=sys.stderr)
        return 1
    rec = measure(bench, args.workload, args.seed, args.seconds, t_start,
                  bool(args.trace))
    line = bench_run.result_line(bench, args.workload, bool(args.trace), rec)
    line["tokens_per_s"] = (rec["tokens"] / rec["window_s"]
                            if rec["tokens"] is not None else None)
    line["setup_s"] = rec["setup_s"]
    line["program"] = summary(rec)
    line["program"]["record_cost_us"] = rec["record_cost_us"]
    print(json.dumps(line), flush=True)
    if args.fixture and rec.get("trace"):
        tr.save(one_tick(rec), args.fixture)
    return 0


if __name__ == "__main__":
    sys.exit(main())
