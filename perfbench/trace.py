"""From the profiler's trace to numbers.

``read`` keeps, from a ``*.xplane.pb``, the events of the first TPU's
``XLA Ops`` and ``XLA Modules`` lines and the benchmark's own host spans
(names starting ``bench.``), as plain lists ``[name, start_ns, dur_ns]``.
Everything else here works on that dict, so a small recorded trace checks
the arithmetic without a chip.  The span ``bench.window`` marks the traced
window on the trace's own clock.
"""
from __future__ import annotations

import glob
import gzip
import json
import re
from typing import Dict, List, Tuple

OPS, MODULES = "XLA Ops", "XLA Modules"
_OP = re.compile(r"(%\S+) = \(?([a-z0-9]+\[[\d,]*\])")
WINDOW = "bench.window"


def read(profile_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {profile_dir}")
    data = ProfileData.from_file(paths[0])
    out: Dict[str, list] = {OPS: [], MODULES: [], "host": []}
    devices = sorted(p.name for p in data.planes
                     if p.name.startswith("/device:TPU:"))
    for plane in data.planes:
        if devices and plane.name == devices[0]:
            for line in plane.lines:
                if line.name in (OPS, MODULES):
                    out[line.name] += [[e.name, e.start_ns, e.duration_ns]
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events
                                if e.name.startswith("bench.")]
    out["device"] = devices[0] if devices else None
    return out


def save(tr: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(tr, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def window(tr: dict) -> Tuple[float, float]:
    spans = [e for e in tr["host"] if e[0] == WINDOW]
    if not spans:
        raise ValueError("trace has no bench.window span")
    _, s, d = spans[0]
    return float(s), float(s + d)


def _clip(events, lo: float, hi: float) -> List[Tuple[str, float, float]]:
    out = []
    for name, s, d in events:
        a, b = max(float(s), lo), min(float(s) + float(d), hi)
        if b > a:
            out.append((name, a, b))
    return out


def _union(iv) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted((a, b) for _, a, b in iv):
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def busy_s(tr: dict) -> float:
    """Seconds of the window in which some operation ran on the device."""
    lo, hi = window(tr)
    return sum(b - a for a, b in _union(_clip(tr[OPS], lo, hi))) / 1e9


def window_s(tr: dict) -> float:
    lo, hi = window(tr)
    return (hi - lo) / 1e9


def op_seconds(tr: dict, needle: str, line: str = OPS) -> Tuple[float, int]:
    """Total device seconds and count of the events on ``line`` whose op
    name (not its operands) holds ``needle``, inside the window."""
    lo, hi = window(tr)
    ev = [e for e in _clip(tr[line], lo, hi)
          if needle in e[0].split(" = ")[0]]
    return sum(b - a for _, a, b in ev) / 1e9, len(ev)


def _self_times(events) -> List[Tuple[str, float]]:
    """Each op's own time: its interval less the ops nested in it (a
    ``while`` holds the ops of its body on the same line)."""
    out: List[Tuple[str, float]] = []
    stack: List[list] = []             # [name, end, own time]
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= b - a
        stack.append([name, b, b - a])
    out += [(name, own) for name, _, own in stack]
    return out


def short_name(hlo: str) -> str:
    """``%name = type[shape]...`` → ``%name type[shape]``."""
    m = _OP.match(hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:80]


def top_ops(tr: dict, n: int = 10) -> List[list]:
    """The device ops that took most time of their own in the window,
    summed by name."""
    lo, hi = window(tr)
    tot: Dict[str, float] = {}
    for name, own in _self_times(_clip(tr[OPS], lo, hi)):
        key = short_name(name)
        tot[key] = tot.get(key, 0.0) + own / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: dict, n: int = 10) -> List[list]:
    """Idle device time, summed by the innermost benchmark host span that
    covers each gap's midpoint (``bench.between_ticks`` where none does:
    admission, forks, lane resets)."""
    lo, hi = window(tr)
    busy = _union(_clip(tr[OPS], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    host = [(name, float(s), float(s) + float(d)) for name, s, d in tr["host"]
            if name != WINDOW]
    tot: Dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        covering = [h for h in host if h[1] <= mid < h[2]]
        label = (min(covering, key=lambda h: h[2] - h[1])[0]
                 if covering else "bench.between_ticks")
        tot[label] = tot.get(label, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
