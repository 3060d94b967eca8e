"""Whether what the timed path served is right.

Once the window has closed and the program's state is freed, a sample of the
finished requests, drawn from the seed and always holding the longest, is
run through the configuration's plain reference, teacher-forced on each
chain's prompt and served tokens.  At each scored position the gap is how far
the served (greedy) token's logit lies below the reference's best logit
there; ``GAPS`` names the statistics of those gaps that a configuration may
hold to a limit (its ``check.limits``).  The control asks the same of the
reference computed in float8 (``precision="fp8"``): the gap of the token the
lower precision puts first.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

GAPS = ("max_gap", "p99_gap", "mean_gap", "off_best")


def select(results, prompts: Dict[int, np.ndarray], seed: int,
           count: int, max_tokens: int) -> list:
    """The longest ok request, then others in an order drawn from the seed,
    while the sample holds at most ``max_tokens`` sequence tokens."""
    ok = [r for r in results if r.status == "ok" and r.uid in prompts]
    if not ok:
        return []
    size = lambda r: len(prompts[r.uid]) * len(r.lengths) + int(
        r.lengths.sum())
    longest = max(ok, key=lambda r: (len(prompts[r.uid]) + int(
        r.lengths.max()), r.uid))
    rest = [r for r in ok if r is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    out, used = [longest], size(longest)
    for i in order:
        r = rest[int(i)]
        if len(out) >= count or used + size(r) > max_tokens:
            continue
        out.append(r)
        used += size(r)
    return out


def sequences(sample, prompts: Dict[int, np.ndarray]) -> List[dict]:
    """One sequence per served chain: prompt + served tokens, scored from
    the last prompt position on (each position against the token served
    after it)."""
    seqs = []
    for r in sample:
        p = np.asarray(prompts[r.uid], np.int32)
        for c in range(len(r.lengths)):
            n = int(r.lengths[c])
            if n == 0:
                continue
            seqs.append({"uid": r.uid, "tokens": np.concatenate(
                [p, r.tokens[c, :n].astype(np.int32)]), "start": len(p) - 1})
    return seqs


def _summary(gaps: List[np.ndarray], seqs: List[dict]) -> dict:
    """The widest gap, where it lies, and how the rest spread."""
    flat = np.concatenate(gaps)
    i = int(np.argmax([g.max() for g in gaps]))
    return {"max_gap": float(flat.max()),
            "max_gap_at": int(seqs[i]["start"] + np.argmax(gaps[i])),
            "p99_gap": float(np.quantile(flat, 0.99)),
            "mean_gap": float(flat.mean()),
            "off_best": float((flat > 0).mean()),
            "tokens": int(flat.size), "sequences": len(seqs)}


def served_gap(ref, seed: int, cfg: dict, seqs: List[dict]) -> dict:
    """Widest gap of a served token below the reference's best."""
    out = ref.score(seed, cfg, seqs, "f32")
    return _summary([o["best"] - o["served"] for o in out], seqs)


def control_gap(ref, seed: int, cfg: dict, seqs: List[dict]) -> dict:
    """The same, in the float32 reference, of the token the float8
    reference puts first at each position of the same sequences."""
    low = ref.score(seed, cfg, seqs, "fp8")
    probed = [dict(q, probe=o["arg"]) for q, o in zip(seqs, low)]
    out = ref.score(seed, cfg, probed, "f32")
    return _summary([o["best"] - o["probe"] for o in out], seqs)


def structural(results, vocab: int,
               expect: Optional[int] = None) -> List[str]:
    """Faults that need no reference: a request that did not end ``ok``,
    a token outside the vocabulary, a chain that emitted nothing."""
    faults = []
    for r in results:
        if r.status != "ok":
            faults.append(f"request {r.uid} ended {r.status}")
            continue
        for c, n in enumerate(r.lengths):
            toks = r.tokens[c, :int(n)]
            if n == 0:
                faults.append(f"request {r.uid} chain {c} emitted nothing")
            elif not ((toks >= 0) & (toks < vocab)).all():
                faults.append(f"request {r.uid}: token outside the vocabulary")
    if expect is not None and len(results) < expect:
        faults.append(f"{expect - len(results)} requests never finished")
    return faults
