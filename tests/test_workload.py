"""Workload generators: seeded determinism, arrival-process shape, and the
multi-turn prefix-rehit property.

The determinism contract is the whole point of ``repro.serving.workload``:
same seed ⇒ bit-identical ``Request`` trace (uids, arrivals, prompts,
budgets, widths), so tests, benchmarks, and the chaos harness replay the
exact traffic they were calibrated on.  The checker is plain code shared by
a seeded deterministic test and a hypothesis ``@given`` fuzzer.
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.serving import workload
from repro.serving.workload import WorkloadSpec

SPEC = WorkloadSpec(vocab=64, max_len=24, prompt_len=(4, 10),
                    max_new=(2, 6), widths=(1, 2), eos_id=5, deadline=12)


def _trace_fields(reqs):
    return [(r.uid, r.arrival, r.max_new, r.width, r.eos_id, r.deadline,
             tuple(r.prompt.tolist())) for r in reqs]


def check_trace_contract(reqs, spec, n):
    """Every generator output obeys the submit contract and spec bounds."""
    assert len(reqs) == n
    assert [r.uid for r in reqs] == list(range(n))
    arrivals = [r.arrival for r in reqs]
    assert arrivals == sorted(arrivals), "traces are sorted by arrival"
    for r in reqs:
        assert spec.prompt_len[0] <= len(r.prompt) <= spec.prompt_len[1]
        assert spec.max_new[0] <= r.max_new <= spec.max_new[1]
        assert len(r.prompt) + r.max_new <= spec.max_len
        assert r.width in spec.widths
        assert r.prompt.dtype == np.int32
        assert (r.prompt >= 2).all() and (r.prompt < spec.vocab).all()
        if spec.eos_id is not None:
            assert not (r.prompt == spec.eos_id).any()


def check_determinism(make):
    """same seed ⇒ bit-identical trace; different seed ⇒ a distinct one."""
    a, b, c = make(7), make(7), make(8)
    assert _trace_fields(a) == _trace_fields(b)
    assert _trace_fields(a) != _trace_fields(c)


@pytest.mark.parametrize("gen", ["poisson", "burst"])
def test_trace_determinism_and_contract_seeded(gen):
    n = 12
    if gen == "poisson":
        def make(seed):
            return workload.poisson_trace(seed, n, rate=0.7, spec=SPEC)
    else:
        def make(seed):
            return workload.burst_trace(seed, n, rate=1.5, on_ticks=4,
                                        off_ticks=6, spec=SPEC)
    check_determinism(make)
    check_trace_contract(make(3), SPEC, n)


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=10 ** 6),
       st.integers(min_value=1, max_value=20),
       st.floats(min_value=0.1, max_value=3.0))
def test_trace_determinism_and_contract_fuzzed(seed, n, rate):
    def make(s):
        return workload.poisson_trace(s, n, rate=rate, spec=SPEC)
    if n >= 2:        # a 1-request trace can collide across seeds
        check_determinism(make)
    check_trace_contract(make(seed), SPEC, n)


def test_burst_arrivals_respect_off_windows():
    """No arrival ever lands in an off window, and the within-burst offsets
    span the on window (it is a burst, not a point mass)."""
    on, off = 4, 8
    arr = workload.burst_arrivals(0, 200, rate=2.0, on_ticks=on,
                                  off_ticks=off)
    offsets = arr % (on + off)
    assert (offsets < on).all(), "arrival inside an off window"
    assert len(np.unique(offsets)) > 1
    assert len(np.unique(arr // (on + off))) > 1, "all in one burst"


def test_poisson_arrivals_rate_scales_span():
    """Higher rate compresses the same request count into fewer ticks."""
    slow = workload.poisson_arrivals(0, 100, 0.25)
    fast = workload.poisson_arrivals(0, 100, 2.5)
    assert slow[-1] > fast[-1] * 3
    assert (np.diff(slow) >= 0).all() and (np.diff(fast) >= 0).all()


def test_multi_turn_sessions_rehit_their_prefix():
    """Within a session, every turn's prompt starts with the previous turn's
    full prompt (the radix prefix-cache re-hit shape), and the previous
    turn's simulated reply is embedded right after it."""
    spec = WorkloadSpec(vocab=64, max_len=96, prompt_len=(4, 8),
                        max_new=(2, 4))
    reqs = workload.multi_turn_trace(0, sessions=3, turns=3, spec=spec)
    assert len(reqs) > 3
    assert [r.uid for r in reqs] == list(range(len(reqs)))
    # group turns by session: within a session prompts are strict prefix
    # extensions, so sorting by length recovers turn order
    by_head = {}
    for r in reqs:
        by_head.setdefault(tuple(r.prompt[:4].tolist()), []).append(r)
    multi = [sorted(v, key=lambda r: len(r.prompt))
             for v in by_head.values() if len(v) > 1]
    assert multi, "no session produced two turns"
    for turns in multi:
        for prev, nxt in zip(turns, turns[1:]):
            assert len(nxt.prompt) > len(prev.prompt)
            np.testing.assert_array_equal(
                nxt.prompt[:len(prev.prompt)], prev.prompt,
                err_msg="turn does not extend its session context")
            assert nxt.arrival > prev.arrival


def test_multi_turn_determinism():
    spec = WorkloadSpec(vocab=64, max_len=64, prompt_len=(4, 8),
                        max_new=(2, 4))

    def make(seed):
        return workload.multi_turn_trace(seed, sessions=2, turns=3,
                                         spec=spec)
    a, b, c = make(1), make(1), make(2)
    assert _trace_fields(a) == _trace_fields(b)
    assert _trace_fields(a) != _trace_fields(c)


def test_spec_validation():
    with pytest.raises(ValueError, match="prompt_len"):
        WorkloadSpec(vocab=64, max_len=24, prompt_len=(5, 4))
    with pytest.raises(ValueError, match="max_len"):
        WorkloadSpec(vocab=64, max_len=10, prompt_len=(4, 10),
                     max_new=(2, 6))
    with pytest.raises(ValueError, match="width_weights"):
        WorkloadSpec(vocab=64, max_len=24, widths=(1, 2),
                     width_weights=(1.0,))
    with pytest.raises(ValueError, match="rate"):
        workload.poisson_arrivals(0, 4, 0.0)


def test_trace_summary_offered_load():
    reqs = workload.burst_trace(0, 10, rate=1.5, on_ticks=4, off_ticks=6,
                                spec=SPEC)
    s = workload.trace_summary(reqs)
    assert s["requests"] == 10
    assert s["span_ticks"] >= 1
    assert s["prompt_tokens"] == sum(len(r.prompt) for r in reqs)
    assert s["max_new_tokens"] == sum(r.max_new * r.width for r in reqs)
    assert s["offered_tokens_per_tick"] == pytest.approx(
        (s["prompt_tokens"] + s["max_new_tokens"]) / s["span_ticks"])
    assert workload.trace_summary([])["requests"] == 0
