"""The reduction from trace to metrics: busy and idle time, kernel and
program time, and the gaps' host labels, on a hand-made trace with known
answers and on a small trace recorded on a TPU v5e."""
import os

import numpy as np
import pytest

from perfbench import costs, readers
from perfbench import trace as tr
from perfbench.tests import smoke

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_dms_reasoning.json.gz")
MS = 1_000_000


def _synthetic():
    # window 0-100 ms; ops busy 10-25, 25-40, 60-70 and 95-120 (clipped
    # at 100); host: tick 0-50 with dispatch 0-12, tick 55-100
    return {
        "XLA Ops": [["fusion.1", 10 * MS, 15 * MS],
                    ["%reshape.3 = bf16[4] reshape(%dms_decode.1)",
                     40 * MS, 1 * MS],
                    ["dms_decode", 25 * MS, 15 * MS],
                    ["dms_decode", 60 * MS, 10 * MS],
                    ["fusion.1", 95 * MS, 25 * MS],
                    ["before", -20 * MS, 5 * MS]],
        "XLA Modules": [["jit_chunk_fn(1)", 10 * MS, 30 * MS],
                        ["jit_chunk_fn(2)", 60 * MS, 40 * MS],
                        ["jit_reset_fn", 45 * MS, 1 * MS]],
        "host": [["bench.window", 0, 100 * MS],
                 ["bench.tick", 0, 50 * MS],
                 ["bench.dispatch", 0, 12 * MS],
                 ["bench.tick", 55 * MS, 45 * MS]],
        "device": "/device:TPU:0"}


def test_busy_is_the_union_of_op_intervals_in_the_window():
    t = _synthetic()
    assert tr.window_s(t) == pytest.approx(0.1)
    # 10-41 + 60-70 + 95-100 = 46 ms
    assert tr.busy_s(t) == pytest.approx(0.046)
    run = {"trace": t}
    assert readers.device_idle_pct(run) == pytest.approx(54.0)


def test_kernel_and_program_time():
    t = _synthetic()
    assert tr.op_seconds(t, "dms_decode") == (pytest.approx(0.025), 2)
    assert tr.op_seconds(t, "chunk_fn", tr.MODULES) == (
        pytest.approx(0.07), 2)
    run = {"trace": t, "chunk": 7}
    assert readers.chunk_step_ms(run) == pytest.approx(70 / 14)
    assert tr.top_ops(t)[:2] == [["dms_decode", pytest.approx(0.025)],
                                 ["fusion.1", pytest.approx(0.020)]]


def test_nested_ops_count_their_own_time():
    t = {"XLA Ops": [["%while.1 = (s32[]) while(%x)", 0, 10 * MS],
                     ["%fusion.2 = bf16[8] fusion(%y)", 2 * MS, 3 * MS],
                     ["%dms_decode.3 = bf16[8] custom-call(%z)", 6 * MS,
                      2 * MS]],
         "XLA Modules": [], "host": [["bench.window", 0, 10 * MS]]}
    assert dict(tr.top_ops(t)) == {"%while.1 s32[]": pytest.approx(0.005),
                                   "%fusion.2 bf16[8]": pytest.approx(0.003),
                                   "%dms_decode.3 bf16[8]": pytest.approx(
                                       0.002)}
    assert tr.busy_s(t) == pytest.approx(0.010)


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    gaps = dict(tr.idle_gaps(_synthetic()))
    # a gap goes whole to the span at its midpoint: 0-10 (dispatch),
    # 41-60 (midpoint 50.5: between ticks), 70-95 (tick 1)
    assert gaps == {"bench.dispatch": pytest.approx(0.010),
                    "bench.between_ticks": pytest.approx(0.019),
                    "bench.tick": pytest.approx(0.025)}


def test_roofline_share_is_the_bound_over_kernel_time():
    t = _synthetic()
    live = np.full((4, 2), 1000.0)
    run = {"trace": t, "counters_traced": {"live": live}, "hf": smoke.HF,
           "device_kind": "TPU v5 lite"}
    flops, nbytes = costs.kernel_flops_bytes(smoke.HF, live)
    bound = max(flops / 197e12, nbytes / 819e9)
    assert readers.kernel_roofline_pct(run, "dms_decode") == pytest.approx(
        bound / 0.025 * 100)
    assert readers.kernel_roofline_pct(dict(run, trace=None),
                                       "dms_decode") is None


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="recorded trace fixture absent")
def test_recorded_chip_trace():
    t = tr.load(FIXTURE)
    assert t["device"].startswith("/device:TPU:")
    w = tr.window_s(t)
    busy = tr.busy_s(t)
    assert 0 < busy <= w
    secs, n = tr.op_seconds(t, "chunk_fn", tr.MODULES)
    assert n >= 1 and 0 < secs <= w
    k, kn = tr.op_seconds(t, "dms_decode")
    assert kn == n * 8 * 28 and 0 < k < secs     # chunk 8 x 28 layers
    assert dict(tr.top_ops(t))[next(
        name for name, _ in tr.top_ops(t) if "dms_decode" in name)] == \
        pytest.approx(k)
    assert sum(v for _, v in tr.idle_gaps(t)) == pytest.approx(w - busy)
