"""Metric arithmetic: a rate is all the work of the window over all of
its time; a tail is over every request due in it, and a failed or
unfinished request counts as missing."""
import math

import numpy as np
import pytest

from perfbench import readers
from perfbench.tests import smoke
from perfbench import spec


def _req(due, first, fin, tokens=11, status="ok", admitted=None):
    return {"uid": 0, "status": status, "tokens": tokens, "due": due,
            "submitted": due, "admitted": due if admitted is None
            else admitted, "first_token": first, "finished": fin}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return spec.Bench(smoke.build(str(tmp_path_factory.mktemp("bench"))))


def _read(bench, name, run):
    return bench.reader(name).read(run)


def test_rate_is_all_tokens_over_the_whole_window(bench):
    run = {"tokens": 3000, "window_s": 12.0}
    assert _read(bench, "tokens_per_s", run) == 250.0
    assert _read(bench, "tokens_per_s", {"tokens": None,
                                         "window_s": 1.0}) is None


def test_tail_is_over_all_requests():
    vals = list(range(1, 101))
    assert readers.p95(vals) == pytest.approx(np.percentile(vals, 95))


def test_a_missing_request_counts_against_the_tail(bench):
    ok = [_req(0.0, 1.0 + i / 100, 2.0 + i / 100) for i in range(19)]
    run = {"mode": "open", "requests": ok + [_req(0.0, 9.0, 10.0)]}
    with_slow = _read(bench, "ttft_p95_s", run)
    run["requests"][-1] = _req(0.0, None, None, status="failed")
    # the failed request is worse than any served one: the tail is then a
    # missing request and the metric has nothing to report
    assert _read(bench, "ttft_p95_s", run) is None
    # with enough requests a single miss leaves the tail finite, and higher
    run = {"mode": "open", "requests": ok * 5 + [_req(0.0, None, None,
                                                      status="failed")]}
    assert math.isfinite(_read(bench, "ttft_p95_s", run))
    assert with_slow > _read(bench, "ttft_p95_s", {"mode": "open",
                                                   "requests": ok})


def test_ttft_tpot_and_queue_wait_from_due_time(bench):
    reqs = [_req(10.0, 12.0, 22.0, tokens=11, admitted=10.5)] * 20
    run = {"mode": "open", "requests": reqs}
    assert _read(bench, "ttft_p95_s", run) == pytest.approx(2.0)
    assert _read(bench, "tpot_p95_ms", run) == pytest.approx(1000.0)
    assert _read(bench, "sched.queue_wait_p95_s", run) == pytest.approx(0.5)
    assert _read(bench, "ttft_p95_s", dict(run, mode="closed")) is None


def test_lane_util_and_kv_reads(bench):
    run = {"active_lanes": [8, 4, 0, 4], "lanes": 8}
    assert _read(bench, "sched.lane_util", run) == pytest.approx(50.0)
    reads = np.array([[10.0, 20.0], [30.0, 0.0]])
    run = {"counters": {"reads": reads, "act": reads > 0,
                        "live": reads, "ticks": 1}}
    assert _read(bench, "kv_reads_per_step", run) == pytest.approx(30.0)
    assert _read(bench, "kv_reads_per_step", {"counters": None}) is None


def test_step_mfu_counts_active_lane_steps_only(bench):
    hf = smoke.HF
    act = np.array([[True, False], [True, True]])
    live = np.array([[100.0, 500.0], [50.0, 0.0]])
    window = {"host": [["bench.window", 0.0, 2e9]]}       # 2 s, in ns
    run = {"counters_traced": {"act": act, "live": live, "reads": live},
           "trace": window, "hf": hf, "window_s": 30.0,
           "device_kind": "TPU v5 lite"}
    from perfbench import costs
    per_tok = 2.0 * costs.matmul_params(hf)
    attn = 4.0 * hf["num_attention_heads"] * hf["head_dim"]
    want = (3 * per_tok + attn * 150.0) / (2.0 * 197e12) * 100
    assert _read(bench, "step_mfu", run) == pytest.approx(want)
    with pytest.raises(KeyError, match="no peaks"):
        _read(bench, "step_mfu", dict(run, device_kind="cpu"))
    assert _read(bench, "step_mfu", dict(run, trace=None)) is None
