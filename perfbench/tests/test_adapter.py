"""The clock adapter changes nothing of what the scheduler decides: the
same submissions give the same tokens and the same ticks as a plain
``Scheduler.run()``, with open-loop submission and pauses in between."""
import dataclasses

import jax
import numpy as np
import pytest

from perfbench import adapter, traffic
from repro.configs import get_smoke
from repro.core.config import KVPolicyConfig
from repro.models import transformer as tfm
from repro.serving.engine import Engine
from repro.serving.scheduler import Request

LANES, MAX_LEN = 8, 64


@pytest.fixture(scope="module")
def engine():
    arch = get_smoke("qwen-r1-1.5b")
    arch = dataclasses.replace(arch, dms=dataclasses.replace(
        arch.dms, window=4, logit_bias=0.5))
    params = tfm.init_model(jax.random.PRNGKey(0), arch)
    return Engine(arch, params, KVPolicyConfig(kind="dms", cr=4.0, window=4))


def _requests(n=10):
    mix = {"arrival": {"kind": "poisson", "rate_per_s": 1.0},
           "prompt_len": {"dist": "loguniform", "min": 3, "max": 20},
           "max_new": {"dist": "loguniform", "min": 2, "max": 12}}
    reqs = traffic.generate(mix, 7, 512, float(n))
    widths = [1, 2, 1, 4, 1, 2, 1, 1, 3, 1]
    return [(r, widths[i % len(widths)]) for i, r in enumerate(reqs)]


def _key(results):
    return {r.uid: (r.tokens.tobytes(), tuple(r.lengths), r.status,
                    r.admitted_tick, r.first_token_tick, r.finished_tick)
            for r in results}


def test_open_loop_submission_and_pauses_match_plain_run(engine):
    reqs = _requests()
    # adapter: each request submitted at the tick its (tick-valued) due
    # time comes, the run paused and resumed at every fifth boundary
    clocked = adapter.attach(engine.scheduler(LANES, MAX_LEN))
    arrival = {}
    pending = [(int(r.due_s), r, w) for r, w in reqs]

    def source(s):
        while pending and pending[0][0] <= s.ticks:
            _, r, w = pending.pop(0)
            arrival[r.uid] = s.ticks
            s.submit(Request(uid=r.uid, prompt=r.prompt, max_new=r.max_new,
                             width=w, arrival=s.ticks))

    clocked.source = source
    clocked.pause_when = lambda s: s.ticks % 5 == 0 and s.starts.get(
        s.ticks) is None and s.ticks not in paused
    paused = set()
    while pending or clocked.queue or clocked.active_reqs:
        source(clocked)
        if clocked.drive():
            paused.add(clocked.ticks)
        elif pending:
            clocked.ticks = max(clocked.ticks, pending[0][0])
    assert paused, "the run was never paused"

    # plain: the same requests with the arrivals the adapter recorded
    plain = engine.scheduler(LANES, MAX_LEN)
    for r, w in reqs:
        plain.submit(Request(uid=r.uid, prompt=r.prompt, max_new=r.max_new,
                             width=w, arrival=arrival[r.uid]))
    plain_res = plain.run()
    assert _key(clocked.retired()) == _key(plain_res)
    assert len(plain_res) == len(reqs)
    # every dispatched tick has both stamps, in order
    for k, t in clocked.starts.items():
        if k in clocked.ends:
            assert clocked.ends[k] >= t


def test_closed_batch_matches_plain_run(engine):
    reqs = _requests()
    clocked = adapter.attach(engine.scheduler(LANES, MAX_LEN))
    plain = engine.scheduler(LANES, MAX_LEN)
    for s in (clocked, plain):
        for r, w in reqs:
            s.submit(Request(uid=r.uid, prompt=r.prompt, max_new=r.max_new,
                             width=w))
    clocked.pause_when = lambda s: s.ticks == 3 and 3 not in s.starts
    assert clocked.drive()
    tokens_at_pause = clocked.tokens_so_far()
    clocked.pause_when = None
    assert not clocked.drive()
    res = plain.run()
    assert _key(clocked.retired()) == _key(res)
    assert clocked.tokens_so_far() == sum(int(r.lengths.sum()) for r in res)
    assert 0 <= tokens_at_pause < clocked.tokens_so_far()
    assert np.all(np.diff(sorted(clocked.starts)) == 1)
