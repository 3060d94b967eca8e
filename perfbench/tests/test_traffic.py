"""The traffic generator: seeded, stratified, within each mix's bounds."""
import json
import os

import numpy as np
import pytest

from perfbench import traffic

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")
SEED = 2 ** 31 + 12345           # seeds run past 32 signed bits
# an open loop with chat-like lengths; no cell serves one yet, so its rate
# here is only a test value
OPEN = {"arrival": {"kind": "poisson", "rate_per_s": 2.0},
        "prompt_len": {"dist": "lognormal", "median": 512, "sigma": 1.0,
                       "min": 32, "max": 2048},
        "max_new": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                    "min": 16, "max": 512},
        "width": 1}
MIXES = sorted(f[:-5] for f in os.listdir(TRAFFIC)
               if f.endswith(".json")) + ["open"]


def _mix(name):
    if name == "open":
        return OPEN
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def _key(reqs):
    return [(r.uid, r.due_s, r.prompt.tobytes(), r.max_new, r.width)
            for r in reqs]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a = traffic.generate(mix, SEED, 151936, 40.0, 4608)
    b = traffic.generate(mix, SEED, 151936, 40.0, 4608)
    assert _key(a) == _key(b)
    c = traffic.generate(mix, SEED + 1, 151936, 40.0, 4608)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_serves_the_same_work(name):
    """Stratified lengths in one fixed order: another seed changes token
    ids (and open-loop arrival times), not the sizes or their order."""
    mix = _mix(name)
    a = traffic.generate(mix, 1, 151936, 40.0, 4608)
    b = traffic.generate(mix, 2, 151936, 40.0, 4608)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert [r.prompt.tobytes() for r in a] != [r.prompt.tobytes() for r in b]
    q = traffic._quantiles(mix["max_new"], len(a))
    assert sorted(r.max_new for r in a) == sorted(q.tolist())


@pytest.mark.parametrize("name", MIXES)
def test_lengths_and_arrivals_within_the_mix(name):
    mix = _mix(name)
    reqs = traffic.generate(mix, SEED, 151936, 40.0, 4608)
    plen = np.array([len(r.prompt) for r in reqs])
    new = np.array([r.max_new for r in reqs])
    assert plen.min() >= mix["prompt_len"]["min"]
    assert plen.max() <= mix["prompt_len"]["max"]
    assert new.min() >= mix["max_new"]["min"]
    assert new.max() <= mix["max_new"]["max"]
    due = np.array([r.due_s for r in reqs])
    assert (np.diff(due) >= 0).all() and due.min() >= 0 and due.max() < 40.0
    toks = np.concatenate([r.prompt for r in reqs])
    assert toks.min() >= traffic.FIRST_TOKEN and toks.max() < 151936


def test_counts_and_medians():
    chat = traffic.generate(OPEN, SEED, 151936, 40.0, 4608)
    assert len(chat) == round(OPEN["arrival"]["rate_per_s"] * 40.0)
    assert np.median([r.max_new for r in chat]) == pytest.approx(128, rel=0.1)
    reason = traffic.generate(_mix("reasoning-w4"), SEED, 151936, 40.0, 4608)
    assert all(r.width == 4 and r.due_s == 0.0 for r in reason)
    assert np.median([r.max_new for r in reason]) == pytest.approx(2048,
                                                                   rel=0.05)


def test_too_long_for_max_len_is_refused():
    mix = _mix("reasoning-w4")
    with pytest.raises(ValueError, match="exceed max_len"):
        traffic.generate(mix, SEED, 151936, 40.0, 1024)
