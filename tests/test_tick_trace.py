"""Tick-loop tracing: the scheduler's per-tick record (``tick_log``), its
``sched.*`` profiler spans, and the wall-clock stamps on each result.

Pinned here: one row per tick with ordered stamps; the rows' token counts
add up to the results' tokens; ``prefill_lanes`` follows the prefill
schedule; the spans nest as docs/serving.md says; and tracing with the
profiler on changes no result bit.
"""
import glob
import math
import weakref

import jax
import numpy as np
import pytest

from repro.core.config import KVPolicyConfig
from repro.serving.engine import Engine
from repro.serving.scheduler import TICK_LOG_ROWS, Request, TickRecord

CHUNK = 4
STAMPS = ("start", "prepare", "dispatch", "returned", "ready", "end")
PHASES = ("sched.admit", "sched.prepare", "sched.dispatch", "sched.readback",
          "sched.book")

# (uid, prompt length, max_new, width, arrival): two prompts of different
# chunk counts at tick 0, a fork, and an arrival after an idle stretch
MIX = [(0, 10, 9, 1, 0), (1, 17, 6, 1, 0), (2, 6, 5, 2, 1),
       (3, 5, 3, 1, 30)]


@pytest.fixture(scope="module")
def engine(tiny_arch, tiny_params):
    return Engine(tiny_arch, tiny_params, KVPolicyConfig(kind="dms", cr=2.0),
                  chunk=CHUNK)


def _serve(engine, mix=MIX, lanes=4):
    sched = engine.scheduler(num_lanes=lanes, max_len=40)
    rng = np.random.default_rng(5)
    for uid, plen, new, width, arrival in mix:
        sched.submit(Request(
            uid=uid, prompt=rng.integers(3, 500, size=plen).astype(np.int32),
            max_new=new, width=width, arrival=arrival))
    return sched, sched.run()


@pytest.fixture(scope="module")
def served(engine):
    return _serve(engine)


def _stamps(row):
    return [getattr(row, k) for k in STAMPS if getattr(row, k) is not None]


def test_one_row_per_tick_with_ordered_stamps(served):
    sched, results = served
    rows = list(sched.tick_log)
    assert [r.tick for r in rows] == list(range(sched.ticks))
    for row in rows:
        s = _stamps(row)
        assert s == sorted(s), row
        assert row.start is not None and row.end is not None
    # a busy tick is stamped at every boundary and spends host time
    busy = [r for r in rows if r.dispatch is not None]
    assert busy and all(None not in (r.prepare, r.returned, r.ready)
                        for r in busy)
    # the idle stretch before the late arrival dispatches nothing
    idle = [r for r in rows if r.dispatch is None]
    assert idle and all(r.prepare is None and r.returned is None
                        and r.ready is None and r.active_lanes == 0
                        and r.tokens == 0 for r in idle)
    assert all(r.active_lanes > 0 for r in busy)


def test_row_tokens_add_up_to_the_results(served):
    sched, results = served
    assert all(r.status == "ok" for r in results)
    assert sum(r.tokens for r in sched.tick_log) == sum(
        int(r.lengths.sum()) for r in results)


def test_prefill_lanes_follow_the_prefill_schedule(served):
    sched, results = served
    # a prompt of T tokens admitted at tick a prefills in ticks
    # a .. a + ceil(T / chunk) - 1, one lane whatever its width
    want = np.zeros(sched.ticks, int)
    plen = {uid: p for uid, p, *_ in MIX}
    for r in results:
        a = r.admitted_tick
        want[a:a + math.ceil(plen[r.uid] / CHUNK)] += 1
    got = np.array([r.prefill_lanes for r in sched.tick_log])
    np.testing.assert_array_equal(got, want)
    assert ((got > 0) == (want > 0)).all()


def test_result_stamps_sit_inside_their_ticks(served):
    sched, results = served
    row = {r.tick: r for r in sched.tick_log}
    for r in results:
        assert (r.submitted_s <= r.admitted_s <= r.first_token_s
                <= r.finished_s)
        # the first token is sampled while its tick admits and forks
        ft = row[r.first_token_tick]
        assert ft.start <= r.first_token_s <= ft.prepare
        # a request finishes while the tick before finished_tick books
        fin = row[r.finished_tick - 1]
        assert fin.ready <= r.finished_s <= fin.end
        assert r.ttft_s == pytest.approx(r.first_token_s - r.submitted_s)
        gen = int(r.lengths.max())
        assert r.tpot_s == pytest.approx(
            (r.finished_s - r.first_token_s) / (gen - 1))


def test_clock_is_public_and_replaceable(engine):
    ticks = iter(range(10_000))
    sched = engine.scheduler(num_lanes=2, max_len=40)
    sched.clock = lambda: float(next(ticks))
    sched.submit(Request(uid=0, prompt=np.arange(3, 9, dtype=np.int32),
                         max_new=3))
    (res,) = sched.run()
    assert res.submitted_s == 0.0
    assert all(float(s).is_integer() for row in sched.tick_log
               for s in _stamps(row))


def test_tick_log_keeps_the_latest_rows(engine):
    sched = engine.scheduler(num_lanes=2, max_len=40)
    assert sched.tick_log.maxlen == TICK_LOG_ROWS
    for k in range(TICK_LOG_ROWS + 3):
        sched.tick_log.append(TickRecord(k, 0.0))
    assert sched.tick_log[0].tick == 3


def _xplane_spans(path):
    from jax.profiler import ProfileData
    (pb,) = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(pb).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name.startswith("sched.")]
    return out


def _inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


@pytest.fixture(scope="module")
def traced(engine, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(path):
        sched, results = _serve(engine)
    return sched, results, _xplane_spans(path)


def test_profiler_on_changes_no_result_bit(served, traced):
    (_, plain), (_, on, _) = served, traced
    key = lambda rs: {r.uid: (r.tokens.tobytes(), r.lengths.tobytes(),
                              r.status, r.admitted_tick, r.first_token_tick,
                              r.finished_tick, r.meter.kv_reads)
                      for r in rs}
    assert key(plain) == key(on)


def test_spans_appear_nested_as_documented(traced):
    sched, _, spans = traced
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    ticks = by["sched.tick"]
    assert len(ticks) == sched.ticks
    for name in PHASES:
        assert name in by, name
        assert all(_inside(s, ticks) for s in by[name]), name
    # one admission per iteration; the other phases only on busy ticks
    assert len(by["sched.admit"]) == sched.ticks
    busy = sum(r.dispatch is not None for r in sched.tick_log)
    for name in PHASES[1:]:
        assert len(by[name]) == busy, name
    assert by["sched.reclaim"] and all(_inside(s, by["sched.book"])
                                       for s in by["sched.reclaim"])
    # the phases of one tick follow each other in the documented order
    first = min(ticks, key=lambda s: s[1])
    order = sorted((s for s in spans if s[0] in PHASES and _inside(s, [first])),
                   key=lambda s: s[1])
    assert [s[0] for s in order] == list(PHASES)



def test_the_dispatched_state_is_released_before_booking(engine):
    """Splitting the tick into phases must not keep the chunk's input state
    alive while the tick books (a lane reset there would hold a third
    decode state on the device)."""
    sched = engine.scheduler(num_lanes=2, max_len=40)
    sched.submit(Request(uid=0, prompt=np.arange(3, 7, dtype=np.int32),
                         max_new=2))
    dispatched, alive = [], []
    chunk_jit, book = sched._chunk_jit, sched._book

    def spy_chunk(*args):
        out = chunk_jit(*args)
        new = {id(x) for x in jax.tree_util.tree_leaves(out[0])}
        dispatched.append([weakref.ref(x) for x in
                           jax.tree_util.tree_leaves(args[1])
                           if id(x) not in new])
        return out

    def spy_book(*a, **kw):
        alive.append(sum(r() is not None for r in dispatched[-1]))
        return book(*a, **kw)

    sched._chunk_jit, sched._book = spy_chunk, spy_book
    (res,) = sched.run()
    assert res.status == "ok" and dispatched and all(dispatched)
    assert alive == [0] * len(dispatched)
