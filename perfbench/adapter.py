"""A wall clock for the scheduler, without changing what it decides.

``Scheduler`` counts time in ticks and has no public step.  ``attach``
turns a scheduler made by ``Engine.scheduler`` into a :class:`Clocked` one,
which only adds:

* ``starts[k]``: host time at tick k's boundary, after admission and forks,
  just before the chunk is dispatched (a first token sampled at tick k is on
  the host by then);
* ``ends[k]``: host time once tick k's outputs are read back and booked
  (tokens emitted and requests finished in tick k are known then);
* ``source(sched)``: called at the top of every loop iteration, before
  admission — an open-loop generator submits the requests now due there;
* ``pause_when(sched)``: checked at each tick boundary; when true,
  :class:`Paused` leaves ``run()`` before the tick changes anything, and a
  later ``run()`` carries on from the same boundary.

Results are read from ``retired()`` (the scheduler's own ledger of every
retired request), since a paused ``run()`` returns nothing.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

from repro.serving.scheduler import RequestResult, Scheduler


class Paused(Exception):
    pass


class Clocked(Scheduler):
    starts: Dict[int, float]
    ends: Dict[int, float]
    source: Optional[Callable[["Clocked"], None]]
    pause_when: Optional[Callable[["Clocked"], bool]]
    on_boundary: Optional[Callable[["Clocked"], None]]

    def _expire_queued(self, results) -> None:
        if self.source is not None:
            self.source(self)
        super()._expire_queued(results)

    def _record_timeline(self) -> None:
        if self.pause_when is not None and self.pause_when(self):
            raise Paused
        if self.on_boundary is not None:
            self.on_boundary(self)
        self.starts[self.ticks] = self.clock()
        super()._record_timeline()

    def _tick(self, results) -> None:
        k = self.ticks
        super()._tick(results)
        self.ends[k] = self.clock()

    def drive(self) -> bool:
        """``run()`` until the queue drains (False) or a pause (True)."""
        try:
            self.run()
        except Paused:
            return True
        return False

    def retired(self) -> List[RequestResult]:
        return list(self._finished)

    def in_flight(self) -> List[RequestResult]:
        """Requests still running, as results of what they have served so
        far (a greedy token is final once emitted)."""
        return [r.result(0.0, self.ticks) for r in self.active_reqs]

    def tokens_so_far(self) -> int:
        """Output tokens of every request, retired or in flight."""
        done = sum(int(r.lengths.sum()) for r in self._finished)
        return done + sum(len(c) for r in self.active_reqs for c in r.chains)


def attach(sched: Scheduler, clock=time.perf_counter) -> Clocked:
    sched.__class__ = Clocked
    sched.clock = clock
    sched.starts, sched.ends = {}, {}
    sched.source = sched.pause_when = sched.on_boundary = None
    return sched
