"""One run of one cell: set-up, the measured window, the check, the record.

The window drives the serving stack as a deployment would:
``Engine.scheduler(lanes, max_len)`` → ``Scheduler.run`` → the chunk
program (``make_chunk_fn`` → ``decode_step``) → the configuration's KV
policy cache → the ``dms_decode`` kernel, with the shared-prefill fork
(``gather_lanes``) for width > 1.  The scheduler is clocked by
``perfbench.adapter`` and changes nothing of what it decides.

* A closed mix queues all its requests at once; set-up runs until every
  request admitted at the first tick has forked and is decoding, and the
  window then counts the output tokens of ``seconds`` seconds.
* An open mix submits each request once its due time has passed; the
  window is ``seconds`` of arrivals, and every request due in it is drained
  after it closes, each timed from its due time.

``run`` returns the run record that the metric readers read.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

from perfbench import adapter, check, spec, traffic

_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/jaxpr_trace_duration")
TRACE_SECONDS = 4.0          # traced part of the window, in its middle
WARM_UID = -1


class Compiles:
    """Compile seconds and compile count, from JAX's own monitoring
    events (as ``chip_smoke.py`` listens to them)."""

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0

        def on_duration(event, secs, **_):
            if event in _COMPILE_EVENTS:
                self.seconds += secs
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


def _recorder(fn: Callable, store: Dict[int, Any], sched) -> Callable:
    """Keep each tick's chunk outputs (already read back by the scheduler,
    so reading them again costs no sync) and name the dispatch in the
    trace."""
    import jax

    def call(*args):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            out = fn(*args)
        store[sched.ticks] = (out[10], out[9], out[8])    # act, reads, live
        return out

    return call


def _annotated(fn: Callable, name: str) -> Callable:
    import jax

    def call(*args):
        with jax.profiler.TraceAnnotation(name):
            return fn(*args)

    return call


def run(bench: spec.Bench, workload: str, seed: int, seconds: float,
        trace: bool, t_start: float, compile_cache: bool = True,
        break_program: Optional[Callable] = None,
        control: bool = False) -> dict:
    """Set up, measure, check.  ``compile_cache=False`` keeps CPU test
    runs out of the persistent cache; ``break_program(engine, sched)``
    plants a fault in the timed path, and ``control`` puts the float8
    reference in the program's place for the check (tests of the check
    only)."""
    import jax

    from repro.launch.compile_cache import setup_compile_cache
    from repro.serving.engine import Engine
    from repro.serving.scheduler import Request

    if compile_cache:
        setup_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = Compiles()
    cell = bench.workload(workload)
    cfg = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    model = spec.model_module(cfg)
    arch, policy = model.program_arch(cfg), model.program_policy(cfg)
    hf = cfg["hf"]

    params = model.program_params(seed, cfg)
    jax.block_until_ready(params)
    engine = Engine(arch, params, policy, chunk=cfg["chunk"])
    sched = adapter.attach(engine.scheduler(cfg["lanes"], cfg["max_len"],
                                            seed=seed))
    _check_arena(sched, cfg)
    if break_program is not None:
        break_program(engine, sched)
    width = int(mix.get("width", 1))

    # warm-up: one short request of the mix's width through prefill, fork,
    # decode and reclaim, so every program the window runs is compiled
    warm = np.arange(traffic.FIRST_TOKEN,
                     traffic.FIRST_TOKEN + cfg["chunk"] + 1, dtype=np.int32)
    sched.submit(Request(uid=WARM_UID, prompt=warm,
                         max_new=2 * cfg["chunk"], width=width))
    sched.drive()

    reqs = traffic.generate(mix, seed, hf["vocab_size"], seconds,
                            cfg["max_len"])
    prompts = {r.uid: r.prompt for r in reqs}
    closed = mix["arrival"]["kind"] == "closed"
    counters: Dict[int, Any] = {}
    if trace:
        sched._chunk_jit = _recorder(sched._chunk_jit, counters, sched)
        sched._tick = _annotated(sched._tick, "bench.tick")
    due_at: Dict[int, float] = {}
    submit_at: Dict[int, float] = {}

    if closed:
        for r in reqs:
            sched.submit(Request(uid=r.uid, prompt=r.prompt,
                                 max_new=r.max_new, width=r.width,
                                 arrival=sched.ticks))
        first: list = []

        def started(s) -> bool:
            if not first:
                first.extend(s.active_reqs)
            return all(r.first_token_tick >= 0 for r in first)

        sched.pause_when = started
        if not sched.drive():
            raise RuntimeError("the batch finished during set-up")

    t_open = time.perf_counter()
    setup_s = t_open - t_start
    compiles_open = compiles.count
    tok_open, tick_open = sched.tokens_so_far(), sched.ticks
    prof = _Profiler(t_open + max(0.0, (seconds - TRACE_SECONDS) / 2),
                     min(TRACE_SECONDS, seconds)) if trace else None
    if prof is not None:
        sched.on_boundary = prof.boundary

    if closed:
        sched.pause_when = lambda s: s.clock() >= t_open + seconds
        if not sched.drive():
            raise RuntimeError("the queue ran dry inside the window: the "
                               "mix needs more requests")
        t_close = time.perf_counter()
        tick_close = sched.ticks
        tokens = sched.tokens_so_far() - tok_open
    else:
        pending = list(reqs)

        def source(s) -> None:
            now = s.clock()
            while pending and t_open + pending[0].due_s <= now:
                r = pending.pop(0)
                due_at[r.uid] = t_open + r.due_s
                submit_at[r.uid] = now
                s.submit(Request(uid=r.uid, prompt=r.prompt,
                                 max_new=r.max_new, width=r.width,
                                 arrival=s.ticks))

        sched.source = source
        sched.pause_when = None
        while True:
            # run() returns once the system is idle; it only calls the
            # source while it has work, so an idle system is fed from here
            source(sched)
            sched.drive()
            if not pending:
                break
            time.sleep(max(0.0, t_open + pending[0].due_s
                           - time.perf_counter()))
        t_close = t_open + seconds
        tick_close = 1 + max((k for k, t in sched.starts.items()
                              if t < t_close), default=tick_open - 1)
        tokens = None
    if prof is not None:
        prof.stop()
    sched.on_boundary = None
    compiles_in_window = compiles.count - compiles_open

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    results = [r for r in sched.retired() if r.uid != WARM_UID]
    timed = _timed(sched, results, due_at, submit_at)
    record = {
        "mode": "closed" if closed else "open",
        "window_s": t_close - t_open, "setup_s": setup_s,
        "compile_s": compiles.seconds,
        "compiles_in_window": compiles_in_window,
        "tokens": tokens, "lanes": cfg["lanes"], "chunk": cfg["chunk"],
        "hf": hf, "requests": timed,
        "active_lanes": _in_window(sched, "active_lanes", tick_open,
                                   tick_close),
        "queue_depth": _in_window(sched, "queue_depth", tick_open,
                                  tick_close),
        "t_open": t_open,
        "peak_bytes": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
        "device_kind": dev.device_kind, "platform": dev.platform,
        "device_count": len(jax.devices()),
        "counters": _counters(counters, tick_open, tick_close),
        "trace": None,
        "ticks": _tick_times(sched, tick_open, tick_close),
    }
    if closed:
        record["attempted"] = sum(1 for r in reqs if r.uid in _admitted(
            sched, results))
    else:
        record["attempted"] = len(reqs)
    if prof is not None:
        record["trace"] = prof.reduce()
        record["counters_traced"] = _counters(counters, prof.ticks[0],
                                              prof.ticks[1])

    # the check: program state freed first, the reference after the peak
    faults = check.structural(results, hf["vocab_size"],
                              None if closed else len(reqs))
    record["failed"] = sum(1 for r in results if r.status != "ok") + (
        0 if closed else max(0, len(reqs) - len(results)))
    # a closed mix's window finishes few of its long requests: its sample
    # also draws on what the requests still running had served by the close
    served = results + sched.in_flight() if closed else results
    sample = check.select(served, prompts, seed, mix["check"]["requests"],
                          mix["check"]["max_tokens"])
    seqs = check.sequences(sample, prompts)
    record["seqs"] = seqs
    sched.state = None
    del sched, engine, params
    gc.collect()
    t_check = time.perf_counter()
    if seqs:
        gap = check.control_gap if control else check.served_gap
        record["check"] = gap(spec.reference_module(cfg), seed, cfg, seqs)
    else:
        record["check"] = {k: float("inf") for k in check.GAPS}
        record["check"].update(tokens=0, sequences=0)
        faults.append("no finished request to compare")
    record["check"]["seconds"] = time.perf_counter() - t_check
    record["faults"] = faults
    record["limits"] = dict(cfg["check"]["limits"])
    record["correct"] = not faults and all(
        record["check"][k] <= v for k, v in record["limits"].items())
    return record


def _check_arena(sched, cfg: dict) -> None:
    """The arena the program provisioned is the one the reference models."""
    from repro.core import policy as policy_lib
    pc = next(policy_lib.iter_policy_caches(sched.state))
    slots = getattr(pc.cache, "slots", cfg["max_len"])
    if slots != cfg["arena_slots"]:
        raise ValueError(f"program provisions {slots} slots a lane, the "
                         f"configuration states {cfg['arena_slots']}")


def _admitted(sched, results) -> set:
    uids = {r.uid for r in results}
    return uids | {r.req.uid for r in sched.active_reqs}


def _timed(sched, results, due_at, submit_at) -> list:
    """Each retired request with its ticks mapped to host seconds."""
    out = []
    for r in results:
        ft = r.first_token_tick
        out.append({
            "uid": r.uid, "status": r.status,
            "tokens": int(r.lengths.max()) if len(r.lengths) else 0,
            "due": due_at.get(r.uid), "submitted": submit_at.get(r.uid),
            "admitted": sched.starts.get(r.admitted_tick),
            "first_token": sched.starts.get(ft) if ft >= 0 else None,
            "finished": sched.ends.get(r.finished_tick - 1),
        })
    return out


def _in_window(sched, key: str, lo: int, hi: int) -> list:
    """The scheduler's own timeline (one sample per tick boundary) over
    the window's ticks."""
    return sched._timeline[key][lo:hi]


def _tick_times(sched, lo: int, hi: int) -> dict:
    """The window's ticks on the host clock: the median tick (dispatch to
    booked readback), and the longest ticks and the longest gaps between
    one tick's readback and the next dispatch, each as [tick in the window,
    seconds]."""
    ks = [k for k in range(lo, hi) if k in sched.starts and k in sched.ends]
    if not ks:
        return {}
    tick = [(k - lo, sched.ends[k] - sched.starts[k]) for k in ks]
    gap = [(k - lo, sched.starts[k + 1] - sched.ends[k]) for k in ks
           if k + 1 in sched.starts]
    top = lambda xs: [list(x) for x in sorted(xs, key=lambda x: -x[1])[:3]]
    return {"tick_median_s": float(np.median([d for _, d in tick])),
            "longest_ticks": top(tick), "longest_gaps": top(gap)}


def _counters(store: Dict[int, Any], lo: int, hi: int) -> Optional[dict]:
    """The chunk program's per-step ``act``/``reads``/``live`` outputs of
    ticks ``lo <= k < hi``, stacked (steps, lanes)."""
    ks = [k for k in sorted(store) if lo <= k < hi]
    if not ks:
        return None
    pick = lambda i: np.concatenate([np.asarray(store[k][i]) for k in ks])
    return {"act": pick(0), "reads": pick(1), "live": pick(2),
            "ticks": len(ks)}


class _Profiler:
    """Traces the device from the first tick boundary after ``at`` for
    ``seconds``, starting and stopping at boundaries so the trace holds
    whole ticks."""

    def __init__(self, at: float, seconds: float):
        self.at, self.seconds = at, seconds
        self.dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        self.on, self.done = False, False
        self.ticks = [0, 0]
        self._span = None
        self._t0 = 0.0

    def boundary(self, sched) -> None:
        import jax
        now = sched.clock()
        if not self.on and not self.done and now >= self.at:
            jax.profiler.start_trace(self.dir)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
            self.on, self._t0 = True, sched.clock()
            self.ticks[0] = sched.ticks
        elif self.on and now >= self._t0 + self.seconds:
            self.ticks[1] = sched.ticks
            self.stop()

    def stop(self) -> None:
        import jax
        if self.on:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on, self.done = False, True

    def reduce(self) -> Optional[dict]:
        from perfbench import trace as tr
        if not self.done:
            return None
        try:
            return tr.read(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
