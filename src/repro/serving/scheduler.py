"""Continuous-batching scheduler: the serving engine's admission / prefill /
fork / decode / reclaim lifecycle over a fixed arena of batch *lanes*.

The paper's hyper-scaling claim is a serving-time claim — more chains per
fixed KV budget — so the engine must actually serve: requests arrive over
time with different prompt lengths and stop at different steps.  This module
replaces the lockstep fixed batch with a real scheduler:

* **Lanes.**  The decode state is provisioned once for ``num_lanes`` batch
  rows.  Lanes are independent: each sits at its own sequence position
  (per-lane ``length`` in every cache, per-lane ``pos_t`` through RoPE and
  window masking) and is switched on/off per step by the ``active`` mask of
  :func:`repro.models.transformer.decode_step`.
* **Chunked prefill.**  Prompts are teacher-forced through the *decode* path
  in fixed-size T-chunks (one ``lax.scan`` compiled per chunk size, not one
  trace per prompt length), preserving exact per-policy eviction semantics —
  TOVA/H2O/DMS evict mid-prompt exactly as a per-token loop would.  Decoding
  lanes keep decoding inside the same chunk: prefill and decode interleave in
  one jitted step, which is what makes the batching *continuous*.
* **Shared-prefill fork.**  A width-W (hyper-scaling) request prefills its
  prompt in ONE lane; the finished cache is then forked into W chains via
  :meth:`KVPolicy.fork_cache` (`gather_lanes` inside the fixed batch).
  Forked chains carry bitwise-identical state, so step-0 logits match W
  independent prefills at 1/W of the prefill-phase KV reads.
* **EOS reclamation.**  A chain that emits EOS (or hits its token budget)
  goes inactive immediately — zero further KV reads — and its lane's arena
  is reclaimed (:meth:`KVPolicy.reclaim_cache`) for the next queued request.
* **Cross-request prefix reuse.**  With a
  :class:`~repro.serving.prefix_cache.PrefixCache` attached, admission looks
  up the longest cached prefix of the prompt, imports its snapshot into the
  lane (:meth:`KVPolicy.import_prefix`) — device-to-device when the boundary
  sits in the cache's hot tier — and chunk-prefills only the suffix; prefill
  offers a snapshot at chunk boundaries the cache's export policy asks for
  (all of them under ``"always"``, only prefixes earlier traffic missed on
  under ``"second-miss"``), deferred into the device slab when one exists;
  EOS reclamation offers the finished prompt's prefix chain back to the
  tree (LRU refresh).
  A full-prompt hit skips prefill entirely — the cached boundary logits
  stand in for the hold-state sample.
* **Honest per-request metering.**  Each request owns two
  :class:`BudgetMeter`\\ s (prefill phase / decode phase) fed only by its own
  lanes' per-step ``live_tokens`` / ``reads_tokens``.  Finished lanes
  contribute zero reads; idle lanes are never attributed to anyone.
* **Failure semantics & preemption.**  Oversubscribed paged pools used to
  fail *silently*: :func:`repro.core.block_pool.alloc` latches ``exhausted``
  and drops the write, and the victim lane keeps decoding against zeroed
  keys.  The scheduler now defines what happens instead.  Before each chunk
  it checks that the active set's worst-case pool demand (plus any
  fault-injected ghost pages) still fits the pool — an exact bound, pure
  host arithmetic — and when it does not, **preempts** the youngest
  request: every lane's full decode state is
  snapshotted to host through the prefix-cache export machinery
  (:meth:`_preempt`), its lanes and pool pages are freed, and it requeues
  with exponential backoff and a bounded retry count — on re-admission it
  resumes *bitwise-exactly* from the snapshot, zero prompt re-prefill
  (:meth:`_resume`).  The tick boundary also arms two tripwires: a NaN/Inf
  logit check that **fails** the poisoned request (reclaiming its lanes
  instead of letting it squat), and an ``exhausted``-latch backstop that
  fails every request whose chunk raced a mid-chunk exhaustion (post-hoc
  attribution of a dropped write is impossible, so nobody keeps tokens from
  that chunk).  Every request ends in a definite
  :attr:`RequestResult.status`: ``ok`` (possibly after N preemptions —
  ``preempt_count``), ``failed``, or ``timeout`` (per-request deadline
  ticks).  docs/serving.md "Failure semantics & preemption" is the contract;
  ``serving/faults.py`` is the chaos harness that proves it.
* **SLO & overload control.**  With an :class:`SLOSpec` attached the
  scheduler meters TTFT (arrival → first sampled token, in ticks) and TPOT
  (decode ticks per post-first token) on every result and runs a
  *degradation ladder* when offered load exceeds capacity — **throttle**
  hyper-scaling fork width (a width-W request is served at W′, flagged
  ``degraded``, tokens equal to a solo width-W′ run) with hysteresis
  (``cooldown_ticks``) so the preemption path cannot storm; **shed** queued
  requests that provably cannot meet their deadline/TTFT SLO even if
  admitted this very tick (status ``rejected``, zero prefill reads burned —
  unlike ``timeout``, which fires only after the deadline has passed);
  the bounded queue (``max_queue``) **rejects** the newest arrivals at the
  door when the live backlog of arrived requests exceeds it; only then
  the PR-9 rungs: **preempt**, and finally **fail**.  Every projection is
  pure host arithmetic over admission descriptors and the read-only radix
  probe (:meth:`PrefixCache.covered`) — zero device syncs, zero compiles
  (the analysis tripwires cover these paths).  :meth:`Scheduler.slo_stats`
  reports goodput (offered requests finishing ``ok`` within SLO), TTFT/TPOT
  percentiles, and queue-depth / lane-utilization timelines;
  ``serving/workload.py`` generates the traffic, ``benchmarks/slo_harness.py``
  gates the goodput win over the uncontrolled baseline.
* **Tick-loop tracing.**  Each run-loop iteration is one ``sched.tick``
  profiler span (``jax.profiler.TraceAnnotation``, so it lands on the
  device trace's clock) holding its phases — ``sched.admit``,
  ``sched.prepare``, ``sched.dispatch``, ``sched.readback``, ``sched.book``
  (with ``sched.reclaim`` inside) — and one :class:`TickRecord` in
  :attr:`Scheduler.tick_log`, stamped with :attr:`Scheduler.clock` as each
  phase starts.  Always on: with the profiler off a span costs a TraceMe
  check, and no phase adds a device sync.  docs/serving.md "Tick-loop
  observability" names the spans and the chunk program's scopes.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.hostsync import sanctioned
from repro.core import block_pool
from repro.core import policy as policy_lib
from repro.core.hyperscale import BudgetMeter
from repro.models import transformer as tfm
from repro.serving import prefix_cache as prefix_cache_lib
from repro.serving.prefix_cache import PrefixCache


@dataclass
class Request:
    """One serving request: a prompt and a generation budget.

    ``width`` > 1 asks for W parallel hyper-scaling chains sharing one
    prefill.  ``eos_id`` enables early exit (None = decode the full budget).
    ``arrival`` delays admission until that scheduler tick (staggered-arrival
    simulation for benchmarks/tests).  ``deadline`` bounds end-to-end latency
    in ticks from arrival: a request still running (or still queued) past it
    times out with a definite status instead of squatting lanes forever.
    ``max_preempts`` bounds how often the scheduler may evict-and-resume this
    request before giving up and failing it."""

    uid: int
    prompt: np.ndarray            # (T0,) int32
    max_new: int
    width: int = 1
    eos_id: Optional[int] = None
    arrival: int = 0
    deadline: Optional[int] = None
    max_preempts: int = 3


@dataclass(frozen=True)
class SLOSpec:
    """Latency SLO + overload-control knobs (attach via ``Scheduler(slo=)``).

    ``ttft_ticks`` bounds arrival → first sampled token; ``tpot_ticks``
    bounds decode ticks per post-first token (both measured on every result;
    either may be None = unconstrained for goodput accounting).
    ``max_queue`` bounds the live backlog of arrived-but-unadmitted
    requests — arrivals past it are ``rejected`` at the door (backpressure,
    a definite outcome; enforced per tick so preloaded traces with future
    arrivals are not counted against today's queue).  ``shed``
    enables SLO-aware admission: a queued request that *provably* cannot
    meet its deadline/TTFT SLO even if admitted this tick is rejected
    before it burns any prefill reads.  ``degrade_width`` enables the
    throttle rung of the degradation ladder: under lane/pool pressure a
    width-W request is admitted at ``min_width`` instead (result flagged
    ``degraded``), and ``cooldown_ticks`` of hysteresis keep the throttle
    engaged after pressure recedes so admission cannot flap into the
    preemption path."""

    ttft_ticks: Optional[int] = None
    tpot_ticks: Optional[float] = None
    max_queue: Optional[int] = None
    shed: bool = True
    degrade_width: bool = True
    min_width: int = 1
    cooldown_ticks: int = 4


@dataclass
class RequestResult:
    """``status`` is always definite: ``"ok"`` (``preempt_count`` > 0 means
    preempted×N then completed — tokens still bitwise-equal to an
    uninterrupted run), ``"failed"`` (pool exhaustion backstop, NaN/Inf
    logits, retry budget exhausted, or unservable under injected pressure),
    ``"timeout"`` (deadline ticks exceeded), or ``"rejected"`` (bounded-queue
    backpressure on arrival, or SLO-driven shed while queued — either way the
    request never touched a lane and burned zero prefill reads).
    ``latency_ticks`` is end-to-end (arrival → finished), queueing and
    backoff included.  ``ttft_ticks`` is arrival → first sampled token (-1
    when no token was ever sampled); ``tpot_ticks`` is decode ticks per
    post-first token (0.0 for single-token generations).  ``degraded`` marks
    a hyper-scaling request served at reduced width by the overload ladder —
    ``tokens`` then has the *served* width's rows and equals a solo run at
    that width.  ``submitted_s``/``admitted_s``/``first_token_s``/
    ``finished_s`` are the scheduler's :attr:`~Scheduler.clock` at
    :meth:`~Scheduler.submit` and at the points the matching ticks are
    taken (None where the event never happened)."""

    uid: int
    tokens: np.ndarray            # (W, max_new) int32, padded after EOS
    lengths: np.ndarray           # (W,) generated tokens per chain (incl. EOS)
    meter: BudgetMeter            # prefill + decode, sequential merge
    prefill_meter: BudgetMeter
    decode_meter: BudgetMeter
    admitted_tick: int = 0
    finished_tick: int = 0
    status: str = "ok"
    preempt_count: int = 0
    latency_ticks: int = 0
    first_token_tick: int = -1
    ttft_ticks: int = -1
    tpot_ticks: float = 0.0
    degraded: bool = False
    submitted_s: Optional[float] = None
    admitted_s: Optional[float] = None
    first_token_s: Optional[float] = None
    finished_s: Optional[float] = None

    @property
    def ttft_s(self) -> Optional[float]:
        """Submission → first sampled token, in clock seconds."""
        if self.first_token_s is None or self.submitted_s is None:
            return None
        return self.first_token_s - self.submitted_s

    @property
    def tpot_s(self) -> Optional[float]:
        """Seconds per post-first token (0.0 for single-token generations,
        None when no token was sampled or the request has not finished)."""
        if self.first_token_s is None or self.finished_s is None:
            return None
        gen = int(self.lengths.max()) if len(self.lengths) else 0
        return ((self.finished_s - self.first_token_s) / (gen - 1)
                if gen > 1 else 0.0)


#: rows :attr:`Scheduler.tick_log` keeps; older ticks drop off the front
TICK_LOG_ROWS = 4096


@dataclass
class TickRecord:
    """One run-loop iteration (one tick) on :attr:`Scheduler.clock`.

    Each stamp is taken as its phase starts; None where the phase did not
    run (an idle tick, or one whose lanes were all preempted, dispatches
    nothing): ``start`` (``sched.tick``: admission begins), ``prepare``
    (``sched.prepare``), ``dispatch`` (the chunk program is called),
    ``returned`` (the call returned: the work is enqueued, the readback
    begins), ``ready`` (the host arrays are in hand, ``sched.book``
    begins) and ``end``.  ``active_lanes`` counts lanes the chunk stepped,
    ``prefill_lanes`` lanes it fed prompt tokens, ``tokens`` the output
    tokens added to chains since the last logged row (first tokens sampled
    at a fork included), so the log's tokens add up to the results', and
    ``moe_pairs`` the (token, expert) pairs the chunk's expert layers
    computed, over its steps, lanes and layers (0 for a dense model)."""

    tick: int
    start: float
    prepare: Optional[float] = None
    dispatch: Optional[float] = None
    returned: Optional[float] = None
    ready: Optional[float] = None
    end: Optional[float] = None
    active_lanes: int = 0
    prefill_lanes: int = 0
    tokens: int = 0
    moe_pairs: int = 0


class _ReqState:
    def __init__(self, req: Request, pad_id: int):
        self.req = req
        self.lanes: List[int] = []             # lane -> chain index by order
        self.width = req.width                 # SERVED width (ladder may cut)
        self.consumed = 0                      # prompt tokens prefetched
        self.prefill_chunks = 0                # chunks prefilled (export stride)
        self.hold_logits: Optional[np.ndarray] = None
        self.chains: List[List[int]] = [[] for _ in range(req.width)]
        self.chain_done = [False] * req.width
        self.prefill_meter = BudgetMeter()
        self.decode_meter = BudgetMeter()
        self.pad_id = pad_id
        self.admitted_tick = -1                # -1 = never admitted
        self.first_token_tick = -1             # -1 = no token ever sampled
        self.submitted_s: Optional[float] = None   # clock stamps (seconds)
        self.admitted_s: Optional[float] = None
        self.first_token_s: Optional[float] = None
        self.status = "ok"
        self.preempt_count = 0
        self.resume_at = 0                     # backoff: earliest re-admission
        # preemption snapshot: per-lane host state trees + host lane scalars
        self.snaps: Optional[List[Any]] = None
        self.saved: Optional[Dict[str, np.ndarray]] = None

    @property
    def done(self) -> bool:
        return bool(self.lanes) and all(self.chain_done)

    def ready(self, tick: int) -> bool:
        return self.req.arrival <= tick and self.resume_at <= tick

    def degrade(self, width: int) -> None:
        """Throttle to ``width`` chains (admission-time only: chains are
        still empty, no lane holds anything of ours yet)."""
        self.width = width
        self.chains = [[] for _ in range(width)]
        self.chain_done = [False] * width

    def result(self, peak_bytes: float, finished_tick: int,
               finished_s: Optional[float] = None) -> RequestResult:
        w, m = self.width, self.req.max_new
        toks = np.full((w, m), self.pad_id, np.int32)
        lens = np.zeros((w,), np.int32)
        for c, chain in enumerate(self.chains):
            lens[c] = len(chain)
            toks[c, :len(chain)] = chain
        for meter in (self.prefill_meter, self.decode_meter):
            meter.observe_peak_bytes(peak_bytes)
        ft = self.first_token_tick
        gen = int(lens.max()) if w else 0
        tpot = ((finished_tick - ft) / (gen - 1)
                if ft >= 0 and gen > 1 else 0.0)
        return RequestResult(
            uid=self.req.uid, tokens=toks, lengths=lens,
            meter=self.prefill_meter.merge_sequential(self.decode_meter),
            prefill_meter=self.prefill_meter, decode_meter=self.decode_meter,
            admitted_tick=self.admitted_tick, finished_tick=finished_tick,
            status=self.status, preempt_count=self.preempt_count,
            latency_ticks=max(0, finished_tick - self.req.arrival),
            first_token_tick=ft,
            ttft_ticks=ft - self.req.arrival if ft >= 0 else -1,
            tpot_ticks=float(tpot), degraded=self.width < self.req.width,
            submitted_s=self.submitted_s, admitted_s=self.admitted_s,
            first_token_s=self.first_token_s, finished_s=finished_s)


def make_chunk_fn(arch, *, use_kernel: bool = False,
                  temperature: float = 0.0) -> Callable:
    """Build the jittable mixed prefill/decode chunk step.

    One call advances every active lane ``chunk`` steps: prefill lanes
    teacher-force their next prompt tokens (``feed`` / ``feed_valid``),
    decode lanes sample autoregressively, finished/idle lanes are frozen by
    the ``active`` mask.  Compiled once per (num_lanes, chunk) — admission,
    prompt length, and EOS timing never retrace."""

    def chunk_fn(params, state, feed, feed_valid, cur_tok, pos, decoding,
                 finished, lane_eos, budget_left, rng, poison):
        # feed/feed_valid: (B, C); every other lane array: (B,)
        def body(carry, xs):
            (state, cur_tok, pos, finished, emit_cnt, rng, last_logits,
             bad) = carry
            tok_feed, fv = xs
            prefill_now = fv & ~decoding & ~finished
            decode_now = decoding & ~finished & (emit_cnt < budget_left)
            active = prefill_now | decode_now
            token = jnp.where(prefill_now, tok_feed, cur_tok)[:, None]
            rng, sub = jax.random.split(rng)
            logits, state, aux = tfm.decode_step(
                params, token, state, arch, pos,
                use_kernel=use_kernel, active=active)
            # fault injection + numeric tripwire: ``poison`` NaNs chosen
            # lanes' logits for this chunk (the chaos harness); ``bad``
            # latches any non-finite logit row an *active* lane produced —
            # injected or real — for the scheduler's tick-boundary check.
            # All-False poison is an identity select: the common path is
            # bitwise-unchanged.
            logits = jnp.where(poison[:, None], jnp.float32(jnp.nan), logits)
            bad = bad | (active & ~jnp.all(jnp.isfinite(logits), axis=-1))
            with jax.named_scope("sample"):
                if temperature > 0.0:
                    nxt = jax.random.categorical(sub, logits / temperature,
                                                 axis=-1)
                else:
                    nxt = jnp.argmax(logits, axis=-1)
                nxt = nxt.astype(jnp.int32)
            emitted = jnp.where(decode_now, nxt, -1)
            cur_tok = jnp.where(decode_now, nxt, cur_tok)
            finished = finished | (decode_now & (lane_eos >= 0)
                                   & (nxt == lane_eos))
            emit_cnt = emit_cnt + decode_now.astype(jnp.int32)
            pos = pos + active.astype(jnp.int32)
            last_logits = jnp.where(active[:, None], logits, last_logits)
            return ((state, cur_tok, pos, finished, emit_cnt, rng,
                     last_logits, bad),
                    (emitted, aux["live_tokens"], aux["reads_tokens"], active,
                     aux["moe_pairs"]))

        b = feed.shape[0]
        carry0 = (state, cur_tok, pos, finished, jnp.zeros((b,), jnp.int32),
                  rng, jnp.zeros((b, arch.padded_vocab), jnp.float32),
                  jnp.zeros((b,), bool))
        carry, ys = jax.lax.scan(body, carry0, (feed.T, feed_valid.T))
        (state, cur_tok, pos, finished, emit_cnt, rng, last_logits,
         bad) = carry
        emitted, live, reads, act, pairs = ys          # each (C, B)
        # ``pairs`` last, so the outputs before it keep their indices
        return (state, cur_tok, pos, finished, emit_cnt, rng, last_logits,
                emitted, live, reads, act, bad, pairs)

    return chunk_fn


class Scheduler:
    """Drives one lane arena to completion over a queue of requests.

    The jitted step/reset/gather functions are owned by the caller (the
    :class:`~repro.serving.engine.Engine`) so their compile caches persist
    across Scheduler instances — per-request scheduling never retraces."""

    def __init__(self, arch, params, policy, *, num_lanes: int, max_len: int,
                 chunk: int = 8, chunk_jit=None, reset_jit=None,
                 gather_jit=None, use_kernel: bool = False,
                 temperature: float = 0.0, seed: int = 0, pad_id: int = 0,
                 prefix_cache: Optional[PrefixCache] = None,
                 export_jit=None, import_jit=None, faults=None,
                 on_pressure: str = "preempt", oversub: float = 1.0,
                 slo: Optional[SLOSpec] = None):
        self.arch, self.params, self.policy = arch, params, policy
        self.num_lanes, self.max_len, self.chunk = num_lanes, max_len, chunk
        self.pad_id = pad_id
        # failure-semantics knobs: ``faults`` is a serving.faults.FaultPlan
        # (tests/benchmarks only); ``on_pressure`` picks what pool pressure
        # does ("preempt" = evict-and-resume, "ignore" = the seed behaviour —
        # silent dropped writes, kept only to demonstrate the corruption);
        # ``oversub`` > 1 admits against 1/oversub of worst-case pool demand
        # (the documented oversubscription contract preemption absorbs).
        if on_pressure not in ("preempt", "ignore"):
            raise ValueError(f"on_pressure must be 'preempt' or 'ignore', "
                             f"got {on_pressure!r}")
        if oversub < 1.0:
            raise ValueError("oversub < 1 would reserve more than worst-case "
                             "demand; shrink pool_blocks instead")
        if slo is not None and slo.min_width < 1:
            raise ValueError("SLOSpec.min_width must be >= 1")
        self.faults = faults
        self.on_pressure = on_pressure
        self.oversub = float(oversub)
        self.slo = slo
        # lifecycle observability (lifecycle_stats / pool_stats / serve.py)
        self.preemptions = 0
        self.resumes = 0
        self.failures = 0
        self.timeouts = 0
        self.completed = 0
        self.rejected = 0              # bounded-queue backpressure on arrival
        self.shed = 0                  # SLO-driven queue sheds (also rejected)
        self.degraded = 0              # width-throttled admissions
        self.offered = 0               # every submit() that passed validation
        # SLO observability: every retired result (any status) plus per-tick
        # queue-depth / active-lane samples — all host-side, zero syncs
        self._finished: List[RequestResult] = []
        self._timeline: Dict[str, List[int]] = {"queue_depth": [],
                                                "active_lanes": []}
        self._hot_until = -1           # throttle hysteresis: degrade before it
        # tick-loop tracing: the clock every stamp reads, the last
        # TICK_LOG_ROWS ticks, and the row the current iteration stamps
        self.clock: Callable[[], float] = time.perf_counter
        self.tick_log: "collections.deque[TickRecord]" = collections.deque(
            maxlen=TICK_LOG_ROWS)
        self._row = TickRecord(0, self.clock())
        self._chunk_jit = chunk_jit or jax.jit(make_chunk_fn(
            arch, use_kernel=use_kernel, temperature=temperature))
        self._reset_jit = reset_jit or jax.jit(self._reset_fn,
                                               static_argnames=("b", "ml"))
        self._gather_jit = gather_jit or jax.jit(tfm.gather_lanes)
        self.prefix_cache = prefix_cache
        self._export_jit = export_jit or jax.jit(tfm.export_lane_state)
        self._import_jit = import_jit or jax.jit(tfm.import_lane_state)
        self.temperature = temperature

        self.state = tfm.init_decode_state(arch, num_lanes, max_len, policy)
        self.signature = tfm.lane_state_signature(self.state)
        # per-boundary snapshot bytes are shape-derived and constant for this
        # arena; knowing them up front lets _export_prefix skip the jitted
        # export entirely when no snapshot can ever fit in either tier.
        # eval_shape on the real export (no FLOPs, no allocation) rather than
        # whole-state-bytes // num_lanes: state leaves need not be
        # lane-proportional — a paged state's shared block pool has no lane
        # axis at all, and its snapshots densify to fixed-arena shape.
        snap_shapes = jax.eval_shape(tfm.export_lane_state, self.state,
                                     jnp.int32(0))
        self._snap_nbytes = (prefix_cache_lib.snapshot_nbytes(snap_shapes)
                             + int(arch.padded_vocab) * 4)  # + fp32 logits row
        self.peak_bytes = float(policy_lib.state_peak_bytes(self.state))
        # paged-pool admission descriptors: (kv_heads, arena_blocks, block_p,
        # pool_blocks) per pooled cache — a lane's worst-case footprint is
        # now a real byte-budget question, answered host-side in _admit
        self._pool_descs = []
        for pc in policy_lib.iter_policy_caches(self.state):
            pool = getattr(pc.cache, "pool", None)
            if pool is not None:
                phys = pc.cache.phys            # (nsb, B, H, NB)
                self._pool_descs.append(
                    (int(phys.shape[-2]), int(phys.shape[-1]),
                     int(pool.block_p), int(pool.num_blocks)))
        self.rng = jax.random.PRNGKey(seed)
        self._host_rng = jax.random.PRNGKey(seed ^ 0x5EED0)

        b = num_lanes
        self.pos = np.zeros((b,), np.int32)
        self.cur_tok = np.zeros((b,), np.int32)
        self.decoding = np.zeros((b,), bool)
        self.finished = np.zeros((b,), bool)
        self.lane_eos = np.full((b,), -1, np.int32)
        self.owner: List[Optional[_ReqState]] = [None] * b
        self.chain_of = np.zeros((b,), np.int32)
        self.queue: List[_ReqState] = []
        self.active_reqs: List[_ReqState] = []
        self.ticks = 0
        self.steps = 0

    def _reset_fn(self, state, mask, b, ml):
        fresh = tfm.init_decode_state(self.arch, b, ml, self.policy)
        return tfm.reclaim_lanes(state, mask, fresh)

    # -- public ------------------------------------------------------------

    def submit(self, req: Request) -> None:
        if req.width > self.num_lanes:
            raise ValueError(
                f"request width {req.width} > num_lanes {self.num_lanes}")
        if len(req.prompt) == 0:
            raise ValueError("empty prompt: nothing to sample from")
        if len(req.prompt) + req.max_new > self.max_len:
            raise ValueError("prompt + max_new exceeds scheduler max_len")
        # a request whose worst-case pool demand exceeds the pool can never
        # be admitted (it would spin the run loop forever) — and mid-flight
        # it could exhaust the pool solo, which no victim selection can fix.
        # Rejecting here also guarantees the solo-fit invariant the
        # preemption layer relies on: one active request alone always fits.
        demand = self._lane_pool_demand(len(req.prompt) + req.max_new)
        for i, d in enumerate(demand):
            if req.width * d > self._pool_descs[i][3]:
                raise ValueError(
                    f"request {req.uid}: worst-case pool demand "
                    f"{req.width * d} blocks exceeds pool {i} capacity "
                    f"{self._pool_descs[i][3]} — unservable at any load")
        self.offered += 1
        st = _ReqState(req, self.pad_id)
        st.submitted_s = self.clock()
        self.queue.append(st)

    def pool_stats(self) -> Optional[Dict[str, Any]]:
        """Paged-pool observability: live/free/allocated blocks, CoW share
        counts, fragmentation, high-water mark — aggregated over every pooled
        cache in the decode state (host-side sync; None when nothing is
        paged), plus the scheduler's request-lifecycle counters under
        ``"lifecycle"``.  Surfaced by launch/serve.py's run summary."""
        out = policy_lib.state_pool_stats(self.state)
        if out is not None:
            out["lifecycle"] = self.lifecycle_stats()
        return out

    def lifecycle_stats(self) -> Dict[str, int]:
        """Preemption / failure observability: how this scheduler's requests
        left the system.  ``preemptions`` counts evictions (a request can
        contribute several), ``resumes`` successful snapshot re-admissions;
        ``completed``/``failures``/``timeouts`` partition finished requests
        by terminal status; ``rejected`` counts bounded-queue backpressure on
        arrival, ``shed`` SLO-driven queue sheds (both retire as status
        ``rejected``), and ``degraded`` width-throttled admissions."""
        return {"preemptions": self.preemptions, "resumes": self.resumes,
                "completed": self.completed, "failures": self.failures,
                "timeouts": self.timeouts, "rejected": self.rejected,
                "shed": self.shed, "degraded": self.degraded}

    def slo_stats(self) -> Dict[str, Any]:
        """Goodput / latency observability over everything retired so far:
        goodput (fraction of offered requests finishing ``ok`` within the
        attached SLO), TTFT/TPOT percentiles over ok requests, per-status
        counts, and queue-depth / lane-utilization timeline aggregates —
        joined with :meth:`lifecycle_stats`.  Pure host arithmetic over the
        retired-result ledger."""
        out = compute_slo_stats(self._finished, self.slo,
                                offered=self.offered,
                                timeline=self._timeline,
                                num_lanes=self.num_lanes)
        out["lifecycle"] = self.lifecycle_stats()
        return out

    def run(self) -> List[RequestResult]:
        """Run the queue to completion; results in completion order.

        Termination is unconditional: every iteration either advances the
        clock (idle or chunk tick) or retires a request (completion, failure,
        timeout, retry exhaustion), and a queue that can never admit again —
        idle lanes, every request ready, no pending fault release — is
        failed out rather than spun on (see :meth:`_starved`)."""
        results: List[RequestResult] = []
        while self.queue or self.active_reqs:
            # a row left unlogged (a pause raised mid-iteration) hands its
            # tokens on, so the log's tokens add up to the results'
            self._row = TickRecord(self.ticks, self.clock(), tokens=(
                self._row.tokens if self._row.end is None else 0))
            with self._phase("sched.tick"):
                with self._phase("sched.admit"):
                    if self.faults is not None:
                        self.faults.on_tick(self, results)
                    self._expire_queued(results)
                    self._bound_queue(results)
                    self._shed_queued(results)
                    # fork before admitting: freed lanes must reach held
                    # hyperscale requests before new admissions can take them
                    self._fork_ready()
                    self._admit()
                    self._fork_ready()
                if not any(o is not None for o in self.owner):
                    if not self.queue and not self.active_reqs:
                        break
                    if self._starved():
                        self._fail_starved(results)
                        continue
                    # nothing admitted yet (future arrivals / backoff): tick
                    # time passes with no dispatch
                    self._record_timeline()
                    self.ticks += 1
                else:
                    self._tick(results)
            self._row.end = self.clock()
            self.tick_log.append(self._row)
        return results

    @contextlib.contextmanager
    def _phase(self, name: str, stamp: Optional[str] = None):
        """One phase of the tick loop: a profiler span named ``name`` and,
        with ``stamp``, the current :class:`TickRecord`'s field of that name
        set to :attr:`clock` as the phase starts."""
        if stamp is not None:
            setattr(self._row, stamp, self.clock())
        with jax.profiler.TraceAnnotation(name):
            yield

    # -- lifecycle stages --------------------------------------------------

    def _idle_lanes(self) -> List[int]:
        return [l for l in range(self.num_lanes) if self.owner[l] is None]

    def _lane_pool_demand(self, tokens: int) -> List[int]:
        """Worst-case pool blocks ONE chain of a ``tokens``-token request can
        ever hold, per pooled descriptor: ``H * min(ceil(T / bp), NB)`` — the
        request can't map more blocks than its tokens span, and the cache's
        logical arena caps retention at ``NB`` blocks per head regardless.
        Empty when nothing is paged (fixed arenas: admission is lanes-only).
        """
        return [h * min(-(-tokens // bp), nb)
                for (h, nb, bp, _) in self._pool_descs]

    def _reserved_demand(self, tokens: int, width: int) -> List[int]:
        """Pool blocks admission reserves for a ``tokens``-token request at
        serving width ``width``: worst case scaled by the oversubscription
        factor.  ``oversub == 1`` (the default) reserves the full width-W
        worst case — a fixed-arena-sound contract under which the pool can
        *never* exhaust via the public API (the CoW fork shares pages, so
        divergence only grows demand toward the reserved bound, never past
        it).  ``oversub > 1`` is the explicit contract change: admit more,
        and let the preemption layer absorb the overflow when divergence
        actually materializes."""
        return [math.ceil(width * d / self.oversub)
                for d in self._lane_pool_demand(tokens)]

    def _pool_fits(self, tokens: int, width: int) -> bool:
        """Byte-budget admission: would admitting ``req`` let total
        *reserved* pool demand exceed any pool's block count?  Host-side
        static arithmetic — no device sync.  With the default provisioning
        (``pool_blocks = B*H*NB``) this can never bind (lane demand is at
        most ``H*NB``), so fixed-arena-equivalent configs admit identically;
        an operator shrinks ``pool_blocks`` to oversubscribe lanes against
        live-token footprint (the hyper-scaling capacity win), and
        ``oversub > 1`` additionally under-reserves worst-case demand (see
        :meth:`_reserved_demand` — preemption absorbs what materializes)."""
        if not self._pool_descs:
            return True
        demand = self._reserved_demand(tokens, width)
        reserved = [0] * len(self._pool_descs)
        for r in self.active_reqs:
            d = self._reserved_demand(len(r.req.prompt) + r.req.max_new,
                                      r.width)
            for i in range(len(reserved)):
                reserved[i] += d[i]
        return all(reserved[i] + demand[i] <= self._pool_descs[i][3]
                   for i in range(len(self._pool_descs)))

    def _admit(self) -> None:
        """Admit queued requests into idle lanes — FIFO with skip-scan.

        A width-W request occupies one prefill lane now and W-1 fork lanes
        later; those W-1 are *reserved* at admission (``sum(width)`` over
        admitted requests never exceeds ``num_lanes``), which makes the fork
        wait in :meth:`_fork_ready` deadlock- and starvation-free: held
        requests' lanes can never be re-admitted out from under them.  Paged
        states add a second gate (:meth:`_pool_fits`): admission reserves
        worst-case pool blocks too (scaled by ``oversub``), so an
        oversubscribed lane count can never deadlock the shared pool.

        Preempted requests re-admit through the same scan once their backoff
        expires, with one extra gate: actual free pages must cover their full
        unscaled demand (a resumed victim that would land straight back
        under pressure ping-pongs forever — better to keep waiting)."""
        # idle lanes are always pristine (fresh at construction; _tick
        # reclaims every lane of a completing request, fork targets included;
        # chunk steps never mutate inactive lanes) — no reset needed here
        while True:
            idle = self._idle_lanes()
            if not idle:
                break
            reserved = sum(r.width - len(r.lanes)
                           for r in self.active_reqs)
            avail = len(idle) - reserved
            free = None                  # lazy free-page readback, ≤1 / pass
            nxt, nxt_w = None, 0
            for r in self.queue:
                if not r.ready(self.ticks):
                    continue
                w = self._effective_width(r)
                if w > avail or not self._pool_fits(
                        len(r.req.prompt) + r.req.max_new, w):
                    continue
                if r.snaps is not None and self._pool_descs \
                        and self._pressure_possible():
                    if free is None:
                        free = self._free_blocks()
                    need = self._lane_pool_demand(
                        len(r.req.prompt) + r.req.max_new)
                    if any(free[i] < len(r.snaps) * need[i]
                           for i in range(len(need))):
                        continue         # resume free-gate: wait it out
                nxt, nxt_w = r, w
                break
            if nxt is None:
                break
            self.queue.remove(nxt)
            if nxt.snaps is not None:
                self._resume(nxt, idle)
                continue
            if nxt_w < nxt.width:
                # throttle rung: serve the hyper-scaling request at reduced
                # width (degraded quality beats a preemption storm); arm the
                # hysteresis window so admission doesn't flap back
                nxt.degrade(nxt_w)
                self.degraded += 1
                self._hot_until = max(self._hot_until,
                                      self.ticks + self.slo.cooldown_ticks)
            lane = idle.pop(0)
            self.owner[lane] = nxt
            self.chain_of[lane] = 0
            nxt.lanes = [lane]
            nxt.admitted_tick = self.ticks
            nxt.admitted_s = self.clock()
            self.active_reqs.append(nxt)
            self.pos[lane] = 0
            self.decoding[lane] = False
            self.finished[lane] = False
            self.lane_eos[lane] = -1 if nxt.req.eos_id is None else nxt.req.eos_id
            self._import_prefix(nxt, lane)

    # -- SLO & overload control (degradation ladder) -------------------------

    def _effective_width(self, r: _ReqState) -> int:
        """The width this request would be admitted at right now — the
        *throttle* rung of the degradation ladder.  Full width unless an
        SLOSpec enables width degradation AND either the throttle window is
        hot (lane demand exceeds the arena, or hysteresis from a recent
        throttle/preemption) or the pool fits the request only at reduced
        width.  Resumed requests keep their snapshot width (their lanes'
        state already has that shape).  Pure host arithmetic."""
        w = r.width
        if r.snaps is not None or self.slo is None \
                or not self.slo.degrade_width:
            return w
        lo = min(w, max(1, self.slo.min_width))
        if lo == w:
            return w
        if self._throttled():
            return lo
        tokens = len(r.req.prompt) + r.req.max_new
        if self._pool_descs and not self._pool_fits(tokens, w) \
                and self._pool_fits(tokens, lo):
            return lo                 # degrade instead of waiting to preempt
        return w

    def _throttled(self) -> bool:
        """Is the throttle window hot?  Overload signal: the *ready backlog*
        alone (arrived, unadmitted lane demand) exceeds the whole arena —
        even an empty arena could not take the waiting traffic at full
        width.  Active lanes deliberately don't count: one wide request plus
        a single arrival is a momentary queue, not overload, and must not
        degrade traffic a calm system would serve at full width.  Observing
        overload arms ``cooldown_ticks`` of hysteresis, so the throttle
        disengages only after a quiet cooldown — admission cannot flap
        between full-width and degraded and feed the preemption path."""
        if self.ticks < self._hot_until:
            return True
        backlog = sum(q.width for q in self.queue if q.ready(self.ticks))
        if backlog > self.num_lanes:
            self._hot_until = self.ticks + self.slo.cooldown_ticks
            return True
        return False

    def _min_prefill_ticks(self, r: _ReqState) -> int:
        """Optimistic prefill ticks if admitted THIS tick: chunked suffix
        after the longest cached prefix (read-only radix probe — no stats,
        no recency, no device work).  A lower bound: prefix reuse and idle
        lanes can only make the real admission this fast, never faster."""
        plen = len(r.req.prompt)
        cached = 0
        if self.prefix_cache is not None:
            cached = min(plen, self.prefix_cache.covered(
                self.signature, r.req.prompt))
        return -(-(plen - cached) // self.chunk)

    def _min_service_ticks(self, r: _ReqState) -> int:
        """Provable lower bound on admission → completion ticks: optimistic
        prefill plus the fewest decode ticks any outcome allows (one token —
        the first sample could be EOS — when ``eos_id`` is set, the full
        ``max_new`` budget otherwise).  Matches the tick mechanics exactly:
        token 0 is sampled at the post-prefill boundary and the final chunk
        that completes the request has already advanced the clock."""
        gen = 1 if r.req.eos_id is not None else r.req.max_new
        return self._min_prefill_ticks(r) + max(-(-(gen - 1) // self.chunk), 1)

    def _bound_queue(self, results: List[RequestResult]) -> None:
        """Bounded-queue backpressure (``max_queue``): when the backlog of
        *arrived*, never-admitted requests exceeds the bound, the newest
        arrivals bounce off the door with status ``rejected`` — a definite
        outcome instead of an unbounded wait.  The bound is enforced at
        arrival time against the live backlog, not at :meth:`submit` — a
        preloaded trace's future arrivals never count against today's queue.
        Preempted requests (``admitted_tick >= 0``) occupy depth but are
        never bounced: they were already accepted once."""
        slo = self.slo
        if slo is None or slo.max_queue is None:
            return
        arrived = [r for r in self.queue if r.req.arrival <= self.ticks]
        fresh = [r for r in arrived if r.admitted_tick == -1]
        over = len(arrived) - slo.max_queue
        # newest first: FIFO order is the door's admission promise
        for r in sorted(fresh, key=lambda r: (r.req.arrival, r.req.uid),
                        reverse=True)[:max(0, over)]:
            self.queue.remove(r)
            r.status = "rejected"
            self.rejected += 1
            self._finish(r, results, 0.0)

    def _shed_queued(self, results: List[RequestResult]) -> None:
        """The *shed* rung: reject queued requests that provably cannot meet
        their deadline (or TTFT SLO) even if admitted this very tick.  Today
        is the cheapest moment to say no — a shed request has burned zero
        prefill reads (``admitted_tick == -1``), unlike a ``timeout``, which
        fires only after the deadline has already passed and any prefill
        spend is lost.  Preempted requests are exempt (their prefill is
        already paid; expiry handles them).  Pure host arithmetic — the
        projection adds no device syncs and no compiles."""
        slo = self.slo
        if slo is None or not slo.shed:
            return
        for r in list(self.queue):
            if r.admitted_tick != -1 or r.req.arrival > self.ticks:
                continue
            arr = r.req.arrival
            dl = r.req.deadline
            doomed = (dl is not None and
                      self.ticks + self._min_service_ticks(r) > arr + dl)
            if not doomed and slo.ttft_ticks is not None:
                doomed = (self.ticks + self._min_prefill_ticks(r)
                          > arr + slo.ttft_ticks)
            if doomed:
                self.queue.remove(r)
                r.status = "rejected"
                self.shed += 1
                self._finish(r, results, 0.0)

    def _finish(self, r: _ReqState, results: List[RequestResult],
                peak_bytes: float) -> None:
        """Single choke point for retiring a request: the result goes to the
        caller AND onto the ledger :meth:`slo_stats` aggregates."""
        res = r.result(peak_bytes, self.ticks, self.clock())
        results.append(res)
        self._finished.append(res)

    def _record_timeline(self) -> None:
        self._timeline["queue_depth"].append(len(self.queue))
        self._timeline["active_lanes"].append(
            sum(o is not None for o in self.owner))

    # -- preemption, failure semantics, pool pressure ------------------------

    def _pressure_possible(self) -> bool:
        """Can the pool come under pressure at all?  With the default sound
        admission (``oversub == 1``) and no fault injector, reserved demand
        bounds real demand and exhaustion is impossible — every pressure
        readback and preemption check is skipped, so the sound path pays
        zero extra host syncs."""
        return self.faults is not None or self.oversub > 1.0

    def _free_blocks(self) -> List[int]:
        """Free pages per pooled descriptor, worst row over stacked
        superblocks (each superblock row allocates independently, so the
        scarcest row binds first).  A ``sanctioned("pool-pressure")``
        readback — only taken when :meth:`_pressure_possible`."""
        out = []
        with sanctioned("pool-pressure"):
            for pc in policy_lib.iter_policy_caches(self.state):
                pool = getattr(pc.cache, "pool", None)
                if pool is None:
                    continue
                ref = np.asarray(pool.ref)
                flat = ref.reshape(-1, ref.shape[-1])
                out.append(int((flat == 0).sum(axis=-1).min()))
        return out

    def _ghost_rows(self) -> List[int]:
        """Worst-row injector-held ghost pages per pooled descriptor (all
        zero without a fault plan) — pages reserved by nobody the scheduler
        can evict, so they shrink the effective pool."""
        out = [0] * len(self._pool_descs)
        if self.faults is None:
            return out
        for i in range(len(out)):
            g = self.faults.ghosts.get(i)
            if g is not None:
                out[i] = int(np.asarray(g).reshape(-1, g.shape[-1])
                             .sum(axis=-1).max())
        return out

    def _relieve_pressure(self, results: List[RequestResult]) -> None:
        """Preemptive eviction at the tick boundary: while the worst-case
        pool demand of the active set (plus injector-held ghost pages) does
        not fit the pool, preempt the youngest active request (latest
        admission: its eviction wastes the least finished work, and FIFO
        order keeps the oldest request making progress — no starvation).

        The check is exact, not heuristic: a request never holds more pages
        than its worst-case demand (logical blocks cap retention; a CoW copy
        replaces a mapping, it doesn't add one), so an active set whose
        worst cases fit can never exhaust the pool mid-chunk — the same
        bound :meth:`submit` enforces for a single request.  Pure host
        arithmetic over the admission descriptors and the host-side ghost
        ledger: the sound path costs zero device syncs."""
        ghost = self._ghost_rows()
        while self.active_reqs:
            total = [0] * len(self._pool_descs)
            for r in self.active_reqs:
                d = self._lane_pool_demand(
                    len(r.req.prompt) + r.req.max_new)
                w = max(len(r.lanes), r.width)
                for i in range(len(total)):
                    total[i] += w * d[i]
            if all(total[i] + ghost[i] <= self._pool_descs[i][3]
                   for i in range(len(total))):
                return
            victim = max(self.active_reqs,
                         key=lambda r: (r.admitted_tick, r.req.uid))
            self._preempt(victim, results)

    def _preempt(self, r: _ReqState, results: List[RequestResult],
                 reason: str = "pool pressure") -> None:
        """Evict ``r`` without corrupting it: snapshot every lane's complete
        decode state to host (the same per-policy export the prefix cache's
        cold tier round-trips bitwise), free its lanes and pool pages, and
        requeue with exponential backoff.  Chains, meters, and consumed
        prompt ride the host-side request state, so resume re-prefills
        nothing.  Past ``max_preempts`` the request fails instead — retries
        are bounded, statuses definite."""
        r.preempt_count += 1
        self.preemptions += 1
        if self.slo is not None:
            # a preemption is the strongest overload signal there is: arm
            # the throttle window so follow-on admissions degrade width
            # instead of re-inflating demand (the ladder's anti-storm rung)
            self._hot_until = max(self._hot_until,
                                  self.ticks + self.slo.cooldown_ticks)
        lanes = list(r.lanes)
        give_up = r.preempt_count > r.req.max_preempts
        if not give_up:
            r.snaps = [prefix_cache_lib.to_host(
                self._export_jit(self.state, jnp.int32(lane)),
                tag="preempt-snapshot") for lane in lanes]
            r.saved = {
                "pos": self.pos[lanes].copy(),
                "cur_tok": self.cur_tok[lanes].copy(),
                "decoding": self.decoding[lanes].copy(),
                "finished": self.finished[lanes].copy(),
                "lane_eos": self.lane_eos[lanes].copy(),
            }
        self.active_reqs.remove(r)
        self._release_lanes(r, lanes)
        if give_up:
            r.status = "failed"
            self.failures += 1
            self._finish(r, results, self._req_peak(len(lanes)))
        else:
            r.resume_at = self.ticks + (1 << (r.preempt_count - 1))
            self.queue.append(r)

    def _resume(self, r: _ReqState, idle: List[int]) -> None:
        """Re-admit a preempted request from its host snapshots: import each
        lane's snapshot into a pristine lane (zero prompt re-prefill),
        restore the host lane scalars, and continue exactly where the
        preemption stopped.  Greedy decoding carries no RNG stream, so the
        continuation is bitwise-equal to the uninterrupted run (ref
        attention; the kernel's paged table order is reassociation-sensitive
        — see docs/serving.md)."""
        lanes = idle[:len(r.snaps)]
        for j, lane in enumerate(lanes):
            self.state = self._import_jit(self.state, r.snaps[j],
                                          jnp.int32(lane))
            self._reapply_ghosts()
            self.owner[lane] = r
            self.chain_of[lane] = j
            self.pos[lane] = r.saved["pos"][j]
            self.cur_tok[lane] = r.saved["cur_tok"][j]
            self.decoding[lane] = r.saved["decoding"][j]
            self.finished[lane] = r.saved["finished"][j]
            self.lane_eos[lane] = r.saved["lane_eos"][j]
        r.lanes = list(lanes)
        r.snaps = None
        r.saved = None
        self.active_reqs.append(r)
        self.resumes += 1

    def _retire(self, r: _ReqState, status: str,
                results: List[RequestResult]) -> None:
        """Terminal non-ok transition: reclaim lanes + pool pages, count,
        emit the result.  The failed/timed-out request stops squatting the
        arena immediately."""
        r.status = status
        if status == "timeout":
            self.timeouts += 1
        else:
            self.failures += 1
        self.active_reqs.remove(r)
        lanes = list(r.lanes)
        self._release_lanes(r, lanes)
        self._finish(r, results, self._req_peak(len(lanes)))

    def _release_lanes(self, r: _ReqState, lanes: List[int]) -> None:
        reclaim = np.zeros((self.num_lanes,), bool)
        for lane in lanes:
            self.owner[lane] = None
            reclaim[lane] = True
            self.decoding[lane] = False
            self.finished[lane] = False
            self.pos[lane] = 0
            self.cur_tok[lane] = 0
            self.lane_eos[lane] = -1
        r.lanes = []
        self._reset(reclaim)

    def _req_peak(self, n_lanes: int) -> float:
        return self.peak_bytes * n_lanes / self.num_lanes

    def _reapply_ghosts(self) -> None:
        # lifecycle ops (gather/reclaim/import) recompute ref = recount(phys),
        # which would silently drop fault-injected ghost refs — re-add them so
        # injected pool pressure survives the ops it is meant to stress
        if self.faults is not None and self.faults.has_ghosts():
            self.state = self.faults.reapply(self.state)

    def _expire_queued(self, results: List[RequestResult]) -> None:
        """Deadline enforcement for requests still *waiting* (never admitted,
        or preempted and backing off): past the deadline they time out
        without ever touching a lane.

        Boundary semantics (pinned by tests/test_scheduler.py): a deadline
        ``dl`` grants the closed tick window ``[arrival, arrival + dl]``.
        Strict ``>`` here and in the active-path check in :meth:`_tick` —
        both fire first at ``ticks == arrival + dl + 1``, and a request
        completing exactly at ``arrival + dl`` is ``ok`` (completion wins
        the tie in :meth:`_tick`, which collects tokens before the deadline
        scan)."""
        for r in list(self.queue):
            dl = r.req.deadline
            if dl is not None and self.ticks - r.req.arrival > dl:
                self.queue.remove(r)
                r.status = "timeout"
                self.timeouts += 1
                self._finish(r, results, 0.0)

    def _starved(self) -> bool:
        """True when nothing can ever change: all lanes idle, every queued
        request past arrival and backoff, the admission scan just admitted
        none of them, and no pending fault release can free the pages they
        are waiting on.  (Unreachable without injected ghost pages: with
        idle lanes and an empty pool the solo-fit bound admits any submitted
        request.)"""
        if any(not r.ready(self.ticks) for r in self.queue):
            return False
        if self.faults is not None and self.faults.can_unblock():
            return False
        return True

    def _fail_starved(self, results: List[RequestResult]) -> None:
        for r in list(self.queue):
            self.queue.remove(r)
            r.status = "failed"
            self.failures += 1
            self._finish(r, results, 0.0)

    def _import_prefix(self, r: _ReqState, lane: int) -> None:
        """Longest-cached-prefix import: the lane resumes at token boundary L
        and chunked prefill feeds only ``prompt[L:]``.  A hot-tier hit hands
        back a device-resident slab slice, so the jitted lane insert below is
        device-to-device — zero host↔device snapshot bytes; a cold hit ships
        its host snapshot up through the same jit (and promotes).  The
        avoided prefill reads go on the request's *saved* axis (``kv_reads``
        stays the honest paid integral); a full-prompt hit skips prefill
        entirely, with the cached boundary logits standing in as the
        hold-state sample."""
        if self.prefix_cache is None:
            return
        hit = self.prefix_cache.lookup(self.signature, r.req.prompt)
        if hit is None:
            return
        if self._pool_descs and self._pressure_possible():
            # a paged prefix import bulk-allocates the whole boundary's pages
            # up front; under pressure that can exhaust the pool mid-import —
            # degrade to a cold prefill instead (pays reads, stays correct)
            free = self._free_blocks()
            need = self._lane_pool_demand(hit.length)
            if any(free[i] < need[i] for i in range(len(need))):
                return
        self.state = self._import_jit(self.state, hit.snapshot,
                                      jnp.int32(lane))
        self._reapply_ghosts()
        self.pos[lane] = hit.length
        r.consumed = hit.length
        r.prefill_meter.observe_saved_reads(hit.reads_cum)
        if hit.length == len(r.req.prompt):
            with sanctioned("tick-boundary"):  # once per admission
                r.hold_logits = np.asarray(hit.logits).copy()

    def _want_prefix_export(self, r: _ReqState) -> bool:
        """Gate the per-chunk snapshot export on pure host checks, so the
        skip paths (no cache, over-budget snapshot, off-stride boundary,
        boundary already in the tree, no earlier traffic asked under
        ``second-miss``) cost no device sync at all — at most one radix
        descent total (``want_export``)."""
        if self.prefix_cache is None:
            return False
        if not self.prefix_cache.can_store(self._snap_nbytes):
            return False                   # can never fit: skip the export
        prefix = r.req.prompt[:r.consumed]
        return self.prefix_cache.want_export(
            self.signature, prefix, chunk_index=r.prefill_chunks,
            final=r.consumed == len(r.req.prompt))

    def _export_prefix(self, r: _ReqState, lane: int, logits) -> None:
        """Offer the just-prefilled boundary ``prompt[:consumed]`` to the
        radix tree.  ``reads_cum`` is what a cold prefill of this prefix
        reads — the request's own paid prefill reads plus whatever its own
        admission-time import saved (the invariant holds recursively, so hits
        on hits stay honest).  ``logits`` predict the boundary token, letting
        a later full-prompt hit skip prefill entirely.

        The export is *deferred*: one jitted lane slice hands the cache a
        device snapshot (and an unsynced device logits row).  With a hot
        tier the snapshot goes straight into the device slab — zero
        host↔device bytes, no stall of the decode scan — and is only
        materialized to host if the hot tier later demotes it.  Without a
        hot tier the cache materializes immediately (the seed behaviour).
        ``second-miss`` export gating (see :meth:`_want_prefix_export`)
        bounds how often this O(arena) copy happens at all: cold unshared
        prompts export nothing."""
        prefix = r.req.prompt[:r.consumed]
        snap = self._export_jit(self.state, jnp.int32(lane))
        reads_cum = r.prefill_meter.kv_reads_saved + r.prefill_meter.kv_reads
        self.prefix_cache.insert(self.signature, prefix, snap, logits,
                                 reads_cum)

    def _fork_ready(self) -> None:
        """hold → decode: fork prefilled lanes into W chains, sample token 0."""
        for r in list(self.active_reqs):
            if r.hold_logits is None or len(r.lanes) == r.width:
                continue
            need = r.width - 1
            idle = self._idle_lanes()
            if len(idle) < need:
                continue                      # wait for lanes to free up
            src = np.arange(self.num_lanes, dtype=np.int32)
            for lane in idle[:need]:
                src[lane] = r.lanes[0]
                self.owner[lane] = r
                self.chain_of[lane] = len(r.lanes)
                r.lanes.append(lane)
            self.state = self._gather_jit(self.state, jnp.asarray(src))
            self._reapply_ghosts()
            self.pos[r.lanes] = self.pos[r.lanes[0]]
            self.lane_eos[r.lanes] = self.lane_eos[r.lanes[0]]
            self._start_decode(r)
        for r in list(self.active_reqs):      # width-1 fast path
            if r.hold_logits is not None and len(r.lanes) == r.width \
                    and not self.decoding[r.lanes].any():
                self._start_decode(r)

    def _start_decode(self, r: _ReqState) -> None:
        """Sample each chain's first token from the shared prefill logits."""
        w = len(r.lanes)
        logits = jnp.asarray(r.hold_logits)[None].repeat(w, axis=0)
        if self.temperature > 0.0:
            self._host_rng, sub = jax.random.split(self._host_rng)
            first = jax.random.categorical(sub, logits / self.temperature,
                                           axis=-1)
        else:
            first = jnp.argmax(logits, axis=-1)
        with sanctioned("tick-boundary"):      # once per request, not per step
            first = np.asarray(first, np.int32)
        if r.first_token_tick < 0:
            r.first_token_tick = self.ticks    # TTFT endpoint
            r.first_token_s = self.clock()
        self._row.tokens += w
        r.decode_meter.observe_step([0.0], new_tokens=w,
                                    reads_tokens_per_layer=[0.0])
        for c, lane in enumerate(r.lanes):
            tok = int(first[c])
            r.chains[c].append(tok)
            self.cur_tok[lane] = tok
            self.decoding[lane] = True
            if (r.req.eos_id is not None and tok == r.req.eos_id) \
                    or len(r.chains[c]) >= r.req.max_new:
                self.finished[lane] = True
        r.hold_logits = None

    def _tick(self, results: List[RequestResult]) -> None:
        with self._phase("sched.prepare", "prepare"):
            self._record_timeline()
            # preemptive pressure relief BEFORE dispatch: post-hoc preemption
            # cannot be bitwise (writes were already dropped mid-chunk), so
            # the margin check runs at the boundary, where snapshots are
            # still exact
            if self.on_pressure == "preempt" and self._pool_descs \
                    and self._pressure_possible():
                self._relieve_pressure(results)
                if not self.active_reqs:
                    self.ticks += 1    # everything evicted: time still passes
                    return
            b, c = self.num_lanes, self.chunk
            feed = np.zeros((b, c), np.int32)
            feed_valid = np.zeros((b, c), bool)
            budget_left = np.zeros((b,), np.int32)
            prefill_take: Dict[int, int] = {}
            for lane in range(b):
                r = self.owner[lane]
                if r is None:
                    continue
                if self.decoding[lane]:
                    budget_left[lane] = r.req.max_new - len(
                        r.chains[self.chain_of[lane]])
                elif r.hold_logits is None and lane == r.lanes[0]:
                    take = min(c, len(r.req.prompt) - r.consumed)
                    if take > 0:
                        feed[lane, :take] = r.req.prompt[
                            r.consumed:r.consumed + take]
                        feed_valid[lane, :take] = True
                        prefill_take[lane] = take
            poison = (self.faults.poison(self.ticks, b)
                      if self.faults is not None else None)
            if poison is None:
                poison = np.zeros((b,), bool)
            args = self._chunk_args(feed, feed_valid, budget_left, poison)

        with self._phase("sched.dispatch", "dispatch"):
            out = self._chunk_jit(*args)
            # the dispatched (old) decode state must not outlive the call:
            # kept through booking, a lane reset would hold a third state
            del args
        with self._phase("sched.readback", "returned"):
            (self.state, cur_tok, pos, finished, _, self.rng, last_logits,
             emitted, live, reads, act, bad, pairs) = out
            # the scheduler's ONE sanctioned host sync: once per chunk, never
            # per step (the host-sync tripwire in repro.analysis enforces
            # this).  The failure tripwires ride the same boundary: the
            # chunk's latched bad-logit mask and the pool's exhausted latch
            # are chunk outputs, not extra stalls.
            with sanctioned("tick-boundary"):
                self.cur_tok = np.array(cur_tok)   # writable host copies
                self.pos = np.array(pos)
                self.finished = np.array(finished)
                emitted = np.asarray(emitted)      # (C, B)
                live = np.asarray(live)
                reads = np.asarray(reads)
                act = np.asarray(act)
                bad = np.asarray(bad)              # (B,) non-finite logits
                self._row.moe_pairs = int(np.asarray(pairs).sum())
                exhausted = (self._pools_exhausted()
                             if self._pool_descs
                             and self.on_pressure != "ignore" else False)
        with self._phase("sched.book", "ready"):
            self._book(results, prefill_take, last_logits, emitted, live,
                       reads, act, bad, exhausted)

    def _book(self, results: List[RequestResult],
              prefill_take: Dict[int, int], last_logits, emitted, live,
              reads, act, bad, exhausted: bool) -> None:
        """Book a read-back chunk: advance the clock, fail doomed requests,
        meter, complete prefills, collect tokens, reclaim finished lanes and
        enforce deadlines.  ``last_logits`` is still on the device."""
        b, c = self.num_lanes, self.chunk
        self.ticks += 1
        self.steps += c
        self._row.active_lanes = int(act.any(axis=0).sum())
        self._row.prefill_lanes = len(prefill_take)

        # failure semantics, decided BEFORE token/hold collection: a doomed
        # request keeps nothing from a corrupt or poisoned chunk
        doomed: Dict[int, Tuple[_ReqState, str]] = {}
        if exhausted:
            # the pool latched exhausted INSIDE the chunk: some write was
            # silently dropped, and post-hoc attribution is impossible —
            # every request that stepped this chunk is suspect.  The
            # preemptive margin check above makes this a loud backstop (it
            # fires only when injected faults ate pages mid-chunk or the
            # margin bound was defeated), never the normal pressure path.
            for r in self.active_reqs:
                if any(act[:, lane].any() for lane in r.lanes):
                    doomed[id(r)] = (r, "failed")
            self._clear_pool_flags()
        for lane in range(b):
            r = self.owner[lane]
            if r is not None and bad[lane]:
                # NaN/Inf logit tripwire: fail the poisoned request and
                # reclaim its lanes instead of decoding garbage forever
                doomed[id(r)] = (r, "failed")

        # per-request, per-step metering from this request's own lanes only
        for r in self.active_reqs:
            lanes = r.lanes
            meter = (r.decode_meter if self.decoding[lanes[0]]
                     else r.prefill_meter)
            for t in range(c):
                if not act[t, lanes].any():
                    continue
                meter.observe_step(
                    [float(live[t, lanes].sum())],
                    new_tokens=int((emitted[t, lanes] >= 0).sum()),
                    reads_tokens_per_layer=[float(reads[t, lanes].sum())])

        # prefill completion -> hold (host samples token 0 next tick)
        ll = None
        for lane, take in prefill_take.items():
            r = self.owner[lane]
            if id(r) in doomed:
                continue
            r.consumed += take
            r.prefill_chunks += 1
            if r.consumed == len(r.req.prompt):
                if ll is None:
                    with sanctioned("tick-boundary"):   # prefill completion
                        ll = np.asarray(last_logits)
                r.hold_logits = ll[lane].copy()
            if self._want_prefix_export(r):
                # deferred export: the device logits row rides along unsynced
                # (ll materialization above is only for prefill completion)
                self._export_prefix(r, lane, last_logits[lane])

        # collect emitted tokens; EOS / budget exhaustion finishes chains.
        # Doomed requests collect nothing: a token sampled after a dropped
        # pool write or from poisoned logits must never reach a result.
        for lane in range(b):
            r = self.owner[lane]
            if r is None or not self.decoding[lane] or id(r) in doomed:
                continue
            chain = r.chains[self.chain_of[lane]]
            for t in range(c):
                tok = emitted[t, lane]
                if tok >= 0:
                    chain.append(int(tok))
                    self._row.tokens += 1
            if self.finished[lane] or len(chain) >= r.req.max_new:
                r.chain_done[self.chain_of[lane]] = True
                self.finished[lane] = True

        # reclaim lanes of completed requests
        done = [r for r in self.active_reqs
                if r.done and id(r) not in doomed]
        if done:
            reclaim = np.zeros((b,), bool)
            for r in done:
                self.active_reqs.remove(r)
                self.completed += 1
                if self.prefix_cache is not None:
                    # EOS reclamation offers the finished prompt's prefix
                    # chain back to the tree (LRU recency refresh)
                    self.prefix_cache.touch(self.signature, r.req.prompt)
                self._finish(r, results, self._req_peak(len(r.lanes)))
                for lane in r.lanes:
                    self.owner[lane] = None
                    reclaim[lane] = True
                    self.decoding[lane] = False
                    self.finished[lane] = False
                    self.pos[lane] = 0
            with self._phase("sched.reclaim"):
                self._reset(reclaim)

        # deadlines: completion above wins a tie; anything still active past
        # its deadline times out now (definite status, lanes reclaimed).
        # Strict ``>`` against the post-increment clock — the same boundary
        # as _expire_queued: the closed window [arrival, arrival + dl] is
        # usable, the first doomed tick is arrival + dl + 1 (pinned by
        # tests/test_scheduler.py::test_deadline_boundary_exact_tick)
        for r in list(self.active_reqs):
            dl = r.req.deadline
            if dl is not None and self.ticks - r.req.arrival > dl:
                doomed.setdefault(id(r), (r, "timeout"))
        for r, status in doomed.values():
            self._retire(r, status, results)

    def _chunk_args(self, feed, feed_valid, budget_left, poison) -> tuple:
        return (self.params, self.state, jnp.asarray(feed),
                jnp.asarray(feed_valid), jnp.asarray(self.cur_tok),
                jnp.asarray(self.pos), jnp.asarray(self.decoding),
                jnp.asarray(self.finished), jnp.asarray(self.lane_eos),
                jnp.asarray(budget_left), self.rng, jnp.asarray(poison))

    def lower_chunk(self):
        """Lower the chunk program with the argument types :meth:`_tick`
        dispatches (idle lanes) — the exact program the scheduler serves."""
        b, c = self.num_lanes, self.chunk
        return self._chunk_jit.lower(*self._chunk_args(
            np.zeros((b, c), np.int32), np.zeros((b, c), bool),
            np.zeros((b,), np.int32), np.zeros((b,), bool)))

    def _pools_exhausted(self) -> bool:
        # reads only the per-pool exhausted scalars — part of the chunk's
        # output state, synced inside the caller's tick-boundary region
        for pc in policy_lib.iter_policy_caches(self.state):
            pool = getattr(pc.cache, "pool", None)
            if pool is not None and bool(np.asarray(pool.exhausted).any()):
                return True
        return False

    def _clear_pool_flags(self) -> None:
        """Un-latch ``exhausted`` once the backstop has failed the affected
        requests — the latch is sticky device state, and leaving it set
        would condemn every later request on the same pool."""
        self.state = policy_lib.map_pooled_caches(
            self.state,
            lambda idx, cache: dataclasses.replace(
                cache, pool=block_pool.clear_flags(cache.pool)))

    def _reset(self, mask: np.ndarray) -> None:
        self.state = self._reset_jit(self.state, jnp.asarray(mask),
                                     b=self.num_lanes, ml=self.max_len)
        self._reapply_ghosts()


# -- SLO accounting (shared by Scheduler.slo_stats and benchmarks) -----------


def slo_attained(res: RequestResult, slo: Optional[SLOSpec]) -> bool:
    """Did this request land inside the SLO?  ``ok`` status is necessary;
    with no SLO attached it is also sufficient.  Measuring an *uncontrolled*
    run against the same SLOSpec (as ``benchmarks/slo_harness.py`` does) is
    the point of keeping this a pure function of the result."""
    if res.status != "ok":
        return False
    if slo is None:
        return True
    if slo.ttft_ticks is not None and not (
            0 <= res.ttft_ticks <= slo.ttft_ticks):
        return False
    if slo.tpot_ticks is not None and res.tpot_ticks > slo.tpot_ticks:
        return False
    return True


def _pctiles(xs: List[float]) -> Dict[str, float]:
    if not xs:
        return {"p50": -1.0, "p90": -1.0, "max": -1.0}
    a = np.asarray(xs, np.float64)
    return {"p50": float(np.percentile(a, 50)),
            "p90": float(np.percentile(a, 90)), "max": float(a.max())}


def compute_slo_stats(results: List[RequestResult],
                      slo: Optional[SLOSpec] = None, *,
                      offered: Optional[int] = None,
                      timeline: Optional[Dict[str, List[int]]] = None,
                      num_lanes: Optional[int] = None) -> Dict[str, Any]:
    """Goodput + latency aggregates over retired results.

    Goodput is the fraction of *offered* requests (``offered`` defaults to
    ``len(results)``) that finished ``ok`` within ``slo`` — rejected, shed,
    timed-out, and failed requests all count against it, which is exactly
    why shedding hopeless work can raise it: lanes spend their ticks on
    requests that can still land inside the SLO."""
    offered = len(results) if offered is None else int(offered)
    by_status: Dict[str, int] = {}
    for r in results:
        by_status[r.status] = by_status.get(r.status, 0) + 1
    ok = [r for r in results if r.status == "ok"]
    within = sum(1 for r in results if slo_attained(r, slo))
    out: Dict[str, Any] = {
        "offered": offered,
        "finished": len(results),
        "statuses": by_status,
        "ok": len(ok),
        "ok_within_slo": int(within),
        "goodput": within / offered if offered else 0.0,
        "degraded": sum(1 for r in results if r.degraded),
        "ttft": _pctiles([float(r.ttft_ticks) for r in ok
                          if r.ttft_ticks >= 0]),
        "tpot": _pctiles([float(r.tpot_ticks) for r in ok
                          if r.ttft_ticks >= 0]),
    }
    if timeline is not None:
        qd = timeline.get("queue_depth", [])
        al = timeline.get("active_lanes", [])
        out["queue_depth"] = {"mean": float(np.mean(qd)) if qd else 0.0,
                              "max": int(max(qd)) if qd else 0}
        out["lane_util"] = (float(np.mean(al)) / num_lanes
                            if al and num_lanes else 0.0)
    return out
