"""Serving engine: scheduler-driven continuous batching, DMS-compressed
paged KV, shared-prefill hyperscale fork, and exact budget metering.

The engine is the production face of the paper: a request asks for W parallel
chains of up to L tokens at compression CR; the engine provisions slot arenas
of ``P ≈ L/CR + w`` per kv head (the physical memory saving), decodes with
the compressed cache, and reports the two paper budget metrics (KV reads,
peak tokens) measured from the real cache state.

Generation runs on the :class:`~repro.serving.scheduler.Scheduler` lane
arena: prompts prefill in T-chunks through the decode path (exact eviction
semantics), hyperscale requests prefill **once** and fork the cache into W
chains (:meth:`KVPolicy.fork_cache`), EOS exits early and reclaims the lane,
and every request gets its own honest prefill/decode meters — a finished
chain contributes zero KV reads.

With ``prefix_cache_mb > 0`` the engine owns a cross-request
:class:`~repro.serving.prefix_cache.PrefixCache`: prompts sharing a prefix
with earlier traffic (system prompts, few-shot headers, multi-turn chats)
import the cached KV snapshot and prefill only their suffix — avoided reads
land on the meters' ``kv_reads_saved`` axis, paid reads stay honest.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import ArchConfig, KVPolicyConfig
from repro.core.hyperscale import BudgetMeter, ScalingConfig, majority_vote
from repro.kernels.dms_decode import ops as dkops
from repro.models import transformer as tfm
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import (Request, RequestResult, Scheduler,
                                     make_chunk_fn)


@dataclass
class GenerationResult:
    tokens: np.ndarray            # (W, L_gen)
    meter: BudgetMeter
    answers: List[int] = field(default_factory=list)
    requests: List[RequestResult] = field(default_factory=list)


# Engine.scheduler's prefix_cache default: "use the engine's own cache".
# A sentinel (not None) so callers can pass prefix_cache=None to get one
# explicitly cold scheduler from a warm engine.
_ENGINE_CACHE = object()


class Engine:
    """Single-host engine; the same step functions lower onto the production
    mesh (see launch/serve.py).

    ``use_kernel=None`` follows the platform: the compiled Pallas decode
    kernel on TPU, the reference attention elsewhere."""

    def __init__(self, arch: ArchConfig, params, policy: KVPolicyConfig,
                 use_kernel: Optional[bool] = None, temperature: float = 0.0,
                 chunk: int = 8, prefix_cache_mb: float = 0.0,
                 prefix_cache_device_mb: float = 0.0,
                 export_policy: str = "always", export_stride: int = 1):
        self.arch = arch
        self.params = params
        self.policy = policy
        if use_kernel is None:
            use_kernel = dkops.kernel_by_default()
        self.use_kernel = use_kernel
        self.temperature = temperature
        self.chunk = chunk
        # engine-owned so it persists across Scheduler instances: every
        # served prompt seeds prefix reuse for all later traffic.
        # prefix_cache_device_mb buys the device-resident hot tier (zero-copy
        # hit path, deferred exports); export_policy="second-miss" stops
        # unshared prompts from exporting at all; export_stride=N keeps only
        # every Nth chunk boundary (+ the full-prompt one) — bounded slot
        # churn on very long shared prefixes.
        self.prefix_cache = (
            PrefixCache(int(prefix_cache_mb * 2 ** 20),
                        int(prefix_cache_device_mb * 2 ** 20),
                        export_policy=export_policy,
                        export_stride=export_stride)
            if prefix_cache_mb > 0 or prefix_cache_device_mb > 0 else None)
        # jitted once per Engine: the compile cache survives across Scheduler
        # instances (per-request scheduling never retraces)
        self._chunk_jit = jax.jit(make_chunk_fn(
            arch, use_kernel=use_kernel, temperature=temperature))
        self._gather_jit = jax.jit(tfm.gather_lanes)
        self._reset_jit = jax.jit(self._reset_fn, static_argnames=("b", "ml"))
        self._prefill_jit = jax.jit(self._prefill, static_argnames=("t",))
        self._export_jit = jax.jit(tfm.export_lane_state)
        self._import_jit = jax.jit(tfm.import_lane_state)

    def _reset_fn(self, state, mask, b, ml):
        fresh = tfm.init_decode_state(self.arch, b, ml, self.policy)
        return tfm.reclaim_lanes(state, mask, fresh)

    # -- jitted internals ------------------------------------------------

    def _prefill(self, params, tokens, state, t):
        # reference per-token prefill through the decode path (exact cache-
        # policy semantics); production serving uses the scheduler's chunked
        # prefill — tests pin the two equivalent per policy
        def body(carry, tok_t):
            state, i = carry
            _, state, _ = tfm.decode_step(
                params, tok_t[:, None], state, self.arch, i,
                use_kernel=self.use_kernel)
            return (state, i + 1), None

        (state, _), _ = jax.lax.scan(
            body, (state, jnp.zeros((), jnp.int32)), tokens.T)
        return state

    def scheduler(self, num_lanes: int, max_len: int, *, seed: int = 0,
                  chunk: Optional[int] = None,
                  prefix_cache: Any = _ENGINE_CACHE, faults: Any = None,
                  on_pressure: str = "preempt", oversub: float = 1.0,
                  slo: Any = None) -> Scheduler:
        """A lane arena bound to this engine's jitted step functions.

        The engine's :class:`PrefixCache` (if any) rides along by default, so
        prompts served by one scheduler seed prefix reuse in the next; pass
        ``prefix_cache=None`` for an explicitly cold scheduler, or another
        PrefixCache instance to override.  ``faults`` attaches a
        :class:`~repro.serving.faults.FaultPlan` (chaos tests/benchmarks);
        ``on_pressure``/``oversub`` configure the preemption layer and
        ``slo`` an :class:`~repro.serving.scheduler.SLOSpec` for the
        overload-control ladder (see :class:`Scheduler`)."""
        if prefix_cache is _ENGINE_CACHE:
            prefix_cache = self.prefix_cache
        return Scheduler(
            self.arch, self.params, self.policy,
            num_lanes=num_lanes, max_len=max_len,
            chunk=chunk or self.chunk, chunk_jit=self._chunk_jit,
            reset_jit=self._reset_jit, gather_jit=self._gather_jit,
            use_kernel=self.use_kernel, temperature=self.temperature,
            seed=seed, prefix_cache=prefix_cache,
            export_jit=self._export_jit, import_jit=self._import_jit,
            faults=faults, on_pressure=on_pressure, oversub=oversub,
            slo=slo)

    # -- public API -------------------------------------------------------

    def generate(self, prompts: np.ndarray, max_new: int, seed: int = 0,
                 eos_id: Optional[int] = None) -> GenerationResult:
        """prompts: (B, T0) int32 — B requests served concurrently, one lane
        each.  With ``eos_id`` set, chains exit early: no further KV reads
        are metered for a finished lane and its arena is reclaimed; output
        rows are padded with ``eos_id`` past each chain's end."""
        b, t0 = prompts.shape
        sched = self.scheduler(b, t0 + max_new, seed=seed)
        for i in range(b):
            sched.submit(Request(uid=i, prompt=np.asarray(prompts[i]),
                                 max_new=max_new, eos_id=eos_id))
        results = {r.uid: r for r in sched.run()}
        pad = eos_id if eos_id is not None else 0
        tokens = np.stack([
            _pad_chain(results[i].tokens[0], results[i].lengths[0],
                       max_new, pad)
            for i in range(b)])
        meter = BudgetMeter()
        for i in range(b):            # concurrent requests: co-resident lanes
            meter = meter.merge(results[i].meter)
        return GenerationResult(tokens=tokens, meter=meter,
                                requests=[results[i] for i in range(b)])

    def hyperscale_generate(self, prompt: np.ndarray, cfg: ScalingConfig,
                            seed: int = 0) -> GenerationResult:
        """One problem, W parallel chains (paper L-W-CR scaling).

        The prompt prefills ONCE; the cache then forks into W chains
        (shared-prefill fork) — prefill-phase KV reads are W× lower than
        re-prefilling per chain, and step-0 logits are bitwise identical."""
        max_new = cfg.max_len - int(prompt.shape[0])
        sched = self.scheduler(cfg.width, cfg.max_len, seed=seed)
        sched.submit(Request(uid=0, prompt=np.asarray(prompt),
                             max_new=max_new, width=cfg.width,
                             eos_id=cfg.eos_id))
        res = sched.run()[0]
        return GenerationResult(tokens=res.tokens, meter=res.meter,
                                requests=[res])


def _pad_chain(chain: np.ndarray, length: int, max_new: int, pad: int
               ) -> np.ndarray:
    out = np.full((max_new,), pad, np.int32)
    out[:length] = chain[:length]
    return out


def answer_from_chain(chain: np.ndarray, eq_token: int = 1) -> Optional[int]:
    """Extract the answer token from a generated chain.

    Our synthetic tasks answer right after the last ``eq_token`` ("=") the
    model emits; chains that never emit one answer with their first token
    (prompts end in "=", so token 0 is the direct answer)."""
    chain = np.asarray(chain)
    if len(chain) == 0:
        return None
    eq_pos = np.where(chain[:-1] == eq_token)[0]
    if len(eq_pos):
        return int(chain[eq_pos[-1] + 1])
    return int(chain[0])


def evaluate_hyperscale(
    engine: Engine, prompts: np.ndarray, answers: np.ndarray,
    cfg: ScalingConfig, seed: int = 0, eq_token: int = 1,
) -> Dict[str, float]:
    """Accuracy + budget over an eval set for one L-W-CR point."""
    meter = BudgetMeter()
    hits = 0
    for i in range(len(prompts)):
        res = engine.hyperscale_generate(prompts[i], cfg, seed=seed + i)
        votes = [answer_from_chain(res.tokens[w], eq_token=eq_token)
                 for w in range(cfg.width)]
        pred = majority_vote([str(v) for v in votes if v is not None])
        hits += int(pred is not None and int(pred) == int(answers[i]))
        meter = meter.merge(res.meter)
    n = max(len(prompts), 1)
    return {
        "accuracy": hits / n,
        "kv_reads": meter.kv_reads / n,
        "peak_tokens": meter.peak_tokens / n,
        "peak_bytes": meter.peak_bytes / n,
        "config": cfg.label,
    }
