"""Chip smoke run: serve qwen-r1-1.5b at its published widths on one TPU.

    python chip_smoke.py

Drives the normal serving path (``repro.launch.serve.main`` → ``Engine`` →
``Scheduler`` → KVPolicy caches → compiled ``dms_decode`` Pallas kernel) with
seed-0 random weights, one process, in phases:

  a  device check: the first JAX device must be a TPU, else exit 1
  b  dms at CR 8, fixed arenas: 8 lanes, 6 staggered requests behind a
     256-token shared prefix (prefix cache with a device tier), then one
     width-4 request that hits that prefix in the cache and goes through
     the shared-prefill fork
  c  the same traffic with ``--paged`` (shared page pool)
  d  tova (the kernel's weights-out mode), 3 requests
  e  the chunk program each of b–d served contains the kernel as a
     ``tpu_custom_call`` (checked as each phase ends); one decode step from
     one prefilled state with the kernel and with the reference agrees
     within ``LOGIT_TOL``
  f  every request ends ``ok`` with in-vocabulary tokens (the scheduler
     fails any request that produced a non-finite logit)

Each phase prints one ``smoke`` line (compile and wall seconds, peak device
bytes so far, live bytes before and after, ticks, tokens): smoke readings,
not benchmark numbers.  The first failure exits non-zero.  The last stdout
line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import functools
import gc
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# kernel vs reference logits after one decode step from the same state: the
# two differ only in attention rounding (kernel: f32 softmax on bf16 K/V;
# reference: bf16 probabilities into the PV product), so the gap is bounded
# relative to the logit scale.
LOGIT_TOL = 2e-2

_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/jaxpr_trace_duration")
_clock = {"compile_s": 0.0, "cache_hits": 0}


class SmokeFailure(Exception):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _listen() -> None:
    import jax

    def on_duration(event, secs, **_):
        if event in _COMPILE_EVENTS:
            _clock["compile_s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _clock["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def _memory() -> dict:
    """Peak device bytes since start, and device bytes live now."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("peak_bytes_in_use", "bytes_in_use")}


def _sizes(full: bool) -> tuple:
    """(shared prefix, own prompt, new tokens).  The shared prefix is a
    multiple of chunk × export stride (64 tokens), so its boundary is
    exported and a later prompt that starts with it hits the cache."""
    return (256, 256, 64) if full else (64, 16, 8)


def _serve_argv(policy: str, full: bool, paged: bool = False,
                requests: int = 6) -> list:
    shared, prompt, new = _sizes(full)
    argv = ["--arch", "qwen-r1-1.5b", "--policy", policy, "--cr", "8",
            "--use-kernel", "--num-lanes", "8", "--requests", str(requests),
            "--stagger", "--shared-prefix", str(shared),
            "--prompt-len", str(prompt), "--max-new", str(new),
            "--prefix-cache-mb", "256", "--prefix-cache-device-mb", "64",
            "--export-stride", "8"]
    return argv + (["--full"] if full else []) + (["--paged"] if paged else [])


def _check_results(results, vocab: int, expect: int) -> int:
    _check(len(results) == expect, f"{len(results)} results, want {expect}")
    tokens = 0
    for r in results:
        _check(r.status == "ok", f"request {r.uid} ended {r.status}")
        for chain, n in zip(r.tokens, r.lengths):
            _check(n > 0, f"request {r.uid}: a chain emitted nothing")
            toks = chain[:n]
            _check(bool(((toks >= 0) & (toks < vocab)).all()),
                   f"request {r.uid}: token outside the vocabulary")
            tokens += int(n)
    return tokens


def _kernel_in_program(sched, on_chip: bool) -> bool:
    """The chunk program, lowered as the scheduler calls it, holds the
    compiled kernel (on the chip) / the Pallas call (interpret rehearsal)."""
    if on_chip:
        text = sched.lower_chunk().compile().as_text()
        return "tpu_custom_call" in text and "dms_decode" in text
    return "dms_decode" in sched.lower_chunk().as_text(debug_info=True)


def _fork(engine, sched, full: bool):
    """One width-4 request through the shared-prefill fork, on a fresh
    scheduler of the same engine (same jitted chunk program).  Its prompt
    starts with the prefix ``serve.main`` put before every prompt (the first
    draw of its seed-0 generator), so admission imports that prefix from
    the prefix cache before the fork."""
    import numpy as np
    from repro.serving.scheduler import Request

    shared, own, new = _sizes(full)
    vocab = engine.arch.vocab_size
    prompt = np.concatenate([
        np.random.default_rng(0).integers(3, vocab, size=(shared,)),
        np.random.default_rng(1).integers(3, vocab, size=(own,)),
    ]).astype(np.int32)
    hits = engine.prefix_cache.stats()["hits"]
    fork = engine.scheduler(num_lanes=sched.num_lanes, max_len=sched.max_len)
    fork.submit(Request(uid=100, prompt=prompt, max_new=new, width=4))
    results = fork.run()
    _check(engine.prefix_cache.stats()["hits"] > hits,
           "fork: the shared prefix missed the prefix cache")
    return fork, results


def _serve_phase(name: str, argv: list, full: bool, on_chip: bool,
                 expect: int, with_fork: bool) -> dict:
    from repro.launch import serve

    left = _memory()["bytes_in_use"]          # what earlier phases left
    c0, h0, t0 = _clock["compile_s"], _clock["cache_hits"], time.time()
    engine, sched, results = serve.main(argv)
    _check(engine.use_kernel, f"phase {name}: the kernel is off")
    ticks, tokens = sched.ticks, _check_results(
        results, engine.arch.vocab_size, expect)
    if with_fork:
        fork, fres = _fork(engine, sched, full)
        _check(len(fres[0].lengths) == 4, "fork: not 4 chains")
        ticks += fork.ticks
        tokens += _check_results(fres, engine.arch.vocab_size, 1)
    wall = time.time() - t0
    _check(_kernel_in_program(sched, on_chip),
           f"phase {name}: no dms_decode kernel in the chunk program")
    line = {"smoke": name, "policy": engine.policy.kind,
            "paged": engine.policy.paged, "compile_s": _clock["compile_s"] - c0,
            "cache_hits": _clock["cache_hits"] - h0, "wall_s": wall,
            "bytes_in_use_before": left, **_memory(), "ticks": ticks,
            "tokens": tokens,
            "prefix_hits": engine.prefix_cache.stats()["hits"],
            "kernel_in_hlo": True}
    print(json.dumps(line), flush=True)
    return engine


def _logit_check(arch, params, policy, full: bool) -> dict:
    """One decode step with the kernel and with the reference from the same
    kernel-prefilled state (the served seed-0 weights, phase b's policy)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer as tfm

    plen = 320 if full else 24
    state = tfm.init_decode_state(arch, 1, plen + 8, policy)
    steps = {k: jax.jit(functools.partial(tfm.decode_step, arch=arch,
                                          use_kernel=k))
             for k in (True, False)}
    prompt = np.random.default_rng(2).integers(
        3, arch.vocab_size, size=(plen + 1,)).astype(np.int32)
    c0, t0 = _clock["compile_s"], time.time()
    for t in range(plen):
        _, state, _ = steps[True](params, jnp.asarray(prompt[None, t:t + 1]),
                                  state, pos_t=jnp.int32(t))
    tok, pos = jnp.asarray(prompt[None, plen:]), jnp.int32(plen)
    lk = np.asarray(steps[True](params, tok, state, pos_t=pos)[0])
    lr = np.asarray(steps[False](params, tok, state, pos_t=pos)[0])
    live = slice(0, arch.vocab_size)
    lk, lr = lk[:, live], lr[:, live]
    _check(bool(np.isfinite(lk).all() and np.isfinite(lr).all()),
           "non-finite logits")
    diff, scale = float(np.abs(lk - lr).max()), float(np.abs(lr).max())
    _check(diff <= LOGIT_TOL * max(scale, 1.0),
           f"kernel vs reference logits differ by {diff} (scale {scale})")
    line = {"smoke": "e_logits", "prefill_tokens": plen,
            "max_abs_diff": diff, "max_abs_logit": scale,
            "tol": LOGIT_TOL * max(scale, 1.0),
            "argmax_equal": bool((lk.argmax(-1) == lr.argmax(-1)).all()),
            "compile_s": _clock["compile_s"] - c0,
            "wall_s": time.time() - t0, **_memory()}
    print(json.dumps(line), flush=True)
    return line


def run_phases(full: bool = True) -> None:
    """Phases b–f.  ``full=False`` is the CPU rehearsal: the smoke config,
    shorter prompts, the kernel in interpret mode."""
    import jax
    on_chip = jax.default_backend() == "tpu"
    _listen()
    # one full-width parameter tree on the chip at a time: each phase's
    # engine is dropped before the next builds its own
    b = _serve_phase("b_dms_fixed", _serve_argv("dms", full), full, on_chip,
                     expect=6, with_fork=True)
    policy = b.policy
    del b
    gc.collect()
    _serve_phase("c_dms_paged", _serve_argv("dms", full, paged=True), full,
                 on_chip, expect=6, with_fork=True)
    gc.collect()
    d = _serve_phase("d_tova", _serve_argv("tova", full, requests=3), full,
                     on_chip, expect=3, with_fork=False)
    _logit_check(d.arch, d.params, policy, full)


def main() -> int:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}; "
              "no CPU fallback", file=sys.stderr)
        return 1
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import setup_compile_cache
    cache = setup_compile_cache()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"device": device, "compile_cache": cache}), flush=True)
    try:
        run_phases(full=True)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
