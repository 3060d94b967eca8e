"""The comparison that decides ``correct``, driven through a whole run at
smoke size on the CPU (the harness's look for a chip is skipped): sound
runs pass, the float8 control fails, and faults planted in the timed path
make ``correct`` come out false."""
import time

import jax.numpy as jnp
import pytest

from perfbench import harness, spec
from perfbench.tests import smoke


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return spec.Bench(smoke.build(str(tmp_path_factory.mktemp("bench"))))


def _run(bench, workload, seed, break_program=None):
    return harness.run(bench, workload, seed, 1.0, False, time.perf_counter(),
                       compile_cache=False, break_program=break_program)


@pytest.fixture(scope="module")
def vanilla_run(bench):
    return _run(bench, "smoke.vanilla.closed", 3)


def test_sound_runs_are_correct(bench, vanilla_run):
    assert vanilla_run["correct"], vanilla_run["check"]
    assert vanilla_run["check"]["tokens"] > 100
    rec = _run(bench, "smoke.dms.open", 1)
    assert rec["correct"], rec["check"]
    assert rec["failed"] == 0 and rec["attempted"] == len(rec["requests"])


@pytest.mark.parametrize("workload", ["smoke.vanilla.closed",
                                      "smoke.dms.closed"])
def test_float8_control_is_not_correct(bench, workload):
    """The float8 reference in the program's place: a whole run whose
    check reads the token the lower precision puts first."""
    rec = harness.run(bench, workload, 3, 1.0, False, time.perf_counter(),
                      compile_cache=False, control=True)
    assert not rec["correct"]
    assert any(rec["check"][k] > v for k, v in rec["limits"].items())


def _wrap(sched, change):
    fn = sched._chunk_jit
    sched._chunk_jit = lambda *args: change(args, fn(*args))


def _state_unchanged(engine, sched):
    _wrap(sched, lambda args, out: (args[1],) + tuple(out[1:]))


def _token_altered(engine, sched):
    vocab = engine.arch.vocab_size

    def change(args, out):
        emitted = out[7]
        bumped = jnp.where(emitted >= 0, (emitted + 1) % vocab, emitted)
        return tuple(out[:7]) + (bumped,) + tuple(out[8:])

    _wrap(sched, change)


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered],
                         ids=["state_unchanged", "token_altered"])
@pytest.mark.parametrize("workload", ["smoke.dms.closed", "smoke.dms.open"])
def test_planted_fault_is_not_correct(bench, workload, fault):
    rec = _run(bench, workload, 1, break_program=fault)
    assert not rec["correct"]
    assert any(rec["check"][k] > v for k, v in rec["limits"].items())
