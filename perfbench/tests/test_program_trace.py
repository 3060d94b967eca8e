"""The reduction of the program's own tracing: scopes from HLO metadata,
device seconds by scope inside the chunk program, host ms a tick from the
scheduler's tick rows, idle gaps put down to the scheduler's phases; the
accepted readers unchanged by the added spans and scopes; and one traced
smoke run through the accepted harness with the rows and scopes added."""
import os
import tempfile

import numpy as np
import pytest

from perfbench import costs, program_trace as pt, readers, spec
from perfbench import trace as tr
from perfbench.tests import smoke

HERE = os.path.join(os.path.dirname(__file__), "fixtures")
OLD = os.path.join(HERE, "trace_dms_reasoning.json.gz")
NEW = os.path.join(HERE, "trace_dms_program.json.gz")
MS = 1_000_000

_BODY = "jit(chunk_fn)/while/body/closed_call"


def _instr(name, shape, op, path=None):
    meta = f', metadata={{op_name="{path}" stack_frame_id=1}}' if path else ""
    return f"  {name} = {shape} {op}(%a), kind=kLoop{meta}"


HLO = "\n".join([
    "ENTRY %main.62 (p: bf16[8]) -> bf16[8] {",
    _instr("%fusion.42", "bf16[160,848,128]{2,1,0}", "fusion",
           f"{_BODY}/while/body/closed_call/kv_write/jit(_where)/select_n"),
    "  ROOT " + _instr("%fusion.32", "bf16[28,80,2,848,128]{4,3,2,1,0}",
                       "fusion",
                       f"{_BODY}/lane_select/jit(_where)/select_n").lstrip(),
    _instr("%dus.9", "bf16[28,80,2,848,128]{4,3,2,1,0}", "fusion",
           f"{_BODY}/while/body/dynamic_update_slice"),
    _instr("%dms_decode.11", "bf16[160,6,128]{2,1,0}", "custom-call",
           f"{_BODY}/attend/dms_decode/pallas_call"),
    _instr("%copy.1", "bf16[8]{0}", "copy"),
    "}"])


def test_scopes_come_from_the_innermost_named_scope():
    assert pt.scope_of("jit(f)/while/body/sample/lm/dot") == "sample"
    assert pt.scope_of("jit(f)/mlp/attend/x") == "attend"
    assert pt.scope_of("jit(f)/while/body/dynamic_update_slice") is None
    assert pt.hlo_scopes(HLO) == {"%fusion.42": "kv_write",
                                  "%fusion.32": "lane_select",
                                  "%dms_decode.11": "attend"}


def _synthetic():
    # window 0-100 ms; two chunk_fn calls (5-45, 55-95) and a reset program
    # (46-50) whose op shares a name with a scoped op of the chunk program
    ops = [["%fusion.42 = bf16[160,848,128] fusion(%a)", 5 * MS, 5 * MS],
           ["%dms_decode.11 = bf16[160,6,128] custom-call(%q)", 10 * MS,
            20 * MS],
           ["%fusion.32 = bf16[28,80,2,848,128] fusion(%b)", 30 * MS,
            10 * MS],
           ["%dus.9 = bf16[28,80,2,848,128] fusion(%c)", 40 * MS, 5 * MS],
           ["%fusion.42 = bf16[8] fusion(%r)", 46 * MS, 4 * MS],
           ["%fusion.42 = bf16[160,848,128] fusion(%a)", 55 * MS, 5 * MS],
           ["%dms_decode.11 = bf16[160,6,128] custom-call(%q)", 60 * MS,
            35 * MS]]
    host = [["bench.window", 0, 100 * MS],
            ["sched.tick", 0, 54 * MS], ["sched.prepare", 0, 3 * MS],
            ["sched.dispatch", 3 * MS, 2 * MS],
            ["sched.readback", 5 * MS, 41 * MS],
            ["sched.book", 46 * MS, 8 * MS],
            ["sched.reclaim", 46 * MS, 4 * MS],
            ["sched.tick", 54 * MS, 46 * MS],
            ["sched.dispatch", 54 * MS, 1 * MS],
            ["bench.dispatch", 54 * MS, 1 * MS]]
    return {tr.OPS: ops,
            tr.MODULES: [["jit_chunk_fn(1)", 5 * MS, 40 * MS],
                         ["jit_reset_fn(2)", 46 * MS, 4 * MS],
                         ["jit_chunk_fn(1)", 55 * MS, 40 * MS]],
            "host": host, "device": "/device:TPU:0",
            "scopes": pt.hlo_scopes(HLO)}


def test_device_seconds_by_scope_inside_the_chunk_program():
    t = _synthetic()
    got = pt.device_scopes(t)
    assert got == {"kv_write": pytest.approx(0.010),
                   "attend": pytest.approx(0.055),
                   "mlp": 0.0, "sample": 0.0,
                   "lane_select": pytest.approx(0.010),
                   "unscoped": pytest.approx(0.005)}
    # every op of the chunk program is counted once: the split sums to its
    # busy time; the reset program's op is in none
    assert sum(got.values()) == pytest.approx(0.080)
    assert pt.unscoped_ops(t) == [["%dus.9 bf16[28,80,2,848,128]",
                                   pytest.approx(0.005)]]
    run = {"trace": t, "chunk": 4}
    assert pt.scope_step_ms(run, "lane_select") == pytest.approx(10 / 8)
    assert pt.scope_step_ms(run, "kv_write") == pytest.approx(10 / 8)
    assert pt.scope_step_ms(dict(run, trace=dict(t, scopes={})),
                            "kv_write") is None
    assert pt.scope_step_ms({"trace": None, "chunk": 4}, "kv_write") is None


def test_idle_gaps_go_to_the_scheduler_phases():
    split = pt.idle_split(_synthetic())
    # gaps: 0-5 (prepare at 2.5), 45-46 (readback), 50-55 (book at 52.5),
    # 95-100 (tick)
    assert split["sched.prepare"] == pytest.approx(0.005)
    assert split["sched.book"] == pytest.approx(0.005)
    assert split["sched.tick"] == pytest.approx(0.005)
    assert split["sched_share"] == pytest.approx(1.0)
    assert split["sched.readback"] == pytest.approx(0.001)
    # cut at span edges instead: 0-3 prepare, 3-5 and 54-55 dispatch, 50-54
    # book, 95-100 the tick alone
    assert pt.idle_by_phase(_synthetic()) == {
        "sched.prepare": pytest.approx(0.003),
        "sched.readback": pytest.approx(0.001),
        "sched.dispatch": pytest.approx(0.003),
        "sched.book": pytest.approx(0.004),
        "sched.tick": pytest.approx(0.005)}


def _row(k, start, ready_wait=0.4, book=0.01):
    return {"tick": k, "start": start, "prepare": start + 0.001,
            "dispatch": start + 0.003, "returned": start + 0.005,
            "ready": start + 0.005 + ready_wait,
            "end": start + 0.005 + ready_wait + book, "active_lanes": 80,
            "prefill_lanes": 0, "tokens": 640}


def test_host_ms_per_tick_is_the_median_outside_the_wait():
    rows = [_row(0, 0.0), _row(1, 1.0, book=0.02), _row(2, 2.0, book=0.03),
            {"tick": 3, "start": 3.0, "prepare": None, "dispatch": None,
             "returned": None, "ready": None, "end": 3.001,
             "active_lanes": 0, "prefill_lanes": 0, "tokens": 0}]
    assert pt.host_ms_per_tick({"sched_ticks": rows}) == pytest.approx(25.0)
    assert pt.host_ms_per_tick({"sched_ticks": []}) is None
    assert pt.host_ms_per_tick({}) is None
    top = pt.longest_ticks(rows, n=2)
    assert [r["tick"] for r in top] == [2, 1]
    assert top[0]["phases"] == {"admit": pytest.approx(0.001),
                                "prepare": pytest.approx(0.002),
                                "dispatch": pytest.approx(0.002),
                                "readback": pytest.approx(0.4),
                                "book": pytest.approx(0.03)}
    assert pt.phases(rows[3]) == {}


def test_one_tick_cut_keeps_one_dispatching_tick():
    run = {"trace": _synthetic(), "sched_ticks": [_row(0, 0.0)], "chunk": 4}
    cut = pt.one_tick(run, k=0)
    t = cut["trace"]
    assert tr.window(t) == (0.0, 54.0 * MS)
    assert [e[0] for e in t["host"]][:2] == ["bench.window", "sched.tick"]
    assert {pt.op_key(e[0]) for e in t[tr.OPS]} <= set(t["scopes"]) | {
        "%dus.9"}
    assert cut["sched_ticks"] == run["sched_ticks"] and cut["chunk"] == 4


# -- a tick recorded on the chip ---------------------------------------------


@pytest.mark.skipif(not os.path.exists(NEW), reason="fixture absent")
def test_recorded_tick_reads_the_three_program_metrics():
    # one dispatching tick of qwen-r1-1.5b.dms-cr8.reasoning-w4 on a TPU v5e
    # (seed 3900000001), cut by one_tick, with the window's 21 tick rows
    run = tr.load(NEW)
    t = run["trace"]
    assert pt.host_ms_per_tick(run) == pytest.approx(10.248439999998027)
    assert pt.scope_step_ms(run, "kv_write") == pytest.approx(
        3.4955857499999574)
    assert pt.scope_step_ms(run, "lane_select") == pytest.approx(
        9.082323875000002)
    # the scope split covers the chunk program's busy time exactly once
    assert sum(pt.device_scopes(t).values()) == pytest.approx(tr.busy_s(t))
    assert pt.unscoped_ops(t, 1)[0][0].startswith(
        "%select_dynamic-update-slice_fusion")
    # every idle second lies under a scheduler phase, and the split by
    # phase sums to the idle time
    idle = tr.window_s(t) - tr.busy_s(t)
    assert pt.idle_split(t)["sched_share"] == pytest.approx(1.0)
    by = pt.idle_by_phase(t)
    assert sum(by.values()) == pytest.approx(idle)
    assert set(by) <= {"sched.tick", "sched.admit", "sched.prepare",
                       "sched.dispatch", "sched.readback", "sched.book"}
    # the accepted readers read the new fixture as they read the old one
    assert readers.chunk_step_ms(run) == pytest.approx(78.667972)


# -- the accepted readers, unchanged by what the program's tracing adds -----

# their values on the older recorded trace (one TPU v5e)
OLD_VALUES = {"chunk_step_ms": 76.40434662499999,
              "device_idle_pct": 0.7225129324004542,
              "busy_s": 0.611234295, "window_s": 0.615682682,
              "dms_decode_s": 0.379543511, "dms_decode_n": 224}


def _accepted(t):
    live = np.full((8, 80), 700.0)
    run = {"trace": t, "chunk": 8, "counters_traced": {"live": live},
           "hf": {"num_hidden_layers": 28, "num_key_value_heads": 2,
                  "num_attention_heads": 12, "head_dim": 128},
           "device_kind": "TPU v5 lite"}
    secs, n = tr.op_seconds(t, "dms_decode")
    return {"chunk_step_ms": readers.chunk_step_ms(run),
            "device_idle_pct": readers.device_idle_pct(run),
            "busy_s": tr.busy_s(t), "window_s": tr.window_s(t),
            "dms_decode_s": secs, "dms_decode_n": n,
            "roofline": readers.kernel_roofline_pct(run, "dms_decode"),
            "top_ops": tr.top_ops(t)}


@pytest.mark.skipif(not os.path.exists(OLD), reason="fixture absent")
def test_accepted_readers_read_the_old_values_with_program_spans_added():
    t = tr.load(OLD)
    before = _accepted(t)
    for k, v in OLD_VALUES.items():
        assert before[k] == pytest.approx(v, rel=1e-12), k
    flops, nbytes = costs.kernel_flops_bytes(
        {"num_hidden_layers": 28, "num_key_value_heads": 2,
         "num_attention_heads": 12, "head_dim": 128}, np.full((8, 80), 700.0))
    assert before["roofline"] == pytest.approx(
        max(flops / 197e12, nbytes / 819e9) / OLD_VALUES["dms_decode_s"]
        * 100)
    # the program's spans join the host list and its scopes ride beside:
    # every accepted number stays; only the gaps' labels can change
    lo, hi = tr.window(t)
    extended = dict(t, scopes={"%broadcast_select_fusion.32": "lane_select"},
                    host=t["host"] + [["sched.tick", lo, hi - lo],
                                      ["sched.book", lo, (hi - lo) / 2]])
    assert _accepted(extended) == before
    assert sum(v for _, v in tr.idle_gaps(extended)) == pytest.approx(
        sum(v for _, v in tr.idle_gaps(t)))


# -- a traced smoke run through the accepted harness --------------------------


@pytest.fixture(scope="module")
def smoke_run():
    import time
    bench = spec.Bench(smoke.build(tempfile.mkdtemp()))
    return pt.measure(bench, "smoke.dms.closed", 3_000_000_001, 3.0,
                      time.perf_counter(), compile_cache=False)


def test_traced_smoke_run_carries_tick_rows_and_spans(smoke_run):
    rec = smoke_run
    rows = rec["sched_ticks"]
    assert rows and all(rec["t_open"] <= r["start"] <= r["end"]
                        <= rec["t_open"] + rec["window_s"] for r in rows)
    assert [r["tick"] for r in rows] == list(
        range(rows[0]["tick"], rows[0]["tick"] + len(rows)))
    # the closed window's tokens are the rows' tokens
    assert sum(r["tokens"] for r in rows) == pytest.approx(
        rec["tokens"], abs=max(r["tokens"] for r in rows))
    assert pt.host_ms_per_tick(rec) > 0
    names = {h[0] for h in rec["trace"]["host"]}
    assert {"sched.tick", "sched.admit", "sched.prepare", "sched.dispatch",
            "sched.readback", "sched.book"} <= names
    assert set(rec["trace"]["scopes"].values()) == set(pt.SCOPES)
    assert rec["record_cost_us"] > 0
    assert rec["correct"]


def test_untraced_smoke_run_carries_tick_rows_only():
    import time
    bench = spec.Bench(smoke.build(tempfile.mkdtemp()))
    rec = pt.measure(bench, "smoke.vanilla.closed", 3_000_000_002, 1.0,
                     time.perf_counter(), trace=False, compile_cache=False)
    assert rec["trace"] is None and rec["sched_ticks"]
    got = pt.summary(rec)
    assert got["host_ms_per_tick"] > 0 and got["kv_write_step_ms"] is None
    assert all(set(t["phases"]) == {"admit", "prepare", "dispatch",
                                    "readback", "book"}
               for t in got["longest_ticks"])
