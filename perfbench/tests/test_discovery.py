"""The harness finds a configuration, a traffic mix and a metric that are
added as new files, by the names ``BENCHMARK.json`` gives them, without
an edit to any file already there."""
import json
import os

from perfbench import spec, traffic
from perfbench.tests import smoke


def test_new_config_mix_and_metric_are_found(tmp_path):
    root = smoke.build(str(tmp_path))
    before = _files(root)
    cfg = smoke.config("smoke.new", "vanilla")
    with open(os.path.join(root, "perfbench/configs/smoke.new.json"),
              "w") as f:
        json.dump(cfg, f)
    mix = dict(smoke.OPEN, width=2)
    with open(os.path.join(root, "perfbench/traffic/new-mix.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "perfbench/metrics/new.metric.py"),
              "w") as f:
        f.write("def read(run):\n    return run['window_s'] * 2\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "smoke.new", "source": "smoke",
                           "file": "perfbench/configs/smoke.new.json",
                           "reduced": [], "why": "new"})
    doc["workloads"].append({"name": "smoke.new.mix", "config": "smoke.new",
                             "traffic": "new-mix", "chips": 1, "why": "new"})
    doc["per_layer"].append({"name": "new.metric", "unit": "s",
                             "better": "lower", "source": "host_clock",
                             "layer": "scheduler", "moves": "setup_s",
                             "workloads": ["smoke.new.mix"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)

    bench = spec.Bench(root)
    cell = bench.workload("smoke.new.mix")
    assert bench.config(cell["config"])["name"] == "smoke.new"
    got = bench.traffic(cell["traffic"])
    assert got == mix
    assert all(r.width == 2 for r in traffic.generate(got, 1, 512, 2.0))
    names = [m["name"] for m in bench.metrics("smoke.new.mix", True)]
    assert "new.metric" in names and "sched.lane_util" not in names
    out = spec.read_metrics(bench, "smoke.new.mix", True,
                            {"window_s": 3.0, "compile_s": 1.0})
    assert out["new.metric"] == {"value": 6.0, "unit": "s"}
    assert out["compile_s"] == {"value": 1.0, "unit": "s"}
    after = _files(root)
    assert [p for p in before if p != "BENCHMARK.json"
            and after[p] != before[p]] == []


def _files(root):
    out = {}
    for dp, _, fs in os.walk(root):
        for name in fs:
            path = os.path.join(dp, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = f.read()
    return out


def test_unknown_names_are_refused(tmp_path):
    bench = spec.Bench(smoke.build(str(tmp_path)))
    for bad in ("../configs/x", "a b", "x/y"):
        try:
            bench.traffic(bad)
        except ValueError:
            continue
        raise AssertionError(f"{bad!r} was accepted")
