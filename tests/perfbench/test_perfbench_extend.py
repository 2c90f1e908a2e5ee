"""A configuration, a traffic mix, a per-layer metric and a cell are each
added by new files plus one new entry: no file that is there is edited."""

import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny as tiny  # noqa: E402
from perfbench import harness  # noqa: E402


def test_dummy_config_traffic_metric_and_cell_run_from_new_files(tmp_path,
                                                                 monkeypatch):
    root = str(tmp_path)
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(tiny.REPO, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for folder, _, files in os.walk(root):
        for name in files:
            path = os.path.join(folder, name)
            before[path] = open(path, "rb").read()

    def new(path, obj):
        path = os.path.join(root, path)
        assert not os.path.exists(path)
        with open(path, "w") as fh:
            fh.write(obj if isinstance(obj, str) else json.dumps(obj))

    read = lambda path: json.load(open(os.path.join(root, path)))
    config = read("perfbench/configs/lstm_ptb.json")
    config.update(name="lstm_dummy", source="https://example.org/dummy")
    new("perfbench/configs/lstm_dummy.json", config)
    mix = read("perfbench/traffic/gtopk_r001_b2048.json")
    mix.update(name="gtopk_r01_dummy", density=0.01)
    new("perfbench/traffic/gtopk_r01_dummy.json", mix)
    new("perfbench/metrics/final_sync_ms.json",
        {"name": "final_sync_ms", "layer": "trainer loop", "unit": "ms",
         "moves": "throughput", "source": "program_span",
         "reader": {"span": "final_sync"}})
    new("perfbench/metrics/steps_traced.json",
        {"name": "steps_traced", "layer": "trainer loop", "unit": "steps",
         "moves": "throughput", "source": "program_counter",
         "reader": {"module": "steps_traced"}})
    new("perfbench/metrics/steps_traced.py",
        "def read(ctx):\n    return float(ctx['steps'])\n")

    bench = read("BENCHMARK.json")
    bench["configs"].append({"name": "lstm_dummy", "source": config["source"],
                             "file": "perfbench/configs/lstm_dummy.json",
                             "reduced": [], "why": "dummy"})
    bench["workloads"].append({"name": "lstm_dummy.r01", "config": "lstm_dummy",
                               "traffic": "gtopk_r01_dummy", "chips": 1,
                               "why": "dummy"})
    for name, unit, source in (("final_sync_ms", "ms", "program_span"),
                               ("steps_traced", "steps", "program_counter")):
        bench["per_layer"].append(
            {"name": name, "unit": unit, "better": "lower", "source": source,
             "layer": "trainer loop", "moves": "throughput",
             "workloads": ["lstm_dummy.r01"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)

    # The copy's metric readers are found on the copy's path.
    monkeypatch.syspath_prepend(root)
    for name in [m for m in sys.modules if m.startswith("perfbench.metrics")]:
        monkeypatch.delitem(sys.modules, name)
    import perfbench
    monkeypatch.setattr(perfbench, "__path__",
                        [os.path.join(root, "perfbench")])
    monkeypatch.setattr(harness, "ROOT", root)

    cell = tiny.tiny_cell("lstm_dummy.r01", root)
    assert cell.traffic["density"] == 0.01
    result, lines = tiny.run(cell, traced=True)
    assert result["correct"] is True, lines
    assert result["metrics"]["steps_traced"]["value"] == 2.0
    assert result["metrics"]["final_sync_ms"]["value"] > 0
    assert "comm_ms" not in result["metrics"]
    # An old cell does not report the new cell's metrics.
    assert "steps_traced" not in {
        m["name"] for m in harness.load_cell("lstm_ptb.gtopk", root).per_layer}
    for path, content in before.items():
        if not path.endswith("BENCHMARK.json"):
            assert open(path, "rb").read() == content, path
