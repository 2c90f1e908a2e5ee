"""BENCHMARK.json and the files it names, against the contract's letter, and
the command's behaviour where there is no chip or no program."""

import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def every_entry():
    b = bench()
    return [(kind, e) for kind in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in b[kind]]


def test_top_level_keys_and_limits():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert all(os.path.isdir(os.path.join(REPO, p)) for p in b["paths"])
    assert not any(w.startswith("/") or ".." in w for w in b["command"])
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 4)
    assert {w["chips"] for w in b["workloads"]} <= {1, 4}


@pytest.mark.parametrize("kind,entry", every_entry(),
                         ids=lambda v: v if isinstance(v, str) else v["name"])
def test_entry_has_just_the_contracts_keys_and_characters(kind, entry):
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}[kind]
    optional = {"workloads"} if kind in ("end_to_end", "per_layer") else set()
    assert keys <= set(entry) <= keys | optional
    assert NAME.match(entry["name"])
    for key in ("config", "traffic", "moves"):
        if key in entry:
            assert NAME.match(entry[key])
    for key in ("why", "layer", "source"):
        if key in entry and kind in ("configs", "workloads") or key == "layer" and key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
                and "\t" not in entry[key]
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    if kind == "end_to_end":
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.1
    if kind == "configs":
        assert entry["file"].startswith("perfbench/configs/")
        with open(os.path.join(REPO, entry["file"])) as fh:
            config = json.load(fh)
        assert config["name"] == entry["name"]
        assert config["source"] == entry["source"]
        assert config["reduced"] == entry["reduced"] == []


def test_names_are_unique_and_every_config_is_used():
    b = bench()
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in b[kind]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert "loss_at_32" not in metrics
    assert {w["config"] for w in b["workloads"]} == {c["name"] for c in b["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert "setup_s" in [m["name"] for m in b["end_to_end"]]


@pytest.mark.parametrize("metric", bench()["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_moves_a_metric_its_cells_report(metric):
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    moved = next(m for m in b["end_to_end"] if m["name"] == metric["moves"])
    reporting = set(moved.get("workloads", cells))
    assert set(metric.get("workloads", cells)) <= reporting <= cells
    # Its own file says the same as its entry.
    with open(os.path.join(REPO, "perfbench", "metrics",
                           metric["name"] + ".json")) as fh:
        spec = json.load(fh)
    for key in ("name", "layer", "unit", "moves", "source"):
        assert spec[key] == metric[key], key
    reader = spec["reader"]
    assert ("span" in reader) != ("module" in reader)
    if "module" in reader:
        assert callable(importlib.import_module(
            f"perfbench.metrics.{reader['module']}").read)


@pytest.mark.parametrize("workload", [w["name"] for w in bench()["workloads"]])
def test_cell_loads_and_its_files_agree(workload):
    cell = harness.load_cell(workload)
    assert {"setup_s", "throughput"} <= {m["name"] for m in cell.end_to_end}
    assert cell.per_layer
    tr, cfg = cell.traffic, cell.config
    assert tr["chips"] == cell.chips
    assert tr["ratio_steps"][1] <= tr["probe_steps"] <= tr["pool_batches"]
    assert tr["chunk_steps"] >= 16
    assert set(tr["limits"]) >= {"window_compiles", "nonfinite_losses",
                                 "loss_ratio", "value_gap_1", "dparam_gap_3"}
    assert all("why" in limit for limit in tr["limits"].values())
    ref = importlib.import_module(f"perfbench.refmodels.{cfg['reference_model']}")
    macs = ref.forward_macs(cfg["sizes"])
    assert cfg["flops_per_sample"] == dict(
        cfg["flops_per_sample"], forward_macs=macs, train=6 * macs)


def test_unknown_device_kind_is_an_error_not_a_default():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v9 imaginary")


def _run(cwd, *args):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "resnet50.gtopk",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_accelerator_is_an_error_and_prints_no_result():
    proc = _run(REPO)
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout and '"correct"' not in proc.stdout
    assert "no CPU fallback" in proc.stderr


def test_alone_in_a_directory_is_an_error_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout and '"correct"' not in proc.stdout
    assert "gtopkssgd_tpu" in proc.stderr
