"""Tiny presets of the benchmark's cells for the CPU rehearsals.

The cell's own files at a size the CPU holds: a few rows, small images, a
four-step probe, two-step chunks. Limits here are the rehearsal's own: on the
CPU the program's selection is exact and both sides round alike, so sound
runs agree to rounding and the control stands far off.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench import harness  # noqa: E402

LIMITS = {
    "window_compiles": {"max": 0}, "nonfinite_losses": {"max": 0},
    "loss_gap_1_3": {"max": 0.02}, "value_gap_1": {"max": 5e-3},
    "value_gap_2": {"max": 0.05}, "dparam_gap_3": {"max": 0.9},
    "loss_ratio": {"min": 0.9, "max": 1.1},
}
SPARSE = {"support_recall_1": {"min": 0.95}, "support_recall_2": {"min": 0.9}}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def tiny_cell(workload, root=harness.ROOT):
    cell = harness.load_cell(workload, root)
    cell.traffic.update(batch_size=2, pool_batches=4, probe_steps=4,
                        ratio_steps=[3, 4], chunk_steps=2, trace_steps=2)
    limits = dict(LIMITS)
    if cell.traffic["compression"] != "dense":
        limits.update(SPARSE)
    if cell.chips > 1:
        limits["replica_leaves_differing"] = {"max": 0}
    if cell.config["input"]["kind"] == "images":
        cell.config["input"]["image_size"] = 32
        cell.config["sizes"]["image_size"] = 32
        # Two rows through BatchNorm make every step chaotic: the second
        # step's numbers say little at this size, the first step's numbers
        # carry the rehearsal.
        limits.update(loss_gap_1_3={"max": 0.5}, value_gap_2={"max": 1.0})
        if "support_recall_2" in limits:
            limits["support_recall_2"] = {"min": 0.3}
    cell.traffic["limits"] = limits
    return cell


def run(cell, traced, seed=7, seconds=0.2):
    import time

    lines = []
    result = harness.run_cell(cell, seed, seconds, traced,
                              started=time.perf_counter(), emit=lines.append)
    # The last line of a run is this object, as JSON.
    return json.loads(json.dumps(result)), lines


def check_schema(cell, result, traced):
    assert RESULT_KEYS <= set(result)
    assert DEVICE_KEYS <= set(result["device"])
    assert result["device"]["count"] == cell.chips
    assert result["attempted"] >= cell.traffic["probe_steps"] + 2
    assert result["failed"] == 0
    names = {m["name"]: m for m in (cell.per_layer if traced else cell.end_to_end)}
    if traced:
        assert set(result["metrics"]) <= set(names) and result["metrics"]
        assert result["device"]["busy_s"] > 0 < result["device"]["window_s"]
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert 0 < len(result["breakdown"]["device_ops"]) <= 10
    else:
        assert set(result["metrics"]) == set(names)
    for name, metric in result["metrics"].items():
        assert metric["unit"] == names[name]["unit"]
        assert isinstance(metric["value"], float)
