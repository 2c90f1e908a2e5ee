"""The on-hardware convergence runner (benchmarks/convergence_run.py) stays
runnable: tiny end-to-end invocation on the CI mesh, artifact shape checked.

The real artifact is produced on the bench chip
(benchmarks/results/convergence_*.jsonl); this test only pins the harness
so the committed results remain reproducible.
"""

import json
import sys

from tests.conftest import load_benchmark_module


def _load_runner():
    return load_benchmark_module("convergence_run")


def test_convergence_runner_end_to_end(tmp_path, monkeypatch):
    mod = _load_runner()
    out = tmp_path / "conv.jsonl"
    monkeypatch.setattr(sys, "argv", [
        "convergence_run.py", "--dnn", "resnet20", "--steps", "4",
        "--chunk", "2", "--batch-size", "4", "--eval-batches", "1",
        "--nworkers", "2", "--modes", "dense,gtopk",
        "--out", str(out),
    ])
    mod.main()
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    report = rows[-1]
    modes = {s["mode"] for s in report["modes"]}
    assert modes == {"dense", "gtopk"}
    for s in report["modes"]:
        assert "final_loss" in s and "val_top1" in s
        assert "final_loss_vs_dense" in s
    # First row is the run-manifest provenance header (same schema as the
    # metrics.jsonl header); curve rows are the untagged ones.
    assert rows[0].get("kind") == "manifest" and "config_hash" in rows[0]
    curve = [r for r in rows[:-1]
             if r.get("kind") not in ("summary", "manifest")]
    assert {r["step"] for r in curve if r["mode"] == "dense"} == {2, 4}


def test_convergence_runner_arm_suffixes(tmp_path, monkeypatch):
    """Arm syntax "<mode>+warmup" / "<mode>+corr" (round-2 review #4's
    arm set) resolves to the right TrainConfig knobs and flows through to
    the artifact rows under the full arm label."""
    mod = _load_runner()
    out = tmp_path / "conv.jsonl"
    monkeypatch.setattr(sys, "argv", [
        "convergence_run.py", "--dnn", "resnet20", "--steps", "2",
        "--chunk", "2", "--batch-size", "4", "--eval-batches", "1",
        "--nworkers", "2", "--modes", "gtopk+corr",
        "--out", str(out),
    ])
    mod.main()
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    assert rows[-1]["modes"][0]["mode"] == "gtopk+corr"

    # selection-kernel arm (weak #4's exact-vs-approx A/B): forces the
    # approx kernel below the 2^20-param auto threshold and trains
    out2 = tmp_path / "conv_approx.jsonl"
    monkeypatch.setattr(sys, "argv", [
        "convergence_run.py", "--dnn", "resnet20", "--steps", "2",
        "--chunk", "2", "--batch-size", "4", "--eval-batches", "1",
        "--nworkers", "2", "--modes", "gtopk+approx",
        "--out", str(out2),
    ])
    mod.main()
    rows2 = [json.loads(l) for l in out2.read_text().splitlines()]
    assert rows2[-1]["modes"][0]["mode"] == "gtopk+approx"

    import pytest

    monkeypatch.setattr(sys, "argv", [
        "convergence_run.py", "--modes", "gtopk+bogus", "--steps", "2",
        "--nworkers", "2", "--batch-size", "4", "--out", str(out),
    ])
    with pytest.raises(SystemExit, match="bogus"):
        mod.main()


def test_recompute_rebuilds_thresholds_preserving_measured_fields(tmp_path):
    """--recompute replaces steps_to_* from stored curves (both the
    absolute family and the dense-drop family) and keeps measured fields
    and provenance rows byte-identical."""
    mod = load_benchmark_module("convergence_run")
    path = tmp_path / "conv.jsonl"
    rows = []
    for mode, losses in (("dense", [4.0, 2.0, 1.0, 1.0]),
                         ("gtopk", [4.0, 3.0, 2.0, 1.0])):
        rows += [{"mode": mode, "density": 1.0, "step": 10 * (i + 1),
                  "loss": l, "throughput": 1.0}
                 for i, l in enumerate(losses)]
    rows.append({"note": "provenance", "kind": "note"})
    # final_loss follows the runner's convention: the rolling-3 tail
    # mean of the curve (mean(2,1,1) = 1.3333 for dense).
    rows.append({"mode": "dense", "density": 1.0, "final_loss": 1.33333,
                 "val_top1": 0.9, "steps_to_0.5x_ref": 123,
                 "kind": "summary"})
    rows.append({"mode": "gtopk", "density": 0.001, "final_loss": 2.0,
                 "val_top1": 0.8, "kind": "summary"})
    rows.append({"dnn": "resnet20", "steps": 40, "batch_size": 4,
                 "device_kind": "cpu", "nworkers": 1,
                 "threshold_reference_loss": 0.0, "modes": [],
                 "kind": "report"})
    with open(path, "w") as fh:
        for r in rows:
            fh.write(json.dumps(r) + "\n")

    report = mod.recompute_report(str(path))
    dense, gtopk = report["modes"]
    # Stale absolute key replaced: the rolling-3 mean first clears
    # 0.5*ref=2.0 at sample 4 (mean(2,1,1)=1.33; sample 3's mean(4,2,1)
    # = 2.33 misses), so the stale 123 must become 40.
    assert dense["steps_to_0.5x_ref"] == 40
    # dense drop = 4.0-1.3333 = 2.6667; the 98% target 1.3867 is first
    # cleared by dense's rolling mean 1.3333 at step 40.
    assert dense["steps_to_0.98_of_dense_drop"] == 40
    # gtopk's rolling-3 mean bottoms at 2.0 > the 1.3867 target: the
    # full-window rule must report None (a truncated window would not).
    assert gtopk["steps_to_0.98_of_dense_drop"] is None
    # Measured fields preserved.
    assert dense["val_top1"] == 0.9 and gtopk["val_top1"] == 0.8
    assert gtopk["final_loss_vs_dense"] == 1.5
    # Provenance row survives the rewrite.
    kept = [json.loads(l) for l in open(path)]
    assert any(r.get("kind") == "note" for r in kept)
    assert any(r.get("kind") == "report" and "recomputed" in r for r in kept)
