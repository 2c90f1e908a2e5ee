"""Comm-model calibration (obs/calib.py) and the cross-run registry
(obs/registry.py).

The fit is pinned against synthetic ground truth — samples generated from
the exact alpha-beta decomposition the ledger prices, with injected
straggler outliers — so the robust estimator's recovery is checked
against known constants, never against itself. The closed loop (ISSUE
acceptance) is demonstrated end-to-end: a calibrated artifact whose alpha
differs from the committed probe flips the planner's chosen schedule at
the tree/balanced crossover with no code change, only the artifact.
"""

import json
import os

import pytest

from gtopkssgd_tpu.obs.calib import (
    CommCalibrator,
    fit_alpha_beta,
    load_fit_file,
    message_count,
)
from gtopkssgd_tpu.obs.events import AnomalyHalt, AnomalyMonitor, Thresholds
from gtopkssgd_tpu.obs.ledger import load_alpha_beta
from gtopkssgd_tpu.parallel import tree_rounds
from gtopkssgd_tpu.obs import registry as obs_registry
from gtopkssgd_tpu.obs import report as obs_report
from gtopkssgd_tpu.utils.metrics import MetricsLogger

# Ground truth for the synthetic streams: a fast fabric, far from the
# committed ~22 ms loopback-TCP probe fit so drift is unambiguous.
TRUE_ALPHA, TRUE_BETA = 4.0, 2.0


def _stream(wire_mode="gtopk", p=4, n=32, alpha=TRUE_ALPHA,
            beta=TRUE_BETA, straggler_every=0, straggler_x=5.0):
    """(msgs, wire_bytes, t_comm_ms) samples from the exact model the
    calibrator inverts, bytes swept over 8 distinct levels; every
    ``straggler_every``-th sample inflated by ``straggler_x``."""
    msgs = message_count(wire_mode, p)
    out = []
    for i in range(n):
        b = 200_000 + 40_000 * (i % 8)
        t = msgs * (alpha + (b / msgs) * 8e-6 / beta)
        if straggler_every and i % straggler_every == 0:
            t *= straggler_x
        out.append((msgs, b, t))
    return out


# ------------------------------------------------------------------ fit

def test_message_count_matches_ledger_decomposition():
    # the alpha multipliers of predict_comm_ms's schedules
    assert message_count("dense", 4) == 6            # 2(p-1)
    assert message_count("gtopk_balanced", 4) == 6   # 2(p-1)
    assert message_count("allgather", 4) == 3        # p-1
    assert message_count("gtopk", 8) == tree_rounds(8)
    assert message_count("gtopk_hier", 8, ici_size=4) == \
        tree_rounds(2)
    assert message_count("gtopk", 1) == 0            # nothing on the wire


def test_fit_recovers_ground_truth_exactly():
    fit = fit_alpha_beta(_stream())
    assert fit["identifiable"] == "alpha_beta"
    assert fit["alpha_ms"] == pytest.approx(TRUE_ALPHA, rel=1e-9)
    assert fit["beta_gbps"] == pytest.approx(TRUE_BETA, rel=1e-9)
    assert fit["resid_ms"] == pytest.approx(0.0, abs=1e-9)


def test_fit_robust_to_ten_percent_stragglers():
    """The ISSUE's pinned property: 10% of samples inflated 5x (a
    straggling rank) must not drag the fit — Theil-Sen medians ride
    through where least squares would be pulled arbitrarily far."""
    fit = fit_alpha_beta(_stream(n=40, straggler_every=10))
    assert fit["alpha_ms"] == pytest.approx(TRUE_ALPHA, rel=0.05)
    assert fit["beta_gbps"] == pytest.approx(TRUE_BETA, rel=0.05)
    # the outliers show up where they should: the residual spread
    assert fit["resid_ms"] >= 0.0


def test_fit_alpha_only_when_bytes_constant():
    """The live-run degenerate case: a fixed-k run ships near-constant
    bytes, the slope is unidentifiable, and the fit must hold beta at
    the baseline instead of hallucinating a bandwidth."""
    msgs = message_count("gtopk", 4)
    b = 400_000
    samples = [(msgs, b, msgs * (TRUE_ALPHA + (b / msgs) * 8e-6 / 25.0))
               for _ in range(12)]
    fit = fit_alpha_beta(samples, baseline_beta_gbps=25.0)
    assert fit["identifiable"] == "alpha_only"
    assert fit["beta_gbps"] == pytest.approx(25.0)
    assert fit["alpha_ms"] == pytest.approx(TRUE_ALPHA, rel=1e-6)


def test_fit_needs_two_usable_samples():
    assert fit_alpha_beta([]) is None
    assert fit_alpha_beta([(2, 1000.0, 5.0)]) is None
    # non-finite / non-positive samples are discarded, not fatal
    assert fit_alpha_beta([(2, -1.0, 5.0), (0, 1000.0, 5.0)]) is None


# ----------------------------------------------------------- calibrator

def test_refit_window_cadence_and_calib_records(tmp_path):
    """One 'calib' record per completed refit window, durably written
    through MetricsLogger (kind registration included)."""
    out = str(tmp_path)
    with MetricsLogger(out) as m:
        c = CommCalibrator("gtopk", 4, metrics=m, refit_interval=8,
                           min_samples=4)
        recs = [r for i, (msgs, b, t) in enumerate(_stream(n=32))
                if (r := c.observe(i, b, t)) is not None]
    assert len(recs) == 4                    # 32 samples / window of 8
    assert [r["n_samples"] for r in recs] == [8, 16, 24, 32]
    assert recs[-1]["alpha_fit_ms"] == pytest.approx(TRUE_ALPHA)
    assert recs[-1]["beta_fit_gbps"] == pytest.approx(TRUE_BETA)
    # drift vs the startup fit appears from the second refit on
    assert "drift_alpha_startup_x" not in recs[0]
    assert recs[1]["drift_alpha_startup_x"] == pytest.approx(1.0)
    logged = [json.loads(l) for l in
              open(os.path.join(out, "metrics.jsonl"))]
    assert [r["kind"] for r in logged] == ["calib"] * 4
    assert logged[-1]["alpha_fit_ms"] == pytest.approx(TRUE_ALPHA)


def test_drift_rule_fires_after_warmup():
    """Baseline = the committed ~22 ms probe fit, live fabric 4 ms: a
    >4x divergence in alpha. The rule arms only after comm_drift_warmup
    refits, then fires on every refit."""
    mon = AnomalyMonitor(halt_on=None)
    c = CommCalibrator(
        "gtopk", 4,
        baseline={"alpha_ms": 21.8594, "beta_gbps": 0.6,
                  "fit_source": "dcn_probe_4proc.json"},
        monitor=mon, refit_interval=8, min_samples=4)
    for i, (msgs, b, t) in enumerate(_stream(n=32)):
        c.observe(i, b, t)
    # 4 refits, warmup 2 -> fires on refits 3 and 4
    assert mon.summary() == {"comm_model_drift": 2}
    ev = mon.events[0]
    assert ev["severity"] == "warn"
    assert ev["value"] == pytest.approx(21.8594 / TRUE_ALPHA, rel=1e-4)
    assert "dcn_probe_4proc.json" in ev["message"]


def test_drift_rule_quiet_when_fit_matches_baseline():
    mon = AnomalyMonitor(halt_on=None)
    c = CommCalibrator(
        "gtopk", 4,
        baseline={"alpha_ms": TRUE_ALPHA, "beta_gbps": TRUE_BETA},
        monitor=mon, refit_interval=4, min_samples=4)
    for i, (msgs, b, t) in enumerate(_stream(n=24)):
        c.observe(i, b, t)
    assert mon.summary() == {}


def test_drift_rule_honors_halt_on_after_durable_record(tmp_path):
    """--obs-halt-on warn semantics: the halt propagates out of
    observe(), and the triggering calib record is already on disk when
    it does (record-then-raise, like every monitor rule)."""
    out = str(tmp_path)
    m = MetricsLogger(out)
    mon = AnomalyMonitor(metrics=m, halt_on="warn",
                         thresholds=Thresholds(comm_drift_warmup=0))
    c = CommCalibrator(
        "gtopk", 4, baseline={"alpha_ms": 21.8594, "beta_gbps": 0.6},
        metrics=m, monitor=mon, refit_interval=4, min_samples=4)
    with pytest.raises(AnomalyHalt) as exc:
        for i, (msgs, b, t) in enumerate(_stream(n=8)):
            c.observe(i, b, t)
    m.close()
    assert exc.value.event["rule"] == "comm_model_drift"
    recs = [json.loads(l) for l in
            open(os.path.join(out, "metrics.jsonl"))]
    kinds = [r["kind"] for r in recs]
    # the calib record that diagnosed the drift precedes the event
    assert kinds.index("calib") < kinds.index("event")


def test_calibrator_quarantines_overlapped_samples():
    """PR 15: samples measured under the overlapped pipeline report the
    EXPOSED comm span (part of the wire time hidden under selection), so
    the per-message alpha-beta inversion does not hold for them. They
    must never enter the serial fit — here every overlapped sample is
    corrupted to a third of the true time, and the fit still recovers
    the ground truth exactly."""
    c = CommCalibrator("gtopk", 4, refit_interval=8, min_samples=4,
                       fit_window=8, max_samples=8)
    rec = None
    for i, (msgs, b, t) in enumerate(_stream(n=16)):
        # an overlapped twin of every serial sample, 3x too fast
        assert c.observe(i, b, t / 3.0, overlapped=True) is None
        rec = c.observe(i, b, t) or rec
    assert len(c.samples) == 8                    # trimmed to max_samples
    assert len(c.overlap_samples) == 8            # quarantined AND trimmed
    assert all(s[2] < min(x[2] for x in c.samples)
               for s in c.overlap_samples)        # the fast twins, apart
    assert rec is not None
    assert rec["n_overlap_excluded"] == 8
    assert rec["alpha_fit_ms"] == pytest.approx(TRUE_ALPHA, rel=1e-9)
    assert rec["beta_fit_gbps"] == pytest.approx(TRUE_BETA, rel=1e-9)
    # overlapped observes never advance the refit window: 16 tagged
    # samples alone produce no fit at all
    c2 = CommCalibrator("gtopk", 4, refit_interval=4, min_samples=4)
    for i, (msgs, b, t) in enumerate(_stream(n=16)):
        assert c2.observe(i, b, t, overlapped=True) is None
    assert c2.samples == [] and c2.fits == []


# ------------------------------------------- artifact + the closed loop

def test_artifact_roundtrips_through_planner_inputs(tmp_path):
    from gtopkssgd_tpu.parallel.planner import planner_inputs

    c = CommCalibrator("gtopk", 4, refit_interval=8)
    for i, (msgs, b, t) in enumerate(_stream(n=16)):
        c.observe(i, b, t)
    d = str(tmp_path / "probe")
    path = c.write_artifact(d, manifest={
        "config_hash": "abc123", "git_sha": "deadbee",
        "compression": "gtopk", "nworkers": 4})
    assert os.path.basename(path) == "calib_fit_4proc.json"
    doc = json.load(open(path))
    assert doc["provenance"]["config_hash"] == "abc123"
    assert doc["alpha_beta_fit"]["alpha_ms"] == pytest.approx(TRUE_ALPHA)
    # ledger.load_alpha_beta reads the dcn_probe-compatible payload
    fit = load_alpha_beta(search_dir=d)
    assert fit["alpha_ms"] == pytest.approx(TRUE_ALPHA)
    assert fit["source"] == "calib_fit_4proc.json"
    inputs = planner_inputs(d)
    assert inputs["fit_source"] == "calib_fit_4proc.json"
    assert inputs["beta_gbps"] == pytest.approx(TRUE_BETA)


def test_artifact_none_without_samples(tmp_path):
    c = CommCalibrator("gtopk", 4)
    assert c.write_artifact(str(tmp_path)) is None


def test_calib_artifact_flips_planner_schedule(tmp_path):
    """ISSUE acceptance: the closed obs->planner loop. At (p=32,
    n=25.6M, k=256k) the committed ~22 ms probe alpha prices the
    hypercube tree cheapest; a run calibrated on a fast fabric
    (alpha ~ 0.1 ms) writes an artifact that — with NO code change —
    makes the same planner call pick the balanced schedule."""
    from gtopkssgd_tpu.parallel.planner import build_decision, resolve_plan

    shape = dict(p=32, n=25_557_032, k=255_571)
    committed = build_decision("gtopk", **shape)
    assert committed.plan.name == "tree"

    c = CommCalibrator("gtopk", 32, refit_interval=8)
    for i, (msgs, b, t) in enumerate(
            _stream(wire_mode="gtopk", p=32, n=16, alpha=0.1)):
        c.observe(i, b, t)
    d = str(tmp_path / "calibrated")
    c.write_artifact(d)

    calibrated = build_decision("gtopk", probe_dir=d, **shape)
    assert calibrated.inputs["fit_source"] == "calib_fit_32proc.json"
    assert calibrated.inputs["alpha_ms"] == pytest.approx(0.1, rel=0.05)
    assert calibrated.plan.name == "balanced"
    # the optimizer is handed the decision's name, and its trace-time
    # lookup returns the schedule that was decided
    assert resolve_plan("gtopk", calibrated.plan.name).schedule == "balanced"
    assert resolve_plan("gtopk", committed.plan.name).schedule == "tree"


def test_load_alpha_beta_numeric_proc_sort(tmp_path):
    """Satellite regression: lexicographic basename sort ranked 8proc
    over 16proc; the numeric sort honors the docstring's "largest proc
    count present wins", and a calib_fit outranks a dcn_probe at equal
    proc count."""
    d = str(tmp_path)
    for n in (2, 8, 16):
        with open(os.path.join(d, f"dcn_probe_{n}proc.json"), "w") as fh:
            json.dump({"procs": n, "alpha_beta_fit":
                       {"alpha_ms": float(n), "beta_gbps": 1.0}}, fh)
    fit = load_alpha_beta(search_dir=d)
    assert fit["source"] == "dcn_probe_16proc.json"
    assert fit["alpha_ms"] == 16.0
    # explicit nprocs still pins the exact count
    assert load_alpha_beta(search_dir=d, nprocs=8)["alpha_ms"] == 8.0
    # in-situ calibration beats the synthetic probe at the same P
    with open(os.path.join(d, "calib_fit_16proc.json"), "w") as fh:
        json.dump({"procs": 16, "alpha_beta_fit":
                   {"alpha_ms": 99.0, "beta_gbps": 2.0}}, fh)
    assert load_alpha_beta(search_dir=d)["source"] == "calib_fit_16proc.json"


def test_load_fit_file_rejects_malformed(tmp_path):
    p = str(tmp_path / "bad.json")
    with open(p, "w") as fh:
        json.dump({"alpha_beta_fit": {"alpha_ms": 1.0}}, fh)  # no beta
    with pytest.raises(ValueError):
        load_fit_file(p)
    good = str(tmp_path / "calib_fit_2proc.json")
    with open(good, "w") as fh:
        json.dump({"alpha_beta_fit":
                   {"alpha_ms": 1.5, "beta_gbps": 3.0}}, fh)
    fit = load_fit_file(good)
    assert fit == {"alpha_ms": 1.5, "beta_gbps": 3.0,
                   "source": "calib_fit_2proc.json"}


# ------------------------------------------------------------- registry

def _entry(config_hash="cfg0", git_sha="aaaa", **stats):
    base = dict(steps_per_sec=2.0, loss_last=1.5, alpha_ms=4.0,
                beta_gbps=2.0, wire_bytes_per_step=1e6)
    base.update(stats)
    return {"time": 0.0, "config_hash": config_hash, "git_sha": git_sha,
            "stats": base}


def _run_records(config_hash="cfg0", loss=1.5, with_calib=True):
    recs = [{"kind": "manifest", "time": 100.0, "rank": 0,
             "config_hash": config_hash, "git_sha": "bbbb",
             "dnn": "resnet20", "compression": "gtopk", "nworkers": 2},
            {"kind": "train", "time": 101.0, "rank": 0, "step": 1,
             "loss": 2.0},
            {"kind": "train", "time": 103.0, "rank": 0, "step": 5,
             "loss": loss},
            {"kind": "obs", "time": 102.0, "rank": 0, "step": 2,
             "wire_bytes": 1e6, "audit_recall": 0.93},
            {"kind": "obs", "time": 102.5, "rank": 0, "step": 4,
             "wire_bytes": 1e6, "audit_recall": 0.97},
            {"kind": "attr", "time": 102.6, "rank": 0,
             "t_comm_us": 200.0, "t_total_us": 1000.0}]
    if with_calib:
        recs.append({"kind": "calib", "time": 103.5, "rank": 0,
                     "step": 5, "alpha_fit_ms": 4.0,
                     "beta_fit_gbps": 2.0, "n_samples": 8})
    return recs


def test_run_summary_distills_the_stream():
    s = obs_registry.run_summary(_run_records())
    assert s["config_hash"] == "cfg0"
    st = s["stats"]
    assert st["steps_per_sec"] == pytest.approx(2.0)   # 4 steps / 2 s
    assert st["loss_last"] == pytest.approx(1.5)
    assert st["mean_comm_ratio"] == pytest.approx(0.2)
    assert st["alpha_ms"] == pytest.approx(4.0)
    assert st["recall_floor"] == pytest.approx(0.93)
    assert st["wire_bytes_per_step"] == pytest.approx(1e6)
    # no manifest -> nothing to key on
    assert obs_registry.run_summary(_run_records()[1:]) is None


def test_run_summary_carries_pipeline_shape():
    """PR 15 plan-shape stats: pipeline from the plan record (the
    decision as executed), n_buckets from the manifest's bucket_ks, and
    overlap_frac averaged over the attr records."""
    recs = _run_records()
    recs[0]["bucket_ks"] = [120, 80, 56]
    recs.insert(1, {"kind": "plan", "time": 100.5, "rank": 0,
                    "name": "tree", "pipeline": "overlap"})
    for rec, f in zip([r for r in recs if r.get("kind") == "attr"],
                      (0.5,)):
        rec["overlap_frac"] = f
    recs.append({"kind": "attr", "time": 102.8, "rank": 0,
                 "t_comm_us": 100.0, "t_total_us": 1000.0,
                 "overlap_frac": 0.7})
    st = obs_registry.run_summary(recs)["stats"]
    assert st["pipeline"] == "overlap"
    assert st["n_buckets"] == 3
    assert st["overlap_frac"] == pytest.approx(0.6)
    # no plan record -> the manifest stamp is the fallback
    plain = _run_records()
    plain[0]["pipeline"] = "serial"
    st2 = obs_registry.run_summary(plain)["stats"]
    assert st2["pipeline"] == "serial"
    assert "overlap_frac" not in st2 and "n_buckets" not in st2
    # the history table prints the three new columns for every entry
    entry = obs_registry.run_summary(recs)
    (row,) = obs_registry.history_rows([entry])
    assert len(row) == len(obs_registry.HISTORY_HEADER)
    hdr = obs_registry.HISTORY_HEADER
    assert row[hdr.index("pipeline")] == "overlap"
    assert row[hdr.index("B")] == "3"
    assert row[hdr.index("ovl_frac")] == "0.6000"
    (row2,) = obs_registry.history_rows([obs_registry.run_summary(plain)])
    assert row2[hdr.index("pipeline")] == "serial"
    assert row2[hdr.index("B")] == "-"


def test_regress_pins_pipeline_and_bucket_shape():
    """The exact-string loop: a pipeline flipped serial<->overlap under
    the same config is a plan regression; overlap_frac gets a purely
    absolute 0.1 slack so a serial 0.0 baseline still bounds the run;
    n_buckets is exact."""
    base = _entry(pipeline="overlap", n_buckets=4, overlap_frac=0.6)

    def _status(cur, field):
        rows, failures = obs_registry.regress(cur, base)
        return {r[0]: r[4] for r in rows}[field], failures

    same = _entry(pipeline="overlap", n_buckets=4, overlap_frac=0.62)
    st, fails = _status(same, "pipeline")
    assert st == "ok" and fails == 0
    # pipeline silently collapsed back to serial -> FAIL
    st, fails = _status(
        _entry(pipeline="serial", n_buckets=4, overlap_frac=0.62),
        "pipeline")
    assert st == "FAIL" and fails >= 1
    # pipeline vanished entirely -> MISSING
    st, fails = _status(_entry(n_buckets=4, overlap_frac=0.62), "pipeline")
    assert st == "MISSING" and fails >= 1
    # overlap collapsed past the 0.1 absolute slack -> FAIL
    st, _ = _status(
        _entry(pipeline="overlap", n_buckets=4, overlap_frac=0.45),
        "overlap_frac")
    assert st == "FAIL"
    # the DP re-deciding B under the same config -> FAIL (exact)
    st, _ = _status(
        _entry(pipeline="overlap", n_buckets=5, overlap_frac=0.6),
        "n_buckets")
    assert st == "FAIL"
    # new instrumentation on the current side is not a regression
    rows, fails = obs_registry.regress(
        _entry(pipeline="overlap"), _entry())
    assert {r[0]: r[4] for r in rows}["pipeline"] == "new"
    assert fails == 0
    # serial baseline 0.0 bounds a mildly-overlapped current run
    rows, fails = obs_registry.regress(
        _entry(overlap_frac=0.08), _entry(overlap_frac=0.0))
    assert {r[0]: r[4] for r in rows}["overlap_frac"] == "ok"
    assert fails == 0


def test_registry_append_history_and_torn_lines(tmp_path, capsys):
    d = str(tmp_path / "reg")
    obs_registry.append_run(d, _entry())
    obs_registry.append_run(d, _entry(git_sha="cccc", steps_per_sec=2.2))
    # a run killed mid-append leaves a torn line; it must be skipped
    with open(obs_registry.registry_path(d), "a") as fh:
        fh.write('{"time": 1.0, "config_')
    entries, bad = obs_registry.load_registry(d)
    assert len(entries) == 2 and bad == 1
    rows = obs_registry.history_rows(entries)
    assert len(rows) == 2
    assert obs_registry.history_rows(entries, config_hash="nope") == []
    # the offline CLI contract: exit 0 with entries, 1 without
    assert obs_report.main(["history", d]) == 0
    assert "2 run(s)" in capsys.readouterr().out
    assert obs_report.main(["history", str(tmp_path / "empty")]) == 1


def _run_dir(tmp_path, name, **kw):
    d = tmp_path / name
    d.mkdir()
    with open(d / "metrics.jsonl", "w") as fh:
        for rec in _run_records(**kw):
            fh.write(json.dumps(rec) + "\n")
    return str(d)


def test_regress_exit_contract(tmp_path):
    """Exit codes follow the gate contract: 0 within tolerance, 1 on
    drift (or a vanished stat), 2 on usage (empty registry / no
    same-config baseline without --allow-mismatch)."""
    reg = str(tmp_path / "reg")
    run = _run_dir(tmp_path, "run")
    # 2: registry empty
    assert obs_report.main(["regress", run, "--registry", reg]) == 2
    obs_registry.append_run(
        reg, obs_registry.run_summary(_run_records()))
    # 0: identical stats
    assert obs_report.main(["regress", run, "--registry", reg]) == 0
    # 1: loss regressed far past 25% rtol
    worse = _run_dir(tmp_path, "worse", loss=15.0)
    assert obs_report.main(["regress", worse, "--registry", reg]) == 1
    # 1: a stat the baseline had (alpha_ms) vanished from the run
    gone = _run_dir(tmp_path, "gone", with_calib=False)
    assert obs_report.main(["regress", gone, "--registry", reg]) == 1
    # 2 unless --allow-mismatch: different config_hash
    other = _run_dir(tmp_path, "other", config_hash="cfg1")
    assert obs_report.main(["regress", other, "--registry", reg]) == 2
    assert obs_report.main(["regress", other, "--registry", reg,
                            "--allow-mismatch"]) == 0


def test_regress_picks_latest_same_config_baseline(tmp_path):
    reg = str(tmp_path / "reg")
    obs_registry.append_run(reg, _entry(config_hash="cfgX"))
    obs_registry.append_run(reg, _entry(steps_per_sec=9.0))
    entries, _ = obs_registry.load_registry(reg)
    cur = obs_registry.run_summary(_run_records())
    base = obs_registry.pick_baseline(cur, entries)
    assert base["stats"]["steps_per_sec"] == 9.0   # newest cfg0 entry
    rows, failures = obs_registry.regress(cur, base)
    # 2.0 vs 9.0 steps/sec is far outside 25%
    assert failures >= 1


# -------------------------------------------------- trainer integration

def test_trainer_calibrates_and_writes_artifact(tmp_path):
    """End-to-end on the 2-device CPU mesh: --obs-calib captures real
    profiler-attributed dispatches, logs calib records, stamps fit
    provenance into the manifest, writes the end-of-run artifact, and
    appends a registry line the regress CLI can read."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer
    from gtopkssgd_tpu.parallel.planner import planner_inputs

    out = str(tmp_path / "run")
    reg = str(tmp_path / "reg")
    cfg = TrainConfig(dnn="resnet20", batch_size=4, nworkers=2,
                      compression="gtopk_layerwise", density=0.01,
                      seed=42, max_epochs=1, log_interval=1,
                      obs_interval=1, eval_batches=1, out_dir=out,
                      obs_calib=True, obs_calib_interval=1,
                      registry=reg)
    with Trainer(cfg) as t:
        assert t.calib is not None
        t.train(5)
        assert len(t.calib.samples) >= 4, \
            "profiler attribution produced no usable comm samples"
    recs = [json.loads(l) for l in open(os.path.join(out, "metrics.jsonl"))]
    man = next(r for r in recs if r["kind"] == "manifest")
    assert man["comm_fit_source"]          # provenance stamped
    calibs = [r for r in recs if r["kind"] == "calib"]
    assert calibs, "no calib record logged"
    assert calibs[0]["wire_mode"] and calibs[0]["p"] == 2
    assert calibs[0]["n_samples"] >= 4
    # end-of-run artifact closes the loop for the NEXT run
    art = os.path.join(out, "calib_fit_2proc.json")
    assert os.path.exists(art)
    assert json.load(open(art))["provenance"]["config_hash"] == \
        man["config_hash"]
    assert planner_inputs(out)["fit_source"] == "calib_fit_2proc.json"
    # registry got this run's line; regress against itself passes
    entries, bad = obs_registry.load_registry(reg)
    assert len(entries) == 1 and bad == 0
    assert entries[0]["config_hash"] == man["config_hash"]
    assert obs_report.main(["regress", out, "--registry", reg]) == 0
    # provenance lines print from the shards alone
    assert obs_report.main(["plan", out]) == 0
    assert obs_report.main(["ledger", out]) == 0


def test_trainer_comm_model_fit_flag(tmp_path):
    """--comm-model-fit: an explicit artifact prices the plan decision,
    and its filename lands in manifest + plan record (that the step
    runs the decided plan: test_planner's decides_once test)."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    fit_path = str(tmp_path / "calib_fit_2proc.json")
    with open(fit_path, "w") as fh:
        json.dump({"procs": 2, "alpha_beta_fit":
                   {"alpha_ms": 7.25, "beta_gbps": 3.5}}, fh)
    out = str(tmp_path / "run")
    cfg = TrainConfig(dnn="resnet20", batch_size=4, nworkers=2,
                      compression="gtopk_layerwise", density=0.01,
                      seed=42, max_epochs=1, log_interval=1,
                      eval_batches=1, out_dir=out,
                      comm_model_fit=fit_path)
    with Trainer(cfg) as t:
        d = t._plan_decision
        assert d.inputs["fit_source"] == "calib_fit_2proc.json"
        assert d.inputs["alpha_ms"] == pytest.approx(7.25)
    recs = [json.loads(l) for l in open(os.path.join(out, "metrics.jsonl"))]
    man = next(r for r in recs if r["kind"] == "manifest")
    assert man["comm_fit_source"] == "calib_fit_2proc.json"
    assert man["comm_fit_alpha_ms"] == pytest.approx(7.25)
    plan = next(r for r in recs if r["kind"] == "plan")
    assert plan["fit_source"] == "calib_fit_2proc.json"
    # a malformed artifact fails at startup, never silently falls back
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as fh:
        fh.write("{}")
    with pytest.raises(ValueError):
        Trainer(TrainConfig(dnn="resnet20", batch_size=4, nworkers=2,
                            compression="gtopk_layerwise", density=0.01,
                            seed=42, comm_model_fit=bad))
