"""Distributed critical path: interval algebra, the cross-rank chain
walk, the critpath_shift rule, and the report/exporter/registry
surfaces.

The committed fixture (tests/fixtures/critpath/, regenerated by
make_critpath_fixture.py in the same dir) is a synthetic 3-rank run
over steps 1-6: steps 1-3 are compute-bound through rank 0 (rank 1
wait-dominated, rank 2 in between, and 2.5 s late at every step —
a persistent straggler), steps 4-6 are a barrier stall on every rank
so the global critical stage shifts compute -> wait for exactly the
default critpath_shift_windows. Values are hand-chosen, so the
expected chains here are hand-computed and asserted exactly.
"""

import json
import os
import sys

import pytest

from tests.conftest import run_two_process

from gtopkssgd_tpu.obs import critpath

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "critpath")


def _chain(res):
    return [(p["rank"], p["stage"], p["t0_us"], p["t1_us"])
            for p in res["chain"]]


# ------------------------------------------------------ interval algebra
def test_wait_split_proportional():
    """Overlapping comm intervals union first; each union interval is
    cut at its proportional share of the wire budget."""
    wire, wait = critpath.wait_split([(0.0, 100.0), (50.0, 200.0),
                                      (300.0, 400.0)], 150.0)
    # union = [(0,200),(300,400)], 300 us total, wire_frac = 0.5
    assert wire == [(0.0, 100.0), (300.0, 350.0)]
    assert wait == [(100.0, 200.0), (350.0, 400.0)]


def test_wait_split_degenerate_budgets():
    iv = [(0.0, 100.0)]
    wire, wait = critpath.wait_split(iv, float("inf"))
    assert (wire, wait) == ([(0.0, 100.0)], [])
    wire, wait = critpath.wait_split(iv, 0.0)
    assert (wire, wait) == ([], [(0.0, 100.0)])
    assert critpath.wait_split([], 10.0) == ([], [])


def test_stage_segments_normalize_and_split():
    segs = critpath.stage_segments(
        {"compute": [(1000.0, 1800.0)], "select": [(1800.0, 1900.0)],
         "comm": [(1900.0, 2000.0)]}, wire_us=60.0)
    assert segs == [
        {"stage": "compute", "t0_us": 0.0, "t1_us": 800.0},
        {"stage": "select", "t0_us": 800.0, "t1_us": 900.0},
        {"stage": "comm", "t0_us": 900.0, "t1_us": 960.0},
        {"stage": "wait", "t0_us": 960.0, "t1_us": 1000.0},
    ]


def test_stage_segments_fill_gaps_tiles_the_wall():
    """Real-capture mode: uncovered gaps are absorbed into the stage
    that ran latest before them, so segments tile the window and the
    single-rank chain explains the full wall."""
    segs = critpath.stage_segments(
        {"compute": [(0.0, 100.0), (150.0, 300.0)],
         "select": [(300.0, 320.0)],
         "comm": [(400.0, 500.0)]}, wire_us=60.0, fill_gaps=True)
    assert segs == [
        {"stage": "compute", "t0_us": 0.0, "t1_us": 300.0},
        {"stage": "select", "t0_us": 300.0, "t1_us": 400.0},
        {"stage": "comm", "t0_us": 400.0, "t1_us": 460.0},
        {"stage": "wait", "t0_us": 460.0, "t1_us": 500.0},
    ]
    assert critpath.critical_path(
        {0: segs})["crit_frac"] == pytest.approx(1.0)


def test_coarsen_compacts_but_totals_stay_fine():
    """Micro-flicker collapses into the surrounding stage for the
    durable record; build_record's totals must come from the fine
    list so the budget is not skewed by the compaction."""
    fine = [{"stage": "compute", "t0_us": 0.0, "t1_us": 300.0},
            {"stage": "select", "t0_us": 300.0, "t1_us": 305.0},
            {"stage": "compute", "t0_us": 305.0, "t1_us": 400.0}]
    coarse = critpath.coarsen(fine, min_us=50.0)
    assert coarse == [{"stage": "compute", "t0_us": 0.0,
                       "t1_us": 400.0}]
    rec = critpath.build_record(coarse,
                                totals=critpath.stage_totals(fine))
    assert rec["t_select_us"] == pytest.approx(5.0)
    assert rec["t_compute_us"] == pytest.approx(395.0)
    # A sustained stage survives coarsening.
    fine.append({"stage": "comm", "t0_us": 400.0, "t1_us": 600.0})
    assert critpath.coarsen(fine, min_us=50.0) == [
        {"stage": "compute", "t0_us": 0.0, "t1_us": 400.0},
        {"stage": "comm", "t0_us": 400.0, "t1_us": 600.0}]


def test_dominant_stage_tie_breaks_in_stage_order():
    assert critpath.dominant_stage(
        {"compute": 5.0, "wait": 5.0}) == "compute"
    assert critpath.dominant_stage({"wait": 5.0, "comm": 5.0}) == "comm"
    assert critpath.dominant_stage({}) is None
    assert critpath.dominant_stage({"compute": 0.0}) is None


def test_build_record_fields():
    segs = critpath.stage_segments(
        {"compute": [(0.0, 60.0)], "select": [(60.0, 70.0)],
         "comm": [(70.0, 90.0)]}, wire_us=10.0)
    rec = critpath.build_record(segs, step=3)
    assert rec["step"] == 3
    assert rec["wall_us"] == 90.0
    assert rec["t_compute_us"] == 60.0
    assert rec["t_select_us"] == 10.0
    assert rec["t_comm_wire_us"] == 10.0
    assert rec["t_wait_us"] == 10.0
    assert rec["wait_frac"] == pytest.approx(10.0 / 90.0, abs=1e-6)
    assert rec["crit_stage"] == "compute"
    assert rec["segments"] == segs


# -------------------------------------------------------- critical path
def test_critical_path_runs_through_busy_rank():
    """A rank waiting while another works hands the chain off to the
    busy rank — skew-wait never absorbs blame when real work covers
    the instant."""
    res = critpath.critical_path({
        0: [{"stage": "compute", "t0_us": 0.0, "t1_us": 900.0},
            {"stage": "comm", "t0_us": 900.0, "t1_us": 1000.0}],
        1: [{"stage": "compute", "t0_us": 0.0, "t1_us": 400.0},
            {"stage": "comm", "t0_us": 400.0, "t1_us": 500.0},
            {"stage": "wait", "t0_us": 500.0, "t1_us": 1000.0}],
    })
    assert _chain(res) == [(0, "compute", 0.0, 900.0),
                           (0, "comm", 900.0, 1000.0)]
    assert res["crit_rank"] == 0
    assert res["crit_stage"] == "compute"
    assert res["crit_frac"] == pytest.approx(1.0)
    assert res["blocked_us"] == {0: 0.0, 1: 500.0}


def test_critical_path_barrier_wait_joins_chain():
    """When EVERY rank waits over the same span, the wait itself is the
    bottleneck and must appear on the chain."""
    segs = [{"stage": "compute", "t0_us": 0.0, "t1_us": 100.0},
            {"stage": "wait", "t0_us": 100.0, "t1_us": 900.0},
            {"stage": "comm", "t0_us": 900.0, "t1_us": 1000.0}]
    res = critpath.critical_path({0: list(segs), 1: list(segs)})
    assert _chain(res) == [(0, "compute", 0.0, 100.0),
                           (0, "wait", 100.0, 900.0),
                           (0, "comm", 900.0, 1000.0)]
    assert res["crit_stage"] == "wait"
    assert res["crit_frac"] == pytest.approx(1.0)


def test_critical_path_tie_breaks_are_deterministic():
    """Identical rank timelines: the walk always lands on the lowest
    rank, and repeated calls return identical chains."""
    segs = [{"stage": "compute", "t0_us": 0.0, "t1_us": 500.0},
            {"stage": "comm", "t0_us": 500.0, "t1_us": 600.0}]
    by_rank = {2: list(segs), 0: list(segs), 1: list(segs)}
    first = critpath.critical_path(by_rank)
    assert first["crit_rank"] == 0
    assert all(p["rank"] == 0 for p in first["chain"])
    for _ in range(3):
        assert critpath.critical_path(by_rank) == first


def test_critical_path_gap_lowers_crit_frac():
    """An uncovered span (profiler hole) is skipped over, not invented:
    the chain jumps to the previous segment end and crit_frac drops."""
    res = critpath.critical_path({
        0: [{"stage": "compute", "t0_us": 0.0, "t1_us": 400.0},
            {"stage": "comm", "t0_us": 600.0, "t1_us": 1000.0}],
    })
    covered = sum(p["t1_us"] - p["t0_us"] for p in res["chain"])
    assert covered == pytest.approx(800.0)
    assert res["crit_frac"] == pytest.approx(0.8)


def test_critical_path_empty():
    res = critpath.critical_path({})
    assert res["chain"] == [] and res["crit_stage"] is None


# ------------------------------------------------------ fleet fixture join
def test_fixture_join_matches_hand_computed_chains():
    from gtopkssgd_tpu.obs import fleet
    merged = fleet.merge([FIXTURE])
    rows = merged["critpath"]
    assert [r["step"] for r in rows] == [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
    for r in rows[:3]:
        assert (r["crit_rank"], r["crit_stage"]) == (0, "compute")
        assert [(p["rank"], p["stage"], p["t0_us"], p["t1_us"])
                for p in r["chain"]] == [(0, "compute", 0.0, 900.0),
                                         (0, "comm", 900.0, 1000.0)]
    for r in rows[3:]:
        assert (r["crit_rank"], r["crit_stage"]) == (0, "wait")
        assert [(p["rank"], p["stage"], p["t0_us"], p["t1_us"])
                for p in r["chain"]] == [(0, "compute", 0.0, 100.0),
                                         (0, "wait", 100.0, 900.0),
                                         (0, "comm", 900.0, 1000.0)]
    assert all(r["crit_frac"] == pytest.approx(1.0) for r in rows)
    budgets = merged["critpath_budget"]
    assert budgets[0]["compute"] == pytest.approx(3000.0)
    assert budgets[0]["comm"] == pytest.approx(600.0)
    assert budgets[0]["wait"] == pytest.approx(2400.0)
    # Blocked time is per-rank wall-clock wait, independent of the chain.
    assert budgets[1]["blocked_us"] == pytest.approx(3900.0)
    assert budgets[2]["blocked_us"] == pytest.approx(3300.0)


def test_fixture_straggler_rows_carry_stage():
    from gtopkssgd_tpu.obs import fleet
    merged = fleet.merge([FIXTURE])
    st = merged["stragglers"]
    assert [s["slowest_rank"] for s in st] == [2] * 6
    # rank 2's LOCAL critical stage rides the row: compute while the
    # fleet is compute-bound, wait once the barrier stall begins.
    assert [s["stage"] for s in st] == (["compute"] * 3 + ["wait"] * 3)


def test_fixture_shift_fires_on_third_consecutive_step():
    from gtopkssgd_tpu.obs import fleet
    merged = fleet.merge([FIXTURE])
    shifts = [e for e in merged["events"] if e["rule"] == "critpath_shift"]
    assert len(shifts) == 1
    ev = shifts[0]
    assert ev["step"] == 6.0
    assert (ev["from_stage"], ev["to_stage"]) == ("compute", "wait")
    flagged = [r["step"] for r in merged["critpath"] if r["shift"]]
    assert flagged == [6.0]


# ------------------------------------------------------- shift rule unit
def test_critpath_shift_warmup_streak_and_rearm():
    from gtopkssgd_tpu.obs.events import AnomalyMonitor, Thresholds
    m = AnomalyMonitor(thresholds=Thresholds(critpath_shift_windows=3))
    # Warmup: first observation only establishes the modal stage.
    assert m.observe_critpath(1, crit_stage="compute") == []
    assert m.observe_critpath(2, crit_stage="compute") == []
    # Two differing windows: below threshold, no event.
    assert m.observe_critpath(3, crit_stage="wait") == []
    assert m.observe_critpath(4, crit_stage="wait") == []
    # Alternation restarts the streak — churn is not a shift.
    assert m.observe_critpath(5, crit_stage="comm") == []
    assert m.observe_critpath(6, crit_stage="wait") == []
    assert m.observe_critpath(7, crit_stage="wait") == []
    evs = m.observe_critpath(8, crit_stage="wait")
    assert len(evs) == 1 and evs[0]["rule"] == "critpath_shift"
    assert evs[0]["from_stage"] == "compute"
    assert evs[0]["to_stage"] == "wait"
    # Fired => the new stage is adopted as modal and the rule re-arms.
    assert m.observe_critpath(9, crit_stage="wait") == []


def test_critpath_shift_records_durable_event_before_halt(tmp_path):
    """Halt ordering: the event must land in metrics.jsonl (flushed)
    BEFORE AnomalyHalt propagates, so a halted run's shard still
    explains itself."""
    from gtopkssgd_tpu.obs.events import (AnomalyHalt, AnomalyMonitor,
                                          HALT_EXIT_CODE, Thresholds)
    from gtopkssgd_tpu.utils.metrics import MetricsLogger
    path = tmp_path / "metrics.jsonl"
    with MetricsLogger(str(tmp_path)) as log:
        m = AnomalyMonitor(thresholds=Thresholds(critpath_shift_windows=2),
                           metrics=log, halt_on="warn")
        m.observe_critpath(1, crit_stage="compute")
        m.observe_critpath(2, crit_stage="wait")
        with pytest.raises(AnomalyHalt):
            m.observe_critpath(3, crit_stage="wait")
        assert HALT_EXIT_CODE == 44
        recs = [json.loads(ln) for ln in
                path.read_text().splitlines() if ln.strip()]
        durable = [r for r in recs if r.get("kind") == "event"
                   and r.get("rule") == "critpath_shift"]
        assert durable and durable[0]["to_stage"] == "wait"


# --------------------------------------------------------- report surfaces
def test_report_critpath_cli(capsys):
    from gtopkssgd_tpu.obs import report
    assert report.main(["critpath", FIXTURE]) == 0
    out = capsys.readouterr().out
    assert "r0:compute > r0:comm" in out
    assert "r0:compute > r0:wait > r0:comm" in out
    assert "modal critical stage" in out
    assert "blocked" in out


def test_report_critpath_cli_halt_exits_44(capsys):
    from gtopkssgd_tpu.obs import report
    from gtopkssgd_tpu.obs.events import HALT_EXIT_CODE
    rc = report.main(["critpath", FIXTURE, "--halt-on", "warn"])
    assert rc == HALT_EXIT_CODE == 44
    out = capsys.readouterr().out
    assert "critpath_shift" in out


def test_report_critpath_cli_json(tmp_path, capsys):
    from gtopkssgd_tpu.obs import report
    out_json = tmp_path / "critpath.json"
    assert report.main(["critpath", FIXTURE, "--json",
                        str(out_json)]) == 0
    capsys.readouterr()
    doc = json.loads(out_json.read_text())
    assert len(doc["rows"]) == 6
    assert doc["rows"][0]["crit_stage"] == "compute"
    assert "0" in doc["budgets"] or 0 in doc["budgets"]
    assert any(e["rule"] == "critpath_shift" for e in doc["events"])


def test_report_fleet_prints_straggler_stage_column(capsys):
    from gtopkssgd_tpu.obs import report
    assert report.main(["fleet", FIXTURE]) == 0
    out = capsys.readouterr().out
    straggler = out[out.index("[straggler]"):]
    assert "stage" in straggler.splitlines()[1]
    assert "wait" in straggler


def test_report_watch_prints_behind_median_and_stage(capsys):
    from gtopkssgd_tpu.obs import report
    rc = report.main(["watch", FIXTURE, "--interval", "0.01",
                      "--iterations", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "behind_median_s=2.5000" in out
    assert "crit_stage=wait" in out


# ------------------------------------------------- timeline + exporter
def test_timeline_gets_critpath_stage_lanes():
    from gtopkssgd_tpu.obs import fleet
    from gtopkssgd_tpu.obs.timeline import (timeline_from_records,
                                            validate_timeline)
    by_rank, _ = fleet.load_shards(fleet.resolve_targets([FIXTURE]))
    recs = [r for rr in by_rank.values() for r in rr]
    doc = timeline_from_records(recs)
    assert validate_timeline(doc) == []
    lanes = {e["tid"] for e in doc["traceEvents"]
             if e.get("cat") == "critpath"}
    assert lanes == {100, 101, 102}
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "thread_name"}
    assert {"critpath rank 0", "critpath rank 1",
            "critpath rank 2"} <= names
    # The chain runs through rank 0 at every fixture step, so only lane
    # 100's segments carry the critical flag.
    crit = [e for e in doc["traceEvents"] if e.get("cat") == "critpath"
            and e["args"]["critical"]]
    assert crit and all(e["tid"] == 100 for e in crit)


def test_exporter_serves_critpath_gauges():
    from gtopkssgd_tpu.obs.exporter import MetricsExporter
    exp = MetricsExporter()
    exp.observe({"kind": "critpath", "step": 4, "wall_us": 1000.0,
                 "wait_frac": 0.8, "t_wait_us": 800.0,
                 "crit_stage": "wait",
                 "segments": [{"stage": "wait", "t0_us": 0.0,
                               "t1_us": 800.0}]})
    text = exp.scrape()
    assert "gtopk_critpath_wait_frac" in text
    assert "gtopk_critpath_wall_us" in text
    assert "segments" not in text  # lists never become gauges


# ----------------------------------------------------------- registry
def test_run_summary_carries_crit_stage_modal_and_wait_frac():
    from gtopkssgd_tpu.obs import fleet, registry
    by_rank, _ = fleet.load_shards(fleet.resolve_targets([FIXTURE]))
    recs = [r for rr in by_rank.values() for r in rr]
    entry = registry.run_summary(recs)
    assert entry is not None
    stats = entry["stats"]
    # 12 of the 18 fixture records are locally wait-dominated.
    assert stats["crit_stage_modal"] == "wait"
    assert stats["wait_frac"] == pytest.approx(9.6 / 18.0, abs=1e-6)


def test_regress_pins_crit_fields():
    from gtopkssgd_tpu.obs import registry
    assert any(f == "wait_frac" and atol == 0.10
               for f, _, atol in registry.REGRESS_CHECKS)
    assert "crit_stage_modal" in registry.REGRESS_EXACT_STR
    base = {"stats": {"wait_frac": 0.20, "crit_stage_modal": "compute"}}
    same = {"stats": {"wait_frac": 0.25, "crit_stage_modal": "compute"}}
    _, fails = registry.regress(same, base)
    assert fails == 0
    moved = {"stats": {"wait_frac": 0.35, "crit_stage_modal": "wait"}}
    rows, fails = registry.regress(moved, base)
    assert fails == 2
    failed = {r[0] for r in rows if r[-1] != "PASS"}
    assert {"wait_frac", "crit_stage_modal"} <= failed


def test_history_header_has_crit_columns():
    from gtopkssgd_tpu.obs import registry
    assert "crit_stage" in registry.HISTORY_HEADER
    assert "wait_frac" in registry.HISTORY_HEADER


def test_metrics_kind_registered():
    from gtopkssgd_tpu.utils.metrics import KINDS
    assert "critpath" in KINDS


# ------------------------------------------------- 2-process end-to-end
CRITPATH_WORKER = r"""
import sys
sys.path.insert(0, sys.argv[3])  # repo root (script itself lives in tmp)
import jax
jax.config.update("jax_platforms", "cpu")
from gtopkssgd_tpu.utils import enable_compilation_cache
enable_compilation_cache()
coord, pid = sys.argv[1], int(sys.argv[2])
try:
    jax.distributed.initialize(coordinator_address=coord, num_processes=2,
                               process_id=pid)
except Exception as e:
    print("DISTRIBUTED-UNSUPPORTED:", e)
    raise SystemExit(99)
try:
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("capability probe")
except Exception as e:
    print("DISTRIBUTED-UNSUPPORTED:", e)
    raise SystemExit(99)
from gtopkssgd_tpu.trainer import TrainConfig, Trainer

# Same model/config family as the fleet e2e so the persistent
# compilation cache is shared; critpath on at every-step cadence.
cfg = TrainConfig(dnn="resnet20", batch_size=4, nworkers=2,
                  compression="gtopk", density=0.01, max_epochs=1,
                  log_interval=1, eval_batches=1, out_dir=sys.argv[4],
                  obs_critpath=True, obs_calib_interval=1)
with Trainer(cfg) as t:
    t.train(2)
print(f"CRITPATH-OK pid={pid}")
"""


@pytest.mark.slow  # ~2-3 min: two real processes + jit compile; the
# single-rank capture->segments path stays covered tier-1 through
# test_obs_attr's profiler tests + the unit algebra above, and the
# 2-proc path through the obs gate smoke's critpath sub-run.
def test_two_process_critpath_records_and_join(tmp_path):
    """End-to-end: two real processes (jax.distributed over CPU) with
    --obs-critpath at every-step cadence; each shard must carry durable
    critpath records whose segments the fleet joiner can chain. Skips
    on jax builds without CPU cross-process collectives."""
    out_dir = run_two_process(CRITPATH_WORKER, tmp_path, "CRITPATH-OK")

    from gtopkssgd_tpu.obs import fleet

    by_rank, _ = fleet.load_shards(fleet.resolve_targets([out_dir]))
    for r, recs in by_rank.items():
        cps = [x for x in recs if x.get("kind") == "critpath"]
        assert cps, f"rank {r}: no critpath records"
        for cp in cps:
            assert cp["wall_us"] > 0
            assert cp["segments"], cp
            assert cp["crit_stage"] in critpath.STAGES
    rows, budgets = fleet.critpath_rows(by_rank)
    assert rows
    for row in rows:
        assert 0.0 < row["crit_frac"] <= 1.0
        assert row["chain"]
    assert budgets
