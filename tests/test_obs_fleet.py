"""Fleet observability: shard naming, cross-rank merge, straggler
analytics, the comm-model ledger, live export, and the registered-kind
contract.

The committed fixture (tests/fixtures/fleet/, regenerated by
make_fleet_fixture.py in the same dir) is a synthetic 3-rank run with the
two defects the merger must handle baked in deterministically: rank 2
arrives 2.5 s late at every step (persistent straggler — above the auto
threshold of 2x the 1.0 s cadence) and rank 1 is missing step 3 (ragged
shard). Values are hand-chosen, so assertions here are exact.
"""

import io
import json
import os
import re
import shutil
import sys
import urllib.request

import pytest

from tests.conftest import run_two_process

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "fleet")


# --------------------------------------------------------------- shards
def test_shard_name_round_trip():
    from gtopkssgd_tpu.utils.metrics import shard_filename, shard_rank

    assert shard_filename(0) == "metrics.rank0.jsonl"
    assert shard_filename(17) == "metrics.rank17.jsonl"
    assert shard_rank("metrics.rank3.jsonl") == 3
    assert shard_rank("/a/b/metrics.rank12.jsonl") == 12
    assert shard_rank("metrics.jsonl") is None
    assert shard_rank("metrics.rankX.jsonl") is None


def test_sharded_logger_writes_every_rank(tmp_path):
    from gtopkssgd_tpu.utils.metrics import MetricsLogger

    for rank in (0, 1):
        with MetricsLogger(str(tmp_path), rank=rank, shard=True) as m:
            m.log("train", step=1, loss=0.5)
    assert (tmp_path / "metrics.rank0.jsonl").exists()
    assert (tmp_path / "metrics.rank1.jsonl").exists()
    rec = json.loads((tmp_path / "metrics.rank1.jsonl").read_text())
    assert rec["rank"] == 1 and rec["loss"] == 0.5


def test_unsharded_logger_keeps_classic_behavior(tmp_path):
    from gtopkssgd_tpu.utils.metrics import MetricsLogger

    with MetricsLogger(str(tmp_path), rank=0) as m:
        m.log("train", step=1)
    with MetricsLogger(str(tmp_path / "other"), rank=1) as m:
        m.log("train", step=1)
    assert (tmp_path / "metrics.jsonl").exists()
    assert not (tmp_path / "other").exists()  # rank!=0 writes nothing


def test_unregistered_kind_raises(tmp_path):
    from gtopkssgd_tpu.utils.metrics import MetricsLogger

    # Kind assembled at runtime so static analysis (graftlint's
    # metric-kind rule) doesn't flag this deliberate negative fixture.
    typo = "tpyo" + "_kind"
    with MetricsLogger(str(tmp_path)) as m:
        with pytest.raises(ValueError, match="unregistered"):
            m.log(typo, step=1)


def test_logger_sink_sees_records_even_without_file():
    from gtopkssgd_tpu.utils.metrics import MetricsLogger

    seen = []
    with MetricsLogger(None, rank=3, sink=seen.append) as m:
        m.log("train", step=2, loss=1.0)
    assert seen and seen[0]["rank"] == 3 and seen[0]["loss"] == 1.0


# The former grep-based sweep for unregistered literal kinds
# (test_every_logged_kind_literal_is_registered) is superseded by
# graftlint's metric-kind rule, which resolves kinds through the AST
# (constants, name bindings, f-strings) instead of a regex — see
# tests/test_analysis.py for its regression coverage and
# python -m gtopkssgd_tpu.analysis for the enforcement entry point.


# ---------------------------------------------------------------- merge
def test_fixture_merge_stats_and_skew():
    from gtopkssgd_tpu.obs import fleet

    merged = fleet.merge([FIXTURE])
    assert merged["ranks"] == [0, 1, 2]
    assert merged["manifest"]["config_hash"] == "fleetfix0001beef"

    rows = {(r["src"], r["step"], r["field"]): r for r in merged["rows"]}
    loss1 = rows[("obs", 1.0, "loss")]
    # loss at step 1: r0=1.90, r1=1.91, r2=1.92 (hand-chosen).
    assert loss1["n_ranks"] == 3
    assert loss1["min"] == pytest.approx(1.90)
    assert loss1["median"] == pytest.approx(1.91)
    assert loss1["max"] == pytest.approx(1.92)
    assert loss1["skew"]["r0"] == pytest.approx(-0.01)
    assert loss1["skew"]["r1"] == pytest.approx(0.0)
    assert loss1["skew"]["r2"] == pytest.approx(0.01)
    assert loss1["skew_max"] == pytest.approx(0.01)
    assert loss1["std"] > 0

    # wire_bytes identical across ranks -> zero skew everywhere.
    wb = rows[("obs", 2.0, "wire_bytes")]
    assert wb["skew_max"] == 0.0 and wb["std"] == 0.0

    # lag rows: rank 2 is 2.5 s behind the first-arriving rank.
    lag = rows[("obs", 1.0, "lag_s")]
    assert lag["max"] == pytest.approx(2.5)
    assert lag["skew"]["r2"] == pytest.approx(2.5)
    assert lag["skew"]["r0"] == pytest.approx(0.0)


def test_fixture_merge_handles_ragged_shard():
    from gtopkssgd_tpu.obs import fleet

    merged = fleet.merge([FIXTURE])
    rows = {(r["step"], r["field"]): r for r in merged["rows"]}
    # rank 1 is missing step 3: stats keep going with 2 contributors.
    assert rows[(3.0, "loss")]["n_ranks"] == 2
    assert "r1" not in rows[(3.0, "loss")]["skew"]
    assert rows[(2.0, "loss")]["n_ranks"] == 3
    # and the straggler pass still attributes every step.
    assert [s["step"] for s in merged["stragglers"]] == [1.0, 2.0, 3.0,
                                                         4.0]


def test_fixture_straggler_attribution_and_persistence():
    from gtopkssgd_tpu.obs import fleet

    merged = fleet.merge([FIXTURE])
    stragglers = merged["stragglers"]
    assert all(s["slowest_rank"] == 2 for s in stragglers)
    assert stragglers[0]["lag_s"] == pytest.approx(2.5)
    # Constant lag: transient during EWMA warmup, persistent after.
    assert [s["persistent"] for s in stragglers] == [False, False, True,
                                                     True]
    fired = [e for e in merged["events"]
             if e["rule"] == "straggler_persistent"]
    assert fired and all(e["rank_behind"] == 2 for e in fired)
    assert fired[0]["severity"] == "warn"


def test_straggler_respects_halt_on():
    from gtopkssgd_tpu.obs import fleet
    from gtopkssgd_tpu.obs.events import AnomalyHalt, AnomalyMonitor

    with pytest.raises(AnomalyHalt) as exc:
        fleet.merge([FIXTURE], monitor=AnomalyMonitor(halt_on="warn"))
    assert exc.value.event["rule"] == "straggler_persistent"
    assert exc.value.event["rank_behind"] == 2
    # halt_on="error" must NOT halt on a warn-severity straggler.
    merged = fleet.merge([FIXTURE],
                         monitor=AnomalyMonitor(halt_on="error"))
    assert any(e["rule"] == "straggler_persistent"
               for e in merged["events"])


def test_steady_fleet_fires_no_straggler(tmp_path):
    from gtopkssgd_tpu.obs import fleet

    for rank in range(2):
        with open(tmp_path / f"metrics.rank{rank}.jsonl", "w") as fh:
            for step in range(1, 6):
                fh.write(json.dumps({
                    "kind": "obs", "time": 100.0 + step + 0.01 * rank,
                    "rank": rank, "step": step, "loss": 1.0}) + "\n")
    merged = fleet.merge([str(tmp_path)])
    assert merged["events"] == []
    assert all(not s["persistent"] for s in merged["stragglers"])


def test_merge_rejects_config_hash_mismatch(tmp_path):
    from gtopkssgd_tpu.obs import fleet

    for name in os.listdir(FIXTURE):
        if name.endswith(".jsonl"):
            shutil.copy(os.path.join(FIXTURE, name), tmp_path / name)
    # Rewrite rank 1's manifest with a different hash: now provably a
    # different run.
    path = tmp_path / "metrics.rank1.jsonl"
    lines = path.read_text().splitlines()
    man = json.loads(lines[0])
    man["config_hash"] = "deadbeefdeadbeef"
    path.write_text("\n".join([json.dumps(man)] + lines[1:]) + "\n")

    with pytest.raises(ValueError, match="config_hash mismatch"):
        fleet.merge([str(tmp_path)])
    merged = fleet.merge([str(tmp_path)], allow_mismatch=True)
    assert merged["ranks"] == [0, 1, 2]


def test_merge_single_metrics_jsonl_is_one_rank_fleet(tmp_path):
    from gtopkssgd_tpu.obs import fleet

    with open(tmp_path / "metrics.jsonl", "w") as fh:
        for step in (1, 2):
            fh.write(json.dumps({"kind": "obs", "time": float(step),
                                 "rank": 0, "step": step,
                                 "loss": 1.0}) + "\n")
    merged = fleet.merge([str(tmp_path)])
    assert merged["ranks"] == [0]
    assert all(r["n_ranks"] == 1 and r["skew_max"] == 0.0
               for r in merged["rows"])
    assert merged["stragglers"] == []


# ----------------------------------------------------------- report CLI
def test_report_fleet_cli_names_lagging_rank(capsys):
    from gtopkssgd_tpu.obs import report

    assert report.main(["fleet", FIXTURE]) == 0
    out = capsys.readouterr().out
    assert "min" in out and "median" in out and "max" in out
    assert "persistent straggler: rank 2" in out
    assert "straggler_persistent" in out


def test_report_fleet_cli_json(tmp_path, capsys):
    from gtopkssgd_tpu.obs import report

    out_json = tmp_path / "fleet.json"
    assert report.main(["fleet", FIXTURE, "--json", str(out_json)]) == 0
    capsys.readouterr()
    doc = json.loads(out_json.read_text())
    assert doc["ranks"] == [0, 1, 2]
    assert any(r["field"] == "lag_s" for r in doc["rows"])


def test_report_loads_shard_dir_as_concatenation():
    from gtopkssgd_tpu.obs import report

    records, bad = report.load_records(FIXTURE)
    assert bad == 0
    assert {r["rank"] for r in records} == {0, 1, 2}
    # 3 manifests + (4 + 3 + 4) obs records
    assert len(records) == 14


def test_report_compare_accepts_shard_dirs(tmp_path, capsys):
    from gtopkssgd_tpu.obs import report

    other = tmp_path / "b"
    other.mkdir()
    for name in os.listdir(FIXTURE):
        if not name.endswith(".jsonl"):
            continue
        recs = []
        with open(os.path.join(FIXTURE, name)) as fh:
            for line in fh:
                rec = json.loads(line)
                if rec.get("kind") == "obs":
                    rec["loss"] = rec["loss"] * 2
                recs.append(rec)
        with open(other / name, "w") as fh:
            for rec in recs:
                fh.write(json.dumps(rec) + "\n")
    assert report.main([FIXTURE, str(other)]) == 0
    out = capsys.readouterr().out
    assert "compare:" in out
    # fleet-merged mean doubled -> +100% on loss; wire_bytes unchanged.
    assert "+100.0%" in out
    assert "+0.0%" in out


def test_report_summarize_notes_unregistered_kind(tmp_path, capsys):
    from gtopkssgd_tpu.obs import report

    path = tmp_path / "metrics.jsonl"
    path.write_text(json.dumps(
        {"kind": "not_a_kind", "time": 1.0, "rank": 0, "x": 1}) + "\n")
    assert report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "unregistered kind" in out and "not_a_kind" in out


def test_report_watch_tails_live_shards(tmp_path):
    from gtopkssgd_tpu.obs import report

    with open(tmp_path / "metrics.rank0.jsonl", "w") as fh:
        fh.write(json.dumps({"kind": "train", "time": 1.0, "rank": 0,
                             "step": 7, "loss": 0.75}) + "\n")
    with open(tmp_path / "metrics.rank1.jsonl", "w") as fh:
        fh.write(json.dumps({"kind": "event", "time": 1.0, "rank": 1,
                             "rule": "nan_loss"}) + "\n")
    buf = io.StringIO()
    assert report.run_watch([str(tmp_path)], interval=0.01,
                            iterations=2, out=buf) == 0
    out = buf.getvalue()
    assert out.count("watch @") == 2
    assert "rank 0" in out and "step=7" in out and "loss=0.7500" in out
    assert "last_event=nan_loss" in out


def test_report_watch_cli(capsys):
    from gtopkssgd_tpu.obs import report

    rc = report.main(["watch", FIXTURE, "--interval", "0.01",
                      "--iterations", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "watch @" in out and "rank 2" in out


# -------------------------------------------------------------- exporter
def test_exporter_serves_valid_openmetrics_over_http():
    from gtopkssgd_tpu.obs.exporter import CONTENT_TYPE, MetricsExporter

    with MetricsExporter(port=0) as ex:
        ex.observe({"kind": "train", "time": 1.0, "rank": 0, "step": 3,
                    "loss": 1.25})
        ex.observe({"kind": "train", "time": 2.0, "rank": 0, "step": 4,
                    "loss": 1.20})  # latest wins
        ex.observe({"kind": "fleet", "time": 2.0, "rank": 0,
                    "src": "obs", "field": "loss", "step": 4,
                    "skew_max": 0.01})
        url = f"http://127.0.0.1:{ex.port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"] == CONTENT_TYPE
            body = resp.read().decode("utf-8")

    # Parses as OpenMetrics text: TYPE lines, sample lines, EOF last.
    lines = body.strip().splitlines()
    assert lines[-1] == "# EOF"
    sample = re.compile(
        r'^[a-zA-Z_][a-zA-Z0-9_]*(\{[a-zA-Z0-9_]+="[^"]*"'
        r'(,[a-zA-Z0-9_]+="[^"]*")*\})? -?[0-9.+eEinfNa]+$')
    families = set()
    for line in lines[:-1]:
        if line.startswith("# TYPE "):
            name, mtype = line[len("# TYPE "):].rsplit(" ", 1)
            assert mtype == "gauge"
            families.add(name)
        else:
            assert sample.match(line), line
            assert line.split("{")[0].split(" ")[0] in families
    assert 'gtopk_train_loss{rank="0"} 1.2' in body  # latest value
    assert "1.25" not in body
    assert 'gtopk_fleet_skew_max{field="loss",rank="0",src="obs"}' in body


def test_exporter_404_off_path():
    from gtopkssgd_tpu.obs.exporter import MetricsExporter

    with MetricsExporter(port=0) as ex:
        url = f"http://127.0.0.1:{ex.port}/nope"
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(url, timeout=10)
        assert exc.value.code == 404


# ---------------------------------------------------------------- ledger
def test_ledger_ratio_is_one_on_model_generated_records():
    import math

    from gtopkssgd_tpu.obs import ledger

    n, rho, p = 10000, 0.01, 8
    k = max(1, math.ceil(rho * n))
    alpha, beta = 0.5, 10.0
    pred = ledger.predict_comm_ms("gtopk", p, n=n, k=k, alpha_ms=alpha,
                                  beta_gbps=beta, ici_size=1)
    assert pred > 0
    recs = [
        {"kind": "manifest", "compression": "gtopk", "nworkers": p,
         "density": rho, "num_params": n, "process_count": p},
        {"kind": "attr", "rank": 0, "t_comm_us": pred * 1e3},
        {"kind": "attr", "rank": 3, "t_comm_us": pred * 1e3},
    ]
    rows = ledger.ledger_rows(recs, alpha_ms=alpha, beta_gbps=beta)
    attr_rows = [r for r in rows if r["source"] == "attr"]
    assert len(attr_rows) == 2
    for row in attr_rows:
        assert row["ratio"] == pytest.approx(1.0)
    summary = ledger.summarize_ledger(rows)
    assert summary["attr"]["mean_ratio"] == pytest.approx(1.0)


def test_ledger_flags_slow_ranks():
    import math

    from gtopkssgd_tpu.obs import ledger

    n, rho, p = 10000, 0.01, 4
    k = max(1, math.ceil(rho * n))
    pred = ledger.predict_comm_ms("gtopk", p, n=n, k=k, alpha_ms=0.2,
                                  beta_gbps=10.0, ici_size=1)
    recs = [{"kind": "manifest", "compression": "gtopk", "nworkers": p,
             "density": rho, "num_params": n, "process_count": p}]
    for rank in range(p):
        factor = 1.8 if rank == 3 else 1.0
        recs.append({"kind": "attr", "rank": rank,
                     "t_comm_us": pred * 1e3 * factor})
    rows = ledger.ledger_rows(recs, alpha_ms=0.2, beta_gbps=10.0)
    summary = ledger.summarize_ledger(rows)
    assert summary["attr"]["max_ratio"] == pytest.approx(1.8)
    assert "3" in summary["attr"]["worst_ranks"]
    assert summary["attr"]["worst_ranks"]["3"] == pytest.approx(1.8)


def test_ledger_wire_bytes_rows():
    from gtopkssgd_tpu.obs import ledger

    # gtopk p=4 (pow2): tree_rounds = 2, predicted bytes = 2 * 8k.
    n, rho, p = 10000, 0.01, 4
    k = 100
    recs = [
        {"kind": "manifest", "compression": "gtopk", "nworkers": p,
         "density": rho, "num_params": n},
        {"kind": "obs", "rank": 1, "step": 5, "wire_bytes": 2 * 8 * k},
    ]
    rows = ledger.ledger_rows(recs, alpha_ms=0.0, beta_gbps=10.0)
    wire = [r for r in rows if r["source"] == "wire_bytes"]
    assert len(wire) == 1
    assert wire[0]["ratio"] == pytest.approx(1.0)


def test_ledger_without_manifest_returns_empty():
    from gtopkssgd_tpu.obs import ledger

    assert ledger.ledger_rows([{"kind": "attr", "t_comm_us": 10.0}]) == []


# Hand-counted comm model at n=10000, k=100 (fp32: one k-set is 800 B,
# the dense buffer 40000 B), 2-chip slices, alpha 0.3 ms a DCN message.
# DCN at 12 Gbit/s moves a byte in 1/1.5e6 ms, ICI at 800 in 1e-8 ms.
_DCN, _ICI, _A = 1 / 1.5e6, 1e-8, 0.3
_PREDICT_BY_HAND = {
    # p=1: no wire but the modes that hand their own set around on ICI.
    # p=4 is 2 slices, p=6 is 3 (ragged: fold + unfold ride DCN).
    "dense": {1: 0.0,
              # ring in the slice, ring over 2 slices, 2 DCN messages
              4: 40000 * _ICI + 40000 * _DCN + 2 * _A,
              # ring over 3 slices moves 2*(2/3) of the buffer, 4 messages
              6: 40000 * _ICI + (4 / 3) * 40000 * _DCN + 4 * _A},
    "gtopk": {1: 0.0,
              # 2 rounds: 1 inside the slice, 1 across
              4: 800 * _ICI + (800 * _DCN + _A),
              # 4 rounds (2 + fold + unfold): 1 inside, 3 across
              6: 800 * _ICI + 3 * (800 * _DCN + _A)},
    "gtopk_balanced": {
        1: 800 * _ICI,                       # cap = k, its own share
        # cap = ceil(1.5 * 100 / 4) = 38 -> 304 B; 3 ICI hops, 4 DCN
        4: 3 * 304 * _ICI + 4 * (304 * _DCN + _A),
        # cap = ceil(1.5 * 100 / 6) = 25 -> 200 B; 3 ICI hops, 8 DCN
        6: 3 * 200 * _ICI + 8 * (200 * _DCN + _A)},
    "allgather": {1: 800 * _ICI,
                  4: 2 * 800 * _ICI + 2 * 800 * _DCN + _A,
                  6: 2 * 800 * _ICI + 4 * 800 * _DCN + 2 * _A},
    "gtopk_hier": {1: 0.0,
                   # dense ring in the slice, then the tree over slices:
                   # 1 round over 2 slices, 3 over 3 (1 + fold + unfold)
                   4: 40000 * _ICI + (800 * _DCN + _A),
                   6: 40000 * _ICI + 3 * (800 * _DCN + _A)},
}


@pytest.mark.parametrize("wire_mode", sorted(_PREDICT_BY_HAND))
def test_comm_model_predict_matches_hand_count(wire_mode):
    """The one comm model (parallel/comm_model.predict) against values
    counted by hand, and its two other names against it: the ledger's
    predict_comm_ms (alpha_ms / beta_gbps) and, for the tree, the
    scaling script's project() column."""
    from gtopkssgd_tpu.obs import ledger
    from gtopkssgd_tpu.parallel.comm_model import predict
    from tests.conftest import load_benchmark_module

    for p, want in _PREDICT_BY_HAND[wire_mode].items():
        got = predict(wire_mode, p, n=10000, k=100, dcn_alpha_ms=0.3,
                      dcn_gbps=12.0, ici_gbps=800.0, ici_size=2)
        assert got == pytest.approx(want, rel=1e-9, abs=1e-15), p
        assert ledger.predict_comm_ms(
            wire_mode, p, n=10000, k=100, alpha_ms=0.3, beta_gbps=12.0,
            ici_gbps=800.0, ici_size=2) == got
    if wire_mode == "gtopk":
        # the layerwise mode ships the flat tree's wire ...
        assert predict("gtopk_layerwise", 6, n=10000, k=100,
                       dcn_alpha_ms=0.3, dcn_gbps=12.0, ici_gbps=800.0,
                       ici_size=2) == got
        # ... B buckets are B merges, summed ...
        assert ledger.predict_comm_ms(
            "gtopk", 6, n=20000, k=200, alpha_ms=0.3, beta_gbps=12.0,
            ici_gbps=800.0, ici_size=2,
            buckets=((10000, 100), (10000, 100))
        ) == pytest.approx(2 * got)
        # ... and project()'s comm_ms column is predict() rounded.
        scaling_model = load_benchmark_module("scaling_model")
        assert scaling_model.predict is predict
        proj = scaling_model.project(
            "gtopk", 6, n=10000, k=100, compute_ms=5.0, overhead_ms=1.0,
            ici_gbps=800.0, dcn_gbps=12.0, ici_size=2, batch=4,
            dcn_alpha_ms=0.3)
        assert proj["comm_ms"] == round(got, 3)


def test_report_ledger_cli(tmp_path, capsys):
    import math

    from gtopkssgd_tpu.obs import ledger, report

    n, rho, p = 10000, 0.01, 8
    k = max(1, math.ceil(rho * n))
    pred = ledger.predict_comm_ms("gtopk", p, n=n, k=k, alpha_ms=0.5,
                                  beta_gbps=10.0, ici_size=1)
    with open(tmp_path / "metrics.jsonl", "w") as fh:
        fh.write(json.dumps({
            "kind": "manifest", "time": 1.0, "rank": 0,
            "compression": "gtopk", "nworkers": p, "density": rho,
            "num_params": n}) + "\n")
        fh.write(json.dumps({
            "kind": "attr", "time": 2.0, "rank": 0,
            "t_comm_us": pred * 1e3}) + "\n")
    rc = report.main(["ledger", str(tmp_path),
                      "--alpha-ms", "0.5", "--beta-gbps", "10.0"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "mean_ratio" in out
    assert "r0=1.0" in out  # the worst-rank column carries the ratio


# ------------------------------------------------------------- manifest
def test_manifest_carries_fleet_identity():
    from gtopkssgd_tpu.obs.manifest import run_manifest

    man = run_manifest()
    assert man.get("process_index") == 0
    assert man.get("process_count") == 1
    assert "coordinator_address" in man  # None in single-process runs


# ------------------------------------------------- 2-process end-to-end
FLEET_WORKER = r"""
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

# Same config as test_multihost's first leg, so the persistent
# compilation cache is shared between the two e2e tests.
cfg = TrainConfig(dnn="resnet20", batch_size=4, nworkers=2,
                  compression="gtopk", density=0.01, max_epochs=1,
                  log_interval=1, eval_batches=1, out_dir=sys.argv[4])
with Trainer(cfg) as t:
    t.train(2)
import os
assert os.path.exists(
    os.path.join(sys.argv[4], f"metrics.rank{pid}.jsonl"))
print(f"FLEET-OK pid={pid}")
"""


def test_two_process_run_shards_and_merges(tmp_path):
    """End-to-end: two real processes (jax.distributed over CPU), each
    writing its own metrics.rank{r}.jsonl; the fleet merger aligns them
    and attributes per-step lag. Skips on jax builds without CPU
    cross-process collectives (same rc-99 contract as test_multihost)."""
    out_dir = run_two_process(FLEET_WORKER, tmp_path, "FLEET-OK")

    from gtopkssgd_tpu.obs import fleet

    merged = fleet.merge([out_dir])
    assert merged["ranks"] == [0, 1]
    assert merged["manifest"] is not None
    assert merged["manifest"]["process_count"] == 2
    assert merged["manifest"]["coordinator_address"] is not None
    rows = [r for r in merged["rows"] if r["n_ranks"] == 2]
    assert rows, "no 2-rank merged rows — shards misaligned"
    # Both processes ran the same wall-clock window: lag is bounded by
    # the run itself (sanity, not a straggler assertion).
    assert merged["events"] == []
