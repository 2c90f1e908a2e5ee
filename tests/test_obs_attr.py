"""Step-time attribution (obs.trace_attr), the host timeline exporter
(obs.timeline), and their report-CLI surfaces.

The attribution parser is pinned against a COMMITTED chrome trace
(tests/fixtures/trace/cpu_smoke.trace.json.gz — a real jax.profiler
capture of a tiny program built to exercise every bucket; regeneration
script sits next to it), plus synthetic traces where the expected self
times are computable by hand. The timeline recorder round-trips through
its own schema validator — the same one ``report timeline`` runs.
"""

import gzip
import json
import os

import pytest

from gtopkssgd_tpu.obs import report as obs_report
from gtopkssgd_tpu.obs.timeline import (
    TimelineRecorder,
    timeline_from_records,
    validate_timeline,
)
from gtopkssgd_tpu.obs.trace_attr import (
    _interval_union,
    _intersection_us,
    attribute,
    classify_op,
    classify_span,
    find_trace_file,
    format_attr,
    host_span_means,
    overlap_fraction,
    self_durations_us,
)

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "trace", "cpu_smoke.trace.json.gz")


# ----------------------------------------------------------- classifiers

def test_classify_op_buckets():
    assert classify_op("sort.17") == "select"
    assert classify_op("Sort.2") == "select"
    assert classify_op("top-k.3") == "select"
    assert classify_op("all-reduce.1") == "comm"
    assert classify_op("all-gather-start") == "comm"
    assert classify_op("collective-permute.4") == "comm"
    assert classify_op("reduce-scatter.9") == "comm"
    assert classify_op("fusion.12") == "compute"
    assert classify_op("convolution.3") == "compute"
    assert classify_op("dot.1") == "compute"
    # reduce-window is pooling, NOT top-k — the documented near-miss
    assert classify_op("reduce-window.5") == "compute"
    # TPU fusion naming carries the root op
    assert classify_op("fusion.sort.2") == "select"
    assert classify_op("fusion.all-reduce.7") == "comm"


def test_classify_span_buckets():
    assert classify_span("bench/compress") == "select"
    assert classify_span("bench/compress_per_leaf") == "select"
    assert classify_span("bench/comm") == "comm"
    assert classify_span("train/step") == "compute"
    assert classify_span("bench/forward_backward") == "compute"
    # unmatched host phases stay OUT of the three-term split
    assert classify_span("io") is None
    assert classify_span("obs_read") is None


# ------------------------------------------------------------ self times

def _ev(name, ts, dur, pid=1, tid=1, **args):
    e = {"ph": "X", "name": name, "ts": ts, "dur": dur,
         "pid": pid, "tid": tid}
    if args:
        e["args"] = args
    return e


def test_self_durations_subtract_nested_children():
    # while [0,100) wraps two children; sibling [120,150) is flat
    events = [
        _ev("while.1", 0, 100),
        _ev("collective-permute.1", 10, 30),
        _ev("fusion.1", 50, 20),
        _ev("dot.1", 120, 30),
    ]
    selfs = self_durations_us(events)
    assert selfs == [50.0, 30.0, 20.0, 30.0]


def test_self_durations_deep_nesting_and_shared_start():
    # grandchild nests inside child; a same-start pair resolves longest
    # first (the (ts, -end) sort)
    events = [
        _ev("call.1", 0, 80),
        _ev("while.1", 0, 60),
        _ev("sort.1", 10, 20),
    ]
    selfs = self_durations_us(events)
    assert selfs == [20.0, 40.0, 20.0]


# ---------------------------------------------------- committed fixture

def test_fixture_attribution_roundtrip():
    rec = attribute(FIXTURE, mode="fixture")
    assert rec["mode"] == "fixture"
    assert rec["source"] == "ops"        # CPU trace: annotations are host-side
    assert rec["n_op_events"] > 0
    for t in ("compute", "select", "comm"):
        assert rec[f"t_{t}_us"] > 0, f"bucket {t} empty in fixture"
        assert 0 < rec[f"frac_{t}"] < 1
    total = sum(rec[f"t_{t}_us"] for t in ("compute", "select", "comm"))
    assert rec["t_total_us"] == pytest.approx(total, abs=0.5)
    fracs = sum(rec[f"frac_{t}"] for t in ("compute", "select", "comm"))
    assert fracs == pytest.approx(1.0, abs=1e-4)
    # the fixture's known op mix lands where the classifier says
    assert "sort" in rec["top_select_ops"]
    assert ("all-reduce" in rec["top_comm_ops"]
            or "collective-permute" in rec["top_comm_ops"])
    table = format_attr(rec)
    for line in ("T_compute", "T_select", "T_comm", "source=ops"):
        assert line in table


def test_fixture_carries_host_annotations():
    means = host_span_means(FIXTURE)
    assert any(n.startswith("train/step") for n in means)
    assert all(v >= 0 for v in means.values())


def test_find_trace_file_resolution(tmp_path):
    assert find_trace_file(FIXTURE) == FIXTURE       # file passthrough
    nested = tmp_path / "plugins" / "profile" / "run1"
    nested.mkdir(parents=True)
    target = nested / "host.trace.json.gz"
    with gzip.open(target, "wt") as fh:
        json.dump({"traceEvents": []}, fh)
    assert find_trace_file(str(tmp_path)) == str(target)
    with pytest.raises(FileNotFoundError):
        find_trace_file(str(tmp_path / "empty"))


# ------------------------------------------------- synthetic source choice

def _synthetic_trace(span_us, op_us):
    """Device pid 7 with an annotated lane and an op lane; host pid 0."""
    events = [
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "thread_name", "pid": 7, "tid": 1,
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "name": "thread_name", "pid": 7, "tid": 2,
         "args": {"name": "annotations"}},
    ]
    t = 0.0
    for name, us in op_us:
        events.append(_ev(name, t, us, pid=7, tid=1, hlo_op=name))
        t += us
    t = 0.0
    for name, us in span_us:
        events.append(_ev(name, t, us, pid=7, tid=2))
        t += us
    return {"traceEvents": events}


def test_attribute_prefers_annotated_device_spans():
    trace = _synthetic_trace(
        span_us=[("train/step", 60.0), ("train/step/compress", 30.0),
                 ("train/step/comm", 10.0)],
        op_us=[("fusion.1", 50.0), ("sort.1", 30.0), ("all-reduce.1", 20.0)])
    rec = attribute(trace)
    assert rec["source"] == "spans"
    assert rec["t_compute_us"] == pytest.approx(60.0)
    assert rec["t_select_us"] == pytest.approx(30.0)
    assert rec["t_comm_us"] == pytest.approx(10.0)


def test_attribute_falls_back_to_ops_on_thin_span_coverage():
    trace = _synthetic_trace(
        span_us=[("train/step", 5.0)],     # < half the op total
        op_us=[("fusion.1", 50.0), ("sort.1", 30.0), ("all-reduce.1", 20.0)])
    rec = attribute(trace)
    assert rec["source"] == "ops"
    assert rec["frac_select"] == pytest.approx(0.3)
    assert rec["frac_comm"] == pytest.approx(0.2)


def test_attribute_mixes_sources_per_class():
    # Only the comm scope propagated onto the device lanes: its span
    # (15µs) covers ≥ half of comm's op time (20µs), while compute and
    # select have no spans at all. The per-class choice keeps span truth
    # for comm and the op classifier for the rest — before PR 15 the
    # thin global coverage dragged ALL three onto ops.
    trace = _synthetic_trace(
        span_us=[("train/step/comm", 15.0)],
        op_us=[("fusion.1", 50.0), ("sort.1", 30.0), ("all-reduce.1", 20.0)])
    rec = attribute(trace)
    assert rec["source"] == "mixed"
    assert rec["source_comm"] == "spans"
    assert rec["source_compute"] == "ops"
    assert rec["source_select"] == "ops"
    assert rec["t_comm_us"] == pytest.approx(15.0)
    assert rec["t_compute_us"] == pytest.approx(50.0)
    assert rec["t_select_us"] == pytest.approx(30.0)
    # the report table prints the per-class pick, not just the label
    table = format_attr(rec)
    assert "source=mixed" in table
    assert "spans" in table and "ops" in table


def test_attribute_thin_span_class_falls_to_ops():
    # A comm span UNDER the coverage floor (5 < 0.5 * 20) must not win:
    # every class lands on ops and the label stays "ops", not "mixed".
    trace = _synthetic_trace(
        span_us=[("train/step/comm", 5.0)],
        op_us=[("fusion.1", 50.0), ("sort.1", 30.0), ("all-reduce.1", 20.0)])
    rec = attribute(trace)
    assert rec["source"] == "ops"
    assert rec["source_comm"] == "ops"
    assert rec["t_comm_us"] == pytest.approx(20.0)


# ---------------------------------------------------- overlap measurement

def test_interval_union_merges_and_drops_degenerate():
    assert _interval_union([]) == []
    assert _interval_union([(5.0, 5.0), (3.0, 1.0)]) == []   # degenerate
    assert _interval_union([(0.0, 2.0), (1.0, 3.0), (3.0, 4.0),
                            (10.0, 11.0)]) == [(0.0, 4.0), (10.0, 11.0)]


def test_intersection_of_disjoint_unions():
    a = [(0.0, 10.0), (20.0, 30.0)]
    b = [(5.0, 25.0), (29.0, 40.0)]
    # [5,10) + [20,25) + [29,30)
    assert _intersection_us(a, b) == pytest.approx(11.0)
    assert _intersection_us(a, []) == 0.0


def test_overlap_fraction_bounds():
    assert overlap_fraction([], [(0.0, 5.0)]) == 0.0           # no comm
    assert overlap_fraction([(0.0, 4.0)], []) == 0.0           # no other
    assert overlap_fraction([(0.0, 4.0)], [(0.0, 4.0)]) == 1.0  # hidden
    assert overlap_fraction([(0.0, 4.0)], [(2.0, 6.0)]) == 0.5


def _two_lane_op_trace(lane1, lane2):
    """Two executor op lanes (args.hlo_op marks op events) so comm on one
    lane can be wall-clock concurrent with compute on the other."""
    events = []
    for tid, ops in ((1, lane1), (2, lane2)):
        for name, ts, dur in ops:
            events.append(_ev(name, ts, dur, pid=3, tid=tid, hlo_op=name))
    return {"traceEvents": events}


def test_attribute_measures_cross_lane_comm_overlap():
    # comm [0,100) on lane 1, compute [50,150) on lane 2: half the comm
    # window is hidden under compute.
    trace = _two_lane_op_trace(
        [("all-reduce.1", 0.0, 100.0)],
        [("fusion.1", 50.0, 100.0)])
    rec = attribute(trace)
    assert rec["overlap_frac"] == pytest.approx(0.5)
    # a strictly serial schedule measures exactly zero
    serial = _two_lane_op_trace(
        [("all-reduce.1", 0.0, 100.0)],
        [("fusion.1", 100.0, 100.0)])
    assert attribute(serial)["overlap_frac"] == 0.0
    # format_attr surfaces the measurement
    assert "overlap_frac=0.5000" in format_attr(rec)


# ------------------------------------------------------ timeline recorder

def test_timeline_recorder_roundtrip(tmp_path):
    tl = TimelineRecorder(rank=0, label="test")
    import time
    t0 = time.perf_counter()
    tl.span_sink("train/io", t0, 0.002)
    tl.span_sink("train/dispatch", t0 + 0.002, 0.005)
    tl.instant("event:nan_loss", args={"rule": "nan_loss",
                                       "severity": "error", "step": 3})
    tl.counter("train", {"loss": 2.5, "throughput": 100.0})
    doc = tl.to_doc()
    assert validate_timeline(doc) == []
    names = [e["name"] for e in doc["traceEvents"]]
    assert "process_name" in names and "thread_name" in names
    assert "train/io" in names and "event:nan_loss" in names
    # write() appends timeline.json to a directory target
    path = tl.write(str(tmp_path))
    assert path == str(tmp_path / "timeline.json")
    with open(path) as fh:
        assert validate_timeline(json.load(fh)) == []


def test_timeline_counter_drops_nan_and_bools():
    tl = TimelineRecorder()
    tl.counter("train", {"loss": float("nan"), "flag": True})
    assert all(e.get("ph") != "C" for e in tl.to_doc()["traceEvents"])
    tl.counter("train", {"loss": 1.0, "bad": float("nan")})
    (c,) = [e for e in tl.to_doc()["traceEvents"] if e.get("ph") == "C"]
    assert c["args"] == {"loss": 1.0}


def test_timeline_from_records_markers_and_counters():
    records = [
        {"kind": "manifest", "time": 0.5, "compression": "gtopk"},
        {"kind": "train", "time": 1.0, "step": 10, "loss": 2.5,
         "throughput": 50.0},
        {"kind": "obs", "time": 1.5, "step": 10, "achieved_density": 0.01,
         "tau": 0.5},
        {"kind": "event", "time": 2.0, "rule": "nan_loss",
         "severity": "error", "step": 11, "message": "boom"},
        {"kind": "stall", "time": 3.0, "label": "train"},
        {"kind": "train", "step": 12, "loss": 2.0},   # no time -> skipped
    ]
    doc = timeline_from_records(records, label="runX")
    assert validate_timeline(doc) == []
    body = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
    assert [e["ph"] for e in body] == ["C", "C", "i", "i"]
    marker = body[2]
    assert marker["name"] == "event:nan_loss"
    assert marker["args"]["severity"] == "error"
    assert body[3]["name"] == "stall"


def test_validate_timeline_rejects_bad_docs():
    assert validate_timeline({}) == ["traceEvents is not a list"]
    bad_x = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 0, "ts": 1.0}]}        # no dur
    assert any("without dur" in p for p in validate_timeline(bad_x))
    non_mono = {"traceEvents": [
        {"ph": "i", "name": "a", "pid": 0, "ts": 5.0},
        {"ph": "i", "name": "b", "pid": 0, "ts": 1.0}]}
    assert any("not monotonic" in p for p in validate_timeline(non_mono))


# ------------------------------------------------------ report CLI smokes

def _write_run(path, rows):
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "metrics.jsonl"), "w") as fh:
        for r in rows:
            fh.write(json.dumps(r) + "\n")


def test_report_attr_from_trace_and_run(tmp_path, capsys):
    # straight from the committed fixture trace
    assert obs_report.main(["attr", FIXTURE, "--mode", "fixture"]) == 0
    out = capsys.readouterr().out
    assert "T_compute" in out and "T_select" in out and "T_comm" in out
    # from a run's logged attr record (what the gate smoke writes)
    run = str(tmp_path / "run")
    _write_run(run, [
        {"kind": "attr", "time": 1.0, "rank": 0, "source": "ops",
         "t_compute_us": 900.0, "t_select_us": 80.0, "t_comm_us": 20.0,
         "t_total_us": 1000.0, "frac_compute": 0.9, "frac_select": 0.08,
         "frac_comm": 0.02, "n_op_events": 10},
    ])
    json_out = str(tmp_path / "attr.json")
    assert obs_report.main(["attr", run, "--json", json_out]) == 0
    assert "0.9000" in capsys.readouterr().out
    assert json.load(open(json_out))["frac_compute"] == 0.9
    # a run without attr records is a soft failure, not a crash
    empty = str(tmp_path / "empty")
    _write_run(empty, [{"kind": "train", "time": 1.0, "loss": 2.0}])
    assert obs_report.main(["attr", empty]) == 1
    capsys.readouterr()
    assert obs_report.main(["attr", str(tmp_path / "missing_dir")]) == 2
    capsys.readouterr()


def test_report_events_summarizes_per_rule(tmp_path, capsys):
    run = str(tmp_path / "run")
    _write_run(run, [
        {"kind": "train", "time": 1.0, "step": 1, "loss": 2.0},
        {"kind": "event", "time": 1.1, "rule": "density_collapse",
         "severity": "warn", "step": 2, "value": 0.0001,
         "threshold": 0.001, "message": "collapsed"},
        {"kind": "event", "time": 1.2, "rule": "density_collapse",
         "severity": "warn", "step": 5, "value": 0.0002,
         "threshold": 0.001, "message": "still collapsed"},
        {"kind": "event", "time": 1.3, "rule": "nan_loss",
         "severity": "error", "step": 7, "message": "boom"},
    ])
    json_out = str(tmp_path / "events.json")
    assert obs_report.main(["events", run, "--json", json_out]) == 0
    out = capsys.readouterr().out
    assert "density_collapse" in out and "nan_loss" in out
    summary = json.load(open(json_out))
    dc = summary["density_collapse"]
    assert dc["count"] == 2
    assert dc["first_step"] == 2 and dc["last_step"] == 5
    assert dc["last_value"] == 0.0002
    # an event-free run reads as a clean bill, exit 0
    clean = str(tmp_path / "clean")
    _write_run(clean, [{"kind": "train", "time": 1.0, "loss": 2.0}])
    assert obs_report.main(["events", clean]) == 0
    assert "none recorded" in capsys.readouterr().out


def test_report_timeline_writes_and_validates(tmp_path, capsys):
    run = str(tmp_path / "run")
    _write_run(run, [
        {"kind": "train", "time": 1.0, "step": 2, "loss": 2.5,
         "throughput": 10.0},
        {"kind": "event", "time": 1.5, "rule": "loss_spike",
         "severity": "warn", "step": 3, "value": 7.0, "threshold": 6.0,
         "message": "spiked"},
    ])
    assert obs_report.main(["timeline", run]) == 0
    out = capsys.readouterr().out
    assert "timeline" in out
    path = os.path.join(run, "timeline.json")
    assert os.path.exists(path)
    doc = json.load(open(path))
    assert validate_timeline(doc) == []
    assert any(e.get("name") == "event:loss_spike"
               for e in doc["traceEvents"])
