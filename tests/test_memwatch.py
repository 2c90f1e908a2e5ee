"""Compile- and memory-plane observability (obs/memwatch.py) and its
wiring: AOT compile accounting on real jitted CPU executables, the
executable-cache recompile watch, the three mem-plane anomaly rules
(recompile_storm / device_mem_leak / hbm_headroom), the reshape fault
that manufactures a deterministic retrace, and the report/registry
round-trips of the new fields.

Extraction is pinned against a real ``lower().compile()`` so the keys
track jax's actual API shapes (cost_analysis returns a LIST of dicts on
CPU; memory_analysis a CompiledMemoryStats); the rules are pinned with
synthetic streams so their streak/latch semantics are checked against
known inputs, never against themselves. CPU has no memory_stats, which
doubles as the degraded-backend case the watch must survive.
"""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gtopkssgd_tpu.obs import HALT_EXIT_CODE
from gtopkssgd_tpu.obs import registry as obs_registry
from gtopkssgd_tpu.obs import report as obs_report
from gtopkssgd_tpu.obs.events import AnomalyHalt, AnomalyMonitor, Thresholds
from gtopkssgd_tpu.obs.memwatch import (
    CompileWatch,
    MemWatch,
    batch_shape_key,
    compile_record,
    compiled_flops,
    cost_summary,
    device_memory_summary,
    live_array_summary,
    memory_summary,
)
from gtopkssgd_tpu.resilience import FaultInjector
from gtopkssgd_tpu.utils.metrics import MetricsLogger


def _records(out_dir):
    path = os.path.join(out_dir, "metrics.jsonl")
    return [json.loads(line) for line in open(path)]


# -------------------------------------------------------------- extraction

def test_extraction_roundtrip_on_jitted_step():
    """cost/memory summaries off a real compiled executable: identifier-
    safe keys, the peak-HBM decomposition identity, and compiled_flops
    as the one flop path."""
    x = jnp.arange(16, dtype=jnp.float32)
    compiled = jax.jit(lambda v: (v * 2.0 + 1.0).sum()).lower(x).compile()
    cost = cost_summary(compiled)
    assert set(cost) <= {"flops", "bytes_accessed"}
    assert compiled_flops(compiled) == cost.get("flops")
    mem = memory_summary(compiled)
    assert mem, "CPU memory_analysis produced nothing"
    assert mem["argument_bytes"] >= x.nbytes
    assert mem["output_bytes"] >= 4
    expect = (mem.get("argument_bytes", 0) + mem.get("output_bytes", 0)
              + mem.get("temp_bytes", 0)
              + mem.get("generated_code_bytes", 0)
              - mem.get("alias_bytes", 0))
    assert mem["peak_hbm_bytes"] == max(expect, 0)
    rec = compile_record(compiled, shape_key="k", lower_s=0.5, compile_s=2)
    assert rec["shape_key"] == "k"
    assert rec["lower_s"] == 0.5 and rec["compile_s"] == 2.0
    assert rec["peak_hbm_bytes"] == mem["peak_hbm_bytes"]


def test_batch_shape_key_identity_and_digest():
    a = {"x": np.zeros((4, 3), np.float32)}
    assert batch_shape_key(a) == "4x3:float32"
    assert batch_shape_key(a) == batch_shape_key(
        {"x": jax.ShapeDtypeStruct((4, 3), jnp.float32)}), \
        "abstract and concrete leaves must hit the same memo entry"
    assert batch_shape_key({"x": np.zeros((2, 3), np.float32)}) \
        != batch_shape_key(a)
    # a train-state-sized tree collapses to a digest, not a page
    big = [np.zeros((i + 1,), np.float32) for i in range(64)]
    key = batch_shape_key(big)
    assert key.startswith("sha1:") and key.endswith(":64leaves")
    assert len(key) <= 160


# ---------------------------------------------------------- recompile watch

def test_compile_watch_adopts_baseline_then_detects_growth():
    fn = jax.jit(lambda v: v + 1.0)
    fn(jnp.zeros((4,), jnp.float32))
    watch = CompileWatch(fn, use_monitoring=False)
    assert watch.poll() is None      # first poll adopts, never fires
    assert watch.poll() is None      # stable cache
    fn(jnp.zeros((8,), jnp.float32))  # new shape -> retrace
    grown, size = watch.poll()
    assert grown == 1 and size == watch.last
    assert watch.poll() is None      # growth reported exactly once
    watch.close()


def test_recompile_warmup_arms_before_firing():
    """Arm-before-update: growth during the first recompile_warmup polls
    is warm-up compilation, not a storm."""
    mon = AnomalyMonitor(thresholds=Thresholds(recompile_warmup=2))
    assert mon.observe_compile(1, cache_size=1, grew=False) == []
    assert mon.observe_compile(2, cache_size=2, grew=True) == []
    fired = mon.observe_compile(3, cache_size=3, grew=True)
    assert [e["rule"] for e in fired] == ["recompile_storm"]


def test_recompile_storm_record_before_halt(tmp_path):
    """The full chain on a real jitted fn: cache growth -> fsync'd
    recompile record -> recompile_storm -> AnomalyHalt under
    halt_on=warn, with the record durably written BEFORE the halt."""
    out = str(tmp_path)
    metrics = MetricsLogger(out)
    mon = AnomalyMonitor(metrics=metrics, halt_on="warn",
                         thresholds=Thresholds(recompile_warmup=0))
    mw = MemWatch(metrics=metrics, monitor=mon, mem_interval=10_000)
    fn = jax.jit(lambda v: v * 2.0)
    fn(jnp.zeros((4,), jnp.float32))
    mw.attach(fn)
    mw.poll(1)                        # adopts the baseline — no fire
    fn(jnp.zeros((8,), jnp.float32))  # drifting dispatch shape
    with pytest.raises(AnomalyHalt):
        mw.poll(2)
    assert mw.recompile_count == 1
    mw.close()
    metrics.close()
    recs = _records(out)
    recompiles = [r for r in recs if r["kind"] == "compile"
                  and r.get("event") == "recompile"]
    assert len(recompiles) == 1
    assert recompiles[0]["recompile_count"] == 1
    assert recompiles[0]["step"] == 2
    storms = [r for r in recs if r["kind"] == "event"
              and r["rule"] == "recompile_storm"]
    assert len(storms) == 1
    assert recs.index(recompiles[0]) < recs.index(storms[0])


# ------------------------------------------------------- compile accounting

def test_memwatch_accounts_once_per_shape(tmp_path):
    out = str(tmp_path)
    metrics = MetricsLogger(out)
    mw = MemWatch(metrics=metrics, mem_interval=10_000)
    fn = jax.jit(lambda v: (v * 2.0).sum())
    x = jnp.zeros((16,), jnp.float32)
    r1 = mw.account(fn, x, step=0)
    r2 = mw.account(fn, x, step=5)   # memoized: same record, no relog
    assert r1 is r2 and r1["shape_index"] == 0
    assert mw.peak_hbm_bytes == r1["peak_hbm_bytes"]
    r3 = mw.account(fn, jnp.zeros((32,), jnp.float32), step=6)
    assert r3["shape_index"] == 1 and r3["step"] == 6
    metrics.close()
    comps = [r for r in _records(out) if r["kind"] == "compile"]
    assert len(comps) == 2
    assert {c["shape_key"] for c in comps} == set(mw.shapes)
    assert all(c["compile_s"] >= 0 and c["lower_s"] >= 0 for c in comps)


# ------------------------------------------------------------ memory plane

def test_device_mem_leak_fires_once_per_monotonic_run():
    mon = AnomalyMonitor(thresholds=Thresholds(mem_leak_windows=3))
    stream = [100, 200, 300, 400, 500,   # run 1: fires at the 3rd growth
              500,                       # plateau: streak + latch reset
              600, 700, 800, 900]        # run 2: fires again
    fired = []
    for step, live in enumerate(stream):
        fired += mon.observe_memory(step, live_bytes=live)
    assert [e["rule"] for e in fired] == ["device_mem_leak"] * 2
    assert [e["step"] for e in fired] == [3, 8]


def test_hbm_headroom_fires_on_crossing_and_rearms():
    mon = AnomalyMonitor(thresholds=Thresholds(hbm_headroom_frac=0.9))
    assert mon.observe_memory(1, bytes_in_use=80, bytes_limit=100) == []
    fired = mon.observe_memory(2, bytes_in_use=95, bytes_limit=100)
    assert [e["rule"] for e in fired] == ["hbm_headroom"]
    assert fired[0]["value"] == pytest.approx(0.95)
    # latched while it stays over; re-arms after dropping below
    assert mon.observe_memory(3, bytes_in_use=96, bytes_limit=100) == []
    assert mon.observe_memory(4, bytes_in_use=50, bytes_limit=100) == []
    fired = mon.observe_memory(5, bytes_in_use=99, bytes_limit=100)
    assert [e["rule"] for e in fired] == ["hbm_headroom"]


def test_missing_memory_stats_degrades_to_live_arrays():
    """CPU backends report no memory_stats: the watch must sample
    live_arrays alone, with no device fields and no headroom rule."""
    assert device_memory_summary() == {}
    la = live_array_summary()
    assert la["live_count"] >= 0 and la["live_bytes"] >= 0
    mw = MemWatch(mem_interval=1)
    rec = mw.sample(step=7)
    assert rec["step"] == 7 and rec["recompile_count"] == 0
    assert "live_bytes" in rec
    assert "bytes_in_use" not in rec and "headroom_frac" not in rec
    mw.close()


# ------------------------------------------------------------ reshape fault

def test_reshape_inject_halves_batch_axis_once():
    """The host batch is a list of per-shard dicts ([nsteps, B, ...]
    leaves); the fault cuts every shard's B axis alike."""
    inj = FaultInjector("reshape@3")
    batch = [{"x": np.zeros((1, 4, 8), np.float32),
              "y": np.zeros((1, 4), np.int32)} for _ in range(2)]
    out = inj.reshape_batch(batch, 2, 3)
    assert [s["x"].shape for s in out] == [(1, 2, 8)] * 2
    assert [s["y"].shape for s in out] == [(1, 2)] * 2
    # a point fault is consumed: the next dispatch is back to canonical
    again = inj.reshape_batch(batch, 3, 4)
    assert [s["x"].shape for s in again] == [(1, 4, 8)] * 2
    assert inj.summary() == {"reshape": 1}


def test_reshape_inject_noop_on_singleton_batch():
    inj = FaultInjector("reshape@1")
    batch = [{"x": np.zeros((1, 1, 8), np.float32)} for _ in range(2)]
    out = inj.reshape_batch(batch, 0, 1)
    # cannot halve 1: recorded no-op
    assert [s["x"].shape for s in out] == [(1, 1, 8)] * 2
    assert inj.summary() == {"reshape": 1}


# ------------------------------------------------------ report + registry

def _synthetic_run(tmp_path):
    out = str(tmp_path / "run")
    with MetricsLogger(out) as m:
        m.log("manifest", flush=True, config_hash="cfg0", git_sha="abcd",
              peak_hbm_bytes=1000)
        m.log("train", step=1, loss=2.0)
        m.log("train", step=2, loss=1.5)
        m.log("compile", flush=True, shape_key="4x3:float32", step=0,
              shape_index=0, flops=100.0, bytes_accessed=400.0,
              temp_bytes=600, argument_bytes=300, output_bytes=100,
              generated_code_bytes=0, peak_hbm_bytes=1000,
              lower_s=0.1, compile_s=0.2)
        m.log("compile", flush=True, event="recompile", step=3,
              cache_size=2, recompile_count=1, compile_events=2)
        m.log("event", flush=True, rule="recompile_storm",
              severity="warn", step=3, value=2.0, threshold=0.0,
              message="synthetic")
        m.log("mem", step=2, live_bytes=500, live_count=5,
              live_bytes_float32=500, recompile_count=0)
        m.log("mem", step=4, live_bytes=520, live_count=5,
              live_bytes_float32=520, recompile_count=1)
    return out


def test_report_compile_and_mem_subcommands(tmp_path, capsys):
    out = _synthetic_run(tmp_path)
    assert obs_report.main(["compile", out]) == 0
    text = capsys.readouterr().out
    assert "1 distinct dispatch shape" in text
    assert "recompile_count=1" in text and "recompile_storm events=1" in text
    assert "manifest peak_hbm_bytes=1000" in text
    assert obs_report.main(["mem", out]) == 0
    text = capsys.readouterr().out
    assert "2 sample(s)" in text and "float32" in text
    assert "no memory_stats" in text          # synthetic run has none
    assert "recompile_storm=1" in text
    comp = obs_report.summarize_compile(_records(out))
    assert comp["peak_hbm_bytes"] == 1000
    assert comp["recompile_count"] == 1 and comp["storm_events"] == 1
    mem = obs_report.summarize_mem(_records(out))
    assert mem["samples"] == 2 and mem["live_bytes_last"] == 520
    assert mem["by_dtype"] == {"float32": 520}
    assert mem["rules"] == {"recompile_storm": 1}


def test_exporter_and_watch_surface_mem_gauges(tmp_path):
    """Satellite: the space-plane gauges flow through the OpenMetrics
    exporter (generic numeric-field ingest — no exporter change needed,
    pin the family names so a field rename can't silently drop them)
    and ``report watch`` prints them on its per-rank summary line."""
    import io

    from gtopkssgd_tpu.obs.exporter import MetricsExporter

    exp = MetricsExporter()          # observe/scrape need no HTTP server
    exp.observe({"kind": "mem", "step": 4, "live_bytes": 520,
                 "bytes_in_use": 900, "peak_bytes_in_use": 1100,
                 "recompile_count": 1})
    text = exp.scrape()
    for family in ("gtopk_mem_live_bytes 520",
                   "gtopk_mem_bytes_in_use 900",
                   "gtopk_mem_peak_bytes_in_use 1100",
                   "gtopk_mem_recompile_count 1"):
        assert family.split()[0] in text and family.replace(
            " ", '{rank="0"} ', 1) in text
    out = _synthetic_run(tmp_path)
    buf = io.StringIO()
    assert obs_report.run_watch([out], interval=0.0, iterations=1,
                                out=buf) == 0
    line = buf.getvalue()
    assert "live_bytes=520" in line and "recompile_count=1" in line


def test_registry_and_regress_carry_mem_fields(tmp_path):
    entry = obs_registry.run_summary(_records(_synthetic_run(tmp_path)))
    assert entry["stats"]["peak_hbm_bytes"] == 1000
    assert entry["stats"]["recompile_count"] == 1
    _, fails = obs_registry.regress(entry, entry)
    assert fails == 0
    # recompile_count is an exact-match check: ANY drift fails
    cur = copy.deepcopy(entry)
    cur["stats"]["recompile_count"] = 2
    _, fails = obs_registry.regress(cur, entry)
    assert fails == 1
    # peak-HBM tolerates 10%; +20% is a program-size regression
    cur = copy.deepcopy(entry)
    cur["stats"]["peak_hbm_bytes"] = 1200
    _, fails = obs_registry.regress(cur, entry)
    assert fails == 1


def test_registry_recompile_count_absent_without_memwatch():
    """Runs without --obs-mem must not grow a vacuous 0 — absent on both
    sides means not-applicable to regress."""
    records = [{"kind": "manifest", "time": 1.0, "config_hash": "c"},
               {"kind": "train", "step": 1, "time": 1.0, "loss": 1.0}]
    entry = obs_registry.run_summary(records)
    assert "recompile_count" not in entry["stats"]
    assert "peak_hbm_bytes" not in entry["stats"]


# ------------------------------------------------------------- trainer e2e

def test_trainer_obs_mem_accounts_and_stays_stable(tmp_path):
    """End-to-end on the 2-device CPU mesh (canonical gate-smoke config,
    cached executable): --obs-mem stamps peak_hbm_bytes into the
    manifest, logs exactly one compile record for the one dispatch
    shape, samples mem windows with recompile_count pinned at 0, and the
    new fields round-trip through report and the registry."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    out = str(tmp_path / "run")
    reg = str(tmp_path / "reg")
    cfg = TrainConfig(dnn="resnet20", batch_size=4, nworkers=2,
                      compression="gtopk_layerwise", density=0.01,
                      seed=42, max_epochs=1, log_interval=1,
                      obs_interval=1, eval_batches=1, out_dir=out,
                      obs_mem=True, obs_mem_interval=1, registry=reg)
    with Trainer(cfg) as t:
        assert t.memwatch is not None
        t.train(4)
        assert t.memwatch.recompile_count == 0
        assert len(t.memwatch.shapes) == 1
    recs = _records(out)
    assert recs[0]["kind"] == "manifest"
    assert recs[0]["peak_hbm_bytes"] > 0
    comps = [r for r in recs if r["kind"] == "compile"]
    assert len(comps) == 1 and comps[0].get("event") is None
    assert comps[0]["peak_hbm_bytes"] == recs[0]["peak_hbm_bytes"]
    mems = [r for r in recs if r["kind"] == "mem"]
    assert mems and all(r["recompile_count"] == 0 for r in mems)
    live = [r["live_bytes"] for r in mems]
    assert max(live) - min(live) <= 0.5 * min(live), \
        "live bytes should be stable over a 4-step CPU run"
    assert not any(r["kind"] == "event" for r in recs)
    assert obs_report.main(["mem", out]) == 0
    assert obs_report.main(["compile", out]) == 0
    assert obs_report.main(["plan", out]) == 0
    entries, bad = obs_registry.load_registry(reg)
    assert len(entries) == 1 and bad == 0
    assert entries[0]["stats"]["recompile_count"] == 0
    assert entries[0]["stats"]["peak_hbm_bytes"] == \
        recs[0]["peak_hbm_bytes"]
    assert obs_report.main(["regress", out, "--registry", reg]) == 0


@pytest.mark.slow  # compiles the halved-batch executable cold (~1 min);
# the tier-1 equivalent is the gate smoke's storm leg (run_mem_smoke)
def test_reshape_storm_halts_dist_trainer_with_exit_44(tmp_path):
    """The acceptance chain through the CLI: an injected second dispatch
    shape retraces the step, recompile_count lands at exactly 1, the
    storm fires with warmup 0, and --obs-halt-on warn exits 44 — with
    the recompile record durably on disk before the halt."""
    from gtopkssgd_tpu import dist_trainer

    out = str(tmp_path / "run")
    rc = dist_trainer.main([
        "--dnn", "resnet20", "--batch-size", "4", "--nworkers", "2",
        "--compression", "gtopk_layerwise", "--density", "0.01",
        "--seed", "42", "--eval-batches", "1", "--log-interval", "1",
        "--obs-interval", "1", "--num-iters", "5",
        "--obs-mem", "--obs-mem-interval", "1",
        "--obs-recompile-warmup", "0", "--obs-halt-on", "warn",
        "--inject", "reshape@3", "--out-dir", out])
    assert rc == HALT_EXIT_CODE
    recs = _records(out)
    assert [r["fault"] for r in recs if r["kind"] == "inject"] == \
        ["reshape"]
    recompiles = [r for r in recs if r["kind"] == "compile"
                  and r.get("event") == "recompile"]
    assert len(recompiles) == 1
    assert recompiles[0]["recompile_count"] == 1
    storms = [r for r in recs if r["kind"] == "event"
              and r["rule"] == "recompile_storm"]
    assert len(storms) == 1
    assert recs.index(recompiles[0]) < recs.index(storms[0])
    # both dispatch shapes got their compile accounting
    shapes = [r for r in recs if r["kind"] == "compile"
              and r.get("event") is None]
    assert len(shapes) == 2
    assert obs_report.main(["compile", out]) == 0
