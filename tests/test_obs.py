"""Observability subsystem (gtopkssgd_tpu.obs): on-device counters,
tracing spans, the stall watchdog, and the report CLI.

Counter semantics are pinned on tiny models where the expected values are
computable by hand; the watchdog is driven with a deliberately-stalled
armed region (never a real wedged backend); the report CLI round-trips a
synthetic metrics.jsonl.
"""

import gc
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gtopkssgd_tpu.obs import (
    HALT_EXIT_CODE,
    TELEMETRY_FIELDS,
    AnomalyHalt,
    AnomalyMonitor,
    StallWatchdog,
    Thresholds,
    Tracer,
    counters as obs_counters,
)
from gtopkssgd_tpu.obs import report as obs_report
from gtopkssgd_tpu.obs import tracing
from gtopkssgd_tpu.optimizer import flat_residual, gtopk_sgd
from gtopkssgd_tpu.ops import k_for_density
from gtopkssgd_tpu.utils.metrics import MetricsLogger


def _tiny_params():
    return {
        "w": jnp.arange(1, 101, dtype=jnp.float32).reshape(10, 10) / 100,
        "b": jnp.ones((7,), jnp.float32),
    }


def _tiny_grads(params):
    # strictly nonzero, globally distinct magnitudes -> top-k has no ties
    # and the threshold path keeps exactly k elements
    leaves, treedef = jax.tree.flatten(params)
    total = sum(x.size for x in leaves)
    flat = jnp.arange(1, total + 1, dtype=jnp.float32) * 1e-3
    out, off = [], 0
    for x in leaves:
        out.append(flat[off:off + x.size].reshape(x.shape))
        off += x.size
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------- counters

def test_gtopk_counters_single_worker():
    params = _tiny_params()
    grads = _tiny_grads(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    rho = 0.05
    tx = gtopk_sgd(0.1, compression="gtopk", density=rho, axis_name=None,
                   telemetry=True)
    state = tx.init(params)
    # init telemetry is the zero struct with the full field set
    assert set(state.telemetry) == set(TELEMETRY_FIELDS)
    _, state = jax.jit(tx.update)(grads, state, params)
    tel = {k: float(v) for k, v in state.telemetry.items()}

    k = k_for_density(n, rho)
    # achieved density within one element of the requested rho
    assert abs(tel["sent_elems"] - k) <= 1
    assert abs(tel["achieved_density"] - k / n) <= 1.0 / n
    assert tel["tau"] > 0
    assert tel["residual_norm"] > 0          # error feedback accumulated
    assert tel["grad_norm_pre"] > 0
    assert 0 < tel["grad_norm_post"] < tel["grad_norm_pre"]
    assert tel["wire_bytes"] == 8 * k        # p=1: one (f32, i32) set


def test_dense_counters_single_worker():
    params = _tiny_params()
    grads = _tiny_grads(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    tx = gtopk_sgd(0.1, compression="dense", axis_name=None, telemetry=True)
    state = tx.init(params)
    _, state = jax.jit(tx.update)(grads, state, params)
    tel = {k: float(v) for k, v in state.telemetry.items()}
    assert tel["achieved_density"] == 1.0
    assert tel["sent_elems"] == n
    assert tel["residual_norm"] == 0.0       # dense mode: no error feedback
    assert tel["tau"] == 0.0
    assert tel["grad_norm_post"] == pytest.approx(tel["grad_norm_pre"],
                                                  rel=1e-6)
    assert tel["wire_bytes"] == 4 * n


def test_layerwise_counters_respect_per_leaf_quota():
    params = _tiny_params()
    grads = _tiny_grads(params)
    rho = 0.05
    tx = gtopk_sgd(0.1, compression="gtopk_layerwise", density=rho,
                   axis_name=None, telemetry=True)
    state = tx.init(params)
    _, state = jax.jit(tx.update)(grads, state, params)
    tel = {k: float(v) for k, v in state.telemetry.items()}
    k_total = sum(k_for_density(int(x.size), rho)
                  for x in jax.tree.leaves(params))
    assert abs(tel["sent_elems"] - k_total) <= 1
    assert tel["tau"] > 0 and tel["residual_norm"] > 0


def test_telemetry_off_keeps_state_empty():
    params = _tiny_params()
    tx = gtopk_sgd(0.1, compression="gtopk", density=0.05, axis_name=None)
    state = tx.init(params)
    assert state.telemetry == ()
    _, state = jax.jit(tx.update)(_tiny_grads(params), state, params)
    assert state.telemetry == ()


def test_warmup_phase_reads_as_dense_then_sparse():
    params = _tiny_params()
    grads = _tiny_grads(params)
    tx = gtopk_sgd(0.1, compression="gtopk", density=0.05, axis_name=None,
                   warmup_dense_steps=1, telemetry=True)
    state = tx.init(params)
    _, state = jax.jit(tx.update)(grads, state, params)
    assert float(state.telemetry["achieved_density"]) == pytest.approx(
        1.0, rel=1e-6)                                        # warm-up step
    _, state = jax.jit(tx.update)(grads, state, params)
    assert float(state.telemetry["achieved_density"]) < 0.1   # sparse now


def test_counters_replicated_under_spmd_mesh():
    from functools import partial

    from jax.sharding import Mesh, PartitionSpec as P

    from gtopkssgd_tpu.optimizer import (
        GTopKSGDState,
        expand_residual_per_device,
    )

    p = 8
    mesh = Mesh(np.array(jax.devices()[:p]), ("dp",))
    params = _tiny_params()
    n = sum(x.size for x in jax.tree.leaves(params))
    rho = 0.05
    tx = gtopk_sgd(0.1, compression="gtopk", density=rho, axis_name="dp",
                   telemetry=True)
    state = expand_residual_per_device(jax.jit(tx.init)(params), p, mesh)
    spec = GTopKSGDState(count=P(), residual=P("dp"), inner=P(),
                         telemetry=P())

    @partial(jax.shard_map, mesh=mesh, in_specs=(P("dp"), spec, P()),
             out_specs=(P(), spec), check_vma=False)
    def step(grads, st, prms):
        g = jax.tree.map(lambda x: x[0], grads)
        s = st._replace(residual=jax.tree.map(lambda r: r[0], st.residual))
        upd, s2 = tx.update(g, s, prms)
        return upd, s2._replace(
            residual=jax.tree.map(lambda r: r[None], s2.residual))

    base = _tiny_grads(params)
    grads = jax.tree.map(
        lambda x: jnp.stack([x * (1.0 + 0.1 * i) for i in range(p)]), base)
    _, state = jax.jit(step)(grads, state, params)
    tel = {k: float(v) for k, v in state.telemetry.items()}
    k = k_for_density(n, rho)
    assert abs(tel["sent_elems"] - k) <= 1    # pmean of identical counts
    assert tel["tau"] > 0 and tel["residual_norm"] > 0
    # wire model: gtopk hypercube sends k pairs per round, log2(p) rounds
    assert tel["wire_bytes"] == 8 * k * int(np.log2(p))


def test_counter_helpers_edge_cases():
    assert float(obs_counters.tree_l2(())) == 0.0
    assert float(obs_counters.selected_tau(jnp.zeros(4))) == 0.0
    vals = jnp.array([0.0, -0.5, 2.0, 0.0])
    assert float(obs_counters.selected_tau(vals)) == 0.5
    assert float(obs_counters.sent_count(vals)) == 2.0
    keep = jnp.array([False, True, True, False])
    acc = jnp.array([9.0, -3.0, 1.0, 9.0])
    assert float(obs_counters.keep_tau(keep, acc)) == 1.0
    assert float(obs_counters.keep_tau(jnp.zeros(4, bool), acc)) == 0.0
    # residual_l2 reads v (not u) under momentum correction
    res = {"v": jnp.array([3.0, 4.0]), "u": jnp.array([100.0, 100.0])}
    assert float(obs_counters.residual_l2(res)) == 5.0


# --------------------------------------------------------------- spans

def test_span_nesting_builds_paths():
    tr = Tracer()
    with tr.span("train"):
        with tr.span("io"):
            pass
        with tr.span("dispatch"):
            pass
    with tr.span("eval"):
        pass
    summary = tr.stats.summary()
    assert set(summary) == {"train", "train/io", "train/dispatch", "eval"}
    assert all(sec >= 0 for sec in summary.values())
    assert tr.current_path == ""             # stack fully unwound


def test_span_nesting_is_per_thread():
    tr = Tracer()
    seen = {}
    release = threading.Event()

    def worker():
        with tr.span("worker_phase"):
            seen["inside"] = tr.current_path
            release.wait(2.0)

    with tr.span("main_phase"):
        t = threading.Thread(target=worker)
        t.start()
        while "inside" not in seen and t.is_alive():
            time.sleep(0.01)
        # the worker's open span must not nest under main's
        assert seen["inside"] == "worker_phase"
        release.set()
        t.join()
    assert "main_phase/worker_phase" not in tr.stats.summary()


def test_span_flush_logs_one_record_and_resets(tmp_path):
    with MetricsLogger(str(tmp_path)) as metrics:
        tr = Tracer(metrics=metrics)
        with tr.span("io"):
            pass
        summary = tr.flush(step=7)
        assert "io" in summary
        assert tr.stats.summary() == {}      # reset after flush
        assert tr.flush(step=8) == {}        # empty window -> no record
    recs = [json.loads(l) for l in
            open(os.path.join(tmp_path, "metrics.jsonl"))]
    spans = [r for r in recs if r["kind"] == "spans"]
    assert len(spans) == 1 and spans[0]["step"] == 7 and "io" in spans[0]


def test_disabled_tracer_records_nothing():
    before = tracing.buffered_spans()
    seen = []
    tr = Tracer(enabled=False, sink=lambda *a: seen.append(a))
    with tr.span("x", step=1):
        pass
    assert tr.stats.summary() == {}
    assert seen == [] and tracing.buffered_spans() == before


def _own(marker):
    return [r for r in tracing.buffered_spans() if r.path.startswith(marker)]


def test_span_records_carry_step_thread_and_parent_path():
    tr = Tracer()
    with tr.span("t1io", step=7):
        with tr.span("wait"):
            pass
        with tr.span("put", step=8):      # an explicit step wins
            pass
    with tr.span("t1final"):
        pass
    recs = {r.path: r for r in _own("t1")}
    assert set(recs) == {"t1io", "t1io/wait", "t1io/put", "t1final"}
    assert recs["t1io"].step == recs["t1io/wait"].step == 7
    assert recs["t1io/put"].step == 8 and recs["t1final"].step is None
    me = threading.current_thread().name
    for r in recs.values():
        assert r.thread == me and r.anchor_ns == tr.anchor_ns
        assert r.dur >= 0 and r.t0 <= time.perf_counter()
    # A child closes before its parent and lies inside it.
    assert recs["t1io"].t0 <= recs["t1io/wait"].t0
    assert (recs["t1io/put"].t0 + recs["t1io/put"].dur
            <= recs["t1io"].t0 + recs["t1io"].dur)


def test_span_buffer_is_bounded_and_outlives_its_tracer():
    cap = tracing.SPAN_BUFFER.maxlen
    assert cap is not None and cap >= 4096
    tr = Tracer()
    with tr.span("t2kept", step=3):
        pass
    del tr
    gc.collect()
    assert [r.step for r in _own("t2kept")] == [3]
    saved = tracing.buffered_spans()
    try:
        tr = Tracer()
        for i in range(cap + 10):
            with tr.span("t2flood", step=i):
                pass
        spans = tracing.buffered_spans()
        assert len(spans) == cap
        assert spans[0].step == 10 and spans[-1].step == cap + 9
    finally:
        tracing.SPAN_BUFFER.clear()
        tracing.SPAN_BUFFER.extend(saved)


def test_worker_thread_span_reaches_the_buffer_and_not_the_sink():
    sunk = []
    tr = Tracer(sink=lambda path, t0, dur: sunk.append(path))

    def worker():
        with tr.span("t3prefetch/assemble", step=0):
            pass

    t = threading.Thread(target=worker, name="t3-worker")
    t.start()
    t.join(5.0)
    assert not t.is_alive()
    with tr.span("t3io", step=0):
        pass
    assert sunk == ["t3io"]
    assert set(tr.stats.summary()) == {"t3io"}
    threads = {r.path: r.thread for r in _own("t3")}
    assert threads == {"t3prefetch/assemble": "t3-worker",
                       "t3io": threading.current_thread().name}


def test_clock_anchor_puts_a_perf_counter_stamp_on_the_epoch_clock():
    tr = Tracer()
    gaps = []
    for _ in range(5):
        wall = time.time_ns()
        with tr.span("t4now"):
            pass
        gaps.append(abs(tracing.epoch_ns(_own("t4now")[-1]) - wall))
    assert min(gaps) < 1_000_000, gaps        # within a millisecond
    assert abs(tracing.clock_anchor_ns() - tr.anchor_ns) < 1_000_000


# ------------------------------------------------------------ watchdog

def test_watchdog_fires_on_stalled_region():
    fired = []
    wd = StallWatchdog(0.15, poll_s=0.03, on_stall=fired.append,
                       diagnostics=lambda: {"phase_means_s": {"io": 1.5}})
    try:
        wd.arm("train_step", step=12)
        wd.heartbeat(step=12)
        deadline = time.monotonic() + 3.0
        while not wd.fired and time.monotonic() < deadline:
            time.sleep(0.02)                 # the "stalled" main thread
        assert wd.fired
        (rec,) = fired
        assert rec["kind"] == "stall"
        assert rec["label"] == "train_step"
        assert rec["armed_step"] == 12
        assert rec["last_completed_step"] == 12
        assert rec["waited_s"] >= 0.15
        assert rec["phase_means_s"] == {"io": 1.5}
        assert "device" in rec
    finally:
        wd.close()


def test_watchdog_heartbeat_prevents_firing():
    fired = []
    wd = StallWatchdog(0.25, poll_s=0.03, on_stall=fired.append)
    try:
        wd.arm("train", step=0)
        for s in range(8):                   # 0.4s total, never 0.25s idle
            time.sleep(0.05)
            wd.heartbeat(step=s)
        wd.disarm()
        time.sleep(0.3)                      # disarmed: silence
        assert not wd.fired and fired == []
    finally:
        wd.close()


def test_watchdog_fires_once_and_validates():
    with pytest.raises(ValueError):
        StallWatchdog(0.0)
    fired = []
    wd = StallWatchdog(0.05, poll_s=0.02, on_stall=fired.append)
    try:
        with wd.watch("region"):
            time.sleep(0.4)                  # several deadlines deep
        time.sleep(0.1)
        assert len(fired) == 1               # one diagnostic, not a storm
    finally:
        wd.close()


def test_watchdog_diagnostics_failure_is_contained():
    fired = []

    def bad_diag():
        raise RuntimeError("host state gone")

    wd = StallWatchdog(0.05, poll_s=0.02, on_stall=fired.append,
                       diagnostics=bad_diag)
    try:
        wd.arm("x")
        deadline = time.monotonic() + 2.0
        while not fired and time.monotonic() < deadline:
            time.sleep(0.02)
        assert fired and "diagnostics_error" in fired[0]
    finally:
        wd.close()


# ----------------------------------------------------------- report CLI

def _write_run(path, rows):
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "metrics.jsonl"), "w") as fh:
        for r in rows:
            fh.write(json.dumps(r) + "\n")


def test_report_roundtrips_synthetic_run(tmp_path, capsys):
    run = str(tmp_path / "runA")
    _write_run(run, [
        {"kind": "train", "time": 1.0, "rank": 0, "step": 10, "loss": 2.5},
        {"kind": "train", "time": 2.0, "rank": 0, "step": 20, "loss": 2.0},
        {"kind": "obs", "time": 2.0, "rank": 0, "step": 20,
         "achieved_density": 0.001, "wire_bytes": 21800.0},
    ])
    # torn final line (the watchdog hard-exit case) must not be fatal
    with open(os.path.join(run, "metrics.jsonl"), "a") as fh:
        fh.write('{"kind": "train", "loss": 1.')
    assert obs_report.main([run]) == 0
    out = capsys.readouterr().out
    assert "skipped 1 malformed line" in out
    assert "[train]" in out and "[obs]" in out
    assert "achieved_density" in out and "loss" in out
    summary = obs_report.summarize(obs_report.load_records(run)[0])
    assert summary["train"]["loss"] == {
        "count": 2, "mean": 2.25, "min": 2.0, "max": 2.5, "last": 2.0}


def test_report_compares_two_runs(tmp_path, capsys):
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    _write_run(a, [{"kind": "obs", "time": 1.0, "rank": 0,
                    "wire_bytes": 100.0, "achieved_density": 0.001}])
    _write_run(b, [{"kind": "obs", "time": 1.0, "rank": 0,
                    "wire_bytes": 300.0, "achieved_density": 0.001}])
    json_out = str(tmp_path / "diff.json")
    assert obs_report.main([a, b, "--json", json_out]) == 0
    out = capsys.readouterr().out
    assert "wire_bytes" in out and "+200" in out
    with open(json_out) as fh:
        payload = json.load(fh)
    d = payload["diff"]["obs"]["wire_bytes"]
    assert d["delta"] == 200.0 and d["delta_pct"] == pytest.approx(200.0)


def test_report_compare_zero_baseline_prints_dash(tmp_path, capsys):
    # a counter that was 0 in the baseline has no meaningful percent
    # change — the report must print "—", not "+nan%"/"+inf%"
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    _write_run(a, [{"kind": "obs", "time": 1.0, "rank": 0,
                    "wire_bytes": 0.0}])
    _write_run(b, [{"kind": "obs", "time": 1.0, "rank": 0,
                    "wire_bytes": 300.0}])
    json_out = str(tmp_path / "diff.json")
    assert obs_report.main([a, b, "--json", json_out]) == 0
    out = capsys.readouterr().out
    assert "—" in out
    assert "nan%" not in out and "inf%" not in out
    d = json.load(open(json_out))["diff"]["obs"]["wire_bytes"]
    assert d["delta"] == 300.0 and d["delta_pct"] is None


def test_report_errors_are_exit_code_2(tmp_path, capsys):
    assert obs_report.main([str(tmp_path / "missing")]) == 2
    capsys.readouterr()


# ----------------------------------------------- metrics logger lifecycle

def test_metrics_logger_context_manager(tmp_path):
    with MetricsLogger(str(tmp_path)) as m:
        m.log("train", step=1, loss=2.0)
        m.log("eval", step=1, top1=0.5)
    assert m._fh is None                     # guaranteed close on exit
    recs = [json.loads(l) for l in
            open(os.path.join(tmp_path, "metrics.jsonl"))]
    assert [r["kind"] for r in recs] == ["train", "eval"]
    m.log("train", step=2, loss=1.0)         # post-close: no crash, no write
    assert len(open(os.path.join(tmp_path, "metrics.jsonl")).readlines()) == 2


def test_metrics_logger_rank_nonzero_writes_nothing(tmp_path):
    with MetricsLogger(str(tmp_path / "r1"), rank=1) as m:
        m.log("train", step=1, loss=2.0)
    assert not os.path.exists(str(tmp_path / "r1" / "metrics.jsonl"))


def test_metrics_logger_flush_is_durable_and_kind_validated(tmp_path):
    m = MetricsLogger(str(tmp_path))
    try:
        m.log("event", flush=True, rule="nan_loss", severity="error", step=3)
        # flush=True fsyncs: the record is on disk while the logger is
        # still open (what keeps a diagnosis through a hard kill)
        recs = [json.loads(l) for l in
                open(os.path.join(tmp_path, "metrics.jsonl"))]
        assert recs[-1]["rule"] == "nan_loss"
        with pytest.raises(ValueError):
            m.log("", step=1)
        with pytest.raises(ValueError):
            m.log(None, step=1)
    finally:
        m.close()


# ------------------------------------------------------- anomaly monitor

def test_monitor_nan_loss_fires_error_event():
    mon = AnomalyMonitor(rho=0.01)
    (ev,) = mon.observe(3, loss=float("nan"))
    assert ev["rule"] == "nan_loss" and ev["severity"] == "error"
    assert ev["step"] == 3 and ev["value"] is None
    (ev,) = mon.observe(4, loss=float("inf"))
    assert ev["rule"] == "nan_loss"
    assert mon.summary() == {"nan_loss": 2}


def test_monitor_loss_spike_needs_warmup_and_variance():
    mon = AnomalyMonitor(thresholds=Thresholds(loss_warmup=3))
    for step, loss in enumerate([1.0, 1.02, 0.98, 1.0, 1.01]):
        assert mon.observe(step, loss=loss) == []
    (ev,) = mon.observe(9, loss=50.0)          # many sigma above the EWMA
    assert ev["rule"] == "loss_spike" and ev["severity"] == "warn"
    assert ev["value"] > ev["threshold"] == 6.0
    # a steady loss after the spike decays back to silence
    assert mon.observe(10, loss=1.0) == []


def test_monitor_density_collapse_requires_rho():
    mon = AnomalyMonitor(rho=0.01)
    (ev,) = mon.observe(1, loss=1.0,
                        telemetry={"achieved_density": 0.0001})
    assert ev["rule"] == "density_collapse"
    assert ev["threshold"] == pytest.approx(0.001)
    # healthy density: silent
    assert mon.observe(2, loss=1.0,
                       telemetry={"achieved_density": 0.01}) == []
    # dense runs (rho None) never evaluate the rule
    dense = AnomalyMonitor(rho=None)
    assert dense.observe(1, loss=1.0,
                         telemetry={"achieved_density": 0.0}) == []


def test_monitor_residual_blowup_and_age_runaway():
    mon = AnomalyMonitor(rho=0.01, thresholds=Thresholds(loss_warmup=3))
    for step in range(4):
        assert mon.observe(step, telemetry={"residual_norm": 1.0}) == []
    (ev,) = mon.observe(9, telemetry={"residual_norm": 100.0})
    assert ev["rule"] == "residual_blowup"
    # auto age threshold is 100/rho = 1e4 steps
    assert Thresholds().age_max(0.01) == pytest.approx(1e4)
    assert Thresholds(residual_age_max=5.0).age_max(0.01) == 5.0
    (ev,) = AnomalyMonitor(rho=0.01).observe(1, max_residual_age=2e4)
    assert ev["rule"] == "residual_age_runaway"
    assert AnomalyMonitor(rho=None).observe(1, max_residual_age=1e9) == []


def test_monitor_halt_severity_ordering(tmp_path):
    with pytest.raises(ValueError):
        AnomalyMonitor(halt_on="fatal")
    # error-level halt ignores warns but trips on nan_loss — and the
    # event record is durably written BEFORE the raise
    with MetricsLogger(str(tmp_path)) as metrics:
        mon = AnomalyMonitor(metrics=metrics, rho=0.01, halt_on="error")
        assert [e["rule"] for e in mon.observe(
            1, loss=1.0, telemetry={"achieved_density": 0.0})] \
            == ["density_collapse"]
        with pytest.raises(AnomalyHalt) as exc:
            mon.observe(2, loss=float("nan"))
        assert exc.value.event["rule"] == "nan_loss"
        recs = [json.loads(l) for l in
                open(os.path.join(tmp_path, "metrics.jsonl"))]
        assert [r["rule"] for r in recs if r["kind"] == "event"] \
            == ["density_collapse", "nan_loss"]
    # warn-level halt trips on the first warn
    mon = AnomalyMonitor(rho=0.01, halt_on="warn")
    with pytest.raises(AnomalyHalt):
        mon.observe(1, loss=1.0, telemetry={"achieved_density": 0.0})


# ------------------------------------------------------- trainer smoke

def test_trainer_emits_obs_records_and_report_reads_them(tmp_path):
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    out = str(tmp_path / "run")
    with Trainer(TrainConfig(
            dnn="resnet20", batch_size=4, nworkers=1, compression="gtopk",
            density=0.01, log_interval=2, eval_batches=1, max_epochs=1,
            out_dir=out)) as t:
        t.train(2)
    recs = [json.loads(l) for l in open(os.path.join(out, "metrics.jsonl"))]
    # provenance header is the FIRST record of every metrics.jsonl
    man = recs[0]
    assert man["kind"] == "manifest"
    assert man["compression"] == "gtopk"
    assert man["mesh_shape"] == {"dp": 1}
    assert man["jax_version"] == jax.__version__
    assert "config_hash" in man and "git_sha" in man
    obs = [r for r in recs if r["kind"] == "obs"]
    assert len(obs) == 2                     # obs_interval=1 -> per step
    for r in obs:
        for field in ("achieved_density", "tau", "residual_norm",
                      "wire_bytes", "grad_norm_pre", "grad_norm_post",
                      "sent_elems", "step"):
            assert field in r
    assert any(r["kind"] == "spans" for r in recs)  # tracer flushed
    # the report CLI aggregates what the trainer wrote
    summary = obs_report.summarize(recs)
    assert summary["obs"]["achieved_density"]["count"] == 2


# ------------------------------------------------- per-layer telemetry

def _layer_tel(state):
    return {f: np.asarray(v) for f, v in state.telemetry["layers"].items()}


@pytest.mark.parametrize(
    "mode", ["gtopk", "allgather", "gtopk_hier", "gtopk_layerwise"])
def test_per_layer_telemetry_sparse_modes(mode):
    params = _tiny_params()
    grads = _tiny_grads(params)
    rho = 0.05
    tx = gtopk_sgd(0.1, compression=mode, density=rho, axis_name=None,
                   telemetry=True, telemetry_layers=True)
    state = tx.init(params)
    sizes = np.array([x.size for x in jax.tree.leaves(params)])
    lay = _layer_tel(state)
    assert set(lay) == set(obs_counters.LAYER_FIELDS)
    assert all(v.shape == (len(sizes),) for v in lay.values())
    tel_def = jax.tree.structure(state.telemetry)

    _, state = jax.jit(tx.update)(grads, state, params)
    lay = _layer_tel(state)
    # per-layer sent counts reassemble the whole-model counter exactly
    sent = lay["density"] * sizes
    assert np.allclose(sent.sum(), float(state.telemetry["sent_elems"]),
                       atol=1.0)
    assert (lay["grad_norm_pre"] > 0).all()
    # flat modes may legitimately starve a small layer (all its coords
    # below the global tau -> m_k 0); mass ratios stay in [0, 1] and at
    # least one layer captures mass
    assert ((lay["m_k"] >= 0) & (lay["m_k"] <= 1 + 1e-6)).all()
    assert lay["m_k"].max() > 0
    # the whole-model mass ratio is an acc-mass-weighted mean of the
    # per-layer ones, so it must land inside their range
    m = float(state.telemetry["m_k"])
    assert lay["m_k"].min() - 1e-6 <= m <= lay["m_k"].max() + 1e-6
    # treedef is stable across steps (lax.cond/scan compatibility)
    _, state = jax.jit(tx.update)(grads, state, params)
    assert jax.tree.structure(state.telemetry) == tel_def


def test_per_layer_telemetry_dense_noop():
    params = _tiny_params()
    grads = _tiny_grads(params)   # strictly nonzero -> every coord ships
    tx = gtopk_sgd(0.1, compression="dense", axis_name=None,
                   telemetry=True, telemetry_layers=True)
    state = tx.init(params)
    _, state = jax.jit(tx.update)(grads, state, params)
    lay = _layer_tel(state)
    assert np.allclose(lay["density"], 1.0)
    assert np.allclose(lay["m_k"], 1.0)
    assert np.allclose(lay["tau"], 0.0)
    assert np.allclose(lay["residual_norm"], 0.0)  # no error feedback
    assert np.allclose(lay["residual_age"], 0.0)   # everything delivered


def test_residual_age_monotonic():
    params = _tiny_params()
    grads = _tiny_grads(params)
    tx = gtopk_sgd(0.1, compression="gtopk", density=0.05, axis_name=None,
                   telemetry=True, telemetry_layers=True)
    state = tx.init(params)
    # no mesh axis named: the age buffer is slabs, as the residual is
    age = lambda st: np.asarray(flat_residual(st.telemetry["age"], params))
    assert age(state).shape == (sum(x.size for x in jax.tree.leaves(params)),)
    ages = [age(state)]
    step = jax.jit(tx.update)
    for _ in range(3):
        _, state = step(grads, state, params)
        ages.append(age(state))
    for i, (prev, cur) in enumerate(zip(ages, ages[1:]), start=1):
        # every coordinate either shipped (age resets to 0) or aged by 1
        assert np.all((cur == 0) | (cur == prev + 1))
        assert cur.max() <= i
    # constant grads + error feedback: the small-magnitude tail keeps
    # losing the selection, so SOME coordinate is older than one step
    assert ages[-1].max() >= 2
    # and the per-layer mean age reported matches the raw buffer
    lay = _layer_tel(state)
    off, means = 0, []
    for x in jax.tree.leaves(params):
        means.append(ages[-1][off:off + x.size].mean())
        off += x.size
    assert np.allclose(lay["residual_age"], means, rtol=1e-5)


def test_recall_audit_sampling():
    params = _tiny_params()
    grads = _tiny_grads(params)
    tx = gtopk_sgd(0.1, compression="gtopk", density=0.05, axis_name=None,
                   telemetry=True, telemetry_audit_interval=2)
    state = tx.init(params)
    assert float(state.telemetry["audit_recall"]) == -1.0  # never audited
    step = jax.jit(tx.update)
    _, state = step(grads, state, params)      # count=0 -> audited
    r1 = float(state.telemetry["audit_recall"])
    # exact threshold selection on all-distinct magnitudes IS the top-k
    assert r1 == pytest.approx(1.0)
    _, state = step(grads, state, params)      # count=1 -> carries value
    assert float(state.telemetry["audit_recall"]) == pytest.approx(r1)


def test_audit_flags_require_telemetry():
    with pytest.raises(ValueError):
        gtopk_sgd(0.1, compression="gtopk", density=0.05, axis_name=None,
                  telemetry_layers=True)
    with pytest.raises(ValueError):
        gtopk_sgd(0.1, compression="gtopk", density=0.05, axis_name=None,
                  telemetry_audit_interval=2)


# ------------------------------------------------------------- manifest

def test_manifest_roundtrip_and_hash_stability(tmp_path):
    from gtopkssgd_tpu.obs.manifest import config_hash, git_sha, run_manifest

    cfg = {"dnn": "resnet20", "density": 0.01, "nworkers": 2,
           "batch_size": 4, "seed": 42, "compression": "gtopk"}
    man = run_manifest(cfg, extra_field="x")
    # json round-trip (what MetricsLogger does) preserves everything
    back = json.loads(json.dumps(man))
    assert back == man
    assert back["config_hash"] == config_hash(cfg)
    assert back["extra_field"] == "x"
    for key in ("dnn", "density", "nworkers", "batch_size", "seed"):
        assert back[key] == cfg[key]
    # hash is insertion-order independent and value sensitive
    assert config_hash(dict(reversed(list(cfg.items())))) == config_hash(cfg)
    assert config_hash({**cfg, "density": 0.02}) != config_hash(cfg)
    sha = git_sha()
    assert sha is None or isinstance(sha, str)


# ------------------------------------------------------- report gate

def _synthetic_run(tmp_path, sent=100.0):
    run = tmp_path / "run"
    run.mkdir(exist_ok=True)
    recs = [
        {"kind": "manifest", "compression": "gtopk", "nworkers": 2},
        {"kind": "obs", "step": 1, "sent_elems": sent, "tau": 0.5},
        {"kind": "obs", "step": 2, "sent_elems": sent, "tau": 0.7},
        {"kind": "layers", "step": 2, "layer": "w", "density": 0.05},
        {"kind": "layers", "step": 2, "layer": "b", "density": 0.10},
    ]
    with open(run / "metrics.jsonl", "w") as fh:
        for r in recs:
            fh.write(json.dumps(r) + "\n")
    return str(run)


def _baseline(tmp_path, **overrides):
    base = {
        "manifest": {"compression": "gtopk"},
        "checks": [
            {"kind": "obs", "field": "sent_elems", "stat": "mean",
             "expect": 100.0, "rtol": 0.05},
            {"kind": "obs", "field": "tau", "stat": "last",
             "expect": 0.7, "atol": 0.01},
            {"kind": "layers", "layer": "w", "field": "density",
             "stat": "mean", "expect": 0.05, "rtol": 0.1},
        ],
    }
    base.update(overrides)
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps(base))
    return str(path)


def test_report_gate_passes_within_tolerance(tmp_path):
    run = _synthetic_run(tmp_path)
    assert obs_report.run_gate(run, _baseline(tmp_path)) == 0


def test_report_gate_fails_on_drift(tmp_path):
    run = _synthetic_run(tmp_path, sent=120.0)   # > 5% off the baseline
    assert obs_report.run_gate(run, _baseline(tmp_path)) == 1


def test_report_gate_fails_on_missing_field_and_manifest(tmp_path):
    run = _synthetic_run(tmp_path)
    base = _baseline(tmp_path, checks=[
        {"kind": "obs", "field": "vanished", "expect": 1.0, "rtol": 0.5}])
    assert obs_report.run_gate(run, base) == 1     # silently-gone counter
    base = _baseline(tmp_path, manifest={"compression": "dense"})
    assert obs_report.run_gate(run, base) == 1     # provenance mismatch


def test_report_gate_usage_errors(tmp_path):
    run = _synthetic_run(tmp_path)
    assert obs_report.run_gate(run, str(tmp_path / "nope.json")) == 2
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"checks": []}))
    assert obs_report.run_gate(run, str(bad)) == 2


def test_report_gate_write_restamps_expectations(tmp_path):
    run = _synthetic_run(tmp_path, sent=120.0)
    base = _baseline(tmp_path)
    out = str(tmp_path / "new_baseline.json")
    assert obs_report.run_gate(run, base, write=out) == 1
    regen = json.loads(open(out).read())
    by_field = {c["field"]: c for c in regen["checks"]}
    assert by_field["sent_elems"]["expect"] == pytest.approx(120.0)
    assert by_field["sent_elems"]["rtol"] == 0.05   # spec preserved
    assert obs_report.run_gate(run, out) == 0       # regenerated -> green


@pytest.mark.slow  # ~193 s: full smoke (train + mem + chaos + overlap +
# critpath arms). run_gate's check math, write-restamp and failure modes
# stay tier-1 via the test_report_gate_* tests above; drift against the
# committed baseline is enforced per-commit by regenerating with
# --write-baseline and on the slow tier.
def test_gate_smoke_matches_committed_baseline(tmp_path):
    """The drift gate: the canonical tiny gtopk_layerwise run must
    stay inside the committed baseline's tolerances. If an INTENTIONAL
    change moves a counter, regenerate with
    `python benchmarks/obs_gate_smoke.py --write-baseline` in the same
    commit."""
    from benchmarks.obs_gate_smoke import BASELINE, run_smoke

    out = run_smoke(str(tmp_path / "run"))
    assert obs_report.run_gate(out, BASELINE) == 0


# --------------------------------------------- anomaly events in training

def _event_cfg(out, **overrides):
    """2-device CPU-mesh trainer at the monitor's tightest cadence."""
    from gtopkssgd_tpu.trainer import TrainConfig

    kw = dict(dnn="resnet20", batch_size=4, nworkers=2,
              compression="gtopk_layerwise", density=0.01, seed=42,
              max_epochs=1, log_interval=1, obs_interval=1, eval_batches=1,
              out_dir=out)
    kw.update(overrides)
    return TrainConfig(**kw)


def _patch_loss(monkeypatch, scale):
    """Wrap Trainer._loss_fn so the scalar loss becomes loss * scale —
    NaN injects a divergence, 0.0 zeroes every gradient (and therefore
    the achieved density) without touching the trainer's plumbing."""
    from gtopkssgd_tpu.trainer import Trainer

    orig = Trainer._loss_fn

    def poisoned(self, params, batch_stats, carry, batch, rng, train):
        loss, rest = orig(self, params, batch_stats, carry, batch, rng,
                          train)
        return loss * scale, rest

    monkeypatch.setattr(Trainer, "_loss_fn", poisoned)


def test_trainer_nan_loss_event_and_halt_within_one_step(
        tmp_path, monkeypatch):
    """The acceptance property: an injected NaN produces a durably
    written event record AND (with --obs-halt-on error semantics) stops
    the run, both within a single step."""
    from gtopkssgd_tpu.trainer import Trainer

    _patch_loss(monkeypatch, jnp.nan)
    out = str(tmp_path / "run")
    with Trainer(_event_cfg(out, obs_halt_on="error")) as t:
        with pytest.raises(AnomalyHalt) as exc:
            t.train(2)
    assert exc.value.event["rule"] == "nan_loss"
    recs = [json.loads(l) for l in open(os.path.join(out, "metrics.jsonl"))]
    evs = [r for r in recs if r["kind"] == "event"]
    assert evs, "no event record written"
    assert evs[0]["rule"] == "nan_loss"
    assert evs[0]["severity"] == "error"
    assert evs[0]["step"] == 1               # caught within one step
    # the report CLI reads the stream back
    assert obs_report.main(["events", out]) == 0


def test_trainer_density_collapse_event_and_timeline(
        tmp_path, monkeypatch):
    _patch_loss(monkeypatch, 0.0)            # zero grads -> nothing selected
    from gtopkssgd_tpu.trainer import Trainer

    out = str(tmp_path / "run")
    with Trainer(_event_cfg(out, obs_timeline=out)) as t:
        t.train(2)                           # no halt configured: runs on
    recs = [json.loads(l) for l in open(os.path.join(out, "metrics.jsonl"))]
    evs = [r for r in recs if r["kind"] == "event"]
    rules = {r["rule"] for r in evs}
    assert "density_collapse" in rules
    assert "nan_loss" not in rules           # loss 0.0 is finite
    first = min(r["step"] for r in evs if r["rule"] == "density_collapse")
    assert first == 1                        # caught within one step
    # the live timeline was written on exit and carries the marker
    from gtopkssgd_tpu.obs import validate_timeline

    doc = json.load(open(os.path.join(out, "timeline.json")))
    assert validate_timeline(doc) == []
    names = [e.get("name") for e in doc["traceEvents"]]
    assert "event:density_collapse" in names
    assert "dispatch" in names               # Tracer spans flowed through


def test_dist_trainer_halt_exit_code(tmp_path, monkeypatch):
    from gtopkssgd_tpu import dist_trainer

    _patch_loss(monkeypatch, jnp.nan)
    assert HALT_EXIT_CODE == 44              # the watchdog owns 43
    rc = dist_trainer.main([
        "--dnn", "resnet20", "--batch-size", "4", "--nworkers", "2",
        "--compression", "gtopk_layerwise", "--density", "0.01",
        "--num-iters", "2", "--eval-batches", "1", "--log-interval", "1",
        "--obs-halt-on", "error", "--out-dir", str(tmp_path / "run"),
    ])
    assert rc == HALT_EXIT_CODE
    recs = [json.loads(l) for l in
            open(str(tmp_path / "run" / "metrics.jsonl"))]
    assert any(r["kind"] == "event" and r["rule"] == "nan_loss"
               for r in recs)
