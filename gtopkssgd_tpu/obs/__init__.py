"""Unified observability subsystem (the paper's measured decomposition,
made first-class).

The paper's entire argument is a measured decomposition — compute vs.
selection vs. communication time and the sparsity achieved on the wire
(arXiv:1901.04359; arXiv:1911.08772 ties convergence to the error-feedback
residual dynamics). This package turns the repo's scattered primitives
(host timers, a bare jsonl logger, a --profile-dir flag) into one layer:

  counters.py — on-device training-health counters computed INSIDE the
      jitted step (achieved density, top-k threshold tau, pre/post
      compression gradient norms, error-feedback residual norm, wire
      bytes) and carried out through the optimizer state, so compression
      quality is a per-step metric for every mode.
  tracing.py  — the one span system: every closed span (path, start,
      duration, step id, thread) goes to a bounded in-memory buffer, the
      training loop's own also to window means (metrics.jsonl) and the
      sink; one clock anchor per tracer puts a span on the epoch clock
      of a profiler trace's profile_start_time, so spans and a device
      trace line up by time (the TPU runtime's host tracer is off).
  watchdog.py — dispatch stall watchdog: a monitor thread that detects a
      dispatched step failing to become ready within a deadline, emits
      a structured diagnostic and fails fast instead of hanging.
  trace_attr.py — chrome-trace parser: buckets device-lane self times
      into the paper's
      T_compute/T_select/T_comm decomposition (annotation names when the
      platform propagates them to device lanes, an op-name classifier —
      sort/top-k → select, collectives → comm — as the fallback), plus
      the capture() helper that keeps op events in the trace by running
      the profiler with the Python tracer off.
  timeline.py — host-side Chrome-trace/Perfetto export
      (``--obs-timeline``): every Tracer span as a duration event,
      telemetry as counter tracks, anomaly events and watchdog stalls as
      instant markers — one file correlating host and device phases.
  events.py   — online anomaly monitor over the per-step telemetry:
      NaN/Inf loss, EWMA loss spikes, achieved-density collapse vs. rho,
      residual-norm blow-up, residual-age runaway; severity-tagged
      "event" records (fsync'd) and optional ``--obs-halt-on``
      fail-fast (exit 44).
  report.py   — ``python -m gtopkssgd_tpu.obs.report`` aggregates one or
      two metrics.jsonl runs into per-kind/per-metric summaries (incl.
      per-layer breakdown tables from "layers" records), a side-by-side
      regression-triage comparison, and a ``gate`` subcommand diffing a
      run against a committed baseline JSON with per-field tolerances
      (nonzero exit on regression — the tier-1 drift gate), and the
      ``attr`` / ``events`` / ``timeline`` subcommands over the three
      modules above.
  manifest.py — run-manifest header (config hash, resolved headline
      flags, mesh shape, jax/backend versions, git sha, process index /
      coordinator for multi-host) written as the first record of every
      metrics file so runs are self-describing.
  fleet.py    — cross-host layer: multi-process runs shard metrics per
      rank (metrics.rank{r}.jsonl); the merger aligns records by
      (kind, step) across ranks into per-step min/median/max/std rows
      with a per-rank skew vector, validates shards via each manifest's
      config_hash, and attributes the per-step slowest rank (persistent
      vs transient via an EWMA of rank lag — the straggler_persistent
      anomaly rule, so --obs-halt-on covers it).
  ledger.py   — comm-model ledger: joins measured per-step T_comm (attr
      records) and wire bytes (obs counters) against the alpha-beta
      comm model (parallel/comm_model.predict, fed by a fit
      artifact's alpha/beta when present) into
      predicted-vs-measured ratio rows.
  exporter.py — live OpenMetrics endpoint (``--obs-export-port``):
      stdlib http.server thread serving the latest value of every
      metric field at localhost:PORT/metrics; wired in as the
      MetricsLogger sink.
  calib.py    — online comm-model calibrator (``--obs-calib``): fits
      {alpha_ms, beta_gbps} live from the run's own measured
      (wire_bytes, t_comm) samples with an outlier-robust Theil-Sen
      estimator, logs "calib" records per refit window, feeds the
      comm_model_drift anomaly rule, and writes a dcn_probe-compatible
      calib_fit_{P}proc.json artifact at end of run that the planner
      consumes next run — the obs->planner loop, closed.
  memwatch.py — compile- and memory-plane watch (``--obs-mem``): AOT
      compile accounting (one fsync'd "compile" record per distinct
      dispatch shape — cost/memory analysis, lower/compile wall times,
      peak-HBM estimate stamped into the manifest; benchmark.py's MFU
      consumes the same cost extraction), a jit executable-cache
      recompile watch feeding the recompile_storm rule, and sampled
      live-memory "mem" records (jax.live_arrays + per-device
      memory_stats where the backend exposes them) feeding the
      device_mem_leak / hbm_headroom rules.
  registry.py — append-only cross-run registry (``--registry DIR``):
      one runs.jsonl line per run (manifest subset + steps/sec, comm
      ratio, fitted alpha/beta, recall floor, wire bytes/step); read
      back offline via ``report history`` (trend table keyed by
      config_hash) and ``report regress`` (current run vs registry
      baseline under per-field rtol checks, gate exit contract).

Per-layer counters (counters.LAYER_FIELDS, flag-gated): achieved
density, tau, pre/post-compression norms, error-feedback residual norm
and mean residual AGE (steps since a coordinate last shipped), and the
mass-capture ratio m(k) = ||selected||^2/||acc||^2 whose per-layer skew
explains top-k convergence gaps (arXiv:1911.08772) — plus a sampled
exact-vs-production top-k recall audit reusing ops.topk's exact path as
ground truth.
"""

from gtopkssgd_tpu.obs.calib import (
    CommCalibrator,
    fit_alpha_beta,
    load_fit_file,
    message_count,
)
from gtopkssgd_tpu.obs.counters import (
    LAYER_FIELDS,
    TELEMETRY_FIELDS,
    keep_tau,
    layer_names,
    make_telemetry,
    mass_ratio,
    selected_tau,
    sent_count,
    dsa_counters,
    model_counters,
    model_scalars,
    moe_balance_counters,
    moe_counters,
    readable_counters,
    telemetry_scalars,
    topk_recall,
    tree_l2,
    zero_telemetry,
)
from gtopkssgd_tpu.obs.events import (
    HALT_EXIT_CODE,
    AnomalyHalt,
    AnomalyMonitor,
    Thresholds,
)
from gtopkssgd_tpu.obs.exporter import MetricsExporter
from gtopkssgd_tpu.obs.manifest import (
    config_hash,
    coordinator_address,
    git_sha,
    run_manifest,
)
from gtopkssgd_tpu.obs.memwatch import (
    CompileWatch,
    MemWatch,
    batch_shape_key,
    compiled_flops,
    cost_summary,
    memory_summary,
)
from gtopkssgd_tpu.obs.timeline import (
    TimelineRecorder,
    timeline_from_records,
    validate_timeline,
)
from gtopkssgd_tpu.obs.tracing import Tracer
from gtopkssgd_tpu.obs.watchdog import StallWatchdog

__all__ = [
    "HALT_EXIT_CODE",
    "LAYER_FIELDS",
    "TELEMETRY_FIELDS",
    "AnomalyHalt",
    "AnomalyMonitor",
    "CommCalibrator",
    "CompileWatch",
    "MemWatch",
    "MetricsExporter",
    "Thresholds",
    "TimelineRecorder",
    "Tracer",
    "StallWatchdog",
    "batch_shape_key",
    "compiled_flops",
    "config_hash",
    "coordinator_address",
    "cost_summary",
    "fit_alpha_beta",
    "git_sha",
    "keep_tau",
    "memory_summary",
    "layer_names",
    "load_fit_file",
    "make_telemetry",
    "mass_ratio",
    "message_count",
    "run_manifest",
    "selected_tau",
    "sent_count",
    "dsa_counters",
    "model_counters",
    "model_scalars",
    "moe_balance_counters",
    "moe_counters",
    "readable_counters",
    "telemetry_scalars",
    "timeline_from_records",
    "topk_recall",
    "tree_l2",
    "validate_timeline",
    "zero_telemetry",
]
