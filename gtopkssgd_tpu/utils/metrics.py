"""Structured metrics (upgrade over the reference's text-only logging).

The reference's de-facto metrics pipeline was parsing per-rank text logs
(SURVEY.md §5). Here every record is appended as one JSON line to
``metrics.jsonl`` AND logged as the familiar human-readable line, so both
machine analysis and eyeballs work.

``MetricsLogger`` is a context manager; owners that cannot use ``with``
(the Trainer holds one for its whole lifetime) call ``close()`` from their
own ``__exit__``. The file is opened line-buffered, so every completed
record hits the OS on its own ``write`` — a run killed mid-step (the stall
watchdog hard-exits, the kernel OOM-kills) loses at most the line being
written, without paying an explicit ``flush()`` syscall per record.

Multi-process runs (``shard=True``): EVERY process writes its own shard
with the deterministic name ``metrics.rank{r}.jsonl`` in the same out
dir, so cross-host comparison is possible at all — the fleet layer
(obs/fleet.py) merges shards by (kind, step) and validates via each
shard's manifest header that they belong to the same run. Single-process
runs keep the classic ``metrics.jsonl`` (rank 0 only).
"""

from __future__ import annotations

import json
import logging
import os
import re
import time
from typing import Any, Callable, Dict, Optional

# Registered record kinds. Shared with the report CLI (which flags
# unregistered kinds in a run) and enforced at log() time, so a typo'd
# kind fails loudly instead of silently vanishing from every report;
# graftlint's metric-kind rule (gtopkssgd_tpu/analysis) additionally
# resolves every static `.log(...)` call site against this set, so a
# typo is caught before any run.
KINDS = frozenset({
    "manifest",    # run provenance header (obs/manifest.py), first record
    "train",       # per-log-interval training stats
    "eval",        # validation metrics
    "epoch",       # end-of-epoch combined stats
    "obs",         # on-device compression/comm counters (obs/counters.py)
    "layers",      # per-layer telemetry, one record per layer per obs step
    "spans",       # Tracer window means (obs/tracing.py flush)
    "event",       # anomaly events (obs/events.py)
    "stall",       # watchdog stall diagnostic (obs/watchdog.py)
    "attr",        # T_compute/T_select/T_comm split (obs/trace_attr.py)
    "attr_error",  # attribution capture failure (gate smoke)
    "fleet",       # cross-rank merged per-step stats (obs/fleet.py)
    "ledger",      # predicted-vs-measured comm model rows (obs/ledger.py)
    "inject",      # injected-fault firings (resilience/inject.py)
    "recovery",    # recovery actions + end-of-run summary
                   # (resilience/policy.py, trainer emergency save)
    "twostage",    # twostage-vs-exact A/B evidence row (gate smoke):
                   # audit recall + T_select fractions for both methods
    "codec",       # wire-codec A/B evidence row (gate smoke): measured
                   # int8-vs-fp32 wire-bytes ratios, ledger audit, recall
    "lint",        # graftlint summary row (gate smoke): finding counts
                   # from python -m gtopkssgd_tpu.analysis, gated at 0
                   # non-baselined findings
    "plan",        # comm-planner decision (parallel/planner.py): chosen
                   # wire plan + every candidate's modeled score; also
                   # the gate smoke's balanced-vs-tree A/B evidence row
    "bucket",      # gradient-bucketing evidence row (parallel/bucketing):
                   # trainer logs the chosen BucketPlan (boundaries,
                   # per-bucket k, modeled ms for B in {1, chosen, L});
                   # the gate smoke logs the bucketed-vs-leafwise A/B
                   # (collective-count ratio, audited recall, bytes ratio)
    "calib",       # live comm-model refit (obs/calib.py): fitted
                   # alpha/beta, residual spread, n_samples, drift vs
                   # the planner's committed inputs and the startup fit
    "regress",     # cross-run regression evidence row (gate smoke):
                   # registry regress exit codes + fitted-vs-true check
                   # against obs/registry.py's runs.jsonl baseline
    "overlap",     # pipelined-vs-serial A/B evidence row (gate smoke):
                   # bit-identity deltas, measured overlap_frac from the
                   # trace capture, and the DP's B>1 crossover pin
    "compile",     # compile-plane accounting (obs/memwatch.py): one
                   # record per distinct dispatch shape (cost/memory
                   # analysis + lower/compile wall times) and one per
                   # executable-cache growth (recompile), fsync'd
    "mem",         # sampled live-memory window (obs/memwatch.py):
                   # live_arrays count/bytes by dtype + per-device
                   # memory_stats where the backend exposes them
    "critpath",    # per-step stage-interval record (obs/critpath.py):
                   # ordered {stage, t0_us, t1_us} segments with the
                   # comm span split into wire vs skew-wait by the
                   # ledger's alpha-beta model; fleet joins these
                   # across ranks into the global critical path
    "goodput",     # cumulative goodput/badput decomposition
                   # (obs/goodput.py): per-category seconds summing to
                   # measured wall (conservation), goodput_frac /
                   # other_frac, fsync'd every N steps + final summary
    "linkmap",     # per-(axis, peer) link weather map (obs/linkmap.py):
                   # one snapshot per calibrator capture with every
                   # link's EWMA latency/bandwidth, the carved
                   # per-round intervals, and the worst-link summary;
                   # fsync'd — written BEFORE the link_degraded rule
                   # can halt the run
    "resize",      # elastic fleet resize (resilience/elastic.py): one
                   # fsync'd record per resize decision — old_p, new_p,
                   # reason (preempt|evict|inject), evicted_ranks,
                   # drained_step, restore_step, lineage_id,
                   # resize_epoch — durable BEFORE any process exits 46
    "forecast",    # scale-out forecast record (obs/forecast.py): the
                   # hindcast error (predicted vs measured step time on
                   # THIS run), the per-P-target recommendation grid
                   # with resid-derived uncertainty bands, and the
                   # tree->balanced crossover P; fsync'd — written
                   # BEFORE the forecast_drift rule can halt the run
})

_SHARD_RE = re.compile(r"^metrics\.rank(\d+)\.jsonl$")


def shard_filename(rank: int) -> str:
    """Deterministic per-rank shard name; the join key the fleet merger
    (and a human with `ls`) recovers the rank from."""
    return f"metrics.rank{rank}.jsonl"


def shard_rank(path: str) -> Optional[int]:
    """Rank encoded in a shard filename, or None for non-shard names."""
    m = _SHARD_RE.match(os.path.basename(path))
    return int(m.group(1)) if m else None


class MetricsLogger:
    def __init__(self, out_dir: Optional[str] = None,
                 logger: Optional[logging.Logger] = None, rank: int = 0,
                 shard: bool = False,
                 sink: Optional[Callable[[Dict[str, Any]], None]] = None):
        """``shard=True`` (multi-process runs) writes
        ``metrics.rank{rank}.jsonl`` on EVERY rank; the default writes
        ``metrics.jsonl`` on rank 0 only. ``sink`` is called with each
        completed record (file or no file) — the live exporter's hook
        (obs.exporter.MetricsExporter.observe matches it); sink errors
        are swallowed so export can never take down training."""
        self.logger = logger
        self.rank = rank
        self.sink = sink
        self._fh = None
        if out_dir is not None and (shard or rank == 0):
            os.makedirs(out_dir, exist_ok=True)
            name = shard_filename(rank) if shard else "metrics.jsonl"
            self._fh = open(os.path.join(out_dir, name), "a", buffering=1)

    def log(self, kind: str, *, flush: bool = False,
            **fields: Any) -> Dict[str, Any]:
        """``flush=True`` fsyncs the record to disk before returning —
        for diagnostics that must survive a hard kill in the very next
        instruction (anomaly ``event`` records, the manifest header);
        line buffering alone only guarantees the write reaches the OS."""
        if not isinstance(kind, str) or not kind:
            raise ValueError(
                f"metrics kind must be a non-empty str, got {kind!r}")
        if kind not in KINDS:
            raise ValueError(
                f"unregistered metrics kind {kind!r}; add it to "
                f"utils.metrics.KINDS (registered: {sorted(KINDS)})")
        rec = {"kind": kind, "time": time.time(), "rank": self.rank, **fields}
        if self._fh is not None:
            self._fh.write(json.dumps(rec) + "\n")
            if flush:
                try:
                    self._fh.flush()
                    os.fsync(self._fh.fileno())
                except OSError:
                    pass
        if self.sink is not None:
            try:
                self.sink(rec)
            except Exception:
                pass
        if self.logger is not None:
            human = " ".join(
                f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                for k, v in fields.items()
            )
            self.logger.info("[%s] %s", kind, human)
        return rec

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
