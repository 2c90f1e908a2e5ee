"""Distributed training driver + CLI (reference L4/L5: dist_trainer.py's
``main()`` — MPI init, rank→GPU bind, param broadcast, iteration loop —
plus the mpirun launch scripts' flag surface).

TPU-native redesign: there is no per-rank process and no broadcast — ONE
SPMD program spans the mesh. ``jax.distributed.initialize()`` (multi-host)
replaces ``MPI.COMM_WORLD`` init; device binding is the mesh; the initial
"broadcast params from rank 0" is implicit (replicated init from one seed);
the iteration loop with throughput logging lives in Trainer.fit.

Flags keep the reference's names (--dnn, --dataset, --density,
--compression, --nworkers, --nsteps-update, --batch-size, --max-epochs,
--data-dir) so reference experiment scripts translate 1:1:

    mpirun -np 8 python dist_trainer.py --dnn resnet20 --density 0.001
becomes
    python -m gtopkssgd_tpu.dist_trainer --dnn resnet20 --density 0.001 \
        --nworkers 8

Wire-format flag (parallel.codec — no reference equivalent; the MPI
reference always shipped fp32 values + int32 indices):

    --wire-codec CODEC                   on-wire sparse-set encoding for
                                         every exchange round. Grammar:
                                         fp32 (identity, default) |
                                         int8[:BLOCK] | fp8[:BLOCK] —
                                         block-scaled 8-bit values (bf16
                                         scales, BLOCK defaults to 64)
                                         + Elias-Fano bitpacked indices;
                                         quantization error folds into
                                         the error-feedback residual.
                                         Recorded in the run manifest;
                                         audit measured-vs-modeled bytes
                                         with ``report ledger``

Comm-planner flag (parallel.planner — no reference equivalent; the MPI
reference hand-picked its one tree):

    --comm-plan PLAN                     wire-plan pin. 'auto' (default)
                                         scores every schedule that
                                         realizes --compression with
                                         the alpha-beta model (newest
                                         dcn_probe alpha_beta_fit when
                                         present, documented fallback
                                         constants otherwise) and keeps
                                         the historical schedule on
                                         ties, so defaults never change
                                         the wire. Plan grammar: tree
                                         (hypercube) | balanced (the
                                         Ok-Topk split-and-reduce,
                                         arXiv:2201.07598) for gtopk /
                                         gtopk_layerwise; allgather,
                                         hier, dense name their modes'
                                         single schedule. The decision
                                         (chosen plan + every
                                         candidate's score) is the
                                         'plan' metrics record —
                                         ``report plan`` prints it —
                                         and the manifest carries
                                         comm_plan / comm_plan_schedule
                                         so the ledger audits the
                                         schedule that actually ran.

Bucketing flag (parallel.bucketing — no reference equivalent; the MPI
reference merged layer-by-layer with no cost model):

    --buckets SPEC                       gtopk_layerwise gradient
                                         bucketing. Grammar: concat
                                         (default — historical wire:
                                         per-leaf selection, ONE
                                         concatenated merge) | leaf
                                         (one merge per param leaf) |
                                         an int B | auto. B/auto
                                         partition the leaves into
                                         contiguous byte-balanced
                                         buckets by an exact DP over
                                         the alpha-beta model (cost
                                         B*alpha + wire_bytes/beta;
                                         'auto' also picks B), then run
                                         one fused two-stage selection
                                         and one codec-framed merge per
                                         bucket, scattering update and
                                         error-feedback residual back
                                         to the leaves. Boundaries are
                                         stamped into the manifest
                                         (bucket_boundaries/_sizes/_ks)
                                         and logged as the 'bucket'
                                         record; ``report plan`` prints
                                         them with modeled ms for
                                         B in {1, chosen, L}.

    --pipeline SPEC                      bucketed layerwise only: bucket
                                         execution order. Grammar:
                                         serial (default — the paper's
                                         sequential select->merge
                                         chain, pinned with
                                         optimization barriers) |
                                         overlap (double-buffered
                                         stages: bucket b+1's fused
                                         selection runs concurrently
                                         with bucket b's codec-framed
                                         merge; bit-identical to
                                         serial) | auto (cheaper
                                         modeled pipeline span wins;
                                         also switches --buckets auto
                                         to overlap pricing, where the
                                         DP objective is the per-stage
                                         max(T_select, T_merge) — so
                                         'auto auto' can pick a larger
                                         B than serial pricing would).
                                         The resolved order is stamped
                                         into the manifest/'plan'/
                                         'bucket' records and carried
                                         by ``report history`` /
                                         ``report regress``; 'report
                                         attr' measures the realized
                                         overlap_frac from the trace.

Observability flags (obs subsystem — no reference equivalent; the
reference's only telemetry was text logs):

    --obs-counters / --no-obs-counters   on-device compression counters
                                         (achieved density, tau, grad/
                                         residual norms, wire bytes) as
                                         per-step "obs" records (default on)
    --obs-interval N                     log "obs" every N steps (step k is
                                         read after step k+1 is queued, so
                                         the chip does not wait for it)
    --obs-layers                         per-layer compression telemetry
                                         (density, tau, norms, residual
                                         age, mass-capture m(k)) as one
                                         "layers" record per layer per obs
                                         step (default off; adds [L]-sized
                                         optimizer state)
    --obs-audit-interval N               every N steps, audit the
                                         production top-k selection against
                                         the exact top-k (recall in the
                                         "obs" record's audit_recall;
                                         0 = off)
    --obs-watchdog SECONDS               dispatch stall watchdog: fail fast
                                         with a structured diagnostic (exit
                                         43) instead of hanging forever on
                                         a device that stopped answering
                                         (0 = off)
    --obs-events / --no-obs-events       online anomaly monitor over the
                                         synced loss/telemetry (NaN/Inf
                                         loss, EWMA loss spike, density
                                         collapse vs rho, residual blow-up
                                         and age runaway) emitting fsync'd
                                         severity-tagged "event" records
                                         (default on)
    --obs-halt-on {error,warn}           fail fast (exit 44) when an
                                         anomaly event of at least this
                                         severity fires; default: record
                                         only, never halt
    --obs-timeline PATH                  write the host-side Chrome-trace
                                         timeline (Tracer spans, telemetry
                                         counter tracks, event/stall
                                         markers) to PATH on exit — open
                                         in chrome://tracing or Perfetto
    --obs-export-port PORT               serve the latest metric values as
                                         OpenMetrics text on localhost
                                         (curl localhost:PORT/metrics);
                                         0 = off (default), -1 = ephemeral
    --obs-calib / --no-obs-calib         live comm-model calibration
                                         (obs.calib): profile-attribute a
                                         dispatch every
                                         --obs-calib-interval steps, fit
                                         alpha/beta online from measured
                                         (wire_bytes, t_comm) with a
                                         robust (median-of-slopes)
                                         estimator; 'calib' records per
                                         refit, comm_model_drift anomaly
                                         vs the planner's inputs, and an
                                         end-of-run calib_fit_{P}proc
                                         .json artifact the next run's
                                         planner consumes (default off —
                                         each sample costs a capture)
    --obs-calib-interval N               steps between calibration
                                         captures (default 25)
    --obs-critpath / --no-obs-critpath   per-step stage-interval records
                                         (obs.critpath): profile-attribute
                                         a dispatch every
                                         --obs-calib-interval steps
                                         (shares the calibrator's capture
                                         when both are on) into ordered
                                         {stage, t0, t1} segments with
                                         the comm span split into wire
                                         vs skew-wait by the ledger's
                                         alpha-beta model; one durable
                                         'critpath' record per sample,
                                         joined across ranks by
                                         `report critpath` into the
                                         global critical path
                                         (default off)
    --obs-critpath-shift-windows K       consecutive joined steps whose
                                         critical stage differs from the
                                         established modal stage before
                                         the critpath_shift anomaly
                                         fires (default 3)
    --obs-mem / --no-obs-mem             compile/memory-plane watch
                                         (obs.memwatch): AOT compile
                                         accounting — one fsync'd
                                         "compile" record per distinct
                                         dispatch shape, peak-HBM
                                         estimate stamped into the
                                         manifest — plus jit-cache
                                         recompile tracking
                                         (recompile_storm rule) and
                                         sampled live-memory "mem"
                                         records feeding the
                                         device_mem_leak / hbm_headroom
                                         rules (default off — costs one
                                         AOT compile per dispatch shape)
    --obs-mem-interval N                 steps between live-memory
                                         samples (default 50)
    --obs-recompile-warmup N             compile-watch polls before
                                         recompile_storm arms (default
                                         1; 0 = any cache growth fires)
    --obs-mem-leak-windows K             consecutive growing live-bytes
                                         windows before device_mem_leak
                                         fires (default 3)
    --obs-hbm-headroom-frac F            bytes_in_use/bytes_limit
                                         fraction above which
                                         hbm_headroom fires (default
                                         0.92)
    --obs-goodput / --no-obs-goodput     goodput/badput wall-clock
                                         ledger (obs.goodput): partition
                                         the run's measured wall into
                                         productive step compute vs the
                                         closed badput taxonomy (select,
                                         comm, wait, compile, ckpt,
                                         wasted, degraded, data,
                                         startup), unattributed
                                         remainder surfaced as
                                         other_frac (conservation: the
                                         categories sum to wall by
                                         construction). Pure host
                                         arithmetic at sync points the
                                         loop already pays — default on.
                                         Inspect with 'report goodput'
                                         (per-rank bars, --compare,
                                         --advise eviction hint)
    --obs-goodput-interval N             optimizer steps between
                                         periodic durable 'goodput'
                                         records (default 50; <= 0
                                         keeps only the end-of-run
                                         summary). Each record feeds
                                         the goodput_collapse rule
    --obs-goodput-collapse-windows K     consecutive ledger records
                                         with goodput_frac below half
                                         its own EWMA before the
                                         goodput_collapse anomaly fires
                                         (default 3; honors
                                         --obs-halt-on like every rule)
    --obs-linkmap / --no-obs-linkmap     per-(axis, peer) network
                                         weather map (obs.linkmap):
                                         carve each calibration
                                         capture's measured comm span
                                         over the schedule's
                                         round->peer join, keep EWMA
                                         latency/bandwidth per link,
                                         log one durable 'linkmap'
                                         record per capture. Needs
                                         --obs-calib (rides its
                                         cadence); default off.
                                         Inspect with 'report linkmap'
    --obs-link-degraded-x X              one link's EWMA latency above
                                         X times the fleet median
                                         counts as a degraded window
                                         (default 4.0)
    --obs-link-degraded-windows K        consecutive degraded windows
                                         before the link_degraded
                                         anomaly fires (default 3; a
                                         recovered window re-arms;
                                         honors --obs-halt-on)
    --obs-forecast / --no-obs-forecast   scale-out forecast plane
                                         (obs.forecast): hindcast the
                                         analytic step model against
                                         this run each calibration
                                         capture, forecast step time /
                                         goodput at the P targets
                                         across schedules and axis
                                         trees, one durable 'forecast'
                                         record per capture. Needs
                                         --obs-calib (rides its
                                         cadence); default off.
                                         Inspect with 'report forecast'
    --obs-forecast-targets LIST          comma-separated modeled worker
                                         counts the forecast grid
                                         prices (default 32,256,1024)
    --obs-forecast-drift-x X             hindcast error factor beyond
                                         which a capture counts as
                                         drifted; 3 consecutive drifted
                                         captures fire forecast_drift
                                         (default 4.0; honors
                                         --obs-halt-on)
    --registry DIR                       append one summary line per run
                                         to DIR/runs.jsonl (obs.registry:
                                         manifest header + steps/sec,
                                         comm ratio, fitted alpha/beta,
                                         recall floor, wire bytes/step);
                                         read back with 'report history' /
                                         'report regress'
    --comm-model-fit PATH                explicit alpha/beta artifact
                                         (dcn_probe_*.json or
                                         calib_fit_*.json) pricing the
                                         comm planner, with the filename
                                         stamped as fit provenance in the
                                         manifest and the decided
                                         schedule pinned into the
                                         optimizer

Resilience flags (gtopkssgd_tpu/resilience — turn detect-and-halt into
detect-and-recover):

    --inject SPEC                        deterministic step-keyed fault
                                         injection (nan_grad@K,
                                         slow_rank:R:DURs@A-B,
                                         loader_raise@K, preempt@K,
                                         corrupt_ckpt@latest, reshape@K
                                         — a changed dispatch shape
                                         that forces a retrace)
    --recover-policy POLICY              rule=action[:budget[:param]] maps
                                         anomaly rules to skip / rollback /
                                         degrade instead of exit 44
    --preempt-save / --no-preempt-save   SIGTERM/SIGINT -> emergency
                                         step-granular checkpoint -> exit
                                         45; resume with --resume
    --allow-ckpt-mismatch                restore past a config_hash/state-
                                         digest integrity mismatch
    --elastic / --no-elastic             elastic fleet (resilience/
                                         elastic.py): a membership
                                         change — preemption, a
                                         goodput-advised eviction, or
                                         an injected resize@K:NEWP /
                                         evict_rank:R@K — drains to a
                                         step boundary, emergency-saves
                                         (sidecar meta records the
                                         residual partition width),
                                         rewrites out-dir/elastic.json
                                         (lineage_id + resize_epoch),
                                         logs a durable "resize"
                                         record, and exits 46; relaunch
                                         with --resume --elastic and
                                         the new --nworkers. The resume
                                         re-partitions the dp-sharded
                                         error-feedback residual onto
                                         the new P (grow = zero rows,
                                         shrink = masked-fold addition
                                         conserving the pending
                                         gradient mass) and re-derives
                                         planner/bucketing/calibration
                                         at the new size. Both sides of
                                         a resize must pass --elastic
    --evict-after-windows K              elastic: self-check the merged
                                         per-rank goodput/straggler
                                         view every K goodput windows
                                         and evict the rank
                                         eviction_decision names
                                         (default 3; 0 disables the
                                         automatic check)
    --min-fleet N                        elastic: never resize below N
                                         workers (default 1; a refused
                                         preemption-resize falls back
                                         to classic exit-45 semantics)

Exit codes come from the single-source registry
``gtopkssgd_tpu/exit_codes.py`` (0 ok, 43 stall watchdog, 44 anomaly
halt, 45 preempted-after-save, 46 elastic-resize restart, 99 multihost
designed skip — see that module for the full table; graftlint's
exit-code rule rejects literals minted anywhere else).

Summarize or diff the resulting metrics.jsonl with
``python -m gtopkssgd_tpu.obs.report <out-dir> [<other-out-dir>]``.
Multi-host runs shard metrics per rank (metrics.rank{r}.jsonl); merge
them with ``python -m gtopkssgd_tpu.obs.report fleet <out-dir>`` and
tail a live run with ``... report watch <out-dir>``.
"""

from __future__ import annotations

import argparse
import os
from typing import Callable, Optional, Sequence

import jax

from gtopkssgd_tpu.trainer import TrainConfig, Trainer


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        "gtopkssgd_tpu.dist_trainer",
        description="gTop-k S-SGD training on TPU (SPMD over a dp mesh)",
    )
    p.add_argument("--dnn", default="resnet20")
    p.add_argument("--dataset", default=None)
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-worker batch size (global = batch*nworkers)")
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=None)
    p.add_argument("--nesterov", action="store_true")
    p.add_argument("--compression", default=None,
                   choices=["none", "dense", "gtopk", "allgather", "topk",
                            "gtopk_hier", "gtopk_layerwise"],
                   help="None/dense = psum baseline; gtopk = tree sparse "
                        "allreduce; allgather/topk = DGC-style union; "
                        "gtopk_layerwise = per-layer top-k + per-layer "
                        "error feedback (flat gradient never materializes); "
                        "gtopk_hier = dense within ICI slice, gtopk across "
                        "slices (set --hier-ici)")
    p.add_argument("--density", type=float, default=0.001)
    p.add_argument("--hier-ici", type=int, default=1,
                   help="gtopk_hier: devices per ICI slice (dense psum "
                        "within each contiguous block of this many ranks, "
                        "gTop-k hypercube across the nworkers/hier_ici "
                        "slices)")
    p.add_argument("--topk-method", default="auto",
                   choices=["auto", "exact", "blockwise", "approx",
                            "threshold", "pallas", "twostage",
                            "simrecall"])
    p.add_argument("--wire-codec", default="fp32",
                   help="on-wire sparse-set codec for every exchange "
                        "round: fp32 (identity), int8[:BLOCK] or "
                        "fp8[:BLOCK] (block-scaled values, bf16 scales, "
                        "Elias-Fano bitpacked indices; BLOCK defaults "
                        "to 64). Quantization error folds into the "
                        "error-feedback residual")
    p.add_argument("--comm-plan", default="auto",
                   help="wire-plan pin (parallel.planner). 'auto' "
                        "(default) scores every schedule that realizes "
                        "--compression with the alpha-beta model "
                        "(dcn_probe fit when present) and keeps the "
                        "historical schedule on ties; a plan name pins "
                        "it: tree | balanced (Ok-Topk split-and-reduce) "
                        "for gtopk/gtopk_layerwise, allgather / hier / "
                        "dense for their modes. Decision is logged as "
                        "the 'plan' record (``report plan``) and "
                        "stamped into the run manifest")
    p.add_argument("--buckets", default="concat",
                   help="gtopk_layerwise only: gradient bucketing "
                        "(parallel.bucketing). 'concat' (default) keeps "
                        "the historical wire — per-leaf selection, one "
                        "concatenated merge; 'leaf' runs one merge per "
                        "param leaf; an int B or 'auto' partitions the "
                        "leaves into contiguous byte-balanced buckets "
                        "('auto' picks B itself) by an exact alpha-beta "
                        "DP — cost B*alpha + wire_bytes/beta — and runs "
                        "one fused selection + one codec-framed merge "
                        "per bucket. Boundaries are stamped into the "
                        "manifest and logged as the 'bucket' record "
                        "(``report plan`` prints them)")
    p.add_argument("--pipeline", default="serial",
                   help="bucketed layerwise only: bucket execution "
                        "order. 'serial' (default) pins the paper's "
                        "sequential select->merge chain; 'overlap' "
                        "double-buffers the stages so bucket b+1's "
                        "selection runs under bucket b's merge — "
                        "bit-identical to serial; 'auto' picks the "
                        "cheaper modeled span and prices --buckets "
                        "auto with the overlap objective. Requires a "
                        "bucketed wire (--buckets != concat) for "
                        "'overlap'")
    p.add_argument("--clip-grad-norm", type=float, default=None)
    p.add_argument("--steps-per-dispatch", type=int, default=1,
                   help="optimizer steps per jitted dispatch (lax.scan "
                        "on-device); >1 amortizes per-step dispatch "
                        "cost for small models")
    p.add_argument("--nsteps-update", type=int, default=1,
                   help="gradient accumulation micro-steps per comm round")
    p.add_argument("--max-epochs", type=int, default=140)
    p.add_argument("--warmup-epochs", type=int, default=0,
                   help="linear LR ramp over the first N epochs")
    p.add_argument("--dense-warmup-epochs", type=int, default=0,
                   help="sparse modes: communicate dense for the first N "
                        "epochs before enabling top-k (warm-up training)")
    p.add_argument("--momentum-correction", action="store_true",
                   help="sparse modes: DGC momentum correction + factor "
                        "masking — velocity accumulates locally BEFORE "
                        "selection (arXiv:1712.01887 s3, TPU extension)")
    p.add_argument("--nworkers", type=int, default=0,
                   help="mesh size (0 = all visible devices)")
    p.add_argument("--data-dir", default=None)
    p.add_argument("--out-dir", default=None)
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--model-preset", default=None,
                   help="the decoders' sizes: qwen3_next takes 80b_a3b_ep64 "
                        "or tiny, keye_vl2 takes 30b_a3b_ep16 or tiny, "
                        "trinity_mini takes 26b_a3b_ep16 or tiny, kanana2 "
                        "takes 30b_a3b_ep16 or tiny, ouro takes 2p6b_l5 "
                        "or tiny, sdar takes 30b_a3b_ep8 or tiny, kimi_linear "
                        "takes 48b_a3b_ep32 or tiny (each model's PRESETS); another name, or a model that "
                        "has no presets, is an error")
    p.add_argument("--s2d", action="store_true",
                   help="resnet50: space-to-depth stem (4x4x12 conv on 2x2 "
                        "pixel blocks; a superset of the 7x7x3 map — exact "
                        "embedding test-pinned — at MXU-friendly channel "
                        "width)")
    p.add_argument("--num-iters", type=int, default=None,
                   help="train a fixed number of steps instead of epochs")
    p.add_argument("--synth-hard", action="store_true",
                   help="synthetic CIFAR only: the discriminative variant "
                        "(weak spatial class signal + 10%% train label "
                        "noise; data/cifar.py) — arms can separate on "
                        "val accuracy instead of saturating at 1.0")
    p.add_argument("--eval-batches", type=int, default=None)
    p.add_argument("--log-interval", type=int, default=50)
    p.add_argument("--prefetch", type=int, default=2,
                   help="host batches assembled ahead by a background "
                        "thread (0 = synchronous assembly)")
    p.add_argument("--decode-workers", type=int, default=0,
                   help="ImageNet real-file path: decode worker processes "
                        "(reference DataLoader num_workers; ~280 img/s per "
                        "core vs ~6.8k img/s per v5e chip at bs=128 — see "
                        "benchmarks/results/input_path_1core_host.json)")
    p.add_argument("--obs-counters", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="on-device compression/comm counters logged as "
                        "per-step 'obs' records (--no-obs-counters traces "
                        "the step exactly as before the obs subsystem)")
    p.add_argument("--obs-interval", type=int, default=1,
                   help="log an 'obs' record every N optimizer steps; "
                        "step k's counters are read after step k+1 is "
                        "queued, so the chip does not wait for the read "
                        "(it waits under --recover-policy, --inject, "
                        "--obs-halt-on, --obs-mem and --elastic)")
    p.add_argument("--obs-layers", action=argparse.BooleanOptionalAction,
                   default=False,
                   help="per-layer compression-quality telemetry "
                        "(obs.counters.LAYER_FIELDS) logged as one "
                        "'layers' record per layer per obs step; opt-in "
                        "because it adds [L]-sized optimizer state "
                        "(checkpoint treedef change) and a few segment "
                        "reductions to the jitted step")
    p.add_argument("--obs-audit-interval", type=int, default=0,
                   help="every N optimizer steps, audit the production "
                        "top-k selection against the exact top-k of the "
                        "accumulator (ops.topk exact path); recall lands "
                        "in the 'obs' record's audit_recall field "
                        "(-1 = never audited); 0 disables")
    p.add_argument("--obs-watchdog", type=float, default=0.0,
                   help="seconds a dispatched step may go without host-"
                        "visible progress before the stall watchdog dumps "
                        "a structured diagnostic and exits 43 (0 = off); "
                        "set well above log-interval * step time")
    p.add_argument("--obs-events", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="online anomaly monitor (obs.events): NaN/Inf "
                        "loss, EWMA loss spike, achieved-density collapse "
                        "vs rho, residual blow-up/age runaway — each "
                        "firing logs a severity-tagged fsync'd 'event' "
                        "record at the obs/log sync points (no extra "
                        "device reads)")
    p.add_argument("--obs-halt-on", default=None,
                   choices=["error", "warn"],
                   help="fail fast when an anomaly event of at least this "
                        "severity fires: the event record is flushed, "
                        "then the run exits 44 (the stall watchdog owns "
                        "43); default records without halting")
    p.add_argument("--obs-timeline", default=None, metavar="PATH",
                   help="write the host-side Chrome-trace timeline "
                        "(obs.timeline: Tracer spans, telemetry counter "
                        "tracks, event/stall markers) here on exit; view "
                        "in chrome://tracing or ui.perfetto.dev. Rebuild "
                        "one later from metrics.jsonl with 'python -m "
                        "gtopkssgd_tpu.obs.report timeline <out-dir>'")
    p.add_argument("--obs-export-port", type=int, default=0,
                   help="serve the latest metric values as OpenMetrics "
                        "text on this localhost HTTP port "
                        "(obs.exporter; curl localhost:PORT/metrics); "
                        "0 disables (default), -1 binds an ephemeral "
                        "port (logged at startup)")
    p.add_argument("--obs-calib", action=argparse.BooleanOptionalAction,
                   default=False,
                   help="live comm-model calibration (obs.calib): every "
                        "--obs-calib-interval steps, profile-attribute "
                        "one dispatch and feed measured (wire_bytes, "
                        "t_comm) to an online robust alpha/beta fitter — "
                        "'calib' records per refit, a comm_model_drift "
                        "anomaly when the live fit diverges from the "
                        "planner's inputs, and an end-of-run "
                        "calib_fit_{P}proc.json artifact in out-dir that "
                        "the next run's planner can consume. Opt-in: "
                        "each sample costs a profiler capture + sync")
    p.add_argument("--obs-calib-interval", type=int, default=25,
                   help="optimizer steps between calibration captures")
    p.add_argument("--obs-critpath",
                   action=argparse.BooleanOptionalAction, default=False,
                   help="per-step stage-interval records (obs.critpath): "
                        "every --obs-calib-interval steps, "
                        "profile-attribute one dispatch into ordered "
                        "{stage, t0, t1} segments, splitting the comm "
                        "span into wire vs skew-wait via the ledger's "
                        "alpha-beta model, and log a durable 'critpath' "
                        "record; `report critpath` joins the per-rank "
                        "records into the global critical path. Opt-in: "
                        "each sample costs a profiler capture + sync")
    p.add_argument("--obs-critpath-shift-windows", type=int, default=3,
                   help="consecutive joined steps whose critical stage "
                        "differs from the established modal stage "
                        "before the critpath_shift anomaly fires")
    p.add_argument("--obs-mem", action=argparse.BooleanOptionalAction,
                   default=False,
                   help="compile/memory-plane watch (obs.memwatch): AOT "
                        "compile accounting — one fsync'd 'compile' record "
                        "per distinct dispatch shape (cost/memory analysis, "
                        "lower/compile wall times) with the peak-HBM "
                        "estimate stamped into the manifest — plus jit "
                        "executable-cache recompile tracking (the "
                        "recompile_storm rule) and sampled live-memory "
                        "'mem' records (jax.live_arrays + per-device "
                        "memory_stats) feeding device_mem_leak / "
                        "hbm_headroom. Opt-in: costs one ahead-of-time "
                        "compile per dispatch shape at startup")
    p.add_argument("--obs-mem-interval", type=int, default=50,
                   help="optimizer steps between live-memory samples")
    p.add_argument("--obs-recompile-warmup", type=int, default=1,
                   help="compile-watch polls before recompile_storm arms "
                        "(0 fires on any executable-cache growth; the "
                        "default tolerates the first real dispatch)")
    p.add_argument("--obs-mem-leak-windows", type=int, default=3,
                   help="consecutive growing live-memory windows before "
                        "device_mem_leak fires")
    p.add_argument("--obs-hbm-headroom-frac", type=float, default=0.92,
                   help="bytes_in_use/bytes_limit fraction above which "
                        "hbm_headroom fires (backends without "
                        "memory_stats never trip it)")
    p.add_argument("--obs-goodput", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="goodput/badput wall-clock ledger (obs.goodput): "
                        "partition measured wall into productive step "
                        "compute vs the badput taxonomy (select/comm/"
                        "wait/compile/ckpt/wasted/degraded/data/startup) "
                        "with the unattributed remainder surfaced as "
                        "other_frac; cumulative durable 'goodput' "
                        "records + an end-of-run summary. Host-side "
                        "arithmetic only — default on; inspect with "
                        "'report goodput'")
    p.add_argument("--obs-goodput-interval", type=int, default=50,
                   help="optimizer steps between periodic durable "
                        "'goodput' records (<= 0 keeps only the "
                        "end-of-run summary); each record feeds the "
                        "goodput_collapse rule")
    p.add_argument("--obs-goodput-collapse-windows", type=int, default=3,
                   help="consecutive ledger records with goodput_frac "
                        "below half its own EWMA before goodput_collapse "
                        "fires (honors --obs-halt-on)")
    p.add_argument("--obs-linkmap", action=argparse.BooleanOptionalAction,
                   default=False,
                   help="per-(axis, peer) network weather map "
                        "(obs.linkmap): carve each calibration capture's "
                        "measured comm span over the schedule's "
                        "round->peer join into per-link EWMA latency/"
                        "bandwidth, one durable 'linkmap' record per "
                        "capture, feeding the link_degraded rule. Needs "
                        "--obs-calib (rides its cadence); inspect with "
                        "'report linkmap'")
    p.add_argument("--obs-link-degraded-x", type=float, default=4.0,
                   help="one link's EWMA latency above this multiple of "
                        "the fleet median counts as a degraded window")
    p.add_argument("--obs-link-degraded-windows", type=int, default=3,
                   help="consecutive degraded windows before "
                        "link_degraded fires (a recovered window "
                        "re-arms; honors --obs-halt-on)")
    p.add_argument("--obs-forecast",
                   action=argparse.BooleanOptionalAction, default=False,
                   help="scale-out forecast plane (obs.forecast): "
                        "hindcast the analytic step model against this "
                        "run each calibration capture, then forecast "
                        "step time/goodput at the P targets across "
                        "schedules and axis trees — one durable "
                        "'forecast' record per capture, feeding the "
                        "forecast_drift rule. Needs --obs-calib (rides "
                        "its cadence); inspect with 'report forecast'")
    p.add_argument("--obs-forecast-targets", default="32,256,1024",
                   metavar="LIST",
                   help="comma-separated modeled worker counts the "
                        "forecast grid prices")
    p.add_argument("--obs-forecast-drift-x", type=float, default=4.0,
                   help="hindcast error factor beyond which a capture "
                        "counts as drifted; 3 consecutive drifted "
                        "captures fire forecast_drift (honors "
                        "--obs-halt-on)")
    p.add_argument("--registry", default=None, metavar="DIR",
                   help="append this run's summary line (manifest subset "
                        "+ steps/sec, comm ratio, fitted alpha/beta, "
                        "recall floor, wire bytes/step) to DIR/runs.jsonl "
                        "on exit (obs.registry); inspect offline with "
                        "'report history DIR' and gate with 'report "
                        "regress OUT_DIR --registry DIR'")
    p.add_argument("--comm-model-fit", default=None, metavar="PATH",
                   help="explicit alpha/beta fit artifact (a dcn_probe_*"
                        ".json or calib_fit_*.json) pricing the comm "
                        "planner instead of the probe-dir lookup; the "
                        "filename lands in the manifest/plan record as "
                        "fit provenance and the decided schedule is "
                        "pinned through to the optimizer. A malformed "
                        "file fails at startup")
    p.add_argument("--inject", default=None, metavar="SPEC",
                   help="step-keyed fault injection (resilience subsystem; "
                        "grammar KIND[:ARG...]@STEP|A-B|latest, comma-"
                        "separated): nan_grad@120 poisons the gradient at "
                        "step 120; slow_rank:2:2.5s@50-60 sleeps 2.5s per "
                        "step on rank 2; loader_raise@75 raises from the "
                        "data loader; preempt@200 delivers SIGTERM; "
                        "corrupt_ckpt@latest truncates the newest "
                        "checkpoint before restore; reshape@9 halves the "
                        "batch axis of step 9's host batch (forces a "
                        "retrace — recompile-storm chaos). Deterministic, "
                        "so chaos runs reproduce in CI")
    p.add_argument("--recover-policy", default=None, metavar="POLICY",
                   help="map anomaly rules to recovery actions instead of "
                        "exit 44 (grammar rule=action[:budget[:param]], "
                        "comma-separated; actions: skip, rollback, "
                        "degrade) — e.g. 'nan_loss=skip,"
                        "density_collapse=degrade:2:100'. Requires "
                        "--obs-events; unmapped rules keep halt semantics")
    p.add_argument("--allow-ckpt-mismatch", action="store_true",
                   help="restore a checkpoint whose recorded config_hash/"
                        "state digest disagrees with this run's (normally "
                        "refused: resuming under different flags silently "
                        "changes the experiment)")
    p.add_argument("--elastic", action=argparse.BooleanOptionalAction,
                   default=False,
                   help="elastic fleet (resilience/elastic.py): treat "
                        "membership changes (preemption, goodput-"
                        "advised eviction, injected resize@K:NEWP) as "
                        "a drain + checkpoint + lineage rewrite + exit "
                        "46 resize instead of run death; relaunch with "
                        "--resume --elastic at the new --nworkers and "
                        "the dp-sharded residual is re-partitioned "
                        "onto the new fleet (both sides of a resize "
                        "need this flag)")
    p.add_argument("--evict-after-windows", type=int, default=3,
                   help="elastic: self-check the merged per-rank "
                        "goodput/straggler view every this-many "
                        "--obs-goodput-interval windows and evict the "
                        "rank eviction_decision names (0 disables the "
                        "automatic check; injected evict_rank:R@K "
                        "still works)")
    p.add_argument("--min-fleet", type=int, default=1,
                   help="elastic: never resize below this many workers "
                        "(a preemption-resize that would falls back to "
                        "classic exit-45 preempt semantics)")
    p.add_argument("--preempt-save", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="intercept SIGTERM/SIGINT: forced step-granular "
                        "emergency checkpoint, then exit 45 (resume with "
                        "--resume); --no-preempt-save keeps the default "
                        "signal disposition")
    p.add_argument("--resume", action="store_true",
                   help="restore the latest checkpoint from out-dir")
    p.add_argument("--multihost", action="store_true",
                   help="call jax.distributed.initialize() first")
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace (TensorBoard/Perfetto"
                        " format) of --profile-steps early training steps")
    p.add_argument("--profile-steps", type=int, default=10)
    return p


def config_from_args(args: argparse.Namespace) -> TrainConfig:
    nworkers = args.nworkers or jax.device_count()
    return TrainConfig(
        dnn=args.dnn,
        dataset=args.dataset,
        batch_size=args.batch_size,
        lr=args.lr,
        momentum=args.momentum,
        weight_decay=args.weight_decay,
        nesterov=args.nesterov,
        compression=args.compression,
        density=args.density,
        hier_ici=args.hier_ici,
        topk_method=args.topk_method,
        wire_codec=args.wire_codec,
        comm_plan=args.comm_plan,
        buckets=args.buckets,
        pipeline=args.pipeline,
        clip_grad_norm=args.clip_grad_norm,
        nsteps_update=args.nsteps_update,
        steps_per_dispatch=args.steps_per_dispatch,
        warmup_epochs=args.warmup_epochs,
        dense_warmup_epochs=args.dense_warmup_epochs,
        momentum_correction=args.momentum_correction,
        max_epochs=args.max_epochs,
        nworkers=nworkers,
        data_dir=args.data_dir,
        out_dir=args.out_dir,
        seed=args.seed,
        dtype=args.dtype,
        model_preset=args.model_preset,
        space_to_depth=args.s2d,
        synth_hard=args.synth_hard,
        eval_batches=args.eval_batches,
        log_interval=args.log_interval,
        obs_counters=args.obs_counters,
        obs_interval=args.obs_interval,
        obs_layers=args.obs_layers,
        obs_audit_interval=args.obs_audit_interval,
        obs_watchdog=args.obs_watchdog,
        obs_events=args.obs_events,
        obs_halt_on=args.obs_halt_on,
        obs_timeline=args.obs_timeline,
        obs_export_port=args.obs_export_port,
        obs_calib=args.obs_calib,
        obs_calib_interval=args.obs_calib_interval,
        obs_critpath=args.obs_critpath,
        obs_critpath_shift_windows=args.obs_critpath_shift_windows,
        obs_mem=args.obs_mem,
        obs_mem_interval=args.obs_mem_interval,
        obs_recompile_warmup=args.obs_recompile_warmup,
        obs_mem_leak_windows=args.obs_mem_leak_windows,
        obs_hbm_headroom_frac=args.obs_hbm_headroom_frac,
        obs_goodput=args.obs_goodput,
        obs_goodput_interval=args.obs_goodput_interval,
        obs_goodput_collapse_windows=args.obs_goodput_collapse_windows,
        obs_linkmap=args.obs_linkmap,
        obs_link_degraded_x=args.obs_link_degraded_x,
        obs_link_degraded_windows=args.obs_link_degraded_windows,
        obs_forecast=args.obs_forecast,
        obs_forecast_targets=args.obs_forecast_targets,
        obs_forecast_drift_x=args.obs_forecast_drift_x,
        registry=args.registry,
        comm_model_fit=args.comm_model_fit,
        inject=args.inject,
        recover_policy=args.recover_policy,
        allow_ckpt_mismatch=args.allow_ckpt_mismatch,
        elastic=args.elastic,
        evict_after_windows=args.evict_after_windows,
        min_fleet=args.min_fleet,
        prefetch=args.prefetch,
        decode_workers=args.decode_workers,
    )


def main(argv: Optional[Sequence[str]] = None, *,
         inspect: Optional[Callable[[Trainer], None]] = None) -> int:
    """The CLI entry point. ``inspect``, when given, is called with the
    live Trainer once the run has completed and before it closes — how
    chip_smoke.py and tests look at the state a run through this entry
    point leaves behind (where the parameters live, the compiled step)."""
    from gtopkssgd_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    args = build_argparser().parse_args(argv)
    from gtopkssgd_tpu.exit_codes import EXIT_RESIZE_RESTART
    from gtopkssgd_tpu.resilience import (
        PREEMPT_EXIT_CODE,
        Preempted,
        PreemptionGuard,
        ResizeRestart,
        describe_policy,
        retry_call,
    )

    if args.multihost:
        # Multi-host pod slice / multislice: one process per host, same SPMD
        # program; ICI inside a slice, DCN across slices — both are just the
        # 'dp' axis to the program (reference: MPI.COMM_WORLD over ethernet).
        # Coordinator rendezvous races at pod startup (hosts come up in
        # arbitrary order) — the shared retry helper absorbs them.
        retry_call(jax.distributed.initialize, retries=3, delay=2.0,
                   desc="jax.distributed.initialize")
        # Announce this process's fleet identity up front — the same
        # process_index/count/coordinator triple lands in each shard's
        # run manifest (obs/manifest.py), which is how the fleet merger
        # validates that shards being merged belong to one run.
        from gtopkssgd_tpu.obs.manifest import coordinator_address

        print(f"[dist] process {jax.process_index()}/"
              f"{jax.process_count()} coordinator="
              f"{coordinator_address()} recovery="
              f"{describe_policy(args.recover_policy)}", flush=True)
    else:
        # The resolved policy is part of the run's identity — print it
        # where the operator (and the log scraper) will find it.
        print(f"[dist] recovery policy: "
              f"{describe_policy(args.recover_policy)}", flush=True)
    from gtopkssgd_tpu.obs.events import HALT_EXIT_CODE, AnomalyHalt

    with Trainer(config_from_args(args)) as trainer:
        guard = None
        if args.preempt_save:
            guard = PreemptionGuard(logger=trainer.logger).install()
            trainer.preempt = guard
        try:
            rc = _run(args, trainer)
            trainer.finalize_resilience("completed")
            if inspect is not None:
                inspect(trainer)
            return rc
        except AnomalyHalt as halt:
            # The monitor flushed the event record before raising; this
            # path only reports and maps to the contract exit code.
            trainer.logger.error("anomaly halt: %s", halt)
            trainer.finalize_resilience("halted")
            return HALT_EXIT_CODE
        except Preempted as why:
            # Emergency checkpoint already durable (_preempt_now saved
            # before raising); the exit code tells the harness to
            # relaunch with --resume.
            trainer.logger.warning("preempted: %s", why)
            trainer.finalize_resilience("preempted")
            return PREEMPT_EXIT_CODE
        except ResizeRestart as why:
            # Checkpoint, lineage file, and the durable "resize" record
            # all landed before the raise (_resize_now's contract); the
            # exit code tells the supervisor to relaunch at the new P
            # with --resume --elastic and the new --nworkers.
            trainer.logger.warning("elastic resize: %s", why)
            trainer.finalize_resilience("resized")
            return EXIT_RESIZE_RESTART
        finally:
            if guard is not None:
                guard.close()


def _run(args: argparse.Namespace, trainer: Trainer) -> int:
    if args.resume:
        restored = trainer.restore()
        trainer.logger.info("resume: %s",
                            "restored" if restored else "fresh")
    if args.profile_dir:
        # SURVEY.md §5 tracing: the reference only had host timer
        # dicts; here a real jax.profiler device trace complements
        # them, with the traced steps' spans beside it as spans.json
        # (obs.tracing.profile). One dispatch first so compilation
        # stays out of the trace; step counts round up to whole
        # dispatches so the path composes with --steps-per-dispatch.
        from gtopkssgd_tpu.obs import tracing

        spd = trainer.cfg.steps_per_dispatch
        warm = spd
        traced = max(spd, -(-args.profile_steps // spd) * spd)
        trainer.train(warm)
        with tracing.profile(args.profile_dir):
            trainer.train(traced)
        trainer.logger.info("profiler: %d-step trace + spans.json -> %s",
                            traced, args.profile_dir)
    if args.num_iters is not None:
        stats = trainer.train(args.num_iters)
        stats.update(trainer.test())
    else:
        stats = trainer.fit()
    trainer.logger.info("done: %s", stats)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
