"""Trainer (reference L3: dl_trainer.py::DLTrainer) — builds model, data,
and optimizer from flag-equivalent config, owns the jitted SPMD train step,
the eval loops, LR schedules, gradient accumulation, and checkpointing.

Reference parity map (SURVEY.md C1):
  DLTrainer(dnn, dataset, batch_size, ...)  -> Trainer(TrainConfig(...))
  .train(n_iters)                           -> .train(n_iters)
  .test()                                   -> .test()
  per-dataset LR step schedules             -> _lr_schedule()
  grad accumulation (nsteps_update)         -> micro-batch lax.scan in-step
  checkpoint save (params only, rank 0)     -> Orbax save of FULL TrainState
                                               (params, batch_stats, opt
                                               state incl. residual, step)

TPU-native redesign: the reference runs P processes each owning one GPU and
a background comm thread; here ONE process traces ONE SPMD train step over
the whole `dp` mesh axis. The global batch is assembled host-side as
[P, B, ...] (per-rank shards from the same DataPartitioner semantics) and
sharded over the axis; compression + the gtopk collective run inside the
step via the optimizer transform; BatchNorm running stats are pmean'd so
the replicated state stays bit-identical (the reference let per-rank stats
drift and checkpointed rank 0's).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from gtopkssgd_tpu import native
from gtopkssgd_tpu.data import get_dataset
from gtopkssgd_tpu.data.cifar import CIFAR_MEAN, CIFAR_STD
from gtopkssgd_tpu.data.imagenet import IMAGENET_MEAN, IMAGENET_STD
from gtopkssgd_tpu.models import get_model
from gtopkssgd_tpu.optimizer import (
    GTopKSGDState,
    expand_residual_per_device,
    flat_residual,
    gtopk_sgd,
    leaf_form_state,
    leaf_plan,
    slab_residual,
)
from gtopkssgd_tpu.obs import (
    AnomalyMonitor,
    StallWatchdog,
    Thresholds,
    TimelineRecorder,
    Tracer,
    layer_names,
    model_counters,
    model_scalars,
    readable_counters,
    telemetry_scalars,
)
from gtopkssgd_tpu.obs.manifest import config_hash, run_manifest
from gtopkssgd_tpu.obs.watchdog import _default_on_stall
from gtopkssgd_tpu.parallel import make_mesh
from gtopkssgd_tpu.utils import (
    CheckpointManager,
    MetricsLogger,
    Prefetcher,
    get_logger,
)


@dataclasses.dataclass
class TrainConfig:
    """Flag set matching the reference entrypoints (SURVEY.md §5 config):
    --dnn --dataset --batch-size --lr --nworkers --density --compression
    --nsteps-update --data-dir --max-epochs, plus TPU-specific knobs."""

    dnn: str = "resnet20"
    dataset: Optional[str] = None  # default: the model's canonical dataset
    batch_size: int = 32           # per-worker (global = batch_size*nworkers)
    lr: Optional[float] = None     # default per dataset
    momentum: float = 0.9
    weight_decay: Optional[float] = None  # default per dataset
    nesterov: bool = False
    compression: Optional[str] = None     # None/'dense'|'gtopk'|'allgather'
                                          # |'gtopk_hier' (TPU extension)
    density: float = 0.001
    hier_ici: int = 1              # gtopk_hier: devices per ICI slice (dense
                                   # psum within, gtopk across slices)
    topk_method: str = "auto"
    wire_codec: str = "fp32"       # on-wire sparse-set encoding for every
                                   # exchange round (parallel.codec grammar:
                                   # fp32 | int8[:BLOCK] | fp8[:BLOCK])
    comm_plan: str = "auto"        # wire-plan pin (parallel.planner):
                                   # 'auto' scores candidates with the
                                   # alpha-beta model; a plan name (tree |
                                   # balanced | allgather | hier | dense)
                                   # pins the schedule for this mode
    buckets: str = "concat"        # gtopk_layerwise only: gradient
                                   # bucketing (parallel.bucketing grammar:
                                   # concat | leaf | auto | an int B).
                                   # 'concat' = historical single-merge
                                   # wire; 'leaf' = one merge per param
                                   # leaf; 'auto'/B = alpha-beta-optimal
                                   # byte-balanced contiguous buckets
    pipeline: str = "serial"       # bucketed layerwise only: bucket
                                   # execution order (modes.PIPELINES +
                                   # 'auto'). 'serial' = the paper's
                                   # sequential select->merge chain;
                                   # 'overlap' = double-buffered stages
                                   # (bucket b+1's selection runs under
                                   # bucket b's merge), bit-identical;
                                   # 'auto' = cheaper modeled span wins
    clip_grad_norm: Optional[float] = None  # default: LSTMs clip (ref §3.4)
    nsteps_update: int = 1
    warmup_epochs: int = 0         # linear LR ramp over the first N epochs
                                   # (large-batch warm-up, Goyal-style)
    dense_warmup_epochs: int = 0   # sparse modes: communicate DENSE for the
                                   # first N epochs, then switch to top-k
                                   # (reference C6 warm-up trick / DGC
                                   # warm-up training, arXiv:1712.01887)
    momentum_correction: bool = False  # sparse modes: DGC momentum
                                   # correction + factor masking (velocity
                                   # accumulates BEFORE selection;
                                   # arXiv:1712.01887 §3, TPU extension)
    max_epochs: int = 140
    nworkers: int = 1
    data_dir: Optional[str] = None
    out_dir: Optional[str] = None
    seed: int = 42
    dtype: str = "float32"         # compute dtype: 'float32' | 'bfloat16'
    model_preset: Optional[str] = None  # the decoders only (qwen3_next:
                                   # '80b_a3b_ep64', 'tiny'; keye_vl2:
                                   # '30b_a3b_ep16', 'tiny'; trinity_mini:
                                   # '26b_a3b_ep16', 'tiny'; kanana2:
                                   # '30b_a3b_ep16', 'tiny'; ouro:
                                   # '2p6b_l5', 'tiny'; sdar:
                                   # '30b_a3b_ep8', 'tiny'; kimi_linear:
                                   # '48b_a3b_ep32', 'tiny'): which of the
                                   # model's PRESETS to build, the
                                   # published sizes as one chip's share
                                   # of an expert group (ouro: the first
                                   # layers of its stack) or the tests'
                                   # size (ModelSpec.presets; any other
                                   # name, or any name for a model without
                                   # presets, fails at construction);
                                   # None = the model's default
    space_to_depth: bool = False   # resnet50: MXU-friendly s2d stem (same
                                   # linear map as the 7x7/2 conv; see
                                   # models/resnet.py and the equivalence
                                   # test)
    eval_batches: Optional[int] = None   # cap eval batches (None = full)
    synth_hard: bool = False       # synthetic CIFAR only: the
                                   # discriminative variant (weak spatial
                                   # class patterns + 10% train label
                                   # noise) — see data/cifar.py::_synthetic;
                                   # no effect with real data present
    log_interval: int = 50
    obs_counters: bool = True      # on-device training-health counters
                                   # (obs.counters: achieved density, tau,
                                   # grad/residual norms, wire bytes)
                                   # computed inside the jitted step and
                                   # logged as "obs" records; off -> the
                                   # step traces identically to pre-obs
    obs_interval: int = 1          # log an "obs" record every N optimizer
                                   # steps. The read of step k's counters
                                   # blocks after step k+1 is queued, so
                                   # the chip does not wait for it; it
                                   # blocks on the newest step only under
                                   # a recovery policy, an injector,
                                   # obs_halt_on, obs_mem or elastic
                                   # (something may act on the reading
                                   # before the next dispatch): raise
                                   # this there to keep the overlap
    obs_layers: bool = False       # per-layer compression-quality
                                   # telemetry (obs.counters.LAYER_FIELDS:
                                   # density, tau, grad/residual norms,
                                   # mean residual age, mass-capture
                                   # m(k)), logged as one "layers" record
                                   # per layer per obs step. Opt-in: it
                                   # adds [L]-sized state (a treedef
                                   # change checkpoints from default runs
                                   # would not restore into) and a few
                                   # segment reductions to the step.
                                   # Requires obs_counters.
    obs_audit_interval: int = 0    # every N optimizer steps, audit the
                                   # production top-k selection against
                                   # the exact top-k of the accumulator
                                   # (ops.topk exact path as ground
                                   # truth); recall lands in the "obs"
                                   # record's audit_recall (-1 = never
                                   # audited). 0 disables. Requires
                                   # obs_counters.
    obs_watchdog: float = 0.0      # seconds a dispatched step may go
                                   # without host-visible progress before
                                   # the stall watchdog dumps a diagnostic
                                   # and fails fast (obs.watchdog, exit
                                   # code 43); 0 disables. Set it well
                                   # above log_interval * step_time: the
                                   # heartbeat fires on blocking reads
                                   # (obs/log records, the end-of-train
                                   # sync), not on async enqueues
    obs_events: bool = True        # online anomaly monitor (obs.events)
                                   # over the synced loss/telemetry:
                                   # NaN/Inf loss, EWMA loss spike,
                                   # density collapse vs rho, residual
                                   # blow-up/age runaway — severity-
                                   # tagged "event" records, fsync'd.
                                   # Piggybacks on reads the loop already
                                   # does (obs/log intervals); never adds
                                   # a device sync.
    obs_halt_on: Optional[str] = None  # "error" | "warn": raise
                                   # AnomalyHalt (dist_trainer exit 44)
                                   # when an event of at least this
                                   # severity fires; None = record only
    obs_timeline: Optional[str] = None  # write the host-side Chrome-
                                   # trace timeline (obs.timeline: Tracer
                                   # spans, telemetry counter tracks,
                                   # event/stall markers) here on exit
                                   # (a directory gets timeline.json
                                   # appended); None disables
    obs_export_port: int = 0       # serve the latest metric values as
                                   # OpenMetrics text on this localhost
                                   # HTTP port (obs.exporter; curl
                                   # localhost:PORT/metrics). -1 binds an
                                   # ephemeral port (tests); 0 disables.
                                   # Every process exports — scrape each
                                   # host for its own rank's view
    inject: Optional[str] = None   # step-keyed fault injection spec
                                   # (resilience/inject.py grammar:
                                   # KIND[:ARG...]@STEP|A-B|latest,
                                   # comma-separated — e.g.
                                   # "nan_grad@120,preempt@200");
                                   # deterministic, so chaos runs
                                   # reproduce in CI. None disables
    recover_policy: Optional[str] = None  # map anomaly rules to
                                   # recovery actions instead of exit
                                   # 44 (resilience/policy.py grammar:
                                   # rule=action[:budget[:param]] —
                                   # e.g. "nan_loss=skip,
                                   # density_collapse=degrade:2:100").
                                   # Requires obs_events. None = halt
                                   # semantics unchanged
    allow_ckpt_mismatch: bool = False  # restore a checkpoint whose
                                   # recorded config_hash/state digest
                                   # disagrees with this run's (the
                                   # explicit escape hatch; normally a
                                   # mismatched resume is refused)
    elastic: bool = False          # elastic fleet (resilience/
                                   # elastic.py): membership changes
                                   # (preemption, eviction, injected
                                   # resize@K:NEWP) drain + save +
                                   # rewrite the elastic.json lineage
                                   # + exit 46 for a relaunch at the
                                   # new P; resume re-partitions the
                                   # dp-sharded residual onto the new
                                   # mesh. BOTH sides of a resize must
                                   # run with elastic on (the ckpt
                                   # config_hash nulls nworkers only
                                   # under this flag)
    evict_after_windows: int = 3   # elastic: self-check the fleet's
                                   # merged goodput/straggler view
                                   # every this-many obs_goodput
                                   # windows and evict the rank
                                   # eviction_decision names (0
                                   # disables the automatic check;
                                   # injected evict_rank still works)
    min_fleet: int = 1             # elastic: never resize below this
                                   # many workers (an eviction or
                                   # shrink that would is refused and
                                   # degrades to preempt semantics)
    prefetch: int = 2              # host batches assembled ahead by a
                                   # background thread (0 = synchronous;
                                   # reference C8 parity with DataLoader
                                   # worker overlap)
    decode_workers: int = 0        # ImageNet real-file path: decode worker
                                   # processes (reference DataLoader
                                   # num_workers; one host core decodes
                                   # ~280 img/s vs the ~6.8k img/s a v5e
                                   # chip eats at bs=128 — input_path
                                   # artifact)
    steps_per_dispatch: int = 1    # optimizer steps per jitted dispatch:
                                   # >1 stages that many host batches and
                                   # lax.scan's the train step on-device,
                                   # amortizing per-step dispatch cost.
                                   # Pays only where dispatch DOMINATES —
                                   # ms-scale steps on a real chip (a v5e
                                   # runs ResNet-20-sized steps at 100s
                                   # of dispatches/sec); measured NEUTRAL
                                   # on the CPU meshes (steps are
                                   # seconds: 5.5 vs 6.0 s/step at
                                   # mesh8, 6.5 vs 7.5 at mesh2 — host
                                   # overhead never dominates there).
                                   # Semantics identical to
                                   # steps_per_dispatch=1 (per-step RNG,
                                   # warm-up cond, BPTT carry all thread
                                   # through the scan; equality
                                   # test-pinned); train() reports the
                                   # dispatch's last-step loss, same as
                                   # the per-step path reports its last
                                   # step. num_iters must divide.
    obs_calib: bool = False        # live comm-model calibration
                                   # (obs/calib.py): profile-attribute a
                                   # dispatch every obs_calib_interval
                                   # steps, feed measured (wire_bytes,
                                   # t_comm) to an online alpha/beta
                                   # fitter; "calib" records per refit,
                                   # comm_model_drift rule vs the
                                   # planner's inputs, end-of-run
                                   # calib_fit_{P}proc.json artifact in
                                   # out_dir. Needs obs_counters and
                                   # nworkers > 1; off by default — each
                                   # measurement is a profiler capture
    obs_calib_interval: int = 25   # steps between calibration captures
    obs_critpath: bool = False     # per-step stage-interval records
                                   # (obs/critpath.py): profile-attribute
                                   # a dispatch every obs_calib_interval
                                   # steps (shares the calibrator's
                                   # capture when both are on) and log a
                                   # durable "critpath" record — ordered
                                   # {stage, t0, t1} segments with the
                                   # comm span wait-split against the
                                   # ledger-modeled wire time — feeding
                                   # the fleet's global critical-path
                                   # join and the critpath_shift rule
    obs_critpath_shift_windows: int = 3  # consecutive joined steps whose
                                   # global critical stage differs from
                                   # the modal one before critpath_shift
                                   # fires (obs.events.Thresholds)
    registry: Optional[str] = None  # append this run's summary line to
                                   # DIR/runs.jsonl on exit
                                   # (obs/registry.py; read back with
                                   # `report history` / `report
                                   # regress`). None disables
    comm_model_fit: Optional[str] = None  # explicit alpha/beta fit
                                   # artifact (dcn_probe_*.json or
                                   # calib_fit_*.json) pricing the comm
                                   # planner, overriding the probe-dir
                                   # lookup; the filename is stamped as
                                   # fit provenance in manifest + plan
                                   # record. Malformed file fails at
                                   # startup. None = default lookup
    obs_mem: bool = False          # compile/memory-plane watch
                                   # (obs/memwatch.py): AOT compile
                                   # accounting — one fsync'd "compile"
                                   # record per distinct dispatch shape
                                   # (cost/memory analysis, lower/
                                   # compile wall times) with the
                                   # peak-HBM estimate stamped into the
                                   # manifest — plus the jit-cache
                                   # recompile watch (recompile_storm
                                   # rule) and sampled live-memory
                                   # "mem" records feeding the
                                   # device_mem_leak / hbm_headroom
                                   # rules. Costs one AOT compile per
                                   # distinct dispatch shape
    obs_mem_interval: int = 50     # steps between live-memory samples
                                   # (jax.live_arrays + memory_stats
                                   # reads are host-side but not free);
                                   # samples land at sync points the
                                   # loop already pays
    obs_recompile_warmup: int = 1  # compile-watch polls before the
                                   # recompile_storm rule arms; 0 means
                                   # ANY executable-cache growth fires
                                   # (obs.events.Thresholds)
    obs_mem_leak_windows: int = 3  # consecutive growing live-bytes
                                   # windows before device_mem_leak
                                   # fires (a plateau resets the streak)
    obs_hbm_headroom_frac: float = 0.92  # bytes_in_use / bytes_limit
                                   # fraction above which hbm_headroom
                                   # fires (backends without
                                   # memory_stats never arm it)
    obs_goodput: bool = True       # goodput/badput wall-clock ledger
                                   # (obs/goodput.py): partition the
                                   # run's measured wall into productive
                                   # step compute vs the badput taxonomy
                                   # (select/comm/wait/compile/ckpt/
                                   # wasted/degraded/data/startup), with
                                   # the unattributed remainder surfaced
                                   # as other_frac (conservation). Pure
                                   # host arithmetic at sync points the
                                   # loop already pays — on by default.
                                   # One durable cumulative "goodput"
                                   # record every obs_goodput_interval
                                   # steps + an end-of-run summary
    obs_goodput_interval: int = 50  # optimizer steps between periodic
                                   # durable "goodput" records (<= 0
                                   # keeps only the end-of-run summary);
                                   # each record also feeds the
                                   # goodput_collapse rule
    obs_goodput_collapse_windows: int = 3  # consecutive ledger records
                                   # with goodput_frac below half its
                                   # EWMA before goodput_collapse fires
                                   # (obs.events.Thresholds)
    obs_linkmap: bool = False      # per-(axis, peer) network weather
                                   # map (obs/linkmap.py): carve each
                                   # calibration capture's measured comm
                                   # span over the schedule's
                                   # round->peer join, keep EWMA
                                   # latency/bandwidth per link, log a
                                   # durable "linkmap" record per
                                   # capture, feed the link_degraded
                                   # rule. Rides the calibrator cadence,
                                   # so it implies the same capture cost
                                   # as obs_calib
    obs_link_degraded_x: float = 4.0  # one link's EWMA latency over
                                   # the fleet median by this factor
                                   # counts as a degraded window
                                   # (obs.events.Thresholds)
    obs_link_degraded_windows: int = 3  # consecutive degraded windows
                                   # before link_degraded fires; a
                                   # recovered window re-arms
                                   # (obs.events.Thresholds)
    obs_forecast: bool = False     # scale-out forecast plane
                                   # (obs/forecast.py): hindcast the
                                   # analytic step model against THIS
                                   # run each calibration capture, then
                                   # forecast step time / goodput at
                                   # the P targets across schedules and
                                   # axis trees. One durable "forecast"
                                   # record per capture; feeds the
                                   # forecast_drift rule. Requires
                                   # obs_calib (rides its cadence)
    obs_forecast_targets: str = "32,256,1024"  # comma-separated modeled
                                   # worker counts the forecast grid
                                   # prices (ROADMAP item 3 evidence
                                   # targets)
    obs_forecast_drift_x: float = 4.0  # hindcast error factor beyond
                                   # which a capture counts as drifted;
                                   # 3 consecutive drifted captures
                                   # fire forecast_drift
                                   # (obs.events.Thresholds)

    # --- per-dataset defaults (the reference hardcoded these in DLTrainer) --
    def resolved(self) -> "TrainConfig":
        cfg = dataclasses.replace(self)
        if cfg.steps_per_dispatch < 1:
            raise ValueError(
                f"steps_per_dispatch={cfg.steps_per_dispatch} must be "
                ">= 1")
        if cfg.dataset is None:
            from gtopkssgd_tpu.models import get_model as _gm
            cfg.dataset = _gm(cfg.dnn)[1].dataset
        defaults = {
            # dataset: (lr, weight_decay, clip)
            "cifar10": (0.1, 5e-4, None),
            "imagenet": (0.01 if cfg.dnn == "alexnet" else 0.1, 1e-4, None),
            "ptb": (1.0, 0.0, 0.25),
            "an4": (3e-4, 0.0, 400.0),
            "tokens": (0.5, 0.0, 1.0),
        }
        lr, wd, clip = defaults.get(cfg.dataset, (0.1, 0.0, None))
        if cfg.lr is None:
            cfg.lr = lr
        if cfg.weight_decay is None:
            cfg.weight_decay = wd
        if cfg.clip_grad_norm is None:
            cfg.clip_grad_norm = clip
        return cfg


# Per-dataset normalization constants for the uint8 wire format: pipelines
# ship raw pixels, the jitted step normalizes on device.
_WIRE_STATS = {
    "cifar10": (CIFAR_MEAN, CIFAR_STD),
    "imagenet": (IMAGENET_MEAN, IMAGENET_STD),
}


class TrainState(NamedTuple):
    """The whole checkpointable training state, one pytree. Residual lives
    inside opt_state (GTopKSGDState), so resume preserves error feedback."""

    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: Any


def shard_steps_per_epoch(ds, batch_size: int, nsteps_update: int = 1) -> int:
    """Optimizer steps per epoch for a rank's dataset shard.

    Must be identical on EVERY process of a multi-host run (each step
    issues collectives; disagreement desyncs the SPMD program). The
    partitioner gives the last rank the dataset remainder, so the count is
    derived from the MINIMUM shard size — a pure function of
    (n, nworkers, batch_size) every process agrees on — rather than from
    whichever shard happens to be local. Shared by the Trainer and the
    convergence runner so max_epochs-from-steps arithmetic cannot drift
    from the LR schedule's epoch length."""
    spe = ds.steps_per_epoch()
    part = getattr(ds, "partitioner", None)
    if part is not None and part.nworkers > 1:
        spe = (part.n // part.nworkers) // batch_size
    return max(1, spe // nsteps_update)


def _lead_axis(arrays):
    """``arrays`` as one [len(arrays), ...] leaf, and the bytes copied to
    make it: a single array is a view of itself (``a[None]``), several are
    one ``np.stack``, the only copy the data forces."""
    if len(arrays) == 1:
        return np.asarray(arrays[0])[None], 0
    out = np.stack(arrays)
    return out, out.nbytes


@jax.jit
def _hold_counters(counters):
    """Copies of a step's readable counters in buffers of their own, so
    that the next dispatch, which donates the state they live in, leaves
    them readable: the same values and dtypes, no arithmetic. One small
    program queued behind the step, the same one for every step of a run
    (``Trainer.train(1)`` runs it too, so a later call compiles nothing)."""
    return jax.tree.map(jnp.copy, counters)


class Trainer:
    def __init__(self, config: TrainConfig):
        self.cfg = cfg = config.resolved()
        self.process_rank = jax.process_index()
        self.logger = get_logger("trainer", rank=self.process_rank)
        # Live OpenMetrics endpoint (obs.exporter): fed as the metrics
        # sink so it sees exactly the records this rank produces, file
        # or no file. Started before the logger so the sink exists.
        self.exporter = None
        if cfg.obs_export_port:
            from gtopkssgd_tpu.obs.exporter import MetricsExporter

            port = max(0, cfg.obs_export_port)
            self.exporter = MetricsExporter(port=port).start()
            self.logger.info(
                "obs exporter: http://127.0.0.1:%d/metrics",
                self.exporter.port)
        # Multi-process runs shard per rank (metrics.rank{r}.jsonl) so
        # the fleet merger (obs/fleet.py) has per-host streams to align;
        # single-process keeps the classic metrics.jsonl.
        self.metrics = MetricsLogger(
            cfg.out_dir, self.logger, rank=self.process_rank,
            shard=jax.process_count() > 1,
            sink=self.exporter.observe if self.exporter else None)
        # Goodput/badput ledger (obs/goodput.py): constructed FIRST so
        # its wall-clock t0 covers the whole init (model/data/compile
        # all land in startup/compile, not in a blind spot). The monitor
        # is attached below once it exists.
        self.goodput = None
        if cfg.obs_goodput:
            from gtopkssgd_tpu.obs.goodput import GoodputLedger
            self.goodput = GoodputLedger(
                metrics=self.metrics,
                interval=cfg.obs_goodput_interval)
        # Host timeline (obs.timeline): spans + telemetry tracks + event
        # markers as one chrome-trace JSON, written on __exit__ (and
        # best-effort on a watchdog stall). Rank 0 only, like metrics.
        self.timeline = (
            TimelineRecorder(rank=self.process_rank)
            if cfg.obs_timeline and self.process_rank == 0 else None
        )
        # Span tracer (obs.tracing): every host phase of the loop as a
        # span with its step id, into the span buffer, the window means
        # and the sink. Replaces the bare StepTimer (utils/timers.py
        # keeps the primitive).
        self.tracer = Tracer(
            metrics=self.metrics,
            sink=self.timeline.span_sink if self.timeline else None,
        )
        # Online anomaly monitor (obs.events): fed at the obs/log sync
        # points below; density rules only make sense when a sparse mode
        # has a configured rho.
        from gtopkssgd_tpu.modes import DENSE_MODES

        self.monitor = (
            AnomalyMonitor(
                metrics=self.metrics,
                rho=(cfg.density
                     if cfg.compression not in DENSE_MODES else None),
                halt_on=cfg.obs_halt_on,
                thresholds=Thresholds(
                    recompile_warmup=cfg.obs_recompile_warmup,
                    mem_leak_windows=cfg.obs_mem_leak_windows,
                    hbm_headroom_frac=cfg.obs_hbm_headroom_frac,
                    critpath_shift_windows=cfg.obs_critpath_shift_windows,
                    goodput_collapse_windows=(
                        cfg.obs_goodput_collapse_windows),
                    link_degraded_x=cfg.obs_link_degraded_x,
                    link_degraded_windows=cfg.obs_link_degraded_windows,
                    forecast_drift_x=cfg.obs_forecast_drift_x),
                timeline=self.timeline,
            )
            if cfg.obs_events else None
        )
        if self.goodput is not None:
            self.goodput.monitor = self.monitor
        self.watchdog = (
            StallWatchdog(cfg.obs_watchdog,
                          on_stall=self._on_stall,
                          diagnostics=self._stall_diagnostics)
            if cfg.obs_watchdog > 0 else None
        )
        # Resilience layer (gtopkssgd_tpu/resilience): deterministic
        # step-keyed fault injection, and the recovery manager that
        # claims monitor events before they escalate to a halt. The
        # preemption guard is NOT installed here — a library object
        # must not steal the host process's signal handlers; dist_trainer
        # (or a test) installs one and assigns it to `self.preempt`.
        from gtopkssgd_tpu.resilience import (
            FaultInjector,
            RecoveryManager,
            parse_policy,
            retry_call,
        )

        self.injector = (
            FaultInjector(cfg.inject, metrics=self.metrics,
                          logger=self.logger, rank=self.process_rank)
            if cfg.inject else None
        )
        self.recovery = (
            RecoveryManager(parse_policy(cfg.recover_policy),
                            metrics=self.metrics, logger=self.logger)
            if cfg.recover_policy else None
        )
        if self.recovery is not None:
            if self.monitor is None:
                raise ValueError(
                    "recover_policy requires obs_events (recovery acts "
                    "on AnomalyMonitor events)")
            self.monitor.recovery = self.recovery.claim
        self.preempt = None

        self.model, self.spec = get_model(
            cfg.dnn,
            dtype=jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32,
            space_to_depth=cfg.space_to_depth,
            preset=cfg.model_preset,
        )
        self.mesh = make_mesh(cfg.nworkers)
        self.p = cfg.nworkers

        # In a multi-host run each process feeds only the mesh positions its
        # own devices occupy; _device_batch builds the global [P, ...] batch
        # from the per-device pieces (single host: all ranks are local).
        self.local_ranks = [
            i for i, d in enumerate(self.mesh.devices.flat)
            if d.process_index == self.process_rank
        ]
        self._local_devices = [
            self.mesh.devices.flat[r] for r in self.local_ranks]
        self._dp_sharding = NamedSharding(self.mesh, P("dp"))
        # Bytes the batch assembly has copied on the host since the last
        # "train" record (its host_copied_mb).
        self._host_copied_bytes = 0
        # Counter reads since the last "train" record (its
        # obs_reads_lagged / obs_reads_sync): those made with a later
        # dispatch already queued behind the step read, and those that
        # waited for the newest dispatched step.
        self._obs_reads = {"lagged": 0, "sync": 0}
        data_kw = dict(
            batch_size=cfg.batch_size, data_dir=cfg.data_dir, seed=cfg.seed
        )
        if cfg.dataset == "imagenet" and cfg.decode_workers > 0:
            data_kw["decode_workers"] = cfg.decode_workers
        if cfg.dataset == "cifar10" and cfg.synth_hard:
            data_kw["synth_hard"] = True
        # Which of a model's forms the step compiles as (a model that
        # chooses one from the backend and its shapes says which): in the
        # manifest and every "train" record.
        self._model_forms = {}
        if cfg.dataset == "tokens":
            # A model with a mask token keeps it out of the data: the ids
            # are drawn from those below it.
            sizes = self.model.sizes
            data_kw.update(seq_len=sizes["seq_len"], vocab_size=sizes.get(
                "mask_token_id", sizes["vocab_rows"]))
            if hasattr(self.model, "forms"):
                self._model_forms = self.model.forms(data_kw["seq_len"])
        def _dataset(**kw):
            # Data-loader setup rides the shared retry/backoff helper
            # (resilience/preempt.py): a transient storage blip at
            # startup must not kill a pod-sized run before step 1.
            return retry_call(
                functools.partial(get_dataset, cfg.dataset, **kw),
                retries=2, delay=0.5, logger=self.logger,
                desc=f"get_dataset({cfg.dataset})")

        self.train_shards = [
            _dataset(split="train", rank=r, nworkers=cfg.nworkers,
                     **data_kw)
            for r in self.local_ranks
        ]
        self.val_data = _dataset(split="test", **data_kw)
        self.steps_per_epoch = shard_steps_per_epoch(
            self.train_shards[0], cfg.batch_size, cfg.nsteps_update
        )

        # Explicit comm-model fit (--comm-model-fit): loaded once here —
        # a malformed artifact fails at startup, not mid-run. It prices
        # the plan decision below and its filename is stamped as fit
        # provenance.
        self._comm_fit = None
        if cfg.comm_model_fit:
            from gtopkssgd_tpu.obs.calib import load_fit_file
            self._comm_fit = load_fit_file(cfg.comm_model_fit)
        params, batch_stats = self._init_params()
        # Wire-plan decision (parallel.planner): made once, here, from
        # the parameter count, before the optimizer exists; _make_tx
        # hands the chosen plan's name to gtopk_sgd, whose traced step
        # looks it up. Logged as the "plan" record (chosen plan + every
        # candidate's score) and stamped into the manifest so the ledger
        # prices the schedule that ran. Dense / single-device runs have
        # no sparse wire to plan.
        self._plan_decision = None
        # Bucket plan (parallel.bucketing): resolved host-side from the
        # SAME leaf sizes the optimizer's trace-time plan_buckets sees
        # (params pytree flatten order), so the manifest/"bucket" record
        # describe the boundaries that actually ran. Layerwise-only.
        self._bucket_plan = None
        if cfg.compression == "gtopk_layerwise":
            from gtopkssgd_tpu.parallel import parse_buckets, plan_buckets
            if parse_buckets(cfg.buckets) != "concat":
                leaf_sizes = tuple(
                    int(leaf.size)
                    for leaf in jax.tree_util.tree_leaves(params))
                self._bucket_plan = plan_buckets(
                    leaf_sizes, cfg.density, buckets=cfg.buckets,
                    p=self.p, codec=cfg.wire_codec,
                    pipeline=cfg.pipeline)
        if cfg.compression not in (None, "none", "dense") and self.p > 1:
            from gtopkssgd_tpu.parallel import build_decision
            from gtopkssgd_tpu.parallel.bucketing import buckets_key
            bplan = self._bucket_plan
            k = (bplan.k_total if bplan is not None
                 else max(1, int(np.ceil(cfg.density * self.num_params))))
            fit_kw = {}
            if self._comm_fit is not None:
                fit_kw = dict(alpha_ms=self._comm_fit["alpha_ms"],
                              beta_gbps=self._comm_fit["beta_gbps"],
                              fit_source=self._comm_fit["source"])
            self._plan_decision = build_decision(
                cfg.compression, p=self.p, n=self.num_params, k=k,
                codec=cfg.wire_codec, ici_size=cfg.hier_ici,
                pin=cfg.comm_plan,
                bucketing=buckets_key(cfg.buckets),
                buckets=bplan.pairs() if bplan is not None else None,
                pipeline=(bplan.pipeline if bplan is not None
                          else "serial"),
                **fit_kw)
        self.tx = self._make_tx()
        self.state, self.carry = self._init_state(params, batch_stats)
        # Layer-name column for "layers" records: index i of every
        # telemetry [L] array is leaf i of the params pytree in jax.tree
        # flatten order — the same order the optimizer's segment map uses.
        self._layer_names = (
            layer_names(self.state.params) if cfg.obs_layers else ())
        plan_extra = {}
        if self._plan_decision is not None:
            d = self._plan_decision
            plan_extra = {"comm_plan": d.plan.name,
                          "comm_plan_schedule": d.plan.schedule,
                          "comm_plan_pin": d.pin,
                          # which comm model priced this plan — the
                          # ledger/plan report headers read these back
                          "comm_fit_source": d.inputs.get("fit_source"),
                          "comm_fit_alpha_ms": d.inputs.get("alpha_ms"),
                          "comm_fit_beta_gbps": d.inputs.get("beta_gbps")}
        if self._bucket_plan is not None:
            plan_extra.update(self._bucket_plan.to_manifest())
        plan_extra.update(self._model_forms)
        # Whether the one-device step took the leaf form, and how far
        # (optimizer.py's slabs form): static, from the parameters' shapes.
        self._slab_state = leaf_form_state(
            cfg.compression, None if self.p == 1 else "dp")
        if self._slab_state:
            plan_extra.update(leaf_plan(params).counters())
        # Compile-plane accounting (obs/memwatch.py, --obs-mem): build
        # the jitted step and AOT lower/compile it at the canonical
        # dispatch shape BEFORE the manifest is assembled, so the
        # compile record's peak-HBM estimate rides the manifest header
        # (run_manifest's **extra). The AOT pass never executes —
        # abstract ShapeDtypeStruct batch leaves stand in for data, so
        # no batch is consumed from the stream.
        self._train_step = self._build_train_step()
        self.memwatch = None
        init_compile = None
        if cfg.obs_mem:
            from gtopkssgd_tpu.obs.memwatch import MemWatch
            self.memwatch = MemWatch(
                metrics=self.metrics, monitor=self.monitor,
                mem_interval=cfg.obs_mem_interval, logger=self.logger)
            # Ledger cursor: init-so-far is startup, the AOT pass that
            # follows is compile (train_started() later picks up the
            # rest of init as startup).
            if self.goodput is not None:
                self.goodput.mark("startup")
            init_compile = self.memwatch.account(
                self._train_step, self.state, self.carry,
                self._abstract_batch(), step=0, log=False)
            if self.goodput is not None:
                self.goodput.mark("compile")
            if self.memwatch.peak_hbm_bytes is not None:
                plan_extra["peak_hbm_bytes"] = self.memwatch.peak_hbm_bytes
        # Elastic lineage (resilience/elastic.py): one LOGICAL run =
        # one lineage_id, carried across resizes via out_dir's
        # elastic.json — adopted when the relaunch finds one, minted
        # fresh otherwise. Stamped into the manifest ONLY under
        # cfg.elastic so non-elastic manifests stay byte-stable.
        self.lineage = None
        if cfg.elastic:
            from gtopkssgd_tpu.resilience.elastic import (
                load_lineage, mint_lineage_id, write_lineage)
            self.lineage = load_lineage(cfg.out_dir)
            if self.lineage is None:
                self.lineage = {"lineage_id": mint_lineage_id(),
                                "resize_epoch": 0, "p": self.p}
                if cfg.out_dir:
                    write_lineage(cfg.out_dir, **self.lineage)
            plan_extra["lineage_id"] = self.lineage["lineage_id"]
            plan_extra["resize_epoch"] = int(
                self.lineage.get("resize_epoch", 0))
        # Run-manifest header: first record of every metrics file, so
        # each is self-describing (config hash + resolved headline flags,
        # mesh, jax/backend versions, git sha). In sharded multi-process
        # runs EVERY rank writes it — config_hash is the join key the
        # fleet merger validates before aligning shards.
        self._manifest = run_manifest(
            cfg, mesh=self.mesh, num_params=self.num_params,
            steps_per_epoch=self.steps_per_epoch, **plan_extra)
        self.metrics.log("manifest", flush=True, **self._manifest)
        # The manifest stays the FIRST record; the deferred startup
        # compile record lands right after it, and the recompile watch
        # arms on the same jitted callable the loop dispatches.
        if init_compile is not None:
            self.memwatch.log_compile(init_compile)
        if self.memwatch is not None:
            self.memwatch.attach(self._train_step)
        if self._plan_decision is not None:
            self.metrics.log("plan", flush=True,
                             **self._plan_decision.record())
        if self._bucket_plan is not None:
            self.metrics.log("bucket", flush=True,
                             **self._bucket_record())
        # Live comm-model calibrator (obs/calib.py): fed measured
        # (wire_bytes, t_comm) from the profiler-attributed dispatches in
        # train(); its drift baseline is the EXACT inputs that priced
        # this run's plan. p == 1 has no wire to calibrate.
        self.calib = None
        self.linkmap = None
        self.forecaster = None
        if cfg.obs_calib and cfg.obs_counters and self.p > 1:
            from gtopkssgd_tpu.obs.calib import CommCalibrator
            d = self._plan_decision
            if d is not None:
                wire_mode = d.plan.wire_mode
                inputs = d.inputs
            else:
                from gtopkssgd_tpu.parallel.planner import planner_inputs
                wire_mode, inputs = "dense", planner_inputs(None)
            self.calib = CommCalibrator(
                wire_mode, self.p,
                baseline={key: inputs.get(key) for key in
                          ("alpha_ms", "beta_gbps", "ici_gbps",
                           "fit_source")},
                metrics=self.metrics, monitor=self.monitor,
                ici_size=cfg.hier_ici)
            # Link weather map (obs/linkmap.py): carves the SAME
            # (wire_bytes, t_comm) capture the calibrator consumes over
            # the schedule's round->peer join; rides the calib cadence,
            # so it only exists when the calibrator does.
            if cfg.obs_linkmap:
                from gtopkssgd_tpu.obs.linkmap import LinkMap
                self.linkmap = LinkMap(
                    wire_mode, self.p, rank=self.process_rank,
                    ici_size=cfg.hier_ici,
                    alpha_ms=float(inputs.get("alpha_ms") or 0.1),
                    beta_gbps=float(inputs.get("beta_gbps") or 25.0),
                    ici_gbps=float(inputs.get("ici_gbps") or 1600.0),
                    metrics=self.metrics, monitor=self.monitor)
            # Scale-out forecast plane (obs/forecast.py): the digital
            # twin hindcasts against this run and forecasts the P
            # targets, riding the same capture cadence (it consumes the
            # calibrator's refits, the weather map's snapshots, and the
            # critpath budgets the loop already produces).
            if cfg.obs_forecast:
                from gtopkssgd_tpu.obs.forecast import StepForecaster
                bplan = self._bucket_plan
                fc_k = (bplan.k_total if bplan is not None
                        else max(1, int(np.ceil(
                            cfg.density * self.num_params))))
                if cfg.compression in (None, "none", "dense"):
                    fc_k = self.num_params
                try:
                    targets = tuple(
                        int(t) for t in
                        str(cfg.obs_forecast_targets).split(",")
                        if t.strip())
                except ValueError:
                    raise ValueError(
                        "--obs-forecast-targets must be a comma-"
                        "separated list of worker counts, got "
                        f"{cfg.obs_forecast_targets!r}")
                self.forecaster = StepForecaster(
                    {"mode": cfg.compression or "dense", "p": self.p,
                     "n": self.num_params, "k": fc_k,
                     "codec": cfg.wire_codec,
                     "schedule": (d.plan.schedule
                                  if d is not None else None),
                     "bucketing": cfg.buckets or "concat",
                     "buckets": (bplan.pairs()
                                 if bplan is not None else None),
                     "ici_size": cfg.hier_ici},
                    baseline=inputs, targets=targets,
                    metrics=self.metrics, monitor=self.monitor)
        self._eval_step = self._build_eval_step()
        # Degrade fallback (recover-policy "degrade"): the sparse step
        # stays canonical; a dense-allreduce variant over the SAME
        # optimizer state treedef (warmup_dense_steps=2**30 selects the
        # dense branch of the compiled update) is built lazily on the
        # first degrade action.
        self._sparse_step = self._train_step
        self._dense_step = None
        self._degraded = False
        self._degrade_until = 0
        # Checkpoints: orbax save/restore of the live sharded state; on
        # multi-host EVERY process participates (orbax coordinates; each
        # writes its addressable residual shards) over a shared filesystem.
        # The manager stamps each save with a config_hash so a mismatched
        # resume is refused instead of silently changing the experiment —
        # computed with the resilience knobs nulled out: an injected-fault
        # run and its clean resume are the SAME experiment (the injection
        # perturbs execution, never the checkpointable state treedef), and
        # a chaos run that could not be resumed without --inject would
        # defeat the preempt/resume path it exists to test.
        nulled = dict(inject=None, recover_policy=None,
                      allow_ckpt_mismatch=False)
        if cfg.elastic:
            # A resize changes nworkers and NOTHING else about the
            # experiment, so pre- and post-resize checkpoints must
            # agree on config_hash: under --elastic the fleet size and
            # the elastic knobs are nulled too (which is why BOTH sides
            # of a resize must run with --elastic — a non-elastic
            # resume of an elastic checkpoint is refused as a
            # different experiment, by design).
            # out_dir/registry are workspace plumbing, not experiment
            # identity — and the relaunch contract puts the resumed run
            # in a FRESH out_dir (reusing the old one would corrupt its
            # registry summary), so they cannot key the ckpt hash.
            nulled.update(nworkers=0, elastic=False,
                          evict_after_windows=3, min_fleet=1,
                          out_dir=None, registry=None)
        ckpt_hash = config_hash(dataclasses.replace(cfg, **nulled))
        self._ckpt = (
            CheckpointManager(f"{cfg.out_dir}/ckpt",
                              config_hash=ckpt_hash,
                              logger=self.logger)
            if cfg.out_dir else None
        )
        self._set_iters(start_epoch=0)

    def _bucket_record(self) -> dict:
        """The "bucket" evidence record: the chosen BucketPlan's
        boundaries and per-bucket rows, plus the modeled comm ms of the
        two degenerate partitions (B=1 single merge, B=L per-leaf) so a
        report reader can see where the chosen B sits on the alpha-beta
        curve without re-running the DP."""
        from gtopkssgd_tpu.parallel import bucketing, plan_buckets
        from gtopkssgd_tpu.parallel.planner import planner_inputs
        cfg, bplan = self.cfg, self._bucket_plan
        inputs = planner_inputs(None)
        alpha, beta = inputs["alpha_ms"], inputs["beta_gbps"]
        kw = dict(p=self.p, codec=cfg.wire_codec,
                  alpha_ms=alpha, beta_gbps=beta)
        sizes = bplan.leaf_sizes

        def _ms(spec):
            alt = plan_buckets(sizes, cfg.density, buckets=spec,
                               pipeline=bplan.pipeline, **kw)
            return bucketing.partition_cost_ms(
                alt, pipeline=bplan.pipeline, **kw)

        return {
            "buckets": bplan.spec,
            "n_buckets": bplan.n_buckets,
            "n_leaves": len(sizes),
            "boundaries": list(bplan.boundaries),
            "bucket_sizes": list(bplan.sizes),
            "bucket_ks": list(bplan.ks),
            "pipeline": bplan.pipeline,
            "rows": bucketing.describe(bplan, **kw),
            "modeled_ms": bucketing.partition_cost_ms(
                bplan, pipeline=bplan.pipeline, **kw),
            "modeled_ms_b1": _ms(1),
            "modeled_ms_leaf": _ms("leaf"),
            # True wall-clock spans under both orders — the A/B a report
            # reader needs to see what pipelining bought at this B.
            "span_serial_ms": bucketing.pipeline_span_ms(
                bplan, pipeline="serial", **kw),
            "span_overlap_ms": bucketing.pipeline_span_ms(
                bplan, pipeline="overlap", **kw),
            "alpha_ms": alpha,
            "beta_gbps": beta,
        }

    def _feed_calibrator(self, step: int, spd: int,
                         trace_dir: str) -> None:
        """Attribute the just-captured dispatch and feed one measured
        (wire_bytes, t_comm_ms) sample to the comm calibrator. Wire
        bytes come from the same on-device telemetry the obs records
        read; t_comm from the profiler attribution, normalized per
        optimizer step. Attribution failure degrades to a warning — a
        missed sample must never take down training. AnomalyHalt from
        the drift rule propagates like any monitor halt."""
        import shutil

        from gtopkssgd_tpu.obs.trace_attr import attribute
        try:
            rec = attribute(trace_dir, mode=self.cfg.compression)
        except Exception as e:
            self.logger.warning("calib attribution failed: %s", e)
            return
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        t_comm_us = rec.get("t_comm_us")
        if not isinstance(t_comm_us, (int, float)) or t_comm_us <= 0:
            return
        tel = self.state.opt_state.telemetry
        if not tel:
            return
        wire = float(telemetry_scalars(tel).get("wire_bytes", 0.0))
        if wire <= 0:
            return
        # Overlapped dispatches measure a partially-hidden t_comm; tag
        # them so the calibrator quarantines the sample instead of
        # biasing the serial alpha-beta fit (obs/calib.py).
        overlapped = (self._bucket_plan is not None
                      and self._bucket_plan.pipeline == "overlap")
        t_comm_ms = float(t_comm_us) / 1e3 / spd
        calib_rec = self.calib.observe(step, wire_bytes=wire,
                                       t_comm_ms=t_comm_ms,
                                       overlapped=overlapped)
        lm_rec = None
        if self.linkmap is not None and not overlapped:
            # Same sample, carved per link; overlapped spans are
            # quarantined here for the same reason the calibrator
            # quarantines them — a partially-hidden t_comm would bias
            # every link's EWMA low. May raise AnomalyHalt (after its
            # durable record), like any monitor-fed surface.
            lm_rec = self.linkmap.observe(step, t_comm_ms=t_comm_ms,
                                          wire_bytes=wire)
        if self.forecaster is not None:
            # The forecast reprices itself from whatever this capture
            # refreshed: a completed refit window's fit, the weather
            # map's degradation multiple.
            if calib_rec is not None:
                self.forecaster.note_calib(calib_rec)
            if lm_rec is not None:
                self.forecaster.note_linkmap(lm_rec)

    def _log_critpath(self, step: int, spd: int, trace_dir: str,
                      cleanup: bool = True) -> None:
        """Attribute the just-captured dispatch into ordered stage
        intervals (obs/critpath.py) and log one durable "critpath"
        record. The wire budget for the wait split comes from the
        ledger's alpha-beta model priced on this run's manifest,
        scaled by spd (the capture spans spd optimizer steps); when
        the model can't parameterize, the whole comm span stays
        "comm" and no wait is claimed. Feeds the local crit_stage to
        the anomaly monitor (critpath_shift rule). ``cleanup=False``
        leaves the trace dir for the calibrator feed that follows."""
        import shutil

        from gtopkssgd_tpu.obs import critpath
        from gtopkssgd_tpu.obs.trace_attr import attribute
        try:
            w = critpath.modeled_wire_us(self._manifest, nprocs=self.p)
            rec = attribute(trace_dir, mode=self.cfg.compression,
                            stage_intervals=True,
                            wire_us=None if w is None else w * spd)
        except Exception as e:
            self.logger.warning("critpath attribution failed: %s", e)
            return
        finally:
            if cleanup:
                shutil.rmtree(trace_dir, ignore_errors=True)
        cp = rec.get("critpath")
        if not cp:
            return
        self.metrics.log("critpath", flush=True, step=step, **cp)
        if self.goodput is not None:
            # The ledger splits step time by the stage shares this
            # record just measured (compute->goodput, select/comm/wait
            # ->their badput buckets).
            self.goodput.note_stage_fracs(cp)
        if self.forecaster is not None:
            # Per-step compute/select budgets + the measured wall the
            # hindcast compares against; fed BEFORE the shift rule so
            # a halt there never starves the forecast of its budgets.
            self.forecaster.note_critpath(cp, spd=spd)
        # AnomalyHalt from the shift rule propagates like any monitor
        # halt — the durable event record lands before the raise.
        if self.monitor is not None:
            self.monitor.observe_critpath(
                step, crit_stage=cp.get("crit_stage"))

    def _make_tx(self, warmup_dense_steps: Optional[int] = None,
                 slabs: Optional[bool] = None):
        """The optimizer transform; ``warmup_dense_steps`` overrides the
        config-derived value (the degrade fallback passes 2**30 to pin
        the always-dense branch — identical state treedef, so the live
        state flows between the sparse and degraded steps unchanged).
        ``slabs`` overrides the state's form (this run's: the leaf form
        on one device, flat [N] buffers on a mesh) for the template of a
        checkpoint that another form wrote."""
        cfg = self.cfg
        if slabs is None:
            slabs = self.p == 1
        if warmup_dense_steps is None:
            warmup_dense_steps = (
                cfg.dense_warmup_epochs * self.steps_per_epoch)
        return gtopk_sgd(
            self._lr_schedule(),
            momentum=cfg.momentum,
            weight_decay=cfg.weight_decay,
            nesterov=cfg.nesterov,
            compression=cfg.compression,
            density=cfg.density,
            topk_method=cfg.topk_method,
            wire_codec=cfg.wire_codec,
            comm_plan=(self._plan_decision.plan.name
                       if self._plan_decision is not None
                       else cfg.comm_plan),
            buckets=cfg.buckets,
            pipeline=cfg.pipeline,
            clip_grad_norm=cfg.clip_grad_norm,
            axis_name=None if slabs else "dp",
            hier_ici_size=cfg.hier_ici,
            warmup_dense_steps=warmup_dense_steps,
            momentum_correction=cfg.momentum_correction,
            telemetry=cfg.obs_counters,
            telemetry_layers=cfg.obs_layers,
            telemetry_audit_interval=cfg.obs_audit_interval,
        )

    def _set_iters(self, start_epoch: int, skip_steps: int = 0) -> None:
        """(Re)create the persistent per-shard iterators from a given epoch
        permutation — used at init and to fast-forward after restore.
        ``skip_steps`` drains that many optimizer steps' worth of batches
        from each shard on top of the epoch seek: emergency preemption
        checkpoints land MID-epoch, and a bit-exact resumed loss trace
        needs the data stream aligned to the restored step, not the
        enclosing epoch boundary."""

        def gen(ds, start):
            e = start
            while True:
                yield from ds.epoch(e)
                e += 1

        # Stop the old worker BEFORE the new iterators exist: its produce
        # closure must never observe them (a batch it pulled from the new
        # stream would be discarded by close()'s drain — a silent skip).
        self.close()
        iters = [gen(s, start_epoch) for s in self.train_shards]
        for it in iters:
            for _ in range(skip_steps * self.cfg.nsteps_update):
                next(it)
        self._iters = iters
        # (Re)start the background prefetcher on the fresh iterators. The
        # closure binds the local `iters` list, not self._iters, so even a
        # leaked worker could only ever touch its own generation of
        # iterators. The worker assembles numpy batches only (views, or
        # the stack a micro axis forces); jax.device_put stays on the
        # consumer thread.
        self._prefetch = (
            Prefetcher(lambda: self._shard_batches(iters),
                       depth=self.cfg.prefetch, tracer=self.tracer)
            if self.cfg.prefetch > 0 else None
        )

    def close(self) -> None:
        """Release background resources (the prefetch worker and any
        dataset decode pools). Safe to call repeatedly; training can
        continue afterwards only via a new `_set_iters` (restore does
        this — dataset pools re-create lazily) — eval is unaffected."""
        if getattr(self, "_prefetch", None) is not None:
            self._prefetch.close()
            self._prefetch = None
        for ds in (list(getattr(self, "train_shards", []))
                   + [getattr(self, "val_data", None)]):
            if ds is not None and hasattr(ds, "close"):
                ds.close()

    def __enter__(self) -> "Trainer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
        if self.watchdog is not None:
            self.watchdog.close()
        if self.timeline is not None:
            try:
                path = self.timeline.write(self.cfg.obs_timeline)
                self.logger.info("timeline -> %s", path)
            except OSError as e:
                self.logger.warning("timeline write failed: %s", e)
        # End-of-run calibration artifact: the dcn_probe-compatible fit
        # the NEXT run's planner_inputs can consume (copy into the probe
        # dir or pass via --comm-model-fit). Before metrics.close — the
        # registry summary below reads the stream back.
        if (getattr(self, "calib", None) is not None and self.cfg.out_dir
                and self.process_rank == 0):
            try:
                path = self.calib.write_artifact(
                    self.cfg.out_dir, manifest=self._manifest)
                if path:
                    self.logger.info("comm-model fit -> %s", path)
            except OSError as e:
                self.logger.warning("calib artifact write failed: %s", e)
        # End-of-run goodput summary (final=1): BEFORE the registry
        # append below, so the registry line's goodput_frac reads this
        # run's own decomposition back from the stream.
        if self.goodput is not None:
            try:
                self.goodput.log_record(int(self.state.step), final=True)
            except Exception as e:
                self.logger.warning("goodput summary failed: %s", e)
        self._append_registry()
        if getattr(self, "memwatch", None) is not None:
            self.memwatch.close()
        # The metrics file outlives close() (restore() can resume a closed
        # Trainer's training); only leaving the context ends the run.
        self.metrics.close()
        if self.exporter is not None:
            self.exporter.close()

    def _append_registry(self) -> None:
        """One summary line per run into the workspace registry
        (obs/registry.py) — read back offline with `report history` /
        `report regress`. Shared by the normal __exit__ path and the
        watchdog stall path, so an exit-43 run still leaves its line
        (with final_status='stalled') like the 44/45 paths do via
        __exit__. Best-effort: a registry failure never masks the exit
        it is recording."""
        if not (self.cfg.registry and self.cfg.out_dir
                and self.process_rank == 0):
            return
        try:
            from gtopkssgd_tpu.obs import registry as _registry
            from gtopkssgd_tpu.obs.report import load_records
            records, _bad = load_records(self.cfg.out_dir)
            entry = _registry.run_summary(records)
            if entry is not None:
                path = _registry.append_run(self.cfg.registry, entry)
                self.logger.info("registry += %s", path)
        except (OSError, ValueError) as e:
            self.logger.warning("registry append failed: %s", e)

    # ------------------------------------------------------------ watchdog
    def _stall_diagnostics(self) -> Dict[str, Any]:
        """Host-side state merged into the stall record: the span phase
        means of the current logging window (what the run was spending
        time on when it died). Never touches the device — the backend is
        presumed wedged when this runs."""
        return {
            "phase_means_s": {
                path: round(sec, 6)
                for path, sec in self.tracer.stats.summary().items()
            },
        }

    def _on_stall(self, record: Dict[str, Any]) -> None:
        """Persist the diagnostic to metrics.jsonl (line-buffered, so it
        survives the hard exit), then take the default action (stderr dump
        + os._exit(43)). Runs on the watchdog thread while the backend is
        presumed wedged — NOTHING here may touch the device (the stall
        record's own step stands in for state.step), and os._exit skips
        __exit__, so the run's registry line and final records must land
        here or nowhere."""
        step = record.get("step")
        step = int(step) if isinstance(step, (int, float)) else 0
        try:
            self.metrics.log("stall", flush=True, **{
                k: v for k, v in record.items() if k not in ("kind", "time")
            })
            # The exit-43 equivalents of what finalize_resilience and
            # __exit__ write on the 44/45 paths: the final_status the
            # registry line keys on, and the goodput decomposition of
            # the wall this run DID burn before it wedged.
            if self.goodput is not None:
                self.goodput.log_record(step, final=True)
            self.metrics.log(
                "recovery", flush=True, action="summary",
                final_status="stalled", completed=0,
                n_recoveries=(self.recovery.n_recoveries
                              if self.recovery is not None else 0),
                step=step)
            self.metrics.close()
            self._append_registry()
        except Exception:
            pass
        # Best-effort timeline flush: everything here is host-side, and
        # the whole point of the file is correlating exactly this kind of
        # death with what the host was doing.
        if self.timeline is not None:
            try:
                self.timeline.instant("stall", args={
                    k: v for k, v in record.items()
                    if isinstance(v, (int, float, str))})
                self.timeline.write(self.cfg.obs_timeline)
            except Exception:
                pass
        _default_on_stall(record)

    # ------------------------------------------------------------------ lr
    def _lr_schedule(self):
        """Per-dataset step schedules, parity with the reference's hardcoded
        DLTrainer schedules (exact reference epochs unverifiable — mount was
        empty; these are the standard recipes the paper's setup implies).
        ``warmup_epochs`` prepends a linear ramp from base/10 to base
        (large-batch warm-up, the reference C6 settings.py warmup knob)."""
        cfg = self.cfg
        spe = self.steps_per_epoch
        base = cfg.lr
        if cfg.warmup_epochs > 0:
            w = cfg.warmup_epochs * spe
            inner = self._dataset_schedule(base, spe)
            inner_fn = (inner if callable(inner)
                        else (lambda step, v=inner: v))

            def schedule(step):
                ramp = base * (0.1 + 0.9 * jnp.minimum(step, w) / w)
                return jnp.where(step < w, ramp, inner_fn(step))

            return schedule
        return self._dataset_schedule(base, spe)

    def _dataset_schedule(self, base, spe):
        cfg = self.cfg
        if cfg.dataset == "cifar10":
            # x0.1 at 50% and 75% of training (classic CIFAR recipe). For
            # tiny max_epochs the two boundaries can collide or land at
            # step 0 (which would start training at 0.1x lr) — drop such
            # degenerate boundaries instead of silently merging them.
            boundaries = {}
            for frac in (0.5, 0.75):
                b = int(cfg.max_epochs * frac) * spe
                if b > 0 and b not in boundaries:
                    boundaries[b] = 0.1
            return optax.piecewise_constant_schedule(base, boundaries)
        if cfg.dataset == "imagenet":
            return optax.piecewise_constant_schedule(
                base, {30 * spe: 0.1, 60 * spe: 0.1, 80 * spe: 0.1}
            )
        if cfg.dataset == "ptb":
            # constant for 6 epochs then /1.25 per epoch (Zaremba-style decay)
            return lambda step: base * jnp.power(
                0.8, jnp.maximum(0, step // spe - 5)
            )
        if cfg.dataset == "an4":
            # deepspeech-style 1/1.01 per-epoch anneal
            return lambda step: base * jnp.power(1 / 1.01, step // spe)
        return base

    # ---------------------------------------------------------------- state
    def _init_params(self):
        """(params, batch_stats) of the freshly initialised model; sets
        ``num_params``, which the wire plan is decided from."""
        cfg = self.cfg
        rng = jax.random.PRNGKey(cfg.seed)
        batch = self._peek_batch()
        x = jnp.asarray(batch[self.spec.input_key][0])
        # One jitted call: the initialisers are all it runs (the forward
        # pass it traces is dead code there), where an eager init runs
        # every layer op by op (47 s of ResNet-50's set-up, PERF.md).
        variables = jax.jit(
            lambda key, x: self.model.init({"params": key, "dropout": key}, x)
        )(rng, x)
        params = variables["params"]
        n = sum(x.size for x in jax.tree.leaves(params))
        self.num_params = n
        self.logger.info(
            "model=%s dataset=%s params=%.3fM workers=%d compression=%s density=%g",
            cfg.dnn, cfg.dataset, n / 1e6, cfg.nworkers,
            cfg.compression, cfg.density,
        )
        return params, variables.get("batch_stats", {})

    def _init_state(self, params, batch_stats) -> Tuple[TrainState, Any]:
        opt_state = jax.jit(self.tx.init)(params)
        if self.p > 1:
            # The error-feedback residual is genuinely PER-DEVICE state (it
            # depends on each device's local gradients and top-k picks), so
            # it is carried as an explicit [P, N] leaf sharded P('dp') —
            # unlike the rest of the state, which is replicated.
            # Checkpointing then captures every device's residual, not just
            # device 0's.
            opt_state = expand_residual_per_device(opt_state, self.p, self.mesh)
        state = TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats=batch_stats,
            opt_state=opt_state,
        )
        carry = self._zero_carry()
        # Commit every leaf to its steady-state mesh placement. Freshly
        # built jnp arrays are UNCOMMITTED (SingleDeviceSharding), so
        # dispatch 1 would trace against UnspecifiedValue shardings while
        # its outputs come back committed-replicated — and dispatch 2
        # would then retrace and recompile the whole step: a full extra
        # XLA compile at startup and a permanent second cache entry the
        # recompile watch (obs/memwatch.py) flags. The residual is
        # already committed P('dp') by expand_residual_per_device and
        # passes through untouched.
        rep = NamedSharding(self.mesh, P())

        def commit(leaf):
            if getattr(leaf, "committed", False):
                return leaf
            return jax.device_put(leaf, rep)

        return jax.tree.map(commit, state), jax.tree.map(commit, carry)

    def _zero_carry(self):
        """The state a model threads from window to window, [P, ...] per
        leaf; () for a model that has none."""
        if not self.spec.carry:
            return ()
        one = self.model.initial_carry(self.cfg.batch_size)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (self.p,) + a.shape), one)

    def _peek_batch(self):
        it = iter(self.train_shards[0])
        b = next(it)
        return {k: v[None] for k, v in b.items()}

    def _abstract_batch(self):
        """ShapeDtypeStruct pytree of the canonical global dispatch
        batch ([P, (spd,) nsteps_update, B, ...] — the exact leaves
        _device_batch builds), for the AOT compile-accounting
        pass: lowering against it consumes no data and executes
        nothing. Carries the dispatch's real P('dp') sharding so the
        accounted executable is bit-for-bit the one the first dispatch
        runs — which also lets that dispatch hit the persistent
        compilation cache the AOT pass just warmed."""
        cfg = self.cfg
        lead = ((self.p, cfg.steps_per_dispatch, cfg.nsteps_update)
                if cfg.steps_per_dispatch > 1
                else (self.p, cfg.nsteps_update))
        return {
            k: jax.ShapeDtypeStruct(
                lead + tuple(np.asarray(v[0]).shape),
                np.asarray(v[0]).dtype, sharding=self._dp_sharding)
            for k, v in self._peek_batch().items()
        }

    # ------------------------------------------------------------ loss fns
    def _loss_fn(self, params, batch_stats, carry, batch, rng, train: bool):
        """Per-device loss. Returns (loss, (new_batch_stats, new_carry, aux))."""
        model, kind = self.model, self.spec.loss
        variables = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats
        mutable = ["batch_stats"] if (train and batch_stats) else []
        kw = dict(train=train, rngs={"dropout": rng} if train else None)

        def run(x, *args):
            if mutable:
                out, mut = model.apply(variables, x, *args, mutable=mutable, **kw)
                return out, mut["batch_stats"]
            return model.apply(variables, x, *args, **kw), batch_stats

        if kind == "own":
            (loss, counts), new_bs = run(batch[self.spec.input_key],
                                         batch["targets"])
            aux = model_counters(counts)
            return loss, (new_bs, carry, aux)
        if kind == "tokens":
            (logits, new_carry), new_bs = run(batch["tokens"], carry)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["targets"]
            ).mean()
            aux = {"tokens": jnp.asarray(logits.shape[0] * logits.shape[1])}
            return loss, (new_bs, new_carry, aux)
        if kind == "ctc":
            logits, new_bs = run(batch["spectrogram"], batch["input_lengths"])
            t_out = logits.shape[1]
            out_len = self.model.output_length(batch["input_lengths"])
            logit_pad = (
                jnp.arange(t_out)[None, :] >= out_len[:, None]
            ).astype(jnp.float32)
            label_pad = (
                jnp.arange(batch["labels"].shape[1])[None, :]
                >= batch["label_lengths"][:, None]
            ).astype(jnp.float32)
            loss = optax.ctc_loss(
                logits, logit_pad, batch["labels"], label_pad
            ).mean()
            # Eval wants the logits for greedy decode; keep them out of the
            # train path (they'd bloat the scanned aux and be meaningless
            # after averaging).
            aux = {} if train else {"logits": logits}
            return loss, (new_bs, carry, aux)
        # vision
        x = batch["image"]
        if x.dtype == jnp.uint8:
            # Vision pipelines ship raw uint8 pixels across H2D (4x fewer
            # bytes than f32) and normalize HERE, on device, where XLA
            # fuses it into the first conv (wire-format notes in
            # data/cifar.py and data/imagenet.py).
            mean, std = _WIRE_STATS[self.cfg.dataset]
            x = (x.astype(jnp.float32) / 255.0 - jnp.asarray(mean)
                 ) / jnp.asarray(std)
        logits, new_bs = run(x)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["label"]
        ).mean()
        top1 = (logits.argmax(-1) == batch["label"]).mean()
        # top-5 (reference reported top-1/top-5 for vision — SURVEY.md §3.5)
        _, top5_idx = lax.top_k(logits, min(5, logits.shape[-1]))
        top5 = (top5_idx == batch["label"][:, None]).any(-1).mean()
        return loss, (new_bs, carry, {"top1": top1, "top5": top5})

    # ------------------------------------------------------------ the step
    def _build_train_step(self, tx=None):
        cfg, p = self.cfg, self.p
        tx = self.tx if tx is None else tx
        # Recovery holds the pre-step state snapshot across the dispatch
        # (skip restores it bit-identically), so buffer donation is off
        # when a recovery policy is active.
        donate = (0, 1) if self.recovery is None else ()

        def step(state: TrainState, carry, batch):
            # batch leaves: [nsteps_update, B, ...]; carry: per-device pytree.
            rng = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), state.step)
            if p > 1:
                rng = jax.random.fold_in(rng, lax.axis_index("dp"))

            def micro(acc, xs):
                mb, micro_idx = xs
                grads_sum, bs, cr = acc
                # Each micro-batch draws its own dropout mask (folding the
                # scan index in) — sharing one mask across the accumulation
                # would correlate the micro-gradients.
                mrng = jax.random.fold_in(rng, micro_idx)
                (loss, (bs, cr, aux)), grads = jax.value_and_grad(
                    self._loss_fn, has_aux=True
                )(state.params, bs, cr, mb, mrng, True)
                grads_sum = jax.tree.map(jnp.add, grads_sum, grads)
                return (grads_sum, bs, cr), (loss, aux)

            # The named scopes are metadata on the compiled operations: a
            # device trace prices the step by them; the arithmetic and the
            # fusion are what they were. Three levels, each read from an
            # operation's path by its own rule (perfbench/metrics/):
            #   gtopk/<stage>, the OUTERMOST counts (scoped.py): fwd_bwd and
            #     apply here; flatten, clip, unflatten and apply in the
            #     optimizer; accumulate, select, mask, repair in
            #     compression and ops/topk; allreduce[/round<i>] in the
            #     collectives; telemetry in the counters;
            #   layer/<kind>, the INNERMOST counts (layer_ms.py): the
            #     decoders' layer kinds, in models/*, inside gtopk/fwd_bwd;
            #   part/<name>, the INNERMOST within a kind (part_ms.py): proj,
            #     pointwise, layout, kernel through the attention kinds
            #     (layer/attn, layer/attn_window, layer/attn_full), in the
            #     mixers and models/decoder.py, forward and in the written-
            #     out backward passes alike. A loop whose body has kinds of
            #     its own (Keye's indexer) stands outside every part: what
            #     the compiler files under the loop's name is the body's.
            # An operation's pass is read from the same path: forward,
            # replay (a remat's second forward) or backward.
            with jax.named_scope("gtopk/fwd_bwd"):
                zero_grads = jax.tree.map(jnp.zeros_like, state.params)
                (grads, new_bs, new_carry), (losses, auxes) = lax.scan(
                    micro, (zero_grads, state.batch_stats, carry),
                    (batch, jnp.arange(cfg.nsteps_update)),
                )
                grads = jax.tree.map(
                    lambda g: g / cfg.nsteps_update, grads)
            updates, opt_state = tx.update(
                grads, state.opt_state, state.params
            )
            with jax.named_scope("gtopk/apply"):
                params = optax.apply_updates(state.params, updates)
            loss = losses.mean()
            aux = jax.tree.map(lambda a: a.mean(), auxes)
            if p > 1:
                loss = lax.pmean(loss, "dp")
                aux = jax.tree.map(lambda a: lax.pmean(a, "dp"), aux)
                if new_bs:
                    new_bs = jax.tree.map(lambda a: lax.pmean(a, "dp"), new_bs)
            new_state = TrainState(
                step=state.step + 1,
                params=params,
                batch_stats=new_bs,
                opt_state=opt_state,
            )
            return new_state, new_carry, loss, aux

        spd = cfg.steps_per_dispatch

        def run_steps(state, c, local_batch):
            """One or spd optimizer steps on the stripped (per-device)
            state. With spd > 1 the batch leaves carry an extra leading
            [spd] axis and the step runs under lax.scan — one dispatch,
            spd updates; per-step RNG stays exact because step() derives
            it from state.step, which increments inside the scan."""
            if spd == 1:
                return step(state, c, local_batch)

            def body(sc, mb):
                s, cc = sc
                s, cc, loss, aux = step(s, cc, mb)
                return (s, cc), (loss, aux)

            (s, c2), (losses, auxes) = lax.scan(
                body, (state, c), local_batch)
            # Report the LAST scanned step's loss/aux — identical
            # convention to the per-step path, whose caller also reads
            # the most recent step.
            return (s, c2, losses[-1],
                    jax.tree.map(lambda a: a[-1], auxes))

        def gtopk_train_step(state, carry, batch):
            # The function's name is the compiled step's: a reader finds
            # its programs in a device trace as jit_gtopk_train_step (the
            # XLA Modules line), whether built directly or under shard_map.
            # Both the p==1 direct path and the per-device shard_map block
            # see a leading shard dim of size 1 — strip it, run, restore.
            # The residual travels the same way: global [P, N], per-device
            # [1, N] inside the block, [N] inside step().
            c = jax.tree.map(lambda a: a[0], carry) if carry != () else ()
            if p > 1:
                # tree.map covers both the flat-[N] residual and the
                # layerwise per-leaf tuple.
                state = state._replace(opt_state=state.opt_state._replace(
                    residual=jax.tree.map(
                        lambda r: r[0], state.opt_state.residual)))
            s, c2, loss, aux = run_steps(
                state, c, jax.tree.map(lambda b: b[0], batch)
            )
            if p > 1:
                s = s._replace(opt_state=s.opt_state._replace(
                    residual=jax.tree.map(
                        lambda r: r[None], s.opt_state.residual)))
            if carry != ():
                c2 = jax.tree.map(lambda a: a[None], c2)
            return s, c2, loss, aux

        if p == 1:
            return jax.jit(gtopk_train_step, donate_argnums=donate)

        # Per-leaf specs: everything in the state is replicated EXCEPT the
        # error-feedback residual, which is per-device ([P, N], sharded over
        # 'dp'). check_vma stays off for a structural reason: the gtopk
        # result is value-identical on every device (the hypercube merge is
        # symmetric) but built from lax.ppermute exchanges, which the
        # varying-axes checker must conservatively type as device-varying —
        # it cannot prove value-level replication without an O(N) collective
        # on the hot path. Replication of params/opt state is instead
        # asserted by tests (tests/test_optimizer.py replica-consistency,
        # tests/test_trainer.py::test_residual_sharding_multiworker).
        state_spec = TrainState(
            step=P(), params=P(), batch_stats=P(),
            # telemetry scalars are pmean'd inside the optimizer, so P()
            # (replicated) is sound for them.
            opt_state=GTopKSGDState(count=P(), residual=P("dp"), inner=P(),
                                    telemetry=P()),
        )
        smapped = jax.shard_map(
            gtopk_train_step,
            mesh=self.mesh,
            in_specs=(state_spec, P("dp"), P("dp")),
            out_specs=(state_spec, P("dp"), P(), P()),
            check_vma=False,
        )
        return jax.jit(smapped, donate_argnums=donate)

    def _build_eval_step(self):
        """Eval step; sharded over the mesh when p > 1 (round-2 review
        weak #6: the reference evaluated rank-0-only — SURVEY.md §3.5 —
        which serializes the whole val set through one chip while P-1
        idle; TPU-first eval spreads P val batches per dispatch over the
        same P('dp') convention training uses, so eval walltime scales
        ~1/P). The PTB LSTM keeps the sequential path: its eval threads a
        BPTT carry through the val stream in order, which is semantically
        serial. Per-shard metrics come back un-reduced ([P]-leading) and
        are weighted on host — identical arithmetic to the sequential
        path, no psum needed."""
        def ev(params, batch_stats, carry, batch):
            loss, (_, new_carry, aux) = self._loss_fn(
                params, batch_stats, carry, batch,
                jax.random.PRNGKey(0), False,
            )
            return loss, new_carry, aux

        # Multi-process runs keep the sequential path too: the sharded
        # step's [P]-leading outputs span non-addressable devices there,
        # so np.asarray on them would raise — and with 1 device per host
        # there is nothing to shard locally anyway.
        if (self.p == 1 or self.spec.carry
                or jax.process_count() > 1):
            def single(state, carry, batch):
                return ev(state.params, state.batch_stats, carry, batch)
            self._eval_sharded = False
            return jax.jit(single)

        def block(params, batch_stats, batch):
            # [1, B, ...] per-device shard -> strip, run, restore the
            # leading dim so out_specs P('dp') reassembles [P] metrics.
            loss, _, aux = ev(params, batch_stats, (),
                              jax.tree.map(lambda b: b[0], batch))
            pad = lambda a: a[None]
            return pad(loss), jax.tree.map(pad, aux)

        smapped = jax.shard_map(
            block, mesh=self.mesh,
            in_specs=(P(), P(), P("dp")),
            out_specs=(P("dp"), P("dp")),
            check_vma=False,
        )

        def sharded(state, carry, batch):
            loss, aux = smapped(state.params, state.batch_stats, batch)
            return loss, carry, aux

        self._eval_sharded = True
        return jax.jit(sharded)

    # ------------------------------------------------------------- batches
    def _shard_batches(self, iters):
        """One host batch as a list of per-shard dicts (this process's
        mesh positions, in mesh order) with leaves [nsteps_update, B, ...],
        and the bytes copied to make it. No axis across the shards exists
        on the host: each shard's rows are bound for a different chip."""
        n = self.cfg.nsteps_update
        shards, copied = [], 0
        for it in iters:
            micro = [next(it) for _ in range(n)]
            shard = {}
            for k in micro[0]:
                shard[k], nbytes = _lead_axis([m[k] for m in micro])
                copied += nbytes
            shards.append(shard)
        return shards, copied

    def _device_batch(self, shards):
        """Per-shard host batches -> device arrays [P, ...] sharded
        P('dp') over the mesh. Shard i's leaves go straight from their
        host arrays to this process's i-th mesh device, and the global
        array is built from those pieces: no stack on the host, no stop
        on the default device, and one path for a single process and for
        a multi-host run (where the pieces are this process's part)."""
        pieces = jax.device_put(
            [{k: np.asarray(v)[None] for k, v in s.items()} for s in shards],
            self._local_devices)
        return {
            k: jax.make_array_from_single_device_arrays(
                (self.p,) + pieces[0][k].shape[1:], self._dp_sharding,
                [piece[k] for piece in pieces])
            for k in pieces[0]
        }

    def _fetch_host(self, step: int, spd: int) -> List[Dict[str, np.ndarray]]:
        """One host batch (per-shard dicts) from the prefetcher (or
        synchronously); its copied bytes go to the ``train`` record's
        ``host_copied_mb``. With an injector active, loader faults
        (injected or real) are absorbed by the shared retry helper — a
        transient IOError costs a retry, not the run."""
        def fetch():
            if self.injector is not None:
                self.injector.check_loader(step, step + spd)
            return (next(self._prefetch) if self._prefetch is not None
                    else self._shard_batches(self._iters))

        if self.injector is None:
            shards, copied = fetch()
        else:
            from gtopkssgd_tpu.resilience import retry_call

            shards, copied = retry_call(
                fetch, retries=2, delay=0.05, logger=self.logger,
                desc="host batch fetch")
        self._host_copied_bytes += copied
        return shards

    def _read_counters(self, step: int, counters, loss, aux, *,
                       lagged: bool) -> None:
        """Read one dispatched step's counters, loss and model counters
        in one transfer (it blocks until that step ran) and write what
        the step says: the scalars as one "obs" record, the per-layer [L]
        columns as one "layers" record a layer, and the anomaly monitor's
        observation. ``step`` is the optimizer step the dispatch ended
        on; ``counters`` are ``readable_counters`` of its state, or their
        held copies where a later dispatch has donated that state
        (``lagged``: one is already queued behind the step read). The
        model's own counters (an expert layer's loads) ride the step's
        aux: same sync, same "obs" record."""
        spd = self.cfg.steps_per_dispatch
        with self.tracer.span("obs_read", step=step - spd):
            counters, loss, aux = jax.device_get((counters, loss, aux))
            scalars = telemetry_scalars(counters)
            self.metrics.log("obs", step=step, **scalars,
                             **model_scalars(aux))
            max_age = None
            cols = counters.get("layers")
            if cols is not None:
                ages = cols.get("residual_age")
                if ages is not None and ages.size:
                    max_age = float(np.max(ages))
                for i, lname in enumerate(self._layer_names):
                    self.metrics.log(
                        "layers", step=step, layer=lname,
                        **{f: float(c[i]) for f, c in cols.items()})
            if self.timeline is not None:
                self.timeline.counter("obs", scalars)
        # The step is synced by the read above, so feeding the monitor
        # costs nothing extra.
        if self.monitor is not None:
            self.monitor.observe(
                step, loss=float(loss), telemetry=scalars,
                max_residual_age=max_age)
        self._obs_reads["lagged" if lagged else "sync"] += 1

    # -------------------------------------------------------------- train
    def train(self, num_iters: int, epoch: int = 0) -> Dict[str, float]:
        """Run `num_iters` optimizer steps (reference DLTrainer.train)."""
        cfg = self.cfg
        inj, rec, guard = self.injector, self.recovery, self.preempt
        gp = self.goodput
        t_start, samples = time.perf_counter(), 0
        last_loss, last_aux = float("nan"), {}
        if num_iters <= 0:
            return {"loss": float("nan"), "throughput": 0.0, "wall": 0.0}
        # Host-side mirror of state.step: reading int(self.state.step) would
        # block on the device every iteration and kill async IO/compute
        # overlap; the mirror is exact (the step increments by
        # steps_per_dispatch per dispatch, and so does the mirror below).
        step = int(self.state.step)
        if self.cfg.prefetch > 0 and self._prefetch is None:
            # close() drained batches the worker had already pulled from
            # self._iters; silently falling back to the sync path would
            # skip them. Training may only resume through _set_iters
            # (restore() does this) or a fresh Trainer.
            raise RuntimeError(
                "Trainer is closed; build a new Trainer (restore() "
                "re-opens it only when a saved checkpoint exists)"
            )
        spd = cfg.steps_per_dispatch
        if spd > 1 and num_iters % spd != 0:
            raise ValueError(
                f"num_iters={num_iters} must be a multiple of "
                f"steps_per_dispatch={spd} (one compiled program per "
                "dispatch shape; a ragged tail would compile a second)")
        wd = self.watchdog
        if wd is not None:
            wd.arm("train", step=step)
        if gp is not None:
            # First entry: everything since init not yet attributed is
            # startup; re-entries (fit()'s later epochs) drop the
            # inter-epoch span (eval/ckpt marked their own shares; the
            # rest is honestly `other`).
            gp.train_started()
        # Whether the counter read may lag the dispatch by one (see the
        # read below): not where a recovery policy, an injector, a halt
        # rule, the memory watch or an elastic fleet's eviction check
        # may act on a step's reading before the next step is queued.
        lag_ok = (rec is None and inj is None and cfg.obs_halt_on is None
                  and self.memwatch is None and not cfg.elastic)
        # (step, counters, loss, aux) of a dispatched step not yet read.
        held = None

        def read_held(lagged):
            """Read the held step, if any; returns its step id."""
            nonlocal held
            if held is None:
                return None
            reading, held = held, None
            self._read_counters(*reading, lagged=lagged)
            return reading[0]

        try:
            for _ in range(num_iters // spd if spd > 1 else num_iters):
                # Preemption flag check at the iteration boundary: the
                # signal handler (resilience/preempt.py) only sets the
                # flag; the emergency save + unwind happen HERE, where
                # the state is whole. Under --elastic a preemption is a
                # RESIZE to P-1 (the fleet re-forms without the lost
                # capacity) unless that would shrink below min_fleet,
                # in which case _resize_now falls back to exit-45
                # preempt semantics.
                if guard is not None and guard.triggered:
                    read_held(lagged=False)
                    if cfg.elastic:
                        self._resize_now(self.p - 1, reason="preempt")
                    self._preempt_now()
                # Degrade cooldown expiry: re-enter the sparse step.
                if self._degraded and step >= self._degrade_until:
                    self._train_step = self._sparse_step
                    self._degraded = False
                    if rec is not None:
                        rec.degraded = False
                        rec.record("sparse_resume", step=step)
                if inj is not None:
                    inj.sleep_if_slow(step, step + spd)
                    if gp is not None:
                        # Injected slowness is exactly the skew-wait the
                        # taxonomy's `wait` bucket accounts.
                        gp.mark("wait")
                with self.tracer.span("io", step=step):
                    # io/wait: blocked on the prefetch queue (or, without
                    # a prefetcher, assembling the batch here).
                    with self.tracer.span("wait"):
                        hosts = [self._fetch_host(step, spd)
                                 for _ in range(spd)]
                    if spd == 1:
                        host = hosts[0]
                    else:
                        # Per shard [spd, nsteps_update, B, ...]: the
                        # scan axis leads (gtopk_train_step strips the
                        # shard dim first). A forced copy.
                        host = [
                            {k: np.stack([h[i][k] for h in hosts])
                             for k in hosts[0][i]}
                            for i in range(len(hosts[0]))
                        ]
                        self._host_copied_bytes += sum(
                            v.nbytes for s in host for v in s.values())
                    if inj is not None:
                        # reshape fault: a deliberately different
                        # dispatch shape (B axis sits after the micro —
                        # and with spd > 1 the scan — dim).
                        host = inj.reshape_batch(
                            host, step, step + spd,
                            axis=1 if spd == 1 else 2)
                    # io/put: the hand-over to the runtime, one put per
                    # local shard. It returns before the batch is on the
                    # chips.
                    with self.tracer.span("put"):
                        batch = self._device_batch(host)
                if gp is not None:
                    gp.mark("data")  # host batch assembly + H2D
                if rec is not None:
                    # Pre-step snapshot: what a `skip` action restores.
                    # Valid across the dispatch because donation is
                    # disabled whenever recovery is active.
                    prev_state, prev_carry = self.state, self.carry
                if inj is not None:
                    self.state = inj.poison_params(
                        self.state, step, step + spd)
                calib_now = (
                    self.calib is not None and cfg.obs_calib_interval > 0
                    and (step + spd) % cfg.obs_calib_interval < spd)
                # Critpath rides the SAME capture cadence (and the same
                # captured trace, when both are on) — one profiled
                # dispatch serves both consumers.
                critpath_now = (
                    cfg.obs_critpath and cfg.obs_calib_interval > 0
                    and (step + spd) % cfg.obs_calib_interval < spd)
                capture_now = calib_now or critpath_now
                with self.tracer.span("dispatch", step=step):
                    # Async enqueue only — the span must NOT drain the
                    # queue (the overlap is the point). The step's device
                    # time is the jit_gtopk_train_step program in a
                    # profiler trace, which starts once its inputs are on
                    # the chip and the step queued before it has ended:
                    # step_start_lag_ms (perfbench) is that wait.
                    if capture_now:
                        # Calibration sample: profile exactly this
                        # dispatch, blocking inside the capture so the
                        # device comm events land in the trace — a sync
                        # plus profiler overhead, which is why the
                        # cadence is opt-in (obs_calib_interval).
                        import tempfile

                        from gtopkssgd_tpu.obs.trace_attr import capture
                        trace_dir = tempfile.mkdtemp(prefix="calib_trace_")
                        with capture(trace_dir):
                            self.state, self.carry, loss, aux = (
                                self._train_step(self.state, self.carry,
                                                 batch))
                            jax.block_until_ready(loss)
                    else:
                        self.state, self.carry, loss, aux = self._train_step(
                            self.state, self.carry, batch
                        )
                samples += (cfg.batch_size * cfg.nworkers
                            * cfg.nsteps_update * spd)
                if gp is not None:
                    # The dispatch span is step time: split by the
                    # latest critpath stage fractions (all goodput until
                    # one exists); while degraded, the excess over the
                    # clean-step EWMA is the degraded-mode delta.
                    gp.step_mark(begin=True, degraded=self._degraded)
                step += spd
                if critpath_now:
                    # Must run BEFORE the calibrator feed — that call
                    # deletes the trace dir when it finishes.
                    self._log_critpath(step, spd, trace_dir,
                                       cleanup=not calib_now)
                if calib_now:
                    self._feed_calibrator(step, spd, trace_dir)
                if capture_now and self.forecaster is not None:
                    # One forecast per capture: compose the budgets and
                    # fit the two feeds above just refreshed into a
                    # durable "forecast" record, then the drift rule
                    # (which may raise AnomalyHalt — after the record).
                    self.forecaster.observe(step)
                if capture_now and gp is not None:
                    # Host-side trace attribution is observability
                    # overhead — no taxonomy bucket; drop it to `other`
                    # rather than inflate a category it isn't.
                    gp.mark(None)
                if inj is not None:
                    # preempt injection delivers a real SIGTERM through
                    # the installed guard; the flag check right after
                    # makes the firing step-deterministic.
                    inj.maybe_preempt(step - spd, step, guard)
                    # resize@K:NEWP / evict_rank:R@K fire at the same
                    # post-dispatch boundary (durable "inject" record
                    # either way; no-op warning without --elastic).
                    self._check_injected_resize(step - spd, step)
                if guard is not None and guard.triggered:
                    # The step before's records first, as they always
                    # were written before this save and unwind.
                    read_held(lagged=True)
                    if cfg.elastic:
                        self._resize_now(self.p - 1, reason="preempt")
                    self._preempt_now()
                # With spd > 1 a dispatch may jump over the exact
                # boundary; log when any step inside it crossed one.
                log_now = step % cfg.log_interval < spd
                # The counter read (obs.counters, carried in
                # opt_state.telemetry) blocks until the step it reads
                # ran, which is also the watchdog's honest progress
                # proof. It lags the dispatch by one: this step's
                # counters are copied out (the next dispatch donates the
                # state they live in) and held, and the read that blocks
                # here is the step before's, with this step queued behind
                # it. So the chip goes from one step to the next while
                # the host reads, fetches the next batch and dispatches.
                # The read stays on this step where something may act on
                # it before the next dispatch (lag_ok, a capture), or
                # where the "train" row below waits for this step anyway.
                counters = None
                if (cfg.obs_counters and cfg.obs_interval > 0
                        and step % cfg.obs_interval < spd):
                    tel = self.state.opt_state.telemetry
                    if tel:
                        counters = readable_counters(tel)
                hold = (counters is not None and lag_ok
                        and not (capture_now or log_now))
                if hold:
                    # Queued behind this step, before the host blocks.
                    counters = _hold_counters(counters)
                # The step a blocking read proved to have run, if any;
                # whether the monitor saw this iteration's step.
                synced, observed = read_held(lagged=True), False
                if hold:
                    held = (step, counters, loss, aux)
                elif counters is not None:
                    self._read_counters(step, counters, loss, aux,
                                        lagged=False)
                    synced, observed = step, self.monitor is not None
                if log_now:
                    last_loss = float(loss)
                    last_aux = {k: float(v) for k, v in aux.items()}
                    elapsed = time.perf_counter() - t_start
                    row = dict(
                        step=step, epoch=epoch, loss=last_loss,
                        throughput=samples / elapsed,
                        # Megabytes the batch assembly copied on the host
                        # since the last such record: 0 on the view path.
                        host_copied_mb=self._host_copied_bytes / 1e6,
                        # Counter reads since the last such record.
                        obs_reads_lagged=self._obs_reads["lagged"],
                        obs_reads_sync=self._obs_reads["sync"],
                        **last_aux, **self._model_forms,
                    )
                    self._host_copied_bytes = 0
                    self._obs_reads = {"lagged": 0, "sync": 0}
                    if cfg.dataset == "ptb":
                        row["ppl"] = float(np.exp(min(last_loss, 20.0)))
                    self.metrics.log("train", **row)
                    self.tracer.flush(step)
                    if self.timeline is not None:
                        self.timeline.counter("train", row)
                    # Monitor at the log cadence too, so NaN detection
                    # works with obs counters disabled (loss only — the
                    # float() above already paid the sync).
                    if self.monitor is not None and not observed:
                        self.monitor.observe(step, loss=last_loss)
                        observed = True
                    synced = step
                if gp is not None:
                    # The obs/log blocking reads drained the dispatched
                    # step — that wait IS step time, same split as the
                    # dispatch span (tiny when nothing synced).
                    gp.step_mark(degraded=self._degraded)
                if rec is not None:
                    # Apply any actions the monitor's claims queued this
                    # iteration. `step` may rewind (skip/rollback restore
                    # an earlier state) — the host mirror follows the
                    # restored state.step so the data stream and LR
                    # schedule stay aligned.
                    pending = rec.pop_pending()
                    if pending:
                        step = self._apply_recovery(
                            pending, prev_state, prev_carry, step)
                    elif observed:
                        rec.note_ok()
                if synced is not None:
                    # The newest step known complete that the state
                    # still holds: behind `step` after a lagged read,
                    # `step` itself after a rewind.
                    synced = min(synced, step)
                if wd is not None and synced is not None:
                    wd.heartbeat(step=synced)
                if self.memwatch is not None and synced is not None:
                    # Compile/memory watch at a sync the loop already
                    # paid: accounts a never-seen dispatch shape (one
                    # fsync'd "compile" record), logs executable-cache
                    # growth, samples live memory every
                    # obs_mem_interval steps. May raise AnomalyHalt
                    # (recompile_storm / device_mem_leak /
                    # hbm_headroom) — records are durably written
                    # first.
                    self.memwatch.poll(
                        step, fn=self._train_step,
                        args=(self.state, self.carry, batch))
                    if gp is not None:
                        # A never-seen dispatch shape AOT-compiles here;
                        # warm polls cost ~nothing.
                        gp.mark("compile")
                if gp is not None and synced is not None:
                    # Periodic durable "goodput" record + the
                    # goodput_collapse feed, at a sync the loop already
                    # paid. AnomalyHalt propagates AFTER the record is
                    # durable, like every monitor halt.
                    gp.tick(synced)
                    # Elastic eviction self-check, every
                    # evict_after_windows goodput windows (rank 0 — it
                    # owns the merged fleet view): a persistently
                    # underperforming rank named by goodput advise()
                    # triggers the evict resize path.
                    if (cfg.elastic and cfg.evict_after_windows > 0
                            and cfg.obs_goodput_interval > 0
                            and cfg.out_dir
                            and self.process_rank == 0
                            and step % (cfg.obs_goodput_interval
                                        * cfg.evict_after_windows)
                            < spd):
                        self._maybe_evict(step)
            # The last dispatched step's read: nothing is queued behind
            # it. Every call returns with every record written and
            # nothing held.
            if read_held(lagged=False) is not None and gp is not None:
                gp.step_mark(degraded=self._degraded)
                gp.tick(step)
            with self.tracer.span("final_sync"):
                jax.block_until_ready(self.state)
            if gp is not None:
                # Draining the last dispatched steps is step time too.
                gp.step_mark(degraded=self._degraded)
            if wd is not None:
                wd.heartbeat(step=step)
        finally:
            if wd is not None:
                wd.disarm()
        wall = time.perf_counter() - t_start
        return {
            "loss": float(loss),
            "throughput": samples / wall,
            "wall": wall,
            **{k: float(v) for k, v in aux.items()},
        }

    # --------------------------------------------------------------- eval
    def test(self) -> Dict[str, float]:
        """Full-validation metrics (reference DLTrainer.test): top-1 for
        vision, perplexity for PTB, greedy-decode CER for AN4. When the
        eval step is sharded (p > 1, non-LSTM) the val stream is consumed
        in groups of P batches per dispatch; a partial tail group is
        padded by repeating its last batch, with the pad shards excluded
        from the host-side weighting (weight bookkeeping is per REAL
        batch, so the numbers are identical to the sequential path)."""
        cfg = self.cfg
        spec = self.spec
        losses, top1s, top5s, weights = [], [], [], []
        cer_counts = np.zeros(4, np.int64)  # char errs, chars, word errs, words
        carry = (
            self.model.initial_carry(cfg.batch_size) if spec.carry else ()
        )

        def account(batch, loss, aux):
            losses.append(float(loss))
            weights.append(len(next(iter(batch.values()))))
            if "top1" in aux:
                top1s.append(float(aux["top1"]))
            if "top5" in aux:
                top5s.append(float(aux["top5"]))
            if spec.loss == "ctc":
                cer_counts[:] += self._greedy_error_counts(
                    batch, aux["logits"])

        def flush_group(group):
            nvalid = len(group)
            while len(group) < self.p:  # pad shards, zero-weighted below
                group.append(group[-1])
            loss, _, aux = self._eval_step(
                self.state, (), self._device_batch(group))
            loss = np.asarray(loss)
            aux = {k: np.asarray(v) for k, v in aux.items()}
            for i in range(nvalid):
                account(group[i], loss[i],
                        {k: v[i] for k, v in aux.items()})

        group = []
        for i, batch in enumerate(self.val_data.epoch(0)):
            if cfg.eval_batches is not None and i >= cfg.eval_batches:
                break
            if getattr(self, "_eval_sharded", False):
                group.append(batch)
                if len(group) == self.p:
                    flush_group(group)
                    group = []
                continue
            jb = {k: jnp.asarray(v) for k, v in batch.items()}
            loss, carry_out, aux = self._eval_step(self.state, carry, jb)
            if spec.carry:
                carry = carry_out
            account(jb, loss, aux)
        if group:
            flush_group(group)
        w = np.asarray(weights, np.float64)
        mean_loss = float(np.average(losses, weights=w)) if losses else float("nan")
        out = {"val_loss": mean_loss}
        if top1s:
            out["val_top1"] = float(np.average(top1s, weights=w))
        if top5s:
            out["val_top5"] = float(np.average(top5s, weights=w))
        if cfg.dataset == "ptb":
            out["val_ppl"] = float(np.exp(min(mean_loss, 20.0)))
        if cer_counts[1] > 0:
            out["val_cer"] = float(cer_counts[0] / cer_counts[1])
            out["val_wer"] = float(cer_counts[2] / max(1, cer_counts[3]))
        self.metrics.log("eval", step=int(self.state.step), **out)
        if self.goodput is not None:
            # Eval is productive work — the job exists to train AND
            # measure the model — so it accrues to goodput, not to a
            # badput bucket (the taxonomy has none for it) and not to
            # `other` (which must stay an accounting gap, pinned ~0 on
            # clean runs by the gate smoke).
            self.goodput.mark("goodput")
        return out

    # Space in the 29-char AN4 vocabulary (LABELS = "_'A..Z ") — word
    # boundary for WER.
    _AN4_SPACE_ID = 28

    def _greedy_error_counts(self, batch, logits) -> np.ndarray:
        """Greedy CTC decode -> [char_errors, chars, word_errors, words]
        (reference reported WER/CER for AN4 via greedy decode — SURVEY.md
        §3.5). Error rates are aggregated corpus-level (sum of edit
        distances / sum of reference lengths), the standard ASR convention.
        `logits` come from the jitted eval step — no second forward pass;
        the blank/repeat collapse is vectorized, only the per-utterance
        edit distance (C++, gtopkssgd_tpu.native) runs in a loop."""
        pred = np.asarray(logits.argmax(-1))  # [B, T']
        out_len = np.asarray(self.model.output_length(batch["input_lengths"]))
        labels = np.asarray(batch["labels"])
        lab_len = np.asarray(batch["label_lengths"])
        bsz, t_out = pred.shape
        valid = np.arange(t_out)[None, :] < out_len[:, None]
        prev = np.concatenate(
            [np.zeros((bsz, 1), pred.dtype), pred[:, :-1]], axis=1)
        keep = valid & (pred != 0) & (pred != prev)

        def words(seq):
            out, cur = [], []
            for c in seq:
                if c == self._AN4_SPACE_ID:
                    if cur:
                        out.append(tuple(cur))
                    cur = []
                else:
                    cur.append(c)
            if cur:
                out.append(tuple(cur))
            return out

        counts = np.zeros(4, np.int64)
        for b in range(bsz):
            seq = pred[b][keep[b]].tolist()
            ref = labels[b, : lab_len[b]].tolist()
            counts[0] += native.edit_distance(seq, ref)
            counts[1] += max(1, len(ref))
            # word-level: map word tuples to ids, edit-distance those
            sw, rw = words(seq), words(ref)
            ids = {}
            to_ids = lambda ws: [ids.setdefault(t, len(ids)) for t in ws]
            counts[2] += native.edit_distance(to_ids(sw), to_ids(rw))
            counts[3] += max(1, len(rw))
        return counts

    # ----------------------------------------------------------- epochs/ckpt
    def fit(self, max_epochs: Optional[int] = None) -> Dict[str, float]:
        """Epoch loop: train + eval + checkpoint (reference dist_trainer
        main loop)."""
        cfg = self.cfg
        epochs = max_epochs or cfg.max_epochs
        if cfg.steps_per_dispatch > 1 and (
                self.steps_per_epoch % cfg.steps_per_dispatch != 0):
            raise ValueError(
                f"steps_per_dispatch={cfg.steps_per_dispatch} must divide "
                f"steps_per_epoch={self.steps_per_epoch} for epoch "
                "training (train() dispatches fixed-shape programs)")
        result = {}
        # Resume-aware: a restored state at step S has completed S /
        # steps_per_epoch epochs; train only the remainder (restore() already
        # fast-forwarded the data iterators to this epoch's permutation).
        start_epoch = int(self.state.step) // self.steps_per_epoch
        for epoch in range(start_epoch, epochs):
            self.reset_carry()  # BPTT state does not cross epochs (ref §3.4)
            train_stats = self.train(self.steps_per_epoch, epoch=epoch)
            result = {**train_stats, **self.test()}
            self.metrics.log("epoch", epoch=epoch, **result)
            if self._ckpt is not None:
                self.save()
        return result

    def reset_carry(self) -> None:
        """Zero the recurrent carry (epoch boundary: each PTB row restarts at
        its stream start, so end-of-corpus state must not leak in)."""
        self.carry = self._zero_carry()

    def save(self) -> None:
        """Orbax save of the LIVE (sharded) state. Every process must call
        this — orbax coordinates multi-host writes internally and each
        process persists its addressable shards of the P('dp') residual;
        a host-side numpy conversion would crash on multi-host (the
        residual spans non-addressable devices) and was how round 1 lost
        every rank-but-0 residual."""
        if self._ckpt is not None:
            # meta.residual_p: the residual's partition width, so an
            # elastic different-P resume can build the OLD-shape
            # template without guessing (utils/checkpoint.py sidecar).
            self._ckpt.save(int(self.state.step), self.state,
                            meta=self._ckpt_meta())
            if self.goodput is not None:
                self.goodput.mark("ckpt")

    def _ckpt_meta(self) -> dict:
        """What a restoring run has to know of the saved state before it
        can build a template (utils/checkpoint.py sidecar): the
        residual's partition width, so that an elastic different-P resume
        need not guess the old shape, and its form, slabs
        (optimizer.py's slabs form) or the flat [N] buffers every mesh run
        and every checkpoint older than the leaf form holds."""
        return {"residual_p": self.p,
                "residual_form": "slabs" if self._slab_state else "flat"}

    def restore(self) -> bool:
        if self._ckpt is None or self._ckpt.latest_step() is None:
            return False
        if self.injector is not None:
            # corrupt_ckpt@latest fires here, right before the read — the
            # restore path's torn-checkpoint fallback is what's under test.
            self.injector.maybe_corrupt_ckpt(self._ckpt.directory)
        # Abstract template with explicit shardings: orbax restores every
        # leaf directly INTO its target placement — replicated over the
        # mesh for params/step/momentum, P('dp') for the per-device
        # residual (no dense single-device materialization, and every
        # process of a multi-host run reads only its own residual shards).
        # Elastic resumes first consult the sidecar's residual_p: a
        # checkpoint saved at a DIFFERENT fleet size takes the
        # re-partitioning path instead of the shape-identical one.
        meta = self._ckpt.sidecar_meta()
        old_p = 0
        if self.cfg.elastic:
            old_p = int(meta.get("residual_p") or 0)
        old_slabs = meta.get("residual_form") == "slabs"
        # A one-device checkpoint from before the leaf form holds flat
        # buffers where this run holds slabs: the same path, no resize.
        if (old_p and old_p != self.p) or old_slabs != self._slab_state:
            self.state = self._restore_resized(old_p or self.p, old_slabs)
        else:
            self.state = self._ckpt.restore(
                self._state_template(),
                allow_mismatch=self.cfg.allow_ckpt_mismatch)
        step = int(self.state.step)
        self.logger.info("restored step %d from %s", step,
                         self._ckpt.directory)
        # Fast-forward the data stream to the restored position. Epoch
        # checkpoints land on a boundary (skip_steps=0); emergency
        # preemption saves land MID-epoch, and the remainder drains that
        # many steps' batches so the resumed trace is the uninterrupted
        # one.
        self._set_iters(step // self.steps_per_epoch,
                        skip_steps=step % self.steps_per_epoch)
        if self.goodput is not None:
            # Restore + iterator fast-forward are checkpoint cost.
            self.goodput.mark("ckpt")
        return True

    def _restore_resized(self, old_p: int, old_slabs: bool):
        """Restore a checkpoint whose per-device buffers another fleet
        size or another form wrote: an elastic resize (the residual is
        partitioned over ``old_p`` rows, this run's over ``self.p``), a
        one-device run resumed on a mesh or the reverse (slabs against
        flat [N] buffers), a one-device checkpoint older than the leaf
        form. Build a template in the SAVED shape, from the state the
        same configuration makes in that form — replicated, since old_p
        need not divide the new mesh — so the integrity digest verifies
        against what was actually written; then, host-side, bring the
        residual to flat rows, re-partition them (resilience/elastic.py:
        grow = zero rows, shrink = masked-fold addition conserving the
        pending gradient mass) and commit them in this run's form and
        placement. The age buffer of ``--obs-layers`` follows the
        residual's form; every other leaf restores shape-identically."""
        from gtopkssgd_tpu.resilience.elastic import repartition_buffer

        rep = NamedSharding(self.mesh, P())
        params = self.state.params

        def leaf(x, lead=()):
            return jax.ShapeDtypeStruct(lead + x.shape, x.dtype,
                                        sharding=rep)

        saved = jax.eval_shape(self._make_tx(slabs=old_slabs).init, params)
        has_age = "age" in (saved.telemetry or {})
        template = jax.tree.map(leaf, self.state)
        opt = template.opt_state._replace(residual=jax.tree.map(
            lambda r: leaf(r, (old_p,) if old_p > 1 else ()),
            saved.residual))
        if has_age:
            opt = opt._replace(telemetry=dict(
                opt.telemetry, age=jax.tree.map(leaf, saved.telemetry["age"])))
        restored = self._ckpt.restore(
            template._replace(opt_state=opt),
            allow_mismatch=self.cfg.allow_ckpt_mismatch)
        dp = NamedSharding(self.mesh, P("dp"))

        def reform(buffers):
            """Saved buffers -> this run's form, through flat vectors."""
            buffers = jax.tree.map(np.asarray, buffers)
            if old_slabs:
                buffers = flat_residual(buffers, params, np)
            return buffers

        def repartition(buf):
            out = repartition_buffer(buf if old_p > 1 else buf[None],
                                     max(1, self.p))
            if self.p == 1:
                return out[0]
            return jax.make_array_from_callback(
                out.shape, dp, lambda idx, o=out: o[idx])

        def commit(buffers):
            if self._slab_state:
                buffers = slab_residual(buffers, params, np)
            return jax.tree.map(
                lambda b: b if isinstance(b, jax.Array)
                else jax.device_put(b, rep), buffers)

        opt = restored.opt_state
        opt = opt._replace(residual=commit(jax.tree.map(
            repartition, reform(opt.residual))))
        if has_age:
            opt = opt._replace(telemetry=dict(
                opt.telemetry, age=commit(reform(opt.telemetry["age"]))))
        self.logger.warning(
            "restore: residual brought from %d row(s) of %s to %d of %s "
            "(pending gradient mass conserved)", old_p,
            "slabs" if old_slabs else "flat buffers", self.p,
            "slabs" if self._slab_state else "flat buffers")
        return restored._replace(opt_state=opt)

    # ---------------------------------------------------------- resilience
    def _preempt_now(self) -> None:
        """The preemption flag is set: force a step-granular emergency
        save (orbax force=True — the step may equal an existing epoch
        save) and unwind via Preempted, which dist_trainer maps to exit
        45. Runs on the train-loop thread where the state is whole."""
        from gtopkssgd_tpu.resilience import Preempted

        step = int(self.state.step)  # blocks: the save must be post-step
        if self._ckpt is not None:
            self._ckpt.save(step, self.state, force=True,
                            meta=self._ckpt_meta())
            if self.goodput is not None:
                # The emergency save is the preempt fault's designated
                # badput: ckpt.
                self.goodput.mark("ckpt")
            self.metrics.log("recovery", flush=True,
                             action="emergency_save", step=step)
            self.logger.warning(
                "preemption: emergency checkpoint at step %d -> %s",
                step, self._ckpt.directory)
        else:
            self.logger.warning(
                "preemption at step %d with no out_dir: nothing saved",
                step)
        raise Preempted(f"preemption signal at step {step}")

    def _resize_now(self, new_p: int, *, reason: str,
                    evicted_ranks=()) -> None:
        """Elastic resize: drain (the caller sits at an iteration
        boundary; int(state.step) blocks until the state is whole) ->
        emergency-save with the residual's partition width in the
        sidecar meta -> rewrite the elastic.json lineage file for the
        new P -> durable "resize" record -> ResizeRestart, which
        dist_trainer maps to exit 46. Everything lands on disk BEFORE
        the unwind, so the supervisor can relaunch the moment the
        process exits. A resize below min_fleet is refused: preemption
        falls back to classic exit-45 semantics, an eviction downgrades
        to a warning."""
        from gtopkssgd_tpu.resilience.elastic import (
            ResizeRestart, mint_lineage_id, write_lineage)

        cfg = self.cfg
        new_p = int(new_p)
        floor = max(1, cfg.min_fleet)
        if new_p < floor:
            self.logger.warning(
                "elastic: refusing resize %d -> %d below min_fleet=%d "
                "(%s)", self.p, new_p, floor, reason)
            if reason == "preempt":
                self._preempt_now()
            return
        if self._ckpt is None:
            self.logger.warning(
                "elastic: resize (%s) at step %d with no out_dir — "
                "nothing to hand the relaunch; ignoring",
                reason, int(self.state.step))
            return
        step = int(self.state.step)  # blocks: the save must be post-step
        self._ckpt.save(step, self.state, force=True,
                        meta=self._ckpt_meta())
        if self.goodput is not None:
            self.goodput.mark("ckpt")
        lineage = dict(self.lineage or {})
        lineage.update(
            lineage_id=lineage.get("lineage_id") or mint_lineage_id(),
            resize_epoch=int(lineage.get("resize_epoch", 0)) + 1,
            prev_p=self.p, p=new_p, reason=reason,
            evicted_ranks=[int(r) for r in evicted_ranks],
            drained_step=step)
        write_lineage(cfg.out_dir, **lineage)
        self.lineage = lineage
        self.metrics.log(
            "resize", flush=True, step=step, old_p=self.p, new_p=new_p,
            reason=reason,
            evicted_ranks=[int(r) for r in evicted_ranks],
            drained_step=step, restore_step=step,
            lineage_id=lineage["lineage_id"],
            resize_epoch=lineage["resize_epoch"])
        self.logger.warning(
            "elastic resize (%s): p %d -> %d at step %d; checkpoint + "
            "lineage durable under %s — relaunch with --resume "
            "--elastic --nworkers %d", reason, self.p, new_p, step,
            cfg.out_dir, new_p)
        raise ResizeRestart(
            f"resize {self.p} -> {new_p} ({reason}) at step {step}")

    def _check_injected_resize(self, prev: int, new: int) -> None:
        """Injected resize@K:NEWP / evict_rank:R@K at the step
        boundary. The durable "inject" record lands either way; without
        cfg.elastic the request downgrades to a warning, so a chaos
        spec cannot opt a run into semantics its flags didn't."""
        inj, cfg = self.injector, self.cfg
        new_p = inj.pending_resize(prev, new)
        if new_p is not None:
            if not cfg.elastic:
                self.logger.warning(
                    "inject: resize to P=%d ignored — run without "
                    "--elastic", new_p)
            else:
                self._resize_now(new_p, reason="inject")
        rank = inj.pending_evict(prev, new)
        if rank is not None:
            if not cfg.elastic:
                self.logger.warning(
                    "inject: evict_rank %d ignored — run without "
                    "--elastic", rank)
            else:
                self._resize_now(self.p - 1, reason="evict",
                                 evicted_ranks=(rank,))

    def _maybe_evict(self, step: int) -> None:
        """Elastic eviction self-check (every evict_after_windows
        goodput windows): merge this run's per-rank metric shards and
        act on resilience/elastic.py's ``eviction_decision`` — goodput
        ``advise()`` names the rank, the straggler EWMA corroborates.
        Naturally inert for single-shard runs (advise needs >= 2
        ranks' ledgers) and when the merge cannot be built: the
        self-check must never take down a healthy run."""
        cfg = self.cfg
        try:
            from gtopkssgd_tpu.obs import fleet
            from gtopkssgd_tpu.resilience.elastic import eviction_decision
            merged = fleet.merge([cfg.out_dir])
            decision = eviction_decision(
                merged, p=self.p, min_fleet=cfg.min_fleet)
        except Exception as e:
            self.logger.debug(
                "elastic: eviction self-check skipped (%s: %s)",
                type(e).__name__, e)
            return
        if decision is None:
            return
        self.logger.warning("elastic: eviction decision %s", decision)
        self._resize_now(decision["new_p"], reason="evict",
                         evicted_ranks=(decision["rank"],))

    def _apply_recovery(self, pending, prev_state, prev_carry,
                        step: int) -> int:
        """Apply the actions claimed during this iteration's monitor
        observations. Returns the (possibly rewound) host step mirror."""
        from gtopkssgd_tpu.obs.events import AnomalyHalt

        rec = self.recovery
        for event, spec in pending:
            rule = spec.rule
            if spec.action == "skip":
                # Discard the just-applied update: restore the pre-step
                # snapshot — params, momentum, step count, AND the
                # error-feedback residual, bit-identical (donation is off
                # under recovery, so the buffers are intact).
                self.state, self.carry = prev_state, prev_carry
                rec.consecutive_skips += 1
                step = int(self.state.step)
                if self.goodput is not None:
                    # The discarded update's step time was NOT progress:
                    # reclassify it as wasted (nan_grad's designated
                    # badput).
                    self.goodput.wasted_step()
                rec.record("skip", step, rule,
                           consecutive=rec.consecutive_skips,
                           budget=spec.budget)
            elif spec.action == "rollback":
                if self._ckpt is None or self._ckpt.latest_step() is None:
                    self.logger.error(
                        "recovery: rollback for rule %s but no checkpoint "
                        "exists — escalating to halt", rule)
                    raise AnomalyHalt(event)
                uses = rec.rollback_uses.get(rule, 0)
                wait = spec.param * (2 ** uses)
                rec.rollback_uses[rule] = uses + 1
                if wait > 0:
                    time.sleep(wait)
                self.restore()
                step = int(self.state.step)
                if self.goodput is not None:
                    # restore() marked its own span ckpt (backoff sleep
                    # included); the rewound step's attribution becomes
                    # wasted work.
                    self.goodput.wasted_step()
                rec.record("rollback", step, rule, backoff_s=wait,
                           use=uses + 1, budget=spec.budget)
            elif spec.action == "degrade":
                if self._degraded:
                    continue
                if self._dense_step is None:
                    # Dense-allreduce fallback over the SAME state
                    # treedef: the always-dense branch of the compiled
                    # update (warmup_dense_steps=2**30).
                    self._dense_step = self._build_train_step(
                        tx=self._make_tx(warmup_dense_steps=1 << 30))
                self._train_step = self._dense_step
                self._degraded = True
                rec.degraded = True
                rec.degrade_episodes += 1
                self._degrade_until = step + int(spec.param)
                rec.record("degrade", step, rule,
                           until_step=self._degrade_until,
                           episode=rec.degrade_episodes,
                           budget=spec.budget)
        return step

    def finalize_resilience(self, status: str) -> None:
        """End-of-run summary record — what ``report recovery`` and the
        gate smoke's structural checks key on. No-op for runs with no
        resilience surface (keeps default metrics files byte-stable)."""
        if (self.injector is None and self.recovery is None
                and status == "completed"):
            return
        n = self.recovery.n_recoveries if self.recovery is not None else 0
        self.metrics.log(
            "recovery", flush=True, action="summary", final_status=status,
            completed=int(status == "completed"), n_recoveries=n,
            step=int(self.state.step))

    def _state_template(self):
        rep = NamedSharding(self.mesh, P())

        def leaf(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep)

        template = jax.tree.map(leaf, self.state)
        if self.p > 1:
            dp = NamedSharding(self.mesh, P("dp"))
            template = template._replace(opt_state=template.opt_state._replace(
                residual=jax.tree.map(
                    lambda r: jax.ShapeDtypeStruct(
                        r.shape, r.dtype, sharding=dp),
                    self.state.opt_state.residual)))
        return template


