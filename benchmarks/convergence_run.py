"""On-hardware convergence curves: dense vs gtopk vs allgather, same seed.

The reference's top-level correctness gate is convergence-as-test (SURVEY.md
§4: "does it still reach baseline accuracy at rho=0.001") — its paper
figures are accuracy-vs-epoch curves per workload. The CI suite proves the
same property cheaply on an 8-way virtual CPU mesh
(tests/test_convergence.py); this runner produces the committed
on-hardware artifact: identical-seed training runs per compression mode on
the real chip, loss sampled every ``--chunk`` steps, held-out eval at the
end, one JSONL row per sample.

Steps-to-threshold uses ONE shared absolute reference for every mode (the
dense run's first sampled loss, falling back to the max across modes), so
the cross-mode comparison is like-for-like; per-mode "fraction of my own
first sample" would compare different absolute loss levels whenever early
transients differ between modes.

Data is the deterministic synthetic CIFAR stand-in (learnable class-mean
signal — data/cifar.py) unless ``--data-dir`` points at the real pickles;
with one chip the gtopk collective is a no-op but error-feedback
select/repair runs at full production semantics, which is exactly the
convergence-relevant machinery (the multi-device collective itself is
oracle-tested and convergence-tested 8-way in CI).

Usage:
  python benchmarks/convergence_run.py --dnn resnet20 --steps 1200 \
      --modes dense,gtopk,allgather --density 0.001
Writes benchmarks/results/convergence_<dnn>_<device>.jsonl
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

THRESHOLD_FRACS = (0.5, 0.2, 0.1, 0.02)


def max_epochs_for(args) -> int:
    """Epochs the --steps budget spans — mode-independent, computed ONCE.

    max_epochs drives the LR schedule; leaving it at 1 for a multi-epoch
    fixed-step run would degenerate the CIFAR decay boundaries to step 0
    (constant LR). steps_per_epoch is pure shard arithmetic — one rank-0
    dataset through the SAME helper the Trainer uses
    (trainer.py::shard_steps_per_epoch), no throwaway Trainer build.
    """
    from gtopkssgd_tpu.data import get_dataset
    from gtopkssgd_tpu.trainer import TrainConfig, shard_steps_per_epoch

    rcfg = TrainConfig(
        dnn=args.dnn, batch_size=args.batch_size,
        nworkers=args.nworkers or jax.device_count(),
        data_dir=args.data_dir,
    ).resolved()
    ds = get_dataset(rcfg.dataset, split="train", batch_size=rcfg.batch_size,
                     rank=0, nworkers=rcfg.nworkers,
                     data_dir=rcfg.data_dir or None, seed=args.seed)
    spe = shard_steps_per_epoch(ds, rcfg.batch_size, rcfg.nsteps_update)
    return max(1, math.ceil(args.steps / spe))


def run_mode(args, mode: str, density: float, max_epochs: int,
             stream=None):
    """Train one mode; returns (curve_rows, summary) — steps-to-threshold
    is computed later in main() against the shared reference. When
    ``stream`` is given, every curve row is also appended+flushed to it as
    it is measured: a multi-mode run is tens of minutes of compute, and a
    timeout/preemption mid-run must not lose the modes already measured
    (learned the hard way — a 50-minute 3-mode run died in mode 3 with
    nothing on disk)."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    # Arm syntax: a compression mode optionally tagged with mitigation
    # suffixes — "gtopk+warmup" (1 dense-warmup epoch) and/or
    # "gtopk+corr" (DGC momentum correction) — so the verdict's arm set
    # {dense, gtopk, gtopk+warmup, layerwise, correction} is expressible
    # from the CLI without bespoke flags per arm.
    parts = mode.split("+")
    base_mode, extra = parts[0], {}
    for tag in parts[1:]:
        if tag == "warmup":
            extra["dense_warmup_epochs"] = 1
        elif tag == "corr":
            extra["momentum_correction"] = True
        elif tag in ("exact", "approx", "blockwise", "pallas", "simrecall"):
            # Selection-kernel A/B arms (round-3 verdict weak #4: no
            # conv-net had converged through the production approx path;
            # "gtopk+approx" forces the kernel the >2^20-param auto
            # route uses, at any model size). "simrecall" is the
            # CPU-runnable pessimistic stand-in for approx (the CPU
            # backend lowers approx_max_k to an exact top-k, so +approx
            # arms on the CPU mesh silently test exact selection —
            # ops/topk.py::simrecall_topk_abs).
            extra["topk_method"] = tag
        elif tag in ("int8wire", "fp8wire"):
            # Wire-codec A/B arms: "gtopk+int8wire" runs the identical
            # schedule with the quantized on-wire codec so the verdict
            # can pin the final-loss delta of codec error (which folds
            # into the error-feedback residual) against the fp32 wire.
            extra["wire_codec"] = tag[:-4]
        else:
            raise SystemExit(f"unknown arm suffix {tag!r} in {mode!r} "
                             "(know: warmup, corr, exact, approx, "
                             "blockwise, pallas, simrecall, int8wire, "
                             "fp8wire)")
    density = 1.0 if base_mode in ("dense", "none") else density
    cfg = TrainConfig(
        dnn=args.dnn,
        batch_size=args.batch_size,
        nworkers=args.nworkers or jax.device_count(),
        compression=base_mode,
        density=density,
        seed=args.seed,
        max_epochs=max_epochs,
        log_interval=10_000_000,  # curve sampling happens here, not in logs
        eval_batches=args.eval_batches,
        data_dir=args.data_dir,
        dtype=args.dtype,
        synth_hard=args.synth_hard,
        **extra,
    )
    curve, losses = [], []
    with Trainer(cfg) as trainer:
        done = 0
        while done < args.steps:
            n = min(args.chunk, args.steps - done)
            stats = trainer.train(n)
            done += n
            losses.append(stats["loss"])
            row = {
                "mode": mode, "density": density, "step": done,
                "loss": round(stats["loss"], 5),
                "throughput": round(stats["throughput"], 1),
            }
            curve.append(row)
            if stream is not None:
                stream.write(json.dumps(row) + "\n")
                stream.flush()
            print(f"  {mode:10s} step {done:5d}  loss {stats['loss']:.4f}",
                  flush=True)
        ev = trainer.test()
    final = sum(losses[-3:]) / min(3, len(losses))  # smooth tail
    summary = {"mode": mode, "density": density,
               "final_loss": round(final, 5),
               **{k: round(float(v), 5) for k, v in ev.items()}}
    return curve, summary


DROP_FRACS = (0.5, 0.8, 0.9, 0.98)


def _first_step_rolling_below(curve, thr: float):
    """First step at which the ROLLING-3 mean of sampled losses is <= thr
    (None if never, and None for an empty curve). train(n) reports only
    the chunk's last micro-step loss, so a single-sample criterion
    rewards transient dips (and forgives rebounds); the 3-sample window
    is the same smoothing final_loss uses. The window must be FULL — a
    truncated window at the curve's start would re-admit exactly the
    single-sample dip the smoothing exists to reject — so the earliest
    reportable crossing is the window-th sample."""
    steps = [r["step"] for r in curve]
    losses = [r["loss"] for r in curve]
    w = min(3, len(losses))
    if w == 0:
        return None
    return next(
        (steps[i] for i in range(w - 1, len(losses))
         if sum(losses[i - w + 1:i + 1]) / w <= thr),
        None,
    )


def steps_to_drop_fracs(curve, drop_target: dict):
    """Steps to cover each fraction of the DENSE arm's achieved
    improvement (start -> final). The absolute thresholds of
    steps_to_thresholds suit CIFAR (loss -> ~0), but are meaningless for
    workloads with a high irreducible loss floor — PTB's LM loss bottoms
    out near 4.3, so "0.5x the initial loss" never happens and every
    field is null (the round-3 LSTM artifact's original rows). Measuring
    against the dense drop asks the comparable question on every
    workload: how fast does each mode cover the improvement dense
    achieves on the same budget?"""
    start, total = drop_target["start"], drop_target["drop"]
    return {
        f"steps_to_{frac}_of_dense_drop":
            _first_step_rolling_below(curve, start - frac * total)
        for frac in DROP_FRACS
    }


def steps_to_thresholds(curve, reference_loss: float):
    """Steps to cross absolute fractions of the shared reference loss
    (the dense curve's first sample; see _first_step_rolling_below for
    the rolling-window rule)."""
    return {
        f"steps_to_{frac}x_ref":
            _first_step_rolling_below(curve, reference_loss * frac)
        for frac in THRESHOLD_FRACS
    }


def attach_thresholds(summaries, curves):
    """(Re)compute both threshold families onto the summary rows in place:
    absolute fractions of the shared reference loss AND fractions of the
    dense arm's achieved drop. Returns the shared reference loss. Stale
    steps_to_* keys are replaced wholesale so --recompute never leaves a
    mixed-method row."""
    dense = next(
        (s for s in summaries if s["mode"] in ("dense", "none")), None)
    firsts = {m: c[0]["loss"] for m, c in curves.items() if c}
    if not firsts:
        raise SystemExit("no curve rows at all — nothing to threshold")
    ref = firsts.get(dense["mode"]) if dense else None
    if ref is None:
        ref = max(firsts.values())
    drop_target = None
    if dense is not None and curves.get(dense["mode"]):
        dstart = curves[dense["mode"]][0]["loss"]
        drop_target = {"start": dstart,
                       "drop": dstart - dense["final_loss"]}
    for s in summaries:
        for key in [k for k in s if k.startswith("steps_to")]:
            del s[key]
        s.update(steps_to_thresholds(curves[s["mode"]], ref))
        if drop_target is not None and drop_target["drop"] > 0:
            s.update(steps_to_drop_fracs(curves[s["mode"]], drop_target))
        if dense is not None:
            s["final_loss_vs_dense"] = round(
                s["final_loss"] / max(dense["final_loss"], 1e-9), 4)
    return ref


def _write_tail(fh, summaries, report):
    """Summary + report serialization shared by fresh runs and --recompute
    so both always emit the same artifact shape."""
    for s in summaries:
        fh.write(json.dumps({**s, "kind": "summary"}) + "\n")
    fh.write(json.dumps({**report, "kind": "report"}) + "\n")


def recompute_report(path: str) -> dict:
    """Rebuild the summary/report rows of an existing artifact from its
    own curve rows (e.g. after a threshold-method change), preserving
    measured fields (final_loss, eval metrics, provenance notes) and
    replacing only the derived steps_to_* columns."""
    import collections

    with open(path) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    curves = collections.defaultdict(list)
    summaries, report, extras = [], None, []
    for r in rows:
        kind = r.pop("kind", None)
        if kind == "summary":
            summaries.append(r)
        elif kind == "report":
            report = r
        elif kind is None and "step" in r and "loss" in r and "mode" in r:
            curves[r["mode"]].append(r)
        else:
            # Pass provenance rows through byte-identically: re-add the
            # kind tag only if the row actually had one.
            extras.append({**r, "kind": kind} if kind is not None else r)
    if report is None or not summaries:
        raise SystemExit(f"{path}: no report/summary rows to recompute")
    ref = attach_thresholds(summaries, curves)
    report["modes"] = summaries
    report["threshold_reference_loss"] = round(ref, 5)
    report["recomputed"] = ("steps_to_* columns rebuilt from the stored "
                            "curve rows by --recompute; measured fields "
                            "untouched")
    # Same crash-durability rule as main(): never truncate the only copy
    # of a measured artifact — write a sibling and rename on success.
    partial = path + ".recompute"
    with open(partial, "w") as fh:
        for mode_rows in curves.values():
            for r in mode_rows:
                fh.write(json.dumps(r) + "\n")
        for r in extras:
            fh.write(json.dumps(r) + "\n")
        _write_tail(fh, summaries, report)
    os.replace(partial, path)
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dnn", default="resnet20")
    ap.add_argument("--steps", type=int, default=1200)
    ap.add_argument("--chunk", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--modes", default="dense,gtopk,allgather")
    ap.add_argument("--density", type=float, default=0.001)
    ap.add_argument("--nworkers", type=int, default=0)
    ap.add_argument("--eval-batches", type=int, default=16)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--data-dir", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--synth-hard", action="store_true",
                    help="synthetic CIFAR: the discriminative variant "
                         "(weak spatial class signal + 10%% train label "
                         "noise) so arms can SEPARATE on val accuracy — "
                         "the easy task pins every arm at val_top1=1.0 "
                         "(round-4 verdict: accuracy parity was "
                         "unfalsifiable)")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="compute dtype for every arm (the bench headline "
                         "runs bfloat16; a bf16-vs-f32 convergence A/B "
                         "backs that config's correctness)")
    ap.add_argument("--recompute", default="",
                    help="rebuild an existing artifact's steps_to_* "
                         "columns from its stored curve rows, then exit "
                         "(no training, no device)")
    ap.add_argument("--platform", default="", choices=["", "cpu8", "cpu2"],
                    help="cpu8/cpu2 = force an 8- or 2-way virtual CPU "
                         "mesh in-process (force_cpu_mesh, as "
                         "tests/conftest.py does; cpu2 was the "
                         "measured-fastest long-run config on a 1-core "
                         "host)")
    args = ap.parse_args()

    if args.recompute:
        print(json.dumps(recompute_report(args.recompute)))
        return

    if args.platform in ("cpu8", "cpu2"):
        from gtopkssgd_tpu.utils import force_cpu_mesh

        force_cpu_mesh(int(args.platform[3:]))

    from gtopkssgd_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    epochs = max_epochs_for(args)
    device_tag = (f"cpu_mesh{args.platform[3:]}" if args.platform else
                  jax.devices()[0].device_kind.replace(" ", "_"))
    # The dtype is an artifact dimension: a bf16 run must not clobber the
    # f32 capture of the same dnn/device.
    dtype_tag = "" if args.dtype == "float32" else "_bf16"
    hard_tag = "_hard" if args.synth_hard else ""
    out = args.out or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results",
        f"convergence_{args.dnn}{dtype_tag}{hard_tag}_{device_tag}.jsonl",
    )
    # Stream to a .partial sibling and rename on success: crash-durability
    # for THIS run's rows without truncating a previous complete artifact
    # at time zero (a re-run that dies in mode 1 must not destroy the last
    # good capture).
    partial = out + ".partial"
    curves, summaries = {}, []
    with open(partial, "w") as fh:
        # Self-describing artifact: the same manifest header metrics.jsonl
        # carries (config hash over the argparse namespace, backend, git
        # sha). --recompute passes it through untouched as an extras row.
        from gtopkssgd_tpu.obs.manifest import run_manifest

        fh.write(json.dumps(
            {**run_manifest(vars(args)), "kind": "manifest"}) + "\n")
        fh.flush()
        for mode in args.modes.split(","):
            mode = mode.strip()
            print(f"[convergence] {args.dnn} {mode} rho={args.density} "
                  f"steps={args.steps} epochs={epochs}", flush=True)
            curve, summary = run_mode(args, mode, args.density, epochs,
                                      stream=fh)
            curves[mode] = curve
            summaries.append(summary)

        # Both threshold families (absolute-reference + dense-drop) live
        # in attach_thresholds, shared with --recompute.
        ref = attach_thresholds(summaries, curves)

        report = {"dnn": args.dnn, "steps": args.steps,
                  "batch_size": args.batch_size, "dtype": args.dtype,
                  "synth_hard": args.synth_hard,
                  "device_kind": jax.devices()[0].device_kind,
                  "nworkers": args.nworkers or jax.device_count(),
                  "threshold_reference_loss": round(ref, 5),
                  "modes": summaries}
        _write_tail(fh, summaries, report)
    os.replace(partial, out)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
