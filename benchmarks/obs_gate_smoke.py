"""Gate smoke: the canonical tiny CPU run behind the obs regression gate.

ONE place defines the run that the committed baseline
(benchmarks/results/obs_gate_baseline_cpu.json) describes: a few
gtopk_layerwise steps of resnet20 on a 2-way CPU mesh with per-layer
telemetry and the recall audit on. Both consumers import it:

  tests/test_obs.py         runs it in-process and asserts
                            ``report gate`` exits 0 against the committed
                            baseline — the tier-1 drift gate.
  this file as a script     regenerates the run and, with
                            --write-baseline, re-stamps the baseline's
                            expectations (after an INTENTIONAL behavior
                            change; review the JSON diff like code).

Tolerances live in the baseline, not here: tight (5%) on structurally
deterministic counters (sent_elems, wire_bytes, achieved_density — fixed
by k and the layer shapes), loose on value-dependent statistics (norms,
m(k), recall) that may wobble with compiler version or thread count.

Usage:
  python benchmarks/obs_gate_smoke.py                  # run + gate
  python benchmarks/obs_gate_smoke.py --write-baseline # regenerate
  python benchmarks/obs_gate_smoke.py --only goodput   # one sub-smoke,
                                       # gated against the SUBSET of the
                                       # committed checks its kinds own
  python benchmarks/obs_gate_smoke.py --only goodput --write-baseline
                                       # re-stamp ONLY that subset's
                                       # expectations back into the
                                       # committed baseline (all other
                                       # checks untouched)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "results",
    "obs_gate_baseline_cpu.json")

SMOKE_STEPS = 4

# Sub-smoke registry: name -> the metrics kinds its grafted record(s)
# carry, i.e. exactly the committed baseline checks that ``--only NAME``
# runs and (with --write-baseline) re-stamps. The main canonical run
# always executes — it hosts the grafted records the gate reads.
SMOKES = {
    "recovery": ("inject", "recovery"),
    "twostage": ("twostage",),
    "codec": ("codec",),
    "plan": ("plan",),
    "bucket": ("bucket",),
    "overlap": ("overlap",),
    "calib": ("calib", "regress"),
    "mem": ("mem",),
    "critpath": ("critpath",),
    "goodput": ("goodput",),
    "linkmap": ("linkmap",),
    "forecast": ("forecast",),
    "elastic": ("resize",),
    "lint": ("lint",),
}
# Sub-smokes a selected one cannot run without: the plan A/B reuses the
# codec smoke's fp32 arms as its tree baseline.
SMOKE_DEPS = {"plan": ("codec",)}


def _selected(name: str, only) -> bool:
    return (only is None or name == only
            or name in SMOKE_DEPS.get(only, ()))


def smoke_config(out_dir: str):
    """The canonical gate-smoke TrainConfig. Any field change here
    invalidates the committed baseline — regenerate it in the same
    commit (--write-baseline)."""
    from gtopkssgd_tpu.trainer import TrainConfig

    return TrainConfig(
        dnn="resnet20",
        batch_size=4,
        nworkers=2,
        compression="gtopk_layerwise",
        density=0.01,
        seed=42,
        max_epochs=1,
        log_interval=2,
        eval_batches=1,
        obs_layers=True,
        obs_audit_interval=2,
        obs_interval=2,
        out_dir=out_dir,
    )


def run_recovery_smoke(out_dir: str) -> str:
    """Injected-fault recovery sub-run: same canonical model/compression
    (so it reuses the persistent compile cache), 3 steps with a NaN
    injected at step 2 and ``nan_loss=skip`` claiming the anomaly. The
    run must exit 0 — the recovery path turning a would-be exit 44 into
    a completed run IS the property under test. Returns its run dir
    (a subdir, so ``resolve_paths`` on the parent never sees it)."""
    from gtopkssgd_tpu import dist_trainer

    rec_dir = os.path.join(out_dir, "recovery")
    rc = dist_trainer.main([
        "--dnn", "resnet20", "--batch-size", "4", "--nworkers", "2",
        "--compression", "gtopk_layerwise", "--density", "0.01",
        "--seed", "42", "--num-iters", "3", "--eval-batches", "1",
        "--log-interval", "1", "--obs-interval", "1",
        "--obs-halt-on", "error",
        "--inject", "nan_grad@2", "--recover-policy", "nan_loss=skip",
        "--out-dir", rec_dir,
    ])
    if rc != 0:
        raise RuntimeError(
            f"recovery smoke exited {rc} (expected 0: the nan_loss=skip "
            f"policy should claim the injected NaN)")
    return rec_dir


def run_twostage_smoke(out_dir: str) -> dict:
    """Exact-vs-twostage A/B on the fused p=1 threshold path (the ISSUE-6
    tentpole's consumer): two tiny flat-gtopk sub-runs differing ONLY in
    --topk-method, each with the recall audit on and two steps traced for
    the paper's T_compute/T_select/T_comm split. Returns the fields the
    main run logs as ONE "twostage" record so the drift gate can pin

      audit_recall_twostage      twostage tau keeps a SUPERSET of the
                                 exact top-k (tau_twostage <= tau_exact),
                                 so the audited recall floor is ~1.0
      select_frac_regression     max(0, frac_select_twostage -
                                 frac_select_exact): one-sided "T_select
                                 fraction no worse than exact" evidence

    On a platform without usable op traces the frac fields are omitted
    (same degradation as run_smoke's attr_error path)."""
    from gtopkssgd_tpu.obs import report
    from gtopkssgd_tpu.obs.trace_attr import attribute, capture
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    per_method: dict = {}
    for method in ("exact", "twostage"):
        sub = os.path.join(out_dir, f"twostage_ab_{method}")
        cfg = TrainConfig(
            dnn="resnet20", batch_size=4, nworkers=1,
            compression="gtopk", density=0.01, seed=42,
            max_epochs=1, log_interval=2, eval_batches=1,
            obs_interval=1, obs_audit_interval=2,
            topk_method=method, out_dir=sub)
        with Trainer(cfg) as t:
            t.train(2)  # audit fires at step 2 (obs_audit_interval=2)
            trace_dir = os.path.join(sub, "trace")
            try:
                with capture(trace_dir):
                    t.train(2)
                frac = attribute(trace_dir, mode=method).get("frac_select")
            except Exception:  # platform without usable op traces
                frac = None
        recs, _ = report.load_records(sub)
        audited = [r["audit_recall"] for r in recs
                   if r.get("kind") == "obs"
                   and float(r.get("audit_recall", -1.0)) >= 0.0]
        per_method[method] = {
            "audit_recall": max(audited) if audited else -1.0,
            "frac_select": frac,
        }
    rec = {
        "audit_recall_exact": per_method["exact"]["audit_recall"],
        "audit_recall_twostage": per_method["twostage"]["audit_recall"],
    }
    fs_e = per_method["exact"]["frac_select"]
    fs_t = per_method["twostage"]["frac_select"]
    if fs_e is not None and fs_t is not None:
        rec["frac_select_exact"] = fs_e
        rec["frac_select_twostage"] = fs_t
        rec["select_frac_ratio"] = round(fs_t / max(fs_e, 1e-9), 4)
        rec["select_frac_regression"] = round(max(0.0, fs_t - fs_e), 6)
    return rec


def run_codec_smoke(out_dir: str) -> dict:
    """int8-vs-fp32 wire-codec A/B (the ISSUE-7 tentpole's consumer):
    four tiny flat-gtopk sub-runs — codec x density over {fp32, int8} x
    {0.001, 0.01} — differing ONLY in those two fields, each with the
    recall audit on. Returns the fields the main run logs as ONE "codec"
    record so the drift gate can pin the PR's acceptance numbers:

      wire_ratio_rho001        int8/fp32 measured wire_bytes at rho=1e-3
                               (the DCN regime k): ~0.32, i.e. >=3x
      dcn_excess_rho001        max(0, ratio - 1/3): one-sided ">=3x
                               reduction" evidence, exactly 0.0
      wire_excess_rho01        max(0, ratio@rho=0.01 - 0.30): the gate
                               smoke's own density meets the same bar
      audit_recall_int8        audited recall under the lossy codec
                               (flat gtopk reselects the exact top-k of
                               the dequantized merge, so the floor is
                               ~1.0 — well above the 0.95 acceptance)
      residual_norm_int8       error feedback stays bounded with the
                               quantization error folded in
      ledger_bytes_ratio_int8  obs/ledger.py's modeled-vs-measured wire
                               bytes on the int8 sub-run: ~1.0 means the
                               codec-aware model explains the achieved
                               bytes (the "ledger-audited" acceptance)

    The ratios divide two structurally deterministic counters (byte
    counts are fixed by k, n and the codec bit budget), so tolerances in
    the baseline are tight; the one-sided excess fields are exact."""
    from gtopkssgd_tpu.obs import ledger, report
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    measured: dict = {}
    int8_records = None
    for rho in (0.001, 0.01):
        for codec in ("fp32", "int8"):
            sub = os.path.join(
                out_dir, f"codec_ab_{codec}_rho{rho:g}".replace(".", "p"))
            cfg = TrainConfig(
                dnn="resnet20", batch_size=4, nworkers=2,
                compression="gtopk", density=rho, seed=42,
                max_epochs=1, log_interval=2, eval_batches=1,
                obs_interval=1, obs_audit_interval=2,
                wire_codec=codec, out_dir=sub)
            with Trainer(cfg) as t:
                t.train(2)  # audit fires at step 2 (obs_audit_interval)
            recs, _ = report.load_records(sub)
            obs = [r for r in recs if r.get("kind") == "obs"]
            wire = [float(r["wire_bytes"]) for r in obs
                    if isinstance(r.get("wire_bytes"), (int, float))]
            audited = [float(r["audit_recall"]) for r in obs
                       if float(r.get("audit_recall", -1.0)) >= 0.0]
            res = [float(r["residual_norm"]) for r in obs
                   if isinstance(r.get("residual_norm"), (int, float))]
            measured[(codec, rho)] = {
                "wire_bytes": sum(wire) / len(wire) if wire else 0.0,
                "audit_recall": max(audited) if audited else -1.0,
                "residual_norm": res[-1] if res else -1.0,
            }
            if codec == "int8" and rho == 0.001:
                int8_records = recs
    r001 = (measured[("int8", 0.001)]["wire_bytes"]
            / max(measured[("fp32", 0.001)]["wire_bytes"], 1e-9))
    r01 = (measured[("int8", 0.01)]["wire_bytes"]
           / max(measured[("fp32", 0.01)]["wire_bytes"], 1e-9))
    rec = {
        "wire_codec": "int8",
        "wire_bytes_fp32_rho001": measured[("fp32", 0.001)]["wire_bytes"],
        "wire_bytes_int8_rho001": measured[("int8", 0.001)]["wire_bytes"],
        "wire_bytes_fp32_rho01": measured[("fp32", 0.01)]["wire_bytes"],
        "wire_bytes_int8_rho01": measured[("int8", 0.01)]["wire_bytes"],
        "wire_ratio_rho001": round(r001, 6),
        "wire_ratio_rho01": round(r01, 6),
        "dcn_excess_rho001": round(max(0.0, r001 - 1.0 / 3.0), 6),
        "wire_excess_rho01": round(max(0.0, r01 - 0.30), 6),
        "dcn_reduction_x": round(1.0 / max(r001, 1e-9), 4),
        "audit_recall_int8": measured[("int8", 0.001)]["audit_recall"],
        "recall_floor_breach": round(max(
            0.0, 0.95 - measured[("int8", 0.001)]["audit_recall"]), 6),
        "residual_norm_int8": measured[("int8", 0.001)]["residual_norm"],
    }
    # The ledger audit: join the int8 sub-run's achieved wire_bytes
    # against the codec-aware comm model (obs/ledger.py reads wire_codec
    # from the manifest). Mean ratio ~1.0 IS the acceptance evidence
    # that the measured reduction matches the modeled one.
    rows = [r for r in ledger.ledger_rows(int8_records or [])
            if r.get("source") == "wire_bytes"
            and isinstance(r.get("ratio"), (int, float))]
    if rows:
        rec["ledger_bytes_ratio_int8"] = round(
            sum(float(r["ratio"]) for r in rows) / len(rows), 6)
        rec["ledger_rows_int8"] = len(rows)
    return rec


def run_plan_smoke(out_dir: str, codec_rec: dict) -> dict:
    """Balanced-vs-tree comm-planner A/B (the ISSUE-9 tentpole's
    consumer): two tiny flat-gtopk sub-runs pinned to the Ok-Topk
    balanced schedule (--comm-plan balanced) at the codec smoke's two
    densities; the tree arms are REUSED from the codec smoke's fp32
    sub-runs (same config except the pin, and their auto plan resolves
    to the tree at this shape), so the A/B costs two runs, not four.
    Returns the fields the main run logs as ONE "plan" record:

      wire_ratio_rho001/rho01    balanced/tree measured wire_bytes. At
                                 p=2 the balanced schedule's 2p-1=3
                                 capped messages cost MORE than the
                                 tree's single full exchange (~2.25x:
                                 3*cap/k with cap=ceil(1.5k/2)) — the
                                 planner's whole point is that this is
                                 shape-dependent; the crossover at
                                 p>=8 is pinned model-side in
                                 tests/test_planner.py and
                                 benchmarks/merge_bench.py
      recall_floor_breach        max(0, 0.95 - audited recall) under the
                                 balanced schedule: exactly 0.0 (the
                                 capped scatter drops nothing at these
                                 shapes and repair is exact)
      ledger_bytes_ratio_balanced  obs/ledger.py modeled-vs-measured
                                 wire bytes on a balanced sub-run: ~1.0
                                 means the plan-keyed model explains
                                 the balanced wire exactly

    The ratios divide structurally deterministic byte counters, so the
    baseline pins them tight; the breach field is exact."""
    from gtopkssgd_tpu.obs import ledger, report
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    tree_bytes = {0.001: codec_rec["wire_bytes_fp32_rho001"],
                  0.01: codec_rec["wire_bytes_fp32_rho01"]}
    measured: dict = {}
    bal_records = None
    for rho in (0.001, 0.01):
        sub = os.path.join(
            out_dir, f"plan_ab_balanced_rho{rho:g}".replace(".", "p"))
        cfg = TrainConfig(
            dnn="resnet20", batch_size=4, nworkers=2,
            compression="gtopk", density=rho, seed=42,
            max_epochs=1, log_interval=2, eval_batches=1,
            obs_interval=1, obs_audit_interval=2,
            comm_plan="balanced", out_dir=sub)
        with Trainer(cfg) as t:
            t.train(2)  # audit fires at step 2 (obs_audit_interval)
        recs, _ = report.load_records(sub)
        obs = [r for r in recs if r.get("kind") == "obs"]
        wire = [float(r["wire_bytes"]) for r in obs
                if isinstance(r.get("wire_bytes"), (int, float))]
        audited = [float(r["audit_recall"]) for r in obs
                   if float(r.get("audit_recall", -1.0)) >= 0.0]
        measured[rho] = {
            "wire_bytes": sum(wire) / len(wire) if wire else 0.0,
            "audit_recall": max(audited) if audited else -1.0,
        }
        if rho == 0.001:
            bal_records = recs
    r001 = measured[0.001]["wire_bytes"] / max(tree_bytes[0.001], 1e-9)
    r01 = measured[0.01]["wire_bytes"] / max(tree_bytes[0.01], 1e-9)
    rec = {
        "schedule": "balanced",
        "wire_bytes_balanced_rho001": measured[0.001]["wire_bytes"],
        "wire_bytes_tree_rho001": tree_bytes[0.001],
        "wire_bytes_balanced_rho01": measured[0.01]["wire_bytes"],
        "wire_bytes_tree_rho01": tree_bytes[0.01],
        "wire_ratio_rho001": round(r001, 6),
        "wire_ratio_rho01": round(r01, 6),
        "audit_recall_balanced": measured[0.001]["audit_recall"],
        "recall_floor_breach": round(max(
            0.0, 0.95 - measured[0.001]["audit_recall"]), 6),
    }
    # The ledger audit: the balanced sub-run's achieved wire_bytes
    # against the plan-keyed comm model (obs/ledger.py reads
    # comm_plan_schedule from the manifest). Mean ratio ~1.0 IS the
    # evidence that the (2p-1)*wire_set_bytes(cap, n) accounting
    # matches what the schedule put on the wire.
    rows = [r for r in ledger.ledger_rows(bal_records or [])
            if r.get("source") == "wire_bytes"
            and isinstance(r.get("ratio"), (int, float))]
    if rows:
        rec["ledger_bytes_ratio_balanced"] = round(
            sum(float(r["ratio"]) for r in rows) / len(rows), 6)
        rec["ledger_rows_balanced"] = len(rows)
    return rec


def run_bucket_smoke(out_dir: str) -> dict:
    """Bucketed-vs-per-leaf layerwise A/B (the bucketing tentpole's
    consumer): two tiny gtopk_layerwise sub-runs at the DCN-regime
    density (rho=0.001, p=2, 2 steps) differing ONLY in --buckets —
    'leaf' (one merge per param leaf, B=L) vs 'auto' (the alpha-beta DP,
    which at the committed ~22 ms alpha collapses resnet20's 65 leaves
    to B=1). Returns the fields the main run logs as ONE "bucket"
    record so the drift gate can pin the PR's acceptance numbers:

      collective_ratio           leaf/auto per-step sparse-merge count
                                 from the collective_count telemetry
                                 (structural: L=65 over B=1). The
                                 acceptance bar is >=3x fewer merges
      collective_floor_breach    max(0, 3 - ratio): one-sided ">=3x"
                                 evidence, exactly 0.0
      audit_recall_bucketed      audited recall on the bucketed arm
                                 (per-bucket exact top-k audit), floor
                                 0.95
      ledger_bytes_ratio_bucketed  obs/ledger.py modeled-vs-measured
                                 wire bytes on the bucketed arm: ~1.0
                                 means the bucket-summed model explains
                                 the achieved bytes

    Counts and byte counters are structural (fixed by the leaf shapes
    and the DP's boundaries), so the baseline pins them tight."""
    from gtopkssgd_tpu.obs import ledger, report
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    measured: dict = {}
    auto_records = None
    for buckets in ("leaf", "auto"):
        sub = os.path.join(out_dir, f"bucket_ab_{buckets}")
        cfg = TrainConfig(
            dnn="resnet20", batch_size=4, nworkers=2,
            compression="gtopk_layerwise", density=0.001, seed=42,
            max_epochs=1, log_interval=2, eval_batches=1,
            obs_interval=1, obs_audit_interval=2,
            buckets=buckets, out_dir=sub)
        with Trainer(cfg) as t:
            t.train(2)  # audit fires at step 2 (obs_audit_interval)
            n_buckets = t._bucket_plan.n_buckets
        recs, _ = report.load_records(sub)
        obs = [r for r in recs if r.get("kind") == "obs"]
        coll = [float(r["collective_count"]) for r in obs
                if isinstance(r.get("collective_count"), (int, float))]
        wire = [float(r["wire_bytes"]) for r in obs
                if isinstance(r.get("wire_bytes"), (int, float))]
        audited = [float(r["audit_recall"]) for r in obs
                   if float(r.get("audit_recall", -1.0)) >= 0.0]
        measured[buckets] = {
            "n_buckets": n_buckets,
            "collective_count": max(coll) if coll else 0.0,
            "wire_bytes": sum(wire) / len(wire) if wire else 0.0,
            "audit_recall": max(audited) if audited else -1.0,
        }
        if buckets == "auto":
            auto_records = recs
    ratio = (measured["leaf"]["collective_count"]
             / max(measured["auto"]["collective_count"], 1e-9))
    wire_ratio = (measured["auto"]["wire_bytes"]
                  / max(measured["leaf"]["wire_bytes"], 1e-9))
    rec = {
        "buckets": "auto",
        "n_buckets_leaf": measured["leaf"]["n_buckets"],
        "n_buckets_auto": measured["auto"]["n_buckets"],
        "collective_count_leaf": measured["leaf"]["collective_count"],
        "collective_count_auto": measured["auto"]["collective_count"],
        "collective_ratio": round(ratio, 4),
        "collective_floor_breach": round(max(0.0, 3.0 - ratio), 6),
        "wire_bytes_leaf": measured["leaf"]["wire_bytes"],
        "wire_bytes_auto": measured["auto"]["wire_bytes"],
        "wire_ratio_auto_leaf": round(wire_ratio, 6),
        "audit_recall_bucketed": measured["auto"]["audit_recall"],
        "recall_floor_breach": round(max(
            0.0, 0.95 - measured["auto"]["audit_recall"]), 6),
    }
    # The ledger audit: the bucketed arm's achieved wire_bytes against
    # the bucket-summed comm model (obs/ledger.py reads the manifest's
    # bucket_sizes/bucket_ks and prices each bucket over its OWN local
    # index space). Mean ratio ~1.0 IS the evidence that the bucketed
    # wire accounting matches what the schedule put on the wire.
    rows = [r for r in ledger.ledger_rows(auto_records or [])
            if r.get("source") == "wire_bytes"
            and isinstance(r.get("ratio"), (int, float))]
    if rows:
        rec["ledger_bytes_ratio_bucketed"] = round(
            sum(float(r["ratio"]) for r in rows) / len(rows), 6)
        rec["ledger_rows_bucketed"] = len(rows)
    return rec


def run_overlap_smoke(out_dir: str) -> dict:
    """Pipelined-vs-serial A/B (the overlapped-pipeline tentpole's
    consumer): for each codec in {fp32, int8:64}, two tiny bucketed
    gtopk_layerwise sub-runs (p=2, 2 steps, --buckets 4) differing ONLY
    in --pipeline — 'serial' (the paper's barrier-pinned sequential
    chain) vs 'overlap' (double-buffered stages). Returns the fields
    the main run logs as ONE "overlap" record so the drift gate pins
    the PR's acceptance numbers:

      bit_delta_fp32 / bit_delta_int8   max |serial - overlap| over
                                 EVERY param, error-feedback residual,
                                 and telemetry leaf after 2 steps.
                                 optimization_barrier is the identity,
                                 so these are EXACTLY 0.0 — any epsilon
                                 means the overlap reordered arithmetic
      audit_recall_overlap       worst audited recall across the two
                                 overlapped arms, floor 0.95
      overlap_frac               measured (not modeled) hidden-comm
                                 fraction: a profiler capture of the
                                 overlapped fp32 arm through
                                 obs.trace_attr.attribute — the 2-way
                                 CPU mesh runs its lanes on separate
                                 threads, so real cross-lane
                                 concurrency shows up even here
      overlap_frac_positive      1.0 iff overlap_frac > 0 (the
                                 "overlap is real, not modeled-only"
                                 acceptance pin)
      crossover_n_buckets        model-side DP pin at the ResNet-50
                                 crossover (alpha=0.1 ms, P=8, committed
                                 beta): overlap pricing must choose
                                 B > 1 where serial pricing collapses
                                 to B=1, and 'auto' must pick overlap

    The bit-identity comparison is the strongest structural pin in the
    file: both arms share seed, data order, and boundaries, so every
    leaf of (params, opt_state) — residuals and counters included —
    must agree bit-for-bit."""
    import jax
    import numpy as np

    from gtopkssgd_tpu.obs import report
    from gtopkssgd_tpu.obs.trace_attr import attribute, capture
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    def _arm(codec: str, pipe: str):
        sub = os.path.join(
            out_dir, f"overlap_ab_{codec.split(':')[0]}_{pipe}")
        cfg = TrainConfig(
            dnn="resnet20", batch_size=4, nworkers=2,
            compression="gtopk_layerwise", density=0.01, seed=42,
            max_epochs=1, log_interval=2, eval_batches=1,
            obs_interval=1, obs_audit_interval=2,
            wire_codec=codec, buckets="4", pipeline=pipe, out_dir=sub)
        frac = None
        with Trainer(cfg) as t:
            t.train(2)
            leaves = [np.asarray(l) for l in jax.tree_util.tree_leaves(
                (t.state.params, t.state.opt_state))]
            if codec == "fp32" and pipe == "overlap":
                # The measured-overlap evidence: capture the pipelined
                # dispatch and attribute it — op-event interval unions
                # across the two device lanes.
                trace_dir = os.path.join(sub, "trace")
                with capture(trace_dir):
                    t.train(2)
                frac = attribute(
                    trace_dir, mode=cfg.compression).get("overlap_frac")
        recs, _ = report.load_records(sub)
        audited = [float(r["audit_recall"]) for r in recs
                   if r.get("kind") == "obs"
                   and float(r.get("audit_recall", -1.0)) >= 0.0]
        recall = max(audited) if audited else -1.0
        return leaves, recall, frac

    deltas, recalls, frac = {}, [], None
    for codec in ("fp32", "int8:64"):
        s_leaves, _, _ = _arm(codec, "serial")
        o_leaves, recall, f = _arm(codec, "overlap")
        if f is not None:
            frac = f
        recalls.append(recall)
        deltas[codec] = max(
            float(np.max(np.abs(a.astype(np.float64)
                                - b.astype(np.float64))))
            if a.size else 0.0
            for a, b in zip(s_leaves, o_leaves))
    # Model-side crossover pin: at ICI-class alpha the overlap-priced
    # DP must open up B > 1 on real ResNet-50 leaf sizes while serial
    # pricing keeps the single merge, and 'auto' must take the
    # overlapped order (all deterministic — pure cost model).
    from benchmarks.merge_bench import _model_leaf_sizes
    from gtopkssgd_tpu.parallel import plan_buckets
    from gtopkssgd_tpu.parallel.planner import planner_inputs

    sizes = _model_leaf_sizes("resnet50")
    kw = dict(p=8, codec="fp32", alpha_ms=0.1,
              beta_gbps=planner_inputs()["beta_gbps"])
    cross = plan_buckets(sizes, 0.001, buckets="auto",
                         pipeline="overlap", **kw)
    cross_serial = plan_buckets(sizes, 0.001, buckets="auto",
                                pipeline="serial", **kw)
    cross_auto = plan_buckets(sizes, 0.001, buckets="auto",
                              pipeline="auto", **kw)
    recall_min = min(recalls)
    return {
        "pipeline": "overlap",
        "n_buckets": 4.0,
        "bit_delta_fp32": deltas["fp32"],
        "bit_delta_int8": deltas["int8:64"],
        "bit_identity_ok": float(deltas["fp32"] == 0.0
                                 and deltas["int8:64"] == 0.0),
        "audit_recall_overlap": recall_min,
        "recall_floor_breach": round(max(0.0, 0.95 - recall_min), 6),
        "overlap_frac": (round(float(frac), 6)
                         if frac is not None else -1.0),
        "overlap_frac_positive": float(frac is not None and frac > 0),
        "crossover_n_buckets": float(cross.n_buckets),
        "crossover_b_gt1": float(cross.n_buckets > 1),
        "crossover_serial_b1": float(cross_serial.n_buckets == 1),
        "crossover_auto_overlap": float(
            cross_auto.pipeline == "overlap"),
    }


def run_calib_smoke(out_dir: str) -> dict:
    """Self-calibrating comm-model smoke (the ISSUE-13 tentpole's
    consumer): drives obs/calib.py and obs/registry.py against SYNTHETIC
    ground truth — no trainer, no timing noise, so the baseline can pin
    the estimator itself tight. A 32-sample stream generated from the
    exact alpha-beta decomposition (alpha=4 ms, beta=2 Gbps, p=4 gtopk
    tree) with every 10th sample inflated 5x (an injected straggler)
    feeds a CommCalibrator whose reference is the committed ~22 ms
    4-proc probe fit. Returns the fields the main run logs as ONE
    "calib" record:

      alpha_fit_ms / beta_fit_gbps  robust fit over the full stream;
                                 the stragglers must not drag it off
                                 the known constants (tight rtol)
      n_refits / drift_events    structural: 32 samples / window of 8
                                 -> exactly 4 refits; comm_drift_warmup
                                 =2 of them armed -> exactly 2 firings
                                 of comm_model_drift vs the stale probe
      fit_src_is_calib           the end-of-run artifact round-trips
                                 through planner_inputs: next run's
                                 planner would price with THIS run's
                                 measured fit, not the probe — the
                                 obs->planner loop, closed

    Alongside, the registry contract is exercised offline (synthetic
    record streams through report's history/regress CLI paths) and the
    exit codes are pinned as a "regress" record: 2 on an empty
    registry, 0 against itself, 1 on a 10x-worsened loss, 0 from
    history — the same contract ``report gate`` follows."""
    import json as _json

    from gtopkssgd_tpu.obs import report
    from gtopkssgd_tpu.obs import registry as _registry
    from gtopkssgd_tpu.obs.calib import CommCalibrator, message_count
    from gtopkssgd_tpu.obs.events import AnomalyMonitor
    from gtopkssgd_tpu.parallel.planner import planner_inputs

    true_alpha, true_beta = 4.0, 2.0
    p, wire_mode = 4, "gtopk"
    msgs = message_count(wire_mode, p)
    mon = AnomalyMonitor(halt_on=None)
    cal = CommCalibrator(
        wire_mode, p,
        baseline={"alpha_ms": 21.8594, "beta_gbps": 0.6,
                  "fit_source": "dcn_probe_4proc.json"},
        monitor=mon, refit_interval=8, min_samples=4)
    n_refits = 0
    for i in range(32):
        b = 200_000 + 40_000 * (i % 8)
        t = msgs * (true_alpha + (b / msgs) * 8e-6 / true_beta)
        if i % 10 == 0:
            t *= 5.0  # injected straggler: the fit must ride through
        if cal.observe(i, b, t) is not None:
            n_refits += 1
    fit = cal.final_fit()
    calib_dir = os.path.join(out_dir, "calib_probe")
    art = cal.write_artifact(calib_dir, manifest={"config_hash": "smoke"})
    inputs = planner_inputs(calib_dir)
    src_ok = (art is not None
              and inputs.get("fit_source") == os.path.basename(art))

    # Registry exit-code contract on synthetic runs (subdirs, so
    # resolve_paths on the parent never sees their metrics.jsonl).
    def _write_run(name: str, loss: float) -> str:
        d = os.path.join(out_dir, name)
        os.makedirs(d, exist_ok=True)
        recs = [
            {"kind": "manifest", "time": 100.0, "rank": 0,
             "config_hash": "calib_smoke", "git_sha": "0" * 7},
            {"kind": "train", "time": 101.0, "rank": 0, "step": 1,
             "loss": loss},
            {"kind": "train", "time": 103.0, "rank": 0, "step": 5,
             "loss": loss},
            {"kind": "calib", "time": 103.5, "rank": 0, "step": 5,
             "alpha_fit_ms": fit["alpha_ms"],
             "beta_fit_gbps": fit["beta_gbps"],
             "n_samples": fit["n_samples"]},
        ]
        with open(os.path.join(d, "metrics.jsonl"), "w") as fh:
            for r in recs:
                fh.write(_json.dumps(r) + "\n")
        return d

    reg_dir = os.path.join(out_dir, "calib_registry")
    run_a = _write_run("calib_run_a", loss=1.5)
    rc_empty = report.run_regress(run_a, reg_dir)
    recs_a, _ = report.load_records(run_a)
    _registry.append_run(reg_dir, _registry.run_summary(recs_a))
    rc_pass = report.run_regress(run_a, reg_dir)
    rc_fail = report.run_regress(_write_run("calib_run_b", loss=15.0),
                                 reg_dir)
    rc_history = report.run_history(reg_dir)
    return {
        "alpha_fit_ms": fit["alpha_ms"],
        "beta_fit_gbps": fit["beta_gbps"],
        "alpha_true_ms": true_alpha,
        "beta_true_gbps": true_beta,
        "resid_ms": fit["resid_ms"],
        "n_samples": float(fit["n_samples"]),
        "n_refits": float(n_refits),
        "drift_events": float(mon.summary().get("comm_model_drift", 0)),
        "fit_src_is_calib": 1.0 if src_ok else 0.0,
        "planner_alpha_ms": inputs["alpha_ms"],
        "regress_rc_empty": float(rc_empty),
        "regress_rc_pass": float(rc_pass),
        "regress_rc_fail": float(rc_fail),
        "history_rc": float(rc_history),
    }


def run_mem_smoke(out_dir: str) -> dict:
    """Compile/memory-plane smoke (the ISSUE-14 tentpole's consumer):
    two instrumented sub-runs of the canonical model under ``--obs-mem``
    (both reuse the persistent compile cache), returning the fields the
    main run logs as ONE "mem" record:

      clean leg (4 steps)        mem_rc==0; exactly ONE "compile" record
                                 (one dispatch shape for the whole run —
                                 the committed-at-init sharding fix);
                                 recompile_count pinned at 0 after
                                 warmup; live-bytes stable across the
                                 sampled windows; peak_hbm_bytes in the
                                 manifest, equal to the compile record's
                                 estimate, and carried into the registry
                                 line (regress vs itself exits 0);
                                 ``report mem`` / ``report compile``
                                 round-trip the records (exit 0)
      storm leg (reshape@3)      the injected second dispatch shape
                                 retraces the step: recompile_count
                                 lands at exactly 1, recompile_storm
                                 fires with warmup 0, --obs-halt-on
                                 warn exits 44 — with BOTH shapes'
                                 compile accounting on disk before the
                                 halt (record-before-rule)"""
    import json as _json

    from gtopkssgd_tpu import dist_trainer
    from gtopkssgd_tpu.obs import report
    from gtopkssgd_tpu.obs import registry as _registry

    canon = [
        "--dnn", "resnet20", "--batch-size", "4", "--nworkers", "2",
        "--compression", "gtopk_layerwise", "--density", "0.01",
        "--seed", "42", "--eval-batches", "1", "--log-interval", "1",
        "--obs-interval", "1", "--obs-mem", "--obs-mem-interval", "1",
    ]

    def _recs(d):
        with open(os.path.join(d, "metrics.jsonl")) as fh:
            return [_json.loads(line) for line in fh]

    mem_dir = os.path.join(out_dir, "memwatch")
    reg_dir = os.path.join(out_dir, "mem_registry")
    mem_rc = dist_trainer.main(canon + [
        "--num-iters", "4", "--registry", reg_dir, "--out-dir", mem_dir])
    recs = _recs(mem_dir)
    manifest = next(r for r in recs if r["kind"] == "manifest")
    shapes = [r for r in recs if r["kind"] == "compile"
              and r.get("event") is None]
    mems = [r for r in recs if r["kind"] == "mem"]
    live = [r["live_bytes"] for r in mems if r.get("live_bytes")]
    peak = manifest.get("peak_hbm_bytes", 0) or 0
    peak_matches = (len(shapes) == 1
                    and shapes[0].get("peak_hbm_bytes") == peak)
    entries, _bad = _registry.load_registry(reg_dir)
    reg_stats = (entries[-1].get("stats", {}) if entries else {})
    reg_has_fields = ("peak_hbm_bytes" in reg_stats
                      and "recompile_count" in reg_stats)

    storm_dir = os.path.join(out_dir, "memstorm")
    storm_rc = dist_trainer.main(canon + [
        "--num-iters", "5", "--inject", "reshape@3",
        "--obs-recompile-warmup", "0", "--obs-halt-on", "warn",
        "--out-dir", storm_dir])
    storm_recs = _recs(storm_dir)
    storm_recompiles = [r for r in storm_recs if r["kind"] == "compile"
                        and r.get("event") == "recompile"]
    storm_shapes = [r for r in storm_recs if r["kind"] == "compile"
                    and r.get("event") is None]
    storm_events = [r for r in storm_recs if r["kind"] == "event"
                    and r.get("rule") == "recompile_storm"]
    return {
        "mem_rc": float(mem_rc),
        "compile_records": float(len(shapes)),
        "recompile_count": float(max(
            (r.get("recompile_count", 0) for r in mems), default=0)),
        "mem_samples": float(len(mems)),
        "live_ratio": (max(live) / min(live)) if live else 0.0,
        "peak_hbm_bytes": float(peak),
        "peak_matches_compile": 1.0 if peak_matches else 0.0,
        "registry_has_mem_fields": 1.0 if reg_has_fields else 0.0,
        "mem_report_rc": float(report.run_mem(mem_dir)),
        "compile_report_rc": float(report.run_compile(mem_dir)),
        "mem_regress_rc": float(report.run_regress(mem_dir, reg_dir)),
        "storm_rc": float(storm_rc),
        "storm_recompile_count": float(
            max((r.get("recompile_count", 0) for r in storm_recompiles),
                default=0)),
        "storm_events": float(len(storm_events)),
        "storm_shapes": float(len(storm_shapes)),
    }


def run_goodput_smoke(out_dir: str) -> dict:
    """Goodput-ledger smoke (the goodput tentpole's consumer): a clean
    and a chaos leg of the canonical run under the default ledger
    (``--obs-goodput``), returning the fields the main run logs as ONE
    "goodput" record so the drift gate can pin the PR's acceptance
    numbers:

      clean leg (4 steps)        rc==0; the end-of-run record is final;
                                 CONSERVATION by measurement — the
                                 taxonomy explains the wall clock:
                                 clean_other_frac pinned <= 0.05 (atol)
                                 and clean_conservation_err ~ 0 (the
                                 |wall - sum(categories+other)| residual
                                 is a construction invariant)
      chaos leg (6 steps)        nan_grad@2 claimed by nan_loss=skip,
                                 slow_rank:0:0.2@3-4, preempt@5: each
                                 injected fault must land in its
                                 DESIGNATED badput category —
                                 chaos_wasted_hit   the skipped step's
                                                    wall in `wasted`
                                                    (n_wasted_steps>=1)
                                 chaos_wait_hit     the injected 0.2 s
                                                    sleeps in `wait`
                                 chaos_ckpt_hit     the emergency save
                                                    in `ckpt`
                                 chaos_rc           the preemption exits
                                                    45 WITH the final
                                                    goodput record on
                                                    disk first
                                                    (record-before-exit)

    The hit fields are one-sided indicators (1.0 exact); the clean-leg
    fracs are timing-dependent, so only the conservation remainder is
    pinned (loose atol), never the split itself."""
    import json as _json

    from gtopkssgd_tpu import dist_trainer
    from gtopkssgd_tpu.obs import goodput as _goodput

    canon = [
        "--dnn", "resnet20", "--batch-size", "4", "--nworkers", "2",
        "--compression", "gtopk_layerwise", "--density", "0.01",
        "--seed", "42", "--eval-batches", "1", "--log-interval", "1",
        "--obs-interval", "1", "--obs-goodput-interval", "2",
    ]

    def _final_goodput(d):
        with open(os.path.join(d, "metrics.jsonl")) as fh:
            recs = [_json.loads(line) for line in fh]
        finals = [r for r in recs if r.get("kind") == "goodput"
                  and r.get("final")]
        return finals[-1] if finals else None

    clean_dir = os.path.join(out_dir, "goodput_clean")
    clean_rc = dist_trainer.main(canon + [
        "--num-iters", "4", "--out-dir", clean_dir])
    clean = _final_goodput(clean_dir) or {}

    chaos_dir = os.path.join(out_dir, "goodput_chaos")
    chaos_rc = dist_trainer.main(canon + [
        "--num-iters", "6",
        "--inject", "nan_grad@2,slow_rank:0:0.2@3-4,preempt@5",
        "--recover-policy", "nan_loss=skip",
        "--out-dir", chaos_dir])
    chaos = _final_goodput(chaos_dir) or {}

    def _s(rec, cat):
        return float(rec.get(f"{cat}_s", 0.0))

    return {
        "clean_rc": float(clean_rc),
        "clean_final": float(bool(clean.get("final"))),
        "clean_goodput_frac": float(clean.get("goodput_frac", -1.0)),
        "clean_other_frac": float(clean.get("other_frac", 1.0)),
        "clean_conservation_err": (
            round(_goodput.conservation_error(clean), 9) if clean
            else -1.0),
        "chaos_rc": float(chaos_rc),
        "chaos_final": float(bool(chaos.get("final"))),
        "chaos_n_wasted": float(chaos.get("n_wasted_steps", 0)),
        "chaos_wasted_hit": float(_s(chaos, "wasted") > 0.0
                                  and chaos.get("n_wasted_steps", 0) >= 1),
        # two injected 0.2 s sleeps; >= 0.15 tolerates clock slop while
        # still requiring at least one to have been accounted as wait
        "chaos_wait_hit": float(_s(chaos, "wait") >= 0.15),
        "chaos_ckpt_hit": float(_s(chaos, "ckpt") > 0.0),
        "chaos_wait_s": round(_s(chaos, "wait"), 6),
        "chaos_wasted_s": round(_s(chaos, "wasted"), 6),
        "chaos_conservation_err": (
            round(_goodput.conservation_error(chaos), 9) if chaos
            else -1.0),
    }


def run_linkmap_smoke(out_dir: str) -> dict:
    """Link-level weather-map smoke (the linkmap tentpole's consumer):
    a clean and a slow-link leg of a SYNTHETIC p=4 gtopk tree fleet —
    no trainer, no timing noise, so the baseline can pin the carve,
    the fleet merge, and the degradation rule exactly. Every rank runs
    its own LinkMap writing a real per-rank shard
    (metrics.rank{r}.jsonl), exactly the layout ``report linkmap``
    merges in production. Returns the fields the main run logs as ONE
    "linkmap" record:

      clean leg (4 windows)      every rank observes its exactly-modeled
                                 span, so after the carve every link's
                                 EWMA is identical: clean_max_dev_x
                                 (max |vs_median - 1| over the merged
                                 rows) is exactly 0, n_links is the
                                 tree's 4 distinct pairs, and
                                 ``report linkmap`` exits 0 — the
                                 no-false-positive pin
      slow leg (6 windows)       the degraded pair comes from the SAME
                                 resilience grammar production uses:
                                 parse_inject("slow_rank:2:...") names
                                 rank 2, and the slow link is the pair
                                 (2, 2^1)=(2,3) — both endpoints of a
                                 slow link measure the stall, so both
                                 ranks' spans are inflated. The carve
                                 spreads each rank's inflation over its
                                 2 rounds, the endpoint-mean merge
                                 concentrates it on dcn:2-3 (t0+d/2 vs
                                 t0+d/4 on the adjacent pairs), so the
                                 fleet-median rule must name EXACTLY
                                 the injected pair: slow_worst_src=2,
                                 slow_worst_dst=3 (atol 0). Feeding the
                                 merged map to an AnomalyMonitor at
                                 x=1.5/windows=3 with halt_on=warn must
                                 fire link_degraded on window 3 and
                                 halt — with the event record already
                                 durable in the shard (slow_fired,
                                 durable_before_halt, halt_exit_ok all
                                 exactly 1)

    Everything here is deterministic arithmetic (synthetic spans, exact
    carve, EWMA of a constant stream), so the baseline pins the ratio
    fields tight and the indicator fields exact."""
    from gtopkssgd_tpu.obs import linkmap as _linkmap
    from gtopkssgd_tpu.obs import report
    from gtopkssgd_tpu.obs.events import (AnomalyHalt, AnomalyMonitor,
                                          HALT_EXIT_CODE, Thresholds)
    from gtopkssgd_tpu.resilience.inject import parse_inject
    from gtopkssgd_tpu.utils.metrics import MetricsLogger

    p, wire_mode = 4, "gtopk"
    wire = 400_000.0
    delay_ms = 50.0

    def _modeled_span(rank: int) -> float:
        mine = _linkmap.rank_rounds(
            _linkmap.round_peers(wire_mode, p), rank)
        return sum(_linkmap.round_weights(mine, wire))

    def _fleet_ewma(maps: dict) -> dict:
        merged: dict = {}
        for lm in maps.values():
            for key, v in lm.ewma_by_link().items():
                merged.setdefault(key, []).append(v)
        return {k: sum(vs) / len(vs) for k, vs in merged.items()}

    # ---- clean leg: exactly-modeled spans, zero deviation expected.
    clean_dir = os.path.join(out_dir, "linkmap_clean")
    loggers = {r: MetricsLogger(out_dir=clean_dir, rank=r, shard=True)
               for r in range(p)}
    maps = {r: _linkmap.LinkMap(wire_mode, p, rank=r,
                                metrics=loggers[r])
            for r in range(p)}
    for step in range(1, 5):
        for rank, lm in maps.items():
            lm.observe(step, t_comm_ms=_modeled_span(rank),
                       wire_bytes=wire)
    for log in loggers.values():
        log.close()
    clean_recs, _ = report.load_records(clean_dir)
    clean_sum = _linkmap.summarize_linkmap(clean_recs)
    clean_max_dev = max(
        (abs(float(r["vs_median_x"]) - 1.0) for r in clean_sum["rows"]
         if isinstance(r.get("vs_median_x"), (int, float))),
        default=-1.0)
    clean_rc = report.run_linkmap([clean_dir])

    # ---- slow leg: the injected pair, the fleet rule, the halt.
    fault = parse_inject("slow_rank:2:0.05s@1-6")[0]
    slow_rank = int(fault.args[0])
    slow_peer = slow_rank ^ 1
    slow_dir = os.path.join(out_dir, "linkmap_slow")
    loggers = {r: MetricsLogger(out_dir=slow_dir, rank=r, shard=True)
               for r in range(p)}
    maps = {r: _linkmap.LinkMap(wire_mode, p, rank=r,
                                metrics=loggers[r])
            for r in range(p)}
    mon = AnomalyMonitor(
        thresholds=Thresholds(link_degraded_x=1.5,
                              link_degraded_windows=3),
        metrics=loggers[0], halt_on="warn")
    halted = 0.0
    try:
        for step in range(1, 7):
            for rank, lm in maps.items():
                t = _modeled_span(rank)
                if rank in (slow_rank, slow_peer):
                    t += delay_ms
                lm.observe(step, t_comm_ms=t, wire_bytes=wire)
            mon.observe_links(step, _fleet_ewma(maps))
    except AnomalyHalt:
        halted = float(HALT_EXIT_CODE == 44)
    for log in loggers.values():
        log.close()
    ev = next((e for e in mon.events if e["rule"] == "link_degraded"),
              None)
    slow_recs, _ = report.load_records(slow_dir)
    durable = any(r.get("kind") == "event"
                  and r.get("rule") == "link_degraded"
                  for r in slow_recs)
    slow_sum = _linkmap.summarize_linkmap(slow_recs)
    worst = slow_sum.get("worst") or {}
    slow_rc = report.run_linkmap([slow_dir])
    lo, hi = sorted((slow_rank, slow_peer))
    return {
        "clean_rc": float(clean_rc),
        "clean_links": float(clean_sum["n_links"]),
        "clean_max_dev_x": round(float(clean_max_dev), 6),
        "slow_fired": float(ev is not None),
        "slow_halted": halted,
        "durable_before_halt": float(durable),
        "slow_worst_src": float(worst.get("src", -1)),
        "slow_worst_dst": float(worst.get("dst", -1)),
        "slow_worst_is_injected_pair": float(
            worst.get("src") == lo and worst.get("dst") == hi),
        "slow_vs_median_x": (round(float(ev["value"]), 6)
                             if ev else -1.0),
        "slow_report_rc": float(slow_rc),
    }


def run_forecast_smoke(out_dir: str) -> dict:
    """Scale-out forecast smoke (the forecast tentpole's consumer):
    a clean and a drifted leg of a SYNTHETIC p=4 gtopk run — no
    trainer, no timing noise, so the baseline can pin the hindcast
    arithmetic, the per-target recommendation strings, and the
    forecast_drift halt contract exactly. Both legs write real
    metrics shards (the layout ``report forecast`` reads) through a
    live StepForecaster. Returns the fields the main run logs as ONE
    "forecast" record:

      clean leg (1 capture)      the critpath wall is CONSTRUCTED as
                                 compute + select + modeled comm x
                                 degrade (same predict_comm_ms the
                                 forecaster prices with), so the
                                 hindcast error is exactly 1.0 — the
                                 model-explains-its-own-run ceiling
                                 pin (clean_err_x, atol 1e-6). The
                                 durable record re-read from the shard
                                 parameterizes ``report forecast``
                                 (clean_rc 0) and carries the per-P
                                 grid (clean_n_rows) plus the exact
                                 recommendation indicators the regress
                                 plane pins as strings
      drift leg (3 captures)     the wall is 10x the model's
                                 prediction, so each capture's
                                 hindcast error (~10x) exceeds
                                 forecast_drift_x=4.0; the streak
                                 fires forecast_drift on capture 3
                                 with halt_on=warn — with the
                                 forecast AND event records already
                                 durable in the shard (drift_fired,
                                 durable_before_halt, drift_halted
                                 all exactly 1, drift_windows exactly
                                 3)

    Everything here is deterministic arithmetic (synthetic budgets,
    the fitted-model identity, an EWMA of a constant stream), so the
    baseline pins the ratio fields tight and the indicators exact."""
    from gtopkssgd_tpu.obs import forecast as _forecast
    from gtopkssgd_tpu.obs import report
    from gtopkssgd_tpu.obs.events import (AnomalyHalt, AnomalyMonitor,
                                          HALT_EXIT_CODE, Thresholds)
    from gtopkssgd_tpu.obs.ledger import predict_comm_ms, wire_mode_for
    from gtopkssgd_tpu.utils.metrics import MetricsLogger

    params = {"mode": "gtopk", "p": 4, "n": 1_000_000, "k": 10_000,
              "codec": "fp32", "schedule": "tree",
              "bucketing": "concat", "buckets": None, "ici_size": 1}
    fit = {"alpha_ms": 0.5, "beta_gbps": 8.0, "resid_ms": 0.02,
           "fit_source": "smoke"}
    compute_ms, select_ms = 10.0, 2.0
    # One degraded link among four: degrade_factor = mean/median = 1.25.
    links = [{"ewma_ms": 1.0}, {"ewma_ms": 1.0},
             {"ewma_ms": 1.0}, {"ewma_ms": 2.0}]
    degrade = _forecast.degrade_factor(links)
    wm = wire_mode_for(params["mode"], params["schedule"],
                       params["bucketing"])
    comm = predict_comm_ms(wm, params["p"], n=params["n"],
                           k=params["k"], alpha_ms=fit["alpha_ms"],
                           beta_gbps=fit["beta_gbps"],
                           codec=params["codec"])
    pred_ms = compute_ms + select_ms + comm * degrade

    def _critpath(wall_ms: float) -> dict:
        return {"wall_us": wall_ms * 1e3,
                "t_compute_us": compute_ms * 1e3,
                "t_select_us": select_ms * 1e3}

    # ---- clean leg: measured == modeled, so the hindcast is exact.
    clean_dir = os.path.join(out_dir, "forecast_clean")
    log = MetricsLogger(out_dir=clean_dir, rank=0, shard=True)
    fc = _forecast.StepForecaster(params, baseline=fit, metrics=log)
    fc.note_calib({"alpha_fit_ms": fit["alpha_ms"],
                   "beta_fit_gbps": fit["beta_gbps"],
                   "resid_ms": fit["resid_ms"]})
    fc.note_linkmap({"links": links})
    fc.note_critpath(_critpath(pred_ms))
    rec = fc.observe(step=1)
    log.close()
    clean_recs, _ = report.load_records(clean_dir)
    clean_durable = any(r.get("kind") == "forecast" for r in clean_recs)
    clean_rc = report.run_forecast([clean_dir])

    # ---- drift leg: reality 10x the model -> streak -> fire -> halt.
    drift_dir = os.path.join(out_dir, "forecast_drift")
    log = MetricsLogger(out_dir=drift_dir, rank=0, shard=True)
    mon = AnomalyMonitor(
        thresholds=Thresholds(forecast_drift_x=4.0,
                              forecast_drift_windows=3),
        metrics=log, halt_on="warn")
    fcd = _forecast.StepForecaster(params, baseline=fit, metrics=log,
                                   monitor=mon)
    fcd.note_linkmap({"links": links})
    halted = 0.0
    try:
        for step in range(1, 4):
            fcd.note_critpath(_critpath(10.0 * pred_ms))
            fcd.observe(step)
    except AnomalyHalt:
        halted = float(HALT_EXIT_CODE == 44)
    log.close()
    ev = next((e for e in mon.events if e["rule"] == "forecast_drift"),
              None)
    drift_recs, _ = report.load_records(drift_dir)
    n_forecast = sum(1 for r in drift_recs
                     if r.get("kind") == "forecast")
    durable = any(r.get("kind") == "event"
                  and r.get("rule") == "forecast_drift"
                  for r in drift_recs)
    return {
        "clean_err_x": float(rec["hindcast_err_x"]),
        "clean_rc": float(clean_rc),
        "clean_durable": float(clean_durable),
        "clean_n_rows": float(len(rec["rows"])),
        "clean_degrade_x": round(float(rec["degrade_x"]), 6),
        "clean_rec_p256_balanced": float(
            str(rec.get("rec_p256", "")).startswith("balanced")),
        "clean_rec_p1024_balanced": float(
            str(rec.get("rec_p1024", "")).startswith("balanced")),
        "clean_has_crossover": float(rec.get("crossover_p")
                                     is not None),
        "clean_band_p256_ms": round(
            float(rec["step_ms_hi_p256"] - rec["step_ms_p256"]), 6),
        "drift_fired": float(ev is not None),
        "drift_halted": halted,
        "drift_windows": float(ev["windows"]) if ev else -1.0,
        "drift_err_x": (round(float(ev["value"]), 6)
                        if ev else -1.0),
        "durable_before_halt": float(durable),
        "drift_n_forecast_records": float(n_forecast),
    }


def run_elastic_smoke(out_dir: str) -> dict:
    """Elastic-fleet smoke (the elastic tentpole's consumer): three
    resize loops of the canonical run under ``--elastic``
    (resilience/elastic.py), each closed end-to-end — drain, durable
    "resize" record, exit 46, relaunch in a FRESH out_dir (ckpt +
    elastic.json copied over, exactly the supervisor contract) at the
    new --nworkers. Returns the fields the main run logs as ONE
    "resize" record so the drift gate can pin the PR's acceptance
    numbers:

      shrink leg (2 -> 1)        resize@3:1 drains at step 3, saves,
                                 logs exactly ONE "resize" record
                                 (old_p=2, new_p=1, reason=inject,
                                 drained_step=3) and exits 46; the
                                 relaunch restores at P=1 (residual
                                 folded 2 -> 1) and completes (rc 0)
                                 with the SAME lineage_id at
                                 resize_epoch 1. Both registry lines
                                 carry the lineage, so history renders
                                 ONE lineage with 2 runs and
                                 pick_baseline joins the post-resize
                                 segment to the pre-resize entry
                                 across the config_hash change
      grow leg (1 -> 2)          resize@3:2 -> 46 -> relaunch at P=2:
                                 the comm stack re-derives at the new
                                 size for free, pinned by the
                                 post-resize "plan" record scoring at
                                 p=2 (at p=1 no plan decision exists
                                 to score)
      evict leg                  the decision function: a synthetic
                                 3-rank fleet view whose rank 0 sits
                                 far below the median goodput_frac
                                 (dominant badput: wait) with a
                                 persistent-straggler row — advise()
                                 names rank 0, eviction_decision
                                 returns new_p=2 with the straggler
                                 corroborated, and refuses at
                                 min_fleet=3 (never below the floor);
                                 the fleet arithmetic pins the exact
                                 recovered goodput fraction. The loop
                                 then closes in the trainer: a 2-way
                                 run with injected 0.2 s straggler
                                 stalls and evict_rank:0@3 resizes
                                 with reason=evict (evicted_ranks=[0])
                                 -> 46 -> relaunch at P=1 completes,
                                 and the post-resize goodput_frac
                                 exceeds the straggler-burdened
                                 pre-resize one (one-sided indicator)

    Exit codes, record counts, lineage identity, and the synthetic
    fleet arithmetic are structural (exact pins); the real-timing
    goodput comparison enters only as the one-sided indicator."""
    import json as _json
    import shutil

    from gtopkssgd_tpu import dist_trainer
    from gtopkssgd_tpu.obs import goodput as _goodput
    from gtopkssgd_tpu.obs import registry as _registry
    from gtopkssgd_tpu.resilience import eviction_decision

    canon = [
        "--dnn", "resnet20", "--batch-size", "4",
        "--compression", "gtopk_layerwise", "--density", "0.01",
        "--seed", "42", "--eval-batches", "1", "--log-interval", "1",
        "--obs-interval", "1",
    ]

    def _recs(d):
        with open(os.path.join(d, "metrics.jsonl")) as fh:
            return [_json.loads(line) for line in fh]

    def _relaunch_dir(src: str, dst: str) -> str:
        """The supervisor contract: a FRESH out_dir seeded with the
        checkpoint tree and the lineage file (reusing the old out_dir
        would corrupt its registry summary — run_summary keys on the
        FIRST manifest in the stream)."""
        os.makedirs(dst, exist_ok=True)
        shutil.copytree(os.path.join(src, "ckpt"),
                        os.path.join(dst, "ckpt"))
        shutil.copy2(os.path.join(src, "elastic.json"),
                     os.path.join(dst, "elastic.json"))
        return dst

    def _final_goodput_frac(d) -> float:
        finals = [r for r in _recs(d) if r.get("kind") == "goodput"
                  and r.get("final")]
        return float(finals[-1].get("goodput_frac", -1.0)) if finals \
            else -1.0

    # ---- shrink leg: 2 -> 1 with the registry lineage join.
    reg_dir = os.path.join(out_dir, "elastic_registry")
    shrink_a = os.path.join(out_dir, "elastic_shrink")
    shrink_rc = dist_trainer.main(canon + [
        "--nworkers", "2", "--elastic", "--inject", "resize@3:1",
        "--num-iters", "6", "--registry", reg_dir,
        "--out-dir", shrink_a])
    resizes = [r for r in _recs(shrink_a) if r.get("kind") == "resize"]
    rz = resizes[-1] if resizes else {}
    shrink_b = _relaunch_dir(shrink_a,
                             os.path.join(out_dir, "elastic_shrink_post"))
    resume_rc = dist_trainer.main(canon + [
        "--nworkers", "1", "--elastic", "--resume",
        "--num-iters", "6", "--registry", reg_dir,
        "--out-dir", shrink_b])
    with open(os.path.join(shrink_b, "elastic.json")) as fh:
        lineage_b = _json.load(fh)
    entries, _bad = _registry.load_registry(reg_dir)
    lineages = {e.get("lineage_id") for e in entries
                if e.get("lineage_id")}
    joined = (_registry.pick_baseline(entries[-1], entries[:-1])
              if len(entries) >= 2 else None)
    hist = _registry.history_rows(
        entries, config_hash=entries[0].get("config_hash")) \
        if entries else []

    # ---- grow leg: 1 -> 2, the comm stack re-derived at the new P.
    grow_a = os.path.join(out_dir, "elastic_grow")
    grow_rc = dist_trainer.main(canon + [
        "--nworkers", "1", "--elastic", "--inject", "resize@3:2",
        "--num-iters", "6", "--out-dir", grow_a])
    grow_b = _relaunch_dir(grow_a,
                           os.path.join(out_dir, "elastic_grow_post"))
    grow_resume_rc = dist_trainer.main(canon + [
        "--nworkers", "2", "--elastic", "--resume",
        "--num-iters", "6", "--out-dir", grow_b])
    grow_plans = [r for r in _recs(grow_b) if r.get("kind") == "plan"]
    grow_plan_p = float(grow_plans[-1].get("p", -1)) if grow_plans \
        else -1.0

    # ---- evict leg, decision half: synthetic 3-rank fleet view with
    # exact arithmetic (no timing noise) — rank 0 far below the median,
    # wait-dominated, persistent per the straggler plane.
    by_rank = {
        0: {"goodput_frac": 0.45, "goodput_s": 45.0, "wait_s": 55.0,
            "wall_s": 100.0},
        1: {"goodput_frac": 0.92, "goodput_s": 92.0, "wait_s": 8.0,
            "wall_s": 100.0},
        2: {"goodput_frac": 0.95, "goodput_s": 95.0, "wait_s": 5.0,
            "wall_s": 100.0},
    }
    merged = {
        "goodput_by_rank": by_rank,
        "stragglers": [{"slowest_rank": 0, "persistent": True,
                        "ewma_lag_s": 0.4}],
    }
    decision = eviction_decision(merged, p=3, min_fleet=1,
                                 margin=0.02) or {}
    refused = eviction_decision(merged, p=3, min_fleet=3, margin=0.02)
    pre_fleet = _goodput.fleet_decomposition(by_rank) or {}
    post_fleet = _goodput.fleet_decomposition(
        {r: d for r, d in by_rank.items()
         if r != decision.get("rank")}) or {}
    fleet_gain = (float(post_fleet.get("goodput_frac", 0.0))
                  - float(pre_fleet.get("goodput_frac", 0.0)))

    # ---- evict leg, trainer half: the straggler-burdened pre-resize
    # run (injected 0.2 s stalls) evicts rank 0 -> 46 -> the clean
    # post-resize run's goodput_frac must exceed it.
    evict_a = os.path.join(out_dir, "elastic_evict")
    evict_rc = dist_trainer.main(canon + [
        "--nworkers", "2", "--elastic",
        "--inject", "slow_rank:0:0.2@1-2,evict_rank:0@3",
        "--num-iters", "6", "--out-dir", evict_a])
    ev_resizes = [r for r in _recs(evict_a) if r.get("kind") == "resize"]
    ev = ev_resizes[-1] if ev_resizes else {}
    pre_frac = _final_goodput_frac(evict_a)
    evict_b = _relaunch_dir(evict_a,
                            os.path.join(out_dir, "elastic_evict_post"))
    evict_resume_rc = dist_trainer.main(canon + [
        "--nworkers", "1", "--elastic", "--resume",
        "--num-iters", "6", "--out-dir", evict_b])
    post_frac = _final_goodput_frac(evict_b)

    return {
        "shrink_rc": float(shrink_rc),
        "shrink_resize_records": float(len(resizes)),
        "shrink_old_p": float(rz.get("old_p", -1)),
        "shrink_new_p": float(rz.get("new_p", -1)),
        "shrink_reason_inject": float(rz.get("reason") == "inject"),
        "shrink_drained_step": float(rz.get("drained_step", -1)),
        "shrink_resume_rc": float(resume_rc),
        "lineage_stable": float(
            bool(rz.get("lineage_id"))
            and lineage_b.get("lineage_id") == rz.get("lineage_id")),
        "resize_epoch_resume": float(lineage_b.get("resize_epoch", -1)),
        "registry_lineages": float(len(lineages)),
        "registry_runs": float(len(entries)),
        "regress_lineage_join": float(
            joined is not None
            and joined.get("lineage_id") == entries[-1].get("lineage_id")
            and joined.get("config_hash")
            != entries[-1].get("config_hash")),
        "history_rows_joined": float(len(hist)),
        "grow_rc": float(grow_rc),
        "grow_resume_rc": float(grow_resume_rc),
        "grow_post_plan_p": grow_plan_p,
        "advise_rank": float(decision.get("rank", -1)),
        "decision_new_p": float(decision.get("new_p", -1)),
        "decision_persistent": float(
            bool(decision.get("persistent_straggler"))),
        "decision_min_fleet_refused": float(refused is None),
        "fleet_gain_frac": round(fleet_gain, 6),
        "evict_rc": float(evict_rc),
        "evict_reason_evict": float(ev.get("reason") == "evict"),
        "evict_evicted_rank": float(
            (ev.get("evicted_ranks") or [-1])[0]),
        "evict_resume_rc": float(evict_resume_rc),
        "evict_goodput_pre": round(pre_frac, 6),
        "evict_goodput_post": round(post_frac, 6),
        "evict_goodput_improved": float(post_frac > pre_frac),
    }


def run_smoke(out_dir: str, only=None) -> str:
    """Train the canonical run; returns the run dir (metrics.jsonl inside).

    ``only`` (a SMOKES name) restricts the sub-smokes to that one (plus
    its SMOKE_DEPS); the canonical main run still executes — it hosts
    the grafted records — but only the selected smoke's records enter
    the stream, matching the subset gate main() builds for ``--only``.

    After the baseline steps, two more run under the profiler
    (obs.trace_attr.capture — Python tracer off, so op events survive)
    and the paper's T_compute/T_select/T_comm split of that trace is
    logged as an "attr" record, putting the decomposition itself under
    the drift gate's frac checks. Finally the run's own records are
    fleet-merged (obs/fleet.py) and logged back as "fleet" records: on
    this single-process run the merge is a 1-rank fleet, so n_ranks is
    exactly 1 and every skew_max exactly 0 — structural invariants the
    baseline pins, putting the merge path itself under the drift gate.

    Before all that, a chaos sub-run (run_recovery_smoke) exercises the
    resilience path — injected NaN claimed by a skip policy — and its
    inject/recovery records are grafted into this run's stream, so the
    baseline also pins recovery structure (one firing, one recovery,
    final_status=completed). The twostage and codec A/B sub-runs graft
    one summary record each the same way ("twostage", "codec")."""
    from gtopkssgd_tpu.obs import fleet, report
    from gtopkssgd_tpu.obs.trace_attr import attribute, capture
    from gtopkssgd_tpu.trainer import Trainer

    # Chaos sub-run first (its own Trainer, its own subdir), then the
    # main run re-logs ONLY the resilience records so the baseline can
    # pin recovery structure without the sub-run's train/obs rows
    # polluting the main run's value statistics. The twostage A/B runs
    # the same way: its sub-runs live in subdirs and only the single
    # summary record enters this run's stream.
    rec_dir = (run_recovery_smoke(out_dir)
               if _selected("recovery", only) else None)
    twostage_rec = (run_twostage_smoke(out_dir)
                    if _selected("twostage", only) else None)
    codec_rec = (run_codec_smoke(out_dir)
                 if _selected("codec", only) else None)
    plan_rec = (run_plan_smoke(out_dir, codec_rec)
                if _selected("plan", only) else None)
    bucket_rec = (run_bucket_smoke(out_dir)
                  if _selected("bucket", only) else None)
    overlap_rec = (run_overlap_smoke(out_dir)
                   if _selected("overlap", only) else None)
    calib_rec = (run_calib_smoke(out_dir)
                 if _selected("calib", only) else None)
    mem_rec = (run_mem_smoke(out_dir)
               if _selected("mem", only) else None)
    goodput_rec = (run_goodput_smoke(out_dir)
                   if _selected("goodput", only) else None)
    linkmap_rec = (run_linkmap_smoke(out_dir)
                   if _selected("linkmap", only) else None)
    forecast_rec = (run_forecast_smoke(out_dir)
                    if _selected("forecast", only) else None)
    elastic_rec = (run_elastic_smoke(out_dir)
                   if _selected("elastic", only) else None)
    critpath_rec = critpath_real = None
    if _selected("critpath", only):
        critpath_rec, critpath_real = run_critpath_smoke(out_dir)

    cfg = smoke_config(out_dir)
    with Trainer(cfg) as t:
        t.train(SMOKE_STEPS)
        trace_dir = os.path.join(out_dir, "trace")
        try:
            with capture(trace_dir):
                t.train(2)
            rec = attribute(trace_dir, mode=cfg.compression)
        except Exception as e:  # platform without usable op traces
            t.metrics.log("attr_error", error=str(e)[:200])
        else:
            t.metrics.log("attr", flush=True, **{
                k: v for k, v in rec.items() if v is not None})
        # The metrics file is line-buffered, so everything logged above
        # is already readable mid-run; merge obs records only (train
        # records at log_interval=2 over 6 steps give 3 more rows each
        # but no extra coverage).
        merged = fleet.merge([out_dir], kinds=("obs",))
        for row in merged["rows"]:
            t.metrics.log("fleet", **fleet.row_record(row))
        # Graft the chaos sub-run's inject/recovery records into this
        # run's stream (re-stamped time/rank) so the gate's structural
        # recovery checks (exactly one firing, n_recoveries, completed)
        # read from the same metrics.jsonl as everything else.
        if rec_dir is not None:
            rec_records, _ = report.load_records(rec_dir)
            for r in rec_records:
                if r.get("kind") in ("inject", "recovery"):
                    t.metrics.log(r["kind"], **{
                        k: v for k, v in r.items()
                        if k not in ("kind", "time", "rank")})
        # Same graft for the twostage A/B evidence: the gate pins the
        # audited recall floor and the one-sided T_select regression.
        if twostage_rec is not None:
            t.metrics.log("twostage", **twostage_rec)
        # And the wire-codec A/B: int8-vs-fp32 wire-bytes ratios, the
        # one-sided >=3x DCN-reduction evidence, the audited recall
        # floor under the lossy codec, and the ledger's modeled-vs-
        # measured bytes ratio.
        if codec_rec is not None:
            t.metrics.log("codec", **codec_rec)
        # And the comm-planner A/B: balanced-vs-tree measured wire
        # ratios, the recall floor under the balanced schedule, and the
        # plan-keyed ledger's modeled-vs-measured bytes ratio. (The
        # trainer already logged this run's own "plan" decision record,
        # whose plan_is_default=1.0 the baseline pins — defaults keep
        # the historical tree wire.)
        if plan_rec is not None:
            t.metrics.log("plan", **plan_rec)
        # And the bucketing A/B: leaf-vs-auto collective counts (the
        # one-sided >=3x fewer-merges evidence), the audited recall
        # floor on the bucketed arm, and the bucket-summed ledger's
        # modeled-vs-measured bytes ratio.
        if bucket_rec is not None:
            t.metrics.log("bucket", **bucket_rec)
        # And the overlapped-pipeline A/B: exact-zero serial-vs-overlap
        # bit-identity deltas (fp32 + int8), the measured overlap_frac
        # from the pipelined arm's trace capture, the recall floor, and
        # the model-side DP crossover pin (B>1 under overlap pricing at
        # ResNet-50/alpha=0.1). Durable evidence -> flush=True.
        if overlap_rec is not None:
            t.metrics.log("overlap", flush=True, **overlap_rec)
        # And the calibration smoke: the robust fit pinned against its
        # synthetic ground truth, the exact refit/drift-firing counts,
        # the closed obs->planner artifact round-trip, and (as a
        # separate "regress" record) the registry CLI's exit-code
        # contract. Both kinds are durable -> flush=True.
        if calib_rec is not None:
            _regress_keys = ("regress_rc_empty", "regress_rc_pass",
                             "regress_rc_fail", "history_rc")
            t.metrics.log("calib", flush=True, **{
                k: v for k, v in calib_rec.items()
                if k not in _regress_keys})
            t.metrics.log("regress", flush=True, **{
                k: v for k, v in calib_rec.items() if k in _regress_keys})
        # And the compile/memory-plane smoke: one-executable discipline
        # on the clean leg (recompile_count 0, one compile record, the
        # manifest's peak-HBM matched and registry-carried) and the full
        # storm chain on the chaos leg (reshape -> retrace -> exactly
        # one recompile -> exit 44).
        if mem_rec is not None:
            t.metrics.log("mem", **mem_rec)
        # And the goodput smoke: the clean leg's conservation pins
        # (other_frac <= 0.05, construction-invariant remainder ~0) and
        # the chaos leg's fault-to-category indicators (skip -> wasted,
        # slow_rank -> wait, emergency save -> ckpt, preempt -> 45 with
        # the final record durable first). Durable -> flush=True.
        if goodput_rec is not None:
            t.metrics.log("goodput", flush=True, **goodput_rec)
        # And the linkmap smoke: the clean fleet's zero-deviation pin
        # (no false positives), the slow leg naming exactly the
        # injected pair (slow_rank inject grammar -> worst link), and
        # the link_degraded fire/halt contract with the event record
        # durable before the raise. Durable evidence -> flush=True.
        if linkmap_rec is not None:
            t.metrics.log("linkmap", flush=True, **linkmap_rec)
        # And the forecast smoke: the clean leg's exact hindcast
        # ceiling (measured == modeled -> err 1.0), the per-target
        # recommendation indicators and resid-derived band, and the
        # drifted leg's forecast_drift fire/halt contract with the
        # forecast + event records durable before the raise.
        # Durable evidence -> flush=True.
        if forecast_rec is not None:
            t.metrics.log("forecast", flush=True, **forecast_rec)
        # And the elastic smoke: three closed resize loops (shrink,
        # grow, evict) — exit-46 contract, exactly-one durable resize
        # record, lineage identity across the relaunch, the registry's
        # lineage join, the post-resize plan re-scored at the new P,
        # and the eviction decision's exact synthetic-fleet arithmetic
        # with the one-sided post-eviction goodput indicator.
        # Durable evidence -> flush=True.
        if elastic_rec is not None:
            t.metrics.log("resize", flush=True, **elastic_rec)
        # And the critical-path smoke: one REAL per-step stage-interval
        # record from the overlap arm (so the registry's wait_frac /
        # crit_stage_modal path runs on gate data) plus the summary the
        # baseline pins — the >=90%-coverage floor breach (exact), the
        # synthetic skewed arm's wait share, and the clean/skewed
        # critpath_shift firing counts with the exit-44 halt contract.
        # Durable evidence -> flush=True on both.
        if critpath_real is not None:
            t.metrics.log("critpath", flush=True, **critpath_real)
        if critpath_rec is not None:
            t.metrics.log("critpath", flush=True, **critpath_rec)
        # Static-analysis gate: run graftlint in-process over the
        # package + benchmarks against the committed repo baseline and
        # record the counts; the gate pins non_baselined at exactly 0,
        # so a new invariant violation fails the same drift gate as a
        # numeric regression.
        if _selected("lint", only):
            t.metrics.log("lint", **run_lint_smoke())
    return out_dir


def run_critpath_smoke(out_dir: str) -> tuple:
    """Distributed-critical-path smoke (the critpath tentpole's
    consumer): two tiny p=2 arms differing ONLY in --pipeline (serial
    vs overlap), each with --obs-critpath at every-step cadence so the
    trainer's own capture gate logs durable per-step stage-interval
    records, plus a deterministic synthetic skewed/clean pair for the
    fields real timing can't pin. Returns (summary_record,
    real_record): the summary the gate pins and one real per-step
    record from the overlap arm grafted into the main stream (so the
    registry's wait_frac/crit_stage_modal path runs on gate data).

      crit_frac                min over every logged record of the
                               single-rank chain walk's coverage of
                               that record's measured step wall —
                               gap-filled attribution must explain
                               the whole captured dispatch
      crit_frac_floor_breach   max(0, 0.90 - crit_frac): the PR's
                               >=90%-coverage acceptance pin, exact
      n_records                total critpath records across both
                               arms (2 steps x 2 arms)
      wait_frac_skewed         synthetic barrier-stall rank record
                               (fixture geometry): exactly 0.8
      crit_stage_skewed_wait   1.0 iff the joined 2-rank skewed step's
                               global critical stage is "wait"
      shift_events_clean       critpath_shift firings on a 6-step
                               constant-stage stream: exactly 0
      shift_events_skewed      firings on compute x3 -> wait x3 at
                               the default 3-window threshold:
                               exactly 1
      halt_exit_ok             1.0 iff halt_on="warn" raises
                               AnomalyHalt on that shift and the
                               halt exit code contract is 44
    """
    from gtopkssgd_tpu.obs import critpath, report
    from gtopkssgd_tpu.obs.events import (AnomalyHalt, AnomalyMonitor,
                                          HALT_EXIT_CODE, Thresholds)
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    fracs = {}
    n_records = 0
    real_rec = None
    for pipe in ("serial", "overlap"):
        sub = os.path.join(out_dir, f"critpath_{pipe}")
        cfg = TrainConfig(
            dnn="resnet20", batch_size=4, nworkers=2,
            compression="gtopk_layerwise", density=0.01, seed=42,
            max_epochs=1, log_interval=2, eval_batches=1,
            obs_interval=1, wire_codec="fp32", buckets="4",
            pipeline=pipe, out_dir=sub,
            obs_critpath=True, obs_calib_interval=1)
        with Trainer(cfg) as t:
            t.train(2)
        recs, _ = report.load_records(sub)
        cps = [r for r in recs if r.get("kind") == "critpath"]
        n_records += len(cps)
        arm_fracs = []
        for cp in cps:
            res = critpath.critical_path({0: cp["segments"]})
            arm_fracs.append(res["crit_frac"])
        fracs[pipe] = min(arm_fracs) if arm_fracs else 0.0
        if pipe == "overlap" and cps:
            real_rec = {k: v for k, v in cps[-1].items()
                        if k not in ("kind", "time", "rank")}
    crit_frac = min(fracs.values()) if fracs else 0.0

    # ---- deterministic synthetic pair (fixture geometry): real CPU
    # timing can't pin wait shares or shift counts, hand-built segment
    # sets can, and they run the SAME join/rule code paths.
    stalled = [{"stage": "compute", "t0_us": 0.0, "t1_us": 100.0},
               {"stage": "wait", "t0_us": 100.0, "t1_us": 900.0},
               {"stage": "comm", "t0_us": 900.0, "t1_us": 1000.0}]
    skew_rec = critpath.build_record(stalled)
    joined = critpath.critical_path({0: list(stalled), 1: list(stalled)})

    clean_mon = AnomalyMonitor()
    for step in range(1, 7):
        clean_mon.observe_critpath(step, crit_stage="compute")
    shift_clean = sum(e["rule"] == "critpath_shift"
                      for e in clean_mon.events)
    skew_mon = AnomalyMonitor(
        thresholds=Thresholds(critpath_shift_windows=3))
    for step, stage in enumerate(["compute"] * 3 + ["wait"] * 3, 1):
        skew_mon.observe_critpath(step, crit_stage=stage)
    shift_skew = sum(e["rule"] == "critpath_shift"
                     for e in skew_mon.events)
    halt_ok = 0.0
    halt_mon = AnomalyMonitor(
        thresholds=Thresholds(critpath_shift_windows=3), halt_on="warn")
    try:
        for step, stage in enumerate(["compute"] * 3 + ["wait"] * 3, 1):
            halt_mon.observe_critpath(step, crit_stage=stage)
    except AnomalyHalt:
        halt_ok = float(HALT_EXIT_CODE == 44)

    summary = {
        "n_records": float(n_records),
        "crit_frac": round(float(crit_frac), 6),
        "crit_frac_serial": round(float(fracs.get("serial", 0.0)), 6),
        "crit_frac_overlap": round(float(fracs.get("overlap", 0.0)), 6),
        "crit_frac_floor_breach": round(max(0.0, 0.90 - crit_frac), 6),
        "wait_frac_skewed": skew_rec["wait_frac"],
        "crit_stage_skewed_wait": float(joined["crit_stage"] == "wait"),
        "shift_events_clean": float(shift_clean),
        "shift_events_skewed": float(shift_skew),
        "halt_exit_ok": halt_ok,
    }
    return summary, real_rec


def run_lint_smoke() -> dict:
    """Graftlint finding counts for the shipped tree, as a gate record.

    Uses the analysis engine directly (no subprocess, no jax) with the
    repo-root baseline, scanning the same paths CI lints:
    gtopkssgd_tpu/ and benchmarks/.
    """
    from gtopkssgd_tpu.analysis import ALL_RULES, load_baseline, run

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    result = run(
        [os.path.join(repo, "gtopkssgd_tpu"),
         os.path.join(repo, "benchmarks")],
        rules=ALL_RULES,
        baseline=load_baseline(
            os.path.join(repo, "graftlint_baseline.json")),
        root=repo)
    return {
        "files_scanned": result.files_scanned,
        "non_baselined": len(result.findings),
        "baselined": len(result.baselined),
        "suppressed": len(result.suppressed),
        "stale_baseline": len(result.stale_baseline),
    }


def _write_subset_baseline(out_dir: str, name: str) -> str:
    """Extract the committed baseline checks the named sub-smoke owns
    (by kind; layer checks never belong to a sub-smoke) into a derived
    subset file inside the run dir. Manifest pins are dropped — the
    subset run's manifest is the main run's, and those pins belong to
    the full gate."""
    with open(BASELINE) as fh:
        base = json.load(fh)
    kinds = set(SMOKES[name])
    checks = [c for c in base.get("checks", [])
              if c.get("layer") is None and c.get("kind") in kinds]
    if not checks:
        raise SystemExit(
            f"--only {name}: the committed baseline has no checks with "
            f"kind in {sorted(kinds)} — add the check specs to "
            f"{os.path.basename(BASELINE)} first, then re-stamp their "
            f"expectations with --only {name} --write-baseline")
    sub = {
        "description": (f"{name} subset of {os.path.basename(BASELINE)} "
                        "(derived per run; not committed)"),
        "checks": checks,
    }
    path = os.path.join(out_dir, f"gate_subset_{name}.json")
    with open(path, "w") as fh:
        json.dump(sub, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return path


def _merge_subset_baseline(restamped_path: str) -> None:
    """Fold a re-stamped subset back into the committed baseline:
    each subset check replaces the committed check with the same
    identity (report._check_id), everything else — other checks, their
    order, the manifest pins — is untouched. This is what makes
    ``--only NAME --write-baseline`` safe: it can only move the
    expectations the named sub-smoke owns."""
    from gtopkssgd_tpu.obs.report import _check_id

    with open(restamped_path) as fh:
        restamped = {_check_id(c): c for c in json.load(fh)["checks"]}
    with open(BASELINE) as fh:
        base = json.load(fh)
    merged = 0
    for i, check in enumerate(base.get("checks", [])):
        new = restamped.pop(_check_id(check), None)
        if new is not None:
            base["checks"][i] = new
            merged += 1
    # A subset check absent from the committed list can only mean the
    # committed file changed under us; append rather than drop it.
    base["checks"].extend(restamped.values())
    with open(BASELINE, "w") as fh:
        json.dump(base, fh, indent=1, sort_keys=True)
        fh.write("\n")
    print(f"merged {merged + len(restamped)} re-stamped check(s) "
          f"into {BASELINE}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        "obs_gate_smoke",
        description="Run the canonical obs-gate smoke and gate (or "
                    "regenerate) the committed baseline.")
    ap.add_argument("--write-baseline", action="store_true",
                    help="re-stamp the committed baseline's expectations "
                         "from this run instead of failing on drift")
    ap.add_argument("--only", choices=sorted(SMOKES), default=None,
                    help="run ONE sub-smoke (plus its dependencies) and "
                         "gate just the baseline checks its kinds own; "
                         "with --write-baseline, merge only those "
                         "re-stamped checks back into the committed "
                         "baseline")
    ap.add_argument("--out-dir", default=None,
                    help="keep the run here (default: a temp dir)")
    args = ap.parse_args(argv)

    # The gate is a CPU-mesh smoke wherever it runs (as tests/conftest.py).
    from gtopkssgd_tpu.utils import enable_compilation_cache, force_cpu_mesh

    force_cpu_mesh(smoke_config("ignored").nworkers)
    enable_compilation_cache()

    out = args.out_dir or tempfile.mkdtemp(prefix="obs_gate_smoke_")
    os.makedirs(out, exist_ok=True)

    from gtopkssgd_tpu.obs import report

    if args.only:
        subset = _write_subset_baseline(out, args.only)
        run_smoke(out, only=args.only)
        write = subset + ".new" if args.write_baseline else None
        rc = report.run_gate(out, subset, write=write)
        if write and os.path.exists(write):
            _merge_subset_baseline(write)
        return rc

    run_smoke(out)
    write = BASELINE if args.write_baseline else None
    return report.run_gate(out, BASELINE, write=write)


if __name__ == "__main__":
    raise SystemExit(main())
