"""Sparse merge-accumulate cost on real hardware — the evidence for/against
a fused Pallas merge kernel.

SURVEY.md §2 (native table) and §7 step 6 name a "sparse merge-accumulate"
kernel as on the critical path of every gTop-k tree round (the reference did
this merge host-side in numpy inside allreducer.py::gtopk_sparse_allreduce).
The TPU rebuild's per-round merge is `ops.topk.merge_sparse_sets` — an XLA
program (concat 2k -> argsort by index -> adjacent duplicate sum -> top_k).
This benchmark measures, at the reference's real (N, k) operating points:

  * `merge`       — merge_sparse_sets itself, one tree round's on-device cost;
  * `merge_chain` — log2(32) = 5 chained merges, a whole 32-worker tree's
                    merge work as XLA sees it (collectives excluded — one
                    chip — so this is the pure compute side of the tree);
  * `merge_argsort_topk` — the round-1 formulation (argsort + jnp.take
                    gathers, lax.top_k reselect), kept as the measured
                    justification for the carried-sort rewrite;
  * `dense_scatter` — the naive alternative (scatter both sets into a dense
                    f32[N] + exact top_k over N), to show why the sort-based
                    sparse formulation was chosen.

The verdict this artifact encodes: whether the XLA merge is already cheap
relative to its train step (ResNet-50's device_step_ms, 217 ms at batch
512 — PERF_LEDGER.jsonl), i.e. whether a hand-fused Pallas merge kernel could
buy anything measurable.

Run:  python -m benchmarks.merge_bench [--out PATH] [--quick]
"""

from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp

from gtopkssgd_tpu.ops import merge_sparse_sets, scatter_add_dense, topk_abs
from gtopkssgd_tpu.ops.topk import k_for_density
from gtopkssgd_tpu.utils import time_calls

SIZES = {
    "resnet20-270k": 272_474,
    "resnet50-25.6M": 25_557_032,
    "vgg16-61M": 61_090_496,
}
DENSITIES = (0.001, 0.01)
CHAIN_ROUNDS = 5  # log2(32): the paper's cluster size


def _random_sets(n: int, k: int, count: int):
    """`count` distinct sparse sets with disjoint-ish random indices —
    the honest case for the merge (round-1 lesson: replicated inputs are
    the duplicate-heavy cheapest case)."""
    sets = []
    for i in range(count):
        kk = jax.random.PRNGKey(i)
        idx = jax.random.randint(kk, (k,), 0, n, jnp.int32)
        vals = jax.random.normal(jax.random.fold_in(kk, 1), (k,), jnp.float32)
        sets.append((vals, idx))
    return sets


def _time(fn, args, min_seconds: float):
    return time_calls(fn, args, min_seconds, 4)


def time_merge(n: int, k: int, min_seconds: float):
    (va, ia), (vb, ib) = _random_sets(n, k, 2)
    fn = jax.jit(lambda a, b, c, d: merge_sparse_sets(a, b, c, d, k, n))
    return _time(fn, (va, ia, vb, ib), min_seconds)


def time_merge_chain(n: int, k: int, min_seconds: float):
    sets = _random_sets(n, k, CHAIN_ROUNDS + 1)

    def chain(first, rest):
        v, i = first
        for rv, ri in rest:
            v, i = merge_sparse_sets(v, i, rv, ri, k, n)
        return v, i

    fn = jax.jit(chain)
    return _time(fn, (sets[0], sets[1:]), min_seconds)


def _merge_argsort_topk(va, ia, vb, ib, k, n):
    """Round-1 merge formulation, retained for comparison only."""
    from jax import lax

    cat_idx = jnp.concatenate([ia, ib])
    cat_val = jnp.concatenate([va, vb])
    order = jnp.argsort(cat_idx)
    si = jnp.take(cat_idx, order)
    sv = jnp.take(cat_val, order)
    dup = jnp.concatenate([jnp.zeros((1,), bool), si[1:] == si[:-1]])
    next_dup = jnp.concatenate([dup[1:], jnp.zeros((1,), bool)])
    summed = sv + jnp.where(next_dup, jnp.roll(sv, -1), 0.0)
    merged_val = jnp.where(dup, 0.0, summed)
    merged_idx = jnp.where(dup, n, si).astype(jnp.int32)
    _, sel = lax.top_k(jnp.abs(merged_val), k)
    return jnp.take(merged_val, sel), jnp.take(merged_idx, sel)


def time_merge_argsort(n: int, k: int, min_seconds: float):
    (va, ia), (vb, ib) = _random_sets(n, k, 2)
    fn = jax.jit(lambda a, b, c, d: _merge_argsort_topk(a, b, c, d, k, n))
    return _time(fn, (va, ia, vb, ib), min_seconds)


def time_dense_scatter(n: int, k: int, min_seconds: float):
    (va, ia), (vb, ib) = _random_sets(n, k, 2)

    def dense_merge(va, ia, vb, ib):
        d = scatter_add_dense(n, ia, va) + scatter_add_dense(n, ib, vb)
        return topk_abs(d, k)

    fn = jax.jit(dense_merge)
    return _time(fn, (va, ia, vb, ib), min_seconds)


VARIANTS = {
    "merge": time_merge,
    "merge_chain5": time_merge_chain,
    "merge_argsort_topk": time_merge_argsort,
    "dense_scatter": time_dense_scatter,
}

PLAN_WORKERS = (8, 16, 32)


def plan_rows(sizes: dict, densities) -> list:
    """Model-side balanced-vs-tree schedule comparison at the same
    operating points (no mesh needed — these are the planner's own
    inputs: comm_bytes_per_step for per-rank wire volume, the scaling
    model for projected ms). One row per (size, density, P) so BENCH
    rounds carry the crossover evidence next to the measured merge cost:
    balanced wire is O(k) flat in P, the tree's O(k log P), so
    bytes_ratio < 1 from P=8 up at these shapes."""
    from benchmarks.scaling_model import predict
    from gtopkssgd_tpu.parallel import balanced_cap, comm_bytes_per_step
    from gtopkssgd_tpu.parallel.planner import planner_inputs

    # Price with exactly what the planner scores with (the committed
    # dcn_probe alpha-beta fit when present, documented fallbacks else).
    inp = planner_inputs()
    model = dict(ici_gbps=inp["ici_gbps"], dcn_gbps=inp["beta_gbps"],
                 dcn_alpha_ms=inp["alpha_ms"], ici_size=1)
    rows = []
    for label, n in sizes.items():
        for rho in densities:
            k = k_for_density(n, rho)
            for p in PLAN_WORKERS:
                tree_b = comm_bytes_per_step("gtopk", n, k, p)
                bal_b = comm_bytes_per_step(
                    "gtopk", n, k, p, schedule="balanced")
                rows.append({
                    "size": label, "n": n, "density": rho, "k": k,
                    "p": p, "cap": balanced_cap(k, p, n),
                    "tree_wire_bytes": tree_b,
                    "balanced_wire_bytes": bal_b,
                    "bytes_ratio": round(bal_b / max(tree_b, 1), 4),
                    "tree_ms_model": round(
                        predict("gtopk", p, n=n, k=k, **model), 4),
                    "balanced_ms_model": round(
                        predict("gtopk_balanced", p, n=n, k=k, **model),
                        4),
                })
    return rows


FORECAST_WORKERS = (256, 1024)
# (tree label, ici_size): flat dp prices every hop on the slow DCN
# link; the pod tree keeps 16-chip ICI domains local and pays DCN only
# across slices (scaling_model's slice split) — the two axis trees
# ROADMAP item 3 asks the evidence rows to span.
FORECAST_TREES = (("flat", 1), ("pod", 16))


def forecast_rows(sizes: dict, densities) -> list:
    """Scale-out forecast evidence rows (ROADMAP item 3): modeled comm
    ms at P in {256, 1024} across two axis trees x two wire schedules,
    priced from the planner's own inputs (obs/forecast.py grid over the
    committed fit artifact), with uncertainty columns from the fit's
    Theil-Sen residual when the artifact records one (probe-era
    artifacts don't — their bands are honestly absent/0). One row per
    (size, density, P, schedule, tree); the per-P recommended plan and
    the tree->balanced crossover ride each (size, density) group."""
    from gtopkssgd_tpu.obs import forecast as _forecast
    from gtopkssgd_tpu.parallel.planner import planner_inputs

    inp = planner_inputs()
    fit = {"alpha_ms": inp["alpha_ms"], "beta_gbps": inp["beta_gbps"],
           "ici_gbps": inp["ici_gbps"], "resid_ms": inp.get("resid_ms"),
           "fit_source": inp.get("fit_source")}
    rows = []
    for label, n in sizes.items():
        for rho in densities:
            k = k_for_density(n, rho)
            params = {"mode": "gtopk", "n": n, "k": k, "codec": "fp32"}
            grid = _forecast.grid_rows(
                params, fit, compute_ms=0.0,
                targets=FORECAST_WORKERS, trees=FORECAST_TREES)
            recs = _forecast.recommend(grid)
            cross = _forecast.crossover_p(
                params, fit, p_max=max(FORECAST_WORKERS),
                trees=FORECAST_TREES)
            for r in grid:
                rows.append({
                    "size": label, "n": n, "density": rho, "k": k,
                    "p": r["p"], "plan": r["plan"],
                    "wire_mode": r["wire_mode"],
                    "ici_size": r["ici_size"], "msgs": r["msgs"],
                    "comm_ms_model": r["comm_ms"],
                    "comm_ms_lo": r["step_ms_lo"],
                    "comm_ms_hi": r["step_ms_hi"],
                    "band_ms": r["band_ms"],
                    "recommended": r["plan"] == recs[r["p"]]["plan"],
                    "crossover_p": cross,
                    "fit_source": fit.get("fit_source"),
                })
    return rows


BUCKET_ALPHAS_MS = (0.1, 5.0, 22.0)   # ICI-class, mid, measured-DCN latency
BUCKET_MODELS = ("resnet50", "vgg16")
BUCKET_DENSITY = 0.001


def _model_leaf_sizes(dnn: str):
    """Param leaf sizes in jax.tree flatten order — the exact axis the
    optimizer's bucket plan partitions — via eval_shape (no weights are
    materialized, so this is milliseconds even for the 25M-param net)."""
    import jax.numpy as jnp

    from gtopkssgd_tpu.models import get_model
    model, spec = get_model(dnn)
    x = jnp.zeros((1,) + spec.example_shape, jnp.float32)
    var = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x, train=False))
    return tuple(int(l.size) for l in jax.tree_util.tree_leaves(var["params"]))


def bucket_rows(p: int = 32) -> list:
    """Bucketing evidence rows (parallel.bucketing): per-leaf vs
    DP-bucketed modeled comm ms across the alpha sweep. One row per
    (model, alpha): the DP's chosen B, its modeled ms, and the two
    degenerate partitions (B=1 single merge, B=L per-leaf) — showing the
    latency-bound regime (alpha=22 ms DCN: B collapses toward 1, per-leaf
    pays L*alpha) and the bandwidth-bound one (alpha=0.1 ms ICI-class:
    larger B wins back bucket-local index bits)."""
    from gtopkssgd_tpu.parallel import bucketing, plan_buckets
    from gtopkssgd_tpu.parallel.planner import planner_inputs

    beta = planner_inputs()["beta_gbps"]
    rows = []
    for dnn in BUCKET_MODELS:
        sizes = _model_leaf_sizes(dnn)
        for alpha in BUCKET_ALPHAS_MS:
            kw = dict(p=p, codec="fp32", alpha_ms=alpha, beta_gbps=beta)

            def _ms(spec):
                plan = plan_buckets(sizes, BUCKET_DENSITY,
                                    buckets=spec, **kw)
                return plan, bucketing.partition_cost_ms(plan, **kw)

            auto, auto_ms = _ms("auto")
            _, leaf_ms = _ms("leaf")
            _, b1_ms = _ms(1)
            rows.append({
                "model": dnn, "n_leaves": len(sizes), "n": sum(sizes),
                "density": BUCKET_DENSITY, "p": p,
                "alpha_ms": alpha, "beta_gbps": beta,
                "auto_n_buckets": auto.n_buckets,
                "auto_ms_model": round(auto_ms, 4),
                "b1_ms_model": round(b1_ms, 4),
                "leaf_ms_model": round(leaf_ms, 4),
                "leaf_over_auto": round(leaf_ms / max(auto_ms, 1e-9), 4),
            })
    return rows


PIPELINE_WORKERS = (8, 32)
PIPELINE_BS = tuple(range(1, 9))


def pipeline_rows() -> list:
    """Overlapped-pipeline evidence rows (parallel.bucketing): modeled
    serial-vs-overlapped wall-clock span per (model, alpha, P, B). Each
    order gets its own DP boundaries (serial pricing sums merge cost,
    overlap prices the per-stage max(T_select, T_merge)), then the TRUE
    span formula — sum(sel+merge) serial; fill + sum of interior maxes +
    drain overlapped — so the row is the honest A/B 'auto' compares. The
    sweep shows where pipelining pays: at alpha=0.1 ms (ICI-class) the
    overlapped span dips below serial B=1 from small B on, while at the
    measured-DCN alpha=22 ms the per-bucket latency term dwarfs anything
    selection can hide and serial B=1 stays cheapest."""
    from gtopkssgd_tpu.parallel import bucketing, plan_buckets
    from gtopkssgd_tpu.parallel.planner import planner_inputs

    beta = planner_inputs()["beta_gbps"]
    rows = []
    for dnn in BUCKET_MODELS:
        sizes = _model_leaf_sizes(dnn)
        for alpha in BUCKET_ALPHAS_MS:
            for p in PIPELINE_WORKERS:
                kw = dict(p=p, codec="fp32", alpha_ms=alpha,
                          beta_gbps=beta)
                for b in PIPELINE_BS:

                    def _span(pipe):
                        plan = plan_buckets(sizes, BUCKET_DENSITY,
                                            buckets=b, pipeline=pipe,
                                            **kw)
                        return bucketing.pipeline_span_ms(plan, **kw)

                    ser, ovl = _span("serial"), _span("overlap")
                    rows.append({
                        "model": dnn, "density": BUCKET_DENSITY,
                        "p": p, "alpha_ms": alpha, "beta_gbps": beta,
                        "n_buckets": b,
                        "serial_span_ms": round(ser, 4),
                        "overlap_span_ms": round(ovl, 4),
                        "overlap_speedup": round(ser / max(ovl, 1e-9),
                                                 4),
                    })
    return rows


def main():
    from gtopkssgd_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--min-seconds", type=float, default=1.0)
    args = ap.parse_args()

    device = jax.devices()[0].device_kind.replace(" ", "_")
    sizes = dict(list(SIZES.items())[:1]) if args.quick else SIZES
    densities = DENSITIES[:1] if args.quick else DENSITIES
    min_s = 0.3 if args.quick else args.min_seconds

    rows = []
    for label, n in sizes.items():
        for rho in densities:
            k = k_for_density(n, rho)
            for name, timer in VARIANTS.items():
                try:
                    sec, steps = timer(n, k, min_s)
                    err = None
                except Exception as e:  # record, don't abort the sweep
                    sec, steps, err = None, 0, f"{type(e).__name__}: {e}"
                rows.append({
                    "size": label, "n": n, "density": rho, "k": k,
                    "variant": name, "ms": (
                        round(sec * 1e3, 4) if sec is not None else None),
                    "steps_timed": steps, "error": err,
                })
                ms = f"{sec * 1e3:9.3f} ms" if sec is not None else "FAILED"
                print(f"{label:16s} rho={rho:<6g} {name:14s} {ms}",
                      flush=True)

    result = {
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
        "chain_rounds": CHAIN_ROUNDS,
        "rows": rows,
        # Comm-planner evidence rows: balanced-vs-tree wire volume and
        # modeled ms per (size, density, P) — the full grid even under
        # --quick, since these are model-side (milliseconds to compute).
        "plan_rows": plan_rows(SIZES, DENSITIES),
        # Bucketing evidence rows: per-leaf vs DP-bucketed modeled comm
        # ms across the alpha sweep — also model-side, full grid always.
        "bucket_rows": bucket_rows(),
        # Pipeline evidence rows: serial-vs-overlapped modeled span per
        # (model, alpha, P, B) — model-side, full grid always.
        "pipeline_rows": pipeline_rows(),
        # Scale-out forecast evidence rows: modeled P in {256, 1024}
        # across axis trees with uncertainty columns (ROADMAP item 3) —
        # model-side, full grid always.
        "forecast_rows": forecast_rows(SIZES, DENSITIES),
    }
    out = args.out or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "results", f"merge_bench_{device}.json",
    )
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
