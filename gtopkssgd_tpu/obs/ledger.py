"""Comm-model ledger: measured T_comm / wire bytes vs the alpha-beta model.

The paper's scaling argument (arXiv:1901.04359 §3, re-parameterized in
``parallel/comm_model.py``) predicts per-step communication time
from mode, worker count, gradient size and link constants. PRs 1–3 made
the MEASURED side observable — per-rank ``attr`` records carry the
profiler-derived T_comm split, ``obs`` counter records carry the achieved
wire_bytes — but nothing ever reconciled the two. This module does the
join: for every rank (and step, where attribution is per-step) it emits a
predicted-vs-measured ratio row, so the report can say "comm is 1.8x the
alpha-beta model on ranks 3–4" instead of leaving both numbers in
separate files.

Reading a ratio:
  ~1       the model explains the wire — imbalance hunting should look
           at compute/input, not the collective
  >>1      measured comm far above model: congestion, a straggling host
           serializing the tree rounds, or link constants that flatter
           the hardware (re-run benchmarks/dcn_probe.py and feed its
           alpha_beta_fit back in)
  <1       model too pessimistic (overlap the model ignores, or compute
           classified as comm leaked out of attribution)

Model constants come from, in priority order: explicit arguments, a
fit artifact's ``alpha_beta_fit`` (``load_alpha_beta``), and the comm
model's documented defaults. The model itself, the fit-file grammar and
the defaults live in ``parallel/comm_model.py``, beside the collectives
they price; this module keeps their names for its callers.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Mapping, Optional, Sequence

from gtopkssgd_tpu.parallel import balanced_cap, get_codec, tree_rounds
from gtopkssgd_tpu.parallel.comm_model import (  # noqa: F401 (re-exported)
    DEFAULT_DCN_GBPS,
    DEFAULT_ICI_GBPS,
    load_alpha_beta,
    predict,
    wire_mode_for,
)


def predict_comm_ms(mode: str, p: int, *, n: int, k: int,
                    alpha_ms: float = 0.0,
                    beta_gbps: float = DEFAULT_DCN_GBPS,
                    ici_gbps: float = DEFAULT_ICI_GBPS,
                    ici_size: int = 1,
                    codec: str = "fp32",
                    buckets: Optional[Sequence[Sequence[int]]] = None
                    ) -> float:
    """Predicted comm_ms: ``comm_model.predict`` under the ledger's
    names for the slow link's constants (alpha_ms / beta_gbps), with the
    documented defaults for any the caller leaves out."""
    return predict(mode, p, n=n, k=k, ici_gbps=ici_gbps,
                   dcn_gbps=beta_gbps, ici_size=ici_size,
                   dcn_alpha_ms=alpha_ms, codec=codec, buckets=buckets)


def _manifest_params(manifest: Optional[Mapping[str, Any]]
                     ) -> Optional[Dict[str, Any]]:
    """(mode, p, n, k) from a run-manifest record; None when the header
    lacks what the model needs."""
    if not manifest:
        return None
    mode = manifest.get("compression")
    p = manifest.get("nworkers")
    n = manifest.get("num_params")
    if not mode or not isinstance(p, int) or not isinstance(n, int):
        return None
    rho = manifest.get("density")
    k = (max(1, math.ceil(rho * n))
         if isinstance(rho, (int, float)) and rho > 0 else n)
    if mode == "dense":
        k = n
    codec = manifest.get("wire_codec")
    # The planner stamps the resolved wire schedule into the manifest
    # (comm_plan_schedule; comm_plan is the plan NAME, kept for humans).
    # Pre-planner runs have neither -> None -> historical model.
    schedule = manifest.get("comm_plan_schedule")
    # Bucketed layerwise runs additionally stamp the chosen partition
    # (BucketPlan.to_manifest): per-bucket element counts and wire ks.
    # Unbucketed runs (and every pre-bucketing run) have neither ->
    # buckets=None -> the single-merge model.
    sizes, ks = manifest.get("bucket_sizes"), manifest.get("bucket_ks")
    buckets = None
    if (isinstance(sizes, (list, tuple)) and isinstance(ks, (list, tuple))
            and sizes and len(sizes) == len(ks)):
        buckets = tuple(
            (int(n_b), int(k_b)) for n_b, k_b in zip(sizes, ks))
    return {"mode": str(mode), "p": p, "n": n, "k": k,
            "codec": str(codec) if codec else "fp32",
            "schedule": str(schedule) if schedule else None,
            "bucketing": str(manifest.get("buckets") or "concat"),
            "buckets": buckets}


def ledger_rows(records: Sequence[Mapping[str, Any]],
                manifest: Optional[Mapping[str, Any]] = None,
                alpha_ms: Optional[float] = None,
                beta_gbps: Optional[float] = None,
                ici_gbps: float = DEFAULT_ICI_GBPS,
                ici_size: Optional[int] = None,
                probe_dir: Optional[str] = None) -> List[dict]:
    """The join: one ratio row per measured T_comm observation.

    ``records`` is a merged (or single-shard) record stream; the
    manifest (explicit, or found in-stream) supplies the model inputs;
    ``attr`` records supply measured per-rank T_comm (t_comm_us) and the
    ``obs`` counter records supply measured wire_bytes per step. Fitted
    alpha/beta default to the newest dcn_probe artifact when present.
    Returns [] rather than guessing when the manifest can't parameterize
    the model.
    """
    if manifest is None:
        for rec in records:
            if rec.get("kind") == "manifest":
                manifest = rec
                break
    params = _manifest_params(manifest)
    if params is None:
        return []

    fit_source = "defaults"
    if alpha_ms is None or beta_gbps is None:
        fit = load_alpha_beta(search_dir=probe_dir)
        if fit is not None:
            alpha_ms = fit["alpha_ms"] if alpha_ms is None else alpha_ms
            beta_gbps = (fit["beta_gbps"] if beta_gbps is None
                         else beta_gbps)
            fit_source = fit["source"]
    alpha_ms = 0.0 if alpha_ms is None else float(alpha_ms)
    beta_gbps = (DEFAULT_DCN_GBPS if beta_gbps is None
                 else float(beta_gbps))

    if ici_size is None:
        # Cross-process hops are the slow link; devices per process is
        # the natural ICI-domain size. process_count is in the manifest
        # since PR 2; absent (or single-process) means every hop is
        # "DCN" for the fallback topology, which is the conservative
        # read for a ledger about the slow link.
        pc = manifest.get("process_count") if manifest else None
        if isinstance(pc, int) and pc > 1 and params["p"] % pc == 0:
            ici_size = params["p"] // pc
        else:
            ici_size = 1

    wm = wire_mode_for(params["mode"], params.get("schedule"),
                       bucketing=params.get("bucketing"))
    buckets = params.get("buckets")
    predicted_ms = predict_comm_ms(
        wm, params["p"], n=params["n"], k=params["k"],
        alpha_ms=alpha_ms, beta_gbps=beta_gbps, ici_gbps=ici_gbps,
        ici_size=ici_size, codec=params["codec"], buckets=buckets)

    base = {
        "mode": params["mode"], "p": params["p"],
        "n": params["n"], "k": params["k"], "codec": params["codec"],
        "schedule": params.get("schedule"),
        "bucketing": params.get("bucketing", "concat"),
        "n_buckets": len(buckets) if buckets else None,
        "alpha_ms": round(alpha_ms, 6), "beta_gbps": round(beta_gbps, 6),
        "ici_size": ici_size, "fit_source": fit_source,
        "predicted_comm_ms": round(predicted_ms, 6),
    }
    rows: List[dict] = []
    for rec in records:
        kind = rec.get("kind")
        rank = rec.get("rank", 0)
        if kind == "attr":
            t_comm_us = rec.get("t_comm_us")
            if not isinstance(t_comm_us, (int, float)):
                continue
            measured_ms = float(t_comm_us) / 1e3
            n_steps = rec.get("n_steps")
            if isinstance(n_steps, (int, float)) and n_steps > 0:
                measured_ms /= float(n_steps)
            rows.append({
                **base, "source": "attr", "rank": rank,
                "step": rec.get("step"),
                "measured_comm_ms": round(measured_ms, 6),
                "ratio": (round(measured_ms / predicted_ms, 6)
                          if predicted_ms > 0 else None),
            })
        elif kind == "obs":
            wire = rec.get("wire_bytes")
            if not isinstance(wire, (int, float)) or wire <= 0:
                continue
            # Bytes-side sanity row: achieved wire bytes vs the model's
            # per-device volume (codec set bytes per sparse round — 8k
            # under the fp32 identity; dense ring 2(p-1)/p x 4n). No
            # timing — the ratio checks volume accounting, the attr rows
            # check time. Bucketed runs sum the per-merge volume over
            # the stamped (n_b, k_b) pairs — the same B merges the
            # optimizer issues and the telemetry counter models.
            p = params["p"]

            def _sparse_pred_bytes(k, nn):
                codec = get_codec(params["codec"])
                set_bytes = codec.wire_set_bytes(k, nn)
                if wm == "gtopk_balanced":
                    # comm_bytes_per_step's balanced formula verbatim:
                    # p-1 scatter rounds + a p-slice allgather, one
                    # encoded cap-of-n set each.
                    return max(1, 2 * p - 1) * codec.wire_set_bytes(
                        balanced_cap(k, p, nn), nn)
                if wm in ("gtopk", "gtopk_hier"):
                    return tree_rounds(
                        p if wm == "gtopk"
                        else max(1, p // ici_size)) * set_bytes
                if wm == "allgather":
                    return set_bytes * (p - 1)
                return 0.0

            if wm == "dense":
                nn = params["n"]
                pred_bytes = 2.0 * (p - 1) / p * 4 * nn if p > 1 else 0.0
            elif buckets:
                pred_bytes = sum(
                    _sparse_pred_bytes(k_b, n_b) for n_b, k_b in buckets)
            else:
                pred_bytes = _sparse_pred_bytes(params["k"], params["n"])
            rows.append({
                **base, "source": "wire_bytes", "rank": rank,
                "step": rec.get("step"),
                "measured_wire_bytes": float(wire),
                "predicted_wire_bytes": round(pred_bytes, 1),
                "ratio": (round(float(wire) / pred_bytes, 6)
                          if pred_bytes > 0 else None),
            })
    return rows


def summarize_ledger(rows: Sequence[Mapping[str, Any]]
                     ) -> Dict[str, Dict[str, Any]]:
    """{source: {count, mean_ratio, min_ratio, max_ratio, worst_ranks}}
    — the report's one-glance view; worst_ranks are the ranks whose mean
    ratio sits highest (the "ranks 3–4" in the module docstring)."""
    by_source: Dict[str, List[Mapping[str, Any]]] = {}
    for row in rows:
        if isinstance(row.get("ratio"), (int, float)):
            by_source.setdefault(str(row.get("source")), []).append(row)
    out: Dict[str, Dict[str, Any]] = {}
    for source, rws in by_source.items():
        ratios = [float(r["ratio"]) for r in rws]
        by_rank: Dict[Any, List[float]] = {}
        for r in rws:
            by_rank.setdefault(r.get("rank", 0), []).append(
                float(r["ratio"]))
        rank_means = {rk: sum(v) / len(v) for rk, v in by_rank.items()}
        worst = sorted(rank_means, key=rank_means.get, reverse=True)[:2]
        out[source] = {
            "count": len(ratios),
            "mean_ratio": round(sum(ratios) / len(ratios), 4),
            "min_ratio": round(min(ratios), 4),
            "max_ratio": round(max(ratios), 4),
            "worst_ranks": {str(rk): round(rank_means[rk], 4)
                            for rk in worst},
        }
    return out
