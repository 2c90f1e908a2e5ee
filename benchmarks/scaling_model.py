"""Multi-chip throughput projection from single-chip measurements.

Only ONE real TPU chip is reachable from this environment, so multi-chip
performance cannot be measured directly. This tool does the next honest
thing: it combines

  * a MEASURED single-chip step decomposition (--compute-ms and
    --overhead-ms: a cell's fwd_bwd_ms + apply_ms and its compress_ms,
    PERF_LEDGER.jsonl),
  * a link-aware bandwidth model of the per-device communication volume,
    matching the complexity classes the collectives implement (dense
    ring O(N), DGC allgather O(kP), gtopk O(k log P), hier O(N on ICI +
    k log(P/S) on DCN)) — an independent model, deliberately NOT
    `comm_bytes_per_step` (that reports the paper's volume convention;
    this one needs per-link assignment and ring-transfer factors), and
  * published per-chip interconnect bandwidths,

into a projected images/sec/chip vs P curve for each reduction mode —
the same complexity-table analysis the paper used to argue for gTop-k on
1 GbE (arXiv:1901.04359 §3), re-parameterized for TPU links. The model is
deliberately simple (bandwidth-cost, no latency/overlap terms) and
labeled as a projection everywhere; its purpose is design guidance
(where does sparsity pay?) and judging transparency, not a benchmark.

Key structural fact it surfaces: on ICI (hundreds of GB/s) a dense psum
of ResNet-50's 102 MB gradient costs ~1 ms — comparable to gtopk's
selection overhead — so sparsification buys little inside a slice. On
DCN (tens of Gbit/s shared per host) the same dense reduction costs tens
of ms and gTop-k's O(k log P) wins by an order of magnitude; the
hierarchical mode keeps the dense hop on ICI and sends only the sparse
set over DCN.

Usage:
  python -m benchmarks.scaling_model                    # defaults
  python -m benchmarks.scaling_model --compute-ms 60.1 \
      --n 25557032 --density 0.001 --batch 128 \
      --ici-gbps 400 --dcn-gbps 25 --overhead-ms 5.4
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

# The comm model itself is the package's (the planner scores wire plans
# with it and the comm ledger audits against it); this script adds the
# compute/overhead/throughput bookkeeping around it.
from gtopkssgd_tpu.parallel.comm_model import predict  # noqa: E402


def project(mode: str, p: int, *, n: int, k: int, compute_ms: float,
            overhead_ms: float, ici_gbps: float, dcn_gbps: float,
            ici_size: int, batch: int, dcn_alpha_ms: float = 0.0,
            codec: str = "fp32") -> dict:
    """Projected step time at P devices for one reduction mode.

    step = compute + (selection overhead, sparse modes only) + the comm
    model's comm_ms (``gtopkssgd_tpu.parallel.comm_model.predict`` — the
    link split and phase shapes are documented there). ``dcn_alpha_ms``
    is the fitted per-message latency of the slow link (dcn_probe.py's
    alpha_beta_fit); at alpha=0 and P inside one slice this reduces to a
    bandwidth-only model.
    """
    comm_ms = predict(mode, p, n=n, k=k, ici_gbps=ici_gbps,
                      dcn_gbps=dcn_gbps, ici_size=ici_size,
                      dcn_alpha_ms=dcn_alpha_ms, codec=codec)
    extra = 0.0 if mode == "dense" else overhead_ms
    step_ms = compute_ms + extra + comm_ms
    return {
        "mode": mode,
        "p": p,
        "codec": codec,
        "comm_ms": round(comm_ms, 3),
        "step_ms": round(step_ms, 3),
        "images_per_sec_per_chip": round(batch / step_ms * 1e3, 1),
    }


def main():
    ap = argparse.ArgumentParser()
    # Defaults = ResNet-50 on one TPU v5e at b128, as measured before
    # the ledger: 60.1 ms fwd+bwd+apply, 5.4 ms gtopk overhead
    # (compress + residual + scatter). Pass a cell's own stage times
    # from PERF_LEDGER.jsonl for today's numbers.
    ap.add_argument("--compute-ms", type=float, default=60.1)
    ap.add_argument("--overhead-ms", type=float, default=5.4)
    ap.add_argument("--n", type=int, default=25_557_032)
    ap.add_argument("--density", type=float, default=0.001)
    ap.add_argument("--batch", type=int, default=128)
    # v5e: 4 ICI links/chip at ~100 GB/s-class aggregate; DCN per host
    # measured in tens of Gbit/s. Both overridable — the CONCLUSION
    # (dense wins on ICI, sparse wins on DCN) is insensitive to 2x
    # errors in either.
    ap.add_argument("--ici-gbps", type=float, default=1600.0,
                    help="aggregate ICI Gbit/s per chip")
    ap.add_argument("--dcn-gbps", type=float, default=25.0,
                    help="effective DCN Gbit/s per host")
    ap.add_argument("--ici-size", type=int, default=16,
                    help="chips per ICI domain (slice)")
    ap.add_argument("--dcn-alpha-ms", type=float, default=0.0,
                    help="fitted per-message DCN latency (dcn_probe.py "
                         "alpha_beta_fit.alpha_ms); 0 = bandwidth-only")
    ap.add_argument("--wire-codec", default="fp32",
                    help="sparse payload codec (parallel.codec grammar: "
                         "fp32 | int8[:BLOCK] | fp8[:BLOCK])")
    ap.add_argument("--ps", type=int, nargs="+",
                    default=[1, 4, 16, 32, 64, 256])
    args = ap.parse_args()

    k = max(1, math.ceil(args.density * args.n))
    kw = dict(n=args.n, k=k, compute_ms=args.compute_ms,
              overhead_ms=args.overhead_ms, ici_gbps=args.ici_gbps,
              dcn_gbps=args.dcn_gbps, ici_size=args.ici_size,
              batch=args.batch, dcn_alpha_ms=args.dcn_alpha_ms,
              codec=args.wire_codec)
    print(json.dumps({"model": ("latency+bandwidth projection (see "
                                "docstring; alpha=0 => bandwidth-only)"),
                      "k": k, **{a: getattr(args, a)
                                 for a in ("compute_ms", "overhead_ms",
                                           "n", "density", "batch",
                                           "ici_gbps", "dcn_gbps",
                                           "ici_size", "dcn_alpha_ms")}}))
    for p in args.ps:
        for mode in ("dense", "gtopk", "gtopk_balanced", "allgather",
                     "gtopk_hier"):
            print(json.dumps(project(mode, p, **kw)))


if __name__ == "__main__":
    main()
