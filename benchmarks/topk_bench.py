"""Top-k strategy sweep on real hardware — the evidence behind `auto`.

The reference leans on `torch.topk`'s CUDA kernel (SURVEY.md §2 native
table: the #1 custom-kernel obligation). The TPU rebuild has six
strategies (ops/topk.py, ops/pallas_topk.py); this benchmark measures all
of them at the reference's real problem sizes:

    N = 2.7e5   (ResNet-20 CIFAR scale)
    N = 2.5e7   (ResNet-50 ImageNet scale)
    N = 6.1e7   (AlexNet/VGG-16 scale)

with k = ceil(rho * N) at rho in {0.001, 0.01}, and writes a JSON artifact
(benchmarks/results/topk_bench_<device>.json) so the choice of the
production method is reproducible, not folklore. Each selection row also
carries `recall_vs_exact` (exact-vs-method index recall on the same random
vector) so approximate methods (approx, twostage, simrecall) are compared
on both axes at once. `tau_*` rows time the tau-only API (ops.select_tau,
what compress_by_threshold consumes at p=1) — no (vals, idx) set, no
gather — with recall measured on the threshold MASK |x| >= tau (>= the
index-set recall by the superset property).

Timing uses the same discipline as the main benchmark: back-to-back
dispatch, one block_until_ready on the last output, window >> dispatch
noise (utils/timers.py::timed_window).

The artifact also carries wire-codec microbench rows (`codec_rows`:
bytes/elem, roundtrip error, recall-after-quantization vs exact for
fp32/int8/fp8 — parallel/codec.py). Off the chip the Pallas kernels run in
interpret mode and the artifact says so (`pallas_interpret`, `backend`):
its ms columns are then NOT device numbers — recall columns and codec byte
ratios are the meaningful fields there.

Run:  python -m benchmarks.topk_bench [--out PATH] [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SIZES = {
    "resnet20-270k": 272_474,
    "resnet50-25.6M": 25_557_032,
    "vgg16-61M": 61_090_496,
}
DENSITIES = (0.001, 0.01)
METHODS = ("exact", "blockwise", "threshold", "approx", "pallas",
           "twostage")
# The tau-only consumers (compress_by_threshold at p=1) care about these.
TAU_METHODS = ("exact", "threshold", "twostage")


def _selector(method: str, k: int, interpret: bool):
    import jax

    from gtopkssgd_tpu.ops.pallas_topk import pallas_topk_abs
    from gtopkssgd_tpu.ops.topk import (
        select_tau, select_topk, twostage_topk_abs,
    )

    if method == "pallas":
        return jax.jit(lambda v: pallas_topk_abs(v, k, interpret=interpret))
    if method == "twostage" and interpret:
        # Exercise the fused kernel (not the XLA reference) even off-TPU.
        return jax.jit(lambda v: twostage_topk_abs(
            v, k, use_pallas=True, interpret=True))
    if method.startswith("tau_"):
        return jax.jit(lambda v: select_tau(v, k, method[4:]))
    return jax.jit(lambda v: select_topk(v, k, method=method))


def time_method(method: str, n: int, k: int, min_seconds: float = 1.0,
                interpret: bool = False):
    import jax
    import jax.numpy as jnp

    from gtopkssgd_tpu.utils import time_calls

    x = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32)
    return time_calls(_selector(method, k, interpret), (x,), min_seconds, 4)


def recall_vs_exact(method: str, n: int, k: int, interpret: bool) -> float:
    """Index recall (tau rows: mask recall) of `method` against exact
    top-k on the same vector the timing loop used."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from gtopkssgd_tpu.ops.topk import topk_abs

    x = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32)
    _, exact_idx = topk_abs(x, k)
    exact_idx = np.asarray(exact_idx)
    out = _selector(method, k, interpret)(x)
    if method.startswith("tau_"):
        tau = float(out)
        hit = np.abs(np.asarray(x)[exact_idx]) >= tau
        return float(hit.mean())
    _, idx = out
    return float(
        len(set(np.asarray(idx).tolist()) & set(exact_idx.tolist())) / k)


def one_pass_evidence(n: int) -> dict:
    """Evidence (asserted in tests/test_pallas_topk.py) that the counting
    pass reads x ONCE.

    Compares the largest operand/result element count in the compiled
    HLO of the production count_fn (ops.topk.bucketize_counts — the XLA
    twin of the fused Pallas counting kernel) against the vmapped
    8-reduction it replaced: the old formulation materializes/loops an
    8xN compare, the single-pass one never exceeds 1xN. Returns the op
    sizes plus the boolean the gate asserts."""
    import jax
    import jax.numpy as jnp

    from gtopkssgd_tpu.ops.topk import bucketize_counts

    x = jnp.ones((n,), jnp.float32)
    thr = jnp.linspace(0.1, 0.9, 8)

    def vmap8(mag, t):
        return jax.vmap(lambda tt: jnp.sum((mag >= tt).astype(jnp.int32)))(t)

    def max_elems(fn):
        txt = jax.jit(fn).lower(x, thr).compile().as_text()
        best = 0
        for m in re.finditer(r"\b(?:f32|s32|s64|pred|u32|u8|s8)\[([\d,]+)\]",
                             txt):
            elems = 1
            for d in m.group(1).split(","):
                if d:
                    elems *= int(d)
            best = max(best, elems)
        return best

    single = max_elems(bucketize_counts)
    vmapped = max_elems(vmap8)
    return {
        "n": n,
        "bucketize_max_op_elems": single,
        "vmap8_max_op_elems": vmapped,
        "bucketize_passes_over_x": round(single / n, 2),
        "vmap8_passes_over_x": round(vmapped / n, 2),
        "single_pass": bool(single <= 2 * n < vmapped),
    }


def codec_rows(n: int, min_seconds: float = 0.3) -> list:
    """Wire-codec encode/decode microbench: bytes/elem on the wire,
    encode->decode roundtrip value error, and selection recall AFTER
    quantization (top-2k candidates requantized, top-k reselected from
    the dequantized magnitudes, recalled against the exact top-k — the
    merge-then-reselect operation every tree round performs on decoded
    values). fp32 rows pin the identity: 8 bytes/elem, zero error,
    recall 1."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from gtopkssgd_tpu.ops.topk import k_for_density, topk_abs
    from gtopkssgd_tpu.parallel import get_codec, roundtrip_aligned
    from gtopkssgd_tpu.utils import time_calls

    x = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32)
    rows = []
    for rho in DENSITIES:
        k = k_for_density(n, rho)
        ev, ei = topk_abs(x, k)
        exact_idx = set(np.asarray(ei).tolist())
        cv, ci = topk_abs(x, 2 * k)
        for name in ("fp32", "int8", "fp8"):
            c = get_codec(name)
            fn = jax.jit(lambda v, i: c.decode(
                c.encode(v, i, n=n), k=k, n=n))
            sec, steps = time_calls(fn, (ev, ei), min_seconds, 4)
            vq = np.asarray(roundtrip_aligned(c, ev, ei, n=n))
            evn = np.asarray(ev)
            rel_err = float(np.linalg.norm(vq - evn)
                            / max(np.linalg.norm(evn), 1e-12))
            # recall after quantization: reselect k of 2k candidates
            # from dequantized magnitudes
            cq = np.asarray(roundtrip_aligned(c, cv, ci, n=n))
            keep = np.argsort(-np.abs(cq), kind="stable")[:k]
            requant_idx = set(np.asarray(ci)[keep].tolist())
            recall = len(requant_idx & exact_idx) / k
            rows.append({
                "n": n, "density": rho, "k": k, "codec": c.name,
                "bytes_per_elem": round(c.wire_set_bytes(k, n) / k, 3),
                "wire_ratio_vs_fp32": round(
                    c.wire_set_bytes(k, n) / (8 * k), 4),
                "roundtrip_rel_err": round(rel_err, 6),
                "recall_after_quantization": round(recall, 4),
                "roundtrip_ms": round(sec * 1e3, 4),
                "steps_timed": steps,
            })
            print(f"codec {c.name:8s} rho={rho:<6g} "
                  f"{rows[-1]['bytes_per_elem']:6.2f} B/elem "
                  f"err={rel_err:.5f} recall={recall:.4f}", flush=True)
    return rows


def run_sweep(quick: bool, min_seconds: float, interpret: bool,
              with_recall: bool = True):
    from gtopkssgd_tpu.ops.topk import k_for_density

    sizes = dict(list(SIZES.items())[:1]) if quick else SIZES
    densities = DENSITIES[:1] if quick else DENSITIES
    rows = []
    for label, n in sizes.items():
        for rho in densities:
            k = k_for_density(n, rho)
            for method in METHODS + tuple(
                    f"tau_{m}" for m in TAU_METHODS):
                try:
                    sec, steps = time_method(
                        method, n, k, min_seconds, interpret)
                    rec = (recall_vs_exact(method, n, k, interpret)
                           if with_recall else None)
                    err = None
                except Exception as e:  # record, don't abort the sweep
                    sec, steps, rec = None, 0, None
                    err = f"{type(e).__name__}: {e}"
                rows.append({
                    "size": label, "n": n, "density": rho, "k": k,
                    "method": method, "ms": (
                        round(sec * 1e3, 4) if sec is not None else None),
                    "recall_vs_exact": (
                        round(rec, 4) if rec is not None else None),
                    "steps_timed": steps, "error": err,
                })
                ms = f"{sec * 1e3:9.3f} ms" if sec is not None else "FAILED"
                rc = f" recall={rec:.4f}" if rec is not None else ""
                print(f"{label:16s} rho={rho:<6g} {method:13s} {ms}{rc}",
                      flush=True)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="one size, one density, short windows")
    ap.add_argument("--min-seconds", type=float, default=1.0)
    args = ap.parse_args(argv)

    import jax

    from gtopkssgd_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    device = jax.devices()[0].device_kind.replace(" ", "_")
    interpret = jax.default_backend() != "tpu"
    min_s = 0.3 if args.quick else args.min_seconds

    result = {
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
        "pallas_interpret": interpret,
        "rows": run_sweep(args.quick, min_s, interpret),
        "codec_rows": codec_rows(list(SIZES.values())[0]),
    }

    out = args.out or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "results",
        f"topk_bench_{device}.json",
    )
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {out}")
    return out


if __name__ == "__main__":
    main()
