"""Benchmark: gTop-k S-SGD step throughput vs the dense-allreduce baseline.

Prints ONE JSON line with the driver-required keys plus the supporting
absolute numbers that make the headline ratio auditable:

  metric       — "<dnn>_gtopk_rho<rho>_train_throughput_<P>chip"
  value        — gtopk (rho=0.001) images/sec/chip
  unit         — "images/sec/chip"
  vs_baseline  — value / dense-psum images/sec/chip, same run, same chip
  ...plus      — dense absolute throughput, step ms for both modes,
                 XLA-counted FLOPs/step, achieved TFLOP/s and MFU, device.

Default workload is the north-star one (BASELINE.md): ResNet-50 at
224x224, bf16, synthetic ImageNet shapes. The default --compression=auto
measures BOTH the flat gtopk and gtopk_layerwise (the round-2 serial-tail
fix) and headlines the faster one, with both absolutes in the output. On
ONE chip neither mode communicates, so the sparse mode = dense +
selection overhead and vs_baseline is expected to be <= 1.0; sparsity
pays off only when a network is in the path (the multi-chip sweep lives
in benchmarks/sweep.py).

The p=1 ratio measured through round 3 (~0.90 at bs=128 / 0.98 at
bs=256, benchmarks/results/bench_r3_TPU_v5_lite.json) was structural for
the INDEX-SET formulation: compress-chain reformulations all landed within
noise in the fused step (fused_variants artifact) because the
scatter/gather through the flat [N] vector serialized against the backward
epilogue. Round 3 replaced the p=1 selection with a threshold form
(compress_by_threshold: one top-k reduction for tau + elementwise masks, no
scatter/gather) and made BatchNorm emit the compute dtype (halving
inter-conv HBM bytes for BOTH modes); neither change has been measured on
a chip. Larger per-chip batch amortizes the fixed tail but also drops the
dense baseline's own throughput, so the default stays at the batch both
modes prefer.

The measured step is the full production path (forward + backward + error-
feedback compress + collective + SGD update) in one jitted SPMD program
over every visible chip, timed over a >= 2 s window that ends with a
block_until_ready on the FULL updated state (see
gtopkssgd_tpu/benchmark.py::measure_throughput for the discipline).

A run that finds no accelerator fails: the exit code is non-zero and no
metric is printed.

Usage: python bench.py [--dnn resnet50] [--batch-size 128] [--min-seconds 2]
"""

from __future__ import annotations

import argparse
import json


def latest_bench_artifact_path():
    """Newest committed bench_r*.json in NUMERIC round order (a
    lexicographic sort would rank bench_r10 before bench_r2 and pin a
    stale round forever). Read by benchmarks/time_to_quality.py.
    Returns None if none exist."""
    import glob
    import os
    import re

    here = os.path.dirname(os.path.abspath(__file__))
    paths = sorted(
        glob.glob(os.path.join(here, "benchmarks", "results",
                               "bench_r*.json")),
        key=lambda p: (int(m.group(1)) if
                       (m := re.search(r"bench_r(\d+)", p)) else -1, p))
    return paths[-1] if paths else None


def main():
    import jax
    from gtopkssgd_tpu.utils import enable_compilation_cache

    if jax.default_backend() == "cpu":
        # The metric is a device metric; a CPU run must not print one.
        raise SystemExit("bench.py: no accelerator (jax backend is cpu)")
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dnn", default="resnet50")
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--min-seconds", type=float, default=2.0)
    ap.add_argument("--density", type=float, default=0.001)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--topk-method", default="auto")
    ap.add_argument("--s2d", action="store_true",
                    help="resnet50: space-to-depth stem (4x4x12 conv on "
                         "2x2 pixel blocks instead of 7x7x3 — a superset "
                         "of the 7x7 map, exact embedding pinned in "
                         "tests/test_models.py; MXU-friendly channel "
                         "width)")
    ap.add_argument("--momentum-correction", action="store_true",
                    help="DGC velocity-before-selection on the sparse "
                         "arm (the measured best cold-start config; "
                         "dense baseline arm is unaffected — it is "
                         "classic momentum already)")
    ap.add_argument("--attr-trace", default=None, metavar="DIR",
                    help="after the timed windows, re-run the headline "
                         "sparse mode under the profiler (Python tracer "
                         "off — obs.trace_attr.capture) and fold the "
                         "paper's T_compute/T_select/T_comm fractions "
                         "into the output JSON; the raw trace stays in "
                         "DIR for TensorBoard/Perfetto")
    ap.add_argument("--compression", default="auto",
                    help="sparse mode to benchmark against the dense "
                         "baseline (gtopk | gtopk_layerwise | allgather); "
                         "'auto' measures gtopk AND gtopk_layerwise and "
                         "headlines whichever is faster (round-2 verdict: "
                         "the serial-tail fix must show up in the "
                         "driver's number when it wins)")
    args = ap.parse_args()

    from gtopkssgd_tpu.benchmark import BenchConfig, measure_throughput

    cfg = BenchConfig(
        dnn=args.dnn, batch_size=args.batch_size,
        min_seconds=args.min_seconds, density=args.density,
        dtype=args.dtype, topk_method=args.topk_method, s2d=args.s2d,
        momentum_correction=args.momentum_correction,
    )
    if args.compression == "auto" and args.momentum_correction:
        # layerwise x correction is a measured-worse combination
        # (warmup_ab ablation; gtopk_sgd warns on it) — a corr bench
        # compares flat gtopk+corr vs dense only.
        args.compression = "gtopk"
    if args.compression == "auto":
        candidates = {
            m: measure_throughput(cfg, m, args.density)
            for m in ("gtopk", "gtopk_layerwise")
        }
        mode = max(candidates,
                   key=lambda m: candidates[m]["images_per_sec_per_chip"])
        gtopk = candidates[mode]
        alt = {f"{m}_images_per_sec_per_chip":
               round(r["images_per_sec_per_chip"], 2)
               for m, r in candidates.items()}
    else:
        mode = args.compression
        gtopk = measure_throughput(cfg, mode, args.density)
        alt = {}
    dense = measure_throughput(cfg, "dense", 1.0)
    attr = {}
    if args.attr_trace:
        # Everything is jit-cached by the measurements above, so the
        # traced window is pure execution — exactly what attribution
        # wants on the trace.
        from gtopkssgd_tpu.obs.trace_attr import attribute, capture

        with capture(args.attr_trace):
            measure_throughput(cfg, mode, args.density)
        rec = attribute(args.attr_trace, mode=mode)
        attr = {f"attr_{k}": rec[k] for k in
                ("source", "frac_compute", "frac_select", "frac_comm")}
    p = jax.device_count()

    def _r(v, nd=4):
        return round(v, nd) if isinstance(v, float) else v

    mode_label = mode + ("+corr" if args.momentum_correction else "")
    print(json.dumps({
        "metric": f"{args.dnn}_{mode_label}_rho{args.density}"
                  f"_train_throughput_{p}chip",
        "value": round(gtopk["images_per_sec_per_chip"], 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(
            gtopk["images_per_sec_per_chip"]
            / dense["images_per_sec_per_chip"], 4
        ),
        **alt,
        **attr,
        "dense_images_per_sec_per_chip": round(
            dense["images_per_sec_per_chip"], 2),
        "gtopk_step_ms": round(gtopk["sec_per_step"] * 1e3, 3),
        "dense_step_ms": round(dense["sec_per_step"] * 1e3, 3),
        "gtopk_steps_timed": gtopk["steps_timed"],
        "dense_steps_timed": dense["steps_timed"],
        "flops_per_step": gtopk["flops_per_step"],
        "gtopk_achieved_tflops_per_chip": _r(
            gtopk["achieved_tflops_per_chip"], 2),
        "dense_achieved_tflops_per_chip": _r(
            dense["achieved_tflops_per_chip"], 2),
        "gtopk_mfu": _r(gtopk["mfu"]),
        "dense_mfu": _r(dense["mfu"]),
        "num_params": gtopk["num_params"],
        "batch_size_per_chip": args.batch_size,
        "device_kind": jax.devices()[0].device_kind,
        "nchips": p,
    }))


if __name__ == "__main__":
    main()
