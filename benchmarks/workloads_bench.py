"""All six reference workloads end-to-end on the real chip.

The unit/oracle tests prove every model family trains on the virtual CPU
mesh; this benchmark proves the same through the PRODUCTION Trainer on
actual TPU silicon — model build, synthetic data pipeline, prefetch,
jitted train step with compression, eval — and records throughput per
workload (samples/sec through trainer.train, host pipeline included;
bench.py remains the device-step-only headline).

Writes benchmarks/results/workloads_<device>.json.

Run:  python -m benchmarks.workloads_bench [--steps 20] [--quick]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import time

import jax

# (dnn, per-chip batch, extra config) — batch sizes pick the paper's
# per-worker values where they fit one chip comfortably.
WORKLOADS = [
    ("vgg16", 128, {}),
    ("resnet20", 128, {}),
    ("alexnet", 64, {"dtype": "bfloat16"}),
    ("resnet50", 64, {"dtype": "bfloat16"}),
    ("lstm", 20, {}),
    ("lstman4", 8, {}),
]


def bench_workload(dnn: str, batch: int, extra: dict, steps: int):
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    t0 = time.perf_counter()
    with Trainer(TrainConfig(
        dnn=dnn, batch_size=batch, nworkers=1, compression="gtopk",
        density=0.001, max_epochs=1, log_interval=10 ** 9,
        eval_batches=1, **extra,
    )) as t:
        build_s = time.perf_counter() - t0
        warm = t.train(3)           # compile + warm
        run = t.train(steps)        # timed window (train() fences state)
        ev = t.test()
    return {
        "dnn": dnn,
        "batch_size": batch,
        "steps": steps,
        "samples_per_sec": round(run["throughput"], 2),
        "step_ms": round(run["wall"] / steps * 1e3, 2),
        "loss_finite": math.isfinite(run["loss"]),
        "eval_keys": sorted(ev.keys()),
        "build_seconds": round(build_s, 1),
        "compile_seconds": round(warm["wall"], 1),
        **{k: extra[k] for k in extra},
    }


def measure_h2d_mbps() -> float:
    """Measured host->device bandwidth — context for the samples/sec
    numbers of input-bound rows. The pipelines ship uint8 to keep this
    transfer a quarter of the float32 size."""
    import jax
    import numpy as np
    import jax.numpy as jnp

    x = np.zeros((32, 224, 224, 3), np.float32)
    jax.block_until_ready(jnp.asarray(x))  # warm
    t0 = time.perf_counter()
    jax.block_until_ready(jnp.asarray(x))
    return x.nbytes / 1e6 / (time.perf_counter() - t0)


def main():
    from gtopkssgd_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    device = jax.devices()[0].device_kind.replace(" ", "_")
    workloads = WORKLOADS[:2] if args.quick else WORKLOADS
    steps = 5 if args.quick else args.steps

    rows = []
    for dnn, batch, extra in workloads:
        try:
            row = bench_workload(dnn, batch, extra, steps)
        except Exception as e:  # record, keep sweeping
            row = {"dnn": dnn, "batch_size": batch,
                   "error": f"{type(e).__name__}: {e}"}
        rows.append(row)
        print(json.dumps(row), flush=True)

    out = args.out or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "results", f"workloads_{device}.json",
    )
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"device_kind": jax.devices()[0].device_kind,
                   "backend": jax.default_backend(),
                   "mode": "gtopk rho=0.001, nworkers=1, synthetic data",
                   "h2d_mbytes_per_sec": round(measure_h2d_mbps(), 1),
                   "note": "samples/sec includes the host pipeline and "
                           "H2D transfer; see measure_h2d_mbps docstring",
                   "rows": rows}, f, indent=1)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
