"""Dense-MFU ablation ladder (round-4 verdict weak #2 / next-round #3).

The round-3 bench artifact put DENSE ResNet-50 at MFU 0.23-0.26 on the
v5e — the sparse-vs-dense ratio compares two slow configurations. No
device trace of that step has attributed time per op yet, so this
decomposes by ablation: time a LADDER of configurations, each isolating
one suspect, and read the gap structure off the deltas.

Rungs (all ResNet-50, synthetic ImageNet shapes, bf16 compute unless the
rung says otherwise):

  fwd          — forward pass only (train=True BN statistics included):
                 the MXU-resident floor of the workload.
  fwd_bwd      — + backward: adds the transposed convs; the fwd->fwd_bwd
                 MFU drop isolates backward-pass inefficiency.
  full         — + SGD momentum update: the full dense production step
                 (bench.py's dense arm); fwd_bwd->full isolates the
                 optimizer/epilogue cost.
  bf16_params  — full step with the PARAMS also cast to bfloat16
                 ("bf16-everywhere"): halves weight HBM reads; isolates
                 the cost of f32 master weights on the step.
  bf16_input   — full step with the input batch staged as bf16 (halves
                 activation bytes into the stem conv).
  s2d          — full step with the space-to-depth stem (4x4x12 conv on
                 2x2 pixel blocks): isolates the 7x7/2 stem's padding
                 waste on the MXU.
  batch ladder — full step at bs 128/256/512: fixed-cost amortization +
                 better MXU tiling at larger batch.

Each rung prints one JSON line; the assembled artifact goes to
benchmarks/results/mfu_ablation_<device>.json. XLA-flag variants run as
child processes (flags bind at backend init), driven by --xla-variant.

Usage:
  python benchmarks/mfu_ablation.py                 # full ladder + artifact
  python benchmarks/mfu_ablation.py --rungs fwd,full --batch-sizes 128
  python benchmarks/mfu_ablation.py --rung full --batch-size 256
                                                    # child mode (one line)
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gtopkssgd_tpu.exit_codes import EXIT_ERROR  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results")

# XLA flag variants worth one measurement each (child processes; a flag
# that regresses or no-ops is a result too). Kept short deliberately:
# each costs a fresh backend init + compile.
XLA_VARIANTS = {
    "latency_hiding_sched": "--xla_tpu_enable_latency_hiding_scheduler=true",
    "vmem_128k": "--xla_tpu_scoped_vmem_limit_kib=131072",
}


def _measure_rung(rung: str, batch_size: int, min_seconds: float,
                  dnn: str = "resnet50") -> dict:
    """Time one rung with the shared discipline (timed_window, fenced by
    block_until_ready on the full state — utils/timers.py) and XLA's own
    cost_analysis FLOPs, exactly like benchmark.measure_throughput."""
    import jax
    import jax.numpy as jnp
    import optax

    from gtopkssgd_tpu.benchmark import (
        BenchConfig,
        _compiled_flops,
        _peak_flops_per_chip,
        _setup,
        time_compiled_step,
    )

    cfg = BenchConfig(dnn=dnn, batch_size=batch_size,
                      s2d=(rung == "s2d"))
    model, spec, variables, _, shape = _setup(cfg, None, 1.0)
    classes = 10 if spec.dataset == "cifar10" else 1000
    rng = jax.random.PRNGKey(1)
    x = jax.random.normal(rng, shape)
    y = jax.random.randint(rng, (batch_size,), 0, classes)
    params = variables["params"]
    bstats = variables.get("batch_stats", {})
    if rung == "bf16_params":
        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    if rung == "bf16_input":
        x = x.astype(jnp.bfloat16)

    def loss_fn(params, bstats, x):
        out = model.apply(
            {"params": params, "batch_stats": bstats}, x, train=True,
            mutable=["batch_stats"],
            rngs={"dropout": jax.random.PRNGKey(0)})
        logits, nbs = out
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, nbs["batch_stats"]

    if rung == "fwd":
        def step(state, x):
            params, bstats, mom = state
            loss, nbs = loss_fn(params, bstats, x)
            return (params, nbs, mom), loss
    elif rung == "fwd_bwd":
        def step(state, x):
            params, bstats, mom = state
            (loss, nbs), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, bstats, x)
            # grads must stay live or XLA dead-code-eliminates the
            # backward; fold them into the carried state cheaply.
            probe = jax.tree.map(lambda g: g.sum(), grads)
            return (params, nbs, probe), loss
    else:  # full / bf16_params / bf16_input / s2d: fwd+bwd+momentum SGD
        def step(state, x):
            params, bstats, mom = state
            (loss, nbs), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, bstats, x)
            mom = jax.tree.map(lambda m, g: 0.9 * m + g, mom, grads)
            params = jax.tree.map(
                lambda p, m: p - (0.1 * m).astype(p.dtype), params, mom)
            return (params, nbs, mom), loss

    if rung in ("fwd", "fwd_bwd"):
        # no optimizer state on these rungs; a token scalar tree keeps the
        # carried-state structure uniform without 100 MB of dead HBM
        mom0 = jax.tree.map(lambda a: jnp.zeros((), a.dtype), params)
    else:
        mom0 = jax.tree.map(jnp.zeros_like, params)
    state = (params, bstats, mom0)
    fn = jax.jit(step, donate_argnums=0)
    compiled = fn.lower(state, x).compile()
    flops = _compiled_flops(compiled)
    sec, steps, _ = time_compiled_step(compiled, state, x, min_seconds)
    peak = _peak_flops_per_chip()
    achieved = flops / sec if flops else None
    return {
        "rung": rung,
        "batch_size": batch_size,
        "sec_per_step": round(sec, 6),
        "images_per_sec": round(batch_size / sec, 2),
        "steps_timed": steps,
        "flops_per_step": flops,
        "achieved_tflops": round(achieved / 1e12, 2) if achieved else None,
        "mfu": round(achieved / peak, 4) if achieved and peak else None,
        "device_kind": jax.devices()[0].device_kind,
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }


def _run_child(rung: str, batch_size: int, extra_flag: str,
               min_seconds: float, dnn: str = "resnet50",
               cpu: bool = False) -> dict:
    """One rung in a child interpreter with XLA_FLAGS extended — flags
    bind at backend init, so in-process variants are impossible."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + extra_flag).strip()
    env["PYTHONPATH"] = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    cmd = [sys.executable, os.path.abspath(__file__), "--rung", rung,
           "--batch-size", str(batch_size), "--dnn", dnn,
           "--min-seconds", str(min_seconds)]
    if cpu:
        cmd.append("--cpu")
    try:
        out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                             timeout=900)
    except subprocess.TimeoutExpired as e:
        # A hung child must cost one error row, not the whole ladder's
        # artifact (the already-measured rows still get written).
        return {"rung": rung, "batch_size": batch_size,
                "xla_flags": extra_flag,
                "error": f"child timed out after {e.timeout:.0f}s"}
    if out.returncode != 0:
        return {"rung": rung, "batch_size": batch_size,
                "xla_flags": extra_flag, "error": out.stderr[-500:]}
    try:
        return json.loads(out.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        # RC=0 with garbage stdout happens: a child that died in a C
        # extension after printing warnings, or a wrapper that swallowed
        # the JSON line. Same policy as the timeout above — one error row,
        # not a crashed ladder.
        return {"rung": rung, "batch_size": batch_size,
                "xla_flags": extra_flag,
                "error": "malformed child stdout: "
                         + out.stdout.strip()[-300:]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rung", default="",
                    help="child mode: measure ONE rung and print one line")
    ap.add_argument("--dnn", default="resnet50")
    ap.add_argument("--rungs",
                    default="fwd,fwd_bwd,full,bf16_params,bf16_input,s2d")
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--batch-sizes", default="128,256,512",
                    help="extra 'full' rungs at these batch sizes")
    ap.add_argument("--min-seconds", type=float, default=2.0)
    ap.add_argument("--skip-xla-variants", action="store_true")
    ap.add_argument("--cpu", action="store_true",
                    help="force the host CPU backend (harness smoke / CI; "
                         "MFU is then None — a CPU run has no device "
                         "metric)")
    args = ap.parse_args()

    if args.rung:  # child mode: one rung, one JSON line
        if args.cpu:
            from gtopkssgd_tpu.utils import force_cpu_mesh

            force_cpu_mesh(1)
        from gtopkssgd_tpu.utils import enable_compilation_cache

        enable_compilation_cache()
        row = _measure_rung(args.rung, args.batch_size, args.min_seconds,
                            dnn=args.dnn)
        print(json.dumps(row))
        return

    # Parent mode NEVER initializes a backend: a chip belongs to one
    # process, so a parent holding it would doom every variant child.
    # Each rung runs in its own child, one at a time (the persistent
    # compile cache keeps repeat compiles cheap).
    work = []
    for rung in [r.strip() for r in args.rungs.split(",") if r.strip()]:
        if rung == "s2d" and args.dnn != "resnet50":
            continue  # s2d is a resnet50 stem transform
        work.append((rung, args.batch_size, "", None))
    for bs in [int(b) for b in args.batch_sizes.split(",") if b]:
        if bs != args.batch_size:  # args.batch_size ran as the 'full' rung
            work.append(("full", bs, "", None))
    if not args.skip_xla_variants and not args.cpu:
        # TPU-only flags: meaningless (or fatal) on the CPU backend
        for name, flag in XLA_VARIANTS.items():
            work.append(("full", args.batch_size, flag, name))

    rows, errors_in_a_row, aborted = [], 0, None
    for rung, bs, flag, variant in work:
        row = _run_child(rung, bs, flag, args.min_seconds, dnn=args.dnn,
                         cpu=args.cpu)
        if variant:
            row["variant"] = variant
        rows.append(row)
        print(json.dumps(row), flush=True)
        errors_in_a_row = errors_in_a_row + 1 if "error" in row else 0
        if errors_in_a_row >= 2:
            # Two consecutive failed children: the fault is not one
            # rung's. Stop spending chip time — the measured rows still
            # get written below, and the nonzero exit says the ladder is
            # incomplete.
            aborted = (f"2 consecutive child failures at rung {rung!r}; "
                       f"remaining {len(work) - len(rows)} rungs skipped")
            print(json.dumps({"aborted": aborted}), file=sys.stderr)
            break

    device = next((r["device_kind"].replace(" ", "_") for r in rows
                   if "device_kind" in r), "unknown")
    os.makedirs(RESULTS_DIR, exist_ok=True)
    out_path = os.path.join(RESULTS_DIR, f"mfu_ablation_{device}.json")
    art = {
        "dnn": args.dnn,
        "what": ("dense ResNet-50 MFU ablation ladder — see module "
                 "docstring for rung definitions; deltas between rungs "
                 "attribute the MFU gap"),
        "rows": rows,
    }
    if aborted:
        art["aborted"] = aborted
    with open(out_path, "w") as f:
        json.dump(art, f, indent=1)
    print(json.dumps({"artifact": out_path, "rows": len(rows)}))
    if aborted:
        raise SystemExit(EXIT_ERROR)


if __name__ == "__main__":
    main()
