"""Cold-start mitigation A/B through the production Trainer — the generator
for benchmarks/results/warmup_ab_cpu_mesh8.json.

Round 2 committed that artifact without its generator; this script makes
its arms reproducible. The artifact's two ``*restore_rejected_u_ablation``
entries (a locally-picked but globally-rejected coordinate's velocity u
RESTORED alongside its repaired residual value; measured worse, so the
shipped semantics mask u at the LOCAL selection — the NOTE in optimizer.py)
stay as the record of that decision: the option that produced them is gone
(PR 44), so those two entries can no longer be regenerated from this tree.

Protocol (unchanged from the round-2 capture): 8-way SPMD over a virtual CPU
mesh (REAL collectives), ResNet-20 / synthetic CIFAR, rho=0.001, batch
4/worker, 200 steps, identical seed; loss sampled every 25 steps, held-out
eval at the end.

Usage:
  python benchmarks/warmup_ab.py --arms cold_start
Arms merge into the existing artifact (existing entries are preserved).

The 8-way virtual CPU mesh is forced IN-SCRIPT (force_cpu_mesh, as
tests/conftest.py does), so the arms never depend on the shell's
environment.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gtopkssgd_tpu.utils import force_cpu_mesh  # noqa: E402

force_cpu_mesh(8)

RESULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")
ARTIFACT = os.path.join(RESULTS, "warmup_ab_cpu_mesh8.json")

# arm name -> TrainConfig overrides on the shared base config
ARMS = {
    "cold_start": {},
    "dense_warmup_1_epoch": {"dense_warmup_epochs": 1},
    "layerwise_cold_start": {"compression": "gtopk_layerwise"},
    "layerwise_dense_warmup_1_epoch": {
        "compression": "gtopk_layerwise", "dense_warmup_epochs": 1},
    "momentum_correction_cold_start": {"momentum_correction": True},
    "layerwise_momentum_correction_cold_start": {
        "compression": "gtopk_layerwise", "momentum_correction": True},
}


def run_arm(name: str, args) -> dict:
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    kw = dict(
        dnn="resnet20", nworkers=8, compression="gtopk",
        density=args.density, batch_size=4, seed=args.seed,
        log_interval=10_000_000, eval_batches=args.eval_batches,
    )
    kw.update(ARMS[name])
    cfg = TrainConfig(**kw)
    # Same max_epochs-from-steps arithmetic as convergence_run.py so the LR
    # schedule sees the true epoch span instead of a constant LR.
    from gtopkssgd_tpu.data import get_dataset
    from gtopkssgd_tpu.trainer import shard_steps_per_epoch

    rcfg = cfg.resolved()
    ds = get_dataset(rcfg.dataset, split="train", batch_size=rcfg.batch_size,
                     rank=0, nworkers=rcfg.nworkers, seed=args.seed)
    spe = shard_steps_per_epoch(ds, rcfg.batch_size, rcfg.nsteps_update)
    cfg.max_epochs = max(1, math.ceil(args.steps / spe))

    losses = []
    with Trainer(cfg) as trainer:
        done = 0
        while done < args.steps:
            n = min(25, args.steps - done)
            stats = trainer.train(n)
            done += n
            losses.append(round(stats["loss"], 3))
            print(f"  {name:42s} step {done:4d} loss {stats['loss']:.4f}",
                  flush=True)
        ev = trainer.test()
    return {"losses_every_25_steps": losses,
            "val_top1": round(float(ev.get("val_top1", 0.0)), 3),
            "val_loss": round(float(ev.get("val_loss", float("nan"))), 3)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arms", default="cold_start",
                    help=f"comma list from {sorted(ARMS)}")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--density", type=float, default=0.001)
    ap.add_argument("--eval-batches", type=int, default=16)
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args()

    from gtopkssgd_tpu.utils import enable_compilation_cache

    enable_compilation_cache()

    doc = {}
    if os.path.exists(ARTIFACT):
        with open(ARTIFACT) as fh:
            doc = json.load(fh)

    for name in args.arms.split(","):
        name = name.strip()
        if name not in ARMS:
            raise SystemExit(f"unknown arm {name!r}; pick from {sorted(ARMS)}")
        print(f"[warmup_ab] arm={name} steps={args.steps} "
              f"rho={args.density}", flush=True)
        # Merge INTO any existing entry: curated fields added by hand
        # (e.g. the 'note' explanations the optimizer docstrings cite)
        # survive a re-measurement instead of being silently dropped.
        entry = doc.get(name, {})
        entry.update(run_arm(name, args))
        doc[name] = entry

    tmp = ARTIFACT + ".partial"
    with open(tmp, "w") as fh:
        json.dump(doc, fh, indent=2)
        fh.write("\n")
    os.replace(tmp, ARTIFACT)
    print(json.dumps({k: v for k, v in doc.items()
                      if isinstance(v, dict) and "val_top1" in v}))


if __name__ == "__main__":
    main()
