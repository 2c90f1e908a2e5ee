"""Launch one registered experiment (reference C9: the mpirun scripts).

    python -m experiments.run cifar10_resnet20_gtopk
    python -m experiments.run --list
    python -m experiments.run imagenet_resnet50_gtopk --nworkers 8 \
        --num-iters 100          # scale to the hardware at hand / CI

Overrides mirror dist_trainer flags; anything not overridden runs with the
paper's exact configuration from the registry.
"""

from __future__ import annotations

import argparse
from typing import Optional, Sequence

from experiments import EXPERIMENTS


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser("experiments.run")
    ap.add_argument("name", nargs="?", help="experiment name (see --list)")
    ap.add_argument("--list", action="store_true", dest="list_all")
    ap.add_argument("--nworkers", type=int, default=None)
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--max-epochs", type=int, default=None)
    ap.add_argument("--num-iters", type=int, default=None,
                    help="fixed step count instead of the full epoch run")
    ap.add_argument("--eval-batches", type=int, default=None)
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--out-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-interval", type=int, default=None)
    args = ap.parse_args(argv)

    if args.list_all or not args.name:
        width = max(len(n) for n in EXPERIMENTS)
        for name, spec in EXPERIMENTS.items():
            print(f"{name:<{width}}  [{spec['_baseline']:>14}]  "
                  f"{spec['_desc']}")
        return 0

    if args.name not in EXPERIMENTS:
        ap.error(f"unknown experiment {args.name!r} (try --list)")
    spec = {k: v for k, v in EXPERIMENTS[args.name].items()
            if not k.startswith("_")}
    for field in ("nworkers", "batch_size", "max_epochs", "data_dir",
                  "out_dir", "eval_batches", "log_interval"):
        v = getattr(args, field)
        if v is not None:
            spec[field] = v

    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    with Trainer(TrainConfig(**spec)) as trainer:
        if args.resume:
            restored = trainer.restore()
            trainer.logger.info("resume: %s",
                                "restored" if restored else "fresh")
        if args.num_iters is not None:
            stats = trainer.train(args.num_iters)
            stats.update(trainer.test())
        else:
            stats = trainer.fit()
        trainer.logger.info("done: %s", stats)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
