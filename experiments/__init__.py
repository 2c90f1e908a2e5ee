"""The reference's experiment grid (SURVEY.md C9: the per-experiment
mpirun shell-script family), as a registry of runnable configs.

Each entry reproduces one of the paper's workload configurations
(arXiv:1901.04359 experiments; batch sizes / epochs are the paper's setup
as reconstructed in SURVEY.md — the reference mount was empty, so exact
script values carry [M] confidence and must be re-checked if the mount is
ever populated). Names follow `<dataset>_<dnn>_<mode>`; every entry maps
to a BASELINE.json config (see experiments/README.md).

Run one:      python -m experiments.run cifar10_resnet20_gtopk
List all:     python -m experiments.run --list
CI-scale:     python -m experiments.run <name> --num-iters 30 --nworkers 2
"""

from __future__ import annotations

from typing import Any, Dict

# kwargs are TrainConfig fields; "nworkers" here is the paper's worker
# count (overridable — a v5e-8 slice would use --nworkers 8).
EXPERIMENTS: Dict[str, Dict[str, Any]] = {
    # --- BASELINE.json config #1: single-worker CPU/1-chip reference ----
    "cifar10_vgg16_single": dict(
        dnn="vgg16", batch_size=128, nworkers=1, compression=None,
        density=0.001, max_epochs=140,
        _desc="VGG-16/CIFAR-10 single worker, plain SGD (PR1 ref config)",
        _baseline="#1",
    ),
    # --- paper grid, CIFAR-10 ------------------------------------------
    "cifar10_vgg16_gtopk": dict(
        dnn="vgg16", batch_size=128, nworkers=4, compression="gtopk",
        density=0.001, max_epochs=140,
        _desc="VGG-16/CIFAR-10, 4-worker gTop-k rho=0.001",
        _baseline="#1/#2 family",
    ),
    "cifar10_resnet20_gtopk": dict(
        dnn="resnet20", batch_size=128, nworkers=4, compression="gtopk",
        density=0.001, max_epochs=140,
        _desc="ResNet-20/CIFAR-10, 4-worker gTop-k rho=0.001",
        _baseline="#2",
    ),
    "cifar10_resnet20_gtopk_warmup": dict(
        dnn="resnet20", batch_size=128, nworkers=4, compression="gtopk",
        density=0.001, max_epochs=140, warmup_epochs=4,
        dense_warmup_epochs=4,
        _desc="ResNet-20/CIFAR-10, 4-worker gTop-k with the warm-up "
              "trick (epochs 0-3: LR ramps up AND communication stays "
              "dense, concurrently; top-k starts at epoch 4 — removes "
              "the sparse cold-start ramp)",
        _baseline="#2 warm-up variant",
    ),
    "cifar10_resnet20_dense": dict(
        dnn="resnet20", batch_size=128, nworkers=4, compression="dense",
        density=1.0, max_epochs=140,
        _desc="ResNet-20/CIFAR-10, 4-worker dense-psum baseline",
        _baseline="#2 baseline",
    ),
    "cifar10_resnet20_allgather": dict(
        dnn="resnet20", batch_size=128, nworkers=4, compression="allgather",
        density=0.001, max_epochs=140,
        _desc="ResNet-20/CIFAR-10, 4-worker Top-k allgather (DGC baseline)",
        _baseline="#2 topk-baseline",
    ),
    # --- paper grid, ImageNet ------------------------------------------
    "imagenet_resnet50_gtopk": dict(
        dnn="resnet50", batch_size=32, nworkers=16, compression="gtopk",
        density=0.001, max_epochs=90, dtype="bfloat16",
        _desc="ResNet-50/ImageNet, 16-worker gTop-k rho=0.001 "
              "(north-star workload)",
        _baseline="#3",
    ),
    "imagenet_resnet50_dense": dict(
        dnn="resnet50", batch_size=32, nworkers=16, compression="dense",
        density=1.0, max_epochs=90, dtype="bfloat16",
        _desc="ResNet-50/ImageNet, 16-worker dense-psum baseline",
        _baseline="#3 baseline",
    ),
    "imagenet_alexnet_gtopk": dict(
        dnn="alexnet", batch_size=64, nworkers=16, compression="gtopk",
        density=0.001, max_epochs=95, dtype="bfloat16",
        _desc="AlexNet/ImageNet, 16-worker gTop-k rho=0.001",
        _baseline="#3",
    ),
    # --- paper grid, language/speech -----------------------------------
    "ptb_lstm_gtopk": dict(
        dnn="lstm", batch_size=20, nworkers=4, compression="gtopk",
        density=0.001, max_epochs=40,
        _desc="2-layer LSTM/PTB, 4-worker gTop-k (non-conv flat-gradient "
              "stress; clip-before-compress path)",
        _baseline="#4",
    ),
    "an4_lstm_gtopk": dict(
        dnn="lstman4", batch_size=8, nworkers=4, compression="gtopk",
        density=0.001, max_epochs=100,
        _desc="BiLSTM-CTC/AN4, 4-worker gTop-k rho=0.001",
        _baseline="paper workload 6",
    ),
    # --- TPU extension (NOT reference parity): hierarchical two-level ---
    # Dense psum inside each 4-chip ICI slice, gTop-k across slices — the
    # pod-scale idiom SURVEY.md §5 names for multislice/DCN runs.
    "imagenet_resnet50_gtopk_hier": dict(
        dnn="resnet50", batch_size=32, nworkers=16, compression="gtopk_hier",
        hier_ici=4, density=0.001, max_epochs=90, dtype="bfloat16",
        _desc="ResNet-50/ImageNet, 16 workers as 4 ICI slices x 4: dense "
              "within slice, gTop-k across (TPU extension)",
        _baseline="extension",
    ),
    # --- TPU extension (NOT reference parity): layer-wise selection -----
    # Per-layer top-k_l + per-layer error feedback (arXiv:1911.08772
    # lineage); the flat [N] gradient never materializes, un-serializing
    # the selection from the backward epilogues. Same gTop-k hypercube on
    # the wire.
    "imagenet_resnet50_gtopk_layerwise": dict(
        dnn="resnet50", batch_size=32, nworkers=16,
        compression="gtopk_layerwise", density=0.001, max_epochs=90,
        dtype="bfloat16",
        _desc="ResNet-50/ImageNet, 16-worker layer-wise gTop-k rho=0.001 "
              "(TPU extension)",
        _baseline="extension",
    ),
    "cifar10_resnet20_gtopk_layerwise": dict(
        dnn="resnet20", batch_size=128, nworkers=4,
        compression="gtopk_layerwise", density=0.001, max_epochs=140,
        _desc="ResNet-20/CIFAR-10, 4-worker layer-wise gTop-k rho=0.001 "
              "(TPU extension; measured 2.2x lower cold-start loss than "
              "flat gtopk — convergence_resnet20_layerwise artifact)",
        _baseline="extension",
    ),
    # --- the measured recommended configuration ------------------------
    # Round-4 1200-step identical-seed 3-arm head-to-head
    # (convergence_resnet20_recommended1200_cpu_mesh2.jsonl): flat gTop-k
    # + DGC momentum correction matches dense step-for-step to 90% of
    # the dense loss drop (300 vs 300 steps; gtopk+warmup needs 450) and
    # ends with the LOWEST val loss of the three arms (2e-05 vs dense
    # 4e-05, warmup 5e-05; val_top1 saturates at 1.0 for ALL arms on the
    # synthetic eval — the decision rests on val loss + steps), no
    # warm-up phase needed. Same winner as every shorter-budget A/B
    # (0.73 vs 0.59 val_top1 at 200 steps, warmup_ab artifact). This is
    # the config the README tells a reference user to run.
    "cifar10_resnet20_gtopk_recommended": dict(
        dnn="resnet20", batch_size=128, nworkers=4, compression="gtopk",
        momentum_correction=True, density=0.001, max_epochs=140,
        _desc="RECOMMENDED: ResNet-20/CIFAR-10, 4-worker gTop-k "
              "rho=0.001 + DGC momentum correction — dense-parity "
              "val accuracy at the measured 1200-step horizon, no "
              "warm-up phase needed",
        _baseline="#2 recommended variant",
    ),
}

# BASELINE.json config #5 (density sweep) is a benchmark, not a training
# run, so it has no entry here: it is the density cells PERF.md section 7
# queues for perfbench/ (resnet50.gtopk_r01 first), measured on the chip
# and recorded in PERF_LEDGER.jsonl.
