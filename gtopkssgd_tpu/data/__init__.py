"""Data pipelines (reference C8: the dataset builders inside dl_trainer.py
plus the AN4 audio loader files).

Four dataset families matching the reference workloads — CIFAR-10, ImageNet,
PTB, AN4 — and ``tokens`` (data/tokens.py: synthetic windows for a decoder,
no real-file path), each with:

  * deterministic per-rank sharding (reference ``DataPartitioner``:
    every rank sees a disjoint 1/P slice of the epoch, reshuffled per epoch
    from a shared seed so replicas stay in lockstep);
  * a **synthetic fallback** when ``data_dir`` has no real data, so every
    pipeline (and CI, and the benchmark harness) runs in a zero-egress
    environment with identical shapes/dtypes to the real thing;
  * host-side numpy batches handed to jax at the step boundary (on TPU the
    transfer overlaps with the previous step; the native C++ reader in
    gtopkssgd_tpu/native accelerates the real-file path).

The batch contract (what ``Trainer`` relies on, checked per dataset in
tests/test_data.py): **a yielded batch is not written to after it is
yielded.** The trainer copies nothing on the host that the data does not
force: it queues the yielded arrays themselves (as views) in the prefetcher
and hands them to the runtime, which reads them until the transfer to the
chip has completed. A leaf may be a fresh array or a view of a store the
dataset only reads (PTB's token grid); it may not be a buffer the dataset
fills again for a later batch. A dataset that reuses a buffer copies it at
its own ``yield``, once per shard, not in the trainer.

``get_dataset`` mirrors the reference's ``--dataset`` flag dispatch.
"""

from __future__ import annotations

from typing import Any, Dict

from gtopkssgd_tpu.data.an4 import AN4Dataset
from gtopkssgd_tpu.data.cifar import CIFAR10Dataset
from gtopkssgd_tpu.data.imagenet import ImageNetDataset
from gtopkssgd_tpu.data.partition import DataPartitioner, partition_indices
from gtopkssgd_tpu.data.ptb import PTBDataset
from gtopkssgd_tpu.data.tokens import TokenWindows

_DATASETS = {
    "cifar10": CIFAR10Dataset,
    "imagenet": ImageNetDataset,
    "ptb": PTBDataset,
    "an4": AN4Dataset,
    "tokens": TokenWindows,
}


def get_dataset(
    name: str,
    *,
    split: str = "train",
    batch_size: int = 32,
    rank: int = 0,
    nworkers: int = 1,
    data_dir: str | None = None,
    seed: int = 0,
    **kwargs: Any,
):
    """Build a dataset by its reference ``--dataset`` flag string.

    ``batch_size`` is per-worker (reference semantics: the global batch is
    batch_size * nworkers). ``rank``/``nworkers`` select this worker's shard.
    """
    try:
        cls = _DATASETS[name]
    except KeyError:
        raise ValueError(
            f"unknown dataset {name!r}; available: {sorted(_DATASETS)}"
        ) from None
    return cls(
        split=split,
        batch_size=batch_size,
        rank=rank,
        nworkers=nworkers,
        data_dir=data_dir,
        seed=seed,
        **kwargs,
    )


def available_datasets():
    return sorted(_DATASETS)


__all__ = [
    "get_dataset",
    "available_datasets",
    "DataPartitioner",
    "partition_indices",
    "CIFAR10Dataset",
    "ImageNetDataset",
    "PTBDataset",
    "AN4Dataset",
    "TokenWindows",
]
