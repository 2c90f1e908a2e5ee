"""Data pipelines: sharding disjointness, shapes, determinism, synthetic
fallbacks. The reference had no pipeline tests at all (SURVEY.md §4) —
sharding bugs there would surface only as wrong convergence curves.
"""

import numpy as np
import pytest

from gtopkssgd_tpu.data import (
    available_datasets,
    get_dataset,
    partition_indices,
)


def test_partition_disjoint_and_covering():
    n, p = 103, 4
    shards = [partition_indices(n, r, p, seed=1, epoch=2) for r in range(p)]
    allidx = np.concatenate(shards)
    assert len(allidx) == n
    assert len(set(allidx.tolist())) == n  # disjoint cover
    # deterministic across calls, different across epochs
    again = partition_indices(n, 2, p, seed=1, epoch=2)
    np.testing.assert_array_equal(shards[2], again)
    other_epoch = partition_indices(n, 2, p, seed=1, epoch=3)
    assert not np.array_equal(shards[2], other_epoch)
    with pytest.raises(ValueError):
        partition_indices(n, 4, p)


def test_registry():
    assert {"cifar10", "imagenet", "ptb", "an4"} <= set(available_datasets())
    with pytest.raises(ValueError):
        get_dataset("mnist")


def test_cifar_synthetic_batches():
    ds = get_dataset("cifar10", batch_size=16, rank=0, nworkers=2)
    assert ds.synthetic
    batch = next(iter(ds))
    assert batch["image"].shape == (16, 32, 32, 3)
    assert batch["image"].dtype == np.uint8  # wire format: raw pixels,
    assert batch["label"].shape == (16,) and batch["label"].dtype == np.int32
    assert ds.steps_per_epoch() > 0         # normalization is on-device


def test_cifar_rank_shards_disjoint_same_epoch():
    a = get_dataset("cifar10", batch_size=8, rank=0, nworkers=2, augment=False)
    b = get_dataset("cifar10", batch_size=8, rank=1, nworkers=2, augment=False)
    ia = a.partitioner.indices(0)
    ib = b.partitioner.indices(0)
    assert not set(ia.tolist()) & set(ib.tolist())


def test_cifar_eval_deterministic():
    ds = get_dataset("cifar10", split="test", batch_size=8)
    b1 = next(iter(ds))
    b2 = next(iter(get_dataset("cifar10", split="test", batch_size=8)))
    np.testing.assert_array_equal(b1["image"], b2["image"])


def test_imagenet_synthetic():
    ds = get_dataset("imagenet", batch_size=4, num_classes=50)
    batch = next(iter(ds))
    assert batch["image"].shape == (4, 224, 224, 3)
    assert batch["image"].dtype == np.uint8  # wire format: raw pixels,
    assert batch["label"].max() < 50         # normalization is on-device


def test_ptb_bptt_windows_and_carry_layout():
    ds = get_dataset("ptb", batch_size=4, bptt=35)
    it = iter(ds)
    b1, b2 = next(it), next(it)
    assert b1["tokens"].shape == (4, 35)
    # targets are tokens shifted by one within the stream
    np.testing.assert_array_equal(b1["targets"][:, :-1], b1["tokens"][:, 1:])
    # consecutive windows are temporally contiguous (carry validity)
    np.testing.assert_array_equal(b2["tokens"][:, 0], b1["targets"][:, -1])
    assert ds.vocab_size == 10000


def test_ptb_rank_rows_disjoint():
    a = get_dataset("ptb", batch_size=4, rank=0, nworkers=2)
    b = get_dataset("ptb", batch_size=4, rank=1, nworkers=2)
    assert not np.array_equal(a.inputs, b.inputs)
    assert a.inputs.shape == b.inputs.shape


def test_an4_synthetic_ctc_batches():
    ds = get_dataset("an4", batch_size=4)
    batch = next(iter(ds))
    b, t, f = batch["spectrogram"].shape
    assert (b, f) == (4, 161) and t % 16 == 0
    assert batch["labels"].shape[0] == 4
    assert (batch["input_lengths"] <= t).all()
    assert (batch["label_lengths"] > 0).all()
    assert (batch["labels"] < ds.num_chars).all()


def test_synthetic_class_signal_shared_across_splits():
    """Train and held-out synthetic data must carry the SAME class signal —
    otherwise eval on synthetic runs is structurally chance-level (the bug
    this pins: offsets/signatures were drawn from split-specific streams).
    """
    from gtopkssgd_tpu.data.an4 import _synth_utterances
    from gtopkssgd_tpu.data.cifar import _synthetic

    # CIFAR: per-class mean color of train vs test must agree per class.
    for seed in (0, 7):
        tr_img, tr_lab = _synthetic("train", seed)
        te_img, te_lab = _synthetic("test", seed)
        tr_mean = np.stack([
            tr_img[tr_lab == c].mean(axis=(0, 1, 2)) for c in range(10)
        ])  # [10, 3]
        te_mean = np.stack([
            te_img[te_lab == c].mean(axis=(0, 1, 2)) for c in range(10)
        ])
        # Every class's train-mean color is closest to the SAME class's
        # test-mean color.
        d = np.linalg.norm(tr_mean[:, None, :] - te_mean[None, :, :], axis=-1)
        assert (d.argmin(axis=1) == np.arange(10)).all()

    # ImageNet: the class-offset table itself must be identical.
    from gtopkssgd_tpu.data.imagenet import ImageNetDataset

    tr = ImageNetDataset(split="train", batch_size=2, num_classes=16,
                         image_size=32, seed=3)
    te = ImageNetDataset(split="val", batch_size=2, num_classes=16,
                         image_size=32, seed=3)
    assert tr.synthetic and te.synthetic
    np.testing.assert_array_equal(tr._offsets, te._offsets)

    # AN4: per-char spectral signature direction must correlate across
    # splits (utterance noise differs; the char->spectrum mapping must not).
    tr_utts = _synth_utterances("train", 5, 29)
    te_utts = _synth_utterances("test", 5, 29)

    def char_means(utts):
        acc = {c: [] for c in range(1, 29)}
        for u in utts[:64]:
            L = len(u["labels"])
            fp = u["spec"].shape[0] // L
            for j, ch in enumerate(u["labels"]):
                acc[int(ch)].append(u["spec"][j * fp:(j + 1) * fp].mean(0))
        return {c: np.mean(v, axis=0) for c, v in acc.items() if v}

    tm, em = char_means(tr_utts), char_means(te_utts)
    common = sorted(set(tm) & set(em))
    assert len(common) >= 20
    cos = [
        float(np.dot(tm[c], em[c])
              / (np.linalg.norm(tm[c]) * np.linalg.norm(em[c]) + 1e-9))
        for c in common
    ]
    assert np.mean(cos) > 0.5, np.mean(cos)


class TestSynthHard:
    """The discriminative synthetic-CIFAR variant (data/cifar.py::_synthetic
    hard=True): weak spatial class patterns + train-only label noise."""

    def test_train_label_noise_rate(self):
        from gtopkssgd_tpu.data.cifar import _synthetic

        _, easy = _synthetic("train", seed=7)
        _, hard = _synthetic("train", seed=7, hard=True)
        flipped = (easy != hard).mean()
        # 10% resampled uniformly over 10 classes -> ~9% actually differ
        assert 0.05 < flipped < 0.14, flipped

    def test_test_split_labels_clean_and_signal_shared(self):
        from gtopkssgd_tpu.data.cifar import _synthetic

        imgs_a, lab_a = _synthetic("test", seed=7, hard=True)
        # test-split labels must be CLEAN (noise is train-only)
        import numpy as _np
        _np.testing.assert_array_equal(lab_a, _synthetic("test", seed=7)[1])
        # class signal must be split-independent: average image of one
        # class in train and test must correlate (shared pattern), while
        # two different classes must not
        timgs, tlab = _synthetic("train", seed=7, hard=True)
        import numpy as np

        def class_mean(imgs, lab, c):
            m = imgs[lab == c].astype(np.float32).mean(0)
            return (m - m.mean()).ravel()

        same = np.corrcoef(class_mean(imgs_a, lab_a, 3),
                           class_mean(timgs, tlab, 3))[0, 1]
        diff = np.corrcoef(class_mean(imgs_a, lab_a, 3),
                           class_mean(timgs, tlab, 4))[0, 1]
        assert same > 0.3 and abs(diff) < 0.2, (same, diff)

    def test_signal_is_spatial_not_flat(self):
        from gtopkssgd_tpu.data.partition import signal_rng
        import numpy as np

        pat = signal_rng(7).standard_normal((10, 32, 32, 3)) * 0.07
        # per-class pattern varies across pixels (a flat offset would not)
        assert np.std(pat[0], axis=(0, 1)).min() > 0.01

    def test_trainer_plumbing(self):
        from gtopkssgd_tpu.trainer import TrainConfig

        cfg = TrainConfig(dnn="resnet20", synth_hard=True).resolved()
        assert cfg.synth_hard and cfg.dataset == "cifar10"


@pytest.mark.parametrize("name,kw", [
    ("cifar10", dict(batch_size=8)),
    ("imagenet", dict(batch_size=2, num_classes=10)),
    ("ptb", dict(batch_size=4)),
    ("an4", dict(batch_size=2)),
])
def test_yielded_batch_is_not_written_to_afterwards(name, kw):
    """The contract in data/__init__.py: the trainer queues and transfers
    the yielded arrays themselves, so a dataset builds no later batch in
    the memory of an earlier one. Leaf by leaf, consecutive batches share
    no memory, and the first batch still reads the same after four more
    were drawn."""
    it = iter(get_dataset(name, **kw))
    first = next(it)
    snapshot = {k: np.array(v, copy=True) for k, v in first.items()}
    prev = first
    for _ in range(4):
        batch = next(it)
        assert set(batch) == set(first)
        for k in batch:
            assert not np.shares_memory(batch[k], prev[k]), (name, k)
            assert not np.shares_memory(batch[k], first[k]), (name, k)
        prev = batch
    for k, v in first.items():
        np.testing.assert_array_equal(v, snapshot[k])
