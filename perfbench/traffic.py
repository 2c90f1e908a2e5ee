"""The one generator of input: a pool of batches drawn from the seed.

A traffic file gives the load (``batch_size`` per chip, ``chips``,
``pool_batches``), a configuration file the kind of rows (``input``). Every
seed gets the same sizes; only the values differ. The pool is made once, in
set-up, so that the window's host work is what the program does with a
batch (prefetch, host-to-device copy, resharding) and never its generation.

Rows are learnable, so that the loss falls over the probe and a selection
that sends the wrong coordinates shows in it:
  images  uint8 noise in [32, 160) plus the row's class pattern in [0, 96):
          ``pattern_cells`` x ``pattern_cells`` colours, upsampled to the
          image size (coarse, so that the loss falls within a probe's few
          steps over rows never seen twice); labels uniform over classes.
  tokens  each row of the batch is one long stream, cut into consecutive
          windows (the hidden state the model carries stays meaningful): the
          next token is a fixed permutation of the last one with probability
          ``follow``, else a draw from a Zipf-like law over the vocabulary.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8


def make_pool(config, traffic, seed):
    """List of ``pool_batches`` host batches; leaves are [chips, batch, ...]."""
    spec = config["input"]
    maker = {"images": _images, "tokens": _tokens}[spec["kind"]]
    return maker(spec, traffic["chips"], traffic["batch_size"],
                 traffic["pool_batches"], seed)


def _images(spec, chips, batch, pool, seed):
    size, classes = spec["image_size"], spec["classes"]
    pixels = size * size * 3
    root = np.random.default_rng(np.random.SeedSequence([seed, 0]))
    bank = root.integers(32, 160, (1 << 22) + pixels, dtype=np.uint8)
    cells = spec["pattern_cells"]
    cell = -(-size // cells)
    low = root.integers(0, 96, (classes, cells, cells, 3), dtype=np.uint8)
    patterns = np.repeat(np.repeat(low, cell, 1), cell, 2)[:, :size, :size]

    def one(index):
        rng = np.random.default_rng(np.random.SeedSequence([seed, 1, index]))
        labels = rng.integers(0, classes, (chips, batch)).astype(np.int32)
        starts = rng.integers(0, 1 << 22, (chips, batch))
        images = np.empty((chips, batch, size, size, 3), np.uint8)
        for w in range(chips):
            for i in range(batch):
                s = starts[w, i]
                np.add(bank[s:s + pixels].reshape(size, size, 3),
                       patterns[labels[w, i]], out=images[w, i])
        return {"image": images, "label": labels}

    with ThreadPoolExecutor(THREADS) as ex:
        return list(ex.map(one, range(pool)))


def _tokens(spec, chips, batch, pool, seed):
    vocab, bptt, follow = spec["vocab_size"], spec["bptt"], spec["follow"]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0]))
    rows, length = chips * batch, pool * bptt + 1
    law = 1.0 / (np.arange(vocab) + 10.0)
    draws = np.searchsorted(np.cumsum(law / law.sum()),
                            rng.random((rows, length))).clip(0, vocab - 1)
    keep = rng.random((rows, length)) < follow
    successor = rng.permutation(vocab)
    stream = np.empty((rows, length), np.int32)
    stream[:, 0] = draws[:, 0]
    for t in range(1, length):
        stream[:, t] = np.where(keep[:, t], successor[stream[:, t - 1]],
                                draws[:, t])
    stream = stream.reshape(chips, batch, length)
    return [{"tokens": np.ascontiguousarray(stream[:, :, b * bptt:(b + 1) * bptt]),
             "targets": np.ascontiguousarray(
                 stream[:, :, b * bptt + 1:(b + 1) * bptt + 1])}
            for b in range(pool)]


class PoolShard:
    """One worker's view of the pool, with the three methods the program's
    trainer asks of a dataset. ``steps_per_epoch`` is the traffic file's: it
    only places the learning-rate schedule's boundaries, which the cells keep
    beyond any run."""

    def __init__(self, pool, rank, steps_per_epoch):
        self.pool, self.rank, self._spe = pool, rank, steps_per_epoch

    def steps_per_epoch(self):
        return self._spe

    def epoch(self, epoch=0):
        for batch in self.pool:
            yield {k: v[self.rank] for k, v in batch.items()}

    def __iter__(self):
        while True:
            yield from self.epoch()
