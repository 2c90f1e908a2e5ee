"""Synthetic token windows for a decoder: each row of a batch is one
independent sequence of ``seq_len`` ids drawn over ``vocab_size`` (a
sliced vocabulary is a smaller vocabulary: ids come from the slice).

There is no real-file path: the decoder's tokenizer and corpus are not in
this repository, and the benchmark brings its own stream (perfbench's pool
stands in for this class). Ids follow a Zipf-like law and, with
probability one half, a fixed successor of the token before, so that a
model can learn something from them. The stream is drawn once per
(split, seed) and every batch is a view of it (the batch contract of
``data/__init__.py``); ranks read disjoint rows.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterator

import numpy as np

from gtopkssgd_tpu.data.partition import split_id as _split_id

SYNTH_WINDOWS = {"train": 64, "valid": 8, "test": 8}


@functools.lru_cache(maxsize=4)
def _stream(split: str, seed: int, rows: int, seq_len: int, vocab: int):
    rng = np.random.default_rng(
        np.random.SeedSequence([seed, _split_id(split), vocab]))
    length = SYNTH_WINDOWS[split] * seq_len + 1
    law = 1.0 / (np.arange(vocab) + 10.0)
    draws = np.searchsorted(np.cumsum(law / law.sum()),
                            rng.random((rows, length))).clip(0, vocab - 1)
    follow = rng.random((rows, length)) < 0.5
    successor = rng.permutation(vocab)
    out = np.empty((rows, length), np.int32)
    out[:, 0] = draws[:, 0]
    for t in range(1, length):
        out[:, t] = np.where(follow[:, t], successor[out[:, t - 1]],
                             draws[:, t])
    return out


class TokenWindows:
    def __init__(self, *, split="train", batch_size=4, rank=0, nworkers=1,
                 data_dir=None, seed=0, seq_len=4096, vocab_size=18992):
        self.split = "valid" if split in ("val", "valid") else split
        self.batch_size, self.seq_len = batch_size, seq_len
        self.vocab_size = vocab_size
        self.synthetic = True
        rows = _stream(self.split, seed, batch_size * nworkers, seq_len,
                       vocab_size)
        self.rows = rows[rank * batch_size:(rank + 1) * batch_size]

    def steps_per_epoch(self) -> int:
        return SYNTH_WINDOWS[self.split]

    def epoch(self, epoch: int = 0) -> Iterator[Dict[str, np.ndarray]]:
        for w in range(self.steps_per_epoch()):
            lo = w * self.seq_len
            yield {"tokens": self.rows[:, lo:lo + self.seq_len],
                   "targets": self.rows[:, lo + 1:lo + self.seq_len + 1]}

    def __iter__(self):
        while True:
            yield from self.epoch()
