"""Background host-batch prefetcher (reference C8 parity: torch
DataLoader's worker processes overlapped batch assembly + augmentation
with GPU compute; here ONE daemon thread overlaps numpy batch assembly —
including the C++ augment loops, which release the GIL inside
native.dataprep — with the device step).

What the Trainer's worker assembles is a list of per-shard dicts, one per
local mesh position: it draws the datasets' batches (decode, augment) and
gives each shard its micro axis, a view when ``nsteps_update`` is 1 and one
``np.stack`` per shard otherwise. It never stacks across shards: a queued
batch holds the datasets' own arrays (data/__init__.py: not written to
after the yield), and the consumer puts each shard on its own device.

Design constraints honored:

  * Determinism: a single worker thread pulls from the underlying
    iterators strictly in order, so the batch stream is identical to the
    synchronous path (tested).
  * JAX single-threaded discipline: the worker touches ONLY numpy/host
    code; `jax.device_put` stays on the consumer thread.
  * Failure transparency: an exception in assembly is captured and
    re-raised at the consumer's next __next__, not swallowed.
"""

from __future__ import annotations

import itertools
import queue
import threading
from typing import Callable


class Prefetcher:
    """Wraps a zero-arg `produce` callable (returns the next host batch)
    with a bounded background queue of `depth` pre-assembled batches.
    With a ``tracer`` (obs.tracing.Tracer) every ``produce()`` runs inside
    a ``prefetch/assemble`` span that carries the batch's sequence number;
    it is a worker thread's span, so it reaches the span buffer only."""

    _STOP = object()

    def __init__(self, produce: Callable[[], object], depth: int = 2,
                 tracer=None):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._produce = produce
        self._tracer = tracer
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._err = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="prefetch")
        self._thread.start()

    def _assemble(self, seq: int):
        if self._tracer is None:
            return self._produce()
        with self._tracer.span("prefetch/assemble", step=seq):
            return self._produce()

    def _run(self):
        for seq in itertools.count():
            if self._stop.is_set():
                return
            try:
                item = self._assemble(seq)
            except BaseException as e:  # propagate to the consumer
                self._err = e
                self._q.put(self._STOP)
                return
            # Bounded put that stays responsive to close()
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def __iter__(self):
        return self

    def __next__(self):
        if self._stop.is_set():
            # close() ran (nothing else sets _stop): the worker is gone
            # and the queue drained; blocking on get() would hang forever.
            raise RuntimeError("prefetcher is closed")
        if self._err is not None:
            # Worker already died; fail every subsequent call instead of
            # blocking forever on a queue that will never be fed again.
            raise RuntimeError("prefetch worker failed") from self._err
        item = self._q.get()
        if item is self._STOP:
            raise RuntimeError("prefetch worker failed") from self._err
        return item

    def close(self):
        """Stop the worker and discard queued batches (used when the
        underlying iterators are re-created, e.g. on checkpoint restore).

        Raises if the worker cannot be joined: returning with the thread
        still alive would let a replacement prefetcher race it on the
        same underlying iterators (generators are not thread-safe).
        """
        self._stop.set()
        # drain so a blocked put wakes up
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=60.0)
        if self._thread.is_alive():
            raise RuntimeError(
                "prefetch worker did not stop within 60 s; "
                "refusing to hand its iterators to a replacement"
            )
