"""Background host-batch prefetcher: ordering, failure, trainer equivalence.

The reference's data pipelines got async batch assembly from torch
DataLoader worker processes (SURVEY.md C8); here one daemon thread
overlaps numpy assembly with the device step. The contract that matters:
the batch stream is EXACTLY the synchronous stream (determinism), and
worker exceptions surface at the consumer.
"""

import time

import numpy as np
import pytest

from gtopkssgd_tpu.utils import Prefetcher


def test_order_preserved():
    src = iter(range(100))
    pf = Prefetcher(lambda: next(src), depth=3)
    got = [next(pf) for _ in range(50)]
    pf.close()
    assert got == list(range(50))


def test_same_stream_with_and_without_a_tracer():
    """The tracer only watches: the batch stream is the untraced one, and
    every assembly is one ``prefetch/assemble`` span of the worker thread
    that carries the batch's sequence number and never reaches the sink."""
    from gtopkssgd_tpu.obs import tracing

    def stream():
        rng = np.random.default_rng(5)
        return lambda: rng.standard_normal(8)

    plain = Prefetcher(stream(), depth=2)
    want = [next(plain) for _ in range(20)]
    plain.close()
    sunk = []
    tracer = tracing.Tracer(sink=lambda *span: sunk.append(span))
    mark = time.perf_counter()
    traced = Prefetcher(stream(), depth=2, tracer=tracer)
    got = [next(traced) for _ in range(20)]
    traced.close()
    assert all(np.array_equal(a, b) for a, b in zip(want, got))
    spans = [r for r in tracing.buffered_spans()
             if r.anchor_ns == tracer.anchor_ns and r.t0 >= mark]
    assert {r.path for r in spans} == {"prefetch/assemble"}
    assert {r.thread for r in spans} == {"prefetch"}
    # The worker runs at most depth + 1 batches ahead of the consumer.
    assert [r.step for r in spans][:20] == list(range(20))
    assert 20 <= len(spans) <= 20 + 3
    assert sunk == [] and tracer.stats.summary() == {}


def test_worker_exception_propagates():
    def produce():
        raise ValueError("boom")

    pf = Prefetcher(produce, depth=2)
    with pytest.raises(RuntimeError, match="prefetch worker failed"):
        next(pf)
    pf.close()


def test_next_after_failure_keeps_raising():
    def produce():
        raise ValueError("boom")

    pf = Prefetcher(produce, depth=2)
    for _ in range(3):  # every call fails; none may block
        with pytest.raises(RuntimeError, match="prefetch worker failed"):
            next(pf)
    pf.close()


def test_close_unblocks_full_queue():
    pf = Prefetcher(lambda: 1, depth=1)
    time.sleep(0.2)  # let the worker fill the queue and block on put
    pf.close()       # must not hang
    assert not pf._thread.is_alive()


def test_bad_depth():
    with pytest.raises(ValueError):
        Prefetcher(lambda: 1, depth=0)


def test_next_after_close_raises():
    pf = Prefetcher(lambda: 1, depth=1)
    pf.close()
    with pytest.raises(RuntimeError, match="closed"):
        next(pf)


def test_trainer_train_after_close_raises():
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    t = Trainer(TrainConfig(
        dnn="resnet20", batch_size=2, nworkers=1, compression=None,
        max_epochs=1, eval_batches=1,
    ))
    t.close()
    with pytest.raises(RuntimeError, match="closed"):
        t.train(1)


def test_trainer_stream_identical_with_and_without_prefetch():
    """Two trainers, same seed, prefetch on vs off: identical loss
    trajectory — the prefetcher must not reorder, drop, or duplicate
    batches."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    def losses(prefetch):
        with Trainer(TrainConfig(
            dnn="resnet20", batch_size=2, nworkers=8, compression="gtopk",
            density=0.01, max_epochs=1, log_interval=1, eval_batches=1,
            prefetch=prefetch,
        )) as t:
            return [float(t.train(1)["loss"]) for _ in range(3)]

    a = losses(0)
    b = losses(2)
    np.testing.assert_array_equal(a, b)
