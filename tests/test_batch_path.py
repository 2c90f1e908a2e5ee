"""A shard's batch goes from the dataset's array to that shard's own device.

The host batch is a list of per-shard dicts ([nsteps_update, B, ...] leaves,
views when ``nsteps_update`` is 1); ``Trainer._device_batch`` puts shard i on
mesh device i and builds the global ``[P, ...]`` array from the pieces. What
the step is handed must be, bit for bit, the layout the host used to stack:
``[P, (steps_per_dispatch,) nsteps_update, B, ...]``.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from gtopkssgd_tpu import trainer as program
from gtopkssgd_tpu.trainer import TrainConfig, Trainer
from perfbench.harness import LoweringCounter
from perfbench.traffic import PoolShard


def small_cfg(**kw):
    base = dict(dnn="resnet20", batch_size=2, compression="gtopk",
                density=0.01, max_epochs=1, log_interval=1, eval_batches=1)
    return TrainConfig(**dict(base, **kw))


def capture_dispatches(trainer, dispatches):
    """Run ``Trainer.train`` with the compiled step swapped for one that
    keeps the device batch it is handed: the whole io path runs (prefetch
    worker, scan-axis stack, the puts) and nothing compiles."""
    seen = []

    def fake_step(state, carry, batch):
        seen.append(batch)
        return state, carry, jnp.zeros(()), {}

    trainer._train_step = fake_step
    trainer.train(dispatches * trainer.cfg.steps_per_dispatch)
    return seen


def stacked_reference(trainer, dispatches):
    """The stacked host layout, built from fresh iterators over the
    trainer's own datasets: a stack per shard over the micro-batches, one
    across the shards, one over the scan axis behind the shard dim."""
    n, spd = trainer.cfg.nsteps_update, trainer.cfg.steps_per_dispatch
    iters = [iter(ds) for ds in trainer.train_shards]

    def one():
        micro = [[next(it) for _ in range(n)] for it in iters]
        return {k: np.stack([np.stack([m[k] for m in shard])
                             for shard in micro]) for k in micro[0][0]}

    out = []
    for _ in range(dispatches):
        hosts = [one() for _ in range(spd)]
        out.append(hosts[0] if spd == 1 else
                   {k: np.stack([h[k] for h in hosts], axis=1)
                    for k in hosts[0]})
    return out


@pytest.mark.parametrize("prefetch", [0, 2])
@pytest.mark.parametrize("spd", [1, 2])
@pytest.mark.parametrize("nsteps", [1, 2])
@pytest.mark.parametrize("p", [1, 4])
def test_device_batch_is_the_stacked_layout_on_each_shards_device(
        p, nsteps, spd, prefetch):
    with Trainer(small_cfg(nworkers=p, nsteps_update=nsteps,
                           steps_per_dispatch=spd,
                           prefetch=prefetch)) as t:
        want = stacked_reference(t, 3)
        got = capture_dispatches(t, 3)
        assert len(got) == len(want) == 3
        sharding = NamedSharding(t.mesh, P("dp"))
        for batch, ref in zip(got, want):
            assert set(batch) == set(ref)
            for k, leaf in batch.items():
                assert leaf.shape == ref[k].shape
                assert leaf.dtype == ref[k].dtype
                assert leaf.sharding == sharding
                np.testing.assert_array_equal(np.asarray(leaf), ref[k])
                assert [s.device for s in leaf.addressable_shards] \
                    == list(t.mesh.devices.flat)
                for i, s in enumerate(leaf.addressable_shards):
                    np.testing.assert_array_equal(
                        np.asarray(s.data), ref[k][i:i + 1])


def pool_trainer(monkeypatch, tmp_path, p, **kw):
    """A trainer on the benchmark's own dataset, which yields views of a
    pool of arrays it holds (as a loader over pre-decoded data does)."""
    rng = np.random.default_rng(7)
    pool = [{"image": rng.integers(0, 255, (p, 2, 32, 32, 3), dtype=np.uint8),
             "label": rng.integers(0, 10, (p, 2)).astype(np.int32)}
            for _ in range(4)]

    def dataset(name, *, split, rank=0, **_):
        return PoolShard(pool, rank if split == "train" else 0, len(pool))

    monkeypatch.setattr(program, "get_dataset", dataset)
    return pool, Trainer(small_cfg(nworkers=p, out_dir=str(tmp_path), **kw))


def train_records(out_dir):
    with open(os.path.join(out_dir, "metrics.jsonl")) as fh:
        return [r for r in map(json.loads, fh) if r["kind"] == "train"]


@pytest.mark.parametrize("prefetch", [0, 2])
@pytest.mark.parametrize("p", [1, 4])
def test_view_path_copies_nothing_on_the_host(monkeypatch, tmp_path, p,
                                              prefetch):
    """nsteps_update 1: every per-shard leaf is a view of the dataset's own
    array, and the ``train`` record says 0 MB were copied."""
    pool, t = pool_trainer(monkeypatch, tmp_path, p, prefetch=prefetch)
    with t:
        shards = t._fetch_host(0, 1)
        assert len(shards) == p
        for rank, shard in enumerate(shards):
            for k, leaf in shard.items():
                assert leaf.shape == (1,) + pool[0][k].shape[1:]
                assert np.shares_memory(leaf, pool[0][k][rank])
                np.testing.assert_array_equal(leaf[0], pool[0][k][rank])
        capture_dispatches(t, 3)
    rows = train_records(str(tmp_path))
    assert len(rows) == 3
    assert [r["host_copied_mb"] for r in rows] == [0.0] * 3


@pytest.mark.parametrize("spd", [1, 2])
def test_forced_stack_is_counted_to_the_byte(monkeypatch, tmp_path, spd):
    """nsteps_update 2: one stack per shard is the copy the data forces
    (and with steps_per_dispatch 2 one more over the scan axis);
    ``host_copied_mb`` is exactly those bytes per record."""
    p, nsteps = 4, 2
    pool, t = pool_trainer(monkeypatch, tmp_path, p, nsteps_update=nsteps,
                           steps_per_dispatch=spd, log_interval=spd)
    with t:
        capture_dispatches(t, 2)
    step_bytes = nsteps * sum(v.nbytes for v in pool[0].values())
    # per dispatch: spd micro stacks, and the scan-axis stack of them all
    want = (spd * step_bytes * (2 if spd > 1 else 1)) / 1e6
    rows = train_records(str(tmp_path))
    assert len(rows) == 2
    assert [r["host_copied_mb"] for r in rows] == [want] * 2


def test_accounted_step_is_the_dispatched_one(tmp_path):
    """p = 4 in one process: the executable the AOT pass accounts from
    ``_abstract_batch`` is the one the first dispatch runs (same lowering,
    same input shardings), the step's jit cache holds one entry after
    both, and nothing lowers between a first call and a later one."""
    window_compiles = LoweringCounter()   # the benchmark's own meter
    cfg = small_cfg(nworkers=4, batch_size=4, obs_mem=True,
                    obs_mem_interval=1, out_dir=str(tmp_path))
    with Trainer(cfg) as t:
        abstract = t._abstract_batch()
        real = t._device_batch(t._shard_batches(
            [iter(ds) for ds in t.train_shards])[0])
        assert {k: (v.shape, v.dtype, v.sharding) for k, v in real.items()} \
            == {k: (v.shape, v.dtype, v.sharding)
                for k, v in abstract.items()}
        accounted = t._train_step.lower(t.state, t.carry, abstract)
        dispatched = t._train_step.lower(t.state, t.carry, real)
        assert accounted.as_text() == dispatched.as_text()
        assert accounted.compile().input_shardings \
            == dispatched.compile().input_shardings
        t.train(1)                      # the probe's call
        assert t._train_step._cache_size() == 1
        window_compiles.active = True
        t.train(2)                      # the window's
        window_compiles.active = False
        assert window_compiles.count == 0
        assert t._train_step._cache_size() == 1
        assert t.memwatch.recompile_count == 0
        assert len(t.memwatch.shapes) == 1
