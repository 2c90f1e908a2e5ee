"""``Trainer.train`` reads step k's counters after step k+1 is queued.

The read that blocks in iteration k is the step before's: its counters were
copied out (``trainer._hold_counters``) before the next dispatch donated the
state they live in. What a run writes must not know the difference: the same
``obs`` / ``layers`` / ``train`` / ``event`` records as a run whose read
stays on the newest step, which is what a recovery policy, an injector,
``obs_halt_on``, ``obs_mem`` and ``elastic`` still get.
"""

import json
import os

import jax
import numpy as np
import pytest

from gtopkssgd_tpu import trainer as program
from gtopkssgd_tpu.obs.tracing import SPAN_BUFFER, buffered_spans
from gtopkssgd_tpu.trainer import TrainConfig, Trainer
from perfbench.harness import LoweringCounter

# What differs by design between two runs of one configuration: the clock,
# and the counts of which read ran.
VOLATILE = ("time", "throughput", "obs_reads_lagged", "obs_reads_sync")
KINDS = ("obs", "layers", "train", "event")
# Forces the read onto the newest step and changes nothing a healthy run
# writes: no event of severity "error" fires in these runs.
SYNC = dict(obs_halt_on="error")

CONFIGS = {
    "gtopk": dict(compression="gtopk", density=0.01),
    "dense": dict(compression="dense"),
    "lstm_carry": dict(dnn="lstm", batch_size=4, compression="gtopk",
                       density=0.05),
    "gtopk_p4": dict(compression="gtopk", density=0.01, nworkers=4),
    "obs_layers": dict(compression="gtopk", density=0.01, obs_layers=True),
    "spd2": dict(compression="gtopk", density=0.01, steps_per_dispatch=2),
}
# The audited recall is carried from step to step (-1 until an audit ran),
# so it is an input of the step and its array is donated; a counter the
# step only writes is no input of the compiled program and jit leaves its
# old array alone.
AUDIT = dict(obs_audit_interval=2)


def cfg_for(name, out_dir, **kw):
    base = dict(dnn="resnet20", batch_size=2, nworkers=1, log_interval=4,
                eval_batches=1, max_epochs=1, out_dir=str(out_dir))
    return TrainConfig(**{**base, **CONFIGS[name], **kw})


def run(name, out_dir, calls, **kw):
    """Train in the given ``train()`` calls; the records of the four kinds,
    kind by kind in the order written, and the final flat parameters."""
    with Trainer(cfg_for(name, out_dir, **kw)) as t:
        for n in calls:
            t.train(n)
        params = np.concatenate([np.asarray(leaf).ravel() for leaf
                                 in jax.tree.leaves(t.state.params)])
    return records(out_dir), params


def records(out_dir, keep=KINDS, drop=VOLATILE):
    by_kind = {}
    with open(os.path.join(str(out_dir), "metrics.jsonl")) as fh:
        for rec in map(json.loads, fh):
            if rec["kind"] in keep:
                by_kind.setdefault(rec["kind"], []).append(
                    {k: v for k, v in rec.items() if k not in drop})
    return by_kind


def loop_spans(trainer, calls):
    """[(path, step)] of the loop's dispatch, read and sync spans over the
    given ``train()`` calls, in the order they opened."""
    SPAN_BUFFER.clear()
    for n in calls:
        trainer.train(n)
    spans = sorted((s for s in buffered_spans()
                    if s.path in ("dispatch", "obs_read", "final_sync")),
                   key=lambda s: s.t0)
    return [(s.path, s.step) for s in spans]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_lagged_and_synchronous_reads_write_the_same_records(tmp_path, name):
    steps = 8 if name == "spd2" else 6
    lagged, params = run(name, tmp_path / "lagged", [steps])
    sync, params_sync = run(name, tmp_path / "sync", [steps], **SYNC)
    assert sorted(lagged) == sorted(sync)
    spd = CONFIGS[name].get("steps_per_dispatch", 1)
    assert [r["step"] for r in lagged["obs"]] == list(
        range(spd, steps + 1, spd))
    assert ("layers" in lagged) == (name == "obs_layers")
    for kind in lagged:
        assert lagged[kind] == sync[kind], kind
    np.testing.assert_array_equal(params, params_sync)


@pytest.mark.parametrize("name", ["gtopk", "spd2"])
def test_each_read_follows_the_next_dispatch(tmp_path, name):
    """dispatch(k) opens before obs_read(step=k-1); one read a dispatch;
    the last one before ``final_sync``; nothing is held across calls."""
    spd = CONFIGS[name].get("steps_per_dispatch", 1)
    with Trainer(cfg_for(name, tmp_path, log_interval=1000)) as t:
        seen = loop_spans(t, [4 * spd, spd])
    ids = [k * spd for k in range(5)]
    want = [("dispatch", ids[0])]
    for k in (1, 2, 3):
        want += [("dispatch", ids[k]), ("obs_read", ids[k - 1])]
    want += [("obs_read", ids[3]), ("final_sync", None),
             ("dispatch", ids[4]), ("obs_read", ids[4]),
             ("final_sync", None)]
    assert seen == want
    rows = records(tmp_path, keep=("obs",))["obs"]
    assert [r["step"] for r in rows] == [i + spd for i in ids]


@pytest.mark.parametrize("name", ["gtopk", "gtopk_p4"])
def test_single_step_calls_equal_one_call(tmp_path, name):
    """The benchmark's probe drives ``train(1)``; its window ``train(n)``."""
    one, params_one = run(name, tmp_path / "one", [1] * 6)
    whole, params_whole = run(name, tmp_path / "whole", [6])
    assert one == whole
    np.testing.assert_array_equal(params_one, params_whole)


def test_nothing_lowers_after_the_first_call(tmp_path):
    """``train(1)`` runs every program a later ``train(16)`` runs: the
    step and the copy that holds its counters."""
    counter = LoweringCounter()     # the benchmark's own meter
    with Trainer(cfg_for("gtopk", tmp_path, log_interval=50)) as t:
        t.train(1)
        counter.active = True
        t.train(16)
        counter.active = False
        assert t._train_step._cache_size() == 1
    assert counter.count == 0
    rows = records(tmp_path, keep=("obs",))["obs"]
    assert [r["step"] for r in rows] == list(range(1, 18))


@pytest.mark.parametrize("name", ["gtopk", "gtopk_p4"])
def test_a_lagged_read_never_touches_a_donated_array(monkeypatch, tmp_path,
                                                     name):
    """Donation is on: by the time a lagged read runs, the state its step
    left has been donated to the next dispatch, and what is read are the
    copies (that they hold the donated arrays' values is the records'
    equality with a synchronous run's)."""
    sources, checked = [], []
    hold, read = program._hold_counters, Trainer._read_counters

    def holding(counters):
        sources.append(counters)
        return hold(counters)

    def reading(self, step, counters, loss, aux, *, lagged):
        if lagged:
            donated = sources[len(checked)]
            assert donated["audit_recall"].is_deleted()
            assert not any(a.is_deleted()
                           for a in jax.tree.leaves(counters))
            checked.append(step)
        return read(self, step, counters, loss, aux, lagged=lagged)

    monkeypatch.setattr(program, "_hold_counters", holding)
    monkeypatch.setattr(Trainer, "_read_counters", reading)
    with Trainer(cfg_for(name, tmp_path / "lagged", log_interval=1000,
                         obs_layers=True, **AUDIT)) as t:
        assert "age" in t.state.opt_state.telemetry
        t.train(5)
    assert checked == [1, 2, 3, 4]
    assert len(sources) == 5 and all("age" not in c for c in sources)
    monkeypatch.undo()
    sync, _ = run(name, tmp_path / "sync", [5], log_interval=1000,
                  obs_layers=True, **AUDIT, **SYNC)
    assert records(tmp_path / "lagged") == sync


SYNCHRONOUS = {
    "recover_policy": dict(recover_policy="nan_loss=skip"),
    "inject": dict(inject="slow_rank:0:0.001@2-3"),
    "obs_halt_on": dict(obs_halt_on="warn"),
    "obs_mem": dict(obs_mem=True, obs_mem_interval=1),
    "elastic": dict(elastic=True),
}


@pytest.mark.parametrize("what", sorted(SYNCHRONOUS))
def test_read_stays_on_the_newest_step_where_something_may_act(tmp_path,
                                                                what):
    """A recovery policy, an injector, a halt rule, the memory watch and
    an elastic fleet act on a step's reading before the next dispatch:
    there the loop is what it was, read(k) before dispatch(k+1)."""
    with Trainer(cfg_for("dense", tmp_path, log_interval=1000,
                         **SYNCHRONOUS[what])) as t:
        seen = loop_spans(t, [3])
        assert t._obs_reads == {"lagged": 0, "sync": 3}
    want = []
    for k in range(3):
        want += [("dispatch", k), ("obs_read", k)]
    assert seen == want + [("final_sync", None)]


@pytest.mark.parametrize("calls,kw,want", [
    ([8], {}, [(3, 1), (3, 1)]),
    ([1] * 8, {}, [(0, 4), (0, 4)]),
    ([8], SYNC, [(0, 4), (0, 4)]),
    ([8], dict(obs_interval=2), [(1, 1), (1, 1)]),
], ids=["one_call", "single_step_calls", "forced_sync", "obs_interval_2"])
def test_train_record_counts_the_reads_that_ran(tmp_path, calls, kw, want):
    """``obs_reads_lagged`` / ``obs_reads_sync`` since the last ``train``
    record: a read is lagged when a later dispatch was queued behind the
    step it waited for; the row's own step is read on the spot."""
    with Trainer(cfg_for("gtopk", tmp_path, **kw)) as t:
        for n in calls:
            t.train(n)
    rows = records(tmp_path, keep=("train",), drop=())["train"]
    assert [r["step"] for r in rows] == [4, 8]
    assert [(r["obs_reads_lagged"], r["obs_reads_sync"])
            for r in rows] == want
