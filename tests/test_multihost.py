"""Multi-host path: 2 real processes over jax.distributed on CPU.

Until round 2 the multi-host code (`jax.distributed.initialize`, the
global batch built from this process's per-device pieces in
Trainer._device_batch, per-process shard iterators) was dead code in every
test. This launches TWO actual processes, each owning one CPU device of a
2-device mesh, and runs distributed gtopk training steps across them —
the closest single-machine analogue of the reference's `mpirun -np 2`
smoke (SURVEY.md §4). Skipped cleanly if the jax build lacks CPU
cross-process collectives.

Also covers the profiler flag in the single-process path.
"""

import os
import sys

import pytest

from tests.conftest import run_two_process

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import sys
sys.path.insert(0, sys.argv[3])  # repo root (script itself lives in tmp)
import jax
jax.config.update("jax_platforms", "cpu")
from gtopkssgd_tpu.utils import enable_compilation_cache
enable_compilation_cache()
coord, pid = sys.argv[1], int(sys.argv[2])
try:
    jax.distributed.initialize(coordinator_address=coord, num_processes=2,
                               process_id=pid)
except Exception as e:  # unsupported build -> tell the parent to skip
    print("DISTRIBUTED-UNSUPPORTED:", e)
    raise SystemExit(99)
assert jax.device_count() == 2 and jax.local_device_count() == 1
# initialize() succeeding only proves the COORDINATION service works; the
# pinned jaxlib CPU wheel can still lack cross-process XLA computations
# ("Multiprocess computations aren't implemented on the CPU backend",
# raised from the first collective — observed from orbax's directory-sync
# broadcast inside Trainer.__init__). Probe one tiny collective up front
# so unsupported builds hit the parent's skip path instead of failing
# deep inside training.
try:
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("capability probe")
except Exception as e:
    print("DISTRIBUTED-UNSUPPORTED:", e)
    raise SystemExit(99)
import numpy as np
from gtopkssgd_tpu.trainer import TrainConfig, Trainer

cfg = TrainConfig(dnn="resnet20", batch_size=4, nworkers=2,
                  compression="gtopk", density=0.01, max_epochs=1,
                  log_interval=1, eval_batches=1, out_dir=sys.argv[4])
t = Trainer(cfg)
stats = t.train(2)
assert int(t.state.step) == 2
assert np.isfinite(stats["loss"]), stats
# multi-host checkpoint: every process participates (sharded residual)
t.save()
res_before = np.asarray(
    t.state.opt_state.residual.addressable_shards[0].data)
t2 = Trainer(cfg)
assert t2.restore() and int(t2.state.step) == 2
res_after = np.asarray(
    t2.state.opt_state.residual.addressable_shards[0].data)
np.testing.assert_array_equal(res_before, res_after)
t2.train(1)
assert int(t2.state.step) == 3
t.close(); t2.close()

# Hierarchical mode across the PROCESS boundary: with 2 processes x 1
# device and hier_ici=2 there is ONE slice spanning both processes, so the
# intra-slice dense psum itself crosses DCN-analogue transport — the
# degenerate-but-real case (cross-slice tree empty, level-1 psum does all
# the reducing) that no single-process test can exercise.
hcfg = TrainConfig(dnn="resnet20", batch_size=4, nworkers=2,
                   compression="gtopk_hier", hier_ici=2, density=0.01,
                   max_epochs=1, log_interval=1, eval_batches=1)
with Trainer(hcfg) as th:
    hstats = th.train(1)
    assert np.isfinite(hstats["loss"]), hstats

# Layer-wise mode across the process boundary: the residual is a PER-LEAF
# pytree each sharded P('dp') — state assembly/donation over real
# cross-process transport is a different code path from the flat [N]
# residual the gtopk step above exercised.
lcfg = TrainConfig(dnn="resnet20", batch_size=4, nworkers=2,
                   compression="gtopk_layerwise", density=0.01,
                   max_epochs=1, log_interval=1, eval_batches=1)
with Trainer(lcfg) as tl:
    lstats = tl.train(1)
    assert np.isfinite(lstats["loss"]), lstats
print(f"MULTIHOST-OK pid={pid} loss={stats['loss']:.4f} "
      f"hier_loss={hstats['loss']:.4f} lw_loss={lstats['loss']:.4f}")
"""


def test_two_process_distributed_gtopk(tmp_path):
    run_two_process(WORKER, tmp_path, "MULTIHOST-OK")


def test_profile_dir_writes_trace(tmp_path):
    from gtopkssgd_tpu.dist_trainer import main

    prof = tmp_path / "prof"
    rc = main(["--dnn", "resnet20", "--batch-size", "4", "--nworkers", "1",
               "--num-iters", "1", "--eval-batches", "1",
               "--profile-dir", str(prof), "--profile-steps", "2"])
    assert rc == 0
    # The trace lands under <dir>/plugins/profile/<run>/ with a .trace.json.gz
    found = [f for f in prof.rglob("*") if f.is_file()]
    assert any("trace" in f.name for f in found), found
    # ... and the traced steps' spans beside it, on the epoch clock.
    import json

    with open(prof / "spans.json") as fh:
        written = json.load(fh)
    steps = {s["path"]: set() for s in written["spans"]}
    for s in written["spans"]:
        steps[s["path"]].add(s["step"])
        assert s["epoch_ns"] == int(s["t0"] * 1e9) + s["anchor_ns"]
    # One warm step first, so the two traced ones are steps 1 and 2.
    assert steps["dispatch"] == steps["io"] == steps["io/wait"] == {1, 2}
    assert steps["obs_read"] == {1, 2}
    assert abs(written["anchor_ns"] - written["spans"][0]["anchor_ns"]) < 1e6
