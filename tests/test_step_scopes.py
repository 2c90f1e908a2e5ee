"""The compiled step names its stages: every ``jax.named_scope`` of the
stage map (PERF.md section 3) is on at least one operation of the lowered
step, in every build of it, and the module has its fixed name. A device
trace is reduced to stage times by these names
(perfbench/metrics/scoped.py), so a refactor that drops one fails here
before it blinds a metric on the chip."""

import re

import pytest

from gtopkssgd_tpu.trainer import TrainConfig, Trainer

MODEL = {
    "resnet": dict(dnn="resnet20"),
    "lstm": dict(dnn="lstm", dataset="ptb"),
}
EVERY_STEP = {"gtopk/fwd_bwd", "gtopk/flatten", "gtopk/unflatten",
              "gtopk/apply", "gtopk/telemetry"}
# The LSTM's configuration clips the gradient's global norm (--dataset ptb).
CLIPS = {"resnet": set(), "lstm": {"gtopk/clip"}}
SPARSE = {"gtopk/accumulate", "gtopk/select", "gtopk/mask"}
BUILD = {
    "sparse_p1": (dict(compression="gtopk", density=0.01),
                  EVERY_STEP | SPARSE),
    "dense_p1": (dict(compression="dense"), EVERY_STEP),
    "sparse_dp4": (dict(compression="gtopk", density=0.01, nworkers=4),
                   EVERY_STEP | SPARSE | {
                       "gtopk/repair", "gtopk/allreduce/round0",
                       "gtopk/allreduce/round1"}),
    "dense_dp4": (dict(compression="dense", nworkers=4),
                  EVERY_STEP | {"gtopk/allreduce"}),
    # The leaves form (gtopk_layerwise), which no cell runs: a leaf's
    # accumulate is a bare add, its repair the form's own scatter, and at
    # P > 1 the index selection has no scope of its own.
    "layerwise_p1": (dict(compression="gtopk_layerwise", density=0.01),
                     EVERY_STEP | {"gtopk/select", "gtopk/mask"}),
    "layerwise_dp4": (dict(compression="gtopk_layerwise", density=0.01,
                           nworkers=4),
                      EVERY_STEP | {"gtopk/mask", "gtopk/allreduce/round0",
                                    "gtopk/allreduce/round1"}),
}


def lowered_step(**flags):
    base = dict(batch_size=4, nworkers=1, log_interval=5, eval_batches=1,
                max_epochs=1, prefetch=0)
    trainer = Trainer(TrainConfig(**dict(base, **flags)))
    try:
        batch = trainer._device_batch(
            trainer._shard_batches(trainer._iters)[0])
        return trainer._train_step.lower(
            trainer.state, trainer.carry, batch).as_text(debug_info=True)
    finally:
        trainer.close()


def scopes_in(text):
    """The scope paths that operations of the lowered module carry."""
    return set(re.findall(
        r'loc\("(?:[^"]*?/)?(gtopk/[a-z_]+(?:/round\d+)?)', text))


@pytest.mark.parametrize("build", sorted(BUILD))
@pytest.mark.parametrize("model", sorted(MODEL))
def test_lowered_step_carries_every_scope_of_its_build(model, build):
    flags, want = BUILD[build]
    want = want | CLIPS[model]
    text = lowered_step(**MODEL[model], **flags)
    assert re.search(r"module @(\S+)", text).group(1) == "jit_gtopk_train_step"
    found = scopes_in(text)
    # A round's operations are under gtopk/allreduce too.
    found |= {s.rsplit("/round", 1)[0] for s in found}
    assert want <= found, sorted(want - found)
    stages = {s for s in found if "/round" not in s}
    assert stages <= EVERY_STEP | SPARSE | CLIPS[model] | {
        "gtopk/repair", "gtopk/allreduce"}
    if "p1" in build:
        assert "gtopk/allreduce" not in stages
    if build.startswith("dense"):
        assert not stages & SPARSE


def test_telemetry_scope_is_absent_without_the_counters():
    text = lowered_step(**MODEL["resnet"], compression="gtopk", density=0.01,
                        obs_counters=False)
    found = scopes_in(text)
    assert "gtopk/telemetry" not in found
    assert EVERY_STEP - {"gtopk/telemetry"} | SPARSE <= found
