"""Resilience subsystem (gtopkssgd_tpu.resilience): fault injection,
recovery policies, preemption-safe checkpointing, and their trainer
wiring.

Grammar/budget/guard semantics are pinned with pure unit tests;
checkpoint integrity with real orbax round-trips of tiny pytrees; the
trainer paths end to end on the 2-way CPU mesh with the canonical
gate-smoke config (resnet20/bs4/gtopk_layerwise/rho=0.01/seed 42 — one
compiled step shared across tests via the persistent compile cache).
The error-feedback invariant under test throughout: a recovery must
never drop, zero, or double-count the residual (arXiv:1911.08772 ties
convergence to its dynamics), so skip restores it bit-identically and
resume-after-preempt reproduces the uninterrupted loss trace exactly.
"""

import json
import os
import signal
import time

import jax
import numpy as np
import pytest

from gtopkssgd_tpu.obs import HALT_EXIT_CODE
from gtopkssgd_tpu.resilience import (
    PREEMPT_EXIT_CODE,
    FaultInjector,
    InjectedLoaderError,
    PreemptionGuard,
    RecoveryManager,
    describe_policy,
    parse_inject,
    parse_policy,
    retry_call,
)
from gtopkssgd_tpu.resilience.inject import LATEST, corrupt_checkpoint_dir
from gtopkssgd_tpu.utils.checkpoint import (
    CheckpointManager,
    CheckpointMismatch,
    state_digest,
)

# The canonical tiny run (same model/flags as benchmarks/obs_gate_smoke.py
# so every dist_trainer e2e below reuses one cached XLA executable).
CANON = [
    "--dnn", "resnet20", "--batch-size", "4", "--nworkers", "2",
    "--compression", "gtopk_layerwise", "--density", "0.01",
    "--seed", "42", "--eval-batches", "1", "--log-interval", "1",
    "--obs-interval", "1",
]


def _records(out_dir):
    path = os.path.join(out_dir, "metrics.jsonl")
    return [json.loads(line) for line in open(path)]


def _train_losses(out_dir):
    return {r["step"]: r["loss"] for r in _records(out_dir)
            if r["kind"] == "train"}


# ------------------------------------------------------- inject grammar

def test_parse_inject_grammar():
    faults = parse_inject(
        "nan_grad@120, slow_rank:2:2.5s@50-60, corrupt_ckpt@latest,"
        "preempt@200,loader_raise@75")
    by_kind = {f.kind: f for f in faults}
    assert len(faults) == 5
    assert by_kind["nan_grad"].start == by_kind["nan_grad"].end == 120
    assert by_kind["nan_grad"].point
    sr = by_kind["slow_rank"]
    assert (sr.start, sr.end, sr.args) == (50, 60, ("2", "2.5s"))
    assert not sr.point
    assert by_kind["corrupt_ckpt"].start == LATEST
    # spec() round-trips through the parser
    for f in faults:
        assert parse_inject(f.spec())[0].spec() == f.spec()


@pytest.mark.parametrize("bad", [
    "nan_grad",                 # no @WHEN
    "frobnicate@3",             # unknown kind
    "nan_grad@latest",          # latest is corrupt_ckpt-only
    "corrupt_ckpt@5",           # corrupt_ckpt is restore-keyed
    "nan_grad@0",               # steps are 1-based
    "nan_grad@9-5",             # inverted window
    "nan_grad@x",               # non-numeric step
    "slow_rank:1@5",            # missing duration arg
    "slow_rank:1:-2s@5",        # negative duration
    "nan_grad:7@5",             # args on an argless kind
    " , ",                      # empty spec
])
def test_parse_inject_rejects(bad):
    with pytest.raises(ValueError):
        parse_inject(bad)


def test_fault_window_point_consumed_range_refires():
    point = parse_inject("nan_grad@3")[0]
    assert point.window(0, 2) is None        # window is (prev, new]
    assert point.window(2, 3) == 3
    point.fired = 1
    # a skip rewinds the step counter; a consumed point fault must not
    # re-fire when the same window is dispatched again
    assert point.window(2, 3) is None
    rng = parse_inject("nan_grad@2-4")[0]
    assert rng.window(0, 1) is None
    for prev in (1, 2, 3):
        rng.fired += 1
        assert rng.window(prev, prev + 1) == prev + 1
    assert rng.window(4, 5) is None


def test_injector_loader_raise_consumed():
    inj = FaultInjector("loader_raise@2")
    inj.check_loader(0, 1)                   # step 1: inert
    with pytest.raises(InjectedLoaderError):
        inj.check_loader(1, 2)
    inj.check_loader(1, 2)                   # consumed: the retry succeeds
    assert inj.summary() == {"loader_raise": 1}


# ------------------------------------------------------- policy grammar

def test_parse_policy_grammar_and_defaults():
    pol = parse_policy("nan_loss=skip, loss_spike=rollback:4:0.25,"
                       "density_collapse=degrade")
    assert pol["nan_loss"].budget == 3 and pol["nan_loss"].param == 0.0
    assert pol["loss_spike"].budget == 4 and pol["loss_spike"].param == 0.25
    assert pol["density_collapse"].param == 50.0
    desc = describe_policy("loss_spike=rollback:4:0.25")
    assert "backoff=0.25s" in desc
    assert describe_policy(None).startswith("none")


@pytest.mark.parametrize("bad", [
    "nan_loss",                     # no '='
    "typo_rule=skip",               # unknown rule
    "nan_loss=retry",               # unknown action
    "nan_loss=skip,nan_loss=skip",  # rule mapped twice
    "nan_loss=skip:0",              # budget < 1
    "nan_loss=skip:x",              # non-int budget
    "nan_loss=skip:1:2:3",          # extra ':' parts
    ",",                            # empty
])
def test_parse_policy_rejects(bad):
    with pytest.raises(ValueError):
        parse_policy(bad)


def test_recovery_manager_budgets():
    rec = RecoveryManager(parse_policy(
        "nan_loss=skip:2,loss_spike=rollback:1,density_collapse=degrade:1"))
    assert not rec.claim({"rule": "residual_blowup"})   # unmapped rule
    # skip: budget bounds CONSECUTIVE skips, a clean step resets
    assert rec.claim({"rule": "nan_loss"})
    rec.consecutive_skips = 2                # as the trainer's apply would
    assert not rec.claim({"rule": "nan_loss"})
    rec.note_ok()
    assert rec.claim({"rule": "nan_loss"})
    # rollback: per-rule total budget
    assert rec.claim({"rule": "loss_spike"})
    rec.rollback_uses["loss_spike"] = 1
    assert not rec.claim({"rule": "loss_spike"})
    # degrade: claims while already degraded stand but queue nothing
    assert rec.claim({"rule": "density_collapse"})
    n_pending = len(rec.pending)
    rec.degraded = True
    assert rec.claim({"rule": "density_collapse"})
    assert len(rec.pending) == n_pending
    assert [spec.action for _, spec in rec.pop_pending()] == \
        ["skip", "skip", "rollback", "degrade"]
    assert rec.pending == []


# ------------------------------------------------------ guard and retry

def test_preemption_guard_flag_and_restore():
    before = signal.getsignal(signal.SIGTERM)
    with PreemptionGuard() as g:
        assert g.install() is g              # idempotent
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 5.0
        while not g.triggered and time.time() < deadline:
            time.sleep(0.01)                 # delivery is async
        assert g.triggered and g.signum == signal.SIGTERM
    assert signal.getsignal(signal.SIGTERM) == before


def test_retry_call_backoff_and_reraise():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise IOError("transient")
        return "ok"

    assert retry_call(flaky, retries=3, delay=0.0) == "ok"
    assert len(calls) == 3

    def always():
        calls.append(1)
        raise KeyError("hard")

    calls.clear()
    with pytest.raises(KeyError):
        retry_call(always, retries=2, delay=0.0)
    assert len(calls) == 3                   # 1 try + 2 retries

    calls.clear()
    with pytest.raises(ValueError):          # not in the retry filter
        retry_call(lambda: (_ for _ in ()).throw(ValueError("no")),
                   retries=3, delay=0.0, exceptions=(IOError,))


# ------------------------------------------------- checkpoint integrity

def _tiny_state(scale=1.0):
    return {
        "w": np.arange(64, dtype=np.float32).reshape(8, 8) * scale,
        "step": np.asarray(int(scale), np.int32),
    }


def test_checkpoint_integrity_roundtrip_and_mismatch(tmp_path):
    d = str(tmp_path / "ckpt")
    mgr = CheckpointManager(d, config_hash="aaaa")
    mgr.save(1, _tiny_state(1.0))
    mgr.save(2, _tiny_state(2.0))
    assert mgr.all_steps() == [1, 2]
    assert os.path.exists(os.path.join(d, "integrity-2.json"))
    mgr.close()

    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
        _tiny_state())
    # clean restore: latest step, verified
    same = CheckpointManager(d, config_hash="aaaa")
    got = same.restore(template)
    assert same.last_restored_step == 2
    np.testing.assert_array_equal(got["w"], _tiny_state(2.0)["w"])
    same.close()
    # config mismatch: refused with the escape hatch named, no fallback
    other = CheckpointManager(d, config_hash="bbbb")
    with pytest.raises(CheckpointMismatch, match="allow-ckpt-mismatch"):
        other.restore(template)
    got = other.restore(template, allow_mismatch=True)
    assert np.asarray(got["step"]) == 2
    other.close()
    # structure mismatch: a different treedef/shape is refused too
    bad_template = {"w": jax.ShapeDtypeStruct((4, 4), np.float32),
                    "step": jax.ShapeDtypeStruct((), np.int32)}
    assert state_digest(bad_template) != state_digest(template)
    strict = CheckpointManager(d, config_hash="aaaa")
    with pytest.raises(CheckpointMismatch, match="digest"):
        strict.restore(bad_template)
    strict.close()


def test_two_processes_write_one_steps_sidecar(tmp_path, monkeypatch):
    """The processes of a run share the checkpoint directory and write the
    same sidecar. One is overtaken between its write and its rename: with
    one temporary name for both, its rename found the file gone (PR 35:
    ``test_multihost.py::test_two_process_distributed_gtopk`` failed so in
    two whole runs of three)."""
    from gtopkssgd_tpu.utils import checkpoint

    d = str(tmp_path / "ckpt")
    slow, fast = (CheckpointManager(d, config_hash="aaaa") for _ in range(2))
    replace, overtaken = os.replace, []

    def overtaking(src, dst):
        if not overtaken:
            overtaken.append(src)
            with monkeypatch.context() as other:
                other.setattr(checkpoint.os, "getpid", lambda: os.getppid())
                fast._write_integrity(2, _tiny_state(2.0))
        replace(src, dst)

    monkeypatch.setattr(checkpoint.os, "replace", overtaking)
    slow._write_integrity(2, _tiny_state(2.0))
    assert overtaken and slow._read_integrity(2)["step"] == 2
    assert sorted(os.listdir(d)) == ["integrity-2.json"]
    slow.close()
    fast.close()


def test_corrupt_latest_falls_back_to_previous_step(tmp_path):
    d = str(tmp_path / "ckpt")
    mgr = CheckpointManager(d, config_hash="aaaa")
    mgr.save(1, _tiny_state(1.0))
    mgr.save(2, _tiny_state(2.0))
    mgr.close()
    assert corrupt_checkpoint_dir(os.path.join(d, "2")) > 0

    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
        _tiny_state())
    mgr = CheckpointManager(d, config_hash="aaaa")
    got = mgr.restore(template)              # torn latest -> previous
    assert mgr.last_restored_step == 1
    np.testing.assert_array_equal(got["w"], _tiny_state(1.0)["w"])
    # with EVERY step corrupt there is nothing to fall back to
    corrupt_checkpoint_dir(os.path.join(d, "1"))
    fresh = CheckpointManager(d, config_hash="aaaa")
    with pytest.raises(RuntimeError, match="no restorable checkpoint"):
        fresh.restore(template)
    fresh.close()
    mgr.close()


def test_injector_corrupts_latest_step_dir_once(tmp_path):
    d = str(tmp_path / "ckpt")
    for step, size in ((3, 256), (7, 256)):
        os.makedirs(os.path.join(d, str(step)))
        with open(os.path.join(d, str(step), "data.bin"), "wb") as fh:
            fh.write(b"x" * size)
    inj = FaultInjector("corrupt_ckpt@latest")
    assert inj.maybe_corrupt_ckpt(d)
    assert os.path.getsize(os.path.join(d, "7", "data.bin")) == 16
    assert os.path.getsize(os.path.join(d, "3", "data.bin")) == 256
    assert not inj.maybe_corrupt_ckpt(d)     # @latest fires once
    assert inj.summary() == {"corrupt_ckpt": 1}


# --------------------------------------------------- trainer e2e (mesh)

def test_nan_skip_restores_state_bit_identical(tmp_path):
    """An injected NaN at step 2 claimed by nan_loss=skip must leave the
    trainer EXACTLY at its post-step-1 state: params, momentum, step
    counter, and the error-feedback residual all bit-identical to a run
    that never dispatched step 2 at all."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    base = dict(
        dnn="resnet20", batch_size=4, nworkers=2,
        compression="gtopk_layerwise", density=0.01, seed=42,
        log_interval=1, obs_interval=1, eval_batches=1, max_epochs=1,
    )
    with Trainer(TrainConfig(**base)) as a:
        a.train(1)
        clean = jax.device_get((a.state.params, a.state.opt_state))
    out = str(tmp_path / "chaos")
    with Trainer(TrainConfig(**base, obs_halt_on="error",
                             inject="nan_grad@2",
                             recover_policy="nan_loss=skip",
                             out_dir=out)) as b:
        b.train(2)                           # dispatch 2 is poisoned+skipped
        assert int(b.state.step) == 1
        assert b.recovery.n_recoveries == 1
        chaos = jax.device_get((b.state.params, b.state.opt_state))
        b.finalize_resilience("completed")
    for la, lb in zip(jax.tree.leaves(clean), jax.tree.leaves(chaos)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    kinds = [r["kind"] for r in _records(out)]
    assert "inject" in kinds and "recovery" in kinds


@pytest.mark.slow  # 3 full dist_trainer runs (~30 s on the 1-core host)
def test_preempt_emergency_save_then_exact_resume(tmp_path):
    """Injected SIGTERM after step 2 -> emergency save -> exit 45; a
    --resume run (note: WITHOUT --inject — resilience knobs are excluded
    from checkpoint identity, or no chaos run could ever be resumed
    cleanly) replays steps 3-4 with losses bit-identical to the
    uninterrupted trace."""
    from gtopkssgd_tpu import dist_trainer

    ref = str(tmp_path / "ref")
    assert dist_trainer.main(
        CANON + ["--num-iters", "4", "--out-dir", ref]) == 0
    run = str(tmp_path / "run")
    rc = dist_trainer.main(CANON + [
        "--num-iters", "4", "--inject", "preempt@2", "--out-dir", run])
    assert rc == PREEMPT_EXIT_CODE
    recs = _records(run)
    saves = [r for r in recs if r["kind"] == "recovery"
             and r.get("action") == "emergency_save"]
    assert [r["step"] for r in saves] == [2]
    assert any(r.get("final_status") == "preempted" for r in recs)
    assert dist_trainer.main(
        CANON + ["--num-iters", "2", "--resume", "--out-dir", run]) == 0
    ref_loss, run_loss = _train_losses(ref), _train_losses(run)
    for step in (3, 4):
        assert run_loss[step] == ref_loss[step]


def test_skip_budget_exhaustion_halts_and_reports(tmp_path):
    """A PERSISTENT fault (NaN every step) burns the consecutive-skip
    budget and then falls through to the existing halt semantics: the
    run must NOT limp forever. ``report recovery`` renders the record
    trail of the dead run."""
    from gtopkssgd_tpu import dist_trainer
    from gtopkssgd_tpu.obs import report

    out = str(tmp_path / "run")
    rc = dist_trainer.main(CANON + [
        "--num-iters", "5", "--inject", "nan_grad@1-99",
        "--recover-policy", "nan_loss=skip:2", "--obs-halt-on", "error",
        "--out-dir", out])
    assert rc == HALT_EXIT_CODE
    recs = _records(out)
    skips = [r for r in recs if r["kind"] == "recovery"
             and r.get("action") == "skip"]
    assert [r["consecutive"] for r in skips] == [1, 2]
    summary = [r for r in recs if r.get("action") == "summary"]
    assert summary and summary[-1]["final_status"] == "halted"
    assert report.main(["recovery", out]) == 0


@pytest.mark.slow  # 2 full dist_trainer runs; the tier-1 equivalents are
# the gate smoke's chaos sub-run (exit 0 + structure, via test_obs) and
# test_skip_budget_exhaustion (claim-refusal -> exit 44)
def test_chaos_run_completes_only_with_policy(tmp_path):
    """The acceptance pair: the same injected NaN exits 0 when a skip
    policy claims it and HALT_EXIT_CODE when no policy is configured."""
    from gtopkssgd_tpu import dist_trainer
    from gtopkssgd_tpu.obs.report import summarize_recovery

    good = str(tmp_path / "good")
    rc = dist_trainer.main(CANON + [
        "--num-iters", "3", "--inject", "nan_grad@2",
        "--recover-policy", "nan_loss=skip", "--obs-halt-on", "error",
        "--out-dir", good])
    assert rc == 0
    s = summarize_recovery(_records(good))
    assert s["final_status"] == "completed" and s["n_recoveries"] == 1
    assert s["events_claimed"] == 1 and s["events_unclaimed"] == 0
    bare = str(tmp_path / "bare")
    rc = dist_trainer.main(CANON + [
        "--num-iters", "3", "--inject", "nan_grad@2",
        "--obs-halt-on", "error", "--out-dir", bare])
    assert rc == HALT_EXIT_CODE


@pytest.mark.slow  # compiles the dense-fallback executable (~1 min cold)
def test_degrade_swaps_to_dense_and_resumes_sparse(tmp_path):
    """degrade flips the train step to the dense-allreduce fallback (the
    warm-up branch of the same update treedef) and re-enters sparse after
    the cooldown; the run keeps training throughout."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    out = str(tmp_path / "run")
    cfg = TrainConfig(
        dnn="resnet20", batch_size=4, nworkers=2,
        compression="gtopk_layerwise", density=0.01, seed=42,
        log_interval=1, obs_interval=1, eval_batches=1, max_epochs=1,
        obs_halt_on="error", recover_policy="density_collapse=degrade:1:2",
        out_dir=out)
    with Trainer(cfg) as t:
        t.train(1)
        # fire the policy through the real monitor hook (the rule's
        # trigger condition itself is pinned by test_obs)
        assert t.monitor.recovery({"rule": "density_collapse", "step": 1})
        t.train(2)                           # applies degrade, trains dense
        assert t._degraded
        t.train(3)                           # cooldown of 2 steps expires
        assert not t._degraded
        assert int(t.state.step) == 6
        t.finalize_resilience("completed")
    actions = [r.get("action") for r in _records(out)
               if r["kind"] == "recovery"]
    assert "degrade" in actions and "sparse_resume" in actions
