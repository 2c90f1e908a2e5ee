"""Trainer: end-to-end smoke on every workload family, 8-way SPMD gtopk
training, checkpoint round-trip with residual preservation, CLI parsing.

The reference's only integration test was "train to accuracy" (SURVEY.md
§4); these are the cheap equivalents: loss falls on synthetic data in a few
steps, replicated state stays consistent, resume is exact.
"""

import jax
import numpy as np
import pytest

from gtopkssgd_tpu.dist_trainer import build_argparser, config_from_args
from gtopkssgd_tpu.trainer import TrainConfig, Trainer


def small_cfg(**kw):
    base = dict(
        dnn="resnet20", batch_size=8, nworkers=1, log_interval=5,
        eval_batches=2, max_epochs=1,
    )
    base.update(kw)
    return TrainConfig(**base)


def test_single_worker_dense_loss_falls():
    t = Trainer(small_cfg())
    stats = t.train(15)
    first = t.metrics  # smoke: metrics object exists
    assert np.isfinite(stats["loss"])
    ev = t.test()
    assert "val_top1" in ev and 0.0 <= ev["val_top1"] <= 1.0
    assert "val_top5" in ev and ev["val_top5"] >= ev["val_top1"]


@pytest.mark.slow  # ~62 s: 15 8-way steps on the serial box. The 8-way
# SPMD mesh stays tier-1 via test_prefetch / test_hier / test_sharded_eval
# (all nworkers=8) and the gtopk trainer path via the 2-way tests here;
# multi-step loss behavior rides test_convergence.
def test_spmd_gtopk_8way_trains():
    t = Trainer(small_cfg(
        nworkers=8, compression="gtopk", density=0.01, batch_size=4, lr=0.05,
    ))
    s0 = t.train(3)
    s1 = t.train(12)
    assert np.isfinite(s1["loss"])
    assert s1["loss"] < s0["loss"] * 1.5  # no blow-up; usually falls
    assert int(t.state.step) == 15


def test_gradient_accumulation_steps():
    t = Trainer(small_cfg(nsteps_update=2, batch_size=4))
    stats = t.train(4)
    assert int(t.state.step) == 4
    assert np.isfinite(stats["loss"])


@pytest.mark.slow  # ~28 s: trains both arms 8 steps each. The spd guard
# rails stay tier-1 (test_steps_per_dispatch_rejects_ragged_num_iters,
# test_s2d_cli_flag_and_guard); bitwise spd-vs-per-step equivalence is
# the slow-tier property this pins.
def test_steps_per_dispatch_matches_per_step_path():
    """spd > 1 (lax.scan inside the dispatch) must train IDENTICALLY to
    the per-step path: same seed + same data stream -> same params. The
    per-step RNG folds state.step, which increments inside the scan, so
    dropout/selection draws line up step for step. Covers the sparse
    path (gtopk) + multi-worker collectives + error-feedback residual
    state threading through the scan."""
    kw = dict(nworkers=2, compression="gtopk", density=0.01,
              batch_size=4, lr=0.05, prefetch=0)
    a = Trainer(small_cfg(**kw))
    a.train(8)
    b = Trainer(small_cfg(steps_per_dispatch=4, **kw))
    b.train(8)
    assert int(b.state.step) == 8
    pa = jax.tree.leaves(a.state.params)
    pb = jax.tree.leaves(b.state.params)
    for la, lb in zip(pa, pb):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=2e-5, atol=2e-6)
    ra = np.asarray(jax.tree.leaves(a.state.opt_state.residual)[0])
    rb = np.asarray(jax.tree.leaves(b.state.opt_state.residual)[0])
    np.testing.assert_allclose(ra, rb, rtol=2e-5, atol=2e-6)


def test_steps_per_dispatch_rejects_ragged_num_iters():
    t = Trainer(small_cfg(steps_per_dispatch=4))
    with pytest.raises(ValueError, match="multiple of"):
        t.train(6)


def test_ptb_trainer_carry_and_ppl():
    t = Trainer(small_cfg(dnn="lstm", batch_size=4, compression="gtopk",
                          density=0.05, eval_batches=2))
    stats = t.train(4)
    assert np.isfinite(stats["loss"])
    ev = t.test()
    assert "val_ppl" in ev and ev["val_ppl"] > 1.0


@pytest.mark.slow  # ~143 s: LSTM CTC compile + 2 steps on the 1-core host
def test_an4_trainer_ctc():
    t = Trainer(small_cfg(dnn="lstman4", batch_size=4, eval_batches=1))
    stats = t.train(2)
    assert np.isfinite(stats["loss"])
    ev = t.test()
    assert "val_cer" in ev and ev["val_cer"] >= 0.0
    assert "val_wer" in ev and ev["val_wer"] >= 0.0


@pytest.mark.slow  # ~308 s: 8-way LSTM steps with accumulation on 1 core
def test_an4_distributed_accumulated_shapes_stack():
    # Regression: AN4 batches must have fixed shapes so nworkers>1 and
    # nsteps_update>1 can stack them (variable per-batch padding used to
    # crash np.stack in the trainer's batch assembly).
    t = Trainer(small_cfg(dnn="lstman4", batch_size=2, nworkers=2,
                          nsteps_update=2, compression="gtopk",
                          density=0.05, eval_batches=1))
    stats = t.train(2)
    assert np.isfinite(stats["loss"])


def test_train_zero_iters_is_noop():
    t = Trainer(small_cfg())
    stats = t.train(0)
    assert stats["throughput"] == 0.0 and int(t.state.step) == 0


def test_checkpoint_roundtrip_preserves_residual(tmp_path):
    cfg = small_cfg(compression="gtopk", density=0.05,
                    out_dir=str(tmp_path / "run"))
    t = Trainer(cfg)
    t.train(5)
    t.save()
    # one device: the residual is the leaf form's slabs, together N long
    slabs = [np.asarray(r) for r in t.state.opt_state.residual]
    assert sum(r.size for r in slabs) == t.num_params
    assert any((r != 0).any() for r in slabs)  # error feedback accumulated
    t2 = Trainer(cfg)
    assert t2.restore()
    for got, want in zip(t2.state.opt_state.residual, slabs, strict=True):
        np.testing.assert_array_equal(np.asarray(got), want)
    assert int(t2.state.step) == 5
    # resumed training continues without error
    t2.train(2)
    assert int(t2.state.step) == 7


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("flags", [
    dict(), dict(momentum_correction=True),
    dict(obs_counters=True, obs_layers=True)],
    ids=["plain", "correction", "obs_layers"])
def test_flat_checkpoint_of_one_device_restores_into_the_leaf_form(
        tmp_path, flags):
    """A one-device checkpoint from before the leaf form (the residual, its
    v and u and the age buffer as [N] vectors; a sidecar that names no
    form) restores into the slabs, one host-side reshape, and the run
    continues bit-equal to an uninterrupted one."""
    from gtopkssgd_tpu.optimizer import flat_residual

    cfg = small_cfg(compression="gtopk", density=0.05,
                    out_dir=str(tmp_path / "run"), **flags)
    whole = Trainer(small_cfg(compression="gtopk", density=0.05, **flags))
    whole.train(5)
    t = Trainer(cfg)
    t.train(3)
    opt, params = t.state.opt_state, t.state.params
    old = opt._replace(residual=flat_residual(opt.residual, params))
    if flags.get("obs_layers"):
        old = old._replace(telemetry=dict(
            opt.telemetry, age=flat_residual(opt.telemetry["age"], params)))
    n = t.num_params
    assert all(r.shape == (n,) for r in jax.tree.leaves(old.residual))
    t._ckpt.save(3, t.state._replace(opt_state=old),
                 meta={"residual_p": 1})          # the older sidecar
    t.close()
    t2 = Trainer(cfg)
    assert t2.restore() and int(t2.state.step) == 3
    for got, want in zip(_leaves(t2.state.opt_state), _leaves(opt),
                         strict=True):
        np.testing.assert_array_equal(got, want)
    t2.train(2)
    for got, want in zip(_leaves(t2.state), _leaves(whole.state),
                         strict=True):
        np.testing.assert_array_equal(got, want)
    # and what it saves now names its form and restores as it is
    t2.save()
    assert t2._ckpt.sidecar_meta() == {"residual_p": 1,
                                       "residual_form": "slabs"}
    t3 = Trainer(cfg)
    assert t3.restore() and int(t3.state.step) == 5
    for got, want in zip(_leaves(t3.state), _leaves(whole.state),
                         strict=True):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("old_p,new_p", [(1, 4), (4, 1)])
def test_elastic_resume_between_one_device_and_a_mesh(tmp_path, old_p, new_p):
    """P = 1 holds slabs and a mesh [P, N] rows: an elastic resume
    re-partitions through the flat form both ways, and the pending
    gradient mass is conserved (the rows' sum, coordinate by coordinate)."""
    from gtopkssgd_tpu.optimizer import flat_residual

    def cfg(p):
        return small_cfg(nworkers=p, batch_size=4, compression="gtopk",
                         density=0.05, elastic=True,
                         out_dir=str(tmp_path / "run"))

    def rows(t):
        res = t.state.opt_state.residual
        if t.p == 1:
            assert isinstance(res, tuple)
            return np.asarray(flat_residual(res, t.state.params))[None]
        return np.asarray(res)

    t = Trainer(cfg(old_p))
    t.train(3)
    t.save()
    saved = rows(t)
    assert saved.shape == (old_p, t.num_params) and saved.any()
    t.close()
    t2 = Trainer(cfg(new_p))
    assert t2.restore() and int(t2.state.step) == 3
    got = rows(t2)
    assert got.shape == (new_p, t2.num_params)
    if new_p > old_p:
        np.testing.assert_array_equal(got[:old_p], saved)
        assert not got[old_p:].any()
    else:
        np.testing.assert_allclose(got.sum(0), saved.sum(0), rtol=1e-6,
                                   atol=1e-7)
    t2.train(2)
    assert int(t2.state.step) == 5


def test_residual_sharding_multiworker_roundtrip(tmp_path):
    """The error-feedback residual is per-device state: it must be carried
    as a [P, N] leaf (not collapsed to device 0's copy), genuinely differ
    across devices, and survive a checkpoint round-trip in full — while the
    params stay bit-identical on every device (replica consistency)."""
    cfg = small_cfg(nworkers=4, batch_size=4, compression="gtopk",
                    density=0.05, out_dir=str(tmp_path / "run"))
    t = Trainer(cfg)
    t.train(5)
    res = np.asarray(t.state.opt_state.residual)
    assert res.shape[0] == 4 and res.shape[1] == t.num_params
    # each device sees different data, so residuals must differ...
    assert any((res[0] != res[i]).any() for i in range(1, 4))
    # ...while the replicated params are bit-identical on every device
    leaf = jax.tree.leaves(t.state.params)[0]
    shards = [np.asarray(s.data) for s in leaf.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)
    t.save()
    t2 = Trainer(cfg)
    assert t2.restore()
    np.testing.assert_array_equal(np.asarray(t2.state.opt_state.residual), res)
    t2.train(2)
    assert int(t2.state.step) == 7


def test_cli_flags_match_reference_names():
    args = build_argparser().parse_args([
        "--dnn", "vgg16", "--density", "0.001", "--compression", "gtopk",
        "--nworkers", "4", "--batch-size", "16", "--nsteps-update", "2",
        "--max-epochs", "3",
    ])
    cfg = config_from_args(args)
    assert cfg.dnn == "vgg16" and cfg.density == 0.001
    assert cfg.compression == "gtopk" and cfg.nworkers == 4
    assert cfg.nsteps_update == 2 and cfg.max_epochs == 3


def test_per_dataset_defaults_resolve():
    cfg = TrainConfig(dnn="lstm").resolved()
    assert cfg.dataset == "ptb" and cfg.clip_grad_norm == 0.25
    cfg = TrainConfig(dnn="resnet50").resolved()
    assert cfg.dataset == "imagenet" and cfg.lr == 0.1


@pytest.mark.slow  # ~60 s: one real ResNet-50 compile+step. The uint8
# pipeline dtype contract stays tier-1 in tests/test_data.py and the
# on-device normalization consumer in test_real_data's decode tests;
# ResNet-50 shapes stay covered by test_models.
def test_imagenet_uint8_wire_trains_one_step():
    """End-to-end through the uint8 wire format: the ImageNet pipeline
    ships raw pixels, the jitted step normalizes on device — one real
    ResNet-50 step + eval must produce finite losses. (The pipelines'
    dtype is pinned in tests/test_data.py; this pins the consumer.)"""
    import numpy as np

    with Trainer(TrainConfig(
        dnn="resnet50", batch_size=2, nworkers=1, compression="gtopk",
        density=0.01, max_epochs=1, log_interval=1, eval_batches=1,
    )) as t:
        stats = t.train(1)
        assert np.isfinite(stats["loss"]), stats
        ev = t.test()
        assert np.isfinite(ev["val_loss"]) and "val_top5" in ev


@pytest.mark.slow  # ~200 s: trains across the warmup boundary on 1 core
def test_dense_warmup_and_lr_ramp_cross_boundary():
    """Warm-up knobs (reference C6 settings.py): dense-communication phase
    for the first N epochs of a sparse run, plus a linear LR ramp — one
    jitted step covers both phases (no recompile at the switch), and the
    residual stays zeros until the sparse phase begins."""
    t = Trainer(small_cfg(
        nworkers=4, compression="gtopk", density=0.01, batch_size=4,
        dense_warmup_epochs=1, warmup_epochs=1, max_epochs=4,
    ))
    spe = t.steps_per_epoch
    # LR ramp: base/10 at step 0, base at the end of warmup.
    sched = t._lr_schedule()
    base = t.cfg.lr
    np.testing.assert_allclose(float(sched(0)), 0.1 * base, rtol=1e-5)
    assert float(sched(spe // 2)) < base
    np.testing.assert_allclose(float(sched(spe)), base, rtol=1e-5)

    # Train across the warmup boundary in one Trainer (same jit).
    t.train(spe)  # dense-communication phase
    res_warm = np.asarray(t.state.opt_state.residual)
    assert not res_warm.any(), "residual must stay zero during dense warmup"
    stats = t.train(2)  # sparse phase begins
    assert np.isfinite(stats["loss"])
    assert np.asarray(t.state.opt_state.residual).any(), (
        "error feedback should start after warmup"
    )


def test_warmup_cli_flags():
    args = build_argparser().parse_args([
        "--warmup-epochs", "2", "--dense-warmup-epochs", "3",
    ])
    cfg = config_from_args(args)
    assert cfg.warmup_epochs == 2 and cfg.dense_warmup_epochs == 3


@pytest.mark.slow  # ~42 s: multi-epoch fit() loop; the checkpoint
# save/resume contract stays tier-1 via
# test_checkpoint_roundtrip_preserves_residual and the layerwise/
# momentum-correction roundtrip tests
def test_fit_epoch_loop_with_checkpoint(tmp_path, monkeypatch):
    """fit() (reference dist_trainer main loop): epoch-driven train + eval +
    checkpoint each epoch; a fresh Trainer resumes into the NEXT epoch."""
    from gtopkssgd_tpu.data import cifar

    # Shrink the synthetic corpus so an epoch is 8 optimizer steps; a
    # distinct seed keeps the lru_cached full-size corpus of other tests,
    # and clearing the cache afterwards keeps the 128-sample corpus from
    # leaking to any later test that happens to share the seed.
    monkeypatch.setattr(cifar, "SYNTH_TRAIN", 128)
    cifar._synthetic.cache_clear()
    try:
        _run_fit(tmp_path)
    finally:
        cifar._synthetic.cache_clear()


def _run_fit(tmp_path):
    cfg = small_cfg(
        nworkers=4, batch_size=4, compression="gtopk", density=0.01,
        max_epochs=2, eval_batches=1, out_dir=str(tmp_path), seed=123,
    )
    with Trainer(cfg) as t:
        spe = t.steps_per_epoch
        assert spe == 8
        stats = t.fit()
        assert int(t.state.step) == 2 * spe
        assert np.isfinite(stats["loss"]) and "val_top1" in stats
    with Trainer(cfg) as t2:
        assert t2.restore()
        assert int(t2.state.step) == 2 * spe
        # fit() from a fully-trained checkpoint is a no-op, not a retrain.
        t2.fit()
        assert int(t2.state.step) == 2 * spe


def test_sharded_eval_matches_sequential_and_batches_groups():
    """test() on a p>1 mesh shards the val stream P('dp') (TPU-first eval
    — the reference evaluated rank-0-only, SURVEY.md §3.5): metrics must
    equal the sequential single-device path exactly (same batches, same
    host-side weighting, pad shards of a partial tail group excluded),
    and the number of device dispatches must be ceil(nbatches / P) — the
    structural 1/P walltime property, asserted without timing flakiness.
    eval_batches=5 on an 8-way mesh exercises the pad path (one group,
    3 pad shards)."""
    cfg8 = small_cfg(nworkers=8, batch_size=4, eval_batches=5,
                     compression="gtopk", density=0.01)
    cfg1 = small_cfg(nworkers=1, batch_size=4, eval_batches=5)
    t8, t1 = Trainer(cfg8), Trainer(cfg1)
    assert t8._eval_sharded and not t1._eval_sharded

    calls = {"n": 0}
    inner = t8._eval_step

    def counting(*a):
        calls["n"] += 1
        return inner(*a)

    t8._eval_step = counting
    ev8, ev1 = t8.test(), t1.test()
    assert calls["n"] == 1  # ceil(5 / 8)
    for k in ("val_loss", "val_top1", "val_top5"):
        np.testing.assert_allclose(ev8[k], ev1[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)

    # two full groups + a partial one
    t8.cfg.eval_batches = 17
    t1.cfg.eval_batches = 17
    calls["n"] = 0
    ev8, ev1 = t8.test(), t1.test()
    assert calls["n"] == 3  # ceil(17 / 8)
    np.testing.assert_allclose(ev8["val_loss"], ev1["val_loss"],
                               rtol=1e-5, atol=1e-6)


def test_sharded_eval_an4_cer_path():
    """AN4 eval (CER/WER via greedy decode) rides the sharded path too:
    per-shard logits come back [P, B, T, V] and the host-side error
    counting sees only the real (non-pad) shards."""
    t = Trainer(small_cfg(dnn="lstman4", batch_size=2, nworkers=2,
                          compression="gtopk", density=0.05,
                          eval_batches=3))
    assert t._eval_sharded
    ev = t.test()
    assert np.isfinite(ev["val_loss"])
    assert 0.0 <= ev["val_cer"] and ev["val_wer"] >= 0.0


def test_ptb_eval_stays_sequential():
    """The PTB LSTM threads a BPTT carry through the val stream in order
    — semantically serial, so it must keep the sequential eval path even
    on a multi-device mesh."""
    t = Trainer(small_cfg(dnn="lstm", batch_size=4, nworkers=4,
                          compression="gtopk", density=0.05,
                          eval_batches=2))
    assert not t._eval_sharded
    ev = t.test()
    assert ev["val_ppl"] > 1.0


def test_s2d_cli_flag_and_guard():
    """--s2d plumbs to TrainConfig.space_to_depth; a non-resnet50 model
    rejects it with a clean error instead of a constructor TypeError."""
    args = build_argparser().parse_args(
        ["--dnn", "resnet50", "--s2d", "--nworkers", "1"])
    cfg = config_from_args(args)
    assert cfg.space_to_depth
    bad = small_cfg(space_to_depth=True)  # dnn=resnet20
    with pytest.raises(ValueError, match="resnet50 stem"):
        Trainer(bad)
