"""DGC momentum correction (`momentum_correction=True`): unit invariants.

TPU extension (arXiv:1712.01887 §3.1-3.2 — not reference parity: the
reference runs torch momentum-SGD on the sparse GLOBAL update). Velocity
``u = m*u + g`` accumulates locally BEFORE selection, the accumulated
velocity ``v += u`` is what top-k reads, and momentum factor masking
zeroes u at the LOCAL selection (while the error-feedback repair returns
a globally-rejected pick's VALUE to v — the measured semantics; see
test_correction_masks_at_local_selection). Pinned here:

  * 3-step numpy oracle of the v/u recursions + masking at p=1;
  * the dense warm-up phase is ALGEBRAICALLY classic momentum-SGD on the
    mean gradient (mean is linear in u) — bit-comparable to the dense
    baseline until the phase switch, for flat and layerwise alike;
  * 8-way replica consistency + convergence at low density;
  * construction-time rejection of meaningless combinations;
  * Trainer integration: the {"v","u"} residual dict rides the per-device
    plumbing and survives a checkpoint round-trip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import pytest

from gtopkssgd_tpu.optimizer import flat_residual, gtopk_sgd
from gtopkssgd_tpu.parallel import make_mesh

PDEV = 8


def small_params():
    return {"w": jnp.zeros((32,)), "b": jnp.zeros((5,))}


def test_correction_p1_matches_dgc_oracle():
    n, density, m = 37, 0.2, 0.5
    params = small_params()
    tx = gtopk_sgd(1.0, momentum=m, compression="gtopk", density=density,
                   axis_name=None, momentum_correction=True)
    state = tx.init(params)
    assert set(state.residual.keys()) == {"v", "u"}

    rng = np.random.default_rng(0)
    v, u = np.zeros(n), np.zeros(n)
    k = int(np.ceil(density * n))
    upd = jax.jit(tx.update)
    for _ in range(3):
        g = rng.standard_normal(n).astype(np.float32)
        grads = {"w": jnp.asarray(g[:32]), "b": jnp.asarray(g[32:])}
        updates, state = upd(grads, state, params)
        # tree.flatten order is b, w
        gg = np.concatenate([g[32:], g[:32]])
        u = m * u + gg
        acc = v + u
        sel = np.argsort(-np.abs(acc))[:k]
        applied = np.zeros(n)
        applied[sel] = acc[sel]
        v = acc.copy()
        v[sel] = 0.0
        u[sel] = 0.0  # momentum factor masking
        got = -np.concatenate(
            [np.asarray(updates["b"]), np.asarray(updates["w"])])
        np.testing.assert_allclose(got, applied, rtol=1e-5, atol=1e-6)
        # no mesh axis named: the state holds slabs (optimizer.py's slabs form)
        res = flat_residual(state.residual, params)
        np.testing.assert_allclose(np.asarray(res["v"]), v,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(res["u"]), u,
                                   rtol=1e-5, atol=1e-6)


def _spmd_step(tx, mesh):
    def step(params, state, grads):
        grads = jax.tree.map(lambda g: g[0], grads)
        updates, state = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    return jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P(), P("dp")),
        out_specs=(P(), P()), check_vma=False))


@pytest.mark.parametrize("mode", ["gtopk", "gtopk_layerwise"])
def test_correction_warmup_phase_is_classic_momentum(mode):
    """mean_i(m*u_i + g_i) == m*mean(u) + mean(g): the correction's dense
    warm-up phase IS momentum-SGD on the mean gradient, so it must track
    the dense baseline until the phase switch and diverge after."""
    params = small_params()
    mesh = make_mesh(PDEV)
    rng = np.random.default_rng(4)
    grads = jax.tree.map(
        lambda p: jnp.asarray(
            rng.standard_normal((PDEV,) + p.shape), jnp.float32), params)

    tx_c = gtopk_sgd(0.1, momentum=0.9, compression=mode, density=0.05,
                     axis_name="dp", axis_size=PDEV, warmup_dense_steps=2,
                     momentum_correction=True)
    tx_d = gtopk_sgd(0.1, momentum=0.9, compression="dense",
                     axis_name="dp", axis_size=PDEV)
    s_c = jax.jit(tx_c.init)(params)
    s_d = jax.jit(tx_d.init)(params)
    step_c, step_d = _spmd_step(tx_c, mesh), _spmd_step(tx_d, mesh)
    p_c = p_d = params
    for i in range(3):
        p_c, s_c = step_c(p_c, s_c, grads)
        p_d, s_d = step_d(p_d, s_d, grads)
        same = all(
            np.allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)
            for a, b in zip(jax.tree.leaves(p_c), jax.tree.leaves(p_d)))
        assert same == (i < 2), f"step {i}: warm-up phase mismatch"


def test_correction_spmd_converges_replicated():
    n, per_dev = 32, 16
    rng = np.random.default_rng(3)
    w_true = rng.standard_normal(n).astype(np.float32)
    X = rng.standard_normal((PDEV, per_dev, n)).astype(np.float32)
    y = X @ w_true

    params = {"w": jnp.zeros((n,))}
    mesh = make_mesh(PDEV)
    tx = gtopk_sgd(0.03, momentum=0.5, compression="gtopk", density=0.1,
                   axis_name="dp", axis_size=PDEV, momentum_correction=True)
    state = jax.jit(tx.init)(params)

    def step(params, state, Xs, ys):
        def loss(p):
            r = Xs[0] @ p["w"] - ys[0]
            return 0.5 * jnp.mean(r * r)
        grads = jax.grad(loss)(params)
        updates, state = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    smapped = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P(), P("dp"), P("dp")),
        out_specs=(P(), P()), check_vma=False))

    def global_loss(params):
        r = X.reshape(-1, n) @ np.asarray(params["w"]) - y.reshape(-1)
        return 0.5 * float(np.mean(r * r))

    l0 = global_loss(params)
    for _ in range(60):
        params, state = smapped(params, state, jnp.asarray(X), jnp.asarray(y))
    assert global_loss(params) < 0.3 * l0
    for leaf in jax.tree.leaves(params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)


def _mask_semantics_fixture():
    """8-way construction with a known global accept set: device d's
    gradient peaks at coords {2d, 2d+1} with magnitude growing in d, so
    the global top-2 is {14, 15} (device 7's picks) and every other
    device's picks are globally rejected. Tie-free by construction."""
    n, k_density = 16, 2 / 16
    params = {"w": jnp.zeros((n,))}
    mesh = make_mesh(PDEV)
    g = np.zeros((PDEV, n), np.float32)
    for d in range(PDEV):
        g[d, 2 * d] = 10.0 + 2 * d
        g[d, 2 * d + 1] = 9.0 + 2 * d
    return n, k_density, params, mesh, g


def _run_one_masked_step(params, mesh, g, tx):
    state = jax.jit(tx.init)(params)

    def step(grads, state):
        _, s2 = tx.update({"w": grads[0]}, state, params)
        return s2.residual["v"][None], s2.residual["u"][None]

    v_all, u_all = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P("dp"), P()),
        out_specs=(P("dp"), P("dp")), check_vma=False))(
            jnp.asarray(g), state)
    return np.asarray(v_all), np.asarray(u_all)


def test_correction_masks_at_local_selection():
    """Pins the SHIPPED masking semantics (optimizer.py, measured design
    decision): the momentum factor mask follows the LOCAL selection, not
    the global accept set. A locally-picked but globally-rejected
    coordinate keeps its VALUE in the residual v (error-feedback repair)
    while its velocity u stays masked — restoring u as well double-tracks
    the same mass and diverges (restore_rejected_u_ablation entry of
    benchmarks/results/warmup_ab_cpu_mesh8.json)."""
    n, k_density, params, mesh, g = _mask_semantics_fixture()
    tx = gtopk_sgd(0.1, momentum=0.9, compression="gtopk",
                   density=k_density, axis_name="dp", axis_size=PDEV,
                   momentum_correction=True)
    v_all, u_all = _run_one_masked_step(params, mesh, g, tx)
    # device 7's picks {14, 15} ARE the global set: delivered, so both
    # the velocity and the residual slot are consumed.
    assert u_all[7, 14] == 0.0 and u_all[7, 15] == 0.0
    assert v_all[7, 14] == 0.0 and v_all[7, 15] == 0.0
    # device 0's picks {0, 1} were globally REJECTED: the repair returns
    # their VALUE to v (u = m*0 + g = g on step 1, and v selects from u),
    # but u is masked at the local selection and stays masked.
    np.testing.assert_allclose(v_all[0, :2], g[0, :2], rtol=1e-6)
    np.testing.assert_array_equal(u_all[0, :2], np.zeros(2))
    # un-picked coordinates are untouched everywhere (no stray masking):
    # device 0 never selected {14, 15} and contributed 0 mass there.
    assert v_all[0, 14] == 0.0 and u_all[0, 14] == 0.0


def test_correction_rejects_meaningless_combinations():
    for kw, msg in (
        (dict(compression="dense"), "sparse"),
        (dict(compression="gtopk", momentum=0.0), "momentum"),
        (dict(compression="gtopk", nesterov=True), "nesterov"),
    ):
        with pytest.raises(ValueError, match=msg):
            gtopk_sgd(0.1, momentum=kw.pop("momentum", 0.9),
                      axis_name=None, momentum_correction=True, **kw)


def test_correction_trainer_checkpoint_roundtrip(tmp_path):
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    cfg = TrainConfig(
        dnn="resnet20", batch_size=4, nworkers=4, log_interval=5,
        eval_batches=2, max_epochs=1, compression="gtopk", density=0.05,
        momentum_correction=True, out_dir=str(tmp_path / "run"),
    )
    t = Trainer(cfg)
    t.train(5)
    res = t.state.opt_state.residual
    assert set(res.keys()) == {"v", "u"}
    v, u = np.asarray(res["v"]), np.asarray(res["u"])
    assert v.shape[0] == 4 and u.shape == v.shape
    assert (u != 0).any() and (v != 0).any()
    t.save()
    t2 = Trainer(cfg)
    assert t2.restore()
    np.testing.assert_array_equal(
        np.asarray(t2.state.opt_state.residual["v"]), v)
    np.testing.assert_array_equal(
        np.asarray(t2.state.opt_state.residual["u"]), u)
    t2.train(2)
    assert int(t2.state.step) == 7


def test_correction_layerwise_combination_warns():
    """The layerwise x correction combination is measured worse than
    either parent and the round-3 masking ablations rule out a semantics
    fix (warmup_ab artifact: 0.250 combo vs 0.734/0.281 alone; restore-u
    collapses it to 0.094) — construction warns, citing the artifact."""
    with pytest.warns(UserWarning, match="warmup_ab"):
        gtopk_sgd(0.1, momentum=0.9, compression="gtopk_layerwise",
                  density=0.01, axis_name=None, momentum_correction=True)


def test_spike_recovery_via_error_feedback():
    """Regression pin for the observed in-vivo self-heal (round-4 VGG CPU
    probe, convergence_vgg16_cpu_mesh2.jsonl step 40->160: the corr arm
    blew up to loss 27.7 after a gradient spike and error feedback pulled
    it back to dense tracking). Synthetic reproduction: gtopk+corr SGD on
    a least-squares objective; one step receives a 100x gradient spike.
    Asserts (a) the spike visibly damages the iterate, (b) the run
    re-converges to match the clean run's loss within a bounded number of
    steps — the repair/EF path absorbing the injected mass rather than
    replaying it forever.
    """
    n, density, steps, spike_at = 256, 0.1, 200, 40
    rng = np.random.default_rng(3)
    target = rng.standard_normal(n).astype(np.float32)
    # the poison is a RANDOM direction (a corrupted batch), not a scaled
    # true gradient — on a deterministic quadratic a same-direction spike
    # is merely a beneficial overshoot
    spike_vec = 100.0 * np.random.default_rng(9).standard_normal(
        n).astype(np.float32)

    def run(spike: bool):
        params = {"w": jnp.zeros((n,))}
        # lr inside the EF-delay stability region: with density 0.1 a
        # coordinate waits ~10 steps between selections, and momentum
        # amplifies the batched replay by 1/(1-m) — lr*2*10/(1-0.9) must
        # stay < 2 or the CLEAN run diverges (observed at lr=0.05)
        tx = gtopk_sgd(0.003, momentum=0.9, compression="gtopk",
                       density=density, axis_name=None,
                       momentum_correction=True)
        state = tx.init(params)
        upd = jax.jit(tx.update)
        losses = []
        for t in range(steps):
            g = 2.0 * (np.asarray(params["w"]) - target)
            if spike and t == spike_at:
                g = g + spike_vec
            updates, state = upd({"w": jnp.asarray(g)}, state, params)
            params = optax.apply_updates(params, updates)
            losses.append(float(((np.asarray(params["w"]) - target) ** 2)
                                .mean()))
        return losses

    clean = run(False)
    spiked = run(True)
    # (a) the spike did real damage in the window after injection
    window = range(spike_at + 1, spike_at + 30)
    assert max(spiked[i] / clean[i] for i in window) > 2.0
    # (b) recovery: by the end the spiked run tracks the clean run again
    assert spiked[-1] < 2.0 * clean[-1] + 1e-4, (spiked[-1], clean[-1])
    # (c) the worst post-spike loss occurs near the spike, not at the end
    worst = max(range(spike_at, steps), key=lambda i: spiked[i])
    assert worst < spike_at + 30
