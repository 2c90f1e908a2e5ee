"""Distributed gTop-k optimizer: invariants + SPMD equivalences on 8 devices.

What the reference could only validate by training a full model to accuracy
(SURVEY.md §4 "convergence-as-test"), we pin down as unit invariants:

  * dense mode == plain optax SGD (single device and 8-way replicated);
  * error-feedback mass conservation: applied + residual' == grad + residual;
  * gtopk at density=1.0 == dense allreduce (the tree is lossless when k=N);
  * gtopk at low density still drives a least-squares loss down with
    bit-identical replicated params on every device.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from gtopkssgd_tpu.ops import scatter_add_dense
from gtopkssgd_tpu.optimizer import GTopKSGDState, gtopk_sgd
from gtopkssgd_tpu.parallel import make_mesh

PDEV = 8


def quad_params():
    return {"w": jnp.arange(1.0, 7.0), "b": jnp.ones((3,))}


def test_dense_mode_matches_plain_sgd():
    params = quad_params()
    grads = jax.tree.map(lambda p: 0.1 * p + 1.0, params)
    tx = gtopk_sgd(0.5, momentum=0.9, weight_decay=0.01, compression="dense",
                   axis_name=None)
    ref = optax.chain(optax.add_decayed_weights(0.01), optax.sgd(0.5, momentum=0.9))
    s, rs = tx.init(params), ref.init(params)
    for _ in range(3):
        u, s = tx.update(grads, s, params)
        ru, rs = ref.update(grads, rs, params)
        jax.tree.map(np.testing.assert_allclose, u, ru)


def test_error_feedback_mass_conservation():
    # applied update mass + new residual == accumulated gradient, elementwise.
    n, density = 64, 0.125
    params = {"w": jnp.zeros((n,))}
    tx = gtopk_sgd(1.0, momentum=0.0, compression="gtopk", density=density,
                   axis_name=None)
    state = tx.init(params)
    rng = np.random.default_rng(1)
    residual_before = np.asarray(state.residual)
    for step in range(4):
        g = rng.standard_normal(n).astype(np.float32)
        updates, state = tx.update({"w": jnp.asarray(g)}, state, params)
        # momentum=0, lr=1 => -update is exactly the applied dense gradient.
        applied = -np.asarray(updates["w"])
        acc = g + residual_before
        np.testing.assert_allclose(
            applied + np.asarray(state.residual), acc, rtol=1e-5, atol=1e-6
        )
        # exactly k entries applied
        assert (np.abs(applied) > 0).sum() == int(np.ceil(density * n))
        residual_before = np.asarray(state.residual)


def _spmd_step(tx, mesh):
    def step(params, state, grads):
        grads = jax.tree.map(lambda g: g[0], grads)  # drop the shard dim
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        return params, state

    return jax.jit(
        jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(), P(), P("dp")),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )


def test_gtopk_density1_equals_dense_psum():
    n = 40
    params = {"w": jnp.zeros((n,))}
    mesh = make_mesh(PDEV)
    rng = np.random.default_rng(2)
    grads = rng.standard_normal((PDEV, n)).astype(np.float32)

    outs = {}
    for mode, density in [("dense", 1.0), ("gtopk", 1.0), ("allgather", 1.0)]:
        tx = gtopk_sgd(0.1, momentum=0.0, compression=mode, density=density,
                       axis_name="dp", axis_size=PDEV)
        state = jax.jit(tx.init)(params)
        step = _spmd_step(tx, mesh)
        p, _ = step(params, state, {"w": jnp.asarray(grads)})
        outs[mode] = np.asarray(p["w"])

    np.testing.assert_allclose(outs["gtopk"], outs["dense"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs["allgather"], outs["dense"], rtol=1e-5, atol=1e-6)
    want = -0.1 * grads.mean(axis=0)
    np.testing.assert_allclose(outs["dense"], want, rtol=1e-5, atol=1e-6)


def test_gtopk_spmd_least_squares_converges_replicated():
    # P devices each hold a data shard of the same least-squares problem;
    # gtop-k at 10% density must still drive the global loss down and keep
    # params bit-identical on all devices (SPMD replica consistency — the
    # property the reference's global-topk broadcast exists to guarantee).
    n, per_dev = 32, 16
    rng = np.random.default_rng(3)
    w_true = rng.standard_normal(n).astype(np.float32)
    X = rng.standard_normal((PDEV, per_dev, n)).astype(np.float32)
    y = X @ w_true

    mesh = make_mesh(PDEV)
    tx = gtopk_sgd(0.03, momentum=0.5, compression="gtopk", density=0.1,
                   axis_name="dp", axis_size=PDEV)
    params = {"w": jnp.zeros((n,))}
    state = jax.jit(tx.init)(params)

    def loss_fn(params, xb, yb):
        pred = xb @ params["w"]
        return jnp.mean((pred - yb) ** 2)

    def step(params, state, xb, yb):
        loss, grads = jax.value_and_grad(loss_fn)(params, xb[0], yb[0])
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        return params, state, jax.lax.pmean(loss, "dp")

    spmd = jax.jit(
        jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(), P(), P("dp"), P("dp")),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )
    losses = []
    for _ in range(100):
        params, state, loss = spmd(params, state, jnp.asarray(X), jnp.asarray(y))
        losses.append(float(loss))
    assert losses[-1] < 0.05 * losses[0], losses[::10]


def test_clip_before_compression():
    n = 16
    params = {"w": jnp.zeros((n,))}
    tx = gtopk_sgd(1.0, momentum=0.0, compression="gtopk", density=1.0,
                   clip_grad_norm=1.0, axis_name=None)
    state = tx.init(params)
    g = np.zeros(n, np.float32)
    g[0] = 100.0
    updates, state = tx.update({"w": jnp.asarray(g)}, state, params)
    # clipped to unit norm before compression: applied grad ~ [1, 0, ...]
    np.testing.assert_allclose(-np.asarray(updates["w"])[0], 1.0, rtol=1e-4)


def test_state_is_checkpointable_pytree():
    # The residual must live in ordinary optimizer state (the reference lost
    # residuals on resume because they sat in a class attribute).
    params = quad_params()
    tx = gtopk_sgd(0.1, compression="gtopk", density=0.5, axis_name=None)
    state = tx.init(params)
    assert isinstance(state, GTopKSGDState)
    leaves = jax.tree.leaves(state)
    assert any(l.size == 9 for l in leaves)  # residual over 9 params
    # round-trips through flatten/unflatten (what Orbax does)
    flat, treedef = jax.tree.flatten(state)
    state2 = jax.tree.unflatten(treedef, flat)
    g = jax.tree.map(jnp.ones_like, params)
    u1, _ = tx.update(g, state, params)
    u2, _ = tx.update(g, state2, params)
    jax.tree.map(np.testing.assert_array_equal, u1, u2)


def test_dense_warmup_matches_dense_then_switches():
    """warmup_dense_steps=W (reference C6 warm-up trick): the first W steps
    of a sparse mode are bit-equal to the dense baseline with the residual
    untouched (zeros); step W switches to the sparse pipeline and error
    feedback begins."""
    n, W = 40, 2
    params = {"w": jnp.zeros((n,))}
    mesh = make_mesh(PDEV)
    rng = np.random.default_rng(7)
    grads = {"w": jnp.asarray(
        rng.standard_normal((PDEV, n)).astype(np.float32))}

    tx_w = gtopk_sgd(0.1, momentum=0.9, compression="gtopk", density=0.1,
                     axis_name="dp", axis_size=PDEV, warmup_dense_steps=W)
    tx_d = gtopk_sgd(0.1, momentum=0.9, compression="dense",
                     axis_name="dp", axis_size=PDEV)
    sw, sd = jax.jit(tx_w.init)(params), jax.jit(tx_d.init)(params)
    step_w, step_d = _spmd_step(tx_w, mesh), _spmd_step(tx_d, mesh)

    pw, pd = params, params
    for i in range(W):
        pw, sw = step_w(pw, sw, grads)
        pd, sd = step_d(pd, sd, grads)
        np.testing.assert_allclose(np.asarray(pw["w"]), np.asarray(pd["w"]),
                                   rtol=1e-6, atol=1e-7)
        assert not np.any(np.asarray(sw.residual)), f"residual dirty at {i}"

    # Step W: sparse pipeline activates. With momentum the dense-phase
    # buffer keeps every coordinate moving, so the sparse selection is
    # asserted via the residual: k = 10% of n coords selected => at least
    # the other 90% of the accumulated gradient mass lands in the residual.
    pw, sw = step_w(pw, sw, grads)
    assert np.any(np.asarray(sw.residual)), "error feedback never started"
    assert (np.abs(np.asarray(sw.residual)) > 0).sum() >= n - int(n * 0.1)


def test_warmup_rejected_for_negative():
    import pytest

    with pytest.raises(ValueError):
        gtopk_sgd(0.1, compression="gtopk", warmup_dense_steps=-1)


def test_dense_warmup_hier_matches_dense_scale():
    """Regression: in gtopk_hier mode the warm-up dense branch receives the
    SLICE-SUMMED gradient, so a full-axis psum over-counts by ici_size —
    the warm-up step must still equal the plain dense baseline exactly."""
    n = 40
    params = {"w": jnp.zeros((n,))}
    mesh = make_mesh(PDEV)
    rng = np.random.default_rng(11)
    grads = {"w": jnp.asarray(
        rng.standard_normal((PDEV, n)).astype(np.float32))}

    tx_h = gtopk_sgd(0.1, momentum=0.0, compression="gtopk_hier",
                     density=0.1, axis_name="dp", axis_size=PDEV,
                     hier_ici_size=4, warmup_dense_steps=1)
    tx_d = gtopk_sgd(0.1, momentum=0.0, compression="dense",
                     axis_name="dp", axis_size=PDEV)
    sh, sd = jax.jit(tx_h.init)(params), jax.jit(tx_d.init)(params)
    ph, _ = _spmd_step(tx_h, mesh)(params, sh, grads)
    pd, _ = _spmd_step(tx_d, mesh)(params, sd, grads)
    np.testing.assert_allclose(np.asarray(ph["w"]), np.asarray(pd["w"]),
                               rtol=1e-5, atol=1e-6)
