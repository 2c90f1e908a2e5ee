"""Layer-wise gTop-k (`compression='gtopk_layerwise'`): unit invariants.

TPU extension (arXiv:1911.08772 layer-wise-top-k lineage; the reference
always flattens — SURVEY.md §3.1 "flatten all param.grads into one
vector"). The mode keeps selection + error feedback per layer so the flat
[N] gradient never materializes; the collective is the unchanged gTop-k
hypercube over the concatenated per-layer sets. These tests pin:

  * per-leaf k_l = ceil(rho * n_l) selections at p=1, against a numpy
    per-leaf top-k oracle (including error-feedback mass conservation);
  * density=1.0 degenerates to the dense-psum mean (8-way);
  * 8-way SPMD: replicas stay bit-identical and a least-squares loss falls;
  * the dense warm-up phase bit-equals the dense baseline;
  * Trainer integration: per-device tuple residual, checkpoint round-trip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from gtopkssgd_tpu.ops import k_for_density
from gtopkssgd_tpu.optimizer import gtopk_sgd
from gtopkssgd_tpu.parallel import make_mesh

PDEV = 8


def tree_params():
    return {
        "conv": jnp.zeros((4, 8)),   # 32 elems -> k=4 at rho=0.125
        "bias": jnp.zeros((5,)),     # 5 elems  -> k=1
        "bn": jnp.zeros((2, 3)),     # 6 elems  -> k=1
    }


def rand_grads(rng, params, lead=()):
    return jax.tree.map(
        lambda p: jnp.asarray(
            rng.standard_normal(lead + p.shape), jnp.float32), params
    )


def test_layerwise_p1_matches_per_leaf_topk_oracle():
    density = 0.125
    params = tree_params()
    tx = gtopk_sgd(1.0, momentum=0.0, compression="gtopk_layerwise",
                   density=density, axis_name=None)
    state = tx.init(params)
    # residual is a pytree: one flat buffer per leaf, in tree.flatten order
    leaves = jax.tree.leaves(params)
    assert isinstance(state.residual, tuple)
    assert [r.shape for r in state.residual] == [(l.size,) for l in leaves]

    rng = np.random.default_rng(0)
    res_before = [np.zeros(l.size, np.float32) for l in leaves]
    upd = jax.jit(tx.update)
    for _ in range(3):
        grads = rand_grads(rng, params)
        updates, state = upd(grads, state, params)
        g_leaves = [np.asarray(g).reshape(-1) for g in jax.tree.leaves(grads)]
        u_leaves = [np.asarray(u).reshape(-1)
                    for u in jax.tree.leaves(updates)]
        for g, u, res, res_new in zip(
                g_leaves, u_leaves, res_before, state.residual):
            n = g.size
            k = k_for_density(n, density)
            acc = g + res
            applied = -u  # momentum=0, lr=1
            # exactly this leaf's k entries applied, and they are the
            # top-k of |acc| with their exact acc values
            nz = np.flatnonzero(np.abs(applied) > 0)
            assert len(nz) == k
            want_idx = np.argsort(-np.abs(acc))[:k]
            assert set(nz) == set(want_idx)
            np.testing.assert_allclose(applied[nz], acc[nz], rtol=1e-6)
            # error-feedback mass conservation per leaf
            np.testing.assert_allclose(
                applied + np.asarray(res_new), acc, rtol=1e-5, atol=1e-6)
        res_before = [np.asarray(r) for r in state.residual]


def _spmd_step(tx, mesh):
    def step(params, state, grads):
        grads = jax.tree.map(lambda g: g[0], grads)
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


def test_layerwise_density1_equals_dense_mean():
    params = tree_params()
    mesh = make_mesh(PDEV)
    rng = np.random.default_rng(2)
    grads = rand_grads(rng, params, lead=(PDEV,))
    tx = gtopk_sgd(0.1, momentum=0.0, compression="gtopk_layerwise",
                   density=1.0, axis_name="dp", axis_size=PDEV)
    state = jax.jit(tx.init)(params)
    p2, _ = _spmd_step(tx, mesh)(params, state, grads)
    for leaf, g in zip(jax.tree.leaves(p2), jax.tree.leaves(grads)):
        want = -0.1 * np.asarray(g).mean(axis=0)
        np.testing.assert_allclose(np.asarray(leaf), want,
                                   rtol=1e-5, atol=1e-6)


def test_layerwise_spmd_converges_replicated():
    # Two-leaf least-squares; rho low enough that each step is genuinely
    # sparse. Replica consistency = the property the global broadcast of
    # the reference exists to guarantee (SURVEY.md §2 parallelism).
    n1, n2, per_dev = 24, 8, 16
    rng = np.random.default_rng(3)
    w_true = rng.standard_normal(n1 + n2).astype(np.float32)
    X = rng.standard_normal((PDEV, per_dev, n1 + n2)).astype(np.float32)
    y = X @ w_true

    params = {"a": jnp.zeros((n1,)), "b": jnp.zeros((n2,))}
    mesh = make_mesh(PDEV)
    tx = gtopk_sgd(0.03, momentum=0.5, compression="gtopk_layerwise",
                   density=0.1, axis_name="dp", axis_size=PDEV)
    state = jax.jit(tx.init)(params)

    def loss_grads(params, Xs, ys):
        def loss(p):
            w = jnp.concatenate([p["a"], p["b"]])
            r = Xs @ w - ys
            return 0.5 * jnp.mean(r * r)
        return jax.grad(loss)(params)

    def step(params, state, Xs, ys):
        grads = loss_grads(params, Xs[0], ys[0])
        updates, state = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    smapped = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P("dp"), P("dp")),
        out_specs=(P(), P()),
        check_vma=False,
    ))

    def global_loss(params):
        w = np.concatenate([np.asarray(params["a"]), np.asarray(params["b"])])
        r = X.reshape(-1, n1 + n2) @ w - y.reshape(-1)
        return 0.5 * float(np.mean(r * r))

    l0 = global_loss(params)
    for _ in range(60):
        params, state = smapped(params, state, jnp.asarray(X), jnp.asarray(y))
    assert global_loss(params) < 0.3 * l0
    # error feedback is live: some rejected mass sits in the residual
    res = [np.asarray(r) for r in state.residual]
    assert any((r != 0).any() for r in res)
    # replica consistency: every device holds bit-identical params
    for leaf in jax.tree.leaves(params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)


def test_layerwise_warmup_phase_bit_equals_dense():
    params = tree_params()
    mesh = make_mesh(PDEV)
    rng = np.random.default_rng(4)
    grads = rand_grads(rng, params, lead=(PDEV,))

    tx_lw = gtopk_sgd(0.1, momentum=0.9, compression="gtopk_layerwise",
                      density=0.05, axis_name="dp", axis_size=PDEV,
                      warmup_dense_steps=2)
    tx_d = gtopk_sgd(0.1, momentum=0.9, compression="dense",
                     axis_name="dp", axis_size=PDEV)
    s_lw = jax.jit(tx_lw.init)(params)
    s_d = jax.jit(tx_d.init)(params)
    step_lw, step_d = _spmd_step(tx_lw, mesh), _spmd_step(tx_d, mesh)
    p_lw = p_d = params
    for i in range(3):
        p_lw, s_lw = step_lw(p_lw, s_lw, grads)
        p_d, s_d = step_d(p_d, s_d, grads)
        # Warmup steps compute the same math but not always the same BITS:
        # once the momentum trace is nonzero (step >= 1), XLA:CPU may
        # contract mu*trace + g into an FMA in one program and not the
        # other (the layerwise program carries a live lax.cond sparse
        # branch, so fusion decisions differ), a 1-ULP divergence
        # (observed 7.5e-9 on f32 params). So: warmup agrees to ULP-scale
        # tolerance, the first sparse step diverges by orders of
        # magnitude more.
        diff = max(
            float(np.abs(np.asarray(a) - np.asarray(b)).max())
            for a, b in zip(jax.tree.leaves(p_lw), jax.tree.leaves(p_d))
        )
        if i < 2:
            assert diff <= 1e-6, f"warmup step {i}: diff {diff}"
        else:
            assert diff > 1e-3, f"step {i}: sparse phase did not engage"


@pytest.mark.slow  # ~27 s: LSTM compile + 4 steps + eval. The layerwise
# selection semantics stay tier-1 via the oracle/density1/warmup tests
# above; the LSTM trainer path (carry + ppl eval) via
# test_ptb_trainer_carry_and_ppl; clip resolution is config-level and
# cheap to re-check there.
def test_layerwise_lstm_clip_before_compress_trains():
    """PTB/LSTM path under layerwise: per-leaf selection composes with the
    clip-BEFORE-compress ordering (SURVEY.md §3.4 — the global norm is a
    sum of per-leaf sums, no concatenation) and the BPTT carry."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    t = Trainer(TrainConfig(
        dnn="lstm", batch_size=4, nworkers=1, log_interval=5,
        eval_batches=2, max_epochs=1, compression="gtopk_layerwise",
        density=0.05,
    ))
    stats = t.train(4)
    assert np.isfinite(stats["loss"])
    ev = t.test()
    assert "val_ppl" in ev and ev["val_ppl"] > 1.0
    # the lstm config resolves to a clip threshold, so the clip branch
    # genuinely traced
    assert t.cfg.resolved().clip_grad_norm is not None


def test_layerwise_never_materializes_flat_gradient():
    """The mode's design claim, pinned mechanically: the compiled p=1
    update program contains NO tensor of the flat [N] shape — selection,
    error feedback, and the update all stay per-leaf — while the flat
    gtopk program is full of them (ravel/acc/residual/scatter). This is
    the property that lets XLA fuse each leaf's compress chain into that
    leaf's backward epilogue instead of serializing behind a whole-model
    concatenation (the measured p=1 serial tail of the flat path)."""
    from gtopkssgd_tpu.models import get_model

    model, _ = get_model("resnet20")
    variables = model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 32, 32, 3)))
    params = variables["params"]
    n = sum(l.size for l in jax.tree.leaves(params))
    grads = jax.tree.map(jnp.ones_like, params)
    flat_shape = f"f32[{n}]"

    counts = {}
    for mode in ("gtopk", "gtopk_layerwise"):
        tx = gtopk_sgd(0.1, compression=mode, density=0.001, axis_name=None)
        st = jax.jit(tx.init)(params)
        hlo = jax.jit(tx.update).lower(grads, st, params).compile().as_text()
        counts[mode] = hlo.count(flat_shape)
    assert counts["gtopk"] > 0  # sanity: the flat path does materialize [N]
    assert counts["gtopk_layerwise"] == 0, counts


def test_layerwise_trainer_checkpoint_roundtrip(tmp_path):
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    cfg = TrainConfig(
        dnn="resnet20", batch_size=4, nworkers=4, log_interval=5,
        eval_batches=2, max_epochs=1, compression="gtopk_layerwise",
        density=0.05, out_dir=str(tmp_path / "run"),
    )
    t = Trainer(cfg)
    t.train(5)
    res = t.state.opt_state.residual
    assert isinstance(res, tuple) and len(res) == len(
        jax.tree.leaves(t.state.params))
    big = [np.asarray(r) for r in res if r.size]
    assert all(r.shape[0] == 4 for r in big)
    assert any((r[0] != r[i]).any() for r in big for i in range(1, 4))
    # params replicated bit-identically
    leaf = jax.tree.leaves(t.state.params)[0]
    shards = [np.asarray(s.data) for s in leaf.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)
    t.save()
    t2 = Trainer(cfg)
    assert t2.restore()
    for a, b in zip(res, t2.state.opt_state.residual):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    t2.train(2)
    assert int(t2.state.step) == 7
