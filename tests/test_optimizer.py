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


# ------------------------------------------------------------------
# The one-device step on the gradient's own leaves (the slabs form, PR 42)
# against the flat [N] expression it replaced, written out here:
# ravel_pytree, compress_by_threshold on the vector, unravel.

import functools  # noqa: E402
import warnings  # noqa: E402

from jax.flatten_util import ravel_pytree  # noqa: E402

import pytest  # noqa: E402

from gtopkssgd_tpu import compression  # noqa: E402
from gtopkssgd_tpu.obs import counters as obs_counters  # noqa: E402
from gtopkssgd_tpu.optimizer import flat_residual  # noqa: E402

# 2-D, stacked experts, a [n, 1] gate, vectors, a last axis that is no
# multiple of 128, a matrix under the grouping size.
MIXED = {"attn": (64, 256), "experts": (4, 32, 128), "gate": (96, 1),
         "bias": (37,), "scale": (256,), "odd": (48, 130), "tiny": (8, 16)}
LR, MOM = 0.25, 0.5
# Counters that are float32 sums over N: equal to the bit where every
# partial sum is exact (the data below, without clip or momentum in front
# of the sums), to rounding where the order of summation shows (2e-4: the
# flat expression's per-layer sums are sequential float32 segment sums,
# off by 5e-5 over 16k elements).
SUMS = ("grad_norm_pre", "grad_norm_post", "residual_norm", "m_k")


@pytest.fixture
def in_place_from_4096(monkeypatch):
    """Leaves of `MIXED` on both sides of the rule: attn, experts and odd
    in place, the other four in the grouped vector."""
    monkeypatch.setattr(compression, "IN_PLACE_MIN_ELEMS", 4096)
    plan = compression.plan_leaves([MIXED[k] for k in sorted(MIXED)])
    assert len(plan.in_place) == 3 and len(plan.grouped) == 4
    return plan


def quarters(rng, shape, bound=8):
    """Multiples of 1/4 in [-2, 2]: squares and their sums over these
    sizes are exact in float32 whatever the order."""
    return jnp.asarray(rng.integers(-bound, bound + 1, shape) / 4.0,
                       jnp.float32)


def mixed_tree(rng, zero_share=0.0):
    tree = {k: quarters(rng, s) for k, s in MIXED.items()}
    if zero_share:
        tree = {k: jnp.where(jnp.asarray(rng.random(v.shape)) < zero_share,
                             0.0, v) for k, v in tree.items()}
    return tree


def flat_expression(grads_seq, params, *, density, method="auto", clip=None,
                    correction=False, warmup=0, layers=False):
    """The parent's P = 1 step, on the [N] vector: per step (update tree,
    residual, counters)."""
    comp = compression.TopKCompressor(density=density, method=method)
    n = sum(int(np.prod(s)) for s in MIXED.values())
    sizes = obs_counters.layer_sizes(params)
    seg = obs_counters.segment_ids(sizes)
    residual = jnp.zeros((n,), jnp.float32)
    u = jnp.zeros((n,), jnp.float32)
    age = jnp.zeros((n,), jnp.float32)
    inner = optax.sgd(LR, momentum=None if correction else MOM)
    inner_state = inner.init(params)

    # jitted as the step is: XLA divides by a constant as it sees fit
    @functools.partial(jax.jit, static_argnums=0)
    def step(warm, grads, residual, u, age, inner_state):
        flat, unravel = ravel_pytree(grads)
        if clip is not None:
            gnorm = jnp.sqrt(jnp.sum(flat * flat))
            flat = flat * jnp.minimum(1.0, clip / (gnorm + 1e-6))
        src = flat
        if correction:
            u = MOM * u + flat
            src = u
        if warm:
            dense, tau = src, jnp.float32(0.0)
            sent, m_k = jnp.float32(n), jnp.float32(1.0)
            lsel = obs_counters.dense_phase_selection_stats(sizes)[0]
        else:
            acc = src + residual
            keep, residual, tau = comp.compress_by_threshold(
                acc, grad=src, residual=residual)
            dense = acc - residual
            if correction:
                u = jnp.where(keep, 0.0, u)
            sent = obs_counters.kept_count(keep)
            m_k = obs_counters.mass_ratio(acc, dense)
            mask = dense != 0
            seg_sum = lambda x: jax.ops.segment_sum(
                x, seg, num_segments=len(sizes), indices_are_sorted=True)
            ltau = jax.ops.segment_min(
                jnp.where(mask, jnp.abs(dense), jnp.inf), seg,
                num_segments=len(sizes), indices_are_sorted=True)
            lsel = {"sent": seg_sum(mask.astype(jnp.float32)),
                    "tau": jnp.where(jnp.isfinite(ltau), ltau, 0.0),
                    "m_k": seg_sum(dense * dense) / jnp.maximum(
                        seg_sum(acc * acc), 1e-30)}
        updates, inner_state = inner.update(unravel(dense), inner_state,
                                            params)
        tel = obs_counters.make_telemetry(
            n=n, k=comp.k(n), p=1, mode="gtopk",
            grad_norm_pre=obs_counters.tree_l2(flat),
            grad_norm_post=obs_counters.tree_l2(dense),
            residual_norm=obs_counters.tree_l2(residual),
            tau=tau, sent_elems=sent, m_k=m_k)
        if layers:
            age = obs_counters.update_age(age, dense != 0)
            tel["layers"] = obs_counters.assemble_layer_telemetry(
                sel_stats=lsel, sizes=sizes,
                grad_norm_pre_l=obs_counters.seg_l2(flat, seg, len(sizes)),
                grad_norm_post_l=obs_counters.seg_l2(dense, seg, len(sizes)),
                residual_norm_l=obs_counters.seg_l2(
                    residual, seg, len(sizes)),
                age=age, seg=seg)
            tel["age"] = age
        return updates, residual, u, age, inner_state, tel

    out = []
    for t, grads in enumerate(grads_seq):
        updates, residual, u, age, inner_state, tel = step(
            t < warmup, grads, residual, u, age, inner_state)
        out.append((updates, {"v": residual, "u": u} if correction
                    else residual, tel))
    return out


def leaf_steps(grads_seq, params, axis_name, *, density, method="auto",
               clip=None, correction=False, warmup=0, layers=False):
    tx = gtopk_sgd(LR, momentum=MOM, compression="gtopk", density=density,
                   topk_method=method, clip_grad_norm=clip,
                   momentum_correction=correction, warmup_dense_steps=warmup,
                   telemetry=True, telemetry_layers=layers,
                   axis_name=axis_name)
    state = tx.init(params)
    step = jax.jit(tx.update)
    out = []
    for grads in grads_seq:
        updates, state = step(grads, state, params)
        residual, tel = state.residual, dict(state.telemetry)
        if axis_name is None:   # the state is slabs: bring it to [N]
            assert all(isinstance(r, tuple)
                       for r in (residual.values() if correction
                                 else [residual]))
            residual = flat_residual(residual, params)
            if layers:
                tel["age"] = flat_residual(tel["age"], params)
        out.append((updates, residual, tel))
    return out


def assert_same_steps(got, want, exact_sums, fused_products=False):
    """``fused_products``: the velocity of a clipped gradient is
    ``m * u + g * scale``, which XLA's CPU backend contracts into a fused
    multiply-add or not by the fusion it lands in (one rounding of 1e-8)."""
    same = (functools.partial(np.testing.assert_allclose, rtol=1e-4,
                              atol=1e-7)
            if fused_products else np.testing.assert_array_equal)
    assert len(got) == len(want)
    for t, ((gu, gr, gt), (wu, wr, wt)) in enumerate(zip(got, want)):
        jax.tree.map(same, gu, wu)
        jax.tree.map(same, gr, wr)
        assert set(gt) == set(wt)
        for key in wt:
            for name, g, w in ([(key, gt[key], wt[key])]
                               if key != "layers" else
                               [(f, gt[key][f], wt[key][f])
                                for f in wt[key]]):
                if not fused_products and (
                        exact_sums or name.split("/")[-1] not in SUMS):
                    np.testing.assert_array_equal(
                        np.asarray(g), np.asarray(w), err_msg=f"{t} {name}")
                else:
                    np.testing.assert_allclose(
                        np.asarray(g), np.asarray(w), rtol=2e-4,
                        err_msg=f"{t} {name}")


VARIANTS = {
    "plain": dict(),
    "clip": dict(clip=3.0),
    "correction": dict(correction=True),
    "warmup": dict(warmup=1),
    "obs_layers": dict(layers=True),
    "exact": dict(method="exact"),
    "approx": dict(method="approx"),
    "blockwise": dict(method="blockwise"),
    "correction_warmup_layers": dict(
        correction=True, warmup=1, layers=True),
    "clip_correction": dict(clip=3.0, correction=True),
}


@pytest.mark.parametrize("axis_name", [None, "dp"],
                         ids=["slab_state", "flat_state"])
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_leaf_form_is_the_flat_expression(in_place_from_4096, variant,
                                          axis_name):
    """Update, residual, tau and every counter of three steps (the residual
    feeds back), with the state as slabs (no axis named: the trainer's one
    device) and as the flat [N] an unbound axis leaves it."""
    kw = VARIANTS[variant]
    rng = np.random.default_rng(42)
    params = mixed_tree(rng)
    grads_seq = [mixed_tree(rng) for _ in range(3)]
    if variant == "clip_correction":
        # no ties for a product's last bit to break (assert_same_steps)
        grads_seq = [jax.tree.map(
            lambda g: jnp.asarray(rng.standard_normal(g.shape), jnp.float32),
            grads) for grads in grads_seq]
    want = flat_expression(grads_seq, params, density=0.01, **kw)
    got = leaf_steps(grads_seq, params, axis_name, density=0.01, **kw)
    # the clip's scale and the velocity's 1/2 leave the quarter grid
    exact = not (kw.get("clip") or kw.get("correction"))
    assert_same_steps(got, want, exact_sums=exact,
                      fused_products=variant == "clip_correction")
    if not kw.get("warmup"):
        assert float(got[0][2]["sent_elems"]) >= 0.01 * in_place_from_4096.n


def test_leaf_form_ties_at_tau_all_pass(in_place_from_4096):
    """Magnitudes on a grid of 17 values: hundreds tie at tau, in every
    slab, and all of them are sent (count above k), as the flat form."""
    rng = np.random.default_rng(3)
    params = mixed_tree(rng)
    grads_seq = [mixed_tree(rng) for _ in range(3)]
    want = flat_expression(grads_seq, params, density=0.05)
    got = leaf_steps(grads_seq, params, None, density=0.05)
    assert_same_steps(got, want, exact_sums=True)
    n = in_place_from_4096.n
    assert float(got[0][2]["sent_elems"]) > 1.2 * np.ceil(0.05 * n)


def test_leaf_form_fewer_than_k_nonzeros_keeps_no_zero(in_place_from_4096):
    rng = np.random.default_rng(4)
    params = mixed_tree(rng)
    grads_seq = [mixed_tree(rng, zero_share=0.97) for _ in range(3)]
    want = flat_expression(grads_seq, params, density=0.2)
    got = leaf_steps(grads_seq, params, None, density=0.2)
    assert_same_steps(got, want, exact_sums=True)
    flat0 = np.asarray(ravel_pytree(grads_seq[0])[0])
    assert float(got[0][2]["sent_elems"]) == np.count_nonzero(flat0)
    assert float(got[0][2]["tau"]) == np.abs(flat0[flat0 != 0]).min()
    assert not np.asarray(got[0][1]).any()      # nothing is left behind


@pytest.mark.parametrize("method", ["auto", "exact", "approx"])
def test_leaf_form_k_at_least_n_sends_every_nonzero(in_place_from_4096,
                                                    method):
    rng = np.random.default_rng(5)
    params = mixed_tree(rng)
    grads_seq = [mixed_tree(rng) for _ in range(2)]
    want = flat_expression(grads_seq, params, density=1.0, method=method)
    got = leaf_steps(grads_seq, params, None, density=1.0, method=method)
    assert_same_steps(got, want, exact_sums=True)
    flat0 = np.asarray(ravel_pytree(grads_seq[0])[0])
    assert float(got[0][2]["sent_elems"]) == np.count_nonzero(flat0)


def test_leaf_form_state_is_slabs_only_without_an_axis():
    params = {k: jnp.zeros(s) for k, s in MIXED.items()}
    n = sum(x.size for x in jax.tree.leaves(params))
    slabs = gtopk_sgd(0.1, compression="gtopk", axis_name=None).init(params)
    assert isinstance(slabs.residual, tuple)
    assert sum(r.size for r in slabs.residual) == n
    for kw in (dict(compression="gtopk", axis_name="dp"),
               dict(compression="gtopk_hier", axis_name="dp")):
        assert gtopk_sgd(0.1, **kw).init(params).residual.shape == (n,)
    dense = gtopk_sgd(0.1, compression="dense", axis_name=None).init(params)
    assert dense.residual.shape == (0,)
    lw = gtopk_sgd(0.1, compression="gtopk_layerwise",
                   axis_name=None).init(params)
    assert [r.shape for r in lw.residual] == [
        (int(np.prod(MIXED[k])),) for k in sorted(MIXED)]


# ------------------------------------------------------------------
# The leaves form (gtopk_layerwise) against the slabs form. On a tree of
# ONE leaf the per-leaf k is the global k, and under the exact kernel tau
# is the k-th largest magnitude in both, so the two forms have to take the
# same step: what pins the third form of optimizer.update_fn to the other
# two, as test_leaf_form_is_the_flat_expression pins slabs to flat.

@pytest.mark.parametrize("correction", [False, True],
                         ids=["momentum", "correction"])
@pytest.mark.parametrize("clip", [None, 0.5], ids=["no_clip", "clip"])
def test_layerwise_of_one_leaf_is_the_flat_modes_step(clip, correction):
    rng = np.random.default_rng(11)
    params = {"w": jnp.asarray(rng.standard_normal((24, 40)), jnp.float32)}
    grads_seq = [
        {"w": jnp.asarray(rng.standard_normal((24, 40)), jnp.float32)}
        for _ in range(3)]
    runs = {}
    for mode in ("gtopk", "gtopk_layerwise"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # layerwise x correction
            tx = gtopk_sgd(LR, momentum=MOM, weight_decay=1e-3,
                           compression=mode, density=0.02,
                           topk_method="exact", clip_grad_norm=clip,
                           momentum_correction=correction, axis_name=None,
                           telemetry=True)
        state = tx.init(params)
        update = jax.jit(tx.update)
        steps = []
        for grads in grads_seq:
            updates, state = update(grads, state, params)
            residual = state.residual
            if mode == "gtopk":
                residual = flat_residual(residual, params)
            else:  # one leaf's flat buffer
                residual = jax.tree.map(
                    lambda r: r[0], residual,
                    is_leaf=lambda x: isinstance(x, tuple))
            steps.append((updates, residual, state.telemetry))
        runs[mode] = steps
    k = int(np.ceil(0.02 * 24 * 40))
    for (u_f, r_f, t_f), (u_l, r_l, t_l) in zip(*runs.values()):
        assert np.count_nonzero(np.asarray(u_f["w"])) >= k
        np.testing.assert_array_equal(np.asarray(u_l["w"]),
                                      np.asarray(u_f["w"]))
        jax.tree.map(np.testing.assert_array_equal, r_l, r_f)
        for key in ("tau", "sent_elems", "achieved_density"):
            assert float(t_l[key]) == float(t_f[key]), key
