"""Error-feedback compressor invariants (SURVEY.md §4 test strategy)."""

import jax.numpy as jnp
import numpy as np

from gtopkssgd_tpu.compression import (
    NoneCompressor,
    TopKCompressor,
    get_compressor,
)
from gtopkssgd_tpu.ops import scatter_add_dense


def test_registry():
    assert isinstance(get_compressor(None), NoneCompressor)
    assert isinstance(get_compressor("none"), NoneCompressor)
    c = get_compressor("topk", density=0.01)
    assert isinstance(c, TopKCompressor) and c.density == 0.01
    c = get_compressor("gtopk", density=0.001)
    assert isinstance(c, TopKCompressor)


def test_mass_conservation(rng):
    """Invariant: sent + residual == acc, elementwise (no gradient mass is
    created or destroyed by compression)."""
    n = 4096
    comp = TopKCompressor(density=0.01, method="exact")
    grad = rng.standard_normal(n).astype(np.float32)
    residual = comp.init_residual(n)
    acc = comp.accumulate(jnp.asarray(grad), residual)
    vals, idx, new_res = comp.compress(acc)
    sent = scatter_add_dense(n, idx, vals)
    np.testing.assert_allclose(
        np.asarray(sent + new_res), np.asarray(acc), rtol=1e-6, atol=1e-7
    )
    # Selected slots are zeroed in the residual.
    assert np.all(np.asarray(new_res)[np.asarray(idx)] == 0.0)


def test_residual_accumulates_over_steps(rng):
    """Unselected gradient mass must build up and eventually win selection —
    the error-feedback property that preserves convergence at rho=1e-3."""
    n = 1000
    comp = TopKCompressor(density=0.001, method="exact")  # k = 1
    residual = comp.init_residual(n)
    small = np.full(n, 0.001, np.float32)
    small[7] = 1.0  # dominant coordinate wins first
    acc = comp.accumulate(jnp.asarray(small), residual)
    vals, idx, residual = comp.compress(acc)
    assert int(idx[0]) == 7
    # Feed zero grads; residual mass alone must get selected (any non-7 slot
    # has accumulated 0.001 and slot 7 has 0).
    acc = comp.accumulate(jnp.zeros(n), residual)
    vals2, idx2, residual = comp.compress(acc)
    assert int(idx2[0]) != 7
    assert abs(float(vals2[0]) - 0.001) < 1e-6


def test_repair_returns_rejected_mass(rng):
    n = 256
    comp = TopKCompressor(density=0.05, method="exact")
    grad = rng.standard_normal(n).astype(np.float32)
    acc = comp.accumulate(jnp.asarray(grad), comp.init_residual(n))
    vals, idx, res = comp.compress(acc)
    # Pretend the global top-k kept only the first half of our local picks.
    k = vals.shape[0]
    global_idx = idx[: k // 2]
    repaired = comp.repair(res, vals, idx, global_idx)
    r = np.asarray(repaired)
    li, lv = np.asarray(idx), np.asarray(vals)
    kept = set(np.asarray(global_idx).tolist())
    for i in range(k):
        if li[i] in kept:
            assert r[li[i]] == 0.0
        else:
            np.testing.assert_allclose(r[li[i]], lv[i], rtol=1e-6)
    # After repair: residual + globally-applied == acc (global mass view).
    applied = scatter_add_dense(n, global_idx, vals[: k // 2])
    np.testing.assert_allclose(
        np.asarray(applied + repaired), np.asarray(acc), rtol=1e-6, atol=1e-7
    )


def test_none_compressor_passthrough(rng):
    n = 64
    comp = NoneCompressor()
    g = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    acc = comp.accumulate(g, comp.init_residual(n))
    vals, idx, res = comp.compress(acc)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(g))
    np.testing.assert_array_equal(np.asarray(idx), np.arange(n))
    assert res.shape == (0,)


def test_compress_by_threshold_matches_exact_topk_partition(rng):
    """With the exact kernel and no ties, the threshold mask IS the top-k
    set, and (keep, residual) partition acc exactly."""
    n = 257
    comp = TopKCompressor(density=0.05, method="exact")
    acc = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    keep, res, tau = comp.compress_by_threshold(acc)
    vals, idx, res_idx_form = comp.compress(acc)
    # Reported tau is the smallest kept magnitude.
    assert float(tau) == float(np.abs(np.asarray(vals)).min())
    # Same selected set (random floats: ties have measure zero).
    mask = np.zeros(n, bool)
    mask[np.asarray(idx)] = True
    np.testing.assert_array_equal(np.asarray(keep), mask)
    # Same residual, bit-for-bit partition: keep*acc + residual == acc.
    np.testing.assert_array_equal(np.asarray(res), np.asarray(res_idx_form))
    recon = np.where(np.asarray(keep), np.asarray(acc), 0.0) + np.asarray(res)
    np.testing.assert_array_equal(recon, np.asarray(acc))


def test_compress_by_threshold_ties_all_pass():
    """Magnitude ties at tau are all selected (count may exceed k), and the
    partition invariant still holds exactly."""
    acc = jnp.asarray([3.0, -3.0, 3.0, 1.0, -1.0, 0.5] + [0.0] * 10)
    comp = TopKCompressor(density=2 / 16, method="exact")  # k = 2
    keep, res, tau = comp.compress_by_threshold(acc)
    assert float(tau) == 3.0
    k = np.asarray(keep)
    assert k[:3].all() and not k[3:].any()  # all three |3.0| ties pass
    assert int(k.sum()) == 3 > comp.k(16)
    np.testing.assert_array_equal(
        np.where(k, np.asarray(acc), 0.0) + np.asarray(res), np.asarray(acc)
    )


def test_compress_by_threshold_tau_zero_keeps_only_nonzeros():
    """Degenerate tau == 0 (fewer than k nonzeros): |x| >= 0 is vacuously
    true, so an unguarded mask would select EVERY coordinate — under
    momentum correction that zeroes the whole velocity buffer for the
    leaf. The guard masks zeros out: only the actual nonzeros pass, and
    the partition invariant still holds exactly. (Round-3 advisor.)"""
    n = 64
    comp = TopKCompressor(density=8 / 64, method="exact")  # k = 8
    acc = jnp.zeros(n).at[3].set(2.0).at[17].set(-1.0)  # 2 nonzeros < k
    keep, res, tau = comp.compress_by_threshold(acc)
    # tau follows the kept set (smallest kept magnitude), not the kernel's
    # zero-padded report.
    assert float(tau) == 1.0
    k = np.asarray(keep)
    assert int(k.sum()) == 2 and k[3] and k[17]
    np.testing.assert_array_equal(
        np.where(k, np.asarray(acc), 0.0) + np.asarray(res), np.asarray(acc)
    )


def test_compress_by_threshold_superset_of_kernel_selection(rng):
    """For ANY selection kernel, the threshold mask contains every index the
    kernel itself returned (tau = min |kernel vals|), so threshold recall
    >= kernel recall — the documented approx-kernel guarantee."""
    n = 4096
    comp = TopKCompressor(density=0.01, method="blockwise")
    acc = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    keep, _, _ = comp.compress_by_threshold(acc)
    _, idx = __import__("gtopkssgd_tpu.ops", fromlist=["select_topk"]).select_topk(
        acc, comp.k(n), comp.method
    )
    assert np.asarray(keep)[np.asarray(idx)].all()


def test_compress_by_threshold_select_tau_partition_parity(rng):
    """compress_by_threshold's tau now comes from the tau-only API
    (ops.select_tau — no (vals, idx) set, no gather); per method the
    keep/residual partition must be IDENTICAL to the legacy formulation
    that built the mask from min|vals| of the corresponding select_topk."""
    from gtopkssgd_tpu.ops import select_topk

    n = 8192
    acc = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    for method in ("exact", "blockwise", "approx", "threshold"):
        comp = TopKCompressor(density=0.01, method=method)
        keep, res, kept_tau = comp.compress_by_threshold(acc)
        vals, _ = select_topk(acc, comp.k(n), method)
        tau_ref = float(np.abs(np.asarray(vals)).min())
        want = (np.abs(np.asarray(acc)) >= tau_ref) & (
            np.abs(np.asarray(acc)) > 0.0)
        np.testing.assert_array_equal(np.asarray(keep), want, err_msg=method)
        np.testing.assert_array_equal(
            np.where(want, 0.0, np.asarray(acc)), np.asarray(res),
            err_msg=method)


def test_compress_by_threshold_fused_operands_same_partition(rng):
    """Passing the unfused operands (grad, residual with
    acc == grad + residual) must yield the exact same partition as the
    materialized-accumulator call — the fused path changes WHERE the
    accumulate happens, never the selected set."""
    n = 4096
    g = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    r = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    acc = g + r
    comp = TopKCompressor(density=0.01, method="exact")
    keep_a, res_a, tau_a = comp.compress_by_threshold(acc)
    keep_b, res_b, tau_b = comp.compress_by_threshold(
        acc, grad=g, residual=r)
    np.testing.assert_array_equal(np.asarray(keep_a), np.asarray(keep_b))
    np.testing.assert_array_equal(np.asarray(res_a), np.asarray(res_b))
    assert float(tau_a) == float(tau_b)


def test_compress_by_threshold_twostage_superset_of_exact(rng):
    """twostage tau is the k-th largest CANDIDATE magnitude <= the exact
    tau, so its keep mask contains the ENTIRE exact top-k — the property
    behind the audited recall floor of 1.0 at p=1."""
    from gtopkssgd_tpu.ops import topk_abs

    n = 100_000
    acc = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    comp = TopKCompressor(density=0.001, method="twostage")
    keep, res, _ = comp.compress_by_threshold(acc)
    _, exact_idx = topk_abs(acc, comp.k(n))
    assert np.asarray(keep)[np.asarray(exact_idx)].all()
    np.testing.assert_array_equal(
        np.where(np.asarray(keep), 0.0, np.asarray(acc)), np.asarray(res))


# ------------------------------------------------------------------
# The leaf form (PR 42): slabs, and one threshold over all of them.

import jax  # noqa: E402
import pytest  # noqa: E402

from gtopkssgd_tpu import compression  # noqa: E402

SHAPES = [(64, 256), (4, 32, 128), (96, 1), (37,), (256,), (48, 130),
          (8, 16)]


def test_plan_reads_size_and_last_axis_only(monkeypatch):
    big = compression.IN_PLACE_MIN_ELEMS
    plan = compression.plan_leaves(
        [(big // 128, 128), (big // 128 - 1, 128), (big,), (big, 1),
         (2, big // 256, 128), (big // 64, 64), (7,)])
    assert plan.in_place == (0, 4)          # large, two axes, a row of lanes
    assert plan.grouped == (1, 2, 3, 5, 6)
    assert plan.counters() == {
        "leaves_in_place": 2, "leaves_grouped": 5,
        "elems_in_place_share": 2 * big / plan.n}
    assert plan.slab_shapes == (
        (big // 128, 128), (2, big // 256, 128),
        (plan.n - 2 * big,))
    # nothing to group: no grouped slab; nothing in place: one vector
    assert compression.plan_leaves([(big // 128, 128)]).slab_shapes == (
        (big // 128, 128),)
    assert compression.plan_leaves([(3, 4), (5,)]).slab_shapes == ((17,),)
    monkeypatch.setattr(compression, "IN_PLACE_MIN_ELEMS", 4096)
    small = compression.plan_leaves(SHAPES)
    assert small.in_place == (0, 1, 5) and len(small.grouped) == 4


@pytest.mark.parametrize("xp", [jnp, np], ids=["device", "host"])
def test_slabs_round_trip_through_leaves_and_the_flat_vector(
        rng, monkeypatch, xp):
    monkeypatch.setattr(compression, "IN_PLACE_MIN_ELEMS", 4096)
    plan = compression.plan_leaves(SHAPES)
    leaves = [xp.asarray(rng.standard_normal(s).astype(np.float32))
              for s in SHAPES]
    flat = xp.concatenate([a.reshape(-1) for a in leaves])
    slabs = plan.split(leaves, xp)
    assert tuple(s.shape for s in slabs) == plan.slab_shapes
    assert slabs[0] is leaves[0]                  # in place: the leaf itself
    for got, want in zip(plan.join(slabs), leaves, strict=True):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(plan.to_flat(slabs, xp)),
                                  np.asarray(flat))
    for got, want in zip(plan.from_flat(flat, xp), slabs, strict=True):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("method", ["auto", "exact", "approx", "twostage"])
@pytest.mark.parametrize("density", [0.002, 0.1, 1.0])
def test_compress_leaves_by_threshold_is_the_vectors_partition(
        rng, monkeypatch, method, density):
    """keep, residual and kept_tau over slabs == compress_by_threshold over
    their flat vector, ties and zeros included (values on a grid)."""
    monkeypatch.setattr(compression, "IN_PLACE_MIN_ELEMS", 4096)
    plan = compression.plan_leaves(SHAPES)
    comp = TopKCompressor(density=density, method=method)
    leaves = [jnp.asarray(rng.integers(-6, 7, s) / 4.0, jnp.float32)
              for s in SHAPES]
    slabs = plan.split(leaves)
    keeps, residuals, kept_tau = jax.jit(
        comp.compress_leaves_by_threshold)(slabs)
    want_keep, want_res, want_tau = comp.compress_by_threshold(
        plan.to_flat(slabs))
    np.testing.assert_array_equal(
        np.asarray(plan.to_flat(keeps)), np.asarray(want_keep))
    np.testing.assert_array_equal(
        np.asarray(plan.to_flat(residuals)), np.asarray(want_res))
    assert float(kept_tau) == float(want_tau) > 0
    for keep, res, slab in zip(keeps, residuals, slabs, strict=True):
        assert keep.shape == res.shape == slab.shape
    if density == 1.0:      # every nonzero is sent, no zero is
        assert int(sum(k.sum() for k in keeps)) == int(
            sum(np.count_nonzero(np.asarray(a)) for a in leaves))
