"""Unit tests for ops.topk against numpy oracles."""

import jax.numpy as jnp
import numpy as np
import pytest

from gtopkssgd_tpu.ops import (
    blockwise_topk_abs,
    k_for_density,
    membership_mask,
    merge_sparse_sets,
    scatter_add_dense,
    select_topk,
    topk_abs,
)


def np_topk_abs(x, k):
    idx = np.argsort(-np.abs(x), kind="stable")[:k]
    return x[idx], idx


def test_k_for_density():
    assert k_for_density(1000, 0.001) == 1
    assert k_for_density(1001, 0.001) == 2
    assert k_for_density(10, 1.0) == 10
    assert k_for_density(5, 1e-9) == 1


@pytest.mark.parametrize("n,k", [(100, 5), (1000, 37), (65536 * 3 + 17, 100)])
@pytest.mark.parametrize("method", ["exact", "blockwise"])
def test_topk_matches_oracle(rng, n, k, method):
    x = rng.standard_normal(n).astype(np.float32)
    vals, idx = select_topk(jnp.asarray(x), k, method)
    vals, idx = np.asarray(vals), np.asarray(idx)
    ov, oi = np_topk_abs(x, k)
    # Same magnitude multiset (tie order may differ between implementations).
    np.testing.assert_allclose(
        np.sort(np.abs(vals)), np.sort(np.abs(ov)), rtol=1e-6
    )
    # Selected values really live at the claimed indices.
    np.testing.assert_array_equal(x[idx], vals)
    assert len(set(idx.tolist())) == k


def test_topk_signed_values(rng):
    x = rng.standard_normal(256).astype(np.float32)
    vals, idx = topk_abs(jnp.asarray(x), 16)
    np.testing.assert_array_equal(np.asarray(vals), x[np.asarray(idx)])


def test_blockwise_handles_padding(rng):
    # n not divisible by block count; top element near the padded tail.
    n = 1000003
    x = rng.standard_normal(n).astype(np.float32) * 0.1
    x[n - 1] = 50.0
    x[0] = -49.0
    vals, idx = blockwise_topk_abs(jnp.asarray(x), 4)
    idx = np.asarray(idx)
    assert n - 1 in idx and 0 in idx
    assert np.all(idx < n)


def test_merge_sparse_sets_oracle(rng):
    n = 500
    for _ in range(10):
        k = 16
        ia = rng.choice(n, size=k, replace=False).astype(np.int32)
        ib = rng.choice(n, size=k, replace=False).astype(np.int32)
        va = rng.standard_normal(k).astype(np.float32)
        vb = rng.standard_normal(k).astype(np.float32)
        mv, mi = merge_sparse_sets(
            jnp.asarray(va), jnp.asarray(ia), jnp.asarray(vb), jnp.asarray(ib), k, n
        )
        dense = np.zeros(n, np.float32)
        np.add.at(dense, ia, va)
        np.add.at(dense, ib, vb)
        got = np.zeros(n, np.float32)
        np.add.at(got, np.asarray(mi) % (n + 1), np.asarray(mv))
        got = got[:n] if got.shape[0] == n else got
        ov, oi = np_topk_abs(dense, k)
        want = np.zeros(n, np.float32)
        want[oi] = ov
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_merge_is_order_symmetric(rng):
    # Both ppermute partners must compute the identical merged set.
    n, k = 200, 8
    ia = rng.choice(n, size=k, replace=False).astype(np.int32)
    ib = rng.choice(n, size=k, replace=False).astype(np.int32)
    va = rng.standard_normal(k).astype(np.float32)
    vb = rng.standard_normal(k).astype(np.float32)
    mv1, mi1 = merge_sparse_sets(
        jnp.asarray(va), jnp.asarray(ia), jnp.asarray(vb), jnp.asarray(ib), k, n
    )
    mv2, mi2 = merge_sparse_sets(
        jnp.asarray(vb), jnp.asarray(ib), jnp.asarray(va), jnp.asarray(ia), k, n
    )
    np.testing.assert_array_equal(np.asarray(mi1), np.asarray(mi2))
    np.testing.assert_allclose(np.asarray(mv1), np.asarray(mv2), rtol=1e-6)


def test_merge_with_sentinel_padding():
    # Sentinel slots (idx == n, val 0) may repeat; they must never displace
    # real mass.
    n, k = 50, 4
    ia = np.array([1, 2, n, n], np.int32)
    va = np.array([1.0, -2.0, 0.0, 0.0], np.float32)
    ib = np.array([2, 3, n, n], np.int32)
    vb = np.array([5.0, 0.5, 0.0, 0.0], np.float32)
    mv, mi = merge_sparse_sets(
        jnp.asarray(va), jnp.asarray(ia), jnp.asarray(vb), jnp.asarray(ib), k, n
    )
    dense = np.asarray(scatter_add_dense(n, mi, mv))
    want = np.zeros(n, np.float32)
    want[1], want[2], want[3] = 1.0, 3.0, 0.5
    np.testing.assert_allclose(dense, want, rtol=1e-6)


def test_scatter_drops_sentinel():
    out = scatter_add_dense(
        4, jnp.array([0, 4, 2], jnp.int32), jnp.array([1.0, 9.0, 2.0])
    )
    np.testing.assert_allclose(np.asarray(out), [1.0, 0.0, 2.0, 0.0])


def test_membership_mask():
    q = jnp.array([3, 7, 1, 9], jnp.int32)
    s = jnp.array([9, 3, 5], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(membership_mask(q, s)), [True, False, False, True]
    )


class TestSimrecall:
    """ops.topk.simrecall_topk_abs — the CPU-runnable pessimistic model of
    approx_max_k selection (round-4 verdict missing #2). These pin the
    properties the convergence A/B leans on: real-but-imperfect recall,
    backfill from the next ranks, and exact determinism per input."""

    def test_valid_sparse_set(self, rng):
        from gtopkssgd_tpu.ops import simrecall_topk_abs

        x = rng.standard_normal(5000).astype(np.float32)
        vals, idx = simrecall_topk_abs(jnp.asarray(x), 100)
        vals, idx = np.asarray(vals), np.asarray(idx)
        assert len(set(idx.tolist())) == 100  # unique, no sentinels needed
        np.testing.assert_array_equal(x[idx], vals)

    def test_recall_near_target(self, rng):
        from gtopkssgd_tpu.ops import simrecall_topk_abs

        x = rng.standard_normal(20000).astype(np.float32)
        k = 1000
        _, idx = simrecall_topk_abs(jnp.asarray(x), k)
        true_k = set(np.argsort(-np.abs(x), kind="stable")[:k].tolist())
        hit = len(true_k & set(np.asarray(idx).tolist())) / k
        # Binomial(k=1000, p=0.95): std ~0.7%; 4 sigma on either side,
        # and strictly below 1.0 — the selector must actually drop.
        assert 0.91 <= hit <= 0.99

    def test_backfill_comes_from_next_ranks(self, rng):
        from gtopkssgd_tpu.ops import simrecall_topk_abs

        x = rng.standard_normal(20000).astype(np.float32)
        k = 1000
        _, idx = simrecall_topk_abs(jnp.asarray(x), k)
        order = np.argsort(-np.abs(x), kind="stable")
        ranks = np.empty(len(x), np.int64)
        ranks[order] = np.arange(len(x))
        got = ranks[np.asarray(idx)]
        # Every selected element sits within the exact top-(k+pad) pool.
        pad = max(16, int(np.ceil(k * 0.05 * 4)))
        assert got.max() < k + pad

    def test_deterministic_per_input(self, rng):
        from gtopkssgd_tpu.ops import simrecall_topk_abs

        x = jnp.asarray(rng.standard_normal(4000).astype(np.float32))
        v1, i1 = simrecall_topk_abs(x, 200)
        v2, i2 = simrecall_topk_abs(x, 200)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
        # ...but the drop pattern is data-dependent: a different gradient
        # drops a different set (mirrors approx misses moving step to step).
        _, i3 = simrecall_topk_abs(x * 1.7 + 0.01, 200)
        assert not np.array_equal(np.asarray(i1), np.asarray(i3))

    def test_jit_and_dispatch(self, rng):
        import jax

        x = jnp.asarray(rng.standard_normal(3000).astype(np.float32))
        f = jax.jit(lambda x: select_topk(x, 50, "simrecall"))
        vals, idx = f(x)
        assert vals.shape == (50,) and idx.shape == (50,)
        assert idx.dtype == jnp.int32


# ------------------------------------------------------------------
# One tau over an accumulator that exists only as leaves (PR 42).

from gtopkssgd_tpu.ops import (  # noqa: E402
    approx_bin_size,
    bin_maxima,
    select_tau,
    select_tau_leaves,
)
from gtopkssgd_tpu.ops import topk as topk_mod  # noqa: E402

LEAF_SHAPES = [(64, 256), (4, 32, 128), (96, 1), (37,), (48, 130), (8, 16)]


def leaves_of(rng, shapes=LEAF_SHAPES, grid=None):
    if grid:
        return [jnp.asarray(rng.integers(-grid, grid + 1, s) / 4.0,
                            jnp.float32) for s in shapes]
    return [jnp.asarray(rng.standard_normal(s), jnp.float32) for s in shapes]


def flat_of(leaves):
    return jnp.concatenate([a.reshape(-1) for a in leaves])


@pytest.mark.parametrize("method", ["auto", "exact", "approx", "blockwise",
                                    "threshold", "twostage", "simrecall"])
@pytest.mark.parametrize("density", [0.001, 0.05])
def test_tau_over_leaves_is_the_flat_vectors(rng, method, density):
    """Off the chip every method's tau over the leaves is `select_tau`'s
    over their concatenation, to the bit."""
    leaves = leaves_of(rng)
    k = k_for_density(sum(a.size for a in leaves), density)
    want = select_tau(flat_of(leaves), k, method)
    got = select_tau_leaves(leaves, k, method)
    assert float(got) == float(want) > 0


def test_tau_over_leaves_with_ties_zeros_and_k_past_n(rng):
    leaves = leaves_of(rng, grid=3)                  # seven magnitudes
    n = sum(a.size for a in leaves)
    flat = np.abs(np.asarray(flat_of(leaves)))
    for k in (1, 50, n // 3, n - 1):
        want = np.sort(flat)[n - k]
        for method in ("exact", "approx", "auto"):
            assert float(select_tau_leaves(leaves, k, method)) == want
    # k >= n: the smallest magnitude of all, a zero here
    assert float(select_tau_leaves(leaves, n, "auto")) == 0.0
    assert float(select_tau_leaves(leaves, n + 5, "exact")) == 0.0
    # fewer nonzeros than k: tau is 0, and the masks keep no zero
    sparse = [jnp.where(jnp.abs(a) > 0.6, a, 0.0) for a in leaves]
    nonzero = int(sum(np.count_nonzero(np.asarray(a)) for a in sparse))
    assert float(select_tau_leaves(sparse, nonzero + 1, "exact")) == 0.0


def test_approx_bin_size_is_xlas_recall_formula():
    # floor(log2(N * 0.0513 / k)) at the recall target 0.95
    assert approx_bin_size(458_272_769, 458_273) == 32      # rho = 0.001
    assert approx_bin_size(25_557_032, 25_558) == 32
    assert approx_bin_size(25_557_032, 255_571) == 4        # rho = 0.01
    assert approx_bin_size(25_557_032, 2_555_704) == 1      # rho = 0.1
    assert approx_bin_size(1 << 20, 1049) == 32
    assert approx_bin_size(2048, 3) == 2          # 1024 outputs at least
    assert approx_bin_size(1000, 1) == 1


@pytest.mark.parametrize("shape,group", [
    ((64, 256), 32), ((4, 32, 128), 32), ((70, 130), 32), ((20, 16), 32),
    ((5000,), 32), ((37,), 8), ((96, 1), 4), ((64, 384), 4)])
def test_bin_maxima_partition_the_leaf(rng, shape, group):
    """Every element lies in one bin; a bin holds one element of each of
    `group` slabs of consecutive rows, slab s's taken s lane tiles further
    along the row (rows far apart and columns apart: no two members share
    a row or, in a leaf wider than the shifts, a column); the rows past the
    last whole slab are one bin a column; the maxima are the bins' own."""
    x = np.abs(rng.standard_normal(shape)).astype(np.float32)
    got = np.sort(np.asarray(bin_maxima(jnp.asarray(x), group)))
    rows = (x.reshape(-1, shape[-1]) if len(shape) > 1 else
            np.pad(x, (0, -x.size % 128)).reshape(-1, 128))
    n_rows, cols = rows.shape
    per = n_rows // group
    want, seen = [], np.zeros(rows.shape, int)
    for i in range(per):
        for j in range(cols):
            members = [(s * per + i, (j + 128 * s) % cols)
                       for s in range(group)]
            assert len({r for r, _ in members}) == group
            if cols >= 128 * group:
                assert len({c for _, c in members}) == group
            for r, c in members:
                seen[r, c] += 1
            want.append(max(rows[r, c] for r, c in members))
    for j in range(cols if n_rows > per * group else 0):
        seen[per * group:, j] += 1
        want.append(rows[per * group:, j].max())
    assert (seen == 1).all()
    np.testing.assert_array_equal(got, np.sort(np.asarray(want, np.float32)))
    assert got[-1] == x.max()


def test_bins_keep_a_gradients_hot_rows_and_columns_apart(rng):
    """A weight's gradient is a sum of outer products: whole rows and whole
    columns are large. The k-th largest bin maximum still sends about k (a
    bin of consecutive rows of one column would send twenty times k)."""
    for shape, hot in (((640, 4096), "columns"), ((4096, 640), "rows")):
        x = np.abs(rng.standard_normal(shape)).astype(np.float32)
        if hot == "columns":
            x *= np.where(rng.random(shape[1]) < 0.02, 50.0, 1.0)[None, :]
        else:       # the first rows, one after the other
            x *= np.where(np.arange(shape[0]) < 80, 50.0, 1.0)[:, None]
        k = x.size // 1000
        cand = np.asarray(bin_maxima(jnp.asarray(x), 32))
        tau = np.sort(cand)[cand.size - k]
        assert k <= (x >= tau).sum() <= 1.1 * k, hot


def test_binned_tau_is_approx_max_ks_rule_on_leaves(rng):
    """The chip's form, run here: tau is the k-th largest bin maximum, no
    larger than the exact k-th magnitude, and the mask `>= tau` holds the
    kernel's promise of 0.95 of the exact top k and all the candidates."""
    leaves = leaves_of(rng, [(256, 256), (8, 64, 128), (512, 130), (4000,)])
    n = sum(a.size for a in leaves)
    k = k_for_density(n, 0.001)
    group = approx_bin_size(n, k)
    assert group == 32
    mags = [jnp.abs(a) for a in leaves]
    tau = float(topk_mod._binned_tau_leaves(mags, k, group))
    cand = np.concatenate([np.asarray(bin_maxima(m, group)) for m in mags])
    assert n / 33 < cand.size < n / 31
    assert tau == np.sort(cand)[cand.size - k]
    flat = np.abs(np.asarray(flat_of(leaves)))
    exact = np.sort(flat)[n - k]
    assert 0 < tau <= exact
    kept = flat >= tau
    assert kept.sum() >= k
    top = np.argsort(-flat)[:k]
    assert kept[top].mean() == 1.0       # a superset of the exact top k
    assert kept.sum() <= k / 0.95 + 1    # and of no more than recall allows
    # too few candidates for k: the exact search
    few = [jnp.abs(a) for a in leaves_of(rng, [(64, 16), (40, 8)])]
    assert float(topk_mod._binned_tau_leaves(few, 60, 32)) == float(
        topk_mod._exact_tau_leaves(few, 60))
