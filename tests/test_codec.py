"""Wire codec (parallel/codec.py): packed-buffer roundtrips, byte
accounting, partner symmetry through the hypercube under quantization,
and the error-feedback fold/repair composition.

The reference shipped fp32 values + int32 indices over MPI; the codec
layer replaces that payload with block-scaled 8-bit values and
Elias-Fano bitpacked indices while preserving the merge oracle's
bitwise-agreement contract (both partners decode identical sets because
encode is deterministic). These tests pin exactly that contract — plus
the fp32 identity, so the historical byte formula stays the default.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from gtopkssgd_tpu.compression import TopKCompressor
from gtopkssgd_tpu.parallel import (
    comm_bytes_per_step,
    get_codec,
    gtopk_allreduce,
    hier_gtopk_allreduce,
    make_mesh,
    roundtrip_aligned,
    topk_allgather,
    tree_rounds,
)

K = 8
N = 300


def make_sets(rng, p, k=K, n=N, sentinels=0):
    vals = np.zeros((p, k), np.float32)
    idxs = np.full((p, k), n, np.int32)
    for d in range(p):
        kk = k - sentinels
        idxs[d, :kk] = rng.choice(n, size=kk, replace=False)
        vals[d, :kk] = rng.standard_normal(kk).astype(np.float32) * 5
    return vals, idxs


def run_collective(fn, mesh, vals, idxs):
    """shard_map a per-device (vals, idx) collective over the dp axis and
    return host arrays stacked [p, ...]."""
    body = jax.shard_map(
        lambda v, i: jax.tree.map(lambda x: x[None], fn(v[0], i[0])),
        mesh=mesh, in_specs=(P("dp"), P("dp")), out_specs=P("dp"),
        check_vma=False)
    return jax.tree.map(np.asarray, jax.jit(body)(jnp.asarray(vals),
                                                  jnp.asarray(idxs)))


# ---------------------------------------------------------------------------
# Satellite: fp32-codec bytes pin the pre-codec hardcoded formula.


def test_fp32_codec_bytes_match_legacy_formula():
    """Regression: the default (fp32) codec must reproduce the old
    hardcoded 4-byte-values + 4-byte-indices accounting exactly, for
    every mode comm_bytes_per_step models."""
    n, k = 272_474, 2_725
    assert get_codec("fp32").wire_set_bytes(k, n) == 8 * k
    # gtopk: 8k per round x tree rounds (pow2 and ragged)
    assert comm_bytes_per_step("gtopk", n, k, 32) == 8 * k * 5
    assert comm_bytes_per_step("gtopk", n, k, 6) == 8 * k * 4
    assert comm_bytes_per_step("gtopk", n, k, 12) == 8 * k * 5
    # hier: dense 4n on ICI + 8k per cross-slice round
    assert comm_bytes_per_step("gtopk_hier", n, k, 12, ici_size=4) == (
        4 * n + 8 * k * tree_rounds(3))
    # allgather union: every device pulls p sets
    assert comm_bytes_per_step("allgather", n, k, 32) == 8 * k * 32
    # dense is codec-independent
    assert comm_bytes_per_step("dense", n, k, 32) == 4 * n
    assert comm_bytes_per_step("dense", n, k, 32, codec="int8") == 4 * n


def test_quantized_codec_bytes_hit_reduction_targets():
    """The acceptance numbers: at ResNet-20 scale the int8 wire is
    >= 3x smaller than fp32 at rho=0.001 and under the 0.30 gate bound
    at rho=0.01 (Elias-Fano index bits shrink as k grows)."""
    n = 272_474
    for name in ("int8", "fp8"):
        c = get_codec(name)
        k1 = max(1, -(-n // 1000))   # ceil(0.001 * n)
        k2 = max(1, -(-n // 100))    # ceil(0.01 * n)
        assert c.wire_set_bytes(k1, n) * 3 <= 8 * k1
        assert c.wire_set_bytes(k2, n) <= 0.30 * 8 * k2
        # comm model composes the same set bytes per round
        assert comm_bytes_per_step("gtopk", n, k1, 8, codec=name) == (
            c.wire_set_bytes(k1, n) * 3)


def test_get_codec_grammar():
    assert get_codec("fp32") is get_codec("fp32")
    assert get_codec("int8").block == 64
    assert get_codec("int8:128").block == 128
    assert get_codec("fp8:32").name == "fp8:32"
    c = get_codec("int8")
    assert get_codec(c) is c  # instance passthrough
    with pytest.raises(ValueError):
        get_codec("int4")
    with pytest.raises(ValueError):
        get_codec("int8:7")  # block must be a multiple of 4


# ---------------------------------------------------------------------------
# Roundtrip: indices lossless, values bounded by the block quant step.


@pytest.mark.parametrize("name", ["int8", "fp8", "int8:32", "fp8:128"])
@pytest.mark.parametrize("sentinels", [0, 3])
def test_roundtrip_lossless_indices_bounded_values(rng, name, sentinels):
    c = get_codec(name)
    k, n = 13, 1_000
    idx = np.full(k, n, np.int32)
    vals = np.zeros(k, np.float32)
    kk = k - sentinels
    idx[:kk] = rng.choice(n, size=kk, replace=False)
    vals[:kk] = rng.standard_normal(kk).astype(np.float32) * 10
    perm = rng.permutation(k)
    idx, vals = idx[perm], vals[perm]

    dv, di = jax.jit(
        lambda v, i: c.decode(c.encode(v, i, n=n), k=k, n=n)
    )(jnp.asarray(vals), jnp.asarray(idx))
    dv, di = np.asarray(dv), np.asarray(di)

    # Index coding is exactly lossless (as a sorted multiset).
    np.testing.assert_array_equal(np.sort(idx), np.sort(di))
    # Values come back index-sorted; error bounded by ~1 quant step of
    # the block max (int8) or the e4m3 relative precision (fp8).
    order = np.argsort(idx, kind="stable")
    sv = vals[order]
    qmax = 127.0 if name.startswith("int8") else 448.0
    bound = np.abs(sv).max() / qmax * 2.2 + 0.07 * np.abs(sv).max()
    assert np.abs(dv - sv).max() <= bound
    # Wire buffer size matches the byte accounting exactly.
    (wire,) = c.encode(jnp.asarray(vals), jnp.asarray(idx), n=n)
    assert wire.size * 4 == c.wire_set_bytes(k, n)
    # roundtrip_aligned returns the SAME dequantized values in the
    # ORIGINAL slot order (the optimizer's residual-fold contract).
    ra = np.asarray(roundtrip_aligned(
        c, jnp.asarray(vals), jnp.asarray(idx), n=n))
    np.testing.assert_array_equal(ra[order], dv)


def test_fp32_roundtrip_is_identity(rng):
    c = get_codec("fp32")
    vals = rng.standard_normal(K).astype(np.float32)
    idx = rng.choice(N, size=K, replace=False).astype(np.int32)
    dv, di = c.decode(c.encode(jnp.asarray(vals), jnp.asarray(idx), n=N),
                      k=K, n=N)
    np.testing.assert_array_equal(np.asarray(dv), vals)
    np.testing.assert_array_equal(np.asarray(di), idx)
    ra = roundtrip_aligned(c, jnp.asarray(vals), jnp.asarray(idx), n=N)
    np.testing.assert_array_equal(np.asarray(ra), vals)


# ---------------------------------------------------------------------------
# Partner symmetry through the tree: every rank decodes the bit-identical
# merged set, including non-pow2 masked folds and the hier ICI/DCN split.


@pytest.mark.parametrize("p", [3, 5, 6, 7])
@pytest.mark.parametrize("codec", ["int8", "fp8"])
def test_partner_symmetry_nonpow2(rng, p, codec):
    vals, idxs = make_sets(rng, p)
    mesh = make_mesh(p)
    gv, gi = run_collective(
        functools.partial(gtopk_allreduce, k=K, n=N, axis_name="dp",
                          axis_size=p, codec=codec),
        mesh, vals, idxs)
    for r in range(1, p):
        np.testing.assert_array_equal(gv[0], gv[r])
        np.testing.assert_array_equal(gi[0], gi[r])
    # Semantics survive quantization: the scattered result is close to
    # the fp32-wire result of the same inputs.
    fv, fi = run_collective(
        functools.partial(gtopk_allreduce, k=K, n=N, axis_name="dp",
                          axis_size=p, codec="fp32"),
        mesh, vals, idxs)
    got = np.zeros(N + 1, np.float32)
    np.add.at(got, gi[0], gv[0])
    want = np.zeros(N + 1, np.float32)
    np.add.at(want, fi[0], fv[0])
    # same support up to quantization-induced tau ties; compare values
    # only where both selected
    both = (got[:N] != 0) & (want[:N] != 0)
    assert both.sum() >= K - 2
    np.testing.assert_allclose(got[:N][both], want[:N][both],
                               rtol=0.15, atol=0.2)


@pytest.mark.parametrize("codec", ["int8", "fp8"])
@pytest.mark.parametrize("p,ici", [(8, 4), (6, 2)])
def test_hier_split_partner_symmetry(rng, codec, p, ici):
    """ICI/DCN split: slice-identical inputs (the ici_dense_psum
    precondition), quantized cross-slice tree — all p ranks must end
    bit-identical, pow2 and ragged slice counts alike."""
    n_slices = p // ici
    sv, si = make_sets(rng, n_slices)
    vals = np.repeat(sv, ici, axis=0)
    idxs = np.repeat(si, ici, axis=0)
    mesh = make_mesh(p)
    gv, gi = run_collective(
        functools.partial(hier_gtopk_allreduce, k=K, n=N, axis_name="dp",
                          axis_size=p, ici_size=ici, codec=codec),
        mesh, vals, idxs)
    for r in range(1, p):
        np.testing.assert_array_equal(gv[0], gv[r])
        np.testing.assert_array_equal(gi[0], gi[r])


@pytest.mark.parametrize("codec", ["fp32", "int8"])
def test_allgather_union_bit_identical(rng, codec):
    p = 8
    vals, idxs = make_sets(rng, p)
    mesh = make_mesh(p)
    dense = run_collective(
        functools.partial(topk_allgather, k=K, n=N, axis_name="dp",
                          axis_size=p, codec=codec),
        mesh, vals, idxs)
    for r in range(1, p):
        np.testing.assert_array_equal(dense[0], dense[r])


def test_fp32_codec_reproduces_precodec_tree(rng):
    """The fp32 identity must leave the tree bit-for-bit unchanged:
    explicit codec="fp32" equals the default-argument path on ragged p."""
    p = 6
    vals, idxs = make_sets(rng, p)
    mesh = make_mesh(p)
    a = run_collective(
        functools.partial(gtopk_allreduce, k=K, n=N, axis_name="dp",
                          axis_size=p),
        mesh, vals, idxs)
    b = run_collective(
        functools.partial(gtopk_allreduce, k=K, n=N, axis_name="dp",
                          axis_size=p, codec="fp32"),
        mesh, vals, idxs)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


# ---------------------------------------------------------------------------
# Error accounting: fold + repair compose to exact restoration.


def test_fold_wire_error_then_repair_restores_exact_value(rng):
    """A locally-picked, globally-rejected coordinate must find its FULL
    original value in the residual: the wire fold banks (vals - vq)
    before the collective, the repair banks vq after — their sum is the
    pre-quantization selection exactly (no codec error leaks)."""
    n, k = 64, 6
    comp = TopKCompressor(density=k / n)
    c = get_codec("int8:4")
    vals = (rng.standard_normal(k).astype(np.float32) * 3).astype(np.float32)
    idx = rng.choice(n, size=k, replace=False).astype(np.int32)
    vq = np.asarray(roundtrip_aligned(c, jnp.asarray(vals),
                                      jnp.asarray(idx), n=n))
    residual = jnp.zeros(n, jnp.float32)
    residual = comp.fold_wire_error(residual, jnp.asarray(idx),
                                    jnp.asarray(vals - vq))
    # Global set rejects the first three local picks.
    gidx = np.full(k, n, np.int32)
    gidx[:k - 3] = idx[3:]
    repaired = comp.repair(residual, jnp.asarray(vq), jnp.asarray(idx),
                           jnp.asarray(gidx))
    repaired = np.asarray(repaired)
    np.testing.assert_allclose(repaired[idx[:3]], vals[:3], rtol=1e-6)
    # Delivered picks keep only the (small) folded quant error.
    qstep = np.abs(vals).max() / 127.0
    assert np.abs(repaired[idx[3:]]).max() <= qstep * 1.1


# ---------------------------------------------------------------------------
# Satellite: convergence A/B — int8 wire tracks fp32 within tolerance.


def test_convergence_ab_int8_vs_fp32_wire(tmp_path, monkeypatch):
    """convergence_run.py arm suffix "+int8wire" trains, labels the arm,
    and lands within tolerance of the fp32 wire at identical seed/steps
    (codec error is absorbed by the error-feedback residual)."""
    import json
    import sys

    from tests.conftest import load_benchmark_module

    mod = load_benchmark_module("convergence_run")
    out = tmp_path / "conv_codec.jsonl"
    monkeypatch.setattr(sys, "argv", [
        "convergence_run.py", "--dnn", "resnet20", "--steps", "4",
        "--chunk", "2", "--batch-size", "4", "--eval-batches", "1",
        "--nworkers", "2", "--density", "0.01",
        "--modes", "gtopk,gtopk+int8wire",
        "--out", str(out),
    ])
    mod.main()
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    summary = {s["mode"]: s for s in rows[-1]["modes"]}
    assert set(summary) == {"gtopk", "gtopk+int8wire"}
    fp32_loss = summary["gtopk"]["final_loss"]
    int8_loss = summary["gtopk+int8wire"]["final_loss"]
    assert abs(int8_loss - fp32_loss) <= 0.15, (fp32_loss, int8_loss)
