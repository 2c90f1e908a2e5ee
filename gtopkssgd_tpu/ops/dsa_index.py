"""Pallas TPU kernels for the sparse-attention decoder's indexer
(``models/keye_vl2.py``): the per-head ``[heads, queries, keys]`` products
q^I.k^I, their ReLU, weighting and sum over the heads live in VMEM tiles;
only ``[queries, keys]`` arrays (the index scores out, their cotangent in)
reach HBM.

The mathematics is ``models/keye_vl2.py::index_scores``' and its
``jax.vjp``'s, rounding for rounding: I_ts = sum_j w_tj ReLU(qI_tj . kI_s)
with the products in ``dtype`` and float32 accumulation, the ReLU, the
weights and the sum over the heads in float32; backwards, d_dots_j =
d_I . w_j . [dots_j > 0] in float32 **rounded to ``dtype``** where it enters
the two products that autodiff's transposes make (the TPU's default
precision rounds a float32 operand so), d_w_j = sum_s d_I ReLU(dots_j) in
float32. What autodiff then rounds once more, d_qI and d_kI to ``dtype``,
the kernels hand on in float32. ``index_scores`` stays the oracle
(``tests/test_dsa_index_kernel.py``, interpret mode) and the path of every
backend but the TPU.

A call covers a span of a layer's rows (a bucket of query blocks) against
the keys up to the span's end, as ``dsa_attention.probabilities`` does; a
key tile wholly after a query tile's last row is not visited (its index map
stays on the last tile visited, so nothing is fetched for it). Layouts (the
whole layer's arrays, whatever the span):

  qi             [B, J, S, D] in ``dtype``     ki   [B, S, D] in ``dtype``
  w              [B, S, J] float32, a column a head beside a query tile's
                 rows; ``backward_k`` takes it as [B, J, S], along the lanes
                 of its transposed products
  scores, d_s    [B, rows, keys] float32, the span's rows alone

Three kernels: ``scores``, ``backward_q`` (d_qI and d_w: query tiles
outside, key tiles swept) and ``backward_k`` (d_kI: key tiles outside, query
tiles swept, the products made transposed so that d_dots^T . qI is a plain
product).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gtopkssgd_tpu.ops.dsa_attention import (
    F32, _NT, _first_query_tile, _last_key_tile, _params)

# Queries and keys a tile: a step makes J products of TILE_Q x TILE_K.
TILE_Q, TILE_K = 512, 512


def _span_tiles(length, span, tq, tk):
    """(tq, tk, the span's first query tile, its rows, its keys)."""
    tq, tk = min(tq or TILE_Q, length), min(tk or TILE_K, length)
    start, count = span
    keys = start + count
    if start % tq or count % tq or keys % tk or keys > length:
        raise ValueError(f"rows {start}..{keys} of {length}: not whole tiles "
                         f"of {tq} x {tk}")
    return tq, tk, start // tq, count, keys


def _row_specs(heads, tq, dim, tk, first):
    """Block specs for a grid (b, i, j) of the span's query tiles outside and
    the key tiles swept: of qi, ki, w (a column a head) and a [rows, keys]
    array of the span read tile by tile. Key tiles past the last one a query
    tile visits repeat its index, so nothing is fetched for them."""
    clamp = lambda i, j: jnp.minimum(j, _last_key_tile(first + i, tq, tk))
    return (pl.BlockSpec((None, heads, tq, dim),
                         lambda b, i, j: (b, 0, first + i, 0)),
            pl.BlockSpec((None, tk, dim), lambda b, i, j: (b, clamp(i, j), 0)),
            pl.BlockSpec((None, tq, heads), lambda b, i, j: (b, first + i, 0)),
            pl.BlockSpec((None, tq, tk), lambda b, i, j: (b, i, clamp(i, j))))


_SWEEP = ("parallel", "parallel", "arbitrary")


# ------------------------------------------------------------------- scores
def _scores_kernel(q_ref, k_ref, w_ref, s_ref, *, first):
    heads, tq, _ = q_ref.shape
    tk = k_ref.shape[0]
    i, j = pl.program_id(1), pl.program_id(2)
    visited = j <= _last_key_tile(first + i, tq, tk)

    @pl.when(visited)
    def _():
        k, w = k_ref[...], w_ref[...]
        acc = jnp.zeros((tq, tk), F32)
        for h in range(heads):
            dots = lax.dot_general(q_ref[h], k, _NT,
                                   preferred_element_type=F32)
            acc = acc + w[:, h:h + 1] * jnp.maximum(dots, 0.0)
        s_ref[...] = acc

    @pl.when(jnp.logical_not(visited))
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)


def scores(qi, ki, w, *, span, tile_q=None, tile_k=None, interpret=False):
    """I [B, rows, keys] float32 of the rows ``span`` = (first row, rows)
    against the keys up to the span's end, 0 in the tiles after a query
    tile's last row."""
    batch, heads, length, dim = qi.shape
    tq, tk, first, count, keys = _span_tiles(length, span, tile_q, tile_k)
    return pl.pallas_call(
        functools.partial(_scores_kernel, first=first),
        grid=(batch, count // tq, keys // tk),
        in_specs=list(_row_specs(heads, tq, dim, tk, first)[:3]),
        out_specs=pl.BlockSpec((None, tq, tk), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((batch, count, keys), F32),
        compiler_params=_params(_SWEEP),
        name="dsa_index_scores", interpret=interpret,
    )(qi, ki, w)


# ----------------------------------------------------------------- backward
def _backward_q_kernel(q_ref, k_ref, w_ref, ds_ref, dq_ref, dw_ref, dq_acc,
                       dw_acc, *, first, dtype):
    heads, tq, _ = q_ref.shape
    tk = k_ref.shape[0]
    i, j = pl.program_id(1), pl.program_id(2)
    last = _last_key_tile(first + i, tq, tk)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    @pl.when(j <= last)
    def _():
        k, w, d_s = k_ref[...], w_ref[...], ds_ref[...]
        columns = []
        for h in range(heads):
            dots = lax.dot_general(q_ref[h], k, _NT,
                                   preferred_element_type=F32)
            live = dots > 0.0
            columns.append(jnp.sum(jnp.where(live, dots * d_s, 0.0), axis=1,
                                   keepdims=True))
            d_dots = jnp.where(live, w[:, h:h + 1] * d_s, 0.0).astype(dtype)
            dq_acc[h] += jnp.dot(d_dots, k, preferred_element_type=F32)
        dw_acc[...] += jnp.concatenate(columns, axis=1)

    @pl.when(j == last)
    def _():
        dq_ref[...] = dq_acc[...]
        dw_ref[...] = dw_acc[...]


def backward_q(qi, ki, w, d_scores, *, span, dtype, tile_q=None, tile_k=None,
               interpret=False):
    """(d_qi [B, J, rows, D], d_w [B, rows, J]) float32 of the span's rows
    from ``d_scores`` [B, rows, keys] float32."""
    batch, heads, length, dim = qi.shape
    tq, tk, first, count, keys = _span_tiles(length, span, tile_q, tile_k)
    return pl.pallas_call(
        functools.partial(_backward_q_kernel, first=first, dtype=dtype),
        grid=(batch, count // tq, keys // tk),
        in_specs=list(_row_specs(heads, tq, dim, tk, first)),
        out_specs=[pl.BlockSpec((None, heads, tq, dim),
                                lambda b, i, j: (b, 0, i, 0)),
                   pl.BlockSpec((None, tq, heads), lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((batch, heads, count, dim), F32),
                   jax.ShapeDtypeStruct((batch, count, heads), F32)],
        scratch_shapes=[pltpu.VMEM((heads, tq, dim), F32),
                        pltpu.VMEM((tq, heads), F32)],
        compiler_params=_params(_SWEEP),
        name="dsa_index_backward_q", interpret=interpret,
    )(qi, ki, w, d_scores)


def _backward_k_kernel(q_ref, k_ref, w_ref, ds_ref, dk_ref, dk_acc, *, first,
                       tiles, dtype):
    """The products transposed, [tk, tq]: a head's weights lie along the
    lanes as ``w`` [J, S] holds them, and the sum over queries is a plain
    product."""
    heads, tq, _ = q_ref.shape
    tk = k_ref.shape[0]
    j, i = pl.program_id(1), pl.program_id(2)
    start = jnp.maximum(_first_query_tile(j, tq, tk) - first, 0)

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)

    @pl.when(i >= start)
    def _():
        k, w, d_s = k_ref[...], w_ref[...], ds_ref[...].T
        acc = jnp.zeros(dk_acc.shape, F32)
        for h in range(heads):
            q = q_ref[h]
            dots = lax.dot_general(k, q, _NT, preferred_element_type=F32)
            d_dots = jnp.where(dots > 0.0, w[h:h + 1] * d_s, 0.0
                               ).astype(dtype)
            acc = acc + jnp.dot(d_dots, q, preferred_element_type=F32)
        dk_acc[...] += acc

    @pl.when(i == tiles - 1)
    def _():
        dk_ref[...] = dk_acc[...]


def backward_k(qi, ki, w_rows, d_scores, *, span, dtype, tile_q=None,
               tile_k=None, interpret=False):
    """d_ki [B, keys, D] float32: what the span's rows give the keys up to
    the span's end. ``w_rows`` [B, J, S]."""
    batch, heads, length, dim = qi.shape
    tq, tk, first, count, keys = _span_tiles(length, span, tile_q, tile_k)
    clamp = lambda j, i: jnp.maximum(
        i, _first_query_tile(j, tq, tk) - first)
    return pl.pallas_call(
        functools.partial(_backward_k_kernel, first=first, tiles=count // tq,
                          dtype=dtype),
        grid=(batch, keys // tk, count // tq),
        in_specs=[pl.BlockSpec((None, heads, tq, dim),
                               lambda b, j, i: (b, 0, first + clamp(j, i), 0)),
                  pl.BlockSpec((None, tk, dim), lambda b, j, i: (b, j, 0)),
                  pl.BlockSpec((None, heads, tq),
                               lambda b, j, i: (b, 0, first + clamp(j, i))),
                  pl.BlockSpec((None, tq, tk),
                               lambda b, j, i: (b, clamp(j, i), j))],
        out_specs=pl.BlockSpec((None, tk, dim), lambda b, j, i: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, keys, dim), F32),
        scratch_shapes=[pltpu.VMEM((tk, dim), F32)],
        compiler_params=_params(_SWEEP),
        name="dsa_index_backward_k", interpret=interpret,
    )(qi, ki, w_rows, d_scores)
