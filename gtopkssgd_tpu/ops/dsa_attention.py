"""Pallas TPU kernels for the sparse-attention decoder's masked attention
(``models/keye_vl2.py``): the ``[heads, queries, keys]`` logits, weights and
``d_logits`` live in VMEM tiles and never reach HBM.

The mathematics is ``models/keye_vl2.py::attend_group``'s, rounding for
rounding: logits = q.k^T in ``dtype`` with float32 accumulation, scaled,
minus ``top`` (the caller's bound on a row's logits: no running maximum, so
nothing is rescaled), the keys outside ``keep`` masked out, ``exp`` in
float32 **rounded to ``dtype``**, the rounded weights' float32 sum and
weights.v accumulated in float32; the backward pass rounds
``w / total * (dE - mean)`` to ``dtype`` where ``_attend_group_bwd`` does.
``attend_group`` stays the oracle (``tests/test_dsa_attention_kernel.py``,
interpret mode) and the path of every backend but the TPU.

One call covers a layer's whole sequence, one key-value head's R query
heads folded into the rows of each product (a q tile is ``[R * tq, D]``). A
key tile wholly after a query tile's last row is not visited (its index map
stays on the last tile visited, so nothing is fetched for it). Layouts:

  q, d_out, o, d_q   [B, G, R, S, D]       k, v, d_k, d_v   [B, G, S, D]
  keep               [B, S, S] int8        (causality is the caller's:
                                            ``keep`` holds it already)
  top, total, ...    [B, G, R, S]          float32, a number a row (the
                                            queries along the lanes: with a
                                            last axis of 1 the TPU pads
                                            every number to a 128-lane row,
                                            256 MB an array at 16,384 tokens)

Four kernels: ``forward`` (o, total), ``probabilities`` (the head-mean of
w / total, what the indexer's loss needs: for a span of rows against the
keys up to the span's end, since [S, S] float32 is a gigabyte),
``backward_q`` (q tiles outside, key tiles swept) and ``backward_kv`` (key
tiles outside, q tiles swept, the logits made transposed so that a row's
numbers lie along the lanes).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
# Queries and keys a tile. A step multiplies R * TILE_Q rows by TILE_K keys.
TILE_Q, TILE_K = 512, 512
# A step's [R * tq, tk] float32 temporaries are megabytes each; the default
# scoped limit (16 MiB) is under what the v5e's 128 MiB of VMEM allow.
VMEM_LIMIT = 96 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))          # a [m, d] x [n, d] -> [m, n] product


def _params(semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def _bias(keep):
    """int8 0/1 -> float32 -inf/0: added to a logit, it masks the key."""
    return jnp.where(keep.astype(F32) > 0.0, 0.0, -jnp.inf)


def _column(rows):
    """A tile's numbers a row, [R, tq] as the arrays hold them, as the
    column [R * tq, 1] beside a q tile's rows (head by head)."""
    across = rows.T
    return jnp.concatenate([across[:, r:r + 1]
                            for r in range(rows.shape[0])], axis=0)


def _rows(column, heads):
    """The inverse: [R * tq, 1] -> [R, tq]."""
    tq = column.shape[0] // heads
    return jnp.concatenate([column[r * tq:(r + 1) * tq]
                            for r in range(heads)], axis=1).T


def _lanes(rows):
    """[R, tq] -> [1, R * tq]: beside the transposed logits' columns."""
    return jnp.concatenate([rows[r:r + 1] for r in range(rows.shape[0])],
                           axis=1)


def _weights(q, k, keep, top, scale, dtype):
    """exp(q.k^T * scale - top) over the kept keys, rounded to ``dtype``:
    q [R * tq, D], k [tk, D], keep [tq, tk] int8, top [R * tq, 1]."""
    rows, tq, tk = q.shape[0], keep.shape[0], keep.shape[1]
    logits = lax.dot_general(q, k, _NT, preferred_element_type=F32) * scale \
        - top
    logits = logits.reshape(rows // tq, tq, tk) + _bias(keep)[None]
    return jnp.exp(logits).reshape(rows, tk).astype(dtype)


def _last_key_tile(i, tq, tk):
    """The last key tile that holds a key not after query tile i's rows."""
    return ((i + 1) * tq - 1) // tk


def _first_query_tile(j, tq, tk):
    """The first query tile with a row not before key tile j's keys."""
    return j * tk // tq


def _tiles(length, tq, tk):
    """The tile sizes of a call (``TILE_Q`` x ``TILE_K`` unless given)."""
    tq, tk = min(tq or TILE_Q, length), min(tk or TILE_K, length)
    if length % tq or length % tk:
        raise ValueError(f"{length} tokens: not whole tiles of {tq} x {tk}")
    return tq, tk


def _row_specs(heads, tq, dim, tk):
    """Block specs of a [B, G, R, S, D] array (``rows(dim)``) or a
    [B, G, R, S] one (``rows()``), of k / v and of ``keep`` for a grid
    (b, g, i, j): key tiles past the last one a query tile visits repeat
    its index, so nothing is fetched for them."""
    clamp = lambda i, j: jnp.minimum(j, _last_key_tile(i, tq, tk))
    rows = lambda *width: pl.BlockSpec(
        (None, None, heads, tq) + width,
        lambda b, g, i, j: (b, g, 0, i) + (0,) * len(width))
    keys = pl.BlockSpec((None, None, tk, dim),
                        lambda b, g, i, j: (b, g, clamp(i, j), 0))
    keep = pl.BlockSpec((None, tq, tk),
                        lambda b, g, i, j: (b, i, clamp(i, j)))
    return rows, keys, keep


_SWEEP = ("parallel", "parallel", "parallel", "arbitrary")


# ------------------------------------------------------------------ forward
def _forward_kernel(q_ref, k_ref, v_ref, keep_ref, top_ref, o_ref, total_ref,
                    acc_ref, sum_ref, *, scale, dtype):
    heads, tq, dim = q_ref.shape
    rows, tk = heads * tq, k_ref.shape[0]
    i, j = pl.program_id(2), pl.program_id(3)
    last = _last_key_tile(i, tq, tk)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        sum_ref[...] = jnp.zeros_like(sum_ref)

    @pl.when(j <= last)
    def _():
        w = _weights(q_ref[...].reshape(rows, dim), k_ref[...], keep_ref[...],
                     _column(top_ref[...]), scale, dtype)
        sum_ref[...] += jnp.sum(w.astype(F32), axis=1, keepdims=True)
        acc_ref[...] += jnp.dot(w, v_ref[...], preferred_element_type=F32)

    @pl.when(j == last)
    def _():
        total = sum_ref[...]
        o_ref[...] = (acc_ref[...] / total).reshape(heads, tq, dim)
        total_ref[...] = _rows(total, heads)


def forward(q, k, v, keep, top, *, dtype, tile_q=None, tile_k=None,
            interpret=False):
    """(o [B, G, R, S, D] float32, total [B, G, R, S] float32)."""
    batch, groups, heads, length, dim = q.shape
    tq, tk = _tiles(length, tile_q, tile_k)
    rows, keys, mask = _row_specs(heads, tq, dim, tk)
    return pl.pallas_call(
        functools.partial(_forward_kernel, scale=1.0 / math.sqrt(dim),
                          dtype=dtype),
        grid=(batch, groups, length // tq, length // tk),
        in_specs=[rows(dim), keys, keys, mask, rows()],
        out_specs=[rows(dim), rows()],
        out_shape=[jax.ShapeDtypeStruct(q.shape, F32),
                   jax.ShapeDtypeStruct(top.shape, F32)],
        scratch_shapes=[pltpu.VMEM((heads * tq, dim), F32),
                        pltpu.VMEM((heads * tq, 1), F32)],
        compiler_params=_params(_SWEEP),
        name="dsa_attention_forward", interpret=interpret,
    )(q, k, v, keep, top)


# ------------------------------------------------------------ probabilities
def _probabilities_kernel(q_ref, k_ref, keep_ref, top_ref, inv_ref, p_ref, *,
                          first, scale, dtype, groups):
    heads, tq, dim = q_ref.shape
    rows, tk = heads * tq, k_ref.shape[0]
    i, j, g = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    visited = j <= _last_key_tile(first + i, tq, tk)

    @pl.when(g == 0)
    def _():
        p_ref[...] = jnp.zeros_like(p_ref)

    @pl.when(visited)
    def _():
        w = _weights(q_ref[...].reshape(rows, dim), k_ref[...], keep_ref[...],
                     _column(top_ref[...]), scale, dtype)
        share = w.astype(F32) * _column(inv_ref[...])
        p_ref[...] += jnp.sum(share.reshape(heads, tq, tk), axis=0)

    @pl.when(visited & (g == groups - 1))
    def _():
        p_ref[...] = p_ref[...] / (groups * heads)


def probabilities(q, k, keep, top, inv_total, *, span, dtype, tile_q=None,
                  tile_k=None, interpret=False):
    """p [B, rows, keys] float32 of the rows ``span`` = (first row, rows)
    against the keys up to the span's end: the mean over all G * R heads of
    w / total (``inv_total`` = 1 / total), 0 in the tiles after a query
    tile's last row. The arguments are the whole layer's."""
    batch, groups, heads, length, dim = q.shape
    start, count = span
    tq, tk = _tiles(length, tile_q, tile_k)
    keys = start + count
    if start % tq or count % tq or keys % tk:
        raise ValueError(f"rows {start}..{keys}: not whole tiles of "
                         f"{tq} x {tk}")
    first = start // tq
    clamp = lambda i, j: jnp.minimum(j, _last_key_tile(first + i, tq, tk))
    rows = lambda *width: pl.BlockSpec(
        (None, None, heads, tq) + width,
        lambda b, i, j, g: (b, g, 0, first + i) + (0,) * len(width))
    return pl.pallas_call(
        functools.partial(_probabilities_kernel, first=first,
                          scale=1.0 / math.sqrt(dim), dtype=dtype,
                          groups=groups),
        grid=(batch, count // tq, keys // tk, groups),
        in_specs=[rows(dim),
                  pl.BlockSpec((None, None, tk, dim),
                               lambda b, i, j, g: (b, g, clamp(i, j), 0)),
                  pl.BlockSpec((None, tq, tk),
                               lambda b, i, j, g: (b, first + i, clamp(i, j))),
                  rows(), rows()],
        out_specs=pl.BlockSpec((None, tq, tk), lambda b, i, j, g: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((batch, count, keys), F32),
        compiler_params=_params(_SWEEP),
        name="dsa_attention_probabilities", interpret=interpret,
    )(q, k, keep, top, inv_total)


# ----------------------------------------------------------------- backward
def _backward_q_kernel(q_ref, k_ref, v_ref, keep_ref, top_ref, inv_ref,
                       mean_ref, do_ref, dq_ref, acc_ref, *, scale, dtype):
    heads, tq, dim = q_ref.shape
    rows, tk = heads * tq, k_ref.shape[0]
    i, j = pl.program_id(2), pl.program_id(3)
    last = _last_key_tile(i, tq, tk)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j <= last)
    def _():
        k = k_ref[...]
        w = _weights(q_ref[...].reshape(rows, dim), k, keep_ref[...],
                     _column(top_ref[...]), scale, dtype)
        d_e = lax.dot_general(do_ref[...].reshape(rows, dim), v_ref[...], _NT,
                              preferred_element_type=F32)
        d_logits = (w.astype(F32) * _column(inv_ref[...])
                    * (d_e - _column(mean_ref[...]))).astype(dtype)
        acc_ref[...] += jnp.dot(d_logits, k, preferred_element_type=F32)

    @pl.when(j == last)
    def _():
        dq_ref[...] = (acc_ref[...] * scale).reshape(heads, tq, dim)


def backward_q(q, k, v, keep, top, inv_total, mean, d_out, *, dtype,
               tile_q=None, tile_k=None, interpret=False):
    """d_q [B, G, R, S, D] float32. ``d_out`` in ``dtype``; ``mean`` =
    sum_d d_out * o, a number a row, float32."""
    batch, groups, heads, length, dim = q.shape
    tq, tk = _tiles(length, tile_q, tile_k)
    rows, keys, mask = _row_specs(heads, tq, dim, tk)
    return pl.pallas_call(
        functools.partial(_backward_q_kernel, scale=1.0 / math.sqrt(dim),
                          dtype=dtype),
        grid=(batch, groups, length // tq, length // tk),
        in_specs=[rows(dim), keys, keys, mask, rows(), rows(), rows(),
                  rows(dim)],
        out_specs=rows(dim),
        out_shape=jax.ShapeDtypeStruct(q.shape, F32),
        scratch_shapes=[pltpu.VMEM((heads * tq, dim), F32)],
        compiler_params=_params(_SWEEP),
        name="dsa_attention_backward_q", interpret=interpret,
    )(q, k, v, keep, top, inv_total, mean, d_out)


def _backward_kv_kernel(q_ref, k_ref, v_ref, keep_ref, top_ref, inv_ref,
                        mean_ref, do_ref, dos_ref, dk_ref, dv_ref, dk_acc,
                        dv_acc, *, scale, dtype, tiles):
    """The logits transposed, [tk, R * tq]: a row's ``top``, 1 / total and
    ``mean`` lie along the lanes as the arrays hold them, and the sums over
    queries are plain products."""
    heads, tq, dim = q_ref.shape
    rows, tk = heads * tq, k_ref.shape[0]
    j, i = pl.program_id(2), pl.program_id(3)

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(i >= _first_query_tile(j, tq, tk))
    def _():
        q, v = q_ref[...].reshape(rows, dim), v_ref[...]
        logits = lax.dot_general(k_ref[...], q, _NT,
                                 preferred_element_type=F32) * scale \
            - _lanes(top_ref[...])
        bias = _bias(keep_ref[...])                     # [tk, tq]
        w = jnp.exp(logits + jnp.concatenate([bias] * heads, axis=1)
                    ).astype(dtype)
        dv_acc[...] += jnp.dot(w, dos_ref[...].reshape(rows, dim),
                               preferred_element_type=F32)
        d_e = lax.dot_general(v, do_ref[...].reshape(rows, dim), _NT,
                              preferred_element_type=F32)
        d_logits = (w.astype(F32) * _lanes(inv_ref[...])
                    * (d_e - _lanes(mean_ref[...]))).astype(dtype)
        dk_acc[...] += jnp.dot(d_logits, q, preferred_element_type=F32)

    @pl.when(i == tiles - 1)
    def _():
        dk_ref[...] = dk_acc[...] * scale
        dv_ref[...] = dv_acc[...]


def backward_kv(q, k, v, keep_t, top, inv_total, mean, d_out, d_out_scaled,
                *, dtype, tile_q=None, tile_k=None, interpret=False):
    """(d_k, d_v) [B, G, S, D] float32. ``keep_t`` [B, S, S] int8 (the mask
    transposed: keys by queries); ``d_out_scaled`` = (d_out / total) in
    ``dtype``."""
    batch, groups, heads, length, dim = q.shape
    tq, tk = _tiles(length, tile_q, tile_k)
    clamp = lambda j, i: jnp.maximum(i, _first_query_tile(j, tq, tk))
    rows = pl.BlockSpec((None, None, heads, tq, dim),
                        lambda b, g, j, i: (b, g, 0, clamp(j, i), 0))
    lanes = pl.BlockSpec((None, None, heads, tq),
                         lambda b, g, j, i: (b, g, 0, clamp(j, i)))
    keys = pl.BlockSpec((None, None, tk, dim),
                        lambda b, g, j, i: (b, g, j, 0))
    return pl.pallas_call(
        functools.partial(_backward_kv_kernel, scale=1.0 / math.sqrt(dim),
                          dtype=dtype, tiles=length // tq),
        grid=(batch, groups, length // tk, length // tq),
        in_specs=[rows, keys, keys,
                  pl.BlockSpec((None, tk, tq),
                               lambda b, g, j, i: (b, j, clamp(j, i))),
                  lanes, lanes, lanes, rows, rows],
        out_specs=[keys, keys],
        out_shape=[jax.ShapeDtypeStruct(k.shape, F32)] * 2,
        scratch_shapes=[pltpu.VMEM((tk, dim), F32)] * 2,
        compiler_params=_params(_SWEEP),
        name="dsa_attention_backward_kv", interpret=interpret,
    )(q, k, v, keep_t, top, inv_total, mean, d_out, d_out_scaled)
