"""Pallas TPU kernels for the decoders' causal and sliding-window softmax
attention (``models/decoder.py::blocked_causal_attention``): the
``[heads, queries, keys]`` scores, weights and ``d_logits`` live in VMEM
tiles and never reach HBM.

Query t attends to the keys s with 0 <= t - s < ``window`` (``window``
None: 0 <= t - s). Both bounds are arithmetic on a tile's two indices: no
mask array exists anywhere, only a band's edge tiles make the comparison
(by ``iota``), and **a key tile wholly outside the band is neither visited
nor fetched**: the grid's last axis is as long as the widest query tile's
band (5 key tiles of 32 at a window of 2,048 and tiles of 512; every tile
up to the diagonal without a window, where the steps past it repeat the
last tile's index, so nothing is fetched for them).

A second rule, **block diffusion** (``block_length`` given;
``models/sdar.py``), is no band: the rows are a sequence's ``half`` clean
tokens and then its ``half`` noised ones, and with b(t) = t // block_length
on a row's position in its own half, a clean query sees the clean keys with
b(s) <= b(t); a noised query sees the clean keys with b(s) < b(t) and the
noised keys with b(s) = b(t), and no query any other key. The same contract
holds: a clean query tile sweeps the clean key tiles up to its own; a noised
one the clean key tiles up to its own and then its own noised tile; a clean
key tile is swept by the clean query tiles from its own on and the noised
ones that see it, a noised key tile by its own query tile. At 2 x 8,192
rows and tiles of 512 that is 288 tiles visited a head group of 1,024, and
the three kinds of edge tile are masked by ``iota`` arithmetic (blocks are
a power of two and divide a tile).

The precision is the XLA form's: q.k^T in the arrays' dtype with float32
accumulation, scale, maximum, ``exp`` and row sums in float32, the weights
rounded to that dtype for the a.v product, float32 accumulation and a
float32 output. The softmax is the online one (a running maximum m and sum
l, rescaled where the maximum rises), so what is rounded is the weight
before its normalisation, with the same relative rounding. The backward
pass makes p = exp(logits - lse) again from the rows' log-sum-exp
``lse`` = m + log l and rounds p and ``d_logits`` = p * (dP - delta) to the
dtype for their products; d_q, d_k, d_v are float32.

One call covers a layer's whole sequence, one key-value head's R query
heads folded into the rows of each product (a q tile is ``[R * tq, D]``: a
key tile is fetched once for its R heads). Keys may be wider than values
(latent attention: D = 192 beside D_v = 128): q and k have the key width
D, which also sets the scale 1 / sqrt(D), v and o the value width D_v, and
a block's last axis is its array's. Layouts, as ``ops/dsa_attention.py``'s:

  q, d_q       [B, G, R, S, D]        k, d_k   [B, G, S, D]
  d_out, o     [B, G, R, S, D_v]      v, d_v   [B, G, S, D_v]
  lse, delta   [B, G, R, S]           float32, a number a row, the queries
                                      along the lanes (a last axis of 1
                                      would cost 128 lanes a number)

Three kernels: ``forward`` (o, lse), ``backward_q`` (q tiles outside, the
band's key tiles swept) and ``backward_kv`` (key tiles outside, the query
tiles that see them swept). ``forward`` and ``backward_kv`` make the logits
transposed, [tk, R * tq], so that a row's numbers (m, l, lse, delta) lie
along the lanes as the arrays hold them: as columns [R * tq, 1] they cost a
register for every 8 rows, and the forward kernel, which touches m and l at
every step, ran at 48% of the MXU's peak so and runs at 64% transposed
(24.0 -> 18.0 ms a causal layer of 16,384 tokens; PERF.md section 6, PR 36).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.numpy import log as _ln   # graftlint reads any x.log(...) as a metrics call

from gtopkssgd_tpu.ops.dsa_attention import (
    _NT, _SWEEP, _column, _lanes, _params, _tiles as _whole_tiles)

F32 = jnp.float32
# Queries and keys a tile. A step multiplies R * TILE_Q rows by TILE_K keys.
TILE_Q, TILE_K = 512, 512
# What a key outside the band scores: finite, so that a row whose first
# visited tile holds none of its keys has a maximum to subtract (its weights
# there are wiped when a real key raises the maximum; every row sees itself).
MASKED = -0.7 * float(jnp.finfo(F32).max)
_TN = (((0,), (0,)), ((), ()))          # a [n, d] x [n, m] -> [d, m] product


def _tiles(length, tq, tk):
    """The tile sizes of a call (``TILE_Q`` x ``TILE_K`` unless given)."""
    return _whole_tiles(length, tq or TILE_Q, tk or TILE_K)


def _at_least_0(x):
    return max(x, 0) if isinstance(x, int) else jnp.maximum(x, 0)


def _at_most(x, top):
    return min(x, top) if isinstance(x, int) else jnp.minimum(x, top)


# The band, in tiles; ``i`` and ``j`` are ints or traced grid indices.
def first_key_tile(i, tq, tk, window):
    """The key tile of the oldest key query tile i's first row sees."""
    return 0 * i if window is None \
        else _at_least_0(i * tq - window + 1) // tk


def last_key_tile(i, tq, tk):
    """The key tile of query tile i's last row."""
    return ((i + 1) * tq - 1) // tk


def first_query_tile(j, tq, tk):
    """The query tile of key tile j's first key."""
    return j * tk // tq


def last_query_tile(j, tq, tk, window, length):
    """The query tile of the last row that sees key tile j's last key."""
    last = length // tq - 1
    return last + 0 * j if window is None \
        else _at_most(((j + 1) * tk + window - 2) // tq, last)


def key_tiles(length, tq, tk, window):
    """[(first, last) key tile of each query tile]."""
    return [(first_key_tile(i, tq, tk, window), last_key_tile(i, tq, tk))
            for i in range(length // tq)]


def query_tiles(length, tq, tk, window):
    """[(first, last) query tile of each key tile]."""
    return [(first_query_tile(j, tq, tk),
             last_query_tile(j, tq, tk, window, length))
            for j in range(length // tk)]


def _sweep(spans):
    """The grid's last axis: the longest of ``spans``."""
    return max(last - first + 1 for first, last in spans)


def _window(window, length):
    """A window that cuts no pair is none."""
    return None if window is None or window >= length else window


def _inside(i, j, tq, tk, window):
    """Whether every pair of query tile i and key tile j is in the band."""
    seen = i * tq >= (j + 1) * tk - 1
    return seen if window is None \
        else seen & ((i + 1) * tq - 1 - j * tk < window)


def _pick(cond, a, b):
    """``a`` where ``cond`` else ``b``, of ints or of traced grid indices."""
    return (a if cond else b) if isinstance(cond, (bool, int)) \
        else jnp.where(cond, a, b)


class Band:
    """Query t sees the keys s with 0 <= t - s (< ``window``): a tile's
    schedule by the functions above."""

    def __init__(self, window, length, tq, tk):
        self.window, self.length, self.tq, self.tk = window, length, tq, tk

    def key_steps(self):
        return _sweep(key_tiles(self.length, self.tq, self.tk, self.window))

    def query_steps(self):
        return _sweep(query_tiles(self.length, self.tq, self.tk, self.window))

    def key_tile(self, i, at):
        """The key tile of query tile i's sweep step ``at``."""
        return first_key_tile(i, self.tq, self.tk, self.window) + at

    def visits_key(self, i, at, j):
        """Whether that step is one of the tile's visits."""
        return j <= last_key_tile(i, self.tq, self.tk)

    def fetched_key_tile(self, i, at):
        """The tile the step's block holds: past the last visit the last
        tile's again, so nothing is fetched."""
        return jnp.minimum(
            first_key_tile(i, self.tq, self.tk, self.window) + at,
            last_key_tile(i, self.tq, self.tk))

    def query_tile(self, j, at):
        return first_query_tile(j, self.tq, self.tk) + at

    def visits_query(self, j, at, i):
        return i <= last_query_tile(j, self.tq, self.tk, self.window,
                                    self.length)

    def fetched_query_tile(self, j, at):
        return jnp.minimum(
            first_query_tile(j, self.tq, self.tk) + at,
            last_query_tile(j, self.tq, self.tk, self.window, self.length))

    def inside(self, i, j):
        return _inside(i, j, self.tq, self.tk, self.window)

    def seen(self, rows, keys, i, j):
        apart = rows - keys
        return apart >= 0 if self.window is None \
            else (apart >= 0) & (apart < self.window)


class BlockDiffusion:
    """Rows [clean; noised] of ``length`` / 2 each under the block-diffusion
    rule with blocks of ``block`` (the module's docstring). A tile lies in
    one half (``length`` / 2 is whole tiles) and holds whole blocks; i and j
    count tiles over both halves, ints or traced grid indices alike."""

    @staticmethod
    def fits(block, half, tq, tk):
        """Whether halves of ``half`` rows in blocks of ``block`` can run at
        these tiles: a power of two under a tile, whole tiles a half."""
        return block & (block - 1) == 0 and block < min(tq, tk) \
            and half % tq == 0 and half % tk == 0

    def __init__(self, block, length, tq, tk):
        half = length // 2
        if 2 * half != length or not self.fits(block, half, tq, tk):
            raise ValueError(
                f"blocks of {block} in halves of {half}: a power of two "
                f"under whole tiles of {tq} x {tk} in each half")
        self.block, self.shift = block, block.bit_length() - 1
        self.half, self.tq, self.tk = half, tq, tk
        self.nq, self.nk = half // tq, half // tk

    def key_visits(self, i):
        """Of query tile i: (the clean key tiles it sweeps, from tile 0: up
        to its last row's block, a noised tile short of it; the first key
        tile of its own noised rows; all the tiles it sweeps, a noised tile
        its own rows' after the clean ones)."""
        tq, tk = self.tq, self.tk
        noised = i // self.nq
        p = i - noised * self.nq
        clean = ((p + 1) * tq - noised * self.block - 1) // tk + 1
        first = p * tq // tk
        own = ((p + 1) * tq - 1) // tk - first + 1
        return clean, self.nk + first, clean + noised * own

    def key_tile(self, i, at):
        clean, own, _ = self.key_visits(i)
        return _pick(at < clean, at, own + at - clean)

    def visits_key(self, i, at, j):
        return at < self.key_visits(i)[2]

    def fetched_key_tile(self, i, at):
        return self.key_tile(i, jnp.minimum(at, self.key_visits(i)[2] - 1))

    def query_visits(self, j):
        """Of key tile j: (the first clean query tile that sweeps it and
        how many do, the first noised one and how many). A clean key tile:
        the clean query tiles from its own on and the noised ones from its
        first key's next block on; a noised one: its own rows' tiles."""
        tq, tk, nq = self.tq, self.tk, self.nq
        noised = j // self.nk
        p = j - noised * self.nk
        first = p * tk // tq
        own = ((p + 1) * tk - 1) // tq - first + 1
        later = (p * tk + self.block) // tq
        return (first, (1 - noised) * (nq - first),
                nq + _pick(noised > 0, first, later),
                _pick(noised > 0, own, _at_least_0(nq - later)))

    def query_tile(self, j, at):
        first, clean, first_noised, _ = self.query_visits(j)
        return _pick(at < clean, first + at, first_noised + at - clean)

    def visits_query(self, j, at, i):
        _, clean, _, noised = self.query_visits(j)
        return at < clean + noised

    def fetched_query_tile(self, j, at):
        _, clean, _, noised = self.query_visits(j)
        return self.query_tile(j, jnp.minimum(at, clean + noised - 1))

    def key_steps(self):
        return max(self.key_visits(i)[2] for i in range(2 * self.nq))

    def query_steps(self):
        return max(clean + noised for _, clean, _, noised in
                   map(self.query_visits, range(2 * self.nk)))

    def inside(self, i, j):
        """No pair of the tile is cut: a clean key tile whose last block is
        the query tile's first block or earlier (a noised query: earlier)."""
        noised = i // self.nq
        first_block = ((i - noised * self.nq) * self.tq) >> self.shift
        last_block = ((j + 1) * self.tk - 1) >> self.shift
        return (j < self.nk) & (last_block <= first_block - noised)

    def seen(self, rows, keys, i, j):
        """b(key) <= b(query) for a clean query, < for a noised one on a
        clean key tile, = on a noised one: as two bounds on b(key), so that
        a tile's kind is arithmetic on its indices and no select."""
        noised, key_noised = i // self.nq, j // self.nk
        qb = (rows - noised * self.half) >> self.shift
        kb = (keys - key_noised * self.half) >> self.shift
        return (kb <= qb - noised * (1 - key_noised)) & (kb >= qb * key_noised)


def visited_tiles(length, tq, tk, block):
    """[[the key tiles query tile i visits, in order]] under the
    block-diffusion rule: what the forward kernel's grid fetches."""
    rule = BlockDiffusion(block, length, tq, tk)
    return [[rule.key_tile(i, at) for at in range(rule.key_visits(i)[2])]
            for i in range(length // tq)]


def _rule(window, block_length, length, tile_q, tile_k):
    """The call's rule with its tile sizes: a band unless a block length
    is given."""
    if block_length is not None:
        tq, tk = _tiles(length // 2, tile_q, tile_k)
        if window is not None:
            raise ValueError("a window and a block length: one rule a call")
        return BlockDiffusion(block_length, length, tq, tk)
    tq, tk = _tiles(length, tile_q, tile_k)
    return Band(_window(window, length), length, tq, tk)


def _bias(rule, i, j, transposed=False):
    """The rule inside a tile, as what is added to a logit: 0 for a pair it
    lets through, ``MASKED`` for another. [tq, tk], or [tk, tq] for the
    transposed logits."""
    tq, tk = rule.tq, rule.tk
    shape = (tk, tq) if transposed else (tq, tk)
    rows = i * tq + lax.broadcasted_iota(jnp.int32, shape, int(transposed))
    keys = j * tk + lax.broadcasted_iota(jnp.int32, shape,
                                         int(not transposed))
    return jnp.where(rule.seen(rows, keys, i, j), 0.0, MASKED)


def _edges(visited, inside, step):
    """``step(masked)`` for a visited tile: without the comparison where
    the whole tile is inside the rule."""
    pl.when(visited & inside)(lambda: step(False))
    pl.when(visited & jnp.logical_not(inside))(lambda: step(True))


def _row_specs(heads, rule):
    """Block specs for a grid (b, g, i, step) that sweeps query tile i's
    key tiles: of a [B, G, R, S, D] array (``rows(dim)``) or a
    [B, G, R, S] one (``rows()``), and of k / v (``keys(dim)``). A step
    past the tile's last key tile repeats its index, so nothing is fetched
    for it."""
    rows = lambda *width: pl.BlockSpec(
        (None, None, heads, rule.tq) + width,
        lambda b, g, i, step: (b, g, 0, i) + (0,) * len(width))
    keys = lambda width: pl.BlockSpec(
        (None, None, rule.tk, width),
        lambda b, g, i, step: (b, g, rule.fetched_key_tile(i, step), 0))
    return rows, keys


# ------------------------------------------------------------------ forward
def _forward_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                    acc_ref, *, scale, rule):
    """The logits transposed, [tk, R * tq], as in ``_backward_kv_kernel``:
    a row's maximum and sum are reductions over sublanes and lie along the
    lanes ([1, R * tq]: 32 registers, where a column [R * tq, 1] takes 512),
    and so does what rescales the accumulator, [D, R * tq]."""
    heads, tq, dim = q_ref.shape
    rows, tk = heads * tq, k_ref.shape[0]
    i, at = pl.program_id(2), pl.program_id(3)
    j = rule.key_tile(i, at)

    @pl.when(at == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked):
        v = v_ref[...]
        logits = lax.dot_general(k_ref[...], q_ref[...].reshape(rows, dim),
                                 _NT, preferred_element_type=F32) * scale
        if masked:
            logits = logits + jnp.concatenate(
                [_bias(rule, i, j, transposed=True)] * heads, axis=1)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(logits, axis=0, keepdims=True))
        fade = jnp.exp(m - m_new)
        w = jnp.exp(logits - m_new)
        m_ref[...] = m_new
        l_ref[...] = fade * l_ref[...] + jnp.sum(w, axis=0, keepdims=True)
        acc_ref[...] = fade * acc_ref[...] + lax.dot_general(
            v, w.astype(v.dtype), _TN, preferred_element_type=F32)

    _edges(rule.visits_key(i, at, j), rule.inside(i, j), step)

    @pl.when(at == pl.num_programs(3) - 1)
    def _():
        total = l_ref[...]
        o_ref[...] = (acc_ref[...] / total).T.reshape(o_ref.shape)
        lse = m_ref[...] + _ln(total)
        lse_ref[...] = jnp.concatenate(
            [lse[:, r * tq:(r + 1) * tq] for r in range(heads)], axis=0)


def forward(q, k, v, *, window=None, block_length=None, tile_q=None,
            tile_k=None, interpret=False):
    """(o [B, G, R, S, D_v] float32, lse [B, G, R, S] float32)."""
    batch, groups, heads, length, dim = q.shape
    value_dim = v.shape[-1]
    rule = _rule(window, block_length, length, tile_q, tile_k)
    rows, keys = _row_specs(heads, rule)
    tq = rule.tq
    return pl.pallas_call(
        functools.partial(_forward_kernel, scale=1.0 / math.sqrt(dim),
                          rule=rule),
        grid=(batch, groups, length // tq, rule.key_steps()),
        in_specs=[rows(dim), keys(dim), keys(value_dim)],
        out_specs=[rows(value_dim), rows()],
        out_shape=[jax.ShapeDtypeStruct(q.shape[:-1] + (value_dim,), F32),
                   jax.ShapeDtypeStruct(q.shape[:-1], F32)],
        scratch_shapes=[pltpu.VMEM((1, heads * tq), F32),
                        pltpu.VMEM((1, heads * tq), F32),
                        pltpu.VMEM((value_dim, heads * tq), F32)],
        compiler_params=_params(_SWEEP),
        name="flash_attention_forward", interpret=interpret,
    )(q, k, v)


# ----------------------------------------------------------------- backward
def _backward_q_kernel(q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref,
                       dq_ref, acc_ref, *, scale, rule):
    heads, tq, dim = q_ref.shape
    rows, tk = heads * tq, k_ref.shape[0]
    i, at = pl.program_id(2), pl.program_id(3)
    j = rule.key_tile(i, at)

    @pl.when(at == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked):
        k = k_ref[...]
        logits = lax.dot_general(q_ref[...].reshape(rows, dim), k, _NT,
                                 preferred_element_type=F32) * scale
        if masked:
            logits = (logits.reshape(heads, tq, tk)
                      + _bias(rule, i, j)[None]).reshape(rows, tk)
        p = jnp.exp(logits - _column(lse_ref[...]))
        d_p = lax.dot_general(do_ref[...].reshape(rows, -1), v_ref[...], _NT,
                              preferred_element_type=F32)
        d_logits = (p * (d_p - _column(delta_ref[...]))).astype(k.dtype)
        acc_ref[...] += jnp.dot(d_logits, k, preferred_element_type=F32)

    _edges(rule.visits_key(i, at, j), rule.inside(i, j), step)

    @pl.when(at == pl.num_programs(3) - 1)
    def _():
        dq_ref[...] = (acc_ref[...] * scale).reshape(heads, tq, dim)


def backward_q(q, k, v, lse, delta, d_out, *, window=None, block_length=None,
               tile_q=None, tile_k=None, interpret=False):
    """d_q [B, G, R, S, D] float32. ``d_out`` [B, G, R, S, D_v] in the
    dtype of q, k, v; ``delta`` = sum_d d_out * o, a number a row, float32."""
    batch, groups, heads, length, dim = q.shape
    value_dim = v.shape[-1]
    rule = _rule(window, block_length, length, tile_q, tile_k)
    rows, keys = _row_specs(heads, rule)
    tq = rule.tq
    return pl.pallas_call(
        functools.partial(_backward_q_kernel, scale=1.0 / math.sqrt(dim),
                          rule=rule),
        grid=(batch, groups, length // tq, rule.key_steps()),
        in_specs=[rows(dim), keys(dim), keys(value_dim), rows(), rows(),
                  rows(value_dim)],
        out_specs=rows(dim),
        out_shape=jax.ShapeDtypeStruct(q.shape, F32),
        scratch_shapes=[pltpu.VMEM((heads * tq, dim), F32)],
        compiler_params=_params(_SWEEP),
        name="flash_attention_backward_q", interpret=interpret,
    )(q, k, v, lse, delta, d_out)


def _backward_kv_kernel(q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref,
                        dk_ref, dv_ref, dk_acc, dv_acc, *, scale, rule):
    """The logits transposed, [tk, R * tq]: a row's ``lse`` and ``delta``
    lie along the lanes as the arrays hold them, and the sums over queries
    are plain products."""
    heads, tq, dim = q_ref.shape
    rows, tk = heads * tq, k_ref.shape[0]
    j, at = pl.program_id(2), pl.program_id(3)
    i = rule.query_tile(j, at)

    @pl.when(at == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked):
        q, d_o = q_ref[...].reshape(rows, dim), do_ref[...].reshape(rows, -1)
        logits = lax.dot_general(k_ref[...], q, _NT,
                                 preferred_element_type=F32) * scale
        if masked:
            logits = logits + jnp.concatenate(
                [_bias(rule, i, j, transposed=True)] * heads, axis=1)
        p = jnp.exp(logits - _lanes(lse_ref[...]))
        dv_acc[...] += jnp.dot(p.astype(q.dtype), d_o,
                               preferred_element_type=F32)
        d_p = lax.dot_general(v_ref[...], d_o, _NT,
                              preferred_element_type=F32)
        d_logits = (p * (d_p - _lanes(delta_ref[...]))).astype(q.dtype)
        dk_acc[...] += jnp.dot(d_logits, q, preferred_element_type=F32)

    _edges(rule.visits_query(j, at, i), rule.inside(i, j), step)

    @pl.when(at == pl.num_programs(3) - 1)
    def _():
        dk_ref[...] = dk_acc[...] * scale
        dv_ref[...] = dv_acc[...]


def backward_kv(q, k, v, lse, delta, d_out, *, window=None,
                block_length=None, tile_q=None, tile_k=None, interpret=False):
    """(d_k [B, G, S, D], d_v [B, G, S, D_v]) float32."""
    batch, groups, heads, length, dim = q.shape
    value_dim = v.shape[-1]
    rule = _rule(window, block_length, length, tile_q, tile_k)
    tq, tk, query_tile = rule.tq, rule.tk, rule.fetched_query_tile

    rows = lambda width: pl.BlockSpec(
        (None, None, heads, tq, width),
        lambda b, g, j, step: (b, g, 0, query_tile(j, step), 0))
    lanes = pl.BlockSpec((None, None, heads, tq),
                         lambda b, g, j, step: (b, g, 0, query_tile(j, step)))
    keys = lambda width: pl.BlockSpec((None, None, tk, width),
                                      lambda b, g, j, step: (b, g, j, 0))
    return pl.pallas_call(
        functools.partial(_backward_kv_kernel, scale=1.0 / math.sqrt(dim),
                          rule=rule),
        grid=(batch, groups, length // tk, rule.query_steps()),
        in_specs=[rows(dim), keys(dim), keys(value_dim), lanes, lanes,
                  rows(value_dim)],
        out_specs=[keys(dim), keys(value_dim)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, F32),
                   jax.ShapeDtypeStruct(v.shape, F32)],
        scratch_shapes=[pltpu.VMEM((tk, dim), F32),
                        pltpu.VMEM((tk, value_dim), F32)],
        compiler_params=_params(_SWEEP),
        name="flash_attention_backward_kv", interpret=interpret,
    )(q, k, v, lse, delta, d_out)
