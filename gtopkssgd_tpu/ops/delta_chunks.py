"""Pallas TPU kernels for the Gated DeltaNet's chunk algebra
(``models/qwen3_next.py::delta_chunks``): what the chunked delta rule needs
of every chunk that does not depend on the state. A chunk's ``[C, C]`` and
``[C, d]`` float32 blocks (the decay mask, A, the inverse of I + A, the
right-hand side) live in VMEM; only the inputs and the five outputs touch
HBM, forward and backward.

The mathematics is ``delta_chunks``', product for product: with gamma the
running sum of the log decay inside a chunk,

    D_tj = e^{gamma_t - gamma_j}  (j <= t),   A = strict(beta D * K K^T),
    [U | W] = (I + A)^-1 [beta V | beta e^gamma K],   attn = D * Q K^T,
    q_in = e^gamma Q,   k_out = e^{gamma_C - gamma} K.

**The unit-triangular system by products** (``unit_lower_inverse``): the
``BASE`` x ``BASE`` diagonal blocks of I + A are inverted by substitution
(column elimination, ``BASE`` rank-one updates, every block of every chunk of
a grid step at once: the blocks lie side by side along the lanes of a
``[BASE, C]`` row block a chunk), then two blocks' inverses give their
pair's, [[X1, 0], [-X2 L21 X1, X2]], up to ``C``, as two ``[C, C]`` products
a level on the MXU. No series in A: its entries reach +-1 and its powers
cancel in float32.

Precision is the XLA form's: float32 in and out, every product at
``Precision.HIGHEST``, ``exp`` and sums in float32.

Layouts (H value heads, H_k key heads, n chunks of C tokens, S = n C):

  q, k, d_q, d_k             [B, S, H_k * d_k]   read by key head: value
                                                 head h takes the 128-lane
                                                 columns of key head
                                                 h // (H / H_k), so the
                                                 repeat to H heads is never
                                                 made
  v, d_v                     [B, S, H * d_v]
  gamma, beta, and their d_  [B, H, n, C]        a number a token, the
                                                 chunk's tokens along the
                                                 lanes (a last axis of 1
                                                 would cost 128 lanes a
                                                 number)
  u, w, q_in, k_out          [n, B, H, C, d]     what the state's pass
                                                 reads (``scan_chunks``, or
                                                 ``ops/delta_scan.py``)
  attn                       [n, B, H, C, C]

The grid is (sequence, block of ``BLOCK`` chunks, value head), the heads
innermost: the value heads of a key head follow each other, so its q and k
block is fetched once for them and, backward, their d_q and d_k add up in
the output's block before it is written.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gtopkssgd_tpu.ops.dsa_attention import _NT, _params
from gtopkssgd_tpu.ops.flash_attention import _TN

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
# Chunks a grid step, and the side of the diagonal blocks inverted by
# substitution (a float32 sublane tile).
BLOCK, BASE = 16, 16
# Chunks a loop step inside a grid step: independent chains of small
# products, one's filling the other's waits.
GROUP = 4
_SEMANTICS = ("parallel", "parallel", "arbitrary")

_mm = functools.partial(jnp.dot, precision=HIGHEST, preferred_element_type=F32)
_nt = functools.partial(lax.dot_general, dimension_numbers=_NT,
                        precision=HIGHEST, preferred_element_type=F32)
_tn = functools.partial(lax.dot_general, dimension_numbers=_TN,
                        precision=HIGHEST, preferred_element_type=F32)


def block_of(chunks):
    """Chunks a grid step at ``chunks`` chunks a sequence, or None where
    they are not whole blocks."""
    block = min(BLOCK, chunks)
    return block if chunks % block == 0 else None


class _Masks:
    """The [C, C] index masks of a chunk, made once a grid step."""

    def __init__(self, chunk):
        self.rows = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        self.cols = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        self.eye = self.rows == self.cols
        self.lower = self.rows >= self.cols
        self.strict = self.rows > self.cols
        self.diagonal = self.rows // BASE == self.cols // BASE

    def column(self, row):
        """[1, C] -> [C, 1], exactly: one term a sum."""
        return jnp.sum(jnp.where(self.eye, row, 0.0), axis=1, keepdims=True)

    def row(self, column):
        """[C, 1] -> [1, C]."""
        return jnp.sum(jnp.where(self.eye, column, 0.0), axis=0,
                       keepdims=True)

    def pair(self, size):
        """The lower-left block of every pair of diagonal blocks of
        ``size``."""
        return (self.rows // (2 * size) == self.cols // (2 * size)) \
            & (self.rows // size > self.cols // size)


def _spread(chunk):
    """[C, BASE * C] of 0 and 1: a compact row block times it gives, for
    each j < BASE, column j of every diagonal block along that block's own
    lanes (``[:, j * C:(j + 1) * C]``)."""
    source = lax.broadcasted_iota(jnp.int32, (chunk, BASE * chunk), 0)
    target = lax.broadcasted_iota(jnp.int32, (chunk, BASE * chunk), 1)
    lane, j = target % chunk, target // chunk
    return jnp.where((source // BASE == lane // BASE)
                     & (source % BASE == j), 1.0, 0.0).astype(F32)


def compact(a, masks):
    """The diagonal blocks of a [C, C] matrix as one [BASE, C] row block:
    block b in the lanes b * BASE .. (b + 1) * BASE."""
    chunk = a.shape[0]
    return jnp.sum(jnp.where(masks.diagonal, a, 0.0).reshape(
        chunk // BASE, BASE, chunk), axis=0)


def invert_diagonal_blocks(blocks):
    """``blocks`` [m, BASE, C], each the diagonal blocks D_b (strictly lower
    triangular) of a chunk's A in ``compact`` form, to (I + D_b)^-1 in the
    same form: I + D = (I + a_0 e_0^T) ... (I + a_{BASE-1} e_{BASE-1}^T), a_j
    its column j, so the inverse is BASE rank-one updates of I in turn,
    X <- X - a_j X[j, :], which is substitution."""
    count, _, chunk = blocks.shape
    columns = _mm(blocks.reshape(count * BASE, chunk), _spread(chunk))
    row = lax.broadcasted_iota(jnp.int32, (BASE, chunk), 0)
    lane = lax.broadcasted_iota(jnp.int32, (BASE, chunk), 1)
    x = jnp.broadcast_to(jnp.where(row == lane % BASE, 1.0, 0.0).astype(F32),
                         blocks.shape)
    for j in range(BASE - 1):
        column = columns[:, j * chunk:(j + 1) * chunk].reshape(blocks.shape)
        x = x - column * x[:, j:j + 1, :]
    return x


def merge_blocks(xs, matrices, masks):
    """``xs`` [BASE, C] each, chunks' inverted diagonal blocks in compact
    form, and their ``matrices`` a [C, C] to the chunks' (I + a)^-1 [C, C].
    A level takes the rows of every pair's second block alone (the others
    of -X2 L21 X1 are zero): two [C / 2, C] x [C, C] products a chunk, the
    chunks' side by side so that one's products fill the other's waits."""
    chunk = matrices[0].shape[0]
    xs = [jnp.where(masks.diagonal,
                    jnp.concatenate([x] * (chunk // BASE), axis=0), 0.0)
          for x in xs]
    size = BASE
    while size < chunk:
        second = [slice(at + size, at + 2 * size)
                  for at in range(0, chunk, 2 * size)]
        pair = masks.pair(size)
        take = lambda m: jnp.concatenate([m[rows] for rows in second], axis=0)
        below = take(pair)
        products = [_mm(jnp.where(below, take(a), 0.0), x)       # L21 X1
                    for a, x in zip(matrices, xs)]
        nothing = jnp.zeros((size, chunk), F32)
        products = [_mm(take(x), jnp.concatenate(               # X2 (L21 X1)
            [part for at in range(0, chunk // 2, size)
             for part in (nothing, p[at:at + size])], axis=0))
            for x, p in zip(xs, products)]
        xs = [jnp.concatenate(
            [part for at, rows in zip(range(0, chunk // 2, size), second)
             for part in (x[rows.start - size:rows.start],
                          x[rows] - p[at:at + size])], axis=0)
            for x, p in zip(xs, products)]
        size *= 2
    return xs


def unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower triangular ``a`` [m, C, C] float32:
    what the kernels do a grid step, as one function of arrays (its test
    calls it outside any kernel)."""
    masks = _Masks(a.shape[-1])
    x = invert_diagonal_blocks(jnp.stack([compact(one, masks) for one in a]))
    return jnp.stack(merge_blocks(list(x), list(a), masks))


class _Chunk:
    """One chunk's blocks in VMEM, made from the kernel's input refs: the
    columns of its numbers and, with ``products``, what both passes need
    before the inverse."""

    def __init__(self, masks, i, q_ref, k_ref, g_ref, b_ref, products=True):
        chunk = g_ref.shape[-1]
        self.i = i
        self.tokens = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        self.compact = pl.ds(pl.multiple_of(i * BASE, BASE), BASE)
        self.k = k_ref[self.tokens, :]
        g_row = g_ref[pl.ds(i, 1), :]
        self.g, self.beta = masks.column(g_row), masks.column(
            b_ref[pl.ds(i, 1), :])
        self.grow = jnp.exp(self.g)                            # e^gamma
        if not products:
            return
        self.q = q_ref[self.tokens, :]
        self.fade = jnp.exp(g_row[:, chunk - 1:] - self.g)     # e^{gamma_C - gamma}
        both = _nt(jnp.concatenate([self.k, self.q], axis=0), self.k)
        self.kk, qk = both[:chunk], both[chunk:]
        self.decay = jnp.exp(jnp.where(masks.lower, self.g - g_row, -jnp.inf))
        self.a = jnp.where(masks.strict, self.beta * self.decay * self.kk, 0.0)
        self.attn = self.decay * qk

    def rhs(self, v):
        return jnp.concatenate(
            [self.beta * v, (self.beta * self.grow) * self.k], axis=1)


def _chunks(count, body):
    """``body([i, ...])`` over the grid step's ``count`` chunks, ``GROUP``
    independent ones a loop step."""
    group = math.gcd(count, GROUP)

    def step(at, _):
        body([at * group + j for j in range(group)])
        return _

    lax.fori_loop(0, count // group, step, None)


def _invert(compact_ref):
    """The grid step's compact diagonal blocks, inverted in place."""
    rows, chunk = compact_ref.shape
    compact_ref[...] = invert_diagonal_blocks(
        compact_ref[...].reshape(rows // BASE, BASE, chunk)).reshape(
            rows, chunk)


# ------------------------------------------------------------------ forward
def _forward_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, u_ref, w_ref, attn_ref,
                    qin_ref, kout_ref, a_ref, compact_ref):
    count, chunk = g_ref.shape
    d_v = v_ref.shape[-1]
    masks = _Masks(chunk)
    refs = (q_ref, k_ref, g_ref, b_ref)

    def before(ids):
        for c in [_Chunk(masks, i, *refs) for i in ids]:
            attn_ref[c.i] = c.attn
            qin_ref[c.i] = c.q * c.grow
            kout_ref[c.i] = c.k * c.fade
            a_ref[c.i] = c.a
            compact_ref[c.compact, :] = compact(c.a, masks)

    _chunks(count, before)
    _invert(compact_ref)

    def after(ids):
        chunks = [_Chunk(masks, i, *refs, products=False) for i in ids]
        inverses = merge_blocks([compact_ref[c.compact, :] for c in chunks],
                                [a_ref[c.i] for c in chunks], masks)
        for c, inverse in zip(chunks, inverses):
            solved = _mm(inverse, c.rhs(v_ref[c.tokens, :]))
            u_ref[c.i] = solved[:, :d_v]
            w_ref[c.i] = solved[:, d_v:]

    _chunks(count, after)


def _specs(rep, chunk, d_k, d_v, count):
    """Block specs of a grid (sequence, chunk block, value head), ``rep``
    value heads a key head."""
    tokens = count * chunk
    keys = pl.BlockSpec((None, tokens, d_k), lambda b, i, h: (b, i, h // rep))
    values = pl.BlockSpec((None, tokens, d_v), lambda b, i, h: (b, i, h))
    numbers = pl.BlockSpec((None, None, count, chunk),
                           lambda b, i, h: (b, h, i, 0))
    out = lambda width: pl.BlockSpec((count, None, None, chunk, width),
                                     lambda b, i, h: (i, b, h, 0, 0))
    return keys, values, numbers, out


def _scratch(count, chunk):
    return [pltpu.VMEM((count, chunk, chunk), F32),
            pltpu.VMEM((count * BASE, chunk), F32)]


def _sizes(q, v, gamma, key_heads):
    batch, heads, chunks, chunk = gamma.shape
    d_k, d_v = q.shape[-1] // key_heads, v.shape[-1] // heads
    count = block_of(chunks)
    if count is None or chunk % BASE or q.shape[1] != chunks * chunk:
        raise ValueError(f"{chunks} chunks of {chunk}: not whole blocks of "
                         f"{BLOCK} chunks of whole {BASE}-row tiles")
    return batch, heads, chunks, chunk, d_k, d_v, count


def forward(q, k, v, gamma, beta, *, key_heads, interpret=False):
    """(u, w, attn, q_in, k_out), float32."""
    batch, heads, chunks, chunk, d_k, d_v, count = _sizes(
        q, v, gamma, key_heads)
    keys, values, numbers, out = _specs(
        heads // key_heads, chunk, d_k, d_v, count)
    return pl.pallas_call(
        _forward_kernel,
        grid=(batch, chunks // count, heads),
        in_specs=[keys, keys, values, numbers, numbers],
        out_specs=[out(d_v), out(d_k), out(chunk), out(d_k), out(d_k)],
        out_shape=[jax.ShapeDtypeStruct(
            (chunks, batch, heads, chunk, width), F32)
            for width in (d_v, d_k, chunk, d_k, d_k)],
        scratch_shapes=_scratch(count, chunk),
        compiler_params=_params(_SEMANTICS),
        name="delta_chunks_forward", interpret=interpret,
    )(q, k, v, gamma, beta)


# ----------------------------------------------------------------- backward
def _backward_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, du_ref, dw_ref,
                     dattn_ref, dqin_ref, dkout_ref, dq_ref, dk_ref, dv_ref,
                     dg_ref, db_ref, a_ref, compact_ref, *, rep):
    """The chunk's blocks again in VMEM, then, with r the right-hand side
    and y = T r:  d_r = T^T d_y,  d_A = -strict(d_r y^T);  the rest is
    products and pointwise rules on blocks already there."""
    count, chunk = g_ref.shape
    d_v = v_ref.shape[-1]
    masks = _Masks(chunk)
    first = pl.program_id(2) % rep == 0
    rows = lambda x: jnp.sum(x, axis=1, keepdims=True)
    refs = (q_ref, k_ref, g_ref, b_ref)

    def before(ids):
        for c in [_Chunk(masks, i, *refs) for i in ids]:
            a_ref[c.i] = c.a
            compact_ref[c.compact, :] = compact(c.a, masks)

    _chunks(count, before)
    _invert(compact_ref)

    def one(c, inverse):
        i = c.i
        v = v_ref[c.tokens, :]
        solved = _mm(inverse, c.rhs(v))
        d_rhs = _tn(inverse, jnp.concatenate([du_ref[i], dw_ref[i]], axis=1))
        d_a = -jnp.where(masks.strict, _nt(d_rhs, solved), 0.0)
        d_rv, d_rk = d_rhs[:, :d_v], d_rhs[:, d_v:]
        along_k = c.grow * rows(d_rk * c.k)
        d_beta = rows(d_rv * v) + along_k
        d_gamma = c.beta * along_k
        # A = beta D * K K^T and attn = D * Q K^T: through D both reach
        # gamma_t (their rows' sums) and gamma_j (less their columns').
        d_attn = dattn_ref[i]
        through = d_a * c.decay
        d_beta += rows(through * c.kk)
        through_decay = d_a * c.a + d_attn * c.attn
        d_gamma += rows(through_decay)
        stacked = jnp.concatenate(
            [c.beta * through, d_attn * c.decay], axis=0)         # [2 C, C]
        direct = _mm(stacked, c.k)
        d_q, d_k = direct[chunk:], direct[:chunk] + _tn(
            stacked, jnp.concatenate([c.k, c.q], axis=0))
        d_k += (c.beta * c.grow) * d_rk
        # q_in = e^gamma Q and k_out = e^{gamma_C - gamma} K.
        d_qin, d_kout = dqin_ref[i], dkout_ref[i]
        d_q += c.grow * d_qin
        d_k += c.fade * d_kout
        out = c.fade * rows(d_kout * c.k)
        d_gamma += c.grow * rows(d_qin * c.q) - out
        last = jnp.where(masks.cols[:1] == chunk - 1,
                         jnp.sum(out, axis=0, keepdims=True), 0.0)
        dg_ref[pl.ds(i, 1), :] = masks.row(d_gamma) + last \
            - jnp.sum(through_decay, axis=0, keepdims=True)
        db_ref[pl.ds(i, 1), :] = masks.row(d_beta)
        dv_ref[c.tokens, :] = c.beta * d_rv

        @pl.when(first)
        def _():
            dq_ref[c.tokens, :] = d_q
            dk_ref[c.tokens, :] = d_k

        @pl.when(jnp.logical_not(first))
        def _():
            dq_ref[c.tokens, :] += d_q
            dk_ref[c.tokens, :] += d_k

    def after(ids):
        chunks = [_Chunk(masks, i, *refs) for i in ids]
        inverses = merge_blocks([compact_ref[c.compact, :] for c in chunks],
                                [c.a for c in chunks], masks)
        for c, inverse in zip(chunks, inverses):
            one(c, inverse)

    _chunks(count, after)


def backward(q, k, v, gamma, beta, d_u, d_w, d_attn, d_qin, d_kout, *,
             key_heads, interpret=False):
    """(d_q, d_k, d_v, d_gamma, d_beta) in the layouts of q, k, v, gamma,
    beta: d_q and d_k summed over a key head's value heads."""
    batch, heads, chunks, chunk, d_k, d_v, count = _sizes(
        q, v, gamma, key_heads)
    keys, values, numbers, out = _specs(
        heads // key_heads, chunk, d_k, d_v, count)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, F32)
    return pl.pallas_call(
        functools.partial(_backward_kernel, rep=heads // key_heads),
        grid=(batch, chunks // count, heads),
        in_specs=[keys, keys, values, numbers, numbers,
                  out(d_v), out(d_k), out(chunk), out(d_k), out(d_k)],
        out_specs=[keys, keys, values, numbers, numbers],
        out_shape=[like(q), like(k), like(v), like(gamma), like(beta)],
        scratch_shapes=_scratch(count, chunk),
        compiler_params=_params(_SEMANTICS),
        name="delta_chunks_backward", interpret=interpret,
    )(q, k, v, gamma, beta, d_u, d_w, d_attn, d_qin, d_kout)
