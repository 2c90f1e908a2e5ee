"""The chunked delta rule in XLA's products, for both decoders that run one:
``qwen3_next``'s Gated DeltaNet, whose decay is a number a head and token
(``g`` [B, S, H]), and ``kimi_linear``'s Kimi Delta Attention, whose decay is
a number a key channel (``g`` [B, S, H, d_k]). ``delta_chunks`` is told the
kind by the shape of ``g``; ``scan_chunks`` is one pass for both.

The recurrence, per head, S in R^{d_k x d_v}, S_0 = 0:

    S' = D_t S_{t-1},  delta_t = beta_t (v_t - S'^T k_t),
    S_t = S' + k_t delta_t^T,  o_t = S_t^T q_t,

with D_t = e^{g_t} I (a head's number) or Diag(e^{g_t}) (a channel's).
Inside a chunk of C tokens, with gamma_t the running sum of g and S_0 the
state at the chunk's start, it unrolls to a unit lower-triangular system

    (I + A) Delta = beta V - (beta e^gamma K) S_0,
    A_tj = beta_t sum_d k_t[d] e^{gamma_t[d] - gamma_j[d]} k_j[d]   (j < t),

so Delta = U - W S_0 with [U | W] = (I + A)^-1 [beta V | beta e^gamma K]:
one triangular solve a chunk, all chunks at once on the MXU, and only the
d_k x d_v state crosses chunks (``scan_chunks``).

**A decay a head** comes out of the sum: A = beta (e^{gamma_t - gamma_j}) *
K K^T, one product under a [C, C] mask of exponents that are never positive.

**A decay a channel does not factor**, and the obvious factoring,
(k_t e^{gamma_t}) . (k_j e^{-gamma_j}), overflows float32 once a channel's
in-chunk log-decay passes -88, which an unbounded gate allows. The form
here keeps every exponent at or below zero: the chunk is cut into
sub-blocks of ``SUB`` rows; a sub-block of rows against the columns before
it is a product of k_t e^{gamma_t - ref} and k_j e^{ref - gamma_j}, ref
the log-decay at the row sub-block's first row (gamma falls with t, so both
exponents are <= 0 there); a diagonal sub-block is taken pair by pair
([SUB, SUB, d_k], e^{gamma_t - gamma_j} for j <= t, never [C, C, d_k]). A
factor that underflows stands for a weight that is itself below float32.

The small products run at the highest matmul precision, so that they agree
with the references' float32 recurrences before both round to the
projections' dtype.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
_mm = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)

# Rows of a sub-block of the per-channel rule's chunk (module docstring).
SUB = 16


def chunk_of(seq_len: int) -> int:
    """Tokens in a chunk of the delta rule: 64, less for a short sequence."""
    return min(64, max(1, seq_len // 4))


def causal_conv(x, kernel):
    """Depthwise causal convolution, x [B, S, C], kernel [K, C]."""
    width, length = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(kernel[i] * padded[:, i:i + length] for i in range(width))


def _channel_products(q, k, gamma, sub):
    """(sum_d k_t e^{gamma_t - gamma_j} k_j, the same with q_t) over the
    pairs j <= t of every chunk, zero above the diagonal: q, k, gamma
    [..., C, d_k] to two [..., C, C], no exponent positive."""
    chunk = q.shape[-2]
    count = chunk // sub
    blocks = lambda a: a.reshape(a.shape[:-2] + (count, sub, a.shape[-1]))
    q_b, k_b, g_b = blocks(q), blocks(k), blocks(gamma)
    # Diagonal sub-blocks, pair by pair: [..., count, sub (t), sub (j), d_k].
    seen = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    weight = jnp.exp(jnp.where(
        seen, g_b[..., :, None, :] - g_b[..., None, :, :], -jnp.inf))
    keys = weight * k_b[..., None, :, :]
    diagonal = [jnp.sum(a[..., :, None, :] * keys, -1) for a in (k_b, q_b)]
    rows = [[], []]
    for i in range(count):
        parts = [[d[..., i, :, :]] for d in diagonal]
        if i:
            ref = g_b[..., i, :1, :]
            fall = jnp.exp(g_b[..., i, :, :] - ref)
            before = k[..., :i * sub, :] * jnp.exp(
                ref - gamma[..., :i * sub, :])
            for part, a in zip(parts, (k_b, q_b)):
                part.insert(0, _mm("...td,...jd->...tj",
                                   a[..., i, :, :] * fall, before))
        if i < count - 1:
            after = jnp.zeros(q.shape[:-2] + (sub, (count - 1 - i) * sub), F32)
            for part in parts:
                part.append(after)
        for row, part in zip(rows, parts):
            row.append(jnp.concatenate(part, -1))
    return tuple(jnp.concatenate(row, -2) for row in rows)


def _solved(a, rhs, d_v):
    """(U, W) = (I + A)^-1 [beta V | beta e^gamma K], split at ``d_v``."""
    solved = lax.linalg.triangular_solve(
        a, rhs, left_side=True, lower=True, unit_diagonal=True)
    return solved[..., :d_v], solved[..., d_v:]


def delta_chunks(q, k, v, g, beta, chunk):
    """What the chunked delta rule needs of every chunk that does not
    depend on the state: q, k [B, S, H, d_k], v [B, S, H, d_v], beta
    [B, S, H] and g, the log decay (<= 0), [B, S, H] (a number a head) or
    [B, S, H, d_k] (a number a channel), float32, S a multiple of
    ``chunk``, to ``(u, w, attn, q_in, k_out, decay)`` with the chunk axis
    in front ([n, B, H, C, ...]; ``decay`` [n, B, H, 1, 1] a head,
    [n, B, H, d_k] a channel).

    With gamma_t the running sum of g inside a chunk (module docstring),
    [U | W] = (I + A)^-1 [beta V | beta e^gamma K], ``attn`` is
    sum_d q_t e^{gamma_t - gamma_j} k_j for j <= t, ``q_in`` is e^gamma Q,
    ``k_out`` is e^{gamma_C - gamma} K and ``decay`` e^{gamma_C}."""
    batch, length, heads, _ = q.shape
    d_v = v.shape[-1]
    n = length // chunk
    # [B, S, H, ...] -> [n, B, H, C, ...]
    split = lambda a: jnp.moveaxis(
        a.reshape((batch, n, chunk, heads) + a.shape[3:]), (1, 3), (0, 2))
    channel = g.ndim == q.ndim
    q, k, v, g, beta = map(split, (q, k, v, g, beta))
    if channel:
        gamma = jnp.cumsum(g, axis=-2)                      # [n, B, H, C, d_k]
        strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
        kk, attn = _channel_products(q, k, gamma, min(SUB, chunk))
        a = jnp.where(strict, beta[..., :, None] * kk, 0.0)
        fall, last = jnp.exp(gamma), gamma[..., -1:, :]
        rhs = jnp.concatenate(
            [beta[..., None] * v, beta[..., None] * fall * k], -1)
        return (*_solved(a, rhs, d_v), attn, q * fall,
                k * jnp.exp(last - gamma), jnp.exp(last[..., 0, :]))
    gamma = jnp.cumsum(g, axis=-1)                              # [n, B, H, C]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    decay = jnp.exp(jnp.where(
        lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    a = jnp.where(strict, beta[..., :, None] * decay
                  * _mm("...td,...jd->...tj", k, k), 0.0)
    rhs = jnp.concatenate(
        [beta[..., None] * v, (beta * jnp.exp(gamma))[..., None] * k], -1)
    return (*_solved(a, rhs, d_v),
            decay * _mm("...td,...jd->...tj", q, k),
            q * jnp.exp(gamma)[..., None],
            k * jnp.exp(gamma[..., -1:] - gamma)[..., None],
            jnp.exp(gamma[..., -1])[..., None, None])


def delta_chunks_by_segments(q, k, v, g, beta, chunk, segment):
    """``delta_chunks`` over ``segment`` chunks at a time (their common
    divisor with the sequence's chunks, where that is fewer), each segment
    under a checkpoint of its own in one ``lax.map``: the algebra's float32
    intermediates (the per-channel rule's pairwise sub-blocks above all)
    are live for one segment's tokens, forward and again backward."""
    batch, length = q.shape[:2]
    n = length // chunk
    segment = math.gcd(n, segment)
    cut = lambda a: jnp.moveaxis(a.reshape(
        (batch, n // segment, segment * chunk) + a.shape[2:]), 1, 0)
    out = lax.map(jax.checkpoint(lambda args: delta_chunks(*args, chunk)),
                  tuple(map(cut, (q, k, v, g, beta))))
    # [segments, segment, B, ...] -> [n, B, ...]
    return tuple(a.reshape((n,) + a.shape[2:]) for a in out)


def scan_chunks(u, w, attn, q_in, k_out, decay):
    """The state's pass over the chunks ``delta_chunks`` prepared, S_0 = 0:

        Delta = U - W S,   O = (e^gamma Q) S + attn Delta,
        S <- e^{gamma_C} S + (e^{gamma_C - gamma} K)^T Delta,

    ``decay`` scaling S whole ([n, B, H, 1, 1]) or row by row
    ([n, B, H, d_k]). Returns o [B, n C, H, d_v]."""
    n, batch, heads, chunk, d_v = u.shape

    def step(state, xs):
        u_i, w_i, attn_i, q_i, k_i, decay_i = xs
        if decay_i.ndim < state.ndim:
            decay_i = decay_i[..., None]
        delta = u_i - _mm("...cd,...dv->...cv", w_i, state)
        out = _mm("...cd,...dv->...cv", q_i, state) \
            + _mm("...tj,...jv->...tv", attn_i, delta)
        return decay_i * state + _mm("...cd,...cv->...dv", k_i, delta), out

    state = jnp.zeros((batch, heads, w.shape[-1], d_v), F32)
    _, out = lax.scan(step, state, (u, w, attn, q_in, k_out, decay))
    return jnp.moveaxis(out, (0, 2), (1, 3)).reshape(
        batch, n * chunk, heads, d_v)


def pad_to_chunks(arrays, chunk):
    """(arrays padded along the sequence to a multiple of ``chunk``, the
    length before). A padded token decays nothing and writes nothing."""
    length = arrays[0].shape[1]
    pad = -length % chunk
    if pad:
        arrays = tuple(
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in arrays)
    return arrays, length


def xla_delta_rule(q, k, v, g, beta, chunk):
    """The delta rule over whole sequences of any length S, chunk by chunk
    in XLA's products, g a number a head or a number a channel: o
    [B, S, H, d_v]."""
    arrays, length = pad_to_chunks((q, k, v, g, beta), chunk)
    return scan_chunks(*delta_chunks(*arrays, chunk))[:, :length]
