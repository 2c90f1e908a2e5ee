"""Pallas TPU kernels for the Gated DeltaNet's state pass
(``models/qwen3_next.py::scan_chunks``): the one part of the chunked delta
rule that is sequential, the ``[d_k, d_v]`` state of a head crossing the
chunks ``ops/delta_chunks.py`` prepared. The state lives in VMEM scratch
across the chunk axis of the grid, forward, and its cotangent backward, on
the same grid walked from the last chunk to the first; nothing is stacked
by a loop of XLA's and o comes out in the layout its reader takes.

The mathematics is ``scan_chunks``', product for product, S_0 = 0:

    delta = u - w S,   o = q_in S + attn delta,
    S <- decay S + k_out^T delta,

and backward, with D the cotangent of the state after the chunk and delta
made again from the state saved at its start:

    d_delta = attn^T d_o + k_out D,       d_u = d_delta,
    d_q_in = d_o S^T,    d_w = -d_delta S^T,
    d_attn = d_o delta^T,   d_k_out = delta D^T,   d_decay = sum(D * S),
    D <- decay D + q_in^T d_o - w^T d_delta.

Products that share a right-hand side are one product of stacked rows
([w; q_in] S, [d_o; d_delta] S^T, [q_in; w]^T [d_o; -d_delta]).

Precision is the XLA form's: float32 in and out, every product at
``Precision.HIGHEST``.

Layouts (H value heads, n chunks of C tokens, S = n C):

  u, w, q_in, k_out, attn   [n, B, H, C, .]    as ``delta_chunks`` writes them
  decay, d_decay            [B, H, n]          a number a chunk and head, the
                                               chunks along the lanes
  o, d_o                    [B, S, H, d_v]     what the gated norm reads: a
                                               head's rows go to and come
                                               from its sublane of every
                                               token's tile ([B, S, H * d_v]
                                               tiles 8 tokens, not 8 heads:
                                               the reshape is a copy of the
                                               array on the chip)
  states                    [n, B, H, d_k, d_v]  the state at the start of
                                               every chunk, for the backward

The grid is (sequence, block of ``HEADS`` value heads, block of ``CHUNKS``
chunks), the chunks last and in order. A step's heads are written side by
side, stage by stage: a head's chain of dependent float32 products is bound
by its waits, and another head's products fill them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gtopkssgd_tpu.ops.delta_chunks import _SEMANTICS, F32, _mm, _nt, _tn
from gtopkssgd_tpu.ops.dsa_attention import _params

# Value heads and chunks a grid step.
HEADS, CHUNKS = 8, 4


def _stacked(a, b):
    return jnp.concatenate([a, b], axis=0)


class _Step:
    """What both kernels need of a grid step: which chunk a loop step is,
    its rows of o, and the heads' decays there as [1, 1] blocks."""

    def __init__(self, block, u_ref, decay_ref):
        self.count, self.heads, self.chunk, _ = u_ref.shape
        self.block = block
        self.row = lax.broadcasted_iota(jnp.int32, decay_ref.shape, 0)
        self.lane = lax.broadcasted_iota(jnp.int32, decay_ref.shape, 1)
        self.decays = decay_ref[...]

    def at(self, k):
        self.here = self.lane == self.block * self.count + k
        self.rows = pl.ds(pl.multiple_of(k * self.chunk, self.chunk),
                          self.chunk)
        decay = jnp.sum(jnp.where(self.here, self.decays, 0.0), axis=1,
                        keepdims=True)
        return [decay[h:h + 1] for h in range(self.heads)]


# ------------------------------------------------------------------ forward
def _forward_kernel(u_ref, w_ref, attn_ref, qin_ref, kout_ref, decay_ref,
                    o_ref, *rest):
    *states_ref, state_ref = rest
    step = _Step(pl.program_id(2), u_ref, decay_ref)
    heads, chunk = range(step.heads), step.chunk

    @pl.when(step.block == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    def one(k, _):
        decay = step.at(k)
        states = [state_ref[h] for h in heads]
        for ref in states_ref:
            for h in heads:
                ref[k, h] = states[h]
        reads = [_mm(_stacked(w_ref[k, h], qin_ref[k, h]), states[h])
                 for h in heads]
        deltas = [u_ref[k, h] - reads[h][:chunk] for h in heads]
        inside = [_mm(attn_ref[k, h], deltas[h]) for h in heads]
        written = [_tn(kout_ref[k, h], deltas[h]) for h in heads]
        for h in heads:
            o_ref[step.rows, h, :] = reads[h][chunk:] + inside[h]
            state_ref[h] = decay[h] * states[h] + written[h]
        return _

    lax.fori_loop(0, step.count, one, None)


def _blocks(u, w, decay):
    """(the arrays' sizes, heads and chunks a grid step)."""
    chunks, batch, heads, chunk, d_v = u.shape
    if decay.shape != (batch, heads, chunks):
        raise ValueError(f"decay {decay.shape}: not [B, H, n] of u {u.shape}")
    # A step's heads are whole sublane tiles of o's [H, d_v], or all of them.
    block = math.gcd(heads, HEADS)
    return (chunks, batch, heads, chunk, w.shape[-1], d_v,
            block if block % 8 == 0 else heads, math.gcd(chunks, CHUNKS))


def _specs(sizes, at):
    """Block specs of a grid (sequence, head block, chunk block), the
    grid's step i at chunk block ``at(i)``."""
    chunks, _, _, chunk, d_k, d_v, heads, count = sizes
    blocks = lambda *shape: pl.BlockSpec(
        (count, None, heads) + shape, lambda b, h, i: (at(i), b, h, 0, 0))
    numbers = pl.BlockSpec((None, None, heads, chunks),
                           lambda b, h, i: (b, h, 0, 0))
    tokens = pl.BlockSpec((None, count * chunk, heads, d_v),
                          lambda b, h, i: (b, at(i), h, 0))
    prepared = [blocks(chunk, d_v), blocks(chunk, d_k), blocks(chunk, chunk),
                blocks(chunk, d_k), blocks(chunk, d_k)]
    return prepared, numbers, tokens, blocks(d_k, d_v)


def _by_block(decay, heads):
    """[B, H, n] -> [B, H / heads, heads, n]: a step's block is whole."""
    batch, every, chunks = decay.shape
    return decay.reshape(batch, every // heads, heads, chunks)


@functools.partial(jax.jit, static_argnames=("states", "interpret"))
def forward(u, w, attn, q_in, k_out, decay, *, states=False, interpret=False):
    """o [B, S, H, d_v] float32 and, with ``states``, the state at the
    start of every chunk [n, B, H, d_k, d_v]."""
    sizes = _blocks(u, w, decay)
    chunks, batch, every, chunk, d_k, d_v, heads, count = sizes
    prepared, numbers, tokens, state = _specs(sizes, lambda i: i)
    shape = lambda *s: jax.ShapeDtypeStruct(s, F32)
    out = pl.pallas_call(
        _forward_kernel,
        grid=(batch, every // heads, chunks // count),
        in_specs=[*prepared, numbers],
        out_specs=[tokens] + [state] * states,
        out_shape=[shape(batch, chunks * chunk, every, d_v)]
        + [shape(chunks, batch, every, d_k, d_v)] * states,
        scratch_shapes=[pltpu.VMEM((heads, d_k, d_v), F32)],
        compiler_params=_params(_SEMANTICS),
        name="delta_scan_forward", interpret=interpret,
    )(u, w, attn, q_in, k_out, _by_block(decay, heads))
    return tuple(out) if states else out[0]


# ----------------------------------------------------------------- backward
def _backward_kernel(u_ref, w_ref, attn_ref, qin_ref, kout_ref, decay_ref,
                     states_ref, do_ref, du_ref, dw_ref, dattn_ref, dqin_ref,
                     dkout_ref, ddecay_ref, carry_ref):
    step = _Step(pl.num_programs(2) - 1 - pl.program_id(2), u_ref, decay_ref)
    heads, chunk = range(step.heads), step.chunk
    total = lambda x: jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1,
                              keepdims=True)

    @pl.when(pl.program_id(2) == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)
        ddecay_ref[...] = jnp.zeros_like(ddecay_ref)

    def one(j, _):
        k = step.count - 1 - j
        decay = step.at(k)
        states = [states_ref[k, h] for h in heads]
        carries = [carry_ref[h] for h in heads]
        d_o = [do_ref[step.rows, h, :] for h in heads]
        deltas = [u_ref[k, h] - _mm(w_ref[k, h], states[h]) for h in heads]
        d_deltas = [_tn(attn_ref[k, h], d_o[h])
                    + _mm(kout_ref[k, h], carries[h]) for h in heads]
        through = [_nt(_stacked(d_o[h], d_deltas[h]), states[h])
                   for h in heads]
        d_attn = [_nt(d_o[h], deltas[h]) for h in heads]
        d_kout = [_nt(deltas[h], carries[h]) for h in heads]
        back = [_tn(_stacked(qin_ref[k, h], w_ref[k, h]),
                    _stacked(d_o[h], -d_deltas[h])) for h in heads]
        d_decay = ddecay_ref[...]
        for h in heads:
            du_ref[k, h] = d_deltas[h]
            dqin_ref[k, h] = through[h][:chunk]
            dw_ref[k, h] = -through[h][chunk:]
            dattn_ref[k, h] = d_attn[h]
            dkout_ref[k, h] = d_kout[h]
            d_decay = jnp.where(step.here & (step.row == h),
                                total(carries[h] * states[h]), d_decay)
            carry_ref[h] = decay[h] * carries[h] + back[h]
        ddecay_ref[...] = d_decay
        return _

    lax.fori_loop(0, step.count, one, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def backward(u, w, attn, q_in, k_out, decay, states, d_o, *, interpret=False):
    """(d_u, d_w, d_attn, d_q_in, d_k_out, d_decay) in the layouts of u, w,
    attn, q_in, k_out and decay, of d_o [B, S, H, d_v]."""
    sizes = _blocks(u, w, decay)
    chunks, batch, every, _, _, _, heads, count = sizes
    last = chunks // count - 1
    prepared, numbers, tokens, state = _specs(sizes, lambda i: last - i)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, F32)
    numbered = _by_block(decay, heads)
    *d_prepared, d_decay = pl.pallas_call(
        _backward_kernel,
        grid=(batch, every // heads, chunks // count),
        in_specs=[*prepared, numbers, state, tokens],
        out_specs=[*prepared, numbers],
        out_shape=[like(u), like(w), like(attn), like(q_in), like(k_out),
                   like(numbered)],
        scratch_shapes=[pltpu.VMEM((heads, w.shape[-1], u.shape[-1]), F32)],
        compiler_params=_params(_SEMANTICS),
        name="delta_scan_backward", interpret=interpret,
    )(u, w, attn, q_in, k_out, numbered, states, d_o)
    return (*d_prepared, d_decay.reshape(decay.shape))
