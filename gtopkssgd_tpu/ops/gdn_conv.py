"""Pallas TPU kernels for what a Gated DeltaNet layer does to its
projections before the chunk algebra (``models/qwen3_next.py::prepare``):

    activations [B, S, >= C] (any float dtype) -> float32
      -> depthwise causal convolution, ``taps`` [K, C] float32
      -> SiLU -> split into q | k | v by columns
      -> unit norm of q (then / sqrt(d_k)) and of k over each head's channels

as **one kernel forward and one backward**. The float32 passes of the XLA
form (a padded copy, K shifted multiply-adds, the SiLU, the sums of squares,
and all of them again transposed) never touch HBM: the forward reads the
activations once, in the dtype they come in, and writes q, k and v; the
backward reads the activations and the three cotangents and writes the
activations' cotangent, in their dtype, and the taps'.

The arithmetic is the XLA form's: float32 after the load, the taps added in
the same order, ``x * sigmoid(x)``, ``a * rsqrt(sum a^2 + 1e-6)``, q's
``1 / sqrt(d_k)`` after its norm.

Layouts (H_k key heads of d_k channels, H value heads of d_v; C = 2 H_k d_k +
H d_v, the taps' width):

  x                 [B, S, >= C]    read in place: the columns past C (the
                                    layer's output gate) are never fetched
  taps              [K, C]          K <= 9
  q, k, d_q, d_k    [B, S, H_k d_k] what ``ops/delta_chunks.py`` reads
  v, d_v            [B, S, H d_v]
  d_x               like x          zero in the columns past C

The grid is (block of channels, sequence, block of tokens), the tokens
innermost: the taps' cotangent is a sum over tokens and sequences and adds
up in an output block that stays where it is while they pass. A channel
block is whole heads and lies inside one of q, k, v; the blocks of the two
it does not touch stay where they were last (their index maps hold still),
so nothing is fetched or written for them.

**Halos.** A token block's convolution reads the K - 1 tokens before it, and
the transposed convolution of the backward pass the K - 1 *following*
tokens' pre-activation cotangents. Both come through further block specs on
the same arrays (``HALO`` rows of the activations, ``PAD`` of the float32
cotangents), masked to zero at a sequence's start and end: the padded copy
of ``causal_conv`` is never made, and the following tokens' cotangents are
made again from their halo, not written to HBM and convolved in a second
pass.
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
# Tokens and (at most) channels a grid step; tokens a loop step inside it,
# which works on [ROWS, head] pieces that stay in registers. A step's
# blocks and scratch are 8.5 MB forward and 10.3 backward at 256 x 1024:
# under the 16 MiB a kernel has by default, on purpose. Inside a loop over
# sequences XLA fuses the backward call with the update of the loop's
# stacked output, and that fusion takes no ``vmem_limit_bytes`` (at 512
# tokens: "scoped allocation with size 20.62M and limit 16.00M", from the
# whole step's compile for a described v5e, not from the kernel's own).
TOKENS, CHANNELS, ROWS = 256, 1024, 64
# Rows of a halo block of the activations (a bfloat16 sublane tile), and how
# many of them, and of a float32 halo block, are used: a float32 tile.
HALO, PAD = 16, 8
# Channels a head is whole multiples of: a norm's sum is over whole lane rows.
LANES = 128
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary", "arbitrary"))


def block_of(length):
    """Tokens a grid step at sequences of ``length`` tokens, or None where
    they are not whole blocks of whole halo tiles."""
    tokens = min(TOKENS, length)
    return tokens if tokens % HALO == 0 and length % tokens == 0 else None


def _shifted(window, back):
    """``window`` [n + PAD, c] to its rows ``PAD - back .. PAD - back + n``:
    the tokens ``back`` before each of the window's last n."""
    if back:
        window = pltpu.roll(window, back, 0)
    return window[PAD:]


def _convolved(window, taps):
    """(the causal convolution at the last n rows of ``window`` [n + PAD,
    c], the K shifted inputs it multiplied), the taps in ``causal_conv``'s
    order: the oldest token first."""
    width = taps.shape[0]
    shifted = [_shifted(window, width - 1 - i) for i in range(width)]
    total = taps[0:1] * shifted[0]
    for i in range(1, width):
        total = total + taps[i:i + 1] * shifted[i]
    return total, shifted


def _head_sum(a):
    return jnp.sum(a, axis=-1, keepdims=True)


def _pieces(tokens, channels, rows, head, body):
    """``body(first token, lane slice)`` over a block's [rows, head]
    pieces: a loop over the tokens, the heads unrolled inside it."""
    def step(at, _):
        at = pl.multiple_of(at * rows, rows)
        for lane in range(0, channels, head):
            body(at, pl.ds(lane, head))

    lax.fori_loop(0, tokens // rows, step, None)


# ------------------------------------------------------------------ forward
def _forward_kernel(x_ref, before_ref, taps_ref, q_ref, k_ref, v_ref, ext_ref,
                    *, key_blocks, head, rows):
    """``ext_ref`` [PAD + tokens, channels]: the block's activations in
    float32 under the PAD tokens before them."""
    j, t = pl.program_id(0), pl.program_id(2)
    tokens, channels = x_ref.shape
    before = before_ref[...].astype(F32)[HALO - PAD:]
    ext_ref[:PAD] = jnp.where(t > 0, before, 0.0)
    ext_ref[PAD:] = x_ref[...].astype(F32)

    def write(out_ref, normed, scale):
        def piece(at, lanes):
            c, _ = _convolved(ext_ref[pl.ds(at, rows + PAD), lanes],
                              taps_ref[:, lanes])
            a = c * jax.nn.sigmoid(c)
            if normed:
                a = a * lax.rsqrt(_head_sum(a * a) + 1e-6)
            out_ref[pl.ds(at, rows), lanes] = a * scale if scale else a

        _pieces(tokens, channels, rows, head, piece)

    pl.when(j < key_blocks)(
        lambda: write(q_ref, True, 1.0 / math.sqrt(head)))
    pl.when((j >= key_blocks) & (j < 2 * key_blocks))(
        lambda: write(k_ref, True, None))
    pl.when(j >= 2 * key_blocks)(lambda: write(v_ref, False, None))


def _held(first, count, batch, steps, row_block=None):
    """The index map (grid (channel block j, sequence b, token block t)) of
    an array whose columns are the channel blocks ``first .. first + count``
    of the grid: inside them block (b, t, j - first), before them the first
    block they will touch and after them the last they did, so that the
    array's block moves (and is fetched or written) for its own grid steps
    only. ``row_block`` turns (t, steps) into the row block to take."""
    row_block = row_block or (lambda t, steps: t)

    def index(j, b, t):
        before, after = j < first, j >= first + count
        hold = lambda at, last: jnp.where(
            before, 0, jnp.where(after, last, at))
        return (hold(b, batch - 1), row_block(hold(t, steps - 1), steps),
                jnp.clip(j - first, 0, count - 1))

    return index


def blocks_of(length, width, conv_width, key_width, head):
    """(tokens, channels) a grid step for sequences of ``length`` tokens of
    ``width`` channels, the first ``conv_width`` of them convolved: q and k
    ``key_width`` each in heads of ``head``, then v; or None where they are
    not whole token blocks of whole halo tiles and whole 128-lane heads."""
    tokens = block_of(length)
    channels = math.gcd(key_width, conv_width - 2 * key_width, CHANNELS)
    whole = tokens is not None and head % LANES == 0 and channels % head == 0 \
        and width >= conv_width > 2 * key_width and width % channels == 0
    return (tokens, channels) if whole else None


def _sizes(x, taps, key_width, head):
    """(batch, tokens a step, steps, channels a step, channel blocks of q,
    of q, k and v, of x; rows a loop step)."""
    batch, length, width = x.shape
    taps_width, conv_width = taps.shape
    blocks = blocks_of(length, width, conv_width, key_width, head)
    if blocks is None or not 1 <= taps_width <= PAD + 1:
        raise ValueError(
            f"{length} tokens of {conv_width} of {width} channels, heads of "
            f"{head}, {taps_width} taps: not whole blocks of {TOKENS} tokens "
            f"of whole {HALO}-row tiles and whole 128-lane heads")
    tokens, channels = blocks
    return (batch, tokens, length // tokens, channels, key_width // channels,
            conv_width // channels, width // channels, math.gcd(tokens, ROWS))


def _input_specs(tokens, steps, channels, blocks, batch, taps_width):
    """The activations' block, the HALO rows before it and after it, and
    the taps' columns, for the grid's first ``blocks`` channel blocks (the
    backward's grid goes on past them)."""
    per = tokens // HALO
    spec = lambda rows, row_block=None: pl.BlockSpec(
        (None, rows, channels), _held(0, blocks, batch, steps, row_block))
    before = spec(HALO, lambda t, steps: jnp.maximum(t * per - 1, 0))
    after = spec(HALO, lambda t, steps: jnp.minimum(
        (t + 1) * per, steps * per - 1))
    taps = pl.BlockSpec((taps_width, channels),
                        lambda j, b, t: (0, jnp.minimum(j, blocks - 1)))
    return spec(tokens), before, after, taps


# Jitted, so that a step's layers trace and lower each kernel's body once
# between them (a ``pallas_call`` traces its kernel at every call: half a
# second of a backward kernel's unrolled heads, nine calls a step).
_jit = functools.partial(jax.jit,
                         static_argnames=("key_width", "head", "interpret"))


@_jit
def forward(x, taps, *, key_width, head, interpret=False):
    """(q, k, v) float32 of activations x [B, S, >= C] and taps [K, C]:
    q and k ``key_width`` columns each in heads of ``head``, v the rest."""
    batch, tokens, steps, channels, key_blocks, blocks, _, rows = _sizes(
        x, taps, key_width, head)
    block, before, _, taps_spec = _input_specs(
        tokens, steps, channels, blocks, batch, taps.shape[0])
    value_blocks = blocks - 2 * key_blocks
    out = lambda first, count: pl.BlockSpec(
        (None, tokens, channels), _held(first, count, batch, steps))
    shape = lambda count: jax.ShapeDtypeStruct(
        (batch, x.shape[1], count * channels), F32)
    return pl.pallas_call(
        functools.partial(_forward_kernel, key_blocks=key_blocks, head=head,
                          rows=rows),
        grid=(blocks, batch, steps),
        in_specs=[block, before, taps_spec],
        out_specs=[out(0, key_blocks), out(key_blocks, key_blocks),
                   out(2 * key_blocks, value_blocks)],
        out_shape=[shape(key_blocks), shape(key_blocks), shape(value_blocks)],
        scratch_shapes=[pltpu.VMEM((PAD + tokens, channels), F32)],
        compiler_params=_PARAMS,
        name="gdn_conv_forward", interpret=interpret,
    )(x, x, taps)


# ----------------------------------------------------------------- backward
def _conv_cotangent(window, d_out, taps, normed, scale):
    """(the convolution's cotangent at the last n rows of ``window``
    [n + PAD, head] from the output's there, the shifted inputs): the
    convolution, the SiLU and the norm made again."""
    c, shifted = _convolved(window, taps)
    gate = jax.nn.sigmoid(c)
    d_a = d_out
    if normed:
        a = c * gate
        r = lax.rsqrt(_head_sum(a * a) + 1e-6)
        d_a = r * d_out - a * ((r * r * r) * _head_sum(d_out * a))
        if scale:
            d_a = d_a * scale
    return d_a * (gate * (1.0 + c * (1.0 - gate))), shifted


def _backward_kernel(x_ref, before_ref, after_ref, taps_ref,
                     dq_ref, dk_ref, dv_ref, dq_after, dk_after, dv_after,
                     dx_ref, dtaps_ref, ext_ref, dc_ref,
                     *, key_blocks, blocks, head, rows):
    """``ext_ref`` [PAD + tokens + PAD, channels]: the block's activations
    in float32 between the PAD tokens before and after them. ``dc_ref``
    [tokens + PAD, channels]: the convolution's cotangent at the block's
    tokens and at the PAD following ones (zero past the sequence's end).
    ``dtaps_ref`` [K * PAD, channels]: tap i's cotangent in rows i * PAD ..
    (i + 1) * PAD, the tokens folded onto PAD sublanes; the caller adds the
    PAD up. Past the grid's first ``blocks`` channel blocks x was not
    convolved: its cotangent there is zero."""
    j, b, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    last = t == pl.num_programs(2) - 1
    tokens, channels = x_ref.shape
    width = taps_ref.shape[0]
    fold = lambda a: jnp.sum(a.reshape(rows // PAD, PAD, head), axis=0)

    def read(d_ref, d_after, normed, scale):
        before = before_ref[...].astype(F32)[HALO - PAD:]
        after = after_ref[...].astype(F32)[:PAD]
        ext_ref[:PAD] = jnp.where(t > 0, before, 0.0)
        ext_ref[pl.ds(PAD, tokens)] = x_ref[...].astype(F32)
        ext_ref[pl.ds(PAD + tokens, PAD)] = jnp.where(last, 0.0, after)

        def tokens_step(at, totals):
            at = pl.multiple_of(at * rows, rows)
            sums = []                 # [head 0's K taps, head 1's, ...]
            for lane in range(0, channels, head):
                lanes = pl.ds(lane, head)
                d_c, shifted = _conv_cotangent(
                    ext_ref[pl.ds(at, rows + PAD), lanes],
                    d_ref[pl.ds(at, rows), lanes], taps_ref[:, lanes],
                    normed, scale)
                dc_ref[pl.ds(at, rows), lanes] = d_c
                sums += [fold(d_c * one) for one in shifted]
            return tuple(
                total + jnp.concatenate(sums[i::width], axis=1)
                for i, total in enumerate(totals))

        zero = jnp.zeros((PAD, channels), F32)
        totals = lax.fori_loop(0, tokens // rows, tokens_step,
                               (zero,) * width)
        first = (b == 0) & (t == 0)
        for i, total in enumerate(totals):
            at = slice(i * PAD, (i + 1) * PAD)
            dtaps_ref[at, :] = jnp.where(first, 0.0, dtaps_ref[at, :]) + total
        # The PAD following tokens' cotangent, from the halos.
        for lane in range(0, channels, head):
            lanes = pl.ds(lane, head)
            d_c, _ = _conv_cotangent(
                ext_ref[pl.ds(tokens, 2 * PAD), lanes], d_after[:, lanes],
                taps_ref[:, lanes], normed, scale)
            dc_ref[pl.ds(tokens, PAD), lanes] = jnp.where(last, 0.0, d_c)

        def transposed(at, lanes):
            window = dc_ref[pl.ds(at, rows + PAD), lanes]
            taps = taps_ref[:, lanes]
            total = None
            for i in range(width):
                ahead = width - 1 - i
                moved = pltpu.roll(window, rows + PAD - ahead, 0) \
                    if ahead else window
                term = taps[i:i + 1] * moved[:rows]
                total = term if total is None else total + term
            dx_ref[pl.ds(at, rows), lanes] = total.astype(dx_ref.dtype)

        _pieces(tokens, channels, rows, head, transposed)

    pl.when(j < key_blocks)(
        lambda: read(dq_ref, dq_after, True, 1.0 / math.sqrt(head)))
    pl.when((j >= key_blocks) & (j < 2 * key_blocks))(
        lambda: read(dk_ref, dk_after, True, None))
    pl.when((j >= 2 * key_blocks) & (j < blocks))(
        lambda: read(dv_ref, dv_after, False, None))

    @pl.when(j >= blocks)
    def _():
        dx_ref[...] = jnp.zeros_like(dx_ref)


@_jit
def backward(x, taps, d_q, d_k, d_v, *, key_width, head, interpret=False):
    """(d_x in x's shape and dtype, zero past the taps' columns; d_taps
    [K, C] float32)."""
    batch, tokens, steps, channels, key_blocks, blocks, every, rows = _sizes(
        x, taps, key_width, head)
    width, conv_width = taps.shape
    value_blocks = blocks - 2 * key_blocks
    per = tokens // PAD
    following = lambda t, steps: jnp.minimum((t + 1) * per, steps * per - 1)
    own = lambda first, count: pl.BlockSpec(
        (None, tokens, channels), _held(first, count, batch, steps))
    halo = lambda first, count: pl.BlockSpec(
        (None, PAD, channels),
        _held(first, count, batch, steps, row_block=following))
    regions = [(0, key_blocks), (key_blocks, key_blocks),
               (2 * key_blocks, value_blocks)]
    d_x, d_taps = pl.pallas_call(
        functools.partial(_backward_kernel, key_blocks=key_blocks,
                          blocks=blocks, head=head, rows=rows),
        grid=(every, batch, steps),
        in_specs=[*_input_specs(tokens, steps, channels, blocks, batch, width),
                  *(own(*region) for region in regions),
                  *(halo(*region) for region in regions)],
        out_specs=[
            pl.BlockSpec((None, tokens, channels), lambda j, b, t: (b, t, j)),
            pl.BlockSpec((width * PAD, channels),
                         lambda j, b, t: (0, jnp.minimum(j, blocks - 1)))],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((width * PAD, conv_width), F32)],
        scratch_shapes=[pltpu.VMEM((tokens + 2 * PAD, channels), F32),
                        pltpu.VMEM((tokens + PAD, channels), F32)],
        compiler_params=_PARAMS,
        name="gdn_conv_backward", interpret=interpret,
    )(x, x, x, taps, d_q, d_k, d_v, d_q, d_k, d_v)
    return d_x, jnp.sum(d_taps.reshape(width, PAD, conv_width), axis=1)
