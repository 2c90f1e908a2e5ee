"""Qwen3-Next's hybrid decoder: Gated DeltaNet x3 : gated attention x1,
each followed by a sparse mixture of experts with a shared expert
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, ``qwen3_next``).

The layer equations are written out in ``perfbench/refmodels/qwen3_next.py``
(the frozen plain reference; parameter names and shapes are equal leaf for
leaf, ``tests/test_qwen3_next.py`` holds the two together). What differs
here is how they are computed:

  * the delta rule in its **chunked form**: inside a chunk of ``chunk``
    tokens the recurrence is a unit lower-triangular system, solved for all
    chunks at once on the MXU (the WY form: ``delta = U - W S``,
    ``delta_chunks``, ``GDN_SEQUENCES`` at a time), and only the d_k x d_v
    state crosses chunks, in one pass for the whole batch. The XLA form of
    both (``delta_chunks``, ``scan_chunks``, with ``pad_to_chunks``,
    ``chunk_of`` and ``causal_conv``) lives in ``models/delta_rule.py``,
    which ``kimi_linear`` imports too: there the rule is told by the shape
    of ``g`` whether the decay is a number a head (this model) or a number
    a key channel; this file keeps what is Qwen's own. Both have two
    forms, chosen by ``delta_form`` and ``scan_form`` from what they
    observe (no flag): on a TPU at chunks of 64, heads of whole lane rows
    and whole blocks of chunks the fused kernels of ``ops/delta_chunks.py``,
    forward and backward, in which a chunk's blocks stay in VMEM and the
    triangular system is inverted by products, and for the state's pass
    the two kernels of ``ops/delta_scan.py`` (``kernel_scan_chunks``), in
    which the state stays in VMEM across the chunks, forward, and its
    cotangent across the same chunks in reverse, backward, from the state
    the forward rule saved at every chunk's start; elsewhere XLA's
    products and ``triangular_solve`` and one ``lax.scan``
    (``scan_chunks``; backward by autodiff through the same scan), the
    oracles of the kernels' tests (``forms`` says which compiled:
    ``delta_form``, ``scan_form``);
  * a DeltaNet layer's depthwise causal convolution, SiLU and unit norms
    in two forms as well, chosen by ``conv_form`` (no flag): on a TPU at
    heads of whole lane rows and whole token blocks the two fused kernels
    of ``ops/gdn_conv.py``, which read the projection's output in place
    and write q, k and v in the layout the chunk kernels read; elsewhere
    ``causal_conv`` and XLA's float32 passes, the oracle of the kernels'
    tests (``forms`` says which compiled: ``conv_form``). Where both are
    kernels a step's sequences go through them in one call each; in the
    XLA forms a sequence at a time (``GDN_SEQUENCES``);
  * attention without the [B, H, S, S] scores ever in HBM at once
    (``models/decoder.py``'s ``blocked_causal_attention``): on a TPU at
    whole tiles the fused flash kernels of ``ops/flash_attention.py``,
    elsewhere blocks of queries, each against the keys up to its own end
    (``forms`` says which compiled: ``attention_form``);
  * the expert layer, the query-block attention, the rotary embedding, the
    norms and the head's loss are ``models/decoder.py``'s, shared with the
    other decoder of the zoo (``keye_vl2``): a dropless share of an expert
    group, told which experts it holds;
  * the head and the loss a sequence at a time; every layer under
    ``jax.checkpoint``, which keeps nothing of a layer but, by name, the
    outputs of the two checkpoints nested inside it (``KEPT_BYTES``,
    ``kept_across_remat``): the chunks' algebra (``prepare``) and the
    attention's query blocks (``one``) then run twice a step, forward and
    for their own backward, and not a third time when the layer's forward
    is replayed (the attention's forward kernel once: its backward takes
    the output and the rows' log-sum-exp, kept under the same name; the
    chunks' forward kernel once: its backward takes the kernel's inputs
    and makes a chunk's blocks itself).

Precision is the reference's: float32 parameters, residual stream, norms,
router, softmax, recurrence state and loss; matrix products in ``dtype``.
The chunked algebra's small products run at the highest matmul precision,
so that they agree with the reference's float32 recurrence before both
round to ``dtype`` for the output projection.

Stages are named for the device trace (``layer/gdn_proj``,
``layer/gdn_scan``, ``layer/attn``, ``layer/moe_router``,
``layer/moe_experts``, ``layer/shared_expert``, ``layer/head``), forward
and backward alike; the per-layer loads of the held experts and the slots
dropped (always 0) go out with the loss for ``obs.counters.moe_counters``.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.numpy import log as _ln   # graftlint reads any x.log(...) as a metrics call

from gtopkssgd_tpu.models import decoder
from gtopkssgd_tpu.models.decoder import (
    F32, MOE_COUNTS, SparseMoE, _normal, attention_form,
    blocked_causal_attention, decoder_shell, dense, kept_by_name,
    query_block_of, rms_norm0, rotary)
from gtopkssgd_tpu.models.delta_rule import (
    causal_conv, chunk_of, delta_chunks, pad_to_chunks, scan_chunks)
from gtopkssgd_tpu.ops import delta_chunks as delta_kernels
from gtopkssgd_tpu.ops import delta_scan as scan_kernels
from gtopkssgd_tpu.ops import gdn_conv as conv_kernels

# The published sizes (config.json of Qwen3-Next-80B-A3B-Instruct) with the
# three cuts of perfbench/configs/qwen3_next_80b_a3b_ep64.json, whose
# ``sizes`` a test holds equal to this preset key for key; and the size
# every CPU test runs.
PRESETS = {
    "80b_a3b_ep64": dict(
        hidden_size=2048, num_hidden_layers=4, full_attention_interval=4,
        linear_num_key_heads=16, linear_num_value_heads=32,
        linear_key_head_dim=128, linear_value_head_dim=128,
        linear_conv_kernel_dim=4,
        num_attention_heads=16, num_key_value_heads=2, head_dim=256,
        partial_rotary_factor=0.25, rope_theta=10000000, rms_norm_eps=1e-6,
        num_experts=512, num_experts_per_tok=10, moe_intermediate_size=512,
        shared_expert_intermediate_size=512, norm_topk_prob=True,
        experts_held=8, expert_offset=0, expert_parallel=64,
        vocab_size=151936, vocab_rows=18992, seq_len=4096),
    "tiny": dict(
        hidden_size=64, num_hidden_layers=4, full_attention_interval=4,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=16,
        linear_conv_kernel_dim=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        partial_rotary_factor=0.25, rope_theta=10000000, rms_norm_eps=1e-6,
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, norm_topk_prob=True,
        experts_held=4, expert_offset=0, expert_parallel=4,
        vocab_size=1024, vocab_rows=128, seq_len=128),
}


# Sequences whose convolution and chunk algebra are live at once in a
# Gated DeltaNet layer, by the form of the two (the XLA form of the algebra
# is ``delta_rule.delta_chunks``, a number a head). In the XLA form their
# float32 intermediates are what fills the chip: 2.7 GB a sequence of 4,096
# tokens at the published widths, so one at a time, in a ``lax.map``. With
# the chunks' blocks in VMEM (PR 38) a sequence still cost the convolution's
# [4096, 8192] float32 passes, 0.42 GB (the cell's step read 13.62 GB at 1,
# 14.04 at 2 and 14.93 at all 4 against a line of 14.5). With the
# convolution, SiLU and norms in kernels too (PR 43) those passes do not
# exist: the step reads 14.02 GB at 1, 13.75 at 2 and 13.75 at all 4
# (compiled for a described v5e), so the cell's four sequences go through
# both kernels in one call each and the loop, its stacking and its slices
# are gone.
GDN_SEQUENCES = {"xla": 1, "kernel": 4}

# What a layer's remat keeps from its forward to its backward pass, by
# ``checkpoint_name``: the stacked outputs of ``GatedDeltaNet``'s ``prepare``
# (u, w, q_in, k_out [n, B, H, C, 128] and attn [n, B, H, C, C] float32:
# 4 x 268.4 + 134.2 MB = 1.208 GB a layer at 4 x 4,096 tokens) and the
# attention's output before its gate ([B, S, 16, 256] float32, 268 MB; in
# the kernel form its rows' log-sum-exp too, 1 MB that the budget leaves out).
# ``prepare`` and ``one`` keep their own ``jax.checkpoint``: that is what
# holds one sequence's 2.7 GB (``GDN_SEQUENCES``) and one query block's
# scores to the moment they are used; only their outputs live on. Without
# the names the layer's replay would run both a second time just to hand
# their own backward passes the same values.
KEPT_CHUNKS, KEPT_ATTENTION = "gdn_chunks", "attn_out"
# (Both are this model's readings at its own N and shapes: ``kimi_linear``,
# which shares ``delta_rule``, decides what it keeps from its own step.)
# Bytes the layers of one step may keep so, together: what a v5e (15.75
# GiB, ``bytes_limit`` 16.909 GB) has left after a GiB of margin and the
# step itself. With nothing kept XLA's analysis gives the benchmark's step
# 10.632 GB at 4 x 4,096 tokens and 16.118 GB at 8 x 4,096: 5.146 GB of
# state and flat vectors (16 B a parameter) and 334,844 B a token. At the
# cell's 16,384 tokens that leaves 5.20 GB, the four layers keep 3 x 1.208 +
# 0.268 = 3.89 GB and the step reads 14.306 GB (PERF.md section 6, PR 30);
# at 8 sequences nothing is left and every layer is rematerialised whole,
# as all were before (the published depth of 48 would keep 43 GB). Both
# constants are PR 30's readings of the XLA form; since the attention's and
# the chunks' kernels (PRs 36, 38) the cell's step reads 13.62 GB with the
# four layers kept and 11.98 with none, the XLA form of the chunks 13.81
# and 11.99 (compiled for a described v5e, PR 38).
KEPT_BYTES = 16_909_000_000 - 2 ** 30 - 5_146_000_000
STEP_BYTES_A_TOKEN = 334_844


def is_attention(sizes, i):
    return (i + 1) % sizes["full_attention_interval"] == 0


def kept_budget(batch, length):
    return max(0, KEPT_BYTES - STEP_BYTES_A_TOKEN * batch * length)


def kept_across_remat(sizes, batch, length, budget):
    """[(bytes layer i's remat would keep by name, whether it does)]
    for a step of ``batch`` sequences of ``length`` tokens: in layer order,
    a layer keeps its outputs if they fit in what is left of ``budget``."""
    chunk = chunk_of(sizes["seq_len"])
    chunks = -(-length // chunk)
    d_k, d_v = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    delta = 4 * chunks * batch * sizes["linear_num_value_heads"] * (
        chunk * (d_v + 3 * d_k + chunk) + 1)   # u; w, q_in, k_out; attn; decay
    attention = 4 * batch * length * sizes["num_attention_heads"] \
        * sizes["head_dim"]
    out, total = [], 0
    for i in range(sizes["num_hidden_layers"]):
        size = attention if is_attention(sizes, i) else delta
        keep = total + size <= budget
        total += size * keep
        out.append((size, keep))
    return out



def _a_log(key, shape, dtype=F32):
    return _ln(jax.random.uniform(key, shape, dtype, 1e-4, 16.0))


def _dt_bias(key, shape, dtype=F32):
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(0.1)))
    return dt + _ln(-jnp.expm1(-dt))


def _conv(key, shape, dtype=F32):
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def conv_form(length, key_width, value_width, d_k):
    """``kernel`` where a Gated DeltaNet layer's convolution, SiLU and unit
    norms run as the two Pallas kernels of ``ops/gdn_conv.py``, ``xla`` where
    as ``causal_conv`` and XLA's float32 passes: the kernels need a TPU,
    q, k (``key_width`` channels each, heads of ``d_k``) and v
    (``value_width``) of whole 128-lane heads, and a length of whole token
    blocks."""
    width = 2 * key_width + value_width
    whole = conv_kernels.blocks_of(
        length, width + value_width, width, key_width, d_k) is not None
    return "kernel" if decoder.on_tpu() and whole else "xla"


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def kernel_conv(x, taps, key_width, d_k):
    """The convolution, SiLU, split and unit norms of ``prepare`` in kernels
    (interpret mode off the TPU): x [B, S, >= C] in any float dtype, read in
    place (the columns past the taps' C are the layer's output gate), to
    float32 q, k [B, S, key_width] (q over sqrt(d_k)) and v [B, S, the
    rest], in the layout ``kernel_delta_chunks`` reads."""
    return conv_kernels.forward(x, taps, key_width=key_width, head=d_k,
                                interpret=not decoder.on_tpu())


def _kernel_conv_fwd(x, taps, key_width, d_k):
    # The residuals are the inputs: the backward kernel makes the
    # convolution, the SiLU and the norms again in VMEM.
    return kernel_conv(x, taps, key_width, d_k), (x, taps)


def _kernel_conv_bwd(key_width, d_k, residuals, cotangents):
    return conv_kernels.backward(*residuals, *cotangents, key_width=key_width,
                                 head=d_k, interpret=not decoder.on_tpu())


kernel_conv.defvjp(_kernel_conv_fwd, _kernel_conv_bwd)


# ----------------------------------------------------- chunked delta rule
def delta_form(length, chunk, d_k, d_v):
    """``kernel`` where the chunks' algebra runs as the Pallas kernels of
    ``ops/delta_chunks.py``, ``xla`` where as ``delta_chunks``' products and
    triangular solve: the kernels need a TPU, chunks of 64, heads of whole
    128-lane rows and a padded length of whole blocks of chunks."""
    whole = chunk == 64 and d_k % 128 == 0 and d_v % 128 == 0 \
        and delta_kernels.block_of(-(-length // chunk)) is not None
    return "kernel" if decoder.on_tpu() and whole else "xla"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunk_kernels(q, k, v, gamma, beta, key_heads):
    return delta_kernels.forward(q, k, v, gamma, beta, key_heads=key_heads,
                                 interpret=not decoder.on_tpu())


def _chunk_kernels_fwd(q, k, v, gamma, beta, key_heads):
    # The residuals are the inputs: under ``prepare``'s own checkpoint
    # nothing of the forward kernel is computed again for the backward one,
    # which makes a chunk's blocks itself.
    return (_chunk_kernels(q, k, v, gamma, beta, key_heads),
            (q, k, v, gamma, beta))


def _chunk_kernels_bwd(key_heads, residuals, cotangents):
    return delta_kernels.backward(*residuals, *cotangents,
                                  key_heads=key_heads,
                                  interpret=not decoder.on_tpu())


_chunk_kernels.defvjp(_chunk_kernels_fwd, _chunk_kernels_bwd)


def kernel_delta_chunks(q, k, v, g, beta, chunk):
    """``delta_chunks`` with the algebra in kernels (interpret mode off the
    TPU): the same values and gradients, but q and k come by *key* head,
    [B, S, H_k, d_k] with H a multiple of H_k, and value head h reads key
    head h // (H / H_k): the repeat to H heads is never made."""
    batch, length, heads = g.shape
    n = length // chunk
    # [B, S, H] -> [B, H, n, C]: a chunk's numbers along the lanes.
    numbers = lambda a: a.reshape(batch, n, chunk, heads).transpose(0, 3, 1, 2)
    flat = lambda a: a.reshape(batch, length, -1)
    gamma = jnp.cumsum(numbers(g), axis=-1)
    out = _chunk_kernels(flat(q), flat(k), flat(v), gamma, numbers(beta),
                         q.shape[2])
    return out + (jnp.exp(gamma[..., -1]).transpose(2, 0, 1)[..., None, None],)


def scan_form(length, chunk, d_k, d_v):
    """``kernel`` where the state's pass runs as the Pallas kernels of
    ``ops/delta_scan.py``, ``xla`` where as ``scan_chunks``' ``lax.scan``:
    where the chunks' algebra is in kernels (``delta_form``) the state's
    pass over what they wrote is too."""
    return delta_form(length, chunk, d_k, d_v)


@jax.custom_vjp
def _scan_kernels(u, w, attn, q_in, k_out, decay):
    return scan_kernels.forward(u, w, attn, q_in, k_out, decay,
                                interpret=not decoder.on_tpu())


def _scan_kernels_fwd(*prepared):
    # What the backward kernel takes besides the inputs: the state at the
    # start of every chunk. A layer's first forward under its remat asks
    # for no residuals and runs the primal above, which writes none
    # (``optimize_remat``: an opaque call's unused output is not dropped
    # otherwise).
    out, states = scan_kernels.forward(*prepared, states=True,
                                       interpret=not decoder.on_tpu())
    return out, prepared + (states,)


def _scan_kernels_bwd(residuals, d_out):
    return scan_kernels.backward(*residuals, d_out,
                                 interpret=not decoder.on_tpu())


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd,
                     optimize_remat=True)


def kernel_scan_chunks(u, w, attn, q_in, k_out, decay):
    """``scan_chunks`` with the state in the kernels' VMEM (interpret mode
    off the TPU): the same values and gradients. The kernels take ``decay``
    [B, H, n], the chunks along the lanes, and write o as [B, n C, H, d_v]
    themselves: no transpose on either side of them."""
    return _scan_kernels(u, w, attn, q_in, k_out,
                         decay[..., 0, 0].transpose(1, 2, 0))


def chunked_delta_rule(q, k, v, g, beta, chunk):
    """The gated delta rule over whole sequences, chunk by chunk: q, k
    [B, S, H, d_k], v [B, S, H, d_v], g and beta [B, S, H], float32, any
    S, to o [B, S, H, d_v]. The chunks' algebra in the form ``delta_form``
    finds."""
    arrays, length = pad_to_chunks((q, k, v, g, beta), chunk)
    shapes = (length, chunk, q.shape[-1], v.shape[-1])
    chunks = kernel_delta_chunks if delta_form(*shapes) == "kernel" \
        else delta_chunks
    scan = kernel_scan_chunks if scan_form(*shapes) == "kernel" \
        else scan_chunks
    return scan(*chunks(*arrays, chunk))[:, :length]


# ------------------------------------------------------------------ modules
class GatedDeltaNet(nn.Module):
    sizes: dict
    dtype: Any

    @nn.compact
    def __call__(self, x):
        s, dtype = self.sizes, self.dtype
        d = s["hidden_size"]
        h_k, h_v = s["linear_num_key_heads"], s["linear_num_value_heads"]
        d_k, d_v = s["linear_key_head_dim"], s["linear_value_head_dim"]
        key_w, val_w = h_k * d_k, h_v * d_v
        w_qkvz = self.param("in_proj_qkvz", _normal(),
                            (d, 2 * key_w + 2 * val_w), F32)
        w_ba = self.param("in_proj_ba", _normal(), (d, 2 * h_v), F32)
        conv = self.param("conv", _conv, (s["linear_conv_kernel_dim"],
                                          2 * key_w + val_w), F32)
        a_log = self.param("A_log", _a_log, (h_v,), F32)
        dt_bias = self.param("dt_bias", _dt_bias, (h_v,), F32)
        w_g = self.param("norm", nn.initializers.ones, (d_v,), F32)
        w_out = self.param("out_proj", _normal(), (val_w, d), F32)

        batch, length = x.shape[:2]
        chunk = chunk_of(s["seq_len"])
        kernels = delta_form(length, chunk, d_k, d_v) == "kernel"
        scan = kernel_scan_chunks if scan_form(
            length, chunk, d_k, d_v) == "kernel" else scan_chunks
        fused = conv_form(length, key_w, val_w, d_k) == "kernel"
        group = math.gcd(batch, GDN_SEQUENCES[
            "kernel" if fused and kernels else "xla"])
        # The kernels read q and k by key head; the XLA form by value head.
        repeat = (lambda a: a) if kernels else (
            lambda a: jnp.repeat(a, h_v // h_k, axis=2))

        @jax.checkpoint
        def prepare(args):
            """Convolution, heads and the chunks' own algebra for ``group``
            sequences: their float32 intermediates are live for these
            sequences only, and again in the backward pass."""
            qkv, ba = args
            with jax.named_scope("layer/gdn_proj"):
                ba = ba.astype(F32)
                heads = lambda a, n, w: a.reshape(group, length, n, w)
                if fused:
                    q, k, v = kernel_conv(qkv, conv, key_w, d_k)
                    q, k = (repeat(heads(a, h_k, d_k)) for a in (q, k))
                    v = heads(v, h_v, d_v)
                else:
                    qkv = jax.nn.silu(causal_conv(qkv.astype(F32), conv))
                    q = heads(qkv[..., :key_w], h_k, d_k)
                    k = heads(qkv[..., key_w:2 * key_w], h_k, d_k)
                    v = heads(qkv[..., 2 * key_w:], h_v, d_v)
                    unit = lambda a: a * lax.rsqrt(
                        jnp.sum(a * a, -1, keepdims=True) + 1e-6)
                    q = repeat(unit(q) / math.sqrt(d_k))
                    k = repeat(unit(k))
                beta = jax.nn.sigmoid(ba[..., :h_v])
                g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., h_v:] + dt_bias)
            with jax.named_scope("layer/gdn_scan"):
                arrays, _ = pad_to_chunks((q, k, v, g, beta), chunk)
                return (kernel_delta_chunks if kernels else delta_chunks)(
                    *arrays, chunk)

        with jax.named_scope("layer/gdn_proj"):
            qkvz = dense(x, w_qkvz, dtype)
            ba = dense(x, w_ba, dtype)
        # The kernels read qkvz's convolved columns in place.
        qkv = qkvz if fused else qkvz[..., :2 * key_w + val_w]
        if group == batch:
            prepared, whole = prepare((qkv, ba)), lambda a: a
        else:
            grouped = lambda a: a.reshape(
                (batch // group, group) + a.shape[1:])
            prepared = lax.map(prepare, (grouped(qkv), grouped(ba)))
            # [groups, n, group, H, C, ...] -> [n, B, H, C, ...]
            whole = lambda a: jnp.moveaxis(a, 0, 1).reshape(
                (a.shape[1], batch) + a.shape[3:])
        with jax.named_scope("layer/gdn_scan"):
            # The state crosses the chunks of every sequence in one pass.
            o = scan(*(checkpoint_name(whole(a), KEPT_CHUNKS)
                       for a in prepared))[:, :length]
        with jax.named_scope("layer/gdn_proj"):
            z = qkvz[..., 2 * key_w + val_w:].astype(F32).reshape(
                batch, length, h_v, d_v)
            o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + s["rms_norm_eps"]) * w_g
            gated = (o * jax.nn.silu(z)).reshape(batch, length, val_w)
            return dense(gated, w_out, dtype)


class GatedAttention(nn.Module):
    sizes: dict
    dtype: Any

    @nn.compact
    def __call__(self, x):
        s, dtype = self.sizes, self.dtype
        d, dim = s["hidden_size"], s["head_dim"]
        heads, kv_heads = s["num_attention_heads"], s["num_key_value_heads"]
        w_q = self.param("q_proj", _normal(), (d, heads * 2 * dim), F32)
        w_k = self.param("k_proj", _normal(), (d, kv_heads * dim), F32)
        w_v = self.param("v_proj", _normal(), (d, kv_heads * dim), F32)
        w_qn = self.param("q_norm", nn.initializers.zeros, (dim,), F32)
        w_kn = self.param("k_norm", nn.initializers.zeros, (dim,), F32)
        w_o = self.param("o_proj", _normal(), (heads * dim, d), F32)

        batch, length = x.shape[:2]
        eps = s["rms_norm_eps"]
        rotary_dims = int(dim * s["partial_rotary_factor"])
        with jax.named_scope("layer/attn"):
            with jax.named_scope("part/proj"):
                qg = dense(x, w_q, dtype).reshape(
                    batch, length, heads, 2 * dim)
                q, gate = qg[..., :dim], qg[..., dim:].astype(F32)
                k = dense(x, w_k, dtype).reshape(batch, length, kv_heads, dim)
                v = dense(x, w_v, dtype).reshape(
                    batch, length, kv_heads, dim).astype(F32)
            with jax.named_scope("part/pointwise"):
                q = rotary(rms_norm0(q, w_qn, eps), s["rope_theta"],
                           rotary_dims)
                k = rotary(rms_norm0(k, w_kn, eps), s["rope_theta"],
                           rotary_dims)
            # Its own parts inside: part/layout and part/kernel.
            out = blocked_causal_attention(
                q, k, v, dtype, query_block_of(s["seq_len"]),
                kept=KEPT_ATTENTION)
            with jax.named_scope("part/pointwise"):
                out = out * jax.nn.sigmoid(gate)
            with jax.named_scope("part/proj"):
                return dense(
                    out.reshape(batch, length, heads * dim), w_o, dtype)



class Layer(nn.Module):
    sizes: dict
    dtype: Any
    attention: bool

    @nn.compact
    def __call__(self, x):
        s = self.sizes
        d, eps = s["hidden_size"], s["rms_norm_eps"]
        w_in = self.param("input_norm", nn.initializers.zeros, (d,), F32)
        w_post = self.param("post_norm", nn.initializers.zeros, (d,), F32)
        mixer = (GatedAttention if self.attention else GatedDeltaNet)(
            s, self.dtype, name="mixer")
        # The layer's own norms and residual adds count for the kind they
        # feed (part/pointwise: read inside layer/attn alone); scopes inside
        # the mixer and the expert layer are innermost.
        with jax.named_scope("layer/attn" if self.attention
                             else "layer/gdn_proj"):
            with jax.named_scope("part/pointwise"):
                h = rms_norm0(x, w_in, eps)
            y = mixer(h)
            with jax.named_scope("part/pointwise"):
                x = x + y.astype(F32)
        with jax.named_scope("layer/moe_router"):
            y, load, dropped, _ = SparseMoE(s, self.dtype, name="moe")(
                rms_norm0(x, w_post, eps))
            return x + y, (load, dropped)



class Qwen3Next(nn.Module):
    """``__call__(tokens, targets)`` gives the mean cross-entropy and the
    expert layers' counts ``{"moe_load": [layers, held], "moe_dropped":
    [layers]}``; without targets, the logits [B, S, vocab_rows]."""
    preset: str = "80b_a3b_ep64"
    dtype: Any = jnp.float32

    @property
    def sizes(self):
        return PRESETS[self.preset]

    def forms(self, length):
        """What the step compiles as at sequences of ``length``, for the
        run's manifest and ``train`` records: a run on the chip that fell
        back to the blocked attention says so."""
        s = self.sizes
        d_k, d_v = s["linear_key_head_dim"], s["linear_value_head_dim"]
        return {"attention_form": attention_form(length, s["head_dim"]),
                "delta_form": delta_form(
                    length, chunk_of(s["seq_len"]), d_k, d_v),
                "scan_form": scan_form(
                    length, chunk_of(s["seq_len"]), d_k, d_v),
                "conv_form": conv_form(
                    length, s["linear_num_key_heads"] * d_k,
                    s["linear_num_value_heads"] * d_v, d_k)}

    @nn.compact
    def __call__(self, tokens, targets=None, *, train: bool = False):
        s = self.sizes
        # A layer past the budget keeps the expert layer's dispatch alone.
        policy = {True: kept_by_name(KEPT_CHUNKS, KEPT_ATTENTION),
                  False: kept_by_name()}
        keeps = [keep for _, keep in kept_across_remat(
            s, *tokens.shape, kept_budget(*tokens.shape))]
        return decoder_shell(
            self, tokens, targets,
            lambda i: nn.remat(Layer, policy=policy[keeps[i]])(
                s, self.dtype, is_attention(s, i), name=f"layer_{i}"),
            len(keeps), MOE_COUNTS)
