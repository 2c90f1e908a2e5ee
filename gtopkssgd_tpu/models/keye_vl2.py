"""Keye-VL-2.0-30B-A3B's decoder: grouped-query attention under a learned
sparse-attention indexer (each query attends to the ``topk`` keys its
indexer scores highest), each layer followed by a sparse mixture of experts
with no shared expert
(https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B, ``KeyeVL2``; the
language model's text-only step: no vision tower is built).

The layer equations are written out in ``perfbench/refmodels/keye_vl2.py``
(the frozen plain reference; parameter names and shapes are equal leaf for
leaf, ``tests/test_keye_vl2.py`` holds the two together). What differs here
is how they are computed:

  * queries in blocks of ``q_chunk_size``, ``BUCKET`` blocks of equal key
    extent at a time (one ``lax.map``: a block sees the keys up to its
    bucket's end, the rest of the triangle masked), so the [S, S] index
    scores and the [H, S, S] attention scores never exist at once;
  * the selection, once a layer and outside every gradient
    (``select_thresholds``): a query's threshold tau_t, the ``topk``-th
    largest of its index scores, by 32 counting passes over the scores' bit
    patterns (``kth_largest``: exact, no sort). A layer's remat keeps the
    thresholds by name (``KEPT_SELECTION``, 4 bytes a query): the backward
    pass compares the recomputed scores with them and does not select
    again;
  * the attention in its **masked form**: every key up to the bucket's end
    is multiplied, the keys outside S_t = {s <= t: I_ts >= tau_t} masked out
    of the softmax, one key-value head's query heads at a time, the softmax's
    exponent taken against a bound from the norms (``LOGIT_CAP``), and the
    probabilities' sum over the heads carried along for the indexer's loss;
  * on a TPU, at shapes that fill whole tiles, the **kernel form**
    ("attention, kernel form" below; ``attention_form`` decides, from the
    backend and the shapes alone): the index scores and their gradient as
    the Pallas kernels of ``ops/dsa_index.py``, which keep the
    [J, block, keys] products in VMEM and hand out a bucket's [rows, keys]
    scores, made once a forward pass (the thresholds, the mask and the loss
    read that one array) and once more in the backward rule; the attention
    as the kernels of ``ops/dsa_attention.py``, which keep the
    [heads, block, keys] arrays in VMEM and read the layer's mask, a byte a
    pair. XLA's form (``index_scores``, ``select_thresholds``,
    ``attend_group``) is every other backend's and the kernels' oracle;
  * the expert layer, the head and the loss are ``models/decoder.py``'s, as
    the other decoder's are; every layer under ``jax.checkpoint``, which
    keeps by name the thresholds and what the query blocks' own checkpoint
    gives out (``KEPT_ATTENTION``), so that a block runs twice a step, not
    three times (in the kernel form: the mask, the output, the weights' sums
    and the probabilities, so that the replay runs no kernel at all).

Precision is the reference's: float32 parameters, residual stream, norms,
rotary, the sum over the indexer's heads, thresholds, both softmaxes,
router and both losses; projections, q.k, a.v and the index products
qI.kI in ``dtype`` with float32 accumulation.

Stages are named for the device trace (``layer/dsa_index``: the indexer's
projections, norm, rotary, index scores and loss; ``layer/dsa_select``:
thresholds and masks; ``layer/attn``; ``layer/moe_router``;
``layer/moe_experts``; ``layer/head``), forward and backward alike. With
the loss go the held experts' loads and dropped slots (always 0) and, for
``obs.counters.dsa_counters``, each layer's number of keys kept and its
indexer loss.
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

from gtopkssgd_tpu.ops import dsa_attention as kernels
from gtopkssgd_tpu.ops import dsa_index as index_kernels
from gtopkssgd_tpu.models.decoder import (
    F32, MOE_COUNTS, SparseMoE, _normal, decoder_shell, dense, kept_by_name,
    kernel_layout, on_tpu, rms_norm0, rotary)

# The published sizes (config.json of Keye-VL-2.0-30B-A3B; ``sa_config``'s
# keys flat) with the three cuts of
# perfbench/configs/keye_vl2_30b_a3b_ep16.json, whose ``sizes`` a test holds
# equal to this preset key for key; and the size every CPU test runs.
PRESETS = {
    "30b_a3b_ep16": dict(
        hidden_size=2048, num_hidden_layers=4,
        num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        rope_theta=10000000, rms_norm_eps=1e-6,
        indexer_num_heads=16, indexer_head_dim=64, indexer_num_kv_heads=1,
        topk=2048, q_chunk_size=512, kv_chunk_size=512,
        num_experts=128, num_experts_per_tok=8, moe_intermediate_size=768,
        norm_topk_prob=True,
        experts_held=8, expert_offset=0, expert_parallel=16,
        vocab_size=151936, vocab_rows=18992, seq_len=16384),
    "tiny": dict(
        hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        rope_theta=10000000, rms_norm_eps=1e-6,
        indexer_num_heads=4, indexer_head_dim=16, indexer_num_kv_heads=1,
        topk=8, q_chunk_size=8, kv_chunk_size=8,
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
        norm_topk_prob=True,
        experts_held=4, expert_offset=0, expert_parallel=4,
        vocab_size=1024, vocab_rows=128, seq_len=48),
}

# Query blocks that share a key extent and one compiled body: the keys up
# to the end of the bucket's last block. At 4 a masked pair in eleven is
# beyond its block's end (8 bodies a layer at 16,384 tokens, not 32).
BUCKET = 4
# What a layer's remat keeps from its forward to its backward pass, by
# ``checkpoint_name``: the thresholds tau [B, S] float32 (64 KB a layer at
# 16,384 tokens; not differentiated), and the outputs of the query blocks'
# own checkpoint: the attention's output in ``dtype`` before its output
# projection ([B, S, H, D], 134 MB a layer), the indexer loss and the count
# of keys of every query. With them the layer's replay runs neither the
# selection nor a block's forward a second time: a block runs twice a step
# (forward, and once more for its own backward), not three times. A layer
# always keeps them: 0.54 GB over the four layers, no byte budget decides.
# (The kernel form keeps the output in float32 and two arrays more: below.)
KEPT_SELECTION, KEPT_ATTENTION = "dsa_tau", "dsa_attn_out"


# ---------------------------------------------------------------- selection
def index_scores(qi, ki, w, dtype):
    """I_ts = sum_j w_tj ReLU(qI_tj . kI_s): qi [B, Q, J, D], ki [B, K, D],
    w [B, Q, J] float32 -> [B, Q, K] float32."""
    dots = jnp.einsum("bqjd,bkd->bjqk", qi.astype(dtype), ki.astype(dtype),
                      preferred_element_type=F32)
    return jnp.sum(jnp.moveaxis(w, 2, 1)[..., None] * jax.nn.relu(dots), axis=1)


def ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def from_ordered_bits(u):
    """The inverse, but that 0 (under every float's pattern) gives -inf."""
    bits = jnp.where(u >> 31 == 1, u ^ jnp.uint32(1 << 31), ~u)
    return jnp.where(u == 0, -jnp.inf, lax.bitcast_convert_type(bits, F32))


def kth_largest(keys, k):
    """The ``k``-th largest of each row of uint32 ``keys`` [..., n], exactly
    (0 where a row has fewer than ``k`` non-zero keys): the answer's bits
    from the top, a bit staying set when ``k`` keys still reach it."""
    def body(i, prefix):
        reach = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= reach[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, reach, prefix)

    return lax.fori_loop(0, 32, body, jnp.zeros(keys.shape[:-1], jnp.uint32))


def row_thresholds(scores, rows, topk):
    """The ``topk``-th largest of each row of ``scores`` [B, Q, K] among the
    keys up to the row's own position ``rows`` [Q], -inf where they are
    fewer: [B, Q] float32."""
    valid = rows[:, None] >= jnp.arange(scores.shape[-1])[None, :]
    ranked = jnp.where(valid, ordered_bits(scores), 0)
    return from_ordered_bits(kth_largest(ranked, topk))


def buckets(length, block):
    """[(first query, queries, key extent)] of a sequence padded to whole
    blocks: ``BUCKET`` blocks a bucket, fewer in the last."""
    padded = -(-length // block) * block
    step = BUCKET * block
    return [(start, min(step, padded - start), min(start + step, padded))
            for start in range(0, padded, step)]


def _blocks(a, block, axis=1):
    """[B, n * block, ...] -> [n, B, block, ...] for ``lax.map``: the
    sequence ``axis`` cut into blocks, their count in front."""
    a = a.reshape(a.shape[:axis] + (-1, block) + a.shape[axis + 1:])
    return jnp.moveaxis(a, axis, 0)


def _unblocks(a):
    """[n, B, block, ...] -> [B, n * block, ...]."""
    a = jnp.moveaxis(a, 0, 1)
    return a.reshape((a.shape[0], -1) + a.shape[3:])


def select_thresholds(qi, ki, w, topk, dtype, block):
    """tau [B, S] float32: the ``topk``-th largest index score of each query
    among the keys up to itself, -inf for a query with fewer than ``topk``
    of them. qi [B, S, J, D], ki [B, S, D], w [B, S, J], S a multiple of
    ``block``; no gradient passes."""
    qi, ki, w = map(lax.stop_gradient, (qi, ki, w))
    out = []
    for start, queries, extent in buckets(qi.shape[1], block):
        with jax.named_scope("part/layout"):
            keys = ki[:, :extent]

        def one(args, keys=keys):
            qi_b, w_b, rows = args
            with jax.named_scope("layer/dsa_index"):
                scores = index_scores(qi_b, keys, w_b, dtype)
            with jax.named_scope("layer/dsa_select"):
                return row_thresholds(scores, rows, topk)

        part = slice(start, start + queries)
        # The blocks' cutting and joining are the mixer's part/layout;
        # ``one``'s operations have kinds of their own, and the loop itself
        # stands outside every part: the compiler files what it fuses into
        # the loop's own slices under the loop's name, and that is the
        # indexer's work, not a layout's.
        with jax.named_scope("part/layout"):
            rows = jnp.arange(start, start + queries).reshape(-1, block)
            blocks = (_blocks(qi[:, part], block), _blocks(w[:, part], block),
                      rows)
        taus = lax.map(one, blocks)
        with jax.named_scope("part/layout"):
            out.append(_unblocks(taus))
    with jax.named_scope("part/layout"):
        return jnp.concatenate(out, 1)


# ---------------------------------------------------------------- attention
# The softmax's exponent is taken against ``top``, a bound on a row's logits
# from the norms of its query and of the longest key before it
# (|q.k| <= |q| |k|), and not against the row's own maximum, which would cost
# a pass over the [H, block, keys] float32 logits in memory: exp, mask and
# the rounding to ``dtype`` then ride on the product that makes the logits,
# and the weights' sum divides the output. The softmax is the same whatever
# is subtracted; in float32 a row keeps its precision while its largest
# logit lies within some 40 of ``top``, which RMS-normed q and k hold to
# (|logit| <= |q| |k| / sqrt(D) = sqrt(D) (1 + w_q)(1 + w_k), 11.3 at
# w = 0); the cap keeps a bound beyond that from pushing every weight under
# the smallest float.
LOGIT_CAP = 60.0


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def attend_group(q, k, v, keep, top, dtype):
    """One key-value head's query heads over the kept keys of a query block:
    q [B, Q, R, D], k, v [B, K, D] in ``dtype``, keep [B, Q, K] bool, top
    [B, R, Q, 1] float32 -> (o [B, R, Q, D] float32, the R heads'
    probabilities summed [B, Q, K] float32, which takes no gradient).

    The weights exp(logit - top) are rounded to ``dtype`` where the product
    that makes the logits ends, and their sum divides the output. The
    backward pass is written out so that the softmax's difference
    dE - sum_s p_s dE_s is taken in float32 where the product dE = do v^T
    ends, and only its product with the weights is rounded: left to
    autodiff, the two halves would each be rounded to ``dtype`` first."""
    return _attend_group(q, k, v, keep, top, dtype)[0]


def _attend_group(q, k, v, keep, top, dtype):
    logits = jnp.einsum("bqrd,bkd->brqk", q, k, preferred_element_type=F32) \
        / math.sqrt(q.shape[-1])
    weights = jnp.exp(jnp.where(keep[:, None], logits - top, -jnp.inf)
                      ).astype(dtype)
    total = jnp.sum(weights.astype(F32), -1, keepdims=True)
    out = jnp.einsum("brqk,bkd->brqd", weights, v,
                     preferred_element_type=F32) / total
    share = jnp.sum(weights.astype(F32) / total, axis=1)
    return (out, share), (q, k, v, top, weights, total, out)


def _attend_group_bwd(dtype, kept, cotangents):
    q, k, v, top, weights, total, out = kept
    d_out, _ = cotangents
    scale = 1.0 / math.sqrt(q.shape[-1])
    mean = jnp.sum(d_out * out, -1, keepdims=True)       # sum_s p_s dE_s
    d_logits = (weights.astype(F32) / total * (jnp.einsum(
        "brqd,bkd->brqk", d_out.astype(dtype), v, preferred_element_type=F32)
        - mean)).astype(dtype)
    d_v = jnp.einsum("brqk,brqd->bkd", weights, (d_out / total).astype(dtype),
                     preferred_element_type=F32)
    d_q = jnp.moveaxis(jnp.einsum("brqk,bkd->brqd", d_logits, k,
                                  preferred_element_type=F32), 1, 2) * scale
    d_k = jnp.einsum("brqk,bqrd->bkd", d_logits, q,
                     preferred_element_type=F32) * scale
    return (d_q.astype(q.dtype), d_k.astype(k.dtype), d_v.astype(v.dtype),
            None, jnp.zeros_like(top))


attend_group.defvjp(_attend_group, _attend_group_bwd)


def index_loss(scores, keep, p):
    """KL(p_t || softmax_{S_t} I_t) of every query: scores, p [B, Q, K]
    float32, keep [B, Q, K] bool -> [B, Q]."""
    log_q = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    seen = keep & (p > 0)
    return jnp.sum(jnp.where(
        seen, p * (_ln(jnp.where(seen, p, 1.0))
                   - jnp.where(seen, log_q, 0.0)), 0.0), -1)


def sparse_attention(q, k, v, qi, ki, w, tau, dtype, block):
    """Softmax attention of every query over its key set S_t = {s <= t:
    I_ts >= tau_t}, and the indexer's loss against it.

    q [B, S, H, D], k, v [B, S, H_kv, D], qi [B, S, J, D_I], ki [B, S, D_I],
    w [B, S, J], tau [B, S], float32, S a multiple of ``block`` ->
    (o [B, S, H, D] float32, KL(p_t || softmax_{S_t} I_t) [B, S], |S_t|
    [B, S] int32). Gradients reach q, k, v through o alone and qi, ki, w
    through the loss alone; tau takes none."""
    batch, length, heads, dim = q.shape
    kv_heads = k.shape[2]
    # One key-value head with its query heads at a time: [G, B, S, ...].
    with jax.named_scope("part/layout"):
        q = jnp.moveaxis(q.reshape(batch, length, kv_heads, heads // kv_heads,
                                   dim), 2, 0).astype(dtype)
        k, v = (jnp.moveaxis(a, 2, 0).astype(dtype) for a in (k, v))
    norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)), -1))
    with jax.named_scope("part/pointwise"):
        q_norm, k_norm = lax.stop_gradient((norm(q), norm(k)))
    outs, losses, kept = [], [], []
    for start, queries, extent in buckets(length, block):
        with jax.named_scope("part/layout"):
            k_e, v_e, ki_e = k[:, :, :extent], v[:, :, :extent], ki[:, :extent]
        # What no logit of a query's row passes (``LOGIT_CAP``): [G, B, Q, R].
        with jax.named_scope("part/pointwise"):
            top = jnp.minimum(q_norm[:, :, start:start + queries] * jnp.max(
                k_norm[:, :, :extent], -1)[..., None, None] / math.sqrt(dim),
                LOGIT_CAP)

        @jax.checkpoint
        def one(args, k_e=k_e, v_e=v_e, ki_e=ki_e, extent=extent):
            q_b, top_b, qi_b, w_b, tau_b, rows = args
            with jax.named_scope("layer/dsa_index"):
                scores = index_scores(qi_b, ki_e, w_b, dtype)
            with jax.named_scope("layer/dsa_select"):
                keep = (rows[:, None] >= jnp.arange(extent)[None, :]) \
                    & (lax.stop_gradient(scores) >= tau_b[..., None])

            def group(mass, args):
                q_g, k_g, v_g, top_g = args
                out, share = attend_group(q_g, k_g, v_g, keep, top_g, dtype)
                return mass + share, out

            # ``attend_group``'s products and softmax, forward and in its
            # written-out backward pass.
            with jax.named_scope("layer/attn"), jax.named_scope("part/kernel"):
                mass, out = lax.scan(
                    group, jnp.zeros(scores.shape, F32), (q_b, k_e, v_e, top_b))
            with jax.named_scope("layer/dsa_index"):
                kl = index_loss(scores, keep, mass / heads)
            return out, kl, keep.sum(-1, dtype=jnp.int32)

        part = slice(start, start + queries)
        # q's blocks [n, G, B, block, R, D] and their bounds [n, G, B, R,
        # block, 1]; the scan inside runs over G.
        with jax.named_scope("part/layout"):
            rows = jnp.arange(start, start + queries).reshape(-1, block)
            blocks = (_blocks(q[:, :, part], block, 2),
                      jnp.swapaxes(_blocks(top, block, 2), -1, -2)[..., None],
                      _blocks(qi[:, part], block), _blocks(w[:, part], block),
                      _blocks(tau[:, part], block), rows)
        out, kl, count = lax.map(one, blocks)
        # [n, G, B, R, block, D] -> [B, n * block, G * R, D]
        with jax.named_scope("part/layout"):
            outs.append(out.transpose(2, 0, 4, 1, 3, 5).reshape(
                batch, queries, heads, dim))
            losses.append(_unblocks(kl))
            kept.append(_unblocks(count))
    with jax.named_scope("part/layout"):
        return (jnp.concatenate(outs, 1), jnp.concatenate(losses, 1),
                jnp.concatenate(kept, 1))


# ------------------------------------------------- attention, kernel form
# The same attention and the same indexer with the [heads, block, keys]
# arrays in VMEM tiles (``ops/dsa_attention.py``, ``ops/dsa_index.py``):
# where the backend is a TPU and the shapes fill whole tiles, a layer's
# attention is one forward kernel, one kernel a bucket for the head-mean
# probabilities, and two backward kernels; its indexer one ``scores`` kernel
# a bucket in the forward pass, and in the backward rule one ``scores``, one
# ``backward_q`` and one ``backward_k`` a bucket. A bucket's scores
# [B, rows, keys] are made **once** a forward pass: the thresholds
# (``kth_largest`` over them, a query block at a time), the mask
# ``score >= tau`` and the loss all read that one array, so a query keeps
# what its threshold counted, bit for bit. What a layer's remat keeps by
# name grows by what the backward pass would otherwise make again with the
# forward kernels, the selection and a pass of index scores: the weights'
# sums (``total`` [B, G, R, S], 2 MB a layer), the output in float32
# (268 MB), the layer's mask, a bit a pair (34 MB), and the probabilities
# (0.6 GB in float32: a bucket's rows against its keys; made again they are
# 61 ms a step). The thresholds live between the scores and the mask alone:
# this form keeps none.
KEPT_MASKS, KEPT_PROBABILITIES = "dsa_keep", "dsa_p"


def attention_form(length, dim, block, index_dim):
    """``kernel`` where ``sparse_attention`` and its index scores run as the
    Pallas kernels, ``masked`` where as XLA's masked products: the kernels
    need a TPU, a head of whole 128-lane rows, an indexer head of whole half
    rows, and blocks, buckets and a (padded) length of whole tiles."""
    padded = -(-length // block) * block
    bucket = min(BUCKET * block, padded)
    whole = (dim % 128 == 0 and index_dim % 64 == 0
             and block % kernels.TILE_Q == 0 and bucket % kernels.TILE_K == 0
             and block % index_kernels.TILE_Q == 0
             and bucket % index_kernels.TILE_K == 0)
    return "kernel" if on_tpu() and whole else "masked"


def _tops(q, k, block):
    """What no logit of a query's row passes (``LOGIT_CAP``), from the norms
    of the query and of the longest key up to its bucket's end: q
    [B, G, R, S, D], k [B, G, S, D] -> [B, G, R, S] float32."""
    norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)), -1))
    q_norm, k_norm = norm(q), norm(k)
    return jnp.concatenate([
        jnp.minimum(q_norm[..., start:start + queries] * jnp.max(
            k_norm[..., :extent], -1)[..., None, None]
            / math.sqrt(q.shape[-1]), LOGIT_CAP)
        for start, queries, extent in buckets(q.shape[3], block)], -1)


def _pack_rows(mask):
    """[B, S, S] int8 of 0 / 1 -> [B, S // 8, S] uint8, a bit a row of
    each eighth of the rows: whole rows stay whole, so the TPU shifts and
    adds along no lane."""
    batch, length, _ = mask.shape
    eighths = mask.astype(jnp.uint8).reshape(batch, 8, length // 8, length)
    return sum(eighths[:, bit] << bit for bit in range(8))


def _unpack_rows(packed):
    """The inverse."""
    batch, rows, length = packed.shape
    return jnp.stack([(packed >> bit) & 1 for bit in range(8)], 1).astype(
        jnp.int8).reshape(batch, 8 * rows, length)


def _index_layout(qi, ki, dtype):
    """The index kernels' operands: qi [B, S, J, D] -> [B, J, S, D], both in
    ``dtype`` (what ``index_scores`` rounds them to)."""
    return jnp.moveaxis(qi, 2, 1).astype(dtype), ki.astype(dtype)


def _bucket_thresholds(score, start, topk, block):
    """``select_thresholds``' counting passes over a bucket's scores
    [B, rows, keys] as they stand: tau [B, rows] float32. A query block at a
    time, as there: 512 rows' bit patterns (32 MB at most) stay in VMEM
    through ``kth_largest``'s 32 passes, a bucket's 2,048 rows' would not
    (5.5 against 10.6 ms a layer on the chip: PERF.md section 6, PR 40)."""
    rows = jnp.arange(start, start + score.shape[1]).reshape(-1, block)
    return _unblocks(lax.map(
        lambda args: row_thresholds(*args, topk),
        (_blocks(score, block), rows)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def kernel_attention(q, k, v, qi, ki, w, topk, dtype, block):
    """``select_thresholds`` and ``sparse_attention`` with the index scores
    and the attention in kernels: the same values and gradients, the
    thresholds (the ``topk``-th largest score of each query) taken inside
    from the scores the mask is made of."""
    return _kernel_attention(q, k, v, qi, ki, w, topk, dtype, block)[0]


def _kernel_attention(q, k, v, qi, ki, w, topk, dtype, block):
    batch, length, heads, dim = q.shape
    interpret = not on_tpu()
    with jax.named_scope("layer/attn"):
        q_l, k_l, v_l = kernel_layout(q, k, v, dtype)
        with jax.named_scope("part/pointwise"):
            top = _tops(q_l, k_l, block)
    with jax.named_scope("layer/dsa_index"):
        qi_l, ki_l = _index_layout(qi, ki, dtype)
    scores, keeps, counts = [], [], []
    for start, queries, extent in buckets(length, block):
        with jax.named_scope("layer/dsa_index"):
            score = index_kernels.scores(
                qi_l, ki_l, w, span=(start, queries), interpret=interpret)
        with jax.named_scope("layer/dsa_select"):
            tau = _bucket_thresholds(score, start, topk, block)
            keep = (jnp.arange(start, start + queries)[:, None]
                    >= jnp.arange(extent)[None, :]) & (score >= tau[..., None])
            # The layer's mask is [S, S]: a row's keys past its bucket's
            # end are not kept.
            keeps.append(jnp.pad(keep.astype(jnp.int8), (
                (0, 0), (0, 0), (0, length - extent))))
            counts.append(keep.sum(-1, dtype=jnp.int32))
        scores.append(score)
    with jax.named_scope("layer/dsa_select"):
        mask = jnp.concatenate(keeps, 1)
        packed = checkpoint_name(_pack_rows(mask), KEPT_MASKS)
    with jax.named_scope("layer/attn"), jax.named_scope("part/kernel"):
        out, total = kernels.forward(q_l, k_l, v_l, mask, top, dtype=dtype,
                                     interpret=interpret)
        out, total = (checkpoint_name(a, KEPT_ATTENTION)
                      for a in (out, total))
        # The heads' mean probabilities, a bucket's queries over its keys
        # (with the reciprocal of the rows' sums they are handed).
        ps = tuple(checkpoint_name(kernels.probabilities(
            q_l, k_l, mask, top, 1.0 / total, span=(start, queries),
            dtype=dtype, interpret=interpret), KEPT_PROBABILITIES)
            for start, queries, _ in buckets(length, block))
    with jax.named_scope("layer/dsa_index"):
        loss = jnp.concatenate([
            index_loss(score, keep[..., :score.shape[-1]] > 0, p)
            for score, keep, p in zip(scores, keeps, ps)], 1)
    with jax.named_scope("part/layout"):
        o = out.transpose(0, 3, 1, 2, 4).reshape(batch, length, heads, dim)
        counts = jnp.concatenate(counts, 1)
    return (o, loss, counts), \
        (q_l, k_l, v_l, qi, ki, w, top, out, total, packed, ps)


def _kernel_attention_bwd(topk, dtype, block, kept, cotangents):
    q_l, k_l, v_l, qi, ki, w, top, out, total, packed, ps = kept
    d_o, d_kl, _ = cotangents
    batch, groups, rep, length, dim = q_l.shape
    interpret = not on_tpu()
    with jax.named_scope("part/pointwise"):
        inv_total = 1.0 / total
    with jax.named_scope("layer/dsa_select"):
        mask = _unpack_rows(packed)
    # The indexer: a bucket's scores once more, the loss's gradient through
    # them in XLA ([rows, keys] float32 passes), and that cotangent through
    # the two backward kernels into qI and w, and into kI.
    with jax.named_scope("layer/dsa_index"):
        qi_l, ki_l = _index_layout(qi, ki, dtype)
        w_rows = jnp.swapaxes(w, 1, 2)
        d_qi, d_w, d_ki = [], [], jnp.zeros(ki.shape, F32)
        for (start, queries, extent), p in zip(buckets(length, block), ps):
            span = dict(span=(start, queries), interpret=interpret)
            rows = slice(start, start + queries)
            score = index_kernels.scores(qi_l, ki_l, w, **span)
            d_score, = jax.vjp(lambda s: index_loss(
                s, mask[:, rows, :extent] > 0, p), score)[1](d_kl[:, rows])
            d_qi_b, d_w_b = index_kernels.backward_q(
                qi_l, ki_l, w, d_score, dtype=dtype, **span)
            d_ki = d_ki.at[:, :extent].add(index_kernels.backward_k(
                qi_l, ki_l, w_rows, d_score, dtype=dtype, **span))
            d_qi.append(d_qi_b), d_w.append(d_w_b)
        d_qi = jnp.moveaxis(jnp.concatenate(d_qi, 2), 1, 2)
        d_w = jnp.concatenate(d_w, 1)
    with jax.named_scope("layer/attn"):
        with jax.named_scope("part/layout"):
            d_out = d_o.reshape(batch, length, groups, rep, dim).transpose(
                0, 2, 3, 1, 4)
            mean = jnp.sum(d_out * out, -1)              # sum_s p_s dE_s
            d_low = d_out.astype(dtype)
        with jax.named_scope("part/kernel"):
            d_q = kernels.backward_q(
                q_l, k_l, v_l, mask, top, inv_total, mean, d_low,
                dtype=dtype, interpret=interpret)
        with jax.named_scope("part/layout"):
            mask_t = jnp.swapaxes(mask, 1, 2)
            d_low_kv = d_out.astype(dtype)
            d_scaled = (d_out / total[..., None]).astype(dtype)
        with jax.named_scope("part/kernel"):
            d_k, d_v = kernels.backward_kv(
                q_l, k_l, v_l, mask_t, top, inv_total, mean, d_low_kv,
                d_scaled, dtype=dtype, interpret=interpret)
        with jax.named_scope("part/layout"):
            d_q = d_q.transpose(0, 3, 1, 2, 4).reshape(
                batch, length, groups * rep, dim)
            d_k, d_v = (a.transpose(0, 2, 1, 3) for a in (d_k, d_v))
    return d_q, d_k, d_v, d_qi, d_ki, d_w


kernel_attention.defvjp(_kernel_attention, _kernel_attention_bwd)


# The layers are alike: under ``jit`` the two are traced once for the first
# layer, and the others (their derivatives and transposes too) take the same
# jaxpr from jax's caches: the step's trace, paid at every start, is that
# much shorter. XLA inlines the calls.
_select_thresholds = jax.jit(select_thresholds, static_argnums=(3, 4, 5))
_sparse_attention = jax.jit(sparse_attention, static_argnums=(7, 8))
_kernel_attention_once = jax.jit(kernel_attention, static_argnums=(6, 7, 8))


def layer_norm(x, scale, bias, eps):
    x = x.astype(F32)
    centred = x - jnp.mean(x, -1, keepdims=True)
    return centred * lax.rsqrt(
        jnp.mean(centred * centred, -1, keepdims=True) + eps) * scale + bias


# ------------------------------------------------------------------ modules
class SparseAttention(nn.Module):
    """(y [B, S, d], this layer's indexer loss, its sum of |S_t|)."""
    sizes: dict
    dtype: Any

    @nn.compact
    def __call__(self, h):
        s, dtype = self.sizes, self.dtype
        d, dim = s["hidden_size"], s["head_dim"]
        heads, kv_heads = s["num_attention_heads"], s["num_key_value_heads"]
        j, d_i = s["indexer_num_heads"], s["indexer_head_dim"]
        w_q = self.param("q_proj", _normal(), (d, heads * dim), F32)
        w_kv = self.param("kv_proj", _normal(), (d, 2 * kv_heads * dim), F32)
        w_qn = self.param("q_norm", nn.initializers.zeros, (dim,), F32)
        w_kn = self.param("k_norm", nn.initializers.zeros, (dim,), F32)
        w_o = self.param("o_proj", _normal(), (heads * dim, d), F32)
        w_i = self.param("index_proj", _normal(), (d, j * d_i + d_i + j), F32)
        a_ki = self.param("index_k_norm_scale", nn.initializers.ones, (d_i,),
                          F32)
        b_ki = self.param("index_k_norm_bias", nn.initializers.zeros, (d_i,),
                          F32)

        batch, length = h.shape[:2]
        if self.is_initializing():
            # Every parameter is made; tracing the rest at 16,384 tokens
            # would be seconds of each start for shapes alone.
            return jnp.zeros(h.shape, dtype), jnp.zeros((), F32), \
                jnp.zeros((), jnp.int32)
        eps, theta = s["rms_norm_eps"], s["rope_theta"]
        block = min(s["q_chunk_size"], length)
        # Whole blocks: a padded query sees what the last one sees and is
        # cut off again; no real query sees a padded key (they come later).
        pad = lambda a: jnp.pad(
            a, ((0, 0), (0, -length % block)) + ((0, 0),) * (a.ndim - 2))
        with jax.named_scope("layer/attn"):
            with jax.named_scope("part/proj"):
                q = dense(h, w_q, dtype).reshape(batch, length, heads, dim)
                kv = dense(h, w_kv, dtype).reshape(
                    batch, length, 2, kv_heads, dim)
            with jax.named_scope("part/pointwise"):
                k, v = kv[:, :, 0], kv[:, :, 1].astype(F32)
                q = rotary(rms_norm0(q, w_qn, eps), theta, dim)
                k = rotary(rms_norm0(k, w_kn, eps), theta, dim)
        with jax.named_scope("layer/dsa_index"):
            index = dense(lax.stop_gradient(h), w_i, dtype).astype(F32)
            qi = rotary(index[..., :j * d_i].reshape(batch, length, j, d_i),
                        theta, d_i)
            ki = rotary(layer_norm(index[..., j * d_i:j * d_i + d_i], a_ki,
                                   b_ki, eps)[:, :, None], theta, d_i)[:, :, 0]
            w = index[..., j * d_i + d_i:] / math.sqrt(j * d_i)
        # From here to the output projection every operation under no
        # ``layer/`` scope of its own has the kind of ``Layer``'s: the
        # blocks' cutting and joining are its part/layout.
        with jax.named_scope("part/layout"):
            q, k, v, qi, ki, w = map(pad, (q, k, v, qi, ki, w))
        if attention_form(length, dim, block, d_i) == "kernel":
            # Takes its thresholds from the scores it makes, and keeps its
            # own output (float32, the kernels' layout) by name.
            out, kl, kept = _kernel_attention_once(
                q, k, v, qi, ki, w, s["topk"], dtype, block)
            with jax.named_scope("part/layout"):
                out = out.astype(dtype)
        else:
            tau = checkpoint_name(
                _select_thresholds(qi, ki, w, s["topk"], dtype, block),
                KEPT_SELECTION)
            out, kl, kept = _sparse_attention(q, k, v, qi, ki, w, tau, dtype,
                                              block)
            # ``dense`` would round ``out`` to ``dtype`` anyway: kept so.
            with jax.named_scope("part/layout"):
                out = checkpoint_name(out.astype(dtype), KEPT_ATTENTION)
        kl, kept = (checkpoint_name(a, KEPT_ATTENTION) for a in (kl, kept))
        with jax.named_scope("layer/attn"), jax.named_scope("part/proj"):
            y = dense(out[:, :length].reshape(batch, length, heads * dim),
                      w_o, dtype)
        with jax.named_scope("layer/dsa_index"):
            return y, jnp.mean(kl[:, :length]), jnp.sum(kept[:, :length])


class Layer(nn.Module):
    sizes: dict
    dtype: Any

    @nn.compact
    def __call__(self, x):
        s = self.sizes
        d, eps = s["hidden_size"], s["rms_norm_eps"]
        w_in = self.param("input_norm", nn.initializers.zeros, (d,), F32)
        w_post = self.param("post_norm", nn.initializers.zeros, (d,), F32)
        # The layer's own norms and residual adds count for the kind they
        # feed and for its part/pointwise; scopes inside the mixer and the
        # expert layer are innermost.
        with jax.named_scope("layer/attn"):
            with jax.named_scope("part/pointwise"):
                h = rms_norm0(x, w_in, eps)
            y, index_loss, kept = SparseAttention(
                s, self.dtype, name="mixer")(h)
            with jax.named_scope("part/pointwise"):
                x = x + y.astype(F32)
        with jax.named_scope("layer/moe_router"):
            y, load, dropped, _ = SparseMoE(s, self.dtype, name="moe")(
                rms_norm0(x, w_post, eps))
            return x + y, (load, dropped, kept, index_loss)


def keys_due(length, topk):
    """sum over t < length of min(t + 1, topk): what the layers keep of one
    sequence when no score ties at a threshold."""
    topk = min(topk, length)
    return topk * (topk + 1) // 2 + (length - topk) * topk


class KeyeVL2(nn.Module):
    """``__call__(tokens, targets)`` gives the objective, mean
    cross-entropy + the indexer loss L_I, and the layers' counts
    ``{"moe_load": [layers, held], "moe_dropped": [layers], "dsa_kept":
    [layers], "dsa_due": [], "dsa_index_loss": [layers]}``; without targets,
    the logits [B, S, vocab_rows]."""
    preset: str = "30b_a3b_ep16"
    dtype: Any = jnp.float32

    @property
    def sizes(self):
        return PRESETS[self.preset]

    def forms(self, length):
        """What the step compiles as at sequences of ``length``, for the
        run's manifest and ``train`` records: a run on the chip that fell
        back to the masked attention and XLA's index scores says so (one
        rule decides both: the index kernels run inside the kernel form)."""
        s = self.sizes
        form = attention_form(length, s["head_dim"],
                              min(s["q_chunk_size"], length),
                              s["indexer_head_dim"])
        return {"dsa_attention_form": form,
                "dsa_index_form": "kernel" if form == "kernel" else "xla"}

    @nn.compact
    def __call__(self, tokens, targets=None, *, train: bool = False):
        s = self.sizes
        by_name = kept_by_name(
            KEPT_SELECTION, KEPT_ATTENTION, KEPT_MASKS, KEPT_PROBABILITIES)
        out = decoder_shell(
            self, tokens, targets,
            lambda i: nn.remat(Layer, policy=by_name)(
                s, self.dtype, name=f"layer_{i}"),
            s["num_hidden_layers"],
            MOE_COUNTS + ("dsa_kept", "dsa_index_loss"))
        if targets is None:
            return out
        loss, counts = out
        batch, length = tokens.shape
        return loss + jnp.mean(counts["dsa_index_loss"]), dict(
            counts,
            dsa_due=jnp.asarray(batch * keys_due(length, s["topk"])))
