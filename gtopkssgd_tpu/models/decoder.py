"""What the decoders of this zoo share (``qwen3_next``, ``keye_vl2``,
``trinity_mini``, ``kanana2``, ``ouro``, ``sdar``): the dropless expert
layer of one chip's share of an expert group, a dense layer's feed-forward,
the rotary embedding, the zero-centred RMSNorm, the causal and
sliding-window attention (keys as wide as values or wider) and the same
under block diffusion's visibility rule (``block_diffusion_attention``),
the head's loss a sequence at a time, and the shell round the layers in
its pieces.
Each model file states its own layer equations and imports these; nothing
here knows a model's sizes beyond the ``sizes`` dict it is handed.

The attention has two forms, chosen by ``attention_form`` from what it
observes (no flag): where the backend is a TPU and head and length fill
whole tiles, the fused flash kernels of ``ops/flash_attention.py``, one
call a layer forward and two backward, whose ``[heads, queries, keys]``
scores, weights and ``d_logits`` stay in VMEM and which skip the key tiles
that the diagonal and the window cut away; everywhere else XLA's products
in blocks of queries, the float32 oracle of the kernels' tests. Either way
its operations are named for the device trace, within the caller's
``layer/<kind>``: ``part/layout`` (to and from the kernels' layout, the
blocks' cutting and joining) and ``part/kernel`` (the Pallas calls; in the
blocked form the products and softmax they stand for), forward and in the
written-out backward rule alike; the mixers name their own ``part/proj`` and
``part/pointwise`` (the three levels of names: ``trainer._build_train_step``).

The expert layer is told which experts it holds (``experts_held`` from
``expert_offset`` of ``num_experts``): it routes over all of them, sorts
the token-slots that fall on its own experts into expert order and runs
them as grouped products (``lax.ragged_dot``), a block of slots at a time
in a loop as long as this step's routing needs: no token is dropped
whatever the imbalance, and a balanced step runs one block. What the absent
experts would add is left out; nothing stands in for the other chips or
their all-to-all. A model with a shared expert gives its width
(``shared_expert_intermediate_size``); one without leaves the key out and
the layer has no such leaves. What the dispatch chose (ids and their scores,
the sort's order, the sorted slots' tokens and weights, the held experts'
sizes) is kept across a layer's remat by name (``KEPT_DISPATCH``): every
model builds its layers' policy with ``kept_by_name``, and the replay makes
no second top-k or sort.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from gtopkssgd_tpu.ops import flash_attention as flash

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


def query_block_of(seq_len: int) -> int:
    return min(512, max(1, seq_len // 2))


# ------------------------------------------------------------------ pieces
def dense(x, w, dtype):
    return jnp.dot(x.astype(dtype), w.astype(dtype))


def rms_norm0(x, w, eps):
    """Zero-centred RMSNorm: the stored weight is the scale minus one."""
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _normal():
    return nn.initializers.normal(0.02)


# --------------------------------------------------------------- attention
def rotary(x, theta, rotary_dims):
    """Rotate-half rotary embedding on the first ``rotary_dims`` of the
    last axis; x [B, S, H, D] float32, positions 0..S-1."""
    length, half = x.shape[1], rotary_dims // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) * 2.0 / rotary_dims))
    angle = jnp.arange(length, dtype=F32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    rot, rest = x[..., :rotary_dims], x[..., rotary_dims:]
    turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], -1)
    return jnp.concatenate(
        [rot * jnp.cos(angle) + turned * jnp.sin(angle), rest], -1)


def on_tpu():
    return jax.default_backend() == "tpu"


def attention_form(length, dim, value_dim=None):
    """``kernel`` where ``blocked_causal_attention`` runs as the Pallas
    kernels, ``blocked`` where as XLA's products a block of queries at a
    time: the kernels need a TPU, a value head (``value_dim``; the key
    head's ``dim`` if None) of whole 128-lane rows, a key head of whole
    half rows (latent attention: 192 beside 128) and a length of whole
    tiles."""
    value_dim = dim if value_dim is None else value_dim
    whole = value_dim % 128 == 0 and dim % 64 == 0 \
        and length % flash.TILE_Q == 0 and length % flash.TILE_K == 0
    return "kernel" if on_tpu() and whole else "blocked"


def kernel_layout(q, k, v, dtype):
    """q [B, S, H, D] -> [B, G, R, S, D], k [B, S, G, D] -> [B, G, S, D],
    v [B, S, G, D_v] -> [B, G, S, D_v], in ``dtype``: what the attention
    kernels read."""
    batch, length, heads, dim = q.shape
    groups = k.shape[2]
    with jax.named_scope("part/layout"):
        q = q.reshape(batch, length, groups, heads // groups, dim).transpose(
            0, 2, 3, 1, 4).astype(dtype)
        k, v = (a.transpose(0, 2, 1, 3).astype(dtype) for a in (k, v))
    return q, k, v


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def kernel_causal_attention(q, k, v, dtype, window, kept):
    """``blocked_causal_attention`` with the attention in kernels
    (interpret mode off the TPU): the same arguments but the block, the
    same values and gradients."""
    return _kernel_attention(q, k, v, dtype, kept, window=window)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def kernel_diffusion_attention(q, k, v, dtype, block_length, kept):
    """``block_diffusion_attention`` with the attention in the same
    kernels, told the rule by its block length."""
    return _kernel_attention(q, k, v, dtype, kept,
                             block_length=block_length)[0]


def _kernel_attention(q, k, v, dtype, kept, **rule):
    """(out, what the backward rule takes); ``rule`` is the kernels'
    ``window`` or ``block_length``."""
    batch, length, heads, _ = q.shape
    q_l, k_l, v_l = kernel_layout(q, k, v, dtype)
    with jax.named_scope("part/kernel"):
        out, lse = flash.forward(q_l, k_l, v_l, interpret=not on_tpu(),
                                 **rule)
    with jax.named_scope("part/layout"):
        out = out.transpose(0, 3, 1, 2, 4).reshape(
            batch, length, heads, v.shape[3])
        if kept is not None:
            # Named here, where the backward pass takes them from: a remat
            # that keeps the name runs the forward kernel once a step.
            out, lse = (checkpoint_name(a, kept) for a in (out, lse))
    return out, (q_l, k_l, v_l, out, lse)


def _kernel_attention_bwd(dtype, residuals, d_out, **rule):
    q_l, k_l, v_l, out, lse = residuals
    batch, groups, rep, length, dim = q_l.shape
    rows = lambda a: jnp.moveaxis(
        a.reshape((batch, length, groups, rep) + a.shape[3:]), 1, 3)
    with jax.named_scope("part/layout"):
        delta = rows(jnp.sum(d_out * out, -1))         # sum_s p_s dP_s
        d_out = rows(d_out).astype(dtype)
    run = dict(rule, interpret=not on_tpu())
    with jax.named_scope("part/kernel"):
        d_q = flash.backward_q(q_l, k_l, v_l, lse, delta, d_out, **run)
        d_k, d_v = flash.backward_kv(q_l, k_l, v_l, lse, delta, d_out, **run)
    with jax.named_scope("part/layout"):
        return (jnp.moveaxis(d_q, 3, 1).reshape(
                    batch, length, groups * rep, dim),
                d_k.transpose(0, 2, 1, 3), d_v.transpose(0, 2, 1, 3))


kernel_causal_attention.defvjp(
    lambda q, k, v, dtype, window, kept: _kernel_attention(
        q, k, v, dtype, kept, window=window),
    lambda dtype, window, kept, residuals, d_out: _kernel_attention_bwd(
        dtype, residuals, d_out, window=window))
kernel_diffusion_attention.defvjp(
    lambda q, k, v, dtype, block_length, kept: _kernel_attention(
        q, k, v, dtype, kept, block_length=block_length),
    lambda dtype, block_length, kept, residuals, d_out: _kernel_attention_bwd(
        dtype, residuals, d_out, block_length=block_length))


@jax.named_scope("part/kernel")
def _attend(q_b, k_b, v_b, seen, dtype):
    """A block's queries [B, Q, G, R, D] over the keys it was handed
    [B, K, G, D]; ``seen()`` [Q, K]: which key each query attends to."""
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q_b, k_b,
                        preferred_element_type=F32) / math.sqrt(q_b.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen(), scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(dtype), v_b,
                      preferred_element_type=F32)


def blocked_causal_attention(q, k, v, dtype, block, window=None, kept=None):
    """q [B, S, H, D], k [B, S, H_kv, D], v [B, S, H_kv, D_v] float32 ->
    [B, S, H, D_v] float32: query t sees the keys s with 0 <= t - s
    (< ``window``, if given), scores scaled by 1 / sqrt(D). The output is
    named ``kept`` for a remat's policy (``checkpoint_name``).

    In the kernel form (``attention_form``) one call covers the sequence
    and ``kept`` names the rows' log-sum-exp too. In the blocked form
    queries run in blocks of ``block``, each against keys 0 .. its own end
    and rematerialised in the backward pass; with a ``window`` a block that
    starts past it is handed the ``window + block`` keys from ``window``
    before its start to its end, never the sequence: such blocks are alike
    and run as one ``lax.map``."""
    if attention_form(q.shape[1], q.shape[3], v.shape[3]) == "kernel":
        return kernel_causal_attention(q, k, v, dtype, window, kept)
    batch, length, heads, dim = q.shape
    kv_heads = k.shape[2]
    with jax.named_scope("part/layout"):
        q = q.reshape(
            batch, length, kv_heads, heads // kv_heads, dim).astype(dtype)
        k, v = k.astype(dtype), v.astype(dtype)

    attend = functools.partial(_attend, dtype=dtype)

    @functools.partial(jax.checkpoint, static_argnums=3)
    def one(q_b, k_b, v_b, start):
        def seen():
            rows = start + jnp.arange(q_b.shape[1])
            return rows[:, None] >= jnp.arange(k_b.shape[1])[None, :]

        return attend(q_b, k_b, v_b, seen)

    @functools.partial(jax.checkpoint, static_argnums=(1, 2))
    def windowed(start, queries, keys):
        """``queries`` from ``start`` (static or not) against the ``keys``
        that end with the block: cut out in here, so that the backward pass
        is handed the sequence's q, k, v and not every block's copy."""
        first = start + queries - keys
        cut = lambda a, at, size: lax.dynamic_slice_in_dim(a, at, size, 1)

        def seen():
            apart = (start + jnp.arange(queries))[:, None] \
                - (first + jnp.arange(keys))[None, :]
            return (apart >= 0) & (apart < window)

        with jax.named_scope("part/layout"):
            cuts = (cut(q, start, queries), cut(k, first, keys),
                    cut(v, first, keys))
        return attend(*cuts, seen)

    # Blocks that end inside the first window see every key before them;
    # the whole blocks from ``alike`` on each see window + block keys.
    plain = length if window is None else min(length, window) // block * block
    outs = []
    for start in range(0, plain, block):
        end = min(start + block, length)
        with jax.named_scope("part/layout"):
            cuts = q[:, start:end], k[:, :end], v[:, :end]
        outs.append(one(*cuts, start))
    if plain < length:
        alike = min(length, -(-window // block) * block)
        count = (length - alike) // block
        for start in range(plain, alike, block):
            end = min(start + block, length)
            outs.append(windowed(start, end - start,
                                 min(end, window + end - start)))
        if count:
            out = lax.map(lambda start: windowed(start, block, window + block),
                          alike + block * jnp.arange(count))
            with jax.named_scope("part/layout"):
                outs.append(jnp.moveaxis(out, 0, 1).reshape(
                    (batch, count * block) + out.shape[3:]))
        start = alike + count * block
        if start < length:
            outs.append(windowed(start, length - start,
                                 window + length - start))
    with jax.named_scope("part/layout"):
        out = jnp.concatenate(outs, 1).reshape(
            batch, length, heads, v.shape[3])
        return out if kept is None else checkpoint_name(out, kept)


def diffusion_attention_form(half, dim, block_length):
    """``attention_form`` for ``block_diffusion_attention`` at halves of
    ``half`` rows: the kernels besides need each half to be whole tiles and
    a tile whole blocks of ``block_length``, a power of two."""
    fits = flash.BlockDiffusion.fits(block_length, half, flash.TILE_Q,
                                     flash.TILE_K)
    return attention_form(half, dim) if fits else "blocked"


def block_diffusion_attention(q, k, v, dtype, block, block_length, kept=None):
    """``blocked_causal_attention`` under the block-diffusion rule: the 2L
    rows of q [B, 2L, H, D], k, v [B, 2L, H_kv, D] are a sequence's L clean
    tokens and then its L noised ones, and with b(t) = t // ``block_length``
    on a row's position in its own half a clean query sees the clean keys
    with b(s) <= b(t), a noised query the clean keys with b(s) < b(t) and
    the noised keys with b(s) = b(t). L is whole blocks.

    The kernel form (``diffusion_attention_form``) is the same three
    kernels told the rule, one call over all 2L rows. In the blocked form a
    block of ``block`` queries is handed the keys it can see and no others:
    clean queries the clean keys up to the end of their last row's block,
    noised queries the clean keys before that and the noised rows of their
    own blocks; never [2L, 2L]."""
    batch, rows, heads, dim = q.shape
    half = rows // 2
    if rows != 2 * half or half % block_length:
        raise ValueError(f"{rows} rows: not two halves of whole blocks of "
                         f"{block_length}")
    if diffusion_attention_form(half, dim, block_length) == "kernel":
        return kernel_diffusion_attention(q, k, v, dtype, block_length, kept)
    kv_heads = k.shape[2]
    with jax.named_scope("part/layout"):
        q = q.reshape(batch, rows, kv_heads, heads // kv_heads,
                      dim).astype(dtype)
        k, v = k.astype(dtype), v.astype(dtype)

    @functools.partial(jax.checkpoint, static_argnums=(3, 4, 5))
    def one(q_b, k_b, v_b, start, clean, first):
        """Queries at positions ``start`` .. of a half against ``clean``
        clean keys from position 0 and then (the noised queries) the noised
        keys from position ``first``."""
        noised = int(clean < k_b.shape[1])

        def seen():
            qb = (start + jnp.arange(q_b.shape[1]))[:, None] // block_length
            at = jnp.arange(k_b.shape[1])[None, :]
            kb = jnp.where(at < clean, at, at - clean + first) // block_length
            return jnp.where(at < clean, kb <= qb - noised, kb == qb)

        return _attend(q_b, k_b, v_b, seen, dtype)

    outs = []
    for noised in (0, 1):
        for start in range(0, half, block):
            end = min(start + block, half)
            # The blocks the queries lie in: positions first .. last - 1.
            first = start // block_length * block_length
            last = -(-end // block_length) * block_length
            clean = last - noised * block_length
            with jax.named_scope("part/layout"):
                keys = lambda a: jnp.concatenate(
                    [a[:, :clean], a[:, half + first:half + last]], 1) \
                    if noised else a[:, :clean]
                cuts = (q[:, noised * half + start:noised * half + end],
                        keys(k), keys(v))
            outs.append(one(*cuts, start, clean, first))
    with jax.named_scope("part/layout"):
        out = jnp.concatenate(outs, 1).reshape(batch, rows, heads, v.shape[3])
        return out if kept is None else checkpoint_name(out, kept)


def normed_mixer(x, mixer, w_in, eps, w_post=None):
    """A layer's attention half, inside the caller's ``layer/<kind>``:
    x + ``mixer``(Norm(x; w_in)), the mixer's output through Norm(.; w_post)
    first where the model has a norm after it (the sandwich). The norms and
    the residual add are the kind's ``part/pointwise``; the mixer names its
    own parts."""
    with jax.named_scope("part/pointwise"):
        h = rms_norm0(x, w_in, eps)
    y = mixer(h)
    with jax.named_scope("part/pointwise"):
        return x + (y if w_post is None else rms_norm0(y, w_post, eps))


# ------------------------------------------------------------ expert layer
# What a layer's remat keeps of the expert layer's dispatch by
# ``checkpoint_name``, so that the layer's replay makes no second top-k,
# sort, count or gather: the chosen experts' ids and scores [T, top], the
# sorted slots' order, token and weight [T * top], which of them fall on a
# held expert (a bool each) and the held experts' sizes [held] (21 B a
# token-slot: 2.8 MB a layer at 16,384 tokens and 8 experts a token, 3.4
# MB at 10). The router's logits and scores are not kept
# ([T, experts] float32, 33.6 MB a layer at 512 experts): their second run
# is cheap and the softmax's backward reads the scores.
KEPT_DISPATCH = "moe_dispatch"


def kept_by_name(*names):
    """The remat policy of a decoder layer: the model's own ``names`` are
    kept across the remat and, whatever the model keeps, the expert
    layer's dispatch."""
    return jax.checkpoint_policies.save_only_these_names(
        *names, KEPT_DISPATCH)


# ``lax.top_k`` over the last axis, differentiated through ids that carry
# the dispatch's name. top_k's own rule takes the tangent's entries at its
# raw index output, which no name reaches, so a replay would run the top-k
# again for them; this rule is the same gather through the named ids. Only
# the rule names them: under a remat it is the rule that is traced. The
# softmax routers take top_k's own values this way; ``route``'s biased path
# cannot (it chooses by other scores than it weighs) and pays for its
# ``take_along_axis``: a gather of 1.67 ms a layer over [16384, 512] beside
# a top_k of 1.44 (PERF.md section 6, PR 46, call A), which is why the
# softmax path is not written in the biased path's form.
@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def kept_top_k(scores, top):
    values, ids = lax.top_k(scores, top)
    return values, ids


@kept_top_k.defjvp
def _kept_top_k_jvp(top, primals, tangents):
    values, ids = lax.top_k(*primals, top)
    ids = checkpoint_name(ids, KEPT_DISPATCH)
    return (values, ids), (
        jnp.take_along_axis(tangents[0], ids, -1),
        np.zeros(ids.shape, jax.dtypes.float0))


def route(x, router, top, normalise, score_func="softmax", bias=None,
          scale=1.0):
    """(weights of the ``top`` experts [T, top] float32, their ids), over
    every expert of the model, held here or not. ``score_func`` scores the
    logits (``softmax`` over the experts, or ``sigmoid`` of each); a
    ``bias`` [experts] is added to choose the experts and to nothing else:
    the weights are the unbiased scores, renormalised over the chosen if
    ``normalise``, times ``scale``."""
    logits = jnp.dot(x.astype(F32), router, precision=HIGHEST)
    if score_func == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"score_func {score_func!r}: softmax or sigmoid")
    if bias is None:
        values, ids = kept_top_k(scores, top)
    else:
        _, ids = lax.top_k(lax.stop_gradient(scores) + bias, top)
        ids = checkpoint_name(ids, KEPT_DISPATCH)
        values = jnp.take_along_axis(scores, ids, -1)
    values = checkpoint_name(values, KEPT_DISPATCH)
    if normalise:
        total = jnp.sum(values, -1, keepdims=True)
        # Sigmoid scores can all underflow; softmax's largest cannot.
        values = values / (total if score_func == "softmax" else total + 1e-20)
    return values if scale == 1.0 else values * scale, ids


def balanced_bias(bias, counts, rate):
    """The aux-loss-free balancing step: an expert that took fewer than the
    mean of ``counts`` [experts] rises by ``rate``, one that took more
    falls, and the bias stays centred."""
    counts = counts.astype(F32)
    delta = rate * jnp.sign(jnp.mean(counts) - counts)
    return bias + delta - jnp.mean(delta)


def sort_held_slots(ids, probs, offset, held):
    """Token-slots in expert order, the held experts' first.

    Returns (token of each sorted slot, its routing weight, slots per held
    expert [held]); slots of experts held elsewhere sort last with weight 0
    and belong to no group."""
    top = ids.shape[1]
    local = ids.reshape(-1) - offset
    key = jnp.where((local >= 0) & (local < held), local, held)
    kept = lambda a: checkpoint_name(a, KEPT_DISPATCH)
    order = kept(jnp.argsort(key, stable=True))
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    weight = jnp.where(kept(key[order] < held), probs.reshape(-1)[order], 0.0)
    return kept((order // top).astype(jnp.int32)), kept(weight), kept(sizes)


def blocks_run(sizes, rows, max_blocks):
    """How many blocks of ``rows`` sorted slots are run: as many as hold
    every slot on a held expert, unless ``max_blocks`` caps them."""
    need = (jnp.sum(sizes) + rows - 1) // rows
    return need if max_blocks is None else jnp.minimum(need, max_blocks)


def slots_dropped(sizes, rows, max_blocks):
    """Slots on held experts that no block took: 0 unless ``max_blocks``
    cuts the loop short, which the model never does."""
    return jnp.maximum(
        jnp.sum(sizes) - blocks_run(sizes, rows, max_blocks) * rows, 0)


def _expert_block(x, token, weight, sizes, gate, up, down, *, start, rows,
                  dtype):
    """The held experts' output for sorted slots start .. start + rows,
    added at their tokens: [T, d] float32."""
    ends = jnp.cumsum(sizes)
    window = lambda a: jnp.clip(a, start, start + rows)
    inside = (window(ends) - window(ends - sizes)).astype(jnp.int32)
    # Rows past the block's last group belong to no expert. The grouped
    # product leaves them unwritten on the TPU, forward and backward, so
    # they are zeroed on the way in, after every product and (by the
    # transposes of the same selects) on the way back.
    valid = (jnp.arange(rows) < jnp.sum(inside))[:, None]
    with jax.named_scope("layer/moe_router"):
        token = lax.dynamic_slice(token, (start,), (rows,))
        weight = lax.dynamic_slice(weight, (start,), (rows,))
        taken = jnp.where(valid, x[token], 0.0).astype(dtype)
    with jax.named_scope("layer/moe_experts"):
        grouped = lambda a, w: jnp.where(
            valid, lax.ragged_dot(a, w.astype(dtype), inside), 0).astype(F32)
        hidden = jax.nn.silu(grouped(taken, gate)) * grouped(taken, up)
        out = grouped(hidden.astype(dtype), down)
    with jax.named_scope("layer/moe_router"):
        return jnp.zeros(x.shape, F32).at[token].add(out * weight[:, None])


def _held_experts(rows, max_blocks, dtype, x, token, weight, sizes,
                  gate, up, down):
    """sum over the held experts j of weight_j E_j(x), [T, d] float32.

    The sorted slots are run ``rows`` at a time in a loop whose trip count
    is this step's (``blocks_run``): every slot on a held expert is
    computed whatever the imbalance, the buffer stays one block, and a
    balanced step runs one block. A loop of unknown length has no
    automatic transpose, so the backward pass is written out: the same
    loop, each block's vjp recomputed and accumulated."""
    def body(i, total):
        return total + _expert_block(
            x, token, weight, sizes, gate, up, down,
            start=i * rows, rows=rows, dtype=dtype)

    return lax.fori_loop(0, blocks_run(sizes, rows, max_blocks), body,
                         jnp.zeros(x.shape, F32))


held_experts = jax.custom_vjp(_held_experts, nondiff_argnums=(0, 1, 2))


def _held_fwd(rows, max_blocks, dtype, *args):
    return _held_experts(rows, max_blocks, dtype, *args), args


def _held_bwd(rows, max_blocks, dtype, args, ct):
    x, token, weight, sizes, gate, up, down = args

    def body(i, grads):
        start = i * rows

        def block(x, part, gate, up, down):
            full = lax.dynamic_update_slice(
                jnp.zeros_like(weight), part, (start,))
            return _expert_block(x, token, full, sizes, gate, up, down,
                                 start=start, rows=rows, dtype=dtype)

        part = lax.dynamic_slice(weight, (start,), (rows,))
        _, pull = jax.vjp(block, x, part, gate, up, down)
        dx, dpart, *dexperts = pull(ct)
        gx, gweight, *gexperts = grads
        return (gx + dx, lax.dynamic_update_slice(gweight, dpart, (start,)),
                *(g + d for g, d in zip(gexperts, dexperts)))

    zeros = tuple(jnp.zeros_like(a) for a in (x, weight, gate, up, down))
    dx, dweight, dgate, dup, ddown = lax.fori_loop(
        0, blocks_run(sizes, rows, max_blocks), body, zeros)
    return dx, None, dweight, None, dgate, dup, ddown


held_experts.defvjp(_held_fwd, _held_bwd)


class SparseMoE(nn.Module):
    """``block_rows`` sorted slots are run at a time (None: 4096, or every
    slot the layer can hold if that is fewer); ``max_blocks`` caps the
    loop and so drops slots: only the test that shows ``moe_slots_dropped``
    counting sets it.

    What a model's router is comes from ``sizes``: ``score_func``
    (``softmax`` if left out), ``norm_topk_prob``, ``route_scale`` and, with
    ``load_balance_coeff``, a balancing bias [experts] that is no parameter:
    it lives in the ``batch_stats`` collection, takes no gradient, moves the
    choice of experts and never their weights, and where that collection
    is mutable (a training step) is moved by this step's own counts
    (``balanced_bias``). A shared expert has its sigmoid gate unless
    ``shared_expert_gate`` is False.

    Returns (y, slots per held expert [held], slots dropped, and with a
    balancing bias (tokens chosen per expert [experts], the bias the
    choice was made with), else None)."""
    sizes: dict
    dtype: Any
    block_rows: Any = None
    max_blocks: Any = None

    @nn.compact
    def __call__(self, x):
        s, dtype = self.sizes, self.dtype
        d, width = s["hidden_size"], s["moe_intermediate_size"]
        shared_w = s.get("shared_expert_intermediate_size", 0)
        gated = s.get("shared_expert_gate", True)
        held, offset = s["experts_held"], s["expert_offset"]
        top, experts = s["num_experts_per_tok"], s["num_experts"]
        rate = s.get("load_balance_coeff")
        router = self.param("router", _normal(), (d, experts), F32)
        gate = self.param("experts_gate", _normal(), (held, d, width), F32)
        up = self.param("experts_up", _normal(), (held, d, width), F32)
        down = self.param("experts_down", _normal(), (held, width, d), F32)
        if shared_w:
            s_gate = self.param("shared_gate_proj", _normal(), (d, shared_w),
                                F32)
            s_up = self.param("shared_up_proj", _normal(), (d, shared_w), F32)
            s_down = self.param("shared_down_proj", _normal(), (shared_w, d),
                                F32)
            if gated:
                w_s = self.param("shared_gate", _normal(), (d, 1), F32)
        bias = None if rate is None else self.variable(
            "batch_stats", "router_bias", jnp.zeros, (experts,), F32)

        shape = x.shape
        x = x.reshape(-1, d)
        # A token's top experts are distinct: at most min(top, held) of its
        # slots fall here.
        rows = self.block_rows or min(4096, x.shape[0] * min(top, held))
        balance = None
        with jax.named_scope("layer/moe_router"):
            probs, ids = route(
                x, router, top, s["norm_topk_prob"],
                s.get("score_func", "softmax"),
                None if bias is None else bias.value,
                s.get("route_scale", 1.0))
            if bias is not None:
                chosen = jnp.bincount(ids.reshape(-1), length=experts)
                balance = (chosen, bias.value)
                if not self.is_initializing() \
                        and self.is_mutable_collection("batch_stats"):
                    bias.value = balanced_bias(bias.value, chosen, rate)
            token, weight, load = sort_held_slots(ids, probs, offset, held)
            # The loop's last block may reach past the slots: pad them.
            token = jnp.pad(token, (0, rows))
            weight = jnp.pad(weight, (0, rows))
        y = held_experts(rows, self.max_blocks, dtype, x, token, weight, load,
                         gate, up, down)
        if shared_w:
            with jax.named_scope("layer/shared_expert"):
                share = jax.nn.sigmoid(dense(x, w_s, dtype).astype(F32)) \
                    if gated else None
                hidden = jax.nn.silu(dense(x, s_gate, dtype).astype(F32)) \
                    * dense(x, s_up, dtype).astype(F32)
                out = dense(hidden, s_down, dtype).astype(F32)
                y = y + (out if share is None else share * out)
        return (y.reshape(shape), load,
                slots_dropped(load, rows, self.max_blocks), balance)


class DenseMLP(nn.Module):
    """A dense layer's SwiGLU feed-forward of ``intermediate_size``."""
    sizes: dict
    dtype: Any

    @nn.compact
    def __call__(self, x):
        d, width = self.sizes["hidden_size"], self.sizes["intermediate_size"]
        w_gate = self.param("gate_proj", _normal(), (d, width), F32)
        w_up = self.param("up_proj", _normal(), (d, width), F32)
        w_down = self.param("down_proj", _normal(), (width, d), F32)
        hidden = jax.nn.silu(dense(x, w_gate, self.dtype).astype(F32)) \
            * dense(x, w_up, self.dtype).astype(F32)
        return dense(hidden, w_down, self.dtype).astype(F32)


# Tokens whose logits over the vocabulary's rows exist at once in the loss:
# a sequence, or this many tokens of a longer one (16,384 x 18,992 float32
# would be 1.2 GB, and as much again for their gradient).
LOSS_ROWS = 4096


def token_losses(hidden, head, targets, dtype):
    """Cross-entropy of every position, float32, a sequence (at most
    ``LOSS_ROWS`` tokens of it) at a time."""
    length = hidden.shape[1]
    if length > LOSS_ROWS and length % LOSS_ROWS == 0:
        hidden = hidden.reshape(-1, LOSS_ROWS, hidden.shape[-1])
        targets = targets.reshape(-1, LOSS_ROWS)

    @jax.checkpoint
    def one(args):
        h, t = args
        logits = jnp.dot(h.astype(dtype), head.astype(dtype),
                         preferred_element_type=F32)
        picked = jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    return lax.map(one, (hidden, targets))


# What an expert layer counts (``SparseMoE``'s second and third results)
# and, with a balancing bias, its fourth: the names ``obs.counters`` reads.
MOE_COUNTS = ("moe_load", "moe_dropped")
BALANCE_COUNTS = MOE_COUNTS + ("moe_count", "moe_bias")


def embedded(module, tokens, embed_scale=None):
    """The embedding's rows of ``tokens`` (times ``embed_scale``, if given),
    under ``layer/head``; ``embed`` is ``module``'s parameter."""
    s = module.sizes
    with jax.named_scope("layer/head"):
        table = module.param("embed", _normal(),
                             (s["vocab_rows"], s["hidden_size"]), F32)
        x = table[tokens]
        return x if embed_scale is None else x * embed_scale


def run_layers(layers, x):
    """x through ``layers`` in order, each ``layer(x)`` -> (x, the layer's
    counts or None): (x, the counts that were given, in order)."""
    found = []
    for layer in layers:
        x, count = layer(x)
        if count is not None:
            found.append(count)
    return x, found


def logits_of(hidden, head, dtype):
    """The head's product over every position: [B, S, vocab_rows] float32."""
    return jnp.dot(hidden.astype(dtype), head.astype(dtype),
                   preferred_element_type=F32)


def head_weights(module):
    """``module``'s final norm's weight [d] and its untied head
    [d, vocab_rows]."""
    s = module.sizes
    d = s["hidden_size"]
    return (module.param("final_norm", nn.initializers.zeros, (d,), F32),
            module.param("head", _normal(), (d, s["vocab_rows"]), F32))


def decoder_shell(module, tokens, targets, layer, depth, counts,
                  embed_scale=None):
    """What the decoders' ``__call__`` share round their layers, run inside
    the model's own ``nn.compact`` method (``embed``, ``final_norm`` and
    ``head`` are ``module``'s parameters; ``module.sizes`` gives
    ``hidden_size``, ``vocab_rows`` and ``rms_norm_eps``): the embedding's
    rows (``embedded``), ``layer(i)(x)`` -> (x, the layer's counts or None)
    for i < ``depth`` (``run_layers``), then the final norm and the untied
    head (``head_weights``), embedding and head under ``layer/head``.
    Without targets the logits [B, S, vocab_rows]; with them (the mean
    cross-entropy, {name: the layers' count of that place, stacked over the
    layers that gave any} for the names ``counts``). A model that walks its
    layers more than once, with a head at every pass (``models/ouro.py``),
    calls the three pieces itself."""
    x, found = run_layers([layer(i) for i in range(depth)],
                          embedded(module, tokens, embed_scale))
    with jax.named_scope("layer/head"):
        w_final, head = head_weights(module)
        hidden = rms_norm0(x, w_final, module.sizes["rms_norm_eps"])
        if targets is None:
            return logits_of(hidden, head, module.dtype)
        loss = token_losses(hidden, head, targets, module.dtype).mean()
    return loss, {name: jnp.stack(c) for name, c in zip(counts, zip(*found))}
