"""Keye-VL-2.0-30B-A3B's decoder block, as one chip's share of an expert group.

Source: https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json
(``model_type`` KeyeVL2; the catalog describes its attention as "GQA 32Q/4KV
with DeepSeek-Sparse-Attention indexer"). ``sizes`` is the configuration
file's group of that name: the published widths, with the depth, the experts
held here and the vocabulary rows cut as the file states. Plain
``jax.numpy``: no kernels, no grouped products,
no query blocks that stop at the diagonal, no bound in place of a row's maximum. It imports nothing of the program.

Notation: d = ``hidden_size``, eps = ``rms_norm_eps``, SiLU(x) = x sigma(x),
sg = stop_gradient, t a query position, s <= t a key position,
k = ``topk`` (``sa_config.topk``).

    RMSNorm0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)        w initialised 0
    LayerNorm(x; a, b) = (x - mean x) / sqrt(var x + eps) * a + b     a = 1, b = 0

Layer i of ``num_hidden_layers`` (all alike), on the residual stream x [B, S, d]:

    h = RMSNorm0(x; w_in)
    x <- x + Attention_{S(h)}(h)
    x <- x + MoE(RMSNorm0(x; w_post))

Attention (H = ``num_attention_heads``, H_kv = ``num_key_value_heads``,
D = ``head_dim``, theta = ``rope_theta``; head i reads key head i // (H / H_kv)):

    q_{t,i} = rope(RMSNorm0_D((h_t W_q)_i; w_qn)),  k_{s,g} = rope(RMSNorm0_D((h_s W_k)_g; w_kn)),
    v_{s,g} = (h_s W_v)_g                  [W_k | W_v] one matrix, ``kv_proj``
    rotary embedding in the rotate-half form over all D dimensions, positions
    0..S-1 (text tokens give the three ``mrope_section``s one position each,
    which is the plain rotary embedding)

Indexer (J = ``indexer_num_heads`` heads of D_I = ``indexer_head_dim`` over
ONE key head), on hb = sg(h):

    qI_{t,j} = rope((hb_t W_qI)_j)  in R^{D_I}
    kI_s     = rope(LayerNorm(hb_s W_kI; a, b))  in R^{D_I}
    w_t      = (hb_t W_w) J^-1/2 D_I^-1/2  in R^J
    I_{t,s}  = sum_j w_{t,j} ReLU(qI_{t,j} . kI_s)
    [W_qI | W_kI | W_w] one matrix, ``index_proj``, widths J D_I, D_I, J

Selection, exact: tau_t = the k-th largest of {I_{t,s} : s <= t}, or -inf
while t < k; S_t = {s <= t : I_{t,s} >= tau_t}: the k best keys (every one of
them while there are at most k), ties at tau_t all kept.

    a_{t,i,s} = softmax_{s in S_t}(q_{t,i} . k_{s,g(i)} / sqrt(D))
    o_t = (concat_i sum_{s in S_t} a_{t,i,s} v_{s,g(i)}) W_o

Indexer loss (the sparse stage of DeepSeek-V3.2-Exp's training):

    p_t = sg(sum_i a_{t,i,.}) / H            a distribution over S_t
    L_I = mean over layers, sequences and t of KL(p_t || softmax_{s in S_t} I_{t,s})

By the two stop-gradients W_qI, W_kI, W_w, a and b take their gradient from
L_I alone, and every other leaf from the cross-entropy alone.

MoE (E = ``num_experts``, top = ``num_experts_per_tok``, held experts
``expert_offset`` .. ``expert_offset`` + ``experts_held`` - 1; no shared expert):

    p = softmax_f32(x W_r) over all E;  the ``top`` largest, renormalised to sum 1
    E_j(x) = (SiLU(x W_gate,j) * x W_up,j) W_down,j
    y = sum_{j in top and held} p~_j E_j(x)

What the experts held elsewhere would add is left out (the configuration's
deployment: ``expert_parallel`` chips share each layer's experts, and on one
chip the layer runs without its exchange). No token is dropped.

Model: embedding over ``vocab_rows`` rows -> the layers -> RMSNorm0 -> an
untied head over the same rows; the objective is the mean cross-entropy over
all positions + L_I.

Departures from the published model, each stated in the configuration's
``assumed`` too: q/k RMSNorm per head (the family's convention; the config
has no key for it); the indexer as DeepSeek-V3.2 publishes it (LayerNorm on
kI, rotary on qI and kI over all D_I dimensions, the J^-1/2 D_I^-1/2 scale,
ReLU) but read from h, this config having no query latent, and without its
Hadamard rotation (orthogonal: it changes no dot product) and FP8 cast (a
precision below the configuration's); ``q_chunk_size`` / ``kv_chunk_size``
read as tiling, not mathematics; L_I at weight 1; **the vision tower is not
built** (the catalog holds no size of it: this is the language model's
text-only step); no multi-token prediction, no auxiliary balance loss;
N(0, 0.02) for matrices and the embedding, norms zero-centred (weight 0),
LayerNorm a = 1, b = 0; the column order inside ``kv_proj`` ([k | v]) and
``index_proj`` ([qI | kI | w], heads contiguous) is this file's own; each window of ``seq_len`` tokens an independent
sequence from position 0.

Precision: parameters float32; a projection takes ``dtype`` inputs and
gives a ``dtype`` output; the index products qI . kI, the attention's
q . k and a v take ``dtype`` inputs and accumulate in float32 (at float32 the
highest matmul precision; bfloat16 operands multiply exactly in one pass);
the residual stream, the norms, rotary, ReLU and the sum over the indexer's
heads, tau, both softmaxes, the router (logits at the highest matmul
precision) and both losses are float32. A sequence and a block of ``BLOCK``
queries at a time, against the keys up to the end of the block's quarter of
the sequence (``SECTIONS``; what lies beyond a query is masked, not
skipped), one key-value head at a time inside; tau_t by bisection on the bit patterns of row t (``kth_largest``:
the value a sort gives, which took over half of this file's step on the
chip: 1,077 ms a layer's attention, forward and backward, against 511
without any selection).
Every block and every layer is rematerialised in the backward pass.
"""

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

BLOCK = 256
# Tokens whose logits over the vocabulary's rows exist at once in the loss
# (16,384 x 18,992 float32 are 1.2 GB, and as much again for their gradient).
LOSS_ROWS = 4096
# A sequence's query blocks in this many sections, each against the keys up
# to its own end: at 4 the masked upper part is 3/8 of what is computed, not
# 1/2, and a run's 32 steps take two thirds of the time.
SECTIONS = 4
F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


# ----------------------------------------------------------------- pieces
def dense(x, w, dtype):
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   precision=HIGHEST if dtype == F32 else None)


def rounded(x, dtype):
    """x rounded to ``dtype`` and held in float32: a product of two such
    takes ``dtype`` inputs and accumulates in float32 on the chip (one
    bfloat16 pass at the default precision, exact for such values), and
    the CPU's float32 product of the same values runs where its bfloat16
    one is not implemented."""
    return x.astype(dtype).astype(F32)


def product(spec, a, b, dtype):
    """einsum of two operands rounded to ``dtype``, accumulated and given
    in float32 (at float32 the highest matmul precision)."""
    return jnp.einsum(spec, rounded(a, dtype), rounded(b, dtype),
                      precision=HIGHEST if dtype == F32 else None)


def rms_norm0(x, w, eps):
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def layer_norm(x, scale, bias, eps):
    x = x.astype(F32)
    centred = x - jnp.mean(x, -1, keepdims=True)
    return centred * lax.rsqrt(jnp.mean(centred * centred, -1, keepdims=True)
                               + eps) * scale + bias


def normal(std=0.02):
    return nn.initializers.normal(std)


def rotary(x, theta):
    """Rotate-half rotary embedding over the whole last axis; x [B, S, H, D]
    float32, positions 0..S-1."""
    length, dim = x.shape[1], x.shape[-1]
    half = dim // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) * 2.0 / dim))
    angle = jnp.arange(length, dtype=F32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(angle) + turned * jnp.sin(angle)


def index_scores(qi, ki, w, dtype):
    """I_{t,s} for the queries given: qi [Q, J, D_I], ki [S, D_I], w [Q, J]
    float32 -> [Q, S] float32."""
    dots = product("qjd,sd->qjs", qi, ki, dtype)
    return jnp.sum(w[:, :, None] * jax.nn.relu(dots), axis=1)


def kth_largest(rows, k):
    """The ``k``-th largest of each row of ``rows`` [Q, S] float32 (S >= k,
    no NaN), the value a descending sort holds at place k: by bisection on
    the bit pattern, as ``perfbench/reference.py`` finds its own k-th
    magnitude. A float's bits, the magnitude's turned over under a negative
    sign and the sign bit set otherwise, order as unsigned integers the way
    the floats do (-0.0 just under 0.0); 32 halvings of [0, 2^32) leave the
    one pattern that ``k`` of the row reach and ``k`` do not pass."""
    bits = lax.bitcast_convert_type(rows, jnp.uint32)
    sign = jnp.uint32(1 << 31)
    keys = jnp.where(bits >= sign, ~bits, bits | sign)

    def halve(_, bounds):
        low, high = bounds
        middle = low + (high - low) // 2 + (high - low) % 2
        enough = jnp.sum(keys >= middle[:, None], axis=-1) >= k
        return jnp.where(enough, middle, low), jnp.where(enough, high, middle - 1)

    none = jnp.zeros(rows.shape[:1], jnp.uint32)
    low, _ = lax.fori_loop(0, 32, halve, (none, ~none))
    return lax.bitcast_convert_type(
        jnp.where(low >= sign, low ^ sign, ~low), F32)


def key_set(scores, rows, topk):
    """S_t as a mask: scores [Q, S] of the queries at positions ``rows``
    -> bool [Q, S]."""
    keys = scores.shape[1]
    valid = rows[:, None] >= jnp.arange(keys)[None, :]
    if keys <= topk:
        return valid
    tau = kth_largest(jnp.where(valid, scores, -jnp.inf), topk)
    return valid & (scores >= tau[:, None])


# Under ``jit`` the layers, which are alike, share one trace of this.
@functools.partial(jax.jit, static_argnums=(6, 7))
def sparse_attention(q, k, v, qi, ki, w, topk, dtype):
    """One sequence. q [S, H, D], k, v [S, H_kv, D], qi [S, J, D_I],
    ki [S, D_I], w [S, J], float32 -> (o [S, H, D] float32, KL_t [S],
    |S_t| [S])."""
    length, heads, dim = q.shape
    kv_heads = k.shape[1]
    block = math.gcd(length, BLOCK)
    blocks = length // block
    sections = SECTIONS if blocks % SECTIONS == 0 else 1
    by_group = lambda a: jnp.moveaxis(a, 1, 0)        # [S, G, ...] -> [G, S, ...]

    def section(first, last):
        """Query blocks ``first`` .. ``last`` - 1 against the keys up to the
        last one's end (what lies beyond a query is masked, not skipped)."""
        keys = last * block
        k_g, v_g, ki_s = by_group(k[:keys]), by_group(v[:keys]), ki[:keys]

        @jax.checkpoint
        def one(args):
            q_b, qi_b, w_b, rows = args
            scores = index_scores(qi_b, ki_s, w_b, dtype)
            keep = key_set(lax.stop_gradient(scores), rows, topk)

            def group(args):
                q1, k1, v1 = args          # [Q, H / H_kv, D], [S, D], [S, D]
                logits = product("qrd,sd->rqs", q1, k1, dtype) / math.sqrt(dim)
                probs = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf),
                                       axis=-1)
                return product("rqs,sd->qrd", probs, v1, dtype), probs.sum(0)

            out, mass = lax.map(group, (by_group(
                q_b.reshape(block, kv_heads, heads // kv_heads, dim)), k_g, v_g))
            p = lax.stop_gradient(mass.sum(0)) / heads
            log_q = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
            seen = keep & (p > 0)
            kl = jnp.sum(jnp.where(seen, p * (jnp.log(jnp.where(seen, p, 1.0))
                                              - jnp.where(seen, log_q, 0.0)),
                                   0.0), -1)
            return (jnp.moveaxis(out, 0, 1).reshape(block, heads, dim), kl,
                    keep.sum(-1))

        part = lambda a: a[first * block:keys].reshape(
            (last - first, block) + a.shape[1:])
        return lax.map(one, (part(q), part(qi), part(w),
                             part(jnp.arange(length))))

    step = blocks // sections
    parts = [section(i * step, (i + 1) * step) for i in range(sections)]
    out, kl, kept = (jnp.concatenate([p[i] for p in parts]) for i in range(3))
    return out.reshape(length, heads, dim), kl.reshape(-1), kept.reshape(-1)


def expert(x, gate, up, down, dtype):
    hidden = jax.nn.silu(dense(x, gate, dtype).astype(F32)) \
        * dense(x, up, dtype).astype(F32)
    return dense(hidden, down, dtype)


def route(x, router, top, normalise):
    """(probabilities of the ``top`` experts [T, top] float32, their ids)."""
    logits = jnp.dot(x.astype(F32), router, precision=HIGHEST)
    values, ids = lax.top_k(jax.nn.softmax(logits, axis=-1), top)
    if normalise:
        values = values / jnp.sum(values, -1, keepdims=True)
    return values, ids


# ------------------------------------------------------------------ modules
class SparseAttention(nn.Module):
    """(y [B, S, d], L_I of this layer, sum of |S_t|)."""
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, h):
        s, dtype = self.sizes, self.dtype
        d, dim = s["hidden_size"], s["head_dim"]
        heads, kv_heads = s["num_attention_heads"], s["num_key_value_heads"]
        j, d_i = s["indexer_num_heads"], s["indexer_head_dim"]
        w_q = self.param("q_proj", normal(), (d, heads * dim), F32)
        w_kv = self.param("kv_proj", normal(), (d, 2 * kv_heads * dim), F32)
        w_qn = self.param("q_norm", nn.initializers.zeros, (dim,), F32)
        w_kn = self.param("k_norm", nn.initializers.zeros, (dim,), F32)
        w_o = self.param("o_proj", normal(), (heads * dim, d), F32)
        w_i = self.param("index_proj", normal(), (d, j * d_i + d_i + j), F32)
        a_ki = self.param("index_k_norm_scale", nn.initializers.ones, (d_i,), F32)
        b_ki = self.param("index_k_norm_bias", nn.initializers.zeros, (d_i,), F32)

        batch, length = h.shape[:2]
        if self.is_initializing():
            # The parameters are made; what follows makes none, and its
            # trace at 16,384 tokens is seconds of every run's set-up.
            return jnp.zeros(h.shape, dtype), jnp.zeros((), F32), \
                jnp.zeros((), jnp.int32)
        eps, theta = s["rms_norm_eps"], s["rope_theta"]
        q = dense(h, w_q, dtype).reshape(batch, length, heads, dim)
        kv = dense(h, w_kv, dtype).reshape(batch, length, 2, kv_heads, dim)
        k, v = kv[:, :, 0], kv[:, :, 1].astype(F32)
        q = rotary(rms_norm0(q, w_qn, eps), theta)
        k = rotary(rms_norm0(k, w_kn, eps), theta)
        index = dense(lax.stop_gradient(h), w_i, dtype).astype(F32)
        qi = rotary(index[..., :j * d_i].reshape(batch, length, j, d_i), theta)
        ki = rotary(layer_norm(index[..., j * d_i:j * d_i + d_i], a_ki, b_ki,
                               eps)[:, :, None], theta)[:, :, 0]
        w = index[..., j * d_i + d_i:] / math.sqrt(j * d_i)
        out, kl, kept = lax.map(
            lambda a: sparse_attention(*a, s["topk"], dtype),
            (q, k, v, qi, ki, w))
        y = dense(out.reshape(batch, length, heads * dim), w_o, dtype)
        return y, jnp.mean(kl), jnp.sum(kept)


class SparseMoE(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        s, dtype = self.sizes, self.dtype
        d, width = s["hidden_size"], s["moe_intermediate_size"]
        held, offset = s["experts_held"], s["expert_offset"]
        router = self.param("router", normal(), (d, s["num_experts"]), F32)
        gate = self.param("experts_gate", normal(), (held, d, width), F32)
        up = self.param("experts_up", normal(), (held, d, width), F32)
        down = self.param("experts_down", normal(), (held, width, d), F32)

        shape = x.shape
        x = x.reshape(-1, d)
        probs, ids = route(x, router, s["num_experts_per_tok"],
                           s["norm_topk_prob"])

        @jax.checkpoint
        def held_expert(total, args):
            index, w_gate, w_up, w_down = args
            weight = jnp.sum(jnp.where(ids == offset + index, probs, 0.0), -1)
            out = expert(x, w_gate, w_up, w_down, dtype).astype(F32)
            return total + weight[:, None] * out, None

        y, _ = lax.scan(held_expert, jnp.zeros(x.shape, F32),
                        (jnp.arange(held), gate, up, down))
        return y.reshape(shape)


class Layer(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        s = self.sizes
        w_in = self.param("input_norm", nn.initializers.zeros,
                          (s["hidden_size"],), F32)
        w_post = self.param("post_norm", nn.initializers.zeros,
                            (s["hidden_size"],), F32)
        y, index_loss, kept = SparseAttention(s, self.dtype, name="mixer")(
            rms_norm0(x, w_in, s["rms_norm_eps"]))
        x = x + y.astype(F32)
        moe = SparseMoE(s, self.dtype, name="moe")
        return (x + moe(rms_norm0(x, w_post, s["rms_norm_eps"])),
                index_loss, kept)


class KeyeVL2(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, tokens, train=False):
        """(the final hidden states [B, S, d] float32, the head's matrix,
        L_I, the layers' sums of |S_t| [layers]): ``loss`` applies the head
        a sequence at a time."""
        s = self.sizes
        table = self.param("embed", normal(), (s["vocab_rows"], s["hidden_size"]), F32)
        x = table[tokens]
        index_losses, kept = [], []
        for i in range(s["num_hidden_layers"]):
            x, index_loss, count = nn.remat(Layer)(
                s, self.dtype, name=f"layer_{i}")(x)
            index_losses.append(index_loss)
            kept.append(count)
        w_final = self.param("final_norm", nn.initializers.zeros,
                             (s["hidden_size"],), F32)
        head = self.param("head", normal(), (s["hidden_size"], s["vocab_rows"]), F32)
        return (rms_norm0(x, w_final, s["rms_norm_eps"]), head,
                jnp.mean(jnp.stack(index_losses)), jnp.stack(kept))


# ------------------------------------------------- what reference.py calls
def build(sizes, dtype):
    # Flax freezes a dict attribute and hashes it: scalars only.
    scalars = {k: v for k, v in sizes.items()
               if isinstance(v, (bool, int, float, str))}
    return KeyeVL2(scalars, dtype), jnp.zeros((1, sizes["seq_len"]), jnp.int32)


def initial_carry(sizes, batch, dtype):
    return ()


def token_losses(hidden, head, targets, dtype):
    """Cross-entropy of every position, float32, ``LOSS_ROWS`` tokens of a
    sequence at a time."""
    rows = math.gcd(hidden.shape[1], LOSS_ROWS)
    hidden = hidden.reshape(-1, rows, hidden.shape[-1])
    targets = targets.reshape(-1, rows)

    @jax.checkpoint
    def one(args):
        h, t = args
        logits = product("sd,dv->sv", h, head, dtype)
        picked = jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    return lax.map(one, (hidden, targets))


def losses(module, variables, batch):
    """(cross-entropy, L_I): the objective's two terms."""
    hidden, head, index_loss, _ = module.apply(variables, batch["tokens"], True)
    ce = token_losses(hidden, head, batch["targets"], module.dtype).mean()
    return ce, index_loss


def loss(module, variables, carry, batch, key, train):
    ce, index_loss = losses(module, variables, batch)
    return ce + index_loss, None, carry


# ------------------------------------------------------ work, from shapes
def keys_due(sizes):
    """sum over t of min(t + 1, topk): the pairs the restricted attention
    of one ``seq_len``-token sequence holds."""
    length, topk = sizes["seq_len"], min(sizes["topk"], sizes["seq_len"])
    return topk * (topk + 1) // 2 + (length - topk) * topk


def index_pairs(sizes):
    """sum over t of (t + 1): the pairs the indexer scores."""
    return sizes["seq_len"] * (sizes["seq_len"] + 1) // 2


def _projection_macs(sizes):
    """(attention's, indexer's) projection multiply-accumulates a token."""
    s = sizes
    d, dim = s["hidden_size"], s["head_dim"]
    heads, kv = s["num_attention_heads"], s["num_key_value_heads"]
    j, d_i = s["indexer_num_heads"], s["indexer_head_dim"]
    return 2 * d * heads * dim + 2 * d * kv * dim, d * (j * d_i + d_i + j)


def forward_macs(sizes):
    """Multiply-accumulates of one ``seq_len``-token sequence's forward
    pass, from the shapes: the model's mathematics, whatever form a program
    computes it in. Counted: every matrix product of the layers and the
    head; the index scores of every pair s <= t (J D_I a pair); the
    restricted attention at min(t + 1, topk) keys a query (2 H D a pair);
    the routed experts at the uniform expected load, ``num_experts_per_tok``
    * ``experts_held`` / ``num_experts`` of a slot a token and layer. Not
    counted: the embedding (a lookup), norms, rotary, the selection, both
    softmaxes, the indexer's loss, elementwise gates."""
    s = sizes
    d, length = s["hidden_size"], s["seq_len"]
    attn_proj, index_proj = _projection_macs(s)
    index = index_pairs(s) * s["indexer_num_heads"] * s["indexer_head_dim"]
    attn = keys_due(s) * 2 * s["num_attention_heads"] * s["head_dim"]
    slots = s["num_experts_per_tok"] * s["experts_held"] / s["num_experts"]
    moe = d * s["num_experts"] + slots * 3 * d * s["moe_intermediate_size"]
    per_layer = length * (attn_proj + index_proj + moe) + index + attn
    return int(s["num_hidden_layers"] * per_layer
               + length * d * s["vocab_rows"])


def _three_passes(sizes, batch, macs, floats):
    """(operations, bytes) of a training step: forward once and backward
    twice (recomputation not counted), 2 operations a multiply-accumulate,
    4 bytes a float32 moved, every layer and sequence."""
    times = 3 * sizes["num_hidden_layers"] * batch
    return times * 2 * macs, times * 4 * floats


def dsa_index_work(sizes, batch):
    """(operations, bytes) of the indexer in one training step over
    ``batch`` sequences, for its roofline: its three projections and the
    index scores of every pair s <= t. Bytes, the least a blocked pass must
    move: h read, qI, kI and w written and read once, one threshold a query
    written, and the projections' weights."""
    s = sizes
    _, index_proj = _projection_macs(s)
    j, d_i = s["indexer_num_heads"], s["indexer_head_dim"]
    macs = s["seq_len"] * index_proj + index_pairs(s) * j * d_i
    floats = s["seq_len"] * (s["hidden_size"] + 2 * (j * d_i + d_i + j) + 1) \
        + index_proj
    return _three_passes(s, batch, macs, floats)


def dsa_attn_work(sizes, batch):
    """(operations, bytes) of the main attention in one training step over
    ``batch`` sequences, for its roofline: its four projections and the
    softmax attention over the chosen keys alone, min(t + 1, topk) a query:
    a form that multiplies masked pairs too does more and reads lower.
    Bytes: h read and y written, q, k, v and o written and read once, and
    the projections' weights."""
    s = sizes
    attn_proj, _ = _projection_macs(s)
    heads, kv, dim = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
    macs = s["seq_len"] * attn_proj + keys_due(s) * 2 * heads * dim
    floats = s["seq_len"] * (2 * s["hidden_size"] + 2 * (2 * heads + 2 * kv) * dim) \
        + attn_proj
    return _three_passes(s, batch, macs, floats)
