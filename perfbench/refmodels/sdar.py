"""SDAR-30B-A3B-Chat's decoder under its block-diffusion training objective,
as one chip's share of an expert group.

Source: https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json
(``model_type`` sdar_moe, 30B-A3B; the catalog: "48L GQA", "128 experts,
top-8, 0 shared", "block diffusion"; "SDAR: A Synergistic
Diffusion-AutoRegression Paradigm for Scalable Sequence Generation",
arXiv:2510.06303, whose training objective is the block-diffusion one of
BD3-LM, arXiv:2503.09573). ``sizes`` is the configuration file's group of
that name: the published widths, with the depth, the experts held here and
the vocabulary rows cut as the file states. Plain ``jax.numpy``: no kernels,
no grouped products, the visibility rule a boolean over [block, keys] built
from the rows' block indices, the experts a mask over the held ones. It
imports nothing of the program.

Notation: d = ``hidden_size``, eps = ``rms_norm_eps``, SiLU(x) = x sigma(x),
L = the sequence's length, B = ``block_length``, M = ``mask_token_id``,
e = ``noise_eps``, b(i) = i // B.

    RMSNorm0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)        w initialised 0

Noise, for a sequence x of L tokens, from the step's key (``add_noise``):

    key_t, key_m = split(key)
    t_c ~ U(0, 1) for each block c < L / B (from key_t),  p_c = (1 - e) t_c + e
    m_i = [u_i < p_b(i)],  u_i ~ U(0, 1) for each position (from key_m)
    x~_i = M where m_i else x_i,        w_i = m_i / p_b(i)

(the linear schedule alpha_t = 1 - t of masked diffusion, whose weight
-alpha'_t / (1 - alpha_t) is 1 / t; one t a block is BD3-LM's estimator of
the per-block expectation; e keeps 1 / p finite).

Rows: r = [x ; x~], 2L of them; row i and row L + i both have position i.
Model: E[r] over ``vocab_rows`` rows (M's among them) -> the layers over all
2L rows -> RMSNorm0 of the noised half -> an untied head over the same rows:

    loss = (1 / (batch L)) sum_i w_i CE(logits_i, x_i)

the label of a masked position the clean token AT that position (no shift),
the mask id's logit left in the softmax. The clean rows have no loss; what
reaches them is what the noised rows read of their keys and values.

Layer (all alike), on the residual stream x [batch, 2L, d]:

    x <- x + Attention(RMSNorm0(x; w_in))
    x <- x + MoE(RMSNorm0(x; w_post))

Attention (H = ``num_attention_heads``, H_kv = ``num_key_value_heads``,
D = ``head_dim``; head i reads key head i // (H / H_kv)), on h:

    q_{i,a} = rope(RMSNorm0_D((h_i W_q)_a; w_qn)),
    k_{j,g} = rope(RMSNorm0_D((h_j W_k)_g; w_kn)),  v_{j,g} = (h_j W_v)_g
                                  [W_k | W_v] one matrix, ``kv_proj``;
                                  rope: rotate-half over all D dimensions,
                                  theta = ``rope_theta``, at the row's position
    query row i SEES key row j  iff
        i clean,  j clean   and b(j) <= b(i)          (block-causal)
        i noised, j clean   and b(j) <  b(i)          (the clean past)
        i noised, j noised  and b(j) =  b(i)          (its own block, both ways)
      (b of a row's position; a clean query sees no noised key)
    o_{i,a} = sum_{j seen} softmax_j(q_{i,a} . k_{j,g(a)} / sqrt(D)) v_{j,g(a)}
    y_i = (concat_a o_{i,a}) W_o

MoE (E = ``num_experts``, top = ``num_experts_per_tok``, width
``moe_intermediate_size``; held experts ``expert_offset`` ..
``expert_offset`` + ``experts_held`` - 1; no shared expert, no bias):

    s = softmax_f32(m W_r) over all E;  chosen = the ``top`` largest
    w_e = s_e / sum_{e in chosen} s_e                  (``norm_topk_prob``)
    y = sum_{e in chosen and held} w_e (SiLU(m W_gate,e) * m W_up,e) W_down,e

What the experts held elsewhere would add is left out (the configuration's
deployment: ``expert_parallel`` chips share each layer's experts, and on one
chip the layer runs without its exchange). No token is dropped.

Departures from the published model, each stated in the configuration's
``assumed`` too: B, e, M and the noise rule above (the config states none);
q/k norms per head, zero-centred; N(0, 0.02) for matrices and the
embedding; k_proj and v_proj one leaf; no dropout, no auxiliary loss;
every window of ``seq_len`` tokens an independent sequence from position 0.

Precision: parameters float32; a projection takes ``dtype`` inputs and
gives a ``dtype`` output; q . k and a v take ``dtype`` inputs and accumulate
in float32 (at float32 the highest matmul precision; bfloat16 operands
multiply exactly in one pass); the residual stream, the norms, rotary, the
softmax, the router (logits at the highest matmul precision), the weights
w and the loss are float32. ``CHUNK`` rows at a time (a query's projection
and attention; a row's experts), each chunk under a checkpoint; inside, a
block of ``BLOCK`` queries and a key-value head's query heads at a time
against the keys its chunk is handed (``chunk_keys``: the clean rows up to
the chunk's end and, for a noised chunk, its own rows: at 2 x 8,192 rows
4,096 to 12,288 keys a chunk for the 16,384): what a query does not see
among them is masked by the comparison of block indices, not skipped.
Every layer is rematerialised in the backward pass.
"""

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

BLOCK = 256
# Rows whose mixer or expert intermediates exist at once: the step of
# ``perfbench/reference.py`` holds 27 B a parameter beside them.
CHUNK = 4096
# Rows whose logits over the vocabulary's rows exist at once in the loss.
LOSS_ROWS = 4096
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


def normal(std=0.02):
    return nn.initializers.normal(std)


def rotary(x, theta, positions):
    """Rotate-half rotary embedding over the whole last axis; x [S, H, D]
    float32 at ``positions`` [S]."""
    dim = x.shape[-1]
    half = dim // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) * 2.0 / dim))
    angle = positions.astype(F32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(angle) + turned * jnp.sin(angle)


def add_noise(key, tokens, block_length, mask_id, eps):
    """(x~ [batch, L], w [batch, L] float32, p [batch, L / B]) of ``tokens``
    [batch, L] by the rule of the module's docstring."""
    batch, length = tokens.shape
    key_t, key_m = jax.random.split(key)
    t = jax.random.uniform(key_t, (batch, length // block_length), F32)
    p = (1.0 - eps) * t + eps
    each = jnp.repeat(p, block_length, axis=1)
    masked = jax.random.uniform(key_m, (batch, length), F32) < each
    return (jnp.where(masked, mask_id, tokens),
            jnp.where(masked, 1.0 / each, 0.0), p)


def sees(rows, keys, half, block_length):
    """[len(rows), len(keys)] bool: whether query row i sees key row j, rows
    0 .. half - 1 the clean ones and half .. 2 half - 1 the noised ones:
    the rule as the comparison of block indices it is."""
    q_noised, k_noised = (rows >= half)[:, None], (keys >= half)[None, :]
    qb = ((rows % half) // block_length)[:, None]
    kb = ((keys % half) // block_length)[None, :]
    return jnp.where(k_noised, q_noised & (kb == qb),
                     jnp.where(q_noised, kb < qb, kb <= qb))


def attend(q_b, k_b, v_b, seen, dtype):
    """A block's queries q_b [Q, R, D] of one key-value head over the keys
    k_b, v_b [K, D], ``seen`` [Q, K] bool -> [Q, R, D] float32."""
    logits = product("qrd,sd->rqs", q_b, k_b, dtype) / math.sqrt(q_b.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    return product("rqs,sd->qrd", probs, v_b, dtype)


def attention(q, k, v, rows, keys, half, block_length, dtype):
    """The queries q [C, H, D] at the rows ``rows`` [C] of one sequence's
    2L rows over the keys k, v [K, H_kv, D] at the rows ``keys`` [K],
    float32 -> o [C, H, D] float32. A block of queries at a time, its
    boolean made once from the rows' block indices (``sees``), and inside
    it a key-value head at a time, each head's rows under a checkpoint of
    their own; every block reads every key it is handed, and what a query
    does not see is masked."""
    count, heads, dim = q.shape
    kv_heads = k.shape[1]
    block = math.gcd(count, BLOCK)
    # [C, H, D] -> [blocks, G, block, R, D]; keys [G, K, D].
    q = jnp.moveaxis(q.reshape(-1, block, kv_heads, heads // kv_heads, dim),
                     2, 1)
    k, v = jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)

    def one(args):
        q_b, rows_b = args
        seen = sees(rows_b, keys, half, block_length)
        return lax.map(jax.checkpoint(lambda a: attend(*a, seen, dtype)),
                       (q_b, k, v))

    out = lax.map(one, (q, rows.reshape(-1, block)))
    # [blocks, G, block, R, D] -> [C, H, D]
    return jnp.moveaxis(out, 1, 2).reshape(count, heads, dim)


def chunk_keys(first, count, half):
    """The rows whose keys a chunk of ``count`` queries from row ``first``
    is handed: a chunk lies in one half and holds whole blocks, so the
    clean rows before its end (in its own half's positions) hold every
    clean key it can see, and a noised chunk's own rows every noised one.
    The rule itself is ``sees``' comparison over these rows; this only
    leaves out keys that no query of the chunk can see (the other half's
    share of a clean chunk, the later clean rows, the other noised
    chunks)."""
    clean = jnp.arange(first % half + count)
    return clean if first < half else jnp.concatenate(
        [clean, first + jnp.arange(count)])


def by_chunks(fn, *rows):
    """``fn`` over ``CHUNK`` rows at a time of arrays [T, ...], each chunk
    under a checkpoint; ``fn`` is also handed the chunk's first row."""
    total = rows[0].shape[0]
    size = math.gcd(total, CHUNK)
    cut = lambda a: a.reshape((total // size, size) + a.shape[1:])
    out = lax.map(jax.checkpoint(lambda args: fn(*args)),
                  (*map(cut, rows), size * jnp.arange(total // size)))
    return out.reshape((total,) + out.shape[2:])


def expert(x, gate, up, down, dtype):
    hidden = jax.nn.silu(dense(x, gate, dtype).astype(F32)) \
        * dense(x, up, dtype).astype(F32)
    return dense(hidden, down, dtype)


def route(x, router, top, normalise):
    """(weights of the ``top`` experts [T, top] float32, their ids)."""
    scores = jax.nn.softmax(
        jnp.dot(x.astype(F32), router, precision=HIGHEST), axis=-1)
    values, ids = lax.top_k(scores, top)
    if normalise:
        values = values / jnp.sum(values, -1, keepdims=True)
    return values, ids


# ------------------------------------------------------------------ modules
class Attention(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, h):
        s, dtype = self.sizes, self.dtype
        d, dim = s["hidden_size"], s["head_dim"]
        heads, kv_heads = s["num_attention_heads"], s["num_key_value_heads"]
        w_q = self.param("q_proj", normal(), (d, heads * dim), F32)
        w_kv = self.param("kv_proj", normal(), (d, 2 * kv_heads * dim), F32)
        w_qn = self.param("q_norm", nn.initializers.zeros, (dim,), F32)
        w_kn = self.param("k_norm", nn.initializers.zeros, (dim,), F32)
        w_o = self.param("o_proj", normal(), (heads * dim, d), F32)

        rows = h.shape[1]
        if self.is_initializing():
            # The parameters are made; what follows makes none.
            return jnp.zeros(h.shape, dtype)
        eps, theta = s["rms_norm_eps"], s["rope_theta"]
        half = rows // 2

        # Chunks of one half each: CHUNK rows, or the half where it is shorter.
        count = half if half <= CHUNK else math.gcd(half, CHUNK)

        def sequence(h1):
            kv = dense(h1, w_kv, dtype).reshape(rows, 2, kv_heads, dim)
            k = rotary(rms_norm0(kv[:, 0], w_kn, eps), theta,
                       jnp.arange(rows) % half)
            v = kv[:, 1].astype(F32)

            @jax.checkpoint
            def chunk(h_c, k_c, v_c, mine, keys):
                q = rms_norm0(dense(h_c, w_q, dtype).reshape(-1, heads, dim),
                              w_qn, eps)
                q = rotary(q, theta, mine % half)
                out = attention(q, k_c, v_c, mine, keys, half,
                                s["block_length"], dtype)
                return dense(out.reshape(-1, heads * dim), w_o, dtype)

            outs = []
            for first in range(0, rows, count):
                keys = chunk_keys(first, count, half)
                outs.append(chunk(h1[first:first + count], k[keys], v[keys],
                                  first + jnp.arange(count), keys))
            return jnp.concatenate(outs, 0)

        return lax.map(sequence, h)


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
        weights, ids = route(x, router, s["num_experts_per_tok"],
                             s["norm_topk_prob"])

        def chunk(x, ids, weights, _):
            @jax.checkpoint
            def held_expert(index, w_gate, w_up, w_down):
                weight = jnp.sum(
                    jnp.where(ids == offset + index, weights, 0.0), -1)
                out = expert(x, w_gate, w_up, w_down, dtype).astype(F32)
                return weight[:, None] * out

            # The sum is taken outside the checkpoint: its backward pass
            # needs no running total, so none is kept for every expert.
            y, _ = lax.scan(
                lambda total, args: (total + held_expert(*args), None),
                jnp.zeros(x.shape, F32), (jnp.arange(held), gate, up, down))
            return y

        return by_chunks(chunk, x, ids, weights).reshape(shape)


class Layer(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        s = self.sizes
        d, eps = s["hidden_size"], s["rms_norm_eps"]
        w_in = self.param("input_norm", nn.initializers.zeros, (d,), F32)
        w_post = self.param("post_norm", nn.initializers.zeros, (d,), F32)
        x = x + Attention(s, self.dtype, name="mixer")(
            rms_norm0(x, w_in, eps)).astype(F32)
        return x + SparseMoE(s, self.dtype, name="moe")(
            rms_norm0(x, w_post, eps))


class SDAR(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, tokens, train=False):
        """(the noised half's final hidden states [batch, L, d] float32, the
        head's matrix, the weights w [batch, L]): ``loss`` applies the head
        ``LOSS_ROWS`` rows at a time. The noise is drawn here, at the
        module's top, from the ``dropout`` key it is applied with."""
        s = self.sizes
        d, length = s["hidden_size"], tokens.shape[1]
        noised, weight, _ = add_noise(
            self.make_rng("dropout"), tokens, s["block_length"],
            s["mask_token_id"], s["noise_eps"])
        table = self.param("embed", normal(), (s["vocab_rows"], d), F32)
        x = table[jnp.concatenate([tokens, noised], axis=1)]
        for i in range(s["num_hidden_layers"]):
            x = nn.remat(Layer)(s, self.dtype, name=f"layer_{i}")(x)
        w_final = self.param("final_norm", nn.initializers.zeros, (d,), F32)
        head = self.param("head", normal(), (d, s["vocab_rows"]), F32)
        return (rms_norm0(x[:, length:], w_final, s["rms_norm_eps"]), head,
                weight)


# ------------------------------------------------- what reference.py calls
def build(sizes, dtype):
    # Flax freezes a dict attribute and hashes it: scalars only.
    scalars = {k: v for k, v in sizes.items()
               if isinstance(v, (bool, int, float, str))}
    return SDAR(scalars, dtype), jnp.zeros((1, sizes["seq_len"]), jnp.int32)


def initial_carry(sizes, batch, dtype):
    return ()


def token_losses(hidden, head, targets, dtype):
    """Cross-entropy of every position, float32, ``LOSS_ROWS`` rows of a
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


def loss(module, variables, carry, batch, key, train):
    """(sum_i w_i CE_i / (batch L) with the noise drawn from ``key``, no
    model state, the carry); ``batch["targets"]`` is unused: the label of a
    masked position is ``batch["tokens"]`` at that position."""
    tokens = batch["tokens"]
    hidden, head, weight = module.apply(variables, tokens, train,
                                        rngs={"dropout": key})
    ce = token_losses(hidden, head, tokens, module.dtype).reshape(tokens.shape)
    return jnp.sum(weight * ce) / tokens.size, None, carry


# ------------------------------------------------------ work, from shapes
def live_pairs(sizes):
    """(clean -> clean, noised -> clean, noised -> noised) pairs a layer of
    one ``seq_len``-token sequence holds: with n = L / B blocks of B
    tokens, B^2 n (n + 1) / 2, B^2 n (n - 1) / 2 and B^2 n."""
    block = sizes["block_length"]
    blocks = sizes["seq_len"] // block
    return (block * block * blocks * (blocks + 1) // 2,
            block * block * blocks * (blocks - 1) // 2,
            block * block * blocks)


def _row_macs(sizes):
    """(q and o, k and v, router and the expected routed experts)
    multiply-accumulates a row and layer."""
    s = sizes
    d, dim = s["hidden_size"], s["head_dim"]
    slots = s["num_experts_per_tok"] * s["experts_held"] / s["num_experts"]
    return (2 * d * s["num_attention_heads"] * dim,
            2 * d * s["num_key_value_heads"] * dim,
            d * s["num_experts"] + slots * 3 * d * s["moe_intermediate_size"])


def _pair_macs(sizes):
    """q . k and a v of a pair over every head."""
    return 2 * sizes["num_attention_heads"] * sizes["head_dim"]


def forward_macs(sizes, everything=False):
    """Multiply-accumulates of one ``seq_len``-token sequence's forward
    pass (2L rows), from the shapes: **what the loss depends on**, whatever
    a program computes. Counted: every matrix product of the layers over
    all 2L rows and of the head over the L noised ones; the attention at
    the live pairs (2 H D a pair); the routed experts at the uniform
    expected load, ``num_experts_per_tok`` * ``experts_held`` /
    ``num_experts`` of a slot a row and layer. The last layer's clean rows
    feed nothing but that layer's keys and values: their q, clean -> clean
    pairs, o, router and experts are left out (``everything``: counted, the
    4.08 TMAC a program that computes them multiplies). Not counted: the
    embedding (a lookup), the noise, norms, rotary, softmax."""
    s = sizes
    length, layers = s["seq_len"], s["num_hidden_layers"]
    q_o, k_v, feed_forward = _row_macs(s)
    whole = 2 * length * (q_o + k_v + feed_forward) \
        + sum(live_pairs(s)) * _pair_macs(s)
    unused = 0 if everything else \
        length * (q_o + feed_forward) + live_pairs(s)[0] * _pair_macs(s)
    return int(layers * whole - unused
               + length * s["hidden_size"] * s["vocab_rows"])


def _work(macs, numbers, batch, bytes_each):
    """(operations, bytes) of a training step: forward once and backward
    twice (recomputation not counted), 2 operations a multiply-accumulate."""
    return 3 * batch * 2 * macs, 3 * batch * bytes_each * numbers


def bd_pairs_work(sizes, batch):
    """(operations, bytes) of the attention itself, what the kernels (or
    the products and softmax that stand for them) compute of what the loss
    depends on: q . k and a v at the live pairs, 67,141,632 a layer less
    the last layer's clean -> clean ones, forward once and backward twice;
    a kernel that multiplies the backward's five products for the
    forward's two, and whole edge tiles, does more and cannot read 100%.
    Bytes: q, k, v read and o written once a pass, 2 bytes a number (the
    kernels' dtype)."""
    s = sizes
    heads, kv, dim = (s["num_attention_heads"], s["num_key_value_heads"],
                      s["head_dim"])
    layers = s["num_hidden_layers"]
    pairs = layers * sum(live_pairs(s)) - live_pairs(s)[0]
    numbers = s["seq_len"] * dim * (
        layers * 2 * (2 * heads + 2 * kv) - 2 * heads)
    return _work(pairs * _pair_macs(s), numbers, batch, 2)


def bd_attn_work(sizes, batch):
    """(operations, bytes) of the layers' mixers in one training step: the
    projections and the live pairs the loss depends on (the last layer's
    clean rows: k and v alone). Bytes, the least a blocked pass must move
    in float32: h read and y written, q, k, v and o written and read once,
    and the projections' weights."""
    s = sizes
    length, layers, d = s["seq_len"], s["num_hidden_layers"], s["hidden_size"]
    heads, kv, dim = (s["num_attention_heads"], s["num_key_value_heads"],
                      s["head_dim"])
    q_o, k_v, _ = _row_macs(s)
    macs = layers * 2 * length * (q_o + k_v) - length * q_o
    # A row's numbers: h and y, and q, k, v, o twice; the last layer's
    # clean rows h, and k, v twice.
    row, last_clean = 2 * d + 4 * (heads + kv) * dim, d + 4 * kv * dim
    numbers = (2 * layers - 1) * length * row + length * last_clean \
        + layers * (q_o + k_v)
    operations, _ = bd_pairs_work(s, batch)
    extra, moved = _work(macs, numbers, batch, 4)
    return operations + extra, moved
