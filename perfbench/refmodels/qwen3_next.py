"""Qwen3-Next's hybrid decoder block, as one chip's share of an expert group.

Source: https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json
(``model_type`` qwen3_next). ``sizes`` is the configuration file's group of
that name: the published widths, with the depth, the experts held here and
the vocabulary rows cut as the file states. Plain ``jax.numpy``: no kernels,
no chunked algebra, no grouped products. It imports nothing of the program.

Notation: d = ``hidden_size``, eps = ``rms_norm_eps``, sigma the logistic
function, SiLU(x) = x sigma(x).

    RMSNorm0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)        w initialised 0

Layer i of ``num_hidden_layers``, on the residual stream x [B, S, d]:

    x <- x + Mixer_i(RMSNorm0(x; w_in))
    x <- x + MoE_i(RMSNorm0(x; w_post))

Mixer_i is gated attention when (i + 1) mod ``full_attention_interval`` = 0
and Gated DeltaNet otherwise (3 : 1 at the published interval of 4).

Gated DeltaNet (H_k = ``linear_num_key_heads``, H_v = ``linear_num_value_heads``,
d_k, d_v the two ``linear_*_head_dim``, r = H_v / H_k):

    [q | k | v | z] = x W_qkvz        widths H_k d_k, H_k d_k, H_v d_v, H_v d_v
    [b | a]         = x W_ba          widths H_v, H_v
    [q | k | v]    <- SiLU(conv(q | k | v))      causal, depthwise, kernel
                      ``linear_conv_kernel_dim``, no bias: y_t = sum_i c_i x_{t-K+1+i}
    per head:  q <- q / sqrt(sum q^2 + 1e-6) / sqrt(d_k),  k <- k / sqrt(sum k^2 + 1e-6)
               value head h reads key head h // r             (repeat-interleave)
    beta_t = sigma(b_t),  g_t = -exp(A_log) softplus(a_t + dt_bias)    per value head
    S_0 = 0 in R^{d_k x d_v};  for t = 1..S:
        S     <- exp(g_t) S
        delta  = beta_t (v_t - S^T k_t)
        S     <- S + k_t delta^T
        o_t    = S^T q_t
    y = (RMSNorm(o_t; w_g) * SiLU(z_t)) W_out      RMSNorm over d_v, weight w_g
                                                   initialised 1, not zero-centred

Gated attention (H = ``num_attention_heads``, H_kv = ``num_key_value_heads``,
D = ``head_dim``, R = ``partial_rotary_factor`` * D rotary dimensions):

    [q_h | gate_h]_{h < H} = x W_q        per head D + D
    k = x W_k,  v = x W_v                 H_kv heads of D
    q <- RMSNorm0(q; w_qn),  k <- RMSNorm0(k; w_kn)     per head, over D
    rotary embedding, rotate-half form, theta = ``rope_theta``, on the first
    R of each head's D dimensions, positions 0..S-1
    attn = softmax(q k^T / sqrt(D) + causal mask) v     head h reads key head h // (H / H_kv)
    y = (attn * sigma(gate)) W_o

MoE (E = ``num_experts``, top = ``num_experts_per_tok``, held experts
``expert_offset`` .. ``expert_offset`` + ``experts_held`` - 1):

    p = softmax_f32(x W_r) over all E;  the ``top`` largest, renormalised to sum 1
    E_j(x) = (SiLU(x W_gate,j) * x W_up,j) W_down,j
    y = sum_{j in top and held} p~_j E_j(x)  +  sigma(x w_s) E_shared(x)

What the experts held elsewhere would add is left out (the configuration's
deployment: ``expert_parallel`` chips share each layer's experts, and on one
chip the layer runs without its exchange). No token is dropped.

Model: embedding over ``vocab_rows`` rows -> the layers -> RMSNorm0 -> an
untied head over the same rows -> mean cross-entropy over all positions.

Departures from the published model, each stated in the configuration's
``assumed`` too: no multi-token-prediction module; no auxiliary
load-balancing loss; N(0, 0.02) for matrices and the embedding
(``initializer_range`` is not among the catalog's keys), the depthwise
convolution U(-1/2, 1/2) (PyTorch's Conv1d default at fan-in 4),
A_log = log U(1e-4, 16), dt_bias = softplus^-1(exp(U(log 0.001, log 0.1)));
the column order inside W_qkvz and W_ba is this file's own ([q | k | v | z],
[b | a], heads contiguous); each window of ``seq_len`` tokens is an
independent sequence from position 0.

Precision: parameters float32; matrix products take ``dtype`` inputs and
give ``dtype`` outputs (float32 accumulation on the chip); the residual
stream, the norms, the router (logits at the highest matmul precision,
softmax, top-k), the softmax of attention, the recurrence and the loss are
float32. The recurrence is the per-token one above, written with sums and
products (no matmul, so nothing rounds to bfloat16 on the chip), scanned in
blocks of ``BLOCK`` tokens with each block rematerialised: 4.6 s a step on
the chip, 148 s for a run's 32 steps (chip run, PR 26). The issue allows a
chunked form above 120 s; one was tried and took 6 GB more at its worst
point than the chip has beside the step's eight flat vectors of N, so the
plain recurrence stays. Attention runs one sequence and one key head at a
time; each layer is under ``jax.checkpoint``.
"""

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

BLOCK = 64
F32 = jnp.float32


# ----------------------------------------------------------------- pieces
def dense(x, w, dtype):
    return jnp.dot(x.astype(dtype), w.astype(dtype))


def rounded(x, dtype):
    """x rounded to ``dtype`` and held in float32: a product of two such
    takes ``dtype`` inputs and accumulates in float32 on the chip (one
    bfloat16 pass at the default precision), and the CPU's float32 product
    of the same values runs where its bfloat16 one is not implemented."""
    return x.astype(dtype).astype(F32)


def rms_norm0(x, w, eps):
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def normal(std=0.02):
    return nn.initializers.normal(std)


def a_log_init(key, shape, dtype=F32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-4, 16.0))


def dt_bias_init(key, shape, dtype=F32):
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))           # softplus^-1(dt)


def conv_init(key, shape, dtype=F32):
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def causal_conv(x, kernel):
    """y_t = sum_i kernel[i] * x_{t-K+1+i}; x [B, S, C] float32, kernel [K, C]."""
    width, length = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(kernel[i] * padded[:, i:i + length] for i in range(width))


def delta_rule(q, k, v, g, beta):
    """The per-token recurrence. q, k [B, S, H, d_k], v [B, S, H, d_v],
    g, beta [B, S, H], all float32. Returns o [B, S, H, d_v]."""
    batch, length, heads, d_k = q.shape
    d_v = v.shape[-1]
    pad = -length % BLOCK
    if pad:
        # A padded token leaves the state alone: no decay, nothing written.
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    blocks = (length + pad) // BLOCK

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        read = jnp.sum(state * k_t[..., :, None], axis=-2)          # S^T k
        delta = beta_t[..., None] * (v_t - read)
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.sum(state * q_t[..., :, None], axis=-2)   # S^T q

    @jax.checkpoint
    def block(state, xs):
        return lax.scan(token, state, xs)

    def by_time(a):          # [B, S, ...] -> [blocks, BLOCK, B, ...]
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((blocks, BLOCK) + a.shape[1:])

    state = jnp.zeros((batch, heads, d_k, d_v), F32)
    _, out = lax.scan(block, state, tuple(map(by_time, (q, k, v, g, beta))))
    out = out.reshape((blocks * BLOCK,) + out.shape[2:])[:length]
    return jnp.moveaxis(out, 0, 1)


def rotary(x, theta, rotary_dims):
    """Rotate-half rotary embedding on the first ``rotary_dims`` of the last
    axis; x [B, S, H, D] float32, positions 0..S-1."""
    length, half = x.shape[1], rotary_dims // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) * 2.0 / rotary_dims))
    angle = jnp.arange(length, dtype=F32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    rot, rest = x[..., :rotary_dims], x[..., rotary_dims:]
    turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], -1)
    return jnp.concatenate(
        [rot * jnp.cos(angle) + turned * jnp.sin(angle), rest], -1)


def causal_attention(q, k, v, dtype):
    """q [B, S, H, D], k, v [B, S, H_kv, D] float32 -> [B, S, H, D] float32.
    One sequence and one key head (with its H / H_kv query heads) at a
    time, each rematerialised in the backward pass."""
    batch, length, heads, dim = q.shape
    kv_heads = k.shape[2]
    group = heads // kv_heads
    q = q.reshape(batch, length, kv_heads, group, dim)
    mask = jnp.tril(jnp.ones((length, length), bool))

    @jax.checkpoint
    def one(args):
        q1, k1, v1 = args                  # [S, group, D], [S, D], [S, D]
        scores = jnp.einsum("sgd,td->gst", rounded(q1, dtype),
                            rounded(k1, dtype)) / math.sqrt(dim)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("gst,td->sgd", rounded(probs, dtype),
                          rounded(v1, dtype))

    flat = lambda a: jnp.moveaxis(a, 2, 1).reshape((batch * kv_heads,) + (length,) + a.shape[3:])
    out = lax.map(one, (flat(q), flat(k), flat(v)))
    out = out.reshape(batch, kv_heads, length, group, dim)
    return jnp.moveaxis(out, 1, 2).reshape(batch, length, heads, dim)


def expert(x, gate, up, down, dtype):
    hidden = jax.nn.silu(dense(x, gate, dtype).astype(F32)) \
        * dense(x, up, dtype).astype(F32)
    return dense(hidden, down, dtype)


def route(x, router, top, normalise):
    """(probabilities of the ``top`` experts [T, top] float32, their ids)."""
    logits = jnp.dot(x.astype(F32), router, precision=lax.Precision.HIGHEST)
    values, ids = lax.top_k(jax.nn.softmax(logits, axis=-1), top)
    if normalise:
        values = values / jnp.sum(values, -1, keepdims=True)
    return values, ids


# ------------------------------------------------------------------ modules
class GatedDeltaNet(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        s, dtype = self.sizes, self.dtype
        d = s["hidden_size"]
        h_k, h_v = s["linear_num_key_heads"], s["linear_num_value_heads"]
        d_k, d_v = s["linear_key_head_dim"], s["linear_value_head_dim"]
        key_w, val_w = h_k * d_k, h_v * d_v
        w_qkvz = self.param("in_proj_qkvz", normal(), (d, 2 * key_w + 2 * val_w), F32)
        w_ba = self.param("in_proj_ba", normal(), (d, 2 * h_v), F32)
        conv = self.param("conv", conv_init,
                          (s["linear_conv_kernel_dim"], 2 * key_w + val_w), F32)
        a_log = self.param("A_log", a_log_init, (h_v,), F32)
        dt_bias = self.param("dt_bias", dt_bias_init, (h_v,), F32)
        w_g = self.param("norm", nn.initializers.ones, (d_v,), F32)
        w_out = self.param("out_proj", normal(), (val_w, d), F32)

        batch, length = x.shape[:2]
        qkvz = dense(x, w_qkvz, dtype)
        ba = dense(x, w_ba, dtype).astype(F32)
        qkv = jax.nn.silu(causal_conv(qkvz[..., :2 * key_w + val_w].astype(F32), conv))
        z = qkvz[..., 2 * key_w + val_w:].astype(F32).reshape(batch, length, h_v, d_v)
        heads = lambda a, n, w: a.reshape(batch, length, n, w)
        q = heads(qkv[..., :key_w], h_k, d_k)
        k = heads(qkv[..., key_w:2 * key_w], h_k, d_k)
        v = heads(qkv[..., 2 * key_w:], h_v, d_v)
        unit = lambda a: a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
        q = jnp.repeat(unit(q) / math.sqrt(d_k), h_v // h_k, axis=2)
        k = jnp.repeat(unit(k), h_v // h_k, axis=2)
        beta = jax.nn.sigmoid(ba[..., :h_v])
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., h_v:] + dt_bias)
        o = delta_rule(q, k, v, g, beta)
        o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + s["rms_norm_eps"]) * w_g
        gated = (o * jax.nn.silu(z)).reshape(batch, length, val_w)
        return dense(gated, w_out, dtype)


class GatedAttention(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        s, dtype = self.sizes, self.dtype
        d, dim = s["hidden_size"], s["head_dim"]
        heads, kv_heads = s["num_attention_heads"], s["num_key_value_heads"]
        w_q = self.param("q_proj", normal(), (d, heads * 2 * dim), F32)
        w_k = self.param("k_proj", normal(), (d, kv_heads * dim), F32)
        w_v = self.param("v_proj", normal(), (d, kv_heads * dim), F32)
        w_qn = self.param("q_norm", nn.initializers.zeros, (dim,), F32)
        w_kn = self.param("k_norm", nn.initializers.zeros, (dim,), F32)
        w_o = self.param("o_proj", normal(), (heads * dim, d), F32)

        batch, length = x.shape[:2]
        eps = s["rms_norm_eps"]
        rotary_dims = int(dim * s["partial_rotary_factor"])
        qg = dense(x, w_q, dtype).reshape(batch, length, heads, 2 * dim)
        q, gate = qg[..., :dim], qg[..., dim:].astype(F32)
        k = dense(x, w_k, dtype).reshape(batch, length, kv_heads, dim)
        v = dense(x, w_v, dtype).reshape(batch, length, kv_heads, dim).astype(F32)
        q = rotary(rms_norm0(q, w_qn, eps), s["rope_theta"], rotary_dims)
        k = rotary(rms_norm0(k, w_kn, eps), s["rope_theta"], rotary_dims)
        out = causal_attention(q, k, v, dtype) * jax.nn.sigmoid(gate)
        return dense(out.reshape(batch, length, heads * dim), w_o, dtype)


class SparseMoE(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        s, dtype = self.sizes, self.dtype
        d, width = s["hidden_size"], s["moe_intermediate_size"]
        shared_w = s["shared_expert_intermediate_size"]
        held, offset = s["experts_held"], s["expert_offset"]
        router = self.param("router", normal(), (d, s["num_experts"]), F32)
        gate = self.param("experts_gate", normal(), (held, d, width), F32)
        up = self.param("experts_up", normal(), (held, d, width), F32)
        down = self.param("experts_down", normal(), (held, width, d), F32)
        s_gate = self.param("shared_gate_proj", normal(), (d, shared_w), F32)
        s_up = self.param("shared_up_proj", normal(), (d, shared_w), F32)
        s_down = self.param("shared_down_proj", normal(), (shared_w, d), F32)
        w_s = self.param("shared_gate", normal(), (d, 1), F32)

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
        share = jax.nn.sigmoid(dense(x, w_s, dtype).astype(F32))
        y = y + share * expert(x, s_gate, s_up, s_down, dtype).astype(F32)
        return y.reshape(shape)


class Layer(nn.Module):
    sizes: dict
    dtype: jnp.dtype
    attention: bool

    @nn.compact
    def __call__(self, x):
        s = self.sizes
        w_in = self.param("input_norm", nn.initializers.zeros,
                          (s["hidden_size"],), F32)
        w_post = self.param("post_norm", nn.initializers.zeros,
                            (s["hidden_size"],), F32)
        mixer = (GatedAttention if self.attention else GatedDeltaNet)(
            s, self.dtype, name="mixer")
        x = x + mixer(rms_norm0(x, w_in, s["rms_norm_eps"])).astype(F32)
        moe = SparseMoE(s, self.dtype, name="moe")
        return x + moe(rms_norm0(x, w_post, s["rms_norm_eps"]))


class Qwen3Next(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, tokens, train=False):
        """The final hidden states [B, S, d] float32 and the head's matrix:
        ``loss`` applies it a sequence at a time."""
        s = self.sizes
        table = self.param("embed", normal(), (s["vocab_rows"], s["hidden_size"]), F32)
        x = table[tokens]
        for i in range(s["num_hidden_layers"]):
            attention = (i + 1) % s["full_attention_interval"] == 0
            x = nn.remat(Layer)(s, self.dtype, attention, name=f"layer_{i}")(x)
        w_final = self.param("final_norm", nn.initializers.zeros,
                             (s["hidden_size"],), F32)
        head = self.param("head", normal(), (s["hidden_size"], s["vocab_rows"]), F32)
        return rms_norm0(x, w_final, s["rms_norm_eps"]), head


# ------------------------------------------------- what reference.py calls
def build(sizes, dtype):
    # Flax freezes a dict attribute and hashes it: scalars only.
    scalars = {k: v for k, v in sizes.items()
               if isinstance(v, (bool, int, float, str))}
    return Qwen3Next(scalars, dtype), jnp.zeros((1, sizes["seq_len"]), jnp.int32)


def initial_carry(sizes, batch, dtype):
    return ()


def token_losses(hidden, head, targets, dtype):
    """Cross-entropy of every position, float32, a sequence at a time."""

    @jax.checkpoint
    def one(args):
        h, t = args
        logits = jnp.dot(rounded(h, dtype), rounded(head, dtype))
        picked = jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    return lax.map(one, (hidden, targets))


def loss(module, variables, carry, batch, key, train):
    hidden, head = module.apply(variables, batch["tokens"], train)
    ce = token_losses(hidden, head, batch["targets"], module.dtype).mean()
    return ce, None, carry


# ------------------------------------------------------ work, from shapes
def forward_macs(sizes):
    """Multiply-accumulates of one ``seq_len``-token sequence's forward
    pass, from the shapes. Counted: every matrix product of the layers and
    the head; causal attention at S/2 keys a query; the delta rule's four
    d_k x d_v products a token and head (decay aside: S^T k, k delta^T,
    S^T q); the routed experts at the uniform expected load,
    ``num_experts_per_tok`` * ``experts_held`` / ``num_experts`` of a slot
    a token and layer; the shared expert for every token. Not counted: the
    embedding (a lookup), norms, the convolution's 4 taps, softmax,
    elementwise gates."""
    s = sizes
    d, length = s["hidden_size"], s["seq_len"]
    h_k, h_v = s["linear_num_key_heads"], s["linear_num_value_heads"]
    d_k, d_v = s["linear_key_head_dim"], s["linear_value_head_dim"]
    gdn = (d * (2 * h_k * d_k + 2 * h_v * d_v) + d * 2 * h_v
           + h_v * d_v * d + 3 * h_v * d_k * d_v)
    heads, kv, dim = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
    attn = (d * heads * 2 * dim + 2 * d * kv * dim + heads * dim * d
            + 2 * heads * dim * (length // 2))
    slots = s["num_experts_per_tok"] * s["experts_held"] / s["num_experts"]
    moe = (d * s["num_experts"] + slots * 3 * d * s["moe_intermediate_size"]
           + 3 * d * s["shared_expert_intermediate_size"] + d)
    layers = s["num_hidden_layers"]
    full = layers // s["full_attention_interval"]
    per_token = (layers - full) * gdn + full * attn + layers * moe \
        + d * s["vocab_rows"]
    return int(per_token * length)


def gdn_scan_work(sizes, batch, chunk=64):
    """(operations, bytes) of the delta rule of every Gated DeltaNet layer
    in one training step over ``batch`` sequences, for its roofline.

    Operations: the chunked form's matrix products at one pass each,
    forward once and backward twice (recomputation not counted), 2 a
    multiply-accumulate. A chunk of C tokens of one value head costs
    k k^T and q k^T (2 C^2 d_k), the triangular system applied to beta v
    and beta k (C^2 (d_k + d_v), counted whole), W S, q S and the state's
    update (3 C d_k d_v), and the intra-chunk output (C^2 d_v).

    Bytes: the least a chunked pass must move, so that the share cannot
    read over 100%: q and k once a key head, v and o once a value head, g
    and beta, float32, read or written once forward, and each with its
    gradient once more backward (3 passes in all)."""
    s = sizes
    h_k, h_v = s["linear_num_key_heads"], s["linear_num_value_heads"]
    d_k, d_v = s["linear_key_head_dim"], s["linear_value_head_dim"]
    layers = s["num_hidden_layers"] \
        - s["num_hidden_layers"] // s["full_attention_interval"]
    tokens = batch * s["seq_len"]
    per_chunk = (2 * chunk * chunk * d_k + chunk * chunk * (d_k + d_v)
                 + 3 * chunk * d_k * d_v + chunk * chunk * d_v)
    macs = layers * h_v * (tokens // chunk) * per_chunk
    floats = layers * tokens * (h_k * 2 * d_k + h_v * (2 * d_v + 2))
    return 3 * 2 * macs, 3 * 4 * floats
