"""Kimi-Linear-48B-A3B's hybrid decoder block, as one chip's share of an
expert group.

Source: https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json
(``model_type`` kimi_linear; the mixer is the Kimi Delta Attention of "Kimi
Linear: An Expressive, Efficient Attention Architecture", arXiv:2510.26692;
the catalog: "KDA gated delta-rule linear (conv4); MLA NoPE global, 3 KDA :
1 MLA", "256 experts, top-8, 1 shared"). ``sizes`` is the configuration
file's group of that name: the published widths, with the depth, the experts
held here and the vocabulary rows cut as the file states. Plain
``jax.numpy``: no kernels, no chunked algebra, no grouped products; the
delta rule token by token, causality a mask over [block, keys] rows, the
experts a mask over the held ones. It imports nothing of the program.

Notation: d = ``hidden_size``, eps = ``rms_norm_eps``, sigma the logistic
function, SiLU(x) = x sigma(x), sg = stop_gradient.

    RMSNorm0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)        w initialised 0

Model: x_0 = E[tokens], E over ``vocab_rows`` rows -> the layers ->
RMSNorm0 -> an untied head over the same rows; the objective is the mean
cross-entropy over all positions.

Layer i of ``num_hidden_layers`` on the residual stream x [B, S, d], float32;
its mixer is the i-th of ``layer_kinds`` ("kda" or "mla", comma-separated:
the published ``linear_attn_config`` lists layers 5, 6, 7 under
``kda_layers`` and 8 under ``full_attn_layers``, which are the four held):

    x <- x + Mixer_i(RMSNorm0(x; w_in))
    x <- x + MoE(RMSNorm0(x; w_pre_mlp))

Kimi Delta Attention (H = ``kda_num_heads`` heads of d_k = ``kda_head_dim``
channels, keys and values alike; K = ``kda_conv_kernel_size``; r =
``kda_gate_rank``), on h = the normed x:

    [q~ | k~ | v~] = h W_qkv                    each H d_k wide, heads contiguous
    [q | k | v] = SiLU(conv(q~ | k~ | v~))      causal, depthwise, K taps a channel,
                                                no bias: y_t = sum_i c_i x_{t-K+1+i},
                                                every sequence from position 0
    per head:  q <- q / sqrt(sum q^2 + 1e-6) / sqrt(d_k),  k <- k / sqrt(sum k^2 + 1e-6)
    [f | z | b] = h W_fzb                       widths r, r, H (the low-rank gates'
                                                first matrices and beta's, one leaf)
    g_t = -exp(A_log[head]) softplus(f_t W_f + dt_bias)    in R^{H x d_k}, <= 0:
                                                a log decay for EVERY key channel
    beta_t = sigma(b_t)                         in R^H
    S_0 = 0 in R^{d_k x d_k} per head;  for t = 1..S:
        S      <- Diag(exp(g_t)) S              row d of S decays by exp(g_t[d])
        delta   = beta_t (v_t - S^T k_t)
        S      <- S + k_t delta^T
        o_t     = S^T q_t
      (S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T)
    y = (RMSNorm(o_t; w_g) * sigma(z_t W_z)) W_out    RMSNorm over d_k, weight w_g
                                                initialised 1, not zero-centred

Latent attention without position encoding (``mla_use_nope``; H =
``num_attention_heads``, r = ``kv_lora_rank``, n = ``qk_nope_head_dim``, p =
``qk_rope_head_dim``, D_v = ``v_head_dim``; ``q_lora_rank`` null), on h:

    q_t = h_t W_q,              per head i:  q_{t,i} = [q^nope_{t,i} (n) | q^pe_{t,i} (p)]
    [c_t (r) | k^pe_t (p)] = h_t W_kva        one latent and ONE p-wide key a token
    per head i:  [k^nope_{s,i} (n) | v_{s,i} (D_v)] = (RMSNorm0_r(c_s; w_kvn) W_kvb)_i
    k_{s,i} = [k^nope_{s,i} | k^pe_s]         NO rotary on q^pe or k^pe: the p-wide
                                              parts are kept and left unturned
    o_{t,i} = sum_{s <= t} softmax_s(q_{t,i} . k_{s,i} / sqrt(n + p)) v_{s,i}
    y_t = (concat_i o_{t,i}) W_o

MoE (E = ``num_experts``, top = ``num_experts_per_token``, width
``moe_intermediate_size``; held experts ``expert_offset`` ..
``expert_offset`` + ``experts_held`` - 1; ``num_shared_experts`` shared
experts as one MLP of that many widths, no gate), with b [E] the layer's
``e_score_correction_bias``:

    s = sigma_f32(m W_r) over all E           (``moe_router_activation_func`` sigmoid)
    chosen = the ``top`` largest of s + b     (``num_expert_group`` = ``topk_group`` = 1:
                                               plain top-k)
    w_e = s_e / (sum_{e in chosen} s_e + 1e-20) * ``routed_scaling_factor``
                                              (``moe_renormalize``)
    E_e(m) = (SiLU(m W_gate,e) * m W_up,e) W_down,e
    y = E_shared(m) + sum_{e in chosen and held} w_e E_e(m)

b is no parameter: it takes no gradient and is not in ``params``. It lives
in the flax collection ``batch_stats``, starts at zero, and a training step's
forward pass moves it by this step's own counts c_e = tokens whose
``chosen`` holds e (all E, held or not):

    delta = ``load_balance_coeff`` * sign(mean(c) - c),   b <- b + delta - mean(delta)

What the experts held elsewhere would add is left out (the configuration's
deployment: ``expert_parallel`` chips share each layer's experts, and on one
chip the layer runs without its exchange). No token is dropped.

Departures from the published model, each stated in the configuration's
``assumed`` too: the gates' low rank r = ``kda_head_dim`` and their second
matrices without bias (the decay's has ``dt_bias``); A_log = log U(1, 16) a
head, dt_bias = softplus^-1(exp(U(log 0.001, log 0.1))) a channel; q's
1 / sqrt(d_k); a sigmoid in the output gate; 1e-6 in the unit norms; W_q~,
W_k~, W_v~ one leaf and W_f-down, W_z-down, W_beta one leaf (the column
order is this file's own); the convolution U(-1/2, 1/2); N(0, 0.02) for
matrices and the embedding; the bias's update rule and its rate (Wang et al.,
arXiv:2408.15664, at DeepSeek-V3's 0.001), centred; norms stored
zero-centred; no dropout, no multi-token prediction; each window of
``seq_len`` tokens an independent sequence from position 0; no leading dense
layer (``first_k_dense_replace`` 0: it lies on another pipeline stage).

Precision: parameters float32; a projection takes ``dtype`` inputs and
gives a ``dtype`` output; q . k and a v take ``dtype`` inputs and accumulate
in float32 (at float32 the highest matmul precision); the residual stream,
the norms, the convolution, the gates' softplus and sigmoids, the delta
rule's state, decay and every product of its recurrence (sums and products,
no matmul, so nothing rounds to bfloat16 on the chip), the softmax, the
router (logits at the highest matmul precision) and the loss are float32.

Memory (``perfbench/reference.py``'s step holds 27 B a parameter beside
this): a KDA mixer runs ``SEGMENT`` tokens at a time, each segment under a
checkpoint with the state carried from one to the next, and inside it the
recurrence in blocks of ``BLOCK`` tokens, each rematerialised; the latent
attention a head at a time, each under a checkpoint, a block of ``QUERIES``
queries against every key of the sequence, what a query does not see masked,
not skipped; the feed-forward and the loss ``CHUNK`` and ``LOSS_ROWS``
tokens at a time. Every layer is rematerialised in the backward pass.
"""

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

# Tokens of a sequence whose KDA mixer intermediates exist at once.
SEGMENT = 1024
# Tokens of the recurrence whose per-token states exist at once.
BLOCK = 16
# Queries of one head whose scores over the sequence's keys exist at once.
QUERIES = 512
# Tokens whose feed-forward intermediates exist at once.
CHUNK = 2048
# Tokens whose logits over the vocabulary's rows exist at once in the loss.
LOSS_ROWS = 2048
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


def a_log_init(key, shape, dtype=F32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def dt_bias_init(key, shape, dtype=F32):
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))           # softplus^-1(dt)


def conv_init(key, shape, dtype=F32):
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def kinds_of(sizes):
    return tuple(sizes["layer_kinds"].split(","))


def by_chunks(fn, size, *rows):
    """``fn`` over ``size`` rows at a time of arrays [T, ...] (all of them
    where T is no multiple), each chunk under a checkpoint."""
    total = rows[0].shape[0]
    size = size if total % size == 0 else total
    cut = lambda a: a.reshape((total // size, size) + a.shape[1:])
    out = lax.map(jax.checkpoint(lambda args: fn(*args)),
                  tuple(map(cut, rows)))
    return out.reshape((total,) + out.shape[2:])


# ----------------------------------------------------- Kimi Delta Attention
def delta_rule(q, k, v, g, beta, state):
    """The per-token recurrence from ``state`` [B, H, d_k, d_v]: q, k, g
    [B, T, H, d_k], v [B, T, H, d_v], beta [B, T, H], all float32, T whole
    blocks of ``BLOCK`` or fewer than one. Returns (the state after, o
    [B, T, H, d_v])."""
    length = q.shape[1]
    block = BLOCK if length % BLOCK == 0 else length

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[..., :, None]          # row d by e^{g[d]}
        read = jnp.sum(state * k_t[..., :, None], axis=-2)          # S^T k
        delta = beta_t[..., None] * (v_t - read)
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.sum(state * q_t[..., :, None], axis=-2)   # S^T q

    @jax.checkpoint
    def run(state, xs):
        return lax.scan(token, state, xs)

    def by_time(a):          # [B, T, ...] -> [blocks, block, B, ...]
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((length // block, block) + a.shape[1:])

    state, out = lax.scan(run, state, tuple(map(by_time, (q, k, v, g, beta))))
    return state, jnp.moveaxis(out.reshape((length,) + out.shape[2:]), 0, 1)


class KimiDeltaAttention(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, h):
        s, dtype = self.sizes, self.dtype
        d, heads, d_k = s["hidden_size"], s["kda_num_heads"], s["kda_head_dim"]
        taps, rank = s["kda_conv_kernel_size"], s["kda_gate_rank"]
        width = heads * d_k
        w_qkv = self.param("in_proj_qkv", normal(), (d, 3 * width), F32)
        w_fzb = self.param("in_proj_fzb", normal(), (d, 2 * rank + heads), F32)
        conv = self.param("conv", conv_init, (taps, 3 * width), F32)
        w_f = self.param("f_proj", normal(), (rank, width), F32)
        dt_bias = self.param("dt_bias", dt_bias_init, (width,), F32)
        a_log = self.param("A_log", a_log_init, (heads,), F32)
        w_z = self.param("z_proj", normal(), (rank, width), F32)
        w_g = self.param("norm", nn.initializers.ones, (d_k,), F32)
        w_out = self.param("out_proj", normal(), (width, d), F32)

        batch, length = h.shape[:2]
        if self.is_initializing():
            return jnp.zeros(h.shape, dtype)
        size = SEGMENT if length % SEGMENT == 0 else length
        halo = taps - 1
        # Every segment's tokens under the ``halo`` tokens before them
        # (zeros before a sequence's first: their projection is the
        # convolution's zero padding, there being no bias).
        padded = jnp.pad(h, ((0, 0), (halo, 0), (0, 0)))
        windows = jnp.stack([padded[:, at:at + size + halo]
                             for at in range(0, length, size)])
        unit = lambda a: a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

        @jax.checkpoint
        def segment(state, window):
            qkv = dense(window, w_qkv, dtype).astype(F32)
            qkv = jax.nn.silu(sum(conv[i] * qkv[:, i:i + size]
                                  for i in range(taps)))
            q, k, v = (qkv[..., i * width:(i + 1) * width].reshape(
                batch, size, heads, d_k) for i in range(3))
            q, k = unit(q) / math.sqrt(d_k), unit(k)
            fzb = dense(window[:, halo:], w_fzb, dtype)
            f, z, b = (fzb[..., :rank], fzb[..., rank:2 * rank],
                       fzb[..., 2 * rank:])
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                (dense(f, w_f, dtype).astype(F32) + dt_bias).reshape(
                    batch, size, heads, d_k))
            beta = jax.nn.sigmoid(b.astype(F32))
            state, o = delta_rule(q, k, v, g, beta, state)
            o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + s["rms_norm_eps"]) * w_g
            gate = jax.nn.sigmoid(dense(z, w_z, dtype).astype(F32)).reshape(
                batch, size, heads, d_k)
            return state, dense((o * gate).reshape(batch, size, width),
                                w_out, dtype)

        state = jnp.zeros((batch, heads, d_k, d_k), F32)
        _, out = lax.scan(segment, state, windows)
        return jnp.moveaxis(out, 0, 1).reshape(batch, length, d)


# -------------------------------------------------------- latent attention
def attend(q_b, k_h, v_h, seen, dtype):
    """A block's queries q_b [Q, D] of one head over its keys k_h [S, D],
    v_h [S, D_v], ``seen`` [Q, S] bool -> [Q, D_v] float32."""
    logits = product("qd,sd->qs", q_b, k_h, dtype) / math.sqrt(q_b.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    return product("qs,sd->qd", probs, v_h, dtype)


class LatentAttention(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, h):
        s, dtype = self.sizes, self.dtype
        d, heads, rank = (s["hidden_size"], s["num_attention_heads"],
                          s["kv_lora_rank"])
        nope, rope, value = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                             s["v_head_dim"])
        w_q = self.param("q_proj", normal(), (d, heads * (nope + rope)), F32)
        w_kva = self.param("kv_a_proj", normal(), (d, rank + rope), F32)
        w_kvn = self.param("kv_a_norm", nn.initializers.zeros, (rank,), F32)
        w_kvb = self.param("kv_b_proj", normal(),
                           (rank, heads * (nope + value)), F32)
        w_o = self.param("o_proj", normal(), (heads * value, d), F32)

        length = h.shape[1]
        if self.is_initializing():
            return jnp.zeros(h.shape, dtype)
        block = QUERIES if length % QUERIES == 0 else length
        # A head's columns of W_q and W_kvb: [H, d, n + p], [H, r, n + D_v].
        by_head = lambda w, cols: jnp.moveaxis(
            w.reshape(w.shape[0], heads, cols), 1, 0)

        def sequence(h1):
            latent = dense(h1, w_kva, dtype)
            c_kv = rms_norm0(latent[:, :rank], w_kvn, s["rms_norm_eps"])
            k_pe = latent[:, rank:].astype(F32)

            @jax.checkpoint
            def head(args):
                w_q_i, w_kvb_i = args
                q = dense(h1, w_q_i, dtype).astype(F32)        # [S, n + p]
                kv = dense(c_kv, w_kvb_i, dtype).astype(F32)   # [S, n + D_v]
                k = jnp.concatenate([kv[:, :nope], k_pe], -1)
                v = kv[:, nope:]

                def one(args):
                    q_b, start = args
                    rows = start + jnp.arange(block)
                    seen = rows[:, None] >= jnp.arange(length)[None, :]
                    return attend(q_b, k, v, seen, dtype)

                out = lax.map(jax.checkpoint(one), (
                    q.reshape(-1, block, nope + rope),
                    block * jnp.arange(length // block)))
                return out.reshape(length, value)

            out = lax.map(head, (by_head(w_q, nope + rope),
                                 by_head(w_kvb, nope + value)))
            # [H, S, D_v] -> [S, H D_v]
            return dense(jnp.moveaxis(out, 0, 1).reshape(
                length, heads * value), w_o, dtype)

        return lax.map(sequence, h)


# ------------------------------------------------------------ expert layer
def expert(x, gate, up, down, dtype):
    hidden = jax.nn.silu(dense(x, gate, dtype).astype(F32)) \
        * dense(x, up, dtype).astype(F32)
    return dense(hidden, down, dtype)


def route(x, router, bias, top, normalise, scale):
    """(weights of the ``top`` experts [T, top] float32, their ids)."""
    scores = jax.nn.sigmoid(jnp.dot(x.astype(F32), router, precision=HIGHEST))
    _, ids = lax.top_k(lax.stop_gradient(scores) + bias, top)
    values = jnp.take_along_axis(scores, ids, -1)
    if normalise:
        values = values / (jnp.sum(values, -1, keepdims=True) + 1e-20)
    return values * scale, ids


def balanced(bias, counts, rate):
    """b + delta - mean(delta), delta = rate sign(mean(c) - c)."""
    counts = counts.astype(F32)
    delta = rate * jnp.sign(jnp.mean(counts) - counts)
    return bias + delta - jnp.mean(delta)


class SparseMoE(nn.Module):
    """(y, the tokens that chose each expert [E])."""
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        s, dtype = self.sizes, self.dtype
        d, width = s["hidden_size"], s["moe_intermediate_size"]
        held, offset = s["experts_held"], s["expert_offset"]
        experts = s["num_experts"]
        shared_w = s["num_shared_experts"] * width
        router = self.param("router", normal(), (d, experts), F32)
        gate = self.param("experts_gate", normal(), (held, d, width), F32)
        up = self.param("experts_up", normal(), (held, d, width), F32)
        down = self.param("experts_down", normal(), (held, width, d), F32)
        s_gate = self.param("shared_gate_proj", normal(), (d, shared_w), F32)
        s_up = self.param("shared_up_proj", normal(), (d, shared_w), F32)
        s_down = self.param("shared_down_proj", normal(), (shared_w, d), F32)
        bias = self.variable("batch_stats", "router_bias", jnp.zeros,
                             (experts,), F32)

        shape = x.shape
        x = x.reshape(-1, d)
        if s["moe_router_activation_func"] != "sigmoid":
            raise ValueError("the router scores with a sigmoid")
        weights, ids = route(x, router, bias.value, s["num_experts_per_token"],
                             s["moe_renormalize"], s["routed_scaling_factor"])
        counts = jnp.sum(ids[..., None] == jnp.arange(experts), axis=(0, 1))
        if not self.is_initializing() \
                and self.is_mutable_collection("batch_stats"):
            bias.value = balanced(bias.value, counts, s["load_balance_coeff"])

        def chunk(x, ids, weights):
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
            return y + expert(x, s_gate, s_up, s_down, dtype).astype(F32)

        return by_chunks(chunk, CHUNK, x, ids, weights).reshape(shape), counts


# ------------------------------------------------------------------ modules
class Layer(nn.Module):
    sizes: dict
    dtype: jnp.dtype
    kind: str

    @nn.compact
    def __call__(self, x):
        s = self.sizes
        d, eps = s["hidden_size"], s["rms_norm_eps"]
        w_in, w_pre_mlp = (
            self.param(name, nn.initializers.zeros, (d,), F32)
            for name in ("input_norm", "pre_mlp_norm"))
        mixer = {"kda": KimiDeltaAttention, "mla": LatentAttention}[self.kind]
        x = x + mixer(s, self.dtype, name="mixer")(
            rms_norm0(x, w_in, eps)).astype(F32)
        y, counts = SparseMoE(s, self.dtype, name="moe")(
            rms_norm0(x, w_pre_mlp, eps))
        return x + y, counts


class KimiLinear(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, tokens, train=False):
        """(the final hidden states [B, S, d] float32, the head's matrix,
        the expert layers' selection counts [layers, E]): ``loss`` applies
        the head ``LOSS_ROWS`` tokens at a time."""
        s = self.sizes
        d = s["hidden_size"]
        table = self.param("embed", normal(), (s["vocab_rows"], d), F32)
        x = table[tokens]
        counts = []
        for i, kind in enumerate(kinds_of(s)):
            x, count = nn.remat(Layer)(
                s, self.dtype, kind, name=f"layer_{i}")(x)
            counts.append(count)
        w_final = self.param("final_norm", nn.initializers.zeros, (d,), F32)
        head = self.param("head", normal(), (d, s["vocab_rows"]), F32)
        return rms_norm0(x, w_final, s["rms_norm_eps"]), head, jnp.stack(counts)


# ------------------------------------------------- what reference.py calls
def build(sizes, dtype):
    # Flax freezes a dict attribute and hashes it: scalars only.
    scalars = {k: v for k, v in sizes.items()
               if isinstance(v, (bool, int, float, str))}
    if len(kinds_of(scalars)) != scalars["num_hidden_layers"]:
        raise ValueError("layer_kinds names every layer's mixer")
    return (KimiLinear(scalars, dtype),
            jnp.zeros((1, sizes["seq_len"]), jnp.int32))


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


def loss(module, variables, carry, batch, key, train):
    """(mean cross-entropy, the model state after the step's forward pass:
    the balancing biases moved by its counts when ``train``, else None,
    the carry)."""
    if train and "batch_stats" in variables:
        (hidden, head, _), moved = module.apply(
            variables, batch["tokens"], True, mutable=["batch_stats"])
        state = moved["batch_stats"]
    else:
        hidden, head, _ = module.apply(variables, batch["tokens"], train)
        state = None
    ce = token_losses(hidden, head, batch["targets"], module.dtype).mean()
    return ce, state, carry


# ------------------------------------------------------ work, from shapes
def layer_counts(sizes):
    """(KDA layers, latent-attention layers)."""
    kinds = kinds_of(sizes)
    return kinds.count("kda"), kinds.count("mla")


def causal_pairs(sizes):
    """sum over t of (t + 1): the pairs one sequence's attention holds."""
    return sizes["seq_len"] * (sizes["seq_len"] + 1) // 2


def _kda_projection_macs(sizes):
    """A KDA mixer's projection multiply-accumulates a token: W_qkv, the
    gates' and beta's first matrices, the two gates' second, W_out."""
    s = sizes
    width = s["kda_num_heads"] * s["kda_head_dim"]
    rank = s["kda_gate_rank"]
    return s["hidden_size"] * (3 * width + 2 * rank + s["kda_num_heads"]) \
        + 2 * rank * width + width * s["hidden_size"]


def _mla_projection_macs(sizes):
    """The latent mixer's projection multiply-accumulates a token: W_q of
    H (n + p) columns, W_kva of r + p, W_kvb from r to H (n + D_v), W_o
    from H D_v."""
    s = sizes
    heads, nope, rope, value, rank = (
        s["num_attention_heads"], s["qk_nope_head_dim"],
        s["qk_rope_head_dim"], s["v_head_dim"], s["kv_lora_rank"])
    return s["hidden_size"] * (heads * (nope + rope) + rank + rope
                               + heads * value) \
        + rank * heads * (nope + value)


def _pair_macs(sizes):
    """A pair's multiply-accumulates over the heads: q . k over n + p, a v
    over D_v."""
    s = sizes
    return s["num_attention_heads"] * (
        s["qk_nope_head_dim"] + s["qk_rope_head_dim"] + s["v_head_dim"])


def forward_macs(sizes):
    """Multiply-accumulates of one ``seq_len``-token sequence's forward
    pass, from the shapes: the model's mathematics, whatever form a program
    computes it in. Counted: every matrix product of the layers and the
    head; the delta rule's three d_k x d_k products a token and head (decay
    aside: S^T k, k delta^T, S^T q); the latent attention at the pairs a
    query sees, t + 1 (H (n + p + D_v) a pair); the routed experts at the
    uniform expected load, ``num_experts_per_token`` * ``experts_held`` /
    ``num_experts`` of a slot a token and layer; the shared expert for every
    token. Not counted: the embedding (a lookup), norms, the convolution's
    taps, softmax, elementwise gates, the bias's update."""
    s = sizes
    d, length = s["hidden_size"], s["seq_len"]
    kda_layers, mla_layers = layer_counts(s)
    rule = 3 * s["kda_num_heads"] * s["kda_head_dim"] ** 2
    kda = kda_layers * length * (_kda_projection_macs(s) + rule)
    mla = mla_layers * (length * _mla_projection_macs(s)
                        + causal_pairs(s) * _pair_macs(s))
    width = s["moe_intermediate_size"]
    slots = s["num_experts_per_token"] * s["experts_held"] / s["num_experts"]
    moe = d * s["num_experts"] \
        + (s["num_shared_experts"] + slots) * 3 * d * width
    return int(kda + mla + length * ((kda_layers + mla_layers) * moe
                                     + d * s["vocab_rows"]))


def kda_scan_work(sizes, batch, chunk=64):
    """(operations, bytes) of the delta rule of every KDA layer in one
    training step over ``batch`` sequences, for its roofline: the same work
    whatever implements the stage.

    Operations: a chunked form's matrix products at one bfloat16 pass each,
    forward once and backward twice (recomputation not counted), 2 a
    multiply-accumulate. A chunk of C tokens of one head costs k k^T and
    q k^T under the channels' decay (2 C^2 d_k), the triangular system
    applied to beta v and beta k (C^2 (d_k + d_v), counted whole), W S, q S
    and the state's update (3 C d_k d_v), and the intra-chunk output
    (C^2 d_v). The decay's exponentials and the pairwise sub-blocks a
    bounded form takes are not counted: they are how, not what.

    Bytes: the least a chunked pass must move, so that the share cannot
    read over 100%: q, k, v, o and the log decay g (a number a channel: as
    wide as k) and beta, float32, read or written once forward, and each
    with its gradient once more backward (3 passes in all)."""
    s = sizes
    heads, d_k = s["kda_num_heads"], s["kda_head_dim"]
    d_v = d_k
    layers, _ = layer_counts(s)
    tokens = batch * s["seq_len"]
    per_chunk = (2 * chunk * chunk * d_k + chunk * chunk * (d_k + d_v)
                 + 3 * chunk * d_k * d_v + chunk * chunk * d_v)
    macs = layers * heads * (tokens // chunk) * per_chunk
    floats = layers * tokens * heads * (3 * d_k + 2 * d_v + 1)
    return 3 * 2 * macs, 3 * 4 * floats


def mla_attn_work(sizes, batch):
    """(operations, bytes) of every latent-attention mixer in one training
    step over ``batch`` sequences, for its roofline: the four projections
    and the softmax attention over the pairs s <= t, forward once and
    backward twice (recomputation not counted), 2 operations a
    multiply-accumulate, 4 bytes a float32 moved. Bytes, the least a
    blocked pass must move: h read and y written, q, the latent, k, v and o
    written and read once, and the projections' weights."""
    s = sizes
    heads, nope, rope, value, rank = (
        s["num_attention_heads"], s["qk_nope_head_dim"],
        s["qk_rope_head_dim"], s["v_head_dim"], s["kv_lora_rank"])
    macs = s["seq_len"] * _mla_projection_macs(s) \
        + causal_pairs(s) * _pair_macs(s)
    floats = s["seq_len"] * (
        2 * s["hidden_size"] + 2 * (rank + rope)
        + 2 * heads * (2 * (nope + rope) + 2 * value)) \
        + _mla_projection_macs(s)
    times = 3 * layer_counts(s)[1] * batch
    return times * 2 * macs, times * 4 * floats
