"""Kanana-2-30B-A3B's decoder block, as one chip's share of an expert group.

Source: https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json
(``model_type`` deepseek_v3, ``q_lora_rank`` null; the catalog: "MLA (no
q_lora)", "128 experts, top-6, 2 shared"). ``sizes`` is the configuration
file's group of that name: the published widths, with the depth, the experts
held here and the vocabulary rows cut as the file states. Plain
``jax.numpy``: no kernels, no grouped products, causality a mask over
[block, keys] rows, the experts a mask over the held ones. It imports
nothing of the program.

Notation: d = ``hidden_size``, eps = ``rms_norm_eps``, H =
``num_attention_heads``, r = ``kv_lora_rank``, n = ``qk_nope_head_dim``,
p = ``qk_rope_head_dim``, D_v = ``v_head_dim``, SiLU(x) = x sigma(x),
sg = stop_gradient, t a query position, s a key position.

    RMSNorm0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)        w initialised 0

Model: x_0 = E[tokens], E over ``vocab_rows`` rows -> the layers ->
RMSNorm0 -> an untied head over the same rows; the objective is the mean
cross-entropy over all positions.

Layer i of ``num_hidden_layers`` on the residual stream x [B, S, d]; it is
dense while i < ``first_k_dense_replace``:

    x <- x + Attention(RMSNorm0(x; w_in))
    x <- x + FF(RMSNorm0(x; w_pre_mlp))

Attention (multi-head latent attention), on a = the normed x:

    q_t = a_t W_q,              per head i:  q_{t,i} = [q^nope_{t,i} (n) | q^pe_{t,i} (p)]
    [c_t (r) | k^pe_t (p)] = a_t W_kva        one latent and ONE rotary key a token
    per head i:  [k^nope_{s,i} (n) | v_{s,i} (D_v)] = (RMSNorm0_r(c_s; w_kvn) W_kvb)_i
    rope on q^pe_{t,i} and k^pe_s (``rope_interleave``): the pairs
        (x_2j, x_2j+1) turned by the angle t theta^(-2j/p), j < p/2, computed
        as HF does: the pairs pulled apart ([x_0, x_2, .. | x_1, x_3, ..]),
        then rotate-half; q and k stay in that order alike
    q_{t,i} = [q^nope_{t,i} | rope(q^pe_{t,i})],  k_{s,i} = [k^nope_{s,i} | rope(k^pe_s)]
    o_{t,i} = sum_{s <= t} softmax_s(q_{t,i} . k_{s,i} / sqrt(n + p)) v_{s,i}
    y_t = (concat_i o_{t,i}) W_o           (``rope_scaling`` null: no mscale)

FF of a dense layer, width ``intermediate_size``:

    y = (SiLU(m W_gate) * m W_up) W_down

FF of an expert layer (E = ``n_routed_experts``, top = ``num_experts_per_tok``,
width ``moe_intermediate_size``; held experts ``expert_offset`` ..
``expert_offset`` + ``experts_held`` - 1; ``n_shared_experts`` shared experts
as one MLP of that many widths, no gate), with b [E] the layer's
``e_score_correction_bias``:

    s = sigma_f32(m W_r) over all E           (``scoring_func`` sigmoid)
    chosen = the ``top`` largest of s + b     (``topk_method`` noaux_tc;
                                               ``n_group`` = ``topk_group`` = 1)
    w_e = s_e / (sum_{e in chosen} s_e + 1e-20) * ``routed_scaling_factor``
    E_e(m) = (SiLU(m W_gate,e) * m W_up,e) W_down,e
    y = E_shared(m) + sum_{e in chosen and held} w_e E_e(m)

b is no parameter: it takes no gradient and is not in ``params``. It lives
in the flax collection ``batch_stats`` (the one name for state outside the
parameters that ``perfbench/reference.py`` and the harness carry), starts
at zero, and a training step's forward pass moves it by this step's own
counts c_e = tokens whose ``chosen`` holds e (all E, held or not):

    delta = ``load_balance_coeff`` * sign(mean(c) - c),   b <- b + delta - mean(delta)

What the experts held elsewhere would add is left out (the configuration's
deployment: ``expert_parallel`` chips share each layer's experts, and on one
chip the layer runs without its exchange). No token is dropped.

Departures from the published model, each stated in the configuration's
``assumed`` too: the bias's update rule and its rate (the aux-loss-free
balancing of Wang et al., arXiv:2408.15664, at DeepSeek-V3's 0.001,
arXiv:2412.19437; the config states neither), centred so that the bias's
mean stays 0; norms stored zero-centred (weight 0 for a scale of 1);
N(0, 0.02) for matrices and the embedding; no dropout; each window of
``seq_len`` tokens an independent sequence from position 0; at more than one
worker the bias is the workers' mean after the step (``reference.py``
averages ``batch_stats``) where summed counts would be the published rule.

Precision: parameters float32; a projection takes ``dtype`` inputs and
gives a ``dtype`` output; q . k and a v take ``dtype`` inputs and accumulate
in float32 (at float32 the highest matmul precision; bfloat16 operands
multiply exactly in one pass); the residual stream, the norms, rotary, the
softmax, the router (logits at the highest matmul precision) and the loss
are float32. A sequence and ``CHUNK`` of its tokens at a time (a query's
projection and attention; a token's feed-forward), each chunk under a
checkpoint; inside, a block of ``BLOCK`` queries and a head at a time
against every key of the sequence; what a query does not see is masked, not
skipped. Every layer is rematerialised in the backward pass.
"""

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

BLOCK = 256
# Tokens of a sequence whose mixer or feed-forward intermediates exist at
# once: the step of ``perfbench/reference.py`` holds 27 B a parameter beside
# them.
CHUNK = 4096
# Tokens whose logits over the vocabulary's rows exist at once in the loss.
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


def rope_interleaved(x, theta, first=0):
    """x [S, H, p] float32 at positions first .. first + S - 1: the pairs
    (x_2j, x_2j+1) pulled apart, then rotate-half."""
    length, dim = x.shape[0], x.shape[-1]
    half = dim // 2
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) * 2.0 / dim))
    angle = (first + jnp.arange(length)).astype(F32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(angle) + turned * jnp.sin(angle)


def is_dense(sizes, i):
    return i < sizes["first_k_dense_replace"]


def attend(q_b, k_h, v_h, seen, dtype):
    """A block's queries q_b [Q, D] of one head over its keys k_h [S, D],
    v_h [S, D_v], ``seen`` [Q, S] bool -> [Q, D_v] float32."""
    logits = product("qd,sd->qs", q_b, k_h, dtype) / math.sqrt(q_b.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    return product("qs,sd->qd", probs, v_h, dtype)


def attention(q, k, v, first, dtype):
    """The queries q [C, H, D] at positions first .. first + C - 1 of one
    sequence over its keys k [S, H, D], v [S, H, D_v], float32 -> o
    [C, H, D_v] float32. A block of queries at a time, its mask made once,
    and inside it a head at a time, each head's rows under a checkpoint of
    their own; a block reads every key of the sequence, and what lies
    beyond a query is masked."""
    count, heads, dim = q.shape
    length = k.shape[0]
    block = math.gcd(count, BLOCK)
    # [C, H, D] -> [blocks, H, block, D]; keys [H, S, D].
    q = jnp.moveaxis(q.reshape(-1, block, heads, dim), 2, 1)
    k, v = jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)
    starts = first + block * jnp.arange(q.shape[0])

    def one(args):
        q_b, start = args
        rows = start + jnp.arange(block)
        seen = rows[:, None] >= jnp.arange(length)[None, :]
        return lax.map(jax.checkpoint(lambda a: attend(*a, seen, dtype)),
                       (q_b, k, v))

    out = lax.map(one, (q, starts))
    # [blocks, H, block, D_v] -> [C, H, D_v]
    return jnp.moveaxis(out, 1, 2).reshape(count, heads, v.shape[-1])


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


# ------------------------------------------------------------------ modules
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
            # The parameters are made; what follows makes none.
            return jnp.zeros(h.shape, dtype)
        eps, theta = s["rms_norm_eps"], s["rope_theta"]

        def sequence(h1):
            latent = dense(h1, w_kva, dtype)
            c_kv, k_pe = latent[:, :rank], latent[:, None, rank:].astype(F32)
            kv = dense(rms_norm0(c_kv, w_kvn, eps), w_kvb, dtype).reshape(
                length, heads, nope + value).astype(F32)
            k_pe = jnp.broadcast_to(rope_interleaved(k_pe, theta),
                                    (length, heads, rope))
            k = jnp.concatenate([kv[..., :nope], k_pe], -1)
            v = kv[..., nope:]

            def chunk(h_c, first):
                q = dense(h_c, w_q, dtype).reshape(
                    -1, heads, nope + rope).astype(F32)
                q = jnp.concatenate([
                    q[..., :nope],
                    rope_interleaved(q[..., nope:], theta, first)], -1)
                out = attention(q, k, v, first, dtype)
                return dense(out.reshape(-1, heads * value), w_o, dtype)

            return by_chunks(chunk, h1)

        return lax.map(sequence, h)


class DenseMLP(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        d, width = self.sizes["hidden_size"], self.sizes["intermediate_size"]
        gate = self.param("gate_proj", normal(), (d, width), F32)
        up = self.param("up_proj", normal(), (d, width), F32)
        down = self.param("down_proj", normal(), (width, d), F32)
        rows = by_chunks(lambda m, _: expert(m, gate, up, down, self.dtype),
                         x.reshape(-1, d))
        return rows.astype(F32).reshape(x.shape)


class SparseMoE(nn.Module):
    """(y, the tokens that chose each expert [E])."""
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        s, dtype = self.sizes, self.dtype
        d, width = s["hidden_size"], s["moe_intermediate_size"]
        held, offset = s["experts_held"], s["expert_offset"]
        experts = s["n_routed_experts"]
        shared_w = s["n_shared_experts"] * width
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
        weights, ids = route(x, router, bias.value, s["num_experts_per_tok"],
                             s["norm_topk_prob"], s["routed_scaling_factor"])
        counts = jnp.sum(ids[..., None] == jnp.arange(experts), axis=(0, 1))
        if not self.is_initializing() \
                and self.is_mutable_collection("batch_stats"):
            bias.value = balanced(bias.value, counts, s["load_balance_coeff"])

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
            return y + expert(x, s_gate, s_up, s_down, dtype).astype(F32)

        return by_chunks(chunk, x, ids, weights).reshape(shape), counts


class Layer(nn.Module):
    sizes: dict
    dtype: jnp.dtype
    dense_mlp: bool

    @nn.compact
    def __call__(self, x):
        s = self.sizes
        d, eps = s["hidden_size"], s["rms_norm_eps"]
        w_in, w_pre_mlp = (
            self.param(name, nn.initializers.zeros, (d,), F32)
            for name in ("input_norm", "pre_mlp_norm"))
        x = x + LatentAttention(s, self.dtype, name="mixer")(
            rms_norm0(x, w_in, eps)).astype(F32)
        m = rms_norm0(x, w_pre_mlp, eps)
        if self.dense_mlp:
            y, counts = DenseMLP(s, self.dtype, name="mlp")(m), None
        else:
            y, counts = SparseMoE(s, self.dtype, name="moe")(m)
        return x + y, counts


class Kanana2(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, tokens, train=False):
        """(the final hidden states [B, S, d] float32, the head's matrix,
        the expert layers' selection counts [layers, E]): ``loss`` applies
        the head a sequence at a time."""
        s = self.sizes
        d = s["hidden_size"]
        table = self.param("embed", normal(), (s["vocab_rows"], d), F32)
        x = table[tokens]
        counts = []
        for i in range(s["num_hidden_layers"]):
            x, count = nn.remat(Layer)(
                s, self.dtype, is_dense(s, i), name=f"layer_{i}")(x)
            if count is not None:
                counts.append(count)
        w_final = self.param("final_norm", nn.initializers.zeros, (d,), F32)
        head = self.param("head", normal(), (d, s["vocab_rows"]), F32)
        return rms_norm0(x, w_final, s["rms_norm_eps"]), head, jnp.stack(counts)


# ------------------------------------------------- what reference.py calls
def build(sizes, dtype):
    # Flax freezes a dict attribute and hashes it: scalars only.
    scalars = {k: v for k, v in sizes.items()
               if isinstance(v, (bool, int, float, str))}
    return Kanana2(scalars, dtype), jnp.zeros((1, sizes["seq_len"]), jnp.int32)


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
    """(dense layers, expert layers)."""
    dense_layers = min(sizes["first_k_dense_replace"],
                       sizes["num_hidden_layers"])
    return dense_layers, sizes["num_hidden_layers"] - dense_layers


def causal_pairs(sizes):
    """sum over t of (t + 1): the pairs one sequence's attention holds."""
    return sizes["seq_len"] * (sizes["seq_len"] + 1) // 2


def _projection_macs(sizes):
    """The mixer's projection multiply-accumulates a token: W_q of
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
    head; the attention at the pairs a query sees, t + 1 (H (n + p + D_v) a
    pair); the routed experts at the uniform expected load,
    ``num_experts_per_tok`` * ``experts_held`` / ``n_routed_experts`` of a
    slot a token and layer. Not counted: the embedding (a lookup), norms,
    rotary, softmax, the bias's update."""
    s = sizes
    d, length = s["hidden_size"], s["seq_len"]
    dense_layers, expert_layers = layer_counts(s)
    layers = dense_layers + expert_layers
    attention_ = layers * (length * _projection_macs(s)
                           + causal_pairs(s) * _pair_macs(s))
    width = s["moe_intermediate_size"]
    slots = s["num_experts_per_tok"] * s["experts_held"] \
        / s["n_routed_experts"]
    moe = d * s["n_routed_experts"] \
        + (s["n_shared_experts"] + slots) * 3 * d * width
    feed_forward = length * (dense_layers * 3 * d * s["intermediate_size"]
                             + expert_layers * moe)
    return int(attention_ + feed_forward + length * d * s["vocab_rows"])


def mla_attn_work(sizes, batch):
    """(operations, bytes) of every layer's latent-attention mixer in one
    training step over ``batch`` sequences, for its roofline: the four
    projections and the softmax attention over the pairs s <= t, forward
    once and backward twice (recomputation not counted), 2 operations a
    multiply-accumulate, 4 bytes a float32 moved. Bytes, the least a
    blocked pass must move: h read and y written, q, the latent, k, v and o
    written and read once, and the projections' weights."""
    s = sizes
    heads, nope, rope, value, rank = (
        s["num_attention_heads"], s["qk_nope_head_dim"],
        s["qk_rope_head_dim"], s["v_head_dim"], s["kv_lora_rank"])
    macs = s["seq_len"] * _projection_macs(s) \
        + causal_pairs(s) * _pair_macs(s)
    floats = s["seq_len"] * (
        2 * s["hidden_size"] + 2 * (rank + rope)
        + 2 * heads * (2 * (nope + rope) + 2 * value)) + _projection_macs(s)
    times = 3 * s["num_hidden_layers"] * batch
    return times * 2 * macs, times * 4 * floats


def mla_kernel_work(sizes, batch):
    """(operations, bytes) of the attention itself, what the kernels (or
    the products and softmax that stand for them) compute: q . k over
    n + p and a v over D_v at the pairs s <= t, forward once and backward
    twice: a kernel that multiplies the backward's five products for the
    forward's two, and whole diagonal tiles, does more and cannot read
    100%. Bytes: q, k, v read and o written once a pass, in 2 bytes a
    number (the kernels' dtype)."""
    s = sizes
    heads, key, value = (
        s["num_attention_heads"],
        s["qk_nope_head_dim"] + s["qk_rope_head_dim"], s["v_head_dim"])
    macs = causal_pairs(s) * _pair_macs(s)
    numbers = s["seq_len"] * heads * 2 * (key + value)
    times = 3 * s["num_hidden_layers"] * batch
    return times * 2 * macs, times * 2 * numbers
