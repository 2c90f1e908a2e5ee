"""Trinity-Mini's AFMoE decoder block, as one chip's share of an expert group.

Source: https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json
(``model_type`` afmoe, 26B-A3B; the catalog: "SWA(2048); global every 4th",
"128 experts, top-8, 1 shared"). ``sizes`` is the configuration file's group
of that name: the published widths, with the depth, the leading dense layers,
the experts held here and the vocabulary rows cut as the file states. Plain
``jax.numpy``: no kernels, no grouped products, the window a mask over
[block, keys] rows, the experts a mask over the held ones. It imports
nothing of the program.

Notation: d = ``hidden_size``, eps = ``rms_norm_eps``, SiLU(x) = x sigma(x),
sg = stop_gradient, t a query position, s a key position,
W = ``sliding_window``.

    RMSNorm0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)        w initialised 0

Model: x_0 = E[tokens] sqrt(d) (``mup_enabled``), E over ``vocab_rows`` rows
-> the layers -> RMSNorm0 -> an untied head over the same rows; the
objective is the mean cross-entropy over all positions.

Layer i of ``num_hidden_layers`` on the residual stream x [B, S, d]; it is
dense while i < ``num_dense_layers``, and a FULL or a SLIDING layer as
``layer_kinds`` says (the published ``layer_types`` of the layers the
configuration keeps, comma-separated):

    x <- x + RMSNorm0(Attention(RMSNorm0(x; w_in)); w_post_attn)
    x <- x + RMSNorm0(FF(RMSNorm0(x; w_pre_mlp)); w_post_mlp)

Attention (H = ``num_attention_heads``, H_kv = ``num_key_value_heads``,
D = ``head_dim``; head i reads key head i // (H / H_kv)), on a = the normed x:

    q_{t,i} = RMSNorm0_D((a_t W_q)_i; w_qn),  k_{s,g} = RMSNorm0_D((a_s W_k)_g; w_kn),
    v_{s,g} = (a_s W_v)_g,  g_t = a_t W_g           [W_k | W_v] one matrix, ``kv_proj``
    SLIDING: q, k <- rope(q), rope(k), rotate-half over all D dimensions,
             theta = ``rope_theta``, positions 0..S-1; query t sees the keys
             s with 0 <= t - s < W
    FULL:    no position encoding at all; query t sees the keys s <= t
    o_{t,i} = sum_s softmax_s(q_{t,i} . k_{s,g(i)} / sqrt(D)) v_{s,g(i)}
    y_t = ((concat_i o_{t,i}) * sigma(g_t)) W_o

FF of a dense layer, width ``intermediate_size``:

    y = (SiLU(m W_gate) * m W_up) W_down

FF of an expert layer (E = ``num_experts``, top = ``num_experts_per_tok``,
width ``moe_intermediate_size``; held experts ``expert_offset`` ..
``expert_offset`` + ``experts_held`` - 1; one shared expert of the same width
with no gate), with b [E] the layer's balancing bias:

    s = sigma_f32(m W_r) over all E           (``score_func`` sigmoid)
    chosen = the ``top`` largest of s + b     (b moves the choice alone)
    w_e = s_e / (sum_{e in chosen} s_e + 1e-20) * ``route_scale``     (``route_norm``)
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
``assumed`` too: the bias's update rule (the aux-loss-free balancing of
Wang et al., arXiv:2408.15664, as DeepSeek-V3 trains it, with the config's
``load_balance_coeff`` as its rate; the config states the rate and no rule),
centred so that the bias's mean stays 0; norms stored zero-centred (weight 0
for a scale of 1); N(0, 0.02) for matrices and the embedding; ``n_group`` /
``topk_group`` / ``num_limited_groups`` 1 = no group-limited routing; no
dropout; k_proj and v_proj one leaf ([k | v]); each window of ``seq_len``
tokens an independent sequence from position 0; at more than one worker the
bias is the workers' mean after the step (``reference.py`` averages
``batch_stats``) where summed counts would be the published rule.

Precision: parameters float32; a projection takes ``dtype`` inputs and
gives a ``dtype`` output; q . k and a v take ``dtype`` inputs and accumulate
in float32 (at float32 the highest matmul precision; bfloat16 operands
multiply exactly in one pass); the residual stream, the norms, rotary, the
softmax, the gates, the router (logits at the highest matmul precision)
and the loss are float32. A sequence and ``CHUNK`` of its tokens at a time
(a query's projections, gate and attention; a token's feed-forward), each
chunk under a checkpoint; inside, a block of ``BLOCK`` queries and a
key-value head's query heads at a time: a sliding layer's block against the
W + block keys that end with it, a full layer's against every key of the
sequence; what a query does not see is masked, not skipped. Every layer is
rematerialised in the backward pass.
"""

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

BLOCK = 256
# Tokens of a sequence whose mixer or feed-forward intermediates exist at
# once (a query's projections, gate and attention, a token's expert rows):
# the step of ``perfbench/reference.py`` holds 27 B a parameter beside them.
CHUNK = 4096
# Tokens whose logits over the vocabulary's rows exist at once in the loss
# (16,384 x 25,024 float32 are 1.6 GB, and as much again for their gradient).
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


def rotary(x, theta, first=0):
    """Rotate-half rotary embedding over the whole last axis; x [S, H, D]
    float32 at positions first .. first + S - 1."""
    length, dim = x.shape[0], x.shape[-1]
    half = dim // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) * 2.0 / dim))
    angle = (first + jnp.arange(length)).astype(F32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(angle) + turned * jnp.sin(angle)


def is_dense(sizes, i):
    return i < sizes["num_dense_layers"]


def is_full(sizes, i):
    return sizes["layer_kinds"].split(",")[i] == "full"


def attend(q_b, k_b, v_b, seen, dtype):
    """A block's queries q_b [Q, R, D] of one key-value head over its keys
    k_b, v_b [K, D], ``seen`` [Q, K] bool -> [Q, R, D] float32."""
    logits = product("qrd,sd->rqs", q_b, k_b, dtype) / math.sqrt(q_b.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    return product("rqs,sd->qrd", probs, v_b, dtype)


def attention(q, k, v, first, window, dtype):
    """The queries q [C, H, D] at positions first .. first + C - 1 of one
    sequence over its keys k, v [S, H_kv, D], float32 -> o [C, H, D]
    float32; ``window`` None for a full layer. A block of queries at a time,
    its mask made once, and inside it a key-value head at a time, each
    head's rows under a checkpoint of their own. A sliding layer's block
    reads the W + block keys that end with it; a full layer's reads every
    key of the sequence, and what lies beyond a query is masked."""
    count, heads, dim = q.shape
    length, kv_heads = k.shape[:2]
    block = math.gcd(count, BLOCK)
    # [C, H, D] -> [blocks, G, block, R, D]; keys [G, S, D].
    q = jnp.moveaxis(q.reshape(-1, block, kv_heads, heads // kv_heads, dim),
                     2, 1)
    k, v = jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)
    starts = first + block * jnp.arange(q.shape[0])
    if window is not None:
        # ``window`` keys of padding in front, at positions -W .. -1.
        extent = window + block
        k, v = (jnp.pad(a, ((0, 0), (window, 0), (0, 0))) for a in (k, v))

    def one(args):
        q_b, start = args
        rows = start + jnp.arange(block)
        if window is None:
            k_e, v_e = k, v
            seen = rows[:, None] >= jnp.arange(length)[None, :]
        else:
            keys = start - window + jnp.arange(extent)
            apart = rows[:, None] - keys[None, :]
            seen = (keys >= 0)[None, :] & (apart >= 0) & (apart < window)
            k_e, v_e = (lax.dynamic_slice_in_dim(a, start, extent, 1)
                        for a in (k, v))
        return lax.map(jax.checkpoint(lambda a: attend(*a, seen, dtype)),
                       (q_b, k_e, v_e))

    out = lax.map(one, (q, starts))
    # [blocks, G, block, R, D] -> [C, H, D]
    return jnp.moveaxis(out, 1, 2).reshape(count, heads, dim)


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
class GatedAttention(nn.Module):
    sizes: dict
    dtype: jnp.dtype
    full: bool

    @nn.compact
    def __call__(self, h):
        s, dtype = self.sizes, self.dtype
        d, dim = s["hidden_size"], s["head_dim"]
        heads, kv_heads = s["num_attention_heads"], s["num_key_value_heads"]
        w_q = self.param("q_proj", normal(), (d, heads * dim), F32)
        w_kv = self.param("kv_proj", normal(), (d, 2 * kv_heads * dim), F32)
        w_g = self.param("gate_proj", normal(), (d, heads * dim), F32)
        w_qn = self.param("q_norm", nn.initializers.zeros, (dim,), F32)
        w_kn = self.param("k_norm", nn.initializers.zeros, (dim,), F32)
        w_o = self.param("o_proj", normal(), (heads * dim, d), F32)

        batch, length = h.shape[:2]
        if self.is_initializing():
            # The parameters are made; what follows makes none.
            return jnp.zeros(h.shape, dtype)
        eps, theta = s["rms_norm_eps"], s["rope_theta"]
        window = None if self.full else s["sliding_window"]

        def sequence(h1):
            kv = dense(h1, w_kv, dtype).reshape(length, 2, kv_heads, dim)
            k, v = rms_norm0(kv[:, 0], w_kn, eps), kv[:, 1].astype(F32)
            if not self.full:
                k = rotary(k, theta)

            def chunk(h_c, first):
                q = rms_norm0(dense(h_c, w_q, dtype).reshape(-1, heads, dim),
                              w_qn, eps)
                if not self.full:
                    q = rotary(q, theta, first)
                gate = jax.nn.sigmoid(dense(h_c, w_g, dtype).astype(F32))
                out = attention(q, k, v, first, window, dtype)
                return dense(out.reshape(-1, heads * dim) * gate, w_o, dtype)

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
        weights, ids = route(x, router, bias.value, s["num_experts_per_tok"],
                             s["route_norm"], s["route_scale"])
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
    full: bool
    dense_mlp: bool

    @nn.compact
    def __call__(self, x):
        s = self.sizes
        d, eps = s["hidden_size"], s["rms_norm_eps"]
        w_in, w_post_attn, w_pre_mlp, w_post_mlp = (
            self.param(name, nn.initializers.zeros, (d,), F32)
            for name in ("input_norm", "post_attn_norm", "pre_mlp_norm",
                         "post_mlp_norm"))
        y = GatedAttention(s, self.dtype, self.full, name="mixer")(
            rms_norm0(x, w_in, eps))
        x = x + rms_norm0(y, w_post_attn, eps)
        m = rms_norm0(x, w_pre_mlp, eps)
        if self.dense_mlp:
            y, counts = DenseMLP(s, self.dtype, name="mlp")(m), None
        else:
            y, counts = SparseMoE(s, self.dtype, name="moe")(m)
        return x + rms_norm0(y, w_post_mlp, eps), counts


class TrinityMini(nn.Module):
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
        x = table[tokens] * (math.sqrt(d) if s["mup_enabled"] else 1.0)
        counts = []
        for i in range(s["num_hidden_layers"]):
            x, count = nn.remat(Layer)(
                s, self.dtype, is_full(s, i), is_dense(s, i),
                name=f"layer_{i}")(x)
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
    return TrinityMini(scalars, dtype), jnp.zeros((1, sizes["seq_len"]), jnp.int32)


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
def layer_kinds(sizes):
    """(sliding layers, full layers, dense layers, expert layers)."""
    layers = range(sizes["num_hidden_layers"])
    full = sum(is_full(sizes, i) for i in layers)
    dense_layers = sum(is_dense(sizes, i) for i in layers)
    return (len(layers) - full, full, dense_layers,
            len(layers) - dense_layers)


def window_pairs(sizes):
    """sum over t of min(t + 1, W): the pairs a sliding layer's attention
    of one ``seq_len``-token sequence holds."""
    length = sizes["seq_len"]
    window = min(sizes["sliding_window"], length)
    return window * (window + 1) // 2 + (length - window) * window


def causal_pairs(sizes):
    """sum over t of (t + 1): a full layer's."""
    return sizes["seq_len"] * (sizes["seq_len"] + 1) // 2


def _projection_macs(sizes):
    """The attention's projection multiply-accumulates a token: q, gate and
    o of H D columns each, k and v of H_kv D."""
    s = sizes
    dim = s["head_dim"]
    return s["hidden_size"] * dim * (
        3 * s["num_attention_heads"] + 2 * s["num_key_value_heads"])


def forward_macs(sizes):
    """Multiply-accumulates of one ``seq_len``-token sequence's forward
    pass, from the shapes: the model's mathematics, whatever form a program
    computes it in. Counted: every matrix product of the layers and the
    head; the attention at the pairs a query sees, min(t + 1, W) in a
    sliding layer and t + 1 in a full one (2 H D a pair); the routed
    experts at the uniform expected load, ``num_experts_per_tok`` *
    ``experts_held`` / ``num_experts`` of a slot a token and layer. Not
    counted: the embedding (a lookup), norms, rotary, softmax, gates, the
    bias's update."""
    s = sizes
    d, length = s["hidden_size"], s["seq_len"]
    sliding, full, dense_layers, expert_layers = layer_kinds(s)
    pair = 2 * s["num_attention_heads"] * s["head_dim"]
    attention_ = (sliding + full) * length * _projection_macs(s) \
        + pair * (sliding * window_pairs(s) + full * causal_pairs(s))
    width = s["moe_intermediate_size"]
    slots = s["num_experts_per_tok"] * s["experts_held"] / s["num_experts"]
    moe = d * s["num_experts"] \
        + (s["num_shared_experts"] + slots) * 3 * d * width
    feed_forward = length * (dense_layers * 3 * d * s["intermediate_size"]
                             + expert_layers * moe)
    return int(attention_ + feed_forward + length * d * s["vocab_rows"])


def _attention_work(sizes, batch, layers, pairs):
    """(operations, bytes) of ``layers`` attention mixers in one training
    step over ``batch`` sequences: forward once and backward twice
    (recomputation not counted), 2 operations a multiply-accumulate, 4
    bytes a float32 moved. Bytes, the least a blocked pass must move: h
    read and y written, q, k, v, the gate and o written and read once, and
    the projections' weights."""
    s = sizes
    heads, kv, dim = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
    macs = s["seq_len"] * _projection_macs(s) + pairs * 2 * heads * dim
    floats = s["seq_len"] * (2 * s["hidden_size"]
                             + 2 * (3 * heads + 2 * kv) * dim) \
        + _projection_macs(s)
    times = 3 * layers * batch
    return times * 2 * macs, times * 4 * floats


def swa_attn_work(sizes, batch):
    """The sliding layers' mixers, for their roofline: the five projections
    and the softmax attention over the pairs the window leaves,
    min(t + 1, W) a query: a form that multiplies masked pairs too does
    more and reads lower."""
    return _attention_work(sizes, batch, layer_kinds(sizes)[0],
                           window_pairs(sizes))


def full_attn_work(sizes, batch):
    """The full layers' mixers: the projections and every pair s <= t."""
    return _attention_work(sizes, batch, layer_kinds(sizes)[1],
                           causal_pairs(sizes))
