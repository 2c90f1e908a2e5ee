"""Ouro-2.6B's looped decoder (LoopLM), as the first layers of its stack on
one chip with the whole vocabulary.

Source: https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json
(``model_type`` ouro; the catalog: "layers run several times"; "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741). ``sizes`` is
the configuration file's group of that name: the published widths and
``total_ut_steps``, with the depth cut as the file states. Plain
``jax.numpy``: no kernels, causality a mask over [block, keys] rows, the
passes a Python loop over one set of weights. It imports nothing of the
program.

Notation: d = ``hidden_size``, eps = ``rms_norm_eps``, H =
``num_attention_heads`` (= ``num_key_value_heads``: every head its own keys
and values), D = ``head_dim``, L = ``num_hidden_layers``, R =
``total_ut_steps``, SiLU(x) = x sigma(x), t a query position, s a key
position.

    RMSNorm0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)        w initialised 0

Model: x = E[tokens], E over ``vocab_rows`` rows. For pass r = 1 .. R, with
the SAME L layers' weights in every pass:

    for layer i < L:
        x <- x + RMSNorm0(Attention_i(RMSNorm0(x; w_in)); w_post_attn)
        x <- x + RMSNorm0(SwiGLU_i(RMSNorm0(x; w_pre_mlp)); w_post_mlp)
    z_r = RMSNorm0(x; w_final),   x <- z_r        the normed state is pass
                                                  r's output and pass r + 1's input
    logits_r = z_r W_head                         one untied head, every pass
    l_r[t] = cross-entropy(logits_r[t], target[t])
    g_r[t] = sigma(z_r[t] . w_g + b_g)            r < R; g_R is unused

Attention (rotate-half rotary at theta = ``rope_theta`` on all D dims, the
positions 0 .. S - 1 in every pass; no bias, no window, no q/k norms):

    q, k, v = h W_q, h W_k, h W_v                 d -> H x D each
    o_{t,i} = sum_{s <= t} softmax_s(rope(q_{t,i}) . rope(k_{s,i}) / sqrt(D)) v_{s,i}
    y_t = (concat_i o_{t,i}) W_o

SwiGLU of ``intermediate_size``:  y = (SiLU(m W_gate) * m W_up) W_down

Exit distribution of a token and the objective (the paper's stage-I loss:
the expected task loss under the exit distribution, entropy-regularised):

    p_1 = g_1;  p_r = g_r prod_{j<r} (1 - g_j), 1 < r < R;  p_R = prod_{j<R} (1 - g_j)
    H(p) = - sum_r p_r log p_r
    loss = mean_t [ sum_r p_r[t] l_r[t] - beta H(p[t]) ],   beta = ``exit_entropy_coeff``

Gradients flow through all R passes (no truncation, no stop-gradient); a
layer's gradient is the sum over the passes. ``early_exit_threshold`` is an
inference setting and unused.

Departures from the published model, each stated in the configuration's
``assumed`` too: the sandwich's two extra norms, the norm between the
passes, the gate's bias and its input (the normed state), the exit
distribution, the objective and beta = 0.1 are read from the paper and HF's
``OuroDecoderLayer`` and not from the config; norms stored zero-centred;
N(0, 0.02) for matrices and the embedding, a zero gate (p = (1/2, 1/4, 1/8,
1/8) at the start); no dropout; each window of ``seq_len`` tokens an
independent sequence from position 0. ``Ouro(.., tied=False)`` is the same
model with a set of layers of its own for every pass (R x L leaves,
``pass_<r>_layer_<i>``): what ``tests/test_ouro.py`` holds the tie against.

Precision: parameters float32; a projection takes ``dtype`` inputs and
gives a ``dtype`` output; q . k and a v take ``dtype`` inputs and accumulate
in float32 (at float32 the highest matmul precision; bfloat16 operands
multiply exactly in one pass); the residual stream, the norms, rotary, the
softmax, the gate (its product at the highest matmul precision), the exit
distribution and the loss are float32. A sequence at a time; inside, a
block of ``BLOCK`` queries and a head at a time against every key of the
sequence, what a query does not see masked, not skipped; the loss
``LOSS_ROWS`` tokens at a time. Every layer-pass is rematerialised in the
backward pass, each replay when its cotangent arrives (``late_remat``).
"""

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.special import xlogy

BLOCK = 256
# Tokens whose logits over the vocabulary's rows exist at once in the loss
# (1,024 x 49,152 float32 are 201 MB, and as much again for their gradient).
LOSS_ROWS = 1024
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


def rope(x, theta):
    """x [S, H, D] float32 at positions 0 .. S - 1: rotate-half on all D."""
    length, dim = x.shape[0], x.shape[-1]
    half = dim // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) * 2.0 / dim))
    angle = jnp.arange(length, dtype=F32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(angle) + turned * jnp.sin(angle)


def attend(q_b, k_h, v_h, seen, dtype):
    """A block's queries q_b [Q, D] of one head over its keys k_h, v_h
    [S, D], ``seen`` [Q, S] bool -> [Q, D] float32."""
    logits = product("qd,sd->qs", q_b, k_h, dtype) / math.sqrt(q_b.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    return product("qs,sd->qd", probs, v_h, dtype)


def attention(q, k, v, dtype):
    """One sequence's q, k, v [S, H, D] float32 -> o [S, H, D] float32. A
    block of queries at a time, its mask made once, and inside it a head at
    a time, each head's rows under a checkpoint of their own; a block reads
    every key of the sequence, and what lies beyond a query is masked."""
    length, heads, dim = q.shape
    block = math.gcd(length, BLOCK)
    # [S, H, D] -> [blocks, H, block, D]; keys [H, S, D].
    q = jnp.moveaxis(q.reshape(-1, block, heads, dim), 2, 1)
    k, v = jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)

    def one(args):
        q_b, start = args
        rows = start + jnp.arange(block)
        seen = rows[:, None] >= jnp.arange(length)[None, :]
        return lax.map(jax.checkpoint(lambda a: attend(*a, seen, dtype)),
                       (q_b, k, v))

    out = lax.map(one, (q, block * jnp.arange(q.shape[0])))
    # [blocks, H, block, D] -> [S, H, D]
    return jnp.moveaxis(out, 1, 2).reshape(length, heads, dim)


# ------------------------------------------------------------------ modules
class Attention(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, h):
        s, dtype = self.sizes, self.dtype
        d, dim = s["hidden_size"], s["head_dim"]
        heads = s["num_attention_heads"]
        kv_heads = s["num_key_value_heads"]
        if kv_heads != heads:
            raise ValueError("every head has keys and values of its own")
        w_q = self.param("q_proj", normal(), (d, heads * dim), F32)
        w_k = self.param("k_proj", normal(), (d, kv_heads * dim), F32)
        w_v = self.param("v_proj", normal(), (d, kv_heads * dim), F32)
        w_o = self.param("o_proj", normal(), (heads * dim, d), F32)
        if self.is_initializing():
            # The parameters are made; what follows makes none.
            return jnp.zeros(h.shape, dtype)
        theta = s["rope_theta"]

        def sequence(h1):
            q, k, v = (dense(h1, w, dtype).reshape(-1, heads, dim).astype(F32)
                       for w in (w_q, w_k, w_v))
            out = attention(rope(q, theta), rope(k, theta), v, dtype)
            return dense(out.reshape(-1, heads * dim), w_o, dtype)

        return lax.map(sequence, h)


class SwiGLU(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, m):
        d, width = self.sizes["hidden_size"], self.sizes["intermediate_size"]
        gate = self.param("gate_proj", normal(), (d, width), F32)
        up = self.param("up_proj", normal(), (d, width), F32)
        down = self.param("down_proj", normal(), (width, d), F32)
        hidden = jax.nn.silu(dense(m, gate, self.dtype).astype(F32)) \
            * dense(m, up, self.dtype).astype(F32)
        return dense(hidden, down, self.dtype).astype(F32)


class Layer(nn.Module):
    sizes: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        s = self.sizes
        d, eps = s["hidden_size"], s["rms_norm_eps"]
        w_in, w_post_attn, w_pre_mlp, w_post_mlp = (
            self.param(name, nn.initializers.zeros, (d,), F32)
            for name in ("input_norm", "post_attn_norm", "pre_mlp_norm",
                         "post_mlp_norm"))
        y = Attention(s, self.dtype, name="mixer")(rms_norm0(x, w_in, eps))
        x = x + rms_norm0(y, w_post_attn, eps)
        y = SwiGLU(s, self.dtype, name="mlp")(rms_norm0(x, w_pre_mlp, eps))
        return x + rms_norm0(y, w_post_mlp, eps)


def late_remat(fn):
    """``fn(leaves, x)`` rematerialised in the backward pass, its replay
    held back until its cotangent has arrived: the saved x goes through one
    ``optimization_barrier`` with the cotangent, so that the compiler
    cannot run the 20 layer-passes' replays ahead of the backward pass and
    hold all their intermediates at once (PERF.md section 4, PR 41)."""
    @jax.custom_vjp
    def run(leaves, x):
        return fn(leaves, x)

    def backward(saved, ct):
        leaves, x = saved
        x, ct = lax.optimization_barrier((x, ct))
        return jax.vjp(fn, leaves, x)[1](ct)

    run.defvjp(lambda leaves, x: (fn(leaves, x), (leaves, x)), backward)
    return run


class Ouro(nn.Module):
    sizes: dict
    dtype: jnp.dtype
    tied: bool = True

    @nn.compact
    def __call__(self, tokens, train=False):
        """(every pass's normed state z [R, B, S, d] float32, the head's
        matrix, the gates' logits [R - 1, B, S] of the passes before the
        last): ``loss`` applies the head a piece of a sequence at a time."""
        s = self.sizes
        d, eps = s["hidden_size"], s["rms_norm_eps"]
        passes, depth = s["total_ut_steps"], s["num_hidden_layers"]
        table = self.param("embed", normal(), (s["vocab_rows"], d), F32)
        w_final = self.param("final_norm", nn.initializers.zeros, (d,), F32)
        head = self.param("head", normal(), (d, s["vocab_rows"]), F32)
        w_gate = self.param("exit_gate", nn.initializers.zeros, (d,), F32)
        b_gate = self.param("exit_bias", nn.initializers.zeros, (1,), F32)
        # The layers' names, pass by pass: tied, every pass walks the same
        # L sets of leaves.
        names = [[f"layer_{i}" if self.tied else f"pass_{r}_layer_{i}"
                  for i in range(depth)] for r in range(passes)]
        x = table[tokens]
        if self.is_initializing():
            # The parameters are made, every set once; what follows makes
            # none.
            for name in dict.fromkeys(sum(names, [])):
                Layer(s, self.dtype, name=name)(x)
            return x, head, w_gate
        # A layer-pass as a function of its leaves, under a checkpoint.
        layer = late_remat(lambda leaves, x: Layer(
            s, self.dtype, parent=None).apply({"params": leaves}, x))
        leaves = self.variables["params"]
        states, gates = [], []
        for r in range(passes):
            for name in names[r]:
                x = layer(leaves[name], x)
            x = rms_norm0(x, w_final, eps)
            states.append(x)
            if r < passes - 1:
                gates.append(jnp.dot(x, w_gate, precision=HIGHEST) + b_gate[0])
        return (jnp.stack(states), head,
                jnp.stack(gates) if gates else jnp.zeros((0,) + x.shape[:2]))


# ------------------------------------------------- what reference.py calls
def build(sizes, dtype, tied=True):
    # Flax freezes a dict attribute and hashes it: scalars only.
    scalars = {k: v for k, v in sizes.items()
               if isinstance(v, (bool, int, float, str))}
    return (Ouro(scalars, dtype, tied),
            jnp.zeros((1, sizes["seq_len"]), jnp.int32))


def initial_carry(sizes, batch, dtype):
    return ()


def token_losses(hidden, head, targets, dtype):
    """Cross-entropy of every position [B, S], float32, ``LOSS_ROWS``
    tokens of a sequence at a time."""
    rows = math.gcd(hidden.shape[1], LOSS_ROWS)
    shape = targets.shape
    hidden = hidden.reshape(-1, rows, hidden.shape[-1])
    targets = targets.reshape(-1, rows)

    @jax.checkpoint
    def one(args):
        h, t = args
        logits = product("sd,dv->sv", h, head, dtype)
        picked = jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    return lax.map(one, (hidden, targets)).reshape(shape)


def exit_distribution(gate_logits):
    """p [R, ...] from the gates' logits [R - 1, ...]: p_r = g_r
    prod_{j<r} (1 - g_j), the last pass the remainder."""
    g = jax.nn.sigmoid(gate_logits)
    p, left = [], jnp.ones(gate_logits.shape[1:], F32)
    for g_r in g:
        p.append(g_r * left)
        left = left * (1.0 - g_r)
    return jnp.stack(p + [left])


def objective(losses, p, beta):
    """mean_t [sum_r p_r l_r - beta H(p)], H(p) = -sum_r p_r log p_r."""
    entropy = -jnp.sum(xlogy(p, p), 0)
    return jnp.mean(jnp.sum(p * losses, 0) - beta * entropy)


def passes_of(module, variables, batch):
    """(every pass's per-token cross-entropy [R, B, S], the exit
    distribution [R, B, S])."""
    states, head, gates = module.apply(variables, batch["tokens"], False)
    losses = jnp.stack([
        token_losses(z, head, batch["targets"], module.dtype) for z in states])
    return losses, exit_distribution(gates)


def loss(module, variables, carry, batch, key, train):
    """(the objective, no model state, the carry)."""
    losses, p = passes_of(module, variables, batch)
    return (objective(losses, p, module.sizes["exit_entropy_coeff"]), None,
            carry)


# ------------------------------------------------------ work, from shapes
def causal_pairs(sizes):
    """sum over t of (t + 1): the pairs one sequence's attention holds."""
    return sizes["seq_len"] * (sizes["seq_len"] + 1) // 2


def layer_passes(sizes):
    """How often a layer's mathematics runs in one forward pass of the
    model: every layer in every pass."""
    return sizes["num_hidden_layers"] * sizes["total_ut_steps"]


def _projection_macs(sizes):
    """The mixer's projection multiply-accumulates a token: q and o of H D
    columns, k and v of H_kv D."""
    s = sizes
    return s["hidden_size"] * s["head_dim"] * (
        2 * s["num_attention_heads"] + 2 * s["num_key_value_heads"])


def _pair_macs(sizes):
    """A pair's multiply-accumulates over the heads: q . k and a v over D."""
    return 2 * sizes["num_attention_heads"] * sizes["head_dim"]


def _mlp_macs(sizes):
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def forward_macs(sizes):
    """Multiply-accumulates of one ``seq_len``-token sequence's forward
    pass, from the shapes: the model's mathematics, whatever form a program
    computes it in. Counted: every matrix product of every layer in EVERY
    pass and every pass's head (a parameter is multiplied R times a token);
    the attention at the pairs a query sees, t + 1 (2 H D a pair). Not
    counted: the embedding (a lookup), norms, rotary, softmax, the gate
    (d a token and pass), the exit distribution."""
    s = sizes
    length = s["seq_len"]
    layers = layer_passes(s) * (
        length * (_projection_macs(s) + _mlp_macs(s))
        + causal_pairs(s) * _pair_macs(s))
    heads = s["total_ut_steps"] * length * s["hidden_size"] * s["vocab_rows"]
    return int(layers + heads)


def loop_attn_work(sizes, batch):
    """(operations, bytes) of every layer-pass's attention mixer in one
    training step over ``batch`` sequences, for its roofline: the four
    projections and the softmax attention over the pairs s <= t, all R
    passes, forward once and backward twice (the remat's replay not
    counted), 2 operations a multiply-accumulate, 4 bytes a float32 moved.
    Bytes, the least a blocked pass must move: h read and y written, q, k,
    v and o written and read once, and the projections' weights."""
    s = sizes
    heads, kv, dim = (s["num_attention_heads"], s["num_key_value_heads"],
                      s["head_dim"])
    macs = s["seq_len"] * _projection_macs(s) + causal_pairs(s) * _pair_macs(s)
    floats = s["seq_len"] * (2 * s["hidden_size"]
                             + 2 * (2 * heads + 2 * kv) * dim) \
        + _projection_macs(s)
    times = 3 * layer_passes(s) * batch
    return times * 2 * macs, times * 4 * floats


def loop_mlp_work(sizes, batch):
    """(operations, bytes) of every layer-pass's SwiGLU: the three
    products, all R passes, forward once and backward twice. Bytes: m read
    and y written, the two hidden rows and their product written and read
    once, and the weights."""
    s = sizes
    macs = s["seq_len"] * _mlp_macs(s)
    floats = s["seq_len"] * (2 * s["hidden_size"]
                             + 2 * 3 * s["intermediate_size"]) + _mlp_macs(s)
    times = 3 * layer_passes(s) * batch
    return times * 2 * macs, times * 4 * floats


def loop_head_work(sizes, batch):
    """(operations, bytes) of the R heads: z_r W_head over all
    ``vocab_rows``, every pass, forward once and backward twice (the
    loss's own recomputation of the logits not counted). Bytes: z read,
    the logits written and read once, the head's weights. The embedding's
    lookup, the final norms and the cross-entropy's pointwise passes run
    under the same scope and are not counted."""
    s = sizes
    macs = s["seq_len"] * s["hidden_size"] * s["vocab_rows"]
    floats = s["seq_len"] * (s["hidden_size"] + 2 * s["vocab_rows"]) \
        + s["hidden_size"] * s["vocab_rows"]
    times = 3 * s["total_ut_steps"] * batch
    return times * 2 * macs, times * 4 * floats
