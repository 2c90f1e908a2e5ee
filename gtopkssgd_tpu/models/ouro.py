"""Ouro-2.6B's looped decoder (LoopLM): one stack of dense layers run
``total_ut_steps`` times over the same weights, the final norm after every
pass and fed on to the next, a head and a cross-entropy at every pass, and
a learned exit gate whose distribution over the passes weighs the losses
under an entropy term
(https://huggingface.co/ByteDance/Ouro-2.6B, ``model_type`` ouro; "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741).

The equations are written out in ``perfbench/refmodels/ouro.py`` (the
frozen plain reference; parameter names and shapes are equal leaf for leaf,
``tests/test_ouro.py`` holds the two together). What is this file's own is
the loop, the exit distribution and the objective; the rest is the zoo's
(``models/decoder.py``):

  * a layer is the sandwich (a norm before and after the attention and the
    feed-forward alike) round plain multi-head attention (every head its
    own keys and values, rotate-half rotary on the whole head) and
    ``DenseMLP``. The attention is ``blocked_causal_attention``: on a TPU
    at whole tiles the fused flash kernels of ``ops/flash_attention.py``
    (G = 16 key-value heads, one query head each), everywhere else XLA's
    query blocks; ``forms`` says which compiled (``attention_form``);
  * the loop: the L layers are this module's own ``layer_0 ..`` (made once,
    at initialisation, by ``decoder.run_layers``), so the parameter tree
    holds L layers whatever the passes; a step walks them R times as one
    ``lax.scan`` over the pass, its body the L layers as functions of
    their leaves (the leaves are the loop's constants: one set, and a
    leaf's gradient is the sum over the passes), the final norm, the head's
    loss and the gate. The step holds L layers inside a device loop and
    not R x L unrolled: a third of the compile and 2.7% less time a step
    on the chip, for 1.1 GB more (PERF.md section 6, PR 41). After a pass
    the final norm gives z_r, which is that pass's output and the next
    pass's input. Every layer-pass is under ``jax.checkpoint``, which keeps
    its input and, by name (``KEPT_ATTENTION``), the attention's output and
    rows' log-sum-exp: what the step keeps is layers x passes of them,
    stacked over the passes by the loop;
  * at every pass the head and the cross-entropy of every position
    (``token_losses``, one head) and the gate g_r = sigmoid(z_r . w_g +
    b_g), of which the last pass's is unused. The exit distribution of a token
    is p_r = g_r prod_{j<r} (1 - g_j), the last pass taking the remainder;
    the objective is mean_t [sum_r p_r l_r - beta H(p)] (``exit_objective``).

Precision is the reference's: float32 parameters, residual stream, norms,
rotary, softmax, the gate's product (at the highest matmul precision), the
exit distribution and the loss; matrix products in ``dtype`` with float32
accumulation.

Stages are named for the device trace: ``layer/attn`` round the whole mixer
with its two norms and residual add and, inside it, the parts
(``part/proj``, ``part/pointwise``, ``part/layout``, ``part/kernel``);
``layer/dense_mlp`` round the feed-forward with its two norms and add;
``layer/head`` round the embedding, every pass's final norm, head and
cross-entropy; ``layer/exit_gate`` round the gate's product, the exit
distribution, the weighted sum and the entropy. With the loss go every
pass's mean cross-entropy, the mean exit distribution and its entropy, for
``obs.counters`` (its group ``loop``).
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from gtopkssgd_tpu.models.decoder import (
    F32, HIGHEST, DenseMLP, _normal, attention_form, blocked_causal_attention,
    dense, embedded, head_weights, logits_of, normed_mixer, rms_norm0,
    rotary, run_layers, token_losses)

# The published sizes (config.json of Ouro-2.6B) with the one cut of
# perfbench/configs/ouro_2p6b_l5.json, whose ``sizes`` a test holds equal
# to this preset key for key; and the size every CPU test runs (a pass
# count that is neither 1 nor the layers'). ``exit_entropy_coeff`` is the
# objective's beta: the config states none (the file's ``assumed``).
PRESETS = {
    "2p6b_l5": dict(
        hidden_size=2048, num_hidden_layers=5, total_ut_steps=4,
        num_attention_heads=16, num_key_value_heads=16, head_dim=128,
        rope_theta=1000000, rms_norm_eps=1e-6, intermediate_size=5632,
        exit_entropy_coeff=0.1,
        vocab_size=49152, vocab_rows=49152, seq_len=4096),
    "tiny": dict(
        hidden_size=64, num_hidden_layers=2, total_ut_steps=3,
        num_attention_heads=4, num_key_value_heads=4, head_dim=16,
        rope_theta=1000000, rms_norm_eps=1e-6, intermediate_size=96,
        exit_entropy_coeff=0.1,
        vocab_size=128, vocab_rows=128, seq_len=64),
}

# What a layer-pass's remat keeps from its forward to its backward pass, by
# ``checkpoint_name``: the attention's output ([B, S, H, D] float32, 33.5 MB
# at 4,096 tokens) and, in the kernel form, its rows' log-sum-exp; with the
# layer-pass's input, 20 times over in the published preset.
KEPT_ATTENTION = "loop_attn_out"

def query_block_of(seq_len: int) -> int:
    return min(512, max(1, seq_len // 8))


class Attention(nn.Module):
    sizes: dict
    dtype: Any

    @nn.compact
    def __call__(self, h):
        s, dtype = self.sizes, self.dtype
        d, dim = s["hidden_size"], s["head_dim"]
        heads, kv_heads = s["num_attention_heads"], s["num_key_value_heads"]
        w_q = self.param("q_proj", _normal(), (d, heads * dim), F32)
        w_k = self.param("k_proj", _normal(), (d, kv_heads * dim), F32)
        w_v = self.param("v_proj", _normal(), (d, kv_heads * dim), F32)
        w_o = self.param("o_proj", _normal(), (heads * dim, d), F32)

        batch, length = h.shape[:2]
        if self.is_initializing():
            # Every parameter is made; the rest would be traced for shapes
            # alone at every start.
            return jnp.zeros(h.shape, dtype)
        with jax.named_scope("part/proj"):
            q = dense(h, w_q, dtype).reshape(batch, length, heads, dim)
            k = dense(h, w_k, dtype).reshape(batch, length, kv_heads, dim)
            v = dense(h, w_v, dtype).reshape(batch, length, kv_heads, dim)
        with jax.named_scope("part/pointwise"):
            q, k = (rotary(a.astype(F32), s["rope_theta"], dim)
                    for a in (q, k))
            v = v.astype(F32)
        # Its own parts inside: part/layout and part/kernel.
        out = blocked_causal_attention(
            q, k, v, dtype, query_block_of(s["seq_len"]), None, KEPT_ATTENTION)
        with jax.named_scope("part/proj"):
            return dense(out.reshape(batch, length, heads * dim), w_o, dtype)


class Layer(nn.Module):
    """(x, None): a dense layer counts nothing."""
    sizes: dict
    dtype: Any

    @nn.compact
    def __call__(self, x):
        s = self.sizes
        d, eps = s["hidden_size"], s["rms_norm_eps"]
        w_in, w_post_attn, w_pre_mlp, w_post_mlp = (
            self.param(name, nn.initializers.zeros, (d,), F32)
            for name in ("input_norm", "post_attn_norm", "pre_mlp_norm",
                         "post_mlp_norm"))
        with jax.named_scope("layer/attn"):
            x = normed_mixer(x, Attention(s, self.dtype, name="mixer"),
                             w_in, eps, w_post_attn)
        with jax.named_scope("layer/dense_mlp"):
            y = DenseMLP(s, self.dtype, name="mlp")(
                rms_norm0(x, w_pre_mlp, eps))
            return x + rms_norm0(y, w_post_mlp, eps), None


def exit_distribution(gate_logits):
    """log p [R, ...] of a token's exit distribution from the gates' logits
    a [R - 1, ...] of the passes before the last, g = sigmoid(a):
    p_r = g_r prod_{j<r} (1 - g_j), the last pass the remainder
    prod_j (1 - g_j). In logarithms (log g = log_sigmoid(a), log (1 - g) =
    log_sigmoid(-a)), so that a saturated gate gives a finite p log p."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gate_logits), 0)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]], 0)
    return jnp.concatenate(
        [jax.nn.log_sigmoid(gate_logits) + before, stay[-1:]], 0)


def exit_objective(losses, gate_logits, beta):
    """(mean_t [sum_r p_r l_r - beta H(p)], what ``obs.counters``' group
    ``loop`` reads: every pass's mean cross-entropy [R], the mean exit
    distribution [R], the mean entropy over ln R) from every pass's
    per-token cross-entropy ``losses`` [R, ...] and the gates' logits
    [R - 1, ...]; H(p) = -sum_r p_r log p_r."""
    passes = losses.shape[0]
    log_p = exit_distribution(gate_logits) if passes > 1 \
        else jnp.zeros_like(losses)
    p = jnp.exp(log_p)
    entropy = -jnp.sum(p * log_p, 0)
    objective = jnp.mean(jnp.sum(p * losses, 0) - beta * entropy)
    rows = tuple(range(1, losses.ndim))
    return objective, {
        "loop_loss": jnp.mean(losses, rows),
        "loop_exit_share": jnp.mean(p, rows),
        "loop_exit_entropy": jnp.mean(entropy) / math.log(max(passes, 2)),
    }


class Ouro(nn.Module):
    """``__call__(tokens, targets)`` gives the objective (the exit
    distribution's expected cross-entropy less beta times its entropy, mean
    over the tokens) and ``{"loop_loss": [R], "loop_exit_share": [R],
    "loop_exit_entropy": []}``; without targets, the last pass's logits
    [B, S, vocab_rows]."""
    preset: str = "2p6b_l5"
    dtype: Any = jnp.float32

    @property
    def sizes(self):
        return PRESETS[self.preset]

    def forms(self, length):
        """What the step compiles as at sequences of ``length``, for the
        run's manifest and ``train`` records."""
        return {"attention_form": attention_form(
            length, self.sizes["head_dim"])}

    @nn.compact
    def __call__(self, tokens, targets=None, *, train: bool = False):
        s, dtype = self.sizes, self.dtype
        d, eps = s["hidden_size"], s["rms_norm_eps"]
        names = [f"layer_{i}" for i in range(s["num_hidden_layers"])]
        x = embedded(self, tokens)
        w_final, head = head_weights(self)
        # Stored [d] and [1]: a [d, 1] float32 array pads to 128 lanes a
        # number on the TPU.
        w_gate = self.param("exit_gate", nn.initializers.zeros, (d,), F32)
        b_gate = self.param("exit_bias", nn.initializers.zeros, (1,), F32)
        if self.is_initializing():
            # The L layers as this module's own, once: their parameters
            # are made, one set whatever the passes.
            return run_layers(
                [Layer(s, dtype, name=name) for name in names], x)[0]
        # A layer-pass as a function of the layer's leaves, under its
        # checkpoint: a device loop's body holds no bound module.
        leaves = self.variables["params"]
        by_name = jax.checkpoint_policies.save_only_these_names(KEPT_ATTENTION)
        pure = Layer(s, dtype, parent=None)
        layer = jax.checkpoint(
            lambda p, x: pure.apply({"params": p}, x)[0], policy=by_name)

        def one_pass(x, _):
            """x -> (z_r, (its tokens' cross-entropy, its gate's logits))."""
            for name in names:
                with jax.named_scope(name):
                    x = layer(leaves[name], x)
            with jax.named_scope("layer/head"):
                # z_r: this pass's output and the next one's input.
                x = rms_norm0(x, w_final, eps)
                if targets is None:
                    return x, None
                loss = token_losses(x, head, targets, dtype)
            with jax.named_scope("layer/exit_gate"):
                gate = jnp.dot(x, w_gate, precision=HIGHEST) + b_gate[0]
            return x, (loss, gate.reshape(loss.shape))

        x, counted = jax.lax.scan(
            one_pass, x, None, length=s["total_ut_steps"])
        if targets is None:
            with jax.named_scope("layer/head"):
                return logits_of(x, head, dtype)
        with jax.named_scope("layer/exit_gate"):
            losses, gates = counted
            # The last pass's gate is made in the loop and unused: its
            # exit probability is the remainder.
            return exit_objective(losses, gates[:-1],
                                  s["exit_entropy_coeff"])
