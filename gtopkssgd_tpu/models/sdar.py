"""SDAR-30B-A3B-Chat's decoder trained by block diffusion: every sequence
runs beside its own masked copy, a noised block sees the clean blocks before
it and itself both ways, and the loss is a 1/t-weighted cross-entropy on the
masked positions alone
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat, ``model_type`` sdar_moe;
"SDAR: A Synergistic Diffusion-AutoRegression Paradigm for Scalable Sequence
Generation", arXiv:2510.06303; the objective is BD3-LM's, arXiv:2503.09573).

The equations are written out in ``perfbench/refmodels/sdar.py`` (the
frozen plain reference; parameter names and shapes are equal leaf for leaf,
``tests/test_sdar.py`` holds the two together). What is this file's own is
the noise, the doubled rows and the objective; the rest is the zoo's
(``models/decoder.py``):

  * the noise (``add_noise``) is drawn inside the step from the ``dropout``
    key the trainer threads (the reference draws from the same key by the
    same rule: one t a block of ``block_length`` tokens, each of its
    positions masked with probability (1 - eps) t + eps, weight 1 / that
    where masked). **Without a key** (an evaluation, ``model.apply`` with no
    ``rngs``) it is drawn from the fixed ``PRNGKey(0)``: the same mask
    every call;
  * the rows are the L clean tokens and then the L noised ones, both halves
    at positions 0 .. L - 1, through every layer together;
  * a layer is the norm, grouped-query attention with per-head q/k norms
    and rotate-half rotary, the residual add, the norm and the zoo's
    dropless share of the expert group (``SparseMoE``: softmax router, top
    8 of 128 renormalised, no shared expert, no bias) (``Layer``). The
    attention is ``block_diffusion_attention``: on a TPU at whole tiles
    the flash kernels of ``ops/flash_attention.py`` told the rule (288
    tiles of 512 visited a head group of the 1,024 at 2 x 8,192 rows),
    everywhere else XLA's query blocks, each handed the keys it can see;
    ``forms`` says which compiled (``attention_form``);
  * the final norm, the head and the loss over the noised half only, the
    label of a masked position the clean token at that position (no
    shift); ``targets`` is accepted and unused. The last layer's clean
    rows feed nothing but that layer's keys and values; the program
    computes their q, attention, o and experts all the same (no second
    shape of the attention for one layer in four; PERF.md section 6).

Precision is the reference's: float32 parameters, residual stream, norms,
rotary, router, softmax, the weights w and the loss; matrix products in
``dtype`` with float32 accumulation.

Stages are named for the device trace: ``layer/noise`` round the draw, the
masking, the weights and the building of the 2L rows; ``layer/attn`` round
the mixer with its norm and residual add and, inside it, the parts
(``part/proj``, ``part/pointwise``, ``part/layout``, ``part/kernel``);
``layer/moe_router``, ``layer/moe_experts``; ``layer/head`` round the
embedding, the final norm, the head and the weighted loss. With the loss go
the held experts' loads and dropped slots (always 0) and what the noise
did (``obs.counters``' group ``bd``).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from gtopkssgd_tpu.models.decoder import (
    F32, MOE_COUNTS, SparseMoE, _normal, block_diffusion_attention, dense,
    diffusion_attention_form, embedded, head_weights, kept_by_name,
    logits_of, normed_mixer, rms_norm0, rotary, run_layers, token_losses)

# The published sizes (config.json of SDAR-30B-A3B-Chat) with the three cuts
# of perfbench/configs/sdar_30b_a3b_ep8.json, whose ``sizes`` a test holds
# equal to this preset key for key; and the sizes the CPU tests run
# (``tiny``; a test makes itself the same at a length that is whole blocks
# of 4 tokens and not whole query blocks of the blocked form).
# ``block_length``, ``noise_eps`` and ``mask_token_id`` are in no config.json
# (the file's ``assumed``); the mask id is the slice's last row, and the
# data's ids lie below it.
PRESETS = {
    "30b_a3b_ep8": dict(
        hidden_size=2048, num_hidden_layers=4,
        num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        rope_theta=1000000, rms_norm_eps=1e-6,
        num_experts=128, num_experts_per_tok=8, moe_intermediate_size=768,
        norm_topk_prob=True,
        experts_held=16, expert_offset=0, expert_parallel=8,
        vocab_size=151936, vocab_rows=18992, mask_token_id=18991,
        block_length=4, noise_eps=1e-3, seq_len=8192),
    "tiny": dict(
        hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        rope_theta=1000000, rms_norm_eps=1e-6,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        norm_topk_prob=True,
        experts_held=4, expert_offset=0, expert_parallel=2,
        vocab_size=128, vocab_rows=128, mask_token_id=127,
        block_length=4, noise_eps=1e-3, seq_len=64),
}

# What a layer's remat keeps from its forward to its backward pass, by
# ``checkpoint_name``: the attention's output ([B, 2L, H, D] float32, 268 MB
# a layer at 2 x 8,192 rows) and, in the kernel form, its rows' log-sum-exp.
KEPT_ATTENTION = "bd_attn_out"


def query_block_of(seq_len: int) -> int:
    return min(512, max(1, seq_len // 8))


def add_noise(key, tokens, block_length, mask_id, eps):
    """(the noised tokens, the weights w [B, L] float32, the blocks' masking
    probabilities p [B, L / block_length]) of ``tokens`` [B, L]: for every
    block of ``block_length`` tokens t ~ U(0, 1) and p = (1 - eps) t + eps;
    every position of the block is masked (``mask_id``) with probability p,
    independently; w = 1 / p where masked, else 0. The two draws take the
    two halves of ``jax.random.split(key)``."""
    batch, length = tokens.shape
    key_t, key_m = jax.random.split(key)
    t = jax.random.uniform(key_t, (batch, length // block_length), F32)
    p = (1.0 - eps) * t + eps
    each = jnp.repeat(p, block_length, axis=1)
    masked = jax.random.uniform(key_m, (batch, length), F32) < each
    return (jnp.where(masked, mask_id, tokens),
            jnp.where(masked, 1.0 / each, 0.0), p)


class Attention(nn.Module):
    sizes: dict
    dtype: Any

    @nn.compact
    def __call__(self, h):
        s, dtype = self.sizes, self.dtype
        d, dim = s["hidden_size"], s["head_dim"]
        heads, kv_heads = s["num_attention_heads"], s["num_key_value_heads"]
        w_q = self.param("q_proj", _normal(), (d, heads * dim), F32)
        w_kv = self.param("kv_proj", _normal(), (d, 2 * kv_heads * dim), F32)
        w_qn = self.param("q_norm", nn.initializers.zeros, (dim,), F32)
        w_kn = self.param("k_norm", nn.initializers.zeros, (dim,), F32)
        w_o = self.param("o_proj", _normal(), (heads * dim, d), F32)

        batch, rows = h.shape[:2]
        if self.is_initializing():
            # Every parameter is made; the rest would be traced for shapes
            # alone at every start.
            return jnp.zeros(h.shape, dtype)
        eps, theta = s["rms_norm_eps"], s["rope_theta"]
        # Both halves at positions 0 .. L - 1: the rotary sees each half
        # as a sequence of its own.
        halves = lambda a: rotary(
            a.reshape((2 * batch, rows // 2) + a.shape[2:]), theta,
            dim).reshape(a.shape)
        with jax.named_scope("part/proj"):
            q = dense(h, w_q, dtype).reshape(batch, rows, heads, dim)
            kv = dense(h, w_kv, dtype).reshape(batch, rows, 2, kv_heads, dim)
        with jax.named_scope("part/pointwise"):
            q = halves(rms_norm0(q, w_qn, eps))
            k = halves(rms_norm0(kv[:, :, 0], w_kn, eps))
            v = kv[:, :, 1].astype(F32)
        # Its own parts inside: part/layout and part/kernel.
        out = block_diffusion_attention(
            q, k, v, dtype, query_block_of(s["seq_len"]), s["block_length"],
            KEPT_ATTENTION)
        with jax.named_scope("part/proj"):
            return dense(out.reshape(batch, rows, heads * dim), w_o, dtype)


class Layer(nn.Module):
    """(x, (slots per held expert, slots dropped))."""
    sizes: dict
    dtype: Any

    @nn.compact
    def __call__(self, x):
        s = self.sizes
        d, eps = s["hidden_size"], s["rms_norm_eps"]
        w_in = self.param("input_norm", nn.initializers.zeros, (d,), F32)
        w_post = self.param("post_norm", nn.initializers.zeros, (d,), F32)
        with jax.named_scope("layer/attn"):
            x = normed_mixer(x, Attention(s, self.dtype, name="mixer"),
                             w_in, eps)
        with jax.named_scope("layer/moe_router"):
            y, load, dropped, _ = SparseMoE(s, self.dtype, name="moe")(
                rms_norm0(x, w_post, eps))
            return x + y, (load, dropped)


class SDAR(nn.Module):
    """``__call__(tokens, targets)`` gives the block-diffusion objective,
    sum_i w_i CE(logits_i, tokens_i) / (B L) over the masked positions of
    the noised copy it draws itself (``targets`` is unused), and
    ``{"moe_load": [layers, held], "moe_dropped": [layers],
    "bd_masked_share", "bd_mean_t", "bd_masked_ce", "bd_empty_blocks": []}``;
    without targets, the noised half's logits [B, L, vocab_rows]."""
    preset: str = "30b_a3b_ep8"
    dtype: Any = jnp.float32

    @property
    def sizes(self):
        return PRESETS[self.preset]

    def forms(self, length):
        """What the step compiles as at sequences of ``length``, for the
        run's manifest and ``train`` records."""
        s = self.sizes
        return {"attention_form": diffusion_attention_form(
            length, s["head_dim"], s["block_length"])}

    @nn.compact
    def __call__(self, tokens, targets=None, *, train: bool = False):
        s, dtype = self.sizes, self.dtype
        batch, length = tokens.shape
        with jax.named_scope("layer/noise"):
            key = self.make_rng("dropout") if self.has_rng("dropout") \
                else jax.random.PRNGKey(0)
            noised, weight, p = add_noise(
                key, tokens, s["block_length"], s["mask_token_id"],
                s["noise_eps"])
            rows = jnp.concatenate([tokens, noised], axis=1)
        by_name = kept_by_name(KEPT_ATTENTION)
        x, found = run_layers(
            [nn.remat(Layer, policy=by_name)(s, dtype, name=f"layer_{i}")
             for i in range(s["num_hidden_layers"])], embedded(self, rows))
        with jax.named_scope("layer/head"):
            w_final, head = head_weights(self)
            hidden = rms_norm0(x[:, length:], w_final, s["rms_norm_eps"])
            if targets is None:
                return logits_of(hidden, head, dtype)
            # The label of a masked position is the clean token there.
            ce = token_losses(hidden, head, tokens, dtype).reshape(
                batch, length)
            loss = jnp.sum(weight * ce) / (batch * length)
        with jax.named_scope("layer/noise"):
            masked = weight > 0
            held = jnp.sum(masked)
            blocks = masked.reshape(batch, -1, s["block_length"])
            counts = {
                "bd_masked_share": held / (batch * length),
                "bd_mean_t": jnp.mean((p - s["noise_eps"])
                                      / (1.0 - s["noise_eps"])),
                "bd_masked_ce": jnp.sum(jnp.where(masked, ce, 0.0))
                / jnp.maximum(held, 1),
                "bd_empty_blocks": jnp.mean(~jnp.any(blocks, -1)),
            }
        return loss, dict(
            {name: jnp.stack(c) for name, c in zip(MOE_COUNTS, zip(*found))},
            **counts)
