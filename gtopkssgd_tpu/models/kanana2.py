"""Kanana-2-30B-A3B's decoder: multi-head latent attention (keys and values
made from a ``kv_lora_rank``-wide latent, one rotary key shared by all the
heads, a key head wider than a value head), a leading dense SwiGLU layer
and then a sparse mixture of experts with ungated shared experts under a
sigmoid router whose balancing bias no gradient reaches
(https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601,
``model_type`` deepseek_v3 with ``q_lora_rank`` null).

The layer equations are written out in ``perfbench/refmodels/kanana2.py``
(the frozen plain reference; parameter names and shapes are equal leaf for
leaf, ``tests/test_kanana2.py`` holds the two together). What is this
file's own is the mixer; the rest is the zoo's (``models/decoder.py``):

  * the latent attention makes q (per head ``qk_nope_head_dim`` |
    ``qk_rope_head_dim``), the latent c_kv beside the one rotary key k_pe,
    and from RMSNorm(c_kv) every head's k_nope | v; rotary is the
    interleaved one (pairs (x_2i, x_2i+1), computed as HF does: the pairs
    pulled apart, then rotate-half); k_pe is repeated for the heads and
    joined to k_nope, so the attention itself is ``blocked_causal_attention``
    with 32 key-value heads of a 192-wide key and a 128-wide value, scaled
    by 1 / sqrt(192). On a TPU at whole tiles it runs as the fused flash
    kernels of ``ops/flash_attention.py`` (``forms`` says which compiled:
    ``attention_form``), everywhere else as XLA's query blocks. The rotary
    is applied iff the sizes say so: a model whose latent attention carries
    no position (``mla_use_nope``; ``models/kimi_linear.py`` builds its
    full-attention layers from this class) keeps q_pe and k_pe as they are
    projected, and everything else of the mixer is the same;
  * the expert layer is ``SparseMoE``, told by ``moe_sizes`` that its router
    scores with a sigmoid, chooses on score + bias, weighs by the score
    alone, renormalises and scales by ``routed_scaling_factor``, and that
    ``n_shared_experts`` shared experts are one ungated MLP. The bias
    (``e_score_correction_bias``) lives in ``batch_stats`` and a training
    step moves it from its own counts (``decoder.balanced_bias``);
  * the head and the loss a sequence (``LOSS_ROWS`` tokens of it) at a
    time; every layer under ``jax.checkpoint``, which keeps by name
    (``KEPT_ATTENTION``) the attention's output and, in the kernel form,
    its rows' log-sum-exp, so that the forward kernel runs once a step.

Precision is the reference's: float32 parameters, residual stream, norms,
rotary, router, softmax and loss; matrix products in ``dtype`` with float32
accumulation.

Stages are named for the device trace: ``layer/attn_latent`` round the
whole mixer with its input norm and residual add and, inside it, the parts
(``part/proj``: W_q, W_kva, W_kvb, W_o; ``part/pointwise``: both norms,
rotary, the residual add; ``part/layout``: the splits, k_pe's repeat and
the joins to 192, to and from the kernels' layout; ``part/kernel``);
``layer/dense_mlp``, ``layer/moe_router``, ``layer/moe_experts``,
``layer/shared_expert``, ``layer/head`` as the other decoders'.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from gtopkssgd_tpu.models.decoder import (
    BALANCE_COUNTS, F32, DenseMLP, SparseMoE, _normal, attention_form,
    blocked_causal_attention, decoder_shell, dense, kept_by_name,
    normed_mixer, rms_norm0, rotary)

# The published sizes (config.json of kanana-2-30b-a3b-instruct-2601) with
# the three cuts of perfbench/configs/kanana2_30b_a3b_ep16.json, whose
# ``sizes`` a test holds equal to this preset key for key; and the size
# every CPU test runs (a key head, 16 | 8, that is no multiple of the value
# head). Layer i is dense while i < ``first_k_dense_replace``.
# ``load_balance_coeff`` is the bias's rate: the config states none
# (DeepSeek-V3's 0.001, arXiv:2412.19437; the file's ``assumed``).
PRESETS = {
    "30b_a3b_ep16": dict(
        hidden_size=2048, num_hidden_layers=5, first_k_dense_replace=1,
        num_attention_heads=32, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_theta=1000000,
        rms_norm_eps=1e-6, intermediate_size=6144,
        n_routed_experts=128, num_experts_per_tok=6, moe_intermediate_size=768,
        n_shared_experts=2, scoring_func="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=2.448, load_balance_coeff=0.001,
        experts_held=8, expert_offset=0, expert_parallel=16,
        vocab_size=128256, vocab_rows=16032, seq_len=8192),
    "tiny": dict(
        hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1,
        num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_theta=1000000,
        rms_norm_eps=1e-6, intermediate_size=96,
        n_routed_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
        n_shared_experts=2, scoring_func="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=2.448, load_balance_coeff=0.001,
        experts_held=4, expert_offset=0, expert_parallel=4,
        vocab_size=1024, vocab_rows=128, seq_len=64),
}

# What a layer's remat keeps from its forward to its backward pass, by
# ``checkpoint_name``: the attention's output ([B, S, H, D_v] float32, 268 MB
# a layer at 2 x 8,192 tokens) and, in the kernel form, its rows'
# log-sum-exp (2 MB).
KEPT_ATTENTION = "mla_attn_out"


def query_block_of(seq_len: int) -> int:
    return min(512, max(1, seq_len // 8))


def is_dense(sizes, i):
    return i < sizes["first_k_dense_replace"]


def moe_sizes(sizes):
    """``sizes`` under the names ``SparseMoE`` reads."""
    return dict(
        sizes, num_experts=sizes["n_routed_experts"],
        score_func=sizes["scoring_func"],
        route_scale=sizes["routed_scaling_factor"], shared_expert_gate=False,
        shared_expert_intermediate_size=sizes["n_shared_experts"]
        * sizes["moe_intermediate_size"])


def rotary_interleaved(x, theta):
    """Rotary embedding of the pairs (x_2i, x_2i+1) of the whole last axis,
    as HF's ``apply_rotary_pos_emb_interleave`` (``rope_interleave``): the
    pairs pulled apart ([x_0, x_2, ..., x_1, x_3, ...]), then rotate-half;
    the result stays in that order, in q and k alike. x [B, S, H, D]
    float32."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    apart = jnp.concatenate([pairs[..., 0], pairs[..., 1]], -1)
    return rotary(apart, theta, x.shape[-1])


class LatentAttention(nn.Module):
    sizes: dict
    dtype: Any

    @nn.compact
    def __call__(self, h):
        s, dtype = self.sizes, self.dtype
        d, heads, rank = (s["hidden_size"], s["num_attention_heads"],
                          s["kv_lora_rank"])
        nope, rope, value = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                             s["v_head_dim"])
        w_q = self.param("q_proj", _normal(), (d, heads * (nope + rope)), F32)
        w_kva = self.param("kv_a_proj", _normal(), (d, rank + rope), F32)
        w_kvn = self.param("kv_a_norm", nn.initializers.zeros, (rank,), F32)
        w_kvb = self.param("kv_b_proj", _normal(),
                           (rank, heads * (nope + value)), F32)
        w_o = self.param("o_proj", _normal(), (heads * value, d), F32)

        batch, length = h.shape[:2]
        if self.is_initializing():
            # Every parameter is made; the rest would be traced for shapes
            # alone at every start.
            return jnp.zeros(h.shape, dtype)
        # A model whose latent attention carries no position
        # (``mla_use_nope``) keeps the p-wide parts of q and the shared key
        # as they are projected.
        turned = (lambda a: a.astype(F32)) if s.get("mla_use_nope") else (
            lambda a: rotary_interleaved(a.astype(F32), s["rope_theta"]))
        with jax.named_scope("part/proj"):
            q = dense(h, w_q, dtype).reshape(batch, length, heads, nope + rope)
            latent = dense(h, w_kva, dtype)
        with jax.named_scope("part/layout"):
            q_nope, q_pe = q[..., :nope], q[..., nope:]
            c_kv, k_pe = latent[..., :rank], latent[:, :, None, rank:]
        with jax.named_scope("part/pointwise"):
            c_kv = rms_norm0(c_kv, w_kvn, s["rms_norm_eps"])
            q_pe, k_pe = turned(q_pe), turned(k_pe)
        with jax.named_scope("part/proj"):
            kv = dense(c_kv, w_kvb, dtype).reshape(
                batch, length, heads, nope + value)
        with jax.named_scope("part/layout"):
            q = jnp.concatenate([q_nope.astype(F32), q_pe], -1)
            k = jnp.concatenate([
                kv[..., :nope].astype(F32),
                jnp.broadcast_to(k_pe, (batch, length, heads, rope))], -1)
            v = kv[..., nope:].astype(F32)
        # Its own parts inside: part/layout and part/kernel.
        out = blocked_causal_attention(
            q, k, v, dtype, query_block_of(s["seq_len"]), None, KEPT_ATTENTION)
        with jax.named_scope("part/proj"):
            return dense(out.reshape(batch, length, heads * value), w_o, dtype)


class Layer(nn.Module):
    """(x, the expert layer's counts or None for a dense layer)."""
    sizes: dict
    dtype: Any
    dense_mlp: bool

    @nn.compact
    def __call__(self, x):
        s = self.sizes
        d, eps = s["hidden_size"], s["rms_norm_eps"]
        w_in, w_pre_mlp = (
            self.param(name, nn.initializers.zeros, (d,), F32)
            for name in ("input_norm", "pre_mlp_norm"))
        # The layer's input norm and residual add count for the mixer's
        # kind and its part/pointwise; scopes inside the mixer and the
        # expert layer are innermost (trainer._build_train_step).
        with jax.named_scope("layer/attn_latent"):
            x = normed_mixer(x, LatentAttention(s, self.dtype, name="mixer"),
                             w_in, eps)
        if self.dense_mlp:
            with jax.named_scope("layer/dense_mlp"):
                return x + DenseMLP(s, self.dtype, name="mlp")(
                    rms_norm0(x, w_pre_mlp, eps)), None
        with jax.named_scope("layer/moe_router"):
            y, load, dropped, (chosen, bias) = SparseMoE(
                moe_sizes(s), self.dtype, name="moe")(
                    rms_norm0(x, w_pre_mlp, eps))
            return x + y, (load, dropped, chosen, bias)


class Kanana2(nn.Module):
    """``__call__(tokens, targets)`` gives the mean cross-entropy and the
    expert layers' counts ``{"moe_load": [layers, held], "moe_dropped":
    [layers], "moe_count": [layers, experts], "moe_bias": [layers,
    experts]}`` (the expert layers alone); without targets, the logits
    [B, S, vocab_rows]."""
    preset: str = "30b_a3b_ep16"
    dtype: Any = jnp.float32

    @property
    def sizes(self):
        return PRESETS[self.preset]

    def forms(self, length):
        """What the step compiles as at sequences of ``length``, for the
        run's manifest and ``train`` records."""
        s = self.sizes
        return {"attention_form": attention_form(
            length, s["qk_nope_head_dim"] + s["qk_rope_head_dim"],
            s["v_head_dim"])}

    @nn.compact
    def __call__(self, tokens, targets=None, *, train: bool = False):
        s = self.sizes
        by_name = kept_by_name(KEPT_ATTENTION)
        return decoder_shell(
            self, tokens, targets,
            lambda i: nn.remat(Layer, policy=by_name)(
                s, self.dtype, is_dense(s, i), name=f"layer_{i}"),
            s["num_hidden_layers"], BALANCE_COUNTS)
