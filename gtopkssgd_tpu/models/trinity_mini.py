"""Trinity-Mini's AFMoE decoder: gated grouped-query attention, three
sliding-window layers (rotary positions, a query sees itself and the
``sliding_window`` - 1 keys before it) to one full layer (no position
encoding at all), a norm before and after every mixer and feed-forward,
leading dense SwiGLU layers and then a sparse mixture of experts with an
ungated shared expert, a sigmoid router and a balancing bias that no
gradient reaches
(https://huggingface.co/arcee-ai/Trinity-Mini, ``model_type`` afmoe).

The layer equations are written out in
``perfbench/refmodels/trinity_mini.py`` (the frozen plain reference;
parameter names and shapes are equal leaf for leaf,
``tests/test_trinity_mini.py`` holds the two together). What differs here is
how they are computed:

  * the attention is ``models/decoder.py``'s ``blocked_causal_attention``,
    told each layer's window (``sliding_window`` or None). On a TPU at
    whole tiles it runs as the fused flash kernels of
    ``ops/flash_attention.py``, one call a layer for the whole sequence
    forward and two backward: the scores stay in VMEM, and a query tile of
    512 visits the 5 key tiles of 32 that hold its window (every tile up to
    the diagonal in the full layer), so four layers of five never touch
    the pairs the window cuts away. Everywhere else XLA's products in
    blocks of queries: a full layer's block against the keys up to its own
    end, a sliding layer's against the ``sliding_window`` + block keys that
    end with it, the blocks past the first window alike and one
    ``lax.map``. ``forms`` says which compiled (``attention_form``);
  * the expert layer is the zoo's dropless share of an expert group
    (``SparseMoE``), told by ``sizes`` that its router scores with a
    sigmoid, chooses on score + bias, weighs by the score alone and scales
    by ``route_scale``, and that its shared expert has no gate. The bias
    lives in the flax collection ``batch_stats`` (the one collection
    outside the parameters that the trainer, the benchmark's harness and
    its reference all thread): it is in no gradient, no flat vector, no
    residual and no top-k, and a training step moves it from its own
    routing counts;
  * the head and the loss a sequence (``LOSS_ROWS`` tokens of it) at a
    time; every layer under ``jax.checkpoint``, which keeps by name
    (``KEPT_ATTENTION``) the attention's output and, in the kernel form,
    its rows' log-sum-exp: the forward kernel runs once a step and the
    backward kernels take both from there; in the blocked form a block
    runs twice a step, forward and for its own backward.

Precision is the reference's: float32 parameters, residual stream, norms,
rotary, router, softmax, gates and loss; matrix products in ``dtype`` with
float32 accumulation.

Stages are named for the device trace (``layer/attn_window``,
``layer/attn_full``: a layer's whole mixer with its two norms and residual
add; ``layer/dense_mlp``; ``layer/moe_router``, ``layer/moe_experts``,
``layer/shared_expert``; ``layer/head``), forward and backward alike, and
within the two attention kinds by part (``part/proj``, ``part/pointwise``,
``part/layout``, ``part/kernel``). With the loss go the held experts' loads
and dropped slots (always 0) and every expert's selection count and bias,
for ``obs.counters``.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from gtopkssgd_tpu.models.decoder import (
    BALANCE_COUNTS, F32, DenseMLP, SparseMoE, _normal, attention_form,
    blocked_causal_attention, decoder_shell, dense, kept_by_name,
    normed_mixer, rms_norm0, rotary)

# The published sizes (config.json of Trinity-Mini) with the four cuts of
# perfbench/configs/trinity_mini_26b_a3b_ep16.json, whose ``sizes`` a test
# holds equal to this preset key for key; and the size every CPU test runs.
# Layer i is dense while i < ``num_dense_layers``; ``layer_kinds`` is the
# published ``layer_types`` of the layers kept (published layers 1 and 4-7).
PRESETS = {
    "26b_a3b_ep16": dict(
        hidden_size=2048, num_hidden_layers=5, num_dense_layers=1,
        layer_kinds="sliding,sliding,sliding,sliding,full",
        sliding_window=2048,
        num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        rope_theta=10000, rms_norm_eps=1e-5, mup_enabled=True,
        intermediate_size=6144,
        num_experts=128, num_experts_per_tok=8, moe_intermediate_size=1024,
        num_shared_experts=1, score_func="sigmoid", route_norm=True,
        route_scale=2.826, load_balance_coeff=0.001,
        experts_held=8, expert_offset=0, expert_parallel=16,
        vocab_size=200192, vocab_rows=25024, seq_len=16384),
    "tiny": dict(
        hidden_size=64, num_hidden_layers=5, num_dense_layers=1,
        layer_kinds="sliding,sliding,sliding,sliding,full",
        sliding_window=16,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        rope_theta=10000, rms_norm_eps=1e-5, mup_enabled=True,
        intermediate_size=96,
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
        num_shared_experts=1, score_func="sigmoid", route_norm=True,
        route_scale=2.826, load_balance_coeff=0.001,
        experts_held=4, expert_offset=0, expert_parallel=4,
        vocab_size=1024, vocab_rows=128, seq_len=64),
}

# What a layer's remat keeps from its forward to its backward pass, by
# ``checkpoint_name``: the attention's output before its gate ([B, S, H, D]
# float32, 268 MB a layer at 16,384 tokens, 1.34 GB over the five) and, in
# the kernel form, its rows' log-sum-exp ([B, G, R, S] float32, 2 MB).
KEPT_ATTENTION = "afmoe_attn_out"


def query_block_of(seq_len: int) -> int:
    return min(512, max(1, seq_len // 8))


def is_dense(sizes, i):
    return i < sizes["num_dense_layers"]


def is_sliding(sizes, i):
    return sizes["layer_kinds"].split(",")[i] == "sliding"


def moe_sizes(sizes):
    """``sizes`` under the names ``SparseMoE`` reads."""
    return dict(
        sizes, norm_topk_prob=sizes["route_norm"], shared_expert_gate=False,
        shared_expert_intermediate_size=sizes["num_shared_experts"]
        * sizes["moe_intermediate_size"])


class GatedAttention(nn.Module):
    sizes: dict
    dtype: Any
    sliding: bool

    @nn.compact
    def __call__(self, h):
        s, dtype = self.sizes, self.dtype
        d, dim = s["hidden_size"], s["head_dim"]
        heads, kv_heads = s["num_attention_heads"], s["num_key_value_heads"]
        w_q = self.param("q_proj", _normal(), (d, heads * dim), F32)
        w_kv = self.param("kv_proj", _normal(), (d, 2 * kv_heads * dim), F32)
        w_g = self.param("gate_proj", _normal(), (d, heads * dim), F32)
        w_qn = self.param("q_norm", nn.initializers.zeros, (dim,), F32)
        w_kn = self.param("k_norm", nn.initializers.zeros, (dim,), F32)
        w_o = self.param("o_proj", _normal(), (heads * dim, d), F32)

        batch, length = h.shape[:2]
        if self.is_initializing():
            # Every parameter is made; the rest would be traced for shapes
            # alone at every start.
            return jnp.zeros(h.shape, dtype)
        eps = s["rms_norm_eps"]
        with jax.named_scope("part/proj"):
            q = dense(h, w_q, dtype).reshape(batch, length, heads, dim)
            kv = dense(h, w_kv, dtype).reshape(batch, length, 2, kv_heads, dim)
            gate = dense(h, w_g, dtype).astype(F32)
        with jax.named_scope("part/pointwise"):
            q = rms_norm0(q, w_qn, eps)
            k, v = rms_norm0(kv[:, :, 0], w_kn, eps), kv[:, :, 1].astype(F32)
            if self.sliding:
                q, k = (rotary(a, s["rope_theta"], dim) for a in (q, k))
        # Its own parts inside: part/layout and part/kernel.
        out = blocked_causal_attention(
            q, k, v, dtype, query_block_of(s["seq_len"]),
            s["sliding_window"] if self.sliding else None, KEPT_ATTENTION)
        with jax.named_scope("part/pointwise"):
            out = out.reshape(batch, length, heads * dim) \
                * jax.nn.sigmoid(gate)
        with jax.named_scope("part/proj"):
            return dense(out, w_o, dtype)


class Layer(nn.Module):
    """(x, the expert layer's counts or None for a dense layer)."""
    sizes: dict
    dtype: Any
    sliding: bool
    dense_mlp: bool

    @nn.compact
    def __call__(self, x):
        s = self.sizes
        d, eps = s["hidden_size"], s["rms_norm_eps"]
        w_in, w_post_attn, w_pre_mlp, w_post_mlp = (
            self.param(name, nn.initializers.zeros, (d,), F32)
            for name in ("input_norm", "post_attn_norm", "pre_mlp_norm",
                         "post_mlp_norm"))
        # The layer's own norms and residual adds count for the kind they
        # feed and, in an attention kind, for its part/pointwise; scopes
        # inside the mixer and the expert layer are innermost (the three
        # levels of names: trainer._build_train_step).
        with jax.named_scope("layer/attn_window" if self.sliding
                             else "layer/attn_full"):
            x = normed_mixer(
                x, GatedAttention(s, self.dtype, self.sliding, name="mixer"),
                w_in, eps, w_post_attn)
        if self.dense_mlp:
            with jax.named_scope("layer/dense_mlp"):
                y = DenseMLP(s, self.dtype, name="mlp")(
                    rms_norm0(x, w_pre_mlp, eps))
                return x + rms_norm0(y, w_post_mlp, eps), None
        with jax.named_scope("layer/moe_router"):
            y, load, dropped, (chosen, bias) = SparseMoE(
                moe_sizes(s), self.dtype, name="moe")(
                    rms_norm0(x, w_pre_mlp, eps))
            return x + rms_norm0(y, w_post_mlp, eps), \
                (load, dropped, chosen, bias)


class TrinityMini(nn.Module):
    """``__call__(tokens, targets)`` gives the mean cross-entropy and the
    expert layers' counts ``{"moe_load": [layers, held], "moe_dropped":
    [layers], "moe_count": [layers, experts], "moe_bias": [layers,
    experts]}`` (the expert layers alone); without targets, the logits
    [B, S, vocab_rows]."""
    preset: str = "26b_a3b_ep16"
    dtype: Any = jnp.float32

    @property
    def sizes(self):
        return PRESETS[self.preset]

    def forms(self, length):
        """What the step compiles as at sequences of ``length``, for the
        run's manifest and ``train`` records: a run on the chip that fell
        back to the blocked attention says so."""
        return {"attention_form": attention_form(
            length, self.sizes["head_dim"])}

    @nn.compact
    def __call__(self, tokens, targets=None, *, train: bool = False):
        s = self.sizes
        by_name = kept_by_name(KEPT_ATTENTION)
        return decoder_shell(
            self, tokens, targets,
            lambda i: nn.remat(Layer, policy=by_name)(
                s, self.dtype, is_sliding(s, i), is_dense(s, i),
                name=f"layer_{i}"),
            s["num_hidden_layers"], BALANCE_COUNTS,
            math.sqrt(s["hidden_size"]) if s["mup_enabled"] else None)
