"""Kimi-Linear-48B-A3B's hybrid decoder: Kimi Delta Attention x3 : latent
attention without position encoding x1, each followed by a sparse mixture
of experts with an ungated shared expert under a sigmoid router whose
balancing bias no gradient reaches
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct,
``model_type`` kimi_linear; arXiv:2510.26692).

The layer equations are written out in ``perfbench/refmodels/kimi_linear.py``
(the frozen plain reference; parameter names and shapes are equal leaf for
leaf, ``tests/test_kimi_linear.py`` holds the two together). What is this
file's own is the KDA mixer; the rest is the zoo's:

  * Kimi Delta Attention is a delta rule whose decay is a number a key
    channel, not a number a head: ``g`` [B, S, H, d_k] from a low-rank gate
    of the token. It runs in the **chunked form** of ``models/delta_rule.py``
    (shared with ``qwen3_next``, which hands the same functions a ``g``
    [B, S, H]): inside a chunk of 64 tokens a unit lower-triangular system
    whose coefficients are built with every exponent at or below zero (the
    decay does not factor out of the in-chunk products; sub-blocks of 16
    rows, the diagonal ones pair by pair), ``KDA_SEGMENT`` chunks at a time
    under a checkpoint of their own, and then only the d_k x d_k state
    crosses chunks (``scan_chunks``, the decay a row scaling of the state).
    XLA's products throughout: the chunk algebra as a kernel is a later
    change. The depthwise convolution, SiLU and unit norms of q, k and v run
    as the two kernels of ``ops/gdn_conv.py`` where ``conv_form`` finds a
    TPU, whole 128-lane heads and whole token blocks (``forms`` says which
    compiled), as ``causal_conv`` and XLA's float32 passes elsewhere;
  * the latent attention is ``models/kanana2.py::LatentAttention``, told by
    ``mla_use_nope`` to leave the 64-wide parts of q and the one shared key
    unturned; the attention itself ``blocked_causal_attention`` (the flash
    kernels on a TPU at whole tiles: ``attention_form``);
  * the expert layer is ``decoder.SparseMoE`` under the names ``moe_sizes``
    gives it (sigmoid scores, choice on score + bias, weights renormalised
    and scaled, one ungated shared expert), its bias in ``batch_stats``;
  * every layer under ``jax.checkpoint``, which keeps by name the chunk
    algebra's outputs (``KEPT_CHUNKS``: u, w, q_in, k_out [n, B, H, C, 128],
    attn [n, B, H, C, C] and the decay [n, B, H, 128], 0.61 GB a layer at
    8,192 tokens), the attention's output (``kanana2.KEPT_ATTENTION``) and
    the expert layer's dispatch, so that a layer's replay runs neither the
    chunk algebra nor the attention's forward kernel a second time.

Precision is the reference's: float32 parameters, residual stream, norms,
convolution, gates, the delta rule's state, decay and chunk algebra (its
small products at the highest matmul precision), router, softmax and loss;
matrix products in ``dtype`` with float32 accumulation.

Stages are named for the device trace: ``layer/kda_proj`` (the layer's
input norm, projections, convolution, unit norms, gates, the gated norm,
the output projection and the residual add), ``layer/kda_scan`` (chunk
algebra and the state's pass), ``layer/attn_latent`` with Kanana's four
parts, ``layer/moe_router``, ``layer/moe_experts``, ``layer/shared_expert``,
``layer/head``. The KDA layers' most negative in-chunk log decay and mean
beta go out with the loss beside the expert layers' counts
(``obs.counters``' ``kda`` group).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.numpy import log as _ln   # graftlint reads any x.log(...) as a metrics call

from gtopkssgd_tpu.models import decoder, kanana2
from gtopkssgd_tpu.models.decoder import (
    BALANCE_COUNTS, F32, SparseMoE, _normal, attention_form, decoder_shell,
    dense, kept_by_name, normed_mixer, rms_norm0)
from gtopkssgd_tpu.models.delta_rule import (
    causal_conv, chunk_of, delta_chunks_by_segments, pad_to_chunks,
    scan_chunks)
from gtopkssgd_tpu.models.qwen3_next import _conv, _dt_bias, kernel_conv
from gtopkssgd_tpu.ops import gdn_conv as conv_kernels

# The published sizes (config.json of Kimi-Linear-48B-A3B-Instruct; the
# ``kda_*`` keys are its ``linear_attn_config`` group's ``num_heads``,
# ``head_dim`` and ``short_conv_kernel_size``) with the four cuts of
# perfbench/configs/kimi_linear_48b_a3b_ep32.json, whose ``sizes`` a test
# holds equal to this preset key for key; and the size every CPU test runs.
# ``kda_gate_rank`` and ``load_balance_coeff`` are assumed (the file's
# ``assumed``); ``layer_kinds`` names the mixer of every layer held.
PRESETS = {
    "48b_a3b_ep32": dict(
        hidden_size=2304, num_hidden_layers=4, first_k_dense_replace=0,
        layer_kinds="kda,kda,kda,mla",
        kda_num_heads=32, kda_head_dim=128, kda_conv_kernel_size=4,
        kda_gate_rank=128,
        num_attention_heads=32, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, mla_use_nope=True,
        rope_theta=10000, rms_norm_eps=1e-5,
        num_experts=256, num_experts_per_token=8, moe_intermediate_size=1024,
        num_shared_experts=1, moe_router_activation_func="sigmoid",
        moe_renormalize=True, routed_scaling_factor=2.446,
        load_balance_coeff=0.001,
        experts_held=8, expert_offset=0, expert_parallel=32,
        vocab_size=163840, vocab_rows=20480, seq_len=8192),
    "tiny": dict(
        hidden_size=64, num_hidden_layers=2, first_k_dense_replace=0,
        layer_kinds="kda,mla",
        kda_num_heads=4, kda_head_dim=16, kda_conv_kernel_size=4,
        kda_gate_rank=16,
        num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, mla_use_nope=True,
        rope_theta=10000, rms_norm_eps=1e-5,
        num_experts=16, num_experts_per_token=4, moe_intermediate_size=32,
        num_shared_experts=1, moe_router_activation_func="sigmoid",
        moe_renormalize=True, routed_scaling_factor=2.446,
        load_balance_coeff=0.001,
        experts_held=4, expert_offset=0, expert_parallel=4,
        vocab_size=1024, vocab_rows=128, seq_len=128),
}

# What a KDA layer's remat keeps from its forward to its backward pass, by
# ``checkpoint_name``: the chunk algebra's six outputs (module docstring).
# Every layer keeps: the published step compiled for a described v5e reads
# under the line with all four layers' kept (tests/test_kda_compile.py).
KEPT_CHUNKS = "kda_chunks"
# Chunks whose algebra is live at once (``delta_chunks_by_segments``): at
# the published widths a segment's pairwise sub-blocks are 16 x 32 heads x
# 4 x [16, 16, 128] float32 = 0.27 GB a sequence where XLA writes them out.
KDA_SEGMENT = 16

# What a KDA layer counts beside its expert layer's four.
KDA_COUNTS = ("kda_log_decay_min", "kda_beta_mean")


def kinds_of(sizes):
    return tuple(sizes["layer_kinds"].split(","))


def moe_sizes(sizes):
    """``sizes`` under the names ``SparseMoE`` reads."""
    return dict(
        sizes, num_experts_per_tok=sizes["num_experts_per_token"],
        score_func=sizes["moe_router_activation_func"],
        norm_topk_prob=sizes["moe_renormalize"],
        route_scale=sizes["routed_scaling_factor"], shared_expert_gate=False,
        shared_expert_intermediate_size=sizes["num_shared_experts"]
        * sizes["moe_intermediate_size"])


def _a_log(key, shape, dtype=F32):
    return _ln(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def conv_form(length, width, d_k):
    """``kernel`` where a KDA layer's convolution, SiLU and unit norms run
    as the two Pallas kernels of ``ops/gdn_conv.py``, ``xla`` where as
    ``causal_conv`` and XLA's float32 passes: the kernels need a TPU, q, k
    and v (``width`` channels each, every one convolved) of whole 128-lane
    heads and a length of whole token blocks."""
    whole = conv_kernels.blocks_of(
        length, 3 * width, 3 * width, width, d_k) is not None
    return "kernel" if decoder.on_tpu() and whole else "xla"


@functools.partial(jax.checkpoint, static_argnums=(2,))
def convolved(qkv, conv, d_k):
    """``kernel_conv`` in XLA's passes: q (over sqrt(d_k)), k, v
    [B, S, H d_k] float32 from the projection's output [B, S, 3 H d_k],
    their float32 intermediates live again for the backward pass only."""
    batch, length, width = qkv.shape
    qkv = jax.nn.silu(causal_conv(qkv.astype(F32), conv)).reshape(
        batch, length, 3, -1, d_k)
    unit = lambda a: a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    flat = lambda a: a.reshape(batch, length, width // 3)
    return (flat(unit(qkv[:, :, 0]) / math.sqrt(d_k)),
            flat(unit(qkv[:, :, 1])), flat(qkv[:, :, 2]))


class KimiDeltaAttention(nn.Module):
    """(y, the most negative in-chunk log decay of any channel, the mean
    beta)."""
    sizes: dict
    dtype: Any

    @nn.compact
    def __call__(self, h):
        s, dtype = self.sizes, self.dtype
        d, heads, d_k = s["hidden_size"], s["kda_num_heads"], s["kda_head_dim"]
        rank = s["kda_gate_rank"]
        width = heads * d_k
        w_qkv = self.param("in_proj_qkv", _normal(), (d, 3 * width), F32)
        w_fzb = self.param("in_proj_fzb", _normal(), (d, 2 * rank + heads),
                           F32)
        conv = self.param("conv", _conv,
                          (s["kda_conv_kernel_size"], 3 * width), F32)
        w_f = self.param("f_proj", _normal(), (rank, width), F32)
        dt_bias = self.param("dt_bias", _dt_bias, (width,), F32)
        a_log = self.param("A_log", _a_log, (heads,), F32)
        w_z = self.param("z_proj", _normal(), (rank, width), F32)
        w_g = self.param("norm", nn.initializers.ones, (d_k,), F32)
        w_out = self.param("out_proj", _normal(), (width, d), F32)

        batch, length = h.shape[:2]
        if self.is_initializing():
            # Every parameter is made; the rest would be traced for shapes
            # alone at every start.
            return jnp.zeros(h.shape, dtype), jnp.zeros((), F32), \
                jnp.zeros((), F32)
        chunk = chunk_of(s["seq_len"])
        by_head = lambda a: a.reshape(batch, length, heads, d_k)
        with jax.named_scope("layer/kda_proj"):
            qkv = dense(h, w_qkv, dtype)
            fzb = dense(h, w_fzb, dtype)
            f, z, b = (fzb[..., :rank], fzb[..., rank:2 * rank],
                       fzb[..., 2 * rank:])
            fused = conv_form(length, width, d_k) == "kernel"
            q, k, v = map(by_head, kernel_conv(qkv, conv, width, d_k)
                          if fused else convolved(qkv, conv, d_k))
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(by_head(
                dense(f, w_f, dtype).astype(F32) + dt_bias))
            beta = jax.nn.sigmoid(b.astype(F32))
        with jax.named_scope("layer/kda_scan"):
            arrays, _ = pad_to_chunks((q, k, v, g, beta), chunk)
            # The log decay a chunk adds up to, per channel: gamma_C.
            fallen = lax.stop_gradient(arrays[3]).reshape(
                batch, -1, chunk, heads, d_k).sum(2)
            prepared = delta_chunks_by_segments(*arrays, chunk, KDA_SEGMENT)
            # The state crosses the chunks of every sequence in one pass.
            o = scan_chunks(*(checkpoint_name(a, KEPT_CHUNKS)
                              for a in prepared))[:, :length]
        with jax.named_scope("layer/kda_proj"):
            gate = jax.nn.sigmoid(by_head(dense(z, w_z, dtype).astype(F32)))
            o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + s["rms_norm_eps"]) * w_g
            y = dense((o * gate).reshape(batch, length, width), w_out, dtype)
        return y, jnp.min(fallen), jnp.mean(lax.stop_gradient(beta))


class Layer(nn.Module):
    """(x, the expert layer's counts and the KDA mixer's two: zeros from a
    latent-attention layer, which ``KimiLinear`` leaves out)."""
    sizes: dict
    dtype: Any
    kind: str

    @nn.compact
    def __call__(self, x):
        s = self.sizes
        d, eps = s["hidden_size"], s["rms_norm_eps"]
        w_in, w_pre_mlp = (
            self.param(name, nn.initializers.zeros, (d,), F32)
            for name in ("input_norm", "pre_mlp_norm"))
        # The layer's input norm and residual add count for the mixer's
        # kind; scopes inside the mixer and the expert layer are innermost
        # (trainer._build_train_step).
        if self.kind == "kda":
            with jax.named_scope("layer/kda_proj"):
                y, fallen, beta = KimiDeltaAttention(
                    s, self.dtype, name="mixer")(rms_norm0(x, w_in, eps))
                x = x + y
        else:
            with jax.named_scope("layer/attn_latent"):
                x = normed_mixer(
                    x, kanana2.LatentAttention(s, self.dtype, name="mixer"),
                    w_in, eps)
            fallen = beta = jnp.zeros((), F32)
        with jax.named_scope("layer/moe_router"):
            y, load, dropped, (chosen, bias) = SparseMoE(
                moe_sizes(s), self.dtype, name="moe")(
                    rms_norm0(x, w_pre_mlp, eps))
            return x + y, (load, dropped, chosen, bias, fallen, beta)


class KimiLinear(nn.Module):
    """``__call__(tokens, targets)`` gives the mean cross-entropy and the
    layers' counts ``{"moe_load": [layers, held], "moe_dropped": [layers],
    "moe_count": [layers, experts], "moe_bias": [layers, experts],
    "kda_log_decay_min": [KDA layers], "kda_beta_mean": [KDA layers]}``;
    without targets, the logits [B, S, vocab_rows]."""
    preset: str = "48b_a3b_ep32"
    dtype: Any = jnp.float32

    @property
    def sizes(self):
        return PRESETS[self.preset]

    def forms(self, length):
        """What the step compiles as at sequences of ``length``, for the
        run's manifest and ``train`` records: the chunk algebra and the
        state's pass are XLA's wherever it runs."""
        s = self.sizes
        return {"attention_form": attention_form(
                    length, s["qk_nope_head_dim"] + s["qk_rope_head_dim"],
                    s["v_head_dim"]),
                "conv_form": conv_form(
                    length, s["kda_num_heads"] * s["kda_head_dim"],
                    s["kda_head_dim"]),
                "delta_form": "xla", "scan_form": "xla"}

    @nn.compact
    def __call__(self, tokens, targets=None, *, train: bool = False):
        s = self.sizes
        kinds = kinds_of(s)
        by_name = kept_by_name(KEPT_CHUNKS, kanana2.KEPT_ATTENTION)
        out = decoder_shell(
            self, tokens, targets,
            lambda i: nn.remat(Layer, policy=by_name)(
                s, self.dtype, kinds[i], name=f"layer_{i}"),
            len(kinds), BALANCE_COUNTS + KDA_COUNTS)
        if targets is None:
            return out
        loss, counts = out
        kda = jnp.asarray([i for i, kind in enumerate(kinds) if kind == "kda"])
        return loss, dict(counts, **{
            name: counts[name][kda] for name in KDA_COUNTS})
