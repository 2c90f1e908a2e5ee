"""The Gated DeltaNet's convolution kernels (ops/gdn_conv.py) in interpret
mode on the CPU, against the XLA lines they replace on the TPU
(models/qwen3_next.py::prepare: ``causal_conv``, SiLU, the split and the unit
norms, the oracle): q, k and v and the gradients in the activations and the
taps; the halos at a sequence's start, at a block boundary and at its end;
the rule that chooses between the two forms; a whole layer through the
kernels. (That the kernels compile for the chip is
tests/test_flash_compile.py's; ``tiny`` through them and the form's name in
a run's records are tests/test_delta_chunks_kernel.py's, beside the chunk
kernels'.)"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gtopkssgd_tpu.models import decoder, qwen3_next  # noqa: E402
from gtopkssgd_tpu.ops import gdn_conv as kernels  # noqa: E402
from tests.test_flash_attention_kernel import pallas_calls, rel  # noqa: E402

F32 = jnp.float32
HEAD, KEYS, VALUES = 128, 2, 3
KEY_W, VAL_W = KEYS * HEAD, VALUES * HEAD
CONV_W = 2 * KEY_W + VAL_W
# Two sequences of three token blocks of 32; the layer's output gate rides
# behind the convolved columns, as in ``in_proj_qkvz``'s output.
BATCH, LENGTH, BLOCK = 2, 96, 32


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(kernels, "TOKENS", BLOCK)


def xla_form(x, taps):
    """``prepare``'s XLA lines, on flat heads."""
    a = jax.nn.silu(qwen3_next.causal_conv(x[..., :CONV_W].astype(F32), taps))
    heads = lambda a: a.reshape(a.shape[:2] + (-1, HEAD))
    unit = lambda a: a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    flat = lambda a: a.reshape(a.shape[:2] + (-1,))
    return (flat(unit(heads(a[..., :KEY_W])) / math.sqrt(HEAD)),
            flat(unit(heads(a[..., KEY_W:2 * KEY_W]))), a[..., 2 * KEY_W:])


def kernel_form(x, taps):
    return qwen3_next.kernel_conv(x, taps, KEY_W, HEAD)


def inputs(dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(keys[0], (BATCH, LENGTH, CONV_W + VAL_W))
    taps = jax.random.uniform(keys[1], (4, CONV_W), F32, -0.5, 0.5)
    weights = [jax.random.normal(key, (BATCH, LENGTH, width))
               for key, width in zip(keys[2:], (KEY_W, KEY_W, VAL_W))]
    return x.astype(dtype), taps, weights


def pulled(form, x, taps, weights):
    return jax.jit(jax.grad(lambda x, t: sum(
        jnp.sum(o * w) for o, w in zip(form(x, t), weights)),
        argnums=(0, 1)))(x, taps)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_kernels_outputs_are_the_xla_forms(dtype):
    """q, k [B, S, 256] and v [B, S, 384] float32 from activations in either
    dtype: one arithmetic (on the chip the bits are equal, PERF.md section
    6, PR 43)."""
    x, taps, _ = inputs(dtype)
    got, want = jax.jit(kernel_form)(x, taps), jax.jit(xla_form)(x, taps)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape and a.dtype == F32, name
        assert rel(a, b) < 2e-7, (name, rel(a, b))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_kernels_gradients_are_the_xla_forms(dtype):
    """A scalar of q, k and v differentiated in the activations (in their
    dtype; zero under the gate's columns, which the kernels never read) and
    in the taps (a sum over every token of both sequences)."""
    x, taps, weights = inputs(dtype)
    (d_x, d_taps), (want_x, want_taps) = (
        pulled(form, x, taps, weights) for form in (kernel_form, xla_form))
    assert d_x.shape == x.shape and d_x.dtype == dtype
    assert not np.asarray(d_x[..., CONV_W:], np.float32).any()
    # A bfloat16 cotangent differs by a rounding of its last bit here and
    # there: 2^-8 on one element in some hundred.
    assert rel(d_x, want_x) < (1e-4 if dtype == jnp.bfloat16 else 5e-7)
    assert d_taps.shape == taps.shape
    assert rel(d_taps, want_taps) < 1e-6, rel(d_taps, want_taps)


def only_rows(a, rows):
    """``a`` with every token but ``rows`` of sequence 1 set to zero."""
    keep = np.zeros(a.shape[:2] + (1,), bool)
    keep[1, rows] = True
    return jnp.where(keep, a, 0.0).astype(a.dtype)


@pytest.mark.parametrize("rows,out_rows,reads", [
    # What an unmasked halo would hand block 0 of sequence 1: the last
    # three rows of the halo tile its index clamps to, its own rows 13..15.
    (slice(13, 16), slice(0, 3), False),
    # The last three tokens of block 0: block 1's first three read them
    # through the halo and through nothing else.
    (slice(29, 32), slice(32, 35), True),
], ids=["start_masked", "boundary_read"])
def test_the_forward_halo_is_the_padded_convolutions(rows, out_rows, reads):
    x, taps, _ = inputs(F32, seed=1)
    x = only_rows(x, rows)
    got, want = jax.jit(kernel_form)(x, taps), jax.jit(xla_form)(x, taps)
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-6
        assert bool(jnp.any(a[1, out_rows] != 0.0)) == reads
        assert not bool(jnp.any(a[0]))        # the other sequence: SiLU(0)


@pytest.mark.parametrize("rows,out_rows,reads", [
    # What an unmasked following halo would hand the last block: the first
    # three rows of the tile its index clamps to, the sequence's rows 88..90.
    (slice(88, 91), slice(93, 96), False),
    # Block 1's first three cotangents reach block 0's last three tokens
    # through the following halo alone (their own pre-activations made
    # again from the activations' halo).
    (slice(32, 35), slice(29, 32), True),
], ids=["end_masked", "boundary_read"])
def test_the_transposed_convolutions_halo_is_the_following_tokens(
        rows, out_rows, reads):
    x, taps, weights = inputs(F32, seed=2)
    weights = [only_rows(w, rows) for w in weights]
    (d_x, d_taps), (want_x, want_taps) = (
        pulled(form, x, taps, weights) for form in (kernel_form, xla_form))
    assert float(jnp.max(jnp.abs(d_x - want_x))) < 1e-5
    assert rel(d_taps, want_taps) < 1e-6
    assert bool(jnp.any(d_x[1, out_rows] != 0.0)) == reads
    assert not bool(jnp.any(d_x[0]))


PUBLISHED = qwen3_next.PRESETS["80b_a3b_ep64"]


@pytest.mark.parametrize("tpu,length,key_w,val_w,d_k,form", [
    (False, 4096, 2048, 4096, 128, "xla"),     # the CPU: every test's path
    (True, 4096, 2048, 4096, 128, "kernel"),   # the hybrid decoder's cell
    (True, 16384, 512, 1024, 256, "kernel"),   # heads of two lane rows
    (True, 4000, 2048, 4096, 128, "xla"),      # 15.6 token blocks
    (True, 96, 2048, 4096, 128, "kernel"),     # one short block of 6 tiles
    (True, 100, 2048, 4096, 128, "xla"),       # ... of 6.25
    (True, 4096, 1024, 4096, 64, "xla"),       # a head of half a lane row
    (True, 4096, 384, 768, 384, "xla"),        # no block of whole heads
    (True, 128, 32, 64, 16, "xla"),            # ``tiny``
])
def test_the_conv_form_follows_the_backend_and_the_shapes(
        tpu, length, key_w, val_w, d_k, form, monkeypatch):
    """No flag and no preset's name: the kernels where the backend is a TPU,
    q's, k's and v's channels are whole 128-lane heads and the length whole
    token blocks; ``causal_conv`` and XLA's passes everywhere else."""
    monkeypatch.undo()                         # the published block of 256
    assert jax.default_backend() == "cpu" and not decoder.on_tpu()
    monkeypatch.setattr(decoder, "on_tpu", lambda: tpu)
    assert qwen3_next.conv_form(length, key_w, val_w, d_k) == form


@pytest.mark.parametrize("preset,length", [
    ("80b_a3b_ep64", PUBLISHED["seq_len"]), ("tiny", 128)])
def test_the_models_forms_name_the_conv_form(preset, length, monkeypatch):
    monkeypatch.undo()
    model = qwen3_next.Qwen3Next(preset)
    assert model.forms(length)["conv_form"] == "xla"
    monkeypatch.setattr(decoder, "on_tpu", lambda: True)
    on_chip = {"attention_form": "blocked", "delta_form": "xla",
               "scan_form": "xla",
               "conv_form": "xla"} if preset == "tiny" else dict.fromkeys(
                   ("attention_form", "delta_form", "scan_form", "conv_form"),
                   "kernel")
    assert model.forms(length) == on_chip


def test_a_layer_through_the_kernels_is_the_same_layer(monkeypatch):
    """One ``GatedDeltaNet`` (a key head and two value heads of 128, chunks
    of 32) with the convolution in kernels and the chunks' algebra in XLA,
    so a sequence at a time inside ``lax.map(prepare)``: output and every
    gradient are the XLA form's to float32 rounding, and the step holds the
    forward kernel twice (``prepare``'s checkpoint makes q, k and v again
    for the chunks' backward) and the backward kernel once."""
    sizes = dict(qwen3_next.PRESETS["tiny"], linear_num_key_heads=1,
                 linear_num_value_heads=2, linear_key_head_dim=HEAD,
                 linear_value_head_dim=HEAD)
    layer = qwen3_next.GatedDeltaNet(sizes, F32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, sizes["hidden_size"]))
    params = jax.jit(layer.init)({"params": jax.random.PRNGKey(4)}, x)[
        "params"]
    weight = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    grad = jax.value_and_grad(lambda p, x: jnp.sum(
        layer.apply({"params": p}, x) * weight), argnums=(0, 1))
    want = jax.jit(grad)(params, x)
    assert not pallas_calls(jax.make_jaxpr(grad)(params, x).jaxpr)
    monkeypatch.setattr(qwen3_next, "conv_form", lambda *a: "kernel")
    jax.clear_caches()          # or the second trace is the first's
    got = jax.jit(grad)(params, x)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        assert rel(a, b) < 1e-5, (jax.tree_util.keystr(path), rel(a, b))
    assert {name: len(grids) for name, grids in pallas_calls(
        jax.make_jaxpr(grad)(params, x).jaxpr).items()} == {
            "gdn_conv_forward": 2, "gdn_conv_backward": 1}
    jax.clear_caches()
