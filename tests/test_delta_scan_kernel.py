"""The Gated DeltaNet's state-pass kernels (ops/delta_scan.py) in interpret
mode on the CPU, against the ``lax.scan`` they replace on the TPU
(models/qwen3_next.py::scan_chunks, the oracle): o and the six gradients,
over several blocks of heads and of chunks; the whole delta rule through
both pairs of kernels against the recurrence; the rule that chooses the
form; the model through the kernels; and the form's name in a run's
records. (That the kernels compile for the chip is
tests/test_hybrid_compile.py's.)"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gtopkssgd_tpu.models import decoder, qwen3_next  # noqa: E402
from gtopkssgd_tpu.ops import delta_scan as kernels  # noqa: E402
from gtopkssgd_tpu.ops import gdn_conv  # noqa: E402
from tests.test_delta_chunks_kernel import both_forms, inputs  # noqa: E402
from tests.test_flash_attention_kernel import (  # noqa: E402
    pallas_calls, rel, tiny_step)
from tests.test_qwen3_next import delta_inputs, recurrence  # noqa: E402

F32 = jnp.float32
CHUNK, DIM = 64, 128
PREPARED = ("u", "w", "attn", "q_in", "k_out", "decay")
# (tokens, sequences, key heads, value heads): whole chunks, a length that
# is padded to 5 chunks, two sequences, and 16 heads: two blocks of 8.
SHAPES = [(256, 1, 1, 2), (300, 1, 1, 2), (256, 2, 2, 2), (128, 1, 4, 16)]
# (heads, chunks) a grid step, against 16 heads and 4 chunks.
BLOCKS = [(8, 1), (8, 2), (16, 4), (8, 3), (4, 4)]


def prepared(length, batch, key_heads, heads):
    """What the state's pass reads, as the XLA form of the chunks' algebra
    makes it: (u, w, attn, q_in, k_out, decay), [n, B, H, C, ...]."""
    return jax.jit(both_forms(heads)[0])(
        *inputs(length, batch, key_heads, heads))


@pytest.mark.parametrize("length,batch,key_heads,heads", SHAPES)
def test_the_kernels_output_is_the_scans(length, batch, key_heads, heads):
    """o [B, S, H, d_v] float32: one arithmetic, product for product; and
    the forward rule's o (the kernel that also writes the states) is the
    primal's to the bit."""
    args = prepared(length, batch, key_heads, heads)
    want = jax.jit(qwen3_next.scan_chunks)(*args)
    got = jax.jit(qwen3_next.kernel_scan_chunks)(*args)
    assert got.shape == want.shape and got.dtype == F32
    assert rel(got, want) < 1e-6, rel(got, want)
    ruled, _ = jax.jit(lambda *a: jax.vjp(
        qwen3_next.kernel_scan_chunks, *a))(*args)
    assert np.array_equal(np.asarray(ruled), np.asarray(got))


def gradients(form, args, weight):
    return jax.jit(jax.grad(lambda *a: jnp.sum(form(*a) * weight),
                            argnums=range(6)))(*args)


@pytest.mark.parametrize("length,batch,key_heads,heads", SHAPES)
def test_the_kernels_gradients_are_autodiffs_through_the_scan(
        length, batch, key_heads, heads):
    """d_u, d_w, d_attn, d_q_in, d_k_out and d_decay of a weighted sum of o:
    the reverse-time kernel against autodiff through ``scan_chunks``."""
    args = prepared(length, batch, key_heads, heads)
    weight = jax.random.normal(
        jax.random.PRNGKey(7), jax.eval_shape(qwen3_next.scan_chunks,
                                              *args).shape)
    for name, a, b in zip(
            PREPARED,
            gradients(qwen3_next.kernel_scan_chunks, args, weight),
            gradients(qwen3_next.scan_chunks, args, weight)):
        assert a.shape == b.shape, name
        assert rel(a, b) < 2e-6, (name, rel(a, b))


@pytest.mark.parametrize("heads,chunks", BLOCKS)
def test_any_block_of_heads_and_chunks_is_the_same_pass(heads, chunks,
                                                        monkeypatch):
    """The grid's blocks are a schedule: 16 heads and 4 chunks in steps of
    8 and 16 heads and 1, 2 and 4 chunks (3 chunks a step fall to the
    common divisor, 1; 4 heads a step, not whole sublane tiles of o's
    ``[H, d_v]``, to all 16) give the o and the gradients of the scan."""
    monkeypatch.setattr(kernels, "HEADS", heads)
    monkeypatch.setattr(kernels, "CHUNKS", chunks)
    jax.clear_caches()          # the kernels are jitted: or the first's blocks
    args = prepared(256, 1, 2, 16)
    calls = pallas_calls(jax.make_jaxpr(
        qwen3_next.kernel_scan_chunks)(*args).jaxpr)
    assert calls == {"delta_scan_forward": [
        (1, 16 // {4: 16}.get(heads, heads), 4 // {3: 1}.get(chunks, chunks))]}
    want = jax.jit(qwen3_next.scan_chunks)(*args)
    got = jax.jit(qwen3_next.kernel_scan_chunks)(*args)
    assert rel(got, want) < 1e-6
    weight = jax.random.normal(jax.random.PRNGKey(5), want.shape)
    for name, a, b in zip(
            PREPARED,
            gradients(qwen3_next.kernel_scan_chunks, args, weight),
            gradients(qwen3_next.scan_chunks, args, weight)):
        assert rel(a, b) < 2e-6, (name, rel(a, b))
    jax.clear_caches()


def test_the_backward_takes_the_state_at_every_chunks_start():
    """The forward rule's second output, [n, B, H, d_k, d_v]: S_0 = 0 and
    S_{i+1} = decay_i S_i + k_out_i^T (u_i - w_i S_i), in float64."""
    args = prepared(256, 1, 1, 2)
    decay = args[5][..., 0, 0].transpose(1, 2, 0)
    _, states = kernels.forward(*args[:5], decay, states=True, interpret=True)
    u, w, _, _, k_out, gamma = (np.asarray(a, np.float64) for a in args)
    state = np.zeros(states.shape[1:])
    for i in range(states.shape[0]):
        assert np.max(np.abs(np.asarray(states[i]) - state)) < 1e-5
        delta = u[i] - w[i] @ state
        state = gamma[i] * state + np.swapaxes(k_out[i], -1, -2) @ delta
    assert not np.any(np.asarray(states[0]))


@pytest.mark.parametrize("length", [128, 100])
def test_the_delta_rule_through_both_kernels_equals_the_recurrence(
        length, monkeypatch):
    """``chunked_delta_rule`` with the chunks' algebra and the state's pass
    in kernels, forward and gradients, against the recurrence token by token
    in float64 and the reference's gradients."""
    from perfbench.refmodels import qwen3_next as ref

    args = delta_inputs(length, 0, 1, 2, DIM, DIM)
    monkeypatch.setattr(qwen3_next, "delta_form", lambda *a: "kernel")
    rule = lambda *a: qwen3_next.chunked_delta_rule(*a, CHUNK)
    want = recurrence(*args)
    assert set(pallas_calls(jax.make_jaxpr(rule)(*args).jaxpr)) == {
        "delta_chunks_forward", "delta_scan_forward"}
    assert "scan" not in {e.primitive.name
                          for e in jax.make_jaxpr(rule)(*args).jaxpr.eqns}
    assert np.max(np.abs(np.asarray(rule(*args)) - want)) < 1e-5
    weight = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    pull = lambda fn: jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2, 3, 4)))(*args)
    for mine, theirs in zip(pull(rule), pull(ref.delta_rule)):
        assert float(jnp.max(jnp.abs(mine - theirs))) < 1e-5


PUBLISHED = qwen3_next.PRESETS["80b_a3b_ep64"]


@pytest.mark.parametrize("tpu,length,chunk,d_k,d_v,form", [
    (False, 4096, 64, 128, 128, "xla"),     # the CPU: every test's path
    (True, 4096, 64, 128, 128, "kernel"),   # the hybrid decoder's cell
    (True, 4000, 64, 128, 128, "xla"),      # 63 chunks: no whole block
    (True, 300, 64, 128, 128, "kernel"),    # 5 chunks: one short block
    (True, 4096, 64, 64, 128, "xla"),       # a key head of half a lane row
    (True, 4096, 32, 128, 128, "xla"),      # another chunk
    (True, 128, 32, 16, 16, "xla"),         # ``tiny``
])
def test_the_scan_form_follows_the_backend_and_the_shapes(
        tpu, length, chunk, d_k, d_v, form, monkeypatch):
    """No flag and no preset's name: where the chunks' algebra is in
    kernels (a TPU, chunks of 64, heads of whole lane rows, whole blocks
    of chunks) the state's pass is too, elsewhere ``scan_chunks``."""
    assert jax.default_backend() == "cpu" and not decoder.on_tpu()
    monkeypatch.setattr(decoder, "on_tpu", lambda: tpu)
    shapes = (length, chunk, d_k, d_v)
    assert qwen3_next.scan_form(*shapes) == form
    assert qwen3_next.delta_form(*shapes) == form


@pytest.mark.parametrize("preset,length", [
    ("80b_a3b_ep64", PUBLISHED["seq_len"]), ("tiny", 128)])
def test_the_models_forms_name_the_scan_form(preset, length, monkeypatch):
    model = qwen3_next.Qwen3Next(preset)
    assert model.forms(length)["scan_form"] == "xla"
    monkeypatch.setattr(decoder, "on_tpu", lambda: True)
    assert model.forms(length)["scan_form"] == (
        "xla" if preset == "tiny" else "kernel")


def test_tiny_through_the_kernel_form_is_the_same_model(monkeypatch):
    """``tiny`` (heads of 16, chunks of 32; cut to one DeltaNet layer and
    the attention layer) with the convolution, the chunks' algebra and the
    state's pass in kernels, interpret mode: loss and every leaf's gradient
    are the XLA forms' to float32 rounding. A step holds the state's forward
    kernel twice a DeltaNet layer (the forward pass runs the primal, which
    writes no states; the layer's replay the forward rule, which does) and
    its backward kernel once."""
    monkeypatch.setitem(qwen3_next.PRESETS, "tiny", dict(
        qwen3_next.PRESETS["tiny"], num_hidden_layers=2,
        full_attention_interval=2))
    module = qwen3_next.Qwen3Next("tiny")
    grad, params = tiny_step(module, 128)
    (loss_x, _), grads_x = jax.jit(grad)(params)
    monkeypatch.setattr(qwen3_next, "delta_form", lambda *a: "kernel")
    monkeypatch.setattr(qwen3_next, "conv_form", lambda *a: "kernel")
    monkeypatch.setattr(gdn_conv, "LANES", 16)
    jax.clear_caches()          # or the second trace is the first's
    assert qwen3_next.scan_form(128, 32, 16, 16) == "kernel"
    grad, _ = tiny_step(module, 128)
    (loss, _), grads = jax.jit(grad)(params)
    assert abs(float(loss - loss_x)) < 1e-5 * float(loss_x)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(grads_x)):
        assert rel(a, b) < 1e-4, (jax.tree_util.keystr(path), rel(a, b))
    jaxpr = jax.make_jaxpr(grad)(params)
    assert {name: len(grids) for name, grids in pallas_calls(
        jaxpr.jaxpr).items()} == {
            "delta_scan_forward": 2, "delta_scan_backward": 1,
            "delta_chunks_forward": 1, "delta_chunks_backward": 1,
            "gdn_conv_forward": 2, "gdn_conv_backward": 1}
    jax.clear_caches()


def test_the_runs_records_name_the_scan_form(tmp_path):
    """``scan_form`` in the manifest and in every ``train`` record (``xla``
    here: the CPU), beside ``delta_form`` and ``conv_form``, and in no
    other record."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    with Trainer(TrainConfig(dnn="qwen3_next", dataset="tokens",
                             model_preset="tiny", batch_size=2,
                             compression="gtopk", density=0.01,
                             log_interval=1, out_dir=str(tmp_path))) as t:
        assert t._model_forms["scan_form"] == "xla"
        assert t._manifest["scan_form"] == "xla"
        t.train(2)
    rows = [json.loads(line) for line in open(tmp_path / "metrics.jsonl")]
    named = [r for r in rows if r["kind"] in ("manifest", "train")]
    assert [r["kind"] for r in named] == ["manifest", "train", "train"]
    assert all(r["scan_form"] == "xla" for r in named)
    assert not any("scan_form" in r for r in rows
                   if r["kind"] not in ("manifest", "train"))
