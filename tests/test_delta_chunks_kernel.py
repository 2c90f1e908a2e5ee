"""The Gated DeltaNet's chunk-algebra kernels (ops/delta_chunks.py) in
interpret mode on the CPU, against the XLA form they replace on the TPU
(models/qwen3_next.py::delta_chunks, the oracle): the six outputs and the
gradients in q, k, v, g and beta; the unit-triangular inverse by products
against ``lax.linalg.triangular_solve``; the whole delta rule against the
recurrence; the rule that chooses between the two forms; the model through
the kernels; and the form's name in a run's records. (That the kernels
compile for the chip is tests/test_flash_compile.py's.)"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gtopkssgd_tpu.models import decoder, qwen3_next  # noqa: E402
from gtopkssgd_tpu.ops import delta_chunks as kernels  # noqa: E402
from gtopkssgd_tpu.ops import gdn_conv  # noqa: E402
from tests.test_flash_attention_kernel import (  # noqa: E402
    pallas_calls, rel, tiny_step)
from tests.test_qwen3_next import delta_inputs, recurrence  # noqa: E402

F32 = jnp.float32
CHUNK, DIM = 64, 128
OUTPUTS = ("u", "w", "attn", "q_in", "k_out", "decay")
# (tokens, sequences, key heads, value heads): whole blocks of chunks (4 of
# 64), a length that is padded to 5, one value head a key head, four.
SHAPES = [(256, 1, 1, 2), (300, 1, 1, 2), (256, 2, 2, 2), (128, 1, 1, 4)]


def inputs(length, batch, key_heads, heads, seed=0):
    """q and k by key head, as the kernels read them."""
    q, k, _, _, _ = delta_inputs(length, seed, batch, key_heads, DIM, DIM)
    _, _, v, g, beta = delta_inputs(length, seed + 1, batch, heads, DIM, DIM)
    return q, k, v, g, beta


def both_forms(heads):
    def xla(q, k, v, g, beta):
        rep = heads // q.shape[2]
        arrays, _ = qwen3_next.pad_to_chunks(
            (jnp.repeat(q, rep, 2), jnp.repeat(k, rep, 2), v, g, beta), CHUNK)
        return qwen3_next.delta_chunks(*arrays, CHUNK)

    def kernel(*args):
        arrays, _ = qwen3_next.pad_to_chunks(args, CHUNK)
        return qwen3_next.kernel_delta_chunks(*arrays, CHUNK)

    return xla, kernel


@pytest.mark.parametrize("length,batch,key_heads,heads", SHAPES)
def test_the_kernels_outputs_are_the_xla_forms(length, batch, key_heads,
                                               heads):
    """u, w, attn, q_in, k_out and decay, [n, B, H, C, ...] float32: the two
    forms are one arithmetic (sums in another order inside the inverse)."""
    args = inputs(length, batch, key_heads, heads)
    xla, kernel = both_forms(heads)
    want, got = jax.jit(xla)(*args), jax.jit(kernel)(*args)
    for name, a, b in zip(OUTPUTS, got, want):
        assert a.shape == b.shape and a.dtype == F32, name
        assert rel(a, b) < 1e-6, (name, rel(a, b))


@pytest.mark.parametrize("length,batch,key_heads,heads", SHAPES)
def test_the_kernels_gradients_are_the_xla_forms(length, batch, key_heads,
                                                 heads):
    """A scalar of all six outputs, differentiated in q, k (summed over a
    key head's value heads), v, g and beta."""
    args = inputs(length, batch, key_heads, heads)
    xla, kernel = both_forms(heads)
    keys = jax.random.split(jax.random.PRNGKey(7), len(OUTPUTS))
    weights = [jax.random.normal(key, out.shape)
               for key, out in zip(keys, jax.eval_shape(xla, *args))]
    pull = lambda form: jax.jit(jax.grad(lambda *a: sum(
        jnp.sum(o * w) for o, w in zip(form(*a), weights)),
        argnums=range(5)))(*args)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), pull(kernel),
                          pull(xla)):
        assert a.shape == b.shape, name
        assert rel(a, b) < 2e-6, (name, rel(a, b))


def triangles(kind):
    """Strictly lower triangular A [m, 64, 64] as the delta rule makes them,
    entries up to +-1."""
    rng = np.random.default_rng(3)
    strict = np.tril(np.ones((CHUNK, CHUNK), np.float32), -1)
    sign = (-1.0) ** np.add.outer(np.arange(CHUNK), np.arange(CHUNK))
    return {
        # beta -> 1, no decay, equal keys: I + A is all ones below the
        # diagonal, its inverse bidiagonal, and A's powers reach 1e18.
        "equal_keys": strict[None],
        # ... and keys that alternate between k and -k.
        "opposite_keys": (strict * sign)[None].astype(np.float32),
        "uniform": strict * rng.uniform(-1, 1, (3, CHUNK, CHUNK)).astype(
            np.float32),
        # Entries of one sign and size: the inverse's grow as 1.5^(t - j).
        "growing": (-0.5 * strict)[None],
    }[kind]


@pytest.mark.parametrize("base", [8, 16, 32])
@pytest.mark.parametrize("kind", ["equal_keys", "opposite_keys", "uniform",
                                  "growing"])
def test_the_inverse_by_products_is_substitutions(kind, base, monkeypatch):
    """(I + A)^-1 by substitution in the diagonal blocks and products of
    blocks above them, against ``lax.linalg.triangular_solve``: to float32
    rounding of the largest entry, and exactly where substitution is exact."""
    monkeypatch.setattr(kernels, "BASE", base)
    a = jnp.asarray(triangles(kind))
    want = lax.linalg.triangular_solve(
        a, jnp.broadcast_to(jnp.eye(CHUNK, dtype=F32), a.shape),
        left_side=True, lower=True, unit_diagonal=True)
    got = kernels.unit_lower_inverse(a)
    assert got.shape == want.shape
    gap = float(jnp.max(jnp.abs(got - want)))
    if kind.endswith("keys"):
        assert gap == 0.0, gap
    assert gap <= 2e-6 * float(jnp.max(jnp.abs(want))), gap


@pytest.mark.parametrize("length", [128, 100])
def test_the_delta_rule_through_the_kernels_equals_the_recurrence(
        length, monkeypatch):
    """``chunked_delta_rule`` in its kernel form, forward and gradients,
    against the recurrence token by token in float64 and the reference's
    gradients: the XLA form's own test at the kernels' shapes."""
    from perfbench.refmodels import qwen3_next as ref

    args = delta_inputs(length, 0, 1, 2, DIM, DIM)
    monkeypatch.setattr(qwen3_next, "delta_form", lambda *a: "kernel")
    monkeypatch.setattr(qwen3_next, "scan_form", lambda *a: "xla")
    rule = lambda *a: qwen3_next.chunked_delta_rule(*a, CHUNK)
    want = recurrence(*args)
    assert "delta_chunks_forward" in pallas_calls(
        jax.make_jaxpr(rule)(*args).jaxpr)
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
    (True, 16384, 64, 256, 128, "kernel"),
    (True, 4096, 64, 64, 128, "xla"),       # a key head of half a lane row
    (True, 4096, 64, 128, 192, "xla"),
    (True, 4096, 32, 128, 128, "xla"),      # another chunk
    (True, 128, 32, 16, 16, "xla"),         # ``tiny``
])
def test_the_delta_form_follows_the_backend_and_the_shapes(
        tpu, length, chunk, d_k, d_v, form, monkeypatch):
    """No flag and no preset's name: the kernels where the backend is a TPU,
    chunks are 64 tokens, heads whole lane rows and the padded length whole
    blocks of chunks; XLA's products and triangular solve everywhere else."""
    assert jax.default_backend() == "cpu" and not decoder.on_tpu()
    monkeypatch.setattr(decoder, "on_tpu", lambda: tpu)
    assert qwen3_next.delta_form(length, chunk, d_k, d_v) == form


@pytest.mark.parametrize("preset,length", [
    ("80b_a3b_ep64", PUBLISHED["seq_len"]), ("tiny", 128)])
def test_the_models_forms_name_the_delta_form(preset, length, monkeypatch):
    model = qwen3_next.Qwen3Next(preset)
    assert model.forms(length) == {
        "attention_form": "blocked", "delta_form": "xla", "scan_form": "xla",
        "conv_form": "xla"}
    monkeypatch.setattr(decoder, "on_tpu", lambda: True)
    on_chip = "xla" if preset == "tiny" else "kernel"
    assert model.forms(length)["delta_form"] == on_chip
    assert model.forms(length)["conv_form"] == on_chip


def test_tiny_through_the_kernels_is_the_same_model(monkeypatch):
    """``tiny`` (heads of 16, chunks of 32, two value heads a key head; cut
    to one DeltaNet layer and the attention layer, which is what the counts
    below are of) through the kernels in interpret mode, the convolution's
    (``ops/gdn_conv.py``, its lane rule brought down to the heads of 16)
    and the chunks': loss and every leaf's gradient are the XLA forms' to
    float32 rounding; a step holds one forward and one backward chunk
    kernel a DeltaNet layer (``prepare``'s own checkpoint and the layer's
    replay run no forward kernel again: the backward kernel takes the
    inputs, and the outputs are kept by name), the convolution's forward
    kernel twice (``prepare``'s checkpoint makes q, k and v again for the
    chunk kernel's backward) and its backward once, and no triangular
    solve. (The state's pass stays ``scan_chunks`` here; through its own
    kernels too is tests/test_delta_scan_kernel.py's.)"""
    monkeypatch.setitem(qwen3_next.PRESETS, "tiny", dict(
        qwen3_next.PRESETS["tiny"], num_hidden_layers=2,
        full_attention_interval=2))
    module = qwen3_next.Qwen3Next("tiny")
    grad, params = tiny_step(module, 128)
    (loss_x, _), grads_x = jax.jit(grad)(params)
    monkeypatch.setattr(qwen3_next, "delta_form", lambda *a: "kernel")
    monkeypatch.setattr(qwen3_next, "scan_form", lambda *a: "xla")
    monkeypatch.setattr(qwen3_next, "conv_form", lambda *a: "kernel")
    monkeypatch.setattr(gdn_conv, "LANES", 16)
    jax.clear_caches()          # or the second trace is the first's
    grad, _ = tiny_step(module, 128)
    (loss, _), grads = jax.jit(grad)(params)
    assert abs(float(loss - loss_x)) < 1e-5 * float(loss_x)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(grads_x)):
        assert rel(a, b) < 1e-4, (jax.tree_util.keystr(path), rel(a, b))
    jaxpr = jax.make_jaxpr(grad)(params)
    assert {name: len(grids) for name, grids in pallas_calls(
        jaxpr.jaxpr).items()} == {
            "delta_chunks_forward": 1, "delta_chunks_backward": 1,
            "gdn_conv_forward": 2, "gdn_conv_backward": 1}
    assert "triangular_solve" not in str(jaxpr)
    jax.clear_caches()


def test_the_runs_records_name_the_delta_form(tmp_path):
    """``delta_form`` and ``conv_form`` in the manifest and in every
    ``train`` record (``xla`` here: the CPU), beside ``attention_form``,
    and in no other record."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    forms = {"attention_form": "blocked", "delta_form": "xla",
             "scan_form": "xla", "conv_form": "xla"}
    with Trainer(TrainConfig(dnn="qwen3_next", dataset="tokens",
                             model_preset="tiny", batch_size=2,
                             compression="gtopk", density=0.01,
                             log_interval=1, out_dir=str(tmp_path))) as t:
        assert t._model_forms == forms
        t.train(2)
    rows = [json.loads(line) for line in open(tmp_path / "metrics.jsonl")]
    named = [r for r in rows if r["kind"] in ("manifest", "train")]
    assert [r["kind"] for r in named] == ["manifest", "train", "train"]
    assert all(r[form] == "xla" for r in named
               for form in ("delta_form", "conv_form"))
    assert not any(form in r for r in rows for form in forms
                   if r["kind"] not in ("manifest", "train"))
