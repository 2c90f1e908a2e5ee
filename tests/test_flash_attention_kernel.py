"""The decoders' causal and sliding-window attention kernels
(ops/flash_attention.py) in interpret mode on the CPU, against the XLA form
they replace on the TPU (models/decoder.py::blocked_causal_attention, the
oracle) and against the benchmark's plain reference
(perfbench/refmodels/trinity_mini.py::attention): the output and its
gradients in q, k and v; the key tiles a query tile visits; the rule that
chooses between the two forms; what a layer's remat then keeps; and the
form's name in a run's records. (That the kernels compile for the chip is
tests/test_flash_compile.py's.)"""

import collections
import itertools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gtopkssgd_tpu.models import decoder  # noqa: E402
from gtopkssgd_tpu.models import qwen3_next, trinity_mini  # noqa: E402
from gtopkssgd_tpu.ops import flash_attention as flash  # noqa: E402
from perfbench.refmodels import kanana2 as latent_ref  # noqa: E402
from perfbench.refmodels import trinity_mini as ref  # noqa: E402

F32 = jnp.float32
LENGTH, TILE = 128, 32
# Windows of a 128-token sequence in tiles of 32: none, shorter than a tile,
# a whole number of tiles, not a whole number of tiles, longer than the
# sequence.
WINDOWS = (None, 12, 64, 50, 1000)
# What separates two forms of one arithmetic. float32: sums in another
# order. bfloat16 (unit roundoff u = 2^-9, a relative 2^-9 / sqrt(3) = 1.1e-3
# in the l2 norm for each rounding of a whole array): the output differs by
# the weights' rounding before and after their normalisation, two such
# roundings, 2.3e-3; a gradient passes four (the weights, d_logits, and the
# oracle's own rounding of d_P and of each block's d_q, d_k, d_v to
# ``dtype``, which the kernels leave out: they hand float32 on), 4.5e-3, and
# the limit leaves a third on top.
CLOSE = {jnp.float32: (1e-5, 1e-5), jnp.bfloat16: (2.5e-3, 6e-3)}


def rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def inputs(batch, groups, rep, dim, length=LENGTH, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (batch, length, groups * rep, dim)),
            jax.random.normal(keys[1], (batch, length, groups, dim)),
            jax.random.normal(keys[2], (batch, length, groups, dim)),
            jax.random.normal(keys[3], (batch, length, groups * rep, dim)))


def reference(q, k, v, window, dtype):
    """The benchmark's plain attention, a sequence at a time."""
    return jnp.stack([ref.attention(q[b], k[b], v[b], 0, window, dtype)
                      for b in range(q.shape[0])])


def value_and_grads(form, q, k, v, d_out):
    out, back = jax.vjp(form, q, k, v)
    return (out,) + back(d_out)


# Every window at both precisions with two query heads a key-value head of
# 128; then the other shapes (R in {1, 8}, D = 256, B = 2) at the two
# windows that cut tiles, at both precisions.
SHAPES = [(1, 2, 128), (1, 1, 256), (2, 8, 128), (2, 1, 128), (1, 8, 256)]
CASES = [(w, 1, 2, 128, d) for w in WINDOWS
         for d in (jnp.float32, jnp.bfloat16)] + [
    (w, b, r, dim, d) for (b, r, dim), w, d in itertools.product(
        SHAPES[1:], (None, 50), (jnp.float32, jnp.bfloat16))]


@pytest.mark.parametrize(
    "window,batch,rep,dim,dtype", CASES,
    ids=[f"w{w}-b{b}-r{r}-d{dim}-{d.__name__}" for w, b, r, dim, d in CASES])
def test_kernel_form_equals_the_blocked_form_and_the_reference(
        window, batch, rep, dim, dtype, monkeypatch):
    monkeypatch.setattr(flash, "TILE_Q", TILE)
    monkeypatch.setattr(flash, "TILE_K", TILE)
    q, k, v, d_out = inputs(batch, 2, rep, dim)
    mine = value_and_grads(lambda *a: decoder.kernel_causal_attention(
        *a, dtype, window, None), q, k, v, d_out)
    blocked = value_and_grads(lambda *a: decoder.blocked_causal_attention(
        *a, dtype, 32, window), q, k, v, d_out)
    plain = value_and_grads(lambda *a: reference(*a, window, dtype),
                            q, k, v, d_out)
    for theirs in (blocked, plain):
        for name, a, b, close in zip(("o", "d_q", "d_k", "d_v"), mine, theirs,
                                     (CLOSE[dtype][0],) + (CLOSE[dtype][1],) * 3):
            assert a.dtype == F32 and a.shape == b.shape
            assert rel(a, b) < close, (name, rel(a, b))
    if dtype == jnp.bfloat16:
        # No further from the float32 arithmetic than the blocked form is.
        exact = value_and_grads(lambda *a: decoder.blocked_causal_attention(
            *a, F32, 32, window), q, k, v, d_out)
        for a, b, c in zip(mine, blocked, exact):
            assert rel(a, c) < 1.25 * rel(b, c)


# Keys wider than values (latent attention): the published 192 beside 128,
# a head with keys and values of its own (R = 1) and two query heads a
# key-value head; and ``tiny``'s 24 beside 16, which is no multiple of it.
WIDTHS = [(w, rep, key, value, d)
          for (rep, key, value), w, d in itertools.product(
              [(1, 192, 128), (2, 192, 128), (1, 24, 16)], (None, 50),
              (jnp.float32, jnp.bfloat16))]


@pytest.mark.parametrize(
    "window,rep,key,value,dtype", WIDTHS,
    ids=[f"w{w}-r{r}-k{k}-v{v}-{d.__name__}" for w, r, k, v, d in WIDTHS])
def test_kernel_form_takes_a_key_head_wider_than_the_value_head(
        window, rep, key, value, dtype, monkeypatch):
    """q, k [.., D] beside v, o [.., D_v]: output and d_v of the value
    width, d_q and d_k of the key width, the scale 1 / sqrt(D), against the
    blocked form and (without a window) the latent-attention reference."""
    monkeypatch.setattr(flash, "TILE_Q", TILE)
    monkeypatch.setattr(flash, "TILE_K", TILE)
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(keys[0], (2, LENGTH, 2 * rep, key))
    k = jax.random.normal(keys[1], (2, LENGTH, 2, key))
    v = jax.random.normal(keys[2], (2, LENGTH, 2, value))
    d_out = jax.random.normal(keys[3], (2, LENGTH, 2 * rep, value))
    mine = value_and_grads(lambda *a: decoder.kernel_causal_attention(
        *a, dtype, window, None), q, k, v, d_out)
    assert [a.shape[-1] for a in mine] == [value, key, key, value]
    others = [value_and_grads(lambda *a: decoder.blocked_causal_attention(
        *a, dtype, 32, window), q, k, v, d_out)]
    if window is None and rep == 1:
        others.append(value_and_grads(lambda q, k, v: jnp.stack([
            latent_ref.attention(q[b], k[b], v[b], 0, dtype)
            for b in range(2)]), q, k, v, d_out))
    for theirs in others:
        for name, a, b, close in zip(("o", "d_q", "d_k", "d_v"), mine, theirs,
                                     (CLOSE[dtype][0],) + (CLOSE[dtype][1],) * 3):
            assert a.dtype == F32 and a.shape == b.shape
            assert rel(a, b) < close, (name, rel(a, b))


def test_unequal_tiles_and_the_windows_edges(monkeypatch):
    """Query tiles of 64 against key tiles of 32 and the other way round,
    at windows whose edge falls inside a tile, on a tile's first key and on
    its last: the output and gradients are the blocked form's."""
    q, k, v, d_out = inputs(1, 2, 2, 128)
    for (tq, tk), window in itertools.product(
            [(64, 32), (32, 64)], (31, 32, 33, 65)):
        monkeypatch.setattr(flash, "TILE_Q", tq)
        monkeypatch.setattr(flash, "TILE_K", tk)
        mine = value_and_grads(lambda *a: decoder.kernel_causal_attention(
            *a, F32, window, None), q, k, v, d_out)
        blocked = value_and_grads(lambda *a: decoder.blocked_causal_attention(
            *a, F32, 32, window), q, k, v, d_out)
        for a, b in zip(mine, blocked):
            assert rel(a, b) < 1e-5, (tq, tk, window, rel(a, b))


# ------------------------------------------------------------ tiles visited
def pallas_calls(jaxpr, into=None):
    """{kernel name: [grid of each call]} of a jaxpr, nested ones too."""
    into = collections.defaultdict(list) if into is None else into
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            into[eqn.params["name"]].append(
                tuple(eqn.params["grid_mapping"].grid))
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    pallas_calls(inner, into)
    return into


PUBLISHED = trinity_mini.PRESETS["26b_a3b_ep16"]


@pytest.mark.parametrize("window,visited,steps", [
    (PUBLISHED["sliding_window"], 150, 5), (None, 528, 32)],
    ids=["window", "full"])
def test_a_query_tile_visits_its_bands_key_tiles_only(window, visited, steps):
    """At the published 16,384 tokens and tiles of 512: 5 key tiles of 32 a
    query tile under the window of 2,048 (fewer for the first four), the
    triangle without one; the key tiles' own sweeps mirror it; and the
    grids' last axes are that long, so no step exists for the rest."""
    length, tile = PUBLISHED["seq_len"], 512
    assert (flash.TILE_Q, flash.TILE_K) == (tile, tile)
    spans = flash.key_tiles(length, tile, tile, window)
    assert sum(b - a + 1 for a, b in spans) == visited
    assert max(b - a + 1 for a, b in spans) == steps
    assert all(b == i for i, (a, b) in enumerate(spans))
    assert [a for a, _ in spans] == [
        0 if window is None else max(0, i - 4) for i in range(32)]
    back = flash.query_tiles(length, tile, tile, window)
    assert sum(b - a + 1 for a, b in back) == visited
    assert {(i, j) for i, (a, b) in enumerate(spans) for j in range(a, b + 1)} \
        == {(i, j) for j, (a, b) in enumerate(back) for i in range(a, b + 1)}
    # Every due pair lies in a visited tile.
    due = (lambda t: min(t + 1, window)) if window else (lambda t: t + 1)
    assert all(t - due(t) + 1 >= spans[t // tile][0] * tile
               for t in range(0, length, 37))

    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    q, kv = shape(1, length, 32, 128), shape(1, length, 4, 128)
    grids = pallas_calls(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        decoder.kernel_causal_attention(q, k, v, jnp.bfloat16, window,
                                        None)), (0, 1, 2)))(q, kv, kv).jaxpr)
    assert grids == {"flash_attention_forward": [(1, 4, 32, steps)],
                     "flash_attention_backward_q": [(1, 4, 32, steps)],
                     "flash_attention_backward_kv": [(1, 4, 32, steps)]}


def test_keys_outside_the_band_are_never_read(monkeypatch):
    """Under a window of 50 and tiles of 32 the rows 96.. see keys 47..,
    whose first tile starts at key 32: NaN in every earlier key and value
    leaves those rows' output and d_q as they were, bit for bit (a tile
    that was fetched and masked would give NaN * 0). And a length of no
    whole tiles is refused."""
    monkeypatch.setattr(flash, "TILE_Q", TILE)
    monkeypatch.setattr(flash, "TILE_K", TILE)
    q, k, v, d_out = inputs(1, 2, 2, 128)
    form = lambda q, k, v: decoder.kernel_causal_attention(
        q, k, v, F32, 50, None)[:, 96:]
    poison = lambda a: a.at[:, :32].set(jnp.nan)
    clean = value_and_grads(form, q, k, v, d_out[:, 96:])
    dirty = value_and_grads(form, q, poison(k), poison(v), d_out[:, 96:])
    assert np.isfinite(np.asarray(clean[0])).all()
    assert np.array_equal(np.asarray(clean[0]), np.asarray(dirty[0]))
    assert np.array_equal(np.asarray(clean[1][:, 96:]),
                          np.asarray(dirty[1][:, 96:]))
    with pytest.raises(ValueError, match="not whole tiles"):
        decoder.kernel_causal_attention(q[:, :48], k[:, :48], v[:, :48], F32,
                                        None, None)


# ----------------------------------------------------------- which form runs
QWEN = qwen3_next.PRESETS["80b_a3b_ep64"]


@pytest.mark.parametrize("tpu,length,dim,form", [
    (False, 16384, 128, "blocked"),     # the CPU: every test's path
    (True, 16384, 128, "kernel"),       # the sliding-window decoder's cell
    (True, 4096, 256, "kernel"),        # the hybrid decoder's cell
    (True, 512, 128, "kernel"),
    (True, 16384, 64, "blocked"),       # a head of half a lane row
    (True, 16384, 192, "blocked"),      # values of 1.5 lane rows
    (True, 16000, 128, "blocked"),      # no whole tile
    (True, 64, 128, "blocked"),
    (True, 64, 16, "blocked"),          # ``tiny``
])
def test_the_form_follows_the_backend_and_the_shapes(tpu, length, dim, form,
                                                     monkeypatch):
    """No flag and no preset's name: the kernels where the backend is a TPU
    and head and length fill whole tiles, XLA's blocks everywhere else."""
    assert jax.default_backend() == "cpu" and not decoder.on_tpu()
    monkeypatch.setattr(decoder, "on_tpu", lambda: tpu)
    assert decoder.attention_form(length, dim) == form
    assert decoder.attention_form(length, dim, dim) == form


@pytest.mark.parametrize("tpu,length,key,value,form", [
    (False, 8192, 192, 128, "blocked"),
    (True, 8192, 192, 128, "kernel"),   # the latent-attention decoder's cell
    (True, 8192, 128, 192, "blocked"),  # values of 1.5 lane rows
    (True, 8192, 160, 128, "blocked"),  # keys of no whole half row
    (True, 8192, 64, 128, "kernel"),
    (True, 64, 24, 16, "blocked"),      # ``tiny``
])
def test_the_form_reads_both_widths(tpu, length, key, value, form,
                                    monkeypatch):
    """The values of whole 128-lane rows (the accumulator's sublanes, the
    output's lanes), the keys of whole half rows: each such pair compiles
    for a v5e (tests/test_flash_compile.py has the published one)."""
    monkeypatch.setattr(decoder, "on_tpu", lambda: tpu)
    assert decoder.attention_form(length, key, value) == form


@pytest.mark.parametrize("module,preset,length,dim", [
    (trinity_mini.TrinityMini, "26b_a3b_ep16", PUBLISHED["seq_len"],
     PUBLISHED["head_dim"]),
    (trinity_mini.TrinityMini, "tiny", 64, 16),
    (qwen3_next.Qwen3Next, "80b_a3b_ep64", QWEN["seq_len"], QWEN["head_dim"]),
    (qwen3_next.Qwen3Next, "tiny", 128, 16)])
def test_a_models_forms_are_the_rules(module, preset, length, dim,
                                      monkeypatch):
    # (The hybrid decoder names its chunk algebra's form too:
    # tests/test_delta_chunks_kernel.py.)
    assert module(preset).forms(length)["attention_form"] == "blocked"
    monkeypatch.setattr(decoder, "on_tpu", lambda: True)
    assert module(preset).forms(length)["attention_form"] \
        == decoder.attention_form(length, dim)
    assert (module(preset).forms(length)["attention_form"] == "kernel") \
        == (preset != "tiny")


# ------------------------------------------------ what a layer's remat keeps
def tiny_step(module, length, dtype=F32):
    sizes = module.sizes
    rng = np.random.default_rng(0)
    tokens, targets = (jnp.asarray(rng.integers(
        0, sizes["vocab_rows"], (2, length)), jnp.int32) for _ in range(2))
    variables = jax.jit(lambda key: module.init({"params": key}, tokens))(
        jax.random.PRNGKey(0))
    params = jax.tree.map(lambda p: p + 0.05 * jnp.cos(
        jnp.arange(p.size, dtype=F32).reshape(p.shape)), variables["params"])
    rest = {k: v for k, v in variables.items() if k != "params"}

    def loss(p):
        if not rest:
            return module.apply({"params": p}, tokens, targets, train=True)
        return module.apply({"params": p, **rest}, tokens, targets,
                            train=True, mutable=list(rest))[0]

    return jax.value_and_grad(loss, has_aux=True), params


@pytest.mark.parametrize("module,length,layers", [
    (trinity_mini.TrinityMini("tiny"), 64, 5),
    (qwen3_next.Qwen3Next("tiny"), 128, 1)],
    ids=["trinity_mini", "qwen3_next"])
def test_a_layer_runs_each_kernel_once_a_step_and_the_model_is_the_same(
        module, length, layers, monkeypatch):
    """``tiny`` through the kernels (interpret mode, tiles of 16, a head of
    16): loss and every leaf's gradient are the blocked form's to float32
    rounding, and a step holds one forward and the two backward kernels an
    attention layer: the layer's remat keeps the output and the rows'
    log-sum-exp by name, so its replay runs no forward kernel (without the
    names it does)."""
    grad, params = tiny_step(module, length)
    (loss_b, _), grads_b = jax.jit(grad)(params)
    monkeypatch.setattr(flash, "TILE_Q", 16)
    monkeypatch.setattr(flash, "TILE_K", 16)
    monkeypatch.setattr(decoder, "attention_form", lambda *a: "kernel")
    jax.clear_caches()          # or the second trace is the first's
    grad, _ = tiny_step(module, length)
    (loss, _), grads = jax.jit(grad)(params)
    assert abs(float(loss - loss_b)) < 1e-5 * float(loss_b)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(grads_b)):
        assert rel(a, b) < 1e-4, (jax.tree_util.keystr(path), rel(a, b))
    count = lambda: {name: len(grids) for name, grids in pallas_calls(
        jax.make_jaxpr(grad)(params).jaxpr).items()}
    assert count() == {"flash_attention_forward": layers,
                       "flash_attention_backward_q": layers,
                       "flash_attention_backward_kv": layers}
    monkeypatch.setattr(decoder, "checkpoint_name", lambda x, name: x)
    jax.clear_caches()
    assert count()["flash_attention_forward"] == 2 * layers
    jax.clear_caches()


# -------------------------------------------------------------- the records
@pytest.mark.parametrize("dnn,batch,length", [
    ("trinity_mini", 2, 64), ("qwen3_next", 2, 128)])
def test_the_runs_records_name_the_form_that_compiled(dnn, batch, length,
                                                      tmp_path):
    """``attention_form`` in the manifest and in every ``train`` record
    (``blocked`` here: the CPU), from the model's own ``forms``, and in no
    other record."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    with Trainer(TrainConfig(dnn=dnn, dataset="tokens", model_preset="tiny",
                             batch_size=batch, compression="gtopk",
                             density=0.01, log_interval=1,
                             out_dir=str(tmp_path))) as t:
        assert t._model_forms["attention_form"] == "blocked"
        t.train(2)
    rows = [json.loads(line) for line in open(tmp_path / "metrics.jsonl")]
    named = [r for r in rows if r["kind"] in ("manifest", "train")]
    assert [r["kind"] for r in named] == ["manifest", "train", "train"]
    assert all(r["attention_form"] == "blocked" for r in named)
    assert not any("attention_form" in r for r in rows
                   if r["kind"] not in ("manifest", "train"))


@pytest.mark.parametrize("dnn,dataset", [
    ("resnet20", "cifar10"), ("lstm", "ptb")])
def test_a_model_without_attention_names_no_form(dnn, dataset):
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    with Trainer(TrainConfig(dnn=dnn, dataset=dataset, batch_size=2,
                             compression="dense")) as t:
        assert t._model_forms == {} and "attention_form" not in t._manifest
