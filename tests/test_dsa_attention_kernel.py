"""The sparse-attention decoder's attention kernels (ops/dsa_attention.py)
in interpret mode on the CPU, against the masked XLA form they replace on
the TPU (models/keye_vl2.py::attend_group, the oracle): o, total, p, d_q,
d_k, d_v; the whole ``kernel_attention`` against ``sparse_attention``; the
rule that chooses between the two; and what a layer's remat then keeps.
(That the kernels compile for the chip is tests/test_pallas_compile.py's.)"""

import collections
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gtopkssgd_tpu.models import keye_vl2 as prog
from gtopkssgd_tpu.ops import dsa_attention as kernels
from gtopkssgd_tpu.ops import dsa_index as index_kernels

F32 = jnp.float32
DIM = 128
# What a rounding to ``dtype`` leaves between two forms of one product:
# float32 sums in another order; bfloat16 the oracle's own rounding of
# d_q, d_k and d_v to ``dtype`` (2^-9 an element), which the kernels leave
# out (they hand float32 on).
CLOSE = {jnp.float32: 1e-5, jnp.bfloat16: 4e-3}


def rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def keep_of(scenario, key, length, topk):
    """[1, S, S] bool, causal. ``short``: a threshold of -inf for the rows
    with fewer keys than ``topk`` (they keep them all), a random set of
    ``topk`` for the rest; ``ties``: scores of few whole values, so that a
    threshold keeps every key tied at it (more than ``topk``)."""
    rows = jnp.arange(length)
    causal = rows[:, None] >= rows[None, :]
    scores = jax.random.normal(key, (1, length, length))
    if scenario == "ties":
        scores = jnp.round(2.0 * scores)
    ranked = jnp.sort(jnp.where(causal, scores, -jnp.inf), -1)
    tau = jnp.where(rows + 1 >= topk, ranked[..., length - topk], -jnp.inf)
    keep = causal & (scores >= tau[..., None])
    kept = np.asarray(keep.sum(-1))[0]
    assert (kept[:topk - 1] == np.arange(1, topk)).all()
    assert ((kept[topk:] > topk).mean() > 0.5) == (scenario == "ties")
    return keep


def layer_inputs(dtype, heads, groups, length, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = lambda n: (1, groups, n, length, DIM)
    q = jax.random.normal(keys[0], shape(heads)).astype(dtype)
    k, v = (jax.random.normal(key, shape(1))[:, :, 0].astype(dtype)
            for key in keys[1:3])
    return q, k, v, jax.random.normal(keys[3], q.shape), keys[4]


def oracle(q, k, v, keep, top, d_out, dtype, spans):
    """``attend_group`` a key-value head and a span of rows at a time (the
    spans the masked form's buckets; every span with its own key extent),
    and its gradients for the cotangent ``d_out``."""
    def run(q, k, v):
        outs, shares = [], 0.0
        for g in range(q.shape[1]):
            parts = [prog.attend_group(
                jnp.moveaxis(q[:, g, :, a:b], 1, 2), k[:, g, :b], v[:, g, :b],
                keep[:, a:b, :b], top[:, g, :, a:b, None], dtype)
                for a, b in spans]
            outs.append(jnp.concatenate([o for o, _ in parts], 2))
            shares = shares + jnp.concatenate([jnp.pad(
                s, ((0, 0), (0, 0), (0, q.shape[3] - s.shape[-1])))
                for _, s in parts], 1)
        return jnp.stack(outs, 1), shares / (q.shape[1] * q.shape[2])

    (out, p), back = jax.vjp(run, q, k, v)
    return (out, p) + back((d_out, jnp.zeros_like(p)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("heads", [1, 8])
@pytest.mark.parametrize("scenario", ["short", "ties", "straddle"])
def test_kernels_equal_the_masked_group(scenario, heads, dtype):
    """256 rows in query tiles of 128 and key tiles of 64 or 128. In
    ``straddle`` the oracle runs in three spans of 96, 96 and 64 rows, each
    with its own bound ``top`` and key extent, as the masked form's buckets
    do: the kernels' first query tile holds rows of two of them."""
    length, topk, groups = 256, 40, 2
    q, k, v, d_out, key = layer_inputs(dtype, heads, groups, length)
    keep = keep_of("ties" if scenario == "ties" else "short", key, length,
                   topk)
    spans = [(0, 96), (96, 192), (192, 256)] if scenario == "straddle" \
        else [(0, length)]
    norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)), -1))
    top = jnp.concatenate([
        jnp.minimum(norm(q)[..., a:b] * jnp.max(
            norm(k)[..., :b], -1)[..., None, None] / math.sqrt(DIM),
            prog.LOGIT_CAP) for a, b in spans], -1)
    want = oracle(q, k, v, keep, top, d_out, dtype, spans)

    mask = keep.astype(jnp.int8)
    tiles = dict(dtype=dtype, tile_q=128,
                 tile_k=64 if scenario == "ties" else 128, interpret=True)
    out, total = kernels.forward(q, k, v, mask, top, **tiles)
    inv_total = 1.0 / total
    p = kernels.probabilities(q, k, mask, top, inv_total, span=(0, length),
                              **tiles)
    mean = jnp.sum(d_out * out, -1)
    d_q = kernels.backward_q(q, k, v, mask, top, inv_total, mean,
                             d_out.astype(dtype), **tiles)
    d_k, d_v = kernels.backward_kv(
        q, k, v, jnp.swapaxes(mask, 1, 2), top, inv_total, mean,
        d_out.astype(dtype), (d_out / total[..., None]).astype(dtype), **tiles)
    for name, mine, theirs in zip(("o", "p", "d_q", "d_k", "d_v"),
                                  (out, p, d_q, d_k, d_v), want):
        assert rel(mine, theirs) < CLOSE[dtype], (name, rel(mine, theirs))
    # The weights' sums are the oracle's: p's rows sum to one over S_t.
    assert np.allclose(np.asarray(p.sum(-1)), 1.0, atol=1e-5)
    assert not np.asarray(p)[~np.asarray(keep)].any()
    assert np.isfinite(np.asarray(total)).all() and (total > 0).all()


def test_rows_of_a_span_see_their_own_keys_only():
    """The probabilities of the rows 128..191 of a sequence against the
    keys up to 191, as the program asks for them a bucket at a time: equal
    to those rows of a call over every row, the key tiles after a query
    tile's rows written as zeros; and a span of no whole tiles is refused."""
    length, dtype = 256, jnp.float32
    q, k, v, _, key = layer_inputs(dtype, 2, 2, length, seed=3)
    mask = keep_of("short", key, length, 40).astype(jnp.int8)
    top = jnp.full(q.shape[:-1], 30.0)
    tiles = dict(dtype=dtype, tile_q=32, tile_k=64, interpret=True)
    _, total = kernels.forward(q, k, v, mask, top, **tiles)
    whole = kernels.probabilities(q, k, mask, top, 1.0 / total,
                                  span=(0, length), **tiles)
    span = kernels.probabilities(q, k, mask, top, 1.0 / total,
                                 span=(128, 64), **tiles)
    assert span.shape == (1, 64, 192)
    assert np.array_equal(np.asarray(span), np.asarray(whole[:, 128:192, :192]))
    assert np.asarray(span[:, :32, :128]).any() \
        and not np.asarray(span[:, :32, 160:]).any()
    assert not np.asarray(whole[:, 128:192, 192:]).any()
    with pytest.raises(ValueError, match="not whole tiles"):
        kernels.probabilities(q, k, mask, top, 1.0 / total, span=(96, 64),
                              **tiles)
    with pytest.raises(ValueError, match="not whole tiles"):
        kernels.forward(q[:, :, :, :96], k[:, :, :96], v[:, :, :96],
                        mask[:, :96, :96], top[:, :, :, :96], dtype=dtype,
                        tile_q=64, tile_k=64, interpret=True)


# ------------------------------------------------- the attention as a whole
def attention_inputs(length, heads=4, groups=2, index_heads=4, index_dim=16):
    keys = jax.random.split(jax.random.PRNGKey(5), 8)
    return (jax.random.normal(keys[0], (1, length, heads, DIM)),
            jax.random.normal(keys[1], (1, length, groups, DIM)),
            jax.random.normal(keys[2], (1, length, groups, DIM)),
            # Whole numbers and whole 4096ths: exact index scores, and ties.
            jnp.round(2.0 * jax.random.normal(
                keys[3], (1, length, index_heads, index_dim))),
            jnp.round(2.0 * jax.random.normal(keys[4], (1, length, index_dim))),
            jax.random.randint(keys[5], (1, length, index_heads), -512, 513)
            / 4096.0,
            jax.random.normal(keys[6], (1, length, heads, DIM)),
            jax.random.normal(keys[7], (1, length)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_attention_equals_sparse_attention(dtype, monkeypatch):
    """Values and gradients of the two forms of one layer's attention at
    512 tokens, blocks of 128 in buckets of two, top 100 keys: rows with
    fewer keys than that, ties at thresholds (the index scores are whole
    multiples of 1/4096), both losses' gradients."""
    for module in (kernels, index_kernels):
        monkeypatch.setattr(module, "TILE_Q", 128)
        monkeypatch.setattr(module, "TILE_K", 128)
    monkeypatch.setattr(prog, "BUCKET", 2)
    length, block, topk = 512, 128, 100
    *inputs, d_o, d_kl = attention_inputs(length)
    tau = prog.select_thresholds(*inputs[3:], topk, dtype, block)

    def both(form, selection):
        def loss(*inputs):
            o, kl, count = form(*inputs, selection, dtype, block)
            return jnp.sum(o * d_o) + jnp.sum(kl * d_kl), (o, kl, count)
        return jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(6)), has_aux=True))(*inputs)

    # The kernel form takes its thresholds itself, from the scores its
    # mask is made of; the masked form is handed ``select_thresholds``'.
    (_, (o, kl, count)), grads = both(prog.kernel_attention, topk)
    (_, (o_m, kl_m, count_m)), grads_m = both(prog.sparse_attention, tau)
    assert np.array_equal(np.asarray(count), np.asarray(count_m))
    assert int(count.sum()) > prog.keys_due(length, topk)       # ties
    assert rel(o, o_m) < 1e-6 and rel(kl, kl_m) < 1e-5
    for name, mine, theirs in zip("q k v qi ki w".split(), grads, grads_m):
        assert rel(mine, theirs) < CLOSE[dtype], (name, rel(mine, theirs))
        assert np.isfinite(np.asarray(mine)).all()


# ----------------------------------------------------------- which form runs
PUBLISHED = prog.PRESETS["30b_a3b_ep16"]
TINY = prog.PRESETS["tiny"]


def form_of(sizes, length=None):
    length = length or sizes["seq_len"]
    return prog.attention_form(length, sizes["head_dim"],
                               min(sizes["q_chunk_size"], length),
                               sizes["indexer_head_dim"])


def test_the_form_follows_the_backend_and_the_shapes(monkeypatch):
    """No flag and no preset's name: the kernels where the backend is a TPU
    and head, blocks and length fill whole tiles; the masked form on any
    other backend (this one) and at ``tiny``'s shapes on any backend."""
    assert jax.default_backend() == "cpu" and not prog.on_tpu()
    assert form_of(PUBLISHED) == form_of(TINY) == "masked"
    monkeypatch.setattr(prog, "on_tpu", lambda: True)
    assert form_of(PUBLISHED) == "kernel"
    assert form_of(TINY) == "masked"
    assert form_of(PUBLISHED, length=16000) == "kernel"       # padded to 16,384
    assert form_of(PUBLISHED, length=1024) == "kernel"
    assert form_of(PUBLISHED, length=300) == "masked"         # one short block
    assert form_of(dict(PUBLISHED, head_dim=64)) == "masked"
    assert form_of(dict(PUBLISHED, q_chunk_size=128)) == "masked"
    assert form_of(dict(PUBLISHED, indexer_head_dim=32)) == "masked"
    assert form_of(dict(PUBLISHED, indexer_head_dim=128)) == "kernel"


def tiny_step(dtype=jnp.float32):
    module = prog.KeyeVL2("tiny", dtype)
    rng = np.random.default_rng(0)
    tokens, targets = (jnp.asarray(rng.integers(
        0, TINY["vocab_rows"], (2, 48)), jnp.int32) for _ in range(2))
    params = jax.jit(lambda key: module.init(
        {"params": key}, tokens))(jax.random.PRNGKey(0))["params"]
    params = jax.tree.map(lambda p: p + 0.05 * jnp.cos(
        jnp.arange(p.size, dtype=F32).reshape(p.shape)), params)
    grad = jax.value_and_grad(lambda p: module.apply(
        {"params": p}, tokens, targets, train=True), has_aux=True)
    return grad, params


def test_on_the_tpu_tiny_still_runs_the_masked_form_value_for_value(
        monkeypatch):
    grad, params = tiny_step()
    here = jax.jit(grad)(params)
    monkeypatch.setattr(prog, "on_tpu", lambda: True)
    jax.clear_caches()          # or the second trace is the first's
    grad, _ = tiny_step()
    there = jax.jit(grad)(params)
    for a, b in zip(jax.tree.leaves(here), jax.tree.leaves(there)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert "pallas_call" not in str(jax.make_jaxpr(grad)(params))


def kernel_calls(jaxpr, into=None):
    """How often each kernel (a ``pallas_call``'s name) occurs in a jaxpr,
    nested jaxprs included."""
    into = collections.Counter() if into is None else into
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            into[eqn.params["name"]] += 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    kernel_calls(inner, into)
    return into


def test_a_layer_runs_each_kernel_once_a_step_and_the_model_is_the_same(
        monkeypatch):
    """``tiny`` through the kernels (interpret mode, tiles of 8): the
    model's loss, counts and every leaf's gradient are the masked form's to
    float32 rounding; and a step holds, a layer, one forward kernel, one
    probabilities kernel a bucket and the two backward kernels: the layer's
    remat keeps ``o``, ``total``, p and the mask by name, so neither its
    replay nor the backward pass runs a forward kernel again (without the
    names the replay runs both)."""
    grad, params = tiny_step()
    (loss_m, counts_m), grads_m = jax.jit(grad)(params)
    for module in (kernels, index_kernels):
        monkeypatch.setattr(module, "TILE_Q", 8)
        monkeypatch.setattr(module, "TILE_K", 8)
    monkeypatch.setattr(prog, "attention_form", lambda *a: "kernel")
    jax.clear_caches()          # or the second trace is the first's
    grad, _ = tiny_step()
    (loss, counts), grads = jax.jit(grad)(params)
    assert abs(float(loss - loss_m)) < 1e-5 * float(loss_m)
    assert np.array_equal(np.asarray(counts["dsa_kept"]),
                          np.asarray(counts_m["dsa_kept"]))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(grads_m)):
        assert rel(a, b) < 1e-4, (jax.tree_util.keystr(path), rel(a, b))
    layers, spans = TINY["num_hidden_layers"], len(prog.buckets(48, 8))
    assert kernel_calls(jax.make_jaxpr(grad)(params).jaxpr) == {
        "dsa_attention_forward": layers,
        "dsa_attention_probabilities": layers * spans,
        "dsa_attention_backward_q": layers,
        "dsa_attention_backward_kv": layers,
        # The indexer's: a bucket's scores once in the forward pass and once
        # in the backward rule, where the two backward kernels follow them.
        "dsa_index_scores": 2 * layers * spans,
        "dsa_index_backward_q": layers * spans,
        "dsa_index_backward_k": layers * spans}
    monkeypatch.setattr(prog, "checkpoint_name", lambda x, name: x)
    jax.clear_caches()
    bare = kernel_calls(jax.make_jaxpr(grad)(params).jaxpr)
    assert bare["dsa_attention_forward"] == 2 * layers
    assert bare["dsa_attention_probabilities"] == 2 * layers * spans
    assert bare["dsa_index_scores"] == 3 * layers * spans


def test_the_runs_records_name_the_form_that_compiled(tmp_path):
    """``dsa_attention_form`` in the manifest and in every ``train`` record
    (``masked`` here: the CPU), from the model's own ``forms``; another
    decoder's records do not carry it."""
    import json

    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    assert prog.KeyeVL2("tiny").forms(48) == {
        "dsa_attention_form": "masked", "dsa_index_form": "xla"}
    with Trainer(TrainConfig(dnn="keye_vl2", model_preset="tiny",
                             batch_size=2, compression="gtopk", density=0.01,
                             log_interval=1, out_dir=str(tmp_path))) as t:
        t.train(2)
    rows = [json.loads(line) for line in open(tmp_path / "metrics.jsonl")]
    named = [r for r in rows if r["kind"] in ("manifest", "train")]
    assert [r["kind"] for r in named] == ["manifest", "train", "train"]
    assert all(r["dsa_attention_form"] == "masked"
               and r["dsa_index_form"] == "xla" for r in named)
    assert not any("dsa_attention_form" in r for r in rows
                   if r["kind"] not in ("manifest", "train"))
    with Trainer(TrainConfig(dnn="qwen3_next", model_preset="tiny",
                             batch_size=2, compression="dense")) as t:
        assert "dsa_attention_form" not in t._model_forms
        assert "dsa_attention_form" not in t._manifest
