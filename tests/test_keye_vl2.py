"""The Keye-VL-2.0 decoder (models/keye_vl2.py) at its ``tiny`` preset on the
CPU: against the frozen plain reference (perfbench/refmodels/keye_vl2.py),
the key sets query by query, plain causal attention where every key is
kept, the two loss terms' gradients apart, and what a layer's remat keeps.
(Its expert layer is the other decoder's: the shares and the no-drop rule
are tests/test_qwen3_next.py's, parametrised over both.)"""

import collections
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from gtopkssgd_tpu.models import decoder, get_model, keye_vl2 as prog  # noqa: E402
from gtopkssgd_tpu.obs import counters  # noqa: E402
from perfbench.refmodels import keye_vl2 as ref  # noqa: E402

TINY = prog.PRESETS["tiny"]
INDEXER = ("index_proj", "index_k_norm_scale", "index_k_norm_bias")


def leaves(tree):
    return [(jax.tree_util.keystr(k), v)
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]]


def batch_of(length, seed=0):
    rng = np.random.default_rng(seed)
    draw = lambda: rng.integers(0, TINY["vocab_rows"], (2, length)
                                ).astype(np.int32)
    return {"tokens": draw(), "targets": draw()}


@pytest.fixture(scope="module")
def params():
    """Seeded weights (the reference's init, every leaf then moved off its
    initial value so that a zero-initialised norm weight matters)."""
    module, example = ref.build(TINY, jnp.float32)
    tree = jax.jit(lambda k: module.init({"params": k}, example, False))(
        jax.random.PRNGKey(0))["params"]
    keys = jax.random.split(jax.random.PRNGKey(1), len(jax.tree.leaves(tree)))
    return jax.tree.unflatten(
        jax.tree.structure(tree),
        [p + 0.05 * jax.random.normal(k, p.shape)
         for p, k in zip(jax.tree.leaves(tree), keys)])


def reference_side(params, batch):
    """((loss, gradients), logits, the layers' sums of |S_t|) in float32."""
    module, _ = ref.build(TINY, jnp.float32)
    out = jax.jit(jax.value_and_grad(lambda p: ref.loss(
        module, {"params": p}, (), batch, None, True)[0]))(params)
    hidden, head, _, kept = module.apply({"params": params}, batch["tokens"],
                                         False)
    return out, jnp.dot(hidden, head), kept


def program_side(dtype, params, batch):
    module = prog.KeyeVL2("tiny", dtype)
    (loss, counts), grad = jax.jit(jax.value_and_grad(lambda p: module.apply(
        {"params": p}, batch["tokens"], batch["targets"], train=True),
        has_aux=True))(params)
    return (loss, grad), module.apply({"params": params}, batch["tokens"]), \
        counts


def gaps(reference, program):
    """(relative logit gap, relative loss gap, worst leaf's gradient gap
    over its norm)."""
    ((r_loss, r_grad), r_logits), ((p_loss, p_grad), p_logits) = \
        reference, program
    logit = float(jnp.linalg.norm(p_logits - r_logits)
                  / jnp.linalg.norm(r_logits))
    loss = abs(float(p_loss - r_loss)) / float(r_loss)
    grad = max(float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
               for a, b in zip(jax.tree.leaves(p_grad), jax.tree.leaves(r_grad)))
    return logit, loss, grad


def test_parameters_are_the_references_leaf_for_leaf(params):
    mine = jax.eval_shape(
        lambda k: prog.KeyeVL2("tiny").init(
            {"params": k}, jnp.zeros((1, TINY["seq_len"]), jnp.int32)),
        jax.random.PRNGKey(0))["params"]
    shape = lambda t: [(k, v.shape, v.dtype) for k, v in leaves(t)]
    assert shape(mine) == shape(params)
    # No shared expert, and the indexer's three leaves in every layer.
    names = [k for k, _ in leaves(mine)]
    assert not any("shared" in k for k in names)
    assert sum(k.endswith(f"['{leaf}']") for k in names for leaf in INDEXER) \
        == 3 * TINY["num_hidden_layers"]


def test_program_equals_reference_in_float32_and_not_in_bfloat16(params):
    """A sequence of six times the key budget (48 = 6 query blocks in 2
    buckets). Tolerances: both sides multiply the same float32 values in
    another order (blocks to the bucket's end against all keys, one
    key-value head at a time against a ``lax.map``), so they agree to float32
    rounding: 1e-5 on the logits and the loss, 1e-4 on the worst gradient
    leaf (sound: 1e-6). One precision down, bfloat16 products, breaks them
    (loss 3e-4, gradient 0.3). And every layer keeps the same number of
    keys."""
    batch = batch_of(48)
    r_out, r_logits, r_kept = reference_side(params, batch)
    p_out, p_logits, counts = program_side(jnp.float32, params, batch)
    logit, loss, grad = gaps((r_out, r_logits), (p_out, p_logits))
    assert logit < 1e-5 and loss < 1e-5 and grad < 1e-4, (logit, loss, grad)
    assert np.array_equal(np.asarray(counts["dsa_kept"]), np.asarray(r_kept))
    assert int(counts["dsa_due"]) == 2 * prog.keys_due(48, TINY["topk"]) \
        == 2 * ref.keys_due(TINY)
    assert (np.asarray(counts["dsa_kept"]) >= int(counts["dsa_due"])).all()
    logit, loss, grad = gaps((r_out, r_logits),
                             program_side(jnp.bfloat16, params, batch)[:2])
    assert logit >= 1e-5 or loss >= 1e-5 or grad >= 1e-4, (logit, loss, grad)


def test_a_sequence_of_no_whole_number_of_blocks_is_padded_and_cut(params):
    """20 tokens in blocks of 8: the program pads to 24, and its loss, its
    logits and its layers' key counts are the reference's (which blocks by
    4 there)."""
    batch = batch_of(20)
    r_module, _ = ref.build(TINY, jnp.float32)
    hidden, head, _, r_kept = r_module.apply({"params": params},
                                             batch["tokens"], False)
    r_loss = ref.loss(r_module, {"params": params}, (), batch, None, True)[0]
    module = prog.KeyeVL2("tiny", jnp.float32)
    loss, counts = module.apply({"params": params}, batch["tokens"],
                                batch["targets"], train=True)
    logits = module.apply({"params": params}, batch["tokens"])
    assert logits.shape == (2, 20, TINY["vocab_rows"])
    assert float(jnp.max(jnp.abs(logits - jnp.dot(hidden, head)))) < 1e-5
    assert abs(float(loss - r_loss)) < 1e-5 * float(r_loss)
    assert np.array_equal(np.asarray(counts["dsa_kept"]), np.asarray(r_kept))
    assert int(counts["dsa_due"]) == 2 * prog.keys_due(20, TINY["topk"])


def indexer_inputs(length, seed=2, heads=4, dim=16):
    """qI, kI small whole numbers and w whole 4096ths: every product and
    sum of the index scores is exact in float32, so the scores do not hang
    on the order a side adds them in (and some tie, as ReLU's zeros do)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    whole = lambda key, shape: jnp.round(2.0 * jax.random.normal(key, shape))
    return (whole(keys[0], (2, length, heads, dim)),
            whole(keys[1], (2, length, dim)),
            jax.random.randint(keys[2], (2, length, heads), -512, 513) / 4096.0)


@pytest.mark.parametrize("length,topk,block", [(48, 8, 8), (40, 5, 8),
                                               (16, 16, 8)])
def test_every_query_keeps_the_references_key_set(length, topk, block):
    """S_t by thresholds from counting passes over bit patterns, a bucket of
    query blocks at a time, against the reference's (a bisection of its
    own) and against a sort of each row: the same set for every query, ties
    at the threshold kept on every side."""
    qi, ki, w = indexer_inputs(length)
    tau = prog.select_thresholds(qi, ki, w, topk, jnp.float32, block)
    scores = prog.index_scores(qi, ki, w, jnp.float32)
    rows = jnp.arange(length)
    mine = (rows[:, None] >= rows[None, :]) & (scores >= tau[..., None])
    theirs = jnp.stack([ref.key_set(
        ref.index_scores(qi[b], ki[b], w[b], jnp.float32), rows, topk)
        for b in range(2)])
    assert np.array_equal(np.asarray(mine), np.asarray(theirs))
    causal = np.tril(np.ones((length, length), bool))
    ranked = np.sort(np.where(causal, np.asarray(scores), -np.inf), -1)
    sorted_set = causal & (np.asarray(scores)
                           >= ranked[..., length - min(topk, length), None])
    assert np.array_equal(np.asarray(theirs), sorted_set)
    due = np.tile(np.minimum(np.arange(length) + 1, topk), (2, 1))
    kept = np.asarray(mine.sum(-1))
    assert (kept >= due).all() and (kept == due).mean() > 0.5
    assert (kept[:, :topk] == due[:, :topk]).all()
    # Fewer candidates than the budget: no threshold; just as many: the
    # smallest of them, which keeps them all.
    assert np.isneginf(np.asarray(tau[:, :topk - 1])).all()
    assert np.isfinite(np.asarray(tau[:, topk - 1:])).all()
    # What the attention counts is what the thresholds chose.
    q, k, v = (jnp.ones((2, length, 2, 4)),) * 3
    counted = prog.sparse_attention(q, k, v, qi, ki, w, tau, jnp.float32,
                                    block)[2]
    assert np.array_equal(np.asarray(counted), kept)


def test_kth_largest_is_exact_on_ties_signs_and_short_rows():
    values = jnp.array([[3.0, -1.0, 0.0, -0.0, 3.0, 2.5, -7.0, 1e-30],
                        [-2.0, -3.0, -1.0, -5.0, -4.0, -6.0, -8.0, -7.0]])
    ordered = prog.ordered_bits(values)
    for k, want in [(1, [3.0, -1.0]), (2, [3.0, -2.0]), (3, [2.5, -3.0]),
                    (8, [-7.0, -8.0])]:
        got = prog.from_ordered_bits(prog.kth_largest(ordered, k))
        assert np.array_equal(np.asarray(got), np.array(want, np.float32)), k
    # Fewer than k candidates (the rest masked to 0): -inf keeps them all.
    masked = jnp.where(jnp.arange(8) < 3, ordered, 0)
    assert np.isneginf(np.asarray(
        prog.from_ordered_bits(prog.kth_largest(masked, 4)))).all()


@pytest.mark.parametrize("k", [1, 2, 7, 33, 64])
def test_the_references_threshold_is_the_value_a_sort_holds_at_place_k(k):
    """``kth_largest`` of the reference (bisection on bit patterns) against
    numpy's sort: rows with ties at every place, both zeros, denormals,
    infinities, all-negative rows and rows with fewer than k finite values
    (the masked ones -inf, which then is the answer and keeps them all)."""
    rng = np.random.default_rng(k)
    rows = np.concatenate([
        rng.standard_normal((6, 64)),
        rng.integers(-3, 4, (6, 64)) / 4.0,             # ties, +0.0
        -np.abs(rng.standard_normal((2, 64))),
        np.where(rng.random((4, 64)) < 0.5, -np.inf,
                 rng.standard_normal((4, 64))),
        np.tile([0.0, -0.0, 1e-42, -1e-42, np.inf, -np.inf, 3e38, -3e38],
                (2, 8)),
    ]).astype(np.float32)
    want = np.sort(rows, -1)[:, 64 - k]
    got = np.asarray(jax.jit(ref.kth_largest, static_argnums=1)(
        jnp.asarray(rows), k))
    assert np.array_equal(got, want)          # -0.0 == 0.0: as ">=" reads them
    assert np.array_equal(rows >= got[:, None], rows >= want[:, None])


@pytest.mark.parametrize("length,topk", [(8, 8), (20, 64)])
def test_with_every_key_kept_the_layer_is_plain_causal_attention(length, topk):
    """At S <= topk the key set is every earlier key: the restricted softmax
    attention is the other decoder's causal grouped-query attention, and the
    indexer's loss is still a number."""
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(keys[0], (2, length, 4, 16))
    k = jax.random.normal(keys[1], (2, length, 2, 16))
    v = jax.random.normal(keys[2], (2, length, 2, 16))
    qi, ki, w = indexer_inputs(length)
    block = 4
    tau = prog.select_thresholds(qi, ki, w, topk, jnp.float32, block)
    assert np.isneginf(np.asarray(tau[:, :topk - 1])).all()
    out, kl, kept = prog.sparse_attention(q, k, v, qi, ki, w, tau,
                                          jnp.float32, block)
    want = decoder.blocked_causal_attention(q, k, v, jnp.float32, block)
    assert float(jnp.max(jnp.abs(out - want))) < 1e-5
    assert np.array_equal(np.asarray(kept),
                          np.tile(np.arange(length) + 1, (2, 1)))
    assert np.isfinite(np.asarray(kl)).all() and float(kl.min()) > -1e-6


def test_each_loss_term_reaches_its_own_leaves_and_no_other(params):
    """The two stop-gradients: the indexer's leaves take exactly zero from
    the cross-entropy (the selection is not differentiated), every other
    leaf exactly zero from L_I; in the program and in the reference."""
    batch = batch_of(48)
    module = prog.KeyeVL2("tiny", jnp.float32)
    r_module, _ = ref.build(TINY, jnp.float32)

    def cross_entropy(p):
        logits = module.apply({"params": p}, batch["tokens"])
        picked = jnp.take_along_axis(logits, batch["targets"][..., None], -1)
        return jnp.mean(jax.nn.logsumexp(logits, -1) - picked[..., 0])

    def index_loss(p):
        _, counts = module.apply({"params": p}, batch["tokens"],
                                 batch["targets"], train=True)
        return jnp.mean(counts["dsa_index_loss"])

    sides = {
        "program": (cross_entropy, index_loss),
        "reference": tuple(
            (lambda p, i=i: ref.losses(r_module, {"params": p}, batch)[i])
            for i in (0, 1))}
    for side, (ce, li) in sides.items():
        from_ce, from_li = jax.jit(jax.grad(ce))(params), \
            jax.jit(jax.grad(li))(params)
        for (name, a), (_, b) in zip(leaves(from_ce), leaves(from_li)):
            indexer = any(name.endswith(f"['{leaf}']") for leaf in INDEXER)
            mine, other = (b, a) if indexer else (a, b)
            assert not np.any(np.asarray(other)), (side, name)
            # An absent expert's leaves aside, its own term moves a leaf.
            assert np.any(np.asarray(mine)), (side, name)
    total = jax.jit(jax.grad(lambda p: module.apply(
        {"params": p}, batch["tokens"], batch["targets"], train=True)[0]))(params)
    for (name, a), (_, b), (_, c) in zip(leaves(total), leaves(from_ce),
                                         leaves(from_li)):
        assert float(jnp.max(jnp.abs(a - (b + c)))) < 1e-6, name


# ------------------------------------------- what a layer's remat keeps
def primitives(jaxpr, into=None):
    """How often each primitive occurs in a jaxpr, nested jaxprs included."""
    into = collections.Counter() if into is None else into
    for eqn in jaxpr.eqns:
        into[eqn.primitive.name] += 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    primitives(inner, into)
    return into


def gradient_and_primitives(params, batch):
    module = prog.KeyeVL2("tiny", jnp.bfloat16)
    grad = jax.value_and_grad(lambda p: module.apply(
        {"params": p}, batch["tokens"], batch["targets"], train=True),
        has_aux=True)
    return (jax.jit(grad)(params),
            primitives(jax.make_jaxpr(grad)(params).jaxpr))


def test_the_selection_runs_once_a_layer_and_its_name_changes_no_value(params):
    """A layer's remat keeps the thresholds by name: the counting passes
    (one ``lax.map`` of a 32-pass loop a bucket, two buckets at 48 tokens)
    run in the forward pass and not again when the layer is replayed;
    without the name they run twice, and every value is the same bit for
    bit."""
    batch = batch_of(48)
    kept, kept_count = gradient_and_primitives(params, batch)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(prog, "checkpoint_name", lambda x, name: x)
        bare, bare_count = gradient_and_primitives(params, batch)
    # The loop's shift is the selection's own primitive: one a bucket.
    selection = 2 * TINY["num_hidden_layers"]
    assert kept_count["shift_left"] == selection
    assert bare_count["shift_left"] == 2 * selection
    assert bare_count["scan"] > kept_count["scan"]
    for (name, a), (_, b) in zip(leaves(kept), leaves(bare)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    assert all(np.isfinite(np.asarray(a, np.float32)).all()
               for _, a in leaves(kept))


# ------------------------------------------------ registry, trainer, size
def test_model_registry_and_trainer_run_the_decoder(tmp_path):
    """``--dnn keye_vl2`` through ``Trainer`` like every other model: the
    spec's fields, its presets and nobody else's, two steps, and the
    sparse-attention and expert counters in the records."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    model, spec = get_model("keye_vl2", preset="tiny")
    assert (spec.input_key, spec.loss, spec.carry) == ("tokens", "own", False)
    assert spec.presets == ("30b_a3b_ep16", "tiny")
    assert get_model("qwen3_next")[1].presets == ("80b_a3b_ep64", "tiny")
    with pytest.raises(ValueError, match=r"keye_vl2 has the presets "
                                         r"\['30b_a3b_ep16', 'tiny'\]"):
        get_model("keye_vl2", preset="80b_a3b_ep64")
    with pytest.raises(ValueError, match="has none .*keye_vl2.*qwen3_next"):
        get_model("lstm", preset="tiny")
    with pytest.raises(ValueError, match="has the presets"):
        Trainer(TrainConfig(dnn="keye_vl2", model_preset="30b"))
    with Trainer(TrainConfig(dnn="keye_vl2", model_preset="tiny",
                             batch_size=2, compression="gtopk", density=0.01,
                             log_interval=1, out_dir=str(tmp_path))) as t:
        assert t.cfg.dataset == "tokens"
        assert t.num_params == sum(v.size for v in jax.tree.leaves(
            t.state.params)) == 103_360
        out = t.train(3)
        due = 2 * prog.keys_due(TINY["seq_len"], TINY["topk"])
        assert np.isfinite(out["loss"]) and out["moe_slots_dropped"] == 0.0
        assert out["dsa_keys_due"] == due <= out["dsa_keys_kept"] < 1.1 * due
        assert 0 < out["dsa_index_loss"] < out["loss"]
        assert np.isfinite(t.test()["val_loss"])
    rows = [json.loads(line) for line in open(tmp_path / "metrics.jsonl")]
    train = [r for r in rows if r["kind"] == "train"]
    assert len(train) == 3
    assert all(r["dsa_keys_due"] == due and r["moe_slots_held"] > 0
               for r in train)
    obs = [r for r in rows if r["kind"] == "obs"]
    fields = set(counters.MOE_FIELDS) | set(counters.DSA_FIELDS)
    assert obs and all(fields <= set(r) for r in obs)
    assert counters.last_model_scalars()["dsa_keys_kept"] == \
        train[-1]["dsa_keys_kept"]


def test_model_counters_hold_the_groups_a_model_counts():
    """The registry: a model's ``aux`` holds the groups whose counts it
    returns, ``model_scalars`` reads whichever fields it finds."""
    moe = {"moe_load": jnp.ones((2, 4)), "moe_dropped": jnp.zeros((2,))}
    dsa = {"dsa_kept": jnp.array([714, 712]), "dsa_due": jnp.array(712),
           "dsa_index_loss": jnp.array([0.25, 0.75])}
    assert set(counters.model_counters(moe)) == set(counters.MOE_FIELDS)
    both = counters.model_counters({**moe, **dsa})
    assert set(both) == set(counters.MOE_FIELDS) | set(counters.DSA_FIELDS)
    assert counters.model_counters({"tokens": jnp.ones(())}) == {}
    got = counters.model_scalars({**both, "tokens": 7.0})
    assert got["dsa_keys_kept"] == 713.0 and got["dsa_keys_due"] == 712.0
    assert got["dsa_index_loss"] == 0.5 and "tokens" not in got
    assert counters.last_model_scalars() == got
    assert counters.model_scalars({"tokens": 7.0}) == {}
    assert counters.last_model_scalars() == got


def test_published_preset_counts_its_parameters():
    """N = 314,396,160 from the initialised tree's shapes (no memory
    taken), and the issue's count of a layer's parts."""
    module = prog.KeyeVL2("30b_a3b_ep16", jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda k: module.init({"params": k}, jnp.zeros((1, 64), jnp.int32)),
        jax.random.PRNGKey(0))["params"]
    assert sum(v.size for v in jax.tree.leaves(shapes)) == 314_396_160
    assert all(v.dtype == jnp.float32 for v in jax.tree.leaves(shapes))
    layer = shapes["layer_0"]
    size = lambda tree, pick: sum(
        v.size for k, v in leaves(tree) if pick(k))
    assert size(layer["mixer"], lambda k: "index_" in k) == 2_261_120
    assert size(layer["mixer"], lambda k: "index_" not in k) == 18_874_624
    assert size(layer["moe"], lambda k: "experts_" in k) == 8 * 4_718_592
    assert size(layer, lambda k: True) == 59_150_720
    assert prog.buckets(16384, 512) == [
        (2048 * i, 2048, 2048 * (i + 1)) for i in range(8)]
