"""The Trinity-Mini (AFMoE) decoder (models/trinity_mini.py) at its ``tiny``
preset on the CPU: against the frozen plain reference
(perfbench/refmodels/trinity_mini.py), the window's two edges, rotary on
the sliding layers alone, the balancing bias (no gradient, no parameter,
the choice and never the weights, its update rule), the counts, and the
trainer on a four-device mesh. (Its expert layer is the other decoders':
the shares and the no-drop rule are tests/test_qwen3_next.py's,
parametrised over all three.)"""

import json
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from gtopkssgd_tpu.models import decoder, get_model  # noqa: E402
from gtopkssgd_tpu.models import trinity_mini as prog  # noqa: E402
from gtopkssgd_tpu.obs import counters  # noqa: E402
from perfbench.refmodels import trinity_mini as ref  # noqa: E402

TINY = prog.PRESETS["tiny"]
PUBLISHED = prog.PRESETS["26b_a3b_ep16"]


def leaves(tree):
    return [(jax.tree_util.keystr(k), v)
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]]


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights (the reference's init, every leaf then moved off its
    initial value so that a zero-initialised norm weight matters), biases
    off zero so that they move the choice, and two sequences."""
    module, example = ref.build(TINY, jnp.float32)
    made = jax.jit(lambda k: module.init({"params": k}, example, False))(
        jax.random.PRNGKey(0))
    tree = made["params"]
    keys = jax.random.split(jax.random.PRNGKey(1), len(jax.tree.leaves(tree)))
    params = jax.tree.unflatten(
        jax.tree.structure(tree),
        [p + 0.05 * jax.random.normal(k, p.shape)
         for p, k in zip(jax.tree.leaves(tree), keys)])
    biases = jax.tree.map(
        lambda b: 0.3 * jax.random.normal(jax.random.PRNGKey(2), b.shape),
        made["batch_stats"])
    rng = np.random.default_rng(0)
    draw = lambda: rng.integers(0, TINY["vocab_rows"], (2, TINY["seq_len"])
                                ).astype(np.int32)
    return params, biases, {"tokens": draw(), "targets": draw()}


def program_side(params, biases, batch, dtype=jnp.float32):
    """((loss, (counts, moved biases)), gradients) of a training step's
    forward and backward pass."""
    module = prog.TrinityMini("tiny", dtype)

    def objective(p):
        (loss, counts), moved = module.apply(
            {"params": p, "batch_stats": biases}, batch["tokens"],
            batch["targets"], train=True, mutable=["batch_stats"])
        return loss, (counts, moved["batch_stats"])

    return jax.jit(jax.value_and_grad(objective, has_aux=True))(params)


def reference_side(params, biases, batch):
    module, _ = ref.build(TINY, jnp.float32)

    def objective(p):
        loss, moved, _ = ref.loss(module, {"params": p, "batch_stats": biases},
                                  (), batch, None, True)
        return loss, moved

    return jax.jit(jax.value_and_grad(objective, has_aux=True))(params)


# ------------------------------------------------ against the reference
def test_parameters_and_state_are_the_references_leaf_for_leaf(seeded):
    params, biases, batch = seeded
    made = jax.jit(lambda k: prog.TrinityMini("tiny").init(
        {"params": k}, batch["tokens"]))(jax.random.PRNGKey(0))
    shapes = lambda tree: [(k, v.shape, v.dtype) for k, v in leaves(tree)]
    assert shapes(made["params"]) == shapes(params)
    assert shapes(made["batch_stats"]) == shapes(biases)
    assert set(made) == {"params", "batch_stats"}
    # The bias starts at zero, one [experts] vector an expert layer.
    assert [k for k, _ in leaves(made["batch_stats"])] == [
        f"['layer_{i}']['moe']['router_bias']" for i in range(1, 5)]
    assert not any(np.asarray(v).any() for _, v in leaves(made["batch_stats"]))


def test_program_equals_reference_in_float32_and_not_in_bfloat16(seeded):
    """Loss, logits, every gradient leaf and the moved biases in float32;
    the same program in bfloat16 stands well outside the tolerance."""
    params, biases, batch = seeded
    (want_loss, want_moved), want_grads = reference_side(params, biases, batch)
    (loss, (_, moved)), grads = program_side(params, biases, batch)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    scale = max(float(jnp.max(jnp.abs(g))) for _, g in leaves(want_grads))
    for (name, mine), (_, theirs) in zip(leaves(grads), leaves(want_grads)):
        assert float(jnp.max(jnp.abs(mine - theirs))) < 2e-5 * scale, name
        assert float(jnp.max(jnp.abs(theirs))) > 0, name
    for (name, mine), (_, theirs) in zip(leaves(moved), leaves(want_moved)):
        assert np.array_equal(np.asarray(mine), np.asarray(theirs)), name
    module, _ = ref.build(TINY, jnp.float32)
    state = {"params": params, "batch_stats": biases}
    hidden, head, _ = module.apply(state, batch["tokens"], False)
    logits = prog.TrinityMini("tiny").apply(state, batch["tokens"])
    assert float(jnp.max(jnp.abs(logits - jnp.dot(hidden, head)))) < 1e-4
    (low, _), low_grads = program_side(params, biases, batch, jnp.bfloat16)
    assert abs(float(low) - float(want_loss)) > 1e-4
    assert max(float(jnp.max(jnp.abs(a - b))) for (_, a), (_, b) in
               zip(leaves(low_grads), leaves(want_grads))) > 1e-3 * scale


# ------------------------------------------------------------ the window
def plain_attention(q, k, v, window):
    """softmax over the keys s with 0 <= t - s < window (all s <= t for
    None), every pair multiplied: q [B, S, H, D], k, v [B, S, G, D]."""
    length, rep = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(q.shape[-1])
    apart = jnp.arange(length)[:, None] - jnp.arange(length)[None, :]
    seen = (apart >= 0) & (True if window is None else apart < window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def qkv(length, seed=4):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (2, length, 4, 16)),
            jax.random.normal(keys[1], (2, length, 2, 16)),
            jax.random.normal(keys[2], (2, length, 2, 16)))


# (length, window, block): whole blocks past the window as one map; a
# window of no whole number of blocks; a ragged last block; a sequence
# shorter than its window; a window shorter than a block.
WINDOWS = [(64, 16, 8), (64, 20, 8), (61, 16, 8), (24, 32, 8), (40, 5, 8),
           (64, None, 8)]


@pytest.mark.parametrize("length,window,block", WINDOWS)
def test_blocked_attention_is_the_plain_windowed_softmax(length, window, block):
    q, k, v = qkv(length)
    want = plain_attention(q, k, v, window)
    got = decoder.blocked_causal_attention(q, k, v, jnp.float32, block, window)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    weight = jax.random.normal(jax.random.PRNGKey(5), want.shape)
    pull = lambda fn: jax.grad(lambda *a: jnp.sum(fn(*a) * weight),
                               argnums=(0, 1, 2))(q, k, v)
    for mine, theirs in zip(
            pull(lambda *a: decoder.blocked_causal_attention(
                *a, jnp.float32, block, window)),
            pull(lambda *a: plain_attention(*a, window))):
        assert float(jnp.max(jnp.abs(mine - theirs))) < 1e-4


@pytest.mark.parametrize("query", [16, 37, 63])
def test_a_key_at_distance_w_minus_1_is_seen_and_at_w_is_not(query):
    """Move one key's value: the query W - 1 after it changes, the query W
    after it does not (window 16, blocks of 8, in both the blocks before
    and the mapped blocks after the first window)."""
    window = 16
    q, k, v = qkv(64)
    run = lambda v: decoder.blocked_causal_attention(
        q, k, v, jnp.float32, 8, window)[:, query]
    base = run(v)
    seen = run(v.at[:, query - (window - 1)].add(1.0))
    unseen = run(v.at[:, query - window].add(1.0))
    assert float(jnp.max(jnp.abs(seen - base))) > 1e-4
    assert np.array_equal(np.asarray(unseen), np.asarray(base))


def attention_layer(sliding, sizes, x, seed=6):
    module = prog.GatedAttention(sizes, jnp.float32, sliding)
    shapes = jax.eval_shape(
        lambda k: module.init({"params": k}, x), jax.random.PRNGKey(seed))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves(shapes)))
    params = jax.tree.unflatten(jax.tree.structure(shapes), [
        0.1 * jax.random.normal(k, s.shape)
        for k, s in zip(keys, jax.tree.leaves(shapes))])
    return module.apply(params, x)


def test_rotary_reaches_the_sliding_layers_only(monkeypatch):
    """A full layer never calls it; a sliding layer turns q and k; and with
    it taken away and the window at least the sequence, a sliding layer is
    a full layer."""
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 24, TINY["hidden_size"]))
    wide = dict(TINY, sliding_window=24, seq_len=24)
    turned = attention_layer(True, wide, x)
    full = attention_layer(False, wide, x)
    assert float(jnp.max(jnp.abs(turned - full))) > 1e-4
    calls = []
    monkeypatch.setattr(prog, "rotary",
                        lambda a, theta, dims: calls.append(dims) or a)
    assert np.array_equal(np.asarray(attention_layer(False, wide, x)),
                          np.asarray(full)) and not calls
    unturned = attention_layer(True, wide, x)
    assert calls == [TINY["head_dim"]] * 2
    assert float(jnp.max(jnp.abs(unturned - full))) < 1e-6
    narrow = attention_layer(True, dict(wide, sliding_window=23), x)
    assert float(jnp.max(jnp.abs(narrow - full)[:, :23])) < 1e-6
    assert float(jnp.max(jnp.abs(narrow - full)[:, 23])) > 1e-6


def test_layer_kinds_follow_the_published_pattern():
    """One dense sliding layer, then sliding, sliding, sliding, full: the
    published ``layer_types`` of layers 1 and 4-7, program and reference."""
    with open(os.path.join(REPO, "perfbench", "configs",
                           "trinity_mini_26b_a3b_ep16.json")) as fh:
        published = json.load(fh)["layer_types"]
    assert len(published) == 32
    kept = [published[i].split("_")[0] for i in (1, 4, 5, 6, 7)]
    for sizes in (TINY, PUBLISHED):
        assert sizes["layer_kinds"].split(",") == kept
        kinds = [(prog.is_dense(sizes, i), prog.is_sliding(sizes, i))
                 for i in range(sizes["num_hidden_layers"])]
        assert kinds == [(True, True), (False, True), (False, True),
                         (False, True), (False, False)]
        assert [(ref.is_dense(sizes, i), not ref.is_full(sizes, i))
                for i in range(5)] == kinds
        assert ref.layer_kinds(sizes) == (4, 1, 1, 4)


# ----------------------------------------------------- the balancing bias
def test_the_bias_takes_no_gradient_and_is_no_parameter(seeded):
    params, biases, batch = seeded
    module = prog.TrinityMini("tiny")

    def objective(p, b):
        return module.apply({"params": p, "batch_stats": b}, batch["tokens"],
                            batch["targets"], train=True,
                            mutable=["batch_stats"])[0][0]

    grads = jax.jit(jax.grad(objective, argnums=(0, 1)))(params, biases)
    assert all(not np.asarray(g).any() for _, g in leaves(grads[1]))
    assert all(np.asarray(g).any() for _, g in leaves(grads[0]))
    assert not any("bias" in name for name, _ in leaves(params))


def test_the_bias_moves_the_choice_and_never_the_weights():
    x = jax.random.normal(jax.random.PRNGKey(8), (32, 16))
    router = jax.random.normal(jax.random.PRNGKey(9), (16, 8))
    scores = jax.nn.sigmoid(jnp.dot(x, router, precision="highest"))
    plain, plain_ids = decoder.route(x, router, 2, True, "sigmoid",
                                     jnp.zeros((8,)), 2.826)
    bias = jnp.zeros((8,)).at[5].set(10.0)
    values, ids = decoder.route(x, router, 2, True, "sigmoid", bias, 2.826)
    assert bool(jnp.all(jnp.any(ids == 5, -1)))
    assert not bool(jnp.all(jnp.any(plain_ids == 5, -1)))
    picked = jnp.take_along_axis(scores, ids, -1)
    want = picked / (picked.sum(-1, keepdims=True) + 1e-20) * 2.826
    assert float(jnp.max(jnp.abs(values - want))) < 1e-6
    assert float(jnp.max(jnp.abs(values.sum(-1) - 2.826))) < 1e-5
    # Zero bias: the softmax-free path's choice is top-k of the scores.
    assert np.array_equal(np.asarray(plain_ids),
                          np.asarray(jax.lax.top_k(scores, 2)[1]))
    # Without route_norm the weights are the scores themselves, scaled.
    raw, _ = decoder.route(x, router, 2, False, "sigmoid", bias, 1.0)
    assert float(jnp.max(jnp.abs(raw - picked))) < 1e-6
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        decoder.route(x, router, 2, True, "tanh")


def test_softmax_routing_without_a_bias_is_what_it_was():
    """The other decoders' call: softmax, top-k, renormalised, no scale."""
    x = jax.random.normal(jax.random.PRNGKey(8), (32, 16))
    router = jax.random.normal(jax.random.PRNGKey(9), (16, 8))
    values, ids = decoder.route(x, router, 3, True)
    probs = jax.nn.softmax(jnp.dot(x, router, precision="highest"), -1)
    top, want_ids = jax.lax.top_k(probs, 3)
    assert np.array_equal(np.asarray(ids), np.asarray(want_ids))
    assert np.array_equal(np.asarray(values),
                          np.asarray(top / top.sum(-1, keepdims=True)))


def test_the_update_rule_on_a_hand_made_count_vector():
    counts = jnp.array([0, 4, 8, 8, 20, 8])          # mean 8
    bias = jnp.array([0.5, 0.0, -0.25, 0.0, 0.0, 1.0])
    delta = np.array([1, 1, 0, 0, -1, 0]) * 0.001    # sign(mean - c) rate
    want = np.asarray(bias) + delta - delta.mean()
    for rule in (decoder.balanced_bias, ref.balanced):
        got = np.asarray(rule(bias, counts, 0.001))
        assert np.allclose(got, want, atol=1e-9)
        assert abs(got.sum() - float(bias.sum())) < 1e-6
    even = decoder.balanced_bias(bias, jnp.full((6,), 3), 0.001)
    assert np.array_equal(np.asarray(even), np.asarray(bias))


def test_counts_add_up_and_the_step_moves_each_bias_by_its_own(seeded):
    """sum over the experts of c = top x tokens in every expert layer; the
    bias a layer chose with goes out with the counts, and the moved bias is
    the rule applied to exactly those."""
    params, biases, batch = seeded
    (_, (counts, moved)), _ = program_side(params, biases, batch)
    tokens = batch["tokens"].size
    assert counts["moe_count"].shape == (4, TINY["num_experts"])
    assert np.array_equal(np.asarray(counts["moe_count"].sum(-1)),
                          [TINY["num_experts_per_tok"] * tokens] * 4)
    assert int(counts["moe_load"].sum()) == int(
        counts["moe_count"][:, :TINY["experts_held"]].sum())
    assert not np.asarray(counts["moe_dropped"]).any()
    for i, layer in enumerate(("layer_1", "layer_2", "layer_3", "layer_4")):
        before = biases[layer]["moe"]["router_bias"]
        assert np.array_equal(np.asarray(counts["moe_bias"][i]),
                              np.asarray(before))
        want = decoder.balanced_bias(before, counts["moe_count"][i],
                                     TINY["load_balance_coeff"])
        assert np.allclose(np.asarray(moved[layer]["moe"]["router_bias"]),
                           np.asarray(want), atol=1e-7)
    # Evaluation leaves the collection alone: nothing is mutable.
    loss, _ = prog.TrinityMini("tiny").apply(
        {"params": params, "batch_stats": biases}, batch["tokens"],
        batch["targets"])
    assert np.isfinite(float(loss))


def test_model_counters_hold_the_balance_group():
    count = jnp.array([[2, 6, 4, 4], [4, 4, 4, 12]])
    bias = jnp.array([[0.0, -0.002, 0.001, 0.0], [0.0, 0.0, 0.003, 0.0]])
    got = counters.model_counters({"moe_count": count, "moe_bias": bias})
    assert set(got) == set(counters.MOE_BALANCE_FIELDS)
    assert float(got["moe_count_max"]) == 12.0
    assert float(got["moe_count_mean"]) == 5.0
    assert float(got["moe_bias_absmax"]) == pytest.approx(0.003)
    moe = {"moe_load": jnp.ones((2, 4)), "moe_dropped": jnp.zeros((2,))}
    assert set(counters.model_counters(moe)) == set(counters.MOE_FIELDS)
    both = counters.model_counters({**moe, "moe_count": count,
                                    "moe_bias": bias})
    assert set(both) == set(counters.MOE_FIELDS) | set(
        counters.MOE_BALANCE_FIELDS)
    assert counters.model_scalars(both)["moe_count_max"] == 12.0


def test_remat_keeps_the_attention_by_name_and_changes_no_value(seeded):
    params, biases, batch = seeded
    kept = program_side(params, biases, batch)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(decoder, "checkpoint_name", lambda x, name: x)
        jax.clear_caches()
        bare = program_side(params, biases, batch)
    jax.clear_caches()
    for (name, a), (_, b) in zip(leaves(kept), leaves(bare)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


# ------------------------------------------------ registry, trainer, size
def test_model_registry_and_trainer_run_the_decoder(tmp_path):
    """``--dnn trinity_mini`` through ``Trainer`` like every other model,
    on four devices: the spec's fields, its presets, three steps, the bias
    in ``batch_stats`` (moved, equal on every replica, in no flat vector)
    and the new counters in the records."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    model, spec = get_model("trinity_mini", preset="tiny")
    assert (spec.input_key, spec.loss, spec.carry) == ("tokens", "own", False)
    assert spec.presets == ("26b_a3b_ep16", "tiny")
    with pytest.raises(ValueError, match=r"trinity_mini has the presets "
                                         r"\['26b_a3b_ep16', 'tiny'\]"):
        get_model("trinity_mini", preset="30b_a3b_ep16")
    with Trainer(TrainConfig(dnn="trinity_mini", model_preset="tiny",
                             batch_size=2, nworkers=4, compression="gtopk",
                             density=0.01, log_interval=1,
                             out_dir=str(tmp_path))) as t:
        assert t.cfg.dataset == "tokens"
        assert t.num_params == sum(v.size for v in jax.tree.leaves(
            t.state.params)) == 245_216
        out = t.train(3)
        assert np.isfinite(out["loss"]) and out["moe_slots_dropped"] == 0.0
        tokens = 2 * TINY["seq_len"]
        assert out["moe_count_mean"] == pytest.approx(
            tokens * TINY["num_experts_per_tok"] / TINY["num_experts"])
        assert out["moe_count_max"] >= out["moe_count_mean"]
        # The third step chose with a bias two steps old; a step moves an
        # entry by at most twice load_balance_coeff (delta less its mean).
        assert 0 < out["moe_bias_absmax"] <= 2 * 2 * 0.001 * 1.001
        biases = jax.tree.leaves(t.state.batch_stats)
        assert len(biases) == 4
        for bias in biases:
            assert bias.shape == (TINY["num_experts"],)
            copies = [np.asarray(s.data) for s in bias.addressable_shards]
            assert len(copies) == 4 and np.asarray(bias).any()
            assert all(np.array_equal(copies[0], c) for c in copies[1:])
            assert abs(float(jnp.sum(bias))) < 1e-6
        assert np.isfinite(t.test()["val_loss"])
    rows = [json.loads(line) for line in open(tmp_path / "metrics.jsonl")]
    train = [r for r in rows if r["kind"] == "train"]
    assert len(train) == 3 and all(r["moe_slots_held"] > 0 for r in train)
    obs = [r for r in rows if r["kind"] == "obs"]
    fields = set(counters.MOE_FIELDS) | set(counters.MOE_BALANCE_FIELDS)
    assert obs and all(fields <= set(r) for r in obs)
    assert all(fields <= set(r) for r in train)
    assert counters.last_model_scalars()["moe_count_max"] == \
        train[-1]["moe_count_max"]


def test_published_preset_counts_its_parameters():
    """N = 504,147,200 from the initialised tree's shapes (no memory
    taken), part by part (ISSUE 35's table counts the two 128-wide norms as
    512 and reads 504,148,480); the bias is in no leaf of it."""
    module = prog.TrinityMini("26b_a3b_ep16", jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda k: module.init({"params": k}, jnp.zeros((1, 64), jnp.int32)),
        jax.random.PRNGKey(0))
    assert set(shapes) == {"params", "batch_stats"}
    params = shapes["params"]
    assert sum(v.size for v in jax.tree.leaves(params)) == 504_147_200
    assert all(v.dtype == jnp.float32 for v in jax.tree.leaves(params))
    assert sum(v.size for v in jax.tree.leaves(shapes["batch_stats"])) \
        == 4 * 128
    size = lambda tree: sum(v.size for v in jax.tree.leaves(tree))
    assert size(params["layer_0"]["mixer"]) == 27_263_232
    assert size(params["layer_0"]["mlp"]) == 37_748_736
    assert size(params["layer_0"]) == 65_020_160
    moe = params["layer_4"]["moe"]
    assert size({k: v for k, v in moe.items() if "experts_" in k}) \
        == 8 * 6_291_456
    assert size({k: v for k, v in moe.items() if "shared_" in k}) == 6_291_456
    assert moe["router"].shape == (2048, 128) and "shared_gate" not in moe
    assert size(params["layer_4"]) == 84_156_672
    assert size({k: params[k] for k in ("embed", "head", "final_norm")}) \
        == 102_500_352
    # No leaf has a last axis of 16, 64 or 512 (the flat vector's split).
    assert not any(v.shape[-1] in (16, 64, 512)
                   for v in jax.tree.leaves(params))
    assert prog.query_block_of(16384) == 512


# --------------------------------------- the window's engagement, static
def key_axes(text, block):
    """The last axis of every array of a lowered module that holds a number
    for each (query of a block, key): [..., block, keys], rank >= 4."""
    found = set()
    for dims in re.findall(r"tensor<([0-9x]+)x(?:f32|bf16|i1|i32)>", text):
        shape = [int(d) for d in dims.split("x")]
        if len(shape) >= 4 and shape[-2] == block:
            found.add(shape[-1])
    return found


@pytest.mark.parametrize("window", [2048, None])
def test_a_published_sliding_layer_multiplies_no_key_past_window_plus_block(
        window):
    """16,384 tokens, 32 query heads over 4 key-value heads of 128,
    bfloat16, forward and backward, lowered and not run: a sliding layer's
    query block holds at most W + block = 2,560 keys, the full layer's up
    to the sequence."""
    s = PUBLISHED
    length, block = s["seq_len"], prog.query_block_of(s["seq_len"])
    shape = lambda heads: jax.ShapeDtypeStruct(
        (1, length, heads, s["head_dim"]), jnp.float32)

    def pulled(q, k, v):
        return jax.grad(lambda *a: jnp.sum(decoder.blocked_causal_attention(
            *a, jnp.bfloat16, block, window)), argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(pulled).lower(
        shape(s["num_attention_heads"]), shape(s["num_key_value_heads"]),
        shape(s["num_key_value_heads"])).as_text()
    axes = key_axes(text, block)
    if window is None:
        assert max(axes) == length
    else:
        assert max(axes) == window + block == 2560
        assert {512, 1024, 1536, 2048} <= axes
