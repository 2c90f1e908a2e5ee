"""The Kanana-2 (multi-head latent attention) decoder (models/kanana2.py) at
its ``tiny`` preset on the CPU: against the frozen plain reference
(perfbench/refmodels/kanana2.py) in both forms of its attention, the
interleaved rotary written out pair by pair, the one rotary key the heads
share, the shared attention at a key head wider than the value head, the
balancing bias (no gradient, no parameter, in no flat vector), the shares of
the expert group, and the trainer on a four-device mesh. (What the expert
layer does under imbalance is tests/test_qwen3_next.py's, and the bias's
rule tests/test_trinity_mini.py's: the code is ``models/decoder.py``'s for
all.)"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from gtopkssgd_tpu.models import decoder, get_model  # noqa: E402
from gtopkssgd_tpu.models import kanana2 as prog  # noqa: E402
from gtopkssgd_tpu.obs import counters  # noqa: E402
from gtopkssgd_tpu.ops import flash_attention as flash  # noqa: E402
from perfbench.refmodels import kanana2 as ref  # noqa: E402
from test_trinity_mini import plain_attention  # noqa: E402

TINY = prog.PRESETS["tiny"]
PUBLISHED = prog.PRESETS["30b_a3b_ep16"]
F32 = jnp.float32


def leaves(tree):
    return [(jax.tree_util.keystr(k), v)
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]]


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights (the reference's init, every leaf then moved off its
    initial value so that a zero-initialised norm weight matters), biases
    off zero so that they move the choice, and two sequences."""
    module, example = ref.build(TINY, F32)
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


def program_side(params, biases, batch, dtype=F32):
    """((loss, (counts, moved biases)), gradients) of a training step's
    forward and backward pass."""
    module = prog.Kanana2("tiny", dtype)

    def objective(p):
        (loss, counts), moved = module.apply(
            {"params": p, "batch_stats": biases}, batch["tokens"],
            batch["targets"], train=True, mutable=["batch_stats"])
        return loss, (counts, moved["batch_stats"])

    return jax.jit(jax.value_and_grad(objective, has_aux=True))(params)


@pytest.fixture(scope="module")
def reference_side(seeded):
    params, biases, batch = seeded
    module, _ = ref.build(TINY, F32)

    def objective(p):
        loss, moved, _ = ref.loss(module, {"params": p, "batch_stats": biases},
                                  (), batch, None, True)
        return loss, moved

    return jax.jit(jax.value_and_grad(objective, has_aux=True))(params)


@pytest.fixture
def kernel_form(monkeypatch):
    """The attention as its kernels (interpret mode) at tiles ``tiny``
    fills; jax's caches hold the other form's traces."""
    monkeypatch.setattr(flash, "TILE_Q", 16)
    monkeypatch.setattr(flash, "TILE_K", 16)
    monkeypatch.setattr(decoder, "attention_form", lambda *a: "kernel")
    jax.clear_caches()
    yield
    jax.clear_caches()


# ------------------------------------------------ against the reference
def test_parameters_and_state_are_the_references_leaf_for_leaf(seeded):
    params, biases, batch = seeded
    made = jax.jit(lambda k: prog.Kanana2("tiny").init(
        {"params": k}, batch["tokens"]))(jax.random.PRNGKey(0))
    shapes = lambda tree: [(k, v.shape, v.dtype) for k, v in leaves(tree)]
    assert shapes(made["params"]) == shapes(params)
    assert shapes(made["batch_stats"]) == shapes(biases)
    assert set(made) == {"params", "batch_stats"}
    # One dense layer, then expert layers: one [experts] bias each, zero.
    assert [k for k, _ in leaves(made["batch_stats"])] == [
        f"['layer_{i}']['moe']['router_bias']" for i in range(1, 3)]
    assert not any(np.asarray(v).any() for _, v in leaves(made["batch_stats"]))
    assert set(params["layer_0"]) == {"input_norm", "pre_mlp_norm", "mixer",
                                      "mlp"}
    assert set(params["layer_1"]["mixer"]) == {
        "q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj", "o_proj"}


def check_against_reference(seeded, reference_side):
    params, biases, batch = seeded
    (want_loss, want_moved), want_grads = reference_side
    (loss, (_, moved)), grads = program_side(params, biases, batch)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    scale = max(float(jnp.max(jnp.abs(g))) for _, g in leaves(want_grads))
    for (name, mine), (_, theirs) in zip(leaves(grads), leaves(want_grads)):
        assert float(jnp.max(jnp.abs(mine - theirs))) < 2e-5 * scale, name
        assert float(jnp.max(jnp.abs(theirs))) > 0, name
    for (name, mine), (_, theirs) in zip(leaves(moved), leaves(want_moved)):
        assert np.array_equal(np.asarray(mine), np.asarray(theirs)), name
    module, _ = ref.build(TINY, F32)
    state = {"params": params, "batch_stats": biases}
    hidden, head, _ = module.apply(state, batch["tokens"], False)
    logits = prog.Kanana2("tiny").apply(state, batch["tokens"])
    assert float(jnp.max(jnp.abs(logits - jnp.dot(hidden, head)))) < 1e-4
    return want_loss, want_grads, scale


def test_program_equals_reference_in_float32_and_not_in_bfloat16(
        seeded, reference_side):
    """Loss, logits, every gradient leaf and the moved biases in float32
    (the blocked form: every CPU run's); the same program in bfloat16
    stands well outside the tolerance."""
    want_loss, want_grads, scale = check_against_reference(
        seeded, reference_side)
    (low, _), low_grads = program_side(*seeded, jnp.bfloat16)
    assert abs(float(low) - float(want_loss)) > 1e-4
    assert max(float(jnp.max(jnp.abs(a - b))) for (_, a), (_, b) in
               zip(leaves(low_grads), leaves(want_grads))) > 1e-3 * scale


def test_the_kernel_form_equals_the_reference_too(seeded, reference_side,
                                                  kernel_form):
    """``tiny`` through the flash kernels in interpret mode at a key head of
    24 beside a value head of 16."""
    check_against_reference(seeded, reference_side)


# ------------------------------------------------------------- the mixer
def test_interleaved_rotary_is_the_pairwise_rotation_written_out():
    """Pair (x_2j, x_2j+1) at position t turns by t theta^(-2j/p); the
    result holds the first members in its first half and the second members
    in its second (HF's order), program and reference alike; and a score
    q . k depends on the positions' difference alone."""
    theta, dim = 1e6, 8
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 11, 3, dim))
    t = np.arange(11, dtype=np.float64)[None, :, None, None]
    j = np.arange(dim // 2, dtype=np.float64)
    angle = t * theta ** (-2.0 * j / dim)
    a, b = np.asarray(x)[..., 0::2], np.asarray(x)[..., 1::2]
    want = np.concatenate([a * np.cos(angle) - b * np.sin(angle),
                           b * np.cos(angle) + a * np.sin(angle)], -1)
    got = prog.rotary_interleaved(x, theta)
    assert np.max(np.abs(np.asarray(got) - want)) < 1e-5
    plain = jnp.stack([ref.rope_interleaved(x[i], theta) for i in range(2)])
    assert np.max(np.abs(np.asarray(plain) - want)) < 1e-5
    # The reference at a chunk's first position.
    late = ref.rope_interleaved(x[0, 4:], theta, 4)
    assert np.max(np.abs(np.asarray(late) - want[0, 4:])) < 1e-5
    same = jnp.broadcast_to(x[:, :1], x.shape)
    turned = prog.rotary_interleaved(same, theta)
    near, far = (jnp.sum(turned[:, s] * turned[:, s + 3], -1) for s in (1, 6))
    assert float(jnp.max(jnp.abs(near - far))) < 1e-4
    assert float(jnp.max(jnp.abs(
        near - jnp.sum(turned[:, 1] * turned[:, 2], -1)))) > 1e-3


@pytest.mark.parametrize("length,block", [(64, 8), (61, 8), (24, 32)])
def test_blocked_attention_takes_a_key_head_wider_than_the_value_head(
        length, block):
    """Keys of 24 beside values of 16, four heads with keys and values of
    their own: the output has the value width, the scale the key width's,
    values and gradients the plain softmax's."""
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    q, k = (jax.random.normal(key, (2, length, 4, 24)) for key in keys[:2])
    v = jax.random.normal(keys[2], (2, length, 4, 16))
    want = plain_attention(q, k, v, None)
    got = decoder.blocked_causal_attention(q, k, v, F32, block)
    assert got.shape == (2, length, 4, 16)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    weight = jax.random.normal(keys[3], want.shape)
    pull = lambda fn: jax.grad(lambda *a: jnp.sum(fn(*a) * weight),
                               argnums=(0, 1, 2))(q, k, v)
    for mine, theirs in zip(
            pull(lambda *a: decoder.blocked_causal_attention(
                *a, F32, block)),
            pull(lambda *a: plain_attention(*a, None))):
        assert mine.shape == theirs.shape
        assert float(jnp.max(jnp.abs(mine - theirs))) < 1e-4


def mixer(sizes, x, seed=6):
    module = prog.LatentAttention(sizes, F32)
    shapes = jax.eval_shape(
        lambda k: module.init({"params": k}, x), jax.random.PRNGKey(seed))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves(shapes)))
    params = jax.tree.unflatten(jax.tree.structure(shapes), [
        0.1 * jax.random.normal(k, s.shape)
        for k, s in zip(keys, jax.tree.leaves(shapes))])
    return module, params


def test_the_heads_share_one_rotary_key_and_the_latent(monkeypatch):
    """What reaches the attention: 4 key heads whose last 8 numbers (k_pe)
    are one token's, equal over the heads, and whose first 16 differ; q's
    rotary part differs by head; and the latent's columns past
    ``kv_lora_rank`` feed no value."""
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 24, TINY["hidden_size"]))
    sizes = dict(TINY, seq_len=24)
    module, params = mixer(sizes, x)
    seen = {}

    def spy(q, k, v, *rest):
        seen.update(q=q, k=k, v=v)
        return jnp.zeros(v.shape, F32)

    monkeypatch.setattr(prog, "blocked_causal_attention", spy)
    module.apply(params, x)
    nope, rope = TINY["qk_nope_head_dim"], TINY["qk_rope_head_dim"]
    q, k, v = seen["q"], seen["k"], seen["v"]
    assert q.shape == k.shape == (2, 24, 4, nope + rope)
    assert v.shape == (2, 24, 4, TINY["v_head_dim"])
    assert np.array_equal(np.asarray(k[:, :, 0, nope:]),
                          np.asarray(k[:, :, 3, nope:]))
    assert float(jnp.max(jnp.abs(k[:, :, 0, :nope] - k[:, :, 3, :nope]))) > 1e-3
    assert float(jnp.max(jnp.abs(q[:, :, 0, nope:] - q[:, :, 3, nope:]))) > 1e-3
    # Position 0 is not turned: k_pe there is the projection's own columns.
    latent = jnp.dot(x, params["params"]["kv_a_proj"], precision="highest")
    rank = TINY["kv_lora_rank"]
    apart = jnp.concatenate([latent[:, 0, rank::2], latent[:, 0, rank + 1::2]],
                            -1)
    assert float(jnp.max(jnp.abs(k[:, 0, 1, nope:] - apart))) < 1e-5


# ----------------------------------------------------- the balancing bias
def test_the_bias_takes_no_gradient_and_is_in_no_flat_vector(seeded):
    from jax.flatten_util import ravel_pytree

    params, biases, batch = seeded
    module = prog.Kanana2("tiny")

    def objective(p, b):
        return module.apply({"params": p, "batch_stats": b}, batch["tokens"],
                            batch["targets"], train=True,
                            mutable=["batch_stats"])[0][0]

    grads = jax.jit(jax.grad(objective, argnums=(0, 1)))(params, biases)
    assert all(not np.asarray(g).any() for _, g in leaves(grads[1]))
    assert all(np.asarray(g).any() for _, g in leaves(grads[0]))
    assert not any("bias" in name for name, _ in leaves(params))
    experts = TINY["n_routed_experts"]
    assert ravel_pytree(params)[0].size == sum(
        v.size for _, v in leaves(params)) == 161_824
    assert sum(v.size for _, v in leaves(biases)) == 2 * experts


def test_counts_add_up_and_the_step_moves_each_bias_by_its_own(seeded):
    params, biases, batch = seeded
    (_, (counts, moved)), _ = program_side(params, biases, batch)
    tokens = batch["tokens"].size
    assert counts["moe_count"].shape == (2, TINY["n_routed_experts"])
    assert np.array_equal(np.asarray(counts["moe_count"].sum(-1)),
                          [TINY["num_experts_per_tok"] * tokens] * 2)
    assert int(counts["moe_load"].sum()) == int(
        counts["moe_count"][:, :TINY["experts_held"]].sum())
    assert not np.asarray(counts["moe_dropped"]).any()
    for i, layer in enumerate(("layer_1", "layer_2")):
        before = biases[layer]["moe"]["router_bias"]
        assert np.array_equal(np.asarray(counts["moe_bias"][i]),
                              np.asarray(before))
        want = decoder.balanced_bias(before, counts["moe_count"][i],
                                     TINY["load_balance_coeff"])
        assert np.allclose(np.asarray(moved[layer]["moe"]["router_bias"]),
                           np.asarray(want), atol=1e-7)
    assert set(counters.model_counters(counts)) == set(
        counters.MOE_FIELDS) | set(counters.MOE_BALANCE_FIELDS)


# ----------------------------------------------- the expert group's shares
def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The guide's section 4: 16 experts in ``expert_parallel`` = 4 shares
    of 4 under a bias that moves the choice: the sum of the shares' routed
    parts, with the shared experts (what every chip computes alike) counted
    once, is the uncut layer's, in the program and in the reference."""
    experts, held = TINY["n_routed_experts"], TINY["experts_held"]
    ranks = TINY["expert_parallel"]
    assert ranks * held == experts
    assert PUBLISHED["expert_parallel"] * PUBLISHED["experts_held"] \
        == PUBLISHED["n_routed_experts"] == 128
    whole = dict(TINY, experts_held=experts, expert_offset=0)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 48, TINY["hidden_size"]))
    params = ref.SparseMoE(whole, F32).init(
        {"params": jax.random.PRNGKey(4)}, x)["params"]
    params["router"] = params["router"] * 40.0      # loads that differ
    bias = {"router_bias": 0.3 * jax.random.normal(
        jax.random.PRNGKey(5), (experts,))}

    def share(rank):
        cut = lambda a: a[rank * held:(rank + 1) * held]
        return dict(params, **{k: cut(params[k]) for k in (
            "experts_gate", "experts_up", "experts_down")})

    def layer(side, sizes, p):
        state = {"params": p, "batch_stats": bias}
        if side == "program":
            y, load, dropped, _ = prog.SparseMoE(
                prog.moe_sizes(sizes), F32).apply(state, x)
            assert int(dropped) == 0
            return y, int(load.sum())
        return ref.SparseMoE(sizes, F32).apply(state, x)[0], 0

    uncut, _ = layer("reference", whole, params)
    shared, _ = layer("reference", dict(TINY, expert_offset=10 ** 6), share(0))
    assert bool(jnp.any(shared))
    for side in ("program", "reference"):
        total, slots = 0.0, 0
        for rank in range(ranks):
            y, load = layer(side, dict(TINY, expert_offset=rank * held),
                            share(rank))
            total, slots = total + (y - shared), slots + load
        assert float(jnp.max(jnp.abs(total + shared - uncut))) < 1e-5, side
        if side == "program":     # every token-slot landed on one share
            assert slots == x.shape[0] * x.shape[1] \
                * TINY["num_experts_per_tok"]


# ------------------------------------------------ registry, trainer, size
def test_model_registry_and_trainer_run_the_decoder(tmp_path):
    """``--dnn kanana2`` through ``Trainer`` like every other model, on four
    devices: the spec's fields, its presets, three steps, the bias in
    ``batch_stats`` (moved, equal on every replica) and the form and the
    counters in the records."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    model, spec = get_model("kanana2", preset="tiny")
    assert (spec.input_key, spec.loss, spec.carry) == ("tokens", "own", False)
    assert spec.presets == ("30b_a3b_ep16", "tiny")
    assert model.forms(64) == {"attention_form": "blocked"}
    with pytest.raises(ValueError, match=r"kanana2 has the presets "
                                         r"\['30b_a3b_ep16', 'tiny'\]"):
        get_model("kanana2", preset="26b_a3b_ep16")
    with Trainer(TrainConfig(dnn="kanana2", model_preset="tiny",
                             batch_size=2, nworkers=4, compression="gtopk",
                             density=0.01, log_interval=1,
                             out_dir=str(tmp_path))) as t:
        assert t.cfg.dataset == "tokens"
        assert t.num_params == sum(v.size for v in jax.tree.leaves(
            t.state.params)) == 161_824
        assert t._manifest["attention_form"] == "blocked"
        out = t.train(3)
        assert np.isfinite(out["loss"]) and out["moe_slots_dropped"] == 0.0
        tokens = 2 * TINY["seq_len"]
        assert out["moe_count_mean"] == pytest.approx(
            tokens * TINY["num_experts_per_tok"] / TINY["n_routed_experts"])
        assert 0 < out["moe_bias_absmax"] <= 2 * 2 * 0.001 * 1.001
        biases = jax.tree.leaves(t.state.batch_stats)
        assert len(biases) == 2
        for bias in biases:
            assert bias.shape == (TINY["n_routed_experts"],)
            copies = [np.asarray(s.data) for s in bias.addressable_shards]
            assert len(copies) == 4 and np.asarray(bias).any()
            assert all(np.array_equal(copies[0], c) for c in copies[1:])
        assert np.isfinite(t.test()["val_loss"])
    rows = [json.loads(line) for line in open(tmp_path / "metrics.jsonl")]
    train = [r for r in rows if r["kind"] == "train"]
    assert len(train) == 3
    fields = set(counters.MOE_FIELDS) | set(counters.MOE_BALANCE_FIELDS)
    assert all(fields <= set(r) and r["attention_form"] == "blocked"
               for r in train)


def test_published_preset_counts_its_parameters():
    """N = 424,960,512 from the initialised tree's shapes (no memory
    taken), part by part as ISSUE 39's table and the configuration's
    ``cut.parameters`` have it; the bias is in no leaf of it."""
    module = prog.Kanana2("30b_a3b_ep16", jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda k: module.init({"params": k}, jnp.zeros((1, 64), jnp.int32)),
        jax.random.PRNGKey(0))
    assert set(shapes) == {"params", "batch_stats"}
    params = shapes["params"]
    size = lambda tree: sum(v.size for v in jax.tree.leaves(tree))
    assert size(params) == 424_960_512
    assert all(v.dtype == F32 for v in jax.tree.leaves(params))
    assert size(shapes["batch_stats"]) == 4 * 128
    mixer_ = params["layer_0"]["mixer"]
    assert {k: v.shape for k, v in mixer_.items()} == {
        "q_proj": (2048, 32 * 192), "kv_a_proj": (2048, 576),
        "kv_a_norm": (512,), "kv_b_proj": (512, 32 * 256),
        "o_proj": (4096, 2048)}
    assert size(mixer_) == 26_345_984
    assert size(params["layer_0"]["mlp"]) == 37_748_736
    assert size(params["layer_0"]) == 64_098_816
    moe = params["layer_4"]["moe"]
    assert size({k: v for k, v in moe.items() if "experts_" in k}) \
        == 8 * 4_718_592
    assert size({k: v for k, v in moe.items() if "shared_" in k}) == 9_437_184
    assert moe["router"].shape == (2048, 128) and "shared_gate" not in moe
    assert size(params["layer_4"]) == 73_798_144
    assert size({k: params[k] for k in ("embed", "head", "final_norm")}) \
        == 65_669_120
    assert prog.query_block_of(8192) == 512
    with open(os.path.join(REPO, "perfbench", "configs",
                           "kanana2_30b_a3b_ep16.json")) as fh:
        assert json.load(fh)["parameters"] == size(params)


@pytest.mark.parametrize("tpu,length,form", [
    (False, 8192, "blocked"), (True, 8192, "kernel"), (True, 8000, "blocked")])
def test_the_form_is_the_shared_rule_at_both_widths(tpu, length, form,
                                                    monkeypatch):
    monkeypatch.setattr(decoder, "on_tpu", lambda: tpu)
    assert prog.Kanana2("30b_a3b_ep16").forms(length) == {
        "attention_form": form}
    assert decoder.attention_form(length, 192, 128) == form
    assert prog.Kanana2("tiny").forms(64) == {"attention_form": "blocked"}
