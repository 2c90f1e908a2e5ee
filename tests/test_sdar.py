"""The block-diffusion decoder (models/sdar.py) on the CPU at ``tiny``: the
model against the frozen reference (perfbench/refmodels/sdar.py) on seeded
weights in both attention forms, the noise of the two sides bit for bit,
the objective's corner (everything masked, one block), the clean half's
independence of the noised one, the expert group's shares, the published
preset's parameter count and the model through ``Trainer``. The visibility
rule itself, read back from both forms, is
``test_block_diffusion_attention.py``."""

import json
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gtopkssgd_tpu.models import decoder, get_model
from gtopkssgd_tpu.models import sdar as prog
from gtopkssgd_tpu.obs import counters
from gtopkssgd_tpu.ops import flash_attention as flash
from perfbench.refmodels import sdar as ref

F32 = jnp.float32
TINY, PUBLISHED = prog.PRESETS["tiny"], prog.PRESETS["30b_a3b_ep8"]
NOISE_KEY = jax.random.PRNGKey(11)


class KeyOf(nn.Module):
    @nn.compact
    def __call__(self):
        return self.make_rng("dropout")


def leaves(tree):
    return {jax.tree_util.keystr(k): v for k, v
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


def tokens_of(length, batch=2, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length), 0,
                              TINY["mask_token_id"])


@pytest.fixture(scope="module")
def params():
    """The reference's parameters from a seed, moved off their initial
    values (the norms' zeros): the same leaves at every length."""
    module, example = ref.build(TINY, F32)
    key = jax.random.PRNGKey(3)
    made = jax.jit(lambda k: module.init(
        {"params": k, "dropout": k}, example, False))(key)["params"]
    return jax.tree.map(
        lambda a: a + 0.02 * jax.random.normal(key, a.shape), made)


def reference_side(preset, params, tokens):
    """The reference's loss and gradients, one jitted call."""
    module, _ = ref.build(prog.PRESETS[preset], F32)
    return jax.jit(jax.value_and_grad(lambda p: ref.loss(
        module, {"params": p}, (), {"tokens": tokens}, NOISE_KEY,
        True)[0]))(params)


def program_side(preset, params, tokens):
    model = prog.SDAR(preset, F32)
    return jax.jit(jax.value_and_grad(lambda p: model.apply(
        {"params": p}, tokens, tokens, train=True,
        rngs={"dropout": NOISE_KEY}), has_aux=True))(params)


@pytest.fixture
def tiny_l44(monkeypatch):
    """``tiny`` at 44 tokens: whole blocks of 4 tokens and not whole query
    blocks of the blocked form (5 rows). Only the tests need it."""
    monkeypatch.setitem(prog.PRESETS, "tiny_l44", dict(TINY, seq_len=44))
    return "tiny_l44"


@pytest.fixture
def kernel_form(monkeypatch):
    """The kernel form off the TPU: interpret mode, tiles of 16."""
    monkeypatch.setattr(decoder, "diffusion_attention_form",
                        lambda *a: "kernel")
    monkeypatch.setattr(flash, "TILE_Q", 16)
    monkeypatch.setattr(flash, "TILE_K", 16)
    jax.clear_caches()
    yield
    jax.clear_caches()


# ------------------------------------------- the model against the reference
def test_parameters_are_the_references_leaf_for_leaf(params):
    model = prog.SDAR("tiny", F32)
    mine = jax.eval_shape(lambda k: model.init(
        {"params": k, "dropout": k}, tokens_of(64)), jax.random.PRNGKey(0))
    assert set(mine) == {"params"}
    assert {k: v.shape for k, v in leaves(mine["params"]).items()} \
        == {k: v.shape for k, v in leaves(params).items()}
    assert model.forms(64) == {"attention_form": "blocked"}


def held_to_the_reference(got, want):
    (loss, counts), grads = got
    assert float(loss) == pytest.approx(float(want[0]), rel=2e-6)
    mine, theirs = leaves(grads), leaves(want[1])
    assert set(mine) == set(theirs)
    for name, grad in theirs.items():
        scale = float(jnp.max(jnp.abs(grad)))
        assert scale > 0, name
        assert float(jnp.max(jnp.abs(mine[name] - grad))) < 2e-5 * scale, name
    return counts


def test_blocked_form_equals_the_reference(params, tiny_l44):
    """The loss and every leaf's gradient at a length that is whole blocks
    of 4 tokens and not whole query blocks of 5; the counts are the
    noise's own."""
    tokens = tokens_of(44)
    counts = held_to_the_reference(
        program_side(tiny_l44, params, tokens),
        reference_side(tiny_l44, params, tokens))
    # What flax's ``make_rng`` gives a top-level module applied with the
    # key: the module's path and a call count are folded into it.
    drawn = KeyOf().apply({}, rngs={"dropout": NOISE_KEY})
    _, weight, p = ref.add_noise(drawn, tokens, 4, 127, 1e-3)
    masked = np.asarray(weight) > 0
    assert float(counts["bd_masked_share"]) == pytest.approx(masked.mean())
    assert float(counts["bd_mean_t"]) == pytest.approx(
        float((p.mean() - 1e-3) / (1 - 1e-3)), rel=1e-5)
    assert float(counts["bd_empty_blocks"]) == pytest.approx(
        1 - masked.reshape(2, -1, 4).any(-1).mean())
    assert 0 < float(counts["bd_masked_ce"]) < 2 * math.log(128)
    assert counts["moe_load"].shape == (2, 4)
    assert int(counts["moe_dropped"].sum()) == 0


def test_kernel_form_equals_the_reference(params, kernel_form):
    """The same through the three kernels (interpret mode, 8 tiles of 16
    rows: every kind of edge tile and whole tiles inside the rule)."""
    tokens = tokens_of(64)
    held_to_the_reference(program_side("tiny", params, tokens),
                          reference_side("tiny", params, tokens))


# ------------------------------------------------------------------ the noise
@pytest.mark.parametrize("batch,length", [(2, 64), (1, 8192)])
def test_the_two_sides_draw_the_same_noise_bit_for_bit(batch, length):
    tokens = jax.random.randint(jax.random.PRNGKey(2), (batch, length), 0,
                                18991)
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(42), 7), 0)
    mine = jax.jit(prog.add_noise, static_argnums=(2, 3, 4))(
        key, tokens, 4, 18991, 1e-3)
    theirs = jax.jit(ref.add_noise, static_argnums=(2, 3, 4))(
        key, tokens, 4, 18991, 1e-3)
    for a, b in zip(mine, theirs):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a),
                                                     np.asarray(b))
    noised, weight, p = map(np.asarray, mine)
    masked = weight > 0
    assert (noised[masked] == 18991).all()
    assert np.array_equal(noised[~masked], np.asarray(tokens)[~masked])
    assert np.allclose(weight[masked], 1 / np.repeat(p, 4, 1)[masked])
    assert (p >= 1e-3).all() and (p <= 1).all()


def test_the_weights_mean_is_one_and_the_module_draws_from_its_key(
        params, tiny_l44):
    """E[w] = E[m / p] = 1: over 2M positions within 1% (the variance of w
    is about ln(1 / eps)); and flax's ``make_rng`` gives the two models'
    top-level draws one key, which the gradients' agreement rests on."""
    tokens = jnp.zeros((256, 8192), jnp.int32)
    _, weight, p = jax.jit(prog.add_noise, static_argnums=(2, 3, 4))(
        jax.random.PRNGKey(5), tokens, 4, 18991, 1e-3)
    assert float(jnp.mean(weight)) == pytest.approx(1.0, abs=0.01)
    assert float(jnp.mean(weight > 0)) == pytest.approx(float(p.mean()),
                                                        abs=2e-3)
    # Without a key (an evaluation) the draw is the fixed PRNGKey(0)'s.
    model = prog.SDAR(tiny_l44, F32)
    tokens = tokens_of(44)
    _, counts = jax.jit(lambda: model.apply({"params": params}, tokens,
                                            tokens))()
    _, weight, _ = prog.add_noise(jax.random.PRNGKey(0), tokens, 4, 127, 1e-3)
    assert float(counts["bd_masked_share"]) == pytest.approx(
        float(jnp.mean(weight > 0)))


def test_everything_masked_in_one_block_is_plain_masked_prediction(
        params, monkeypatch):
    """With p = 1 forced and B = L every noised row is the mask token and
    sees the whole noised half and no clean row: the logits do not depend
    on the tokens, and the loss is the mean cross-entropy of those logits
    against the tokens, position for position (no shift)."""
    monkeypatch.setitem(prog.PRESETS, "one_block",
                        dict(TINY, block_length=64))
    monkeypatch.setattr(prog, "add_noise", lambda key, tokens, *_: (
        jnp.full_like(tokens, 127), jnp.ones(tokens.shape, F32),
        jnp.ones((tokens.shape[0], 1), F32)))
    model = prog.SDAR("one_block", F32)
    tokens, others = tokens_of(64), tokens_of(64, seed=9)
    run = jax.jit(lambda t, targets: model.apply({"params": params}, t,
                                                 targets))
    logits = run(tokens, None)
    assert logits.shape == (2, 64, 128)
    assert np.array_equal(np.asarray(logits), np.asarray(run(others, None)))
    loss, counts = run(tokens, tokens)
    plain = -jnp.take_along_axis(jax.nn.log_softmax(logits), tokens[..., None],
                                 -1).mean()
    assert float(loss) == pytest.approx(float(plain), rel=1e-6)
    assert float(counts["bd_masked_share"]) == 1.0
    assert float(counts["bd_masked_ce"]) == pytest.approx(float(plain),
                                                          rel=1e-6)


def test_the_noised_half_does_not_leak_into_the_clean_rows(params):
    """A layer's clean rows are what a block-causal decoder alone would
    give: they do not move with the noised rows beside them, and row t
    does not move with a clean row of a later block."""
    layer = prog.Layer(TINY, F32)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 128, 64))
    run = jax.jit(lambda x: layer.apply({"params": params["layer_0"]}, x)[0])
    base = run(x)
    moved = run(x.at[:, 64:].add(1.0))
    assert np.array_equal(np.asarray(base[:, :64]), np.asarray(moved[:, :64]))
    assert not np.allclose(base[:, 64:], moved[:, 64:])
    later = run(x.at[:, 20].add(1.0))          # a clean row of block 5
    assert np.array_equal(np.asarray(base[:, :20]), np.asarray(later[:, :20]))
    assert np.array_equal(np.asarray(base[:, 64:64 + 24]),
                          np.asarray(later[:, 64:64 + 24]))
    assert not np.allclose(base[:, 64 + 24:], later[:, 64 + 24:])


# ----------------------------------------------- the expert group's shares
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's section 4 at ``expert_parallel`` 8: the 8 experts of
    ``tiny`` one to a rank; the sum of the ranks' parts is the uncut
    layer's, in the program and in the reference; the published preset's 8
    ranks of 16 are its 128."""
    assert PUBLISHED["expert_parallel"] * PUBLISHED["experts_held"] \
        == PUBLISHED["num_experts"] == 128
    assert TINY["expert_parallel"] * TINY["experts_held"] \
        == TINY["num_experts"] == 8
    whole = dict(TINY, experts_held=8, expert_offset=0)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 48, TINY["hidden_size"]))
    params = ref.SparseMoE(whole, F32).init(
        {"params": jax.random.PRNGKey(4)}, x)["params"]
    params["router"] = params["router"] * 40.0      # loads that differ

    def share(rank):
        return dict(params, **{k: params[k][rank:rank + 1] for k in (
            "experts_gate", "experts_up", "experts_down")})

    def layer(side, sizes, p):
        if side == "program":
            y, load, dropped, _ = decoder.SparseMoE(sizes, F32).apply(
                {"params": p}, x)
            assert int(dropped) == 0
            return y, int(load.sum())
        return ref.SparseMoE(sizes, F32).apply({"params": p}, x), 0

    uncut, _ = layer("reference", whole, params)
    for side in ("program", "reference"):
        total, slots = 0.0, 0
        for rank in range(8):
            y, load = layer(side, dict(TINY, experts_held=1,
                                       expert_offset=rank), share(rank))
            total, slots = total + y, slots + load
        assert float(jnp.max(jnp.abs(total - uncut))) < 1e-5, side
        if side == "program":     # every token-slot landed on one share
            assert slots == 2 * 48 * TINY["num_experts_per_tok"]


# ------------------------------------------- counters, registry and trainer
def test_model_counters_hold_the_bd_group():
    counts = {name: jnp.asarray(0.25 * n) for n, name in
              enumerate(counters.BD_FIELDS, 1)}
    got = counters.model_counters(counts)
    assert set(got) == set(counters.BD_FIELDS) and len(got) == 4
    assert all(v.dtype == jnp.float32 and v.shape == () for v in got.values())
    assert float(got["bd_mean_t"]) == 0.5
    assert counters.MODEL_COUNTERS["bd"][0] == counters.BD_FIELDS
    moe = {"moe_load": jnp.ones((2, 4)), "moe_dropped": jnp.zeros((2,))}
    assert set(counters.model_counters({**moe, **counts})) \
        == set(counters.MOE_FIELDS) | set(counters.BD_FIELDS)
    assert counters.model_counters(dict(list(counts.items())[:3])) == {}


def test_model_registry_and_trainer_run_the_decoder(tmp_path):
    """``--dnn sdar`` through ``Trainer`` like every other model: the
    spec's fields, its presets, the data's ids below the mask
    id, three steps whose noise follows the step, and the new
    counters in the records."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    model, spec = get_model("sdar", preset="tiny")
    assert (spec.input_key, spec.loss, spec.carry) == ("tokens", "own", False)
    assert spec.presets == ("30b_a3b_ep8", "tiny")
    with pytest.raises(ValueError, match=r"sdar has the presets "):
        get_model("sdar", preset="30b_a3b_ep16")
    with Trainer(TrainConfig(dnn="sdar", model_preset="tiny", batch_size=2,
                             nworkers=1, compression="gtopk", density=0.01,
                             log_interval=1, out_dir=str(tmp_path))) as t:
        assert t.cfg.dataset == "tokens"
        assert t._manifest["attention_form"] == "blocked"
        assert t.num_params == 91_520
        batch = t._peek_batch()
        assert batch["tokens"].shape[-1] == 64
        assert batch["tokens"].max() < TINY["mask_token_id"] == 127
        out = t.train(3)
        assert np.isfinite(out["loss"]) and out["moe_slots_dropped"] == 0.0
        assert 0.2 < out["bd_masked_share"] < 0.8
    rows = [json.loads(line) for line in open(tmp_path / "metrics.jsonl")]
    train = [r for r in rows if r["kind"] == "train"]
    obs = [r for r in rows if r["kind"] == "obs"]
    fields = set(counters.MOE_FIELDS) | set(counters.BD_FIELDS)
    assert len(train) == 3 and obs
    assert all(fields <= set(r) for r in train + obs)
    assert all(r["attention_form"] == "blocked" for r in train)
    # Every step draws anew.
    assert len({r["bd_mean_t"] for r in train}) == 3
    assert counters.last_model_scalars()["bd_masked_share"] \
        == train[-1]["bd_masked_share"]


def test_published_preset_counts_its_parameters():
    """N = 456,346,624 from the initialised tree's shapes (no memory
    taken), part by part as the configuration's ``cut`` has it."""
    module = prog.SDAR("30b_a3b_ep8", jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda k: module.init({"params": k, "dropout": k},
                              jnp.zeros((1, 64), jnp.int32)),
        jax.random.PRNGKey(0))
    assert set(shapes) == {"params"}
    params = shapes["params"]
    size = lambda tree: sum(v.size for v in jax.tree.leaves(tree))
    assert size(params) == 456_346_624 and size(params) % 512 == 0
    assert all(v.dtype == jnp.float32 for v in jax.tree.leaves(params))
    assert size(params["layer_0"]["mixer"]) == 18_874_624
    moe = params["layer_3"]["moe"]
    assert moe["router"].shape == (2048, 128)
    assert size(moe) == 262_144 + 16 * 4_718_592
    assert size(params["layer_0"]) == 94_638_336
    assert size({k: params[k] for k in ("embed", "head", "final_norm")}) \
        == 77_791_232 + 2_048
    assert not any(v.shape[-1] in (16, 64, 512)
                   for v in jax.tree.leaves(params))
    assert prog.query_block_of(8192) == 512
    assert decoder.diffusion_attention_form(8192, 128, 4) == "blocked"
