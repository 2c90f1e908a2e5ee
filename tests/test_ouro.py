"""The looped decoder (models/ouro.py) at its ``tiny`` preset on the CPU
(2 layers walked 3 times): against the frozen plain reference
(perfbench/refmodels/ouro.py) in both forms of its attention, the tie (L
layers in the tree, a layer's gradient the sum over the passes of what R
untied copies take), the exit distribution and the objective, the ``loop``
counters, and the trainer on a four-device mesh. (The shared attention, the
feed-forward and the loss are ``models/decoder.py``'s and held by the other
decoders' files.)"""

import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from gtopkssgd_tpu.models import decoder, get_model  # noqa: E402
from gtopkssgd_tpu.models import ouro as prog  # noqa: E402
from gtopkssgd_tpu.obs import counters  # noqa: E402
from perfbench.refmodels import ouro as ref  # noqa: E402
from test_kanana2 import kernel_form, leaves  # noqa: E402,F401

TINY = prog.PRESETS["tiny"]
PUBLISHED = prog.PRESETS["2p6b_l5"]
PASSES, DEPTH = TINY["total_ut_steps"], TINY["num_hidden_layers"]
F32 = jnp.float32


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights (the reference's init, every leaf then moved off its
    initial value so that a zero-initialised norm or gate matters) and two
    sequences."""
    module, example = ref.build(TINY, F32)
    tree = jax.jit(lambda k: module.init({"params": k}, example, False))(
        jax.random.PRNGKey(0))["params"]
    keys = jax.random.split(jax.random.PRNGKey(1), len(jax.tree.leaves(tree)))
    params = jax.tree.unflatten(
        jax.tree.structure(tree),
        [p + 0.05 * jax.random.normal(k, p.shape)
         for p, k in zip(jax.tree.leaves(tree), keys)])
    rng = np.random.default_rng(0)
    draw = lambda: rng.integers(0, TINY["vocab_rows"], (2, TINY["seq_len"])
                                ).astype(np.int32)
    return params, {"tokens": draw(), "targets": draw()}


@functools.lru_cache(None)
def stepper(dtype, preset):
    module = prog.Ouro(preset, dtype)
    return jax.jit(jax.value_and_grad(
        lambda p, batch: module.apply({"params": p}, batch["tokens"],
                                      batch["targets"], train=True),
        has_aux=True))


def program_side(params, batch, dtype=F32, preset="tiny"):
    """((objective, counts), gradients); one trace a (dtype, preset) until
    jax's caches are cleared."""
    return stepper(dtype, preset)(params, batch)


@pytest.fixture(scope="module")
def reference_side(seeded):
    """(objective, gradients, every pass's losses [R, B, S], the exit
    distribution [R, B, S])."""
    params, batch = seeded
    module, _ = ref.build(TINY, F32)
    value, grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(module, {"params": p}, (), batch, None, True)[0])
    )(params)
    losses, p = jax.jit(lambda v: ref.passes_of(module, v, batch))(
        {"params": params})
    return value, grads, losses, p


# ------------------------------------------------ against the reference
def test_parameters_are_the_references_leaf_for_leaf_and_one_set(seeded):
    params, batch = seeded
    made = jax.jit(lambda k: prog.Ouro("tiny").init(
        {"params": k}, batch["tokens"]))(jax.random.PRNGKey(0))
    shapes = lambda tree: [(k, v.shape, v.dtype) for k, v in leaves(tree)]
    assert set(made) == {"params"}
    assert shapes(made["params"]) == shapes(params)
    # L layers, not R x L: the passes share them.
    assert set(params) == {"embed", "final_norm", "head", "exit_gate",
                           "exit_bias"} | {f"layer_{i}" for i in range(DEPTH)}
    assert set(params["layer_0"]) == {
        "input_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm",
        "mixer", "mlp"}
    assert set(params["layer_1"]["mixer"]) == {
        "q_proj", "k_proj", "v_proj", "o_proj"}
    # The gate is stored [d] and [1], and starts shut evenly: zeros.
    assert made["params"]["exit_gate"].shape == (TINY["hidden_size"],)
    assert made["params"]["exit_bias"].shape == (1,)
    assert not np.asarray(made["params"]["exit_gate"]).any()
    assert not np.asarray(made["params"]["exit_bias"]).any()


def check_against_reference(seeded, reference_side):
    params, batch = seeded
    want, want_grads, want_losses, want_p = reference_side
    (loss, counts), grads = program_side(params, batch)
    assert abs(float(loss) - float(want)) < 1e-5
    scale = max(float(jnp.max(jnp.abs(g))) for _, g in leaves(want_grads))
    for (name, mine), (_, theirs) in zip(leaves(grads), leaves(want_grads)):
        assert float(jnp.max(jnp.abs(mine - theirs))) < 2e-5 * scale, name
        assert float(jnp.max(jnp.abs(theirs))) > 0, name
    # Every pass's mean cross-entropy and the mean exit distribution.
    assert np.allclose(counts["loop_loss"], want_losses.mean((1, 2)),
                       atol=1e-5)
    assert np.allclose(counts["loop_exit_share"], want_p.mean((1, 2)),
                       atol=1e-6)
    entropy = -jnp.sum(want_p * jnp.log(want_p), 0).mean() / math.log(PASSES)
    assert abs(float(counts["loop_exit_entropy"]) - float(entropy)) < 1e-6
    return want


def test_program_equals_reference_in_float32_and_not_in_bfloat16(
        seeded, reference_side):
    """The objective, every pass's loss, the exit distribution and every
    gradient leaf in float32 (the blocked form:
    every CPU run's); the same program's objective in bfloat16 stands well
    outside the tolerance."""
    want = check_against_reference(seeded, reference_side)
    params, batch = seeded
    low, _ = jax.jit(lambda p: prog.Ouro("tiny", jnp.bfloat16).apply(
        {"params": p}, batch["tokens"], batch["targets"]))(params)
    assert abs(float(low) - float(want)) > 1e-4


def test_every_passes_logits_are_the_references(seeded):
    """Pass r's logits are the head over a model of r passes' last state:
    the program at ``total_ut_steps`` = r against the reference's z_r."""
    params, batch = seeded
    module, _ = ref.build(TINY, F32)
    states, head, _ = jax.jit(lambda p: module.apply(
        {"params": p}, batch["tokens"], False))(params)
    for r in range(1, PASSES + 1):
        prog.PRESETS["tiny_r"] = dict(TINY, total_ut_steps=r)
        try:
            logits = jax.jit(lambda p: prog.Ouro("tiny_r").apply(
                {"params": p}, batch["tokens"]))(params)
        finally:
            del prog.PRESETS["tiny_r"]
        assert float(jnp.max(jnp.abs(
            logits - jnp.dot(states[r - 1], head)))) < 1e-4, r


# ---------------------------------------------------------------- the tie
def test_a_layers_gradient_is_the_sum_over_the_passes_of_untied_copies(
        seeded, reference_side):
    """The reference unrolled with R x L separate leaves of the same values
    gives the same objective, and the program's gradient of ``layer_i`` is
    the sum over r of the untied model's ``pass_r_layer_i``."""
    params, batch = seeded
    untied, _ = ref.build(TINY, F32, tied=False)
    spread = {k: v for k, v in params.items() if not k.startswith("layer_")}
    for r in range(PASSES):
        for i in range(DEPTH):
            spread[f"pass_{r}_layer_{i}"] = params[f"layer_{i}"]
    value, grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(untied, {"params": p}, (), batch, None, True)[0])
    )(spread)
    assert len(jax.tree.leaves(spread)) == len(jax.tree.leaves(params)) \
        + (PASSES - 1) * len(jax.tree.leaves(
            [params[f"layer_{i}"] for i in range(DEPTH)]))
    assert abs(float(value) - float(reference_side[0])) < 1e-6
    (_, _), mine = program_side(params, batch)
    scale = max(float(jnp.max(jnp.abs(g))) for _, g in leaves(mine))
    for i in range(DEPTH):
        summed = jax.tree.map(
            lambda *g: sum(g),
            *(grads[f"pass_{r}_layer_{i}"] for r in range(PASSES)))
        for (name, a), (_, b) in zip(leaves(mine[f"layer_{i}"]),
                                     leaves(summed)):
            assert float(jnp.max(jnp.abs(a - b))) < 2e-5 * scale, (i, name)
        # ... and no single pass's share is the whole of it.
        first = grads[f"pass_0_layer_{i}"]["mlp"]["down_proj"]
        assert float(jnp.max(jnp.abs(
            mine[f"layer_{i}"]["mlp"]["down_proj"] - first))) > 1e-3 * scale
    # What the passes share outside the layers sums as well.
    for name in ("head", "final_norm", "embed", "exit_gate", "exit_bias"):
        assert float(jnp.max(jnp.abs(mine[name] - grads[name]))) \
            < 2e-5 * scale, name


def test_the_kernel_form_equals_the_reference_too(seeded, reference_side,
                                                  kernel_form):
    """``tiny`` through the flash kernels in interpret mode: 4 key-value
    heads of one query head each (G = H, R = 1)."""
    check_against_reference(seeded, reference_side)


# ------------------------------------------ exit distribution, objective
def test_exit_distribution_sums_to_one_and_is_the_products_written_out():
    logits = 3.0 * np.asarray(
        jax.random.normal(jax.random.PRNGKey(2), (3, 2, 7)))
    p = np.asarray(jax.jit(lambda a: jnp.exp(prog.exit_distribution(a)))(
        logits))
    assert p.shape == (4, 2, 7)
    assert np.max(np.abs(p.sum(0) - 1.0)) < 1e-6
    g = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    want = np.stack([g[0], g[1] * (1 - g[0]), g[2] * (1 - g[0]) * (1 - g[1]),
                     (1 - g[0]) * (1 - g[1]) * (1 - g[2])])
    assert np.max(np.abs(p - want)) < 1e-6
    assert np.max(np.abs(np.asarray(
        jax.jit(ref.exit_distribution)(logits)) - want)) < 1e-6
    # A zero gate: (1/2, 1/4, 1/8, 1/8), what the model starts from; and a
    # saturated gate leaves p log p finite, value and gradient.
    hard = jnp.array([[0.0, 60.0], [0.0, -60.0], [0.0, 0.0]])
    even, (value, grad) = jax.jit(lambda a: (
        jnp.exp(prog.exit_distribution(a)),
        jax.value_and_grad(lambda a: prog.exit_objective(
            jnp.ones((4, 2)), a, 0.1)[0])(a)))(hard)
    assert np.allclose(even[:, 0], [0.5, 0.25, 0.125, 0.125])
    assert np.isfinite(float(value)) and np.isfinite(np.asarray(grad)).all()


def test_objective_is_the_weighted_sum_less_beta_times_the_entropy():
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    losses = 5.0 + jax.random.normal(keys[0], (4, 2, 9))
    logits = jax.random.normal(keys[1], (3, 2, 9))
    p = np.asarray(jax.jit(ref.exit_distribution)(logits), np.float64)
    entropy = -(p * np.log(p)).sum(0)
    want = ((p * np.asarray(losses)).sum(0) - 0.1 * entropy).mean()
    got, counts = jax.jit(
        lambda l, a: prog.exit_objective(l, a, 0.1))(losses, logits)
    assert abs(float(got) - want) < 1e-5
    assert abs(float(jax.jit(lambda l, q: ref.objective(l, q, 0.1))(
        losses, jnp.asarray(p, F32))) - want) < 1e-5
    assert abs(float(counts["loop_exit_entropy"])
               - entropy.mean() / math.log(4)) < 1e-6
    assert abs(float(counts["loop_exit_share"].sum()) - 1.0) < 1e-6
    # The entropy's gradient reaches the gates' logits: with equal losses
    # at every pass the weighted sum is flat in them.
    flat, held = jax.jit(lambda a: tuple(
        jax.grad(lambda a: prog.exit_objective(
            jnp.ones((4, 2, 9)), a, beta)[0])(a) for beta in (0.0, 0.1)))(
                logits)
    assert float(jnp.max(jnp.abs(flat))) < 1e-7
    assert float(jnp.min(jnp.abs(held))) > 1e-6


def test_the_entropys_gradient_reaches_the_gates_weight():
    """beta's term alone moves w_g and b_g: with equal losses at every pass
    the weighted sum is flat in the gate, and the objective's gradient of
    the gate's parameters is -beta times the entropy's."""
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    z = jax.random.normal(keys[0], (3, 2, 9, 16))
    w_g, b_g = 0.3 * jax.random.normal(keys[1], (16,)), jnp.array([0.2])
    losses = jnp.ones((4, 2, 9))

    def objective(w, b, beta):
        return prog.exit_objective(losses, jnp.dot(z, w) + b[0], beta)[0]

    flat, held = jax.jit(lambda w, b: tuple(
        jax.grad(objective, (0, 1))(w, b, beta) for beta in (0.0, 0.1)))(
            w_g, b_g)
    assert all(float(jnp.max(jnp.abs(g))) < 1e-6 for g in flat)
    assert all(float(jnp.max(jnp.abs(g))) > 1e-4 for g in held)


def test_one_pass_and_no_entropy_term_is_the_plain_mean_cross_entropy(seeded):
    """R = 1, beta = 0: p = (1), the objective is mean_t l_1[t], and the
    reference says the same."""
    params, batch = seeded
    one = dict(TINY, total_ut_steps=1, exit_entropy_coeff=0.0)
    prog.PRESETS["tiny_1"] = one
    try:
        module = prog.Ouro("tiny_1")
        (loss, counts), logits = jax.jit(lambda p: (
            module.apply({"params": p}, batch["tokens"], batch["targets"]),
            module.apply({"params": p}, batch["tokens"])))(params)
    finally:
        del prog.PRESETS["tiny_1"]
    logp = jax.nn.log_softmax(logits, -1)
    plain = -jnp.take_along_axis(logp, batch["targets"][..., None], -1).mean()
    assert abs(float(loss) - float(plain)) < 1e-5
    assert counts["loop_loss"].shape == (1,)
    assert float(counts["loop_exit_share"][0]) == 1.0
    assert float(counts["loop_exit_entropy"]) == 0.0
    plain_ref, _ = ref.build(one, F32)
    want = jax.jit(lambda p: ref.loss(
        plain_ref, {"params": p}, (), batch, None, True)[0])(params)
    assert abs(float(want) - float(plain)) < 1e-5


# ------------------------------------------------------------- counters
def test_model_counters_hold_the_loop_group():
    loss = jnp.array([5.0, 4.0, 3.5])
    share = jnp.array([0.5, 0.25, 0.25])
    got = counters.model_counters({
        "loop_loss": loss, "loop_exit_share": share,
        "loop_exit_entropy": jnp.float32(0.9)})
    assert set(got) == {f"loop_loss_{r}" for r in (1, 2, 3)} | {
        f"loop_exit_share_{r}" for r in (1, 2, 3)} | {"loop_exit_entropy"}
    assert set(got) < set(counters.LOOP_FIELDS)
    assert len(counters.LOOP_FIELDS) == 9
    assert float(got["loop_loss_2"]) == 4.0
    assert counters.model_scalars(got)["loop_exit_share_1"] == 0.5
    assert counters.last_model_scalars()["loop_exit_entropy"] == \
        pytest.approx(0.9)
    # Four passes fill every field; the other groups stay apart.
    four = counters.model_counters({
        "loop_loss": jnp.ones(4), "loop_exit_share": jnp.ones(4) / 4,
        "loop_exit_entropy": jnp.float32(1.0)})
    assert set(four) == set(counters.LOOP_FIELDS)
    assert not set(counters.LOOP_FIELDS) & (
        set(counters.MOE_FIELDS) | set(counters.DSA_FIELDS)
        | set(counters.MOE_BALANCE_FIELDS))


# ------------------------------------------------ registry, trainer, size
def test_model_registry_and_trainer_run_the_decoder(tmp_path):
    """``--dnn ouro`` through ``Trainer`` like every other model, on four
    devices: the spec's fields, its presets, three steps, the form and the
    counters in the records."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    model, spec = get_model("ouro", preset="tiny")
    assert (spec.input_key, spec.loss, spec.carry) == ("tokens", "own", False)
    assert spec.presets == ("2p6b_l5", "tiny")
    assert model.forms(64) == {"attention_form": "blocked"}
    with pytest.raises(ValueError, match=r"ouro has the presets "
                                         r"\['2p6b_l5', 'tiny'\]"):
        get_model("ouro", preset="30b_a3b_ep16")
    with Trainer(TrainConfig(dnn="ouro", model_preset="tiny",
                             batch_size=2, nworkers=4, compression="gtopk",
                             density=0.01, log_interval=1,
                             out_dir=str(tmp_path))) as t:
        assert t.cfg.dataset == "tokens"
        assert t.num_params == sum(v.size for v in jax.tree.leaves(
            t.state.params)) == 86_657
        assert not t.state.batch_stats
        assert t._manifest["attention_form"] == "blocked"
        out = t.train(3)
        assert np.isfinite(out["loss"])
        shares = [out[f"loop_exit_share_{r}"] for r in (1, 2, 3)]
        assert sum(shares) == pytest.approx(1.0, abs=1e-5)
        assert 0.9 < out["loop_exit_entropy"] <= 1.0
        assert all(np.isfinite(out[f"loop_loss_{r}"]) for r in (1, 2, 3))
        assert "loop_loss_4" not in out
        assert np.isfinite(t.test()["val_loss"])
    rows = [json.loads(line) for line in open(tmp_path / "metrics.jsonl")]
    train = [r for r in rows if r["kind"] == "train"]
    assert len(train) == 3
    fields = {f for f in counters.LOOP_FIELDS if not f.endswith("_4")}
    assert all(fields <= set(r) and r["attention_form"] == "blocked"
               for r in train)
    obs = [r for r in rows if r["kind"] == "obs"]
    assert obs and all(fields <= set(r) for r in obs)


def test_published_preset_counts_its_parameters():
    """N = 458,272,769 from the initialised tree's shapes (no memory
    taken), part by part as ISSUE 41's table and the configuration's
    ``cut.parameters`` have it; five layers, whatever the passes."""
    module = prog.Ouro("2p6b_l5", jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda k: module.init({"params": k}, jnp.zeros((1, 64), jnp.int32)),
        jax.random.PRNGKey(0))
    assert set(shapes) == {"params"}
    params = shapes["params"]
    size = lambda tree: sum(v.size for v in jax.tree.leaves(tree))
    assert size(params) == 458_272_769
    assert all(v.dtype == F32 for v in jax.tree.leaves(params))
    assert sorted(k for k in params if k.startswith("layer_")) == [
        f"layer_{i}" for i in range(5)]
    mixer_ = params["layer_0"]["mixer"]
    assert {k: v.shape for k, v in mixer_.items()} == {
        k: (2048, 2048) for k in ("q_proj", "k_proj", "v_proj", "o_proj")}
    assert size(mixer_) == 16_777_216
    assert size(params["layer_0"]["mlp"]) == 34_603_008
    assert size(params["layer_4"]) == 51_388_416
    assert size({k: params[k] for k in ("embed", "head")}) == 201_326_592
    assert params["final_norm"].shape == (2048,)
    assert size({k: params[k] for k in ("exit_gate", "exit_bias")}) == 2_049
    assert PUBLISHED["total_ut_steps"] == 4
    assert prog.query_block_of(4096) == 512
    with open(os.path.join(REPO, "perfbench", "configs",
                           "ouro_2p6b_l5.json")) as fh:
        assert json.load(fh)["parameters"] == size(params)


@pytest.mark.parametrize("tpu,length,form", [
    (False, 4096, "blocked"), (True, 4096, "kernel"), (True, 4000, "blocked")])
def test_the_form_is_the_shared_rule(tpu, length, form, monkeypatch):
    monkeypatch.setattr(decoder, "on_tpu", lambda: tpu)
    assert prog.Ouro("2p6b_l5").forms(length) == {"attention_form": form}
    assert prog.Ouro("tiny").forms(64) == {"attention_form": "blocked"}


def test_forward_macs_count_every_pass_and_every_passes_head():
    """39.27 TFLOP a trained sample: four times a five-layer model's
    layers and four heads, as ISSUE 41 derives it."""
    s = dict(PUBLISHED)
    token = 4 * 5 * (4 * 2048 * 2048 + 3 * 2048 * 5632) + 4 * 49152 * 2048
    assert token == 1_430_257_664
    pairs = 4096 * 4097 // 2
    want = 4096 * token + 20 * pairs * 2 * 16 * 128
    assert ref.forward_macs(s) == want
    assert abs(6 * want / 1e12 - 39.27) < 0.01
    one = ref.forward_macs(dict(s, total_ut_steps=1))
    assert ref.forward_macs(s) == 4 * one
    # The work functions: the same mathematics by kind, forward once and
    # backward twice.
    total = sum(fn(s, 1)[0] for fn in (
        ref.loop_attn_work, ref.loop_mlp_work, ref.loop_head_work))
    assert total == 6 * want
    assert ref.loop_head_work(s, 1)[0] / total == pytest.approx(
        0.252, abs=2e-3)
