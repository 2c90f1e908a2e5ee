"""The Kimi-Linear decoder (models/kimi_linear.py) at its ``tiny`` preset on
the CPU: against the frozen plain reference (perfbench/refmodels/
kimi_linear.py), loss and every gradient leaf; the chunked per-channel delta
rule (models/delta_rule.py) against the per-token recurrence, at a length
that is not whole chunks and at decays under which the unbounded factoring
overflows; the tie to the Gated DeltaNet's rule; the latent attention without
position encoding against Kanana's mixer; the convolution's kernels at q, k
and v of one width; the shares of the expert group; the registry, the trainer
and the published size. (What the expert layer does under imbalance is
tests/test_qwen3_next.py's and the bias's rule tests/test_trinity_mini.py's:
the code is ``models/decoder.py``'s for all.)"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from gtopkssgd_tpu.models import decoder, delta_rule, get_model  # noqa: E402
from gtopkssgd_tpu.models import kanana2, qwen3_next  # noqa: E402
from gtopkssgd_tpu.models import kimi_linear as prog  # noqa: E402
from gtopkssgd_tpu.obs import counters  # noqa: E402
from gtopkssgd_tpu.ops import gdn_conv  # noqa: E402
from perfbench.refmodels import kimi_linear as ref  # noqa: E402
from test_qwen3_next import leaves  # noqa: E402

TINY = prog.PRESETS["tiny"]
PUBLISHED = prog.PRESETS["48b_a3b_ep32"]
F32 = jnp.float32


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


def program_side(params, biases, batch):
    module = prog.KimiLinear("tiny", F32)

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


# ------------------------------------------------ against the reference
def test_parameters_and_state_are_the_references_leaf_for_leaf(seeded):
    params, biases, batch = seeded
    made = jax.jit(lambda k: prog.KimiLinear("tiny").init(
        {"params": k}, batch["tokens"]))(jax.random.PRNGKey(0))
    shapes = lambda tree: [(k, v.shape, v.dtype) for k, v in leaves(tree)]
    assert shapes(made["params"]) == shapes(params)
    assert shapes(made["batch_stats"]) == shapes(biases)
    assert set(made) == {"params", "batch_stats"}
    assert [k for k, _ in leaves(made["batch_stats"])] == [
        f"['layer_{i}']['moe']['router_bias']" for i in range(2)]
    assert set(params["layer_0"]) == {"input_norm", "pre_mlp_norm", "mixer",
                                      "moe"}
    assert set(params["layer_0"]["mixer"]) == {
        "in_proj_qkv", "in_proj_fzb", "conv", "f_proj", "dt_bias", "A_log",
        "z_proj", "norm", "out_proj"}
    assert set(params["layer_1"]["mixer"]) == {
        "q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj", "o_proj"}
    # A_log = log U(1, 16): every head decays, none by more than 16 a unit.
    a_log = np.asarray(made["params"]["layer_0"]["mixer"]["A_log"])
    assert (a_log >= 0).all() and (a_log <= np.log(16)).all()


def test_program_equals_reference_in_float32(seeded, reference_side):
    """Loss, logits, every gradient leaf and the moved biases in float32
    (the XLA forms: every CPU run's). (What a lower precision does to the
    comparison is the cell's control's to show:
    tests/perfbench/test_perfbench_cell_kimi_linear.py.)"""
    params, biases, batch = seeded
    (want_loss, want_moved), want_grads = reference_side
    (loss, (counts, moved)), grads = program_side(params, biases, batch)
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
    logits = prog.KimiLinear("tiny").apply(state, batch["tokens"])
    assert float(jnp.max(jnp.abs(logits - jnp.dot(hidden, head)))) < 1e-4
    # The counts: the KDA layer's alone, both expert layers'.
    assert counts["kda_log_decay_min"].shape == (1,)
    assert (np.asarray(counts["kda_log_decay_min"]) < 0).all()
    assert ((np.asarray(counts["kda_beta_mean"]) > 0)
            & (np.asarray(counts["kda_beta_mean"]) < 1)).all()
    assert counts["moe_count"].shape == (2, TINY["num_experts"])
    assert set(counters.model_counters(counts)) == set(
        counters.MOE_FIELDS) | set(counters.MOE_BALANCE_FIELDS) \
        | set(counters.KDA_FIELDS)
    assert counters.MODEL_COUNTERS["kda"][0] == counters.KDA_FIELDS


# ------------------------------------------------- the per-channel rule
def rule_inputs(length, decay, heads=2, d_k=16, d_v=16, seed=0):
    """q, k unit a head, v, a log decay a channel drawn in [-decay, 0] a
    token (a quarter of the channels do not decay at all: a row of the
    state that stays while its neighbours vanish), beta in (0, 1)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (2, length, heads, d_k)))
    k = unit(jax.random.normal(keys[1], (2, length, heads, d_k)))
    v = jax.random.normal(keys[2], (2, length, heads, d_v))
    g = -decay * jax.random.uniform(keys[3], (2, length, heads, d_k))
    g = jnp.where(jax.random.uniform(keys[4], (heads, d_k)) < 0.25, 0.0, g)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (2, length, heads)))
    return q, k, v, g, beta


def recurrence(q, k, v, g, beta):
    state = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]), F32)
    return ref.delta_rule(q, k, v, g, beta, state)[1]


def both_rules(args, chunk):
    """(values, gradients of a weighted sum to every argument) of the chunked
    form and of the reference's per-token recurrence."""
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    out = []
    for rule in (lambda *a: delta_rule.xla_delta_rule(*a, chunk), recurrence):
        value, pull = jax.vjp(jax.jit(rule), *args)
        out.append((value, pull(weight)))
    return out


@pytest.mark.parametrize("length,chunk,decay", [
    (77, 16, 0.3), (50, 64, 1.0), (33, 8, 0.1)])
def test_chunked_rule_equals_the_per_token_recurrence(length, chunk, decay):
    """Also at a length that is not whole chunks (77 = 4 x 16 + 13), at a
    chunk of one sub-block (8) and of four (64)."""
    (mine, mine_grads), (want, want_grads) = both_rules(
        rule_inputs(length, decay), chunk)
    assert mine.shape == want.shape == (2, length, 2, 16)
    assert float(jnp.max(jnp.abs(mine - want))) < 2e-5
    for a, b in zip(mine_grads, want_grads):
        assert float(jnp.max(jnp.abs(a - b))) \
            < 5e-5 * max(1.0, float(jnp.max(jnp.abs(b))))


def test_the_hazard_case_stays_finite_and_equal_to_the_recurrence():
    """Gates forced so that a channel's in-chunk log decay passes -200
    (down to about -500 over 64 tokens at up to -8 a token): e^{-gamma}
    overflows float32 there, and k e^{gamma_t} . k e^{-gamma_j} is inf x 0.
    The bounded form's values and gradients are finite and the recurrence's,
    which never forms a positive exponent."""
    args = rule_inputs(128, 8.0, seed=3)
    gamma = jnp.cumsum(args[3].reshape(2, 2, 64, 2, 16), axis=2)
    assert float(jnp.min(gamma)) < -200
    assert not np.isfinite(np.asarray(jnp.exp(-gamma))).all()
    (mine, mine_grads), (want, want_grads) = both_rules(args, 64)
    assert np.isfinite(np.asarray(mine)).all()
    assert float(jnp.max(jnp.abs(mine - want))) < 2e-5
    for a, b in zip(mine_grads, want_grads):
        assert np.isfinite(np.asarray(a)).all()
        assert float(jnp.max(jnp.abs(a - b))) \
            < 5e-5 * max(1.0, float(jnp.max(jnp.abs(b))))
    # The segments are the same algebra, a few chunks at a time.
    arrays, _ = delta_rule.pad_to_chunks(args, 16)
    whole = delta_rule.delta_chunks(*arrays, 16)
    cut = delta_rule.delta_chunks_by_segments(*arrays, 16, 2)
    assert whole[5].shape == (8, 2, 2, 16)            # the decay a channel
    for a, b in zip(whole, cut):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) < 1e-6


def test_a_decay_equal_across_a_heads_channels_is_the_gated_delta_rule():
    """g [B, S, H, d_k] with one number a head and token gives what
    ``qwen3_next.chunked_delta_rule`` gives for g [B, S, H]: the two rules
    are one, told apart by the shape of g."""
    q, k, v, g, beta = rule_inputs(80, 0.2, seed=5)
    a_head = g[..., 0] - 0.05
    want = qwen3_next.chunked_delta_rule(q, k, v, a_head, beta, 16)
    mine = delta_rule.xla_delta_rule(
        q, k, v, jnp.broadcast_to(a_head[..., None], q.shape), beta, 16)
    assert float(jnp.max(jnp.abs(mine - want))) < 1e-5
    assert float(jnp.max(jnp.abs(want))) > 0.1
    # ... and both are the per-token recurrence's.
    assert float(jnp.max(jnp.abs(recurrence(
        q, k, v, jnp.broadcast_to(a_head[..., None], q.shape), beta)
        - want))) < 2e-5


# -------------------------------------------------------------- the mixers
def test_latent_attention_without_position_is_kananas_with_no_rotation(
        monkeypatch):
    """``mla_use_nope``: Kanana's mixer with the rotation made the identity
    (same leaves, same values), and not Kanana's mixer as it is."""
    sizes = dict(TINY, seq_len=24)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 24, TINY["hidden_size"]))
    module = kanana2.LatentAttention(sizes, F32)
    shapes = jax.eval_shape(
        lambda k: module.init({"params": k}, x), jax.random.PRNGKey(6))
    keys = jax.random.split(jax.random.PRNGKey(6), len(leaves(shapes)))
    params = jax.tree.unflatten(jax.tree.structure(shapes), [
        0.1 * jax.random.normal(k, s.shape)
        for k, s in zip(keys, jax.tree.leaves(shapes))])
    mine = jax.jit(module.apply)(params, x)
    turning = kanana2.LatentAttention(dict(sizes, mla_use_nope=False), F32)
    turned = jax.jit(turning.apply)(params, x)
    assert float(jnp.max(jnp.abs(mine - turned))) > 1e-3
    monkeypatch.setattr(kanana2, "rotary_interleaved", lambda a, theta: a)
    assert np.array_equal(
        np.asarray(jax.jit(lambda *a: turning.apply(*a))(params, x)),
        np.asarray(mine))


def test_convolution_kernels_take_q_k_and_v_of_one_width(monkeypatch):
    """``ops/gdn_conv.py`` at the KDA mixer's shape (q, k and v equally
    wide, the activations exactly as wide as the taps), interpret mode:
    ``convolved``'s values and gradients."""
    monkeypatch.setattr(gdn_conv, "LANES", 16)
    monkeypatch.setattr(gdn_conv, "TOKENS", 16)
    jax.clear_caches()
    heads, d_k, length = 2, 16, 32
    width = heads * d_k
    assert gdn_conv.blocks_of(length, 3 * width, 3 * width, width, d_k)
    keys = jax.random.split(jax.random.PRNGKey(8), 3)
    x = jax.random.normal(keys[0], (2, length, 3 * width)).astype(jnp.bfloat16)
    taps = jax.random.uniform(keys[1], (4, 3 * width), F32, -0.5, 0.5)
    weight = jax.random.normal(keys[2], (3, 2, length, width))
    total = lambda fn: lambda x, taps: sum(
        jnp.sum(a * w) for a, w in zip(fn(x, taps), weight))
    kernel = lambda x, taps: qwen3_next.kernel_conv(x, taps, width, d_k)
    plain = lambda x, taps: prog.convolved(x, taps, d_k)
    for a, b in zip(kernel(x, taps), plain(x, taps)):
        assert a.shape == b.shape == (2, length, width)
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5
    for a, b in zip(jax.grad(total(kernel), (0, 1))(x, taps),
                    jax.grad(total(plain), (0, 1))(x, taps)):
        a, b = a.astype(F32), b.astype(F32)
        assert float(jnp.max(jnp.abs(a - b))) \
            < 2e-2 * float(jnp.max(jnp.abs(b)))
    jax.clear_caches()


@pytest.mark.parametrize("tpu,length,conv,attention", [
    (False, 8192, "xla", "blocked"), (True, 8192, "kernel", "kernel"),
    (True, 8000, "xla", "blocked")])
def test_the_forms_follow_the_backend_and_the_shapes(
        tpu, length, conv, attention, monkeypatch):
    monkeypatch.setattr(decoder, "on_tpu", lambda: tpu)
    assert prog.KimiLinear("48b_a3b_ep32").forms(length) == {
        "attention_form": attention, "conv_form": conv, "delta_form": "xla",
        "scan_form": "xla"}
    assert prog.KimiLinear("tiny").forms(128)["conv_form"] == "xla"


# ----------------------------------------------- the expert group's shares
def test_the_shares_of_every_rank_add_up_to_the_uncut_layer():
    """The guide's section 4: 16 experts in ``expert_parallel`` = 4 shares
    of 4 (published: 32 shares of 8, ``expert_offset`` 0, 8, ..., 248)
    under a bias that moves the choice: the sum of the shares' routed
    parts, with the shared expert (what every chip computes alike) counted
    once, is the uncut layer's, in the program and in the reference."""
    experts, held = TINY["num_experts"], TINY["experts_held"]
    ranks = TINY["expert_parallel"]
    assert ranks * held == experts
    assert PUBLISHED["expert_parallel"] * PUBLISHED["experts_held"] \
        == PUBLISHED["num_experts"] == 256
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
            y, load, dropped, _ = decoder.SparseMoE(
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
                * TINY["num_experts_per_token"]


# ------------------------------------------------ registry, trainer, size
def test_model_registry_and_trainer_run_the_decoder(tmp_path):
    """``--dnn kimi_linear`` through ``Trainer`` like every other model, on
    two devices: the spec's fields, its presets, three steps, the bias in
    ``batch_stats`` (moved, equal on every replica) and the forms and the
    counters in the records."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    model, spec = get_model("kimi_linear", preset="tiny")
    assert (spec.input_key, spec.loss, spec.carry) == ("tokens", "own", False)
    assert spec.presets == ("48b_a3b_ep32", "tiny")
    with pytest.raises(ValueError, match=r"kimi_linear has the presets "
                                         r"\['48b_a3b_ep32', 'tiny'\]"):
        get_model("kimi_linear", preset="30b_a3b_ep16")
    with Trainer(TrainConfig(dnn="kimi_linear", model_preset="tiny",
                             batch_size=2, nworkers=2, compression="gtopk",
                             density=0.01, log_interval=1,
                             out_dir=str(tmp_path))) as t:
        assert t.cfg.dataset == "tokens"
        assert t.num_params == sum(v.size for v in jax.tree.leaves(
            t.state.params)) == 118_708
        assert t._manifest["conv_form"] == t._manifest["delta_form"] == "xla"
        assert t._manifest["attention_form"] == "blocked"
        out = t.train(3)
        assert np.isfinite(out["loss"]) and out["moe_slots_dropped"] == 0.0
        assert out["kda_log_decay_min"] < 0 < out["kda_beta_mean"] < 1
        assert 0 < out["moe_bias_absmax"] <= 2 * 2 * 0.001 * 1.001
        biases = jax.tree.leaves(t.state.batch_stats)
        assert len(biases) == 2
        for bias in biases:
            copies = [np.asarray(s.data) for s in bias.addressable_shards]
            assert len(copies) == 2 and np.asarray(bias).any()
            assert np.array_equal(copies[0], copies[1])
        assert np.isfinite(t.test()["val_loss"])
    rows = [json.loads(line) for line in open(tmp_path / "metrics.jsonl")]
    train = [r for r in rows if r["kind"] == "train"]
    assert len(train) == 3
    fields = set(counters.MOE_FIELDS) | set(counters.MOE_BALANCE_FIELDS) \
        | set(counters.KDA_FIELDS)
    assert all(fields <= set(r) and r["scan_form"] == "xla" for r in train)


def test_published_preset_counts_its_parameters():
    """N = 499,213,536 from the initialised tree's shapes (no memory
    taken), part by part as ISSUE 48 and the configuration's
    ``cut.parameters`` have it; the bias is in no leaf of it."""
    module = prog.KimiLinear("48b_a3b_ep32", jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda k: module.init({"params": k}, jnp.zeros((1, 64), jnp.int32)),
        jax.random.PRNGKey(0))
    assert set(shapes) == {"params", "batch_stats"}
    params = shapes["params"]
    size = lambda tree: sum(v.size for v in jax.tree.leaves(tree))
    assert size(params) == 499_213_536
    assert size(params) % 32 == 0 and size(params) % 288
    assert all(v.dtype == F32 for v in jax.tree.leaves(params))
    assert size(shapes["batch_stats"]) == 4 * 256
    assert {k: v.shape for k, v in params["layer_0"]["mixer"].items()} == {
        "in_proj_qkv": (2304, 12288), "in_proj_fzb": (2304, 288),
        "conv": (4, 12288), "f_proj": (128, 4096), "dt_bias": (4096,),
        "A_log": (32,), "z_proj": (128, 4096), "norm": (128,),
        "out_proj": (4096, 2304)}
    assert size(params["layer_0"]["mixer"]) == 39_514_272
    assert {k: v.shape for k, v in params["layer_3"]["mixer"].items()} == {
        "q_proj": (2304, 32 * 192), "kv_a_proj": (2304, 576),
        "kv_a_norm": (512,), "kv_b_proj": (512, 32 * 256),
        "o_proj": (4096, 2304)}
    assert size(params["layer_3"]["mixer"]) == 29_114_880
    moe = params["layer_2"]["moe"]
    assert size(moe) == 64_290_816
    assert moe["router"].shape == (2304, 256) and "shared_gate" not in moe
    assert size(params["layer_0"]) == 103_809_696
    assert size(params["layer_3"]) == 93_410_304
    assert size({k: params[k] for k in ("embed", "head", "final_norm")}) \
        == 94_374_144
    assert prog.kinds_of(PUBLISHED) == ("kda", "kda", "kda", "mla")
    with open(os.path.join(REPO, "perfbench", "configs",
                           "kimi_linear_48b_a3b_ep32.json")) as fh:
        assert json.load(fh)["parameters"] == size(params)
