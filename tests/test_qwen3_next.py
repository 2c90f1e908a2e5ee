"""The Qwen3-Next decoder (models/qwen3_next.py) at its ``tiny`` preset on
the CPU: against the frozen plain reference (perfbench/refmodels/
qwen3_next.py), the chunked delta rule against the per-token recurrence,
the expert shares against the uncut layer, and the rule that no token-slot
is dropped."""

import collections
import contextlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from gtopkssgd_tpu.models import decoder, get_model  # noqa: E402
from gtopkssgd_tpu.models import keye_vl2 as keye_prog  # noqa: E402
from gtopkssgd_tpu.models import qwen3_next as prog  # noqa: E402
from gtopkssgd_tpu.models import trinity_mini as trinity_prog  # noqa: E402
from gtopkssgd_tpu.obs import counters  # noqa: E402
from perfbench.refmodels import keye_vl2 as keye_ref  # noqa: E402
from perfbench.refmodels import qwen3_next as ref  # noqa: E402
from perfbench.refmodels import trinity_mini as trinity_ref  # noqa: E402

TINY = prog.PRESETS["tiny"]


def leaves(tree):
    return [(jax.tree_util.keystr(k), v)
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]]


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights (the reference's init, every leaf then moved off its
    initial value so that a zero-initialised norm weight matters) and a
    batch of two sequences."""
    module, example = ref.build(TINY, jnp.float32)
    params = jax.jit(lambda k: module.init({"params": k}, example, False))(
        jax.random.PRNGKey(0))["params"]
    keys = jax.random.split(jax.random.PRNGKey(1), len(jax.tree.leaves(params)))
    params = jax.tree.unflatten(
        jax.tree.structure(params),
        [p + 0.05 * jax.random.normal(k, p.shape)
         for p, k in zip(jax.tree.leaves(params), keys)])
    rng = np.random.default_rng(0)
    draw = lambda: rng.integers(0, TINY["vocab_rows"], (2, TINY["seq_len"])
                                ).astype(np.int32)
    return params, {"tokens": draw(), "targets": draw()}


@pytest.fixture(scope="module")
def reference_side(seeded):
    """((loss, gradients), logits) of the reference in float32."""
    params, batch = seeded
    module, _ = ref.build(TINY, jnp.float32)

    def both(p):
        hidden, head = module.apply({"params": p}, batch["tokens"], False)
        return jax.value_and_grad(lambda p: ref.loss(
            module, {"params": p}, (), batch, None, True)[0])(p), \
            jnp.dot(hidden, head)

    return jax.jit(both)(params)           # one compile serves both


def program_logits(dtype, params, batch):
    module = prog.Qwen3Next("tiny", dtype)
    return jax.jit(lambda p: module.apply({"params": p}, batch["tokens"]))(
        params)


def program_side(dtype, params, batch):
    module = prog.Qwen3Next("tiny", dtype)
    out = jax.jit(jax.value_and_grad(lambda p: module.apply(
        {"params": p}, batch["tokens"], batch["targets"], train=True)[0]))(params)
    return out, program_logits(dtype, params, batch)


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


def test_parameters_are_the_references_leaf_for_leaf(seeded):
    params, batch = seeded
    mine = jax.eval_shape(
        lambda k: prog.Qwen3Next("tiny").init({"params": k}, batch["tokens"]),
        jax.random.PRNGKey(0))["params"]
    shape = lambda t: [(k, v.shape, v.dtype) for k, v in leaves(t)]
    assert shape(mine) == shape(params)
    assert sum(v.size for _, v in leaves(mine)) == 212_904


def test_program_equals_reference_in_float32(seeded, reference_side):
    logit, loss, grad = gaps(reference_side,
                             program_side(jnp.float32, *seeded))
    assert logit < 1e-4 and loss < 1e-5 and grad < 1e-3, (logit, loss, grad)


def test_bfloat16_compute_fails_the_float32_tolerances(
        seeded, reference_side, kept_and_not):
    """The tolerances above are tight enough to see one precision down: the
    program in bfloat16 against the float32 reference breaks at least one.
    (Its loss and gradient are ``kept_and_not``'s: one compile for both.)"""
    ((loss, _), grad), _ = kept_and_not["kept"]
    logit, loss, grad = gaps(reference_side, (
        (loss, grad), program_logits(jnp.bfloat16, *seeded)))
    assert logit >= 1e-4 or loss >= 1e-5 or grad >= 1e-3, (logit, loss, grad)


def delta_inputs(length, seed=0, batch=2, heads=2, d_k=8, d_v=8):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (batch, length, heads, d_k))) / d_k ** 0.5
    k = unit(jax.random.normal(keys[1], (batch, length, heads, d_k)))
    v = jax.random.normal(keys[2], (batch, length, heads, d_v))
    g = -jax.nn.softplus(jax.random.normal(keys[3], (batch, length, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, length, heads)))
    return q, k, v, g, beta


def recurrence(q, k, v, g, beta):
    """The delta rule token by token, in numpy float64: the definition."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    batch, length, heads, d_k = q.shape
    state = np.zeros((batch, heads, d_k, v.shape[-1]))
    out = np.zeros(v.shape)
    for t in range(length):
        state = state * np.exp(g[:, t])[..., None, None]
        read = np.einsum("bhkv,bhk->bhv", state, k[:, t])
        delta = beta[:, t][..., None] * (v[:, t] - read)
        state = state + np.einsum("bhk,bhv->bhkv", k[:, t], delta)
        out[:, t] = np.einsum("bhkv,bhk->bhv", state, q[:, t])
    return out


@pytest.mark.parametrize("length,chunk", [(64, 16), (150, 16), (7, 16)])
def test_chunked_delta_rule_equals_the_recurrence(length, chunk):
    """Forward and gradients, at lengths that are and are not multiples of
    the chunk: the program's chunked form and the reference's per-token
    scan in blocks, each against the recurrence in float64."""
    args = delta_inputs(length)
    want = recurrence(*args)
    forms = {"program": lambda *a: prog.chunked_delta_rule(*a, chunk),
             "reference": ref.delta_rule}
    weight = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    pull = lambda fn: jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2, 3, 4)))(*args)
    definition = pull(ref.delta_rule)
    for name, fn in forms.items():
        assert np.max(np.abs(np.asarray(fn(*args)) - want)) < 1e-5, name
    for mine, theirs in zip(pull(forms["program"]), definition):
        assert float(jnp.max(jnp.abs(mine - theirs))) < 1e-5


# The expert layer is models/decoder.py's for all three decoders of the zoo:
# with a gated shared expert (qwen3_next), without one (keye_vl2), and with
# an ungated one under a sigmoid router with a balancing bias (trinity_mini,
# whose preset is read through ``moe_sizes`` and whose bias rides in
# ``batch_stats``: zero here).
DECODERS = {
    "qwen3_next": (prog, ref, TINY),
    "keye_vl2": (keye_prog, keye_ref, keye_prog.PRESETS["tiny"]),
    "trinity_mini": (trinity_prog, trinity_ref,
                     trinity_prog.PRESETS["tiny"]),
}
all_decoders = pytest.mark.parametrize("decoder", sorted(DECODERS))


def variables(decoder, params):
    if decoder != "trinity_mini":
        return {"params": params}
    experts = DECODERS[decoder][2]["num_experts"]
    return {"params": params,
            "batch_stats": {"router_bias": jnp.zeros((experts,))}}


def program_layer(decoder, sizes, params, x, **kw):
    """(y, slots per held expert, slots dropped) of the program's layer."""
    prog = DECODERS[decoder][0]
    if decoder == "trinity_mini":
        sizes = prog.moe_sizes(sizes)
    return prog.SparseMoE(sizes, jnp.float32, **kw).apply(
        variables(decoder, params), x)[:3]


def reference_layer(decoder, sizes, params, x):
    y = DECODERS[decoder][1].SparseMoE(sizes, jnp.float32).apply(
        variables(decoder, params), x)
    return y[0] if decoder == "trinity_mini" else y


def moe_params(decoder, seed=3):
    """The parameters of one uncut expert layer: all 16 experts held."""
    _, ref, tiny = DECODERS[decoder]
    whole = dict(tiny, experts_held=tiny["num_experts"], expert_offset=0)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 48, tiny["hidden_size"]))
    module = ref.SparseMoE(whole, jnp.float32)
    params = module.init({"params": jax.random.PRNGKey(seed + 1)}, x)["params"]
    # A livelier router than N(0, 0.02), so that loads differ.
    params["router"] = params["router"] * 40.0
    return whole, params, x


def share_of(params, rank, held):
    cut = lambda a: a[rank * held:(rank + 1) * held]
    return dict(params, **{k: cut(params[k]) for k in
                           ("experts_gate", "experts_up", "experts_down")})


@all_decoders
def test_the_shares_add_up_to_the_uncut_layer(decoder):
    """16 experts in the preset's ``expert_parallel`` = 4 shares of 4: the
    sum of the four shares' outputs, with what every chip computes alike (the
    shared expert, where the model has one) counted once, is the uncut
    layer's, in the program and in the reference."""
    _, _, tiny = DECODERS[decoder]
    whole, params, x = moe_params(decoder)
    ranks, held = tiny["expert_parallel"], tiny["experts_held"]
    assert ranks * held == tiny["num_experts"]
    uncut = reference_layer(decoder, whole, params, x)
    only_shared = dict(tiny, experts_held=held, expert_offset=10 ** 6)
    shared = reference_layer(decoder, only_shared, share_of(params, 0, held), x)
    assert bool(jnp.any(shared)) == bool(
        "shared_expert_intermediate_size" in tiny
        or tiny.get("num_shared_experts"))
    loads = []
    for side in ("program", "reference"):
        total = 0.0
        for rank in range(ranks):
            sizes = dict(tiny, experts_held=held, expert_offset=rank * held)
            p = share_of(params, rank, held)
            if side == "program":
                y, load, dropped = program_layer(decoder, sizes, p, x)
                loads.append(np.asarray(load))
                assert int(dropped) == 0
            else:
                y = reference_layer(decoder, sizes, p, x)
            total = total + (y - shared)
        assert float(jnp.max(jnp.abs(total + shared - uncut))) < 1e-5, side
    # Every token-slot landed on exactly one share.
    assert int(np.sum(loads)) == x.shape[0] * x.shape[1] * tiny["num_experts_per_tok"]


def biased_to_held_experts(decoder="qwen3_next"):
    """An expert layer whose router sends every token to the same four
    experts, all held here: every one of the layer's slots falls here."""
    _, ref, tiny = DECODERS[decoder]
    sizes = dict(tiny, experts_held=4, expert_offset=4)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, tiny["hidden_size"]))
    params = ref.SparseMoE(sizes, jnp.float32).init(
        {"params": jax.random.PRNGKey(6)}, x)["params"]
    # |x.sum| grows with the input, the bias does not depend on it.
    bias = jnp.zeros((tiny["num_experts"],)).at[4:8].set(50.0)
    params["router"] = params["router"] * 0.0 + bias[None, :] / tiny["hidden_size"]
    return sizes, params, jnp.abs(x) + 1.0


@all_decoders
@pytest.mark.parametrize("block_rows", [None, 64])
def test_no_slot_is_dropped_under_a_router_that_overloads_the_held(
        block_rows, decoder):
    _, _, tiny = DECODERS[decoder]
    sizes, params, x = biased_to_held_experts(decoder)
    want = reference_layer(decoder, sizes, params, x)
    y, load, dropped = program_layer(decoder, sizes, params, x,
                                     block_rows=block_rows)
    slots = x.shape[0] * x.shape[1] * tiny["num_experts_per_tok"]
    assert int(jnp.sum(load)) == slots and int(dropped) == 0
    assert float(jnp.max(jnp.abs(y - want))) < 1e-5
    got = counters.moe_counters(load[None], dropped[None])
    assert float(got["moe_slots_dropped"]) == 0.0
    assert float(got["moe_slots_held"]) == slots
    assert float(got["moe_load_max"]) == float(got["moe_load_mean"]) == slots / 4


def test_a_capacity_limited_layer_fails_the_no_drop_test():
    """The same layer with its loop capped at one block of 64 slots: the
    counter counts what was left out and the output is no longer the
    reference's."""
    sizes, params, x = biased_to_held_experts()
    want = ref.SparseMoE(sizes, jnp.float32).apply({"params": params}, x)
    y, load, dropped, _ = prog.SparseMoE(
        sizes, jnp.float32, block_rows=64, max_blocks=1).apply(
            {"params": params}, x)
    assert int(dropped) == int(jnp.sum(load)) - 64 > 0
    assert float(jnp.max(jnp.abs(y - want))) > 1e-3
    got = counters.moe_counters(load[None], dropped[None])
    assert float(got["moe_slots_dropped"]) == float(dropped)


def test_expert_blocks_give_the_gradients_of_one_block():
    """The hand-written backward of the slot loop: four blocks of 64 slots
    against one block of all of them, every gradient."""
    sizes = dict(TINY)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 128, TINY["hidden_size"]))
    params = ref.SparseMoE(sizes, jnp.float32).init(
        {"params": jax.random.PRNGKey(8)}, x)["params"]

    def grads(block_rows):
        module = prog.SparseMoE(sizes, jnp.float32, block_rows=block_rows)
        return jax.grad(lambda p, x: jnp.sum(
            module.apply({"params": p}, x)[0] ** 2), argnums=(0, 1))(params, x)

    one, many = grads(None), grads(64)
    for (name, a), (_, b) in zip(leaves(one), leaves(many)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4, name
    assert float(jnp.linalg.norm(one[0]["router"])) > 0


def test_model_registry_and_trainer_run_the_decoder(tmp_path):
    """``--dnn qwen3_next`` through ``Trainer`` like every other model: the
    spec's fields, a preset that only this model takes, two steps, and the
    expert counters in the ``train`` record."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    model, spec = get_model("qwen3_next", preset="tiny")
    assert (spec.input_key, spec.loss, spec.carry) == ("tokens", "own", False)
    assert get_model("lstm")[1].carry and get_model("lstm")[1].loss == "tokens"
    with pytest.raises(ValueError, match="model-preset"):
        get_model("resnet20", preset="tiny")
    with Trainer(TrainConfig(dnn="qwen3_next", model_preset="tiny",
                             batch_size=2, compression="gtopk", density=0.01,
                             log_interval=1, out_dir=str(tmp_path))) as t:
        assert t.cfg.dataset == "tokens" and t.num_params == 212_904
        out = t.train(2)
        assert np.isfinite(out["loss"]) and out["moe_slots_dropped"] == 0.0
        assert out["moe_slots_held"] > 0
        assert np.isfinite(t.test()["val_loss"])
    import json
    rows = [json.loads(line) for line in open(tmp_path / "metrics.jsonl")]
    train = [r for r in rows if r["kind"] == "train"]
    assert len(train) == 2
    assert all(r["moe_slots_dropped"] == 0.0 and r["moe_load_max"]
               >= r["moe_load_mean"] > 0 for r in train)
    obs = [r for r in rows if r["kind"] == "obs"]
    assert all(set(counters.MOE_FIELDS) <= set(r) for r in obs)
    assert counters.last_model_scalars()["moe_slots_held"] == \
        train[-1]["moe_slots_held"]


def test_published_preset_counts_its_parameters():
    """N = 323,677,248 from the initialised tree's shapes (no memory taken)."""
    module = prog.Qwen3Next("80b_a3b_ep64", jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda k: module.init({"params": k}, jnp.zeros((1, 4096), jnp.int32)),
        jax.random.PRNGKey(0))["params"]
    assert sum(v.size for v in jax.tree.leaves(shapes)) == 323_677_248
    assert all(v.dtype == jnp.float32 for v in jax.tree.leaves(shapes))


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


def gradient_and_primitives(module, variables, batch):
    """(((loss, (counts, what the step moved)), gradient), the gradient's
    primitives) of a decoder's training step on ``batch``, traced anew: a
    policy and a budget are read at trace time."""
    rest = {k: v for k, v in variables.items() if k != "params"}

    def loss(params):
        (value, counts), moved = module.apply(
            {"params": params, **rest}, batch["tokens"], batch["targets"],
            train=True, mutable=list(rest),
            rngs={"dropout": jax.random.PRNGKey(5)})
        return value, (counts, moved)

    grad = jax.value_and_grad(loss, has_aux=True)
    params = variables["params"]
    return (jax.jit(grad)(params),
            primitives(jax.make_jaxpr(grad)(params).jaxpr))


def tiny_gradient(params, batch):
    """``gradient_and_primitives`` of ``tiny`` with bfloat16 products."""
    return gradient_and_primitives(
        prog.Qwen3Next("tiny", jnp.bfloat16), {"params": params}, batch)


@contextlib.contextmanager
def dispatch_unnamed():
    """Every remat policy built inside is without the expert layer's
    dispatch (``decoder.KEPT_DISPATCH``): what a model whose policy forgot
    the name would compile."""
    real = jax.checkpoint_policies.save_only_these_names
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(
            jax.checkpoint_policies, "save_only_these_names",
            lambda *names: real(*(
                name for name in names if name != decoder.KEPT_DISPATCH)))
        yield


@pytest.fixture(scope="module")
def kept_and_not(seeded):
    """{"kept": by name, "not": with a budget of no bytes, which keeps
    the expert layers' dispatch and nothing else, "unnamed": the budget's
    names without the dispatch's} -> ``tiny_gradient``."""
    out = {"kept": tiny_gradient(*seeded)}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(prog, "kept_budget", lambda batch, length: 0)
        out["not"] = tiny_gradient(*seeded)
    with dispatch_unnamed():
        out["unnamed"] = tiny_gradient(*seeded)
    return out


@pytest.mark.parametrize("other", ["not", "unnamed"])
@pytest.mark.parametrize("part", ["loss", "moe_load", "moe_dropped", "gradient"])
def test_keeping_by_name_changes_no_value(kept_and_not, part, other):
    """Same operations on the same values, one execution fewer: every leaf
    bit for bit."""
    pick = lambda out: {"loss": out[0][0], "gradient": out[1], **out[0][1][0]}[part]
    kept, bare = (pick(kept_and_not[k][0]) for k in ("kept", other))
    for (name, a), (_, b) in zip(leaves(kept), leaves(bare)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    assert all(np.isfinite(np.asarray(a, np.float32)).all()
               for _, a in leaves(kept))


@pytest.mark.parametrize("primitive,kept,bare,unnamed", [
    # A DeltaNet layer's solve: forward, once more for ``prepare``'s own
    # backward, two transposed in the solve's VJP; not kept, the layer's
    # replay adds a fifth.
    ("triangular_solve", 4 * 3, 5 * 3, 4 * 3),
    # ``lax.map(prepare)`` is a scan: one fewer a DeltaNet layer.
    ("scan", 17, 17 + 3, 17),
    # A softmax's row maximum: the four routers' forward and again in
    # their layer's replay, whose scores are not kept (4 * 2), the loss's
    # and its own checkpoint's (2), and an attention query block's (two
    # blocks on ``tiny``) forward and under the block's own checkpoint
    # (2 * 2); not kept, the layer's replay adds the two blocks'.
    ("reduce_max", 4 * 2 + 2 + 2 * 2, 4 * 2 + 2 + 2 * 3, 4 * 2 + 2 + 2 * 2),
    # The dispatch: one sort and one top-k an expert layer (four on
    # ``tiny``) in the whole gradient whatever the budget, and two where
    # the policy lacks the dispatch's name.
    ("sort", 4, 4, 4 * 2),
    ("top_k", 4, 4, 4 * 2),
])
def test_a_layers_inner_checkpoints_run_twice_not_three_times(
        kept_and_not, primitive, kept, bare, unnamed):
    assert kept_and_not["kept"][1][primitive] == kept
    assert kept_and_not["not"][1][primitive] == bare
    assert kept_and_not["unnamed"][1][primitive] == unnamed


@pytest.mark.parametrize("preset,batch,budget,want", [
    # The benchmark's cell: every layer keeps its outputs.
    ("80b_a3b_ep64", 4, None,
     [(1_207_992_320, True)] * 3 + [(268_435_456, True)]),
    # Half as many tokens again, and the step's own share of the chip with
    # them: the budget holds one DeltaNet layer and the attention.
    ("80b_a3b_ep64", 6, None,
     [(1_811_988_480, True)] + [(1_811_988_480, False)] * 2
     + [(402_653_184, True)]),
    # Twice the batch fills the chip as it is (16.1 of 16.9 GB): nothing kept.
    ("80b_a3b_ep64", 8, None,
     [(2_415_984_640, False)] * 3 + [(536_870_912, False)]),
    ("tiny", 2, None, [(393_344, True)] * 3 + [(65_536, True)]),
    ("tiny", 2, 393_344 * 2,
     [(393_344, True)] * 2 + [(393_344, False), (65_536, False)]),
    ("tiny", 2, 0, [(393_344, False)] * 3 + [(65_536, False)]),
])
def test_which_layers_keep_their_outputs_follows_from_the_shapes(
        preset, batch, budget, want):
    sizes = prog.PRESETS[preset]
    if budget is None:
        budget = prog.kept_budget(batch, sizes["seq_len"])
    got = prog.kept_across_remat(sizes, batch, sizes["seq_len"], budget)
    assert got == want
    assert sum(size for size, keep in got if keep) <= budget


def test_published_depth_keeps_what_the_budget_holds_and_no_more():
    """48 layers at the cell's batch would keep 43 GB: the first five do
    (5.10 of the 5.20 GB left), the rest are rematerialised whole."""
    sizes = dict(prog.PRESETS["80b_a3b_ep64"], num_hidden_layers=48)
    got = prog.kept_across_remat(sizes, 4, 4096, prog.kept_budget(4, 4096))
    assert sum(size for size, _ in got) == 36 * 1_207_992_320 + 12 * 268_435_456
    assert [i for i, (_, keep) in enumerate(got) if keep] == [0, 1, 2, 3, 4]


def test_layers_past_the_budget_still_differentiate(seeded, kept_and_not,
                                                    monkeypatch):
    """A budget that holds the first two layers' outputs only: the other
    two (a DeltaNet layer and the attention layer) keep their expert
    layer's dispatch and nothing else, and the gradient is the same."""
    monkeypatch.setattr(prog, "kept_budget", lambda batch, length: 393_344 * 2)
    out, count = tiny_gradient(*seeded)
    assert count["triangular_solve"] == 4 * 2 + 5
    # The routers' 4 * 2 and the loss's 2 as kept; the attention layer's
    # replay runs its two query blocks a third time.
    assert count["reduce_max"] == 4 * 2 + 2 + 2 * 3
    assert (count["sort"], count["top_k"]) == (4, 4)
    for (name, a), (_, b) in zip(leaves(out), leaves(kept_and_not["kept"][0])):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
