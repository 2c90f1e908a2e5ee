"""Model zoo: forward shapes, param counts, and train/eval mode plumbing.

Param-count pins are the strongest cheap parity check against the reference's
PyTorch models (SURVEY.md C7): matching counts means matching architecture.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gtopkssgd_tpu.models import available_models, get_model


def n_params(variables):
    return sum(x.size for x in jax.tree.leaves(variables["params"]))


def init_and_apply(model, spec, batch=2, **apply_kw):
    rng = jax.random.PRNGKey(0)
    if spec.name == "lstm":
        x = jnp.zeros((batch,) + tuple(spec.example_shape), jnp.int32)
    else:
        x = jnp.zeros((batch,) + tuple(spec.example_shape), jnp.float32)
    variables = model.init({"params": rng, "dropout": rng}, x)
    out = model.apply(variables, x, **apply_kw)
    return variables, out


def test_registry_lists_reference_workloads():
    # The six paper workloads' model families must all be buildable.
    assert {"vgg16", "resnet20", "resnet50", "alexnet", "lstm", "lstman4"} <= set(
        available_models()
    )
    with pytest.raises(ValueError):
        get_model("not-a-model")


@pytest.mark.parametrize(
    "name,expected_params,tol",
    [
        ("resnet20", 272_474, 0.02),   # He et al. CIFAR ResNet-20 ~0.27M
        ("resnet56", 855_770, 0.02),   # ~0.85M
        ("vgg16", 15_000_000, 0.07),   # CIFAR VGG-16+BN ~14.7-15.3M
        ("alexnet", 61_100_840, 0.001),  # torchvision AlexNet exactly
        ("resnet50", 25_557_032, 0.02),  # ~25.5M
    ],
)
def test_vision_param_counts(name, expected_params, tol):
    # Shape-only: eval_shape traces without compiling/executing, so the big
    # ImageNet models cost milliseconds here instead of minutes.
    model, spec = get_model(name)
    rng = jax.random.PRNGKey(0)
    x = jnp.zeros((1,) + tuple(spec.example_shape), jnp.float32)
    variables = jax.eval_shape(
        lambda: model.init({"params": rng, "dropout": rng}, x)
    )
    got = n_params(variables)
    assert abs(got - expected_params) / expected_params <= tol, got
    out = jax.eval_shape(lambda v: model.apply(v, x), variables)
    classes = 10 if spec.dataset == "cifar10" else 1000
    assert out.shape == (1, classes)
    assert out.dtype == jnp.float32


@pytest.mark.parametrize("name", ["vgg16", "resnet20"])
def test_train_mode_updates_batch_stats(name):
    model, spec = get_model(name)
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (4,) + tuple(spec.example_shape))
    variables = model.init({"params": rng, "dropout": rng}, x)
    out, mutated = model.apply(
        variables, x, train=True,
        rngs={"dropout": rng}, mutable=["batch_stats"],
    )
    # running stats must actually move in train mode
    before = jax.tree.leaves(variables["batch_stats"])
    after = jax.tree.leaves(mutated["batch_stats"])
    assert any(
        not np.allclose(np.asarray(a), np.asarray(b)) for a, b in zip(before, after)
    )


def test_ptb_lstm_shapes_and_carry():
    model, spec = get_model("lstm")
    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (3, 35), 0, 10000)
    variables = model.init({"params": rng}, tokens)
    (logits, carry), _ = model.apply(variables, tokens, mutable=[])
    assert logits.shape == (3, 35, 10000)
    assert len(carry) == 2 and len(carry[0]) == 2
    # carry threads across windows: different carry -> different logits
    logits2, carry2 = model.apply(variables, tokens, carry)
    assert not np.allclose(np.asarray(logits), np.asarray(logits2))
    # Zaremba "medium" ~ 19.8M params
    got = n_params(variables)
    assert abs(got - 19_800_000) / 19_800_000 < 0.05, got


def test_an4_shapes_and_output_length():
    model, spec = get_model("lstman4")
    rng = jax.random.PRNGKey(0)
    # Shape-only, as test_vision_param_counts: eval_shape traces the four
    # bidirectional layers' scans without compiling or running one.
    for t in (100, 101, 57):
        x = jax.ShapeDtypeStruct((2, t, 161), jnp.float32)
        variables = jax.eval_shape(model.init, {"params": rng}, x)
        logits = jax.eval_shape(model.apply, variables, x)
        assert logits.shape[0] == 2 and logits.shape[2] == 29
        assert logits.shape[1] == model.output_length(t), (
            t, logits.shape, model.output_length(t)
        )


def test_bfloat16_forward():
    model, spec = get_model("resnet20", dtype=jnp.bfloat16)
    variables, out = init_and_apply(model, spec, batch=2)
    # params stay f32, output cast back to f32
    assert all(
        v.dtype == jnp.float32 for v in jax.tree.leaves(variables["params"])
    )
    assert out.dtype == jnp.float32


def test_resnet_bf16_forward_tracks_f32():
    """BatchNorm now emits activations in the compute dtype; flax still
    reduces the statistics in f32 (force_float32_reductions), so a bf16
    forward must stay close to the f32 one — this pins the numerics the
    round-3 BN-dtype change relies on."""
    import numpy as np

    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (4, 32, 32, 3), jnp.float32)
    outs = {}
    for dt in (jnp.float32, jnp.bfloat16):
        model, _ = get_model("resnet20", dtype=dt)
        vars_ = model.init({"params": rng}, x[:1])
        logits, _ = model.apply(vars_, x, train=True, mutable=["batch_stats"])
        outs[dt] = np.asarray(logits, np.float32)
        assert np.isfinite(outs[dt]).all()
    # bf16 has ~3 decimal digits; logits of an untrained net are O(1).
    np.testing.assert_allclose(outs[jnp.bfloat16], outs[jnp.float32],
                               atol=0.15, rtol=0.15)


def test_space_to_depth_stem_equivalence():
    """The s2d stem ([B,115,115,12] conv 4x4/VALID) computes the same
    linear map as the 7x7/2 pad-3 stem when its kernel is the 7x7 kernel
    embedded in the zero-padded 8x8 block layout — pinning that the
    opt-in MXU-friendly stem is the SAME architecture, not a different
    one."""
    import numpy as np

    rng = jax.random.PRNGKey(3)
    x = jax.random.normal(rng, (2, 224, 224, 3), jnp.float32)

    std, _ = get_model("resnet50")
    s2d, _ = get_model("resnet50", space_to_depth=True)
    vs = std.init({"params": rng}, x[:1])
    vd = s2d.init({"params": rng}, x[:1])

    # Embed the 7x7 kernel into 8x8 (zero LAST row/col: the pad-3+3
    # window covers rows -3..+4 about each even center) and regroup into
    # the 2x2-block channel layout used by the s2d reshape.
    w7 = np.asarray(vs["params"]["Conv_0"]["kernel"])        # [7,7,3,64]
    w8 = np.zeros((8, 8, 3, 64), np.float32)
    w8[:7, :7] = w7
    w4 = w8.reshape(4, 2, 4, 2, 3, 64).transpose(0, 2, 1, 3, 4, 5)
    w4 = w4.reshape(4, 4, 12, 64)

    vd = jax.tree.map(lambda a: a, vd)  # unfreeze-by-copy (plain dicts)
    vd["params"]["Conv_0"]["kernel"] = jnp.asarray(w4)
    # Same downstream weights so the full forwards must agree.
    for name in vs["params"]:
        if name != "Conv_0":
            vd["params"][name] = vs["params"][name]

    ys = std.apply(vs, x, train=False)
    yd = s2d.apply(vd, x, train=False)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(yd),
                               atol=2e-4, rtol=2e-4)


def test_space_to_depth_param_count():
    """s2d trades the 7x7x3 stem (9408) for 4x4x12 (12288): +2880 params,
    all other shapes unchanged."""
    std, _ = get_model("resnet50")
    s2d, _ = get_model("resnet50", space_to_depth=True)
    x = jnp.zeros((1, 224, 224, 3))
    rng = jax.random.PRNGKey(0)
    n_std, n_s2d = (
        n_params(jax.eval_shape(m.init, {"params": rng}, x))
        for m in (std, s2d))
    assert n_s2d - n_std == 12288 - 9408 == 2880
