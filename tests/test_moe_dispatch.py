"""What a decoder layer's remat keeps of the shared expert layer
(``decoder.KEPT_DISPATCH``), in every model of the registry that has one
(Qwen3-Next's budget has rows of its own in tests/test_qwen3_next.py): the
whole gradient of a ``tiny`` model holds one sort and one top-k an expert
layer, two where the policy lacks the name, and the same values either
way."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gtopkssgd_tpu.models import available_models, get_model
from test_qwen3_next import (
    dispatch_unnamed, gradient_and_primitives, leaves)

# Every decoder: a model built at a preset. One with no expert layer (the
# dense Ouro) is skipped, by its parameters and not by a list kept here.
DECODERS = [name for name in available_models()
            if "tiny" in get_model(name)[1].presets]


@pytest.fixture(scope="module", params=DECODERS)
def kept_and_unnamed(request):
    """(the model's expert layers, {"kept": under the model's own policy,
    "unnamed": the same without the dispatch's name})."""
    module, _ = get_model(request.param, preset="tiny", dtype=jnp.bfloat16)
    sizes = module.sizes
    rng = np.random.default_rng(0)
    # SDAR's data stays below its mask id, the vocabulary's last row.
    vocab = sizes.get("mask_token_id", sizes["vocab_rows"])
    batch = {k: rng.integers(0, vocab, (2, sizes["seq_len"])).astype(np.int32)
             for k in ("tokens", "targets")}
    variables = jax.jit(lambda key: module.init(
        {"params": key}, batch["tokens"]))(jax.random.PRNGKey(0))
    layers = sum(path.endswith("['router']")
                 for path, _ in leaves(variables["params"]))
    if not layers:
        pytest.skip(f"{request.param} has no expert layer")
    out = {"kept": gradient_and_primitives(module, variables, batch)}
    with dispatch_unnamed():
        out["unnamed"] = gradient_and_primitives(module, variables, batch)
    return layers, out


@pytest.mark.parametrize("primitive", ["sort", "top_k"])
def test_a_layers_replay_makes_no_second_dispatch(kept_and_unnamed, primitive):
    layers, out = kept_and_unnamed
    assert out["kept"][1][primitive] == layers
    assert out["unnamed"][1][primitive] == 2 * layers


def test_keeping_the_dispatch_changes_no_value(kept_and_unnamed):
    """Loss, counts, the moved balancing bias and every gradient leaf,
    bit for bit."""
    _, out = kept_and_unnamed
    kept, unnamed = (leaves(out[k][0]) for k in ("kept", "unnamed"))
    assert len(kept) == len(unnamed)
    for (name, a), (_, b) in zip(kept, unnamed):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    assert all(np.isfinite(np.asarray(a, np.float32)).all() for _, a in kept)
