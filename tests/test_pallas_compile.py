"""The selection kernels, asked of the chip's compiler without the chip.

Interpret mode accepts kernels the TPU lowering refuses (a candidate block
whose sublane dimension is below 8) or cannot fit (VMEM). The TPU compiler
is installed here and compiles for a *described* v5e topology, so these
AOT compiles guard the main path's kernels at real widths on every PR at no
chip time. Nothing executes; a passing compile is not a chip run. Skipped,
not failed, where the topology cannot be described (the ``v5e`` fixture is
``conftest.py``'s, shared with ``test_flash_compile.py``).
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

from gtopkssgd_tpu.ops import pallas_topk as pk
from gtopkssgd_tpu.ops.topk import (
    TWOSTAGE_OVERSAMPLE,
    _twostage_pallas_groups,
    k_for_density,
    twostage_topk_abs,
)

RESNET20_N = 272_474
RESNET50_N = 25_557_032


def _compile(fn, *shapes, device):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=device)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


ENTRY_POINTS = {
    "multi_threshold_count":
        (lambda m, t: pk.multi_threshold_count(m, t), "nt"),
    "fused_multi_threshold_count+residual":
        (lambda g, t, r: pk.fused_multi_threshold_count(g, t, r), "ntn"),
    "fused_stage1_candidates":
        (lambda g: pk.fused_stage1_candidates(g, groups=64), "n"),
    "fused_stage1_candidates+residual":
        (lambda g, r: pk.fused_stage1_candidates(g, None, r, groups=64),
         "nn"),
    "fused_stage1_candidates+residual+counts":
        (lambda g, t, r: pk.fused_stage1_candidates(g, t, r, groups=64),
         "ntn"),
}


@pytest.mark.parametrize("n", [RESNET20_N, RESNET50_N])
@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
def test_entry_point_compiles_for_v5e(v5e, entry, n):
    fn, signature = ENTRY_POINTS[entry]
    shapes = [(n,) if c == "n" else (pk.NUM_THRESHOLDS,)
              for c in signature]
    _compile(fn, *shapes, device=v5e)


@pytest.mark.parametrize("density", [1e-2, 1e-3, 1e-4])
def test_twostage_groups_compile_at_resnet50(v5e, density):
    """The stage-1 pass --topk-method twostage runs at each density of
    the sweep: ops.topk picks the group count, the chip's compiler must
    take it (it refused groups=4, the unclamped answer at rho=1e-4).
    Stage 2 is a plain lax.top_k whose TPU compile takes half a minute;
    it is not what was ever refused, so it stays out of this file."""
    n = RESNET50_N
    groups = _twostage_pallas_groups(
        n, k_for_density(n, density), TWOSTAGE_OVERSAMPLE)
    assert pk.MIN_GROUPS <= groups <= pk.MAX_GROUPS
    _compile(lambda g, r: pk.fused_stage1_candidates(
        g, None, r, groups=groups), (n,), (n,), device=v5e)


@pytest.mark.parametrize("groups", [pk.MIN_GROUPS, pk.MAX_GROUPS])
def test_group_clamp_ends_compile(v5e, groups):
    _compile(lambda g, t, r: pk.fused_stage1_candidates(
        g, t, r, groups=groups),
        (RESNET50_N,), (pk.NUM_THRESHOLDS,), (RESNET50_N,), device=v5e)


@pytest.mark.parametrize("groups", [pk.MIN_GROUPS // 2, 2 * pk.MAX_GROUPS])
def test_group_count_outside_clamp_is_refused_before_lowering(groups):
    x = jnp.zeros((pk._BLOCK,), jnp.float32)
    with pytest.raises(ValueError, match="does not compile"):
        pk.fused_stage1_candidates(x, groups=groups, interpret=False)


@pytest.mark.parametrize("n,k,want", [
    (RESNET50_N, k_for_density(RESNET50_N, 1e-4), pk.MIN_GROUPS),
    (RESNET50_N, k_for_density(RESNET50_N, 1e-3), 64),
    (10, 1, pk.MAX_GROUPS),           # a tiny leaf: rpg target below 2
    (300_000, 280_000, None),         # k above the candidates on offer
])
def test_group_choice_stays_inside_what_compiles(n, k, want):
    assert _twostage_pallas_groups(n, k, TWOSTAGE_OVERSAMPLE) == want


def test_twostage_takes_xla_stage1_when_no_group_count_fits(rng):
    """k above half the padded length: the kernel cannot offer k
    candidates at any compiling group count, so selection falls to the
    XLA stage 1 and stays correct."""
    import numpy as np

    n, k = 300_000, 280_000
    x = rng.standard_normal(n).astype(np.float32)
    vals, idx = twostage_topk_abs(jnp.asarray(x), k, use_pallas=True,
                                  interpret=True)
    idx = np.asarray(idx)
    real = idx[idx < n]
    assert len(set(real.tolist())) == len(real) >= 0.95 * k
    np.testing.assert_array_equal(np.asarray(vals)[idx < n], x[real])


# ---------------------------------------------------------------------------
# The sparse-attention decoder's attention kernels (ops/dsa_attention.py) at
# the published shapes, and the published preset's whole step with them.
from gtopkssgd_tpu.models import keye_vl2  # noqa: E402
from gtopkssgd_tpu.ops import dsa_attention as dsa  # noqa: E402
from gtopkssgd_tpu.ops import dsa_index  # noqa: E402

KEYE = keye_vl2.PRESETS["30b_a3b_ep16"]
DSA_LENGTH, DSA_GROUPS, DSA_DIM = (
    KEYE["seq_len"], KEYE["num_key_value_heads"], KEYE["head_dim"])
DSA_HEADS = KEYE["num_attention_heads"] // DSA_GROUPS
DSA_BUCKET = keye_vl2.buckets(DSA_LENGTH, KEYE["q_chunk_size"])[-1]
INDEX_HEADS, INDEX_DIM = KEYE["indexer_num_heads"], KEYE["indexer_head_dim"]


def _dsa_arguments(device):
    """Abstract q, k, keep and a row array of one layer, bfloat16."""
    shape = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, sharding=device)
    rows = (1, DSA_GROUPS, DSA_HEADS, DSA_LENGTH)
    return (shape(rows + (DSA_DIM,), jnp.bfloat16),
            shape((1, DSA_GROUPS, DSA_LENGTH, DSA_DIM), jnp.bfloat16),
            shape((1, DSA_LENGTH, DSA_LENGTH), jnp.int8),
            shape(rows, jnp.float32))


DSA_KERNELS = {
    "forward": lambda q, k, keep, row: dsa.forward(
        q, k, k, keep, row, dtype=jnp.bfloat16),
    "probabilities": lambda q, k, keep, row: dsa.probabilities(
        q, k, keep, row, row, span=DSA_BUCKET[:2], dtype=jnp.bfloat16),
    "backward_q": lambda q, k, keep, row: dsa.backward_q(
        q, k, k, keep, row, row, row, q, dtype=jnp.bfloat16),
    "backward_kv": lambda q, k, keep, row: dsa.backward_kv(
        q, k, k, keep, row, row, row, q, q, dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("kernel", sorted(DSA_KERNELS))
def test_dsa_attention_kernel_compiles_at_the_published_shapes(v5e, kernel):
    """16,384 tokens, 8 query heads a key-value head, heads of 128,
    bfloat16, the tiles the program uses: the layer's forward and backward
    kernels over the whole sequence, the probabilities over its last
    bucket (2,048 rows against every key)."""
    text = jax.jit(DSA_KERNELS[kernel]).lower(*_dsa_arguments(v5e)).compile(
        ).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"dsa_attention_{kernel}" in text


def _index_arguments(device):
    """Abstract qi, ki, w (a column a head), w (a row a head) and the last
    bucket's [rows, keys] float32 of one layer, bfloat16."""
    shape = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, sharding=device)
    return (shape((1, INDEX_HEADS, DSA_LENGTH, INDEX_DIM), jnp.bfloat16),
            shape((1, DSA_LENGTH, INDEX_DIM), jnp.bfloat16),
            shape((1, DSA_LENGTH, INDEX_HEADS), jnp.float32),
            shape((1, INDEX_HEADS, DSA_LENGTH), jnp.float32),
            shape((1, DSA_BUCKET[1], DSA_BUCKET[2]), jnp.float32))


INDEX_KERNELS = {
    "scores": lambda qi, ki, w, w_rows, d: dsa_index.scores(
        qi, ki, w, span=DSA_BUCKET[:2]),
    "backward_q": lambda qi, ki, w, w_rows, d: dsa_index.backward_q(
        qi, ki, w, d, span=DSA_BUCKET[:2], dtype=jnp.bfloat16),
    "backward_k": lambda qi, ki, w, w_rows, d: dsa_index.backward_k(
        qi, ki, w_rows, d, span=DSA_BUCKET[:2], dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("kernel", sorted(INDEX_KERNELS))
def test_dsa_index_kernel_compiles_at_the_published_shapes(v5e, kernel):
    """16,384 tokens, 16 indexer heads of 64, bfloat16, the tiles the
    program uses: the last bucket's 2,048 rows against every key."""
    text = jax.jit(INDEX_KERNELS[kernel]).lower(*_index_arguments(v5e)
                                                ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"dsa_index_{kernel}" in text


def rqk_arrays(text, heads=(DSA_HEADS, DSA_HEADS * DSA_GROUPS, INDEX_HEADS)):
    """The arrays of a compiled module that hold a number for every (query
    head, query of a block, key of a bucket's extent): what the masked form
    writes to HBM (``[8, 512, keys]`` in float32, ``dtype`` and pred; the
    indexer's ``[16, 512, keys]`` products) and the kernels keep in VMEM."""
    import collections
    import re

    extents = {extent for _, _, extent in keye_vl2.buckets(
        DSA_LENGTH, KEYE["q_chunk_size"])}
    found = collections.Counter()
    for dtype, dims in re.findall(r"\b(f32|bf16|s32|s8|u8|pred)\[([0-9,]+)\]",
                                  text):
        big = [int(d) for d in dims.split(",") if d and int(d) > 1]
        if len(big) >= 3 and big[-1] in extents \
                and big[-2] == KEYE["q_chunk_size"] and big[-3] in heads:
            found[dtype, tuple(big)] += 1
    return found


def test_rqk_arrays_finds_the_masked_forms_and_no_other():
    masked = ("%f = bf16[8,512,2048]{2,1,0} fusion(f32[1,8,512,2048]{3,2,1,0} "
              "%a), %m = pred[1,32,512,16384] compare(...)"
              ", f32[1,16,512,2048] %index_dots")
    assert set(rqk_arrays(masked)) == {
        ("bf16", (8, 512, 2048)), ("f32", (8, 512, 2048)),
        ("pred", (32, 512, 16384)), ("f32", (16, 512, 2048))}
    others = ("s8[1,16384,16384] %keep, "
              "f32[1,2048,16384] %p, bf16[1,4,8,16384,128] %q, "
              "f32[1,16,16384,64] %d_qi, bf16[1,16,16384,64] %qi, "
              "f32[4096,2048] %slots, f32[8,512,128] %tile")
    assert not rqk_arrays(others)


@pytest.fixture(scope="module")
def published_step(v5e):
    """(compiled text, bytes) of the published preset's whole Trainer step
    (the ``keye_vl2_ep16.gtopk`` cell's flags) for the described v5e, with
    the attention in its kernel form: the backend here is the CPU, so the
    test, not an option of the program, answers ``on_tpu``. One compile
    (three minutes) serves the tests below."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    abstract = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=v5e), tree)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(keye_vl2, "on_tpu", lambda: True)
        jax.clear_caches()
        with Trainer(TrainConfig(
                dnn="keye_vl2", dataset="tokens", dtype="bfloat16", seed=42,
                model_preset="30b_a3b_ep16", batch_size=1, nworkers=1,
                compression="gtopk", density=0.001, lr=0.1, momentum=0.9,
                weight_decay=0.0, clip_grad_norm=1.0, prefetch=0)) as trainer:
            assert trainer._manifest["dsa_attention_form"] == "kernel"
            assert trainer._manifest["dsa_index_form"] == "kernel"
            batch = trainer._device_batch(
                trainer._shard_batches(trainer._iters)[0])
            compiled = trainer._train_step.lower(
                abstract(trainer.state), abstract(trainer.carry),
                abstract(batch)).compile()
    jax.clear_caches()
    memory = compiled.memory_analysis()
    return compiled.as_text(), (
        memory.temp_size_in_bytes + memory.argument_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes)


def test_published_step_stays_under_its_memory_line(published_step):
    """14.5 GB of the v5e's 16.9 (``bytes_limit`` 16,909,336,064), by XLA's
    ``memory_analysis()`` (temp + argument + output - alias; equal to the
    chip's to the byte, PERF.md section 4)."""
    assert published_step[1] < 14.5e9, published_step[1]


def test_published_step_runs_each_attention_kernel_once_a_layer(
        published_step):
    """The engagement counter, static like the mechanism: a layer holds one
    forward kernel, a probabilities kernel a bucket and the two backward
    kernels (the remat's replay runs none: ``o``, ``total``, p and the mask
    are kept by name)."""
    import re

    calls = [line for line in published_step[0].splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    layers = KEYE["num_hidden_layers"]
    spans = len(keye_vl2.buckets(DSA_LENGTH, KEYE["q_chunk_size"]))
    count = lambda name: sum(
        bool(re.search(rf"dsa_attention_{name}\b", line)) for line in calls)
    assert count("forward") == count("backward_q") == count("backward_kv") \
        == layers
    assert count("probabilities") == spans * layers
    # Each carries its layer kind's scope and, within it, the part the
    # device trace prices the kernels alone by (``attn_kernel_ms``).
    mine = [line for line in calls if "dsa_attention_" in line]
    assert len(mine) == (3 + spans) * layers and all(re.search(
        r'op_name="[^"]*layer/attn/[^"]*part/kernel/dsa_attention_\w+/'
        r'pallas_call"', line) for line in mine)


def test_published_step_runs_the_index_kernels_once_a_bucket(published_step):
    """A layer holds, a bucket, one ``scores`` kernel in its forward pass and
    one in the backward rule (the replay runs none: the mask and p are kept
    by name), one ``backward_q`` and one ``backward_k``; each under the
    indexer's kind and under no part of the attention's, so that
    ``dsa_index_ms`` reads them and ``attn_kernel_ms`` does not."""
    import re

    calls = [line for line in published_step[0].splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "dsa_index_" in line]
    spans = len(keye_vl2.buckets(DSA_LENGTH, KEYE["q_chunk_size"])) \
        * KEYE["num_hidden_layers"]
    count = lambda name: sum(
        bool(re.search(rf"dsa_index_{name}\b", line)) for line in calls)
    assert count("scores") == 2 * spans
    assert count("backward_q") == count("backward_k") == spans
    assert len(calls) == 4 * spans
    for line in calls:
        path = re.search(r'op_name="([^"]*)"', line).group(1)
        assert re.search(r"layer/dsa_index/dsa_index_\w+/pallas_call", path) \
            and "part/" not in path.split("layer/dsa_index/")[-1], path


def test_published_step_holds_no_array_of_heads_queries_keys(published_step):
    assert not rqk_arrays(published_step[0])
    # What the kernels read instead: the layer's masks, a byte a pair.
    assert f"s8[1,{DSA_LENGTH},{DSA_LENGTH}]" in published_step[0]
