"""The decoders' attention kernels (ops/flash_attention.py) asked of the
chip's compiler without the chip: each kernel at its cells' published
shapes, and the sliding-window decoder's whole ``Trainer`` step in its
kernel form. Nothing executes; a passing compile is not a chip run.
Skipped, not failed, where the topology cannot be described
(``conftest.py``'s ``v5e``). One published step a file, so that under
``--dist loadfile`` each whole-step compile has a worker of its own: the
hybrid decoder's is ``test_hybrid_compile.py``, the looped decoder's
``test_looped_compile.py``, the latent-attention decoder's
``test_mla_compile.py`` and the sparse-attention decoder's
``test_pallas_compile.py``; they import ``compiled_step``, ``score_arrays``
and ``KERNELS`` from here.
"""

import collections
import re

import jax
import jax.numpy as jnp
import pytest

from gtopkssgd_tpu.models import (
    decoder, kanana2, ouro, qwen3_next, sdar, trinity_mini)
from gtopkssgd_tpu.ops import flash_attention as flash

TRINITY = trinity_mini.PRESETS["26b_a3b_ep16"]
QWEN = qwen3_next.PRESETS["80b_a3b_ep64"]
KANANA = kanana2.PRESETS["30b_a3b_ep16"]
OURO = ouro.PRESETS["2p6b_l5"]
SDAR = sdar.PRESETS["30b_a3b_ep8"]
# (sequences a step in the cell, query heads, key-value heads, key width,
# value width, tokens, the window)
LAYERS = {
    "sliding": (1, 32, 4, 128, 128, 16384, TRINITY["sliding_window"]),
    "full": (1, 32, 4, 128, 128, 16384, None),
    "hybrid": (4, 16, 2, 256, 256, 4096, None),
    "latent": (2, 32, 32, 192, 128, 8192, None),
    "looped": (1, 16, 16, 128, 128, 4096, None)}
KERNELS = {
    "forward": lambda q, k, v, row, d_out, **kw: flash.forward(q, k, v, **kw),
    "backward_q": lambda q, k, v, row, d_out, **kw: flash.backward_q(
        q, k, v, row, row, d_out, **kw),
    "backward_kv": lambda q, k, v, row, d_out, **kw: flash.backward_kv(
        q, k, v, row, row, d_out, **kw),
}


def test_the_layers_shapes_are_the_published_presets():
    for heads, groups, key, value, length, sizes in (
            LAYERS["full"][1:6] + (TRINITY,), LAYERS["hybrid"][1:6] + (QWEN,),
            LAYERS["looped"][1:6] + (OURO,)):
        assert (heads, groups, key, value, length) == (
            sizes["num_attention_heads"], sizes["num_key_value_heads"],
            sizes["head_dim"], sizes["head_dim"], sizes["seq_len"])
    assert LAYERS["latent"][1:6] == (
        KANANA["num_attention_heads"], KANANA["num_attention_heads"],
        KANANA["qk_nope_head_dim"] + KANANA["qk_rope_head_dim"],
        KANANA["v_head_dim"], KANANA["seq_len"])


@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_flash_attention_kernel_compiles_at_the_published_shapes(
        v5e, layer, kernel):
    """16,384 tokens x 32 / 4 heads of 128 under the window of 2,048 and
    under none, 4 x 4,096 tokens x 16 / 2 heads of 256, 2 x 8,192
    tokens x 32 / 32 heads with keys of 192 beside values of 128 (1.5 rows
    of 128 lanes: a block's last axis is its array's), and 4,096 tokens x
    16 / 16 heads of 128 (the looped decoder's): bfloat16, the tiles the
    program uses, one custom call each."""
    batch, heads, groups, key, value, length, window = LAYERS[layer]
    rows = (batch, groups, heads // groups, length)
    shape = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, sharding=v5e)
    text = jax.jit(lambda *a: KERNELS[kernel](*a, window=window)).lower(
        shape(rows + (key,), jnp.bfloat16),
        shape((batch, groups, length, key), jnp.bfloat16),
        shape((batch, groups, length, value), jnp.bfloat16),
        shape(rows, jnp.float32),
        shape(rows + (value,), jnp.bfloat16)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"flash_attention_{kernel}" in text


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_flash_attention_kernel_compiles_under_the_block_diffusion_rule(
        v5e, kernel):
    """2 x 8,192 rows x 32 / 4 heads of 128 in blocks of 4 tokens (the
    block-diffusion decoder's): the same three kernels told
    ``block_length``, a grid of 32 query tiles x 17 steps forward and 32 key
    tiles x 32 steps in ``backward_kv``, one custom call each."""
    heads, groups, dim = (SDAR["num_attention_heads"],
                          SDAR["num_key_value_heads"], SDAR["head_dim"])
    length = 2 * SDAR["seq_len"]
    rows = (1, groups, heads // groups, length)
    shape = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, sharding=v5e)
    text = jax.jit(lambda *a: KERNELS[kernel](
        *a, block_length=SDAR["block_length"])).lower(
        shape(rows + (dim,), jnp.bfloat16),
        shape((1, groups, length, dim), jnp.bfloat16),
        shape((1, groups, length, dim), jnp.bfloat16),
        shape(rows, jnp.float32),
        shape(rows + (dim,), jnp.bfloat16)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"flash_attention_{kernel}" in text


def score_arrays(text):
    """The arrays of a compiled module that hold a number for every (query
    head, query of a block of 512, key): what the blocked form writes to
    HBM (``[4, 8, 512, keys]`` in float32, ``dtype`` and pred, ``keys`` 512
    to 16,384 in the full layer and 2,560 past the window) and the kernels
    keep in VMEM."""
    found = collections.Counter()
    for dtype, dims in re.findall(r"\b(f32|bf16|pred)\[([0-9,]+)\]", text):
        big = [int(d) for d in dims.split(",") if d and int(d) > 1]
        if len(big) >= 3 and big[-2] == 512 and big[-1] % 512 == 0 \
                and big[-3] in (8, 32):
            found[dtype, tuple(big)] += 1
    return found


def test_score_arrays_finds_the_blocked_forms_and_no_other():
    blocked = ("%f = bf16[1,4,8,512,2560]{4,3,2,1,0} fusion(f32[1,4,8,512,2560] "
               "%a), %m = pred[4,8,512,16384] compare(...), f32[4,8,512,512]")
    assert set(score_arrays(blocked)) == {
        ("bf16", (4, 8, 512, 2560)), ("f32", (4, 8, 512, 2560)),
        ("pred", (4, 8, 512, 16384)), ("f32", (4, 8, 512, 512))}
    others = ("bf16[1,4,8,16384,128] %q, f32[1,4,8,16384] %lse, "
              "f32[8,2048,1024] %experts, f32[4096,25024] %logits, "
              "f32[16384,128] %router, f32[8,512,128] %tile, "
              "f32[1,16384,32,128] %out, f32[4096,2048] %slots")
    assert not score_arrays(others)


def compiled_step(v5e, forms, **flags):
    """(compiled text, bytes) of a decoder's whole Trainer step (a cell's
    flags) for the described v5e, in its kernel forms: the backend here is
    the CPU, so the test, not an option of the program, answers ``on_tpu``."""
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    abstract = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=v5e), tree)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(decoder, "on_tpu", lambda: True)
        jax.clear_caches()
        with Trainer(TrainConfig(
                dataset="tokens", dtype="bfloat16", seed=42, nworkers=1,
                compression="gtopk", density=0.001, momentum=0.9,
                weight_decay=0.0, clip_grad_norm=1.0, prefetch=0,
                **flags)) as trainer:
            assert all(trainer._manifest[form] == "kernel" for form in forms)
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


@pytest.fixture(scope="module")
def published_step(v5e):
    """The sliding-window decoder's step (the ``trinity_mini_ep16.gtopk``
    cell's flags): one compile (two minutes) serves the tests below."""
    return compiled_step(v5e, ["attention_form"], dnn="trinity_mini",
                         model_preset="26b_a3b_ep16", batch_size=1, lr=0.1)


def test_published_step_stays_under_its_memory_line(published_step):
    """12.3 GB of the v5e's 16.9 by XLA's ``memory_analysis()`` (temp +
    argument + output - alias; equal to the chip's to the byte, PERF.md
    section 4): the blocked form's step read 12.19, and the kernel form
    may only take less (the score arrays go, 2 MB a layer come)."""
    assert published_step[1] < 12.3e9, published_step[1]


def test_published_step_runs_each_attention_kernel_once_a_layer(
        published_step):
    """The engagement counter, static like the mechanism: a layer holds one
    forward and the two backward kernels (the remat's replay runs none: the
    output and the rows' log-sum-exp are kept by name), and each call
    carries its layer kind's scope, backward too, so that the device trace
    counts it where it runs (``swa_attn_ms``, ``full_attn_ms``)."""
    calls = [line for line in published_step[0].splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    kinds = TRINITY["layer_kinds"].split(",")
    for name in KERNELS:
        mine = [line for line in calls
                if re.search(rf"flash_attention_{name}\b", line)]
        assert len(mine) == len(kinds), (name, len(mine))
        scopes = collections.Counter(
            re.search(r'op_name="[^"]*layer/(attn_\w+)', line).group(1)
            for line in mine)
        assert scopes == {"attn_window": kinds.count("sliding"),
                          "attn_full": kinds.count("full")}, (name, scopes)
        # ... and, within the kind, the part the device trace prices the
        # kernels alone by (``attn_kernel_ms``).
        assert all(re.search(
            rf'op_name="[^"]*layer/attn_\w+/mixer/part/kernel/'
            rf'flash_attention_{name}/pallas_call"', line) for line in mine)


def test_published_step_holds_no_array_of_heads_queries_keys(published_step):
    assert not score_arrays(published_step[0])
    # What the kernels read and write instead, in their own layout.
    length = TRINITY["seq_len"]
    assert f"bf16[1,4,8,{length},128]" in published_step[0]
    assert f"f32[1,4,8,{length}]" in published_step[0]


# ------------------------------------------ the block-diffusion decoder's step
@pytest.fixture(scope="module")
def published_diffusion_step(v5e):
    """The block-diffusion decoder's step (the ``sdar_ep8.gtopk`` cell's
    flags) in its kernel form: one compile (a minute and a quarter) serves
    the tests below, in this file's process beside Trinity's."""
    return compiled_step(v5e, ["attention_form"], dnn="sdar",
                         model_preset="30b_a3b_ep8", batch_size=1, lr=0.1)


def test_published_diffusion_step_stays_under_its_memory_line(
        published_diffusion_step):
    """12.34 GB of the v5e's 16.9 by ``memory_analysis()``; ISSUE 45's line
    is 14.5."""
    assert published_diffusion_step[1] < 12.4e9, published_diffusion_step[1]


def test_published_diffusion_step_runs_each_kernel_once_a_layer(
        published_diffusion_step):
    """A layer holds one forward and the two backward kernels (the remat's
    replay runs none), each under ``layer/attn/.../part/kernel``, backward
    too."""
    calls = [line for line in published_diffusion_step[0].splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in KERNELS:
        mine = [line for line in calls
                if re.search(rf"flash_attention_{name}\b", line)]
        assert len(mine) == SDAR["num_hidden_layers"], (name, len(mine))
        assert all(re.search(
            rf'op_name="[^"]*layer/attn/mixer/part/kernel/'
            rf'flash_attention_{name}/pallas_call"', line) for line in mine)
    # The other custom calls are the expert layer's grouped products.
    assert sum("flash_attention_" in line for line in calls) \
        == 3 * SDAR["num_hidden_layers"]


def test_published_diffusion_step_holds_no_score_or_mask_array(
        published_diffusion_step):
    """No ``[.., 512, keys]`` score or mask array of the blocked form and no
    ``[.., 16384, 16384]`` one of a mask made whole; what the kernels read
    and write instead, in their own layout, over all 2 x 8,192 rows."""
    text = published_diffusion_step[0]
    assert not score_arrays(text)
    rows = 2 * SDAR["seq_len"]
    assert not re.search(rf"\[[0-9,]*{rows},{rows}\]", text)
    assert f"bf16[1,4,8,{rows},128]" in text
    assert f"f32[1,4,8,{rows}]" in text
