"""The decoders' attention kernels (ops/flash_attention.py) and the Gated
DeltaNet's chunk-algebra and convolution kernels (ops/delta_chunks.py,
ops/gdn_conv.py), asked of the chip's compiler without the chip: each
kernel at its cells' published shapes, and the sliding-window and the
hybrid decoder's whole ``Trainer`` steps in their kernel forms. Nothing
executes; a passing compile is not a chip run. Skipped, not failed,
where the topology cannot be described (``conftest.py``'s
``v5e``). A file of its own beside ``test_pallas_compile.py``, so that the
whole-step compiles of the two run on two workers.
"""

import collections
import re

import jax
import jax.numpy as jnp
import pytest

from gtopkssgd_tpu.models import (
    decoder, kanana2, ouro, qwen3_next, trinity_mini)
from gtopkssgd_tpu.ops import delta_chunks, gdn_conv
from gtopkssgd_tpu.ops import flash_attention as flash

TRINITY = trinity_mini.PRESETS["26b_a3b_ep16"]
QWEN = qwen3_next.PRESETS["80b_a3b_ep64"]
KANANA = kanana2.PRESETS["30b_a3b_ep16"]
OURO = ouro.PRESETS["2p6b_l5"]
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


# ------------------------------------------- the Gated DeltaNet's kernels
DELTA_KERNELS = {
    "forward": lambda q, v, row, wide, square, **kw: delta_chunks.forward(
        q, q, v, row, row, **kw),
    "backward": lambda q, v, row, wide, square, **kw: delta_chunks.backward(
        q, q, v, row, row, wide, wide, square, wide, wide, **kw),
}
CONV_KERNELS = {
    "forward": lambda x, taps, d_q, d_v, **kw: gdn_conv.forward(
        x, taps, **kw),
    "backward": lambda x, taps, d_q, d_v, **kw: gdn_conv.backward(
        x, taps, d_q, d_q, d_v, **kw),
}


def delta_kernel(shape, kernel):
    """(a chunk kernel on a sequence of 4,096 tokens, what
    ``lax.map(prepare)`` handed over at a time before PR 43; 16 key and 32
    value heads of 128, chunks of 64; its arguments' shapes)."""
    length, chunk = QWEN["seq_len"], qwen3_next.chunk_of(QWEN["seq_len"])
    keys, heads = QWEN["linear_num_key_heads"], QWEN["linear_num_value_heads"]
    out = lambda width: shape(length // chunk, 1, heads, chunk, width)
    return (lambda *a: DELTA_KERNELS[kernel](*a, key_heads=keys), (
        shape(1, length, keys * QWEN["linear_key_head_dim"]),
        shape(1, length, heads * QWEN["linear_value_head_dim"]),
        shape(1, heads, length // chunk, chunk), out(128), out(chunk)))


def conv_kernel(shape, kernel):
    """(a convolution kernel on a sequence's ``[1, 4096, 8192]`` of
    ``in_proj_qkvz``'s 12,288 bfloat16 columns, read in place, four taps;
    its arguments' shapes)."""
    length = QWEN["seq_len"]
    key_w = QWEN["linear_num_key_heads"] * QWEN["linear_key_head_dim"]
    val_w = QWEN["linear_num_value_heads"] * QWEN["linear_value_head_dim"]
    return (lambda *a: CONV_KERNELS[kernel](
        *a, key_width=key_w, head=QWEN["linear_key_head_dim"]), (
            shape(1, length, 2 * key_w + 2 * val_w, dtype=jnp.bfloat16),
            shape(QWEN["linear_conv_kernel_dim"], 2 * key_w + val_w),
            shape(1, length, key_w), shape(1, length, val_w)))


@pytest.mark.parametrize("kernel", sorted(DELTA_KERNELS))
@pytest.mark.parametrize("stage", ["delta_chunks", "gdn_conv"])
def test_delta_chunks_kernel_compiles_at_the_published_shapes(
        v5e, stage, kernel):
    """Float32 (the convolution's activations bfloat16), the blocks the
    program uses, one custom call each."""
    shape = lambda *s, dtype=jnp.float32: jax.ShapeDtypeStruct(
        s, dtype, sharding=v5e)
    call, shapes = {"delta_chunks": delta_kernel,
                    "gdn_conv": conv_kernel}[stage](shape, kernel)
    text = jax.jit(call).lower(*shapes).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"{stage}_{kernel}" in text


@pytest.fixture(scope="module")
def published_hybrid_step(v5e):
    """The hybrid decoder's step (the ``qwen3_next_ep64.gtopk`` cell's
    flags), the attention, the chunks' algebra and the convolution in their
    kernel forms: one compile (two minutes) serves the tests below."""
    return compiled_step(v5e, ["attention_form", "delta_form", "conv_form"],
                         dnn="qwen3_next", model_preset="80b_a3b_ep64",
                         batch_size=4, lr=0.5)


def test_published_hybrid_step_stays_under_its_memory_line(
        published_hybrid_step):
    """13.75 GB of the v5e's 16.9 by XLA's ``memory_analysis()`` with all
    four sequences through the DeltaNet layers' kernels at once (14.02 a
    sequence at a time and 13.75 two, PR 43); the line is 14.5 (ISSUE 38).
    Before the convolution's kernels the step read 13.62 a sequence at a
    time and 14.93 at four, and the XLA form of the chunks 13.81."""
    assert published_hybrid_step[1] < 14.5e9, published_hybrid_step[1]


def hybrid_calls(text):
    layers = QWEN["num_hidden_layers"]
    return ([line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line],
            layers, layers - layers // QWEN["full_attention_interval"])


def test_published_hybrid_step_runs_each_delta_kernel_once_a_layer_and_pass(
        published_hybrid_step):
    """The engagement counter, static like the mechanism: a DeltaNet layer
    holds one forward and one backward kernel (one call each for the step's
    four sequences; neither ``prepare``'s own checkpoint nor the layer's
    replay runs the forward kernel again), each under ``layer/gdn_scan`` so
    that the device trace counts it there (``gdn_scan_ms``), backward too;
    and the attention layer its three."""
    calls, layers, deltanet = hybrid_calls(published_hybrid_step[0])
    for name in DELTA_KERNELS:
        mine = [line for line in calls
                if re.search(rf"delta_chunks_{name}\b", line)]
        assert len(mine) == deltanet, (name, len(mine))
        assert all(re.search(
            rf'op_name="[^"]*layer/gdn_scan/[^"]*delta_chunks_{name}/'
            rf'pallas_call"', line) for line in mine), name
    for name in KERNELS:
        assert len([line for line in calls if re.search(
            rf"flash_attention_{name}\b", line)]) == layers - deltanet


def test_published_hybrid_step_runs_each_conv_kernel_once_a_layer_and_pass(
        published_hybrid_step):
    """The convolution's engagement counter: a DeltaNet layer holds the
    forward kernel once in the forward pass and once in the backward pass
    (under ``prepare``'s own checkpoint, ``rematted_computation``: the
    chunks' backward kernel takes q, k and v; the layer's replay runs
    none) and the backward kernel once, each under ``layer/gdn_proj`` so
    that the device trace counts it there (``gdn_proj_ms``), backward too.
    And no loop over the sequences is left round them."""
    calls, _, deltanet = hybrid_calls(published_hybrid_step[0])
    scoped = lambda name, line: re.search(
        rf'op_name="[^"]*layer/gdn_proj/jit\({name}\)/gdn_conv_{name}/'
        'pallas_call"', line)
    forward = [line for line in calls if "gdn_conv_forward" in line]
    backward = [line for line in calls if "gdn_conv_backward" in line]
    assert len(forward) == 2 * deltanet and len(backward) == deltanet
    assert all(scoped("forward", line) for line in forward)
    assert all(scoped("backward", line) for line in backward)
    assert sum("rematted_computation" in line
               for line in forward) == deltanet
    assert not any("/mixer/while/" in line for line in forward + backward)


def test_published_hybrid_step_holds_no_float32_convolution_pass(
        published_hybrid_step):
    """What fails if the form silently falls back: the XLA form's padded
    copy ``[.., 4099, 8192]`` and its float32 passes over ``[.., 4096,
    8192]`` (the convolution, the SiLU, the stacked cotangent), none of
    which the kernels write; and what they read and write instead."""
    text = published_hybrid_step[0]
    assert not re.search(r"\bf32\[(?:\d+,)*409[69],8192\]", text)
    assert "bf16[4,4096,12288]" in text                 # qkvz, in place
    assert "f32[4,4096,2048]" in text and "f32[4,4096,4096]" in text


def test_published_hybrid_step_holds_no_triangular_solve(
        published_hybrid_step):
    """The XLA form's solve is the TPU's ``InvertDiagBlocksLowerTriangular``
    custom call under the name ``triangular_solve`` (65.7 ms a step, PERF.md
    section 6, PR 38): the kernels invert by products."""
    assert "InvertDiagBlocks" not in published_hybrid_step[0]
    assert "triangular_solve" not in published_hybrid_step[0]


# ------------------------------------------------------ the looped decoder
@pytest.fixture(scope="module")
def published_looped_step(v5e):
    """The looped decoder's step (the ``ouro_l5.gtopk`` cell's flags), five
    layers walked four times inside one device loop: one compile (half a
    minute) serves the tests below."""
    return compiled_step(v5e, ["attention_form"], dnn="ouro",
                         model_preset="2p6b_l5", batch_size=1, lr=0.05)


def test_published_looped_step_stays_under_its_memory_line(
        published_looped_step):
    """11.87 GB of the v5e's 16.9 by XLA's ``memory_analysis()`` (temp +
    argument + output - alias); the line is 14.5 (ISSUE 41): 20 layer-passes
    keep their inputs and, by name, the attention's outputs, stacked over
    the passes by the loop (the passes unrolled read 10.79)."""
    assert published_looped_step[1] < 12.2e9, published_looped_step[1]


def test_published_looped_step_runs_each_kernel_once_a_layer_in_its_loop(
        published_looped_step):
    """The passes are one ``lax.scan``: the program holds the five layers
    once forward (the loop over the passes) and once backward (its
    transpose), so one forward and the two backward kernels a layer, each
    run four times a step; the remat's replay runs none (the output and
    the rows' log-sum-exp are kept by name). Each call is under its layer,
    ``layer/attn`` and ``part/kernel`` inside the loop's body, backward
    too, so that the device trace counts it where it runs
    (``loop_attn_ms``, ``loop_attn_kernel_ms``)."""
    calls = [line for line in published_looped_step[0].splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    layers = OURO["num_hidden_layers"]
    for name in KERNELS:
        mine = [line for line in calls
                if re.search(rf"flash_attention_{name}\b", line)]
        assert len(mine) == layers, (name, len(mine))
        found = collections.Counter(re.search(
            rf'op_name="[^"]*/while/body/[^"]*(layer_\d)/[^"]*layer/attn/'
            rf'mixer/part/kernel/flash_attention_{name}/pallas_call"',
            line).group(1) for line in mine)
        assert found == {f"layer_{i}": 1 for i in range(layers)}, (
            name, found)
    assert sum("flash_attention_" in line for line in calls) == 3 * layers


def test_published_looped_step_holds_no_array_of_heads_queries_keys(
        published_looped_step):
    """No ``[.., 512, keys]`` score array of the blocked form (``[1, 16, 1,
    512, keys]``); what the kernels read and write instead, in their own
    layout; and no copy of the flat vector as rows of a leaf's width (PR
    31's hazard: N is odd)."""
    text = published_looped_step[0]
    assert not score_arrays(text)
    assert not re.search(r"\b(?:f32|bf16|pred)\[1,16,1,512,\d+\]", text)
    length = OURO["seq_len"]
    assert f"bf16[1,16,1,{length},128]" in text       # q
    assert f"bf16[1,16,{length},128]" in text         # k, v
    assert f"f32[1,16,1,{length}]" in text            # lse
    assert not re.search(r"f32\[\d+,(?:2048|5632|49152)\]\{[^}]*\} "
                         r"(?:reshape|bitcast)\(f32\[458272769\]", text)
