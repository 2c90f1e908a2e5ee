"""The hybrid decoder's whole ``Trainer`` step in its kernel forms (the
attention, the Gated DeltaNet's chunk algebra, its state pass and its
convolution: ops/flash_attention.py, ops/delta_chunks.py,
ops/delta_scan.py, ops/gdn_conv.py), and the
DeltaNet's kernels alone at the published shapes, asked of the chip's
compiler without the chip (``conftest.py``'s ``v5e``; the attention
kernels alone at its shapes are tests/test_flash_compile.py's "hybrid"
cases). A file of its own, so that this compile (a minute and a half) has
a worker of its own. Nothing executes; a passing compile is not a chip
run."""

import re

import jax
import jax.numpy as jnp
import pytest

from gtopkssgd_tpu.models import qwen3_next
from gtopkssgd_tpu.ops import delta_chunks, delta_scan, gdn_conv
from test_flash_compile import KERNELS, compiled_step

QWEN = qwen3_next.PRESETS["80b_a3b_ep64"]
DELTA_KERNELS = {
    "forward": lambda q, v, row, wide, square, **kw: delta_chunks.forward(
        q, q, v, row, row, **kw),
    "backward": lambda q, v, row, wide, square, **kw: delta_chunks.backward(
        q, q, v, row, row, wide, wide, square, wide, wide, **kw),
}
SCAN_KERNELS = {
    "forward": lambda wide, square, row, state, tokens, **kw:
        delta_scan.forward(wide, wide, square, wide, wide, row, **kw),
    "forward_states": lambda wide, square, row, state, tokens, **kw:
        delta_scan.forward(wide, wide, square, wide, wide, row, states=True,
                           **kw),
    "backward": lambda wide, square, row, state, tokens, **kw:
        delta_scan.backward(wide, wide, square, wide, wide, row, state,
                            tokens, **kw),
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


def scan_kernel(shape, kernel):
    """(a state-pass kernel on the cell's step, four sequences of 4,096
    tokens in one call: 64 chunks of 64, 32 value heads of 128; its
    arguments' shapes)."""
    length, chunk = QWEN["seq_len"], qwen3_next.chunk_of(QWEN["seq_len"])
    heads, d_k, d_v = (QWEN["linear_num_value_heads"],
                       QWEN["linear_key_head_dim"],
                       QWEN["linear_value_head_dim"])
    chunks = lambda *s: shape(length // chunk, 4, heads, *s)
    return SCAN_KERNELS[kernel], (
        chunks(chunk, d_k), chunks(chunk, chunk),
        shape(4, heads, length // chunk), chunks(d_k, d_v),
        shape(4, length, heads, d_v))


@pytest.mark.parametrize("kernel", sorted(SCAN_KERNELS))
def test_delta_scan_kernel_compiles_at_the_published_shapes(v5e, kernel):
    """Float32, the blocks the program uses, one custom call each; the
    forward rule's kernel is the primal's with the states written."""
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=v5e)
    call, shapes = scan_kernel(shape, kernel)
    compiled = jax.jit(call).lower(*shapes).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"delta_scan_{kernel.split('_')[0]}" in text
    assert len(jax.tree.leaves(compiled.out_info)) == {
        "forward": 1, "forward_states": 2, "backward": 6}[kernel]


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
    flags), the attention, the chunks' algebra, the state's pass and the
    convolution in their kernel forms: one compile (two minutes) serves the
    tests below."""
    return compiled_step(
        v5e, ["attention_form", "delta_form", "scan_form", "conv_form"],
        dnn="qwen3_next", model_preset="80b_a3b_ep64", batch_size=4, lr=0.5)


def test_published_hybrid_step_stays_under_its_memory_line(
        published_hybrid_step):
    """13.26 GB of the v5e's 16.9 by XLA's ``memory_analysis()`` with the
    state's pass in kernels (PR 47: a layer's backward holds the state at
    every chunk's start, 0.54 GB, where XLA's scan held that and the
    stacked ``delta``, and the transposed copies of o are gone); 13.75
    with all four sequences through the DeltaNet layers' other kernels at
    once (14.02 a sequence at a time and 13.75 two, PR 43); the line is
    14.5 (ISSUE 38).
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


def test_published_hybrid_step_runs_the_state_pass_in_kernels_alone(
        published_hybrid_step):
    """The state pass's engagement counter: a DeltaNet layer holds the
    forward kernel twice (the forward pass runs the primal, which writes no
    states; the layer's replay runs the forward rule, which writes the
    state at every chunk's start, [64, 4, 32, 128, 128] float32) and the
    backward kernel once, each under ``layer/gdn_scan`` so that the device
    trace counts it there (``gdn_scan_ms``); and no loop of XLA's is left
    in that stage: ``scan_chunks``' ``lax.scan`` was three ``while``s a
    layer (forward, replayed, backward)."""
    text = published_hybrid_step[0]
    calls, _, deltanet = hybrid_calls(text)
    forward = [line for line in calls
               if re.search(r"delta_scan_forward\b", line)]
    backward = [line for line in calls
                if re.search(r"delta_scan_backward\b", line)]
    assert len(forward) == 2 * deltanet and len(backward) == deltanet
    assert all(re.search(
        r'op_name="[^"]*layer/gdn_scan/[^"]*delta_scan_(for|back)ward/'
        r'pallas_call"', line) for line in forward + backward)
    assert sum("f32[64,4,32,128,128]" in line.split(" custom-call(")[0]
               for line in forward) == deltanet
    assert not [line for line in text.splitlines()
                if re.search(r"\bwhile\(", line) and "layer/gdn_scan" in line]


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
