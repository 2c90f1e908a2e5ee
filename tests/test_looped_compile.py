"""The looped decoder's whole ``Trainer`` step in its kernel form, asked of
the chip's compiler without the chip (``conftest.py``'s ``v5e``; the
kernels alone at its shapes are tests/test_flash_compile.py's "looped"
cases). A file of its own, so that this compile (half a minute) has a
worker of its own. Nothing executes; a passing compile is not a chip
run."""

import collections
import re

import pytest

from gtopkssgd_tpu.models import ouro
from test_flash_compile import KERNELS, compiled_step, score_arrays

OURO = ouro.PRESETS["2p6b_l5"]


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
