"""The latent-attention decoder's whole ``Trainer`` step in its kernel form,
asked of the chip's compiler without the chip (``conftest.py``'s ``v5e``;
the kernels alone at its shapes are tests/test_flash_compile.py's "latent"
cases). A file of its own, so that this compile (a minute and a half) has a
worker of its own (one published step a file). Nothing
executes; a passing compile is not a chip run."""

import collections
import re

import pytest

from gtopkssgd_tpu.models import kanana2
from test_flash_compile import KERNELS, compiled_step, score_arrays

KANANA = kanana2.PRESETS["30b_a3b_ep16"]


@pytest.fixture(scope="module")
def published_latent_step(v5e):
    """The step of the ``kanana2_ep16.gtopk`` cell's flags: one compile
    serves the tests below."""
    return compiled_step(v5e, ["attention_form"], dnn="kanana2",
                         model_preset="30b_a3b_ep16", batch_size=2, lr=0.1)


def test_published_latent_step_stays_under_its_memory_line(
        published_latent_step):
    """11.25 GB of the v5e's 16.9 by XLA's ``memory_analysis()`` (temp +
    argument + output - alias); the line is 14.5 (ISSUE 39)."""
    assert published_latent_step[1] < 11.5e9, published_latent_step[1]


def test_published_latent_step_runs_each_attention_kernel_once_a_layer(
        published_latent_step):
    """A layer holds one forward and the two backward kernels (the remat's
    replay runs none: the output and the rows' log-sum-exp are kept by
    name), each under ``layer/attn_latent`` and ``part/kernel``, backward
    too, so that the device trace counts it where it runs (``mla_attn_ms``,
    ``mla_kernel_ms``)."""
    calls = [line for line in published_latent_step[0].splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    layers = KANANA["num_hidden_layers"]
    for name in KERNELS:
        mine = [line for line in calls
                if re.search(rf"flash_attention_{name}\b", line)]
        assert len(mine) == layers, (name, len(mine))
        found = collections.Counter(re.search(
            rf'op_name="[^"]*/(layer_\d)/layer/attn_latent/mixer/part/kernel/'
            rf'flash_attention_{name}/pallas_call"', line).group(1)
            for line in mine)
        assert found == {f"layer_{i}": 1 for i in range(layers)}, (name, found)
    assert sum("flash_attention_" in line for line in calls) == 3 * layers


def test_published_latent_step_holds_no_array_of_heads_queries_keys(
        published_latent_step):
    """No ``[.., 512, keys]`` score array of the blocked form (``[2, 32, 1,
    512, keys]``, keys 512 to 8,192); what the kernels read and write
    instead, in their own layout: q and k 192 wide, v and o 128."""
    text = published_latent_step[0]
    assert not score_arrays(text)
    length = KANANA["seq_len"]
    assert f"bf16[2,32,1,{length},192]" in text       # q
    assert f"bf16[2,32,{length},192]" in text         # k
    assert f"bf16[2,32,{length},128]" in text         # v
    assert f"f32[2,32,1,{length},128]" in text        # o
    assert f"f32[2,32,1,{length}]" in text            # lse
