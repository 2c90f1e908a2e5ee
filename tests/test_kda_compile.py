"""The KDA : MLA hybrid's whole ``Trainer`` step (``kimi_linear``: three Kimi
Delta Attention layers and one latent-attention layer without position
encoding) asked of the chip's compiler without the chip (``conftest.py``'s
``v5e``): the attention and the convolution in their kernel forms, the
per-channel chunk algebra and the state's pass in XLA's, the only form they
have. A file of its own, so that this compile (two and a half minutes) has
a worker of its own (one published step a file). Nothing executes; a
passing compile is not a chip run."""

import collections
import re

import pytest

from gtopkssgd_tpu.models import kimi_linear
from test_flash_compile import KERNELS, compiled_step, score_arrays

KIMI = kimi_linear.PRESETS["48b_a3b_ep32"]


@pytest.fixture(scope="module")
def published_kda_step(v5e):
    """The step of the ``kimi_linear_ep32.gtopk`` cell's flags: the
    attention and the convolution in their kernel forms, the per-channel
    chunk algebra and the state's pass in XLA's (the only form they have)."""
    return compiled_step(v5e, ["attention_form", "conv_form"],
                         dnn="kimi_linear", model_preset="48b_a3b_ep32",
                         batch_size=1, lr=0.05)


def test_published_kda_step_stays_under_its_memory_line(published_kda_step):
    """13.93 GB of the v5e's 16.9 by XLA's ``memory_analysis()`` with all
    four layers' named outputs kept (7.99 GB of it the state's 16 B a
    parameter); the line is 14.5 (ISSUE 48)."""
    assert published_kda_step[1] < 14.2e9, published_kda_step[1]


def test_published_kda_step_runs_the_kernels_it_names(published_kda_step):
    """A KDA layer holds the convolution's forward kernel twice (forward
    pass and replay: q, k and v are not kept) and its backward once, under
    ``layer/kda_proj``; the one latent layer each attention kernel once
    (the replay runs none), under ``layer/attn_latent`` and ``part/kernel``;
    the rule's loops (the segments' ``lax.map`` and the state's scan) stand
    under ``layer/kda_scan``, where ``kda_scan_ms`` reads them."""
    text = published_kda_step[0]
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    kinds = kimi_linear.kinds_of(KIMI)
    found = collections.Counter(re.search(
        r'op_name="[^"]*/(layer_\d)/[^"]*layer/(\w+)/[^"]*/(\w+)/pallas_call"',
        line).groups() for line in calls if "pallas_call" in line)
    want = {}
    for i, kind in enumerate(kinds):
        if kind == "kda":
            want[f"layer_{i}", "kda_proj", "gdn_conv_forward"] = 2
            want[f"layer_{i}", "kda_proj", "gdn_conv_backward"] = 1
        else:
            want.update({(f"layer_{i}", "attn_latent",
                          f"flash_attention_{name}"): 1 for name in KERNELS})
    assert found == want
    loops = [line for line in text.splitlines()
             if " while(" in line and "layer/kda_scan" in line]
    assert len(loops) >= 3 * kinds.count("kda")
    assert not score_arrays(text)
    # The pairwise sub-blocks of a segment, never of a sequence.
    assert "f32[16,1,32,4,16,16,128]" in text
    assert "f32[128,1,32,4,16,16,128]" not in text
