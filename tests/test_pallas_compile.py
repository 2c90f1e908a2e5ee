"""The selection kernels, asked of the chip's compiler without the chip.

Interpret mode accepts kernels the TPU lowering refuses (a candidate block
whose sublane dimension is below 8) or cannot fit (VMEM). The TPU compiler
is installed here and compiles for a *described* v5e topology, so these
AOT compiles guard the main path's kernels at real widths on every PR at no
chip time. Nothing executes; a passing compile is not a chip run. Skipped,
not failed, where the topology cannot be described.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from gtopkssgd_tpu.ops import pallas_topk as pk
from gtopkssgd_tpu.ops.topk import (
    TWOSTAGE_OVERSAMPLE,
    _twostage_pallas_groups,
    k_for_density,
    twostage_topk_abs,
)

RESNET20_N = 272_474
RESNET50_N = 25_557_032


@pytest.fixture(scope="module")
def v5e():
    """One described v5e device; the persistent cache is off around the
    module (a compile for a described device is written to it but cannot
    be read back without a chip, and the next one warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


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
