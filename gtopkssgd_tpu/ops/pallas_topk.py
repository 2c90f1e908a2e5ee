"""Pallas TPU kernels for large-N magnitude top-k (the reference's
`torch.topk` CUDA obligation — SURVEY.md §2 native table, §7 step 6).

Two kernel families share one VMEM-block scan skeleton:

1. **Threshold counting** ("threshold-estimate + compact", the strategy
   SURVEY.md names): exact top-k over a flat f32[N] needs a selection
   threshold tau = the k-th largest |x|. We find tau by monotone
   multisection — each round evaluates ``count(|x| >= t)`` for 8 candidate
   thresholds — then compact the <= cap surviving elements and run one
   small exact `lax.top_k` over them (see ops.topk.threshold_topk_abs).
   XLA would issue 8 separate N-element reductions (8 HBM passes); the
   kernel fuses them into ONE pass — read a VMEM block once, compare
   against all 8 thresholds, accumulate 8 counts. The TPU grid is
   sequential per core, so cross-block accumulation into the same output
   block is safe (standard grid-accumulation pattern).

2. **Fused two-stage stage 1** (generalized two-stage approximate top-k,
   arXiv:2506.04165 lineage): the same one-pass block scan instead emits
   per-bucket partial top-k' candidates — bucket = (sublane-group, lane),
   top-1 per bucket, L = grid * groups * 128 buckets total — AND the same
   8-threshold counts, AND reads ``grad + residual`` as two operands so
   the error-feedback accumulate (compression.py's ``acc = grad +
   residual``) fuses into the selection's HBM pass instead of costing its
   own N-sized read+write. Stage 2 (a small exact `lax.top_k` over the
   <= L candidates) runs outside the kernel in ops.topk.twostage_topk_abs.
   Missing a true top-k element requires it to collide with a LARGER
   element in its bucket, so expected recall ~= 1 - k/(2L); the default
   oversample (ops.topk.TWOSTAGE_OVERSAMPLE) targets recall >= 0.95, and
   error feedback provably absorbs the misses (arXiv:1911.08772 — the
   same argument that justifies the `approx` method).

`lax.top_k` itself cannot lower inside a Pallas TPU kernel (verified:
NotImplementedError in the pinned jax), which is exactly why both
families keep the selection *reduction* (counts / per-bucket maxima) in
the kernel and the final small reselect outside it.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NUM_THRESHOLDS = 8
# One grid step processes BLOCK_ROWS x 128 elements from VMEM.
BLOCK_ROWS = 2048
_LANES = 128
_BLOCK = BLOCK_ROWS * _LANES
# Stage-1 group counts the TPU compiler accepts (AOT-compiled for v5e in
# tests/test_pallas_compile.py). Below 8 the (groups, 128) candidate block
# breaks the lowering's (8, 128) block-shape rule; at 2048 — one row per
# bucket — the kernel's (groups, rpg, 128) temporaries exhaust VMEM.
MIN_GROUPS = 8
MAX_GROUPS = 1024


def _count_kernel(thr_ref, x_ref, out_ref):
    """Accumulate counts of |x_block| >= thr for all 8 thresholds.

    thr_ref: SMEM (NUM_THRESHOLDS,) f32 — candidate thresholds.
    x_ref:   VMEM (BLOCK_ROWS, 128) f32 — this grid step's block (|x|,
             pre-padded with -1 which no threshold >= 0 counts).
    out_ref: SMEM (1, NUM_THRESHOLDS) i32 — running counts (same block for
             every grid step: sequential accumulation; scalar stores must
             target SMEM on TPU).
    """
    first = pl.program_id(0) == 0
    mag = x_ref[:]

    def body(i, _):
        t = thr_ref[i]
        c = jnp.sum((mag >= t).astype(jnp.int32))
        prev = jnp.where(first, 0, out_ref[0, i])  # SMEM: scalar ops only
        out_ref[0, i] = prev + c
        return 0

    jax.lax.fori_loop(0, NUM_THRESHOLDS, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def multi_threshold_count(
    mag: Array, thresholds: Array, *, interpret: bool = False
) -> Array:
    """counts[i] = #{ j : mag[j] >= thresholds[i] } in ONE memory pass.

    mag: f32[N] (non-negative; callers pass |x|). thresholds: f32[8].
    """
    n = mag.shape[0]
    nblocks = max(1, -(-n // _BLOCK))
    padded = nblocks * _BLOCK
    # Pad with -1: strictly below any threshold >= 0, so never counted.
    mag2 = jnp.pad(mag, (0, padded - n), constant_values=-1.0)
    mag2 = mag2.reshape(nblocks * BLOCK_ROWS, _LANES)
    counts = pl.pallas_call(
        _count_kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(
                (BLOCK_ROWS, _LANES),
                lambda i: (i, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, NUM_THRESHOLDS), lambda i: (0, 0), memory_space=pltpu.SMEM
        ),
        out_shape=jax.ShapeDtypeStruct((1, NUM_THRESHOLDS), jnp.int32),
        interpret=interpret,
    )(thresholds, mag2)
    return counts[0]


def pallas_topk_abs(x: Array, k: int, *, interpret: bool = False
                    ) -> Tuple[Array, Array]:
    """Exact (up to boundary ties) magnitude top-k using the Pallas counting
    kernel for threshold search. Same contract as ops.topk.topk_abs."""
    from gtopkssgd_tpu.ops.topk import threshold_topk_abs

    return threshold_topk_abs(
        x, k,
        count_fn=functools.partial(multi_threshold_count, interpret=interpret),
    )


# --------------------------------------------------------------------------
# Fused two-stage stage 1: per-bucket candidates (+ optional counts,
# + optional error-feedback residual) in one HBM pass over the gradient.
# --------------------------------------------------------------------------


def _make_stage1_kernel(n: int, groups: int,
                        with_residual: bool, with_counts: bool):
    """Build the stage-1 kernel for a given flat length / bucket layout.

    Buckets: each grid block's (BLOCK_ROWS, 128) tile is split into
    `groups` row-groups of rpg = BLOCK_ROWS/groups sublanes; one bucket is
    (row-group, lane) — rpg elements at stride 128 in the flat order, so
    contiguous layer slices spread across 128 lanes (adjacent flat indices
    land in different buckets). The kernel emits each bucket's max-|acc|
    element (signed value + global flat index) as a candidate. Everything
    is a lane-aligned max/select reduction — no in-kernel top-k, which
    cannot lower on TPU (module docstring).

    Padding/tail: elements with global index >= n get magnitude -1, which
    loses to every real element (real magnitudes are >= 0). A bucket that
    is ENTIRELY padding emits its first slot: index >= n (the caller
    sentinels it) and value 0 (the wrapper zero-pads the operands).
    """
    rpg = BLOCK_ROWS // groups

    def kernel(*refs):
        refs = list(refs)
        thr_ref = refs.pop(0) if with_counts else None
        g_ref = refs.pop(0)
        r_ref = refs.pop(0) if with_residual else None
        val_ref, idx_ref = refs[0], refs[1]
        cnt_ref = refs[2] if with_counts else None

        i = pl.program_id(0)
        acc = g_ref[:]
        if with_residual:
            # The error-feedback accumulate, fused into the selection's
            # read of the gradient block — acc never hits HBM.
            acc = acc + r_ref[:]
        rows = lax.broadcasted_iota(jnp.int32, (BLOCK_ROWS, _LANES), 0)
        lanes = lax.broadcasted_iota(jnp.int32, (BLOCK_ROWS, _LANES), 1)
        eidx = i * _BLOCK + rows * _LANES + lanes
        mag = jnp.where(eidx < n, jnp.abs(acc), -1.0)

        if with_counts:
            # Same accumulation pattern as _count_kernel, sharing this
            # pass's read of the block (grid is sequential per core).
            first = i == 0

            def cbody(t, _):
                c = jnp.sum((mag >= thr_ref[t]).astype(jnp.int32))
                prev = jnp.where(first, 0, cnt_ref[0, t])
                cnt_ref[0, t] = prev + c
                return 0

            lax.fori_loop(0, NUM_THRESHOLDS, cbody, 0)

        # Per-bucket argmax via reshape: (groups, rpg, 128), reduce the
        # middle (row-within-group) axis. First-max-row tie rule keeps
        # the winner deterministic (lax.top_k's lowest-index-first class).
        mag3 = mag.reshape(groups, rpg, _LANES)
        acc3 = acc.reshape(groups, rpg, _LANES)
        mx = jnp.max(mag3, axis=1)  # (groups, 128)
        riota = lax.broadcasted_iota(jnp.int32, (groups, rpg, _LANES), 1)
        win = jnp.min(
            jnp.where(mag3 == mx[:, None, :], riota, rpg), axis=1)
        val = jnp.sum(
            jnp.where(riota == win[:, None, :], acc3, 0.0), axis=1)
        grow = lax.broadcasted_iota(jnp.int32, (groups, _LANES), 0)
        lane2 = lax.broadcasted_iota(jnp.int32, (groups, _LANES), 1)
        gidx = i * _BLOCK + (grow * rpg + win) * _LANES + lane2
        val_ref[:] = val
        idx_ref[:] = gidx

    return kernel


@functools.partial(jax.jit, static_argnames=("groups", "interpret"))
def fused_stage1_candidates(
    grad: Array,
    thresholds: Optional[Array] = None,
    residual: Optional[Array] = None,
    *,
    groups: int = 8,
    interpret: bool = False,
) -> Tuple[Array, Array, Optional[Array]]:
    """One fused pass over `grad` (+ `residual`): per-bucket candidates.

    Returns (cand_val f32[L], cand_idx i32[L], counts i32[8] | None) with
    L = nblocks * groups * 128 buckets. `groups` must divide BLOCK_ROWS
    and, compiled, lie in [MIN_GROUPS, MAX_GROUPS].
    Candidate indices >= n mark padding buckets (value 0). When
    `thresholds` (f32[8]) is given, the same pass also accumulates the
    multisection counts `#{|grad+residual| >= thr}` — the _count_kernel
    obligation — without a second read of x. When `residual` is given,
    the kernel reads grad and residual and forms acc = grad + residual
    in VMEM: the error-feedback accumulate costs no extra HBM pass and
    the flat [N] accumulator is never materialized.
    """
    n = grad.shape[0]
    if BLOCK_ROWS % groups != 0:
        raise ValueError(f"groups={groups} must divide {BLOCK_ROWS}")
    if not interpret and not MIN_GROUPS <= groups <= MAX_GROUPS:
        raise ValueError(
            f"groups={groups} does not compile for the TPU; use "
            f"{MIN_GROUPS}..{MAX_GROUPS}")
    nblocks = max(1, -(-n // _BLOCK))
    padded = nblocks * _BLOCK
    with_counts = thresholds is not None
    with_residual = residual is not None

    def tile(v):
        return jnp.pad(v, (0, padded - n)).reshape(
            nblocks * BLOCK_ROWS, _LANES)

    vmem_spec = pl.BlockSpec(
        (BLOCK_ROWS, _LANES), lambda i: (i, 0), memory_space=pltpu.VMEM)
    operands, in_specs = [], []
    if with_counts:
        operands.append(thresholds)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    operands.append(tile(grad))
    in_specs.append(vmem_spec)
    if with_residual:
        operands.append(tile(residual))
        in_specs.append(vmem_spec)

    cand_spec = pl.BlockSpec(
        (groups, _LANES), lambda i: (i, 0), memory_space=pltpu.VMEM)
    out_shape = [
        jax.ShapeDtypeStruct((nblocks * groups, _LANES), grad.dtype),
        jax.ShapeDtypeStruct((nblocks * groups, _LANES), jnp.int32),
    ]
    out_specs = [cand_spec, cand_spec]
    if with_counts:
        out_shape.append(
            jax.ShapeDtypeStruct((1, NUM_THRESHOLDS), jnp.int32))
        out_specs.append(pl.BlockSpec(
            (1, NUM_THRESHOLDS), lambda i: (0, 0),
            memory_space=pltpu.SMEM))

    out = pl.pallas_call(
        _make_stage1_kernel(n, groups, with_residual, with_counts),
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    cand_val = out[0].reshape(-1)
    cand_idx = out[1].reshape(-1)
    counts = out[2][0] if with_counts else None
    return cand_val, cand_idx, counts


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_multi_threshold_count(
    grad: Array,
    thresholds: Array,
    residual: Optional[Array] = None,
    *,
    interpret: bool = False,
) -> Array:
    """multi_threshold_count over |grad + residual| without materializing
    the accumulator: counts[i] = #{ j : |grad[j]+residual[j]| >= thr[i] }
    in one fused pass over both operands. With residual=None this is
    multi_threshold_count(|grad|, ...)."""
    if residual is None:
        return multi_threshold_count(
            jnp.abs(grad), thresholds, interpret=interpret)
    n = grad.shape[0]
    nblocks = max(1, -(-n // _BLOCK))
    padded = nblocks * _BLOCK

    def tile(v):
        return jnp.pad(v, (0, padded - n)).reshape(
            nblocks * BLOCK_ROWS, _LANES)

    def kernel(thr_ref, g_ref, r_ref, out_ref):
        i = pl.program_id(0)
        first = i == 0
        rows = lax.broadcasted_iota(jnp.int32, (BLOCK_ROWS, _LANES), 0)
        lanes = lax.broadcasted_iota(jnp.int32, (BLOCK_ROWS, _LANES), 1)
        eidx = i * _BLOCK + rows * _LANES + lanes
        mag = jnp.where(eidx < n, jnp.abs(g_ref[:] + r_ref[:]), -1.0)

        def body(t, _):
            c = jnp.sum((mag >= thr_ref[t]).astype(jnp.int32))
            prev = jnp.where(first, 0, out_ref[0, t])
            out_ref[0, t] = prev + c
            return 0

        lax.fori_loop(0, NUM_THRESHOLDS, body, 0)

    vmem_spec = pl.BlockSpec(
        (BLOCK_ROWS, _LANES), lambda i: (i, 0), memory_space=pltpu.VMEM)
    counts = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  vmem_spec, vmem_spec],
        out_specs=pl.BlockSpec(
            (1, NUM_THRESHOLDS), lambda i: (0, 0),
            memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, NUM_THRESHOLDS), jnp.int32),
        interpret=interpret,
    )(thresholds, tile(grad), tile(residual))
    return counts[0]
