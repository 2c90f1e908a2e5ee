"""Gradient compression with error feedback — pure-functional, jit-resident.

Reference parity (compression.py::TopKCompressor in hclhkbu/gtopkssgd,
SURVEY.md C4): per-step the reference keeps a class-attribute `residuals`
dict, computes `acc = grad + residual`, selects `torch.topk(|acc|, k)`,
zeroes the selected entries out of the residual, and after the allreduce
calls `add_residuals(...)` to return locally-selected-but-globally-rejected
values to the residual (the gTop-k error-feedback repair).

TPU-native redesign: the residual is an explicit flat f32[N] array owned by
the optimizer state (one pytree — so Orbax checkpoints it, fixing the
reference's silent residual reset on resume), and every operation below is a
pure function traced once under `jit`. There is no mutation, no dict keyed by
layer name (the reference flattens all layer grads into one vector per step
anyway — we do the same with `ravel_pytree`), and no host round-trip.

The three-stage protocol used by the distributed optimizer:

    acc             = grad + residual                     (accumulate)
    vals, idx, res' = compress(acc)                       (select + zero-out)
    gvals, gidx     = <sparse allreduce over the dp axis> (parallel/)
    res''           = repair(res', vals, idx, gidx)       (error-feedback fix)
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from gtopkssgd_tpu import modes
from gtopkssgd_tpu.ops import (
    k_for_density,
    membership_mask,
    select_tau,
    select_topk,
)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class TopKCompressor:
    """Magnitude top-k with error feedback. `density` = k / N (reference flag
    `--density`, rho, typically 1e-3). `method` picks the selection kernel
    (see ops.topk.select_topk): auto | exact | blockwise | approx | pallas
    | twostage (fused two-stage bucket select, arXiv:2506.04165) |
    simrecall (the CPU-runnable pessimistic approx stand-in)."""

    density: float
    method: str = "auto"

    def k(self, n: int) -> int:
        return k_for_density(n, self.density)

    def init_residual(self, n: int, dtype=jnp.float32) -> Array:
        return jnp.zeros((n,), dtype)

    @jax.named_scope("gtopk/accumulate")
    def accumulate(self, grad_flat: Array, residual: Array) -> Array:
        """acc = grad + residual (the error-feedback accumulation)."""
        return grad_flat + residual

    def compress(
        self,
        acc: Array,
        *,
        grad: Optional[Array] = None,
        residual: Optional[Array] = None,
    ) -> Tuple[Array, Array, Array]:
        """Select top-k of |acc|; residual keeps everything not selected.

        Returns (vals f32[k], idx i32[k], residual f32[N]).

        When the caller passes the unfused operands (`grad`, `residual`
        with acc == grad + residual), the selection reads them directly —
        the `twostage` kernel folds the error-feedback accumulate into
        its own stage-1 HBM pass instead of consuming a materialized
        accumulator (the other methods fold in XLA; same values either
        way). The returned residual is still acc with the selected
        entries zeroed.
        """
        n = acc.shape[0]
        with jax.named_scope("gtopk/select"):
            if grad is not None:
                vals, idx = select_topk(grad, self.k(n), self.method,
                                        residual=residual)
            else:
                vals, idx = select_topk(acc, self.k(n), self.method)
        with jax.named_scope("gtopk/mask"):
            residual_out = acc.at[idx].set(0.0, mode="drop")
        return vals, idx, residual_out

    def compress_by_threshold(
        self,
        acc: Array,
        *,
        grad: Optional[Array] = None,
        residual: Optional[Array] = None,
    ) -> Tuple[Array, Array, Array]:
        """Mask-form selection for paths that need no wire format.

        Returns (keep bool[N], residual f32[N], kept_tau f32[]) with
        ``keep = |acc| >= tau`` where tau is the k-th largest magnitude
        (as reported by the configured selection kernel),
        ``residual = where(keep, 0, acc)``, and ``kept_tau`` the smallest
        magnitude actually KEPT (0 when the keep set is empty) — the obs
        ``keep_tau`` convention, reported from here so telemetry callers
        do not re-reduce the same mask.

        Semantically this is the same partition as ``compress`` —
        selected entries leave the residual, everything else stays — but
        expressed without index sets: no scatter to zero the residual, no
        gather to read the values. At p=1 (or any point where the
        selected set is applied locally rather than sent), index sets
        buy nothing, and the scatter/gather chain they drag in is what
        blocks XLA from fusing the selection into the surrounding
        elementwise pipeline (measured: the fused-step gtopk-over-dense
        overhead was ~3x the isolated compress cost before this path —
        see benchmarks/results/fused_variants_TPU_v5_lite.json).

        Set-membership caveats vs ``compress``, both convergence-neutral
        under error feedback (the keep/residual partition stays exact by
        construction): magnitude ties at tau all pass (count can exceed
        k), and with the approx kernel tau is the smallest magnitude the
        kernel FOUND, so elements the kernel missed but whose magnitude
        still clears tau are selected here even though compress would
        have dropped them (a strict superset — threshold recall is >=
        the kernel's). When tau == 0 (fewer than k nonzeros in acc, or a
        kernel padding its value slots with 0.0), zeros are masked OUT of
        the keep set rather than selected: |x| >= 0 is vacuously true,
        and "select all" would e.g. zero an entire velocity buffer under
        momentum correction instead of touching <=k coordinates like the
        index form does.

        tau comes from the tau-only API (ops.select_tau) — no k-sized
        (vals, idx) set is materialized and no gather runs just to read
        one scalar. When the caller passes the unfused operands (`grad`,
        `residual` with acc == grad + residual), the tau search reads
        them directly, fusing the error-feedback accumulate into the
        selection pass for the twostage/pallas kernels."""
        n = acc.shape[0]
        with jax.named_scope("gtopk/select"):
            if grad is not None:
                tau = select_tau(grad, self.k(n), self.method,
                                 residual=residual)
            else:
                tau = select_tau(acc, self.k(n), self.method)
        with jax.named_scope("gtopk/mask"):
            keep = (jnp.abs(acc) >= tau) & (jnp.abs(acc) > 0.0)
            kept_tau = jnp.min(jnp.where(keep, jnp.abs(acc), jnp.inf))
            kept_tau = jnp.where(
                jnp.isfinite(kept_tau), kept_tau, 0.0).astype(jnp.float32)
            return keep, jnp.where(keep, 0.0, acc), kept_tau

    @jax.named_scope("gtopk/repair")
    def repair(
        self,
        residual: Array,
        local_vals: Array,
        local_idx: Array,
        global_idx: Array,
    ) -> Array:
        """Error-feedback repair: local selections that did NOT survive the
        global top-k go back into the residual (reference `add_residuals`).
        Without this step their gradient mass would be lost forever and
        convergence degrades — SURVEY.md §7 hard-part #4.

        Known semantic subtlety (inherent to gTop-k, reference included):
        membership is judged against the FINAL global set, so a contribution
        that was dropped mid-tree (its index lost an intermediate top-k) but
        whose index later survived via other devices' mass is counted as
        delivered even though it wasn't — that mass leaks (~0.1-1% of
        communicated mass per step, measured on random gradients). This is
        exactly the gTop-k vs exact-top-k approximation analyzed in
        arXiv:1911.08772; error feedback still bounds the error because the
        leak only affects co-selected coordinates."""
        rejected = ~membership_mask(local_idx, global_idx)
        put_back = jnp.where(rejected, local_vals, 0.0)
        return residual.at[local_idx].add(put_back, mode="drop")

    @jax.named_scope("gtopk/repair")
    def fold_wire_error(
        self,
        residual: Array,
        local_idx: Array,
        wire_err: Array,
    ) -> Array:
        """Fold wire-codec quantization error into the residual.

        ``wire_err = vals - dequant(quant(vals))`` per selected slot
        (parallel.codec.roundtrip_aligned keeps original slot order, so
        it lines up with ``local_idx``). Called BEFORE the collective:
        the shipped values become the requantized ones, the error stays
        local, and the ``repair`` above — which restores the SHIPPED
        value for rejected picks — then composes exactly: requantized
        value + folded error = the original selection. Sentinel slots
        (idx == n) carry zero error and drop out of the scatter."""
        return residual.at[local_idx].add(wire_err, mode="drop")


@dataclasses.dataclass(frozen=True)
class NoneCompressor:
    """Dense passthrough (reference `NoneCompressor`): no selection, no
    residual. Used by the dense-psum baseline path."""

    density: float = 1.0
    method: str = "none"

    def k(self, n: int) -> int:
        return n

    def init_residual(self, n: int, dtype=jnp.float32) -> Array:
        return jnp.zeros((0,), dtype)

    def accumulate(self, grad_flat: Array, residual: Array) -> Array:
        return grad_flat

    def compress(self, acc: Array, *, grad: Optional[Array] = None,
                 residual: Optional[Array] = None
                 ) -> Tuple[Array, Array, Array]:
        n = acc.shape[0]
        idx = jnp.arange(n, dtype=jnp.int32)
        return acc, idx, jnp.zeros((0,), acc.dtype)

    def repair(self, residual, local_vals, local_idx, global_idx):
        return residual


# Name -> class registry, mirroring the reference's module-level
# `compressors` dict ({'topk': TopKCompressor, 'none'/None: NoneCompressor}).
# Keys are derived from the package-wide mode vocabulary (modes.py) so the
# registry can never drift from what the optimizer/collectives accept.
compressors = {
    **{m: NoneCompressor for m in modes.DENSE_MODES},
    **{m: TopKCompressor for m in modes.SPARSE_MODES},
}


def get_compressor(
    name: Optional[str], density: float = 0.001, method: str = "auto"
):
    """Build a configured compressor instance from the `compressors` registry."""
    try:
        cls = compressors[name]
    except KeyError:
        raise ValueError(f"unknown compressor {name!r}") from None
    if cls is NoneCompressor:
        return NoneCompressor()
    return cls(density=density, method=method)
