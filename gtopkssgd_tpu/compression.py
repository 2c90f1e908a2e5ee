"""Gradient compression with error feedback — pure-functional, jit-resident.

Reference parity (compression.py::TopKCompressor in hclhkbu/gtopkssgd,
SURVEY.md C4): per-step the reference keeps a class-attribute `residuals`
dict, computes `acc = grad + residual`, selects `torch.topk(|acc|, k)`,
zeroes the selected entries out of the residual, and after the allreduce
calls `add_residuals(...)` to return locally-selected-but-globally-rejected
values to the residual (the gTop-k error-feedback repair).

TPU-native redesign: the residual is an explicit array owned by the
optimizer state (one pytree — so Orbax checkpoints it, fixing the
reference's silent residual reset on resume), and every operation below is a
pure function traced once under `jit`. There is no mutation, no dict keyed by
layer name, and no host round-trip. On a mesh it is one flat f32[N] vector
(the reference flattens all layer grads into one vector per step, and the
wire's index sets need one index space — we do the same with
`ravel_pytree`); on one device, where nothing is sent, the same selection
runs on the gradient's own leaves (`LeafPlan`, `compress_leaves_by_threshold`)
and the residual is held leaf by leaf.

The three-stage protocol used by the distributed optimizer:

    acc             = grad + residual                     (accumulate)
    vals, idx, res' = compress(acc)                       (select + zero-out)
    gvals, gidx     = <sparse allreduce over the dp axis> (parallel/)
    res''           = repair(res', vals, idx, gidx)       (error-feedback fix)
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gtopkssgd_tpu import modes
from gtopkssgd_tpu.ops import (
    k_for_density,
    membership_mask,
    select_tau,
    select_tau_leaves,
    select_topk,
)

Array = jax.Array

# A leaf the P = 1 step works on where it lies: at least this many elements
# and a last axis of at least a row of lanes (a [2048, 1] gate is 128 times
# its size in tiles). Every other leaf rides in the one grouped vector, whose
# concatenate reads it the moment it is made. Two readings set the size
# (PERF.md section 6, PR 42). Speed asks for no more: ResNet-50's optimizer
# step alone, on the chip, takes 2.9-3.5 ms with its 10 leaves of 2**20
# elements or more in place, 3.4-4.0 with the 29 of 2**18 or more, 3.9-4.1
# with all 161 grouped (the parent's flat form 3.59). Memory asks for no
# less: the readers of a leaf in place all sit at the step's end, so the
# chip's compiler leaves the product that makes a small leaf's gradient
# until then and holds its inputs, the layer's activations, through the rest
# of the backward pass. Qwen's published step reads 14.61 GB by
# `memory_analysis()` with its eighteen [2048, 512] leaves (2**20 elements)
# in place and 13.94 with them grouped (the parent's 13.62).
IN_PLACE_MIN_ELEMS = 1 << 21
IN_PLACE_MIN_LAST = 128


class LeafPlan(NamedTuple):
    """Where each leaf of a gradient tree lives in the P = 1 step's working
    form, the **slabs**: the leaves worked on in place, each in its own
    shape, then one vector that holds every other leaf end to end (in the
    tree's order; absent when there is none). Static, from the shapes."""

    shapes: Tuple[Tuple[int, ...], ...]
    in_place: Tuple[int, ...]
    grouped: Tuple[int, ...]

    @property
    def sizes(self) -> Tuple[int, ...]:
        return tuple(int(np.prod(s, dtype=np.int64)) for s in self.shapes)

    @property
    def n(self) -> int:
        return sum(self.sizes)

    @property
    def slab_shapes(self) -> Tuple[Tuple[int, ...], ...]:
        sizes = self.sizes
        out = [self.shapes[i] for i in self.in_place]
        if self.grouped:
            out.append((sum(sizes[i] for i in self.grouped),))
        return tuple(out)

    def counters(self) -> dict:
        """What the manifest says of the form the step took."""
        sizes = self.sizes
        return {
            "leaves_in_place": len(self.in_place),
            "leaves_grouped": len(self.grouped),
            "elems_in_place_share": (
                sum(sizes[i] for i in self.in_place) / max(1, self.n)),
        }

    def split(self, leaves: Sequence, xp=jnp) -> list:
        """Leaves (the tree's order) -> slabs."""
        slabs = [leaves[i] for i in self.in_place]
        if self.grouped:
            slabs.append(_concatenate(
                [leaves[i].reshape(-1) for i in self.grouped], xp))
        return slabs

    def join(self, slabs: Sequence) -> list:
        """Slabs -> leaves (the tree's order), each in its own shape."""
        leaves = [None] * len(self.shapes)
        for slab, i in zip(slabs, self.in_place):
            leaves[i] = slab
        sizes, off = self.sizes, 0
        for i in self.grouped:
            leaves[i] = slabs[-1][off:off + sizes[i]].reshape(self.shapes[i])
            off += sizes[i]
        return leaves

    def from_flat(self, flat, xp=jnp) -> list:
        """The [N] vector `ravel_pytree` makes of the tree -> slabs."""
        sizes, leaves, off = self.sizes, [], 0
        for shape, size in zip(self.shapes, sizes):
            leaves.append(flat[off:off + size].reshape(shape))
            off += size
        return self.split(leaves, xp)

    def to_flat(self, slabs: Sequence, xp=jnp):
        """Slabs -> the [N] vector in `ravel_pytree`'s order."""
        return _concatenate(
            [leaf.reshape(-1) for leaf in self.join(slabs)], xp)


def _concatenate(parts: Sequence, xp):
    """One concatenate, as ``ravel_pytree`` makes (jnp's cuts a long list
    into groups of 16 and those into one)."""
    if xp is not jnp:
        return xp.concatenate(parts)
    return parts[0] if len(parts) == 1 else jax.lax.concatenate(parts, 0)


def plan_leaves(shapes: Sequence[Sequence[int]]) -> LeafPlan:
    """The rule reads a leaf's size and last axis, nothing else."""
    shapes = tuple(tuple(int(d) for d in s) for s in shapes)
    in_place = tuple(
        i for i, s in enumerate(shapes)
        if len(s) >= 2 and s[-1] >= IN_PLACE_MIN_LAST
        and int(np.prod(s, dtype=np.int64)) >= IN_PLACE_MIN_ELEMS)
    grouped = tuple(i for i in range(len(shapes)) if i not in in_place)
    return LeafPlan(shapes, in_place, grouped)


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
        """Mask-form selection for paths that need no wire format, over
        ONE vector: a leaf or a bucket of the layerwise mode at P = 1
        (optimizer.py's leaves form). The flat modes' one-device step no
        longer builds the [N] vector this once read; its twin over leaves
        is ``compress_leaves_by_threshold`` below, same rules.

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

    def compress_leaves_by_threshold(
        self, accs: Sequence[Array],
    ) -> Tuple[list, list, Array]:
        """`compress_by_threshold` over an accumulator that exists only as
        leaves (any shapes; N elements together): ONE tau, the k(N)-th
        magnitude over all of them by the configured kernel's rule
        (ops.select_tau_leaves), and each leaf's masks in the leaf's own
        shape. Returns (keeps, residuals, kept_tau); ties, the tau = 0 rule
        and kept_tau are `compress_by_threshold`'s, over the whole."""
        k = self.k(sum(int(a.size) for a in accs))
        with jax.named_scope("gtopk/select"):
            tau = select_tau_leaves(accs, k, self.method)
        with jax.named_scope("gtopk/mask"):
            # |acc| >= tau and |acc| > 0, written on acc itself: a
            # magnitude array with two readers (the search's candidates
            # and this compare) is one the compiler writes out.
            keeps = [((a >= tau) | (a <= -tau)) & (a != 0.0) for a in accs]
            kept_tau = jnp.min(jnp.stack([
                jnp.min(jnp.where(keep, jnp.abs(a), jnp.inf))
                for keep, a in zip(keeps, accs)]))
            kept_tau = jnp.where(
                jnp.isfinite(kept_tau), kept_tau, 0.0).astype(jnp.float32)
            return (keeps,
                    [jnp.where(keep, 0.0, a) for keep, a in zip(keeps, accs)],
                    kept_tau)

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
