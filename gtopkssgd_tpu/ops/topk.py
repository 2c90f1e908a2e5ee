"""Top-k selection and sparse (index, value) set algebra, shape-static for XLA.

Reference parity: the reference compressor (compression.py::TopKCompressor in
hclhkbu/gtopkssgd) calls `torch.topk(|acc|, k)` on GPU over the flat gradient
(N up to ~1e8 for ResNet-50) and the allreducer merges (index, value) pairs in
numpy on the host. Here both live on the TPU:

  * `topk_abs`           -- exact magnitude top-k via `lax.top_k` (one shot).
  * `blockwise_topk_abs` -- exact two-stage top-k: per-block candidates then a
                            global reselect.  Much friendlier to the TPU VPU
                            for large N because each `lax.top_k` call runs on
                            a short row of a 2-D batch instead of one huge
                            vector. Used by default for N above a threshold.
  * `approx_topk_abs`    -- `lax.approx_max_k` (TPU-optimized, recall<1);
                            opt-in, changes semantics slightly.
  * `twostage_topk_abs`  -- generalized two-stage approximate top-k
                            (arXiv:2506.04165): one pass emitting per-bucket
                            max candidates (Pallas-fused with the error-
                            feedback accumulate on TPU), then a small exact
                            reselect. Recall ~= 1 - k/(2L); misses stay in
                            the residual (arXiv:1911.08772).
  * `select_tau`         -- tau-only API: the k-th |value| threshold without
                            materializing a k-sized (vals, idx) set, for
                            threshold-mask consumers (compress_by_threshold).
  * `merge_sparse_sets`  -- the per-round merge of the gTop-k tree: sparse sum
                            of two k-sized unique-index sets, then reselect.

Sparse sets are a pair of arrays `(values f32[k], indices i32[k])` with unique
indices; padding slots use `index == n` (one past the end) with value 0 so a
`scatter(..., mode='drop')` ignores them.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

SENTINEL_DTYPE = jnp.int32

Array = jax.Array


def k_for_density(n: int, density: float) -> int:
    """k = max(1, ceil(density * n)) — matches the reference's k choice."""
    return max(1, int(math.ceil(float(density) * n)))


def topk_abs(x: Array, k: int) -> Tuple[Array, Array]:
    """Exact top-k of |x| over a flat vector. Returns (signed values, indices).

    Indices are int32. Output is ordered by descending |value| (ties broken by
    `lax.top_k`'s deterministic lowest-index-first rule, which is what makes
    the SPMD-symmetric gtopk merge produce identical results on every device).
    """
    mag = jnp.abs(x)
    _, idx = lax.top_k(mag, k)
    idx = idx.astype(SENTINEL_DTYPE)
    vals = jnp.take(x, idx, mode="fill", fill_value=0)
    return vals, idx


def blockwise_topk_abs(x: Array, k: int, num_blocks: int = 0) -> Tuple[Array, Array]:
    """Exact top-k of |x| using a two-stage (per-block, then global) select.

    Stage 1 reshapes the flat N-vector into (B, ceil(N/B)) rows and takes the
    top-min(k, row) of each row in one batched `lax.top_k`; stage 2 reselects
    the global top-k among the <= B*k candidates. Exactness: every global
    top-k element is necessarily in its own block's top-k.

    This is the lax formulation of the two-stage kernel strategy listed in
    SURVEY.md §2 (native obligations table) for the `torch.topk` replacement;
    the Pallas version lives in `ops/pallas_topk.py`.
    """
    n = x.shape[0]
    if num_blocks <= 0:
        # Heuristic: rows of ~64k elements keep each top-k call cheap while
        # stage 2 stays small (B * k candidates).
        num_blocks = max(1, n // 65536)
    block = -(-n // num_blocks)  # ceil
    padded = block * num_blocks
    kb = min(k, block)
    xp = jnp.pad(x, (0, padded - n))
    mag = jnp.abs(xp).reshape(num_blocks, block)
    # In-block positions of per-block candidates.
    _, pos = lax.top_k(mag, kb)  # (B, kb)
    base = (jnp.arange(num_blocks, dtype=SENTINEL_DTYPE) * block)[:, None]
    cand_idx = (pos.astype(SENTINEL_DTYPE) + base).reshape(-1)
    cand_val = jnp.take(xp, cand_idx).reshape(-1)
    # Padding elements are 0 and sort last; mask them to sentinel after select.
    _, sel = lax.top_k(jnp.abs(cand_val), k)
    idx = jnp.take(cand_idx, sel)
    vals = jnp.take(cand_val, sel)
    oob = idx >= n
    idx = jnp.where(oob, n, idx).astype(SENTINEL_DTYPE)
    vals = jnp.where(oob, 0.0, vals)
    return vals, idx


def approx_topk_abs(x: Array, k: int, recall_target: float = 0.95) -> Tuple[Array, Array]:
    """TPU-optimized approximate top-k (`lax.approx_max_k`). Opt-in only:
    recall < 1 slightly changes gTop-k semantics (still convergent thanks to
    error feedback, but document any use in experiments)."""
    mag = jnp.abs(x)
    _, idx = lax.approx_max_k(mag, k, recall_target=recall_target)
    idx = idx.astype(SENTINEL_DTYPE)
    vals = jnp.take(x, idx, mode="fill", fill_value=0)
    return vals, idx


def bucketize_counts(mag: Array, thr: Array) -> Array:
    """counts[i] = #{ j : mag[j] >= thr[i] } for all 8 thresholds in ONE
    logical pass over `mag` (the XLA analogue of the fused Pallas
    counting kernel; previously this was a vmapped 8-reduction = 8 HBM
    passes). Sort the thresholds, bucketize every magnitude with one
    `searchsorted`, histogram the bucket ids, and read each threshold's
    count as a suffix sum: mag >= thr_sorted[i]  iff  its bucket id
    (#thresholds <= mag) is > i."""
    nthr = thr.shape[0]
    order = jnp.argsort(thr)
    ts = jnp.take(thr, order)
    bucket = jnp.searchsorted(ts, mag, side="right")  # #{ts <= mag_j}
    hist = jnp.zeros((nthr + 1,), jnp.int32).at[bucket].add(1)
    ge = jnp.cumsum(hist[::-1])[::-1]  # ge[i] = #{bucket >= i}
    counts_sorted = ge[1:]  # threshold i (sorted) needs bucket >= i+1
    return jnp.zeros((nthr,), jnp.int32).at[order].set(counts_sorted)


def threshold_topk_abs(x: Array, k: int, count_fn=None) -> Tuple[Array, Array]:
    """Magnitude top-k by threshold multisection + compaction ("threshold-
    estimate + compact", SURVEY.md §2 native-obligations table).

    Algorithm (all shape-static, 4 + ~3 passes over x):
      1. tau search: maintain a bracket [lo, hi] with count(|x| >= lo) >= k;
         4 rounds of 8-way geometric multisection (counts via `count_fn` —
         one fused Pallas pass per round on TPU, see ops.pallas_topk).
      2. compact every element with |x| >= lo into `cap` slots by cumsum +
         scatter (cap = max(2k, k + 4096)).
      3. one exact `lax.top_k` over the <= cap candidates.

    Exact whenever the survivor count fits in `cap` — always, in practice,
    after 4 refinement rounds on continuous-valued gradients (the bracket
    is ~0.4% wide). Degenerate distributions (k-th-magnitude value repeated
    beyond cap times, or k exceeding the number of nonzeros) fall back to
    index-order tie-breaking among boundary values, which error feedback
    absorbs (same tie-arbitrariness class as lax.top_k's index rule).
    """
    n = x.shape[0]
    if k >= n:
        return topk_abs(x, k)
    if count_fn is None:
        count_fn = bucketize_counts
    mag = jnp.abs(x)
    maxv = jnp.max(mag)
    lo = jnp.zeros((), x.dtype)
    hi = maxv
    for _ in range(4):
        lo_eff = jnp.maximum(lo, maxv * 1e-12 + 1e-30)
        r = (lo_eff / (hi + 1e-30)) ** (1.0 / 9.0)
        powers = jnp.arange(1, 9, dtype=x.dtype)
        thr = hi * r ** powers  # 8 candidates strictly inside (lo, hi)
        counts = count_fn(mag, thr)
        ge = counts >= k
        lo = jnp.maximum(lo, jnp.max(jnp.where(ge, thr, lo)))
        hi = jnp.minimum(hi, jnp.min(jnp.where(ge, hi, thr)))
    tau = lo
    cap = min(n, max(2 * k, k + 4096))
    selected = mag >= tau
    pos = jnp.cumsum(selected.astype(jnp.int32)) - 1
    slot = jnp.where(selected, pos, cap)  # cap = dropped (mode='drop')
    buf_v = jnp.zeros((cap,), x.dtype).at[slot].set(x, mode="drop")
    buf_i = jnp.full((cap,), n, SENTINEL_DTYPE).at[slot].set(
        jnp.arange(n, dtype=SENTINEL_DTYPE), mode="drop"
    )
    _, sel = lax.top_k(jnp.abs(buf_v), k)
    return jnp.take(buf_v, sel), jnp.take(buf_i, sel)


def simrecall_topk_abs(x: Array, k: int,
                       recall: float = 0.95) -> Tuple[Array, Array]:
    """CPU-runnable pessimistic model of `lax.approx_max_k` selection.

    Purpose (round-4 verdict missing #2): the production `auto` policy
    routes every model above AUTO_APPROX_THRESHOLD params through
    `approx_max_k` at recall_target=0.95, but its convergence impact
    cannot be measured on the CPU backend — XLA lowers approx_max_k to an
    EXACT top-k there, so every CPU convergence artifact silently tested
    exact selection. This selector simulates the approximation in a way
    that is exact-backend-independent: take the exact top-(k+pad), drop
    each of the true top-k elements independently with probability
    1-recall, and backfill the freed slots from ranks k..k+pad in rank
    order.

    Pessimism argument: approx_max_k's recall_target is a lower-bound
    target (measured recall is typically above it) and its misses are
    biased toward the SMALLEST magnitudes in the set (they fall off the
    bitonic reduction's per-lane maxima); here misses hit every rank —
    including the largest — uniformly at rate 1-recall, and replacements
    come from strictly lower ranks. A convergence result that survives
    this selector bounds the real approx path from below.

    Determinism: the drop pattern is seeded from the DATA (bitcasts of
    sum(x) AND sum(|x|) folded into a fixed key — the second statistic
    breaks the sign-symmetric collisions the first is blind to), so
    identical-seed A/B runs reproduce exactly, while the dropped set
    still varies step to step as the gradient changes — mirroring how
    approx_max_k's misses depend on the value layout. Degenerate edge: if more than `pad` of the top-k are
    dropped, the tail of the result re-admits dropped elements (sorted
    after the backfill ranks) — slightly less pessimistic there, and only
    relevant at k below ~100 where pad saturates its floor.
    """
    n = x.shape[0]
    pad = max(16, int(math.ceil(k * (1.0 - recall) * 4)))
    m = min(n, k + pad)
    vals, idx = topk_abs(x, m)  # exact top-m, descending |value|
    key = jax.random.fold_in(
        jax.random.PRNGKey(0x51AEC),
        lax.bitcast_convert_type(
            jnp.sum(x, dtype=jnp.float32), jnp.int32),
    )
    # Second statistic: sum(x) alone is blind to sign-symmetric changes
    # (any rearrangement or sign flip preserving the sum replays the same
    # drop pattern); sum(|x|) breaks that degeneracy, and cancellation-
    # heavy gradients keep a near-constant sum(x) while |x| mass moves.
    key = jax.random.fold_in(
        key,
        lax.bitcast_convert_type(
            jnp.sum(jnp.abs(x), dtype=jnp.float32), jnp.int32),
    )
    ranks = jnp.arange(m, dtype=jnp.int32)
    dropped = (ranks < k) & (jax.random.uniform(key, (m,)) > recall)
    # Survivors keep their rank as sort key; dropped ranks sort last, so
    # the first k slots are survivors followed by backfill ranks k..m.
    order = jnp.where(dropped, m + ranks, ranks)
    _, out_val, out_idx = lax.sort((order, vals, idx), num_keys=1,
                                   is_stable=True)
    return out_val[:k], out_idx[:k]


# Stage-1 bucket count target: L ~= TWOSTAGE_OVERSAMPLE * k buckets. With
# top-1-per-bucket selection over a random placement, the expected recall
# is ~= 1 - (k-1)/(2L) (a true top-k element is only lost to a LARGER
# element sharing its bucket, and ranks above it are uniform over buckets)
# -> ~0.97 at oversample 16, comfortably above the 0.95 audit floor.
TWOSTAGE_OVERSAMPLE = 16


def _twostage_pallas_groups(n: int, k: int, oversample: int
                            ) -> Optional[int]:
    """Row-groups per (BLOCK_ROWS, 128) tile for the Pallas stage-1 pass,
    or None when no group count the chip's compiler accepts yields k
    candidates (k above ~half the padded length; the caller then takes
    the XLA stage 1).

    Miss probability is governed by the bucket SIZE (rpg = BLOCK_ROWS /
    groups elements per bucket), not the raw bucket count: tail padding
    inflates L without shrinking the buckets real elements live in. Keep
    rpg <= n/(oversample*k) so expected misses stay ~k/(2*oversample)
    (padding-heavy buckets only get safer). Power-of-two divisor of
    BLOCK_ROWS, clamped to [MIN_GROUPS, MAX_GROUPS] — the range the TPU
    lowering compiles (pallas_topk.py). The lower clamp only adds
    buckets, so the recall bound holds with room; at the upper clamp a
    bucket still holds two elements, so very high densities and tiny
    leaves stay approximate where an unclamped count would have made
    every element its own bucket."""
    from gtopkssgd_tpu.ops.pallas_topk import (
        _BLOCK, _LANES, BLOCK_ROWS, MAX_GROUPS, MIN_GROUPS)

    nblocks = max(1, -(-n // _BLOCK))
    target_rpg = max(1, n // max(1, oversample * k))
    g = MIN_GROUPS
    while BLOCK_ROWS // g > target_rpg and g < MAX_GROUPS:
        g *= 2
    while nblocks * g * _LANES < k and g < MAX_GROUPS:
        g *= 2
    return g if nblocks * g * _LANES >= k else None


def _twostage_candidates(
    x: Array,
    k: int,
    *,
    residual: Optional[Array] = None,
    oversample: int = TWOSTAGE_OVERSAMPLE,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array]:
    """Stage 1 of the two-stage select: per-bucket max-|acc| candidates
    (cand_val f32[L], cand_idx i32[L]) with acc = x (+ residual), L >= k.
    Candidate indices >= n mark padding buckets (value 0)."""
    n = x.shape[0]
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    groups = (_twostage_pallas_groups(n, k, oversample)
              if use_pallas or interpret else None)
    if groups is not None:
        from gtopkssgd_tpu.ops.pallas_topk import fused_stage1_candidates

        interp = (jax.default_backend() != "tpu"
                  if interpret is None else interpret)
        cand_val, cand_idx, _ = fused_stage1_candidates(
            x, residual=residual, groups=groups, interpret=interp)
        return cand_val, cand_idx
    # XLA reference: reshape to (b, L) so bucket j holds flat indices
    # {j, L+j, 2L+j, ...} — the stride-L interleave decorrelates
    # contiguous layer slices — and take one argmax per column. Same
    # bucket-top-1 semantics as the kernel, different bucket membership.
    acc = x if residual is None else x + residual
    L = max(k, min(n, oversample * k))
    b = -(-n // L)
    accp = jnp.pad(acc, (0, b * L - n))
    mat = accp.reshape(b, L)
    rows = jnp.arange(b, dtype=SENTINEL_DTYPE)[:, None]
    cols = jnp.arange(L, dtype=SENTINEL_DTYPE)[None, :]
    mag = jnp.where(rows * L + cols < n, jnp.abs(mat), -1.0)
    win = jnp.argmax(mag, axis=0)  # first max row: deterministic ties
    cand_idx = (win.astype(SENTINEL_DTYPE) * L
                + jnp.arange(L, dtype=SENTINEL_DTYPE))
    cand_val = jnp.take_along_axis(mat, win[None, :], axis=0)[0]
    return cand_val, cand_idx


def twostage_topk_abs(
    x: Array,
    k: int,
    *,
    residual: Optional[Array] = None,
    oversample: int = TWOSTAGE_OVERSAMPLE,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array]:
    """Generalized two-stage approximate magnitude top-k (arXiv:2506.04165
    lineage; the gTop-k-ready variant of `blockwise_topk_abs`).

    Stage 1 reads x ONCE and keeps only each bucket's max-|acc| element
    (L ~= oversample*k buckets); stage 2 exactly reselects the top-k of
    the <= L candidates. Unlike `blockwise_topk_abs` (per-block top-k,
    exact, but a large `lax.top_k` per block), stage 1 here is a pure
    max/argmax reduction — on TPU it runs as the fused Pallas kernel
    (ops.pallas_topk.fused_stage1_candidates) which also folds the
    error-feedback accumulate `x + residual` into the same HBM pass, so
    the flat [N] accumulator is never materialized.

    Approximation: a true top-k element is missed only when a LARGER
    element shares its bucket — expected recall ~= 1 - (k-1)/(2L)
    (~0.97 at the default oversample). Error feedback absorbs misses
    (arXiv:1911.08772), the same argument that admits `approx`.

    `residual`, when given, is added to x INSIDE the selection pass;
    returned values are read from acc = x + residual.
    """
    n = x.shape[0]
    if k >= n:
        acc = x if residual is None else x + residual
        vals, idx = topk_abs(acc, n)
        if k > n:
            vals = jnp.pad(vals, (0, k - n))
            idx = jnp.pad(idx, (0, k - n), constant_values=n)
        return vals, idx
    cand_val, cand_idx = _twostage_candidates(
        x, k, residual=residual, oversample=oversample,
        use_pallas=use_pallas, interpret=interpret)
    _, sel = lax.top_k(jnp.abs(cand_val), k)
    idx = jnp.take(cand_idx, sel)
    vals = jnp.take(cand_val, sel)
    oob = idx >= n
    idx = jnp.where(oob, n, idx).astype(SENTINEL_DTYPE)
    vals = jnp.where(oob, 0.0, vals)
    return vals, idx


def _threshold_tau(x: Array, k: int, count_fn=None) -> Array:
    """tau for the threshold family without building an index set: the
    same multisection bracket as `threshold_topk_abs`, then compact the
    surviving MAGNITUDES (no values, no indices, no gather) and read the
    k-th largest. Degenerate tie behavior (survivors > cap) matches
    threshold_topk_abs by construction — same bracket, same cap."""
    n = x.shape[0]
    mag = jnp.abs(x)
    if k >= n:
        return jnp.min(mag)
    if count_fn is None:
        count_fn = bucketize_counts
    maxv = jnp.max(mag)
    lo = jnp.zeros((), x.dtype)
    hi = maxv
    for _ in range(4):
        lo_eff = jnp.maximum(lo, maxv * 1e-12 + 1e-30)
        r = (lo_eff / (hi + 1e-30)) ** (1.0 / 9.0)
        powers = jnp.arange(1, 9, dtype=x.dtype)
        thr = hi * r ** powers
        counts = count_fn(mag, thr)
        ge = counts >= k
        lo = jnp.maximum(lo, jnp.max(jnp.where(ge, thr, lo)))
        hi = jnp.minimum(hi, jnp.min(jnp.where(ge, hi, thr)))
    cap = min(n, max(2 * k, k + 4096))
    selected = mag >= lo
    pos = jnp.cumsum(selected.astype(jnp.int32)) - 1
    slot = jnp.where(selected, pos, cap)
    buf_m = jnp.zeros((cap,), x.dtype).at[slot].set(mag, mode="drop")
    return lax.top_k(buf_m, k)[0][k - 1]


def select_tau(
    x: Array,
    k: int,
    method: str = "auto",
    *,
    residual: Optional[Array] = None,
) -> Array:
    """The selection threshold tau — the smallest magnitude the configured
    kernel would select — WITHOUT materializing a k-sized (vals, idx) set
    or gathering values. Threshold-mask consumers (TopKCompressor.
    compress_by_threshold, the p=1 paths in optimizer.py) build their
    keep mask as |acc| >= tau directly from this scalar.

    Per method, tau equals min(|vals|) of the (vals, idx) set the
    corresponding `select_topk` would return — the existing mask
    semantics (boundary ties all pass; for approximate kernels the mask
    is a superset of the index set, recall >= the kernel's) carry over
    unchanged. For `twostage`, tau is the k-th largest CANDIDATE
    magnitude, which is >= the value of overall rank k+misses, so the
    mask |acc| >= tau still contains every candidate the two-stage
    reselect would keep.

    `residual`, when given, is the error-feedback residual: tau is
    computed over acc = x + residual (fused into the stage-1/counting
    kernel pass for twostage/pallas; folded by XLA otherwise).
    """
    n = x.shape[0]
    if method == "auto":
        method = _resolve_auto(n)
    if method == "twostage":
        if k >= n:
            acc = x if residual is None else x + residual
            return jnp.min(jnp.abs(acc))
        cand_val, _ = _twostage_candidates(x, k, residual=residual)
        return lax.top_k(jnp.abs(cand_val), k)[0][k - 1]
    acc = x if residual is None else x + residual
    if k >= n:
        return jnp.min(jnp.abs(acc))
    if method == "exact":
        return lax.top_k(jnp.abs(acc), k)[0][k - 1]
    if method == "approx":
        vals, _ = lax.approx_max_k(jnp.abs(acc), k, recall_target=0.95)
        return jnp.min(vals)
    if method == "blockwise":
        num_blocks = max(1, n // 65536)
        block = -(-n // num_blocks)
        kb = min(k, block)
        mag = jnp.abs(jnp.pad(acc, (0, block * num_blocks - n)))
        cand = lax.top_k(mag.reshape(num_blocks, block), kb)[0]
        return lax.top_k(cand.reshape(-1), k)[0][k - 1]
    if method == "threshold":
        return _threshold_tau(acc, k)
    if method == "pallas":
        from gtopkssgd_tpu.ops.pallas_topk import (
            fused_multi_threshold_count,
        )

        interp = jax.default_backend() != "tpu"
        # The count rounds read grad (+ residual) through the fused
        # kernel; only the final compaction touches the folded acc.
        count_fn = lambda _mag, thr: fused_multi_threshold_count(
            x, thr, residual, interpret=interp)
        return _threshold_tau(acc, k, count_fn=count_fn)
    if method == "simrecall":
        vals, _ = simrecall_topk_abs(acc, k)
        return jnp.min(jnp.abs(vals))
    raise ValueError(f"unknown topk method {method!r}")


APPROX_RECALL = 0.95


def approx_bin_size(n: int, k: int, recall_target: float = APPROX_RECALL
                    ) -> int:
    """2^r, the bin whose maxima XLA's `approx_max_k` keeps of an [n]
    operand on the TPU before it sorts them (its recall formula,
    ApproxTopKReductionOutputSize at rank 1: m = max((1 - k) / ln(recall),
    1024) outputs are needed, r = floor(log2(n / m))): 32 at rho = 0.001.
    1 where n is too short to reduce."""
    m = max(int((1.0 - k) / math.log(recall_target)), 1024)
    return 1 << max(0, (n // min(m, n)).bit_length() - 1)


LANES = 128


def bin_maxima(mag: Array, group: int) -> Array:
    """Flat maxima over bins of `group` elements of a leaf's magnitudes in
    the leaf's own shape. [..., C] is read as [rows, C] and cut into `group`
    slabs of rows/group consecutive rows; a bin holds one element of each
    slab, slab s's taken s whole lane tiles further along the row: the
    elements (s * rows/group + i, (j + 128 s) mod C). So a bin's members lie
    in `group` rows far apart and in `group` columns apart, as
    `approx_max_k`'s windows over a flat vector do, and a gradient's hot
    rows and hot columns (it is a sum of outer products) fall into bins of
    their own: bins of consecutive rows of one column sent 2.5-3.8 k on
    the chip where these, and the flat windows, send about 2 k (PERF.md
    section 6, PR 42). The rows past the last whole slab are one more bin
    a column. On the TPU a slab is whole tiles and a shift of whole lane
    tiles moves no lane, so the maximum runs where the leaf lies. A vector
    is read as rows of 128."""
    if mag.ndim < 2:
        mag = jnp.pad(mag.reshape(-1), (0, -mag.size % LANES))
        mag = mag.reshape(-1, LANES)
    cols = mag.shape[-1]
    mag = mag.reshape(-1, cols)
    rows = mag.shape[0]
    per = rows // group
    parts = []
    if per:
        best = mag[:per]
        for s in range(1, group):
            best = jnp.maximum(best, jnp.roll(
                mag[s * per:(s + 1) * per], -(LANES * s) % cols, axis=-1))
        parts.append(best.reshape(-1))
    if rows > per * group:
        parts.append(jnp.max(mag[per * group:], axis=0))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _kth_largest(parts, k: int) -> Array:
    """The k-th largest of the concatenated candidate vectors."""
    cand = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return lax.top_k(cand, k)[0][k - 1]


def _exact_tau_leaves(mags, k: int) -> Array:
    """The exact k-th largest magnitude over all leaves with no operand of
    their summed size: every member of the global top k is among its own
    leaf's top min(k, n_l)."""
    return _kth_largest(
        [lax.top_k(m.reshape(-1), min(k, m.size))[0] for m in mags], k)


def _binned_tau_leaves(mags, k: int, group: int) -> Array:
    """The k-th largest of the leaves' bin maxima: `approx_max_k`'s rule
    (maxima of bins of `group`, then the exact k-th of those by a sort of
    the values alone), with the bins laid along each leaf's rows and not
    along one [N] vector."""
    parts = [bin_maxima(m, group) for m in mags]
    total = sum(p.size for p in parts)
    if total < k:
        return _exact_tau_leaves(mags, k)
    cand = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    # Magnitudes are no less than +0, where a float32's order is its bit
    # pattern's as an integer: the sort's comparator is one integer
    # compare, not the total order over floats that lax.sort builds (the
    # chip sorts 14.3M candidates in 10.9 ms so and in 20.6 ms otherwise:
    # PERF.md section 6, PR 42).
    bits = lax.sort(lax.bitcast_convert_type(cand, jnp.int32),
                    is_stable=False)
    return lax.bitcast_convert_type(bits[total - k], jnp.float32)


def select_tau_leaves(accs, k: int, method: str = "auto") -> Array:
    """`select_tau` for an accumulator that exists only as leaves (a list
    of arrays of any shapes, N elements together): the same threshold rule
    with no [N] operand for `exact` and `approx` (so for `auto`, which
    reads N as `select_tau` does).

    `exact` is the exact k-th magnitude over all leaves. `approx` keeps
    `approx_max_k`'s recall rule on the TPU, bins of `approx_bin_size(N,
    k)` whose maxima are the candidates and the k-th largest candidate tau,
    and is the exact search on every other backend, where XLA lowers
    `approx_max_k` itself to a sort: there tau is `select_tau`'s to the
    bit. The branch is taken when the step is lowered
    (`lax.platform_dependent`), so a step compiled for a described chip
    holds the chip's form. Every other method concatenates the leaves and
    is `select_tau`'s own."""
    n = sum(int(a.size) for a in accs)
    if method == "auto":
        method = _resolve_auto(n)
    mags = [jnp.abs(a) for a in accs]
    if k >= n:
        return functools.reduce(jnp.minimum, [jnp.min(m) for m in mags])
    if method == "exact":
        return _exact_tau_leaves(mags, k)
    if method == "approx":
        group = approx_bin_size(n, k)
        return lax.platform_dependent(
            *mags,
            tpu=lambda *ms: _binned_tau_leaves(ms, k, group),
            default=lambda *ms: _exact_tau_leaves(ms, k))
    return select_tau(
        jnp.concatenate([a.reshape(-1) for a in accs]), k, method)


_METHODS = {
    "exact": lambda x, k: topk_abs(x, k),
    "blockwise": lambda x, k: blockwise_topk_abs(x, k),
    "approx": lambda x, k: approx_topk_abs(x, k),
    "threshold": lambda x, k: threshold_topk_abs(x, k),
    "simrecall": lambda x, k: simrecall_topk_abs(x, k),
    "twostage": lambda x, k: twostage_topk_abs(x, k),
}

# Above this N, "auto" switches from exact lax.top_k to an approximate
# kernel. Measured on the real TPU v5e chip (benchmarks/results/
# topk_bench_TPU_v5_lite.json; regenerate with
# `python benchmarks/topk_bench.py` on hardware — the committed rows
# predate the twostage kernel, which has no on-chip column yet):
#
#     N      rho    exact    blockwise  threshold  approx   pallas
#     272k   0.001  0.40 ms   0.37 ms    3.25 ms   0.16 ms  3.26 ms
#     25.6M  0.001  75.4 ms  144.1 ms  319.0 ms    1.27 ms  309 ms
#     61M    0.001  196  ms  952   ms  736   ms    3.32 ms  736 ms
#
# exact is fine at CIFAR scale but catastrophic at ImageNet scale (75 ms
# against a 60 ms ResNet-50 train step); approx_max_k (the TPU-native
# bitonic partial reduction, arXiv:2206.14286) is ~60x faster at the sizes
# that matter. Its recall_target=0.95 slightly changes which elements are
# selected — safe here because error feedback keeps every missed element
# in the residual for the next step (the same argument that justifies
# top-k sparsification itself, arXiv:1911.08772), and the gtopk tree merge
# (merge_sparse_sets) stays EXACT, so replicas remain in lockstep. Force
# --topk-method exact to reproduce the reference's exact-selection
# semantics at any size.
#
# `twostage` targets the same >AUTO_APPROX_THRESHOLD regime as approx but
# additionally fuses the error-feedback accumulate into its single
# stage-1 pass and feeds the tau-only path (select_tau) — the properties
# the p=1 threshold-mask pipeline needs. GTOPK_AUTO_TWOSTAGE=1 makes
# `auto` prefer it over approx at large N; flip the default only with
# fresh on-chip twostage rows from benchmarks/topk_bench.py.
AUTO_APPROX_THRESHOLD = 1 << 20
AUTO_TWOSTAGE = os.environ.get("GTOPK_AUTO_TWOSTAGE", "") == "1"


def _resolve_auto(n: int) -> str:
    """The `auto` policy, shared by select_topk and select_tau."""
    if n <= AUTO_APPROX_THRESHOLD:
        return "exact"
    return "twostage" if AUTO_TWOSTAGE else "approx"


def select_topk(
    x: Array,
    k: int,
    method: str = "auto",
    *,
    residual: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """Dispatch on top-k strategy.

    "auto" picks exact `lax.top_k` for small N (cost is noise there) and
    an approximate kernel above AUTO_APPROX_THRESHOLD — see the measured
    table above; do not change the policy without re-running
    benchmarks/topk_bench.py on hardware.

    `residual`, when given, selects over acc = x + residual; the
    `twostage` method folds the add into its fused stage-1 pass (the
    accumulator is never materialized), every other method folds it in
    XLA before selecting. Returned values are read from acc either way.
    """
    if method == "auto":
        method = _resolve_auto(x.shape[0])
    if method == "twostage":
        return twostage_topk_abs(x, k, residual=residual)
    if residual is not None:
        x = x + residual
    if method == "pallas":
        from gtopkssgd_tpu.ops.pallas_topk import pallas_topk_abs

        return pallas_topk_abs(x, k, interpret=jax.default_backend() != "tpu")
    try:
        fn = _METHODS[method]
    except KeyError:
        raise ValueError(f"unknown topk method {method!r}") from None
    return fn(x, k)


def merge_sparse_sets(
    vals_a: Array,
    idx_a: Array,
    vals_b: Array,
    idx_b: Array,
    k: int,
    n: int,
) -> Tuple[Array, Array]:
    """Sparse-sum two unique-index sets and reselect the top-k by magnitude.

    This is one round of the gTop-k tree (allreducer.py::gtopk_sparse_allreduce
    in the reference, Algorithm 2 of arXiv:1901.04359): concatenate the two
    (value, index) lists, sum duplicated indices, take top-k of the <=2k
    candidates.  Both partners of a `ppermute` exchange call this on the same
    multiset (in different concatenation order), and the result is
    order-canonical, so all devices stay in lockstep without a re-broadcast:

      * pairs are sorted by index, so slot layout is order-independent;
      * duplicate (real) indices appear at most twice because each input set
        has unique real indices; the pair is summed into its first slot and
        the second slot is voided to the sentinel. Sentinel (padding) slots
        may repeat more than twice but always carry value 0, so the
        run-length-2 assumption only ever drops zeros;
      * the final `lax.top_k` then sees identical (value, index) arrays on
        both partners and its tie-breaking is deterministic.

    Returns (values, indices) of the merged set, descending by |value|.

    Implementation note (measured on TPU v5e — the committed artifact is
    benchmarks/results/merge_bench_TPU_v5_lite.json, `merge` vs
    `merge_argsort_topk` rows): both stages are multi-operand `lax.sort`
    calls that carry the payload through the sort instead of `argsort` +
    `jnp.take` — gathers are the slow path on TPU, and even the final
    k-selection is faster as a carried sort over the 2k candidates than
    as `lax.top_k` + two takes at large k. Per round: 1.27 -> 0.18 ms at
    k=25.6e3 (ResNet-50 rho=0.001), 11.5 -> 1.7 ms at k=2.6e5, 2.7 ->
    0.37 ms at k=61e3 (VGG-16) — 5-7x at ImageNet-scale N. At CIFAR
    scale (k<=2.7e3) both formulations sit at 0.12-0.16 ms and the
    difference is below relevance either way. Stage-2 tie-breaking on
    equal |value| is stable over the stage-1 canonical (index-sorted)
    order, i.e. lowest-index-first — the same rule `lax.top_k` applies,
    so determinism across partners is unchanged.
    """
    cat_idx = jnp.concatenate([idx_a, idx_b])
    cat_val = jnp.concatenate([vals_a, vals_b])
    # Canonical order: sort by index, values carried through the sort;
    # equal (duplicate) indices become adjacent.
    si, sv = lax.sort((cat_idx, cat_val), num_keys=1, is_stable=True)
    dup = jnp.concatenate([jnp.zeros((1,), bool), si[1:] == si[:-1]])
    next_dup = jnp.concatenate([dup[1:], jnp.zeros((1,), bool)])
    summed = sv + jnp.where(next_dup, jnp.roll(sv, -1), 0.0)
    merged_val = jnp.where(dup, 0.0, summed)
    merged_idx = jnp.where(dup, n, si).astype(SENTINEL_DTYPE)
    # Reselect: ascending sort on -|value| with (value, index) carried,
    # then keep the first k.
    _, out_val, out_idx = lax.sort(
        (-jnp.abs(merged_val), merged_val, merged_idx),
        num_keys=1, is_stable=True,
    )
    return out_val[:k], out_idx[:k]


@jax.named_scope("gtopk/mask")
def scatter_add_dense(n: int, idx: Array, vals: Array, dtype=jnp.float32) -> Array:
    """Densify a sparse set: zeros(n).at[idx].add(vals), dropping sentinel
    slots (idx == n falls out of range and `mode='drop'` ignores it)."""
    return jnp.zeros((n,), dtype).at[idx].add(vals.astype(dtype), mode="drop")


def membership_mask(query_idx: Array, set_idx: Array) -> Array:
    """bool[len(query_idx)]: is each query index present in `set_idx`?

    Used for the error-feedback repair step: values selected locally but
    rejected globally go back into the residual (`add_residuals` in the
    reference compressor). Sentinel queries (== n) report membership iff the
    set also carries the sentinel, but callers always mask by value anyway.
    """
    sorted_set = jnp.sort(set_idx)
    pos = jnp.searchsorted(sorted_set, query_idx)
    pos = jnp.clip(pos, 0, set_idx.shape[0] - 1)
    return jnp.take(sorted_set, pos) == query_idx
