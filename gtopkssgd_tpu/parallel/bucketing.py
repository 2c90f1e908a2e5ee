"""Byte-balanced gradient bucketing for the layerwise path.

The committed DCN probe fit (parallel/fits/dcn_probe_*.json) puts
alpha — the per-collective latency term — at ~22 ms, three orders of
magnitude above the per-byte term at realistic rho. Any schedule that
issues one sparse merge per leaf therefore pays L alpha terms per step
where a single concatenated merge pays one. This module computes the
partition of the param leaves into B contiguous buckets that minimizes
the alpha-beta merge cost EXACTLY:

    cost(partition) = sum over buckets b of
        rounds(p, schedule) * alpha_ms                 (latency)
        + comm_bytes(n_b, k_b) / beta                  (volume)

where ``k_b = ceil(density * n_b)`` (the per-bucket k split proportional
to leaf sizes — at B=L it reproduces today's per-leaf quotas, at B=1 it
reproduces the flat mode's global k) and ``comm_bytes`` is the SAME
codec-aware model the ledger prices the wire with
(parallel.collectives.comm_bytes_per_step), so the planner cannot drift
from what the step actually ships. The bandwidth term is not constant in
B: a lossy codec's index words shrink with the bucket-local index space
(Elias-Fano high/low split — parallel.codec), so splitting buys index
bits while costing alpha; the DP resolves that trade exactly.

Bucket indices are BUCKET-LOCAL: each bucket's concatenated operand is
its own [n_b] index space, and every bucket runs the unchanged
codec-framed gTop-k merge (tree or balanced) over its own (vals, idx)
set. The optimizer scatters the reduced update and the error-feedback
residual back to leaves through the static bucket offsets.

Spec grammar (``--buckets``):

    concat   historical default: per-leaf selection, ONE concatenated
             merge over the global index space — today's layerwise wire,
             byte-identical, untouched code path. No BucketPlan exists.
    leaf     B = L: per-leaf selection AND one merge per leaf (the
             fully-layerwise end of the axis the DP interpolates).
    <int>    pinned bucket count B; boundaries still DP-optimal.
    auto     the DP chooses boundaries AND B (cost-minimal over all
             contiguous partitions); ties break toward the historical
             per-leaf end (larger B), so `auto` only coarsens when the
             measured alpha actually pays for it.

The partition DP is O(L^2) states x O(L) transitions — microseconds for
real models (L ~ 10^2) and run once at trace time, host-side.

Pipeline axis (``--pipeline``, PR 15): under the historical ``serial``
execution order a step pays sum_b (T_select_b + T_merge_b), and an
extra bucket can only add alpha — which is why the serial DP honestly
collapses `auto` to B=1. Under ``overlap`` bucket b+1's selection runs
while bucket b's merge rounds are in flight, so the exposed span is the
pipelined

    T_select_1 + sum_{j=2..B} max(T_select_j, T_merge_{j-1}) + T_merge_B

(first select is the fill, last merge the drain). The DP cannot
optimize that non-additive span exactly, so under overlap pricing it
minimizes the additive per-stage surrogate sum_b max(T_select_b,
T_merge_b) — the standard software-pipeline relaxation, exact when
stages are balanced — and `pipeline_span_ms` reports the true span for
the chosen partition. Selection is priced linearly
(`select_cost_ms`), so under SERIAL pricing the select term is
partition-independent and the serial DP objective is unchanged from
PR 11.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, Optional, Sequence, Tuple

from ..ops import k_for_density
from .collectives import comm_bytes_per_step, tree_rounds

BUCKETS_DEFAULT = "concat"

# Specs that are words, not counts. Anything else must parse as int >= 1.
_WORD_SPECS = ("concat", "leaf", "auto")

PIPELINE_DEFAULT = "serial"

# --pipeline spec grammar: the two execution orders (modes.PIPELINES)
# plus 'auto', which prices both and keeps the cheaper modeled span.
_PIPELINE_SPECS = ("serial", "overlap", "auto")

# Modeled per-element cost of the fused two-stage selection, in ms per
# 1e6 elements. This is a MODELED constant, not a fit: selection is a
# local bitonic/threshold pass whose throughput is device-bound, and
# one ms per Melem sits in the measured band of the fused-variants
# bench (benchmarks/results/fused_variants_*.json) without pretending
# per-device precision. Linearity is the load-bearing property — it
# makes sum_b select_cost_ms(n_b) independent of the partition, so the
# serial DP objective (merge cost only) stays exact.
SELECT_GAMMA_MS_PER_MELEM = 1.0


def parse_pipeline(spec) -> str:
    """Normalize a --pipeline spec: 'serial' | 'overlap' | 'auto'.

    Raises ValueError on anything else — at build time, not inside the
    jitted step."""
    if isinstance(spec, str):
        word = spec.strip().lower()
        if word in _PIPELINE_SPECS:
            return word
    raise ValueError(
        f"invalid --pipeline spec {spec!r}; grammar: serial | overlap | auto")


def select_cost_ms(n_elems: int) -> float:
    """Modeled ms of one bucket's fused two-stage selection (top-k over
    an [n_b] operand). Linear in n_b by design — see
    SELECT_GAMMA_MS_PER_MELEM."""
    return SELECT_GAMMA_MS_PER_MELEM * float(n_elems) / 1e6


def parse_buckets(spec) -> object:
    """Normalize a --buckets spec: 'concat' | 'leaf' | 'auto' | int B.

    Accepts the string grammar (CLI) or a bare int (programmatic).
    Raises ValueError on anything else — at build time, not inside the
    jitted step.
    """
    if isinstance(spec, bool):  # bool is an int subclass; reject explicitly
        raise ValueError(f"invalid --buckets spec {spec!r}")
    if isinstance(spec, int):
        if spec < 1:
            raise ValueError(f"--buckets count must be >= 1, got {spec}")
        return spec
    if isinstance(spec, str):
        word = spec.strip().lower()
        if word in _WORD_SPECS:
            return word
        try:
            count = int(word)
        except ValueError:
            raise ValueError(
                f"invalid --buckets spec {spec!r}; grammar: "
                "concat | leaf | auto | <int B>") from None
        if count < 1:
            raise ValueError(f"--buckets count must be >= 1, got {count}")
        return count
    raise ValueError(f"invalid --buckets spec {spec!r}")


def buckets_key(spec) -> str:
    """Canonical hashable form of a spec ('concat'/'leaf'/'auto'/'b{B}') —
    the planner-cache and CommPlan.bucketing key."""
    parsed = parse_buckets(spec)
    return parsed if isinstance(parsed, str) else f"b{parsed}"


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """An ordered partition of the param leaves into contiguous buckets.

    ``boundaries`` are B+1 cut points in LEAF index space
    (boundaries[0] == 0, boundaries[-1] == L): bucket b covers leaves
    ``boundaries[b]:boundaries[b+1]``. ``leaf_sizes`` is the flat element
    count of every leaf (jax.tree flatten order — the same order the
    layerwise residual tuple uses), ``ks`` the per-bucket wire k.
    """

    boundaries: Tuple[int, ...]
    leaf_sizes: Tuple[int, ...]
    ks: Tuple[int, ...]
    spec: str = "auto"
    # Resolved execution order (modes.PIPELINES) — never the 'auto'
    # spec word; plan_buckets resolves that before constructing a plan.
    pipeline: str = PIPELINE_DEFAULT

    def __post_init__(self):
        L = len(self.leaf_sizes)
        b = self.boundaries
        if (len(b) < 2 or b[0] != 0 or b[-1] != L
                or any(b[i] >= b[i + 1] for i in range(len(b) - 1))):
            raise ValueError(
                f"boundaries {b} is not a partition of {L} leaves")
        if len(self.ks) != len(b) - 1:
            raise ValueError(
                f"{len(self.ks)} ks for {len(b) - 1} buckets")
        if self.pipeline not in ("serial", "overlap"):
            raise ValueError(
                f"BucketPlan.pipeline must be a resolved execution order "
                f"(serial|overlap), got {self.pipeline!r}")

    @property
    def n_buckets(self) -> int:
        return len(self.boundaries) - 1

    @property
    def sizes(self) -> Tuple[int, ...]:
        """Element count n_b of every bucket's concatenated operand."""
        return tuple(
            sum(self.leaf_sizes[lo:hi])
            for lo, hi in zip(self.boundaries, self.boundaries[1:]))

    @property
    def k_total(self) -> int:
        return sum(self.ks)

    def leaf_range(self, b: int) -> Tuple[int, int]:
        """(lo, hi) leaf-index range of bucket b."""
        return self.boundaries[b], self.boundaries[b + 1]

    def pairs(self) -> Tuple[Tuple[int, int], ...]:
        """((n_b, k_b), ...) — the shape every wire-cost model prices."""
        return tuple(zip(self.sizes, self.ks))

    def to_manifest(self) -> dict:
        """Manifest extras stamping the chosen partition into the run
        header (obs.manifest.run_manifest(**extra)); the ledger reads
        these back via _manifest_params to price the bucketed wire."""
        return {
            "buckets": self.spec,
            "bucket_boundaries": list(self.boundaries),
            "bucket_sizes": list(self.sizes),
            "bucket_ks": list(self.ks),
            "pipeline": self.pipeline,
        }

    @staticmethod
    def from_manifest(manifest: dict) -> Optional["BucketPlan"]:
        """Inverse of to_manifest (leaf_sizes are not stamped — the
        manifest partition is reconstructed at bucket granularity, which
        is all any consumer prices). None when the run was not bucketed."""
        bounds = manifest.get("bucket_boundaries")
        sizes = manifest.get("bucket_sizes")
        ks = manifest.get("bucket_ks")
        if not bounds or not sizes or not ks:
            return None
        # Bucket-granular reconstruction: each bucket becomes one "leaf"
        # of its summed size, boundaries renumbered 0..B.
        return BucketPlan(
            boundaries=tuple(range(len(sizes) + 1)),
            leaf_sizes=tuple(int(s) for s in sizes),
            ks=tuple(int(k) for k in ks),
            spec=str(manifest.get("buckets", "auto")),
            pipeline=str(manifest.get("pipeline", PIPELINE_DEFAULT)),
        )


def merge_rounds(p: int, schedule: Optional[str] = None) -> int:
    """Number of latency-bearing exchange rounds of ONE sparse merge —
    the multiplier on alpha. Mirrors comm_bytes_per_step's round
    structure: the tree pays tree_rounds(p) sequential hops, the
    balanced (Ok-Topk) schedule a p-1 scatter phase plus a p-1
    allgather phase (its rounds overlap destinations but are still
    serialized phases on the critical path)."""
    if p <= 1:
        return 0
    if schedule == "balanced":
        return 2 * (p - 1)
    return tree_rounds(p)


def bucket_cost_ms(n_b: int, k_b: int, *, p: int, codec="fp32",
                   schedule: Optional[str] = None,
                   alpha_ms: float, beta_gbps: float,
                   mode: str = "gtopk_layerwise") -> float:
    """Modeled ms of one bucket's merge: rounds * alpha + bytes / beta.

    Bytes come from the same comm_bytes_per_step model the ledger and
    telemetry use (codec- and schedule-aware), so the DP optimizes the
    quantity the ledger will later audit."""
    if p <= 1:
        return 0.0
    wire = comm_bytes_per_step(mode, n_b, k_b, p, codec=codec,
                               schedule=schedule)
    beta_bytes_per_ms = max(float(beta_gbps), 1e-9) * 1e9 / 1e3
    return merge_rounds(p, schedule) * float(alpha_ms) + wire / beta_bytes_per_ms


def stage_cost_ms(n_b: int, k_b: int, *, pipeline: str = PIPELINE_DEFAULT,
                  p: int, codec="fp32", schedule: Optional[str] = None,
                  alpha_ms: float, beta_gbps: float,
                  mode: str = "gtopk_layerwise") -> float:
    """The DP's per-bucket objective term under a given execution order.

    serial: the merge cost alone. Selection is priced linearly
    (select_cost_ms), so sum_b select_cost_ms(n_b) is the same for every
    partition and adding it could never change the argmin — the PR 11
    objective is preserved bit-for-bit.

    overlap: max(T_select_b, T_merge_b) — the additive pipeline
    surrogate. The true pipelined span (pipeline_span_ms) staggers
    select_j against merge_{j-1} and is not additive over buckets; the
    surrogate pairs each bucket's own two stages instead, which equals
    the true span (up to fill/drain) when stages are balanced — the
    standard software-pipeline relaxation that keeps the partition DP
    exact over the surrogate."""
    merge = bucket_cost_ms(n_b, k_b, p=p, codec=codec, schedule=schedule,
                           alpha_ms=alpha_ms, beta_gbps=beta_gbps, mode=mode)
    if pipeline == "overlap":
        return max(select_cost_ms(n_b), merge)
    return merge


def partition_cost_ms(plan: BucketPlan, *, p: int, codec="fp32",
                      schedule: Optional[str] = None,
                      alpha_ms: float, beta_gbps: float,
                      mode: str = "gtopk_layerwise",
                      pipeline: str = PIPELINE_DEFAULT) -> float:
    """Total modeled objective of a partition — additive over buckets,
    which is what makes the DP below exact. Under 'serial' this is the
    summed merge cost (the PR 11 objective); under 'overlap' the summed
    per-stage max (see stage_cost_ms)."""
    return sum(
        stage_cost_ms(n_b, k_b, pipeline=pipeline, p=p, codec=codec,
                      schedule=schedule, alpha_ms=alpha_ms,
                      beta_gbps=beta_gbps, mode=mode)
        for n_b, k_b in plan.pairs())


def pipeline_span_ms(plan: BucketPlan, *, p: int, codec="fp32",
                     schedule: Optional[str] = None, alpha_ms: float,
                     beta_gbps: float, mode: str = "gtopk_layerwise",
                     pipeline: Optional[str] = None) -> float:
    """True modeled wall-clock span of one step's select+merge chain
    under an execution order (defaults to the plan's own).

    serial:  sum_b (T_select_b + T_merge_b) — the paper's sequential sum.
    overlap: T_select_1 + sum_{j=2..B} max(T_select_j, T_merge_{j-1})
             + T_merge_B — select_1 is the pipeline fill (nothing to
             hide it under), merge_B the drain, and every interior step
             exposes whichever of the two concurrent stages is longer.

    This is the quantity `auto` pipeline resolution compares and the
    span `report plan` / merge_bench print; the DP optimizes the
    additive surrogate (stage_cost_ms) instead because this one is not
    additive over buckets."""
    pipe = plan.pipeline if pipeline is None else pipeline
    sel = [select_cost_ms(n_b) for n_b in plan.sizes]
    merge = [
        bucket_cost_ms(n_b, k_b, p=p, codec=codec, schedule=schedule,
                       alpha_ms=alpha_ms, beta_gbps=beta_gbps, mode=mode)
        for n_b, k_b in plan.pairs()]
    if pipe != "overlap":
        return sum(sel) + sum(merge)
    span = sel[0]
    for j in range(1, len(sel)):
        span += max(sel[j], merge[j - 1])
    return span + merge[-1]


def _leaf_boundaries(n_leaves: int) -> Tuple[int, ...]:
    return tuple(range(n_leaves + 1))


@functools.lru_cache(maxsize=64)
def _dp_tables(leaf_sizes: Tuple[int, ...], density: float, p: int,
               codec_name: str, schedule: Optional[str],
               alpha_ms: float, beta_gbps: float, mode: str,
               pipeline: str = PIPELINE_DEFAULT):
    """All-B partition DP over contiguous buckets.

    dp[b][i] = best (cost_ms, max_bucket_elems) of splitting the first i
    leaves into exactly b buckets; arg[b][i] the split point realizing
    it. The lexicographic value makes the cost-optimal partition also
    byte-balanced: among equal-cost partitions the one whose LARGEST
    bucket is smallest wins, which is the tie that matters when the
    codec makes cost insensitive to where a boundary falls.

    Returns (dp, arg, segcost) with segcost[(j, i)] the single-bucket
    cost of leaves j..i-1 (reused by report/bench pricing).
    """
    L = len(leaf_sizes)
    prefix = [0]
    for s in leaf_sizes:
        prefix.append(prefix[-1] + s)

    @functools.lru_cache(maxsize=None)
    def seg(j: int, i: int) -> Tuple[float, int]:
        n_b = prefix[i] - prefix[j]
        k_b = k_for_density(n_b, density)
        return (stage_cost_ms(n_b, k_b, pipeline=pipeline, p=p,
                              codec=codec_name, schedule=schedule,
                              alpha_ms=alpha_ms, beta_gbps=beta_gbps,
                              mode=mode), n_b)

    INF = (math.inf, 0)
    dp: List[List[Tuple[float, int]]] = [[INF] * (L + 1) for _ in range(L + 1)]
    arg: List[List[int]] = [[-1] * (L + 1) for _ in range(L + 1)]
    dp[0][0] = (0.0, 0)
    for b in range(1, L + 1):
        # Exactly b buckets need at least b leaves; a bucket per leaf at
        # most, so i ranges b..L.
        for i in range(b, L + 1):
            best, best_j = INF, -1
            for j in range(b - 1, i):
                prev = dp[b - 1][j]
                if prev[0] == math.inf:
                    continue
                c, load = seg(j, i)
                cand = (prev[0] + c, max(prev[1], load))
                # Strict < keeps the SMALLEST split point on ties, i.e.
                # the earliest boundary — deterministic across runs.
                if cand < best:
                    best, best_j = cand, j
            dp[b][i] = best
            arg[b][i] = best_j
    return dp, arg, seg


def _backtrack(arg, b: int, L: int) -> Tuple[int, ...]:
    cuts = [L]
    i = L
    for bb in range(b, 0, -1):
        i = arg[bb][i]
        cuts.append(i)
    return tuple(reversed(cuts))


def optimal_boundaries(leaf_sizes: Sequence[int], density: float, *,
                       n_buckets: Optional[int], p: int, codec="fp32",
                       schedule: Optional[str] = None, alpha_ms: float,
                       beta_gbps: float,
                       mode: str = "gtopk_layerwise",
                       pipeline: str = PIPELINE_DEFAULT) -> Tuple[int, ...]:
    """Exact cost-minimal contiguous partition. ``n_buckets=None`` lets
    the DP choose B too; ties between bucket counts break toward the
    historical per-leaf end (LARGER B), so `auto` never coarsens the
    wire unless the modeled cost strictly improves."""
    sizes = tuple(int(s) for s in leaf_sizes)
    L = len(sizes)
    if L == 0:
        raise ValueError("cannot bucket zero leaves")
    codec_name = getattr(codec, "name", codec)
    dp, arg, _ = _dp_tables(sizes, float(density), int(p), str(codec_name),
                            schedule, float(alpha_ms), float(beta_gbps),
                            mode, str(pipeline))
    if n_buckets is not None:
        b = max(1, min(int(n_buckets), L))
        return _backtrack(arg, b, L)
    best_b, best = L, dp[L][L]
    for b in range(L - 1, 0, -1):  # historical-first: larger B wins ties
        if dp[b][L] < best:
            best_b, best = b, dp[b][L]
    return _backtrack(arg, best_b, L)


def plan_buckets(leaf_sizes: Sequence[int], density: float, *,
                 buckets=BUCKETS_DEFAULT, p: int = 1, codec="fp32",
                 schedule: Optional[str] = None,
                 alpha_ms: Optional[float] = None,
                 beta_gbps: Optional[float] = None,
                 probe_dir: Optional[str] = None,
                 mode: str = "gtopk_layerwise",
                 pipeline: str = PIPELINE_DEFAULT) -> Optional[BucketPlan]:
    """Resolve a --buckets spec against a model's leaf sizes.

    Returns None for 'concat' (the historical single-merge wire — no
    bucket axis exists there, and therefore no pipeline axis either).
    'leaf' and a pinned int are pure structure; 'auto' (and the
    boundary placement of a pinned B) needs alpha/beta — passed
    explicitly or read from the committed probe fit via the planner's
    inputs (parallel.planner.planner_inputs).

    ``pipeline`` resolution also lives here: 'serial'/'overlap' are
    taken as pinned (the DP prices under that order); 'auto' runs the
    DP under BOTH pricings, compares the true modeled spans
    (pipeline_span_ms) of the two winners, and keeps the cheaper —
    ties go to 'serial', the historical order."""
    spec = parse_buckets(buckets)
    if spec == "concat":
        return None
    pipe = parse_pipeline(pipeline)
    sizes = tuple(int(s) for s in leaf_sizes)
    L = len(sizes)
    if L == 0:
        raise ValueError("cannot bucket zero leaves")

    def per_bucket_ks(bounds: Tuple[int, ...]) -> Tuple[int, ...]:
        return tuple(
            k_for_density(sum(sizes[lo:hi]), density)
            for lo, hi in zip(bounds, bounds[1:]))

    # 'leaf' structure needs no pricing, but resolving pipeline 'auto'
    # still does; only fetch probe inputs when something will use them.
    needs_pricing = spec != "leaf" or pipe == "auto"
    if needs_pricing and (alpha_ms is None or beta_gbps is None):
        # Late import: the planner imports this module.
        from .planner import planner_inputs
        inputs = planner_inputs(probe_dir)
        alpha_ms = inputs["alpha_ms"] if alpha_ms is None else alpha_ms
        beta_gbps = inputs["beta_gbps"] if beta_gbps is None else beta_gbps

    def span(plan: BucketPlan) -> float:
        return pipeline_span_ms(plan, p=p, codec=codec, schedule=schedule,
                                alpha_ms=alpha_ms, beta_gbps=beta_gbps,
                                mode=mode)

    if spec == "leaf":
        bounds = _leaf_boundaries(L)
        plans = [BucketPlan(bounds, sizes, per_bucket_ks(bounds),
                            spec="leaf", pipeline=pp)
                 for pp in (("serial", "overlap") if pipe == "auto"
                            else (pipe,))]
        # Strict < keeps 'serial' (listed first) on ties.
        return min(plans, key=span) if len(plans) > 1 else plans[0]

    n_target = None if spec == "auto" else int(spec)

    def solve(pp: str) -> BucketPlan:
        bounds = optimal_boundaries(
            sizes, density, n_buckets=n_target, p=p, codec=codec,
            schedule=schedule, alpha_ms=alpha_ms, beta_gbps=beta_gbps,
            mode=mode, pipeline=pp)
        return BucketPlan(bounds, sizes, per_bucket_ks(bounds),
                          spec=buckets_key(spec), pipeline=pp)

    if pipe != "auto":
        return solve(pipe)
    serial_plan, overlap_plan = solve("serial"), solve("overlap")
    # min() keeps the first argument on ties — serial, the historical
    # order, so 'auto' only pipelines when the modeled span strictly
    # improves.
    return min((serial_plan, overlap_plan), key=span)


def describe(plan: BucketPlan, *, p: int, codec="fp32",
             schedule: Optional[str] = None, alpha_ms: float,
             beta_gbps: float,
             mode: str = "gtopk_layerwise") -> List[dict]:
    """Per-bucket rows for `report plan` / the bench: leaf range, elems,
    wire k, modeled bytes and ms (merge, select, and the pipeline-stage
    term the DP priced)."""
    rows = []
    for b, (n_b, k_b) in enumerate(plan.pairs()):
        lo, hi = plan.leaf_range(b)
        merge_ms = bucket_cost_ms(
            n_b, k_b, p=p, codec=codec, schedule=schedule,
            alpha_ms=alpha_ms, beta_gbps=beta_gbps, mode=mode)
        rows.append({
            "bucket": b,
            "leaves": f"{lo}:{hi}",
            "n_leaves": hi - lo,
            "elems": n_b,
            "k": k_b,
            "wire_bytes": comm_bytes_per_step(
                mode, n_b, k_b, p, codec=getattr(codec, "name", codec),
                schedule=schedule),
            "modeled_ms": merge_ms,
            "select_ms": select_cost_ms(n_b),
            "stage_ms": (max(select_cost_ms(n_b), merge_ms)
                         if plan.pipeline == "overlap" else merge_ms),
        })
    return rows
