"""The alpha-beta comm model: one implementation, and the fits that feed it.

The paper's scaling argument (arXiv:1901.04359 §3), re-parameterized for
TPU links: per-step communication time from mode, worker count, gradient
size and link constants. The planner scores wire plans with
:func:`predict`, the comm ledger (``obs/ledger.py``) audits measured
T_comm against it, and ``benchmarks/scaling_model.py`` projects
throughput from it — all three read this module, so a plan decision is
always reconcilable with the ledger row that later audits it.

Link constants come from a fit artifact (:func:`load_alpha_beta`):
``dcn_probe_{P}proc.json`` (benchmarks/dcn_probe.py) or
``calib_fit_{P}proc.json`` (obs/calib.py, the in-run calibrator). The
default search directory, :data:`FIT_DIR`, is the package's own.
"""

from __future__ import annotations

import glob
import json
import math
import os
import re
from typing import Any, Dict, Optional, Sequence

from gtopkssgd_tpu.parallel.codec import get_codec
from gtopkssgd_tpu.parallel.collectives import balanced_cap, tree_rounds

# v5e: 4 ICI links/chip at ~100 GB/s-class aggregate; DCN per host in
# tens of Gbit/s. Used wherever a caller passes no constant and no fit
# artifact supplies one.
DEFAULT_ICI_GBPS = 1600.0
DEFAULT_DCN_GBPS = 25.0

# Where load_alpha_beta looks when given no directory: the committed
# probe fits (dcn_probe_2proc.json, dcn_probe_4proc.json).
FIT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fits")


def wire_mode_for(mode: str, schedule: Optional[str] = None,
                  bucketing: Optional[str] = None) -> str:
    """Comm-model key for (semantic mode, wire schedule, bucketing): the
    layerwise mode shares the flat tree's wire, and the 'balanced'
    schedule maps the gtopk family onto the Ok-Topk model branch.
    None/'auto'/'tree' keep the mode's historical model — exactly
    sparse_allreduce's plan dispatch, so the ledger always prices the
    schedule that actually ran.

    ``bucketing`` (parallel.bucketing.buckets_key grammar) changes the
    merge MULTIPLICITY, not the per-merge model, so the key stays the
    same base wire mode; pricing callers pass the bucket (n_b, k_b)
    pairs to ``predict(buckets=...)`` and the model sums B independent
    merges of that key. The parameter exists here so every plan/ledger
    call site names the full wire decision in one place."""
    wm = "gtopk" if mode == "gtopk_layerwise" else mode
    if schedule == "balanced" and wm in ("gtopk", "gtopk_hier"):
        return "gtopk_balanced"
    return wm


def _ring_allreduce_bytes(n_bytes: int, p: int) -> float:
    """Bandwidth-optimal dense allreduce moves 2(p-1)/p x the buffer per
    device — 0 at p=1 (no collective), ~2x asymptotically."""
    return 2.0 * (p - 1) / p * n_bytes


def predict(mode: str, p: int, *, n: int, k: int, ici_gbps: float,
            dcn_gbps: float, ici_size: int,
            dcn_alpha_ms: float = 0.0, codec: str = "fp32",
            buckets: Optional[Sequence[Sequence[int]]] = None) -> float:
    """Predicted comm_ms at P devices for one wire mode, unrounded.

    Comm cost = messages x per-message latency + bytes / link-bandwidth
    on the link each phase actually crosses. For flat modes every P is
    assumed to sit behind the slower of the two links when P exceeds one
    ICI domain (`ici_size` chips): conservative for ICI-only pods,
    realistic for multislice. ``dcn_alpha_ms`` is the fitted per-message
    latency of the slow link; ICI latency is kept at 0 —
    microseconds-class, invisible next to ms-scale DCN terms.

    When P spans slices, EVERY mode decomposes into an intra-slice phase
    on ICI plus an inter-slice phase on DCN — charging flat modes DCN
    latency on intra-slice hops while the hier mode gets slice-aware
    accounting would rig the comparison. Phase shapes: dense = ring
    within the slice + ring over the n_slices slice aggregates; gtopk =
    the hypercube's first log2(s) rounds pair intra-slice partners, the
    last log2(n_slices) rounds cross DCN; allgather = gather s*k within
    the slice, then pull the other slices' (p-s)*k over DCN.

    ``codec`` sets the per-set sparse payload
    (parallel.codec.WireCodec.wire_set_bytes — packed values + bf16
    block scales + Elias-Fano bitpacked indices; fp32 identity = the
    historical 8 bytes/element). Every sparse exchange — ICI and DCN
    rounds alike — ships codec bytes, because the tree encodes every
    round; the hier mode's dense intra-slice psum stays 4n fp32.

    ``buckets`` — ((n_b, k_b), ...) from a layerwise BucketPlan
    (parallel.bucketing) — prices the bucketed wire as B independent
    merges of this mode, each over its bucket-local index space, summed.
    That is exactly what the bucketed optimizer path issues."""
    if buckets:
        return sum(
            predict(mode, p, n=int(n_b), k=int(k_b), ici_gbps=ici_gbps,
                    dcn_gbps=dcn_gbps, ici_size=ici_size,
                    dcn_alpha_ms=dcn_alpha_ms, codec=codec)
            for n_b, k_b in buckets)
    # The layerwise mode's wire cost IS gtopk's: the layerwise K differs
    # from ceil(rho*N) only by the +1-per-tiny-leaf ceil rounding (<1%
    # for ResNet-50 at rho=1e-3).
    wire_mode = wire_mode_for(mode)
    set_bytes = get_codec(codec).wire_set_bytes(k, n)
    ici_Bps = ici_gbps * 1e9 / 8
    dcn_Bps = dcn_gbps * 1e9 / 8
    s = min(ici_size, p)
    # ceil, not floor: p=24 with 16-chip slices IS a 2-slice job that
    # crosses DCN (a floor would model it as one all-ICI slice and
    # charge zero DCN cost). Ragged counts are first-class: non-pow2 axes
    # run the masked hypercube in-tree (parallel.collectives._merge_tree),
    # log2(m) + 2 rounds with m = 2^floor(log2 x) — tree_rounds, the
    # implementation's own round count.
    n_slices = max(1, math.ceil(p / s))
    dcn_rounds = tree_rounds(n_slices)
    if wire_mode == "dense":
        return (_ring_allreduce_bytes(4 * n, s) / ici_Bps * 1e3
                + _ring_allreduce_bytes(4 * n, n_slices) / dcn_Bps * 1e3
                + 2 * (n_slices - 1) * dcn_alpha_ms)
    if wire_mode == "gtopk":
        # Split the flat tree's tree_rounds(p) by the link each round
        # actually crosses: hypercube rounds whose XOR bit stays inside a
        # slice pair ICI neighbors; larger bits — and the ragged
        # fold/unfold, which spans slices whenever p > s — cross DCN.
        # (p=24, s=16: 6 rounds total = 4 ICI + fold/unfold on DCN; a
        # tree_rounds(s)+tree_rounds(n_slices) split drops one DCN round
        # at exactly those ragged shapes.)
        total_rounds = tree_rounds(p)
        if n_slices == 1:
            ici_rounds, flat_dcn_rounds = total_rounds, 0
        else:
            m = 1 << (p.bit_length() - 1)
            # floor(log2) via bit_length, not int(math.log2(...)): s is
            # whatever --ici-size the user typed, and the float path
            # silently truncates non-powers-of-two (and can misround at
            # large exact powers); hypercube rounds pair by XOR bit, so
            # floor(log2) is the intended count for ragged s too.
            ici_rounds = min(m, s).bit_length() - 1
            flat_dcn_rounds = total_rounds - ici_rounds
        return (ici_rounds * set_bytes / ici_Bps * 1e3
                + flat_dcn_rounds * (set_bytes / dcn_Bps * 1e3
                                     + dcn_alpha_ms))
    if wire_mode == "gtopk_balanced":
        # Ok-Topk split-and-reduce (parallel.collectives
        # balanced_gtopk_allreduce): p-1 scatter ppermutes + a p-slice
        # allgather, each moving ONE cap-of-n encoded set — O(k) volume
        # vs the tree's O(k log p), paid for with O(p) message count.
        # Link split mirrors allgather's: of each phase's p-1 partner
        # hops, s-1 stay inside the slice, the rest cross DCN; every
        # DCN hop pays the fitted per-message alpha (the term that makes
        # the planner prefer the tree on latency-bound fabrics).
        cap_bytes = get_codec(codec).wire_set_bytes(
            balanced_cap(k, p, n), n)
        ici_hops = 2 * (s - 1) + 1   # scatter + gather + own-set share
        dcn_hops = 2 * (p - s)
        return (ici_hops * cap_bytes / ici_Bps * 1e3
                + dcn_hops * (cap_bytes / dcn_Bps * 1e3 + dcn_alpha_ms))
    if wire_mode == "allgather":
        return ((set_bytes * s) / ici_Bps * 1e3
                + (set_bytes * (p - s)) / dcn_Bps * 1e3
                + (n_slices - 1) * dcn_alpha_ms)
    if wire_mode == "gtopk_hier":
        return (_ring_allreduce_bytes(4 * n, s) / ici_Bps * 1e3
                + dcn_rounds * (set_bytes / dcn_Bps * 1e3
                                + dcn_alpha_ms))
    raise ValueError(mode)


# Fit-artifact filename grammar: the probe writes dcn_probe_{P}proc.json,
# the in-run calibrator (obs/calib.py) writes calib_fit_{P}proc.json with
# the same alpha_beta_fit payload. One regex recovers (family, P) for the
# numeric precedence sort below.
_FIT_ARTIFACT_RE = re.compile(r"^(dcn_probe|calib_fit)_(\d+)proc\.json$")


def _fit_artifact_key(path: str):
    """Precedence sort key (higher wins): proc count NUMERICALLY first —
    the docstring's "largest proc count present" contract, which a plain
    lexicographic basename sort breaks the moment two counts share no
    digit width (it ranked 8proc over 16proc) — then, at equal P, a
    calib_fit over a dcn_probe: the calibrator measured THIS workload's
    wire in-situ, the probe measured synthetic pings."""
    m = _FIT_ARTIFACT_RE.match(os.path.basename(path))
    if m is None:
        return (-1, 0, os.path.basename(path))
    return (int(m.group(2)), 1 if m.group(1) == "calib_fit" else 0,
            os.path.basename(path))


def _parse_fit_artifact(path: str) -> Optional[Dict[str, Any]]:
    """{alpha_ms, beta_gbps, source[, axes]} from one fit artifact, or
    None when unreadable/unusable. The optional ``axes`` section maps
    axis name -> per-axis fit ({"ici": {...}, "dcn": {...}} today,
    arbitrary mesh-axis names later); only axes with numeric alpha_ms
    and beta_gbps > 0 survive parsing."""
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None
    fit = doc.get("alpha_beta_fit") or {}
    alpha, beta = fit.get("alpha_ms"), fit.get("beta_gbps")
    if not (isinstance(alpha, (int, float))
            and isinstance(beta, (int, float)) and beta > 0):
        return None
    out: Dict[str, Any] = {"alpha_ms": float(alpha),
                           "beta_gbps": float(beta),
                           "source": os.path.basename(path)}
    # Theil-Sen residual noise floor (obs/calib.py) — the forecast
    # plane's uncertainty-band source. Probe-era artifacts predate it;
    # absent means "no measured band", never 0-invented.
    if isinstance(fit.get("resid_ms"), (int, float)) \
            and fit["resid_ms"] >= 0:
        out["resid_ms"] = float(fit["resid_ms"])
    axes = doc.get("axes")
    if isinstance(axes, dict):
        clean: Dict[str, Dict[str, float]] = {}
        for name, ax in axes.items():
            if (isinstance(ax, dict)
                    and isinstance(ax.get("alpha_ms"), (int, float))
                    and isinstance(ax.get("beta_gbps"), (int, float))
                    and ax["beta_gbps"] > 0):
                clean[str(name)] = {"alpha_ms": float(ax["alpha_ms"]),
                                    "beta_gbps": float(ax["beta_gbps"])}
                if isinstance(ax.get("resid_ms"), (int, float)) \
                        and ax["resid_ms"] >= 0:
                    clean[str(name)]["resid_ms"] = float(ax["resid_ms"])
        if clean:
            out["axes"] = clean
    return out


def load_alpha_beta(search_dir: Optional[str] = None,
                    nprocs: Optional[int] = None
                    ) -> Optional[Dict[str, Any]]:
    """The fitted {alpha_ms, beta_gbps} from a fit artifact —
    ``dcn_probe_{n}proc.json`` (benchmarks/dcn_probe.py) or
    ``calib_fit_{n}proc.json`` (obs/calib.py, the in-run calibrator) —
    or None. ``nprocs`` restricts to that exact proc count; otherwise
    the largest proc count present wins (closest to a real fleet), with
    proc counts compared numerically. At equal proc count an artifact
    carrying a per-axis ``axes`` section outranks an axis-blind one
    (two measured hops price a hierarchical plan better than one
    blended fit — same spirit as the calib-over-probe rule), then a
    calib_fit outranks a dcn_probe (the calibrator measured the actual
    workload's collectives; the probe measured synthetic pings). The
    returned dict carries the ``axes`` section through when present.
    Default search dir: :data:`FIT_DIR`."""
    if search_dir is None:
        search_dir = FIT_DIR
    if nprocs is not None:
        paths = [os.path.join(search_dir, f"calib_fit_{nprocs}proc.json"),
                 os.path.join(search_dir, f"dcn_probe_{nprocs}proc.json")]
    else:
        paths = sorted(
            glob.glob(os.path.join(search_dir, "dcn_probe_*proc.json"))
            + glob.glob(os.path.join(search_dir, "calib_fit_*proc.json")),
            key=_fit_artifact_key, reverse=True)
    best_key, best = None, None
    for path in paths:
        parsed = _parse_fit_artifact(path)
        if parsed is None:
            continue
        p_key, calib_key, name = _fit_artifact_key(path)
        key = (p_key, 1 if "axes" in parsed else 0, calib_key, name)
        if best_key is None or key > best_key:
            best_key, best = key, parsed
    return best
