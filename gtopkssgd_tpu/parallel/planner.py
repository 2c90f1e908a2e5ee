"""Topology-aware comm planner: wire plans, scored once at startup.

Before this module, the wire algorithm was welded to the compression
mode: gtopk meant the hypercube tree, allgather meant the DGC union, and
adding a schedule meant threading a new mode string through every
dispatch table. The planner splits those concerns. A mode fixes the
SEMANTICS (what sparse set is applied, what repair contract the
optimizer gets); a :class:`CommPlan` fixes the WIRE — per-axis
algorithm, schedule, codec, and ici/dcn split — and is chosen ONCE at
startup (``Trainer.__init__`` -> :func:`build_decision`) by scoring
every semantics-preserving candidate with the same alpha-beta model the
comm ledger audits against (``comm_model.predict``, parameterized from
a ``dcn_probe`` / ``calib_fit`` ``alpha_beta_fit`` artifact when one is
present, documented defaults otherwise). The compiled step is handed
the chosen plan's NAME and looks it up (:func:`resolve_plan`): tracing
scores nothing and reads no file.

Candidate sets are deliberately semantics-preserving: the planner never
swaps gtopk for allgather behind the user's back — it only picks among
wire realizations of the mode the user asked for (today: the hypercube
'tree' vs the Ok-Topk 'balanced' split-and-reduce, arXiv:2201.07598).
Ties and model-indifferent regimes resolve to the hand-picked historical
schedule (:func:`gtopkssgd_tpu.modes.default_schedule`), so default runs
keep their exact pre-planner wire. ``--comm-plan`` pins a plan by name;
the full decision — chosen plan plus the score of every candidate — is
logged as a ``"plan"`` metrics record and stamped into the run manifest,
so every ledger row can be traced back to why its schedule won.

Collectives never import the planner: ``sparse_allreduce`` takes the
plan duck-typed (anything with ``.schedule``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

from gtopkssgd_tpu.modes import (
    ALLGATHER_MODES,
    DENSE_MODES,
    GTOPK_MODES,
    HIER_MODES,
    LAYERWISE_MODES,
    default_schedule,
)
from gtopkssgd_tpu.parallel import bucketing as _bucketing
from gtopkssgd_tpu.parallel.collectives import comm_bytes_per_step
from gtopkssgd_tpu.parallel.comm_model import (
    DEFAULT_DCN_GBPS,
    DEFAULT_ICI_GBPS,
    load_alpha_beta,
    predict,
    wire_mode_for,
)

# Per-message slow-link latency assumed when NO fit artifact is
# available (comm_model.FIT_DIR's dcn_probe_*proc.json). Deliberately
# nonzero: the degenerate alpha=0 bandwidth-only model would let any
# many-small-messages schedule (balanced sends O(p) messages where the
# tree sends O(log p)) win on volume alone and silently change the wire
# at defaults. 0.1 ms is a conservative floor for any cross-host fabric;
# the committed 4-proc probe fit measured ~21.9 ms on loopback-TCP.
PLANNER_DEFAULT_ALPHA_MS = 0.1


@dataclasses.dataclass(frozen=True)
class CommPlan:
    """One fully-specified wire realization of a reduction mode.

    ``schedule`` is the slow-axis algorithm (modes.SCHEDULES), ``intra``
    the ICI-axis phase ('psum' for the hier mode's in-slice dense
    allreduce, 'none' otherwise), ``codec`` the sparse payload codec
    spec, ``ici_size`` the ICI-domain width the plan assumes,
    ``bucketing`` the layerwise merge granularity
    (parallel.bucketing.buckets_key grammar: 'concat' = the historical
    single concatenated merge, 'leaf' = one merge per leaf, 'b{B}' /
    'auto' = the DP partition), ``pipeline`` the RESOLVED execution
    order of the bucketed select/merge chain (modes.PIPELINES —
    'serial' is the historical strictly-sequential step, 'overlap' the
    double-buffered stage loop; resolution of an 'auto' spec happens
    upstream in parallel.bucketing.plan_buckets, the planner carries
    and records the outcome). The name is the plan grammar the
    ``--comm-plan`` flag speaks.
    """

    name: str
    mode: str
    schedule: str
    intra: str = "none"
    codec: str = "fp32"
    ici_size: int = 1
    bucketing: str = "concat"
    pipeline: str = "serial"

    @property
    def wire_mode(self) -> str:
        """Comm-model key (comm_model.predict / ledger) this plan
        prices as — the single mapping shared with the ledger."""
        return wire_mode_for(self.mode, self.schedule,
                             bucketing=self.bucketing)


def _norm_mode(mode: Optional[str]) -> str:
    return "dense" if mode in DENSE_MODES else str(mode)


def candidate_plans(mode: Optional[str], *, codec: str = "fp32",
                    ici_size: int = 1, bucketing: str = "concat",
                    pipeline: str = "serial") -> Tuple[CommPlan, ...]:
    """Every wire plan that realizes ``mode``'s semantics, historical
    default FIRST (selection uses a stable min, so the default wins all
    ties and all model-indifferent regimes). ``bucketing``/``pipeline``
    are carried on the gtopk-family candidates only — they are layerwise
    merge granularity / execution order, orthogonal to which schedule
    each merge runs."""
    m = _norm_mode(mode)
    if m in DENSE_MODES:
        return (CommPlan("dense", m, "psum", "none", codec, 1),)
    if m in ALLGATHER_MODES:
        return (CommPlan("allgather", m, "allgather", "none", codec, 1),)
    if m in HIER_MODES:
        # The hier tree already IS a planned ici/dcn split; a balanced
        # cross-slice variant would need slice-identical owner ranges
        # and is future work — the plan layer makes it additive.
        return (CommPlan("hier", m, "tree", "psum", codec,
                         max(1, ici_size)),)
    if m in GTOPK_MODES or m in LAYERWISE_MODES:
        return (CommPlan("tree", m, "tree", "none", codec, 1, bucketing,
                         pipeline),
                CommPlan("balanced", m, "balanced", "none", codec, 1,
                         bucketing, pipeline))
    raise ValueError(f"unknown mode {mode!r}")


def validate_pin(pin: Optional[str], mode: Optional[str], *,
                 ici_size: int = 1) -> str:
    """Normalize and check a ``--comm-plan`` pin against the mode's
    candidate set at config time — a typo'd or incompatible pin fails
    at startup, not three imports deep into the first traced step."""
    pin = "auto" if pin in (None, "", "auto") else str(pin)
    if pin == "auto":
        return pin
    names = [c.name for c in candidate_plans(mode, ici_size=ici_size)]
    if pin not in names:
        raise ValueError(
            f"--comm-plan {pin!r} does not realize mode {mode!r}; "
            f"valid plans here: auto, {', '.join(names)}")
    return pin


def planner_inputs(probe_dir: Optional[str] = None) -> Dict[str, Any]:
    """The alpha-beta constants the planner scores with, plus where they
    came from: the newest fit artifact (dcn_probe / calib_fit) when one
    exists, else documented fallback defaults (PLANNER_DEFAULT_ALPHA_MS
    + the comm model's DCN bandwidth).

    An artifact carrying a per-axis ``axes`` section prices each hop
    from its OWN measured fit: the "dcn" entry overrides the blended
    slow-link alpha/beta, and the "ici" entry's bandwidth replaces the
    DEFAULT_ICI_GBPS guess — so a hierarchical plan's two hops are
    scored from two measured links, with no caller change needed."""
    fit = load_alpha_beta(search_dir=probe_dir)
    if fit is not None:
        out = {"alpha_ms": fit["alpha_ms"],
               "beta_gbps": fit["beta_gbps"],
               "ici_gbps": DEFAULT_ICI_GBPS,
               "fit_source": fit["source"]}
        # Theil-Sen residual noise floor, when the artifact records one
        # (calib_fit does; probe-era artifacts don't). The forecast
        # plane derives its uncertainty bands from this — absent means
        # absent, not zero-by-decree.
        if "resid_ms" in fit:
            out["resid_ms"] = fit["resid_ms"]
        axes = fit.get("axes")
        if isinstance(axes, dict):
            dcn = axes.get("dcn")
            if dcn is not None:
                out["alpha_ms"] = dcn["alpha_ms"]
                out["beta_gbps"] = dcn["beta_gbps"]
                if "resid_ms" in dcn:
                    out["resid_ms"] = dcn["resid_ms"]
            ici = axes.get("ici")
            if ici is not None:
                out["ici_gbps"] = ici["beta_gbps"]
            out["axes"] = {name: dict(ax)
                           for name, ax in sorted(axes.items())}
        return out
    return {"alpha_ms": PLANNER_DEFAULT_ALPHA_MS,
            "beta_gbps": DEFAULT_DCN_GBPS,
            "ici_gbps": DEFAULT_ICI_GBPS,
            "fit_source": "fallback-defaults"}


def score_plan(plan: CommPlan, p: int, *, n: int, k: int,
               alpha_ms: float, beta_gbps: float, ici_gbps: float,
               buckets: Optional[Tuple[Tuple[int, int], ...]] = None
               ) -> float:
    """Predicted comm_ms of one candidate (comm_model.predict). The
    same number the ledger later audits against measured T_comm, so a
    plan decision is always reconcilable post-hoc. ``buckets`` (the
    BucketPlan's ((n_b, k_b), ...) pairs) prices the bucketed wire as B
    independent merges."""
    return predict(
        plan.wire_mode, p, n=n, k=k, dcn_alpha_ms=alpha_ms,
        dcn_gbps=beta_gbps, ici_gbps=ici_gbps,
        ici_size=plan.ici_size, codec=plan.codec, buckets=buckets)


@dataclasses.dataclass(frozen=True)
class PlanDecision:
    """A resolved plan plus the evidence: every candidate's score and
    the model inputs used. ``record()`` is the flat dict the trainer
    logs as the ``"plan"`` metrics record."""

    plan: CommPlan
    candidates: Tuple[Dict[str, Any], ...]
    inputs: Dict[str, Any]
    pin: str = "auto"

    def record(self) -> Dict[str, Any]:
        historical = default_schedule(self.plan.mode)
        return {
            "plan": self.plan.name,
            "schedule": self.plan.schedule,
            "wire_mode": self.plan.wire_mode,
            "mode": self.plan.mode,
            "intra": self.plan.intra,
            "bucketing": self.plan.bucketing,
            "pipeline": self.plan.pipeline,
            "pin": self.pin,
            # numeric so the gate smoke can pin "defaults kept the
            # historical wire" as a baseline check
            "plan_is_default": float(self.plan.schedule == historical),
            "candidates": list(self.candidates),
            **{key: self.inputs[key] for key in sorted(self.inputs)},
        }


def build_decision(mode: Optional[str], *, p: int, n: int, k: int,
                   codec: str = "fp32", ici_size: int = 1,
                   pin: Optional[str] = "auto",
                   probe_dir: Optional[str] = None,
                   alpha_ms: Optional[float] = None,
                   beta_gbps: Optional[float] = None,
                   ici_gbps: Optional[float] = None,
                   bucketing: str = "concat",
                   buckets: Optional[Tuple[Tuple[int, int], ...]] = None,
                   fit_source: Optional[str] = None,
                   pipeline: str = "serial") -> PlanDecision:
    """Score every candidate plan for (mode, mesh, n, k, codec) and pick
    one: the pinned plan when ``pin`` names one, else the cheapest under
    the model (stable min — the historical default wins ties). Explicit
    alpha/beta/ici arguments override the probe-artifact lookup (tests,
    what-if scoring); ``fit_source`` labels where such an override came
    from (the --comm-model-fit artifact's filename) in place of the
    generic "arg", so the decision record keeps real provenance.
    ``bucketing``/``buckets`` (the resolved --buckets key and the
    BucketPlan's (n_b, k_b) pairs) make the candidate scores price the
    bucketed wire — B merges, each over its bucket-local index space —
    instead of the single concatenated merge. ``pipeline`` is the
    RESOLVED execution order (plan_buckets already decided an 'auto'
    spec); the decision still selects the schedule by comm_ms — the
    wire cost is what the schedule controls — but every candidate row
    also records span_serial_ms/span_overlap_ms, the step-span the two
    execution orders would expose under that schedule, so the recorded
    decision shows what overlap bought."""
    pin = validate_pin(pin, mode, ici_size=ici_size)
    inputs = planner_inputs(probe_dir)
    override_source = fit_source if fit_source is not None else "arg"
    if alpha_ms is not None:
        inputs["alpha_ms"] = float(alpha_ms)
        inputs["fit_source"] = override_source
    if beta_gbps is not None:
        inputs["beta_gbps"] = float(beta_gbps)
        inputs["fit_source"] = override_source
    if ici_gbps is not None:
        inputs["ici_gbps"] = float(ici_gbps)
    cands = candidate_plans(mode, codec=codec, ici_size=ici_size,
                            bucketing=bucketing, pipeline=pipeline)
    # Span pricing needs the bucket shapes; a concat/unbucketed wire is
    # one bucket of the full (n, k) — both execution orders then expose
    # the same span (a B=1 pipeline has nothing to overlap), which is
    # exactly the honest answer for that wire.
    span_pairs = buckets if buckets else ((n, k),)
    span_plan = _bucketing.BucketPlan(
        boundaries=tuple(range(len(span_pairs) + 1)),
        leaf_sizes=tuple(nb for nb, _ in span_pairs),
        ks=tuple(kb for _, kb in span_pairs))
    scored: List[Dict[str, Any]] = []
    for cand in cands:
        ms = score_plan(cand, p, n=n, k=k, alpha_ms=inputs["alpha_ms"],
                        beta_gbps=inputs["beta_gbps"],
                        ici_gbps=inputs["ici_gbps"], buckets=buckets)
        wire_bytes = (
            sum(comm_bytes_per_step(cand.mode, n_b, k_b, p,
                                    ici_size=cand.ici_size,
                                    codec=cand.codec,
                                    schedule=cand.schedule)
                for n_b, k_b in buckets)
            if buckets else
            comm_bytes_per_step(cand.mode, n, k, p,
                                ici_size=cand.ici_size, codec=cand.codec,
                                schedule=cand.schedule))
        spans = {
            pipe: _bucketing.pipeline_span_ms(
                span_plan, p=p, codec=cand.codec,
                schedule=cand.schedule, alpha_ms=inputs["alpha_ms"],
                beta_gbps=inputs["beta_gbps"], mode=cand.mode,
                pipeline=pipe)
            for pipe in ("serial", "overlap")}
        scored.append({
            "name": cand.name, "schedule": cand.schedule,
            "wire_mode": cand.wire_mode, "comm_ms": round(ms, 6),
            "wire_bytes": wire_bytes,
            "span_serial_ms": round(spans["serial"], 6),
            "span_overlap_ms": round(spans["overlap"], 6),
        })
    if pin != "auto":
        chosen = next(c for c in cands if c.name == pin)
    else:
        chosen = cands[min(range(len(cands)),
                           key=lambda i: scored[i]["comm_ms"])]
    inputs = {**inputs, "p": p, "n": n, "k": k, "codec": str(codec),
              "ici_size": ici_size}
    return PlanDecision(plan=chosen, candidates=tuple(scored),
                        inputs=inputs, pin=pin)


def resolve_plan(mode: Optional[str], name: Optional[str] = "auto", *,
                 codec: str = "fp32", ici_size: int = 1) -> CommPlan:
    """The optimizer's trace-time entry point: the candidate of ``mode``
    called ``name``. ``Trainer`` passes the name its one
    :func:`build_decision` chose; 'auto' (an optimizer built with no
    ``Trainer`` above it) is the historical default, the first
    candidate. Nothing is scored and no file is read here."""
    name = validate_pin(name, mode, ici_size=ici_size)
    cands = candidate_plans(mode, codec=codec, ici_size=ici_size)
    return next((c for c in cands if c.name == name), cands[0])
