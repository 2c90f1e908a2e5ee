"""Distributed gTop-k optimizer — the reference's L2 layer, TPU-native.

Reference parity (SURVEY.md C3: the Horovod-style ``DistributedOptimizer``
wrapper in hclhkbu/gtopkssgd, living in/near dist_trainer.py): intercept the
gradients after backward, flatten/merge every layer's grad into ONE vector,
hand it to the compressor + allreducer, then apply the reduced sparse update
with SGD (momentum + weight decay) identically on every rank.

TPU-native redesign (SURVEY.md §7): instead of an object wrapping a stateful
optimizer plus a background communication thread, the whole pipeline is a
pure optax ``GradientTransformation``:

    (grads, state, params) -> (updates, state')

whose state carries the error-feedback residual as an ordinary array. One
jitted SPMD train step contains compute, compression, and the collective;
XLA overlaps them and Orbax checkpoints the residual for free (the reference
silently dropped residuals on resume — a sharp edge fixed here).

Pipeline inside ``update`` on a mesh (P > 1; names match the reference call
stack, SURVEY.md §3.1), where the wire's (vals, idx) sets index one vector:

    flat            = ravel_pytree(grads)                 # "flatten/merge"
    flat            = clip_by_global_norm(flat)           # LSTM path: clip
                                                          #   BEFORE compress
    acc             = flat + residual                     # error feedback
    vals, idx, res  = compressor.compress(acc)            # local top-k
    global set      = sparse_allreduce(mode, ...)         # gtopk tree /
                                                          #   allgather / psum
    res'            = repair(res, vals, idx, gidx)        # add_residuals
    dense update    = scatter(global set) / P             # average
    updates         = SGD(momentum, wd) on dense update   # inner optimizer

The dense modes flatten too (one psum of one vector). On ONE device
(the **slabs** form, every flat sparse mode at P = 1) nothing is sent, so no
[N] vector is made: the same mathematics, one k = rho * N and one
threshold tau over the whole gradient, runs on the gradient's own leaves,

    acc_l   = clip(g_l) + r_l                 each large leaf in its own
    tau     = k-th largest |acc| over all l   shape and layout, the small
    keep_l  = |acc_l| >= tau, zeros never     ones together in one short
    r_l'    = where(keep_l, 0, acc_l)         vector (compression.LeafPlan)
    update  = SGD on where(keep_l, acc_l, 0)

and the state holds r (and v, u, the age buffer) in that form. What still
sees [N]: the mesh and dense paths above, the recall audit's taken branch,
the threshold search of the methods other than exact / approx / auto
(ops.select_tau_leaves), and a state made with a mesh axis named but run
unbound, which the slabs form cuts into slabs and joins again.

The step itself is written once (``update_fn``): split, clip, the
correction's velocity, dense warm-up or select-and-reduce, join, the inner
optimizer, the counters. What the three layouts above answer differently
(the [N] vector, ``gtopk_layerwise``'s leaves, the slabs) is a ``_Form``.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.flatten_util import ravel_pytree

from gtopkssgd_tpu.compression import get_compressor, plan_leaves
from gtopkssgd_tpu.obs import counters as obs_counters
from gtopkssgd_tpu.modes import (
    ALL_MODES,
    DENSE_MODES,
    HIER_MODES,
    LAYERWISE_MODES,
)
from gtopkssgd_tpu.ops import (
    k_for_density,
    membership_mask,
    scatter_add_dense,
    select_topk,
    topk_abs,
)
from gtopkssgd_tpu.parallel import (
    dense_allreduce, get_codec, ici_dense_psum, parse_buckets, plan_buckets,
    resolve_plan, roundtrip_aligned, sparse_allreduce, validate_pin)
from gtopkssgd_tpu.parallel.bucketing import parse_pipeline

Array = jax.Array
ScalarOrSchedule = Union[float, Callable[[Array], Array]]


class GTopKSGDState(NamedTuple):
    """State pytree of the distributed optimizer. ``residual`` holds the
    per-device local compression state — checkpointing this state therefore
    preserves error feedback across resume. Its shape depends on the mode:
    a flat f32[N] error-feedback buffer (empty for the dense path); the
    tuple of slabs of compression.LeafPlan (the large leaves in their own
    shapes, then one vector of the small ones) for a flat sparse mode
    built with no mesh axis named, the one-device step's own form
    (``leaf_form_state``; ``flat_residual`` / ``slab_residual`` turn one
    into the other); a tuple of per-leaf flat buffers for
    ``gtopk_layerwise``; and with ``momentum_correction`` a dict
    ``{"v": <buffer>, "u": <velocity>}`` where v is the
    accumulated-velocity residual DGC selects from and u is the local
    momentum buffer (same form as v). Every consumer (trainer shard_map
    strip/restore, per-device expansion, the checkpoint template)
    tree-maps over the field, so all layouts ride the same plumbing.

    ``telemetry`` (obs subsystem, default off -> an empty pytree) carries
    the on-device training-health counters of the step that PRODUCED this
    state (obs.counters: achieved density, tau, residual norm, grad
    norms, wire bytes, mass-capture ratio) — f32 scalars, replicated
    under shard_map (the optimizer pmeans them), so the host can read
    them without touching per-device state. With ``telemetry_layers``
    it additionally holds ``"layers"`` (obs.counters.LAYER_FIELDS as
    f32[L] arrays, leaf order = jax.tree flatten order of the grads)
    and ``"age"`` (per-coordinate steps-since-last-shipped, in the
    residual's form, replicated by construction); with
    ``telemetry_audit_interval`` an ``"audit_recall"`` scalar (-1 =
    never audited)."""

    count: Array
    residual: Array
    inner: optax.OptState
    telemetry: Any = ()


def _same(buffer):
    return buffer


class _Form(NamedTuple):
    """The layout one optimizer step works the gradient in: the one thing
    in which the step's three forms differ. ``update_fn`` picks one from
    the mode and the bound axis size; no option names them.

      flat    one [N] vector (``ravel_pytree``): the dense modes at any P,
              the sparse modes at P > 1 (the index form a wire needs);
      leaves  every leaf reshaped flat, per-leaf k: ``gtopk_layerwise``;
      slabs   compression.LeafPlan's slabs, one k and one tau: the flat
              sparse modes on one device.

    **parts** is the gradient in the form's layout (one array, or a tuple
    of arrays); a **buffer** is one of the state's (the residual, v, u,
    the age) in the same layout."""

    n: int                   # the gradient's elements
    sizes: Sequence[int]     # of its leaves, the tree's order
    k: int                   # what the wire model is told a step sends
    split: Callable          # grads -> (parts, join); join(parts) -> tree
    sq_norm: Callable        # parts -> sum of squares, in the form's own
    #                          order of float32 additions (the clip's norm)
    sparse_branch: Callable  # (srcs, res_in, us) -> (dense parts, residual,
    #                          u) and, with telemetry, (counters,)
    dense_mean: Callable     # srcs -> their mean over the mesh axis
    layer_l2: Callable       # parts or a buffer -> f32[L] norms by leaf
    layer_age: Callable      # the age buffer -> assemble_layer_telemetry's
    #                          age (and seg) arguments
    plan: Any = None         # the wire plan (parallel.planner), if a wire
    buckets: Any = None      # ((n_b, k_b), ...) of a bucketed wire
    state_in: Callable = _same   # a buffer as the state holds it -> the
    state_out: Callable = _same  # form's layout, and back


def _each_buffer(fn, residual):
    """``fn`` over the state's buffers: the residual alone, or momentum
    correction's v and u (a tuple of parts is one buffer)."""
    return jax.tree.map(fn, residual,
                        is_leaf=lambda x: isinstance(x, tuple))


def gtopk_sgd(
    learning_rate: ScalarOrSchedule,
    *,
    momentum: float = 0.9,
    weight_decay: float = 0.0,
    nesterov: bool = False,
    compression: Optional[str] = "gtopk",
    density: float = 0.001,
    topk_method: str = "auto",
    clip_grad_norm: Optional[float] = None,
    axis_name: Optional[str] = "dp",
    axis_size: Optional[int] = None,
    hier_ici_size: int = 1,
    wire_codec: str = "fp32",
    comm_plan: Optional[str] = "auto",
    buckets: Union[str, int] = "concat",
    pipeline: str = "serial",
    warmup_dense_steps: int = 0,
    momentum_correction: bool = False,
    telemetry: bool = False,
    telemetry_layers: bool = False,
    telemetry_audit_interval: int = 0,
) -> optax.GradientTransformation:
    """Build the distributed gTop-k S-SGD gradient transformation.

    Args mirror the reference's trainer/driver flags: ``learning_rate``
    (float or optax schedule), ``momentum``/``weight_decay``/``nesterov``
    (torch.optim.SGD semantics: wd is added to the *dense* averaged gradient
    before the momentum buffer, exactly like the reference where torch's SGD
    sees the sparse global update but decays every parameter), ``compression``
    + ``density`` (--compression/--density), ``clip_grad_norm`` (the LSTM
    paths clip BEFORE compression — SURVEY.md §3.4), and the mesh axis the
    collective runs over.

    With ``axis_name=None`` no collective is issued: this is the
    single-worker ``dl_trainer.py`` path — compression still runs so a
    1-device density sweep exercises error feedback.

    With ``axis_name`` set, ``update`` must run inside ``jax.shard_map``
    over that axis (the trainer does this for you). The actual axis size is
    derived from the bound mesh axis at trace time (``lax.axis_size``), so it
    cannot silently disagree with the mesh; ``axis_size``, if given, is only
    validated against it.

    ``warmup_dense_steps`` (reference C6 parity: the warm-up trick in
    settings.py — DGC-lineage "warm-up training", arXiv:1712.01887 §3)
    communicates the DENSE averaged gradient for the first W optimizer
    steps of a sparse mode, then switches to the sparse pipeline. Top-k
    at rho=0.001 updates only k coordinates per step, so cold-starting
    sparse costs a long accuracy ramp (measured: an 8-way gtopk run at
    600 steps trails dense 2.0-vs-0.2 in loss purely from the ramp); a
    few dense epochs remove it. Implemented as a ``lax.cond`` on the step
    counter INSIDE the one jitted update, so state shapes are identical
    in both phases, there is no recompile at the boundary, and
    checkpoint/resume lands in the right phase automatically. The
    residual passes through the dense phase unchanged (zeros), so error
    feedback starts exactly at the switch.

    ``compression='gtopk_layerwise'`` (TPU extension, arXiv:1911.08772
    layer-wise-top-k lineage — not reference parity; the reference always
    flattens, SURVEY.md §3.1) keeps error feedback and selection PER
    LAYER: residual is a pytree of per-leaf flat buffers, each leaf
    selects its own top-``ceil(rho * n_leaf)``, and only the concatenated
    (vals, idx) sets — k elements, not N — ever exist in the flat index
    space. The flat [N] gradient is never materialized, so each leaf's
    accumulate/select/zero-out chain can fuse into that leaf's backward
    epilogue instead of serializing behind a whole-model concatenation
    (the measured single-chip cost of the flat path —
    benchmarks/results/fused_variants_TPU_v5_lite.json). The collective
    is the unchanged gTop-k hypercube over the concatenated set, so the
    COMMUNICATED set is still a global magnitude top-K of the union;
    only the local per-device selection is layer-balanced.

    ``compression='gtopk_hier'`` enables the two-level TPU-idiom reduction
    (not reference parity — SURVEY.md §5 design option): the raw gradient is
    first dense-psum'd WITHIN each contiguous block of ``hier_ici_size``
    devices (an ICI slice — cheap, high-bandwidth links), then error
    feedback + top-k run on the slice-summed gradient and the gTop-k
    hypercube runs only ACROSS the ``P / hier_ici_size`` slices (the DCN
    hop, where sparsity pays). Every device of a slice computes identical
    sets, so the per-device residual stays consistent automatically.

    ``wire_codec`` (parallel.codec grammar: ``fp32 | int8[:BLOCK] |
    fp8[:BLOCK]``) selects the on-wire encoding of every sparse exchange.
    With a lossy codec the shipped values are requantized BEFORE the
    collective (``roundtrip_aligned``) and the quantization error
    ``vals - dequant(quant(vals))`` folds into the error-feedback
    residual right here at the compression layer, so codec error is
    self-correcting exactly like selection error; the collective then
    transports bits that decode to precisely the values selection was
    told were sent. Intermediate merge rounds requantize partial sums —
    that second-order error is shared bitwise-identically by all ranks
    (codec determinism) and is NOT residual-fed.

    ``buckets`` (layerwise only; parallel.bucketing grammar ``concat |
    leaf | <int B> | auto``) sets the MERGE GRANULARITY of the layerwise
    path. The historical default ``concat`` keeps today's exact wire:
    per-leaf selection, ONE merge over the concatenated set in the
    global index space. Any other spec switches to the bucketed
    pipeline: the leaves are partitioned into B contiguous byte-balanced
    buckets (the alpha-beta DP of parallel.bucketing — ``auto`` also
    chooses B, a pinned int or ``leaf`` fixes it), each bucket's (grad,
    residual) leaves concatenate into one flat operand, selection runs
    ONCE per bucket (k_b = ceil(density * n_b), the same fused two-stage
    kernels as everywhere else), and each bucket runs its own
    codec-framed merge in its BUCKET-LOCAL index space — B collectives
    per step instead of one, each cheaper in latency-critical regimes
    than L per-leaf merges and each with a smaller Elias-Fano index
    space than the global merge. The reduced update and the
    error-feedback residual scatter back to leaves through static bucket
    offsets, so the state layout (per-leaf residual tuple) and
    checkpoint treedef are identical to ``concat``. ``leaf`` (B = L) is
    per-leaf selection AND per-leaf merges — the fully-layerwise end;
    ``auto`` at B=1 is bit-identical to the flat ``gtopk`` pipeline over
    the raveled model (same k: ceil(density * N)).

    ``pipeline`` (bucketed layerwise only; parallel.bucketing grammar
    ``serial | overlap | auto``) sets the EXECUTION ORDER of the B
    select/merge stages within one step. ``serial`` is the paper's
    strictly sequential T_select + T_comm: bucket b+1's selection is
    gated (``lax.optimization_barrier``) on bucket b's merge outputs,
    so exactly one stage runs at a time — the bit-identity oracle and
    the order every pre-PR-15 run used implicitly. ``overlap`` cuts
    that dependence with a double-buffered stage loop: bucket b+1's
    selection is issued with NO data dependency on bucket b's merge, so
    XLA's latency-hiding scheduler interleaves the selection compute
    with the in-flight ppermute rounds; merges still chain through a
    barrier (one collective in flight — the schedule's round structure
    is preserved). Both orders compute the SAME values through the SAME
    ops (barriers are identity), so results — params, residuals,
    telemetry counters — are bit-identical across serial/overlap for
    every codec and schedule; only the exposed wall-clock differs.
    ``auto`` prices both orders with the bucketing DP's span model and
    keeps the cheaper (ties to serial).

    ``momentum_correction`` (TPU extension, DGC arXiv:1712.01887 §3.1-3.2
    — not reference parity: the reference runs torch momentum-SGD on the
    sparse GLOBAL update) moves momentum BEFORE compression: each device
    keeps a local velocity ``u = momentum*u + grad``, the accumulated
    velocity ``v += u`` is what top-k selects from, transmitted
    coordinates are zeroed out of BOTH v and u (momentum factor masking),
    and the inner optimizer applies the reduced update without further
    momentum. This corrects the staleness that plain post-collective
    momentum suffers when a coordinate is transmitted only once every
    ~1/rho steps. Under gTop-k, masking follows the LOCAL selection: a
    locally-picked but globally-rejected coordinate keeps its VALUE in
    the residual (the error-feedback repair) but its velocity u stays
    masked (the measured decision is in the NOTE at the flat form's repair
    below). During a
    ``warmup_dense_steps`` phase the DENSE mean of u is communicated,
    which is algebraically identical to classic momentum-SGD on the mean
    gradient (mean is linear in u) — exactly the dense baseline at
    weight_decay=0; with weight decay the two differ in whether the
    wd·params term passes through the momentum trace (dense baseline)
    or is added un-momentum'd after the collective (correction).

    ``telemetry`` (obs subsystem) computes the on-device training-health
    counters (obs.counters.TELEMETRY_FIELDS: achieved wire density, top-k
    threshold tau, pre/post-compression gradient norms, error-feedback
    residual norm, modeled wire bytes) inside the jitted update and
    stores them in ``state.telemetry`` — a handful of scalar reductions,
    fused into ops the step already runs; under a bound mesh axis they
    are pmean'd so the stored values are replicated. Off by default: the
    disabled path traces bit-identically to before the flag existed.

    ``telemetry_layers`` (requires ``telemetry``) additionally resolves
    the counters PER LAYER (obs.counters.LAYER_FIELDS — achieved
    density, tau, pre/post grad norm, residual norm, mean residual age,
    mass-capture ratio m(k), arXiv:1911.08772) as f32[L] arrays under
    ``state.telemetry["layers"]``, where index i is leaf i of the grads
    pytree in jax.tree flatten order (obs.counters.layer_names gives the
    matching names). Layer identity is static trace-time structure, so
    the flat modes pay a few segment reductions over the [N] vector and
    the layerwise mode a small reduction per leaf; the
    ``state.telemetry["age"]`` buffer (steps since each coordinate last
    shipped, residual layout) updates from the globally-reduced update,
    which is replicated, so it needs no collective and is excluded from
    the pmean.

    ``telemetry_audit_interval`` > 0 (requires ``telemetry``) runs an
    exact-vs-production top-k recall audit every that-many optimizer
    steps: the exact top-k of the error-feedback accumulator (ops.topk's
    exact path as ground truth) is compared against the set the
    production kernel actually selected, and the recall fraction lands
    in ``state.telemetry["audit_recall"]`` (pmean of per-device
    recalls). Between audits the last audited value is carried; -1 means
    never audited (e.g. still in the dense warm-up phase). The exact
    top-k runs under a lax.cond, so non-audit steps pay nothing.
    """
    mode = compression
    if mode not in ALL_MODES:
        raise ValueError(f"unknown compression mode {mode!r}")
    hier = mode in HIER_MODES
    layerwise = mode in LAYERWISE_MODES
    if hier_ici_size < 1:
        raise ValueError(f"hier_ici_size must be >= 1, got {hier_ici_size}")
    if hier_ici_size > 1 and not hier:
        raise ValueError(
            f"hier_ici_size={hier_ici_size} only applies to hierarchical "
            f"modes {HIER_MODES}, not {mode!r}"
        )
    if warmup_dense_steps < 0:
        raise ValueError(
            f"warmup_dense_steps must be >= 0, got {warmup_dense_steps}"
        )
    if telemetry_audit_interval < 0:
        raise ValueError(
            f"telemetry_audit_interval must be >= 0, got "
            f"{telemetry_audit_interval}"
        )
    if (telemetry_layers or telemetry_audit_interval) and not telemetry:
        raise ValueError(
            "telemetry_layers / telemetry_audit_interval extend the "
            "telemetry counters; they require telemetry=True")
    audit = telemetry_audit_interval > 0
    if nesterov and not momentum:
        # torch.optim.SGD raises here too; silently running plain SGD while
        # the user believes Nesterov is active would be worse.
        raise ValueError("nesterov momentum requires momentum > 0")
    dense_mode = mode in DENSE_MODES
    correction = momentum_correction
    if correction:
        if dense_mode:
            raise ValueError(
                "momentum_correction only applies to sparse modes (the "
                "dense path IS classic momentum-SGD already)")
        if not momentum:
            raise ValueError("momentum_correction requires momentum > 0")
        if nesterov:
            raise ValueError(
                "momentum_correction defines its own velocity recursion; "
                "nesterov is not expressible in it")
    if correction and layerwise:
        import warnings

        # Measured, twice: the combination underperforms BOTH parents at
        # the 200-step A/B (val_top1 0.250 vs 0.734 correction-alone /
        # 0.281 layerwise-alone), and the round-3 masking ablations show
        # it is not a masking-semantics bug (restoring rejected-pick
        # velocities collapses it further, 0.094): per-leaf quota
        # selection neutralizes the velocity-informed global ranking that
        # makes correction work. Allowed (long-budget behavior unknown)
        # but loudly non-default.
        warnings.warn(
            "gtopk_layerwise x momentum_correction measured WORSE than "
            "either alone (benchmarks/results/warmup_ab_cpu_mesh8.json: "
            "cold val_top1 0.250 vs 0.734/0.281; masking ablations rule "
            "out a semantics fix) — prefer one or the other",
            stacklevel=2)
    # The residual's form. With no mesh axis named, a flat mode's step runs
    # on one device (slabs_form) and the state itself is in that step's
    # form, the slabs of compression.LeafPlan: known when the state is
    # made. With an axis named and unbound, or bound at size 1, the state
    # is the flat [N] that a P > 1 step of the same transformation reads,
    # and slabs_form cuts it into slabs and joins it again.
    slab_state = leaf_form_state(mode, axis_name)
    # The slice width the wire and its model are told: 1 but in hier mode.
    ici = hier_ici_size if hier else 1
    compressor = get_compressor(mode, density=density, method=topk_method)
    # Validate the codec spec at build time (bad --wire-codec fails here,
    # not inside the jitted step); the instance is reused every step.
    codec = get_codec(wire_codec)
    # Same build-time discipline for the wire plan: a name that does not
    # realize this mode fails here. The plan was decided above this
    # function (Trainer.__init__, parallel.planner.build_decision);
    # resolve_plan below looks its name up, and 'auto' is the mode's
    # historical schedule. The codec's canonical name labels the plan
    # (wire_codec may be a WireCodec instance).
    comm_plan = validate_pin(comm_plan, mode, ici_size=hier_ici_size)
    codec_spec = getattr(codec, "name", "fp32")
    # Same build-time discipline for --buckets: the spec parses (or
    # fails) here; the partition itself is resolved at trace time, when
    # the leaf sizes are known (plan_buckets below, memoized in the
    # bucketing DP). Bucketing is a layerwise merge granularity — every
    # other mode has exactly one wire set per step by construction.
    bucket_spec = parse_buckets(buckets)
    if bucket_spec != "concat" and not layerwise:
        raise ValueError(
            f"--buckets {buckets!r} only applies to the layerwise mode "
            f"{LAYERWISE_MODES}; {mode!r} has a single wire set per step "
            "already (use --buckets concat)")
    # Same build-time discipline for --pipeline: the spec parses (or
    # fails) here; 'auto' resolves at trace time inside plan_buckets,
    # where the partition and span model live. Overlap needs a bucket
    # axis to pipeline over — a concat wire has ONE select and ONE
    # merge per step, nothing to double-buffer ('auto' degrades to
    # serial there instead of failing, because there is no decision to
    # make).
    pipeline_spec = parse_pipeline(pipeline)
    if pipeline_spec == "overlap" and bucket_spec == "concat":
        raise ValueError(
            f"--pipeline overlap requires a bucketed layerwise wire "
            f"(--buckets leaf|auto|<int B>); --buckets concat has a "
            "single select/merge pair per step, so there are no stages "
            "to overlap (use --pipeline serial or auto)")
    inner = optax.chain(
        optax.add_decayed_weights(weight_decay) if weight_decay else optax.identity(),
        # With momentum correction the velocity lives BEFORE the collective
        # (in state.residual["u"]); the inner optimizer must not apply
        # momentum a second time.
        optax.sgd(learning_rate,
                  momentum=None if correction else (momentum or None),
                  nesterov=nesterov),
    )

    def bound_axis_size() -> int:
        """Size of the mesh axis `update` is actually tracing under (static).
        1 when axis_name is unset or unbound (single-worker path)."""
        if axis_name is None:
            return 1
        try:
            p = lax.axis_size(axis_name)
        except NameError:  # not inside shard_map over axis_name
            if axis_size is not None and axis_size > 1:
                # The caller explicitly expects a multi-device run; falling
                # back to p=1 would silently skip every collective and let
                # replicas drift. Fail loudly instead.
                raise ValueError(
                    f"axis_size={axis_size} was given but mesh axis "
                    f"{axis_name!r} is not bound — is update() running "
                    "inside jax.shard_map over that axis?"
                ) from None
            return 1
        if axis_size is not None and axis_size != p:
            raise ValueError(
                f"axis_size={axis_size} disagrees with mesh axis "
                f"{axis_name!r} of size {p}"
            )
        return p

    def _zero_slabs(params):
        return tuple(jnp.zeros(shape, jnp.float32)
                     for shape in leaf_plan(params).slab_shapes)

    def _init_telemetry(params):
        tel = obs_counters.zero_telemetry()
        if telemetry_layers:
            tel.update(obs_counters.zero_layer_telemetry(
                obs_counters.layer_sizes(params), per_leaf_age=layerwise))
            if slab_state:
                tel["age"] = _zero_slabs(params)
        if audit:
            tel["audit_recall"] = jnp.float32(-1.0)
        return tel

    def init_fn(params) -> GTopKSGDState:
        if layerwise:
            residual = tuple(
                jnp.zeros((int(leaf.size),), jnp.float32)
                for leaf in jax.tree.leaves(params)
            )
        elif slab_state:
            residual = _zero_slabs(params)
        else:
            flat, _ = ravel_pytree(params)
            residual = compressor.init_residual(flat.shape[0])
        if correction:
            # v: the accumulated-velocity buffer selection reads (plays the
            # error-feedback residual's role); u: the local momentum buffer.
            residual = {"v": residual,
                        "u": jax.tree.map(jnp.zeros_like, residual)}
        return GTopKSGDState(
            count=jnp.zeros((), jnp.int32),
            residual=residual,
            inner=inner.init(params),
            telemetry=_init_telemetry(params) if telemetry else (),
        )

    @jax.named_scope(obs_counters.SCOPE)
    def _finish_telemetry(tel, p):
        """pmean the per-device scalars (and [L] layer stats) when a mesh
        axis is bound so the stored telemetry is replicated (out_specs
        P() in the trainer); per-device quantities (residual norm, sent
        count) become axis means — the aggregate a dashboard wants
        anyway. The "age" buffer is EXCLUDED: it is replicated by
        construction (derived from the globally-reduced update), and
        pmean'ing it would spend an O(N) collective on a no-op."""
        if p > 1:
            tel = {
                key: (v if key == "age" else jax.tree.map(
                    lambda x: lax.pmean(x, axis_name), v))
                for key, v in tel.items()
            }
        return tel

    def selection_counters(scalars, layer_stats, recall):
        """A branch's counters, as the tail (``(dict,)`` or ``()``) of what
        it returns: the same keys from the sparse and the dense branch, so
        that both arms of the warm-up ``lax.cond`` have one structure. The
        arguments are thunks (``scalars() -> (tau, sent, m_k)``): each
        runs only where telemetry, telemetry_layers or the audit asks."""
        if not telemetry:
            return ()
        tau, sent, m_k = scalars()
        tel = {"tau": tau, "sent": sent, "m_k": m_k}
        if telemetry_layers:
            tel["lsel"], _ = layer_stats()
        if audit:
            tel["recall"] = recall()
        return (tel,)

    def dense_counters(form):
        """The dense phase's counters (the dense modes, and a sparse
        mode's warm-up): no threshold, everything sent, full mass capture,
        nothing to audit."""
        return selection_counters(
            lambda: (jnp.float32(0.0), jnp.float32(form.n),
                     jnp.float32(1.0)),
            lambda: obs_counters.dense_phase_selection_stats(form.sizes),
            lambda: jnp.float32(-1.0))

    def audited(count, exact_recall):
        """Sampled exact-vs-production recall: ``exact_recall()`` takes the
        exact top-k of the accumulator as ground truth and compares it with
        the production selection. It (and whatever [N] operands it builds)
        exists only inside the cond's taken branch: a step that is not
        audited pays nothing and reports -1."""
        return lax.cond((count % telemetry_audit_interval) == 0,
                        exact_recall, lambda: jnp.float32(-1.0))

    def flat_form(grads, count, p) -> _Form:
        """One [N] vector: the dense modes, and the index form the wire
        needs at P > 1 (a sparse mode comes here only with a wire)."""
        sizes = obs_counters.layer_sizes(grads)
        n = sum(sizes)
        n_layers = len(sizes)
        # Static trace-time layer structure: ravel_pytree flattens in
        # jax.tree order, so the segment map addresses the same leaves
        # obs_counters.layer_names reports.
        seg = obs_counters.segment_ids(sizes) if telemetry_layers else None
        # The wire plan named at build time; the dense modes have no
        # sparse wire to plan.
        plan = (None if dense_mode else
                resolve_plan(mode, comm_plan, codec=codec_spec, ici_size=ici))

        def dense_mean(src):
            # In hier mode the input is already the within-slice SUM
            # (ici_dense_psum in update_fn), so a full-axis psum counts
            # every original gradient hier_ici_size times — divide it back
            # out or every warm-up step trains at an ici_size-inflated
            # effective LR.
            reduced = (dense_allreduce(src, axis_name=axis_name)
                       if p > 1 else src)
            return reduced / (p * ici)

        def sparse_branch(src, residual_in, u_in):
            acc = compressor.accumulate(src, residual_in)
            vals, idx, residual = compressor.compress(
                acc, grad=src, residual=residual_in)
            if codec.lossy and mode != "topk":
                # Fold the wire quantization error into the
                # error-feedback residual and ship the requantized
                # values: the residual repair below then restores vq +
                # folded error = the exact original for rejected picks,
                # and telemetry (tau/sent/mass) describes what actually
                # went on the wire. (mode 'topk' allgathers the exact
                # local picks — its codec path quantizes in
                # topk_allgather and every pick is delivered, so there is
                # nothing to repair and the small symmetric error is left
                # to the next step's selection, like any dense rounding.)
                vq = roundtrip_aligned(codec, vals, idx, n=n)
                residual = compressor.fold_wire_error(
                    residual, idx, vals - vq)
                vals = vq

            def exact_recall():
                ev, ei = topk_abs(acc, compressor.k(n))
                return obs_counters.topk_recall(membership_mask(ei, idx), ev)

            # Selection stats describe the LOCAL selection (what this
            # device put on the wire); the pmean in _finish_telemetry
            # turns them into axis means.
            tel = selection_counters(
                lambda: (obs_counters.selected_tau(vals),
                         obs_counters.sent_count(vals),
                         obs_counters.mass_ratio(acc, vals)),
                lambda: obs_counters.sparse_selection_layer_stats(
                    acc, vals, idx, seg, n_layers),
                lambda: audited(count, exact_recall))
            # Momentum factor masking: a DELIVERED coordinate's velocity
            # restarts (its momentum was consumed); without this the same
            # mass re-sends for ~1/momentum more steps. For the allgather
            # union every local pick is delivered, so masking at the
            # local selection is exact.
            u_out = (u_in.at[idx].set(0.0, mode="drop")
                     if correction else u_in)
            result, gidx, needs_repair = sparse_allreduce(
                mode, vals, idx, k=compressor.k(n), n=n,
                axis_name=axis_name, axis_size=p, ici_size=ici,
                codec=codec, plan=plan,
            )
            if needs_repair:  # gtopk: sparse set + repair
                residual = compressor.repair(residual, vals, idx, gidx)
                dense = scatter_add_dense(n, gidx, result) / p
                # NOTE (measured design decision): under gTop-k a local
                # pick can be globally REJECTED; one could argue its
                # velocity should survive (nothing was transmitted).
                # Measured ablation says NO: the repair above already
                # preserves the rejected VALUE in v, so also keeping u
                # double-tracks the same mass (v += u while u compounds)
                # and persistently-rejected coordinates blow up — see
                # restore_rejected_u_ablation in the
                # warmup_ab_cpu_mesh8.json artifact. The local mask above
                # is the stable generalization, here and in the leaves
                # form (where per-leaf ceil rounding makes tiny leaves
                # pick, and usually get rejected, EVERY step).
            else:  # allgather union: dense, every pick lands
                dense = result / p
            return (dense, residual, u_out) + tel

        return _Form(
            n=n, sizes=sizes, k=n if dense_mode else compressor.k(n),
            plan=plan, split=ravel_pytree,
            sq_norm=lambda flat: jnp.sum(flat * flat),
            sparse_branch=sparse_branch, dense_mean=dense_mean,
            layer_l2=lambda x: obs_counters.seg_l2(x, seg, n_layers),
            layer_age=lambda age: dict(age=age, seg=seg))

    def leaves_form(grads, count, p) -> _Form:
        """Every leaf reshaped flat (``gtopk_layerwise``): per-layer
        select and error feedback, one buffer a layer and never one [N]
        vector; the global reduce runs on the concatenated set, or bucket
        by bucket. Leaf order is jax.tree.flatten order of the grads
        pytree, which init_fn used for the residual, so the two always
        align."""
        leaves, treedef = jax.tree.flatten(grads)
        sizes = [int(leaf.size) for leaf in leaves]
        ks = [k_for_density(s, density) for s in sizes]
        offsets, off = [], 0
        for s in sizes:
            offsets.append(off)
            off += s
        n = off
        kk_total = sum(ks)
        # Bucket partition for this (leaf_sizes, density, p, codec) — the
        # alpha-beta DP of parallel.bucketing; None under the historical
        # 'concat' wire. Resolved host-side at trace time (the DP table is
        # memoized), so boundaries are static structure from here on, like
        # offsets and ks.
        bplan = (plan_buckets(tuple(sizes), density, buckets=bucket_spec,
                              p=p, codec=codec_spec, mode=mode,
                              pipeline=pipeline_spec)
                 if bucket_spec != "concat" else None)
        # The wire plan named at build time; None at p=1 (no wire).
        plan = (resolve_plan(mode, comm_plan, codec=codec_spec)
                if p > 1 else None)

        def split(grads):
            def join(dense_fl):
                return treedef.unflatten([
                    d.reshape(leaf.shape)
                    for d, leaf in zip(dense_fl, leaves)])

            return tuple(g.reshape(-1) for g in jax.tree.leaves(grads)), join

        def threshold_counters(sel, keeps, accs, dense_parts, layer_parts,
                               exact_recall):
            """Counters of the P = 1 threshold form, over leaves or over
            buckets: the whole-model tau from the per-part kept-taus the
            compressor already reduced (a part with a nonempty keep set
            always has tau > 0 — zeros never pass)."""
            def scalars():
                taus = jnp.stack([t for _, _, t in sel])
                kept = taus > 0
                tau = jnp.where(
                    jnp.any(kept),
                    jnp.min(jnp.where(kept, taus, jnp.inf)), 0.0)
                return (tau, sum(obs_counters.kept_count(m) for m in keeps),
                        obs_counters.mass_ratio(accs, dense_parts))

            return selection_counters(
                scalars,
                lambda: obs_counters.leafwise_selection_stats(*layer_parts()),
                lambda: audited(count, exact_recall))

        def sparse_branch(srcs, res_in, us):
            accs = [s + r for s, r in zip(srcs, res_in)]

            def exact_recall(hits_fn):
                """Exact top-kk_total of the concatenated accumulator as
                ground truth; ``hits_fn(exact_idx) -> bool[k]`` is
                membership in the production selection."""
                def _do():
                    ev, ei = topk_abs(jnp.concatenate(accs), kk_total)
                    return obs_counters.topk_recall(hits_fn(ei), ev)
                return _do

            if p == 1:
                # Threshold form of the per-leaf selection (see
                # compress_by_threshold's docstring): each leaf's top-k_l
                # becomes one small reduction for tau_l plus elementwise
                # masks — dropping the per-leaf scatter+gather pairs,
                # which at ~161 leaves were ~2x161 extra kernels on the
                # step. The per-leaf k = ceil(density * n_l) is exactly
                # compressor.k(n_l), so the shared helper applies
                # unchanged leaf by leaf.
                sel = [compressor.compress_by_threshold(
                           a, grad=s, residual=r)
                       for a, s, r in zip(accs, srcs, res_in)]
                keeps = [keep for keep, _, _ in sel]
                new_res = [r for _, r, _ in sel]
                u_out = (tuple(jnp.where(m, 0.0, u)
                               for u, m in zip(us, keeps))
                         if correction else us)
                dense_fl = [a - r for a, r in zip(accs, new_res)]
                tel = threshold_counters(
                    sel, keeps, accs, dense_fl, lambda: (accs, dense_fl),
                    exact_recall(lambda ei: jnp.take(
                        jnp.concatenate(keeps), ei, mode="clip")))
                return (tuple(dense_fl), tuple(new_res), u_out) + tel
            sel = [select_topk(s, kl, topk_method, residual=r)
                   for s, r, kl in zip(srcs, res_in, ks)]
            idx_l = [i for _, i in sel]
            new_res = [a.at[i].set(0.0, mode="drop")
                       for a, i in zip(accs, idx_l)]
            # Momentum factor masking, per leaf, at the LOCAL selection
            # (see the measured-ablation note in the flat form).
            u_out = (tuple(u.at[i].set(0.0, mode="drop")
                           for u, i in zip(us, idx_l))
                     if correction else us)
            vals = jnp.concatenate([v for v, _ in sel])
            idx = jnp.concatenate([
                (i + o).astype(jnp.int32)
                for i, o in zip(idx_l, offsets)
            ])

            def add_per_leaf(bufs, addend):
                """Scatter a [kk_total] vector in concatenation order back
                into the leaves' buffers: static [pos:pos+k_l] slices
                address each leaf's candidates."""
                out, pos = [], 0
                for r, i, kl in zip(bufs, idx_l, ks):
                    out.append(r.at[i].add(addend[pos:pos + kl], mode="drop"))
                    pos += kl
                return out

            if codec.lossy:
                # Wire-error fold, layerwise twin: requantize the
                # concatenated set, ship vq, and scatter the error back
                # into each leaf's residual — the error is in
                # concatenation order because roundtrip_aligned returns
                # original slot order.
                vq = roundtrip_aligned(codec, vals, idx, n=n)
                new_res = add_per_leaf(new_res, vals - vq)
                vals = vq
            gvals, gidx, _ = sparse_allreduce(
                mode, vals, idx, k=kk_total, n=n,
                axis_name=axis_name, axis_size=p, codec=codec,
                plan=plan,
            )
            # Error-feedback repair, split back per leaf: put_back's layout
            # IS the concatenation order. u stays masked at the full LOCAL
            # selection even for globally-rejected picks.
            rejected = ~membership_mask(idx, gidx)
            repaired = add_per_leaf(new_res, jnp.where(rejected, vals, 0.0))
            dense = scatter_add_dense(n, gidx, gvals) / p
            dense_fl = tuple(dense[o:o + s] for o, s in zip(offsets, sizes))
            # Selection stats describe the LOCAL selection (what this
            # device put on the wire), matching sent_elems /
            # achieved_density semantics; the pmean in _finish_telemetry
            # turns them into axis means.
            tel = selection_counters(
                lambda: (obs_counters.selected_tau(vals),
                         obs_counters.sent_count(vals),
                         obs_counters.mass_ratio(accs, vals)),
                lambda: obs_counters.leafwise_sparse_selection_stats(
                    accs, [v for v, _ in sel]),
                lambda: audited(count, exact_recall(
                    lambda ei: membership_mask(ei, idx))))
            return (dense_fl, tuple(repaired), u_out) + tel

        def bucketed_sparse_branch(srcs, res_in, us):
            """Per-BUCKET select/feedback/merge (parallel.bucketing).

            Same pipeline as sparse_branch run B times over bucket
            concatenations instead of once over leaves + one global
            merge: each bucket's (grad, residual) leaves concatenate
            into one flat operand, selection runs once per bucket with
            k_b = ceil(density * n_b), and the merge runs in the
            BUCKET-LOCAL index space (n = n_b) — B collectives per step,
            each a strictly smaller instance of the same codec-framed
            exchange. State stays per leaf: the residual, update, and
            (under correction) velocity scatter back through the static
            bucket offsets, so checkpoints and the warm-up dense branch
            see the identical per-leaf structure. At B=1 this IS the
            flat gtopk pipeline over the raveled model; at B=L it is
            per-leaf selection with per-leaf merges."""
            B = bplan.n_buckets
            ranges = [bplan.leaf_range(b) for b in range(B)]
            bks = list(bplan.ks)
            bns = list(bplan.sizes)

            def bconcat(parts):
                return [parts[lo] if hi - lo == 1
                        else jnp.concatenate(parts[lo:hi])
                        for lo, hi in ranges]

            def bsplit(bufs):
                """Per-bucket flats -> per-leaf flats (static slices)."""
                out = []
                for (lo, hi), buf in zip(ranges, bufs):
                    off = 0
                    for s in sizes[lo:hi]:
                        out.append(buf[off:off + s])
                        off += s
                return tuple(out)

            bsrcs = bconcat(srcs)
            bres = bconcat(res_in)
            bus = bconcat(us) if correction else []
            accs = [s + r for s, r in zip(bsrcs, bres)]

            def exact_recall(hits_fn_per_bucket):
                """Recall against the bucketed ground truth: per-bucket
                exact top-k_b (the contract the bucketed selection
                implements), hits concatenated into one recall
                fraction."""
                def _do():
                    hits, evs = [], []
                    for b, (a, kb) in enumerate(zip(accs, bks)):
                        ev, ei = topk_abs(a, kb)
                        hits.append(hits_fn_per_bucket(b, ei))
                        evs.append(ev)
                    return obs_counters.topk_recall(
                        jnp.concatenate(hits), jnp.concatenate(evs))
                return _do

            if p == 1:
                # Threshold form per bucket (see sparse_branch's p=1
                # note): compressor.k(n_b) == k_b by construction, so
                # the shared helper applies bucket by bucket.
                sel = [compressor.compress_by_threshold(
                           a, grad=s, residual=r)
                       for a, s, r in zip(accs, bsrcs, bres)]
                keeps = [keep for keep, _, _ in sel]
                new_res = [r for _, r, _ in sel]
                u_out_b = ([jnp.where(m, 0.0, u)
                            for u, m in zip(bus, keeps)]
                           if correction else [])
                dense_b = [a - r for a, r in zip(accs, new_res)]
                # Per-leaf stats from per-leaf slices of the bucket
                # accumulator/selection — same values the unbucketed
                # path reduces, just sliced out of the concatenations.
                tel = threshold_counters(
                    sel, keeps, accs, dense_b,
                    lambda: (bsplit(accs), bsplit(dense_b)),
                    exact_recall(lambda b, ei: jnp.take(
                        keeps[b], ei, mode="clip")))
                dense_fl = bsplit(dense_b)
                res_fl = bsplit(new_res)
                u_out = bsplit(u_out_b) if correction else us
                return (dense_fl, res_fl, u_out) + tel
            # --- Pipelined stage loop (--pipeline) ------------------
            # Each bucket is two stages: _select (the fused two-stage
            # local selection + error-feedback zero-out + codec error
            # fold) and _merge (the codec-framed collective in the
            # bucket-local index space + rejected-pick repair + dense
            # scatter). Both execution orders below run the SAME ops on
            # the SAME values — lax.optimization_barrier is the
            # identity — and differ ONLY in the dependence edges handed
            # to XLA's scheduler, so serial and overlap results
            # (params, residuals, telemetry) are bit-identical by
            # construction (test-pinned against the numpy oracle).

            def _select(b, gate=None):
                """Stage 1 of bucket b. ``gate`` (the serial pin) is a
                pytree this stage must not start before: threading the
                stage inputs through one optimization_barrier with it
                makes every op of this stage depend on the gated
                values."""
                s, r = bsrcs[b], bres[b]
                u = bus[b] if correction else None
                if gate is not None:
                    (s, r, u), _ = lax.optimization_barrier(
                        ((s, r, u), gate))
                v, i = select_topk(s, bks[b], topk_method, residual=r)
                new_r = (s + r).at[i].set(0.0, mode="drop")
                # Momentum factor masking at the LOCAL (bucket)
                # selection — same measured-ablation rationale as the
                # other paths.
                u_out = u.at[i].set(0.0, mode="drop") if correction else None
                if codec.lossy:
                    # Wire-error fold per bucket: requantize in the
                    # bucket-local index space (the smaller n_b is
                    # exactly what shrinks the codec's index words) and
                    # fold the error into the bucket residual before
                    # the merge.
                    vq = roundtrip_aligned(codec, v, i, n=bns[b])
                    new_r = new_r.at[i].add(v - vq, mode="drop")
                    v = vq
                return {"v": v, "i": i, "res": new_r, "u": u_out}

            def _merge(b, st):
                """Stage 2 of bucket b: the collective, the
                error-feedback repair of globally-rejected picks, and
                the averaged dense scatter."""
                gvals, gidx, _ = sparse_allreduce(
                    mode, st["v"], st["i"], k=bks[b], n=bns[b],
                    axis_name=axis_name, axis_size=p, codec=codec,
                    plan=plan,
                )
                rejected = ~membership_mask(st["i"], gidx)
                return dict(
                    st,
                    res=st["res"].at[st["i"]].add(
                        jnp.where(rejected, st["v"], 0.0), mode="drop"),
                    dense=scatter_add_dense(bns[b], gidx, gvals) / p)

            outs = []
            if bplan.pipeline == "overlap" and B > 1:
                # Double-buffered stage loop: bucket b+1's selection is
                # issued with NO data dependency on bucket b's merge —
                # the selection compute runs while the ppermute rounds
                # are in flight — and the stage barrier makes merge b+1
                # wait on BOTH (one collective in flight, preserving
                # the schedule's round structure).
                nxt = _select(0)
                for b in range(B):
                    cur = nxt
                    nxt = _select(b + 1) if b + 1 < B else None
                    out = _merge(b, cur)
                    if nxt is not None:
                        out, nxt = lax.optimization_barrier((out, nxt))
                    outs.append(out)
            else:
                # Serial pin — the paper's strictly sequential
                # T_select + T_comm, and the bit-identity oracle: the
                # gate threads bucket b's merge outputs into bucket
                # b+1's selection inputs, so exactly one stage can be
                # in flight.
                gate = None
                for b in range(B):
                    out = _merge(b, _select(b, gate))
                    gate = (out["dense"], out["res"])
                    outs.append(out)
            idx_b = [o["i"] for o in outs]
            vals_b = [o["v"] for o in outs]
            dense_fl = bsplit([o["dense"] for o in outs])
            tel = selection_counters(
                lambda: (obs_counters.selected_tau(jnp.concatenate(vals_b)),
                         sum(obs_counters.sent_count(v) for v in vals_b),
                         obs_counters.mass_ratio(accs, vals_b)),
                lambda: obs_counters.bucketed_sparse_selection_stats(
                    accs, vals_b, idx_b, sizes, bplan.boundaries),
                lambda: audited(count, exact_recall(
                    lambda b, ei: membership_mask(ei, idx_b[b]))))
            res_fl = bsplit([o["res"] for o in outs])
            u_out = bsplit([o["u"] for o in outs]) if correction else us
            return (dense_fl, res_fl, u_out) + tel

        def dense_mean(srcs):
            if p == 1:
                return srcs
            return tuple(dense_allreduce(s, axis_name=axis_name) / p
                         for s in srcs)

        return _Form(
            n=n, sizes=sizes,
            k=bplan.k_total if bplan is not None else kk_total,
            plan=plan, buckets=bplan.pairs() if bplan is not None else None,
            split=split,
            # a Python sum of per-leaf sums — no concatenation needed
            sq_norm=lambda flats: sum(jnp.sum(f * f) for f in flats),
            sparse_branch=(sparse_branch if bplan is None
                           else bucketed_sparse_branch),
            dense_mean=dense_mean, layer_l2=obs_counters.leaf_l2,
            layer_age=lambda age: dict(age=age))

    def slabs_form(grads, count) -> _Form:
        """One device's step of the flat modes: one global k and one tau
        as in the [N] form, on the gradient's own leaves.

        No wire at P = 1, so nothing needs the (vals, idx) format, nor the
        [N] vector it indexes: the elementwise work (accumulate, masks,
        velocity, counters) runs on the **slabs** of compression.LeafPlan
        (a large leaf in its own shape and layout, the small ones in one
        short vector) and the threshold is one number over all of them
        (TopKCompressor.compress_leaves_by_threshold). Masking u at the
        keep mask is exact here: every local pick is delivered. A state
        made with a mesh axis named holds [N] buffers: they are cut into
        slabs on the way in and joined on the way out."""
        treedef = jax.tree.structure(grads)
        lplan = leaf_plan(grads)
        n = lplan.n

        def split(grads):
            def join(dense_sl):
                return treedef.unflatten(lplan.join(dense_sl))

            return tuple(lplan.split(jax.tree.leaves(grads))), join

        def sparse_branch(srcs, res_in, us):
            accs = [compressor.accumulate(s, r)
                    for s, r in zip(srcs, res_in)]
            keeps, new_res, tau_th = (
                compressor.compress_leaves_by_threshold(accs))
            with jax.named_scope("gtopk/mask"):
                dense_sl = [jnp.where(m, a, 0.0)
                            for m, a in zip(keeps, accs)]
                u_out = tuple(jnp.where(m, 0.0, u)
                              for u, m in zip(us, keeps))

            def exact_recall():
                # The exact top-k and the [N] operands it indexes.
                ev, ei = topk_abs(lplan.to_flat(accs), compressor.k(n))
                hits = jnp.take(lplan.to_flat(keeps), ei, mode="clip")
                return obs_counters.topk_recall(hits, ev)

            tel = selection_counters(
                lambda: (tau_th,
                         sum(obs_counters.kept_count(m) for m in keeps),
                         obs_counters.mass_ratio(accs, dense_sl)),
                lambda: obs_counters.leafwise_selection_stats(
                    lplan.join(accs), lplan.join(dense_sl)),
                lambda: audited(count, exact_recall))
            return (tuple(dense_sl), tuple(new_res), u_out) + tel

        return _Form(
            n=n, sizes=lplan.sizes, k=compressor.k(n), split=split,
            # per-slab sums stacked and added at once, so that no slab's
            # sum waits for the slab before it
            sq_norm=lambda slabs: jnp.sum(jnp.stack(
                [jnp.sum(f * f) for f in slabs])),
            sparse_branch=sparse_branch,
            # nothing to reduce at P = 1
            dense_mean=lambda srcs: srcs,
            layer_l2=lambda x: obs_counters.leaf_l2(lplan.join(x)),
            layer_age=lambda age: dict(age=tuple(lplan.join(age))),
            state_in=_same if slab_state else (
                lambda buf: tuple(lplan.from_flat(buf))),
            state_out=_same if slab_state else lplan.to_flat)

    def update_fn(grads, state: GTopKSGDState, params=None):
        """One optimizer step, whatever the form: split, clip, the
        correction's velocity, dense warm-up or select-and-reduce, join,
        the inner optimizer, the counters."""
        p = bound_axis_size()
        if layerwise:
            form = leaves_form(grads, state.count, p)
        elif dense_mode or p > 1:
            form = flat_form(grads, state.count, p)
        else:
            form = slabs_form(grads, state.count)
        # The form's own passes are stages like the others
        # (trainer._build_train_step lists them): flatten, clip, unflatten.
        with jax.named_scope("gtopk/flatten"):
            parts, join = form.split(grads)
            state_res = _each_buffer(form.state_in, state.residual)
        if clip_grad_norm is not None:
            # Reference LSTM path: clip the raw local gradient BEFORE the
            # residual accumulate/compress (order matters for convergence).
            with jax.named_scope("gtopk/clip"):
                gnorm = jnp.sqrt(form.sq_norm(parts))
                scale = jnp.minimum(1.0, clip_grad_norm / (gnorm + 1e-6))
                parts = jax.tree.map(lambda f: f * scale, parts)
        if hier and p > 1:
            if p % hier_ici_size != 0:
                raise ValueError(
                    f"axis size {p} not divisible by "
                    f"hier_ici_size={hier_ici_size}"
                )
            # Level 1 (the flat form: hier has a wire here): dense sum
            # within the ICI slice, BEFORE error feedback — the slice acts
            # as one logical worker from here on, and all of its devices
            # hold identical acc/top-k/residual.
            parts = ici_dense_psum(
                parts, axis_name=axis_name, axis_size=p,
                ici_size=hier_ici_size,
            )
        if correction:
            # DGC velocity recursion on the LOCAL (or slice-summed, in
            # hier mode) gradient; selection reads v + u.
            res_in = state_res["v"]
            us = jax.tree.map(lambda u, f: momentum * u + f,
                              state_res["u"], parts)
            srcs = us
        else:
            res_in, us, srcs = state_res, (), parts

        def dense_branch(srcs, res_in, us):
            # The residual passes through and, with correction, the mean
            # of u IS classic momentum on the mean gradient (mean is
            # linear in u); u is NOT masked (nothing was transmitted
            # sparsely).
            return (form.dense_mean(srcs), res_in, us) + dense_counters(form)

        if dense_mode:
            out = dense_branch(srcs, res_in, us)
        elif warmup_dense_steps > 0:
            out = lax.cond(
                state.count < warmup_dense_steps,
                dense_branch, form.sparse_branch, srcs, res_in, us,
            )
        else:
            out = form.sparse_branch(srcs, res_in, us)
        dense, res_struct, u_new, *btel = out
        residual = {"v": res_struct, "u": u_new} if correction else res_struct

        with jax.named_scope("gtopk/unflatten"):
            avg_grads = join(dense)
            residual = _each_buffer(form.state_out, residual)
        with jax.named_scope("gtopk/apply"):
            updates, inner_state = inner.update(
                avg_grads, state.inner, params)
        tel = state.telemetry
        if telemetry:
            (btel,) = btel
            tel = obs_counters.make_telemetry(
                n=form.n, k=form.k, p=p, mode=mode,
                ici_size=ici, codec=codec,
                schedule=form.plan.schedule if form.plan is not None else None,
                buckets=form.buckets,
                grad_norm_pre=obs_counters.tree_l2(parts),
                grad_norm_post=obs_counters.tree_l2(dense),
                residual_norm=obs_counters.tree_l2(res_struct),
                tau=btel["tau"], sent_elems=btel["sent"],
                m_k=btel["m_k"],
            )
            if telemetry_layers:
                # Delivered = appeared in the globally-reduced update,
                # which is replicated — so the age buffer stays
                # replicated without a collective (see update_age), in
                # the residual's form.
                age = obs_counters.update_age(
                    form.state_in(state.telemetry["age"]),
                    jax.tree.map(lambda d: d != 0, dense))
                tel["layers"] = obs_counters.assemble_layer_telemetry(
                    sel_stats=btel["lsel"], sizes=form.sizes,
                    grad_norm_pre_l=form.layer_l2(parts),
                    grad_norm_post_l=form.layer_l2(dense),
                    residual_norm_l=(
                        jnp.zeros((len(form.sizes),), jnp.float32)
                        if dense_mode else form.layer_l2(res_struct)),
                    **form.layer_age(age))
                tel["age"] = form.state_out(age)
            if audit:
                # Carry the last audited value between audits; -1 means
                # never audited (dense warm-up / dense mode included).
                tel["audit_recall"] = jnp.where(
                    btel["recall"] >= 0.0, btel["recall"],
                    state.telemetry["audit_recall"])
            tel = _finish_telemetry(tel, p)
        new_state = GTopKSGDState(
            count=state.count + 1, residual=residual, inner=inner_state,
            telemetry=tel,
        )
        return updates, new_state

    return optax.GradientTransformation(init_fn, update_fn)


def leaf_form_state(compression: Optional[str],
                    axis_name: Optional[str]) -> bool:
    """Whether ``gtopk_sgd``'s state holds its buffers as slabs (a flat
    sparse mode with no mesh axis named) and not as [N] vectors."""
    return (axis_name is None and compression not in DENSE_MODES
            and compression not in LAYERWISE_MODES)


def leaf_plan(tree):
    """compression.LeafPlan of a parameter (or gradient) tree's shapes."""
    return plan_leaves([leaf.shape for leaf in jax.tree.leaves(tree)])


def flat_residual(residual, params, xp=jnp):
    """A leaf-form state's buffers (``residual``, its ``v`` and ``u``, the
    ``age``: slabs of compression.LeafPlan, what a flat mode's state holds
    when no mesh axis is named) as the [N] vectors of ``ravel_pytree``'s
    order, the form a P > 1 state and an older checkpoint hold. ``xp`` is
    numpy for a host-side conversion."""
    plan = leaf_plan(params)
    return jax.tree.map(lambda slabs: plan.to_flat(slabs, xp), residual,
                        is_leaf=lambda x: isinstance(x, tuple))


def slab_residual(flat, params, xp=jnp):
    """``flat_residual``'s inverse: [N] vectors -> tuples of slabs."""
    plan = leaf_plan(params)
    return jax.tree.map(lambda vec: tuple(plan.from_flat(vec, xp)), flat)


def expand_residual_per_device(opt_state: GTopKSGDState, p: int, mesh):
    """Lift the freshly-initialized residual to the per-device [P, ...]
    convention used under shard_map (leading dim = 'dp'; strip with
    tree-mapped ``r[0]`` inside the block, restore with ``r[None]`` on the
    way out). Works leaf-wise, so it covers both the flat-[N] residual and
    the layerwise per-leaf pytree. The residual at init is zeros by
    construction, so each device's shard is created DIRECTLY in its
    P('dp') placement (make_array_from_callback) — a host-side broadcast
    would materialize the dense [P, N] array on one device first (1.6 GB
    for ResNet-50 x 16 workers), and a jitted zeros-with-out_shardings
    hits a jax sharding-override assertion when the persistent compilation
    cache is enabled.
    """
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(mesh, PartitionSpec("dp"))
    replicated = NamedSharding(mesh, PartitionSpec())

    def expand(res):
        res_shape = (p,) + res.shape
        if res.size == 0:
            # The dense modes' [P, 0] placeholder. jit hands a zero-size
            # output back replicated whatever out_specs say, so placing it
            # P('dp') here would make dispatch 2 see a new input sharding
            # and recompile the whole step (38 s for ResNet-50 at dp=4 on
            # the chip, PR 21).
            return jax.device_put(np.zeros(res_shape, res.dtype), replicated)

        def shard_zeros(index):
            shape = tuple(len(range(*s.indices(dim)))
                          for s, dim in zip(index, res_shape))
            return np.zeros(shape, res.dtype)

        return jax.make_array_from_callback(res_shape, sharding, shard_zeros)

    return opt_state._replace(
        residual=jax.tree.map(expand, opt_state.residual))
