"""On-device training-health counters for the compression pipeline.

Everything here is traced INSIDE the jitted train step (pure jnp on
device-resident arrays — no host round-trips) and carried out through the
optimizer state's ``telemetry`` field, so the per-step numbers ride the
existing metrics path for every mode (gtopk, gtopk_layerwise, gtopk_hier,
allgather, dense).

The counter set is the paper's own analysis axis plus the residual
dynamics arXiv:1911.08772 shows convergence hinges on:

  grad_norm_pre    — L2 of the local gradient entering the pipeline
                     (post-clip, post ICI slice-sum in hier mode)
  grad_norm_post   — L2 of the averaged dense update actually applied
  residual_norm    — L2 of the error-feedback residual AFTER repair (the
                     v buffer under momentum correction)
  tau              — the top-k selection threshold: smallest selected
                     magnitude (0 in dense phases/modes)
  sent_elems       — actual NONZERO elements in the communicated set
                     (padding slots in a <k-nonzero step don't count)
  achieved_density — sent_elems / N vs. the requested rho
  wire_bytes       — the comm-volume model for this step's collective
                     (parallel.comm_bytes_per_step — O(k log P) gtopk,
                     O(k P) allgather, O(N) dense), a static per-step
                     constant that makes jsonl rows self-describing

All values are f32 scalars; under shard_map the optimizer pmeans them over
the dp axis so the stored telemetry is replicated (per-device quantities
like the residual norm become axis means, which is the number you want on
a dashboard anyway).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gtopkssgd_tpu.parallel import comm_bytes_per_step

Array = jax.Array

TELEMETRY_FIELDS = (
    "grad_norm_pre",
    "grad_norm_post",
    "residual_norm",
    "tau",
    "sent_elems",
    "achieved_density",
    "wire_bytes",
    "m_k",
    # Wire-level collective launches per optimizer step (f32 of a static
    # count, like wire_bytes): 0 at p=1, 1 for every single-merge wire,
    # B for the bucketed layerwise path, 2 for the hier mode's two
    # levels. The alpha side of the alpha-beta ledger: each launch pays
    # the per-collective latency that the bucketing DP optimizes, so the
    # bucket gate pins its >=3x merge-count reduction on this counter.
    "collective_count",
)

# Per-layer counter set (telemetry_layers=True). The mass-capture ratio
# m(k) = ||selected||^2 / ||acc||^2 and its per-layer skew are the
# quantities arXiv:1911.08772 ties to the top-k convergence gap;
# residual_age is the mean steps-since-a-coordinate-last-shipped, the
# staleness axis the whole-model residual norm cannot resolve.
LAYER_FIELDS = (
    "density",
    "tau",
    "grad_norm_pre",
    "grad_norm_post",
    "residual_norm",
    "residual_age",
    "m_k",
)

_MASS_EPS = 1e-30

# Every device function below runs under this named scope, so a device
# trace prices what the counters add to the step (perfbench: telemetry_ms).
SCOPE = "gtopk/telemetry"


def zero_telemetry() -> Dict[str, Array]:
    """The fixed telemetry structure at init (all zeros). init_fn uses this
    so the state pytree has an identical treedef at step 0 and step k."""
    return {f: jnp.zeros((), jnp.float32) for f in TELEMETRY_FIELDS}


@jax.named_scope(SCOPE)
def tree_l2(tree) -> Array:
    """L2 norm over every leaf of a pytree (flat arrays, per-leaf tuples,
    or a single array alike). Empty trees / zero-size leaves give 0."""
    leaves = [l for l in jax.tree.leaves(tree) if hasattr(l, "size")]
    if not leaves:
        return jnp.zeros((), jnp.float32)
    total = sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
    return jnp.sqrt(total)


def residual_l2(residual) -> Array:
    """L2 of the error-feedback buffer. Under momentum correction the
    residual field is ``{"v": ..., "u": ...}``; v is the accumulated-
    velocity buffer that plays the residual's role (optimizer.py), so the
    norm reads v only — including u would double-count momentum mass."""
    if isinstance(residual, dict) and "v" in residual:
        residual = residual["v"]
    return tree_l2(residual)


@jax.named_scope(SCOPE)
def selected_tau(vals: Array) -> Array:
    """Top-k threshold from a selected-values buffer: the smallest NONZERO
    selected magnitude. Selection kernels pad value slots with 0.0 when
    fewer than k nonzeros exist; a plain min would report tau=0 for every
    such step and hide the real threshold."""
    mags = jnp.abs(vals)
    nz = mags > 0
    t = jnp.min(jnp.where(nz, mags, jnp.inf))
    return jnp.where(jnp.any(nz), t, 0.0).astype(jnp.float32)


@jax.named_scope(SCOPE)
def keep_tau(keep: Array, acc: Array) -> Array:
    """tau for the mask-form selection (compress_by_threshold): smallest
    kept magnitude, 0 when nothing is kept."""
    mags = jnp.abs(acc)
    t = jnp.min(jnp.where(keep, mags, jnp.inf))
    return jnp.where(jnp.any(keep), t, 0.0).astype(jnp.float32)


@jax.named_scope(SCOPE)
def sent_count(vals: Array) -> Array:
    """Actual nonzeros in a communicated value buffer (f32 scalar)."""
    return jnp.sum((vals != 0).astype(jnp.float32))


@jax.named_scope(SCOPE)
def kept_count(keep: Array) -> Array:
    """Coordinates a keep mask (compress_by_threshold) selects (f32
    scalar): the mask-form counterpart of sent_count."""
    return jnp.sum(keep.astype(jnp.float32))


@jax.named_scope(SCOPE)
def make_telemetry(
    *,
    n: int,
    k: int,
    p: int,
    mode,
    ici_size: int = 1,
    codec="fp32",
    schedule=None,
    buckets=None,
    grad_norm_pre,
    grad_norm_post,
    residual_norm,
    tau,
    sent_elems,
    m_k=0.0,
) -> Dict[str, Array]:
    """Assemble the per-step telemetry dict (all f32 scalars).

    ``n``/``k``/``p``/``mode``/``ici_size``/``codec``/``schedule`` (the
    resolved wire plan's schedule, parallel.planner) are static
    trace-time values; ``wire_bytes`` therefore folds to a constant — the
    model volume for this step's collective from the one shared
    definition (parallel.comm_bytes_per_step), so the metric can never
    drift from the benchmark's comm model. With a quantized wire codec
    the constant is CODEC bytes (packed values + scales + bitpacked
    indices), not logical fp32 bytes.

    ``buckets`` — the bucketed layerwise path's ((n_b, k_b), ...) pairs
    (parallel.bucketing.BucketPlan.pairs) — makes ``wire_bytes`` the sum
    over the B merges actually issued (each over its bucket-local index
    space) and sets ``collective_count`` to B. Like wire_bytes, both are
    static: during a dense warm-up phase they still describe the sparse
    wire the run switches to."""
    sent = jnp.asarray(sent_elems, jnp.float32)
    if buckets:
        wire = sum(
            comm_bytes_per_step(mode, int(n_b), int(k_b), p,
                                ici_size=ici_size, codec=codec,
                                schedule=schedule)
            for n_b, k_b in buckets)
        n_coll = len(buckets) if p > 1 else 0
    else:
        wire = comm_bytes_per_step(mode, n, k, p, ici_size=ici_size,
                                   codec=codec, schedule=schedule)
        if p <= 1:
            n_coll = 0
        else:
            n_coll = 2 if (mode == "gtopk_hier" and ici_size > 1) else 1
    return {
        "grad_norm_pre": jnp.asarray(grad_norm_pre, jnp.float32),
        "grad_norm_post": jnp.asarray(grad_norm_post, jnp.float32),
        "residual_norm": jnp.asarray(residual_norm, jnp.float32),
        "tau": jnp.asarray(tau, jnp.float32),
        "sent_elems": sent,
        "achieved_density": sent / jnp.float32(max(1, n)),
        "wire_bytes": jnp.float32(wire),
        "m_k": jnp.asarray(m_k, jnp.float32),
        "collective_count": jnp.float32(n_coll),
    }


# --------------------------------------------------------------------------
# Per-layer counters (telemetry_layers). Everything below is still pure jnp
# traced inside the jitted step; layer identity is static trace-time
# structure (the grads pytree), so the only runtime cost is a handful of
# segment reductions over arrays the step already materializes.
# --------------------------------------------------------------------------


def layer_names(tree) -> Tuple[str, ...]:
    """Stable per-leaf names in jax.tree.flatten order — '/'-joined pytree
    key paths ('block1/conv1/kernel' for nested flax params). This is the
    SAME order ravel_pytree and the layerwise residual use, so index i of
    every [L] layer-stat array refers to names()[i]."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, _ in leaves:
        parts = []
        for k in path:
            parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
        out.append("/".join(parts) if parts else "param")
    return tuple(out)


def layer_sizes(tree) -> Tuple[int, ...]:
    """Per-leaf element counts in the same flatten order as layer_names."""
    return tuple(int(x.size) for x in jax.tree.leaves(tree))


def segment_ids(sizes: Sequence[int]) -> np.ndarray:
    """i32[N] coordinate->layer map for the flat [N] gradient layout — a
    trace-time numpy constant (XLA folds it), shared by every flat-mode
    segment reduction so layer boundaries cannot drift between fields."""
    return np.repeat(
        np.arange(len(sizes), dtype=np.int32), np.asarray(sizes, np.int64)
    )


def zero_layer_telemetry(sizes: Sequence[int], *, per_leaf_age: bool):
    """Zero per-layer structure for init_fn: [L] zeros per LAYER_FIELDS
    plus the residual-age buffer in the residual's own layout (flat [N]
    for flat modes, per-leaf tuple for layerwise) so the state treedef is
    identical at step 0 and step k."""
    L = len(sizes)
    if per_leaf_age:
        age = tuple(jnp.zeros((int(s),), jnp.float32) for s in sizes)
    else:
        age = jnp.zeros((int(sum(sizes)),), jnp.float32)
    return {
        "layers": {f: jnp.zeros((L,), jnp.float32) for f in LAYER_FIELDS},
        "age": age,
    }


@jax.named_scope(SCOPE)
def seg_l2(x: Array, seg: np.ndarray, L: int) -> Array:
    """Per-layer L2 norms of a flat [N] vector in one segment reduction."""
    x = x.astype(jnp.float32)
    return jnp.sqrt(jax.ops.segment_sum(
        x * x, seg, num_segments=L, indices_are_sorted=True))


def _tree_sq(tree) -> Array:
    return sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree.leaves(tree)
    )


@jax.named_scope(SCOPE)
def mass_ratio(acc, selected) -> Array:
    """Whole-model mass-capture ratio m(k) = ||selected||^2 / ||acc||^2
    (arXiv:1911.08772). Both args may be arrays or pytrees of arrays;
    ``selected`` may be the densified selection or just the selected
    values — only its mass matters."""
    return _tree_sq(selected) / jnp.maximum(_tree_sq(acc), _MASS_EPS)


@jax.named_scope(SCOPE)
def leaf_l2(arrs: Sequence[Array]) -> Array:
    """Stacked per-leaf L2 norms, f32[L] — the layerwise-mode counterpart
    of seg_l2 (one small reduction per leaf; no flat vector exists)."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))) for a in arrs
    ])


@jax.named_scope(SCOPE)
def sparse_selection_layer_stats(
    acc: Array, vals: Array, idx: Array, seg: np.ndarray, L: int
) -> Tuple[Dict[str, Array], Array]:
    """Per-layer selection stats for the flat [N] layout and the (vals,
    idx) wire form, without ever densifying the selection: the selected
    coordinates' layer ids are a gather ``seg[idx]``, and every per-layer
    stat is a k-sized segment reduction (k << N), plus one [N] reduction
    for the per-layer acc mass. Returns ({sent, tau, m_k} as f32[L],
    whole-model m_k). A value-0 slot counts as not sent (padding
    convention, as sent_count has it)."""
    mask = vals != 0
    seg_sel = jnp.take(jnp.asarray(seg), idx, mode="clip")
    sent = jax.ops.segment_sum(
        mask.astype(jnp.float32), seg_sel, num_segments=L)
    tau = jax.ops.segment_min(
        jnp.where(mask, jnp.abs(vals), jnp.inf), seg_sel, num_segments=L)
    tau = jnp.where(jnp.isfinite(tau), tau, 0.0).astype(jnp.float32)
    acc32 = acc.astype(jnp.float32)
    v32 = vals.astype(jnp.float32)
    acc_sq = jax.ops.segment_sum(
        acc32 * acc32, seg, num_segments=L, indices_are_sorted=True)
    sel_sq = jax.ops.segment_sum(v32 * v32, seg_sel, num_segments=L)
    m_k = sel_sq / jnp.maximum(acc_sq, _MASS_EPS)
    whole = jnp.sum(sel_sq) / jnp.maximum(jnp.sum(acc_sq), _MASS_EPS)
    return {"sent": sent, "tau": tau, "m_k": m_k}, whole


@jax.named_scope(SCOPE)
def leafwise_selection_stats(
    accs: Sequence[Array], sel_denses: Sequence[Array]
) -> Tuple[Dict[str, Array], Array]:
    """Per-leaf selection stats where the flat [N] vector never exists
    (the layerwise mode; the flat modes' one-device step, whose leaves
    keep their shapes): ``sel_denses`` are the leaves' selections
    densified (selected values in place, 0 elsewhere). One small
    reduction per leaf, stacked to [L]. Returns ({sent, tau, m_k} as
    f32[L], whole-model m_k)."""
    sents, taus, sel_sqs, acc_sqs = [], [], [], []
    for a, s in zip(accs, sel_denses):
        mask = s != 0
        sents.append(jnp.sum(mask.astype(jnp.float32)))
        t = jnp.min(jnp.where(mask, jnp.abs(s), jnp.inf))
        taus.append(jnp.where(jnp.any(mask), t, 0.0).astype(jnp.float32))
        a32, s32 = a.astype(jnp.float32), s.astype(jnp.float32)
        acc_sqs.append(jnp.sum(a32 * a32))
        sel_sqs.append(jnp.sum(s32 * s32))
    sel_sq = jnp.stack(sel_sqs)
    acc_sq = jnp.stack(acc_sqs)
    whole = jnp.sum(sel_sq) / jnp.maximum(jnp.sum(acc_sq), _MASS_EPS)
    return {
        "sent": jnp.stack(sents),
        "tau": jnp.stack(taus),
        "m_k": sel_sq / jnp.maximum(acc_sq, _MASS_EPS),
    }, whole


@jax.named_scope(SCOPE)
def leafwise_sparse_selection_stats(
    accs: Sequence[Array], vals_list: Sequence[Array]
) -> Tuple[Dict[str, Array], Array]:
    """Per-leaf stats from each leaf's selected VALUES (layerwise p>1
    path, where selection is already per leaf): no scatter needed, one
    k_l-sized reduction per leaf plus the leaf's acc mass."""
    sents, taus, sel_sqs, acc_sqs = [], [], [], []
    for a, v in zip(accs, vals_list):
        mask = v != 0
        sents.append(jnp.sum(mask.astype(jnp.float32)))
        t = jnp.min(jnp.where(mask, jnp.abs(v), jnp.inf))
        taus.append(jnp.where(jnp.any(mask), t, 0.0).astype(jnp.float32))
        a32, v32 = a.astype(jnp.float32), v.astype(jnp.float32)
        acc_sqs.append(jnp.sum(a32 * a32))
        sel_sqs.append(jnp.sum(v32 * v32))
    sel_sq = jnp.stack(sel_sqs)
    acc_sq = jnp.stack(acc_sqs)
    whole = jnp.sum(sel_sq) / jnp.maximum(jnp.sum(acc_sq), _MASS_EPS)
    return {
        "sent": jnp.stack(sents),
        "tau": jnp.stack(taus),
        "m_k": sel_sq / jnp.maximum(acc_sq, _MASS_EPS),
    }, whole


@jax.named_scope(SCOPE)
def bucketed_sparse_selection_stats(
    accs: Sequence[Array], vals_list: Sequence[Array],
    idx_list: Sequence[Array], leaf_sizes: Sequence[int],
    boundaries: Sequence[int],
) -> Tuple[Dict[str, Array], Array]:
    """Per-LEAF stats recovered from bucket-concatenated selections.

    The bucketed layerwise path selects per BUCKET (one (vals, idx) set
    in each bucket's local index space), but --obs-layers reports per
    leaf. Leaf identity inside a bucket is static structure: bucket b
    covers leaves ``boundaries[b]:boundaries[b+1]``, so its local
    coordinate->leaf map is ``segment_ids(leaf_sizes[lo:hi]) + lo`` and
    each bucket's stats are one sparse_selection_layer_stats call over
    the GLOBAL leaf axis. Buckets partition the leaves, so summing the
    per-bucket [L] arrays (each zero outside its own leaf range —
    including tau, where segment_min over an empty segment reports 0)
    recovers exactly the per-leaf stats the unbucketed path computes."""
    L = len(leaf_sizes)
    out: Dict[str, Array] = {}
    for b, (a, v, i) in enumerate(zip(accs, vals_list, idx_list)):
        lo, hi = int(boundaries[b]), int(boundaries[b + 1])
        seg = segment_ids(leaf_sizes[lo:hi]) + np.int32(lo)
        stats, _ = sparse_selection_layer_stats(a, v, i, seg, L)
        out = (stats if not out
               else {key: out[key] + stats[key] for key in out})
    return out, mass_ratio(accs, vals_list)


@jax.named_scope(SCOPE)
def dense_phase_selection_stats(
    sizes: Sequence[int],
) -> Tuple[Dict[str, Array], Array]:
    """The dense (no-compression) phase's trivial selection stats:
    everything ships, so density 1 per layer, no threshold, full mass
    capture. Used by the dense mode and the warm-up dense branch so both
    lax.cond arms return an identical structure."""
    L = len(sizes)
    return {
        "sent": jnp.asarray(np.asarray(sizes, np.float32)),
        "tau": jnp.zeros((L,), jnp.float32),
        "m_k": jnp.ones((L,), jnp.float32),
    }, jnp.float32(1.0)


@jax.named_scope(SCOPE)
def update_age(age, delivered):
    """Residual-age recursion: a coordinate's age resets to 0 the step it
    ships (appears in the applied dense update) and grows by 1 otherwise.
    ``delivered`` is derived from the globally-reduced update, which is
    replicated across the mesh, so the age buffer stays replicated without
    any collective. Works leaf-wise (tree.map) for the layerwise layout.
    Caveat: exact cross-device cancellation of a shipped coordinate reads
    as not-delivered — an epsilon case on real gradients."""
    return jax.tree.map(
        lambda a, d: jnp.where(d, 0.0, a + 1.0), age, delivered)


@jax.named_scope(SCOPE)
def layer_age_means(age, seg: np.ndarray = None, L: int = 0,
                    sizes: Sequence[int] = ()) -> Array:
    """Mean residual age per layer: flat [N] buffer via one segment_sum,
    per-leaf tuple via per-leaf means."""
    if isinstance(age, tuple):
        return jnp.stack([jnp.mean(a) for a in age])
    total = jax.ops.segment_sum(
        age, seg, num_segments=L, indices_are_sorted=True)
    return total / jnp.asarray(np.maximum(np.asarray(sizes, np.float64), 1)
                               .astype(np.float32))


@jax.named_scope(SCOPE)
def assemble_layer_telemetry(
    *,
    sel_stats: Dict[str, Array],
    sizes: Sequence[int],
    grad_norm_pre_l: Array,
    grad_norm_post_l: Array,
    residual_norm_l: Array,
    age,
    seg: np.ndarray = None,
) -> Dict[str, Array]:
    """Glue the branch-dependent selection stats and the branch-independent
    norms/ages into the LAYER_FIELDS dict carried in state.telemetry."""
    L = len(sizes)
    sizes_f = jnp.asarray(np.maximum(np.asarray(sizes, np.float64), 1)
                          .astype(np.float32))
    return {
        "density": sel_stats["sent"] / sizes_f,
        "tau": sel_stats["tau"],
        "grad_norm_pre": grad_norm_pre_l,
        "grad_norm_post": grad_norm_post_l,
        "residual_norm": residual_norm_l,
        "residual_age": layer_age_means(age, seg=seg, L=L, sizes=sizes),
        "m_k": sel_stats["m_k"],
    }


@jax.named_scope(SCOPE)
def topk_recall(hits: Array, exact_vals: Array) -> Array:
    """Recall of the production selection against the exact top-k ground
    truth: fraction of exact-top-k elements (zero-padding slots excluded)
    the production path also selected. ``hits`` is bool[k] membership of
    the exact indices in the selected set."""
    real = jnp.abs(exact_vals) > 0
    n_real = jnp.maximum(jnp.sum(real.astype(jnp.float32)), 1.0)
    return jnp.sum((hits & real).astype(jnp.float32)) / n_real


# What an expert layer that holds a share of its model's experts counts
# (models/qwen3_next.py), per optimizer step: token-slots that fell on
# the held experts (summed over the layers), the largest and the mean load
# of a held expert (over layers x held experts), and the slots that fell
# on a held expert and were not computed, which a dropless layer keeps 0.
MOE_FIELDS = (
    "moe_slots_held",
    "moe_load_max",
    "moe_load_mean",
    "moe_slots_dropped",
)

# What a decoder whose queries each attend to the ``topk`` keys a learned
# indexer scores highest counts (models/keye_vl2.py), per optimizer step:
# the keys its queries kept, sum over the step's queries of |S_t|, mean over
# the layers; what they keep when no score ties at a threshold, sum of
# min(t + 1, topk); and the indexer's own loss term L_I.
DSA_FIELDS = (
    "dsa_keys_kept",
    "dsa_keys_due",
    "dsa_index_loss",
)

# What an expert layer whose router carries a balancing bias counts
# (models/trinity_mini.py), per optimizer step, over every expert of the
# model, held here or not: the tokens the fullest expert was chosen by and
# the mean (layers x experts), which the bias acts on, and the largest
# |bias| the step chose with.
MOE_BALANCE_FIELDS = (
    "moe_count_max",
    "moe_count_mean",
    "moe_bias_absmax",
)

# What a decoder that runs its layers several times over tied weights, with
# a head and a learned exit gate at every pass, counts (models/ouro.py), per
# optimizer step: each pass's mean cross-entropy, the mean of the tokens'
# exit distribution p_r (the shares add up to 1), and the mean entropy of
# that distribution over ln(passes): 1 at an even distribution, 0 when the
# gate has collapsed onto one pass. A model of fewer than ``LOOP_PASSES``
# passes fills the fields of the passes it has.
LOOP_PASSES = 4
LOOP_FIELDS = tuple(
    f"loop_{name}_{r}" for name in ("loss", "exit_share")
    for r in range(1, LOOP_PASSES + 1)) + ("loop_exit_entropy",)

# What a decoder trained by block diffusion counts of the noise it drew
# inside the step (models/sdar.py), per optimizer step: the share of the
# noised half's positions that were masked (about 1/2: the mean of t), the
# mean t over the blocks, the unweighted mean cross-entropy at the masked
# positions (the weighted sum is the loss), and the share of blocks in which
# no position was masked (they add nothing to the loss).
BD_FIELDS = (
    "bd_masked_share",
    "bd_mean_t",
    "bd_masked_ce",
    "bd_empty_blocks",
)

# What a decoder whose delta rule decays every key channel by a number of
# its own counts (models/kimi_linear.py), per optimizer step, over its Kimi
# Delta Attention layers: the most negative log decay any channel adds up to
# inside one chunk (gamma_C; e^gamma_C is the chunk's decay of the state's
# row, and below -87 it is under float32's smallest normal number: how near
# the chunked form's bounded exponents run to underflow), and the mean of
# beta, the delta rule's write strength.
KDA_FIELDS = (
    "kda_log_decay_min",
    "kda_beta_mean",
)

# The model counters last read on the host (``model_scalars``): like the
# span buffer, it outlives the trainer, so a reader can ask afterwards.
_last_model: Dict[str, float] = {}


@jax.named_scope(SCOPE)
def moe_counters(load: Array, dropped: Array) -> Dict[str, Array]:
    """``MOE_FIELDS`` as f32 scalars from the expert layers' counts:
    ``load`` [layers, held] slots per held expert, ``dropped`` [layers]."""
    load = load.astype(jnp.float32)
    return {
        "moe_slots_held": jnp.sum(load),
        "moe_load_max": jnp.max(load),
        "moe_load_mean": jnp.mean(load),
        "moe_slots_dropped": jnp.sum(dropped).astype(jnp.float32),
    }


@jax.named_scope(SCOPE)
def dsa_counters(kept: Array, due: Array, index_loss: Array
                 ) -> Dict[str, Array]:
    """``DSA_FIELDS`` as f32 scalars from the sparse-attention layers'
    counts: ``kept`` [layers] keys kept, ``due`` [] the same for every
    layer, ``index_loss`` [layers]. (float32 holds whole numbers to 2^24
    and even ones to 2^25: a 16,384-token sequence's 31,458,304 is exact.)"""
    return {
        "dsa_keys_kept": jnp.mean(kept.astype(jnp.float32)),
        "dsa_keys_due": due.astype(jnp.float32),
        "dsa_index_loss": jnp.mean(index_loss),
    }


@jax.named_scope(SCOPE)
def moe_balance_counters(count: Array, bias: Array) -> Dict[str, Array]:
    """``MOE_BALANCE_FIELDS`` as f32 scalars from the expert layers'
    selection counts and balancing biases, both [layers, experts]."""
    count = count.astype(jnp.float32)
    return {
        "moe_count_max": jnp.max(count),
        "moe_count_mean": jnp.mean(count),
        "moe_bias_absmax": jnp.max(jnp.abs(bias)),
    }


@jax.named_scope(SCOPE)
def loop_counters(loss: Array, share: Array, entropy: Array
                  ) -> Dict[str, Array]:
    """``LOOP_FIELDS`` as f32 scalars from the looped decoder's counts:
    ``loss`` and ``share`` [passes], ``entropy`` []."""
    out = {"loop_exit_entropy": entropy}
    for r in range(min(loss.shape[0], LOOP_PASSES)):
        out[f"loop_loss_{r + 1}"] = loss[r]
        out[f"loop_exit_share_{r + 1}"] = share[r]
    return out


@jax.named_scope(SCOPE)
def bd_counters(*counts: Array) -> Dict[str, Array]:
    """``BD_FIELDS`` as f32 scalars: the model gives each as a number."""
    return {name: jnp.asarray(count, jnp.float32)
            for name, count in zip(BD_FIELDS, counts)}


@jax.named_scope(SCOPE)
def kda_counters(log_decay_min: Array, beta_mean: Array) -> Dict[str, Array]:
    """``KDA_FIELDS`` as f32 scalars from the KDA layers' counts, both
    [layers]."""
    return {"kda_log_decay_min": jnp.min(log_decay_min),
            "kda_beta_mean": jnp.mean(beta_mean)}


# The registry of model counters: each group's fields, the keys of the
# model's counts it is computed from and its device function. A model's
# ``aux`` holds the groups whose counts it returns; ``model_scalars`` reads
# whichever of the fields it finds.
MODEL_COUNTERS = {
    "moe": (MOE_FIELDS, ("moe_load", "moe_dropped"), moe_counters),
    "dsa": (DSA_FIELDS, ("dsa_kept", "dsa_due", "dsa_index_loss"),
            dsa_counters),
    "moe_balance": (MOE_BALANCE_FIELDS, ("moe_count", "moe_bias"),
                    moe_balance_counters),
    "loop": (LOOP_FIELDS, ("loop_loss", "loop_exit_share",
                           "loop_exit_entropy"), loop_counters),
    "bd": (BD_FIELDS, BD_FIELDS, bd_counters),
    "kda": (KDA_FIELDS, KDA_FIELDS, kda_counters),
}


def model_counters(counts: Dict[str, Array]) -> Dict[str, Array]:
    """The step's ``aux`` of a model that returns its own counts: every
    registered group whose keys are among them."""
    out = {}
    for _, keys, compute in MODEL_COUNTERS.values():
        if all(key in counts for key in keys):
            out.update(compute(*(counts[key] for key in keys)))
    return out


def model_scalars(aux: Dict[str, Array]) -> Dict[str, float]:
    """Host floats of the model's own counters among a step's ``aux``
    (the registry's fields; {} for a model that counts nothing), kept as
    the last read. Blocks on the step like ``telemetry_scalars``: call it
    at the same sync point."""
    found = {key: float(aux[key])
             for fields, _, _ in MODEL_COUNTERS.values()
             for key in fields if key in aux}
    if found:
        _last_model.clear()
        _last_model.update(found)
    return found


def last_model_scalars() -> Dict[str, float]:
    """What ``model_scalars`` last read in this process ({} before any)."""
    return dict(_last_model)


def readable_counters(telemetry: Dict[str, Array]) -> Dict[str, Array]:
    """What the trainer's loop reads of a state's telemetry dict: the
    scalar counters and the per-layer "layers" columns, never the [N]
    "age" buffer (its per-layer mean is already among the columns)."""
    return {key: val for key, val in telemetry.items() if key != "age"}


def telemetry_scalars(telemetry: Dict[str, Array]) -> Dict[str, float]:
    """Host floats of the SCALAR counters in a state's telemetry dict —
    the per-layer "layers" sub-dict and the [N] "age" buffer excluded.
    One sync point shared by the trainer's "obs" record and the anomaly
    monitor, so adding a consumer never adds a device read."""
    return {
        key: float(val) for key, val in telemetry.items()
        if key not in ("layers", "age")
    }
