"""Comm planner + Ok-Topk balanced schedule vs numpy oracles.

Balanced-schedule contracts (ISSUE 9 acceptance): all ranks bit-identical
on ragged and pow2 meshes, fold+repair restores rejected picks exactly,
per-rank wire volume <= the tree's at p >= 8. Planner contracts: monotone
in beta, respects a --comm-plan pin, falls back sanely with no probe
artifact, and auto-selects the hand-picked historical schedule in every
regime the scaling model already covers (no silent behavior change at
defaults).
"""

import numpy as np
import pytest
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from gtopkssgd_tpu.compression import get_compressor
from gtopkssgd_tpu.modes import default_schedule
from gtopkssgd_tpu.parallel import (
    balanced_cap,
    build_decision,
    candidate_plans,
    comm_bytes_per_step,
    make_mesh,
    resolve_plan,
    sparse_allreduce,
    validate_pin,
)
from gtopkssgd_tpu.parallel.planner import (
    PLANNER_DEFAULT_ALPHA_MS,
    CommPlan,
    score_plan,
)

K = 8
N = 300


def make_local_sets(rng, p, k=K, n=N):
    """Random fixed-k local sets with unique indices + sentinel padding
    (same layout as test_collectives)."""
    vals = np.zeros((p, k), np.float32)
    idxs = np.full((p, k), n, np.int32)
    for d in range(p):
        kk = int(rng.integers(k // 2, k + 1))
        ii = rng.choice(n, size=kk, replace=False)
        vals[d, :kk] = rng.normal(size=kk).astype(np.float32)
        idxs[d, :kk] = ii
    return vals, idxs


def np_balanced(vals, idxs, k, n, p):
    """Independent numpy simulator of the balanced schedule: per-dest
    capped largest-|v| scatter, owner-range reduce, owner top-cap,
    global top-k over the (disjoint-index) union. Returns {idx: val}."""
    chunk = -(-n // p)
    cap = balanced_cap(k, p, n)
    acc = np.zeros((p, chunk), np.float64)
    for r in range(p):
        v, i = vals[r], idxs[r]
        real = i < n
        owner = np.minimum(i // chunk, p - 1)
        for s in range(p):
            dest = (r + s) % p
            dmask = real & (owner == dest)
            if s == 0:
                sv, si = np.where(dmask, v, 0.0), i
            else:
                mag = np.where(dmask, np.abs(v), -1.0)
                pos = np.argsort(-mag, kind="stable")[:cap]
                sel = mag[pos] >= 0.0
                sv, si = np.where(sel, v[pos], 0.0), np.where(
                    sel, i[pos], n)
            loc = si - dest * chunk
            ok = (si < n) & (loc >= 0) & (loc < chunk)
            np.add.at(acc[dest], loc[ok], sv[ok])
    cand = {}
    for d in range(p):
        pos = np.argsort(-np.abs(acc[d]), kind="stable")[:cap]
        for q in pos:
            if abs(acc[d][q]) > 0:
                cand[d * chunk + q] = acc[d][q]
    top = sorted(cand.items(), key=lambda kv: -abs(kv[1]))[:k]
    return dict(top)


def _run_balanced(vals, idxs, p, k=K, n=N, codec="fp32"):
    mesh = make_mesh(p)
    def body(v, i):
        gv, gi = sparse_allreduce(
            "gtopk", v[0], i[0], k=k, n=n, axis_name="dp", axis_size=p,
            codec=codec, plan=CommPlan("balanced", "gtopk",
                                       "balanced", codec=codec))[:2]
        return gv[None], gi[None]

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("dp"), P("dp")),
        out_specs=(P("dp"), P("dp"))))
    gv, gi = fn(vals, idxs)  # (p, k): one row per shard, v[0] -> (k,)
    return np.asarray(gv), np.asarray(gi)


@pytest.mark.parametrize("p", [3, 5, 8])
def test_balanced_all_ranks_identical_and_matches_oracle(p):
    rng = np.random.default_rng(17 + p)
    vals, idxs = make_local_sets(rng, p)
    gv, gi = _run_balanced(vals, idxs, p)
    for d in range(1, p):
        assert np.array_equal(gv[0], gv[d])  # bit-identical
        assert np.array_equal(gi[0], gi[d])
    got = {int(i): float(v) for v, i in zip(gv[0], gi[0]) if i < N}
    want = np_balanced(vals, idxs, K, N, p)
    assert set(got) == set(want)
    for i, v in got.items():
        assert np.isclose(v, want[i], rtol=1e-6), (i, v, want[i])


@pytest.mark.parametrize("p", [3, 5, 8])
def test_balanced_ranks_identical_under_lossy_codec(p):
    # Determinism survives quantization: every rank decodes the same
    # allgathered owner sets, so the reselect agrees bitwise.
    rng = np.random.default_rng(29 + p)
    vals, idxs = make_local_sets(rng, p)
    gv, gi = _run_balanced(vals, idxs, p, codec="int8:64")
    for d in range(1, p):
        assert np.array_equal(gv[0], gv[d])
        assert np.array_equal(gi[0], gi[d])


@pytest.mark.parametrize("p", [3, 5, 8])
def test_balanced_repair_restores_rejected_exactly(p):
    # A pick that never lands in gidx (capped out in scatter, lost the
    # owner top-cap, or rejected by the final reselect) must come back
    # into the residual EXACTLY — bitwise, since the fp32 codec is the
    # identity and repair adds the original local value.
    rng = np.random.default_rng(43 + p)
    vals, idxs = make_local_sets(rng, p)
    _, gi = _run_balanced(vals, idxs, p)
    gidx = jnp.asarray(gi[0])
    comp = get_compressor("gtopk", density=K / N, method="exact")
    for r in range(p):
        res = comp.repair(jnp.zeros(N), jnp.asarray(vals[r]),
                          jnp.asarray(idxs[r]), gidx)
        res = np.asarray(res)
        delivered = set(int(i) for i in gi[0] if i < N)
        for v, i in zip(vals[r], idxs[r]):
            if i >= N:
                continue
            if int(i) in delivered:
                assert res[i] == 0.0
            else:
                assert res[i] == v  # exact, not approx


def test_balanced_bytes_beat_tree_at_scale():
    # Acceptance: per-rank wire bytes <= tree's at p >= 8 for realistic
    # k (k >> p; at pathological k ~ p the 2p-1 message framing costs
    # more than log2(p) full sets and the PLANNER keeps the tree).
    n, k = 10_000_000, 10_000
    for p in (8, 12, 16, 32, 64):
        bal = comm_bytes_per_step("gtopk", n, k, p, schedule="balanced")
        tree = comm_bytes_per_step("gtopk", n, k, p)
        assert bal <= tree, (p, bal, tree)
    # and the balanced volume is O(k): grows ~not at all from p=8->64
    b8 = comm_bytes_per_step("gtopk", n, k, 8, schedule="balanced")
    b64 = comm_bytes_per_step("gtopk", n, k, 64, schedule="balanced")
    assert b64 < 1.2 * b8


def test_balanced_cap_bounds():
    assert balanced_cap(10_000, 8, 10_000_000) == 1875
    assert balanced_cap(8, 8, 300) == 2      # ceil(1.5*8/8)
    assert balanced_cap(8, 64, 300) == 1     # floor of 1
    assert balanced_cap(100, 2, 60) == 30    # chunk clamp: ceil(n/p)
    assert balanced_cap(5, 2, 1000) == 4     # <= k clamp inactive here
    assert balanced_cap(5, 1, 1000) == 5     # k clamp at p=1


# ------------------------------------------------------------ planner


def test_planner_auto_matches_historical_at_defaults():
    # No silent behavior change: with the repo's committed dcn_probe
    # artifact (and its ~22 ms alpha), every regime the scaling model's
    # default grid covers keeps the hand-picked historical schedule.
    n = 25_557_032
    for mode, ici in (("gtopk", 1), ("gtopk_layerwise", 1),
                      ("allgather", 1), ("gtopk_hier", 16),
                      ("dense", 1)):
        for p in (1, 4, 16, 32, 64, 256):
            for rho in (0.001, 0.01):
                k = max(1, int(np.ceil(rho * n)))
                d = build_decision(mode, p=p, n=n, k=k, ici_size=ici)
                assert d.plan.schedule == default_schedule(mode), (
                    mode, p, rho, d.candidates)
                assert d.record()["plan_is_default"] == 1.0


def test_planner_fallback_without_probe_artifact(tmp_path):
    # Empty probe dir -> documented fallback constants, and the default
    # regime still keeps the tree (the nonzero alpha floor exists
    # precisely so the degenerate bandwidth-only model cannot flip the
    # schedule silently).
    d = build_decision("gtopk", p=32, n=25_557_032, k=25_558,
                      probe_dir=str(tmp_path))
    assert d.inputs["fit_source"] == "fallback-defaults"
    assert d.inputs["alpha_ms"] == PLANNER_DEFAULT_ALPHA_MS
    assert d.plan.name == "tree"


def test_planner_monotone_in_beta():
    # More slow-link bandwidth can only help; comm_ms strictly falls.
    plan = candidate_plans("gtopk")[1]
    assert plan.name == "balanced"
    last = float("inf")
    for beta in (0.1, 1.0, 10.0, 100.0):
        ms = score_plan(plan, 32, n=25_557_032, k=255_571,
                        alpha_ms=0.0, beta_gbps=beta, ici_gbps=1600.0)
        assert ms < last
        last = ms


def test_planner_balanced_wins_bandwidth_bound_regime():
    # The regime the schedule exists for: latency-free fabric, dense-ish
    # sparse sets, many ranks -> O(k) beats O(k log p).
    d = build_decision("gtopk", p=32, n=25_557_032, k=255_571,
                       alpha_ms=0.0)
    assert d.plan.name == "balanced"
    by_name = {c["name"]: c for c in d.candidates}
    assert by_name["balanced"]["comm_ms"] < by_name["tree"]["comm_ms"]
    assert by_name["balanced"]["wire_bytes"] < by_name["tree"]["wire_bytes"]


def test_planner_respects_pin_and_rejects_bad_pin():
    d = build_decision("gtopk", p=4, n=10_000, k=100, pin="balanced")
    assert d.plan.name == "balanced"  # despite tree scoring cheaper
    assert d.pin == "balanced"
    with pytest.raises(ValueError, match="does not realize"):
        validate_pin("balanced", "dense")
    with pytest.raises(ValueError, match="does not realize"):
        build_decision("allgather", p=4, n=10_000, k=100, pin="tree")
    assert validate_pin(None, "gtopk") == "auto"


def test_planner_candidates_are_semantics_preserving():
    assert [c.name for c in candidate_plans("gtopk")] == [
        "tree", "balanced"]
    assert [c.name for c in candidate_plans("gtopk_layerwise")] == [
        "tree", "balanced"]
    assert [c.name for c in candidate_plans("dense")] == ["dense"]
    assert [c.name for c in candidate_plans(None)] == ["dense"]
    assert [c.name for c in candidate_plans("allgather")] == ["allgather"]
    assert [c.name for c in candidate_plans("gtopk_hier",
                                            ici_size=4)] == ["hier"]


def test_planner_carries_pipeline_and_span_columns():
    """PR 15: the decision record carries the RESOLVED pipeline, and
    every candidate row prices the step-span both execution orders would
    expose — B>1 with nonzero select cost makes the overlapped span
    strictly cheaper, B=1 makes them equal (nothing to overlap)."""
    buckets = ((1_000_000, 1_000),) * 4
    d = build_decision("gtopk_layerwise", p=8, n=4_000_000, k=4_000,
                       alpha_ms=0.1, beta_gbps=0.6, bucketing="b4",
                       buckets=buckets, pipeline="overlap")
    assert d.plan.pipeline == "overlap"
    rec = d.record()
    assert rec["pipeline"] == "overlap"
    for c in d.candidates:
        assert c["span_serial_ms"] > 0
        assert c["span_overlap_ms"] > 0
        assert c["span_overlap_ms"] < c["span_serial_ms"], c["name"]
    # the schedule choice itself stays a comm_ms decision; the spans are
    # evidence, recorded per candidate
    assert {c["name"] for c in d.candidates} == {"tree", "balanced"}
    # an unbucketed wire is one bucket of the full (n, k): both orders
    # expose the same span, and the default pipeline is serial
    d1 = build_decision("gtopk", p=8, n=4_000_000, k=4_000,
                        alpha_ms=0.1, beta_gbps=0.6)
    assert d1.plan.pipeline == "serial"
    assert d1.record()["pipeline"] == "serial"
    for c in d1.candidates:
        assert c["span_overlap_ms"] == pytest.approx(c["span_serial_ms"])
    # pipeline rides only the gtopk-family candidates — a dense wire has
    # no select/merge chain to reorder
    (dense,) = candidate_plans("dense", pipeline="overlap")
    assert dense.pipeline == "serial"


def test_resolve_plan_looks_the_name_up():
    """The optimizer's trace-time entry point is a lookup among the
    mode's candidates: the name Trainer's one decision chose comes back
    as that candidate, labelled with the codec and ici width asked for."""
    for name in ("tree", "balanced"):
        plan = resolve_plan("gtopk_layerwise", name, codec="int8:64")
        assert (plan.name, plan.schedule, plan.codec) == (
            name, name, "int8:64")
    hier = resolve_plan("gtopk_hier", "hier", ici_size=4)
    assert (hier.schedule, hier.intra, hier.ici_size) == ("tree", "psum", 4)
    with pytest.raises(ValueError, match="does not realize"):
        resolve_plan("allgather", "balanced")


def test_resolve_plan_auto_is_the_historical_schedule(tmp_path,
                                                      monkeypatch):
    """'auto' reaching the optimizer with no Trainer above it is the
    mode's hand-picked schedule whatever any fit would price: it scores
    nothing, so a fast-fabric fit in the default directory that flips
    build_decision to 'balanced' leaves it alone."""
    from gtopkssgd_tpu.parallel import comm_model

    with open(tmp_path / "calib_fit_32proc.json", "w") as fh:
        fh.write('{"alpha_beta_fit": {"alpha_ms": 0.0, "beta_gbps": 0.6}}')
    monkeypatch.setattr(comm_model, "FIT_DIR", str(tmp_path))
    shape = dict(p=32, n=25_557_032, k=255_571)
    assert build_decision("gtopk", **shape).plan.name == "balanced"
    for mode in ("gtopk", "gtopk_layerwise", "gtopk_hier", "allgather",
                 "dense"):
        assert (resolve_plan(mode, "auto").schedule
                == resolve_plan(mode, None).schedule
                == default_schedule(mode))


@pytest.mark.parametrize("fit", ["no_fit", "fast_fit"])
@pytest.mark.parametrize("pin", ["auto", "tree", "balanced"])
def test_trainer_decides_once_and_the_step_runs_that_plan(
        tmp_path, monkeypatch, pin, fit):
    """One decision, one optimizer: Trainer.__init__ scores the
    candidates host-side, builds tx once with the chosen plan's name,
    and the schedule in the lowered step is the one the manifest says
    ran — also when an explicit fast-fabric fit flips 'auto' away from
    the historical tree (8 ranks, no per-message latency: the balanced
    schedule's O(k) volume beats the tree's O(k log p))."""
    import json
    import os

    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    flags = {}
    if fit == "fast_fit":
        flags["comm_model_fit"] = str(tmp_path / "calib_fit_8proc.json")
        with open(flags["comm_model_fit"], "w") as fh:
            json.dump({"alpha_beta_fit":
                       {"alpha_ms": 0.0, "beta_gbps": 0.6}}, fh)
    built = []
    make_tx = Trainer._make_tx
    monkeypatch.setattr(
        Trainer, "_make_tx",
        lambda self, *a, **kw: built.append(1) or make_tx(self, *a, **kw))
    out = str(tmp_path / "run")
    with Trainer(TrainConfig(
            dnn="resnet20", batch_size=2, nworkers=8, compression="gtopk",
            density=0.01, comm_plan=pin, log_interval=5, eval_batches=1,
            max_epochs=1, prefetch=0, out_dir=out, **flags)) as t:
        assert len(built) == 1
        batch = t._device_batch(t._shard_batches(t._iters)[0])
        text = t._train_step.lower(t.state, t.carry, batch).as_text()
    with open(os.path.join(out, "metrics.jsonl")) as fh:
        man = next(r for r in map(json.loads, fh)
                   if r["kind"] == "manifest")
    want = pin if pin != "auto" else (
        "balanced" if fit == "fast_fit" else "tree")
    assert man["comm_plan"] == man["comm_plan_schedule"] == want
    assert man["comm_plan_pin"] == pin
    # only the balanced schedule gathers; the tree only permutes
    lowered = "balanced" if "stablehlo.all_gather" in text else "tree"
    assert lowered == man["comm_plan_schedule"]


def test_sparse_allreduce_rejects_unknown_schedule():
    with pytest.raises(ValueError, match="supports schedules"):
        sparse_allreduce("gtopk", jnp.zeros(4), jnp.zeros(4, jnp.int32),
                         k=4, n=10, axis_name="dp", axis_size=2,
                         plan="ring")
