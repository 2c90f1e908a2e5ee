"""The quickest proof that the gTop-k trainer still starts on the chip.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # four chips of one host: the dp mesh

With no arguments, in ONE process that owns the chip:

  kernels   every Pallas entry point of ops/pallas_topk.py, compiled
            (interpret=False) at ResNet-50's N, against the XLA reference:
            threshold counts equal exactly, stage-1 candidates reselect to
            recall >= 0.95 of the exact top-k.
  train/*   ResNet-50 at full width (224x224 synthetic ImageNet, 1000
            classes, 25.6M parameters, bfloat16, per-chip batch 128)
            through ``gtopkssgd_tpu.dist_trainer.main``: ``gtopk`` at
            rho=0.001, ``dense``, and ``gtopk --topk-method twostage`` —
            each counts its steps, logs a finite loss every step and
            leaves its parameters on the chip; the twostage step's
            compiled HLO must hold the Pallas kernel (``tpu_custom_call``).
  fence     the compiled step timed under ``jax.block_until_ready`` and
            under a device-to-host read: the two must agree.

``--chips 4`` runs only the four-chip path and what it is compared with:
ResNet-50 at ``--nworkers 4``, ``gtopk`` against ``dense``, with the mesh,
the residual's sharding, replica identity and the collectives in each
compiled step checked.

Every stdout line is one JSON object; the last is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Trainer logs go to stderr, every record also to
``chiprun_out/chip_smoke/smoke.jsonl``. No accelerator, or any phase
failing, is a non-zero exit with no ``ok`` line: nothing here catches an
error to carry on. Times printed are smoke output, not measurements.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import os
import re
import shutil
import statistics
import sys
import time
from typing import Any, Dict, List, Optional

OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "chiprun_out", "chip_smoke")


@dataclasses.dataclass(frozen=True)
class Size:
    """What the phases run at. FULL is the contract's size; tests pass a
    tiny one to rehearse the same code on the CPU mesh."""
    dnn: str = "resnet50"
    dataset: str = "imagenet"
    batch_size: int = 128
    dtype: str = "bfloat16"
    steps: int = 8
    mesh_steps: int = 6           # --chips 4 arms
    density: float = 0.001
    kernel_n: int = 25_557_032    # ResNet-50's parameter count


FULL = Size()

ARMS = {
    "gtopk": ["--compression", "gtopk"],
    "dense": ["--compression", "dense"],
    "gtopk_twostage": ["--compression", "gtopk",
                       "--topk-method", "twostage"],
}


def emit(record: Dict[str, Any]) -> None:
    line = json.dumps(record)
    print(line, flush=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "smoke.jsonl"), "a") as fh:
        fh.write(line + "\n")


def device_record() -> Dict[str, Any]:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def peak_bytes() -> Optional[int]:
    """Largest peak_bytes_in_use over the local devices (None where the
    backend keeps no such statistic, as the CPU's does not)."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    peaks = [s["peak_bytes_in_use"] for s in stats
             if s and "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def environment_record() -> Dict[str, Any]:
    import importlib.metadata as md

    import jax
    import jaxlib

    from gtopkssgd_tpu import native
    from gtopkssgd_tpu.utils import enable_compilation_cache

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {
        "phase": "environment", "jax": jax.__version__,
        "jaxlib": jaxlib.__version__, "libtpu": libtpu,
        "device": device_record(),
        "compile_cache_dir": enable_compilation_cache(),
        "compile_cache_from_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "dataprep": "native" if native.available() else "numpy",
    }


# ------------------------------------------------------------------ kernels
def phase_kernels(n: int, density: float, *, interpret: bool,
                  seed: int = 0) -> Dict[str, Any]:
    """Each Pallas entry point against the repo's XLA reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from gtopkssgd_tpu.ops.pallas_topk import (
        fused_multi_threshold_count,
        fused_stage1_candidates,
        multi_threshold_count,
    )
    from gtopkssgd_tpu.ops.topk import (
        TWOSTAGE_OVERSAMPLE,
        _twostage_pallas_groups,
        bucketize_counts,
        k_for_density,
        topk_abs,
    )

    t0 = time.perf_counter()
    kg, kr = jax.random.split(jax.random.PRNGKey(seed))
    grad = jax.random.normal(kg, (n,), jnp.float32)
    resid = 0.5 * jax.random.normal(kr, (n,), jnp.float32)
    k = k_for_density(n, density)
    groups = _twostage_pallas_groups(n, k, TWOSTAGE_OVERSAMPLE)
    # Thresholds at the selection's own scale: the k-th magnitude of each
    # operand, bracketed, so the counts are neither 0 nor n.
    exact = {}
    for name, acc in (("grad", grad), ("acc", grad + resid)):
        vals, idx = topk_abs(acc, k)
        exact[name] = (np.asarray(idx),
                       float(jnp.min(jnp.abs(vals))))
    out: Dict[str, Any] = {"phase": "kernels", "n": n, "k": k,
                           "groups": groups, "interpret": interpret}

    def thresholds(tau):
        return jnp.asarray(
            tau * np.array([4, 2, 1.5, 1.1, 1.0, 0.9, 0.5, 0.1]),
            jnp.float32)

    def check_counts(name, got, mag, thr):
        want = np.asarray(bucketize_counts(mag, thr))
        got = np.asarray(got)
        if not np.array_equal(got, want):
            raise AssertionError(
                f"{name}: counts {got.tolist()} != XLA reference "
                f"{want.tolist()}")
        out[name] = {"counts_equal": True, "count_at_tau": int(got[4])}

    thr_g, thr_a = thresholds(exact["grad"][1]), thresholds(exact["acc"][1])
    check_counts("multi_threshold_count",
                 multi_threshold_count(jnp.abs(grad), thr_g,
                                       interpret=interpret),
                 jnp.abs(grad), thr_g)
    check_counts("fused_multi_threshold_count+residual",
                 fused_multi_threshold_count(grad, thr_a, resid,
                                             interpret=interpret),
                 jnp.abs(grad + resid), thr_a)

    def check_candidates(name, with_residual, with_counts):
        acc_name = "acc" if with_residual else "grad"
        thr = thr_a if with_residual else thr_g
        cand_val, cand_idx, counts = fused_stage1_candidates(
            grad, thr if with_counts else None,
            resid if with_residual else None,
            groups=groups, interpret=interpret)
        _, sel = lax.top_k(jnp.abs(cand_val), k)
        got = np.asarray(jnp.take(cand_idx, sel))
        recall = len(np.intersect1d(got, exact[acc_name][0])) / k
        if recall < 0.95:
            raise AssertionError(
                f"{name}: recall {recall:.4f} < 0.95 against topk_abs")
        out[name] = {"recall": round(recall, 4)}
        if with_counts:
            acc = grad + resid if with_residual else grad
            want = np.asarray(bucketize_counts(jnp.abs(acc), thr))
            if not np.array_equal(np.asarray(counts), want):
                raise AssertionError(f"{name}: fused counts differ")
            out[name]["counts_equal"] = True

    check_candidates("fused_stage1_candidates", False, False)
    check_candidates("fused_stage1_candidates+residual", True, False)
    check_candidates("fused_stage1_candidates+residual+counts", True, True)
    out["seconds"] = round(time.perf_counter() - t0, 2)
    out["peak_bytes_in_use"] = peak_bytes()
    return out


# ----------------------------------------------------------------- training
def collective_counts(hlo_text: str) -> Dict[str, int]:
    """Collective instructions in a compiled step's HLO text, the
    synchronous and the async (``-start``) spelling counted apart."""
    def ops(name):
        return len(re.findall(rf"\s{name}\(", hlo_text))

    return {
        "collective_permute": ops("collective-permute"),
        "collective_permute_start": ops("collective-permute-start"),
        "all_reduce": ops("all-reduce"),
        "all_reduce_start": ops("all-reduce-start"),
    }


def d2h_fence(tree) -> None:
    """Wait by reading one element back to the host: a device-to-host copy
    cannot complete before the program that wrote the buffer has run."""
    import jax

    leaf = jax.tree.leaves(tree)[0]
    jax.device_get(leaf.ravel()[:1])


def fence_times(trainer, reps: int = 4) -> Dict[str, Any]:
    """The trainer's compiled step under both fences, alternating."""
    import jax

    step = int(trainer.state.step)
    batch = trainer._device_batch(trainer._fetch_host(step, 1))
    jax.block_until_ready(batch)
    times: Dict[str, List[float]] = {"block_until_ready": [], "d2h": []}
    for i in range(2 * reps):
        name = "block_until_ready" if i % 2 == 0 else "d2h"
        t0 = time.perf_counter()
        out = trainer._train_step(trainer.state, trainer.carry, batch)
        if name == "d2h":
            d2h_fence(out)
        else:
            jax.block_until_ready(out)
        times[name].append(time.perf_counter() - t0)
        # The step donates its state: keep the trainer on the live one.
        trainer.state, trainer.carry = jax.block_until_ready(out[:2])
    bur = statistics.median(times["block_until_ready"])
    d2h = statistics.median(times["d2h"])
    return {"block_until_ready_s": round(bur, 5), "d2h_s": round(d2h, 5),
            "rel_diff": round(abs(bur - d2h) / max(bur, d2h), 4)}


def run_arm(arm: str, size: Size, *, nworkers: int, steps: int,
            seed: int = 0, inspect=None) -> Dict[str, Any]:
    """One run through ``dist_trainer.main``; checks what every arm must
    show and returns its record. ``inspect(trainer, record)`` adds an
    arm's own checks while the trainer is live."""
    import jax
    import numpy as np

    from gtopkssgd_tpu import dist_trainer

    out_dir = os.path.join(OUT_DIR, f"{arm}_p{nworkers}")
    shutil.rmtree(out_dir, ignore_errors=True)
    argv = ["--dnn", size.dnn, "--dataset", size.dataset,
            "--batch-size", str(size.batch_size), "--dtype", size.dtype,
            "--density", str(size.density), "--nworkers", str(nworkers),
            "--num-iters", str(steps), "--log-interval", "1",
            "--eval-batches", "1", "--seed", str(seed),
            "--out-dir", out_dir] + ARMS[arm]
    record: Dict[str, Any] = {
        "phase": f"train/{arm}", "dnn": size.dnn, "nworkers": nworkers,
        "batch_size": size.batch_size, "dtype": size.dtype}
    platform = jax.default_backend()

    def look(trainer):
        if int(trainer.state.step) != steps:
            raise AssertionError(
                f"{arm}: state.step={int(trainer.state.step)} after "
                f"{steps} steps")
        homes = {d for leaf in jax.tree.leaves(trainer.state.params)
                 for d in leaf.devices()}
        if {d.platform for d in homes} != {platform} \
                or len(homes) != nworkers:
            raise AssertionError(
                f"{arm}: parameters live on {sorted(map(str, homes))}, "
                f"expected {nworkers} {platform} device(s)")
        record["num_params"] = trainer.num_params
        record["param_devices"] = sorted(str(d) for d in homes)
        if inspect is not None:
            inspect(trainer, record)
        # One executable for the whole run: a state leaf that comes back
        # from step 1 placed otherwise than it went in recompiles step 2.
        record["step_compiles"] = trainer._train_step._cache_size()
        if record["step_compiles"] != 1:
            raise AssertionError(
                f"{arm}: the step compiled {record['step_compiles']} times")

    t0 = time.perf_counter()
    # The trainer logs to stdout; this script's stdout carries JSON only.
    with contextlib.redirect_stdout(sys.stderr):
        rc = dist_trainer.main(argv, inspect=look)
    record["wall_s"] = round(time.perf_counter() - t0, 2)
    if rc != 0:
        raise AssertionError(f"{arm}: dist_trainer.main returned {rc}")

    with open(os.path.join(out_dir, "metrics.jsonl")) as fh:
        rows = [json.loads(line) for line in fh]
    train = [r for r in rows if r["kind"] == "train"]
    if [r["step"] for r in train] != list(range(1, steps + 1)):
        raise AssertionError(
            f"{arm}: train records for steps "
            f"{[r['step'] for r in train]}, expected 1..{steps}")
    losses = [r["loss"] for r in train]
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{arm}: non-finite loss in {losses}")
    manifest = next(r for r in rows if r["kind"] == "manifest")
    if manifest["backend"] != platform:
        raise AssertionError(f"{arm}: manifest backend {manifest}")
    spans = [r for r in rows if r["kind"] == "spans"]
    stamps = [r["time"] for r in train]
    record.update(
        steps=len(train), losses=[round(x, 4) for x in losses],
        final_loss=round(losses[-1], 4),
        # Step 1's dispatch span is the trace + compile (or cache load).
        compile_s=round(spans[0]["dispatch"], 2),
        # Wall between consecutive per-step loss reads: each read waits
        # for its step (the `fence` phase checks that wait against
        # block_until_ready).
        step_s_median=round(float(np.median(np.diff(stamps))), 5),
        wire_bytes=[r["wire_bytes"] for r in rows if r["kind"] == "obs"],
        peak_bytes_in_use=peak_bytes())
    gc.collect()
    return record


def compiled_step_text(trainer) -> str:
    """HLO text of the step as the loop dispatches it (same arguments, so
    the compile is the persistent cache's entry, not a second one)."""
    step = int(trainer.state.step)
    batch = trainer._device_batch(trainer._fetch_host(step, 1))
    text = trainer._train_step.lower(
        trainer.state, trainer.carry, batch).compile().as_text()
    if "HloModule" not in text:
        raise AssertionError("compiled step has no HLO text")
    return text


def phase_one_chip(size: Size, *, on_chip: bool, seed: int = 0
                   ) -> List[Dict[str, Any]]:
    """The three arms at nworkers=1, the fence check riding the first."""
    records = []
    fence: Dict[str, Any] = {}

    def first(trainer, record):
        fence.update(fence_times(trainer))

    def twostage(trainer, record):
        has = "tpu_custom_call" in compiled_step_text(trainer)
        record["hlo_has_tpu_custom_call"] = has
        if on_chip and not has:
            raise AssertionError(
                "twostage step compiled without the Pallas kernel")

    hooks = {"gtopk": first, "gtopk_twostage": twostage}
    for arm in ARMS:
        rec = run_arm(arm, size, nworkers=1, steps=size.steps, seed=seed,
                      inspect=hooks.get(arm))
        if on_chip and rec["peak_bytes_in_use"] is None:
            raise AssertionError("the chip reported no peak_bytes_in_use")
        records.append(rec)
        if arm == "gtopk":
            records.append({"phase": "fence", **fence})
            if on_chip and fence["rel_diff"] > 0.25:
                raise AssertionError(
                    f"block_until_ready and the D2H fence disagree on a "
                    f"step's duration: {fence}")
    return records


def phase_mesh(size: Size, p: int, *, seed: int = 0
               ) -> List[Dict[str, Any]]:
    """gtopk against dense on a p-device dp mesh."""
    import jax
    import numpy as np

    if jax.device_count() < p:
        raise AssertionError(
            f"--chips {p}: jax sees {jax.device_count()} device(s)")

    def common(trainer, record):
        devices = list(trainer.mesh.devices.flat)
        if len(set(devices)) != p:
            raise AssertionError(f"mesh holds {devices}")
        record["mesh_devices"] = [str(d) for d in devices]
        record["collectives"] = collective_counts(
            compiled_step_text(trainer))
        # Replicated parameters: every device's copy, bit for bit.
        for leaf in jax.tree.leaves(trainer.state.params):
            copies = [np.asarray(s.data) for s in leaf.addressable_shards]
            if len(copies) != p or not all(
                    np.array_equal(copies[0], c) for c in copies[1:]):
                raise AssertionError("replicas' parameters differ")
        record["replicas_identical"] = True

    def sparse(trainer, record):
        common(trainer, record)
        residual = jax.tree.leaves(trainer.state.opt_state.residual)
        homes = {s.device for s in residual[0].addressable_shards}
        if len(homes) != p:
            raise AssertionError(
                f"residual shards live on {sorted(map(str, homes))}")
        record["residual_shard_devices"] = sorted(str(d) for d in homes)

    records = []
    for arm, hook in (("gtopk", sparse), ("dense", common)):
        rec = run_arm(arm, size, nworkers=p, steps=size.mesh_steps,
                      seed=seed, inspect=hook)
        records.append(rec)
    sparse_rec, dense_rec = records
    if not sparse_rec["wire_bytes"] or not all(
            b > 0 for b in sparse_rec["wire_bytes"]):
        raise AssertionError(
            f"sparse arm wire_bytes {sparse_rec['wire_bytes']}")
    c = sparse_rec["collectives"]
    if c["collective_permute"] + c["collective_permute_start"] == 0:
        raise AssertionError(f"no collective-permute in the sparse step: {c}")
    c = dense_rec["collectives"]
    if c["all_reduce"] + c["all_reduce_start"] == 0:
        raise AssertionError(f"no all-reduce in the dense step: {c}")
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"chip_smoke: jax backend is {jax.default_backend()!r}, not "
            "'tpu' — this script proves the chip path and has no other")
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    emit(environment_record())
    if args.chips == 1:
        emit(phase_kernels(FULL.kernel_n, FULL.density, interpret=False,
                           seed=args.seed))
        for rec in phase_one_chip(FULL, on_chip=True, seed=args.seed):
            emit(rec)
    else:
        for rec in phase_mesh(FULL, args.chips, seed=args.seed):
            emit(rec)
    emit({"ok": True, "device": device_record()})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
