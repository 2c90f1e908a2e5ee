"""chip_smoke.py and the start-up rules it relies on, rehearsed off the chip.

The script itself must FAIL here (no accelerator, no fallback); its phase
functions, called directly at a tiny size, run the same code on the virtual
CPU mesh — wrong paths, arguments, meshes and sharding rules show up here
and not in budgeted chip minutes. The compile-cache rule and the
no-silent-default rule of the benchmark's peak table ride along.
"""

import os
import re
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Size(dnn="resnet20", dataset="cifar10", batch_size=4,
                       dtype="float32", steps=3, mesh_steps=3,
                       density=0.01, kernel_n=70_000)


def _run(cmd, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable] + cmd, cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=300)


def test_no_accelerator_is_an_error_not_a_cpu_result():
    """The no-fallback contract: off the chip the smoke exits non-zero,
    prints no result line and runs no step."""
    proc = _run(["chip_smoke.py"])
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout and '"metric"' not in proc.stdout
    assert "[train]" not in proc.stdout + proc.stderr


def test_phase_kernels_rehearsal():
    rec = chip_smoke.phase_kernels(TINY.kernel_n, TINY.density,
                                   interpret=True)
    assert rec["multi_threshold_count"]["counts_equal"]
    assert rec["fused_multi_threshold_count+residual"]["counts_equal"]
    for name in ("fused_stage1_candidates",
                 "fused_stage1_candidates+residual",
                 "fused_stage1_candidates+residual+counts"):
        assert rec[name]["recall"] >= 0.95


def test_phase_one_chip_rehearsal(monkeypatch, tmp_path):
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    recs = {r["phase"]: r for r in
            chip_smoke.phase_one_chip(TINY, on_chip=False)}
    assert set(recs) == {"train/gtopk", "fence", "train/dense",
                         "train/gtopk_twostage"}
    for arm in ("gtopk", "dense", "gtopk_twostage"):
        rec = recs[f"train/{arm}"]
        assert rec["steps"] == TINY.steps == len(rec["losses"])
        assert rec["param_devices"] == [str(jax.devices()[0])]
        assert rec["step_compiles"] == 1
    assert recs["fence"]["block_until_ready_s"] > 0
    assert recs["fence"]["d2h_s"] > 0
    # Off the chip the twostage stage 1 is the XLA reference, and the
    # record says so; on the chip the same line must read true.
    assert recs["train/gtopk_twostage"]["hlo_has_tpu_custom_call"] is False


def test_phase_mesh_rehearsal_on_four_virtual_devices(monkeypatch, tmp_path):
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    sparse, dense = chip_smoke.phase_mesh(TINY, 4)
    four = [str(d) for d in jax.devices()[:4]]
    assert sparse["mesh_devices"] == four == sparse["residual_shard_devices"]
    assert sparse["replicas_identical"] and dense["replicas_identical"]
    # The dense arm's [P, 0] residual placeholder used to come back from
    # step 1 replicated and recompile step 2 (38 s on four chips).
    assert sparse["step_compiles"] == dense["step_compiles"] == 1
    assert all(b > 0 for b in sparse["wire_bytes"])
    c = sparse["collectives"]
    assert c["collective_permute"] + c["collective_permute_start"] > 0
    c = dense["collectives"]
    assert c["all_reduce"] + c["all_reduce_start"] > 0


def test_phase_mesh_refuses_fewer_devices_than_asked():
    with pytest.raises(AssertionError, match="jax sees 8"):
        chip_smoke.phase_mesh(TINY, 16)


def test_collective_counts_reads_both_spellings():
    text = """
  %cp = f32[8]{0} collective-permute(%x), channel_id=1
  %cps.1 = (f32[8]{0}, f32[8]{0}) collective-permute-start(%y), channel_id=2
  %cpd.1 = f32[8]{0} collective-permute-done(%cps.1)
  %ars = f32[8]{0} all-reduce-start(%z), to_apply=%add
  %ard = f32[8]{0} all-reduce-done(%ars)
  %ar.2 = f32[8]{0} all-reduce(%w), to_apply=%add
  %ar.3 = f32[8]{0} all-reduce(%v), to_apply=%add
"""
    assert chip_smoke.collective_counts(text) == {
        "collective_permute": 1, "collective_permute_start": 1,
        "all_reduce": 2, "all_reduce_start": 1}


# ------------------------------------------------------ compile-cache rule
CACHE_PROBE = (
    "from gtopkssgd_tpu.utils import enable_compilation_cache\n"
    "import jax\n"
    "d = enable_compilation_cache()\n"
    "assert d == jax.config.jax_compilation_cache_dir\n"
    "print(d, jax.config.jax_persistent_cache_min_entry_size_bytes)\n")


def test_cache_dir_from_environment_is_left_alone(tmp_path):
    proc = _run(["-c", CACHE_PROBE], JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(tmp_path), "-1"]


def test_cache_dir_defaults_to_the_checkout():
    proc = _run(["-c", CACHE_PROBE])
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [os.path.join(REPO, ".jax_cache"), "-1"]


def test_cache_dir_has_one_setter():
    """conftest, the benchmarks, the entry points and the worker scripts
    all go through utils.settings.enable_compilation_cache."""
    setter = re.compile(
        r"""update\(\s*['"]jax_compilation_cache_dir|GTOPK_JIT_CACHE""")
    sources = [f for f in os.listdir(REPO) if f.endswith(".py")]
    for top in ("gtopkssgd_tpu", "benchmarks", "tests", "experiments"):
        for root, _, files in os.walk(os.path.join(REPO, top)):
            sources += [os.path.relpath(os.path.join(root, f), REPO)
                        for f in files if f.endswith((".py", ".sh"))]
    hits = sorted(path for path in sources
                  if path != os.path.join("tests", "test_chip_smoke.py")
                  and setter.search(open(os.path.join(REPO, path)).read()))
    assert hits == ["gtopkssgd_tpu/utils/settings.py"]


TRIM_PROBE = (
    "import jax, jax.numpy as jnp\n"
    "from jax._src import monitoring\n"
    "from gtopkssgd_tpu.utils import settings\n"
    "calls = []\n"
    "settings.trim_heap = lambda: calls.append(1)\n"
    "before = len(monitoring.get_event_duration_listeners())\n"
    "settings.enable_compilation_cache()\n"
    "settings.enable_compilation_cache()\n"
    "assert len(monitoring.get_event_duration_listeners()) == before + 1\n"
    "settings.TRIM_AFTER_COMPILE_S = 1e9\n"
    "jax.jit(lambda x: x + 1)(jnp.ones(3)).block_until_ready()\n"
    "assert not calls, calls\n"
    "settings.TRIM_AFTER_COMPILE_S = 0.0\n"
    "jax.jit(lambda x: x * 2)(jnp.ones(3)).block_until_ready()\n"
    "assert calls\n"
    "print(len(calls) > 0)\n")


def test_a_long_compile_is_followed_by_a_trim_of_the_heap():
    """Registered once however often the cache is enabled; a compile
    under ``TRIM_AFTER_COMPILE_S`` trims nothing, one over it does, and
    that holds for a compile nobody of this package asked for."""
    proc = _run(["-c", TRIM_PROBE])
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == ["True"]


def test_trim_heap_hands_freed_pages_back():
    """On glibc ``trim_heap`` is True and the process's resident memory
    falls once a fragmented heap has been freed."""
    from gtopkssgd_tpu.utils import settings

    def resident():
        with open("/proc/self/status") as fh:
            return next(int(line.split()[1]) for line in fh
                        if line.startswith("VmRSS"))

    junk = [bytes(4000) for _ in range(100_000)]
    keep = junk[::50]        # pins the arena's top: free() alone trims little
    del junk
    before = resident()
    assert settings.trim_heap() is True
    assert resident() < before - 100_000, (before, resident(), len(keep))


def test_manifest_does_not_guard_the_backend(monkeypatch):
    """A backend that cannot be described is an error on the training
    path, not a manifest with backend=None."""
    from gtopkssgd_tpu.obs import manifest

    def broken():
        raise RuntimeError("backend failed to start")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="failed to start"):
        manifest.run_manifest()
