"""Test harness: run all tests on a virtual 8-device CPU mesh.

The reference (hclhkbu/gtopkssgd) had no test suite at all — multi-node
behavior could only be exercised with a real `mpirun` launch. JAX lets us run
real 8-way SPMD collectives in one process: force 8 host CPU devices before
jax initializes (SURVEY.md §4).
"""

import os

# Must happen before jax initializes its backends.
from gtopkssgd_tpu.utils import (  # noqa: E402
    enable_compilation_cache,
    force_cpu_mesh,
)

force_cpu_mesh(8)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
# Persistent compilation cache: the suite's cost is dominated by XLA:CPU
# compiles of model train steps; caching them on disk makes repeated runs
# (and identical HLO across tests) fast. Where it lives is the helper's
# rule: JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache.
enable_compilation_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="module")
def v5e():
    """One described v5e device, for the files that ask the chip's compiler
    without the chip (``test_pallas_compile.py``, ``test_flash_compile.py``,
    ``test_hybrid_compile.py``, ``test_looped_compile.py``,
    ``test_mla_compile.py``, ``test_kda_compile.py``: one published step a
    file, so that each has a worker of its own). Nothing but a test
    that asks for it describes the topology. The persistent cache is off
    around the module (a compile for a described device is written to it
    but cannot be read back without a chip, and the next one warns)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def load_benchmark_module(name: str):
    """Import benchmarks/<name>.py by path (benchmarks/ is not a package
    on sys.path for the test run). Shared by the tests that pin the
    benchmark harnesses so the loader boilerplate cannot drift."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_two_process(worker_src: str, tmp_path, ok_token: str) -> str:
    """Run a 2-process ``jax.distributed`` worker script (argv: coordinator,
    process id, repo root, out_dir) on the CPU backend and return out_dir.
    Skips on jax builds without CPU cross-process collectives (the workers
    exit 99). Output goes to FILES: with pipes, the worker whose pipe is
    not being drained blocks once XLA has logged 64 KiB, and its peer then
    waits for it inside a collective forever."""
    import socket
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as s:
        s.bind(("localhost", 0))
        coord = f"localhost:{s.getsockname()[1]}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=1")
    env["XLA_FLAGS"] = " ".join(flags)

    script = tmp_path / "worker.py"
    script.write_text(worker_src)
    out_dir = str(tmp_path / "run")
    logs = [tmp_path / f"worker{pid}.log" for pid in (0, 1)]
    procs = []
    for pid, log in enumerate(logs):
        with open(log, "w") as fh:
            procs.append(subprocess.Popen(
                [sys.executable, str(script), coord, str(pid), repo,
                 out_dir],
                env=env, cwd=repo, stdout=fh, stderr=subprocess.STDOUT))
    try:
        for p in procs:
            p.wait(timeout=850)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    outs = [(p.returncode, log.read_text()) for p, log in zip(procs, logs)]
    if any(rc == 99 for rc, _ in outs):
        pytest.skip("jax build lacks CPU cross-process collectives: "
                    + outs[0][1].splitlines()[-1])
    for rc, out in outs:
        assert rc == 0, out[-4000:]
        assert ok_token in out
    return out_dir


# One case of ``perfbench/test_perfbench_contract.py`` cannot hold for a
# configuration that is one chip's share of a model: the test asserts
# ``config["reduced"] == entry["reduced"] == []``, which was true of the two
# unreduced configurations it was written for (PR 23), while the contract
# allows up to 16 reduced keys. No file of the benchmark may be edited by
# the PR that adds a configuration, and ``tests/perfbench/conftest.py`` (a
# benchmark file since PR 26) names only that PR's case, so the cases of
# PRs 31, 35, 39, 41, 45 and 48 are marked here, strictly and with the same wording: when
# a ``benchmark`` PR relaxes the assertion the cases pass, these marks fail,
# and these lines go. Everything else that test checks of an entry is
# checked for Keye's and Trinity's configurations, by name, in
# ``perfbench/test_perfbench_entries_by_name.py``, for Kanana's in
# ``perfbench/test_perfbench_cell_kanana2.py``, for Ouro's in
# ``perfbench/test_perfbench_cell_ouro.py``, for SDAR's in
# ``perfbench/test_perfbench_cell_sdar.py`` and for Kimi-Linear's in
# ``perfbench/test_perfbench_cell_kimi_linear.py``.
STALE = tuple(
    "test_perfbench_contract.py::"
    "test_entry_has_just_the_contracts_keys_and_characters"
    f"[configs-{config}]"
    for config in ("keye_vl2_30b_a3b_ep16", "trinity_mini_26b_a3b_ep16",
                   "kanana2_30b_a3b_ep16", "ouro_2p6b_l5",
                   "sdar_30b_a3b_ep8", "kimi_linear_48b_a3b_ep32"))
# PR 31's own letter test asserts that its configuration, its cell and its six
# metrics are the LAST entries of their lists in BENCHMARK.json: true of the
# PR that appended them, false as soon as the next one appends (PR 35: one
# configuration, one cell, six metrics, at the ends of the lists as the
# contract asks). The file is the benchmark's and not this PR's to edit, and
# a mark takes a whole test, so the case is marked here, strictly: a
# ``benchmark`` PR that makes the test look its entries up by name makes it
# pass, this mark fails, and it goes. Everything it asserts of Keye's entries
# besides their place (the entry against its file, no width under
# ``reduced``, the cell's traffic, the six metrics' lists, the limits' whys)
# is asserted by name in ``perfbench/test_perfbench_entries_by_name.py``.
LAST_NO_MORE = ("test_perfbench_cell_keye_vl2.py::"
                "test_entries_keep_the_contracts_letter")

# ``perfbench/test_perfbench_entries_by_name.py`` ends its letter test with
# "the cell is in no other metric's list": true while every decoder metric
# belonged to one cell, false since PR 37's six (the attention's parts, their
# share and the replay) list all three decoder cells, as ISSUE 37 asks. The
# file is the benchmark's, so its two cases are marked here, strictly: a
# ``benchmark`` PR that lets a shared metric list the cell makes them pass,
# these marks fail, and they go. The marks hide that one assertion only:
# ``perfbench/test_perfbench_parts.py::
# test_entries_keep_the_contracts_letter_beside_the_shared_metrics`` is the
# test's whole body, assertion for assertion (entry keys and characters, no
# width under ``reduced``, the entry against its file, the cell, the metrics'
# units, directions, ``moves``, ``layer`` and lists, the nine limits), with
# that clause naming the six.
SHARED_SINCE = tuple(
    "test_perfbench_entries_by_name.py::"
    f"test_entries_keep_the_contracts_letter[{config}]"
    for config in ("keye_vl2_30b_a3b_ep16", "trinity_mini_26b_a3b_ep16"))


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(SHARED_SINCE):
            item.add_marker(pytest.mark.xfail(
                strict=True,
                reason="asserts its cell is in no other metric's list; six "
                       "metrics list the three decoder cells since PR 37"))
            continue
        if item.nodeid.endswith(STALE):
            item.add_marker(pytest.mark.xfail(
                strict=True,
                reason="asserts reduced == [] of every configuration; this "
                       "one lists its cuts, as the contract asks"))
        elif item.nodeid.endswith(LAST_NO_MORE):
            item.add_marker(pytest.mark.xfail(
                strict=True,
                reason="asserts its entries are the last of BENCHMARK.json's "
                       "lists; a later PR has appended its own"))
