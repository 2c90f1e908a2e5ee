"""The boundary between the program and what measures it.

The compiled step is configured by the package alone: tracing it reads
no file outside ``gtopkssgd_tpu/`` and imports nothing of ``benchmarks/``
(the wire plan it runs was decided above it, host-side, and handed down
by name), the layers below ``obs/`` do not import it, and no module of
the package reaches into ``benchmarks/`` by import or by path. So a
record under ``benchmarks/results/`` can be moved or deleted without
asking whether a measured cell's step reads it.
"""

import ast
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from gtopkssgd_tpu.optimizer import gtopk_sgd
from gtopkssgd_tpu.parallel import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "gtopkssgd_tpu")

# One audit hook for the process (hooks cannot be removed): it records
# only while a test has put a list here.
_watch = {"events": None}
_FILE_EVENTS = {"open", "os.listdir", "os.scandir", "glob.glob"}


def _audit(event, args):
    log = _watch["events"]
    if log is None:
        return
    if event in _FILE_EVENTS and args and isinstance(args[0], (str, bytes)):
        log.append(("file", os.path.abspath(os.fsdecode(args[0]))))
    elif event == "import":
        log.append(("import", args[0]))


sys.addaudithook(_audit)


@pytest.mark.parametrize("mode,extra", [
    ("dense", {}),
    ("gtopk", {}),
    ("allgather", {}),
    ("gtopk_hier", {"hier_ici_size": 2}),
    ("gtopk_layerwise", {"buckets": "concat"}),
])
def test_tracing_the_step_reads_nothing_outside_the_package(mode, extra):
    p, n = 4, 400
    params = {"w": jnp.zeros((n,)), "b": jnp.zeros((n // 10,))}
    grads = jax.tree.map(
        lambda a: jnp.asarray(np.random.default_rng(3).standard_normal(
            (p,) + a.shape).astype(np.float32)), params)
    tx = gtopk_sgd(0.1, compression=mode, density=0.05, axis_name="dp",
                   axis_size=p, **extra)
    state = jax.jit(tx.init)(params)

    def step(params, state, grads):
        grads = jax.tree.map(lambda g: g[0], grads)
        updates, state = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    fn = jax.jit(jax.shard_map(
        step, mesh=make_mesh(p), in_specs=(P(), P(), P("dp")),
        out_specs=(P(), P()), check_vma=False))
    _watch["events"] = events = []
    try:
        fn.lower(params, state, grads)
    finally:
        _watch["events"] = None
    outside = sorted({
        path for kind, path in events if kind == "file"
        and path.startswith(REPO + os.sep)
        and not path.startswith(PKG + os.sep)})
    assert outside == [], outside
    assert not [name for kind, name in events
                if kind == "import" and "benchmarks" in name]


def _modules(*roots):
    for root in roots:
        if root.endswith(".py"):
            yield root
            continue
        for dirpath, _, names in os.walk(root):
            for name in sorted(names):
                if name.endswith(".py"):
                    yield os.path.join(dirpath, name)


def _imported(path):
    """Absolute dotted names a module imports, relative ones resolved."""
    here = os.path.relpath(path, REPO)[:-3].split(os.sep)
    if here[-1] == "__init__":
        here = here[:-1]
        package = here
    else:
        package = here[:-1]
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom):
            base = (package[:len(package) - node.level + 1]
                    if node.level else [])
            mod = ".".join(base + ([node.module] if node.module else []))
            yield mod
            for alias in node.names:
                yield f"{mod}.{alias.name}"


@pytest.mark.parametrize("layer", ["ops", "parallel", "compression.py"])
def test_layers_below_obs_do_not_import_it(layer):
    """``obs`` -> ``parallel`` is the only arrow: the collectives, the
    comm model, the planner, the kernels and the compressors know
    nothing of the planes that watch them."""
    offenders = [
        (os.path.relpath(path, REPO), name)
        for path in _modules(os.path.join(PKG, layer))
        for name in _imported(path)
        if name == "gtopkssgd_tpu.obs"
        or name.startswith("gtopkssgd_tpu.obs.")]
    assert offenders == []


_PATH_CALLS = {"join", "glob", "iglob", "open", "listdir", "scandir",
               "Path", "spec_from_file_location", "import_module"}


def test_package_reaches_into_benchmarks_by_no_import_and_no_path():
    offenders = []
    for path in _modules(PKG):
        rel = os.path.relpath(path, REPO)
        offenders += [(rel, name) for name in _imported(path)
                      if name.split(".")[0] == "benchmarks"]
        for node in ast.walk(ast.parse(open(path).read())):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            called = getattr(func, "attr", getattr(func, "id", None))
            if called not in _PATH_CALLS:
                continue
            offenders += [
                (rel, node.lineno, called) for arg in node.args
                if isinstance(arg, ast.Constant)
                and isinstance(arg.value, str)
                and "benchmarks" in arg.value]
    assert offenders == []
