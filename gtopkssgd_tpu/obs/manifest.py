"""Run manifest: the self-describing header of every metrics.jsonl.

A metrics file divorced from the flags, code revision, and hardware that
produced it is archaeology, not observability — round 5's BENCH triage
spent most of its time reconstructing exactly that context from shell
history. The manifest is ONE extra jsonl record (kind "manifest", written
first) stamping the run with a config hash, the resolved headline flags,
the mesh shape, jax/backend versions, and the git sha, so
``obs.report`` can display provenance and the ``report gate`` can refuse
to compare runs whose configs differ.

Everything here is host-side and dependency-free (stdlib + an
already-initialized jax); git is optional (sha is null outside a
checkout or if git is missing).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import subprocess
from typing import Any, Dict, Optional

# Resolved-config fields surfaced as first-class manifest keys (the rest
# of the config is captured by the hash). Order is display order.
_HEADLINE_KEYS = (
    "dnn",
    "dataset",
    "compression",
    "density",
    "wire_codec",
    "nworkers",
    "batch_size",
    "seed",
)


def _config_dict(config: Any) -> Dict[str, Any]:
    if dataclasses.is_dataclass(config) and not isinstance(config, type):
        return dataclasses.asdict(config)
    return dict(config)


def config_hash(config: Any) -> str:
    """Stable short hash of the FULL config (sorted-key json; non-json
    leaves stringified), so two runs are comparable iff their hashes
    match — headline fields alone under-determine a run."""
    blob = json.dumps(_config_dict(config), sort_keys=True, default=str)
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:16]


def git_sha(repo_dir: Optional[str] = None) -> Optional[str]:
    """Short sha of the working tree this process imported from; None
    when git/the checkout is unavailable (installed package, CI tarball).
    '-dirty' is appended when tracked files have uncommitted changes, so
    a sha in a manifest is only trustworthy when clean."""
    repo_dir = repo_dir or os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            cwd=repo_dir, capture_output=True, text=True, timeout=5)
        if out.returncode != 0 or not out.stdout.strip():
            return None
        sha = out.stdout.strip()
        dirty = subprocess.run(
            ["git", "status", "--porcelain", "--untracked-files=no"],
            cwd=repo_dir, capture_output=True, text=True, timeout=5)
        if dirty.returncode == 0 and dirty.stdout.strip():
            sha += "-dirty"
        return sha
    except Exception:
        return None


def coordinator_address() -> Optional[str]:
    """The jax.distributed coordinator this process joined, or None for
    single-process runs. Read from jax's internal distributed state —
    there is no public accessor — so failures of any shape degrade to
    None rather than killing the run for a header field."""
    try:
        from jax._src import distributed

        return distributed.global_state.coordinator_address
    except Exception:
        return None


def run_manifest(config: Any = None, mesh=None, **extra) -> Dict[str, Any]:
    """Assemble the manifest record body (no "kind"/"time" — the metrics
    logger adds those). ``config`` is any dataclass or mapping;
    ``mesh`` a jax Mesh (axis names -> sizes); ``extra`` lands verbatim
    (e.g. num_params, steps_per_epoch). Requires jax to already be
    initialized in the intended configuration — the backend fields
    record what THIS process actually ran on."""
    import jax

    man: Dict[str, Any] = {}
    if config is not None:
        cfg = _config_dict(config)
        man["config_hash"] = config_hash(cfg)
        for key in _HEADLINE_KEYS:
            if key in cfg:
                man[key] = cfg[key]
    if mesh is not None:
        man["mesh_shape"] = {
            str(name): int(size)
            for name, size in zip(mesh.axis_names, mesh.devices.shape)
        }
    import jaxlib

    man["jax_version"] = jax.__version__
    man["jaxlib_version"] = jaxlib.__version__
    # No guard: a backend that cannot be described cannot train either,
    # and a manifest that says backend=None would hide which device ran.
    man["backend"] = jax.default_backend()
    man["device_kind"] = jax.devices()[0].device_kind
    man["device_count"] = jax.device_count()
    man["process_count"] = jax.process_count()
    # WHICH process wrote this shard — with process_count and the
    # coordinator address, the fleet merger can confirm that shards
    # in one dir really are one distributed run (config_hash is the
    # primary join key; these make mismatch errors explainable).
    man["process_index"] = jax.process_index()
    man["coordinator_address"] = coordinator_address()
    man["git_sha"] = git_sha()
    man.update(extra)
    return man
