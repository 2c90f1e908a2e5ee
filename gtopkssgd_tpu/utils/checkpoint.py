"""Checkpoint/resume via Orbax (reference C1 saved only the model
state_dict at epoch boundaries and silently LOST the compressor residuals
on resume — SURVEY.md §5. Here the whole training state is one pytree, so
the error-feedback residual, momentum, and step count all survive a
restart; the trainer additionally fast-forwards the data stream to the
restored position).

Integrity (resilience subsystem): every save writes a sidecar
``integrity-<step>.json`` next to orbax's step dir, recording the run's
config_hash (obs/manifest.py — the same hash the run manifest carries)
and a digest of the state treedef + per-leaf shapes/dtypes. restore()
verifies both BEFORE handing bytes to orbax:

  config_hash mismatch  -> CheckpointMismatch (refused: resuming a run
                           under different flags silently changes the
                           experiment; ``allow_mismatch`` is the
                           explicit escape hatch, mirroring the fleet
                           merger's --allow-mismatch)
  digest mismatch       -> CheckpointMismatch (the state structure
                           changed — e.g. obs_layers toggled — and an
                           orbax restore into the wrong treedef would
                           fail later and worse)
  corrupt/unreadable    -> fall back to the PREVIOUS step (a machine
                           killed mid-save leaves a torn latest; losing
                           one save interval beats losing the run)

A checkpoint with no sidecar (written before this subsystem) restores
with a warning — old runs stay resumable.
"""

from __future__ import annotations

import json
import hashlib
import os
from typing import Any, List, Optional

import orbax.checkpoint as ocp


class CheckpointMismatch(RuntimeError):
    """Refusal to restore a checkpoint whose recorded config_hash or
    state digest disagrees with the restoring run's."""


def state_digest(state: Any) -> str:
    """Short digest of a pytree's STRUCTURE (treedef + per-leaf
    shape/dtype): two states with equal digests are restore-compatible.
    Works on concrete arrays and ShapeDtypeStruct templates alike."""
    import jax

    leaves, treedef = jax.tree.flatten(state)
    blob = json.dumps([str(treedef)] + [
        [list(getattr(x, "shape", ())), str(getattr(x, "dtype", type(x)))]
        for x in leaves
    ])
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:16]


class CheckpointManager:
    """Orbax CheckpointManager wrapper for one state pytree, plus the
    integrity sidecars described in the module docstring.

    The state must be a pure pytree of arrays/scalars (the trainer's
    TrainState qualifies — residual included, since it lives in opt_state).
    """

    def __init__(self, directory: str, max_to_keep: int = 3,
                 config_hash: Optional[str] = None, logger=None):
        self.directory = os.path.abspath(directory)
        self.config_hash = config_hash
        self.logger = logger
        self.last_restored_step: Optional[int] = None
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, create=True
            ),
        )

    # --------------------------------------------------------- integrity
    def _integrity_path(self, step: int) -> str:
        return os.path.join(self.directory, f"integrity-{step}.json")

    def _write_integrity(self, step: int, state: Any,
                         meta: Optional[dict] = None) -> None:
        rec = {
            "step": int(step),
            "config_hash": self.config_hash,
            "state_digest": state_digest(state),
        }
        if meta:
            # Small json-able facts about the SAVED state that a
            # restoring run needs before it can build a template — e.g.
            # the elastic resize path records residual_p, the partition
            # width of the per-device residual, so a different-P resume
            # knows the old shape without guessing.
            rec["meta"] = dict(meta)
        path = self._integrity_path(step)
        # A temporary of this process's own: the processes of a run share
        # the directory and write the same record, and with one name for
        # all the slower one's rename found the file already moved.
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as fh:
            json.dump(rec, fh, sort_keys=True)
            fh.write("\n")
        os.replace(tmp, path)  # atomic: no torn sidecars

    def _read_integrity(self, step: int) -> Optional[dict]:
        try:
            with open(self._integrity_path(step)) as fh:
                return json.load(fh)
        except (OSError, ValueError):
            return None

    def _prune_integrity(self) -> None:
        """Drop sidecars whose step orbax already garbage-collected
        (max_to_keep), so the directory stays in lockstep."""
        live = set(self.all_steps())
        try:
            names = os.listdir(self.directory)
        except OSError:
            return
        for name in names:
            if not (name.startswith("integrity-")
                    and name.endswith(".json")):
                continue
            stem = name[len("integrity-"):-len(".json")]
            if stem.isdigit() and int(stem) not in live:
                try:
                    os.remove(os.path.join(self.directory, name))
                except OSError:
                    pass

    def _verify(self, step: int, state_template: Any,
                allow_mismatch: bool) -> None:
        rec = self._read_integrity(step)
        if rec is None:
            if self.logger is not None:
                self.logger.warning(
                    "checkpoint step %d has no integrity sidecar "
                    "(pre-resilience save); restoring unverified", step)
            return
        problems: List[str] = []
        want_hash = rec.get("config_hash")
        if (want_hash is not None and self.config_hash is not None
                and want_hash != self.config_hash):
            problems.append(
                f"config_hash {want_hash} != this run's "
                f"{self.config_hash} (different flags)")
        want_digest = rec.get("state_digest")
        have_digest = state_digest(state_template)
        if want_digest is not None and want_digest != have_digest:
            problems.append(
                f"state digest {want_digest} != template {have_digest} "
                "(state treedef/shape change)")
        if not problems:
            return
        msg = (f"checkpoint step {step} in {self.directory} does not "
               f"match this run: " + "; ".join(problems))
        if allow_mismatch:
            if self.logger is not None:
                self.logger.warning("%s — restoring anyway "
                                    "(--allow-ckpt-mismatch)", msg)
            return
        raise CheckpointMismatch(
            msg + " (pass --allow-ckpt-mismatch to override)")

    def sidecar_meta(self, step: Optional[int] = None) -> dict:
        """The ``meta`` dict saved alongside ``step`` (default: latest
        step); {} when the step has no sidecar or the sidecar predates
        the meta channel."""
        if step is None:
            step = self.latest_step()
        if step is None:
            return {}
        rec = self._read_integrity(int(step))
        meta = rec.get("meta") if rec else None
        return dict(meta) if isinstance(meta, dict) else {}

    # ------------------------------------------------------ save/restore
    def save(self, step: int, state: Any, *, force: bool = False,
             meta: Optional[dict] = None) -> bool:
        saved = self._mgr.save(
            step, args=ocp.args.StandardSave(state), force=force
        )
        self._mgr.wait_until_finished()
        if saved:
            self._write_integrity(step, state, meta=meta)
            self._prune_integrity()
        return saved

    def restore(self, state_template: Any, step: Optional[int] = None,
                allow_mismatch: bool = False) -> Any:
        """Restore ``step`` (default: latest), verifying integrity first
        and falling back step-by-step past CORRUPT checkpoints. Mismatch
        refusals (CheckpointMismatch) never fall back — every step of a
        dir shares one run config, so an older step cannot fix it."""
        if step is not None:
            candidates = [step]
        else:
            candidates = sorted(self.all_steps(), reverse=True)
        if not candidates:
            return None
        last_err: Optional[Exception] = None
        for s in candidates:
            self._verify(s, state_template, allow_mismatch)
            try:
                state = self._mgr.restore(
                    s, args=ocp.args.StandardRestore(state_template)
                )
            except Exception as e:  # torn/corrupt step: try the previous
                last_err = e
                if self.logger is not None:
                    self.logger.warning(
                        "checkpoint step %d unreadable (%s: %s); falling "
                        "back to the previous step", s, type(e).__name__,
                        str(e)[:200])
                continue
            self.last_restored_step = int(s)
            if self.logger is not None and s != candidates[0]:
                self.logger.warning(
                    "restored FALLBACK step %d (latest step %d was "
                    "corrupt)", s, candidates[0])
            return state
        raise RuntimeError(
            f"no restorable checkpoint in {self.directory} "
            f"(tried steps {candidates})") from last_err

    def all_steps(self) -> List[int]:
        return sorted(int(s) for s in self._mgr.all_steps())

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def close(self) -> None:
        self._mgr.close()
