"""Deterministic, step-keyed fault injection (``--inject SPEC``).

Chaos testing a distributed trainer is only useful when the chaos is
reproducible: a fault keyed to wall-clock or randomness gives every CI
run a different failure and no way to bisect. Here faults are keyed to
the OPTIMIZER STEP, so the same spec produces the same perturbation at
the same point of the same data stream on every run — on a CPU mesh in
CI just as on a pod.

Spec grammar (comma-separated faults)::

    SPEC  := FAULT ("," FAULT)*
    FAULT := KIND (":" ARG)* "@" WHEN
    WHEN  := STEP | STEP "-" STEP | "latest"       (steps are 1-based)

Kinds:

  nan_grad@K          poison the params fed to step K's dispatch (first
                      leaf multiplied by NaN): loss and gradients go NaN
                      exactly like a real numerical blow-up, and the
                      anomaly monitor's nan_loss rule sees it at the next
                      sync. Point faults fire ONCE (consumed), so a
                      skip-recovery that rewinds the step counter does
                      not re-trigger them; a range (``@2-99``) re-fires
                      every step in the window (how the skip-budget
                      exhaustion path is exercised).
  slow_rank:R:DUR@A-B sleep DUR (e.g. ``2.5s`` or ``0.1``) before each
                      step in [A, B] on the process with index R — a
                      deterministic persistent straggler.
  loader_raise@K      raise InjectedLoaderError from the host batch
                      fetch at step K, once; the trainer's retry_call
                      wrapper absorbs it (consumed on first raise, so
                      the retry succeeds).
  preempt@K           deliver SIGTERM to this process right after step
                      K's dispatch — the real signal, through the real
                      PreemptionGuard handler, so the emergency-save
                      path is tested end to end.
  corrupt_ckpt@latest truncate the files of the LATEST checkpoint step
                      right before the next restore() — exercises
                      integrity verification and the fallback to the
                      previous step.
  reshape@K           halve the per-shard batch axis of step K's host
                      batch before device transfer — a NEW dispatch
                      shape, so the jitted step retraces and the
                      executable cache grows (the deterministic input
                      for obs/memwatch.py's recompile_storm rule).
                      Point faults fire once; the next dispatch is back
                      to the canonical shape. A range re-fires per step
                      in the window (sustained storm).
  resize@K:NEWP       elastic-resize request at the step-K boundary:
                      the trainer drains, emergency-saves, rewrites the
                      lineage file for NEWP workers, and unwinds via
                      ResizeRestart -> exit 46 (resilience/elastic.py).
                      WHEN carries the target fleet size (point fault
                      only — a fleet cannot re-form per-step). Requires
                      --elastic; without it the firing records and
                      warns but training continues.
  evict_rank:R@K      eviction-resize request at the step-K boundary:
                      the chaos stand-in for a goodput-advised
                      straggler eviction — same drain/save/exit-46
                      path as resize with reason=evict, new_p = P-1,
                      evicted_ranks=[R]. Point fault only; requires
                      --elastic.

Every firing logs one fsync'd "inject" record (fault, step, detail), so
``report recovery`` can line injected faults up against the recovery
actions they provoked.
"""

from __future__ import annotations

import dataclasses
import os
import signal
import time
from typing import Any, List, Optional, Tuple

KINDS = ("nan_grad", "slow_rank", "loader_raise", "preempt", "corrupt_ckpt",
         "reshape", "resize", "evict_rank")

# WHEN == "latest" sentinel (corrupt_ckpt: fires at the next restore).
LATEST = -1


class InjectedLoaderError(IOError):
    """The loader_raise fault; retried away by resilience.retry_call."""


@dataclasses.dataclass
class Fault:
    kind: str
    start: int           # first step of the window (LATEST for @latest)
    end: int             # last step (== start for point faults)
    args: Tuple[str, ...] = ()
    fired: int = 0       # firings so far; point faults are consumed at 1

    @property
    def point(self) -> bool:
        return self.start == self.end

    def window(self, prev: int, new: int) -> Optional[int]:
        """The step in (prev, new] this fault fires for, or None. Point
        faults never re-fire (a skip-recovery rewinds the step counter
        past an already-consumed fault); range faults fire once per
        dispatch while the window overlaps."""
        if self.start == LATEST:
            return None
        if self.point and self.fired:
            return None
        lo, hi = max(self.start, prev + 1), min(self.end, new)
        return lo if lo <= hi else None

    def spec(self) -> str:
        if self.kind == "resize":
            # canonical grammar puts the target P after the step:
            # resize@K:NEWP (args holds NEWP; see parse_inject)
            return f"resize@{self.start}:{self.args[0]}"
        head = ":".join((self.kind,) + self.args)
        if self.start == LATEST:
            return f"{head}@latest"
        if self.point:
            return f"{head}@{self.start}"
        return f"{head}@{self.start}-{self.end}"


def _parse_duration(text: str) -> float:
    seconds = float(text[:-1] if text.endswith("s") else text)
    if seconds < 0:
        raise ValueError(f"negative duration {text!r}")
    return seconds


def parse_inject(spec: str) -> List[Fault]:
    """Parse an ``--inject`` spec; raises ValueError with the offending
    fragment on any malformed input (fail at argparse time, not at step
    K three hours in)."""
    faults: List[Fault] = []
    for frag in (f.strip() for f in spec.split(",") if f.strip()):
        if "@" not in frag:
            raise ValueError(
                f"inject fault {frag!r} has no '@WHEN' (grammar: "
                "KIND[:ARG...]@STEP|A-B|latest)")
        head, _, when = frag.rpartition("@")
        parts = head.split(":")
        kind, args = parts[0], tuple(parts[1:])
        if kind not in KINDS:
            raise ValueError(
                f"unknown inject kind {kind!r} (known: {', '.join(KINDS)})")
        if when == "latest":
            if kind != "corrupt_ckpt":
                raise ValueError(
                    f"@latest only applies to corrupt_ckpt, not {kind!r}")
            start = end = LATEST
        elif kind == "resize":
            # resize@K:NEWP — the WHEN carries the target fleet size,
            # so the generic STEP|A-B parse below does not apply.
            if args:
                raise ValueError(
                    f"resize takes no ':' args before '@'; the target P "
                    f"goes after the step (resize@K:NEWP), got {frag!r}")
            lo, sep, newp = when.partition(":")
            try:
                start = end = int(lo)
                new_p = int(newp) if sep else 0
            except ValueError:
                raise ValueError(
                    f"inject fault {frag!r}: resize WHEN must be "
                    "STEP:NEW_P (e.g. resize@3:1)") from None
            if not sep or start < 1 or new_p < 1:
                raise ValueError(
                    f"inject fault {frag!r}: resize needs STEP >= 1 "
                    "and NEW_P >= 1 (grammar resize@K:NEWP)")
            args = (str(new_p),)
        else:
            lo, sep, hi = when.partition("-")
            try:
                start = int(lo)
                end = int(hi) if sep else start
            except ValueError:
                raise ValueError(
                    f"inject fault {frag!r}: WHEN must be STEP, A-B, or "
                    "latest") from None
            if start < 1 or end < start:
                raise ValueError(
                    f"inject fault {frag!r}: bad step window "
                    f"[{start}, {end}]")
            if kind == "corrupt_ckpt":
                raise ValueError(
                    "corrupt_ckpt is keyed to restore time; use "
                    "corrupt_ckpt@latest")
        if kind == "slow_rank":
            if len(args) != 2:
                raise ValueError(
                    f"slow_rank needs RANK:DURATION args, got {frag!r}")
            int(args[0])
            _parse_duration(args[1])
        elif kind == "evict_rank":
            if len(args) != 1:
                raise ValueError(
                    f"evict_rank needs a RANK arg, got {frag!r}")
            try:
                rank = int(args[0])
            except ValueError:
                raise ValueError(
                    f"evict_rank RANK must be an int, got {frag!r}"
                ) from None
            if rank < 0:
                raise ValueError(
                    f"evict_rank RANK must be >= 0, got {frag!r}")
            if start != end:
                raise ValueError(
                    f"evict_rank is a point fault (a fleet re-forms "
                    f"once, not per-step), got {frag!r}")
        elif kind == "resize":
            pass  # args minted from the WHEN parse above
        elif args:
            raise ValueError(f"{kind} takes no ':' args, got {frag!r}")
        faults.append(Fault(kind=kind, start=start, end=end, args=args))
    if not faults:
        raise ValueError(f"empty inject spec {spec!r}")
    return faults


class FaultInjector:
    """Holds the parsed fault list and exposes one hook per injection
    point; the trainer calls each hook with the host step window
    (prev, new] of the dispatch being prepared or retired. Hooks that
    hit no active fault are O(#faults) comparisons — negligible against
    a training step."""

    def __init__(self, spec: str, metrics=None, logger=None, rank: int = 0):
        self.faults = parse_inject(spec)
        self.metrics = metrics
        self.logger = logger
        self.rank = rank

    def _record(self, fault: Fault, step: int, **extra: Any) -> None:
        fault.fired += 1
        if self.logger is not None:
            self.logger.warning("inject: %s fired at step %d",
                                fault.spec(), step)
        if self.metrics is not None:
            self.metrics.log("inject", flush=True, fault=fault.kind,
                             step=step, spec=fault.spec(), **extra)

    def _active(self, kind: str, prev: int, new: int):
        for f in self.faults:
            if f.kind != kind:
                continue
            at = f.window(prev, new)
            if at is not None:
                yield f, at

    # ------------------------------------------------------------- hooks
    def sleep_if_slow(self, prev: int, new: int) -> float:
        """Pre-dispatch: the slow_rank straggler. Returns seconds slept."""
        slept = 0.0
        for f, at in self._active("slow_rank", prev, new):
            if int(f.args[0]) != self.rank:
                continue
            dur = _parse_duration(f.args[1])
            self._record(f, at, seconds=dur)
            time.sleep(dur)
            slept += dur
        return slept

    def check_loader(self, prev: int, new: int) -> None:
        """Inside the host batch fetch: loader_raise. Consumed on the
        first raise, so the surrounding retry_call's retry succeeds."""
        for f, at in self._active("loader_raise", prev, new):
            self._record(f, at)
            raise InjectedLoaderError(
                f"injected loader failure at step {at}")

    def poison_params(self, state, prev: int, new: int):
        """Pre-dispatch: nan_grad. Multiplies the first params leaf by
        NaN so the dispatched step computes a NaN loss/gradients — the
        same HLO as a clean step (no retrace), and the caller's pre-
        poison snapshot stays the clean state a skip restores."""
        hit = False
        for f, at in self._active("nan_grad", prev, new):
            self._record(f, at)
            hit = True
        if not hit:
            return state
        import jax

        leaves, treedef = jax.tree.flatten(state.params)
        leaves[0] = leaves[0] * float("nan")
        return state._replace(params=jax.tree.unflatten(treedef, leaves))

    def reshape_batch(self, shards, prev: int, new: int, axis: int = 1):
        """Pre-transfer: reshape. Halves the batch axis of every shard
        of the assembled host batch (a list of per-shard dicts, numpy
        leaves [nsteps, B, ...] — ``axis`` indexes B; the trainer passes
        2 when steps_per_dispatch stacks an extra axis). A changed
        dispatch shape forces the jitted step to retrace — the
        deterministic recompile chaos input. Loss stays a batch mean, so
        training arithmetic survives the smaller step; a 1-sample batch
        cannot halve and the fault downgrades to a no-op record."""
        for f, at in self._active("reshape", prev, new):
            dim = min(v.shape[axis] for s in shards for v in s.values())
            if dim < 2:
                self._record(f, at, batch_axis=axis, from_dim=dim,
                             to_dim=dim)
                continue
            half = dim // 2
            self._record(f, at, batch_axis=axis, from_dim=dim, to_dim=half)
            cut = (slice(None),) * axis + (slice(0, half),)
            shards = [{k: v[cut] for k, v in s.items()} for s in shards]
        return shards

    def maybe_preempt(self, prev: int, new: int, guard=None) -> None:
        """Post-dispatch: preempt. Sends this process a REAL SIGTERM so
        the PreemptionGuard handler and the emergency-save path run
        exactly as under an external preemption. Requires an installed
        guard — without one the default handler would hard-kill the
        process, so the fault downgrades to a warning."""
        for f, at in self._active("preempt", prev, new):
            if guard is None:
                if self.logger is not None:
                    self.logger.warning(
                        "inject: preempt@%d skipped — no PreemptionGuard "
                        "installed (run via dist_trainer)", at)
                continue
            self._record(f, at)
            os.kill(os.getpid(), signal.SIGTERM)

    def pending_resize(self, prev: int, new: int) -> Optional[int]:
        """Step-boundary check: resize@K:NEW_P. Returns the target
        fleet size when a resize fault fires in (prev, new], else None.
        The durable "inject" record lands here, BEFORE the trainer's
        drain/save/unwind — the process exits 46 shortly after."""
        for f, at in self._active("resize", prev, new):
            new_p = int(f.args[0])
            self._record(f, at, new_p=new_p)
            return new_p
        return None

    def pending_evict(self, prev: int, new: int) -> Optional[int]:
        """Step-boundary check: evict_rank:R@K — the chaos stand-in for
        a goodput-advised straggler eviction. Returns the rank to
        evict, else None."""
        for f, at in self._active("evict_rank", prev, new):
            rank = int(f.args[0])
            self._record(f, at, evicted_rank=rank)
            return rank
        return None

    def maybe_corrupt_ckpt(self, directory: Optional[str]) -> bool:
        """Restore-time: corrupt_ckpt@latest. Truncates every payload
        file of the latest checkpoint step so orbax's restore raises
        while the step directory still lists — the exact shape of a
        half-written checkpoint after a mid-save kill."""
        fired = False
        for f in self.faults:
            if f.kind != "corrupt_ckpt" or f.fired:
                continue
            if not directory or not os.path.isdir(directory):
                continue
            step_dirs = sorted(
                (int(name), os.path.join(directory, name))
                for name in os.listdir(directory) if name.isdigit())
            if not step_dirs:
                continue
            step, target = step_dirs[-1]
            n = corrupt_checkpoint_dir(target)
            self._record(f, step, files=n)
            fired = True
        return fired

    def summary(self):
        """{kind: firings} over the injector's lifetime."""
        out = {}
        for f in self.faults:
            if f.fired:
                out[f.kind] = out.get(f.kind, 0) + f.fired
        return out


def corrupt_checkpoint_dir(step_dir: str, keep_bytes: int = 16) -> int:
    """Truncate every file over 64 bytes under one checkpoint step dir
    (shared by the injector and tests); returns files corrupted."""
    n = 0
    for root, _, files in os.walk(step_dir):
        for name in files:
            path = os.path.join(root, name)
            try:
                if os.path.getsize(path) > 64:
                    with open(path, "r+b") as fh:
                        fh.truncate(keep_bytes)
                    n += 1
            except OSError:
                continue
    return n
