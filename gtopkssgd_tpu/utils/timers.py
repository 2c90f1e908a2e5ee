"""Per-step timing decomposition (reference L0: the wall-clock timer dicts
in utils.py / the profiling switch in settings.py).

The reference accumulated forward/backward/compression/communication times
into dicts and logged them every N iterations — that decomposition is the
paper's own analysis axis. Here the same split, plus `jax.block_until_ready`
fencing so the async dispatch queue doesn't fold every phase into the last.

For phases fused inside one jitted step (the production path — XLA overlaps
comm and compute, so a host-side timer *cannot* see them separately), read
the stages off a device trace by their named scopes (PERF.md section 3);
this timer then reports whole-step time under 'step'.

Instrumentation call sites (the trainer's phase timing) live in
``gtopkssgd_tpu.obs.tracing.Tracer``, which builds on TimingStats and adds
nested span paths plus ``jax.profiler.TraceAnnotation`` scopes; StepTimer
stays as the minimal primitive for harness-internal timing.
"""

from __future__ import annotations

import collections
import time
from contextlib import contextmanager
from typing import Dict

import jax

PHASES = ("io", "forward", "backward", "compress", "comm", "update", "step")


def timed_window(run_chunk, min_seconds: float = 0.5,
                 initial_steps: int = 8):
    """The one timing loop (keep it in ONE place).

    ``run_chunk(steps)`` must dispatch `steps` calls back-to-back and then
    ``jax.block_until_ready`` the FULL output of the last one (a fence on
    `loss` alone stops the clock before the parameter update ran). The
    window grows geometrically until it exceeds ``min_seconds``, so
    dispatch noise and the one fence are small against it. Returns
    (seconds_per_step, steps_timed).
    """
    steps = initial_steps
    while True:
        t0 = time.perf_counter()
        run_chunk(steps)
        elapsed = time.perf_counter() - t0
        if elapsed >= min_seconds:
            return elapsed / steps, steps
        steps = int(steps * min(
            10.0, max(2.0, 1.25 * min_seconds / max(elapsed, 1e-4)))) + 1


def time_calls(fn, args, min_seconds: float = 0.5, initial_steps: int = 8):
    """(seconds_per_call, calls_timed) for ``fn(*args)``: one warm call,
    then timed_window over back-to-back dispatches fenced on the last
    output. The device runs enqueued launches in order, so that fence
    waits for all of them."""
    jax.block_until_ready(fn(*args))

    def chunk(c):
        for _ in range(c):
            out = fn(*args)
        jax.block_until_ready(out)

    return timed_window(chunk, min_seconds, initial_steps)


class TimingStats:
    """Accumulates per-phase seconds; reference utils.py's timer-dict shape."""

    def __init__(self):
        self.totals: Dict[str, float] = collections.defaultdict(float)
        self.counts: Dict[str, int] = collections.defaultdict(int)

    def add(self, phase: str, seconds: float) -> None:
        self.totals[phase] += seconds
        self.counts[phase] += 1

    def mean(self, phase: str) -> float:
        c = self.counts[phase]
        return self.totals[phase] / c if c else 0.0

    def summary(self) -> Dict[str, float]:
        return {p: self.mean(p) for p in self.totals}

    def reset(self) -> None:
        self.totals.clear()
        self.counts.clear()


class StepTimer:
    """Context-manager timer: ``with timer('forward'): ...``.

    ``sync=True`` (default) blocks on JAX's async queue before reading the
    clock, so the phase really finished; pass sync=False for host-only
    phases like data loading.
    """

    def __init__(self, stats: TimingStats | None = None):
        self.stats = stats or TimingStats()

    @contextmanager
    def __call__(self, phase: str, *, sync: bool = True, value=None):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if sync:
                if value is not None:
                    jax.block_until_ready(value)
                else:
                    jax.effects_barrier()
            self.stats.add(phase, time.perf_counter() - t0)
