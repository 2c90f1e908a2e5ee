"""Tracing spans: the program's one span system.

A ``Tracer.span("io", step=7)`` records what a span is: its (nested)
path, its start on ``time.perf_counter()``, its duration, the optimizer
step it belongs to and the thread that ran it. A closed span goes

  * into ``SPAN_BUFFER``, one bounded in-memory buffer shared by every
    tracer and every thread, which outlives the tracer (a reader asks
    ``buffered_spans()`` after the trainer is gone), and
  * where it ran on the thread that built the tracer: into a TimingStats
    accumulator (``flush()`` ships the window's means as one ``spans``
    record) and to the optional ``sink(path, t0, dur)`` hook (the
    timeline recorder's, the benchmark's).

A worker thread's span (the prefetcher's ``prefetch/assemble``) reaches
the buffer only, so the stats and the sink go on describing what the
training loop was doing.

Spans and a profiler trace meet on the wall clock, not by name. Each
tracer takes one clock anchor when it is built (``clock_anchor_ns``: the
offset from ``perf_counter`` to ``time.time_ns()``); ``epoch_ns(record)``
is a span's start on the epoch clock, and a trace's ``Task Environment``
plane gives ``profile_start_time`` in the same unit, its events counting
from there. The span also opens a ``jax.profiler.TraceAnnotation`` of the
same path, which the profiler's host tracer records where it is on; on
the TPU runtime it has to stay off (it logs an event per tile of every
batch it lays out for the chip, PERF.md), so there the buffer and the
anchor are the only way to lay the spans on a trace.

Spans nest: ``span("io")`` containing ``span("wait")`` is recorded under
``"io/wait"`` and inherits the outer span's step. Nesting is tracked
per thread, so the prefetch worker's spans cannot interleave into the
consumer thread's path.
"""

from __future__ import annotations

import collections
import json
import os
import statistics
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, NamedTuple, Optional

import jax

from gtopkssgd_tpu.utils.timers import TimingStats


class SpanRecord(NamedTuple):
    path: str              # nested path, "io/wait"
    t0: float              # time.perf_counter() at open, seconds
    dur: float             # seconds
    step: Optional[int]    # optimizer step (or batch number) it belongs to
    thread: str            # name of the thread that ran it
    anchor_ns: int         # its tracer's clock anchor (clock_anchor_ns)


# Closed spans of every tracer and thread, oldest dropped first: at six
# spans a step, the last five thousand steps. deque.append is atomic.
SPAN_BUFFER: "collections.deque[SpanRecord]" = collections.deque(maxlen=32768)


def buffered_spans() -> List[SpanRecord]:
    """The closed spans still in the buffer, oldest first."""
    return list(SPAN_BUFFER)


def clock_anchor_ns(reads: int = 5) -> int:
    """``time.time_ns()`` minus ``time.perf_counter()`` in nanoseconds:
    the median of a few paired reads, each pair a microsecond apart."""
    return int(statistics.median(
        time.time_ns() - time.perf_counter() * 1e9 for _ in range(reads)))


def epoch_ns(record: SpanRecord) -> int:
    """The span's start on the ``time.time_ns()`` clock, which is the one a
    profiler trace's ``profile_start_time`` is on."""
    return int(record.t0 * 1e9) + record.anchor_ns


@contextmanager
def profile(trace_dir: str):
    """A ``jax.profiler`` trace of the enclosed steps, taken as the
    benchmark takes its own: Python tracer off, host tracer off on the TPU
    platform (on, it makes a step six times slower and the trace twenty
    times larger: PERF.md) and on elsewhere (the CPU backend's operations
    run on host threads). The spans that opened meanwhile are written
    beside the trace as ``spans.json``, each with its start on the epoch
    clock, so they can be laid on the trace without the benchmark: the
    trace's ``Task Environment`` plane holds ``profile_start_time`` on the
    same clock, and its events count from there."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0 if jax.default_backend() == "tpu" else 2
    opened = time.perf_counter()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        rank = jax.process_index()
        name = "spans.json" if rank == 0 else f"spans.r{rank}.json"
        with open(os.path.join(trace_dir, name), "w") as fh:
            json.dump({
                "clock": "epoch_ns = time.time_ns() at the span's start; "
                         "anchor_ns = time.time_ns() - perf_counter() * 1e9",
                "anchor_ns": clock_anchor_ns(),
                "spans": [dict(r._asdict(), epoch_ns=epoch_ns(r))
                          for r in buffered_spans() if r.t0 >= opened],
            }, fh)


class Tracer:
    def __init__(
        self,
        stats: Optional[TimingStats] = None,
        metrics=None,
        enabled: bool = True,
        sink=None,
    ):
        """``metrics`` is a utils.metrics.MetricsLogger (or anything with
        ``.log(kind, **fields)``); ``flush()`` ships the accumulated
        means to it. ``sink`` is an optional callable
        ``(path, t0_perf_counter, dur_seconds)`` invoked when a span of
        the thread that built this tracer closes — the timeline
        recorder's hook (obs.timeline.TimelineRecorder.span_sink matches
        it)."""
        self.stats = stats or TimingStats()
        self.metrics = metrics
        self.enabled = enabled
        self.sink = sink
        self.anchor_ns = clock_anchor_ns()
        self._owner = threading.get_ident()
        self._local = threading.local()

    def _stack(self):
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @property
    def current_path(self) -> str:
        return "/".join(name for name, _ in self._stack())

    @contextmanager
    def span(self, name: str, *, sync: bool = False, value=None,
             step: Optional[int] = None):
        """Time a scope under ``name`` (nested under any open spans).

        ``step`` is the optimizer step the span belongs to; a nested span
        without one takes its parent's. ``sync=True`` blocks on JAX's
        async queue before stopping the clock (``value`` fences just that
        output) — same semantics as the StepTimer this API replaces;
        leave False for host-only phases like data loading, and for
        dispatch phases where the async queue must NOT be drained (the
        whole point of overlap)."""
        if not self.enabled:
            yield
            return
        stack = self._stack()
        if step is None and stack:
            step = stack[-1][1]
        stack.append((name, step))
        path = "/".join(n for n, _ in stack)
        ann = jax.profiler.TraceAnnotation(path)
        t0 = time.perf_counter()
        ann.__enter__()
        try:
            yield
        finally:
            try:
                if sync:
                    if value is not None:
                        jax.block_until_ready(value)
                    else:
                        jax.effects_barrier()
            finally:
                ann.__exit__(None, None, None)
                dur = time.perf_counter() - t0
                stack.pop()
                thread = threading.current_thread()
                SPAN_BUFFER.append(SpanRecord(
                    path, t0, dur, step, thread.name, self.anchor_ns))
                if thread.ident == self._owner:
                    self.stats.add(path, dur)
                    if self.sink is not None:
                        self.sink(path, t0, dur)

    def flush(self, step: Optional[int] = None) -> Dict[str, float]:
        """Ship accumulated per-path mean seconds as ONE 'spans' record
        and reset, so each logging window reports its own means (the
        reference logged its timer dicts every N iterations the same
        way). Returns the summary that was logged."""
        summary = self.stats.summary()
        if summary and self.metrics is not None:
            rec = {} if step is None else {"step": step}
            rec.update({path: round(sec, 6) for path, sec in summary.items()})
            self.metrics.log("spans", **rec)
        self.stats.reset()
        return summary
