"""Chrome-trace parsing and the paper's T_compute/T_select/T_comm split.

The paper's entire argument is a measured three-term decomposition of the
step time (arXiv:1901.04359 §5): forward/backward compute, top-k
selection, and the sparse collective. This module turns a ``jax.profiler``
chrome trace into that decomposition, one implementation for every
consumer (the calibrator's capture, the gate smoke, the report CLI,
tests).

Two attribution sources, in preference order:

  spans — device-lane events named by the ``Tracer``/``TraceAnnotation``
      scopes the trainer emits ("train/step", ...). On TPU the runtime
      propagates annotations onto the device lanes, so when enough
      device time is covered by annotated scopes the named buckets are
      the ground truth.
  ops — fallback op-level classifier over per-op device events: sort /
      top-k → select; all-reduce / all-gather / all-to-all /
      collective-permute / reduce-scatter → comm; everything else
      (fusions, convolutions, dots, loop bookkeeping) → compute. This is
      the path that works on XLA:CPU traces, where op events carry
      ``args.hlo_op`` on the runtime's executor threads and annotations
      stay host-side.

The spans-vs-ops choice is made PER CLASS, not globally: a partially
annotated capture (say only the comm scopes propagated to the device
lanes) keeps span truth for the classes the annotations cover and the
op classifier for the rest (``source`` = "mixed"); before PR 15 one
thin class silently dragged all three onto the op classifier.

Overlap measurement (PR 15): the three per-class sums assume the terms
are disjoint in time — exactly the assumption the overlapped bucket
pipeline breaks. ``attribute`` therefore also reports ``overlap_frac``:
the wall-clock interval union of comm events intersected with the union
of non-comm (compute+select) events, as a fraction of the comm union —
the fraction of communication time HIDDEN under other work. 0.0 on a
strictly serial schedule; > 0 once the pipelined stage loop actually
interleaves. Computed from raw (ts, dur) wall intervals across all
device lanes (cross-lane concurrency is the point), from the op events
when any exist, else from the annotated device spans.

Durations are SELF times: a structural op (``while``, ``call``) nests its
children on the same lane, so summing raw ``dur`` double-counts; each
lane is resolved with an interval-nesting stack (sort by (ts, -end),
subtract same-lane child durations) before bucketing. Validated against
XLA:CPU traces where ``while`` wraps the gtopk hypercube's
collective-permutes: the loop's self time drops to bookkeeping while the
collectives keep their own.

``capture()`` is the capture-side helper: ``jax.profiler.trace``'s
default options enable the Python tracer, which on a trainer-sized
program floods the trace (~1M events) until the XLA op events are
DROPPED; the context manager here runs a ProfilerSession with
``python_tracer_level=0`` so op-level attribution survives.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
from contextlib import contextmanager
from typing import Dict, Iterable, List, Optional, Tuple

# ------------------------------------------------------------ classifiers

# Op-name prefixes per bucket, matched against the HLO instruction name
# (lowercased, suffix digits and all: prefix match handles "sort.42" and
# "all-reduce-start"). Order matters only in that select/comm are carved
# out of the default-compute bucket. NOTE "reduce-window" is pooling
# (compute), which is why the select patterns are exact-ish prefixes and
# not a substring match on "top".
_SELECT_PREFIXES = ("sort", "top-k", "topk", "top_k", "partial-sort")
_COMM_PREFIXES = (
    "all-reduce", "all-gather", "all-to-all", "alltoall",
    "collective-permute", "reduce-scatter", "collective-broadcast",
    "allreduce", "allgather", "send", "recv", "partition-id",
)

# Span-path components per bucket, for annotation-named device events
# (and for bucketing host-side span means). Checked in this order so
# "train/step/compress" lands in select even though "step" would match
# compute.
_SPAN_BUCKET_PATTERNS = (
    ("select", ("compress", "select", "topk", "top_k")),
    ("comm", ("comm", "allreduce", "all_reduce", "allgather")),
    ("compute", ("forward_backward", "apply", "step", "train", "dispatch",
                 "throughput", "fwd", "bwd")),
)

TERMS = ("compute", "select", "comm")


def classify_op(name: str) -> str:
    """Bucket one HLO op name: 'select' | 'comm' | 'compute'."""
    n = name.lower()
    for p in _SELECT_PREFIXES:
        if n.startswith(p):
            return "select"
    for p in _COMM_PREFIXES:
        if n.startswith(p):
            return "comm"
    # Fusions that carry their root op in the name (TPU fusion naming).
    if "fusion" in n:
        for p in _SELECT_PREFIXES:
            if p in n:
                return "select"
        for p in _COMM_PREFIXES:
            if p in n:
                return "comm"
    return "compute"


def classify_span(path: str) -> Optional[str]:
    """Bucket a Tracer span path ('bench/compress' → 'select'); None when
    no component matches any bucket (an unrecognized host phase like
    'io' must not pollute the three-term split)."""
    segs = path.lower().split("/")
    for bucket, pats in _SPAN_BUCKET_PATTERNS:
        for seg in segs:
            for p in pats:
                if p in seg:
                    return bucket
    return None


# -------------------------------------------------------------- trace IO

def find_trace_file(path: str) -> str:
    """Resolve a capture dir (or a direct file path) to the newest
    ``*.trace.json.gz`` under it — the layout jax.profiler exports
    (<dir>/plugins/profile/<ts>/<host>.trace.json.gz)."""
    if os.path.isfile(path):
        return path
    paths = glob.glob(
        os.path.join(path, "**", "*.trace.json.gz"), recursive=True)
    paths += glob.glob(
        os.path.join(path, "**", "*.trace.json"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no chrome trace found under {path}")
    return max(paths, key=os.path.getmtime)


def load_trace(path: str) -> dict:
    """Load a chrome-trace JSON document (plain or gzipped)."""
    path = find_trace_file(path)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        return json.load(fh)


def lane_index(events: Iterable[dict]) -> Tuple[Dict, Dict]:
    """(pid → process name, (pid, tid) → thread name) from metadata."""
    pnames, tnames = {}, {}
    for e in events:
        if e.get("name") == "process_name":
            pnames[e.get("pid")] = e.get("args", {}).get("name", "")
        elif e.get("name") == "thread_name":
            tnames[(e.get("pid"), e.get("tid"))] = (
                e.get("args", {}).get("name", ""))
    return pnames, tnames


def device_pids(pnames: Dict) -> set:
    """Processes that look like accelerator devices."""
    return {pid for pid, name in pnames.items()
            if any(t in name.lower()
                   for t in ("tpu", "device", "xla", "/device"))}


def _event_us(e: dict) -> float:
    """Duration in µs, preferring the profiler's exact device time."""
    ps = e.get("args", {}).get("device_duration_ps")
    return float(ps) / 1e6 if ps else float(e.get("dur", 0.0))


def _is_op_event(e: dict, dev_pids: set, tnames: Dict) -> bool:
    """Per-op device event: carries args.hlo_op (XLA:CPU executor
    threads) or sits in a device pid's "XLA Ops" lane (TPU)."""
    if e.get("ph") != "X":
        return False
    if "hlo_op" in e.get("args", {}):
        return True
    return (e.get("pid") in dev_pids
            and tnames.get((e.get("pid"), e.get("tid"))) == "XLA Ops")


def self_durations_us(events: List[dict]) -> List[float]:
    """Self time (dur minus same-lane nested children) per event, in the
    input order. Caller groups events by lane; this resolves the nesting
    with the (ts, -end) stack sweep."""
    order = sorted(
        range(len(events)),
        key=lambda i: (float(events[i].get("ts", 0.0)),
                       -(float(events[i].get("ts", 0.0))
                         + float(events[i].get("dur", 0.0)))))
    selfs = [0.0] * len(events)
    stack: List[List] = []  # [end_ts, child_dur_sum, index]
    for i in order:
        e = events[i]
        ts = float(e.get("ts", 0.0))
        dur = float(e.get("dur", 0.0))
        while stack and ts >= stack[-1][0] - 1e-9:
            end, child, j = stack.pop()
            selfs[j] = max(0.0, float(events[j].get("dur", 0.0)) - child)
        if stack:
            stack[-1][1] += dur
        stack.append([ts + dur, 0.0, i])
    while stack:
        end, child, j = stack.pop()
        selfs[j] = max(0.0, float(events[j].get("dur", 0.0)) - child)
    return selfs


# ------------------------------------------------------------ attribution

def _interval_union(intervals: List[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into a sorted disjoint union."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _intersection_us(a: List[Tuple[float, float]],
                     b: List[Tuple[float, float]]) -> float:
    """Total overlap length of two disjoint sorted interval unions."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def overlap_fraction(comm_iv: List[Tuple[float, float]],
                     other_iv: List[Tuple[float, float]]) -> float:
    """Fraction of the comm wall-clock union hidden under non-comm
    work: |union(comm) ∩ union(other)| / |union(comm)|. 0.0 when no
    comm intervals exist."""
    comm_u = _interval_union(comm_iv)
    comm_len = sum(e - s for s, e in comm_u)
    if comm_len <= 0:
        return 0.0
    return _intersection_us(comm_u, _interval_union(other_iv)) / comm_len


def attribute(trace, mode: Optional[str] = None,
              min_span_coverage: float = 0.5,
              stage_intervals: bool = False,
              wire_us: Optional[float] = None) -> dict:
    """The paper's decomposition from a chrome trace.

    ``trace`` is a capture dir, a trace file path, or an already-loaded
    chrome-trace dict. Returns a flat record (no 'kind' key — callers log
    it as kind="attr"): t_{compute,select,comm}_us self-time totals,
    frac_* over their sum, the per-class span/ops choice (``source`` =
    "spans" when every class with data uses annotated device spans
    covering ≥ min_span_coverage of that class's op time, "ops" when
    none does, "mixed" otherwise, with the per-class pick in
    ``source_{term}``), the measured ``overlap_frac`` (see module
    docstring), op counts, and the top ops per bucket (strings; the
    report CLI prints them, aggregation ignores them).

    ``stage_intervals=True`` additionally attaches ``rec["critpath"]``:
    the compact per-step stage-interval record (obs/critpath.py) built
    from the same per-class raw wall intervals the overlap measurement
    uses, with the comm span wait-split against ``wire_us`` (the
    ledger-modeled wire time for this step's bytes; None = no model =
    the whole comm span stays ``comm``). Callers pop it and log it as
    its own durable ``critpath`` record — it never rides the attr row.
    """
    trace_file = None
    if isinstance(trace, str):
        trace_file = find_trace_file(trace)
        doc = load_trace(trace_file)
    else:
        doc = trace
    events = doc.get("traceEvents", [])
    pnames, tnames = lane_index(events)
    dev_pids = device_pids(pnames)

    # Group op events per lane, then bucket their self times.
    lanes: Dict[Tuple, List[dict]] = collections.defaultdict(list)
    for e in events:
        if _is_op_event(e, dev_pids, tnames):
            lanes[(e.get("pid"), e.get("tid"))].append(e)
    op_us = {t: 0.0 for t in TERMS}
    op_top: Dict[str, Dict[str, float]] = {t: collections.defaultdict(float)
                                           for t in TERMS}
    # Raw wall (start, end) intervals per bucket, across ALL lanes —
    # the overlap measurement wants wall-clock concurrency (two lanes
    # busy at once), which self times deliberately erase.
    op_iv: Dict[str, List[Tuple[float, float]]] = {t: [] for t in TERMS}
    n_ops = 0
    for lane_events in lanes.values():
        selfs = self_durations_us(lane_events)
        for e, us in zip(lane_events, selfs):
            # device_duration_ps would be exact, but self-time nesting is
            # computed on the lane's wall durations — stay consistent.
            name = e.get("name", "?")
            bucket = classify_op(name)
            op_us[bucket] += us
            op_top[bucket][name] += us
            ts = float(e.get("ts", 0.0))
            # Self time for the interval length: a structural op
            # (while/call) must not blanket its children's window with
            # its own class. Anchored at ts — the self fragments of a
            # wrapper may sit later in its window, an approximation
            # that only matters for the wrappers' bookkeeping slivers.
            if us > 0:
                op_iv[bucket].append((ts, ts + us))
            n_ops += 1

    # Annotation-named DEVICE events (TPU propagates TraceAnnotations to
    # device lanes; op events themselves are excluded above).
    span_us = {t: 0.0 for t in TERMS}
    span_iv: Dict[str, List[Tuple[float, float]]] = {t: [] for t in TERMS}
    n_spans = 0
    for e in events:
        if (e.get("ph") != "X" or e.get("pid") not in dev_pids
                or _is_op_event(e, dev_pids, tnames)):
            continue
        lane = tnames.get((e.get("pid"), e.get("tid")), "")
        if lane in ("Steps", "XLA Modules", "XLA Ops"):
            continue
        bucket = classify_span(str(e.get("name", "")))
        if bucket is not None:
            span_us[bucket] += _event_us(e)
            ts = float(e.get("ts", 0.0))
            dur = float(e.get("dur", 0.0))
            if dur > 0:
                span_iv[bucket].append((ts, ts + dur))
            n_spans += 1

    # Per-CLASS source selection: use a class's annotated spans when
    # they exist and cover at least min_span_coverage of that class's
    # op time (or the op classifier saw nothing for it); fall back to
    # ops for the others. Classes with no data in EITHER source don't
    # vote on the overall label.
    use_spans_t = {
        t: (span_us[t] > 0
            and (op_us[t] == 0
                 or span_us[t] >= min_span_coverage * op_us[t]))
        for t in TERMS}
    chosen = {t: span_us[t] if use_spans_t[t] else op_us[t] for t in TERMS}
    total = sum(chosen.values())
    votes = [use_spans_t[t] for t in TERMS
             if span_us[t] > 0 or op_us[t] > 0]
    source = ("spans" if votes and all(votes)
              else "ops" if not any(votes) else "mixed")

    # Measured comm overlap: wall-interval union of the comm class vs
    # the union of everything else, from the same per-class source the
    # decomposition chose (ops when any exist — spans can blanket a
    # whole step on partially-annotated captures).
    iv = op_iv if n_ops > 0 else span_iv
    ofrac = overlap_fraction(
        iv["comm"], [x for t in TERMS if t != "comm" for x in iv[t]])

    rec = {}
    if stage_intervals:
        # Lazy import: critpath imports this module at module level for
        # the interval algebra; the reverse edge stays call-time only.
        from gtopkssgd_tpu.obs import critpath
        budget = float("inf") if wire_us is None else float(wire_us)
        fine = critpath.stage_segments(iv, budget, fill_gaps=True)
        # Coarse segments for the chain/timeline (compact durable
        # record); exact per-stage totals from the fine list.
        rec["critpath"] = critpath.build_record(
            critpath.coarsen(fine, min_us=500.0),
            totals=critpath.stage_totals(fine))
    rec = {
        **rec,
        "mode": mode,
        "source": source,
        "n_op_events": n_ops,
        "n_span_events": n_spans,
        "t_total_us": round(total, 1),
        "overlap_frac": round(ofrac, 6),
    }
    if trace_file is not None:
        rec["trace_file"] = trace_file
    for t in TERMS:
        rec[f"t_{t}_us"] = round(chosen[t], 1)
        rec[f"frac_{t}"] = round(chosen[t] / total, 6) if total else 0.0
        rec[f"source_{t}"] = "spans" if use_spans_t[t] else "ops"
    for t in TERMS:
        rows = sorted(op_top[t].items(), key=lambda kv: -kv[1])[:3]
        rec[f"top_{t}_ops"] = ", ".join(
            f"{n[:48]}={us / 1e3:.2f}ms" for n, us in rows)
    return rec


def host_span_means(trace) -> Dict[str, float]:
    """Mean µs per annotation path over HOST lanes — the Tracer's view of
    the same names, for correlating against the device split."""
    doc = load_trace(trace) if isinstance(trace, str) else trace
    events = doc.get("traceEvents", [])
    pnames, tnames = lane_index(events)
    dev_pids = device_pids(pnames)
    acc: Dict[str, List[float]] = collections.defaultdict(list)
    for e in events:
        if e.get("ph") != "X" or e.get("pid") in dev_pids:
            continue
        if _is_op_event(e, dev_pids, tnames):
            continue
        name = str(e.get("name", ""))
        if classify_span(name) is not None or "/" in name:
            acc[name].append(float(e.get("dur", 0.0)))
    return {n: sum(v) / len(v) for n, v in acc.items() if v}


# ---------------------------------------------------------------- capture

@contextmanager
def capture(log_dir: str):
    """Profiler capture tuned for attribution: Python tracer OFF.

    ``jax.profiler.trace``'s defaults include the Python tracer, which on
    a trainer-sized program emits ~1M host events and makes the profiler
    DROP the XLA op events attribution needs (measured on XLA:CPU). The
    TraceAnnotation scopes the Tracer emits survive with the Python
    tracer off — they ride the host tracer. Falls back to the public
    jax.profiler.trace if the session API is unavailable."""
    import jax

    jax.devices()  # the profiler needs an initialized backend
    os.makedirs(log_dir, exist_ok=True)
    try:
        from jax._src.lib import xla_client  # noqa: private, pinned jaxlib

        opts = xla_client.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        sess = xla_client.profiler.ProfilerSession(opts)
    except Exception:
        with jax.profiler.trace(log_dir):
            yield
        return
    try:
        yield
    finally:
        sess.stop_and_export(log_dir)


def format_attr(rec: dict) -> str:
    """Render one attr record as the paper's decomposition table."""
    header = ["term", "time_ms", "frac", "src"]
    rows = []
    for t in TERMS:
        us = float(rec.get(f"t_{t}_us", 0.0))
        rows.append([f"T_{t}", f"{us / 1e3:.3f}",
                     f"{float(rec.get(f'frac_{t}', 0.0)):.4f}",
                     str(rec.get(f"source_{t}", rec.get("source", "?")))])
    widths = [max(len(r[i]) for r in [header] + rows)
              for i in range(len(header))]
    lines = ["  ".join(c.ljust(w) for c, w in zip(r, widths))
             for r in [header, ["-" * w for w in widths]] + rows]
    head = (f"[attr] source={rec.get('source')}"
            + (f"  mode={rec['mode']}" if rec.get("mode") else "")
            + f"  total={float(rec.get('t_total_us', 0.0)) / 1e3:.3f}ms"
            + f"  op_events={rec.get('n_op_events')}"
            + (f"  overlap_frac={float(rec['overlap_frac']):.4f}"
               if rec.get("overlap_frac") is not None else ""))
    tops = [f"  top {t}: {rec[f'top_{t}_ops']}"
            for t in TERMS if rec.get(f"top_{t}_ops")]
    return "\n".join([head] + lines + tops)
