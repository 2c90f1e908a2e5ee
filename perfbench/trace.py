"""From a profiler trace to numbers: the benchmark's own reduction.

``extract`` reads an ``.xplane.pb`` into plain lists (device operations and
whole programs per chip, the program's host spans where the trace holds
them), ``place_spans`` puts spans taken on the host's clock onto the trace's,
``save``/``load`` keep the lists as a small gzipped JSON (the recorded
fixture the tests reduce), and the functions below turn them into the
quantities the per-layer readers and the ``breakdown`` report. All times are
nanoseconds on the profiler's clock.

On the TPU the trace is taken with the host tracer off: at level 1 as at
jax's default the runtime logs one ``Transpose`` event a tile while it lays a
batch out for the chip, 0.9 M a thread for each 512-image batch: a step
that takes in such a batch runs 1.65 s under trace for 0.086 s (level 1, ten
steps weigh 618 MB), a ResNet-50 step 1.8 s for 0.28 s (default level); with
it off a traced step costs what an untraced one does and two steps weigh
6.5 MB (chip runs, PR 23, in PERF.md). The program's spans then come
from its own tracer's sink, on the host's clock.
"""

import gzip
import json
import re

COLLECTIVE = r"^(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute|collective-broadcast)"
# Lines of a device plane that hold one event per executed operation.
OP_LINES = ("XLA Ops",)
# ... one event per executed program (a jitted step is one) ...
MODULE_LINES = ("XLA Modules",)
# ... and one per asynchronous operation, from its start to its done: copies
# and the collectives the compiler made asynchronous. They run beside the
# operations of OP_LINES and count towards no busy time, only towards the
# collectives' time.
ASYNC_LINES = ("Async XLA Ops",)
GROUPS = ("devices", "modules", "async")
SPAN_NAMES = ("io", "dispatch", "obs_read", "final_sync")
# The spans in which the host waits for the step it launched last.
BLOCKING = ("obs_read", "final_sync")


def extract(xplane_path):
    """{"devices": {chip: [[name, start_ns, dur_ns], ...]}, "modules" and
    "async": the same for whole programs and for asynchronous operations,
    "spans": [[name, start_ns, dur_ns], ...]}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices, modules, asyncs, spans, host_ops = {}, {}, {}, [], []
    for plane in data.planes:
        match = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if match:
            chip = int(match.group(1))
            for line in plane.lines:
                for lines, group in ((OP_LINES, devices), (ASYNC_LINES, asyncs),
                                     (MODULE_LINES, modules)):
                    if line.name in lines:
                        group.setdefault(chip, []).extend(
                            [op_name(e.name), e.start_ns, e.duration_ns]
                            for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPAN_NAMES:
                        spans.append([e.name, e.start_ns, e.duration_ns])
                    elif e.duration_ns > 0 and any(
                            k == "hlo_op" for k, _ in e.stats):
                        host_ops.append([e.name, e.start_ns, e.duration_ns])
    if not devices and host_ops:
        # The CPU backend runs its operations on host threads: a rehearsal
        # reads them as chip 0's, never a measurement.
        devices[0] = host_ops
    events = {"devices": devices, "modules": modules, "async": asyncs}
    for group in events.values():
        for ops in group.values():
            ops.sort(key=lambda e: e[1])
    spans.sort(key=lambda e: e[1])
    return dict(events, spans=spans)


def place_spans(events, host_spans):
    """Put spans taken on the host's clock, [[name, start_s, dur_s], ...],
    onto the trace's. The offset between the two clocks lies between two
    bounds. Every ``dispatch`` span launches one step program, the k-th the
    k-th long program on the first chip, and a program starts no earlier
    than its dispatch: offset <= min(program start - dispatch start). A
    read that blocks on a step's results (``BLOCKING``) returns no earlier
    than that step's program ends: offset >= max(program end - read end).
    The second bound is short by the read itself, 2 ms a scalar on the v5e;
    the first by whatever the program waits for after its launch, and a
    512-image uint8 batch reaches the chip 50 ms after ``jnp.asarray``
    returned (chip runs, PR 23, second session: PERF.md). So the second is
    taken where the traced steps hold a blocking read, else the first."""
    chip = min(events["modules"])
    longest = max(m[2] for m in events["modules"][chip])
    steps = [m for m in events["modules"][chip] if m[2] > 0.1 * longest]
    launches = [s * 1e9 for n, s, _ in host_spans if n == "dispatch"]
    if not steps or len(steps) != len(launches):
        raise ValueError(f"{len(launches)} dispatch spans for {len(steps)} "
                         "step programs in the trace")
    offset = min(m[1] - d for m, d in zip(steps, launches))
    after_reads = []
    for name, start, dur in host_spans:
        launched = sum(d <= start * 1e9 for d in launches)
        if name in BLOCKING and launched:
            program = steps[launched - 1]
            after_reads.append(program[1] + program[2] - (start + dur) * 1e9)
    if after_reads:
        # Never later than the dispatches allow: a read matched with a
        # program it did not wait for says nothing.
        offset = min(offset, max(after_reads))
    events["spans"] = sorted(
        ([n, s * 1e9 + offset, d * 1e9] for n, s, d in host_spans),
        key=lambda e: e[1])
    return events


def op_name(text):
    """``fusion.12`` from the event's full text ``%fusion.12 = bf16[...] ...``."""
    return text.split(" = ", 1)[0].lstrip("%")


def clip(events, t0, t1):
    """The part of an extracted trace inside [t0, t1]."""
    inside = lambda e: e[1] >= t0 and e[1] + e[2] <= t1
    out = {key: {d: [e for e in ops if inside(e)]
                 for d, ops in events[key].items()} for key in GROUPS}
    return dict(out, spans=[e for e in events["spans"] if inside(e)])


def save(events, path):
    lists = [events["spans"]] + [ops for key in GROUPS
                                 for ops in events[key].values()]
    names = sorted({e[0] for evs in lists for e in evs})
    index = {n: i for i, n in enumerate(names)}
    pack = lambda evs: [[index[n], int(s), int(d)] for n, s, d in evs]
    raw = {key: {str(d): pack(ops) for d, ops in events[key].items()}
           for key in GROUPS}
    with gzip.open(path, "wt") as fh:
        json.dump(dict(raw, names=names, spans=pack(events["spans"])), fh)


def load(path):
    with gzip.open(path, "rt") as fh:
        raw = json.load(fh)
    names = raw["names"]
    unpack = lambda evs: [[names[i], s, d] for i, s, d in evs]
    out = {key: {int(d): unpack(ops) for d, ops in raw[key].items()}
           for key in GROUPS}
    return dict(out, spans=unpack(raw["spans"]))


# ------------------------------------------------------------- intervals
def union(intervals):
    """Sorted, disjoint [start, end] pairs covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def subtract(intervals, cover):
    """The part of ``intervals`` (disjoint, sorted) outside ``cover`` (same)."""
    out, j = [], 0
    for s, e in intervals:
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        i = j
        while s < e and i < len(cover) and cover[i][0] < e:
            if cover[i][0] > s:
                out.append([s, cover[i][0]])
            s = max(s, cover[i][1])
            i += 1
        if s < e:
            out.append([s, e])
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def _pairs(ops):
    return [[s, s + d] for _, s, d in ops]


def window(events):
    """[t0, t1]: from the first device operation to the last one's end."""
    starts = [ops[0][1] for ops in events["devices"].values() if ops]
    ends = [max(s + d for _, s, d in ops)
            for ops in events["devices"].values() if ops]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def busy_seconds(events):
    """Seconds in which an operation ran, averaged over the chips."""
    busy = [total(union(_pairs(ops))) for ops in events["devices"].values()]
    return sum(busy) / len(busy) / 1e9


def collective_seconds(events):
    """(all, exposed): seconds of collective operations averaged over the
    chips, and the part of them during which no other operation ran there."""
    everything, exposed = [], []
    for chip, ops in events["devices"].items():
        comm = union(_pairs([e for e in ops + events["async"].get(chip, [])
                             if re.match(COLLECTIVE, e[0])]))
        other = union(_pairs([e for e in ops if not re.match(COLLECTIVE, e[0])]))
        everything.append(total(comm))
        exposed.append(total(subtract(comm, other)))
    n = len(everything)
    return sum(everything) / n / 1e9, sum(exposed) / n / 1e9


def span_seconds(events, name):
    """Durations (s) of the host spans of that name."""
    return [d / 1e9 for n, _, d in events["spans"] if n == name]


def top_operations(events, limit=10):
    """[[name, seconds]]: device operations by summed time, per chip."""
    sums = {}
    for ops in events["devices"].values():
        for name, _, dur in ops:
            sums[name] = sums.get(name, 0) + dur
    n = len(events["devices"])
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, dur / n / 1e9] for name, dur in ranked]


def idle_gaps(events, limit=10):
    """[[what the host was doing, seconds]]: the longest stretches in which
    chip 0 ran nothing, each named by the program span that covers most of
    it (``host`` where none does)."""
    chip = min(events["devices"])
    t0, t1 = window(events)
    gaps = subtract([[t0, t1]], union(_pairs(events["devices"][chip])))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:limit]:
        best, share = "host", 0
        for name, ss, dd in events["spans"]:
            overlap = min(e, ss + dd) - max(s, ss)
            if overlap > share:
                best, share = name, overlap
        out.append([best, (e - s) / 1e9])
    return out
