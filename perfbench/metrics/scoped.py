"""What the stage metrics and the host-side span metrics share: this run's
trace file, read for what ``perfbench.trace.extract`` leaves out.

The program names the stages of its compiled step with ``jax.named_scope``
(``gtopk/fwd_bwd``, ``gtopk/accumulate``, ``gtopk/select``, ``gtopk/mask``,
``gtopk/repair``, ``gtopk/allreduce[/round<i>]``, ``gtopk/apply``,
``gtopk/telemetry``) and gives the step a fixed name, ``gtopk_train_step``.
On the TPU the scope path of an operation is the ``tf_op`` stat of its event
*metadata* (``jit(gtopk_train_step)/gtopk/mask/select_n:``), which
``jax.profiler.ProfileData`` does not show (an event's own stats are its
offset and duration), so the few fields needed are decoded from the
protobuf here: per device plane the event metadata (name, ``tf_op``,
``program_id``), and the ``Task Environment`` plane's
``profile_start_time`` / ``profile_stop_time``, which are ``time.time_ns()``
stamps; every event of the trace counts its nanoseconds from the first
(chip run, PR 24: PERF.md). A fused operation carries the scope of its root
instruction: a fusion that straddles two stages counts for one of them.

The program's spans come from its span buffer
(``gtopkssgd_tpu.obs.tracing.buffered_spans``), each with its step, its
thread and its start on the same epoch clock, so a span needs no inference
to be laid on the trace. A program without the buffer, the scopes or the
step's name (the parent of PR 24) leaves every reader here with nothing to
read: it returns None.

``run_info(ctx)`` is what the readers ask: ``{"scopes": {operation name:
scope}, "start_ns", "stop_ns", "spans": [[path, start_ns on the trace's
clock, dur_ns, step, thread], ...]}``; a scope is the path from the
outermost ``gtopk/`` through the stage (and the round), ``""`` for an
operation of the step outside every scope. ``ctx["scoped"]`` holds it
already where a test reduces a recorded fixture.
"""

import glob
import os
import re

from perfbench import harness, trace

STEP_PROGRAM = "jit_gtopk_train_step"
SCOPE = re.compile(r"(?:^|/)(gtopk/[a-z_]+(?:/round\d+)?)")


# --------------------------------------------------- the protobuf's fields
def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited or fixed-width value."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield tag >> 3, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _stats(message, field, names):
    """{stat name: value} of the XStat entries under ``field``: a number,
    a string, or the name a reference points at."""
    out = {}
    for number, stat in _fields(message):
        if number != field:
            continue
        name = value = None
        for key, raw in _fields(stat):
            if key == 1:
                name = names.get(raw)
            elif key in (3, 4):
                value = raw
            elif key in (5, 6):
                value = _text(raw)
            elif key == 7:
                value = names.get(raw)
        out[name] = value
    return out


def read_xplane(path):
    """{"start_ns", "stop_ns", "scopes": {program id: {operation: scope}}}
    from a trace file; the stamps are None without a Task Environment."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {"start_ns": None, "stop_ns": None, "scopes": {}}
    for number, plane in _fields(space):
        if number != 1:
            continue
        parts = list(_fields(plane))
        name = next((_text(v) for k, v in parts if k == 2), "")
        names = {}
        for key, entry in parts:
            if key == 5:                      # stat_metadata: id -> name
                pair = dict(_fields(entry))
                names[pair[1]] = _text(dict(_fields(pair[2])).get(2, b""))
        if name == "Task Environment":
            stats = _stats(plane, 6, names)
            start, stop = (stats.get("profile_start_time"),
                           stats.get("profile_stop_time"))
            if start is not None and stop is not None:
                out.update(start_ns=int(start), stop_ns=int(stop))
        elif re.match(r"^/device:TPU:\d+$", name):
            for key, entry in parts:
                if key != 4:                  # event_metadata: id -> event
                    continue
                event = dict(_fields(entry))[2]
                stats = _stats(event, 5, names)
                path_ = stats.get("tf_op")
                if path_ is None:
                    continue
                text = next(
                    (_text(v) for k, v in _fields(event) if k == 2), "")
                out["scopes"].setdefault(str(stats.get("program_id")), {})[
                    trace.op_name(text)] = scope_of(path_)
    return out


def scope_of(path):
    """``gtopk/allreduce/round0`` from ``jit(gtopk_train_step)/gtopk/
    allreduce/round0/gtopk/select/...``: the outermost scope counts."""
    match = SCOPE.search(path)
    return match.group(1) if match else ""


# ------------------------------------------------------------ this run
def _find_xplane(events):
    """The trace file the harness reduced to ``events``: the newest under
    ``chiprun_out/perfbench`` whose device operations are these."""
    files = glob.glob(os.path.join(
        harness.ROOT, "chiprun_out", "perfbench", "*.trace", "plugins",
        "profile", "*", "*.xplane.pb"))
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        if trace.extract(path)["devices"] == events["devices"]:
            return path
    return None


def _buffered_spans(start_ns, stop_ns):
    """The program's closed spans that opened while the trace ran, on the
    trace's clock; None where the program keeps no span buffer."""
    try:
        from gtopkssgd_tpu.obs import tracing
        records = tracing.buffered_spans()
    except (ImportError, AttributeError):
        return None
    spans = []
    for r in records:
        start = tracing.epoch_ns(r)
        if start_ns <= start <= stop_ns:
            spans.append([r.path, start - start_ns, int(r.dur * 1e9), r.step,
                          r.thread])
    return sorted(spans, key=lambda s: s[1])


_last = (None, None)      # (the events last asked about, their run_info)


def run_info(ctx):
    global _last
    if "scoped" in ctx:
        return ctx["scoped"]
    events = ctx["events"]
    if _last[0] is events:
        return _last[1]
    info, path = None, _find_xplane(events)
    if path is not None:
        found = read_xplane(path)
        if found["start_ns"] is not None:
            programs = {m[0] for ms in events["modules"].values() for m in ms
                        if m[0].startswith(STEP_PROGRAM)}
            scopes = {}
            for program in programs:
                scopes.update(found["scopes"].get(
                    program[len(STEP_PROGRAM):].strip("()"), {}))
            info = {"scopes": scopes, "start_ns": found["start_ns"],
                    "stop_ns": found["stop_ns"],
                    "spans": _buffered_spans(found["start_ns"],
                                             found["stop_ns"])}
    _last = (events, info)
    return info


# ------------------------------------------------------------ reductions
def step_programs(events, chip):
    """[[start, end]] of the step's programs on a chip, in order."""
    return [[s, s + d] for name, s, d in events["modules"].get(chip, [])
            if name.startswith(STEP_PROGRAM)]


def step_operations(events, chip):
    """The device operations of a chip that ran inside a step program."""
    programs = step_programs(events, chip)
    ops, j = [], 0
    for op in events["devices"][chip]:
        while j < len(programs) and programs[j][1] < op[1]:
            j += 1
        if j < len(programs) and programs[j][0] <= op[1] \
                and op[1] + op[2] <= programs[j][1]:
            ops.append(op)
    return ops


def scoped_seconds(events, scopes, wanted):
    """Seconds, averaged over the chips, in which an operation of a step
    program ran that ``wanted(name, scope)`` accepts (an operation the map
    does not know has the scope None). None where the map names no stage
    at all or no step program ran: a program without the scopes. Zero is
    a reading: the stage's work rides in other stages' fusions."""
    if not any(scopes.values()):
        return None
    busy, found = [], False
    for chip in events["devices"]:
        ops = step_operations(events, chip)
        found = found or bool(ops)
        busy.append(trace.total(trace.union(
            [[s, s + d] for name, s, d in ops
             if wanted(name, scopes.get(name))])))
    return sum(busy) / len(busy) / 1e9 if found else None
