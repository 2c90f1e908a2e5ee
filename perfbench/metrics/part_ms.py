"""Device time of a decoder's layer kinds by part and by pass: milliseconds
a step in which an operation of the step programs ran whose **innermost**
``layer/<kind>`` scope is one of ``kinds`` (any, if None), whose
**innermost** ``part/<name>`` scope is one of ``parts`` (any or none, if
None) and whose pass is one of ``passes`` (any, if None), mean over the
chips.

A third level of ``jax.named_scope`` under the same reading as
``scoped.py`` (outermost ``gtopk/<stage>``) and ``layer_ms.py`` (innermost
``layer/<kind>``): inside the attention kinds (``layer/attn``,
``layer/attn_window``, ``layer/attn_full``) the program names what an
operation is part of: ``part/proj`` (the q / k / v / gate / o products),
``part/pointwise`` (norms, rotary, the gate's multiply, the residual add),
``part/layout`` (reshapes, transposes and casts to and from the kernels'
layout, the blocks' cutting and joining) and ``part/kernel`` (the Pallas
calls; off the TPU the blocked and masked forms' products and softmax). The
names start with neither ``layer/`` nor ``gtopk/``: ``layer_ms.kind_of`` and
``scoped.scope_of`` read a path that holds one as they read it without.

The **pass** is read from the same path, by the transformations jax
writes into it: ``replay`` where it holds ``rematted_computation`` (a
remat's second forward, inside the backward pass: such a path holds
``transpose(`` too, so this is asked first), else ``backward`` where it
holds ``transpose(`` (the transposed operations, and what a hand-written
backward rule of a ``custom_vjp`` runs), else ``forward``
(``jit(gtopk_train_step)/gtopk/fwd_bwd/.../jvp(Model)/layer_1/...``). The
replays of checkpoints nested in a layer (Qwen's ``prepare``, a query
block of the blocked attention) count as replay as well.

A fused operation counts for its root's path. A program without the
``part/`` scopes (the parent of PR 37) leaves a reader that asks for parts
with nothing to read: None; one that asks for kinds and passes alone reads
it like any other. ``ctx["parts"]`` holds the map {operation name: (kind,
part, pass)} already where a test reduces recorded paths.
"""

import re

from perfbench import trace
from perfbench.metrics import layer_ms, scoped

PART = re.compile(r"(?:^|/)part/([a-z_]+)")
PASSES = ("forward", "replay", "backward")


def part_of(path):
    """``kernel`` from ``.../layer/attn_full/mixer/part/kernel/
    flash_attention_forward/pallas_call``: the innermost, "" for none."""
    found = PART.findall(path)
    return found[-1] if found else ""


def pass_of(path):
    if "rematted_computation" in path:
        return "replay"
    return "backward" if "transpose(" in path else "forward"


def read_paths(path):
    """{program id: {operation name: tf_op path}} from a trace file's
    device planes (event metadata: name, ``tf_op``, ``program_id``)."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for number, plane in scoped._fields(space):
        if number != 1:
            continue
        fields = list(scoped._fields(plane))
        name = next((scoped._text(v) for k, v in fields if k == 2), "")
        if not re.match(r"^/device:TPU:\d+$", name):
            continue
        names = {}
        for key, entry in fields:
            if key == 5:                      # stat_metadata: id -> name
                pair = dict(scoped._fields(entry))
                names[pair[1]] = scoped._text(
                    dict(scoped._fields(pair[2])).get(2, b""))
        for key, entry in fields:
            if key != 4:                      # event_metadata: id -> event
                continue
            event = dict(scoped._fields(entry))[2]
            stats = scoped._stats(event, 5, names)
            if stats.get("tf_op") is None:
                continue
            text = next((scoped._text(v) for k, v in scoped._fields(event)
                         if k == 2), "")
            out.setdefault(str(stats.get("program_id")), {})[
                trace.op_name(text)] = stats["tf_op"]
    return out


_last = (None, None)      # (the events last asked about, their map)


def part_map(ctx):
    """{operation name: (kind, part, pass)} of this run's step programs,
    or None where no trace file matches the events."""
    global _last
    if "parts" in ctx:
        return ctx["parts"]
    events = ctx["events"]
    if _last[0] is events:
        return _last[1]
    found, path = None, scoped._find_xplane(events)
    if path is not None:
        paths = read_paths(path)
        programs = {m[0][len(scoped.STEP_PROGRAM):].strip("()")
                    for modules in events["modules"].values() for m in modules
                    if m[0].startswith(scoped.STEP_PROGRAM)}
        found = {op: (layer_ms.kind_of(tf_op), part_of(tf_op), pass_of(tf_op))
                 for program in programs
                 for op, tf_op in paths.get(program, {}).items()}
    _last = (events, found)
    return found


def seconds(ctx, found, kinds=None, parts=None, passes=None):
    """``scoped.scoped_seconds`` of the operations the three filters
    accept; None where no step program ran."""
    def wanted(_, entry):
        if entry is None:
            return False
        kind, part, pass_ = entry
        return ((kinds is None or kind in kinds)
                and (parts is None or part in parts)
                and (passes is None or pass_ in passes))

    return scoped.scoped_seconds(ctx["events"], found, wanted)


def read(ctx, kinds=None, parts=None, passes=None):
    found = part_map(ctx)
    if not found or not any(kind for kind, _, _ in found.values()):
        return None
    if parts is not None and not any(part for _, part, _ in found.values()):
        return None
    time = seconds(ctx, found, kinds, parts, passes)
    return None if time is None else 1e3 * time / ctx["steps"]
