"""Device time of a decoder's layer kinds: milliseconds a step in which an
operation of the step programs ran whose **innermost** ``layer/<kind>``
scope is one of ``kinds``, forward and backward alike, mean over the chips.

The program names the parts of its decoder layers with ``jax.named_scope``
inside ``gtopk/fwd_bwd`` (``layer/gdn_proj``, ``layer/gdn_scan``,
``layer/attn``, ``layer/moe_router``, ``layer/moe_experts``,
``layer/shared_expert``, ``layer/head``). ``scoped.scope_of`` keeps the
*outermost* ``gtopk/`` scope of an operation's ``tf_op`` path, so all of
these read there as one stage; here the same paths are decoded (scoped.py's
protobuf helpers, by import) and the last ``layer/<kind>`` counts: the
expert products sit inside the router's dispatch, and an operation of the
backward pass carries the scope of the forward operation it transposes.
An operation under no ``layer/`` scope has the kind ``""``; a program
without the scopes (or a trace without ``tf_op``) leaves the reader with
nothing to read: None. ``ctx["layer_kinds"]`` holds the map already where
a test reduces a recorded path.
"""

import re

from perfbench import trace
from perfbench.metrics import scoped

KIND = re.compile(r"(?:^|/)layer/([a-z_]+)")


def kind_of(path):
    """``moe_experts`` from ``jit(gtopk_train_step)/gtopk/fwd_bwd/.../
    layer/moe_router/.../layer/moe_experts/ragged_dot``: the innermost."""
    found = KIND.findall(path)
    return found[-1] if found else ""


def read_kinds(path):
    """{program id: {operation name: kind}} from a trace file's device
    planes (event metadata: name, ``tf_op``, ``program_id``)."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for number, plane in scoped._fields(space):
        if number != 1:
            continue
        parts = list(scoped._fields(plane))
        name = next((scoped._text(v) for k, v in parts if k == 2), "")
        if not re.match(r"^/device:TPU:\d+$", name):
            continue
        names = {}
        for key, entry in parts:
            if key == 5:                      # stat_metadata: id -> name
                pair = dict(scoped._fields(entry))
                names[pair[1]] = scoped._text(
                    dict(scoped._fields(pair[2])).get(2, b""))
        for key, entry in parts:
            if key != 4:                      # event_metadata: id -> event
                continue
            event = dict(scoped._fields(entry))[2]
            stats = scoped._stats(event, 5, names)
            if stats.get("tf_op") is None:
                continue
            text = next((scoped._text(v) for k, v in scoped._fields(event)
                         if k == 2), "")
            out.setdefault(str(stats.get("program_id")), {})[
                trace.op_name(text)] = kind_of(stats["tf_op"])
    return out


_last = (None, None)      # (the events last asked about, their kinds)


def layer_kinds(ctx):
    """{operation name: kind} of this run's step programs, or None."""
    global _last
    if "layer_kinds" in ctx:
        return ctx["layer_kinds"]
    events = ctx["events"]
    if _last[0] is events:
        return _last[1]
    kinds, path = None, scoped._find_xplane(events)
    if path is not None:
        found = read_kinds(path)
        kinds = {}
        for ms in events["modules"].values():
            for module in ms:
                if module[0].startswith(scoped.STEP_PROGRAM):
                    kinds.update(found.get(
                        module[0][len(scoped.STEP_PROGRAM):].strip("()"), {}))
    _last = (events, kinds)
    return kinds


def read(ctx, kinds):
    found = layer_kinds(ctx)
    if not found:
        return None
    seconds = scoped.scoped_seconds(
        ctx["events"], found, lambda _, kind: kind in kinds)
    return None if seconds is None else 1e3 * seconds / ctx["steps"]
