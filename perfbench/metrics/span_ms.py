"""Milliseconds of a program span from its span buffer (scoped.py), over
the spans that opened while the trace ran: their sum for each traced
optimizer step (``per`` "step"), or their mean (``per`` "span")."""
from perfbench.metrics import scoped


def read(ctx, path, per):
    info = scoped.run_info(ctx)
    if info is None or not info["spans"]:
        return None
    durations = [dur for name, _, dur, _, _ in info["spans"] if name == path]
    if not durations:
        return None
    count = ctx["steps"] if per == "step" else len(durations)
    return sum(durations) / 1e6 / count
