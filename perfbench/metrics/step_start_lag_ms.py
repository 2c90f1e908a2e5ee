"""How long a launched step waits for its inputs: the start of a step's
program on the first chip (device trace) minus the end of that step's
``dispatch`` span, which the span buffer's anchor puts on the trace's
clock (scoped.py); the median over the traced steps, in milliseconds.

The chip runs the programs in the order they were dispatched, so the
programs, in order, take the dispatches by their step ids, each the
earliest not yet taken that opened before the program started. A program
with no such dispatch (launched before the trace began) is left out.

The two clocks agree to about a millisecond, and an idle chip starts a
program half a millisecond after its dispatch opened (the LSTM cell: a run
of PR 24 read the program 0.6 ms *before* its dispatch and paired every
program with the step before, 93 ms late). So a dispatch counts as open
``SLACK_NS`` before its stamp; steps shorter than that would pair wrong."""
import statistics

from perfbench.metrics import scoped

SLACK_NS = 5_000_000


def read(ctx):
    info = scoped.run_info(ctx)
    if info is None or not info["spans"] or not ctx["events"]["modules"]:
        return None
    chip = min(ctx["events"]["modules"])
    dispatches = sorted((s for s in info["spans"] if s[0] == "dispatch"),
                        key=lambda s: (s[3] is None, s[3], s[1]))
    lags, taken = [], 0
    for start, _ in scoped.step_programs(ctx["events"], chip):
        if taken < len(dispatches) \
                and dispatches[taken][1] - SLACK_NS <= start:
            _, opened, dur, _, _ = dispatches[taken]
            lags.append(start - (opened + dur))
            taken += 1
    return statistics.median(lags) / 1e6 if lags else None
