from perfbench import trace


def read(ctx, exposed):
    if ctx["chips"] < 2:
        return None
    everything, uncovered = trace.collective_seconds(ctx["events"])
    return 1e3 * (uncovered if exposed else everything) / ctx["steps"]
