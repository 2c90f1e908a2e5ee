from perfbench import trace


def read(ctx):
    t0, t1 = trace.window(ctx["events"])
    return 100.0 * (1.0 - trace.busy_seconds(ctx["events"]) / ((t1 - t0) / 1e9))
