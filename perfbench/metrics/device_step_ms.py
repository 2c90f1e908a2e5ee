from perfbench import trace


def read(ctx):
    return 1e3 * trace.busy_seconds(ctx["events"]) / ctx["steps"]
