"""Device time of the step's named stages (scoped.py): milliseconds a
step in which an operation under one of ``stages`` ran, mean over the
chips; 0.0 where the step has the scopes but no operation is rooted under
these. ``collectives`` False leaves the collective operations out (the
merge compute between them; ``comm_ms`` keeps those)."""
import re

from perfbench import trace
from perfbench.metrics import scoped


def read(ctx, stages, collectives=True):
    info = scoped.run_info(ctx)
    if info is None:
        return None

    def wanted(name, scope):
        if not scope or scope.split("/round")[0] not in stages:
            return False
        return collectives or not re.match(trace.COLLECTIVE, name)

    seconds = scoped.scoped_seconds(ctx["events"], info["scopes"], wanted)
    return None if seconds is None else 1e3 * seconds / ctx["steps"]
