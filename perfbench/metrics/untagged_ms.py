"""Device time of the step that no scope can reach: milliseconds a step in
which the only operations of the step programs that ran were ones whose
event metadata carries no ``tf_op`` (scoped.py's scope None: the
compiler's ``copy-start`` / ``copy-done`` / ``slice`` operations, the
pieces it cuts a ``concatenate`` into, its own custom calls), mean over the
chips. It is the step programs' busy time less the time in which a tagged
operation ran, not the sum of the untagged events: a ``while`` carries no
``tf_op`` and spans its body's operations, which do. With the time under a
``tf_op`` outside every ``gtopk/`` scope it makes up what ``scoped_share``
leaves of the step; 0.0 is a reading."""
from perfbench.metrics import scoped


def read(ctx):
    info = scoped.run_info(ctx)
    if info is None:
        return None
    events, scopes = ctx["events"], info["scopes"]
    busy = scoped.scoped_seconds(events, scopes, lambda *_: True)
    tagged = scoped.scoped_seconds(
        events, scopes, lambda _, scope: scope is not None)
    if busy is None or tagged is None:
        return None
    return 1e3 * (busy - tagged) / ctx["steps"]
