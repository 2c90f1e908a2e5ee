"""The share of the step programs' device-busy time that ran under any
``gtopk/`` scope: the guard that a refactor has not dropped the scopes the
stage metrics read (scoped.py)."""
from perfbench.metrics import scoped


def read(ctx):
    info = scoped.run_info(ctx)
    if info is None:
        return None
    events, scopes = ctx["events"], info["scopes"]
    named = scoped.scoped_seconds(events, scopes, lambda _, scope: bool(scope))
    busy = scoped.scoped_seconds(events, scopes, lambda *_: True)
    return 100.0 * named / busy if named is not None and busy else None
