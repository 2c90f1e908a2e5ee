"""Single-source process exit-code registry.

Drivers, retry loops, and the multihost test harness classify finished
runs by return code WITHOUT parsing logs, so these values are a
cross-tool contract. Every ``sys.exit`` / ``SystemExit`` / ``os._exit``
literal in the tree must come from here — enforced statically by
graftlint's ``exit-code`` rule (``python -m gtopkssgd_tpu.analysis``),
which also rejects ``*_EXIT_CODE`` constants minted outside this module
and collisions inside it.

This module is import-cost-free (no jax, no package deps): the analyzer
reads it by AST and the consumers (watchdog, events, preempt, bench
scripts) import it at process start.
"""

from __future__ import annotations

EXIT_OK = 0                  # run completed
EXIT_ERROR = 1               # generic failure (uncaught exception,
                             # SystemExit("message"), lint findings)
EXIT_USAGE = 2               # CLI usage / unreadable input (argparse's
                             # own convention; report gate I/O errors)
EXIT_STALL = 43              # dispatch-stall watchdog fired
                             # (obs/watchdog.py: a dispatched step made
                             # no host-visible progress by the deadline)
EXIT_ANOMALY_HALT = 44       # --obs-halt-on anomaly fail-fast
                             # (obs/events.py AnomalyHalt)
EXIT_PREEMPTED = 45          # SIGTERM/SIGINT intercepted, emergency
                             # checkpoint durable; relaunch with
                             # --resume (resilience/preempt.py)
EXIT_RESIZE_RESTART = 46     # coordinated elastic resize: state drained
                             # + checkpointed, lineage file rewritten;
                             # relaunch with --resume --elastic on the
                             # new process set (resilience/elastic.py) —
                             # distinct from 45, which means "this
                             # process was told to die", not "the fleet
                             # is re-forming"
EXIT_MULTIHOST_SKIP = 99     # multi-process probe unsupported on this
                             # build (tests/test_multihost.py,
                             # benchmarks/dcn_probe.py: designed skip,
                             # not a failure)

REGISTRY = {
    EXIT_OK: "run completed",
    EXIT_ERROR: "generic failure",
    EXIT_USAGE: "CLI usage error / unreadable input",
    EXIT_STALL: "dispatch-stall watchdog fired",
    EXIT_ANOMALY_HALT: "anomaly monitor fail-fast (--obs-halt-on)",
    EXIT_PREEMPTED: "preempted after emergency checkpoint "
                    "(resume with --resume)",
    EXIT_RESIZE_RESTART: "elastic resize: checkpoint + lineage durable "
                         "(relaunch with --resume --elastic on new P)",
    EXIT_MULTIHOST_SKIP: "multi-process probe unsupported: "
                         "designed skip",
}


def describe(code: int) -> str:
    """Human name for an exit code (unknown codes say so — the lint
    rule should have made them impossible)."""
    return REGISTRY.get(code, f"unregistered exit code {code}")
