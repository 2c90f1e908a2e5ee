"""graftlint — stdlib-``ast`` static analysis for this package's
load-bearing conventions.

The codebase has a growing set of invariants that no type checker or
unit test can see whole-program: nothing inside the jitted hot path may
force a host sync (the dispatch-stall watchdog exists because one did),
every ``.log(`` kind must be registered in ``utils.metrics.KINDS``,
every process exit code must come from the ``gtopkssgd_tpu.exit_codes``
registry, every sparse (vals, idx) exchange in ``parallel/`` must flow
through the wire codec, and durable record kinds must be fsync'd.
graftlint checks all of them from source alone — no JAX import, no
device, runs in seconds — so the wire path stays auditable with no chip
attached (the same "correctness without silicon" posture EQuARX-style
quantized collectives argue for).

Usage::

    python -m gtopkssgd_tpu.analysis gtopkssgd_tpu/ [benchmarks/ ...]
        [--json] [--baseline PATH] [--write-baseline PATH]
        [--rule RULE ...] [--list-rules]

Exit codes (registered in gtopkssgd_tpu.exit_codes): 0 = clean (every
finding suppressed or baselined), 1 = non-baselined findings, 2 = usage.

Suppressions: append ``# graftlint: disable=RULE[,RULE|all]`` to the
flagged line (or the line directly above it). Suppressions are for
reviewed false positives — say why in the same comment.

Baseline: grandfathered findings live in a committed JSON file
(``graftlint_baseline.json`` at the repo root); entries match on
(rule, path, enclosing function, flagged source) so they survive line
drift. ``--write-baseline`` regenerates it; review the diff like code.
"""

from gtopkssgd_tpu.analysis.engine import (  # noqa: F401
    Finding,
    analyze,
    load_baseline,
    run,
)
from gtopkssgd_tpu.analysis.rules import ALL_RULES  # noqa: F401

__all__ = ["Finding", "analyze", "load_baseline", "run", "ALL_RULES"]
