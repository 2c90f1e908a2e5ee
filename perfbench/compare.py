"""The comparison that decides ``correct``: the program against the reference.

Both sides start from the same seed and take the same batches. Each number
below has a limit of its own in the traffic file (``limits``), set from
readings on the chip that PERF.md lists; ``decide`` prints every number
beside its limit. Parameters are compared as flat float32 vectors in the
order of ``jax.tree.leaves``.

  loss_gap_1_3     widest |program - reference| / reference over the losses
                   of steps 1-3: catches rows left out of a batch.
  support_recall_t (sparse) share of the coordinates the reference sends at
                   step t (exact top-k, after the tree at P > 1) that the
                   program's parameters moved with it. A coordinate i counts
                   when |p_t[i] - ref_t[i]| < lr * |sent_t[i]| / 2, where
                   sent_t is what the reference has applied there so far: a
                   coordinate the program did not send differs by all of it.
  value_gap_t      on those coordinates (dense: on all), the norm of the
                   parameters' difference over the norm of what the reference
                   applied: the values on the common support. The control,
                   master weights in bfloat16, fails here.
  dparam_gap_3     the gap between the two norms of the change of the
                   parameters after three steps, against the reference's: a
                   step that leaves the state alone reads 1. Taken over the
                   whole vector: a leaf's norm under a sparse update hangs on
                   a handful of coordinates, and sound runs read up to 0.68
                   by the worst leaf (PERF.md, section 2).
  loss_ratio       the end-to-end metric's value, inside the traffic's band.
"""

import math

import numpy as np


def numbers(program, reference, config, traffic):
    """{name: value} of everything compared. ``program`` and ``reference``
    hold "losses" (per probe step), "params" (flat vectors at steps 0..3)
    and the reference also "updates" (steps 1..2)."""
    lr = config["optimizer"]["lr"]
    momentum = config["optimizer"]["momentum"]
    sparse = traffic["compression"] != "dense"
    out = {}
    pl, rl = np.array(program["losses"]), np.array(reference["losses"])
    out["loss_gap_1_3"] = float(np.max(np.abs(pl[:3] - rl[:3]) / rl[:3]))
    applied = np.zeros_like(reference["params"][0])
    velocity = np.zeros_like(applied)
    for t in (1, 2):
        sent = reference["updates"][t - 1]
        velocity = momentum * velocity + sent
        applied = applied + velocity
        diff = program["params"][t] - reference["params"][t]
        if sparse:
            support = np.flatnonzero(sent)
            hit = np.abs(diff[support]) < 0.5 * lr * np.abs(applied[support])
            out[f"support_recall_{t}"] = float(hit.mean())
            common = support[hit]
        else:
            common = slice(None)
        out[f"value_gap_{t}"] = float(
            np.linalg.norm(diff[common])
            / (lr * np.linalg.norm(applied[common]) + 1e-30))
    moved = lambda side: np.linalg.norm(side["params"][3] - side["params"][0])
    out["dparam_gap_3"] = float(
        abs(moved(program) - moved(reference)) / (moved(reference) + 1e-30))
    out["loss_ratio"] = loss_ratio(program["losses"], reference["losses"],
                                   traffic)
    return out


def loss_ratio(program_losses, reference_losses, traffic):
    first, last = traffic["ratio_steps"]
    mean = lambda xs: sum(xs[first - 1:last]) / (last - first + 1)
    return mean(program_losses) / mean(reference_losses)


def decide(values, limits, emit=print):
    """True when every value keeps its limit; prints each beside its limit.
    A limit is {"max": x}, {"min": x} or {"min": a, "max": b}; a value with
    no limit, or a limit with no value, fails."""
    ok = True
    for name in sorted(set(values) | set(limits)):
        value, limit = values.get(name), limits.get(name)
        good = (value is not None and limit is not None and math.isfinite(value)
                and value >= limit.get("min", -math.inf)
                and value <= limit.get("max", math.inf))
        emit(f"compare {name} = {value!r} limit {show_limit(limit)} "
             f"{'ok' if good else 'FAILED'}")
        ok = ok and good
    return ok


def show_limit(limit):
    if limit is None:
        return "none"
    return "[" + ", ".join(f"{k} {limit[k]}" for k in ("min", "max")
                           if k in limit) + "]"
