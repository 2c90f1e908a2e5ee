"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 perfbench/limits.py --workload W --seeds 11,12,... --control 3 [--program 0]

In ONE process (the step compiles once: the seed is no constant of it), for
each seed: the program's probe against the reference, every number of
``compare.numbers``; and for the first ``--control`` seeds the control, the
reference with its master weights in bfloat16 put in the program's place.
Prints a line per seed and, last, for every number the sound runs' smallest
and largest beside the control's: a limit goes between them (PERF.md,
section 2). ``--program 0`` reads the control alone (the four-chip cell's
sound readings come from its own runs, which print every number). Nothing
here is run by the benchmark's own runs.
"""

import argparse
import gc
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values):
    """Interquartile distance over the median, as the contract reads it."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / (abs(statistics.median(values)) or 1.0)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--program", type=int, choices=[0, 1], default=1)
    args = ap.parse_args(argv)

    from perfbench import compare, harness, reference, traffic

    cell = harness.load_cell(args.workload)
    tr, steps = cell.traffic, cell.traffic["probe_steps"]
    sound, control = [], []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        pool = traffic.make_pool(cell.config, tr, seed)
        program = None
        if args.program:
            trainer = harness.build_trainer(cell, seed, pool)
            program, _ = harness.probe(trainer, steps)
            trainer.close()
            del trainer
            gc.collect()
        ref = reference.train(cell.config, tr, seed, pool, steps)
        if program:
            row = compare.numbers(program, ref, cell.config, tr)
            sound.append(row)
            print(json.dumps({"seed": seed, "sound": row,
                              "losses": [round(x, 4) for x in program["losses"]],
                              "reference_losses": [round(x, 4) for x in ref["losses"]]}),
                  flush=True)
        if i < args.control:
            low = reference.train(cell.config, tr, seed, pool, steps, master_bits=16)
            row = compare.numbers(low, ref, cell.config, tr)
            control.append(row)
            print(json.dumps({"seed": seed, "control": row}), flush=True)
        del program, ref, pool
    summary = {}
    for name in (sound or control)[0]:
        summary[name] = {}
        for label, rows in (("sound", sound), ("control", control)):
            values = [r[name] for r in rows]
            if values:
                summary[name].update({f"{label}_min": min(values),
                                      f"{label}_max": max(values)})
            if len(values) >= 4:
                summary[name][f"{label}_spread"] = spread(values)
    out = os.path.join(cell.root, "chiprun_out", "perfbench")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"limits_{cell.name}.json"), "w") as fh:
        json.dump({"sound": sound, "control": control, "summary": summary}, fh, indent=1)
    print(json.dumps({"summary": summary}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
