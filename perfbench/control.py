"""The control of a sparse cell's limits where ``limits.py --control`` does
not fit: at N = 504M two whole ``reference.train`` results beside
``compare.numbers``' full-size temporaries met the one-chip machine's 40 GiB
of host memory (PR 35).

    python3 perfbench/control.py --workload W --seeds 11,12 [--program 1]

For each seed, in one process: with ``--program 1`` first the program's own
probe (its ``achieved_density`` at steps 1-3, then its numbers against the
reference: one more sound reading), then the reference with its master
weights in bfloat16 in the program's place. ``numbers`` below gives
``compare.numbers``' values by its formulas (a test holds the two equal),
taken in place and on the reference's support, so that nothing of the
vector's size is made but the two running sums; ``compare.decide`` then
holds them to the cell's limits and prints each beside its limit. The
control has to come out not correct. Nothing here is run by the benchmark's
own runs.
"""

import argparse
import gc
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SLICE = 1 << 26


def moved(side):
    """|params after three steps - params at the start|, a slice at a time."""
    first, last = side["params"][0], side["params"][3]
    total = 0.0
    for lo in range(0, first.size, SLICE):
        total += float(np.sum(np.square(
            last[lo:lo + SLICE].astype(np.float64) - first[lo:lo + SLICE])))
    return total ** 0.5


def numbers(program, reference, config, traffic):
    """``compare.numbers`` of a sparse cell, light on memory."""
    from perfbench import compare

    lr = config["optimizer"]["lr"]
    momentum = config["optimizer"]["momentum"]
    out = {}
    pl, rl = np.array(program["losses"]), np.array(reference["losses"])
    out["loss_gap_1_3"] = float(np.max(np.abs(pl[:3] - rl[:3]) / rl[:3]))
    applied = np.zeros_like(reference["params"][0])
    velocity = np.zeros_like(applied)
    for t in (1, 2):
        sent = reference["updates"][t - 1]
        velocity *= momentum
        velocity += sent
        applied += velocity
        support = np.flatnonzero(sent)
        diff = program["params"][t][support] - reference["params"][t][support]
        done = applied[support]
        hit = np.abs(diff) < 0.5 * lr * np.abs(done)
        out[f"support_recall_{t}"] = float(hit.mean())
        out[f"value_gap_{t}"] = float(
            np.linalg.norm(diff[hit])
            / (lr * np.linalg.norm(done[hit]) + 1e-30))
    del applied, velocity
    there = moved(reference)
    out["dparam_gap_3"] = abs(moved(program) - there) / (there + 1e-30)
    out["loss_ratio"] = compare.loss_ratio(program["losses"],
                                           reference["losses"], traffic)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    from perfbench import compare, harness, reference, traffic

    cell = harness.load_cell(args.workload)
    cfg, tr, steps = cell.config, cell.traffic, cell.traffic["probe_steps"]
    # The limits of what ``numbers`` gives: a window's compiles, its losses
    # and the replicas' copies are the runs' own.
    decide = lambda row: compare.decide(
        row, {name: tr["limits"][name] for name in row})
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        pool = traffic.make_pool(cfg, tr, seed)
        program = None
        if args.program:
            trainer = harness.build_trainer(cell, seed, pool)
            obs, log = [], trainer.metrics.log

            def logged(kind, **fields):
                if kind == "obs":
                    obs.append(fields)
                return log(kind, **fields)

            trainer.metrics.log = logged
            program, _ = harness.probe(trainer, steps)
            trainer.close()
            del trainer
            gc.collect()
            print(json.dumps({"seed": seed, "achieved_density": {
                o["step"]: o.get("achieved_density") for o in obs[:3]}}),
                flush=True)
        ref = reference.train(cfg, tr, seed, pool, steps)
        gc.collect()
        if program:
            row = numbers(program, ref, cfg, tr)
            print(json.dumps({"seed": seed, "sound": row}), flush=True)
            decide(row)
            del program
            gc.collect()
        low = reference.train(cfg, tr, seed, pool, steps, master_bits=16)
        low.pop("updates")
        gc.collect()
        row = numbers(low, ref, cfg, tr)
        print(json.dumps({"seed": seed, "control": row,
                          "reference_seconds": ref["seconds"]}), flush=True)
        all_failed &= not decide(row)
        del ref, low, pool
        gc.collect()
    print(json.dumps({"control_not_correct_on_every_seed": bool(all_failed)}))
    return 0 if all_failed else 1


if __name__ == "__main__":
    raise SystemExit(main())
