"""``python3 perfbench/run.py --workload W --seed N --seconds S --trace 0|1``

Runs one cell once on the machine it is started on and prints, as the last
line of its standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``. No TPU, or
fewer chips than the cell asks for, is a non-zero exit with no result.
"""

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    from perfbench import harness

    cell = harness.load_cell(args.workload)
    try:
        import gtopkssgd_tpu  # noqa: F401
    except ImportError:
        raise SystemExit("perfbench: the program (gtopkssgd_tpu) is not in "
                         "this checkout; the benchmark has nothing to run")
    import jax

    if jax.default_backend() != "tpu" or jax.device_count() < cell.chips:
        raise SystemExit(
            f"perfbench: {cell.name} needs {cell.chips} TPU chip(s); jax has "
            f"{jax.device_count()} {jax.default_backend()} device(s). There "
            "is no CPU fallback.")
    # jax keeps its PRNG seeds in 32 bits; larger seeds fold into them.
    seed = args.seed % (2 ** 31 - 1)
    # The program logs to stdout; the result line must be the last one.
    result = harness.run_cell(cell, seed, args.seconds, bool(args.trace),
                              started=STARTED)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
