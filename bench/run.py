"""Run one cell of the benchmark once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, in a process that owns the cell's TPU chips.
With ``--trace 0`` the result holds the cell's end-to-end metrics, measured
over a window of ``--seconds``; with ``--trace 1`` its per-layer metrics, read
from a profiler trace of a short window.  Either way the first steps are
checked against a plain reference, and the last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, (``breakdown``,) ``checks``.  Without a TPU, or with fewer chips
than the cell asks for, it prints no result and exits with code 3.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NO_CHIP = 3


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import harness

    try:
        devices = harness.tpu_devices(harness.spec.cell(
            harness.spec.load_benchmark(), args.workload)["chips"])
    except harness.NoChip as e:
        print(f"bench/run.py: {e}; nothing was run", file=sys.stderr)
        return NO_CHIP
    harness.use_cache_dir()
    result = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                              t_start=T_START, devices=devices)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
