"""Run one benchmark cell once.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for.  The last line of standard output is the result (see
``harness.py``); without an accelerator it exits 2 and prints none.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.NoAccelerator as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
