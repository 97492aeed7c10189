"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time. Fails at once, with no result line, when JAX finds
no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up counts from here: imports included

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()

    try:
        from benchmarks.lib import harness
    except ImportError as e:
        print(f"benchmarks/run.py: no result: the benchmark's files are not all here: {e}", file=sys.stderr)
        return 2
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t_start=T_START)
    except (harness.NoResult, ImportError) as e:
        print(f"benchmarks/run.py: no result: {e}", file=sys.stderr)
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
