"""Readings that the limits of `correct` are set from, on the chip, at the
cell's own size: the program's numbers over many seeds and, for the first
``--controls`` seeds, the stand-ins of harness.STAND_INS (the reference in the control precision,
and with half of the batch left out, put in the program's place). One process, one
short window a seed. Appends one JSON line a seed to ``--out``.

    python3 benchmarks/tools/calibrate.py --workload gpt2s_t1024 --seeds 11,12,13 --controls 3 \
        --out chiprun_out/calibrate_gpt2s_t1024.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--controls", type=int, default=3)
    parser.add_argument("--seconds", type=float, default=1.0)
    parser.add_argument("--out", required=True)
    args = parser.parse_args()
    from benchmarks.lib import harness

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            r = harness.run_cell(args.workload, seed, args.seconds, False, extra_readings=i < args.controls)
        except harness.NoResult as e:
            print(f"calibrate: no result: {e}", file=sys.stderr)
            return 2
        row = {"workload": args.workload, "seed": seed, "correct": r["correct"], "checks": r["checks"],
               "readings": r.get("readings"), "metrics": r["metrics"], "info": r["info"],
               "wall_s": time.perf_counter() - t0}
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps({k: row[k] for k in ("seed", "correct", "checks", "wall_s")}), flush=True)
        if row["readings"]:
            print(json.dumps(row["readings"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
