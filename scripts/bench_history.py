#!/usr/bin/env python
"""Bench-history ledger CLI (ISSUE 14) — read a directory of bench rounds.

Ingests ``BENCH_r*.json`` / ``MULTICHIP_r*.json`` round files under
``--root`` into per-metric trajectories (``telemetry.history``) and prints
the ledger with flat-streak and regression detections. The repo commits no
round files of its own (the driver's ``PERF_LEDGER.jsonl`` is the record of
measured performance); point ``--root`` at a directory that holds some.

Usage::

    python scripts/bench_history.py --root DIR            # ledger + detections
    python scripts/bench_history.py --root DIR --json     # machine-readable
    python scripts/bench_history.py --root DIR --events E # + a `bench_history`
                                                          #   JSONL record

The detector's boundary cases are covered in ``tests/test_run_compare.py``
on round files the tests write themselves.

Exit codes: 0 ok, 2 no round files found under ``--root``.
"""

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from distributed_training_pytorch_tpu.telemetry import history as history_lib  # noqa: E402

REPO_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--root", default=REPO_ROOT,
                        help="directory holding the BENCH_r*/MULTICHIP_r* files "
                             "(default: the repo root)")
    parser.add_argument("--flat-tol", type=float, default=history_lib.FLAT_REL_TOL,
                        help="flat-streak relative band (default %(default)s)")
    parser.add_argument("--flat-rounds", type=int, default=history_lib.FLAT_MIN_ROUNDS,
                        help="rounds needed for a flat streak to fire "
                             "(default %(default)s; one fewer stays quiet)")
    parser.add_argument("--regression-tol", type=float,
                        default=history_lib.REGRESSION_REL_TOL,
                        help="round-over-round bad-direction tolerance "
                             "(default %(default)s)")
    parser.add_argument("--json", action="store_true",
                        help="print the full ledger as one JSON object")
    parser.add_argument("--events", default=None,
                        help="append a bench_history record to this JSONL event log")
    args = parser.parse_args()

    report = history_lib.analyze_history(
        args.root,
        flat_tol=args.flat_tol,
        flat_min_rounds=args.flat_rounds,
        regression_tol=args.regression_tol,
    )
    if not report.entries:
        print(f"bench_history: no BENCH_r*/MULTICHIP_r* round files under "
              f"{args.root}", file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps(report.to_dict(), indent=2, sort_keys=True))
    else:
        print(report.describe())

    if args.events:
        from distributed_training_pytorch_tpu.telemetry import EventLog

        EventLog(args.events, process_index=0).emit(
            "bench_history",
            root=os.path.abspath(args.root),
            entries=len(report.entries),
            series=len(report.series),
            streaks=[s.to_dict() for s in report.streaks],
            regressions=[r.to_dict() for r in report.regressions],
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
