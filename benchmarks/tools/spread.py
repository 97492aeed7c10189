"""Spreads of the full sets, as the contract defines them: for each metric
and set the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median; the wider
of the two sets; and the second set's median against the first's.

    python3 benchmarks/tools/spread.py chiprun_out/sets_<workload>.jsonl
"""

import json
import statistics
import sys


def main(path: str) -> None:
    rows = [json.loads(line) for line in open(path) if line.startswith("{")]
    sets = {s: [r for r in rows if r["set"] == s] for s in (1, 2)}
    names = sorted({k for r in rows if r["set"] in (1, 2) for k in r["metrics"]})
    for name in names:
        out, medians = [], []
        for s in (1, 2):
            values = [r["metrics"][name]["value"] for r in sets[s]]
            if name == "setup_s":
                values = values[1:] if s == 1 else values  # a set's first run may compile
            q1, _, q3 = statistics.quantiles(values, n=4)
            med = statistics.median(values)
            medians.append(med)
            out.append(f"set {s}: n={len(values)} median {med:.4f} spread {(q3 - q1) / med:.5f} "
                       f"min {min(values):.4f} max {max(values):.4f}")
        print(name, "|", " | ".join(out), f"| second/first median {medians[1] / medians[0]:.5f}")
    bad = [(r["set"], r["seed"]) for r in rows if not r["correct"]]
    print("runs:", len(rows), "not correct:", bad)
    for key in rows[0]["checks"]:  # a cell compares the numbers its traffic file gives limits for
        values = [r["checks"][key]["value"] for r in rows]
        print(f"{key}: max {max(values):.3g} over {len(values)} runs, limit {rows[0]['checks'][key]['limit']}")
    traced = [r for r in rows if r["set"] == 0]
    for name in sorted({k for r in traced for k in r["metrics"]}):
        print("traced", name, [round(r["metrics"][name]["value"], 3) for r in traced if name in r["metrics"]])
    for r in traced:
        print("traced device", r["device"], "setup", r["info"]["setup_stamps_s"])


if __name__ == "__main__":
    main(sys.argv[1])
