"""A cell is data: an entry of BENCHMARK.json naming a configuration file and
a traffic file. Nothing here knows any cell, configuration or mix by name."""

from __future__ import annotations

import dataclasses
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmarks")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    data_dirs: tuple


def _find(data_dirs, sub, name):
    for base in data_dirs:
        path = os.path.join(base, sub, name + ".json")
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"no {sub}/{name}.json under {list(data_dirs)}")


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(workload: str, bench_file: str | None = None, data_dirs=None) -> Cell:
    bench_file = bench_file or os.path.join(ROOT, "BENCHMARK.json")
    data_dirs = tuple(data_dirs or ()) + (BENCH_DIR,)
    with open(bench_file) as f:
        bench = json.load(f)
    rows = [w for w in bench["workloads"] if w["name"] == workload]
    if not rows:
        raise KeyError(f"no workload {workload!r} in {bench_file}: {[w['name'] for w in bench['workloads']]}")
    row = rows[0]
    cfg_row = next(c for c in bench["configs"] if c["name"] == row["config"])
    cfg_path = cfg_row["file"]
    if not os.path.isabs(cfg_path):
        cfg_path = os.path.join(os.path.dirname(os.path.abspath(bench_file)), cfg_path)
    with open(cfg_path) as f:
        config = json.load(f)
    with open(_find(data_dirs, "traffic", row["traffic"])) as f:
        traffic = json.load(f)
    if traffic["chips"] != row["chips"]:
        raise ValueError(f"{workload}: BENCHMARK.json says {row['chips']} chips, traffic file {traffic['chips']}")
    return Cell(
        name=workload,
        chips=row["chips"],
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        data_dirs=data_dirs,
    )
