"""Why was a run not warm? One run of a cell with jax's own account of its
compile cache kept: for every program it compiled or loaded, the hash of each
part of the cache key (computation, compile options, accelerator config, XLA
flags, ...), whether the lookup hit, and how long jax says its compiles and
its cache reads took (a hit can be slow too). Run it twice on the same checkout,
with two seeds, and compare the files: a program whose key differs between
the runs names the part that changed; one whose key is the same and still
missed was evicted or never written. (PR 27: no run of `gpt2s_t1024_dp4`
ever found a program in the cache, and the cause was not found; PERF.md
section 7.)

    python3 benchmarks/tools/cache_keys.py --workload gpt2s_t1024_dp4 --seed 11 --out chiprun_out/keys_a.json
    python3 benchmarks/tools/cache_keys.py --workload gpt2s_t1024_dp4 --seed 12 --out chiprun_out/keys_b.json
    python3 benchmarks/tools/cache_keys.py --compare chiprun_out/keys_a.json chiprun_out/keys_b.json
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

PART = re.compile(r"get_cache_key hash of serialized (.+?): ([0-9a-f]+)")
LOOKUP = re.compile(r"(?i)(persistent compilation cache (?:hit|miss)) for '([^']+)' with key '([^']+)'")


class Keep(logging.Handler):
    """jax logs the parts of a key first and the lookup's outcome after."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.parts, self.programs, self.other = {}, [], []

    def emit(self, record):
        msg = record.getMessage()
        if m := PART.search(msg):
            self.parts[m.group(1)] = m.group(2)[:16]
        elif m := LOOKUP.search(msg):
            self.programs.append({"program": m.group(2), "key": m.group(3), "hit": "hit" in m.group(1).lower(),
                                  "parts": self.parts})
            self.parts = {}
        elif "cache" in msg.lower() and record.levelno >= logging.WARNING:
            self.other.append(msg[:300])


def record(args) -> int:
    keep = Keep()
    for name in ("jax._src.cache_key", "jax._src.compiler", "jax._src.compilation_cache"):
        log = logging.getLogger(name)
        log.setLevel(logging.DEBUG)
        log.addHandler(keep)
        log.propagate = False
    import jax.monitoring

    # how long a lookup that hit took to read and load, and a miss to compile: a hit can be slow too
    durations: dict = {}
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: durations.setdefault(event, []).append(round(secs, 3))
        if "compil" in event or "cache" in event else None)
    from benchmarks.lib import harness

    try:
        r = harness.run_cell(args.workload, args.seed, args.seconds, False, require_tpu=not args.allow_cpu,
                             bench_file=args.bench_file, data_dirs=args.data_dir or None)
    except harness.NoResult as e:
        print(f"cache_keys: no result: {e}", file=sys.stderr)
        return 2
    import jax

    directory = jax.config.jax_compilation_cache_dir
    held = sorted(((os.path.getsize(os.path.join(directory, f)), f) for f in os.listdir(directory)
                   if f.endswith("-cache")), reverse=True) if directory and os.path.isdir(directory) else []
    out = {"workload": args.workload, "seed": args.seed, "correct": r["correct"], "setup_s": r["metrics"]["setup_s"],
           "reference_s": r["info"]["reference_s"], "setup_stamps_s": r["info"]["setup_stamps_s"],
           "cache_dir": directory, "cache_max_size": jax.config.jax_compilation_cache_max_size,
           "cache_bytes": sum(s for s, _ in held), "largest_entries": held[:12],
           "durations_s": {k: {"n": len(v), "sum": round(sum(v), 3), "largest": sorted(v, reverse=True)[:8]}
                           for k, v in durations.items()},
           "programs": keep.programs, "warnings": keep.other}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    hits = sum(p["hit"] for p in keep.programs)
    print(f"{args.workload} seed {args.seed}: {len(keep.programs)} lookups, {hits} hits; setup_s "
          f"{out['setup_s']['value']:.1f}; cache {out['cache_bytes'] / 1e6:.1f} MB in {directory}")
    return 0


def compare(path_a: str, path_b: str) -> int:
    a, b = (json.load(open(p)) for p in (path_a, path_b))
    keys_a, keys_b = ({p["key"] for p in run["programs"]} for run in (a, b))
    same = 0
    for p in b["programs"]:
        if p["key"] in keys_a:
            same += 1
            if not p["hit"]:
                print(f"{p['program']}: the key the first run had, and still a miss: evicted or never written")
            continue
        # several programs share a name (jit_add at every shape): compare with those of the first run that the second never asked for
        rivals = [q for q in a["programs"] if q["program"] == p["program"] and q["key"] not in keys_b]
        changed = min(([k for k in p["parts"] if p["parts"][k] != q["parts"].get(k)] for q in rivals), key=len, default=None)
        print(f"{p['program']}: a key the first run never had" +
              (f"; differs from its nearest unmatched {p['program']} in {changed}" if changed is not None else ""))
    print(f"{len(b['programs'])} lookups in the second run, {same} with a key of the first run's, "
          f"{sum(p['hit'] for p in b['programs'])} hits")
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--compare", nargs=2, metavar="FILE")
    parser.add_argument("--workload")
    parser.add_argument("--seed", type=int)
    parser.add_argument("--seconds", type=float, default=1.0)
    parser.add_argument("--out")
    parser.add_argument("--bench-file")           # the tests' seams, for a rehearsal off the chip
    parser.add_argument("--data-dir", action="append")
    parser.add_argument("--allow-cpu", action="store_true")
    args = parser.parse_args()
    if args.compare:
        return compare(*args.compare)
    if not (args.workload and args.seed is not None and args.out):
        parser.error("--workload, --seed and --out, or --compare A B")
    return record(args)


if __name__ == "__main__":
    sys.exit(main())
