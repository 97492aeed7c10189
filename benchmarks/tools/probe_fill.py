"""Is there room on one chip for a configuration whose training state fills
it? A probe, not a cell: the benchmark's own LM family (the files `gpt2-small`
names) at a depth and width given here, written as a configuration file, a
traffic file and a BENCHMARK.json into a temporary directory and run through
``harness.run_cell`` as any cell is, under `t1024_b32`'s limits. It names no
model and is in no BENCHMARK.json. The defaults are 774 million parameters
(14 x 2048, heads of 64, vocabulary 32,768: 12.4 GB of float32 parameters,
gradients and AdamW moments) at 2 x 1024 tokens a step.

    python3 benchmarks/tools/probe_fill.py --seed 11 --out chiprun_out/probe.json
    python3 benchmarks/tools/probe_fill.py --seed 11 --stand-in control      # has to read correct: false

Rerun it before sizing a `model_config` cell whose state is most of the
chip, and after any change to what the harness keeps on the device
(``lib/harness.py:make_weights``, ``lib/recorder.py``, ``lib/refrun.py``).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def write_cell(base: str, args) -> tuple[str, dict]:
    """The probe's three files under ``base``; returns the BENCHMARK.json's path and the configuration."""
    with open(os.path.join(ROOT, "benchmarks", "configs", "gpt2-small.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "traffic", "t1024_b32.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg.update(name="probe-lm", source="benchmarks/tools/probe_fill.py: a size, not a model",
               n_embd=args.n_embd, n_layer=args.n_layer, n_head=args.n_embd // 64, n_inner=args.n_inner,
               vocab_size=args.vocab, n_positions=args.seq_len, n_ctx=args.seq_len)
    for key in ("reduced", "reduced_why", "assumed"):
        cfg.pop(key, None)
    limits = traffic["limits"]["gpt2-small"]
    traffic.update(seq_len=args.seq_len, global_batch=args.batch,
                   reference_block_rows=min(traffic["reference_block_rows"], args.batch),
                   limits={"probe-lm": limits})
    for sub, name, body in (("configs", "probe-lm", cfg), ("traffic", "probe", traffic)):
        os.makedirs(os.path.join(base, sub), exist_ok=True)
        with open(os.path.join(base, sub, name + ".json"), "w") as f:
            json.dump(body, f, indent=1)
    bench["configs"] = [{"name": "probe-lm", "source": cfg["source"], "file": os.path.join(base, "configs", "probe-lm.json"),
                         "reduced": [], "why": "a training state that fills one chip"}]
    bench["workloads"] = [{"name": "probe_fill", "config": "probe-lm", "traffic": "probe", "chips": 1,
                           "why": "is there room beside 12 bytes a parameter of state"}]
    path = os.path.join(base, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return path, cfg


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--n-embd", type=int, default=2048)
    parser.add_argument("--n-layer", type=int, default=14)
    parser.add_argument("--n-inner", type=int, default=8192)
    parser.add_argument("--vocab", type=int, default=32768)
    parser.add_argument("--seq-len", type=int, default=1024)
    parser.add_argument("--batch", type=int, default=2)
    parser.add_argument("--seed", type=int, default=11)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--stand-in", choices=("control",), default=None)
    parser.add_argument("--out", default=None, help="append the result line (or where the run died) to this file")
    args = parser.parse_args()
    if args.n_embd % 64:
        parser.error("--n-embd must be a multiple of 64: heads of 64, so that attention on auto takes flash")

    from benchmarks.lib import harness
    from benchmarks.reference import gpt2

    with tempfile.TemporaryDirectory(prefix="bench_probe_") as base:
        bench_file, cfg = write_cell(base, args)
        parameters = sum(math.prod(s) for s in gpt2.param_shapes(cfg, {"seq_len": args.seq_len}).values())
        row = {"parameters": parameters, "args": vars(args)}
        print(f"probe: {parameters:,} parameters, {16 * parameters / 1e9:.2f} GB at 16 bytes a parameter", file=sys.stderr)
        try:
            result = harness.run_cell("probe_fill", args.seed, args.seconds, False, bench_file=bench_file,
                                      data_dirs=[base], stand_in=args.stand_in)
        except harness.NoResult as e:
            print(f"probe: no result: {e}", file=sys.stderr)
            return 2
        except Exception:  # the tool's one boundary: say where the run died (RESOURCE_EXHAUSTED names no phase itself)
            died = traceback.format_exc()
            print(died[-6000:], file=sys.stderr)
            row.update(reached_result=False, died=died[-6000:])
            result = None
        if result is not None:
            harness.print_result(result)
            row.update(reached_result=True, result=result)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0 if result is not None else 1


if __name__ == "__main__":
    sys.exit(main())
