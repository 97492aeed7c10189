#!/usr/bin/env python
"""XLA-flag / schedule autotuner CLI (ISSUE 17) — sweep a declared candidate
space on the bench workload and write the report as ``TUNED.json``.

The stack could *measure* but nothing *searched*: every knob (latency-hiding
scheduler, scoped VMEM, chain length, Pallas hot paths) sat behind manual
env flags. This CLI runs the search; none of its candidates has been
measured on today's chip:

* **Candidate space** — declared up front (``CANDIDATES`` below, or
  ``--candidates FILE.json``): XLA latency-hiding/async-collective flags
  (applied per-compile via ``train.engine.xla_flag_options`` — never by
  mutating global XLA_FLAGS), ``chain_steps``, microbatch shape, and the
  unified ``pallas`` knob. The grammar is ``train.autotune.Candidate``;
  docs/performance.md "Autotuning" documents it.
* **Measurement** — every candidate runs through
  ``train.autotune.measure_chained_step``: two-length differencing on the
  REAL ``TrainEngine.compile_chained_train_steps`` executable of the
  ``BENCH_MODEL`` workload (``bench.build_bench_setup`` — the program that
  ships), plus a perf_gate-style traced window for category fractions.
* **Ranking + refusal** — ``train.autotune.rank_candidates``: lowest
  step_ms wins; every delta is attributed per-category through
  ``profiling.diff`` (the run_compare implementation); a candidate whose
  provenance differs from the baseline on an UNdeclared key is refused
  (PR 14 rule). A win inside the flat-streak noise band is reverted.
* **Evidence** — ``--emit`` writes the full report (baseline, ranked
  candidates with attribution, refusals, verdict) as TUNED.json. Nothing
  reads it back into a run: a winner becomes a default through a PR judged
  on every benchmark cell, never through an exported ``XLA_FLAGS``.

The sweep measures speed, so it refuses to run without a TPU; only
``--self-test`` pins the CPU.

``--self-test`` (the scripts/verify.sh stage; CPU, ~seconds) runs a real
tiny sweep with two teeth checks: a deliberately 3x de-tuned chain_steps=1
baseline (``--inject-slowdown``, perf_gate's seam pattern — the injection is
printed and applied AFTER measurement) that every real candidate must beat
with per-category attribution attached, and a provenance-mismatched
candidate (undeclared dtype drift) that MUST land in the refused list.
Exit 0 pass, 1 fail.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from distributed_training_pytorch_tpu.telemetry.provenance import provenance_fields
from distributed_training_pytorch_tpu.train import autotune as autotune_lib
from distributed_training_pytorch_tpu.train import xla_flag_options
from distributed_training_pytorch_tpu.train.autotune import Candidate

# The declared bench-host candidate space (docs/performance.md "Autotuning").
# None of these is measured on today's chip — the sweep's job is to find
# which combination wins on the CURRENT program, with evidence.
CANDIDATES = [
    Candidate("latency-hiding",
              {"xla_flags": "--xla_tpu_enable_latency_hiding_scheduler=true"},
              "overlap DMA/collectives with compute"),
    Candidate("async-collectives",
              {"xla_flags": "--xla_tpu_enable_async_collective_fusion=true"
                            " --xla_tpu_enable_async_collective_fusion_fuse_all_gather=true"},
              "async all-reduce/all-gather fusion"),
    Candidate("lhs+scoped-vmem",
              {"xla_flags": "--xla_tpu_enable_latency_hiding_scheduler=true"
                            " --xla_tpu_scoped_vmem_limit_kib=98304"},
              "latency hiding + wider scoped VMEM"),
    Candidate("chain-20", {"chain_steps": 20},
              "longer on-device window amortizes dispatch further"),
    Candidate("chain-40", {"chain_steps": 40}, ""),
    Candidate("pallas-on", {"pallas": True},
              "force the Pallas hot paths (ops/dispatch.py)"),
]


def _load_candidates(path: str | None) -> list[Candidate]:
    if not path:
        return CANDIDATES
    with open(path, encoding="utf-8") as f:
        rows = json.load(f)
    return [Candidate(r["name"], r.get("knobs", {}), r.get("note", "")) for r in rows]


def _result(name, knobs, measurement, note="") -> dict:
    return {"name": name, "knobs": dict(knobs), "note": note,
            "measurement": measurement}


def _print_report(report: dict) -> None:
    base = report["baseline"]
    print(f"autotune: baseline {base['name']}: "
          f"{base['measurement']['step_ms']} ms/step")
    for entry in report["ranked"]:
        line = (f"autotune:   {entry['name']:<18s} "
                f"{entry['measurement']['step_ms']:>9.3f} ms "
                f"({entry['delta_ms']:+.3f} ms)")
        if entry["attribution_text"]:
            line += f"  [{entry['attribution_text']}]"
        print(line)
    for ref in report["refused"]:
        print(f"autotune:   {ref['name']:<18s} REFUSED — provenance differs "
              f"on undeclared keys {ref['differing_keys']}")
    if report["kept"]:
        w = report["winner"]
        print(f"autotune: WINNER {w['name']} ({w['delta_ms']:+.3f} ms, "
              f"knobs {w['knobs']}) — kept (beats baseline past the "
              f"{report['rel_margin']:.0%} flat-streak band)")
    else:
        print("autotune: no candidate beat the baseline past the "
              f"{report['rel_margin']:.0%} band — baseline config stands "
              "(a sub-noise win is reverted, not shipped)")


# ---------------------------------------------------------------- self-test


def _tiny_engine(batch: int = 32):
    """The perf_gate GateNet shape, shrunk: a real conv+dense TrainEngine
    workload that compiles in ~a second on CPU — the sweep measures the
    same executable family the real mode does, just small."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax import linen as nn

    from distributed_training_pytorch_tpu.ops import cross_entropy_loss
    from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
    from distributed_training_pytorch_tpu.train import (
        TrainEngine,
        make_supervised_loss,
    )

    class TuneNet(nn.Module):
        @nn.compact
        def __call__(self, x, *, train: bool = False):
            x = nn.relu(nn.Conv(8, (3, 3))(x))
            x = x.reshape(x.shape[0], -1)
            return nn.Dense(10)(x)

    def criterion(logits, b):
        loss = cross_entropy_loss(logits, b["label"])
        return loss, {"loss": loss}

    model = TuneNet()
    engine = TrainEngine(
        make_supervised_loss(model, criterion),
        optax.sgd(0.05, momentum=0.9),
        mesh_lib.create_mesh(),
    )
    rng = np.random.RandomState(0)
    gbatch = engine.shard_batch({
        "image": rng.randn(batch, 12, 12, 3).astype(np.float32),
        "label": rng.randint(0, 10, size=(batch,)).astype(np.int32),
    })
    state = engine.init_state(
        jax.random.key(0), lambda r: model.init(r, jnp.zeros((1, 12, 12, 3)))
    )
    return engine, state, gbatch, batch


def self_test(inject_slowdown: float) -> int:
    batch = 32
    engine, state, gbatch, batch = _tiny_engine(batch)

    def prov(chain_steps, dtype="float32"):
        return provenance_fields(
            mesh="dp1", dtype=dtype, chain_steps=chain_steps, batch=batch
        )

    # Baseline: a DELIBERATELY de-tuned config — chain_steps=1 (maximum
    # per-dispatch overhead share) with the measured time multiplied by
    # --inject-slowdown AFTER measurement (the measurement itself is
    # untouched; perf_gate's "gate has teeth" seam). Every real candidate
    # below must rank ahead of it, or the ranking has no teeth.
    meas, state = autotune_lib.measure_chained_step(
        engine, state, gbatch, chain_steps=1, windows=2
    )
    meas["provenance"] = prov(1)
    meas["step_ms"] = round(meas["step_ms"] * inject_slowdown, 4)
    meas["injected_slowdown"] = inject_slowdown
    print(f"autotune: SELF-TEST — injected x{inject_slowdown} slowdown into "
          "the de-tuned chain_steps=1 baseline (every real candidate must "
          "out-rank it)")
    baseline = _result("baseline-chain1-detuned", {"chain_steps": 1}, meas)

    results = []
    for cs in (2, 4, 8):
        meas, state = autotune_lib.measure_chained_step(
            engine, state, gbatch, chain_steps=cs, windows=2
        )
        meas["provenance"] = prov(cs)
        results.append(_result(f"chain-{cs}", {"chain_steps": cs}, meas))

    # The refusal leg: same numbers as chain-2, but the provenance says the
    # measurement ran a different compute dtype — and "dtype" is NOT in the
    # candidate's declared knobs. PR 14 rule: refused, never ranked.
    drift = dict(results[0]["measurement"], provenance=prov(2, dtype="bfloat16"))
    results.append(_result("dtype-drift", {"chain_steps": 2}, drift))

    report = autotune_lib.rank_candidates(baseline, results)
    _print_report(report)

    failures = []
    refused_names = {r["name"] for r in report["refused"]}
    if refused_names != {"dtype-drift"}:
        failures.append(f"expected exactly dtype-drift refused, got {refused_names}")
    elif report["refused"][0]["differing_keys"] != ["dtype"]:
        failures.append("refusal must name the undeclared key 'dtype', got "
                        f"{report['refused'][0]['differing_keys']}")
    if any(e["name"] == "dtype-drift" for e in report["ranked"]):
        failures.append("refused candidate leaked into the ranking")
    if not report["kept"]:
        failures.append("no winner kept — the x3-de-tuned baseline was not beaten")
    else:
        if report["winner"]["delta_ms"] >= 0:
            failures.append("winner does not improve on the baseline")
        if not report["winner"]["attribution"]:
            failures.append("winner carries no per-category attribution "
                            "(category capture failed on both sides?)")
    if len(report["ranked"]) < 3:
        failures.append(f"expected >= 3 ranked candidates, got {len(report['ranked'])}")

    # TUNED.json round-trip: emit -> reload gives the report back.
    with tempfile.TemporaryDirectory(prefix="autotune_selftest_") as tmp:
        path = os.path.join(tmp, "TUNED.json")
        autotune_lib.emit_tuned(path, report)
        if autotune_lib.load_tuned(path) != json.loads(json.dumps(report)):
            failures.append("TUNED.json emit/load round-trip mismatch")

    # The XLA_FLAGS bridge: parse + reject, both directions.
    opts = xla_flag_options("--xla_a=2 --xla_b")
    if opts != {"xla_a": "2", "xla_b": "true"}:
        failures.append(f"xla_flag_options parse mismatch: {opts}")
    try:
        xla_flag_options("--not_an_xla_flag=1")
        failures.append("xla_flag_options accepted a non-xla flag")
    except ValueError:
        pass

    if failures:
        for f in failures:
            print(f"autotune: SELF-TEST FAIL — {f}")
        return 1
    print("autotune: self-test OK (ranking teeth, provenance refusal, "
          "TUNED round-trip, XLA-flag bridge)")
    return 0


# --------------------------------------------------------------- real sweep


def run_sweep(args) -> int:
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"autotune: the sweep ranks candidates by device speed and "
              f"found platform {platform!r}, not a TPU — refusing "
              "(--self-test is the CPU mode)", file=sys.stderr)
        return 2

    import bench

    from distributed_training_pytorch_tpu.utils.tpu import enable_fast_rng

    enable_fast_rng()
    steps = int(os.environ.get("BENCH_STEPS", "10"))
    windows = int(os.environ.get("BENCH_WINDOWS", "3"))
    setup = bench.build_bench_setup()
    base_opts = setup["compiler_options"]
    dtype = setup["dtype_name"] or "bf16"

    def prov(chain_steps, batch, extra_flags=None):
        p = provenance_fields(
            mesh=setup["mesh_spec"], dtype=dtype,
            chain_steps=chain_steps, batch=batch,
        )
        if extra_flags:
            # Stamp the EFFECTIVE flags: the sweep applies them per-compile
            # (compiler_options), but the provenance must say what the
            # executable actually ran under.
            p["xla_flags"] = (p["xla_flags"] + " " + extra_flags).strip()
        return p

    print(f"autotune: baseline {setup['model_name']} batch={setup['batch']} "
          f"chain_steps={steps} (BENCH_* env)")
    meas, _ = autotune_lib.measure_chained_step(
        setup["engine"], setup["state"], setup["gbatch"],
        chain_steps=steps, windows=windows, compiler_options=base_opts,
    )
    meas["provenance"] = prov(steps, setup["batch"])
    baseline = _result("baseline", {"chain_steps": steps}, meas)

    results = []
    for cand in _load_candidates(args.candidates):
        cs = int(cand.knobs.get("chain_steps", steps))
        flags = cand.knobs.get("xla_flags")
        opts = dict(base_opts or {})
        if flags:
            opts.update(xla_flag_options(flags))
        cand_setup = setup
        if cand.knobs.get("pallas") is not None:
            # The pallas knob changes the MODEL, not the compile: rebuild
            # the whole setup with BENCH_PALLAS so the candidate measures
            # the program a PALLAS=1 entry would run.
            saved = os.environ.get("BENCH_PALLAS")
            os.environ["BENCH_PALLAS"] = "1" if cand.knobs["pallas"] else "0"
            try:
                cand_setup = bench.build_bench_setup()
            finally:
                if saved is None:
                    os.environ.pop("BENCH_PALLAS", None)
                else:
                    os.environ["BENCH_PALLAS"] = saved
        print(f"autotune: measuring {cand.name} {cand.knobs}")
        try:
            meas, _ = autotune_lib.measure_chained_step(
                cand_setup["engine"], cand_setup["state"], cand_setup["gbatch"],
                chain_steps=cs, windows=windows, compiler_options=opts or None,
            )
        except Exception as e:  # noqa: BLE001 — a candidate that cannot
            # compile/run is reported and skipped; the sweep continues.
            print(f"autotune: {cand.name} failed ({e}) — skipped", file=sys.stderr)
            continue
        meas["provenance"] = prov(cs, cand_setup["batch"], extra_flags=flags)
        results.append(_result(cand.name, cand.knobs, meas, cand.note))

    report = autotune_lib.rank_candidates(baseline, results)
    report["workload"] = {
        "model": setup["model_name"], "batch": setup["batch"],
        "image_size": setup["image_size"], "dtype": dtype,
        "steps": steps, "windows": windows,
    }
    _print_report(report)
    if args.emit:
        autotune_lib.emit_tuned(args.emit, report)
        print(f"autotune: report written to {args.emit}")
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--self-test", action="store_true",
                        help="tiny CPU sweep with teeth + refusal checks "
                             "(the verify.sh stage)")
    parser.add_argument("--inject-slowdown", type=float, default=3.0,
                        metavar="F",
                        help="self-test seam: de-tune the baseline by F after "
                             "measurement (default 3.0)")
    parser.add_argument("--candidates", default=None, metavar="FILE",
                        help="JSON candidate list overriding the built-in "
                             "space ([{name, knobs, note}, ...])")
    parser.add_argument("--emit", default=None, metavar="PATH",
                        help="write the full report (TUNED.json) here")
    args = parser.parse_args()
    if args.self_test:
        import jax

        jax.config.update("jax_platforms", "cpu")  # explicit: a CPU harness
        return self_test(args.inject_slowdown)
    return run_sweep(args)


if __name__ == "__main__":
    sys.exit(main())
