#!/usr/bin/env python
"""Perf-regression gate — step-time CI contract (ISSUE 6).

Nothing *failed* when step time slipped. This gate measures a step time and
compares it against the committed ``PERF_BASELINE.json`` (``profiling.gate``); a
regression past the relative tolerance is a nonzero exit, wired as a
``scripts/verify.sh`` stage next to the retrace/precision/telemetry gates.

Three modes:

* ``--data-wait`` (the verify stage's input-pipeline gate; ISSUE 13 /
  ROADMAP item 5) — trains a few epochs of the real sklearn-digits Trainer
  with telemetry on and gates the **steady-state ``data_wait`` goodput
  fraction** (``telemetry.doctor.steady_fractions`` — the same figure the
  run doctor's ``data_bound`` verdict reads, so the gate and the doctor
  cannot disagree) against a committed CEILING. ``--update`` records
  ``max(0.10, 2 x measured)`` as the ceiling — headroom over today's
  number, still a hard fail for a pipeline that becomes the bottleneck.
  Self-test seam: ``--inject-data-wait S`` sleeps S seconds in every
  batch's production path (the ``ShardedLoader.load_delay_s`` seam) —
  verify.sh asserts the gate FAILS with an injected starved pipeline.
* ``--quick`` (the verify stage; CPU-viable, ~seconds) — times a small
  fixed conv+dense workload through the REAL ``TrainEngine`` chained-step
  path, plus a fixed matmul *calibration* kernel on the same machine, and
  gates the **ratio** ``step_per_calib``. Absolute CPU milliseconds vary
  across dev machines; the ratio of two programs on one machine is stable,
  so one committed baseline serves every contributor (tolerance 50%:
  generous against scheduler noise, still a hard fail for the regressions
  that matter — an accidental per-window retrace is 10x, a lost chained
  dispatch path is 2-3x).
* default (no ``--quick``; needs a TPU and refuses to run without one) —
  times the headline ``BENCH_MODEL`` (vgg16) chained executable exactly as
  ``bench.py`` does and gates absolute ``step_ms`` (tolerance 8%). No
  baseline entry for today's chip is committed; the driver's
  ``PERF_LEDGER.jsonl`` is the record of speed.

The platform is whatever JAX finds (the baseline key carries it, e.g.
``quick-cpu``); ``scripts/verify.sh`` pins ``JAX_PLATFORMS=cpu`` for the two
CPU modes. Nothing here defaults to the CPU.

The update ritual (documented in docs/profiling.md): when a PR
*legitimately* changes step time (new fusion, different default), re-record
with ``--update`` in the same PR and say why in the PR body — the diff to
``PERF_BASELINE.json`` is the reviewable perf claim.

Self-test seam: ``--inject-slowdown F`` multiplies the measured step time by
``F`` after measurement (the measurement itself is untouched) — verify.sh
asserts the gate FAILS with an injected 3x regression, so the gate's teeth
are themselves tested on every run.

FAIL pre-diagnosis (ISSUE 14): quick mode traces one extra window after the
timed pairs and attaches the StepProfile category fractions to the
measurement; a ``--update``-recorded baseline carries them too, and a FAIL
prints the per-category attribution of its own measured-vs-baseline step_ms
delta — the SAME ``profiling.diff`` implementation ``scripts/run_compare.py``
uses (test-enforced: this script defines no attribution of its own).

Exit codes: 0 pass, 1 regression, 2 refused (``--update`` combined with
``--inject-slowdown`` — a poisoned baseline would mask real regressions),
3 no baseline entry for this key (record one with ``--update``), 4 baseline
present but unusable (malformed file or an entry that cannot gate this
measurement's metric — re-record with ``--update``).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distributed_training_pytorch_tpu.ops import cross_entropy_loss
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.profiling import diff as diff_lib
from distributed_training_pytorch_tpu.profiling import gate as gate_lib
from distributed_training_pytorch_tpu.train import TrainEngine, make_supervised_loss

QUICK_STEPS = 8
QUICK_TOLERANCE = 0.5
FULL_TOLERANCE = 0.08
# data_wait mode: the committed entry is a ceiling with built-in headroom
# (see measure_data_wait), so the gate tolerance can stay tight-ish.
DATA_WAIT_TOLERANCE = 0.25
DATA_WAIT_FLOOR_CEILING = 0.10


def _paired_ratio(run_step, run_calib, pairs: int = 5) -> tuple[float, float, float]:
    """Median of ADJACENT-pair ratios: each (workload, calibration) pair runs
    back to back, so machine load cancels within the pair — far more stable
    than best-of(workload)/best-of(calib), whose two minima can come from
    different interference regimes. Returns the MEDIAN pair's
    (ratio, step_s, calib_s) — all three figures come from the same pair, so
    the step_ms/calib_ms a baseline records reproduce its gated ratio exactly
    (a maintainer re-deriving the ratio from the committed numbers must not
    land on a different value)."""
    samples = []
    for _ in range(pairs):
        t0 = time.perf_counter()
        run_step()
        step_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        run_calib()
        calib_s = time.perf_counter() - t0
        samples.append((step_s / calib_s, step_s, calib_s))
    samples.sort(key=lambda s: s[0])
    return samples[len(samples) // 2]


def measure_quick() -> dict:
    """The CPU-viable measurement: a fixed conv+dense train step through the
    real chained-engine path, normalized by a fixed matmul calibration
    kernel. Warmup (compile) excluded from both."""
    from flax import linen as nn

    class GateNet(nn.Module):
        @nn.compact
        def __call__(self, x, *, train: bool = False):
            x = nn.relu(nn.Conv(8, (3, 3))(x))
            x = nn.relu(nn.Conv(16, (3, 3), strides=(2, 2))(x))
            x = x.reshape(x.shape[0], -1)
            return nn.Dense(10)(x)

    def criterion(logits, batch):
        loss = cross_entropy_loss(logits, batch["label"])
        return loss, {"loss": loss}

    model = GateNet()
    engine = TrainEngine(
        make_supervised_loss(model, criterion),
        optax.sgd(0.05, momentum=0.9),
        mesh_lib.create_mesh(),
    )
    rng = np.random.RandomState(0)
    batch = engine.shard_batch(
        {
            "image": rng.randn(64, 16, 16, 3).astype(np.float32),
            "label": rng.randint(0, 10, size=(64,)).astype(np.int32),
        }
    )
    state = engine.init_state(
        jax.random.key(0), lambda r: model.init(r, jnp.zeros((1, 16, 16, 3)))
    )
    compiled = engine.compile_chained_train_steps(state, batch, QUICK_STEPS)

    def run_window():
        nonlocal state
        state, metrics = compiled(state, batch)
        _ = float(metrics["loss"])

    # Calibration kernel: fixed matmul chain, jitted once — pure machine
    # speed, no framework surface, so the step/calib ratio cancels the
    # machine and isolates the framework + XLA program.
    w = jnp.asarray(rng.randn(384, 384).astype(np.float32) * 0.05)

    @jax.jit
    def calib(x):
        for _ in range(8):
            x = jnp.tanh(x @ w)
        return x

    x0 = jnp.ones((384, 384), jnp.float32)
    run_window()  # warmup: first dispatch pays one-time setup
    jax.block_until_ready(calib(x0))  # compile
    ratio, step_s, calib_s = _paired_ratio(
        run_window, lambda: jax.block_until_ready(calib(x0))
    )

    measurement = {
        "workload": "gatenet-conv16x16-b64-chain8",
        "platform": jax.devices()[0].platform,
        "steps": QUICK_STEPS,
        "step_ms": round(step_s / QUICK_STEPS * 1e3, 4),
        "calib_ms": round(calib_s * 1e3, 4),
        "step_per_calib": round(ratio / QUICK_STEPS, 4),
    }
    # Category capture (ISSUE 14): trace ONE extra window of the exact
    # workload AFTER the timed pairs (the trace gates nothing it measures)
    # and attach the StepProfile category fractions. A baseline recorded
    # with --update then carries them, and a later FAIL arrives
    # pre-diagnosed — the attribution of its own measured-vs-baseline
    # step_ms delta, through the SAME profiling.diff implementation
    # run_compare uses (test-enforced). Degrades to an unattributed
    # measurement on any capture/analysis failure.
    import shutil
    import tempfile

    from distributed_training_pytorch_tpu import profiling as profiling_lib

    prof_dir = tempfile.mkdtemp(prefix="perf_gate_prof_")
    try:
        with profiling_lib.trace(prof_dir):
            run_window()
        prof = profiling_lib.analyze_trace(prof_dir, steps=QUICK_STEPS)
        measurement["categories"] = {
            k: round(v, 4) for k, v in prof.categories.items() if v
        }
    except (ValueError, FileNotFoundError, OSError, RuntimeError) as e:
        print(f"perf_gate: category capture failed ({e}) — a FAIL against "
              "this measurement will be unattributed", file=sys.stderr)
    finally:
        shutil.rmtree(prof_dir, ignore_errors=True)
    return measurement


def measure_data_wait(inject_delay_s: float | None = None) -> dict:
    """The input-pipeline measurement: a short real-Trainer digits run with
    telemetry on; the gated figure is the steady-state ``data_wait``
    goodput fraction (``telemetry.doctor.steady_fractions`` — compile /
    restart / overlapped-commit wall excluded from the denominator, so a
    short run's XLA warmup cannot dilute a starved pipeline). The workload
    is ``scripts/run_doctor.py``'s self-test harness — the gate's ceiling
    and the doctor's ``data_bound`` verdict measure the same program
    through the same fraction definition, so they cannot drift."""
    import shutil
    import tempfile

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import run_doctor

    from distributed_training_pytorch_tpu.telemetry import Telemetry
    from distributed_training_pytorch_tpu.telemetry import doctor as doctor_lib

    tmp = tempfile.mkdtemp(prefix="perf_gate_data_wait_")
    try:
        trainer = run_doctor._self_test_trainer(
            tmp,
            load_delay_s=float(inject_delay_s or 0.0),
            telemetry=Telemetry(anomaly=None, mfu=False),
            save_period=None,  # the gate measures the pipeline, not saves
        )
        trainer.train()
        seconds = trainer.goodput.to_state()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    steady = doctor_lib.steady_fractions(seconds)
    return {
        "workload": "digits-conv-b128-chain2",
        "platform": jax.devices()[0].platform,
        # max vs epsilon: gate.check requires measured > 0, and a pipeline
        # this healthy is a pass at any positive ceiling.
        "data_wait_frac": round(max(steady["data_wait"], 1e-6), 4),
        "data_wait_s": round(seconds["data_wait"], 4),
        "injected_delay_s": inject_delay_s or 0,
    }


def measure_full() -> dict:
    """The bench-host measurement: the headline BENCH_MODEL chained
    executable, timed with bench.py's own window protocol (same env knobs),
    gated on absolute step_ms."""
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"perf_gate: full mode gates device step time and found platform "
            f"{platform!r}, not a TPU (--quick / --data-wait are the CPU modes)"
        )
    import bench

    from distributed_training_pytorch_tpu.utils.tpu import enable_fast_rng

    enable_fast_rng()
    setup = bench.build_bench_setup()
    steps = int(os.environ.get("BENCH_STEPS", "10"))
    windows = int(os.environ.get("BENCH_WINDOWS", "6"))
    compiled = setup["engine"].compile_chained_train_steps(
        setup["state"], setup["gbatch"], steps,
        compiler_options=setup["compiler_options"],
    )
    state, dt = bench._time_windows(
        lambda st: compiled(st, setup["gbatch"]), setup["state"], steps, windows,
        os.environ.get("BENCH_REDUCE", "min"),
    )
    return {
        "workload": setup["model_name"],
        "platform": jax.devices()[0].platform,
        "batch": setup["batch"],
        "steps": steps,
        "step_ms": round(dt * 1e3, 4),
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--quick", action="store_true",
                        help="CPU-viable calibrated-ratio mode (the verify stage)")
    parser.add_argument("--data-wait", action="store_true",
                        help="gate the steady-state data_wait goodput fraction "
                             "of a real digits Trainer run against the "
                             "committed ceiling (ROADMAP item 5)")
    parser.add_argument("--inject-data-wait", type=float, default=None, metavar="S",
                        help="self-test seam: sleep S seconds per produced "
                             "batch (loader load_delay_s) before measuring")
    parser.add_argument("--baseline", default=gate_lib.DEFAULT_BASELINE_PATH,
                        help="baseline JSON path (default: repo PERF_BASELINE.json)")
    parser.add_argument("--tolerance", type=float, default=None,
                        help="relative tolerance override (e.g. 0.5 = +50%%)")
    parser.add_argument("--update", action="store_true",
                        help="record this measurement as the new baseline entry")
    parser.add_argument("--inject-slowdown", type=float, default=None, metavar="F",
                        help="self-test seam: multiply measured step time by F")
    parser.add_argument("--events", default=None,
                        help="append a perf_gate record to this JSONL event log")
    args = parser.parse_args()
    if args.update and (args.inject_slowdown or args.inject_data_wait):
        print("perf_gate: refusing --update with an injection seam "
              "(a poisoned baseline would mask real regressions)")
        return 2
    if args.tolerance is not None and args.tolerance <= 0:
        parser.error("--tolerance must be > 0 (a zero-tolerance gate would "
                     "fail on measurement noise alone)")
    if args.data_wait and args.quick:
        parser.error("--data-wait and --quick are distinct measurements — "
                     "run them as separate invocations (verify.sh does)")
    if args.inject_data_wait and not args.data_wait:
        parser.error("--inject-data-wait only applies to --data-wait mode")
    if args.data_wait and args.inject_slowdown:
        parser.error("--inject-slowdown multiplies step time; the data-wait "
                     "measurement has none — use --inject-data-wait")

    if args.data_wait:
        if args.inject_data_wait:
            print(f"perf_gate: SELF-TEST — injecting a {args.inject_data_wait}s "
                  "per-batch loader sleep (the gate below must fail)")
        measurement = measure_data_wait(args.inject_data_wait)
        key = "data-wait-" + measurement["platform"]
    else:
        measurement = measure_quick() if args.quick else measure_full()
        key = ("quick-" if args.quick else f"{measurement['workload']}-") + measurement["platform"]
    if args.inject_slowdown:
        factor = float(args.inject_slowdown)
        measurement["step_ms"] = round(measurement["step_ms"] * factor, 4)
        if "step_per_calib" in measurement:
            measurement["step_per_calib"] = round(
                measurement["step_per_calib"] * factor, 4
            )
        measurement["injected_slowdown"] = factor
        print(f"perf_gate: SELF-TEST — injected x{factor} slowdown into the "
              "measurement (the gate below must fail)")
    print(f"perf_gate: {key}: " + json.dumps(measurement))

    if args.data_wait:
        default_tol = DATA_WAIT_TOLERANCE
    else:
        default_tol = QUICK_TOLERANCE if args.quick else FULL_TOLERANCE
    if args.update and args.data_wait:
        # The entry is a CEILING, not the measurement: record headroom over
        # today's number so scheduler noise on a healthy pipeline never
        # fails the gate, while a pipeline that becomes the bottleneck
        # (fraction 2x+ over healthy) still does. The raw measurement is
        # kept alongside as the reviewable claim.
        measurement = dict(
            measurement,
            measured_data_wait_frac=measurement["data_wait_frac"],
            data_wait_frac=round(
                max(DATA_WAIT_FLOOR_CEILING, 2 * measurement["data_wait_frac"]), 4
            ),
        )
        print(f"perf_gate: recording data_wait ceiling "
              f"{measurement['data_wait_frac']} (measured "
              f"{measurement['measured_data_wait_frac']})")
    if args.update:
        if args.tolerance is not None:
            tol = args.tolerance
        else:
            # preserve a curated per-entry tolerance across re-records; the
            # mode default applies only to entries that never had one
            try:
                existing = gate_lib.load_baseline(args.baseline).get("tolerance", {})
            except (FileNotFoundError, ValueError):
                existing = {}
            tol = existing.get(key, default_tol)
        gate_lib.update_baseline(args.baseline, key, measurement, tolerance=tol)
        print(f"perf_gate: baseline entry {key!r} recorded in {args.baseline} — "
              "commit the diff with a sentence on why perf legitimately changed")
        return 0

    try:
        baseline = gate_lib.load_baseline(args.baseline)
        result = gate_lib.evaluate(
            baseline, key, measurement,
            tolerance=args.tolerance, default_tolerance=default_tol,
        )
    except (FileNotFoundError, KeyError) as e:
        print(f"perf_gate: NO BASELINE — {e}")
        return 3
    except ValueError as e:
        print(f"perf_gate: BAD BASELINE — {e}")
        return 4
    print("perf_gate: " + result.describe())
    attribution = None
    if not result.passed:
        # FAIL upgrade (ISSUE 14): pre-diagnose the regression — attribute
        # the measured-vs-baseline step_ms delta per category through the
        # ONE profiling.diff implementation run_compare uses.
        attribution = diff_lib.attribute_entry_delta(
            baseline["entries"].get(key, {}), measurement
        )
        if attribution:
            print("perf_gate: FAIL attribution (step_ms delta by category): "
                  + diff_lib.describe_rows(attribution))
        elif args.quick:
            print("perf_gate: FAIL unattributed — the baseline entry or this "
                  "measurement lacks `categories`; re-record with --update so "
                  "future failures arrive pre-diagnosed (docs/profiling.md)")
        elif not args.data_wait:
            # Full mode records no category capture (only measure_quick
            # traces a window), so the --update ritual cannot attribute it —
            # point at the bench-side instrument instead.
            print("perf_gate: FAIL unattributed — full mode captures no "
                  "categories; run `BENCH_PROFILE=1 python bench.py` "
                  "before/after and `scripts/run_compare.py` for the "
                  "attribution (docs/profiling.md)")
    if args.events:
        from distributed_training_pytorch_tpu.telemetry import EventLog

        EventLog(args.events, process_index=0).emit(
            "perf_gate",
            key=key,
            metric=result.metric,
            measured=result.measured,
            baseline=result.baseline,
            ratio=result.ratio,
            tolerance=result.tolerance,
            passed=result.passed,
            attribution=(
                [r.to_dict() for r in attribution] if attribution else None
            ),
        )
    return 0 if result.passed else 1


if __name__ == "__main__":
    sys.exit(main())
