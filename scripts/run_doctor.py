#!/usr/bin/env python
"""Run doctor — automated bottleneck diagnosis for a training run (ISSUE 13).

Reads a run directory's telemetry (``<run_dir>/telemetry/events.jsonl``,
the Trainer's flight log) and prints a **ranked, machine-checkable
diagnosis** — one of ``compile_bound`` / ``data_bound`` /
``checkpoint_stall`` / ``straggler`` / ``comm_heavy`` / ``healthy`` — each
verdict carrying the evidence rows (steady-state goodput fractions,
event-log line numbers, timeline track refs) that justify it. The rules
live in ``telemetry/doctor.py`` and are the SAME rules the trainer
projects live into the epoch-end ``doctor/*`` TensorBoard scalars.

Usage::

    python scripts/run_doctor.py <run_dir>            # diagnose
    python scripts/run_doctor.py <run_dir> --json     # machine-readable
    python scripts/run_doctor.py <run_dir> --timeline # + export the
                                                      #   Perfetto trace
    python scripts/run_doctor.py <run_dir> --events E # append a
                                                      #   `run_doctor` JSONL record
    python scripts/run_doctor.py --self-test          # CI gate (below)

``--self-test`` (the verify.sh stage; the perf-gate injected-regression
pattern): trains four short real sklearn-digits runs — a clean twin plus
three with a KNOWN bottleneck injected through existing seams — and
asserts the doctor names each culprit:

* **clean**            -> ``healthy`` (also: its exported timeline must be
  valid trace-event JSON whose goodput spans re-derive the meter's
  fractions within epsilon);
* **data-bound**       -> the ``ShardedLoader.load_delay_s`` seam starves
  the step loop (the perf gate's ``--inject-data-wait`` seam);
* **checkpoint-stall** -> the async saver's ``commit_delay_s`` chaos seam
  backs up the committer until the run stalls on its own saves;
* **hung/straggler**   -> ``FaultPlan("hang")`` injects host-side step
  hangs; the step-time detector fires and the doctor attributes it.

Exit codes: 0 diagnosis produced / self-test passed, 1 self-test
misdiagnosis, 2 no event log at the given path.
"""

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from distributed_training_pytorch_tpu.telemetry import doctor as doctor_lib  # noqa: E402
from distributed_training_pytorch_tpu.telemetry import timeline as timeline_lib  # noqa: E402
from distributed_training_pytorch_tpu.telemetry.events import load_run_events  # noqa: E402


def diagnose_run(run_dir: str):
    # The ONE shared reader (telemetry.events.EventFollower) — the same
    # parse the streaming monitor tails with (ISSUE 15).
    events = load_run_events(run_dir)
    return doctor_lib.diagnose(events)


def _self_test_trainer(tmp: str, **kw):
    """A small real-digits trainer with injection knobs: ``load_delay_s``
    (loader seam), ``commit_delay_s`` (async committer seam), plus any
    Trainer kwargs. Shared with ``scripts/perf_gate.py --data-wait`` — the
    gate's ceiling and the doctor's verdicts measure the SAME workload
    through the same steady-fraction definition, so they cannot drift.

    The net is a small conv (not a Dense toy) ON PURPOSE: its per-step
    wall (~15ms CPU) is large against the fixed per-batch fetch and
    per-save costs, so the healthy twin's steady-state fractions look
    like a real run's (productive-dominated) instead of being swamped by
    micro-run overhead that would read as a bottleneck."""
    import numpy as np
    import optax
    from flax import linen as nn

    from distributed_training_pytorch_tpu.data import ArrayDataSource
    from distributed_training_pytorch_tpu.ops import cross_entropy_loss
    from distributed_training_pytorch_tpu.trainer import Trainer

    load_delay_s = kw.pop("load_delay_s", 0.0)
    commit_delay_s = kw.pop("commit_delay_s", 0.0)

    class DoctorNet(nn.Module):
        @nn.compact
        def __call__(self, x, *, train: bool = False):
            x = nn.relu(nn.Conv(16, (3, 3))(x))
            x = nn.relu(nn.Conv(32, (3, 3))(x))
            x = x.reshape(x.shape[0], -1)
            return nn.Dense(10)(x)

    class DoctorTrainer(Trainer):
        def build_train_dataset(self):
            from sklearn.datasets import load_digits

            digits = load_digits()
            return ArrayDataSource(
                image=(digits.images / 16.0).astype(np.float32)[..., None],
                label=digits.target.astype(np.int32),
            )

        def build_model(self):
            return DoctorNet()

        def build_criterion(self):
            def criterion(logits, batch):
                loss = cross_entropy_loss(logits, batch["label"])
                return loss, {"loss": loss}

            return criterion

        def build_optimizer(self, schedule):
            return optax.sgd(schedule, momentum=0.9)

        def build_scheduler(self):
            return 0.1

        def build_dataloader(self, dataset, phase="train"):
            loader = super().build_dataloader(dataset, phase)
            if load_delay_s:
                loader.load_delay_s = load_delay_s
            return loader

    defaults = dict(
        max_epoch=2,
        batch_size=128,
        save_folder=tmp,
        telemetry="on",
        chain_steps=2,
        log_every=4,
        num_workers=0,
        progress=False,
        have_validate=False,
        save_period=1,
        logger=type("Q", (), {"log": staticmethod(lambda *a, **k: None)})(),
    )
    defaults.update(kw)
    trainer = DoctorTrainer(**defaults)
    if commit_delay_s:
        trainer.saver.commit_delay_s = commit_delay_s
    return trainer


def self_test() -> int:
    import math
    import shutil
    import tempfile

    from distributed_training_pytorch_tpu.fault import FaultPlan
    from distributed_training_pytorch_tpu.telemetry import AnomalyDetector, Telemetry

    # (name, expected top verdict, injection kwargs). Injected runs turn
    # the anomaly detector off where it would double-report the injected
    # disease through a second signal (a starved loader also inflates
    # sync-to-sync window wall) — each run isolates ONE culprit.
    cases = [
        # clean: ONE async save with two epochs of overlap room after it
        # (save_period=3 on a 3-epoch run saves at epoch 0 only). A micro
        # run saving every tiny epoch honestly spends >20% of its steady
        # wall waiting on its own commits — that is checkpoint-stall, not
        # a misdiagnosis; the healthy twin keeps save cost in proportion.
        ("clean", "healthy", dict(max_epoch=3, save_period=3)),
        ("data-bound", "data_bound",
         dict(load_delay_s=0.05, telemetry=Telemetry(anomaly=None))),
        ("checkpoint-stall", "checkpoint_stall",
         dict(commit_delay_s=0.6, max_epoch=3, telemetry=Telemetry(anomaly=None))),
        # hang: chain_steps=1 — a chained run's fault windows fall back to
        # single-step executables never compiled in epoch 0, and that
        # late compile is a LEGITIMATE compile_bound signal that would
        # outrank the straggler verdict this case isolates.
        # hangs land in epoch 1's THIRD window (steps 8-11): the first two
        # clean windows finish the detector's warmup (epoch 0's windows
        # paid compile, so their step times are withheld from the EWMA —
        # the trainer's compile-window rule), and the hung window then
        # trips the step-time detector against a true steady baseline.
        ("hung-straggler", "straggler",
         dict(fault_plan=FaultPlan()
              .add("hang", epoch=1, step=8, payload=0.4)
              .add("hang", epoch=1, step=9, payload=0.4)
              .add("hang", epoch=1, step=10, payload=0.4)
              .add("hang", epoch=1, step=11, payload=0.4),
              chain_steps=1,
              telemetry=Telemetry(anomaly=AnomalyDetector(warmup=2)))),
    ]
    failures = []
    for name, expected, kw in cases:
        tmp = tempfile.mkdtemp(prefix=f"run_doctor_{name}_")
        try:
            trainer = _self_test_trainer(tmp, **kw)
            trainer.train()
            diagnosis = diagnose_run(tmp)
            verdict = diagnosis.verdict
            status = "ok" if verdict == expected else "MISDIAGNOSIS"
            print(f"run_doctor self-test [{name}]: expected {expected!r}, "
                  f"got {verdict!r} — {status}")
            print(diagnosis.describe())
            if verdict != expected:
                failures.append(f"{name}: expected {expected!r}, got {verdict!r}")
            if name == "clean":
                # The timeline acceptance ride-along: export, re-parse with
                # stdlib json, and check the goodput spans re-derive the
                # meter's fractions (the spans ARE the partition).
                trace, path = timeline_lib.export_timeline(tmp)
                with open(path, encoding="utf-8") as f:
                    reparsed = json.load(f)
                derived = timeline_lib.span_bucket_seconds(reparsed)
                want = trainer.goodput.to_state()
                total_d, total_w = sum(derived.values()), sum(want.values())
                for bucket, w in want.items():
                    d = derived.get(bucket, 0.0)
                    if abs(d / max(total_d, 1e-12) - w / max(total_w, 1e-12)) > 1e-6:
                        failures.append(
                            f"timeline: {bucket} span fraction "
                            f"{d / max(total_d, 1e-12):.6f} != goodput fraction "
                            f"{w / max(total_w, 1e-12):.6f}")
                commits = [e for e in reparsed["traceEvents"]
                           if e.get("tid") == timeline_lib.TRACKS["committer"]
                           and e.get("ph") == "X"]
                if not commits:
                    failures.append("timeline: no committer-track spans for the "
                                    "async-checkpointing clean run")
                if not math.isclose(
                    sum(trainer.goodput.fractions().values()), 1.0, abs_tol=1e-6
                ):
                    failures.append("goodput fractions do not sum to 1")
                print(f"run_doctor self-test [clean]: timeline OK ({path})")
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    if failures:
        print("RUN DOCTOR SELF-TEST FAILED:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print("run_doctor self-test OK: healthy twin + 3 injected bottlenecks "
          "each correctly named")
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("run_dir", nargs="?", default=None,
                        help="run directory (the Trainer save_folder) or a "
                             "direct events.jsonl path")
    parser.add_argument("--json", action="store_true",
                        help="print the diagnosis as one JSON object")
    parser.add_argument("--timeline", action="store_true",
                        help="also export <run_dir>/telemetry/timeline.json "
                             "(Perfetto / chrome://tracing)")
    parser.add_argument("--events", default=None,
                        help="append a run_doctor record to this JSONL event log")
    parser.add_argument("--self-test", action="store_true",
                        help="CI gate: diagnose injected bottlenecks (verify.sh)")
    args = parser.parse_args()
    if args.self_test:
        return self_test()
    if args.run_dir is None:
        parser.error("run_dir is required (or use --self-test)")
    try:
        diagnosis = diagnose_run(args.run_dir)
    except FileNotFoundError as e:
        print(f"run_doctor: {e}", file=sys.stderr)
        return 2
    if args.timeline:
        _, path = timeline_lib.export_timeline(args.run_dir)
        print(f"run_doctor: timeline exported to {path} "
              "(open in ui.perfetto.dev or chrome://tracing)")
    if args.json:
        print(json.dumps(diagnosis.to_dict(), indent=2, sort_keys=True))
    else:
        print(f"run_doctor: {args.run_dir}")
        print(diagnosis.describe())
        print(f"verdict: {diagnosis.verdict}")
    if args.events:
        from distributed_training_pytorch_tpu.telemetry import EventLog
        from distributed_training_pytorch_tpu.telemetry.doctor import scalar_fields

        EventLog(args.events, process_index=0).emit(
            "run_doctor",
            run_dir=str(args.run_dir),
            verdict=diagnosis.verdict,
            healthy=diagnosis.healthy,
            scores=scalar_fields(diagnosis.signals),
            steady_fractions=doctor_lib.steady_fractions(
                diagnosis.signals.goodput_seconds or {}
            ),
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
