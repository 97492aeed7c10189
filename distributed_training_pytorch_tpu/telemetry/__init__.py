"""Telemetry subsystem (ISSUE 4): unified run observability.

Production training treats goodput and MFU as first-class run metrics; this
package assembles the raw ingredients the other subsystems already produce
(``utils.hlo_flops`` cost analysis, ``TrainEngine.trace_counts``, fault /
preemption events, loss-scale state) into one surface:

* :mod:`~.events`  — structured JSONL event log (run start/end, compile,
  checkpoint save/restore, preemption, fault injection, loss-scale backoff,
  anomaly) with monotonic timestamps and rank-0 file ownership;
* :mod:`~.goodput` — wall time partitioned into productive-step / compile /
  data-wait / checkpoint / restart-rollback buckets, cumulative across
  kill/resume (counters ride checkpoint meta);
* :mod:`~.stats`   — on-device train-health statistics (grad/param norm,
  update ratio, nonfinite flag) computed inside the compiled step: zero
  extra host syncs, zero retraces, bit-exact chained windows;
* :mod:`~.mfu`     — MFU + roofline fields from cost analysis and measured
  step time, shared by ``bench.py`` and the trainer's per-window reports;
* :mod:`~.anomaly` — host-side detectors (loss spike / grad explosion /
  step-time regression / memory growth / straggler) that run only at
  existing sync points;
* :mod:`~.straggler` — per-chip arrival-skew sampling at the ``log_every``
  syncs (the PR 8 live-memory-skew pattern applied to time), feeding the
  ``straggler`` anomaly kind and the doctor's attribution (ISSUE 13);
* :mod:`~.timeline` — merges a run directory's event log into one
  Chrome/Perfetto trace (windows, epochs, the goodput partition as spans,
  checkpoint snapshot/commit lifecycles with the async committer as its
  own track, profile captures, narrative markers);
* :mod:`~.doctor`   — the ranked bottleneck diagnosis (compile-bound /
  data-bound / checkpoint-stall / straggler / comm-heavy / healthy) shared
  by ``scripts/run_doctor.py`` and the epoch-end ``doctor/*`` scalars;
* :mod:`~.provenance` — the ONE provenance record (git SHA, jax/jaxlib,
  ``XLA_FLAGS``, mesh/dtype/chain_steps) stamped on bench lines, dryrun
  entries, and ``run_start`` events so comparisons are attributable
  (ISSUE 14);
* :mod:`~.history`  — a directory of ``BENCH_r*``/``MULTICHIP_r*`` round
  files as per-metric trajectories with flat-streak + regression detection
  (``scripts/bench_history.py``);
* :mod:`~.monitor`  — the live-operations layer (ISSUE 15): a streaming
  doctor tailing events.jsonl through the shared
  :class:`~.events.EventFollower`, re-deriving the doctor's verdicts
  online plus the liveness kinds (``stale_heartbeat``/``dead`` from the
  heartbeat contract), with debounced :class:`~.monitor.AlertConfig`
  rules (``scripts/run_monitor.py``: live view, fleet table, CI exit
  codes);
* :mod:`~.exporter` — the in-process rank-0 HTTP status endpoint
  (``Telemetry(export_port=...)``): ``/status`` JSON + ``/metrics``
  Prometheus text served from atomically-swapped snapshots of the live
  trainer counters — never blocks the hot loop, degrades to a warning
  when the port is taken;
* :mod:`~.controller` — the closed-loop policy engine (ISSUE 16): per-run
  state machines turning :class:`~.monitor.MonitorStatus` streams into a
  bounded, debounced, budgeted remediation-action catalog (restart /
  exclude-and-replan / knob tune with an A/B-judged keep-or-revert),
  executed and audited by ``scripts/fleet_controller.py``.

Wire-up: ``Trainer(telemetry="on")`` (or a :class:`Telemetry` instance for
knobs); entries honor ``TELEMETRY=1``; see ``docs/observability.md``.
"""

from __future__ import annotations

import dataclasses

from distributed_training_pytorch_tpu.telemetry.anomaly import (  # noqa: F401
    Anomaly,
    AnomalyDetector,
    AnomalyError,
)
from distributed_training_pytorch_tpu.telemetry.events import (  # noqa: F401
    SCHEMA_VERSION,
    EventFollower,
    EventLog,
    load_run_events,
    read_events,
)
from distributed_training_pytorch_tpu.telemetry.goodput import (  # noqa: F401
    BUCKETS,
    GoodputMeter,
)
from distributed_training_pytorch_tpu.telemetry.mfu import (  # noqa: F401
    PEAK_FLOPS,
    device_peak_flops,
    mfu_value,
    window_report,
)
from distributed_training_pytorch_tpu.telemetry.stats import (  # noqa: F401
    STAT_KEYS,
    train_health_stats,
)

__all__ = [
    "Anomaly",
    "AnomalyDetector",
    "AnomalyError",
    "BUCKETS",
    "EventFollower",
    "EventLog",
    "GoodputMeter",
    "PEAK_FLOPS",
    "SCHEMA_VERSION",
    "STAT_KEYS",
    "Telemetry",
    "device_peak_flops",
    "load_run_events",
    "mfu_value",
    "read_events",
    "resolve_telemetry",
    "train_health_stats",
    "window_report",
]

# timeline/doctor/straggler/history/provenance are imported as submodules on demand
# (``from distributed_training_pytorch_tpu.telemetry import timeline``) —
# the trainer hot path must not pay their import, and the package root
# stays import-light for the historical program.


@dataclasses.dataclass
class Telemetry:
    """The ``Trainer(telemetry=...)`` configuration bundle.

    * ``events_path``    — JSONL event-log path (None = the trainer default,
      ``<save_folder>/telemetry/events.jsonl``);
    * ``stats``          — on-device train-health stats in every step's
      metrics (``telemetry.stats``);
    * ``goodput``        — wall-time bucket accounting + checkpoint carry;
    * ``mfu``            — per-window MFU. When ``flops_per_step`` is None
      the trainer probes XLA's per-step FLOP estimate once via
      ``TrainEngine.step_cost_analysis`` at the end of the first trained
      epoch — one extra (off-hot-path) XLA compile that never touches the
      dispatch executables or their trace counts;
    * ``flops_per_step`` — analytic per-step FLOP override (skips the probe;
      e.g. ``bench.vgg16_train_flops_per_image(model, size) * batch``);
    * ``anomaly``        — ``"warn"`` (default) | ``"raise"`` | ``None`` |
      an :class:`AnomalyDetector` instance with custom thresholds;
    * ``memory``         — live device-memory fields (``live_bytes`` /
      ``peak_bytes`` from ``memory.live``, plus per-chip skew on multi-chip
      hosts) on the per-window records, read at the existing ``log_every``
      host syncs (a PJRT allocator query — zero extra device syncs), and
      fed to the anomaly detector's ``memory_growth`` leak check. Degrades
      to absent fields on backends without ``memory_stats`` (CPU);
    * ``straggler``      — per-chip arrival-skew fields
      (``chip_wall_ms_min/max``, ``chip_skew_ms``, ``slowest_chip``,
      ``straggler_ratio`` from ``telemetry.straggler``) on the per-window
      records, sampled at the same ``log_every`` host syncs (the sync was
      about to block on every chip anyway — zero extra device syncs), and
      fed to the anomaly detector's floor-baselined ``straggler`` check.
      Degrades to absent fields on single-chip hosts.
    * ``heartbeat_every_s`` — the liveness pulse (ISSUE 15,
      docs/observability.md "Live monitoring"): a cheap ``heartbeat``
      record at the existing ``log_every`` syncs and — when the
      ``step_timeout`` watchdog is armed — from its patrol thread between
      syncs, debounced to this cadence so an external monitor can tell
      *training / hung / dead* apart from file mtime + record content
      alone. ``0`` disables heartbeats (the pre-ISSUE-15 record stream).
    * ``export_port``    — rank-0 in-process HTTP status endpoint
      (``telemetry.exporter``): ``/status`` JSON and ``/metrics``
      Prometheus text from the live trainer counters. ``None`` (default)
      serves nothing; a taken port degrades to a warning, and the run
      stays bit-exact (params + trace_counts) with the exporter off
      (test-enforced). ``0`` binds an ephemeral port (tests) —
      ``trainer.exporter.port`` reads it back.
    """

    events_path: str | None = None
    stats: bool = True
    goodput: bool = True
    mfu: bool = True
    flops_per_step: float | None = None
    anomaly: AnomalyDetector | str | None = "warn"
    memory: bool = True
    straggler: bool = True
    heartbeat_every_s: float = 30.0
    export_port: int | None = None

    def resolve_anomaly(self) -> AnomalyDetector | None:
        if self.anomaly is None:
            return None
        if isinstance(self.anomaly, AnomalyDetector):
            return self.anomaly
        return AnomalyDetector(action=str(self.anomaly))


def resolve_telemetry(spec) -> Telemetry | None:
    """Trainer-knob resolution: ``None``/``False`` = off (the historical
    program, byte-for-byte); ``True``/``"on"``/``"1"`` = defaults; a
    :class:`Telemetry` instance passes through."""
    if spec is None or spec is False:
        return None
    if spec is True:
        return Telemetry()
    if isinstance(spec, str):
        key = spec.lower()
        if key in ("on", "1", "true", "default"):
            return Telemetry()
        if key in ("off", "0", "false", "none"):
            return None
        raise ValueError(f"unknown telemetry spec {spec!r} (use 'on', 'off', or a Telemetry)")
    if isinstance(spec, Telemetry):
        return spec
    raise TypeError(f"telemetry must be None, bool, str, or Telemetry, got {type(spec)}")
