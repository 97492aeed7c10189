"""Everything a training run tells telemetry, behind one object.

``Trainer`` builds one :class:`RunTelemetry` and calls it at the boundaries
its loops already have, with facts it already holds: counts, host metrics,
times, the engine's trace total. What lives here and nowhere else: the event
vocabulary, the goodput bucket names, the heartbeat debounce, the status
snapshot's swap, and the rule that a window (or epoch) which compiled is
withheld from the anomaly detector's step-time baseline.

With ``telemetry=None`` the same class is built disabled: ``events`` is a
no-op log, ``goodput`` is None, and every method returns at once — the run is
the program without telemetry. No method adds a device sync: the loop hands
over host values it fetched for its own sake (docs/observability.md).
"""

from __future__ import annotations

import os
import threading
import time

import jax

from distributed_training_pytorch_tpu.memory import window_memory_fields
from distributed_training_pytorch_tpu.telemetry import (
    AnomalyError,
    EventLog,
    GoodputMeter,
    resolve_telemetry,
)
from distributed_training_pytorch_tpu.telemetry import doctor, mfu, straggler
from distributed_training_pytorch_tpu.telemetry.events import claim_attempt

__all__ = ["RunTelemetry"]

_HEALTH_KEYS = ("loss", "ce_loss", "grad_norm", "update_ratio", "nonfinite")


class RunTelemetry:
    """One training run's telemetry state and the calls that feed it.

    Call order within ``Trainer.train()``: :meth:`run_start`; per epoch
    :meth:`epoch_start`, then per unit :meth:`fetched` → :meth:`unit_done`
    (with :meth:`sample_arrivals` + :meth:`log_sync` at each ``log_every``
    sync in between), then :meth:`drained` and :meth:`epoch_end`; finally
    :meth:`run_end`. ``traces`` is always ``sum(engine.trace_counts.values())``
    as the caller reads it at that moment.
    """

    def __init__(self, spec, *, save_folder: str, log, metrics_writer):
        config = self.config = resolve_telemetry(spec)
        self.enabled = config is not None
        self._save_folder = save_folder
        self._log = log
        self._writer = metrics_writer
        if self.enabled:
            self.events = EventLog(
                config.events_path or os.path.join(save_folder, "telemetry", "events.jsonl")
            )
            self.goodput = GoodputMeter() if config.goodput else None
            self.anomaly_detector = config.resolve_anomaly()
        else:
            self.events = EventLog(None)
            self.goodput = None
            self.anomaly_detector = None
        # Analytic count from the config, else the one-time probe's (probe_flops).
        self.flops_per_step = config.flops_per_step if self.enabled else None
        self.peak_flops = None  # the whole mesh's: set_mesh
        self._mesh = None
        self._mfu_probed = False
        # Restart generation of this run dir (telemetry/events.py:claim_attempt):
        # stamped on run_start / heartbeat records and checkpoint meta. 0 =
        # unclaimed (telemetry off, or not process 0).
        self.attempt = 0
        self._start_epoch = 0  # compiles in the attempt's first epoch are warm-up
        self._attempt_units = 0  # units executed in epochs already closed
        # The live doctor's inputs, counted where each fact is seen.
        self.anomaly_counts: dict[str, int] = {}
        self.hung_steps = 0
        self.late_compiles = 0
        self._straggler_on = self.enabled and config.straggler
        self._last_straggler: dict | None = None
        self._max_straggler_ratio: float | None = None
        self.last_step_ms = None
        self._last_scale_seen = None
        self._nonfinite_steps = 0
        # Heartbeats come from the loop's syncs and from the watchdog's patrol
        # thread; both pass ONE lock-guarded debounce, and the patrol thread
        # reads the last sync's progress fields as one dict swapped under it.
        self._heartbeat_every_s = (
            float(config.heartbeat_every_s or 0.0) if self.enabled else 0.0
        )
        self._hb_lock = threading.Lock()
        self._hb_last_emit = 0.0
        self._hb_fields: dict = {}
        # The exporter's HTTP threads read whichever complete dict `_status`
        # points at; a sync builds a fresh one and swaps the reference, so the
        # loop never shares mutable state with a scrape.
        self.exporter = None
        self._status: dict = {}
        # Per-epoch anchors (epoch_start): wall time and executed steps at the
        # last sync, and trace totals at the last sync and at the epoch's start.
        self._sync_time = 0.0
        self._sync_executed = 0
        self._sync_traces = 0
        self._epoch_traces = 0
        self._rollback_fetch = False

    # -- construction-time facts -------------------------------------------

    @property
    def stats(self) -> bool:
        """Whether the engine computes the on-device train-health stats."""
        return self.enabled and self.config.stats

    def set_mesh(self, mesh) -> None:
        """The mesh is chosen after the event log exists (the elastic resume
        peek reports through it), so the peak arrives here. None for a device
        with no published peak (the CPU): every ``mfu`` field is then absent."""
        self._mesh = mesh
        if self.enabled:
            chip_peak = mfu.device_peak_flops(mesh.devices.flat[0])
            self.peak_flops = None if chip_peak is None else chip_peak * mesh.devices.size

    def restored(self, meta: dict, seconds: float) -> None:
        """A checkpoint was restored in ``seconds``: continue the interrupted
        run's goodput counters (they ride checkpoint meta; json round-trips
        floats exactly) and book the restore itself as rollback."""
        if self.goodput is None:
            return
        saved = (meta.get("telemetry") or {}).get("goodput")
        if saved:
            self.goodput.load_state(saved)
        self.goodput.account("restart_rollback", seconds)

    def checkpoint_meta(self) -> dict | None:
        """What rides checkpoint meta: the cumulative goodput buckets and the
        attempt that wrote the checkpoint."""
        meta = {}
        if self.goodput is not None:
            meta["goodput"] = self.goodput.to_state()
        if self.attempt:
            meta["attempt"] = self.attempt
        return meta or None

    # -- run boundaries -----------------------------------------------------

    def run_start(
        self, *, epoch: int, max_epoch: int, step, resumed_step_in_epoch: int, batch_size: int,
        batch_replicas: int, chain_steps: int, compute_dtype: str, nonfinite_steps: int,
        shard_assignment: dict | None = None,
    ) -> None:
        """``step`` is ``state.step`` as it lies on the device: it is read
        only when the log is on (a run without telemetry pays no fetch)."""
        self._start_epoch = epoch
        self._nonfinite_steps = nonfinite_steps
        if self.goodput is not None:
            self.goodput.start()
        if self.events.enabled:
            from distributed_training_pytorch_tpu.ops import dispatch as _dispatch
            from distributed_training_pytorch_tpu.telemetry.provenance import provenance_fields

            self.attempt = claim_attempt(self._save_folder)
            mesh_axes = {str(k): int(v) for k, v in self._mesh.shape.items()}
            fields = dict(
                attempt=self.attempt, epoch=epoch, max_epoch=max_epoch, step=int(step),
                resumed_step_in_epoch=resumed_step_in_epoch, processes=jax.process_count(),
                devices=self._mesh.devices.size, mesh=mesh_axes, batch_replicas=batch_replicas,
                chain_steps=chain_steps, compute_dtype=compute_dtype,
            )
            if self.goodput is not None:
                # Zero on a cold start, the carried totals on a resume: the
                # timeline anchors this attempt's goodput spans here.
                fields["goodput_seconds"] = self.goodput.to_state()
            fields["provenance"] = provenance_fields(
                mesh=mesh_axes, dtype=compute_dtype, chain_steps=chain_steps, batch=batch_size
            )
            self.events.emit("run_start", **fields)
            if shard_assignment is not None:
                self.events.emit("shard_assignment", **shard_assignment)
            # ops/dispatch.py buffered the kernel_dispatch decisions made
            # while the model was built; they flush into this log now.
            _dispatch.set_event_sink(self.events.emit)
        if self.enabled and self.config.export_port is not None and jax.process_index() == 0:
            # Built per train() and closed in run_end. A taken port warns and
            # disables; it is never a reason training dies.
            from distributed_training_pytorch_tpu.telemetry.exporter import StatusExporter

            self.exporter = StatusExporter(
                lambda: self._status, self.config.export_port,
                log=lambda msg: self._log(msg, "warning"),
            )
        # A monitor that attaches before the first sync still finds a pulse,
        # and the exporter a snapshot. `units` counts this attempt's executed
        # units across epochs: a liveness marker must be monotone.
        self._attempt_units = 0
        self._pulse({"epoch": epoch, "step_in_epoch": resumed_step_in_epoch, "units": 0})

    def run_end(self, *, step, epoch: int, preempted: bool, nonfinite_steps: int) -> None:
        """``step``: as in :meth:`run_start`."""
        self._nonfinite_steps = nonfinite_steps
        if self.goodput is not None:
            self.goodput.stop()
        if self.events.enabled:
            from distributed_training_pytorch_tpu.ops import dispatch as _dispatch

            _dispatch.clear_event_sink()
            fields = dict(
                step=int(step), epoch=epoch, preempted=preempted, nonfinite_steps=nonfinite_steps
            )
            if self.goodput is not None:
                fields["goodput"] = self.goodput.goodput
                fields["goodput_seconds"] = self.goodput.to_state()
                fields["goodput_fractions"] = self.goodput.fractions()
            if self.anomaly_detector is not None:
                fields["anomalies"] = self.anomaly_detector.total_fired
            self.events.emit("run_end", **fields)
        # A scraper racing the teardown gets the terminal snapshot or a
        # refused connection, never a hang.
        self._update_status(epoch=epoch, phase="finished")
        if self.exporter is not None:
            self.exporter.close()
            self.exporter = None
        self.events.close()  # a re-entered train() reopens it (append)

    # -- the step loop ------------------------------------------------------

    def epoch_start(self, *, resumed: bool, traces: int) -> None:
        """Closes the epoch's preamble. ``resumed``: the first fetch replays
        the loader past batches already trained, which is rollback cost."""
        if not self.enabled:
            return
        if self.goodput is not None:
            self.goodput.tick("other")
        self._rollback_fetch = resumed
        self._sync_time, self._sync_executed = time.perf_counter(), 0
        self._sync_traces = self._epoch_traces = traces

    def fetched(self) -> None:
        """The loop has its next unit (or found the ring finished): everything
        since the previous tick was the wait for the input path."""
        if self.goodput is not None:
            self.goodput.tick("restart_rollback" if self._rollback_fetch else "data_wait")
        self._rollback_fetch = False

    def preflight_ran(self) -> None:
        """The memory preflight's abstract lowerings are XLA compile work."""
        if self.goodput is not None:
            self.goodput.tick("compile")

    def unit_done(self, traced: int, *, epoch: int, step_in_epoch: int) -> None:
        """A unit was dispatched. ``traced`` executables traced inside it: jit
        compiles synchronously inside the call, so such a unit's wall is
        compile time and every other unit's is productive."""
        if not self.enabled:
            return
        if self.goodput is not None:
            self.goodput.tick("compile" if traced else "productive_step")
        if traced:
            if epoch > self._start_epoch:
                # Past the attempt's first epoch a compile is a retrace: what
                # the doctor's compile_bound verdict keys on.
                self.late_compiles += 1
            self.events.emit(
                "compile", epoch=epoch, step_in_epoch=step_in_epoch, executables=traced
            )

    def sample_arrivals(self, metrics, *, fault_plan, epoch: int, step_in_epoch: int) -> dict:
        """Per-chip arrival skew of a unit's device-resident ``metrics``. Call
        BEFORE fetching them: the fetch blocks on every chip anyway, and
        sampling shard by shard first sees which chip it waits for. A
        scheduled ``slow_chip`` fault delays that device's arrival inside the
        sample (timing only); it is asked for here, at a sync, so that it never
        forces a chained window into single steps."""
        if not self._straggler_on:
            return {}
        slow = None
        if fault_plan is not None:
            slow = fault_plan.slow_chip((d.id for d in jax.local_devices()), epoch=epoch)
            if slow is not None:
                self.events.emit(
                    "fault_injection", kind="slow_chip", epoch=epoch,
                    step_in_epoch=step_in_epoch, device=slow[0], delay_ms=slow[1] * 1e3,
                )
        return straggler.sample_arrivals(metrics, slow_chip=slow)

    def log_sync(
        self, metrics: dict, arrivals: dict, *,
        epoch: int, step_in_epoch: int, executed: int, traces: int,
    ) -> None:
        """The ``log_every`` read-back happened: ``metrics`` are the newest
        step's host floats, ``arrivals`` what :meth:`sample_arrivals` gave,
        ``executed`` the epoch's steps so far."""
        if not self.enabled:
            return
        now = time.perf_counter()
        window_steps = executed - self._sync_executed
        window_s = now - self._sync_time
        self._sync_time, self._sync_executed = now, executed
        if window_steps <= 0:
            return
        report = mfu.window_report(
            window_steps, window_s,
            flops_per_step=self.flops_per_step, peak_flops=self.peak_flops,
        )
        self.last_step_ms = report["step_ms"]
        mem_fields = self._live_memory_fields()
        if arrivals:
            # Skew over this window's step time: the anomaly signal and the
            # doctor's attribution input.
            ratio = arrivals["straggler_ratio"] = straggler.ratio(
                arrivals["chip_skew_ms"], report["step_ms"]
            )
            self._last_straggler = arrivals
            if self._max_straggler_ratio is None or ratio > self._max_straggler_ratio:
                self._max_straggler_ratio = ratio
        self.events.emit(
            "window", epoch=epoch, step_in_epoch=step_in_epoch, **report, **mem_fields, **arrivals
        )
        progress = {
            "epoch": epoch,
            "step_in_epoch": step_in_epoch,
            "units": self._attempt_units + executed,
            "step_ms": report["step_ms"],
        }
        if mem_fields.get("live_bytes") is not None:
            progress["live_bytes"] = mem_fields["live_bytes"]
        extra = {
            "straggler_ratio": arrivals.get("straggler_ratio"),
            "loss_scale": metrics.get("loss_scale"),
            "loss": metrics.get("loss"),
        }
        self._pulse(progress, **mem_fields, **{k: v for k, v in extra.items() if v is not None})
        scale = metrics.get("loss_scale")
        if scale is not None:
            if self._last_scale_seen is not None and scale < self._last_scale_seen:
                self.events.emit(
                    "loss_scale_backoff", epoch=epoch, step_in_epoch=step_in_epoch,
                    from_scale=self._last_scale_seen, to_scale=scale,
                )
            self._last_scale_seen = scale
        compiled = traces > self._sync_traces
        self._sync_traces = traces
        self._observe(
            metrics, report, mem_fields, compiled=compiled, epoch=epoch,
            step_in_epoch=step_in_epoch, straggler_ratio=arrivals.get("straggler_ratio"),
        )

    def drained(self) -> None:
        """The epoch's one ``device_get`` returned: that wait was the device
        executing every step still in flight, so it is productive time."""
        if self.goodput is not None:
            self.goodput.tick("productive_step")

    def probe_flops(self, engine, state, abstract_batch, *, engine_step_runs: bool) -> None:
        """Once a run, XLA's own per-step FLOP count (``TrainEngine.
        step_cost_analysis``): one compile off the hot path that touches
        neither the dispatch executables nor ``trace_counts``. Skipped when
        the config gave a count or turned MFU off, before any batch's shapes
        are known, and when a ``train_step`` override means the engine's step
        is not the one running."""
        if (
            not self.enabled
            or not self.config.mfu
            or self._mfu_probed
            or self.flops_per_step is not None
            or abstract_batch is None
            or not engine_step_runs
        ):
            return
        self._mfu_probed = True
        if engine.accum_steps > 1:
            # cost_analysis may count the accumulation scan's body once, and a
            # silently wrong utilisation is worse than none.
            self._log(
                "telemetry: MFU probe skipped under grad accumulation "
                f"(accum_steps={engine.accum_steps}) — XLA may count the "
                "microbatch scan body once; pass Telemetry(flops_per_step=...) "
                "for MFU reporting",
                "warning",
            )
            return
        t0 = time.perf_counter()
        try:
            cost = engine.step_cost_analysis(state, abstract_batch)
        except Exception as e:  # noqa: BLE001 — telemetry must never kill a run
            self._log(f"telemetry: MFU probe failed ({e}) — per-window MFU disabled", "warning")
            return
        dt = time.perf_counter() - t0
        if self.goodput is not None:
            self.goodput.tick("compile")
        # cost_analysis() of a partitioned executable counts ONE device's
        # program and peak_flops is the whole mesh's, so the count is scaled
        # to the mesh (under tensor parallelism that over-counts a little; it
        # never under-counts by the device count).
        self.flops_per_step = (float(cost.get("flops", 0.0)) * self._mesh.devices.size) or None
        self.events.emit(
            "compile", kind="mfu_probe", seconds=dt, flops_per_step=self.flops_per_step
        )

    def epoch_end(
        self, epoch_metrics: dict, *, epoch: int, step_in_epoch: int, executed: int,
        wall_s: float, interrupted: bool, traces: int, nonfinite_steps: int,
    ) -> None:
        """``wall_s`` was closed before :meth:`probe_flops`, whose compile
        must not dilute the epoch's step time."""
        self._nonfinite_steps = nonfinite_steps
        if not self.enabled or not executed:
            return
        report = mfu.window_report(
            executed, wall_s, flops_per_step=self.flops_per_step, peak_flops=self.peak_flops
        )
        self.last_step_ms = report["step_ms"]
        health = {k: epoch_metrics[k] for k in _HEALTH_KEYS if k in epoch_metrics}
        mem_fields = self._live_memory_fields()
        epoch_fields = {}
        if self.goodput is not None:
            # The timeline turns consecutive snapshots into per-bucket spans;
            # the offline doctor reads the last one.
            epoch_fields["goodput_seconds"] = self.goodput.to_state()
        if self._last_straggler:
            epoch_fields["chip_skew_ms"] = self._last_straggler["chip_skew_ms"]
            epoch_fields["straggler_ratio"] = self._last_straggler["straggler_ratio"]
        self.events.emit(
            "epoch_end", epoch=epoch, wall_s=wall_s, interrupted=interrupted,
            **report, **health, **mem_fields, **epoch_fields,
        )
        self._attempt_units += executed
        progress = {
            "epoch": epoch,
            "step_in_epoch": step_in_epoch,
            "units": self._attempt_units,
            "step_ms": report["step_ms"],
        }
        self._pulse(progress, **mem_fields)
        self._observe(
            epoch_metrics, report, mem_fields, compiled=traces > self._epoch_traces,
            epoch=epoch, step_in_epoch=step_in_epoch,
        )

    def write_scalars(self, step) -> None:
        """TensorBoard, after an epoch: goodput fractions, step time and MFU,
        straggler skew, and the live doctor's per-verdict scores (>= 1.0 = over
        the line) by the rules the offline doctor applies to the event log.
        ``step``: as in :meth:`run_start`."""
        if not self.enabled:
            return
        step = int(step)
        if self.goodput is not None:
            self._writer.write(step, self.goodput.fractions(), prefix="goodput")
        if self.last_step_ms is not None:
            self._writer.write(step, self._step_time_fields(), prefix="telemetry")
        if self._last_straggler:
            skew = {
                "skew_ms": self._last_straggler["chip_skew_ms"],
                "ratio": self._last_straggler["straggler_ratio"],
            }
            self._writer.write(step, skew, prefix="straggler")
        self._writer.write(step, doctor.scalar_fields(self._doctor_signals()), prefix="doctor")

    # -- the watchdog's thread ----------------------------------------------

    def hung_step(self, timeout_s: float) -> None:
        self.hung_steps += 1
        self.events.emit("hung_step", timeout_s=timeout_s)

    @property
    def patrol_hook(self):
        """``StepWatchdog(on_patrol=)``: keeps the log pulsing from the
        watchdog's thread while the main thread is stuck inside a step, or
        None when no heartbeat can be written."""
        if self._heartbeat_every_s and self.events.enabled:
            return self._heartbeat_patrol
        return None

    def _heartbeat_patrol(self, since_progress_s: float) -> None:
        # Seconds since the last completed unit is what lets a monitor call the
        # run hung and not slow; the record still arriving tells hung from dead.
        self._emit_heartbeat("watchdog", since_progress_s=since_progress_s)

    # -- internals ----------------------------------------------------------

    def _step_time_fields(self) -> dict:
        fields = {"step_ms": self.last_step_ms}
        value = mfu.mfu_value(self.flops_per_step or 0.0, self.last_step_ms / 1e3, self.peak_flops)
        if value is not None:
            fields["mfu"] = value
        return fields

    def _live_memory_fields(self) -> dict:
        """``live_bytes`` / ``peak_bytes`` (and per-chip skew) from one
        allocator query; ``{}`` where the backend has no ``memory_stats``."""
        return window_memory_fields() if self.config.memory else {}

    def _doctor_signals(self) -> doctor.Signals:
        """What ``doctor.extract_signals`` would distill from this run's
        event log, read off the counters instead."""
        return doctor.Signals(
            goodput_seconds=self.goodput.to_state() if self.goodput else None,
            anomaly_counts=dict(self.anomaly_counts),
            hung_steps=self.hung_steps,
            max_straggler_ratio=self._max_straggler_ratio,
            late_compiles=self.late_compiles,
        )

    def _pulse(self, progress: dict, **status_extra) -> None:
        """At a sync: refresh the progress fields a patrol heartbeat reports
        (even when the pulse itself debounces, it must report the newest
        step), pulse, and publish a status snapshot."""
        with self._hb_lock:
            self._hb_fields = progress
        self._emit_heartbeat("loop")
        self._update_status(
            epoch=progress["epoch"], step_in_epoch=progress["step_in_epoch"],
            units=progress["units"], **status_extra,
        )

    def _emit_heartbeat(self, source: str, **extra) -> None:
        """One ``heartbeat`` record, debounced to ``heartbeat_every_s`` across
        BOTH sources: the contract is that the log pulses at least this often
        while the process lives, not once per source. Host counters only."""
        if not self._heartbeat_every_s or not self.events.enabled:
            return
        now = time.monotonic()
        with self._hb_lock:
            if now - self._hb_last_emit < self._heartbeat_every_s:
                return
            self._hb_last_emit = now
            fields = dict(self._hb_fields)
        fields.update(extra)
        if self.attempt:
            fields["attempt"] = self.attempt
        if self.goodput is not None:
            # The meter's keys are fixed at construction: a patrol-thread read
            # races float updates only.
            fields["goodput_seconds"] = self.goodput.to_state()
        self.events.emit("heartbeat", source=source, **fields)

    def _update_status(self, *, epoch: int, **extra) -> None:
        """Publish a fresh status snapshot (at syncs only, never per unit)."""
        if self.exporter is None or not self.exporter.enabled:
            return
        scores = doctor.scalar_fields(self._doctor_signals())
        verdict, worst = "healthy", 0.0
        for kind, score in scores.items():
            if kind != "healthy" and score >= 1.0 and score > worst:
                verdict, worst = kind, score
        snap = {
            "run_dir": self._save_folder,
            "pid": os.getpid(),
            "t_wall": time.time(),
            "phase": "training",
            "epoch": epoch,
            "nonfinite_steps": self._nonfinite_steps,
            "hung_steps": self.hung_steps,
            "late_compiles": self.late_compiles,
            "anomaly_counts": dict(self.anomaly_counts),
            "doctor_scores": scores,
            "verdict": verdict,
        }
        if self.goodput is not None:
            snap["goodput_seconds"] = self.goodput.to_state()
            snap["goodput_fractions"] = self.goodput.fractions()
            snap["steady_fractions"] = doctor.steady_fractions(snap["goodput_seconds"])
        if self.last_step_ms is not None:
            snap.update(self._step_time_fields())
        snap.update(extra)
        self._status = snap

    def _observe(
        self, metrics, report, mem_fields, *, compiled, epoch, step_in_epoch, straggler_ratio=None
    ) -> None:
        """Feed the anomaly detector one sync's values; count, emit and log
        what fires, and raise when it was built with ``action="raise"``. A
        window or epoch that ``compiled`` has a known-skewed wall: its step
        time neither fires nor feeds the baseline (warm-up alone would only
        delay firing; the first windows would still seed the baseline minutes
        high and mask real regressions)."""
        if self.anomaly_detector is None:
            return
        anomalies = self.anomaly_detector.observe(
            step_in_epoch,
            loss=metrics.get("loss", metrics.get("ce_loss")),
            grad_norm=metrics.get("grad_norm"),
            step_time=None if compiled else report["step_ms"] / 1e3,
            live_bytes=mem_fields.get("live_bytes"),
            straggler_ratio=straggler_ratio,
        )
        for a in anomalies:
            self.anomaly_counts[a.kind] = self.anomaly_counts.get(a.kind, 0) + 1
            self.events.emit(
                "anomaly", kind=a.kind, value=a.value, baseline=a.baseline, factor=a.factor,
                epoch=epoch, step_in_epoch=step_in_epoch,
            )
            self._log(f"telemetry anomaly: {a.describe()}", "warning")
        if anomalies and self.anomaly_detector.action == "raise":
            raise AnomalyError("; ".join(a.describe() for a in anomalies))
