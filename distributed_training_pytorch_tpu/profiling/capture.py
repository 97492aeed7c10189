"""Hot-path trace capture: ``Trainer(profile=ProfileConfig(...))`` (ISSUE 6).

The capture is a tiny state machine the trainer drives at its existing unit
boundaries (a unit = one single step or one chained window), so it is

* **compile-skipping** — tracing starts at the first unit boundary after
  THIS process has dispatched ``skip_steps`` steps (default 1: the first
  dispatched unit, which pays XLA compilation, never pollutes the trace).
  The count is process-local and accumulates across epochs on purpose: a
  mid-epoch resume re-pays compilation on its first unit even though its
  epoch-local step index is large, and a ``skip_steps`` longer than an epoch
  simply starts tracing in a later epoch instead of never firing;
* **chained-window aware** — start/stop land on window boundaries, tracing
  whole windows of the REAL chained program: this capture traces the exact
  execution the run would perform anyway, which is why a
  ``profile=``-on run keeps ``TrainEngine.trace_counts`` and final params
  bit-identical to a ``profile=None`` run (test-enforced);
* **rank-0 owned** — only process 0 captures and writes, the logger/event-log
  file-ownership convention;
* **one-shot** — the first eligible window of the run is traced, then the
  machine parks in ``done`` and every later call is a cheap no-op.

On stop, the trace is summarized into a ``report.StepProfile`` and emitted as
a ``profile_capture`` telemetry event (the EventLog no-ops when telemetry is
off — the capture still writes the trace and logs the summary). Profiling
must never kill training: analysis failure, a trace dir that cannot be
created, and a profiler session that fails to start or stop are all warnings
that park the machine in ``done``.
"""

from __future__ import annotations

import dataclasses
import os

import jax

__all__ = ["ProfileConfig", "resolve_profile", "StepTraceCapture"]


@dataclasses.dataclass
class ProfileConfig:
    """``Trainer(profile=ProfileConfig(...))`` knobs.

    * ``dir``        — trace output dir (None = the trainer default,
      ``<save_folder>/profile``);
    * ``steps``      — train steps to trace (rounded up to whole windows
      under ``chain_steps``);
    * ``skip_steps`` — steps to let pass before tracing starts (default 1
      skips the compile step);
    * ``analyze``    — build a ``StepProfile`` + emit ``profile_capture``
      on stop (off = raw trace only);
    * ``top_k``      — rows kept in the report's per-op table.
    """

    dir: str | None = None
    steps: int = 5
    skip_steps: int = 1
    analyze: bool = True
    top_k: int = 10

    def __post_init__(self):
        if self.steps < 1:
            raise ValueError(f"ProfileConfig.steps must be >= 1, got {self.steps}")
        if self.skip_steps < 0:
            raise ValueError(
                f"ProfileConfig.skip_steps must be >= 0, got {self.skip_steps}"
            )


def resolve_profile(spec) -> ProfileConfig | None:
    """Trainer-knob resolution, mirroring ``telemetry.resolve_telemetry``:
    ``None``/``False`` = off; a string = trace dir with defaults; a
    :class:`ProfileConfig` passes through."""
    if spec is None or spec is False:
        return None
    if isinstance(spec, str):
        return ProfileConfig(dir=spec)
    if isinstance(spec, ProfileConfig):
        return spec
    raise TypeError(
        f"profile must be None, a trace-dir string, or a ProfileConfig, got {type(spec)}"
    )


class StepTraceCapture:
    """Drives one traced window of real training steps at unit boundaries."""

    def __init__(self, config: ProfileConfig, *, log=None, events=None,
                 process_index: int | None = None, flops_source=None):
        if config.dir is None:
            raise ValueError("StepTraceCapture needs a resolved ProfileConfig.dir")
        self.config = config
        self._log = log or (lambda msg, log_type="info": print(f"{log_type.upper()}: {msg}"))
        self._events = events
        # Zero-arg callable -> flops_by_op mapping (see report.flops_index),
        # evaluated lazily at analysis time so the roofline join's one-time
        # probe compile is paid only by a capture that actually completes.
        self._flops_source = flops_source
        proc = jax.process_index() if process_index is None else process_index
        self.active = proc == 0  # rank-0 file ownership
        self.state = "waiting" if self.active else "done"
        self.start_step: int | None = None
        self.steps_traced = 0
        self.report = None  # StepProfile after an analyzed stop
        # Process-local skip accounting: steps THIS process has dispatched
        # (unit boundaries observed while waiting), and the first-step index
        # of the unit currently in flight. step_in_epoch itself cannot gate
        # the skip: a mid-epoch resume starts at a large epoch-local index
        # but its first unit still pays XLA compilation.
        self._steps_seen = 0
        self._unit_start: int | None = None

    def _note_boundary(self, step_in_epoch: int) -> None:
        if self._unit_start is not None:
            self._steps_seen += max(0, step_in_epoch - self._unit_start)
            self._unit_start = None

    def _fail(self, what: str, e: BaseException) -> None:
        # Profiling must never kill training: park the machine and warn.
        self.state = "done"
        self._log(f"profile: {what} failed ({e}) — capture disabled", "warning")
        if self._events is not None:
            self._events.emit("profile_capture", trace_dir=self.config.dir, error=repr(e))

    def maybe_start(self, step_in_epoch: int, sync=None) -> None:
        """Call BEFORE dispatching the unit whose first step is
        ``step_in_epoch``; starts tracing once this process has dispatched
        ``skip_steps`` steps (the compile-paying prefix)."""
        if self.state != "waiting":
            return
        self._note_boundary(step_in_epoch)
        if self._steps_seen < self.config.skip_steps:
            self._unit_start = step_in_epoch  # closed by the next boundary call
            return
        if sync is not None:
            # Drain in-flight dispatches so earlier (untraced) steps' device
            # work cannot bleed into the traced window.
            jax.block_until_ready(sync)
        try:
            os.makedirs(self.config.dir, exist_ok=True)
            jax.profiler.start_trace(self.config.dir)
        except (OSError, RuntimeError) as e:
            # e.g. unwritable trace dir, or another profiler session already
            # active (a user-level profiling.trace() around trainer.train()).
            self._fail("trace start", e)
            return
        self.state = "tracing"
        self.start_step = step_in_epoch

    def maybe_stop(
        self, step_in_epoch: int, sync=None, *, force: bool = False, abort: bool = False
    ) -> None:
        """Call AFTER a unit completes, with the next step index; stops once
        ``config.steps`` steps are covered (``force`` at epoch end).

        ``abort`` (exception-path teardown) stops the process-global profiler
        session but SKIPS analysis: the roofline join compiles an XLA probe
        and the parse reads the trace off disk — neither may delay an
        emergency save racing a preemption grace window. The raw trace stays
        on disk for TensorBoard."""
        if self.state == "waiting":
            self._note_boundary(step_in_epoch)  # skip-prefix unit completed
            return
        if self.state != "tracing":
            return
        covered = step_in_epoch - self.start_step
        if covered < self.config.steps and not force:
            return
        if sync is not None:
            jax.block_until_ready(sync)  # traced work must land inside the window
        try:
            jax.profiler.stop_trace()
        except (OSError, RuntimeError) as e:
            self._fail("trace stop", e)
            return
        self.state = "done"
        self.steps_traced = covered
        self._log(
            f"profile: traced steps [{self.start_step}, {step_in_epoch}) -> "
            f"{self.config.dir}"
        )
        if self.config.analyze and not abort:
            self._analyze()
        elif self._events is not None:
            self._events.emit(
                "profile_capture",
                trace_dir=self.config.dir,
                start_step=self.start_step,
                steps=self.steps_traced,
            )

    def _analyze(self) -> None:
        from distributed_training_pytorch_tpu.profiling.report import analyze_trace

        fields = {
            "trace_dir": self.config.dir,
            "start_step": self.start_step,
            "steps": self.steps_traced,
        }
        flops_by_op = None
        if self._flops_source is not None:
            try:
                flops_by_op = self._flops_source()
            except Exception as e:  # noqa: BLE001 — profiling must never kill training
                self._log(
                    f"profile: roofline join failed ({e}) — top-op table "
                    "carries no FLOPs/bytes columns",
                    "warning",
                )
        try:
            self.report = analyze_trace(
                self.config.dir,
                steps=self.steps_traced or None,
                top_k=self.config.top_k,
                flops_by_op=flops_by_op,
            )
        except (FileNotFoundError, ValueError, OSError) as e:
            # Profiling must never kill training: a trace the analyzer cannot
            # read still exists on disk for TensorBoard.
            self._log(f"profile: trace analysis failed ({e})", "warning")
            if self._events is not None:
                self._events.emit("profile_capture", **fields, error=repr(e))
            return
        summary = self.report.to_dict()
        self._log(f"profile: {self.report.summary()}")
        if self._events is not None:
            self._events.emit(
                "profile_capture",
                **fields,
                source=summary["source"],
                span_us=summary["span_us"],
                step_us=summary["step_us"],
                device_busy_frac=summary["device_busy_frac"],
                dispatch_gap_frac=summary["dispatch_gap_frac"],
                categories=summary["categories"],
            )
