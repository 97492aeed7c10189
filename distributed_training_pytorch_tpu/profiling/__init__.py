"""Profiling subsystem (ISSUE 6): device-time attribution, dispatch/overlap
audit, hot-path capture, and the perf-regression gate.

Telemetry (``telemetry/``, docs/observability.md) answers *how much* of a
run's wall time was productive; this package answers *where the rest went* —
and keeps it from regressing silently:

* :mod:`~.trace`      — the ``trace`` capture context manager, headless
  ``top_ops`` summaries (no TensorBoard server needed), and the program's
  one span-and-counter primitive: ``annotate`` / ``count``, kept in memory
  while a recorder is installed (``recorded`` / ``counters``);
* :mod:`~.xplane`     — minimal ``*.xplane.pb`` wire codec (offsets AND
  durations, so traces support interval analysis);
* :mod:`~.categories` — the ONE HLO-op categorizer (shared by the report,
  ``scripts/profile_step.py``, and bench's ``BENCH_PROFILE`` fields);
* :mod:`~.report`     — ``analyze_trace`` -> :class:`StepProfile`: device
  wall attributed across op categories + the ``idle`` dispatch gap
  (fractions sum to 1), top-op table joined with per-op FLOPs/bytes/
  arithmetic intensity from ``utils.hlo_flops`` (roofline position);
* :mod:`~.capture`    — ``Trainer(profile=ProfileConfig(...))``: traces a
  window of REAL training steps (compile-skipping, chained-window aware,
  rank-0 owned, bit-exact/trace-count-neutral when off) and emits a
  ``profile_capture`` event;
* :mod:`~.gate`       — perf-regression gate logic behind
  ``scripts/perf_gate.py`` and the verify.sh stage (committed
  ``PERF_BASELINE.json``, relative tolerance, CPU-viable calibrated ratio);
* :mod:`~.diff`       — the across-runs layer (ISSUE 14):
  ``diff_profiles(before, after)`` -> :class:`ProfileDiff` with ranked
  per-category step-delta attribution (fractions of delta sum to 1),
  matched/new/removed op deltas and roofline shifts, plus the ONE generic
  ``attribute_delta`` used by ``scripts/run_compare.py`` and perf_gate's
  FAIL diagnosis.

``utils.profiling`` remains as a thin re-export shim for existing imports.
See docs/profiling.md for the capture -> report -> act workflow.
"""

from distributed_training_pytorch_tpu.profiling.capture import (  # noqa: F401
    ProfileConfig,
    StepTraceCapture,
    resolve_profile,
)
from distributed_training_pytorch_tpu.profiling.categories import (  # noqa: F401
    CATEGORIES,
    IDLE,
    categorize,
)
from distributed_training_pytorch_tpu.profiling.diff import (  # noqa: F401
    DeltaRow,
    OpDelta,
    ProfileDiff,
    attribute_delta,
    attribute_entry_delta,
    describe_rows,
    diff_profiles,
)
from distributed_training_pytorch_tpu.profiling.gate import (  # noqa: F401
    GateResult,
    load_baseline,
    update_baseline,
)
from distributed_training_pytorch_tpu.profiling.report import (  # noqa: F401
    REPORT_FIELDS,
    OpRow,
    StepProfile,
    analyze_trace,
    flops_index,
)
from distributed_training_pytorch_tpu.profiling.trace import (  # noqa: F401
    Span,
    annotate,
    count,
    counters,
    install_recorder,
    latest_trace_file,
    recorded,
    session_start_ns,
    top_ops,
    trace,
    uninstall_recorder,
)

__all__ = [
    "CATEGORIES",
    "DeltaRow",
    "GateResult",
    "IDLE",
    "OpDelta",
    "OpRow",
    "ProfileConfig",
    "ProfileDiff",
    "REPORT_FIELDS",
    "Span",
    "StepProfile",
    "StepTraceCapture",
    "analyze_trace",
    "annotate",
    "attribute_delta",
    "attribute_entry_delta",
    "categorize",
    "count",
    "counters",
    "describe_rows",
    "diff_profiles",
    "flops_index",
    "install_recorder",
    "latest_trace_file",
    "load_baseline",
    "recorded",
    "resolve_profile",
    "session_start_ns",
    "top_ops",
    "trace",
    "uninstall_recorder",
    "update_baseline",
]
