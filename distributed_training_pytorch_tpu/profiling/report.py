"""Trace analysis: device-time attribution + dispatch-gap audit (ISSUE 6).

Telemetry (PR 4) answers *how much* of the run's wall time was productive;
this module answers *where the device's own wall went*: a captured XLA trace
is parsed into a :class:`StepProfile` that attributes device wall across op
categories (matmul/conv compute, fusions, copies, collectives, infeed) plus
the ``idle`` gap between device programs — the fractions sum to 1 by
construction, so nothing can leak out of the attribution. The per-op top-k
table joins each hot op against ``utils.hlo_flops``'s per-instruction
itemization, so a hot op carries FLOPs + bytes + arithmetic intensity — its
roofline position: is this op compute-bound (intensity above the chip's
ridge point) or memory-bound?

The ``idle`` bucket is the dispatch-gap audit: device wall spent *between*
programs (per-step dispatch, H2D waits), which no per-op table can show —
only the gaps between event intervals can.

Sources, in preference order:

* **device planes** (TPU/GPU): the ``"XLA Ops"`` line is the synchronous
  critical path — events are sequential, so busy time is the plain sum and
  every gap is real device idleness. On a multi-chip host, ONE representative
  chip plane (the busiest) is analyzed: attribution is per chip, like
  ``step_ms``/MFU.
* **host XLA-runtime threads** (CPU fallback, ``tf_XLA*`` lines): the CPU
  backend has no device plane, but its runtime threads carry per-HLO-op
  events. Threads overlap, so busy time is the *interval union* (summing
  would double-count parallel execution) and runtime bookkeeping events
  (``ThreadpoolListener::*`` etc.) are excluded. This keeps the whole
  capture -> report -> gate pipeline CPU-viable for verify.
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Mapping

from distributed_training_pytorch_tpu.profiling import xplane
from distributed_training_pytorch_tpu.profiling.categories import IDLE, categorize
from distributed_training_pytorch_tpu.profiling.trace import latest_trace_file

__all__ = ["OpRow", "StepProfile", "REPORT_FIELDS", "analyze_trace", "flops_index"]

# Host-runtime bookkeeping events on the tf_XLA* thread lines — infrastructure,
# not HLO op execution; counted neither as busy time nor as ops.
_HOST_NOISE_PREFIXES = (
    "ThreadpoolListener",
    "ThunkExecutor",
    "TaskDispatcher",
    "Thunk::",
    "XlaModule",
    "BatchTimeUs",
)

# First HLO instruction token of a trace event name: "%fusion.3 = ..." or a
# bare "dot.3" (CPU runtime lines) both resolve to their instruction name.
_INSTR_RE = re.compile(r"^%?([\w.\-]+)")


@dataclasses.dataclass
class OpRow:
    """One per-op line of the attribution table."""

    name: str
    category: str
    total_us: float
    count: int
    frac_busy: float  # share of summed op time
    flops: float | None = None  # joined from utils.hlo_flops (matmul/conv only)
    bytes: float | None = None
    arith_intensity: float | None = None  # FLOPs/byte — roofline x-coordinate

    def to_dict(self) -> dict:
        out = {
            "name": self.name,
            "category": self.category,
            "total_us": round(self.total_us, 1),
            "count": self.count,
            "frac_busy": round(self.frac_busy, 4),
        }
        if self.flops is not None:
            out["flops"] = self.flops
        if self.bytes is not None:
            out["bytes"] = self.bytes
        if self.arith_intensity is not None:
            out["arith_intensity"] = round(self.arith_intensity, 2)
        return out


# The stable report schema (test-enforced): every to_dict() carries exactly
# these keys. Consumers (bench JSON, profile_capture events, perf dashboards)
# may rely on them across PRs; additions append, never rename.
REPORT_FIELDS = (
    "trace_path",
    "source",
    "steps",
    "span_us",
    "busy_us",
    "idle_us",
    "step_us",
    "device_busy_frac",
    "dispatch_gap_frac",
    "categories",
    "category_us",
    "top_ops",
)


@dataclasses.dataclass
class StepProfile:
    """Device-time attribution for one traced window of steps.

    ``categories`` maps category -> fraction of the traced span (``idle``
    included) and sums to 1 +- float eps by construction; ``category_us``
    carries the same attribution in microseconds of op self-time (host
    sources can overlap threads, so op self-time may exceed the busy
    interval union — fractions are normalized through the union so the
    partition stays exhaustive)."""

    trace_path: str
    source: str  # "device" | "host-xla"
    steps: int | None
    span_us: float
    busy_us: float
    idle_us: float
    categories: dict[str, float]
    category_us: dict[str, float]
    top_ops: list[OpRow]
    step_us: float | None = None
    device_busy_frac: float = 0.0
    dispatch_gap_frac: float = 0.0

    def to_dict(self) -> dict:
        return {
            "trace_path": self.trace_path,
            "source": self.source,
            "steps": self.steps,
            "span_us": round(self.span_us, 1),
            "busy_us": round(self.busy_us, 1),
            "idle_us": round(self.idle_us, 1),
            "step_us": round(self.step_us, 1) if self.step_us is not None else None,
            "device_busy_frac": round(self.device_busy_frac, 4),
            "dispatch_gap_frac": round(self.dispatch_gap_frac, 4),
            "categories": {k: round(v, 4) for k, v in self.categories.items()},
            "category_us": {k: round(v, 1) for k, v in self.category_us.items()},
            "top_ops": [row.to_dict() for row in self.top_ops],
        }

    def summary(self) -> str:
        """One log line: busy/idle split + the two hottest categories."""
        hot = sorted(
            ((k, v) for k, v in self.categories.items() if k != IDLE),
            key=lambda kv: -kv[1],
        )[:2]
        hot_txt = ", ".join(f"{k} {100 * v:.0f}%" for k, v in hot)
        return (
            f"device busy {100 * self.device_busy_frac:.0f}% / "
            f"gap {100 * self.dispatch_gap_frac:.0f}% over {self.span_us / 1e3:.2f} ms"
            + (f" ({self.steps} steps)" if self.steps else "")
            + (f"; hottest: {hot_txt}" if hot_txt else "")
        )


def _union_us(intervals: list[tuple[int, int]]) -> float:
    """Total length (us) of the union of [start_ps, end_ps) intervals."""
    if not intervals:
        return 0.0
    intervals.sort()
    total_ps = 0
    cur_start, cur_end = intervals[0]
    for start, end in intervals[1:]:
        if start > cur_end:
            total_ps += cur_end - cur_start
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    total_ps += cur_end - cur_start
    return total_ps / 1e6


def _abs_events(line: xplane.TraceLine) -> list[xplane.TraceEvent]:
    """Rebase a line's events onto the shared trace clock: ``offset_ps`` is
    line-LOCAL (relative to ``XLine.timestamp_ns``), so interval analysis
    across lines — the host-thread union, gaps between device lines — must
    add the line's base first or timelines misalign."""
    base_ps = line.timestamp_ns * 1000  # ns -> ps
    if not base_ps:
        return list(line.events)
    return [
        dataclasses.replace(e, start_ps=e.start_ps + base_ps) for e in line.events
    ]


def _select_events(planes: list[xplane.TracePlane]) -> tuple[str, list[xplane.TraceEvent]]:
    """Pick the op-event stream: ONE representative device plane's
    critical-path lines, else the host XLA-runtime threads (CPU). Returns
    (source, events) with event starts rebased to the shared trace clock
    (see :func:`_abs_events`).

    A multi-chip host writes one plane per chip. Attribution is PER CHIP
    (step_ms/MFU are per-chip figures): pooling N planes into one timeline
    would sum op self-time N× against a single span and count ``idle`` only
    where every chip is simultaneously idle — hiding exactly the per-chip
    dispatch gaps the audit exists to expose. Under SPMD every chip runs the
    same program, so one plane is representative; the busiest plane (largest
    op self-time, name as the deterministic tie-break) is the chip gating
    the step."""
    # (op_self_time_ps, plane_name, events) per device plane, split by
    # whether the plane carries a real "XLA Ops" critical-path line.
    op_planes: list[tuple[int, str, list[xplane.TraceEvent]]] = []
    stream_planes: list[tuple[int, str, list[xplane.TraceEvent]]] = []
    for plane in planes:
        if "TPU" not in plane.name and "GPU" not in plane.name:
            continue
        has_op_line = any(line.name == "XLA Ops" for line in plane.lines)
        plane_events: list[xplane.TraceEvent] = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                plane_events.extend(_abs_events(line))
            elif not has_op_line and "Async" not in line.name:
                # GPU stream lines carry op events without an "XLA Ops" line
                # name. Gated to planes WITHOUT one: on TPU the other lines
                # ("Async XLA Ops" DMA windows, "Steps", "XLA Modules") span
                # overlapped/aggregate intervals — promoting them to the
                # critical path would fabricate a near-1 busy fraction.
                plane_events.extend(_abs_events(line))
        if plane_events:
            bucket = op_planes if has_op_line else stream_planes
            bucket.append(
                (sum(e.duration_ps for e in plane_events), plane.name, plane_events)
            )
    for candidates in (op_planes, stream_planes):
        if candidates:
            _, _, events = max(candidates, key=lambda c: (c[0], c[1]))
            return "device", events
    host_events: list[xplane.TraceEvent] = []
    for plane in planes:
        for line in plane.lines:
            if not line.name.startswith("tf_XLA"):
                continue
            for event in _abs_events(line):
                if event.name.startswith(_HOST_NOISE_PREFIXES) or not event.duration_ps:
                    continue
                host_events.append(event)
    return "host-xla", host_events


def flops_index(compiled_or_hlo) -> dict[str, dict]:
    """Per-instruction roofline join table from a compiled executable (or raw
    HLO text): instruction name -> {flops, bytes, arith_intensity} for every
    conv/dot ``utils.hlo_flops`` itemizes. Fusions and custom calls are absent
    (their cost is opaque to the HLO walk) — joined rows simply carry None."""
    from distributed_training_pytorch_tpu.utils import hlo_flops

    text = compiled_or_hlo if isinstance(compiled_or_hlo, str) else compiled_or_hlo.as_text()
    index: dict[str, dict] = {}
    for row in hlo_flops.itemize_hlo_matmul_flops(text):
        entry = {"flops": row["flops"]}
        if row.get("bytes"):
            entry["bytes"] = row["bytes"]
            entry["arith_intensity"] = row["flops"] / row["bytes"]
        index[row["name"]] = entry
    return index


def analyze_trace(
    log_dir_or_file: str,
    *,
    steps: int | None = None,
    top_k: int = 20,
    flops_by_op: Mapping[str, dict] | None = None,
) -> StepProfile:
    """Parse the newest trace under ``log_dir_or_file`` into a StepProfile.

    ``steps`` (the number of train steps the trace covers) turns the span
    into a per-step figure; ``flops_by_op`` (see :func:`flops_index`) joins
    the top-op table with FLOPs/bytes/intensity. Raises ``FileNotFoundError``
    when no trace exists and ``ValueError`` when the trace carries no XLA op
    events at all (nothing to attribute)."""
    path = log_dir_or_file
    if not path.endswith(".xplane.pb"):
        found = latest_trace_file(path)
        if found is None:
            raise FileNotFoundError(f"no *.xplane.pb under {log_dir_or_file}")
        path = found
    source, events = _select_events(xplane.read_trace(path))
    if not events:
        raise ValueError(
            f"{path}: no XLA op events in any device plane or tf_XLA* host "
            "line — was anything dispatched inside the trace window?"
        )

    span_ps = max(e.end_ps for e in events) - min(e.start_ps for e in events)
    span_us = max(span_ps / 1e6, 1e-9)
    busy_us = min(_union_us([(e.start_ps, e.end_ps) for e in events]), span_us)
    idle_us = max(span_us - busy_us, 0.0)

    totals: dict[str, list[float]] = {}
    for event in events:
        acc = totals.setdefault(event.name, [0.0, 0])
        acc[0] += event.duration_ps / 1e6
        acc[1] += 1
    op_total_us = sum(t for t, _ in totals.values()) or 1e-9

    category_us: dict[str, float] = {}
    for name, (total, _) in totals.items():
        cat = categorize(name)
        category_us[cat] = category_us.get(cat, 0.0) + total
    # Fractions over the traced span: op categories share the busy fraction
    # proportionally to their self-time (identity on a sequential device
    # line where op time == busy time; on overlapping host threads this
    # normalizes through the interval union), and idle takes the rest — an
    # exhaustive partition, sum == 1 by construction.
    busy_frac = busy_us / span_us
    categories = {
        cat: (total / op_total_us) * busy_frac for cat, total in category_us.items()
    }
    categories[IDLE] = idle_us / span_us

    rows = []
    for name, (total, count) in sorted(totals.items(), key=lambda kv: -kv[1][0])[:top_k]:
        row = OpRow(
            name=name,
            category=categorize(name),
            total_us=total,
            count=count,
            frac_busy=total / op_total_us,
        )
        if flops_by_op:
            m = _INSTR_RE.match(name)
            joined = flops_by_op.get(m.group(1)) if m else None
            if joined:
                row.flops = joined.get("flops")
                row.bytes = joined.get("bytes")
                row.arith_intensity = joined.get("arith_intensity")
        rows.append(row)

    return StepProfile(
        trace_path=os.path.abspath(path),
        source=source,
        steps=steps,
        span_us=span_us,
        busy_us=busy_us,
        idle_us=idle_us,
        step_us=span_us / steps if steps else None,
        device_busy_frac=busy_frac,
        dispatch_gap_frac=idle_us / span_us,
        categories=categories,
        category_us=category_us,
        top_ops=rows,
    )
