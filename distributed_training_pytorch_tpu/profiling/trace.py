"""Trace capture + headless per-op summaries.

TPU-native analog of the reference's observability hooks — the tqdm live
progress bars (``/root/reference/trainer/trainer.py:143,186``) and the NCCL
flight-recorder buffer (``/root/reference/run.sh:8``). On TPU the equivalent
is an XLA/XProf device trace: ``jax.profiler`` captures per-op device
timelines (including collective ops), viewable in TensorBoard's profile
plugin or summarized directly with :func:`top_ops` /
:func:`~distributed_training_pytorch_tpu.profiling.report.analyze_trace`.

Spans and counters (ISSUE 25). :func:`annotate` is the one way the program
marks a region and :func:`count` the one way it counts at a boundary. Every
``annotate`` opens a ``jax.profiler.TraceAnnotation``, so any capture shows
the program's host spans beside the device ops. While a recorder is installed
(:func:`install_recorder`; ``Trainer.__init__`` does it exactly when
telemetry is on) each span is also kept in memory as a :class:`Span` and each
count added to a named counter; :func:`recorded` / :func:`counters` hand them
out. Nothing is written on the hot path.

The clock. The profiler stamps host events with the realtime clock in
nanoseconds (``time.time_ns()`` reads the same clock: the two agree to a few
microseconds, tests/test_spans.py measures it against a real trace) and, when
a session stops, shifts every timestamp of the xplane so that the session's
start reads 0; the start it subtracted is the ``profile_start_time`` stat of
the xplane's ``Task Environment`` plane (:func:`session_start_ns`). A span is
recorded in unshifted realtime nanoseconds, so it lies on a trace's clock
after subtracting that trace's ``profile_start_time`` — device ops included.
"""

from __future__ import annotations

import collections
import glob
import os
import threading
import time
from contextlib import contextmanager
from typing import Iterator, NamedTuple

import jax

from distributed_training_pytorch_tpu.profiling import xplane

__all__ = [
    "Span",
    "annotate",
    "count",
    "counters",
    "install_recorder",
    "latest_trace_file",
    "recorded",
    "session_start_ns",
    "top_ops",
    "trace",
    "uninstall_recorder",
]


@contextmanager
def trace(log_dir: str) -> Iterator[str]:
    """Capture a device+host trace of the enclosed block into ``log_dir``.

    Yields the log dir. The result is a standard XProf/TensorBoard trace
    (``plugins/profile/<run>/*.xplane.pb``); inspect with TensorBoard,
    :func:`top_ops`, or ``report.analyze_trace``.
    """
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir, create_perfetto_link=False)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


class Span(NamedTuple):
    """One recorded region. ``start_ns`` / ``end_ns``: realtime nanoseconds
    (module docstring: a trace's clock after subtracting its
    ``profile_start_time``). ``thread``: the thread's name. ``parent``: the
    name of the enclosing span on the same thread, None for a root. ``ids``:
    what the site gave (``epoch``, ``unit`` = the global step of the unit's
    first step, ``batch``, ...) plus what it set on exit (``traced``)."""

    name: str
    start_ns: int
    end_ns: int
    thread: str
    parent: str | None
    ids: dict


MAX_SPANS = 1 << 16  # the oldest fall off: a long run keeps its newest ~65k spans and counts


class _Recorder:
    def __init__(self):
        self.spans: collections.deque = collections.deque(maxlen=MAX_SPANS)
        self.counts: collections.deque = collections.deque(maxlen=MAX_SPANS)  # (name, t_ns, value)
        self.totals: dict[str, float] = {}
        self.lock = threading.Lock()  # counters only: deque.append is atomic


# Process-wide on purpose: loader workers and the prefetch thread are not
# handed the trainer, and a reader may ask after the trainer is gone.
_recorder: _Recorder | None = None
_open = threading.local()  # .span: the innermost open recorded span of this thread


def install_recorder() -> None:
    """Start keeping spans and counts (idempotent: a second trainer in the
    process adds to the same record)."""
    global _recorder
    if _recorder is None:
        _recorder = _Recorder()


def uninstall_recorder() -> None:
    """Stop keeping spans and counts and drop what was kept."""
    global _recorder
    _recorder = None


def recorded() -> list[Span]:
    """The spans kept so far, in order of their ends ([] with no recorder)."""
    rec = _recorder
    return list(rec.spans) if rec is not None else []


def counters(since_ns: int | None = None, until_ns: int | None = None) -> dict[str, float]:
    """Counter totals; with bounds, what was counted in ``[since_ns,
    until_ns)`` on the spans' clock ({} with no recorder)."""
    rec = _recorder
    if rec is None:
        return {}
    if since_ns is None and until_ns is None:
        with rec.lock:
            return dict(rec.totals)
    out: dict[str, float] = {}
    for name, t_ns, value in list(rec.counts):
        if (since_ns is None or t_ns >= since_ns) and (until_ns is None or t_ns < until_ns):
            out[name] = out.get(name, 0) + value
    return out


def count(name: str, value: float = 1) -> None:
    """Add ``value`` to the named counter (a no-op with no recorder)."""
    rec = _recorder
    if rec is None:
        return
    with rec.lock:
        rec.totals[name] = rec.totals.get(name, 0) + value
    rec.counts.append((name, time.time_ns(), value))


class annotate:
    """Named region (context manager): ``with annotate("engine.dispatch",
    unit=8) as span: ...; span.set(traced=True)``.

    Always a ``jax.profiler.TraceAnnotation`` (the ids ride along as the
    event's stats); with a recorder installed, also a :class:`Span` kept on
    exit. With none, enter and exit are the annotation's own plus one
    ``is None`` test, and nothing is kept."""

    __slots__ = ("name", "ids", "_annotation", "_recorder", "_start_ns", "_parent")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = ids
        self._annotation = jax.profiler.TraceAnnotation(name, **ids)

    def set(self, **ids) -> None:
        """Ids known only inside the region (``traced`` after a dispatch)."""
        self.ids.update(ids)

    def __enter__(self):
        rec = self._recorder = _recorder
        if rec is not None:
            self._parent = getattr(_open, "span", None)
            _open.span = self
            self._start_ns = time.time_ns()
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        rec = self._recorder
        if rec is not None:
            end_ns = time.time_ns()
            parent = _open.span = self._parent
            rec.spans.append(Span(
                self.name, self._start_ns, end_ns, threading.current_thread().name,
                parent.name if parent is not None else None, self.ids,
            ))
        return False


def session_start_ns(log_dir: str) -> int | None:
    """Realtime nanoseconds at which the newest trace under ``log_dir``
    started: what the profiler subtracted from every timestamp in it, and
    what a reader subtracts from a :class:`Span` to lay it over that trace
    (None where the trace does not say)."""
    path = latest_trace_file(log_dir)
    if path is None:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            return dict(plane.stats).get("profile_start_time")
    return None


def latest_trace_file(log_dir: str) -> str | None:
    """Path of the newest ``*.xplane.pb`` under ``log_dir`` (or None)."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def top_ops(
    log_dir: str, *, limit: int = 20, line: str | None = None
) -> list[tuple[str, float, int]]:
    """Summarize the newest trace in ``log_dir``: device ops by total time.

    Returns ``[(op_name, total_time_us, occurrences), ...]`` over the device
    (TPU/GPU) planes, sorted descending — a headless op profile; no
    TensorBoard server needed.

    ``line`` filters to one named trace line. The TPU device plane carries
    several: ``"XLA Ops"`` is the synchronous critical path (its events sum
    to wall step time), ``"Async XLA Ops"`` holds overlapped DMA/prefetch
    copies whose durations span their async windows — summing across both
    double-counts overlap, so per-op accounting should pass
    ``line="XLA Ops"``. Default (None) keeps every line, preserving the
    "everything the device did" view.
    """
    path = latest_trace_file(log_dir)
    if path is None:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    totals: dict[str, list[float]] = {}
    for plane in xplane.read_trace(path):
        if "TPU" not in plane.name and "GPU" not in plane.name:
            continue
        for trace_line in plane.lines:
            if line is not None and trace_line.name != line:
                continue
            for event in trace_line.events:
                acc = totals.setdefault(event.name, [0.0, 0])
                acc[0] += event.duration_ps / 1e6  # ps -> us
                acc[1] += 1
    ranked = sorted(totals.items(), key=lambda kv: -kv[1][0])
    return [(name, round(t, 1), int(n)) for name, (t, n) in ranked[:limit]]
