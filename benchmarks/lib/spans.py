"""The program's own spans and counters (``profiling/trace.py``: ``annotate``
/ ``count`` under a recorder) laid over the device trace. Pure functions over
plain tuples, so the arithmetic is tested on synthesised spans; ``load`` alone
touches the program, the profiler's file and jax.

A span is ``(name, start_ns, end_ns, thread, parent, ids)``. Its stamps are
realtime nanoseconds; the trace's are the same clock shifted so that the
profiler session's start reads 0, and the shift is the ``profile_start_time``
stat of the xplane's ``Task Environment`` plane. ``session`` reads it (and the
device operations' scopes) from the file the harness's profiler wrote
(``benchmarks/lib/xplane.py``), and ``load`` trusts it only if every device
operation then falls inside the traced slices' span of the host's clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import sys
import tempfile

from benchmarks.lib import xplane
from benchmarks.lib.trace import CONTAINERS, gaps_ns, short_name, union_ns

ROOT = "trainer.train"  # one per Trainer.train() call: the harness's slice
FETCH, DISPATCH = "trainer.fetch", "engine.dispatch"
STAGE, PRODUCE = "prefetch.stage", "loader.batch"
OUTSIDE = "outside_spans"
SLACK_NS = 1e6  # the clocks are called shared when they agree to a millisecond


def roots(spans) -> list:
    return sorted((s for s in spans if s[0] == ROOT), key=lambda s: s[1])


def pick_slices(spans, window_slices: int, trace_slices: int):
    """(traced roots, window roots): the window is the last ``window_slices``
    roots, the traced stretch the ``trace_slices`` before them. None where the
    record holds too few roots to say."""
    r = roots(spans)
    if window_slices < 1 or trace_slices < 1 or len(r) < window_slices + trace_slices:
        return None
    return r[-window_slices - trace_slices:-window_slices], r[-window_slices:]


def self_segments(spans) -> list:
    """``[(name, lo, hi)]`` in time order: for the properly nested spans of
    one thread, the pieces of each span that none of its children covers."""
    out, stack = [], []  # stack entries: [name, end, cursor]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, cursor = stack.pop()
            if end > cursor:
                out.append((name, cursor, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, start, end, *_ in sorted(spans, key=lambda s: (s[1], -s[2])):
        close(start)
        if stack:
            top = stack[-1]
            if start > top[2]:
                out.append((top[0], top[2], start))
            top[2] = max(top[2], start)
        stack.append([name, end, start])
    close(float("inf"))
    return sorted(out, key=lambda seg: seg[1])


def self_ns(spans) -> dict:
    """Self time by span name, the threads taken one by one."""
    out: dict = {}
    for thread in {s[3] for s in spans}:
        for name, lo, hi in self_segments([s for s in spans if s[3] == thread]):
            out[name] = out.get(name, 0) + (hi - lo)
    return out


def split_gaps(gaps, segments) -> dict:
    """Idle ``(start, duration)`` gaps split over ``(name, lo, hi)`` segments
    by length of overlap; what no segment covers goes to ``outside_spans``.
    The values sum to the gaps' total length."""
    out: dict = {}
    for g_lo, dur in gaps:
        g_hi, covered = g_lo + dur, 0.0
        for name, lo, hi in segments:
            if lo >= g_hi:
                break
            part = min(hi, g_hi) - max(lo, g_lo)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
        if dur - covered > 0:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (dur - covered)
    return out


def contained(op_bounds, lo: float, hi: float, slack: float = SLACK_NS) -> bool:
    """No operation starts more than ``slack`` before ``lo`` or ends more than
    ``slack`` after ``hi``: the test that spans and operations share a clock."""
    return all(start >= lo - slack and end <= hi + slack for start, end in op_bounds)


def group_idle(by_span: dict) -> dict:
    """The three shares' numerators: idle time under ``trainer.fetch``, under
    ``engine.dispatch``, and under anything else on the main thread or
    outside every span (``epoch_start``, ``sync``, ``epoch_end``, between
    epochs)."""
    fetch, dispatch = by_span.get(FETCH, 0.0), by_span.get(DISPATCH, 0.0)
    return {"fetch": fetch, "dispatch": dispatch, "glue": sum(by_span.values()) - fetch - dispatch}


def scoped_seconds(ops, patterns, scopes=None) -> float | None:
    """Device time of one plane's operations whose label, or whose scope
    (``scopes``: event name -> the ``tf_op`` of its metadata), holds any
    pattern: the union of their intervals, the ``while`` / ``conditional`` /
    ``call`` containers left out (their children are on the same line and
    carry the scope themselves). None where none matched."""
    scopes = scopes or {}
    hit = [(o.start_ns, o.dur_ns) for o in ops
           if any(p in o.label or p in scopes.get(o.name, "") for p in patterns)
           and short_name(o.name)[1] not in CONTAINERS]
    return union_ns(hit) / 1e9 if hit else None


def plane_mean_seconds(devices: dict, patterns, scopes=None) -> float | None:
    per = [scoped_seconds(ops, patterns, scopes) for ops in devices.values()]
    if not per or all(p is None for p in per):
        return None
    return sum(p or 0.0 for p in per) / len(per)


# -- what touches the program, the profiler's file and the harness's context --


@dataclasses.dataclass
class View:
    spans: list  # every recorded span, realtime ns
    window: tuple  # (lo, hi) realtime ns: first window root's start to the last one's end
    steps: int
    idle_s: dict | None  # {"fetch", "dispatch", "glue"} seconds of the traced stretch, None without a shared clock
    counters: dict  # counted inside the window

    def in_window(self, name: str) -> list:
        lo, hi = self.window
        return [s for s in self.spans if s[0] == name and lo <= s[1] <= hi]


def find_session(at_ns: int | None = None):
    """The profiler session of the file the harness wrote: it keeps its trace
    under ``<TMPDIR>/bench_run_*/trace`` until the run ends, and hands a
    reader the reduced trace only. With ``at_ns``, the session that was
    running at that moment; else the newest. None where no file says so."""
    pattern = os.path.join(tempfile.gettempdir(), "bench_run_*", "trace", "plugins", "profile", "*", "*.xplane.pb")
    for path in sorted(glob.glob(pattern), key=os.path.getmtime, reverse=True):
        found = xplane.read_session(path)
        if found.start_ns is None or found.stop_ns is None:
            continue
        if at_ns is None or found.start_ns <= at_ns <= found.stop_ns:
            return found
    return None


def lay_over(spans, traced_roots, trace, zero_ns: int, slack: float = SLACK_NS):
    """Idle seconds of the traced stretch by the main thread's innermost span,
    averaged over the device planes; None unless the clocks are shared: every
    device operation inside [first traced root's start, + the stretch], and,
    where the trace kept the harness's ``bench.slice`` spans, each traced root
    inside exactly one of them with starts within the slack."""
    stretch_ns = trace.window_s * 1e9
    first, main = traced_roots[0][1], traced_roots[0][3]
    lo, hi = first - zero_ns, first - zero_ns + stretch_ns
    bounds = [(o.start_ns, o.start_ns + o.dur_ns) for ops in trace.devices.values() for o in ops]
    if not bounds or not contained(bounds, lo, hi, slack):
        return None
    slices = [(s, s + d) for n, s, d in trace.host_spans if n == "bench.slice"]
    for _, start, end, *_ in traced_roots if slices else ():
        around = [1 for s, e in slices if abs(start - zero_ns - s) <= slack and e >= end - zero_ns - slack]
        if len(around) != 1:
            return None
    segments = [(n, a - zero_ns, b - zero_ns) for n, a, b in self_segments(
        [s for s in spans if s[3] == main and s[1] < first + stretch_ns and s[2] > first])]
    total: dict = {}
    for ops in trace.devices.values():
        for name, ns in split_gaps(gaps_ns([(o.start_ns, o.dur_ns) for o in ops], lo, hi), segments).items():
            total[name] = total.get(name, 0.0) + ns / 1e9 / len(trace.devices)
    return total


def _recorded(ctx):
    """(the program's span module, its spans as tuples, (traced roots, window
    roots)), read once a run and kept on ``ctx``; None on a commit before the
    program kept any span, or where the record does not hold the run's slices."""
    if "spans_recorded" not in ctx:
        ctx["spans_recorded"] = None
        try:
            from distributed_training_pytorch_tpu import profiling as program
        except ImportError:
            program = None
        if hasattr(program, "recorded"):
            spans = [tuple(s) for s in program.recorded()]
            picked = pick_slices(spans, ctx["steps"] // ctx["traffic"]["steps_per_epoch"],
                                 ctx["traffic"].get("trace_slices", 1))
            if picked is not None:
                ctx["spans_recorded"] = (program, spans, picked)
    return ctx["spans_recorded"]


def session(ctx):
    """The profiler session of this run's traced stretch, found once a run
    and kept on ``ctx``: by the first traced root where the program recorded
    its slices, else the newest the harness wrote."""
    if "xplane_session" not in ctx:
        got = _recorded(ctx)
        ctx["xplane_session"] = find_session(got[2][0][0][1] if got else None)
    return ctx["xplane_session"]


def load(ctx) -> View | None:
    """What the span readers share, made once a run and kept on ``ctx``. None
    where the program records no spans (a commit before ``annotate`` kept
    any) or the record does not hold the run's slices."""
    if "spans_view" not in ctx:
        ctx["spans_view"] = _load(ctx)
    return ctx["spans_view"]


def _load(ctx):
    got = _recorded(ctx)
    if got is None:
        return None
    program, spans, (traced_roots, window_roots) = got
    window = (window_roots[0][1], window_roots[-1][2])
    idle_by_span = None
    trace = ctx.get("trace")
    if trace is not None and trace.devices:
        found = session(ctx)
        if found is not None:
            idle_by_span = lay_over(spans, traced_roots, trace, found.start_ns)
        if idle_by_span is None:
            note = f"no shared clock (profiler session: {found and found.start_ns}): the idle shares are left out"
        else:
            by_size = sorted(idle_by_span.items(), key=lambda kv: -kv[1])
            note = (f"profiler session started at {found.start_ns} ns; idle seconds of the traced stretch by span: "
                    + ", ".join(f"{k} {v:.6f}" for k, v in by_size))
        print(f"benchmarks/lib/spans.py: {note}", file=sys.stderr, flush=True)
    return View(spans=spans, window=window, steps=ctx["steps"],
                idle_s=group_idle(idle_by_span) if idle_by_span is not None else None,
                counters=program.counters(*window))


def idle_share(ctx, part: str) -> float | None:
    view = load(ctx)
    if view is None or view.idle_s is None:
        return None
    return 100.0 * view.idle_s[part] / ctx["trace"].window_s


def scope_share(ctx, patterns) -> float | None:
    """Device time under a scope or a kernel name over the traced stretch, in
    percent. The scopes come from the xplane's event metadata where the file
    is found; a kernel's name is in the event's own name either way."""
    trace = ctx.get("trace")
    if trace is None or not trace.devices:
        return None
    found = session(ctx)
    spent = plane_mean_seconds(trace.devices, patterns, found.scopes if found is not None else None)
    return 100.0 * spent / trace.window_s if spent else None
