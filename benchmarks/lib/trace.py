"""Reduction of a profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: device-busy union, per-operation device time, idle gaps and
what the host was doing in them. Pure functions over plain tuples, so the
arithmetic is tested on a synthesised trace; ``load`` alone touches jax."""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
# The trace names an operation by its whole HLO text; a Mosaic (Pallas) call carries this target.
MOSAIC_CALL = ("tpu_custom_call",)


CONTAINERS = ("while", "conditional", "call")  # their time is their children's, which the line also holds


def short_name(hlo_text: str) -> tuple:
    """('%fusion.12 fusion', 'fusion') from the profiler's event name, which on
    this runtime is the instruction's whole HLO text:
    '%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, ...'."""
    head, sep, rest = hlo_text.partition(" = ")
    if not sep:
        return hlo_text[:80], ""
    m = re.search(r"([a-z][a-z0-9\-_]*)\(", rest)
    opcode = m.group(1) if m else ""
    return f"{head} {opcode}".strip()[:80], opcode


@dataclasses.dataclass
class Op:
    name: str  # the event's name as the profiler gives it
    label: str  # name + its string stats: what a reader's patterns are matched against
    start_ns: float
    dur_ns: float


def union_ns(intervals) -> float:
    """Total length of the union of (start, duration) intervals."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, dur in sorted(intervals):
        hi = lo + dur
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def gaps_ns(intervals, lo: float, hi: float) -> list:
    """The idle (start, duration) stretches of [lo, hi] that no interval covers."""
    out, edge = [], lo
    for start, dur in sorted(intervals):
        if start > edge:
            out.append((edge, min(start, hi) - edge))
        edge = max(edge, start + dur)
        if edge >= hi:
            break
    if edge < hi:
        out.append((edge, hi - edge))
    return [g for g in out if g[1] > 0]


def innermost_span(spans, t_ns: float) -> str:
    """Name of the shortest host span covering ``t_ns``; spans are (name, start, dur)."""
    best = None
    for name, start, dur in spans:
        if start <= t_ns <= start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else "outside_bench_spans"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    devices: dict  # plane name -> [Op]
    host_spans: list  # (name, start_ns, dur_ns)

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the device planes."""
        if not self.devices:
            return 0.0
        per = [union_ns((o.start_ns, o.dur_ns) for o in ops) for ops in self.devices.values()]
        return sum(per) / len(per) / 1e9

    def op_seconds(self, patterns) -> float | None:
        """Summed device time of operations whose label holds any pattern,
        averaged over the device planes; None where none matched."""
        per, hit = [], False
        for ops in self.devices.values():
            t = sum(o.dur_ns for o in ops if any(p in o.label for p in patterns))
            hit = hit or t > 0
            per.append(t)
        return sum(per) / len(per) / 1e9 if hit else None

    def top_ops(self, n: int = 10) -> list:
        first = next(iter(self.devices.values()), [])
        totals: dict = {}
        for o in first:
            name, opcode = short_name(o.name)
            if opcode in CONTAINERS:
                continue
            totals[name] = totals.get(name, 0.0) + o.dur_ns
        return [[k, v / 1e9] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> list:
        first = next(iter(self.devices.values()), [])
        if not first:
            return []
        lo = min(o.start_ns for o in first)
        hi = max(o.start_ns + o.dur_ns for o in first)
        gaps = sorted(gaps_ns([(o.start_ns, o.dur_ns) for o in first], lo, hi), key=lambda g: -g[1])[:n]
        return [[innermost_span(self.host_spans, g[0] + g[1] / 2), g[1] / 1e9] for g in gaps]


def summarize(planes, window_s: float) -> TraceSummary:
    """``planes``: [(plane_name, [(line_name, [(name, label, start_ns, dur_ns)])])]."""
    devices, spans = {}, []
    for plane_name, lines in planes:
        for line_name, events in lines:
            if plane_name.startswith(DEVICE_PLANE) and line_name == OPS_LINE:
                devices.setdefault(plane_name, []).extend(Op(*e) for e in events)
            elif not plane_name.startswith("/device:"):
                spans.extend((n, s, d) for n, _, s, d in events if n.startswith(HOST_SPAN_PREFIX))
    return TraceSummary(window_s=window_s, devices=devices, host_spans=spans)


def _profile_data(logdir: str):
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"the profiler wrote no .xplane.pb under {logdir}")
    return ProfileData.from_file(files[-1])


def load(logdir: str, window_s: float) -> TraceSummary:
    data = _profile_data(logdir)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            events = []
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_SPAN_PREFIX):
                    continue
                label = ev.name
                if device:
                    label += " " + " ".join(str(v) for _, v in ev.stats if isinstance(v, str))
                events.append((ev.name, label, float(ev.start_ns), float(ev.duration_ns)))
            lines.append((line.name, events))
        planes.append((plane.name, lines))
    return summarize(planes, window_s)


def describe(logdir: str, out_path: str, limit: int = 60) -> None:
    """A look at a trace by hand: planes, lines, and the commonest events with
    their stats. Written to a file; used once when the reduction was written."""
    data = _profile_data(logdir)
    with open(out_path, "w") as f:
        for plane in data.planes:
            f.write(f"PLANE {plane.name!r}\n")
            for line in plane.lines:
                events = list(line.events)
                f.write(f"  LINE {line.name!r}: {len(events)} events\n")
                totals: dict = {}
                for ev in events:
                    t = totals.setdefault(ev.name, [0, 0.0, None])
                    t[0] += 1
                    t[1] += ev.duration_ns
                    if t[2] is None:
                        t[2] = {str(k): str(v)[:160] for k, v in ev.stats}
                for name, (n, ns, stats) in sorted(totals.items(), key=lambda kv: -kv[1][1])[:limit]:
                    f.write(f"    {ns / 1e6:10.3f} ms x{n:<5} {name[:100]!r} {stats}\n")
                calls = [name for name in totals if "custom-call(" in name]
                f.write(f"    {len(calls)} distinct custom-call names; whole text of the first two:\n")
                for name in calls[:2]:
                    f.write(f"      {name}\n")
