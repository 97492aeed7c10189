"""Reduction of a profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: device-busy union, per-operation device time, idle gaps and
what the host was doing in them. Pure functions over plain tuples, so the
arithmetic is tested on a synthesised trace; ``load`` alone touches jax."""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
# The trace names an operation by its whole HLO text; a Mosaic (Pallas) call carries this target.
MOSAIC_CALL = ("tpu_custom_call",)


CONTAINERS = ("while", "conditional", "call")  # their time is their children's, which the line also holds
# Operations that move data between chips. The runtime may split one into a `-start` and a `-done`
# half (or wrap it in `async-start` / `async-done` and keep the kind in the instruction's name).
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")
_COLLECTIVE = re.compile(r"^%?(" + "|".join(COLLECTIVES) + r")(?![a-z])")


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


def collective_half(hlo_text: str) -> str | None:
    """Which part of a collective an operation is: "whole" (synchronous),
    "start", "done", or None for anything else. Read from the opcode; under
    the generic async wrapper from the instruction's name."""
    return _half(*short_name(hlo_text))


def _half(name: str, opcode: str) -> str | None:
    if opcode in ("async-start", "async-done"):
        return opcode[len("async-"):] if _COLLECTIVE.match(name) else None
    m = _COLLECTIVE.match(opcode)
    return {"": "whole", "-start": "start", "-done": "done"}.get(opcode[m.end():]) if m else None


@dataclasses.dataclass
class Op:
    name: str  # the event's name as the profiler gives it
    label: str  # name + its string stats: what a reader's patterns are matched against
    start_ns: float
    dur_ns: float


def merged(intervals) -> list:
    """The union of (start, duration) intervals as disjoint (lo, hi) stretches in time order."""
    out = []
    for lo, dur in sorted(intervals):
        hi = lo + dur
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def union_ns(intervals) -> float:
    """Total length of the union of (start, duration) intervals."""
    return float(sum(hi - lo for lo, hi in merged(intervals)))


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


def collective_intervals(ops) -> tuple:
    """((start, duration) of every collective of one device line, the same of
    every other operation). A split collective lasts from its start's
    beginning to its done's end; a done names its start as its operand, and
    the instruction names repeat from step to step, so a done closes the
    latest open start of that name. A half without its partner (cut off by
    the trace's edge) counts for its own length. Containers are in neither
    list: their children are on the same line."""
    collectives, others, open_starts = [], [], {}
    for o in sorted(ops, key=lambda o: o.start_ns):
        name, opcode = short_name(o.name)
        half = _half(name, opcode)
        if half is None:
            if opcode not in CONTAINERS:
                others.append((o.start_ns, o.dur_ns))
        elif half == "start":
            head = o.name.partition(" = ")[0].strip()
            if head in open_starts:  # never closed: its own length
                collectives.append(open_starts[head])
            open_starts[head] = (o.start_ns, o.dur_ns)
        elif half == "done":
            m = re.search(r"-done\(.*?(%[\w.\-]+)", o.name)  # the operand, after its shape
            begun = open_starts.pop(m.group(1), None) if m else None
            lo = begun[0] if begun else o.start_ns
            collectives.append((lo, o.start_ns + o.dur_ns - lo))
        else:
            collectives.append((o.start_ns, o.dur_ns))
    collectives.extend(open_starts.values())
    return collectives, others


def uncovered_ns(intervals, others) -> float:
    """Length of the union of ``intervals`` that no interval of ``others`` covers."""
    cover, total, j = merged(others), 0.0, 0
    for lo, hi in merged(intervals):
        total += hi - lo
        while j < len(cover) and cover[j][1] <= lo:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < hi:
            total -= min(hi, cover[k][1]) - max(lo, cover[k][0])
            k += 1
    return total


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

    @functools.cached_property
    def collective_seconds(self) -> tuple | None:
        """(seconds a collective was in flight, seconds of that in which the
        line ran nothing else), each the union on a device plane, averaged
        over the planes; None where no plane holds a collective."""
        per = []
        for ops in self.devices.values():
            collectives, others = collective_intervals(ops)
            per.append((union_ns(collectives), uncovered_ns(collectives, others)) if collectives else None)
        if not per or all(p is None for p in per):
            return None
        n = len(per)
        return sum(p[0] for p in per if p) / n / 1e9, sum(p[1] for p in per if p) / n / 1e9

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
                # Around the first collective, in time order: what runs while one is in flight.
                events.sort(key=lambda ev: ev.start_ns)
                first = next((i for i, ev in enumerate(events) if collective_half(ev.name)), None)
                if first is not None:
                    f.write("    in time order from 20 events before the first collective (start us, us, name):\n")
                    for ev in events[max(0, first - 20):first + 180]:
                        f.write(f"      {ev.start_ns / 1e3:12.1f} {ev.duration_ns / 1e3:9.1f} {short_name(ev.name)[0]}\n")
                    f.write(f"      the first collective's whole text: {events[first].name}\n")
