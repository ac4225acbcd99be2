"""From a profiler trace (.xplane.pb) to numbers, with nothing but
`jax.profiler.ProfileData`. Kept with the benchmark so that every PR
computes the same number in the same way.

    busy      union of the intervals in which an operation ran on a device
              plane's op line, clipped to the window; averaged over planes
    idle gaps the complement inside the window, each named by the host span
              (TraceAnnotation) that covers its middle
    ops       per-name summed device time on the op line

`python -m benchmark.trace_reduce <file.xplane.pb>` prints what a trace
holds (planes, lines, the heaviest names, the stat keys): look at one by
hand before writing a selector against it.
"""

from __future__ import annotations

import glob
import heapq
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# Host spans the harness writes around its calls into a layer, and the
# program's own SpanTracer mirrors, share this prefix-free namespace; the
# profiler's own python frames start with '$'.
Interval = Tuple[float, float]


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, Any] = field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    """What the reductions need, already pulled out of ProfileData."""
    device_ops: Dict[str, List[Event]]      # plane name -> op-line events
    device_modules: Dict[str, List[Event]]  # plane name -> module-line events
    host_spans: List[Event]                 # host annotations, python threads' first


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def _events(line, keep_stats: bool) -> List[Event]:
    out = []
    for e in line.events:
        stats = dict(e.stats) if keep_stats else {}
        out.append(Event(e.name, float(e.start_ns), float(e.duration_ns),
                         stats))
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE_RE.match(plane.name):
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops[plane.name] = _events(line, True)
                elif line.name == MODULE_LINE:
                    modules[plane.name] = _events(line, True)
        elif plane.name == HOST_PLANE:
            # Python threads carry the TraceAnnotations of the harness and
            # of the program's SpanTracer; the runtime's own threads
            # (pjrt, tfrt, futex...) are kept behind them as a fallback.
            for line in sorted(plane.lines,
                               key=lambda ln: not ln.name.startswith("python")):
                for ev in _events(line, False):
                    if ev.dur_ns > 0 and not ev.name.startswith("$"):
                        ev.stats["python"] = line.name.startswith("python")
                        host.append(ev)
    return Trace(ops, modules, host)


# -- interval arithmetic -----------------------------------------------------
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of union `a` not covered by union `b` (both sorted unions)."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window_of(trace: Trace) -> Interval:
    """First device op start to last device op end, over all planes."""
    starts = [ev.start_ns for evs in trace.device_ops.values() for ev in evs]
    ends = [ev.end_ns for evs in trace.device_ops.values() for ev in evs]
    if not starts:
        raise ValueError("no operation ran on a device in this trace")
    return min(starts), max(ends)


# -- reductions ----------------------------------------------------------------
def matches(ev: Event, select: Dict[str, str]) -> bool:
    """`select` maps "name" or a stat key to a regex; all must be found."""
    for key, pattern in select.items():
        text = ev.name if key == "name" else str(ev.stats.get(key, ""))
        if not re.search(pattern, text):
            return False
    return True


def busy_and_window_s(trace: Trace, window: Optional[Interval] = None
                      ) -> Tuple[float, float]:
    lo, hi = window or window_of(trace)
    busy = [
        total(clip(union((e.start_ns, e.end_ns) for e in evs), lo, hi))
        for evs in trace.device_ops.values()
    ]
    return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9


def selected_seconds(trace: Trace, select: Dict[str, str],
                     line: str = OP_LINE,
                     window: Optional[Interval] = None) -> Tuple[float, int]:
    """(summed device seconds, number of events) of the events a selector
    picks, averaged over device planes."""
    lo, hi = window or window_of(trace)
    per_plane = trace.device_ops if line == OP_LINE else trace.device_modules
    secs, count = [], []
    for evs in per_plane.values():
        hit = [e for e in evs if e.start_ns < hi and e.end_ns > lo
               and matches(e, select)]
        secs.append(sum(e.dur_ns for e in hit) / 1e9)
        count.append(len(hit))
    if not secs:
        return 0.0, 0
    return sum(secs) / len(secs), int(round(sum(count) / len(count)))


def selected_durations_ms(trace: Trace, select: Dict[str, str],
                          line: str = MODULE_LINE,
                          window: Optional[Interval] = None) -> List[float]:
    lo, hi = window or window_of(trace)
    per_plane = trace.device_ops if line == OP_LINE else trace.device_modules
    out: List[float] = []
    for evs in per_plane.values():
        out += [e.dur_ns / 1e6 for e in evs
                if e.start_ns < hi and e.end_ns > lo and matches(e, select)]
    return out


def exposed_seconds(trace: Trace, select: Dict[str, str],
                    window: Optional[Interval] = None) -> float:
    """Seconds in which a selected operation (a collective) ran and no
    other operation did, averaged over device planes."""
    lo, hi = window or window_of(trace)
    out = []
    for evs in trace.device_ops.values():
        sel = union((e.start_ns, e.end_ns) for e in evs if matches(e, select))
        rest = union((e.start_ns, e.end_ns) for e in evs
                     if not matches(e, select))
        out.append(total(clip(subtract(sel, rest), lo, hi)) / 1e9)
    return sum(out) / len(out) if out else 0.0


def op_label(name: str) -> str:
    """An op line's event name is the whole HLO instruction; keep its
    name and the start of what it computes (result shape, operands)."""
    head, _, rest = name.partition(" = ")
    return f"{head} {rest[:100]}".strip()


def top_device_ops(trace: Trace, n: int = 10,
                   window: Optional[Interval] = None) -> List[List[Any]]:
    """[[name, seconds], ...]: the operations that took most device time
    (a `while` holds the operations of its body, which are listed too)."""
    lo, hi = window or window_of(trace)
    acc: Dict[str, float] = {}
    planes = max(1, len(trace.device_ops))
    for evs in trace.device_ops.values():
        for e in evs:
            if lo <= e.start_ns < hi:
                label = op_label(e.name)
                acc[label] = acc.get(label, 0.0) + e.dur_ns / 1e9 / planes
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k[:160], v] for k, v in ranked]


def device_gaps(trace: Trace, window: Optional[Interval] = None,
                plane: Optional[str] = None) -> List[Interval]:
    """The idle intervals of one device plane (the first by name) inside
    the window: disjoint, in time order."""
    lo, hi = window or window_of(trace)
    name = plane or sorted(trace.device_ops)[0]
    busy = clip(union((e.start_ns, e.end_ns)
                      for e in trace.device_ops[name]), lo, hi)
    return subtract([(lo, hi)], busy)


def name_gaps(gaps: Sequence[Interval], host_spans: Sequence[Event],
              n: int = 10) -> List[List[Any]]:
    """[[host span, seconds], ...]: the gaps' seconds, summed by the host
    span that covers each gap's middle; "(no host span)" where none does.
    Of the spans that cover a middle the one named is the first in the
    order (python threads before the runtime's, shorter before longer,
    then as `host_spans` lists them): the innermost.

    `gaps` are disjoint and in time order, so their middles rise: one
    sweep pushes each span onto a heap (keyed by its place in that order)
    once the middle has reached its start, and pops it from the top once
    the middle has passed its end. (gaps + spans) x log spans; the scan of
    every span for every gap that this replaces took 61-98 s of a traced
    serving run (PERF.md, PR 27)."""
    in_order = sorted(host_spans,
                      key=lambda e: (not e.stats.get("python"), e.dur_ns))
    by_start = sorted((sp.start_ns, place, sp.end_ns, sp.name)
                      for place, sp in enumerate(in_order))
    started = 0
    open_spans: List[Tuple[int, float, str]] = []  # (place, end_ns, name)
    acc: Dict[str, float] = {}
    for s, e in gaps:
        mid = (s + e) / 2
        while started < len(by_start) and by_start[started][0] <= mid:
            heapq.heappush(open_spans, by_start[started][1:])
            started += 1
        while open_spans and open_spans[0][1] <= mid:
            heapq.heappop(open_spans)
        cover = open_spans[0][2] if open_spans else "(no host span)"
        acc[cover] = acc.get(cover, 0.0) + (e - s) / 1e9
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k[:160], v] for k, v in ranked]


def idle_gaps(trace: Trace, n: int = 10, window: Optional[Interval] = None,
              plane: Optional[str] = None) -> List[List[Any]]:
    """[[host span, seconds], ...]: idle seconds on one device plane (the
    first by name), summed by the innermost host span covering each gap's
    middle; "(no host span)" where none does."""
    return name_gaps(device_gaps(trace, window, plane), trace.host_spans, n)


# -- looking at a trace by hand ---------------------------------------------------
def describe(path: str, top: int = 25) -> str:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    lines = []
    for plane in data.planes:
        lines.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            acc: Dict[str, List[float]] = {}
            keys: Dict[str, Any] = {}
            for e in evs:
                acc.setdefault(e.name, []).append(float(e.duration_ns))
                if len(keys) < 40:
                    for k, v in e.stats:
                        keys.setdefault(k, v)
            t0 = min(float(e.start_ns) for e in evs)
            t1 = max(float(e.start_ns) + float(e.duration_ns) for e in evs)
            lines.append(
                f"  LINE {line.name!r}: {len(evs)} events, "
                f"{t0 / 1e6:.3f}..{t1 / 1e6:.3f} ms"
            )
            lines.append(f"    stat keys: { {k: str(v)[:60] for k, v in keys.items()} }")
            ranked = sorted(acc.items(), key=lambda kv: -sum(kv[1]))[:top]
            for name, durs in ranked:
                lines.append(
                    f"    {sum(durs) / 1e6:10.3f} ms  x{len(durs):<5d} "
                    f"{name[:120]}"
                )
    return "\n".join(lines)


if __name__ == "__main__":
    print(describe(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 25))
