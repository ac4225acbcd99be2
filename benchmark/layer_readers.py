"""Per-layer metrics: each is a small data file (layer_metrics/<base>.json)
naming where its number comes from and one of a closed set of reductions.
A reader that finds nothing to read returns None and the harness leaves
the metric out of the line.

    {"from": "trace", "reduce": "sum_ms_per_step", "select": {...}}
    {"from": "trace", "reduce": "share_of_window_pct", "select": {...}}
    {"from": "trace", "reduce": "median_ms", "line": "XLA Modules", "select": {...}}
    {"from": "trace", "reduce": "exposed_pct", "select": {...}}
    {"from": "trace", "reduce": "idle_pct"}
    {"from": "trace", "reduce": "roofline_pct",
     "kernels": [{"select": {...}, "fn": "<name in flops.KERNEL_FNS>"}]}
    {"from": "registry", "reduce": "ratio", "num": <term>, "den": <term>, "scale": 1}
         term: {"counter": name} | {"hist_sum": name} | {"hist_count": name}
               | {"host": key}
    {"from": "host", "key": "<value the harness measured>"}

`select` maps "name" (the event's name) or a stat key of the event to a
regular expression; all must match.
"""

from __future__ import annotations

import os
import statistics
import time
from typing import Any, Dict, Optional

from benchmark import flops, trace_reduce
from benchmark.common import say


class Context:
    """What one run offers the readers."""

    def __init__(self, *, trace=None, window=None, steps: int = 0,
                 registry_delta: Optional[Dict[str, float]] = None,
                 host: Optional[Dict[str, float]] = None,
                 body=None, shapes=None, peak=None):
        self.trace = trace            # trace_reduce.Trace or None
        self.window = window          # (lo_ns, hi_ns) or None
        self.steps = steps            # steps inside the traced window
        self.registry_delta = registry_delta or {}
        self.host = host or {}
        self.body, self.shapes, self.peak = body, shapes, peak
        self.notes: Dict[str, Any] = {}


def _term(ctx: Context, term: Dict[str, str]) -> Optional[float]:
    if "host" in term:
        return ctx.host.get(term["host"])
    (kind, name), = term.items()
    return ctx.registry_delta.get(f"{kind}:{name}")


def read(name: str, spec: Dict[str, Any], ctx: Context) -> Optional[float]:
    src = spec["from"]
    if src == "host":
        return ctx.host.get(spec["key"])
    if src == "registry":
        num, den = _term(ctx, spec["num"]), _term(ctx, spec["den"])
        if num is None or not den:
            return None
        return spec.get("scale", 1.0) * num / den
    if src != "trace":
        raise ValueError(f"{name}: unknown source {src!r}")
    tr = ctx.trace
    if tr is None or not tr.device_ops:
        return None
    how = spec["reduce"]
    if how == "idle_pct":
        busy, win = trace_reduce.busy_and_window_s(tr, ctx.window)
        return 100.0 * (1.0 - busy / win)
    if how == "roofline_pct":
        least = spent = 0.0
        bounds = {}
        for k in spec["kernels"]:
            secs, calls = trace_reduce.selected_seconds(
                tr, k["select"], window=ctx.window)
            if not calls:
                continue
            work = flops.KERNEL_FNS[k["fn"]](ctx.body, ctx.shapes)
            t, bound = flops.least_seconds(work, ctx.peak)
            least += t * calls
            spent += secs
            bounds[k["fn"]] = {"bound": bound, "calls": calls,
                               "seconds": secs, "least_seconds": t * calls}
        ctx.notes[name] = bounds
        return 100.0 * least / spent if spent else None
    line = spec.get("line", trace_reduce.OP_LINE)
    if how == "median_ms":
        durs = trace_reduce.selected_durations_ms(
            tr, spec["select"], line=line, window=ctx.window)
        ctx.notes[name] = {"events": len(durs)}
        return statistics.median(durs) if durs else None
    if how == "exposed_pct":
        secs, calls = trace_reduce.selected_seconds(
            tr, spec["select"], window=ctx.window)
        if not calls:
            return None
        _, win = trace_reduce.busy_and_window_s(tr, ctx.window)
        exposed = trace_reduce.exposed_seconds(tr, spec["select"], ctx.window)
        ctx.notes[name] = {"collective_seconds": secs, "exposed_seconds": exposed}
        return 100.0 * exposed / win
    secs, calls = trace_reduce.selected_seconds(
        tr, spec["select"], line=line, window=ctx.window)
    if not calls:
        return None
    if how == "sum_ms_per_step":
        return 1e3 * secs / ctx.steps if ctx.steps else None
    if how == "share_of_window_pct":
        _, win = trace_reduce.busy_and_window_s(tr, ctx.window)
        return 100.0 * secs / win
    raise ValueError(f"{name}: unknown reduction {how!r}")


def registry_view(registry) -> Dict[str, float]:
    """Counters and histogram sums/counts of a luminaai_tpu
    MetricsRegistry as one flat dict (labelled families summed)."""
    out: Dict[str, float] = {}
    for fam in registry.families():
        kids = fam.children()
        if fam.type == "counter":
            out[f"counter:{fam.name}"] = float(sum(c.value for c in kids))
        elif fam.type == "histogram":
            out[f"hist_sum:{fam.name}"] = float(sum(c.sum for c in kids))
            out[f"hist_count:{fam.name}"] = float(sum(c.count for c in kids))
    return out


def delta(after: Dict[str, float], before: Dict[str, float]
          ) -> Dict[str, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def reduce_traced_run(trace_dir: str, cell, ctx_kwargs: Dict[str, Any],
                      keep_as: Optional[str] = None):
    """The traced run's tail, the same for every kind of cell: load the
    trace, read the cell's per-layer metrics, and name the heaviest device
    operations and the longest idle gaps. Returns (values, busy_s,
    window_s, breakdown, notes). Prints one `[benchmark:reduce]` line with
    the seconds each of those took and the counts they grow with, so that
    the log of a run that is cut names the phase it was cut in."""
    import shutil

    marks = [time.time()]

    def lap() -> float:
        marks.append(time.time())
        return marks[-1] - marks[-2]

    xplane = trace_reduce.find_xplane(trace_dir)
    if keep_as:
        shutil.copy(xplane, keep_as + ".xplane.pb")
    tr = trace_reduce.load(xplane)
    spent = {"load_s": lap()}
    win = trace_reduce.window_of(tr)
    busy, win_s = trace_reduce.busy_and_window_s(tr, win)
    ctx = Context(trace=tr, window=win, **ctx_kwargs)
    values = {
        name: read(name, spec, ctx)
        for name, spec in cell.layer_metric_specs().items()
    }
    spent["per_layer_s"] = lap()
    device_ops = trace_reduce.top_device_ops(tr, 10, win)
    spent["top_device_ops_s"] = lap()
    gaps = trace_reduce.device_gaps(tr, win)
    breakdown = {
        "device_ops": device_ops,
        "idle_gaps": trace_reduce.name_gaps(gaps, tr.host_spans, 10),
    }
    spent["idle_gaps_s"] = lap()
    say("reduce", **spent, xplane_bytes=os.path.getsize(xplane),
        device_ops=sum(len(evs) for evs in tr.device_ops.values()),
        gaps=len(gaps), host_spans=len(tr.host_spans))
    return values, busy, win_s, breakdown, ctx.notes
