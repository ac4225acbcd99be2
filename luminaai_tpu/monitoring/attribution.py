"""Performance attribution: turns the PR-2 registry's raw rates into
*why* numbers — where a step's FLOPs, bytes and milliseconds actually go.

Three layers, each usable alone:

1. **Compiled-cost accounting** (`compiled_cost_metrics`): run XLA's own
   cost model (`lowered.compile().cost_analysis()` / `memory_analysis()`)
   on an already-jitted step function and export what the COMPILER says
   the program costs — `compiled_flops_per_step`, `compiled_bytes_accessed`,
   peak/argument/output/temp HBM footprints — next to the analytic
   6·N·T estimate the MFU headline rests on. When the two diverge by more
   than `MFU_DIVERGENCE_THRESHOLD` the cross-check flags it: either the
   analytic model is under-counting (MoE capacity padding, remat
   recompute) or the program compiled something unexpected. Works under
   `JAX_PLATFORMS=cpu`; degrades to `{"available": False, ...}` when a
   backend returns no cost model rather than raising.

2. **Trace attribution** (`classify_op` / `attribute_trace`): the
   per-subsystem step breakdown behind `scripts/analyze_trace.py`, as a
   tested API. `classify_op` maps an
   XLA op's framework name / category / source line onto the model's
   subsystems (flash-attention kernels, MoE dispatch vs expert matmul,
   CE loss, ...); `attribute_trace` folds a whole hlo_stats table into
   ms/step + fraction per subsystem with the dominant roofline bound.

3. **Export** (`export_attribution` / gauges inside
   `compiled_cost_metrics`): everything lands in the unified metrics
   registry (monitoring/telemetry.py) — so `/metrics` and bench
   artifacts carry attribution, not just totals — and optionally as one
   JSONL record per capture for offline trend tooling.

Nothing here touches the device path: cost analysis is an AOT
compile-time query, trace attribution consumes an already-written
profile. No jax import at module scope (the registry contract).
"""

from __future__ import annotations

import json
import math
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Mapping, Optional

from luminaai_tpu.monitoring.telemetry import MetricsRegistry, get_registry

__all__ = [
    "DONATION_COVERAGE_THRESHOLD",
    "MFU_DIVERGENCE_THRESHOLD",
    "SUBSYSTEMS",
    "OpRow",
    "TraceAttribution",
    "analytic_train_flops",
    "attribute_trace",
    "attribute_xplane_dir",
    "classify_op",
    "compiled_cost_metrics",
    "donation_audit",
    "export_attribution",
    "kernel_census",
    "rows_from_hlo_stats",
    "tree_bytes",
]

# Analytic (6·N·T) vs compiled-FLOPs divergence beyond this fraction is
# flagged: the MFU headline and the compiler disagree about the program.
MFU_DIVERGENCE_THRESHOLD = 0.10

# A donated train step must alias (update in place) at least this
# fraction of its resident-state bytes; below it, param/opt-state buffers
# are being COPIED per step — double peak optimizer memory, the exact
# failure donate_argnums exists to prevent.
DONATION_COVERAGE_THRESHOLD = 0.90


# ---------------------------------------------------------------------------
# op classification (promoted from scripts/analyze_trace.py, r3)
# ---------------------------------------------------------------------------

# Canonical subsystem names, in the order reports print them. Keep in sync
# with classify_op's return values — test_attribution pins the mapping.
SUBSYSTEMS = (
    "attn_flash_kernels",
    "ce_loss",
    "moe_expert_matmul",
    "moe_route_dispatch",
    "attn_proj_rope",
    "data_formatting",
    "unattributed(optimizer+dispatch_bwd)",
    "other",
)

_EXPERT_MATMUL_RE = re.compile(r"egch,ehf|egcf,efh|gmm")


def classify_op(fw_name: str, category: str = "", source: str = "") -> str:
    """Map one XLA op onto a model subsystem.

    `fw_name` is the framework op name (jax named-scope path), `category`
    the HLO op category, `source` the source-info column. The rules are
    ordered most-specific-first; an empty framework name is the signature
    of XLA-fused optimizer/backward glue, which has no scope to attribute
    to — it reports as its own bucket rather than polluting "other".
    """
    if "attention" in fw_name and "pallas_call" in fw_name:
        return "attn_flash_kernels"
    if "bch,vh->bcv" in fw_name or "fused.py" in source:
        return "ce_loss"
    if _EXPERT_MATMUL_RE.search(fw_name):
        return "moe_expert_matmul"
    if "/moe/" in fw_name:
        return "moe_route_dispatch"
    if "attention/" in fw_name or "qkv" in fw_name:
        return "attn_proj_rope"
    if category == "data formatting":
        return "data_formatting"
    if not fw_name.strip():
        return "unattributed(optimizer+dispatch_bwd)"
    return "other"


@dataclass
class OpRow:
    """One profiled op: the subset of an xprof hlo_stats row the
    classifier needs. `self_time_us` is total self time across the whole
    trace window (all steps)."""

    self_time_us: float
    fw_name: str = ""
    category: str = ""
    source: str = ""
    bound_by: str = "?"


@dataclass
class TraceAttribution:
    """Per-subsystem step breakdown of one trace window."""

    n_steps: int
    ms_per_step: Dict[str, float]
    fraction: Dict[str, float]
    dominant_bound: Dict[str, str]
    total_ms_per_step: float
    top_ops: List[Dict[str, Any]] = field(default_factory=list)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "n_steps": self.n_steps,
            "total_ms_per_step": round(self.total_ms_per_step, 3),
            "subsystems": {
                name: {
                    "ms_per_step": round(self.ms_per_step[name], 3),
                    "fraction": round(self.fraction[name], 4),
                    "bound": self.dominant_bound[name],
                }
                for name in self.ms_per_step
            },
            "top_ops": self.top_ops,
        }


def attribute_trace(
    rows: Iterable[OpRow], n_steps: int = 1, top_k: int = 10
) -> TraceAttribution:
    """Fold profiled ops into the per-subsystem step breakdown.

    Subsystems are sorted by time (heaviest first) in the result dicts;
    `fraction` is of total self time, so it sums to ~1 regardless of how
    many steps the window covered."""
    if n_steps < 1:
        raise ValueError(f"n_steps must be >= 1, got {n_steps}")
    groups: Dict[str, float] = {}
    bounds: Dict[str, Dict[str, float]] = {}
    kept: List[OpRow] = []
    for r in rows:
        t = float(r.self_time_us or 0.0)
        g = classify_op(r.fw_name or "", r.category or "", r.source or "")
        groups[g] = groups.get(g, 0.0) + t
        bounds.setdefault(g, {})
        b = r.bound_by or "?"
        bounds[g][b] = bounds[g].get(b, 0.0) + t
        kept.append(r)
    total = sum(groups.values())
    order = sorted(groups, key=lambda g: -groups[g])
    kept.sort(key=lambda r: -float(r.self_time_us or 0.0))
    return TraceAttribution(
        n_steps=n_steps,
        ms_per_step={g: groups[g] / n_steps / 1e3 for g in order},
        fraction={g: (groups[g] / total if total else 0.0) for g in order},
        dominant_bound={
            g: max(bounds[g], key=bounds[g].get) if bounds[g] else "?"
            for g in order
        },
        total_ms_per_step=total / n_steps / 1e3,
        top_ops=[
            {
                "ms_per_step": round(
                    float(r.self_time_us or 0.0) / n_steps / 1e3, 3
                ),
                "category": (r.category or "")[:24],
                "bound": r.bound_by or "?",
                "fw_name": (r.fw_name or "")[-90:],
            }
            for r in kept[:top_k]
        ],
    )


def rows_from_hlo_stats(table: Mapping[str, Any]) -> List[OpRow]:
    """Adapt an xprof `hlo_stats` tool table ({"cols": [...], "rows":
    [...]} as returned by xspace_to_tool_data) into OpRows."""
    cols = [c["label"] for c in table["cols"]]
    idx = {c: i for i, c in enumerate(cols)}

    def cell(r, label):
        return r[idx[label]] if label in idx else None

    out = []
    for raw in table["rows"]:
        r = [c.get("v") for c in raw["c"]]
        out.append(
            OpRow(
                self_time_us=float(cell(r, "Total self time (us)") or 0.0),
                fw_name=cell(r, "Framework op name") or "",
                category=cell(r, "HLO op category") or "",
                source=re.sub(r"<[^>]+>", "", cell(r, "Source Info") or ""),
                bound_by=cell(r, "Bound by") or "?",
            )
        )
    return out


def attribute_xplane_dir(
    outdir: str, n_steps: int = 1, top_k: int = 10
) -> TraceAttribution:
    """Attribute a saved jax.profiler trace directory (the
    `plugins/profile/*/*.xplane.pb` layout the trainer's windowed
    capture writes). Requires the xprof
    package; raises RuntimeError with a actionable message when it (or
    the trace) is missing — callers on the training path catch and log."""
    import glob

    paths = glob.glob(
        os.path.join(outdir, "plugins/profile/*/*.xplane.pb")
    )
    if not paths:
        raise RuntimeError(f"no xplane.pb under {outdir}/plugins/profile/*/")
    try:
        from xprof.convert import raw_to_tool_data as rtd
    except ImportError as e:  # pragma: no cover - image bakes xprof in
        raise RuntimeError(f"xprof unavailable for trace analysis: {e}")
    data, _ = rtd.xspace_to_tool_data(paths, "hlo_stats", {})
    return attribute_trace(
        rows_from_hlo_stats(json.loads(data)), n_steps, top_k
    )


def export_attribution(
    attr: TraceAttribution,
    registry: Optional[MetricsRegistry] = None,
    jsonl_path: Optional[str] = None,
) -> Dict[str, Any]:
    """Publish a breakdown: per-subsystem gauges in the registry
    (`attribution_ms_per_step{subsystem=...}` etc.) and, when
    `jsonl_path` is given, one appended JSON record. Returns the record."""
    registry = registry or get_registry()
    g_ms = registry.gauge(
        "attribution_ms_per_step",
        "Per-subsystem self time per train step from the last trace window",
        labelnames=("subsystem",),
    )
    g_frac = registry.gauge(
        "attribution_fraction",
        "Per-subsystem fraction of total step self time",
        labelnames=("subsystem",),
    )
    for name in attr.ms_per_step:
        g_ms.labels(subsystem=name).set(attr.ms_per_step[name])
        g_frac.labels(subsystem=name).set(attr.fraction[name])
    registry.gauge(
        "attribution_total_ms_per_step",
        "Total attributed self time per step from the last trace window",
    ).set(attr.total_ms_per_step)
    record = attr.to_dict()
    if jsonl_path:
        os.makedirs(os.path.dirname(jsonl_path) or ".", exist_ok=True)
        with open(jsonl_path, "a") as f:
            f.write(json.dumps(record) + "\n")
    return record


# ---------------------------------------------------------------------------
# compiled-cost accounting
# ---------------------------------------------------------------------------

def analytic_train_flops(active_params: int, tokens_per_step: int) -> float:
    """The 6·N·T transformer estimate MFU headlines use (fwd 2NT + bwd
    4NT, on ACTIVE params). Per whole step across all chips."""
    return 6.0 * float(active_params) * float(tokens_per_step)


def _cost_dict(compiled) -> Optional[Dict[str, float]]:
    """Compiled.cost_analysis() as a flat float dict; None when the
    backend has no cost model (jax returns a dict or None)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if not isinstance(ca, dict) or not ca:
        return None
    return {str(k): float(v) for k, v in ca.items()
            if isinstance(v, (int, float))}


_KERNEL_OP_NAME = re.compile(r'op_name="[^"]*?(\w+)\)*/pallas_call')


def kernel_census(hlo_text: str) -> Dict[str, int]:
    """Count the Mosaic kernels (`tpu_custom_call`) in a compiled
    program's text by kernel name — the innermost scope in front of
    `/pallas_call` in the op's metadata (`flash_fwd`, `flash_bwd_dq`,
    `flash_bwd_dkv`, megablox `gmm` / `tgmm`, `lane_attention`).
    {} means the program holds no Pallas TPU kernel: a branch that took
    the interpreter or an XLA stand-in shows up here, whatever it
    claimed."""
    counts: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _KERNEL_OP_NAME.search(line)
        name = m.group(1) if m else "unnamed"
        counts[name] = counts.get(name, 0) + 1
    return counts


def compiled_cost_metrics(
    fn,
    *args,
    program: str = "train",
    registry: Optional[MetricsRegistry] = None,
    analytic_flops: Optional[float] = None,
    divergence_threshold: float = MFU_DIVERGENCE_THRESHOLD,
    **kwargs,
) -> Dict[str, Any]:
    """AOT-query XLA's cost model for a jitted callable and export it.

    `fn` may be a raw `jax.jit` function or a wrapper carrying one as
    `fn.jitted` (parallel/train_step.py attaches it); `args`/`kwargs`
    are example arguments of the real shapes/shardings. The compile hits
    the persistent XLA cache (utils/environment.configure_compile_cache),
    so after the step's own compile this costs parse time, not a
    recompile.

    Returns a JSON-able dict. On a backend that returns no cost model or
    a wrapper without a lowerable handle, returns `{"available": False, "reason":
    ...}` — callers embed that verbatim so absence is visible, never
    silent. With `analytic_flops` set, includes the analytic-vs-compiled
    MFU cross-check: `divergence = compiled/analytic - 1`, flagged when
    |divergence| > `divergence_threshold` (default 10%) — the two feed
    the same MFU denominator, so a large gap means the headline MFU and
    the compiled program disagree about the work being measured.
    """
    target = getattr(fn, "jitted", fn)
    lower = getattr(target, "lower", None)
    if lower is None:
        return {
            "available": False,
            "reason": f"{type(fn).__name__} has no .lower/.jitted handle",
        }
    try:
        compiled = lower(*args, **kwargs).compile()
    except Exception as e:
        return {
            "available": False,
            "reason": f"lower/compile failed: {type(e).__name__}: {e}",
        }
    out: Dict[str, Any] = {"available": True, "program": program}
    try:
        out["kernels"] = kernel_census(compiled.as_text())
    except Exception:  # a backend with no text dump still has costs
        out["kernels"] = None

    ca = _cost_dict(compiled)
    if ca is None:
        out["cost_model"] = None
        out["reason"] = "backend returned no cost model"
    else:
        flops = ca.get("flops")
        nbytes = ca.get("bytes accessed")
        out["cost_model"] = {
            "flops_per_step": flops,
            "bytes_accessed": nbytes,
            "arithmetic_intensity": (
                round(flops / nbytes, 3) if flops and nbytes else None
            ),
            "transcendentals": ca.get("transcendentals"),
        }

    mem: Dict[str, Any] = {}
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        for label, attr in (
            ("argument_bytes", "argument_size_in_bytes"),
            ("output_bytes", "output_size_in_bytes"),
            ("temp_bytes", "temp_size_in_bytes"),
            ("alias_bytes", "alias_size_in_bytes"),
            ("generated_code_bytes", "generated_code_size_in_bytes"),
        ):
            v = getattr(ma, attr, None)
            if v is not None:
                mem[label] = int(v)
        # Peak live footprint of one executable call: arguments stay
        # resident, outputs materialize, temps are the scratch high-water
        # mark — minus aliased bytes, so donated buffers (the train step
        # donates its whole TrainState) are counted once, not as both
        # argument and output.
        if mem:
            mem["peak_bytes"] = (
                mem.get("argument_bytes", 0)
                + mem.get("output_bytes", 0)
                + mem.get("temp_bytes", 0)
                + mem.get("generated_code_bytes", 0)
                - mem.get("alias_bytes", 0)
            )
    out["memory"] = mem or None

    flops = (out.get("cost_model") or {}).get("flops_per_step")
    if analytic_flops:
        xc: Dict[str, Any] = {
            "analytic_flops_per_step": analytic_flops,
            "compiled_flops_per_step": flops,
        }
        if flops:
            div = flops / analytic_flops - 1.0
            xc["divergence"] = round(div, 4)
            xc["flagged"] = bool(abs(div) > divergence_threshold)
            xc["threshold"] = divergence_threshold
        else:
            xc["divergence"] = None
            xc["flagged"] = False
            xc["note"] = "no compiled flops to cross-check"
        out["mfu_crosscheck"] = xc

    _export_cost_gauges(out, program, registry)
    return out


def tree_bytes(tree) -> int:
    """Total buffer bytes of a pytree of arrays or ShapeDtypeStructs —
    the resident-state denominator the donation audit divides by. Counts
    anything with (size, dtype); QuantizedTensor leaves flatten to their
    code/scale arrays, so they count at their stored width."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(tree):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is None or dtype is None:
            continue
        try:
            itemsize = int(np.dtype(dtype).itemsize)
        except TypeError:
            # Extended dtypes (typed PRNG keys) refuse np.dtype; their
            # itemsize attribute (when present) covers them, and a
            # scalar key is noise against param/opt bytes regardless.
            itemsize = int(getattr(dtype, "itemsize", 0) or 0)
        total += int(size) * itemsize
    return total


def donation_audit(
    memory: Optional[Mapping[str, Any]],
    donated_bytes: float,
    *,
    expected: bool = True,
    program: str = "train",
    registry: Optional[MetricsRegistry] = None,
    threshold: float = DONATION_COVERAGE_THRESHOLD,
) -> Dict[str, Any]:
    """Audit whether a compiled step actually donates its state buffers.

    `memory` is the dict `compiled_cost_metrics` returns under "memory"
    (XLA's buffer-assignment view of one executable); `donated_bytes` the
    resident bytes of the TrainState the caller donates (params + opt
    state + counters — `tree_bytes(state)`). XLA records every
    input→output aliasing it honored as `alias_bytes`, so

        coverage = alias_bytes / donated_bytes

    is the fraction of the state updated IN PLACE. Coverage below
    `threshold` with `expected=True` means donation silently broke —
    param/opt buffers are copied each step and peak HBM carries the
    state twice (the r3 profile's "optimizer + misc" bucket is where
    that shows up). The temp/state ratio rides along: scratch growth is
    the other way that bucket regresses without any code touching the
    optimizer. Flags, never raises; callers embed the verdict (bench
    `--smoke` extras, trainer cost export) so absence-of-donation is
    visible evidence, not a silent slowdown."""
    out: Dict[str, Any] = {
        "available": bool(memory),
        "program": program,
        "donated_bytes": int(donated_bytes) if donated_bytes else 0,
        "donation_expected": bool(expected),
    }
    if not memory:
        out["reason"] = "no memory analysis from this backend"
        return out
    alias = float(memory.get("alias_bytes") or 0.0)
    temp = float(memory.get("temp_bytes") or 0.0)
    out["alias_bytes"] = int(alias)
    out["temp_bytes"] = int(temp)
    if donated_bytes:
        cov = alias / float(donated_bytes)
        out["coverage"] = round(cov, 4)
        out["temp_to_state_ratio"] = round(temp / float(donated_bytes), 4)
        out["flagged"] = bool(expected and cov < threshold)
        out["threshold"] = threshold
    else:
        out["coverage"] = None
        out["flagged"] = False
        out["reason"] = "donated_bytes unknown"
    registry = registry or get_registry()
    if out.get("coverage") is not None:
        registry.gauge(
            "donation_alias_coverage",
            "alias_bytes / donated state bytes of the step executable "
            "(1.0 = full in-place update)",
            labelnames=("program",),
        ).labels(program=program).set(out["coverage"])
        registry.gauge(
            "donation_audit_flagged",
            "1 when donation was expected but alias coverage fell below "
            "the threshold",
            labelnames=("program",),
        ).labels(program=program).set(1.0 if out["flagged"] else 0.0)
    return out


def _export_cost_gauges(
    out: Dict[str, Any], program: str, registry: Optional[MetricsRegistry]
) -> None:
    registry = registry or get_registry()
    cm = out.get("cost_model") or {}
    mem = out.get("memory") or {}

    def gset(name, help_text, value):
        if value is None or (
            isinstance(value, float) and not math.isfinite(value)
        ):
            return
        registry.gauge(name, help_text, labelnames=("program",)).labels(
            program=program
        ).set(float(value))

    gset(
        "compiled_flops_per_step",
        "XLA cost-model FLOPs for one step executable",
        cm.get("flops_per_step"),
    )
    gset(
        "compiled_bytes_accessed",
        "XLA cost-model bytes accessed for one step executable",
        cm.get("bytes_accessed"),
    )
    gset(
        "compiled_hbm_peak_bytes",
        "Peak live bytes of one step call (args+outputs+temps+code)",
        mem.get("peak_bytes"),
    )
    gset(
        "compiled_hbm_argument_bytes",
        "Argument (resident state) bytes of the step executable",
        mem.get("argument_bytes"),
    )
    gset(
        "compiled_hbm_output_bytes",
        "Output bytes of the step executable",
        mem.get("output_bytes"),
    )
    gset(
        "compiled_hbm_temp_bytes",
        "Scratch/temp high-water bytes of the step executable",
        mem.get("temp_bytes"),
    )
    xc = out.get("mfu_crosscheck") or {}
    gset(
        "analytic_flops_per_step",
        "6·N·T analytic FLOPs the MFU headline assumes",
        xc.get("analytic_flops_per_step"),
    )
    if xc.get("divergence") is not None:
        gset(
            "compiled_mfu_divergence",
            "compiled/analytic FLOPs ratio minus 1; |x|>0.1 is flagged",
            xc.get("divergence"),
        )
        gset(
            "compiled_mfu_divergence_flagged",
            "1 when the analytic-vs-compiled FLOPs cross-check tripped",
            1.0 if xc.get("flagged") else 0.0,
        )
