#!/usr/bin/env python
"""Turn a jax.profiler xplane trace into a per-subsystem step breakdown.

Usage:
    lumina train ... --profile-steps N                      # capture
    python scripts/analyze_trace.py <outdir> [n_steps]      # analyze

n_steps = how many steps the trace window covered. Requires the xprof
package (baked into the image); the conversion runs on CPU — no TPU
needed to analyze a saved trace.

The classifier and aggregation live in
luminaai_tpu/monitoring/attribution.py (tested API; the trainer's
--profile-steps windowed capture uses the same code path) — this script
is just the offline CLI. It also appends the breakdown to
<outdir>/attribution.jsonl so repeated analyses build a trend log.
"""
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main() -> None:
    from luminaai_tpu.monitoring.attribution import (
        attribute_xplane_dir,
        export_attribution,
    )
    from luminaai_tpu.monitoring.telemetry import MetricsRegistry

    outdir = sys.argv[1] if len(sys.argv) > 1 else "profiles/flagship"
    n_steps = int(sys.argv[2]) if len(sys.argv) > 2 else 3

    try:
        attr = attribute_xplane_dir(outdir, n_steps)
    except RuntimeError as e:
        sys.exit(str(e))
    export_attribution(
        attr,
        registry=MetricsRegistry(),  # offline: don't pollute the process sink
        jsonl_path=os.path.join(outdir, "attribution.jsonl"),
    )

    print(f"{'subsystem':38s} {'ms/step':>9s} {'%':>6s}  dominant bound")
    for g, ms in attr.ms_per_step.items():
        print(
            f"{g:38s} {ms:9.2f} {100 * attr.fraction[g]:5.1f}%  "
            f"{attr.dominant_bound[g]}"
        )
    print(f"{'TOTAL':38s} {attr.total_ms_per_step:9.2f}")

    # Top individual ops — where to look next.
    print("\nTop 10 ops by self time:")
    for op in attr.top_ops:
        print(
            f"{op['ms_per_step']:8.2f} ms/step {op['category'][:18]:18s} "
            f"{op['bound']:8s} {op['fw_name'][-70:]}"
        )


if __name__ == "__main__":
    main()
