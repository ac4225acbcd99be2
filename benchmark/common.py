"""What both kinds of cell share: the device refusal, peaks, compile
counting, the earlier-lines printer and the result line."""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
# Set-up is counted from here: the first import of the harness.
PROCESS_T0 = time.time()


class Refused(Exception):
    """The run may not produce a result line (no TPU, unknown device kind,
    wrong chip count)."""


def say(tag: str, **fields: Any) -> None:
    """One labelled earlier line; never the result line."""
    fields = {"t": round(time.time() - PROCESS_T0, 2), **fields}
    print(f"[benchmark:{tag}] " + json.dumps(fields, default=str),
          flush=True)


def load_peaks() -> Dict[str, Dict[str, float]]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        return {k: v for k, v in json.load(f).items() if not k.startswith("_")}


def require_device(chips: int) -> Dict[str, Any]:
    """The device as jax reports it, or Refused. No CPU fallback."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise Refused(f"needs a TPU; jax found platform {d.platform!r}")
    peaks = load_peaks()
    if d.device_kind not in peaks:
        raise Refused(
            f"device kind {d.device_kind!r} is not in peaks.json "
            f"(has {sorted(peaks)})"
        )
    if len(devs) != chips:
        raise Refused(f"cell asks for {chips} chip(s); jax found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "peak": peaks[d.device_kind]}


def memory_peak_bytes() -> int:
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()
    ]
    return int(max(peaks))


class CompileCounter:
    """Counts programs jax builds (lowers) from now on; a persistent-cache
    hit still lowers, so it counts too. Nothing may be built inside the
    measured window."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.lowered = 0
        self.backend_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_: Any) -> None:
        if event == self.LOWER:
            self.lowered += 1
        elif event == self.BACKEND:
            self.backend_s += seconds


def setup_compile_cache() -> str:
    """The program's own rule (JAX_COMPILATION_CACHE_DIR if set, else the
    fixed <checkout>/.jax_cache), and every program cached however fast it
    compiled, so that a second run finds them all."""
    import jax

    from luminaai_tpu.utils.environment import configure_compile_cache

    path = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def warm_profiler(tracer) -> float:
    """--trace 2, once the window's numbers are taken: start and stop the
    profiler once through the program's capture control and throw that
    trace away, so that the cost of its first start falls into no number.
    Returns the seconds it took."""
    import shutil
    import tempfile

    t0 = time.time()
    junk = tempfile.mkdtemp(prefix="benchmark_trace_warm_")
    try:
        if tracer.start_capture(junk):
            tracer.stop_capture()
    finally:
        shutil.rmtree(junk, ignore_errors=True)
    return time.time() - t0


def fold_seed(seed: int) -> int:
    """--seed may exceed 31 bits; jax keys and the Config take an int32."""
    return int(seed) % (2**31 - 1)


def metric_values(wanted: List[Dict[str, Any]], have: Dict[str, Optional[float]]
                  ) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in wanted:
        v = have.get(m["name"])
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Any], device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    return json.dumps(out)

