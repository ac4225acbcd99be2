"""Environment detection: devices, topology, memory, recommended config.

Covers the reference environment module (ref: Src/Main_Scripts/utils/
environment.py — get_system_info, GPU/accelerator introspection, memory
estimates, recommended-config selection), re-targeted at JAX/TPU: the
accelerator story is `jax.devices()` + device memory_stats, topology is the
process/host layout JAX exposes, and the recommendation maps model memory
needs onto a mesh (fsdp/tp/ep) instead of CUDA settings.
"""

from __future__ import annotations

import os
import platform
import sys
from typing import Any, Dict, List, Optional

# Per-chip HBM for known TPU generations (GiB). Used when memory_stats()
# is unavailable (e.g. CPU hosts, some plugin backends).
_TPU_HBM_GB = {
    "v4": 32.0,
    "v5 lite": 16.0,
    "v5e": 16.0,
    "v5p": 95.0,
    "v6 lite": 32.0,
    "v6e": 32.0,
}

# Per-chip bf16 peak (FLOP/s) by generation — the MFU denominator.
# Public figures: v4 275T, v5e 197T, v5p 459T, v6e (Trillium) 918T.
_TPU_PEAK_FLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}


def _lookup_by_device_kind(kind: str, table: Dict[str, float], default):
    """Substring match of a device_kind against a generation table —
    shared by the HBM and peak-FLOPs lookups so they can't drift."""
    kind = kind.lower()
    for key, val in table.items():
        if key in kind:
            return val
    return default


def device_peak_flops(device=None) -> float:
    """bf16 peak FLOP/s for `device` (default: jax.devices()[0]) from the
    generation table. A device_kind that is not in the table raises: a
    utilization computed against another part's peak is not a number."""
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "")
    peak = _lookup_by_device_kind(kind, _TPU_PEAK_FLOPS, None)
    if peak is None:
        raise ValueError(
            f"no peak FLOP/s on record for device_kind {kind!r} "
            f"(known: {sorted(_TPU_PEAK_FLOPS)})"
        )
    return peak


_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives: where
    JAX_COMPILATION_CACHE_DIR says, else the fixed `<checkout>/.jax_cache`
    — the path is part of the cache key, so a directory that moves
    (tempfile, pid, time) never hits."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first
    compile; returns compile_cache_dir(). Where the variable is set, jax
    reads it itself and no directory is set in code. Called by cli.main,
    chip_smoke.py and the bench children."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    return compile_cache_dir()


def get_system_info() -> Dict[str, Any]:
    """Host-side software/hardware summary (ref environment.py
    get_system_info)."""
    info: Dict[str, Any] = {
        "python_version": sys.version.split()[0],
        "platform": platform.platform(),
        "cpu_count": os.cpu_count(),
    }
    try:
        import jax

        info["jax_version"] = jax.__version__
    except Exception:  # pragma: no cover - jax is a hard dep in practice
        info["jax_version"] = None
    try:
        import flax

        info["flax_version"] = flax.__version__
    except Exception:  # pragma: no cover
        info["flax_version"] = None
    try:
        page = os.sysconf("SC_PAGE_SIZE")
        info["host_memory_gb"] = round(
            page * os.sysconf("SC_PHYS_PAGES") / 1e9, 2
        )
        info["host_memory_available_gb"] = round(
            page * os.sysconf("SC_AVPHYS_PAGES") / 1e9, 2
        )
    except (ValueError, OSError):  # pragma: no cover - non-POSIX
        pass
    return info


def _device_memory_gb(device) -> Optional[float]:
    """Best-effort per-device memory: live stats, else known HBM table."""
    try:
        stats = device.memory_stats()
        if stats and "bytes_limit" in stats:
            return round(stats["bytes_limit"] / 1e9, 2)
    except Exception:
        pass
    return _lookup_by_device_kind(
        getattr(device, "device_kind", ""), _TPU_HBM_GB, None
    )


def get_device_info() -> Dict[str, Any]:
    """Accelerator summary (ref environment.py CUDA introspection block)."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    info: Dict[str, Any] = {
        "platform": d0.platform,
        "device_count": len(devices),
        "local_device_count": jax.local_device_count(),
        "device_kind": getattr(d0, "device_kind", "unknown"),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "memory_per_device_gb": _device_memory_gb(d0),
    }
    coords = getattr(d0, "coords", None)
    if coords is not None:
        info["topology_coords_present"] = True
        # Bounding box of chip coordinates ~ slice shape.
        all_coords = [d.coords for d in devices if hasattr(d, "coords")]
        if all_coords:
            dims = len(all_coords[0])
            info["topology_shape"] = tuple(
                max(c[i] for c in all_coords) + 1 for i in range(dims)
            )
    return info


def get_topology() -> Dict[str, Any]:
    """Process/host layout for multi-host planning (ref topology probing)."""
    import jax

    return {
        "process_count": jax.process_count(),
        "process_index": jax.process_index(),
        "devices_per_process": jax.local_device_count(),
        "global_devices": jax.device_count(),
    }


def estimate_training_memory_gb(config) -> Dict[str, float]:
    """Per-chip HBM need for a config under its parallelism settings."""
    est = config.memory_estimate_gb()
    model_shards = max(
        1,
        config.fsdp_parallel_size
        * max(1, config.tensor_parallel_size)
        * max(1, config.expert_parallel_size),
    )
    per_chip = {
        "params_gb": est["parameters_gb"] / model_shards,
        "optimizer_gb": est["optimizer_gb"] / model_shards,
        "activations_gb": est["activations_gb"],
        "total_gb": (est["parameters_gb"] + est["optimizer_gb"]) / model_shards
        + est["activations_gb"],
    }
    return {k: round(v, 3) for k, v in per_chip.items()}


def check_config_fits(config, n_devices: Optional[int] = None) -> Dict[str, Any]:
    """Does this config fit the detected hardware? (ref recommended-config
    validation). Returns {fits, per_chip_gb, available_gb, detail}."""
    dev = get_device_info()
    hbm = dev.get("memory_per_device_gb") or 16.0
    need = estimate_training_memory_gb(config)
    # config.max_memory_usage caps usable HBM (headroom for XLA scratch).
    budget = getattr(config, "max_memory_usage", 0.9)
    fits = need["total_gb"] <= hbm * budget
    return {
        "fits": fits,
        "per_chip_gb": need["total_gb"],
        "available_gb": hbm,
        "platform": dev["platform"],
        "device_count": n_devices or dev["device_count"],
        "detail": need,
    }


def recommend_preset(n_devices: Optional[int] = None) -> str:
    """Pick the largest preset that fits the detected fleet (ref
    environment.py recommended-config logic)."""
    from luminaai_tpu.config import ConfigPresets

    dev = get_device_info()
    n = n_devices or dev["device_count"]
    hbm = dev.get("memory_per_device_gb") or 16.0
    budget_gb = n * hbm * 0.92
    best = "debug"
    for name in ConfigPresets.available():
        cfg = ConfigPresets.get(name)
        total = cfg.memory_estimate_gb()
        need = total["parameters_gb"] + total["optimizer_gb"]
        if need <= budget_gb and cfg.estimate_parameters() > (
            ConfigPresets.get(best).estimate_parameters()
        ):
            best = name
    return best


def _backend_probe() -> Dict[str, Any]:
    """One real matmul on the default backend, cold and warm, plus live
    HBM occupancy."""
    import time as _time

    import jax
    import jax.numpy as jnp

    t0 = _time.perf_counter()
    x = jnp.ones((512, 512), jnp.bfloat16)
    float((x @ x).sum())
    cold = _time.perf_counter() - t0
    t0 = _time.perf_counter()
    float((x @ x).sum())
    warm = _time.perf_counter() - t0
    d = jax.devices()[0]
    try:
        stats = d.memory_stats() or {}
    except Exception:
        stats = {}
    return {
        "platform": d.platform,
        "devices": jax.device_count(),
        "device_kind": getattr(d, "device_kind", "unknown"),
        "cold_matmul_s": round(cold, 2),
        "warm_matmul_s": round(warm, 4),
        "hbm_in_use_gb": (
            round(stats["bytes_in_use"] / 1e9, 3)
            if "bytes_in_use" in stats else None
        ),
        "hbm_limit_gb": (
            round(stats["bytes_limit"] / 1e9, 2)
            if "bytes_limit" in stats else None
        ),
    }


def tpu_runtime_diagnostics() -> Dict[str, Any]:
    """Runtime probes for `cli diagnose` — the TPU counterpart of the
    reference's cuda_debug_script.py allocator/kernel diagnosis.

    Three findings an operator keeps rediscovering by hand:
      - backend reachability, via a REAL matmul. Asked in-process: a
        chip belongs to one process at a time, so a probing child would
        either be refused the chip this process holds or take it away;
      - HBM occupancy/limit from live memory_stats;
      - persistent XLA compile-cache state (entries, size, freshness —
        a cold cache explains a 'slow first step' report).
    """
    import glob
    import time as _time

    out: Dict[str, Any] = {}
    t0 = _time.monotonic()
    try:
        probe = _backend_probe()
        out["backend"] = {
            "status": "ok",
            "probe_seconds": round(_time.monotonic() - t0, 1),
            **probe,
        }
    except Exception as e:
        out["backend"] = {
            "status": "error",
            "probe_seconds": round(_time.monotonic() - t0, 1),
            "last_error": f"{type(e).__name__}: {e}"[-200:],
        }

    cache_dir = compile_cache_dir()
    if os.path.isdir(cache_dir):
        # Stat each entry once, tolerating concurrent eviction (bench/
        # sweep processes share this dir and JAX rewrites entries).
        sizes, mtimes = [], []
        for e in glob.glob(os.path.join(cache_dir, "*")):
            try:
                st = os.stat(e)
            except OSError:
                continue
            sizes.append(st.st_size)
            mtimes.append(st.st_mtime)
        out["compile_cache"] = {
            "dir": cache_dir,
            "entries": len(sizes),
            "total_mb": round(sum(sizes) / 1e6, 1),
            "newest_age_s": (
                round(_time.time() - max(mtimes)) if mtimes else None
            ),
        }
    else:
        out["compile_cache"] = {
            "dir": cache_dir,
            "note": "persistent compile cache is empty (nothing compiled "
                    "here yet)",
        }
    return out


def connectivity_probe(
    payload_mb: float = 4.0, iters: int = 5, registry=None
) -> Dict[str, Any]:
    """ICI/DCN connectivity probe for `cli diagnose` (the role of the
    reference's scripts/net.sh bandwidth/reachability check, TPU-side).

    Two findings an operator needs before debugging a slow or wedged
    multi-host job:

      - **per-host device visibility**: every process must see the same
        global device count and `process_count * local_device_count`
        must cover it — a host whose NICs came up without its ICI links
        shows up here, before a collective hangs;
      - **a small timed all-reduce per mesh axis**: `ici` (devices within
        this host's slice) and, when multiple processes exist, `dcn`
        (across hosts). A healthy axis completes in milliseconds;
        an axis that is orders of magnitude off its peers localizes the
        sick interconnect tier.

    Results are returned AND exported as `diagnose_*` gauges into the
    unified registry so a scraped `/metrics` carries the last probe.
    CPU-safe: on a single-host CPU backend the mesh degenerates to one
    `ici` axis of size (1..n_local) and the psum still executes — the
    numbers then validate the probe machinery, not an interconnect.

    """
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from luminaai_tpu.monitoring.telemetry import get_registry
    # astlint rule LX001 pins this wrapper as the one shard_map entry.
    from luminaai_tpu.parallel.mesh import shard_map

    registry = registry or get_registry()
    n_proc = jax.process_count()
    n_local = jax.local_device_count()
    n_global = jax.device_count()
    visibility: Dict[str, Any] = {
        "process_count": n_proc,
        "process_index": jax.process_index(),
        "local_device_count": n_local,
        "global_device_count": n_global,
        "platform": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        # Every device must belong to exactly one process and the global
        # view must tile evenly across hosts; False here means a host
        # joined the job blind to part of the slice.
        "visibility_ok": n_global == n_proc * n_local,
    }
    out: Dict[str, Any] = {"visibility": visibility, "allreduce": {}}
    _export_visibility_gauges(registry, visibility)

    if n_global % n_proc != 0:
        # A degraded slice (a host lost part of its devices) cannot form
        # the dcn×ici grid — and is exactly what this probe exists to
        # surface. The visibility report above already says which host
        # count is wrong; skip the all-reduce instead of crashing past
        # the evidence.
        out["allreduce"]["skipped"] = (
            f"device grid is ragged ({n_global} devices across {n_proc} "
            "processes): cannot build a dcn×ici mesh — see visibility"
        )
        return out

    # dcn × ici factorization of the device grid: hosts on the slow axis,
    # local chips on the fast one. Single-host runs probe ici only.
    devices = np.array(jax.devices()).reshape(n_proc, n_global // n_proc)
    mesh = Mesh(devices, ("dcn", "ici"))
    n_elems = max(1, int(payload_mb * 1e6 / 4))  # fp32 words

    for axis in ("ici", "dcn") if n_proc > 1 else ("ici",):
        axis_size = mesh.shape[axis]

        @jax.jit
        def _allreduce(x, axis=axis):
            return shard_map(
                lambda v: jax.lax.psum(v, axis),
                mesh=mesh,
                in_specs=PartitionSpec(axis),
                out_specs=PartitionSpec(),
            )(x)

        # Pad the payload up to a multiple of the axis size so the
        # leading dim shards evenly on odd device counts.
        length = -(-n_elems // axis_size) * axis_size
        x = jax.device_put(
            jnp.ones((length,), jnp.float32),
            NamedSharding(mesh, PartitionSpec(axis)),
        )
        try:
            _allreduce(x).block_until_ready()  # compile
            t0 = _time.perf_counter()
            for _ in range(iters):
                y = _allreduce(x)
            y.block_until_ready()
            dt = (_time.perf_counter() - t0) / iters
        except Exception as e:  # probe must never wedge diagnose
            out["allreduce"][axis] = {
                "size": axis_size, "error": f"{type(e).__name__}: {e}"
            }
            continue
        payload_bytes = x.size * 4
        out["allreduce"][axis] = {
            "size": axis_size,
            "payload_mb": round(payload_bytes / 1e6, 2),
            "mean_seconds": round(dt, 6),
            # Algorithmic bandwidth: bytes reduced per second. A size-1
            # axis reports it for completeness, but it measures copy
            # speed, not an interconnect.
            "algo_gbps": round(payload_bytes / max(dt, 1e-9) / 1e9, 3),
        }

    ar_s = registry.gauge(
        "diagnose_allreduce_seconds",
        "Mean timed all-reduce per mesh axis at last diagnose",
        labelnames=("axis",),
    )
    ar_bw = registry.gauge(
        "diagnose_allreduce_gbps",
        "Algorithmic all-reduce bandwidth per mesh axis at last diagnose",
        labelnames=("axis",),
    )
    for axis, rec in out["allreduce"].items():
        if isinstance(rec, dict) and "mean_seconds" in rec:
            ar_s.labels(axis=axis).set(rec["mean_seconds"])
            ar_bw.labels(axis=axis).set(rec["algo_gbps"])
    return out


def _export_visibility_gauges(registry, visibility: Dict[str, Any]) -> None:
    """diagnose_* visibility gauges — exported BEFORE any mesh math so a
    degraded slice (the case the probe exists for) still reports. Names
    avoid the _count suffix: the registry reserves histogram exposition
    suffixes _bucket/_sum/_count for histogram families."""
    g = registry.gauge
    g("diagnose_processes", "Hosts in the job at last diagnose").set(
        visibility["process_count"]
    )
    g(
        "diagnose_local_devices", "Devices visible to this process"
    ).set(visibility["local_device_count"])
    g(
        "diagnose_global_devices", "Global devices at last diagnose"
    ).set(visibility["global_device_count"])
    g(
        "diagnose_device_visibility_ok",
        "1 when global devices == process_count * local devices",
    ).set(1.0 if visibility["visibility_ok"] else 0.0)


def format_diagnostics(include_accelerator: bool = True) -> str:
    """Human-readable diagnostics block (ref Main.py:619
    print_system_diagnostics).

    include_accelerator=False skips every jax touch, so a caller whose
    backend probe failed (cli diagnose) still prints the host facts."""
    lines: List[str] = ["=" * 64, "SYSTEM DIAGNOSTICS", "=" * 64]
    sysinfo = get_system_info()
    lines.append("[host]")
    for k, v in sysinfo.items():
        lines.append(f"  {k}: {v}")
    if not include_accelerator:
        lines.append("[accelerator] skipped: backend probe did not answer")
        lines.append("=" * 64)
        return "\n".join(lines)
    try:
        dev = get_device_info()
        lines.append("[accelerator]")
        for k, v in dev.items():
            lines.append(f"  {k}: {v}")
        topo = get_topology()
        lines.append("[topology]")
        for k, v in topo.items():
            lines.append(f"  {k}: {v}")
    except Exception as e:  # backend can be unavailable
        lines.append(f"[accelerator] unavailable: {e}")
    lines.append("=" * 64)
    return "\n".join(lines)
