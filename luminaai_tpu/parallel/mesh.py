"""Device-mesh construction and multi-host initialization.

Replaces the reference's process-group plumbing (ref: Src/Main_Scripts/core/
backend/backend_deepspeed.py, backend_fsdp.py, backend_colossalai.py — NCCL
process groups, DeepSpeed ZeRO stages, FSDP wrapping). On TPU the single
abstraction is a `jax.sharding.Mesh` with named axes; every parallelism the
reference implements as a separate backend (ZeRO-3 == 'fsdp' axis, Megatron
TP == 'tensor' axis, expert parallel == 'expert' axis, sequence/context
parallel == 'sequence' axis, plain DDP == 'data' axis) is just a different
mesh shape + sharding rule set over the same train step. XLA inserts the
collectives (psum / all-gather / reduce-scatter / all-to-all) on ICI.
"""

from __future__ import annotations

import logging
import math
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from luminaai_tpu.config import Config

logger = logging.getLogger(__name__)

# Default axis order; overridden by Config.mesh_axes. Trailing axes get
# devices that are closest on the physical torus (mesh_utils places the last
# axis on the innermost ring), so the chattiest collectives (tensor) go last.
MESH_AXES = ("data", "pipe", "fsdp", "expert", "sequence", "tensor")


def mesh_shape_from_config(
    config: Config, n_devices: Optional[int] = None
) -> Dict[str, int]:
    """Resolve per-axis sizes; data axis (-1) absorbs remaining devices.

    Mirrors ref backend auto-sizing (world_size // model_parallel), but over
    six named axes instead of DeepSpeed's dp/mp split.
    """
    if n_devices is None:
        n_devices = jax.device_count()
    fixed = {
        "pipe": config.pipeline_parallel_size,
        "fsdp": config.fsdp_parallel_size,
        "expert": config.expert_parallel_size,
        "sequence": config.sequence_parallel_size,
        "tensor": config.tensor_parallel_size,
    }
    model_parallel = math.prod(fixed.values())
    if n_devices % model_parallel != 0:
        raise ValueError(
            f"device count {n_devices} not divisible by model-parallel "
            f"product {model_parallel} (pipe×fsdp×expert×sequence×tensor)"
        )
    dp = config.data_parallel_size
    if dp == -1:
        dp = n_devices // model_parallel
    if dp * model_parallel != n_devices:
        raise ValueError(
            f"mesh {dp}×{model_parallel} != {n_devices} devices; set "
            "data_parallel_size=-1 to auto-size"
        )
    return {"data": dp, **fixed}


def build_mesh(
    config: Config, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """Create the named device mesh for a config.

    Uses `mesh_utils.create_device_mesh` on real TPU slices so axis
    neighbours are ICI neighbours; falls back to a plain reshape for CPU
    meshes (virtual devices have no topology).
    """
    if devices is None:
        devices = jax.devices()
    axes = tuple(config.mesh_axes)
    if sorted(axes) != sorted(MESH_AXES):
        raise ValueError(
            f"mesh_axes must be a permutation of {MESH_AXES}, got {axes}"
        )
    shape = mesh_shape_from_config(config, len(devices))
    dims = tuple(shape[a] for a in axes)
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        device_array = mesh_utils.create_device_mesh(
            dims,
            devices=devices,
            allow_split_physical_axes=config.allow_split_physical_axes,
        )
    else:
        device_array = np.asarray(devices).reshape(dims)
    return Mesh(device_array, axes)


def shard_map(f, mesh, in_specs, out_specs, axis_names=None, check_vma=None):
    """`jax.shard_map` — the ONE entry point repo code calls (models/moe.py,
    ops/ring_attention.py, parallel/pipeline.py; astlint LX001).

    `axis_names` names the manual axes (any iterable; jax wants a set),
    `check_vma` the replication check. Passing neither keeps jax's default.
    """
    kw = {}
    if check_vma is not None:
        kw["check_vma"] = check_vma
    if axis_names is not None:
        kw["axis_names"] = frozenset(axis_names)
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kw
    )


def all_to_all(x, axis_name, *, split_axis, concat_axis, tiled=False,
               axis_index_groups=None):
    """`lax.all_to_all`, the ONE entry point repo code outside parallel/
    calls (astlint LX010). Keeping every explicit collective call site
    routed through parallel/ keeps them enumerable — the comms auditor
    (analysis/jaxpr_audit.enumerate_collectives) and the hierarchical
    dispatch groups (parallel/expert_dispatch.py) both rely on knowing
    where collectives enter model code."""
    return jax.lax.all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis,
        tiled=tiled, axis_index_groups=axis_index_groups,
    )


def ppermute(x, axis_name, perm):
    """`lax.ppermute` through the same sanctioned entry point (LX010) —
    ring attention's KV rotation and the pipeline's stage hops."""
    return jax.lax.ppermute(x, axis_name, perm)


# Explicit registry for the mesh the current trace runs under. The train
# step factories push here (use_mesh below); thread_resources is only a
# legacy fallback for code that entered `with mesh:` directly.
import contextlib
import threading

_ACTIVE_MESH = threading.local()


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """`with mesh:` plus registration for active_mesh()."""
    prev = getattr(_ACTIVE_MESH, "mesh", None)
    _ACTIVE_MESH.mesh = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _ACTIVE_MESH.mesh = prev


def active_mesh() -> Optional[Mesh]:
    """The Mesh whose use_mesh()/`with mesh:` context encloses the caller.

    Model code that needs explicit collectives (ring attention's shard_map)
    runs under the train step's trace context; this recovers that mesh
    without threading it through every flax module attribute. Checks the
    explicit registry first; falls back to the (deprecated) global mesh
    context for callers that used `with mesh:` directly.
    """
    mesh = getattr(_ACTIVE_MESH, "mesh", None)
    if mesh is not None:
        return mesh
    try:
        import warnings

        from jax.interpreters import pxla

        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            m = pxla.thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:  # API moved/removed; no implicit context available
        return None


def initialize_multihost(config: Config) -> None:
    """Bring up the JAX distributed runtime for multi-host training.

    Replaces ref NCCL/MPI env bootstrap (backend communication_backend=nccl;
    MASTER_ADDR/RANK env handling). Over TPU pods the coordination service
    only handles control-plane setup — data-plane collectives ride ICI/DCN
    via XLA, so there is no NCCL analogue to configure.
    """
    if not config.multihost:
        return
    kwargs = {}
    if config.coordinator_address is not None:
        kwargs["coordinator_address"] = config.coordinator_address
    if config.num_processes is not None:
        kwargs["num_processes"] = config.num_processes
    if config.process_id is not None:
        kwargs["process_id"] = config.process_id
    jax.distributed.initialize(**kwargs)
    logger.info(
        "multihost initialized: process %d/%d, %d local / %d global devices",
        jax.process_index(),
        jax.process_count(),
        jax.local_device_count(),
        jax.device_count(),
    )


def describe_mesh(mesh: Mesh) -> str:
    """Human-readable mesh summary for logs/reports."""
    parts = [f"{a}={s}" for a, s in zip(mesh.axis_names, mesh.devices.shape)]
    plat = mesh.devices.flat[0].platform
    return f"Mesh[{' × '.join(parts)}] on {mesh.devices.size} {plat} device(s)"
