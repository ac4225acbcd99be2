"""Hierarchical cross-host gradient reduction with backward/comms overlap.

ROADMAP item 3's other cross-host hot path (the first was MoE token
dispatch, parallel/expert_dispatch.py): fsdp/dp gradient reduction.
Under `grad_reduce="flat"` that sync is whatever GSPMD emits — implicit
all-reduces at full fp32 width, invisible to the comms auditor, and
under gradient accumulation free to re-issue per microbatch inside the
accumulation scan. Scalable pjit/TPUv4 training (arxiv 2204.06514) and
X-MoE's hierarchical exchange (arxiv 2508.13337) both prescribe the
same cure, implemented here as `grad_reduce="hierarchical"`:

  - **shard-local accumulation, one deferred sync**: the whole
    forward/backward/accumulation scan runs inside a partial-auto
    shard_map manual over (data, fsdp). Gradients accumulate
    shard-locally in fp32 across every microbatch; the ONLY collectives
    inside the scan are scalar loss-normalization psums. The H-wide
    payload crosses the wire exactly once, post-scan — the before/after
    collective census is pinned by analysis/jaxpr_audit.audit_grad_reduce.

  - **size-bucketed hierarchical sync**: the gradient pytree flattens
    into fp32 buckets (`grad_reduce_bucket_mb`); each bucket
    reduce-scatters over the ici tier (the fsdp axis plus the in-host
    factor of the data axis), crosses DCN once via a grouped psum over
    the strided cross-host rails (`gradient_dcn_size` factors the data
    axis, reusing the a2a dispatch's `hierarchical_groups`), and
    all-gathers back. DCN sees 1/ici-tier of the payload — few large
    rail-aligned messages instead of a full-width flat ring.

  - **overlap**: buckets are data-independent of each other
    (`grad_reduce_overlap_chunks` floors the bucket count), so bucket
    k's DCN hop overlaps bucket k-1's all-gather under XLA's
    latency-hiding scheduler.

  - **optional DCN compression**: `grad_reduce_dcn_dtype='bf16'` casts
    only the DCN hop down — each shard's scattered chunk is already the
    full fp32 in-host sum before the cast, so in-host accumulation
    precision is untouched. Parity-gated in tests/test_grad_reduce.py.

Loss semantics: the implicit path computes each microbatch's loss as a
weighted mean over the GLOBAL microbatch. Inside the manual region each
shard sees only its slice, so the local loss is rescaled by
local_denom / max(psum(weight_sum), 1) — the gradient of the sum of
those rescaled local losses is exactly the gradient of the global
weighted mean (empty shard slices included), at the cost of one scalar
psum per microbatch. Model AUX losses (MoE load balance, router z) are
computed per shard and averaged (rescale 1/world) — the standard
data-parallel-local balance formulation. For the balance loss, which
is NONLINEAR in the batch routing statistics (Σ_e f_e·p_e of per-shard
fractions ≠ the global-batch product), that is a deliberately
different regularizer from the flat path's global-batch aux: the CE
gradient stays exact, the aux gradient constrains balance per shard
instead of in aggregate. Loss-trajectory parity vs the implicit path
is therefore pinned at 1e-6 for dense models (dp and dp×fsdp CPU
meshes, grad accumulation on and off); MoE configs are pinned at
loose tolerance only (tests/test_grad_reduce.py).

Accumulation-partition caveat: with accum > 1 the manual region slices
microbatches SHARD-LOCALLY (each shard splits its contiguous rows),
while GSPMD's reshape redistributes rows so global microbatch i is a
different row set. With uniform per-row loss weights — the normal LM
case — every partition yields the identical gradient (equal
per-microbatch denominators) and 1e-6 parity holds; with NONUNIFORM
per-row weights the two paths weight microbatches differently (both
are valid equal-weight-per-microbatch accumulation semantics, matching
would cost an extra exchange per microbatch).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from luminaai_tpu.parallel.expert_dispatch import hierarchical_groups
from luminaai_tpu.parallel.mesh import (
    all_gather,
    psum,
    psum_scatter,
    shard_map,
)

logger = logging.getLogger(__name__)

__all__ = [
    "GradReducePlan",
    "make_grad_reduce_plan",
    "export_grad_reduce_gauges",
    "hierarchical_grad_sync",
    "make_hierarchical_grad_fn",
    "grad_reduce_probe",
]


# --------------------------------------------------------------------------
# static plan: bucket layout + byte accounting
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GradReducePlan:
    """Static shape/byte plan for one hierarchical gradient sync.

    Derived purely from gradient avals and config at trace time — the
    numbers describe the traced program, not a run — so bench extras and
    the comms auditor can price the sync without hardware. Per shard,
    per optimizer step:

      ici-tier bytes = the reduce-scatter + all-gather legs: the full
        fp32 bucket payload enters/leaves each shard once each way,
        ring-style (~2*(t-1)/t of it off-chip for a tier of t shards).
      dcn bytes      = the grouped psum over the cross-host rails: each
        shard's SCATTERED chunk (1/ici_tier of the payload) rides a
        ring over the dcn hosts, at `dcn_itemsize` width.

    The flat GSPMD baseline moves the whole fp32 gradient through one
    logical all-reduce whose DCN-crossing share is ~2*(dcn-1)/dcn of
    the full payload — `flat_dcn_bytes`. The hierarchical advantage is
    structural: DCN traffic scales like 1/ici_tier (× 1/2 again under
    bf16 compression) of the flat baseline's.
    """

    world: int            # data * fsdp shards participating in the sync
    dcn: int              # host tier size (1 = single-stage fallback)
    data_size: int
    fsdp_size: int
    grad_bytes: int       # fp32 bytes of the flattened gradient
    padded_bytes: int     # after bucket/scatter padding
    n_buckets: int
    bucket_bytes: int     # per-bucket fp32 bytes (padded/n_buckets)
    overlap_chunks: int
    dcn_itemsize: int     # 4 (fp32) or 2 (bf16-over-DCN)

    @property
    def ici_tier(self) -> int:
        """Shards reduced per host before anything crosses DCN."""
        return self.world // self.dcn

    def stage_bytes(self, stage: str) -> int:
        """One-direction off-device payload bytes per shard for a tier;
        0 when the tier has one participant."""
        if stage == "ici":
            t = self.ici_tier
            return (
                int(self.padded_bytes * (t - 1) / t) if t > 1 else 0
            )
        scattered = self.padded_bytes // max(1, self.ici_tier)
        scattered = scattered * self.dcn_itemsize // 4
        d = self.dcn
        return int(scattered * (d - 1) / d) if d > 1 else 0

    @property
    def hier_dcn_bytes(self) -> int:
        """DCN-crossing bytes per shard per step (reduce + broadcast
        halves of the rail psum)."""
        return 2 * self.stage_bytes("dcn")

    @property
    def flat_dcn_bytes(self) -> int:
        """The implicit GSPMD baseline: one full-width fp32 all-reduce,
        ~2*(dcn-1)/dcn of the whole gradient crossing hosts."""
        d = self.dcn
        return (
            int(2 * self.grad_bytes * (d - 1) / d) if d > 1 else 0
        )

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d.update(
            ici_tier=self.ici_tier,
            ici_stage_bytes=self.stage_bytes("ici"),
            dcn_stage_bytes=self.stage_bytes("dcn"),
            hier_dcn_bytes=self.hier_dcn_bytes,
            flat_dcn_bytes=self.flat_dcn_bytes,
        )
        return d


def make_grad_reduce_plan(
    *,
    grad_elems: int,
    data_size: int,
    fsdp_size: int,
    dcn_size: int = 1,
    bucket_mb: float = 32.0,
    overlap_chunks: int = 1,
    dcn_dtype: Optional[str] = None,
) -> GradReducePlan:
    """Resolve the static bucket layout for a gradient of `grad_elems`
    fp32 elements on a (data, fsdp) grid.

    Bucket count = max(size-derived count, overlap_chunks); the flat
    vector pads to a multiple of n_buckets * scatter_factor so every
    bucket reduce-scatters evenly over the ici tier."""
    data_size = max(1, int(data_size))
    fsdp_size = max(1, int(fsdp_size))
    dcn = max(1, int(dcn_size))
    if data_size % dcn:
        raise ValueError(
            f"gradient_dcn_size {dcn} must divide the data axis "
            f"{data_size}"
        )
    world = data_size * fsdp_size
    grad_bytes = int(grad_elems) * 4
    bucket_bytes = max(1, int(bucket_mb * 2**20))
    n_buckets = max(
        -(-grad_bytes // bucket_bytes), max(1, int(overlap_chunks))
    )
    n_buckets = min(n_buckets, max(1, int(grad_elems)))
    scatter = fsdp_size * (data_size // dcn)
    quantum = n_buckets * scatter
    padded = -(-max(1, int(grad_elems)) // quantum) * quantum
    return GradReducePlan(
        world=world,
        dcn=dcn,
        data_size=data_size,
        fsdp_size=fsdp_size,
        grad_bytes=grad_bytes,
        padded_bytes=padded * 4,
        n_buckets=n_buckets,
        bucket_bytes=padded * 4 // n_buckets,
        overlap_chunks=max(1, int(overlap_chunks)),
        dcn_itemsize=2 if dcn_dtype == "bf16" else 4,
    )


def export_grad_reduce_gauges(plan: GradReducePlan, registry=None) -> None:
    """grad_reduce_bytes{stage} gauges from the static plan. Best-effort
    — the plan is built at trace time inside the train step, so this
    must never break a trace over a telemetry hiccup (same contract as
    expert_dispatch.export_plan_gauges)."""
    try:
        from luminaai_tpu.monitoring.telemetry import get_registry

        registry = registry or get_registry()
        g = registry.gauge(
            "grad_reduce_bytes",
            "Static per-shard one-direction payload bytes of the "
            "hierarchical gradient sync per tier (from the "
            "GradReducePlan, trace time)",
            labelnames=("stage",),
        )
        g.labels(stage="ici").set(float(plan.stage_bytes("ici")))
        g.labels(stage="dcn").set(float(plan.stage_bytes("dcn")))
        registry.gauge(
            "grad_reduce_buckets",
            "Size-bucketed chunk count of the hierarchical gradient "
            "sync at last trace",
        ).set(float(plan.n_buckets))
    except Exception:  # pragma: no cover - telemetry must not break traces
        logger.debug("grad_reduce_bytes gauge export failed", exc_info=True)


# --------------------------------------------------------------------------
# the sync itself (runs inside a shard_map body, manual over data+fsdp)
# --------------------------------------------------------------------------


def hierarchical_grad_sync(
    grads,
    *,
    data_axis: str = "data",
    fsdp_axis: str = "fsdp",
    data_size: int,
    fsdp_size: int,
    dcn_size: int = 1,
    bucket_mb: float = 32.0,
    overlap_chunks: int = 1,
    dcn_dtype: Optional[str] = None,
    plan_out: Optional[Dict[str, Any]] = None,
    registry=None,
):
    """Reduce a pytree of SHARD-LOCAL partial gradients to the global
    sum, staged ici-then-dcn. Must run inside a shard_map body manual
    over (data_axis, fsdp_axis).

    Pipeline per bucket: reduce-scatter over the fsdp axis (always
    in-host), reduce-scatter over the in-host factor of the data axis
    (contiguous groups), ONE grouped psum over the strided cross-host
    rails (optionally bf16), all-gather back in reverse order. Buckets
    are mutually data-independent so XLA overlaps bucket k's DCN hop
    with bucket k-1's gather. Leaves return in their original dtypes.
    """
    leaves, treedef = jax.tree.flatten(grads)
    if not leaves:
        return grads
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    sizes = [int(l.size) for l in leaves]
    total = sum(sizes)
    plan = make_grad_reduce_plan(
        grad_elems=total,
        data_size=data_size,
        fsdp_size=fsdp_size,
        dcn_size=dcn_size,
        bucket_mb=bucket_mb,
        overlap_chunks=overlap_chunks,
        dcn_dtype=dcn_dtype,
    )
    if plan_out is not None:
        plan_out["plan"] = plan
    export_grad_reduce_gauges(plan, registry=registry)

    dcn = plan.dcn
    ici_d = data_size // dcn  # in-host factor of the data axis
    padded = plan.padded_bytes // 4
    flat = jnp.concatenate(
        [l.astype(jnp.float32).reshape(-1) for l in leaves]
    )
    if padded != total:
        flat = jnp.pad(flat, (0, padded - total))
    cl = padded // plan.n_buckets
    g1 = g2 = None
    if dcn > 1:
        g1, g2 = hierarchical_groups(data_size, dcn)

    pieces = []
    for k in range(plan.n_buckets):
        c = flat[k * cl:(k + 1) * cl]
        if fsdp_size > 1:
            c = psum_scatter(c, fsdp_axis, scatter_dimension=0, tiled=True)
        if data_size > 1:
            if ici_d > 1:
                c = psum_scatter(
                    c, data_axis, scatter_dimension=0, tiled=True,
                    axis_index_groups=g1,
                )
            if dcn > 1:
                # The one DCN crossing per bucket. Under bf16
                # compression only this hop narrows: each shard's
                # scattered chunk already holds the full fp32 in-host
                # sum before the cast.
                if dcn_dtype == "bf16":
                    c = c.astype(jnp.bfloat16)
                    if jax.default_backend() == "cpu":
                        # XLA:CPU (jaxlib 0.9.0) aborts the process in
                        # AllReducePromotion on a bf16 all-reduce under
                        # a partial-auto shard_map ("Invalid binary
                        # instruction opcode copy"); the CPU mesh has no
                        # DCN to save bytes on, so sum the bf16-rounded
                        # chunks in fp32 there and round the result.
                        c = psum(
                            c.astype(jnp.float32), data_axis,
                            axis_index_groups=g2,
                        ).astype(jnp.bfloat16)
                    else:
                        c = psum(c, data_axis, axis_index_groups=g2)
                    c = c.astype(jnp.float32)
                else:
                    c = psum(c, data_axis, axis_index_groups=g2)
            if ici_d > 1:
                c = all_gather(
                    c, data_axis, axis=0, tiled=True,
                    axis_index_groups=g1,
                )
        if fsdp_size > 1:
            c = all_gather(c, fsdp_axis, axis=0, tiled=True)
        pieces.append(c)
    out = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
    out = out[:total]

    synced = []
    offset = 0
    for shape, dtype, size in zip(shapes, dtypes, sizes):
        synced.append(
            out[offset:offset + size].reshape(shape).astype(dtype)
        )
        offset += size
    return jax.tree.unflatten(treedef, synced)


# --------------------------------------------------------------------------
# the shard_map wrapper: local accumulation + deferred sync
# --------------------------------------------------------------------------

_WSCALE_KEYS = ("ce_loss", "total_loss", "z_loss")


def _make_local_loss_fn(
    loss_fn: Callable, axes: Tuple[str, ...], world: int
) -> Callable:
    """Wrap a (params, batch, rng) -> (loss, metrics) loss so its
    per-shard gradient SUMS to the implicit path's global gradient.

    The CE loss is a weighted mean over the global microbatch; each
    shard rescales its local mean by local_denom / psum-denom (one
    scalar psum — the weight sums carry no parameter gradient, so
    autodiff sees a data-dependent constant). Model aux losses rescale
    by 1/world: per-shard aux averaged over shards — exact for
    aux terms linear in per-token stats, a per-shard (rather than
    global-batch) regularizer for the nonlinear MoE balance product
    (see module docstring). Metrics are combined to the implicit
    path's global values: weight-scaled for the CE family, summed for
    token counts, pmean'd otherwise."""
    from luminaai_tpu.parallel.train_step import (
        _shifted_mask_weights,
        shift_labels,
    )

    def local_loss(params, batch, rng):
        total, metrics = loss_fn(params, batch, rng)
        _, valid = shift_labels(batch)
        mask, weights = _shifted_mask_weights(batch, valid)
        w = mask if weights is None else mask * weights
        # local_denom mirrors the CE's own max(w_sum, 1) clamp; the
        # GLOBAL denominator clamps the RAW psum (not a sum of clamped
        # locals) so a shard whose slice is all padding contributes 0
        # without inflating the divisor — exactly the implicit path's
        # max(global_w_sum, 1).
        raw_w = w.sum()
        local_denom = jnp.maximum(raw_w, 1.0)
        global_denom = jnp.maximum(jax.lax.psum(raw_w, axes), 1.0)
        wscale = local_denom / global_denom
        ce_part = metrics.get("total_loss", total)
        aux_part = total - ce_part
        scaled = ce_part * wscale + aux_part * (1.0 / world)
        out: Dict[str, jax.Array] = {}
        for key, v in metrics.items():
            if key == "perplexity":
                continue  # recomputed from the global ce below
            if key == "tokens_in_loss":
                out[key] = jax.lax.psum(v, axes)
            elif key in _WSCALE_KEYS:
                out[key] = jax.lax.psum(v * wscale, axes)
            elif key == "loss":
                out[key] = jax.lax.psum(scaled, axes)
            else:
                out[key] = jax.lax.pmean(v, axes)
        if "ce_loss" in out:
            out["perplexity"] = jnp.exp(jnp.clip(out["ce_loss"], max=20.0))
        return scaled, out

    return local_loss


def make_hierarchical_grad_fn(
    config, loss_fn: Callable, mesh, accum: int
) -> Callable:
    """Build the explicit gradient stage for make_train_step:
    `(params, batch, rng) -> (grads, metrics)` with grads fully reduced
    over (data, fsdp) by the hierarchical sync.

    Everything — microbatch scan included — runs inside ONE partial-auto
    shard_map manual over (data, fsdp); tensor/expert/sequence stay
    automatic (all but data/fsdp must be trivial or auto-partitionable,
    enforced by config.validate). Params enter replicated over the
    manual axes (fsdp-sharded params are gathered at region entry — the
    ZeRO-2 trade the explicit sync currently makes; grads and optimizer
    state stay sharded outside). The returned fn also carries a
    `plan_box` dict that holds the GradReducePlan after first trace."""
    from flax import linen as nn
    from jax.sharding import PartitionSpec as P

    from luminaai_tpu.parallel.sharding import manual_axis_rules
    from luminaai_tpu.parallel.train_step import _accumulate_grads

    data_axis, fsdp_axis = "data", "fsdp"
    data_size = int(mesh.shape[data_axis])
    fsdp_size = int(mesh.shape[fsdp_axis])
    world = data_size * fsdp_size
    dcn = int(config.gradient_dcn_size)
    if data_size % dcn:
        raise ValueError(
            f"gradient_dcn_size {dcn} must divide the mesh data axis "
            f"({data_size})"
        )
    axes = (data_axis, fsdp_axis)
    local_loss = _make_local_loss_fn(loss_fn, axes, world)
    rules = manual_axis_rules(config, axes)
    plan_box: Dict[str, Any] = {}

    def body(params, batch, rng):
        # Distinct per-shard rng stream: with routing noise / dropout
        # ON, each shard draws iid noise for its own rows (the implicit
        # path draws one global tensor; both are valid schemes — parity
        # tests run deterministic configs).
        idx = (
            jax.lax.axis_index(data_axis) * fsdp_size
            + jax.lax.axis_index(fsdp_axis)
        )
        rng = jax.random.fold_in(rng, idx)
        with nn.logical_axis_rules(rules):
            grads, metrics = _accumulate_grads(
                local_loss, params, batch, rng, accum
            )
        grads = hierarchical_grad_sync(
            grads,
            data_axis=data_axis,
            fsdp_axis=fsdp_axis,
            data_size=data_size,
            fsdp_size=fsdp_size,
            dcn_size=dcn,
            bucket_mb=config.grad_reduce_bucket_mb,
            overlap_chunks=config.grad_reduce_overlap_chunks,
            dcn_dtype=config.grad_reduce_dcn_dtype,
            plan_out=plan_box,
        )
        return grads, metrics

    fn = shard_map(
        body,
        mesh,
        in_specs=(P(), P((data_axis, fsdp_axis)), P()),
        out_specs=(P(), P()),
        axis_names=axes,
        check_vma=False,
    )
    fn.plan_box = plan_box
    return fn


# --------------------------------------------------------------------------
# diagnose probe: a real timed two-stage reduction over the probe mesh
# --------------------------------------------------------------------------


def grad_reduce_probe(
    payload_mb: float = 4.0, iters: int = 5, registry=None
) -> Dict[str, Any]:
    """Time a REAL two-stage hierarchical gradient reduction over the
    dcn×ici probe factorization — the `cli diagnose` rung that tells
    the MULTICHIP_r* harness what a bucketed gradient sync actually
    costs on this fleet, next to the expert-a2a probe.

    Multi-host jobs use the (process, local-device) grid as the real
    dcn×ici split; a single host with >= 4 devices SIMULATES a 2-host
    tier so the two-stage code path is exercised and timed even on the
    CPU harness. Degrades to the single-stage fallback below 4 devices.
    Exports diagnose_grad_reduce_seconds{stage} gauges mirroring the
    expert-a2a probe's contract."""
    import time as _time

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from luminaai_tpu.monitoring.telemetry import get_registry

    registry = registry or get_registry()
    n_proc = jax.process_count()
    n_global = jax.device_count()
    if n_proc > 1 and n_global % n_proc == 0:
        dcn, ici = n_proc, n_global // n_proc
        simulated = False
    elif n_global >= 4 and n_global % 2 == 0:
        dcn, ici = 2, n_global // 2
        simulated = True
    else:
        dcn, ici = 1, n_global
        simulated = n_proc == 1
    world = dcn * ici
    devices = np.array(jax.devices()[:world]).reshape(world)
    mesh = Mesh(devices, ("data",))
    out: Dict[str, Any] = {
        "world": world, "dcn": dcn, "ici": ici,
        "simulated_dcn": simulated, "stages": {},
    }
    # Per-shard payload sized so the synced gradient is ~payload_mb;
    # rounded to world² so every shard's slice reduce-scatters evenly
    # over any tier factoring.
    elems = max(world * world, int(payload_mb * 1e6 / 4))
    elems = -(-elems // (world * world)) * world * world
    g1, g2 = hierarchical_groups(world, dcn) if dcn > 1 else (None, None)

    def _run_stage(stage_fn, name):
        @jax.jit  # lumina: disable=LX006 -- probe re-times the same buffer; donation would free it between iters
        def stepped(xs):
            return shard_map(
                stage_fn, mesh=mesh,
                in_specs=PartitionSpec("data"),
                out_specs=PartitionSpec("data"),
                check_vma=False,
            )(xs)

        x = jax.device_put(
            jnp.ones((elems,), jnp.float32),
            NamedSharding(mesh, PartitionSpec("data")),
        )
        try:
            stepped(x).block_until_ready()
            t0 = _time.perf_counter()
            for _ in range(iters):
                y = stepped(x)
            y.block_until_ready()
            dt = (_time.perf_counter() - t0) / iters
        except Exception as e:  # probe must never wedge diagnose
            out["stages"][name] = {"error": f"{type(e).__name__}: {e}"}
            return
        payload = elems // world * 4
        out["stages"][name] = {
            "payload_mb": round(elems * 4 / 1e6, 2),
            "mean_seconds": round(dt, 6),
            "algo_gbps": round(payload / max(dt, 1e-9) / 1e9, 3),
        }

    from luminaai_tpu.monitoring.telemetry import MetricsRegistry

    # The sync exports its plan gauges at trace time; the probe's toy
    # payload must not clobber a training process's real
    # grad_reduce_bytes{stage} plan — sink them into a throwaway.
    _plan_sink = MetricsRegistry()

    def _full(v):
        # One full sync over a single-leaf "gradient": the production
        # bucket pipeline end to end.
        return hierarchical_grad_sync(
            v, data_axis="data", fsdp_axis="data",
            data_size=world, fsdp_size=1, dcn_size=dcn,
            bucket_mb=1.0, overlap_chunks=2, registry=_plan_sink,
        )

    if dcn > 1:
        _run_stage(
            lambda v: all_gather(
                psum_scatter(
                    v, "data", scatter_dimension=0, tiled=True,
                    axis_index_groups=g1,
                ),
                "data", axis=0, tiled=True, axis_index_groups=g1,
            ),
            "ici",
        )
        _run_stage(
            lambda v: psum(v, "data", axis_index_groups=g2), "dcn"
        )
        _run_stage(_full, "two_stage")
    else:
        _run_stage(_full, "single_stage")
    g = registry.gauge(
        "diagnose_grad_reduce_seconds",
        "Mean timed hierarchical gradient-sync per stage at last "
        "diagnose",
        labelnames=("stage",),
    )
    for name, rec in out["stages"].items():
        if isinstance(rec, dict) and "mean_seconds" in rec:
            g.labels(stage=name).set(rec["mean_seconds"])
    return out
