"""Cross-host expert parallelism: hierarchical all-to-all token dispatch.

ROADMAP item 3 / X-MoE (PAPERS.md, arxiv 2508.13337): the gmm and
einsum/gather dispatch modes reach remote experts through a REPLICATED
layout — tokens are replicated over the 'expert' mesh axis, every expert
shard runs its experts over the whole local batch, and a full-activation
psum over 'expert' assembles the outputs. That works inside one host's
ICI, but the psum payload is the entire [G, S, H] token tensor: expert
capacity cannot scale past one host because every added expert shard
re-crosses the whole batch. This module replaces that with true token
routing:

  - **padding-free token buffers**: tokens are sorted by destination
    expert shard and packed into per-destination buckets; per-destination
    counts are exchanged FIRST (a [ep, E/ep] int32 all-to-all), so the
    payload all-to-all carries only routed tokens plus a pow2-bucketed
    static bound (`DispatchPlan.bucket_rows`) instead of the
    capacity-padded [E, G, C, H] slabs of the einsum path. Dropped pairs
    never travel.

  - **two-stage hierarchical all-to-all**: the expert axis is factored as
    dcn × ici (hosts × chips-per-host, `config.expert_dcn_size`); stage 1
    exchanges buckets between ICI peers within each host so that every
    token sits on the local rail matching its destination's local index,
    stage 2 crosses hosts along fixed rails. Fewer, larger DCN messages
    (the DeepSpeed/X-MoE hierarchy), and the jaxpr keeps the two stages
    as separate collectives so the comms auditor
    (analysis/jaxpr_audit.enumerate_collectives) can price DCN-crossing
    bytes separately. Single-stage fallback when there is no dcn tier.

  - **dispatch/compute overlap**: the bucket rows are split into chunks
    (`config.moe_a2a_overlap_chunks`); each chunk's stage-2 exchange is
    data-independent of the other chunks' expert FFN compute, so XLA's
    latency-hiding scheduler can run chunk 1's DCN transfer under chunk
    0's grouped matmul.

The expert FFN itself reuses the megablox grouped-matmul contract from
models/moe.py (`gmm_fn`, row-sorted buffers, group_sizes exclusion,
operand masking) so the kernel boundary stays clean per the
portable-dispatch framing of the Triton fused-MoE paper (arxiv
2605.23911): swap the gmm and the whole dispatch pipeline is unchanged.

This module is also the sanctioned home for raw collective calls:
astlint rule LX010 fails `lumina analyze` on direct `lax.all_to_all` /
`lax.ppermute` use outside `parallel/` — route through
`parallel.mesh.all_to_all` / `parallel.mesh.ppermute` (thin wrappers
kept next to the shard_map entry point) so every collective call
site in model code stays enumerable.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from luminaai_tpu.parallel.mesh import all_to_all, shard_map

logger = logging.getLogger(__name__)

__all__ = [
    "DispatchPlan",
    "make_dispatch_plan",
    "hierarchical_groups",
    "hierarchical_all_to_all",
    "a2a_expert_ffn",
    "expert_a2a_probe",
    "next_pow2",
]


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    n = max(1, int(n))
    p = 1
    while p < n:
        p *= 2
    return p


# --------------------------------------------------------------------------
# static plan: bucket bound + byte accounting
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DispatchPlan:
    """Static shape/byte plan for one a2a dispatch (per expert shard).

    Everything here is derived from config shapes at trace time — the
    numbers describe the traced program, not a run — which is what lets
    bench extras and the comms auditor price the dispatch without
    hardware. Byte formulas (one direction; dispatch+combine doubles
    them):

      payload_bytes   = ep * bucket_rows * hidden * itemsize
                        (the bucketed token buffer one shard sends)
      stage ici bytes = payload * (ici-1)/ici   (leaves the chip, stays
                        on-host)
      stage dcn bytes = payload * (dcn-1)/dcn   (crosses hosts)

    The replicated-gather baseline these replace (gmm/einsum dispatch
    with tokens replicated over 'expert' + a full-activation psum over
    the expert axis) moves, per shard per direction,
    ring-allreduce-style ~2*(ax-1)/ax of the full [G_dp, S, H] token
    tensor across the expert axis — `baseline_*_bytes` below. The a2a
    advantage is structural: its payload shards the batch over the
    expert axis (G_local = G_dp/ep) and carries only routed tokens, so
    dcn bytes scale like cf*k/ep of the baseline's.
    """

    ep: int               # expert-axis size (dcn * ici)
    dcn: int              # host tier size (1 = single stage)
    ici: int              # per-host tier size
    local_groups: int     # G_l: batch groups per expert shard
    seq: int
    top_k: int
    capacity: int         # per-(group, expert) token capacity
    experts_local: int    # E / ep
    hidden: int
    itemsize: int         # payload dtype bytes
    bucket_rows: int      # B: pow2-bucketed per-destination row bound
    n_chunks: int         # overlap chunks (stage-2/compute pipelining)
    dp_groups: int        # G_dp: groups per (data,fsdp) shard (baseline)

    @property
    def pair_rows(self) -> int:
        return self.local_groups * self.seq * self.top_k

    @property
    def payload_bytes(self) -> int:
        return self.ep * self.bucket_rows * self.hidden * self.itemsize

    @property
    def counts_bytes(self) -> int:
        return self.ep * self.experts_local * 4

    def stage_bytes(self, stage: str) -> int:
        """One-direction off-device payload bytes for a stage ('ici' or
        'dcn'); 0 when the stage has one participant."""
        ax = self.ici if stage == "ici" else self.dcn
        return int(self.payload_bytes * (ax - 1) / ax) if ax > 1 else 0

    @property
    def a2a_dcn_bytes(self) -> int:
        """DCN-crossing bytes per shard per step (dispatch + combine)."""
        return 2 * self.stage_bytes("dcn")

    @property
    def baseline_psum_bytes(self) -> int:
        """The replicated path's expert-axis psum payload: the full
        per-(data,fsdp)-shard token activation, ring-reduced over the
        expert axis (~2x(ep-1)/ep of it leaves each shard)."""
        act = self.dp_groups * self.seq * self.hidden * self.itemsize
        return int(2 * act * (self.ep - 1) / self.ep) if self.ep > 1 else 0

    @property
    def baseline_dcn_bytes(self) -> int:
        """DCN-crossing share of the replicated path's expert psum."""
        act = self.dp_groups * self.seq * self.hidden * self.itemsize
        return int(2 * act * (self.dcn - 1) / self.dcn) if self.dcn > 1 else 0

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d.update(
            payload_bytes=self.payload_bytes,
            counts_bytes=self.counts_bytes,
            ici_stage_bytes=self.stage_bytes("ici"),
            dcn_stage_bytes=self.stage_bytes("dcn"),
            a2a_dcn_bytes=self.a2a_dcn_bytes,
            baseline_psum_bytes=self.baseline_psum_bytes,
            baseline_dcn_bytes=self.baseline_dcn_bytes,
        )
        return d


def make_dispatch_plan(
    *,
    ep: int,
    dcn_size: int,
    local_groups: int,
    seq: int,
    top_k: int,
    capacity: int,
    num_experts: int,
    hidden: int,
    itemsize: int,
    overlap_chunks: int = 1,
    dp_groups: Optional[int] = None,
) -> DispatchPlan:
    """Resolve the static dispatch plan for one expert shard.

    bucket_rows is the pow2-bucketed bound on tokens any one destination
    shard can receive from this shard: kept pairs are capped both by the
    local pair count (G_l*S*k) and by the destination's capacity budget
    (G_l * E_local * C), so the bucket never overflows — routing-drop
    semantics stay exactly _sort_routing's, which is what pins a2a
    bit-comparable to the gather path."""
    if dcn_size < 1 or ep % dcn_size:
        raise ValueError(
            f"expert_dcn_size {dcn_size} must divide the expert axis {ep}"
        )
    e_l = num_experts // ep
    n_pairs = local_groups * seq * top_k
    bound = min(n_pairs, local_groups * e_l * capacity)
    bucket = next_pow2(bound)
    chunks = max(1, int(overlap_chunks))
    while bucket % chunks:
        chunks -= 1
    return DispatchPlan(
        ep=ep,
        dcn=dcn_size,
        ici=ep // dcn_size,
        local_groups=local_groups,
        seq=seq,
        top_k=top_k,
        capacity=capacity,
        experts_local=e_l,
        hidden=hidden,
        itemsize=itemsize,
        bucket_rows=bucket,
        n_chunks=chunks,
        dp_groups=dp_groups if dp_groups is not None else local_groups * ep,
    )


def export_plan_gauges(plan: DispatchPlan, registry=None) -> None:
    """ep_a2a_bytes{stage} gauges from the static plan. Best-effort: the
    plan is built at trace time inside the model forward, so this must
    never break a trace over a telemetry hiccup."""
    try:
        from luminaai_tpu.monitoring.telemetry import get_registry

        registry = registry or get_registry()
        g = registry.gauge(
            "ep_a2a_bytes",
            "Static per-shard one-direction payload bytes of the expert "
            "a2a dispatch per stage (from the DispatchPlan, trace time)",
            labelnames=("stage",),
        )
        g.labels(stage="ici").set(float(plan.stage_bytes("ici")))
        g.labels(stage="dcn").set(float(plan.stage_bytes("dcn")))
    except Exception:  # pragma: no cover - telemetry must not break traces
        logger.debug("ep_a2a_bytes gauge export failed", exc_info=True)


# --------------------------------------------------------------------------
# hierarchical all-to-all
# --------------------------------------------------------------------------


def hierarchical_groups(
    ep: int, dcn: int
) -> Tuple[List[List[int]], List[List[int]]]:
    """Factor a single expert axis of size ep = dcn*ici into the two
    collective tiers. Shard s = h*ici + i (hosts outermost — matching
    how contiguous device blocks land on hosts for the trailing mesh
    axes). Stage 1 groups are the contiguous per-host blocks (ICI);
    stage 2 groups are the strided cross-host rails (DCN) — the comms
    auditor uses exactly this contiguous-vs-strided signature to
    classify a collective's tier."""
    ici = ep // dcn
    stage1 = [[h * ici + i for i in range(ici)] for h in range(dcn)]
    stage2 = [[h * ici + i for h in range(dcn)] for i in range(ici)]
    return stage1, stage2


def _stage1(x, axis_name, dcn, ici, groups):
    """Intra-host exchange: destination-local-index buckets move to the
    matching ICI peer. [dcn, ici_dest, ...] -> [dcn, ici_src, ...]."""
    return all_to_all(
        x, axis_name, split_axis=1, concat_axis=1, tiled=True,
        axis_index_groups=groups,
    )


def _stage2(x, axis_name, dcn, ici, groups):
    """Cross-host exchange along fixed rails. [dcn_dest, ici, ...] ->
    [dcn_src, ici, ...]. Block-level all-to-all with split == concat is
    an involution, so the combine path reuses the same call."""
    return all_to_all(
        x, axis_name, split_axis=0, concat_axis=0, tiled=True,
        axis_index_groups=groups,
    )


def hierarchical_all_to_all(
    x: jax.Array,
    ici_axis: str,
    *,
    dcn_axis: Optional[str] = None,
    dcn_size: int = 1,
) -> jax.Array:
    """Destination-major bucket exchange, hierarchical when a DCN tier
    exists. `x` is [ep, ...payload...] with leading dim indexing the
    destination shard (d = h*ici + i); returns [ep, ...] with leading
    dim indexing the source shard — i.e. exactly what a single flat
    `all_to_all(tiled=True)` over the whole axis produces, but staged
    ici-then-dcn so the DCN tier sees few large rail-aligned messages.

    Two spellings of the hierarchy:
      - `dcn_axis` names a REAL second mesh axis (the 2D dcn×ici probe
        mesh `cli diagnose` builds); `dcn_size` must then carry that
        axis's size (shapes are static — the body can't ask the mesh);
      - `dcn_size` alone factors a single named axis (the in-model
        path: the standard mesh has one 'expert' axis;
        `config.expert_dcn_size` declares how much of it spans hosts)
        via axis_index_groups.
    With neither, this is the single-stage fallback."""
    if dcn_axis is None and dcn_size <= 1:
        return all_to_all(x, ici_axis, split_axis=0, concat_axis=0,
                          tiled=True)
    if dcn_axis is not None:
        # Real 2D mesh: x's leading dim is still the flat destination
        # id; reshape to (dcn, ici) blocks, stage over each named axis.
        dcn = int(dcn_size)
        ici = x.shape[0] // dcn
        r = x.reshape((dcn, ici) + x.shape[1:])
        r = all_to_all(r, ici_axis, split_axis=1, concat_axis=1, tiled=True)
        r = all_to_all(r, dcn_axis, split_axis=0, concat_axis=0, tiled=True)
        return r.reshape(x.shape)
    ep = x.shape[0]
    dcn = int(dcn_size)
    ici = ep // dcn
    g1, g2 = hierarchical_groups(ep, dcn)
    r = x.reshape((dcn, ici) + x.shape[1:])
    r = _stage1(r, ici_axis, dcn, ici, g1)
    r = _stage2(r, ici_axis, dcn, ici, g2)
    return r.reshape(x.shape)


# --------------------------------------------------------------------------
# the expert FFN over routed buckets (runs inside a shard_map body)
# --------------------------------------------------------------------------


def a2a_expert_ffn(
    x: jax.Array,
    router_probs: jax.Array,
    wi: jax.Array,
    wo: jax.Array,
    *,
    top_k: int,
    capacity: int,
    num_experts: int,
    dtype,
    gmm_fn,
    ep_axis: str,
    plan: DispatchPlan,
    tp_axis: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
    """One expert shard's routed-token expert FFN (shard_map body).

    x [G_l, S, H] and router_probs [G_l, S, E] are this shard's OWN
    token groups — unlike the gmm path, tokens are sharded over the
    expert axis too (EP borrows the data dimension), so adding expert
    shards adds token shards: the layout that scales expert capacity
    across hosts. wi [E_l, H, 2F] / wo [E_l, F, H] are the local
    experts (F possibly tensor-sharded; partial row outputs are psum'd
    over `tp_axis` before the combine exchange, Megatron row-parallel).

    Pipeline: route (_sort_routing, the SAME global capacity semantics
    as every other dispatch mode — parity is pinned against gather) ->
    pack destination buckets via an inverted index + one row gather (no
    H-wide scatter anywhere, the r3 lesson) -> exchange per-destination
    counts -> hierarchical bucket exchange, stage-2 chunked for
    dispatch/compute overlap -> grouped matmul over exactly the
    received rows -> mirrored combine -> unpack + gate-weight on the
    home shard. No full-activation psum exists on this path.

    Returns (out [G_l,S,H], tokens_per_expert [E] local counts,
    dropped [G_l,S], stats {ep_tokens_routed, ep_tokens_dcn} — local
    scalars, psum'd by the caller)."""
    from luminaai_tpu.models.moe import _GMM_ROW_TILE, _sort_routing
    from flax import linen as nn

    G, S, H = x.shape
    E, k, C = num_experts, top_k, capacity
    E_l = wi.shape[0]
    ep, dcn, ici = plan.ep, plan.dcn, plan.ici
    B = plan.bucket_rows
    N = G * S * k

    slot, gate, dropped, counts = _sort_routing(router_probs, k, C)
    gate = gate.astype(dtype)

    # --- pack: destination-major buckets -------------------------------
    # Pair -> global expert (sentinel E for dropped); experts are
    # contiguous per destination shard, so expert-major order IS
    # destination-major order — one stable sort serves both.
    e_pair = jnp.where(slot < E * C, slot // C, E).reshape(-1)  # [N]
    d_pair = jnp.where(e_pair < E, e_pair // E_l, ep)           # [N]
    perm = jnp.argsort(e_pair, stable=True)                     # [N]
    cnt_de = counts.sum(axis=0).astype(jnp.int32).reshape(ep, E_l)
    cnt_d = cnt_de.sum(axis=1)                                  # [ep]
    dstart = jnp.cumsum(cnt_d) - cnt_d
    dest_sorted = d_pair[perm]
    pos = jnp.arange(N) - dstart[jnp.minimum(dest_sorted, ep - 1)]
    valid = dest_sorted < ep
    # Flat bucket slot per sorted rank; dropped pairs -> spill slot.
    flat = jnp.where(valid, dest_sorted * B + pos, ep * B).astype(jnp.int32)
    # Invert slot -> sorted rank (KB-scale int scatter), then fill the
    # send buffer with ONE H-wide row gather through it.
    inv = jnp.full((ep * B + 1,), N, jnp.int32).at[flat].set(
        jnp.arange(N, dtype=jnp.int32)
    )[: ep * B]
    tok_sorted = (perm // k).astype(jnp.int32)
    x_flat = x.astype(dtype).reshape(G * S, H)
    filled = (inv < N)[:, None].astype(dtype)
    sb = (
        x_flat[tok_sorted[jnp.minimum(inv, N - 1)]] * filled
    ).reshape(ep, B, H)

    # --- counts exchange first (padding-free contract) -----------------
    rcnt = all_to_all(
        cnt_de, ep_axis, split_axis=0, concat_axis=0, tiled=True
    )  # [ep_src, E_l]
    rtot = rcnt.sum(axis=1)                    # [ep] rows per source
    rcum = jnp.cumsum(rcnt, axis=1)            # [ep, E_l]

    # --- dispatch exchange: stage 1 once, stage 2 per chunk ------------
    groups = hierarchical_groups(ep, dcn) if dcn > 1 else None
    if groups is not None:
        sb = _stage1(
            sb.reshape(dcn, ici, B, H), ep_axis, dcn, ici, groups[0]
        )

    n_chunks = plan.n_chunks
    Bc = B // n_chunks

    def _exchange(piece):
        if groups is not None:
            return _stage2(piece, ep_axis, dcn, ici, groups[1])
        return all_to_all(
            piece, ep_axis, split_axis=0, concat_axis=0, tiled=True
        )

    def _ffn_chunk(rb_c, row0):
        """Grouped matmul over one received chunk [ep, Bc, H]: rows
        sorted expert-major across sources, group_sizes from the
        exchanged counts, the megablox operand-masking contract from
        _gmm_local (uninitialized tails annihilated via jnp.where on
        the operands, fwd AND both VJPs)."""
        r_ids = row0 + jnp.arange(Bc)
        # expert of bucket row r from source s: how many of source s's
        # per-expert runs end at or before r.
        e_loc = jax.vmap(
            lambda cum: jnp.searchsorted(cum, r_ids, side="right")
        )(rcum)                                   # [ep, Bc]
        live = r_ids[None, :] < rtot[:, None]
        key = jnp.where(live, e_loc, E_l).reshape(-1)  # [M]
        M = ep * Bc
        p2 = jnp.argsort(key, stable=True)
        gs = jnp.sum(
            jax.nn.one_hot(key, E_l + 1, dtype=jnp.int32), axis=0
        )[:E_l]
        Mp = -(-M // _GMM_ROW_TILE) * _GMM_ROW_TILE
        rows = rb_c.reshape(M, H)[p2]
        if Mp != M:
            rows = jnp.pad(rows, ((0, Mp - M), (0, 0)))
        total_kept = gs.sum()
        row_kept = jnp.arange(Mp)[:, None] < total_kept
        lhs = jnp.where(row_kept, rows, 0)
        fused = gmm_fn(
            lhs, wi.astype(dtype), gs, preferred_element_type=dtype
        )
        gate_act, up = jnp.split(fused, 2, axis=-1)
        act = jnp.where(row_kept, nn.silu(gate_act) * up, 0)
        yrow = gmm_fn(
            act, wo.astype(dtype), gs, preferred_element_type=dtype
        )
        yrow = jnp.where(row_kept, yrow, 0.0)[:M]
        if tp_axis is not None:
            # Row-parallel epilogue: partial token outputs join here so
            # only ONE copy rides the combine exchange.
            yrow = jax.lax.psum(yrow, tp_axis)
        inv2 = jnp.argsort(p2)
        return yrow[inv2].reshape(ep, Bc, H)

    back = []
    for c in range(n_chunks):
        if groups is not None:
            piece = sb[:, :, c * Bc:(c + 1) * Bc, :]
        else:
            piece = sb[:, c * Bc:(c + 1) * Bc, :]
        rb_c = _exchange(piece)
        if groups is not None:
            rb_c = rb_c.reshape(ep, Bc, H)
        yb_c = _ffn_chunk(rb_c, c * Bc)
        if groups is not None:
            yb_c = yb_c.reshape(dcn, ici, Bc, H)
        # Stage 2 is a block-permutation involution: the same call
        # routes outputs back toward their source hosts.
        back.append(_exchange(yb_c))
    cb = jnp.concatenate(back, axis=2 if groups is not None else 1)
    if groups is not None:
        cb = _stage1(cb, ep_axis, dcn, ici, groups[0]).reshape(ep, B, H)

    # --- unpack + gate-weight on the home shard ------------------------
    cbf = cb.reshape(ep * B, H)
    y_sorted = cbf[jnp.minimum(flat, ep * B - 1)] * (
        valid[:, None].astype(dtype)
    )
    inv_perm = jnp.argsort(perm)
    y_pairs = y_sorted[inv_perm].reshape(G, S, k, H)
    out = jnp.einsum("gskh,gsk->gsh", y_pairs, gate)

    # Per-stage routed-token stats (local; caller psums): every kept
    # pair rides stage 1, only host-crossing pairs ride stage 2.
    my_host = jax.lax.axis_index(ep_axis) // ici
    dest_host = jnp.arange(ep) // ici
    routed = cnt_d.sum().astype(jnp.float32)
    routed_dcn = jnp.where(
        dest_host != my_host, cnt_d, 0
    ).sum().astype(jnp.float32)
    stats = {"ep_tokens_routed": routed, "ep_tokens_dcn": routed_dcn}
    return out, counts.sum(axis=0).astype(jnp.float32), dropped, stats


# --------------------------------------------------------------------------
# diagnose probe: a real timed two-stage all-to-all over the probe mesh
# --------------------------------------------------------------------------


def expert_a2a_probe(
    payload_mb: float = 4.0, iters: int = 5, registry=None
) -> Dict[str, Any]:
    """Time a REAL two-stage hierarchical all-to-all over the dcn×ici
    probe factorization — the `cli diagnose` rung that tells the
    MULTICHIP_r* harness what an expert-dispatch exchange actually
    costs on this fleet, next to the connectivity probe's all-reduce.

    Multi-host jobs use the (process, local-device) grid as the real
    dcn×ici split; a single host with >= 4 local devices SIMULATES a
    2-host tier (dcn=2) so the two-stage code path is exercised and
    timed even on the CPU harness — the numbers then validate the
    dispatch machinery, not an interconnect. Degrades to the
    single-stage fallback below 4 devices.

    Exports diagnose_expert_a2a_seconds{stage} gauges mirroring the
    connectivity probe's contract."""
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
    ep = dcn * ici
    devices = np.array(jax.devices()[: ep]).reshape(ep)
    mesh = Mesh(devices, ("expert",))
    out: Dict[str, Any] = {
        "ep": ep, "dcn": dcn, "ici": ici, "simulated_dcn": simulated,
        "stages": {},
    }
    # Per-destination buckets sized so the whole exchange carries
    # ~payload_mb per shard.
    H = 128
    rows = max(1, int(payload_mb * 1e6 / 4 / H / ep))
    g1, g2 = hierarchical_groups(ep, dcn) if dcn > 1 else (None, None)

    def _run_stage(stage_fn, name):
        @jax.jit  # lumina: disable=LX006 -- probe re-times the same buffer; donation would free it between iters
        def stepped(xs):
            return shard_map(
                stage_fn, mesh=mesh,
                in_specs=PartitionSpec("expert"),
                out_specs=PartitionSpec("expert"),
                check_vma=False,
            )(xs)

        x = jax.device_put(
            jnp.ones((ep * ep, rows, H), jnp.float32),
            NamedSharding(mesh, PartitionSpec("expert")),
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
        payload = ep * rows * H * 4
        out["stages"][name] = {
            "payload_mb": round(payload / 1e6, 2),
            "mean_seconds": round(dt, 6),
            "algo_gbps": round(payload / max(dt, 1e-9) / 1e9, 3),
        }

    if dcn > 1:
        _run_stage(
            lambda v: _stage1(
                v.reshape((dcn, ici) + v.shape[1:]), "expert", dcn, ici, g1
            ).reshape(v.shape),
            "ici",
        )
        _run_stage(
            lambda v: _stage2(
                v.reshape((dcn, ici) + v.shape[1:]), "expert", dcn, ici, g2
            ).reshape(v.shape),
            "dcn",
        )
        _run_stage(
            lambda v: hierarchical_all_to_all(v, "expert", dcn_size=dcn),
            "two_stage",
        )
    else:
        _run_stage(
            lambda v: hierarchical_all_to_all(v, "expert"), "single_stage"
        )
    g = registry.gauge(
        "diagnose_expert_a2a_seconds",
        "Mean timed expert-dispatch all-to-all per stage at last diagnose",
        labelnames=("stage",),
    )
    for name, rec in out["stages"].items():
        if isinstance(rec, dict) and "mean_seconds" in rec:
            g.labels(stage=name).set(rec["mean_seconds"])
    return out
