"""Mixture-of-Experts layer, TPU-first.

Covers the reference MoE (ref: Src/Main_Scripts/core/model.py:1090 MoEFFNLayer,
:1200 _pytorch_routing, :1244 _compute_auxiliary_loss; CUDA dispatch in
core/moe_cuda_wrapper.py + ColossalAI moe_cuda_kernel.cu). The reference loops
over experts with `index_add_` (a scatter — fine on GPU, hostile to XLA). Here
dispatch/combine are one-hot einsums (GShard/Switch style): everything is a
static-shape matmul that tiles onto the MXU, and sharding the expert dimension
over the 'expert' mesh axis makes XLA insert the all-to-all on ICI — the
TPU-native replacement for the reference's NCCL expert-parallel path.

Capacity-factor semantics: each expert processes at most
C = ceil(cf * S * k / E) tokens per sequence-group; overflow tokens fall back
to the residual stream (tracked as drop_rate). Aux losses: Switch
load-balance (f·P·E) and router z-loss.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from luminaai_tpu.config import Config
from luminaai_tpu.models.layers import default_init
from luminaai_tpu.ops.quantized import QuantizedTensor

Dtype = Any


def _sort_routing(
    router_probs: jax.Array, top_k: int, capacity: int, *,
    select_bias: Optional[jax.Array] = None, renormalize: bool = True,
    scale: float = 1.0, with_chosen: bool = False,
) -> Tuple[jax.Array, ...]:
    """Sort-based top-k assignment with per-expert capacity (no [S,E,C] maps).

    Replicates _top_k_routing's greedy semantics exactly — capacity is
    granted round-major (all tokens' 1st choices in sequence order, then 2nd
    choices, ...) — but via an O(S·k log(S·k)) sort per group instead of
    O(S·E·C) one-hot dispatch/combine tensors. At flagship scale the one-hot
    formulation allocates 2×[G,S,E,C]≈670MB per MoE layer (r2 OOM driver);
    here routing state is three [G,S,k] integer/float arrays. The expert
    buffers are then built with scatter/gather (VPU) while the FFN matmuls
    stay dense [E,G,C,·] on the MXU. (Ref's CUDA dispatch kernels play this
    role: Src/Main_Scripts/core/moe_cuda_wrapper.py:628.)

    router_probs: [G, S, E] scores (softmax probabilities, or sigmoids).
    The combine rule is data (Config.moe_*): the k experts are the top-k
    of score + select_bias ([E], used for the choice alone), their scores
    are divided by their sum if `renormalize` and multiplied by `scale`.
    The defaults are the softmax-renormalised rule this layer always had.
    Returns (per group, vmapped):
      slot:  [G, S, k] int32 flat slot e*C + pos (E*C = dropped sentinel)
      gate:  [G, S, k] renormalized top-k probs (zeroed where dropped)
      dropped: [G, S] 1.0 where a token lost ≥1 of its k slots
      counts: [G, E] kept tokens per expert
      (with_chosen: and chosen [G, E], the pairs per expert before capacity)
    """
    G, S, E = router_probs.shape
    C = capacity

    def per_group(probs):  # [S, E]
        if select_bias is None:
            vals, choice = jax.lax.top_k(probs, top_k)  # [S, k] desc order
        else:
            _, choice = jax.lax.top_k(
                probs + jax.lax.stop_gradient(select_bias), top_k
            )
            vals = jnp.take_along_axis(probs, choice, axis=-1)
        gates = vals
        if renormalize:
            denom = vals.sum(-1, keepdims=True) + 1e-9
            gates = vals / denom
        if scale != 1.0:
            gates = gates * scale
        # Pair index p = round*S + s → round-major FIFO priority, matching
        # the greedy loop (round r assigned before r+1, sequence order
        # within a round).
        e_flat = choice.T.reshape(S * top_k)  # [S*k], p = r*S + s
        order = jnp.argsort(e_flat * (S * top_k) + jnp.arange(S * top_k))
        e_sorted = e_flat[order]
        # Position within the expert's buffer = rank - first rank of that
        # expert's run (offsets from exclusive-cumsum of counts).
        counts_all = jnp.sum(
            jax.nn.one_hot(e_flat, E, dtype=jnp.int32), axis=0
        )  # [E] (pre-capacity)
        starts = jnp.cumsum(counts_all) - counts_all
        pos_sorted = jnp.arange(S * top_k) - starts[e_sorted]
        keep_sorted = pos_sorted < C
        slot_sorted = jnp.where(
            keep_sorted, e_sorted * C + pos_sorted, E * C
        ).astype(jnp.int32)
        # Un-sort back to pair order, then to [S, k].
        slot_flat = jnp.zeros(S * top_k, jnp.int32).at[order].set(slot_sorted)
        slot = slot_flat.reshape(top_k, S).T  # [S, k]
        keep = slot < E * C
        gate = jnp.where(keep, gates, 0.0)
        dropped = jnp.clip(
            jnp.sum(1.0 - keep.astype(probs.dtype), axis=-1), 0.0, 1.0
        )
        counts = jnp.minimum(counts_all, C)
        if with_chosen:
            return slot, gate, dropped, counts, counts_all
        return slot, gate, dropped, counts

    return jax.vmap(per_group)(router_probs)


def _slot_rows(buf_egch, slot, capacity):
    """Gather [G,S,k,H] rows out of an expert-major [E,G,C,H] buffer by
    flat slot id, with the dropped-pair sentinel handling: slot == E*C
    clamps to an arbitrary row and `kept` annihilates it. Single source
    of truth for the combine path AND _dispatch_gather's adjoint (the
    same sentinel/clamp invariant must never drift between them).

    Returns (rows [G,S,k,H], kept [G,S,k,1])."""
    E = buf_egch.shape[0]
    G = slot.shape[0]
    sl = jnp.minimum(slot, E * capacity - 1)
    rows = buf_egch[
        sl // capacity, jnp.arange(G)[:, None, None], sl % capacity
    ]
    kept = (slot < E * capacity).astype(buf_egch.dtype)[..., None]
    return rows, kept


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_gather(x, inv_egc, slot, capacity):
    """Expert-major dispatch gather with a GATHER-only adjoint.

    Forward: expert_in[e,g,c] = x[g, inv_egc[e,g,c]] (masked where the
    slot is unfilled). The plain advanced-indexing VJP would scatter-add
    E·C rows of d_x per group (~50ms/step at flagship scale in the r3
    trace); but the inv table is a bijection on kept slots, and token t's
    kept slots are exactly slot[g,t,r] — so the adjoint is the SAME
    clamped-index row gather the combine path uses: d_x[g,t] =
    Σ_r kept·d_expert_in[slot[g,t,r]]. Zero H-wide scatters anywhere in
    the MoE path.
    """
    out, _ = _dispatch_gather_fwd(x, inv_egc, slot, capacity)
    return out


def _dispatch_gather_fwd(x, inv_egc, slot, capacity):
    G, S, H = x.shape
    filled = (inv_egc < S)[..., None].astype(x.dtype)
    out = (
        x[jnp.arange(G)[None, :, None], jnp.minimum(inv_egc, S - 1)] * filled
    )  # [E, G, C, H]
    return out, slot


def _dispatch_gather_bwd(capacity, res, g):
    slot = res
    rows, kept = _slot_rows(g, slot, capacity)
    # x enters in the layer compute dtype (the fwd casts first), so the
    # cotangent dtype already matches it.
    d_x = jnp.sum(rows * kept, axis=2)  # [G, S, H]
    # Integer index tables get symbolic-zero (float0) cotangents;
    # inv_egc's shape [E, G, C] is g.shape[:3].
    return (
        d_x,
        np.zeros(g.shape[:3], jax.dtypes.float0),
        np.zeros(slot.shape, jax.dtypes.float0),
    )


_dispatch_gather.defvjp(_dispatch_gather_fwd, _dispatch_gather_bwd)

# Test hook: inject a gmm implementation carrying the TPU kernel's
# uninitialized-tail contract (rows past sum(group_sizes) undefined in out
# AND grad_lhs) so _gmm_path's operand masking is pinned without a chip —
# the CPU fallback below self-masks and cannot exercise it.
_GMM_OVERRIDE = None

# megablox m-dimension tile: the kernel walks the sorted row buffer in
# 128-row tiles, so the buffer is padded UP to this boundary. Pad rows sit
# past sum(group_sizes) — the same excluded tail dropped pairs already use
# — so they cost no kernel work and their (uninitialized) outputs/grads are
# annihilated by the row_kept operand masks. This replaced the r5-era
# shape fence (_check_gmm_rows ValueError): any batch/seq/top_k now runs
# dropless (VERDICT r5 #6).
_GMM_ROW_TILE = 128


def _top_k_routing(
    router_probs: jax.Array, top_k: int, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Greedy top-k assignment with per-expert capacity.

    router_probs: [G, S, E] softmax probabilities.
    Returns:
      dispatch: [G, S, E, C] one-hot dispatch mask
      combine:  [G, S, E, C] combine weights (renormalized top-k probs)
      dropped:  [G, S] 1.0 where a token lost at least one of its k slots
    """
    G, S, E = router_probs.shape
    probs = router_probs
    dispatch = jnp.zeros((G, S, E, capacity), dtype=router_probs.dtype)
    combine = jnp.zeros((G, S, E, capacity), dtype=router_probs.dtype)

    # Renormalization denominator over the k selected experts (ref :1200
    # renormalizes top-k probs to sum to 1).
    topk_vals = jax.lax.top_k(probs, top_k)[0]
    denom = topk_vals.sum(-1, keepdims=True) + 1e-9

    expert_count = jnp.zeros((G, E), dtype=jnp.int32)
    masked = probs
    drops = jnp.zeros((G, S), dtype=router_probs.dtype)
    for _ in range(top_k):
        choice = jnp.argmax(masked, axis=-1)  # [G, S]
        onehot = jax.nn.one_hot(choice, E, dtype=probs.dtype)  # [G, S, E]
        # Position of each token within its chosen expert's buffer: running
        # count of earlier tokens (in sequence order) routed to that expert.
        pos_in_expert = (
            jnp.cumsum(onehot, axis=1) - onehot + expert_count[:, None, :]
        )  # [G, S, E]
        pos = jnp.einsum("gse,gse->gs", pos_in_expert, onehot)
        within = pos < capacity
        gate = jnp.take_along_axis(probs, choice[..., None], axis=-1)[..., 0] / denom[..., 0]
        slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=probs.dtype)
        keep = (within.astype(probs.dtype))[..., None, None]
        contrib = onehot[..., None] * slot[:, :, None, :] * keep
        dispatch = dispatch + contrib
        combine = combine + contrib * gate[..., None, None]
        drops = drops + (1.0 - within.astype(probs.dtype))
        expert_count = expert_count + jnp.einsum(
            "gse,gs->ge", onehot, within.astype(probs.dtype)
        ).astype(jnp.int32)
        masked = masked * (1.0 - onehot)  # exclude chosen expert next round

    return dispatch, combine, jnp.clip(drops, 0.0, 1.0)


class MoELayer(nn.Module):
    """Top-k routed expert FFN with capacity-based einsum dispatch.

    Expert weights carry a leading E axis sharded over the 'expert' mesh axis;
    dispatched activations are sharding-constrained so XLA emits all-to-alls
    (expert parallelism) instead of gathering weights.
    """

    config: Config
    dtype: Dtype = jnp.bfloat16
    # Static so nn.remat of the enclosing block never traces it.
    deterministic: bool = True

    @nn.compact
    def __call__(
        self, x: jax.Array, live: Optional[jax.Array] = None
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """`live` [G, S] bool: rows that are tokens (a serving tick's
        other rows are lanes not stepped and a chunk's padding). Read by
        a share's grouped matmul alone (experts_held): rows that are no
        tokens take no row of it and count in none of its pair counters."""
        cfg = self.config
        deterministic = self.deterministic
        G, S, H = x.shape
        E, k = cfg.num_experts, cfg.moe_top_k
        F = cfg.expert_width()
        # What the routed experts read and write: the hidden row, or its
        # projection to a latent (fc1 / fc2 below, experts_held alone).
        Hm = cfg.moe_width()
        gated = cfg.moe_expert_act == "swiglu"
        capacity = max(1, int(cfg.capacity_factor * S * k / E))
        # Round capacity to a multiple of 8 (fp32 sublane) when big enough —
        # keeps the [E, G, C, H] buffers tileable.
        if capacity >= 8:
            capacity = ((capacity + 7) // 8) * 8

        wg = self.param(
            "router",
            nn.with_logical_partitioning(default_init(0.02), ("embed", None)),
            (H, E),
            jnp.float32,
        )
        # Under manual expert parallelism (inside the 1F1B pipe region) the
        # passed-in wi/wo hold only this shard's E/ep experts; declare the
        # local shape so flax's apply-time shape check accepts the slice.
        # Init always happens on the non-manual model (full E).
        E_w = (
            E // cfg.expert_parallel_size
            if cfg.moe_manual_ep and cfg.expert_parallel_size > 1
            else E
        )
        if cfg.experts_held is not None:
            # One chip's share of an expert-parallel group: the weights of
            # experts [offset, offset + count) alone; the router above
            # keeps all E outputs.
            E_w = cfg.experts_held[1]
        rule = {"renormalize": cfg.moe_renormalize,
                "scale": cfg.moe_routed_scale}
        if cfg.moe_selection_bias:
            # Moved by the balancing rule of whoever trains the router
            # (aux-loss-free balancing), never by the loss: it takes part
            # in the choice alone, so its gradient is zero.
            rule["select_bias"] = self.param(
                "selection_bias",
                nn.with_logical_partitioning(
                    nn.initializers.normal(cfg.moe_selection_bias_init_std)
                    if cfg.moe_selection_bias_init_std
                    else nn.initializers.zeros,
                    (None,),
                ),
                (E,),
                jnp.float32,
            )
        wi = self.param(
            "wi",
            nn.with_logical_partitioning(
                default_init(cfg.init_std), ("expert", "embed", "mlp_fused")
            ),
            (E_w, Hm, 2 * F if gated else F),
            jnp.float32,
        )
        wo = self.param(
            "wo",
            nn.with_logical_partitioning(
                default_init(cfg.init_std / jnp.sqrt(2.0)), ("expert", "mlp", "embed")
            ),
            (E_w, F, Hm),
            jnp.float32,
        )
        if cfg.moe_latent_size:
            fc1 = self.param(
                "fc1",
                nn.with_logical_partitioning(
                    default_init(cfg.init_std), ("embed", None)),
                (H, Hm), jnp.float32)
            fc2 = self.param(
                "fc2",
                nn.with_logical_partitioning(
                    default_init(cfg.init_std / jnp.sqrt(2.0)),
                    (None, "embed")),
                (Hm, H), jnp.float32)

        # --- Routing (fp32 throughout; ref :1200) ---
        gate_logits = jnp.einsum("gsh,he->gse", x.astype(jnp.float32), wg)
        gate_logits = gate_logits / cfg.routing_temperature
        if not deterministic and cfg.routing_noise_std > 0:
            noise = (
                jax.random.normal(self.make_rng("routing"), gate_logits.shape)
                * cfg.routing_noise_std
            )
            gate_logits = gate_logits + noise
        if not deterministic and cfg.expert_dropout_rate > 0:
            # Whole-expert dropout (ref trainer.py:1495 enable_expert_dropout):
            # mask a Bernoulli subset of experts out of routing for this step
            # so the router can't collapse onto a favorite. Softmax over the
            # masked logits renormalizes mass onto survivors. Keep-all
            # fallback guards the (rate^E) chance of an empty mask.
            keep = jax.random.bernoulli(
                self.make_rng("routing"),
                1.0 - cfg.expert_dropout_rate,
                (E,),
            )
            keep = jnp.where(keep.any(), keep, jnp.ones_like(keep))
            gate_logits = jnp.where(keep[None, None, :], gate_logits, -1e9)
        if cfg.moe_score_func == "sigmoid":
            router_probs = jax.nn.sigmoid(gate_logits)
        else:
            router_probs = jax.nn.softmax(gate_logits, axis=-1)

        # Quantized serving: the gmm kernel is bf16-only, so int8 expert
        # weights route through the gather buffers (decode shapes rarely
        # satisfy gmm's 128-row tiling anyway).
        dispatch_mode = cfg.moe_dispatch
        if isinstance(wi, QuantizedTensor) and dispatch_mode in (
            "gmm", "a2a"
        ):
            dispatch_mode = "gather"

        ep_stats: Dict[str, jax.Array] = {}
        if dispatch_mode == "a2a":
            # Cross-host expert parallelism (ROADMAP item 3 / X-MoE):
            # tokens shard over (data, fsdp, expert) and are ROUTED to
            # their experts' shards through the hierarchical all-to-all
            # subsystem (parallel/expert_dispatch.py) — padding-free
            # buckets, ici-then-dcn staging, no full-activation psum.
            # Routing semantics are _sort_routing's, so outputs match
            # the replicated-gather path (parity-pinned in
            # tests/test_expert_dispatch.py).
            out, tokens_per_expert, dropped, ep_stats = self._a2a_path(
                x, router_probs, wi, wo, capacity
            )
        elif dispatch_mode == "gmm":
            # Ragged grouped matmul via the Pallas megablox kernel: tokens
            # sorted by expert, each expert's FFN runs over exactly its
            # kept rows — no [E, G, C, H] capacity-padded buffers and no
            # padded-slot FLOPs (~20% of expert matmul work at cf 1.25).
            # Routing/capacity/drop semantics are _sort_routing's, so
            # outputs match the sort/gather paths exactly.
            if cfg.experts_held is not None:
                # One chip's share, told by the configuration: no expert
                # has a capacity of its own (a token picks an expert at
                # most once, so S a group never binds); the one limit is
                # the held experts' rows together, capacity_factor x the
                # expected N * k * count / E.
                rows = x
                if cfg.moe_latent_size:
                    with jax.named_scope("moe.latent_in"):
                        rows = jnp.einsum(
                            "gsh,hl->gsl", x.astype(self.dtype),
                            fc1.astype(self.dtype))
                with jax.named_scope("moe_held"):
                    out, tokens_per_expert, dropped, ep_stats = _gmm_held(
                        rows, router_probs, wi, wo, top_k=k, num_experts=E,
                        offset=cfg.experts_held[0], dtype=self.dtype,
                        gmm_fn=_pick_gmm(), rule=rule,
                        row_bound=held_row_bound(cfg, G * S), live=live,
                        gated=gated,
                        count_hit=cfg.moe_latent_size is not None,
                    )
                if cfg.moe_latent_size:
                    # No bias, so the shares' fc2 of their parts add up.
                    with jax.named_scope("moe.latent_out"):
                        out = jnp.einsum(
                            "gsl,lh->gsh", out, fc2.astype(self.dtype))
            else:
                out, tokens_per_expert, dropped = self._gmm_path(
                    x, router_probs, wi, wo, capacity, rule
                )
        elif dispatch_mode in ("sort", "gather"):
            # Sort-based dispatch: scatter/gather via flat slot ids — no
            # [G,S,E,C] one-hot tensors (see _sort_routing). The expert FFN
            # below still runs dense [E,G,C,·] matmuls on the MXU.
            slot, gate, dropped, counts = _sort_routing(
                router_probs, k, capacity, **rule
            )
            gate = gate.astype(self.dtype)
            tok = jnp.broadcast_to(
                jnp.arange(S)[:, None], (S, k)
            ).reshape(-1)

            if dispatch_mode == "gather":
                # Invert slot→token into an index table first (cheap int32
                # scatter), then fill the expert buffers with a row GATHER
                # — directly in the [E, G, C, H] expert-major layout, so no
                # [G, E·C, H]→[E, G, C, H] activation transpose ever
                # materializes (the int32 index transpose is ~KB-scale).
                # TPU executes H-wide row gathers far better than row
                # scatters; the H-wide scatter-add moves to the backward,
                # where the combine path's gather VJP was already one.
                def invert_group(slot_g):
                    inv = jnp.full((E * capacity + 1,), S, jnp.int32)
                    return inv.at[slot_g.reshape(-1)].set(
                        tok.astype(jnp.int32)
                    )[: E * capacity]

                inv = jax.vmap(invert_group)(slot)  # [G, E*C] token ids
                inv_egc = inv.reshape(G, E, capacity).transpose(1, 0, 2)
                # Unfilled slots (inv == S) gather an arbitrary row and are
                # zeroed by the mask — avoids concatenating a zero row onto
                # x (a whole-activation HBM copy per layer). The custom
                # VJP's adjoint is ALSO a row gather (via the slot table),
                # so no H-wide scatter exists anywhere in this path.
                expert_in = _dispatch_gather(
                    x.astype(self.dtype), inv_egc, slot, capacity
                )  # [E, G, C, H]
            else:

                def scatter_group(xg, slot_g):
                    # Spill row E*C absorbs dropped pairs, sliced off after.
                    buf = jnp.zeros((E * capacity + 1, H), dtype=self.dtype)
                    return buf.at[slot_g.reshape(-1)].set(xg[tok])

                buf = jax.vmap(scatter_group)(x.astype(self.dtype), slot)
                buf = buf[:, : E * capacity]
                expert_in = buf.reshape(G, E, capacity, H).transpose(
                    1, 0, 2, 3
                )
            tokens_per_expert = counts.astype(jnp.float32).sum(axis=0)
        else:
            dispatch, combine_w, dropped = _top_k_routing(
                router_probs, k, capacity
            )
            dispatch = dispatch.astype(self.dtype)
            combine_w = combine_w.astype(self.dtype)
            expert_in = jnp.einsum("gsec,gsh->egch", dispatch, x)
            tokens_per_expert = jnp.einsum(
                "gsec->e", dispatch.astype(jnp.float32)
            )

        if dispatch_mode not in ("gmm", "a2a"):
            # Manual expert parallelism (inside the 1F1B manual-pipe region):
            # tokens arrive SHARDED over the 'expert' mesh axis (ep borrows the
            # data dimension, the DeepSpeed-MoE layout), this shard's wi/wo
            # hold only E/ep experts, and a tiled all-to-all exchanges token
            # buffers so each shard runs its experts over every shard's tokens.
            manual_ep = cfg.moe_manual_ep and cfg.expert_parallel_size > 1
            if manual_ep:
                # [E, G, C, H] -> [E/ep, ep*G, C, H]: split experts to their
                # owners, gather all shards' token groups. (Routed through
                # parallel/mesh.all_to_all — the LX010 entry point.)
                from luminaai_tpu.parallel.mesh import all_to_all

                expert_in = all_to_all(
                    expert_in, "expert", split_axis=0, concat_axis=1, tiled=True
                )
            elif cfg.moe_ep_constraints:
                # Force the all-to-all dispatch layout: activations sharded
                # over 'expert' so each shard runs only its experts' matmuls.
                # Skipped inside the 1F1B manual-pipe region, where the
                # explicit reshard trips XLA's SPMD partitioner group check.
                expert_in = nn.with_logical_constraint(
                    expert_in, ("expert", "activation_exp_batch", None, None)
                )
            if isinstance(wi, QuantizedTensor):
                # Serving path: per-expert int8 MXU dots (ops/quantized.py)
                # — the TPU form of the ref's kernel-swap quantization.
                from luminaai_tpu.ops.quantized import int8_expert

                fused = int8_expert(expert_in, wi, self.dtype)
            else:
                fused = jnp.einsum(
                    "egch,ehf->egcf", expert_in, wi.astype(self.dtype)
                )
            gate_act, up = jnp.split(fused, 2, axis=-1)
            act = nn.silu(gate_act) * up
            if isinstance(wo, QuantizedTensor):
                from luminaai_tpu.ops.quantized import int8_expert

                expert_out = int8_expert(act, wo, self.dtype)
            else:
                expert_out = jnp.einsum(
                    "egcf,efh->egch", act, wo.astype(self.dtype)
                )
            if manual_ep:
                # [E/ep, ep*G, C, H] -> [E, G, C, H]: every token group gets
                # all experts' outputs back for the local combine.
                from luminaai_tpu.parallel.mesh import all_to_all

                expert_out = all_to_all(
                    expert_out, "expert", split_axis=1, concat_axis=0, tiled=True
                )
            elif cfg.moe_ep_constraints:
                expert_out = nn.with_logical_constraint(
                    expert_out, ("expert", "activation_exp_batch", None, None)
                )

            if dispatch_mode in ("sort", "gather"):
                # Dropped pairs carry slot == E*C (one past the end) AND
                # gate == 0: clamping the index gathers an arbitrary row that
                # the zero gate annihilates — no zero-row concatenate (a full
                # [G, E*C, H] HBM copy per layer, ~57ms/step in the r3
                # flagship trace). The gather indexes expert_out's [E, G, C]
                # layout directly (shared _slot_rows), so no expert-major→
                # token-major activation transpose materializes either.
                y, _ = _slot_rows(expert_out, slot, capacity)
                out = jnp.einsum("gskh,gsk->gsh", y, gate)
            else:
                out = jnp.einsum("gsec,egch->gsh", combine_w, expert_out)
        if cfg.expert_output_scaling != 1.0:
            out = out * cfg.expert_output_scaling
        if cfg.moe_shared_size:
            from luminaai_tpu.models.layers import Relu2MLP, SwiGLU

            # ONE shared expert of its own width on the un-projected row.
            with jax.named_scope("moe.shared"):
                out = out + (SwiGLU if gated else Relu2MLP)(
                    cfg.moe_shared_size,
                    dtype=self.dtype,
                    init_std=cfg.init_std,
                    name="shared_expert",
                )(x.astype(self.dtype))
        elif cfg.num_shared_experts:
            from luminaai_tpu.models.layers import SwiGLU

            # num_shared_experts SwiGLUs of width F side by side are one
            # of width n * F whose output is their SUM; 'average' divides
            # it by n.
            with jax.named_scope("moe_shared"):
                shared = SwiGLU(
                    cfg.num_shared_experts * F,
                    dtype=self.dtype,
                    init_std=cfg.init_std,
                    name="shared_expert",
                )(x.astype(self.dtype))
            if cfg.shared_expert_combine == "average":
                shared = shared * (1.0 / cfg.num_shared_experts)
            out = out + shared

        # --- Aux losses + stats (ref :1244) ---
        # f_e: fraction of tokens whose slot went to expert e; P_e: mean prob.
        f = tokens_per_expert / (G * S * k + 1e-9)
        if cfg.moe_score_func == "sigmoid":
            # The balance statistics want a distribution over experts.
            router_probs = router_probs / (
                router_probs.sum(axis=-1, keepdims=True) + 1e-9
            )
        p = router_probs.mean(axis=(0, 1))
        lse2 = jnp.mean(jax.nn.logsumexp(gate_logits, axis=-1) ** 2)
        drop = dropped.mean()
        # Router health (fp32, in-jit — leaves the device only at the
        # trainer's log-window sync): mean per-token entropy of the
        # routing distribution. ln(E) = uniform routing; -> 0 = collapse.
        entropy = -jnp.mean(
            jnp.sum(router_probs * jnp.log(router_probs + 1e-9), axis=-1)
        )
        if cfg.moe_stat_pmean_axes:
            # Token shards each saw a fraction of the batch (over 'expert'
            # when ep borrows the data dim, over 'sequence' under manual
            # sp): average the routing stats over those axes so the aux/z
            # losses are computed from GLOBAL fractions (sum-of-products ≠
            # product-of-sums — matching the non-manual math, grads
            # included via the differentiable pmean).
            axes = tuple(cfg.moe_stat_pmean_axes)
            f = jax.lax.pmean(f, axes)
            p = jax.lax.pmean(p, axes)
            lse2 = jax.lax.pmean(lse2, axes)
            drop = jax.lax.pmean(drop, axes)
            entropy = jax.lax.pmean(entropy, axes)
        aux_loss = jnp.clip(
            jnp.sum(f * p) * E * cfg.load_balancing_weight, max=1.0
        )
        z_loss = lse2 * cfg.router_z_loss_weight
        metrics = {
            "moe_aux_loss": aux_loss,
            "moe_z_loss": z_loss,
            "moe_drop_rate": drop,
            "expert_utilization": f * E,  # 1.0 == perfectly balanced
            "moe_router_entropy": entropy,
            # Hottest expert's share of KEPT (token, slot) pairs: 1/E ==
            # balanced, -> 1.0 == collapse onto one expert. Normalized by
            # the kept mass so capacity drops don't masquerade as balance.
            "moe_max_expert_share": jnp.max(f) / (jnp.sum(f) + 1e-9),
        }
        # a2a dispatch stats: global routed-token counts per hierarchy
        # stage (every kept pair rides stage 1; only host-crossing pairs
        # ride the dcn stage). trainer._export_router_health turns these
        # into ep_dispatch_tokens_total and router_health events.
        metrics.update(ep_stats)
        return out.astype(self.dtype), metrics

    def _gmm_path(
        self, x: jax.Array, router_probs: jax.Array, wi, wo, capacity: int,
        rule: Optional[Dict[str, Any]] = None,
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Ragged expert FFN via the Pallas megablox grouped matmul.

        Tokens are sorted by assigned expert; each expert's two matmuls
        run over exactly its kept rows ([N_kept, H] x [H, 2F]), so the
        capacity-padded [E, G, C, ·] buffers of the sort/gather paths —
        and the ~cf·k/E-1 fraction of wasted padded-slot FLOPs — never
        exist. Routing (slots, gates, drops, per-group capacity) comes
        from the same _sort_routing, so outputs and stats match the other
        dispatch modes exactly. (The TPU counterpart of the ref's grouped
        CUDA expert kernels, Src/Main_Scripts/core/moe_cuda_wrapper.py:628.)

        On a multi-device mesh the path runs under shard_map (GSPMD can't
        partition the Pallas custom call): tokens stay sharded over
        (data, fsdp) exactly as the activation rules place them, expert
        weights stay sharded over 'expert', and each shard runs megablox
        over only the pairs routed to ITS local experts — the kernel's
        group_sizes bound keeps per-shard FLOPs proportional to locally
        kept rows, so the zero-padding win survives dp/fsdp/ep
        composition. A psum over 'expert' combines the partial token
        outputs (each pair contributes on exactly the shard owning its
        expert).

        tensor composes too (r6): wi enters as SEPARATE gate/up halves
        each column-sharded over 'tensor' (the fused [., 2F] layout can't
        shard directly — a contiguous 2F/tp slice would put all of gate
        on the low shards and all of up on the high ones, breaking the
        local silu(gate)*up), wo is row-sharded over its F dim, and each
        shard's partial token outputs join the same psum — now over
        ('expert', 'tensor'). This is Megatron column-then-row parallelism
        expressed inside the shard_map body; the only per-block collective
        stays the output psum. sequence/pipe remain unsupported (config
        rejects — they would split the kernel's row dimension).

        Returns (combined_out [G,S,H], tokens_per_expert [E], dropped [G,S]).
        """
        cfg = self.config
        G, S, H = x.shape
        E, k = cfg.num_experts, cfg.moe_top_k
        gmm = _pick_gmm()
        rule = rule or {}

        from luminaai_tpu.parallel.mesh import active_mesh, shard_map

        mesh = active_mesh()
        multi = mesh is not None and mesh.size > 1
        if not multi or self.is_initializing():
            # Single device — or flax init, whose 1-row dummy batch can't
            # satisfy the sharded layout and whose activations are dead
            # code anyway (only param shapes survive init).
            return _gmm_local(
                x, router_probs, wi, wo,
                top_k=k, capacity=capacity, num_experts=E,
                dtype=self.dtype, gmm_fn=gmm, ep_axis=None, rule=rule,
            )

        for ax in ("sequence", "pipe"):
            if mesh.shape.get(ax, 1) > 1:
                raise ValueError(
                    f"moe_dispatch='gmm' does not compose with the "
                    f"'{ax}' mesh axis (size {mesh.shape[ax]}); use "
                    "'gather' dispatch"
                )
        dp_total = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
        if G % dp_total != 0:
            raise ValueError(
                f"gmm dispatch needs batch groups ({G}) divisible by "
                f"data*fsdp ({dp_total})"
            )
        tp = mesh.shape.get("tensor", 1)

        from jax.sharding import PartitionSpec as P

        tok_spec = P(("data", "fsdp"), None, None)

        if tp == 1:
            def body(x_l, probs_l, wi_l, wo_l):
                out, tpe, dropped = _gmm_local(
                    x_l, probs_l, wi_l, wo_l,
                    top_k=k, capacity=capacity, num_experts=E,
                    dtype=self.dtype, gmm_fn=gmm, ep_axis="expert",
                    rule=rule,
                )
                # Each pair's FFN output lives on the shard owning its
                # expert; tokens are replicated over 'expert', so a psum
                # assembles the full combine. tokens_per_expert sums the
                # per-token-shard local counts into the global [E] the
                # aux-loss math expects.
                out = jax.lax.psum(out, "expert")
                tpe = jax.lax.psum(tpe, ("data", "fsdp"))
                return out, tpe, dropped

            sharded = shard_map(
                body,
                mesh=mesh,
                in_specs=(tok_spec, tok_spec, P("expert", None, None),
                          P("expert", None, None)),
                out_specs=(tok_spec, P(), P(("data", "fsdp"), None)),
                check_vma=False,
            )
            return sharded(x, router_probs, wi, wo)

        # expert x tensor: pass gate/up halves so each tensor shard holds
        # MATCHED F/tp column slices of both (config.validate enforces
        # F % tp == 0). wo row-shards over the same F slices, so
        # silu(gate)*up and the down-projection stay shard-local; the
        # psum over ('expert', 'tensor') assembles the token outputs.
        F = wi.shape[-1] // 2

        def body_tp(x_l, probs_l, wi_g_l, wi_u_l, wo_l):
            wi_l = jnp.concatenate([wi_g_l, wi_u_l], axis=-1)
            out, tpe, dropped = _gmm_local(
                x_l, probs_l, wi_l, wo_l,
                top_k=k, capacity=capacity, num_experts=E,
                dtype=self.dtype, gmm_fn=gmm, ep_axis="expert",
                rule=rule,
            )
            out = jax.lax.psum(out, ("expert", "tensor"))
            tpe = jax.lax.psum(tpe, ("data", "fsdp"))
            return out, tpe, dropped

        sharded = shard_map(
            body_tp,
            mesh=mesh,
            in_specs=(
                tok_spec, tok_spec,
                P("expert", None, "tensor"), P("expert", None, "tensor"),
                P("expert", "tensor", None),
            ),
            out_specs=(tok_spec, P(), P(("data", "fsdp"), None)),
            check_vma=False,
        )
        return sharded(x, router_probs, wi[..., :F], wi[..., F:], wo)


    def _a2a_path(
        self, x: jax.Array, router_probs: jax.Array, wi, wo, capacity: int
    ) -> Tuple[jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
        """Routed expert FFN via the hierarchical all-to-all subsystem
        (parallel/expert_dispatch.py — design rationale lives there).

        Layout contract vs the gmm path: tokens shard over
        ('data', 'fsdp', 'expert') — EP borrows the data dimension, so
        each expert shard holds a DISTINCT token sub-batch and routes
        it, instead of replicating the batch over the expert axis and
        psum-ing full activations. That is what lets expert capacity
        scale past one host: adding expert shards adds token shards,
        and only routed tokens cross the dcn tier. tensor composes per
        the PR 5 contract (gate/up column-parallel halves, wo
        row-parallel, partial rows psum'd over 'tensor' before the
        combine exchange). sequence/pipe are rejected by config.

        Returns (out [G,S,H], tokens_per_expert [E] global, dropped
        [G,S], stats {ep_tokens_routed, ep_tokens_dcn} global)."""
        cfg = self.config
        G, S, H = x.shape
        E, k = cfg.num_experts, cfg.moe_top_k
        gmm = _pick_gmm()

        from luminaai_tpu.parallel.mesh import active_mesh, shard_map

        mesh = active_mesh()
        multi = mesh is not None and mesh.size > 1
        ep = mesh.shape.get("expert", 1) if mesh is not None else 1
        if not multi or self.is_initializing():
            out, tpe, dropped = _gmm_local(
                x, router_probs, wi, wo,
                top_k=k, capacity=capacity, num_experts=E,
                dtype=self.dtype, gmm_fn=gmm, ep_axis=None,
            )
            zero = jnp.float32(0.0)
            return out, tpe, dropped, {
                "ep_tokens_routed": zero, "ep_tokens_dcn": zero,
            }
        if ep == 1:
            # An expert axis is required for routing (config.validate
            # enforces it); a mesh that lost it at runtime still has a
            # correct path — the gmm composition over data/fsdp.
            out, tpe, dropped = self._gmm_path(
                x, router_probs, wi, wo, capacity
            )
            zero = jnp.float32(0.0)
            return out, tpe, dropped, {
                "ep_tokens_routed": zero, "ep_tokens_dcn": zero,
            }

        for ax in ("sequence", "pipe"):
            if mesh.shape.get(ax, 1) > 1:
                raise ValueError(
                    f"moe_dispatch='a2a' does not compose with the "
                    f"'{ax}' mesh axis (size {mesh.shape[ax]}); use "
                    "'gather' dispatch"
                )
        dp_total = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
        tok_shards = dp_total * ep
        if G % tok_shards != 0:
            raise ValueError(
                f"a2a dispatch needs batch groups ({G}) divisible by "
                f"data*fsdp*expert ({tok_shards}) — EP borrows the "
                "data dimension"
            )
        tp = mesh.shape.get("tensor", 1)
        dcn = max(1, int(getattr(cfg, "expert_dcn_size", 1)))

        from luminaai_tpu.parallel.expert_dispatch import (
            a2a_expert_ffn,
            export_plan_gauges,
            make_dispatch_plan,
        )

        plan = make_dispatch_plan(
            ep=ep,
            dcn_size=dcn,
            local_groups=G // tok_shards,
            seq=S,
            top_k=k,
            capacity=capacity,
            num_experts=E,
            hidden=H,
            itemsize=jnp.dtype(self.dtype).itemsize,
            overlap_chunks=max(1, int(getattr(
                cfg, "moe_a2a_overlap_chunks", 1
            ))),
            dp_groups=G // dp_total,
        )
        export_plan_gauges(plan)

        from jax.sharding import PartitionSpec as P

        tok_spec = P(("data", "fsdp", "expert"), None, None)
        tok_axes = ("data", "fsdp", "expert")

        def finish(out, tpe, dropped, stats):
            tpe = jax.lax.psum(tpe, tok_axes)
            stats = {
                name: jax.lax.psum(v, tok_axes)
                for name, v in stats.items()
            }
            return out, tpe, dropped, stats

        if tp == 1:
            def body(x_l, probs_l, wi_l, wo_l):
                return finish(*a2a_expert_ffn(
                    x_l, probs_l, wi_l, wo_l,
                    top_k=k, capacity=capacity, num_experts=E,
                    dtype=self.dtype, gmm_fn=gmm, ep_axis="expert",
                    plan=plan,
                ))

            sharded = shard_map(
                body,
                mesh=mesh,
                in_specs=(tok_spec, tok_spec, P("expert", None, None),
                          P("expert", None, None)),
                out_specs=(tok_spec, P(), P(tok_axes, None),
                           {"ep_tokens_routed": P(),
                            "ep_tokens_dcn": P()}),
                check_vma=False,
            )
            return sharded(x, router_probs, wi, wo)

        # expert x tensor: matched gate/up column slices + row-parallel
        # wo, exactly the gmm path's decomposition (config.validate
        # enforces F % tp == 0); the per-chunk psum over 'tensor' lives
        # inside a2a_expert_ffn so only one output copy rides the
        # combine exchange.
        F = wi.shape[-1] // 2

        def body_tp(x_l, probs_l, wi_g_l, wi_u_l, wo_l):
            wi_l = jnp.concatenate([wi_g_l, wi_u_l], axis=-1)
            return finish(*a2a_expert_ffn(
                x_l, probs_l, wi_l, wo_l,
                top_k=k, capacity=capacity, num_experts=E,
                dtype=self.dtype, gmm_fn=gmm, ep_axis="expert",
                plan=plan, tp_axis="tensor",
            ))

        sharded = shard_map(
            body_tp,
            mesh=mesh,
            in_specs=(
                tok_spec, tok_spec,
                P("expert", None, "tensor"), P("expert", None, "tensor"),
                P("expert", "tensor", None),
            ),
            out_specs=(tok_spec, P(), P(tok_axes, None),
                       {"ep_tokens_routed": P(), "ep_tokens_dcn": P()}),
            check_vma=False,
        )
        return sharded(x, router_probs, wi[..., :F], wi[..., F:], wo)


def _pick_gmm():
    """The grouped-matmul implementation for this backend: the Pallas
    megablox kernel on TPU, a masked-matmul reference elsewhere (megablox
    interpret mode is minutes-per-call even at test sizes; the fallback
    keeps all routing/sort/combine logic under CPU test with identical
    math), or the test-hook override."""
    if _GMM_OVERRIDE is not None:
        return _GMM_OVERRIDE
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm

    def gmm(lhs, rhs, group_sizes, preferred_element_type, **_):
        bounds = jnp.cumsum(group_sizes)
        row_expert = jnp.searchsorted(
            bounds, jnp.arange(lhs.shape[0]), side="right"
        )
        out = jnp.zeros(
            (lhs.shape[0], rhs.shape[-1]), preferred_element_type
        )
        for e in range(rhs.shape[0]):
            sel = (row_expert == e)[:, None].astype(lhs.dtype)
            out = out + (
                (lhs * sel) @ rhs[e]
            ).astype(preferred_element_type)
        return out

    return gmm


def _gmm_local(
    x: jax.Array, router_probs: jax.Array, wi, wo, *,
    top_k: int, capacity: int, num_experts: int, dtype, gmm_fn,
    ep_axis: Optional[str], rule: Optional[Dict[str, Any]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One shard's ragged grouped-matmul expert FFN.

    x [G, S, H] and router_probs [G, S, E] are this shard's LOCAL token
    groups (the whole batch when unsharded); wi [E_l, H, 2F] / wo
    [E_l, F, H] are its LOCAL experts. Routing runs over the FULL expert
    dimension (probs carry all E columns) so capacity/drop semantics are
    global; pairs routed to non-local experts sort to the excluded tail
    exactly like dropped pairs, and their zeroed rows annihilate in the
    combine — each pair contributes only on the shard owning its expert.

    Returns (out [G,S,H] partial over experts, tokens_per_expert [E]
    local-groups count, dropped [G,S])."""
    G, S, H = x.shape
    E, k, C = num_experts, top_k, capacity
    E_l = wi.shape[0]
    N = G * S * k

    slot, gate, dropped, counts = _sort_routing(
        router_probs, k, C, **(rule or {})
    )
    gate = gate.astype(dtype)

    # Pair -> expert; dropped pairs get sentinel E_l and sort after every
    # real (local) expert's run (excluded via group_sizes).
    e_pair = jnp.where(slot < E * C, slot // C, E).reshape(-1)  # [N]
    counts_e = counts.sum(axis=0).astype(jnp.int32)  # [E] kept, local groups
    if ep_axis is not None and E_l != E:
        # Expert-parallel shard: keep only pairs whose expert lives here;
        # everything else joins the excluded tail.
        e_lo = jax.lax.axis_index(ep_axis) * E_l
        loc = e_pair - e_lo
        e_sort = jnp.where((loc >= 0) & (loc < E_l), loc, E_l)
        group_sizes = jax.lax.dynamic_slice_in_dim(counts_e, e_lo, E_l)
    else:
        e_sort = e_pair
        group_sizes = counts_e
    perm = jnp.argsort(e_sort, stable=True)  # [N] pair ids, expert-major
    # Pair id p = ((g*S)+s)*k + r -> its token row in x_flat is p // k.
    x_flat = x.astype(dtype).reshape(G * S, H)
    # Tile padding: megablox walks the sorted buffer in 128-row tiles, so
    # the buffer rounds UP to the boundary. Pad rows are zeros appended
    # past row N — and total_kept <= N always, so they sit in the same
    # excluded tail dropped pairs use: group_sizes never reaches them, no
    # kernel tile processes them beyond the ragged remainder, and the
    # row_kept masks below annihilate whatever the kernel leaves there.
    # This is what makes ANY batch/seq/top_k combination dropless — the
    # r5-era 128-row shape fence raised instead.
    N_pad = -(-N // _GMM_ROW_TILE) * _GMM_ROW_TILE
    # Rows past sum(group_sizes) are never touched by the kernel: its
    # forward leaves those output tiles uninitialized, and its custom
    # VJP leaves the matching grad_lhs rows uninitialized too (it only
    # zeroes the tail when rhs carries more groups than group_sizes —
    # not the case here). Dropped pairs still map via perm//k to REAL
    # token rows, so uninitialized grad rows would scatter-add garbage
    # into real tokens' d_x through the x_flat[perm//k] gather VJP.
    # jnp.where on the OPERANDS fixes both directions: its VJP selects
    # (rather than multiplies), so cotangents for masked rows are
    # annihilated exactly, and NaN garbage cannot leak through. (The pad
    # rows ride the same masks; jnp.pad's VJP is a slice, so their
    # cotangents simply fall off.)
    total_kept = group_sizes.sum()
    row_kept = jnp.arange(N_pad)[:, None] < total_kept  # [N_pad, 1]
    rows = x_flat[perm // k]  # [N, H] sorted rows
    if N_pad != N:
        rows = jnp.pad(rows, ((0, N_pad - N), (0, 0)))
    lhs = jnp.where(row_kept, rows, 0)  # [N_pad, H]

    fused = gmm_fn(
        lhs,
        wi.astype(dtype),
        group_sizes,
        preferred_element_type=dtype,
    )  # [N_pad, 2F]
    gate_act, up = jnp.split(fused, 2, axis=-1)
    act = jnp.where(row_kept, nn.silu(gate_act) * up, 0)
    yrow = gmm_fn(
        act,
        wo.astype(dtype),
        group_sizes,
        preferred_element_type=dtype,
    )  # [N_pad, H]
    # Forward output tiles past the kept region are uninitialized too —
    # zero them before the unsort so garbage can't meet a
    # NaN-propagating gate product.
    yrow = jnp.where(row_kept, yrow, 0.0)[:N]

    inv_perm = jnp.argsort(perm)  # back to pair order
    y_pairs = yrow[inv_perm].reshape(G, S, k, H)
    out = jnp.einsum("gskh,gsk->gsh", y_pairs, gate)
    return out, counts_e.astype(jnp.float32), dropped


def _held_gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """megablox (tm, tk, tn) for the held experts' calls, looked up by
    the problem's sizes (forward, and the two transposed problems of its
    backward). The kernel's default 128 x 128 x 128 walks [8192, 2304] x
    [2304, 2048] in 18,432 grid steps and read 4% of its roofline on the
    chip (PERF.md, PR 35); tiles of up to 512 x 768 x 1024 keep operands,
    accumulator and output under the default scoped VMEM. tn must divide
    n; a k tile that does not divide k is masked by the kernel."""
    def fit(size, most):
        for t in range(min(most, size) // 128 * 128, 127, -128):
            if size % t == 0:
                return t
        return 128

    return (512 if m % 512 == 0 else 128), fit(k, 768), fit(n, 1024)


def held_row_bound(cfg, tokens: int) -> int:
    """Rows of the held experts' sorted buffer for a program of `tokens`
    rows (Config.experts_held): capacity_factor x the expected tokens * k *
    held / E, rounded up to megablox's row tile."""
    _, cnt = cfg.experts_held
    rows = int(
        cfg.capacity_factor * tokens * cfg.moe_top_k * cnt / cfg.num_experts
    )
    return -(-rows // _GMM_ROW_TILE) * _GMM_ROW_TILE


# What held_combine_is_product weighs, a v5e's: the MXU's bf16 peak, and
# a serial float32 scatter-add's cost a row (a fixed part, and the row read,
# added and written back: 0.56 ms for 2,304 rows of 7,168 in kimi-k2's tick,
# PERF.md, PR 45).
_MXU_FLOPS = 197e12
_SCATTER_ROW_S = 0.1e-6
_SCATTER_BYTES_S = 400e9


def held_combine_is_product(tokens: int, rows: int, hidden: int,
                            dtype) -> bool:
    """Which form takes _gmm_held's sorted rows back to their tokens: a
    pure function of the shapes the program has (T tokens, R sorted rows,
    H wide, the model's dtype), as lane_attention_eligible is for the
    lanes' kernel.

    As ONE product on the MXU, out[T, H] = P[T, R] . yrow[R, H], it costs
    2.T.R.H x passes / 197 TFLOP/s (one pass where both operands are bf16,
    six for a float32 program's HIGHEST). As a scatter-add it costs R
    serial read-modify-writes of a float32 row: R x (0.1 us + 8.H B /
    400 GB/s). R cancels: the product does T multiply-adds for an element
    of a sorted row where the scatter does one slow add, so the product is
    a TICK's form (T a few hundred rows) and the scatter a training
    batch's. The product is taken where the estimate has it win by 3 x or
    more (the estimate is the MXU's peak, and a backward pass is two more
    such products): up to T = 1,114 / 1,458 / 2,081 at H 7,168 / 4,096 /
    2,304 in bf16. Measured on the chip (PERF.md, PR 46): the served
    ticks (T 288, R 2,304; H 7,168) 0.048 ms against the scatter's 0.71
    with its float32 widening; kimi-linear's step (T 16,384, R 8,192,
    H 2,304) 3.4 ms against 2.6 forward, so it keeps the scatter."""
    passes = 1 if jnp.dtype(dtype).itemsize <= 2 else 6
    product = 2.0 * tokens * rows * hidden * passes / _MXU_FLOPS
    scatter = rows * (_SCATTER_ROW_S + 8.0 * hidden / _SCATTER_BYTES_S)
    return 3.0 * product <= scatter


def held_combine_form(cfg, tokens: int, dtype):
    """What a program of `tokens` rows built from `cfg` in `dtype` does in
    its held expert layers, for the gauge moe_held_combine_product: T, R,
    H and whether the combine is the product; None without
    Config.experts_held."""
    if not (cfg.use_moe and cfg.experts_held):
        return None
    rows = held_row_bound(cfg, tokens)
    return {
        "T": int(tokens), "R": rows, "H": int(cfg.moe_width()),
        "product": held_combine_is_product(
            tokens, rows, cfg.moe_width(), dtype
        ),
    }


def export_held_combine(form, registry, log) -> None:
    """Say which form a built program's held layers have
    (held_combine_form's answer, not None): the gauge
    moe_held_combine_product (1 the product, 0 the scatter-add) and one
    log line. The scheduler and the trainer call it where they build their
    program: the form is decided when that is traced, not step by step."""
    registry.gauge(
        "moe_held_combine_product",
        "1 where the held experts' rows go back to their tokens as one "
        "product on the MXU, 0 as a scatter-add (models/moe.py "
        "held_combine_is_product)",
    ).set(int(form["product"]))
    log.info(
        "held experts' combine: T=%(T)d R=%(R)d H=%(H)d product=%(product)s",
        form,
    )


def _held_combine(yrow, tok, w_row, row_kept, tokens: int, product: bool):
    """The held experts' sorted rows back to their tokens: out[t] = the
    float32 sum of w_row[r] * yrow[r] over the kept rows r with
    tok[r] == t. yrow [R, H] and w_row [R] are in the model's dtype (the
    kernel's output; the gate as _gmm_held cast it), so each product is
    exact in float32 and the two forms differ by the order of a token's at
    most k additions. Returns float32 [tokens, H].

    The kernel's uninitialised tail (row_kept [R, 1] false) is zeroed
    BEFORE anything multiplies it, in both forms: a NaN there times a zero
    weight, or times a zero of P, is NaN (and for the gradient, a weight's
    cotangent is the row itself)."""
    y = jnp.where(row_kept, yrow, 0)
    if not product:
        # Training shapes: a token has at most k rows here and most have
        # none, so a scatter-add of R << N rows, not a gather of all N
        # pairs.
        y = y.astype(jnp.float32) * w_row[:, None].astype(jnp.float32)
        return jnp.zeros((tokens, y.shape[1]), jnp.float32).at[tok].add(y)
    # A tick's shapes: P[t, r] = the weight of sorted row r where it is
    # token t's and kept, else 0; one pass with a float32 accumulator.
    p = jnp.where(
        (tok[None, :] == jnp.arange(tokens)[:, None]) & row_kept.T,
        w_row[None, :], 0,
    )
    return jax.lax.dot_general(
        p, y, (((1,), (0,)), ((), ())),
        precision=(None if y.dtype.itemsize <= 2
                   else jax.lax.Precision.HIGHEST),
        preferred_element_type=jnp.float32,
    )


def _gmm_held(x, router_probs, wi, wo, *, top_k, num_experts, offset,
              row_bound, dtype, gmm_fn, rule, live=None, gated=True,
              count_hit=False):
    """The grouped-matmul expert FFN of a share the configuration names
    (Config.experts_held): wi / wo hold experts [offset, offset + E_l) of
    `num_experts`, routing runs over all of them, and the sort is
    _gmm_local's (held experts' runs first, every other pair in the
    excluded tail) with three differences: the sorted buffer is cut to
    `row_bound` rows (the held pairs are 1/32 of all pairs where 8 of 256
    are held; a buffer of all N would be 32 times the work), the combine
    takes those rows straight back to their tokens (_held_combine: one
    product on the MXU at a tick's sizes, where the bound is every pair; a
    scatter-add at a training step's, where it is a fraction of them:
    held_combine_is_product), and nothing is psum'd. The operand masks and
    the kernel's uninitialised-tail contract are _gmm_local's. `live`
    [G, S] (a serving tick): rows that are no tokens go to the excluded
    tail with the pairs of experts held elsewhere, and the pair counts are
    over live rows. `gated` False: the experts are the non-gated
    W_down relu(W_up x)^2 (wi is W_up alone). x may be narrower than the
    router's rows (a latent). `count_hit`: the stats also carry
    moe_held_experts_hit, the held experts with a computed row (the
    weights a call reads are the touched experts').

    Returns (out [G,S,H], tokens_per_expert [E], dropped [G,S], and the
    pair counts: routed, held (chosen for a held expert), held and not
    computed (beyond the row bound: must read 0))."""
    E, C = num_experts, x.shape[1]
    slot, gate, dropped, counts, chosen = _sort_routing(
        router_probs, top_k, C, with_chosen=True, **rule
    )
    gate = gate.astype(dtype)
    e_pair = jnp.where(slot < E * C, slot // C, E).reshape(-1)
    counts_e = counts.sum(axis=0).astype(jnp.int32)
    chosen_e = chosen.sum(axis=0)  # [E] pairs before any capacity
    G, S, H = x.shape
    E_l = wi.shape[0]
    k, R = top_k, row_bound
    loc = e_pair - offset
    here = (loc >= 0) & (loc < E_l)
    held = counts_e[offset:offset + E_l]  # pairs each held expert kept
    routed = jnp.float32(G * S * k)
    routed_here = chosen_e[offset:offset + E_l].sum()
    if live is not None:
        here = here & jnp.repeat(live.reshape(-1), k)
        held = jnp.zeros((E_l,), jnp.int32).at[
            jnp.where(here, loc, E_l)
        ].add(1, mode="drop")
        routed = live.sum().astype(jnp.float32) * k
        routed_here = held.sum()
    e_sort = jnp.where(here, loc, E_l)
    # Cut the runs at the bound, the last experts' rows first to go.
    ends = jnp.minimum(jnp.cumsum(held), R)
    group_sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    total = ends[-1]
    perm = jnp.argsort(e_sort, stable=True)[:R]  # held pairs, expert-major
    if perm.shape[0] < R:  # fewer pairs than one row tile (flax init)
        perm = jnp.pad(perm, (0, R - perm.shape[0]))
    tok = perm // k
    row_kept = jnp.arange(R)[:, None] < total
    x_flat = x.astype(dtype).reshape(G * S, H)
    lhs = jnp.where(row_kept, x_flat[tok], 0)
    fused = gmm_fn(lhs, wi.astype(dtype), group_sizes,
                   preferred_element_type=dtype, tiling=_held_gmm_tiling)
    if gated:
        gate_act, up = jnp.split(fused, 2, axis=-1)
        act = jnp.where(row_kept, nn.silu(gate_act) * up, 0)
    else:
        act = jnp.where(row_kept, jnp.square(nn.relu(fused)), 0)
    yrow = gmm_fn(act, wo.astype(dtype), group_sizes,
                  preferred_element_type=dtype, tiling=_held_gmm_tiling)
    with jax.named_scope("moe_held_combine"):
        out = _held_combine(
            yrow, tok, gate.reshape(-1)[perm], row_kept, G * S,
            held_combine_is_product(G * S, R, H, dtype),
        )
    stats = {
        "moe_routed_pairs": routed,
        "moe_held_pairs": routed_here.astype(jnp.float32),
        "moe_held_pairs_dropped": (routed_here - total).astype(jnp.float32),
    }
    if count_hit:
        stats["moe_held_experts_hit"] = (
            group_sizes > 0).sum().astype(jnp.float32)
    return (out.reshape(G, S, H).astype(dtype),
            counts_e.astype(jnp.float32), dropped, stats)
