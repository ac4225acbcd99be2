"""Flagship decoder-only transformer (dense / MoE / MoD / hybrid).

Covers the reference model assembly (ref: Src/Main_Scripts/core/model.py:1487
TransformerBlock, :1545 _should_use_moe, :1618 DeepSeekTransformer) re-designed
for XLA: pre-norm blocks, per-layer MoE placement patterns, MoD-wrapped dense
FFNs in hybrid mode, `jax.checkpoint` rematerialization instead of
torch.utils.checkpoint, and logical sharding constraints on the residual
stream. Static shapes throughout; decode path uses a preallocated KV cache
updated with `lax.dynamic_update_slice`.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from flax import linen as nn

from luminaai_tpu.config import Config
from luminaai_tpu.models.kda import KimiDeltaAttention
from luminaai_tpu.models.layers import (
    Embedder,
    GQAttention,
    LatentAttention,
    LayerNorm,
    RMSNorm,
    SwiGLU,
)
from luminaai_tpu.models.mod import MoDRouter, apply_mod
from luminaai_tpu.models.ssm import ScalarDecaySSM, SelectiveSSM
from luminaai_tpu.models.moe import MoELayer

Dtype = Any

REMAT_POLICIES = {
    "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
    # Store each block's two branch outputs (checkpoint_name tags below):
    # the backward then recomputes only the branch it is differentiating,
    # instead of the whole block, for 2 x [B,S,H] bf16 per layer of HBM.
    "save_outs": jax.checkpoint_policies.save_only_these_names(
        "attn_out", "ffn_out"
    ),
    # save_outs + the flash kernel's (out, lse) residuals (tagged in
    # GQAttention). The attention-branch backward then rebuilds only the
    # cheap q/k/v projections — the forward flash kernel is NOT re-run
    # (checkpoint's DCE drops it once its outputs are saved). Costs
    # ~[B,S,Hq,D] bf16 + [B,Hq,S] fp32 per layer (~105MB at flagship
    # scale); profiled at ~115ms/step of recompute removed (r3 trace).
    # Every mixer's output carries the "attn_out" tag, the latent mixer's
    # flash residuals the same two names. The delta-rule kernels tag three
    # things (ops/kda.py). "kda_inverse", each chunk's T = (I + A)^-1 as
    # `kda_tri` wrote it, IS kept: 16 KB of float32 a chunk a head, 134 MB
    # a layer at 2 x 8192 tokens and 32 heads, so the block's backward
    # re-runs `kda_fwd` alone and `kda_bwd` reads the same T; a layer
    # builds the inverse (ten float32 64^3 products a chunk) once a step
    # where it built it three times. "kda_out" and "kda_states" are NOT
    # kept: the states are 537 MB a layer, so the block's backward runs
    # the forward kernel once more instead. The described-v5e compile of
    # the kimi-linear cell's step reads 14.45 GB so (13.95 before T was
    # carried, 15.19 with the states kept too: PERF.md, PR 35 and 38). A
    # model without the delta-rule mixer has nothing of that name: its
    # step is the same program.
    "save_attn": jax.checkpoint_policies.save_only_these_names(
        "attn_out", "ffn_out", "flash_out", "flash_lse", "kda_inverse"
    ),
    "dots_saveable": jax.checkpoint_policies.dots_saveable,
    # 'full' = save everything, i.e. no recomputation (jax.checkpoint with
    # this policy is a no-op memory-wise; use it to A/B remat itself).
    "full": jax.checkpoint_policies.everything_saveable,
}


def block_norm(cfg: Config, dtype, name: str):
    """A block's (and the final) norm, by Config.norm_kind: RMSNorm, or
    the mean-subtracting LayerNorm without bias."""
    if cfg.norm_kind == "layernorm":
        return LayerNorm(
            cfg.layer_norm_eps, use_bias=False, dtype=dtype, name=name
        )
    return RMSNorm(cfg.rms_norm_eps, dtype=dtype, name=name)


class TransformerBlock(nn.Module):
    """Pre-norm block: x + attn(norm(x)); x + ffn(norm(x)). Under
    Config.parallel_block ONE norm feeds both and both are added to the
    residual: x + attn(norm(x)) + ffn(norm(x)).

    FFN is one of: dense SwiGLU, MoE (per `config.is_moe_layer`), or
    MoD-gated SwiGLU on dense layers in hybrid mode (ref core/model.py:1304).
    """

    config: Config
    layer_idx: int
    dtype: Dtype = jnp.bfloat16
    # Static (module attribute, not call arg) so nn.remat never traces it.
    deterministic: bool = True
    multi_row_update: bool = False  # see GQAttention.multi_row_update

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        *,
        positions: Optional[jax.Array] = None,
        kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,
        cache_index: Optional[jax.Array] = None,
        lane_meta: Optional[Any] = None,
    ):
        cfg = self.config
        deterministic = self.deterministic
        metrics: Dict[str, jax.Array] = {}

        kind = cfg.mixer_kind(self.layer_idx)
        ffn_kind = cfg.ffn_kind(self.layer_idx)
        decoding = kv_cache is not None
        if kind == "none":
            # The layer is its feed-forward alone, ONE norm: a lane keeps
            # nothing of it, so a cached call shows in cache_index alone.
            new_cache = None
            decoding = cache_index is not None
            residual = x
            y = block_norm(cfg, self.dtype, "ffn_norm")(x)
        else:
            normed = block_norm(cfg, self.dtype, "attn_norm")(x)
            if kind == "attention":
                h, new_cache = GQAttention(
                    cfg, dtype=self.dtype,
                    multi_row_update=self.multi_row_update,
                    layer_idx=self.layer_idx, name="attention",
                )(
                    normed,
                    positions=positions,
                    kv_cache=kv_cache,
                    cache_index=cache_index,
                    lane_meta=lane_meta,
                )
            elif kind in ("ssm", "ssm2"):
                mixer = ScalarDecaySSM if kind == "ssm2" else SelectiveSSM
                h, new_cache = mixer(cfg, dtype=self.dtype, name="ssm")(
                    normed,
                    positions=positions,
                    cache=kv_cache,
                    cache_index=cache_index,
                    lane_meta=lane_meta,
                )
            elif kind == "latent":
                h, new_cache = LatentAttention(
                    cfg, dtype=self.dtype, name="latent_attention"
                )(
                    normed,
                    positions=positions,
                    kv_cache=kv_cache,
                    cache_index=cache_index,
                    lane_meta=lane_meta,
                )
            else:
                if kv_cache is not None:
                    raise NotImplementedError(
                        f"layer {self.layer_idx}'s {kind!r} mixer has no "
                        "decode path (no delta-rule state a lane yet)"
                    )
                new_cache = None
                h, kda_stats = KimiDeltaAttention(
                    cfg, dtype=self.dtype, name="kda"
                )(normed)
                metrics.update(kda_stats)
            h = checkpoint_name(h, "attn_out")
            if ffn_kind == "none":
                # The layer is its mixer alone, ONE norm.
                x = x + h
                x = nn.with_logical_constraint(
                    x, ("activation_batch", "activation_length",
                        "activation_embed"))
                return x, new_cache, metrics
            if cfg.parallel_block:
                # The feed-forward reads the same normed rows as the mixer;
                # the residual takes both below.
                residual, y = x + h, normed
            else:
                x = x + h
                x = nn.with_logical_constraint(
                    x,
                    ("activation_batch", "activation_length", "activation_embed"),
                )
                residual = x
                y = block_norm(cfg, self.dtype, "ffn_norm")(x)
        if cfg.is_moe_layer(self.layer_idx):
            # A serving tick's rows at position -1 (a lane not stepped, a
            # chunk's padding) are no tokens: a share's grouped matmul and
            # its pair counters leave them out.
            live = None
            if decoding and positions is not None:
                live = positions >= 0
            ffn_out, moe_metrics = MoELayer(
                cfg, dtype=self.dtype, deterministic=deterministic, name="moe"
            )(y, live=live)
            metrics.update(moe_metrics)
        elif cfg.use_mod and not decoding:
            # MoD skip-routing on dense layers (hybrid mode); decode path runs
            # dense — per-token routing at S=1 has nothing to skip.
            ffn = SwiGLU(
                cfg.intermediate_size,
                dtype=self.dtype,
                init_std=cfg.init_std,
                name="ffn",
            )
            router = MoDRouter(
                cfg.mod_capacity_factor,
                cfg.mod_routing_temperature,
                dtype=self.dtype,
                name="mod_router",
            )
            ffn_out, mod_metrics = apply_mod(
                router, ffn, y,
                stat_pmean_axes=cfg.moe_stat_pmean_axes,
            )
            metrics.update(mod_metrics)
        else:
            ffn_out = SwiGLU(
                cfg.intermediate_size,
                dtype=self.dtype,
                init_std=cfg.init_std,
                name="ffn",
            )(y)

        ffn_out = checkpoint_name(ffn_out, "ffn_out")
        x = residual + ffn_out
        x = nn.with_logical_constraint(
            x, ("activation_batch", "activation_length", "activation_embed")
        )
        return x, new_cache, metrics


def scan_segments(config: Config) -> List[Tuple[int, Tuple[int, ...], int]]:
    """Decompose the layer stack into homogeneous scannable segments.

    Returns [(start_layer, unit_layer_offsets, count)]: the stack is
    `count` repetitions of a unit of len(unit) consecutive layers starting
    at start_layer. Layer kind (MoE vs dense) within a unit is static, so
    `lax.scan` over the unit is well-typed:

      - all/none/sandwich: run-length encoding of is_moe_layer → units of
        length 1 (sandwich yields 3 runs: dense, moe, dense).
      - every_3rd/every_4th: one unit per pattern period (e.g. [d, d, m]),
        so the whole periodic body is a single scan; the non-periodic tail
        becomes trailing count-1 segments.

    Compile time becomes O(#segments), not O(num_layers) — the fix for
    VERDICT r1 weak #5 (b30+ presets timing out on trace/compile).
    """
    L = config.num_layers
    kinds = [config.is_moe_layer(i) for i in range(L)]
    segments: List[Tuple[int, Tuple[int, ...], int]] = []
    period = {"every_3rd": 3, "every_4th": 4}.get(
        config.moe_pattern if config.use_moe else "", 0
    )
    if period and L >= period:
        body = (L // period) * period
        segments.append((0, tuple(range(period)), L // period))
        if body < L:  # non-periodic tail: plain layers
            for i in range(body, L):
                segments.append((i, (0,), 1))
        return segments
    # Run-length encode kinds (covers all/none/sandwich and no-MoE).
    i = 0
    while i < L:
        j = i
        while j < L and kinds[j] == kinds[i]:
            j += 1
        segments.append((i, (0,), j - i))
        i = j
    return segments


class _ScanUnit(nn.Module):
    """One scan step: a unit of consecutive TransformerBlocks.

    `start_layer + offsets` give representative layer indices — valid for
    every repetition because scan_segments only groups layers whose kind
    pattern repeats exactly.
    """

    config: Config
    start_layer: int
    offsets: Tuple[int, ...]
    dtype: Dtype = jnp.bfloat16
    deterministic: bool = True
    multi_row_update: bool = False

    @nn.compact
    def __call__(self, x, caches, positions, cache_index, lane_meta=None):
        new_caches = []
        unit_metrics: List[Dict[str, jax.Array]] = []
        for j, off in enumerate(self.offsets):
            x, nc, m = TransformerBlock(
                self.config,
                layer_idx=self.start_layer + off,
                dtype=self.dtype,
                deterministic=self.deterministic,
                multi_row_update=self.multi_row_update,
                name=f"block_{j}",
            )(
                x,
                positions=positions,
                kv_cache=None if caches is None else caches[j],
                cache_index=cache_index,
                lane_meta=lane_meta,
            )
            new_caches.append(nc)
            if m:
                unit_metrics.append(m)
        merged: Dict[str, jax.Array] = {}
        if unit_metrics:
            keys = set().union(*[m.keys() for m in unit_metrics])
            for key in keys:
                vals = [m[key] for m in unit_metrics if key in m]
                # Everything is summed here; diagnostics carry a __cnt
                # companion so the model-level reduction can form the exact
                # per-contributing-layer mean (identical weighting to the
                # unscanned path, where every layer contributes equally).
                if key.endswith("_min"):
                    merged[key] = jnp.stack(vals).min(axis=0)
                    continue
                merged[key] = jnp.stack(vals).sum(axis=0)
                if not key.endswith("_loss"):
                    merged[f"{key}__cnt"] = jnp.float32(len(vals))
        caches_out = None if caches is None else tuple(new_caches)
        return x, (caches_out, merged)


class LuminaTransformer(nn.Module):
    """Decoder-only LM with dense/MoE/MoD blocks (ref core/model.py:1618)."""

    config: Config

    @property
    def dtype(self):
        return (
            jnp.bfloat16
            if "bf16" in self.config.resolve_precision()
            else jnp.float32
        )

    @nn.compact
    def __call__(
        self,
        input_ids: jax.Array,
        *,
        positions: Optional[jax.Array] = None,
        kv_caches: Optional[List[Tuple[jax.Array, jax.Array]]] = None,
        cache_index: Optional[jax.Array] = None,
        deterministic: bool = True,
        return_hidden: bool = False,
        prefix_embeds: Optional[jax.Array] = None,
        multi_row_update: bool = False,
        lane_meta: Optional[Any] = None,
    ):
        cfg = self.config
        embedder = Embedder(cfg, dtype=self.dtype, name="embedder")
        x = embedder.encode(input_ids)
        n_prefix = 0
        if prefix_embeds is not None:
            # Soft-prompt tuning (training/adapters.py): [B, P, H] virtual
            # tokens prepended before the blocks; the prefix positions are
            # stripped again after final_norm, so outputs cover only real
            # tokens. RoPE/causality shift consistently with the longer
            # sequence. The prefix gets the same stable-embedding scale as
            # real tokens — init_soft_prompt samples raw table rows.
            n_prefix = prefix_embeds.shape[1]
            prefix = prefix_embeds.astype(x.dtype)
            if cfg.use_stable_embedding:
                prefix = prefix * jnp.sqrt(float(cfg.hidden_size)).astype(
                    x.dtype
                )
            x = jnp.concatenate([prefix, x], axis=1)
        x = nn.with_logical_constraint(
            x, ("activation_batch", "activation_length", "activation_embed")
        )

        decoding = kv_caches is not None
        remat_on = (
            cfg.gradient_checkpointing
            and not decoding
            and not self.is_initializing()
        )
        policy = REMAT_POLICIES.get(cfg.remat_policy)

        if cfg.scan_layers:
            x, new_caches, all_metrics = self._apply_scanned(
                x, positions, kv_caches, cache_index, deterministic,
                remat_on, policy, multi_row_update, lane_meta,
            )
        else:
            block_cls = TransformerBlock
            if remat_on:
                # prevent_cse=True is required here: under a plain layer loop
                # XLA would CSE the recomputation against the forward values,
                # keeping every layer's activations alive into the backward
                # pass (observed as per-layer MoE temps coexisting in the r2
                # flagship OOM). Inside nn.scan (below) False is safe — the
                # loop boundary already blocks CSE.
                block_cls = nn.remat(
                    TransformerBlock,
                    policy=policy,
                    prevent_cse=True,
                    static_argnums=(),
                )
            new_caches = []
            all_metrics = []
            for i in range(cfg.num_layers):
                cache_i = kv_caches[i] if decoding else None
                x, new_cache, metrics = block_cls(
                    cfg,
                    layer_idx=i,
                    dtype=self.dtype,
                    deterministic=deterministic,
                    multi_row_update=multi_row_update,
                    name=f"layer_{i}",
                )(
                    x,
                    positions=positions,
                    kv_cache=cache_i,
                    cache_index=cache_index,
                    lane_meta=lane_meta,
                )
                if decoding:
                    new_caches.append(new_cache)
                if metrics:
                    all_metrics.append(metrics)

        x = block_norm(cfg, self.dtype, "final_norm")(x)
        if n_prefix:
            # Strip virtual-token positions before the vocab matmul — the
            # [B, P, V] logits would be computed only to be discarded.
            x = x[:, n_prefix:]
        if return_hidden:
            # Caller fuses the LM head into the loss (ops/fused.py
            # fused_lm_head_cross_entropy) — full [B,S,V] logits never exist.
            aux = self._reduce_metrics(all_metrics)
            if decoding:
                # The serving tick projects only the rows it samples from.
                return x, new_caches, aux
            return x, aux
        logits = embedder.decode(x)
        logits = nn.with_logical_constraint(
            logits, ("activation_batch", "activation_length", "activation_vocab")
        )

        aux = self._reduce_metrics(all_metrics)
        if decoding:
            return logits, new_caches, aux
        return logits, aux

    def _apply_scanned(
        self, x, positions, kv_caches, cache_index, deterministic,
        remat_on, policy, multi_row_update=False, lane_meta=None,
    ):
        """`nn.scan` over homogeneous layer segments (see scan_segments).

        Params gain a leading 'layers' axis per segment (sharded over the
        'pipe' mesh axis under pipeline parallelism, replicated otherwise). KV caches are structured
        per segment: a tuple over unit positions of (k, v) stacked over the
        scan axis — init_cache builds the matching structure.
        """
        cfg = self.config
        decoding = kv_caches is not None
        new_caches = []
        all_metrics: List[Dict[str, jax.Array]] = []
        for s, (start, offsets, count) in enumerate(scan_segments(cfg)):
            unit_cls = _ScanUnit
            if remat_on:
                unit_cls = nn.remat(
                    _ScanUnit, policy=policy, prevent_cse=False,
                    static_argnums=(),
                )
            scanned_cls = nn.scan(
                unit_cls,
                variable_axes={"params": 0},
                split_rngs={"params": True, "routing": True, "dropout": True},
                in_axes=(0, nn.broadcast, nn.broadcast, nn.broadcast),
                out_axes=0,
                length=count,
                metadata_params={nn.meta.PARTITION_NAME: "layers"},
            )
            seg_caches = kv_caches[s] if decoding else None
            x, (caches_out, metrics) = scanned_cls(
                cfg,
                start_layer=start,
                offsets=offsets,
                dtype=self.dtype,
                deterministic=deterministic,
                multi_row_update=multi_row_update,
                name=f"scan_{s}",
            )(x, seg_caches, positions, cache_index, lane_meta)
            if decoding:
                new_caches.append(caches_out)
            if metrics:
                # Reduce the scan axis by summing: loss sums stay exact and
                # diagnostic sums/__cnt pairs accumulate total contributors
                # (count × per-unit contributors) for _reduce_metrics.
                all_metrics.append(
                    {k: v.min(axis=0) if k.endswith("_min") else v.sum(axis=0)
                     for k, v in metrics.items()}
                )
        return x, new_caches, all_metrics

    def _reduce_metrics(
        self, all_metrics: List[Dict[str, jax.Array]]
    ) -> Dict[str, jax.Array]:
        """Sum aux losses over layers; average diagnostics per contributing
        layer. Scanned segments provide (sum, __cnt) pairs; unscanned layers
        provide raw values (count 1 each) — both reduce to the same exact
        mean over all contributing layers."""
        out: Dict[str, jax.Array] = {"aux_loss": jnp.float32(0.0)}
        if not all_metrics:
            return out
        # Sorted: a set of strings iterates by the process's hash seed, and
        # the order the sums are emitted in is part of the compiled program's
        # cache key wherever a caller reads them (the tick's held-pair counts).
        keys = sorted(set().union(*[m.keys() for m in all_metrics]))
        for key in keys:
            if key.endswith("__cnt"):
                continue
            if key.endswith("_loss"):
                out[key] = jnp.stack(
                    [m[key] for m in all_metrics if key in m]
                ).sum()
                out["aux_loss"] = out["aux_loss"] + out[key]
            elif key.endswith("_min"):
                out[key] = jnp.stack(
                    [m[key] for m in all_metrics if key in m]
                ).min()
            else:
                total = cnt = None
                for m in all_metrics:
                    if key not in m:
                        continue
                    v = m[key]
                    n = m.get(f"{key}__cnt", jnp.float32(1.0))
                    total = v if total is None else total + v
                    cnt = n if cnt is None else cnt + n
                out[key] = total / cnt
        return out

    # -- decode cache (ref Chat.py:346 GenerationEngine cache handling) ----
    def init_cache(
        self,
        batch_size: int,
        max_len: int,
        kv_cache_dtype: str = None,
        rolling: bool = True,
        ring=None,
    ):
        """Preallocated KV caches, shaped to match the layer-stack layout:
        per-layer pairs normally; per-segment stacked pairs under
        scan_layers (opaque to the generation engine either way).

        kv_cache_dtype overrides the model config's choice — the
        generation engine passes ITS config so a serving-time override
        (e.g. chat --kv-cache-dtype) doesn't depend on the model having
        been built from the same mutable Config object.

        With attention_window set, the cache is ROLLING: only
        ceil(window/128)*128 slots are allocated (decode never attends
        past the band, so slot `pos % C` holds the freshest key for its
        residue class) — decode-cache HBM is O(window), not
        O(max_context). GQAttention's slot arithmetic reduces to the
        plain layout when the cache never wraps, so this is purely an
        allocation decision. Skipped when max_len exceeds the config
        sequence length (the RoPE table is sized by config.seq_length
        once the cache no longer records absolute positions).

        rolling=False forces the plain position-addressed layout even
        under attention_window — the slot-paged continuous-batching pool
        (inference/kv_pool.py) is admission-bounded so positions never
        wrap, and its per-lane writes assume slot == position.

        ring=(page_size, prefill chunk): the pool's; a layer with a window
        of its own (Config.layer_windows) then keeps a ring of pages a
        lane and not `max_len` rows (GQAttention.init_cache)."""
        cfg = self.config

        def entry(layer, *lead):
            """What a lane keeps of `layer`, by its mixer: pages of k/v,
            pages of one latent a token, a fixed state, or nothing (a
            layer that is its feed-forward alone)."""
            if cfg.mixer_kind(layer) == "none":
                return None
            if cfg.mixer_kind(layer) == "ssm":
                return SelectiveSSM.init_cache(
                    cfg, batch_size, self.dtype, lead)
            if cfg.mixer_kind(layer) == "ssm2":
                return ScalarDecaySSM.init_cache(
                    cfg, batch_size, self.dtype, lead)
            if cfg.mixer_kind(layer) == "latent":
                return LatentAttention.init_cache(
                    cfg, batch_size, max_len, self.dtype, lead)
            return GQAttention.init_cache(
                cfg, batch_size, max_len, self.dtype,
                kv_cache_dtype=kv_cache_dtype, rolling=rolling, lead=lead,
                layer=layer, ring=ring)

        if cfg.scan_layers:
            return [
                tuple(entry(start + off, count) for off in offsets)
                for start, offsets, count in scan_segments(cfg)
            ]
        return [entry(i) for i in range(cfg.num_layers)]


def count_params(params) -> int:
    """Total parameter count (ref core/model.py:1975 get_num_params)."""
    return sum(p.size for p in jax.tree.leaves(params))


def stack_params_for_scan(config: Config, params: Dict) -> Dict:
    """Convert a per-layer ('layer_{i}') param tree to the scanned layout
    ('scan_{s}/block_{j}' with a leading scan axis). The same weights give
    bit-identical outputs in either layout — used for checkpoint interop
    between scan_layers settings and to test scan correctness."""
    out = {k: v for k, v in params.items() if not k.startswith("layer_")}
    for s, (start, offsets, count) in enumerate(scan_segments(config)):
        u = len(offsets)
        seg = {}
        for j, off in enumerate(offsets):
            reps = [params[f"layer_{start + k * u + off}"] for k in range(count)]
            seg[f"block_{j}"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *reps
            )
        out[f"scan_{s}"] = seg
    return out


def unstack_params_from_scan(config: Config, params: Dict) -> Dict:
    """Inverse of stack_params_for_scan."""
    out = {
        k: v for k, v in params.items() if not k.startswith("scan_")
    }
    for s, (start, offsets, count) in enumerate(scan_segments(config)):
        u = len(offsets)
        seg = params[f"scan_{s}"]
        for k in range(count):
            for j, off in enumerate(offsets):
                out[f"layer_{start + k * u + off}"] = jax.tree.map(
                    lambda x, k=k: x[k], seg[f"block_{j}"]
                )
    return out
