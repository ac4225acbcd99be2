"""Core transformer layers, TPU-first (flax.linen).

Covers the reference's dense compute path (ref: Src/Main_Scripts/core/model.py —
RMSNorm:228, LayerNorm:307, RotaryEmbedding:334, DenseGroupedQueryAttention:565,
SwiGLUExpert:1027, DenseSwiGLU:1406) re-designed for XLA: static shapes, einsum
formulations that tile onto the MXU, bf16 compute with fp32 params, and logical
axis names (`flax.linen.with_logical_partitioning`) so the same module runs
under any dp/fsdp/tp/sp mesh layout.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from flax import struct

from luminaai_tpu.config import Config
from luminaai_tpu.ops.quantized import QuantizedTensor

Dtype = Any


def default_init(std: float = 0.02):
    return nn.initializers.normal(stddev=std)


class RMSNorm(nn.Module):
    """Root-mean-square norm (ref core/model.py:228). fp32 accumulation."""

    eps: float = 1e-6
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones, ("embed",)),
            (x.shape[-1],),
            jnp.float32,
        )
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.eps)
        return (y * scale).astype(self.dtype)


class LayerNorm(nn.Module):
    """Standard layernorm with optional bias (ref core/model.py:307)."""

    eps: float = 1e-5
    use_bias: bool = True
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        dim = x.shape[-1]
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones, ("embed",)),
            (dim,),
            jnp.float32,
        )
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.eps) * scale
        if self.use_bias:
            bias = self.param(
                "bias",
                nn.with_logical_partitioning(nn.initializers.zeros, ("embed",)),
                (dim,),
                jnp.float32,
            )
            y = y + bias
        return y.astype(self.dtype)


def yarn_ramp(head_dim: int, theta: float, yarn: Tuple) -> np.ndarray:
    """[head_dim // 2] in [0, 1]: how far YaRN divides each rotation
    frequency by its factor. yarn = (factor, original max positions,
    beta_fast, beta_slow): the pair that turns `beta` times over the
    original context sits at dim(beta) = head_dim ln(original / (2 pi
    beta)) / (2 ln theta); pairs below floor(dim(beta_fast)) keep their
    frequency, pairs above ceil(dim(beta_slow)) are divided by the
    factor, a linear ramp between."""
    _, original, fast, slow = yarn

    def dim(turns):
        return head_dim * math.log(original / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(fast)), 0)
    high = min(math.ceil(dim(slow)), head_dim - 1)
    span = max(high - low, 1e-3)
    return np.clip((np.arange(head_dim // 2) - low) / span, 0.0, 1.0)


def rope_frequencies(
    head_dim: int, max_len: int, theta: float = 10000.0,
    yarn: Optional[Tuple] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Precompute RoPE cos/sin tables in fp32 (ref core/model.py:334).
    `yarn` (Config.yarn()): YaRN's frequencies (yarn_ramp).

    Returns (cos, sin) of shape [max_len, head_dim//2].
    """
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if yarn is not None:
        ramp = jnp.asarray(yarn_ramp(head_dim, theta, yarn), jnp.float32)
        inv_freq = inv_freq * (1.0 - ramp) + inv_freq / yarn[0] * ramp
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    positions: Optional[jax.Array] = None,
    compute_dtype: Optional[Any] = None,
    layout: str = "split",
) -> jax.Array:
    """Rotate q/k (ref core/model.py:471 apply_rotary_pos_emb_optimized).

    x: [B, S, H, D]; cos/sin: [max_len, D//2]; positions: [B, S] (optional).
    layout 'split': pair i is (x[..., i], x[..., i + D/2]) (rotate_half);
    'interleaved': pair i is (x[..., 2i], x[..., 2i + 1]) (GPT-J's). The
    two are one model under a permutation of the head's columns.

    compute_dtype: fp32 by default (exact table math; an [B,S,H,D] fp32
    intermediate + convert per projection). Passing the model compute
    dtype (bf16) does the rotation in bf16 — inputs and outputs are bf16-
    quantized either way, so the only extra rounding is the products';
    the r3 trace prices the fp32 round-trips at ~70ms/step at flagship
    scale (config.rope_dtype sweeps this).
    """
    d2 = x.shape[-1] // 2
    ct = jnp.float32 if compute_dtype is None else compute_dtype
    if positions is None:
        c = cos[None, : x.shape[1], None, :]
        s = sin[None, : x.shape[1], None, :]
    else:
        c = cos[positions][:, :, None, :]
        s = sin[positions][:, :, None, :]
    c, s = c.astype(ct), s.astype(ct)
    if layout == "interleaved":
        x1, x2 = x[..., 0::2].astype(ct), x[..., 1::2].astype(ct)
        out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    x1, x2 = x[..., :d2].astype(ct), x[..., d2:].astype(ct)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


class SwiGLU(nn.Module):
    """Gated FFN: down(silu(gate(x)) * up(x)) (ref core/model.py:1406).

    Fused gate+up projection: one [embed, 2*mlp] matmul keeps the MXU busy
    instead of two half-width ones.
    """

    intermediate_size: int
    dtype: Dtype = jnp.bfloat16
    init_std: float = 0.02

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        hidden = x.shape[-1]
        wi = self.param(
            "wi",
            nn.with_logical_partitioning(
                default_init(self.init_std), ("embed", "mlp_fused")
            ),
            (hidden, 2 * self.intermediate_size),
            jnp.float32,
        )
        wo = self.param(
            "wo",
            nn.with_logical_partitioning(
                default_init(self.init_std / jnp.sqrt(2.0)), ("mlp", "embed")
            ),
            (self.intermediate_size, hidden),
            jnp.float32,
        )
        if isinstance(wi, QuantizedTensor):
            # Serving path: real int8 MXU dots (ops/quantized.py), the
            # TPU form of the ref's kernel-swap quantization
            # (ref trainer.py:658).
            from luminaai_tpu.ops.quantized import int8_project

            fused = int8_project(x, wi, self.dtype)
        else:
            fused = jnp.einsum("...d,df->...f", x, wi.astype(self.dtype))
        gate, up = jnp.split(fused, 2, axis=-1)
        act = nn.silu(gate) * up
        if isinstance(wo, QuantizedTensor):
            from luminaai_tpu.ops.quantized import int8_project

            return int8_project(act, wo, self.dtype)
        return jnp.einsum("...f,fd->...d", act, wo.astype(self.dtype))


class Relu2MLP(nn.Module):
    """The non-gated FFN down(relu(up(x))^2): two matrices, no bias."""

    intermediate_size: int
    dtype: Dtype = jnp.bfloat16
    init_std: float = 0.02

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        hidden = x.shape[-1]
        wi = self.param(
            "wi",
            nn.with_logical_partitioning(
                default_init(self.init_std), ("embed", "mlp")),
            (hidden, self.intermediate_size), jnp.float32)
        wo = self.param(
            "wo",
            nn.with_logical_partitioning(
                default_init(self.init_std / jnp.sqrt(2.0)),
                ("mlp", "embed")),
            (self.intermediate_size, hidden), jnp.float32)
        up = jnp.einsum("...d,df->...f", x, wi.astype(self.dtype))
        return jnp.einsum(
            "...f,fd->...d", jnp.square(nn.relu(up)), wo.astype(self.dtype))


# Above this many bytes of [chunk rows, heads, keys] float32 scores the
# tick's chunk attends through the blocked kernel (GQAttention.
# _tick_attention): 64 x 16 x 2,048 and 64 x 20 x 2,048 scores are 8 and
# 10 MB and stay XLA's; 256 x 64 x 512 (a ring of four pages) are 34 MB,
# 256 x 128 x 16,384 are 2.1 GB.
_CHUNK_SCORES_LIMIT = 16 * 2**20


class GQAttention(nn.Module):
    """Grouped-query attention with RoPE (ref core/model.py:565).

    Flash path: Pallas kernel on TPU (ops/flash_attention.py) replacing the
    reference's FlashAttention-2 CUDA dependency; XLA einsum fallback
    elsewhere. KV cache support for autoregressive decode.
    """

    config: Config
    dtype: Dtype = jnp.bfloat16
    # Static: this S>1 call writes MID-STREAM rows into an existing cache
    # (speculative-decode verification) rather than prefilling a fresh
    # one — a rolling cache then attends the cache with the slot mask
    # (the whole band is resident) instead of the raw prompt rows.
    multi_row_update: bool = False
    # The layer this is, for what Config says a layer (window_of,
    # rope_of); None reads the model's one value.
    layer_idx: Optional[int] = None

    @staticmethod
    def init_cache(cfg: Config, batch_size: int, max_len: int, dtype,
                   kv_cache_dtype: Optional[str] = None,
                   rolling: bool = True, lead=(),
                   layer: Optional[int] = None, ring=None):
        """What a lane keeps of an attention layer: a (k, v) pair of
        [batch, rows, kv_heads, head_dim] (int8: codes and per-row
        scales), which the pool pages, in THIS layer's own shape: its
        k/v heads (Config.kv_heads_of), and, where the value is narrower
        than the key (Config.attn_value_dim), k as a tuple of
        Config.key_parts() arrays of the value's width, the key's columns
        in order and the last zero-padded, beside a v of the same shape
        (192 over 128: two arrays and one, each [.., kv_heads, 128]: the
        rows lie as the lanes' kernel reads them, lane_attention_eligible
        says why). One of two entries by the layer's
        window: whole pages (`max_len` rows), or, for a layer with a
        window of its own in the slot-paged pool (`ring`: the pool's
        (page_size, prefill chunk)), a RING of Config.ring_pages pages a
        lane, read and written at absolute positions through the lane's
        ring table (LaneMeta.ring_table). LuminaTransformer.init_cache
        says when the single-stream engine's rows roll."""
        choice = kv_cache_dtype or cfg.kv_cache_dtype
        C = max_len
        n_ring = None
        if ring is not None and layer is not None:
            n_ring = cfg.ring_pages(layer, *ring)
        if n_ring is not None:
            # Whole pages when the ring would be no smaller.
            C = min(max_len, n_ring * ring[0])
        elif (
            rolling
            and cfg.attention_window is not None
            and max_len <= cfg.seq_length
        ):
            C = min(max_len, ((cfg.attention_window + 127) // 128) * 128)
        parts = cfg.key_parts()
        shape = (*lead, batch_size, C, cfg.kv_heads_of(layer),
                 cfg.head_dim() if parts == 1 else cfg.value_dim())

        def one():
            if choice == "int8":
                # (codes, per-row scales): half the HBM of a bf16 cache,
                # so max batch·context doubles (see config.kv_cache_dtype).
                return (
                    jnp.zeros(shape, dtype=jnp.int8),
                    jnp.ones((*shape[:-1], 1), dtype=jnp.float32),
                )
            return jnp.zeros(shape, dtype=dtype)

        if parts > 1:
            return (tuple(one() for _ in range(parts)), one())
        return (one(), one())

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        *,
        positions: Optional[jax.Array] = None,
        kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,
        cache_index: Optional[jax.Array] = None,
        deterministic: bool = True,
        lane_meta: Optional[Any] = None,
    ):
        cfg = self.config
        B, S, H = x.shape
        n_q, n_kv = cfg.num_heads, cfg.kv_heads_of(self.layer_idx)
        d, dv = cfg.head_dim(), cfg.value_dim()
        parts = cfg.key_parts()
        # The score scale where a head is handed on wider than it is
        # (below: a key kept in zero-padded parts); None: the width's own.
        scale = None

        wq = self.param(
            "wq",
            nn.with_logical_partitioning(
                default_init(cfg.init_std), ("embed", "heads", "head_dim")
            ),
            (H, n_q, d),
            jnp.float32,
        )
        wk = self.param(
            "wk",
            nn.with_logical_partitioning(
                default_init(cfg.init_std), ("embed", "kv_heads", "head_dim")
            ),
            (H, n_kv, d),
            jnp.float32,
        )
        wv = self.param(
            "wv",
            nn.with_logical_partitioning(
                default_init(cfg.init_std), ("embed", "kv_heads", "head_dim")
            ),
            (H, n_kv, dv),
            jnp.float32,
        )
        wo = self.param(
            "wo",
            nn.with_logical_partitioning(
                default_init(cfg.init_std / jnp.sqrt(2.0)),
                ("heads", "head_dim", "embed"),
            ),
            (n_q, dv, H),
            jnp.float32,
        )
        sink = None
        if cfg.sink_of(self.layer_idx):
            # One logit a query head in the softmax's denominator
            # (Config.layer_sink; ops/ragged_paged_attention.py
            # sink_softmax): float32 in every form that attends.
            sink = self.param(
                "sink",
                nn.with_logical_partitioning(
                    nn.initializers.normal(cfg.attn_sink_init_std)
                    if cfg.attn_sink_init_std else nn.initializers.zeros,
                    ("heads",),
                ),
                (n_q,),
                jnp.float32,
            ).astype(jnp.float32)

        if any(isinstance(w, QuantizedTensor) for w in (wq, wk, wv)):
            # Serving path: int8 MXU projections (ops/quantized.py). The
            # int8 dot is already one wide dot_general per weight, so the
            # bf16 fused-concat trick below isn't needed here. Per-weight
            # checks: min_size can leave e.g. the skinnier wk/wv in fp32
            # while wq quantizes.
            from luminaai_tpu.ops.quantized import int8_project

            def _proj(w):
                if isinstance(w, QuantizedTensor):
                    return int8_project(x, w, self.dtype)
                return jnp.einsum("bsd,dhk->bshk", x, w.astype(self.dtype))

            q, k, v = _proj(wq), _proj(wk), _proj(wv)
        elif cfg.tensor_parallel_size == 1:
            # One fused [H, (nq+2*nkv)*d] projection: three skinny matmuls
            # leave the MXU underfed; the weight concat is parameter-sized
            # (a few MB) and XLA folds it. Param tree stays wq/wk/wv so
            # checkpoints are unchanged. Under tensor parallelism the
            # concat axis mixes differently-sharded head dims (GSPMD would
            # replicate the fused weight), so tp keeps the per-weight
            # einsums below.
            wqkv = jnp.concatenate(
                [
                    wq.reshape(H, n_q * d),
                    wk.reshape(H, n_kv * d),
                    wv.reshape(H, n_kv * dv),
                ],
                axis=1,
            ).astype(self.dtype)
            qkv = jnp.einsum("bsd,df->bsf", x, wqkv)
            q = qkv[..., : n_q * d].reshape(B, S, n_q, d)
            k = qkv[..., n_q * d : (n_q + n_kv) * d].reshape(B, S, n_kv, d)
            v = qkv[..., (n_q + n_kv) * d :].reshape(B, S, n_kv, dv)
        else:
            q = jnp.einsum("bsd,dhk->bshk", x, wq.astype(self.dtype))
            k = jnp.einsum("bsd,dhk->bshk", x, wk.astype(self.dtype))
            v = jnp.einsum("bsd,dhk->bshk", x, wv.astype(self.dtype))

        def _out_proj(out):
            if isinstance(wo, QuantizedTensor):
                from luminaai_tpu.ops.quantized import int8_out_proj

                return int8_out_proj(out, wo, self.dtype)
            return jnp.einsum("bshk,hkd->bsd", out, wo.astype(self.dtype))

        if cfg.attn_value_scale != 1.0:
            v = v * jnp.asarray(cfg.attn_value_scale, v.dtype)

        # Runtime length can exceed cfg.seq_length (soft-prompt prefixes
        # prepend virtual tokens); the rope table covers whichever is larger.
        if kv_cache is not None:
            ck0 = kv_cache[0]
            # int8 caches are (codes, scales) pairs; bf16 are plain arrays.
            cache_len = (ck0[0] if isinstance(ck0, tuple) else ck0).shape[1]
            # A rolling (windowed) cache is slot-count-sized, not
            # position-sized — positions still run to config.seq_length
            # (init_cache only rolls when max_context fits it), so the
            # table covers the larger of the two.
            max_len = max(cfg.seq_length, S, cache_len)
        else:
            max_len = max(cfg.seq_length, S)
        window = cfg.window_of(self.layer_idx)
        if cfg.rope_of(self.layer_idx):
            # The head's first rope_dim columns turn (all of them by
            # default), at this layer's own base.
            turn = cfg.rope_dim or d
            cos, sin = rope_frequencies(
                turn, max_len, cfg.rope_theta_of(self.layer_idx))
            rope_ct = self.dtype if cfg.rope_dtype == "bf16" else jnp.float32

            def rotate(t):
                if turn == d:
                    return apply_rope(t, cos, sin, positions,
                                      compute_dtype=rope_ct,
                                      layout=cfg.rope_layout)
                return jnp.concatenate([
                    apply_rope(t[..., :turn], cos, sin, positions,
                               compute_dtype=rope_ct,
                               layout=cfg.rope_layout),
                    t[..., turn:],
                ], axis=-1)

            q, k = rotate(q), rotate(k)

        new_cache = None
        rolling_prefill = False
        per_lane = False
        rolling = False
        if kv_cache is not None:
            ck, cv = kv_cache
            C_cache = (ck[0] if isinstance(ck, tuple) else ck).shape[1]
            if parts > 1:
                # The entry keeps the key in value-width parts, the last
                # zero-padded (init_cache): q and k go on as wide as the
                # parts together, under the head's own scale.
                wide = ((0, 0),) * 3 + ((0, parts * dv - d),)
                q, k = jnp.pad(q, wide), jnp.pad(k, wide)
                scale = 1.0 / d**0.5
            # A cache_index of shape [B] means PER-LANE offsets: each
            # batch row is an independent slot of a paged pool at its own
            # sequence position (continuous batching — the scheduler owns
            # the decode loop and lanes join/leave mid-flight). Writes
            # scatter at per-lane rows; attention masks per lane. The pool
            # is admission-bounded (never wraps), so per-lane mode is
            # always plain-layout even under attention_window.
            per_lane = (
                cache_index is not None
                and getattr(cache_index, "ndim", 0) == 1
            )
            # The single-stream engine's cache is ROLLING only under the
            # one uniform window, when init_cache actually shrank it
            # below the position span; otherwise slot == position and
            # every plain-layout path below applies. (A layer with a
            # window of its own never rolls so: the pool gives it a ring
            # of pages, below.)
            rolling = (
                cfg.attention_window is not None
                and C_cache < max(cfg.seq_length, S)
                and not per_lane
            )
            # A RING of pages (init_cache): the pool's tick hands the
            # lanes' ring tables, and this layer's lane is shorter than
            # the pages the table names.
            ring_table = getattr(lane_meta, "ring_table", None)
            ring = (
                per_lane and window is not None and ring_table is not None
                and C_cache < ring_table.shape[1] * lane_meta.page_size
            )
            # Rolling-cache write index: slot = pos % C; decode wraps.
            if rolling and S == 1:
                write_at = jnp.mod(cache_index, C_cache)
            else:
                write_at = cache_index

            if per_lane and S > 1:
                # Per-lane multi-row write (prefill-into-slot): rows land
                # at their ABSOLUTE positions — no wrap, the pool slot is
                # sized to the request's full token budget. Liveness from
                # the caller's positions as in the rolling scatter below:
                # -1-marked bucket padding drops into the dummy row C so
                # it can never clobber a live slot.
                if positions is None:
                    raise ValueError(
                        "per-lane multi-row cache writes need explicit "
                        "positions (padding rows marked -1)"
                    )
                idx = jnp.where(positions >= 0, positions, C_cache)
                rows = jnp.arange(B)[:, None]

                def _scatter(cache_arr, fresh):
                    buf = jnp.pad(
                        cache_arr,
                        ((0, 0), (0, 1)) + ((0, 0),) * (cache_arr.ndim - 2),
                    )
                    return buf.at[rows, idx].set(fresh)[:, :C_cache]

            elif rolling and S > 1:
                # Multi-row write into a rolling cache: LIVE rows land at
                # pos % C with last-C-wins over live positions. Liveness
                # comes from the caller's positions: the engine marks
                # bucket-padding rows with position -1 — scattering
                # padding as if it were real trailing positions would
                # clobber in-band slots whenever the padded bucket
                # exceeds the slot count. Per-batch-row indices support
                # ragged vmapped prefill lanes; the dummy slot C absorbs
                # discarded rows. The scatter UPDATES the existing cache
                # (untouched slots keep their content), so it serves both
                # prefill (fresh zero cache — identical result) and
                # mid-stream multi-row writes like speculative-decode
                # verification, where K consecutive positions land at a
                # time (all live, k <= C distinct slots).
                if positions is None:
                    live = jnp.broadcast_to(jnp.arange(S) < S, (B, S))
                    pos_live = jnp.broadcast_to(jnp.arange(S), (B, S))
                else:
                    live = positions >= 0
                    pos_live = jnp.where(live, positions, 0)
                # Among THIS batch of rows, only the last C live ones can
                # coexist in the cache (distinct slots). live.sum is the
                # prompt length at prefill; for k-row mid-stream writes
                # (k <= C) the bound is vacuous and every row keeps.
                length_b = live.sum(axis=1, keepdims=True)  # [B, 1]
                keep = jnp.logical_and(
                    live, pos_live >= length_b - C_cache
                )
                idx = jnp.where(keep, pos_live % C_cache, C_cache)  # [B,S]
                rows = jnp.arange(B)[:, None]

                def _scatter(cache_arr, fresh):
                    buf = jnp.pad(
                        cache_arr,
                        ((0, 0), (0, 1)) + ((0, 0),) * (cache_arr.ndim - 2),
                    )
                    return buf.at[rows, idx].set(fresh)[:, :C_cache]

            elif per_lane:
                # One row per batch row, each at its own (slot, position):
                # a decode lane writes its own slot, and the rows of a
                # prefill chunk riding the batch (lane_meta.chunk_rows)
                # write the chunk's slot. A row at position -1 (a lane
                # not stepped, the chunk's padding) writes NOTHING: its
                # out-of-range index is dropped by the scatter.
                n_chunk = getattr(lane_meta, "chunk_rows", 0)
                row_slot = jnp.arange(B - n_chunk)
                if n_chunk:
                    row_slot = jnp.concatenate([
                        row_slot,
                        jnp.broadcast_to(lane_meta.chunk_slot, (n_chunk,)),
                    ])
                row_at = write_at if positions is None else jnp.where(
                    positions[:, 0] >= 0, positions[:, 0], C_cache
                )
                if ring:
                    # Logical page p // page_size lives where the lane's
                    # ring table says; the position stays absolute.
                    ps = lane_meta.page_size
                    at = jnp.maximum(positions[:, 0], 0)
                    page = ring_table[
                        row_slot,
                        jnp.minimum(at // ps, ring_table.shape[1] - 1),
                    ]
                    row_at = jnp.where(
                        positions[:, 0] >= 0, page * ps + at % ps, C_cache
                    )

                def _row_scatter(cache_arr, fresh):
                    return cache_arr.at[row_slot, row_at].set(
                        fresh[:, 0], mode="drop"
                    )

            if ring and S > 1:
                from luminaai_tpu.inference.kv_pool import RingKeepsWindowError

                raise RingKeepsWindowError(
                    "a ring of pages is written one row a lane a tick: a "
                    "multi-row write (speculation's k-row verify, a "
                    "whole-prompt prefill) is not served over a ring"
                )
            # One write for every array of the entry, by the kind of call.
            if S > 1 and (rolling or per_lane):
                put = _scatter
            elif per_lane:
                put = _row_scatter
            else:
                def put(cache_arr, fresh):
                    return jax.lax.dynamic_update_slice(
                        cache_arr, fresh, (0, write_at, 0, 0)
                    )

            if parts > 1:
                # Parts to the kernels that read them in place; the XLA
                # forms lay them side by side (whole_key).
                ck = tuple(
                    put(c, f)
                    for c, f in zip(ck, jnp.split(k, parts, axis=-1))
                )
                cv = put(cv, v)
                k_att, v_att = ck, cv
            elif isinstance(ck, tuple):
                # int8 KV cache (config.kv_cache_dtype='int8'): codes +
                # per-row scales. Quantize the fresh rows at insert; read
                # back the whole cache dequantized — XLA fuses the
                # convert-multiply into the attention dots, so the HBM
                # read is the int8 codes, not a rebuilt bf16 array.
                from luminaai_tpu.ops.quantized import quantize_act

                def _upd(cache, fresh):
                    q8, s = quantize_act(fresh)
                    codes, scales = put(cache[0], q8), put(cache[1], s)
                    deq = (codes.astype(jnp.float32) * scales).astype(
                        self.dtype
                    )
                    return (codes, scales), deq

                ck, k_att = _upd(ck, k)
                cv, v_att = _upd(cv, v)
            else:
                ck, cv = put(ck, k), put(cv, v)
                k_att, v_att = ck, cv
            new_cache = (ck, cv)
            if rolling and S > 1 and self.multi_row_update:
                # A k-row mid-stream write needs slack: row j's band must
                # survive rows j+1..k-1 landing in later slots — without
                # C - window >= k-1, the tail rows evict in-band slots of
                # earlier rows and the slot mask silently reads future
                # draft K/V as the evicted position (review-caught with
                # window % 128 == 0, where slack is zero).
                if S - 1 > C_cache - window:
                    raise ValueError(
                        f"rolling-cache multi-row update of {S} rows "
                        f"needs cache slack >= {S - 1} (cache {C_cache} "
                        f"slots, window {window}); reduce "
                        "draft_k or use a non-multiple-of-128 window"
                    )
            if rolling and S > 1 and not self.multi_row_update:
                # Rolling PREFILL attends the RAW rows (full banded
                # self-attention over the prompt): early prompt rows may
                # have been dropped from the slot-ordered cache, so the
                # slot mask can't serve them. Mid-stream multi-row writes
                # (multi_row_update) attend the cache instead — their
                # whole band is resident by construction.
                rolling_prefill = True
            else:
                k, v = k_att, v_att

        q = nn.with_logical_constraint(
            q, ("activation_batch", "activation_length", "activation_heads", None)
        )

        # Manual ring attention: already inside a shard_map whose manual
        # axes include 'sequence' (the 1F1B pipeline region) — q/k/v are
        # per-shard chunks, so call the ring BODY directly; nesting the
        # ring's own shard_map would be rejected.
        if (
            cfg.ring_manual
            and cfg.sequence_parallel_size > 1
            and kv_cache is None
            and not self.is_initializing()
        ):
            from luminaai_tpu.ops.flash_attention import flash_eligible
            from luminaai_tpu.ops.ring_attention import (
                _ring_attention_shard,
                _ring_attention_shard_flash,
            )

            sp = cfg.sequence_parallel_size
            if cfg.use_flash_attention and flash_eligible(
                S, d, cfg.flash_block_q, cfg.flash_block_kv
            ):
                out = _ring_attention_shard_flash(
                    q, k, v, axis_name="sequence", axis_size=sp,
                    causal=True,
                    block_q=min(cfg.flash_block_q, S),
                    block_kv=min(cfg.flash_block_kv, S),
                    window=window,
                )
            else:
                out = _ring_attention_shard(
                    q, k, v, axis_name="sequence", axis_size=sp,
                    causal=True,
                    window=window,
                )
            y = _out_proj(out)
            return y, new_cache

        # Ring attention: sequence/context parallelism. Activations arrive
        # sequence-sharded (activation_length → 'sequence'); K/V chunks
        # rotate the ring via ppermute instead of XLA all-gathering the full
        # sequence onto every device (ops/ring_attention.py).
        if (
            cfg.use_ring_attention
            and cfg.sequence_parallel_size > 1
            and kv_cache is None
            # init traces with a batch-1 dummy that can't shard over the
            # data axes; param shapes don't depend on the attention path.
            and not self.is_initializing()
        ):
            from luminaai_tpu.ops.ring_attention import ring_attention
            from luminaai_tpu.parallel.mesh import active_mesh

            mesh = active_mesh()
            if mesh is not None and mesh.shape.get("sequence", 1) > 1:
                q_spec = nn.logical_to_mesh_axes(
                    ("activation_batch", "activation_length",
                     "activation_heads", None)
                )
                kv_spec = nn.logical_to_mesh_axes(
                    ("activation_batch", "activation_length",
                     "activation_kv_heads", None)
                )
                out = ring_attention(
                    q, k, v, mesh, causal=True,
                    q_spec=q_spec, kv_spec=kv_spec,
                    use_flash=cfg.use_flash_attention,
                    block_q=cfg.flash_block_q,
                    block_kv=cfg.flash_block_kv,
                    window=window,
                )
                y = _out_proj(out)
                return y, new_cache

        from luminaai_tpu.ops.flash_attention import flash_eligible

        # Rolling prefill attends the raw prompt rows (see the cache
        # block above), which is exactly the no-cache forward — so the
        # banded flash kernel applies there too.
        use_flash = (
            cfg.use_flash_attention
            and (kv_cache is None or rolling_prefill)
            and flash_eligible(S, d, cfg.flash_block_q, cfg.flash_block_kv)
            # init traces with a batch-1 dummy that can't shard over the
            # data axes; param shapes don't depend on the attention path.
            and not self.is_initializing()
        )
        if use_flash:
            from luminaai_tpu.ops.flash_attention import (
                flash_attention_on_mesh,
            )
            from luminaai_tpu.parallel.mesh import active_mesh

            out = flash_attention_on_mesh(
                q,
                k,
                v,
                active_mesh(),
                nn.logical_to_mesh_axes(
                    ("activation_batch", None, "activation_heads", None)
                ),
                nn.logical_to_mesh_axes(
                    ("activation_batch", None, "activation_kv_heads", None)
                ),
                causal=True,
                block_q=cfg.flash_block_q,
                block_kv=cfg.flash_block_kv,
                window=window,
                scale=scale,
                sink=sink,
            )
        else:
            decoding_att = kv_cache is not None and not rolling_prefill
            # The ENGINE's backend choice (threaded as LaneMeta.backend)
            # beats the model's construction-time config — serving-time
            # overrides must not require a model rebuild.
            backend = (
                getattr(lane_meta, "backend", None)
                or getattr(cfg, "attention_backend", "dense")
            )
            if getattr(lane_meta, "chunk_rows", 0):
                with jax.named_scope(
                    "attn_global" if window is None else "attn_window"
                ):
                    out = self._tick_attention(
                        q, k, v, lane_meta, cache_index, positions,
                        backend, ring, scale, sink,
                    )
            elif kv_cache is not None and ring:
                out = self._ring_lanes(q, k, v, lane_meta, backend,
                                       scale, sink)
            elif decoding_att and backend != "dense" and not rolling:
                # Length-aware (LaneMeta) dispatch: scalar-offset decode,
                # batched per-lane decode, and (chunked) prefill all
                # describe themselves the same way and share ONE masking
                # implementation (ops/ragged_paged_attention.py) — the
                # per-variant forks below survive only as the 'dense'
                # oracle and the rolling-cache layouts, whose mod-C slot
                # arithmetic LaneMeta deliberately does not model.
                out = self._ragged_attention(
                    q, k, v, lane_meta, cache_index, positions, backend,
                    scale, sink,
                )
            else:
                out = self._xla_attention(
                    q, k, v, decoding_att, cache_index, scale, sink
                )

        y = _out_proj(out)
        return y, new_cache

    def _window(self) -> Optional[int]:
        return self.config.window_of(self.layer_idx)

    def _ring_lanes(self, q, k, v, meta, backend=None, scale=None,
                    sink=None):
        """The lanes' rows over their rings of pages, read in place: a
        physical page's rows are masked by the positions of the logical
        page the lane's ring table keeps there now, O(ring) rows a lane
        whatever its context. Where the shapes allow it on a TPU
        (lane_attention_engaged) one kernel reads only the blocks of the
        ring in which a stepped lane's query sees a key."""
        from luminaai_tpu.ops.ragged_paged_attention import (
            banded_attention_xla,
            lane_attention,
            lane_attention_engaged,
            ring_key_positions,
            whole_key,
        )

        n_d = q.shape[0]
        k0 = k[0] if isinstance(k, tuple) else k
        if lane_attention_engaged(
            backend, q.shape[1], q.shape[2], k0.shape[2], k0.shape[3],
            meta.page_size, v.shape[3],
        ):
            return lane_attention(
                q, k, v, meta.replace(window=self._window()), ring=True,
                scale=scale, sink=sink,
            )
        lengths = meta.lengths[:n_d]
        kpos = ring_key_positions(
            meta.ring_table[:n_d], lengths, meta.page_size, k0.shape[1]
        )
        return banded_attention_xla(
            q, whole_key(k)[:n_d], v[:n_d], (lengths - 1)[:, None], kpos,
            self._window(), scale=scale, sink=sink,
        )

    def _tick_attention(self, q, k, v, meta, cache_index, positions,
                        backend, ring=False, scale=None, sink=None):
        """A decode batch with a prefill chunk riding it (LaneMeta.
        chunk_rows): the one place the two kinds of rows part and meet
        again. No weight is involved here, so each kind keeps the
        arithmetic it has alone: the decode rows attend as a plain
        decode batch does (their LaneMeta, their extent), and the chunk's
        rows attend their own slot's rows, whole, as one multi-row query
        at `positions`: what a stand-alone chunk program would run.

        Two things are new with a layer that keeps a ring (`ring`): both
        kinds of rows read the ring in place, by the positions its pages
        hold now; and where the chunk's [rows, heads, keys] float32
        scores would not fit beside the weights (_CHUNK_SCORES_LIMIT),
        ring or whole pages, the chunk's rows go through the blocked
        online-softmax kernel (ops/ragged_paged_attention.py
        chunk_attention) and no score leaves VMEM."""
        from luminaai_tpu.ops.ragged_paged_attention import (
            banded_attention_xla,
            chunk_attention,
            chunk_attention_eligible,
            ring_key_positions,
            whole_key,
        )

        n_c = meta.chunk_rows
        n_d = q.shape[0] - n_c
        q_c = q[n_d:, 0][None]  # [1, n_c, Hq, D]
        pos_c = positions[n_d:, 0][None]
        start = jnp.reshape(meta.chunk_start, (1,))
        window = self._window()

        def own(a):  # the chunk's slot of a per-slot array
            return jax.lax.dynamic_slice_in_dim(a, meta.chunk_slot, 1, 0)

        def own_key(a):  # and of a key, its parts side by side
            if isinstance(a, tuple):
                return whole_key(tuple(own(part) for part in a))
            return own(a)

        C = v.shape[1]
        blocked = (
            backend != "dense" and not meta.global_pages
            and 4 * n_c * q.shape[2] * C > _CHUNK_SCORES_LIMIT
            and chunk_attention_eligible(n_c, C, q.shape[3], v.shape[3])
        )
        if ring or blocked:
            lanes = meta.replace(chunk_rows=0, chunk_slot=None,
                                 chunk_start=None)
            if ring:
                out_d = self._ring_lanes(q[:n_d], k, v, lanes, backend,
                                         scale, sink)
            else:
                out_d = self._ragged_attention(
                    q[:n_d], k, v, lanes, cache_index[:n_d], None, backend,
                    scale, sink,
                )
            # The chunk's keys, by position: a ring's pages hold what the
            # table says after this chunk's rows are written; whole pages
            # hold their own row numbers. `end` rows are live.
            end = jnp.max(pos_c) + 1
            if ring:
                kpos = ring_key_positions(
                    own(meta.ring_table), jnp.reshape(end, (1,)),
                    meta.page_size, C,
                )
            else:
                kpos = jnp.arange(C, dtype=jnp.int32)[None]
                kpos = jnp.where(kpos < end, kpos, -1)
            if blocked:
                out_c = chunk_attention(
                    q_c[0], own_key(k)[0], own(v)[0], pos_c[0], kpos[0],
                    window, jnp.minimum(end, C), scale=scale, sink=sink,
                )[None]
            else:
                out_c = banded_attention_xla(
                    q_c, own_key(k), own(v), pos_c, kpos, window,
                    scale=scale, sink=sink,
                )
        elif backend == "dense":
            out_d = self._xla_attention(
                q[:n_d], k, v, True, cache_index[:n_d], scale, sink
            )
            out_c = self._xla_attention(
                q_c, own_key(k), own(v), True, start, scale, sink
            )
        else:
            lanes = meta.replace(chunk_rows=0, chunk_slot=None,
                                 chunk_start=None)
            out_d = self._ragged_attention(
                q[:n_d], k, v, lanes, cache_index[:n_d], None, backend,
                scale, sink,
            )
            if meta.global_pages:
                # The slot's logical pages may live anywhere in the
                # pool (a spliced prefix): gather them through its own
                # row of the table.
                k_c, v_c = k, v
                own_meta = lanes.replace(
                    lengths=jnp.max(pos_c, axis=1).astype(jnp.int32) + 1,
                    page_table=own(meta.page_table),
                    kind="prefill", extent=None,
                )
            else:
                k_c, v_c, own_meta = own_key(k), own(v), None
            out_c = self._ragged_attention(
                q_c, k_c, v_c, own_meta, start, pos_c, backend, scale, sink
            )
        return jnp.concatenate([out_d, out_c[0][:, None]], axis=0)

    def _ragged_attention(self, q, k, v, meta, cache_index, positions,
                          backend, scale=None, sink=None):
        """Dispatch decode/prefill attention through the ragged
        paged-attention interface. Callers on the slot-paged KV pool pass
        a LaneMeta carrying the pool's page table and a static resident-
        extent bound; everyone else (scalar-offset decode, bucketed
        prefill, speculative verify) gets one derived here — identity
        pages, lengths recovered from positions/cache_index, full extent
        — which reproduces the dense per-lane mask bit-for-bit on
        resident rows."""
        from luminaai_tpu.ops.ragged_paged_attention import (
            LaneMeta,
            implied_page_size,
            paged_attention,
        )

        B, Sq = q.shape[0], q.shape[1]
        if meta is not None and meta.lengths is None:
            meta = None  # backend hint only; derive everything below
        if meta is None:
            if positions is not None:
                lengths = jnp.max(positions, axis=1).astype(jnp.int32) + 1
            elif getattr(cache_index, "ndim", 0) == 1:
                lengths = cache_index.astype(jnp.int32) + Sq
            else:
                lengths = jnp.full((B,), cache_index + Sq, jnp.int32)
            meta = LaneMeta(
                lengths=lengths,
                window=self._window(),
                kind="decode" if Sq == 1 else "prefill",
                page_size=implied_page_size(v.shape[1]),
            )
        if meta.window != self._window():
            # The caller's one window for the model; this layer has its own.
            meta = meta.replace(window=self._window())
        return paged_attention(
            q, k, v, meta,
            backend=backend,
            positions=positions if Sq > 1 else None,
            scale=scale, sink=sink,
        )

    def _xla_attention(self, q, k, v, decoding: bool, cache_index,
                       scale=None, sink=None):
        """Einsum attention fallback (ref core/model.py:783 _standard_attention).

        Grouped heads handled by reshape [B,S,Kv,G,D] — XLA maps the group
        dim onto the MXU batch dims; no head replication materialized.
        Honors config.attention_window (sliding window) in both the full
        and the decode (KV cache) paths. `scale`: the score scale where
        q and k come wider than the head (a key kept in parts); `sink`:
        the layer's sink logits (sink_softmax).
        """
        from luminaai_tpu.ops.ragged_paged_attention import (
            sink_softmax,
            whole_key,
        )

        k = whole_key(k)
        B, Sq, n_q, d = q.shape
        Skv, n_kv, dv = k.shape[1], k.shape[2], v.shape[3]
        g = n_q // n_kv
        qg = q.reshape(B, Sq, n_kv, g, d)
        if scale is None:
            scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale

        w = self._window()
        if (
            decoding
            and cache_index is not None
            and getattr(cache_index, "ndim", 0) == 1
        ):
            # PER-LANE decode (continuous batching): every lane sits at
            # its own offset in its own pool slot, so the causal/window
            # mask is batched. The pool never wraps (admission keeps
            # positions < C), so plain slot == position arithmetic holds
            # even when the window is set.
            qp = cache_index[:, None, None] + jnp.arange(Sq)[None, :, None]
            kp = jnp.arange(Skv)[None, None, :]
            mask = kp <= qp
            if w is not None:
                mask = jnp.logical_and(mask, qp - kp < w)
            logits = jnp.where(mask[:, None, None], logits, -1e30)
            probs = sink_softmax(logits, sink).astype(q.dtype)
            out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
            return out.reshape(B, Sq, n_q, dv)

        q_pos = jnp.arange(Sq)[:, None]
        if decoding:
            q_pos = q_pos + cache_index
        k_pos = jnp.arange(Skv)[None, :]
        if (
            decoding
            and w is not None
            and Skv < max(self.config.seq_length, Sq)
        ):
            # ROLLING-cache decode (cache smaller than the position
            # span): slot s holds the freshest position
            # p = t - ((t - s) mod C) of its residue class with p in
            # [length - C, t] all live (length-aware prefill scatter +
            # one write per decode step, each before its attend).
            # back <= t ⇔ p >= 0 (covers causality); back < w is the
            # band, and C >= w keeps every in-band position resident.
            back = jnp.mod(q_pos - k_pos, Skv)
            mask = jnp.logical_and(back <= q_pos, back < w)
        else:
            mask = q_pos >= k_pos
            if w is not None:
                mask = jnp.logical_and(mask, q_pos - k_pos < w)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        probs = sink_softmax(logits, sink).astype(q.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
        return out.reshape(B, Sq, n_q, dv)


@struct.dataclass
class LatentPages:
    """What a lane keeps of a 'latent' layer: pages of ONE row a token,
    [batch, rows, 1, width], shared by every head: [c (kv_lora_rank, after
    its norm); k_r (qk_rope_head_dim, rotated at the token's position);
    zeros up to `latent_entry_width`]. One array, so that a key block is
    fetched once and the value is its first kv_lora_rank columns; the
    axis of length one stands where k/v keep their heads, so the pool
    pages, inserts and exports it as it does k or v (inference/
    kv_pool.py). The node marks the kind (PagedKVPool.slot_bytes)."""

    rows: jax.Array


def is_latent_pages(x) -> bool:
    return isinstance(x, LatentPages)


def latent_entry_width(cfg: Config) -> int:
    """Columns of a latent entry's row: kv_lora_rank + qk_rope_head_dim,
    padded with zeros to whole 128-lane tiles (576 -> 640), which is what
    the chip's tiled layout would allocate anyway and lets the kernels'
    blocks and both matmuls stay tile-aligned."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


class LatentAttention(nn.Module):
    """Multi-head latent attention.

    Keys and values of every head are expanded from ONE low-rank latent a
    token: [c (kv_lora_rank); k_r (qk_rope_head_dim)] = W_kva x, c through
    an RMSNorm, [k_n (qk_nope_head_dim); v (v_head_dim)] a head = W_kvb c,
    and a head's key is [k_n; k_r] with k_r shared by all heads. Queries
    are one projection to heads x (nope + rope), or low-rank
    (`q_lora_rank`: W_qb RMSNorm(W_qa x)). Under `latent_rope` the rope
    parts of q and the shared k_r rotate at the token's position (YaRN's
    frequencies and scale under `yarn_factor`); without it nothing rotates
    (a stack whose recurrent layers carry position). Scores run over
    nope + rope dims (192) times Config.latent_softmax_scale, values and
    the output over v_head_dim (128).

    Two forms of one function. TRAINING (no cache) materialises k and v a
    head and takes the flash kernels at their two widths. SERVING (a
    cache: LatentPages, init_cache) stores the token's [c; rotated k_r]
    row and attends in the ABSORBED form: W_kvb's key half is folded into
    the query (q~ = W_kvb^K' q_n, 512 wide), the scores are q~ . c + q_r .
    k_r against the stored rows, the softmax sums the rows' c, and W_kvb's
    value half is applied after the sum: multi-query attention of every
    head over one shared key of kv_lora_rank + qk_rope_head_dim columns
    whose first kv_lora_rank are the value, with no expansion of what is
    stored. The pool's tick goes through ops/ragged_paged_attention.py's
    lane_attention (the lanes) and chunk_attention (the chunk) over the
    entry in place; every other cached call through latent_attention_xla.
    """

    config: Config
    dtype: Dtype = jnp.bfloat16

    @staticmethod
    def init_cache(cfg: Config, batch_size: int, max_len: int, dtype,
                   lead=()) -> LatentPages:
        """What a lane keeps of a latent layer: `max_len` rows of one
        latent a token (LatentPages), which the pool pages whole."""
        return LatentPages(rows=jnp.zeros(
            (*lead, batch_size, max_len, 1, latent_entry_width(cfg)), dtype))

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        *,
        positions: Optional[jax.Array] = None,
        kv_cache: Optional[LatentPages] = None,
        cache_index: Optional[jax.Array] = None,
        lane_meta: Optional[Any] = None,
    ):
        cfg = self.config
        B, S, H = x.shape
        n = cfg.num_heads
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        dq, rank = dn + dr, cfg.kv_lora_rank

        def mat(name, shape, axes, std=cfg.init_std):
            return self.param(
                name, nn.with_logical_partitioning(default_init(std), axes),
                shape, jnp.float32)

        def norm(name, t):
            return RMSNorm(cfg.rms_norm_eps, dtype=self.dtype, name=name)(t)

        x = x.astype(self.dtype)
        if cfg.q_lora_rank:
            wqa = mat("wq_a", (H, cfg.q_lora_rank), ("embed", None))
            wqb = mat("wq_b", (cfg.q_lora_rank, n, dq),
                      (None, "heads", "head_dim"))
            cq = norm("q_norm", jnp.einsum(
                "bsh,hr->bsr", x, wqa.astype(self.dtype)))
            q = jnp.einsum("bsr,rnd->bsnd", cq, wqb.astype(self.dtype))
        else:
            wq = mat("wq", (H, n, dq), ("embed", "heads", "head_dim"))
            q = jnp.einsum("bsh,hnd->bsnd", x, wq.astype(self.dtype))
        wkva = mat("wkv_a", (H, rank + dr), ("embed", None))
        wkvb = mat("wkv_b", (rank, n, dn + dv), (None, "heads", "head_dim"))
        wo = mat("wo", (n, dv, H), ("heads", "head_dim", "embed"),
                 cfg.init_std / jnp.sqrt(2.0))
        wkvb = wkvb.astype(self.dtype)

        kva = jnp.einsum("bsh,hr->bsr", x, wkva.astype(self.dtype))
        c = norm("kv_norm", kva[..., :rank])
        q_n, q_r, k_r = q[..., :dn], q[..., dn:], kva[..., None, rank:]
        if cfg.latent_rope:
            cached = 0 if kv_cache is None else kv_cache.rows.shape[-3]
            cos, sin = rope_frequencies(
                dr, max(cfg.seq_length, S, cached), cfg.rope_theta,
                yarn=cfg.yarn())
            m = cfg.latent_rope_mscale()
            if m != 1.0:
                cos, sin = cos * m, sin * m
            ct = self.dtype if cfg.rope_dtype == "bf16" else jnp.float32
            q_r = apply_rope(q_r, cos, sin, positions, compute_dtype=ct,
                             layout=cfg.rope_layout)
            k_r = apply_rope(k_r, cos, sin, positions, compute_dtype=ct,
                             layout=cfg.rope_layout)
        scale = cfg.latent_softmax_scale()

        def out_proj(o):
            return jnp.einsum("bsnd,ndh->bsh", o, wo.astype(self.dtype))

        if kv_cache is not None:
            with jax.named_scope("latent_attention"):
                o_lat, new_cache = self._cached(
                    q_n, q_r, c, k_r[:, :, 0], wkvb[..., :dn], scale,
                    kv_cache, positions, cache_index, lane_meta)
            o = jnp.einsum("bsnr,rnd->bsnd", o_lat, wkvb[..., dn:])
            return out_proj(o), new_cache

        kv = jnp.einsum("bsr,rnd->bsnd", c, wkvb)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (B, S, n, dr))], axis=-1)
        q = jnp.concatenate([q_n, q_r], axis=-1)
        v = kv[..., dn:]

        from luminaai_tpu.ops.flash_attention import flash_eligible

        with jax.named_scope("latent_attention"):
            if (
                cfg.use_flash_attention
                and flash_eligible(S, dq, cfg.flash_block_q,
                                   cfg.flash_block_kv)
                and dv % 64 == 0
                and not self.is_initializing()
            ):
                from luminaai_tpu.ops.flash_attention import (
                    flash_attention_on_mesh,
                )
                from luminaai_tpu.parallel.mesh import active_mesh

                spec = nn.logical_to_mesh_axes(
                    ("activation_batch", None, "activation_heads", None))
                out = flash_attention_on_mesh(
                    q, k, v, active_mesh(), spec, spec, causal=True,
                    scale=scale, block_q=cfg.flash_block_q,
                    block_kv=cfg.flash_block_kv,
                )
            else:
                s = jnp.einsum("bqnd,bknd->bnqk", q, k).astype(
                    jnp.float32) * scale
                keep = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
                p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
                out = jnp.einsum("bnqk,bknd->bqnd", p.astype(q.dtype), v)
        return out_proj(out), None

    def _cached(self, q_n, q_r, c, k_r, wk, scale, cache, positions,
                cache_index, meta):
        """The serving half: write the rows' latents into the entry at
        their positions, then the absorbed attention of the rows over it.
        Returns ([B, S, heads, kv_lora_rank] softmax sums of the stored c,
        the new entry). Three callers, told apart as GQAttention tells
        them: the pool's tick (one row a batch row, `cache_index` [B],
        a chunk's rows riding behind the lanes: LaneMeta.chunk_rows), a
        per-lane multi-row write (the whole-prompt bucket), and the
        single-stream engine's scalar offset."""
        from luminaai_tpu.ops.ragged_paged_attention import (
            chunk_attention,
            chunk_attention_eligible,
            lane_attention,
            lane_attention_engaged,
            latent_attention_xla,
        )

        B, S, n, _ = q_n.shape
        rank = c.shape[-1]
        rows = cache.rows
        C, W = rows.shape[1], rows.shape[3]
        if positions is None:
            raise ValueError(
                "a cached latent layer needs each row's position (padding "
                "rows marked -1): the stored key part is rotated at it"
            )

        def widen(parts, lead):
            pad = W - sum(p.shape[-1] for p in parts)
            return jnp.concatenate(
                [*parts, jnp.zeros((*lead, pad), self.dtype)], axis=-1)

        fresh = widen([c, k_r], (B, S))[:, :, None, :].astype(rows.dtype)
        q = widen([jnp.einsum("bsnd,rnd->bsnr", q_n, wk), q_r], (B, S, n))
        per_lane = getattr(cache_index, "ndim", 0) == 1
        n_c = getattr(meta, "chunk_rows", 0) if per_lane and S == 1 else 0
        n_d = B - n_c
        if not per_lane:
            rows = jax.lax.dynamic_update_slice(
                rows, fresh, (0, cache_index, 0, 0))
        else:
            # Each row at its own (slot, position): a lane's own slot, a
            # riding chunk's rows the chunk's slot. A row at position -1
            # (a lane not stepped, padding) writes nothing: its index is
            # out of range and the scatter drops it.
            slot = jnp.arange(n_d)
            if n_c:
                slot = jnp.concatenate(
                    [slot, jnp.broadcast_to(meta.chunk_slot, (n_c,))])
            rows = rows.at[
                slot[:, None], jnp.where(positions >= 0, positions, C)
            ].set(fresh, mode="drop")
        new_cache = LatentPages(rows=rows)

        backend = getattr(meta, "backend", None) or getattr(
            self.config, "attention_backend", "dense")
        if not (per_lane and S == 1):
            return latent_attention_xla(
                q, rows[:B], positions, scale, rank), new_cache

        # The tick: the lanes' rows over their own slots ...
        if n_d and meta.lengths is not None and lane_attention_engaged(
                backend, 1, n, 1, W, meta.page_size):
            lanes = meta.replace(chunk_rows=0, chunk_slot=None,
                                 chunk_start=None, window=None)
            out = lane_attention(q[:n_d], rows, None, lanes, scale=scale,
                                 v_dim=rank)
        else:
            extent = getattr(meta, "extent", None) or C
            out = latent_attention_xla(
                q[:n_d], rows[:n_d, :extent], positions[:n_d], scale, rank)
        if not n_c:
            return out, new_cache
        # ... and the chunk's rows over the chunk's slot, as one
        # multi-row query: blocked over the keys where the [rows, heads,
        # keys] float32 scores would not fit (_CHUNK_SCORES_LIMIT).
        own = jax.lax.dynamic_slice_in_dim(rows, meta.chunk_slot, 1, 0)
        q_c, pos_c = q[n_d:, 0], positions[n_d:, 0]
        if (
            backend != "dense"
            and 4 * n_c * n * C > _CHUNK_SCORES_LIMIT
            and chunk_attention_eligible(n_c, C, W)
        ):
            end = jnp.minimum(jnp.max(pos_c) + 1, C)
            kpos = jnp.arange(C, dtype=jnp.int32)
            out_c = chunk_attention(
                q_c, own[0], None, pos_c, jnp.where(kpos < end, kpos, -1),
                None, end, scale=scale, v_dim=rank,
            )
        else:
            out_c = latent_attention_xla(
                q_c[None], own, pos_c[None], scale, rank)[0]
        return jnp.concatenate([out, out_c[:, None]], axis=0), new_cache


class Embedder(nn.Module):
    """Token embedding with optional stable scaling and tied decode
    (ref core/model.py:1618 embedding handling)."""

    config: Config
    dtype: Dtype = jnp.bfloat16

    def setup(self):
        cfg = self.config
        self.embedding = self.param(
            "embedding",
            nn.with_logical_partitioning(
                default_init(cfg.init_std), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.hidden_size),
            jnp.float32,
        )
        if not cfg.tie_word_embeddings:
            # Untied output head (ref config tie_word_embeddings=False).
            self.lm_head = self.param(
                "lm_head",
                nn.with_logical_partitioning(
                    default_init(cfg.init_std), ("vocab", "embed")
                ),
                (cfg.vocab_size, cfg.hidden_size),
                jnp.float32,
            )

    def encode(self, tokens: jax.Array) -> jax.Array:
        if isinstance(self.embedding, QuantizedTensor):
            from luminaai_tpu.ops.quantized import embed_rows

            x = embed_rows(self.embedding, tokens, self.dtype)
        else:
            x = jnp.take(self.embedding, tokens, axis=0).astype(self.dtype)
        if self.config.use_stable_embedding:
            x = x * jnp.sqrt(float(self.config.hidden_size)).astype(self.dtype)
        return x

    def decode(self, x: jax.Array) -> jax.Array:
        # fp32 logits (accumulated via preferred_element_type) for a
        # numerically stable softmax/CE; operands stay in the compute dtype
        # so the MXU runs bf16 passes instead of fp32 ones.
        head = (
            self.embedding
            if self.config.tie_word_embeddings
            else self.lm_head
        )
        if isinstance(head, QuantizedTensor):
            # Serving path: the vocab projection is the single largest
            # decode matmul — int8 MXU with int32 accumulation, fp32 out.
            from luminaai_tpu.ops.quantized import int8_attend

            return int8_attend(x, head, jnp.float32)
        return jnp.einsum(
            "bsd,vd->bsv",
            x,
            head.astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
