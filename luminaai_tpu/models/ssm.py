"""Selective state-space mixer: Mamba-1's scan with Jamba's three inner
RMSNorms. Position lives in the recurrence, so nothing is rotated. Per
token u in R^hidden, inner width D = ssm_expand * hidden, state N:

    [x~, z]    = W_in u                          (D each)
    x          = silu(conv_K(x~) + b_conv)       causal, depthwise
    [dt, B, C] = W_x x                           (rank R, N, N), each
                                                 through its own RMSNorm
    dt         = softplus(W_dt dt + b_dt)        (D)
    h_t        = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) outer B_t,
                 A = -exp(A_log)                 [N, D] float32
    y_t        = h_t . C_t + D_skip * x_t
    out        = W_out (y * silu(z))

What a lane keeps of such a layer is a `LaneState`: the state h and the
convolution's tail (the last K - 1 rows of x~), fixed in size whatever
the lane's length: no pages, no extent. Three call forms:

- no cache: the whole sequence from zero state, the chunked XLA scan
  (`ops/ssm.py::selective_scan`): training and the logits comparison;
- a cache and one sequence a batch row ([B, T] tokens; `generate()`, the
  whole-prompt bucket path): the same scan entering from the cached state;
- a cache and one token a row with per-lane offsets (the serving tick):
  `lane_meta.chunk_rows` rows at the end are one prefill chunk of slot
  `lane_meta.chunk_slot`, the rows before them one step of each lane:
  `ops/ssm.py::ssm_scan`, one kernel.

In every cached form a row at position -1 changes neither state nor tail.

`ScalarDecaySSM` is Mamba-2's mixer over the same LaneState and the same
three call forms: H heads of P channels (inner D = H P), ONE decay and ONE
step a head, B and C shared by G groups of heads:

    [z, xBC, dt] = W_in u                 (D, D + 2 G N, H)
    xBC          = silu(conv_K(xBC) + b_conv)       over all D + 2GN
    [x, B, C]    = split(xBC)             x [H, P]; B, C [G, N]
    dt_h         = softplus(dt_h + dt_bias_h);  a_h = -exp(A_log_h)
    S_t[h]       = exp(dt_h a_h) S_{t-1}[h] + dt_h x_t[h] outer B_t[g(h)]
    y_t[h]       = S_t[h] . C_t[g(h)] + D_h x_t[h]
    out          = W_out (w * RMSNorm_by_group(y * silu(z)))

(the gate BEFORE the norm, the norm's mean over a group's D / G channels).
The state is [N, D] float32 (channel d of head d // P), the tail the last
K - 1 rows of xBC. Uncached and single-sequence calls run the block form
(`ops/ssm.py::block_scan`); in the tick the lanes go through
`ops/ssm.py::ssm_scan_heads` and the chunk's rows through `block_scan`
entering from the slot's stored state.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import flax
import jax
import jax.numpy as jnp
from flax import linen as nn

from luminaai_tpu.config import Config
from luminaai_tpu.models.kda import _dt_bias_init, causal_conv
from luminaai_tpu.models.layers import default_init
from luminaai_tpu.ops import ssm as ssm_ops

Dtype = Any
_F32 = jnp.float32


@flax.struct.dataclass
class LaneState:
    """What a lane keeps of a state-space layer, slots leading:
    state [slots, N, D] float32, tail [slots, K - 1, D] in the activation
    type, channels on the lane axis. The pool holds it beside the pages
    of k/v and neither pages nor slices it (inference/kv_pool.py)."""

    state: jax.Array
    tail: jax.Array

    def insert(self, fresh: "LaneState", slot) -> "LaneState":
        """`fresh`'s one lane written at `slot` (the slot axis is third
        from the end, whatever leads it)."""
        def put(p, f):
            starts = [0] * p.ndim
            starts[p.ndim - 3] = slot
            return jax.lax.dynamic_update_slice(p, f, tuple(starts))

        return jax.tree.map(put, self, fresh)

    def nbytes(self) -> int:
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(self))


def is_lane_state(x) -> bool:
    return isinstance(x, LaneState)


def _a_log_init(key, shape, dtype=_F32):
    """A_log[n, :] = log(n + 1): the family's S4D-real initialiser."""
    n = jnp.arange(1, shape[0] + 1, dtype=dtype)
    return jnp.broadcast_to(jnp.log(n)[:, None], shape)


def _dt_proj_init(key, shape, dtype=_F32):
    """U(-R^-1/2, R^-1/2), as the family's public code has it."""
    lim = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def _rms(x, scale, eps):
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _sequence_conv(x_raw, tail, conv, live):
    """One sequence a batch row entering from its stored tail: the
    pre-activation rows [B, T, D] float32 and the new tail, the last
    K - 1 live rows (padding trails the live rows)."""
    B, T = x_raw.shape[:2]
    K = conv.shape[0]
    seq = jnp.concatenate([tail, x_raw], axis=1)
    pre = causal_conv(seq.astype(_F32), conv)[:, K - 1:]
    n_live = T if live is None else live.sum(axis=1)
    new_tail = jax.vmap(
        lambda s, n: jax.lax.dynamic_slice_in_dim(s, n, K - 1, 0)
    )(seq, jnp.broadcast_to(n_live, (B,)))
    return pre, new_tail


def _tick_conv(x_raw, tail, conv, live, meta):
    """The tick's convolution input, one token a row: a lane's row
    follows its lane's stored tail, the chunk's rows follow each other
    and, before them, the chunk slot's tail (zeros where the chunk is
    the prompt's first). Returns (pre-activation rows [R, D] float32,
    the new tails [slots, K - 1, D]): a stepped lane's tail moves on
    by its row, the chunk's slot keeps the last K - 1 live rows, every
    other slot what it had."""
    K = conv.shape[0]
    n = getattr(meta, "chunk_rows", 0)
    S = x_raw.shape[0] - n
    own = tail[:S]
    window = jnp.concatenate([own, x_raw[:S, None]], axis=1)   # [S,K,D]
    pre = jnp.einsum("skd,kd->sd", window.astype(_F32), conv)
    moved = jnp.where(live[:S, None, None], window[:, 1:], own)
    new_tail = tail.at[:S].set(moved) if S < tail.shape[0] else moved
    if n:
        before = jnp.where(
            meta.chunk_start == 0, 0, jax.lax.dynamic_index_in_dim(
                tail, meta.chunk_slot, 0, keepdims=False)
        ).astype(tail.dtype)
        seq = jnp.concatenate([before, x_raw[S:]], axis=0)   # [K-1+n,D]
        pre_c = causal_conv(seq[None].astype(_F32), conv)[0, K - 1:]
        pre = jnp.concatenate([pre, pre_c], axis=0)
        n_live = live[S:].sum()
        kept = jax.lax.dynamic_slice_in_dim(seq, n_live, K - 1, 0)
        # A chunk with no live row (no prompt pending) writes nothing.
        slot = jnp.where(n_live > 0, meta.chunk_slot, tail.shape[0])
        new_tail = new_tail.at[slot].set(kept, mode="drop")
    return pre, new_tail


class SelectiveSSM(nn.Module):
    config: Config
    dtype: Dtype = jnp.bfloat16

    @staticmethod
    def init_cache(cfg: Config, batch_size: int, dtype, lead=()) -> LaneState:
        D, N = cfg.ssm_inner(), cfg.ssm_state_size
        return LaneState(
            state=jnp.zeros((*lead, batch_size, N, D), _F32),
            tail=jnp.zeros(
                (*lead, batch_size, cfg.ssm_conv_size - 1, D), dtype),
        )

    @nn.compact
    def __call__(
        self,
        u: jax.Array,
        *,
        positions: Optional[jax.Array] = None,
        cache: Optional[LaneState] = None,
        cache_index: Optional[jax.Array] = None,
        lane_meta: Optional[Any] = None,
    ) -> Tuple[jax.Array, Optional[LaneState]]:
        cfg = self.config
        B, T, H = u.shape
        D, N, R, K = (cfg.ssm_inner(), cfg.ssm_state_size, cfg.ssm_rank(),
                      cfg.ssm_conv_size)
        eps = cfg.rms_norm_eps

        def mat(name, shape, axes, init=None):
            return self.param(
                name, nn.with_logical_partitioning(
                    init or default_init(cfg.init_std), axes), shape, _F32)

        w_in = mat("w_in", (H, 2 * D), ("embed", "mlp_fused"))
        conv = mat("conv", (K, D), (None, "mlp"))
        conv_bias = mat("conv_bias", (D,), ("mlp",), nn.initializers.zeros)
        w_x = mat("w_x", (D, R + 2 * N), ("mlp", None))
        dt_norm = mat("dt_norm", (R,), (None,), nn.initializers.ones)
        b_norm = mat("b_norm", (N,), (None,), nn.initializers.ones)
        c_norm = mat("c_norm", (N,), (None,), nn.initializers.ones)
        w_dt = mat("w_dt", (R, D), (None, "mlp"), _dt_proj_init)
        dt_bias = mat("dt_bias", (D,), ("mlp",), _dt_bias_init)
        a_log = mat("A_log", (N, D), (None, "mlp"), _a_log_init)
        d_skip = mat("D", (D,), ("mlp",), nn.initializers.ones)
        w_out = mat("w_out", (D, H), ("mlp", "embed"),
                    default_init(cfg.init_std / math.sqrt(2.0)))

        u = u.astype(self.dtype)
        xz = jnp.einsum("bth,hf->btf", u, w_in.astype(self.dtype))
        x_raw, z = xz[..., :D], xz[..., D:]
        tick = cache is not None and getattr(cache_index, "ndim", 0) == 1 \
            and T == 1
        live = None if positions is None else positions >= 0      # [B, T]

        # -- the convolution, and the tail a cache keeps of it -----------
        new_tail = None
        if cache is None:
            pre = causal_conv(x_raw.astype(_F32), conv)
        elif tick:
            pre, new_tail = _tick_conv(
                x_raw[:, 0], cache.tail, conv, live[:, 0], lane_meta)
            pre = pre[:, None]
        else:
            pre, new_tail = _sequence_conv(x_raw, cache.tail, conv, live)
        x = jax.nn.silu(pre + conv_bias).astype(self.dtype)

        dbc = jnp.einsum("btd,df->btf", x, w_x.astype(self.dtype))
        low = _rms(dbc[..., :R], dt_norm, eps).astype(self.dtype)
        b_t = _rms(dbc[..., R:R + N], b_norm, eps)
        c_t = _rms(dbc[..., R + N:], c_norm, eps)
        dt = jax.nn.softplus(
            jnp.einsum("btr,rd->btd", low, w_dt.astype(self.dtype))
            .astype(_F32) + dt_bias)
        a = -jnp.exp(a_log.astype(_F32))

        new_cache = None
        if self.is_initializing():
            # init traces a one-row dummy; only the shapes survive it.
            y = jnp.zeros((B, T, D), self.dtype)
        elif tick:
            n_chunk = getattr(lane_meta, "chunk_rows", 0)
            with jax.named_scope("ssm"):
                y, state = ssm_ops.ssm_scan(
                    cache.state, x[:, 0], z[:, 0], dt[:, 0], b_t[:, 0],
                    c_t[:, 0], a, d_skip, positions[:, 0],
                    lanes=B - n_chunk,
                    chunk_slot=lane_meta.chunk_slot if n_chunk else 0,
                    chunk_start=lane_meta.chunk_start if n_chunk else 0,
                )
            y = y[:, None]
            new_cache = LaneState(state=state, tail=new_tail)
        else:
            if live is not None:
                # dt = 0 on a padding row: the state passes it unchanged.
                dt = jnp.where(live[..., None], dt, 0.0)
            with jax.named_scope("ssm"):
                y, state = ssm_ops.selective_scan(
                    x, dt, a, b_t, c_t,
                    h0=None if cache is None else cache.state)
            y = (y + d_skip * x.astype(_F32)) * jax.nn.silu(z.astype(_F32))
            y = y.astype(self.dtype)
            if cache is not None:
                new_cache = LaneState(
                    state=state, tail=new_tail.astype(cache.tail.dtype))
        out = jnp.einsum("btd,dh->bth", y, w_out.astype(self.dtype))
        return out, new_cache



def _log_uniform_a(key, shape, dtype=_F32):
    """A_log = log U(1, 16) a head: the family's initialiser."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _step_bias_init(lo: float, hi: float, floor: float):
    """The inverse softplus of a log-uniform step in [lo, hi], floored."""
    def init(key, shape, dtype=_F32):
        dt = jnp.exp(jax.random.uniform(
            key, shape, dtype, math.log(lo), math.log(hi)))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


def gated_group_norm(y, z, scale, groups: int, eps: float):
    """scale * RMSNorm_by_group(y * silu(z)), float32: the gate first, the
    mean over each of `groups` runs of channels."""
    g = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
    parts = g.reshape(*g.shape[:-1], groups, g.shape[-1] // groups)
    parts = parts * jax.lax.rsqrt(
        jnp.mean(jnp.square(parts), axis=-1, keepdims=True) + eps)
    return parts.reshape(g.shape) * scale


class ScalarDecaySSM(nn.Module):
    config: Config
    dtype: Dtype = jnp.bfloat16

    @staticmethod
    def init_cache(cfg: Config, batch_size: int, dtype, lead=()) -> LaneState:
        return LaneState(
            state=jnp.zeros(
                (*lead, batch_size, cfg.ssm_state_size, cfg.ssm2_inner()),
                _F32),
            tail=jnp.zeros(
                (*lead, batch_size, cfg.ssm_conv_size - 1,
                 cfg.ssm2_conv_width()), dtype),
        )

    @nn.compact
    def __call__(
        self,
        u: jax.Array,
        *,
        positions: Optional[jax.Array] = None,
        cache: Optional[LaneState] = None,
        cache_index: Optional[jax.Array] = None,
        lane_meta: Optional[Any] = None,
    ) -> Tuple[jax.Array, Optional[LaneState]]:
        cfg = self.config
        B, T, H = u.shape
        nh, P, G = cfg.ssm2_num_heads, cfg.ssm2_head_dim, cfg.ssm2_groups
        D, N, K = cfg.ssm2_inner(), cfg.ssm_state_size, cfg.ssm_conv_size
        W = cfg.ssm2_conv_width()

        def mat(name, shape, axes, init=None):
            return self.param(
                name, nn.with_logical_partitioning(
                    init or default_init(cfg.init_std), axes), shape, _F32)

        w_in = mat("w_in", (H, D + W + nh), ("embed", "mlp_fused"))
        conv = mat("conv", (K, W), (None, "mlp"))
        conv_bias = mat("conv_bias", (W,), ("mlp",), nn.initializers.zeros)
        dt_bias = mat("dt_bias", (nh,), (None,), _step_bias_init(
            cfg.ssm2_dt_min, cfg.ssm2_dt_max, cfg.ssm2_dt_floor))
        a_log = mat("A_log", (nh,), (None,), _log_uniform_a)
        d_skip = mat("D", (nh,), (None,), nn.initializers.ones)
        norm = mat("norm", (D,), ("mlp",), nn.initializers.ones)
        w_out = mat("w_out", (D, H), ("mlp", "embed"),
                    default_init(cfg.init_std / math.sqrt(2.0)))

        u = u.astype(self.dtype)
        zxd = jnp.einsum("bth,hf->btf", u, w_in.astype(self.dtype))
        z, raw, dt = zxd[..., :D], zxd[..., D:D + W], zxd[..., D + W:]
        tick = cache is not None and getattr(cache_index, "ndim", 0) == 1 \
            and T == 1
        live = None if positions is None else positions >= 0      # [B, T]

        new_tail = None
        if cache is None:
            pre = causal_conv(raw.astype(_F32), conv)
        elif tick:
            pre, new_tail = _tick_conv(
                raw[:, 0], cache.tail, conv, live[:, 0], lane_meta)
            pre = pre[:, None]
        else:
            pre, new_tail = _sequence_conv(raw, cache.tail, conv, live)
        xbc = jax.nn.silu(pre + conv_bias).astype(self.dtype)
        x = xbc[..., :D].reshape(B, T, nh, P)
        b_t = xbc[..., D:D + G * N].reshape(B, T, G, N)
        c_t = xbc[..., D + G * N:].reshape(B, T, G, N)
        dt = jax.nn.softplus(dt.astype(_F32) + dt_bias)           # [B,T,nh]
        if live is not None:
            # dt = 0 on a row that is no token: the state passes it as it is.
            dt = jnp.where(live[..., None], dt, 0.0)
        a = -jnp.exp(a_log.astype(_F32))

        new_cache = None
        if self.is_initializing():
            # init traces a one-row dummy; only the shapes survive it.
            y = jnp.zeros((B, T, nh, P), _F32)
        elif tick:
            with jax.named_scope("ssm"):
                y, state = self._tick_scan(
                    cache.state, x[:, 0], dt[:, 0], a, b_t[:, 0], c_t[:, 0],
                    positions[:, 0], lane_meta)
            y = y[:, None]
            new_cache = LaneState(state=state, tail=new_tail)
        else:
            with jax.named_scope("ssm"):
                y, state = ssm_ops.block_scan(
                    x, dt, a, b_t, c_t,
                    h0=None if cache is None else cache.state,
                    chunk=cfg.ssm2_chunk)
            if cache is not None:
                new_cache = LaneState(
                    state=state, tail=new_tail.astype(cache.tail.dtype))
        y = (y + d_skip[:, None] * x.astype(_F32)).reshape(B, T, D)
        y = gated_group_norm(y, z, norm, G, cfg.rms_norm_eps)
        out = jnp.einsum("btd,dh->bth", y.astype(self.dtype),
                         w_out.astype(self.dtype))
        return out, new_cache

    def _tick_scan(self, state, x, dt, a, b, c, pos, meta):
        """One tick: rows [R, ...], the first R - chunk_rows step their
        lanes (the kernel, over the pool in place), the rest are one chunk
        of slot `chunk_slot` in block form, entering from that slot's
        stored state (zero where the chunk is the prompt's first) and
        written back to it; a chunk with no live row writes nothing."""
        cfg = self.config
        n = getattr(meta, "chunk_rows", 0)
        S = x.shape[0] - n
        P = cfg.ssm2_head_dim
        if n:
            # Read before the kernel rewrites the pool in place (the
            # chunk's slot is no stepped lane).
            before = jnp.where(
                meta.chunk_start == 0, 0.0, jax.lax.dynamic_index_in_dim(
                    state, meta.chunk_slot, 0, keepdims=False))
        wide = lambda t: jnp.repeat(t, P, axis=-1)      # noqa: E731
        y, state = ssm_ops.ssm_scan_heads(
            state, wide(jnp.exp(dt[:S] * a)),
            wide(dt[:S]) * x[:S].reshape(S, -1).astype(_F32),
            b[:S], c[:S], pos[:S])
        y = y.reshape(S, *x.shape[1:])
        if n:
            # Most ticks carry no chunk: the block form's products are
            # then skipped on the device, not run over rows at -1.
            riding = (pos[S:] >= 0).any()
            y_c, after = jax.lax.cond(
                riding,
                lambda h0: ssm_ops.block_scan(
                    x[None, S:], dt[None, S:], a, b[None, S:], c[None, S:],
                    h0=h0, chunk=cfg.ssm2_chunk),
                lambda h0: (jnp.zeros((1, n, *x.shape[1:]), _F32), h0),
                before[None])
            y = jnp.concatenate([y, y_c[0]], axis=0)
            slot = jnp.where(riding, meta.chunk_slot, state.shape[0])
            state = state.at[slot].set(after[0], mode="drop")
        return y, state
