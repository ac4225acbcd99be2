"""Kimi Delta Attention: the gated delta rule as a token mixer.

A linear-attention layer: every head carries a [head_dim x head_dim] state
that each token decays channel by channel, corrects towards its value along
its key (the delta rule) and reads with its query. Position lives in the
recurrence, so nothing is rotated. The recurrence itself is ops/kda.py
(chunked Pallas kernels, forward and backward); this module is what stands
around it, per token x in R^hidden:

    q, k, v  = silu(conv4(W_q x)), silu(conv4(W_k x)), silu(conv4(W_v x))
               (causal depthwise convolutions over time, one per projection)
    q, k     = q / |q| / sqrt(d), k / |k|      per head (eps 1e-6)
    g        = -exp(A_log) * softplus(W_a2 W_a1 x + dt_bias)   per channel
    beta     = sigmoid(w_beta x)                               per head
    o        = delta_rule(q, k, v, g, beta)
    y        = W_o [rmsnorm_d(o; w) * sigmoid(W_g2 W_g1 x)]

The first two lines (convolution, SiLU, l2 norm and q's scale, from the
q/k/v projection's output to q, k, v in the activations' dtype) are ONE
kernel forward and one backward wherever the recurrence's kernels run:
ops/kda.py::qkv_prepare (float32 in VMEM alone; in XLA they were float32
passes over [B, T, 3 * heads * d] forward, again under remat, and
backward). `qkv_plain` below is the same arithmetic in plain jax: the form
the tests hold the kernel to, init's path, and the path of a head size the
kernels do not tile. q, k, v, g and o stay [B, T, heads * d] from the
projections to the output norm, the layout the kernels read. The decay,
beta's fold into k and v (ops/kda.py::kda_flat) and the output norm x gate
are still XLA's.

Training only: serving needs the state a lane, which inference/ does not
carry yet (GenerationEngine refuses a Config with this mixer).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from luminaai_tpu.config import Config
from luminaai_tpu.models.layers import default_init
from luminaai_tpu.ops import kda as kda_ops

Dtype = Any


def _a_log_init(key, shape, dtype=jnp.float32):
    """A_log = log U(1, 16) a head, as the family's public code has it."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of dt ~ logU(1e-3, 1e-1)."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal convolution over time. x [B, T, D], w [K, D] with
    w[K-1] the tap on the token itself: y_t = sum_i w_i * x_{t-(K-1)+i}."""
    K = w.shape[0]
    T = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, i:i + T] * w[i] for i in range(K))


def qkv_plain(qkv: jax.Array, conv: jax.Array, head_dim: int):
    """q, k, v [B, T, D] in qkv's dtype from the q/k/v projection's output
    qkv [B, T, 3*D] and the three convolutions' taps conv [K, 3*D], in
    plain jax: what ops/kda.py::qkv_prepare computes in one kernel, the
    form its tests hold it to, and the path for head sizes the kernels do
    not tile."""
    B, T, D3 = qkv.shape
    y = jax.nn.silu(causal_conv(qkv.astype(jnp.float32), conv))
    q, k, v = (t.reshape(B, T, -1, head_dim)
               for t in jnp.split(y, 3, axis=-1))

    def unit(t):
        return t * jax.lax.rsqrt(
            jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)

    return tuple(t.astype(qkv.dtype).reshape(B, T, D3 // 3)
                 for t in (unit(q) * head_dim ** -0.5, unit(k), v))


class KimiDeltaAttention(nn.Module):
    config: Config
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.config
        B, T, H = x.shape
        n = cfg.kda_num_heads or cfg.num_heads
        d = cfg.kda_head_dim
        r = d  # the decay's and the output gate's low rank
        D = n * d
        f32 = jnp.float32

        def mat(name, shape, axes, std=cfg.init_std):
            return self.param(
                name, nn.with_logical_partitioning(default_init(std), axes),
                shape, f32)

        wqkv = jnp.concatenate(
            [mat(f"w{c}", (H, D), ("embed", "heads")) for c in "qkv"], axis=1)
        conv = jnp.concatenate(
            [mat(f"conv_{c}", (cfg.kda_conv_size, D), (None, "heads"))
             for c in "qkv"], axis=1)
        w_a1 = mat("w_a1", (H, r), ("embed", None))
        w_a2 = mat("w_a2", (r, D), (None, "heads"))
        w_g1 = mat("w_g1", (H, r), ("embed", None))
        w_g2 = mat("w_g2", (r, D), (None, "heads"))
        w_beta = mat("w_beta", (H, n), ("embed", "heads"))
        a_log = self.param(
            "A_log", nn.with_logical_partitioning(_a_log_init, ("heads",)),
            (n,), f32)
        dt_bias = self.param(
            "dt_bias", nn.with_logical_partitioning(_dt_bias_init, ("heads",)),
            (D,), f32)
        o_norm = self.param(
            "o_norm", nn.with_logical_partitioning(
                nn.initializers.ones, ("head_dim",)), (d,), f32)
        wo = mat("wo", (D, H), ("heads", "embed"),
                 cfg.init_std / math.sqrt(2.0))

        x = x.astype(self.dtype)
        qkv = jnp.einsum("bth,hf->btf", x, wqkv.astype(self.dtype))
        # init traces a one-row dummy; only the shapes survive it.
        if self.is_initializing() or not kda_ops.qkv_prepare_eligible(
                d, cfg.kda_conv_size):
            q, k, v = qkv_plain(qkv, conv, d)
        else:
            q, k, v = kda_ops.qkv_prepare(qkv, conv, heads=n, head_dim=d)

        low = jnp.einsum("bth,hr->btr", x, jnp.concatenate(
            [w_a1, w_g1], axis=1).astype(self.dtype))
        a = jnp.einsum("btr,rf->btf", low[..., :r], w_a2.astype(self.dtype))
        g = -jnp.repeat(jnp.exp(a_log), d) * jax.nn.softplus(
            a.astype(f32) + dt_bias)
        beta = jax.nn.sigmoid(jnp.einsum(
            "bth,hn->btn", x, w_beta.astype(self.dtype)).astype(f32))

        if self.is_initializing():
            o = jnp.zeros((B, T, D), self.dtype)
        else:
            with jax.named_scope("kda"):
                o = kda_ops.kda_flat(q, k, v, g, beta)

        o32 = o.reshape(B, T, n, d).astype(f32)
        o32 = o32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(o32), axis=-1, keepdims=True)
            + cfg.rms_norm_eps) * o_norm
        gate = jax.nn.sigmoid(jnp.einsum(
            "btr,rf->btf", low[..., r:], w_g2.astype(self.dtype)).astype(f32))
        y = (o32.reshape(B, T, D) * gate).astype(self.dtype)
        out = jnp.einsum("btf,fh->bth", y, wo.astype(self.dtype))
        stats = {"kda_decay_min": jax.lax.stop_gradient(
            kda_ops.chunk_decay_min(g))}
        return out, stats
