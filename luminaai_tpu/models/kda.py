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

XLA keeps the matmuls (W_q, W_k, W_v, W_a, W_g, w_beta, W_o); everything
between them is a kernel wherever the recurrence's kernels run, each pair
(forward, backward) reading [B, T, heads * d] once in the layout the
projections write, float32 in VMEM alone, every result rounded once:

  ops/kda.py::qkv_prepare   the first two lines: convolution, SiLU, l2
                            norm and q's scale (in XLA: float32 passes
                            over [B, T, 3 * heads * d]).
  ops/kda.py::gated_kda     the decay g (float32) and beta, beta's fold
                            into k and v, then the recurrence; ONE
                            backward, so that k's cotangent is written
                            once (in XLA: a per-head number reduced and
                            broadcast in a head-a-row tiling, re-tiled
                            both ways).
  ops/kda.py::mixer_out     the output norm x gate in front of W_o.

`qkv_plain`, `gates_plain` and `out_plain` below are the same arithmetic
in plain jax: the forms the tests hold the kernels to, init's path, and
the path of a head size the kernels do not tile.

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


def gates_plain(a: jax.Array, beta_logits: jax.Array, dt_bias: jax.Array,
                a_log: jax.Array):
    """(g [B, T, D] float32, beta [B, T, heads] float32) from the decay
    projection's output a [B, T, D], beta's logits [B, T, heads],
    dt_bias [D] and A_log [heads], in plain jax: with
    ops/kda.py::fold_beta what `mixer_gates_fwd` computes."""
    d = a.shape[-1] // a_log.shape[0]
    g = -jnp.repeat(jnp.exp(a_log), d) * jax.nn.softplus(
        a.astype(jnp.float32) + dt_bias)
    return g, jax.nn.sigmoid(beta_logits.astype(jnp.float32))


def out_plain(o: jax.Array, gate_logits: jax.Array, o_norm: jax.Array,
              eps: float) -> jax.Array:
    """rmsnorm_d(o; o_norm) * sigmoid(gate_logits) in o's dtype, o and the
    logits [B, T, heads * d], o_norm [d], in plain jax: what
    ops/kda.py::mixer_out computes in one kernel."""
    B, T, D = o.shape
    o32 = o.reshape(B, T, -1, o_norm.shape[0]).astype(jnp.float32)
    o32 = o32 * jax.lax.rsqrt(
        jnp.mean(jnp.square(o32), axis=-1, keepdims=True) + eps) * o_norm
    gate = jax.nn.sigmoid(gate_logits.astype(jnp.float32))
    return (o32.reshape(B, T, D) * gate).astype(o.dtype)


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
        init = self.is_initializing()
        kernels = not init and kda_ops.kda_eligible(d, d)
        if kernels and kda_ops.qkv_prepare_eligible(d, cfg.kda_conv_size):
            q, k, v = kda_ops.qkv_prepare(qkv, conv, heads=n, head_dim=d)
        else:
            q, k, v = qkv_plain(qkv, conv, d)

        low = jnp.einsum("bth,hr->btr", x, jnp.concatenate(
            [w_a1, w_g1], axis=1).astype(self.dtype))
        a = jnp.einsum("btr,rf->btf", low[..., :r], w_a2.astype(self.dtype))
        beta_logits = jnp.einsum("bth,hn->btn", x, w_beta.astype(self.dtype))
        gate_logits = jnp.einsum(
            "btr,rf->btf", low[..., r:], w_g2.astype(self.dtype))

        if kernels:
            with jax.named_scope("kda"):
                o, g = kda_ops.gated_kda(
                    q, k, v, a, beta_logits, dt_bias, a_log)
            y = kda_ops.mixer_out(
                o, gate_logits, o_norm, eps=cfg.rms_norm_eps)
        else:
            g, beta = gates_plain(a, beta_logits, dt_bias, a_log)
            if init:
                o = jnp.zeros((B, T, D), self.dtype)
            else:
                with jax.named_scope("kda"):
                    o = kda_ops.kda_flat(q, k, v, g, beta)
            y = out_plain(o, gate_logits, o_norm, cfg.rms_norm_eps)
        out = jnp.einsum("btf,fh->bth", y, wo.astype(self.dtype))
        stats = {"kda_decay_min": jax.lax.stop_gradient(
            kda_ops.chunk_decay_min(g))}
        return out, stats
