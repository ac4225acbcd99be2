"""The plain reference of the `jamba` architecture (AI21 Jamba: Mamba-1
selective state-space layers with three inner RMSNorms, a causal attention
layer WITHOUT positional encoding once a period, a dense SwiGLU on every
layer), in `jax.numpy`, float32, `default_matmul_precision("highest")`:
no kernel, no chunk, no cache. It imports nothing of the program, nothing
of the harness and nothing of another architecture (`manifest.check`
reads this file's imports).

Pre-norm block: h = x + Mixer_l(rmsnorm(x)); y = h + MLP(rmsnorm(h));
MLP(u) = W_down(silu(W_gate u) * W_up u); final rmsnorm; logits over the
TIED embedding; no embedding scale. No bias but the convolution's and dt's.

Layer l is attention where l % period == offset, else Mamba.

Mamba mixer, inner width D = expand * H, state N, on u_t in R^H:
    [x~, z] = W_in u                               (D each)
    x = silu(conv_K(x~) + b_conv)                  causal, depthwise, the
                                                   last tap on the token
    [dt~, B, C] = W_x x                            (rank R, N, N)
    dt~, B, C each through its own rmsnorm         (Jamba's addition)
    dt = softplus(W_dt dt~ + b_dt)                 (D)
    A = -exp(A_log)                                [D, N]
    h_0 = 0; h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) outer B_t   [D, N]
    y_t = h_t C_t + D_skip * x_t
    out = W_out (y * silu(z))
The state is walked TOKEN BY TOKEN (`lax.scan` over t).

Attention mixer: n_q heads of d over n_kv key/value heads, NO positional
encoding (nothing is rotated), causal softmax(q . k / sqrt(d)) v, no bias.

Weights come as a neutral view (adapter.params_view beside this file):
    {"embedding": [V,H], "final_norm": [H],
     "layers": [{"attn_norm": [H], "ffn_norm": [H],
        "mixer": {"w_in": [H,2D], "conv": [K,D], "conv_bias": [D],
                  "w_x": [D,R+2N], "dt_norm": [R], "b_norm": [N],
                  "c_norm": [N], "w_dt": [R,D], "dt_bias": [D],
                  "A_log": [N,D], "D": [D], "w_out": [D,H]}        (ssm)
               | {"wq": [H,nq,d], "wk","wv": [H,nkv,d], "wo": [nq,d,H]}
        "ffn": {"wi": [H,2F], "wo": [F,H]}}]}
(gate | up halves in wi; A_log is laid out state-major, channels last).
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _swiglu(x, wi, wo):
    gate, up = jnp.split(x @ wi.astype(F32), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ wo.astype(F32)


def _conv(x, w, b):
    """Causal depthwise convolution. x [B,T,D], w [K,D], w[K-1] on x_t."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(x) + b.astype(F32)
    for i in range(K):
        out = out + xp[:, i:i + T] * w[i].astype(F32)
    return out


def _mamba(u, mw, eps):
    D = mw["conv"].shape[1]
    N = mw["A_log"].shape[0]
    R = mw["w_dt"].shape[0]
    xz = u @ mw["w_in"].astype(F32)
    x, z = xz[..., :D], xz[..., D:]
    x = jax.nn.silu(_conv(x, mw["conv"], mw["conv_bias"]))
    dbc = x @ mw["w_x"].astype(F32)
    dt = _rmsnorm(dbc[..., :R], mw["dt_norm"], eps)
    Bm = _rmsnorm(dbc[..., R:R + N], mw["b_norm"], eps)
    Cm = _rmsnorm(dbc[..., R + N:], mw["c_norm"], eps)
    dt = jax.nn.softplus(dt @ mw["w_dt"].astype(F32)
                         + mw["dt_bias"].astype(F32))           # [B,T,D]
    A = -jnp.exp(mw["A_log"].astype(F32))                       # [N,D]

    def step(h, row):                                           # h [B,N,D]
        x_t, dt_t, b_t, c_t = row
        h = (jnp.exp(dt_t[:, None, :] * A) * h
             + (dt_t * x_t)[:, None, :] * b_t[:, :, None])
        return h, jnp.einsum("bnd,bn->bd", h, c_t)

    h0 = jnp.zeros((u.shape[0], N, D), F32)
    rows = tuple(jnp.swapaxes(a, 0, 1) for a in (x, dt, Bm, Cm))
    _, y = jax.lax.scan(step, h0, rows)
    y = jnp.swapaxes(y, 0, 1) + mw["D"].astype(F32) * x
    return (y * jax.nn.silu(z)) @ mw["w_out"].astype(F32)


def _attention(u, aw):
    B, T, H = u.shape
    wq, wk, wv, wo = (aw[k].astype(F32) for k in ("wq", "wk", "wv", "wo"))
    n_q, d = wq.shape[1], wq.shape[2]
    n_kv = wk.shape[1]
    q = jnp.einsum("bth,hnd->btnd", u, wq).reshape(B, T, n_kv, n_q // n_kv, d)
    k = jnp.einsum("bth,hnd->btnd", u, wk)
    v = jnp.einsum("bth,hnd->btnd", u, wv)
    s = jnp.einsum("bqngd,bknd->bngqk", q, k) / jnp.sqrt(F32(d))
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bngqk,bknd->bqngd", p, v).reshape(B, T, n_q, d)
    return jnp.einsum("btnd,ndh->bth", o, wo)


def forward(view: Dict[str, Any], ids: jax.Array, *, eps: float,
            layer_kinds: Sequence[str]) -> jax.Array:
    """Logits [B,S,V] in float32 for token ids [B,S]."""
    assert len(view["layers"]) == len(layer_kinds)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(view["embedding"], ids, axis=0).astype(F32)
        for lw, kind in zip(view["layers"], layer_kinds):
            u = _rmsnorm(x, lw["attn_norm"], eps)
            if kind == "ssm":
                x = x + _mamba(u, lw["mixer"], eps)
            else:
                x = x + _attention(u, lw["mixer"])
            x = x + _swiglu(_rmsnorm(x, lw["ffn_norm"], eps),
                            lw["ffn"]["wi"], lw["ffn"]["wo"])
        x = _rmsnorm(x, view["final_norm"], eps)
        return jnp.einsum("bsh,vh->bsv", x, view["embedding"].astype(F32))


def layer_kinds(body: Dict[str, Any]) -> Sequence[str]:
    """'attention' where l % attn_layer_period == attn_layer_offset (the
    family's modelling code; the file's `assumed`), else 'ssm'."""
    period, offset = body["attn_layer_period"], body["attn_layer_offset"]
    return tuple("attention" if i % period == offset else "ssm"
                 for i in range(body["num_hidden_layers"]))


def from_config_file(body: Dict[str, Any]) -> Dict[str, Any]:
    """forward()'s keyword arguments for a configuration file's body."""
    return {"eps": float(body["rms_norm_eps"]),
            "layer_kinds": layer_kinds(body)}
