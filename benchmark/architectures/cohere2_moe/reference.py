"""The plain reference of the `cohere2_moe` architecture: the forward pass
a configuration file states, in `jax.numpy`, float32,
`default_matmul_precision("highest")`; no kernel, no cache, no batching
tricks. It imports nothing of the program, nothing of the harness and
nothing of another architecture (`manifest.check` reads this file's
imports): a later edit of another reference cannot move this one's
`correct`.

What it computes for layer l of kind `layer_types[l]`, input x [S, H]:

    h       = LN(x) = (x - mean) / sqrt(var + eps) * gamma     (no bias;
              ONE norm a layer: the parallel block)
    q       = h W_q  (n_q heads of d);  k = h W_k, v = h W_v  (n_kv heads)
    sliding_attention: q, k rotated over all d dims as INTERLEAVED pairs
              (x[2i], x[2i+1]), theta; key j seen by query i iff
              0 <= i - j < window
    full_attention:    no rotation; j <= i
    attn    = softmax(q k^T / sqrt(d)) v W_o,  n_q / n_kv query heads a
              k/v head
    s       = sigmoid(h W_r)  over all published experts;  T = the top_k
              largest;  w_e = s_e / sum_T s
    routed  = sum over e in T AND held here of  w_e E_e(h),
              E(h) = W_down (silu(W_gate h) * W_up h)
    shared  = (1 / n_shared) sum_j S_j(h)                  ('average')
    x'      = x + attn + routed + shared
    logits  = logit_scale * LN_f(x_L) E^T,  x_0 = E[ids]   (tied)

The share: `wi` / `wo_e` hold experts [held_offset, held_offset + count)
of `num_experts`; the weights w come from the full top-k, and what the
other experts would have added is left out (one chip of an expert-parallel
group, without its exchange). `shared` is whole on every chip.

`q_block` computes the attention of that many queries at a time, so that a
prompt of thousands of tokens fits: the same sums in the same order, a
block of rows at a time.

Weights come as a neutral view (adapter.params_view beside this file):
    {"embedding": [V,H], "final_norm": [H],
     "layers": [{"norm": [H], "wq": [H,nq,d], "wk": [H,nkv,d],
                 "wv": [H,nkv,d], "wo": [nq,d,H], "router": [H,E],
                 "wi": [count,H,2F] (gate | up halves), "wo_e": [count,F,H],
                 "shared_wi": [H, 2 n F] (gate | up halves, expert j's
                 columns j F .. (j+1) F of each), "shared_wo": [n F, H]}]}
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _layernorm(x, scale, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope_interleaved(x, theta):
    """x [B,S,h,d] at positions 0..S-1; pair i is (x[2i], x[2i+1])."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).reshape(
        x.shape)


def _attention(h, lw, kind, theta, window, q_block):
    q = jnp.einsum("bsh,hnd->bsnd", h, lw["wq"].astype(F32))
    k = jnp.einsum("bsh,hnd->bsnd", h, lw["wk"].astype(F32))
    v = jnp.einsum("bsh,hnd->bsnd", h, lw["wv"].astype(F32))
    if kind == "sliding_attention":
        q, k = _rope_interleaved(q, theta), _rope_interleaved(k, theta)
    elif kind != "full_attention":
        raise ValueError(f"unknown layer type {kind!r}")
    B, S, n_q, d = q.shape
    n_kv = k.shape[2]
    qg = q.reshape(B, S, n_kv, n_q // n_kv, d)
    kpos = jnp.arange(S)[None, :]
    outs = []
    step = q_block or S
    for lo in range(0, S, step):
        qpos = jnp.arange(lo, min(lo + step, S))[:, None]
        seen = kpos <= qpos
        if kind == "sliding_attention":
            seen = jnp.logical_and(seen, qpos - kpos < window)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg[:, lo:lo + step], k)
        s = jnp.where(seen[None, None, None], s / jnp.sqrt(F32(d)), -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("bhgqk,bkhd->bqhgd", p, v))
    o = jnp.concatenate(outs, axis=1).reshape(B, S, n_q, d)
    return jnp.einsum("bqnd,ndh->bqh", o, lw["wo"].astype(F32))


def _swiglu(t, gate_w, up_w, down_w):
    act = jax.nn.silu(t @ gate_w.astype(F32)) * (t @ up_w.astype(F32))
    return act @ down_w.astype(F32)


def _expert_layer(h, lw, *, top_k, held_offset, n_shared):
    B, S, H = h.shape
    t = h.reshape(B * S, H)
    s = jax.nn.sigmoid(t @ lw["router"].astype(F32))            # [N, E]
    vals, idx = jax.lax.top_k(s, top_k)
    vals = vals / vals.sum(-1, keepdims=True)
    weight = jnp.zeros_like(s).at[
        jnp.arange(t.shape[0])[:, None], idx].set(vals)
    count = lw["wi"].shape[0]
    held = weight[:, held_offset:held_offset + count]           # [N, count]

    def one_expert(carry, ew):                # one expert upcast at a time
        wi, wo, w_e = ew
        F = wo.shape[0]
        return carry + _swiglu(t, wi[:, :F], wi[:, F:], wo) * w_e[:, None], None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(t),
                             (lw["wi"], lw["wo_e"], held.T))
    nF = lw["shared_wo"].shape[0]
    F = nF // n_shared
    shared = jnp.zeros_like(t)
    for j in range(n_shared):
        cols = slice(j * F, (j + 1) * F)
        shared = shared + _swiglu(
            t, lw["shared_wi"][:, :nF][:, cols],
            lw["shared_wi"][:, nF:][:, cols], lw["shared_wo"][cols])
    return (routed + shared / n_shared).reshape(B, S, H)


def forward(view: Dict[str, Any], ids: jax.Array, *, eps: float,
            theta: float, layer_types: Sequence[str], window: int,
            top_k: int, held_offset: int, num_experts: int, n_shared: int,
            logit_scale: float = 1.0,
            q_block: Optional[int] = None) -> jax.Array:
    """Logits [B,S,V] in float32 for token ids [B,S]."""
    assert len(view["layers"]) == len(layer_types)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(view["embedding"], ids, axis=0).astype(F32)
        for lw, kind in zip(view["layers"], layer_types):
            assert lw["router"].shape[-1] == num_experts
            h = _layernorm(x, lw["norm"], eps)
            x = (x + _attention(h, lw, kind, theta, window, q_block)
                 + _expert_layer(h, lw, top_k=top_k, held_offset=held_offset,
                                 n_shared=n_shared))
        x = _layernorm(x, view["final_norm"], eps)
        return logit_scale * jnp.einsum(
            "bsh,vh->bsv", x, view["embedding"].astype(F32))


def from_config_file(body: Dict[str, Any]) -> Dict[str, Any]:
    """forward()'s keyword arguments for a configuration file's body: the
    kinds of its first `num_hidden_layers` layers, the held range and the
    published expert count."""
    return {
        "eps": float(body["layer_norm_eps"]),
        "theta": float(body["rope_theta"]),
        "layer_types": tuple(
            body["layer_types"][:body["num_hidden_layers"]]),
        "window": int(body["sliding_window"]),
        "top_k": int(body["num_experts_per_tok"]),
        "held_offset": int(
            body.get("deployment", {}).get("experts_held_offset", 0)),
        "num_experts": int(
            body.get("source_values", {}).get("num_experts",
                                              body["num_experts"])),
        "n_shared": int(body["num_shared_experts"]),
        "logit_scale": float(body["logit_scale"]),
    }
