"""The plain reference of the `mimo_v2` architecture: the forward pass a
configuration file states, in `jax.numpy`, float32,
`default_matmul_precision("highest")`; no kernel, no cache, no batching
tricks. It imports nothing of the program, nothing of the harness and
nothing of another architecture (`manifest.check` reads this file's
imports): a later edit of another reference cannot move this one's
`correct`.

What it computes for layer l of kind t = `hybrid_layer_pattern[l]`
(0 full, 1 window), input x [S, H], h = RMSNorm(x, eps):

    q   = W_q h  (n_q heads of d);  k = W_k h  (n_t heads of d);
    v   = value_scale * W_v h  (n_t heads of d_v);  n_full =
          num_key_value_heads, n_window = swa_num_key_value_heads
    the FIRST r = int(d * partial_rotary_factor) columns of every q and k
          head are rotated as split halves (x[i], x[i + r/2]) at
          theta_full = rope_theta, theta_window = swa_rope_theta,
          f_i = theta^(-2i / r); the other d - r carry no position
    s_ij = d^-1/2 q_i . k_j   for 0 <= i - j (full), 0 <= i - j < window
          (window: the query's own position counts)
    p_ij = exp(s_ij) / (sum_j' exp(s_ij') + [t has a sink] exp(b_head)),
          b a float32 logit a query head: the sink takes probability and
          gives no value (one more softmax column, dropped after it)
    o_i  = sum_j p_ij v_j;   x <- x + W_o o
    F    = RMSNorm(x); layer l < dense_layers: x <- x + SwiGLU(F);
          else sigma = sigmoid(W_r F) over all published experts, T = the
          top_k largest of sigma + selection_bias, w_e = sigma_e / sum_T
          sigma (x routed_scale), x <- x + sum over e in T AND held here
          of w_e E_e(F), E(F) = W_down (silu(W_gate F) * W_up F); no
          shared expert
    logits = RMSNorm_f(x_L) W_head^T,  x_0 = E[ids]   (untied)

The share: `wi` / `wo` of an expert layer hold experts [held_offset,
held_offset + count) of `num_experts`; the weights w come from the full
top-k, and what the other experts would have added is left out (one chip
of an expert-parallel group, without its exchange).

`q_block` computes the attention of that many queries at a time, so that a
prompt of thousands of tokens fits at the published widths: the same sums
in the same order, a block of rows at a time.

Weights come as a neutral view (adapter.params_view beside this file):
    {"embedding": [V,H], "lm_head": [V,H], "final_norm": [H],
     "layers": [{"attn_norm": [H], "ffn_norm": [H],
                 "mixer": {"wq": [H,nq,d], "wk": [H,n_t,d],
                           "wv": [H,n_t,dv], "wo": [nq,dv,H],
                           "sink": [nq] (window layers alone)},
                 "ffn": {"wi": [H,2F], "wo": [F,H]}  (dense) or
                        {"router": [H,E], "selection_bias": [E],
                         "wi": [count,H,2F], "wo": [count,F,H]}}]}
(`wi`: gate | up halves.)
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _swiglu(x, wi, wo):
    F = wo.shape[0]
    wi = wi.astype(F32)
    return (jax.nn.silu(x @ wi[:, :F]) * (x @ wi[:, F:])) @ wo.astype(F32)


def _rotate_first(x, r, theta):
    """x [B,S,n,d] at positions 0..S-1: the first r columns turn as split
    halves, the rest pass."""
    inv = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s, x[..., r:]], axis=-1)


def attention(h, mw, *, rotated, theta, window, value_scale, q_block=None):
    """h [B,S,H] -> [B,S,H]; `window` None: a full layer; a sink where the
    view has one."""
    q = jnp.einsum("bsh,hnd->bsnd", h, mw["wq"].astype(F32))
    k = jnp.einsum("bsh,hnd->bsnd", h, mw["wk"].astype(F32))
    v = value_scale * jnp.einsum("bsh,hnd->bsnd", h, mw["wv"].astype(F32))
    q, k = _rotate_first(q, rotated, theta), _rotate_first(k, rotated, theta)
    B, S, n_q, d = q.shape
    n_kv = k.shape[2]
    g = n_q // n_kv
    qg = q.reshape(B, S, n_kv, g, d)
    kpos = jnp.arange(S)[None, :]
    outs = []
    step = q_block or S
    for lo in range(0, S, step):
        qpos = jnp.arange(lo, min(lo + step, S))[:, None]
        seen = kpos <= qpos
        if window is not None:
            seen = jnp.logical_and(seen, qpos - kpos < window)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg[:, lo:lo + step], k)
        s = jnp.where(seen[None, None, None], s / jnp.sqrt(F32(d)), -jnp.inf)
        if "sink" in mw:
            # One more column a head, dropped after the softmax.
            b = jnp.broadcast_to(
                mw["sink"].astype(F32).reshape(1, n_kv, g, 1, 1),
                s.shape[:-1] + (1,))
            p = jax.nn.softmax(jnp.concatenate([s, b], axis=-1),
                               axis=-1)[..., :-1]
        else:
            p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("bhgqk,bkhd->bqhgd", p, v))
    o = jnp.concatenate(outs, axis=1).reshape(B, S, n_q, v.shape[-1])
    return jnp.einsum("bqnd,ndh->bqh", o, mw["wo"].astype(F32))


def expert_layer(x, fw, *, top_k, held_offset, scale):
    """x [B,T,H] -> [B,T,H]: this chip's part of the expert layer."""
    B, T, H = x.shape
    t = x.reshape(B * T, H)
    s = jax.nn.sigmoid(t @ fw["router"].astype(F32))           # [N, E]
    _, idx = jax.lax.top_k(s + fw["selection_bias"].astype(F32), top_k)
    vals = jnp.take_along_axis(s, idx, axis=-1)
    vals = vals / (vals.sum(-1, keepdims=True) + 1e-9)
    weight = jnp.zeros_like(s).at[
        jnp.arange(t.shape[0])[:, None], idx].set(vals * scale)
    count = fw["wi"].shape[0]
    held = weight[:, held_offset:held_offset + count]          # [N, count]

    def one_expert(carry, ew):                # one expert upcast at a time
        wi, wo, w_e = ew
        return carry + _swiglu(t, wi, wo) * w_e[:, None], None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(t),
                          (fw["wi"], fw["wo"], held.T))
    return out.reshape(B, T, H)


def forward(view: Dict[str, Any], ids: jax.Array, *, eps: float,
            kinds: Sequence[int], rotated: int, theta_full: float,
            theta_window: float, window: int, value_scale: float,
            dense_layers: int, top_k: int, held_offset: int,
            num_experts: int, routed_scale: float,
            q_block: Optional[int] = None,
            hidden: bool = False) -> jax.Array:
    """Logits [B,S,V] in float32 for token ids [B,S] (`hidden`: the rows
    the head would read, [B,S,H], after the final norm)."""
    assert len(view["layers"]) == len(kinds)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(view["embedding"], ids, axis=0).astype(F32)
        for i, (lw, kind) in enumerate(zip(view["layers"], kinds)):
            x = x + attention(
                _rmsnorm(x, lw["attn_norm"], eps), lw["mixer"],
                rotated=rotated,
                theta=theta_window if kind else theta_full,
                window=window if kind else None,
                value_scale=value_scale, q_block=q_block)
            y = _rmsnorm(x, lw["ffn_norm"], eps)
            if i < dense_layers:
                x = x + _swiglu(y, lw["ffn"]["wi"], lw["ffn"]["wo"])
            else:
                assert lw["ffn"]["router"].shape[-1] == num_experts
                x = x + expert_layer(
                    y, lw["ffn"], top_k=top_k, held_offset=held_offset,
                    scale=routed_scale)
        x = _rmsnorm(x, view["final_norm"], eps)
        if hidden:
            return x
        return jnp.einsum("bsh,vh->bsv", x, view["lm_head"].astype(F32))


def dense_layers_of(moe_layer_freq: Sequence[int]) -> int:
    """Leading layers without experts: `moe_layer_freq` must be that many
    zeros and ones after them."""
    n = 0
    while n < len(moe_layer_freq) and not moe_layer_freq[n]:
        n += 1
    assert all(moe_layer_freq[n:]), moe_layer_freq
    return n


def from_config_file(body: Dict[str, Any]) -> Dict[str, Any]:
    """forward()'s keyword arguments for a configuration file's body: the
    kinds of its first `num_hidden_layers` layers, the held range and the
    published expert count."""
    L = int(body["num_hidden_layers"])
    scale = body["routed_scaling_factor"]
    return {
        "eps": float(body["layernorm_epsilon"]),
        "kinds": tuple(int(t) for t in body["hybrid_layer_pattern"][:L]),
        "rotated": int(body["head_dim"] * body["partial_rotary_factor"]),
        "theta_full": float(body["rope_theta"]),
        "theta_window": float(body["swa_rope_theta"]),
        "window": int(body["sliding_window"]),
        "value_scale": float(body["attention_value_scale"]),
        "dense_layers": dense_layers_of(body["moe_layer_freq"][:L]),
        "top_k": int(body["num_experts_per_tok"]),
        "held_offset": int(
            body.get("deployment", {}).get("experts_held_offset", 0)),
        "num_experts": int(
            body.get("source_values", {}).get("n_routed_experts",
                                              body["n_routed_experts"])),
        "routed_scale": 1.0 if scale is None else float(scale),
    }
