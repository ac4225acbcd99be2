"""The plain reference: the forward pass a configuration file states, in
`jax.numpy`, float32, `default_matmul_precision("highest")`; no kernel,
no cache, no batching tricks, and no import of the program.

What it computes, per layer, pre-norm:
    x += attn(rmsnorm(x));  x += ffn(rmsnorm(x))
    attn: q,k,v projections, RoPE (split-halves, as HF's rotate_half),
          causal softmax attention with grouped KV heads, out projection
    ffn:  dense  down(silu(gate(x)) * up(x))
          sparse softmax router over all experts, top-k, every expert
                 evaluated densely and combined with the top-k weights
then final rmsnorm and the (untied or tied) vocabulary projection.

`combine` is the rule the configuration file's `departures` name:
"renormalised" divides the k chosen weights by their sum (what
luminaai_tpu's models/moe.py does), "as_is" leaves them (norm_topk_prob
false, as OLMoE's source has it). No expert has a capacity: the
configurations here set capacity_factor = experts / top_k, at which the
program drops nothing.

Weights come as a neutral view (see program_adapter.params_view):
    {"embedding": [V,H], "lm_head": [V,H] | None, "final_norm": [H],
     "layers": [{"attn_norm": [H], "wq": [H,nq,d], "wk": [H,nkv,d],
                 "wv": [H,nkv,d], "wo": [nq,d,H], "ffn_norm": [H],
                 "wi": [H,2F] | [E,H,2F]  (gate | up halves),
                 "wo_ffn": [F,H] | [E,F,H], "router": [H,E] | absent}]}
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta):
    """x [B,S,h,d] at positions 0..S-1."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(x, lw, theta):
    q = jnp.einsum("bsh,hnd->bsnd", x, lw["wq"].astype(F32))
    k = jnp.einsum("bsh,hnd->bsnd", x, lw["wk"].astype(F32))
    v = jnp.einsum("bsh,hnd->bsnd", x, lw["wv"].astype(F32))
    q, k = _rope(q, theta), _rope(k, theta)
    groups = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    s = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    S = x.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bnqk,bknd->bqnd", p, v)
    return jnp.einsum("bqnd,ndh->bqh", o, lw["wo"].astype(F32))


def _dense_ffn(x, lw):
    gate, up = jnp.split(x @ lw["wi"].astype(F32), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ lw["wo_ffn"].astype(F32)


def _sparse_ffn(x, lw, top_k, combine):
    B, S, H = x.shape
    t = x.reshape(B * S, H)
    probs = jax.nn.softmax(t @ lw["router"].astype(F32), axis=-1)  # [T,E]
    vals, idx = jax.lax.top_k(probs, top_k)
    if combine == "renormalised":
        vals = vals / (vals.sum(-1, keepdims=True) + 1e-9)
    elif combine != "as_is":
        raise ValueError(f"unknown combine rule {combine!r}")
    weight = jnp.zeros_like(probs).at[
        jnp.arange(t.shape[0])[:, None], idx
    ].set(vals)  # [T,E], zero for experts not chosen

    def one_expert(carry, ew):
        wi, wo, w_e = ew
        gate, up = jnp.split(t @ wi.astype(F32), 2, axis=-1)
        y = (jax.nn.silu(gate) * up) @ wo.astype(F32)
        return carry + y * w_e[:, None], None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(t), (lw["wi"], lw["wo_ffn"], weight.T)
    )
    return out.reshape(B, S, H)


def forward(view: Dict[str, Any], ids: jax.Array, *, eps: float,
            theta: float, top_k: int = 0,
            combine: str = "renormalised") -> jax.Array:
    """Logits [B,S,V] in float32 for token ids [B,S]."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(view["embedding"], ids, axis=0).astype(F32)
        for lw in view["layers"]:
            x = x + _attention(_rmsnorm(x, lw["attn_norm"], eps), lw, theta)
            y = _rmsnorm(x, lw["ffn_norm"], eps)
            if "router" in lw:
                x = x + _sparse_ffn(y, lw, top_k, combine)
            else:
                x = x + _dense_ffn(y, lw)
        x = _rmsnorm(x, view["final_norm"], eps)
        head = view["lm_head"] if view.get("lm_head") is not None else (
            view["embedding"]
        )
        return jnp.einsum("bsh,vh->bsv", x, head.astype(F32))


def next_token_loss(logits: jax.Array, ids: jax.Array) -> jax.Array:
    """Mean cross-entropy of position t's logits against token t+1."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(F32), axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return -picked.mean()


def from_config_file(body: Dict[str, Any]) -> Dict[str, Any]:
    """forward()'s keyword arguments for a configuration file's body."""
    rule = body.get("reference", {}).get("moe_combine") or (
        "renormalised" if body.get("norm_topk_prob") else "as_is"
    )
    return {
        "eps": float(body["rms_norm_eps"]),
        "theta": float(body["rope_theta"]),
        "top_k": int(body.get("num_experts_per_tok", 0)),
        "combine": rule,
    }
