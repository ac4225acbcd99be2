"""The plain reference of the `kimi_linear` architecture (Kimi-Linear:
delta-rule linear-attention layers, a latent-attention layer without
positions every fourth, a sigmoid-routed expert layer with a shared
expert), in `jax.numpy`, float32, `default_matmul_precision("highest")`:
no kernel, no chunk, no cache. It imports nothing of the program, nothing
of the harness and nothing of another architecture (`manifest.check`
reads this file's imports).

Pre-norm block: h = x + Mixer_l(rmsnorm(x)); y = h + FFN_l(rmsnorm(h));
final rmsnorm; untied head. No bias anywhere.

Delta-rule mixer (KDA), n heads of width d, on x_t in R^H:
    q~, k~, v~ = W_q x, W_k x, W_v x; each through its own causal depthwise
    convolution (K taps, the last on the token itself), then SiLU
    per head: q = q~/|q~| / sqrt(d), k = k~/|k~| (eps 1e-6 under the root)
    g_t = -exp(A_log_h) * softplus(W_a2 W_a1 x_t + dt_bias)  a channel
    beta_t = sigmoid(w_beta,h . x_t)                         a head
    S_0 = 0;  S'_t = Diag(exp(g_t)) S_{t-1}
    S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T;  o_t = S_t^T q_t
    y_t = rmsnorm_d(o_t; w) * sigmoid(W_g2 W_g1 x_t)  a head; out = W_o y
The state is walked TOKEN BY TOKEN (`lax.scan` over t).

Latent mixer (MLA, no positions: nothing is rotated), n heads:
    q = W_q x  a head [q_n (dn); q_r (dr)]
    [c (rank); k_r (dr)] = W_kva x; c = rmsnorm(c)
    [k_n (dn); v (dv)] a head = W_kvb c; k = [k_n; k_r], k_r for all heads
    causal softmax(q . k / sqrt(dn + dr)) v; out = W_o

Expert layer: s = sigmoid(W_r x) over ALL published experts; the k
experts are the top-k of s + b (b used for the choice alone); weights
w_i = scale * s_i / sum_chosen s_j; y = sum_i w_i E_i(x) + E_shared(x),
every E a SwiGLU. Of the published experts this chip holds
[held_offset, held_offset + count): the sum runs over the chosen experts
in that range alone, plus the shared expert, and that partial result goes
on; nothing stands in for the rest.

Weights come as a neutral view (adapter.params_view beside this file):
    {"embedding": [V,H], "lm_head": [V,H], "final_norm": [H],
     "layers": [{"attn_norm": [H], "ffn_norm": [H],
        "mixer": {"wq","wk","wv": [H,n*d], "conv_q","conv_k","conv_v": [K,n*d],
                  "w_a1","w_g1": [H,r], "w_a2","w_g2": [r,n*d],
                  "w_beta": [H,n], "A_log": [n], "dt_bias": [n*d],
                  "o_norm": [d], "wo": [n*d,H]}                    (kda)
               | {"wq": [H,n,dn+dr], "wkv_a": [H,rank+dr], "kv_norm": [rank],
                  "wkv_b": [rank,n,dn+dv], "wo": [n,dv,H]}         (latent)
        "ffn": {"wi": [H,2F], "wo": [F,H]}                         (dense)
             | {"router": [H,E], "selection_bias": [E],
                "wi": [count,H,2F], "wo": [count,F,H],
                "shared_wi": [H,2Fs], "shared_wo": [Fs,H]}         (experts)
     }]}
(gate | up halves in every wi).
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


def _conv(x, w):
    """Causal depthwise convolution. x [B,T,D], w [K,D], w[K-1] on x_t."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(K):
        out = out + xp[:, i:i + T] * w[i].astype(F32)
    return out


def delta_rule(q, k, v, g, beta):
    """q, k, g [B,T,n,d], v [B,T,n,dv], beta [B,T,n]: token by token."""
    B, T, n, d = q.shape

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[..., None]                     # [B,n,d,dv]
        pred = jnp.einsum("bnkv,bnk->bnv", S, k_t)
        S = S + k_t[..., None] * (b_t[..., None] * (v_t - pred))[..., None, :]
        return S, jnp.einsum("bnkv,bnk->bnv", S, q_t)

    t_first = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    _, o = jax.lax.scan(
        step, jnp.zeros((B, n, d, v.shape[-1]), F32),
        (t_first(q), t_first(k), t_first(v), t_first(g), t_first(beta)))
    return jnp.moveaxis(o, 0, 1)


def _kda(x, mw, eps):
    B, T, _ = x.shape
    n = mw["A_log"].shape[0]
    d = mw["o_norm"].shape[0]

    def branch(c):
        y = jax.nn.silu(_conv(x @ mw[f"w{c}"].astype(F32), mw[f"conv_{c}"]))
        return y.reshape(B, T, n, d)

    def unit(t):
        return t * jax.lax.rsqrt(
            jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)

    q, k, v = unit(branch("q")) * d ** -0.5, unit(branch("k")), branch("v")
    a = (x @ mw["w_a1"].astype(F32)) @ mw["w_a2"].astype(F32)
    g = -jnp.exp(mw["A_log"].astype(F32))[:, None] * jax.nn.softplus(
        a + mw["dt_bias"].astype(F32)).reshape(B, T, n, d)
    beta = jax.nn.sigmoid(x @ mw["w_beta"].astype(F32))
    o = _rmsnorm(delta_rule(q, k, v, g, beta), mw["o_norm"], eps)
    gate = jax.nn.sigmoid(
        (x @ mw["w_g1"].astype(F32)) @ mw["w_g2"].astype(F32))
    return (o.reshape(B, T, n * d) * gate) @ mw["wo"].astype(F32)


def _latent(x, mw, eps):
    B, T, _ = x.shape
    rank = mw["kv_norm"].shape[0]
    n, dv = mw["wo"].shape[0], mw["wo"].shape[1]
    dn = mw["wkv_b"].shape[-1] - dv
    q = jnp.einsum("bth,hnd->btnd", x, mw["wq"].astype(F32))
    kva = x @ mw["wkv_a"].astype(F32)
    c = _rmsnorm(kva[..., :rank], mw["kv_norm"], eps)
    kv = jnp.einsum("btr,rnd->btnd", c, mw["wkv_b"].astype(F32))
    k_r = jnp.broadcast_to(kva[:, :, None, rank:],
                           (B, T, n, kva.shape[-1] - rank))
    k = jnp.concatenate([kv[..., :dn], k_r], axis=-1)
    v = kv[..., dn:]
    s = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("bqnd,ndh->bqh", o, mw["wo"].astype(F32))


def expert_layer(x, fw, *, top_k, held_offset, scale, renormalize=True,
                 score="sigmoid", shared=True):
    """The held experts' part of the layer (+ the shared expert if
    `shared`), as dense sums over the held range."""
    B, T, H = x.shape
    t = x.reshape(B * T, H)
    logits = t @ fw["router"].astype(F32)                      # [N, E]
    s = jax.nn.sigmoid(logits) if score == "sigmoid" else jax.nn.softmax(
        logits, axis=-1)
    _, idx = jax.lax.top_k(s + fw["selection_bias"].astype(F32), top_k)
    vals = jnp.take_along_axis(s, idx, axis=-1)
    if renormalize:
        vals = vals / (vals.sum(-1, keepdims=True) + 1e-9)
    weight = jnp.zeros_like(s).at[
        jnp.arange(t.shape[0])[:, None], idx].set(vals * scale)
    count = fw["wi"].shape[0]
    held = weight[:, held_offset:held_offset + count]          # [N, count]

    def one_expert(carry, ew):
        wi, wo, w_e = ew
        return carry + _swiglu(t, wi, wo) * w_e[:, None], None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(t),
                          (fw["wi"], fw["wo"], held.T))
    if shared and "shared_wi" in fw:
        out = out + _swiglu(t, fw["shared_wi"], fw["shared_wo"])
    return out.reshape(B, T, H)


def forward(view: Dict[str, Any], ids: jax.Array, *, eps: float,
            layer_kinds: Sequence[str], dense_layers: int, top_k: int,
            held_offset: int, num_experts: int, routed_scale: float,
            renormalize: bool = True, score: str = "sigmoid") -> jax.Array:
    """Logits [B,S,V] in float32 for token ids [B,S]."""
    assert len(view["layers"]) == len(layer_kinds)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(view["embedding"], ids, axis=0).astype(F32)
        for i, (lw, kind) in enumerate(zip(view["layers"], layer_kinds)):
            mix = {"kda": _kda, "latent": _latent}[kind]
            x = x + mix(_rmsnorm(x, lw["attn_norm"], eps), lw["mixer"], eps)
            y = _rmsnorm(x, lw["ffn_norm"], eps)
            if i < dense_layers:
                x = x + _swiglu(y, lw["ffn"]["wi"], lw["ffn"]["wo"])
            else:
                assert lw["ffn"]["router"].shape[-1] == num_experts
                x = x + expert_layer(
                    y, lw["ffn"], top_k=top_k, held_offset=held_offset,
                    scale=routed_scale, renormalize=renormalize, score=score)
        x = _rmsnorm(x, view["final_norm"], eps)
        return jnp.einsum("bsh,vh->bsv", x, view["lm_head"].astype(F32))


def from_config_file(body: Dict[str, Any]) -> Dict[str, Any]:
    """forward()'s keyword arguments for a configuration file's body: the
    layer kinds of its first `num_hidden_layers` layers, the held range and
    the published expert count."""
    L = body["num_hidden_layers"]
    lin = body["linear_attn_config"]
    kinds = []
    for layer in range(1, L + 1):  # the source counts layers from 1
        if layer in lin["kda_layers"]:
            kinds.append("kda")
        elif layer in lin["full_attn_layers"]:
            kinds.append("latent")
        else:
            raise ValueError(f"layer {layer} is of no stated kind")
    return {
        "eps": float(body["rms_norm_eps"]),
        "layer_kinds": tuple(kinds),
        "dense_layers": int(body["first_k_dense_replace"]),
        "top_k": int(body["num_experts_per_token"]),
        "held_offset": int(
            body.get("deployment", {}).get("experts_held_offset", 0)),
        "num_experts": int(
            body.get("source_values", {}).get("num_experts",
                                              body["num_experts"])),
        "routed_scale": float(body["routed_scaling_factor"]),
        "renormalize": bool(body["moe_renormalize"]),
        "score": body["moe_router_activation_func"],
    }
