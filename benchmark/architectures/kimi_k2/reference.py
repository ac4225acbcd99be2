"""The plain reference of the `kimi_k2` architecture (Kimi K2: multi-head
latent attention with low-rank queries and a YaRN rotation on every layer,
a leading dense layer, then a sigmoid-routed expert layer with a shared
expert), in `jax.numpy`, float32, `default_matmul_precision("highest")`:
no kernel, no cache, no absorbed projections. It imports nothing of the
program, nothing of the harness and nothing of another architecture
(`manifest.check` reads this file's imports).

Pre-norm block: h = x + Attn_l(rmsnorm(x)); y = h + FFN_l(rmsnorm(h));
final rmsnorm; untied head. No bias anywhere. RMSNorm eps from the file.

Attention at position t, heads h = 1..n, widths dn (nope), dr (rope), dv,
latent rank r, query rank rq:
    c_q = rmsnorm(W_qa x) in R^rq;  [q_n,h ; q_r,h] = W_qb,h c_q
    [c' ; k_r] = W_kva x in R^(r + dr);  c = rmsnorm(c')
    [k_n,h ; v_h] = W_kvb,h c
    score_h(t, s) = (q_n,h . k_n,h(s) + rot_t(q_r,h) . rot_s(k_r(s))) sigma,
        s <= t;  k_r is ONE vector a token, shared by the heads
    sigma = (dn + dr)^-1/2 m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
    o_h = sum_s softmax_s(score_h) v_h(s);  out = W_o [o_1 .. o_n]
rot_p turns the dr columns as pairs by the angles p f'_i, i < dr / 2:
    f_i = theta^(-2i / dr)
    dim(rho) = dr ln(original / (2 pi rho)) / (2 ln theta)
    low = floor(dim(beta_fast)), high = ceil(dim(beta_slow)), clipped to
        [0, dr - 1];  ramp_i = clip((i - low) / (high - low), 0, 1)
    f'_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i
and cos, sin are multiplied by mscale(mscale) / mscale(mscale_all_dim),
mscale(a) = 0.1 a ln(factor) + 1. Pair i is (x[i], x[i + dr / 2]) under
`pairs="split"` and (x[2i], x[2i + 1]) under "interleaved": the config
does not say which, and they are one function under a permutation of
W_qb's and W_kva's rope columns (the file's `assumed`).

Expert layer: s = sigmoid(W_r x) over ALL published experts; the k
experts are the top-k of s + b (b used for the choice alone; one group,
no group limit); weights w_i = scale * s_i / sum_chosen s_j;
y = sum_i w_i E_i(x) + E_shared(x), every E a SwiGLU. Of the published
experts this chip holds [held_offset, held_offset + count): the sum runs
over the chosen experts in that range alone, plus the shared expert, and
that partial result goes on; nothing stands in for the rest.

`q_block` computes the attention of that many queries at a time, so that a
prompt of thousands of tokens fits: the same sums, a block of rows at a
time.

Weights come as a neutral view (adapter.params_view beside this file):
    {"embedding": [V,H], "lm_head": [V,H], "final_norm": [H],
     "layers": [{"attn_norm": [H], "ffn_norm": [H],
        "mixer": {"wq_a": [H,rq], "q_norm": [rq], "wq_b": [rq,n,dn+dr],
                  "wkv_a": [H,r+dr], "kv_norm": [r],
                  "wkv_b": [r,n,dn+dv], "wo": [n,dv,H]},
        "ffn": {"wi": [H,2F], "wo": [F,H]}                         (dense)
             | {"router": [H,E], "selection_bias": [E],
                "wi": [count,H,2F], "wo": [count,F,H],
                "shared_wi": [H,2Fs], "shared_wo": [Fs,H]}         (experts)
     }]}
(gate | up halves in every wi).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

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


def _mscale(factor, a):
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(dr: int, theta: float, yarn: Dict[str, float]):
    """f'_i, i < dr / 2, and (low, high) of the ramp."""
    f = theta ** (-jnp.arange(0, dr, 2, dtype=F32) / dr)

    def dim(turns):
        return dr * math.log(
            yarn["original_max_position_embeddings"]
            / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(dim(yarn["beta_slow"])), dr - 1)
    ramp = jnp.clip(
        (jnp.arange(dr // 2, dtype=F32) - low) / max(high - low, 1e-3), 0, 1)
    return f * (1 - ramp) + f / yarn["factor"] * ramp, (low, high)


def softmax_scale(dn: int, dr: int, yarn: Dict[str, float]) -> float:
    return (dn + dr) ** -0.5 * _mscale(
        yarn["factor"], yarn["mscale_all_dim"]) ** 2


def _rotate(x, freqs, mult, pairs):
    """x [B,S,...,dr] at positions 0..S-1 (axis 1)."""
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs[None, :]
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (freqs.shape[0],)
    c, s = (jnp.cos(ang) * mult).reshape(shape), (
        jnp.sin(ang) * mult).reshape(shape)
    if pairs == "interleaved":
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                         axis=-1).reshape(x.shape)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(x, mw, *, eps, dn, theta, yarn, pairs, q_block=None):
    """x [B,S,H] -> [B,S,H]: the expanded form, every head's own k and v."""
    r = mw["kv_norm"].shape[0]
    dr = mw["wkv_a"].shape[1] - r
    c_q = _rmsnorm(x @ mw["wq_a"].astype(F32), mw["q_norm"], eps)
    q = jnp.einsum("bsr,rnd->bsnd", c_q, mw["wq_b"].astype(F32))
    kva = x @ mw["wkv_a"].astype(F32)
    c = _rmsnorm(kva[..., :r], mw["kv_norm"], eps)
    kv = jnp.einsum("bsr,rnd->bsnd", c, mw["wkv_b"].astype(F32))
    freqs, _ = yarn_frequencies(dr, theta, yarn)
    mult = _mscale(yarn["factor"], yarn["mscale"]) / _mscale(
        yarn["factor"], yarn["mscale_all_dim"])
    q_r = _rotate(q[..., dn:], freqs, mult, pairs)            # [B,S,n,dr]
    k_r = _rotate(kva[..., r:], freqs, mult, pairs)           # [B,S,dr]
    q_n, k_n, v = q[..., :dn], kv[..., :dn], kv[..., dn:]
    sigma = softmax_scale(dn, dr, yarn)
    S = x.shape[1]
    kpos = jnp.arange(S)[None, :]
    outs = []
    step = q_block or S
    for lo in range(0, S, step):
        qpos = jnp.arange(lo, min(lo + step, S))[:, None]
        s = (jnp.einsum("bqnd,bknd->bnqk", q_n[:, lo:lo + step], k_n)
             + jnp.einsum("bqnd,bkd->bnqk", q_r[:, lo:lo + step], k_r))
        s = jnp.where((kpos <= qpos)[None, None], s * sigma, -jnp.inf)
        outs.append(jnp.einsum(
            "bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), v))
    o = jnp.concatenate(outs, axis=1)
    return jnp.einsum("bsnd,ndh->bsh", o, mw["wo"].astype(F32))


def expert_layer(x, fw, *, top_k, held_offset, scale, shared=True):
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
    if shared:
        out = out + _swiglu(t, fw["shared_wi"], fw["shared_wo"])
    return out.reshape(B, T, H)


def forward(view: Dict[str, Any], ids: jax.Array, *, eps: float, dn: int,
            theta: float, yarn: Dict[str, float], pairs: str,
            dense_layers: int, top_k: int, held_offset: int,
            num_experts: int, routed_scale: float,
            q_block: Optional[int] = None,
            hidden: bool = False) -> jax.Array:
    """Logits [B,S,V] in float32 for token ids [B,S] (`hidden`: the rows
    the head would read, [B,S,H], after the final norm)."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(view["embedding"], ids, axis=0).astype(F32)
        for i, lw in enumerate(view["layers"]):
            x = x + attention(
                _rmsnorm(x, lw["attn_norm"], eps), lw["mixer"], eps=eps,
                dn=dn, theta=theta, yarn=yarn, pairs=pairs, q_block=q_block)
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


def from_config_file(body: Dict[str, Any]) -> Dict[str, Any]:
    """forward()'s keyword arguments for a configuration file's body."""
    yarn = {k: float(body["rope_scaling"][k]) for k in (
        "factor", "original_max_position_embeddings", "beta_fast",
        "beta_slow", "mscale", "mscale_all_dim")}
    return {
        "eps": float(body["rms_norm_eps"]),
        "dn": int(body["qk_nope_head_dim"]),
        "theta": float(body["rope_theta"]),
        "yarn": yarn,
        "pairs": body["reference"]["rope_pairs"],
        "dense_layers": int(body["first_k_dense_replace"]),
        "top_k": int(body["num_experts_per_tok"]),
        "held_offset": int(
            body.get("deployment", {}).get("experts_held_offset", 0)),
        "num_experts": int(
            body.get("source_values", {}).get("n_routed_experts",
                                              body["n_routed_experts"])),
        "routed_scale": float(body["routed_scaling_factor"]),
    }
