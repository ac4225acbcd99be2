"""The plain reference of the `nemotron_h` architecture (NVIDIA Nemotron-H /
Nemotron 3: Mamba-2 state-space layers with scalar-decay heads and grouped
B/C, causal attention layers WITHOUT positional encoding, LatentMoE expert
layers whose experts run in a narrow latent; every layer ONE sub-layer
behind ONE norm), in `jax.numpy`, float32,
`default_matmul_precision("highest")`: no kernel, no chunk, no cache. It
imports nothing of the program, nothing of the harness and nothing of
another architecture (`manifest.check` reads this file's imports).

Layer l, by the l-th character of the pattern:
    x <- x + f_l(rmsnorm(x; eps));  final rmsnorm; logits over the UNTIED
head; no embedding scale; no bias but the convolution's and dt's.

'M' (Mamba-2), row u in R^hidden; H heads of P (D = H P), G groups, state N:
    [z, xBC, dt] = W_in u             z [D], xBC [D + 2GN], dt [H]
    xBC = silu(conv_K(xBC) + b_conv)  causal, depthwise over ALL D + 2GN
                                      channels, the last tap on the token
    [x, B, C] = split(xBC)            x [H,P]; B, C [G,N]
    dt_h = softplus(dt_h + dt_bias_h);  a_h = -exp(A_log_h)   one a head
    S_0 = 0;  S_t[h] = exp(dt_h a_h) S_{t-1}[h] + dt_h x_t[h] outer B_t[g(h)]
    y_t[h] = S_t[h] . C_t[g(h)] + D_h x_t[h]          g(h) = h // (H / G)
    out = W_out (w * rmsnorm_by_group(y * silu(z)))
the gate BEFORE the norm, the norm's mean over a group's D / G channels.
The state is walked TOKEN BY TOKEN (`lax.scan` over t): the block form is
the program's, and the tests hold one to the other.

'*' (attention): n_q heads of d over n_kv key/value heads, NO positional
encoding (nothing is rotated), causal softmax(q . k / sqrt(d)) v, no bias.

'E' (LatentMoE), row n = rmsnorm(x):
    s   = sigmoid(W_r n)  float32, all E experts
    pick the k largest of s + b         (the bias in the choice alone)
    w_e = scale * s_e / (sum_picked s + 1e-20)
    l   = W_fc1 n                        (latent)
    r   = sum_{e picked, e held} w_e W_down,e relu(W_up,e l)^2
    out = W_fc2 r + W_sd relu(W_su n)^2  (the shared expert on n, not on l)
With experts [held_offset, held_offset + count) held, r is this chip's
part; W_fc2 has no bias, so the shares' W_fc2 r add up.

Weights come as a neutral view (adapter.params_view beside this file):
    {"embedding": [V,hidden], "lm_head": [V,hidden], "final_norm": [hidden],
     "layers": [{"norm": [hidden], and by the layer's kind
        "w_in": [hidden, 2D+2GN+H], "conv": [K, D+2GN], "conv_bias": [D+2GN],
        "dt_bias", "A_log", "D": [H], "gate_norm": [D], "w_out": [D,hidden]
      | "wq": [hidden,nq,d], "wk","wv": [hidden,nkv,d], "wo": [nq,d,hidden]
      | "router": [hidden,E], "selection_bias": [E], "fc1": [hidden,latent],
        "wi": [count,latent,F], "wo": [count,F,latent], "fc2": [latent,hidden],
        "shared_wi": [hidden,Fs], "shared_wo": [Fs,hidden]}]}
(the state is laid out state-major: S[n, h P + p]).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _relu2(x, wi, wo):
    return jnp.square(jax.nn.relu(x @ wi.astype(F32))) @ wo.astype(F32)


def _conv(x, w, b):
    """Causal depthwise convolution. x [B,T,C], w [K,C], w[K-1] on x_t."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(x) + b.astype(F32)
    for i in range(K):
        out = out + xp[:, i:i + T] * w[i].astype(F32)
    return out


def mamba2(u, lw, *, heads: int, groups: int, state: int, eps: float,
           state_dtype=F32, gate_after_norm: bool = False,
           group_shift: int = 0):
    """u [B,T,hidden] -> [B,T,hidden]. The three keyword switches after
    `eps` are CONTROLS that must read as another model: a state kept in
    another type, the gate applied after the norm, B/C of another group."""
    B, T, _ = u.shape
    D = lw["w_out"].shape[0]
    P, G, N = D // heads, groups, state
    zxd = u @ lw["w_in"].astype(F32)
    z, xbc, dt = zxd[..., :D], zxd[..., D:D + D + 2 * G * N], \
        zxd[..., D + D + 2 * G * N:]
    xbc = jax.nn.silu(_conv(xbc, lw["conv"], lw["conv_bias"]))
    x = xbc[..., :D].reshape(B, T, heads, P)
    Bm = xbc[..., D:D + G * N].reshape(B, T, G, N)
    Cm = xbc[..., D + G * N:].reshape(B, T, G, N)
    # head h reads group h // (heads / groups)
    of_head = (jnp.arange(heads) // (heads // G) + group_shift) % G
    Bh, Ch = Bm[:, :, of_head], Cm[:, :, of_head]             # [B,T,H,N]
    dt = jax.nn.softplus(dt + lw["dt_bias"].astype(F32))      # [B,T,H]
    a = -jnp.exp(lw["A_log"].astype(F32))                     # [H]

    def step(S, row):                                         # S [B,H,P,N]
        x_t, dt_t, b_t, c_t = row
        S = (jnp.exp(dt_t * a)[..., None, None] * S.astype(F32)
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        S = S.astype(state_dtype)
        return S, jnp.einsum("bhpn,bhn->bhp", S.astype(F32), c_t)

    S0 = jnp.zeros((B, heads, P, N), state_dtype)
    rows = tuple(jnp.swapaxes(t, 0, 1) for t in (x, dt, Bh, Ch))
    _, y = jax.lax.scan(step, S0, rows)
    y = jnp.swapaxes(y, 0, 1) + lw["D"].astype(F32)[:, None] * x
    y = y.reshape(B, T, D)
    gate = jax.nn.silu(z)

    def by_group(v):
        parts = v.reshape(B, T, G, D // G)
        parts = parts * jax.lax.rsqrt(
            jnp.mean(jnp.square(parts), axis=-1, keepdims=True) + eps)
        return parts.reshape(B, T, D) * lw["gate_norm"].astype(F32)

    y = by_group(y) * gate if gate_after_norm else by_group(y * gate)
    return y @ lw["w_out"].astype(F32)


def attention(u, lw, q_block: Optional[int] = None):
    """Causal attention without positions. `q_block`: the queries in
    blocks of that many rows (the same rows, a smaller score array)."""
    B, T, _ = u.shape
    wq, wk, wv, wo = (lw[k].astype(F32) for k in ("wq", "wk", "wv", "wo"))
    n_q, d = wq.shape[1], wq.shape[2]
    n_kv = wk.shape[1]
    q = jnp.einsum("bth,hnd->btnd", u, wq).reshape(B, T, n_kv, n_q // n_kv, d)
    k = jnp.einsum("bth,hnd->btnd", u, wk)
    v = jnp.einsum("bth,hnd->btnd", u, wv)
    kpos = jnp.arange(T)[None, :]
    step = q_block or T
    outs = []
    for lo in range(0, T, step):
        qpos = jnp.arange(lo, min(lo + step, T))[:, None]
        s = jnp.einsum("bqngd,bknd->bngqk", q[:, lo:lo + step], k)
        s = jnp.where((kpos <= qpos)[None, None, None],
                      s / jnp.sqrt(F32(d)), -jnp.inf)
        outs.append(jnp.einsum("bngqk,bknd->bqngd",
                               jax.nn.softmax(s, axis=-1), v))
    o = jnp.concatenate(outs, axis=1).reshape(B, T, n_q, d)
    return jnp.einsum("btnd,ndh->bth", o, wo)


def expert_layer(n, lw, *, top_k: int, held_offset: int, scale: float,
                 shared: bool = True, bias_in_weights: bool = False):
    """n [B,T,hidden] -> [B,T,hidden]: this chip's part of the expert
    layer, the shared expert included unless `shared` is False (a share
    that is not the one to count it). `bias_in_weights` is a CONTROL: the
    selection bias entering the combine weights reads as another model."""
    B, T, H = n.shape
    t = n.reshape(B * T, H)
    s = jax.nn.sigmoid(t @ lw["router"].astype(F32))           # [N, E]
    biased = s + lw["selection_bias"].astype(F32)
    _, idx = jax.lax.top_k(biased, top_k)
    vals = jnp.take_along_axis(biased if bias_in_weights else s, idx,
                               axis=-1)
    vals = scale * vals / (vals.sum(-1, keepdims=True) + 1e-20)
    weight = jnp.zeros_like(s).at[
        jnp.arange(t.shape[0])[:, None], idx].set(vals)
    count = lw["wi"].shape[0]
    held = weight[:, held_offset:held_offset + count]          # [N, count]
    latent = t @ lw["fc1"].astype(F32)

    def one_expert(carry, ew):                # one expert upcast at a time
        wi, wo, w_e = ew
        return carry + _relu2(latent, wi, wo) * w_e[:, None], None

    r, _ = jax.lax.scan(one_expert, jnp.zeros_like(latent),
                        (lw["wi"], lw["wo"], held.T))
    out = r @ lw["fc2"].astype(F32)
    if shared:
        out = out + _relu2(t, lw["shared_wi"], lw["shared_wo"])
    return out.reshape(B, T, H)


def forward(view: Dict[str, Any], ids: jax.Array, *, eps: float,
            pattern: str, heads: int, groups: int, state: int, top_k: int,
            held_offset: int, num_experts: int, routed_scale: float,
            q_block: Optional[int] = None, hidden: bool = False,
            controls: Optional[Dict[str, Any]] = None) -> jax.Array:
    """Logits [B,S,V] in float32 for token ids [B,S] (`hidden`: the rows
    the head would read, [B,S,hidden], after the final norm). `controls`:
    keyword switches of mamba2 / expert_layer that must read as another
    model (the tests' and the builder's controls; never set by a cell)."""
    assert len(view["layers"]) == len(pattern), (len(view["layers"]), pattern)
    controls = controls or {}
    m_ctl = {k: v for k, v in controls.items()
             if k in ("state_dtype", "gate_after_norm", "group_shift")}
    e_ctl = {k: v for k, v in controls.items() if k == "bias_in_weights"}
    with jax.default_matmul_precision("highest"):
        x = jnp.take(view["embedding"], ids, axis=0).astype(F32)
        for lw, kind in zip(view["layers"], pattern):
            n = _rmsnorm(x, lw["norm"], eps)
            if kind == "M":
                x = x + mamba2(n, lw, heads=heads, groups=groups,
                               state=state, eps=eps, **m_ctl)
            elif kind == "*":
                x = x + attention(n, lw, q_block=q_block)
            else:
                assert kind == "E", kind
                assert lw["router"].shape[-1] == num_experts
                x = x + expert_layer(n, lw, top_k=top_k,
                                     held_offset=held_offset,
                                     scale=routed_scale, **e_ctl)
        x = _rmsnorm(x, view["final_norm"], eps)
        if hidden:
            return x
        return jnp.einsum("bsh,vh->bsv", x, view["lm_head"].astype(F32))


def pattern_of(body: Dict[str, Any]) -> str:
    """The first `num_hidden_layers` characters of
    `hybrid_override_pattern` (the file keeps the published 88)."""
    return str(body["hybrid_override_pattern"])[:int(
        body["num_hidden_layers"])]


def from_config_file(body: Dict[str, Any]) -> Dict[str, Any]:
    """forward()'s keyword arguments for a configuration file's body."""
    return {
        "eps": float(body["norm_eps"]),
        "pattern": pattern_of(body),
        "heads": int(body["mamba_num_heads"]),
        "groups": int(body["n_groups"]),
        "state": int(body["ssm_state_size"]),
        "top_k": int(body["num_experts_per_tok"]),
        "held_offset": int(
            body.get("deployment", {}).get("experts_held_offset", 0)),
        "num_experts": int(
            body.get("source_values", {}).get("n_routed_experts",
                                              body["n_routed_experts"])),
        "routed_scale": float(body["routed_scaling_factor"]),
    }
